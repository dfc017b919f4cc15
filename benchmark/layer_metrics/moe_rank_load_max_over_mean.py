"""``moe_rank_load_max_over_mean`` (model code): the fullest expert-parallel
rank's rows over the mean rank's in the worst expert layer of a step (the
step waits for that rank), the mean over every step the trainer realized in
this process (set-up's three: the window realizes none): the program's
histogram ``moe.rank_load_max_over_mean.steps``. Left out where the program
has none (no exchange between ranks)."""


def read(ctx):
    from deeplearning_cfn_tpu.obs.trace import get_tracer

    return get_tracer().registry.histogram(
        "moe.rank_load_max_over_mean.steps").mean()
