"""``moe_load_max_over_mean`` (model code): the fullest held expert's rows
over the mean held expert's in the worst expert layer of a step, the mean
over every step the trainer realized in this process (set-up's three: they
log every step, the window realizes none): the program's histogram
``moe.load_max_over_mean.steps``. Left out where the program has none."""


def read(ctx):
    from deeplearning_cfn_tpu.obs.trace import get_tracer

    return get_tracer().registry.histogram(
        "moe.load_max_over_mean.steps").mean()
