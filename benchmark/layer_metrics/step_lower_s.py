"""``step_lower_s`` (entry points): seconds jax spent lowering
``train_step``'s jaxpr to an MLIR module (every ``pallas_call`` to Mosaic
among it) inside the process's first ``train.first_step``: the program's
counter ``jit.lower_s{fun=train_step}`` as it moved over that span, kept as
``train.first_step_s{part=lower}``. Left out where the program keeps no such
count."""


def read(ctx):
    from deeplearning_cfn_tpu.obs.trace import get_tracer

    registry = get_tracer().registry
    held = registry.gauge("train.first_step_s").value(part="lower")
    if held is None:
        return None
    ctx["say"](
        f"jax's lowering of train_step: {held:.3f} s inside the first "
        f"step; in the whole process so far "
        f"{registry.counter('jit.lower_s').value(fun='train_step'):.3f} s in "
        f"{registry.counter('jit.lower_count').value(fun='train_step'):.0f}")
    return held
