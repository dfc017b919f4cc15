"""``step_trace_s`` (entry points): seconds jax spent tracing ``train_step``
to a jaxpr (Python: the model's code, the transforms, every ``pallas_call``'s
kernel body) inside the process's first ``train.first_step``: the program's
counter ``jit.trace_s{fun=train_step}`` as it moved over that span, which the
program keeps as ``train.first_step_s{part=trace}`` because the traced run
compiles the step again after its window. Left out where the program keeps
no such count."""


def read(ctx):
    from deeplearning_cfn_tpu.obs.trace import get_tracer

    registry = get_tracer().registry
    held = registry.gauge("train.first_step_s").value(part="trace")
    if held is None:
        return None
    ctx["say"](
        f"jax's trace of train_step: {held:.3f} s inside the first step; "
        f"in the whole process so far "
        f"{registry.counter('jit.trace_s').value(fun='train_step'):.3f} s in "
        f"{registry.counter('jit.trace_count').value(fun='train_step'):.0f}")
    return held
