"""``state_init_s`` (train loop): seconds of the program's span
``train.init_state``, the first of the process: ``create_train_state`` from
the shapes and shardings through the jitted init to the end of its sync. Read
from the tracer's registry (``span_dur_s{name=train.init_state}``); left out
where the program has no such span."""


def read(ctx):
    from deeplearning_cfn_tpu.obs.trace import get_tracer

    registry = get_tracer().registry
    builds = registry.histogram("span_dur_s").samples(name="train.init_state")
    if not builds:
        return None
    jit = {phase: registry.counter(f"jit.{phase}_s").value(fun="make_state")
           for phase in ("trace", "lower", "backend_compile")}
    ctx["say"](f"train.init_state: {builds[0]:.3f} s; of it jax's trace / "
               f"lowering / backend compile of make_state "
               f"{jit['trace']:.3f} / {jit['lower']:.3f} / "
               f"{jit['backend_compile']:.3f} s")
    return builds[0]
