"""``first_step_other_s`` (train loop): what is left of the process's first
``train.first_step`` once jax's seconds for ``train_step``'s trace, lowering
and backend compile (the cache's retrieval on a hit) and the step's own
``train.next_batch`` are taken off: the time neither jax nor the program
names. All from the program's gauge ``train.first_step_s{part}``. The line
printed with it splits it once more, at the end of the step's
``train.dispatch``: before it lies what the jit call did besides the three,
after it the wait for the step's results; and says what the persistent cache
read and saved inside the backend compile. Left out where the program keeps
no such gauge, or where the parts come to more than the whole."""

PARTS = ("whole", "trace", "lower", "backend_compile", "next_batch",
         "dispatch", "cache_retrieval", "cache_saved")


def read(ctx):
    from deeplearning_cfn_tpu.obs.trace import get_tracer

    first = get_tracer().registry.gauge("train.first_step_s")
    s = {part: first.value(part=part) for part in PARTS}
    if None in s.values():
        return None
    jit = s["trace"] + s["lower"] + s["backend_compile"]
    other = s["whole"] - jit - s["next_batch"]
    in_call = s["dispatch"] - jit
    cache = (f"the cache read for {s['cache_retrieval']:.3f} s what had "
             f"taken {s['cache_retrieval'] + s['cache_saved']:.3f} s to "
             f"compile" if s["cache_retrieval"] else "the cache held nothing")
    ctx["say"](
        f"first step {s['whole']:.3f} s = first batch {s['next_batch']:.3f} "
        f"+ trace {s['trace']:.3f} + lowering {s['lower']:.3f} + backend "
        f"compile or cache load {s['backend_compile']:.3f} ({cache}) + other "
        f"{other:.3f}; the step's train.dispatch took {s['dispatch']:.3f} s, "
        f"so {in_call:.3f} s of the other lie inside the jit call and "
        f"{other - in_call:.3f} s in the wait for the step's results")
    if other < 0:
        ctx["say"]("first_step_other_s: the parts come to more than the "
                   "whole, left out")
        return None
    return other
