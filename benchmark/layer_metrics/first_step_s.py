"""``first_step_s`` (train loop): seconds of the program's span
``train.first_step``, the first of the process: from before ``fit``'s first
batch to the end of its first-step sync, which is where the step is traced,
lowered, and compiled or loaded. The runner's "fit's first-step seconds" is
the same two clock reads. The window's own ``fit`` closes a second, short one
(it syncs on its first step too): printed, not taken. Read from the tracer's
registry (``span_dur_s{name=train.first_step}``); left out where the program
has no such span."""


def read(ctx):
    from deeplearning_cfn_tpu.obs.trace import get_tracer

    firsts = get_tracer().registry.histogram("span_dur_s").samples(
        name="train.first_step")
    if not firsts:
        return None
    ctx["say"](f"train.first_step: {len(firsts)} in this process, "
               f"{', '.join(f'{s:.3f}' for s in firsts)} s; the first is "
               f"set-up's")
    return firsts[0]
