"""``moe_gmm_roofline`` (kernels): the least time the chip's peaks allow for
the expert layers' grouped matmuls over the rows really routed, forward and
backward (``harness/opcount_moe.py``), over the device time under the scope
``moe_experts``, which holds the grouped matmuls and the gate's
``silu(.) * .`` between them. The rows are the mean of the program's
histogram ``moe.rows_held.steps`` over every step the trainer realized in
this process: set-up's three, since the window realizes none."""
from harness import opcount, opcount_moe, scopes


def read(ctx):
    config, peaks = ctx["cell"].config, ctx["peaks"]
    if peaks is None or "mlp_layer_types" not in config:
        return None
    from deeplearning_cfn_tpu.obs.trace import get_tracer

    rows = get_tracer().registry.histogram("moe.rows_held.steps").mean()
    seconds = scopes.seconds_matching(ctx, r"\bmoe_experts\b")
    if not rows or seconds is None:
        return None
    layers = sum(config["mlp_layer_types"][i] == "sparse"
                 for i in config["layers_held"])
    flops, nbytes = opcount_moe.experts_step(
        rows, layers, config["num_experts"], config["hidden_size"],
        config["moe_intermediate_size"])
    least, bound = opcount.roofline_seconds(flops, nbytes, peaks)
    steps = ctx["run"]["steps"]
    ctx["say"](f"moe_gmm_roofline: {rows:.0f} rows a step (mean of the "
               f"realized steps) over {layers} expert layers, {bound}-bound,"
               f" least {1e3 * least:.3f} ms a step, "
               f"{1e3 * seconds / steps:.3f} ms a step under moe_experts")
    return 100.0 * least * steps / seconds
