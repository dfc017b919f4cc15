"""``ssm_scan_roofline`` (kernels): the least time the chip's peaks allow
for the scans' own work in a step, forward and backward
(``harness/opcount_ssd.py``: the chunked algorithm's operations at the
published chunk, five arrays and their cotangents moved once; the same
whatever implements the scan, recomputation not counted), over the device
time under the scope ``ssm_scan``, which holds the recomputed forward too.
So it cannot pass 100 %, and a kernel that takes the einsums' place is
judged on the yardstick they were."""
from harness import opcount, opcount_ssd, scopes


def read(ctx):
    config, peaks = ctx["cell"].config, ctx["peaks"]
    if peaks is None or "mamba_n_heads" not in config:
        return None
    seconds = scopes.seconds_matching(ctx, r"\bssm_scan\b")
    if seconds is None:
        return None
    run = ctx["run"]
    flops, nbytes = opcount_ssd.scan_step(
        config, run["global_batch"] * run["seq_len"])
    least, bound = opcount.roofline_seconds(flops, nbytes, peaks)
    ctx["say"](f"ssm_scan_roofline: {opcount_ssd.mamba_layers(config)} "
               f"layers, {flops / 1e9:.1f} GFLOP and {nbytes / 1e9:.3f} GB a"
               f" step, {bound}-bound, least {1e3 * least:.3f} ms a step, "
               f"{1e3 * seconds / run['steps']:.3f} ms a step under "
               f"ssm_scan")
    return 100.0 * least * run["steps"] / seconds
