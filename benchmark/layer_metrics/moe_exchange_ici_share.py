"""``moe_exchange_ici_share`` (collectives): what the expert layers' exchange
must send against what the chip's links could carry in the time it took: the
bytes a rank sends a step by the algorithm chosen (the program's gauge
``moe.exchange.bytes``, a rank a layer a step, forward and backward: its
tokens to the other ranks and the other ranks' float32 parts, and the
transposes; times the expert layers held) over the device time a step under
``moe_exchange_in`` / ``moe_exchange_out`` (``moe_exchange_ms``) over the
chip's published inter-chip bandwidth. That figure: 1,600 Gbit/s = 200 GB/s a
chip (Google Cloud documentation, "TPU v5e" system architecture page;
``harness/device.py:PEAKS`` keeps it as ``ici_bits_per_s``). An asynchronous
collective's transfer runs between its ``-start`` and ``-done`` operations,
whose own time this counts: a share over 100 % says the transfer was hidden
behind other work, not that the links were beaten. Left out where the program
has no exchange."""
from harness.scopes import seconds_matching


def read(ctx):
    from deeplearning_cfn_tpu.obs.trace import get_tracer

    config, peaks = ctx["cell"].config, ctx["peaks"]
    sent = get_tracer().registry.gauge("moe.exchange.bytes").value()
    if peaks is None or not sent or "mlp_layer_types" not in config:
        return None
    seconds = seconds_matching(ctx, r"\bmoe_exchange_(in|out)\b")
    if not seconds:
        return None
    layers = sum(config["mlp_layer_types"][i] == "sparse"
                 for i in config["layers_held"])
    per_step = seconds / ctx["run"]["steps"]
    ctx["say"](f"moe_exchange_ici_share: {sent * layers / 1e6:.1f} MB a rank "
               f"a step over {layers} expert layers, {1e3 * per_step:.3f} ms "
               f"a step under the two scopes")
    return 100.0 * sent * layers / per_step / (peaks["ici_bits_per_s"] / 8.0)
