"""What the readers of the learned-sparse-attention cell share: the share of
the causal pairs the program's selections kept, and a kernel's share of its
roofline under a selection (``opcount_keye_vl2.py`` counts the kept pairs'
products, so a kernel that computes the whole triangle reads at most
``kept_share`` times its matrix-unit share, and one that skips cannot pass
100 %)."""

from __future__ import annotations

from . import opcount, opcount_keye_vl2, scopes


def kept_share(ctx):
    """The mean of the program's histogram
    ``attention.selected.kept_share.steps`` over the steps the trainer
    realized in this process (set-up's three); ``None`` where the
    configuration has no ``sa_config`` or the program no such histogram."""
    if "sa_config" not in ctx["cell"].config:
        return None
    from deeplearning_cfn_tpu.obs.trace import get_tracer

    return get_tracer().registry.histogram(
        "attention.selected.kept_share.steps").mean() or None


def roofline(ctx, name: str, pattern: str, count):
    """``name`` in per cent: ``count(config, run, share)`` gives one layer's
    (operations, bytes); the time is what the trace has under ``pattern``."""
    config, peaks, share = ctx["cell"].config, ctx["peaks"], kept_share(ctx)
    if peaks is None or share is None:
        return None
    seconds = scopes.seconds_matching(ctx, pattern)
    if seconds is None:
        return None
    run, layers = ctx["run"], len(config["layers_held"])
    flops, nbytes = count(config, run, share)
    least, bound = opcount.roofline_seconds(flops, nbytes, peaks)
    steps = run["steps"]
    ctx["say"](f"{name}: kept share {share:.4f}, {layers} layers, "
               f"{flops / 1e9:.1f} GFLOP and {nbytes / 1e9:.3f} GB a layer, "
               f"{bound}-bound, least {1e3 * layers * least:.3f} ms a step, "
               f"{1e3 * seconds / steps:.3f} ms a step in the kernels")
    return 100.0 * layers * least * steps / seconds


def flash_roofline(ctx, backward: bool):
    kernel = r"core_attention/flash_bwd_(dkdv|dq)\b" if backward \
        else r"core_attention/flash_fwd\b"
    return roofline(
        ctx, f"flash_sel_{'bwd' if backward else 'fwd'}_roofline", kernel,
        lambda config, run, share: opcount_keye_vl2.flash_selected(
            run["global_batch"], config["num_attention_heads"],
            config["num_key_value_heads"], run["seq_len"],
            config["head_dim"], share, backward))
