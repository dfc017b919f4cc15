"""The counts and readers ``laguna_xs2_train_4k`` brought: the band's pairs
against a count by hand, the causal half as the widest band, the four
rooflines by layer kind on hand-written operations, the configuration's
operations a token against the parts written down by hand, and the expert
layers' counts reaching the registry from every realized step."""

import contextlib
import json
import os
import sys
import types

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, os.path.join(REPO, "benchmark"))

from harness import (manifest, opcount, opcount_moe,  # noqa: E402
                     opcount_sparse_lm, opcount_window, train_steps,
                     window_kernels)

from test_benchmark_laguna import TINY_LAGUNA, TINY_TRAFFIC  # noqa: E402

PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}


def _config():
    with open(os.path.join(REPO, "benchmark/configs/laguna_xs2.json")) as fh:
        return json.load(fh)


@pytest.mark.parametrize("sq,sk,window", [
    (32, 32, 8), (32, 32, 32), (32, 32, 100), (8, 32, 8), (8, 32, 28),
    (8, 32, 64), (1, 32, 4), (4096, 4096, 512)])
def test_band_pairs_is_the_count_by_hand(sq, sk, window):
    by_hand = sum(1 for i in range(sq) for j in range(sk)
                  if 0 <= (i + sk - sq) - j < window) if sq <= 32 \
        else 511 * 512 // 2 + (4096 - 511) * 512
    assert opcount_window.band_pairs(sq, sk, window) == by_hand


@pytest.mark.parametrize("backward", [False, True])
def test_the_widest_band_is_the_causal_half(backward):
    """``flash_band`` with a window as long as the sequence and as many K/V
    heads as query heads is ``opcount``'s causal call, but for the diagonal
    that the latter's half leaves out: s (s + 1) / 2 pairs against s s / 2."""
    b, h, s, d = 2, 48, 4096, 128
    flops, nbytes = opcount_window.flash_band(b, h, h, s, s, d, s, backward)
    whole = (opcount.flash_backward if backward else opcount.flash_forward)(
        b, h, s, s, d, True)
    assert flops == pytest.approx(whole[0] * (s + 1) / s, rel=1e-12)
    assert nbytes == whole[1]
    grouped = opcount_window.flash_band(b, h, 8, s, s, d, s, backward)
    assert grouped[0] == flops and grouped[1] < nbytes


def _ctx(ops, said):
    return {"cell": types.SimpleNamespace(config=_config()), "peaks": PEAKS,
            "trace": object(), "scoped_ops": ops, "say": said.append,
            "run": {"global_batch": 2, "seq_len": 4096, "steps": 2}}


M = "jit(train_step)/jvp(TransformerCausalLm)"
T = "jit(train_step)/transpose(jvp(TransformerCausalLm))"
CORE = "self_attn/self_attn.core_attention"
OPS = [
    (f"{M}/layer_0/{CORE}/flash_fwd/pallas_call", 0.010),    # full
    (f"{M}/layer_4/{CORE}/flash_fwd/pallas_call", 0.012),    # full
    (f"{M}/layer_1/{CORE}/flash_fwd/pallas_call", 0.008),    # sliding
    (f"{T}/layer_4/{CORE}/flash_bwd_dkdv/pallas_call", 0.014),
    (f"{T}/layer_4/{CORE}/flash_bwd_dq/pallas_call", 0.010),
    (f"{T}/layer_2/{CORE}/flash_bwd_dq/pallas_call", 0.006),
    (f"{M}/layer_4/self_attn/query/dot_general", 0.5),
    (f"{M}/layer_1/mlp/moe_experts/gmm/pallas_call", 0.5),
]


@pytest.mark.parametrize("kind,backward,seconds,heads,window", [
    ("full", False, 0.022, 48, 4096), ("full", True, 0.024, 48, 4096),
    ("window", False, 0.008, 64, 512), ("window", True, 0.006, 64, 512)])
def test_a_roofline_reads_its_own_layers_kernels(kind, backward, seconds,
                                                 heads, window):
    said = []
    got = window_kernels.layer_roofline(_ctx(OPS, said), kind, backward)
    # Layers held: 0 and 4 full under 48 heads, 1 to 3 sliding under 64.
    layers = 2 if kind == "full" else 3
    flops, nbytes = opcount_window.flash_band(2, heads, 8, 4096, 4096, 128,
                                              window, backward)
    least = layers * max(flops / 197e12, nbytes / 819e9)
    assert got == pytest.approx(100.0 * least * 2 / seconds, rel=1e-12)
    assert f"{layers} " in said[-1] and f"flash_{kind}_" in said[-1]


def test_a_roofline_with_nothing_to_read_is_left_out():
    said = []
    assert window_kernels.layer_roofline(_ctx(OPS[-2:], said), "full",
                                         False) is None
    assert "no full_attention layer's kernel" in said[-1]
    gpt = _ctx(OPS, said)
    gpt["cell"] = types.SimpleNamespace(config={"n_embd": 768})
    assert window_kernels.layer_roofline(gpt, "window", True) is None
    for name in ("flash_full_fwd_roofline", "flash_full_bwd_roofline",
                 "mfu_sparse"):
        assert manifest.load_module(
            f"benchmark/layer_metrics/{name}.py", name).read(
                dict(gpt, device={"count": 1},
                     end_to_end={"train_tokens_per_s": 1.0})) is None


def test_operations_a_token_are_the_parts_by_hand():
    """Laguna-XS.2's layers 0-4 at S = 4096, each part written out: 0.70
    GFLOP forward a token, 17.2 TFLOP a step of 8192 tokens."""
    d, hd, kv = 2048, 128, 8
    proj = sum(2 * d * (2 * h * hd + 2 * kv * hd + h)
               for h in (48, 64, 64, 64, 48))
    full = 2 * 4 * 48 * hd * (4096 + 1) / 2
    band = 3 * 4 * 64 * hd * (511 * 256 + (4096 - 511) * 512) / 4096
    dense = 6 * d * 8192
    experts = 4 * (2 * d * 256 + 6 * d * (512 + 8 * 32 / 256 * 512))
    head = 2 * d * 12544
    parts = opcount_sparse_lm.forward_parts(_config(), 4096)
    assert parts == pytest.approx({
        "projections": proj, "cores": full + band, "dense_mlp": dense,
        "experts": experts, "head": head}, rel=1e-12)
    per_token = opcount_sparse_lm.train_flops_per_token(_config(), 4096)
    assert per_token == pytest.approx(3 * 0.6996e9, rel=1e-4)
    said = []
    ctx = dict(_ctx([], said), device={"count": 1},
               end_to_end={"train_tokens_per_s": 29100.0})
    got = manifest.load_module("benchmark/layer_metrics/mfu_sparse.py",
                               "mfu_sparse").read(ctx)
    assert got == pytest.approx(100 * per_token * 29100.0 / 197e12)
    assert 30.9 < got < 31.1


def test_grouped_matmul_counts_the_rows_really_routed():
    flops, nbytes = opcount_moe.experts_step(4 * 8192, 4, 32, 2048, 512)
    # A row: [2048] x [2048, 1024] and [512] x [512, 2048], three times over.
    assert flops == 4 * 8192 * 3 * 2 * (2048 * 1024 + 512 * 2048)
    assert nbytes > 4 * 3 * 2 * 32 * 3 * 2048 * 512   # the matrices alone


def test_every_realized_step_reaches_the_registry():
    """``Trainer.fit`` sets the gauges ``moe.<name>`` to the step it just
    realized and observes every realized step in ``moe.<name>.steps``; the
    two per-layer readers take the latter's mean."""
    import jax

    from deeplearning_cfn_tpu.obs.trace import get_tracer

    cell = types.SimpleNamespace(
        name="tiny_laguna", chips=1, config=dict(_config(), **TINY_LAGUNA),
        traffic=dict(TINY_TRAFFIC, kind="train_steps", repeat_min=0.0,
                     repeat_max=0.9))
    seed = 2 ** 31 + 29
    cfg = train_steps.build_program_config(cell, seed)
    trainer, state, shapes, mesh = train_steps.build_trainer(
        cell, cfg, seed, jax.devices()[:1])
    feed = train_steps.build_feed(
        cell, cfg, seed, mesh, lambda _name: contextlib.nullcontext())
    registry = get_tracer().registry
    seen = {name: registry.histogram(f"moe.{name}.steps")
            for name in ("rows_held", "load_max_over_mean")}
    before = {name: (h.count(), h.sum()) for name, h in seen.items()}
    rng = jax.random.PRNGKey(0)
    train_steps.first_steps(trainer, state, feed, rng, shapes, seed,
                            lambda _line: None)
    for name, h in seen.items():
        assert h.count() - before[name][0] == train_steps.CHECK_STEPS
        assert h.samples()[-1] == registry.gauge(f"moe.{name}").value()
    rows = seen["rows_held"]
    mean = (rows.sum() - before["rows_held"][1]) / train_steps.CHECK_STEPS
    assert 0 < mean < 2 * 4 * 32 * 4   # (layers, batch, positions, choices)
    assert 1.0 <= registry.gauge("moe.load_max_over_mean").value() < 4
