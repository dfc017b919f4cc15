"""Compiled for a described v5e (``test_chip_compile.py``; ``chip_steps.py``
says what goes where): the Laguna cell's grouped, windowed flash shapes and
its four rotary shapes, and the whole train steps of ZAYA1's cell and
Laguna's."""

import jax
import jax.numpy as jnp
import pytest
from chip_steps import (_bench, _gmm_calls, _row_scatters, _rows_calls,
                        v5e_chip)  # noqa: F401
from jax.sharding import SingleDeviceSharding

from deeplearning_cfn_tpu.ops.attention import fused_attention


@pytest.mark.parametrize("name,heads,window", [
    ("laguna_full", 48, 0), ("laguna_sliding", 64, 512)])
@pytest.mark.parametrize("what", ["forward", "grad"])
def test_grouped_windowed_kernels_compile_for_v5e(v5e_chip, name, heads,
                                                  window, what):
    """The Laguna cell's two attention shapes: 8 K/V heads under 48 and 64
    query heads at 4096 positions and head size 128, the sliding one under
    its window of 512 (sub-tiles in all three kernels, on a grid of the
    band: ``_tile_plan``'s plan for it, and index maps that clamp), the
    full one on its whole grid with the dead steps' index maps clamped. No
    dead step of either copies a block for nothing."""
    from deeplearning_cfn_tpu.ops.attention import _grid_gauges

    one_chip = SingleDeviceSharding(v5e_chip)
    q = jax.ShapeDtypeStruct((2, heads, 4096, 128), jnp.bfloat16,
                             sharding=one_chip)
    kv = jax.ShapeDtypeStruct((2, 8, 4096, 128), jnp.bfloat16,
                              sharding=one_chip)

    def attn(q, k, v):
        return fused_attention(q, k, v, causal=True, window=window,
                               implementation="pallas")

    fn = attn if what == "forward" else jax.grad(
        lambda q, k, v: attn(q, k, v).astype(jnp.float32).sum(),
        argnums=(0, 1, 2))
    compiled = jax.jit(fn).lower(q, kv, kv).compile()
    text = compiled.as_text()
    assert text.count("tpu_custom_call") == (1 if what == "forward" else 3)
    # K/V are not repeated to the query heads: every kernel takes them as
    # they are, 8 heads.
    for line in text.splitlines():
        if "tpu_custom_call" in line and " custom-call(" in line:
            assert "bf16[2,8,4096,128]" in line, line[:300]
    for kernel in ("flash_fwd", "flash_bwd_dkdv", "flash_bwd_dq")[
            :1 if what == "forward" else 3]:
        assert _grid_gauges(kernel, window) == (
            (8, 1, 0) if window else (16, 6, 0)), kernel


@pytest.mark.parametrize("name,heads,rope", [
    ("q_sliding", 64, "sliding_rope"), ("q_full", 48, "full_rope"),
    ("k_sliding", 8, "sliding_rope"), ("k_full", 8, "full_rope")])
@pytest.mark.parametrize("what", ["forward", "grad"])
def test_rope_kernel_compiles_for_v5e(v5e_chip, name, heads, rope, what):
    """The Laguna cell's four rotary shapes: q ``[2,4096,64*128]`` turning
    whole heads (one lane rotate), q ``[2,4096,48*128]`` turning 64 of 128
    lanes (YaRN: two rotates and a select), k ``[2,4096,8*128]`` at both.
    Forward reads the projection's layout and writes the flash kernels';
    the gradient is the same kernel the other way round."""
    from deeplearning_cfn_tpu.models.lm import _LAGUNA_XS2
    from deeplearning_cfn_tpu.models.transformer import rope_to_heads

    one_chip = SingleDeviceSharding(v5e_chip)
    x = jax.ShapeDtypeStruct((2, 4096, heads, 128), jnp.bfloat16,
                             sharding=one_chip)
    g = jax.ShapeDtypeStruct((2, heads, 4096, 128), jnp.bfloat16,
                             sharding=one_chip)

    def turn(x):
        return rope_to_heads(x, _LAGUNA_XS2[rope], "pallas")

    # The turn is linear: its gradient alone depends on no x, and a jit
    # without the described chip among its arguments compiles for the CPU.
    compiled = jax.jit(turn).lower(x).compile() if what == "forward" else \
        jax.jit(lambda x, g: jax.vjp(turn, x)[1](g)[0]).lower(x, g).compile()
    text = compiled.as_text()
    assert text.count("tpu_custom_call") == 1
    assert ("rope_fwd" if what == "forward" else "rope_bwd") in text


def test_zaya1_step_compiles_and_fits_a_v5e(v5e_chip):
    """The whole train step of ``zaya1_8b_train_4k`` at the cell's shapes:
    Mosaic takes the flash and rotary kernels at 8 query heads over 2 K/V
    heads of 128 (a group of 4), megablox its 8 groups of 2048 x 4096, the
    latent mixing is in the step under its scope, and arguments plus
    temporaries read under 15 GB of the chip's 16 (and over the quarter of
    it a cell has to fill). PERF.md section 4 has the number."""
    from deeplearning_cfn_tpu.obs.trace import get_tracer

    manifest, rehearse_compile = _bench()

    registry = get_tracer().registry
    mixed = registry.counter("attention.cca.calls")
    turned = registry.counter("attention.rope.calls")
    before = (mixed.value(), turned.value(path="kernel"),
              turned.value(path="xla"))
    rows_before, gmm_before = _rows_calls(), _gmm_calls()
    cell = manifest.Cell(manifest.load_manifest(), "zaya1_8b_train_4k")
    _, compiled, _ = rehearse_compile.compile_step(cell)
    # The one buffer of 8,192 rows: the contraction whole in ``gmm`` forward
    # and transposed (4096 in the first product's backward), a result block
    # of 1024 x 1024 in ``tgmm``; none padded.
    assert _gmm_calls(gmm_before) == {
        ("gmm", "256x2048x1024", "yes"), ("gmm_t", "256x4096x512", "yes"),
        ("gmm_t", "256x2048x1024", "yes"), ("tgmm", "256x1024x1024", "yes")}
    # The tokens and the one buffer of every pair, 8,192 rows of 2048 each
    # (33.5 MB): both sides of every layer by XLA's gathers.
    assert _rows_calls(rows_before) == {("buffer", "gather"): 10,
                                        ("tokens", "gather"): 10}
    # Traced twice (the parameters' shapes, the step), five layers each.
    assert (mixed.value() - before[0], turned.value(path="kernel")
            - before[1], turned.value(path="xla") - before[2]) == (10, 20, 0)
    assert registry.gauge("moe.router.state_layers").value() == 4
    mem = compiled.memory_analysis()
    total = mem.argument_size_in_bytes + mem.temp_size_in_bytes \
        + mem.output_size_in_bytes - mem.alias_size_in_bytes
    assert 4e9 < total < 15e9, total
    text = compiled.as_text()
    kernels = [line for line in text.splitlines()
               if 'custom_call_target="tpu_custom_call"' in line]
    flash = [line for line in kernels if "core_attention/flash_" in line]
    assert len(flash) == 15
    # K/V are not repeated to the query heads: 2 heads under 8.
    assert all("bf16[2,2,4096,128]" in line and "bf16[2,8,4096,128]" in line
               for line in flash)
    rope = [line for line in kernels if "/self_attn/rope/" in line]
    assert sum("rope_fwd" in line for line in rope) == 10
    assert sum("rope_bwd" in line for line in rope) == 10
    # Forward, the forward again (recomputed) and backward: eight grouped
    # matmuls a layer, all under the scope the readers know.
    assert sum("/moe_experts/jit(" in line and "/mlp/" in line
               for line in kernels) == 40
    assert "/self_attn/cca_mix/" in text and "/mlp/moe_router/" in text
    assert "/moe_dispatch/" in text and "/moe_combine/" in text
    assert _row_scatters(text) == [] and "live_rows" not in text


def test_laguna_step_compiles_and_fits_a_v5e(v5e_chip):
    """The whole train step of ``laguna_xs2_train_4k`` at the cell's shapes
    (``benchmark/rehearse_compile.py``, the builder's rehearsal): the chip's
    compiler takes it, the flash kernels, the grouped matmuls and the rotary
    kernels are in it, and arguments plus temporaries fit the chip's 16 GB.
    PERF.md section 4 has the number."""
    from deeplearning_cfn_tpu.obs.trace import get_tracer

    manifest, rehearse_compile = _bench()

    calls = get_tracer().registry.counter("attention.rope.calls")
    before = {path: calls.value(path=path) for path in ("kernel", "xla")}
    rows_before, gmm_before = _rows_calls(), _gmm_calls()
    cell = manifest.Cell(manifest.load_manifest(), "laguna_xs2_train_4k")
    _, compiled, _ = rehearse_compile.compile_step(cell)
    # Each grouped matmul at the tile of its own shape and kernel
    # (``models/moe.py:gmm_tile``), none padded: the usual buffer's 16,384
    # rows in 256-row tiles, the contraction whole in ``gmm``; the second
    # buffer's 65,536 the same but ``tgmm``'s rows, 512 (long groups).
    assert _gmm_calls(gmm_before) == {
        ("gmm", "256x2048x1024", "yes"), ("gmm", "256x512x2048", "yes"),
        ("gmm_t", "256x1024x2048", "yes"), ("gmm_t", "256x2048x512", "yes"),
        ("tgmm", "256x1024x1024", "yes"), ("tgmm", "256x512x2048", "yes"),
        ("tgmm", "512x1024x1024", "yes"), ("tgmm", "512x512x2048", "yes")}
    # Four expert layers, each traced twice, every one moving its rows by
    # XLA's gathers on both sides under either buffer: 8,192 tokens of 2048
    # are a source of 33.5 MB and the usual buffer's 16,384 rows one of 67,
    # under the size from which the row kernel is the cheaper.
    assert _rows_calls(rows_before) == {("buffer", "gather"): 8,
                                        ("tokens", "gather"): 8}
    # ``compile_step`` traces the model twice, once for the parameters'
    # shapes and once in the step: each trace turns q and k of five layers.
    assert {path: calls.value(path=path) - n
            for path, n in before.items()} == {"kernel": 20, "xla": 0}
    mem = compiled.memory_analysis()
    total = mem.argument_size_in_bytes + mem.temp_size_in_bytes \
        + mem.output_size_in_bytes - mem.alias_size_in_bytes
    assert 4e9 < total < 16e9, total
    # 5 forward and 10 backward flash kernels, and the grouped matmuls.
    text = compiled.as_text()
    assert text.count("tpu_custom_call") > 15
    assert "/moe_dispatch/" in text and "/moe_combine/" in text
    assert _row_scatters(text) == [] and "live_rows" not in text
    # q and k of five layers, turned forward and back by the kernel, which
    # keeps the scope that ``blocks_ms`` counts it under.
    kernels = [line for line in text.splitlines()
               if 'custom_call_target="tpu_custom_call"' in line
               and "/rope/" in line]
    assert sum("rope_fwd" in line for line in kernels) == 10
    assert sum("rope_bwd" in line for line in kernels) == 10
    assert all("/self_attn/rope/" in line for line in kernels)
