"""The flash-attention kernels, compiled by the TPU's own compiler for a chip
that is described and not attached (a v5e 2x2), at the shapes the shipped
presets produce, and the whole train step of the cell that runs them under
their third mask (SDAR's). Interpret mode cannot see what this does: a slice
that is not aligned to the tiling, or more fast memory than a kernel may use.
A compile that passes is not a chip run — chip_smoke.py is.
``test_chip_compile_laguna_zaya1.py`` and
``test_chip_compile_mellum2_granite4h.py`` hold the other kernels and cells;
``chip_steps.py`` says what goes where."""

import jax
import jax.numpy as jnp
import pytest
from chip_steps import (_bench, _norms_by_xla, _row_scatters,  # noqa: F401
                        _rows_calls, v5e_chip)
from jax.sharding import SingleDeviceSharding

from deeplearning_cfn_tpu.ops.attention import fused_attention


@pytest.mark.parametrize("name,shape,sk,causal", [
    ("gpt_small_lm", (16, 12, 1024, 64), 1024, True),
    ("bert_long_wikipedia", (8, 12, 4096, 64), 4096, False),
    ("head_dim_128", (1, 8, 2048, 128), 2048, True),
    # Several grid tiles, so the sub-tiles' bounds come from program_id: a
    # slice the chip's tiling refuses, or a body that outgrows VMEM, fails
    # here and not on the chip.
    ("gpt_long_lm", (1, 12, 8192, 64), 8192, True),
    ("causal_sq_lt_sk", (2, 12, 1024, 64), 2048, True),
])
@pytest.mark.parametrize("what", ["forward", "grad"])
def test_flash_kernel_compiles_for_v5e(v5e_chip, name, shape, sk, causal,
                                       what):
    assert v5e_chip.device_kind == "TPU v5 lite"
    one_chip = SingleDeviceSharding(v5e_chip)
    arg = jax.ShapeDtypeStruct(shape, jnp.bfloat16, sharding=one_chip)
    kv = jax.ShapeDtypeStruct(shape[:2] + (sk,) + shape[3:], jnp.bfloat16,
                              sharding=one_chip)

    def attn(q, k, v):
        return fused_attention(q, k, v, causal=causal,
                               implementation="pallas")

    fn = attn if what == "forward" else jax.grad(
        lambda q, k, v: attn(q, k, v).astype(jnp.float32).sum(),
        argnums=(0, 1, 2))
    compiled = jax.jit(fn).lower(arg, kv, kv).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_sdar_step_compiles_and_fits_a_v5e(v5e_chip):
    """The whole train step of ``sdar_30b_a3b_train_bd_8k`` at the cell's
    shapes: six recomputed blocks over the 16,384 positions of a
    block-diffusion row. Mosaic takes the flash kernels under the layout (32
    query heads over 4 K/V heads of 128, both copies in one call, K/V not
    repeated), the rotary kernel with the positions repeated and the grouped
    matmuls at 2048 -> 768; the schedule computes a quarter of the square
    and no dead step copies a block; the task's and the norm's scopes are in
    the text; and 10.3 GB of state with one block's intermediates fit the
    chip, over the quarter of it a cell has to fill."""
    import re

    from deeplearning_cfn_tpu.obs.trace import get_tracer
    from deeplearning_cfn_tpu.ops.attention import BlockDiffusion, \
        _grid_gauges

    manifest, rehearse_compile = _bench()
    registry = get_tracer().registry
    calls = registry.counter("attention.flash.calls")
    blocks = registry.counter("model.blocks.recomputed")
    kept = registry.counter("model.blocks.kept_flash")
    norms = registry.counter("attention.qk_norm.calls")
    before = (calls.value(mask="block_diffusion", path="kernel"),
              calls.value(mask="causal", path="kernel"), blocks.value(),
              kept.value(), norms.value(path="fused"),
              norms.value(path="xla"))
    rows_before = _rows_calls()
    cell = manifest.Cell(manifest.load_manifest(),
                         "sdar_30b_a3b_train_bd_8k")
    assert cell.chips == 1
    _, compiled, _ = rehearse_compile.compile_step(cell)
    # The step traces six calls under the layout; the parameters' shapes
    # come from the plain causal call (``init``), six more.
    assert (calls.value(mask="block_diffusion", path="kernel") - before[0],
            calls.value(mask="causal", path="kernel") - before[1],
            blocks.value() - before[2], kept.value() - before[3]) \
        == (6, 6, 12, 12)
    # A norm pair a layer, inside the rotary kernel both times.
    assert (norms.value(path="fused") - before[4],
            norms.value(path="xla") - before[5]) == (12, 0)
    # Six blocks' pairs over both copies: [1, 32, 16384] rows of 128
    # bfloat16 and a float32, 0.82 GB.
    assert registry.gauge("model.blocks.kept_bytes").value() \
        == 6 * 32 * 16384 * (128 * 2 + 4) == 817_889_280
    layout = BlockDiffusion(8192, 4)
    assert _grid_gauges("flash_fwd", layout=layout) == (144, 64, 0)
    assert _grid_gauges("flash_bwd_dq", layout=layout) == (144, 64, 0)
    assert _grid_gauges("flash_bwd_dkdv", layout=layout) == (256, 176, 0)
    for kernel, share in (("flash_fwd", 0.2578125), ("flash_bwd_dq", 0.265625),
                          ("flash_bwd_dkdv", 0.265625)):
        assert registry.gauge("attention.flash.live_subtile_share").value(
            kernel=kernel, mask="block_diffusion") == share
    assert registry.gauge("train.bd.block_length").value() == 4
    mem = compiled.memory_analysis()
    total = mem.argument_size_in_bytes + mem.temp_size_in_bytes \
        + mem.output_size_in_bytes - mem.alias_size_in_bytes
    assert 4 * 2 ** 30 < total < 15.75 * 2 ** 30, total
    text = compiled.as_text()
    kernels = [line for line in text.splitlines()
               if 'custom_call_target="tpu_custom_call"' in line]
    assert len(kernels) == 150 + 12
    name = lambda line: re.search(r'op_name="([^"]*)"', line).group(1)
    flash = [line for line in kernels if "core_attention/flash_" in line]
    # A layer: forward, dK/dV, dQ, and none again: the recomputation reads
    # the forward's output and row statistics, kept (136 MB a layer).
    assert len(flash) == 18
    assert all("bf16[1,4,16384,128]" in line and "bf16[1,32,16384,128]"
               in line for line in flash)
    for layer in range(6):
        own = sorted((("rematted_computation" in name(line)),
                      re.search(r"/(flash_\w+)", name(line)).group(1))
                     for line in flash if f"/layer_{layer}/" in name(line))
        assert own == [(False, "flash_bwd_dkdv"), (False, "flash_bwd_dq"),
                       (False, "flash_fwd")], own
    rope = [line for line in kernels if "/rope/" in name(line)]
    assert len(rope) == 36 and all("16384" in line for line in rope)
    # q's and k's norm is inside them, forward, recomputed and backward, and
    # XLA norms nothing of 16,384 positions beside them.
    assert sorted(re.search(r"/self_attn/(.*)/pallas_call", name(line))
                  .group(1) for line in rope) \
        == ["qk_norm/rope/norm_rope_bwd"] * 12 \
        + ["qk_norm/rope/norm_rope_fwd"] * 24
    assert not _norms_by_xla(text)
    assert len([line for line in kernels if "/moe_experts/" in name(line)
                ]) == len(kernels) - 54 - 12
    # 16,384 positions of 2048 are a source of 2 ** 26 bytes and the buffer
    # of 32,768 rows one of 2 ** 27: the step's six layers fetch a row to the
    # buffer by XLA's gather and to the tokens by the row kernel; the
    # initialisation traces one row. A layer's two kernels: the rows'
    # cotangents back to the tokens under ``moe_dispatch``, the rows summed
    # into their tokens under ``moe_combine``, once (the recomputed sum is
    # dead code).
    assert _rows_calls(rows_before) == {
        ("buffer", "gather"): 12, ("tokens", "gather"): 6,
        ("tokens", "kernel"): 6}
    rows = [name(line) for line in kernels
            if name(line).endswith("/live_rows/pallas_call")]
    assert len(rows) == 12
    assert sum("/moe_dispatch/" in line for line in rows) == 6
    assert sum("/moe_combine/" in line for line in rows) == 6
    for scope in ("bd_noise", "qk_norm", "moe_router", "lm_head", "lm_loss"):
        assert re.search(rf'op_name="[^"]*\b{scope}\b', text), scope
    assert not _row_scatters(text)
