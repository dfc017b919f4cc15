"""Compiled for a described v5e (``test_chip_compile.py``; ``chip_steps.py``
says what goes where): Granite's convolution kernels, megablox's grouped
matmuls at the three expert cells' widths, and the whole train steps of
Mellum2's cell on four chips and of Granite's."""

import jax
import jax.numpy as jnp
import pytest
from chip_steps import (_bench, _gmm_calls, _norms_by_xla,  # noqa: F401
                        _row_scatters, _rows_calls, v5e_chip)
from jax.sharding import SingleDeviceSharding


@pytest.mark.parametrize("what", ["forward", "grad"])
def test_convolution_kernels_compile_for_v5e(v5e_chip, what):
    """The Granite cell's convolution alone: bfloat16 ``xBC [1, 8192,
    4352]`` under 4 taps, cut at 4096 and 4224 into the scan's x, B and C.
    One kernel a pass (the backward's residuals are the inputs, so its
    gradient runs no forward), the token block the rule's 512."""
    from deeplearning_cfn_tpu.ops.conv import (causal_conv_silu, conv_path,
                                               token_block)

    one_chip = SingleDeviceSharding(v5e_chip)
    shape, cuts = (1, 8192, 4352), (4096, 4224)
    assert conv_path("pallas", shape, 4, cuts) == ("kernel", False)
    assert token_block(8192, 4352, 2) == 512
    x = jax.ShapeDtypeStruct(shape, jnp.bfloat16, sharding=one_chip)
    w = jax.ShapeDtypeStruct((4, 4352), jnp.float32, sharding=one_chip)
    bias = jax.ShapeDtypeStruct((4352,), jnp.float32, sharding=one_chip)
    conv = lambda x, w, bias: causal_conv_silu(x, w, bias, cuts)
    fn = conv if what == "forward" else jax.grad(
        lambda *a: sum(p.astype(jnp.float32).sum() for p in conv(*a)),
        argnums=(0, 1, 2))
    text = jax.jit(fn).lower(x, w, bias).compile().as_text()
    assert text.count("tpu_custom_call") == 1
    assert ("causal_conv_fwd" if what == "forward"
            else "causal_conv_bwd") in text


@pytest.mark.parametrize("name,rows,groups,k,n", [
    ("mellum2_in", 131072, 16, 2304, 1792),
    ("mellum2_out", 131072, 16, 896, 2304),
    ("zaya1_in", 8192, 8, 2048, 4096), ("zaya1_out", 8192, 8, 2048, 2048),
    ("laguna_in", 16384, 32, 2048, 1024),
    ("laguna_out", 16384, 32, 512, 2048),
    # Laguna's second buffer, of every pair: long groups, 512-row tiles.
    ("laguna_every_pair_in", 65536, 32, 2048, 1024)])
@pytest.mark.parametrize("what", ["forward", "grad"])
def test_grouped_matmul_compiles_at_the_cells_widths(v5e_chip, name, rows,
                                                     groups, k, n, what):
    """megablox's ``gmm`` and, through ``models/moe.py:megablox_gmm``'s VJP,
    the backward ``gmm`` and ``tgmm`` at a rank's shapes in the three expert
    cells, each kernel with the tile ``gmm_tile`` chooses for it: Mellum2's
    131,072 buffer rows in 16 groups at widths no power of two divides (2304
    = 18 x 128, 1792 = 14 x 128, 896 = 7 x 128), ZAYA1's 8,192 in 8, Laguna's
    16,384 in 32. Mosaic takes each (VMEM), and the VJP adds no kernel."""
    from deeplearning_cfn_tpu.models.moe import grouped_matmul

    sharding = SingleDeviceSharding(v5e_chip)
    lhs = jax.ShapeDtypeStruct((rows, k), jnp.bfloat16, sharding=sharding)
    rhs = jax.ShapeDtypeStruct((groups, k, n), jnp.bfloat16,
                               sharding=sharding)
    sizes = jax.ShapeDtypeStruct((groups,), jnp.int32, sharding=sharding)
    f = lambda a, b, s: grouped_matmul(a, b, s, "megablox")
    if what == "grad":
        f = jax.grad(lambda a, b, s: jnp.sum(grouped_matmul(
            a, b, s, "megablox").astype(jnp.float32)), argnums=(0, 1))
    since = _gmm_calls()
    text = jax.jit(f).lower(lhs, rhs, sizes).compile().as_text()
    assert text.count("tpu_custom_call") == (1 if what == "forward" else 2)
    calls = _gmm_calls(since)
    assert {kernel for kernel, _, _ in calls} == (
        {"gmm"} if what == "forward" else {"gmm", "gmm_t", "tgmm"})
    assert all(divides == "yes" for _, _, divides in calls)


def test_mellum2_step_compiles_for_four_v5e_chips(v5e_chip):
    """The whole train step of ``mellum2_12b_train_8k_ep4`` at the cell's
    shapes for the four chips of a described ``v5e:2x2`` on ``expert=4``:
    Mosaic takes every Pallas call under its ``shard_map`` (flash, rotary,
    megablox), the state and the temporaries fit a chip, the exchange's
    collectives are in the text under their scopes and nothing else moves
    rows of 2304 between chips, and no row is scattered. PERF.md section 4
    has the number."""
    import re

    from deeplearning_cfn_tpu.obs.trace import get_tracer

    manifest, rehearse_compile = _bench()
    registry = get_tracer().registry
    wrapped = registry.counter("parallel.shard_map.calls")
    exchanged = registry.counter("moe.exchange.calls")
    label = dict(path="all_gather", ranks="4")
    before = {k: wrapped.value(kernel=k) for k in ("flash", "rope", "gmm")}
    exchanges, rows_before = exchanged.value(**label), _rows_calls()
    gmm_before = _gmm_calls()
    cell = manifest.Cell(manifest.load_manifest(),
                         "mellum2_12b_train_8k_ep4")
    assert cell.chips == 4
    _, compiled, _ = rehearse_compile.compile_step(cell)
    # Four layers. The step is traced once on the mesh; the trace for the
    # parameters' shapes initialises, which wraps nothing.
    assert {k: wrapped.value(kernel=k) - n for k, n in before.items()} \
        == {"flash": 4, "rope": 8, "gmm": 4}
    assert exchanged.value(**label) - exchanges == 4
    # A rank's 32,768 tokens of 2304 (its four ranks' after the exchange's
    # gather) are a source of 151 MB and its buffer of 131,072 rows one of
    # 604: the step's four layers fetch their rows by the row kernel on both
    # sides; the initialisation traces one row, by XLA's gather.
    assert _rows_calls(rows_before) == {
        ("buffer", "gather"): 4, ("tokens", "gather"): 4,
        ("buffer", "kernel"): 4, ("tokens", "kernel"): 4}
    # A rank's buffer of 131,072 rows in 16 groups at 2304, 1792 and 896:
    # every kernel's tile divides what it is asked (``divides=yes``), each
    # product's three kernels at a tile of their own.
    assert _gmm_calls(gmm_before) == {
        ("gmm", "256x2304x896", "yes"), ("gmm", "256x896x1152", "yes"),
        ("gmm_t", "256x1792x1152", "yes"), ("gmm_t", "256x2304x896", "yes"),
        ("tgmm", "512x1152x896", "yes"), ("tgmm", "512x896x1152", "yes")}
    # A rank sends 3 x 8192 tokens of 2304 in bfloat16 and float32, twice.
    assert registry.gauge("moe.exchange.bytes").value() \
        == 2 * 3 * 8192 * 2304 * 6
    mem = compiled.memory_analysis()
    total = mem.argument_size_in_bytes + mem.temp_size_in_bytes \
        + mem.output_size_in_bytes - mem.alias_size_in_bytes
    assert 4 * 2 ** 30 < total < 15.75 * 2 ** 30, total
    # 595.1 M parameters a chip with Adam's two moments: the stacks are
    # sharded, 16 experts a chip.
    assert 7.1e9 < mem.argument_size_in_bytes < 7.2e9
    text = compiled.as_text()
    kernels = [line for line in text.splitlines()
               if 'custom_call_target="tpu_custom_call"' in line]
    flash = [line for line in kernels if "core_attention/flash_" in line]
    assert len(flash) == 12 and all("/shard_map/" in line for line in flash)
    # A sequence a chip, 4 K/V heads under 32, not repeated.
    assert all("bf16[1,4,8192,128]" in line and "bf16[1,32,8192,128]" in line
               for line in flash)
    rope = [line for line in kernels if "/self_attn/rope/" in line]
    assert sum("rope_fwd" in line for line in rope) == 8
    assert sum("rope_bwd" in line for line in rope) == 8
    assert any("/moe_experts/" in line and "/mlp/shard_map/" in line
               for line in kernels)
    # The row kernel, a layer, under the usual buffer (the second buffer's
    # branch keeps XLA's gathers): the rows to the buffer, again where the
    # backward pass recomputes them, and their cotangents back to the tokens
    # under ``moe_dispatch``; the rows summed into their tokens and their
    # cotangents under ``moe_combine`` (the recomputed sum is dead code).
    # Inside the layer's own ``shard_map``: no new wrapper.
    rows = [line for line in kernels
            if re.search(r'op_name="[^"]*/live_rows/pallas_call"', line)]
    assert all("/mlp/shard_map/" in line for line in rows)
    assert sum("/moe_dispatch/" in line for line in rows) == 4 * 3
    assert sum("/moe_combine/" in line for line in rows) == 4 * 2
    assert len(rows) == 20 and len(kernels) == 92 + 20
    assert _row_scatters(text, 2304) == []
    moved = [line for line in text.splitlines() if re.search(
        r"= \S*\[(1,)?(8192|32768),2304\]\S* (all-gather|reduce-scatter|"
        r"all-reduce|all-to-all|collective-permute)(-start)?\(", line)]
    assert moved and all(re.search(r"/moe_exchange_(in|out)/", line)
                         for line in moved)
    kinds = {(re.search(r"= (\w+)\[", line).group(1),
              re.search(r"\]\S* (all-gather|reduce-scatter)", line).group(1),
              re.search(r"moe_exchange_(in|out)", line).group(0),
              "transpose(" in line) for line in moved}
    # Forward: the tokens gathered in bfloat16, the parts summed in float32;
    # backward, the transposes.
    assert kinds == {("bf16", "all-gather", "moe_exchange_in", False),
                     ("f32", "reduce-scatter", "moe_exchange_out", False),
                     ("f32", "all-gather", "moe_exchange_out", True),
                     ("bf16", "reduce-scatter", "moe_exchange_in", True)}
    assert "all-to-all" not in text


def test_granite4h_step_compiles_and_fits_a_v5e(v5e_chip):
    """The whole train step of ``granite4_h_micro_train_8k`` at the cell's
    shapes: nine Mamba-2 mixers and one attention layer at 8,192 tokens,
    every block recomputed in the backward pass. Mosaic takes the flash
    kernels at 32 query heads over 8 K/V heads of 64 with no rotary kernel
    beside them, and in every Mamba layer the scan's two kernels
    (``ops/ssd.py``) and the convolution's two (``ops/conv.py``), forward,
    recomputed and backward, with no decay matrix and no float32 ``[1, 8192,
    4352]`` in HBM; the mixers' five scopes are in the text, forward,
    recomputed and backward; no row is scattered inside a block; and 12.4 GB
    of state with
    one block's intermediates fit the chip, over the quarter of it a cell
    has to fill. PERF.md section 4 has the number."""
    import re

    from deeplearning_cfn_tpu.obs.trace import get_tracer

    manifest, rehearse_compile = _bench()
    registry = get_tracer().registry
    scans = registry.counter("ssm.scan.calls")
    convs = registry.counter("ssm.conv.calls")
    blocks = registry.counter("model.blocks.recomputed")
    kept = registry.counter("model.blocks.kept_flash")
    turned = registry.counter("attention.rope.calls")
    scanned = lambda: tuple(scans.value(path=p, chunk="256")
                            for p in ("kernel", "xla"))
    convolved = lambda: tuple(convs.value(path=p) for p in ("kernel", "xla"))
    before = (scanned(), blocks.value(),
              turned.value(path="kernel") + turned.value(path="xla"),
              kept.value(), convolved())
    cell = manifest.Cell(manifest.load_manifest(),
                         "granite4_h_micro_train_8k")
    assert cell.chips == 1
    _, compiled, _ = rehearse_compile.compile_step(cell)
    # Traced twice (the parameters' shapes, the step): nine mixers, each
    # through the scan's kernels and the convolution's, and ten recomputed
    # blocks each; the backward pass traces nothing again, and nothing turns
    # q or k.
    assert (tuple(n - m for n, m in zip(scanned(), before[0])),
            blocks.value() - before[1], turned.value(path="kernel")
            + turned.value(path="xla") - before[2],
            tuple(n - m for n, m in zip(convolved(), before[4]))) \
        == ((18, 0), 20, 0, (18, 0))
    # The attention block keeps its kernel's pair: [1, 32, 8192] rows of 64
    # bfloat16 and a float32.
    assert kept.value() - before[3] == 2
    assert registry.gauge("model.blocks.kept_bytes").value() \
        == 32 * 8192 * (64 * 2 + 4) == 34_603_008
    assert registry.gauge("ssm.scan.chunks").value() == 32
    assert registry.gauge("ssm.state_bytes").value() == 64 * 64 * 128 * 4
    mem = compiled.memory_analysis()
    total = mem.argument_size_in_bytes + mem.temp_size_in_bytes \
        + mem.output_size_in_bytes - mem.alias_size_in_bytes
    assert 4 * 2 ** 30 < total < 15.75 * 2 ** 30, total
    text = compiled.as_text()
    kernels = [line for line in text.splitlines()
               if 'custom_call_target="tpu_custom_call"' in line]
    # The one attention layer: forward, dK/dV, dQ, the forward's output and
    # row statistics kept across the recomputation (33.6 MB + 1 MB); K/V
    # are not repeated to the query heads.
    flash = [line for line in kernels if "/layer_5/" in line]
    assert len(flash) == 3
    assert all("core_attention/flash_" in line
               and "rematted_computation" not in line
               and "bf16[1,8,8192,64]" in line
               and "bf16[1,32,8192,64]" in line for line in flash)
    # Every Mamba layer's scan: the forward, the forward again that keeps
    # the states (recomputed) and the backward, all under the scope the
    # readers know; x is read as the projection left it, [B, S, H * P]. And
    # its convolution the same way: the forward, the forward again and the
    # backward under ``ssm_conv``, x, B and C written as the scan reads them.
    mamba = [line for line in kernels if line not in flash]
    assert len(mamba) == 54 and len(kernels) == 57
    name_of = lambda line: re.search(r'op_name="([^"]*)"', line).group(1)
    passes = lambda names, kernel: sorted(
        ("rematted_computation" in name, "transpose(jvp" in name,
         re.search(rf"/({kernel}_\w+)", name).group(1)) for name in names)
    for layer in (0, 1, 2, 3, 4, 6, 7, 8, 9):
        own = [name_of(line) for line in mamba if f"/layer_{layer}/" in line]
        scan = [name for name in own if "/self_attn/ssm_scan/" in name]
        conv = [name for name in own if "/self_attn/ssm_conv/" in name]
        assert len(scan) == len(conv) == 3 and len(own) == 6, own
        assert passes(scan, "ssd") == [
            (False, False, "ssd_fwd"), (False, True, "ssd_bwd"),
            (True, True, "ssd_fwd")], scan
        assert passes(conv, "causal_conv") == [
            (False, False, "causal_conv_fwd"),
            (False, True, "causal_conv_bwd"),
            (True, True, "causal_conv_fwd")], conv
    assert all("bf16[1,8192,4096]" in line for line in mamba)
    assert all("bf16[1,8192,4352]" in line for line in mamba
               if "causal_conv_" in line)
    # The convolution's float32 passes are gone with XLA's shifts.
    assert "f32[1,8192,4352]" not in text
    # The decay matrix of 64 heads never reaches HBM.
    assert not re.search(r"f32\[[\d,]*,256,256\]", text)
    for scope in ("ssm_in_proj", "ssm_conv", "ssm_scan", "ssm_gate_norm",
                  "ssm_out_proj"):
        for layer in (0, 4, 6, 9):
            held = re.findall(rf'op_name="([^"]*/layer_{layer}/[^"]*'
                              rf'/self_attn/{scope}/[^"]*)"', text)
            assert any("transpose(jvp" not in name for name in held), scope
            assert any("rematted_computation" in name for name in held), \
                scope
    assert "/layer_5/" in text and not re.search(
        r"/layer_5/[^\"]*/self_attn/ssm_", text)
    # What is scattered is the loss's one-hot and the embedding's gradient.
    assert not [line.strip()[:200] for line in text.splitlines()
                if re.search(r"= \S+ scatter\(", line)
                and re.search(r"/layer_\d+/", line)]


def test_keye_step_compiles_and_fits_a_v5e(v5e_chip):
    """The whole train step of ``keye_vl2_30b_a3b_train_16k`` at the cell's
    shapes: six recomputed blocks of learned sparse attention over one row
    of 16,384 tokens. Mosaic takes the selection's kernel (a tile of rows'
    index scores in VMEM, the threshold by bisection), the flash kernels
    under the selection (the packed words a fourth operand, 32 query heads
    over 4 K/V heads of 128) and the loss's pass; each runs once a layer:
    the recomputation reads the selection, the forward's output and row
    statistics and the loss's gradients, kept; the indexer's scopes are in
    the text; and 10.55 GB of state with one block's intermediates fit the
    chip, over the quarter of it a cell has to fill."""
    import re

    from deeplearning_cfn_tpu.obs.trace import get_tracer
    from deeplearning_cfn_tpu.ops.attention import _grid_gauges

    manifest, rehearse_compile = _bench()
    registry = get_tracer().registry
    calls = registry.counter("attention.flash.calls")
    selections = registry.counter("attention.selected.calls")
    blocks = registry.counter("model.blocks.recomputed")
    before = (calls.value(mask="selected", path="kernel"),
              calls.value(mask="causal", path="kernel"),
              selections.value(path="kernel"), blocks.value())
    rows_before = _rows_calls()
    cell = manifest.Cell(manifest.load_manifest(),
                         "keye_vl2_30b_a3b_train_16k")
    assert cell.chips == 1
    _, compiled, _ = rehearse_compile.compile_step(cell)
    # Traced twice (the parameters' shapes, the step), six layers each: every
    # attention call is a selected one.
    assert (calls.value(mask="selected", path="kernel") - before[0],
            calls.value(mask="causal", path="kernel") - before[1],
            selections.value(path="kernel") - before[2],
            blocks.value() - before[3]) == (12, 0, 12, 12)
    assert registry.gauge("attention.selected.topk").value() == 2048
    # The causal triangle's tiles, 16 x 16 of 1024: 136 live, and a dead
    # step names the diagonal's block again.
    assert _grid_gauges("flash_fwd", selected=True) == (256, 120, 0)
    assert _grid_gauges("flash_bwd_dq", selected=True) == (256, 120, 0)
    assert _grid_gauges("flash_bwd_dkdv", selected=True) == (256, 120, 0)
    assert registry.gauge("attention.flash.live_subtile_share").value(
        kernel="flash_fwd", mask="selected") == 136 / 256
    mem = compiled.memory_analysis()
    total = mem.argument_size_in_bytes + mem.temp_size_in_bytes \
        + mem.output_size_in_bytes - mem.alias_size_in_bytes
    assert 4 * 2 ** 30 < total < 15.75 * 2 ** 30, total
    text = compiled.as_text()
    kernels = [line for line in text.splitlines()
               if 'custom_call_target="tpu_custom_call"' in line]
    # SDAR's expert layer at SDAR's shape: a row goes to the buffer by XLA's
    # gather (the tokens are 2 ** 26 bytes) and to the tokens by the row
    # kernel (the buffer is 2 ** 27); the initialisation traces one row.
    assert _rows_calls(rows_before) == {
        ("buffer", "gather"): 12, ("tokens", "gather"): 6,
        ("tokens", "kernel"): 6}
    # A layer: 3 flash, the selection, the loss's pass, 6 rotary, 16 grouped
    # matmuls, 2 of the row kernel (the rows' cotangents to the tokens, the
    # rows summed into their tokens).
    assert len(kernels) == 6 * (3 + 1 + 1 + 6 + 16 + 2) == 174
    assert sum("/live_rows/pallas_call" in line for line in kernels) == 12
    name = lambda line: re.search(r'op_name="([^"]*)"', line).group(1)
    # The rotary kernels hold q's and k's norm (the indexer's heads of 64
    # turn by XLA), and XLA norms nothing of 16,384 positions beside them.
    rope = [name(line) for line in kernels if "/rope/" in name(line)]
    assert len(rope) == 36 and all(
        "/self_attn/qk_norm/rope/norm_rope_" in line for line in rope)
    assert not _norms_by_xla(text)
    for layer in range(6):
        mine = re.compile(r"/(flash_\w+|index_select|index_loss)\b")
        own = sorted(
            (("rematted_computation" in name(line)),
             mine.search(name(line)).group(1))
            for line in kernels if f"/layer_{layer}/" in name(line)
            and mine.search(name(line)))
        assert own == [(False, "flash_bwd_dkdv"), (False, "flash_bwd_dq"),
                       (False, "flash_fwd"), (False, "index_loss"),
                       (False, "index_select")], own
    flash = [line for line in kernels if "core_attention/flash_" in line]
    # The words go in beside q, k and v: [1, 16384, 512] int32.
    assert len(flash) == 18 and all(
        "s32[1,16384,512]" in line and "bf16[1,4,16384,128]" in line
        for line in flash)
    for scope in ("indexer_proj", "indexer_select", "indexer_loss"):
        assert re.search(rf'op_name="[^"]*/self_attn/[^"]*{scope}', text), \
            scope
    assert not _row_scatters(text)
