"""Comm-volume analysis (parallel/comm_volume.py): HLO parsing and the
structural contract of the sequence-parallel strategies (r03 verdict,
Next #9 — the table a pod profile is checked against)."""

import pytest

from deeplearning_cfn_tpu.config import MeshConfig
from deeplearning_cfn_tpu.parallel.comm_volume import (
    comm_volume,
    compile_detection_step,
    compile_train_step,
)


def test_comm_volume_parses_hlo_text():
    """Parser unit contract: plain ops, async -start/-done pairs (payload
    counted once), and the all-reduce combiner's tuple-with-index-comments
    line (the r04 parser bug: '/*index=N*/' contains '=')."""
    hlo = """
HloModule m
  %x = bf16[2,4]{1,0} parameter(0)
  %p = bf16[2,4]{1,0} collective-permute(%x), channel_id=1
  %ag-start = (f32[8]{0}, f32[16]{0}) all-gather-start(%x), dim=0
  %ag-done = f32[16]{0} all-gather-done(%ag-start)
  %big = (f32[32]{0}, f32[32,32]{1,0}, /*index=2*/f32[4]{0}) all-reduce(%a, %b, %c), channel_id=2
  %gte = f32[32]{0} get-tuple-element(%big), index=0
  %a2a = f32[16]{0} all-to-all(%x), dim=0
  %cps = (u32[2,4]{1,0}, u32[2,4]{1,0}, u32[], u32[]) collective-permute-start(%i), channel_id=3
"""
    vol = comm_volume(hlo)
    # Sync permute + the async -start form (whose (in, out, ctx, ctx)
    # tuple must count the output once, not in+out+ctx).
    assert vol["collective-permute"] == {"count": 2,
                                         "bytes": 2 * 4 * 2 + 2 * 4 * 4}
    # Async all-gather-start: (input alias f32[8], output f32[16]) — the
    # payload is the 64-byte output, not the 96-byte tuple.
    assert vol["all-gather"] == {"count": 1, "bytes": 64}
    # Sync combiner tuple: every member IS output — summed.
    assert vol["all-reduce"] == {"count": 1,
                                 "bytes": 4 * (32 + 32 * 32 + 4)}
    assert vol["all-to-all"] == {"count": 1, "bytes": 64}
    assert vol["total"]["count"] == 5


def test_comm_volume_rejects_unknown_dtype():
    with pytest.raises(ValueError, match="unknown dtype"):
        comm_volume("  %q = f8e4m3fn[8]{0} all-reduce(%x)\n")


def test_seq_parallel_comm_structure(devices):
    """The strategies' collective SIGNATURES: ring moves K/V by ppermute
    (no all-to-all), Ulysses by all-to-all (no ppermute), byte-identical
    at equal shapes; pure DP has only the grad all-reduce. Compiled from
    the real train step on the fake-device mesh."""
    ring = comm_volume(compile_train_step(
        "bert_long", MeshConfig(data=2, seq=4), seq_impl="ring"))
    uly = comm_volume(compile_train_step(
        "bert_long", MeshConfig(data=2, seq=4), seq_impl="ulysses"))
    dp = comm_volume(compile_train_step(
        "bert_long", MeshConfig(data=8), seq_impl="ring"))

    assert ring["collective-permute"]["count"] > 0
    assert ring["all-to-all"]["count"] == 0
    assert uly["all-to-all"]["count"] > 0
    assert uly["collective-permute"]["count"] == 0
    # The textbook trade: same bytes moved, different op kind (ring rides
    # neighbor links, Ulysses needs full bisection).
    assert ring["collective-permute"]["bytes"] == uly["all-to-all"]["bytes"]
    # Pure DP: grad all-reduce only — no seq-axis movement of any kind.
    assert dp["collective-permute"]["count"] == 0
    assert dp["all-to-all"]["count"] == 0
    assert dp["all-gather"]["count"] == 0
    assert dp["all-reduce"]["count"] >= 1
    # Grad all-reduce bytes must cover the full param tuple (not just the
    # loss scalar — the r04 parser bug made it 4 bytes).
    assert dp["all-reduce"]["bytes"] > 50_000


def test_spatial_shard_halo_structure(devices):
    """The data+spatial detection step (SURVEY §3.2's one beyond-DP
    requirement) must move conv halos over 'spatial' — visible as
    collective-permute/all-gather traffic that the pure-DP compile of the
    same model does not have."""
    sp = comm_volume(compile_detection_step(MeshConfig(data=4, spatial=2)))
    dp = comm_volume(compile_detection_step(MeshConfig(data=8)))
    sp_moves = sp["collective-permute"]["count"] + sp["all-gather"]["count"]
    dp_moves = dp["collective-permute"]["count"] + dp["all-gather"]["count"]
    assert sp_moves > dp_moves, (sp, dp)
    assert sp["total"]["bytes"] > dp["total"]["bytes"], (sp, dp)
