"""The trace reduction on a small trace recorded on a v5e chip
(``benchmark/fixtures/small.xplane.pb``: three calls of the flash forward
and backward kernels at [2,4,1024,64] with a 20 ms host sleep between them),
and the shape-based operation counts against hand counts."""

import json
import os
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, os.path.join(REPO, "benchmark"))

from harness import kernels, opcount, stats, xplane  # noqa: E402
from harness.device import PEAKS, peaks_of  # noqa: E402

FIXTURES = os.path.join(REPO, "benchmark", "fixtures")
SPANS = ("window", "fit hook", "next(batch)")


@pytest.fixture(scope="module")
def trace():
    return xplane.Trace.from_file(
        os.path.join(FIXTURES, "small.xplane.pb"), SPANS)


@pytest.fixture(scope="module")
def calls():
    with open(os.path.join(FIXTURES, "small.pallas_calls.json")) as fh:
        return json.load(fh)


def test_busy_and_idle_share(trace):
    window = trace.window("window")
    assert (window[1] - window[0]) / 1e9 == pytest.approx(0.065718564)
    busy = trace.busy_s(window)
    assert busy == pytest.approx(0.000435665, rel=1e-6)
    assert 1.0 - busy / 0.065718564 > 0.99


def test_one_kernels_summed_time(trace, calls):
    forward = {c["name"] for c in calls if not c["backward"]}
    backward = {c["name"] for c in calls if c["backward"]}
    assert trace.events_named(forward) == (pytest.approx(0.000100778), 3)
    assert trace.events_named(backward) == (pytest.approx(0.000264367), 6)
    assert trace.events_named({"no_such_op"}) == (0.0, 0)


def test_idle_gap_is_labelled_by_the_host_span_that_covers_it(trace):
    gaps = trace.idle_gaps(trace.window("window"))
    assert gaps[0][0] == "next(batch)"
    assert gaps[0][1] == pytest.approx(0.065, abs=0.002)
    assert [g[0] for g in gaps] == ["next(batch)", "fit hook"]


def test_top_operations_are_the_kernels_under_their_short_names(trace):
    top = trace.top_ops(3)
    assert [n.split(" ")[0] for n, _ in top] == [
        "transpose_jvp___.2", "transpose_jvp___.3", "jvp__.1"]
    assert top[2][0] == "jvp__.1 (bf16[2,4,1024,64]"
    assert all(" = " not in n for n, _ in trace.top_ops(10))


@pytest.mark.parametrize("backward,low,high", [(False, 10.0, 25.0),
                                               (True, 10.0, 30.0)])
def test_flash_roofline_reader_on_the_recorded_trace(trace, calls, backward,
                                                     low, high):
    said = []
    ctx = {"trace": trace, "peaks": peaks_of("TPU v5 lite"),
           "run": {"pallas_calls": calls}, "window": trace.window("window"),
           "say": said.append}
    share = kernels.flash_roofline(ctx, backward=backward)
    assert low < share < high and "compute-bound" in said[0]
    ctx["run"] = {"pallas_calls": []}
    assert kernels.flash_roofline(ctx, backward=backward) is None
    assert "XLA attention path ran" in said[-1]


def test_a_trace_without_a_device_plane_fails_loudly():
    with pytest.raises(xplane.TraceError, match="no device plane"):
        xplane.Trace({}, [])
    with pytest.raises(xplane.TraceError):
        xplane.find_xplane(FIXTURES + "/nowhere")


def test_pallas_calls_are_read_from_compiled_text():
    text = (
        '  %self_attn.core_attention.36 = (bf16[16,12,1024,64]{3,2,1,0}, '
        'f32[16,12,1024,8]{3,2,1,0}) custom-call(%a, %b, %c), '
        'custom_call_target="tpu_custom_call", operand_layout_constraints='
        '{bf16[16,12,1024,64]{3,2,1,0}, bf16[16,12,1024,64]{3,2,1,0}}, '
        'metadata={op_name="jit(train_step)/jvp(layer_0)/pallas_call"}\n'
        '  %self_attn.core_attention.48 = bf16[16,12,1024,64]{3,2,1,0} '
        'custom-call(%a), custom_call_target="tpu_custom_call", '
        'operand_layout_constraints={bf16[16,12,1024,64]{3,2,1,0}}, '
        'metadata={op_name="jit(train_step)/transpose(jvp(layer_0))/'
        'pallas_call"}\n'
        '  %other = f32[2]{0} custom-call(%a), custom_call_target="Sharding"\n')
    assert xplane.pallas_calls(text) == [
        {"name": "self_attn.core_attention.36", "backward": False,
         "shape": [16, 12, 1024, 64]},
        {"name": "self_attn.core_attention.48", "backward": True,
         "shape": [16, 12, 1024, 64]}]


def test_one_gpt2_block_by_hand():
    # 4 d^2 + 2 d d_ff = 4*589824 + 2*2359296 = 7077888 weights a token is
    # multiplied by; attention 4*1024*768 halved = 1572864.
    assert opcount.block_matmul_params(768, 3072) == 7077888
    assert opcount.block_forward_flops(1, 1024, 768, 3072, True) == \
        2 * 7077888 + 1572864
    assert opcount.block_forward_flops(16384, 1024, 768, 3072, False) == \
        16384 * (2 * 7077888 + 2 * 1572864)


def test_gpt2_small_training_operations_per_token_by_hand():
    # 6 * (12 * 7077888 + 768 * 50257) + 6 * 12 * 1024 * 768
    assert opcount.lm_train_flops_per_token(12, 768, 3072, 50257, 1024) == \
        6 * (84934656 + 38597376) + 56623104 == 797815296


def test_one_flash_call_by_hand():
    flops, nbytes = opcount.flash_forward(16, 12, 1024, 1024, 64, True)
    assert flops == 4 * 16 * 12 * 1024 * 1024 * 64 // 2 == 25769803776
    assert nbytes == 2 * 16 * 12 * 64 * 4096 == 100663296
    bflops, bbytes = opcount.flash_backward(16, 12, 1024, 1024, 64, True)
    assert bflops == 2.5 * flops and bbytes == 2 * nbytes
    full, _ = opcount.flash_forward(16, 12, 1024, 1024, 64, False)
    assert full == 2 * flops
    seconds, bound = opcount.roofline_seconds(flops, nbytes, PEAKS[
        "TPU v5 lite"])
    assert bound == "compute" and seconds == pytest.approx(flops / 197e12)
    assert opcount.roofline_seconds(1.0, 819e9, PEAKS["TPU v5 lite"]) == \
        (1.0, "memory")


def test_an_unknown_device_kind_is_an_error():
    with pytest.raises(KeyError, match="no published peaks"):
        peaks_of("TPU v9 imaginary")


def test_percentiles():
    assert stats.percentile([1, 2, 3, 4, 5], 50) == 3
    assert stats.percentile([1, 2, 3, 4], 95) == pytest.approx(3.85)
    assert stats.median([5.0]) == 5.0
    with pytest.raises(ValueError):
        stats.percentile([], 50)
