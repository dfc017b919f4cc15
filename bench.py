"""Headline benchmark: ResNet-50 training throughput, images/sec/chip.

Prints ONE JSON line {"metric", "value", "unit", "vs_baseline"} (plus "mfu",
"measured", "platform", "device_kind" and diagnostics) and exits 0 when the
number was measured. The measurement itself lives in
deeplearning_cfn_tpu/bench.py (full train step — fwd + bwd + LARS update —
on synthetic ImageNet-shaped data, bf16, donated buffers, pipelined timed
block ending in one block_until_ready, MFU from XLA cost analysis).

It runs in this one process: a chip belongs to one process at a time, so
there is no probe child and no retry child. It runs on the TPU, or on the
CPU only where the CPU was asked for by name (JAX_PLATFORMS=cpu) — there is
no fallback from one to the other. When nothing was measured the line is a
red record — "measured": false, with null (never 0.0) value/vs_baseline/mfu,
so nothing that aggregates these lines can average in a fake zero — and the
exit code is 1. Stage markers ("[bench-stage] t=+Xs <name>" on stderr) say
where a run that was cut at a time limit had got to.

Env overrides: DLCFN_BENCH_PRESET, DLCFN_BENCH_STEPS, DLCFN_BENCH_WARMUP,
DLCFN_BENCH_GLOBAL_BATCH.

Regression gate: when DLCFN_BENCH_DIFF_AGAINST points at a prior contract
record (JSON file, or a JSONL whose last record wins), the green record is
compared against it with obs/diff.py's direction-aware comparator
(value/mfu regress when they fall, mean_step_s when it rises; tolerance
DLCFN_BENCH_DIFF_TOLERANCE, default 0.10) and carries the verdict in
"regression_gate". The gate annotates — it never flips the exit code or
nulls a measured value; unmeasured records are never compared.

vs_baseline: the reference repo publishes no numbers (BASELINE.json
"published": {}), so the ratio is computed against the external context
anchor recorded in BASELINE.md — TF+Horovod ResNet-50 at ~375 images/sec per
V100 GPU (Horovod paper arXiv:1802.05799), the stack the reference's
flagship workload ran on.
"""

from __future__ import annotations

import json
import os
import sys
import traceback

METRIC = "imagenet_resnet50_train_images_per_sec_per_chip"
UNIT = "images/sec/chip"


def _red_record(error: str) -> dict:
    return {
        "metric": METRIC,
        # null, not 0.0: a red record must be unusable as a number.
        # "measured": false remains the primary flag.
        "value": None,
        "unit": UNIT,
        "vs_baseline": None,
        "mfu": None,
        "measured": False,
        "error": error[-2000:],
    }


def _finalize_green(record: dict) -> dict:
    """Post-process a record the measurement returned.

    A record taken on the CPU without the CPU having been asked for by name
    is a hard failure: the in-package bench already refuses to start that
    way, and nothing downstream may relabel such a number as a chip's.

    The null-over-zero rule: ANY record marked measured=false (whatever the
    reason) has its value/vs_baseline/mfu and perf fields nulled, so no
    unmeasured number ever survives into a green-looking line.
    """
    from deeplearning_cfn_tpu.runtime.platform import cpu_requested

    record.setdefault("measured", True)
    if record.get("platform", record.get("device_kind")) == "cpu" \
            and not cpu_requested():
        raise RuntimeError(
            "the bench ran on the CPU without the CPU having been asked "
            "for by name (JAX_PLATFORMS=cpu): no accelerator was measured")
    if record.get("measured") is False:
        record["value"] = None
        record["vs_baseline"] = None
        record["mfu"] = None
        # Serving-scenario perf fields follow the same null-over-zero
        # rule: an unmeasured run must not ship speculation/quantization
        # numbers either. Only nulled when present so non-serving records
        # keep their exact key set.
        for key in ("spec_gamma", "spec_accept_rate",
                    "tokens_per_target_step", "weight_bytes",
                    "e2e_latency_p50_s", "e2e_latency_p95_s",
                    "goodput_tokens_per_sec", "wasted_tokens",
                    "decode_p95_colocated", "decode_p95_disagg",
                    "decode_p95_no_adversary",
                    "handoff_latency_p50_s", "handoff_latency_p95_s",
                    "handoff_bytes", "kv_cache_bytes",
                    "spec_chain_len_p50", "host_syncs_per_token",
                    "offered_load_rps", "scale_events",
                    "time_to_scale_s", "p95_during_burst",
                    "qos_p95_by_class", "preemptions",
                    "preempted_tokens_replayed",
                    "fair_share_violation_max",
                    "qos_decode_p95_no_adversary",
                    "radix_hit_tokens_per_request",
                    "prefill_tokens_saved_ratio",
                    "radix_hit_rate", "radix_sweep",
                    "radix_hit_rate_prefix_affinity",
                    "radix_hit_rate_round_robin",
                    "prefill_chunk", "chunked_decode_p95",
                    "unchunked_decode_p95",
                    "chunk_ticks_per_prefill_p50",
                    "chaos_plan", "faults_injected",
                    "degrade_transitions", "degrade_events",
                    "deadline_wasted_tokens",
                    "net_decode_p95_disagg", "net_decode_p95_colocated",
                    "autoscale_time_to_scale_s",
                    "net_stream_ttfb_p50", "net_stream_ttfb_p95"):
            if key in record:
                record[key] = None
    return record


def _apply_diff_gate(record: dict) -> dict:
    """Regression-gate a green record against DLCFN_BENCH_DIFF_AGAINST
    (see module docstring). Purely additive: any failure inside the gate
    is recorded and the contract line still ships."""
    prior_path = os.environ.get("DLCFN_BENCH_DIFF_AGAINST")
    if not prior_path:
        return record
    tol = float(os.environ.get("DLCFN_BENCH_DIFF_TOLERANCE", "0.10"))
    try:
        from deeplearning_cfn_tpu.obs.diff import (
            diff_bench_records, load_bench_record)

        prior = load_bench_record(prior_path)
        if prior is None:
            record["regression_gate"] = {
                "against": prior_path, "ok": True,
                "skipped": "no parseable prior record"}
        else:
            gate = diff_bench_records(prior, record, tolerance=tol)
            gate["against"] = prior_path
            record["regression_gate"] = gate
    except Exception as e:  # never let the gate eat the contract line
        record["regression_gate"] = {"against": prior_path, "ok": True,
                                     "error": str(e)[:500]}
    return record


def main() -> int:
    try:
        from deeplearning_cfn_tpu.bench import run_bench

        record = _finalize_green(run_bench(
            preset=os.environ.get("DLCFN_BENCH_PRESET", "imagenet_resnet50"),
            steps=int(os.environ.get("DLCFN_BENCH_STEPS", "30")),
            warmup=int(os.environ.get("DLCFN_BENCH_WARMUP", "5")),
            global_batch=int(os.environ.get("DLCFN_BENCH_GLOBAL_BATCH",
                                            "0"))))
    except Exception as e:
        # The one boundary that must still print the contract line: the
        # traceback goes to stderr, the red record to stdout, and the exit
        # code says red.
        traceback.print_exc()
        record = _red_record(f"{type(e).__name__}: {e}")
    if record["measured"]:
        record = _apply_diff_gate(record)
    print(json.dumps(record))
    return 0 if record["measured"] else 1


if __name__ == "__main__":
    sys.exit(main())
