"""In-package benchmark harness: step-time/throughput for any preset.

The reference's performance story was external (nccl-tests + the example
scripts' own throughput prints); here measurement is a first-class verb
(``dlcfn-tpu bench``). Root-level ``bench.py`` wraps the ResNet-50 flagship
case of this harness for the driver contract.
"""

from __future__ import annotations

import json
import os
import sys
import time
from typing import Dict, Optional

_T0 = time.monotonic()


def stage(name: str, **info) -> None:
    """Emit a stage-timestamped marker to stderr, so the tail of a run that
    was cut at its time limit says which phase it was in (backend init?
    first compile? the timed block?)."""
    extra = "".join(f" {k}={v}" for k, v in info.items())
    print(f"[bench-stage] t=+{time.monotonic() - _T0:.1f}s {name}{extra}",
          file=sys.stderr, flush=True)

# External context anchor (BASELINE.md): TF+Horovod ResNet-50 on V100, the
# stack the reference's flagship workload ran on (~375 img/s/GPU, Horovod
# paper arXiv:1802.05799). The reference itself publishes no numbers.
HOROVOD_V100_IMG_PER_SEC_PER_GPU = 375.0

# Presets whose MFU numerator must come from a DENSE-equivalent compile:
# XLA's cost analysis counts a lax.scan body once, so the GPipe schedule's
# double scan (ticks × stage layers) under-counts the trunk by ~T·L/S —
# the r03 "0.05*" footnote. The dense twin computes the same math with the
# layer loop unrolled, so ITS cost analysis is the honest useful-FLOPs
# count at identical shapes (same hidden/layers/heads/seq contract,
# asserted at bench time).
_DENSE_FLOPS_EQUIV = {
    "bert_pipelined_wikipedia": "bert_base_wikipedia",
}

# Presets whose parallelism strategy needs a >1 mesh axis to engage: on a
# single chip they run a DENSE fallback, and the number must say so
# (r03 Weak #4 — a fallback number must never read as a ring measurement).
_SEQ_PARALLEL_PRESETS = {"bert_long_wikipedia", "gpt_long_lm"}

_UNITS = {
    "cifar10_resnet20": "images/sec/chip",
    "imagenet_resnet50": "images/sec/chip",
    "maskrcnn_coco": "images/sec/chip",
    "bert_base_wikipedia": "sequences/sec/chip",
    "transformer_nmt_wmt": "sequences/sec/chip",
    "bert_moe_wikipedia": "sequences/sec/chip",
    "bert_pipelined_wikipedia": "sequences/sec/chip",
    "bert_long_wikipedia": "sequences/sec/chip",
    "gpt_small_lm": "sequences/sec/chip",
    "gpt_long_lm": "sequences/sec/chip",
    "imagenet_vit_s16": "images/sec/chip",
}

# Peak dense bf16 FLOPs/sec per chip, keyed by device_kind substring.
# Order matters: more specific kinds first ("v5p" before "v5").
_PEAK_FLOPS_BF16 = (
    ("v6", 918e12),
    ("trillium", 918e12),
    ("v5p", 459e12),
    ("v5e", 197e12),
    ("v5 lite", 197e12),
    ("v5litepod", 197e12),
    ("v5", 459e12),
    ("v4", 275e12),
    ("v3", 123e12),
    ("v2", 45e12),
)


def peak_flops_per_chip(device) -> Optional[float]:
    """Peak bf16 FLOPs/sec for ``device``. None on the CPU (which runs only
    where it was asked for by name, and carries ``mfu: null``); an
    accelerator kind that is not in the table is an error, not a default."""
    if device.platform == "cpu":
        return None
    kind = getattr(device, "device_kind", "").lower()
    for key, peak in _PEAK_FLOPS_BF16:
        if key in kind:
            return peak
    raise ValueError(
        f"unknown accelerator kind {device.device_kind!r}: add its peak "
        f"bf16 FLOP/s (with its source) to _PEAK_FLOPS_BF16")


def _flops_of(compiled) -> Optional[float]:
    """Per-device FLOPs of one execution of an AOT-compiled step, from XLA's
    own cost analysis (no hand-derived model FLOP formula to drift out of
    date). The analyzed module is the post-GSPMD per-device program, so the
    number is already per-chip."""
    try:
        analysis = compiled.cost_analysis()
        if isinstance(analysis, (list, tuple)):
            analysis = analysis[0] if analysis else {}
        flops = float(analysis.get("flops", 0.0))
        return flops if flops > 0 else None
    except Exception:
        return None


def annotate_record(record: Dict, preset: str, mesh_shape: Dict[str, int],
                    gb: int, preset_gb: int) -> Dict:
    """Fallback/underfill labels (r03 Weak #4/#5): a number measured in a
    degraded configuration must say so in the artifact itself."""
    if preset in _SEQ_PARALLEL_PRESETS:
        seq_ways = int(mesh_shape.get("seq", 1))
        record["fallback"] = seq_ways == 1
        if seq_ways == 1:
            record["fallback_note"] = (
                "dense-attention fallback (mesh seq=1): NOT a ring/Ulysses "
                "sequence-parallel measurement")
    if gb < preset_gb:
        record["batch_underfilled"] = True
        record["preset_global_batch"] = preset_gb
    return record


def _dense_equiv_flops(preset: str, cfg, mesh, gb: int) -> Optional[float]:
    """Per-device FLOPs of the dense twin of a scanned preset (see
    _DENSE_FLOPS_EQUIV): same shapes, layer loop unrolled, AOT-compiled on
    the same mesh purely for cost analysis — never executed."""
    import jax

    from .config import apply_overrides
    from .data import build_pipeline
    from .parallel.mesh import local_batch_size
    from .presets import get_preset
    from .train import create_train_state
    from .train.optim import build_optimizer, build_schedule
    from .train.task import build_task
    from .train.trainer import Trainer

    dcfg = get_preset(_DENSE_FLOPS_EQUIV[preset])
    dcfg.train.global_batch = gb
    dcfg.train.grad_accum_steps = 1
    dcfg.data.seq_len = cfg.data.seq_len
    dcfg.data.vocab_size = cfg.data.vocab_size
    for k in ("hidden_size", "num_layers", "num_heads", "mlp_dim",
              "max_len"):
        if k in cfg.model.kwargs:
            dcfg.model.kwargs[k] = cfg.model.kwargs[k]
    apply_overrides(dcfg, ["data.prefetch=0", "data.synthetic=true"])
    dcfg.data.num_train_examples = gb
    dcfg.data.num_eval_examples = gb
    task = build_task(dcfg, mesh=mesh)
    sched = build_schedule(dcfg.schedule, 1000, gb, 100)
    tx = build_optimizer(dcfg.optimizer, sched)
    state = create_train_state(
        jax.random.PRNGKey(0), task.init, tx, mesh,
        param_rules=getattr(task, "param_rules", ()),
        shard_opt_state=dcfg.train.shard_opt_state)
    trainer = Trainer(dcfg, task.loss_fn, tx, mesh=mesh,
                      spatial_dim=getattr(task, "spatial_dim", None),
                      spatial_keys=getattr(task, "spatial_keys", None))
    pipe = build_pipeline(dcfg.data, local_batch_size(gb, mesh),
                          dcfg.model.num_classes, seed=0, train=True)
    dev_batch = trainer.device_batch(next(iter(pipe.one_epoch(0))))
    compiled = trainer.train_step.lower(
        state, dev_batch, jax.random.PRNGKey(1)).compile()
    return _flops_of(compiled)


def run_bench(
    preset: str = "imagenet_resnet50",
    steps: int = 20,
    global_batch: int = 0,
    warmup: int = 4,
    mesh=None,
    include_input: bool = False,
    step_window: int = 1,
) -> Dict:
    """Run ``steps`` timed train steps of ``preset`` on synthetic data and
    return the one-line JSON record the driver expects.

    The headline number reuses one device-resident batch — pure step
    throughput, no host input in the timed path. ``include_input=True``
    additionally times a loop that pulls a fresh batch from the host
    pipeline (+ ``device_batch`` transfer) every step and reports it as
    ``value_with_input`` — the trained-throughput number, which is the one
    that regresses when the input pipeline can't keep up.

    ``step_window`` > 1 benches the fused multi-step program instead
    (``trainer.window_step``: a lax.scan over K steps per dispatch — the
    train-loop fast path); the record says which program was measured
    (``step_window``) plus its ``compile_s`` and ``steps_per_sec``.
    """
    stage("import_jax")
    import jax

    from .runtime.platform import require_accelerator

    stage("backend_init")  # first jax.devices() triggers PJRT client init
    require_accelerator()
    devices = jax.devices()
    stage("devices_ok", n=len(devices),
          kind=getattr(devices[0], "device_kind", "unknown"))
    import numpy as np

    from .config import MeshConfig, apply_overrides
    from .data import build_pipeline
    from .parallel.mesh import build_mesh, local_batch_size
    from .presets import get_preset
    from .train import create_train_state
    from .train.optim import build_optimizer, build_schedule
    from .train.task import build_task
    from .train.trainer import Trainer

    cfg = get_preset(preset)
    if step_window < 1:
        raise ValueError(f"step_window must be >= 1, got {step_window}")
    cfg.train.step_window = step_window
    if global_batch:
        cfg.train.global_batch = global_batch
        # An explicit batch is a step-time probe like the single-chip
        # default path: keeping the preset's accumulation factor would make
        # sweep entries reject batches that don't divide it (ADVICE r3 #1).
        cfg.train.grad_accum_steps = 1
    elif jax.device_count() == 1:
        # Single-chip bench: a per-chip-sized batch, not the pod-sized one.
        # Sized to saturate the MXU without blowing HBM; override with
        # --global-batch (or DLCFN_BENCH_GLOBAL_BATCH via the wrapper) to
        # sweep.
        per_chip = {"imagenet_resnet50": 512, "cifar10_resnet20": 512,
                    "bert_base_wikipedia": 32, "transformer_nmt_wmt": 64,
                    "maskrcnn_coco": 4,
                    # seq-4096 activations: batch 8 fits one 16 GB chip
                    "bert_long_wikipedia": 8,
                    # GPT-small @ seq 1024: 16 seqs/chip
                    "gpt_small_lm": 16,
                    # seq-16384: 1 seq/chip (dense fallback on one chip)
                    "gpt_long_lm": 1,
                    "imagenet_vit_s16": 256}.get(preset, 64)
        cfg.train.global_batch = per_chip
        # Single-chip step-time probe: accumulation is a memory/global-
        # batch device-scaling tool, and the tiny per-chip batches above
        # need not divide a preset's accum factor (gpt_long_lm: batch 1
        # vs accum 2 would be rejected by the Trainer).
        cfg.train.grad_accum_steps = 1
    apply_overrides(cfg, ["data.prefetch=0", "data.synthetic=true"])
    # One batch is all the bench consumes — don't materialize the default
    # multi-GB synthetic dataset (8192×224² ImageNet ≈ 5 GB host RAM).
    cfg.data.num_train_examples = cfg.train.global_batch
    cfg.data.num_eval_examples = cfg.train.global_batch

    mesh = mesh if mesh is not None else build_mesh(MeshConfig(data=-1))
    n_chips = mesh.devices.size
    gb = cfg.train.global_batch

    task = build_task(cfg, mesh=mesh)
    sched = build_schedule(cfg.schedule, max(steps * 10, 1000), gb, 100)
    tx = build_optimizer(cfg.optimizer, sched)
    state = create_train_state(jax.random.PRNGKey(0), task.init, tx, mesh,
                               param_rules=getattr(task, "param_rules", ()),
                               shard_opt_state=cfg.train.shard_opt_state)
    trainer = Trainer(cfg, task.loss_fn, tx, mesh=mesh,
                      spatial_dim=getattr(task, "spatial_dim", None),
                      spatial_keys=getattr(task, "spatial_keys", None))

    stage("build", preset=preset, global_batch=gb)
    pipe = build_pipeline(cfg.data, local_batch_size(gb, mesh),
                          cfg.model.num_classes, seed=0, train=True)
    host_batch = next(iter(pipe.one_epoch(0)))
    dev_batch = trainer.device_batch(host_batch)
    step_rng = jax.random.PRNGKey(1)

    # One AOT compile, reused for execution AND cost analysis — calling
    # trainer.train_step would jit-compile a second, separate executable.
    # step_window > 1 compiles the fused K-step scan program instead; one
    # dispatch then advances K steps, fed by a K-tuple reusing the same
    # device batch (batches are NOT donated, so reuse is safe).
    k = step_window
    stage("first_compile", step_window=k)
    t_c = time.perf_counter()
    if k > 1:
        win_batch = (dev_batch,) * k
        compiled_step = trainer.window_step.lower(
            state, win_batch, step_rng).compile()

        def dispatch(st):
            return compiled_step(st, win_batch, step_rng)
    else:
        compiled_step = trainer.train_step.lower(
            state, dev_batch, step_rng).compile()

        def dispatch(st):
            return compiled_step(st, dev_batch, step_rng)
    compile_s = time.perf_counter() - t_c

    stage("warmup", n=max(warmup, 1))
    for _ in range(max(warmup, 1)):
        state, m = dispatch(state)
    jax.block_until_ready(m)
    n_windows = max(1, steps // k)
    stage("timed", steps=n_windows * k)

    # Timed block: dispatch every step back-to-back with NO per-step sync —
    # steady-state pipelined throughput, the number that matters at pod
    # scale — then one trailing block_until_ready inside the timed region
    # (the state chains through the loop, so the last step's metrics are
    # ready only when every step has run).
    t0 = time.perf_counter()
    for _ in range(n_windows):
        state, m = dispatch(state)
    jax.block_until_ready(m)
    mean_step_s = (time.perf_counter() - t0) / (n_windows * k)

    # MFU: XLA-counted per-device FLOPs per step vs one chip's peak bf16
    # rate. null on the CPU (no peak) or when cost analysis is unavailable
    # — never 0.0. Scanned presets take their numerator from a dense-twin
    # compile (cost analysis counts a scan body once — r03 Weak #3). That
    # same counts-the-body-once behavior makes the windowed program's
    # analysis a per-STEP number, which is exactly what mean_step_s pairs
    # with.
    flops = _flops_of(compiled_step)
    mfu_source = "xla_cost_analysis"
    if preset in _DENSE_FLOPS_EQUIV:
        stage("dense_equiv_compile", twin=_DENSE_FLOPS_EQUIV[preset])
        try:
            dense_flops = _dense_equiv_flops(preset, cfg, mesh, gb)
        except Exception as e:  # the twin is only a label source — a
            # failure there (OOM from its extra state, preset drift) must
            # not discard the already-measured step time.
            dense_flops = None
            mfu_source = f"xla_cost_analysis (dense twin failed: {e})"
        if dense_flops:
            flops = dense_flops
            mfu_source = f"dense_equivalent:{_DENSE_FLOPS_EQUIV[preset]}"
    peak = peak_flops_per_chip(devices[0])
    mfu = round(flops / (mean_step_s * peak), 4) if flops and peak else None

    per_chip = gb / mean_step_s / n_chips
    unit = _UNITS.get(preset, "items/sec/chip")
    record = {
        "metric": f"{preset}_train_{unit.split('/')[0]}_per_sec_per_chip",
        "value": round(per_chip, 2),
        "unit": unit,
        # The V100 anchor is a ResNet-50/ImageNet number — a ratio against
        # it is only meaningful for that preset.
        "vs_baseline": round(per_chip / HOROVOD_V100_IMG_PER_SEC_PER_GPU, 3)
        if preset == "imagenet_resnet50" else 0.0,
        "mfu": mfu,
        "steps": n_windows * k,
        "step_window": k,
        "steps_per_sec": round(1.0 / mean_step_s, 3),
        "compile_s": round(compile_s, 2),
        "global_batch": gb,
        "n_chips": n_chips,
        "mean_step_s": round(mean_step_s, 5),
        "platform": devices[0].platform,
        "device_kind": devices[0].device_kind,
        # The mesh the step actually ran on. On one chip every preset
        # degenerates to {data: 1} — in particular bert_long then runs its
        # DENSE flash-attention fallback, not ring/Ulysses (those need a
        # seq axis > 1); the mesh field keeps that visible in the artifact.
        "mesh": {k: int(v) for k, v in mesh.shape.items()},
        "mfu_source": mfu_source,
        "measured": True,
    }
    annotate_record(record, preset, dict(mesh.shape), gb,
                    get_preset(preset).train.global_batch)
    # Post-run HBM occupancy (PJRT memory_stats; absent on CPU): how close
    # the chosen batch runs to the chip's limit — context for batch sweeps.
    try:
        stats = jax.local_devices()[0].memory_stats() or {}
        # Peak is the batch-headroom number (post-run bytes_in_use has
        # already dropped the step's activation temporaries).
        if "peak_bytes_in_use" in stats:
            record["hbm_gib_peak"] = round(
                stats["peak_bytes_in_use"] / 2**30, 2)
        if "bytes_in_use" in stats:
            record["hbm_gib_in_use"] = round(
                stats["bytes_in_use"] / 2**30, 2)
        if "bytes_limit" in stats:
            record["hbm_gib_limit"] = round(
                stats["bytes_limit"] / 2**30, 2)
    except Exception:
        pass

    if include_input:
        stage("timed_with_input", steps=steps)
        # A few distinct host batches (bounded memory) cycled through the
        # real pipeline path: host batch → device_batch transfer → step.
        # Restore the preset's prefetch depth — the headline bench zeroed
        # it, but trained throughput overlaps host work with device steps.
        cfg.data.num_train_examples = 2 * gb
        cfg.data.prefetch = get_preset(preset).data.prefetch or 2
        feed_pipe = build_pipeline(cfg.data, local_batch_size(gb, mesh),
                                   cfg.model.num_classes, seed=1,
                                   train=True)
        it = feed_pipe.epochs()

        def feed():
            if k > 1:
                return tuple(trainer.device_batch(next(it))
                             for _ in range(k))
            return trainer.device_batch(next(it))

        try:
            state, m = compiled_step(state, feed(), step_rng)
            float(np.asarray(m["loss"]).reshape(-1)[-1])
            t0 = time.perf_counter()
            for _ in range(n_windows):
                state, m = compiled_step(state, feed(), step_rng)
            float(np.asarray(m["loss"]).reshape(-1)[-1])
            step_s = (time.perf_counter() - t0) / (n_windows * k)
        finally:
            it.close()  # stop the prefetch worker, release its buffers
        record["value_with_input"] = round(gb / step_s / n_chips, 2)
        record["mean_step_s_with_input"] = round(step_s, 5)

    stage("done", value=record["value"])
    return record


def run_obs_overhead_smoke(
    preset: str = "transformer_nmt_wmt",
    steps: int = 30,
    warmup: int = 5,
    global_batch: int = 0,
    mesh=None,
) -> Dict:
    """Measure the obs span tracer's per-step cost: the SAME compiled step,
    once with spans disabled (``DLCFN_OBS_OFF``-equivalent) and once fully
    instrumented (span + sink write per step — the train loop's worst
    case). The acceptance bar is <= 5% step-time delta on the CPU
    transformer_nmt config; the record reports ``overhead_pct`` so the
    driver can gate on it."""
    stage("import_jax")
    import jax

    from .runtime.platform import require_accelerator

    require_accelerator()
    import numpy as np

    from .config import MeshConfig, apply_overrides
    from .data import build_pipeline
    from .obs.sinks import MemorySink
    from .obs.trace import Tracer, configured, set_enabled, span
    from .parallel.mesh import build_mesh, local_batch_size
    from .presets import get_preset
    from .train import create_train_state
    from .train.optim import build_optimizer, build_schedule
    from .train.task import build_task
    from .train.trainer import Trainer

    cfg = get_preset(preset)
    cfg.train.global_batch = global_batch or (
        64 if jax.device_count() == 1 else cfg.train.global_batch)
    cfg.train.grad_accum_steps = 1
    apply_overrides(cfg, ["data.prefetch=0", "data.synthetic=true"])
    cfg.data.num_train_examples = cfg.train.global_batch
    cfg.data.num_eval_examples = cfg.train.global_batch
    mesh = mesh if mesh is not None else build_mesh(MeshConfig(data=-1))
    gb = cfg.train.global_batch

    task = build_task(cfg, mesh=mesh)
    tx = build_optimizer(cfg.optimizer,
                         build_schedule(cfg.schedule, 1000, gb, 100))
    state = create_train_state(jax.random.PRNGKey(0), task.init, tx, mesh,
                               param_rules=getattr(task, "param_rules", ()),
                               shard_opt_state=cfg.train.shard_opt_state)
    trainer = Trainer(cfg, task.loss_fn, tx, mesh=mesh,
                      spatial_dim=getattr(task, "spatial_dim", None),
                      spatial_keys=getattr(task, "spatial_keys", None))
    pipe = build_pipeline(cfg.data, local_batch_size(gb, mesh),
                          cfg.model.num_classes, seed=0, train=True)
    dev_batch = trainer.device_batch(next(iter(pipe.one_epoch(0))))
    rng = jax.random.PRNGKey(1)
    stage("first_compile")
    compiled = trainer.train_step.lower(state, dev_batch, rng).compile()

    # The compiled step donates the state buffers, so each loop must hand
    # its final state to the next one — re-entering with the original
    # `state` would pass already-donated buffers.
    def timed_loop(st, enabled: bool):
        set_enabled(enabled)
        try:
            for _ in range(max(warmup, 1)):
                st, m = compiled(st, dev_batch, rng)
            float(np.asarray(m["loss"]).reshape(-1)[-1])
            t0 = time.perf_counter()
            for i in range(steps):
                with span("train.dispatch", step=i, k=1):
                    st, m = compiled(st, dev_batch, rng)
            float(np.asarray(m["loss"]).reshape(-1)[-1])
            return st, (time.perf_counter() - t0) / steps
        finally:
            set_enabled(None)

    # A dedicated tracer with a live sink so the "on" loop pays the FULL
    # instrumented cost (id alloc, record build, sink write) — then the
    # process default is restored.
    tracer = Tracer()
    sink = MemorySink()
    tracer.add_sink(sink)
    configured(tracer)
    try:
        stage("timed_obs_off", steps=steps)
        state, off_s = timed_loop(state, False)
        stage("timed_obs_on", steps=steps)
        state, on_s = timed_loop(state, True)
    finally:
        configured(None)

    overhead_pct = (on_s - off_s) / off_s * 100.0
    record = {
        "metric": f"{preset}_obs_overhead_pct",
        "value": round(overhead_pct, 3),
        "unit": "percent",
        "obs_off_step_s": round(off_s, 6),
        "obs_on_step_s": round(on_s, 6),
        "steps": steps,
        "global_batch": gb,
        "preset": preset,
        "device_kind": getattr(jax.devices()[0], "device_kind", "unknown"),
        "measured": True,
    }
    # The smoke also proves the export path end-to-end: the spans the
    # instrumented loop just emitted must round-trip through the
    # Perfetto exporter into structurally valid trace-event JSON (the
    # cheap no-viewer gate — parse + nesting check, nothing rendered).
    import tempfile

    from .obs.export import build_trace, validate_trace

    stage("trace_export", spans=len(sink.records))
    trace = build_trace(sink.records)
    problems = validate_trace(trace)
    trace_path = os.path.join(
        tempfile.mkdtemp(prefix="dlcfn_obs_smoke_"), "trace.json")
    with open(trace_path, "w") as fh:
        json.dump(trace, fh)
    with open(trace_path) as fh:
        reparsed = json.load(fh)
    trace_valid = (not problems
                   and isinstance(reparsed.get("traceEvents"), list)
                   and len(reparsed["traceEvents"]) > 0)
    record["trace_json_path"] = trace_path
    record["trace_events"] = len(trace["traceEvents"])
    record["trace_valid"] = trace_valid
    if problems:
        record["trace_problems"] = problems[:5]
    stage("done", overhead_pct=record["value"])
    return record


def main(argv=None) -> None:
    """``python -m deeplearning_cfn_tpu.bench``: run one preset and print
    the contract JSON line (root ``bench.py`` runs the flagship case)."""
    import argparse
    import json

    parser = argparse.ArgumentParser(description=__doc__)
    parser.add_argument("--preset", default="imagenet_resnet50")
    parser.add_argument("--steps", type=int, default=30)
    parser.add_argument("--warmup", type=int, default=5)
    parser.add_argument("--global-batch", type=int, default=0)
    parser.add_argument("--with-input", action="store_true",
                        help="also time steps with the host input pipeline "
                             "in the loop (value_with_input)")
    parser.add_argument("--step-window", type=int, default=1,
                        help="fuse K steps per dispatch (bench the "
                             "train-loop fast path's scan program)")
    parser.add_argument("--obs-smoke", action="store_true",
                        help="measure obs span overhead (instrumented vs "
                             "disabled step time) instead of throughput")
    args = parser.parse_args(argv)
    stage("start", preset=args.preset)
    if args.obs_smoke:
        record = run_obs_overhead_smoke(
            preset=args.preset, steps=args.steps, warmup=args.warmup,
            global_batch=args.global_batch)
    else:
        record = run_bench(preset=args.preset, steps=args.steps,
                           warmup=args.warmup,
                           global_batch=args.global_batch,
                           include_input=args.with_input,
                           step_window=args.step_window)
    print(json.dumps(record), flush=True)


if __name__ == "__main__":
    main()
