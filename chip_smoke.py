#!/usr/bin/env python3
"""The quickest proof that the system still starts on the chip.

Drives the repository's main path once on a TPU, through the entry points a
user calls (``cli.main`` train/serve, the ``Trainer``, ``fused_attention``),
at the full width of models the repo ships, in ONE process (a chip belongs
to one process at a time):

1. ``resnet50_train`` — ``imagenet_resnet50`` (50 layers, 224x224, 1000
   classes, bf16, LARS) on synthetic data at the per-chip batch the repo's
   bench uses: a few steps, a committed checkpoint, then the same command
   with more steps, which must resume from that checkpoint.
2. ``nmt_train`` / ``nmt_serve`` — ``transformer_nmt_wmt`` at its
   transformer-base widths: a few steps and a checkpoint, then ``serve``
   over that checkpoint with the paged KV pool and the fused decode window,
   and again with ``--decode-window 1``; the greedy tokens of the two runs
   are compared.
3. ``flash_attention`` — the Pallas kernel against the reference, forward
   and ``jax.grad``, at the shapes ``gpt_small_lm`` and
   ``bert_long_wikipedia`` produce; and ``implementation="auto"`` must
   lower to the kernel.

``--chips 4`` runs, and only runs, the path across chips: BERT-base on a
``data=2 x model=2`` mesh against the same seeded steps on one device, then
``bert_long_wikipedia`` with ring attention over ``data=2 x seq=2`` against
the dense ``seq=1`` run.

Each phase prints one JSON line when it ends. A phase that fails prints its
error and the script exits non-zero at once. The last line of stdout is
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``.
Numbers on earlier lines (compile seconds, step times) are diagnostics, not
measurements. Without a TPU the script exits non-zero before any phase; it
never selects a platform itself. What the entry points print goes to
``<out>/logs``; everything is generated from ``--seed``.
"""

from __future__ import annotations

import argparse
import contextlib
import json
import math
import os
import shutil
import sys
import time

REPO_ROOT = os.path.dirname(os.path.abspath(__file__))

# One chip: a per-chip batch for each preset.
RESNET_OVERRIDES = (
    "data.synthetic=true", "train.global_batch=512",
    # A few batches, not the default 8192-image (5 GB) synthetic set.
    "data.num_train_examples=2048", "data.num_eval_examples=512",
    "train.log_every_steps=1", "checkpoint.every_steps=3",
)
NMT_OVERRIDES = (
    "data.synthetic=true", "train.global_batch=64",
    "data.num_train_examples=256", "data.num_eval_examples=64",
    "train.log_every_steps=1",
    # Crosses the accelerator side of trainer.py's accumulation branch.
    "train.grad_accum_steps=2",
    # The preset's rsqrt schedule clips its 4000-step warm-up to
    # total_steps - 1, which over a handful of steps is a learning rate
    # near 0.5. A constant 1e-5 is what the first steps of the real
    # warm-up see, and it leaves the weights near their seeded init: a few
    # steps at a larger rate teach the model the most frequent target
    # token and nothing else, and every request then ends at its first
    # token (EOS), which serves nothing.
    "schedule.name=constant", "schedule.base_lr=0.00001",
    "schedule.warmup_steps=0",
)
# The one beam request of the served trace: its width and its budget.
BEAM_WIDTH, BEAM_BUDGET = 4, 4
# [B, H, S, D] and causal, as the two presets produce them on one chip.
FLASH_SHAPES = (
    ("gpt_small_lm", (16, 12, 1024, 64), True),
    ("bert_long_wikipedia", (8, 12, 4096, 64), False),
)
# The repo's own bf16 tolerances (tests/test_ops.py): forward, gradient.
FLASH_TOL = (3e-2, 5e-2)
# A window-4 / window-1 disagreement whose top-2 logit margin, as a share
# of the largest |logit|, is under this is an argmax tie in bf16.
TIE_MARGIN = 0.03

# Four chips.
BERT_TP_OVERRIDES = (
    "data.synthetic=true", "train.global_batch=32",
    "data.num_train_examples=128", "data.num_eval_examples=32",
    "train.shard_opt_state=false", "data.prefetch=0",
)
BERT_LONG_OVERRIDES = (
    "data.synthetic=true", "train.global_batch=4",
    "data.num_train_examples=16", "data.num_eval_examples=4",
    "train.shard_opt_state=false", "data.prefetch=0",
)
MESH_LOSS_RTOL = 2e-3


def require_tpu():
    """The devices, or exit: this script proves the chip path and nothing
    else. It never sets JAX_PLATFORMS itself."""
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        sys.exit(f"chip_smoke: needs a TPU, but jax's default backend is "
                 f"{devices[0].platform!r} ({len(devices)} device(s)); "
                 f"nothing was run")
    return devices


class Phases:
    """Runs the phases and prints their lines. Each line carries what jax's
    persistent compile cache was asked and what it answered during the
    phase, as jax itself reports it."""

    EVENTS = {"/jax/compilation_cache/compile_requests_use_cache": "requests",
              "/jax/compilation_cache/cache_hits": "hits"}

    def __init__(self):
        import jax

        self.cache = {"requests": 0, "hits": 0}
        jax.monitoring.register_event_listener(self._count)

    def _count(self, event, **_):
        if event in self.EVENTS:
            self.cache[self.EVENTS[event]] += 1

    def run(self, name, fn, *args, **kwargs):
        """Run one phase and print its line. A failure prints its line too
        and then propagates: the script stops there, non-zero."""
        t0 = time.perf_counter()
        before = dict(self.cache)
        try:
            info = fn(*args, **kwargs)
        except BaseException as e:
            print(json.dumps({
                "phase": name, "ok": False,
                "seconds": round(time.perf_counter() - t0, 1),
                "error": f"{type(e).__name__}: {e}"[:2000]}), flush=True)
            raise
        print(json.dumps({
            "phase": name, "ok": True,
            "seconds": round(time.perf_counter() - t0, 1), **info,
            "compile_cache": {k: v - before[k]
                              for k, v in self.cache.items()}}), flush=True)
        return info


def check(cond, message):
    if not cond:
        raise RuntimeError(message)


def cli(argv, log_path):
    """``dlcfn-tpu <argv>`` in this process, its stdout appended to
    ``log_path``; returns that output."""
    from deeplearning_cfn_tpu.cli.main import main

    os.makedirs(os.path.dirname(log_path), exist_ok=True)
    with open(log_path, "a+") as log:
        start = log.tell()
        with contextlib.redirect_stdout(log):
            rc = main(list(argv))
        log.flush()
        log.seek(start)
        out = log.read()
    check(rc == 0, f"`{' '.join(argv[:3])} ...` exited {rc}; see {log_path}")
    return out


def read_jsonl(path):
    with open(path) as fh:
        return [json.loads(ln) for ln in fh if ln.strip()]


def fresh_dir(path):
    """An empty directory of this run's own: a checkpoint left by an
    earlier run would turn the first train into a resume."""
    shutil.rmtree(path, ignore_errors=True)
    os.makedirs(path)
    return path


def train_and_resume(out, preset, overrides, steps, more_steps):
    """``train`` for ``steps``, then the same command for ``more_steps``:
    init, compile, step, metrics JSONL, save, restore."""
    workdir = fresh_dir(os.path.join(out, preset))
    log = os.path.join(out, "logs", f"{preset}.log")
    argv = ["train", "--preset", preset, f"workdir={workdir}", *overrides]
    cli(argv + [f"train.steps={steps}"], log)
    run_dir = os.path.join(workdir, preset)
    commit = os.path.join(run_dir, "ckpt", f"step_{steps:08d}", "COMMIT")
    check(os.path.exists(commit), f"no committed checkpoint at {commit}")
    resumed = cli(argv + [f"train.steps={more_steps}"], log)
    check(f"resumed from step {steps}" in resumed,
          f"the second run did not resume from step {steps}; see {log}")
    records = read_jsonl(os.path.join(run_dir, "metrics.jsonl"))
    steps_logged = [r for r in records if "loss" in r and "step" in r]
    losses = {int(r["step"]): float(r["loss"]) for r in steps_logged}
    check(sorted(losses) == list(range(1, more_steps + 1)),
          f"expected a loss for every step 1..{more_steps}, got "
          f"{sorted(losses)}")
    check(all(math.isfinite(v) for v in losses.values()),
          f"non-finite loss: {losses}")
    prefix = "final_eval_"
    finals = [{k[len(prefix):]: v for k, v in r.items()
               if k.startswith(prefix)} for r in records]
    finals = [r for r in finals if r]
    check(len(finals) == 2 and all(
        math.isfinite(v) for r in finals for v in r.values()),
        f"final evals: {finals}")
    compiles = [r["compile_s"] for r in steps_logged if "compile_s" in r]
    step_times = sorted(r["step_time_s"] for r in steps_logged
                        if "step_time_s" in r)
    return {
        "preset": preset, "steps": more_steps, "resumed_from": steps,
        "loss_first": losses[1], "loss_last": losses[more_steps],
        "compile_s": [round(c, 1) for c in compiles],
        "step_time_s_median": round(step_times[len(step_times) // 2], 4),
        "final_eval": finals[-1], "workdir": workdir,
    }


def make_trace(path, seed, n_greedy, vocab_size, max_src_len):
    """One beam request with a short budget, then ``n_greedy`` greedy
    requests of mixed source length, from ``seed``. With a slot for every
    row, all are admitted in the first tick: the few ticks that hold the
    beam take the engine's host path, and from then on the queue is empty
    and the greedy rows decode in fused windows (``Engine._plan_window``),
    whichever of them ends early."""
    import numpy as np

    from deeplearning_cfn_tpu.models.decoding import EOS_ID

    rng = np.random.RandomState(seed)
    lengths = [max_src_len // 2] + list(
        np.linspace(3, max_src_len - 1, n_greedy).astype(int))
    with open(path, "w") as fh:
        for i, n in enumerate(lengths):
            rec = {"id": f"q{i}", "src_ids": [
                int(t) for t in rng.randint(4, vocab_size, size=n)]
                + [EOS_ID]}
            if i == 0:
                rec.update(beam_size=BEAM_WIDTH, max_new_tokens=BEAM_BUDGET)
            fh.write(json.dumps(rec) + "\n")
    return len(lengths)


def serve_once(out, overrides, trace_path, window, serve_flags, tag):
    metrics_path = os.path.join(out, "serve", f"metrics_{tag}.jsonl")
    if os.path.exists(metrics_path):
        os.unlink(metrics_path)
    printed = cli(
        ["serve", "--preset", "transformer_nmt_wmt",
         "--requests", trace_path, "--decode-window", str(window),
         "--metrics-path", metrics_path, *serve_flags, *overrides],
        os.path.join(out, "logs", f"serve_{tag}.log"))
    results = {}
    for ln in printed.splitlines():
        if ln.startswith("{"):
            rec = json.loads(ln)
            results[rec["id"]] = rec
    return results, read_jsonl(metrics_path)[-1]


def divergence_margins(overrides, cases):
    """For each ``(src_ids, prefix)``: the top-2 logit margin at the
    position after ``prefix``, from a teacher-forced pass of the served
    checkpoint — (margin, largest |logit|, top-2 token ids)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from deeplearning_cfn_tpu.config import apply_overrides
    from deeplearning_cfn_tpu.models.decoding import BOS_ID, PAD_ID
    from deeplearning_cfn_tpu.presets import get_preset
    from deeplearning_cfn_tpu.serve.loader import load_engine

    cfg = apply_overrides(get_preset("transformer_nmt_wmt"), list(overrides))
    engine, _, _ = load_engine(cfg, capacity=1)
    model, variables = engine.model, engine.variables
    # One padded shape for every case, so one compile. The decoder is
    # causal: position len(prefix) sees only BOS + prefix.
    s_len = engine.max_src_len
    t_len = max(len(prefix) for _, prefix in cases) + 1

    @jax.jit
    def logits_at(src, mask, tgt_in, at):
        enc = model.apply(variables, src, mask, method=type(model).encode)
        logits = model.apply(variables, tgt_in, enc, mask,
                             method=type(model).decode)
        return logits[0, at].astype(jnp.float32)

    out = []
    for src_ids, prefix in cases:
        src = np.full((1, s_len), PAD_ID, np.int32)
        src[0, :len(src_ids)] = src_ids
        tgt_in = np.full((1, t_len), PAD_ID, np.int32)
        tgt_in[0, :len(prefix) + 1] = [BOS_ID] + list(prefix)
        last = np.asarray(logits_at(src, (src != PAD_ID).astype(np.int32),
                                    tgt_in, len(prefix)))
        top2 = np.argsort(last)[-2:][::-1]
        out.append((float(last[top2[0]] - last[top2[1]]),
                    float(np.abs(last).max()), [int(t) for t in top2]))
    return out


def nmt_serve(out, overrides, seed, n_greedy=8, max_new_tokens=24):
    """Serve one trace through the paged pool with the fused window, then
    with window 1, and compare the greedy tokens."""
    serve_flags = ("--slots", str(n_greedy + BEAM_WIDTH),
                   "--max-new-tokens", str(max_new_tokens),
                   "--kv-block-size", "16")
    from deeplearning_cfn_tpu.config import apply_overrides
    from deeplearning_cfn_tpu.presets import get_preset

    cfg = apply_overrides(get_preset("transformer_nmt_wmt"), list(overrides))
    fresh_dir(os.path.join(out, "serve"))
    trace_path = os.path.join(out, "serve", "trace.jsonl")
    n = make_trace(trace_path, seed, n_greedy, cfg.data.vocab_size,
                   cfg.data.seq_len)
    trace = {r["id"]: r for r in read_jsonl(trace_path)}
    fused, snap = serve_once(out, overrides, trace_path, 4, serve_flags,
                             "window4")
    plain, snap1 = serve_once(out, overrides, trace_path, 1, serve_flags,
                              "window1")
    for tag, results in (("window 4", fused), ("window 1", plain)):
        check(sorted(results) == sorted(trace),
              f"{tag}: {len(results)} results for {n} requests")
        # A first token was produced for each (ttft); the printed tokens
        # stop before EOS, so a request may be done and print none.
        bad = {i: r["state"] for i, r in results.items()
               if r["state"] != "done" or r["ttft_s"] is None}
        check(not bad, f"{tag}: not done, or no first token: {bad}")
        check(any(r["tokens"] for r in results.values()),
              f"{tag}: every request printed an empty token list")
    check(snap["serve_completed"] == n and snap1["serve_completed"] == n,
          f"completed {snap['serve_completed']}/{snap1['serve_completed']}"
          f" of {n}")
    # The paged pool and the fused window were really used.
    check(snap["serve_kv_blocks_total"] > 0
          and snap["serve_kv_block_utilization"] > 0,
          f"paged KV pool unused: {snap}")
    check(snap["serve_decode_windows"] > 0
          and snap["serve_steps_per_window"] >= 2.0,
          f"fused decode window hardly used: steps/window "
          f"{snap['serve_steps_per_window']}")
    check(snap1["serve_steps_per_window"] == 1.0,
          f"--decode-window 1 fused steps: {snap1['serve_steps_per_window']}")

    greedy = [i for i, r in trace.items() if "beam_size" not in r]
    same = [i for i in greedy if fused[i]["tokens"] == plain[i]["tokens"]]
    info = {
        "requests": n, "done": n,
        "tokens_served": snap["serve_tokens_generated"],
        "tokens_per_request": [len(fused[i]["tokens"]) for i in trace],
        "kv_blocks_total": snap["serve_kv_blocks_total"],
        "kv_block_utilization": round(snap["serve_kv_block_utilization"], 4),
        "decode_windows": snap["serve_decode_windows"],
        "steps_per_window": round(snap["serve_steps_per_window"], 3),
        "window4_vs_window1_identical": f"{len(same)}/{len(greedy)}",
    }
    differing = [i for i in greedy if i not in same]
    if differing:
        # Not passed over in silence: say where each one parts, and
        # whether the two candidates there were a tie in bf16.
        firsts = []
        for rid in differing:
            a, b = fused[rid]["tokens"], plain[rid]["tokens"]
            at = next((k for k, (x, y) in enumerate(zip(a, b)) if x != y),
                      min(len(a), len(b)))
            firsts.append((rid, at, a, b))
        margins = divergence_margins(
            overrides, [(trace[rid]["src_ids"], a[:at])
                        for rid, at, a, _ in firsts])
        info["divergences"] = [{
            "request": rid, "position": at,
            "window4_token": a[at] if at < len(a) else None,
            "window1_token": b[at] if at < len(b) else None,
            "top2_tokens": top2, "top2_logit_margin": round(margin, 5),
            "max_abs_logit": round(scale, 3),
            "tie": margin <= TIE_MARGIN * scale,
        } for (rid, at, a, b), (margin, scale, top2)
            in zip(firsts, margins)]
        check(all(d["tie"] for d in info["divergences"]),
              f"window 4 and window 1 disagree beyond a bf16 argmax tie: "
              f"{info['divergences']}")
    return info


def flash_attention(shapes=FLASH_SHAPES, kernel="pallas", seed=0,
                    expect_auto_kernel=True):
    """The kernel against the reference, forward and grad; and ``auto``
    must lower to the kernel at these shapes."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from deeplearning_cfn_tpu.ops.attention import fused_attention

    def loss(fn):
        # A fixed random cotangent, so that the gradient check does not
        # reduce to that of a plain sum.
        return lambda q, k, v, w: jnp.sum(
            fn(q, k, v).astype(jnp.float32) * w)

    def rel_err(a, b):
        a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
        return float(np.abs(a - b).max() / max(np.abs(b).max(), 1e-6))

    rows = []
    for name, (b, h, s, d), causal in shapes:
        keys = jax.random.split(jax.random.PRNGKey(seed), 4)
        q, k, v = (jax.random.normal(kk, (b, h, s, d), jnp.bfloat16)
                   for kk in keys[:3])
        w = jax.random.normal(keys[3], (b, h, s, d), jnp.float32)

        def attn(impl):
            return lambda q, k, v: fused_attention(
                q, k, v, causal=causal, implementation=impl)

        t0 = time.perf_counter()
        out_k = jax.jit(attn(kernel))(q, k, v)
        grads_k = jax.jit(jax.grad(loss(attn(kernel)), argnums=(0, 1, 2)))(
            q, k, v, w)
        jax.block_until_ready((out_k, grads_k))
        kernel_s = time.perf_counter() - t0
        # The reference holds [B, H, S, S] f32 scores (6.4 GB at once for
        # bert_long, several times that in its backward): batch elements
        # are independent, so it runs under 1 GiB of scores at a time.
        chunk = max(1, min(b, (1 << 30) // (h * s * s * 4)))
        ref_fwd = jax.jit(attn("reference"))
        ref_grad = jax.jit(jax.grad(loss(attn("reference")),
                                    argnums=(0, 1, 2)))
        outs, grads = [], []
        for i in range(0, b, chunk):
            sl = slice(i, i + chunk)
            outs.append(ref_fwd(q[sl], k[sl], v[sl]))
            grads.append(ref_grad(q[sl], k[sl], v[sl], w[sl]))
        out_r = jnp.concatenate(outs)
        grads_r = [jnp.concatenate(g) for g in zip(*grads)]
        fwd_err = rel_err(out_k, out_r)
        grad_err = max(rel_err(a, r) for a, r in zip(grads_k, grads_r))
        check(np.isfinite(np.asarray(out_k, np.float32)).all(),
              f"{name}: non-finite kernel output")
        check(fwd_err <= FLASH_TOL[0] and grad_err <= FLASH_TOL[1],
              f"{name} {(b, h, s, d)}: kernel vs reference forward "
              f"{fwd_err:.4f} (tol {FLASH_TOL[0]}), grad {grad_err:.4f} "
              f"(tol {FLASH_TOL[1]})")
        row = {"shape": [b, h, s, d], "causal": causal,
               "fwd_rel_err": round(fwd_err, 5),
               "grad_rel_err": round(grad_err, 5),
               "kernel_compile_and_run_s": round(kernel_s, 1)}
        if expect_auto_kernel:
            # _auto_use_pallas decides from jax.default_backend(): a branch
            # that quietly took the reference path on the TPU shows here.
            for what, fn, args in (
                    ("forward", attn("auto"), (q, k, v)),
                    ("grad", jax.grad(loss(attn("auto")), argnums=(0, 1, 2)),
                     (q, k, v, w))):
                text = jax.jit(fn).lower(*args).compile().as_text()
                check("tpu_custom_call" in text,
                      f"{name}: implementation='auto' {what} did not lower "
                      f"to the Pallas kernel on "
                      f"{jax.default_backend()}")
            row["auto_lowers_to_kernel"] = True
        rows.append({"preset": name, **row})
    return {"kernel": kernel, "tolerance_fwd_grad": list(FLASH_TOL),
            "shapes": rows}


# -- the path across chips ---------------------------------------------------


def train_steps(cfg, mesh, n_steps):
    """``n_steps`` seeded steps of ``cfg`` on ``mesh`` through the normal
    task / state / Trainer path: (losses, state, first device batch, the
    step program as lowered)."""
    import jax

    from deeplearning_cfn_tpu.data import build_pipeline
    from deeplearning_cfn_tpu.parallel.mesh import local_batch_size
    from deeplearning_cfn_tpu.train import create_train_state
    from deeplearning_cfn_tpu.train.optim import (build_optimizer,
                                                  build_schedule)
    from deeplearning_cfn_tpu.train.task import build_task
    from deeplearning_cfn_tpu.train.trainer import Trainer

    gb = cfg.train.global_batch
    task = build_task(cfg, mesh=mesh)
    tx = build_optimizer(cfg.optimizer,
                         build_schedule(cfg.schedule, 1000, gb, 100))
    state = create_train_state(
        jax.random.PRNGKey(cfg.train.seed), task.init, tx, mesh,
        param_rules=getattr(task, "param_rules", ()),
        shard_opt_state=cfg.train.shard_opt_state)
    trainer = Trainer(cfg, task.loss_fn, tx, mesh=mesh)
    pipe = build_pipeline(cfg.data, local_batch_size(gb, mesh),
                          cfg.model.num_classes, seed=cfg.train.seed,
                          train=True)
    batches = iter(pipe.one_epoch(0))
    rng = jax.random.PRNGKey(1)
    losses, first = [], None
    for _ in range(n_steps):
        batch = trainer.device_batch(next(batches))
        first = batch if first is None else first
        state, metrics = trainer.train_step(state, batch, rng)
        losses.append(float(metrics["loss"]))
    check(all(math.isfinite(v) for v in losses), f"losses {losses}")
    return losses, state, first, \
        trainer.train_step.lower(state, first, rng).as_text()


def losses_agree(a, b, what):
    worst = max(abs(x - y) / max(abs(y), 1e-6) for x, y in zip(a, b))
    check(worst <= MESH_LOSS_RTOL,
          f"{what}: per-step losses {a} vs {b} differ by {worst:.4f} "
          f"(tolerance {MESH_LOSS_RTOL})")
    return round(worst, 5)


def preset_on(preset, overrides, mesh_cfg):
    from deeplearning_cfn_tpu.config import apply_overrides
    from deeplearning_cfn_tpu.presets import get_preset

    cfg = apply_overrides(get_preset(preset), list(overrides))
    cfg.mesh = mesh_cfg
    return cfg


def mesh_dp_tp(devices, overrides=BERT_TP_OVERRIDES, n_steps=3):
    """BERT-base on data=2 x model=2 against the same steps on one device."""
    import jax
    # The sharding assertion the multi-chip dry run already makes on CPU.
    from __graft_entry__ import _count_axis_sharded as count_axis_sharded

    from deeplearning_cfn_tpu.config import MeshConfig
    from deeplearning_cfn_tpu.parallel.mesh import build_mesh

    def cfg_for(mesh_cfg):
        return preset_on("bert_base_wikipedia", overrides, mesh_cfg)

    mesh_cfg = MeshConfig(data=2, model=2)
    mesh = build_mesh(mesh_cfg, devices=devices)
    check(len({d.id for d in mesh.devices.flat}) == 4,
          f"the mesh does not hold four distinct devices: {mesh.devices}")
    losses, state, batch, _ = train_steps(cfg_for(mesh_cfg), mesh, n_steps)
    n_model = count_axis_sharded(state.params, "model")
    # PARAM_RULES: q, k, v, out and the two MLP kernels of every layer.
    want = 6 * cfg_for(mesh_cfg).model.kwargs["num_layers"]
    check(n_model >= want,
          f"expected the PARAM_RULES kernels sharded over 'model' (>= "
          f"{want} leaves), got {n_model}")
    check(count_axis_sharded(batch, "data") == len(batch),
          "the batch is not sharded over 'data'")
    in_use = [(d.memory_stats() or {}).get("bytes_in_use") for d in devices]
    if devices[0].platform == "tpu":
        # Code that has never seen more than one chip may put everything
        # on the first. (The CPU reports no memory statistics.)
        check(all(b and b > (64 << 20) for b in in_use),
              f"bytes_in_use per device: {in_use}")
    del state, batch
    one = build_mesh(MeshConfig(data=1), devices=devices[:1])
    ref_losses = train_steps(cfg_for(MeshConfig(data=1)), one, n_steps)[0]
    return {"preset": "bert_base_wikipedia", "mesh": "data=2 x model=2",
            "steps": n_steps, "losses": losses,
            "one_device_losses": ref_losses,
            "max_rel_diff": losses_agree(losses, ref_losses,
                                         "data=2 x model=2 vs one device"),
            "tolerance": MESH_LOSS_RTOL,
            "model_sharded_leaves": n_model,
            "bytes_in_use_per_device": in_use,
            "jax_devices": len(jax.devices())}


def mesh_ring(devices, overrides=BERT_LONG_OVERRIDES, n_steps=2):
    """``bert_long_wikipedia`` with ring attention over data=2 x seq=2
    (ppermute over the chips' interconnect) against the dense seq=1 run."""
    from deeplearning_cfn_tpu.config import MeshConfig
    from deeplearning_cfn_tpu.parallel.mesh import build_mesh

    def cfg_for(mesh_cfg):
        return preset_on("bert_long_wikipedia", overrides, mesh_cfg)

    mesh_cfg = MeshConfig(data=2, seq=2)
    mesh = build_mesh(mesh_cfg, devices=devices)
    losses, _, _, program = train_steps(cfg_for(mesh_cfg), mesh, n_steps)
    # SeqParallelAttention quietly runs dense attention when the mesh is
    # not threaded through to it: the ring's K/V rotation must be there.
    check("collective_permute" in program,
          "ring attention fell back to dense: no collective_permute in "
          "the lowered train step")
    one = build_mesh(MeshConfig(data=1), devices=devices[:1])
    ref_losses = train_steps(cfg_for(MeshConfig(data=1)), one, n_steps)[0]
    return {"preset": "bert_long_wikipedia", "mesh": "data=2 x seq=2",
            "seq_impl": "ring", "steps": n_steps, "losses": losses,
            "dense_seq1_losses": ref_losses,
            "max_rel_diff": losses_agree(losses, ref_losses,
                                         "ring seq=2 vs dense seq=1"),
            "tolerance": MESH_LOSS_RTOL}


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--chips", type=int, default=1, choices=(1, 4),
                        help="4 runs the path across chips and nothing else")
    parser.add_argument("--out", default=os.path.join(REPO_ROOT,
                                                      "chip_smoke_out"),
                        help="the one directory this run writes to")
    parser.add_argument("--seed", type=int, default=0)
    args = parser.parse_args(argv)

    devices = require_tpu()
    check(len(devices) >= args.chips,
          f"--chips {args.chips} but jax found {len(devices)} device(s)")
    devices = devices[:args.chips]
    out = os.path.abspath(args.out)
    os.makedirs(out, exist_ok=True)

    import jax

    from deeplearning_cfn_tpu import dataio
    from deeplearning_cfn_tpu.runtime.platform import configure_compile_cache

    cache_dir = configure_compile_cache()
    phases = Phases()

    def cache_entries():
        return len(os.listdir(cache_dir)) if os.path.isdir(cache_dir) else 0

    phases.run("start", lambda: {
        "jax": jax.__version__, "platform": devices[0].platform,
        "device_kind": devices[0].device_kind, "devices": len(devices),
        "native_loader": dataio.status(),
        "compile_cache_dir": cache_dir,
        "compile_cache_entries_before": cache_entries(), "out": out})

    if args.chips == 4:
        phases.run("mesh_dp_tp", mesh_dp_tp, devices)
        phases.run("mesh_ring", mesh_ring, devices)
    else:
        phases.run("resnet50_train", train_and_resume, out,
                   "imagenet_resnet50", RESNET_OVERRIDES, 6, 9)
        nmt = phases.run("nmt_train", train_and_resume, out,
                         "transformer_nmt_wmt", NMT_OVERRIDES, 3, 5)
        phases.run("nmt_serve", nmt_serve, out,
                   (f"workdir={nmt['workdir']}",), args.seed)
        phases.run("flash_attention", flash_attention, seed=args.seed)

    phases.run("compile_cache", lambda: {
        "compile_cache_dir": cache_dir,
        "compile_cache_entries_after": cache_entries(),
        "requests_total": phases.cache["requests"],
        "hits_total": phases.cache["hits"]})
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(jax.devices())}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
