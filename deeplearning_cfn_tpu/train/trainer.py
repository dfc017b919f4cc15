"""The sharded trainer — the rebuild's canonical hot loop (SURVEY.md §4.4).

Reference equivalents replaced here:
- Horovod path (§4.2): per-GPU process, ``hvd.DistributedOptimizer`` wrapping
  grads in a background-thread NCCL allreduce, ``BroadcastGlobalVariablesHook``.
- KVStore path (§4.3): ``kvstore.push(grads) → server aggregates → pull``.

Both become ONE jit-compiled program per step: forward, backward, gradient
psum over ICI (inserted by XLA because the batch dim is sharded over the
'data' mesh axis and the loss is a global mean), optimizer update — with zero
host round-trips inside the step, donated buffers, and async dispatch so the
input pipeline overlaps device compute.
"""

from __future__ import annotations

import contextlib
import time
from typing import Any, Callable, Dict, Iterator, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
import optax
from jax.sharding import Mesh

from ..config import ExperimentConfig
from ..obs.trace import get_tracer, obs_enabled, span
from ..parallel.mesh import build_mesh, validate_batch
from ..parallel.sharding import batch_sharding, replicated
from ..runtime import jit_events
from .state import TrainState

PyTree = Any
Batch = Dict[str, np.ndarray]
LossFn = Callable[..., Tuple[jnp.ndarray, Dict[str, jnp.ndarray]]]

# What jax's events call the step: the name of the function
# ``Trainer._train_step_fn`` returns (``runtime/jit_events.py``).
STEP_FUN = "train_step"


class _LazyShardedJit:
    """jit the train step with ``out_shardings`` pinned to the INPUT
    state's layout (captured at first call, when concrete arrays with
    shardings exist). Without the constraint, GSPMD propagates a ZeRO-1
    sharded optimizer slot's layout through ``optax.apply_updates`` into
    the new params — silently partitioning weights that the pure-DP
    contract says stay replicated, and forcing a recompile at step 2 when
    the changed input layout comes back around. Exposes ``lower`` so AOT
    callers (the bench) keep working."""

    def __init__(self, fn, donate_argnums):
        self._fn = fn
        self._donate = donate_argnums
        self._jitted = None

    def _ensure(self, state):
        if self._jitted is None:
            state_sh = jax.tree_util.tree_map(
                lambda leaf: leaf.sharding
                if isinstance(leaf, jax.Array) else None, state)
            self._jitted = jax.jit(
                self._fn, donate_argnums=self._donate,
                out_shardings=(state_sh, None))
        return self._jitted

    def __call__(self, state, batch, rng):
        return self._ensure(state)(state, batch, rng)

    def lower(self, state, batch, rng):
        return self._ensure(state).lower(state, batch, rng)


class Trainer:
    """Owns the compiled train/eval steps and the step loop.

    Parameters
    ----------
    loss_fn:
        ``loss_fn(params, batch_stats, batch, rng, train) -> (loss, aux)``
        where ``aux`` is a dict of scalar metrics plus (when training) a
        ``"batch_stats"`` entry with updated BN stats and, where the model
        sows any, a ``"nudges"`` entry: steps for parameters that no
        gradient moves, added after the optimizer's
        (``TrainState.apply_gradients``). The loss must be a
        global-batch mean — that is what makes the compiler's psum correct.
        It is what is differentiated and, unless ``aux`` has a ``"loss"``
        of its own, what a step reports as ``loss`` (a task whose objective
        has a second term reports its first there: ``CausalLmTask`` under an
        indexer's loss).
    """

    def __init__(
        self,
        cfg: ExperimentConfig,
        loss_fn: LossFn,
        tx,
        mesh: Optional[Mesh] = None,
        spatial_dim: Optional[int] = None,
        spatial_keys: Optional[Tuple[str, ...]] = None,
        donate: bool = True,
        eval_derived: Optional[Dict[str, Callable[[Dict[str, float]],
                                                  float]]] = None,
    ):
        self.cfg = cfg
        self.loss_fn = loss_fn
        self.tx = tx
        self.mesh = mesh if mesh is not None else build_mesh(cfg.mesh)
        validate_batch(cfg.train.global_batch, self.mesh)
        accum = cfg.train.grad_accum_steps
        if accum > 1 and cfg.train.global_batch % accum != 0:
            raise ValueError(
                f"global batch {cfg.train.global_batch} must be divisible "
                f"by grad_accum_steps ({accum})")
        if accum > 1:
            # Each microbatch must still split over the data ways.
            validate_batch(cfg.train.global_batch // accum, self.mesh)
        if cfg.train.grad_accum_unroll not in ("auto", "scan", "unroll"):
            # Validated here, unconditionally — not in the accum-only step
            # builder, where a typo'd value would stay silent until
            # grad_accum_steps is later raised above 1.
            raise ValueError(
                f"train.grad_accum_unroll must be auto|scan|unroll, got "
                f"{cfg.train.grad_accum_unroll!r}")
        if cfg.train.device_prefetch < 0:
            raise ValueError(
                f"train.device_prefetch must be >= 0, got "
                f"{cfg.train.device_prefetch}")
        self.spatial_dim = spatial_dim
        # Which batch keys the spatial shard applies to (None = any array
        # with >=4 dims). Detection restricts it to "image" — its mask
        # targets are also 4-D but their dim 1 is a box count, not height.
        self.spatial_keys = spatial_keys
        self._train_step = None
        self._eval_step = None
        self._donate = donate
        # Post-aggregation metric transforms (task.eval_derived): computed
        # from the EXACT cross-batch aggregates, for metrics that are a
        # nonlinear function of a mean — perplexity = exp(mean CE) is not
        # the mean of per-batch exp(CE) (Jensen), so it cannot be a
        # per-batch eval metric.
        self.eval_derived = dict(eval_derived or {})
        # The start of this trainer's life, told apart from the rest of it:
        # until its first step is synced, a trace or a compile of the step
        # is that step's own (``train.first_step``); after it, a retrace.
        jit_events.install()
        self._first_synced = False
        self._retrace: Optional[list] = None  # [t0, t1, jax's s] if open
        self._retraces_unreported = 0

    # -- sharding helpers ---------------------------------------------------

    def _spatial_for(self, key: str, ndim: int) -> Optional[int]:
        if ndim < 4 or self.spatial_dim is None:
            return None
        if self.spatial_keys is not None and key not in self.spatial_keys:
            return None
        return self.spatial_dim

    def batch_shardings(self, batch: Batch):
        return {
            k: batch_sharding(self.mesh, np.ndim(v),
                              self._spatial_for(k, np.ndim(v)))
            for k, v in batch.items()
        }

    def device_batch(self, batch: Batch, global_batch: Optional[int] = None):
        """Stitch per-process host arrays into globally-sharded jax.Arrays."""
        gb = global_batch or self.cfg.train.global_batch
        out = {}
        for k, v in batch.items():
            sh = batch_sharding(self.mesh, v.ndim,
                                self._spatial_for(k, v.ndim))
            global_shape = (gb,) + tuple(v.shape[1:])
            if jax.process_count() == 1:
                out[k] = jax.device_put(v, sh)
            else:
                out[k] = jax.make_array_from_process_local_data(
                    sh, v, global_shape
                )
        return out

    # -- compiled steps -----------------------------------------------------

    def _train_step_fn(self):
        """The raw (unjitted) per-step function. ``fold_in(rng,
        state.step)`` keys the step's randomness off the state's own
        counter, so a run resumed from a checkpoint, or ``fit`` called
        twice, draws the streams one uninterrupted run draws."""
        tx = self.tx
        loss_fn = self.loss_fn
        ema_decay = self.cfg.train.ema_decay
        accum = self.cfg.train.grad_accum_steps

        def grads_and_metrics(state, batch, step_rng):
            def compute(params):
                return loss_fn(params, state.batch_stats, batch,
                               step_rng, True)

            (loss, aux), grads = jax.value_and_grad(compute, has_aux=True)(
                state.params
            )
            new_stats = aux.pop("batch_stats", state.batch_stats)
            return grads, new_stats, {"loss": loss, **aux}

        def accum_grads_and_metrics(state, batch, step_rng):
            # Microbatch split is STRIDED along the batch dim (row i goes
            # to microbatch i % accum): per device this is a local
            # reshape+transpose of its contiguous shard — no cross-device
            # resharding — and batch rows are i.i.d., so the partition
            # choice is semantically free.
            #
            # Averaging contract (pinned by test_trainer.py's accum
            # equivalence test): microbatch means are averaged UNIFORMLY,
            # which is exactly DP-over-`accum`-more-devices semantics
            # (each device means its shard locally, psum-mean across).
            # For token-weighted losses with ragged masks this is NOT
            # bit-equal to accum=1 on the same global batch (that would
            # weight microbatches by their mask sums); matching the DP
            # contract is the deliberate choice — accum exists to emulate
            # a larger device count.
            def split(v):
                g = v.shape[0]
                return v.reshape(g // accum, accum, *v.shape[1:]) \
                        .swapaxes(0, 1)

            micro = jax.tree_util.tree_map(split, batch)

            def body(carry, xs):
                g_acc, stats, m_acc = carry
                i, mb = xs
                # Distinct dropout noise per microbatch.
                mb_rng = jax.random.fold_in(step_rng, i)

                def compute(params):
                    return loss_fn(params, stats, mb, mb_rng, True)

                (loss, aux), grads = jax.value_and_grad(
                    compute, has_aux=True)(state.params)
                new_stats = aux.pop("batch_stats", stats)
                metrics = {"loss": loss, **aux}
                g_acc = jax.tree_util.tree_map(jnp.add, g_acc, grads)
                m_acc = jax.tree_util.tree_map(jnp.add, m_acc, metrics)
                return (g_acc, new_stats, m_acc), None

            g0 = jax.tree_util.tree_map(jnp.zeros_like, state.params)
            # Probe the metric dict's structure abstractly to build the
            # scan carry's accumulator — a forward-only eval_shape of
            # loss_fn (tracing the backward too would double the abstract
            # trace cost just to read dict keys).
            _, aux_probe = jax.eval_shape(
                lambda p: loss_fn(
                    p, state.batch_stats,
                    jax.tree_util.tree_map(lambda v: v[0], micro),
                    step_rng, True),
                state.params)
            aux_probe = dict(aux_probe)
            aux_probe.pop("batch_stats", None)
            # Scalars, and the tree of `nudges` where the model sows any.
            m0 = {"loss": jnp.zeros((), jnp.float32),
                  **jax.tree_util.tree_map(
                      lambda v: jnp.zeros(v.shape, jnp.float32), aux_probe)}
            # "auto": unroll on CPU — XLA:CPU runs convs inside a while-
            # loop body ~10x slower than straight-line (measured r04:
            # 54.8 s/step scanned vs 4.9 s unrolled at identical flops);
            # keep the scan on accelerators, where accum exists to bound
            # memory and the loop body compiles well.
            mode = self.cfg.train.grad_accum_unroll
            unroll = accum if (
                mode == "unroll"
                or (mode == "auto" and jax.default_backend() == "cpu")
            ) else 1
            (g_sum, new_stats, m_sum), _ = jax.lax.scan(
                body, (g0, state.batch_stats, m0),
                (jnp.arange(accum), micro), unroll=unroll)
            inv = 1.0 / accum
            grads = jax.tree_util.tree_map(lambda g: g * inv, g_sum)
            metrics = jax.tree_util.tree_map(lambda v: v * inv, m_sum)
            return grads, new_stats, metrics

        def train_step(state: TrainState, batch: Batch, rng: jax.Array):
            # The scopes name what no flax module names, so that a trace
            # can give every device operation of the step to a section of
            # the program (docs/OBSERVABILITY.md lists them).
            with jax.named_scope("step_rng"):
                step_rng = jax.random.fold_in(rng, state.step)
            if accum > 1:
                grads, new_stats, metrics = accum_grads_and_metrics(
                    state, batch, step_rng)
            else:
                grads, new_stats, metrics = grads_and_metrics(
                    state, batch, step_rng)
            with jax.named_scope("optimizer"):
                new_state = state.apply_gradients(
                    grads, tx, ema_decay, metrics.pop("nudges", None))
                new_state = new_state.replace(batch_stats=new_stats)
                # Same implementation clip_by_global_norm uses, so the
                # logged norm matches the clipping decision.
                metrics["grad_norm"] = optax.global_norm(grads)
            return new_state, metrics

        return train_step

    def _build_train_step(self):
        donate = (0,) if self._donate else ()
        return _LazyShardedJit(self._train_step_fn(), donate)

    def _build_eval_step(self):
        loss_fn = self.loss_fn

        def eval_step(state: TrainState, batch: Batch):
            params = state.ema_params if state.ema_params is not None \
                else state.params
            loss, aux = loss_fn(params, state.batch_stats, batch, None, False)
            aux.pop("batch_stats", None)
            return {"loss": loss, **aux}

        return jax.jit(eval_step)

    @property
    def train_step(self):
        if self._train_step is None:
            self._train_step = self._build_train_step()
        return self._train_step

    @property
    def eval_step(self):
        if self._eval_step is None:
            self._eval_step = self._build_eval_step()
        return self._eval_step

    # -- loops --------------------------------------------------------------

    def fit(
        self,
        state: TrainState,
        train_iter: Iterator[Batch],
        num_steps: int,
        rng: jax.Array,
        eval_iter_fn: Optional[Callable[[], Iterator[Batch]]] = None,
        eval_every: int = 0,
        eval_steps: int = 0,
        hooks: Tuple[Callable[[int, TrainState, Dict[str, float]], None], ...] = (),
        log_every: int = 50,
        metrics_writer=None,
        start_step: Optional[int] = None,
        trace_dir: Optional[str] = None,
        trace_steps: int = 0,
    ) -> TrainState:
        """The step loop. Dispatches async; only syncs on metrics at
        ``log_every`` boundaries so device compute and host input prep overlap
        (the reference achieved this with MXNet/TF's async engines; here it is
        jax dispatch + explicit sync points). Each turn: next batch,
        dispatch, first-step sync, log boundary, hooks, eval.

        With ``train.device_prefetch`` d > 0, host batches are staged to
        device (``device_batch``) on a background thread, d deep, so
        host→device transfer overlaps the previous step's compute.

        The first dispatched step carries the step's trace, lowering and
        compile (or its load from the cache); the loop syncs on it and
        restarts the throughput window — so the first ``examples_per_sec``
        measures later steps only (a boundary with none yet omits the
        throughput keys). Its wall time, from before the first batch to the
        end of that sync, is the span record ``train.first_step`` (with
        jax's own seconds for the three, ``runtime/jit_events.py``) and,
        from the same two clock reads, the key ``compile_s`` of the first
        logged record: the first step's seconds, whatever the key's name
        says. Once this trainer's first step is synced, a trace or compile
        of the step is a retrace: counter ``train.step_retraces``, a
        ``train.retrace`` span record, ``retraces`` on the next record.

        ``trace_dir`` + ``trace_steps``: capture a jax.profiler trace of
        ``trace_steps`` hot-loop steps (skipping the first, compile-heavy
        step) — the Horovod-timeline role (SURVEY §6 tracing row).
        """
        from ..runtime.profiling import trace_steps as profiler_trace

        watchdog = None
        if self.cfg.train.hang_timeout_s > 0:
            from ..runtime.watchdog import StepWatchdog

            # First-compile happens inside the first sync window; give it
            # the same budget again on top.
            watchdog = StepWatchdog(
                self.cfg.train.hang_timeout_s,
                first_beat_grace_s=self.cfg.train.hang_timeout_s)

        step = int(state.step) if start_step is None else start_step
        trace_start = step + 1 if trace_dir and trace_steps > 0 else -1
        trace_stop = trace_start + trace_steps
        trace_stack = contextlib.ExitStack()  # owns start/stop (profiling.py)
        tracing = False
        window_start = time.perf_counter()
        window_examples = 0
        last_realized: Optional[Dict[str, float]] = None
        gb = self.cfg.train.global_batch
        compile_s: Optional[float] = None
        first_sync_done = False
        first_t0 = time.monotonic()  # the tracer's clock
        jit_before = jit_events.totals(STEP_FUN)

        batch_iter = None  # device-staging wrapper, when enabled
        if self.cfg.train.device_prefetch > 0:
            from ..data.pipeline import DevicePrefetcher

            batch_iter = DevicePrefetcher(
                train_iter, self.device_batch,
                depth=self.cfg.train.device_prefetch)

            def next_batch():
                return next(batch_iter)
        else:
            def next_batch():
                return self.device_batch(next(train_iter))

        former_watch = jit_events.watch(STEP_FUN, self._on_step_jit)
        # finally: stop a prefetched iterator's worker thread (and free its
        # buffered batches) instead of abandoning it blocked on a full
        # queue for the rest of the process.
        try:
            while step < num_steps:
                if step == trace_start:
                    trace_stack.enter_context(profiler_trace(trace_dir))
                    tracing = True
                # What the loop waited for its input, prefetcher and all.
                with span("train.next_batch", step=step) as waited:
                    batch = next_batch()
                # The span brackets DISPATCH of the compiled step alone
                # (async — not device time; honest step time is the
                # boundary-derived step_time_s key below).
                # DLCFN_OBS_OFF=1 makes this a shared no-op.
                with span("train.dispatch", step=step) as dispatch:
                    state, metrics = self.train_step(state, batch, rng)
                if self._retrace is not None:
                    self._close_retrace(dispatch.span_id, step)
                del batch  # the device keeps what the step still reads
                window_examples += gb
                step += 1
                if tracing and step >= trace_stop:
                    jax.block_until_ready(metrics)
                    trace_stack.close()
                    tracing = False

                if not first_sync_done:
                    # The first dispatch traced + compiled; sync on it,
                    # record its seconds, and restart the throughput window
                    # so the first logged examples_per_sec is honest.
                    jax.block_until_ready(metrics)
                    compile_s = self._close_first_step(
                        step - 1, first_t0, jit_before,
                        next_batch_s=waited.dur_s, dispatch_s=dispatch.dur_s)
                    window_start = time.perf_counter()
                    window_examples = 0
                    first_sync_done = True
                    if watchdog is not None:
                        watchdog.beat()

                if step % max(log_every, 1) == 0 or step >= num_steps:
                    # Sync point: realize the step just dispatched.
                    with span("train.realize", step=step):
                        realized = {
                            k: float(v) for k, v in
                            jax.device_get(metrics).items()
                        }
                        # What the expert layers counted in this step, in
                        # the registry too: the gauge holds this step, the
                        # histogram every realized step
                        # (docs/OBSERVABILITY.md).
                        # Likewise what a block-diffusion step drew
                        # (``bd_masked_share``), the indexers' loss
                        # (``train.indexer_kl``) and what their selections
                        # kept (``attention.selected.kept_share``, ``ties``).
                        registry = get_tracer().registry
                        for k, v in realized.items():
                            for prefix, family in (
                                    ("moe_", "moe."), ("bd_", "train.bd."),
                                    ("indexer_", "train.indexer_"),
                                    ("sel_", "attention.selected.")):
                                if k.startswith(prefix):
                                    name = family + k[len(prefix):]
                                    registry.gauge(name).set(v)
                                    registry.histogram(
                                        name + ".steps").observe(v)
                    # Throughput covers everything dispatched since the
                    # last boundary.
                    elapsed = time.perf_counter() - window_start
                    if window_examples > 0:
                        realized["examples_per_sec"] = \
                            window_examples / max(elapsed, 1e-9)
                        realized["examples_per_sec_per_device"] = (
                            realized["examples_per_sec"]
                            / self.mesh.devices.size
                        )
                        # Additive key (obs report feed): honest synced
                        # per-step wall time over the same post-compile
                        # window as examples_per_sec.
                        realized["step_time_s"] = (
                            elapsed / max(window_examples // gb, 1)
                        )
                    window_start = time.perf_counter()
                    window_examples = 0
                    realized["step"] = step
                    if compile_s is not None:
                        realized["compile_s"] = compile_s
                        compile_s = None
                    if self._retraces_unreported:
                        realized["retraces"] = self._retraces_unreported
                        self._retraces_unreported = 0
                    if metrics_writer is not None:
                        metrics_writer.write(realized)
                    last_realized = realized
                    if watchdog is not None:
                        # device_get above proved device-side progress.
                        watchdog.beat()

                # Hooks run every step (checkpoint cadence must not couple
                # to log cadence); the metrics argument is the last
                # realized record, if any.
                t_hooks = time.perf_counter()
                with span("train.hooks", step=step):
                    for hook in hooks:
                        hook(step, state, last_realized)
                if watchdog is not None and \
                        time.perf_counter() - t_hooks > 1.0:
                    # A hook that blocked for real host work (a slow
                    # checkpoint write) and COMPLETED is liveness evidence
                    # — beat so it can't eat the next window's budget. The
                    # threshold keeps ordinary (sub-ms) hook calls from
                    # beating every step, which would blind the watchdog
                    # to device hangs behind async dispatch.
                    watchdog.beat()

                if (
                    eval_iter_fn is not None
                    and eval_every > 0
                    and step % eval_every == 0
                ):
                    with span("train.eval", step=step):
                        eval_metrics = self.evaluate(state, eval_iter_fn(),
                                                     eval_steps,
                                                     watchdog=watchdog)
                    if metrics_writer is not None:
                        metrics_writer.write(
                            {"step": step, **{f"eval_{k}": v
                                              for k, v in
                                              eval_metrics.items()}}
                        )
                    if watchdog is not None:
                        # A completed eval is progress too — don't let a
                        # long eval eat the next window's budget.
                        watchdog.beat()
            return state
        finally:
            jit_events.watch(*former_watch)
            self._retrace = None
            if watchdog is not None:
                watchdog.stop()
            trace_stack.close()  # no-op unless exited mid-capture
            if batch_iter is not None:
                batch_iter.close()  # joins its worker, closes train_iter
            else:
                close = getattr(train_iter, "close", None)
                if close is not None:
                    close()

    def _close_first_step(self, step: int, t0: float,
                          jit_before: Dict[str, float],
                          **spans_s: Optional[float]) -> float:
        """The seconds from ``t0`` (``time.monotonic``) to now, which is the
        end of the first step's sync, and with spans on the record
        ``train.first_step``: jax's seconds for the step's trace, lowering
        and backend compile inside it, what the persistent cache was asked,
        answered and saved (``jit_events.totals``, the differences over the
        span), and the seconds of the step's own ``train.next_batch`` and
        ``train.dispatch`` (``spans_s``). The first such record of the
        process also stays in the gauge ``train.first_step_s{part}``
        (``whole`` and every attr in seconds), where a reader in the process
        finds the start's split after later steps and compiles have moved
        the counters on."""
        dur = time.monotonic() - t0
        self._first_synced = True
        if obs_enabled():
            now = jit_events.totals(STEP_FUN)
            held = {k: v - jit_before[k] for k, v in now.items()}
            held.update({k: v for k, v in spans_s.items() if v is not None})
            held = {k: round(v, 6) for k, v in held.items()}
            tracer = get_tracer()
            tracer.record_span("train.first_step", t0, dur, step=step,
                               **held)
            first = tracer.registry.gauge(
                "train.first_step_s",
                "the process's first train.first_step, by part")
            if first.value(part="whole") is None:
                first.set(dur, part="whole")
                for k, v in held.items():
                    if k.endswith("_s"):
                        first.set(v, part=k[:-2])
        return dur

    def _on_step_jit(self, phase: str, seconds: float) -> None:
        """``jit_events``' watcher while ``fit`` runs, called after each
        trace, lowering and backend compile of the step on the thread that
        dispatched it. Before this trainer's first sync they are the first
        step's own. After it they are a retrace, kept open as [start, end,
        jax's seconds] until the loop closes it behind the dispatch that
        provoked it (``_close_retrace``)."""
        if not self._first_synced:
            return
        now = time.monotonic()
        if self._retrace is None:
            self._retrace = [now - seconds, now, 0.0]
        self._retrace[1] = now
        self._retrace[2] += seconds

    def _close_retrace(self, dispatch_id: Optional[int], step: int) -> None:
        """Count the open retrace and write it down as a ``train.retrace``
        record under the ``train.dispatch`` span that provoked it, whichever
        of jax's three phases it went through (a trace whose jaxpr jax had
        compiled before stops there)."""
        (t0, t1, jit_s), self._retrace = self._retrace, None
        self._retraces_unreported += 1
        tracer = get_tracer()
        tracer.registry.counter(
            "train.step_retraces",
            "traces or compiles of the step after the first sync").inc()
        tracer.record_span("train.retrace", t0, t1 - t0,
                           parent_id=dispatch_id, step=step,
                           jit_s=round(jit_s, 6))

    def evaluate(self, state: TrainState, eval_iter: Iterator[Batch],
                 max_steps: int = 0, watchdog=None) -> Dict[str, float]:
        """Weighted cross-batch aggregation: each batch's metrics carry
        their normalizer (``eval_weight``, or a per-metric
        ``<name>__weight``), so the result is the exact full-set metric —
        not a mean of batch means, which is biased whenever batches have
        unequal effective weights (padded eval tails, per-token metrics).

        ``watchdog``: beaten after every realized eval batch (each
        device_get proves device-side progress), so an eval pass longer
        than ``hang_timeout_s`` doesn't kill a healthy run — the operator
        budget only has to cover ONE eval batch, not the whole pass."""
        totals: Dict[str, float] = {}
        wsums: Dict[str, float] = {}
        examples = 0.0
        eb = self.cfg.train.eval_batch or self.cfg.train.global_batch
        for i, batch in enumerate(eval_iter):
            if max_steps and i >= max_steps:
                break
            dev_batch = self.device_batch(batch, global_batch=eb)
            metrics = {k: float(v) for k, v in
                       jax.device_get(self.eval_step(state, dev_batch))
                       .items()}
            if watchdog is not None:
                watchdog.beat()
            default_w = metrics.pop("eval_weight", float(eb))
            examples += default_w
            for k, v in metrics.items():
                if k.endswith("__weight"):
                    continue
                w = metrics.get(f"{k}__weight", default_w)
                totals[k] = totals.get(k, 0.0) + v * w
                wsums[k] = wsums.get(k, 0.0) + w
        out = {k: totals[k] / max(wsums[k], 1e-9) for k in totals}
        out["examples"] = examples
        for name, fn in self.eval_derived.items():
            out[name] = float(fn(out))
        return out

