"""Runner for traffic of kind ``train_steps``: optimizer steps through the
program's ``Trainer.fit`` with its input pipeline running.

Set-up builds ONE trainer with its state, drives it from the seed through
its first steps (the ones the reference follows) and hands that same trainer,
state and feed to the timed window. The window is cut by the clock in a
``fit`` hook that ends in ``block_until_ready``; the feed never ends, so no
step rate runs it out of data. After the window the program's state is
freed, and only then does the float32 reference run.
"""

from __future__ import annotations

import math
import time
from typing import Any, Dict, List

import numpy as np

from . import compare, weights
from .device import memory_peak_bytes

CHECK_STEPS = 3


class WindowClosed(Exception):
    """Raised by the hook when the clock passes the window's end."""


def make_tokens(seed: int, traffic: Dict[str, Any], seq_len: int,
                vocab_size: int) -> np.ndarray:
    """``[num_examples, seq_len + 1]`` token rows from the seed, every row
    different. Row ``r`` repeats its previous token with a probability of its
    own between ``repeat_min`` and ``repeat_max``: with a tied output that
    makes the rows' losses differ widely at seeded weights, so that a step
    which left part of its batch out shows in the loss."""
    n = int(traffic["num_examples"])
    rng = np.random.Generator(np.random.PCG64(seed))
    fresh = rng.integers(4, vocab_size, size=(n, seq_len + 1), dtype=np.int32)
    p_row = np.linspace(traffic["repeat_min"], traffic["repeat_max"], n)
    rng.shuffle(p_row)
    keep = rng.random((n, seq_len + 1)) < p_row[:, None]
    keep[:, 0] = False
    # Position t takes the token of the last position that was drawn fresh.
    idx = np.where(keep, 0, np.arange(seq_len + 1)[None, :])
    src = np.maximum.accumulate(idx, axis=1)
    return np.take_along_axis(fresh, src, axis=1)


class Feed:
    """The never-ending batch iterator ``fit`` draws from. Times each
    ``next()`` of the program's pipeline and keeps the token rows of the
    first batches for the reference. It has no ``close``: ``fit`` closes
    what it is given when it returns, and the window needs the same feed."""

    def __init__(self, inner, keep: int, annotate):
        self._inner = inner
        self._keep = keep
        self._annotate = annotate
        self.first: List[np.ndarray] = []
        self.wait_s: List[float] = []

    def __iter__(self):
        return self

    def __next__(self):
        t0 = time.perf_counter()
        with self._annotate("next(batch)"):
            batch = next(self._inner)
        self.wait_s.append(time.perf_counter() - t0)
        if len(self.first) < self._keep:
            self.first.append(np.array(batch["tokens"]))
        return batch


class Records:
    """Stands where ``fit`` expects a metrics writer: keeps every step's
    realized record."""

    def __init__(self):
        self.rows: List[Dict[str, float]] = []

    def write(self, record):
        self.rows.append(dict(record))


def _adam_mu(opt_state):
    """The first-moment tree of optax's Adam inside a chained state."""
    if hasattr(opt_state, "mu"):
        return opt_state.mu
    if isinstance(opt_state, (tuple, list)):
        for child in opt_state:
            found = _adam_mu(child)
            if found is not None:
                return found
    if hasattr(opt_state, "inner_state"):
        return _adam_mu(opt_state.inner_state)
    return None


def build_program_config(cell, seed: int):
    from deeplearning_cfn_tpu.config import apply_overrides
    from deeplearning_cfn_tpu.presets import get_preset

    cfg = get_preset(cell.config["preset"])
    cfg.preset = cell.config["preset"]
    apply_overrides(cfg, list(cell.config["overrides"])
                    + list(cell.traffic["overrides"]))
    # The program's own seed, folded small: ``data/pipeline.py`` seeds numpy
    # with (seed + 1) * 7919 + ..., which numpy refuses from 2**32 on, so a
    # train.seed over ~542,000 kills the pipeline's worker. The weights and
    # the tokens take the whole of --seed.
    cfg.train.seed = seed % 400_009
    return cfg


def build_trainer(cell, cfg, seed: int, devices):
    """The trainer, its state from seeded weights, and the parameter shapes,
    as ``train/run.py:run_experiment`` builds them, on exactly ``devices``."""
    import jax

    from deeplearning_cfn_tpu.parallel.mesh import build_mesh
    from deeplearning_cfn_tpu.train.optim import build_optimizer, \
        build_schedule
    from deeplearning_cfn_tpu.train.state import create_train_state
    from deeplearning_cfn_tpu.train.task import build_task
    from deeplearning_cfn_tpu.train.trainer import Trainer

    mesh = build_mesh(cfg.mesh, devices=devices)
    if mesh.devices.size != len(devices):
        raise RuntimeError(
            f"the mesh has {mesh.devices.size} devices, the cell {len(devices)}")
    task = build_task(cfg, mesh=mesh)
    schedule = build_schedule(cfg.schedule, cfg.train.steps,
                              cfg.train.global_batch, None)
    tx = build_optimizer(cfg.optimizer, schedule)
    key = weights.seed_key(seed)
    shapes = jax.eval_shape(task.init, key)["params"]
    state = create_train_state(
        key, lambda rng: {"params": weights.make(shapes, rng)}, tx, mesh,
        param_rules=getattr(task, "param_rules", ()),
        shard_opt_state=cfg.train.shard_opt_state)
    trainer = Trainer(cfg, task.loss_fn, tx, mesh=mesh)
    return trainer, state, shapes, mesh


def build_feed(cell, cfg, seed: int, mesh, annotate) -> Feed:
    from deeplearning_cfn_tpu.data.pipeline import ArraySource, DataPipeline
    from deeplearning_cfn_tpu.parallel.mesh import local_batch_size

    tokens = make_tokens(seed, cell.traffic, cfg.data.seq_len,
                         cfg.data.vocab_size)
    source = ArraySource({
        "tokens": tokens,
        "loss_mask": np.ones((len(tokens), cfg.data.seq_len), np.float32)})
    pipe = DataPipeline(
        source, local_batch_size(cfg.train.global_batch, mesh),
        seed=cfg.train.seed, shuffle=True, prefetch=cfg.data.prefetch,
        native=cfg.data.use_native_loader, num_workers=cfg.data.num_workers)
    return Feed(pipe.epochs(), CHECK_STEPS, annotate)


def pallas_kernels(trainer, state, batch, rng):
    """The Pallas kernels of the compiled step, from its optimised text:
    their instruction names are what the trace calls them."""
    from .xplane import pallas_calls

    text = trainer.train_step.lower(state, batch, rng).compile().as_text()
    return pallas_calls(text)


def first_steps(trainer, state, feed, rng, shapes, seed, say):
    """Drive the trainer through its first ``CHECK_STEPS`` steps with the
    window's own call and feed. Returns the state after them and what the
    comparison needs from the program: each step's loss, the first
    gradient's per-leaf norms as Adam got it, the per-leaf norms of the
    parameters' change."""
    import jax
    import jax.numpy as jnp

    records = Records()
    seen: Dict[str, Any] = {}
    b1 = float(trainer.cfg.optimizer.b1)

    @jax.jit
    def grad_norms(mu):
        return jax.tree_util.tree_map(
            lambda m: jnp.sqrt(jnp.sum(jnp.square(m))) / (1.0 - b1), mu)

    @jax.jit
    def change_norms(params, key):
        start = weights.make(shapes, key)
        return jax.tree_util.tree_map(
            lambda p, s: jnp.sqrt(jnp.sum(jnp.square(p - s))), params, start)

    def hook(step, st, _last):
        # Also warms the window's marker program (``st.step + 0``).
        jax.block_until_ready(st.step + 0)
        if step == 1:
            mu = _adam_mu(st.opt_state)
            if mu is None:
                raise RuntimeError("no Adam first moment in the optimizer "
                                   "state: the comparison reads it")
            seen["grad_norms"] = jax.device_get(grad_norms(mu))
        if step == CHECK_STEPS:
            seen["change_norms"] = jax.device_get(
                change_norms(st.params, weights.seed_key(seed)))

    state = trainer.fit(state, feed, num_steps=CHECK_STEPS, rng=rng,
                        hooks=(hook,), log_every=1, metrics_writer=records)
    losses = [r["loss"] for r in records.rows if "loss" in r]
    if len(losses) != CHECK_STEPS:
        raise RuntimeError(f"expected {CHECK_STEPS} losses from the first "
                           f"steps, got {records.rows}")
    compile_s = [r["compile_s"] for r in records.rows if "compile_s" in r]
    say(f"first steps: losses {losses}; fit's first-step seconds "
        f"{compile_s}")
    return state, {"loss": losses,
                   "grad_norms": weights.flat(seen["grad_norms"]),
                   "change_norms": weights.flat(seen["change_norms"])}


def window(trainer, state, feed, rng, seconds: float, max_steps: int,
           annotate):
    """The timed window: ``fit`` from the state the first steps left, until
    the clock or ``max_steps`` ends it. The hook waits for the step *before*
    the one just dispatched (through a marker computed from that step's
    state), so one step is always queued behind the running one and the
    host's own time between steps is hidden, as it is in the program's
    default loop, which syncs every 50 steps: waiting for every step itself
    exposed ~9 ms of host time a step, and twice as much on a busy host.
    Returns the window's start, the time each finished step was seen to have
    ended, and the final state."""
    import jax

    ends: List[float] = []
    box = {"state": state, "marker": None}
    t0 = time.perf_counter()
    deadline = t0 + seconds

    def hook(_step, st, _last):
        with annotate("fit hook"):
            before = box["marker"]
            box["state"], box["marker"] = st, st.step + 0
            if before is None:
                return
            jax.block_until_ready(before)
            now = time.perf_counter()
            if now > deadline:
                raise WindowClosed
            ends.append(now)
            if len(ends) >= max_steps:
                raise WindowClosed

    try:
        with annotate("window"):
            trainer.fit(state, feed, num_steps=2 ** 31 - 1, rng=rng,
                        hooks=(hook,), log_every=2 ** 30)
    except WindowClosed:
        pass
    return t0, ends, jax.block_until_ready(box["state"])


def run(ctx) -> Dict[str, Any]:
    import jax
    import jax.numpy as jnp

    cell, seed, say = ctx["cell"], ctx["seed"], ctx["say"]
    devices = ctx["devices"]
    annotate = ctx["annotate"]
    cfg = build_program_config(cell, seed)
    say(f"program config: preset {cfg.preset}, global batch "
        f"{cfg.train.global_batch} x {cfg.data.seq_len}, vocab "
        f"{cfg.data.vocab_size}, mesh data={cfg.mesh.data}, ZeRO-1 "
        f"{cfg.train.shard_opt_state}, dropout "
        f"{cfg.model.kwargs.get('dropout_rate')}")
    stated = {cell.config["embd_pdrop"], cell.config["resid_pdrop"]}
    if stated != {float(cfg.model.kwargs.get("dropout_rate", 0.0))}:
        raise RuntimeError(
            f"the configuration file states dropout {sorted(stated)} and "
            f"the program runs {cfg.model.kwargs.get('dropout_rate')}")

    ctx["phase"]("set-up")
    trainer, state, shapes, mesh = build_trainer(cell, cfg, seed, devices)
    feed = build_feed(cell, cfg, seed, mesh, annotate)
    rng = jax.random.split(jax.random.PRNGKey(cfg.train.seed), 3)[2]
    state, program = first_steps(trainer, state, feed, rng, shapes, seed, say)
    tokens_per_step = cfg.train.global_batch * cfg.data.seq_len
    say(f"device memory after the first steps: peak "
        f"{memory_peak_bytes(devices)} bytes")

    ctx["phase"]("window")
    traced = ctx["trace"]
    max_steps = int(cell.traffic.get("trace_steps", 12)) if traced \
        else 2 ** 31 - 1
    in_setup = ctx["events"].mark()
    waits_before = len(feed.wait_s)
    with ctx["profiler"]():
        t0, ends, state = window(trainer, state, feed, rng, ctx["seconds"],
                                 max_steps, annotate)
    in_window = ctx["events"].mark()
    peak = memory_peak_bytes(devices)
    say(f"window: {len(ends)} steps in {ends[-1] - t0 if ends else 0:.3f} s;"
        f" compile requests inside the window: {in_window['compiles']} "
        f"(should be 0); device memory peak {peak} bytes")
    if not ends:
        raise RuntimeError("no step finished inside the window")
    # The window syncs on no loss; a step that went non-finite shows in the
    # parameters it left.
    total = float(jax.jit(lambda p: sum(
        jnp.sum(jnp.square(x)) for x in jax.tree_util.tree_leaves(p)))(
            state.params))
    failed = 0 if math.isfinite(total) else len(ends)
    step_s = np.diff([t0] + ends)
    kernels = None
    if traced:
        # Compiling the step again (a cache hit) costs seconds; only the
        # traced run pays them, after its window.
        batch = trainer.device_batch(
            {"tokens": feed.first[0],
             "loss_mask": np.ones((len(feed.first[0]), cfg.data.seq_len),
                                  np.float32)})
        kernels = pallas_kernels(trainer, state, batch, rng)
        say(f"attention path in the compiled step: "
            f"{len(kernels)} tpu_custom_call "
            f"({'Pallas kernels' if kernels else 'the XLA path ran'})")

    ctx["phase"]("comparison")
    first_batches = list(feed.first)
    waits = [float(w) for w in feed.wait_s[waits_before:]]
    hp = dict(cell.config["optimizer"])
    del trainer, state, feed
    t_ref = time.perf_counter()
    params = jax.jit(lambda key: weights.make(shapes, key))(
        weights.seed_key(seed))
    reference = cell.reference.train_steps(
        params, first_batches, cell.config, hp, precision="float32",
        block_rows=int(cell.traffic.get("reference_block_rows", 2)), rng=rng)
    del params
    limits = {**cell.config["limits"], **cell.traffic.get("limits", {})}
    verdict = compare.train(program, reference, limits, say)
    say(f"reference and comparison took {time.perf_counter() - t_ref:.1f} s "
        f"(not counted in setup_s)")
    if failed:
        say("the window left non-finite parameters: every step counts as "
            "failed")

    return {
        "correct": bool(verdict and failed == 0),
        "attempted": len(ends),
        "failed": failed,
        "window_start": t0,
        "memory_peak_bytes": peak,
        "end_to_end": {
            "train_tokens_per_s": len(ends) * tokens_per_step
            / (ends[-1] - t0),
        },
        "layer": {
            "step_s": [float(s) for s in step_s],
            "input_wait_s": waits,
            "tokens_per_step": tokens_per_step,
            "seq_len": cfg.data.seq_len,
            "global_batch": cfg.train.global_batch,
            "steps": len(ends),
            "pallas_calls": kernels or [],
            "compile_in_setup": in_setup,
            "compile_in_window": in_window,
        },
    }
