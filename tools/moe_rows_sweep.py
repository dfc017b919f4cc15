#!/usr/bin/env python3
"""Time the ways an expert layer moves its rows, alone on the chip.

    python3 tools/moe_rows_sweep.py [--steps 20] [--shape zaya1]
    python3 tools/moe_rows_sweep.py --quick --case mellum2_rank:32768:2304

For each of the expert cells' buffer shapes (rows of 2048 in bfloat16, the
(token, choice) pairs of 8,192 tokens sorted by expert as ``HeldExpertsMlp``
sorts them; ``--case shape:tokens:width[:share]`` names another size and
another share of the choices on held experts, and
``--separating`` the cases that tell a row's width from its source's size:
a rank of Mellum2's routing at widths 2048 / 2304 / 2560 and 8,192 / 32,768
tokens, Laguna's at width 2304) it times, forward and backward apart:

* ``order``: the sort the layer already makes, and the three ways to its
  inverse ``inv`` (a scatter of ``arange``, a second ``argsort``, counting
  with a cumulative sum over ``[pairs, groups + 1]``);
* *dispatch*, ``xs[r] = m[token[r]]``: the forward gather (the same either
  way), and its backward as autodiff writes it (a scatter-add of the rows'
  cotangents into the tokens) and as ``models/moe.py:take_rows`` does (a
  gather of each token's ``k`` rows through ``inv``), in the three forms of
  that gather: a gather a choice added in turn, one gather
  ``[tokens, k, F]`` summed over the middle, one gather ``[k, tokens, F]``
  summed over whole slabs (the module takes the first under the usual
  buffer and the second under the buffer of every pair). A dead pair reads
  the last row: pointed at row 0 instead it costs the same (PERF.md, PR 33);
* *combine*, ``out[t] = sum_j w y[inv[t k + j]]``: forward as
  ``jax.ops.segment_sum`` of the weighted float32 rows and as
  ``models/moe.py:sum_rows`` in the same three forms, and backward either
  way.

* with ``--quick`` only what ``models/moe.py`` does today and its candidates:
  each of the four movements by XLA's gathers (``path="gather"``) and by the
  row kernel (``ops/rows.py``, ``path="kernel"``: the source's copy to pairs
  of rows and the indices' preparation are in its time; ``view`` is that
  copy alone), and with ``--views`` XLA's gather over the source seen
  another way: as 32-bit words ``[M, F / 2]``, as ``[M, F / 256, 256]``,
  split at 2048 columns.

A time is the device's busy time a call: the sum of the durations of every
operation the call put on the device, from the profiler's trace. Beside it
the least the chip's bandwidth allows: the live rows read once and the
result written once. ``unequal`` is the largest difference from the
scatter-add form's result (with ``--quick``, from XLA's gather). ``models/moe
.py`` takes its path from this table (PERF.md, PRs 33 and 36). A chip run
only: it stops where jax finds no TPU.
"""

from __future__ import annotations

import argparse
import functools
import json
import os
import shutil
import sys
import tempfile

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, _ROOT)
sys.path.insert(0, os.path.join(_ROOT, "benchmark"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from deeplearning_cfn_tpu.models import moe  # noqa: E402

TOKENS, WIDTH = 8192, 2048
# name -> (choices a token, experts, experts held, whether the buffer holds
# every pair or the usual twice a uniform router's rows, the share of a
# token's choices that fall on held experts)
SHAPES = {
    # Laguna-XS.2's usual buffer: twice a uniform router's 8,192 rows.
    "laguna": (8, 256, 32, False, 1 / 8),
    # Its other buffer, every pair, taken where a step sends over 16,384.
    "laguna_every_pair": (8, 256, 32, True, 0.3),
    # ZAYA1-8B: one choice a token, the one buffer of every pair.
    "zaya1": (1, 16, 8, True, 1 / 2),
    # A rank of Mellum2's four on its host, after the gather of the ranks'
    # tokens (``mellum2_rank:32768:2304``): 262,144 pairs, 16 of 64 experts
    # held, twice a uniform router's 65,536 rows.
    "mellum2_rank": (8, 64, 16, False, 1 / 4),
    # SDAR-30B-A3B's and Keye-VL-2.0-30B-A3B's layer (``sdar:16384:2048``):
    # 131,072 pairs, 16 of 128 experts held, twice a uniform router's 16,384
    # rows: a buffer of 2 ** 27 bytes over tokens of 2 ** 26. Their steps'
    # layers hold 14.8 to 21.4 k live rows (``sdar:16384:2048:0.113`` and
    # ``:0.163``).
    "sdar": (8, 128, 16, False, 1 / 8),
}
# What tells a row's width from its source's size (PERF.md, PR 36).
SEPARATING = [f"mellum2_rank:{t}:{w}" for t in (8192, 32768)
              for w in (2048, 2304, 2560)] + ["laguna:8192:2304"]


def _busy_ms(fn, args, steps):
    """Device time a call of the compiled ``fn``: every operation's duration
    in a trace of ``steps`` calls, summed, over ``steps``."""
    from harness import xplane

    jax.block_until_ready(fn(*args))
    trace_dir = tempfile.mkdtemp(prefix="moe_rows_sweep_")
    try:
        with jax.profiler.trace(trace_dir):
            for _ in range(steps):
                r = fn(*args)
            jax.block_until_ready(r)
        trace = xplane.Trace.from_file(xplane.find_xplane(trace_dir), [])
        return sum(trace.op_seconds(0).values()) / steps * 1e3
    finally:
        shutil.rmtree(trace_dir, ignore_errors=True)


def routing(key, top_k, experts, held, share):
    """``group [pairs]`` as the layer makes it: a held expert's number, or
    ``held`` for every other. ``share`` of the choices fall on held experts,
    a token's choices on distinct ones."""
    # Weighted choice without replacement: the largest of log(u) / w.
    odds = share / (1 - share) * (experts - held) / held
    scores = jnp.log(jax.random.uniform(key, (TOKENS, experts))) \
        / jnp.where(jnp.arange(experts) < held, odds, 1.0)
    _, chosen = jax.lax.top_k(scores, top_k)
    return jnp.minimum(chosen.reshape(-1), held).astype(jnp.int32)


# -- the candidates for inv ---------------------------------------------------


def order_of(group):
    return jnp.argsort(group, stable=True).astype(jnp.int32)


def inv_by_argsort(group, order):
    return moe.inverse_permutation(order)


def inv_by_scatter(group, order):
    return jnp.zeros_like(order).at[order].set(
        jnp.arange(order.shape[0], dtype=order.dtype), unique_indices=True)


def inv_by_counting(group, order, count):
    """``start[group[p]]`` + the earlier pairs of ``p``'s group."""
    one = group[:, None] == jnp.arange(count + 1)[None, :]
    upto = jnp.cumsum(one, axis=0, dtype=jnp.int32)
    sizes = upto[-1]
    start = jnp.cumsum(sizes) - sizes
    return jnp.sum(jnp.where(one, upto - 1 + start[None, :], 0), axis=1)


# -- the forms of the two movements --------------------------------------------


def plain_take(m, token, n_live):
    live = (jnp.arange(token.shape[0]) < n_live)[:, None]
    return jnp.where(live, m[token], 0)


def plain_sum(y, weight, pair, n_live, top_k):
    live = (jnp.arange(pair.shape[0]) < n_live)[:, None]
    y = jnp.where(live, y, 0).astype(jnp.float32) * weight[pair][:, None]
    return jax.ops.segment_sum(y, pair // top_k, num_segments=TOKENS) \
        .astype(jnp.bfloat16)


def rows_token_major(y, weight, inv, n_live, top_k):
    """``moe._rows_of_tokens`` as one gather with the tokens leading,
    ``[tokens, k, F]``, and the sum over the middle."""
    at = inv.reshape(-1, top_k)
    live = at < n_live
    got = y[jnp.minimum(at, y.shape[0] - 1)].astype(jnp.float32)
    if weight is not None:
        got = got * weight.reshape(-1, top_k)[..., None]
    return jnp.sum(jnp.where(live[..., None], got, 0), axis=1)


def rows_choice_major(y, weight, inv, n_live, top_k):
    """The same with the choices leading, ``[k, tokens, F]``, and the sum
    over whole slabs."""
    at = inv.reshape(-1, top_k).T
    live = (at < n_live)[..., None]
    got = y[jnp.minimum(at, y.shape[0] - 1)].astype(jnp.float32)
    if weight is not None:
        got = got * weight.reshape(-1, top_k).T[..., None]
    return jnp.sum(jnp.where(live, got, 0), axis=0)


def rows_by_choice(y, weight, inv, n_live, top_k):
    """The same, a gather a choice into one float32 sum."""
    at = inv.reshape(-1, top_k)
    total = jnp.zeros((at.shape[0], y.shape[1]), jnp.float32)
    for j in range(top_k):
        got = y[jnp.minimum(at[:, j], y.shape[0] - 1)].astype(jnp.float32)
        if weight is not None:
            got = got * weight.reshape(-1, top_k)[:, j, None]
        total = total + jnp.where((at[:, j] < n_live)[:, None], got, 0)
    return total


GATHERS = {"module": moe._rows_of_tokens, "by_choice": rows_by_choice,
           "token_major": rows_token_major, "choice_major": rows_choice_major}


# -- the source seen another way, for XLA's gather -----------------------------


def as_words(src):
    """``[M, F]`` bfloat16 as 32-bit words ``[M, F / 2]``, and the way back."""
    seen = jax.lax.bitcast_convert_type(
        src.reshape(src.shape[0], -1, 2), jnp.uint32)
    return (seen,), lambda got: jax.lax.bitcast_convert_type(
        got[0], jnp.bfloat16).reshape(got[0].shape[0], -1)


def as_tiles256(src):
    seen = src.reshape(src.shape[0], -1, 256)
    return (seen,), lambda got: got[0].reshape(got[0].shape[0], -1)


def split_at_2048(src):
    return (src[:, :2048], src[:, 2048:]), \
        lambda got: jnp.concatenate(got, axis=1)


VIEWS = {"words": as_words, "tiles256": as_tiles256, "split2048": split_at_2048}


def viewed_take(view, m, token, n_live):
    parts, back = view(m)
    live = (jnp.arange(token.shape[0]) < n_live)[:, None]
    return jnp.where(live, back([p[token] for p in parts]), 0)


def viewed_sum(view, y, weight, inv, n_live, top_k):
    """``rows_by_choice`` over the viewed source."""
    parts, back = view(y)
    at = jnp.minimum(inv, y.shape[0] - 1).reshape(-1, top_k)
    total = jnp.zeros((at.shape[0], y.shape[1]), jnp.float32)
    for j in range(top_k):
        got = back([p[at[:, j]] for p in parts]).astype(jnp.float32) \
            * weight.reshape(-1, top_k)[:, j, None]
        total = total + jnp.where(
            (inv.reshape(-1, top_k)[:, j] < n_live)[:, None], got, 0)
    return total.astype(y.dtype)


def measure(name, steps, say, quick=False, views=False, share=None):
    top_k, experts, held, every_pair, usual_share = SHAPES[name]
    share = share or usual_share
    pairs = TOKENS * top_k
    rows = pairs if every_pair else moe._buffer_rows(pairs, held, experts)
    keys = jax.random.split(jax.random.PRNGKey(0), 5)
    group = routing(keys[0], top_k, experts, held, share)
    order = order_of(group)
    inv = moe.inverse_permutation(order)
    n_held = jnp.sum(group < held)
    n_live = jnp.minimum(rows, n_held)
    pair = order[:rows]
    token = pair // top_k
    m = jax.random.normal(keys[1], (TOKENS, WIDTH), jnp.bfloat16)
    y = jax.random.normal(keys[2], (rows, WIDTH), jnp.bfloat16)
    g_out = jax.random.normal(keys[3], (TOKENS, WIDTH), jnp.bfloat16)
    weight = jax.random.uniform(keys[4], (TOKENS * top_k,), jnp.float32)
    from harness import device

    peak = device.peaks_of(jax.devices()[0].device_kind)["hbm_bytes_per_s"]
    live_rows = int(n_live)
    head = {"shape": name, "tokens": TOKENS, "width": WIDTH, "top_k": top_k,
            "rows": rows, "pairs": pairs, "live_rows": live_rows}
    # The live rows read, the tokens written, both in bfloat16.
    floor_ms = (live_rows + TOKENS) * WIDTH * 2 / peak * 1e3

    def timed(what, form, fn, args, want=None, floor=None):
        line = {**head, "what": what, "form": form}
        try:
            compiled = jax.jit(fn).lower(*args).compile()
            got = compiled(*args)
            if want is not None:
                line["unequal"] = max(
                    float(jnp.max(jnp.abs(a.astype(jnp.float32)
                                          - b.astype(jnp.float32))))
                    for a, b in zip(jax.tree.leaves(got),
                                    jax.tree.leaves(want)))
            line["ms"] = _busy_ms(compiled, args, steps)
            if floor is not None:
                line["floor_ms"] = floor
        except Exception as e:  # a form the chip refuses: go on
            line["error"] = f"{type(e).__name__}: {e}"[:400]
            got = None
        say(line)
        return got

    # Both sides of the movement by the one way (``moe.rows_path``'s pair).
    take_back = lambda way: lambda d, t, i, n: jax.vjp(
        lambda m: moe.take_rows(m, t, i, n, top_k, (way, way)), m)[1](d)[0]
    sum_there = lambda way: lambda y, w, o, i, n: moe.sum_rows(
        y, w, o, i, n, top_k, None, (way, way))
    sum_back = lambda way: lambda y, w, o, i, n, g: jax.vjp(
        lambda y, w: moe.sum_rows(y, w, o, i, n, top_k, None, (way, way)),
        y, w)[1](g)
    d_xs = y
    there = 2 * live_rows * WIDTH * 2 / peak * 1e3
    if quick:
        # The four movements as the module makes them, by XLA's gathers and
        # by the row kernel, and XLA's gather over the re-viewed source.
        views = {form: view for form, view in VIEWS.items()
                 if views and (form != "split2048" or WIDTH > 2048)}
        for src, seen in ((m, "tokens"), (y, "buffer")):
            timed("view", seen, lambda s: s.reshape(-1, 2, s.shape[1]),
                  (src,), floor=2 * src.size * 2 / peak * 1e3)
        want = timed("dispatch_fwd", "gather", plain_take,
                     (m, token, n_live), floor=there)
        timed("dispatch_fwd", "kernel", lambda m, t, i, n: moe.take_rows(
            m, t, i, n, top_k, ("kernel", "kernel")),
            (m, token, inv, n_live), want=want, floor=there)
        for form, view in views.items():
            timed("dispatch_fwd", form, functools.partial(viewed_take, view),
                  (m, token, n_live), want=want, floor=there)
        want = timed("dispatch_bwd", "take_rows", take_back("gather"),
                     (d_xs, token, inv, n_live), floor=floor_ms)
        timed("dispatch_bwd", "kernel", take_back("kernel"),
              (d_xs, token, inv, n_live), want=want, floor=floor_ms)
        want = timed("combine_fwd", "module", sum_there("gather"),
                     (y, weight, order, inv, n_live), floor=floor_ms)
        timed("combine_fwd", "kernel", sum_there("kernel"),
              (y, weight, order, inv, n_live), want=want, floor=floor_ms)
        for form, view in views.items():
            timed("combine_fwd", form, lambda y, w, i, n, view=view:
                  viewed_sum(view, y, w, i, n, top_k),
                  (y, weight, inv, n_live), want=want, floor=floor_ms)
        want = timed("combine_bwd", "sum_rows", sum_back("gather"),
                     (y, weight, order, inv, n_live, g_out), floor=floor_ms)
        timed("combine_bwd", "kernel", sum_back("kernel"),
              (y, weight, order, inv, n_live, g_out), want=want,
              floor=floor_ms)
        return

    timed("order", "argsort", order_of, (group,))
    for form, fn in (("scatter", inv_by_scatter), ("argsort", inv_by_argsort),
                     ("counting", functools.partial(inv_by_counting,
                                                    count=held))):
        timed("inv", form, fn, (group, order), want=inv)

    # Dispatch: forward the one gather; backward by scatter-add and by gather.
    timed("dispatch_fwd", "gather", plain_take, (m, token, n_live),
          floor=there)
    want = timed("dispatch_bwd", "scatter_add", lambda d, t, n: jax.vjp(
        lambda m: plain_take(m, t, n), m)[1](d)[0], (d_xs, token, n_live),
        floor=floor_ms)
    for form, gather in GATHERS.items():
        timed("dispatch_bwd", form, lambda d, i, n, gather=gather: gather(
            d, None, i, n, top_k).astype(d.dtype), (d_xs, inv, n_live),
            want=want, floor=floor_ms)
    timed("dispatch_bwd", "take_rows", take_back("gather"),
          (d_xs, token, inv, n_live), want=want, floor=floor_ms)

    # Combine: forward by segment_sum and by gather; backward either way.
    want = timed("combine_fwd", "scatter_add", lambda y, w, p, n: plain_sum(
        y, w, p, n, top_k), (y, weight, pair, n_live), floor=floor_ms)
    for form, gather in GATHERS.items():
        timed("combine_fwd", form, lambda y, w, i, n, gather=gather: gather(
            y, w, i, n, top_k).astype(y.dtype), (y, weight, inv, n_live),
            want=want, floor=floor_ms)
    want = timed("combine_bwd", "scatter_add", lambda y, w, p, n, g: jax.vjp(
        lambda y, w: plain_sum(y, w, p, n, top_k), y, weight)[1](g),
        (y, weight, pair, n_live, g_out), floor=floor_ms)
    timed("combine_bwd", "sum_rows", sum_back("gather"),
          (y, weight, order, inv, n_live, g_out), want=want, floor=floor_ms)


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--steps", type=int, default=20)
    ap.add_argument("--out", default=os.path.join(
        _ROOT, "chiprun_out", "moe_rows_sweep.jsonl"))
    ap.add_argument("--shape", action="append", choices=sorted(SHAPES),
                    help="only this shape (repeatable)")
    ap.add_argument("--tokens", type=int, default=TOKENS)
    ap.add_argument("--width", type=int, default=WIDTH)
    ap.add_argument("--case", action="append", default=[],
                    metavar="SHAPE:TOKENS:WIDTH[:SHARE]",
                    help="a shape at its own size, and the share of the "
                    "choices that fall on held experts (repeatable)")
    ap.add_argument("--separating", action="store_true",
                    help="the cases that tell the width from the size")
    ap.add_argument("--quick", action="store_true",
                    help="the module's four movements by XLA's gathers and "
                    "by the row kernel")
    ap.add_argument("--views", action="store_true",
                    help="with --quick: XLA's gather over the re-viewed "
                    "source too")
    args = ap.parse_args()
    if jax.default_backend() != "tpu":
        sys.exit("moe_rows_sweep: no TPU here; a time comes only from a "
                 "chip run")
    cases = args.case + (SEPARATING if args.separating else [])
    if not cases:
        cases = [f"{name}:{args.tokens}:{args.width}" for name in args.shape
                 or [n for n in SHAPES if n != "mellum2_rank"]]
    os.makedirs(os.path.dirname(args.out), exist_ok=True)
    with open(args.out, "w") as f:
        def say(line):
            text = json.dumps(line)
            print(text, flush=True)
            f.write(text + "\n")
            f.flush()

        say({"device": jax.devices()[0].device_kind, "steps": args.steps})
        for case in cases:
            name, tokens, width, *share = case.split(":")
            globals().update(TOKENS=int(tokens), WIDTH=int(width))
            measure(name, args.steps, say, args.quick, args.views,
                    float(share[0]) if share else None)


if __name__ == "__main__":
    main()
