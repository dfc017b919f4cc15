"""Op-level microbenchmarks: where does a train step's time actually go?

The preset benches (``bench.py``) time whole train steps; this module times
the *pieces* — backbone fwd+bwd, RPN top-k, static NMS, ROI-align, mask
head — at the exact shapes the maskrcnn preset uses, plus A/B variants
(classic vs space-to-depth ResNet stem). It exists because single-number
benches can't tell a gather-bound ROI-align from a slow backbone, and the
0.05-MFU detection step needed a diagnosis, not a guess.

Run: ``python -m deeplearning_cfn_tpu.opsbench [--suite detection|resnet]``
Prints one JSON line per timing. Works on any backend (CPU numbers are for
relative sanity only; the point is the real chip).

Timing contract: every timed function returns a scalar; the loop chains a
data-dependent token through successive calls, so the calls run back to
back, and the timed region ends in ONE trailing host read of the last
result (which waits for the device like ``block_until_ready``).
"""

from __future__ import annotations

import json
import time
from typing import Callable, Dict


def timed_scalar(fn: Callable, *args, steps: int = 10, warmup: int = 2
                 ) -> float:
    """Mean ms/call of ``fn(*args, token)`` where fn returns a f32 scalar.

    The token (f32 scalar, 0.0) is derived from the previous call's result,
    making every dispatch data-dependent on the last; the trailing
    ``float(out)`` waits for the device inside the timed region.
    """
    import jax.numpy as jnp

    tok = jnp.float32(0.0)
    for _ in range(max(warmup, 1)):
        out = fn(*args, tok)
    float(out)  # sync: warmup finished, queue empty
    t0 = time.perf_counter()
    for _ in range(steps):
        out = fn(*args, (out * 0).astype(jnp.float32))
    float(out)
    return (time.perf_counter() - t0) / steps * 1000.0


def _scalarize(tree) -> "jax.Array":
    """Reduce an arbitrary pytree to one f32 scalar (keeps it all live)."""
    import jax
    import jax.numpy as jnp

    return sum(jnp.sum(a.astype(jnp.float32))
               for a in jax.tree_util.tree_leaves(tree))


def _emit(name: str, ms: float, **extra) -> None:
    print(json.dumps({"op": name, "ms": round(ms, 2), **extra}), flush=True)


def suite_resnet(batch: int = 512, steps: int = 10, image_size: int = 224
                 ) -> Dict[str, float]:
    """Classic 7×7 stem vs space-to-depth stem, full fwd+bwd. Defaults to
    the imagenet_resnet50 bench shape (224²); ``image_size`` shrinks it for
    CPU smoke runs — stem-comparison numbers are only meaningful at 224.
    The s2d stem exists because the 7×7/s2 conv has 3 input channels —
    ~2% MXU lane packing (models/resnet.py)."""
    import jax
    import jax.numpy as jnp

    from .models import build_model

    if image_size % 2:
        raise ValueError(
            f"image_size must be even (s2d folds 2x2 blocks), got "
            f"{image_size}")
    results = {}
    x = jnp.zeros((batch, image_size, image_size, 3), jnp.bfloat16)
    y = jnp.zeros((batch,), jnp.int32)
    for name in ("resnet50", "resnet50_s2d"):
        model = build_model(name, num_classes=1000, dtype=jnp.bfloat16)
        variables = model.init(jax.random.PRNGKey(0), x[:8], train=True)
        params, bs = variables["params"], variables["batch_stats"]

        @jax.jit
        def step(p, x, y, tok, model=model, bs=bs):
            def lf(p):
                import optax
                logits, _ = model.apply(
                    {"params": p, "batch_stats": bs}, x + tok,
                    train=True, mutable=["batch_stats"])
                return optax.softmax_cross_entropy_with_integer_labels(
                    logits, y).mean()
            l, g = jax.value_and_grad(lf)(p)
            return l + _scalarize(g)

        ms = timed_scalar(step, params, x, y, steps=steps)
        results[name] = ms
        _emit(f"{name}_fwd_bwd", ms, batch=batch,
              img_per_s=round(batch / ms * 1000, 1))
    return results


def suite_detection(batch: int = 4, steps: int = 5, image_size: int = 0
                    ) -> Dict[str, float]:
    """Time the maskrcnn_coco train step's pieces at preset shapes."""
    import jax
    import jax.numpy as jnp

    from .ops.detection import multilevel_roi_align, nms_static
    from .presets import get_preset
    from .train.task import build_task
    from .train.detection_task import MASK_ROI_SIZE, ROI_SIZE, STRIDES

    cfg = get_preset("maskrcnn_coco")
    cfg.train.global_batch = batch
    if image_size:  # shrink for CPU smoke runs
        cfg.model.kwargs["image_size"] = image_size
        cfg.data.image_size = image_size
    task = build_task(cfg)
    B, S = batch, task.image_size
    results = {}

    rng = jax.random.PRNGKey(0)
    variables = task.init(rng)
    params = variables["params"]
    batch_stats = variables.get("batch_stats", {})
    max_boxes = cfg.data.max_boxes
    batch_data = {
        "image": jnp.zeros((B, S, S, 3), jnp.float32),
        "boxes": jnp.tile(jnp.asarray([[10.0, 10.0, 200.0, 200.0]]),
                          (B, max_boxes, 1)),
        "labels": jnp.ones((B, max_boxes), jnp.int32),
        "masks": jnp.ones((B, max_boxes, 28, 28), jnp.float32),
    }

    def run(name, fn, *args, n=steps, **extra):
        ms = timed_scalar(jax.jit(fn), *args, steps=n)
        results[name] = ms
        _emit(name, ms, **extra)

    # 1. Backbone + FPN + RPN heads, fwd+bwd (the conv compute).
    def bb(p, images, tok):
        def lf(p):
            out, _ = task.model.apply(
                {"params": p, "batch_stats": batch_stats}, images + tok,
                train=True, mutable=["batch_stats"])
            return (_scalarize(list(out["pyramid"].values()))
                    + _scalarize(out["rpn_logits"])
                    + _scalarize(out["rpn_deltas"]))
        l, g = jax.value_and_grad(lf)(p)
        return l + _scalarize(g)

    run("backbone_rpn_fwd_bwd", bb, params, batch_data["image"], batch=B)

    # Fixed RPN-shaped inputs for the post-backbone pieces.
    A = task.anchors.shape[0]
    rl = jax.random.normal(jax.random.PRNGKey(1), (B, A))
    rd = 0.1 * jax.random.normal(jax.random.PRNGKey(2), (B, A, 4))

    # 2. Proposal path: decode + top-k(pre_nms) + NMS. Forward-only (it is
    # stop_gradient'd in the loss).
    def props(rl, rd, tok):
        p, v = jax.vmap(task._proposals_infer)(rl + tok, rd)
        return _scalarize(p) + _scalarize(v)

    run("proposals_decode_topk_nms", props, rl, rd,
        anchors=int(A), pre_nms=task.pre_nms_topk,
        post_nms=task.post_nms_topk)

    # 3. top_k alone over the anchor scores (the sort-ish candidate).
    def topk_only(rl, tok):
        s, i = jax.lax.top_k(rl + tok, task.pre_nms_topk)
        return _scalarize(s) + _scalarize(i)

    run("rpn_top_k", topk_only, rl, anchors=int(A), k=task.pre_nms_topk)

    # 4. NMS alone at post-NMS width.
    kb = jax.random.uniform(jax.random.PRNGKey(3), (B, task.pre_nms_topk, 4))
    ks = jax.random.uniform(jax.random.PRNGKey(4), (B, task.pre_nms_topk))

    def nms_only(kb, ks, tok):
        idx, keep = jax.vmap(
            lambda b, s: nms_static(b, s + tok, task.nms_iou,
                                    task.post_nms_topk))(kb, ks)
        return _scalarize(idx) + _scalarize(keep)

    run("nms_static", nms_only, kb, ks, k=task.post_nms_topk)

    # 5. ROI-align fwd+bwd at box-head and mask-head shapes. P = post-NMS
    # proposals + appended GT (the train-path width).
    P = task.post_nms_topk + max_boxes
    pyramid = {
        lvl: jnp.zeros((B, max(1, S // st), max(1, S // st), 256),
                       jnp.bfloat16)
        for lvl, st in STRIDES.items()
    }
    boxes = jnp.tile(
        jnp.asarray([[8.0, 8.0, 264.0, 264.0]], jnp.float32), (B, P, 1))

    def roi(pyr, boxes, tok):
        def lf(pyr):
            rois = jax.vmap(lambda f, b: multilevel_roi_align(
                f, b, out_size=ROI_SIZE, strides=STRIDES))(pyr, boxes)
            return _scalarize(rois) + tok
        l, g = jax.value_and_grad(lf)(pyr)
        return l + _scalarize(g)

    run("roi_align_box_fwd_bwd", roi, pyramid, boxes,
        P=int(P), out=ROI_SIZE)

    m_boxes = boxes[:, :task.num_mask_rois]

    def roi_mask(pyr, boxes, tok):
        def lf(pyr):
            rois = jax.vmap(lambda f, b: multilevel_roi_align(
                f, b, out_size=MASK_ROI_SIZE, strides=STRIDES))(pyr, boxes)
            return _scalarize(rois) + tok
        l, g = jax.value_and_grad(lf)(pyr)
        return l + _scalarize(g)

    run("roi_align_mask_fwd_bwd", roi_mask, pyramid, m_boxes,
        P=int(task.num_mask_rois), out=MASK_ROI_SIZE)

    # 6. Box + mask heads fwd+bwd at ROI shapes.
    rois = jnp.zeros((B, P, ROI_SIZE, ROI_SIZE, 256), jnp.bfloat16)
    m_rois = jnp.zeros((B, task.num_mask_rois, MASK_ROI_SIZE,
                        MASK_ROI_SIZE, 256), jnp.bfloat16)

    def heads(p, rois, m_rois, tok):
        def lf(p):
            cls_logits, box_deltas = task.model.apply(
                {"params": p}, rois + tok, method=task.model.run_box_head)
            mask_logits = task.model.apply(
                {"params": p}, m_rois, method=task.model.run_mask_head)
            return (_scalarize(cls_logits) + _scalarize(box_deltas)
                    + _scalarize(mask_logits))
        l, g = jax.value_and_grad(lf)(p)
        return l + _scalarize(g)

    run("box_and_mask_heads_fwd_bwd", heads, params, rois, m_rois)

    # 7. Full loss fwd+bwd — the whole step minus optimizer (measured free).
    def full(p, batch_data, r, tok):
        def lf(p):
            l, m = task.loss_fn(p, batch_stats, batch_data, r, True)
            return l + tok
        l, g = jax.value_and_grad(lf)(p)
        return l + _scalarize(g)

    run("full_loss_fwd_bwd", full, params, batch_data, rng, batch=B)

    accounted = sum(v for k, v in results.items()
                    if k not in ("full_loss_fwd_bwd", "rpn_top_k"))
    _emit("sum_of_pieces", accounted, full=results.get("full_loss_fwd_bwd"))
    return results


def main(argv=None) -> None:
    import argparse

    from .runtime.platform import require_accelerator

    require_accelerator()

    parser = argparse.ArgumentParser(description=__doc__)
    parser.add_argument("--suite", default="detection",
                        choices=["detection", "resnet", "all"])
    parser.add_argument("--steps", type=int, default=5)
    parser.add_argument("--batch", type=int, default=0)
    parser.add_argument("--image-size", type=int, default=0,
                        help="override the input image size for BOTH suites "
                             "(CPU smoke; chip numbers should use the "
                             "defaults: resnet 224, detection 1024)")
    args = parser.parse_args(argv)
    if args.suite in ("resnet", "all"):
        suite_resnet(batch=args.batch or 512, steps=args.steps,
                     image_size=args.image_size or 224)
    if args.suite in ("detection", "all"):
        suite_detection(batch=args.batch or 4, steps=args.steps,
                        image_size=args.image_size)


if __name__ == "__main__":
    main()
