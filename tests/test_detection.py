"""Tests for the Mask R-CNN workload: detection-op numerics (IoU, box
codec, static NMS, ROI-align), data-source invariants, and short-horizon
end-to-end training (SURVEY.md §8 hard-part #1 made testable on CPU)."""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deeplearning_cfn_tpu.config import (
    CheckpointConfig,
    DataConfig,
    ExperimentConfig,
    MeshConfig,
    ModelConfig,
    OptimizerConfig,
    ScheduleConfig,
    TrainConfig,
)
from deeplearning_cfn_tpu.data.detection import make_detection_source
from deeplearning_cfn_tpu.metrics import read_metrics
from deeplearning_cfn_tpu.ops.detection import (
    decode_boxes,
    encode_boxes,
    generate_anchors,
    iou_matrix,
    multilevel_roi_align,
    nms_static,
    roi_align,
)
from deeplearning_cfn_tpu.train.run import run_experiment


# -- box math ---------------------------------------------------------------


def test_iou_matrix_basics():
    a = jnp.asarray([[0, 0, 10, 10], [0, 0, 5, 5]], jnp.float32)
    b = jnp.asarray([[0, 0, 10, 10], [5, 5, 10, 10], [20, 20, 30, 30]],
                    jnp.float32)
    iou = np.asarray(iou_matrix(a, b))
    np.testing.assert_allclose(iou[0], [1.0, 0.25, 0.0], atol=1e-6)
    np.testing.assert_allclose(iou[1, 0], 0.25, atol=1e-6)
    assert iou[1, 1] == 0.0  # touching corners, no overlap


def test_box_codec_roundtrip():
    rng = np.random.RandomState(0)
    anchors = jnp.asarray(
        np.stack([rng.uniform(0, 50, 32), rng.uniform(0, 50, 32),
                  rng.uniform(60, 100, 32), rng.uniform(60, 100, 32)], 1),
        jnp.float32)
    boxes = anchors + jnp.asarray(rng.uniform(-5, 5, (32, 4)), jnp.float32)
    deltas = encode_boxes(boxes, anchors)
    back = decode_boxes(deltas, anchors)
    np.testing.assert_allclose(np.asarray(back), np.asarray(boxes),
                               atol=1e-3, rtol=1e-4)


def test_nms_static_suppresses():
    boxes = jnp.asarray([
        [0, 0, 10, 10],      # score .9 — kept
        [1, 1, 11, 11],      # heavy overlap with 0 — suppressed
        [50, 50, 60, 60],    # disjoint — kept
        [0, 0, 10.5, 10.5],  # overlap with 0 — suppressed
    ], jnp.float32)
    scores = jnp.asarray([0.9, 0.8, 0.7, 0.6])
    idx, keep = nms_static(boxes, scores, iou_threshold=0.5, max_outputs=4)
    kept = set(np.asarray(idx)[np.asarray(keep)].tolist())
    assert kept == {0, 2}


def test_nms_static_padding_sentinels_and_valid_mask():
    """Padded candidates must never appear in the output — whether marked
    by the finite -1e30 sentinel convention or by an explicit validity
    mask (regression: exact -inf was the only recognized padding)."""
    boxes = jnp.asarray([
        [0, 0, 10, 10],
        [50, 50, 60, 60],
        [0, 0, 0, 0],      # padding
        [0, 0, 0, 0],      # padding
    ], jnp.float32)
    scores = jnp.asarray([0.9, 0.8, -1e30, -1e30])
    idx, keep = nms_static(boxes, scores, iou_threshold=0.5, max_outputs=4)
    kept = set(np.asarray(idx)[np.asarray(keep)].tolist())
    assert kept == {0, 1}

    # Explicit validity mask overrides scores: box 1 is masked out even
    # though its score is high.
    valid = jnp.asarray([True, False, False, False])
    idx, keep = nms_static(boxes, scores, iou_threshold=0.5, max_outputs=4,
                           valid=valid)
    kept = set(np.asarray(idx)[np.asarray(keep)].tolist())
    assert kept == {0}


def test_roi_align_identity_crop():
    """Aligning a box that covers exactly the feature map reproduces it
    (up to bilinear smoothing at the bin centers)."""
    feat = jnp.arange(16, dtype=jnp.float32).reshape(4, 4, 1)
    out = roi_align(feat, jnp.asarray([[0.0, 0.0, 4.0, 4.0]]), out_size=4,
                    sampling_ratio=1)
    np.testing.assert_allclose(np.asarray(out)[0, :, :, 0],
                               np.asarray(feat)[:, :, 0], atol=1e-5)


def test_roi_align_constant_region():
    feat = jnp.ones((8, 8, 3)) * 5.0
    out = roi_align(feat, jnp.asarray([[2.0, 2.0, 6.0, 6.0]]), out_size=2)
    np.testing.assert_allclose(np.asarray(out), 5.0, atol=1e-5)


def test_multilevel_roi_align_routes_by_size():
    feats = {2: jnp.ones((32, 32, 1)) * 2.0, 3: jnp.ones((16, 16, 1)) * 3.0}
    strides = {2: 4, 3: 8}
    # Small box → level 2, huge box → clipped to level 3.
    boxes = jnp.asarray([[0, 0, 8, 8], [0, 0, 120, 120]], jnp.float32)
    out = multilevel_roi_align(feats, boxes, out_size=2, strides=strides,
                               canonical_level=2, canonical_size=16.0)
    assert np.allclose(np.asarray(out)[0], 2.0)
    assert np.allclose(np.asarray(out)[1], 3.0)


def test_multilevel_roi_align_matches_dense_reference():
    """The flat-pyramid single-gather formulation must equal the dense
    reference (align every box on every level, one-hot select by target
    level) bit-for-bit in f32 — including boxes hanging off the map edge
    and degenerate boxes."""
    import jax

    rng = np.random.RandomState(0)
    strides = {2: 4, 3: 8, 4: 16}
    feats = {
        lvl: jnp.asarray(rng.randn(64 // (2 ** i), 64 // (2 ** i), 8),
                         jnp.float32)
        for i, lvl in enumerate(sorted(strides))
    }
    boxes = jnp.asarray(np.concatenate([
        rng.uniform(0, 256, (12, 4)),
        [[(-8.0), -8.0, 20.0, 20.0],     # off the top-left edge
         [200.0, 200.0, 400.0, 400.0],   # off the bottom-right edge
         [17.0, 17.0, 17.0, 17.0]],      # degenerate (zero-area)
    ]), jnp.float32)
    boxes = jnp.stack([
        jnp.minimum(boxes[:, 0], boxes[:, 2]),
        jnp.minimum(boxes[:, 1], boxes[:, 3]),
        jnp.maximum(boxes[:, 0], boxes[:, 2]),
        jnp.maximum(boxes[:, 1], boxes[:, 3]),
    ], axis=1)

    def dense_reference(feats, boxes, out_size):
        levels = sorted(feats)
        from deeplearning_cfn_tpu.ops.detection import EPS, box_area
        sqrt_area = jnp.sqrt(jnp.maximum(box_area(boxes), EPS))
        target = jnp.floor(4 + jnp.log2(sqrt_area / 224.0 + EPS))
        target = jnp.clip(target, levels[0], levels[-1]).astype(jnp.int32)
        outs = [roi_align(feats[lvl], boxes, out_size,
                          spatial_scale=1.0 / strides[lvl])
                for lvl in levels]
        stacked = jnp.stack(outs, axis=0)
        sel = (target[None, :] == jnp.asarray(
            levels, jnp.int32)[:, None]).astype(stacked.dtype)
        return jnp.einsum("lnhwc,ln->nhwc", stacked, sel)

    # Each side one compiled program: run operation by operation, the two
    # are some 230 tiny programs to compile.
    for out_size in (7, 14):
        got = jax.jit(lambda f, b: multilevel_roi_align(
            f, b, out_size, strides))(feats, boxes)
        want = jax.jit(lambda f, b: dense_reference(f, b, out_size))(
            feats, boxes)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=1e-5, atol=1e-5)

    # Gradients must match too (the align sits inside the train step).
    def loss_new(f):
        return jnp.sum(multilevel_roi_align(f, boxes, 7, strides) ** 2)

    def loss_ref(f):
        return jnp.sum(dense_reference(f, boxes, 7) ** 2)

    g_new = jax.jit(jax.grad(loss_new))(feats)
    g_ref = jax.jit(jax.grad(loss_ref))(feats)
    for lvl in feats:
        np.testing.assert_allclose(np.asarray(g_new[lvl]),
                                   np.asarray(g_ref[lvl]),
                                   rtol=1e-4, atol=1e-4)


def test_generate_anchors_layout():
    anchors = generate_anchors((32, 32), strides=[8, 16], scales=[16, 32])
    # 4*4*3 + 2*2*3 anchors, all finite, centers inside the image.
    assert anchors.shape == (60, 4)
    assert np.isfinite(np.asarray(anchors)).all()
    centers = np.asarray((anchors[:, :2] + anchors[:, 2:]) / 2)
    assert (centers >= 0).all() and (centers <= 32).all()


# -- data -------------------------------------------------------------------


def test_detection_source_invariants():
    src = make_detection_source(16, image_size=64, num_classes=7,
                                max_boxes=8, seed=0)
    a = src.arrays
    assert a["image"].shape == (16, 64, 64, 3)
    assert a["boxes"].shape == (16, 8, 4)
    assert a["masks"].shape == (16, 8, 28, 28)
    valid = a["labels"] > 0
    assert valid.any() and (a["labels"] < 7).all()
    b = a["boxes"][valid]
    assert (b[:, 2] > b[:, 0]).all() and (b[:, 3] > b[:, 1]).all()
    assert (b >= 0).all() and (b <= 64).all()
    # Masks nontrivial for valid objects, empty for padding.
    assert a["masks"][valid].max() == 1.0
    assert a["masks"][~valid].sum() == 0.0


# -- end-to-end -------------------------------------------------------------


def _tiny_cfg():
    return ExperimentConfig(
        model=ModelConfig(
            name="maskrcnn_resnet50", num_classes=7,
            kwargs=dict(image_size=64, pre_nms_topk=64, post_nms_topk=16,
                        num_mask_rois=4, anchor_scale=4.0)),
        data=DataConfig(name="coco", image_size=64, num_train_examples=32,
                        num_eval_examples=4, max_boxes=4),
        train=TrainConfig(global_batch=4, dtype="float32", eval_batch=4,
                          log_every_steps=2),
        optimizer=OptimizerConfig(name="momentum", momentum=0.9,
                                  weight_decay=1e-4, grad_clip_norm=10.0),
        schedule=ScheduleConfig(name="constant", base_lr=0.01,
                                warmup_steps=5),
        # data=4 × model=2 fills the 8 fake devices at global_batch 4
        # (the idle 'model' axis just replicates — params have no TP rules).
        mesh=MeshConfig(data=4, model=2),
        checkpoint=CheckpointConfig(async_write=False),
    )


def test_detect_one_postprocessing():
    """Per-class NMS + global top-K on hand-crafted head outputs: duplicate
    boxes of the same class are suppressed, same-position boxes of distinct
    classes both survive, sub-threshold and invalid proposals drop out."""
    from deeplearning_cfn_tpu.train.detection_task import DetectionTask

    task = DetectionTask(_tiny_cfg())
    p, c = 6, 3  # 6 proposals, background + 2 foreground classes
    props = jnp.asarray(np.array([
        [0, 0, 10, 10],
        [0, 1, 10, 11],    # heavy overlap with 0 → NMS victim (class 1)
        [30, 30, 40, 40],  # distinct location, class 2
        [0, 0, 10, 10],    # same place as 0 but class 2 → must survive
        [50, 50, 60, 60],  # below score threshold
        [70, 70, 80, 80],  # invalid proposal
    ], np.float32))
    valid = jnp.asarray([True, True, True, True, True, False])
    probs = np.full((p, c), 0.01, np.float32)
    probs[0, 1] = 0.9
    probs[1, 1] = 0.8
    probs[2, 2] = 0.7
    probs[3, 2] = 0.6
    probs[4, 1] = 0.04  # below the 0.05 floor
    probs[5, 1] = 0.9   # invalid → ignored
    deltas = jnp.zeros((p, c, 4), np.float32)
    boxes, scores, classes = task._detect_one(
        jnp.asarray(probs), deltas, props, valid,
        topk=4, score_thr=0.05, nms_iou=0.5)
    boxes, scores, classes = map(np.asarray, (boxes, scores, classes))
    kept = [(int(c_), float(s)) for c_, s in zip(classes, scores) if c_ > 0]
    assert kept == [(1, pytest.approx(0.9)), (2, pytest.approx(0.7)),
                    (2, pytest.approx(0.6))], kept
    # Survivor boxes: 0 (cls 1), 2 and 3 (cls 2) — deltas were zero so the
    # output boxes equal the proposals.
    np.testing.assert_allclose(boxes[0], props[0])
    np.testing.assert_allclose(boxes[1], props[2])
    np.testing.assert_allclose(boxes[2], props[3])


def test_deconv_to_upsample_conversion():
    """Pin the pre-round-4 checkpoint conversion: a 2×2/stride-2
    SAME ConvTranspose and Dense(converted weights) + MaskHead's
    depth-to-space must agree to f32 rounding. flax ConvTranspose puts
    kernel tap (a, b) at output offset (1-a, 1-b), so the conversion must
    flip both spatial axes — the unflipped formula swaps every 2×2 block."""
    import flax.linen as nn

    from deeplearning_cfn_tpu.models.maskrcnn import convert_deconv_to_upsample

    c, c_out = 5, 7
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.normal(size=(2, 3, 3, c)), jnp.float32)
    w_convt = jnp.asarray(rng.normal(size=(2, 2, c, c_out)), jnp.float32)

    deconv = nn.ConvTranspose(c_out, (2, 2), strides=(2, 2), padding="SAME",
                              use_bias=False)
    ref = deconv.apply({"params": {"kernel": w_convt}}, x)

    w_dense = convert_deconv_to_upsample(np.asarray(w_convt))
    y = x @ jnp.asarray(w_dense)  # [B, s, s, 4*Cout]
    b, s = x.shape[0], x.shape[1]
    y = y.reshape(b, s, s, 2, 2, c_out)
    y = y.transpose(0, 1, 3, 2, 4, 5).reshape(b, 2 * s, 2 * s, c_out)
    np.testing.assert_allclose(np.asarray(y), np.asarray(ref), atol=1e-6)

    # The unflipped formula must NOT match — guards against the doc bug
    # silently coming back.
    w_bad = np.asarray(w_convt).transpose(2, 0, 1, 3).reshape(c, 4 * c_out)
    y_bad = x @ jnp.asarray(w_bad)
    y_bad = y_bad.reshape(b, s, s, 2, 2, c_out)
    y_bad = y_bad.transpose(0, 1, 3, 2, 4, 5).reshape(b, 2 * s, 2 * s, c_out)
    assert np.abs(np.asarray(y_bad) - np.asarray(ref)).max() > 0.1

    with pytest.raises(ValueError):
        convert_deconv_to_upsample(np.zeros((3, 3, c, c_out)))


def test_maskrcnn_trains_end_to_end(tmp_workdir):
    """Full pipeline: synthetic COCO → RPN/RoI/mask losses all finite and
    the total improving over a short horizon."""
    cfg = _tiny_cfg()
    cfg.workdir = os.path.join(tmp_workdir, "work")
    cfg.train.steps = 6  # CPU detection steps are ~40s; keep the horizon short
    cfg.train.eval_every_steps = 1000  # skip mid-run eval (compile cost)
    cfg.data.prefetch = 0
    cfg.eval.detect_topk = 8  # keep the inference compile small on CPU
    final = run_experiment(cfg)
    records = [r for r in read_metrics(
        os.path.join(cfg.workdir, "maskrcnn_resnet50", "metrics.jsonl"))
        if "loss" in r]
    assert records, "no train metrics logged"
    for r in records:
        for key in ["rpn_cls_loss", "rpn_box_loss", "roi_cls_loss",
                    "roi_box_loss", "mask_loss", "proposal_recall"]:
            assert key in r and np.isfinite(r[key]), (key, r)
    first, last = records[0], records[-1]
    assert last["loss"] < first["loss"], (first["loss"], last["loss"])
    # Acceptance metric: the final eval runs the static-shape inference path
    # (per-class NMS → fixed-K boxes + masks) and scores COCO-style mAP —
    # 6 steps won't produce detections that match GT, but the full pipeline
    # must execute and land final_eval_map / final_eval_mask_map in
    # metrics.jsonl (BASELINE.md tracking row 5).
    for key in ("map", "map50", "mask_map"):
        assert key in final and np.isfinite(final[key]) \
            and 0.0 <= final[key] <= 1.0, (key, final)
    logged = [r for r in read_metrics(
        os.path.join(cfg.workdir, "maskrcnn_resnet50", "metrics.jsonl"))
        if "final_eval_map" in r]
    assert logged and "final_eval_mask_map" in logged[-1]


def test_maskrcnn_spatial_shard_compiles(devices, tmp_workdir):
    """The data+spatial shard (SURVEY.md §3.2's one beyond-DP strategy):
    mesh data=4 × spatial=2, image H sharded — one step must compile and
    produce finite losses."""
    cfg = _tiny_cfg()
    cfg.workdir = os.path.join(tmp_workdir, "work")
    cfg.mesh = MeshConfig(data=4, spatial=2)
    cfg.train.steps = 2
    cfg.train.eval_every_steps = 1000
    cfg.data.prefetch = 0
    # Keep final eval ON: the inference path (predict_fn's NMS/top-k/
    # roi-align) must also compile and run with the image spatially sharded
    # — a production multichip run hits it at the very end of training.
    cfg.eval.detect_topk = 4
    final = run_experiment(cfg)
    assert np.isfinite(final["loss"])
    assert "map" in final and np.isfinite(final["map"])
