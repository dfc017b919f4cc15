"""Mask R-CNN training task: proposals, target assignment, losses.

The reference buried this logic in TensorPack's model zoo with dynamic
shapes and CUDA ops (SURVEY.md §3.1/§8); here every stage is a fixed-shape
jnp computation living inside the one jit-compiled train step:

1. RPN targets — dense anchor↔GT IoU assignment (no 256-anchor sampling:
   positives and negatives are averaged separately, which is deterministic,
   shape-static, and equivalent in expectation to balanced sampling).
2. Proposals — decode → top-K → dense NMS (ops/detection.nms_static), with
   GT boxes appended (the standard train-time stabilizer); stop_gradient.
3. RoI heads — multilevel ROI-align (gather-based), class+box losses over
   all valid proposals, mask loss over the top-`num_mask_rois` positives
   with GT masks resampled from GT-box-aligned to proposal-aligned frames.

All losses are global means over their own weight sums, so DP gradient
psum over the mesh stays correct (same contract as the other tasks).
"""

from __future__ import annotations

import functools
from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp
import numpy as np
import optax

from ..config import ExperimentConfig
from ..models import build_model
from .task import eval_params, example_mask, realized_eval_batches
from ..ops.detection import (
    decode_boxes,
    encode_boxes,
    generate_anchors,
    iou_matrix,
    multilevel_roi_align,
    nms_static,
    _bilinear_sample,
)

PyTree = Any

STRIDES = {2: 4, 3: 8, 4: 16, 5: 32, 6: 64}
LEVELS = (2, 3, 4, 5, 6)
ROI_SIZE = 7
MASK_ROI_SIZE = 14
MASK_SIZE = 28


def _huber(x, delta: float = 1.0):
    ax = jnp.abs(x)
    return jnp.where(ax <= delta, 0.5 * x * x, delta * (ax - 0.5 * delta))


def _mean_where(values, weights):
    return jnp.sum(values * weights) / jnp.maximum(jnp.sum(weights), 1.0)


class DetectionTask:
    """Loss-producing task for maskrcnn_* models (cfg preset maskrcnn_coco)."""

    exact_eval = True  # consume the padded full eval set (COCO protocol)

    def __init__(self, cfg: ExperimentConfig):
        self.cfg = cfg
        dtype = jnp.bfloat16 if cfg.train.dtype == "bfloat16" else jnp.float32
        kw = dict(cfg.model.kwargs)
        self.image_size = int(kw.pop("image_size", cfg.data.image_size))
        kw.pop("max_boxes", None)
        self.pre_nms_topk = int(kw.pop("pre_nms_topk", 1024))
        self.post_nms_topk = int(kw.pop("post_nms_topk", 256))
        self.num_mask_rois = int(kw.pop("num_mask_rois", 64))
        self.nms_iou = float(kw.pop("nms_iou", 0.7))
        anchor_scale = float(kw.pop("anchor_scale", 8.0))
        self.model = build_model(cfg.model.name, cfg.model.num_classes,
                                 dtype, **kw)
        self.spatial_dim = 1  # shard image H over the 'spatial' mesh axis
        self.spatial_keys = ("image",)  # masks' dim 1 is a box count
        self.param_rules = ()
        s = self.image_size
        self.anchors = generate_anchors(
            (s, s), strides=[STRIDES[l] for l in LEVELS],
            scales=[anchor_scale * STRIDES[l] for l in LEVELS])

    # -- init ---------------------------------------------------------------

    def init(self, rng: jax.Array):
        s = self.image_size
        images = jnp.zeros((1, s, s, 3), jnp.float32)

        def init_all(mdl):
            out = mdl(images, train=False)
            c = out["pyramid"][2].shape[-1]
            mdl.run_box_head(jnp.zeros((1, 8, ROI_SIZE, ROI_SIZE, c)))
            mdl.run_mask_head(
                jnp.zeros((1, 8, MASK_ROI_SIZE, MASK_ROI_SIZE, c)))
            return out

        return self.model.init(rng, method=init_all)

    # -- per-image pure functions -------------------------------------------

    def _rpn_targets(self, gt_boxes, gt_valid):
        """[A] cls target (1 pos / 0 neg / -1 ignore) + [A,4] box deltas."""
        iou = iou_matrix(self.anchors, gt_boxes)  # [A, G]
        iou = iou * gt_valid[None, :]
        max_iou = jnp.max(iou, axis=1)
        matched = jnp.argmax(iou, axis=1)
        pos = max_iou >= 0.7
        # Force-match: the best anchor for each valid GT is positive even
        # below threshold (keeps small objects trainable).
        best_anchor = jnp.argmax(iou, axis=0)  # [G]
        # .max, not .set: two GTs sharing a best anchor must not un-force it.
        force = jnp.zeros_like(pos).at[best_anchor].max(gt_valid > 0)
        pos = pos | force
        neg = (max_iou < 0.3) & ~pos
        cls_t = jnp.where(pos, 1.0, jnp.where(neg, 0.0, -1.0))
        box_t = encode_boxes(gt_boxes[matched], self.anchors)
        return cls_t, box_t

    def _proposals(self, rpn_logits, rpn_deltas, gt_boxes, gt_valid):
        """→ boxes [P,4], valid [P] with P = post_nms_topk + max_boxes:
        the inference proposals plus appended GT boxes (the standard
        train-time stabilizer)."""
        props, keep = self._proposals_infer(rpn_logits, rpn_deltas)
        props = jnp.concatenate([props, gt_boxes], axis=0)
        valid = jnp.concatenate([keep, gt_valid > 0], axis=0)
        return jax.lax.stop_gradient(props), valid

    def _roi_targets(self, props, valid, gt_boxes, gt_labels, gt_valid):
        iou = iou_matrix(props, gt_boxes) * gt_valid[None, :]
        max_iou = jnp.max(iou, axis=1)
        matched = jnp.argmax(iou, axis=1)
        pos = (max_iou >= 0.5) & valid
        cls_t = jnp.where(pos, gt_labels[matched], 0)  # 0 = background
        box_t = encode_boxes(gt_boxes[matched], props)
        return cls_t, box_t, pos, matched, max_iou

    @staticmethod
    def _resample_mask(gt_mask, gt_box, prop):
        """GT-box-aligned [28,28] mask → proposal-aligned [28,28] target."""
        gy0, gx0, gy1, gx1 = gt_box[0], gt_box[1], gt_box[2], gt_box[3]
        gh = jnp.maximum(gy1 - gy0, 1e-3)
        gw = jnp.maximum(gx1 - gx0, 1e-3)
        py = prop[0] + (jnp.arange(MASK_SIZE) + 0.5) / MASK_SIZE * \
            jnp.maximum(prop[2] - prop[0], 1e-3)
        px = prop[1] + (jnp.arange(MASK_SIZE) + 0.5) / MASK_SIZE * \
            jnp.maximum(prop[3] - prop[1], 1e-3)
        ys = (py - gy0) / gh * MASK_SIZE - 0.5
        xs = (px - gx0) / gw * MASK_SIZE - 0.5
        yy = jnp.broadcast_to(ys[:, None], (MASK_SIZE, MASK_SIZE))
        xx = jnp.broadcast_to(xs[None, :], (MASK_SIZE, MASK_SIZE))
        return _bilinear_sample(gt_mask[:, :, None], yy, xx)[..., 0]

    # -- inference ----------------------------------------------------------

    def _proposals_infer(self, rpn_logits, rpn_deltas):
        """Inference proposals: decode → top-K → NMS (no GT append)."""
        scores = jax.nn.sigmoid(rpn_logits)
        boxes = decode_boxes(rpn_deltas, self.anchors,
                             clip_hw=(self.image_size, self.image_size))
        k = min(self.pre_nms_topk, scores.shape[0])
        top_scores, top_idx = jax.lax.top_k(scores, k)
        top_boxes = boxes[top_idx]
        keep_idx, keep = nms_static(top_boxes, top_scores, self.nms_iou,
                                    min(self.post_nms_topk, k))
        return top_boxes[keep_idx], keep

    def _detect_one(self, cls_probs, box_deltas, props, valid,
                    topk: int, score_thr: float, nms_iou: float):
        """Per-image post-processing: class-specific box decode, per-class
        NMS, global top-K → fixed-K (boxes [K,4], scores [K], classes [K],
        class 0 = empty slot). All static shapes — the per-class loop is a
        vmap over the (C-1)×P score/delta planes."""
        num_classes = cls_probs.shape[-1]
        s = self.image_size
        p = cls_probs.shape[0]
        k_per_class = min(topk, p)

        def per_class(c_probs, c_deltas):
            boxes_c = decode_boxes(c_deltas, props, clip_hw=(s, s))
            ok = valid & (c_probs >= score_thr)
            idx, keep = nms_static(boxes_c, c_probs, nms_iou, k_per_class,
                                   valid=ok)
            return boxes_c[idx], jnp.where(keep, c_probs[idx], 0.0)

        fg_probs = jnp.moveaxis(cls_probs[:, 1:], 1, 0)      # [C-1, P]
        fg_deltas = jnp.moveaxis(box_deltas[:, 1:, :], 1, 0)  # [C-1, P, 4]
        boxes_pc, scores_pc = jax.vmap(per_class)(fg_probs, fg_deltas)
        classes_pc = jnp.broadcast_to(
            jnp.arange(1, num_classes, dtype=jnp.int32)[:, None],
            scores_pc.shape)
        flat_boxes = boxes_pc.reshape(-1, 4)
        flat_scores = scores_pc.reshape(-1)
        flat_classes = classes_pc.reshape(-1)
        k_out = min(topk, flat_scores.shape[0])
        top_scores, top_i = jax.lax.top_k(flat_scores, k_out)
        out_boxes = flat_boxes[top_i]
        out_classes = jnp.where(top_scores > 0.0, flat_classes[top_i], 0)
        return out_boxes, top_scores, out_classes

    def predict_fn(self, topk: int, score_thr: float, nms_iou: float):
        """Build the jittable full inference step:
        (variables, images) → {boxes [B,K,4], scores, classes, masks}."""

        def infer(mdl, images):
            out = mdl(images, train=False)
            props, valid = jax.vmap(self._proposals_infer)(
                out["rpn_logits"], out["rpn_deltas"])
            align = functools.partial(
                multilevel_roi_align, out_size=ROI_SIZE, strides=STRIDES)
            rois = jax.vmap(lambda f, b: align(f, b))(out["pyramid"], props)
            cls_logits, box_deltas = mdl.run_box_head(rois)
            cls_probs = jax.nn.softmax(cls_logits.astype(jnp.float32), -1)
            boxes, scores, classes = jax.vmap(
                lambda cp, bd, pr, va: self._detect_one(
                    cp, bd, pr, va, topk, score_thr, nms_iou)
            )(cls_probs, box_deltas, props, valid)
            m_rois = jax.vmap(lambda f, b: multilevel_roi_align(
                f, b, out_size=MASK_ROI_SIZE, strides=STRIDES))(
                    out["pyramid"], boxes)
            mask_logits = mdl.run_mask_head(m_rois)
            m = jnp.take_along_axis(
                mask_logits, classes[:, :, None, None, None], axis=4)[..., 0]
            masks = jax.nn.sigmoid(m.astype(jnp.float32))
            return {"boxes": boxes, "scores": scores, "classes": classes,
                    "masks": masks}

        def predict(variables, images):
            return self.model.apply(variables, images, method=infer)

        return jax.jit(predict)

    def final_eval(self, state, eval_iter_fn, trainer):
        """COCO-style box/mask mAP over the eval set — the TensorPack Mask
        R-CNN workload's acceptance metric (BASELINE.md row 5). Runs the
        static-shape inference path per batch and streams per-image results
        into metrics/coco_map.DetectionAccumulator."""
        from ..metrics.coco_map import DetectionAccumulator

        ev = self.cfg.eval
        if not ev.enabled:
            return {}
        variables = {"params": eval_params(state)}
        if state.batch_stats:
            variables["batch_stats"] = state.batch_stats
        predict = self.predict_fn(ev.detect_topk, ev.detect_score_threshold,
                                  ev.detect_nms_iou)
        eb = self.cfg.train.eval_batch or self.cfg.train.global_batch
        acc = DetectionAccumulator()
        s = self.image_size
        for det, gt, emask in realized_eval_batches(
                trainer, eb, eval_iter_fn,
                lambda dev: predict(variables, dev["image"]),
                batch_keys=("boxes", "labels", "masks")):
            for i in range(det["boxes"].shape[0]):
                if emask is not None and emask[i] == 0:
                    continue
                acc.add_image(
                    det["boxes"][i], det["scores"][i], det["classes"][i],
                    gt["boxes"][i], gt["labels"][i],
                    pred_masks=det["masks"][i], gt_masks=gt["masks"][i],
                    image_hw=(s, s))
        return acc.compute(with_masks=True)

    # -- loss ---------------------------------------------------------------

    def loss_fn(self, params, batch_stats, batch, rng, train
                ) -> Tuple[jnp.ndarray, Dict[str, jnp.ndarray]]:
        cfg = self.cfg
        nmr = self.num_mask_rois

        def forward(mdl, batch):
            images = batch["image"]
            gt_boxes = batch["boxes"].astype(jnp.float32)
            gt_labels = batch["labels"]
            gt_valid = (gt_labels > 0).astype(jnp.float32)
            # Padded eval-tail examples (exact_eval contract) carry zero
            # weight in every loss/metric; matching stays per-image so
            # zero-weight images never affect real ones.
            ex = example_mask(batch, images.shape[0])
            out = mdl(images, train=train)

            # RPN losses (vmapped target assignment, dense weighting).
            cls_t, box_t = jax.vmap(self._rpn_targets)(gt_boxes, gt_valid)
            rpn_bce = optax.sigmoid_binary_cross_entropy(
                out["rpn_logits"], jnp.maximum(cls_t, 0.0))
            pos_w = (cls_t == 1.0).astype(jnp.float32) * ex[:, None]
            neg_w = (cls_t == 0.0).astype(jnp.float32) * ex[:, None]
            rpn_cls_loss = _mean_where(rpn_bce, pos_w) + \
                _mean_where(rpn_bce, neg_w)
            rpn_box_loss = _mean_where(
                _huber(out["rpn_deltas"] - box_t).sum(-1), pos_w)

            # Proposals + RoI targets.
            props, valid = jax.vmap(self._proposals)(
                out["rpn_logits"], out["rpn_deltas"], gt_boxes, gt_valid)
            roi_cls_t, roi_box_t, roi_pos, matched, max_iou = jax.vmap(
                self._roi_targets)(props, valid, gt_boxes, gt_labels,
                                   gt_valid)

            # Box head on all proposals.
            align = functools.partial(
                multilevel_roi_align, out_size=ROI_SIZE, strides=STRIDES)
            rois = jax.vmap(lambda f, b: align(f, b))(
                out["pyramid"], props)
            cls_logits, box_deltas = mdl.run_box_head(rois)
            valid_f = valid.astype(jnp.float32) * ex[:, None]
            pos_f = roi_pos.astype(jnp.float32) * ex[:, None]
            ce = optax.softmax_cross_entropy_with_integer_labels(
                cls_logits, roi_cls_t)
            roi_cls_loss = _mean_where(ce, valid_f)
            # Class-specific deltas at the target class.
            sel = jnp.take_along_axis(
                box_deltas, roi_cls_t[:, :, None, None].astype(jnp.int32)
                .repeat(4, -1), axis=2)[:, :, 0, :]
            roi_box_loss = _mean_where(
                _huber(sel - roi_box_t).sum(-1), pos_f)

            # Mask head on the top positives (static top-k by match score).
            mask_score = max_iou * pos_f
            _, mask_sel = jax.lax.top_k(mask_score, nmr)  # [B, nmr]
            take = lambda a, i: jnp.take_along_axis(
                a, i.reshape(i.shape + (1,) * (a.ndim - 2)), axis=1)
            m_props = take(props, mask_sel)
            m_pos = jnp.take_along_axis(pos_f, mask_sel, axis=1)
            m_cls = jnp.take_along_axis(roi_cls_t, mask_sel, axis=1)
            m_matched = jnp.take_along_axis(matched, mask_sel, axis=1)
            m_rois = jax.vmap(lambda f, b: multilevel_roi_align(
                f, b, out_size=MASK_ROI_SIZE, strides=STRIDES))(
                    out["pyramid"], m_props)
            mask_logits = mdl.run_mask_head(m_rois)  # [B,nmr,28,28,C]
            m_gt_masks = take(batch["masks"], m_matched)
            m_gt_boxes = take(gt_boxes, m_matched)
            mask_t = jax.vmap(jax.vmap(self._resample_mask))(
                m_gt_masks, m_gt_boxes, m_props)
            m_logit = jnp.take_along_axis(
                mask_logits,
                m_cls[:, :, None, None, None].astype(jnp.int32),
                axis=4)[..., 0]
            mask_bce = optax.sigmoid_binary_cross_entropy(
                m_logit, jax.lax.stop_gradient(mask_t)).mean((-1, -2))
            mask_loss = _mean_where(mask_bce, m_pos)

            # Proposal recall @0.5 — the convergence signal for tests.
            prop_gt_iou = jax.vmap(iou_matrix)(props, gt_boxes)
            best = jnp.max(prop_gt_iou * valid_f[:, :, None], axis=1)
            recall = _mean_where((best >= 0.5).astype(jnp.float32),
                                 gt_valid * ex[:, None])

            losses = {
                "rpn_cls_loss": rpn_cls_loss,
                "rpn_box_loss": rpn_box_loss,
                "roi_cls_loss": roi_cls_loss,
                "roi_box_loss": roi_box_loss,
                "mask_loss": mask_loss,
            }
            total = sum(losses.values())
            metrics = {**losses, "proposal_recall": recall}
            if not train:
                metrics["eval_weight"] = jnp.sum(ex)
            return total, metrics

        variables = {"params": params}
        if batch_stats:
            variables["batch_stats"] = batch_stats
        if train:
            (total, metrics), mutated = self.model.apply(
                variables, batch, method=forward, mutable=["batch_stats"])
            metrics["batch_stats"] = mutated.get("batch_stats", batch_stats)
        else:
            total, metrics = self.model.apply(variables, batch,
                                              method=forward)
        return total, metrics
