"""Task definitions: glue a model into the Trainer's loss_fn contract.

The reference expressed this per-script (each example had its own loss/metric
code inline — SURVEY.md §3.1); here a Task builds the ``loss_fn(params,
batch_stats, batch, rng, train)`` closure from a Flax module plus the config,
so every workload shares one trainer.
"""

from __future__ import annotations

from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp
import numpy as np
import optax

from ..config import ExperimentConfig
from ..models import build_model
from ..obs.trace import get_tracer

PyTree = Any

# MoE auxiliary-loss weights (ST-MoE's standard values); applied by tasks
# whose model reports router losses (models/moe.py).
MOE_LOAD_BALANCE_WEIGHT = 0.01
MOE_ROUTER_Z_WEIGHT = 0.001


def cross_entropy(logits: jnp.ndarray, labels: jnp.ndarray,
                  smoothing: float = 0.0) -> jnp.ndarray:
    num_classes = logits.shape[-1]
    if smoothing > 0:
        on = 1.0 - smoothing
        off = smoothing / (num_classes - 1)
        targets = jax.nn.one_hot(labels, num_classes) * (on - off) + off
        return optax.softmax_cross_entropy(logits, targets)
    return optax.softmax_cross_entropy_with_integer_labels(logits, labels)


def eval_params(state) -> PyTree:
    """EMA params when tracked, else the live params — the same preference
    Trainer.eval_step applies."""
    return state.ema_params if state.ema_params is not None else state.params


def realized_eval_batches(trainer, eval_batch: int, eval_iter_fn,
                          compute, batch_keys: Tuple[str, ...] = ()):
    """Drive a jitted ``compute(dev_batch)`` over the eval set and realize
    results to host: yields ``(outputs, batch_subset, eval_mask_or_None)``
    per batch, each as numpy-compatible host values. In multi-process runs
    the outputs (and the requested batch keys + eval_mask) are allgathered
    so every process sees the full global batch — final acceptance metrics
    (BLEU, mAP) are then exact everywhere, not per-shard approximations.
    """
    for batch in eval_iter_fn():
        dev = trainer.device_batch(batch, global_batch=eval_batch)
        out = compute(dev)
        extra = {k: dev[k] for k in batch_keys}
        emask = dev.get("eval_mask")
        if jax.process_count() > 1:
            from jax.experimental import multihost_utils

            out, extra = multihost_utils.process_allgather((out, extra))
            if emask is not None:
                emask = multihost_utils.process_allgather(emask)
        out = jax.device_get(out)
        extra = jax.device_get(extra)
        emask = None if emask is None else np.asarray(jax.device_get(emask))
        yield out, extra, emask


def example_mask(batch: Dict[str, jnp.ndarray], n: int) -> jnp.ndarray:
    """Per-example validity [B]: the pipeline's eval-tail padding mask when
    present (drop_remainder=False), else all-ones. Tasks weight every eval
    metric by it so padded examples contribute exactly nothing — and the
    trainer aggregates across batches by these weights, making metrics
    exact over the full eval set."""
    mask = batch.get("eval_mask")
    return jnp.ones((n,), jnp.float32) if mask is None else mask


class ClassificationTask:
    """Image classification (CIFAR ResNet-20, ImageNet ResNet-50).

    Batch contract: ``{"image": [B,H,W,C] float32, "label": [B] int32}``.
    """

    exact_eval = True  # consumes eval_mask; gets the padded full eval set

    def __init__(self, cfg: ExperimentConfig):
        self.cfg = cfg
        dtype = jnp.bfloat16 if cfg.train.dtype == "bfloat16" else jnp.float32
        self.model = build_model(
            cfg.model.name, cfg.model.num_classes, dtype, **cfg.model.kwargs
        )
        # A model family owns its tensor-parallel rules: read PARAM_RULES
        # from the model's defining module (vit exports the transformer
        # rules; resnet exports none). Name-prefix checks here would
        # silently drop TP for any new transformer classifier.
        import sys

        self.param_rules = getattr(
            sys.modules[type(self.model).__module__], "PARAM_RULES", ())

    def init(self, rng: jax.Array):
        shape = (1, self.cfg.data.image_size, self.cfg.data.image_size, 3)
        dummy = jnp.zeros(shape, jnp.float32)
        return self.model.init(rng, dummy, train=False)

    def _forward_train(self, params, batch_stats, images, rng):
        variables = {"params": params}
        rngs = {"dropout": rng} if rng is not None else None
        if batch_stats:
            variables["batch_stats"] = batch_stats
            logits, mutated = self.model.apply(
                variables, images, train=True, mutable=["batch_stats"],
                rngs=rngs,
            )
            return logits, mutated.get("batch_stats", batch_stats)
        # Stats-free models (ViT): still a true train-mode forward —
        # dropout active, driven by the step rng.
        return self.model.apply(variables, images, train=True,
                                rngs=rngs), batch_stats

    def loss_fn(self, params: PyTree, batch_stats: PyTree,
                batch: Dict[str, jnp.ndarray], rng, train: bool
                ) -> Tuple[jnp.ndarray, Dict[str, jnp.ndarray]]:
        has_stats = bool(batch_stats)
        if train:
            logits, new_stats = self._forward_train(
                params, batch_stats, batch["image"], rng)
        else:
            variables = {"params": params}
            if has_stats:
                variables["batch_stats"] = batch_stats
            logits = self.model.apply(variables, batch["image"], train=False)
            new_stats = batch_stats
        # Global-batch (masked) mean: with the batch dim sharded over
        # 'data', XLA turns these sums into local-sum + psum over ICI — the
        # Horovod allreduce.
        mask = example_mask(batch, logits.shape[0])
        denom = jnp.maximum(jnp.sum(mask), 1e-6)
        ce = cross_entropy(logits, batch["label"],
                           self.cfg.train.label_smoothing)
        loss = jnp.sum(ce * mask) / denom
        correct = (jnp.argmax(logits, axis=-1) == batch["label"]) \
            .astype(jnp.float32)
        accuracy = jnp.sum(correct * mask) / denom
        aux: Dict[str, jnp.ndarray] = {"accuracy": accuracy}
        if train:
            aux["batch_stats"] = new_stats
        else:
            # Top-5, the ImageNet-era companion metric (the reference's
            # example scripts printed both). top_k would sort; a rank
            # comparison is one reduction, no sort.
            label_logit = jnp.take_along_axis(
                logits, batch["label"][:, None], axis=-1)
            rank = jnp.sum((logits > label_logit).astype(jnp.int32), -1)
            top5 = (rank < 5).astype(jnp.float32)
            aux["accuracy_top5"] = jnp.sum(top5 * mask) / denom
            aux["eval_weight"] = jnp.sum(mask)
        return loss, aux


class MlmTask:
    """BERT MLM+NSP pretraining (reference: TF+Horovod BERT scripts).

    Loss = masked-LM cross-entropy (weighted mean over real predictions) +
    next-sentence cross-entropy — the standard BERT objective. Batch
    contract documented in data/text.py make_mlm_source.
    """

    exact_eval = True

    def __init__(self, cfg: ExperimentConfig, mesh=None):
        self.cfg = cfg
        dtype = jnp.bfloat16 if cfg.train.dtype == "bfloat16" else jnp.float32
        kwargs = dict(cfg.model.kwargs)
        kwargs.setdefault("vocab_size", cfg.data.vocab_size)
        kwargs.setdefault("max_len", max(cfg.data.seq_len, 128))
        if cfg.model.name in ("bert_pipelined", "bert_long"):
            # These trunks run shard_map over the mesh; give them the
            # trainer's mesh and the batch-dim spec the trainer will feed.
            from ..parallel.mesh import build_mesh
            from ..parallel.sharding import batch_sharding

            mesh = mesh if mesh is not None else build_mesh(cfg.mesh)
            kwargs.setdefault("mesh", mesh)
            spec0 = batch_sharding(mesh, 1).spec[0]
            if cfg.model.name == "bert_pipelined":
                from ..models.pipelined import PARAM_RULES

                kwargs.setdefault("batch_spec", spec0)
            else:
                from ..models.bert_long import PARAM_RULES

                kwargs.setdefault("batch_axes", spec0)
        else:
            from ..models.bert import PARAM_RULES
        self.param_rules = PARAM_RULES
        self.model = build_model(cfg.model.name, cfg.model.num_classes,
                                 dtype, **kwargs)

    def init(self, rng: jax.Array):
        s = self.cfg.data.seq_len
        p = max(1, int(s * 0.2))
        ids = jnp.zeros((1, s), jnp.int32)
        return self.model.init(rng, ids, jnp.ones((1, s), jnp.int32), ids,
                               jnp.zeros((1, p), jnp.int32), train=False)

    def loss_fn(self, params, batch_stats, batch, rng, train):
        rngs = {"dropout": rng} if (train and rng is not None) else None
        apply = lambda p, b: self.model.apply(
            {"params": p}, b["input_ids"], b["input_mask"],
            b["segment_ids"], b["mlm_positions"], train=train, rngs=rngs)
        out = apply(params, batch)
        mask = example_mask(batch, batch["input_ids"].shape[0])
        weights = batch["mlm_weights"] * mask[:, None]
        mlm_ce = cross_entropy(out["mlm_logits"], batch["mlm_ids"])
        # Weighted global mean — masked slots carry no gradient, and the
        # normalizer is the global count, so DP psum stays correct.
        token_denom = jnp.maximum(jnp.sum(weights), 1e-6)
        mlm_loss = jnp.sum(mlm_ce * weights) / token_denom
        example_denom = jnp.maximum(jnp.sum(mask), 1e-6)
        nsp_ce = cross_entropy(out["nsp_logits"], batch["nsp_label"])
        nsp_loss = jnp.sum(nsp_ce * mask) / example_denom
        loss = mlm_loss + nsp_loss
        if "moe_load_balance" in out:
            # MoE models: load-balance + router z-loss at the standard
            # ST-MoE weights. Per-token means, so DP psum stays correct.
            loss = loss + MOE_LOAD_BALANCE_WEIGHT * out["moe_load_balance"] \
                + MOE_ROUTER_Z_WEIGHT * out["moe_router_z"]
        mlm_hits = (jnp.argmax(out["mlm_logits"], -1) == batch["mlm_ids"])
        nsp_hits = (jnp.argmax(out["nsp_logits"], -1) == batch["nsp_label"]) \
            .astype(jnp.float32)
        aux = {
            "mlm_loss": mlm_loss,
            "nsp_loss": nsp_loss,
            "mlm_accuracy": jnp.sum(mlm_hits * weights) / token_denom,
            "nsp_accuracy": jnp.sum(nsp_hits * mask) / example_denom,
        }
        if "moe_load_balance" in out:
            aux["moe_load_balance"] = out["moe_load_balance"]
            aux["moe_router_z"] = out["moe_router_z"]
        if train:
            aux["batch_stats"] = batch_stats
        else:
            # Per-metric weights: MLM metrics are token-weighted, NSP (and
            # the combined loss) example-weighted.
            aux["eval_weight"] = jnp.sum(mask)
            aux["mlm_loss__weight"] = jnp.sum(weights)
            aux["mlm_accuracy__weight"] = jnp.sum(weights)
        return loss, aux


class Seq2SeqTask:
    """Transformer NMT (reference: Sockeye MXNet, dist_device_sync).

    Per-token label-smoothed cross-entropy, masked to real target positions,
    normalized by the global token count (Sockeye's per-token loss).
    """

    exact_eval = True

    def __init__(self, cfg: ExperimentConfig):
        self.cfg = cfg
        dtype = jnp.bfloat16 if cfg.train.dtype == "bfloat16" else jnp.float32
        kwargs = dict(cfg.model.kwargs)
        kwargs.setdefault("vocab_size", cfg.data.vocab_size)
        kwargs.setdefault("max_len", max(cfg.data.seq_len, 64))
        self.model = build_model(cfg.model.name, 0, dtype, **kwargs)
        from ..models.transformer_nmt import PARAM_RULES

        self.param_rules = PARAM_RULES

    def init(self, rng: jax.Array):
        s = self.cfg.data.seq_len
        ids = jnp.zeros((1, s), jnp.int32)
        return self.model.init(rng, ids, jnp.ones((1, s), jnp.int32), ids,
                               train=False)

    def final_eval(self, state, eval_iter_fn, trainer) -> Dict[str, float]:
        """Decode the eval set (models/decoding.py) and score corpus BLEU —
        the Sockeye workload's acceptance metric (BASELINE.md row 6).

        Runs the beam (or greedy, beam_size<=1) searcher jit-compiled over
        the mesh-sharded eval batches; hypotheses/references are realized to
        host and scored with metrics/bleu.py. Multi-process runs allgather
        the decoded ids so every process scores the full eval set.
        """
        from ..metrics.bleu import corpus_bleu
        from ..models import decoding
        from ..models.decoding import strip_special

        ev = self.cfg.eval
        if not ev.enabled:
            return {}
        max_len = ev.max_decode_len or self.cfg.data.seq_len
        model_max = getattr(self.model, "max_len", None)
        if model_max is not None and max_len > model_max:
            # The cached path's cache (and the position table) are sized
            # model.max_len; past it, clamped dynamic slices would decode
            # garbage silently. Fail loudly where the configs meet.
            raise ValueError(
                f"eval decode length {max_len} exceeds the model's "
                f"max_len {model_max}")
        variables = {"params": eval_params(state)}

        greedy = decoding.greedy_decode_cached if ev.use_kv_cache \
            else decoding.greedy_decode
        beam = decoding.beam_decode_cached if ev.use_kv_cache \
            else decoding.beam_decode
        if ev.beam_size <= 1:
            decode = jax.jit(lambda v, src, mask: greedy(
                self.model, v, src, mask, max_len))
        else:
            decode = jax.jit(lambda v, src, mask: beam(
                self.model, v, src, mask, max_len, ev.beam_size,
                ev.length_penalty)[0])

        eb = self.cfg.train.eval_batch or self.cfg.train.global_batch
        hyps, refs = [], []
        for out, extra, emask in realized_eval_batches(
                trainer, eb, eval_iter_fn,
                lambda dev: decode(variables, dev["src_ids"],
                                   dev["src_mask"]),
                batch_keys=("tgt_out_ids",)):
            out = np.asarray(out)
            tgt = np.asarray(extra["tgt_out_ids"])
            for i in range(out.shape[0]):
                if emask is not None and emask[i] == 0:
                    continue
                hyps.append(strip_special(out[i]))
                refs.append(strip_special(tgt[i]))
        return {"bleu": corpus_bleu(hyps, refs, smooth=True)}

    def loss_fn(self, params, batch_stats, batch, rng, train):
        rngs = {"dropout": rng} if (train and rng is not None) else None
        apply = lambda p, b: self.model.apply(
            {"params": p}, b["src_ids"], b["src_mask"], b["tgt_in_ids"],
            train=train, rngs=rngs)
        logits = apply(params, batch)
        ex_mask = example_mask(batch, batch["src_ids"].shape[0])
        mask = batch["tgt_mask"] * ex_mask[:, None]
        ce = cross_entropy(logits, batch["tgt_out_ids"],
                           self.cfg.train.label_smoothing)
        denom = jnp.maximum(jnp.sum(mask), 1e-6)
        loss = jnp.sum(ce * mask) / denom
        hits = (jnp.argmax(logits, -1) == batch["tgt_out_ids"])
        aux = {
            "token_accuracy": jnp.sum(hits * mask) / denom,
        }
        if train:
            aux["batch_stats"] = batch_stats
        else:
            # Token-weighted: Sockeye's per-token loss convention.
            aux["eval_weight"] = jnp.sum(mask)
        return loss, aux


# The weight of the indexers' KL loss in the objective of a model of learned
# sparse attention (``BlockStyle.indexer``).
INDEXER_KL_WEIGHT = 1.0


class CausalLmTask:
    """Decoder-only next-token pretraining (GPT family — beyond the
    reference's workload era; models/lm.py explains why it earns a slot).

    Loss = token-weighted mean cross-entropy of tokens[:, 1:] given
    tokens[:, :-1]; metrics include perplexity and next-token accuracy.
    Batch contract: data/text.py make_lm_source.
    """

    exact_eval = True

    def __init__(self, cfg: ExperimentConfig, mesh=None):
        from ..models.lm import PARAM_RULES

        self.cfg = cfg
        dtype = jnp.bfloat16 if cfg.train.dtype == "bfloat16" else jnp.float32
        kwargs = dict(cfg.model.kwargs)
        kwargs.setdefault("vocab_size", cfg.data.vocab_size)
        kwargs.setdefault("max_len", max(cfg.data.seq_len, 128))
        if cfg.model.name == "gpt_long":
            # Sequence-parallel trunk: needs the trainer's mesh and the
            # batch-dim spec it will feed (same contract as bert_long).
            from ..parallel.mesh import build_mesh
            from ..parallel.sharding import batch_sharding

            mesh = mesh if mesh is not None else build_mesh(cfg.mesh)
            kwargs.setdefault("mesh", mesh)
            kwargs.setdefault("batch_axes", batch_sharding(mesh, 1).spec[0])
        elif mesh is not None:
            # The trunk of blocks: on a mesh of more than one device its
            # Pallas kernels go under a shard_map over the batch axes and
            # an expert layer exchanges over `expert` (parallel/kernels.py).
            kwargs.setdefault("mesh", mesh)
        self.param_rules = PARAM_RULES
        self.model = build_model(cfg.model.name, 0, dtype, **kwargs)

    def init(self, rng: jax.Array):
        ids = jnp.zeros((1, self.cfg.data.seq_len), jnp.int32)
        return self.model.init(rng, ids, train=False)

    def _apply(self, params, inputs, train, **call):
        """``(logits, what the expert layers report or None, what the model
        sowed)`` of the model over ``inputs``; ``call`` goes to its
        ``__call__``. A training step also collects what the model sows as
        `nudges`: steps for parameters that no gradient moves (a router's
        balancing bias), which the trainer adds after the optimizer's."""
        apply = lambda p, ids: self.model.apply(
            {"params": p}, ids, train=train,
            mutable=["nudges"] if train else False, **call)
        out, sown = apply(params, inputs) if train \
            else (apply(params, inputs), {})
        logits, moe_aux = out if isinstance(out, tuple) else (out, None)
        return logits, moe_aux, sown

    def loss_fn(self, params, batch_stats, batch, rng, train):
        rngs = {"dropout": rng} if (train and rng is not None) else None
        inputs = batch["tokens"][:, :-1]
        logits, moe_aux, sown = self._apply(params, inputs, train, rngs=rngs)
        # The program's own scope (docs/OBSERVABILITY.md): nothing below is
        # inside a flax module, so without it a trace cannot tell the loss
        # and its backward pass from the optimizer.
        with jax.named_scope("lm_loss"):
            targets = batch["tokens"][:, 1:]
            mask = example_mask(batch, inputs.shape[0])
            weights = batch["loss_mask"] * mask[:, None]
            ce = cross_entropy(logits, targets)
            denom = jnp.maximum(jnp.sum(weights), 1e-6)
            # CE kept separate from the optimization objective: perplexity
            # is defined on cross-entropy alone, and MoE aux terms below
            # must not contaminate it.
            ce_loss = jnp.sum(ce * weights) / denom
            loss = ce_loss
            hits = (jnp.argmax(logits, -1) == targets).astype(jnp.float32)
            aux = {"token_accuracy": jnp.sum(hits * weights) / denom}
            if moe_aux is not None and "indexer_kl" in moe_aux:
                # A model of learned sparse attention: its indexers' loss
                # joins the objective whole (DSA's sparse stage; the
                # stop-gradients that keep it from the trunk, and the
                # cross-entropy from the indexers, are the attention
                # block's), and is reported beside the cross-entropy with
                # what the selections kept.
                moe_aux = dict(moe_aux)
                aux["indexer_kl"] = moe_aux.pop("indexer_kl")
                loss = loss + INDEXER_KL_WEIGHT * aux["indexer_kl"]
                # The step's `loss` stays the cross-entropy (the trainer
                # lets a task's own `loss` stand for the objective's).
                aux["loss"] = ce_loss
                aux["sel_kept_share"] = moe_aux.pop("selected_kept_share")
                aux["sel_ties"] = moe_aux.pop("selected_ties")
            if moe_aux is not None:
                # What the model's expert layers report goes to the step's
                # metrics as moe_<name>; of it the capacity layer's two
                # losses join the objective (ST-MoE's weights, as in
                # MlmTask). A layer that reports none trains on
                # cross-entropy alone.
                for name, weight in (
                        ("load_balance", MOE_LOAD_BALANCE_WEIGHT),
                        ("router_z", MOE_ROUTER_Z_WEIGHT)):
                    if name in moe_aux:
                        loss = loss + weight * moe_aux[name]
                aux.update({f"moe_{k}": v for k, v in moe_aux.items()})
            if train:
                # Per-step perplexity for the train log only: exp of THIS
                # step's token-mean CE (clipped against random-init
                # overflow). Eval perplexity is derived post-aggregation
                # instead — a weighted mean of per-batch exp(CE) is not
                # perplexity (Jensen); see eval_derived below.
                aux["perplexity"] = jnp.exp(jnp.minimum(ce_loss, 20.0))
                aux["batch_stats"] = batch_stats
                if sown.get("nudges"):
                    aux["nudges"] = sown["nudges"]
            else:
                # Every eval metric here (incl. the losses) is
                # token-weighted: the default normalizer is the batch's real
                # token count, so cross-batch aggregation yields the exact
                # full-set token-mean even with ragged loss_masks or padded
                # eval tails.
                aux["ce_loss"] = ce_loss
                aux["eval_weight"] = jnp.sum(weights)
        return loss, aux

    # Derived post-aggregation (Trainer.evaluate): exact perplexity from
    # the aggregated token-mean CE (NOT the MoE-augmented objective).
    eval_derived = {
        "perplexity": lambda m: float(np.exp(min(m["ce_loss"], 20.0))),
    }


# The token a masked position shows the model, and the least rate a row is
# noised at. SDAR's own mask id lies outside any slice of the vocabulary a
# chip holds; 3 is an id no data source draws (they start at 4).
BD_MASK_ID = 3
BD_MIN_RATE = 1e-3


def draw_block_diffusion_noise(rng: jax.Array, rows: int, length: int):
    """``(rate [rows], masked [rows, length])``, a pure function of ``rng``:
    a rate ``t = eps + (1 - eps) u`` a row, ``u`` uniform, and each token
    masked independently with probability ``t``."""
    k_rate, k_mask = jax.random.split(rng)
    rate = BD_MIN_RATE + (1.0 - BD_MIN_RATE) * jax.random.uniform(
        k_rate, (rows,), jnp.float32)
    masked = jax.random.uniform(k_mask, (rows, length), jnp.float32) \
        < rate[:, None]
    return rate, masked


class BlockDiffusionLmTask(CausalLmTask):
    """A decoder trained as a block-diffusion model (SDAR, BD3-LM): a row
    of ``L = data.seq_len`` tokens is cut into blocks of ``b =
    train.block_diffusion``; the step draws a rate ``t`` a row and masks each
    token with probability ``t`` (``draw_block_diffusion_noise``, from the
    step's key), lays the noised copy before the clean row, ``[B, 2 L]``,
    and calls the model with the layout (``ops/attention.py:BlockDiffusion``:
    a noised block sees itself in both directions and the clean blocks before
    it; both copies stand at the positions ``0 .. L - 1``). Logits come back
    for the noised copy alone, for the token *at* each position (no shift),
    and the loss is ``sum over masked i of -log p(x_i) / t`` over the rows'
    ``L`` positions. The same model and parameters as ``CausalLmTask``'s:
    ``init`` traces the plain causal call. Batch contract as there (the
    row's last token, the next-token target, is not read)."""

    def __init__(self, cfg: ExperimentConfig, mesh=None):
        from ..ops.attention import BlockDiffusion

        super().__init__(cfg, mesh)
        self.layout = BlockDiffusion(cfg.data.seq_len,
                                     cfg.train.block_diffusion)
        registry = get_tracer().registry
        registry.gauge(
            "train.bd.block_length",
            "tokens a block of the block-diffusion objective holds",
        ).set(self.layout.block)
        registry.gauge(
            "train.bd.positions_per_token",
            "positions the model runs for each data token of a step",
        ).set(2)

    def loss_fn(self, params, batch_stats, batch, rng, train):
        length = self.layout.length
        clean = batch["tokens"][:, :length]
        with jax.named_scope("bd_noise"):
            rate, masked = draw_block_diffusion_noise(
                rng if rng is not None else jax.random.PRNGKey(0),
                clean.shape[0], length)
            inputs = jnp.concatenate(
                [jnp.where(masked, BD_MASK_ID, clean), clean], axis=1)
        logits, moe_aux, sown = self._apply(params, inputs, train,
                                            layout=self.layout)
        with jax.named_scope("lm_loss"):
            rows = example_mask(batch, clean.shape[0])
            counted = batch["loss_mask"][:, :length] * rows[:, None]
            weights = counted * masked / rate[:, None]
            denom = jnp.maximum(jnp.sum(counted), 1e-6)
            loss = jnp.sum(cross_entropy(logits, clean) * weights) / denom
            seen = jnp.maximum(jnp.sum(counted * masked), 1e-6)
            hits = (jnp.argmax(logits, -1) == clean).astype(jnp.float32)
            aux = {"token_accuracy": jnp.sum(hits * counted * masked) / seen,
                   "bd_masked_share": jnp.sum(counted * masked) / denom}
            if moe_aux is not None:
                aux.update({f"moe_{k}": v for k, v in moe_aux.items()})
            if train:
                aux["batch_stats"] = batch_stats
                if sown.get("nudges"):
                    aux["nudges"] = sown["nudges"]
            else:
                aux["ce_loss"] = loss
                aux["eval_weight"] = jnp.sum(counted)
        return loss, aux

    # The objective is a bound on the likelihood, not a cross-entropy a
    # perplexity is defined on.
    eval_derived = {}


def build_task(cfg: ExperimentConfig, mesh=None):
    """Task registry keyed by model family: ``resnet*`` / ``vit*``
    :class:`ClassificationTask`, ``bert*`` :class:`MlmTask`,
    ``transformer_nmt*`` :class:`Seq2SeqTask`, ``maskrcnn*`` the detection
    task, ``gpt*`` :class:`CausalLmTask` or, where the preset (or an
    override) sets ``train.block_diffusion`` to a block length,
    :class:`BlockDiffusionLmTask`.

    ``mesh``: pass the trainer's Mesh when the model needs it at
    construction time (the pipelined trunk's shard_map); tasks that don't
    ignore it. When omitted, mesh-needing tasks build it from cfg.mesh —
    correct as long as the caller does the same (build_mesh is
    deterministic over jax.devices())."""
    name = cfg.model.name
    if name.startswith("resnet") or name.startswith("vit"):
        return ClassificationTask(cfg)
    if name.startswith("gpt"):
        task = BlockDiffusionLmTask if cfg.train.block_diffusion \
            else CausalLmTask
        return task(cfg, mesh=mesh)
    if name.startswith("bert"):
        return MlmTask(cfg, mesh=mesh)
    if name.startswith("transformer_nmt"):
        return Seq2SeqTask(cfg)
    if name.startswith("maskrcnn"):
        from .detection_task import DetectionTask

        return DetectionTask(cfg)
    raise KeyError(f"no task for model {name!r}")
