"""Named experiment presets — the five BASELINE.json acceptance configs.

These are the rebuild's equivalent of the reference's bundled example scripts
(SURVEY.md §3.1): each preset pins the model/data/optimizer/schedule recipe the
corresponding reference workload used, re-expressed for the pjit-DP trainer.
"""

from __future__ import annotations

import copy
from typing import Callable, Dict, List

from .config import (
    CheckpointConfig,
    DataConfig,
    ExperimentConfig,
    MeshConfig,
    ModelConfig,
    OptimizerConfig,
    ScheduleConfig,
    StackConfig,
    TrainConfig,
)

_REGISTRY: Dict[str, Callable[[], ExperimentConfig]] = {}


def register_preset(name: str):
    def deco(fn: Callable[[], ExperimentConfig]):
        if name in _REGISTRY:
            raise ValueError(f"duplicate preset {name!r}")
        _REGISTRY[name] = fn
        return fn

    return deco


def list_presets() -> List[str]:
    return sorted(_REGISTRY)


def get_preset(name: str) -> ExperimentConfig:
    if name not in _REGISTRY:
        raise KeyError(f"unknown preset {name!r}; available: {list_presets()}")
    cfg = _REGISTRY[name]()
    cfg.preset = name
    return copy.deepcopy(cfg)


@register_preset("cifar10_resnet20")
def _cifar10_resnet20() -> ExperimentConfig:
    """CIFAR-10 ResNet-20 — the reference's CPU-runnable smoke workload
    (MXNet ``train_cifar10.py --network resnet --kv-store dist_sync``)."""
    return ExperimentConfig(
        model=ModelConfig(name="resnet20", num_classes=10),
        data=DataConfig(name="cifar10", image_size=32),
        train=TrainConfig(global_batch=128, epochs=60.0, dtype="float32"),
        optimizer=OptimizerConfig(name="momentum", momentum=0.9, weight_decay=1e-4),
        schedule=ScheduleConfig(
            name="step",
            base_lr=0.1,
            warmup_epochs=1.0,
            step_boundaries=(0.5, 0.75),
            step_factors=(0.1, 0.01),
        ),
        mesh=MeshConfig(data=-1),
        stack=StackConfig(slice_type="v5p-8"),
    )


@register_preset("imagenet_resnet50")
def _imagenet_resnet50() -> ExperimentConfig:
    """ImageNet ResNet-50 DP — the north-star config (reference: TF+Horovod
    ResNet-50, NCCL allreduce over EFA). Large-batch LARS recipe to 75.9%."""
    return ExperimentConfig(
        model=ModelConfig(name="resnet50", num_classes=1000),
        data=DataConfig(name="imagenet", image_size=224),
        train=TrainConfig(global_batch=8192, epochs=90.0, dtype="bfloat16",
                          label_smoothing=0.1),
        optimizer=OptimizerConfig(
            name="lars", momentum=0.9, weight_decay=1e-4, trust_coefficient=0.001
        ),
        schedule=ScheduleConfig(
            name="cosine",
            base_lr=2.0,  # LARS base for batch 8192 ("
            warmup_epochs=5.0,
            scale_with_batch=True,
            reference_batch=8192,
        ),
        mesh=MeshConfig(data=-1),
        stack=StackConfig(slice_type="v5p-256"),
    )


@register_preset("bert_base_wikipedia")
def _bert_base() -> ExperimentConfig:
    """BERT-base MLM+NSP pretraining (reference: TF+Horovod BERT scripts).

    Recipe fidelity: hidden/layers/heads/mlp and dropout 0.1 match the
    BERT-base paper config the reference scripts ran. Intentional
    deviations: LAMB instead of Adam (the established large-batch BERT
    recipe — the reference's batch was per-GPU Adam at an older scale) and
    a cosine decay instead of linear (equivalent envelope, one scheduler
    fewer).
    """
    return ExperimentConfig(
        model=ModelConfig(
            name="bert_base",
            num_classes=2,  # NSP head
            kwargs=dict(
                hidden_size=768, num_layers=12, num_heads=12, mlp_dim=3072,
                max_len=512, dropout_rate=0.1,
            ),
        ),
        data=DataConfig(name="wikipedia_mlm", seq_len=128, vocab_size=30522),
        train=TrainConfig(global_batch=1024, steps=100_000, dtype="bfloat16",
                          shard_opt_state=True),  # ZeRO-1: LAMB slots /N
        optimizer=OptimizerConfig(name="lamb", weight_decay=0.01,
                                  grad_clip_norm=1.0),
        schedule=ScheduleConfig(name="cosine", base_lr=1e-3, warmup_steps=3000),
        mesh=MeshConfig(data=-1),
        stack=StackConfig(slice_type="v5p-64"),
    )


@register_preset("bert_moe_wikipedia")
def _bert_moe() -> ExperimentConfig:
    """BERT-base with Mixture-of-Experts FFNs (every other layer, 8
    experts, top-2) on a data×expert mesh — the expert-parallelism
    flagship. No reference equivalent (SURVEY.md §3.2 lists EP as absent);
    recipe is bert_base_wikipedia's with the GShard layer convention and
    ST-MoE aux-loss weights (train/task.py)."""
    return ExperimentConfig(
        model=ModelConfig(
            name="bert_base",
            num_classes=2,
            kwargs=dict(
                hidden_size=768, num_layers=12, num_heads=12, mlp_dim=3072,
                max_len=512, dropout_rate=0.1,
                num_experts=8, moe_every=2, moe_top_k=2,
            ),
        ),
        data=DataConfig(name="wikipedia_mlm", seq_len=128, vocab_size=30522),
        train=TrainConfig(global_batch=1024, steps=100_000, dtype="bfloat16",
                          shard_opt_state=True),
        optimizer=OptimizerConfig(name="lamb", weight_decay=0.01,
                                  grad_clip_norm=1.0),
        schedule=ScheduleConfig(name="cosine", base_lr=1e-3, warmup_steps=3000),
        mesh=MeshConfig(data=-1, expert=8),
        stack=StackConfig(slice_type="v5p-64"),
    )


@register_preset("bert_pipelined_wikipedia")
def _bert_pipelined() -> ExperimentConfig:
    """BERT-base with the trunk pipelined over 4 stages (GPipe schedule,
    ops/pipeline.py) — the pipeline-parallelism flagship. No reference
    equivalent (SURVEY.md §3.2 lists PP as absent). Dropout must be 0 in
    the pipelined trunk (models/pipelined.py); 8 microbatches keep the
    bubble at (4-1)/(8+4-1) ≈ 27% of ticks."""
    return ExperimentConfig(
        model=ModelConfig(
            name="bert_pipelined",
            num_classes=2,
            kwargs=dict(
                hidden_size=768, num_layers=12, num_heads=12, mlp_dim=3072,
                max_len=512, n_microbatches=8,
            ),
        ),
        data=DataConfig(name="wikipedia_mlm", seq_len=128, vocab_size=30522),
        train=TrainConfig(global_batch=1024, steps=100_000, dtype="bfloat16",
                          shard_opt_state=True),
        optimizer=OptimizerConfig(name="lamb", weight_decay=0.01,
                                  grad_clip_norm=1.0),
        schedule=ScheduleConfig(name="cosine", base_lr=1e-3, warmup_steps=3000),
        mesh=MeshConfig(data=-1, pipe=4),
        stack=StackConfig(slice_type="v5p-64"),
    )


@register_preset("bert_long_wikipedia")
def _bert_long() -> ExperimentConfig:
    """Long-context BERT: sequence 4096 with ring attention over a 'seq'
    mesh axis (models/bert_long.py) — the long-context flagship. No
    reference equivalent (its max sequence was BERT's 512 — SURVEY.md §6);
    packed-sequence contract (no padding bias). Switch strategy with
    model.kwargs.seq_impl=ulysses (needs heads % seq ways == 0)."""
    return ExperimentConfig(
        model=ModelConfig(
            name="bert_long",
            num_classes=2,
            kwargs=dict(
                hidden_size=768, num_layers=12, num_heads=12, mlp_dim=3072,
                max_len=4096, seq_impl="ring",
            ),
        ),
        data=DataConfig(name="wikipedia_mlm", seq_len=4096,
                        vocab_size=30522),
        train=TrainConfig(global_batch=256, steps=100_000, dtype="bfloat16",
                          shard_opt_state=True),
        optimizer=OptimizerConfig(name="lamb", weight_decay=0.01,
                                  grad_clip_norm=1.0),
        schedule=ScheduleConfig(name="cosine", base_lr=6e-4,
                                warmup_steps=3000),
        mesh=MeshConfig(data=-1, seq=4),
        stack=StackConfig(slice_type="v5p-64"),
    )


@register_preset("gpt_long_lm")
def _gpt_long() -> ExperimentConfig:
    """Long-context causal LM: GPT trunk at sequence 16384 with ring
    attention over a 'seq' mesh axis (models/lm.py LongCausalLm) — the
    causal long-context flagship, proving the sequence-parallel ops'
    causal masking at scale. Same recipe family as gpt_small_lm; packed
    sequences. seq_impl=ulysses needs heads % seq ways == 0 — with this
    preset's 12 heads that means also setting mesh.seq to 4 or 6 (the
    default 8 does not divide 12)."""
    return ExperimentConfig(
        model=ModelConfig(
            name="gpt_long",
            kwargs=dict(
                hidden_size=768, num_layers=12, num_heads=12, mlp_dim=3072,
                max_len=16384, seq_impl="ring",
            ),
        ),
        data=DataConfig(name="lm_text", seq_len=16384, vocab_size=32768),
        train=TrainConfig(global_batch=64, steps=100_000, dtype="bfloat16",
                          shard_opt_state=True, grad_accum_steps=2),
        optimizer=OptimizerConfig(name="adamw", b1=0.9, b2=0.95,
                                  weight_decay=0.1, grad_clip_norm=1.0),
        schedule=ScheduleConfig(name="cosine", base_lr=3e-4,
                                warmup_steps=2000),
        mesh=MeshConfig(data=-1, seq=8),
        stack=StackConfig(slice_type="v5p-64"),
    )


@register_preset("maskrcnn_coco")
def _maskrcnn() -> ExperimentConfig:
    """Mask R-CNN COCO — the one beyond-DP config: pjit data+spatial shard
    (reference: TensorPack HorovodTrainer multi-node)."""
    return ExperimentConfig(
        model=ModelConfig(
            name="maskrcnn_resnet50",
            num_classes=91,
            kwargs=dict(image_size=1024),  # GT padding is data.max_boxes
        ),
        data=DataConfig(name="coco", image_size=1024, max_boxes=100),
        train=TrainConfig(global_batch=64, epochs=24.0, dtype="bfloat16"),
        optimizer=OptimizerConfig(name="momentum", momentum=0.9,
                                  weight_decay=1e-4, grad_clip_norm=10.0),
        schedule=ScheduleConfig(
            name="step", base_lr=0.08, warmup_steps=500,
            step_boundaries=(0.66, 0.88), step_factors=(0.1, 0.01),
        ),
        mesh=MeshConfig(data=-1, spatial=2),
        stack=StackConfig(slice_type="v5p-64"),
    )


@register_preset("imagenet_vit_s16")
def _vit_s16() -> ExperimentConfig:
    """ViT-Small/16 ImageNet from scratch — beyond the reference's
    conv-era vision stack (models/vit.py explains the inclusion). Recipe:
    the DeiT-style from-scratch setup — AdamW(0.9, 0.999) wd 0.05, cosine
    with warmup, dropout 0.1, 300-epoch-equivalent step budget; GAP head.
    """
    return ExperimentConfig(
        model=ModelConfig(
            name="vit_s16", num_classes=1000,
            kwargs=dict(dropout_rate=0.1),
        ),
        data=DataConfig(name="imagenet", image_size=224),
        train=TrainConfig(global_batch=1024, epochs=300, dtype="bfloat16",
                          label_smoothing=0.1, shard_opt_state=True),
        optimizer=OptimizerConfig(name="adamw", weight_decay=0.05,
                                  grad_clip_norm=1.0),
        schedule=ScheduleConfig(name="cosine", base_lr=1e-3,
                                warmup_epochs=5.0),
        mesh=MeshConfig(data=-1),
        stack=StackConfig(slice_type="v5p-64"),
    )


@register_preset("gpt_small_lm")
def _gpt_small() -> ExperimentConfig:
    """GPT-2-small decoder-only LM pretraining — beyond the reference's
    workload era (its newest family is BERT); included because one causal
    trunk exercises flash causal attention, KV-cached decode, TP rules,
    and gradient accumulation together (models/lm.py). Recipe: GPT-2/124M
    dims, AdamW(0.9, 0.95) wd 0.1, cosine to zero after linear warmup,
    grad clip 1.0 — the now-standard small-LM pretraining recipe."""
    return ExperimentConfig(
        model=ModelConfig(
            name="gpt_small",
            kwargs=dict(max_len=1024, dropout_rate=0.1),
        ),
        data=DataConfig(name="lm_text", seq_len=1024, vocab_size=32768),
        train=TrainConfig(global_batch=512, steps=100_000, dtype="bfloat16",
                          grad_accum_steps=1, shard_opt_state=True),
        optimizer=OptimizerConfig(name="adamw", b1=0.9, b2=0.95,
                                  weight_decay=0.1, grad_clip_norm=1.0),
        schedule=ScheduleConfig(name="cosine", base_lr=6e-4,
                                warmup_steps=2000),
        mesh=MeshConfig(data=-1),
        stack=StackConfig(slice_type="v5p-32"),
    )


@register_preset("laguna_xs2_lm")
def _laguna_xs2() -> ExperimentConfig:
    """Laguna-XS.2 (poolside, 33.4B-A3B: window and full attention mixed
    over grouped K/V heads, 256 routed experts 8 a token and a shared one)
    pre-trained on one chip's share of a pod: the chip is one of 8 that share
    each layer, experts 0-31 of 256 and 12,544 of the 100,352 vocabulary rows
    here, attention and the dense MLP whole (data-parallel attention beside
    expert-parallel experts), and it holds layers 0-4 of 40 as one pipeline
    stage: the dense layer, then one whole period (sliding x 3, full). The
    exchange with the other chips is not here (models/moe.py). Sequences of
    4096, the source's pre-training context. Recipe: gpt_small_lm's (the
    source publishes none), no auxiliary loss."""
    return ExperimentConfig(
        model=ModelConfig(
            name="gpt_laguna_xs2",
            kwargs=dict(layers_held=(0, 1, 2, 3, 4), experts_held=(0, 32)),
        ),
        data=DataConfig(name="lm_text", seq_len=4096, vocab_size=12_544),
        train=TrainConfig(global_batch=2, steps=100_000, dtype="bfloat16",
                          shard_opt_state=False),
        optimizer=OptimizerConfig(name="adamw", b1=0.9, b2=0.95,
                                  weight_decay=0.1, grad_clip_norm=1.0),
        schedule=ScheduleConfig(name="cosine", base_lr=6e-4,
                                warmup_steps=2000),
        mesh=MeshConfig(data=-1),
        stack=StackConfig(slice_type="v5e-8"),
    )


@register_preset("zaya1_8b_lm")
def _zaya1_8b() -> ExperimentConfig:
    """ZAYA1-8B (Zyphra, 8.4 B parameters, 0.76 B active: attention inside a
    compressed, convolved latent, 16 experts of width 2048 one a token by an
    MLP router that carries its state from layer to layer, a scaled
    residual, a tied head) pre-trained on one chip's share of a pod: the
    chip is one of 8 that share each layer, experts 0-7 of 16 (each half of
    the experts on 4 of the 8 chips, which split the batch) and 32,896 of
    the 262,272 vocabulary rows here (the eighth, in whole 128-lane tiles),
    attention and the router whole, and it holds layers 0-4 of 40 as one
    pipeline stage. The exchange with the other chips is not here
    (models/moe.py). Sequences of 4096, the length of the source's first
    pre-training phase. Recipe: gpt_small_lm's (the source's own is not in
    its config), no auxiliary loss; a router's balancing bias is moved
    after every step by its load (models/moe.py:MlpStateRouter: the
    source's own controller is not published either)."""
    return ExperimentConfig(
        model=ModelConfig(
            name="gpt_zaya1_8b",
            kwargs=dict(layers_held=(0, 1, 2, 3, 4), experts_held=(0, 8)),
        ),
        data=DataConfig(name="lm_text", seq_len=4096, vocab_size=32_896),
        train=TrainConfig(global_batch=2, steps=100_000, dtype="bfloat16",
                          shard_opt_state=False),
        optimizer=OptimizerConfig(name="adamw", b1=0.9, b2=0.95,
                                  weight_decay=0.1, grad_clip_norm=1.0),
        schedule=ScheduleConfig(name="cosine", base_lr=6e-4,
                                warmup_steps=2000),
        mesh=MeshConfig(data=-1),
        stack=StackConfig(slice_type="v5e-8"),
    )


@register_preset("mellum2_12b_lm")
def _mellum2_12b() -> ExperimentConfig:
    """Mellum2-12B-A2.5B (JetBrains: sliding-window and full attention 3 : 1
    over grouped K/V heads, every MLP 64 experts of width 896, 8 a token by
    softmax scores) pre-trained on one four-chip host of a pod whose pipeline
    stages are a host each: this stage holds layers 0-3 of 28 (one whole
    period: sliding x 3, full) with **all 64 experts of each**, 16 a chip on
    the mesh's `expert` axis, everything else on every chip (the batch
    rides the axis outside the expert layers), and the exchange between the
    four ranks is run (models/moe.py). The stage is given an embedding and a
    head over a quarter of the vocabulary (24,576 of 98,304 rows). Sequences
    of 8192, the source's original context. Recipe: gpt_small_lm's (the
    source publishes none), no auxiliary loss."""
    return ExperimentConfig(
        model=ModelConfig(
            name="gpt_mellum2_12b",
            kwargs=dict(layers_held=(0, 1, 2, 3)),
        ),
        data=DataConfig(name="lm_text", seq_len=8192, vocab_size=24_576),
        train=TrainConfig(global_batch=4, steps=100_000, dtype="bfloat16",
                          shard_opt_state=False),
        optimizer=OptimizerConfig(name="adamw", b1=0.9, b2=0.95,
                                  weight_decay=0.1, grad_clip_norm=1.0),
        schedule=ScheduleConfig(name="cosine", base_lr=6e-4,
                                warmup_steps=2000),
        mesh=MeshConfig(data=1, expert=4),
        stack=StackConfig(slice_type="v5e-4"),
    )


@register_preset("granite4_h_micro_lm")
def _granite4_h_micro() -> ExperimentConfig:
    """granite-4.0-h-micro (IBM, 3 B parameters, dense: nine layers in ten a
    Mamba-2 mixer, 64 heads of 64 with a state of 128 scanned in chunks of
    256, and one attention over 8 K/V heads with no positions; Granite's
    four multipliers; a tied head) pre-trained on one chip's share of a pod:
    the chip holds layers 0-9 of 40 as one pipeline stage of four (one whole
    period: Mamba x 5, attention, Mamba x 4) and 12,544 of the 100,352
    vocabulary rows (8 chips share the vocabulary, embedding and tied head
    alike); every layer whole, every width published. Sequences of 8192.
    Every block is recomputed in the backward pass, one at a time
    (`remat_blocks`): ten blocks' intermediates at 8,192 tokens do not fit
    beside 12.4 GB of weights, gradients and Adam's moments. The attention
    block keeps its flash forward kernel's output and row statistics
    (34.6 MB) and runs the kernel once. Recipe: gpt_small_lm's (the source's
    own is not in its config)."""
    return ExperimentConfig(
        model=ModelConfig(
            name="gpt_granite4_h_micro",
            kwargs=dict(layers_held=tuple(range(10)), remat_blocks=True),
        ),
        data=DataConfig(name="lm_text", seq_len=8192, vocab_size=12_544),
        train=TrainConfig(global_batch=1, steps=100_000, dtype="bfloat16",
                          shard_opt_state=False),
        optimizer=OptimizerConfig(name="adamw", b1=0.9, b2=0.95,
                                  weight_decay=0.1, grad_clip_norm=1.0),
        schedule=ScheduleConfig(name="cosine", base_lr=6e-4,
                                warmup_steps=2000),
        mesh=MeshConfig(data=-1),
        stack=StackConfig(slice_type="v5e-8"),
    )


@register_preset("sdar_30b_a3b_lm")
def _sdar_30b_a3b() -> ExperimentConfig:
    """SDAR-30B-A3B-Chat (JetLM: Qwen3-MoE's layer, 32 query heads over 4
    K/V heads with an RMSNorm on q and k, 128 experts of width 768 8 a
    token, trained as a block-diffusion model) adapted on one chip's share of
    a pod in which 8 chips share each layer: the chip holds layers 0-5 of 48
    as one pipeline stage of eight, experts 0-15 of each layer's 128 and
    19,072 of the 151,936 vocabulary rows (embedding and untied head alike);
    attention whole, every width published. Rows of 8192 tokens in blocks of
    4 (`train.block_diffusion`): a step runs the noised copy and the clean
    row, 16,384 positions, through every layer. Every block is recomputed in
    the backward pass (`remat_blocks`), each keeping its flash forward
    kernel's output and row statistics (136 MB a block, 0.82 GB) and
    running the kernel once. Recipe: gpt_small_lm's (the source publishes
    none), no auxiliary loss."""
    return ExperimentConfig(
        model=ModelConfig(
            name="gpt_sdar_30b_a3b",
            kwargs=dict(layers_held=tuple(range(6)), experts_held=(0, 16),
                        remat_blocks=True),
        ),
        data=DataConfig(name="lm_text", seq_len=8192, vocab_size=19_072),
        train=TrainConfig(global_batch=1, steps=100_000, dtype="bfloat16",
                          shard_opt_state=False, block_diffusion=4),
        optimizer=OptimizerConfig(name="adamw", b1=0.9, b2=0.95,
                                  weight_decay=0.1, grad_clip_norm=1.0),
        schedule=ScheduleConfig(name="cosine", base_lr=6e-4,
                                warmup_steps=2000),
        mesh=MeshConfig(data=-1),
        stack=StackConfig(slice_type="v5e-8"),
    )


@register_preset("keye_vl2_30b_a3b_lm")
def _keye_vl2_30b_a3b() -> ExperimentConfig:
    """Keye-VL-2.0-30B-A3B's language model (Kwai-Keye: Qwen3-MoE's layer
    with learned sparse attention, an indexer of 16 heads of 64 choosing
    each row's 2048 keys, and rotary positions in three sections) trained on
    one chip's share of a pod in which 8 chips share each layer: the chip
    holds layers 0-5 of 48 as one pipeline stage of eight, experts 0-15 of
    each layer's 128 and 19,072 of the 151,936 vocabulary rows (embedding
    and untied head alike); attention and the indexer whole, every width
    published. One packed text row of 16,384 tokens a step, next-token
    prediction with the indexer's KL loss beside the cross-entropy (the
    sparse stage of DSA's recipe). Every block is recomputed in the backward
    pass (`remat_blocks`). Recipe: gpt_small_lm's (the source publishes
    none), no auxiliary balance loss."""
    return ExperimentConfig(
        model=ModelConfig(
            name="gpt_keye_vl2_30b_a3b",
            kwargs=dict(layers_held=tuple(range(6)), experts_held=(0, 16),
                        remat_blocks=True),
        ),
        data=DataConfig(name="lm_text", seq_len=16_384, vocab_size=19_072),
        train=TrainConfig(global_batch=1, steps=100_000, dtype="bfloat16",
                          shard_opt_state=False),
        optimizer=OptimizerConfig(name="adamw", b1=0.9, b2=0.95,
                                  weight_decay=0.1, grad_clip_norm=1.0),
        schedule=ScheduleConfig(name="cosine", base_lr=6e-4,
                                warmup_steps=2000),
        mesh=MeshConfig(data=-1),
        stack=StackConfig(slice_type="v5e-8"),
    )


@register_preset("transformer_nmt_wmt")
def _nmt() -> ExperimentConfig:
    """Transformer NMT WMT En-De (reference: Sockeye + MXNet
    ``--kvstore dist_device_sync``).

    Recipe fidelity: transformer-base dims, dropout 0.1, label smoothing
    0.1, Adam(0.9, 0.98) with rsqrt/4000-warmup — the Sockeye/"Attention
    Is All You Need" base recipe. Intentional deviations: pre-LN blocks
    (stable without Sockeye's custom init; post-LN needs it) and tied
    source/target/output embeddings (Sockeye's default, kept).
    """
    return ExperimentConfig(
        model=ModelConfig(
            name="transformer_nmt",
            kwargs=dict(
                hidden_size=512, num_layers=6, num_heads=8, mlp_dim=2048,
                dropout_rate=0.1,
            ),
        ),
        data=DataConfig(name="wmt_en_de", seq_len=128, vocab_size=32000),
        train=TrainConfig(global_batch=2048, steps=100_000, dtype="bfloat16",
                          label_smoothing=0.1),
        optimizer=OptimizerConfig(name="adamw", b1=0.9, b2=0.98,
                                  weight_decay=0.0, grad_clip_norm=0.0),
        schedule=ScheduleConfig(name="rsqrt", base_lr=1.0, warmup_steps=4000),
        mesh=MeshConfig(data=-1),
        stack=StackConfig(slice_type="v5p-32"),
    )
