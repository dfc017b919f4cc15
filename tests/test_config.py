"""Config system + presets: the training-tier flag surface (SURVEY.md §6)."""

import dataclasses

import pytest

from deeplearning_cfn_tpu.config import ExperimentConfig, apply_overrides
from deeplearning_cfn_tpu.presets import get_preset, list_presets

BASELINE_PRESETS = [
    "cifar10_resnet20",
    "imagenet_resnet50",
    "bert_base_wikipedia",
    "maskrcnn_coco",
    "transformer_nmt_wmt",
]


def test_all_baseline_presets_registered():
    assert set(BASELINE_PRESETS) <= set(list_presets())


@pytest.mark.parametrize("name", BASELINE_PRESETS)
def test_presets_construct_and_serialize(name):
    cfg = get_preset(name)
    assert cfg.preset == name
    d = cfg.to_dict()
    assert d["model"]["name"]
    assert cfg.to_json()


def test_preset_isolation():
    a = get_preset("cifar10_resnet20")
    a.train.global_batch = 999
    b = get_preset("cifar10_resnet20")
    assert b.train.global_batch != 999


def test_overrides_scalar_types():
    cfg = ExperimentConfig()
    apply_overrides(cfg, [
        "train.global_batch=256",
        "schedule.base_lr=0.5",
        "train.shard_opt_state=true",
        "model.name=resnet50",
        "mesh.model=2",
    ])
    assert cfg.train.global_batch == 256
    assert cfg.schedule.base_lr == 0.5
    assert cfg.train.shard_opt_state is True
    assert cfg.model.name == "resnet50"
    assert cfg.mesh.model == 2


def test_overrides_tuple_and_dict():
    cfg = ExperimentConfig()
    apply_overrides(cfg, ["schedule.step_boundaries=0.5,0.75"])
    assert cfg.schedule.step_boundaries == (0.5, 0.75)
    apply_overrides(cfg, ["model.kwargs.depth=20"])
    assert cfg.model.kwargs["depth"] == 20


def test_overrides_unknown_key_raises():
    cfg = ExperimentConfig()
    with pytest.raises(KeyError):
        apply_overrides(cfg, ["train.nonexistent=1"])
    with pytest.raises(KeyError):  # gone at PR 46: BlockStyle.remat is the way
        apply_overrides(cfg, ["train.remat=true"])
    with pytest.raises(KeyError):
        apply_overrides(cfg, ["nosection.x=1"])
    with pytest.raises(ValueError):
        apply_overrides(cfg, ["no_equals_sign"])


def test_config_is_dataclass_tree():
    cfg = ExperimentConfig()
    assert dataclasses.is_dataclass(cfg.train)
    assert dataclasses.is_dataclass(cfg.stack)
