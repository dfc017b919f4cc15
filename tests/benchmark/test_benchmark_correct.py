"""``correct`` can come out false. At a size a test run can hold, on the CPU:

* the control — the plain reference put in the program's place and computed
  one precision below the one the configuration states — fails the
  comparison;
* a run driven past the harness's look for a chip, with the timed path
  broken underneath (a train step that returns its state unchanged, a train
  step that draws other dropout masks), ends with ``correct`` false;
* the reference's dropout masks are the program's.
"""

import contextlib
import json
import os
import sys
import types

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, os.path.join(REPO, "benchmark"))

from harness import compare, device, manifest, weights  # noqa: E402
from harness import train_steps  # noqa: E402

from benchmark_tiny_tree import TINY_GPT  # noqa: E402


def tiny_cell(config_name, tiny, traffic_name, traffic_changes):
    with open(os.path.join(REPO, "benchmark", "configs",
                           config_name + ".json")) as fh:
        config = dict(json.load(fh), **tiny)
    with open(os.path.join(REPO, "benchmark", "traffic",
                           traffic_name + ".json")) as fh:
        traffic = dict(json.load(fh), **traffic_changes)
    reference = manifest.load_module(
        f"benchmark/references/{config_name}.py", f"ref_{config_name}")
    return types.SimpleNamespace(name="tiny", chips=1, config=config,
                                 traffic=traffic, reference=reference)


@pytest.fixture()
def train_cell():
    return tiny_cell("gpt2_small", TINY_GPT, "train_packed_1k", {
        "overrides": ["train.global_batch=8", "mesh.data=1",
                      "train.shard_opt_state=false"], "num_examples": 64})


def run_ctx(cell, said, seed=2 ** 31 + 5, seconds=1.5):
    import jax

    null = lambda _name=None: contextlib.nullcontext()
    return {"cell": cell, "seed": seed, "seconds": seconds, "trace": False,
            "devices": jax.devices()[:1], "events": device.CompileEvents(),
            "say": said.append, "phase": lambda _n: None, "annotate": null,
            "profiler": null}


# -- the controls --------------------------------------------------------


def test_training_control_in_lower_precision_fails(train_cell, monkeypatch):
    import jax

    monkeypatch.setenv("BENCHMARK_REHEARSAL", "cpu")
    cell, seed = train_cell, 2 ** 31 + 11
    cfg = train_steps.build_program_config(cell, seed)
    _, _, shapes, _ = train_steps.build_trainer(cell, cfg, seed,
                                                jax.devices()[:1])
    params = jax.jit(lambda key: weights.make(shapes, key))(
        weights.seed_key(seed))
    tokens = train_steps.make_tokens(seed, cell.traffic, 64, 512)
    batches = [tokens[i * 8:(i + 1) * 8] for i in range(3)]
    hp = dict(cell.config["optimizer"])
    rng = jax.random.PRNGKey(9)
    ref = cell.reference.train_steps(params, batches, cell.config, hp,
                                     "float32", rng=rng)
    below = cell.reference._precision.below(cell.config["precision"])
    assert below == "bfloat16"
    low = cell.reference.train_steps(params, batches, cell.config, hp, below,
                                     rng=rng)
    said = []
    assert compare.train(ref, ref, cell.config["limits"], said.append)
    assert not compare.train(low, ref, cell.config["limits"], said.append)
    assert any("OVER THE LIMIT" in s for s in said)
    # The faults that the numbers a lower precision hardly moves are there
    # to catch: half of the batch left out, a state that never changes.
    half = cell.reference.train_steps(params, batches, cell.config, hp,
                                      "float32", rng=rng, rows=4)
    numbers = compare.train_numbers(half, ref)
    assert numbers["train_loss_rel"] > 3 * cell.config["limits"][
        "train_loss_rel"]
    # A step that drops nothing, and one that draws other masks.
    plain = cell.reference.train_steps(
        params, batches, dict(cell.config, embd_pdrop=0.0, resid_pdrop=0.0),
        hp, "float32")
    other = cell.reference.train_steps(params, batches, cell.config, hp,
                                       "float32", rng=jax.random.PRNGKey(10))
    for wrong in (plain, other):
        assert not compare.train(wrong, ref, cell.config["limits"],
                                 said.append)
    with pytest.raises(ValueError, match="needs the trainer's key"):
        cell.reference.train_steps(params, batches, cell.config, hp)
    still = dict(ref, change_norms={k: 0.0 for k in ref["change_norms"]})
    assert compare.train_numbers(still, ref)["train_change_norm_gap"] == 1.0


# -- the timed path broken underneath -----------------------------------


def test_sound_training_run_is_correct(train_cell, monkeypatch):
    monkeypatch.setenv("BENCHMARK_REHEARSAL", "cpu")
    said = []
    result = train_steps.run(run_ctx(train_cell, said))
    assert result["correct"] is True, said
    assert result["attempted"] >= 1 and result["failed"] == 0


def test_train_step_that_returns_its_state_unchanged_is_not_correct(
        train_cell, monkeypatch):
    from deeplearning_cfn_tpu.train.trainer import Trainer

    monkeypatch.setenv("BENCHMARK_REHEARSAL", "cpu")
    sound = Trainer._train_step_fn

    def broken(self):
        step = sound(self)

        def train_step(state, batch, rng):
            new, metrics = step(state, batch, rng)
            return state.replace(step=new.step), metrics

        return train_step

    monkeypatch.setattr(Trainer, "_train_step_fn", broken)
    said = []
    result = train_steps.run(run_ctx(train_cell, said))
    assert result["correct"] is False
    over = [s for s in said if "OVER THE LIMIT" in s]
    assert any("train_change_norm_gap: 1 " in s for s in over), said
    assert any("train_grad_norm_gap" in s for s in over)


def test_train_step_that_draws_other_masks_is_not_correct(train_cell,
                                                          monkeypatch):
    """The program's dropout moved to another stream (here: the step's key
    folded once more) no longer matches the reference's masks."""
    import jax

    from deeplearning_cfn_tpu.train.trainer import Trainer

    monkeypatch.setenv("BENCHMARK_REHEARSAL", "cpu")
    sound = Trainer._train_step_fn

    def broken(self):
        step = sound(self)
        return lambda state, batch, rng: step(
            state, batch, jax.random.fold_in(rng, 1))

    monkeypatch.setattr(Trainer, "_train_step_fn", broken)
    said = []
    result = train_steps.run(run_ctx(train_cell, said))
    assert result["correct"] is False
    assert any("train_loss_rel" in s and "OVER THE LIMIT" in s for s in said)


def test_the_references_dropout_masks_are_the_programs(train_cell,
                                                       monkeypatch):
    """Every dropout site of the program's model, by name, drops exactly the
    elements the reference's stream says. If this fails after a change to
    the program (a module renamed, another way of drawing the masks), the
    training cell's ``correct`` fails with it."""
    import flax.linen as nn
    import jax
    import jax.numpy as jnp

    from deeplearning_cfn_tpu.train.task import build_task

    monkeypatch.setenv("BENCHMARK_REHEARSAL", "cpu")
    cell = train_cell
    cfg = train_steps.build_program_config(cell, 7)
    model = build_task(cfg).model
    ids = jnp.arange(3 * 64, dtype=jnp.int32).reshape(3, 64) % 512
    variables = model.init(jax.random.PRNGKey(0), ids, train=False)
    key = jax.random.PRNGKey(2 ** 31 - 3)
    _, seen = model.apply(
        variables, ids, train=True, rngs={"dropout": key},
        capture_intermediates=lambda m, _: isinstance(m, nn.Dropout),
        mutable=["intermediates"])
    kept = {tuple(k for k in path if k != "__call__"): np.asarray(v != 0)
            for path, v in weights.flat(seen["intermediates"]).items()
            for path in [tuple(path.split("/")[:-1])]}
    masks = cell.reference.dropout_masks(key, cell.config, (3, 64, 64))
    streams = cell.config["dropout_streams"]
    sites = {"embd": tuple(streams["embd"])}
    for i in range(cell.config["n_layer"]):
        sites[f"attn_{i}"] = tuple(p.format(i=i) for p in streams["attn"])
        sites[f"mlp_{i}"] = tuple(p.format(i=i) for p in streams["mlp"])
    assert set(kept) == set(sites.values()) and len(masks) == len(sites)
    for name, path in sites.items():
        m = np.asarray(masks[name])
        assert 0.8 < m.mean() < 0.97
        assert np.array_equal(kept[path], m), name
