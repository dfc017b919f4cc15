"""The ``sdar_30b_a3b`` configuration: what the committed manifest says of
it, found by name; its file against the source's published ``config``; the
op-count module against numbers worked by hand; the five readers PR 43
brought on hand-written operations; and, at a size a test run can hold on the
CPU, the cell rehearsed end to end through ``run.py`` in a tiny tree built by
adding files, the configuration's own controls reading ``correct`` false, and
its calibration script.

(The mask through the kernels, the program against the reference leaf by
leaf, the shares, the noise and the published widths are
``tests/test_sdar.py``.)"""

import json
import os
import shutil
import subprocess
import sys
import types

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, os.path.join(REPO, "benchmark"))

from harness import (compare, manifest, opcount, opcount_sdar,  # noqa: E402
                     train_steps, weights)

import benchmark_tiny_tree  # noqa: E402
from test_benchmark_cells_train import last_line, run_cell  # noqa: E402

CELL, CONFIG = "sdar_30b_a3b_train_bd_8k", "sdar_30b_a3b"
TRAFFIC = "train_bd_8k_b4_v19072"

# The source's config.json as the catalog has it
# (https://huggingface.co/JetLM/SDAR-30B-A3B-Chat/blob/main/config.json),
# every key that says something of the model's shape.
PUBLISHED = {
    "attention_bias": False, "decoder_sparse_step": 1, "head_dim": 128,
    "hidden_act": "silu", "hidden_size": 2048, "intermediate_size": 6144,
    "max_position_embeddings": 32768, "max_window_layers": 48,
    "mlp_only_layers": [], "model_type": "sdar_moe",
    "moe_intermediate_size": 768, "norm_topk_prob": True,
    "num_attention_heads": 32, "num_experts": 128, "num_experts_per_tok": 8,
    "num_hidden_layers": 48, "num_key_value_heads": 4, "rms_norm_eps": 1e-06,
    "rope_scaling": None, "rope_theta": 1000000, "sliding_window": None,
    "tie_word_embeddings": False, "use_sliding_window": False,
    "vocab_size": 151936,
}

# What ``gpt_sdar_tiny`` (models/lm.py) is, in the source's keys: all 8
# experts held, 2 a token.
TINY = {
    "hidden_size": 64, "num_attention_heads": 4, "num_key_value_heads": 2,
    "head_dim": 16, "moe_intermediate_size": 32, "num_experts": 8,
    "num_experts_per_tok": 2, "num_hidden_layers": 2, "vocab_size": 96,
    "layers_held": [0, 1], "experts_held": [0, 8],
    "mlp_layer_types": ["sparse", "sparse"],
    "published": {"num_experts": 8, "vocab_size": 96,
                  "num_hidden_layers": 2},
    "overrides": [
        "model.name=gpt_sdar_tiny", "model.kwargs.layers_held=[0,1]",
        "model.kwargs.experts_held=[0,8]", "train.dtype=float32",
        "data.synthetic=true", "train.log_every_steps=1",
        "data.use_native_loader=false", "checkpoint.every_steps=0",
        "eval.enabled=false"],
    "precision": "float32",
    # float32 on the CPU against float32: what is left is the order of the
    # sums. Each control moves one of them a hundredfold and more.
    "limits": {"train_loss_rel": 1e-5, "train_grad_norm_gap": 1e-4,
               "train_change_norm_gap": 1e-2},
}
TINY_TRAFFIC = {
    "overrides": ["train.global_batch=2", "data.seq_len=64",
                  "data.vocab_size=96", "mesh.data=1",
                  "train.shard_opt_state=false", "train.block_diffusion=4"],
    "num_examples": 16, "trace_steps": 3}
SEED = 2 ** 31 + 43


def _load(relpath):
    with open(os.path.join(REPO, relpath)) as fh:
        return json.load(fh)


# -- the committed manifest, by name -----------------------------------------

THE_FIVE = {"mfu_sdar", "flash_bd_fwd_roofline", "flash_bd_bwd_roofline",
            "bd_noise_ms", "qk_norm_ms"}
LISTS_THE_CELL = benchmark_tiny_tree.EVERY_TRAINING_CELL | THE_FIVE | {
    "attn_core_ms", "moe_ms", "moe_load_max_over_mean"}


def test_the_real_manifest_has_the_configuration_and_the_cell():
    """Found by name: no place, no count (PERF.md section 6, PR 40)."""
    real = _load("BENCHMARK.json")
    config = next(c for c in real["configs"] if c["name"] == CONFIG)
    assert config["source"] == ("https://huggingface.co/JetLM/"
                                "SDAR-30B-A3B-Chat/blob/main/config.json")
    assert config["file"] == f"benchmark/configs/{CONFIG}.json"
    assert sorted(config["reduced"]) == ["layers_held", "num_experts",
                                         "vocab_size"]
    mine = next(w for w in real["workloads"] if w["name"] == CELL)
    assert (mine["config"], mine["traffic"], mine["chips"]) == (
        CONFIG, TRAFFIC, 1)
    traffic = _load(f"benchmark/traffic/{TRAFFIC}.json")
    assert traffic["kind"] == "train_steps"
    assert {"train.global_batch=1", "data.seq_len=8192",
            "data.vocab_size=19072", "mesh.data=1",
            "train.shard_opt_state=false", "train.block_diffusion=4"} == set(
                traffic["overrides"])
    assert (traffic["num_examples"], traffic["repeat_min"],
            traffic["repeat_max"], traffic["trace_steps"],
            traffic["reference_block_rows"]) == (256, 0.0, 0.9, 12, 1)


def test_the_real_manifest_lists_the_cell_on_what_it_reads():
    """A later PR may list the cell on more; the readers that find nothing
    in this model do not list it."""
    real = _load("BENCHMARK.json")
    listed = benchmark_tiny_tree.metrics_listing(real, CELL)
    assert listed >= LISTS_THE_CELL
    assert not listed & {
        "mfu", "mfu_sparse", "mfu_zaya1", "mfu_mellum2", "mfu_granite4h",
        "cca_mix_ms", "moe_router_ms", "flash_fwd_roofline",
        "flash_window_fwd_roofline", "flash_full_fwd_roofline",
        "flash_hybrid_fwd_roofline", "collective_ms", "dropout_ms",
        "ssm_ms", "ssm_scan_roofline"}
    for m in real["per_layer"]:
        if m["name"] in THE_FIVE:
            assert m["workloads"] == [CELL] or CELL in m["workloads"]
            assert m["moves"] == "train_tokens_per_s"
            assert m["layer"] in ("model code", "kernels")
            assert os.path.exists(os.path.join(
                REPO, "benchmark", "layer_metrics", m["name"] + ".py"))


# -- the configuration's file ------------------------------------------------


def test_the_file_holds_every_published_key_unchanged():
    body = _load(f"benchmark/configs/{CONFIG}.json")
    assert sorted(body["reduced"]) == ["layers_held", "num_experts",
                                       "vocab_size"]
    differs = sorted(k for k, v in PUBLISHED.items()
                     if k not in body or body[k] != v)
    assert differs == ["num_experts", "vocab_size"]
    assert body["vocab_size"] == 19_072 == 149 * 128
    assert 151_936 // 8 == 18_992 <= body["vocab_size"] < 18_992 + 128
    assert body["num_experts"] == 16 == 128 // 8
    assert body["experts_held"] == [0, 16]
    assert body["published"] == dict(
        body["published"], num_experts=128, vocab_size=151_936,
        num_hidden_layers=48)
    assert body["layers_held"] == list(range(6))
    assert body["mlp_layer_types"] == ["sparse"] * 48
    assert body["preset"] == "sdar_30b_a3b_lm"
    assert body["precision"] == "bfloat16"
    assert body["embd_pdrop"] == body["resid_pdrop"] == 0.0
    assert "8 chips share each layer" in body["deployment"]
    assert "645,950,976 parameters, 10.34 GB" in body["deployment"]
    assert body["noise"]["block_length"] == 4
    assert body["noise"]["mask_id"] == 3 and body["noise"]["min_rate"] == 1e-3
    # Every reading that is not in the config says what it was chosen over.
    for key in ("block_length", "noise_schedule", "loss_weight", "shift",
                "layout", "qk_norm", "seq_len"):
        assert "chosen over" in body["assumed"][key].lower(), key
    for key in ("mask_id", "router", "aux_loss", "optimizer", "kernel_init"):
        assert key in body["assumed"], key


def test_the_preset_runs_what_the_file_states():
    from deeplearning_cfn_tpu.presets import get_preset

    body = _load(f"benchmark/configs/{CONFIG}.json")
    cell = types.SimpleNamespace(
        config=body, traffic=_load(f"benchmark/traffic/{TRAFFIC}.json"))
    cfg = train_steps.build_program_config(cell, 1)
    assert cfg.model.name == "gpt_sdar_30b_a3b"
    assert list(cfg.model.kwargs["layers_held"]) == body["layers_held"]
    assert list(cfg.model.kwargs["experts_held"]) == body["experts_held"]
    assert cfg.model.kwargs["remat_blocks"] is True
    assert (cfg.train.global_batch, cfg.data.seq_len, cfg.data.vocab_size,
            cfg.train.block_diffusion) == (1, 8192, body["vocab_size"],
                                           body["noise"]["block_length"])
    hp = body["optimizer"]
    assert (cfg.optimizer.b1, cfg.optimizer.b2, cfg.optimizer.weight_decay,
            cfg.optimizer.grad_clip_norm, cfg.schedule.base_lr,
            cfg.schedule.warmup_steps) == (
                hp["b1"], hp["b2"], hp["weight_decay"], hp["grad_clip_norm"],
                hp["base_lr"], hp["warmup_steps"])
    assert get_preset("sdar_30b_a3b_lm").train.block_diffusion == 4


def test_limits_are_committed_with_the_readings_they_were_set_from():
    """At least twice the largest sound reading, and every control over at
    least one limit, by the file's own table."""
    body = _load(f"benchmark/configs/{CONFIG}.json")
    limits, table = body["limits"], body["limits_set_from"]["table"]
    numbers = lambda row: {k: [float(x) for x in str(v).replace("..", ",")
                                .split(",")] for k, v in row.items()}
    sound = numbers(table["sound"])
    assert set(limits) == set(sound) == {
        "train_loss_rel", "train_grad_norm_gap", "train_change_norm_gap"}
    for name, limit in limits.items():
        assert limit >= 2.0 * max(sound[name]), name
    controls = {k: numbers(v) for k, v in table.items() if k != "sound"}
    assert set(controls) >= {
        "int8", "clean_sees_noised", "staircase_off_by_one",
        "positions_run_on", "no_qk_norm", "no_rate_weight", "an_expert_out"}
    for control, readings in controls.items():
        assert any(min(readings[name]) > limits[name] for name in limits), \
            control


# -- the operations, by hand -------------------------------------------------


def test_operations_a_token_are_the_count_by_hand():
    config = _load(f"benchmark/configs/{CONFIG}.json")
    assert opcount_sdar.live_pairs(8192, 4) == 8192 ** 2 + 8192 * 4 \
        == 67_141_632
    # Noised over noised L b, noised over clean L (L - b) / 2, clean over
    # clean L (L + b) / 2.
    assert 8192 * 4 + 8192 * 8188 // 2 + 8192 * 8196 // 2 == 67_141_632
    parts = opcount_sdar.forward_parts(config, 8192)
    # Six layers, two positions a data token: q, o 2048 x 4096, k, v 2048 x
    # 512; the router 2048 x 128; of a position's 8 experts one in
    # expectation is held here (16 of 128), three products of 2048 x 768.
    assert parts["projections"] == 6 * 2 * 2 * (2 * 2048 * 4096
                                                + 2 * 2048 * 512)
    assert parts["router"] == 6 * 2 * 2 * 2048 * 128
    assert parts["experts"] == 6 * 2 * 6 * 2048 * 768 * 1.0
    # A data token's live pairs: L + b columns, 32 heads of 128, two
    # products of a multiply-add.
    assert parts["cores"] == 6 * 4 * 32 * 128 * 8196
    assert parts["head"] == 2 * 2048 * 19072
    total = opcount_sdar.train_flops_per_token(config, 8192)
    assert total == 3 * sum(parts.values())
    assert total == pytest.approx(4.369e9, rel=1e-3)
    # The mechanism does most of the counted work: attention's cores 55 %.
    assert 0.54 < parts["cores"] / sum(parts.values()) < 0.56


def test_the_kernels_least_time_by_hand():
    peaks = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    flops, nbytes = opcount_sdar.flash_layout(1, 32, 4, 8192, 4, 128, False)
    assert flops == 4 * 32 * 67_141_632 * 128
    # q and o 32 heads, k and v 4 heads, 16,384 positions of 128 in bfloat16.
    assert nbytes == 2 * 128 * 16384 * (2 * 32 + 2 * 4)
    least, bound = opcount.roofline_seconds(flops, nbytes, peaks)
    assert bound == "compute" and least == pytest.approx(5.584e-3, rel=1e-3)
    back, moved = opcount_sdar.flash_layout(1, 32, 4, 8192, 4, 128, True)
    assert back == 2.5 * flops and moved == 2 * nbytes


# -- the five per-layer metrics PR 43 brought --------------------------------


def test_the_new_readers_find_their_scopes_and_nothing_elsewhere():
    """On hand-written operations: each reader sums its own scopes, forward,
    recomputed and backward; a program without them (the parent commit,
    another configuration) leaves the metric out and does not raise."""
    m = "jit(train_step)/jvp(TransformerCausalLm)/layer_1/checkpoint"
    t = "jit(train_step)/transpose(jvp(TransformerCausalLm))/layer_1/" \
        "rematted_computation"
    flash = "self_attn/core_attention/flash"
    ops = [("jit(train_step)/bd_noise/threefry2x32", 0.0010),
           ("jit(train_step)/bd_noise/concatenate", 0.0006),
           ("jit(train_step)/jvp(TransformerCausalLm)/bd_noise/slice", 0.0004),
           (f"{m}/self_attn/qk_norm/query_norm/mul", 0.004),
           (f"{t}/self_attn/qk_norm/key_norm/mul", 0.002),
           (f"{m}/{flash}_fwd", 0.100), (f"{t}/{flash}_fwd", 0.100),
           (f"{t}/{flash}_bwd_dkdv", 0.200), (f"{t}/{flash}_bwd_dq", 0.150),
           (f"{m}/self_attn/query/dot_general", 0.5),
           (f"{m}/mlp/moe_experts/gmm", 0.5)]
    mine = types.SimpleNamespace(
        config=_load(f"benchmark/configs/{CONFIG}.json"))
    peaks = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    said = []
    ctx = {"cell": mine, "peaks": peaks, "trace": object(),
           "scoped_ops": ops, "say": said.append,
           "run": {"steps": 2, "global_batch": 1, "seq_len": 8192},
           "end_to_end": {"train_tokens_per_s": 12_000.0},
           "device": {"count": 1}}
    read = lambda name: manifest.load_module(
        f"benchmark/layer_metrics/{name}.py", name).read
    assert read("bd_noise_ms")(ctx) == pytest.approx(1.0)
    assert read("qk_norm_ms")(ctx) == pytest.approx(3.0)
    # Six layers' forward calls once each: 6 x 5.584 ms against 100 ms a
    # step in the kernel, which ran each twice.
    assert read("flash_bd_fwd_roofline")(ctx) == pytest.approx(
        100 * 6 * 5.584 / 100.0, rel=1e-3)
    assert "compute-bound" in said[-1]
    assert read("flash_bd_bwd_roofline")(ctx) == pytest.approx(
        100 * 6 * 2.5 * 5.584 / 175.0, rel=1e-3)
    assert read("mfu_sdar")(ctx) == pytest.approx(
        100 * 4.369e9 * 12_000 / 197e12, rel=1e-3)
    assert "GFLOP a trained token" in said[-1]
    bare = dict(ctx, scoped_ops=ops[-2:])
    for name in ("bd_noise_ms", "qk_norm_ms", "flash_bd_fwd_roofline",
                 "flash_bd_bwd_roofline"):
        assert read(name)(bare) is None, name
    mellum = types.SimpleNamespace(
        config=_load("benchmark/configs/mellum2_12b.json"))
    for name in ("flash_bd_fwd_roofline", "flash_bd_bwd_roofline",
                 "mfu_sdar"):
        assert read(name)(dict(ctx, cell=mellum)) is None, name
        assert read(name)(dict(ctx, peaks=None)) is None, name


# -- the cell rehearsed through run.py; the controls -------------------------


@pytest.fixture(scope="module")
def cell():
    return types.SimpleNamespace(
        name="tiny_sdar", chips=1,
        config=dict(_load(f"benchmark/configs/{CONFIG}.json"), **TINY),
        traffic=dict(_load(f"benchmark/traffic/{TRAFFIC}.json"),
                     **TINY_TRAFFIC),
        reference=manifest.load_module(
            f"benchmark/references/{CONFIG}.py", "ref_sdar_30b_a3b"))


@pytest.fixture(scope="module")
def tree(tmp_path_factory):
    """``benchmark_tiny_tree``'s copy with a tiny ``sdar_moe`` configuration,
    traffic and cell added beside what is there, on every list that names
    the real cell."""
    dst = benchmark_tiny_tree.build(str(tmp_path_factory.mktemp("sdar")))
    bench = os.path.join(dst, "benchmark")
    with open(os.path.join(dst, "BENCHMARK.json")) as fh:
        m = json.load(fh)
    with open(os.path.join(bench, "configs", "sdar_tiny.json"), "w") as fh:
        json.dump(dict(_load(f"benchmark/configs/{CONFIG}.json"), **TINY,
                       name="sdar_tiny"), fh, indent=1)
    shutil.copy(os.path.join(bench, "references", f"{CONFIG}.py"),
                os.path.join(bench, "references", "sdar_tiny.py"))
    with open(os.path.join(bench, "traffic", "tiny_train_sdar.json"),
              "w") as fh:
        json.dump(dict(_load(f"benchmark/traffic/{TRAFFIC}.json"),
                       **TINY_TRAFFIC), fh, indent=1)
    m["configs"].append({
        "name": "sdar_tiny", "source": "CPU rehearsal",
        "file": "benchmark/configs/sdar_tiny.json", "reduced": ["tiny"],
        "why": "CPU rehearsal"})
    m["workloads"].append({
        "name": "tiny_sdar", "config": "sdar_tiny",
        "traffic": "tiny_train_sdar", "chips": 1, "why": "CPU rehearsal"})
    benchmark_tiny_tree.list_like(m, "tiny_sdar", CELL)
    with open(os.path.join(dst, "BENCHMARK.json"), "w") as fh:
        json.dump(m, fh, indent=1)
    return dst


@pytest.mark.parametrize("trace", [0, 1])
def test_tiny_sdar_cell_runs_and_is_correct(tree, trace):
    p = run_cell(tree, "tiny_sdar", trace=trace)
    line = last_line(p)
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] >= 1
    wanted = {"train_tokens_per_s", "setup_s"} if not trace else \
        {"step_ms", "compile_s", "input_stall_ms", "dispatch_ms",
         "moe_load_max_over_mean"}
    assert wanted <= set(line["metrics"])
    if trace:
        # Nothing of the device trace on a CPU; no reader raised.
        for name in sorted(THE_FIVE | {"attn_core_ms", "blocks_ms", "moe_ms",
                                       "moe_gmm_roofline", "hbm_peak_gb"}):
            assert f"per-layer {name}: nothing to read" in p.stdout
    assert "compile requests inside the window: 0" in p.stdout
    assert "compare train_change_norm_gap" in p.stdout


def test_a_manifest_without_the_cell_fails_at_once_on_its_name(tmp_path):
    """What the driver sees when it tries the cell on the parent commit: a
    manifest without the cell ends ``run.py`` with exit code 1 and a
    ``KeyError`` that names it, before jax is imported."""
    tree = str(tmp_path)
    shutil.copytree(os.path.join(REPO, "benchmark"),
                    os.path.join(tree, "benchmark"),
                    ignore=shutil.ignore_patterns("__pycache__"))
    real = _load("BENCHMARK.json")
    real["workloads"] = [w for w in real["workloads"] if w["name"] != CELL]
    with open(os.path.join(tree, "BENCHMARK.json"), "w") as fh:
        json.dump(real, fh)
    p = subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload", CELL, "--seed",
         "1", "--seconds", "1", "--trace", "0"], cwd=tree,
        env=benchmark_tiny_tree.env(), capture_output=True, text=True,
        timeout=120)
    assert p.returncode == 1
    assert f"BENCHMARK.json has no workload named '{CELL}'" in p.stdout


@pytest.fixture(scope="module")
def followed(cell):
    """The tiny cell's weights and batches, the sound reference's three
    steps, and the reference with a fault in its place."""
    import jax

    from deeplearning_cfn_tpu.train.task import build_task

    cfg = train_steps.build_program_config(cell, SEED)
    task = build_task(cfg)
    shapes = jax.eval_shape(task.init, weights.seed_key(SEED))["params"]
    make = jax.jit(lambda key: weights.make(shapes, key))
    tokens = train_steps.make_tokens(SEED, cell.traffic, 64, 96)
    batches = [tokens[i * 2:(i + 1) * 2] for i in range(3)]
    hp = dict(cell.config["optimizer"])
    rng = jax.random.PRNGKey(17)
    follow = lambda **kw: cell.reference.train_steps(
        make(weights.seed_key(SEED)), batches, cell.config, hp, rng=rng, **kw)
    return follow, follow()


@pytest.mark.parametrize("control", [
    dict(faults=("clean_sees_noised",)),
    dict(faults=("staircase_off_by_one",)),
    dict(faults=("positions_run_on",)), dict(faults=("no_qk_norm",)),
    dict(faults=("no_rate_weight",)), dict(experts_out=(3,))],
    ids=lambda c: str(next(iter(c.values()))[0]))
def test_a_control_is_not_correct(cell, followed, control):
    """The configuration's own controls: the reference in the program's
    place with one fault reads ``correct`` false. (That the program's own
    steps read true is the rehearsal above.)"""
    follow, sound = followed
    said = []
    limits = cell.config["limits"]
    assert compare.train(sound, sound, limits, said.append)
    assert not compare.train(follow(**control), sound, limits, said.append)
    assert any("OVER THE LIMIT" in s for s in said)


def test_the_reference_needs_the_trainers_key(cell):
    with pytest.raises(ValueError, match="rng"):
        cell.reference.train_steps({}, [], cell.config,
                                   dict(cell.config["optimizer"]))


def test_calibration_script_reads_its_controls_in_the_tiny_tree(tree):
    """``calibrate_sdar_30b_a3b.py`` end to end at the tiny size: it finds
    the cell, follows the program's own first steps with the reference (a
    sound reading, within the tiny cell's limits) and reads two of its
    controls, both far over them."""
    p = subprocess.run(
        [sys.executable, "benchmark/calibrate_sdar_30b_a3b.py",
         "--workload", "tiny_sdar", "--seeds", "1", "--controls",
         "staircase_off_by_one,no_rate_weight"], cwd=tree,
        env=benchmark_tiny_tree.env(), capture_output=True, text=True,
        timeout=600)
    assert p.returncode == 0, p.stdout[-2000:] + p.stderr[-2000:]
    lines = [line for line in p.stdout.splitlines() if "READING" in line]
    assert "sound (within the file's limits)" in lines[0]
    readings = lines[1:]
    assert [line.split("control ")[1].split(":")[0] for line in readings] \
        == ["staircase_off_by_one", "no_rate_weight"]
    for line in readings:
        grad = float(line.split("train_grad_norm_gap ")[1].split(",")[0])
        assert grad > TINY["limits"]["train_grad_norm_gap"], line


def test_the_reference_compiles_outside_the_persistent_cache(tmp_path):
    """What compiles under the reference's ``_uncached`` leaves no entry in
    jax's persistent cache, and what compiles after it does again: on the
    chip the reference's entries pushed the timed step's out of a cache
    that holds little more than that one (PERF.md, PR 43)."""
    code = (
        "import os, sys, importlib.util, jax, jax.numpy as jnp\n"
        "jax.config.update('jax_persistent_cache_min_entry_size_bytes', -1)\n"
        "spec = importlib.util.spec_from_file_location('ref', sys.argv[1])\n"
        "ref = importlib.util.module_from_spec(spec)\n"
        "spec.loader.exec_module(ref)\n"
        "held = lambda: len(os.listdir(sys.argv[2])) "
        "if os.path.isdir(sys.argv[2]) else 0\n"
        "with ref._uncached():\n"
        "    jax.jit(lambda x: jnp.sin(x) * 3)(jnp.ones(8)).block_until_ready()\n"
        "inside = held()\n"
        "jax.jit(lambda x: jnp.cos(x) * 5)(jnp.ones(8)).block_until_ready()\n"
        "print(inside, held())\n")
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_ENABLE_COMPILATION_CACHE="true",  # the suite's is off
               JAX_COMPILATION_CACHE_DIR=str(tmp_path / "cache"),
               JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS="0")
    p = subprocess.run(
        [sys.executable, "-c", code,
         os.path.join(REPO, f"benchmark/references/{CONFIG}.py"),
         str(tmp_path / "cache")],
        env=env, capture_output=True, text=True, timeout=300)
    assert p.returncode == 0, p.stderr[-2000:]
    inside, after = (int(n) for n in p.stdout.split())
    assert inside == 0 and after > 0
