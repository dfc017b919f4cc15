"""The ``keye_vl2_30b_a3b`` configuration: what the committed manifest says
of it, found by name; its file against the source's published ``config``;
the op-count module against numbers worked by hand; the seven readers PR 47
brought on hand-written operations; and, at a size a test run can hold on the
CPU, the cell rehearsed end to end through ``run.py`` in a tiny tree built by
adding files, the configuration's own controls reading ``correct`` false, and
its calibration script.

(The selection through the kernels, the program against the reference leaf
by leaf, the shares and the published widths are ``tests/test_keye.py``.)"""

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

from harness import (compare, manifest, opcount,  # noqa: E402
                     opcount_keye_vl2, train_steps, weights)

import benchmark_tiny_tree  # noqa: E402
from test_benchmark_cells_train import last_line, run_cell  # noqa: E402

CELL, CONFIG = "keye_vl2_30b_a3b_train_16k", "keye_vl2_30b_a3b"
TRAFFIC = "train_packed_16k_v19072"
SOURCE = ("https://huggingface.co/Kwai-Keye/Keye-VL-2.0-30B-A3B/blob/main/"
          "config.json")

# The source's config.json as the catalog has it.
PUBLISHED = {
    "attention_bias": False, "decoder_sparse_step": 1, "head_dim": 128,
    "hidden_act": "silu", "hidden_size": 2048, "intermediate_size": 6144,
    "max_position_embeddings": 262144, "max_window_layers": 48,
    "mlp_only_layers": [], "model_type": "KeyeVL2",
    "moe_intermediate_size": 768, "norm_topk_prob": True,
    "num_attention_heads": 32, "num_experts": 128, "num_experts_per_tok": 8,
    "num_hidden_layers": 48, "num_key_value_heads": 4,
    "num_local_experts": 128, "rms_norm_eps": 1e-06,
    "rope_scaling": {"mrope_section": [16, 24, 24], "rope_type": "default",
                     "type": "default"},
    "rope_theta": 10000000,
    "sa_config": {"indexer_head_dim": 64, "indexer_num_heads": 16,
                  "indexer_num_kv_heads": 1, "kv_chunk_size": 512,
                  "q_chunk_size": 512, "topk": 2048},
    "sliding_window": None, "tie_word_embeddings": False,
    "use_sliding_window": False, "vocab_size": 151936,
}

# What ``gpt_keye_tiny`` (models/lm.py) is, in the source's keys: all 8
# experts held, 2 a token; a row of 64 keeps 16.
TINY = {
    "hidden_size": 64, "num_attention_heads": 4, "num_key_value_heads": 2,
    "head_dim": 16, "moe_intermediate_size": 32, "num_experts": 8,
    "num_experts_per_tok": 2, "num_hidden_layers": 2, "vocab_size": 96,
    "layers_held": [0, 1], "experts_held": [0, 8],
    "mlp_layer_types": ["sparse", "sparse"],
    "rope_scaling": {"mrope_section": [2, 3, 3]},
    "sa_config": {"indexer_head_dim": 8, "indexer_num_heads": 2,
                  "indexer_num_kv_heads": 1, "topk": 16},
    "published": {"num_experts": 8, "vocab_size": 96,
                  "num_hidden_layers": 2},
    "overrides": [
        "model.name=gpt_keye_tiny", "model.kwargs.layers_held=[0,1]",
        "model.kwargs.experts_held=[0,8]", "train.dtype=float32",
        "data.synthetic=true", "train.log_every_steps=1",
        "data.use_native_loader=false", "checkpoint.every_steps=0",
        "eval.enabled=false"],
    "precision": "float32",
    # float32 on the CPU against float32: what is left is the order of the
    # sums. Each control moves one of them a hundredfold and more.
    "limits": {"train_loss_rel": 1e-5, "train_grad_norm_gap": 1e-3,
               "train_change_norm_gap": 1e-2},
}
TINY_TRAFFIC = {
    "overrides": ["train.global_batch=2", "data.seq_len=64",
                  "data.vocab_size=96", "mesh.data=1",
                  "train.shard_opt_state=false"],
    "num_examples": 16, "trace_steps": 3}
SEED = 2 ** 31 + 47

THE_SEVEN = {"mfu_keye_vl2", "indexer_ms", "index_select_ms",
             "indexer_loss_ms", "flash_sel_fwd_roofline",
             "flash_sel_bwd_roofline", "index_select_roofline"}
LISTS_THE_CELL = benchmark_tiny_tree.EVERY_TRAINING_CELL | THE_SEVEN | {
    "attn_core_ms", "moe_ms", "moe_load_max_over_mean", "moe_gmm_roofline",
    "qk_norm_ms"}
PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}


def _load(relpath):
    with open(os.path.join(REPO, relpath)) as fh:
        return json.load(fh)


# -- the committed manifest, by name -----------------------------------------


def test_the_real_manifest_has_the_configuration_and_the_cell():
    """Found by name: no place, no count."""
    real = _load("BENCHMARK.json")
    config = next(c for c in real["configs"] if c["name"] == CONFIG)
    assert config["source"] == SOURCE
    assert config["file"] == f"benchmark/configs/{CONFIG}.json"
    assert sorted(config["reduced"]) == ["layers_held", "num_experts",
                                         "vocab_size"]
    mine = next(w for w in real["workloads"] if w["name"] == CELL)
    assert (mine["config"], mine["traffic"], mine["chips"]) == (
        CONFIG, TRAFFIC, 1)
    assert len(mine["why"]) <= 200 and len(config["why"]) <= 200
    traffic = _load(f"benchmark/traffic/{TRAFFIC}.json")
    assert traffic["kind"] == "train_steps"
    assert {"train.global_batch=1", "data.seq_len=16384",
            "data.vocab_size=19072", "mesh.data=1",
            "train.shard_opt_state=false"} == set(traffic["overrides"])
    assert (traffic["num_examples"], traffic["repeat_min"],
            traffic["repeat_max"], traffic["trace_steps"],
            traffic["reference_block_rows"]) == (256, 0.0, 0.9, 12, 1)


def test_the_real_manifest_lists_the_cell_on_what_it_reads():
    real = _load("BENCHMARK.json")
    listed = benchmark_tiny_tree.metrics_listing(real, CELL)
    assert listed >= LISTS_THE_CELL
    assert not listed & {
        "mfu", "mfu_sparse", "mfu_zaya1", "mfu_mellum2", "mfu_granite4h",
        "mfu_sdar", "flash_bd_fwd_roofline", "bd_noise_ms", "cca_mix_ms",
        "moe_router_ms", "flash_fwd_roofline", "flash_window_fwd_roofline",
        "collective_ms", "dropout_ms", "ssm_ms"}
    for m in real["per_layer"]:
        if m["name"] in THE_SEVEN:
            assert m["workloads"] == [CELL] or CELL in m["workloads"]
            assert m["moves"] == "train_tokens_per_s"
            assert m["layer"] in ("model code", "kernels")
            assert os.path.exists(os.path.join(
                REPO, "benchmark", "layer_metrics", m["name"] + ".py"))


# -- the configuration's file ------------------------------------------------


def test_the_file_holds_every_published_key_unchanged():
    body = _load(f"benchmark/configs/{CONFIG}.json")
    assert body["source"] == SOURCE
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
    assert body["preset"] == "keye_vl2_30b_a3b_lm"
    assert body["precision"] == "bfloat16"
    assert body["embd_pdrop"] == body["resid_pdrop"] == 0.0
    assert "8 chips share each layer" in body["deployment"]
    assert "659,517,696 parameters, 10.55 GB" in body["deployment"]
    # Every reading that is not in the config says what it was chosen over.
    for key in ("selection", "indexer_input", "indexer_rope", "indexer_loss",
                "index_precision", "seq_len"):
        assert "chosen over" in body["assumed"][key].lower(), key
    for key in ("ties", "index_key_norm", "index_scale", "qk_norm", "mrope",
                "router", "aux_loss", "optimizer", "kernel_init",
                "vision_tower"):
        assert key in body["assumed"], key


def test_the_preset_runs_what_the_file_states():
    from deeplearning_cfn_tpu.models.lm import _KEYE_VL2_30B_A3B

    body = _load(f"benchmark/configs/{CONFIG}.json")
    cell = types.SimpleNamespace(
        config=body, traffic=_load(f"benchmark/traffic/{TRAFFIC}.json"))
    cfg = train_steps.build_program_config(cell, 1)
    assert cfg.model.name == "gpt_keye_vl2_30b_a3b"
    assert list(cfg.model.kwargs["layers_held"]) == body["layers_held"]
    assert list(cfg.model.kwargs["experts_held"]) == body["experts_held"]
    assert cfg.model.kwargs["remat_blocks"] is True
    assert (cfg.train.global_batch, cfg.data.seq_len, cfg.data.vocab_size,
            cfg.train.block_diffusion) == (1, 16384, body["vocab_size"], 0)
    hp = body["optimizer"]
    assert (cfg.optimizer.b1, cfg.optimizer.b2, cfg.optimizer.weight_decay,
            cfg.optimizer.grad_clip_norm, cfg.schedule.base_lr,
            cfg.schedule.warmup_steps) == (
                hp["b1"], hp["b2"], hp["weight_decay"], hp["grad_clip_norm"],
                hp["base_lr"], hp["warmup_steps"])
    z, sa = _KEYE_VL2_30B_A3B, body["sa_config"]
    assert dict(z["indexer"]) == {
        "heads": sa["indexer_num_heads"], "head_dim": sa["indexer_head_dim"],
        "topk": sa["topk"]}
    assert list(z["rope"].sections) == body["rope_scaling"]["mrope_section"]
    assert z["rope"].theta == body["rope_theta"]
    assert (z["hidden_size"], z["heads"], z["kv_heads"], z["head_dim"],
            z["experts"], z["top_k"], z["expert_width"], z["num_layers"]) \
        == (body["hidden_size"], body["num_attention_heads"],
            body["num_key_value_heads"], body["head_dim"],
            body["published"]["num_experts"], body["num_experts_per_tok"],
            body["moe_intermediate_size"], body["num_hidden_layers"])


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
        "int8", "no_selection", "topk_1024", "selection_not_causal",
        "indexer_loss_dropped", "indexer_sees_lm_gradient", "an_expert_out"}
    for control, readings in controls.items():
        assert any(min(readings[name]) > limits[name] for name in limits), \
            control


# -- the operations, by hand -------------------------------------------------


def test_operations_a_token_are_the_count_by_hand():
    config = _load(f"benchmark/configs/{CONFIG}.json")
    assert opcount_keye_vl2.causal_pairs(16384) == 16384 * 16385 / 2
    share = opcount_keye_vl2.kept_share_expected(16384, 2048)
    # The first 2048 rows whole, 2048 a row after.
    assert share == (2048 * 2049 / 2 + 2048 * 14336) / (16384 * 16385 / 2)
    assert share == pytest.approx(0.2344, abs=2e-4)
    assert opcount_keye_vl2.kept_share_expected(1024, 2048) == 1.0
    parts = opcount_keye_vl2.forward_parts(config, 16384, share)
    # Six layers: q, o 2048 x 4096, k, v 2048 x 512; the indexer 2048 x
    # (1024 + 64 + 16); its scores 16 heads of 64 over a token's 8192.5
    # causal pairs; attention 32 heads of 128, two products, over the kept.
    assert parts["projections"] == 6 * 2 * (2 * 2048 * 4096 + 2 * 2048 * 512)
    assert parts["indexer_projections"] == 6 * 2 * 2048 * 1104
    assert parts["index_scores"] == 6 * 2 * 16 * 64 * 8192.5
    assert parts["cores"] == pytest.approx(
        6 * 4 * 32 * 128 * share * 8192.5)
    assert parts["router"] == 6 * 2 * 2048 * 128
    assert parts["experts"] == 6 * 6 * 2048 * 768 * 1.0
    assert parts["head"] == 2 * 2048 * 19072
    total = opcount_keye_vl2.train_flops_per_token(config, 16384, share)
    rest = sum(v for k, v in parts.items() if k != "index_scores")
    assert total == pytest.approx(
        3 * rest + parts["index_scores"] * (1 + 2 * share))
    assert total == pytest.approx(1.8886e9, rel=1e-3)


def test_the_kernels_least_time_by_hand():
    share = 0.2344
    flops, nbytes = opcount_keye_vl2.flash_selected(
        1, 32, 4, 16384, 128, share, False)
    assert flops == pytest.approx(4 * 32 * share * 16384 * 16385 / 2 * 128)
    # q and o 32 heads, k and v 4 heads of 128 in bfloat16, a bit a pair.
    assert nbytes == 2 * 128 * 2 * 16384 * 36 + 16384 * 16385 / 2 / 8
    least, bound = opcount.roofline_seconds(flops, nbytes, PEAKS)
    assert bound == "compute" and least == pytest.approx(2.617e-3, rel=2e-3)
    back, _ = opcount_keye_vl2.flash_selected(1, 32, 4, 16384, 128, share,
                                              True)
    assert back == 2.5 * flops
    flops, nbytes = opcount_keye_vl2.index_select(1, 16, 64, 16384)
    assert flops == 2 * 16 * 64 * 16384 * 16385 / 2
    assert nbytes == 16384 * (2 * (1024 + 64) + 4 * 16) \
        + 16384 * 16385 / 2 / 8
    least, bound = opcount.roofline_seconds(flops, nbytes, PEAKS)
    assert bound == "compute" and least == pytest.approx(1.395e-3, rel=2e-3)


# -- the seven per-layer metrics PR 47 brought -------------------------------


def test_the_new_readers_find_their_scopes_and_nothing_elsewhere():
    """On hand-written operations: each reader sums its own scopes, forward,
    recomputed and backward; a program without them (the parent commit,
    another configuration) leaves the metric out and does not raise."""
    from deeplearning_cfn_tpu.obs.trace import get_tracer

    m = "jit(train_step)/jvp(TransformerCausalLm)/layer_1/checkpoint"
    t = "jit(train_step)/transpose(jvp(TransformerCausalLm))/layer_1/" \
        "rematted_computation"
    flash = "self_attn/core_attention/flash"
    ops = [(f"{m}/self_attn/indexer_proj/index_query/dot_general", 0.004),
           (f"{t}/self_attn/indexer_proj/index_key_norm/mul", 0.002),
           (f"{m}/self_attn/jit(_select_pallas)/indexer_select/index_select",
            0.060),
           (f"{m}/self_attn/indexer_loss/jit(_loss_pallas)/indexer_loss/"
            "index_loss", 0.080),
           (f"{t}/self_attn/indexer_loss/mul", 0.002),
           (f"{m}/{flash}_fwd", 0.100),
           (f"{t}/{flash}_bwd_dkdv", 0.200), (f"{t}/{flash}_bwd_dq", 0.150),
           (f"{m}/self_attn/query/dot_general", 0.5),
           (f"{m}/mlp/moe_experts/gmm", 0.5)]
    mine = types.SimpleNamespace(
        config=_load(f"benchmark/configs/{CONFIG}.json"))
    said = []
    ctx = {"cell": mine, "peaks": PEAKS, "trace": object(),
           "scoped_ops": ops, "say": said.append,
           "run": {"steps": 2, "global_batch": 1, "seq_len": 16384},
           "end_to_end": {"train_tokens_per_s": 15_000.0},
           "device": {"count": 1}}
    read = lambda name: manifest.load_module(
        f"benchmark/layer_metrics/{name}.py", name).read
    steps = get_tracer().registry.histogram(
        "attention.selected.kept_share.steps")
    if not steps.mean():
        # Without a step realized in this process nothing is read.
        for name in sorted(THE_SEVEN - {"indexer_ms", "index_select_ms",
                                        "indexer_loss_ms"}):
            assert read(name)(ctx) is None, name
        steps.observe(0.2344)
    share = steps.mean()
    assert read("indexer_ms")(ctx) == pytest.approx(3.0)
    assert read("index_select_ms")(ctx) == pytest.approx(30.0)
    assert read("indexer_loss_ms")(ctx) == pytest.approx(41.0)
    fwd, _ = opcount_keye_vl2.flash_selected(1, 32, 4, 16384, 128, share,
                                             False)
    assert read("flash_sel_fwd_roofline")(ctx) == pytest.approx(
        100 * 6 * fwd / 197e12 * 2 / 0.100, rel=1e-6)
    assert "compute-bound" in said[-1]
    assert read("flash_sel_bwd_roofline")(ctx) == pytest.approx(
        100 * 6 * 2.5 * fwd / 197e12 * 2 / 0.350, rel=1e-6)
    assert read("index_select_roofline")(ctx) == pytest.approx(
        100 * 6 * 1.395e-3 * 2 / 0.060, rel=2e-3)
    per_token = opcount_keye_vl2.train_flops_per_token(mine.config, 16384,
                                                       share)
    assert read("mfu_keye_vl2")(ctx) == pytest.approx(
        100 * per_token * 15_000 / 197e12)
    assert "GFLOP a trained token" in said[-1]
    bare = dict(ctx, scoped_ops=ops[-2:])
    for name in sorted(THE_SEVEN - {"mfu_keye_vl2"}):
        assert read(name)(bare) is None, name
    sdar = types.SimpleNamespace(
        config=_load("benchmark/configs/sdar_30b_a3b.json"))
    for name in ("flash_sel_fwd_roofline", "flash_sel_bwd_roofline",
                 "index_select_roofline", "mfu_keye_vl2"):
        assert read(name)(dict(ctx, cell=sdar)) is None, name
        assert read(name)(dict(ctx, peaks=None)) is None, name


# -- the cell rehearsed through run.py; the controls -------------------------


@pytest.fixture(scope="module")
def cell():
    return types.SimpleNamespace(
        name="tiny_keye", chips=1,
        config=dict(_load(f"benchmark/configs/{CONFIG}.json"), **TINY),
        traffic=dict(_load(f"benchmark/traffic/{TRAFFIC}.json"),
                     **TINY_TRAFFIC),
        reference=manifest.load_module(
            f"benchmark/references/{CONFIG}.py", "ref_keye_vl2_30b_a3b"))


@pytest.fixture(scope="module")
def tree(tmp_path_factory):
    """``benchmark_tiny_tree``'s copy with a tiny ``KeyeVL2`` configuration,
    traffic and cell added beside what is there, on every list that names
    the real cell."""
    dst = benchmark_tiny_tree.build(str(tmp_path_factory.mktemp("keye")))
    bench = os.path.join(dst, "benchmark")
    with open(os.path.join(dst, "BENCHMARK.json")) as fh:
        m = json.load(fh)
    with open(os.path.join(bench, "configs", "keye_tiny.json"), "w") as fh:
        json.dump(dict(_load(f"benchmark/configs/{CONFIG}.json"), **TINY,
                       name="keye_tiny"), fh, indent=1)
    shutil.copy(os.path.join(bench, "references", f"{CONFIG}.py"),
                os.path.join(bench, "references", "keye_tiny.py"))
    with open(os.path.join(bench, "traffic", "tiny_train_keye.json"),
              "w") as fh:
        json.dump(dict(_load(f"benchmark/traffic/{TRAFFIC}.json"),
                       **TINY_TRAFFIC), fh, indent=1)
    m["configs"].append({
        "name": "keye_tiny", "source": "CPU rehearsal",
        "file": "benchmark/configs/keye_tiny.json", "reduced": ["tiny"],
        "why": "CPU rehearsal"})
    m["workloads"].append({
        "name": "tiny_keye", "config": "keye_tiny",
        "traffic": "tiny_train_keye", "chips": 1, "why": "CPU rehearsal"})
    benchmark_tiny_tree.list_like(m, "tiny_keye", CELL)
    with open(os.path.join(dst, "BENCHMARK.json"), "w") as fh:
        json.dump(m, fh, indent=1)
    return dst


@pytest.mark.parametrize("trace", [0, 1])
def test_tiny_keye_cell_runs_and_is_correct(tree, trace):
    p = run_cell(tree, "tiny_keye", trace=trace)
    line = last_line(p)
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] >= 1
    wanted = {"train_tokens_per_s", "setup_s"} if not trace else \
        {"step_ms", "compile_s", "input_stall_ms", "dispatch_ms",
         "moe_load_max_over_mean"}
    assert wanted <= set(line["metrics"])
    if trace:
        # Nothing of the device trace on a CPU; no reader raised.
        for name in sorted((THE_SEVEN - {"mfu_keye_vl2"}) | {
                "attn_core_ms", "blocks_ms", "moe_ms", "moe_gmm_roofline",
                "qk_norm_ms", "hbm_peak_gb"}):
            assert f"per-layer {name}: nothing to read" in p.stdout
    assert "compile requests inside the window: 0" in p.stdout
    assert "compare train_change_norm_gap" in p.stdout


def test_a_manifest_without_the_cell_fails_at_once_on_its_name(tmp_path):
    """What the driver sees when it tries the cell on the parent commit
    under a manifest of its own; under this PR's manifest the parent ends
    on the preset's name (``PERF.md``, PR 47)."""
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
    follow = lambda **kw: cell.reference.train_steps(
        make(weights.seed_key(SEED)), batches, cell.config, hp, **kw)
    return follow, follow()


@pytest.mark.parametrize("control", [
    dict(faults=("no_selection",)), dict(faults=("topk_1024",)),
    dict(faults=("selection_not_causal",)),
    dict(faults=("indexer_loss_dropped",)),
    dict(faults=("indexer_sees_lm_gradient",)), dict(experts_out=(3,))],
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


def test_calibration_script_reads_its_controls_in_the_tiny_tree(tree):
    """``calibrate_keye_vl2_30b_a3b.py`` end to end at the tiny size: it
    finds the cell, follows the program's own first steps with the reference
    (a sound reading, within the tiny cell's limits) and reads two of its
    controls, both far over them."""
    p = subprocess.run(
        [sys.executable, "benchmark/calibrate_keye_vl2_30b_a3b.py",
         "--workload", "tiny_keye", "--seeds", "1", "--controls",
         "no_selection,indexer_loss_dropped"], cwd=tree,
        env=benchmark_tiny_tree.env(), capture_output=True, text=True,
        timeout=600)
    assert p.returncode == 0, p.stdout[-2000:] + p.stderr[-2000:]
    lines = [line for line in p.stdout.splitlines() if "READING" in line]
    assert "sound (within the file's limits)" in lines[0]
    readings = lines[1:]
    assert [line.split("control ")[1].split(":")[0] for line in readings] \
        == ["no_selection", "indexer_loss_dropped"]
    for line in readings:
        grad = float(line.split("train_grad_norm_gap ")[1].split(",")[0])
        assert grad > TINY["limits"]["train_grad_norm_gap"], line
