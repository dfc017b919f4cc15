"""The ``granite4_h_micro`` configuration: what the committed manifest says
of it, found by name; its file against the source's published ``config``;
the two op-count modules against numbers worked by hand; the five readers
PR 41 brought on hand-written operations; and, at a size a test run can hold
on the CPU, the cell rehearsed end to end through ``run.py`` in a tiny tree
built by adding files, the configuration's own controls reading ``correct``
false, and its calibration script.

(The program against the reference leaf by leaf, the chunked scan against
the recurrence and the published widths are ``tests/test_granite4_h.py`` and
``tests/test_ssd.py``.)"""

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

from harness import (compare, manifest, opcount, opcount_granite4h,  # noqa: E402
                     opcount_ssd, train_steps, weights)

import benchmark_tiny_tree  # noqa: E402
from test_benchmark_cells_train import last_line, run_cell  # noqa: E402

CELL, CONFIG = "granite4_h_micro_train_8k", "granite4_h_micro"

# The source's config.json as the catalog has it
# (https://huggingface.co/ibm-granite/granite-4.0-h-micro/blob/main/config.json),
# every key that says something of the model's shape.
PUBLISHED = {
    "attention_bias": False, "attention_multiplier": 0.015625,
    "embedding_multiplier": 12, "hidden_act": "silu", "hidden_size": 2048,
    "intermediate_size": 8192,
    "layer_types": (["mamba"] * 5 + ["attention"] + ["mamba"] * 4) * 4,
    "logits_scaling": 8, "mamba_chunk_size": 256, "mamba_conv_bias": True,
    "mamba_d_conv": 4, "mamba_d_head": 64, "mamba_d_state": 128,
    "mamba_expand": 2, "mamba_n_groups": 1, "mamba_n_heads": 64,
    "mamba_proj_bias": False, "max_position_embeddings": 131072,
    "model_type": "granitemoehybrid", "normalization_function": "rmsnorm",
    "num_attention_heads": 32, "num_experts_per_tok": 0,
    "num_hidden_layers": 40, "num_key_value_heads": 8,
    "num_local_experts": 0, "position_embedding_type": "nope",
    "residual_multiplier": 0.22, "rms_norm_eps": 1e-05, "rope_scaling": None,
    "rope_theta": 10000, "shared_intermediate_size": 8192,
    "tie_word_embeddings": True, "vocab_size": 100352,
}

# What ``gpt_granite4_h_tiny`` (models/lm.py) is, in the source's keys.
TINY = {
    "hidden_size": 64, "num_attention_heads": 4, "num_key_value_heads": 2,
    "attention_multiplier": 0.0625, "shared_intermediate_size": 128,
    "intermediate_size": 128, "mamba_n_heads": 4, "mamba_d_head": 32,
    "mamba_d_state": 16, "mamba_n_groups": 1, "mamba_d_conv": 4,
    "mamba_chunk_size": 8, "num_hidden_layers": 4, "vocab_size": 96,
    "layer_types": ["mamba", "attention", "mamba", "mamba"],
    "layers_held": [0, 1, 2, 3],
    "published": {"vocab_size": 96, "num_hidden_layers": 4},
    "overrides": [
        "model.name=gpt_granite4_h_tiny",
        "model.kwargs.layers_held=[0,1,2,3]", "train.dtype=float32",
        "data.synthetic=true", "train.log_every_steps=1",
        "data.use_native_loader=false", "checkpoint.every_steps=0",
        "eval.enabled=false"],
    "precision": "float32",
    # float32 on the CPU against float32: what is left is the order of the
    # sums (the chunked scan's exponential of a running sum against the
    # recurrence's running product). A state not handed on, a tap dropped or
    # the gate after the norm moves them a hundredfold and more.
    "limits": {"train_loss_rel": 1e-5, "train_grad_norm_gap": 1e-4,
               "train_change_norm_gap": 1e-2},
}
TINY_TRAFFIC = {
    "overrides": ["train.global_batch=4", "data.seq_len=32",
                  "data.vocab_size=96", "mesh.data=1",
                  "train.shard_opt_state=false"],
    "num_examples": 32, "trace_steps": 3}
SEED = 2 ** 31 + 41


def _load(relpath):
    with open(os.path.join(REPO, relpath)) as fh:
        return json.load(fh)


# -- the committed manifest, by name -----------------------------------------

THE_FIVE = {"ssm_ms", "ssm_scan_ms", "ssm_conv_ms", "ssm_scan_roofline",
            "mfu_granite4h"}
LISTS_THE_CELL = benchmark_tiny_tree.EVERY_TRAINING_CELL | THE_FIVE | {
    "attn_core_ms"}


def test_the_real_manifest_has_the_configuration_and_the_cell():
    """Found by name: no place, no count (PERF.md section 6, PR 40)."""
    real = _load("BENCHMARK.json")
    config = next(c for c in real["configs"] if c["name"] == CONFIG)
    assert config["source"] == ("https://huggingface.co/ibm-granite/"
                                "granite-4.0-h-micro/blob/main/config.json")
    assert config["file"] == f"benchmark/configs/{CONFIG}.json"
    assert sorted(config["reduced"]) == ["layers_held", "vocab_size"]
    mine = next(w for w in real["workloads"] if w["name"] == CELL)
    assert (mine["config"], mine["traffic"], mine["chips"]) == (
        CONFIG, "train_packed_8k_v12544", 1)
    traffic = _load("benchmark/traffic/train_packed_8k_v12544.json")
    assert traffic["kind"] == "train_steps"
    assert {"train.global_batch=1", "data.seq_len=8192",
            "data.vocab_size=12544", "mesh.data=1",
            "train.shard_opt_state=false"} == set(traffic["overrides"])
    assert (traffic["num_examples"], traffic["trace_steps"],
            traffic["reference_block_rows"]) == (256, 12, 1)


def test_the_real_manifest_lists_the_cell_on_what_it_reads():
    """A later PR may list the cell on more; the readers that find nothing
    in a dense, position-free model do not list it."""
    real = _load("BENCHMARK.json")
    listed = benchmark_tiny_tree.metrics_listing(real, CELL)
    assert listed >= LISTS_THE_CELL
    assert not listed & {
        "moe_ms", "moe_gmm_roofline", "moe_load_max_over_mean", "mfu",
        "mfu_sparse", "mfu_zaya1", "mfu_mellum2", "cca_mix_ms",
        "flash_fwd_roofline", "flash_window_fwd_roofline",
        "flash_full_fwd_roofline", "flash_hybrid_fwd_roofline",
        "collective_ms", "dropout_ms"}
    for m in real["per_layer"]:
        if m["name"] in THE_FIVE:
            assert m["workloads"] == [CELL] or CELL in m["workloads"]
            assert m["moves"] == "train_tokens_per_s"
            assert m["layer"] in ("model code", "kernels")


# -- the configuration's file ------------------------------------------------


def test_the_file_holds_every_published_key_unchanged():
    body = _load(f"benchmark/configs/{CONFIG}.json")
    assert sorted(body["reduced"]) == ["layers_held", "vocab_size"]
    differs = sorted(k for k, v in PUBLISHED.items()
                     if k not in body or body[k] != v)
    assert differs == ["vocab_size"]
    assert body["vocab_size"] == 12_544 == 98 * 128 == 100_352 // 8
    assert body["published"]["vocab_size"] == PUBLISHED["vocab_size"]
    assert body["published"]["num_hidden_layers"] == 40
    # One whole period: Mamba x 5, attention, Mamba x 4.
    assert body["layers_held"] == list(range(10))
    held = [body["layer_types"][i] for i in body["layers_held"]]
    assert held.count("mamba") == 9 and held[5] == "attention"
    assert body["preset"] == "granite4_h_micro_lm"
    assert body["precision"] == "bfloat16"
    assert body["embd_pdrop"] == body["resid_pdrop"] == 0.0
    assert "4 pipeline" in body["deployment"] or "four pipeline" in \
        body["deployment"]
    assert "8 chips sharing the vocabulary" in body["deployment"]
    assert "772,160,448 parameters, 12.36 GB" in body["deployment"]
    # Every reading that is not in the config says what it was chosen over.
    for key in ("mixer_projection_order", "convolution", "discretisation",
                "gated_norm", "packed_rows", "seeded_mixer"):
        assert "chosen over" in body["assumed"][key].lower(), key
    for key in ("attention", "multipliers", "sequence_length",
                "recomputation", "optimizer", "kernel_init"):
        assert key in body["assumed"], key


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
        "int8", "half_row", "state_dropped", "no_decay", "tap_dropped",
        "residual_1", "scores_over_8", "gate_after_norm"}
    for control, readings in controls.items():
        assert any(min(readings[name]) > limits[name] for name in limits), \
            control


# -- the operations, by hand -------------------------------------------------


def test_operations_a_token_are_the_count_by_hand():
    config = _load(f"benchmark/configs/{CONFIG}.json")
    parts = opcount_granite4h.forward_parts(config, 8192)
    # Nine Mamba layers: in_proj 2048 x (4096 + 4352 + 64), out_proj 4096 x
    # 2048, a multiply-add two operations; four taps on 4,352 channels.
    assert parts["ssm_projections"] == 9 * 2 * (2048 * 8512 + 4096 * 2048)
    assert parts["ssm_conv"] == 9 * 2 * 4 * 4352
    # The chunked scan at 256: C B^T once a group, the decayed block times
    # dt x a head, a chunk's closing state, C times the entering state.
    scan = 2 * 256 * 128 + 2 * 256 * 64 * 64 + 2 * 128 * 64 * 64 \
        + 2 * 128 * 64 * 64
    assert scan == 4_259_840
    assert opcount_ssd.scan_forward_flops_per_token(config) == scan
    assert parts["ssm_scan"] == 9 * scan
    # The attention layer: q, o 2048 x 2048, k, v 2048 x 512; q k^T and p v
    # over 32 heads of 64, a row sees 4096.5 columns on average.
    assert parts["attn_projections"] == 2 * (2 * 2048 * 2048
                                             + 2 * 2048 * 512)
    assert parts["attn_cores"] == 2 * 2 * 32 * 64 * 4096.5
    assert parts["mlp"] == 10 * 2 * 3 * 2048 * 8192
    assert parts["head"] == 2 * 2048 * 12544
    # A Mamba layer 156.6 MFLOP a token, the attention layer 155.2, the
    # head 51.4: 3 x (9 x 156.6 + 155.2 + 51.4) = 4.85 GFLOP a trained token.
    mamba = (parts["ssm_projections"] + parts["ssm_conv"]
             + parts["ssm_scan"]) / 9 + parts["mlp"] / 10
    attention = parts["attn_projections"] + parts["attn_cores"] \
        + parts["mlp"] / 10
    assert mamba == pytest.approx(156.6e6, rel=1e-3)
    assert attention == pytest.approx(155.2e6, rel=1e-3)
    total = opcount_granite4h.train_flops_per_token(config, 8192)
    assert total == 3 * sum(parts.values())
    assert total == pytest.approx(4.848e9, rel=1e-3)
    # The mixer without its MLP is about 36 % of a layer's counted work.
    assert 0.35 < (mamba - parts["mlp"] / 10) / mamba < 0.37


def test_the_scans_work_a_step_by_hand():
    config = _load(f"benchmark/configs/{CONFIG}.json")
    assert opcount_ssd.mamba_layers(config) == 9
    # x and y 4096 each, B and C 128 each in bfloat16, dt 64 in float32;
    # and as much again for their cotangents.
    a_token = 2 * (2 * (4096 + 128 + 128 + 4096) + 4 * 64)
    assert opcount_ssd.scan_bytes_per_token(config) == a_token == 34_304
    flops, nbytes = opcount_ssd.scan_step(config, 8192)
    assert flops == 3 * 4_259_840 * 8192 * 9
    assert nbytes == a_token * 8192 * 9
    least, bound = opcount.roofline_seconds(
        flops, nbytes, {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9})
    assert bound == "compute" and least == pytest.approx(4.783e-3, rel=1e-3)


# -- the five per-layer metrics PR 41 brought --------------------------------


def test_the_new_readers_find_their_scopes_and_nothing_elsewhere():
    """On hand-written operations: each reader sums its own scopes, forward,
    recomputed and backward; a program without them (the parent commit,
    another configuration) leaves the metric out and does not raise."""
    m = "jit(train_step)/jvp(TransformerCausalLm)/layer_1/checkpoint"
    t = "jit(train_step)/transpose(jvp(TransformerCausalLm))/layer_1/" \
        "rematted_computation"
    ops = [(f"{m}/self_attn/ssm_in_proj/in_proj/dot_general", 0.010),
           (f"{t}/self_attn/ssm_conv/conv/mul", 0.004),
           (f"{m}/self_attn/ssm_conv/conv/add", 0.002),
           (f"{m}/self_attn/ssm_scan/bgrcij,bgrcjp->bgrcip/dot_general",
            0.020),
           (f"{t}/self_attn/ssm_scan/while/body/mul", 0.030),
           (f"{t}/self_attn/ssm_gate_norm/gate_norm/mul", 0.003),
           (f"{t}/self_attn/ssm_out_proj/out_proj/dot_general", 0.005),
           (f"{m}/self_attn/query/dot_general", 0.5),
           (f"{m}/mlp/mlp_in/dot_general", 0.5)]
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
    assert read("ssm_ms")(ctx) == pytest.approx(37.0)
    assert said[-1] == ("ssm_ms, ms a step by scope: in_proj 5.00, conv 3.00, "
                        "scan 25.00, gate_norm 1.50, out_proj 2.50")
    assert read("ssm_scan_ms")(ctx) == pytest.approx(25.0)
    assert read("ssm_conv_ms")(ctx) == pytest.approx(3.0)
    # 942.2 GFLOP a step over nine layers: 4.783 ms at the peak, 25 measured.
    assert read("ssm_scan_roofline")(ctx) == pytest.approx(
        100 * 4.783 / 25.0, rel=1e-3)
    assert "compute-bound" in said[-1]
    assert read("mfu_granite4h")(ctx) == pytest.approx(
        100 * 4.848e9 * 12_000 / 197e12, rel=1e-3)
    assert "GFLOP a trained token" in said[-1]
    bare = dict(ctx, scoped_ops=ops[-2:])
    for name in ("ssm_ms", "ssm_scan_ms", "ssm_conv_ms", "ssm_scan_roofline"):
        assert read(name)(bare) is None, name
    laguna = types.SimpleNamespace(
        config=_load("benchmark/configs/laguna_xs2.json"))
    for name in ("ssm_scan_roofline", "mfu_granite4h"):
        assert read(name)(dict(ctx, cell=laguna)) is None, name
        assert read(name)(dict(ctx, peaks=None)) is None, name


# -- the cell rehearsed through run.py; the controls -------------------------


@pytest.fixture(scope="module")
def cell():
    return types.SimpleNamespace(
        name="tiny_granite4h", chips=1,
        config=dict(_load(f"benchmark/configs/{CONFIG}.json"), **TINY),
        traffic=dict(_load("benchmark/traffic/train_packed_8k_v12544.json"),
                     **TINY_TRAFFIC),
        reference=manifest.load_module(
            f"benchmark/references/{CONFIG}.py", "ref_granite4_h_micro"))


@pytest.fixture(scope="module")
def tree(tmp_path_factory):
    """``benchmark_tiny_tree``'s copy with a tiny ``granitemoehybrid``
    configuration, traffic and cell added beside what is there, on every
    list that names the real cell."""
    dst = benchmark_tiny_tree.build(str(tmp_path_factory.mktemp("granite")))
    bench = os.path.join(dst, "benchmark")
    with open(os.path.join(dst, "BENCHMARK.json")) as fh:
        m = json.load(fh)
    with open(os.path.join(bench, "configs", "granite4h_tiny.json"),
              "w") as fh:
        json.dump(dict(_load(f"benchmark/configs/{CONFIG}.json"), **TINY,
                       name="granite4h_tiny"), fh, indent=1)
    shutil.copy(os.path.join(bench, "references", f"{CONFIG}.py"),
                os.path.join(bench, "references", "granite4h_tiny.py"))
    with open(os.path.join(bench, "traffic", "tiny_train_granite4h.json"),
              "w") as fh:
        json.dump(dict(_load("benchmark/traffic/train_packed_8k_v12544.json"),
                       **TINY_TRAFFIC), fh, indent=1)
    m["configs"].append({
        "name": "granite4h_tiny", "source": "CPU rehearsal",
        "file": "benchmark/configs/granite4h_tiny.json", "reduced": ["tiny"],
        "why": "CPU rehearsal"})
    m["workloads"].append({
        "name": "tiny_granite4h", "config": "granite4h_tiny",
        "traffic": "tiny_train_granite4h", "chips": 1,
        "why": "CPU rehearsal"})
    benchmark_tiny_tree.list_like(m, "tiny_granite4h", CELL)
    with open(os.path.join(dst, "BENCHMARK.json"), "w") as fh:
        json.dump(m, fh, indent=1)
    return dst


@pytest.mark.parametrize("trace", [0, 1])
def test_tiny_granite4h_cell_runs_and_is_correct(tree, trace):
    p = run_cell(tree, "tiny_granite4h", trace=trace)
    line = last_line(p)
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] >= 1
    wanted = {"train_tokens_per_s", "setup_s"} if not trace else \
        {"step_ms", "compile_s", "input_stall_ms", "dispatch_ms"}
    assert wanted <= set(line["metrics"])
    if trace:
        # Nothing of the device trace on a CPU; no reader raised.
        for name in sorted(THE_FIVE | {"attn_core_ms", "blocks_ms",
                                       "head_loss_ms", "hbm_peak_gb"}):
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
    tokens = train_steps.make_tokens(SEED, cell.traffic, 32, 96)
    batches = [tokens[i * 4:(i + 1) * 4] for i in range(3)]
    hp = dict(cell.config["optimizer"])
    follow = lambda **kw: cell.reference.train_steps(
        make(weights.seed_key(SEED)), batches, cell.config, hp, **kw)
    return follow, follow()


@pytest.mark.parametrize("control", [
    dict(carry_state=False), dict(decay=False), dict(drop_tap=0),
    dict(residual_multiplier=1.0), dict(attention_multiplier=0.125),
    dict(gate_after_norm=True), dict(row_share=0.5)],
    ids=lambda c: next(iter(c)))
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
    """``calibrate_granite4_h_micro.py`` end to end at the tiny size: it
    finds the cell, follows the program's own first steps with the reference
    (a sound reading, within the tiny cell's limits) and reads two of its
    controls, both far over them."""
    p = subprocess.run(
        [sys.executable, "benchmark/calibrate_granite4_h_micro.py",
         "--workload", "tiny_granite4h", "--seeds", "1", "--controls",
         "state_dropped,gate_after_norm"], cwd=tree,
        env=benchmark_tiny_tree.env(), capture_output=True, text=True,
        timeout=600)
    assert p.returncode == 0, p.stdout[-2000:] + p.stderr[-2000:]
    lines = [line for line in p.stdout.splitlines() if "READING" in line]
    assert "sound (within the file's limits)" in lines[0]
    readings = lines[1:]
    assert [line.split("control ")[1].split(":")[0] for line in readings] \
        == ["state_dropped", "gate_after_norm"]
    for line in readings:
        grad = float(line.split("train_grad_norm_gap ")[1].split(",")[0])
        assert grad > TINY["limits"]["train_grad_norm_gap"], line
