"""The training cell end to end at tiny sizes on the CPU, through the same
``run.py``, on one device and (as the four-chip cell will run) on four
virtual devices; and the proof that the tiny tree was built by adding
files."""

import filecmp
import json
import os
import subprocess
import sys

import pytest

from benchmark_tiny_tree import REPO, build, env

KEYS = {"correct", "attempted", "failed", "metrics", "device"}


def run_cell(tree, workload, n_devices=1, trace=0, seed=3_000_000_019,
             extra_env=None, seconds=2):
    e = env(n_devices)
    e.update(extra_env or {})
    p = subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload", workload,
         "--seed", str(seed), "--seconds", str(seconds), "--trace",
         str(trace)], cwd=tree, env=e, capture_output=True, text=True,
        timeout=600)
    return p


def last_line(p):
    assert p.returncode == 0, p.stdout[-3000:] + p.stderr[-2000:]
    return json.loads(p.stdout.strip().splitlines()[-1])


@pytest.fixture(scope="module")
def tree(tmp_path_factory):
    return build(str(tmp_path_factory.mktemp("bench_train")))


@pytest.mark.parametrize("workload,n_devices,trace", [
    ("tiny_train", 1, 0), ("tiny_train", 1, 1), ("tiny_train_dp4", 4, 0)])
def test_training_cell_runs_and_prints_the_contract_line(tree, workload,
                                                         n_devices, trace):
    p = run_cell(tree, workload, n_devices, trace)
    line = last_line(p)
    assert KEYS <= set(line) and set(line) - KEYS <= {
        "breakdown", "end_to_end_traced"}
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] >= 1
    assert line["device"]["count"] == n_devices
    assert set(line["device"]) >= {"platform", "kind", "count",
                                   "memory_peak_bytes"}
    wanted = {"train_tokens_per_s", "setup_s"} if not trace else \
        {"step_ms", "input_wait_ms", "compile_s"}
    assert wanted <= set(line["metrics"])
    for m in line["metrics"].values():
        assert set(m) == {"value", "unit"} and m["value"] > 0
    assert "compile requests inside the window: 0" in p.stdout
    assert "compare train_grad_norm_gap" in p.stdout


def test_more_visible_devices_than_chips_change_nothing(tree):
    line = last_line(run_cell(tree, "tiny_train", n_devices=4))
    assert line["device"]["count"] == 1 and line["correct"] is True


def test_fewer_devices_than_chips_fail_without_a_result(tree):
    p = run_cell(tree, "tiny_train_dp4", n_devices=2)
    assert p.returncode != 0
    assert "cannot measure" in p.stdout and "jax sees 2" in p.stdout
    assert not p.stdout.strip().splitlines()[-1].startswith("{")


def test_cpu_not_said_to_be_a_rehearsal_fails_without_a_result(tree):
    p = run_cell(tree, "tiny_train", extra_env={"BENCHMARK_REHEARSAL": ""})
    assert p.returncode != 0 and "cannot measure" in p.stdout
    assert not p.stdout.strip().splitlines()[-1].startswith("{")


def test_without_the_program_the_run_fails_and_says_where(tree):
    """A directory that holds only BENCHMARK.json and the benchmark's
    paths: the import of the system under test fails, with its phase."""
    p = run_cell(tree, "tiny_train", extra_env={"PYTHONPATH": ""})
    assert p.returncode != 0 and "FAILED in phase" in p.stdout
    assert "deeplearning_cfn_tpu" in p.stdout
    assert not p.stdout.strip().splitlines()[-1].startswith("{")


def test_a_large_seed_and_the_same_seed_give_the_same_inputs():
    import numpy as np

    from benchmark_tiny_tree import REPO
    sys.path.insert(0, REPO + "/benchmark")
    from harness.train_steps import make_tokens
    traffic = {"num_examples": 8, "repeat_min": 0.0, "repeat_max": 0.9}
    a = make_tokens(2 ** 31 + 12345, traffic, 32, 512)
    b = make_tokens(2 ** 31 + 12345, traffic, 32, 512)
    c = make_tokens(2 ** 31 + 12346, traffic, 32, 512)
    assert a.shape == (8, 33) and np.array_equal(a, b)
    assert not np.array_equal(a, c)
    assert len({tuple(r) for r in a}) == 8 and a.min() >= 4 and a.max() < 512


def test_the_tiny_tree_only_adds_files_and_manifest_entries(tree):
    """A later PR adds a configuration, a traffic mix, a cell and a per-layer
    metric the same way: no file that is there is edited."""
    cmp = filecmp.dircmp(os.path.join(REPO, "benchmark"),
                         os.path.join(tree, "benchmark"),
                         ignore=["__pycache__", ".jax_cache"])

    def walk(c):
        yield c
        for sub in c.subdirs.values():
            yield from walk(sub)

    added = []
    for c in walk(cmp):
        assert not c.diff_files and not c.left_only, (c.left, c.diff_files)
        added += c.right_only
    assert {"gpt2_tiny.json", "gpt2_tiny.py", "tiny_train.json",
            "tiny_train_dp4.json"} <= set(added)
    with open(os.path.join(REPO, "BENCHMARK.json")) as fh:
        before = json.load(fh)
    with open(os.path.join(tree, "BENCHMARK.json")) as fh:
        after = json.load(fh)
    for group in ("configs", "workloads"):
        assert after[group][:len(before[group])] == before[group]


def test_a_new_per_layer_metric_is_one_file_and_one_entry(tree):
    reader = os.path.join(tree, "benchmark", "layer_metrics",
                          "steps_in_window.py")
    with open(reader, "w") as fh:
        fh.write("def read(ctx):\n    return ctx['run'].get('steps')\n")
    path = os.path.join(tree, "BENCHMARK.json")
    with open(path) as fh:
        m = json.load(fh)
    m["per_layer"].append({
        "name": "steps_in_window", "unit": "steps", "better": "higher",
        "source": "program_counter", "layer": "train loop",
        "moves": "train_tokens_per_s", "workloads": ["tiny_train"]})
    with open(path, "w") as fh:
        json.dump(m, fh)
    line = last_line(run_cell(tree, "tiny_train", trace=1))
    assert line["metrics"]["steps_in_window"]["value"] >= 1
