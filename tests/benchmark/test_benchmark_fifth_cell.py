"""A later PR's cell, rehearsed: the committed benchmark copied to a temporary
tree with a fifth cell added as the driver wants one added (files added, a
``configs`` entry last, a ``workloads`` entry last, its name last on every
list that names the cell it is like, a per-layer entry last) and
``tests/benchmark/`` copied beside it. The tests that read the committed
manifest are run there and pass: a test that holds an older cell or metric
to a place or a count fails here, in the PR that writes it, and not in the
next PR that brings a cell (PR 39 was refused for two such tests of PR 35's
and PR 37's).

Which tests those are goes by name: every test of a file with ``manifest``
in its name, and every test named ``test_the_tiny_tree…`` or with ``manifest``
in its name. The last test here holds the other files to that rule."""

import ast
import json
import os
import re
import shutil
import subprocess
import sys

import pytest

from benchmark_tiny_tree import REPO, env, list_like

SELECTED = "manifest or test_the_tiny_tree"
LIKE = {"cell": "laguna_xs2_train_4k", "config": "laguna_xs2",
        "traffic": "train_packed_4k"}
# PR 35's assertion, which no cell after Mellum2's could pass.
PIN = '''

def test_the_real_manifest_keeps_the_cell_last():
    real = _load("BENCHMARK.json")
    mine = next(w for w in real["workloads"] if w["name"] == CELL)
    assert mine == list(real["workloads"]).pop()
'''


def _copy_of_the_benchmark(dst):
    ignore = shutil.ignore_patterns("__pycache__", ".jax_cache")
    for path in ("benchmark", os.path.join("tests", "benchmark")):
        shutil.copytree(os.path.join(REPO, path), os.path.join(dst, path),
                        ignore=ignore)
    for name in ("BENCHMARK.json", "PERF.md"):
        shutil.copy(os.path.join(REPO, name), os.path.join(dst, name))


def _add_a_fifth_cell(tree, per_layer):
    """What a ``model_config`` PR does, and a ``perf_opt`` or ``tracing`` PR
    with its last step: files beside what is there, entries at the ends."""
    bench = os.path.join(tree, "benchmark")
    path = os.path.join(bench, "configs", LIKE["config"] + ".json")
    with open(path) as fh:
        config = dict(json.load(fh), name="fifth")
    with open(os.path.join(bench, "configs", "fifth.json"), "w") as fh:
        json.dump(config, fh, indent=1)
    shutil.copy(os.path.join(bench, "references", LIKE["config"] + ".py"),
                os.path.join(bench, "references", "fifth.py"))
    shutil.copy(os.path.join(bench, "traffic", LIKE["traffic"] + ".json"),
                os.path.join(bench, "traffic", "train_fifth.json"))
    with open(os.path.join(tree, "BENCHMARK.json")) as fh:
        manifest = json.load(fh)
    like = next(c for c in manifest["configs"] if c["name"] == LIKE["config"])
    manifest["configs"].append(dict(
        like, name="fifth", file="benchmark/configs/fifth.json"))
    manifest["workloads"].append({
        "name": "fifth_train", "config": "fifth", "traffic": "train_fifth",
        "chips": 1, "why": "a later PR's cell, rehearsed"})
    list_like(manifest, "fifth_train", LIKE["cell"])
    if per_layer:
        with open(os.path.join(bench, "layer_metrics", "fifth_ms.py"),
                  "w") as fh:
            fh.write("def read(ctx):\n    return None\n")
        manifest["per_layer"].append({
            "name": "fifth_ms", "unit": "ms", "better": "lower",
            "source": "device_trace", "layer": "model code",
            "moves": "train_tokens_per_s", "workloads": ["fifth_train"]})
    with open(os.path.join(tree, "BENCHMARK.json"), "w") as fh:
        json.dump(manifest, fh, indent=1)


def _run_the_selected_tests(tree):
    return subprocess.run(
        [sys.executable, "-m", "pytest", os.path.join("tests", "benchmark"),
         "-k", SELECTED, "-q", "-x", "--rootdir", tree,
         "-p", "no:cacheprovider", "-p", "no:randomly"],
        cwd=tree, env=env(1), capture_output=True, text=True, timeout=900)


@pytest.mark.parametrize("per_layer", [False, True],
                         ids=["a_cell", "a_cell_and_a_per_layer_metric"])
def test_a_fifth_cell_appended_last_fails_no_test_that_is_there(
        tmp_path, per_layer):
    tree = str(tmp_path)
    _copy_of_the_benchmark(tree)
    _add_a_fifth_cell(tree, per_layer)
    p = _run_the_selected_tests(tree)
    assert p.returncode == 0, p.stdout[-6000:] + p.stderr[-2000:]
    passed = int(re.search(r"(\d+) passed", p.stdout).group(1))
    # The manifest's own file alone has a case a cell, a configuration and a
    # metric: the run was of the copy, fifth cell and all.
    with open(os.path.join(tree, "BENCHMARK.json")) as fh:
        manifest = json.load(fh)
    assert "fifth_train" in [w["name"] for w in manifest["workloads"]]
    assert passed > 2 * len(manifest["workloads"]) + len(
        manifest["configs"]) + len(manifest["per_layer"])
    assert "skipped" not in p.stdout and "xfailed" not in p.stdout


def test_a_test_that_holds_a_cell_to_the_lists_end_fails_the_rehearsal(
        tmp_path):
    """The rehearsal sees what it is for: PR 35's pin, put back in the
    copy, fails there and nothing else does."""
    tree = str(tmp_path)
    _copy_of_the_benchmark(tree)
    _add_a_fifth_cell(tree, per_layer=False)
    with open(os.path.join(tree, "tests", "benchmark",
                           "test_benchmark_mellum2.py"), "a") as fh:
        fh.write(PIN)
    p = _run_the_selected_tests(tree)
    assert p.returncode == 1, p.stdout[-6000:] + p.stderr[-2000:]
    assert "1 failed" in p.stdout
    assert re.search(r"FAILED .*test_benchmark_mellum2\.py::"
                     r"test_the_real_manifest_keeps_the_cell_last", p.stdout)


READS_THE_COMMITTED_MANIFEST = re.compile(
    r'_load\(\s*"BENCHMARK\.json"\s*\)|REPO,\s*"BENCHMARK\.json"'
    r'|\b_manifest\(\)')


def test_every_test_that_reads_the_committed_manifest_is_in_the_rehearsal():
    """By the names ``-k`` selects: a test function that opens the
    checkout's ``BENCHMARK.json`` has ``manifest`` in its name or is named
    ``test_the_tiny_tree…``, and a file that opens it anywhere else, in a helper, a fixture or
    as it is imported, has ``manifest`` in the file's."""
    here = os.path.dirname(os.path.abspath(__file__))
    outside = []
    for name in sorted(os.listdir(here)):
        # Not this file, whose ``PIN`` is such a test, spelt out.
        if not re.fullmatch(r"test_.*\.py", name) or "manifest" in name \
                or name == os.path.basename(__file__):
            continue
        with open(os.path.join(here, name)) as fh:
            source = fh.read()
        for node in ast.parse(source).body:
            if READS_THE_COMMITTED_MANIFEST.search(
                    ast.get_source_segment(source, node)) and not (
                    isinstance(node, ast.FunctionDef) and re.match(
                        "test_the_tiny_tree|test_.*manifest", node.name)):
                outside.append(f"{name}:{node.lineno}")
    assert outside == []
