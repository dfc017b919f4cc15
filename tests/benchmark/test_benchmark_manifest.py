"""``BENCHMARK.json`` is well-formed by the rules of the benchmark's contract,
and every name in it leads to a file: nothing is registered in code."""

import json
import os
import re

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


def _manifest():
    with open(os.path.join(REPO, "BENCHMARK.json")) as fh:
        return json.load(fh)


M = _manifest()
CELLS = [w["name"] for w in M["workloads"]]
CONFIGS = [c["name"] for c in M["configs"]]
END_TO_END = [m["name"] for m in M["end_to_end"]]
PER_LAYER = [m["name"] for m in M["per_layer"]]


def _reports(metric, cell):
    return "workloads" not in metric or cell in metric["workloads"]


def test_top_level_keys_and_size():
    assert set(M) == {"command", "paths", "run_seconds", "configs",
                      "workloads", "end_to_end", "per_layer"}
    assert os.path.getsize(os.path.join(REPO, "BENCHMARK.json")) <= 65536
    assert isinstance(M["run_seconds"], int) and 1 <= M["run_seconds"] <= 51
    assert 1 <= len(M["command"]) <= 32
    assert M["paths"] == ["benchmark", "tests/benchmark"]
    assert M["command"][1].startswith("benchmark/")


def test_names_are_unique():
    for group in ("configs", "workloads"):
        names = [e["name"] for e in M[group]]
        assert len(names) == len(set(names)), group
    metrics = END_TO_END + PER_LAYER
    assert len(metrics) == len(set(metrics))
    pairs = [(w["config"], w["traffic"]) for w in M["workloads"]]
    assert len(pairs) == len(set(pairs))


def test_four_chip_cells_within_quota():
    four = [w for w in M["workloads"] if w["chips"] == 4]
    assert all(w["chips"] in (1, 4) for w in M["workloads"])
    assert len(four) <= max(1, len(M["workloads"]) // 4)


def test_setup_s_is_an_end_to_end_metric_of_every_cell():
    setup = next(m for m in M["end_to_end"] if m["name"] == "setup_s")
    assert "workloads" not in setup and setup["bound"] <= 0.1


@pytest.mark.parametrize("cell", CELLS)
def test_cell_is_well_formed_and_its_files_exist(cell):
    w = next(w for w in M["workloads"] if w["name"] == cell)
    assert set(w) == {"name", "config", "traffic", "chips", "why"}
    assert NAME.match(w["name"]) and NAME.match(w["traffic"])
    assert 1 <= len(w["why"]) <= 200 and "\n" not in w["why"]
    assert w["config"] in CONFIGS
    traffic = os.path.join(REPO, "benchmark", "traffic",
                           w["traffic"] + ".json")
    with open(traffic) as fh:
        kind = json.load(fh)["kind"]
    assert os.path.exists(os.path.join(REPO, "benchmark", "harness",
                                       kind + ".py"))


@pytest.mark.parametrize("cell", CELLS)
def test_cell_reports_setup_another_end_to_end_and_a_per_layer(cell):
    e2e = [m["name"] for m in M["end_to_end"] if _reports(m, cell)]
    assert "setup_s" in e2e and len(e2e) >= 2
    layer = [m for m in M["per_layer"]
             if (cell in m["workloads"] if "workloads" in m
                 else m["moves"] in e2e)]
    assert layer


@pytest.mark.parametrize("config", CONFIGS)
def test_config_has_a_file_a_reference_and_a_cell(config):
    c = next(c for c in M["configs"] if c["name"] == config)
    assert set(c) == {"name", "source", "file", "reduced", "why"}
    assert c["file"] == f"benchmark/configs/{config}.json"
    with open(os.path.join(REPO, c["file"])) as fh:
        body = json.load(fh)
    assert body["name"] == config
    assert sorted(body["reduced"]) == sorted(c["reduced"])
    assert len(c["reduced"]) <= 16
    widths = re.compile(r"(_dim|_rank)$|hidden|intermediate|d_model|d_ff|"
                        r"n_embd|n_inner|head")
    assert not [k for k in c["reduced"] if widths.search(k)]
    assert os.path.exists(os.path.join(REPO, "benchmark", "references",
                                       config + ".py"))
    assert any(w["config"] == config for w in M["workloads"])
    for key in ("assumed", "deployment", "limits", "precision"):
        assert key in body, key


def test_no_config_file_without_a_cell():
    on_disk = {f[:-5] for f in os.listdir(
        os.path.join(REPO, "benchmark", "configs")) if f.endswith(".json")}
    assert on_disk == {w["config"] for w in M["workloads"]}
    references = {f[:-3] for f in os.listdir(
        os.path.join(REPO, "benchmark", "references"))
        if f.endswith(".py") and f != "precision.py"}
    assert references == on_disk


@pytest.mark.parametrize("metric", END_TO_END)
def test_end_to_end_metric_is_well_formed(metric):
    m = next(m for m in M["end_to_end"] if m["name"] == metric)
    assert set(m) - {"workloads"} == {"name", "unit", "better", "bound",
                                      "source"}
    assert NAME.match(m["name"]) and UNIT.match(m["unit"])
    assert m["better"] in ("lower", "higher")
    assert m["source"] in ("host_clock", "device_trace")
    assert 0.01 <= m["bound"] <= 0.1
    for cell in m.get("workloads", []):
        assert cell in CELLS


@pytest.mark.parametrize("metric", PER_LAYER)
def test_per_layer_metric_is_well_formed_and_has_a_reader(metric):
    m = next(m for m in M["per_layer"] if m["name"] == metric)
    assert set(m) - {"workloads"} == {"name", "unit", "better", "source",
                                      "layer", "moves"}
    assert NAME.match(m["name"]) and UNIT.match(m["unit"])
    assert m["better"] in ("lower", "higher") and m["source"] in SOURCES
    assert 1 <= len(m["layer"]) <= 200
    assert m["moves"] in END_TO_END
    moved = next(e for e in M["end_to_end"] if e["name"] == m["moves"])
    # Every cell that reports this metric reports the one it moves.
    for cell in m.get("workloads", CELLS):
        assert cell in CELLS
        if "workloads" in m:
            assert _reports(moved, cell), (metric, cell)
    assert os.path.exists(os.path.join(REPO, "benchmark", "layer_metrics",
                                       metric + ".py"))
    if m["name"].endswith("_roofline") or "mfu" in m["name"]:
        assert m["unit"] == "%"


def test_no_per_layer_entry_waits_in_a_file():
    """Until PR 40 an entry that could not be appended waited in
    ``benchmark/per_layer_pending*.json``. Three of those files are kept,
    empty, only because ``docs/OBSERVABILITY.md`` names their paths and no
    ``benchmark`` PR may edit it: an entry goes in the manifest."""
    bench = os.path.join(REPO, "benchmark")
    for name in os.listdir(bench):
        if name.startswith("per_layer_pending"):
            with open(os.path.join(bench, name)) as fh:
                assert json.load(fh) == [], name


def test_one_layer_name_per_layer():
    layers = {m["layer"] for m in M["per_layer"]}
    with open(os.path.join(REPO, "PERF.md")) as fh:
        perf = fh.read()
    for layer in layers:
        assert layer in perf, f"PERF.md's list of layers lacks {layer!r}"


def test_files_under_paths_have_plain_names():
    plain = re.compile(r"^[A-Za-z0-9_.\-/]+$")
    for root in M["paths"]:
        for d, dirs, files in os.walk(os.path.join(REPO, root)):
            dirs[:] = [x for x in dirs if x != "__pycache__"]
            for f in files:
                rel = os.path.relpath(os.path.join(d, f), REPO)
                assert plain.match(rel), rel
