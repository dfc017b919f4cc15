"""The five readers of the start path (``first_step_s``, ``step_trace_s``,
``step_lower_s``, ``state_init_s``, ``first_step_other_s``): what they read
from the program's registry, on the tiny cell end to end on the CPU, which
cells the committed manifest lists them for, and that they read nothing and
raise nothing from a program that has no such span or counter, as PR 37's
parent has not."""

import json
import os
import re
import subprocess
import sys

import pytest

from benchmark_tiny_tree import REPO, build, env

NAMES = ["first_step_s", "step_trace_s", "step_lower_s", "state_init_s",
         "first_step_other_s"]


def _load(relpath):
    with open(os.path.join(REPO, relpath)) as fh:
        return json.load(fh)


@pytest.mark.parametrize("name", NAMES)
def test_the_real_manifest_lists_every_training_cell_for_a_setup_metric(
        name):
    """The five time ``Trainer.fit``'s first step and the state's build, so
    they list every cell that reports ``train_tokens_per_s``, whatever those
    are, and a later serving cell owes them nothing: an explicit list, which
    a PR that adds a training cell extends by its name."""
    real = _load("BENCHMARK.json")
    metric = next(m for m in real["per_layer"] if m["name"] == name)
    assert (metric["unit"], metric["better"], metric["moves"]) == \
        ("s", "lower", "setup_s")
    rate = next(m for m in real["end_to_end"]
                if m["name"] == "train_tokens_per_s")
    training = rate.get("workloads", [w["name"] for w in real["workloads"]])
    assert sorted(metric["workloads"]) == sorted(training)
    assert metric["source"] == (
        "program_counter" if name.startswith("step_") else "program_span")
    assert metric["layer"] == (
        "entry points" if name.startswith("step_") else "train loop")


# -- the readers against a registry -------------------------------------------


def _reader(name):
    sys.path.insert(0, os.path.join(REPO, "benchmark"))
    try:
        from harness import manifest
    finally:
        sys.path.pop(0)
    return manifest.load_module(f"benchmark/layer_metrics/{name}.py",
                                f"setup_reader_{name}").read


@pytest.fixture()
def tracer():
    from deeplearning_cfn_tpu.obs import Tracer, configured

    t = Tracer()
    configured(t)
    yield t
    configured(None)


@pytest.mark.parametrize("name", NAMES)
def test_a_program_without_the_spans_and_counters_yields_none(name, tracer):
    """The parent's registry has ``span_dur_s`` and the loop's three spans
    and nothing of this PR: the reader returns None, says nothing, and does
    not raise."""
    for span_name in ("train.next_batch", "train.dispatch", "train.hooks"):
        tracer.record_span(span_name, 0.0, 0.01, step=0)
    said = []
    assert _reader(name)({"say": said.append}) is None
    assert said == []


def _feed(tracer, first_step_s=47.5):
    """A process's start as the program records it, then what the traced
    run adds after its window: a second ``fit``'s short first step, which
    the program keeps out of the gauge, and the step compiled again
    (``pallas_kernels``)."""
    registry = tracer.registry
    tracer.record_span("train.init_state", 0.0, 4.25)
    tracer.record_span("train.first_step", 5.0, first_step_s, step=0)
    first = registry.gauge("train.first_step_s")
    for part, seconds in (("whole", first_step_s), ("next_batch", 0.5),
                          ("dispatch", 40.0), ("trace", 12.0), ("lower", 8.0),
                          ("backend_compile", 5.0), ("cache_retrieval", 4.0),
                          ("cache_saved", 76.0)):
        first.set(seconds, part=part)
    tracer.record_span("train.first_step", 60.0, 0.6, step=3)
    for part, seconds in (("trace", 23.0), ("lower", 15.5),
                          ("backend_compile", 10.5)):
        registry.counter(f"jit.{part}_s").inc(seconds, fun="train_step")
        registry.counter(f"jit.{part}_count").inc(2, fun="train_step")


def test_the_readers_take_the_first_of_the_process_not_its_total(tracer):
    _feed(tracer)
    said = []
    got = {name: _reader(name)({"say": said.append}) for name in NAMES}
    assert got == {"first_step_s": 47.5, "step_trace_s": 12.0,
                   "step_lower_s": 8.0, "state_init_s": 4.25,
                   "first_step_other_s": pytest.approx(47.5 - 25.5)}
    text = "\n".join(said)
    assert "2 in this process, 47.500, 0.600 s" in text
    assert "12.000 s inside the first step; in the whole process so far " \
           "23.000 s in 2" in text
    # 40 s of dispatch less jax's 25: 15 s inside the call, 7 s of waiting.
    assert "15.000 s of the other lie inside the jit call and 7.000 s in " \
           "the wait" in text
    assert "the cache read for 4.000 s what had taken 80.000 s to compile" \
        in text


def test_other_is_left_out_with_a_reason_where_it_would_be_negative(tracer):
    _feed(tracer, first_step_s=20.0)
    tracer.registry.gauge("train.first_step_s").set(0.0,
                                                    part="cache_retrieval")
    said = []
    assert _reader("first_step_other_s")({"say": said.append}) is None
    assert "cache load 5.000 (the cache held nothing) + other" in said[0]
    assert said[-1] == ("first_step_other_s: the parts come to more than "
                        "the whole, left out")
    assert _reader("first_step_s")({"say": said.append}) == 20.0


# -- the tiny cell on the CPU -------------------------------------------------


@pytest.fixture(scope="module")
def traced_run(tmp_path_factory):
    """``tiny_train --trace 1`` from the tiny tree: ``build`` adds the tiny
    cell to the five's lists as to every list that names the cell it is
    `like`."""
    tree = build(str(tmp_path_factory.mktemp("bench_setup_spans")))
    p = subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload", "tiny_train",
         "--seed", "3700000019", "--seconds", "2", "--trace", "1"],
        cwd=tree, env=env(1), capture_output=True, text=True, timeout=600)
    assert p.returncode == 0, p.stdout[-3000:] + p.stderr[-2000:]
    return p.stdout, json.loads(p.stdout.strip().splitlines()[-1])


def test_the_tiny_cell_reports_the_five_each_above_zero(traced_run):
    _, line = traced_run
    assert line["correct"] is True
    for name in NAMES:
        assert line["metrics"][name]["unit"] == "s"
        assert line["metrics"][name]["value"] > 0


def test_the_parts_are_no_more_than_the_whole_and_the_whole_less_than_setup(
        traced_run):
    stdout, line = traced_run
    v = {name: line["metrics"][name]["value"] for name in NAMES}
    assert v["step_trace_s"] + v["step_lower_s"] + v["first_step_other_s"] \
        <= v["first_step_s"]
    setup_s = line["end_to_end_traced"]["setup_s"]
    assert v["first_step_s"] + v["state_init_s"] < setup_s
    # jax's own listener of the benchmark counted every compile of set-up;
    # the step's is one of them.
    assert v["first_step_s"] - v["step_trace_s"] - v["step_lower_s"] \
        - v["first_step_other_s"] < line["metrics"]["compile_s"]["value"] \
        + 1.0


def test_first_step_s_is_the_runners_first_step_seconds(traced_run):
    stdout, line = traced_run
    said = re.search(r"fit's first-step seconds \[([0-9.e+-]+)\]", stdout)
    assert float(said.group(1)) == pytest.approx(
        line["metrics"]["first_step_s"]["value"], rel=1e-6)
    # The window's own fit closed a second one; the reader said so.
    assert re.search(r"train\.first_step: 2 in this process", stdout)
    # Nothing of the start path is a profiler annotation: the window's
    # program spans are the three of the steady loop.
    assert "program spans in the trace: {'train.next_batch': 4, " \
           "'train.dispatch': 4, 'train.hooks': 4}" in stdout
    assert "compile requests inside the window: 0 (should be 0)" in stdout
