"""obs/ subsystem: metrics registry, span tracer, sinks, run reports.

Parity tests pin the migration contracts: ServeMetrics keeps its exact
attribute surface and snapshot keys/values after moving onto the registry,
and percentile() keeps its interpolation semantics. The report tests run
against COMMITTED fixture logs generated from real train/serve/launcher
runs (tests/fixtures/obs/), so `obs summarize` is tested on the actual
byte shapes the runners emit.
"""

import json
import os

import pytest

from deeplearning_cfn_tpu.metrics.jsonl import MetricsWriter
from deeplearning_cfn_tpu.obs import (
    AlertingWriter,
    JsonlFollower,
    JsonlSink,
    MemorySink,
    MetricsRegistry,
    SloEngine,
    TailState,
    Tracer,
    build_trace,
    check_run,
    configured,
    diff_runs,
    exponential_buckets,
    export_trace,
    get_tracer,
    load_rules,
    obs_enabled,
    percentile,
    render_diff,
    render_prometheus,
    render_report,
    set_enabled,
    span,
    summarize,
    tail,
    validate_trace,
    write_prometheus,
)
from deeplearning_cfn_tpu.obs.diff import direction
from deeplearning_cfn_tpu.obs.slo import Rule, RuleError
from deeplearning_cfn_tpu.serve.metrics import ServeMetrics

FIXTURES = os.path.join(os.path.dirname(__file__), "fixtures", "obs")


# -- percentile edge cases (satellite: never raise, never NaN) ---------------


def test_percentile_empty_returns_none():
    assert percentile([], 50) is None
    assert percentile([], 95) is None


def test_percentile_single_sample_is_that_sample():
    for q in (0, 50, 95, 100):
        assert percentile([0.25], q) == 0.25


def test_percentile_all_ties_no_nan():
    p = percentile([2.0] * 7, 95)
    assert p == 2.0
    assert p == p  # not NaN


def test_percentile_interpolates():
    # rank = (n-1) * q/100; for [1..5], p50 = 3.0, p95 = 4.8
    xs = [5.0, 1.0, 4.0, 2.0, 3.0]
    assert percentile(xs, 50) == 3.0
    assert percentile(xs, 95) == pytest.approx(4.8)


def test_percentile_does_not_mutate_input():
    xs = [3.0, 1.0, 2.0]
    percentile(xs, 50)
    assert xs == [3.0, 1.0, 2.0]


# -- registry + instruments --------------------------------------------------


def test_counter_inc_and_monotonicity():
    reg = MetricsRegistry()
    c = reg.counter("reqs", "requests")
    assert c.value() == 0
    c.inc()
    c.inc(3)
    assert c.value() == 4
    with pytest.raises(ValueError):
        c.inc(-1)


def test_counter_labels_are_independent_series():
    reg = MetricsRegistry()
    c = reg.counter("reqs", "requests")
    c.inc(2, state="ok")
    c.inc(5, state="err")
    assert c.value(state="ok") == 2
    assert c.value(state="err") == 5
    assert c.labels(state="ok").value() == 2
    assert c.series()[(("state", "ok"),)] == 2


def test_gauge_set_and_inc():
    reg = MetricsRegistry()
    g = reg.gauge("depth", "queue depth")
    assert g.value() is None
    g.set(7)
    assert g.value() == 7
    g.inc(-2)
    assert g.value() == 5


def test_registry_get_or_create_returns_same_instrument():
    reg = MetricsRegistry()
    assert reg.counter("x", "d") is reg.counter("x", "d")


def test_registry_kind_mismatch_raises_typeerror():
    reg = MetricsRegistry()
    reg.counter("x", "d")
    with pytest.raises(TypeError):
        reg.gauge("x", "d")
    with pytest.raises(TypeError):
        reg.histogram("x", "d")


def test_exponential_buckets_shape():
    assert exponential_buckets(start=1e-3, factor=2.0, count=4) == \
        (1e-3, 2e-3, 4e-3, 8e-3)
    with pytest.raises(ValueError):
        exponential_buckets(start=0)


def test_histogram_buckets_and_exact_percentiles():
    reg = MetricsRegistry()
    h = reg.histogram("lat", "latency", buckets=(0.1, 1.0))
    for v in (0.05, 0.5, 5.0):
        h.observe(v)
    assert h.count() == 3
    assert h.sum() == pytest.approx(5.55)
    # exact percentiles come from retained samples, not bucket edges
    assert h.percentile(50) == 0.5
    assert h.samples() == [0.05, 0.5, 5.0]
    ((_, series),) = h.series().items()
    assert series.bucket_counts == [1, 1, 1]  # per-bucket incl +Inf


def test_histogram_empty_percentile_is_none():
    reg = MetricsRegistry()
    h = reg.histogram("lat", "latency")
    assert h.percentile(50) is None
    assert h.mean() is None
    assert h.count() == 0 and h.sum() == 0.0 and h.samples() == []


def test_histogram_keep_samples_false_drops_raw_series():
    reg = MetricsRegistry()
    h = reg.histogram("hot", "hot path", keep_samples=False)
    h.observe(0.2)
    assert h.count() == 1
    assert h.samples() == []
    assert h.percentile(50) is None  # no raw series -> no exact percentile


def test_histogram_labelled_series():
    reg = MetricsRegistry()
    h = reg.histogram("span_dur_s", "d")
    h.observe(0.1, name="a")
    h.observe(0.2, name="a")
    h.observe(9.0, name="b")
    assert h.count(name="a") == 2
    assert h.percentile(50, name="a") == pytest.approx(0.15)
    assert h.count(name="b") == 1


def test_registry_snapshot_is_json_able():
    reg = MetricsRegistry()
    reg.counter("c", "c").inc(2, state="ok")
    reg.gauge("g", "g").set(1.5)
    reg.histogram("h", "h").observe(0.2)
    snap = json.loads(json.dumps(reg.snapshot()))
    assert snap["c"]["kind"] == "counter"
    assert snap["c"]["series"]["state=ok"] == 2
    assert snap["h"]["series"][""]["count"] == 1
    assert snap["h"]["series"][""]["p50"] == 0.2


# -- span tracer -------------------------------------------------------------


@pytest.fixture()
def fresh_tracer():
    t = Tracer()
    configured(t)
    try:
        yield t
    finally:
        configured(None)
        set_enabled(None)


def test_span_ids_deterministic_from_one(fresh_tracer):
    sink = MemorySink()
    fresh_tracer.add_sink(sink)
    with span("a"):
        pass
    with span("b"):
        pass
    assert [r["span_id"] for r in sink.records] == [1, 2]
    assert all(r["parent_id"] is None for r in sink.records)


def test_span_nesting_sets_parent_id(fresh_tracer):
    sink = MemorySink()
    fresh_tracer.add_sink(sink)
    with span("outer"):
        with span("inner", step=3):
            pass
    inner, outer = sink.records  # inner closes (and is recorded) first
    assert inner["span"] == "inner"
    assert inner["parent_id"] == outer["span_id"]
    assert inner["step"] == 3
    assert outer["parent_id"] is None
    assert inner["dur_s"] <= outer["dur_s"]
    assert inner["t0_s"] >= outer["t0_s"]


def test_span_records_failure_and_reraises(fresh_tracer):
    sink = MemorySink()
    fresh_tracer.add_sink(sink)
    with pytest.raises(RuntimeError):
        with span("boom"):
            raise RuntimeError("x")
    (rec,) = sink.records
    assert rec["ok"] is False


def test_span_annotate_adds_attrs(fresh_tracer):
    sink = MemorySink()
    fresh_tracer.add_sink(sink)
    with span("ckpt.save", step=4) as sp:
        sp.annotate(retries=2)
    (rec,) = sink.records
    assert rec["step"] == 4
    assert rec["retries"] == 2


def test_spans_feed_duration_histogram(fresh_tracer):
    with span("work"):
        pass
    h = fresh_tracer.registry.histogram("span_dur_s", "span durations by name")
    assert h.count(name="work") == 1


def test_memory_sink_by_span(fresh_tracer):
    sink = MemorySink()
    fresh_tracer.add_sink(sink)
    with span("a"):
        pass
    with span("b"):
        pass
    assert [r["span"] for r in sink.by_span("a")] == ["a"]


def test_env_gate_disables_spans(fresh_tracer, monkeypatch):
    sink = MemorySink()
    fresh_tracer.add_sink(sink)
    monkeypatch.setenv("DLCFN_OBS_OFF", "1")
    assert not obs_enabled()
    with span("a") as sp:
        sp.annotate(ignored=True)  # null span: no-op, no raise
    assert sink.records == []
    monkeypatch.delenv("DLCFN_OBS_OFF")
    assert obs_enabled()
    with span("a"):
        pass
    assert len(sink.records) == 1


def test_set_enabled_overrides_env(fresh_tracer, monkeypatch):
    sink = MemorySink()
    fresh_tracer.add_sink(sink)
    monkeypatch.setenv("DLCFN_OBS_OFF", "1")
    set_enabled(True)  # programmatic override beats the env var
    with span("a"):
        pass
    assert len(sink.records) == 1
    set_enabled(False)
    with span("b"):
        pass
    assert len(sink.records) == 1


def _profiled_events(tmp_path, body):
    """Run ``body`` under a jax.profiler session and return the host-plane
    events of the written ``.xplane.pb`` as ``{name: [(start, end)]}``."""
    import glob

    import jax
    from jax.profiler import ProfileData

    jax.profiler.start_trace(str(tmp_path))
    try:
        body()
    finally:
        jax.profiler.stop_trace()
    (path,) = glob.glob(os.path.join(
        str(tmp_path), "plugins", "profile", "*", "*.xplane.pb"))
    events = {}
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/device:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                events.setdefault(ev.name, []).append(
                    (ev.start_ns, ev.start_ns + ev.duration_ns))
    return events


def test_live_span_is_an_event_of_the_profiler_trace(fresh_tracer, tmp_path):
    sink = MemorySink()
    fresh_tracer.add_sink(sink)

    def body():
        with span("obs_test.outer", step=1):
            with span("obs_test.inner"):
                pass
            with span("obs_test.inner"):
                pass

    events = _profiled_events(tmp_path, body)
    (outer,) = events["obs_test.outer"]
    inners = sorted(events["obs_test.inner"])
    assert len(inners) == 2
    # Nested spans overlap as nested, siblings do not overlap at all.
    assert all(outer[0] <= s and e <= outer[1] for s, e in inners)
    assert inners[0][1] <= inners[1][0]
    # The JSONL records are what they were: one per span, on the tracer's
    # own clock.
    assert [r["span"] for r in sink.records] == [
        "obs_test.inner", "obs_test.inner", "obs_test.outer"]


@pytest.mark.parametrize("how", ["env", "set_enabled"])
def test_span_switched_off_writes_no_profiler_event(fresh_tracer, tmp_path,
                                                    monkeypatch, how):
    if how == "env":
        monkeypatch.setenv("DLCFN_OBS_OFF", "1")
    else:
        set_enabled(False)

    def body():
        with span("obs_test.off"):
            pass

    assert "obs_test.off" not in _profiled_events(tmp_path, body)


def test_span_outside_a_profiler_session_still_records(fresh_tracer):
    sink = MemorySink()
    fresh_tracer.add_sink(sink)
    with pytest.raises(RuntimeError):
        with span("obs_test.alone"):
            raise RuntimeError("x")
    (rec,) = sink.records
    assert rec["span"] == "obs_test.alone" and rec["ok"] is False


def test_obs_starts_without_jax_and_spans_work_there():
    """``obs tail/summarize/diff/check/export`` run in processes that never
    load jax: no module of ``obs/`` imports it, and a span there is a span
    without an annotation."""
    import subprocess
    import sys

    code = (
        "import sys\n"
        "import deeplearning_cfn_tpu.obs as obs\n"
        "from deeplearning_cfn_tpu.obs import trace\n"
        "sink = obs.MemorySink()\n"
        "obs.get_tracer().add_sink(sink)\n"
        "with obs.span('a'):\n"
        "    pass\n"
        "assert [r['span'] for r in sink.records] == ['a']\n"
        "assert trace._profiler_annotation('a') is None\n"
        "assert 'jax' not in sys.modules, 'obs imported jax'\n")
    p = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, timeout=120,
                       cwd=os.path.dirname(os.path.dirname(__file__)))
    assert p.returncode == 0, p.stderr[-2000:]


def test_get_tracer_returns_configured_default():
    t = Tracer()
    configured(t)
    try:
        assert get_tracer() is t
    finally:
        configured(None)
    assert get_tracer() is not t


def test_remove_sink_stops_delivery(fresh_tracer):
    sink = MemorySink()
    fresh_tracer.add_sink(sink)
    fresh_tracer.remove_sink(sink)
    fresh_tracer.remove_sink(sink)  # idempotent
    with span("a"):
        pass
    assert sink.records == []


# -- sinks -------------------------------------------------------------------


def test_jsonl_sink_writes_span_records(fresh_tracer, tmp_path):
    path = str(tmp_path / "m.jsonl")
    sink = JsonlSink(MetricsWriter(path, also_stdout=False))
    fresh_tracer.add_sink(sink)
    with span("a", step=1):
        pass
    sink.close()
    (line,) = open(path).read().splitlines()
    rec = json.loads(line)
    assert rec["span"] == "a" and rec["span_id"] == 1 and "ts" in rec


def test_render_prometheus_text_format():
    reg = MetricsRegistry()
    reg.counter("reqs_total", "requests").inc(3, state="ok")
    reg.gauge("depth", "queue depth").set(2)
    h = reg.histogram("lat_s", "latency", buckets=(0.1, 1.0))
    h.observe(0.05)
    h.observe(0.5)
    text = render_prometheus(reg)
    assert "# TYPE reqs_total counter" in text
    assert 'reqs_total{state="ok"} 3' in text
    assert "# TYPE depth gauge" in text
    assert "depth 2" in text
    assert 'lat_s_bucket{le="0.1"} 1' in text
    assert 'lat_s_bucket{le="1"} 2' in text
    assert 'lat_s_bucket{le="+Inf"} 2' in text
    assert "lat_s_count 2" in text
    assert "lat_s_sum 0.55" in text


def test_render_prometheus_escapes_label_values():
    reg = MetricsRegistry()
    reg.counter("c", "d").inc(1, msg='a"b\nc\\d')
    text = render_prometheus(reg)
    assert 'msg="a\\"b\\nc\\\\d"' in text


def test_write_prometheus_atomic(tmp_path):
    reg = MetricsRegistry()
    reg.counter("c", "d").inc()
    path = str(tmp_path / "metrics.prom")
    text = write_prometheus(reg, path)
    assert open(path).read() == text
    assert os.listdir(str(tmp_path)) == ["metrics.prom"]  # no tmp leftover


# -- ServeMetrics parity after the registry migration ------------------------


def _drive(m: ServeMetrics):
    m.record_submit()
    m.record_submit()
    m.record_admit(queue_wait_s=0.5)
    m.record_admit(queue_wait_s=1.5)
    m.record_first_token(0.2)
    m.record_finish("done", 2.0)
    m.record_step(active_rows=2, queue_depth=3, new_tokens=5,
                  step_time_s=0.01)
    m.record_step(active_rows=1, queue_depth=3, new_tokens=3,
                  step_time_s=0.03)
    m.record_reject(retry_after_s=0.25)


def test_serve_metrics_attribute_surface_parity():
    m = ServeMetrics(capacity=4, clock=lambda: 0.0)
    _drive(m)
    # the exact pre-migration attribute surface, live values
    assert m.submitted == 2 and isinstance(m.submitted, int)
    assert m.admitted == 2
    assert m.completed == 1
    assert m.rejected == 1
    assert m.cancelled == 0 and m.expired == 0
    assert m.tokens_generated == 8
    assert m.steps == 2 and m.windows == 2
    assert m.queue_wait_s == [0.5, 1.5]
    assert m.ttft_s == [0.2]
    assert m.latency_s == [2.0]
    assert m.step_latency_s == [0.01, 0.03]
    assert m.busy_time_s == pytest.approx(0.04)
    assert m.last_queue_depth == 3
    assert m.last_retry_after_s == 0.25
    assert m.mean_slot_occupancy == pytest.approx(0.375)
    assert m.mean_steps_per_window == 1.0
    assert m.tokens_per_sec == pytest.approx(8 / 0.04)
    assert m.ckpt_load_retries == 0


def test_serve_metrics_snapshot_keys_and_values_parity():
    m = ServeMetrics(capacity=4, clock=lambda: 0.0)
    _drive(m)
    snap = m.snapshot()
    # key set is the pre-migration JSONL contract
    assert set(snap) == {
        "serve_submitted", "serve_rejected", "serve_admitted",
        "serve_completed", "serve_cancelled", "serve_expired",
        "serve_steps", "serve_decode_windows", "serve_steps_per_window",
        "serve_queue_depth", "serve_slot_capacity", "serve_slot_occupancy",
        "serve_tokens_generated", "serve_tokens_per_sec",
        "serve_ckpt_load_retries", "serve_retry_after_hint_s",
        "serve_queue_wait_p50_s", "serve_queue_wait_p95_s",
        "serve_ttft_p50_s", "serve_ttft_p95_s",
        "serve_latency_p50_s", "serve_latency_p95_s",
        "serve_step_latency_p50_s", "serve_step_latency_p95_s",
        "serve_uptime_s",
    }
    # counters serialize as ints (1 not 1.0) — the byte-compat contract
    for k in ("serve_submitted", "serve_admitted", "serve_completed",
              "serve_rejected", "serve_tokens_generated", "serve_steps",
              "serve_decode_windows", "serve_queue_depth",
              "serve_ckpt_load_retries"):
        assert isinstance(snap[k], int), k
    # percentiles are the exact list-based values, not bucket estimates
    assert snap["serve_queue_wait_p50_s"] == percentile([0.5, 1.5], 50)
    assert snap["serve_queue_wait_p95_s"] == percentile([0.5, 1.5], 95)
    assert snap["serve_step_latency_p50_s"] == percentile([0.01, 0.03], 50)
    assert snap["serve_ttft_p50_s"] == 0.2
    assert snap["serve_latency_p95_s"] == 2.0


def test_serve_metrics_empty_percentiles_are_none():
    snap = ServeMetrics(capacity=2).snapshot()
    assert snap["serve_queue_wait_p50_s"] is None
    assert snap["serve_ttft_p95_s"] is None
    assert snap["serve_tokens_per_sec"] is None


def test_serve_metrics_ckpt_load_retries_settable():
    m = ServeMetrics(capacity=2)
    m.ckpt_load_retries = 3  # serve/loader.py assigns this directly
    assert m.ckpt_load_retries == 3
    assert m.snapshot()["serve_ckpt_load_retries"] == 3


def test_serve_metrics_registry_is_queryable():
    m = ServeMetrics(capacity=2)
    _drive(m)
    c = m.registry.counter("serve_requests_total",
                           "request lifecycle events by state")
    assert c.value(state="submitted") == 2
    assert c.value(state="admitted") == 2


def test_serve_metrics_instances_do_not_share_state():
    a, b = ServeMetrics(capacity=2), ServeMetrics(capacity=2)
    a.record_submit()
    assert a.submitted == 1 and b.submitted == 0


# -- StepTimer on the registry ----------------------------------------------


def _fake_clock(monkeypatch, ticks):
    from deeplearning_cfn_tpu.runtime import profiling

    it = iter(ticks)
    monkeypatch.setattr(profiling.time, "perf_counter", lambda: next(it))


def test_step_timer_summary_has_percentiles(monkeypatch):
    from deeplearning_cfn_tpu.runtime.profiling import StepTimer

    _fake_clock(monkeypatch, [0.0, 1.0, 1.0, 2.0, 2.0, 3.5, 3.5, 4.0])
    t = StepTimer(warmup=1)
    for _ in range(4):
        t.start()
        t.stop()
    s = t.summary()
    assert t.steps == 3
    assert s["steps"] == 3
    assert s["mean_step_s"] == pytest.approx(1.0)
    assert s["p50_step_s"] == 1.0
    assert s["p95_step_s"] == pytest.approx(1.45)
    assert s["min_step_s"] == 0.5 and s["max_step_s"] == 1.5


def test_step_timer_feeds_registry_histogram(monkeypatch):
    from deeplearning_cfn_tpu.runtime.profiling import StepTimer

    _fake_clock(monkeypatch, [0.0, 1.0])
    reg = MetricsRegistry()
    t = StepTimer(warmup=0, registry=reg)
    t.start()
    t.stop()
    h = reg.histogram("step_time_s", "synced per-step wall time")
    assert h.count() == 1 and h.samples() == [1.0]


def test_step_timer_empty_summary():
    from deeplearning_cfn_tpu.runtime.profiling import StepTimer

    assert StepTimer().summary() == {"steps": 0}


# -- trace_steps hardening ---------------------------------------------------


def test_trace_steps_body_error_not_masked_by_stop(monkeypatch, tmp_path):
    from deeplearning_cfn_tpu.runtime import profiling

    monkeypatch.setattr(profiling.jax.profiler, "start_trace",
                        lambda d: None)

    def bad_stop():
        raise OSError("flush failed")

    monkeypatch.setattr(profiling.jax.profiler, "stop_trace", bad_stop)
    # body error wins; stop_trace's secondary failure is swallowed
    with pytest.raises(ValueError, match="body"):
        with profiling.trace_steps(str(tmp_path)):
            raise ValueError("body")
    # body succeeded -> stop_trace failure must surface
    with pytest.raises(OSError, match="flush"):
        with profiling.trace_steps(str(tmp_path)):
            pass


# -- lazy MetricsWriter (satellite: no jax at construction) ------------------


def test_metrics_writer_construction_is_side_effect_free(tmp_path):
    path = str(tmp_path / "sub" / "m.jsonl")
    w = MetricsWriter(path, also_stdout=False)
    # no file, no directory until the first write
    assert not os.path.exists(os.path.dirname(path))
    w.write({"a": 1})
    w.close()
    assert json.loads(open(path).read())["a"] == 1


def test_metrics_writer_all_processes_never_asks_jax(tmp_path):
    w = MetricsWriter(str(tmp_path / "m.jsonl"), also_stdout=False,
                      all_processes=True)
    assert w.enabled  # resolved without touching jax.process_index()


# -- run reports over committed fixture logs ---------------------------------


def test_summarize_train_fixture_dir():
    s = summarize(os.path.join(FIXTURES, "train"))
    assert s["source"]["files"] == 2
    assert s["source"]["records"] == 25
    assert s["source"]["skipped_lines"] == 0
    tr = s["train"]
    assert tr["last_step"] == 6
    assert 0.2 < tr["step_time_s"]["p50"] < 0.31
    assert tr["step_time_s"]["p95"] >= tr["step_time_s"]["p50"]
    assert tr["examples_per_sec"]["last"] == pytest.approx(115.15, abs=0.01)
    assert tr["examples_per_sec"]["peak"] == pytest.approx(118.36, abs=0.01)
    assert tr["loss"]["first"] == pytest.approx(2.3026, abs=1e-3)
    assert tr["compile_s"] == pytest.approx(5.258, abs=1e-2)
    assert tr["ckpt_store_retries"] == 0
    assert tr["eval"]["final_eval_accuracy"] == 0.125
    sp = s["spans"]
    assert sp["ckpt.save"]["count"] == 4  # steps 2,4,6 + final forced save
    assert "failed" not in sp["ckpt.save"]  # no failures recorded
    assert sp["train.dispatch"]["count"] == 6
    assert sp["train.realize"]["count"] == 6
    la = s["launch"]
    assert la["attempts"] == 2
    assert la["outcomes"] == ["crash", "ok"]
    assert la["success"] is True and la["restarts"] == 1


def test_summarize_serve_fixture_file():
    s = summarize(os.path.join(FIXTURES, "serve", "metrics.jsonl"))
    assert s["source"]["files"] == 1
    sv = s["serve"]
    assert sv["submitted"] == 4 and sv["admitted"] == 4
    assert sv["completed"] == 4 and sv["rejected"] == 0
    assert sv["tokens_generated"] == 16
    assert sv["tokens_per_sec"] > 0
    assert sv["queue_wait_s"]["p50"] > 0
    assert sv["ttft_s"]["p95"] >= sv["ttft_s"]["p50"]
    assert s["spans"]["serve.decode"]["count"] == 4
    assert s["spans"]["serve.admit"]["count"] == 4
    assert "train" not in s


def test_render_report_is_human_text():
    s = summarize(os.path.join(FIXTURES, "train"))
    text = render_report(s)
    assert "run report:" in text
    assert "last step" in text
    assert "launch:" in text and "crash, ok" in text


def test_summarize_skips_malformed_lines(tmp_path):
    p = tmp_path / "m.jsonl"
    p.write_text('{"step": 1, "loss": 2.0}\nnot json\n{"step": 2}\n')
    s = summarize(str(p))
    assert s["source"]["records"] == 2
    assert s["source"]["skipped_lines"] == 1
    assert s["train"]["last_step"] == 2


def test_summarize_empty_input(tmp_path):
    p = tmp_path / "m.jsonl"
    p.write_text("")
    s = summarize(str(p))
    assert s["source"]["records"] == 0
    assert "no train" in render_report(s)  # renders, no raise


# -- where the start of a run went (PR 37) -----------------------------------

_START_RECORDS = [
    {"span": "train.init_state", "span_id": 1, "parent_id": None,
     "t0_s": 0.5, "dur_s": 4.25, "ok": True, "params": 1784000000,
     "bytes": 28544000000},
    {"span": "train.next_batch", "span_id": 2, "parent_id": None,
     "t0_s": 5.0, "dur_s": 0.5, "ok": True, "step": 0},
    {"span": "train.dispatch", "span_id": 3, "parent_id": None,
     "t0_s": 5.5, "dur_s": 40.0, "ok": True, "step": 0},
    {"span": "train.first_step", "span_id": 4, "parent_id": None,
     "t0_s": 5.0, "dur_s": 47.5, "ok": True, "step": 0, "trace_s": 12.0,
     "lower_s": 8.0, "backend_compile_s": 5.0, "next_batch_s": 0.5,
     "dispatch_s": 40.0, "cache_requests": 1, "cache_hits": 1,
     "cache_misses": 0, "cache_retrieval_s": 4.0, "cache_saved_s": 76.0},
    {"step": 1, "loss": 2.0, "compile_s": 47.5},
    {"span": "train.next_batch", "span_id": 5, "parent_id": None,
     "t0_s": 53.0, "dur_s": 0.001, "ok": True, "step": 1},
    {"span": "train.retrace", "span_id": 7, "parent_id": 6, "t0_s": 60.0,
     "dur_s": 30.0, "ok": True, "step": 6, "jit_s": 29.0},
    {"step": 7, "loss": 1.5, "retraces": 1},
    {"span": "train.first_step", "span_id": 9, "parent_id": None,
     "t0_s": 95.0, "dur_s": 0.6, "ok": True, "step": 7, "trace_s": 0.0,
     "lower_s": 0.0, "backend_compile_s": 0.0, "next_batch_s": 0.001,
     "dispatch_s": 0.002, "cache_requests": 0, "cache_hits": 0,
     "cache_misses": 0, "cache_retrieval_s": 0.0, "cache_saved_s": 0.0},
]


def _jsonl(tmp_path, records):
    p = tmp_path / "m.jsonl"
    p.write_text("".join(json.dumps(r) + "\n" for r in records))
    return str(p)


def test_summarize_splits_the_first_step_and_counts_retraces(tmp_path):
    start = summarize(_jsonl(tmp_path, _START_RECORDS))["train"]["start"]
    assert start["first_step_s"] == 47.5  # the run's first, not its last
    assert (start["trace_s"], start["lower_s"],
            start["backend_compile_s"]) == (12.0, 8.0, 5.0)
    assert start["next_batch_s"] == 0.5  # the batch of the span's own step
    assert start["other_s"] == pytest.approx(47.5 - 25.5)
    assert (start["cache_hits"], start["cache_misses"],
            start["cache_requests"]) == (1, 0, 1)
    assert (start["cache_retrieval_s"], start["cache_saved_s"]) == (4.0, 76.0)
    assert start["init_state_s"] == 4.25
    assert start["params"] == 1784000000
    assert start["retraces"] == 1 and start["retrace_steps"] == [6]


_HIT = {"cache_hits": 1, "cache_misses": 0, "cache_retrieval_s": 4.0,
        "cache_saved_s": 76.0}
_STORED = {"cache_hits": 0, "cache_misses": 1, "cache_retrieval_s": 0.0,
           "cache_saved_s": 0.0}


@pytest.mark.parametrize("cache,label,said", [
    (_HIT, "cache load", "1 hit(s), 0 stored of 1 request(s); read in 4s, "
                         "saved 76s"),
    (_STORED, "compile", "0 hit(s), 1 stored of 1 request(s); read in 0s, "
                         "saved 0s"),
])
def test_render_report_shows_the_start_under_first_step(tmp_path, cache,
                                                        label, said):
    records = [dict(r, **cache) if r.get("span") ==
               "train.first_step" and r["step"] == 0 else r
               for r in _START_RECORDS]
    lines = render_report(summarize(_jsonl(tmp_path, records))).splitlines()
    at = lines.index("  first step          47.5s")
    assert lines[at + 1:at + 8] == [
        "    trace / lower     12s / 8s",
        f"    {label:<17} 5s  (cache: {said})",
        "    first batch       0.5s",
        "    other             22s",
        "  state build         4.25s  (1784000000 params, 28544000000 "
        "bytes)",
        "  retraces            1  (at step 6)",
        "  ckpt store retries  -",
    ]


def test_a_run_without_the_span_keeps_the_one_line_under_its_new_label():
    """The committed fixture predates ``train.first_step``: its
    ``compile_s`` is shown as what it always was, the first step's
    seconds, and no split is made up."""
    s = summarize(os.path.join(FIXTURES, "train"))
    assert s["train"]["start"] is None
    lines = render_report(s).splitlines()
    at = next(i for i, l in enumerate(lines)
              if l.startswith("  first step "))
    assert lines[at].split() == ["first", "step", "5.258s"]
    assert lines[at + 1].startswith("  ckpt store retries")
    assert not any(l.startswith("  compile ") for l in lines)


# -- CLI verb ----------------------------------------------------------------


def test_cli_obs_summarize(capsys):
    from deeplearning_cfn_tpu.cli.main import main

    rc = main(["obs", "summarize", os.path.join(FIXTURES, "train")])
    assert rc == 0
    out = capsys.readouterr().out
    assert "run report:" in out and "last step" in out


def test_cli_obs_summarize_json(capsys):
    from deeplearning_cfn_tpu.cli.main import main

    rc = main(["obs", "summarize", "--json",
               os.path.join(FIXTURES, "serve", "metrics.jsonl")])
    assert rc == 0
    s = json.loads(capsys.readouterr().out)
    assert s["serve"]["completed"] == 4


def test_cli_obs_summarize_missing_path(capsys):
    from deeplearning_cfn_tpu.cli.main import main

    assert main(["obs", "summarize", "/nonexistent/m.jsonl"]) == 1


# -- trace export (tentpole: spans -> Perfetto trace events) -----------------


def test_build_trace_round_trip_nesting(fresh_tracer):
    sink = MemorySink()
    fresh_tracer.add_sink(sink)
    with span("train.step", step=1):
        with span("train.dispatch"):
            pass
        with span("train.realize"):
            pass
    trace = build_trace(sink.records)
    assert validate_trace(trace) == []
    xs = {e["name"]: e for e in trace["traceEvents"] if e.get("ph") == "X"}
    outer = xs["train.step"]
    for name in ("train.dispatch", "train.realize"):
        inner = xs[name]
        # Same track, child interval inside the parent's.
        assert (inner["pid"], inner["tid"]) == (outer["pid"], outer["tid"])
        assert inner["ts"] >= outer["ts"]
        assert inner["ts"] + inner["dur"] <= outer["ts"] + outer["dur"] + 0.5
    assert outer["args"]["step"] == 1
    assert outer["cat"] == "train"


def test_build_trace_request_spans_tagged(fresh_tracer):
    sink = MemorySink()
    fresh_tracer.add_sink(sink)
    e = fresh_tracer._epoch
    parent = fresh_tracer.record_span("serve.request", e + 1.0, 2.0,
                                      request_id="r1", state="done")
    fresh_tracer.record_span("serve.request.queue", e + 1.0, 0.5,
                             parent_id=parent, request_id="r1")
    fresh_tracer.record_span("serve.request.decode", e + 1.5, 1.5,
                             parent_id=parent, request_id="r1",
                             ttft_s=0.8)
    trace = build_trace(sink.records)
    assert validate_trace(trace) == []
    xs = [ev for ev in trace["traceEvents"] if ev.get("ph") == "X"]
    assert len(xs) == 3
    # Request lifecycles live on their own process group, tagged by id.
    assert all(ev["pid"] == 2 for ev in xs)
    assert all(ev["args"]["request_id"] == "r1" for ev in xs)
    decode = next(ev for ev in xs if ev["name"] == "serve.request.decode")
    assert decode["args"]["ttft_s"] == 0.8


def test_record_request_trace_emits_lifecycle_spans(fresh_tracer):
    from types import SimpleNamespace

    sink = MemorySink()
    fresh_tracer.add_sink(sink)
    sm = ServeMetrics(capacity=2)
    req = SimpleNamespace(id="req-7", submitted_at=10.0, admitted_at=10.4,
                          finished_at=12.0, state="done", beam_size=2,
                          tokens=[1, 2, 3], ttft_s=0.9)
    sm.record_request_trace(req)
    by_name = {r["span"]: r for r in sink.records}
    assert set(by_name) == {"serve.request", "serve.request.queue",
                            "serve.request.decode"}
    parent = by_name["serve.request"]
    assert parent["request_id"] == "req-7"
    assert parent["tokens"] == 3
    assert parent["dur_s"] == pytest.approx(2.0)
    assert by_name["serve.request.queue"]["parent_id"] == parent["span_id"]
    assert by_name["serve.request.queue"]["dur_s"] == pytest.approx(0.4)
    decode = by_name["serve.request.decode"]
    assert decode["parent_id"] == parent["span_id"]
    assert decode["ttft_s"] == 0.9


def test_record_request_trace_skips_unfinished(fresh_tracer):
    from types import SimpleNamespace

    sink = MemorySink()
    fresh_tracer.add_sink(sink)
    sm = ServeMetrics(capacity=2)
    sm.record_request_trace(SimpleNamespace(id="r", submitted_at=1.0,
                                            finished_at=None))
    assert sink.records == []


def test_export_trace_train_fixture(tmp_path):
    out = str(tmp_path / "trace.json")
    summary = export_trace(os.path.join(FIXTURES, "train"), out)
    assert summary["problems"] == []
    assert summary["spans"] == 16
    assert summary["records"] == 25
    with open(out) as fh:
        trace = json.load(fh)
    assert validate_trace(trace) == []
    instants = sorted(e["name"] for e in trace["traceEvents"]
                      if e.get("ph") == "i")
    assert instants == ["launch_attempt:crash", "launch_attempt:ok"]
    counters = {e["name"] for e in trace["traceEvents"]
                if e.get("ph") == "C"}
    assert {"loss", "examples_per_sec"} <= counters


def test_build_trace_deterministic():
    from deeplearning_cfn_tpu.obs.report import collect

    records, _, _ = collect(os.path.join(FIXTURES, "train"))
    assert json.dumps(build_trace(records)) == \
        json.dumps(build_trace(records))


def test_validate_trace_flags_bad_shapes():
    assert validate_trace([]) != []
    assert validate_trace({"traceEvents": [{"ph": "X"}]}) != []  # no name
    bad_ts = {"traceEvents": [
        {"ph": "X", "name": "a", "ts": -1.0, "dur": 1.0}]}
    assert any("bad ts" in p for p in validate_trace(bad_ts))
    overlap = {"traceEvents": [
        {"ph": "X", "name": "a", "pid": 1, "tid": 0, "ts": 0.0,
         "dur": 10.0},
        {"ph": "X", "name": "b", "pid": 1, "tid": 0, "ts": 5.0,
         "dur": 10.0}]}
    assert any("overlaps" in p for p in validate_trace(overlap))


def test_cli_obs_export(tmp_path, capsys):
    from deeplearning_cfn_tpu.cli.main import main

    out = str(tmp_path / "trace.json")
    rc = main(["obs", "export", os.path.join(FIXTURES, "train"),
               "-o", out])
    assert rc == 0
    assert "ui.perfetto.dev" in capsys.readouterr().out
    with open(out) as fh:
        assert json.load(fh)["traceEvents"]


def test_cli_obs_export_missing_path(capsys):
    from deeplearning_cfn_tpu.cli.main import main

    assert main(["obs", "export", "/nonexistent/run"]) == 1


# -- SLO rules ---------------------------------------------------------------


def test_threshold_exactly_at_limit_does_not_fire():
    r = Rule({"metric": "lat", "kind": "threshold", "max": 1.0})
    assert r.observe({"lat": 1.0}) is None      # at the limit: contract, ok
    alert = r.observe({"lat": 1.0001})          # strictly above: breach
    assert alert is not None
    assert alert["event"] == "alert"
    assert alert["value"] == pytest.approx(1.0001)
    assert alert["limit"] == 1.0
    r2 = Rule({"metric": "tps", "kind": "threshold", "min": 2.0})
    assert r2.observe({"tps": 2.0}) is None
    assert r2.observe({"tps": 1.9}) is not None


def test_threshold_edge_triggered_rearms():
    r = Rule({"metric": "lat", "kind": "threshold", "max": 1.0})
    assert r.observe({"lat": 2.0}) is not None   # ok -> breach: fires
    assert r.observe({"lat": 3.0}) is None       # still breached: latched
    assert r.observe({"lat": 0.5}) is None       # recovery re-arms
    assert r.observe({"lat": 2.0}) is not None   # second edge fires
    assert r.fired == 2


def test_percentile_rule_min_count_gate():
    r = Rule({"metric": "step_time_s", "kind": "percentile", "q": 95,
              "max": 1.0, "min_count": 3})
    assert r.observe({"step_time_s": 2.0}) is None   # gated: n=1
    assert r.observe({"step_time_s": 2.0}) is None   # gated: n=2
    alert = r.observe({"step_time_s": 2.0})          # n=3: p95=2.0 > 1.0
    assert alert is not None and alert["kind"] == "percentile"
    assert alert["value"] == pytest.approx(2.0)


def test_drop_rule_warmup_and_peak():
    r = Rule({"metric": "eps", "kind": "drop", "max_drop_frac": 0.5,
              "warmup": 2})
    assert r.observe({"eps": 100.0}) is None    # establishing the peak
    assert r.observe({"eps": 100.0}) is None    # warmup
    alert = r.observe({"eps": 40.0})            # 60% below peak: fires
    assert alert is not None
    assert "dropped" in alert["detail"]
    assert r.observe({"eps": 45.0}) is None     # latched
    assert r.observe({"eps": 90.0}) is None     # recovered, re-armed
    assert r.observe({"eps": 30.0}) is not None


def test_rule_ignores_missing_and_non_numeric():
    r = Rule({"metric": "lat", "kind": "threshold", "max": 1.0})
    assert r.observe({"other": 5.0}) is None
    assert r.observe({"lat": "fast"}) is None
    assert r.observe({"lat": True}) is None


def test_rule_alert_carries_step():
    r = Rule({"metric": "loss", "kind": "threshold", "max": 1.0})
    alert = r.observe({"step": 12, "loss": 3.0})
    assert alert["step"] == 12


def test_load_rules_rejects_bad_specs(tmp_path):
    def _load(doc):
        p = tmp_path / "r.json"
        p.write_text(doc if isinstance(doc, str) else json.dumps(doc))
        return load_rules(str(p))

    with pytest.raises(RuleError):
        _load("{not json")
    with pytest.raises(RuleError):
        _load({"no_rules": []})
    with pytest.raises(RuleError):
        _load({"rules": [{"metric": "x", "kind": "wat", "max": 1}]})
    with pytest.raises(RuleError):
        _load({"rules": [{"metric": "x", "kind": "threshold"}]})  # no limit
    with pytest.raises(RuleError):
        _load({"rules": [{"metric": "x", "kind": "drop"}]})  # no frac
    with pytest.raises(RuleError):
        _load({"rules": [{"kind": "threshold", "max": 1}]})  # no metric
    rules = _load({"rules": [{"metric": "x", "max": 1}]})  # kind defaults
    assert rules[0].kind == "threshold"
    assert rules[0].name == "x-threshold"


def test_check_run_clean_fixtures():
    rules = os.path.join(FIXTURES, "rules.json")
    for run in ("train", "serve"):
        result = check_run(os.path.join(FIXTURES, run), rules)
        assert result["ok"], result["alerts"]
        assert result["alerts"] == []


def test_check_run_breach_fixture_fires_and_tolerates_torn_line():
    result = check_run(os.path.join(FIXTURES, "breach"),
                       os.path.join(FIXTURES, "rules.json"))
    assert not result["ok"]
    assert result["skipped_lines"] >= 1  # the deliberately torn last line
    assert sorted(a["rule"] for a in result["alerts"]) == [
        "serve-queue-wait-p95",
        "serve-tokens-per-sec-floor",
        "train-step-time-p95",
        "train-throughput-drop",
    ]


def test_check_run_skips_preexisting_alert_records(tmp_path):
    p = tmp_path / "m.jsonl"
    rules = tmp_path / "r.json"
    rules.write_text(json.dumps({"rules": [
        {"name": "lat", "metric": "value", "kind": "threshold",
         "max": 1.0}]}))
    with p.open("w") as fh:
        # An alert line from a previous live run: its "value" field must
        # not be re-fed into the rules.
        fh.write(json.dumps({"event": "alert", "rule": "lat",
                             "value": 9.0, "limit": 1.0}) + "\n")
        fh.write(json.dumps({"ts": 1.0, "value": 0.5}) + "\n")
    result = check_run(str(p), str(rules))
    assert result["ok"]
    assert result["records"] == 2


def test_alerting_writer_emits_alert_inline(tmp_path):
    p = tmp_path / "m.jsonl"
    engine = SloEngine([Rule({"metric": "loss", "kind": "threshold",
                              "max": 1.0})])
    w = AlertingWriter(MetricsWriter(str(p)), engine)
    w.write({"step": 1, "loss": 0.5})
    w.write({"step": 2, "loss": 3.0})
    w.close()
    recs = [json.loads(l) for l in p.read_text().splitlines()]
    assert len(recs) == 3
    assert recs[2]["event"] == "alert"       # right after its trigger
    assert recs[2]["step"] == 2
    assert len(engine.alerts) == 1


def test_cli_obs_check_rc_contract(capsys):
    from deeplearning_cfn_tpu.cli.main import main

    rules = os.path.join(FIXTURES, "rules.json")
    assert main(["obs", "check", os.path.join(FIXTURES, "train"),
                 "--rules", rules]) == 0
    assert "obs check OK" in capsys.readouterr().out
    assert main(["obs", "check", os.path.join(FIXTURES, "breach"),
                 "--rules", rules]) == 1
    out = capsys.readouterr().out
    assert "obs check BREACH" in out and "ALERT " in out
    assert main(["obs", "check", "/nonexistent/run",
                 "--rules", rules]) == 2
    assert main(["obs", "check", os.path.join(FIXTURES, "train"),
                 "--rules", "/nonexistent/rules.json"]) == 2


def test_cli_obs_check_json(capsys):
    from deeplearning_cfn_tpu.cli.main import main

    rc = main(["obs", "check", os.path.join(FIXTURES, "breach"),
               "--rules", os.path.join(FIXTURES, "rules.json"),
               "--json"])
    assert rc == 1
    result = json.loads(capsys.readouterr().out)
    assert result["ok"] is False
    assert len(result["alerts"]) == 4


# -- cross-run diff ----------------------------------------------------------


def test_diff_identical_runs_zero_deltas():
    train = os.path.join(FIXTURES, "train")
    report = diff_runs(train, train)
    assert report["ok"]
    assert report["regressions"] == []
    assert report["common_metrics"] > 0
    assert report["only_a"] == report["only_b"] == []
    for m in report["metrics"].values():
        assert not m["regressed"]
        assert m["delta_p50"] in (None, 0.0)
        assert m["delta_p95"] in (None, 0.0)
    assert "regressions: 0" in render_diff(report)


def test_diff_flags_injected_regression(tmp_path):
    src = os.path.join(FIXTURES, "train", "metrics.jsonl")
    slow = tmp_path / "metrics.jsonl"
    with open(src) as fh, slow.open("w") as out:
        for line in fh:
            rec = json.loads(line)
            if isinstance(rec.get("step_time_s"), (int, float)):
                rec["step_time_s"] *= 3.0
            out.write(json.dumps(rec) + "\n")
    report = diff_runs(src, str(slow))
    assert not report["ok"]
    assert "step_time_s" in report["regressions"]
    m = report["metrics"]["step_time_s"]
    assert m["direction"] == "lower"
    assert m["rel_p50"] == pytest.approx(2.0)
    # The same 3x slowdown read the other way is an improvement, not a
    # regression.
    assert diff_runs(str(slow), src)["ok"]


def test_diff_direction_awareness():
    assert direction("examples_per_sec") == "higher"
    assert direction("serve_tokens_per_sec") == "higher"
    assert direction("loss") == "lower"
    assert direction("step_time_s") == "lower"
    assert direction("serve_queue_wait_p95_s") == "lower"
    assert direction("serve_latency_p95_s") == "lower"
    assert direction("span:serve.decode") == "lower"
    assert direction("accuracy") is None


def test_cli_obs_diff_self_and_regression(tmp_path, capsys):
    from deeplearning_cfn_tpu.cli.main import main

    train = os.path.join(FIXTURES, "train")
    assert main(["obs", "diff", train, train]) == 0
    assert "regressions: 0" in capsys.readouterr().out
    src = os.path.join(train, "metrics.jsonl")
    slow = tmp_path / "metrics.jsonl"
    with open(src) as fh, slow.open("w") as out:
        for line in fh:
            rec = json.loads(line)
            if isinstance(rec.get("step_time_s"), (int, float)):
                rec["step_time_s"] *= 3.0
            out.write(json.dumps(rec) + "\n")
    assert main(["obs", "diff", src, str(slow)]) == 1
    assert main(["obs", "diff", src, "/nonexistent"]) == 2
    rc = main(["obs", "diff", train, train, "--json"])
    capsys.readouterr()
    assert rc == 0


# -- live tail ---------------------------------------------------------------


def test_follower_buffers_partial_lines(tmp_path):
    p = tmp_path / "m.jsonl"
    f = JsonlFollower(str(p))
    assert f.poll() == []                        # missing file: no raise
    with p.open("w") as fh:
        fh.write('{"step": 1}\n{"step": 2, "lo')
        fh.flush()
    assert f.poll() == [{"step": 1}]             # torn tail held back
    with p.open("a") as fh:
        fh.write('ss": 2.5}\n')
    assert f.poll() == [{"step": 2, "loss": 2.5}]  # completed on next poll
    assert f.skipped == 0


def test_follower_resets_on_truncation(tmp_path):
    p = tmp_path / "m.jsonl"
    p.write_text('{"step": 1}\n{"step": 2}\n')
    f = JsonlFollower(str(p))
    assert len(f.poll()) == 2
    p.write_text('{"step": 9}\n')                # rotated/truncated
    assert f.poll() == [{"step": 9}]


def test_tail_state_status_line():
    s = TailState()
    s.update({"step": 4, "step_time_s": 0.25, "examples_per_sec": 128.0,
              "loss": 2.1})
    line = s.status_line()
    assert "step 4" in line and "4 steps/s" in line and "loss 2.1" in line
    s.update({"event": "alert", "rule": "loss-ceiling"})
    assert "alerts 1 (last: loss-ceiling)" in s.status_line()
    s.update({"span": "ckpt.save", "ok": False})
    assert "span-failures 1" in s.status_line()


def test_tail_once_renders_fixture_status():
    import io

    buf = io.StringIO()
    rc = tail(os.path.join(FIXTURES, "serve"), once=True, out=buf)
    assert rc == 0
    assert "serve q=0 25.41 tok/s done 4/4" in buf.getvalue()
    assert "alerts 0" in buf.getvalue()


def test_tail_live_slo_engine_prints_alerts(tmp_path):
    import io

    p = tmp_path / "metrics.jsonl"
    p.write_text('{"ts": 1.0, "step": 1, "loss": 99.0}\n')
    engine = SloEngine([Rule({"name": "loss-cap", "metric": "loss",
                              "kind": "threshold", "max": 10.0})])
    buf = io.StringIO()
    tail(str(p), once=True, slo_engine=engine, out=buf)
    assert "ALERT loss-cap:" in buf.getvalue()


def test_cli_obs_tail_once(capsys):
    from deeplearning_cfn_tpu.cli.main import main

    rc = main(["obs", "tail", os.path.join(FIXTURES, "train"), "--once"])
    assert rc == 0
    assert "step 6" in capsys.readouterr().out


# -- bounded histogram retention (satellite) ---------------------------------


def test_histogram_exact_below_cap():
    reg = MetricsRegistry()
    h = reg.histogram("h", max_samples=8)
    for i in range(8):
        h.observe(float(i))
    assert h.samples() == [float(i) for i in range(8)]  # byte-identical
    assert h.count() == 8
    assert h.percentile(50) == percentile([float(i) for i in range(8)], 50)


def test_histogram_reservoir_bounds_retention():
    reg = MetricsRegistry()
    h = reg.histogram("h", max_samples=8)
    for i in range(1000):
        h.observe(float(i))
    assert len(h.samples()) == 8            # retention bounded
    assert h.count() == 1000                # count stays exact
    assert h.sum() == float(sum(range(1000)))  # sum stays exact
    assert all(0.0 <= v < 1000.0 for v in h.samples())
    assert h.percentile(50) is not None


def test_histogram_reservoir_deterministic():
    def _fill():
        reg = MetricsRegistry()
        h = reg.histogram("h", max_samples=16)
        for i in range(500):
            h.observe(float(i))
        return h.samples()

    assert _fill() == _fill()               # seeded: no run-to-run drift


def test_histogram_max_samples_validated():
    reg = MetricsRegistry()
    with pytest.raises(ValueError):
        reg.histogram("bad", max_samples=0)


def test_histogram_default_cap_unchanged_for_short_runs():
    # Default-config histograms behave exactly as before the cap for any
    # realistic test-sized series.
    reg = MetricsRegistry()
    h = reg.histogram("h")
    xs = [0.1 * i for i in range(100)]
    for v in xs:
        h.observe(v)
    assert h.samples() == xs


# -- summarize: --since-step and empty dirs (satellite) ----------------------


def test_summarize_since_step_filters_train_records():
    train = os.path.join(FIXTURES, "train")
    full = summarize(train)
    late = summarize(train, since_step=4)
    assert late["source"]["since_step"] == 4
    assert late["source"]["records"] < full["source"]["records"]
    assert late["train"]["records"] < full["train"]["records"]
    assert late["train"]["last_step"] == full["train"]["last_step"]


def test_cli_obs_summarize_since_step(capsys):
    from deeplearning_cfn_tpu.cli.main import main

    rc = main(["obs", "summarize", "--json", "--since-step", "4",
               os.path.join(FIXTURES, "train")])
    assert rc == 0
    s = json.loads(capsys.readouterr().out)
    assert s["source"]["since_step"] == 4


def test_cli_obs_summarize_empty_dir(tmp_path, capsys):
    from deeplearning_cfn_tpu.cli.main import main

    rc = main(["obs", "summarize", str(tmp_path)])
    assert rc == 1
    assert "empty run dir" in capsys.readouterr().err


# -- phase-budget SLO rules --------------------------------------------------


_PHASE_RULE = {
    "name": "request-p95", "kind": "phase_budget",
    "metric": "serve_latency_p95_s", "max": 1.0,
    "phases": {
        "prefill": {"metric": "serve_phase_prefill_p95_s", "budget": 0.2},
        "decode": {"metric": "serve_phase_decode_p95_s", "budget": 0.7},
    },
}


def test_phase_budget_attributes_breach_to_worst_phase():
    r = Rule(dict(_PHASE_RULE))
    # Within SLO: phases are remembered, nothing fires.
    assert r.observe({"serve_latency_p95_s": 0.9,
                      "serve_phase_prefill_p95_s": 0.1,
                      "serve_phase_decode_p95_s": 0.6}) is None
    alert = r.observe({"serve_latency_p95_s": 1.4,
                       "serve_phase_prefill_p95_s": 0.1,
                       "serve_phase_decode_p95_s": 1.2})
    assert alert is not None and alert["kind"] == "phase_budget"
    assert alert["phase"] == "decode"        # 1.2/0.7 beats 0.1/0.2
    assert "decode" in alert["detail"]
    assert alert["value"] == pytest.approx(1.4)
    assert alert["limit"] == 1.0


def test_phase_budget_attribution_survives_split_records():
    # Total and phase metrics arrive in SEPARATE records (snapshot
    # streams interleave); the last phase observation still attributes.
    r = Rule(dict(_PHASE_RULE))
    assert r.observe({"serve_phase_prefill_p95_s": 0.5}) is None
    alert = r.observe({"serve_latency_p95_s": 2.0})
    assert alert is not None and alert["phase"] == "prefill"


def test_phase_budget_unattributed_when_phases_within_budget():
    r = Rule(dict(_PHASE_RULE))
    alert = r.observe({"serve_latency_p95_s": 1.5,
                       "serve_phase_prefill_p95_s": 0.1,
                       "serve_phase_decode_p95_s": 0.5})
    assert alert is not None and alert["phase"] == "unattributed"
    assert "within budget" in alert["detail"]


def test_phase_budget_edge_triggered_like_threshold():
    r = Rule(dict(_PHASE_RULE))
    rec = {"serve_latency_p95_s": 2.0, "serve_phase_decode_p95_s": 1.5}
    assert r.observe(rec) is not None       # ok -> breach fires
    assert r.observe(rec) is None           # latched
    assert r.observe({"serve_latency_p95_s": 0.5}) is None  # re-arms
    assert r.observe(rec) is not None
    assert r.fired == 2


def test_phase_budget_spec_validation():
    with pytest.raises(RuleError):   # needs max
        Rule({"metric": "m", "kind": "phase_budget",
              "phases": {"p": {"metric": "x", "budget": 1.0}}})
    with pytest.raises(RuleError):   # needs non-empty phases
        Rule({"metric": "m", "kind": "phase_budget", "max": 1.0})
    with pytest.raises(RuleError):
        Rule({"metric": "m", "kind": "phase_budget", "max": 1.0,
              "phases": {}})
    with pytest.raises(RuleError):   # phase needs a positive budget
        Rule({"metric": "m", "kind": "phase_budget", "max": 1.0,
              "phases": {"p": {"metric": "x", "budget": 0}}})
    with pytest.raises(RuleError):   # bool budget is not a number here
        Rule({"metric": "m", "kind": "phase_budget", "max": 1.0,
              "phases": {"p": {"metric": "x", "budget": True}}})
    with pytest.raises(RuleError):   # phase needs a metric string
        Rule({"metric": "m", "kind": "phase_budget", "max": 1.0,
              "phases": {"p": {"budget": 1.0}}})


# -- histogram snapshot honesty fields (satellite) ---------------------------


def test_histogram_snapshot_reports_window_and_retention():
    reg = MetricsRegistry()
    h = reg.histogram("h", max_samples=4)
    for i in range(10):
        h.observe(float(i), ts=100.0 + i)
    snap = reg.snapshot()["h"]["series"][""]
    assert snap["count"] == 10
    assert snap["samples_retained"] == 4     # reservoir cap bites
    assert snap["window_start_ts"] == 100.0
    assert snap["window_end_ts"] == 109.0


def test_histogram_snapshot_window_none_without_timestamps():
    reg = MetricsRegistry()
    h = reg.histogram("h")
    h.observe(1.0)
    h.observe(2.0)
    snap = reg.snapshot()["h"]["series"][""]
    assert snap["samples_retained"] == snap["count"] == 2
    assert snap["window_start_ts"] is None
    assert snap["window_end_ts"] is None


# -- the fleet signal bus ----------------------------------------------------


def test_rolling_window_prunes_to_record_time():
    from deeplearning_cfn_tpu.obs.signals import RollingWindow

    w = RollingWindow(window_s=10.0)
    w.add(0.0, 1.0)
    w.add(5.0, 2.0)
    w.add(14.0, 3.0)              # cutoff 4.0: drops the t=0 sample
    snap = w.snapshot()
    assert snap["samples"] == 2
    assert snap["window_start_ts"] == 5.0
    assert snap["window_end_ts"] == 14.0
    assert snap["last"] == 3.0
    with pytest.raises(ValueError):
        RollingWindow(window_s=0)


def test_signal_bus_fleet_aggregate_and_replay_determinism():
    from deeplearning_cfn_tpu.obs.signals import SignalBus

    def _fold():
        bus = SignalBus(names=["replica-0", "replica-1"])
        bus.observe("replica-0", {"ts": 1.0, "serve_tokens_per_sec": 10.0,
                                  "serve_queue_depth": 1,
                                  "serve_latency_p95_s": 0.2})
        bus.observe("replica-1", {"ts": 2.0, "serve_tokens_per_sec": 5.0,
                                  "serve_queue_depth": 0,
                                  "serve_latency_p95_s": 0.6})
        bus.observe("replica-1", {"event": "alert", "rule": "lat"})
        return bus.snapshot()

    a, b = _fold(), _fold()
    assert a == b                 # the bus never reads a clock
    assert a["event"] == "signal_snapshot"
    f = a["fleet"]
    assert f["replicas"] == 2 and f["replicas_live"] == 2
    assert f["tokens_per_sec"] == 15.0
    assert f["queue_depth"] == 1
    assert f["worst_latency_p95_s"] == 0.6
    assert f["alerts"] == 1
    assert a["replicas"]["replica-1"]["last_alert"] == "lat"
    assert json.dumps(a)          # one JSONL line, the autoscaler wire


def test_signal_bus_sequences_records_without_timestamps():
    from deeplearning_cfn_tpu.obs.signals import SignalBus

    bus = SignalBus()
    bus.observe("r", {"serve_queue_depth": 3})      # no ts anywhere
    win = bus.snapshot()["replicas"]["r"]["windowed"]["queue_depth"]
    assert win["samples"] == 1
    assert win["window_start_ts"] == 1.0            # seq counter stands in
    assert win["last"] == 3


def test_signal_bus_membership_churn_mid_window():
    """The autoscaler adds/removes replicas while the bus is live: a
    joiner registers on first observe and lands in the aggregate
    immediately, without disturbing the incumbents' rolling windows; a
    leaver simply stops reporting (its last values persist — the bus is
    an observer, not the membership authority, which is the router)."""
    from deeplearning_cfn_tpu.obs.signals import SignalBus

    bus = SignalBus(names=["replica-0"])
    bus.observe("replica-0", {"ts": 1.0, "serve_queue_depth": 3,
                              "serve_tokens_per_sec": 10.0})
    before = bus.replica("replica-0").snapshot()["windowed"]["queue_depth"]
    # Join mid-window: unknown name auto-registers on first observe.
    bus.observe("auto-both-0", {"ts": 1.5, "serve_queue_depth": 2,
                                "serve_tokens_per_sec": 4.0})
    f = bus.fleet()
    assert f["replicas"] == 2 and f["replicas_live"] == 2
    assert f["queue_depth"] == 5          # joiner counted immediately
    assert f["tokens_per_sec"] == 14.0
    after = bus.replica("replica-0").snapshot()["windowed"]["queue_depth"]
    assert after == before                # incumbent fold untouched
    # The incumbent keeps folding into the SAME window after the join.
    bus.observe("replica-0", {"ts": 2.0, "serve_queue_depth": 1})
    win = bus.replica("replica-0").snapshot()["windowed"]["queue_depth"]
    assert win["samples"] == before["samples"] + 1
    assert win["last"] == 1
    # Leave: the joiner drains away and stops reporting; the aggregate
    # still sums its last-known values (staleness is visible in ts, not
    # silently zeroed) and stays JSON-serializable.
    bus.observe("replica-0", {"ts": 3.0, "serve_queue_depth": 0})
    f = bus.fleet()
    assert f["queue_depth"] == 2          # 0 + joiner's last 2
    assert json.dumps(bus.snapshot())


def test_signal_bus_churn_replay_determinism():
    """Folding the same churn sequence twice — registration order,
    joins, and all — yields identical snapshots (the autoscaler's
    decisions replay from the seed only if its inputs do)."""
    from deeplearning_cfn_tpu.obs.signals import SignalBus

    def _fold():
        bus = SignalBus(names=["replica-0"])
        bus.observe("replica-0", {"ts": 1.0, "serve_queue_depth": 4})
        bus.observe("auto-both-0", {"ts": 1.2, "serve_queue_depth": 1})
        bus.observe("auto-both-1", {"ts": 1.4, "serve_queue_depth": 1})
        bus.observe("replica-0", {"ts": 2.0, "serve_queue_depth": 2})
        return bus.snapshot()

    assert _fold() == _fold()


def test_fleet_tail_state_autoscale_membership_and_state():
    """`obs tail --fleet` folds scale events into live membership and a
    controller state; a fleet that never scales keeps the legacy status
    line byte for byte."""
    from deeplearning_cfn_tpu.obs.tail import FleetTailState

    fixed = FleetTailState(["replica-0"])
    fixed.update("replica-0", {"ts": 1.0, "serve_queue_depth": 0,
                               "serve_submitted": 2,
                               "serve_completed": 2})
    legacy = fixed.status_line()
    assert "members" not in legacy and "scale" not in legacy

    st = FleetTailState(["replica-0", "#autoscale"])
    st.update("replica-0", {"ts": 1.0, "serve_queue_depth": 4,
                            "phase": "both"})
    st.update("#autoscale", {"event": "scale_event", "action": "scale_up",
                             "ts": 1.1, "phase": "both",
                             "replica": "auto-both-0",
                             "reason": "queue_depth 4 > 1.5"})
    assert st.scale_state() == "scaling-up"
    assert st.members == {"replica-0": "both", "auto-both-0": "both"}
    line = st.status_line()
    assert "members auto-both-0:both,replica-0:both" in line
    assert "scale scaling-up" in line and "queue_depth 4 > 1.5" in line
    # The control stream never pollutes the replica bus.
    assert "#autoscale" not in st.bus.replicas
    st.update("#autoscale", {"event": "scale_event",
                             "action": "drain_begin", "ts": 2.0,
                             "phase": "both", "replica": "auto-both-0",
                             "reason": "pool calm"})
    assert st.scale_state() == "draining"
    st.update("#autoscale", {"event": "scale_event",
                             "action": "scale_down", "ts": 2.1,
                             "phase": "both", "replica": "auto-both-0",
                             "reason": "drained idle", "drained": True})
    assert st.scale_state() == "steady"
    assert st.members == {"replica-0": "both"}
    assert st.scale_ups == 1 and st.scale_downs == 1


def test_fleet_tail_follows_autoscale_jsonl_and_new_replicas(tmp_path):
    """End to end over a fleet root on disk: the tail discovers the
    autoscale.jsonl control stream AND a replica dir created after the
    follow started (autoscaled membership is not fixed at startup)."""
    import io

    from deeplearning_cfn_tpu.obs.tail import (
        FleetTailState,
        _fleet_followers,
    )

    root = tmp_path / "fleet"
    (root / "replica-0").mkdir(parents=True)
    (root / "replica-0" / "metrics.jsonl").write_text(json.dumps(
        {"ts": 1.0, "serve_queue_depth": 1, "serve_submitted": 1,
         "serve_completed": 0}) + "\n")
    pairs = _fleet_followers(str(root))
    names = [n for n, _ in pairs]
    assert "#autoscale" in names
    # A replica dir that appears later is picked up by a re-discovery.
    (root / "auto-both-0").mkdir()
    (root / "auto-both-0" / "metrics.jsonl").write_text(json.dumps(
        {"ts": 2.0, "serve_queue_depth": 0, "serve_submitted": 1,
         "serve_completed": 1}) + "\n")
    (root / "autoscale.jsonl").write_text(json.dumps(
        {"event": "scale_event", "action": "scale_up", "ts": 1.5,
         "phase": "both", "replica": "auto-both-0",
         "reason": "queue_depth 3 > 1.5"}) + "\n")
    known = {f.path for _, f in pairs}
    for name, f in _fleet_followers(str(root)):
        if f.path not in known:
            pairs.append((name, f))
    assert {n for n, _ in pairs if not n.startswith("#")} \
        == {"auto-both-0", "replica-0"}
    st = FleetTailState([n for n, _ in pairs])
    for name, f in pairs:
        for rec in f.poll():
            st.update(name, rec)
    line = st.status_line()
    assert "scale scaling-up" in line
    assert "auto-both-0" in line

    from deeplearning_cfn_tpu.obs.tail import tail
    buf = io.StringIO()
    assert tail(str(root), once=True, fleet=True, out=buf) == 0
    assert "scale scaling-up" in buf.getvalue()


def test_fold_autoscale_report_section():
    """summarize --fleet's autoscale fold: counts, drained-vs-forced
    split, and the steady/scaling-up/draining state derivation."""
    from deeplearning_cfn_tpu.obs.report import fold_autoscale

    up = {"event": "scale_event", "action": "scale_up", "ts": 1.0,
          "phase": "both", "replica": "auto-both-0", "reason": "q"}
    drain = {"event": "scale_event", "action": "drain_begin", "ts": 2.0,
             "phase": "both", "replica": "auto-both-0", "reason": "calm"}
    down = {"event": "scale_event", "action": "scale_down", "ts": 3.0,
            "phase": "both", "replica": "auto-both-0",
            "reason": "drained idle", "drained": True}
    assert fold_autoscale([up])["state"] == "scaling-up"
    assert fold_autoscale([up, drain])["state"] == "draining"
    full = fold_autoscale([up, drain, down])
    assert full["state"] == "steady"
    assert full["scale_ups"] == 1 and full["scale_downs"] == 1
    assert full["drained_scale_downs"] == 1
    assert full["last_action"] == "scale_down"
    assert full["last_reason"] == "drained idle"
