"""Multi-tenant QoS tests: weighted fair-share admission (deficit
round-robin over per-class sub-queues), per-tenant rate limits with
class-specific retry-after hints, and the engine's preemptive eviction
path — a latency-class arrival that cannot place evicts a running
batch-class stream, which later resumes and must finish with EXACTLY the
tokens it would have produced unpreempted (restart-from-scratch resume is
a pure scheduling event, invisible in outputs).

The back-compat contract rides along: untagged single-tenant traffic
must behave — and serialize — byte-identically to the pre-QoS engine
(FIFO pop order, no qos_* metric keys, unchanged submit call shapes).
"""

import dataclasses
import json

import numpy as np
import pytest

from deeplearning_cfn_tpu.serve.queue import (
    DEFAULT_QOS_CLASS,
    OverloadError,
    QosSpec,
    RateLimitError,
    RequestQueue,
    RequestState,
    default_qos_classes,
)


class FakeClock:
    def __init__(self):
        self.t = 0.0

    def __call__(self):
        return self.t

    def advance(self, dt):
        self.t += dt


def _q(max_depth=200, clock=None, classes=True):
    kw = {}
    if clock is not None:
        kw["clock"] = clock
    if classes:
        kw["qos_classes"] = default_qos_classes()
    return RequestQueue(max_depth=max_depth, **kw)


# -- queue: fair-share admission ---------------------------------------------


def test_untagged_traffic_pops_in_exact_fifo_and_stays_qos_inactive():
    q = RequestQueue(max_depth=8)
    rids = [q.submit([5, 2, 1], 4, request_id=f"r{i}").id
            for i in range(6)]
    assert not q.qos_active
    assert [q.pop_ready().id for _ in range(6)] == rids
    assert q.fair_share_violation_max() is None


def test_tagged_submit_flips_qos_active():
    q = RequestQueue(max_depth=8)
    q.submit([5, 2, 1], 4)
    assert not q.qos_active
    q.submit([5, 2, 1], 4, qos_class="latency")
    assert q.qos_active


def test_drr_is_weighted_starvation_free_and_fifo_within_class():
    q = _q()
    lat = [q.submit([5, 2, 1], 8, qos_class="latency", tenant="a",
                    request_id=f"l{i}").id for i in range(40)]
    bat = [q.submit([5, 2, 1], 8, qos_class="batch", tenant="b",
                    request_id=f"b{i}").id for i in range(40)]
    order = [q.pop_ready().id for _ in range(80)]
    assert q.pop_ready() is None
    # FIFO within each class, whatever the interleave.
    assert [o for o in order if o.startswith("l")] == lat
    assert [o for o in order if o.startswith("b")] == bat
    # Starvation-free: batch is served while latency still has backlog
    # (weight 8 vs 1 → roughly one batch round per 8 latency rounds,
    # NOT "batch only after latency drains").
    first_batch = order.index("b0")
    assert first_batch < len(lat), "batch starved until latency drained"
    # Weighted: latency dominates the contended prefix ~8:1.
    prefix = order[:48]
    n_lat = sum(1 for o in prefix if o.startswith("l"))
    assert n_lat > 2 * (len(prefix) - n_lat)


def test_drr_blocked_class_skipped_without_losing_its_claim():
    q = _q()
    big = q.submit([5, 2, 1], 8, qos_class="latency", request_id="big")
    q.submit([5, 2, 1], 8, qos_class="batch", request_id="small")
    # The latency head cannot place: its class blocks (FIFO — nothing
    # behind it may jump), but batch keeps draining.
    got = q.pop_ready(can_place=lambda r: r.id != "big")
    assert got is not None and got.id == "small"
    # Once placeable, the blocked head is served before anything else.
    assert q.pop_ready().id == "big"
    assert big.state is RequestState.QUEUED  # engine flips it on placement
    assert q.pop_ready() is None


def test_pop_returns_none_when_every_head_is_unplaceable():
    q = _q()
    q.submit([5, 2, 1], 8, qos_class="latency")
    q.submit([5, 2, 1], 8, qos_class="batch")
    assert q.pop_ready(can_place=lambda r: False) is None
    assert q.depth == 2


def test_fair_share_violation_tracks_contended_shortfall():
    q = _q()
    for i in range(8):
        q.submit([5, 2, 1], 8, qos_class="latency", request_id=f"l{i}")
        q.submit([5, 2, 1], 8, qos_class="batch", request_id=f"b{i}")
    for _ in range(16):
        q.pop_ready()
    v = q.fair_share_violation_max()
    assert v is not None and 0.0 <= v <= 1.0


# -- queue: rate limits and per-class hints ----------------------------------


def test_rate_limit_is_per_tenant_and_hint_is_rate_derived():
    clock = FakeClock()
    classes = default_qos_classes()
    classes["batch"] = dataclasses.replace(classes["batch"],
                                           rate_per_s=2.0, burst=2.0)
    q = RequestQueue(max_depth=500, clock=clock, qos_classes=classes)
    q.submit([5, 2, 1], 4, qos_class="batch", tenant="noisy")
    q.submit([5, 2, 1], 4, qos_class="batch", tenant="noisy")
    with pytest.raises(RateLimitError) as ei:
        q.submit([5, 2, 1], 4, qos_class="batch", tenant="noisy")
    # IS-A OverloadError: every existing shed/backoff path handles it.
    assert isinstance(ei.value, OverloadError)
    assert ei.value.rate_limited and ei.value.tenant == "noisy"
    assert ei.value.retry_after_s == pytest.approx(0.5)
    # A different tenant in the same class has its own bucket.
    q.submit([5, 2, 1], 4, qos_class="batch", tenant="quiet")
    # The bucket refills on the clock.
    clock.advance(0.5)
    q.submit([5, 2, 1], 4, qos_class="batch", tenant="noisy")


def test_batch_overload_hint_exceeds_latency_hint_under_backlog():
    clock = FakeClock()
    classes = default_qos_classes()
    classes["batch"] = dataclasses.replace(classes["batch"],
                                           rate_per_s=2.0, burst=100.0)
    q = RequestQueue(max_depth=10, clock=clock, qos_classes=classes)
    for i in range(10):
        q.submit([5, 2, 1], 4, qos_class="batch", request_id=f"b{i}")
    with pytest.raises(OverloadError) as bat:
        q.submit([5, 2, 1], 4, qos_class="batch")
    with pytest.raises(OverloadError) as lat:
        q.submit([5, 2, 1], 4, qos_class="latency")
    # Batch is told to wait out its own backlog (10 pending / 2 per s);
    # latency gets the base (cold-start floor) estimate.
    assert bat.value.retry_after_s == pytest.approx(5.0)
    assert lat.value.retry_after_s == \
        RequestQueue.DEFAULT_RETRY_AFTER_FLOOR_S
    assert bat.value.retry_after_s > lat.value.retry_after_s


def test_qos_spec_validation():
    with pytest.raises(ValueError):
        QosSpec("bad", weight=0)
    with pytest.raises(ValueError):
        QosSpec("bad", rate_per_s=-1.0)
    with pytest.raises(ValueError):
        _q().submit([5, 2, 1], 4, qos_class="no-such-class")


def test_default_class_is_standard():
    q = _q()
    req = q.submit([5, 2, 1], 4)
    assert req.qos_class == DEFAULT_QOS_CLASS == "standard"
    assert req.tenant is None


# -- engine: preemptive eviction + token-identical resume --------------------


SRC_LEN = 8
MAX_NEW = 6


@pytest.fixture(scope="module")
def qos_model():
    import jax

    from deeplearning_cfn_tpu.models.transformer_nmt import (
        transformer_nmt_tiny,
    )

    model = transformer_nmt_tiny(vocab_size=96, hidden_size=32,
                                 num_layers=1, num_heads=2, mlp_dim=64,
                                 max_len=32)
    init = model.init(
        jax.random.PRNGKey(0), np.zeros((1, SRC_LEN), np.int32),
        np.ones((1, SRC_LEN), np.int32),
        np.zeros((1, SRC_LEN), np.int32), train=False)
    return model, {"params": init["params"]}


def _mk_engine(qos_model, **kw):
    from deeplearning_cfn_tpu.serve.engine import Engine

    model, variables = qos_model
    kw.setdefault("capacity", 2)
    kw.setdefault("max_src_len", SRC_LEN)
    kw.setdefault("queue_depth", 16)
    kw.setdefault("default_max_new_tokens", MAX_NEW)
    kw.setdefault("decode_window", 2)
    return Engine(model, variables, **kw)


def _srcs(n):
    rng = np.random.RandomState(7)
    return [[int(t) for t in rng.randint(3, 96, size=SRC_LEN)]
            for _ in range(n)]


def _drain_tokens(engine, rids):
    engine.run_until_drained()
    out = {}
    for rid in rids:
        req = engine.poll(rid)
        assert req.state is RequestState.DONE
        out[rid] = list(req.tokens)
    return out


@pytest.mark.parametrize("beam,kv", [(1, 0), (1, 4), (2, 0), (2, 4)],
                         ids=["greedy-dense", "greedy-paged",
                              "beam-dense", "beam-paged"])
def test_preempt_resume_token_parity(qos_model, beam, kv):
    """A batch-class stream evicted mid-decode by a latency arrival must
    resume and finish token-identical to an unpreempted run — greedy and
    beam, dense and paged caches alike."""
    srcs = _srcs(3)
    kw = dict(kv_block_size=kv)

    # Baseline: same requests, untagged, no contention-driven eviction.
    base = _mk_engine(qos_model, **kw)
    b1 = base.submit(srcs[0], max_new_tokens=MAX_NEW, beam_size=beam)
    b2 = None
    if beam == 1:
        b2 = base.submit(srcs[1], max_new_tokens=MAX_NEW)
    b3 = base.submit(srcs[2], max_new_tokens=2)
    base_rids = [r.id for r in (b1, b2, b3) if r is not None]
    baseline = _drain_tokens(base, base_rids)

    eng = _mk_engine(qos_model, **kw)
    # Fill every row with preemptible batch work: one beam-2 group (two
    # rows) or two greedy streams.
    r1 = eng.submit(srcs[0], max_new_tokens=MAX_NEW, beam_size=beam,
                    tenant="tenant-b", qos_class="batch")
    r2 = None
    if beam == 1:
        r2 = eng.submit(srcs[1], max_new_tokens=MAX_NEW,
                        tenant="tenant-b", qos_class="batch")
    for _ in range(2):      # let the batch work decode a bit first
        eng.step()
    # The latency arrival cannot place → evicts a batch stream.
    r3 = eng.submit(srcs[2], max_new_tokens=2, tenant="tenant-a",
                    qos_class="latency")
    rids = [r.id for r in (r1, r2, r3) if r is not None]
    tokens = _drain_tokens(eng, rids)

    assert eng.metrics.preemptions >= 1
    assert eng.metrics.qos_token_loss == 0
    snap = eng.metrics.snapshot()
    assert snap["serve_preemptions"] == eng.metrics.preemptions
    # Every decoded token is goodput or audited waste — preemption
    # replay never double-counts.
    assert snap["serve_goodput_tokens"] + snap["serve_wasted_tokens"] \
        == snap["serve_tokens_generated"]
    preempted = [rid for rid in rids
                 if eng.poll(rid).preemptions > 0]
    assert preempted, "no request recorded a preemption"
    for rid in preempted:
        assert eng.poll(rid).preempted_s >= 0.0
    # The contract: preemption is invisible in outputs.
    assert len(base_rids) == len(rids)
    for brid, rid in zip(base_rids, rids):
        assert tokens[rid] == baseline[brid], \
            f"preempted run diverged on {rid}"


def test_preemption_needs_qos_traffic(qos_model):
    """Untagged traffic never preempts — the engine stays byte-for-byte
    the pre-QoS scheduler, including its metrics snapshot keys."""
    eng = _mk_engine(qos_model)
    srcs = _srcs(3)
    rids = [eng.submit(s, max_new_tokens=3).id for s in srcs]
    tokens = _drain_tokens(eng, rids)
    assert all(len(t) > 0 for t in tokens.values())
    assert eng.metrics.preemptions == 0
    snap = eng.metrics.snapshot()
    assert "serve_preemptions" not in snap
    assert "serve_qos_by_class" not in snap
    assert not eng.queue.qos_active


def test_qos_snapshot_surfaces_by_class(qos_model):
    eng = _mk_engine(qos_model)
    srcs = _srcs(2)
    rids = [
        eng.submit(srcs[0], max_new_tokens=3, tenant="a",
                   qos_class="latency").id,
        eng.submit(srcs[1], max_new_tokens=3, tenant="b",
                   qos_class="batch").id,
    ]
    _drain_tokens(eng, rids)
    snap = eng.metrics.snapshot()
    by_cls = snap["serve_qos_by_class"]
    assert by_cls["latency"]["completed"] == 1
    assert by_cls["batch"]["completed"] == 1
    assert by_cls["latency"]["latency_p95_s"] is not None


@pytest.mark.parametrize("beam", [1, 2], ids=["greedy", "beam"])
def test_preempt_resume_parity_across_disagg_handoff(qos_model, beam):
    """Preemption composes with disaggregation: a batch-class stream
    imported onto a decode engine via the KV handoff is evicted by a
    direct latency submit, re-prefills locally, and still finishes
    token-identical to a co-located run of the same trace."""
    srcs = _srcs(3)

    co = _mk_engine(qos_model, kv_block_size=4)
    c1 = co.submit(srcs[0], max_new_tokens=MAX_NEW, beam_size=beam)
    c2 = None
    if beam == 1:
        c2 = co.submit(srcs[1], max_new_tokens=MAX_NEW)
    c3 = co.submit(srcs[2], max_new_tokens=2)
    co_rids = [r.id for r in (c1, c2, c3) if r is not None]
    baseline = _drain_tokens(co, co_rids)

    pre = _mk_engine(qos_model, kv_block_size=4, phase="prefill")
    dec = _mk_engine(qos_model, kv_block_size=4, phase="decode")
    parked = [pre.submit(srcs[0], max_new_tokens=MAX_NEW,
                         beam_size=beam, tenant="tenant-b",
                         qos_class="batch")]
    if beam == 1:
        parked.append(pre.submit(srcs[1], max_new_tokens=MAX_NEW,
                                 tenant="tenant-b", qos_class="batch"))
    pre.run_until_drained()
    imported = []
    for req in parked:
        assert pre.handoff_ready(req.id)
        art = pre.export_handoff(req.id)
        imported.append(dec.import_handoff(
            art, request_id=req.id + "#a1", tenant="tenant-b",
            qos_class="batch"))
        pre.release_handoff(req.id)
    assert dec.queue.qos_active
    for _ in range(2):
        dec.step()
    lat = dec.submit(srcs[2], max_new_tokens=2, tenant="tenant-a",
                     qos_class="latency")
    rids = [r.id for r in imported] + [lat.id]
    tokens = _drain_tokens(dec, rids)

    assert dec.metrics.preemptions >= 1
    assert dec.metrics.qos_token_loss == 0
    for brid, rid in zip(co_rids, rids):
        assert tokens[rid] == baseline[brid], \
            f"handoff+preempt run diverged on {rid}"


# -- fleet: router threading + ledger ----------------------------------------


def test_router_ledger_tags_tenant_class_and_preemptions(qos_model):
    from deeplearning_cfn_tpu.fleet import EngineReplica, Router

    eng = _mk_engine(qos_model, capacity=1, kv_block_size=4)
    router = Router([EngineReplica("replica-0", eng)])
    b = router.submit(_srcs(1)[0], max_new_tokens=MAX_NEW,
                      tenant="tenant-b", qos_class="batch")
    router.step()
    lat = router.submit(_srcs(2)[1], max_new_tokens=2,
                        tenant="tenant-a", qos_class="latency")
    plain = router.submit(_srcs(3)[2], max_new_tokens=2)
    router.run_until_drained()
    for rid in (b, lat, plain):
        assert router.result(rid)["state"] == "done"
    entry = router.ledger[b]
    assert entry["tenant"] == "tenant-b"
    assert entry["qos_class"] == "batch"
    assert entry["preemptions"] >= 1
    assert entry["phases"]["preempted_s"] >= 0.0
    assert router.ledger[lat]["qos_class"] == "latency"
    # Untagged requests keep the exact pre-QoS ledger key set.
    assert "tenant" not in router.ledger[plain]
    assert "qos_class" not in router.ledger[plain]
    assert "preempted_s" not in router.ledger[plain]["phases"]


# -- loadgen: tenant mixes ---------------------------------------------------


def test_tenants_mix_classes_carry_tags():
    from deeplearning_cfn_tpu.loadgen import parse_trace_spec

    spec = parse_trace_spec("poisson:mix=tenants", src_len=12,
                            max_new_tokens=16, requests=12)
    by_name = {c.name: c for c in spec.classes}
    assert by_name["interactive"].tenant == "tenant-a"
    assert by_name["interactive"].qos_class == "latency"
    assert by_name["bulk"].tenant == "tenant-b"
    assert by_name["bulk"].qos_class == "batch"
    # The uniform mix stays untagged.
    uni = parse_trace_spec("poisson", src_len=12, max_new_tokens=16)
    assert all(c.tenant is None and c.qos_class is None
               for c in uni.classes)


class _CaptureRouter:
    def __init__(self):
        self.ledger = {}
        self.calls = []

    def submit(self, src_ids, max_new_tokens, request_id, **kw):
        self.calls.append((request_id, dict(kw)))
        self.ledger[request_id] = {"phases": {}}
        return request_id

    def step(self):
        return False

    def pending(self):
        return 0


@pytest.mark.parametrize("mix,tagged", [("tenants", True),
                                        ("uniform", False)])
def test_replay_submits_tenant_tags_through_router(mix, tagged):
    from deeplearning_cfn_tpu.loadgen import (
        LoadGenerator,
        VirtualClock,
        parse_trace_spec,
        replay,
    )

    spec = parse_trace_spec(f"poisson:duration=0.5,mix={mix}",
                            src_len=8, max_new_tokens=4, requests=8)
    gen = LoadGenerator(spec, seed=0)
    router = _CaptureRouter()
    replay(gen, router, VirtualClock(), tick_s=0.05)
    assert router.calls
    if tagged:
        by_cls = {s.request_id: s.qos_class for s in gen.schedule}
        for rid, kw in router.calls:
            assert kw["qos_class"] == by_cls[rid]
            assert kw["tenant"] in ("tenant-a", "tenant-b")
    else:
        # Back-compat call shape: untagged replay must not even pass
        # the kwargs (pre-QoS router fakes reject unknown keys).
        for _, kw in router.calls:
            assert "tenant" not in kw and "qos_class" not in kw


# -- obs: SLO rules, report, tail --------------------------------------------


def test_slo_rule_class_field_reads_nested_qos_section():
    from deeplearning_cfn_tpu.obs.slo import Rule, RuleError

    rule = Rule({"metric": "latency_p95_s", "class": "latency",
                 "kind": "threshold", "max": 0.5})
    ok = {"serve_qos_by_class": {
        "latency": {"latency_p95_s": 0.4},
        "batch": {"latency_p95_s": 9.0}}}
    assert rule.observe(ok) is None
    bad = {"serve_qos_by_class": {"latency": {"latency_p95_s": 0.7}}}
    alert = rule.observe(bad)
    assert alert is not None and alert["class"] == "latency"
    # A top-level key of the same name is NOT the per-class value.
    rule2 = Rule({"metric": "latency_p95_s", "class": "latency",
                  "kind": "threshold", "max": 0.5})
    assert rule2.observe({"latency_p95_s": 0.7}) is None
    with pytest.raises(RuleError):
        Rule({"metric": "latency_p95_s", "class": "", "max": 1.0})


def test_summarize_reports_per_tenant_sections(tmp_path):
    from deeplearning_cfn_tpu.obs.report import render_report, summarize

    p = tmp_path / "metrics.jsonl"
    snap = {"serve_completed": 3, "serve_submitted": 3,
            "serve_preemptions": 2, "serve_preempted_tokens_replayed": 7,
            "serve_qos_token_loss": 0,
            "serve_fair_share_violation_max": 0.1,
            "serve_qos_by_class": {
                "latency": {"completed": 1, "latency_p50_s": 0.01,
                            "latency_p95_s": 0.02},
                "batch": {"completed": 2, "latency_p50_s": 0.5,
                          "latency_p95_s": 0.9}}}
    p.write_text(json.dumps(snap) + "\n")
    out = summarize(str(p))
    qos = out["serve"]["qos"]
    assert qos["preemptions"] == 2
    assert qos["by_class"]["batch"]["completed"] == 2
    text = render_report(out)
    assert "qos latency" in text and "qos batch" in text
    assert "preemptions" in text
    # Single-tenant snapshots keep the exact pre-QoS section shape.
    p2 = tmp_path / "plain.jsonl"
    p2.write_text(json.dumps({"serve_completed": 1}) + "\n")
    out2 = summarize(str(p2))
    assert "qos" not in out2["serve"]
    assert "qos" not in render_report(out2)


def test_tail_status_line_shows_preemptions():
    from deeplearning_cfn_tpu.obs.tail import FleetTailState, TailState

    st = TailState()
    st.update({"serve_submitted": 2, "serve_completed": 1})
    assert "preempt" not in st.status_line()
    st.update({"serve_submitted": 3, "serve_preemptions": 2})
    assert "preempt 2" in st.status_line()
    fst = FleetTailState(["replica-0", "replica-1"])
    fst.update("replica-0", {"serve_submitted": 2, "serve_preemptions": 1})
    fst.update("replica-1", {"serve_submitted": 2, "serve_preemptions": 3})
    assert "preempt 4" in fst.status_line()
    fplain = FleetTailState(["replica-0"])
    fplain.update("replica-0", {"serve_submitted": 2})
    assert "preempt" not in fplain.status_line()
