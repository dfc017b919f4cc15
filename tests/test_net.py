"""net/ tests: the wire codec under fuzz/truncation, lossless typed
overload round-trips, an in-process loopback server↔client exchange,
and — marked slow — REAL child-process fleets: cross-process token
parity vs the in-process fleet (greedy + beam) and the zero-drop
contract across a SIGKILL'd replica mid-stream.

The contract under test everywhere: promoting replicas from in-process
objects to socket-backed processes must be invisible in outputs —
token-identical on the same seeded trace — while zero requests drop.
"""

import os
import random
import struct
import threading

import pytest

from deeplearning_cfn_tpu.fleet.router import (
    FleetOverloadError,
    NoReplicasError,
)
from deeplearning_cfn_tpu.net.codec import (
    MAX_FRAME_BYTES,
    CodecError,
    CorruptFrame,
    FrameReader,
    FrameTooLarge,
    FrameType,
    VersionMismatch,
    encode_frame,
    error_header,
    raise_error_header,
    read_frames,
)
from deeplearning_cfn_tpu.serve.handoff import HandoffCorruptError
from deeplearning_cfn_tpu.serve.queue import (
    DeadlineExceededError,
    OverloadError,
    RateLimitError,
)

# -- codec: round trip, truncation, fuzz -------------------------------------


def test_frame_round_trip_all_types():
    frames = [
        (FrameType.SUBMIT, {"rid": "r-1", "src_ids": [3, 7, 11]}, b""),
        (FrameType.TOKENS, {"req": {"id": "a", "tokens": [1, 2]}}, b""),
        (FrameType.HANDOFF_EXPORT_OK, {"rid": "r-2"}, b"\x00\x01npz"),
        (FrameType.HEALTH_OK, {"rid": "r-3", "queue_depth": 0}, b""),
    ]
    blob = b"".join(encode_frame(t, h, b) for t, h, b in frames)
    decoded, consumed = read_frames(blob)
    assert consumed == len(blob)
    assert [(f.ftype, f.header, f.body) for f in decoded] == frames


def test_partial_frame_is_silence_not_error():
    blob = encode_frame(FrameType.SUBMIT, {"rid": "r", "src_ids": [1]})
    reader = FrameReader()
    for cut in range(len(blob)):
        r = FrameReader()
        r.feed(blob[:cut])
        assert r.next() is None, f"phantom frame at truncation {cut}"
    # Byte-at-a-time delivery reassembles exactly one frame.
    for i in range(len(blob)):
        reader.feed(blob[i:i + 1])
    frames = list(reader)
    assert len(frames) == 1 and frames[0].header["rid"] == "r"
    assert reader.buffered == 0


def test_oversized_frame_rejected_before_buffering():
    reader = FrameReader()
    reader.feed(struct.pack(">I", MAX_FRAME_BYTES + 1))
    with pytest.raises(FrameTooLarge):
        reader.next()
    # The reader is poisoned: a framing-desync stream can't resync.
    with pytest.raises(CodecError):
        reader.feed(b"x")
        reader.next()


def test_version_mismatch_rejected():
    blob = bytearray(encode_frame(FrameType.HEALTH, {"rid": "r"}))
    blob[4] ^= 0x7F   # the version byte lives right after the prefix
    reader = FrameReader()
    reader.feed(bytes(blob))
    with pytest.raises(VersionMismatch):
        reader.next()


def test_garbage_bytes_rejected():
    reader = FrameReader()
    # A plausible length prefix followed by garbage: bad version or a
    # corrupt header, never a parsed frame.
    reader.feed(struct.pack(">I", 64) + b"\xde\xad" * 32)
    with pytest.raises(CodecError):
        reader.next()


def test_fuzz_random_garbage_never_yields_frames():
    rng = random.Random(0)
    for _ in range(200):
        reader = FrameReader()
        reader.feed(bytes(rng.randrange(256)
                          for _ in range(rng.randrange(1, 80))))
        try:
            frame = reader.next()
        except CodecError:
            continue
        # Not rejected means incomplete: silence, never a phantom frame.
        assert frame is None


def test_fuzz_corrupted_valid_frame():
    base = encode_frame(FrameType.SUBMIT,
                        {"rid": "r", "src_ids": list(range(16))},
                        b"body-bytes")
    rng = random.Random(1)
    for _ in range(200):
        blob = bytearray(base)
        for _ in range(rng.randrange(1, 4)):
            blob[rng.randrange(len(blob))] ^= 1 << rng.randrange(8)
        reader = FrameReader()
        reader.feed(bytes(blob))
        try:
            frame = reader.next()
        except CodecError:
            continue
        if frame is not None:
            # Flips confined to header values/body can still parse —
            # but the frame must be structurally whole, and the stream
            # must stay in sync for the next frame.
            assert isinstance(frame.header, dict)
            reader.feed(encode_frame(FrameType.HEALTH, {"rid": "h"}))
            follow = reader.next()
            assert follow is not None and follow.header["rid"] == "h"


# -- typed overload round trips ----------------------------------------------


def test_fleet_overload_round_trips_losslessly():
    exc = FleetOverloadError(7, 8, 0.25,
                             per_replica={"r0": 0.25, "r1": None})
    h = error_header(exc, rid="rid-1", recovery_horizon_s=1.5)
    assert h["code"] == "fleet_overload"
    with pytest.raises(FleetOverloadError) as ei:
        raise_error_header(h)
    back = ei.value
    assert (back.depth, back.max_depth, back.retry_after_s) == (7, 8, 0.25)
    assert back.per_replica == {"r0": 0.25, "r1": None}
    assert back.recovery_horizon_s == 1.5
    assert back.rid == "rid-1"
    assert isinstance(back, OverloadError)


def test_rate_limit_round_trips_losslessly():
    exc = RateLimitError("latency", "tenant-a", 0.75, 3, 4)
    h = error_header(exc)
    assert h["code"] == "rate_limit"
    with pytest.raises(RateLimitError) as ei:
        raise_error_header(h)
    back = ei.value
    assert back.qos_class == "latency"
    assert back.tenant == "tenant-a"
    assert back.retry_after_s == 0.75
    assert (back.depth, back.max_depth) == (3, 4)


def test_overload_and_draining_round_trip():
    h = error_header(OverloadError(2, 2, retry_after_s=0.05))
    assert h["code"] == "overload"
    with pytest.raises(OverloadError) as ei:
        raise_error_header(h)
    assert ei.value.retry_after_s == 0.05
    # "draining" means exactly "try the next candidate" — a plain
    # OverloadError, so mid-placement routers need no special case.
    with pytest.raises(OverloadError):
        raise_error_header({"code": "draining", "message": "draining"})


def test_remaining_error_codes_round_trip():
    cases = [
        (DeadlineExceededError("too late"), DeadlineExceededError),
        (KeyError("nope"), KeyError),
        (HandoffCorruptError("bad npz"), HandoffCorruptError),
        (ValueError("bad submit"), ValueError),
        (RuntimeError("boom"), RuntimeError),
    ]
    for exc, klass in cases:
        with pytest.raises(klass):
            raise_error_header(error_header(exc))
    with pytest.raises(NoReplicasError):
        raise_error_header({"code": "no_replicas", "message": "none"})
    # handoff_corrupt must NOT degrade to the generic "invalid" even
    # though HandoffCorruptError IS-A ValueError.
    assert error_header(HandoffCorruptError("x"))["code"] \
        == "handoff_corrupt"


# -- in-process loopback: server thread ↔ RemoteReplica ----------------------


@pytest.fixture(scope="module")
def loopback(tmp_path_factory):
    """One tiny-engine ReplicaServer on a unix socket in a daemon
    thread, plus a connected RemoteReplica. Module-scoped: one jax
    model build for every loopback test."""
    import jax
    import numpy as np

    from deeplearning_cfn_tpu.models.transformer_nmt import (
        transformer_nmt_tiny,
    )
    from deeplearning_cfn_tpu.net.client import RemoteReplica
    from deeplearning_cfn_tpu.net.server import ReplicaServer
    from deeplearning_cfn_tpu.serve.engine import Engine

    model = transformer_nmt_tiny(vocab_size=96, max_len=64)
    init = model.init(jax.random.PRNGKey(0),
                      np.zeros((1, 8), np.int32),
                      np.ones((1, 8), np.int32),
                      np.zeros((1, 8), np.int32), train=False)
    engine = Engine(model, {"params": init["params"]}, capacity=2,
                    max_src_len=8, queue_depth=4,
                    default_max_new_tokens=4, decode_window=4)
    addr = f"unix://{tmp_path_factory.mktemp('net')}/replica.sock"
    server = ReplicaServer(engine, addr, replica_id="loop")
    thread = threading.Thread(target=server.serve_forever, daemon=True)
    thread.start()
    replica = RemoteReplica("loop", addr,
                            connect_retry_deadline_s=30.0).connect()
    yield replica
    replica.drain()
    replica.close()
    thread.join(timeout=10)


def test_loopback_submit_stream_and_result(loopback):
    req = loopback.submit([5, 9, 13, 2], max_new_tokens=4,
                          request_id="loop-1")
    assert req.id == "loop-1"
    # Polls of 20 ms each: a minute, so that the server's first compile
    # fits on a machine that six test workers share (100 polls did not).
    deadline = 3000
    while req.state.value not in ("done", "cancelled", "expired") \
            and deadline:
        loopback.step()
        deadline -= 1
    assert req.state.value == "done"
    assert len(req.tokens) >= 1
    assert req.ttft_s is not None


def test_loopback_health_and_unknown_cancel(loopback):
    h = loopback.health()
    assert h["replica"] == "loop"
    assert h["queue_max_depth"] == 4
    # Same duck type as EngineReplica: unknown-id cancel is a KeyError,
    # round-tripped over the wire as the typed unknown_request frame.
    with pytest.raises(KeyError):
        loopback.cancel("never-submitted")


# -- real child processes (slow) ---------------------------------------------


def _spawn(tmp_path, phases, **kwargs):
    from deeplearning_cfn_tpu.net.bench import spawn_process_fleet

    defaults = dict(slots=2, src_len=8, max_new_tokens=4,
                    queue_depth=16, decode_window=4, seed=0)
    defaults.update(kwargs)
    return spawn_process_fleet(str(tmp_path), phases, **defaults)


def _drive(router, trace, max_new_tokens, beam_size=1, prefix="q"):
    rids = []
    for i, src in enumerate(trace):
        while True:
            try:
                rids.append(router.submit(
                    src, max_new_tokens=max_new_tokens,
                    beam_size=beam_size, request_id=f"{prefix}{i}"))
                break
            except (OverloadError, NoReplicasError):
                router.step()
    router.run_until_drained(idle_timeout_s=60.0)
    return {rid: list(router.result(rid)["tokens"]) for rid in rids}


@pytest.mark.slow
def test_cross_process_token_parity_greedy_and_beam(tmp_path):
    from deeplearning_cfn_tpu.net.bench import (
        _reference_tokens,
        _teardown,
    )
    from deeplearning_cfn_tpu.net.router import NetRouter
    from deeplearning_cfn_tpu.serve.bench import _fixed_trace

    trace = _fixed_trace(4, 8, 96, seed=0)
    warm = trace[0]
    sup, remotes = _spawn(tmp_path, ["both", "both"], warmup_src=warm)
    try:
        rt = NetRouter(remotes, supervisor=sup)
        got_greedy = _drive(rt, trace, 4, beam_size=1, prefix="g")
        got_beam = _drive(rt, trace, 4, beam_size=2, prefix="b")
        assert rt.dropped_requests == 0
    finally:
        _teardown(sup, remotes)
    for beam, got, prefix in ((1, got_greedy, "g"), (2, got_beam, "b")):
        # The reference helper submits with request ids q0..qN in trace
        # order; match by index.
        ref = _reference_tokens(trace, 4, beam, slots=2, src_len=8,
                                queue_depth=16, decode_window=4, seed=0)
        for i in range(len(trace)):
            assert got[f"{prefix}{i}"] == ref[f"q{i}"], \
                f"beam={beam} request {i} parity broken"


@pytest.mark.slow
def test_sigkill_mid_stream_zero_drops(tmp_path):
    from deeplearning_cfn_tpu.net.bench import _teardown
    from deeplearning_cfn_tpu.net.router import NetRouter
    from deeplearning_cfn_tpu.serve.bench import _fixed_trace

    trace = _fixed_trace(6, 8, 96, seed=0)
    sup, remotes = _spawn(tmp_path, ["both", "both"],
                          warmup_src=trace[0], max_restarts=1)
    try:
        rt = NetRouter(remotes, supervisor=sup)
        rids = []
        for i, src in enumerate(trace):
            while True:
                try:
                    rids.append(rt.submit(src, max_new_tokens=8,
                                          request_id=f"k{i}"))
                    break
                except (OverloadError, NoReplicasError):
                    rt.step()
        # SIGKILL one replica while its streams are mid-decode: the
        # router must evacuate and replay them elsewhere, zero drops.
        victim = sup._replicas[1].handle._procs[0].proc
        victim.kill()
        rt.run_until_drained(idle_timeout_s=60.0)
        assert rt.dropped_requests == 0
        results = [rt.result(rid) for rid in rids]
        assert all(r["state"] == "done" for r in results)
        assert all(len(r["tokens"]) >= 1 for r in results)
        assert rt.evacuations >= 1 or rt.reconnects >= 1
    finally:
        _teardown(sup, remotes)
