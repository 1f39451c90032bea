"""Multicore match service (layer-1/layer-2 split): the shared-memory
window ring, the wire codec, and the worker<->service protocol.

The correctness anchor is the REFEREE PROPERTY: a worker's windows
served by the shared service must be bit-identical to the same windows
served by a plain single-process ``MatchEngine`` — under sub/unsub
churn, rule fids, shared subscriptions, injected faults on every
``multicore.*`` failpoint seam, ring exhaustion, service crash, and
service restart.  Any ring trouble may change the PATH (svc →
host-fallback) but never the RESULT, and never leaks a ring slot.

Plus the hostile-schedule regressions for the handoff seams (racesim):
a late doorbell after a worker re-hello superseded its connection, a
service stop racing an in-flight window, and the resume-shard
invariant (a foreign-shard worker never checkpoints) under
disconnect/reconnect interleaving.
"""

import asyncio
import itertools
import os
import random
import socket
import threading
import time

import numpy as np
import pytest

from emqx_tpu import failpoints as fp
from emqx_tpu.broker import shmring
from emqx_tpu.broker.broker import Broker
from emqx_tpu.broker.matchclient import ServiceMatchEngine
from emqx_tpu.broker.multicore import PortReservation, free_ports
from emqx_tpu.broker.resume import shard_of
from emqx_tpu.broker.session import SubOpts
from emqx_tpu.config import BrokerConfig
from emqx_tpu.engine import MatchEngine
from emqx_tpu.message import Message
from emqx_tpu.ops import matchsvc as wire
from emqx_tpu.ops.matchsvc import MatchService
from tools.racesim import run_seeds


@pytest.fixture(autouse=True)
def _clean_failpoints():
    fp.clear()
    yield
    fp.clear()


def wait_until(cond, timeout=10.0, what="condition"):
    t0 = time.monotonic()
    while not cond():
        assert time.monotonic() - t0 < timeout, f"timeout: {what}"
        time.sleep(0.01)


# ------------------------------------------------- in-process service

class SvcThread:
    """A real `MatchService` on a real unix socket, its event loop in
    a daemon thread — so the thread-based `ServiceMatchEngine` client
    talks to it exactly as a worker process would, without spawning
    processes (the cth-cluster pattern one layer down)."""

    def __init__(self, socket_path, engine_kw=None):
        self.socket_path = socket_path
        self.engine_kw = engine_kw
        self.svc = None
        self._loop = None
        self._stop_ev = None
        self._started = threading.Event()
        self._thread = threading.Thread(target=self._run, daemon=True)

    def _run(self):
        asyncio.run(self._main())

    async def _main(self):
        self._loop = asyncio.get_running_loop()
        self._stop_ev = asyncio.Event()
        self.svc = MatchService(
            self.socket_path, use_device=False,
            engine_kw=self.engine_kw,
        )
        await self.svc.start()
        self._started.set()
        await self._stop_ev.wait()
        await self.svc.stop()

    def start(self):
        if os.path.exists(self.socket_path):
            os.unlink(self.socket_path)
        self._thread.start()
        assert self._started.wait(10), "service failed to start"
        return self

    def stop(self):
        loop = self._loop
        if loop is not None and loop.is_running():
            loop.call_soon_threadsafe(self._stop_ev.set)
        self._thread.join(10)
        assert not self._thread.is_alive(), "service thread hung"


def _attach_engine(sock, **kw):
    kw.setdefault("reconnect_backoff", 0.05)
    eng = ServiceMatchEngine(sock, worker_id=0, **kw)
    wait_until(lambda: eng.attached, what="client attach")
    return eng


def _match_via(eng, topics):
    """One window through the submit/finish pipeline (the executor-
    thread path the broker batcher drives), returning (result, path)."""
    info = {}
    pending = eng.match_batch_submit(topics)
    out = eng.match_batch_finish(pending, info=info)
    return out, info.get("path", pending[0])


# ------------------------------------------------------ ring + ports

def test_port_reservation_holds_ports_until_release():
    """The TOCTOU fix: a reserved port stays BOUND (a rival bind
    fails) until its owner's release, then binds cleanly."""
    res = PortReservation(2)
    try:
        port = res.ports[0]
        rival = socket.socket()
        with pytest.raises(OSError):
            rival.bind(("127.0.0.1", port))
        rival.close()
        res.release(port)
        owner = socket.socket()
        owner.setsockopt(socket.SOL_SOCKET, socket.SO_REUSEADDR, 1)
        owner.bind(("127.0.0.1", port))  # the worker's real bind
        owner.close()
        assert len(set(res.ports)) == 2
    finally:
        res.release_all()
    # the compatibility probe still hands back distinct ports
    ports = free_ports(3)
    assert len(set(ports)) == 3


def test_ring_acquire_release_and_full():
    ring = shmring.WindowRing.create(slots=2, slot_bytes=4096)
    try:
        a, b = ring.acquire(), ring.acquire()
        assert {a, b} == {0, 1}
        with pytest.raises(shmring.RingFull):
            ring.acquire()
        ring.release(a)
        ring.release(a)  # double release is idempotent
        assert ring.free_slots() == 1
        assert ring.acquire() == a
        ring.release(a)
        ring.release(b)
    finally:
        ring.close()


def test_ring_write_read_roundtrip_and_stale_rejection():
    ring = shmring.WindowRing.create(slots=2, slot_bytes=4096)
    try:
        n = ring.write(0, epoch=3, seq=7, kind=shmring.KIND_MATCH_REQ,
                       parts=(b"abc", b"def"))
        assert n == 6
        kind, payload = ring.read(0, 3, 7)
        assert kind == shmring.KIND_MATCH_REQ and payload == b"abcdef"
        # a stale (epoch, seq) — a dead incarnation's leftover — is
        # rejected, never misread as the current window's response
        assert ring.read(0, 2, 7) is None
        assert ring.read(0, 3, 8) is None
        with pytest.raises(ValueError):
            ring.write(0, 3, 8, shmring.KIND_MATCH_REQ,
                       (b"x" * (ring.payload_capacity + 1),))
    finally:
        ring.close()


def test_ring_attach_sees_owner_writes():
    owner = shmring.WindowRing.create(slots=4, slot_bytes=4096)
    try:
        svc_side = shmring.WindowRing.attach(owner.name)
        assert (svc_side.slots, svc_side.slot_bytes) == (4, 4096)
        owner.write(2, 1, 5, shmring.KIND_MATCH_REQ, (b"hello",))
        assert svc_side.read(2, 1, 5) == (shmring.KIND_MATCH_REQ,
                                          b"hello")
        # response written back through the attached side, same slot
        svc_side.write(2, 1, 5, shmring.KIND_MATCH_RESP, (b"resp",))
        assert owner.read(2, 1, 5) == (shmring.KIND_MATCH_RESP, b"resp")
        svc_side.close()
    finally:
        owner.close()


# ------------------------------------------------------- wire codec

def test_wire_match_roundtrip():
    topics = ["a/b", "", "x/" + "y" * 300, "ünï/ço∂é"]
    payload = b"".join(wire.pack_match_req(topics, True))
    assert wire.unpack_match_req(payload) == (topics, True)

    id_sets = [[3, 1, 2], [], [7], list(range(50))]
    resp = b"".join(wire.pack_match_resp(id_sets))
    rows = wire.unpack_match_resp(resp)
    assert [sorted(int(x) for x in r) for r in rows] == [
        sorted(s) for s in id_sets
    ]


def test_wire_decide_roundtrip():
    rng = np.random.default_rng(0)
    r, n, b = 16, 40, 8
    cols = (
        rng.integers(0, 3, r).astype(np.int8),
        rng.random(r) < 0.3, rng.random(r) < 0.3, rng.random(r) < 0.1,
    )
    rows = (
        rng.integers(0, r, n).astype(np.int64),
        rng.integers(0, 50, n).astype(np.int64),
        rng.integers(0, b, n).astype(np.int64),
        rng.integers(0, 3, b).astype(np.int8),
        rng.random(b) < 0.5,
        rng.integers(-1, 50, b).astype(np.int32),
    )
    for send_cols in (cols, None):
        payload = b"".join(wire.pack_decide_req(send_cols, 9, *rows))
        got = wire.unpack_decide_req(payload)
        if send_cols is None:
            assert got[0] is None
        else:
            for mine, theirs in zip(cols, got[0]):
                np.testing.assert_array_equal(np.asarray(mine),
                                              np.asarray(theirs))
        assert got[1] == 9
        for mine, theirs in zip(rows, got[2:]):
            np.testing.assert_array_equal(np.asarray(mine),
                                          np.asarray(theirs))

    packed = rng.integers(0, 255, n).astype(np.uint8)
    for path in ("dev", "host"):
        out, p = wire.unpack_decide_resp(
            b"".join(wire.pack_decide_resp(packed, path))
        )
        np.testing.assert_array_equal(out, packed)
        assert p == path


# ----------------------------------------- the referee property

_FILTERS = ["t/#", "t/+/x", "t/1/x", "s/only", "$share/g1/t/+/x",
            "a/b/c", "a/+/c", "a/#", "+/b/#", "deep/" + "l/" * 8 + "#"]
_TOPICS = ["t/1/x", "t/2/x", "s/only", "a/b/c", "a/z/c", "q/b/r",
           "deep/" + "l/" * 8 + "end", "none/of/these", "t/zzz"]


def _random_churn(eng, referee, rng, rounds):
    """Apply the same random sub/unsub churn (client fids, rule-tuple
    fids, shared subs) to the service-backed engine and the referee."""
    live = []
    for k in range(rounds):
        if live and rng.random() < 0.35:
            fid = live.pop(rng.randrange(len(live)))
            assert eng.delete(fid) == referee.delete(fid)
        else:
            flt = rng.choice(_FILTERS)
            fid = (("rule", f"r{k}", 0) if rng.random() < 0.2
                   else f"c{k}")
            eng.insert(flt, fid)
            referee.insert(flt, fid)
            live.append(fid)
    return live


def test_service_match_bit_identical_to_referee(tmp_path):
    """THE acceptance gate: sharded dispatch through the service is
    bit-identical to the single-process referee, across random churn,
    with every undisturbed window actually served by the service."""
    sock = str(tmp_path / "svc.sock")
    svc = SvcThread(sock).start()
    eng = _attach_engine(sock)
    referee = MatchEngine(use_device=False)
    rng = random.Random(4242)
    try:
        for _ in range(8):
            _random_churn(eng, referee, rng, rounds=12)
            topics = [rng.choice(_TOPICS) for _ in range(6)]
            out, path = _match_via(eng, topics)
            assert path == "svc"
            assert out == referee.match_batch(topics)
            # the loop-thread sync path stays pinned to the mirror
            # and agrees too
            assert eng.match_batch(topics) == referee.match_batch(topics)
        assert eng.svc_stats["windows"] == 8
        assert eng.svc_stats["fallbacks"] == 0
        assert eng._ring.free_slots() == eng._ring.slots
    finally:
        eng.close()
        svc.stop()


def test_route_delete_propagates_to_service(tmp_path):
    sock = str(tmp_path / "svc.sock")
    svc = SvcThread(sock).start()
    eng = _attach_engine(sock)
    try:
        eng.insert("gone/#", "g1")
        eng.insert("kept/#", "k1")
        out, path = _match_via(eng, ["gone/x", "kept/x"])
        assert path == "svc" and out == [{"g1"}, {"k1"}]
        assert eng.delete("g1")
        out, path = _match_via(eng, ["gone/x", "kept/x"])
        assert path == "svc" and out == [set(), {"k1"}]
        # deleting again reports absent on both sides
        assert not eng.delete("g1")
    finally:
        eng.close()
        svc.stop()


def test_decide_over_ring_bit_identical(tmp_path):
    """The decide kernel through the ring (cols shipped on first rev,
    cache-hit on the second window) equals the local referee."""
    sock = str(tmp_path / "svc.sock")
    svc = SvcThread(sock).start()
    eng = _attach_engine(sock)
    referee = MatchEngine(use_device=False)
    rng = np.random.default_rng(7)
    r, n, b = 32, 200, 16
    cols = (
        rng.integers(0, 3, r).astype(np.int8),
        rng.random(r) < 0.3, rng.random(r) < 0.3, rng.random(r) < 0.1,
    )
    try:
        for i in range(2):  # window 2 exercises the cols cache hit
            args = (
                rng.integers(0, r, n), rng.integers(0, 50, n),
                rng.integers(0, b, n),
                rng.integers(0, 3, b).astype(np.int8),
                rng.random(b) < 0.5,
                rng.integers(-1, 50, b).astype(np.int32),
            )
            got = eng._ring_decide(cols, 5, *args)
            assert got is not None, f"ring decide window {i} fell back"
            want, _ = referee.decide_window(cols, 5, *args)
            np.testing.assert_array_equal(got[0], want)
        assert eng.svc_stats["decides"] == 2
        assert eng._cols_sent_rev == 5
        assert eng._ring.free_slots() == eng._ring.slots
    finally:
        eng.close()
        svc.stop()


# -------------------------------------------- chaos: failpoint seams

def test_submit_seam_drop_falls_back_bit_identical(tmp_path):
    sock = str(tmp_path / "svc.sock")
    svc = SvcThread(sock).start()
    eng = _attach_engine(sock)
    referee = MatchEngine(use_device=False)
    try:
        _random_churn(eng, referee, random.Random(1), rounds=10)
        fp.configure("multicore.ring.submit", "drop")
        pending = eng.match_batch_submit(_TOPICS)
        assert pending[0] != "svc"  # window degraded at submit
        assert eng.match_batch_finish(pending) == \
            referee.match_batch(_TOPICS)
        assert eng._ring.free_slots() == eng._ring.slots
        fp.clear()
        _, path = _match_via(eng, _TOPICS)  # seam disarmed: svc again
        assert path == "svc"
    finally:
        eng.close()
        svc.stop()


@pytest.mark.parametrize("completion_first", [False, True])
def test_complete_seam_error_falls_back_without_slot_leak(
    tmp_path, completion_first
):
    """An injected completion fault degrades the window to the mirror
    AND quarantines-then-drains its slot: the late completion from the
    (healthy) service returns it to the free list — and one that had
    arrived before the fault frees it at once (no later doorbell would
    come to drain a quarantine)."""
    sock = str(tmp_path / "svc.sock")
    svc = SvcThread(sock).start()
    eng = _attach_engine(sock)
    referee = MatchEngine(use_device=False)
    try:
        _random_churn(eng, referee, random.Random(2), rounds=10)
        fp.configure("multicore.ring.complete", "error")
        info = {}
        pending = eng.match_batch_submit(_TOPICS)
        assert pending[0] == "svc"  # submit succeeded; completion fails
        if completion_first:
            wait_until(lambda: pending[1][1] in eng._done,
                       what="completion before finish")
        out = eng.match_batch_finish(pending, info=info)
        assert info["path"] == "host-fallback"
        assert out == referee.match_batch(_TOPICS)
        assert eng.svc_stats["fallbacks"] == 1
        fp.clear()
        # the service still served the window; its late completion
        # doorbell releases the quarantined slot — no leak
        wait_until(
            lambda: eng._ring.free_slots() == eng._ring.slots,
            what="abandoned slot drained by late completion",
        )
        _, path = _match_via(eng, _TOPICS)
        assert path == "svc"
    finally:
        eng.close()
        svc.stop()


def test_ring_full_degrades_window_in_process(tmp_path):
    sock = str(tmp_path / "svc.sock")
    svc = SvcThread(sock).start()
    eng = _attach_engine(sock)
    try:
        eng.insert("t/#", "c0")
        held = [eng._ring.acquire() for _ in range(eng._ring.slots)]
        out, path = _match_via(eng, ["t/x"])
        assert path != "svc" and out == [{"c0"}]
        assert eng.svc_stats["ring_full"] >= 1
        for s in held:
            eng._ring.release(s)
        out, path = _match_via(eng, ["t/x"])
        assert path == "svc" and out == [{"c0"}]
    finally:
        eng.close()
        svc.stop()


def test_oversize_window_degrades_in_process(tmp_path):
    sock = str(tmp_path / "svc.sock")
    svc = SvcThread(sock).start()
    eng = _attach_engine(sock, ring_slot_bytes=2048)
    try:
        eng.insert("big/#", "c0")
        topics = ["big/" + "x" * 200 for _ in range(40)]  # > slot
        out, path = _match_via(eng, topics)
        assert path != "svc"
        assert out == [{"c0"}] * len(topics)
        assert eng._ring.free_slots() == eng._ring.slots
    finally:
        eng.close()
        svc.stop()


# ------------------------------------- service crash / restart loop

def test_service_crash_fallback_then_reattach(tmp_path):
    """The availability story end-to-end: service dies → every window
    still served correctly from the mirror; service returns → client
    re-attaches, REPLAYS its full route set (including churn applied
    while detached), and serves via the service again."""
    sock = str(tmp_path / "svc.sock")
    svc = SvcThread(sock).start()
    eng = _attach_engine(sock)
    referee = MatchEngine(use_device=False)
    rng = random.Random(3)
    try:
        _random_churn(eng, referee, rng, rounds=10)
        _, path = _match_via(eng, _TOPICS)
        assert path == "svc"

        svc.stop()  # crash
        wait_until(lambda: not eng.attached, what="detach on EOF")
        # churn lands ONLY on the mirror while detached — the replay
        # must carry it to the next incarnation
        _random_churn(eng, referee, rng, rounds=10)
        out, path = _match_via(eng, _TOPICS)
        assert path != "svc"
        assert out == referee.match_batch(_TOPICS)

        svc2 = SvcThread(sock).start()
        try:
            wait_until(lambda: eng.attached, what="re-attach")
            out, path = _match_via(eng, _TOPICS)
            assert path == "svc"
            assert out == referee.match_batch(_TOPICS)
            assert eng.svc_stats["reconnects"] >= 2
            assert eng._ring.free_slots() == eng._ring.slots
        finally:
            svc2.stop()
    finally:
        eng.close()


def test_restart_during_inflight_window(tmp_path):
    """The hostile handoff: the doorbell is lost (swallowed send), the
    service dies while the window waits — the window must degrade to
    the mirror and the slot must come back when the incarnation
    provably dies (EOF detach), never leaking."""
    sock = str(tmp_path / "svc.sock")
    svc = SvcThread(sock).start()
    eng = _attach_engine(sock, rpc_timeout=30.0)
    referee = MatchEngine(use_device=False)
    try:
        _random_churn(eng, referee, random.Random(5), rounds=8)
        eng._send = lambda obj: True  # doorbell eaten by the "crash"
        pending = eng.match_batch_submit(_TOPICS)
        assert pending[0] == "svc"
        killer = threading.Timer(0.3, svc.stop)
        killer.start()
        info = {}
        out = eng.match_batch_finish(pending, info=info)
        killer.join()
        assert info["path"] == "host-fallback"
        assert out == referee.match_batch(_TOPICS)
        wait_until(lambda: eng._ring.free_slots() == eng._ring.slots,
                   what="in-flight slot released on detach")
    finally:
        eng.close()


def test_timeout_quarantines_slot_then_reattach_drains(tmp_path):
    """A timed-out window QUARANTINES its slot (a hung service may
    still write there) instead of freeing it; the next epoch bump
    proves the old incarnation dead and drains the quarantine."""
    sock = str(tmp_path / "svc.sock")
    svc = SvcThread(sock).start()
    eng = _attach_engine(sock, rpc_timeout=0.2)
    referee = MatchEngine(use_device=False)
    try:
        _random_churn(eng, referee, random.Random(6), rounds=8)
        eng._send = lambda obj: True  # service never hears the bell
        info = {}
        out = eng.match_batch_finish(
            eng.match_batch_submit(_TOPICS), info=info
        )
        assert info["path"] == "host-fallback"
        assert out == referee.match_batch(_TOPICS)
        # the slot is quarantined, NOT freed: the service (which this
        # client cannot prove dead) may still write there
        assert eng._ring.free_slots() == eng._ring.slots - 1
        with eng._lk:
            assert len(eng._abandoned) == 1

        svc.stop()  # EOF: incarnation provably dead → quarantine drains
        wait_until(lambda: eng._ring.free_slots() == eng._ring.slots,
                   what="quarantine drained")
    finally:
        eng.close()


# ------------------------------------------- broker-level chaos

def _broker_with_service(sock):
    cfg = BrokerConfig()
    cfg.engine.use_device = False
    cfg.multicore.service_socket = sock
    cfg.multicore.worker_id = 0
    cfg.multicore.n_workers = 1
    return Broker(config=cfg)


class FakeChannel:
    def __init__(self):
        self.sent = []
        self.closed = None

    def send_packets(self, pkts):
        self.sent.extend(pkts)

    def close(self, reason):
        self.closed = reason


def test_broker_delivers_through_service_and_through_faults(tmp_path):
    """A worker Broker wired to the service delivers identically with
    the service healthy, with every multicore seam erroring, and with
    the service gone — the CPU-fallback acceptance invariant."""
    sock = str(tmp_path / "svc.sock")
    svc = SvcThread(sock).start()
    b = _broker_with_service(sock)
    eng = b.router.engine
    assert isinstance(eng, ServiceMatchEngine)
    wait_until(lambda: eng.attached, what="broker engine attach")
    try:
        for i in range(4):
            ch = FakeChannel()
            s, _ = b.cm.open_session(True, f"c{i}", ch)
            opts = SubOpts(qos=1)
            s.subscribe(f"mc/{i}/#", opts)
            b.subscribe(f"c{i}", f"mc/{i}/#", opts)

        def publish_all():
            return b.publish_many([
                Message(topic=f"mc/{i}/v", qos=1, payload=b"d")
                for i in range(4)
            ])

        assert publish_all() == [1] * 4  # healthy: via the service
        assert eng.svc_stats["windows"] >= 1

        fp.configure("multicore.ring.submit", "error")
        assert publish_all() == [1] * 4  # seam error: host fallback
        fp.clear()
        fp.configure("multicore.ring.complete", "error")
        assert publish_all() == [1] * 4
        fp.clear()

        svc.stop()  # service gone entirely
        wait_until(lambda: not eng.attached, what="detach")
        assert publish_all() == [1] * 4

        svc2 = SvcThread(sock).start()
        try:
            wait_until(lambda: eng.attached, what="re-attach")
            before = eng.svc_stats["windows"]
            assert publish_all() == [1] * 4
            assert eng.svc_stats["windows"] > before
            info = b.node_info()
            assert info["multicore"]["service"]["attached"] is True
        finally:
            svc2.stop()
    finally:
        b.shutdown()  # also closes the engine + unlinks the ring


# --------------------------------------------- resume shard homes

def test_shard_of_is_stable_and_covers_all_shards():
    # cross-process stability is the point: pin the exact hash rule
    import zlib

    for cid in ("veh-1", "ünïcode", ""):
        assert shard_of(cid, 4) == \
            zlib.crc32(cid.encode("utf-8")) % 4
    assert shard_of("anything", 1) == 0
    assert shard_of("anything", 0) == 0
    hit = {shard_of(f"client-{i}", 4) for i in range(200)}
    assert hit == {0, 1, 2, 3}


def _durable_cfg(data_dir, shard_index=0, shard_count=1):
    cfg = BrokerConfig()
    cfg.engine.use_device = False
    cfg.durable.enable = True
    cfg.durable.data_dir = str(data_dir)
    cfg.durable.resume.shard_index = shard_index
    cfg.durable.resume.shard_count = shard_count
    return cfg


def _connect_durable(b, cid):
    ch = FakeChannel()
    s, _ = b.cm.open_session(False, cid, ch, expiry_interval=3600.0)
    opts = SubOpts(qos=1)
    s.subscribe("t/#", opts)
    b.subscribe(cid, "t/#", opts)
    return ch


def test_foreign_shard_worker_never_checkpoints(tmp_path):
    """Split-brain prevention: only the client's home shard writes its
    checkpoint; a foreign-shard worker counts + skips, so no two
    workers ever hold rival checkpoints for one client."""
    cid = "veh-1"
    home = shard_of(cid, 2)
    b = Broker(config=_durable_cfg(tmp_path / "w_foreign",
                                   shard_index=1 - home, shard_count=2))
    ch = _connect_durable(b, cid)
    assert not b.resume_home_shard(cid)
    b.cm.disconnect(cid, ch)
    b.channel_disconnected(cid)
    assert not os.path.exists(b.durable._state_path(cid))
    assert b.metrics.val("session.resume.foreign_shard") == 1
    b.durable.close()

    b2 = Broker(config=_durable_cfg(tmp_path / "w_home",
                                    shard_index=home, shard_count=2))
    ch2 = _connect_durable(b2, cid)
    assert b2.resume_home_shard(cid)
    b2.cm.disconnect(cid, ch2)
    b2.channel_disconnected(cid)
    assert os.path.exists(b2.durable._state_path(cid))
    assert b2.metrics.val("session.resume.foreign_shard") == 0
    b2.durable.close()


# --------------------------------------- racesim: handoff seams

class _StubWriter:
    def __init__(self):
        self.lines = []

    def write(self, data):
        self.lines.append(data)

    def close(self):
        pass


def _supersede_workload():
    """A worker re-hellos (service restarted from ITS point of view)
    while a doorbell from the superseded connection is still in
    flight: the late doorbell must degrade to an error completion,
    never touch the closed ring, and the new incarnation must win."""

    async def main():
        svc = MatchService("unused.sock", use_device=False)
        r1 = shmring.WindowRing.create(slots=2, slot_bytes=4096)
        r2 = shmring.WindowRing.create(slots=2, slot_bytes=4096)
        try:
            w_old = await svc._handle_hello(
                {"worker": 0, "epoch": 1, "ring": r1.name},
                _StubWriter(),
            )
            svc._apply_routes(w_old, [[0, "t/#"]], ())
            slot = r1.acquire()
            r1.write(slot, 1, 1, shmring.KIND_MATCH_REQ,
                     wire.pack_match_req(["t/x"], False))

            async def supersede():
                await asyncio.sleep(0)
                await svc._handle_hello(
                    {"worker": 0, "epoch": 2, "ring": r2.name},
                    _StubWriter(),
                )

            async def late_doorbell():
                await asyncio.sleep(0)
                out = svc._serve_window(w_old, slot, 1)
                assert out["t"] in ("c", "e")

            await asyncio.gather(supersede(), late_doorbell())
            assert svc._workers[0].epoch == 2
            # the superseded connection's routes were dropped with it;
            # only worker-0 state from the LIVE incarnation remains
            assert svc._workers[0].fids == set()
        finally:
            for w in list(svc._workers.values()):
                svc._drop_worker(w)
            r1.close()
            r2.close()

    return main()


def test_race_late_doorbell_after_supersede():
    for o in run_seeds(_supersede_workload, seeds=range(12)):
        assert not o.failed, (o.label, o.error)


def _stop_race_workload():
    """`MatchService.stop` racing an in-flight window: whatever the
    interleaving, the window completes or errors cleanly and stop
    leaves the service empty (no routes, no workers, rings closed)."""

    async def main():
        svc = MatchService("unused.sock", use_device=False)
        ring = shmring.WindowRing.create(slots=2, slot_bytes=4096)
        try:
            w = await svc._handle_hello(
                {"worker": 0, "epoch": 1, "ring": ring.name},
                _StubWriter(),
            )
            svc._apply_routes(w, [[0, "a/#"], [1, "b/#"]], ())
            slot = ring.acquire()
            ring.write(slot, 1, 1, shmring.KIND_MATCH_REQ,
                       wire.pack_match_req(["a/x", "b/y"], False))

            async def serve():
                await asyncio.sleep(0)
                out = svc._serve_window(w, slot, 1)
                assert out["t"] in ("c", "e")

            async def stop():
                await asyncio.sleep(0)
                await svc.stop()

            await asyncio.gather(serve(), stop())
            assert not svc._workers
            assert len(svc.engine) == 0
        finally:
            ring.close()

    return main()


def test_race_stop_during_inflight_window():
    for o in run_seeds(_stop_race_workload, seeds=range(12)):
        assert not o.failed, (o.label, o.error)


_shard_dirs = itertools.count()


def _shard_rebalance_workload(base_dir):
    """Disconnect-checkpoint racing a takeover reconnect on a FOREIGN
    shard worker: under every interleaving the foreign worker must
    never write a checkpoint (the home worker owns the one canonical
    copy)."""
    cid = "veh-race"
    foreign = 1 - shard_of(cid, 2)

    async def main():
        data_dir = os.path.join(base_dir, f"run{next(_shard_dirs)}")
        b = Broker(config=_durable_cfg(data_dir, shard_index=foreign,
                                       shard_count=2))
        try:
            ch = _connect_durable(b, cid)

            async def disconnect():
                await asyncio.sleep(0)
                b.cm.disconnect(cid, ch)
                await asyncio.sleep(0)
                b.channel_disconnected(cid)

            async def takeover():
                await asyncio.sleep(0)
                ch2 = FakeChannel()
                b.cm.open_session(False, cid, ch2,
                                  expiry_interval=3600.0)

            await asyncio.gather(disconnect(), takeover())
            assert not os.path.exists(b.durable._state_path(cid))
        finally:
            b.durable.close()

    return main()


def test_race_foreign_shard_disconnect_vs_takeover(tmp_path):
    outs = run_seeds(lambda: _shard_rebalance_workload(str(tmp_path)),
                     seeds=range(10))
    for o in outs:
        assert not o.failed, (o.label, o.error)


# ---------------------------------------------- merged nodes view

def test_node_info_carries_multicore_and_shard_surface(tmp_path):
    cfg = _durable_cfg(tmp_path / "ds", shard_index=1, shard_count=3)
    cfg.multicore.n_workers = 3
    cfg.multicore.worker_id = 1
    b = Broker(config=cfg)
    info = b.node_info()
    assert info["node_status"] == "running"
    assert info["multicore"] == {"worker_id": 1, "n_workers": 3}
    assert "durability" in info
    import json as _json

    _json.dumps(info)  # JSON-safe for the mgmt surface
    b.durable.close()


def test_merged_nodes_view_across_cluster(tmp_path):
    """ANY worker's api answers for the whole pool: its /api/v5/nodes
    row set carries every peer's node_info over the cluster RPC."""
    from emqx_tpu.broker.listener import BrokerServer
    from emqx_tpu.cluster import ClusterNode
    from emqx_tpu.config import ListenerConfig

    async def t():
        servers, nodes = [], []
        try:
            for i in range(2):
                cfg = BrokerConfig()
                cfg.engine.use_device = False
                cfg.listeners = [ListenerConfig(port=0)]
                cfg.node_name = f"worker{i}"
                cfg.multicore.n_workers = 2
                cfg.multicore.worker_id = i
                srv = BrokerServer(cfg)
                await srv.start()
                seeds = [("worker0", "127.0.0.1", nodes[0].port)] \
                    if nodes else []
                node = ClusterNode(
                    f"worker{i}", srv.broker,
                    heartbeat_interval=0.05, down_after=1.0,
                )
                await node.start(seeds=seeds)
                servers.append(srv)
                nodes.append(node)
            deadline = time.monotonic() + 10
            while time.monotonic() < deadline:
                if nodes[0].peers_alive():
                    break
                await asyncio.sleep(0.05)
            rows = [servers[0].broker.node_info()]
            rows += await nodes[0].fetch_node_infos()
            names = {r["node"] for r in rows}
            assert names == {"worker0", "worker1"}
            for r in rows:
                assert r["node_status"] == "running"
                assert r["multicore"]["n_workers"] == 2
            assert {r["multicore"]["worker_id"] for r in rows} == {0, 1}
        finally:
            for node in reversed(nodes):
                await node.stop()
            for srv in reversed(servers):
                await srv.stop()

    asyncio.run(t())
