"""Window decision columns (PR 9): vectorized per-delivery QoS /
no-local / body-slot decisions, fused into the window pipeline.

The referee suite for the three dispatch paths:

  * device-fused   — `engine.decide_force = "dev"` runs the packed
    column through ops.match_kernel.decide_batch (JAX);
  * host-vectorized — `"host"` pins the numpy twin;
  * scalar fallback — `Broker._decide_columns = False` takes the
    pre-columns per-run path (`_dispatch_scalar` → deliver_run_native
    / Session.deliver).

All three must put bit-identical bytes on every connection's wire,
with identical delivery counts, per-qos sent metrics, and (pid, qos)
inflight windows, over random worlds mixing qos / no_local / RAP /
subid / upgrade_qos / v4-v5 / inflight pressure.  Plus: the lazy
delivery-list materialization (zero per-delivery tuples for windows
nobody consumes), the sampled-run tracer guard, the router attribute
columns staying in sync under churn, and the chaos criterion — 100%
device decide failure mid-stream still delivers QoS1 through the PR 1
circuit breaker.
"""

import random

import numpy as np
import pytest

from emqx_tpu import failpoints as fp
from emqx_tpu.broker.broker import Broker
from emqx_tpu.broker.channel import Channel
from emqx_tpu.broker.session import SubOpts
from emqx_tpu.codec import mqtt as C
from emqx_tpu.config import BrokerConfig
from emqx_tpu.message import Message
from emqx_tpu.ops import dispatchasm, match_kernel
from emqx_tpu.router import Router

_native = dispatchasm.load()


def _broker(decide=None, columns=True):
    cfg = BrokerConfig()
    cfg.engine.use_device = False
    b = Broker(config=cfg)
    b._decide_columns = columns
    if decide is not None:
        b.router.engine.decide_force = decide
    return b


class WireChannel(Channel):
    def __init__(self, broker, version=C.MQTT_V5):
        self.writes = []

        def send(pkts):
            self.writes.append(
                b"".join(C.serialize(p, self.version) for p in pkts)
            )

        super().__init__(broker, send=send, close=lambda r: None)
        self.version = version


# ------------------------------------------------ three-path parity

def _build_world(seed):
    rng = random.Random(seed)
    clients = []
    for i in range(12):
        subs = []
        for f in range(rng.randint(1, 3)):
            flt = rng.choice(
                ["t/#", "t/+/x", f"t/{f}/x", "s/only",
                 "$share/g1/t/+/x"]
            )
            subs.append({
                "flt": flt,
                "qos": rng.randint(0, 2),
                "rap": rng.random() < 0.4,
                "no_local": rng.random() < 0.3,
                "subid": rng.randint(1, 9)
                if rng.random() < 0.2 else None,
            })
        clients.append({
            "cid": f"c{i}",
            "version": rng.choice([C.MQTT_V4, C.MQTT_V5]),
            "upgrade": rng.random() < 0.3,
            "max_inflight": rng.choice([2, 4, 32]),
            "subs": subs,
        })
    windows = []
    for _ in range(4):
        win = []
        for _ in range(rng.randint(1, 12)):
            win.append({
                "topic": rng.choice(
                    ["t/1/x", "t/2/x", "t/0/x", "s/only", "t/deep/x"]
                ),
                "qos": rng.randint(0, 2),
                "retain": rng.random() < 0.3,
                "payload": bytes(
                    rng.randrange(256)
                    for _ in range(rng.randint(0, 200))
                ),
                "from": rng.choice(["c0", "c1", "pub"]),
            })
        windows.append(win)
    return clients, windows


def _run_world(clients, windows, mode, setup=None, ts=1.0e9):
    b = _broker(
        decide=mode if mode in ("host", "dev") else None,
        columns=mode != "scalar",
    )
    # deterministic shared-group picks so all three runs pick the
    # same member for every message
    b.router.shared._rng.seed(1234)
    chans = {}
    for c in clients:
        ch = WireChannel(b, version=c["version"])
        session, _ = b.cm.open_session(
            True, c["cid"], ch, max_inflight=c["max_inflight"]
        )
        session.upgrade_qos = c["upgrade"]
        # a case's own session state: where the packet-id counter
        # stands, and packet ids already in flight
        session._next_pid = c.get("next_pid", session._next_pid)
        for pid in c.get("held", ()):
            session.inflight.insert(pid, _held_entry())
        for s in c["subs"]:
            opts = SubOpts(
                qos=s["qos"], retain_as_published=s["rap"],
                no_local=s["no_local"], subid=s["subid"],
            )
            session.subscribe(s["flt"], opts)
            b.subscribe(c["cid"], s["flt"], opts)
        chans[c["cid"]] = ch
    if setup is not None:
        setup(b, chans, mode)
    counts = []
    plain = []
    for win in windows:
        msgs = [
            Message(
                topic=w["topic"], qos=w["qos"], retain=w["retain"],
                payload=w["payload"], from_client=w["from"],
                timestamp=ts,
            )
            for w in win
        ]
        counts.append(b.publish_many(msgs))
        rec = b.profiler.windows(1)[0]
        plain.append((rec["n_clients_plain"], rec["n_clients"]))
    wires = {
        cid: b"".join(bytes(x) for x in ch.writes)
        for cid, ch in chans.items()
    }
    sent = {
        k: b.metrics.val(k)
        for k in ("messages.sent", "messages.qos0.sent",
                  "messages.qos1.sent", "messages.qos2.sent",
                  "packets.publish.sent", "messages.delivered")
    }
    inflights = {
        c["cid"]: sorted(
            (pid, e.qos)
            for pid, e in b.cm.lookup(c["cid"]).inflight.items()
        )
        for c in clients
    }
    stats = b.router.engine.stats()
    board = [(e["clientid"], e["topic"]) for e in b.slow_subs.top()]
    return counts, wires, sent, inflights, stats, plain, board


def _held_entry():
    from emqx_tpu.broker.session import _InflightEntry, _PUBLISHING

    return _InflightEntry(
        _PUBLISHING, Message(topic="held", qos=1), 1, 1.0e9
    )


@pytest.mark.skipif(_native is None, reason="native dispatchasm unavailable")
@pytest.mark.parametrize("seed", [1, 2, 7, 23, 41])
@pytest.mark.parametrize("stamp", ["old", "unstamped"])
def test_three_paths_bit_identical(seed, stamp):
    # an old stamp puts every window past the slow-subs threshold:
    # the board the window's one pass leaves is the one a scan a run
    # leaves (equal latencies: the later delivery stays)
    ts = 1.0e9 if stamp == "old" else 0.0
    clients, windows = _build_world(seed)
    scalar = _run_world(clients, windows, "scalar", ts=ts)
    host = _run_world(clients, windows, "host", ts=ts)
    dev = _run_world(clients, windows, "dev", ts=ts)
    assert sum(p for p, _ in host[5]) > 0 and host[5] == dev[5]
    assert scalar[6] == host[6] == dev[6]
    assert len(scalar[6]) == (10 if stamp == "old" else 0)
    for other, label in ((host, "host"), (dev, "dev")):
        assert scalar[0] == other[0], (label, "counts")
        for cid in scalar[1]:
            assert scalar[1][cid] == other[1][cid], (label, cid)
        assert scalar[2] == other[2], (label, "sent metrics")
        assert scalar[3] == other[3], (label, "inflight")
    # the pinned paths really ran where they claim
    assert host[4]["decide_host_windows"] > 0
    assert host[4]["decide_dev_windows"] == 0
    assert dev[4]["decide_dev_windows"] > 0
    # and the parity run exercised every decoded byte stream
    for cid, wire in dev[1].items():
        version = next(
            c["version"] for c in clients if c["cid"] == cid
        )
        for pkt in C.StreamParser(version=version).feed(wire):
            assert pkt.type == C.PUBLISH


def _client(cid, flt="t/#", qos=1, **kw):
    sub = {"flt": flt, "qos": qos, "rap": False, "no_local": False,
           "subid": None}
    sub.update({k: kw.pop(k) for k in list(kw) if k in sub})
    return {"cid": cid, "version": C.MQTT_V5, "upgrade": False,
            "max_inflight": 32, "subs": [sub], **kw}


def _window(n, qos=1, frm="pub", retain=False):
    return [{"topic": f"t/{i}", "qos": qos, "retain": retain,
             "payload": b"p%d" % i, "from": frm} for i in range(n)]


def _closes_midway(b, chans, mode):
    """`b1`'s channel starts closing while the window is dispatched:
    in the columns path when the LAST run corks (b1 is planned by
    then, the splice has not run), in the scalar path on b1's own
    cork (before its one write): the same wire either way."""
    victim = chans["b1"]
    trigger = chans["b2" if mode != "scalar" else "b1"]
    real = trigger.cork

    def cork():
        victim._closing = True
        real()

    trigger.cork = cork


# what the window's columnar pass newly decides, each held to the
# scalar referee: (clients, windows, set-up, plain runs of n a window)
_PASS_WORLDS = {
    # both protocol versions planned in one splice: one key_slots a
    # version over that version's rows
    "v4_and_v5": (
        [_client("a0", version=C.MQTT_V4), _client("a1"),
         _client("a2", version=C.MQTT_V4, qos=0), _client("a3", qos=2)],
        [_window(5, qos=2, retain=True), _window(3)],
        None, [(4, 4), (4, 4)],
    ),
    # the id block would pass 65,535: the exact allocator's list
    "pid_wraps": (
        [_client("a0"), _client("a1", next_pid=65533), _client("a2")],
        [_window(6)], None, [(2, 3)],
    ),
    # an id of the block is still in flight: the exact allocator skips
    "pid_collides": (
        [_client("a0"), _client("a1", next_pid=10, held=[13]),
         _client("a2", held=[40])],
        [_window(6), _window(2)], None, [(2, 3), (3, 3)],
    ),
    # no room for the run's four QoS1 deliveries: the per-delivery
    # loop sends two and queues two, beside two plain runs
    "no_room": (
        [_client("a0"), _client("a1", max_inflight=2), _client("a2")],
        [_window(4)], None, [(2, 3)],
    ),
    # a run whose every delivery no-local drops, between plain runs
    "all_dropped": (
        [_client("a0"), _client("a1", no_local=True), _client("a2")],
        [_window(4, frm="a1"), _window(2)], None, [(3, 3), (3, 3)],
    ),
    # a channel that starts closing between plan and splice: its blob
    # is dropped and not counted as sent
    "closing": (
        [_client("b0"), _client("b1"), _client("b2")],
        [_window(3)], _closes_midway, [(3, 3)],
    ),
    # sessions that upgrade and sessions that do not, one window:
    # both QoS variants' columns, entries shared where (message, QoS)
    # agree
    "upgrade_mixed": (
        [_client("a0", qos=2, upgrade=True), _client("a1", qos=0),
         _client("a2", qos=0, upgrade=True), _client("a3", qos=2),
         _client("a4", qos=1, upgrade=True)],
        [_window(4, qos=1), _window(3, qos=0), _window(3, qos=2)],
        None, [(5, 5)] * 3,
    ),
}


@pytest.mark.skipif(_native is None, reason="native dispatchasm unavailable")
@pytest.mark.parametrize("case", sorted(_PASS_WORLDS))
def test_columnar_pass_worlds_match_scalar(case):
    clients, windows, setup, want_plain = _PASS_WORLDS[case]
    scalar = _run_world(clients, windows, "scalar", setup)
    for mode in ("host", "dev"):
        other = _run_world(clients, windows, mode, setup)
        assert scalar[6] == other[6] != [], (mode, "slow-subs board")
        assert scalar[0] == other[0], (mode, "counts")
        for cid in scalar[1]:
            assert scalar[1][cid] == other[1][cid], (mode, cid)
        assert scalar[2] == other[2], (mode, "sent metrics")
        assert scalar[3] == other[3], (mode, "inflight")
        assert other[5] == want_plain, (mode, "plain runs")
    assert any(scalar[1].values()) and any(scalar[3].values())
    if case == "closing":
        assert scalar[1]["b1"] == b"" and scalar[2]["messages.sent"] == 6
        assert scalar[0] == [[3, 3, 3]]  # counted as delivered, as ever
    if case == "pid_wraps":
        assert [p for p, _ in scalar[3]["a1"]] == [
            1, 2, 3, 4, 65534, 65535
        ]
    if case == "pid_collides":
        assert [p for p, _ in scalar[3]["a1"]][:4] == [11, 12, 13, 14]
    if case == "no_room":
        assert len(scalar[3]["a1"]) == 2


def test_decide_kernel_twins_bit_identical():
    """decide_batch (device) vs decide_batch_host (numpy) over random
    columns, including the padded-bucket path the engine uses."""
    rng = np.random.default_rng(7)
    for _ in range(20):
        r, n, b = 64, int(rng.integers(1, 700)), int(rng.integers(1, 40))
        cols = (
            rng.integers(0, 3, r).astype(np.int8),
            rng.random(r) < 0.3,
            rng.random(r) < 0.4,
            rng.random(r) < 0.2,
        )
        orows = rng.integers(0, r, n)
        crows = rng.integers(0, 100, n)
        midx = rng.integers(0, b, n)
        mq = rng.integers(0, 3, b).astype(np.int8)
        mr = rng.random(b) < 0.5
        mf = rng.integers(-1, 100, b).astype(np.int32)
        host = match_kernel.decide_batch_host(
            *cols, orows, crows, midx, mq, mr, mf
        )
        from emqx_tpu.engine import MatchEngine

        eng = MatchEngine(use_device=False)
        dev = eng._decide_device(
            cols, 0, orows, crows, midx, mq, mr, mf
        )
        assert np.array_equal(host, dev)


# --------------------------------------------- router attribute table

def test_router_opts_columns_track_churn():
    """Random subscribe/refresh/unsubscribe churn (direct + shared):
    the numpy attribute columns must mirror the opts table exactly."""
    rng = random.Random(5)
    r = Router()
    live = {}
    for step in range(400):
        cid = f"c{rng.randrange(8)}"
        flt = rng.choice(
            ["a/#", "b/+", "c/d", "$share/g/a/#", "$share/h/b/+"]
        )
        if (cid, flt) in live and rng.random() < 0.4:
            r.unsubscribe(cid, flt)
            del live[(cid, flt)]
        else:
            opts = SubOpts(
                qos=rng.randint(0, 2),
                no_local=rng.random() < 0.5,
                retain_as_published=rng.random() < 0.5,
                subid=rng.randint(1, 5)
                if rng.random() < 0.3 else None,
            )
            r.subscribe(cid, flt, opts)
            live[(cid, flt)] = opts
    qos, nl, rap, sid = r.opts_columns()
    checked = 0
    for slot, opts in enumerate(r._opts_table):
        if opts is None:
            continue
        checked += 1
        assert qos[slot] == opts.qos
        assert nl[slot] == opts.no_local
        assert rap[slot] == opts.retain_as_published
        assert sid[slot] == (opts.subid is not None)
    assert checked == len(
        [o for o in r._opts_table if o is not None]
    ) and checked > 0


# --------------------------------------------------- lazy deliveries

def _fanout_broker(n=8, qos=1, **kw):
    b = _broker(**kw)
    for i in range(n):
        cid = f"f{i}"
        ch = WireChannel(b)
        s, _ = b.cm.open_session(True, cid, ch)
        s.subscribe("t/#", SubOpts(qos=qos))
        b.subscribe(cid, "t/#", SubOpts(qos=qos))
    return b


def test_no_consumer_materializes_zero_delivery_tuples(monkeypatch):
    """No hook, no batch sink, no tracer: a whole fanout window must
    allocate ZERO per-delivery (msg, opts) tuples."""
    b = _fanout_broker(8)
    calls = []
    orig = Broker._materialize_run

    def spy(msgs, router, sm_l, so_a, k, e):
        calls.append((k, e))
        return orig(msgs, router, sm_l, so_a, k, e)

    monkeypatch.setattr(Broker, "_materialize_run", staticmethod(spy))
    counts = b.publish_many(
        [Message(topic=f"t/{i}", qos=1) for i in range(6)]
    )
    assert counts == [8] * 6
    assert calls == []


def test_delivered_hook_still_gets_per_run_lists():
    """Satellite 1 must not change the hook contract: with a callback
    registered, `message.delivered` fires once per (window, client)
    with the full delivery list."""
    b = _fanout_broker(3)
    got = []
    b.hooks.add(
        "message.delivered",
        lambda cid, ds: got.append((cid, len(ds), ds[0][0].topic)),
    )
    b.publish_many([Message(topic="t/a", qos=0)] * 2)
    assert sorted(got) == [
        ("f0", 2, "t/a"), ("f1", 2, "t/a"), ("f2", 2, "t/a")
    ]


def test_empty_hook_registry_skips_hook_walk(monkeypatch):
    """Satellite 1: with nothing registered, the window never calls
    hooks.run("message.delivered", ...) at all."""
    b = _fanout_broker(4)
    names = []
    orig_run = b.hooks.run

    def spy(name, *a):
        names.append(name)
        return orig_run(name, *a)

    monkeypatch.setattr(b.hooks, "run", spy)
    b.publish_many([Message(topic="t/x", qos=0)] * 3)
    assert "message.delivered" not in names


# ------------------------------- the columnar pass engages, counted

def _exact_broker(n_subs, n_topics, max_inflight=32):
    """``n_subs`` subscribers over ``n_topics`` exact topics (the
    benchmark's two exact deployments in small): QoS1 where every
    subscriber has a topic of its own, QoS 0/1 alternating else."""
    b = _broker()
    for i in range(n_subs):
        cid = f"s{i}"
        ch = WireChannel(b)
        s, _ = b.cm.open_session(True, cid, ch, max_inflight=max_inflight)
        opts = SubOpts(qos=1 if n_topics == n_subs else i // n_topics & 1)
        s.subscribe(f"x/{i % n_topics}", opts)
        b.subscribe(cid, f"x/{i % n_topics}", opts)
    return b


@pytest.mark.skipif(_native is None, reason="native dispatchasm unavailable")
@pytest.mark.parametrize("shape", ["p2p_512", "fanout_4x250", "hook"])
def test_window_is_served_by_the_columnar_pass(shape, monkeypatch):
    """A point-to-point window of 512 runs and a fan-out window of
    4 x 250: every run plain, ONE in-flight entry a (message, QoS),
    ONE `key_slots` a protocol version, and the ring field that
    `deliver_plain_run_pct.flood` reads says so; a window with a
    `message.delivered` hook has no plain run."""
    import importlib.util
    import json
    import os

    from emqx_tpu.broker import session as S

    if shape == "fanout_4x250":
        b = _exact_broker(1000, 4, max_inflight=4096)
        msgs = [Message(topic=f"x/{i % 4}", qos=1) for i in range(64)]
        runs, owed = 1000, 64 * 250
    else:
        b = _exact_broker(512, 512)
        msgs = [Message(topic=f"x/{i}", qos=1) for i in range(512)]
        runs, owed = 512, 512
    if shape == "hook":
        b.hooks.add("message.delivered", lambda cid, ds: None)
    built = []

    class Entry(S._InflightEntry):
        __slots__ = ()

        def __init__(self, phase, msg, qos, ts):
            built.append((id(msg), qos))
            super().__init__(phase, msg, qos, ts)

    monkeypatch.setattr(S, "_InflightEntry", Entry)
    slots = []
    real = C.DispatchEncoder.key_slots
    monkeypatch.setattr(
        C.DispatchEncoder, "key_slots",
        lambda self, msgs, version, keys: slots.append(version)
        or real(self, msgs, version, keys),
    )
    assert sum(b.publish_many(msgs)) == owed
    (rec,) = b.profiler.windows(1)
    assert rec["n_clients"] == runs
    assert rec["n_clients_plain"] == (0 if shape == "hook" else runs)
    assert len(built) == len(set(built)) == len(msgs)
    assert slots == [C.MQTT_V5]
    # the benchmark's reader over this ring, and over a ring of a
    # program from before the field
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    spec = importlib.util.spec_from_file_location(
        "profiler_ratio",
        os.path.join(root, "benchmark", "readers", "profiler_ratio.py"),
    )
    reader = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(reader)
    args = json.load(open(os.path.join(
        root, "benchmark", "metrics", "deliver_plain_run_pct.flood.json"
    )))["args"]
    ring = b.profiler.windows(1)
    assert reader.read({"ring": ring, "window_s": 1.0}, **args) == (
        0.0 if shape == "hook" else 100.0
    )
    old = [{k: v for k, v in r.items() if k != "n_clients_plain"}
           for r in ring]
    assert reader.read({"ring": old, "window_s": 1.0}, **args) is None


# ------------------------------------------- sampled-run tracer guard

def _tracing_broker(rate, n=6, filters=()):
    cfg = BrokerConfig()
    cfg.engine.use_device = False
    cfg.tracing.enable = True
    cfg.tracing.sample_rate = rate
    cfg.tracing.topic_filters = list(filters)
    b = Broker(config=cfg)
    for i in range(n):
        cid = f"f{i}"
        ch = WireChannel(b)
        s, _ = b.cm.open_session(True, cid, ch)
        s.subscribe("t/#", SubOpts(qos=1))
        b.subscribe(cid, "t/#", SubOpts(qos=1))
    return b


def test_unsampled_window_materializes_nothing(monkeypatch):
    """Lifecycle tracing ACTIVE but nothing sampled (rate 0): the
    fanout window still allocates zero per-delivery tuples — the
    OBS601 sampled-guard idiom applied to materialization."""
    b = _tracing_broker(rate=0.0)
    calls = []
    orig = Broker._materialize_run
    monkeypatch.setattr(
        Broker, "_materialize_run",
        staticmethod(lambda *a: calls.append(a) or orig(*a)),
    )
    assert b.lifecycle.active
    counts = b.publish_many(
        [Message(topic=f"t/{i}", qos=1) for i in range(6)]
    )
    assert counts == [6] * 6
    assert calls == []


def test_sampled_message_materializes_only_its_runs(monkeypatch):
    """A pinned-topic sample mid-window materializes the delivery
    lists ONLY for runs that carry the sampled message, and its
    lifecycle span names the delivering clients."""
    b = _tracing_broker(rate=0.0, n=0, filters=["hot/#"])
    # two disjoint subscriber groups: only g* receive the sampled topic
    for i in range(3):
        cid = f"g{i}"
        ch = WireChannel(b)
        s, _ = b.cm.open_session(True, cid, ch)
        s.subscribe("hot/#", SubOpts(qos=1))
        b.subscribe(cid, "hot/#", SubOpts(qos=1))
    for i in range(3):
        cid = f"h{i}"
        ch = WireChannel(b)
        s, _ = b.cm.open_session(True, cid, ch)
        s.subscribe("cold/#", SubOpts(qos=1))
        b.subscribe(cid, "cold/#", SubOpts(qos=1))
    runs = []
    orig = Broker._materialize_run
    monkeypatch.setattr(
        Broker, "_materialize_run",
        staticmethod(lambda *a: runs.append(a[-2:]) or orig(*a)),
    )
    counts = b.publish_many([
        Message(topic="hot/x", qos=1),
        Message(topic="cold/x", qos=1),
    ])
    assert counts == [3, 3]
    # exactly the three hot-subscriber runs materialized (1 delivery
    # each); the three cold runs allocated nothing
    assert len(runs) == 3
    assert all(e - k == 1 for k, e in runs)
    (span,) = b.lifecycle.store.spans()
    assert sorted(span["attrs"]["clients"]) == ["g0", "g1", "g2"]
    assert span["attrs"]["clients_total"] == 3


# --------------------------------------------------- chaos: breaker

@pytest.fixture(autouse=True)
def _clear_failpoints():
    fp.clear()
    yield
    fp.clear()


def test_device_decide_failure_midstream_still_delivers_qos1():
    """Acceptance chaos criterion: 100% device decide failure
    mid-stream — every QoS1 window still delivers (host columns), and
    enough consecutive faults trip the shared PR 1 breaker, after
    which the decide step stops even trying the device."""
    b = _fanout_broker(4, decide="dev")
    eng = b.router.engine
    assert b.publish_many(
        [Message(topic="t/ok", qos=1)] * 2
    ) == [4, 4]
    assert eng.stats()["decide_dev_windows"] >= 1
    trips = []
    eng.on_breaker_trip = lambda info: trips.append(info)
    fp.configure("dispatch.decide.device", "error", prob=1.0)
    for i in range(4):  # breaker_threshold is 3
        assert b.publish_many(
            [Message(topic=f"t/{i}", qos=1)] * 2
        ) == [4, 4]
    stats = eng.stats()
    assert stats["decide_dev_errors"] >= 3
    assert stats["breaker_open"] is True
    assert trips and trips[0]["reason"] == "decide"
    # breaker open: no further device attempts, still delivering
    errs = stats["decide_dev_errors"]
    assert b.publish_many([Message(topic="t/z", qos=1)]) == [4]
    assert eng.stats()["decide_dev_errors"] == errs


# ------------------------------------------------ columns plumbing

def test_columns_path_engages_and_records_decide_stage():
    b = _fanout_broker(4)
    counts = b.publish_many(
        [Message(topic=f"t/{i}", qos=1) for i in range(8)]
    )
    assert counts == [4] * 8
    (win,) = b.profiler.windows(1)
    assert "decide" in win["stages_us"]
    if _native is not None:
        assert "assemble" in win["stages_us"]
    assert b.profiler.summary()["decide"]["count"] >= 1


def test_scalar_env_kill_switch(monkeypatch):
    monkeypatch.setenv("EMQX_TPU_NO_DECIDE", "1")
    cfg = BrokerConfig()
    cfg.engine.use_device = False
    b = Broker(config=cfg)
    assert b._decide_columns is False
    ch = WireChannel(b)
    s, _ = b.cm.open_session(True, "c1", ch)
    s.subscribe("t/#", SubOpts(qos=1))
    b.subscribe("c1", "t/#", SubOpts(qos=1))
    assert b.publish(Message(topic="t/a", qos=1)) == 1
    (win,) = b.profiler.windows(1)
    assert "decide" not in win["stages_us"]


def test_shared_sub_single_delivery_through_columns():
    """One shared group member gets each message; group opts ride the
    interned opts-table slots."""
    b = _broker()
    for cid in ("s1", "s2"):
        ch = WireChannel(b)
        sess, _ = b.cm.open_session(True, cid, ch)
        opts = SubOpts(qos=1)
        sess.subscribe("$share/g/t/#", opts)
        b.subscribe(cid, "$share/g/t/#", opts)
    counts = b.publish_many(
        [Message(topic=f"t/{i}", qos=1) for i in range(10)]
    )
    assert counts == [1] * 10
    total = sum(
        len(b.cm.lookup(cid).inflight) for cid in ("s1", "s2")
    )
    assert total == 10


def test_closing_channel_run_not_counted_as_sent():
    """A channel that started closing mid-window drops its blob; the
    window-level sent flush must not count it (parity with the scalar
    path, which checks _closing before bumping)."""
    b = _fanout_broker(2)
    b.cm.channel("f0")._closing = True
    before = b.metrics.val("messages.sent")
    b.publish_many([Message(topic="t/a", qos=1)])
    assert b.metrics.val("messages.sent") - before == 1
    assert b.metrics.val("messages.qos1.sent") == 1


def test_decide_auto_first_device_window_warms_not_records():
    """Auto policy hygiene: the first device decide window pays the
    JIT compile and must not seed the cost EWMA (which would pin the
    policy to host forever); the second window records."""
    from emqx_tpu.engine import MatchEngine

    eng = MatchEngine(use_device=None)
    rng = np.random.default_rng(3)
    r, n, bsz = 64, 4096, 16
    cols = (
        rng.integers(0, 3, r).astype(np.int8),
        rng.random(r) < 0.3, rng.random(r) < 0.3, rng.random(r) < 0.1,
    )
    args = (
        rng.integers(0, r, n), rng.integers(0, 50, n),
        rng.integers(0, bsz, n),
        rng.integers(0, 3, bsz).astype(np.int8),
        rng.random(bsz) < 0.5,
        rng.integers(-1, 50, bsz).astype(np.int32),
    )
    _, path1 = eng.decide_window(cols, 1, *args)
    assert path1 == "dev"  # unmeasured big window probes the device
    assert eng._dec_dev_us is None  # compile window not recorded
    _, path2 = eng.decide_window(cols, 1, *args)
    assert path2 == "dev"
    assert eng._dec_dev_us is not None


def test_sampled_span_clients_exclude_no_local_drops():
    """The span's delivering-clients list must not name a client whose
    only delivery was no-local-dropped."""
    b = _tracing_broker(rate=0.0, n=0, filters=["hot/#"])
    for cid, nl in (("gx", True), ("gy", False)):
        ch = WireChannel(b)
        s, _ = b.cm.open_session(True, cid, ch)
        opts = SubOpts(qos=1, no_local=nl)
        s.subscribe("hot/#", opts)
        b.subscribe(cid, "hot/#", opts)
    # published BY gx: gx's no_local subscription drops it on gx only
    assert b.publish(Message(topic="hot/x", qos=1, from_client="gx")) == 2
    (span,) = b.lifecycle.store.spans()
    assert span["attrs"]["clients"] == ["gy"]
