"""Route changes in batches (`Router.begin_routes` / `end_routes`,
`MatchEngine.apply_routes`, `delete_many`) held to a plain model: a dict
of live filters matched by `topic.match` alone; the delta fold's
capacity class, which follows the delta and not the base; and the
served broker, whose SUBACK and UNSUBACK leave only once their route
batch is applied."""

import asyncio
import random
import threading
import time

import numpy as np
import pytest

from emqx_tpu import topic as T
from emqx_tpu import tp
from emqx_tpu.broker.listener import BrokerServer
from emqx_tpu.broker.session import SubOpts
from emqx_tpu.codec import mqtt as C
from emqx_tpu.config import BrokerConfig, ListenerConfig
from emqx_tpu.engine import MatchEngine
from emqx_tpu.router import Router

from mqtt_client import TestClient
from tests_fakes import drain_folds

LEVELS = ("a", "b", "c", "d")


class Model:
    """The plain reference: live filters by fid, matched one by one."""

    def __init__(self) -> None:
        self.live = {}

    def match(self, topic):
        return {fid for fid, flt in self.live.items() if T.match(topic, flt)}


def a_filter(rng):
    ws = [rng.choice(LEVELS + ("+",)) for _ in range(rng.randint(1, 4))]
    if rng.random() < 0.3:
        ws.append("#")
    return "/".join(ws)


def a_topic(rng):
    return "/".join(rng.choice(LEVELS) for _ in range(rng.randint(1, 5)))


def agree(eng, model, rng, n=40):
    topics = [a_topic(rng) for _ in range(n)]
    for topic, got in zip(topics, eng.match_batch(topics)):
        assert got == model.match(topic), (topic, sorted(got))


def engine(fold_async):
    eng = MatchEngine(max_levels=8, use_device=True, delta_aut_threshold=16,
                      rebuild_threshold=10**9)
    eng._fold_async = fold_async
    return eng


def step(eng, model, rng, fid_next):
    """One random route change: a batch, a single insert or delete, a
    batch insert or a batch delete."""
    r = rng.random()
    live = list(model.live)
    if r < 0.4:
        gone = rng.sample(live, min(len(live), rng.randint(0, 6)))
        pairs = []
        for _ in range(rng.randint(0, 8)):
            fid = rng.choice(live) if live and rng.random() < 0.2 \
                else next(fid_next)
            if fid not in gone and fid not in dict(
                    (f, 1) for _, f in pairs):
                pairs.append((a_filter(rng), fid))
        eng.apply_routes(pairs, gone)
        for fid in gone:
            del model.live[fid]
        model.live.update({fid: flt for flt, fid in pairs})
    elif r < 0.6:
        fid = next(fid_next)
        flt = a_filter(rng)
        eng.insert(flt, fid)
        model.live[fid] = flt
    elif r < 0.75 and live:
        fid = rng.choice(live)
        assert eng.delete(fid)
        del model.live[fid]
    elif r < 0.9:
        pairs = [(a_filter(rng), next(fid_next)) for _ in range(5)]
        eng.insert_many(pairs)
        model.live.update({fid: flt for flt, fid in pairs})
    else:
        gone = rng.sample(live, min(len(live), 5)) + [-1]
        assert eng.delete_many(gone) == len(gone) - 1
        for fid in gone[:-1]:
            del model.live[fid]


@pytest.mark.parametrize("fold_async", [False, True])
@pytest.mark.parametrize("seed", [1, 2, 3])
def test_batched_and_single_changes_match_the_model(seed, fold_async):
    rng = random.Random(seed)
    eng, model = engine(fold_async), Model()
    fid_next = iter(range(10**6))
    for i in range(400):
        step(eng, model, rng, fid_next)
        if i % 25 == 0:
            agree(eng, model, rng)
    drain_folds(eng)
    agree(eng, model, rng, 200)
    assert eng.stats()["folds"] > 0  # the delta automaton served too


def test_a_batch_applied_while_a_fold_is_in_flight():
    """The fold is pinned before its adoption while a batch deletes some
    of what it folds and inserts more; before and after the commit the
    engine answers as the model does."""
    rng = random.Random(7)
    eng, model = engine(True), Model()
    fid_next = iter(range(10**6))
    with tp.collect() as trace, tp.force_ordering(
        after="released_by_hand", block="fold_adopt"
    ) as gate:
        while not tp.events_of(trace, "fold_capture"):
            pairs = [(a_filter(rng), next(fid_next)) for _ in range(4)]
            eng.apply_routes(pairs, ())
            model.live.update({fid: flt for flt, fid in pairs})
        deadline = time.monotonic() + 30
        while not tp.events_of(trace, "fold_assemble_done"):
            assert time.monotonic() < deadline
            time.sleep(0.005)
        gone = rng.sample(list(model.live), 10)
        pairs = [(a_filter(rng), next(fid_next)) for _ in range(10)]
        eng.apply_routes(pairs, gone)
        for fid in gone:
            del model.live[fid]
        model.live.update({fid: flt for flt, fid in pairs})
        assert eng._folding
        agree(eng, model, rng, 100)
        gate.set()
        drain_folds(eng)
    tp.assert_present(trace, "fold_commit")
    agree(eng, model, rng, 200)


def test_batches_applied_against_busy_matchers_lose_nothing():
    """Matcher threads hold the lock in their overlays while batches wait
    for it and land (a short switch interval makes the threads
    interleave often); the engine ends where the model does."""
    import sys

    rng = random.Random(5)
    eng, model = engine(True), Model()
    fid_next = iter(range(10**6))
    for _ in range(20):
        pairs = [(a_filter(rng), next(fid_next)) for _ in range(4)]
        eng.apply_routes(pairs, ())
        model.live.update({fid: flt for flt, fid in pairs})
    stop = threading.Event()
    errors = []

    def matcher():
        r = random.Random(threading.get_ident())
        while not stop.is_set():
            try:
                eng.match_batch([a_topic(r) for _ in range(32)])
            except Exception as e:  # surfaced below
                errors.append(e)
                return

    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-4)
    threads = [threading.Thread(target=matcher) for _ in range(4)]
    for t in threads:
        t.start()
    try:
        for _ in range(60):
            gone = rng.sample(list(model.live), min(3, len(model.live)))
            pairs = [(a_filter(rng), next(fid_next)) for _ in range(3)]
            eng.apply_routes(pairs, gone)
            for fid in gone:
                del model.live[fid]
            model.live.update({fid: flt for flt, fid in pairs})
    finally:
        stop.set()
        for t in threads:
            t.join(10)
        sys.setswitchinterval(interval)
    assert not any(t.is_alive() for t in threads) and not errors
    drain_folds(eng)
    agree(eng, model, rng, 200)
    st = eng.stats()
    assert st["route_batches"] == 80 and st["route_ops"] == 20 * 4 + 60 * 6


def test_apply_routes_waits_for_a_held_lock_and_counts_the_wait():
    eng = MatchEngine(use_device=False)
    held = threading.Event()

    def holder():
        with eng._mlock:
            held.set()
            time.sleep(0.2)

    t = threading.Thread(target=holder)
    t.start()
    assert held.wait(10)
    eng.apply_routes([("a/+", "a/+")], ())
    t.join(10)
    assert not t.is_alive()
    st = eng.stats()
    assert (st["route_batches"], st["route_ops"]) == (1, 1)
    assert st["route_wait_us"] >= 100_000 > st["route_apply_us"]
    assert eng.match("a/x") == {"a/+"}


# ------------------------------------------------------- the fold class

def fleet(n, base=0):
    return [(f"v/{base + i}/{i % 7}/#", base + i) for i in range(n)]


def test_a_boot_table_starts_no_fold_and_leaves_the_class_to_the_delta():
    """A boot's table past ``rebuild_threshold`` starts the build and no
    fold beside it; the first fold after the swap is sized by the delta
    (the minimum class), not by the base it sits next to."""
    eng = MatchEngine(max_levels=8, use_device=True, background_rebuild=True,
                      delta_aut_threshold=64)
    with tp.collect() as trace:
        eng.insert_many(fleet(5000))
        assert eng._building and not eng._folding
        eng._build_thread.join(60)
        with eng._mlock:
            eng._poll_swap()
    tp.assert_absent(trace, "fold_capture")
    base_nodes = eng._aut.node_rows.shape[0]
    assert base_nodes > 4096 and eng.index_stats()["residual"] == 0
    eng._fold_async = False
    up0 = eng.stats()["fold_upload_bytes"]
    eng.insert_many(fleet(200, base=10**6))
    daut = eng._dtier[0]
    assert daut is not None
    # 200 filters need ~600 node rows: the class floor, 4,096, holds
    # four times that
    assert daut.node_rows.shape[0] == eng._fold_class[0] == 4096
    up = eng.stats()["fold_upload_bytes"] - up0
    base_bytes = sum(a.nbytes for a in eng._aut.device_arrays()
                     if isinstance(a, np.ndarray))
    assert 0 < up < base_bytes
    st = eng.stats()
    assert st["folds"] == 1
    for phase in ("capture", "encode", "assemble", "upload", "warm",
                  "commit"):
        assert st[f"fold_{phase}_us"] >= 0
    assert st["fold_us"] >= st["fold_assemble_us"] > 0


def test_inserts_after_the_build_snapshot_fold_once_it_swaps():
    eng = MatchEngine(max_levels=8, use_device=True, background_rebuild=True,
                      delta_aut_threshold=64)
    eng._fold_async = False
    release = threading.Event()
    real_build = eng._build

    def slow_build(*a, **kw):
        release.wait(30)
        return real_build(*a, **kw)

    eng._build = slow_build
    eng.insert_many(fleet(5000))
    eng.insert_many(fleet(300, base=10**6))  # after the snapshot
    assert eng._building and eng._dtier[0] is None  # no fold meanwhile
    assert eng.match("v/1000005/5/x") == {1000005}  # the residual serves
    release.set()
    eng._build_thread.join(60)
    with eng._mlock:
        eng._poll_swap()
    assert eng._dtier[0] is not None
    assert eng._daut_fids == set(range(10**6, 10**6 + 300))
    assert eng.match("v/1000005/5/x") == {1000005}
    assert eng.match("v/42/0/x") == {42}


def test_a_discarded_fold_leaves_the_class_as_it_was():
    """A fold the base swap throws away (its inputs predate the new
    base) does not raise the sticky class, however large it was."""
    eng = MatchEngine(max_levels=8, use_device=True, background_rebuild=True,
                      delta_aut_threshold=2000, rebuild_threshold=2500)
    with tp.collect() as trace:
        with tp.force_ordering(after="daut_drop",
                               block="fold_assemble_done"):
            eng.insert_many(fleet(2000))  # folds: 2,000 filters
            assert eng._folding
            eng.insert_many(fleet(600, base=5000))  # the build starts
            deadline = time.monotonic() + 30
            while not tp.events_of(trace, "daut_drop"):
                assert time.monotonic() < deadline
                eng.match_batch(["v/1/1/x"])  # polls the swap
                time.sleep(0.02)
        drain_folds(eng)
    tp.assert_present(trace, "fold_discard")
    tp.assert_absent(trace, "fold_commit")
    assert eng._fold_class is None
    assert eng.stats()["folds"] == 0


def test_the_class_holds_a_delta_that_grows_and_steps_with_room():
    """The first fold takes a class four times what it needs; a delta
    that grows inside it (as the fleet's live set does with its churn)
    folds at that class; one that outgrows it steps to four times what
    it needs then."""
    from emqx_tpu.engine import _EncArena
    from emqx_tpu.ops.automaton import assemble_automaton

    def needs(eng):
        arena = _EncArena(eng.max_levels)
        arena.apply(list(eng._delta.items()), (), eng._tdict)
        aut = assemble_automaton(*arena.views(), max_levels=eng.max_levels)
        return aut.node_rows.shape[0], len(aut.fp_rows)

    eng = MatchEngine(max_levels=8, use_device=True, delta_aut_threshold=64,
                      rebuild_threshold=10**9)
    eng._fold_async = False
    eng.insert_many(fleet(700))
    first = eng._fold_class
    rows, buckets = needs(eng)  # 1,401 node rows, 2,048 buckets
    assert first == (8192, 8192) and first[0] >= 4 * rows
    assert eng._dtier[0].node_rows.shape[0] == first[0]
    assert len(eng._dtier[0].fp_rows) == first[1]
    eng.insert_many(fleet(700, base=10**6))  # twice the filters
    assert eng._fold_class == first and eng.stats()["folds"] == 2
    eng.insert_many(fleet(8000, base=2 * 10**6))
    rows, buckets = needs(eng)
    assert eng._fold_class[0] >= 4 * rows > first[0]
    assert eng._fold_class[1] >= 4 * buckets > first[1]


# ------------------------------------------------------ the route scope

def test_routes_in_a_scope_wait_for_its_end_in_net_order():
    eng = MatchEngine(use_device=False)
    router = Router(eng)
    events = []
    router.on_route_added = lambda f: events.append(("+", f))
    router.on_route_removed = lambda f: events.append(("-", f))
    router.subscribe("a", "x/+", SubOpts(qos=1))  # outside: at once
    assert eng.match("x/1") == {"x/+"} and events == [("+", "x/+")]
    acks = []
    router.begin_routes()
    router.unsubscribe("a", "x/+")
    router.subscribe("b", "x/+", SubOpts(qos=0))
    router.subscribe("b", "y/#", SubOpts(qos=0))
    router.after_routes(lambda: acks.append(len(eng)), time.perf_counter(), 2)
    assert len(eng) == 1 and "y/#" not in eng._by_fid and not acks
    router.end_routes()
    # a delete then an insert of one filter in one batch keeps it
    assert eng.match("x/1") == {"x/+"} and eng.match("y/z") == {"y/#"}
    assert acks == [2]
    assert events == [("+", "x/+"), ("-", "x/+"), ("+", "x/+"),
                      ("+", "y/#")]
    st = eng.stats()
    assert st["route_batches"] == 2 and st["route_ops"] == 2
    assert st["route_ack_filters"] == 2 and st["route_ack_us"] >= 0
    # nothing staged: an ack goes at once
    router.after_routes(lambda: acks.append("now"), time.perf_counter(), 1)
    assert acks == [2, "now"]


def test_the_router_against_the_model_with_shared_groups():
    """Random plain and ``$share`` subscriptions made and ended in and
    out of scopes: after every scope the engine routes exactly the real
    filters someone holds, and the hooks told the same story."""
    rng = random.Random(11)
    eng = MatchEngine(max_levels=8, use_device=True, delta_aut_threshold=8,
                      rebuild_threshold=10**9)
    eng._fold_async = False
    router = Router(eng)
    told = set()
    router.on_route_added = told.add
    router.on_route_removed = told.discard
    held = set()  # (client, full filter)
    for _ in range(60):
        scoped = rng.random() < 0.7
        if scoped:
            router.begin_routes()
        for _ in range(rng.randint(1, 8)):
            cid = f"c{rng.randint(0, 5)}"
            real = "/".join(rng.choice(LEVELS + ("+",))
                            for _ in range(rng.randint(1, 3)))
            flt = (f"$share/g{rng.randint(0, 1)}/{real}"
                   if rng.random() < 0.3 else real)
            if (cid, flt) in held and rng.random() < 0.6:
                router.unsubscribe(cid, flt)
                held.discard((cid, flt))
            else:
                router.subscribe(cid, flt, SubOpts(qos=0))
                held.add((cid, flt))
        if scoped:
            router.end_routes()
        routes = {T.parse_share(f).topic if T.parse_share(f) else f
                  for _, f in held}
        assert set(eng._by_fid) == routes == told
        assert router.subscription_count() == len(held)
        model = Model()
        model.live = {r: r for r in routes}
        agree(eng, model, rng, 20)


# -------------------------------------------------------- served broker

def run(coro):
    return asyncio.run(asyncio.wait_for(coro, 120))


async def served():
    cfg = BrokerConfig()
    cfg.listeners = [ListenerConfig(bind="127.0.0.1", port=0)]
    cfg.engine.use_device = True
    cfg.engine.batch_max = 64
    srv = BrokerServer(cfg)
    eng = srv.broker.router.engine
    eng.insert_many([(f"table/{i}/+", ("t", i)) for i in range(64)])
    eng.rebuild()  # a base: the served windows are the device's
    await srv.start()
    return srv


async def silent(client, seconds):
    """Nothing reaches ``client`` for ``seconds``."""
    with pytest.raises(asyncio.TimeoutError):
        pkt = await client.recv(timeout=seconds)
        raise AssertionError(f"received {pkt!r}")


async def until(cond, timeout=10.0):
    deadline = time.monotonic() + timeout
    while not cond():
        assert time.monotonic() < deadline
        await asyncio.sleep(0.005)


def held_overlay(gate, seconds=0.3):
    """Let the pinned overlay go ``seconds`` from now, from a thread of
    its own (the loop may be waiting for the lock it holds); returns
    where the instant of the release will be."""
    released = {}

    def release():
        time.sleep(seconds)
        released["at"] = time.monotonic()
        gate.set()

    threading.Thread(target=release, daemon=True).start()
    return released


def test_a_suback_leaves_only_after_its_batch_waited_out_an_overlay():
    """A window's overlay holds the matcher's lock (pinned there, let go
    by a timer thread): the turn that read a SUBSCRIBE waits for the
    lock once, applies its batch and only then writes the SUBACK; the
    next window serves the route."""

    async def go():
        srv = await served()
        sub, pub = TestClient(srv.listeners[0].port, "s"), \
            TestClient(srv.listeners[0].port, "p")
        try:
            await sub.connect()
            await pub.connect()
            with tp.collect() as trace, tp.force_ordering(
                after="released_by_hand", block="overlay_locked"
            ) as gate:
                await pub.send(C.Publish(topic="table/1/x", qos=1,
                                         packet_id=1, payload=b"held"))
                await until(lambda: tp.events_of(trace, "overlay_locked"))
                released = held_overlay(gate)
                await sub.send(C.Subscribe(packet_id=7, subscriptions=[
                    C.Subscription(topic_filter="news/+", qos=1)]))
                ack = await sub.expect(C.SUBACK, timeout=30)
                got_at = time.monotonic()
                await pub.expect(C.PUBACK, timeout=30)
            assert list(ack.reason_codes) == [1]
            applied, = tp.events_of(trace, "routes_applied")
            assert released["at"] < applied["ts"] < got_at
            st = srv.broker.router.engine.stats()
            assert st["route_wait_us"] >= 100_000
            await pub.publish("news/1", b"after", qos=1)
            msg = await sub.recv_publish(timeout=10)
            assert msg.payload == b"after"
        finally:
            await sub.close()
            await pub.close()
            await srv.stop()

    run(go())


def test_no_delivery_follows_an_unsuback():
    async def go():
        srv = await served()
        port = srv.listeners[0].port
        sub, pub = TestClient(port, "s"), TestClient(port, "p")
        try:
            await sub.connect()
            await pub.connect()
            await sub.subscribe("news/#", qos=1)
            await pub.publish("news/a", b"before", qos=1)
            assert (await sub.recv_publish(timeout=10)).payload == b"before"
            await sub.unsubscribe("news/#")
            for i in range(20):
                await pub.publish(f"news/{i}", b"after", qos=1)
            await silent(sub, 0.5)
            assert "news/#" not in srv.broker.router.engine._by_fid
        finally:
            await sub.close()
            await pub.close()
            await srv.stop()

    run(go())


def test_a_filter_handed_over_in_one_turn_keeps_its_route():
    """One client unsubscribes a filter and another subscribes it in
    the same route scope (one turn's reads): the batch nets to no
    change, the route stays, and the new holder is served."""

    async def go():
        srv = await served()
        port = srv.listeners[0].port
        a, b, pub = (TestClient(port, c) for c in ("a", "b", "p"))
        broker = srv.broker
        try:
            for c in (a, b, pub):
                await c.connect()
            await a.subscribe("hand/+", qos=0)
            ops0 = broker.router.engine.stats()["route_ops"]
            router = broker.router
            router.begin_routes()
            broker.unsubscribe("a", "hand/+")
            broker.subscribe("b", "hand/+", SubOpts(qos=0))
            router.end_routes()
            assert broker.router.engine.stats()["route_ops"] == ops0
            assert "hand/+" in broker.router.engine._by_fid
            await pub.publish("hand/1", b"x", qos=1)
            assert (await b.recv_publish(timeout=10)).payload == b"x"
            await silent(a, 0.3)
        finally:
            for c in (a, b, pub):
                await c.close()
            await srv.stop()

    run(go())


def test_retained_replay_follows_its_suback_behind_a_held_overlay():
    async def go():
        srv = await served()
        port = srv.listeners[0].port
        sub, pub = TestClient(port, "s"), TestClient(port, "p")
        try:
            await sub.connect()
            await pub.connect()
            await pub.publish("kept/1", b"old", qos=1, retain=True)
            with tp.collect() as trace, tp.force_ordering(
                after="released_by_hand", block="overlay_locked"
            ) as gate:
                await pub.send(C.Publish(topic="table/2/x", qos=1,
                                         packet_id=9, payload=b"held"))
                await until(lambda: tp.events_of(trace, "overlay_locked"))
                released = held_overlay(gate)
                await sub.send(C.Subscribe(packet_id=3, subscriptions=[
                    C.Subscription(topic_filter="kept/+", qos=1)]))
                first = await sub.recv(timeout=30)
                second = await sub.recv(timeout=30)
            applied, = tp.events_of(trace, "routes_applied")
            assert released["at"] < applied["ts"]
            assert first.type == C.SUBACK
            assert second.type == C.PUBLISH and second.retain
            assert second.payload == b"old"
        finally:
            await sub.close()
            await pub.close()
            await srv.stop()

    run(go())
