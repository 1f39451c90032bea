"""The turn clock (`LoopClock.install` / `mark`), the executors' CPU
beside their wall time (`CpuLaps`) and the collections' clock
(`FlightRecorder._gc_cb` -> `LoopClock.attach_gc`): the phases cut
the loop thread's wall time exactly, a planted stall lands in the
phase that holds it, and the facts of `asyncio`'s iteration order the
edges lean on hold on the installed Python.  No timing assertion is
tighter than a factor of two."""

import asyncio
import gc
import sys
import threading
import time

import pytest

from emqx_tpu import observability
from emqx_tpu.broker import connection as conn_mod
from emqx_tpu.broker.channel import Channel
from emqx_tpu.broker.listener import BrokerServer
from emqx_tpu.codec import mqtt as C
from emqx_tpu.config import BrokerConfig, ListenerConfig
from emqx_tpu.flightrec import FlightRecorder
from emqx_tpu.observability import CpuLaps, Laps, LoopClock, NO_LAPS, Profiler

PHASES = ("poll", "recv", "reads", "acks", "tail")
STALL = 0.06


def run(coro):
    return asyncio.run(coro)


def spent(lc):
    """Seconds by phase so far, the running phase's open part closed
    (on the loop thread)."""
    lc.mark(lc._phase)
    return dict(zip(PHASES, lc._spent))


def fake_loop():
    """A loop that is nothing but a selector whose ``select`` can be
    wrapped: the test calls ``loop._selector.select(0)`` for a poll."""

    class Sel:
        def select(self, timeout=None):
            return []

    class Loop:
        _selector = Sel()

    return Loop


async def served(enable=True):
    from mqtt_client import TestClient

    cfg = BrokerConfig()
    cfg.listeners = [ListenerConfig(bind="127.0.0.1", port=0)]
    cfg.profiler.enable = enable
    srv = BrokerServer(cfg)
    await srv.start()
    port = srv.listeners[0].port
    sub = TestClient(port, "sub")
    await sub.connect()
    await sub.subscribe("t/#", qos=1)
    pub = TestClient(port, "pub")
    await pub.connect()
    return srv, pub, sub


async def round_trip(pub, sub, n=4, first=1):
    for i in range(n):
        await pub.send(C.Publish(topic="t/x", payload=b"p", qos=1,
                                 packet_id=first + i))
    for _ in range(n):
        await sub.recv_publish(timeout=20)
    for _ in range(n):
        await pub.expect(C.PUBACK, timeout=20)


# ------------------------------------------------- the partition


def test_phases_of_a_stretch_sum_to_its_wall_time():
    async def main():
        srv, pub, sub = await served()
        try:
            lc = srv.broker.profiler.loop
            t0, before = time.perf_counter(), spent(lc)
            for k in range(10):
                await round_trip(pub, sub, first=1 + 4 * k)
                await asyncio.sleep(0.01)
            after, wall = spent(lc), time.perf_counter() - t0
        finally:
            await srv.stop()
        grown = {p: after[p] - before[p] for p in PHASES}
        assert all(v >= 0 for v in grown.values())
        assert sum(grown.values()) == pytest.approx(wall, rel=0.01)
        # every phase ran: the loop polled, read, handled, acknowledged
        assert all(grown[p] > 0 for p in PHASES), grown
        return srv.broker.profiler

    prof = run(main())
    # and record by record: the five fields of a window are the wall
    # time since the window committed before it
    wins = sorted(prof.windows(100), key=lambda w: w["seq"])
    assert len(wins) >= 10
    total = sum(w[f"loop_{p}_us"] for w in wins[1:] for p in PHASES)
    laps = ("batch_wait", "prepare", "match_submit", "match_wait",
            "dispatch_wait", "expand", "decide", "deliver", "flush", "rules")

    def committed(w):
        return w["at"] + sum(w["stages_us"].get(k, 0.0) for k in laps) / 1e6

    span = committed(wins[-1]) - committed(wins[0])
    assert total / 1e6 == pytest.approx(span, rel=0.02, abs=2e-3)
    for w in wins:
        assert w["loop_turns"] >= w["loop_recv_turns"] >= 0
        assert w["stages_us"]["collect"] <= w["stages_us"]["batch_wait"] + 1


@pytest.mark.parametrize("where", ["recv", "reads", "acks", "tail"])
def test_a_planted_stall_lands_in_its_phase(where, monkeypatch):
    """A `time.sleep` in a `data_received`, in `ReadTurn._run`, in a
    done-callback of a window's future and in a plain `call_soon`."""
    armed = []

    def stall_once():
        if armed:
            armed.pop()
            time.sleep(STALL)

    if where == "recv":
        real_recv = conn_mod.Connection.data_received

        def data_received(self, data):
            real_recv(self, data)
            stall_once()

        monkeypatch.setattr(conn_mod.Connection, "data_received",
                            data_received)
    elif where == "reads":
        real_reads = conn_mod.Connection._handle_reads

        def handle_reads(self):
            stall_once()
            real_reads(self)

        monkeypatch.setattr(conn_mod.Connection, "_handle_reads",
                            handle_reads)
    elif where == "acks":
        real_acked = Channel._publish_acked

        def publish_acked(self, packet_id, qos, fut):
            stall_once()
            real_acked(self, packet_id, qos, fut)

        monkeypatch.setattr(Channel, "_publish_acked", publish_acked)

    async def main():
        srv, pub, sub = await served()
        try:
            lc = srv.broker.profiler.loop
            await round_trip(pub, sub)  # warm: the first window's paths
            await asyncio.sleep(0.05)
            before = spent(lc)
            armed.append(1)
            if where == "tail":
                asyncio.get_running_loop().call_soon(stall_once)
            await round_trip(pub, sub, first=5)
            await asyncio.sleep(0.05)
            after = spent(lc)
        finally:
            await srv.stop()
        assert not armed  # the stall ran
        return {p: after[p] - before[p] for p in PHASES}

    grown = run(main())
    assert grown[where] >= 0.9 * STALL, grown
    for other in ("recv", "reads", "acks", "tail"):
        if other != where:
            assert grown[other] < 0.5 * STALL, (other, grown)


# ------------------------------------------- asyncio's iteration order


def test_run_once_order_the_edges_lean_on():
    """After a `select`, `_run_once` runs first the handles that were
    queued before it, then the `_read_ready` handles of that poll; a
    `call_soon` made in an iteration runs in the next; so the
    done-callbacks of futures resolved in an iteration, and a
    `call_soon` made behind them, run in a row in the next iteration,
    behind what was queued before them and ahead of its reads."""
    order = []

    class Proto(asyncio.Protocol):
        def data_received(self, data):
            order.append(("read", turns[0]))

    class Turns:
        """`turns[0]`: the `select` calls so far, by the clock's own
        hook."""

        def __init__(self):
            self.clock = LoopClock()

        def __getitem__(self, _):
            return self.clock.turns

    turns = Turns()

    async def main():
        loop = asyncio.get_running_loop()
        assert turns.clock.install(loop)
        try:
            server = await loop.create_server(Proto, "127.0.0.1", 0)
            port = server.sockets[0].getsockname()[1]
            _r, w = await asyncio.open_connection("127.0.0.1", port)
            await asyncio.sleep(0.05)
            futs = [loop.create_future() for _ in range(3)]
            for i, f in enumerate(futs):
                f.add_done_callback(
                    lambda f, i=i: order.append((f"cb{i}", turns[0])))
            done = loop.create_future()

            def resolve():
                # the peer's bytes are in the socket before this turn's
                # next poll: its read handle follows whatever is queued
                w.write(b"x")
                order.append(("early", turns[0]))
                loop.call_soon(
                    lambda: order.append(("queued-before", turns[0])))
                for f in futs:
                    f.set_result(1)
                loop.call_soon(lambda: order.append(("uncork", turns[0])))
                loop.call_soon(lambda: order.append(("end", turns[0])))
                loop.call_later(0.2, done.set_result, None)

            loop.call_soon(resolve)
            await done
            w.close()
            server.close()
            await server.wait_closed()
        finally:
            turns.clock.uninstall()

    run(main())
    names = [n for n, _ in order]
    turn = dict(order)
    at = names.index("early")
    # a `call_soon` made in an iteration runs in the next ...
    assert turn["queued-before"] == turn["early"] + 1
    # ... in the order made: the callbacks and what was queued behind
    # them in a row, nothing between
    assert names[at:at + 7] == ["early", "queued-before", "cb0", "cb1",
                                "cb2", "uncork", "end"]
    assert {turn[n] for n in ("cb0", "cb1", "cb2", "uncork", "end")} == {
        turn["early"] + 1}
    # the poll's read handles run behind everything queued before it
    assert names.index("read") > names.index("end")
    assert turn["read"] >= turn["end"]


def test_a_turns_reads_are_handled_in_the_next_iteration_before_its_reads():
    """`ReadTurn.add` queues the run during the turn's reads: it runs
    in the next iteration, ahead of what that iteration's poll finds
    readable, so `reads` follows `poll` and `recv` follows `reads`."""
    seen = []

    class Clock:
        READS, TAIL = LoopClock.READS, LoopClock.TAIL

        def recv(self):
            seen.append("recv")

        def mark(self, phase):
            seen.append({self.READS: "reads", self.TAIL: "tail"}[phase])

    class Conn:
        def _handle_reads(self):
            seen.append("handled")

    async def main():
        turn = conn_mod.ReadTurn(Clock())
        for _ in range(100):
            turn.add(Conn())
        assert seen == ["recv"]  # a clock call a turn, none a read
        await asyncio.sleep(0)
        await asyncio.sleep(0)
        assert seen == ["recv", "reads"] + ["handled"] * 100 + ["tail"]

    run(main())


def test_clock_reads_of_a_turn_of_a_hundred_reads(monkeypatch):
    """`select`, a hundred `data_received`s, the turn's run of them and
    the next `select`: at most six `perf_counter` reads of the turn
    clock's own, whatever the reads."""

    class Conn:
        def _handle_reads(self):
            pass

    Loop = fake_loop()

    async def main():
        lc = LoopClock()
        assert lc.install(Loop)
        turn = conn_mod.ReadTurn(lc)
        ticks = []
        real = time.perf_counter

        def counted():
            ticks.append(1)
            return real()

        monkeypatch.setattr(observability.time, "perf_counter", counted)
        Loop._selector.select(0)       # poll: in and out
        for _ in range(100):
            turn.add(Conn())           # recv: the first one marks
        await asyncio.sleep(0)         # the run: reads, then tail
        await asyncio.sleep(0)
        Loop._selector.select(0)       # (the next turn's poll: two more)
        monkeypatch.undo()
        lc.uninstall()
        assert len(ticks) - 2 <= 6, len(ticks)
        assert (lc.turns, lc.recv_turns) == (2, 1)

    run(main())


# --------------------------------------------- install / uninstall


def test_hook_is_gone_after_stop_and_two_clocks_share_one_wrapper():
    async def main():
        loop = asyncio.get_running_loop()
        sel = loop._selector
        assert "select" not in vars(sel)
        srv, pub, sub = await served()
        assert vars(sel)["select"].turn_clocks == [srv.broker.profiler.loop]
        other = LoopClock()
        assert other.install(loop) and not other.install(loop)
        assert len(vars(sel)["select"].turn_clocks) == 2
        await round_trip(pub, sub)
        await srv.stop()
        assert vars(sel)["select"].turn_clocks == [other]  # still hooked
        turns = other.turns
        await asyncio.sleep(0.01)
        assert other.turns > turns > 0
        other.uninstall()
        other.uninstall()  # (twice is once)
        assert "select" not in vars(sel)
        turns = other.turns
        await asyncio.sleep(0.01)
        assert other.turns == turns
        other.mark(other.RECV)  # nothing while not installed
        assert other._phase == other.TAIL

    run(main())


def test_no_hook_with_the_profiler_disabled():
    async def main():
        sel = asyncio.get_running_loop()._selector
        srv, pub, sub = await served(enable=False)
        try:
            assert srv.broker.profiler.loop is None
            assert "select" not in vars(sel)
            await round_trip(pub, sub)
        finally:
            await srv.stop()
        assert srv.broker.profiler.windows() == []

    run(main())


@pytest.mark.parametrize("loop", [object(), type("L", (), {
    "_selector": type("S", (), {"__slots__": ("select_",)})()})()],
    ids=["no-selector", "selector-without-select"])
def test_turn_fields_absent_on_a_loop_without_a_selector(loop):
    prof = Profiler(ring_size=4)
    lc = prof.loop
    assert lc.install(loop) is False
    lc.recv()
    lc.mark(lc.READS)
    lc.ingress(time.perf_counter(), 1, 1, 0)
    prof.commit(prof.begin(1))
    w, = prof.windows(1)
    assert w["loop_ingress_reads"] == 1 and "loop_cpu_us" in w
    assert not [k for k in w if k in {
        "loop_poll_us", "loop_recv_us", "loop_reads_us", "loop_acks_us",
        "loop_tail_us", "loop_turns", "loop_recv_turns"}]
    assert lc.bursts() == [("loop_ingress",) + lc.bursts()[0][1:]]
    # and `take` hands the plain fields alone
    assert len(lc.take()) == len(LoopClock.FIELDS)


def test_a_selector_that_refuses_the_attribute_is_left_alone():
    class Sel:
        __slots__ = ()

        def select(self, timeout=None):
            return []

    class Loop:
        _selector = Sel()

    lc = LoopClock()
    assert lc.install(Loop) is False and lc._sel is None


# ------------------------------------------------ bursts in the trace


def test_poll_and_recv_bursts_export_and_merge_as_ingress_does():
    Loop = fake_loop()
    prof = Profiler(ring_size=4)
    lc = prof.loop
    assert lc.install(Loop)
    rec = prof.begin(1)
    for _ in range(3):  # three polls and three recvs, each < 200 us apart
        Loop._selector.select(0)
        lc.recv()
        lc.recv()  # (a second listener's first read: the phase is open)
    Loop._selector.select(0)
    time.sleep(2 * LoopClock.BURST_GAP_S + 1e-3)
    Loop._selector.select(0)  # a poll past the gap: a burst of its own
    lc.recv()
    rec.lap("prepare")
    prof.commit(rec)
    lc.uninstall()
    assert lc.recv_turns == 4 and lc.turns == 5
    names = [b[0] for b in lc.bursts()]
    assert names.count("loop_poll_wait") == 2
    # (the fourth recv: what the commit's `take` closed of it)
    assert names.count("loop_recv") == 2
    xs = [e for e in prof.chrome_trace()["traceEvents"] if e["ph"] == "X"]
    assert {e["name"] for e in xs} == {"loop_poll_wait", "loop_recv"}
    assert all(e["tid"] == Profiler.LOOP_TID and e["dur"] >= 0 for e in xs)
    w, = prof.windows(1)
    assert (w["loop_turns"], w["loop_recv_turns"]) == (5, 4)


# ------------------------------------------------ CPU beside the wall


def spin(seconds):
    end = time.perf_counter() + seconds
    n = 0
    while time.perf_counter() < end:
        n += 1
    return n


def burn(n):
    x = 0
    for i in range(n):
        x += i * i
    return x


def test_cpu_laps_hand_cpu_back_with_no_start_and_waits_have_none():
    tm = CpuLaps(3)
    spin(0.02)
    tm.lap("encode", then=None)
    time.sleep(0.03)
    tm.lap("device_wait")
    spin(0.01)
    tm.lap("overlay")
    spin(0.01)
    tm.lap("overlay")  # (a repeated name sums, as the wall one does)
    timings = tm.timings()
    assert [n for n, _s, _d in timings] == [
        "encode", "device_wait", "overlay", "overlay",
        "encode_cpu", "overlay_cpu", "overlay_cpu"]
    assert all(s is not None for n, s, _d in timings if "_cpu" not in n)
    assert all(s is None for n, s, _d in timings if n.endswith("_cpu"))
    wall = {}
    for name, _s, dur in timings:
        wall[name] = wall.get(name, 0.0) + dur
    for name in ("encode", "overlay"):
        assert 0 < wall[name + "_cpu"] <= wall[name] + 1e-3
    # on a window's record: sub-stages, summed by name, out of the trace
    prof = Profiler(ring_size=4)
    rec = prof.begin(1)
    rec.lap_parts("match_wait", "finish_queue_wait", time.perf_counter(),
                  timings)
    prof.commit(rec)
    st = prof.windows(1)[0]["stages_us"]
    assert st["overlay_cpu"] == pytest.approx(wall["overlay_cpu"] * 1e6, abs=1)
    assert "device_wait_cpu" not in st and "overlay_cpu" in prof.summary()
    names = {e["name"] for e in prof.chrome_trace()["traceEvents"]}
    assert "overlay" in names and "overlay_cpu" not in names


def test_plain_laps_and_no_laps_read_no_cpu_clock(monkeypatch):
    def no_cpu():
        raise AssertionError("thread_time read")

    monkeypatch.setattr(observability.time, "thread_time", no_cpu)
    tm = Laps(1)
    tm.lap("prepare")
    assert [n for n, _s, _d in tm.timings()] == ["prepare"]
    assert NO_LAPS.lap("overlay") == 0.0 and NO_LAPS.timings() == ()
    rec = Profiler(ring_size=2).begin(1)  # (its own laps: none either;
    rec.lap("prepare")                    # `stamp_cpu` is off its thread)


def test_a_thread_spinning_on_the_gil_widens_wall_less_cpu():
    """The same work alone and beside a thread that holds the GIL:
    its CPU holds (within a factor of two), its wall time grows, and
    the difference is the time it wanted to run and did not."""
    work = 400_000
    burn(work)  # warm
    old = sys.getswitchinterval()
    sys.setswitchinterval(0.002)
    try:
        alone = CpuLaps(0)
        burn(work)
        alone.lap("overlay")
        stop = threading.Event()

        def hog():
            while not stop.is_set():
                burn(20_000)

        t = threading.Thread(target=hog, daemon=True)
        t.start()
        try:
            time.sleep(0.01)
            beside = CpuLaps(0)
            burn(work)
            beside.lap("overlay")
        finally:
            stop.set()
            t.join()
    finally:
        sys.setswitchinterval(old)
    (_, _, wall_a), (_, _, cpu_a) = alone.timings()
    (_, _, wall_b), (_, _, cpu_b) = beside.timings()
    assert cpu_a <= wall_a + 1e-3 and cpu_b <= wall_b + 1e-3
    assert 0.5 * cpu_a <= cpu_b <= 2.0 * cpu_a + 5e-3
    # (on a host with a spare core the two threads still share the GIL)
    assert wall_b - cpu_b > 2 * (wall_a - cpu_a) + 0.25 * cpu_a


# ------------------------------------------------------- collections


def test_gc_totals_grow_by_a_forced_collection_and_a_stall_is_an_event():
    prof = Profiler(ring_size=8)
    fl = FlightRecorder(gc_stall_ms=1e9, watchdog_stall_ms=0)
    fl.profiler = prof
    prof.commit(prof.begin(1))
    assert "gc_us" not in prof.windows(1)[0]  # no callback armed: absent
    n_callbacks = len(gc.callbacks)
    fl.arm_watchdog()
    try:
        assert len(gc.callbacks) == n_callbacks + 1
        fl.arm_watchdog()  # (armed once)
        assert len(gc.callbacks) == n_callbacks + 1
        prof.commit(prof.begin(1))
        quiet = prof.windows(1)[0]
        gc.collect()
        gc.collect()
        prof.commit(prof.begin(1))
        w = prof.windows(1)[0]
        assert w["gc_collections"] >= 2 and w["gc_us"] > 0
        assert quiet["gc_collections"] >= 0 and quiet["gc_us"] >= 0.0
        assert fl.gc_clock()[1] >= 2
        assert not [e for e in prof.events() if e["kind"] == "gc_pause"]
        # a pause at or over the threshold: an interval of the export
        fl.gc_stall_ms = 0.0
        gc.collect()
        fl.gc_stall_ms = 1e9
        ev, = [e for e in prof.events() if e["kind"] == "gc_pause"]
        assert ev["generation"] == 2 and ev["dur_ms"] >= 0
        x, = [e for e in prof.chrome_trace()["traceEvents"]
              if e["ph"] == "X" and e["name"] == "gc_pause"]
        assert x["tid"] == 0 and x["args"] == {"generation": 2}
        # no histogram was touched from inside the collection
        assert "engine_gc_pause" not in prof.summary()
        prof.reset()
        gc.collect()
        prof.commit(prof.begin(1))
        assert prof.windows(1)[0]["gc_collections"] >= 1
    finally:
        fl.stop()
    assert len(gc.callbacks) == n_callbacks
    prof.commit(prof.begin(1))
    assert "gc_collections" not in prof.windows(1)[0]


def test_a_served_broker_has_one_gc_callback_and_the_fields_in_every_record():
    async def main():
        n_callbacks = len(gc.callbacks)
        srv, pub, sub = await served()
        try:
            assert len(gc.callbacks) == n_callbacks + 1
            gc.collect()
            await round_trip(pub, sub)
        finally:
            await srv.stop()
        assert len(gc.callbacks) == n_callbacks
        wins = srv.broker.profiler.windows(10)
        assert wins and all("gc_us" in w for w in wins)
        assert sum(w["gc_collections"] for w in wins) >= 1

    run(main())


# ---------------------------------------------------------- the REST


def test_rest_records_carry_the_new_fields_and_the_trace_the_bursts():
    """`/api/v5/profiler`: the turn fields, the `_cpu` sub-stages and
    the `gc_*` fields in a window's record; `/api/v5/profiler/trace`:
    the two new burst names on the loop's track."""
    import tempfile

    from api_helper import auth_session

    async def main():
        from mqtt_client import TestClient

        cfg = BrokerConfig()
        cfg.listeners = [ListenerConfig(bind="127.0.0.1", port=0)]
        cfg.api.enable = True
        cfg.api.port = 0
        cfg.engine.use_device = False
        with tempfile.TemporaryDirectory() as tmp:
            cfg.api.data_dir = tmp
            srv = BrokerServer(cfg)
            await srv.start()
            try:
                port = srv.listeners[0].port
                sub = TestClient(port, "sub")
                await sub.connect()
                await sub.subscribe("t/#", qos=1)
                pub = TestClient(port, "pub")
                await pub.connect()
                await round_trip(pub, sub)
                http, api = await auth_session(srv)
                async with http:
                    async with http.get(api + "/api/v5/profiler") as r:
                        assert r.status == 200
                        body = await r.json()
                    async with http.get(api + "/api/v5/profiler/trace") as r:
                        assert r.status == 200
                        trace = await r.json()
            finally:
                await srv.stop()
        return body, trace

    body, trace = run(main())
    win = [w for w in body["windows"] if w["source"] == "batcher"][0]
    for field in ("loop_poll_us", "loop_recv_us", "loop_reads_us",
                  "loop_acks_us", "loop_tail_us", "loop_turns",
                  "loop_recv_turns", "gc_us", "gc_collections"):
        assert field in win, field
    assert {"collect", "tokenize", "tokenize_cpu"} <= set(win["stages_us"])
    assert "tokenize_cpu" in body["histograms_us"]
    bursts = {e["name"] for e in trace["traceEvents"] if e["ph"] == "X"}
    assert {"loop_poll_wait", "loop_recv"} <= bursts
