"""Chaos: failure-driven device→host degradation of the match engine.

With a failpoint forcing 100% device-step errors, the broker must keep
delivering QoS1 traffic on the host path, trip the device-path circuit
breaker (raising the ``engine_device_path`` $SYS alarm), and — once the
fault clears — re-close the breaker via the background probe and
deactivate the alarm.  Engine-level tests pin the mechanics (trip
threshold, host fallback correctness, watchdog deadline, probe
re-close); the broker test asserts the end-to-end acceptance
invariant."""

import asyncio
import json
import time

import pytest

from emqx_tpu import failpoints as fp
from emqx_tpu.engine import MatchEngine


def run(coro):
    return asyncio.run(coro)


@pytest.fixture(autouse=True)
def _clean_failpoints():
    fp.clear()
    yield
    fp.clear()


def make_engine(n=64, **kw):
    eng = MatchEngine(use_device=True, **kw)
    for i in range(n):
        eng.insert(f"dev/{i}/+", f"w{i}")
    eng.insert("exact/topic", "e0")
    eng.rebuild()
    return eng


def wait_until(cond, timeout=5.0, what="condition"):
    t0 = time.monotonic()
    while not cond():
        assert time.monotonic() - t0 < timeout, f"timeout: {what}"
        time.sleep(0.01)


# ----------------------------------------------------------- engine

def test_device_errors_fall_back_to_host_and_trip_breaker():
    eng = make_engine()
    trips, clears = [], []
    eng.on_breaker_trip = trips.append
    eng.on_breaker_clear = clears.append
    eng.breaker_threshold = 3
    eng.breaker_probe_interval = 3600.0  # no probe during this test

    fp.configure("engine.device_step", "error")
    for k in range(6):
        out = eng.match_batch([f"dev/{k}/x", "exact/topic", "none/y"])
        # every window is served EXACTLY on the host oracle
        assert out[0] == {f"w{k}"}
        assert out[1] == {"e0"}
        assert out[2] == set()
    assert eng.breaker_info()["open"] is True
    assert len(trips) == 1 and trips[0]["failures"] == 3
    # after the trip the device path is not attempted: the failpoint
    # stops firing and device_errors stays at the trip count
    errs = eng.breaker_info()["device_errors"]
    eng.match_batch(["dev/0/x"])
    assert eng.breaker_info()["device_errors"] == errs
    assert clears == []


def test_probe_recloses_breaker_after_fault_clears():
    eng = make_engine()
    clears = []
    eng.on_breaker_clear = clears.append
    eng.breaker_threshold = 2
    eng.breaker_probe_interval = 3600.0
    fp.configure("engine.device_step", "error")
    for _ in range(3):
        eng.match_batch(["dev/1/x"])
    assert eng.breaker_info()["open"]

    # fault persists: the probe fails and the breaker stays open
    eng.breaker_probe_interval = 0.0
    eng.match_batch(["dev/1/x"])  # host window schedules a probe
    wait_until(lambda: eng.breaker_info()["probes"] >= 1, what="probe")
    wait_until(lambda: not eng._brk_probing, what="probe done")
    assert eng.breaker_info()["open"]

    # fault clears: the next probe closes it and matching returns to
    # the device path
    fp.clear("engine.device_step")
    eng.match_batch(["dev/1/x"])
    wait_until(lambda: not eng.breaker_info()["open"], what="re-close")
    # the probe thread flips `open` BEFORE it runs the clear callback:
    # waiting on the flag alone races the callback (observed flaky
    # under load) — wait for the callback itself
    wait_until(lambda: len(clears) == 1, what="clear callback")
    assert eng.match_batch(["dev/2/x"])[0] == {"w2"}
    assert eng.breaker_info()["consecutive_failures"] == 0


def test_watchdog_deadline_counts_slow_windows():
    """A device window that RETURNS but blows the watchdog deadline is
    breaker food too — a wedged link degrades to host-only without a
    single exception being raised."""
    eng = make_engine()
    eng.breaker_threshold = 2
    eng.breaker_probe_interval = 3600.0
    eng.breaker_deadline = 0.01
    fp.configure("engine.device_step", "delay", delay=0.05)
    out1 = eng.match_batch(["dev/3/x"])
    out2 = eng.match_batch(["dev/4/x"])
    assert out1[0] == {"w3"} and out2[0] == {"w4"}
    info = eng.breaker_info()
    assert info["slow_windows"] >= 2 and info["open"] is True


def test_insert_delete_keep_working_while_tripped():
    """Degraded mode is not read-only: churn lands in the host tiers
    and matches correctly while the breaker is open."""
    eng = make_engine()
    eng.breaker_threshold = 1
    eng.breaker_probe_interval = 3600.0
    fp.configure("engine.device_step", "error")
    eng.match_batch(["dev/0/x"])
    assert eng.breaker_info()["open"]
    eng.insert("new/+/sub", "n1")
    eng.delete("w5")
    out = eng.match_batch(["new/a/sub", "dev/5/x", "dev/6/x"])
    assert out[0] == {"n1"} and out[1] == set() and out[2] == {"w6"}


# ----------------------------------------------------------- broker

def test_broker_survives_total_device_failure_qos1():
    """The acceptance invariant: 100% device-step errors; QoS1 traffic
    keeps flowing (host path), the $SYS alarm raises on trip and
    clears after the probe re-closes the breaker."""

    async def t():
        from emqx_tpu.broker.listener import BrokerServer
        from emqx_tpu.config import BrokerConfig, ListenerConfig
        from mqtt_client import TestClient

        cfg = BrokerConfig()
        cfg.listeners = [ListenerConfig(port=0)]
        srv = BrokerServer(cfg)
        await srv.start()
        broker = srv.broker
        eng = broker.router.engine
        eng.use_device = True  # pin: every window attempts the device
        eng.breaker_threshold = 3
        eng.breaker_probe_interval = 3600.0
        port = srv.listeners[0].port

        mon = TestClient(port, "mon")
        await mon.connect()
        await mon.subscribe("$SYS/brokers/+/alarms/#")

        async def recv_alarm():
            # the next $SYS message of THIS alarm: on a busy machine
            # os_mon's high_cpu alarm comes and goes on the same topics
            while True:
                pkt = await mon.recv_publish(timeout=5)
                if json.loads(pkt.payload)["name"] == "engine_device_path":
                    return pkt
        sub = TestClient(port, "sub")
        await sub.connect()
        await sub.subscribe("chaos/+/q", qos=1)
        # build the device automaton so the device path is live
        eng.rebuild()
        assert eng._aut is not None and eng._aut.n_nodes > 1

        fp.configure("engine.device_step", "error")
        for i in range(8):
            # QoS1 publish acks only after dispatch: delivery rides
            # the host fallback while every device window errors
            await pub_one(srv, port, i)
        got = set()
        for _ in range(8):
            pkt = await sub.recv_publish(timeout=5)
            got.add(pkt.topic)
        assert got == {f"chaos/{i}/q" for i in range(8)}

        # breaker tripped and the $SYS alarm is active + published
        assert eng.breaker_info()["open"] is True
        deadline = asyncio.get_event_loop().time() + 5
        while not any(
            a.name == "engine_device_path"
            for a in broker.alarms.active()
        ):
            assert asyncio.get_event_loop().time() < deadline
            await asyncio.sleep(0.02)
        alarm_pkt = await recv_alarm()
        assert alarm_pkt.topic.endswith("/alarms/activate")
        assert broker.metrics.val("engine.breaker.trip") == 1

        # fault clears: probe re-closes, alarm deactivates, traffic
        # still exact
        fp.clear("engine.device_step")
        eng.breaker_probe_interval = 0.0
        await pub_one(srv, port, 8)
        deadline = asyncio.get_event_loop().time() + 5
        while eng.breaker_info()["open"]:
            assert asyncio.get_event_loop().time() < deadline
            await asyncio.sleep(0.02)
        clear_pkt = await recv_alarm()
        assert clear_pkt.topic.endswith("/alarms/deactivate")
        assert not any(
            a.name == "engine_device_path"
            for a in broker.alarms.active()
        )
        pkt = await sub.recv_publish(timeout=5)
        assert pkt.topic == "chaos/8/q"
        assert broker.metrics.val("engine.breaker.clear") == 1

        await sub.disconnect()
        await mon.disconnect()
        await srv.stop()

    async def pub_one(srv, port, i):
        from mqtt_client import TestClient

        pub = TestClient(port, f"pub{i}")
        await pub.connect()
        await pub.publish(f"chaos/{i}/q", b"payload", qos=1)
        await pub.disconnect()

    run(t())
