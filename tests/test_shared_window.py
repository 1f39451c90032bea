"""A window's shared-subscription picks as one operation
(`SharedSubManager.pick_window`) against the scalar `pick` it replaces
on the served path, and the broker around it: the fallback to the
redispatch path for a key with an ineligible member, the strategy as
configuration, and the window record's span and counters."""

import asyncio
import random

import numpy as np
import pytest

from emqx_tpu.broker.broker import Broker
from emqx_tpu.broker.listener import BrokerServer
from emqx_tpu.broker.session import SubOpts
from emqx_tpu.broker.shared import STRATEGIES, SharedSubManager
from emqx_tpu.config import BrokerConfig, ListenerConfig, check_config
from emqx_tpu.message import Message

from mqtt_client import TestClient

DETERMINISTIC = ("round_robin", "round_robin_per_group", "sticky",
                 "hash_topic", "hash_clientid")


def _keys(rng, n_keys, max_members):
    """``n_keys`` (group, filter) keys over three group names, so that
    a group spans filters; members numbered by client row."""
    keys, row = [], 0
    for k in range(n_keys):
        group = f"g{rng.randrange(3)}"
        members = []
        for _ in range(rng.randint(1, max_members)):
            members.append((f"c{row}", row, 1000 + row))
            row += 1
        keys.append((group, f"f/{k}", members))
    return keys


def _twins(strategy, keys, seed):
    """The same membership in two managers of one seed: one picks by
    window, the other row by row."""
    out = []
    for _ in range(2):
        m = SharedSubManager(strategy=strategy, seed=seed)
        for group, flt, members in keys:
            for cid, row, slot in members:
                m.join(group, flt, cid, row, slot)
        out.append(m)
    return out


def _window(rng, keys, vec, n_msgs):
    """A window's messages and its shared rows: each message owes a
    random few keys, in a random order (the router's order is a
    message's matched filters, then a filter's groups)."""
    msgs = [Message(topic=f"t/{rng.randrange(97)}",
                    from_client=f"p{rng.randrange(31)}")
            for _ in range(n_msgs)]
    kids = [int(vec.keys_by_filter[flt][0]) for _g, flt, _m in keys]
    s_msg, s_key = [], []
    for i in range(n_msgs):
        for j in rng.sample(range(len(keys)), rng.randint(0, len(keys))):
            s_msg.append(i)
            s_key.append(kids[j])
    return msgs, np.asarray(s_msg, np.int64), np.asarray(s_key, np.int64)


def _scalar(mgr, keys_by_kid, msgs, s_msg, s_key):
    rows, slots = [], []
    for i, kid in zip(s_msg.tolist(), s_key.tolist()):
        group, flt, members = keys_by_kid[kid]
        cid = mgr.pick(group, flt, msgs[i])
        _c, row, slot = next(m for m in members if m[0] == cid)
        rows.append(row)
        slots.append(slot)
    return rows, slots


@pytest.mark.parametrize("seed", range(6))
@pytest.mark.parametrize("strategy", DETERMINISTIC)
def test_window_pick_equals_the_scalar_pick_row_for_row(strategy, seed):
    rng = random.Random(seed * 31 + len(strategy))
    keys = _keys(rng, rng.randint(1, 8), 64)
    vec, ref = _twins(strategy, keys, seed)
    by_kid = {int(vec.keys_by_filter[flt][0]): (g, flt, mem)
              for g, flt, mem in keys}
    # two windows: the counters carry from one to the next
    for n_msgs in (rng.randint(1, 4096), rng.randint(1, 300)):
        msgs, s_msg, s_key = _window(rng, keys, vec, n_msgs)
        rows, slots, served = vec.pick_window(s_msg, s_key, msgs)
        want_rows, want_slots = _scalar(ref, by_kid, msgs, s_msg, s_key)
        assert served.all()
        assert rows.tolist() == want_rows
        assert slots.tolist() == want_slots
        assert vec._rr == ref._rr
        assert vec._rr_group == ref._rr_group
        assert vec._sticky == ref._sticky
    assert vec.stats()["picks"] == vec.stats()["picks_vector"] > 0


def test_random_picks_a_member_of_the_key_uniformly():
    rng = random.Random(5)
    keys = _keys(rng, 8, 64)
    vec, _ = _twins("random", keys, 11)
    msgs, s_msg, s_key = _window(rng, keys, vec, 2000)
    rows, slots, served = vec.pick_window(s_msg, s_key, msgs)
    assert served.all()
    members = {int(vec.keys_by_filter[flt][0]): {r for _c, r, _s in mem}
               for _g, flt, mem in keys}
    assert all(r in members[k] for k, r in zip(s_key.tolist(),
                                                rows.tolist()))
    assert (slots == rows + 1000).all()
    # one key of eight members over 100,000 rows: each member's share
    # within the chi-square bound at p = 0.001 for 7 degrees of freedom
    one = SharedSubManager(strategy="random", seed=3)
    for k in range(8):
        one.join("g", "f", f"c{k}", k, k)
    n = 100_000
    rows, _s, _ok = one.pick_window(
        np.zeros(n, np.int64), np.zeros(n, np.int64), [Message(topic="f")]
    )
    seen = np.bincount(rows, minlength=8)
    chi2 = float(((seen - n / 8) ** 2 / (n / 8)).sum())
    assert chi2 < 24.32, seen


# ------------------------------------------------------ the broker side


class _Chan:
    def __init__(self):
        self.sent = []

    def send_packets(self, pkts):
        self.sent.extend(pkts)

    def close(self, reason):
        pass


def _member(b, cid, flt):
    ch = _Chan()
    session, _ = b.cm.open_session(True, cid, ch)
    session.subscribe(flt, SubOpts(qos=0))
    b.subscribe(cid, flt, SubOpts(qos=0))
    return ch


def _publish(b, n, topic="t"):
    return b.publish_many([Message(topic=topic) for _ in range(n)])


def _join_and_leave():
    """Members join and leave between windows, down to none."""
    b = Broker(shared_strategy="round_robin")
    chans = {c: _member(b, c, "$share/g/t") for c in ("a", "b")}
    assert _publish(b, 4) == [1] * 4
    assert [len(chans[c].sent) for c in "ab"] == [2, 2]
    chans["c"] = _member(b, "c", "$share/g/t")
    # the key's counter goes on at 4: b, c, a
    assert _publish(b, 3) == [1] * 3
    assert [len(chans[c].sent) for c in "abc"] == [3, 3, 1]
    for c in "ab":
        b.unsubscribe(c, "$share/g/t")
    assert _publish(b, 2) == [1, 1]
    assert len(chans["c"].sent) == 3
    b.unsubscribe("c", "$share/g/t")
    assert not b.router.shared.keys_by_filter
    assert _publish(b, 2) == [0, 0]
    st = b.router.shared.stats()
    assert st["picks"] == st["picks_vector"] == 9
    assert st["picks_fallback"] == st["picks_no_member"] == 0


def _ineligible_member():
    """A member with no session makes its key take `_shared_pick` row
    by row, and its rows still reach the live member; the other key
    stays vector."""
    b = Broker(shared_strategy="round_robin")
    live = _member(b, "a", "$share/g/t")
    b.router.subscribe("ghost", "$share/g/t", SubOpts(qos=0))
    other = _member(b, "o", "$share/h/t")
    calls = []
    real = b._shared_pick
    b._shared_pick = lambda *a: calls.append(a) or real(*a)
    assert _publish(b, 3) == [2, 2, 2]
    assert len(live.sent) == 3 and len(other.sent) == 3
    assert [(real_flt, group) for _m, real_flt, group in calls] == (
        [("t", "g")] * 3
    )
    st = b.router.shared.stats()
    assert st["picks_fallback"] == 3 and st["picks_vector"] == 3
    assert st["picks_no_member"] == 0


def _no_eligible_member():
    """A key none of whose members may take a pick delivers nothing,
    as the scalar path did, and is counted."""
    b = Broker(shared_strategy="round_robin")
    for ghost in ("a", "b"):
        b.router.subscribe(ghost, "$share/g/t", SubOpts(qos=0))
    assert _publish(b, 2) == [0, 0]
    st = b.router.shared.stats()
    assert st["picks_fallback"] == st["picks_no_member"] == 2


def _check_config_refuses():
    cfg = BrokerConfig()
    assert cfg.mqtt.shared_subscription_strategy == "round_robin"
    assert not check_config(cfg)
    cfg.mqtt.shared_subscription_strategy = "fastest"
    problems = check_config(cfg)
    assert any("shared_subscription_strategy" in p and "'fastest'" in p
               for p in problems), problems
    for name in STRATEGIES:
        cfg.mqtt.shared_subscription_strategy = name
        assert not check_config(cfg)


def _runtime_update():
    """A runtime update switches the strategy and starts its state
    over; a name outside the strategies changes nothing."""
    b = Broker()
    assert b.router.shared.strategy == "round_robin"
    chans = {c: _member(b, c, "$share/g/t") for c in ("a", "b")}
    _publish(b, 1)
    assert b.router.shared._rr
    b.apply_config("mqtt.shared_subscription_strategy", "sticky")
    assert b.router.shared.strategy == "sticky"
    assert b.config.mqtt.shared_subscription_strategy == "sticky"
    assert not b.router.shared._rr
    before = [len(chans[c].sent) for c in "ab"]
    _publish(b, 6)
    got = sorted(len(chans[c].sent) - n for c, n in zip("ab", before))
    assert got == [0, 6]
    with pytest.raises(ValueError):
        b.apply_config("mqtt.shared_subscription_strategy", "fastest")
    assert b.config.mqtt.shared_subscription_strategy == "sticky"


def _served(strategy):
    """A served broker picks by its configuration, and a window with
    shared rows records the pick's span and counters."""
    async def t():
        cfg = BrokerConfig()
        cfg.listeners = [ListenerConfig(port=0)]
        if strategy is not None:
            cfg.mqtt.shared_subscription_strategy = strategy
        server = BrokerServer(cfg)
        assert server.broker.router.shared.strategy == (
            strategy or "round_robin"
        )
        await server.start()
        port = server.listeners[0].port
        try:
            subs = [TestClient(port, f"m{k}") for k in range(2)]
            for c in subs:
                await c.connect()
                await c.subscribe("$share/g/work")
            plain = TestClient(port, "plain")
            await plain.connect()
            await plain.subscribe("solo")
            pub = TestClient(port, "pub")
            await pub.connect()
            await pub.publish("solo", b"x", qos=1)
            await plain.recv_publish()
            for i in range(4):
                await pub.publish("work", str(i).encode(), qos=1)
            got = []
            for c in subs:
                while True:
                    try:
                        got.append((c.client_id, await c.recv_publish(
                            timeout=0.5)))
                    except (asyncio.TimeoutError, AssertionError):
                        break
            ring = server.broker.profiler.windows(64)
            for c in subs + [plain, pub]:
                await c.disconnect()
            return got, ring
        finally:
            await server.stop()

    got, ring = asyncio.run(t())
    assert sorted(m.payload for _c, m in got) == [b"0", b"1", b"2", b"3"]
    by = [cid for cid, _m in got]
    if strategy == "sticky":
        assert len(set(by)) == 1
    else:  # round robin: two each
        assert sorted(by) == ["m0", "m0", "m1", "m1"]
    shared = [r for r in ring if r["n_shared"]]
    assert sum(r["n_shared"] for r in shared) == 4
    assert all(r["n_shared_vector"] == r["n_shared"]
               and r["stages_us"]["shared_pick"] > 0 for r in shared)
    plain = [r for r in ring if r["n_deliveries"] and not r["n_shared"]]
    assert plain and all("shared_pick" not in r["stages_us"]
                         and r["n_shared_vector"] == 0 for r in plain)


@pytest.mark.parametrize("case", [
    _join_and_leave,
    _ineligible_member,
    _no_eligible_member,
    _check_config_refuses,
    _runtime_update,
    lambda: _served(None),
    lambda: _served("sticky"),
], ids=["join-and-leave", "ineligible-member", "no-eligible-member",
        "check-config-refuses", "runtime-update", "served-default",
        "served-sticky"])
def test_window_pick_in_the_broker(case):
    case()
