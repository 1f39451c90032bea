"""Columnar rule-engine WHERE evaluation: the rules x window matrix.

The referee suite for the three rule-eval paths:

  * device       — ``engine.rules_force = "dev"`` runs the stacked
    program through ops.match_kernel.rules_eval_batch (JAX);
  * host-vectorized — ``"host"`` pins the numpy twin;
  * scalar referee  — ``RuleEngine.eval_force = "scalar"`` pins the
    per-rule interpreter walk over the same lazy envs (the oracle).

All three must produce identical matched sets, per-rule
matched/passed/failed counters, and action invocation ORDER over
random rule sets (lowerable + interpreter-fallback, overlapping topic
filters, numeric/string/presence predicates, absent fields, malformed
JSON payloads) x random windows.  Plus kernel-vs-twin equality over
random padded columns, ``rules_rev`` cache-invalidation churn,
per-RULE (not per-window) fallback degradation, the lazy-env
allocation bound, and the chaos criterion: 100% device rules-eval
failure mid-stream still fires the correct actions via the host path,
trips the shared breaker, stops device attempts, and the background
probe re-closes it."""

import json
import random
import time

import numpy as np
import pytest

from emqx_tpu import failpoints as fp
from emqx_tpu.broker.broker import Broker
from emqx_tpu.config import BrokerConfig
from emqx_tpu.engine import MatchEngine
from emqx_tpu.message import Message
from emqx_tpu.ops.match_kernel import rules_eval_host
from emqx_tpu.rules.columns import WindowColumns
from emqx_tpu.rules.engine import FunctionAction, RuleEngine
from emqx_tpu.rules.predicate import build_stack, lower_where
from emqx_tpu.rules.runtime import LazyEnv, build_env, eval_where
from emqx_tpu.rules.sql import parse_sql


@pytest.fixture(autouse=True)
def _clear_failpoints():
    fp.clear()
    yield
    fp.clear()


def wait_until(cond, timeout=5.0, what="condition"):
    t0 = time.monotonic()
    while not cond():
        assert time.monotonic() - t0 < timeout, f"timeout: {what}"
        time.sleep(0.01)


# ------------------------------------------------ random rule worlds

# lowerable, no arithmetic, integer-valued fields: device-eligible
# under the f32 gate
_LOW_NOARITH = [
    "payload.a > 2",
    "payload.a >= payload.b",
    "payload.a = 3",
    "payload.s = 'x'",
    "payload.s != 'y'",
    "payload.s IN ('x', 'q')",
    "qos IN (1, 2)",
    "retain != 1",
    "is_null(payload.a)",
    "is_not_null(payload.s) AND payload.s != 'z'",
    "NOT (payload.a > 0) AND payload.b <= 2",
    "payload.missing = payload.gone",
    "payload.s > payload.s2",
    "topic > clientid",
    "payload.a = 1 OR payload.missing > 1",
    "payload.x != 1",
    "clientid = 'c1'",
    "payload.obj = payload.obj2",
]

# lowerable with arithmetic (float64 host twin territory)
_LOW_ARITH = [
    "payload.a + 1 >= payload.b * 2",
    "payload.a div 2 = 1",
    "payload.a mod 2 = 0",
    "payload.a / payload.b > 1",
    "payload.a - 0.5 < payload.b",
]

# non-lowerable: per-RULE interpreter fallback
_FALLBACK = [
    "regex_match(payload.s, 'x.*')",
    "lower(clientid) = 'c1'",
    "CASE WHEN qos = 0 THEN true ELSE false END",
    "topic LIKE 't/%'",
]

_FILTERS = ["t/#", "t/+/x", "t/1/x", "t/2/#", "s/only"]
_TOPICS = ["t/1/x", "t/2/x", "t/2/y", "s/only", "q/none"]


def _rand_payload(rng, ints_only=False):
    payload = {}
    if rng.random() < 0.8:
        payload["a"] = (
            rng.randint(-5, 5) if ints_only or rng.random() < 0.7
            else round(rng.uniform(-5, 5), 2)
        )
    if rng.random() < 0.7:
        payload["b"] = rng.randint(0, 3)
    if rng.random() < 0.6:
        payload["s"] = rng.choice(["x", "y", "z", "xq"])
    if rng.random() < 0.5:
        payload["s2"] = rng.choice(["x", "y"])
    if rng.random() < 0.3:
        payload["x"] = rng.choice([1, "y"])
    if rng.random() < 0.3:
        payload["obj"] = rng.choice([{"k": 1}, {"k": 2}, [1, 2]])
    if rng.random() < 0.3:
        payload["obj2"] = rng.choice([{"k": 1}, [1, 2]])
    body = json.dumps(payload).encode()
    if rng.random() < 0.08:
        body = b"not json {"
    return body


def _build_world(seed, preds):
    rng = random.Random(seed)
    rules = []
    for i in range(rng.randint(6, 14)):
        flt = rng.choice(_FILTERS)
        pred = rng.choice(preds)
        rules.append((f"r{i}", f'SELECT * FROM "{flt}" WHERE {pred}'))
    windows = []
    ints_only = preds is _LOW_NOARITH
    for _ in range(5):
        win = []
        for _ in range(rng.randint(1, 10)):
            win.append(Message(
                topic=rng.choice(_TOPICS),
                payload=_rand_payload(rng, ints_only=ints_only),
                qos=rng.randint(0, 2),
                retain=bool(rng.getrandbits(1)),
                from_client=rng.choice(["c1", "c2"]),
                timestamp=1.7e9,
            ))
        windows.append(win)
    return rules, windows


def _run_world(rules, windows, mode):
    cfg = BrokerConfig()
    cfg.engine.use_device = False
    b = Broker(config=cfg)
    if mode == "scalar":
        b.rules.eval_force = "scalar"
    else:
        b.router.engine.rules_force = mode
    fired = []
    for rid, sql in rules:
        b.rules.add_rule(
            rid, sql,
            actions=[FunctionAction(
                lambda sel, msg, rid=rid: fired.append(
                    (rid, msg.topic, bytes(msg.payload))
                )
            )],
        )
    for win in windows:
        b.publish_many([
            Message(
                topic=m.topic, payload=m.payload, qos=m.qos,
                retain=m.retain, from_client=m.from_client,
                timestamp=m.timestamp,
            )
            for m in win
        ])
    counters = {
        rid: (r.matched, r.passed, r.failed)
        for rid, r in b.rules.rules.items()
    }
    return (
        fired,
        counters,
        b.metrics.val("rules.matched"),
        b.rules.stats(),
        b.router.engine.stats(),
    )


@pytest.mark.parametrize("seed", [1, 2, 7, 23, 41, 97])
def test_three_paths_identical_mixed_rules(seed):
    """Mixed lowerable/arith/fallback registries: matched sets,
    per-rule counters and action order identical across scalar
    referee / host columns / device."""
    rules, windows = _build_world(
        seed, _LOW_NOARITH + _LOW_ARITH + _FALLBACK
    )
    scalar = _run_world(rules, windows, "scalar")
    host = _run_world(rules, windows, "host")
    dev = _run_world(rules, windows, "dev")
    for other, label in ((host, "host"), (dev, "dev")):
        assert scalar[0] == other[0], (label, "action order")
        assert scalar[1] == other[1], (label, "rule counters")
        assert scalar[2] == other[2], (label, "rules.matched")
    # the pinned paths really ran where they claim
    assert scalar[3]["scalar_windows"] > 0
    assert scalar[3]["matrix_windows"] == 0
    assert host[3]["matrix_windows"] > 0
    assert host[4]["rules_host_windows"] > 0
    assert host[4]["rules_dev_windows"] == 0


@pytest.mark.parametrize("seed", [3, 11, 29, 43, 61, 83])
def test_three_paths_identical_device_eligible(seed):
    """Arith-free integer worlds pass the f32 gate: the dev pin must
    actually reach the device kernel and stay bit-identical."""
    rules, windows = _build_world(seed, _LOW_NOARITH)
    scalar = _run_world(rules, windows, "scalar")
    dev = _run_world(rules, windows, "dev")
    assert scalar[0] == dev[0]
    assert scalar[1] == dev[1]
    assert dev[4]["rules_dev_windows"] > 0


# ------------------------------------------------- kernel vs twin

def test_kernel_vs_twin_over_random_padded_columns():
    """The padded-bucket device path (engine._rules_device) must equal
    the unpadded host twin over random programs x random windows."""
    rng = random.Random(5)
    preds = [rng.choice(_LOW_NOARITH) for _ in range(23)]
    wheres = [
        parse_sql(f'SELECT * FROM "t" WHERE {p}').where for p in preds
    ]
    stack = build_stack([(str(i), w) for i, w in enumerate(wheres)])
    assert not stack.fallback
    eng = MatchEngine(use_device=False)
    for rev in range(3):  # cache re-keys per rev
        msgs = [
            Message(
                topic=rng.choice(_TOPICS),
                payload=_rand_payload(rng, ints_only=True),
                qos=rng.randint(0, 2),
                retain=bool(rng.getrandbits(1)),
                from_client="c1",
            )
            for _ in range(rng.randint(1, 70))
        ]
        cols = WindowColumns(msgs, stack.paths, stack.lit_strings)
        host = rules_eval_host(
            stack.code, stack.a0, stack.a1, stack.a2, stack.a3,
            stack.litn, cols.lit_ranks, stack.last,
            cols.num, cols.sid, cols.err, cols.prs,
        )
        dev = eng._rules_device(stack, rev, cols)
        assert np.array_equal(host, dev)
        # and both equal the interpreter oracle (rules sharing a
        # deduped program row share its matrix row)
        for i, w in enumerate(wheres):
            want = [eval_where(w, build_env(m)) for m in msgs]
            row = stack.row_of[str(i)]
            assert host[row].tolist() == want, preds[i]


def _small_stack_and_window(n_msgs=20):
    stack = build_stack([
        (str(i), parse_sql(
            f'SELECT * FROM "t" WHERE payload.a > {i} '
            f"and payload.s = 's{i % 3}'"
        ).where)
        for i in range(5)
    ])
    msgs = [
        Message(topic="t", qos=0,
                payload=b'{"a": %d, "s": "s%d"}' % (k % 7, k % 3))
        for k in range(n_msgs)
    ]
    return stack, msgs


def test_register_file_past_the_budget_is_refused_by_shape(monkeypatch):
    """A (S, R, W) the device cannot hold is a counted policy decision
    served by the host twin: never dispatched, never breaker food."""
    import emqx_tpu.engine as E

    stack, msgs = _small_stack_and_window()
    cols = WindowColumns(msgs, stack.paths, stack.lit_strings)
    eng = MatchEngine(use_device=False)
    eng.rules_force = "dev"
    cells = E._rules_cells(stack, cols.n)
    monkeypatch.setattr(E, "RULES_DEV_MAX_CELLS", cells - 1)
    host, path = eng.rules_eval_window(stack, 0, cols)
    assert path == "host"
    st = eng.stats()
    assert st["rules_dev_refused"] == 1 and st["rules_dev_errors"] == 0
    assert st["breaker_device_errors"] == 0
    monkeypatch.setattr(E, "RULES_DEV_MAX_CELLS", cells)
    dev, path = eng.rules_eval_window(stack, 0, cols)
    assert path == "dev" and np.array_equal(host, dev)
    assert eng.stats()["rules_dev_refused"] == 1


def test_warmup_compiles_the_rules_kernel_before_traffic():
    """`warmup()` compiles the registered program at every window
    bucket, and no served window compiles again: its planes are the
    WHERE stack's paths, whatever the SELECTs read
    (`test_planes_are_the_where_paths_and_no_env_is_left_behind`)."""
    from emqx_tpu.ops.match_kernel import rules_eval_batch

    stack, msgs = _small_stack_and_window()
    eng = MatchEngine(use_device=True)  # rules only: no automaton
    eng.rules_source = lambda: (stack, 0)
    assert eng.warmup(32) == 0
    warmed = rules_eval_batch._cache_size()
    for n in (3, 20):
        cols = WindowColumns(msgs[:n], stack.paths, stack.lit_strings)
        mat, path = eng.rules_eval_window(stack, 0, cols)
        assert path == "dev"
        want = [
            [eval_where(parse_sql(
                f'SELECT * FROM "t" WHERE payload.a > {i} '
                f"and payload.s = 's{i % 3}'"
            ).where, build_env(m)) for m in msgs[:n]]
            for i in range(5)
        ]
        assert mat.tolist() == want
    assert rules_eval_batch._cache_size() == warmed


def test_planes_are_the_where_paths_and_no_env_is_left_behind(
    monkeypatch,
):
    """The window's planes cover the WHERE stack's paths alone, however
    much the lowered SELECTs read besides; and a message that only the
    matrix and a lowered SELECT read materialises no env field beyond
    ``payload`` (most build no env at all)."""
    import emqx_tpu.rules.engine as RE

    built, windows = [], []

    class Cols(WindowColumns):
        def __init__(self, msgs, paths, lits, envs=None):
            super().__init__(msgs, paths, lits, envs)
            built.append(self)
            windows.append(envs)

    monkeypatch.setattr(RE, "WindowColumns", Cols)
    eng = RuleEngine()  # standalone: the host twin
    fired = []
    act = [FunctionAction(lambda s, m: fired.append(s))]
    eng.add_rule(
        "star", 'SELECT * FROM "t/#" WHERE payload.a > 1', act
    )
    eng.add_rule(
        "wide", "SELECT payload.b AS b, payload.c.d AS d, clientid, "
        'payload FROM "t/#" WHERE qos >= 0 and payload.a > 2', act,
    )
    stack = eng._stacked()
    assert sorted(stack.paths) == [("payload", "a"), ("qos",)]
    assert len(eng._select_progs()) == 2
    msgs = [
        Message(topic="t/1", payload=b'{"a": %d, "b": "x"}' % a, qos=1)
        for a in range(5)
    ]
    assert eng.apply_batch(
        [(m, ["star", "wide"]) for m in msgs]
    ) == 5
    assert len(fired) == 5 and eng.stats()["select_scalar_rows"] == 0
    (cols,) = built
    n_p = len(stack.paths)
    assert cols.paths == tuple(stack.paths)
    for plane in (cols.num, cols.sid, cols.err, cols.prs):
        assert plane.shape == (n_p, 5)
    # a0, a1 fired nothing; a2 `star` only; a3, a4 both: the whole
    # payload (`SELECT payload`, `*`) is the one read that builds an
    # env, and it builds that one field
    (envs,) = windows
    assert [e is None for e in envs.envs] == [
        True, True, False, False, False,
    ]
    assert all(
        set(e) == {"payload"} for e in envs.envs if e is not None
    )


def test_server_start_warms_and_later_folds_follow():
    """`BrokerServer.start()` warms before a listener accepts — the
    rules kernel and the restored table's match kernels at every
    bucket up to ``batch_max`` — and a fold after it warms the same
    buckets in its own thread, so no served window compiles."""
    import asyncio

    from emqx_tpu.broker.listener import BrokerServer
    from emqx_tpu.config import BrokerConfig, ListenerConfig
    from emqx_tpu.ops.match_kernel import (
        match_batch_compact, rules_eval_batch,
    )

    cfg = BrokerConfig()
    cfg.listeners = [ListenerConfig(bind="127.0.0.1", port=0)]
    cfg.engine.use_device = True
    cfg.engine.batch_max = 64
    server = BrokerServer(cfg)
    eng = server.broker.router.engine
    server.broker.rules.add_rule(
        "r", 'SELECT * FROM "t/#" WHERE payload.a > 1', []
    )
    n_base = cfg.engine.rebuild_threshold  # starts a background build
    eng.insert_many([(f"a/{i}/+", i) for i in range(n_base)])
    rules_cold = rules_eval_batch._cache_size()

    def buckets_of(aut):
        sig = (aut.node_rows.shape[0], len(aut.fp_rows), aut.kernel_levels)
        return {s[3] for s in eng._warmed_shapes if s[:3] == sig}

    async def run():
        await server.start()
        try:
            assert eng.index_stats()["base"] == n_base + 1
            assert buckets_of(eng._aut) == {16, 32, 64}
            assert rules_eval_batch._cache_size() == rules_cold + 3
            for i in range(eng.delta_aut_threshold):
                eng.insert(f"b/{i}/#", n_base + 1 + i)
            while eng.index_stats()["folding"]:
                await asyncio.sleep(0.01)
            assert eng.index_stats()["folded"] == eng.delta_aut_threshold
            assert buckets_of(eng._dtier[0]) == {16, 32, 64}
            warmed = match_batch_compact._cache_size()
            got = eng.match_batch(
                [f"a/{i}/x" for i in range(40)] + ["b/7/y"]
            )
            assert got[3] == {3} and got[40] == {n_base + 8}
            assert match_batch_compact._cache_size() == warmed
        finally:
            await server.stop()

    asyncio.run(run())


def test_host_twin_block_chunking_and_program_dedup():
    """Registries past RULES_HOST_BLOCK evaluate in slabs (distinct
    literals defeat dedup), and identical programs share one row."""
    n_rules = 2048 + 37
    stack = build_stack([
        (
            str(i),
            parse_sql(
                f'SELECT * FROM "t" WHERE payload.a > {i}'
            ).where,
        )
        for i in range(n_rules)
    ])
    assert stack.n_rules == n_rules  # all distinct: no dedup
    msgs = [
        Message(topic="t", payload=b'{"a": %d}' % a, qos=0)
        for a in (0, 1, 500, 2090)
    ]
    cols = WindowColumns(msgs, stack.paths, stack.lit_strings)
    mat = rules_eval_host(
        stack.code, stack.a0, stack.a1, stack.a2, stack.a3,
        stack.litn, cols.lit_ranks, stack.last,
        cols.num, cols.sid, cols.err, cols.prs,
    )
    assert mat.shape == (n_rules, 4)
    for i in (0, 1, 1000, 2048, 2084):
        assert mat[i].tolist() == [0 > i, 1 > i, 500 > i, 2090 > i]
    # identical programs dedup to ONE matrix row, counters stay exact
    w = parse_sql('SELECT * FROM "t" WHERE payload.a > 1').where
    dedup = build_stack([(str(i), w) for i in range(500)])
    assert dedup.n_lowered == 500 and dedup.n_rules == 1
    assert all(v == 0 for v in dedup.row_of.values())


# --------------------------------------------- registry churn / rev

def test_rules_rev_invalidates_stack_and_device_cache():
    cfg = BrokerConfig()
    cfg.engine.use_device = False
    b = Broker(config=cfg)
    b.router.engine.rules_force = "dev"
    hits = []
    b.rules.add_rule(
        "r1", 'SELECT * FROM "t/#" WHERE payload.v > 1',
        actions=[FunctionAction(lambda s, m: hits.append("r1"))],
    )
    rev1 = b.rules.rules_rev
    stack1 = b.rules._stacked()
    assert b.rules._stacked() is stack1  # cached within a rev
    b.publish(Message(topic="t/a", payload=b'{"v": 5}'))
    assert hits == ["r1"]
    # churn: add, remove, disable — each bumps rules_rev
    b.rules.add_rule(
        "r2", 'SELECT * FROM "t/#" WHERE payload.v > 10',
        actions=[FunctionAction(lambda s, m: hits.append("r2"))],
    )
    assert b.rules.rules_rev > rev1
    assert b.rules._stacked() is not stack1
    b.publish(Message(topic="t/b", payload=b'{"v": 50}'))
    assert hits == ["r1", "r1", "r2"]
    b.rules.enable_rule("r1", False)
    b.publish(Message(topic="t/c", payload=b'{"v": 50}'))
    assert hits == ["r1", "r1", "r2", "r2"]
    b.rules.remove_rule("r2")
    b.rules.enable_rule("r1", True)
    b.publish(Message(topic="t/d", payload=b'{"v": 50}'))
    assert hits == ["r1", "r1", "r2", "r2", "r1"]
    # the device program cache re-keyed on every rev it saw
    assert b.router.engine._rul_prog_cache is not None


def test_single_regex_rule_degrades_per_rule_not_per_window():
    """Acceptance: one non-lowerable rule must not push the whole
    registry off the matrix path."""
    cfg = BrokerConfig()
    cfg.engine.use_device = False
    b = Broker(config=cfg)
    fired = []
    for i in range(20):
        b.rules.add_rule(
            f"low{i}", f'SELECT * FROM "t/#" WHERE payload.v > {i}',
            actions=[FunctionAction(
                lambda s, m, i=i: fired.append(f"low{i}")
            )],
        )
    b.rules.add_rule(
        "rx", "SELECT * FROM \"t/#\" WHERE regex_match(payload.s, 'ab.*')",
        actions=[FunctionAction(lambda s, m: fired.append("rx"))],
    )
    st = b.rules.stats()
    assert st["lowered"] == 20 and st["fallback"] == 1
    b.publish(Message(topic="t/1", payload=b'{"v": 10, "s": "abc"}'))
    st = b.rules.stats()
    assert st["matrix_windows"] == 1  # window stayed on the matrix
    assert st["scalar_windows"] == 0
    assert st["fallback_rule_evals"] == 1  # only rx walked the envs
    assert sorted(fired) == sorted(
        [f"low{i}" for i in range(10)] + ["rx"]
    )


# ------------------------------------------------------- lazy envs

def test_lazy_env_materializes_only_referenced_fields():
    """Satellite: a 1-field fallback rule over a wide payload must
    materialize one env field (payload), decode its JSON once, and
    never build the full 13-field env."""
    eng = RuleEngine()  # standalone: no broker
    eng.add_rule(
        "rx", "SELECT payload.f1 AS v FROM \"w/#\" "
        "WHERE regex_match(payload.f1, 'x.*')",
    )
    wide = {f"f{k}": "x%d" % k for k in range(100)}
    decodes = []
    orig_loads = json.loads

    def counting_loads(s, *a, **kw):
        decodes.append(1)
        return orig_loads(s, *a, **kw)

    json.loads = counting_loads
    try:
        msgs = [
            Message(topic="w/1", payload=json.dumps(wide).encode())
            for _ in range(4)
        ]
        hits = eng.apply_batch([(m, ["rx"]) for m in msgs])
    finally:
        json.loads = orig_loads
    assert hits == 4
    assert len(decodes) == 4  # one decode per message, window-wide
    rule = eng.rules["rx"]
    assert rule.passed == 4


def test_lazy_env_entry_count_regression():
    """The env dict itself stays thin: len(env) counts materialized
    fields, and a single-field predicate stays at 1."""
    m = Message(
        topic="w/1",
        payload=json.dumps(
            {f"f{k}": k for k in range(200)}
        ).encode(),
        qos=1,
    )
    env = LazyEnv(m)
    w = parse_sql('SELECT * FROM "w" WHERE payload.f7 > 3').where
    assert eval_where(w, env)
    assert len(env) == 1  # payload only — not the 13-field build_env
    assert set(env) == {"payload"}
    # full build_env for comparison materializes everything
    assert len(build_env(m)) == 13


# --------------------------------------------------- chaos: breaker

def test_device_rules_failure_midstream_breaker_and_probe():
    """Acceptance chaos criterion (FP301 seam dispatch.rules.device):
    100% device rules-eval failure mid-stream still fires the correct
    actions via the host path, trips the shared PR 1 breaker, stops
    device attempts, and the background probe re-closes it once the
    fault clears."""
    # use_device stays AUTO (the shipping default): unmeasured small
    # match windows serve on host — so a device-match success cannot
    # reset the consecutive-failure count between rules windows —
    # while the heal probe can still force the device path
    cfg = BrokerConfig()
    b = Broker(config=cfg)
    eng = b.router.engine
    eng.rules_force = "dev"
    eng.breaker_probe_interval = 3600.0
    fired = []
    for i in range(6):
        b.rules.add_rule(
            f"r{i}", f'SELECT * FROM "t/#" WHERE payload.v >= {i}',
            actions=[FunctionAction(
                lambda s, m, i=i: fired.append(i)
            )],
        )
    # fold the rule filters into the base automaton: the heal probe
    # re-tries DEVICE MATCHING, which needs a non-empty device table
    eng.rebuild()

    def pub(k):
        b.publish_many([Message(
            topic=f"t/{k}", payload=b'{"v": 3}', qos=0,
        )])

    pub(0)
    assert eng._rul_stats["dev_windows"] >= 1
    assert sorted(fired) == [0, 1, 2, 3]  # v=3 passes rules 0..3
    trips = []
    eng.on_breaker_trip = lambda info: trips.append(info)
    fp.configure("dispatch.rules.device", "error", prob=1.0)
    fired.clear()
    for k in range(4):  # breaker_threshold is 3
        pub(k)
    # every window still fired the correct actions via host columns
    assert sorted(fired) == sorted([0, 1, 2, 3] * 4)
    assert eng.breaker_open is True
    assert trips and trips[0]["reason"] == "rules"
    assert eng._rul_stats["dev_errors"] >= 3
    # breaker open: no further device attempts, still firing
    errs = eng._rul_stats["dev_errors"]
    fired.clear()
    pub(9)
    assert sorted(fired) == [0, 1, 2, 3]
    assert eng._rul_stats["dev_errors"] == errs
    # fault clears: a rules window schedules the probe, which
    # re-closes the shared breaker
    fp.clear("dispatch.rules.device")
    eng.breaker_probe_interval = 0.0
    pub(10)
    wait_until(lambda: not eng.breaker_open, what="breaker re-close")
    dev_before = eng._rul_stats["dev_windows"]
    pub(11)
    assert eng._rul_stats["dev_windows"] > dev_before


# -------------------------------------------------- policy / knobs

def test_rules_auto_first_device_window_warms_not_records():
    """EWMA hygiene: the first device rules window pays the JIT
    compile and must not seed the cost estimate."""
    where = parse_sql('SELECT * FROM "t" WHERE payload.v > 1').where
    stack = build_stack([(str(i), where) for i in range(8)])
    msgs = [
        Message(topic="t", payload=b'{"v": 2}') for _ in range(4)
    ]
    cols = WindowColumns(msgs, stack.paths, stack.lit_strings)
    eng = MatchEngine(use_device=False)
    eng.rules_force = "dev"
    _, path1 = eng.rules_eval_window(stack, 1, cols)
    assert path1 == "dev"
    assert eng._rul_dev_us is None  # compile window not recorded
    _, path2 = eng.rules_eval_window(stack, 1, cols)
    assert path2 == "dev"
    assert eng._rul_dev_us is not None


def test_matrix_env_kill_switch(monkeypatch):
    monkeypatch.setenv("EMQX_TPU_NO_RULES_MATRIX", "1")
    cfg = BrokerConfig()
    cfg.engine.use_device = False
    b = Broker(config=cfg)
    hits = []
    b.rules.add_rule(
        "r", 'SELECT * FROM "t/#" WHERE payload.v > 1',
        actions=[FunctionAction(lambda s, m: hits.append(1))],
    )
    b.publish(Message(topic="t/a", payload=b'{"v": 2}'))
    assert hits == [1]
    st = b.rules.stats()
    assert st["matrix_enabled"] is False
    assert st["scalar_windows"] == 1 and st["matrix_windows"] == 0


def test_arith_and_f32_unsafe_windows_stay_on_host_twin():
    """The f32 gate binds even under a dev pin: arith programs and
    f32-lossy columns take the float64 host twin."""
    eng = MatchEngine(use_device=False)
    eng.rules_force = "dev"
    # arith program
    w = parse_sql('SELECT * FROM "t" WHERE payload.a + 1 > 2').where
    stack = build_stack([("r", w)])
    msgs = [Message(topic="t", payload=b'{"a": 5}')]
    cols = WindowColumns(msgs, stack.paths, stack.lit_strings)
    mat, path = eng.rules_eval_window(stack, 1, cols)
    assert path == "host" and mat[0, 0]
    # f32-lossy column (millisecond timestamp)
    w2 = parse_sql(
        'SELECT * FROM "t" WHERE timestamp > 1753000000100'
    ).where
    stack2 = build_stack([("r", w2)])
    m = Message(topic="t", payload=b"{}")
    m.timestamp = 1753000000.2
    cols2 = WindowColumns([m], stack2.paths, stack2.lit_strings)
    mat2, path2 = eng.rules_eval_window(stack2, 2, cols2)
    assert path2 == "host" and mat2[0, 0]


def _standalone_parity(sql, payloads):
    """One rule x given payloads through the matrix path AND the
    scalar referee; both must agree with the interpreter."""
    got = {}
    for force in ("scalar", None):
        eng = RuleEngine()
        eng.eval_force = force
        eng.add_rule("r", sql)
        msgs = [Message(topic="w/1", payload=p) for p in payloads]
        got[force] = eng.apply_batch([(m, ["r"]) for m in msgs])
        counters = eng.rules["r"]
        got[(force, "ctr")] = (counters.matched, counters.passed)
    assert got["scalar"] == got[None], sql
    assert got[("scalar", "ctr")] == got[(None, "ctr")], sql
    return got[None]


def test_review_no_var_path_registry_does_not_crash():
    """Code-review r1: a registry whose only lowered predicate
    references ZERO var paths (constant compound equality) must not
    IndexError on the zero-path err plane."""
    hits = _standalone_parity(
        'SELECT * FROM "w/#" WHERE 1 + 1 = 2', [b"{}", b"{}"]
    )
    assert hits == 2


def test_review_string_concat_plus_falls_back_per_rule():
    """Code-review r1: '+' over two could-be-string operands CONCATS
    in the interpreter — such rules must degrade to the interpreter,
    while single-var arithmetic stays lowerable."""
    w = parse_sql(
        'SELECT * FROM "w" WHERE payload.a + payload.b = payload.c'
    ).where
    assert lower_where(w) is None
    assert lower_where(
        parse_sql('SELECT * FROM "w" WHERE payload.a + 1 > 2').where
    ) is not None
    hits = _standalone_parity(
        'SELECT * FROM "w/#" WHERE payload.a + payload.b = payload.c',
        [b'{"a": "2", "b": "3", "c": "23"}', b'{"a": 1, "b": 2, "c": 3}'],
    )
    assert hits == 2  # concat match AND numeric match
    _standalone_parity(
        'SELECT * FROM "w/#" WHERE payload.a + payload.b != 5',
        [b'{"a": "2", "b": "3"}'],
    )


def test_review_literal_nan_payload_degrades_window():
    """Code-review r1: json.loads accepts a literal NaN, which would
    alias the num lane's sentinel — the window degrades to the
    interpreter and stays bit-identical (NOT(nan > 0) is True)."""
    hits = _standalone_parity(
        'SELECT * FROM "w/#" WHERE NOT (payload.a > 0)',
        [b'{"a": NaN}', b'{"a": 1}', b'{"a": -1}'],
    )
    assert hits == 2  # NaN row matches via NOT, like the interpreter


def test_review_nested_bool_number_term_equality():
    """Code-review r1: Python container equality has True == 1; the
    canonical term encoding must agree."""
    hits = _standalone_parity(
        'SELECT * FROM "w/#" WHERE payload.a = payload.b',
        [b'{"a": [true], "b": [1]}', b'{"a": [true], "b": [2]}'],
    )
    assert hits == 1


def test_lowering_rejects_non_lowerable_shapes():
    for src in (
        "lower(clientid) = 'c1'",
        "CASE WHEN qos = 0 THEN true ELSE false END",
        "topic LIKE 't/%'",
        "payload.s > 'abc'",  # string ordering vs literal
    ):
        w = parse_sql(f'SELECT * FROM "t" WHERE {src}').where
        assert lower_where(w) is None, src
    # and WHERE-less rules lower to an always-true row
    prog = lower_where(None)
    assert prog is not None and len(prog.steps) == 1
