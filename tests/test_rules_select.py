"""Batched SELECT lowering + compiled templates: referee equality.

The output half of the rule matrix (PR 20).  Three contracts:

  * compiled templates are BIT-identical to the pre-PR regex renderer
    (a verbatim copy of it is the fuzz oracle), in both scalar
    (`TemplateProgram.render`) and column (`render_rows`) form;
  * batched SELECT + window-shaped actions produce exactly the same
    per-(rule, action) output streams as the scalar interpreter
    referee (`select_force="scalar"`) over seeded random worlds
    mixing lowerable and degraded rules, templated and JSON sink
    payloads, aggregate pushes, malformed payloads and absent fields;
  * the arithmetic/typing edge cases the interpreter pins (int-ness
    through json.dumps, string ``+`` concat, div-by-zero -> None,
    error-vs-missing operands) hold through the compiled lane.
"""

import json
import random
import re

import pytest

from emqx_tpu.broker.broker import Broker
from emqx_tpu.config import BrokerConfig
from emqx_tpu.message import Message
from emqx_tpu.rules.engine import (
    AggregateAction, ConsoleAction, FunctionAction, RepublishAction,
    RuleEngine, SinkAction, render_template,
)
from emqx_tpu.rules.runtime import (
    _UNREAD, LazyEnv, WindowEnvs, eval_select,
)
from emqx_tpu.rules.select import (
    TemplateProgram, build_select_stack, compile_select,
    compile_template, materialize_rows,
)
from emqx_tpu.rules.sql import parse_sql
from emqx_tpu.aggregator import Aggregator


# ------------------------------------------------ the pre-PR renderer
# (verbatim copy of the regex-walk render_template this PR replaced —
# the oracle the compiled form must match byte for byte)

_PLACEHOLDER = re.compile(r"\$\{([^}]+)\}")


def _old_render_template(template, data):
    def sub(m):
        cur = data
        for part in m.group(1).split("."):
            if isinstance(cur, dict) and part in cur:
                cur = cur[part]
            else:
                return "undefined"
        if isinstance(cur, bool):
            return "true" if cur else "false"
        if isinstance(cur, bytes):
            return cur.decode("utf-8", "replace")
        if isinstance(cur, float) and cur.is_integer():
            return str(int(cur))
        if isinstance(cur, (dict, list)):
            return json.dumps(cur)
        return str(cur)

    return _PLACEHOLDER.sub(sub, template)


_FUZZ_VALUES = [
    0, 1, -3, 2.5, 4.0, -0.0, True, False, None, "", "x", "a%sb",
    "100% done", b"raw\xffbytes", {"k": 1, "j": [1, "s"]}, [1, 2.5],
    {"nested": {"deep": True}},
]

# values legal INSIDE a dict/list a placeholder may resolve to — the
# old renderer json.dumps'es containers, so bytes may only appear as
# a leaf, never nested (that crashed the old renderer too)
_FUZZ_NESTED = [v for v in _FUZZ_VALUES if not isinstance(v, bytes)]

_FUZZ_KEYS = ["a", "b", "payload", "topic", "v", "s"]


def _fuzz_template(rng):
    parts = []
    for _ in range(rng.randint(0, 6)):
        kind = rng.random()
        if kind < 0.45:
            parts.append(rng.choice(
                ["lit ", "x%sy", "100%", "{", "}", "$", "${", "a.b ",
                 "", "plain-literal "]
            ))
        else:
            depth = rng.randint(1, 3)
            parts.append(
                "${" + ".".join(
                    rng.choice(_FUZZ_KEYS) for _ in range(depth)
                ) + "}"
            )
    return "".join(parts)


def _fuzz_data(rng, depth=0):
    d = {}
    for k in _FUZZ_KEYS:
        if rng.random() < 0.6:
            if depth < 2 and rng.random() < 0.3:
                d[k] = _fuzz_data(rng, depth + 1)
            elif depth:
                d[k] = rng.choice(_FUZZ_NESTED)
            else:
                d[k] = rng.choice(_FUZZ_VALUES)
    return d


@pytest.mark.parametrize("seed", [3, 11, 29, 57])
def test_compiled_template_matches_old_renderer_fuzz(seed):
    rng = random.Random(seed)
    for _ in range(400):
        tmpl = _fuzz_template(rng)
        data = _fuzz_data(rng)
        expect = _old_render_template(tmpl, data)
        prog = TemplateProgram(tmpl)
        assert prog.render(data) == expect, tmpl
        # the public entry point rides the cache
        assert render_template(tmpl, data) == expect, tmpl


@pytest.mark.parametrize("seed", [5, 17])
def test_render_rows_matches_per_row_render(seed):
    rng = random.Random(seed)
    for _ in range(120):
        tmpl = _fuzz_template(rng)
        prog = TemplateProgram(tmpl)
        rows = [_fuzz_data(rng) for _ in range(rng.randint(1, 7))]
        # column view: union of head keys, column per key
        heads = set()
        for part in prog.parts:
            if part.__class__ is not str:
                heads.add(part[0])
        cols = {
            h: [r.get(h) for r in rows]
            for h in heads
            if any(h in r for r in rows)
        }
        got = prog.render_rows(cols, len(rows))
        # render_rows reads missing-in-SOME-rows keys through the
        # column (None cells); mirror that view in the scalar twin
        twin = [
            {h: c[i] for h, c in cols.items()}
            for i in range(len(rows))
        ]
        assert got == [prog.render(t) for t in twin], tmpl


def test_compile_template_caches():
    a = compile_template("x ${v} y")
    b = compile_template("x ${v} y")
    assert a is b
    assert a.n_slots == 1


# ------------------------------------------- lowering unit behavior


def test_compile_select_covers_and_rejects():
    lowered = [
        "SELECT * FROM \"t/#\"",
        "SELECT payload.a AS a, topic FROM \"t/#\"",
        "SELECT payload.a + 1 AS b, 'k' AS lit FROM \"t/#\"",
        "SELECT payload.a * 2 + payload.b AS c FROM \"t/#\"",
        "SELECT payload.a div 2 AS d, payload.a mod 2 AS e "
        "FROM \"t/#\"",
        "SELECT -payload.a AS n FROM \"t/#\"",
    ]
    degraded = [
        "SELECT lower(payload.s) AS l FROM \"t/#\"",
        "SELECT CASE WHEN qos = 0 THEN 1 ELSE 2 END AS c "
        "FROM \"t/#\"",
        "SELECT payload.a > 1 AS cmp FROM \"t/#\"",
    ]
    for sql in lowered:
        assert compile_select(parse_sql(sql)) is not None, sql
    for sql in degraded:
        assert compile_select(parse_sql(sql)) is None, sql


def test_select_slots_name_paths_and_read_the_fired_rows_only():
    """A program's slots name var paths (no plane rows), each with
    where a window reads it; `materialize_rows` reads them for the
    rows asked for and touches no other message."""
    parsed = parse_sql(
        'SELECT payload.a AS a, qos, payload, flags.retain AS r '
        'FROM "t/#"'
    )
    progs = build_select_stack([
        ("r1", parsed),
        ("r2", parse_sql('SELECT lower(topic) AS l FROM "t/#"')),
    ])
    assert set(progs) == {"r1"}  # r2 stays with the interpreter
    prog = progs["r1"]
    assert prog.paths == (
        ("payload", "a"), ("qos",), ("payload",), ("flags", "retain"),
    )
    assert prog.reads == (
        ("json", ("a",)), ("msg", "qos"), ("env", ("payload",)),
        ("env", ("flags", "retain")),
    )
    msgs = [
        Message(topic="t/1", payload=b'{"a": %d}' % i, qos=i % 3)
        for i in range(6)
    ]
    envs = WindowEnvs(msgs)
    rows = [1, 4]
    names, cols = materialize_rows(prog, envs, rows)
    assert names == ["a", "qos", "payload", "r"]
    assert [dict(zip(names, r)) for r in zip(*cols)] == [
        eval_select(parsed, LazyEnv(msgs[i])) for i in rows
    ]
    assert type(cols[2][0]) is str  # the payload flattens to text
    # only the fired rows were read: an env (for the two paths that
    # need one) and a decode each, nothing for the other four
    assert [e is not None for e in envs.envs] == [
        i in rows for i in range(6)
    ]
    assert [d is not _UNREAD for d in envs._data] == [
        i in rows for i in range(6)
    ]


# ----------------------------------- seeded-world referee equality


class FakeWorker:
    """Just enough of BufferWorker for the engine's sink handoff."""

    def __init__(self):
        self.queries = []

    def enqueue(self, q):
        self.queries.append(q)
        return True

    def enqueue_batch(self, qs):
        self.queries.extend(qs)
        return 0


_SELECTS = [
    "*",
    "payload.a AS a, topic",
    "payload.a + payload.b AS s, payload.a * 2 AS d, 'k' AS lit",
    "payload.s + '!' AS cat, clientid",
    "payload.a / payload.b AS q, payload.a mod 2 AS m",
    "payload.obj AS o, payload.a AS a",
    "payload.a AS x, payload.b AS x",  # duplicate alias
    "-payload.a AS neg, 7 AS seven",
    # degraded per rule (function call / CASE): scalar interpreter
    "lower(clientid) AS l, payload.a AS a",
    "CASE WHEN qos = 0 THEN 'q0' ELSE 'qn' END AS c",
]

_WHERES = [
    "payload.a >= 0", "payload.b > 0", "qos >= 0",
    "payload.s = 'x' OR payload.a < 2", "is_not_null(payload.a)",
]

_TEMPLATES = [
    None,  # JSON dump of the selected columns
    '{"t":"${topic}","a":${a}}',
    "v=${a} s=${s} cat=${cat} missing=${nope}",
    "${o} ${x} ${neg}",
]

_FILTERS = ["t/#", "t/+/x", "t/1/x", "t/2/#"]
_TOPICS = ["t/1/x", "t/2/x", "t/2/y", "q/none"]


def _world(seed):
    rng = random.Random(seed)
    rules = []
    for i in range(rng.randint(5, 10)):
        sel = rng.choice(_SELECTS)
        rules.append((
            f"r{i}",
            f'SELECT {sel} FROM "{rng.choice(_FILTERS)}" '
            f"WHERE {rng.choice(_WHERES)}",
            rng.choice(_TEMPLATES),
        ))
    windows = []
    for _ in range(6):
        win = []
        for _ in range(rng.randint(1, 12)):
            payload = {}
            if rng.random() < 0.85:
                payload["a"] = (
                    rng.randint(-5, 5) if rng.random() < 0.7
                    else round(rng.uniform(-5, 5), 2)
                )
            if rng.random() < 0.7:
                payload["b"] = rng.randint(0, 3)
            if rng.random() < 0.6:
                payload["s"] = rng.choice(["x", "y", "zz"])
            if rng.random() < 0.3:
                payload["obj"] = rng.choice(
                    [{"k": 1}, [1, 2], {"k": {"d": True}}]
                )
            body = json.dumps(payload).encode()
            if rng.random() < 0.08:
                body = b"not json {"
            win.append(Message(
                topic=rng.choice(_TOPICS), payload=body,
                qos=rng.randint(0, 2),
                retain=bool(rng.getrandbits(1)),
                from_client=rng.choice(["c1", "c2"]),
                timestamp=1.7e9,
            ))
        windows.append(win)
    return rules, windows


def _run_select_world(rules, windows, force):
    cfg = BrokerConfig()
    cfg.engine.use_device = False
    b = Broker(config=cfg)
    b.rules.select_force = force
    sinks, aggs = {}, {}
    for rid, sql, tmpl in rules:
        sinks[rid] = FakeWorker()
        b.resources._workers[f"sink:{rid}"] = sinks[rid]
        records = []
        aggs[rid] = records
        agg = Aggregator(
            lambda k, body: None, interval_s=1e9, max_records=10**9
        )
        real_push = agg.push
        agg.push = lambda rs, _rp=real_push, _rec=records: (
            _rec.extend(rs), _rp(rs)
        )[1]
        b.rules.add_rule(rid, sql, actions=[
            SinkAction(f"sink:{rid}", payload=tmpl),
            AggregateAction(agg),
        ])
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
        rid: (r.matched, r.passed, r.actions_success,
              r.actions_failed)
        for rid, r in b.rules.rules.items()
    }
    return (
        {rid: w.queries for rid, w in sinks.items()},
        aggs,
        counters,
        b.rules.stats(),
    )


@pytest.mark.parametrize("seed", [2, 9, 13, 31, 71])
def test_batched_select_bit_identical_to_scalar_referee(seed):
    """Per-(rule, action) sink query streams, aggregate record
    streams and action counters identical between the batched lane
    and the scalar interpreter referee, over worlds mixing lowered
    and degraded rules."""
    rules, windows = _world(seed)
    ref = _run_select_world(rules, windows, "scalar")
    bat = _run_select_world(rules, windows, "batched")
    assert ref[0] == bat[0], "sink query streams differ"
    assert ref[1] == bat[1], "aggregate record streams differ"
    assert ref[2] == bat[2], "rule counters differ"
    # the lanes really ran where they claim
    assert ref[3]["select_batched_rows"] == 0
    if bat[3]["select_lowered"] and any(
        n for n in ref[0].values()
    ):
        assert (
            bat[3]["select_batched_rows"] > 0
            or bat[3]["select_scalar_rows"] > 0
        )


def test_int_ness_and_arith_edges_through_batched_lane():
    """The typing contract: json.dumps(5) != json.dumps(5.0), string
    '+' concat, div-by-zero -> None field, missing operand -> None,
    lookup ERROR operand -> None — identical in both lanes."""
    rules = [(
        "r1",
        "SELECT payload.v * 2 + 1 AS v2, payload.s + '-t' AS cat, "
        'payload.v / payload.z AS dz, payload.v + payload.nope AS mn '
        'FROM "t/#" WHERE is_not_null(payload.v)',
        None,
    )]
    msgs = [
        Message(topic="t/a", payload=json.dumps(
            {"v": 2, "s": "x", "z": 0}
        ).encode()),
        Message(topic="t/a", payload=json.dumps(
            {"v": 2.0, "s": "y", "z": 2}
        ).encode()),
        Message(topic="t/a", payload=b"not json {"),
    ]
    ref = _run_select_world(rules, [msgs], "scalar")
    bat = _run_select_world(rules, [msgs], "batched")
    assert ref[0] == bat[0]
    q0 = json.loads(bat[0]["r1"][0])
    assert q0["v2"] == 5 and json.dumps(q0["v2"]) == "5"  # int stays
    assert q0["cat"] == "x-t"
    assert q0["dz"] is None  # div by zero
    assert q0["mn"] is None  # missing operand
    q1 = json.loads(bat[0]["r1"][1])
    assert q1["v2"] == 5.0 and json.dumps(q1["v2"]) == "5.0"


def test_select_force_and_ewma_breaker_stats():
    """select_force pins the lane; the cost-EWMA breaker state is
    visible in stats()."""
    cfg = BrokerConfig()
    cfg.engine.use_device = False
    b = Broker(config=cfg)
    w = FakeWorker()
    b.resources._workers["s"] = w
    b.rules.add_rule(
        "r1", 'SELECT payload.a AS a FROM "t/#" WHERE payload.a > 0',
        actions=[SinkAction("s")],
    )
    msgs = [
        Message(topic="t/1", payload=b'{"a": 3}') for _ in range(4)
    ]
    b.rules.select_force = "scalar"
    b.publish_many(list(msgs))
    st = b.rules.stats()
    assert st["select_scalar_rows"] == 4
    assert st["select_batched_rows"] == 0
    b.rules.select_force = "batched"
    b.publish_many(list(msgs))
    st = b.rules.stats()
    assert st["select_batched_rows"] == 4
    assert st["select_lowered"] == 1
    assert "select_batch_disabled" in st
    assert "select_batched_us_ewma" in st
    assert len(w.queries) == 8


# ------------------- the per-rule run for every action kind (PR 29)
# A rule whose SELECT lowered takes the per-rule run whatever its
# actions; the interpreter (`select_force = "scalar"`) is the referee
# for the sequence of calls, not for their totals alone.

_RUN_PAYLOADS = [
    b"not json {", b"[1, 2]", b"5", b'"text"', b"null", b"",
    b'{"b": 1}',  # lacks the key
    b'{"a": {"b": {"c": [1, {"d": null}]}}, "z": 0}',
    b'{"a": 1267650600228229401496703205376, "z": 3}',  # 2**100
    b'{"a": 9007199254740993, "z": 2, "s": "x"}',  # 2**53 + 1
    b'{"a": 2.5, "z": 0.0, "s": "y"}',
    b'{"a": true, "z": false}', b'{"a": null, "z": null}',
    b'{"a": "str", "z": 1, "s": 7}',
    b'{"a": 4, "z": 2, "s": "zz", "obj": {"k": [1, 2]}}',
    b'\xff\xfe{"a": 1}',  # not UTF-8
]

_RUN_SELECTS = [
    "*",
    "*, payload.a AS topic",  # an alias over a star field
    "payload.a AS x, payload.z AS x, topic AS x",  # aliases collide
    "payload",
    "payload AS p, payload.a.b AS ab, payload.a.b.c AS abc",
    "payload.a / payload.z AS q, payload.s + 1 AS bad, -payload.a AS n",
    "payload.a + payload.z AS s, payload.a div payload.z AS d, 'k' AS l",
    "flags.retain AS r, flags AS f, topic.x AS tx, nope AS nope",
    "payload.obj AS o, payload.obj.k AS k, clientid, username, qos",
    "timestamp, id, node, event, retain, pub_props",
    # not lowered: the interpreter serves these in both runs
    "lower(clientid) AS l, payload.a AS a",
]

_RUN_WHERES = [
    None, "qos >= 0", "payload.z >= 0", "is_not_null(payload.a)",
    "payload.s = 'x' OR qos < 2",
    "regex_match(topic, 't/.*')",  # WHERE stays with the interpreter
]


def _action_world(seed):
    rng = random.Random(seed)
    rules = []
    for i in range(rng.randint(6, 12)):
        where = rng.choice(_RUN_WHERES)
        sql = (
            f'SELECT {rng.choice(_RUN_SELECTS)} '
            f'FROM "{rng.choice(_FILTERS)}"'
            + (f" WHERE {where}" if where else "")
        )
        rules.append((f"r{i}", sql, [
            rng.choice(["fn", "fn", "raise", "console", "republish",
                        "sink"])
            for _ in range(rng.randint(1, 3))
        ]))
    windows = [
        [
            (rng.choice(_TOPICS), rng.choice(_RUN_PAYLOADS),
             rng.randint(0, 2), bool(rng.getrandbits(1)),
             rng.choice(["c1", "c2"]))
            for _ in range(rng.randint(1, 14))
        ]
        for _ in range(5)
    ]
    return rules, windows


def _run_action_world(rules, windows, force):
    """The world through one lane: every action call in order, the
    per-rule counters, the broker's metrics, the lane's row counts."""
    cfg = BrokerConfig()
    cfg.engine.use_device = False
    b = Broker(config=cfg)
    b.rules.select_force = force
    calls = []
    sinks = {}

    def fn_action(tag, fail):
        def fn(selected, msg):
            # the dict as handed over: a later mutation by another
            # action of the firing would show in the copy of that one
            calls.append((tag, list(selected.items()), msg.topic,
                          msg.payload))
            if fail and len(calls) % 3 == 0:
                raise RuntimeError("one action of one firing")
            selected["seen_by"] = tag

        return FunctionAction(fn)

    for rid, sql, kinds in rules:
        actions = []
        for k, kind in enumerate(kinds):
            if kind in ("fn", "raise"):
                actions.append(
                    fn_action(f"{rid}.{k}", kind == "raise")
                )
            elif kind == "console":
                actions.append(ConsoleAction())
            elif kind == "republish":
                actions.append(RepublishAction(
                    topic="out/${topic}", payload="${payload} ${x}",
                ))
            else:
                # a worker of its own: a rule of sinks alone hands a
                # window over action-major, so only the stream of one
                # (rule, action) has the referee's order
                name = f"sink:{rid}.{k}"
                sinks[name] = b.resources._workers[name] = FakeWorker()
                actions.append(SinkAction(name, payload="${topic}"))
        b.rules.add_rule(rid, sql, actions=actions)
    # what a republish lands on: its order is part of the sequence
    b.rules.add_rule(
        "echo", 'SELECT topic, payload FROM "out/#"',
        actions=[fn_action("echo", False)],
    )
    for w, win in enumerate(windows):
        b.publish_many([
            Message(topic=t, payload=p, qos=q, retain=r,
                    from_client=c, timestamp=1.7e9,
                    mid=b"%08d%08d" % (w, k))
            for k, (t, p, q, r, c) in enumerate(win)
        ])
    counters = {
        rid: (r.matched, r.passed, r.failed, r.actions_success,
              r.actions_failed)
        for rid, r in b.rules.rules.items()
    }
    # ``actions.batched`` is the bulk hand-over's own count (a rule
    # of sinks alone), which the referee never makes
    metrics = {
        k: v for k, v in b.metrics.all().items()
        if v and k != "actions.batched"
    }
    queries = {name: w.queries for name, w in sinks.items()}
    return calls, queries, counters, metrics, b.rules.stats()


@pytest.mark.parametrize("seed", [1, 4, 7, 12, 23, 42, 77, 101])
def test_per_rule_run_equals_interpreter_call_for_call(seed):
    """Function, console, republish and sink actions in one rule: the
    per-rule run makes the interpreter's calls, in its order, with
    its ``selected`` (keys, their order, values and their types), and
    leaves the rule and broker counters equal."""
    rules, windows = _action_world(seed)
    ref = _run_action_world(rules, windows, "scalar")
    run = _run_action_world(rules, windows, None)
    assert len(ref[0]) > 0
    for a, b in zip(ref[0], run[0]):
        assert a == b
        # == lets 1 pass for True and 2 for 2.0: hold the types too
        assert [type(v) for _, v in a[1]] == [type(v) for _, v in b[1]]
    assert len(ref[0]) == len(run[0])
    assert ref[1] == run[1], "sink query streams differ"
    assert ref[2] == run[2], "rule counters differ"
    assert ref[3] == run[3], "broker metrics differ"
    assert ref[4]["select_batched_rows"] == 0
    assert run[4]["select_batched_rows"] > 0
    assert run[4]["select_ewma_off"] == 0


def _fn_broker():
    cfg = BrokerConfig()
    cfg.engine.use_device = False
    return Broker(config=cfg)


def test_two_function_actions_keep_firing_major_order():
    b = _fn_broker()
    calls = []
    b.rules.add_rule(
        "r", 'SELECT payload.a AS a FROM "t/#" WHERE payload.a > 0',
        actions=[
            FunctionAction(lambda s, m: calls.append(("f", s["a"]))),
            FunctionAction(lambda s, m: calls.append(("g", s["a"]))),
        ],
    )
    b.publish_many([
        Message(topic="t/1", payload=b'{"a": %d}' % a)
        for a in (3, 0, 5, 7)
    ])
    assert calls == [
        ("f", 3), ("g", 3), ("f", 5), ("g", 5), ("f", 7), ("g", 7),
    ]
    assert b.rules.stats()["select_batched_rows"] == 3


def test_action_that_raises_fails_one_action_not_the_run():
    b = _fn_broker()
    calls = []

    def picky(selected, msg):
        if selected["a"] == 5:
            raise ValueError("not this one")
        calls.append(("picky", selected["a"]))

    rule = b.rules.add_rule(
        "r", 'SELECT payload.a AS a FROM "t/#" WHERE payload.a > 0',
        actions=[
            FunctionAction(picky),
            FunctionAction(lambda s, m: calls.append(("after", s["a"]))),
        ],
    )
    b.publish_many([
        Message(topic="t/1", payload=b'{"a": %d}' % a)
        for a in (3, 5, 7)
    ])
    assert calls == [
        ("picky", 3), ("after", 3), ("after", 5),
        ("picky", 7), ("after", 7),
    ]
    assert (rule.actions_success, rule.actions_failed) == (5, 1)
    assert b.metrics.val("actions.success") == 5
    assert b.metrics.val("actions.failed") == 1
    assert b.rules.stats()["select_batched_rows"] == 3


def test_each_firing_gets_its_own_selected_dict():
    b = _fn_broker()
    seen = []

    def keep(selected, msg):
        assert "mark" not in selected  # no other firing's dict
        selected["mark"] = msg.topic
        seen.append(selected)

    b.rules.add_rule(
        "r", 'SELECT * FROM "t/#"',
        actions=[FunctionAction(keep), FunctionAction(
            # the firing's second action sees what its first left
            lambda s, m: seen.append(s["mark"])
        )],
    )
    b.rules.add_rule(
        "r2", 'SELECT * FROM "t/#"', actions=[FunctionAction(keep)],
    )
    b.publish_many([
        Message(topic=f"t/{i}", payload=b"{}") for i in range(3)
    ])
    dicts = [s for s in seen if isinstance(s, dict)]
    assert len(dicts) == 6 and len({id(d) for d in dicts}) == 6
    assert [s for s in seen if isinstance(s, str)] == [
        "t/0", "t/1", "t/2",
    ]


def test_window_record_counts_firings_and_those_a_run_served():
    """``rules_firings`` / ``rules_firings_run`` on the window's
    record: rows that passed a WHERE on a rule with actions, and
    those of them a per-rule run served."""
    b = _fn_broker()
    hits = []
    act = [FunctionAction(lambda s, m: hits.append(1))]
    b.rules.add_rule(
        "low", 'SELECT topic FROM "t/#" WHERE payload.a > 0', act
    )
    b.rules.add_rule(  # SELECT not lowered: the interpreter's
        "fn", 'SELECT lower(topic) AS l FROM "t/#" WHERE payload.a > 1',
        act,
    )
    b.rules.add_rule(  # no actions: not a firing anybody serves
        "bare", 'SELECT topic FROM "t/#" WHERE payload.a > 0',
    )
    b.publish_many([
        Message(topic="t/1", payload=b'{"a": %d}' % a)
        for a in (0, 1, 2, 3)
    ])
    rec = b.profiler.windows(1)[-1]
    assert (rec["rules_firings"], rec["rules_firings_run"]) == (5, 3)
    assert len(hits) == 5
    b.publish(Message(topic="q/none", payload=b"{}"))
    rec = b.profiler.windows(1)[-1]
    assert (rec["rules_firings"], rec["rules_firings_run"]) == (0, 0)
