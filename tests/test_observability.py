"""Window-pipeline profiler: histogram buckets/merge/percentiles,
flight-recorder ring + Chrome trace export, Prometheus text-format
round-trip of the full scrape, OTLP histogram datapoints, the $SYS
profiler summary, slow-subs expiry, and the PERF401 single-encode
gate over the instrumented dispatch path."""

import asyncio
import json
import re
import tempfile
import time

# auto-cleaned parent for per-test mgmt stores
_MGMT_TMP = tempfile.TemporaryDirectory(prefix="emqx-obs-")

import aiohttp

from emqx_tpu.broker.broker import Broker
from emqx_tpu.broker.channel import Channel
from emqx_tpu.broker.listener import BrokerServer
from emqx_tpu.broker.session import SubOpts
from emqx_tpu.codec import mqtt as C
from emqx_tpu.config import BrokerConfig, ListenerConfig
from emqx_tpu.message import Message
from emqx_tpu.observability import (
    BOUNDS, Histogram, HistogramSnapshot, N_BUCKETS, Profiler, prom_name,
)
from api_helper import auth_session


def run(coro):
    return asyncio.run(coro)


# ------------------------------------------------------------ histogram


def test_histogram_bucket_boundaries():
    """Bucket i holds integer values with bit_length i: v <= 2^i - 1
    and v > 2^(i-1) - 1 — the O(1) index must agree with the exported
    ``le`` bounds exactly."""
    h = Histogram()
    for v in (0, 1, 2, 3, 4, 7, 8, 1023, 1024):
        h.record(v)
    snap = h.snapshot()
    assert snap.count == 9
    assert snap.counts[0] == 1  # v=0
    assert snap.counts[1] == 1  # v=1
    assert snap.counts[2] == 2  # v=2,3
    assert snap.counts[3] == 2  # v=4,7
    assert snap.counts[4] == 1  # v=8
    assert snap.counts[10] == 1  # v=1023 <= 2^10-1
    assert snap.counts[11] == 1  # v=1024
    # every recorded value v in bucket i satisfies v <= BOUNDS[i]
    for i in range(N_BUCKETS - 1):
        assert BOUNDS[i] == (1 << i) - 1


def test_histogram_overflow_lands_in_last_bucket():
    h = Histogram()
    h.record(float(1 << 40))  # way past the largest finite bound
    h.record(-5.0)  # negative clamps into bucket 0, never IndexError
    snap = h.snapshot()
    assert snap.counts[N_BUCKETS - 1] == 1
    assert snap.counts[0] == 1


def test_histogram_merge():
    a, b = Histogram(), Histogram()
    for v in (1, 10, 100):
        a.record(v)
    for v in (1000, 10000):
        b.record(v)
    m = a.snapshot().merge(b.snapshot())
    assert m.count == 5
    assert m.sum == 1 + 10 + 100 + 1000 + 10000
    assert sum(m.counts) == 5
    # merge is per-bucket: the merged p99 sees b's large values
    assert m.percentile(99) > a.snapshot().percentile(99)


def test_histogram_percentiles_monotone_and_bounded():
    h = Histogram()
    h.record_many([100.0] * 50 + [1000.0] * 50)
    snap = h.snapshot()
    p50, p99 = snap.percentile(50), snap.percentile(99)
    assert p50 <= p99
    # 100 lives in (63, 127], 1000 in (511, 1023]
    assert 63 <= p50 <= 127
    assert 511 <= p99 <= 1023
    # empty histogram: 0.0, not a crash
    assert Histogram().snapshot().percentile(99) == 0.0


def test_histogram_record_many_bulk():
    h = Histogram()
    h.record_many([float(i) for i in range(64)])
    snap = h.snapshot()
    assert snap.count == 64
    assert snap.sum == sum(range(64))


# ------------------------------------------------------ flight recorder


def test_flight_recorder_ring_wraparound():
    prof = Profiler(ring_size=4)
    for i in range(10):
        rec = prof.begin(i + 1)
        rec.lap("prepare")
        prof.commit(rec)
    wins = prof.windows(100)
    assert len(wins) == 4  # ring capacity, not total committed
    assert [w["seq"] for w in wins] == [10, 9, 8, 7]  # newest first
    assert prof.summary()["prepare"]["count"] == 10  # histograms keep all


def test_window_record_spans_are_contiguous():
    prof = Profiler()
    rec = prof.begin(3, source="publish")
    rec.lap("prepare")
    time.sleep(0.002)
    rec.lap("expand")
    prof.commit(rec)
    spans = rec.spans
    assert [s[0] for s in spans] == ["prepare", "expand"]
    # offsets are monotone and each span starts where the prior ended
    assert spans[0][1] == 0.0 or spans[0][1] >= 0.0
    assert abs((spans[0][1] + spans[0][2]) - spans[1][1]) < 1e-9
    assert spans[1][2] >= 0.002


def test_profiler_disabled_is_noop():
    prof = Profiler(enabled=False)
    assert prof.begin(5) is None
    prof.stage("tokenize", 0.001)  # no-op, no crash
    prof.event("xla_compile", 0.5)
    assert prof.windows() == []
    assert prof.events() == []
    assert all(s.count == 0 for s in prof.snapshots().values())


def _fanout_broker(n_subs=3):
    cfg = BrokerConfig()
    cfg.engine.use_device = False
    b = Broker(config=cfg)
    sink = []
    for i in range(n_subs):
        ch = Channel(b, send=lambda pkts: sink.append(pkts),
                     close=lambda r: None)
        cid = f"c{i}"
        session, _ = b.cm.open_session(True, cid, ch)
        session.subscribe("t/#", SubOpts(qos=0))
        b.subscribe(cid, "t/#", SubOpts(qos=0))
    return b, sink


def test_dispatch_window_records_stages_and_sizes():
    b, _sink = _fanout_broker(n_subs=3)
    counts = b.publish_many(
        [Message(topic="t/1", payload=b"x") for _ in range(4)]
    )
    assert counts == [3, 3, 3, 3]
    (win,) = b.profiler.windows(1)
    assert win["source"] == "publish"
    assert win["n_msgs"] == 4
    assert win["n_deliveries"] == 12
    assert win["n_clients"] == 3
    assert win["path"] == "host"
    assert win["breaker_open"] is False
    for stage in ("prepare", "match_submit", "match_wait",
                  "dispatch_wait", "expand", "deliver", "flush"):
        assert stage in win["stages_us"], win["stages_us"]
    assert len(win["e2e_ms"]) == 4  # one e2e sample per routed message
    # engine-internal tokenize stage histogrammed too
    assert b.profiler.summary()["tokenize"]["count"] >= 1


def test_profiler_disabled_broker_still_dispatches():
    cfg = BrokerConfig()
    cfg.engine.use_device = False
    cfg.profiler.enable = False
    b = Broker(config=cfg)
    ch = Channel(b, send=lambda pkts: None, close=lambda r: None)
    session, _ = b.cm.open_session(True, "c0", ch)
    session.subscribe("t/#", SubOpts(qos=0))
    b.subscribe("c0", "t/#", SubOpts(qos=0))
    assert b.publish_many([Message(topic="t/1", payload=b"x")]) == [1]
    assert b.profiler.windows() == []


# --------------------------------------------------------- chrome trace


def test_chrome_trace_export_is_valid():
    """The flight-recorder export must be loadable Chrome trace-event
    JSON: required keys on every event, strictly paired + properly
    nested B/E events per track, monotone non-decreasing timestamps
    within each track, durations on X events."""
    b, _sink = _fanout_broker()
    for _ in range(3):
        b.publish_many([Message(topic="t/x", payload=b"p")] * 2)
    b.profiler.event("xla_compile", 0.25, nodes=4096)  # engine track
    trace = b.profiler.chrome_trace()
    events = trace["traceEvents"]
    assert events, "empty trace"
    assert json.loads(json.dumps(trace))  # JSON-serializable
    per_tid = {}
    for ev in events:
        assert ev["ph"] in ("B", "E", "X", "M"), ev
        assert "pid" in ev and "tid" in ev and "name" in ev
        if ev["ph"] == "M":
            continue
        assert isinstance(ev["ts"], (int, float))
        if ev["ph"] == "X":
            assert ev["dur"] >= 0
            continue
        per_tid.setdefault(ev["tid"], []).append(ev)
    assert per_tid, "no B/E span events"
    for tid, evs in per_tid.items():
        stack = []
        last_ts = -1.0
        for ev in evs:
            assert ev["ts"] >= last_ts, f"ts not monotone on tid {tid}"
            last_ts = ev["ts"]
            if ev["ph"] == "B":
                stack.append(ev["name"])
            else:
                assert stack, f"E without B on tid {tid}: {ev}"
                assert stack.pop() == ev["name"], "mismatched B/E pair"
        assert not stack, f"unclosed B events on tid {tid}: {stack}"


def test_chrome_trace_window_limit():
    prof = Profiler(ring_size=16)
    for i in range(8):
        rec = prof.begin(1)
        rec.lap("prepare")
        prof.commit(rec)
    limited = prof.chrome_trace(limit=2)
    spans = [e for e in limited["traceEvents"] if e["ph"] == "B"]
    assert len(spans) == 2  # one "prepare" B per window, 2 windows


def test_flight_record_labels_device_fallback_honestly():
    """A device fault the engine degrades INTERNALLY (submit- or
    finish-side) must label the window 'host-fallback', never 'dev'
    or plain 'host' — the recorder exists to diagnose exactly these
    windows."""
    cfg = BrokerConfig()
    cfg.engine.use_device = True
    b = Broker(config=cfg)
    eng = b.router.engine
    for i in range(4):
        b.subscribe(f"w{i}", f"f/{i}/+", SubOpts(qos=0))
    eng.rebuild()  # device automaton exists -> device path chosen
    eng.breaker_threshold = 10_000  # keep the breaker closed

    # submit-side fault: kernel dispatch raises, window serves on host
    orig = eng._flat_dispatch
    eng._flat_dispatch = lambda *a, **k: (_ for _ in ()).throw(
        RuntimeError("injected dispatch fault")
    )
    try:
        b.publish_many([Message(topic="f/0/x", payload=b"p")])
    finally:
        eng._flat_dispatch = orig
    (win,) = b.profiler.windows(1)
    assert win["path"] == "host-fallback", win

    # finish-side fault: result transfer raises inside the engine
    orig_res = eng._flat_result
    eng._flat_result = lambda tok: (_ for _ in ()).throw(
        RuntimeError("injected result fault")
    )
    try:
        b.publish_many([Message(topic="f/1/x", payload=b"p")])
    finally:
        eng._flat_result = orig_res
    (win,) = b.profiler.windows(1)
    assert win["path"] == "host-fallback", win

    # healthy window on the same broker: labeled dev
    b.publish_many([Message(topic="f/2/x", payload=b"p")])
    (win,) = b.profiler.windows(1)
    assert win["path"] == "dev", win


# ------------------------------------------- engine lifecycle events


def test_engine_fold_and_device_put_events():
    """A synchronous delta fold on the CPU backend must record
    delta_fold + device_put events (with transfer bytes) through the
    engine's profiler hook."""
    from emqx_tpu.engine import MatchEngine

    eng = MatchEngine(use_device=True, delta_aut_threshold=4,
                      rebuild_threshold=10_000)
    prof = Profiler()
    eng.profiler = prof
    eng._fold_async = False  # deterministic: fold inline on insert
    eng.insert_many([(f"a/{i}/+", i) for i in range(8)])
    kinds = {e["kind"] for e in prof.events()}
    assert "delta_fold" in kinds, prof.events()
    assert "device_put" in kinds
    dp = next(e for e in prof.events() if e["kind"] == "device_put")
    assert dp["bytes"] > 0
    assert prof.summary()["engine_delta_fold"]["count"] >= 1
    # and the stats() gauge surface is numeric-exportable
    stats = eng.stats()
    for key in ("base", "delta", "folded", "residual", "deep",
                "auto_host_windows", "auto_dev_windows",
                "breaker_open", "breaker_trips"):
        assert key in stats


# ------------------------------------------------- prometheus scrape


def _make_server(**cfg_kw):
    cfg = BrokerConfig()
    cfg.listeners = [ListenerConfig(port=0)]
    cfg.api.enable = True
    cfg.api.data_dir = tempfile.mkdtemp(dir=_MGMT_TMP.name)
    cfg.api.port = 0
    cfg.engine.use_device = False
    for k, v in cfg_kw.items():
        setattr(cfg, k, v)
    return BrokerServer(cfg)


_NAME_RE = re.compile(r"[a-zA-Z_:][a-zA-Z0-9_:]*$")
_SAMPLE_RE = re.compile(
    r"^([a-zA-Z_:][a-zA-Z0-9_:]*)"  # metric name
    r'(\{[a-zA-Z_][a-zA-Z0-9_]*="[^"]*"(?:,[a-zA-Z_][a-zA-Z0-9_]*='
    r'"[^"]*")*\})?'  # optional labels
    r" (-?[0-9.eE+-]+|NaN|\+Inf|-Inf)$"  # value
)


def _parse_prometheus(text):
    """Strict text-format parse: returns (types, samples) and raises
    AssertionError on anything a real parser would reject."""
    types = {}
    samples = []  # (family-resolved name, labels-str, value)
    for line in text.splitlines():
        if not line:
            continue
        if line.startswith("# TYPE "):
            _, _, name, kind = line.split(" ", 3)
            assert _NAME_RE.match(name), f"bad family name {name!r}"
            assert kind in ("counter", "gauge", "histogram", "summary")
            assert name not in types, f"duplicate TYPE for {name}"
            types[name] = kind
            continue
        if line.startswith("# HELP "):
            parts = line.split(" ", 3)
            assert len(parts) >= 3 and _NAME_RE.match(parts[2])
            continue
        assert not line.startswith("#"), f"unknown comment {line!r}"
        m = _SAMPLE_RE.match(line)
        assert m, f"unparseable sample line {line!r}"
        samples.append((m.group(1), m.group(2) or "", m.group(3)))
    return types, samples


def test_prometheus_full_scrape_round_trips():
    async def t():
        srv = _make_server()
        await srv.start()
        # traffic through the REAL pipeline so histograms have samples
        b = srv.broker
        ch = Channel(b, send=lambda pkts: None, close=lambda r: None)
        session, _ = b.cm.open_session(True, "pm", ch)
        session.subscribe("p/#", SubOpts(qos=0))
        b.subscribe("pm", "p/#", SubOpts(qos=0))
        for _ in range(3):
            b.publish_many([Message(topic="p/t", payload=b"x")] * 4)
        # an extra-registry counter with a name that NEEDS sanitizing
        b.metrics.inc("5xx.responses-total")
        async with aiohttp.ClientSession() as http:
            async with http.get(
                f"http://127.0.0.1:{srv.api.port}/metrics"
            ) as r:
                assert r.status == 200
                text = await r.text()
        await srv.stop()
        return text

    text = run(t())
    types, samples = _parse_prometheus(text)
    # the pre-existing exposition contract
    assert types["emqx_messages_received"] == "counter"
    assert types["emqx_connections_count"] == "gauge"
    # sanitized: no family may start with a digit or carry a '-'
    assert "emqx__5xx_responses_total" in types or any(
        n.startswith("emqx_") and "5xx" in n for n in types
    )
    for name in types:
        assert _NAME_RE.match(name)
    # engine gauge surface (satellite: MatchEngine.stats() exported)
    for g in ("emqx_engine_base", "emqx_engine_delta",
              "emqx_engine_residual", "emqx_engine_deep",
              "emqx_engine_auto_host_windows",
              "emqx_engine_breaker_open"):
        assert types.get(g) == "gauge", f"missing engine gauge {g}"
    # >= 4 histogram families with _bucket/_sum/_count samples
    hist_fams = [n for n, k in types.items() if k == "histogram"]
    assert len(hist_fams) >= 4, hist_fams
    by_name = {}
    for name, labels, value in samples:
        by_name.setdefault(name, []).append((labels, value))
    sampled = 0
    for fam in hist_fams:
        buckets = by_name.get(fam + "_bucket", [])
        assert buckets, f"{fam}: no _bucket samples"
        # cumulative, ordered le, +Inf last and == _count
        les, counts = [], []
        for labels, value in buckets:
            m = re.search(r'le="([^"]+)"', labels)
            assert m, f"{fam}: bucket without le label"
            les.append(m.group(1))
            counts.append(int(value))
        assert les[-1] == "+Inf"
        finite = [float(le) for le in les[:-1]]
        assert finite == sorted(finite)
        assert counts == sorted(counts), f"{fam}: not cumulative"
        (_, count_v), = by_name[fam + "_count"]
        assert int(count_v) == counts[-1]
        assert fam + "_sum" in by_name
        sampled += int(count_v)
    assert sampled > 0, "no histogram recorded any sample"


def test_prometheus_one_type_line_per_family():
    async def t():
        srv = _make_server()
        await srv.start()
        async with aiohttp.ClientSession() as http:
            async with http.get(
                f"http://127.0.0.1:{srv.api.port}/metrics"
            ) as r:
                text = await r.text()
        await srv.stop()
        return text

    text = run(t())
    type_names = [
        line.split(" ", 3)[2]
        for line in text.splitlines()
        if line.startswith("# TYPE ")
    ]
    assert len(type_names) == len(set(type_names))
    help_names = [
        line.split(" ", 3)[2]
        for line in text.splitlines()
        if line.startswith("# HELP ")
    ]
    assert len(help_names) == len(set(help_names))


def test_prom_name_sanitizer():
    assert prom_name("emqx_a.b") == "emqx_a_b"
    assert prom_name("5xx_total") == "_5xx_total"
    assert prom_name("a-b/c d") == "a_b_c_d"
    assert _NAME_RE.match(prom_name(""))
    assert _NAME_RE.match(prom_name("emqx_ok_name"))


# ------------------------------------------------- profiler REST + ctl


def test_profiler_rest_endpoints():
    async def t():
        srv = _make_server()
        await srv.start()
        http, api = await auth_session(srv)
        async with http:
            # publish through the BATCHER (the server wires one): the
            # flight record must carry source=batcher + batch_wait
            async with http.post(
                api + "/api/v5/publish",
                json={"topic": "nope/t", "payload": "x"},
            ) as r:
                assert r.status == 200
            async with http.get(api + "/api/v5/profiler") as r:
                assert r.status == 200
                body = await r.json()
            assert body["enabled"] is True
            assert "histograms_us" in body and "engine" in body
            assert body["windows"], "no window records after a publish"
            win = body["windows"][0]
            assert win["source"] == "batcher"
            assert "batch_wait" in win["stages_us"]
            assert "prepare" in win["stages_us"]
            # trace endpoint returns Chrome trace JSON
            async with http.get(api + "/api/v5/profiler/trace") as r:
                assert r.status == 200
                trace = await r.json()
            assert any(
                e["ph"] == "B" for e in trace["traceEvents"]
            )
            async with http.get(
                api + "/api/v5/profiler/trace?windows=bogus"
            ) as r:
                assert r.status == 400
            # reset clears histograms + ring
            async with http.delete(api + "/api/v5/profiler") as r:
                assert r.status == 204
            async with http.get(api + "/api/v5/profiler") as r:
                body = await r.json()
            assert body["windows"] == []
        await srv.stop()

    run(t())


def test_ctl_profiler_commands(tmp_path):
    import subprocess
    import sys as _sys

    async def t():
        srv = _make_server()
        await srv.start()
        b = srv.broker
        ch = Channel(b, send=lambda pkts: None, close=lambda r: None)
        session, _ = b.cm.open_session(True, "cc", ch)
        session.subscribe("c/#", SubOpts(qos=0))
        b.subscribe("cc", "c/#", SubOpts(qos=0))
        b.publish_many([Message(topic="c/t", payload=b"x")] * 3)
        api = f"http://127.0.0.1:{srv.api.port}"

        def ctl(*args):
            out = subprocess.run(
                [_sys.executable, "-m", "emqx_tpu.ctl", "--api", api,
                 *args],
                capture_output=True, text=True, timeout=30,
                cwd="/root/repo",
            )
            assert out.returncode == 0, out.stderr
            return out.stdout

        loop = asyncio.get_running_loop()
        summary = await loop.run_in_executor(None, ctl, "profiler")
        assert "profiler on" in summary
        assert "deliver" in summary and "engine:" in summary
        trace_path = str(tmp_path / "trace.json")
        traced = await loop.run_in_executor(
            None, ctl, "profiler", "trace", trace_path
        )
        assert "perfetto" in traced
        with open(trace_path) as f:
            trace = json.load(f)
        assert trace["traceEvents"]
        reset = await loop.run_in_executor(
            None, ctl, "profiler", "reset"
        )
        assert "reset" in reset
        await srv.stop()

    run(t())


# ------------------------------------------------------- OTLP + $SYS


def test_otlp_payload_has_histograms_and_engine_gauges():
    from emqx_tpu.otel import OtelExporter

    b, _sink = _fanout_broker()
    b.publish_many([Message(topic="t/1", payload=b"x")] * 4)
    exp = OtelExporter(b, "http://127.0.0.1:9")  # never contacted
    payload = json.loads(exp.metrics_payload(time.time()))
    metrics = payload["resourceMetrics"][0]["scopeMetrics"][0]["metrics"]
    by_name = {m["name"]: m for m in metrics}
    hists = [m for m in metrics if "histogram" in m]
    assert len(hists) >= 4, [m["name"] for m in hists]
    for m in hists:
        (dp,) = m["histogram"]["dataPoints"]
        assert len(dp["bucketCounts"]) == len(dp["explicitBounds"]) + 1
        assert sum(int(c) for c in dp["bucketCounts"]) == int(dp["count"])
        assert m["histogram"]["aggregationTemporality"] == 2
    assert "emqx_engine_base" in by_name
    assert "gauge" in by_name["emqx_engine_base"]
    # float EWMA gauges export as asDouble once measured; absent until
    # then (None is skipped, not exported as 0)
    assert "emqx_engine_breaker_open" in by_name


def test_sys_heartbeat_includes_profiler_summary():
    from emqx_tpu.sys_topics import SysTopics

    b, _sink = _fanout_broker()
    b.publish_many([Message(topic="t/1", payload=b"x")] * 2)
    sys_t = SysTopics(b, node_name="n1")
    msgs = sys_t.heartbeat_messages()
    prof_msgs = [m for m in msgs if m.topic.endswith("/profiler")]
    assert len(prof_msgs) == 1
    body = json.loads(prof_msgs[0].payload)
    assert body["stages_us"]["deliver"]["count"] >= 1
    assert "p99" in body["stages_us"]["deliver"]
    assert "base" in body["engine"]
    # disabled profiler: no $SYS topic (and no stale zeros)
    cfg = BrokerConfig()
    cfg.engine.use_device = False
    cfg.profiler.enable = False
    b2 = Broker(config=cfg)
    msgs2 = SysTopics(b2, node_name="n1").heartbeat_messages()
    assert not any(m.topic.endswith("/profiler") for m in msgs2)


def test_sys_first_heartbeat_comes_one_interval_after_boot():
    """As `emqx_sys`, whose ``init/1`` only starts its timers: nothing
    is published at boot, the first heartbeat when the interval has
    passed, and one an interval from then on."""
    from emqx_tpu.sys_topics import SysTopics

    b, _sink = _fanout_broker()
    b.config.sys.interval = 60.0
    sys_t = SysTopics(b, node_name="n1")
    boot = sys_t.started_at
    assert sys_t.tick(boot + 1.0) == 0  # (the first housekeeping tick)
    assert sys_t.tick(boot + 59.0) == 0
    n = sys_t.tick(boot + 60.0)
    assert n >= 8
    assert sys_t.tick(boot + 61.0) == 0
    assert sys_t.tick(boot + 120.0) == n
    b.config.sys.enable = False
    assert sys_t.tick(boot + 600.0) == 0


# ------------------------------------------------- slow subs / config


def test_slow_subs_entry_expiry():
    from emqx_tpu.ops_guard import SlowSubs

    ss = SlowSubs(top_k=5, threshold_ms=10.0, expire_interval=30.0)
    ss.record("c1", "t", 50.0)
    ss.record("c2", "t", 80.0)
    now = time.time()
    assert ss.tick(now + 10) == 0
    assert len(ss.top()) == 2
    assert ss.tick(now + 31) == 2
    assert ss.top() == []
    # expire_interval <= 0 disables expiry
    ss2 = SlowSubs(expire_interval=0.0, threshold_ms=1.0)
    ss2.record("c", "t", 5.0)
    assert ss2.tick(time.time() + 1e6) == 0
    assert len(ss2.top()) == 1


def test_slow_subs_config_wiring():
    cfg = BrokerConfig()
    cfg.engine.use_device = False
    cfg.slow_subs.threshold_ms = 123.0
    cfg.slow_subs.top_k = 7
    cfg.slow_subs.expire_interval = 42.0
    b = Broker(config=cfg)
    assert b.slow_subs.threshold_ms == 123.0
    assert b.slow_subs.top_k == 7
    assert b.slow_subs.expire_interval == 42.0
    cfg2 = BrokerConfig()
    cfg2.engine.use_device = False
    cfg2.slow_subs.enable = False
    b2 = Broker(config=cfg2)
    b2.slow_subs.record("c", "t", 1e9)  # below an inf threshold
    assert b2.slow_subs.top() == []


def test_flapping_deque_window_trim():
    from emqx_tpu.ops_guard import BannedList, FlappingDetector

    banned = BannedList()
    fl = FlappingDetector(banned, max_count=3, window=60.0)
    assert not fl.on_disconnect("c1")
    assert not fl.on_disconnect("c1")
    assert fl.on_disconnect("c1")  # third strike inside the window
    assert banned.is_banned(clientid="c1")
    # hits outside the window are trimmed (deque popleft path)
    fl2 = FlappingDetector(banned, max_count=3, window=0.0)
    for _ in range(10):
        assert not fl2.on_disconnect("c2")  # every hit expires at once


# ------------------------------------------------- perf gate (PERF401)


def test_instrumented_dispatch_passes_perf_gate():
    """The profiler threading through _dispatch_window/_deliver_run/
    Session.deliver must not have introduced per-subscriber encode
    calls: the PERF401 single-encode gate stays clean over the
    instrumented hot path."""
    from tools.brokerlint import run_lint

    findings = [
        f for f in run_lint(["emqx_tpu/broker", "emqx_tpu/engine.py"])
        if f.rule == "PERF401"
    ]
    assert not findings, "\n".join(f.render() for f in findings)


def test_profiler_overhead_window_shape():
    """Overhead smoke: the always-on profiler adds a BOUNDED number of
    record objects per window (one WindowRecord + spans), and a 256-
    fanout window commits with all stages present — the accounting
    that backs the <5% dispatch-throughput acceptance bound."""
    b, sink = _fanout_broker(n_subs=64)
    n_before = len(b.profiler.windows(1000))
    for _ in range(5):
        b.publish_many([Message(topic="t/1", payload=b"x" * 64)] * 8)
    wins = b.profiler.windows(1000)
    assert len(wins) == n_before + 5  # exactly one record per window
    w = wins[0]
    assert w["n_deliveries"] == 8 * 64
    # spans bounded, not per-delivery (13: `tokenize_cpu`, the CPU
    # twin of the one engine section a host-matched window has)
    assert len(w["stages_us"]) <= 13
    # one transport write per subscriber per window (corked flush
    # unchanged by instrumentation)
    assert len(sink) >= 64


# ------------------------- sub-spans, the loop's clock, annotations

MATCH_WAIT_SUBS = ("finish_queue_wait", "device_wait", "expand_codes",
                   "overlay_lock_wait", "overlay", "dense_rematch")
MATCH_SUBMIT_SUBS = ("submit_queue_wait", "tokenize", "encode",
                     "kernel_dispatch")


async def _served_windows(enable=True, rounds=6, trace_dir=None,
                          during=None):
    """A device-pinned broker behind its real listener: ``rounds``
    windows of 8 QoS1 publishes from one socket to one QoS1 subscriber
    socket, a lowered rule firing on each.  Returns the server's
    profiler after ``stop()`` (the ring survives it)."""
    from emqx_tpu.rules.engine import FunctionAction
    from mqtt_client import TestClient

    cfg = BrokerConfig()
    cfg.listeners = [ListenerConfig(bind="127.0.0.1", port=0)]
    cfg.engine.use_device = True
    cfg.profiler.enable = enable
    srv = BrokerServer(cfg)
    eng = srv.broker.router.engine
    fired = []
    srv.broker.rules.add_rule(
        "r0", 'SELECT payload.v AS v FROM "f/+/x" WHERE payload.v > 1',
        [FunctionAction(lambda sel, msg: fired.append(sel))],
    )
    eng.insert_many([(f"f/{i}/+", f"tab{i}") for i in range(64)])
    eng.rebuild()  # a base automaton on the device: windows go `dev`
    await srv.start()
    port = srv.listeners[0].port
    sub = TestClient(port, "sub")
    await sub.connect()
    await sub.subscribe("f/+/x", qos=1)
    pub = TestClient(port, "pub")
    await pub.connect()
    loop = asyncio.get_running_loop()
    if trace_dir is not None:
        import jax

        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 2
        await loop.run_in_executor(None, lambda: jax.profiler.start_trace(
            trace_dir, profiler_options=opts
        ))
    try:
        for k in range(rounds):
            # (a round's eight in one write: one read on either read
            # path, so a window a round, however eagerly it is read)
            pub.writer.write(b"".join(C.serialize(C.Publish(
                topic=f"f/{i}/x", payload=b'{"v": 3}', qos=1,
                packet_id=k * 8 + i + 1,
            ), pub.version) for i in range(8)))
            await pub.writer.drain()
            for _ in range(8):
                await sub.recv_publish(timeout=20)
            for _ in range(8):
                await pub.expect(C.PUBACK, timeout=20)
            if during is not None and k == rounds // 2:
                during(srv.broker.profiler)
        await asyncio.sleep(0.2)  # the subscriber's last PUBACKs land
    finally:
        if trace_dir is not None:
            await loop.run_in_executor(None, jax.profiler.stop_trace)
        await srv.stop()
    assert len(fired) == rounds * 8
    return srv.broker.profiler


def test_match_laps_are_covered_by_their_sub_spans():
    """Every `dev` window's queue, device and host parts add up to its
    two match laps: nothing larger than a tenth of either is unnamed,
    and no part is counted twice."""
    prof = run(_served_windows())
    wins = [w for w in prof.windows(100) if w["source"] == "batcher"]
    assert len(wins) >= 6 and all(w["path"] == "dev" for w in wins)
    for w in wins:
        st = w["stages_us"]
        for lap, subs in (("match_wait", MATCH_WAIT_SUBS),
                          ("match_submit", MATCH_SUBMIT_SUBS)):
            parts = sum(st.get(s, 0.0) for s in subs)
            assert 0.9 * st[lap] <= parts <= st[lap] + 1.0, (lap, st)
        # the device round trips that block the loop thread are inside
        # their laps, and the rules lap's three parts inside it
        assert 0 < st["decide_device_wait"] < st["decide"]
        assert 0 < st["rules_device_wait"] < st["rules_eval"]
        assert 0 < st["rules_actions"]
        assert (st["rules_extract"] + st["rules_eval"]
                + st["rules_actions"]) <= st["rules"] + 1.0
        assert w["n_clips"] == st.get("dense_rematch", 0) == 0
    # the engine's tokenize is a sub-span of the window now, and still
    # a histogram of the scrape (one sample a window)
    assert prof.summary()["tokenize"]["count"] == len(prof.windows(100))


def test_chrome_trace_nests_sub_spans_inside_their_parents():
    """Each sub-span with a start is a B/E pair inside its parent lap
    on the window's track; the parents still chain contiguously."""
    prof = run(_served_windows(rounds=3))
    trace = prof.chrome_trace()
    parents = set(Profiler.STAGES) - {"tokenize", "assemble"}
    by_tid = {}
    for ev in trace["traceEvents"]:
        if ev["ph"] in ("B", "E"):
            by_tid.setdefault(ev["tid"], []).append(ev)
    nested = set()
    for tid, evs in by_tid.items():
        stack, last_ts, last_parent_end = [], -1.0, None
        for ev in evs:
            assert ev["ts"] >= last_ts, (tid, ev)
            last_ts = ev["ts"]
            if ev["ph"] == "B":
                if not stack:
                    assert ev["name"] in parents, ev
                    if last_parent_end is not None:
                        assert ev["ts"] == last_parent_end, ev
                else:
                    assert stack[0] in parents
                    nested.add((stack[0], ev["name"]))
                stack.append(ev["name"])
            else:
                assert stack.pop() == ev["name"], (tid, ev)
                if not stack:
                    last_parent_end = ev["ts"]
        assert not stack
    assert {("match_submit", "submit_queue_wait"),
            ("match_submit", "tokenize"), ("match_submit", "encode"),
            ("match_submit", "kernel_dispatch"),
            ("match_wait", "finish_queue_wait"),
            ("match_wait", "device_wait"), ("match_wait", "expand_codes"),
            ("match_wait", "overlay_lock_wait"), ("match_wait", "overlay"),
            ("decide", "decide_device_wait"), ("rules", "rules_eval"),
            ("rules", "rules_device_wait"),
            ("rules", "rules_actions")} <= nested
    # the loop's bursts: X events on their own track, on the windows'
    # clock, none before the export's epoch (the oldest window's start)
    bursts = [e for e in trace["traceEvents"]
              if e["ph"] == "X" and e["tid"] == Profiler.LOOP_TID]
    assert {e["name"] for e in bursts} == {
        "loop_ingress", "loop_egress", "loop_poll_wait", "loop_recv"}
    span_end = max(e["ts"] for evs in by_tid.values() for e in evs)
    for e in bursts:
        assert e["ts"] >= 0 and e["dur"] >= 0
        assert e["ts"] <= span_end + 1e6
    oldest = min(w["at"] for w in prof.windows(100))
    first_b = min(e["ts"] for evs in by_tid.values() for e in evs)
    assert first_b == 0.0  # the epoch IS the oldest window's `at`
    assert json.loads(json.dumps(trace)) and oldest > 0


def test_chrome_trace_clips_what_began_before_the_oldest_window():
    prof = Profiler(ring_size=4)
    lc = prof.loop
    t0 = time.perf_counter()
    lc.egress(t0 - 0.5, 1)              # a burst long before any window
    prof.event("xla_compile", 0.25)     # and an engine event
    lc.ingress(time.perf_counter(), 1, 1, 0)  # open as the window starts
    t1 = time.perf_counter()
    rec = prof.begin(1)
    rec.lap("prepare")
    # merges: gap < 200 us, however long a busy machine kept this
    # thread off the CPU between the two reads
    lc.ingress(
        min(time.perf_counter() - 150e-6, t1 + 150e-6), 1, 1, 0
    )
    prof.commit(rec)
    xs = [e for e in prof.chrome_trace()["traceEvents"] if e["ph"] == "X"]
    assert [e["name"] for e in xs] == ["loop_ingress"]  # one merged burst
    assert xs[0]["ts"] == 0.0 and xs[0]["dur"] > 0  # clipped to the epoch
    b = [e for e in prof.chrome_trace()["traceEvents"] if e["ph"] == "B"]
    assert b[0]["ts"] == 0.0


def test_loop_fields_sum_to_the_clock_and_rebase_on_reset():
    """The ring's loop_* fields are the accumulator's growth cut at
    each commit: over the ring they sum to it, and `reset()` (the
    benchmark calls it as its window opens) re-bases them."""
    from emqx_tpu.observability import LoopClock

    marks = []

    def during(prof):
        prof.reset()
        marks.append({f: getattr(prof.loop, f) for f in LoopClock.FIELDS})
        marks.append(prof.loop.cpu_s)

    prof = run(_served_windows(rounds=6, during=during))
    lc = prof.loop
    # one more commit takes what the loop did after the last window
    prof.commit(prof.begin(0))
    wins = prof.windows(100)
    assert 3 <= len(wins) <= 4  # only the windows after the reset
    for f in LoopClock.FIELDS:
        key = "loop_" + (f[:-2] + "_us" if f.endswith("_s") else f)
        grown = getattr(lc, f) - marks[0][f]
        if f.endswith("_s"):
            grown *= 1e6
        summed = sum(w[key] for w in wins)
        # (a record rounds its microseconds to a tenth)
        assert abs(summed - grown) <= 0.1 * len(wins), (f, summed, grown)
        # (hand-backs need a socket that would not take a write)
        assert summed > 0 or f == "egress_parked", f
    # the loop thread's CPU: each window carries what the thread spent
    # since the previous window began, from the reset on
    cpu = sum(w["loop_cpu_us"] for w in wins)
    assert abs(cpu - (lc.cpu_s - marks[1]) * 1e6) <= 0.1 * len(wins)
    assert 0 < cpu <= (wins[0]["at"] - wins[-1]["at"] + 1.0) * 1e6
    # 8 PUBLISH in and 8 PUBACKs back a round, both through sockets
    assert sum(w["loop_ingress_publishes"] for w in wins) >= 16
    assert sum(w["loop_ingress_acks"] for w in wins) >= 8
    assert lc.ingress_publishes == 48 and lc.ingress_acks == 48
    # the subscriber's PUBACKs are the four-byte form: each crossed in
    # a run, and the field is cut into the records like its neighbours
    assert lc.ingress_acks_run == 48
    assert 8 <= sum(w["loop_ingress_acks_run"] for w in wins) <= sum(
        w["loop_ingress_acks"] for w in wins)
    # 96 PUBLISH and PUBACK packets, + 2 CONNECT, 1 SUBSCRIBE
    assert lc.ingress_reads >= 3 + lc.ingress_publish_reads
    assert lc.egress_packets >= 96 and lc.egress_writes <= lc.egress_packets
    assert lc.ingress_publish_reads <= lc.ingress_publishes
    assert 0 < lc.egress_writes_sender + lc.egress_writes <= 2 * lc.egress_writes
    assert 0 < lc.egress_in_window_s <= lc.egress_s


def test_sender_fields_reach_the_ring_and_the_trace():
    """With the native sender thread: the loop's share of the writes
    it took (`loop_egress_writes_sender`, `_bytes_sender`,
    `loop_egress_parked`) and the thread's own clock (`sender_send_us`,
    `sender_writes`) are in every record, the acks' and the windows'
    flush scopes both hand over, and the hand-over's interval is in
    the trace's `loop_egress` bursts."""
    from emqx_tpu.ops import sockwriter

    if sockwriter.load() is None:
        pytest.skip("native sockwriter not built")
    marks = []

    def during(prof):
        marks.append(prof.loop.sender_clock())

    prof = run(_served_windows(rounds=6, during=during))
    lc = prof.loop
    wins = prof.windows(100)
    for w in wins:
        for key in ("loop_egress_writes_sender", "loop_egress_in_window_us",
                    "loop_egress_parked", "sender_send_us", "sender_writes"):
            assert key in w, key
        assert w["loop_egress_writes_sender"] <= w["loop_egress_writes"]
        assert w["loop_egress_in_window_us"] <= w["loop_egress_us"]
    # a round is one window flush to the subscriber and one scope of
    # acks to the publisher; CONNACK / SUBACK are lone writes
    # (what the loop did after the last commit is in no record)
    n_sender = sum(w["loop_egress_writes_sender"] for w in wins)
    assert 10 <= n_sender <= lc.egress_writes_sender < lc.egress_writes
    assert lc.egress_writes_sender >= 12
    assert lc.egress_parked == 0
    # the thread made the sends, and clocked them: the records hold
    # its growth window by window, nothing twice
    sends = sum(w["sender_writes"] for w in wins)
    assert 0 < marks[0][1] <= sends <= lc.egress_writes_sender
    assert sum(w["sender_send_us"] for w in wins) > marks[0][0] * 1e6 > 0
    assert lc.sender_clock is None  # stopped with the server
    bursts = [e for e in prof.chrome_trace()["traceEvents"]
              if e["ph"] == "X" and e["name"] == "loop_egress"]
    assert bursts


def test_sender_clock_is_cut_into_the_windows_like_the_loops():
    """`take_sender` hands each committed window the growth of the
    thread's clock since the previous one; `reset()` re-bases it; a
    sender that starts later begins from zero."""
    prof = Profiler(ring_size=8)
    lc = prof.loop
    clock = [0.0, 0]
    prof.commit(prof.begin(1))
    assert "sender_send_us" not in prof.windows(1)[0]  # no sender yet
    lc.attach_sender(lambda: tuple(clock))
    clock[:] = [0.004, 10]
    prof.commit(prof.begin(1))
    clock[:] = [0.009, 25]
    prof.commit(prof.begin(1))
    new, old = prof.windows(2)
    assert (old["sender_send_us"], old["sender_writes"]) == (4000.0, 10)
    assert (new["sender_send_us"], new["sender_writes"]) == (5000.0, 15)
    clock[:] = [0.020, 40]
    prof.reset()  # (the benchmark's window opens)
    clock[:] = [0.021, 42]
    prof.commit(prof.begin(1))
    w, = prof.windows(8)
    assert (w["sender_send_us"], w["sender_writes"]) == (1000.0, 2)
    lc.attach_sender(None)
    prof.commit(prof.begin(1))
    assert "sender_writes" not in prof.windows(1)[0]
    fresh = [0.001, 1]
    lc.attach_sender(lambda: tuple(fresh))
    prof.commit(prof.begin(1))
    assert prof.windows(1)[0]["sender_writes"] == 1


def test_egress_clock_has_the_hand_over_and_not_the_send():
    """`LoopClock.egress` keeps its meaning, the loop thread's time in
    `_send_packets`: a write the sender takes costs it no `send`, and
    a scope's one hand-over is added (`egress_submit`) as time, not as
    a write."""
    from emqx_tpu.observability import LoopClock

    lc = LoopClock()
    t0 = time.perf_counter()
    lc.in_window = True
    lc.egress(t0 - 0.002, 3, True)
    lc.egress(t0 - 0.001, 1)
    assert (lc.egress_writes, lc.egress_writes_sender) == (2, 1)
    assert lc.egress_in_window_s == lc.egress_s >= 0.003
    assert lc.egress_packets == 4
    before = lc.egress_s
    lc.egress_submit(time.perf_counter() - 0.005)
    assert 0.005 <= lc.egress_s - before < 0.05
    assert lc.egress_in_window_s == lc.egress_s
    assert (lc.egress_writes, lc.egress_writes_sender) == (2, 1)
    assert [b[0] for b in lc.bursts()] == ["loop_egress"] * len(lc.bursts())


def test_a_read_counts_an_ack_run_as_the_packets_it_carries():
    """One socket write of five minimal PUBACKs, a v5 PUBACK with a
    reason code and a PINGREQ: seven packets and six acks to the
    loop's clock, five of them in runs, however the reads fell."""
    from mqtt_client import TestClient

    async def main():
        cfg = BrokerConfig()
        cfg.listeners = [ListenerConfig(bind="127.0.0.1", port=0)]
        srv = BrokerServer(cfg)
        await srv.start()
        lc = srv.broker.profiler.loop
        try:
            sub = TestClient(srv.listeners[0].port, "sub")
            await sub.connect()
            await sub.subscribe("a/#", qos=1)
            for i in range(6):
                srv.broker.publish(Message(topic="a/b", payload=b"x", qos=1))
            pids = [(await sub.expect(C.PUBLISH)).packet_id for _ in range(6)]
            # (seven packets, six of them acks: a mixed read, so it is
            # filed under neither packet type)
            before = (lc.ingress_ack_reads, lc.ingress_acks,
                      lc.ingress_acks_run)
            sub.writer.write(
                b"".join(bytes((0x40, 2, p >> 8, p & 255)) for p in pids[:5])
                + bytes((0x40, 3, pids[5] >> 8, pids[5] & 255, 0))
                + C.serialize(C.Pingreq(), C.MQTT_V5)
            )
            await sub.expect(C.PINGRESP)
            after = (lc.ingress_ack_reads, lc.ingress_acks,
                     lc.ingress_acks_run)
            assert [a - b for a, b in zip(after, before)] == [0, 6, 5]
            m = srv.broker.metrics
            assert m.val("packets.puback.received") == 6
            assert m.val("messages.acked") == 6
            assert len(srv.broker.cm.lookup("sub").inflight) == 0
        finally:
            await srv.stop()

    run(main())


def test_loop_clock_takes_and_resets_the_run_count():
    prof = Profiler(ring_size=4)
    lc = prof.loop
    lc.ingress(time.perf_counter(), 19, 1, 18, 17)
    lc.ingress(time.perf_counter(), 2, 0, 2)  # scalar acks: none in runs
    rec = prof.begin(1)
    prof.commit(rec)
    w, = prof.windows(10)
    assert (w["loop_ingress_reads"], w["loop_ingress_acks"],
            w["loop_ingress_acks_run"]) == (2, 20, 17)
    lc.ingress(time.perf_counter(), 4, 0, 4, 4)
    prof.reset()  # re-bases: what came before is no later window's
    lc.ingress(time.perf_counter(), 3, 0, 3, 3)
    prof.commit(prof.begin(1))
    assert prof.windows(1)[0]["loop_ingress_acks_run"] == 3
    assert lc.ingress_acks_run == 24  # the accumulator only grows


def test_profiler_disabled_reads_no_new_clock(monkeypatch):
    """`profiler.enable = false`: a served window reads none of the
    clocks this instrumentation added and writes no field."""
    from emqx_tpu import engine as engine_mod
    from emqx_tpu import observability as obs
    from emqx_tpu.broker import connection as conn_mod

    class NoClock:
        def __getattr__(self, name):
            raise AssertionError(f"time.{name} read with the profiler off")

    def no_laps(*a, **k):
        raise AssertionError("a lap clock was made with the profiler off")

    monkeypatch.setattr(conn_mod, "time", NoClock())
    monkeypatch.setattr(engine_mod, "CpuLaps", no_laps)
    monkeypatch.setattr(obs, "annotation", no_laps)
    prof = run(_served_windows(enable=False, rounds=2))
    assert prof.loop is None and prof.begin(1) is None
    assert prof.windows() == []
    assert all(s.count == 0 for s in prof.snapshots().values())


def test_jax_trace_holds_the_windows_annotations(tmp_path):
    """The program writes its synchronous window sections into the
    profiler's own trace (`emqx/<name>`, with the window's seq), so a
    device trace carries the host's working spans on its own clock."""
    import glob

    from jax.profiler import ProfileData

    prof = run(_served_windows(rounds=3, trace_dir=str(tmp_path)))
    seqs = {w["seq"] for w in prof.windows(100)}
    found, = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    seen = {}
    for plane in ProfileData.from_file(found).planes:
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith("emqx/"):
                    seq = {k: v for k, v in ev.stats}.get("seq")
                    seen.setdefault(ev.name, set()).add(seq)
    assert {"emqx/match_submit", "emqx/device_wait", "emqx/expand_codes",
            "emqx/overlay", "emqx/expand", "emqx/decide", "emqx/deliver",
            "emqx/flush", "emqx/rules"} <= set(seen)
    for name in ("emqx/overlay", "emqx/deliver"):
        assert seen[name] and seen[name] <= seqs, (name, seen[name])


def test_kernel_phases_carry_named_scopes():
    """Metadata only: the jitted programs keep their names (the
    benchmark reads `jit_<function>` modules) and gain scopes."""
    import jax.numpy as jnp

    from emqx_tpu.ops import match_kernel as mk

    def z(n, d):
        return jnp.zeros(n, d)

    low = mk.match_batch_compact.lower(
        z((64, 16), jnp.uint32), z((32, 8), jnp.int32), jnp.uint32(1),
        z((16, 8), jnp.int32), z(16, jnp.int32), z(16, bool),
        f_width=16, m_cap=128, c_cap=32,
    )
    text = low.as_text(debug_info=True)
    for scope in ("level_scan", "hit_prefix_sum", "compact"):
        assert f"jit(match_batch_compact)/{scope}" in text, scope
    low = mk.decide_batch.lower(
        z(8, jnp.int8), z(8, bool), z(8, bool), z(8, bool),
        z(64, jnp.int32), z(64, jnp.int32), z(64, jnp.int32),
        z(16, jnp.int8), z(16, bool), z(16, jnp.int32),
    )
    assert "jit(decide_batch)/decide_columns" in low.as_text(debug_info=True)
    assert mk.rules_eval_batch.__name__ == "rules_eval_batch"
