"""Headline benchmark: batched wildcard route matching on one chip.

Reproduces BASELINE.json configs 3-4: up to 10M mixed `+`/`#` wildcard
subscriptions, Zipf-skewed fan-out-heavy publish stream.  North star
(BASELINE.md): 1M publishes/s routed with p99 match < 1 ms.

Honest full-path timing (VERDICT r1 weak #2): the clock covers
topic-string tokenization, device match, device-side CSR expansion to
filter positions, and materializing host-visible fid arrays — i.e.
everything `emqx_router:match_routes/1` does per publish
(/root/reference/apps/emqx/src/emqx_router.erl:205-212), batched.

Also reports InsertRps measured concurrently with matching (the
reference's own micro-bench shape, apps/emqx/src/emqx_broker_bench.erl:
25-35) against a MatchEngine with background rebuild.

Prints ONE JSON line:
  {"metric": ..., "value": N, "unit": ..., "vs_baseline": N}
Extra detail goes to BENCH_DETAILS.json, never stdout.
"""

import asyncio
import json
import os
import sys
import time


def log(msg):
    print(msg, file=sys.stderr, flush=True)


def device_info():
    """The device a result was taken on, as JAX reports it."""
    import jax

    devs = jax.devices()
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def write_result(name, out):
    """Write one result object beside this file; every one of them
    says which device it ran on."""
    out["device"] = device_info()
    path = os.path.join(os.path.dirname(__file__) or ".", name)
    with open(path, "w") as f:
        json.dump(out, f, indent=2)


def make_filters(n_subs, fanout):
    """Fleet-telemetry-style wildcard set with ~`fanout` subscribers per
    matched topic across every filter family (fan-out heavy per VERDICT
    r1, but with distinctness scaling with n_subs so fan-out stays at
    the configured level instead of exploding at 10M)."""
    n_vehicles = max(n_subs // (2 * fanout), 1)
    n_dev = max(n_subs // (5 * fanout), 1)
    n_site = max(n_subs // (5 * fanout), 1)
    n_alert = max(n_subs // (10 * fanout), 1)
    filters = []
    for i in range(n_subs):
        kind = i % 10
        if kind < 5:  # fanout x subscribers share each vehicle
            filters.append((i, ("vehicles", f"v{i % n_vehicles}", "sensors", "#")))
        elif kind < 7:
            filters.append((i, ("dev", f"g{i % n_dev}", "+", f"d{i % 7}")))
        elif kind < 9:
            filters.append((i, ("site", "+", "floor", f"f{i % n_site}", "#")))
        else:
            filters.append((i, ("alerts", f"z{i % n_alert}", "+", "+")))
    return filters, (n_vehicles, n_dev, n_site, n_alert)


def make_topics(rng, n, pops):
    n_vehicles, n_dev, n_site, n_alert = pops
    zipf = rng.zipf(1.3, size=n) % max(n_vehicles, 1)
    topics = []
    for i in range(n):
        k = i % 10
        if k < 6:
            topics.append(f"vehicles/v{zipf[i]}/sensors/temp")
        elif k < 8:
            topics.append(f"dev/g{i % n_dev}/x/d{i % 7}")
        elif k < 9:
            topics.append(f"site/s{i % 7}/floor/f{i % n_site}/a")
        else:
            topics.append(f"nomatch/q{i}")
    return topics


def measure_insert_rps(base_filters, n_insert, log):
    """InsertRps into a live MatchEngine (background rebuild on) while a
    match stream keeps running — no stop-the-world allowed."""
    from emqx_tpu.engine import MatchEngine

    eng = MatchEngine(
        max_levels=16,
        rebuild_threshold=65536,
        background_rebuild=True,
        use_device=True,
    )
    for fid, ws in base_filters:
        eng._wild.insert("/".join(ws), fid)
        eng._by_fid[fid] = "/".join(ws)
    eng.rebuild()
    probe = [f"vehicles/v{i}/sensors/temp" for i in range(16)]
    eng.match_batch(probe)  # compile the base kernel
    # warm every delta-automaton shape class the timed run will touch
    # (first folds + XLA compiles are one-time costs a live broker pays
    # at boot, not steady churn): insert as many dummies as the run
    # will, matching at geometric points so each capacity class compiles
    n_warm = min(n_insert, 120_000)
    step = max(n_warm // 8, 1)
    for i in range(n_warm):
        eng.insert(f"warm/{i % 31}/+/w{i}", -1 - i)
        if i % step == step - 1:
            eng.match_batch(probe)
    eng.match_batch(probe)
    for i in range(n_warm):
        eng.delete(-1 - i)
    eng.rebuild()  # reset to a clean base; delta tier re-warms from hot cache
    eng.match_batch(probe)

    # the 10M-sub phases leave gigabytes of static Python objects;
    # gen-2 collections rescanning them mid-churn cost 100+ ms pauses
    # (the reference tunes BEAM GC for the same reason — fullsweep /
    # emqx_gc policies).  Freeze the static heap for the timed region.
    import gc

    gc.collect()
    gc.freeze()

    nxt = len(base_filters)
    t0 = time.perf_counter()
    match_time = 0.0
    match_lat = []
    # route ops arrive in windows, as the reference's router syncer
    # batches them (?MAX_BATCH_SIZE 1000, emqx_router_syncer.erl:58):
    # insert_many is the engine's equivalent of one syncer batch
    window = 512
    for w0 in range(0, n_insert, window):
        eng.insert_many([
            (f"ins/{i % 4099}/+/x{i}", nxt + i)
            for i in range(w0, min(w0 + window, n_insert))
        ])
        if (w0 // window) % 4 == 3:  # match stream stays hot mid-churn
            m0 = time.perf_counter()
            eng.match_batch(probe)
            dt = time.perf_counter() - m0
            match_time += dt
            match_lat.append(dt)
    el = time.perf_counter() - t0 - match_time
    gc.unfreeze()
    rps = n_insert / el
    import numpy as _np

    lat_ms = _np.array(match_lat or [0.0]) * 1e3
    p50, p99 = _np.percentile(lat_ms, [50, 99])
    log(
        f"insert: {n_insert} inserts in {el:.2f}s -> {rps:,.0f}/s "
        f"(interleaved {len(match_lat)} match batches, p50 {p50:.1f} ms "
        f"p99 {p99:.1f} ms, stats={eng.index_stats()})"
    )
    # drain the engine's background build/fold threads: leaking them
    # into the next bench phase steals GIL from its measurement
    for tname in ("_build_thread", "_fold_thread"):
        t = getattr(eng, tname, None)
        if t is not None and t.is_alive():
            t.join(120)
    eng._poll_swap()
    return rps, float(p50), float(p99)


def run_dispatch_fanout_bench(log):
    """Dispatch-half microbench: fixed fan-out sweep (1 / 16 / 256
    subscribers per message) through the REAL window pipeline —
    publish_many → CSR expansion → per-client grouping →
    single-encode → corked per-connection write — with wire encode +
    write counted (each channel's send serializes every packet and
    appends to a sink, exactly Connection._send_packets minus the
    socket).  Host matching (the match half has its own benches);
    QoS 0 subscribers so the clock sees fan-out, not ack windows.

    Reports routed msg/s per fan-out level as
    ``dispatch_fanout_msgs_per_s``."""
    from emqx_tpu.broker.broker import Broker
    from emqx_tpu.broker.channel import Channel
    from emqx_tpu.broker.session import SubOpts
    from emqx_tpu.codec import mqtt as C
    from emqx_tpu.config import BrokerConfig
    from emqx_tpu.message import Message

    window = 64
    n_for = {1: 20000, 16: 4000, 256: 500}
    out = {}

    def setup(fanout, qos, label, max_inflight=None):
        """One broker + `fanout` subscribed channels writing into a
        byte/write-count sink."""
        cfg = BrokerConfig()
        cfg.engine.use_device = False
        b = Broker(config=cfg)
        sink = [0, 0]  # bytes written, write calls

        def send(pkts):
            data = b"".join(C.serialize(p, C.MQTT_V5) for p in pkts)
            sink[0] += len(data)
            sink[1] += 1

        flt = f"fan/{label}"
        kw = {} if max_inflight is None else {
            "max_inflight": max_inflight
        }
        for i in range(fanout):
            ch = Channel(b, send=send, close=lambda r: None)
            cid = f"f{label}-{i}"
            session, _ = b.cm.open_session(True, cid, ch, **kw)
            session.subscribe(flt, SubOpts(qos=qos))
            b.subscribe(cid, flt, SubOpts(qos=qos))
        return b, sink, flt

    def pump(b, flt, fanout, qos):
        """Warm, then route n_for windows; returns (rate, stages)."""
        n = n_for[fanout] if fanout in n_for else n_for[256]
        msgs = [Message(topic=flt, payload=b"x" * 64, qos=qos)
                for _ in range(n)]
        b.publish_many(msgs[:window])  # warm
        t0 = time.perf_counter()
        total = 0
        for w0 in range(window, n, window):
            w = msgs[w0:w0 + window]
            # stamp at "ingress": pre-built messages would otherwise
            # age across the run and trip the slow-subs scan on every
            # delivery — a harness artifact production never pays
            now = time.time()
            for m in w:
                m.timestamp = now
            total += sum(b.publish_many(w))
        dt = time.perf_counter() - t0
        routed = n - window
        assert total == routed * fanout, (total, routed * fanout)
        # the profiler rides the instrumented hot path (its shipping
        # default): per-stage p50/p99 says WHERE window time goes, not
        # just msg/s.  "e2e" is excluded: this harness constructs all
        # messages (timestamp-stamped) BEFORE the timed loop, so its
        # e2e samples measure time-since-bench-start, not delivery
        # latency — the broker e2e bench stamps at ingest and reports
        # the real number
        stages = {}
        for name, snap in b.profiler.snapshots().items():
            if snap.count and name != "e2e":
                stages[name] = {
                    "count": snap.count,
                    "p50_us": round(snap.percentile(50), 1),
                    "p99_us": round(snap.percentile(99), 1),
                }
        return routed / dt, routed, dt, stages

    def report(tag, fanout, rate, routed, dt, stages, sink):
        stage_str = " ".join(
            f"{k}={v['p50_us']:.0f}us"
            for k, v in sorted(stages.items())
            if k in ("expand", "decide", "deliver", "assemble",
                     "flush", "match_submit")
        )
        log(
            f"dispatch fanout {tag}: {rate:,.0f} msg/s "
            f"({routed * fanout / dt:,.0f} deliveries/s, "
            f"{sink[1]} writes, {sink[0] / (1 << 20):.1f} MiB; "
            f"stage p50 {stage_str})"
        )

    for fanout in (1, 16, 256):
        b, sink, flt = setup(fanout, qos=0, label=str(fanout))
        rate, routed, dt, stages = pump(b, flt, fanout, qos=0)
        out[f"fanout_{fanout}"] = rate
        out[f"fanout_{fanout}_stages"] = stages
        report(str(fanout), fanout, rate, routed, dt, stages, sink)

    # QoS1 row: the per-delivery session bookkeeping (packet-id
    # alloc, inflight insert, pid splice into the shared body) that
    # QoS0 fan-out never exercises — the half PR 5's native assembly
    # + block bookkeeping attack.  Unbounded inflight (the clients
    # never ack): the clock sees assembly, not window backpressure.
    # Since PR 9 this row registers a no-op `message.delivered` hook:
    # it measures the HOOK-CONSUMER case (per-run delivery lists
    # materialized for the callback), directly comparable to the
    # always-materializing pre-PR9 path.
    b, sink, flt = setup(256, qos=1, label="256q1", max_inflight=0)
    b.hooks.add("message.delivered", lambda cid, ds: None)
    rate, routed, dt, stages = pump(b, flt, 256, qos=1)
    out["fanout_256_qos1"] = rate
    out["fanout_256_qos1_stages"] = stages
    report("256 qos1", 256, rate, routed, dt, stages, sink)

    # the no-hooks twin: nothing consumes per-delivery lists, so the
    # window skips the hook walk AND the delivery-tuple
    # materialization — the lazy-deliveries win shows up as the gap
    # between this row and fanout_256_qos1
    b, sink, flt = setup(256, qos=1, label="256q1nh", max_inflight=0)
    rate, routed, dt, stages = pump(b, flt, 256, qos=1)
    out["fanout_256_qos1_nohooks"] = rate
    out["fanout_256_qos1_nohooks_stages"] = stages
    report("256 qos1 nohooks", 256, rate, routed, dt, stages, sink)
    out["note"] = (
        "publish_many windows of 64, QoS0, 64 B payloads stamped at "
        "ingress, host matching; encode+write counted (every packet "
        "serialized into a per-connection sink).  Pre-PR3 "
        "per-subscriber dispatch on this harness: fanout 1 -> "
        "33,314, 16 -> 4,709, 256 -> 267 msg/s (one transport write "
        "per delivery); PR3's window path (CSR expand -> encode-once "
        "-> corked flush) must hold fanout 256 at >= 3x that 267 "
        "baseline, and PR5's native assemble path (per-run decision "
        "scan -> GIL-released arena splice, the 'assemble' sub-stage) "
        "must hold >= 2x the PR4 number on the same box.  PR9 adds "
        "the 'decide' stage (window decision columns) and the "
        "fanout_256_qos1_nohooks row (lazy delivery lists: "
        "fanout_256_qos1 registers a no-op delivered hook, the "
        "nohooks row does not)."
    )
    return out


def run_replay_bench(log, n_sessions=256, n_backlog=64,
                     storm_sessions=2000):
    """Durable-replay bench (the mass-reconnect scenario): N
    checkpointed sessions, each owed an M-message QoS1 backlog from
    shared streams, reconnect and drain through the resume scheduler.

    ``replay_sessions_per_s``: scalar (per-session mqueue bake +
    per-packet encode) vs windowed (batched multi-session DS reads +
    dispatch windows through decide columns / encode-once / native
    splice) on identical worlds — run interleaved by the caller for
    A/B medians.  Encode+write counted exactly like the fanout bench
    (every packet serialized into a per-connection sink).

    ``reconnect_storm``: a larger storm with live publishes
    interleaved between scheduler rounds — drain wall time, live
    delivery p50/p99 while draining, and the max parked depth."""
    import shutil
    import tempfile

    from emqx_tpu.broker.broker import Broker
    from emqx_tpu.broker.channel import Channel
    from emqx_tpu.broker.session import SubOpts
    from emqx_tpu.codec import mqtt as C
    from emqx_tpu.config import BrokerConfig
    from emqx_tpu.ds.persist import DurableSessions
    from emqx_tpu.message import Message

    def seed(data_dir, n_sess, n_msgs):
        ds = DurableSessions(str(data_dir))
        t0 = time.time() - 60.0
        for i in range(n_sess):
            ds.save(f"r{i}", {"r/#": {"qos": 1}}, 7200.0, now=t0)
        ds.add_filter("r/#")
        # shared streams: every session replays the SAME backlog (the
        # broadcast-outage shape where windowed reads coalesce)
        ds.persist([
            Message(topic=f"r/{k % 8}/x", qos=1, payload=b"x" * 64,
                    timestamp=time.time())
            for k in range(n_msgs)
        ])
        ds.sync()
        ds.close()

    def drain(data_dir, n_sess, mode):
        """``scalar`` = the pre-scheduler shape (per-session
        `replay_chunk` reads, no sharing, mqueue bake + per-packet
        encode — what the resume loop did before this subsystem);
        ``sched_scalar`` = the scheduler pacing the SAME mqueue path
        with batched reads; ``windowed`` = batched reads + dispatch
        windows through decide columns / encode-once / native
        splice."""
        cfg = BrokerConfig()
        cfg.engine.use_device = False
        cfg.durable.enable = True
        cfg.durable.data_dir = str(data_dir)
        cfg.durable.resume.windowed = mode == "windowed"
        cfg.durable.resume.max_concurrent = 64
        cfg.durable.resume.park_queue_cap = n_sess
        b = Broker(config=cfg)
        scheduled = mode != "scalar"
        if scheduled:
            b.resume.running = True
        sink = [0, 0]

        def send(pkts):
            data = b"".join(C.serialize(p, C.MQTT_V5) for p in pkts)
            sink[0] += len(data)
            sink[1] += 1

        cids = [f"r{i}" for i in range(n_sess)]
        t0 = time.perf_counter()
        for cid in cids:
            ch = Channel(b, send=send, close=lambda r: None)
            ch.version = C.MQTT_V5
            session, present = b.open_session(
                False, cid, ch, expiry_interval=7200.0, max_inflight=0
            )
            assert present
            if not scheduled:
                # the legacy flow: replay filled the mqueue inside
                # open_session; CONNACK is followed by resume()
                ch.send_packets(session.resume())
        rounds = 0
        if scheduled:
            while any(b.resume.pending(c) for c in cids):
                b.resume.drain_once()
                rounds += 1
        dt = time.perf_counter() - t0
        sent = b.metrics.all().get("messages.sent", 0)
        stages = {}
        for name, snap in b.profiler.snapshots().items():
            if snap.count and name in (
                "replay_read", "expand", "decide", "deliver",
                "assemble", "flush",
            ):
                stages[name] = {
                    "count": snap.count,
                    "p50_us": round(snap.percentile(50), 1),
                    "p99_us": round(snap.percentile(99), 1),
                }
        b.durable.close()
        return n_sess / dt, sent, dt, rounds, stages, sink

    out = {}
    for tag in ("scalar", "sched_scalar", "windowed"):
        d = tempfile.mkdtemp(prefix=f"replay_{tag}_")
        try:
            seed(d, n_sessions, n_backlog)
            rate, sent, dt, rounds, stages, sink = drain(
                d, n_sessions, tag
            )
            assert sent >= n_sessions * n_backlog, (sent, tag)
            out[f"replay_sessions_per_s_{tag}"] = rate
            out[f"replay_{tag}_stages"] = stages
            log(
                f"replay {tag}: {rate:,.1f} sessions/s "
                f"({n_sessions} sessions x {n_backlog} qos1 msgs in "
                f"{dt:.2f}s, {rounds} rounds, {sent:,} deliveries, "
                f"{sink[0] / (1 << 20):.1f} MiB wire)"
            )
        finally:
            shutil.rmtree(d, ignore_errors=True)
    if out.get("replay_sessions_per_s_scalar"):
        out["replay_windowed_vs_scalar"] = (
            out["replay_sessions_per_s_windowed"]
            / out["replay_sessions_per_s_scalar"]
        )

    # reconnect storm: drain a big park queue while live publishes
    # measure event-loop availability between scheduler rounds
    d = tempfile.mkdtemp(prefix="replay_storm_")
    try:
        seed(d, storm_sessions, 8)
        cfg = BrokerConfig()
        cfg.engine.use_device = False
        cfg.durable.enable = True
        cfg.durable.data_dir = d
        cfg.durable.resume.max_concurrent = 64
        cfg.durable.resume.park_queue_cap = storm_sessions
        b = Broker(config=cfg)
        b.resume.running = True
        sink = [0]

        def send2(pkts):
            sink[0] += sum(
                len(C.serialize(p, C.MQTT_V5)) for p in pkts
            )

        cids = [f"r{i}" for i in range(storm_sessions)]
        for cid in cids:
            ch = Channel(b, send=send2, close=lambda r: None)
            ch.version = C.MQTT_V5
            b.open_session(False, cid, ch, expiry_interval=7200.0,
                           max_inflight=0)
        parked_max = b.resume.info()["parked"]
        live_ch = Channel(b, send=send2, close=lambda r: None)
        live_ch.version = C.MQTT_V5
        ls, _ = b.cm.open_session(True, "live", live_ch)
        ls.subscribe("live/x", SubOpts(qos=0))
        b.subscribe("live", "live/x", SubOpts(qos=0))
        live_lat = []
        pending = set(cids)
        t0 = time.perf_counter()
        rounds = 0
        while pending:
            b.resume.drain_once()
            rounds += 1
            if rounds % 5 == 0:
                t1 = time.perf_counter()
                b.publish_many([Message(
                    topic="live/x", qos=0, payload=b"hb",
                    timestamp=time.time(),
                )])
                live_lat.append(time.perf_counter() - t1)
            if rounds % 50 == 0 or len(pending) < 128:
                pending = {c for c in pending
                           if b.resume.pending(c)}
        storm_dt = time.perf_counter() - t0
        live_lat.sort()
        out["reconnect_storm"] = {
            "sessions": storm_sessions,
            "backlog_per_session": 8,
            "drain_s": storm_dt,
            "sessions_per_s": storm_sessions / storm_dt,
            "parked_max": parked_max,
            "live_publish_p50_ms": (
                live_lat[len(live_lat) // 2] * 1e3 if live_lat else 0
            ),
            "live_publish_p99_ms": (
                live_lat[int(len(live_lat) * 0.99)] * 1e3
                if live_lat else 0
            ),
        }
        log(
            f"reconnect storm: {storm_sessions} sessions drained in "
            f"{storm_dt:.2f}s "
            f"({storm_sessions / storm_dt:,.0f} sessions/s), "
            f"parked_max={parked_max}, live publish p99 "
            f"{out['reconnect_storm']['live_publish_p99_ms']:.1f} ms"
        )
        b.durable.close()
    finally:
        shutil.rmtree(d, ignore_errors=True)
    return out


def run_durability_bench(log, iters=None, n_msgs=None,
                         recovery_msgs=None, write_json=True):
    """Durability A/B (BENCH_r12, the PR 15 tentpole): persistent-
    session QoS1 publish throughput under the four fsync disciplines —

      * ``never``      no fsync anywhere (the pre-PR hot path);
      * ``interval``   periodic group flush off the tick (acks free);
      * ``always``     group-commit: ONE fsync amortized per dispatch
                       window before the window's acks release;
      * ``naive``      the counterfactual the group commit exists to
                       beat: fsync per MESSAGE (window size 1).

    Interleaved iterations, medians reported.  The acceptance bar:
    ``always`` >= 5x ``naive`` and ``interval`` within ~10% of
    ``never`` (no robustness tax on the default).

    Plus cold-recovery numbers on a >=1M-message store: native
    segment-scan reopen (index rebuild) and the full census rebuild
    after metadata loss (the log-is-source-of-truth path).
    """
    import shutil
    import statistics
    import tempfile

    from emqx_tpu.broker.broker import Broker
    from emqx_tpu.config import BrokerConfig
    from emqx_tpu.ds.builtin_local import LocalStorage
    from emqx_tpu.ds.native import DsLog
    from emqx_tpu.message import Message

    iters = iters or int(os.environ.get("BENCH_DUR_ITERS", "5"))
    n_msgs = n_msgs or int(os.environ.get("BENCH_DUR_MSGS", "2048"))
    recovery_msgs = recovery_msgs or int(
        os.environ.get("BENCH_DUR_RECOVERY_MSGS", "1000000")
    )
    window = 64

    def one_run(mode):
        """One measured pass: a detached persistent subscriber's
        filter arms the gate, the publisher pushes QoS1 windows
        through publish_many (the loop-less group-commit path: in
        `always` mode each window ends with its covering flush, the
        contract a socketed PUBACK rides)."""
        d = tempfile.mkdtemp(prefix=f"dur_{mode}_")
        try:
            cfg = BrokerConfig()
            cfg.engine.use_device = False
            cfg.durable.enable = True
            cfg.durable.data_dir = d
            cfg.durable.fsync = "always" if mode == "naive" else mode
            b = Broker(config=cfg)
            b.durable.save(
                "psub", {"bench/#": {"qos": 1}}, 7200.0,
                now=time.time() - 30.0,
            )
            b.durable.add_filter("bench/#")
            win = 1 if mode == "naive" else window
            payload = b"x" * 64
            msgs = [
                Message(
                    topic=f"bench/{i % 128}/t", qos=1,
                    payload=payload, timestamp=time.time(),
                )
                for i in range(n_msgs)
            ]
            t0 = time.perf_counter()
            for off in range(0, n_msgs, win):
                b.publish_many(msgs[off:off + win])
            dt = time.perf_counter() - t0
            syncs = b.durable.gate.sync_count
            stored = b.durable.storage.stats()["messages"]
            assert stored == n_msgs, (mode, stored)
            if mode in ("always", "naive"):
                assert not b.durable.gate.dirty  # acked => flushed
                assert syncs >= (n_msgs // win)
            b.durable.close()
            return n_msgs / dt, syncs
        finally:
            shutil.rmtree(d, ignore_errors=True)

    modes = ("never", "interval", "always", "naive")
    rates = {m: [] for m in modes}
    syncs = {m: 0 for m in modes}
    for it in range(iters):
        for m in modes:  # interleaved: drift hits every mode equally
            r, s = one_run(m)
            rates[m].append(r)
            syncs[m] = s
        log(
            f"durability iter {it}: " + ", ".join(
                f"{m}={rates[m][-1]:,.0f}/s" for m in modes
            )
        )
    med = {m: statistics.median(rates[m]) for m in modes}
    out = {
        "publish_qos1_msgs_per_s": {m: med[m] for m in modes},
        "syncs_per_run": syncs,
        "always_vs_naive": med["always"] / med["naive"],
        "interval_vs_never": med["interval"] / med["never"],
        "window": window,
        "n_msgs": n_msgs,
        "iters": iters,
    }
    log(
        f"durability medians: never={med['never']:,.0f} "
        f"interval={med['interval']:,.0f} always={med['always']:,.0f} "
        f"naive={med['naive']:,.0f} msg/s; always/naive="
        f"{out['always_vs_naive']:.1f}x (>=5x bar), interval/never="
        f"{out['interval_vs_never']:.2f} (~0.9+ bar)"
    )

    # ---- cold recovery on a >=1M-message store (log scan + census
    # rebuild after metadata loss)
    d = tempfile.mkdtemp(prefix="dur_recovery_")
    try:
        store = LocalStorage(d, n_streams=16)
        payload = b"r" * 16
        t_fill0 = time.perf_counter()
        batch = 4096
        msgs = [
            Message(
                topic=f"f/{i % 512}/t", qos=1, payload=payload,
                timestamp=1e9 + i,
            )
            for i in range(batch)
        ]
        filled = 0
        while filled < recovery_msgs:
            store.store_batch(msgs[: min(batch, recovery_msgs - filled)])
            filled += batch
        store.sync()
        store.close()
        fill_dt = time.perf_counter() - t_fill0
        size_mb = sum(
            os.path.getsize(os.path.join(d, f))
            for f in os.listdir(d)
        ) / (1 << 20)
        # clean reopen: native segment scan rebuilds the (stream, ts)
        # index; the census cache is valid and skips the decode pass
        t0 = time.perf_counter()
        store = LocalStorage(d, n_streams=16)
        open_clean_s = time.perf_counter() - t0
        n = store.stats()["messages"]
        store.close()
        # metadata loss: census gone — the log is the source of truth
        # and the census rebuild decodes every record (it runs in the
        # background now; rebuild_now() joins so the decode pass is
        # what the timer sees)
        os.unlink(os.path.join(d, "census.json"))
        t0 = time.perf_counter()
        store = LocalStorage(d, n_streams=16)
        store.rebuild_now()
        rebuild_s = time.perf_counter() - t0
        assert store.stats()["messages"] == n >= recovery_msgs
        store.close()
        # native-only recovery floor (no census logic at all)
        t0 = time.perf_counter()
        lg = DsLog(d)
        native_open_s = time.perf_counter() - t0
        lg.close()
        out["cold_recovery"] = {
            "messages": int(n),
            "store_mb": round(size_mb, 1),
            "fill_s": round(fill_dt, 2),
            "native_open_s": round(native_open_s, 3),
            "open_clean_s": round(open_clean_s, 3),
            "census_rebuild_s": round(rebuild_s, 2),
        }
        log(
            f"cold recovery: {n:,} msgs ({size_mb:.0f} MiB) — native "
            f"open {native_open_s:.2f}s, clean open {open_clean_s:.2f}s, "
            f"census rebuild after meta loss {rebuild_s:.1f}s"
        )
    finally:
        shutil.rmtree(d, ignore_errors=True)

    if write_json:
        write_result("BENCH_r12.json", out)
    return out


def run_ds_shard_bench(log, iters=None, n_msgs=None,
                       recovery_msgs=None, write_json=True):
    """Sharded DS store A/B (BENCH_r13, the PR 16 tentpole): three
    measurements —

      * APPEND THROUGHPUT at 1/2/4 shards with ``always`` semantics
        (every window fsynced before the next): four writer threads
        drive the segment engine directly — the layer sharding
        changes.  One shard = every writer serializes on ONE store
        mutex, which is held ACROSS the fsync (dslog.cpp), so appends
        stall for the whole flush; N shards = N independent mutexes
        and fsync barriers whose IO waits overlap.  Two interleaved
        configs, medians of interleaved iterations:

          - ``io_bound`` (the acceptance row): 4 KiB records, window
            2 — commit wait dominates, so the per-shard barrier
            independence is what the clock sees.  Bar: 4 shards >=
            2x one shard.
          - ``cpu_bound`` (the honest counterpoint): 96 B records,
            window 16 — per-record CPU dominates, and THE BENCH BOX
            HAS ONE CORE, so the only parallelism sharding can add
            is fsync-wait/append overlap; the ratio compresses
            toward 1x as CPU share grows.  On a multi-core box this
            row scales too (the flushes run truly in parallel); a
            1-core box bounds any workload's speedup by
            (cpu + io) / max(cpu, io).

        The session layer above the engine (encode, census journal,
        gate bookkeeping) is shard-independent CPU and identical in
        both columns; driving it here would only dilute the A/B with
        a constant.
      * RESTART-TO-SERVING on a 1M-message 4-shard store, three
        metadata states: intact (snapshot folded, journal empty — the
        O(1)-ish fast path, bar: < 2 s), journal-replay (crash after
        a flush, before the fold: snapshot + journal + per-stream
        delta scan from the watermark — O(delta)), and full rebuild
        after metadata loss (every record decoded; runs in the
        background, so both time-to-serving and time-to-complete are
        reported).
      * GC RECLAIM RATE under live appends: retention passes
        interleave with an appending writer; reclaimed records/s plus
        proof the writer never stalls.
    """
    import concurrent.futures
    import shutil
    import statistics
    import tempfile
    import threading

    from emqx_tpu.ds.native import DsLog
    from emqx_tpu.ds.sharded import ShardedStorage
    from emqx_tpu.message import Message

    iters = iters or int(os.environ.get("BENCH_SHARD_ITERS", "9"))
    n_msgs = n_msgs or int(os.environ.get("BENCH_SHARD_MSGS", "4096"))
    recovery_msgs = recovery_msgs or int(
        os.environ.get("BENCH_SHARD_RECOVERY_MSGS", "1000000")
    )
    n_threads = 4

    def one_run(n_shards, window, recsize, total):
        d = tempfile.mkdtemp(prefix=f"shard{n_shards}_")
        try:
            logs = [
                DsLog(os.path.join(d, f"shard-{i:02d}"))
                for i in range(n_shards)
            ]
            per = total // n_threads
            rec = b"x" * recsize

            def writer(tid):
                lg = logs[tid % n_shards]
                for i in range(0, per, window):
                    for j in range(window):
                        lg.append(tid, 1_000_000 + i + j, rec)
                    lg.sync()  # the always-mode fsync barrier

            t0 = time.perf_counter()
            with concurrent.futures.ThreadPoolExecutor(n_threads) as ex:
                list(ex.map(writer, range(n_threads)))
            dt = time.perf_counter() - t0
            for lg in logs:
                lg.close()
            return total / dt
        finally:
            shutil.rmtree(d, ignore_errors=True)

    shard_counts = (1, 2, 4)
    configs = {
        "io_bound": dict(window=2, recsize=4096, total=n_msgs),
        "cpu_bound": dict(window=16, recsize=96, total=n_msgs * 2),
    }
    rates = {c: {n: [] for n in shard_counts} for c in configs}
    for it in range(iters):
        for cfg, kw in configs.items():
            for n in shard_counts:  # interleaved: drift hits all
                rates[cfg][n].append(one_run(n, **kw))
        log(
            f"ds_shard iter {it}: " + "; ".join(
                cfg + " " + ", ".join(
                    f"{n}={rates[cfg][n][-1]:,.0f}/s"
                    for n in shard_counts
                )
                for cfg in configs
            )
        )
    out = {"writer_threads": n_threads, "iters": iters}
    for cfg, kw in configs.items():
        med = {n: statistics.median(rates[cfg][n]) for n in shard_counts}
        out["append_" + cfg] = {
            **{str(n) + "_shard_msgs_per_s": med[n]
               for n in shard_counts},
            "shards4_vs_1": med[4] / med[1],
            **kw,
        }
        log(
            f"ds_shard {cfg} medians: 1={med[1]:,.0f} "
            f"2={med[2]:,.0f} 4={med[4]:,.0f} msg/s; "
            f"4/1={med[4] / med[1]:.2f}x"
            + (" (>=2x bar)" if cfg == "io_bound" else "")
        )

    # ---- restart-to-serving at 1M messages, three metadata states
    d = tempfile.mkdtemp(prefix="shard_recovery_")
    try:
        n_shards = 4
        st = ShardedStorage(d, n_shards=n_shards, layout="hash")
        payload = b"r" * 16
        batch = 4096
        t_fill0 = time.perf_counter()
        filled = 0
        while filled < recovery_msgs:
            n = min(batch, recovery_msgs - filled)
            st.store_batch([
                Message(topic=f"f/{(filled + i) % 512}/t", qos=1,
                        payload=payload, timestamp=1e9 + filled + i)
                for i in range(n)
            ])
            filled += n
        st.sync_data()
        st.save_meta()
        fill_dt = time.perf_counter() - t_fill0
        st.close()  # folds every shard's journal into its snapshot

        # 1: metadata intact — snapshot + empty journal, delta scan
        # finds nothing (the < 2 s acceptance bar)
        t0 = time.perf_counter()
        st = ShardedStorage(d, n_shards=n_shards, layout="hash")
        open_intact_s = time.perf_counter() - t0
        total = st.stats()["messages"]
        assert total >= recovery_msgs, total

        # 2: journal-replay — append a delta tail, flush the journal,
        # then drop the handles WITHOUT the close-time fold (the
        # crash-after-flush state): reopen pays snapshot + journal
        # replay + delta scan from the watermark
        delta = recovery_msgs // 100
        st.store_batch([
            Message(topic=f"g/{i % 64}/t", qos=1, payload=payload,
                    timestamp=2e9 + i)
            for i in range(delta)
        ])
        st.sync_data()
        st.save_meta()  # journal append, NO fold
        for inner in st.stores:
            inner._log.close()  # crash: no close-time fold
        t0 = time.perf_counter()
        st = ShardedStorage(d, n_shards=n_shards, layout="hash")
        open_journal_s = time.perf_counter() - t0
        assert st.stats()["messages"] == total + delta
        st.close()

        # 3: full rebuild after metadata loss — serving starts
        # immediately (reads go unpruned to the log); completion is
        # the background decode pass over every record
        for i in range(n_shards):
            sub = os.path.join(d, f"shard-{i:02d}")
            for f in ("census.json", "census.journal"):
                p = os.path.join(sub, f)
                if os.path.exists(p):
                    os.unlink(p)
        t0 = time.perf_counter()
        st = ShardedStorage(d, n_shards=n_shards, layout="hash")
        open_rebuild_serving_s = time.perf_counter() - t0
        st.rebuild_now()
        open_rebuild_complete_s = time.perf_counter() - t0
        assert st.stats()["messages"] == total + delta
        st.close()
        out["restart_to_serving"] = {
            "messages": int(total + delta),
            "shards": n_shards,
            "fill_s": round(fill_dt, 2),
            "intact_s": round(open_intact_s, 3),
            "journal_replay_s": round(open_journal_s, 3),
            "journal_delta_msgs": delta,
            "rebuild_serving_s": round(open_rebuild_serving_s, 3),
            "rebuild_complete_s": round(open_rebuild_complete_s, 2),
        }
        log(
            f"restart-to-serving @ {total + delta:,} msgs x "
            f"{n_shards} shards: intact {open_intact_s:.3f}s "
            f"(< 2 s bar), journal replay ({delta:,} delta) "
            f"{open_journal_s:.3f}s, rebuild serving "
            f"{open_rebuild_serving_s:.3f}s / complete "
            f"{open_rebuild_complete_s:.1f}s"
        )
    finally:
        shutil.rmtree(d, ignore_errors=True)

    # ---- GC reclaim rate under live appends
    d = tempfile.mkdtemp(prefix="shard_gc_")
    try:
        st = ShardedStorage(
            d, n_shards=4, layout="hash", seg_bytes=1 << 16
        )
        payload = b"g" * 128
        base_ts = 1e9
        st.store_batch([
            Message(topic=f"f/{i % 64}/t", qos=1, payload=payload,
                    timestamp=base_ts + i)
            for i in range(50_000)
        ], sync=True)
        stop = threading.Event()
        appended = [0]

        def appender():
            i = 0
            while not stop.is_set():
                st.store_batch([
                    Message(topic=f"f/{(i + j) % 64}/t", qos=1,
                            payload=payload,
                            timestamp=base_ts + 100_000 + i + j)
                    for j in range(256)
                ])
                i += 256
                appended[0] = i

        th = threading.Thread(target=appender, daemon=True)
        th.start()
        reclaimed = 0
        t0 = time.perf_counter()
        # advancing cutoff: each pass releases another slice of the
        # backlog while the writer keeps appending
        for cut in range(10):
            cutoff = int((base_ts + (cut + 1) * 5_000) * 1e6)
            reclaimed += st.gc_pinned(cutoff, {})
            time.sleep(0.02)
        gc_dt = time.perf_counter() - t0
        stop.set()
        th.join()
        st.close()
        out["gc_under_load"] = {
            "reclaimed_records": int(reclaimed),
            "reclaim_records_per_s": round(reclaimed / gc_dt, 1),
            "live_appends_during_gc": int(appended[0]),
        }
        log(
            f"gc under load: {reclaimed:,} records reclaimed in "
            f"{gc_dt:.2f}s ({reclaimed / gc_dt:,.0f}/s) while "
            f"{appended[0]:,} live appends landed"
        )
    finally:
        shutil.rmtree(d, ignore_errors=True)

    if write_json:
        write_result("BENCH_r13.json", out)
    return out


def run_cluster_forward_bench(log, n_msgs=None, iters=None,
                              write_json=True):
    """Cluster window forwarding A/B (BENCH_r09): batched scatter
    throughput and per-message forward latency across a 2-node
    in-process cluster — one publisher on node A, one QoS1 wildcard
    subscriber on node B, every message crossing the inter-node link
    as sequenced at-least-once window frames.

    Rows: ``tcp`` (the stock PeerLink), ``quic`` (the in-repo QUIC
    peer transport, PSK profile), and ``quic_loss1`` (QUIC under
    seeded 1% datagram loss on both quic seams — the robustness case
    TCP byte streams handle with head-of-line stalls).  Interleaved
    iterations; medians carry the signal.  Acceptance: QUIC lossless
    throughput >= the TCP baseline (no robustness tax on the happy
    path)."""
    import asyncio

    from emqx_tpu import failpoints as fpmod
    from emqx_tpu.broker.listener import BrokerServer
    from emqx_tpu.cluster import ClusterNode
    from emqx_tpu.codec import mqtt as C
    from emqx_tpu.config import BrokerConfig, ListenerConfig

    n_msgs = n_msgs or int(os.environ.get("BENCH_CF_MSGS", 3000))
    iters = iters or int(os.environ.get("BENCH_CF_ITERS", 5))
    payload = b"x" * int(os.environ.get("BENCH_CF_PAYLOAD", 200))

    async def once(mode, loss=0.0, seed=0):
        def mk_cfg():
            cfg = BrokerConfig()
            cfg.listeners = [ListenerConfig(port=0)]
            cfg.engine.use_device = False  # measure the wire, not XLA
            # unbounded-ish session windows: the clock must see the
            # forward pipeline, not the subscriber's ack window (same
            # rationale as run_replay_bench)
            cfg.mqtt.max_inflight = 4096
            cfg.mqtt.max_mqueue_len = 1_000_000
            return cfg

        sa = BrokerServer(mk_cfg())
        await sa.start()
        sb = BrokerServer(mk_cfg())
        await sb.start()
        fast = dict(
            heartbeat_interval=0.2, down_after=5.0,
            flush_interval=0.002, consensus="lww",
            transport_mode=mode,
        )
        a = ClusterNode("bfa", sa.broker, **fast)
        await a.start()
        b = ClusterNode("bfb", sb.broker, **fast)
        await b.start(seeds=[("bfa", "127.0.0.1", a.port)])
        lat = []
        try:
            loop = asyncio.get_running_loop()

            async def open_conn(port, cid):
                r, w = await asyncio.open_connection("127.0.0.1", port)
                w.write(C.serialize(
                    C.Connect(client_id=cid, proto_ver=C.MQTT_V5),
                    C.MQTT_V5,
                ))
                await w.drain()
                p = C.StreamParser(version=C.MQTT_V5)
                while True:
                    data = await r.read(1 << 16)
                    assert data, "closed during CONNECT"
                    if list(p.feed(data)):
                        break
                return r, w, p

            sr, sw, sp = await open_conn(
                sb.listeners[0].port, "cf-sub"
            )
            sw.write(C.serialize(
                C.Subscribe(packet_id=1, subscriptions=[
                    C.Subscription(topic_filter="cf/#", qos=1)
                ]),
                C.MQTT_V5,
            ))
            await sw.drain()
            while True:
                data = await sr.read(1 << 16)
                assert data
                if any(p.type == C.SUBACK for p in sp.feed(data)):
                    break
            await asyncio.sleep(0.4)  # route delta -> node A

            pr, pw, pp = await open_conn(
                sa.listeners[0].port, "cf-pub"
            )

            async def drain_pub():  # eat PUBACKs to the publisher
                while True:
                    data = await pr.read(1 << 16)
                    if not data:
                        return
                    list(pp.feed(data))

            drainer = loop.create_task(drain_pub())
            if loss > 0.0:
                fpmod.configure("cluster.quic.send", "drop",
                                prob=loss, seed=seed)
                fpmod.configure("cluster.quic.recv", "drop",
                                prob=loss, seed=seed + 1)
            sent_at = {}
            got = set()
            done = loop.create_future()

            async def consume():
                while len(got) < n_msgs:
                    data = await sr.read(1 << 16)
                    assert data, "subscriber link died"
                    now = time.perf_counter()
                    acks = []
                    for pkt in sp.feed(data):
                        if pkt.type != C.PUBLISH:
                            continue
                        if pkt.topic not in got:
                            got.add(pkt.topic)
                            lat.append(now - sent_at[pkt.topic])
                        if pkt.qos:
                            acks.append(C.serialize(
                                C.Puback(packet_id=pkt.packet_id),
                                C.MQTT_V5,
                            ))
                    if acks:
                        sw.write(b"".join(acks))
                        await sw.drain()
                done.set_result(None)

            eater = loop.create_task(consume())
            # flow-controlled publisher: a bounded outstanding window
            # keeps the measure steady-state (and off this sandbox
            # kernel's zero-window pathology on single-connection
            # multi-hundred-KB bursts)
            window = 256
            t0 = time.perf_counter()
            for i in range(n_msgs):
                while i - len(got) >= window:
                    await asyncio.sleep(0.001)
                topic = f"cf/{i}"
                sent_at[topic] = time.perf_counter()
                pw.write(C.serialize(
                    C.Publish(topic=topic, payload=payload, qos=1,
                              packet_id=(i % 60000) + 1),
                    C.MQTT_V5,
                ))
                if i % 64 == 63:
                    await pw.drain()
            await pw.drain()
            await asyncio.wait_for(done, timeout=120)
            dt = time.perf_counter() - t0
            eater.cancel()
            drainer.cancel()
            assert len(got) == n_msgs, (
                f"forwarded loss: {n_msgs - len(got)} missing"
            )
            lat.sort()
            return {
                "msgs_per_s": n_msgs / dt,
                "fwd_p50_ms": lat[len(lat) // 2] * 1e3,
                "fwd_p99_ms": lat[int(len(lat) * 0.99)] * 1e3,
            }
        finally:
            fpmod.clear()
            await b.stop()
            await sb.stop()
            await a.stop()
            await sa.stop()

    rows = [
        ("tcp", "tcp", 0.0),
        ("quic", "quic", 0.0),
        ("quic_loss1", "quic", 0.01),
    ]
    runs = {name: [] for name, _, _ in rows}
    for it in range(iters):  # interleaved A/B: noise hits all rows
        for name, mode, loss in rows:
            r = asyncio.run(once(mode, loss, seed=20260804 + it))
            runs[name].append(r)
            log(
                f"cluster_forward[{name}] iter {it}: "
                f"{r['msgs_per_s']:,.0f} msg/s, p50 "
                f"{r['fwd_p50_ms']:.1f} ms, p99 {r['fwd_p99_ms']:.1f} ms"
            )

    def med(xs):
        xs = sorted(xs)
        return xs[len(xs) // 2]

    summary = {
        name: {
            k: round(med([r[k] for r in rs]), 2)
            for k in ("msgs_per_s", "fwd_p50_ms", "fwd_p99_ms")
        }
        for name, rs in runs.items()
    }
    log(f"cluster_forward medians: {json.dumps(summary)}")
    if write_json:
        out = {
            "pr": 11,
            "metric": "cluster_forward_msgs_per_s",
            "methodology": (
                "Interleaved A/B, {it} iterations each, same box "
                "(bench.py run_cluster_forward_bench): 2-node "
                "in-process cluster (lww), one publisher on node A "
                "bursting {n} QoS1 publishes ({p}B payloads) that all "
                "forward to node B's wildcard subscriber as sequenced "
                "at-least-once window frames; throughput clocks first "
                "publish to last delivery, latency is per-message "
                "publish->delivery on one clock.  'tcp' = the stock "
                "PeerLink; 'quic' = the in-repo QUIC peer transport "
                "(PSK profile, control+forward streams, selective-ACK "
                "recovery); 'quic_loss1' = QUIC under seeded 1% "
                "datagram loss on cluster.quic.send AND .recv (the "
                "failpoint seams) — zero-loss is asserted in-run.  "
                "Medians reported; ratios carry the signal."
            ).format(it=iters, n=n_msgs, p=len(payload)),
            "runs": runs,
            "medians": summary,
            "criteria": {
                "quic_vs_tcp_lossless_throughput": round(
                    summary["quic"]["msgs_per_s"]
                    / summary["tcp"]["msgs_per_s"], 3,
                ),
                "quic_loss1_p99_vs_lossless": round(
                    summary["quic_loss1"]["fwd_p99_ms"]
                    / max(summary["quic"]["fwd_p99_ms"], 1e-9), 3,
                ),
            },
        }
        write_result("BENCH_r09.json", out)
    return summary


def run_rules_bench(log, iters=None, write_json=True):
    """Rule-engine WHERE evaluation A/B (BENCH_r10): N registered
    rules x a fanout dispatch window through the REAL pipeline
    (publish_many -> trie match of rule topic filters -> rule sink ->
    apply_batch), on identical worlds:

      * ``scalar`` — RuleEngine.eval_force="scalar": the per-rule
        interpreter referee (per-message eval_where over lazy envs);
      * ``host``   — the stacked rules x window matrix on the numpy
        twin (matched-row slice);
      * ``dev``    — the fused rules_eval_batch JAX kernel.

    Registries of 1k and 10k lowerable rules partitioned over 16
    topic groups (each message matches ~N/16 rules), predicates a
    rotating mix of numeric/string/IN/presence shapes at ~1/8 pass
    rate so action dispatch stays off the clock.  Interleaved
    iterations; medians carry the signal; per-stage attribution
    (extract vs eval) from the profiler's ``rules`` lap +
    ``rules_extract``/``rules_eval`` sub-stages."""
    import numpy as _np  # noqa: F401  (env sanity: numpy present)

    from emqx_tpu.broker.broker import Broker
    from emqx_tpu.config import BrokerConfig
    from emqx_tpu.message import Message

    from emqx_tpu.rules.runtime import (
        build_env, eval_select, eval_where,
    )

    iters = iters or int(os.environ.get("BENCH_RULES_ITERS", 5))
    window = 64
    n_groups = 16

    _PREDS = [
        "payload.v = {k}",
        "payload.v > 29 AND payload.s = 'x'",
        "payload.s IN ('q', 'z{k}')",
        "is_null(payload.w) AND payload.v >= 30",
        "payload.v IN ({k}, 31)",
        "NOT (payload.v < 30) AND payload.s != 'y'",
    ]

    def prepr_apply_batch(eng):
        """The PRE-PR `RuleEngine.apply_batch`, verbatim (the
        acceptance baseline): full `build_env` per matched message,
        one Python pass per rule with per-rule PredicateProgram
        column extraction, per-hit metrics."""

        def apply_batch(items, rec=None):
            if not items:
                return 0
            if len(items) == 1:
                return eng.apply(items[0][0], items[0][1])
            msgs = [m for m, _ in items]
            env_cache = [None] * len(items)

            def env(i):
                e = env_cache[i]
                if e is None:
                    e = env_cache[i] = build_env(msgs[i])
                return e

            by_rule = {}
            for i, (_, rids) in enumerate(items):
                for rid in rids:
                    by_rule.setdefault(rid, []).append(i)
            hits = 0
            for rid, idxs in by_rule.items():
                rule = eng.rules.get(rid)
                if rule is None or not rule.enabled:
                    continue
                rule.matched += len(idxs)
                if rule.program is not None and len(idxs) > 1:
                    mask = rule.program.eval_batch(
                        [env(i) for i in idxs]
                    )
                    passed = [
                        i for i, ok in zip(idxs, mask.tolist()) if ok
                    ]
                else:
                    passed = [
                        i for i in idxs
                        if eval_where(rule.parsed.where, env(i))
                    ]
                rule.failed += len(idxs) - len(passed)
                rule.passed += len(passed)
                hits += len(passed)
                for i in passed:
                    selected = eval_select(rule.parsed, env(i))
                    eng._run_firings(rule, [(selected, msgs[i])])
            if eng.broker is not None and hits:
                eng.broker.metrics.inc("rules.matched", hits)
            return hits

        return apply_batch

    def build(mode, n_rules):
        cfg = BrokerConfig()
        cfg.engine.use_device = False  # match half: host trie
        b = Broker(config=cfg)
        if mode == "prepr":
            b.rules.apply_batch = prepr_apply_batch(b.rules)
        elif mode == "referee":
            b.rules.eval_force = "scalar"
        else:
            b.router.engine.rules_force = mode
        for i in range(n_rules):
            pred = _PREDS[i % len(_PREDS)].format(k=24 + i % 8)
            b.rules.add_rule(
                f"r{i}",
                f'SELECT * FROM "bench/{i % n_groups}/#" '
                f"WHERE {pred}",
            )
        return b

    def pump(b, n_msgs):
        msgs = [
            Message(
                topic=f"bench/{j % n_groups}/x",
                payload=(
                    '{"v": %d, "s": "%s"}' % (j % 32, "xyq"[j % 3])
                ).encode(),
                qos=0,
            )
            for j in range(n_msgs)
        ]
        b.publish_many(msgs[:window])  # warm (JIT compile off-clock)
        t0 = time.perf_counter()
        for w0 in range(window, n_msgs, window):
            w = msgs[w0:w0 + window]
            now = time.time()
            for m in w:
                m.timestamp = now
            b.publish_many(w)
        dt = time.perf_counter() - t0
        return (n_msgs - window) / dt

    results = {}
    for n_rules in (1000, 10000):
        n_msgs = window * (33 if n_rules == 1000 else 9)
        brokers = {
            mode: build(mode, n_rules)
            for mode in ("prepr", "referee", "host", "dev")
        }
        runs = {m: [] for m in brokers}
        for it in range(iters):
            for mode, b in brokers.items():
                runs[mode].append(pump(b, n_msgs))

        def med(xs):
            return sorted(xs)[len(xs) // 2]

        stages = {}
        for mode, b in brokers.items():
            snap = {}
            for name, s in b.profiler.snapshots().items():
                if s.count and name in (
                    "rules", "rules_extract", "rules_eval",
                ):
                    snap[name] = {
                        "count": s.count,
                        "p50_us": round(s.percentile(50), 1),
                        "p99_us": round(s.percentile(99), 1),
                    }
            snap["engine"] = {
                k: v for k, v in b.rules.stats().items()
                if isinstance(v, (int, float)) and v is not None
            }
            stages[mode] = snap
        medians = {m: round(med(rs), 1) for m, rs in runs.items()}
        key = f"rules_{n_rules}"
        # rule-match throughput isolated to the rules STAGE (the part
        # this PR vectorizes): pre-PR rules-lap p50 / matrix rules-lap
        # p50 — the end-to-end msg/s ratio additionally carries the
        # match/expand floor both paths share
        try:
            stage_ratio = round(
                stages["prepr"]["rules"]["p50_us"]
                / stages["host"]["rules"]["p50_us"], 2,
            )
        except (KeyError, ZeroDivisionError):
            stage_ratio = None
        results[key] = {
            "runs": {m: [round(r, 1) for r in rs]
                     for m, rs in runs.items()},
            "medians_msgs_per_s": medians,
            "speedup_host_vs_prepr": round(
                medians["host"] / medians["prepr"], 2
            ),
            "speedup_dev_vs_prepr": round(
                medians["dev"] / medians["prepr"], 2
            ),
            "speedup_host_vs_referee": round(
                medians["host"] / medians["referee"], 2
            ),
            "stage_speedup_host_vs_prepr": stage_ratio,
            "stages": stages,
        }
        log(
            f"rules bench {n_rules}: prepr {medians['prepr']:,.0f} "
            f"referee {medians['referee']:,.0f} "
            f"host {medians['host']:,.0f} dev {medians['dev']:,.0f} "
            f"msg/s (host "
            f"{results[key]['speedup_host_vs_prepr']}x vs pre-PR, "
            f"{results[key]['speedup_host_vs_referee']}x vs referee)"
        )
    if write_json:
        out = {
            "pr": 12,
            "metric": "rules_match_msgs_per_s",
            "methodology": (
                "Interleaved A/B, {it} iterations each, same box "
                "(bench.py run_rules_bench): one broker per path, N "
                "lowerable rules over 16 topic groups (each 64-msg "
                "publish window matches ~N/16 rules; predicates mix "
                "numeric/string/IN/presence shapes at ~2-3% pass "
                "rate), no subscribers, host topic matching.  "
                "'prepr' = the pre-PR apply_batch verbatim (full "
                "build_env per message, one Python pass + per-rule "
                "PredicateProgram extraction per rule — the "
                "acceptance baseline); 'referee' = the per-pair "
                "interpreter oracle the property suite pins "
                "bit-identical (it already benefits from this PR's "
                "lazy envs); 'host' = numpy rules x window matrix "
                "over shared window columns (matched-row slice); "
                "'dev' = fused rules_eval_batch JAX kernel (this box "
                "is CPU-only: the dev row rides CPU XLA; ratios, not "
                "absolutes, carry the signal).  Medians reported.  "
                "Stage attribution: profiler 'rules' lap with "
                "rules_extract/rules_eval sub-stages."
            ).format(it=iters),
            **results,
        }
        write_result("BENCH_r10.json", out)
    return results


def run_rule_egress_bench(log, iters=None, write_json=True):
    """Rule-engine OUTPUT half A/B (BENCH_r16, the PR 20 tentpole):
    1k registered rules x 64-msg publish windows through the REAL
    end-to-end action pipeline — SELECT materialization, payload
    templating, buffered sink worker, and an actual TCP round-trip to
    an in-process loopback sink server per delivery:

      * ``scalar``  — select_force="scalar" (the per-row interpreter
        referee) + a max_batch=1 sink worker: one eval_select + one
        template render + ONE sink round-trip per action row (the
        pre-PR shape);
      * ``batched`` — select_force="batched" + micro-batching worker
        + ``on_query_batch``: one `materialize_rows` pass per (rule,
        window), one `render_rows` per action, ONE sink round-trip
        per flushed micro-batch.

    Both sides run the SAME WHERE matrix (the PR 12 half stays on) so
    the ratio isolates the output half.  An iteration clocks publish
    -> last action ACKED by the sink server.  Interleaved iterations,
    medians."""
    import struct as _struct

    from emqx_tpu.broker.broker import Broker
    from emqx_tpu.config import BrokerConfig
    from emqx_tpu.message import Message
    from emqx_tpu.resources import Resource

    iters = iters or int(os.environ.get("BENCH_EGRESS_ITERS", 5))
    window = 64
    n_groups = 16
    n_rules = 1000
    n_windows = int(os.environ.get("BENCH_EGRESS_WINDOWS", 6))

    class TcpSink(Resource):
        """Length-framed loopback sink: each frame carries N
        newline-joined records, the server acks with the count — so
        every ``on_query`` is one real RTT and every
        ``on_query_batch`` amortizes the window into one."""

        max_batch = 1

        def __init__(self, port: int) -> None:
            self.port = port
            self._r = self._w = None

        async def on_start(self) -> None:
            self._r, self._w = await asyncio.open_connection(
                "127.0.0.1", self.port
            )

        async def on_stop(self) -> None:
            if self._w is not None:
                self._w.close()

        async def _send(self, records) -> int:
            body = b"\n".join(
                r.encode() if isinstance(r, str) else r
                for r in records
            )
            self._w.write(_struct.pack(">I", len(body)) + body)
            await self._w.drain()
            hdr = await self._r.readexactly(4)
            return _struct.unpack(">I", hdr)[0]

        async def on_query(self, query) -> None:
            await self._send([query])

        async def on_query_batch(self, queries) -> int:
            return await self._send(queries)

    _TMPL = (
        '{"t":"${topic}","v":${v},"v2":${v2},"s":"${s}"}'
    )

    async def build(mode, port):
        cfg = BrokerConfig()
        cfg.engine.use_device = False  # match half: host trie
        b = Broker(config=cfg)
        from emqx_tpu.rules.engine import SinkAction

        sink = TcpSink(port)
        if mode == "scalar":
            b.rules.select_force = "scalar"
            sink.max_batch = 1
            worker = await b.resources.create(
                "bench_sink", sink, max_buffer=1_000_000
            )
        else:
            b.rules.select_force = "batched"
            sink.max_batch = 4096
            worker = await b.resources.create(
                "bench_sink", sink, max_buffer=1_000_000,
                batch_records=512, batch_age=0.002,
            )
        for i in range(n_rules):
            b.rules.add_rule(
                f"r{i}",
                f"SELECT payload.v AS v, topic, "
                f"payload.v * 2 + {i % 8} AS v2, payload.s AS s "
                f'FROM "bench/{i % n_groups}/#" '
                f"WHERE payload.v >= 16",
                actions=[SinkAction("bench_sink", payload=_TMPL)],
            )
        return b, worker

    def make_msgs(n_msgs):
        return [
            Message(
                topic=f"bench/{j % n_groups}/x",
                payload=(
                    '{"v": %d, "s": "%s"}' % (j % 32, "xyq"[j % 3])
                ).encode(),
                qos=0,
            )
            for j in range(n_msgs)
        ]

    async def pump(b, worker, received):
        """One timed iteration: publish every window, then wait for
        the LAST enqueued action's sink ack."""
        msgs = make_msgs(window * n_windows)
        base_matched = worker.stats["matched"]
        base_dropped = worker.stats["dropped"]
        base_rcvd = received["n"]
        t0 = time.perf_counter()
        for w0 in range(0, len(msgs), window):
            w = msgs[w0:w0 + window]
            now = time.time()
            for m in w:
                m.timestamp = now
            b.publish_many(w)
            # yield so the drain loop overlaps with publish (the
            # broker's event loop does this for free)
            await asyncio.sleep(0)
        expect = (
            worker.stats["matched"] - base_matched
            - (worker.stats["dropped"] - base_dropped)
        )
        while received["n"] - base_rcvd < expect:
            await asyncio.sleep(0.0005)
        dt = time.perf_counter() - t0
        return expect / dt

    async def main():
        received = {"n": 0}

        async def handle(reader, writer):
            try:
                while True:
                    hdr = await reader.readexactly(4)
                    (ln,) = _struct.unpack(">I", hdr)
                    body = await reader.readexactly(ln)
                    cnt = body.count(b"\n") + 1 if body else 0
                    received["n"] += cnt
                    writer.write(_struct.pack(">I", cnt))
                    await writer.drain()
            except (
                asyncio.IncompleteReadError, ConnectionResetError
            ):
                pass

        server = await asyncio.start_server(
            handle, "127.0.0.1", 0
        )
        port = server.sockets[0].getsockname()[1]
        sides = {}
        for mode in ("scalar", "batched"):
            sides[mode] = await build(mode, port)
        runs = {m: [] for m in sides}
        # warm both sides off-clock (imports, JIT, template cache)
        for mode, (b, worker) in sides.items():
            await pump(b, worker, received)
        for _ in range(iters):
            for mode, (b, worker) in sides.items():
                runs[mode].append(await pump(b, worker, received))
        stats = {}
        for mode, (b, worker) in sides.items():
            snap = worker.batch_hist.snapshot()
            stats[mode] = {
                "engine": {
                    k: v for k, v in b.rules.stats().items()
                    if isinstance(v, (int, float)) and v is not None
                },
                "sink": {
                    **{
                        k: v for k, v in worker.stats.items()
                        if isinstance(v, (int, float))
                    },
                    "batch_p50": round(snap.percentile(50), 1),
                    "batch_p99": round(snap.percentile(99), 1),
                },
            }
            await b.resources.stop_all()
        server.close()
        await server.wait_closed()
        return runs, stats

    runs, stats = asyncio.run(main())

    def med(xs):
        return sorted(xs)[len(xs) // 2]

    medians = {m: round(med(rs), 1) for m, rs in runs.items()}
    speedup = round(medians["batched"] / medians["scalar"], 2)
    results = {
        "runs": {m: [round(r, 1) for r in rs]
                 for m, rs in runs.items()},
        "medians_actions_per_s": medians,
        "speedup_batched_vs_scalar": speedup,
        "stages": stats,
    }
    log(
        f"rule egress bench {n_rules} rules: "
        f"scalar {medians['scalar']:,.0f} "
        f"batched {medians['batched']:,.0f} actions/s "
        f"({speedup}x)"
    )
    if write_json:
        out = {
            "pr": 20,
            "metric": "rule_action_throughput_actions_per_s",
            "methodology": (
                "Interleaved A/B, {it} iterations each, same box "
                "(bench.py run_rule_egress_bench): 1k lowerable "
                "SELECT rules over 16 topic groups (each 64-msg "
                "window matches ~62 rules, WHERE pass rate 1/2), "
                "every action a templated-payload sink delivery to "
                "an in-process loopback TCP server that acks each "
                "frame (a REAL per-delivery round-trip).  'scalar' "
                "= per-row eval_select + per-record sink RTT "
                "(max_batch=1, the pre-PR shape); 'batched' = "
                "windowed SELECT lowering (materialize_rows + "
                "render_rows) + micro-batched worker flushes "
                "(batch_records=512, batch_age=2ms) + one RTT per "
                "flushed batch.  Both sides run the same WHERE "
                "matrix; an iteration clocks publish -> last action "
                "ACK.  Medians reported."
            ).format(it=iters),
            "rules_1000": results,
        }
        write_result("BENCH_r16.json", out)
    return results


def run_overload_bench(log, iters=None, write_json=True):
    """Overload-protection A/B (BENCH_r11): the PR 13 acceptance
    counterfactual.  Two halves:

    * **steady state** — fanout-256 QoS1 windows with the olp ladder
      ENABLED AT LEVEL 0 vs disabled (disabled == pre-PR behavior;
      the byte-identity is property-tested), paired interleaved —
      the "overhead within noise" criterion;
    * **flood + slow-subscriber storm** — real sockets: QoS0
      flooders at well over dispatch capacity, a slow subscriber
      that stops reading, a steady QoS1 publisher and a PINGREQ
      control plane, run with OLP ON vs OFF (interleaved).  Reports
      live QoS1 publish→PUBACK p50/p99, control-ping p99, peak RSS
      delta, shed counters, max ladder level, recovery time back to
      level 0, and asserts ZERO acked-QoS1 loss in every run.
    """
    import asyncio
    import statistics

    from emqx_tpu.broker.broker import Broker
    from emqx_tpu.broker.channel import Channel
    from emqx_tpu.broker.listener import BrokerServer
    from emqx_tpu.broker.session import SubOpts
    from emqx_tpu.codec import mqtt as C
    from emqx_tpu.config import BrokerConfig, ListenerConfig
    from emqx_tpu.message import Message
    from emqx_tpu.sysmon import _rss_bytes

    iters = int(
        os.environ.get("BENCH_OVERLOAD_ITERS", iters or 3)
    )
    flood_s = float(os.environ.get("BENCH_OVERLOAD_FLOOD_S", 4.0))
    out = {}

    # ---------------------------------------- steady-state fanout A/B

    def fanout_once(olp_on):
        cfg = BrokerConfig()
        cfg.engine.use_device = False
        cfg.olp.enable = olp_on
        b = Broker(config=cfg)
        sink = [0]

        def send(pkts):
            sink[0] += sum(
                len(C.serialize(p, C.MQTT_V5)) for p in pkts
            )

        flt = "fan/olp"
        for i in range(256):
            ch = Channel(b, send=send, close=lambda r: None)
            cid = f"o{i}"
            session, _ = b.cm.open_session(
                True, cid, ch, max_inflight=0
            )
            session.subscribe(flt, SubOpts(qos=1))
            b.subscribe(cid, flt, SubOpts(qos=1))
        n = 500
        msgs = [Message(topic=flt, payload=b"x" * 64, qos=1)
                for _ in range(n)]
        b.publish_many(msgs[:64])  # warm
        t0 = time.perf_counter()
        for w0 in range(64, n, 64):
            w = msgs[w0:w0 + 64]
            now = time.time()
            for m in w:
                m.timestamp = now
            b.publish_many(w)
        return (n - 64) / (time.perf_counter() - t0)

    on_rates, off_rates = [], []
    for _ in range(5):  # paired interleaved
        off_rates.append(fanout_once(False))
        on_rates.append(fanout_once(True))
    off_med = statistics.median(off_rates)
    on_med = statistics.median(on_rates)
    out["steady_fanout256_qos1_olp_off_msgs_per_s"] = off_med
    out["steady_fanout256_qos1_olp_on_msgs_per_s"] = on_med
    out["steady_overhead_ratio"] = on_med / off_med
    log(
        f"overload steady-state fanout-256 qos1: olp-off "
        f"{off_med:,.0f} msg/s vs olp-on(level 0) {on_med:,.0f} "
        f"({on_med / off_med:.3f}x — must be within noise)"
    )

    # ------------------------------------------- flood counterfactual

    async def flood_run(olp_on):
        cfg = BrokerConfig()
        cfg.listeners = [ListenerConfig(port=0)]
        cfg.engine.batch_max = 128
        cfg.olp.enable = olp_on
        cfg.olp.sample_interval = 0.05
        cfg.olp.min_hold = 0.3
        cfg.olp.batcher_fill = [0.3, 0.6, 50.0]
        # pin the machine-state signals inert: the flood signal
        # (batcher fill) is the one this scenario exercises
        cfg.olp.loop_lag_ms = [1e6, 1e6, 1e6]
        cfg.olp.e2e_p99_ms = [1e6, 1e6, 1e6]
        cfg.olp.mqueue_backlog = [1e9, 1e9, 1e9]
        cfg.olp.sysmem = [0.999, 0.9995, 0.9999]
        cfg.olp.procmem = [0.97, 0.98, 0.99]
        cfg.olp.cpu = [1e6, 1e6, 1e6]
        srv = BrokerServer(cfg)
        await srv.start()
        broker = srv.broker
        port = srv.listeners[0].port
        loop = asyncio.get_running_loop()
        rss0 = _rss_bytes()
        peak_rss = rss0
        max_level = 0
        stop = asyncio.Event()

        async def sampler():
            nonlocal peak_rss, max_level
            while not stop.is_set():
                broker.olp.tick(time.time())
                max_level = max(max_level, broker.olp.level)
                peak_rss = max(peak_rss, _rss_bytes())
                await asyncio.sleep(0.02)

        async def conn(cid):
            r, w = await asyncio.open_connection("127.0.0.1", port)
            w.write(C.serialize(
                C.Connect(client_id=cid, proto_ver=C.MQTT_V5),
                C.MQTT_V5,
            ))
            await w.drain()
            p = C.StreamParser(version=C.MQTT_V5)
            while True:
                data = await r.read(1 << 16)
                assert data
                if any(pk.type == C.CONNACK for pk in p.feed(data)):
                    return r, w, p

        sam = loop.create_task(sampler())
        # live subscriber: qos1 live traffic + the qos0 flood
        sr, sw, sp = await conn("live_sub")
        sw.write(C.serialize(C.Subscribe(
            packet_id=1,
            subscriptions=[C.Subscription("live/#", qos=1),
                           C.Subscription("flood/#", qos=0)],
        ), C.MQTT_V5))
        await sw.drain()
        got = set()
        flood_got = [0]
        done = asyncio.Event()

        async def sub_loop():
            while not done.is_set():
                data = await sr.read(1 << 16)
                if not data:
                    return
                acks = []
                for pk in sp.feed(data):
                    if pk.type != C.PUBLISH:
                        continue
                    if pk.topic.startswith("live/"):
                        got.add(bytes(pk.payload))
                        if pk.qos:
                            acks.append(C.serialize(
                                C.Puback(packet_id=pk.packet_id),
                                C.MQTT_V5,
                            ))
                    else:
                        flood_got[0] += 1
                if acks:
                    sw.write(b"".join(acks))

        sub_task = loop.create_task(sub_loop())
        # slow-subscriber storm: subscribe the flood, then stop reading
        slow_ws = []
        for i in range(2):
            _zr, zw, _zp = await conn(f"slow{i}")
            zw.write(C.serialize(C.Subscribe(
                packet_id=1,
                subscriptions=[C.Subscription("flood/#", qos=0)],
            ), C.MQTT_V5))
            await zw.drain()
            slow_ws.append(zw)
        flood_on = True
        flood_sent = [0]

        async def flooder(i):
            _r, w, _p = await conn(f"flood{i}")
            payload = b"f" * 2048
            k = 0
            while flood_on:
                burst = b"".join(
                    C.serialize(C.Publish(
                        topic=f"flood/{i}/{k + j}", qos=0,
                        payload=payload,
                    ), C.MQTT_V5)
                    for j in range(64)
                )
                k += 64
                flood_sent[0] += 64
                w.write(burst)
                try:
                    await asyncio.wait_for(w.drain(), 1.0)
                except asyncio.TimeoutError:
                    await asyncio.sleep(0.05)
            w.close()

        flooders = [loop.create_task(flooder(i)) for i in range(3)]
        # steady qos1 publisher + control pings
        pr, pw, pp = await conn("steady")
        cr, cw, cp = await conn("control")
        ack_lat = []
        ping_lat = []
        pending = {}
        acked = set()

        async def pub_reader():
            while not done.is_set():
                data = await pr.read(1 << 14)
                if not data:
                    return
                for pk in pp.feed(data):
                    if pk.type == C.PUBACK:
                        t0 = pending.pop(pk.packet_id, None)
                        if t0 is not None:
                            ack_lat.append(
                                (time.perf_counter() - t0) * 1e3
                            )
                        acked.add(pk.packet_id)

        pub_rd = loop.create_task(pub_reader())
        sent = []
        t_end = time.time() + flood_s
        seq = 0
        while time.time() < t_end:
            seq += 1
            pid = (seq % 60000) + 1
            pending[pid] = time.perf_counter()
            sent.append(seq)
            pw.write(C.serialize(C.Publish(
                topic="live/x", qos=1, packet_id=pid,
                payload=b"s%d" % seq,
            ), C.MQTT_V5))
            await pw.drain()
            t0 = time.perf_counter()
            cw.write(C.serialize(C.Pingreq(), C.MQTT_V5))
            await cw.drain()
            try:
                data = await asyncio.wait_for(cr.read(1 << 10), 10.0)
                if any(pk.type == C.PINGRESP for pk in cp.feed(data)):
                    ping_lat.append(
                        (time.perf_counter() - t0) * 1e3
                    )
            except asyncio.TimeoutError:
                ping_lat.append(10_000.0)
            await asyncio.sleep(0.05)
        flood_on = False
        await asyncio.gather(*flooders, return_exceptions=True)
        # drain: every acked QoS1 must arrive (zero-loss assertion)
        want = {b"s%d" % s for s in sent}
        deadline = time.time() + 15.0
        while time.time() < deadline and not want <= got:
            await asyncio.sleep(0.1)
        lost = len(want - got)
        assert lost == 0, f"acked-QoS1 loss with olp_on={olp_on}"
        recovery_s = None
        if olp_on:
            t0 = time.time()
            while time.time() - t0 < 15.0 and broker.olp.level:
                await asyncio.sleep(0.05)
            recovery_s = round(time.time() - t0, 2)
        m = broker.metrics
        shed_total = (
            m.val("delivery.dropped.olp_shed")
            + m.val("messages.dropped.olp_shed")
            + m.val("delivery.dropped.out_buffer")
        )
        res = {
            "publish_ack_p50_ms": statistics.median(ack_lat or [0]),
            "publish_ack_p99_ms": sorted(ack_lat or [0])[
                max(0, int(len(ack_lat) * 0.99) - 1)
            ],
            "ping_p99_ms": sorted(ping_lat or [0])[
                max(0, int(len(ping_lat) * 0.99) - 1)
            ],
            "peak_rss_delta_mb": round(
                (peak_rss - rss0) / (1 << 20), 1
            ),
            "max_level": max_level,
            "recovery_s": recovery_s,
            "qos1_sent": len(sent),
            "qos1_lost": lost,
            "flood_published": flood_sent[0],
            "flood_delivered_to_live_sub": flood_got[0],
            "shed_total": shed_total,
        }
        done.set()
        stop.set()
        for w in (sw, pw, cw, *slow_ws):
            w.close()
        sub_task.cancel()
        pub_rd.cancel()
        await asyncio.gather(
            sub_task, pub_rd, sam, return_exceptions=True
        )
        await srv.stop()
        return res

    runs = {"olp_on": [], "olp_off": []}
    for i in range(iters):
        # interleaved A/B, off first (the counterfactual baseline)
        runs["olp_off"].append(asyncio.run(flood_run(False)))
        runs["olp_on"].append(asyncio.run(flood_run(True)))

    def med(mode, key):
        vals = [r[key] for r in runs[mode] if r[key] is not None]
        return statistics.median(vals) if vals else None

    for mode in ("olp_off", "olp_on"):
        out[mode] = {
            k: med(mode, k)
            for k in ("publish_ack_p50_ms", "publish_ack_p99_ms",
                      "ping_p99_ms", "peak_rss_delta_mb",
                      "max_level", "recovery_s", "qos1_lost",
                      "flood_delivered_to_live_sub", "shed_total")
        }
        out[mode]["runs"] = runs[mode]
        log(
            f"overload flood [{mode}]: publish-ack p99 "
            f"{out[mode]['publish_ack_p99_ms']:.1f} ms, ping p99 "
            f"{out[mode]['ping_p99_ms']:.1f} ms, peak RSS delta "
            f"{out[mode]['peak_rss_delta_mb']:.1f} MB, max level "
            f"{out[mode]['max_level']}, shed {out[mode]['shed_total']}"
            + (f", recovery {out[mode]['recovery_s']}s"
               if out[mode]["recovery_s"] is not None else "")
        )
    out["note"] = (
        "flood: 3 QoS0 flooder connections (2 KiB payloads, 64-msg "
        "bursts) + 2 slow subscribers that stop reading + a steady "
        "QoS1 publisher and a PINGREQ control plane, for "
        f"{flood_s:.0f}s per run, interleaved OFF/ON x{iters}, "
        "medians; batcher batch_max=128 so the batcher-fill signal "
        "drives the ladder (L1@0.3, L2@0.6).  Zero acked-QoS1 loss "
        "asserted in EVERY run.  olp_on must keep ping/publish p99 "
        "bounded via L2 QoS0-delivery shedding and step back to "
        "level 0 after the flood (recovery_s); olp_off is the "
        "counterfactual the ladder prevents.  Steady-state: "
        "fanout-256 QoS1 with olp enabled at level 0 vs disabled "
        "(disabled == pre-PR dispatch byte-for-byte), paired "
        "interleaved x5."
    )
    if write_json:
        write_result("BENCH_r11.json", out)
    return out


def run_flightrec_bench(log, iters=None, write_json=True):
    """Flight-recorder overhead A/B (BENCH_r15, the flight-recorder
    tentpole's acceptance criterion): fanout-256 QoS1 windows with the
    always-on recorder ARMED (one ring append per committed window via
    Profiler.commit, plus a tick — SLO delta check, samplers — inside
    the timed region) vs disabled (``flight.enable=false``: the
    recorder object exists but ``armed`` is False and the profiler
    hook is None — the pre-PR dispatch byte-for-byte, which the
    property suite pins bit-identical).  Paired interleaved on one
    box; medians.  The criterion: armed-vs-off median throughput
    within 2%."""
    import statistics

    from emqx_tpu.broker.broker import Broker
    from emqx_tpu.broker.channel import Channel
    from emqx_tpu.broker.session import SubOpts
    from emqx_tpu.codec import mqtt as C
    from emqx_tpu.config import BrokerConfig
    from emqx_tpu.message import Message

    iters = int(os.environ.get("BENCH_FLIGHT_ITERS", iters or 5))

    def fanout_once(armed):
        cfg = BrokerConfig()
        cfg.engine.use_device = False
        cfg.flight.enable = armed
        b = Broker(config=cfg)
        sink = [0]

        def send(pkts):
            sink[0] += sum(
                len(C.serialize(p, C.MQTT_V5)) for p in pkts
            )

        flt = "fan/flight"
        for i in range(256):
            ch = Channel(b, send=send, close=lambda r: None)
            cid = f"f{i}"
            session, _ = b.cm.open_session(
                True, cid, ch, max_inflight=0
            )
            session.subscribe(flt, SubOpts(qos=1))
            b.subscribe(cid, flt, SubOpts(qos=1))
        n = 500
        msgs = [Message(topic=flt, payload=b"x" * 64, qos=1)
                for _ in range(n)]
        b.publish_many(msgs[:64])  # warm
        t0 = time.perf_counter()
        for w0 in range(64, n, 64):
            w = msgs[w0:w0 + 64]
            now = time.time()
            for m in w:
                m.timestamp = now
            b.publish_many(w)
        # the recorder's 1 Hz housekeeping, charged to the armed side
        # (production runs it from the broker tick)
        b.flight.tick(profiler=b.profiler)
        dt = time.perf_counter() - t0
        b.flight.stop()
        return (n - 64) / dt

    on_rates, off_rates = [], []
    for _ in range(iters):  # paired interleaved
        off_rates.append(fanout_once(False))
        on_rates.append(fanout_once(True))
    off_med = statistics.median(off_rates)
    on_med = statistics.median(on_rates)
    ratio = on_med / off_med
    results = {
        "fanout256_qos1_flight_off_msgs_per_s": off_med,
        "fanout256_qos1_flight_on_msgs_per_s": on_med,
        "armed_over_off_ratio": ratio,
        "within_2pct": bool(ratio >= 0.98),
        "iters": iters,
    }
    log(
        f"flightrec fanout-256 qos1: recorder-off {off_med:,.0f} "
        f"msg/s vs armed {on_med:,.0f} ({ratio:.3f}x — criterion "
        f">= 0.98)"
    )
    if write_json:
        out = {
            "schema": "flight-recorder overhead A/B",
            "note": (
                "Interleaved A/B, {it} iteration pairs, same box "
                "(bench.py run_flightrec_bench): fanout-256 QoS1, "
                "500 msgs in 64-msg windows per iteration, fresh "
                "broker per run.  'armed' = always-on flight "
                "recorder (ring append per committed window + one "
                "tick with SLO delta check inside the timed "
                "region); 'off' = flight.enable=false (the pre-PR "
                "dispatch — the property suite pins the armed wire "
                "bit-identical to it).  Medians; acceptance is "
                "armed/off >= 0.98."
            ).format(it=iters),
            **results,
        }
        write_result("BENCH_r15.json", out)
    return results


def run_broker_bench(log, mode="auto"):
    """End-to-end socket benchmark (BASELINE config 1 shape, the
    emqtt_bench workload): N publishers / M wildcard subscribers over
    real TCP + the full codec → channel → batcher → match → dispatch
    path, in-process, against an engine PRELOADED with
    BENCH_BROKER_BG_SUBS background wildcard subscriptions (default
    1M) so the match step does production-scale work.

    ``mode``: "host" pins use_device=False (the reference-equivalent
    CPU trie per window); "auto" is the SHIPPING default (per-window
    adaptive host/device policy); "device" pins every window through
    the device, which documents the device round-trip as a latency
    floor.  Reports routed msg/s
    and delivery latency percentiles (publish write → subscriber read,
    same clock)."""
    import asyncio
    import struct

    import numpy as np

    from emqx_tpu.broker.listener import BrokerServer
    from emqx_tpu.codec import mqtt as C
    from emqx_tpu.config import BrokerConfig, ListenerConfig

    n_subs = int(os.environ.get("BENCH_BROKER_SUBS", 100))
    n_pubs = int(os.environ.get("BENCH_BROKER_PUBS", 100))
    n_msgs = int(os.environ.get("BENCH_BROKER_MSGS", 300))
    n_bg = int(os.environ.get("BENCH_BROKER_BG_SUBS", 1_000_000))
    inflight = int(os.environ.get("BENCH_BROKER_INFLIGHT", 256))
    device = mode == "device"
    if device:
        # the pinned-device variant pays one host↔device round-trip
        # per window; fewer messages keep it quick
        n_msgs = int(os.environ.get("BENCH_BROKER_MSGS_DEVICE", 50))
    total = n_pubs * n_msgs
    lat: list = []

    async def bench():
        cfg = BrokerConfig()
        cfg.listeners = [ListenerConfig(port=0)]
        cfg.engine.batch_window_ms = float(
            os.environ.get("BENCH_BROKER_WINDOW_MS", 1.0)
        )
        cfg.engine.use_device = {
            "host": False, "auto": None, "device": True
        }[mode]
        if device and n_bg == 0:
            # force even a tiny live set onto the device automaton
            cfg.engine.rebuild_threshold = min(n_subs, 64)
        srv = BrokerServer(cfg)
        await srv.start()

        if n_bg:
            # background wildcard set: the fleet-telemetry families at
            # scale (distinct fids over shared patterns — the standalone
            # bench's fan-out shape) + per-live-sub matching filters so
            # every bench topic fans out ~9x in the MATCH step.  fids
            # are ints: no subscriber sessions, so dispatch skips them
            # after lookup — the measured cost is routing, as intended.
            t_bg = time.perf_counter()
            bg_filters, _pops = make_filters(n_bg, 8)

            def preload():
                eng = srv.broker.router.engine
                for fid, ws in bg_filters:
                    eng._wild.insert("/".join(ws), 1_000_000_000 + fid)
                    eng._by_fid[1_000_000_000 + fid] = "/".join(ws)
                for i in range(n_subs):
                    for k in range(8):
                        flt = f"bench/{i}/+"
                        eng._wild.insert(flt, 2_000_000_000 + i * 8 + k)
                        eng._by_fid[2_000_000_000 + i * 8 + k] = flt
                if mode != "host":
                    eng.rebuild()
                    eng.warmup(4096)

            await asyncio.get_running_loop().run_in_executor(
                None, preload
            )
            log(
                f"preloaded {n_bg + n_subs * 8} background wildcard "
                f"subs in {time.perf_counter() - t_bg:.1f}s (mode={mode})"
            )
        port = srv.listeners[0].port
        loop = asyncio.get_running_loop()
        received = 0
        all_done = loop.create_future()
        sub_ready = [asyncio.Event() for _ in range(n_subs)]

        async def open_conn(cid):
            r, w = await asyncio.open_connection("127.0.0.1", port)
            w.write(
                C.serialize(
                    C.Connect(client_id=cid, proto_ver=C.MQTT_V5), C.MQTT_V5
                )
            )
            await w.drain()
            p = C.StreamParser(version=C.MQTT_V5)
            while True:
                data = await r.read(1 << 16)
                assert data, "connection closed during CONNECT"
                pkts = list(p.feed(data))
                if pkts:
                    assert pkts[0].type == C.CONNACK
                    break
            return r, w, p

        async def subscriber(i):
            nonlocal received
            r, w, p = await open_conn(f"bs{i}")
            w.write(
                C.serialize(
                    C.Subscribe(
                        packet_id=1,
                        subscriptions=[
                            C.Subscription(
                                topic_filter=f"bench/{i}/#", qos=0
                            )
                        ],
                    ),
                    C.MQTT_V5,
                )
            )
            await w.drain()
            while True:
                data = await r.read(1 << 16)
                if not data:
                    return
                for pkt in p.feed(data):
                    if pkt.type == C.SUBACK:
                        sub_ready[i].set()
                    elif pkt.type == C.PUBLISH:
                        lat.append(
                            loop.time()
                            - struct.unpack_from("d", pkt.payload)[0]
                        )
                        received += 1
                        if received >= total and not all_done.done():
                            all_done.set_result(None)

        async def publisher(j):
            r, w, p = await open_conn(f"bp{j}")
            acked = 0
            ack_evt = asyncio.Event()

            async def ack_reader():
                nonlocal acked
                while acked < n_msgs:
                    data = await r.read(1 << 16)
                    if not data:
                        return
                    for pkt in p.feed(data):
                        if pkt.type == C.PUBACK:
                            acked += 1
                            ack_evt.set()

            t = loop.create_task(ack_reader())
            pid = 0
            for k in range(n_msgs):
                sub_i = (j + k * 7) % n_subs
                pid = (pid % 65535) + 1
                w.write(
                    C.serialize(
                        C.Publish(
                            topic=f"bench/{sub_i}/v",
                            payload=struct.pack("d", loop.time()),
                            qos=1,
                            packet_id=pid,
                        ),
                        C.MQTT_V5,
                    )
                )
                if (k & 31) == 0:
                    await w.drain()
                while k - acked >= inflight:
                    ack_evt.clear()
                    await ack_evt.wait()
            await w.drain()
            await t
            w.close()

        probe_lat: list = []

        async def probe():
            """Low-rate probe: delivery latency under load without the
            queueing delay a saturating publisher measures (its own
            number is just backlog depth)."""
            r, w, p = await open_conn("bprobe")
            w.write(
                C.serialize(
                    C.Subscribe(
                        packet_id=1,
                        subscriptions=[
                            C.Subscription(topic_filter="probe/#", qos=0)
                        ],
                    ),
                    C.MQTT_V5,
                )
            )
            await w.drain()

            async def reader():
                while True:
                    data = await r.read(1 << 16)
                    if not data:
                        return
                    for pkt in p.feed(data):
                        if pkt.type == C.PUBLISH:
                            probe_lat.append(
                                loop.time()
                                - struct.unpack_from("d", pkt.payload)[0]
                            )

            rt = loop.create_task(reader())
            try:
                while True:
                    w.write(
                        C.serialize(
                            C.Publish(
                                topic="probe/t",
                                payload=struct.pack("d", loop.time()),
                                qos=0,
                            ),
                            C.MQTT_V5,
                        )
                    )
                    await w.drain()
                    await asyncio.sleep(0.005)
            except asyncio.CancelledError:
                rt.cancel()
                raise

        sub_tasks = [loop.create_task(subscriber(i)) for i in range(n_subs)]
        await asyncio.gather(*(e.wait() for e in sub_ready))
        if device and n_bg == 0:
            t_warm = time.perf_counter()

            def build_and_warm():
                # the threshold crossing kicked a BACKGROUND rebuild;
                # force a synchronous one (joins the builder) so the
                # automaton exists before warming the batch buckets
                eng = srv.broker.router.engine
                eng.rebuild()
                return eng.warmup(4096)

            warmed = await loop.run_in_executor(None, build_and_warm)
            log(
                f"warmed {warmed} kernel batch buckets in "
                f"{time.perf_counter() - t_warm:.1f}s"
            )
        probe_task = loop.create_task(probe())
        t0 = time.perf_counter()
        await asyncio.gather(*(publisher(j) for j in range(n_pubs)))
        await asyncio.wait_for(all_done, 120)
        elapsed = time.perf_counter() - t0
        loaded_probe = list(probe_lat)
        # quiet phase: pipeline latency with the backlog drained — the
        # number comparable to the reference's sub-ms delivery claim
        probe_lat.clear()
        await asyncio.sleep(1.5)
        quiet_probe = list(probe_lat)
        probe_task.cancel()
        for t in sub_tasks:
            t.cancel()
        stats = srv.broker.router.engine.index_stats()
        stages = {
            name: {
                "count": snap.count,
                "p50_us": round(snap.percentile(50), 1),
                "p99_us": round(snap.percentile(99), 1),
            }
            for name, snap in srv.broker.profiler.snapshots().items()
            if snap.count
        }
        await srv.stop()
        return elapsed, loaded_probe, quiet_probe, stats, stages

    (
        elapsed, loaded_probe, quiet_probe, eng_stats, window_stages
    ) = asyncio.run(bench())
    lat_ms = np.array(lat) * 1e3
    quiet_ms = np.array(quiet_probe or [0.0]) * 1e3
    loaded_ms = np.array(loaded_probe or [0.0]) * 1e3
    out = {
        "mode": mode,
        "msgs_per_s": total / elapsed,
        "delivery_p50_ms": float(np.percentile(quiet_ms, 50)),
        "delivery_p99_ms": float(np.percentile(quiet_ms, 99)),
        "loaded_probe_p50_ms": float(np.percentile(loaded_ms, 50)),
        "loaded_probe_p99_ms": float(np.percentile(loaded_ms, 99)),
        "saturated_sojourn_p50_ms": float(np.percentile(lat_ms, 50)),
        "pubs": n_pubs,
        "subs": n_subs,
        "bg_subs": n_bg,
        "total_msgs": total,
        "engine_stats": eng_stats,
        # per-stage window-pipeline percentiles from the profiler:
        # WHERE the window milliseconds live, not just the rate
        "window_stages_us": window_stages,
        "used_device_path": eng_stats.get("auto_dev_windows", 0) > 0
        or (mode == "device" and eng_stats.get("base", 0) > 0),
        "note": "in-process harness: clients share the broker's "
        "event loop; QoS1 publishers, 256 inflight, wildcard subs + "
        "bg_subs preloaded background wildcard set, full codec both "
        "directions; delivery p50/p99 from a 200 Hz probe after the "
        "flood drains (pipeline latency); loaded_probe = same probe "
        "during the flood (includes bounded queueing); "
        "saturated_sojourn = the flood's own messages (backlog depth, "
        "not pipeline).  mode=device pins every window through the "
        "device: its latency floor is the device round-trip "
        "(dispatch_rtt_ms).  mode=auto is the shipping "
        "default: per-window measured-cost policy (host for shallow "
        "windows, device offload under congestion).",
    }
    log(
        f"broker e2e[{mode}]: {out['msgs_per_s']:,.0f} msg/s routed "
        f"({n_pubs}p/{n_subs}s+{n_bg}bg, qos1), delivery p50 "
        f"{out['delivery_p50_ms']:.1f} ms p99 "
        f"{out['delivery_p99_ms']:.1f} ms "
        f"(loaded probe p99 {out['loaded_probe_p99_ms']:.0f} ms, "
        f"saturated sojourn p50 "
        f"{out['saturated_sojourn_p50_ms']:.0f} ms, "
        f"auto={eng_stats.get('auto_host_windows')}h/"
        f"{eng_stats.get('auto_dev_windows')}d)"
    )
    return out


def main():
    import numpy as np

    import jax

    from emqx_tpu import topic as T
    from emqx_tpu.ops.automaton import (build_automaton, expand_codes_dedup,
                                        expand_codes_flat)
    from emqx_tpu.engine import _pad_batch
    from emqx_tpu.ops.dictionary import PAD_TOK, TokenDict, encode_topics
    from emqx_tpu.ops.match_kernel import match_batch, match_batch_compact

    from emqx_tpu.engine import enable_compile_cache

    enable_compile_cache()  # shape-class compiles persist across runs

    device = device_info()
    platform = device["platform"]
    if platform != "tpu":
        # a measurement path that finds no chip fails: a CPU run gives
        # counts and correctness, never a rate
        raise SystemExit(
            f"bench.py needs a TPU, found platform={platform!r}"
        )
    n_subs = int(os.environ.get("BENCH_SUBS", 10_000_000))
    batch = int(os.environ.get("BENCH_BATCH", 32768))
    iters = int(os.environ.get("BENCH_ITERS", 50))
    f_width = int(os.environ.get("BENCH_F", 4))
    m_cap = int(os.environ.get("BENCH_M", 16))
    depth = int(os.environ.get("BENCH_DEPTH", 8))  # batches in flight
    fanout = int(os.environ.get("BENCH_FANOUT", 8))
    n_insert = int(os.environ.get("BENCH_INSERTS", 100_000))
    max_levels = 16
    rng = np.random.default_rng(0)

    log(f"platform={platform} subs={n_subs} batch={batch} iters={iters} "
        f"fanout~{fanout}")

    t0 = time.perf_counter()
    filters, pops = make_filters(n_subs, fanout)
    gen_s = time.perf_counter() - t0

    t0 = time.perf_counter()
    tdict = TokenDict()
    aut = build_automaton(filters, tdict, max_levels=max_levels)
    build_s = time.perf_counter() - t0
    fid_arr = np.arange(n_subs, dtype=np.int64)  # position == fid here
    log(
        f"built automaton: nodes={aut.n_nodes} buckets={len(aut.fp_rows)} "
        f"salt={aut.salt} kernel_levels={aut.kernel_levels} "
        f"in {build_s:.2f}s (gen {gen_s:.2f}s)"
    )

    streams = [
        make_topics(rng, batch, pops) for _ in range(iters)
    ]

    dev = tuple(jax.device_put(a) for a in aut.device_arrays())

    # per-topic MATRIX encode cache: live publish streams are
    # Zipf-heavy, so a hot topic is one dict hit yielding a row index
    # and the batch materializes as one fancy-index gather (the
    # engine's production path uses the same scheme,
    # engine._encode_rows).  Invalidated on dictionary growth, same
    # as the engine's generation check.
    levels = aut.kernel_levels
    enc_index = {}
    enc_mat = np.full((65536, levels), PAD_TOK, np.int32)
    enc_len = np.zeros(65536, np.int32)
    enc_dol = np.zeros(65536, bool)
    enc_state = [len(tdict), 0]  # [dict generation, rows used]

    nat = tdict.native()

    def submit(topic_strings):
        """Tokenize + dispatch one batch; returns device arrays without
        blocking (JAX async dispatch keeps `depth` batches in flight so
        host<->device latency amortizes away, as the broker's pipelined
        publish path does).  Tokenize = one C-speed map() over the row
        cache + a native (GIL-released) batch encode of the misses —
        the production engine's _encode_rows scheme."""
        nonlocal enc_mat, enc_len, enc_dol
        b = len(topic_strings)
        if len(tdict) != enc_state[0]:
            enc_index.clear()
            enc_state[:] = [len(tdict), 0]
        used = enc_state[1]
        if used >= 524288:  # reset only at a batch boundary (aliasing)
            enc_index.clear()
            used = 0
        js = list(map(enc_index.get, topic_strings))
        if None in js:
            miss_rows = {}
            miss_ts = []
            for i, j in enumerate(js):
                if j is None:
                    t = topic_strings[i]
                    r = miss_rows.get(t)
                    if r is None:
                        r = miss_rows[t] = used + len(miss_ts)
                        miss_ts.append(t)
                    js[i] = r
            need = used + len(miss_ts)
            while need > len(enc_len):
                cap = len(enc_len) * 2
                m2 = np.full((cap, levels), PAD_TOK, np.int32)
                m2[: len(enc_len)] = enc_mat
                enc_mat = m2
                enc_len = np.resize(enc_len, cap)
                enc_dol = np.resize(enc_dol, cap)
            if nat is not None:
                nat.encode_topics_into(
                    miss_ts, levels, enc_mat[used:need],
                    enc_len[used:need], enc_dol[used:need],
                )
            else:
                get = tdict.get
                for k, t in enumerate(miss_ts):
                    ws = T.words(t)
                    n = min(len(ws), levels)
                    row = enc_mat[used + k]
                    row[:] = PAD_TOK
                    for j2 in range(n):
                        row[j2] = get(ws[j2])
                    enc_len[used + k] = n
                    enc_dol[used + k] = bool(ws) and ws[0].startswith("$")
            enc_index.update(miss_rows)
            used = need
        idx = np.fromiter(js, np.int64, count=b)
        enc_state[1] = used
        # dedup the window: Zipf streams repeat hot topics (~2x here),
        # and each unique topic needs only one device row + one slot in
        # the device->host code transfer (the production engine dedups
        # the same way, engine._flat_dispatch)
        uniq, inv = np.unique(idx, return_inverse=True)
        tokens, lengths, dollar = _pad_batch(
            enc_mat[uniq], enc_len[uniq], enc_dol[uniq]
        )
        # COMPACT output layout: the dense [B, m_cap] code matrix at a
        # few-percent fill is 1 MB/batch of mostly -1 on the
        # device->host link
        out = match_batch_compact(
            *dev,
            tokens,
            lengths,
            dollar,
            f_width=f_width,
            m_cap=m_cap,
            c_cap=tokens.shape[0],
        )
        # start the device->host copies immediately so transfers overlap
        # with the next batches' compute instead of serializing on the
        # round-trip at drain time
        out[0].copy_to_host_async()
        out[1].copy_to_host_async()
        out[2].copy_to_host_async()
        return out, len(uniq), inv, (tokens, lengths, dollar)

    def drain(pending):
        """Transfer the compact code form and expand to per-topic fid
        lists with vectorized host CSR — the full route-lookup result
        (`emqx_router:match_routes` per topic), fanned back from the
        deduplicated device batch to every original topic row."""
        out, n_uniq, inv, enc = pending
        flat, counts, total = out
        if int(np.asarray(total)[0]) > len(flat):
            # compact buffer clipped: dense-kernel fallback (correct at
            # any fill; the c_cap sizing makes this rare)
            codes, _, ovf = match_batch(
                *dev, *enc, f_width=f_width, m_cap=m_cap
            )
            rows, pos = expand_codes_dedup(
                aut.code_off, aut.code_idx, np.asarray(codes)[:n_uniq], inv
            )
            return rows, fid_arr[pos], np.asarray(ovf)[:n_uniq][inv]
        counts = np.asarray(counts).astype(np.int64)
        ovf_u = counts < 0
        rows, pos = expand_codes_flat(
            aut.code_off, aut.code_idx, np.asarray(flat),
            np.where(ovf_u, -counts - 1, counts), inv,
        )
        fids = fid_arr[pos]  # flat (topic_row, fid) pairs
        return rows, fids, ovf_u[:n_uniq][inv]

    # warmup / compile
    t0 = time.perf_counter()
    rows, fids, ovf = drain(submit(streams[0]))
    log(f"compile+first batch: {time.perf_counter() - t0:.2f}s; "
        f"ovf={int(ovf.sum())} mean_fanout={len(fids) / batch:.2f}")

    # (a) device-only throughput: batches pre-encoded so the clock sees
    # only dispatch + device compute (host tokenize cost is excluded
    # here and included in the full-path phase below)
    encoded = [
        encode_topics(tdict, [T.words(t) for t in s], aut.kernel_levels)
        for s in streams
    ]
    # warm the full-batch shape (the pipelined phase above runs the
    # DEDUPED batch shape, so this one may not be compiled yet)
    match_batch(*dev, *encoded[0], f_width=f_width, m_cap=m_cap)[
        1
    ].block_until_ready()
    t0 = time.perf_counter()
    outs = [
        match_batch(
            *dev, *e, f_width=f_width, m_cap=m_cap
        )
        for e in encoded
    ]
    outs[-1][1].block_until_ready()
    device_rate = batch * iters / (time.perf_counter() - t0)
    del encoded, outs
    log(f"device-only match rate: {device_rate:,.0f} topics/s")

    # (b) full path, pipelined: submit keeps `depth` batches in flight,
    # drain produces host-visible fid lists for every batch
    from collections import deque

    total_matches = 0
    ovf_total = 0
    inflight = deque()
    t_start = time.perf_counter()
    for s in streams:
        inflight.append(submit(s))
        if len(inflight) >= depth:
            rows, fids, ovf = drain(inflight.popleft())
            total_matches += len(fids)
            ovf_total += int(ovf.sum())
    while inflight:
        rows, fids, ovf = drain(inflight.popleft())
        total_matches += len(fids)
        ovf_total += int(ovf.sum())
    elapsed = time.perf_counter() - t_start

    # (c) single-batch synchronous latency (includes the host<->device
    # round-trip, reported beside it as dispatch_rtt_ms)
    lat = []
    for s in streams[: min(iters, 10)]:
        t0 = time.perf_counter()
        drain(submit(s))
        lat.append(time.perf_counter() - t0)
    lat_ms = np.array(lat) * 1e3
    p50, p99 = np.percentile(lat_ms, [50, 99])

    # measure the bare dispatch round-trip to attribute latency fairly
    tiny = jax.jit(lambda a: a + 1)
    ta = jax.device_put(np.zeros(8, np.int32))
    np.asarray(tiny(ta))
    t0 = time.perf_counter()
    for _ in range(5):
        np.asarray(tiny(ta))
    dispatch_rtt_ms = (time.perf_counter() - t0) / 5 * 1e3

    # (c2) small-window sync latency: a production publish window is
    # ~1-4k topics, not 32k; this is the per-window match latency the
    # broker's pipeline hides, reported net of the link RTT so the
    # compute+transfer cost is visible separately from the bare
    # dispatch round-trip.
    small = [s[:1024] for s in streams[: min(iters, 10)]]
    drain(submit(small[0]))  # warm the 1024 shape
    lat_small = []
    for s in small:
        t0 = time.perf_counter()
        drain(submit(s))
        lat_small.append(time.perf_counter() - t0)
    small_ms = np.array(lat_small) * 1e3
    small_p50, small_p99 = np.percentile(small_ms, [50, 99])

    # host-trie rate at full scale: the reference-equivalent per-topic
    # CPU path against the SAME 10M-sub set — the honest at-scale
    # comparison for the device's batched full path
    host_rate = 0.0
    if os.environ.get("BENCH_HOST_RATE", "1") != "0":
        from emqx_tpu.ops.trie_native import make_trie

        t0 = time.perf_counter()
        htrie = make_trie()
        for fid, ws in filters:
            htrie.insert("/".join(ws), fid, ws)
        host_build_s = time.perf_counter() - t0
        sample = [T.words(t) for t in streams[0][:20000]]
        for ws in sample[:200]:
            htrie.match_words(ws)
        t0 = time.perf_counter()
        for ws in sample:
            htrie.match_words(ws)
        host_rate = len(sample) / (time.perf_counter() - t0)
        log(
            f"host trie @ {n_subs} subs: {host_rate:,.0f} topics/s "
            f"(build {host_build_s:.1f}s)"
        )
        del htrie

    total_topics = batch * iters
    rate = total_topics / elapsed

    insert_rps, churn_p50, churn_p99 = measure_insert_rps(
        filters[: min(n_subs, 1_000_000)], n_insert, log
    )

    def sub_bench(label: str, script: str, timeout: float,
                  env=None) -> dict:
        """One tool-subprocess bench phase: runs `tools/<script>`,
        parses its one-line JSON, logs the child's stderr tail when it
        fails (a swallowed traceback made every child failure read as
        'list index out of range').  A phase that fails raises: the
        run exits non-zero rather than report without it."""
        import subprocess

        log(f"{label} (subprocess {script})...")
        out = subprocess.run(
            [sys.executable,
             os.path.join(os.path.dirname(os.path.abspath(__file__)),
                          "tools", script)],
            capture_output=True, text=True, timeout=timeout,
            env=env,
        )
        if out.returncode != 0 or not out.stdout.strip():
            raise RuntimeError(
                f"{label} failed rc={out.returncode}: "
                f"{out.stderr[-2000:]}"
            )
        stats = json.loads(out.stdout.strip().splitlines()[-1])
        log(f"{label}: {stats}")
        return stats

    sharded_stats = {}
    if os.environ.get("BENCH_SHARDED", "1") != "0":
        # the sharded engine runs on the driver's virtual 8-device CPU
        # mesh in a SUBPROCESS (this process must keep seeing the TPU)
        sharded_stats.update(sub_bench(
            "sharded mesh bench", "bench_sharded.py", 420
        ))
    if os.environ.get("BENCH_DS", "1") != "0":
        # DS layout: LTS learned-structure replay vs flat hash shards
        sharded_stats.update(sub_bench(
            "ds layout bench", "bench_ds.py", 420,
            env=dict(os.environ, JAX_PLATFORMS="cpu"),
        ))
    if os.environ.get("BENCH_CLUSTER_SHARDED", "1") != "0":
        # cluster-sharded route index: 2 OS-process nodes, the filter
        # set partitioned by rendezvous hash (~1/N each), scatter-
        # gather matching checked against the full-knowledge oracle
        sharded_stats.update(sub_bench(
            "cluster-sharded bench", "bench_cluster_sharded.py", 600,
            env=dict(os.environ, BENCH_SHARD_FILTERS=os.environ.get(
                "BENCH_SHARD_FILTERS", "1000000")),
        ))
    if os.environ.get("BENCH_MC", "1") != "0":
        # multi-core broker: worker processes + loadgen processes (the
        # whole phase lives outside this TPU-holding process)
        sharded_stats.update(sub_bench(
            "multicore broker bench", "bench_multicore.py", 540
        ))

    fanout_stats = {}
    if os.environ.get("BENCH_FANOUT_DISPATCH", "1") != "0":
        # the dispatch half of the pipeline (BENCH_r06+ tracks the
        # PR 3 tentpole): fixed fan-out sweep, encode+write counted
        fanout_stats = run_dispatch_fanout_bench(log)

    replay_stats = {}
    if os.environ.get("BENCH_REPLAY", "1") != "0":
        # mass-reconnect durable replay (BENCH_r08 tracks the resume
        # scheduler): scalar vs windowed sessions/s + storm drain
        replay_stats = run_replay_bench(log)

    durability_stats = {}
    if os.environ.get("BENCH_DURABILITY", "1") != "0":
        # fsync-mode A/B + naive per-message-fsync counterfactual +
        # cold recovery (BENCH_r12 tracks the PR 15 tentpole)
        durability_stats = run_durability_bench(log)

    ds_shard_stats = {}
    if os.environ.get("BENCH_DS_SHARD", "1") != "0":
        # sharded DS store: 1/2/4-shard fsynced append throughput,
        # restart-to-serving at 1M msgs (intact / journal-replay /
        # full rebuild), GC reclaim under live appends (BENCH_r13
        # tracks the PR 16 tentpole)
        ds_shard_stats = run_ds_shard_bench(log)

    cluster_fwd_stats = {}
    if os.environ.get("BENCH_CLUSTER_FORWARD", "1") != "0":
        # at-least-once window forwarding over tcp vs quic vs quic@1%
        # datagram loss (BENCH_r09 tracks the PR 11 tentpole)
        cluster_fwd_stats = run_cluster_forward_bench(log)

    overload_stats = {}
    if os.environ.get("BENCH_OVERLOAD", "1") != "0":
        # overload ladder on/off counterfactual + steady-state A/B
        # (BENCH_r11 tracks the PR 13 tentpole)
        overload_stats = run_overload_bench(log)

    flight_stats = {}
    if os.environ.get("BENCH_FLIGHT", "1") != "0":
        # always-on flight recorder armed vs off (BENCH_r15 tracks
        # the flight-recorder tentpole's <=2% overhead criterion)
        flight_stats = run_flightrec_bench(log)

    rules_stats = {}
    if os.environ.get("BENCH_RULES", "1") != "0":
        # rule-engine WHERE matrix vs the scalar interpreter referee
        # at 1k/10k registered rules (BENCH_r10 tracks the PR 12
        # tentpole)
        rules_stats = run_rules_bench(log)

    rule_egress_stats = {}
    if os.environ.get("BENCH_RULE_EGRESS", "1") != "0":
        # rule OUTPUT half: batched SELECT + micro-batched sink
        # egress vs the per-row scalar referee with per-record sink
        # round-trips (BENCH_r16 tracks the PR 20 tentpole)
        rule_egress_stats = run_rule_egress_bench(log)

    broker_stats = {}
    if os.environ.get("BENCH_BROKER", "1") != "0":
        # three rows at >=1M background subs: host-pinned (the
        # reference-equivalent per-window CPU trie), the SHIPPING
        # default (device on, adaptive per-window policy — must beat
        # host on throughput AND p99 or the policy has failed), and
        # device-pinned (documents the device round-trip floor)
        host = run_broker_bench(log, "host")
        broker_stats = {"broker_" + k: v for k, v in host.items()}
        auto = run_broker_bench(log, "auto")
        broker_stats.update(
            {"broker_device_" + k: v for k, v in auto.items()}
        )
        forced = run_broker_bench(log, "device")
        broker_stats.update(
            {"broker_device_forced_" + k: v for k, v in forced.items()}
        )

    details = {
        "platform": platform,
        "n_subs": n_subs,
        "batch": batch,
        "iters": iters,
        "build_s": build_s,
        "nodes": aut.n_nodes,
        "salt": aut.salt,
        "rate_topics_per_s": rate,
        "device_only_rate_topics_per_s": device_rate,
        "sync_batch_latency_ms_p50": float(p50),
        "sync_batch_latency_ms_p99": float(p99),
        "sync_1k_window_ms_p50": float(small_p50),
        "sync_1k_window_ms_p99": float(small_p99),
        "sync_1k_window_net_of_rtt_ms_p50": float(
            max(small_p50 - dispatch_rtt_ms, 0.0)
        ),
        "sync_1k_window_net_of_rtt_ms_p99": float(
            max(small_p99 - dispatch_rtt_ms, 0.0)
        ),
        "host_trie_rate_topics_per_s": float(host_rate),
        "dispatch_rtt_ms": float(dispatch_rtt_ms),
        "pipeline_depth": depth,
        "overflow_frac": ovf_total / total_topics,
        "mean_matches_per_topic": total_matches / total_topics,
        "insert_rps": insert_rps,
        "churn_match_p50_ms": churn_p50,
        "churn_match_p99_ms": churn_p99,
        "timing_covers": "cached tokenize (per-topic encode rows, "
        "Zipf-hit-rate dependent — matches the production engine's "
        "cache) + device match + async compact-code transfer + "
        "vectorized host CSR expand to per-topic fid lists",
        "dispatch_fanout_msgs_per_s": fanout_stats,
        "replay": replay_stats,
        "durability": durability_stats,
        "ds_shard": ds_shard_stats,
        "cluster_forward": cluster_fwd_stats,
        "rules": rules_stats,
        "rule_egress": rule_egress_stats,
        "overload": overload_stats,
        "flightrec": flight_stats,
        **sharded_stats,
        **broker_stats,
    }
    write_result("BENCH_DETAILS.json", details)
    log(json.dumps(details))

    print(
        json.dumps(
            {
                "metric": "wildcard_topic_matches_per_sec_per_chip",
                "value": round(rate, 1),
                "unit": (
                    f"topics/s full-path @ {n_subs} wildcard subs, "
                    f"fanout {total_matches / total_topics:.1f} "
                    f"({insert_rps:,.0f} inserts/s; device-only "
                    f"{device_rate:,.0f}/s; broker e2e "
                    f"{broker_stats.get('broker_msgs_per_s', 0):,.0f} "
                    f"msg/s qos1 p99 "
                    f"{broker_stats.get('broker_delivery_p99_ms', 0):.0f}"
                    f" ms)"
                ),
                "vs_baseline": round(rate / 1_000_000, 3),
                "device": device,
            }
        )
    )


if __name__ == "__main__":
    main()
