#!/usr/bin/env python3
"""Bring-up check: the served match path on the attached TPU.

    python chip_smoke.py [--subs N] [--seed S]
    python chip_smoke.py --chips 4 [--subs N] [--seed S]

The default run needs one chip and drives, in ONE process that touches
JAX, with the device pinned and its work counted:

  preflight  the platform must be ``tpu``; the six native libraries
             are built from ``native/*.cpp`` and must all load;
  engine     `MatchEngine(use_device=True)` at the shipped kernel
             widths, loaded with ``--subs`` wildcard subscriptions
             through its public API, then 4096-topic Zipf windows
             pipelined through submit/finish — every window served by
             the device and equal to the `HostTrie` referee;
  served     a `BrokerServer` built the way `listener.main()` builds
             it with ``engine.use_device = true``, the same background
             table and the rule-engine rules in place before
             `start()` — which builds, uploads and warms on its own —
             then live wildcard subscribers and publishers over
             loopback TCP from a child process that never imports
             JAX.  Every PUBACK, every delivery and every rule firing
             is checked against the scalar referees; every window is
             matched, decided and rule-evaluated on the device, and no
             rules program compiles inside the traffic.

``--chips 4`` runs only the sharded engine on a four-device mesh and
what it is compared with (the single-device engine and the referee).

Any phase that raises or any check that fails exits non-zero.  Facts
go out one JSON object a line; the LAST line is the device line
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``.
There is no CPU branch and no smaller size: without a TPU the script
exits non-zero before doing any work.
"""

from __future__ import annotations

import argparse
import asyncio
import gc
import json
import os
import sys
import threading
import time
from collections import deque

REPO = os.path.dirname(os.path.abspath(__file__))

PLATFORM = "tpu"        # the platform every phase must run on
WINDOW_TOPICS = 4096    # topics a window (the batcher's batch_max)
N_WINDOWS = 8           # steady windows in the engine phase
PIPELINE = 4            # windows in flight (engine.pipeline_windows)
N_RULES = 100           # BASELINE config 4's rule count
N_LIVE = 300            # live wildcard subscribers over TCP
LIVE_FILTERS = 4        # filters each of them subscribes to
N_PUBLISH = 4000        # QoS1 publishes from the client child
N_PUBLISHERS = 8
FANOUT = 8              # subscribers sharing each background filter


class CheckFailed(Exception):
    pass


def check(cond, what: str) -> None:
    if not cond:
        raise CheckFailed(what)


# ------------------------------------------------------------ reporting

_DEVICE: dict = {}


def say(phase: str, **facts) -> None:
    """One JSON line of facts, each carrying the platform and kind."""
    print(json.dumps({"phase": phase, **_DEVICE, **facts}), flush=True)


class CompileLog:
    """Every XLA compile request of this process, by jitted function,
    with whether the persistent cache served it (`jax.monitoring`)."""

    def __init__(self) -> None:
        import jax.monitoring as mon

        self.requests: list = []  # (fun_name, seconds, cache_hit)
        self._hit = threading.local()
        mon.register_event_listener(self._on_event)
        mon.register_event_duration_secs_listener(self._on_duration)

    def _on_event(self, event: str, **kw) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            self._hit.flag = True

    def _on_duration(self, event: str, secs: float, **kw) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            hit = getattr(self._hit, "flag", False)
            self._hit.flag = False
            self.requests.append((kw.get("fun_name", "?"), secs, hit))

    def mark(self) -> int:
        return len(self.requests)

    def since(self, mark: int) -> dict:
        """Compile requests since ``mark``: how many, how many the
        persistent cache did not have, and the seconds by function."""
        reqs = self.requests[mark:]
        by_fn: dict = {}
        for fn, secs, _ in reqs:
            by_fn[fn] = round(by_fn.get(fn, 0.0) + secs, 3)
        return {
            "compile_requests": len(reqs),
            "fresh_compiles": sum(1 for r in reqs if not r[2]),
            "compile_s": round(sum(r[1] for r in reqs), 3),
            "compile_s_by_fn": by_fn,
        }


def memory(device) -> dict:
    stats = device.memory_stats() or {}
    return {
        "bytes_in_use": stats.get("bytes_in_use"),
        "peak_bytes_in_use": stats.get("peak_bytes_in_use"),
        "bytes_limit": stats.get("bytes_limit"),
    }


# ------------------------------------------------------------ workload

def background(n_subs: int):
    """The four fleet-telemetry filter families (bench.make_filters) as
    ``(filter, fid)`` pairs, and their id populations; the publish
    streams over them are `bench.make_topics`."""
    from bench import make_filters

    filters, pops = make_filters(n_subs, FANOUT)
    return [("/".join(ws), fid) for fid, ws in filters], pops


def referee(pairs):
    from emqx_tpu.ops.trie_host import HostTrie

    ref = HostTrie()
    for flt, fid in pairs:
        ref.insert(flt, fid)
    return ref


# ------------------------------------------------------------ preflight

def preflight(need_devices: int):
    """Platform, versions, native libraries.  Returns jax.devices()."""
    import jax
    import jaxlib

    devs = jax.devices()
    check(
        devs[0].platform == PLATFORM,
        f"need platform {PLATFORM!r}, JAX found {devs[0].platform!r}",
    )
    check(
        len(devs) >= need_devices,
        f"need {need_devices} {PLATFORM} devices, JAX found {len(devs)}",
    )
    _DEVICE.update(platform=devs[0].platform, kind=devs[0].device_kind)
    # a copied tree's mtimes prove nothing: build the libraries anew
    # from the committed sources rather than trust what lies there
    from emqx_tpu.ops import nativelib

    failed = {n: why for n, why in nativelib.rebuild().items() if why}
    check(not failed, f"native rebuild failed: {failed}")
    from emqx_tpu.ds import native as dslog
    from emqx_tpu.ops import dispatchasm, sockwriter, sortutil_native
    from emqx_tpu.ops import tokdict_native, trie_native

    seams = {
        "hosttrie": trie_native, "sortutil": sortutil_native,
        "tokdict": tokdict_native, "dispatchasm": dispatchasm,
        "dslog": dslog, "sockwriter": sockwriter,
    }
    loaded = {
        name: "native" if mod.load() is not None else "python"
        for name, mod in seams.items()
    }
    check(
        all(v == "native" for v in loaded.values()),
        f"a native seam serves from its Python twin: {loaded}",
    )
    check(
        type(trie_native.make_trie()).__name__ == "NativeTrie",
        "make_trie() did not return the native trie",
    )
    from emqx_tpu import failpoints
    from emqx_tpu.engine import enable_compile_cache

    say(
        "preflight", jax=jax.__version__, jaxlib=jaxlib.__version__,
        runtime=devs[0].client.platform_version.strip().split("\n")[0],
        devices=len(devs), native=loaded,
        compile_cache=enable_compile_cache(),
        failpoints_armed=failpoints.load_env(),
    )
    return devs


# ---------------------------------------------------------------- engine

def assert_clean(eng, what: str) -> None:
    brk = eng.breaker_info()
    check(
        brk["device_errors"] == 0 and brk["trips"] == 0
        and brk["slow_windows"] == 0 and not brk["open"],
        f"{what}: device breaker saw faults: {brk}",
    )


def run_windows(eng, ref, windows, compiles: CompileLog, what: str):
    """Pipeline ``windows`` through submit/finish as the batcher does;
    every window must be served by the device and equal the referee."""
    from emqx_tpu import topic as T

    mark = compiles.mark()
    inflight: deque = deque()
    n_topics = n_ovf = matches = 0
    wall = []

    def finish() -> None:
        nonlocal n_topics, matches
        topics, pending, t0 = inflight.popleft()
        info: dict = {}
        got = eng.match_batch_finish(pending, info=info)
        wall.append(time.perf_counter() - t0)
        check(
            info.get("path") == "dev",
            f"{what}: a window was served by {info.get('path')!r}",
        )
        want = {t: ref.match(t) for t in set(topics)}
        for t, g in zip(topics, got):
            check(
                g == want[t],
                f"{what}: {t!r} matched {len(g)} fids, referee "
                f"{len(want[t])}",
            )
            matches += len(g)
        n_topics += len(topics)

    for topics in windows:
        inflight.append(
            (topics, eng.match_batch_submit(topics), time.perf_counter())
        )
        if len(inflight) >= PIPELINE:
            finish()
    while inflight:
        finish()
    steady = compiles.since(mark)
    # the share of topics the kernel flagged (frontier or match-cap
    # overflow) and the host re-matched: read off the flat device path
    for topics in windows:
        ovf = eng.match_batch_flat([T.words(t) for t in topics])[2]
        n_ovf += int(ovf.sum())
    share = n_ovf / max(n_topics, 1)
    check(
        share <= 0.5,
        f"{what}: {share:.2f} of topics overflowed to the host trie",
    )
    check(
        steady["compile_requests"] == 0,
        f"{what}: compiled inside the steady windows: {steady}",
    )
    assert_clean(eng, what)
    return {
        "windows": len(windows), "topics": n_topics,
        "all_windows_dev": True, "equal_to_referee": True,
        "mean_matches_per_topic": round(matches / max(n_topics, 1), 2),
        "overflow_share": share,
        "steady_compile_requests": steady["compile_requests"],
        "window_wall_s": [round(w, 4) for w in wall],
    }


def engine_phase(args, dev, pairs, pops, ref, compiles: CompileLog):
    import numpy as np
    from bench import make_topics

    from emqx_tpu.config import BrokerEngineConfig
    from emqx_tpu.engine import MatchEngine
    from emqx_tpu.observability import Profiler

    shipped = BrokerEngineConfig()
    eng = MatchEngine(
        max_levels=shipped.max_levels, f_width=shipped.f_width,
        m_cap=shipped.m_cap, use_device=True,
        # one build, at rebuild(): not one more at the threshold
        rebuild_threshold=1 << 62,
    )
    eng.profiler = Profiler()
    mem0 = memory(dev)
    t0 = time.perf_counter()
    eng.insert_many(pairs)
    insert_s = time.perf_counter() - t0
    t0 = time.perf_counter()
    eng.rebuild()
    build_s = time.perf_counter() - t0
    # the first window uploads the tables and compiles one small
    # program: what the allocator holds then is the resident table
    mark = compiles.mark()
    t0 = time.perf_counter()
    eng.match_batch(["vehicles/v0/sensors/temp"])
    first_s = time.perf_counter() - t0
    first = compiles.since(mark)
    gc.collect()
    mem_up = memory(dev)
    mark = compiles.mark()
    t0 = time.perf_counter()
    buckets = eng.warmup(WINDOW_TOPICS)
    warm_s = time.perf_counter() - t0
    check(buckets > 0, "warmup() found the device path off")
    uploads = [
        e for e in eng.profiler.events(256) if e["kind"] == "device_put"
    ]
    gc.collect()
    mem1 = memory(dev)
    idx = eng.index_stats()
    say(
        "engine_load", subs=len(eng), base=idx["base"],
        f_width=eng.f_width, m_cap=eng.m_cap, max_levels=eng.max_levels,
        insert_s=round(insert_s, 3), build_s=round(build_s, 3),
        # newest first: the base tables' upload in the first window,
        # before it the delta fold's that insert_many() started and
        # rebuild() discarded
        uploads=[{"bytes": e["bytes"], "s": round(e["dur_ms"] / 1e3, 3),
                  "throttled": e["throttled"]} for e in uploads],
        first_window_s=round(first_s, 3),
        first_window_compile_s=first["compile_s"],
        warmup_s=round(warm_s, 3), warmup_buckets=buckets,
        **{"warmup_" + k: v for k, v in compiles.since(mark).items()},
        memory_before=mem0, memory_after_upload=mem_up,
        memory_after_warmup=mem1,
    )
    check(idx["base"] == len(pairs), f"base holds {idx['base']} filters")
    rng = np.random.default_rng(args.seed)
    windows = [
        make_topics(rng, WINDOW_TOPICS, pops) for _ in range(N_WINDOWS)
    ]
    say("engine_windows",
        **run_windows(eng, ref, windows, compiles, "engine"),
        breaker=eng.breaker_info(), memory=memory(dev))


# ---------------------------------------------------------------- served

def rule_sql(i: int) -> str:
    """Arithmetic-free WHERE clauses over the publishers' payloads:
    numeric and string comparisons, IN lists, presence, AND/OR/NOT —
    all lowerable, all f32-exact, so the device rules step takes them."""
    kind = i % 5
    if kind == 0:
        return (f'SELECT payload.seq as seq FROM "vehicles/+/sensors/#" '
                f"WHERE payload.temp > {i % 40}")
    if kind == 1:
        return (f'SELECT * FROM "dev/#" WHERE payload.dev = \'d{i % 7}\' '
                f"and payload.hum <= {20 + i % 60}")
    if kind == 2:
        return (f'SELECT topic FROM "site/+/floor/#" WHERE '
                f"payload.temp >= {i % 30} or not (payload.hum < {i % 50})")
    if kind == 3:
        return (f'SELECT clientid FROM "vehicles/#" WHERE '
                f"payload.dev in ('d{i % 7}', 'd{(i + 3) % 7}') "
                f"and is_not_null(payload.hum)")
    return (f'SELECT payload FROM "#" WHERE payload.temp = {i % 50} '
            f"and payload.dev != 'd{i % 7}'")


def live_filters(j: int) -> list:
    """Subscriber ``j``'s filters, disjoint from one another (a publish
    reaches it through at most one).  All are distinct across the
    subscribers but the two broad ones that a fifth of them share."""
    kind, out = j % 5, []
    for n in range(LIVE_FILTERS):
        k = j // 5 + 1 + n * (N_LIVE // 5 + 1)
        if kind == 0:
            out.append(f"vehicles/v{k}/sensors/#")
        elif kind == 1:
            out.append(f"dev/g{k}/+/d{k % 7}")
        elif kind == 2:
            out.append(f"site/+/floor/f{k}/#")
        elif kind == 4:
            out.append(f"vehicles/v{k}/#")
        elif n:
            out.append(f"site/+/floor/f{k}/a")
        else:
            out.append("vehicles/+/sensors/temp" if k % 2 else "dev/+/x/+")
    return out


def payload_of(seq: int) -> bytes:
    return json.dumps({
        "seq": seq, "temp": seq * 7 % 50, "hum": seq * 13 % 100,
        "dev": f"d{seq % 7}", "ok": seq % 3 == 0,
    }).encode()


async def served_phase(args, dev, pairs, pops, compiles: CompileLog):
    import numpy as np
    from bench import make_topics

    from emqx_tpu import topic as T
    from emqx_tpu.broker.listener import BrokerServer
    from emqx_tpu.config import (
        BrokerConfig, ListenerConfig, apply_env_overrides, check_config,
    )
    from emqx_tpu.message import Message
    from emqx_tpu.rules.engine import FunctionAction
    from emqx_tpu.rules.runtime import build_env, eval_where

    loop = asyncio.get_running_loop()
    # the config the way listener.main() builds it, the device pinned
    cfg = BrokerConfig()
    cfg.listeners = [ListenerConfig(bind="127.0.0.1", port=0)]
    cfg.engine.use_device = True
    cfg.engine.batch_max = WINDOW_TOPICS
    # keep every window's record, not the last 256: the check reads
    # each window's match path
    cfg.profiler.ring_size = 1 << 16
    apply_env_overrides(cfg)
    check(not check_config(cfg), f"config: {check_config(cfg)}")
    server = BrokerServer(cfg)
    broker = server.broker
    eng = broker.router.engine
    check(eng.use_device is True, "the broker's engine is not pinned")
    subs = [(f"sub{j}", live_filters(j), j % 2) for j in range(N_LIVE)]
    n_live_filters = len({f for _, flts, _ in subs for f in flts})
    # the broker gets everything onto the device by its own means: the
    # restored table through the background rebuild, the live filters
    # through the delta fold — so the sizes must reach its thresholds
    check(
        len(pairs) >= eng.rebuild_threshold
        and n_live_filters >= eng.delta_aut_threshold,
        f"{len(pairs)} subs / {n_live_filters} live filters are below "
        f"the engine's rebuild / fold thresholds",
    )

    # what a boot restores before start(): the rules and the table
    fired: list = []  # (rule_id, seq) per rule firing
    for i in range(N_RULES):
        rid = f"r{i}"
        broker.rules.add_rule(rid, rule_sql(i), [FunctionAction(
            lambda sel, msg, rid=rid: fired.append(
                (rid, json.loads(msg.payload)["seq"])
            )
        )])
    mark = compiles.mark()
    t0 = time.perf_counter()
    await loop.run_in_executor(None, eng.insert_many, pairs)
    insert_s = time.perf_counter() - t0
    # start() waits for the build that insert_many() started, then
    # compiles every window bucket and the rules program
    t0 = time.perf_counter()
    await server.start()
    start_s = time.perf_counter() - t0
    t_start = time.time()
    clock = {}  # seconds after start() at which each step ended
    child = None
    try:
        idx = eng.index_stats()
        check(
            idx["base"] == len(pairs) + N_RULES and idx["residual"] == 0,
            f"start() left filters outside the base automaton: {idx}",
        )
        gc.collect()
        say(
            "served_start", subs=len(eng), rules=N_RULES,
            insert_s=round(insert_s, 3), start_s=round(start_s, 3),
            **{"start_" + k: v for k, v in compiles.since(mark).items()},
            memory=memory(dev),
        )
        port = server.listeners[0].port
        rng = np.random.default_rng(args.seed + 1)
        topics = make_topics(rng, N_PUBLISH, pops)
        pubs = [(t, payload_of(seq).decode()) for seq, t in enumerate(topics)]
        # what the scalar referees say: deliveries per subscription by
        # topic.match_words, rule firings by the interpreter
        words = {t: T.words(t) for t in set(topics)}
        hits: dict = {}  # filter -> the distinct topics it matches

        def matched_by(flt: str) -> set:
            if flt not in hits:
                fw = T.words(flt)
                hits[flt] = {
                    t for t, tw in words.items() if T.match_words(tw, fw)
                }
            return hits[flt]

        want_recv = {
            cid: sorted(
                (seq, min(1, qos)) for flt in flts
                for seq, t in enumerate(topics) if t in matched_by(flt)
            )
            for cid, flts, qos in subs
        }
        envs = [
            build_env(Message(topic=t, payload=payload_of(seq), qos=1))
            for seq, t in enumerate(topics)
        ]
        want_fired = {
            (rid, seq)
            for rid, rule in broker.rules.rules.items()
            for seq, t in enumerate(topics)
            if any(t in matched_by(f) for f in rule.parsed.froms)
            and eval_where(rule.parsed.where, envs[seq])
        }
        n_expect = sum(len(v) for v in want_recv.values())
        check(n_expect > 0 and want_fired, "the workload exercises nothing")

        # the client child: codec + asyncio only, never JAX
        mark = compiles.mark()
        child = await asyncio.create_subprocess_exec(
            sys.executable, os.path.abspath(__file__), "--client",
            stdin=asyncio.subprocess.PIPE, stdout=asyncio.subprocess.PIPE,
        )
        child.stdin.write((json.dumps({
            "port": port, "subs": subs, "pubs": pubs,
            "publishers": N_PUBLISHERS, "expect": n_expect,
        }) + "\n").encode())
        await child.stdin.drain()
        ready = json.loads(await asyncio.wait_for(
            child.stdout.readline(), 120
        ))
        clock["subscribed"] = round(time.time() - t_start, 3)
        check(
            ready["granted"] == [[q] * LIVE_FILTERS for _, _, q in subs],
            f"granted QoS differ from asked: {ready['granted'][:8]}...",
        )
        # the live filters crossed the fold threshold: the engine
        # folds them into the device's delta automaton in its own
        # thread (upload and bucket warm-up included); left in the
        # host-matched residual their deliveries would prove nothing
        # about the device
        deadline = time.monotonic() + 600
        while True:
            idx = eng.index_stats()
            if idx["folded"] and not idx["folding"]:
                break
            check(time.monotonic() < deadline, f"no delta fold: {idx}")
            await asyncio.sleep(0.05)
        clock["folded"] = round(time.time() - t_start, 3)
        check(
            idx["folded"] >= eng.delta_aut_threshold
            and idx["folded"] + idx["residual"] == n_live_filters,
            f"live filters not where the fold should leave them: {idx}",
        )
        n_filters = len(pairs) + N_RULES + n_live_filters
        check(
            len(eng) == n_filters,
            f"{n_filters - len(eng)} subscriptions left before traffic",
        )
        gc.collect()
        say(
            "served_load", subs=len(eng), live_subs=N_LIVE,
            live_filters=n_live_filters, base=idx["base"],
            folded=idx["folded"], residual=idx["residual"],
            **{"fold_" + k: v for k, v in compiles.since(mark).items()},
            memory=memory(dev),
        )
        broker.profiler.reset()
        mark = compiles.mark()
        t0 = time.perf_counter()
        child.stdin.write(b"go\n")
        await child.stdin.drain()
        out, _ = await asyncio.wait_for(child.communicate(), 600)
        traffic_s = time.perf_counter() - t0
        clock["traffic_done"] = round(time.time() - t_start, 3)
        check(child.returncode == 0, f"client child rc={child.returncode}")
        got = json.loads(out.decode().strip().splitlines()[-1])
    finally:
        if child is not None and child.returncode is None:
            child.kill()
            await child.wait()
        await server.stop()

    check(
        got["pubacks"] == N_PUBLISH,
        f"{got['pubacks']} PUBACKs for {N_PUBLISH} QoS1 publishes",
    )
    for cid, want in want_recv.items():
        have = sorted(map(tuple, got["received"].get(cid, [])))
        check(
            have == want,
            f"{cid}: received {len(have)} deliveries, expected "
            f"{len(want)}",
        )
    check(
        len(fired) == len(set(fired)) and set(fired) == want_fired,
        f"rules fired {len(fired)} times ({len(set(fired))} distinct), "
        f"the interpreter says {len(want_fired)}",
    )
    stats = eng.stats()
    recs = broker.profiler.windows(1 << 16)
    paths: dict = {}
    for r in recs:
        paths[r["path"]] = paths.get(r["path"], 0) + 1
    check(
        recs and set(paths) == {"dev"},
        f"window match paths: {paths}",
    )
    check(
        stats["decide_dev_windows"] > 0 and stats["rules_dev_windows"] > 0,
        f"the device decided or evaluated no window: {stats}",
    )
    check(
        stats["decide_dev_errors"] == 0 and stats["rules_dev_errors"] == 0
        and stats["rules_dev_refused"] == 0,
        f"device step errors: {stats}",
    )
    rstats = broker.rules.stats()
    check(
        rstats["lowered"] == N_RULES and rstats["fallback"] == 0
        and rstats["scalar_windows"] == 0
        and rstats["fallback_rule_evals"] == 0,
        f"rules left the matrix for the interpreter: {rstats}",
    )
    # start() compiled the rules program at every window bucket: one
    # compiling in a dispatch window holds ordered dispatch behind it
    served = compiles.since(mark)
    check(
        "rules_eval_batch" not in served["compile_s_by_fn"],
        f"the rules program compiled inside the traffic: {served}",
    )
    assert_clean(eng, "served")
    say(
        "served_traffic", publishes=N_PUBLISH, pubacks=got["pubacks"],
        deliveries=n_expect, rule_firings=len(fired),
        traffic_s=round(traffic_s, 3), windows=len(recs),
        window_paths=paths,
        window_msgs_max=max(r["n_msgs"] for r in recs),
        decide_dev_windows=stats["decide_dev_windows"],
        decide_host_windows=stats["decide_host_windows"],
        rules_dev_windows=stats["rules_dev_windows"],
        rules_host_windows=stats["rules_host_windows"],
        rules_lowered=rstats["lowered"],
        rules_program_rows=rstats["program_rows"],
        **{"served_" + k: v for k, v in served.items()},
        breaker=eng.breaker_info(), memory=memory(dev),
        # anomaly dumps the broker's flight recorder took meanwhile
        # (an event-loop stall, an SLO breach): seen, not hidden
        clock=clock, flight_dumps=[
            {"reason": d["reason"], "detail": d.get("detail"),
             "at": round(d["at"] - t_start, 3)}
            for d in broker.flight.local_dumps()
        ],
    )


# -------------------------------------------------- the JAX-free client

async def _client(plan: dict) -> dict:
    from emqx_tpu.codec import mqtt as C

    ver = C.MQTT_V5
    received: dict = {}
    n_recv = 0
    pubacks = 0

    async def connect(cid: str):
        r, w = await asyncio.open_connection("127.0.0.1", plan["port"])
        # keepalive off: the subscribers sit idle through the broker's
        # rebuild and warm-up, minutes at 10M subscriptions
        w.write(C.serialize(
            C.Connect(client_id=cid, proto_ver=ver, keepalive=0), ver
        ))
        await w.drain()
        return r, w, C.StreamParser(version=ver)

    async def packets(r, parser):
        while True:
            data = await r.read(1 << 16)
            if not data:
                return
            for pkt in parser.feed(data):
                yield pkt

    async def subscriber(cid: str, flts: list, qos: int, granted: dict,
                         ready: asyncio.Event):
        nonlocal n_recv
        r, w, parser = await connect(cid)
        mine = received.setdefault(cid, [])
        async for pkt in packets(r, parser):
            if pkt.type == C.CONNACK:
                assert pkt.reason_code == 0, pkt
                w.write(C.serialize(C.Subscribe(packet_id=1, subscriptions=[
                    C.Subscription(topic_filter=flt, qos=qos)
                    for flt in flts
                ]), ver))
            elif pkt.type == C.SUBACK:
                granted[cid] = list(pkt.reason_codes)
                ready.set()
            elif pkt.type == C.PUBLISH:
                if pkt.qos == 1:
                    w.write(C.serialize(
                        C.Puback(packet_id=pkt.packet_id), ver
                    ))
                mine.append((json.loads(pkt.payload)["seq"], pkt.qos))
                n_recv += 1

    async def publisher(k: int, items):
        nonlocal pubacks
        r, w, parser = await connect(f"pub{k}")
        acked = asyncio.Event()
        pending = set()

        async def reader():
            nonlocal pubacks
            async for pkt in packets(r, parser):
                if pkt.type == C.CONNACK:
                    assert pkt.reason_code == 0, pkt
                    acked.set()
                elif pkt.type == C.PUBACK:
                    pending.discard(pkt.packet_id)
                    pubacks += 1
                    acked.set()

        task = asyncio.ensure_future(reader())
        await acked.wait()
        pid = 0
        for n, (topic, payload) in enumerate(items):
            while len(pending) >= 32:  # the session's receive maximum
                acked.clear()
                await acked.wait()
            pid = pid % 65535 + 1
            pending.add(pid)
            w.write(C.serialize(C.Publish(
                topic=topic, payload=payload.encode(), qos=1, packet_id=pid,
            ), ver))
            if n % 64 == 63:
                await w.drain()
                await asyncio.sleep(0.02)  # spread over a few seconds
        await w.drain()
        while pending:
            acked.clear()
            await asyncio.wait_for(acked.wait(), 120)
        task.cancel()
        w.close()

    granted: dict = {}
    tasks, events = [], []
    for cid, flts, qos in plan["subs"]:
        ev = asyncio.Event()
        events.append(ev)
        tasks.append(asyncio.ensure_future(
            subscriber(cid, flts, qos, granted, ev)
        ))
    await asyncio.wait_for(
        asyncio.gather(*(e.wait() for e in events)), 120
    )
    print(json.dumps({
        "ready": True, "granted": [granted[c] for c, _, _ in plan["subs"]],
    }), flush=True)
    loop = asyncio.get_running_loop()
    go = await loop.run_in_executor(None, sys.stdin.readline)
    assert go.strip() == "go", go
    k = plan["publishers"]
    await asyncio.gather(*(
        publisher(j, plan["pubs"][j::k]) for j in range(k)
    ))
    # every delivery the referee expects, then a quiet moment in which
    # anything it does not expect would still arrive
    deadline = time.monotonic() + 120
    while n_recv < plan["expect"] and time.monotonic() < deadline:
        await asyncio.sleep(0.05)
    await asyncio.sleep(1.0)
    for t in tasks:
        t.cancel()
    return {"pubacks": pubacks, "received": received}


def client_main() -> None:
    plan = json.loads(sys.stdin.readline())
    out = asyncio.run(_client(plan))
    assert "jax" not in sys.modules, "the client child imported JAX"
    print(json.dumps(out), flush=True)


# ------------------------------------------------------------ four chips

def per_device(devs) -> list:
    """What sits on which device: live array bytes and the allocator's
    own count."""
    import jax

    held = {d.id: 0 for d in devs}
    seen = set()  # a shard's view is a live array too: count buffers once
    for a in jax.live_arrays():
        for s in a.addressable_shards:
            buf = (s.device.id, s.data.unsafe_buffer_pointer())
            if buf not in seen:
                seen.add(buf)
                held[s.device.id] = held.get(s.device.id, 0) + s.data.nbytes
    return [
        {"device": d.id, "live_array_bytes": held[d.id], **memory(d)}
        for d in devs
    ]


def sharded_phase(args, devs, pairs, pops, ref, compiles: CompileLog):
    import jax
    import numpy as np
    from bench import make_topics

    from emqx_tpu.config import BrokerEngineConfig
    from emqx_tpu.engine import MatchEngine
    from emqx_tpu.parallel.sharded import ShardedMatchEngine, make_mesh

    k = args.chips
    shipped = BrokerEngineConfig()
    kw = dict(
        f_width=shipped.f_width, m_cap=shipped.m_cap,
        max_levels=shipped.max_levels, rebuild_threshold=1 << 62,
    )
    mesh = make_mesh(k, devices=devs)
    check(mesh.shape["sub"] == k, f"mesh {dict(mesh.shape)}")
    rng = np.random.default_rng(args.seed)
    windows = [
        make_topics(rng, WINDOW_TOPICS, pops) for _ in range(N_WINDOWS)
    ]

    sh = ShardedMatchEngine(mesh, **kw)
    t0 = time.perf_counter()
    sh.insert_many(pairs)
    sh.rebuild()
    build_s = time.perf_counter() - t0
    mark = compiles.mark()
    t0 = time.perf_counter()
    sh.match_batch(windows[0])
    first_s = time.perf_counter() - t0
    # the tables are split: every mesh-wide array's shard on a device
    # is 1/K of the stack, and the devices' allocators agree
    stack = [
        a for a in jax.live_arrays() if len(a.sharding.device_set) == k
    ]
    check(
        sorted(a.shape for a in stack)
        == sorted(t.shape for t in sh.index.tables),
        f"mesh-wide arrays {[a.shape for a in stack]}",
    )
    for a in stack:
        for s in a.addressable_shards:
            check(
                s.data.shape == (a.shape[0] // k,) + a.shape[1:],
                f"device {s.device.id} holds {s.data.shape} of {a.shape}",
            )
    gc.collect()
    placed = per_device(devs[:k])
    in_use = [p["bytes_in_use"] for p in placed]
    if None in in_use:  # only the CPU backend's allocator keeps no count
        check(devs[0].platform != "tpu", "memory_stats() reports nothing")
        in_use = [p["live_array_bytes"] for p in placed]
    say(
        "sharded_load", subs=len(sh), mesh=dict(mesh.shape),
        build_s=round(build_s, 3), first_window_s=round(first_s, 3),
        **compiles.since(mark),
        tables=[{
            "shape": a.shape, "sharding": str(a.sharding.spec),
            "shard_shape": a.addressable_shards[0].data.shape,
            "shard_bytes": a.addressable_shards[0].data.nbytes,
        } for a in stack],
        placement=placed,
        in_use_spread=round(max(in_use) / max(min(in_use), 1), 4),
    )
    check(
        max(in_use) <= 1.05 * min(in_use),
        f"devices hold unequal shares after the sharded load: {in_use}",
    )

    single = MatchEngine(use_device=True, **kw)
    single.insert_many(pairs)
    single.rebuild()

    def compare(what: str) -> None:
        for w in windows:
            a, b = sh.match_batch(w), single.match_batch(w)
            want = {t: ref.match(t) for t in set(w)}
            for t, x, y in zip(w, a, b):
                check(
                    x == want[t] and y == want[t],
                    f"{what}: {t!r} sharded {len(x)} single {len(y)} "
                    f"referee {len(want[t])}",
                )
        assert_clean(sh, what)
        assert_clean(single, what)

    compare("sharded")
    # churn as dryrun_multichip does: the delta automaton, the
    # tombstones and an incremental sharded rebuild, all re-compared
    base = 10 ** 9
    churn = [
        (f"vehicles/v{i % 97}/+/c{i}", base + i) for i in range(2048)
    ] + [("vehicles/+/sensors/temp", base + 5000)]
    dead = [pairs[0][1], pairs[7][1], pairs[len(pairs) // 2][1]]
    for e in (sh, single):
        e.insert_many(churn)
        for fid in dead:
            check(e.delete(fid), f"delete({fid})")
    for flt, fid in churn:
        ref.insert(flt, fid)
    for fid in dead:
        ref.delete_id(fid)
    compare("sharded after churn")
    for e in (sh, single):
        e.rebuild()
    compare("sharded after rebuild")
    gc.collect()
    say(
        "sharded_compare", windows=len(windows),
        topics=len(windows) * WINDOW_TOPICS,
        equal_to_single_and_referee=True, churned=len(churn),
        deleted=len(dead), **compiles.since(mark),
        placement=per_device(devs[:k]),
        breaker=sh.breaker_info(),
    )


# ------------------------------------------------------------------ main

def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--subs", type=int, default=1_000_000,
                    help="background wildcard subscriptions")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: only the sharded engine and its comparison")
    args = ap.parse_args(argv)
    t_all = time.perf_counter()
    compiles = CompileLog()
    devs = preflight(args.chips)
    pairs, pops = background(args.subs)
    t0 = time.perf_counter()
    ref = referee(pairs)
    say("referee", subs=len(pairs), build_s=round(time.perf_counter() - t0, 3))
    if args.chips == 4:
        sharded_phase(args, devs, pairs, pops, ref, compiles)
    else:
        engine_phase(args, devs[0], pairs, pops, ref, compiles)
        del ref
        gc.collect()
        asyncio.run(served_phase(args, devs[0], pairs, pops, compiles))
    import jax

    say("total", seconds=round(time.perf_counter() - t_all, 3),
        **compiles.since(0))
    print(json.dumps({"ok": True, "device": {
        "platform": devs[0].platform, "kind": devs[0].device_kind,
        "count": len(jax.devices()),
    }}), flush=True)
    return 0


if __name__ == "__main__":
    if sys.argv[1:2] == ["--client"]:
        client_main()
    else:
        sys.exit(main())
