#!/usr/bin/env python3
"""One run of one benchmark cell on the attached TPU.

    python benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

This process holds the chip and hosts the system under test: a
`BrokerServer` built the way `listener.main()` builds it, with the
configuration's table and rules loaded before `start()`.  The window
drives only `BrokerServer.start()` and its TCP listener.  The load comes
from child processes (`loadgen.py`) that import neither JAX nor the
program; every end-to-end number is taken at their sockets.

Order: table and rules -> start() -> subscribers -> the engine's own
delta fold -> warm-up traffic of the cell's mix (and, where the cell
has a ``churn`` group, subscriptions made and ended on a schedule from
here to the window's end) -> the measured window -> drain -> read
counters and memory -> stop() -> compare with the plain reference
(`referee.py`) -> print.  Everything before the window is
`setup_s`.  What belongs to one configuration, one cell or one metric
is a data file found by its name in BENCHMARK.json; see README.md.

The last line of stdout is the result object.  Without a TPU (or with a
device kind that `peaks.json` does not list) it exits non-zero and
prints no result: there is no CPU branch and no smaller size.
"""

import time

T_PROC = time.monotonic()

import argparse
import asyncio
import gc
import importlib.util
import json
import os
import resource
import shutil
import sys
import tempfile
import threading
from array import array

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(HERE)
for _p in (REPO, HERE):
    if _p not in sys.path:
        sys.path.insert(0, _p)

import referee
import traffic

PLATFORM = "tpu"                  # the platform every run must find
PEAKS_FILE = os.path.join(HERE, "peaks.json")
DRAIN_S = 60.0                    # how long a late answer is waited for


class Refused(Exception):
    """The run cannot be made here; exit non-zero, print no result."""


def log(**facts) -> None:
    print(json.dumps(facts), flush=True)


def load_json(*parts):
    with open(os.path.join(*parts)) as f:
        return json.load(f)


# ------------------------------------------------------------ data files

def load_cell(name: str, overrides=None):
    """The cell's entry in BENCHMARK.json, its traffic file, its
    configuration's file, and the metrics that list it.  A rehearsal's
    ``overrides`` merge into the two files key by key; a group it lists
    under ``replace`` is put in whole (one that swaps a generator must
    not inherit the old one's arguments)."""
    bench = load_json(REPO, "BENCHMARK.json")
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise Refused(f"no cell {name!r} in BENCHMARK.json")
    cell = cells[name]
    work = load_json(HERE, "workloads", name + ".json")
    conf = load_json(HERE, "configs", cell["config"] + ".json")
    overrides = overrides or {}
    whole = overrides.get("replace", ())
    for target, over in ((work, "workload"), (conf, "config")):
        for k, v in (overrides.get(over) or {}).items():
            if (k not in whole and isinstance(v, dict)
                    and isinstance(target.get(k), dict)):
                target[k].update(v)
            else:
                target[k] = v
    # a generator nobody has is refused here, before the chip is taken
    for kind, group in (("table", conf["table"]), ("live", conf["live"]),
                        ("pool", work["topics"]),
                        ("churn", work.get("churn"))):
        if group is not None:
            traffic.generator(kind, group["generator"])
    # which metrics the cell reports is BENCHMARK.json's to say; how
    # each is read is the metric's own file
    metrics = {}
    for kind in ("end_to_end", "per_layer"):
        for m in bench[kind]:
            if name in m.get("workloads", [name]):
                how = load_json(HERE, "metrics", m["name"] + ".json")
                metrics[m["name"]] = {
                    **how, "unit": m["unit"],
                    "end_to_end": kind == "end_to_end",
                }
    return cell, work, conf, metrics


def reader(name: str):
    spec = importlib.util.spec_from_file_location(
        "reader_" + name, os.path.join(HERE, "readers", name + ".py")
    )
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


# ----------------------------------------------------- compile requests

class CompileLog:
    """Every XLA compile request of this process, by jitted function,
    with whether the persistent cache served it (`jax.monitoring`).
    A copy of `chip_smoke.CompileLog`, with the instant of each."""

    def __init__(self) -> None:
        import jax.monitoring as mon

        self.requests: list = []  # (fun_name, seconds, cache_hit, ended)
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
            self.requests.append(
                (kw.get("fun_name", "?"), secs, hit, time.monotonic())
            )

    def mark(self) -> int:
        return len(self.requests)

    def since(self, mark: int, until: float = None) -> dict:
        """The requests from ``mark`` on that ended before the instant
        ``until`` (monotonic clock), summed by function."""
        reqs = [r for r in self.requests[mark:]
                if until is None or r[3] <= until]
        by_fn: dict = {}
        for fn, secs, _, _ in reqs:
            by_fn[fn] = round(by_fn.get(fn, 0.0) + secs, 3)
        return {
            "requests": len(reqs),
            "fresh": sum(1 for r in reqs if not r[2]),
            "seconds": sum(r[1] for r in reqs),
            "by_fn": by_fn,
        }


# -------------------------------------------------------------- children

class Child:
    """One `loadgen.py` process and its line protocol."""

    def __init__(self, proc) -> None:
        self.proc = proc

    @classmethod
    async def spawn(cls, plan: dict) -> "Child":
        env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
        proc = await asyncio.create_subprocess_exec(
            sys.executable, os.path.join(HERE, "loadgen.py"),
            stdin=asyncio.subprocess.PIPE, stdout=asyncio.subprocess.PIPE,
            limit=1 << 22, env=env,
        )
        self = cls(proc)
        self.say(json.dumps(plan))
        return self

    def say(self, line: str) -> None:
        self.proc.stdin.write(line.encode() + b"\n")

    async def hear(self, timeout: float) -> dict:
        line = await asyncio.wait_for(self.proc.stdout.readline(), timeout)
        if not line:
            raise Refused(
                f"a load generator died (rc={self.proc.returncode})"
            )
        return json.loads(line)

    async def ask(self, line: str, timeout: float) -> dict:
        self.say(line)
        return await self.hear(timeout)

    async def arrays(self, head: dict, counts: list) -> list:
        out = []
        for spec, n in zip(head["arrays"], counts):
            raw = await asyncio.wait_for(
                self.proc.stdout.readexactly(8 * n), 120
            )
            out.append(np.frombuffer(
                raw, dtype=np.int64 if spec.endswith(":q") else np.float64
            ))
        return out

    async def end(self) -> None:
        if self.proc.returncode is None:
            try:
                self.proc.stdin.close()
                await asyncio.wait_for(self.proc.wait(), 10)
            except (asyncio.TimeoutError, OSError):
                self.proc.kill()
                await self.proc.wait()


# ------------------------------------------------------------- preflight

def preflight(chips: int):
    import jax

    devs = jax.devices()
    if devs[0].platform != PLATFORM:
        raise Refused(
            f"need platform {PLATFORM!r}, JAX found {devs[0].platform!r}"
        )
    if len(devs) < chips:
        raise Refused(f"need {chips} devices, JAX found {len(devs)}")
    peaks = load_json(PEAKS_FILE)
    if devs[0].device_kind not in peaks:
        raise Refused(
            f"device kind {devs[0].device_kind!r} is not in peaks.json"
        )
    from emqx_tpu.ds import native as dslog
    from emqx_tpu.ops import dispatchasm, sortutil_native
    from emqx_tpu.ops import tokdict_native, trie_native

    seams = {"hosttrie": trie_native, "sortutil": sortutil_native,
             "tokdict": tokdict_native, "dispatchasm": dispatchasm,
             "dslog": dslog}
    python = [n for n, mod in seams.items() if mod.load() is None]
    if python:
        raise Refused(f"native seams serve from their Python twins: {python}")
    from emqx_tpu import failpoints
    from emqx_tpu.engine import enable_compile_cache

    cache = enable_compile_cache()
    armed = failpoints.load_env()
    return devs, peaks[devs[0].device_kind], cache, armed


async def churn_dump(kids: list, cmd: str):
    """Each churn child's header and its arrays, joined across the
    children by name (``dump``: the child goes on; ``stop``: it ends)."""
    heads, parts = [], {}
    for kid in kids:
        head = await kid.ask(cmd, 60)
        n = [head["lives"]] * 7 + [head["receipts"]] * 4
        for spec, a in zip(head["arrays"], await kid.arrays(head, n)):
            parts.setdefault(spec.split(":")[0], []).append(a)
        heads.append(head)
    return heads, {k: np.concatenate(v) for k, v in parts.items()}


def body_depth(flt: str) -> int:
    """Levels of a filter's body, its trailing ``#`` apart."""
    return len([w for w in flt.split("/") if w != "#"])


def churn_plans(group: dict, pops, seed: int, seconds: float, port: int):
    """The churned filters and one plan a churn child: connection ``c``,
    filter ``g`` and arrival ``j`` of the seeded Poisson block go to
    child ``c % n``, ``g % n`` and ``j % n``.  A child's filter comes
    round again only after its other filters; a schedule on which it
    could come round within two ``dwell_s`` (so before its last life's
    UNSUBACK) is refused."""
    filters = traffic.churn_filters(group, pops, seed)
    n, dwell = group["churn_children"], group["dwell_s"]
    block = traffic.poisson_schedule(group["rate"], seconds, seed)
    if not 1 <= n <= min(group["clients"], len(block), len(filters)):
        raise Refused(f"churn: {n} children for {group['clients']} "
                      f"connections, {len(filters)} filters and "
                      f"{len(block)} cycles a window")
    plans = []
    for k in range(n):
        mine, dues = list(range(k, len(filters), n)), block[k::n]
        reps = len(mine) // len(dues) + 2
        t = np.concatenate([dues + b * seconds for b in range(reps)])
        gap = float((t[len(mine):] - t[:-len(mine)]).min())
        if gap < 2 * dwell:
            raise Refused(f"churn: a filter comes round {gap:.2f} s after "
                          f"its last cycle began, under 2 x dwell_s: "
                          f"more filters or a lower rate")
        plans.append({
            "role": "churn", "port": port,
            "conns": list(range(k, group["clients"], n)),
            "filters": [[g, filters[g]] for g in mine],
            "dues": dues.tolist(), "period": seconds, "dwell_s": dwell,
            "qos": group["qos"], "ack_wait_s": DRAIN_S,
        })
    return filters, plans


def churn_facts(ch: "referee.Churned", t0: float, t1: float,
                cpu: list, seconds: float, folds: dict, heads: list) -> dict:
    """What the result line says of the churn, beside what it compares:
    subscriptions made and ended in the window, the delays of their
    acknowledgements, the folds the engine made in it (the count and the
    summed time of the ``engine_delta_fold`` histogram between the
    window's two instants, ``folds``) and the churn children's CPU
    share."""
    def pct(a, q):
        return float(np.percentile(a, q)) if len(a) else None

    made = (ch.s0 >= t0) & (ch.s0 < t1) & (ch.s1 > 0)
    ended = (ch.u0 >= t0) & (ch.u0 < t1) & (ch.u1 < np.inf)
    sub_ms = (ch.s1 - ch.s0)[made] * 1e3
    unsub_ms = (ch.u1 - ch.u0)[ended] * 1e3
    return {
        "subscribed": int(made.sum()), "unsubscribed": int(ended.sum()),
        "lives": len(ch.s0), "owed": ch.n_owed,
        "suback_ms_p50": pct(sub_ms, 50), "suback_ms_p99": pct(sub_ms, 99),
        "unsuback_ms_p50": pct(unsub_ms, 50),
        "unsuback_ms_p99": pct(unsub_ms, 99),
        "folds": folds["t1"][0] - folds["t0"][0],
        "fold_ms": (folds["t1"][1] - folds["t0"][1]) / 1e3,
        "cpu_pct_busiest": 100.0 * max(cpu) / seconds,
        "clashes": sum(h["clashes"] for h in heads),
    }


def memory_peak(dev) -> int:
    stats = dev.memory_stats() or {}
    return int(stats.get("peak_bytes_in_use") or 0)


# ------------------------------------------------------------------- run

async def run_cell(args, cell, work, conf, metrics, subs, routed, devs,
                   peak, compiles, fault=None, trace_dir=None):
    from emqx_tpu.broker.listener import BrokerServer
    from emqx_tpu.config import (
        BrokerConfig, ListenerConfig, apply_env_overrides, check_config,
    )
    from emqx_tpu.rules.engine import FunctionAction

    loop = asyncio.get_running_loop()
    dev = devs[0]
    # the config the way listener.main() builds it, the device pinned
    cfg = BrokerConfig()
    cfg.listeners = [ListenerConfig(bind="127.0.0.1", port=0)]
    for group in ("engine", "mqtt"):
        for k, v in conf.get(group, {}).items():
            if not hasattr(getattr(cfg, group), k):
                raise Refused(f"no config key {group}.{k}")
            setattr(getattr(cfg, group), k, v)
    # keep every window's record, not the last 256: the comparison reads
    # each window's match path, the readers each window's stages
    cfg.profiler.ring_size = 1 << 17
    churn = work.get("churn")
    apply_env_overrides(cfg)
    if check_config(cfg):
        raise Refused(f"config: {check_config(cfg)}")
    server = BrokerServer(cfg)
    broker = server.broker
    eng = broker.router.engine
    if eng.use_device is not True:
        raise Refused("the broker's engine is not pinned to the device")

    # what a boot restores before start(): the rules and the table
    n_rules = conf["rules"]["count"]
    fired_rule, fired_seq = array("i"), array("q")
    lo, hi = traffic.SEQ_AT, traffic.SEQ_AT + traffic.SEQ_W
    for i in range(n_rules):
        broker.rules.add_rule(f"r{i}", traffic.rule_sql(i), [FunctionAction(
            lambda sel, msg, i=i: (
                fired_rule.append(i), fired_seq.append(int(msg.payload[lo:hi]))
            )
        )])
    pairs, pops = traffic.generate("table", conf["table"])
    n_table = len(pairs)
    table_depth = max([body_depth(f) for f, _ in pairs[:10]] + [0])
    t = time.monotonic()
    if pairs:
        await loop.run_in_executor(None, eng.insert_many, pairs)
    del pairs
    insert_s = time.monotonic() - t
    # control.py's way in: one guarantee broken underneath the path
    undo = fault(server) if fault is not None else None
    children: list = []
    try:
        t = time.monotonic()
        mark = compiles.mark()
        await server.start()
        start_s = time.monotonic() - t
        idx = eng.index_stats()
        if n_table and (idx["base"] != n_table + n_rules or idx["residual"]):
            raise Refused(f"start() left filters outside the base: {idx}")
        log(phase="start", insert_s=insert_s, start_s=start_s,
            compiles=compiles.since(mark), index=idx)
        port = server.listeners[0].port

        # ---------------------------------------- subscribers, then fold
        n_live_filters = len(set(routed))
        t = time.monotonic()
        mark = compiles.mark()
        n_sub = work["subscriber_children"]
        sub_kids = [
            await Child.spawn({"role": "sub", "port": port,
                               "conns": subs[k::n_sub]})
            for k in range(n_sub)
        ]
        children += sub_kids
        for k, kid in enumerate(sub_kids):
            ready = await kid.hear(300)
            want = [[q] * len(f) for _, f, q in subs[k::n_sub]]
            if ready["granted"] != want:
                raise Refused("granted QoS differ from those asked for")
        subscribe_s = time.monotonic() - t
        t = time.monotonic()
        wildcard = any("+" in f or "#" in f for f in routed)
        if wildcard and n_live_filters >= eng.delta_aut_threshold:
            # the live filters crossed the fold threshold: the engine
            # folds them into the device's delta automaton in its own
            # thread; left in the host-matched residual their deliveries
            # would prove nothing about the device
            while True:
                idx = eng.index_stats()
                if idx["folded"] and not idx["folding"]:
                    break
                if time.monotonic() - t > 600:
                    raise Refused(f"no delta fold: {idx}")
                await asyncio.sleep(0.05)
        fold_s = time.monotonic() - t
        log(phase="subscribed", live_subscribers=len(subs),
            live_filters=n_live_filters, subscribe_s=subscribe_s,
            fold_s=fold_s, compiles=compiles.since(mark),
            index=eng.index_stats())

        # ------------------------- churn connections, idle until warm-up
        churn_kids = []
        if churn is not None:
            churn_flts, plans = churn_plans(churn, pops, args.seed,
                                            args.seconds, port)
            churn_kids = [await Child.spawn(plan) for plan in plans]
            children += churn_kids
            for kid in churn_kids:
                await kid.hear(300)

        # ------------------------------------------ publishers, warm-up
        k_pub = work["publishers"]
        pool = traffic.topic_pool(work["topics"], pops, args.seed, k_pub)
        n_pub = work["publisher_children"]
        pub_kids = [
            await Child.spawn({
                "role": "pub", "port": port, "publishers": k_pub,
                "conns": list(range(k, k_pub, n_pub)), "pool": pool,
                "inflight": work["inflight"], "qos": work["qos"],
            }) for k in range(n_pub)
        ]
        children += pub_kids
        for kid in pub_kids:
            await kid.hear(300)
        t = time.monotonic()
        mark = compiles.mark()
        for kid in churn_kids:
            kid.say(f"start {t + 0.05}")
        for kid in pub_kids:
            kid.say(f"warm {work['warmup_publishes']}")
        warm = [await kid.hear(900) for kid in pub_kids]
        n_warm = sum(w["sent"] for w in warm)
        # then bursts of every size a window can have, so that each
        # batch bucket is compiled at the capacity multiplier the flood
        # left behind (a ramp or a lull would else compile in the window)
        for burst in work.get("warmup_bursts", []):
            for kid in pub_kids:
                kid.say(f"warm {n_warm + burst}")
            warm = [await kid.hear(900) for kid in pub_kids]
            n_warm = sum(w["sent"] for w in warm)
        await asyncio.sleep(0.3)
        warm_s = time.monotonic() - t
        log(phase="warmup", publishes=n_warm, warm_s=warm_s,
            compiles=compiles.since(mark))
        if any(w["outstanding"] for w in warm):
            raise Refused(f"warm-up publishes never acknowledged: {warm}")

        # --------------------------------------------------- the window
        gc.collect()
        broker.profiler.reset()
        stats0 = eng.stats()
        rstats0 = broker.rules.stats()
        mark = compiles.mark()
        t0 = time.monotonic() + 0.25
        t1 = t0 + args.seconds
        wall0 = time.time() + 0.25  # the ring stamps the wall clock
        for kid in churn_kids + sub_kids:
            kid.say(f"window {t0} {t1}")
        folds = {}
        if churn_kids:
            def fold_mark(at):
                snap = broker.profiler.snapshots().get("engine_delta_fold")
                folds[at] = (snap.count, snap.sum) if snap else (0, 0.0)
            loop.call_at(t0, fold_mark, "t0")
            loop.call_at(t1, fold_mark, "t1")
        if work["loop"] == "paced":
            # sequence numbers follow the warm-up's; connection
            # seq % publishers sends it, so each child gets its own
            base = (n_warm // k_pub + 1) * k_pub
            due = traffic.poisson_schedule(
                work["rate"], args.seconds, args.seed
            )
            seqs = base + np.arange(len(due))
            for k, kid in enumerate(pub_kids):
                own = (seqs % k_pub) % n_pub == k
                plan = [[int(s), float(d)]
                        for s, d in zip(seqs[own], due[own])]
                kid.say(f"paced {t0} {t1} {json.dumps(plan)}")
        else:
            for kid in pub_kids:
                kid.say(f"flood {t0} {t1}")
        setup_s = t0 - T_PROC
        traced = None
        if args.trace:
            import jax

            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            opts.host_tracer_level = 2
            at = min(work["trace"]["at_s"], max(args.seconds - 1.5, 0) / 2)
            await asyncio.sleep(max(t0 + at - time.monotonic(), 0))
            w0 = time.time_ns()
            await loop.run_in_executor(None, lambda: jax.profiler.start_trace(
                trace_dir, profiler_options=opts
            ))
            m0 = time.monotonic()
            await asyncio.sleep(
                min(work["trace"]["seconds"], max(t1 - m0 - 0.5, 0.5))
            )
            m1 = time.monotonic()
            await loop.run_in_executor(None, jax.profiler.stop_trace)
            traced = {"dir": trace_dir, "wall_ns": w0, "seconds": m1 - m0}
        for kid in pub_kids:
            await kid.hear(args.seconds + DRAIN_S + 120)
        for kid in churn_kids:
            await kid.hear(DRAIN_S + 120)
        inside = compiles.since(mark, until=t1)

        # ----------------------------------------------------- drain
        sent, dues, sends, acks = [], [], [], []
        pub_cpu, refused, stray = [], 0, 0
        for kid in pub_kids:
            head = await kid.ask("stop", 60)
            a = await kid.arrays(head, [head["n"]] * 4)
            sent.append(a[0]); dues.append(a[1])
            sends.append(a[2]); acks.append(a[3])
            pub_cpu.append(head["cpu_s"])
            refused += head["refused"]
            stray += head["stray_acks"] + head["closed"]
        sent, dues = np.concatenate(sent), np.concatenate(dues)
        sends, acks = np.concatenate(sends), np.concatenate(acks)
        churned = None
        if churn_kids:
            # every life has ended: its four instants are final
            heads, lives = await churn_dump(churn_kids, "dump")
            churned = referee.Churned(pool, churn_flts, lives, sent,
                                      sends, acks)
        exp = referee.Expected(pool, subs, n_rules, sent, churn=churned)
        plain = exp.n_deliveries - (churned.n_owed if churned else 0)
        t = time.monotonic()
        while True:
            got = sum([(await kid.ask("count", 30))["count"]
                       for kid in sub_kids])
            if got >= plain or time.monotonic() - t > DRAIN_S:
                break
            await asyncio.sleep(0.1)
        while churned is not None and time.monotonic() - t <= DRAIN_S:
            _, r = await churn_dump(churn_kids, "dump")
            life = churned.attribute(r["r_conn"], r["r_seq"], r["r_t"])
            if not churned.missing(life, r["r_seq"]).any():
                break
            await asyncio.sleep(0.1)
        await asyncio.sleep(0.5)  # anything nobody expects still arrives
        drain_s = time.monotonic() - t1
        received = [None] * len(subs)
        recv_t = [None] * len(subs)
        qos_seen = [0] * len(subs)
        sub_cpu, sub_closed = [], 0
        for k, kid in enumerate(sub_kids):
            head = await kid.ask("stop", 60)
            n = sum(c[0] for c in head["conns"])
            s, ts = await kid.arrays(head, [n, n])
            at = 0
            for j, (cnt, seen, _dups, closed) in zip(
                range(k, len(subs), n_sub), head["conns"]
            ):
                received[j], recv_t[j] = s[at:at + cnt], ts[at:at + cnt]
                qos_seen[j] = seen
                sub_closed += closed
                at += cnt
            sub_cpu.append(head["cpu_s"])
        churn_got = None
        if churned is not None:
            heads, r = await churn_dump(churn_kids, "stop")
            churn_got = (r["r_conn"], r["r_seq"], r["r_t"], r["r_qos"])
            received.append(r["r_seq"])
            recv_t.append(r["r_t"])
            sub_cpu += [h["cpu_s"] for h in heads]
            sub_closed += sum(h["closed"] + h["refused"] + h["stray"]
                              for h in heads)

        # ------------------------------- counters, memory, then stop()
        stats1 = eng.stats()
        rstats1 = broker.rules.stats()
        ring = broker.profiler.windows(1 << 17)
        spans = []
        if traced and ring:
            # the export's timestamps count from its own epoch, the
            # oldest window's start: put them back on the wall clock
            spans = broker.profiler.chrome_trace()["traceEvents"]
            traced["spans_epoch_ns"] = int(
                min(r["at"] for r in ring) * 1e9
            )
        brk = eng.breaker_info()
        drops = {k: v for k, v in broker.metrics.all().items()
                 if "drop" in k and v}
        mem_peak = memory_peak(dev)
        late = compiles.since(mark)
    finally:
        for kid in children:
            await kid.end()
        await server.stop()
        if undo is not None:
            undo()

    # ------------------------------------------------------- compare
    t = time.monotonic()
    engine = {k: stats1[k] - stats0[k] for k in stats1
              if isinstance(stats1[k], int) and not isinstance(stats1[k], bool)
              and isinstance(stats0.get(k), int)}
    # the guarantee is held over every window served, the drain's too;
    # the readers get the windows that opened inside the measured one
    paths: dict = {}
    for r in ring:
        paths[r["path"]] = paths.get(r["path"], 0) + 1
    n_windows = len(ring)
    ring = [r for r in ring if wall0 <= r["at"] < wall0 + args.seconds]
    on_device = conf["guarantees"]["device_steps"]
    device = {
        "device_errors": brk["device_errors"] + brk["trips"]
        + brk["slow_windows"] + engine["decide_dev_errors"]
        + engine["rules_dev_errors"] + engine["rules_dev_refused"],
        "client_errors": refused + stray + sub_closed,
    }
    if "match" in on_device:
        device["windows_not_dev"] = n_windows - paths.get("dev", 0)
    if "decide" in on_device:
        device["decide_host_windows"] = engine["decide_host_windows"]
        device["no_decide_dev_window"] = int(engine["decide_dev_windows"] == 0)
    if "rules" in on_device:
        device["rules_host_windows"] = engine["rules_host_windows"]
        device["no_rules_dev_window"] = int(engine["rules_dev_windows"] == 0)
        device["rules_not_lowered"] = (
            n_rules - rstats1["lowered"] + rstats1["fallback"]
            + rstats1["scalar_windows"] - rstats0["scalar_windows"]
            + rstats1["fallback_rule_evals"] - rstats0["fallback_rule_evals"]
        )
    numbers, failed_seqs = referee.judge(
        exp, k_pub, sent[acks > 0], received[:len(subs)], qos_seen,
        np.frombuffer(fired_rule, dtype=np.int32),
        np.frombuffer(fired_seq, dtype=np.int64), device, churn_got,
    )
    in_window = sent[sends >= t0]
    failed = len(np.intersect1d(in_window, failed_seqs))
    correct = all(v <= lim for _, v, lim in numbers) and len(in_window) > 0
    compare_s = time.monotonic() - t

    # -------------------------------------------------------- reduce
    all_t = np.concatenate(recv_t) if recv_t else np.zeros(0)
    all_s = np.concatenate(received) if received else np.zeros(0, np.int64)
    in_win = (all_t >= t0) & (all_t < t1)
    pub_in = sends >= t0
    run = {
        "window_s": args.seconds, "ring": ring, "engine": engine,
        "compiles": inside, "peak": peak, "config": conf, "workload": work,
        "publishes": int(pub_in.sum()),
        "deliveries": int(in_win.sum()),
        "loadgen": {
            "setup_s": setup_s,
            "deliver_rate": float(in_win.sum()) / args.seconds,
            "publish_rate": float(pub_in.sum()) / args.seconds,
            "cpu_pct_busiest": 100.0 * max(pub_cpu + sub_cpu) / args.seconds,
        },
        "trace": None,
        "shapes": {
            "f_width": cfg.engine.f_width,
            "matches_per_row": conf["shapes"]["matches_per_row"],
            # the automaton scans one level past its deepest filter body
            "kernel_levels": 1 + max(
                [body_depth(f) for f in routed]
                + [body_depth(f) for f in referee.RULE_FROM[:n_rules]]
                + [table_depth]
            ),
        },
    }
    lg = run["loadgen"]
    # the window second by second, for the log: a stall or a drift shows
    lg["deliveries_by_second"] = np.bincount(
        (all_t[in_win] - t0).astype(int), minlength=int(args.seconds)
    ).tolist()
    if work["loop"] == "paced":
        # from the instant a publish was DUE to its receipt / its PUBACK
        order = np.argsort(sent)
        mine = np.isin(all_s, in_window)
        at = order[np.searchsorted(sent[order], all_s[mine])]
        lg["deliver_ms"] = (all_t[mine] - dues[at]) * 1e3
        ok = pub_in & (acks > 0)
        lg["puback_ms"] = (acks[ok] - dues[ok]) * 1e3
        lg["late_ms"] = (sends[pub_in] - dues[pub_in]) * 1e3
        lg["late_ms_p99"] = float(np.percentile(lg["late_ms"], 99))
        # does the delay grow through the window?  the median by quarter
        q = np.minimum(((dues[at] - t0) / args.seconds * 4).astype(int), 3)
        lg["deliver_p50_ms_by_quarter"] = [
            float(np.median(lg["deliver_ms"][q == k])) if (q == k).any()
            else None for k in range(4)
        ]
    if traced:
        import trace_reduce

        run["trace"] = trace_reduce.reduce_dir(traced, spans)
    out_metrics = {}
    want_e2e = not args.trace
    for name, m in metrics.items():
        if bool(m.get("end_to_end")) != want_e2e:
            continue
        value = reader(m["reader"])(run, **m.get("args", {}))
        if value is not None:
            out_metrics[name] = {"value": float(value), "unit": m["unit"]}
    import jax

    device_out = {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices()), "memory_peak_bytes": mem_peak,
    }
    result = {
        "correct": bool(correct), "attempted": int(len(in_window)),
        "failed": int(failed), "metrics": out_metrics, "device": device_out,
    }
    if run["trace"]:
        device_out["busy_s"] = run["trace"]["busy_s"]
        device_out["window_s"] = run["trace"]["window_s"]
        result["breakdown"] = run["trace"]["breakdown"]
    if churned is not None:
        result["churn"] = churn_facts(
            churned, t0, t1, sub_cpu[len(sub_kids):], args.seconds,
            folds, heads,
        )
    churn_log = {"churn": result["churn"]} if churned is not None else {}
    log(phase="window", cell=cell["name"], seed=args.seed,
        platform=dev.platform, kind=dev.device_kind,
        publishes=run["publishes"], deliveries=run["deliveries"],
        warm_publishes=n_warm, expected_deliveries=exp.n_deliveries,
        expected_firings=exp.n_firings, windows=n_windows, paths=paths,
        engine=engine, compiles_in_window=inside, compiles_to_the_end=late,
        loadgen={k: v for k, v in lg.items() if not hasattr(v, "shape")},
        pub_cpu_s=pub_cpu, sub_cpu_s=sub_cpu, drain_s=drain_s,
        compare_s=compare_s, broker_drops=drops,
        setup={"insert_s": insert_s, "start_s": start_s,
               "subscribe_s": subscribe_s, "fold_s": fold_s,
               "warm_s": warm_s}, **churn_log)
    result["compared"] = {n: [v, lim] for n, v, lim in numbers}
    for n, v, lim in numbers:
        print(f"compared {n} {v} limit {lim}", file=sys.stderr)
    sys.stderr.flush()
    return result


def main(argv=None, fault=None, overrides=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    trace_dir = tempfile.mkdtemp(prefix="bench_trace_") if args.trace else None
    try:
        soft, hard = resource.getrlimit(resource.RLIMIT_NOFILE)
        if soft < hard:
            resource.setrlimit(resource.RLIMIT_NOFILE, (hard, hard))
        cell, work, conf, metrics = load_cell(args.workload, overrides)
        # the live set and the filters the broker routes for it (a
        # `$share` filter's own, once): one MQTT does not allow is
        # refused here, before the chip is taken (`referee.Overlap`)
        subs = traffic.generate("live", conf["live"])
        routed = [referee.real_filter(f) for _, flts, _ in subs for f in flts]
        churners = work["churn"]["clients"] if "churn" in work else 0
        need = 2 * (work["publishers"] + len(subs) + churners) + 256
        if hard != resource.RLIM_INFINITY and hard < need:
            raise Refused(f"RLIMIT_NOFILE {hard} < {need} sockets")
        compiles = CompileLog()
        devs, peak, cache, armed = preflight(cell["chips"])
        log(phase="preflight", platform=devs[0].platform,
            kind=devs[0].device_kind, devices=len(devs),
            compile_cache=cache, failpoints_armed=armed,
            cell=cell["name"], seed=args.seed, seconds=args.seconds)
        result = asyncio.run(run_cell(
            args, cell, work, conf, metrics, subs, routed, devs, peak,
            compiles, fault, trace_dir,
        ))
    except (Refused, traffic.BadGenerator, referee.Overlap) as e:
        print(f"refused: {e}", file=sys.stderr)
        return 1
    finally:
        if trace_dir:
            shutil.rmtree(trace_dir, ignore_errors=True)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
