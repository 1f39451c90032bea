"""From a `jax.profiler` trace to the trace metrics and the breakdown.

`extract` reads the device planes of an ``.xplane.pb`` (with nothing
but JAX) into plain lists; `reduce` works on those lists alone, so the
recorded fixture under `tests/benchmark` checks the arithmetic without
a chip.  Busy time is the union of the intervals in which an XLA
operation ran on the device; a kernel's time is the sum of the device
durations of the XLA modules (jitted programs) that carry its name.

The program's ``emqx/<name>`` `TraceAnnotation`s are in the chip's
``.xplane.pb`` (host planes) and nothing here reads them yet; its
kernels' `named_scope`s were not seen there.  So kernels are told apart
by the module names JAX gives them (``jit_<function>``), and idle gaps
are named from the benchmark's side, by the profiler ring's stage laps
put on the trace's clock.
"""

import glob
import os
import re

DEVICE_PLANE = "/device:TPU:"
OPS_LINE, MODULES_LINE = "XLA Ops", "XLA Modules"


def extract(path: str) -> dict:
    """``{plane: {line: [[name, start_ns, duration_ns], ...]}}`` for the
    device planes of one ``.xplane.pb``."""
    from jax.profiler import ProfileData

    out: dict = {}
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith(DEVICE_PLANE):
            continue
        lines = out.setdefault(plane.name, {})
        for line in plane.lines:
            if line.name in (OPS_LINE, MODULES_LINE):
                lines[line.name] = [
                    [ev.name, int(ev.start_ns), int(ev.duration_ns)]
                    for ev in line.events
                ]
    return out


def union(intervals):
    """Merged ``[(start, end)]`` of possibly overlapping intervals."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1][1] = e
        else:
            out.append([s, e])
    return out


def short(op_name: str) -> str:
    """An XLA op's event name is its whole HLO line: keep the name, the
    result's element type and dimensions, and the opcode."""
    head, _, rest = op_name.partition(" = ")
    if not rest:
        return op_name[:96]
    shape = rest.split("{", 1)[0].strip("( ")
    opcode = re.search(r"([a-z][a-z0-9\-]*)\(", rest)
    return f"{head} {shape} {opcode.group(1) if opcode else ''}"[:96].strip()


def module_of(event_name: str) -> str:
    """``jit_match_batch_compact(1234)`` -> ``jit_match_batch_compact``."""
    return event_name.split("(", 1)[0]


def host_intervals(spans: list) -> list:
    """The profiler's chrome-trace B/E pairs as ``(name, start_us,
    end_us)`` on that export's own epoch."""
    open_at: dict = {}
    out = []
    for ev in spans:
        if ev.get("ph") == "B":
            open_at[(ev["tid"], ev["name"])] = ev["ts"]
        elif ev.get("ph") == "E":
            b = open_at.pop((ev["tid"], ev["name"]), None)
            if b is not None:
                out.append((ev["name"], b, ev["ts"]))
        elif ev.get("ph") == "X":
            out.append((ev["name"], ev["ts"], ev["ts"] + ev["dur"]))
    return out


def reduce(planes: dict, window_ns=None, host=None) -> dict:
    """The numbers of one traced window.

    ``planes``: what `extract` returned; ``window_ns``: ``(start, end)``
    of the traced window on the trace's clock (default: first to last
    device event); ``host``: ``[(name, start_ns, end_ns)]`` host stages
    on the same clock, for naming idle gaps.  Returns None where no
    operation ran on a device."""
    per_dev = []
    for lines in planes.values():
        ops = lines.get(OPS_LINE) or lines.get(MODULES_LINE) or []
        if ops:
            per_dev.append((ops, lines.get(MODULES_LINE) or []))
    if not per_dev:
        return None
    if window_ns is None:
        window_ns = (
            min(e[1] for ops, _ in per_dev for e in ops),
            max(e[1] + e[2] for ops, _ in per_dev for e in ops),
        )
    w0, w1 = window_ns
    busy = 0
    by_op: dict = {}
    modules: dict = {}
    gaps = []
    for ops, mods in per_dev:
        merged = union(
            (max(s, w0), min(s + d, w1)) for _, s, d in ops
            if s + d > w0 and s < w1
        )
        busy += sum(e - s for s, e in merged)
        for name, s, d in ops:
            if s + d > w0 and s < w1:
                by_op[name] = by_op.get(name, 0) + d
        for name, s, d in mods:
            if s >= w0 and s + d <= w1:
                m = modules.setdefault(module_of(name), [0, 0])
                m[0] += d
                m[1] += 1
        edge = w0
        for s, e in merged + [[w1, w1]]:
            if s > edge:
                gaps.append((edge, s))
            edge = max(edge, e)
    n_dev = len(per_dev)
    gaps.sort(key=lambda g: g[0] - g[1])
    named: dict = {}
    for s, e in gaps[:2000]:
        mid = (s + e) // 2
        # windows overlap (the pipeline): a stage that works names the
        # gap before one that waits
        open_now = [name for name, hs, he in host or () if hs <= mid < he]
        working = [n for n in open_now if not n.endswith("_wait")]
        what = (working or open_now or ["no window open"])[0]
        named[what] = named.get(what, 0) + (e - s)
    def top(d):
        return [[short(k), v / 1e9] for k, v in
                sorted(d.items(), key=lambda kv: -kv[1])[:10]]

    return {
        "busy_s": busy / n_dev / 1e9,
        "window_s": (w1 - w0) / 1e9,
        "devices": n_dev,
        "modules": {k: {"s": v[0] / 1e9, "n": v[1]}
                    for k, v in modules.items()},
        "breakdown": {"device_ops": top(by_op), "idle_gaps": top(named)},
    }


def reduce_dir(traced: dict, spans: list) -> dict:
    """Reduce the trace `run.py` just wrote: ``traced`` holds its
    directory, the wall-clock ns at which it started and its length."""
    found = glob.glob(
        os.path.join(traced["dir"], "**", "*.xplane.pb"), recursive=True
    )
    if not found:
        return None
    planes = extract(max(found, key=os.path.getmtime))
    starts = [e[1] for ln in planes.values() for evs in ln.values()
              for e in evs]
    if not starts:
        return None
    # device timestamps are ns since the Unix epoch where they are that
    # large, else ns since the trace began
    base = 0 if min(starts) > 10 ** 17 else traced["wall_ns"]
    w0 = traced["wall_ns"] - base
    window = (w0, w0 + int(traced["seconds"] * 1e9))
    host = []
    epoch_ns = traced.get("spans_epoch_ns")
    if epoch_ns is not None:
        host = [
            (name, int(epoch_ns + b * 1e3) - base, int(epoch_ns + e * 1e3) - base)
            for name, b, e in host_intervals(spans)
        ]
    out = reduce(planes, window, host)
    if out is not None:
        # the ring's windows that opened inside the traced window
        out["window_wall"] = (traced["wall_ns"] / 1e9,
                              traced["wall_ns"] / 1e9 + traced["seconds"])
    return out
