"""Dispatch-perf rules (PERF401, PERF402, PERF403).

PR 3 made fan-out single-encode: each unique PUBLISH body is
serialized once per dispatch window and only the packet id is patched
per subscriber (`codec.mqtt.DispatchEncoder`).  PERF401 enforces that
invariant the same way FP301 enforces failpoint seams:
``DISPATCH_FUNCS`` declares the dispatch-marked hot-loop functions,
and any ``serialize(``/``encode(`` call nested inside a loop in one
of them fires PERF401 — a per-subscriber re-encode sneaking back into
the fan-out path fails tier-1 instead of silently re-paying the cost
the window encoder removed.

PERF402 guards the other per-delivery cost PR 5 amortized: a clock
read (``time.time()``/``perf_counter()``/``datetime.now()``-shaped
call) inside a dispatch-marked loop.  The delivery runs take ONE
clock read per run (`Session.deliver`'s hoisted ``now``,
`deliver_run_native`'s bulk `Inflight.insert_run`); a per-iteration
clock sneaking back in is a finding.

PERF403 guards what PR 9's decision columns amortized: a SubOpts
field read (``opts.qos``, ``opts.no_local``, ``opts.
retain_as_published``, ``opts.subid``, ...) inside a dispatch-marked
loop.  The window computes every per-delivery decision as ONE
vectorized pass over the router's attribute columns
(`Router.opts_columns` + `ops.match_kernel.decide_batch[_host]`); a
per-delivery Python attribute read sneaking back into the hot loops
re-pays the cost the columns removed.  The scalar referee paths
(`Session.deliver`, `deliver_run_native`, the detached-queue branch)
keep their reads under justified inline ignores — they ARE the
reference semantics the columns are property-tested against.

An intentional in-loop site takes a justified inline
``# brokerlint: ignore[PERF401]`` / ``[PERF402]`` / ``[PERF403]``.
A declared function that no longer exists is itself a finding, so
the declaration list cannot silently rot.
"""

from __future__ import annotations

import ast
from typing import List, NamedTuple, Sequence

from .engine import ModuleContext, call_tail, dotted_name


class DispatchFn(NamedTuple):
    path_suffix: str   # module path suffix, posix ('broker/broker.py')
    qualname: str      # dotted function name inside the module


# the window fan-out hot loops: expansion/grouping, per-client
# delivery (columns + scalar), the session's packet builder, the
# native-run fast path (decision scan + block bookkeeping), and the
# durable-replay hot path (scheduler round + window build + the
# scalar resume referee) — a mass reconnect drives these exactly as
# hard as live fan-out drives the rest
DISPATCH_FUNCS = (
    DispatchFn("emqx_tpu/broker/broker.py", "Broker._dispatch_window"),
    DispatchFn("emqx_tpu/broker/broker.py", "Broker._dispatch_columns"),
    DispatchFn("emqx_tpu/broker/broker.py", "Broker._dispatch_scalar"),
    DispatchFn("emqx_tpu/broker/broker.py", "Broker._deliver_run"),
    # rule-engine hot path (the rules x window matrix): one column
    # extraction + one matrix eval per window, actions per PASSING
    # (rule, message) only — no per-candidate encode/clock/SubOpts
    # work may creep back in
    DispatchFn("emqx_tpu/rules/engine.py", "RuleEngine.apply_batch"),
    DispatchFn("emqx_tpu/rules/columns.py", "WindowColumns.__init__"),
    # windowed egress (PR 20, PR 29): the SELECT of a rule's fired
    # rows, its actions firing by firing or as one hand-over, and the
    # sink flush loop — per-ROW work stays per-RULE-RUN or per-WINDOW
    DispatchFn("emqx_tpu/rules/select.py", "materialize_rows"),
    DispatchFn("emqx_tpu/rules/engine.py",
               "RuleEngine._run_rule_rows"),
    DispatchFn("emqx_tpu/rules/engine.py", "RuleEngine._run_firings"),
    DispatchFn("emqx_tpu/resources.py", "BufferWorker._flush_once"),
    DispatchFn("emqx_tpu/engine.py", "MatchEngine.rules_eval_window"),
    DispatchFn("emqx_tpu/broker/broker.py", "Broker._resume_enqueue"),
    DispatchFn("emqx_tpu/broker/session.py", "Session.deliver"),
    DispatchFn("emqx_tpu/broker/session.py", "Session.deliver_run_native"),
    DispatchFn("emqx_tpu/broker/session.py", "Session.alloc_packet_ids"),
    DispatchFn("emqx_tpu/broker/resume.py", "ResumeScheduler.drain_once"),
    DispatchFn("emqx_tpu/broker/resume.py",
               "ResumeScheduler._drain_window"),
    DispatchFn("emqx_tpu/broker/resume.py",
               "ResumeScheduler._append_run"),
    # cluster forward reliability hot path (PR 11): one encode + one
    # clock read per peer frame, span work gated on the sampled copy
    DispatchFn("emqx_tpu/cluster/node.py",
               "ClusterNode._flush_forwards"),
    DispatchFn("emqx_tpu/cluster/node.py",
               "ClusterNode._handle_forward_batch"),
    DispatchFn("emqx_tpu/cluster/node.py",
               "ClusterNode._handle_fwd_ack"),
    DispatchFn("emqx_tpu/cluster/quic_transport.py",
               "_send_datagrams"),
    # overload ladder (olp): the level machine and the shed
    # accounting both sit inside dispatch/tick paths — no per-unit
    # clock reads, encodes, or unguarded trace work may creep in
    # (the shed MASK itself is policed via _dispatch_columns above)
    DispatchFn("emqx_tpu/olp.py", "LoadMonitor.observe"),
    DispatchFn("emqx_tpu/olp.py", "LoadMonitor.shed"),
)

# callee tails that mean "re-encode a wire frame"
_ENCODE_TAILS = {"serialize", "encode", "encode_publish"}

# callee tails that mean "read a clock" (time module, datetime
# classmethods, monotonic/perf counters) — once per run, not per
# delivery (PERF402)
_CLOCK_TAILS = {
    "time", "time_ns", "monotonic", "monotonic_ns",
    "perf_counter", "perf_counter_ns", "now", "utcnow", "today",
}

# SubOpts fields the window decision columns replace: reading one of
# these per delivery inside a dispatch loop is PERF403.  The receiver
# must LOOK like a SubOpts binding (its dotted tail contains "opts"),
# so `msg.qos` and `packet.qos` stay clean.
_SUBOPT_FIELDS = {
    "qos", "no_local", "retain_as_published", "retain_handling",
    "subid", "share_group",
}


def _function_map(tree: ast.Module):
    """qualname -> FunctionDef/AsyncFunctionDef for the whole module."""
    out = {}

    def walk(node, prefix: str) -> None:
        for child in ast.iter_child_nodes(node):
            if isinstance(child, ast.ClassDef):
                walk(child, f"{prefix}{child.name}.")
            elif isinstance(child, (ast.FunctionDef,
                                    ast.AsyncFunctionDef)):
                out[f"{prefix}{child.name}"] = child
                walk(child, f"{prefix}{child.name}.")

    walk(tree, "")
    return out


def _loop_calls(fn: ast.AST, tails) -> List[ast.Call]:
    """Calls with a callee tail in ``tails`` lexically inside a
    for/while loop of `fn` (nested def/lambda subtrees are pruned: a
    closure DEFINED in the loop is not a per-subscriber call)."""
    hits: List[ast.Call] = []

    def walk(node: ast.AST, in_loop: bool) -> None:
        for child in ast.iter_child_nodes(node):
            if isinstance(child, (ast.FunctionDef, ast.AsyncFunctionDef,
                                  ast.Lambda)) and child is not fn:
                continue
            child_in_loop = in_loop or isinstance(
                child, (ast.For, ast.AsyncFor, ast.While)
            )
            if (
                in_loop
                and isinstance(child, ast.Call)
                and call_tail(child) in tails
            ):
                hits.append(child)
            walk(child, child_in_loop)

    walk(fn, False)
    return hits


def _loop_opts_reads(fn: ast.AST) -> List[ast.Attribute]:
    """SubOpts field reads (`opts.qos`-shaped Attribute nodes whose
    receiver's dotted tail names an opts binding) executed PER
    ITERATION of a for/while loop in `fn`.  A ``for`` statement's
    target/iterable evaluate once per loop, so they inherit the
    enclosing context; a ``while`` test runs every iteration, so it
    counts as loop body.  Nested def/lambda subtrees pruned as in
    `_loop_calls`."""
    hits: List[ast.Attribute] = []

    def visit(node: ast.AST, in_loop: bool) -> None:
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef,
                             ast.Lambda)) and node is not fn:
            return
        if (
            in_loop
            and isinstance(node, ast.Attribute)
            and node.attr in _SUBOPT_FIELDS
        ):
            base = dotted_name(node.value)
            if base and "opts" in base.split(".")[-1]:
                hits.append(node)
        if isinstance(node, (ast.For, ast.AsyncFor)):
            visit(node.target, in_loop)
            visit(node.iter, in_loop)
            for sub in node.body:
                visit(sub, True)
            for sub in node.orelse:  # else-suite: once per loop
                visit(sub, in_loop)
            return
        if isinstance(node, ast.While):
            visit(node.test, True)  # re-evaluated every iteration
            for sub in node.body:
                visit(sub, True)
            for sub in node.orelse:
                visit(sub, in_loop)
            return
        for child in ast.iter_child_nodes(node):
            visit(child, in_loop)

    visit(fn, False)
    return hits


def check(ctx: ModuleContext,
          dispatch: Sequence[DispatchFn] = DISPATCH_FUNCS) -> None:
    relevant = [d for d in dispatch if ctx.path.endswith(d.path_suffix)]
    if not relevant:
        return
    fns = _function_map(ctx.tree)
    for d in relevant:
        fn = fns.get(d.qualname)
        if fn is None:
            ctx.report(
                ctx.tree, "PERF401", d.qualname,
                f"declared dispatch function `{d.qualname}` not found "
                f"in {ctx.path} — update "
                f"tools/brokerlint/perfrules.py:DISPATCH_FUNCS",
                detail="missing",
            )
            continue
        for call in _loop_calls(fn, _ENCODE_TAILS):
            ctx.report(
                call, "PERF401", d.qualname,
                f"per-subscriber `{call_tail(call)}(` inside the "
                f"dispatch hot loop `{d.qualname}` — encode once per "
                f"window via codec.mqtt.DispatchEncoder instead",
                detail=call_tail(call),
            )
        for call in _loop_calls(fn, _CLOCK_TAILS):
            ctx.report(
                call, "PERF402", d.qualname,
                f"per-delivery clock read `{call_tail(call)}(` inside "
                f"the dispatch hot loop `{d.qualname}` — read the "
                f"clock once per run (hoist it above the loop)",
                detail=call_tail(call),
            )
        for attr in _loop_opts_reads(fn):
            ctx.report(
                attr, "PERF403", d.qualname,
                f"per-delivery SubOpts read `.{attr.attr}` inside the "
                f"dispatch hot loop `{d.qualname}` — consume the "
                f"window decision columns (Router.opts_columns + "
                f"decide_batch) instead of per-delivery attribute "
                f"reads",
                detail=attr.attr,
            )


__all__ = ["check", "DispatchFn", "DISPATCH_FUNCS"]
