"""Rule registry + execution, wired into the broker's match step.

Mirrors `emqx_rule_engine` (/root/reference/apps/emqx_rule_engine/src/
emqx_rule_engine.erl): each rule's FROM filters register in the topic
index (:536 `emqx_topic_index:insert` into ?RULE_TOPIC_INDEX) and
per-message lookup is a match over that index (:226-231
`get_rules_for_topic`).  Here the rule filters go into the *same*
MatchEngine as subscriptions under a distinct fid class
``("rule", rule_id, i)``, so one batched device step returns routes
and rule hits together; `Broker._dispatch` splits the classes.

Actions mirror the reference's builtins (emqx_rule_actions): republish
(with ${var} placeholder templates, `emqx_placeholder` semantics),
console, and arbitrary Python callables (the hook for
resource/bridge-style sinks).

Execution is window-at-a-time: `apply_batch` decodes the dispatch
window ONCE into shared column planes and evaluates every lowerable
rule's WHERE as one rules x window boolean matrix (host numpy twin or
the fused device kernel in ops/match_kernel.py, per the match
engine's cost EWMAs) — the PAPER.md blueprint's "rule engine's SQL
predicates compiled into the same batched kernel".  Non-lowerable
predicates degrade per RULE to the interpreter over the same lazily
materialized envs, never pushing the window off the matrix path.
The planes hold what the matrix reads and nothing else: a rule whose
SELECT lowered reads its values for the rows it fired on and runs its
actions over them, once a window (`_run_rule_rows`).
"""

from __future__ import annotations

import functools
import json as _json
import logging
import os
import time
from collections import Counter
from dataclasses import dataclass, field
from typing import (
    Any, Callable, Dict, Iterable, List, Optional, Tuple,
)

import numpy as np

from ..message import Message
from .columns import WindowColumns
from .predicate import (
    PredicateProgram, StackedRules, build_stack, compile_where,
)
from .runtime import WindowEnvs, build_env, eval_select, eval_where
from .select import (
    SelectProgram, build_select_stack, compile_template,
    materialize_rows, rows_as_dicts,
)
from .sql import ParsedSql, parse_sql

log = logging.getLogger("emqx_tpu.rules")

RULE_FID = "rule"  # fid class tag

# republish chains are legal but must terminate (the reference relies
# on operator care; we hard-cap recursion)
MAX_REPUBLISH_DEPTH = 8


def render_template(template: str, data: Dict[str, Any]) -> str:
    """${a.b} placeholder substitution (emqx_placeholder parity),
    through the compiled segment-program cache (`select.py`) — action
    templates attached to registered rules are compiled once at
    rule-add and skip even the cache probe."""
    return compile_template(template).render(data)


@dataclass
class RepublishAction:
    topic: str  # template
    payload: str = "${payload}"  # template
    qos: int = 0
    retain: bool = False

    kind: str = "republish"


@dataclass
class ConsoleAction:
    kind: str = "console"


@dataclass
class FunctionAction:
    fn: Callable[[Dict[str, Any], Message], None]
    kind: str = "function"


@dataclass
class SinkAction:
    """Forward the rule output to a registered resource's buffer worker
    (the bridge/action path: emqx_resource buffered IO).  The payload
    template renders against the SELECTed columns; None sends them as
    JSON."""

    resource_id: str
    payload: Optional[str] = None  # template; None => selected as JSON
    kind: str = "sink"


@dataclass
class AggregateAction:
    """Push the SELECTed columns into an Aggregator (the
    emqx_connector_aggregator path: records batch into time-bucketed
    CSV/JSONL objects and flush to the aggregator's delivery sink)."""

    aggregator: Any  # emqx_tpu.aggregator.Aggregator
    kind: str = "aggregate"


Action = Any


@dataclass
class Rule:
    rule_id: str
    sql: str
    parsed: ParsedSql
    actions: List[Action] = field(default_factory=list)
    enabled: bool = True
    description: str = ""
    # compiled WHERE column program (None when the AST has nodes the
    # compiler doesn't cover → per-message interpreter fallback)
    program: Optional[PredicateProgram] = None
    # counters (emqx_rule_metrics)
    matched: int = 0
    passed: int = 0
    failed: int = 0
    actions_success: int = 0
    actions_failed: int = 0

    def metrics(self) -> Dict[str, int]:
        return {
            "matched": self.matched,
            "passed": self.passed,
            "failed": self.failed,
            "actions.success": self.actions_success,
            "actions.failed": self.actions_failed,
        }


class RuleEngine:
    def __init__(self, broker=None) -> None:
        self.broker = broker
        self.rules: Dict[str, Rule] = {}
        # registry mutation counter: the stacked matrix program and
        # the engine's device program-array cache both key on it, so
        # add/remove/enable churn invalidates them coherently
        self.rules_rev = 0
        self._stack_cache: Optional[Tuple[int, StackedRules]] = None
        # "scalar" pins the per-rule interpreter referee (the
        # property suites' oracle); None takes the matrix path with
        # host-vs-device resolved by the match engine's cost EWMAs
        self.eval_force: Optional[str] = None
        # SELECT lane pin: "scalar" keeps the interpreter referee for
        # every rule's SELECT+actions, "batched" pins the column
        # transform past the cost gate, None auto (EWMA-gated)
        self.select_force: Optional[str] = None
        self._sel_cache: Optional[
            Tuple[int, Dict[str, SelectProgram]]
        ] = None
        # cost-EWMA gate state (the WHERE matrix idiom): per-row us
        # for each lane, sampled on single-lane windows only; tripping
        # the breaker pins scalar until registry churn
        self._sel_batch_off = False
        self._sel_us_b: Optional[float] = None
        self._sel_us_s: Optional[float] = None
        self._sel_n_b = 0
        self._sel_n_s = 0
        self._stats = {
            "matrix_windows": 0, "scalar_windows": 0,
            "fallback_rule_evals": 0,
            "select_batched_rows": 0, "select_scalar_rows": 0,
            "select_ewma_off": 0,
        }
        cfg_on = True
        if broker is not None:
            cfg_on = getattr(
                broker.config.engine, "rules_matrix", True
            )
        self._matrix_enabled = cfg_on and (
            os.environ.get("EMQX_TPU_NO_RULES_MATRIX") != "1"
        )
        # rev-keyed flatten tables: a stable position per rule (the
        # REGISTRY enumeration order — deterministic, so action order
        # is reproducible across paths and runs), its Rule object /
        # liveness / matrix row resolved once per rev, and a cache
        # mapping each distinct raw id-list the router's expansion
        # emits to its deduped position array — same-topic messages
        # share one entry, so steady-state windows flatten with ~one
        # dict probe per MESSAGE instead of per (rule x message) pair
        self._flat_key: Optional[Tuple[int, bool]] = None
        self._pos_objs: List[Rule] = []
        self._pos_live = np.zeros(0, bool)
        self._pos_row = np.zeros(0, np.int64)
        self._pos_of: Dict[str, int] = {}
        self._ids_cache: Dict[Tuple[str, ...], np.ndarray] = {}
        # per-position lowered SELECT of a rule with actions: its
        # firings take the per-rule run; None degrades the rule to
        # the scalar referee loop
        self._pos_selp: List[Optional[SelectProgram]] = []

    # ------------------------------------------------------ registry

    def _stacked(self) -> StackedRules:
        """The enabled registry's stacked WHERE program, rebuilt only
        when ``rules_rev`` moved (registry churn invalidates)."""
        cached = self._stack_cache
        if cached is not None and cached[0] == self.rules_rev:
            return cached[1]
        stack = build_stack([
            (rid, r.parsed.where)
            for rid, r in self.rules.items()
            if r.enabled
        ])
        self._stack_cache = (self.rules_rev, stack)
        return stack

    def device_program(self) -> Optional[Tuple[StackedRules, int]]:
        """The stacked WHERE program and its revision as the match
        engine's device rules step would receive them, or None when
        the matrix path is off (`MatchEngine.warmup` compiles the
        rules kernel for it before traffic)."""
        if not self._matrix_enabled or self.eval_force == "scalar":
            return None
        return self._stacked(), self.rules_rev

    def _select_progs(self) -> Dict[str, SelectProgram]:
        """The enabled registry's lowered SELECT programs, by rule."""
        cached = self._sel_cache
        if cached is not None and cached[0] == self.rules_rev:
            return cached[1]
        sel = build_select_stack([
            (rid, r.parsed)
            for rid, r in self.rules.items()
            if r.enabled
        ])
        self._sel_cache = (self.rules_rev, sel)
        return sel

    def add_rule(
        self,
        rule_id: str,
        sql: str,
        actions: Optional[List[Action]] = None,
        enabled: bool = True,
        description: str = "",
    ) -> Rule:
        # validate fully BEFORE touching the registry/index, so a bad
        # update cannot destroy or half-register a live rule
        parsed = parse_sql(sql)
        from .. import topic as T

        for flt in parsed.froms:
            T.validate_filter(flt)
        if rule_id in self.rules:
            self.remove_rule(rule_id)
        rule = Rule(
            rule_id=rule_id,
            sql=sql,
            parsed=parsed,
            actions=list(actions or ()),
            enabled=enabled,
            description=description,
            program=compile_where(parsed.where),
        )
        # precompile every action template ONCE at rule-add (the old
        # render_template re-walked the regex per message); both the
        # batched transform and the scalar referee render through the
        # attached programs
        for a in rule.actions:
            if isinstance(a, RepublishAction):
                a._topic_prog = compile_template(a.topic)
                a._payload_prog = compile_template(a.payload)
            elif isinstance(a, SinkAction) and a.payload is not None:
                a._payload_prog = compile_template(a.payload)
        self.rules[rule_id] = rule
        self.rules_rev += 1
        if self.broker is not None:
            eng = self.broker.router.engine
            for i, flt in enumerate(parsed.froms):
                eng.insert(flt, (RULE_FID, rule_id, i))
        return rule

    def remove_rule(self, rule_id: str) -> bool:
        rule = self.rules.pop(rule_id, None)
        if rule is None:
            return False
        self.rules_rev += 1
        if self.broker is not None:
            eng = self.broker.router.engine
            for i in range(len(rule.parsed.froms)):
                eng.delete((RULE_FID, rule_id, i))
        return True

    def enable_rule(self, rule_id: str, enabled: bool) -> None:
        self.rules[rule_id].enabled = enabled
        self.rules_rev += 1

    # ----------------------------------------------------- execution

    def apply(self, msg: Message, rule_ids: List[str]) -> int:
        """Run the listed rules against one message; returns how many
        passed their WHERE (emqx_rule_runtime:apply_rules/3)."""
        if not rule_ids:
            return 0
        env = build_env(msg)
        hits = 0
        for rid in rule_ids:
            rule = self.rules.get(rid)
            if rule is None or not rule.enabled:
                continue
            rule.matched += 1
            if not eval_where(rule.parsed.where, env):
                rule.failed += 1
                continue
            rule.passed += 1
            hits += 1
            selected = eval_select(rule.parsed, env)
            self._run_firings(rule, [(selected, msg)])
        if self.broker is not None and hits:
            self.broker.metrics.inc("rules.matched", hits)
        return hits

    def apply_batch(
        self, items: List[Tuple[Message, List[str]]], rec=None
    ) -> int:
        """Run rule hits for a whole dispatch window in ONE registry
        pass: the window's messages decode once into shared column
        planes (`WindowColumns`), every lowerable rule's WHERE
        evaluates as a row of the stacked rules x window boolean
        matrix (numpy host twin or the fused device kernel, chosen by
        the match engine's cost EWMAs), and only non-lowerable rules
        (regex/UDF-shaped calls, CASE) degrade — per RULE, not per
        window — to the interpreter over the SAME lazily-materialized
        envs.  Matched/passed/failed counters update once per rule
        and broker metrics flush in one `inc_bulk` pass.

        The planes cover the WHERE stack's paths alone.  A rule
        whose SELECT lowered reads its values for the rows it fired
        on, once a window (`_run_rule_rows`), whatever its actions.

        ``rec`` (the window's profiler record) takes the sub-stages
        of the ``rules`` lap: ``rules_extract`` (column extraction),
        ``rules_eval`` (the matrix, with the device round trip that
        blocks the loop as ``rules_device_wait`` inside it) and
        ``rules_actions`` (the actions' loop); and the window's
        firings on rules with actions (``rules_firings``), with how
        many of them a per-rule run served (``rules_firings_run``)."""
        if not items:
            return 0
        msgs = [m for m, _ in items]
        n = len(msgs)
        envs = WindowEnvs(msgs)
        env = envs.env

        # flatten the sink to (rule-position, msg) pair columns over
        # the rev-stable position space (see __init__): one flatten-
        # cache probe per message on the steady state, with dedup and
        # canonical ordering done by `np.unique` once per DISTINCT
        # raw id list
        use_matrix = (
            self._matrix_enabled and self.eval_force != "scalar"
        )
        stack: Optional[StackedRules] = None
        sel_progs: Dict[str, SelectProgram] = {}
        if use_matrix:
            stack = self._stacked()
            sel_progs = self._select_progs()
        key = (self.rules_rev, use_matrix)
        if self._flat_key != key:
            self._flat_key = key
            objs = list(self.rules.values())
            n_all = len(objs)
            self._pos_objs = objs
            self._pos_of = {
                r.rule_id: k for k, r in enumerate(objs)
            }
            self._pos_live = np.fromiter(
                (r.enabled for r in objs), bool, n_all
            )
            row_of = stack.row_of if stack is not None else {}
            self._pos_row = np.fromiter(
                (
                    row_of.get(r.rule_id, -1) if r.enabled else -1
                    for r in objs
                ),
                np.int64, n_all,
            )
            self._ids_cache = {}
            self._pos_selp = [
                sel_progs.get(r.rule_id)
                if r.enabled and r.actions else None
                for r in objs
            ]
            # registry churn re-arms the SELECT cost gate
            self._sel_batch_off = False
        objs = self._pos_objs
        n_pos = len(objs)
        pos_of = self._pos_of
        cache = self._ids_cache
        parts: List[np.ndarray] = []
        lens: List[int] = []
        for _, rids in items:
            ck = tuple(rids)
            arr = cache.get(ck)
            if arr is None:
                if len(cache) > 4096:
                    cache.clear()
                arr = cache[ck] = np.unique(np.fromiter(
                    (
                        pos_of[r] for r in rids if r in pos_of
                    ),
                    np.int64,
                ))
            parts.append(arr)
            lens.append(arr.size)
        ppos = (
            np.concatenate(parts) if parts
            else np.zeros(0, np.int64)
        )
        pmsg = np.repeat(np.arange(n, dtype=np.int64), lens)
        plive = self._pos_live[ppos]
        prow = self._pos_row[ppos]
        matrix = None
        cols: Optional[WindowColumns] = None
        if use_matrix:
            known = prow >= 0
            active = np.unique(prow[known])
            if active.size:
                t0 = time.perf_counter()
                cols = WindowColumns(
                    msgs, stack.paths, stack.lit_strings, envs
                )
                t1 = time.perf_counter()
                if cols.has_nan_value:
                    # a literal NaN payload value aliases the num
                    # lane's not-a-number sentinel: this window's
                    # rules take the interpreter (bit-exactness over
                    # speed for a pathological payload)
                    pass
                elif self.broker is not None:
                    ev_info: Optional[Dict] = (
                        {} if rec is not None else None
                    )
                    matrix, _path = (
                        self.broker.router.engine.rules_eval_window(
                            stack, self.rules_rev, cols, rows=active,
                            info=ev_info,
                        )
                    )
                    if ev_info:
                        start, dur = ev_info["device_wait"]
                        rec.sub("rules_device_wait", dur, start)
                else:  # standalone: the host twin directly
                    from ..ops.match_kernel import rules_eval_host

                    sub = rules_eval_host(
                        stack.code[active], stack.a0[active],
                        stack.a1[active], stack.a2[active],
                        stack.a3[active], stack.litn[active],
                        cols.lit_ranks, stack.last[active],
                        cols.num, cols.sid, cols.err, cols.prs,
                    )
                    matrix = np.zeros(
                        (stack.n_rules, cols.n), bool
                    )
                    matrix[active] = sub
                if matrix is not None:
                    self._stats["matrix_windows"] += 1
                    if rec is not None:
                        t2 = time.perf_counter()
                        rec.sub("rules_extract", t1 - t0, t0)
                        rec.sub("rules_eval", t2 - t1, t1)
        if matrix is None:
            self._stats["scalar_windows"] += 1
            known = np.zeros(len(ppos), bool)
        passmask = np.zeros(len(ppos), bool)
        if matrix is not None:
            passmask[known] = matrix[prow[known], pmsg[known]]
        # per-RULE interpreter fallback riding the shared lazy envs
        # (one JSON decode per message, window-wide)
        fb = np.nonzero(plive & ~known)[0]
        if fb.size:
            self._stats["fallback_rule_evals"] += int(fb.size)
            ppos_l = ppos.tolist()
            pmsg_l = pmsg.tolist()
            for j in fb.tolist():
                rule = objs[ppos_l[j]]
                passmask[j] = eval_where(
                    rule.parsed.where, env(pmsg_l[j])
                )
        passmask &= plive
        # matched/passed/failed flush: ONE bincount pass over the
        # pair columns, one += per rule TOUCHED this window
        m_cnt = np.bincount(ppos[plive], minlength=n_pos)
        p_cnt = np.bincount(ppos[passmask], minlength=n_pos)
        touched = np.nonzero(m_cnt)[0]
        for pos, mc, pc in zip(
            touched.tolist(),
            m_cnt[touched].tolist(),
            p_cnt[touched].tolist(),
        ):
            rule = objs[pos]
            rule.matched += mc
            rule.passed += pc
            rule.failed += mc - pc
        hits = int(passmask.sum())
        mloc: Counter = Counter()  # one inc_bulk flush per window
        sel = np.nonzero(passmask)[0]
        if sel.size:
            # canonical action order: rule-major in REGISTRY order,
            # message index ascending within a rule — identical
            # across the device / host / scalar-referee paths
            order = np.lexsort((pmsg[sel], ppos[sel]))
            spos = ppos[sel][order]
            smsg_l = pmsg[sel][order].tolist()
            # the per-rule run serves every lowered SELECT unless the
            # lane is pinned to the referee or its cost gate tripped
            use_run = self.select_force != "scalar" and (
                self.select_force == "batched"
                or not self._sel_batch_off
            )
            selp = self._pos_selp
            t_act0 = time.perf_counter()  # hoisted (no clocks in loop)
            rows_b = 0
            rows_s = 0
            # one run of consecutive pairs a rule (the pairs are
            # rule-major after the lexsort)
            cuts = (np.flatnonzero(spos[1:] != spos[:-1]) + 1).tolist()
            for k, k2 in zip([0] + cuts, cuts + [len(smsg_l)]):
                pos = spos[k]
                rule = objs[pos]
                if not rule.actions:
                    # nothing consumes the SELECT columns: skip the
                    # per-hit projection entirely (counter-only rules)
                    continue
                rows = smsg_l[k:k2]
                prog = selp[pos] if use_run else None
                if prog is not None:
                    self._run_rule_rows(rule, prog, envs, rows, mloc)
                    rows_b += k2 - k
                else:
                    # a generator: a firing's SELECT is evaluated
                    # when the firing before it has run its actions
                    parsed = rule.parsed
                    self._run_firings(rule, (
                        (eval_select(parsed, env(i)), msgs[i])
                        for i in rows
                    ), mloc)
                    rows_s += k2 - k
            t_act1 = time.perf_counter()
            self._sel_lane_account(rows_b, rows_s, t_act1 - t_act0)
            if rec is not None:
                rec.sub("rules_actions", t_act1 - t_act0, t_act0)
                rec.rules_firings = rows_b + rows_s
                rec.rules_firings_run = rows_b
        if hits:
            mloc["rules.matched"] += hits
        if self.broker is not None and mloc:
            self.broker.metrics.inc_bulk(mloc)
        return hits

    def _sel_lane_account(
        self, rows_b: int, rows_s: int, dt: float
    ) -> None:
        """Fold one window's SELECT+action lap into the per-lane cost
        EWMAs (sampled on single-lane windows only, so the figures
        aren't cross-contaminated) and trip the batched lane's cost
        breaker when it measures materially slower than the scalar
        referee — re-armed by registry churn, overridden by
        ``select_force``."""
        if rows_b and not rows_s:
            us = dt * 1e6 / rows_b
            self._sel_us_b = (
                us if self._sel_us_b is None
                else 0.2 * us + 0.8 * self._sel_us_b
            )
            self._sel_n_b += 1
        elif rows_s and not rows_b:
            us = dt * 1e6 / rows_s
            self._sel_us_s = (
                us if self._sel_us_s is None
                else 0.2 * us + 0.8 * self._sel_us_s
            )
            self._sel_n_s += 1
        if rows_b:
            self._stats["select_batched_rows"] += rows_b
        if rows_s:
            self._stats["select_scalar_rows"] += rows_s
        if (
            self.select_force is None
            and not self._sel_batch_off
            and self._sel_n_b >= 16
            and self._sel_n_s >= 16
            and self._sel_us_b is not None
            and self._sel_us_s is not None
            and self._sel_us_b > self._sel_us_s * 1.5
        ):
            self._sel_batch_off = True
            self._stats["select_ewma_off"] += 1

    def _run_rule_rows(
        self,
        rule: Rule,
        prog: SelectProgram,
        envs: WindowEnvs,
        rows: List[int],
        mloc: Counter,
    ) -> None:
        """One rule's whole fired-row set through its lowered SELECT:
        one `materialize_rows` pass over the rows, then the actions.

        A rule of window-shaped actions alone (Sink / Aggregate) gets
        ONE bulk handoff per (action, window) —
        `BufferWorker.enqueue_batch` for sinks, one `Aggregator.push`
        for aggregate actions.  Counter totals and per-sink query
        streams match the scalar referee exactly (same values, same
        order); only the cross-ACTION interleave differs (action-major
        within a rule).

        A rule with a function, console or republish action keeps the
        referee's order to the call: firing by firing, the rule's
        actions in their order, one fresh ``selected`` dict a firing,
        a raising action failing itself alone (`_run_firings`)."""
        names, colvals = materialize_rows(prog, envs, rows)
        n = len(rows)
        if not all(
            isinstance(a, (SinkAction, AggregateAction))
            for a in rule.actions
        ):
            msgs = envs.msgs
            self._run_firings(rule, zip(
                rows_as_dicts(names, colvals, n),
                [msgs[i] for i in rows],
            ), mloc)
            return
        resources = (
            self.broker.resources if self.broker is not None else None
        )
        for action in rule.actions:
            try:
                if isinstance(action, AggregateAction):
                    action.aggregator.push(
                        rows_as_dicts(names, colvals, n)
                    )
                else:  # SinkAction
                    if resources is None:
                        raise RuntimeError(
                            "sink action without a broker"
                        )
                    worker = resources.get(action.resource_id)
                    if worker is None:
                        raise RuntimeError(
                            f"unknown resource {action.resource_id!r}"
                        )
                    if action.payload is not None:
                        prog_t = getattr(action, "_payload_prog", None)
                        if prog_t is None:
                            prog_t = compile_template(action.payload)
                        colmap: Dict[str, Any] = {}
                        for nm, col in zip(names, colvals):
                            colmap[nm] = col
                        queries = prog_t.render_rows(colmap, n)
                    else:
                        queries = [
                            _json.dumps(d, default=str)
                            for d in rows_as_dicts(names, colvals, n)
                        ]
                    worker.enqueue_batch(queries)
                rule.actions_success += n
                mloc["actions.success"] += n
                mloc["actions.batched"] += n
            except Exception as exc:
                rule.actions_failed += n
                mloc["actions.failed"] += n
                log.warning(
                    "rule %s batched action %s failed: %s",
                    rule.rule_id,
                    getattr(action, "kind", action),
                    exc,
                )

    def _run_firings(
        self,
        rule: Rule,
        firings: Iterable[Tuple[Dict[str, Any], Message]],
        mloc: Optional[Counter] = None,
    ) -> None:
        """The rule's actions over ``firings`` (``(selected, msg)``
        each), firing-major: a firing's actions run in their order on
        its one ``selected`` dict, and an action that raises fails
        itself alone.  The kind of each action is resolved once here,
        not once a firing."""
        runs = [
            (a, a.fn if isinstance(a, FunctionAction)
             else functools.partial(self._run_action, a))
            for a in rule.actions
        ]
        ok = failed = 0
        for selected, msg in firings:
            for action, run in runs:
                try:
                    run(selected, msg)
                    ok += 1
                except Exception as exc:
                    failed += 1
                    log.warning(
                        "rule %s action %s failed: %s",
                        rule.rule_id,
                        getattr(action, "kind", action),
                        exc,
                    )
        rule.actions_success += ok
        rule.actions_failed += failed
        done = {"actions.success": ok, "actions.failed": failed}
        if mloc is not None:
            mloc.update(done)
        elif self.broker is not None:
            self.broker.metrics.inc_bulk(done)

    def _run_action(
        self, action: Action, selected: Dict[str, Any], msg: Message
    ) -> None:
        if isinstance(action, RepublishAction):
            depth = int(msg.headers.get("republish_depth", 0))
            if depth >= MAX_REPUBLISH_DEPTH:
                raise RuntimeError("republish depth cap hit (rule loop?)")
            tprog = getattr(action, "_topic_prog", None)
            if tprog is None:
                tprog = compile_template(action.topic)
            pprog = getattr(action, "_payload_prog", None)
            if pprog is None:
                pprog = compile_template(action.payload)
            out = Message(
                topic=tprog.render(selected),
                payload=pprog.render(selected).encode(),
                qos=action.qos,
                retain=action.retain,
                from_client=msg.from_client,
                from_username=msg.from_username,
                headers={"republish_depth": depth + 1},
            )
            if self.broker is None:
                raise RuntimeError("republish without a broker")
            self.broker.publish(out)
        elif isinstance(action, ConsoleAction):
            log.info("rule output: %s", selected)
        elif isinstance(action, FunctionAction):
            action.fn(selected, msg)
        elif isinstance(action, AggregateAction):
            action.aggregator.push([selected])
        elif isinstance(action, SinkAction):
            if self.broker is None:
                raise RuntimeError("sink action without a broker")
            worker = self.broker.resources.get(action.resource_id)
            if worker is None:
                raise RuntimeError(
                    f"unknown resource {action.resource_id!r}"
                )
            if action.payload is not None:
                pprog = getattr(action, "_payload_prog", None)
                if pprog is None:
                    pprog = compile_template(action.payload)
                query: Any = pprog.render(selected)
            else:
                query = _json.dumps(selected, default=str)
            worker.enqueue(query)
        else:
            raise RuntimeError(f"unknown action {action!r}")

    def info(self) -> List[Dict[str, Any]]:
        return [
            {
                "id": r.rule_id,
                "sql": r.sql,
                "enabled": r.enabled,
                "description": r.description,
                **r.metrics(),
            }
            for r in self.rules.values()
        ]

    def stats(self) -> Dict[str, Any]:
        """The rule-eval gauge surface (`MatchEngine.stats()`-form):
        lowered-vs-fallback registry split, path window counts, the
        engine's per-cell cost EWMAs and breaker state — exposed
        through ``/metrics``, ``GET /api/v5/rules`` and $SYS."""
        stack = self._stacked()
        out: Dict[str, Any] = {
            "rules": len(self.rules),
            "lowered": stack.n_lowered,
            "program_rows": stack.n_rules,  # after program dedup
            "fallback": len(stack.fallback),
            "matrix_enabled": self._matrix_enabled,
            "matrix_windows": self._stats["matrix_windows"],
            "scalar_windows": self._stats["scalar_windows"],
            "fallback_rule_evals": self._stats["fallback_rule_evals"],
            # output half (PR 20): lowered SELECT registry split, the
            # per-lane row counts and cost EWMAs, breaker state
            "select_lowered": len(self._select_progs()),
            "select_batched_rows": self._stats["select_batched_rows"],
            "select_scalar_rows": self._stats["select_scalar_rows"],
            "select_ewma_off": self._stats["select_ewma_off"],
            "select_batched_us_ewma": self._sel_us_b,
            "select_scalar_us_ewma": self._sel_us_s,
            "select_batch_disabled": self._sel_batch_off,
        }
        if self.broker is not None:
            eng = self.broker.router.engine
            out["host_windows"] = eng._rul_stats["host_windows"]
            out["dev_windows"] = eng._rul_stats["dev_windows"]
            out["dev_errors"] = eng._rul_stats["dev_errors"]
            out["host_us_ewma"] = eng._rul_host_us
            out["dev_us_ewma"] = eng._rul_dev_us
            out["breaker_open"] = eng.breaker_open
        return out
