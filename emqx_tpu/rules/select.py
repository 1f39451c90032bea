"""Batched SELECT lowering + precompiled ``${a.b}`` templates.

The output half of the rule matrix (the WHERE half lives in
`predicate.py`/`columns.py`): a lowerable SELECT list — field
projections, literals, arithmetic, ``*`` — compiles ONCE per registry
revision into a `SelectProgram` whose slots name var paths, and
`materialize_rows` reads those paths for the rows that fired, a rule
at a time, from where the interpreter reads them: the message's own
fields and the window's one JSON decode a message
(`runtime.WindowEnvs`).  A message no rule fired on costs the SELECT
lane nothing, and no value passes through a column plane.  Rules
whose SELECT uses nodes the compiler doesn't cover (function calls,
CASE, comparisons) degrade per RULE to the scalar interpreter
(`runtime.eval_select`), which stays the property-tested referee.

Placeholder templates (``${a.b}``, `emqx_placeholder` semantics) get
the same treatment: `compile_template` parses a template ONCE into a
segment program (literal chunks + resolved path tuples) instead of
re-walking the regex and re-splitting every dotted path per message.
`TemplateProgram.render` is the scalar form (bit-identical to the old
`render_template`, fuzz-pinned by tests/test_rules_select.py) and
`render_rows` the column form used by the batched egress.

Value semantics are anchored to the interpreter on purpose:

- projection/star values are what `lookup_var` gives for the path
  (int-ness and nested objects as they are, the payload flattened to
  ``str``); a lookup error or a missing key is ``None``, exactly
  `eval_select`'s catch;
- arithmetic closures call `runtime.arith_op` — the SAME function the
  interpreter calls — so int-ness preservation (``json.dumps(5)`` !=
  ``json.dumps(5.0)``), string ``+`` concat and div-by-zero ->
  ``None`` hold bit-identically;
- expression operands distinguish lookup ERROR (`LOOKUP_ERROR` in
  the slot: raises, field -> ``None``) from missing (operand is
  ``None`` -> arithmetic raises), like `lookup_var`.
"""

from __future__ import annotations

import json
import re
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

from .runtime import (
    LOOKUP_ERROR, EvalError, WindowEnvs, _PayloadStr, _STAR_FIELDS,
    _default_name, _env_field, arith_op, lookup_var, read_of, walk,
)
from .sql import ParsedSql

_PLACEHOLDER = re.compile(r"\$\{([^}]+)\}")

_MISSING = object()


def stringify(v: Any) -> str:
    """Template placeholder value -> text (emqx_placeholder parity;
    the exact `render_template` substitution semantics, shared by the
    scalar and column renderers)."""
    t = type(v)
    if t is str:  # exact-type fast path: the dominant case by far
        return v
    if t is int:
        return str(v)
    if isinstance(v, bool):
        return "true" if v else "false"
    if isinstance(v, bytes):
        return v.decode("utf-8", "replace")
    if isinstance(v, float) and v.is_integer():
        return str(int(v))
    if isinstance(v, (dict, list)):
        return json.dumps(v)
    return str(v)


class TemplateProgram:
    """One parsed ``${a.b}`` template: an alternating sequence of
    literal string chunks and pre-split path tuples."""

    __slots__ = ("template", "parts", "n_slots", "_fmt")

    def __init__(self, template: str) -> None:
        self.template = template
        parts: List[Any] = []
        pos = 0
        n_slots = 0
        for m in _PLACEHOLDER.finditer(template):
            if m.start() > pos:
                parts.append(template[pos:m.start()])
            parts.append(tuple(m.group(1).split(".")))
            n_slots += 1
            pos = m.end()
        if pos < len(template):
            parts.append(template[pos:])
        self.parts = tuple(parts)
        self.n_slots = n_slots
        # %-format twin of ``parts`` (literals escaped): the column
        # renderer substitutes whole ROWS at C speed with one
        # ``fmt % tuple`` per row instead of a per-part join
        self._fmt = "".join(
            p.replace("%", "%%") if p.__class__ is str else "%s"
            for p in parts
        )

    def render(self, data: Dict[str, Any]) -> str:
        """Scalar substitution against one SELECTed row."""
        if not self.n_slots:
            return self.template
        out: List[str] = []
        for part in self.parts:
            if part.__class__ is str:
                out.append(part)
                continue
            cur: Any = data
            for seg in part:
                if isinstance(cur, dict) and seg in cur:
                    cur = cur[seg]
                else:
                    cur = _MISSING
                    break
            out.append(
                "undefined" if cur is _MISSING else stringify(cur)
            )
        return "".join(out)

    def render_rows(
        self, cols: Dict[str, Sequence[Any]], n: int
    ) -> List[str]:
        """Column substitution: one rendered string per row, reading
        each placeholder's head from the SELECTed output columns.
        Bit-identical to calling `render` on each row's dict."""
        if not self.n_slots:
            return [self.template] * n
        vcols: List[List[str]] = []
        for part in self.parts:
            if part.__class__ is str:
                continue
            col = cols.get(part[0], _MISSING)
            if col is _MISSING:
                vcols.append(["undefined"] * n)
            elif len(part) == 1:
                vcols.append([stringify(v) for v in col])
            else:
                rest = part[1:]
                vals: List[str] = []
                for v in col:
                    cur: Any = v
                    for seg in rest:
                        if isinstance(cur, dict) and seg in cur:
                            cur = cur[seg]
                        else:
                            cur = _MISSING
                            break
                    vals.append(
                        "undefined" if cur is _MISSING
                        else stringify(cur)
                    )
                vcols.append(vals)
        fmt = self._fmt
        if len(vcols) == 1:
            return [fmt % (v,) for v in vcols[0]]
        return [fmt % t for t in zip(*vcols)]


# compiled-template cache: action templates are a small fixed set per
# registry, but ad-hoc render_template callers ride the same cache
_TEMPLATE_CACHE: Dict[str, TemplateProgram] = {}
_TEMPLATE_CACHE_CAP = 4096


def compile_template(template: str) -> TemplateProgram:
    prog = _TEMPLATE_CACHE.get(template)
    if prog is None:
        if len(_TEMPLATE_CACHE) >= _TEMPLATE_CACHE_CAP:
            _TEMPLATE_CACHE.clear()
        prog = _TEMPLATE_CACHE[template] = TemplateProgram(template)
    return prog


# ------------------------------------------------------ SELECT lowering


class _Unsupported(Exception):
    pass


_ARITH_SYMS = ("+", "-", "*", "/", "div", "mod")


def _compile_expr(
    expr: tuple, reg: Callable[[Tuple[str, ...]], int]
) -> Callable[[tuple], Any]:
    """AST subtree -> closure over one row's gathered operand values
    (``vals``), indexed by the local path slots ``reg`` hands out.
    Raises `_Unsupported` on nodes outside the lowerable subset
    (calls, CASE, comparisons, IN, NOT)."""
    kind = expr[0]
    if kind == "lit":
        v = expr[1]
        return lambda vals: v
    if kind == "var":
        k = reg(expr[1])

        def var_fn(vals, _k=k):
            v = vals[_k]
            if v is LOOKUP_ERROR:
                # `lookup_var` raised for this row: the interpreter's
                # eval_expr propagates, so the compiled form does too
                raise EvalError("lookup error")
            return v

        return var_fn
    if kind == "neg":
        f = _compile_expr(expr[1], reg)

        def neg_fn(vals, _f=f):
            v = _f(vals)
            if not isinstance(v, (int, float)) or isinstance(v, bool):
                raise EvalError(f"negating non-number {v!r}")
            return -v

        return neg_fn
    if kind == "op" and expr[1] in _ARITH_SYMS:
        sym = expr[1]
        fa = _compile_expr(expr[2], reg)
        fb = _compile_expr(expr[3], reg)
        return lambda vals: arith_op(sym, fa(vals), fb(vals))
    raise _Unsupported(kind)


class SelectProgram:
    """One rule's lowered SELECT list.

    ``fields`` entries are ``(kind, name, arg)``:

    - ``("var", name, slot)`` — projection of local path slot
    - ``("lit", name, value)`` — constant column
    - ``("expr", name, fn)`` — compiled arithmetic closure
    - ``("star", None, ((name, slot), ...))`` — ``*`` expansion over
      the eight `_STAR_FIELDS`

    ``paths`` is the tuple of var paths the program reads; slots index
    into it, and ``reads`` says where each slot's values come from
    (`runtime.read_of`)."""

    __slots__ = ("fields", "paths", "reads")

    def __init__(self, fields: tuple, paths: tuple) -> None:
        self.fields = fields
        self.paths = paths
        self.reads = tuple(read_of(p) for p in paths)


def compile_select(parsed: ParsedSql) -> Optional[SelectProgram]:
    """Lower a SELECT list, or None when any field uses nodes outside
    the compiled subset (the rule then degrades to the interpreter)."""
    paths: List[Tuple[str, ...]] = []
    pix: Dict[Tuple[str, ...], int] = {}

    def reg(path: Tuple[str, ...]) -> int:
        k = pix.get(path)
        if k is None:
            k = pix[path] = len(paths)
            paths.append(path)
        return k

    fields: List[tuple] = []
    try:
        for f in parsed.fields:
            if f.star:
                fields.append((
                    "star", None,
                    tuple((k, reg((k,))) for k in _STAR_FIELDS),
                ))
                continue
            name = f.alias or _default_name(f.expr)
            e = f.expr
            if e[0] == "lit":
                fields.append(("lit", name, e[1]))
            elif e[0] == "var":
                fields.append(("var", name, reg(e[1])))
            else:
                fields.append(("expr", name, _compile_expr(e, reg)))
    except _Unsupported:
        return None
    return SelectProgram(tuple(fields), tuple(paths))


def build_select_stack(
    rules: Sequence[Tuple[str, ParsedSql]],
) -> Dict[str, SelectProgram]:
    """The lowered SELECT program of every rule that has one."""
    progs: Dict[str, SelectProgram] = {}
    for rid, parsed in rules:
        prog = compile_select(parsed)
        if prog is not None:
            progs[rid] = prog
    return progs


def materialize_rows(
    prog: SelectProgram,
    envs: WindowEnvs,
    rows: Sequence[int],
) -> Tuple[List[str], List[List[Any]]]:
    """One rule's SELECT over the window rows it fired on, in one
    pass: read each of the program's paths for ``rows``, then produce
    one output column per SELECT field.  Returns ``(names, columns)``
    aligned with the (star-expanded) field list; a per-row dict built
    as ``dict(zip(names, row))`` is bit-identical to
    `runtime.eval_select` (duplicate names keep first position, last
    value — plain dict-assignment semantics)."""
    msgs = envs.msgs
    n = len(rows)
    datas: Optional[List[Any]] = None
    gv: List[List[Any]] = []  # a slot's values, LOOKUP_ERROR included
    for how, arg in prog.reads:
        if how == "msg":
            gv.append([_env_field(msgs[i], arg) for i in rows])
        elif how == "json":
            if datas is None:
                decoded = envs.decoded
                datas = [decoded(i) for i in rows]
            gv.append([walk(d, arg) for d in datas])
        else:
            col: List[Any] = []
            for i in rows:
                try:
                    v = lookup_var(envs.env(i), arg)
                except Exception:
                    v = LOOKUP_ERROR
                # the payload as a whole reads as its text, exactly
                # eval_select's output conversion
                col.append(str(v) if type(v) is _PayloadStr else v)
            gv.append(col)
    names: List[str] = []
    colvals: List[List[Any]] = []
    vrows = None

    def projected(k: int) -> List[Any]:
        if prog.reads[k][0] == "msg":
            return gv[k]  # a message's field never fails to read
        return [None if v is LOOKUP_ERROR else v for v in gv[k]]

    for kind, name, arg in prog.fields:
        if kind == "star":
            for sname, k in arg:
                names.append(sname)
                colvals.append(projected(k))
        elif kind == "var":
            names.append(name)
            colvals.append(projected(arg))
        elif kind == "lit":
            names.append(name)
            colvals.append([arg] * n)
        else:  # compiled expression
            if vrows is None:  # one transpose, shared by every expr
                vrows = list(zip(*gv)) if gv else [()] * n
            out: List[Any] = []
            for vals in vrows:
                try:
                    out.append(arg(vals))
                except (EvalError, TypeError, ValueError):
                    out.append(None)
            names.append(name)
            colvals.append(out)
    return names, colvals


def rows_as_dicts(
    names: Sequence[str], colvals: Sequence[Sequence[Any]], n: int
) -> List[Dict[str, Any]]:
    """`materialize_rows`'s columns as one fresh dict a row, equal to
    ``dict(zip(names, row))`` (duplicate names keep first position,
    last value), filled a column at a time: a third of the cost of a
    transpose and a ``dict(zip())`` a row."""
    out: List[Dict[str, Any]] = [{} for _ in range(n)]
    for name, col in zip(names, colvals):
        for d, v in zip(out, col):
            d[name] = v
    return out
