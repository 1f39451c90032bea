"""Window column extraction for the stacked rule-matrix program.

`WindowColumns` decodes each window message ONCE into parallel numpy
planes over the union of var paths the lowerable rules' WHERE clauses
reference (predicate.StackedRules.paths): a float64 numeric lane, a
per-window RANK-interned string lane, a lookup-error lane and a
presence lane per path.  `ops.match_kernel.rules_eval_host` /
`rules_eval_batch` then evaluate the whole registry against these
planes as one rules x window boolean matrix.

String interning rides one per-window dictionary (the string-dict
idiom `PredicateProgram.extract_columns` introduced), but assigns
SORTED ranks instead of first-seen ids: rank order == lexicographic
order, so the kernel's ordering comparisons cover interpreter string
ordering (`topic > clientid`) as well as equality.  The dictionary is
seeded with the registry's string-literal table, so literal operands
resolve to per-window ranks in one vectorized lookup
(``lit_ranks``).  Booleans take reserved ids OUTSIDE the orderable
rank space (-2 true / -3 false): equality-comparable, never
string-ordered — exactly the interpreter's Erlang-term semantics.

Non-scalar JSON values (dicts/lists) intern by a canonical encoding
under a NUL-prefixed namespace (NUL cannot occur in MQTT UTF-8
strings), so ``payload.a = payload.b`` over equal objects matches the
interpreter's term equality.

The planes cover the WHERE stack's paths and nothing else: a lowered
SELECT reads its values for the rows that fired, from the messages
(`select.materialize_rows`), never from a plane.  Both go through one
`runtime.WindowEnvs`, which holds each message's JSON decode once a
window; the extractor builds no `LazyEnv` for a path it can read from
the message or the decode, so a message that only the matrix reads
leaves none behind.
"""

from __future__ import annotations

from typing import Any, Dict, List, Optional, Sequence, Tuple

import numpy as np

from ..message import Message
from .runtime import (
    LOOKUP_ERROR, WindowEnvs, _env_field, lookup_var, read_of, walk,
)

# reserved string-lane ids: bools are equality-comparable but must
# never participate in rank (string) ordering
SID_NONE = -1
SID_TRUE = -2
SID_FALSE = -3
# non-scalar terms encode as -4 - rank: equality-comparable through
# the shared dictionary, excluded (negative) from rank ordering
SID_TERM_BASE = -4


def _canon(v: Any) -> str:
    """Canonical encoding for non-scalar JSON values such that
    encodings are equal iff Python ``==`` holds (numbers normalize
    through float, like Python's cross-type numeric equality —
    including bools, since the interpreter's container equality is
    plain ``==`` where ``True == 1``)."""
    if isinstance(v, (int, float)):  # bool is an int: True == 1
        return "n" + repr(float(v))
    if isinstance(v, str):
        return "s" + v
    if v is None:
        return "z"
    if isinstance(v, list):
        return "[" + ",".join(_canon(x) for x in v) + "]"
    if isinstance(v, dict):
        return (
            "{"
            + ",".join(f"{k}:{_canon(v[k])}" for k in sorted(v))
            + "}"
        )
    return "?" + repr(v)


class WindowColumns:
    """One window's shared column planes: ``num``/``sid``/``err``/
    ``prs`` are ``[P, W]`` over the WHERE stack's paths."""

    __slots__ = (
        "n", "paths", "num", "sid", "err", "prs", "lit_ranks",
        "n_strings", "has_nan_value",
    )

    def __init__(
        self,
        msgs: Sequence[Message],
        paths: Sequence[Tuple[str, ...]],
        lit_strings: Sequence[str],
        envs: Optional[WindowEnvs] = None,
    ) -> None:
        n = len(msgs)
        n_paths = len(paths)
        self.n = n
        self.paths = tuple(paths)
        if envs is None:
            envs = WindowEnvs(msgs)
        self.has_nan_value = False
        # one Python list a plane, one array build each at the end
        # (a numpy scalar store a cell costs three list stores)
        nan = float("nan")
        num = [[nan] * n for _ in range(n_paths)]
        sid = [[SID_NONE] * n for _ in range(n_paths)]
        err = [[False] * n for _ in range(n_paths)]
        prs = [[False] * n for _ in range(n_paths)]
        # (plane, msg, string, is_term) cells holding a string-interned
        # value, resolved after the scan once the window's full
        # dictionary is known
        pending: List[Tuple[int, int, str, bool]] = []
        # where each path is read: below the payload from the window's
        # one decode a message, a field of the message from the
        # message, anything else through the generic env lookup
        by_read: Dict[str, List[Tuple[int, Any]]] = {
            "json": [], "msg": [], "env": [],
        }
        for p, path in enumerate(paths):
            how, arg = read_of(path)
            by_read[how].append((p, arg))
        pay_paths = by_read["json"]
        msg_paths = by_read["msg"]
        env_paths = by_read["env"]

        def classify(p: int, i: int, v: Any) -> None:
            if isinstance(v, bool):
                sid[p][i] = SID_TRUE if v else SID_FALSE
            elif isinstance(v, (int, float)):
                if v != v:
                    # a LITERAL NaN payload value (json.loads accepts
                    # NaN) would alias the not-a-number sentinel; the
                    # caller degrades this window to the interpreter
                    self.has_nan_value = True
                num[p][i] = v
            elif isinstance(v, str):
                pending.append((p, i, str(v), False))
            elif v is not None:
                # non-scalar term: canonical id, equality-only
                pending.append((p, i, "\x00j" + _canon(v), True))
            else:
                return
            prs[p][i] = True

        decoded = envs.decoded
        for i in range(n):
            if pay_paths:
                data = decoded(i)
                for p, rest in pay_paths:
                    v = walk(data, rest)
                    if v is LOOKUP_ERROR:
                        err[p][i] = True
                    else:
                        classify(p, i, v)
            for p, key in msg_paths:
                classify(p, i, _env_field(msgs[i], key))
            for p, path in env_paths:
                try:
                    v = lookup_var(envs.env(i), path)
                except Exception:
                    err[p][i] = True
                    continue
                classify(p, i, v)
        # rank interning: literals seed the dictionary so every
        # literal operand resolves even when absent from the window
        strings = set(lit_strings)
        for _, _, s, _t in pending:
            strings.add(s)
        rank = {s: r for r, s in enumerate(sorted(strings))}
        self.n_strings = len(rank)
        for p, i, s, term in pending:
            sid[p][i] = SID_TERM_BASE - rank[s] if term else rank[s]
        self.num = np.array(num, np.float64).reshape(n_paths, n)
        self.sid = np.array(sid, np.int32).reshape(n_paths, n)
        self.err = np.array(err, bool).reshape(n_paths, n)
        self.prs = np.array(prs, bool).reshape(n_paths, n)
        self.lit_ranks = np.fromiter(
            (rank[s] for s in lit_strings), np.int32, len(lit_strings)
        )

    def f32_safe(self) -> bool:
        """True when every numeric cell round-trips float32 — the
        device kernel computes in f32 (TPU-native), so a window
        carrying f32-unsafe values (millisecond timestamps are the
        canonical offender) stays on the float64 host twin, exactly
        the `PredicateProgram._f32_safe` rule."""
        finite = self.num[np.isfinite(self.num)]
        if finite.size == 0:
            return True
        return bool((finite == finite.astype(np.float32)).all())
