"""The device's decide step in the `jax.profiler` trace of the traced
sub-window, as `trace_reduce.py` reduced it: the XLA module
``jit_decide_batch`` (`ops/match_kernel.decide_batch`, one run a
dispatch window that delivers).  Nothing without a trace, and nothing
where the trace holds no such module (the host decided, or no window
delivered).

``kernel_us_per_window``: that module's device time / dispatch windows
the program opened inside the traced window (the ring's records whose
``at`` lies in it).
``roofline_pct``: the least time the chip could take for the decisions
of those windows (`kernel_work_decide.decide_window` over the ring's
``n_deliveries`` and ``n_msgs``, peaks from `peaks.json`) / that device
time.  Real deliveries, not the padded bucket the kernel ran.
"""

import kernel_work
import kernel_work_decide

MODULE = "jit_decide_batch"


def read(run, reduction):
    tr = run.get("trace")
    if not tr:
        return None
    secs = tr["modules"].get(MODULE, {}).get("s")
    lo, hi = tr["window_wall"]
    wins = [r for r in run["ring"] if lo <= r["at"] < hi]
    if not secs or not wins:
        return None
    if reduction == "kernel_us_per_window":
        return secs * 1e6 / len(wins)
    if reduction == "roofline_pct":
        rows = sum(r.get("n_deliveries") or 0 for r in wins)
        if not rows:
            return None
        work = kernel_work_decide.decide_window(
            rows, sum(r["n_msgs"] for r in wins)
        )
        least, bound = kernel_work.least_seconds(work, run["peak"])
        run.setdefault("notes", {})["decide_kernel_bound"] = bound
        return 100.0 * least / secs
    raise ValueError(f"unknown reduction {reduction!r}")
