"""What the `jax.profiler` trace of the traced sub-window shows, as
`trace_reduce.py` reduced it.  Nothing without a trace.

``idle_pct``: 1 - device busy time / traced window.
``kernel_us_per_window``: device time of the XLA modules named in
``kernels`` / dispatch windows the program opened meanwhile.
``roofline_pct``: the least time the chip could take for the match work
of the messages of those windows (`kernel_work.match_window`, peaks
from `peaks.json`) / that device time.
"""

import kernel_work


def _windows(run):
    lo, hi = run["trace"]["window_wall"]
    return [r for r in run["ring"] if lo <= r["at"] < hi]


def read(run, reduction, kernels=()):
    tr = run.get("trace")
    if not tr:
        return None
    if reduction == "idle_pct":
        return 100.0 * (1.0 - tr["busy_s"] / tr["window_s"])
    secs = sum(tr["modules"][k]["s"] for k in kernels if k in tr["modules"])
    wins = _windows(run)
    if not secs or not wins:
        return None
    if reduction == "kernel_us_per_window":
        return secs * 1e6 / len(wins)
    if reduction == "roofline_pct":
        shapes = run["shapes"]
        work = kernel_work.match_window(
            sum(r["n_msgs"] for r in wins), shapes["f_width"],
            shapes["kernel_levels"], shapes["matches_per_row"],
        )
        least, bound = kernel_work.least_seconds(work, run["peak"])
        run.setdefault("notes", {})["match_kernel_bound"] = bound
        return 100.0 * least / secs
    raise ValueError(f"unknown reduction {reduction!r}")
