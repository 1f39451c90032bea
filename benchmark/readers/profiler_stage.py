"""The program's own stage clocks (`Profiler` stage laps, host clock),
over every dispatch window of the measured window.

``us_per_msg``: the stages' seconds summed over all windows, divided by
the messages those windows carried.  ``p<q>_ms``: that percentile of
one window's time in the stages."""

import numpy as np


def read(run, stages, statistic="us_per_msg"):
    per_window = [
        sum(r["stages_us"].get(s, 0.0) for s in stages)
        for r in run["ring"] if any(s in r["stages_us"] for s in stages)
    ]
    if not per_window:
        return None
    if statistic == "us_per_msg":
        msgs = sum(r["n_msgs"] for r in run["ring"])
        return sum(per_window) / msgs if msgs else None
    q = float(statistic.split("_")[0].lstrip("p"))
    return float(np.percentile(per_window, q)) / 1e3
