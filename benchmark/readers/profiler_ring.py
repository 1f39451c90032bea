"""A field of the program's window ring (`Profiler.windows()`: one
record a dispatch window of the measured window), reduced by
``statistic``: mean, max, sum or p<q>."""

import numpy as np


def read(run, field, statistic="mean"):
    values = [r[field] for r in run["ring"] if r.get(field) is not None]
    if not values:
        return None
    if statistic in ("mean", "max", "sum"):
        return float(getattr(np, statistic)(values))
    return float(np.percentile(values, float(statistic.lstrip("p"))))
