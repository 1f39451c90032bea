"""What the load generators' sockets and the harness's own clock
measured (``setup_s`` is the harness's: process start to the first
message of the window): ``field`` of the run's load-generator record.  A field that holds one
sample a message (latencies, lateness) takes a ``statistic``."""

import numpy as np


def read(run, field, statistic=None):
    value = run["loadgen"].get(field)
    if value is None:
        return None
    if statistic is None:
        return value
    if len(value) == 0:
        return None
    if statistic == "mean":
        return float(np.mean(value))
    return float(np.percentile(value, float(statistic.lstrip("p"))))
