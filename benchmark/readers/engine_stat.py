"""One counter of `MatchEngine.stats()`: its growth over the measured
window."""


def read(run, key):
    return run["engine"].get(key)
