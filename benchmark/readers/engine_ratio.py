"""One counter of `MatchEngine.stats()` over another: their growths over
the measured window, ``key`` over ``per`` (a second counter, or
``"window_s"``, the window's length in seconds), times ``scale``.  A
counter that did not grow reads 0; None where the program has no such
counter (one from before it counted it) or the denominator is zero."""


def read(run, key, per, scale=1.0):
    top = run["engine"].get(key)
    if top is None:
        return None
    under = run["window_s"] if per == "window_s" else run["engine"].get(per)
    return scale * top / under if under else None
