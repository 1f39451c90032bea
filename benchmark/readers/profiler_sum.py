"""A sum of ring fields and stage laps of the program's window ring,
less another such sum, over the measured window, per unit of a third.

``plus`` and ``minus`` (optional) are each ``{"fields": [...],
"stages": [...]}``: plain fields of a window's record and names of its
``stages_us`` (laps and sub-stages).  A stage that a window lacks
counts as zero there, as in `profiler_stage`; a field that no window
carries is a program from before it was counted, and the whole reading
is None.  ``per`` names the field the difference is divided by (for a
cost a message: ``n_msgs``) or ``"window_s"``; ``scale`` multiplies the
quotient.  None too where the denominator is zero."""


def total(ring, group):
    """The group's sum over the ring, None where a named field is in
    no record."""
    out = 0.0
    for field in group.get("fields", []):
        values = [r[field] for r in ring if r.get(field) is not None]
        if not values:
            return None
        out += sum(values)
    for stage in group.get("stages", []):
        out += sum(r["stages_us"].get(stage, 0.0) for r in ring)
    return out


def read(run, plus, minus=None, per="n_msgs", scale=1.0):
    ring = run["ring"]
    over, under = total(ring, plus), total(ring, minus or {})
    if over is None or under is None:
        return None
    if per == "window_s":
        by = run["window_s"]
    else:
        by = sum(r.get(per) or 0 for r in ring)
    return scale * (over - under) / by if by else None
