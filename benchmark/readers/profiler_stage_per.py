"""The program's stage laps (`profiler_stage`'s sum over every dispatch
window of the measured window) per unit of a field of the window ring:
``per`` names the field (``n_clients``: the client runs a window
dispatched, so ``deliver`` + ``flush`` over it is the cost of one run).
None where no window has the stages, or the ring lacks the field or it
sums to zero."""


def read(run, stages, per):
    laps = sum(r["stages_us"].get(s, 0.0) for r in run["ring"] for s in stages)
    under = sum(r.get(per) or 0 for r in run["ring"])
    return laps / under if laps and under else None
