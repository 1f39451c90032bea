"""A field of the program's window ring summed over the measured
window, per unit of another: ``per`` names a second ring field (for a
cost a message: ``n_msgs``) or ``"window_s"``, the window's length in
seconds; ``scale`` multiplies the quotient (1e-4 turns microseconds a
second into per cent).  None where the ring lacks the field (a program
from before it counted it) or the denominator is zero."""


def read(run, field, per, scale=1.0):
    values = [r[field] for r in run["ring"] if r.get(field) is not None]
    if not values:
        return None
    if per == "window_s":
        under = run["window_s"]
    else:
        under = sum(r.get(per) or 0 for r in run["ring"])
    return scale * sum(values) / under if under else None
