"""Bytes and operations a kernel needs, from its shapes alone, whatever
implements it.  A later PR that rewrites a kernel is held to the same
work: the roofline share is this work over the chip's peak, divided by
the time the trace shows.
"""


def match_window(rows: int, f_width: int, kernel_levels: int,
                 matches_per_row: float = 0.0) -> dict:
    """The wildcard match of ``rows`` topics against one automaton.

    Per level each of the ``f_width`` frontier lanes of a topic needs one
    64 B fingerprint-bucket gather (the literal edge) and one 32 B
    node-row gather (the ``+`` edge, terminal flags and the incoming
    edge it is verified against): `ops/match_kernel.py:15-20`.  Added to
    that, the token input (4 B a level, a length and a ``$`` flag a
    topic) and the compact output (a count a topic, 4 B a match).

    Operations: a bucket compare is 8 fingerprints wide, with a hash and
    a verification of a handful of integer operations beside it; 40 a
    lane and level is generous, and bytes still bound the kernel."""
    lanes = rows * f_width * kernel_levels
    return {
        "bytes": lanes * (64 + 32) + rows * (4 * kernel_levels + 8)
        + rows * (4 + 4 * matches_per_row),
        "ops": lanes * 40,
    }


def least_seconds(work: dict, peak: dict) -> tuple:
    """``(seconds, bound)``: the least time the chip could take, and
    which peak sets it (``hbm`` or ``ops``)."""
    by_bytes = work["bytes"] / (peak["hbm_GBps"] * 1e9)
    # integer work: the vector unit, far under the bf16 matrix peak; the
    # matrix peak is the published figure, and it only loosens the bound
    by_ops = work["ops"] / (peak["bf16_TFLOPs"] * 1e12)
    return (by_bytes, "hbm") if by_bytes >= by_ops else (by_ops, "ops")
