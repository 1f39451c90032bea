"""Bytes and operations the window decide step needs, from its shapes
alone, whatever implements it (`kernel_work.py`'s rule, for the kernel
that `kernel_work.py` does not have).  The roofline share is this work
over the chip's peak (`kernel_work.least_seconds`), divided by the time
the trace shows for XLA module ``jit_decide_batch``.
"""


def decide_window(rows: int, messages: int) -> dict:
    """The packed decision column of ``rows`` deliveries of a window of
    ``messages`` publishes.

    A row reads its three int32 indices (subscription row, subscriber
    row, message index: 12 B), gathers the subscription's four 1 B
    attributes (QoS, no-local, retain-as-published, subscription id
    present) and its message's QoS, retain flag and publisher row
    (1 + 1 + 4 B), and writes 1 B.  The message columns (6 B a message)
    come in once.  Real rows, not the bucket the program pads them to:
    padding is the program's choice, and it lowers the share.

    Operations: two compares, a min, a max, a shift, three selects and
    four ors a row; 12 is generous, and bytes still bound the kernel."""
    return {
        "bytes": rows * (12 + 4 + 6 + 1) + messages * 6,
        "ops": rows * 12,
    }
