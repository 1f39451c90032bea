"""ctypes binding for native/dispatchasm.cpp: GIL-released per-run
PUBLISH assembly for the dispatch fan-out.

One call splices a whole client run — head span, 2-byte packet-id
patch, tail span per delivery — out of the window encoder's arena into
one contiguous wire buffer (the connection's corked write), replacing
the per-delivery Python join + ``Packet`` object churn that dominated
the ``deliver`` stage p99 at high fan-out.  A missing or unbuildable
``.so`` (``ops/nativelib.py``) degrades to the pure-Python
per-delivery loop in ``Session.deliver``, which stays bit-identical
(property-tested in tests/test_dispatch_native.py)."""

from __future__ import annotations

import ctypes

from . import nativelib

_I64P = ctypes.POINTER(ctypes.c_int64)
_U8P = ctypes.POINTER(ctypes.c_uint8)


def _bind(lib) -> None:
    lib.da_assemble_run.restype = ctypes.c_int64
    lib.da_assemble_run.argtypes = [
        _U8P,                    # arena
        _I64P, _I64P,            # head_off, head_len
        _I64P, _I64P,            # tail_off, tail_len
        _I64P, _I64P,            # body idx, pid (-1 = no pid)
        ctypes.c_int64,          # n deliveries
        _U8P,                    # out
    ]
    lib.da_assemble_window.restype = ctypes.c_int64
    lib.da_assemble_window.argtypes = [
        _U8P,                    # arena
        _I64P, _I64P,            # head_off, head_len
        _I64P, _I64P,            # tail_off, tail_len
        _I64P, _I64P,            # body idx, pid (-1 = no pid)
        _I64P, _I64P,            # run_start, run_out_off
        ctypes.c_int64,          # n runs
        ctypes.c_int64,          # n deliveries total
        _U8P,                    # out
    ]


def load():
    return nativelib.load("dispatchasm", _bind)


def assemble_run(lib, views, body, pid_ptr, n: int,
                 out: bytearray) -> int:
    """Splice one run into ``out`` (sized by the caller).  ``views``
    is the encoder's cached ``native_views()`` tuple (arena export +
    span-table pointers); ``body`` is a contiguous int64 numpy column
    and ``pid_ptr`` an already-converted int64 pointer (QoS0 runs
    reuse one cached all--1 column); ``out`` is wrapped in place
    (``from_buffer`` pins it only for the call)."""
    arena, ho, hl, to, tl = views
    return lib.da_assemble_run(
        arena, ho, hl, to, tl,
        body.ctypes.data_as(_I64P), pid_ptr,
        n,
        (ctypes.c_uint8 * len(out)).from_buffer(out),
    )


def assemble_window(lib, views, body, pid, run_start, run_out_off,
                    n_runs: int, n_total: int, out: bytearray) -> int:
    """Splice one whole dispatch window — every client's run — into
    ``out`` with a single GIL-released call.  ``body``/``pid`` are the
    window-wide int64 delivery columns; ``run_start`` indexes each
    run's first delivery and ``run_out_off`` its precomputed byte
    offset into ``out`` (the splice plan).  Returns bytes written, or
    a NEGATIVE -(j+1) when run ``j``'s bytes would not land at its
    planned offset (a span-table mismatch the caller must treat as a
    failed window, never as wire)."""
    arena, ho, hl, to, tl = views
    return lib.da_assemble_window(
        arena, ho, hl, to, tl,
        body.ctypes.data_as(_I64P), pid.ctypes.data_as(_I64P),
        run_start.ctypes.data_as(_I64P),
        run_out_off.ctypes.data_as(_I64P),
        n_runs, n_total,
        (ctypes.c_uint8 * len(out)).from_buffer(out),
    )
