"""ctypes binding for native/sockwriter.cpp: the native sender thread
that does a flush scope's socket writes off the event loop thread.

A flush scope (a dispatch window's ``flush``, the publishers' acks in
``PublishBatcher._uncork_all``) brackets its uncorks with
`SockSender.begin` / `end`; inside it `Connection._send_packets`
appends ``(slot, bytes)`` with `add` in place of ``writer.write`` and
`end` hands the whole batch over in ONE GIL-released call that wakes
the thread once.  The thread does the ``send(2)`` calls, first in first
out, on its own ``dup`` of each descriptor; what a socket would not
take comes back through `on_parked` for the connection's transport,
and an errno through `on_failed`.  The order rule, the back-pressure
and the close are `Connection`'s (broker/connection.py); the native
side's are at the top of the C++ source.

Where the library is absent or unbuildable (``ops/nativelib.py``)
`load` returns None and every connection keeps the transport path;
which connections take the sender is decided from what their socket
is."""

from __future__ import annotations

import ctypes
import logging
import os
import time
from typing import Dict, List, Optional

from . import nativelib

_I32P = ctypes.POINTER(ctypes.c_int32)
_I64P = ctypes.POINTER(ctypes.c_int64)

log = logging.getLogger("emqx_tpu.ops")


def _bind(lib) -> None:
    vp, i32, i64 = ctypes.c_void_p, ctypes.c_int32, ctypes.c_int64
    for name, res, args in (
        ("sw_create", vp, []),
        ("sw_event_fd", ctypes.c_int, [vp]),
        ("sw_open", i32, [vp, ctypes.c_int]),
        ("sw_close", None, [vp, i32]),
        ("sw_submit", i64, [vp, i64, _I32P,
                            ctypes.POINTER(ctypes.c_char_p), _I64P]),
        ("sw_pending", i64, [vp, i32]),
        ("sw_poll", i64, [vp, _I32P, _I32P, _I64P, i64]),
        ("sw_take", i64, [vp, i32, ctypes.c_char_p, i64]),
        ("sw_unpark", None, [vp, i32]),
        ("sw_stats", None, [vp, _I64P]),
        ("sw_stop", None, [vp]),
    ):
        fn = getattr(lib, name)
        fn.restype, fn.argtypes = res, args


def load():
    return nativelib.load("sockwriter", _bind)


class SockSender:
    """One native sender thread and the loop's side of it (every
    method is the event loop thread's)."""

    _POLL_CAP = 256

    def __init__(self, lib, loop, loop_clock=None) -> None:
        self._lib = lib
        self._h = lib.sw_create()
        if not self._h:
            raise OSError("sw_create failed")
        self._loop = loop
        self._lc = loop_clock  # observability.LoopClock, or None
        self._efd = lib.sw_event_fd(self._h)
        self._conns: Dict[int, object] = {}  # slot -> Connection
        self._depth = 0
        self._slots: List[int] = []
        self._datas: List[bytes] = []
        self._ev_slots = (ctypes.c_int32 * self._POLL_CAP)()
        self._ev_errs = (ctypes.c_int32 * self._POLL_CAP)()
        self._ev_lens = (ctypes.c_int64 * self._POLL_CAP)()
        loop.add_reader(self._efd, self._on_event)
        if loop_clock is not None:
            loop_clock.attach_sender(self.clock)

    # ------------------------------------------------------ lifetime

    def stop(self) -> None:
        """Drain and join the thread; it closes what it still owns."""
        h, self._h = self._h, None
        if h is None:
            return
        if self._lc is not None:
            self._lc.attach_sender(None)
        self._loop.remove_reader(self._efd)
        self._conns.clear()
        self._slots, self._datas = [], []
        self._lib.sw_stop(h)

    def open(self, fd: int, conn) -> int:
        """A slot over the thread's own dup of ``fd``, or -1."""
        if self._h is None:
            return -1
        slot = self._lib.sw_open(self._h, fd)
        if slot >= 0:
            self._conns[slot] = conn
        return slot

    def close(self, slot: int) -> None:
        """Queue-order close: nothing more is handed over for the
        slot, what was goes out first."""
        if self._h is None or self._conns.pop(slot, None) is None:
            return
        if slot in self._slots:
            # closed inside a flush scope it had written in: those
            # writes go the way of the cork buffer, and never behind
            # the marker (the slot's next owner would send them)
            keep = [i for i, s in enumerate(self._slots) if s != slot]
            self._slots = [self._slots[i] for i in keep]
            self._datas = [self._datas[i] for i in keep]
        self._lib.sw_close(self._h, slot)

    # ---------------------------------------------------- the scope

    @property
    def in_scope(self) -> bool:
        return self._depth > 0 and self._h is not None

    def begin(self) -> None:
        self._depth += 1

    def end(self) -> None:
        """Close a flush scope; the outermost hands the batch over."""
        self._depth -= 1
        if self._depth == 0 and self._slots:
            self._submit()

    def add(self, slot: int, data: bytes) -> None:
        """One write for the thread: into the open scope's batch, or
        (a lone write following bytes the thread still holds) at
        once."""
        self._slots.append(slot)
        self._datas.append(data)
        if self._depth == 0:
            self._submit()

    def _submit(self) -> None:
        slots, datas = self._slots, self._datas
        self._slots, self._datas = [], []
        if self._h is None:
            return
        lc = self._lc
        t0 = time.perf_counter() if lc is not None else 0.0
        n = len(slots)
        self._lib.sw_submit(
            self._h, n,
            (ctypes.c_int32 * n)(*slots),
            (ctypes.c_char_p * n)(*datas),
            (ctypes.c_int64 * n)(*map(len, datas)),
        )
        if lc is not None:
            lc.egress_submit(t0)

    def pending(self, slot: int) -> int:
        """Bytes handed over for the slot and not yet sent, taken back
        or dropped."""
        if self._h is None:
            return 0
        return self._lib.sw_pending(self._h, slot)

    def unpark(self, slot: int) -> None:
        self._lib.sw_unpark(self._h, slot)

    # ------------------------------------------- what the thread tells

    def _on_event(self) -> None:
        try:
            os.read(self._efd, 8)
        except BlockingIOError:
            pass
        lib, h = self._lib, self._h
        if h is None:
            return
        n = lib.sw_poll(h, self._ev_slots, self._ev_errs, self._ev_lens,
                        self._POLL_CAP)
        for i in range(n):
            slot = self._ev_slots[i]
            conn = self._conns.get(slot)
            if conn is None:
                continue  # closed meanwhile: the marker drops it
            # one connection's fault must not strand the others'
            # parked bytes: the poll has already dropped their flags
            try:
                err = self._ev_errs[i]
                if err:
                    conn.on_sender_failed(err)
                    continue
                buf = ctypes.create_string_buffer(self._ev_lens[i])
                got = lib.sw_take(h, slot, buf, len(buf))
                if got > 0:
                    if self._lc is not None:
                        self._lc.egress_parked += 1
                    conn.on_sender_parked(buf.raw[:got])
            except Exception:
                log.exception("sender event for slot %d failed", slot)

    def stats(self) -> Dict[str, int]:
        """The thread's own counters, read from native atomics."""
        out = (ctypes.c_int64 * 5)()
        if self._h is not None:
            self._lib.sw_stats(self._h, out)
        return {"send_ns": out[0], "sends": out[1], "parks": out[2],
                "queued_bytes": out[3], "slots": out[4]}

    def clock(self):
        """``(seconds inside send(2), send calls)`` so far."""
        st = self.stats()
        return (st["send_ns"] * 1e-9, st["sends"])


def start(loop, loop_clock=None) -> Optional[SockSender]:
    """The process's sender, or None where the library is absent."""
    lib = load()
    if lib is None:
        return None
    try:
        return SockSender(lib, loop, loop_clock)
    except OSError:
        log.exception("native sender did not start; transports serve")
        return None
