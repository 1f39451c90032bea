"""ctypes binding for native/sockreader.cpp: the native reader thread
that does the plain-TCP connections' socket reads off the event loop
thread.

The thread ``recv``s every armed slot that is readable, once, into one
bounded arena and tells the loop through an eventfd.  The loop's side,
`SockReader._on_event`, takes the whole batch in ONE call that holds
the GIL (``sr_take``: the records and one blob of their bytes) and
hands each read to its connection as a transport would: the bytes to
`Connection.data_received` (its ``_reads``, then the listener's
`ReadTurn`), an end of stream to `Connection.on_reader_eof`, an errno
to `Connection.on_reader_failed`.  A slot is read once and not again
until the loop re-arms it: `_rearm`, queued behind the turn's
`ReadTurn._run`, re-arms in one more call every slot handled that
turn whose connection is neither paused nor closed, so a pause a read
asks for takes effect before the connection's next ``recv``.  The
loop thread makes no system call a read: one eventfd read (inside
``sr_take``) and two calls a wake-up.  The native side's rules (the
``dup``, the queue-order close, the arena's bound) are at the top of
the C++ source.

Where the library is absent or unbuildable (``ops/nativelib.py``)
`load` returns None and every connection reads through its transport;
which connections take the reader is decided from what their socket
is (`Connection.connection_made`)."""

from __future__ import annotations

import ctypes
import logging
from typing import Dict, List, Optional, Tuple

from . import nativelib

_I32P = ctypes.POINTER(ctypes.c_int32)
_I64P = ctypes.POINTER(ctypes.c_int64)

log = logging.getLogger("emqx_tpu.ops")


def _bind(lib) -> None:
    vp, i32, i64 = ctypes.c_void_p, ctypes.c_int32, ctypes.c_int64
    for name, res, args in (
        ("sr_create", vp, []),
        ("sr_event_fd", ctypes.c_int, [vp]),
        ("sr_arena_cap", i64, []),
        ("sr_open", i32, [vp, ctypes.c_int]),
        ("sr_close", None, [vp, i32]),
        ("sr_rearm", None, [vp, i64, _I32P]),
        ("sr_pause", None, [vp, i32]),
        ("sr_resume", None, [vp, i32]),
        ("sr_reading", ctypes.c_int, [vp, i32]),
        ("sr_take", i64, [vp, _I32P, _I64P, _I64P, i64, ctypes.c_char_p,
                          _I64P]),
        ("sr_stats", None, [vp, _I64P]),
        ("sr_stop", None, [vp]),
    ):
        fn = getattr(lib, name)
        fn.restype, fn.argtypes = res, args


def load():
    return nativelib.load("sockreader", _bind)


class SockReader:
    """One native reader thread and the loop's side of it (every
    method is the event loop thread's)."""

    _TAKE_CAP = 4096

    def __init__(self, lib, loop, loop_clock=None) -> None:
        # the calls of the loop's side keep the GIL (none of them waits
        # on anything but a lock the thread holds for a recv at most):
        # a call that let it go would queue behind the executors for it
        # on the way back, the cost the thread is here to take away
        self._lib = ctypes.PyDLL(lib._name)
        _bind(self._lib)
        self._stop_lib = lib
        self._h = self._lib.sr_create()
        if not self._h:
            raise OSError("sr_create failed")
        self._loop = loop
        self._lc = loop_clock  # observability.LoopClock, or None
        self._efd = self._lib.sr_event_fd(self._h)
        self._conns: Dict[int, object] = {}  # slot -> Connection
        self._paused: set = set()  # slots
        self.wakes = 0
        cap = self._TAKE_CAP
        self._slots = (ctypes.c_int32 * cap)()
        self._offs = (ctypes.c_int64 * cap)()
        self._lens = (ctypes.c_int64 * cap)()
        self._blob = ctypes.create_string_buffer(self._lib.sr_arena_cap())
        self._nbytes = ctypes.c_int64()
        loop.add_reader(self._efd, self._on_event)
        if loop_clock is not None:
            loop_clock.attach_reader(self.clock)

    # ------------------------------------------------------ lifetime

    def stop(self) -> None:
        """Join the thread; it closes what it still owns."""
        h, self._h = self._h, None
        if h is None:
            return
        if self._lc is not None:
            self._lc.attach_reader(None)
        self._loop.remove_reader(self._efd)
        self._conns.clear()
        self._paused.clear()
        self._stop_lib.sr_stop(h)

    def open(self, fd: int, conn) -> int:
        """A slot over the thread's own dup of ``fd``, or -1.  The
        thread arms it in queue order: behind the re-arms asked for
        before, so it is not read ahead of an older connection's bytes
        that came first."""
        if self._h is None:
            return -1
        slot = self._lib.sr_open(self._h, fd)
        if slot >= 0:
            self._conns[slot] = conn
        return slot

    def close(self, slot: int) -> None:
        """Queue-order close: no read of the slot reaches the loop
        from here on."""
        if self._h is None or self._conns.pop(slot, None) is None:
            return
        self._paused.discard(slot)
        self._lib.sr_close(self._h, slot)

    def pause(self, slot: int) -> None:
        """No ``recv`` of the slot begins after this returns."""
        if self._h is not None and slot in self._conns:
            self._paused.add(slot)
            self._lib.sr_pause(self._h, slot)

    def resume(self, slot: int) -> None:
        if self._h is not None and slot in self._paused:
            self._paused.discard(slot)
            self._lib.sr_resume(self._h, slot)

    def reading(self, slot: int) -> bool:
        """The slot is open and not paused, as the thread sees it."""
        return self._h is not None and bool(
            self._lib.sr_reading(self._h, slot)
        )

    # -------------------------------------------- the loop's wake-up

    def _on_event(self) -> None:
        """One batch: each read to its connection, in arrival order.
        The turn clock's ``recv`` phase, from here to the hand-off."""
        lc, h = self._lc, self._h
        if h is None:
            return
        if lc is not None:
            lc.recv()
        self.wakes += 1
        n = self._lib.sr_take(h, self._slots, self._offs, self._lens,
                              self._TAKE_CAP, self._blob, self._nbytes)
        if n:
            raw = ctypes.string_at(self._blob, self._nbytes.value)
            conns = self._conns
            taken: List[Tuple[int, object]] = []
            for slot, off, ln in zip(self._slots[:n], self._offs[:n],
                                     self._lens[:n]):
                conn = conns.get(slot)
                if conn is None:
                    continue  # closed meanwhile
                if ln > 0:
                    conn.data_received(raw[off:off + ln])
                    taken.append((slot, conn))
                    continue
                # one connection's end must not cost the others their
                # reads: the batch is off its sockets already
                try:
                    if ln == 0:
                        conn.on_reader_eof()
                    else:
                        conn.on_reader_failed(-ln)
                except Exception:
                    log.exception("reader event for slot %d failed", slot)
            if taken:
                # behind the `ReadTurn._run`s the reads just queued
                self._loop.call_soon(self._rearm, taken)
        if lc is not None:
            lc.mark(lc.TAIL)

    def _rearm(self, taken: List[Tuple[int, object]]) -> None:
        """After the turn's run: read again every slot it handled whose
        connection is neither paused nor closed, in one call."""
        if self._h is None:
            return
        lc = self._lc
        if lc is not None:
            lc.mark(lc.RECV)
        conns, paused = self._conns, self._paused
        slots = [s for s, c in taken
                 if conns.get(s) is c and s not in paused]
        if slots:
            n = len(slots)
            self._lib.sr_rearm(self._h, n, (ctypes.c_int32 * n)(*slots))
        if lc is not None:
            lc.mark(lc.TAIL)

    # ------------------------------------------------------ counters

    def stats(self) -> Dict[str, int]:
        """The thread's own counters, read from native atomics."""
        out = (ctypes.c_int64 * 5)()
        if self._h is not None:
            self._lib.sr_stats(self._h, out)
        return {"recv_ns": out[0], "recvs": out[1], "full_waits": out[2],
                "records": out[3], "slots": out[4]}

    def clock(self) -> Tuple[float, int, int]:
        """``(seconds inside recv(2), recv calls, wake-ups taken)`` so
        far."""
        st = self.stats()
        return (st["recv_ns"] * 1e-9, st["recvs"], self.wakes)


def start(loop, loop_clock=None) -> Optional[SockReader]:
    """The process's reader, or None where the library is absent."""
    lib = load()
    if lib is None:
        return None
    try:
        return SockReader(lib, loop, loop_clock)
    except OSError:
        log.exception("native reader did not start; transports read")
        return None
