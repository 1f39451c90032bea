"""Small asyncio helpers shared across the broker's lifecycles."""

from __future__ import annotations

import asyncio
from typing import Callable, List


async def cancel_and_wait(task: asyncio.Task, poll: float = 0.5) -> None:
    """Cancel `task` and wait until it actually ends, RE-cancelling as
    needed: a cancel that lands exactly as an inner ``wait_for``'s
    future resolves is swallowed (bpo-37658 — wait_for returns the
    result instead of raising), the task loops on, and a single
    ``cancel(); await task`` would hang the caller's shutdown forever.
    The task's terminal exception (CancelledError or its own crash) is
    absorbed — this is a shutdown path."""
    while not task.done():
        task.cancel()
        await asyncio.wait([task], timeout=poll)
    try:
        await task
    except BaseException:
        pass


class Gate(asyncio.Event):
    """An `asyncio.Event` that a callback can wait for as a coroutine
    does: ``call(fn)`` runs ``fn()`` inside the next `set` (at once
    while set).  For a waiter with no coroutine to park, such as a
    transport's ``data_received``."""

    def __init__(self) -> None:
        super().__init__()
        self._calls: List[Callable[[], None]] = []

    def call(self, fn: Callable[[], None]) -> None:
        if self.is_set():
            fn()
        else:
            self._calls.append(fn)

    def set(self) -> None:
        super().set()
        calls, self._calls = self._calls, []
        for fn in calls:
            fn()
