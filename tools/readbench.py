"""What one socket read costs the thread that reads it: asyncio's
selector transport (a ``_read_ready`` handle, a ``recv`` and a
``data_received`` a readable socket, on the event loop thread) against
the native reader thread (``ops/sockreader.py``: the ``recv``s on its
own thread, the loop takes a batch a wake-up and re-arms it in one
more call).

A writer child process floods N loopback connections with small
writes, round robin; the event loop counts what it is handed.  For
each side the loop thread's CPU and wall time over the measured
window, a read, and (native) the reader thread's own time inside
``recv`` a read.  Nothing of the broker runs: the reads are counted,
not parsed.

    python tools/readbench.py [--conns 1000] [--seconds 5] [--size 64]
                              [--mode both|asyncio|native]

Prints one JSON line a side.  ``RLIMIT_NOFILE`` must allow about three
descriptors a connection (the writer's, the loop's and the reader
thread's ``dup``)."""

from __future__ import annotations

import argparse
import asyncio
import json
import os
import resource
import socket
import subprocess
import sys
import threading
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))

WARM_S = 1.0


def writer(port: int, conns: int, seconds: float, size: int) -> None:
    """The child: connect, then write ``size`` bytes a socket, round
    robin, until the time is up."""
    socks = [socket.create_connection(("127.0.0.1", port))
             for _ in range(conns)]
    for s in socks:
        s.setsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)
    payload = b"p" * size
    sys.stdout.write("ready\n")
    sys.stdout.flush()
    end = time.monotonic() + seconds
    while time.monotonic() < end:
        for s in socks:
            s.sendall(payload)
    for s in socks:
        s.close()


class Counted(asyncio.Protocol):
    """A connection that counts its reads; on the native side its
    transport is paused for good and the reader thread reads it."""

    reads = 0
    rdr = None

    def connection_made(self, transport):
        self.transport = transport
        if Counted.rdr is not None:
            fd = transport.get_extra_info("socket").fileno()
            self.slot = Counted.rdr.open(fd, self)
            if self.slot >= 0:
                transport.pause_reading()

    def data_received(self, data):
        Counted.reads += 1

    def on_reader_eof(self):
        self.transport.close()

    def on_reader_failed(self, err):
        self.transport.abort()

    def connection_lost(self, exc):
        if Counted.rdr is not None and self.slot >= 0:
            Counted.rdr.close(self.slot)


async def side(mode: str, conns: int, seconds: float, size: int) -> dict:
    from emqx_tpu.ops import sockreader

    loop = asyncio.get_running_loop()
    Counted.reads = 0
    Counted.rdr = sockreader.start(loop) if mode == "native" else None
    if mode == "native" and Counted.rdr is None:
        return {"mode": mode, "error": "native sockreader not built"}
    server = await loop.create_server(Counted, "127.0.0.1", 0,
                                      backlog=conns)
    port = server.sockets[0].getsockname()[1]
    child = await asyncio.create_subprocess_exec(
        sys.executable, os.path.abspath(__file__), "--writer", str(port),
        "--conns", str(conns), "--seconds", str(seconds + 2 * WARM_S),
        "--size", str(size), stdout=asyncio.subprocess.PIPE,
    )
    await child.stdout.readline()  # connected
    await asyncio.sleep(WARM_S)
    turns = [0]
    sel = loop._selector
    inner = sel.select

    def counted(timeout=None):
        turns[0] += 1
        return inner(timeout)

    sel.select = counted

    def reading():
        st = Counted.rdr.stats() if Counted.rdr is not None else {}
        return (time.perf_counter(), time.thread_time(), Counted.reads,
                turns[0], st.get("recv_ns", 0), st.get("recvs", 0),
                Counted.rdr.wakes if Counted.rdr is not None else 0)

    t0 = reading()
    await asyncio.sleep(seconds)
    t1 = reading()
    del sel.select
    await child.wait()
    server.close()
    if Counted.rdr is not None:
        Counted.rdr.stop()
        Counted.rdr = None
    wall, cpu, reads, polls, recv_ns, recvs, wakes = (
        b - a for a, b in zip(t0, t1)
    )
    out = {
        "mode": mode, "conns": conns, "size": size, "reads": reads,
        "reads_per_s": reads / wall,
        "loop_cpu_us_per_read": cpu * 1e6 / reads if reads else None,
        "loop_wall_us_per_read": wall * 1e6 / reads if reads else None,
        "loop_cpu_pct": 100.0 * cpu / wall,
        "reads_per_poll": reads / polls if polls else None,
    }
    if mode == "native":
        out["reader_recv_us_per_read"] = (
            recv_ns * 1e-3 / recvs if recvs else None)
        out["reader_busy_pct"] = recv_ns * 1e-7 / wall
        out["reads_per_wake"] = reads / wakes if wakes else None
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--conns", type=int, default=1000)
    ap.add_argument("--seconds", type=float, default=5.0)
    ap.add_argument("--size", type=int, default=64)
    ap.add_argument("--mode", choices=("both", "asyncio", "native"),
                    default="both")
    ap.add_argument("--writer", type=int, default=0, help=argparse.SUPPRESS)
    args = ap.parse_args(argv)
    if args.writer:
        writer(args.writer, args.conns, args.seconds, args.size)
        return 0
    soft, hard = resource.getrlimit(resource.RLIMIT_NOFILE)
    want = 3 * args.conns + 64
    if soft < want:
        resource.setrlimit(resource.RLIMIT_NOFILE, (min(want, hard), hard))
    modes = ("asyncio", "native") if args.mode == "both" else (args.mode,)
    for mode in modes:
        res = asyncio.run(side(mode, args.conns, args.seconds, args.size))
        print(json.dumps(res), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
