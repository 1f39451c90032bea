#!/usr/bin/env python3
"""The benchmark's load generator: a child process of `run.py` that
speaks MQTT 5 over loopback TCP and stamps CLOCK_MONOTONIC, which every
process of one host shares.

It imports neither JAX nor `emqx_tpu`: the few packet types it needs
(CONNECT/CONNACK, SUBSCRIBE/SUBACK, PUBLISH, PUBACK, DISCONNECT) are
written out here, so a change to the program's codec cannot move the
yardstick.  `tests/benchmark` holds it against `emqx_tpu.codec`.

One process is either subscribers or publishers.  The parent writes a
plan (one JSON line) to stdin, then commands, one a line:

    subscribers   window T0 T1   stamp CPU time at both instants
                  count          -> {"count": deliveries so far}
                  stop           -> header line + raw arrays, then exit
    publishers    warm N         closed loop until N publishes in all
                  flood T0 T1    closed loop, ``inflight`` a connection
                  paced T0 T1 [[seq, due], ...]   open loop
                  stop           -> header line + raw arrays, then exit

Replies are one JSON line each; ``stop`` follows its line with the raw
bytes of the arrays it names, in order.
"""

import asyncio
import json
import os
import resource
import sys
import time
from array import array

MQTT_V5 = 5
CONNECT, CONNACK, PUBLISH, PUBACK = 1, 2, 3, 4
SUBSCRIBE, SUBACK, DISCONNECT = 8, 9, 14

now = time.monotonic


# ----------------------------------------------------------------- codec

def varint(n: int) -> bytes:
    out = bytearray()
    while True:
        d, n = n & 127, n >> 7
        out.append(d | 128 if n else d)
        if not n:
            return bytes(out)


def utf8(s: str) -> bytes:
    b = s.encode()
    return len(b).to_bytes(2, "big") + b


def packet(first: int, body: bytes) -> bytes:
    return bytes([first]) + varint(len(body)) + body


def connect(client_id: str, keepalive: int = 0) -> bytes:
    # clean start, no will, no credentials, no properties
    return packet(CONNECT << 4, utf8("MQTT") + bytes([MQTT_V5, 0x02])
                  + keepalive.to_bytes(2, "big") + b"\x00" + utf8(client_id))


def subscribe(packet_id: int, filters, qos: int) -> bytes:
    body = packet_id.to_bytes(2, "big") + b"\x00"
    for flt in filters:
        body += utf8(flt) + bytes([qos])
    return packet(SUBSCRIBE << 4 | 2, body)


def publish_head(topic: str, qos: int, payload_len: int) -> bytes:
    """Everything of a PUBLISH before its packet id: the payload's
    length is fixed, so the head is made once a topic."""
    t = utf8(topic)
    rl = len(t) + (2 if qos else 0) + 1 + payload_len
    return bytes([PUBLISH << 4 | qos << 1]) + varint(rl) + t


def publish(topic: str, qos: int, packet_id: int, payload: bytes) -> bytes:
    return (publish_head(topic, qos, len(payload))
            + (packet_id.to_bytes(2, "big") if qos else b"")
            + b"\x00" + payload)


def puback(packet_id: int) -> bytes:
    return b"\x40\x02" + packet_id.to_bytes(2, "big")


def disconnect() -> bytes:
    return b"\xe0\x00"


def split(buf: bytes):
    """``(packets, rest)``: every whole packet in ``buf`` as
    ``(first_byte, body_start, end)`` offsets, and the unfinished tail."""
    out, i, n = [], 0, len(buf)
    while n - i >= 2:
        j, mult, rl = i + 1, 1, 0
        while True:
            if j >= n:
                return out, buf[i:]
            d = buf[j]
            j += 1
            rl += (d & 127) * mult
            if d < 128:
                break
            mult <<= 7
        if j + rl > n:
            break
        out.append((buf[i], j, j + rl))
        i = j + rl
    return out, buf[i:]


def read_varint(buf: bytes, p: int):
    mult, v = 1, 0
    while True:
        d = buf[p]
        p += 1
        v += (d & 127) * mult
        if d < 128:
            return v, p
        mult <<= 7


def parse_publish(buf: bytes, first: int, p: int, end: int):
    """``(topic, qos, dup, packet_id, payload)`` of a v5 PUBLISH."""
    qos = first >> 1 & 3
    tl = buf[p] << 8 | buf[p + 1]
    topic = buf[p + 2:p + 2 + tl]
    p += 2 + tl
    pid = 0
    if qos:
        pid = buf[p] << 8 | buf[p + 1]
        p += 2
    plen, p = read_varint(buf, p)
    return topic, qos, first >> 3 & 1, pid, buf[p + plen:end]


def parse_suback(buf: bytes, p: int, end: int):
    """``(packet_id, reason_codes)`` of a v5 SUBACK."""
    pid = buf[p] << 8 | buf[p + 1]
    plen, p = read_varint(buf, p + 2)
    return pid, list(buf[p + plen:end])


SEQ_AT, SEQ_W = 7, 10  # traffic.payload_of's fixed-width "seq" field


def payload_of(seq: int) -> bytes:
    # a copy of traffic.payload_of: this file stands alone
    return (
        b'{"seq":%10d,"temp":%2d,"hum":%2d,"dev":"d%d","ok":%s}'
        % (seq, seq * 7 % 50, seq * 13 % 100, seq % 7,
           b"true " if seq % 3 == 0 else b"false")
    )


PAYLOAD_LEN = len(payload_of(0))


# --------------------------------------------------------- subscribers

class Subscriber(asyncio.Protocol):
    def __init__(self, owner, idx, cid, filters, qos):
        self.owner, self.idx = owner, idx
        self.cid, self.filters, self.qos = cid, filters, qos
        self.buf = b""
        self.seqs = array("q")
        self.ts = array("d")
        self.qos_seen = 0      # bit q set: a delivery came at QoS q
        self.dups = 0          # deliveries with the DUP flag
        self.granted = None
        self.transport = None
        self.closed = False

    def connection_made(self, transport):
        self.transport = transport
        transport.write(connect(self.cid))

    def connection_lost(self, exc):
        self.closed = True

    def data_received(self, data):
        t = now()
        buf = self.buf + data if self.buf else data
        pkts, self.buf = split(buf)
        acks = []
        seqs, ts = self.seqs, self.ts
        for first, p, end in pkts:
            kind = first >> 4
            if kind == PUBLISH:
                _, qos, dup, pid, payload = parse_publish(buf, first, p, end)
                if qos:
                    acks.append(puback(pid))
                self.qos_seen |= 1 << qos
                self.dups += dup
                seqs.append(int(payload[SEQ_AT:SEQ_AT + SEQ_W]))
                ts.append(t)
            elif kind == CONNACK:
                if buf[p + 1] != 0:
                    raise RuntimeError(f"{self.cid}: CONNACK {buf[p + 1]}")
                self.transport.write(subscribe(1, self.filters, self.qos))
            elif kind == SUBACK:
                self.granted = parse_suback(buf, p, end)[1]
                self.owner.subscribed()
        if acks:
            self.transport.write(b"".join(acks))


class Subscribers:
    def __init__(self, plan):
        self.plan = plan
        self.conns = []
        self.n_subscribed = 0
        self.all_subscribed = asyncio.Event()
        self.cpu = [0.0, 0.0]

    def subscribed(self):
        self.n_subscribed += 1
        if self.n_subscribed == len(self.plan["conns"]):
            self.all_subscribed.set()

    async def run(self, lines):
        loop = asyncio.get_running_loop()
        port = self.plan["port"]
        for lo in range(0, len(self.plan["conns"]), 64):
            batch = self.plan["conns"][lo:lo + 64]
            made = await asyncio.gather(*(
                loop.create_connection(
                    lambda i=lo + k, c=c: Subscriber(self, i, *c),
                    "127.0.0.1", port,
                ) for k, c in enumerate(batch)
            ))
            self.conns += [proto for _, proto in made]
        await asyncio.wait_for(self.all_subscribed.wait(), 300)
        reply({"ready": True, "granted": [c.granted for c in self.conns]})
        async for cmd in lines:
            if cmd[0] == "window":
                for k in (0, 1):
                    loop.call_at(float(cmd[1 + k]), self.stamp_cpu, k)
            elif cmd[0] == "count":
                reply({"count": sum(len(c.seqs) for c in self.conns)})
            elif cmd[0] == "stop":
                break
        reply({
            "conns": [[len(c.seqs), c.qos_seen, c.dups, int(c.closed)]
                      for c in self.conns],
            "cpu_s": self.cpu[1] - self.cpu[0],
            "arrays": ["seqs:q", "ts:d"],
        })
        out = sys.stdout.buffer
        for c in self.conns:
            out.write(c.seqs.tobytes())
        for c in self.conns:
            out.write(c.ts.tobytes())
        out.flush()
        for c in self.conns:
            if not c.closed:
                c.transport.write(disconnect())
                c.transport.close()

    def stamp_cpu(self, k):
        self.cpu[k] = time.process_time()


# ----------------------------------------------------------- publishers

class Publisher(asyncio.Protocol):
    def __init__(self, owner, conn):
        self.owner, self.conn = owner, conn
        self.buf = b""
        self.pid = 0
        self.pending = {}      # packet id -> index into the owner's arrays
        self.queue = []        # paced: publishes waiting for an inflight slot
        self.n = 0             # publishes this connection has sent
        self.transport = None
        self.connected = asyncio.Event()
        self.closed = False

    def connection_made(self, transport):
        self.transport = transport
        transport.write(connect(f"pub{self.conn}"))

    def connection_lost(self, exc):
        self.closed = True

    def send(self, seq, due):
        o = self.owner
        self.pid = self.pid % 65535 + 1
        self.pending[self.pid] = len(o.seqs)
        o.seqs.append(seq)
        o.dues.append(due)
        o.sends.append(now())
        o.acks.append(0.0)
        o.outstanding += 1
        self.n += 1
        self.transport.write(
            o.heads[seq % len(o.heads)] + self.pid.to_bytes(2, "big")
            + b"\x00" + payload_of(seq)
        )

    def next_seq(self):
        return self.n * self.owner.k + self.conn

    def data_received(self, data):
        t = now()
        buf = self.buf + data if self.buf else data
        pkts, self.buf = split(buf)
        o = self.owner
        for first, p, end in pkts:
            kind = first >> 4
            if kind == PUBACK:
                at = self.pending.pop(buf[p] << 8 | buf[p + 1], None)
                if at is None:
                    o.stray_acks += 1
                    continue
                if end - p > 2 and buf[p + 2] >= 0x80:
                    o.refused += 1
                o.acks[at] = t
                o.outstanding -= 1
                if o.mode == "flood":
                    if t < o.until:
                        self.send(self.next_seq(), 0.0)
                elif o.mode == "warm":
                    seq = self.next_seq()
                    if seq < o.warm_total:
                        self.send(seq, 0.0)
                elif self.queue:
                    self.send(*self.queue.pop(0))
            elif kind == CONNACK:
                if buf[p + 1] != 0:
                    raise RuntimeError(f"pub{self.conn}: CONNACK {buf[p + 1]}")
                self.connected.set()
        if not o.outstanding:
            o.idle.set()


class Publishers:
    def __init__(self, plan):
        self.plan = plan
        self.k = plan["publishers"]
        self.inflight = plan["inflight"]
        qos = plan.get("qos", 1)
        self.heads = [publish_head(t, qos, PAYLOAD_LEN) for t in plan["pool"]]
        self.conns = {}
        self.seqs, self.dues = array("q"), array("d")
        self.sends, self.acks = array("d"), array("d")
        self.outstanding = 0
        self.stray_acks = self.refused = 0
        self.idle = asyncio.Event()
        self.mode, self.until, self.warm_total = "idle", 0.0, 0
        self.cpu = [0.0, 0.0]

    def stamp_cpu(self, k):
        self.cpu[k] = time.process_time()

    async def drained(self, timeout):
        if self.outstanding:
            self.idle.clear()
            try:
                await asyncio.wait_for(self.idle.wait(), timeout)
            except asyncio.TimeoutError:
                pass

    async def run(self, lines):
        loop = asyncio.get_running_loop()
        port = self.plan["port"]
        mine = self.plan["conns"]
        for lo in range(0, len(mine), 64):
            made = await asyncio.gather(*(
                loop.create_connection(
                    lambda c=c: Publisher(self, c), "127.0.0.1", port
                ) for c in mine[lo:lo + 64]
            ))
            for _, proto in made:
                self.conns[proto.conn] = proto
        await asyncio.wait_for(asyncio.gather(*(
            c.connected.wait() for c in self.conns.values()
        )), 300)
        reply({"ready": True})
        async for cmd in lines:
            if cmd[0] == "warm":
                self.mode, self.warm_total = "warm", int(cmd[1])
                for c in self.conns.values():
                    for _ in range(self.inflight):
                        if c.next_seq() < self.warm_total:
                            c.send(c.next_seq(), 0.0)
                await self.drained(600)
                self.mode = "idle"
                reply({"warm_done": True, "sent": len(self.seqs),
                       "outstanding": self.outstanding})
            elif cmd[0] == "flood":
                t0, t1 = float(cmd[1]), float(cmd[2])
                loop.call_at(t0, self.stamp_cpu, 0)
                loop.call_at(t1, self.stamp_cpu, 1)
                await asyncio.sleep(max(t0 - now(), 0))
                self.mode, self.until = "flood", t1
                for c in self.conns.values():
                    for _ in range(self.inflight):
                        c.send(c.next_seq(), 0.0)
                await asyncio.sleep(max(t1 - now(), 0))
                await self.drained(60)
                self.mode = "idle"
                reply({"window_done": True, "outstanding": self.outstanding})
            elif cmd[0] == "paced":
                t0, t1 = float(cmd[1]), float(cmd[2])
                loop.call_at(t0, self.stamp_cpu, 0)
                loop.call_at(t1, self.stamp_cpu, 1)
                self.mode = "paced"
                for seq, due in json.loads(cmd[3]):
                    wait = t0 + due - now()
                    if wait > 0:
                        await asyncio.sleep(wait)
                    c = self.conns[seq % self.k]
                    if len(c.pending) >= self.inflight:
                        c.queue.append((seq, t0 + due))
                    else:
                        c.send(seq, t0 + due)
                await asyncio.sleep(max(t1 - now(), 0))
                await self.drained(60)
                self.mode = "idle"
                reply({"window_done": True, "outstanding": self.outstanding})
            elif cmd[0] == "stop":
                break
        reply({
            "n": len(self.seqs), "stray_acks": self.stray_acks,
            "refused": self.refused, "cpu_s": self.cpu[1] - self.cpu[0],
            "closed": sum(c.closed for c in self.conns.values()),
            "arrays": ["seqs:q", "dues:d", "sends:d", "acks:d"],
        })
        out = sys.stdout.buffer
        for a in (self.seqs, self.dues, self.sends, self.acks):
            out.write(a.tobytes())
        out.flush()
        for c in self.conns.values():
            if not c.closed:
                c.transport.write(disconnect())
                c.transport.close()


# ------------------------------------------------------------------ main

def reply(obj) -> None:
    sys.stdout.buffer.write(json.dumps(obj).encode() + b"\n")
    sys.stdout.buffer.flush()


async def stdin_lines():
    """Commands from the parent, read off the loop's thread."""
    loop = asyncio.get_running_loop()
    while True:
        line = await loop.run_in_executor(None, sys.stdin.readline)
        if not line:
            return
        yield line.rstrip("\n").split(" ", 3)


async def amain(plan) -> None:
    role = Subscribers if plan["role"] == "sub" else Publishers
    await role(plan).run(stdin_lines())


def main() -> None:
    soft, hard = resource.getrlimit(resource.RLIMIT_NOFILE)
    if soft < hard:
        resource.setrlimit(resource.RLIMIT_NOFILE, (hard, hard))
    plan = json.loads(sys.stdin.readline())
    asyncio.run(amain(plan))
    if "jax" in sys.modules or "emqx_tpu" in sys.modules:
        print("the load generator imported the program", file=sys.stderr)
        os._exit(3)
    # the stdin reader thread may sit in readline(): leave without it
    sys.stdout.flush()
    os._exit(0)


if __name__ == "__main__":
    main()
