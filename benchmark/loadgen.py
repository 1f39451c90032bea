#!/usr/bin/env python3
"""The benchmark's load generator: a child process of `run.py` that
speaks MQTT 5 over loopback TCP and stamps CLOCK_MONOTONIC, which every
process of one host shares.

It imports neither JAX nor `emqx_tpu`: the few packet types it needs
(CONNECT/CONNACK, SUBSCRIBE/SUBACK, UNSUBSCRIBE/UNSUBACK, PUBLISH,
PUBACK, DISCONNECT) are written out here, so a change to the program's
codec cannot move the yardstick.  `tests/benchmark` holds it against
`emqx_tpu.codec`.

One process is subscribers, publishers or churn.  The parent writes a
plan (one JSON line) to stdin, then commands, one a line:

    subscribers   window T0 T1   stamp CPU time at both instants
                  count          -> {"count": deliveries so far}
                  stop           -> header line + raw arrays, then exit
    publishers    warm N         closed loop until N publishes in all
                  flood T0 T1    closed loop, ``inflight`` a connection
                  paced T0 T1 [[seq, due], ...]   open loop
                  stop           -> header line + raw arrays, then exit
    churn         start T        subscribe -> hold -> unsubscribe cycles
                                 from T, open loop, the plan's block of
                                 due offsets repeated every ``period``
                  window T0 T1   from T0 the block once more, then at T1
                                 every live subscription ends; once each
                                 SUBACK and UNSUBACK is in, or the plan's
                                 ``ack_wait_s`` has passed ->
                                 {"churn_done": ...}
                  dump           -> header line + raw arrays
                  stop           -> the same, then exit

Replies are one JSON line each; ``dump`` and ``stop`` follow their line
with the raw bytes of the arrays it names, in order.
"""

import asyncio
import functools
import json
import os
import resource
import sys
import time
from array import array

MQTT_V5 = 5
CONNECT, CONNACK, PUBLISH, PUBACK = 1, 2, 3, 4
SUBSCRIBE, SUBACK, UNSUBSCRIBE, UNSUBACK, DISCONNECT = 8, 9, 10, 11, 14

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


def unsubscribe(packet_id: int, filters) -> bytes:
    body = packet_id.to_bytes(2, "big") + b"\x00"
    for flt in filters:
        body += utf8(flt)
    return packet(UNSUBSCRIBE << 4 | 2, body)


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
    """``(packet_id, reason_codes)`` of a v5 SUBACK, or of an UNSUBACK,
    which has the same layout (MQTT 5.0 sections 3.9, 3.11)."""
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


# ---------------------------------------------------------- connections

async def open_all(port: int, makers) -> list:
    """One connection a protocol factory, 64 opened at a time; returns
    the protocols once each has set its ``ready`` event."""
    loop = asyncio.get_running_loop()
    conns = []
    for lo in range(0, len(makers), 64):
        made = await asyncio.gather(*(
            loop.create_connection(m, "127.0.0.1", port)
            for m in makers[lo:lo + 64]
        ))
        conns += [proto for _, proto in made]
    await asyncio.wait_for(asyncio.gather(*(
        c.ready.wait() for c in conns
    )), 300)
    return conns


def close_all(conns) -> None:
    for c in conns:
        if not c.closed:
            c.transport.write(disconnect())
            c.transport.close()


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
        self.ready = asyncio.Event()   # set at the SUBACK
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
                self.ready.set()
        if acks:
            self.transport.write(b"".join(acks))


class Subscribers:
    def __init__(self, plan):
        self.plan = plan
        self.conns = []
        self.cpu = [0.0, 0.0]

    async def run(self, lines):
        loop = asyncio.get_running_loop()
        self.conns = await open_all(self.plan["port"], [
            functools.partial(Subscriber, self, i, *c)
            for i, c in enumerate(self.plan["conns"])
        ])
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
        close_all(self.conns)

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
        self.ready = asyncio.Event()   # set at the CONNACK
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
                self.ready.set()
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
        self.conns = {c.conn: c for c in await open_all(
            self.plan["port"],
            [functools.partial(Publisher, self, c)
             for c in self.plan["conns"]],
        )}
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
        close_all(self.conns.values())


# ---------------------------------------------------------------- churn

class ChurnConn(asyncio.Protocol):
    """One churn connection: it holds whatever subscriptions its owner
    gives it, and files every delivery it receives by its own index."""

    def __init__(self, owner, idx):
        self.owner, self.idx = owner, idx
        self.buf = b""
        self.pid = 0
        self.pending = {}      # packet id -> the life a SUB/UNSUBACK ends
        self.transport = None
        self.ready = asyncio.Event()   # set at the CONNACK
        self.closed = False

    def connection_made(self, transport):
        self.transport = transport
        transport.write(connect(f"churn{self.idx}"))

    def connection_lost(self, exc):
        self.closed = True

    def send(self, make, life, *args) -> None:
        """``make(packet_id, *args)``, with a packet id no ack this
        connection still waits for holds."""
        self.pid = self.pid % 65535 + 1
        while self.pid in self.pending:
            self.pid = self.pid % 65535 + 1
        self.pending[self.pid] = life
        self.transport.write(make(self.pid, *args))

    def data_received(self, data):
        t = now()
        buf = self.buf + data if self.buf else data
        pkts, self.buf = split(buf)
        o = self.owner
        acks = []
        for first, p, end in pkts:
            kind = first >> 4
            if kind == PUBLISH:
                _, qos, _dup, pid, payload = parse_publish(buf, first, p, end)
                if qos:
                    acks.append(puback(pid))
                o.r_conn.append(self.idx)
                o.r_seq.append(int(payload[SEQ_AT:SEQ_AT + SEQ_W]))
                o.r_t.append(t)
                o.r_qos.append(qos)
            elif kind in (SUBACK, UNSUBACK):
                pid, codes = parse_suback(buf, p, end)
                life = self.pending.pop(pid, None)
                if life is None:
                    o.stray += 1
                elif kind == SUBACK:
                    o.subacked(life, t, codes)
                else:
                    o.unsubacked(life, t, codes)
            elif kind == CONNACK:
                if buf[p + 1] != 0:
                    raise RuntimeError(
                        f"churn{self.idx}: CONNACK {buf[p + 1]}"
                    )
                self.ready.set()
        if acks:
            self.transport.write(b"".join(acks))


class Churn:
    """Subscribe -> hold -> unsubscribe cycles on a seeded open-loop
    schedule: cycle ``i`` of this process subscribes its connection
    ``i % clients`` to its filter ``i % len(filters)`` at QoS
    ``qos[i % len(qos)]`` when it is due, and unsubscribes it
    ``dwell_s`` after that, whatever the broker answered.  A filter is
    not taken again before the UNSUBACK of its last life is in (a cycle
    that would is counted in ``clashes`` and skipped); the plan's
    filters are disjoint on the pool, so no topic matches two filters
    that one connection holds, and each receipt belongs to one life.

    For each life it keeps four instants on the clock the other roles
    stamp (SUBSCRIBE sent, SUBACK in, UNSUBSCRIBE sent, UNSUBACK in;
    0.0 where one never came) and for each delivery the connection, the
    sequence number, the instant and the QoS."""

    ARRAYS = ["conn:q", "filter:q", "qos:q", "sub:d", "suback:d",
              "unsub:d", "unsuback:d", "r_conn:q", "r_seq:q", "r_t:d",
              "r_qos:q"]

    def __init__(self, plan):
        self.plan = plan
        self.fids = [g for g, _f in plan["filters"]]
        self.flts = [f for _g, f in plan["filters"]]
        self.dues, self.period = plan["dues"], plan["period"]
        self.dwell, self.qos = plan["dwell_s"], plan["qos"]
        self.conns = []
        # a life: its connection and filter here, then the arrays
        self.life_c, self.life_f = [], []
        self.conn, self.filt, self.q = array("q"), array("q"), array("q")
        self.sub, self.suback = array("d"), array("d")
        self.unsub, self.unsuback = array("d"), array("d")
        self.r_conn, self.r_seq = array("q"), array("q")
        self.r_t, self.r_qos = array("d"), array("q")
        self.live = {}         # life -> the timer of its UNSUBSCRIBE
        self.holding = set()   # filters whose last UNSUBACK is not in
        self.outstanding = 0   # SUBSCRIBEs and UNSUBSCRIBEs not acked
        self.refused = self.stray = self.clashes = 0
        self.idle = asyncio.Event()
        self.t0 = self.t1 = None
        self.cpu = [0.0, 0.0]

    def stamp_cpu(self, k):
        self.cpu[k] = time.process_time()

    # ---------------------------------------------------------- a life

    def begin(self, i: int, due: float) -> None:
        f = i % len(self.flts)
        if f in self.holding:
            self.clashes += 1
            return
        self.holding.add(f)
        life = len(self.life_c)
        c = self.conns[i % len(self.conns)]
        q = self.qos[i % len(self.qos)]
        self.life_c.append(c)
        self.life_f.append(f)
        self.conn.append(c.idx)
        self.filt.append(self.fids[f])
        self.q.append(q)
        for a in (self.suback, self.unsub, self.unsuback):
            a.append(0.0)
        self.outstanding += 1
        self.sub.append(now())
        c.send(subscribe, life, [self.flts[f]], q)
        self.live[life] = asyncio.get_running_loop().call_at(
            due + self.dwell, self.end, life
        )

    def end(self, life: int) -> None:
        self.live.pop(life).cancel()
        self.outstanding += 1
        self.unsub[life] = now()
        self.life_c[life].send(unsubscribe, life,
                               [self.flts[self.life_f[life]]])

    def subacked(self, life: int, t: float, codes) -> None:
        self.suback[life] = t
        self.refused += codes != [self.q[life]]
        self.acked()

    def unsubacked(self, life: int, t: float, codes) -> None:
        self.unsuback[life] = t
        self.refused += codes != [0]
        self.holding.discard(self.life_f[life])
        self.acked()

    def acked(self) -> None:
        self.outstanding -= 1
        if not self.outstanding:
            self.idle.set()

    # -------------------------------------------------------- schedule

    def warm_dues(self, start: float):
        """The block's due instants from ``start`` on, a block every
        ``period`` seconds, until the window opens."""
        b = 0
        while self.dues:
            for d in self.dues:
                yield start + b * self.period + d
            b += 1

    async def schedule(self, start: float) -> None:
        i = 0
        for at in self.warm_dues(start):
            await asyncio.sleep(max(at - now(), 0))
            if self.t0 is not None and at >= self.t0:
                break
            self.begin(i, at)
            i += 1
        while self.t0 is None:
            await asyncio.sleep(0.05)
        for d in self.dues:
            await asyncio.sleep(max(self.t0 + d - now(), 0))
            self.begin(i, self.t0 + d)
            i += 1
        await asyncio.sleep(max(self.t1 - now(), 0))
        for life in list(self.live):
            self.end(life)
        if self.outstanding:
            self.idle.clear()
            try:
                await asyncio.wait_for(self.idle.wait(),
                                       self.plan["ack_wait_s"])
            except asyncio.TimeoutError:
                pass
        reply({"churn_done": True, "outstanding": self.outstanding})

    def dump(self) -> None:
        reply({
            "lives": len(self.conn), "receipts": len(self.r_seq),
            "refused": self.refused, "stray": self.stray,
            "clashes": self.clashes, "cpu_s": self.cpu[1] - self.cpu[0],
            "closed": sum(c.closed for c in self.conns),
            "arrays": self.ARRAYS,
        })
        out = sys.stdout.buffer
        for a in (self.conn, self.filt, self.q, self.sub, self.suback,
                  self.unsub, self.unsuback, self.r_conn, self.r_seq,
                  self.r_t, self.r_qos):
            out.write(a.tobytes())
        out.flush()

    async def run(self, lines):
        loop = asyncio.get_running_loop()
        self.conns = await open_all(self.plan["port"], [
            functools.partial(ChurnConn, self, c) for c in self.plan["conns"]
        ])
        reply({"ready": True})
        task = None
        async for cmd in lines:
            if cmd[0] == "start":
                task = asyncio.ensure_future(self.schedule(float(cmd[1])))
            elif cmd[0] == "window":
                self.t0, self.t1 = float(cmd[1]), float(cmd[2])
                loop.call_at(self.t0, self.stamp_cpu, 0)
                loop.call_at(self.t1, self.stamp_cpu, 1)
            elif cmd[0] == "dump":
                self.dump()
            elif cmd[0] == "stop":
                break
        if task is not None:
            task.cancel()
        self.dump()
        close_all(self.conns)


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
    role = {"sub": Subscribers, "pub": Publishers, "churn": Churn}
    await role[plan["role"]](plan).run(stdin_lines())


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
