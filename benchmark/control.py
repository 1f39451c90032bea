#!/usr/bin/env python3
"""The control of `correct`: a run of `run.py` with one guarantee of the
configuration broken underneath the timed path.  Each must read
``"correct": false``.  The benchmark's own runs never come here.

    python benchmark/control.py --fault <name> --workload <cell> --seed <n> --seconds <s>

    lost_match    an answer altered where it is produced: once in 8
                  windows the match step returns nothing, so the
                  deliveries and rule firings that window owes never
                  happen
    weak_ack      a weakened acknowledgement: one publish in 500 is
                  acknowledged and never dispatched
    host_decide   the device guarantee broken: delivery decisions pinned
                  to the host twin (the program's own `decide_force`)
    host_match    the same for the match step, by the program's own
                  failpoint ``engine.device_step=error`` (the windows are
                  then served by the host trie: right answers, wrong path)
    share_lost    a delivery lost where it reaches the channel: once in
                  500 QoS 0 publishes sent to a client that holds shared
                  subscriptions alone, the frame never leaves, so a
                  group misses that publish (however the member was
                  picked)
    churn_sub_lost   a churned SUBSCRIBE acknowledged and never routed:
                  the session keeps the subscription and the SUBACK
                  grants it, but the broker inserts no route, so what
                  it was owed is missing
    churn_unsub_kept an UNSUBSCRIBE acknowledged and its route kept: the
                  session drops the subscription and the UNSUBACK
                  reports success, but the route stays, so later
                  publishes reach a subscription that has ended
    churn_unsub_ignored an UNSUBSCRIBE ignored whole: no route or session
                  change and no UNSUBACK, so the subscription's end is
                  never answered (an unanswered life, `client_errors`)

A fault takes the `BrokerServer` before `start()` and returns what
undoes it (tests run several in one process).
"""

import argparse
import asyncio
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
PUBLISH = 3                    # MQTT's control packet type
sys.path.insert(0, HERE)


def lost_match(server):
    eng = server.broker.router.engine
    real = eng.match_batch_finish
    calls = [0]

    def finish(pending, *a, **kw):
        matched = real(pending, *a, **kw)
        calls[0] += 1
        if calls[0] % 8 == 0:
            matched = [set() for _ in matched]
        return matched

    eng.match_batch_finish = finish
    return lambda: None


def weak_ack(server):
    from emqx_tpu.broker.broker import PublishBatcher

    real = PublishBatcher.publish
    calls = [0]

    def publish(self, msg, source=None):
        calls[0] += 1
        if calls[0] % 500 == 250:
            fut = asyncio.get_running_loop().create_future()
            fut.set_result(1)
            return fut
        return real(self, msg, source)

    PublishBatcher.publish = publish

    def undo():
        PublishBatcher.publish = real
    return undo


def host_decide(server):
    server.broker.router.engine.decide_force = "host"
    return lambda: None


def host_match(server):
    from emqx_tpu import failpoints

    failpoints.configure("engine.device_step", "error")
    return lambda: failpoints.clear("engine.device_step")


def share_lost(server):
    from emqx_tpu.broker.channel import Channel

    real_wire, real_packets = Channel.send_wire, Channel.send_packets
    calls = [0]

    def lost(ch, qos: int) -> bool:
        """A QoS 0 publish to a member of groups alone: every publish
        it is sent is one of its groups' share."""
        subs = ch.session.subscriptions if ch.session is not None else ()
        if qos or not subs or not all(f.startswith("$share/") for f in subs):
            return False
        calls[0] += 1
        return calls[0] % 500 == 250

    def send_packets(self, packets):
        packets = [p for p in packets
                   if p.type != PUBLISH or not lost(self, p.qos)]
        return real_packets(self, packets)

    def send_wire(self, data, npub, count=True):
        # the run's frames one by one: fixed header, remaining length
        data, kept, at = bytes(data), bytearray(), 0
        while at < len(data):
            head, k, size, shift = data[at], at + 1, 0, 0
            while True:
                size |= (data[k] & 127) << shift
                shift, k = shift + 7, k + 1
                if data[k - 1] < 128:
                    break
            if head >> 4 == PUBLISH and lost(self, head >> 1 & 3):
                npub = (npub[0] - 1, npub[1], npub[2])
            else:
                kept += data[at:k + size]
            at = k + size
        return real_wire(self, bytes(kept), npub, count)

    Channel.send_wire, Channel.send_packets = send_wire, send_packets

    def undo():
        Channel.send_wire, Channel.send_packets = real_wire, real_packets
    return undo


def _churned(real, skipped):
    """``real`` for every call but a churn connection's (`loadgen`
    names them ``churn<i>``), which gets ``skipped``."""
    def call(clientid, *a, **kw):
        if clientid.startswith("churn"):
            return skipped
        return real(clientid, *a, **kw)
    return call


def churn_sub_lost(server):
    broker = server.broker
    broker.subscribe = _churned(broker.subscribe, [])
    return lambda: None


def churn_unsub_kept(server):
    broker = server.broker
    broker.unsubscribe = _churned(broker.unsubscribe, True)
    return lambda: None


def churn_unsub_ignored(server):
    from emqx_tpu.broker.channel import Channel

    real = Channel._handle_unsubscribe

    def handle(self, pkt):
        if not self.client.clientid.startswith("churn"):
            real(self, pkt)

    Channel._handle_unsubscribe = handle

    def undo():
        Channel._handle_unsubscribe = real
    return undo


FAULTS = {"lost_match": lost_match, "weak_ack": weak_ack,
          "host_decide": host_decide, "host_match": host_match,
          "share_lost": share_lost, "churn_sub_lost": churn_sub_lost,
          "churn_unsub_kept": churn_unsub_kept,
          "churn_unsub_ignored": churn_unsub_ignored}


def main(argv=None) -> int:
    import run

    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--fault", required=True, choices=sorted(FAULTS))
    args, rest = ap.parse_known_args(argv)
    # an answer the fault took away never comes: no minute's wait for it
    run.DRAIN_S = 5.0
    return run.main(rest, fault=FAULTS[args.fault])


if __name__ == "__main__":
    sys.exit(main())
