"""The plain reference and the comparison that decides `correct`.

MQTT topic matching and the five `traffic.rule_sql` predicates written
out directly: no `emqx_tpu.topic`, no `HostTrie`, no `rules.runtime`,
nothing the program made.  It answers, for the publishes the window
really sent, who must have received what and which rule must have
fired, and `judge` holds the run to the configuration's guarantees.
Every number compared is exact, so every limit is 0.
"""

import numpy as np


# ------------------------------------------------------ topic matching

def matches(topic: str, flt: str) -> bool:
    """MQTT 3.1.1 / 5 section 4.7, level by level."""
    tw, fw = topic.split("/"), flt.split("/")
    for i, f in enumerate(fw):
        if f == "#":
            # '#' also matches the parent level; '$' topics are not
            # matched by a leading wildcard
            return not (i == 0 and topic.startswith("$"))
        if i >= len(tw):
            return False
        if f == "+":
            if i == 0 and topic.startswith("$"):
                return False
            continue
        if f != tw[i]:
            return False
    return len(tw) == len(fw)


class FilterTree:
    """The same semantics over many filters at once: a plain dict tree
    walked level by level (a thousand filters times tens of thousands
    of topics is too slow one pair at a time)."""

    def __init__(self) -> None:
        self.root: dict = {}

    def add(self, flt: str, value) -> None:
        node = self.root
        for w in flt.split("/"):
            node = node.setdefault(w, {})
        node.setdefault(None, []).append(value)

    def match(self, topic: str) -> list:
        out: list = []
        words = topic.split("/")
        dollar = topic.startswith("$")

        def walk(node: dict, i: int) -> None:
            if "#" in node and not (i == 0 and dollar):
                out.extend(node["#"].get(None, ()))
            if i == len(words):
                out.extend(node.get(None, ()))
                return
            nxt = node.get(words[i])
            if nxt is not None:
                walk(nxt, i + 1)
            if "+" in node and not (i == 0 and dollar):
                walk(node["+"], i + 1)

        walk(self.root, 0)
        return out


# ----------------------------------------------------- rule predicates

RULE_FROM = ("vehicles/+/sensors/#", "dev/#", "site/+/floor/#",
             "vehicles/#", "#")


def rule_where(i: int, seq: np.ndarray) -> np.ndarray:
    """Rule ``i``'s WHERE over the payloads of ``seq`` (what
    `traffic.payload_of` puts in them), written out directly."""
    temp, hum, dev = seq * 7 % 50, seq * 13 % 100, seq % 7
    kind = i % 5
    if kind == 0:       # payload.temp > i % 40
        return temp > i % 40
    if kind == 1:       # payload.dev = 'd<i%7>' and payload.hum <= 20 + i%60
        return (dev == i % 7) & (hum <= 20 + i % 60)
    if kind == 2:       # temp >= i%30 or not (hum < i%50)
        return (temp >= i % 30) | ~(hum < i % 50)
    if kind == 3:       # dev in (d<i%7>, d<(i+3)%7>) and is_not_null(hum)
        return (dev == i % 7) | (dev == (i + 3) % 7)
    # temp = i % 50 and dev != 'd<i%7>'
    return (temp == i % 50) & (dev != i % 7)


# ------------------------------------------------------------ expected

class Overlap(Exception):
    """A topic of the pool matches two filters of one subscriber: the
    broker owes a delivery a subscription, the count below one a
    subscriber, so the run would blame the program for the live set."""


class Expected:
    """What the reference says of the publishes ``seqs`` (any order):
    deliveries a subscriber and firings a rule."""

    def __init__(self, pool, subs, n_rules: int, seqs: np.ndarray):
        self.seqs = np.sort(np.asarray(seqs, dtype=np.int64))
        self.subs = subs
        self.n_rules = n_rules
        self.n_pool = len(pool)
        tix = self.seqs % len(pool)
        used = np.unique(tix)
        tree = FilterTree()
        for j, (_cid, flts, _qos) in enumerate(subs):
            for flt in flts:
                tree.add(flt, j)
        # subscriber -> pool indices of the topics it must receive; its
        # filters are disjoint on the whole pool (at most one delivery a
        # publish), whichever topics this run came to send
        sent_on = set(used.tolist())
        hit = [[] for _ in subs]
        for t, topic in enumerate(pool):
            owed = tree.match(topic)
            if len(set(owed)) != len(owed):
                j = next(j for j in owed if owed.count(j) > 1)
                raise Overlap(
                    f"topic {topic!r} matches more than one filter of "
                    f"subscriber {subs[j][0]!r}: "
                    f"{[f for f in subs[j][1] if matches(topic, f)]}"
                )
            if t in sent_on:
                for j in owed:
                    hit[j].append(t)
        self.sub_seqs = [
            self.seqs[np.isin(tix, np.asarray(h, dtype=np.int64))]
            if h else self.seqs[:0] for h in hit
        ]
        from_hit = [
            np.isin(tix, np.asarray(
                [t for t in used if matches(pool[t], f)], dtype=np.int64
            )) for f in RULE_FROM
        ]
        self.rule_seqs = [
            self.seqs[from_hit[i % 5] & rule_where(i, self.seqs)]
            for i in range(n_rules)
        ]
        self.n_deliveries = sum(len(s) for s in self.sub_seqs)
        self.n_firings = sum(len(s) for s in self.rule_seqs)


def _diff(have: np.ndarray, want: np.ndarray):
    """``(missing, unexpected, duplicates)`` of ``have`` against the
    sorted, duplicate-free ``want``; the first two as arrays."""
    uniq, counts = np.unique(have, return_counts=True)
    missing = np.setdiff1d(want, uniq, assume_unique=True)
    unexpected = np.setdiff1d(uniq, want, assume_unique=True)
    return missing, unexpected, int((counts - 1).sum())


def judge(exp: Expected, publishers: int, acked: np.ndarray,
          received: list, qos_seen: list, fired_rule: np.ndarray,
          fired_seq: np.ndarray, device: dict) -> tuple:
    """The numbers compared, each ``(name, value, limit)``, and the
    sequence numbers of the publishes that failed (a PUBACK, a delivery
    or a firing they owe is missing).

    ``acked``: the sequence numbers whose PUBACK came back;
    ``received[j]``: subscriber j's deliveries in arrival order;
    ``qos_seen[j]``: bit mask of the QoS its deliveries came at;
    ``fired_*``: every rule firing the actions saw; ``device``: the
    counts that say which steps the device served."""
    unacked = np.setdiff1d(exp.seqs, acked)
    out = [("pubacks_missing", len(unacked), 0)]
    failed = [unacked]
    missing = unexpected = dups = disorder = wrong_qos = 0
    for j, (_cid, _flts, qos) in enumerate(exp.subs):
        have = np.asarray(received[j], dtype=np.int64)
        m, u, d = _diff(have, exp.sub_seqs[j])
        failed.append(m)
        missing, unexpected, dups = missing + len(m), unexpected + len(u), dups + d
        # publish order per publisher and topic, as the session sees it
        key = (have % exp.n_pool) * publishers + have % publishers
        order = np.argsort(key, kind="stable")
        hs, ks = have[order], key[order]
        disorder += int(((np.diff(hs) <= 0) & (np.diff(ks) == 0)).sum()) - d
        # granted QoS: min(publish QoS 1, subscription QoS)
        if len(have) and qos_seen[j] != 1 << min(qos, 1):
            wrong_qos += 1
    out += [
        ("deliveries_missing", missing, 0),
        ("deliveries_unexpected", unexpected, 0),
        ("deliveries_duplicated", dups, 0),
        ("deliveries_out_of_order", max(disorder, 0), 0),
        ("subscribers_wrong_qos", wrong_qos, 0),
    ]
    if exp.n_rules:
        m = u = d = 0
        for i in range(exp.n_rules):
            mi, ui, di = _diff(fired_seq[fired_rule == i], exp.rule_seqs[i])
            failed.append(mi)
            m, u, d = m + len(mi), u + len(ui), d + di
        out += [("firings_missing", m, 0), ("firings_unexpected", u, 0),
                ("firings_duplicated", d, 0)]
    out += [(name, value, 0) for name, value in device.items()]
    return out, np.unique(np.concatenate(failed))
