"""The plain reference and the comparison that decides `correct`.

MQTT topic matching, the group rule of shared subscriptions and the five
`traffic.rule_sql` predicates written out directly: no `emqx_tpu.topic`,
no `HostTrie`, no `rules.runtime`, nothing the program made.  It
answers, for the publishes the window really sent, who must have
received what and which rule must have fired, and `judge` holds the run
to the configuration's guarantees.  Every number compared is exact, so
every limit is 0.
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
    subscriber, so the run would blame the program for the live set.
    A ``$share`` filter MQTT 5.0 section 4.8.2 does not allow is refused
    as one."""


SHARE = "$share"


def _is_filter(flt: str) -> bool:
    """A non-empty MQTT filter: ``+`` a whole level, ``#`` a whole last
    level (section 4.7.1)."""
    words = flt.split("/")
    return bool(flt) and all(
        ("+" not in w or w == "+") and ("#" not in w or w == "#")
        for w in words
    ) and "#" not in words[:-1]


def share_of(flt: str):
    """``(name, rest)`` of a shared subscription ``$share/<name>/<rest>``,
    None for a plain filter.  ``<name>`` is one level with no ``+`` or
    ``#``, ``<rest>`` a non-empty filter that is not itself shared."""
    words = flt.split("/")
    if words[0] != SHARE:
        return None
    name, rest = (words + [""])[1], "/".join(words[2:])
    if (not name or "+" in name or "#" in name or not _is_filter(rest)
            or rest.split("/")[0] == SHARE):
        raise Overlap(
            f"filter {flt!r}: a shared subscription is "
            f"$share/<name>/<filter>, <name> one level with no '+' or "
            f"'#', <filter> a non-empty filter"
        )
    return name, rest


def real_filter(flt: str) -> str:
    """The filter the broker routes: ``<rest>`` of a shared one."""
    share = share_of(flt)
    return flt if share is None else share[1]


class Expected:
    """What the reference says of the publishes ``seqs`` (any order):
    deliveries a subscriber, deliveries a shared-subscription group, and
    firings a rule.

    A group is a pair ``(<name>, <rest>)``; its members are the
    subscribers that hold the filter ``$share/<name>/<rest>``.  It is owed
    each publish its ``<rest>`` matches once, by any one member
    (MQTT 5.0 section 4.8.2); plain filters are owed one delivery a
    subscriber.  ``sub_seqs[j]`` is subscriber j's plain part,
    ``group_seqs[g]`` group g's."""

    def __init__(self, pool, subs, n_rules: int, seqs: np.ndarray):
        self.seqs = np.sort(np.asarray(seqs, dtype=np.int64))
        self.subs = subs
        self.n_rules = n_rules
        self.n_pool = len(pool)
        tix = self.seqs % len(pool)
        used = np.unique(tix)
        tree = FilterTree()
        index: dict = {}
        self.groups: list = []       # g -> (name, rest)
        self.groups_of = [[] for _ in subs]
        for j, (_cid, flts, _qos) in enumerate(subs):
            for flt in flts:
                share = share_of(flt)
                g = -1                   # a plain filter
                if share is not None:
                    g = index.setdefault(share, len(self.groups))
                    if g == len(self.groups):
                        self.groups.append(share)
                    self.groups_of[j].append(g)
                tree.add(flt if share is None else share[1], (j, g))
        # subscriber -> pool indices of the topics its plain filters owe
        # it; its filters, plain and shared, are disjoint on the whole
        # pool (at most one delivery a publish, each receipt owed to one
        # of its subscriptions), whichever topics this run came to send
        sent_on = set(used.tolist())
        hit = [[] for _ in subs]
        # group -> the pool's topics its filter matches
        self.group_topics = np.zeros((len(self.groups), len(pool)), bool)
        for t, topic in enumerate(pool):
            owed = tree.match(topic)
            js = [j for j, _g in owed]
            if len(set(js)) != len(js):
                j = next(j for j in js if js.count(j) > 1)
                both = [f for f in subs[j][1]
                        if matches(topic, real_filter(f))]
                raise Overlap(
                    f"topic {topic!r} matches more than one filter of "
                    f"subscriber {subs[j][0]!r}: {both}"
                )
            for j, g in owed:
                if g >= 0:
                    self.group_topics[g, t] = True
                elif t in sent_on:
                    hit[j].append(t)
        self.sub_seqs = [
            self.seqs[np.isin(tix, np.asarray(h, dtype=np.int64))]
            if h else self.seqs[:0] for h in hit
        ]
        self.group_seqs = [self.seqs[m[tix]] for m in self.group_topics]
        from_hit = [
            np.isin(tix, np.asarray(
                [t for t in used if matches(pool[t], f)], dtype=np.int64
            )) for f in RULE_FROM
        ]
        self.rule_seqs = [
            self.seqs[from_hit[i % 5] & rule_where(i, self.seqs)]
            for i in range(n_rules)
        ]
        self.n_deliveries = sum(len(s) for s in self.sub_seqs) + sum(
            len(s) for s in self.group_seqs
        )
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
    counts that say which steps the device served.

    A member's receipts on its groups' topics are pooled by group and
    held to the group's publishes (one given to two members is
    duplicated, one given to none missing); the rest to its plain part,
    where a topic none of its filters matches reads unexpected.  Order
    and QoS are each subscriber's own."""
    unacked = np.setdiff1d(exp.seqs, acked)
    out = [("pubacks_missing", len(unacked), 0)]
    failed = [unacked]
    missing = unexpected = dups = disorder = wrong_qos = 0
    owed = []                # (receipts, the publishes they are held to)
    pooled = [[] for _ in exp.groups]
    for j, (_cid, _flts, qos) in enumerate(exp.subs):
        have = np.asarray(received[j], dtype=np.int64)
        plain = have
        if exp.groups_of[j]:
            tix = have % exp.n_pool
            rest = np.ones(len(have), bool)
            for g in exp.groups_of[j]:
                mine = exp.group_topics[g, tix]
                pooled[g].append(have[mine])
                rest &= ~mine
            plain = have[rest]
        owed.append((plain, exp.sub_seqs[j]))
        # publish order per publisher and topic, as the session sees it
        key = (have % exp.n_pool) * publishers + have % publishers
        order = np.argsort(key, kind="stable")
        hs, ks = have[order], key[order]
        twice = len(have) - len(np.unique(have))
        disorder += int(((np.diff(hs) <= 0) & (np.diff(ks) == 0)).sum())
        disorder -= twice
        # granted QoS: min(publish QoS 1, subscription QoS)
        if len(have) and qos_seen[j] != 1 << min(qos, 1):
            wrong_qos += 1
    owed += [(np.concatenate(got), want)
             for got, want in zip(pooled, exp.group_seqs)]
    for have, want in owed:
        m, u, d = _diff(have, want)
        failed.append(m)
        missing, unexpected, dups = missing + len(m), unexpected + len(u), dups + d
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
