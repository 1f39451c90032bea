"""The plain reference and the comparison that decides `correct`.

MQTT topic matching, the group rule of shared subscriptions, the
lifetime of a subscription and the five `traffic.rule_sql` predicates
written out directly: no `emqx_tpu.topic`, no `HostTrie`, no
`rules.runtime`, nothing the program made.  It answers, for the
publishes the window really sent, who must have received what and which
rule must have fired, and `judge` holds the run to the configuration's
guarantees.  Every number compared is exact, so every limit is 0.
"""

from bisect import bisect_right

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

    def __init__(self, pool, subs, n_rules: int, seqs: np.ndarray,
                 churn: "Churned" = None):
        self.seqs = np.sort(np.asarray(seqs, dtype=np.int64))
        self.churn = churn
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
        ) + (churn.n_owed if churn is not None else 0)
        self.n_firings = sum(len(s) for s in self.rule_seqs)



class Churned:
    """Subscriptions made and ended while the run was open, and what each
    was owed and may have got (MQTT 5.0 sections 3.8.4 and 3.10.4).

    A life holds one filter ``f`` on one connection, between four
    instants: SUBSCRIBE sent ``s0``, SUBACK in ``s1``, UNSUBSCRIBE sent
    ``u0``, UNSUBACK in ``u1`` (0.0: it never came).  A publish ``p``
    whose topic ``f`` matches, sent at ``send(p)`` and acknowledged at
    ``ack(p)`` (0.0: never), is

    * **owed** to the life if ``send(p) > s1`` and ``0 < ack(p) < u0``.
      The broker inserts the route before it writes the SUBACK
      (`emqx_tpu/broker/channel.py` `_subscribe_body`: `_do_subscribe`
      -> `Broker.subscribe` -> `Router.subscribe` ->
      `MatchEngine.insert`, then `Suback`), so ``p`` is matched with
      ``f`` in its window; and it acknowledges a QoS1 publish only once
      that window has been dispatched to the sessions
      (`emqx_tpu/broker/broker.py` `_dispatch_loop`: `publish_dispatch`,
      then `fut.set_result`, whose callback writes the PUBACK), so ``p``
      had been added for delivery before the UNSUBSCRIBE left, and
      section 3.10.4 lets the server stop adding new messages only
      (`Session.unsubscribe` drops no queued message);
    * **permitted** if ``ack(p) > s0`` (or ``p`` was never acknowledged)
      and ``send(p) < u1``: a delivery needs ``p`` matched after the
      SUBSCRIBE was read and before the UNSUBSCRIBE was, and its PUBACK
      follows that match;
    * **unexpected** if received outside the permitted band, as is a
      receipt on a topic that no filter its connection held matches.

    A receipt belongs to the life of its connection whose filter matches
    its topic and that subscribed last before the receipt came.  The
    filters are disjoint on the pool (a topic on two of them is refused,
    an `Overlap`), so one life at most can claim it.

    Both acknowledgements are owed too (sections 3.8.4 and 3.10.4): a
    life that its SUBACK or its UNSUBACK never answered, once the run
    has waited for them, is ``unanswered``, which `judge` counts under
    ``client_errors``.  Without that, an UNSUBSCRIBE ignored whole (the
    route kept, no UNSUBACK) would leave the band open (``u1`` = inf)
    and every later receipt permitted."""

    def __init__(self, pool, filters, lives: dict, seqs, sends, acks):
        self.n_pool = len(pool)
        tree = FilterTree()
        for k, flt in enumerate(filters):
            tree.add(flt, k)
        self.filter_of = np.full(len(pool), -1, np.int64)
        for t, topic in enumerate(pool):
            hit = tree.match(topic)
            if len(hit) > 1:
                raise Overlap(
                    f"topic {topic!r} matches more than one churned "
                    f"filter: {[filters[k] for k in hit]}"
                )
            if hit:
                self.filter_of[t] = hit[0]
        L = {k: np.asarray(v) for k, v in lives.items()}
        self.conn, self.filt, self.qos = L["conn"], L["filter"], L["qos"]
        self.s0, self.s1 = L["sub"], L["suback"]
        self.u0 = np.where(L["unsub"] > 0, L["unsub"], np.inf)
        self.u1 = np.where(L["unsuback"] > 0, L["unsuback"], np.inf)
        self.unanswered = int(((self.s1 == 0) | (self.u1 == np.inf)).sum())
        seqs = np.asarray(seqs, dtype=np.int64)
        order = np.argsort(seqs, kind="stable")
        self.p_seq = seqs[order]
        self.p_send = np.asarray(sends)[order]
        self.p_ack = np.asarray(acks)[order]
        # the publishes each filter matches
        p_f = self.filter_of[self.p_seq % self.n_pool]
        by_f = np.argsort(p_f, kind="stable")
        cuts = np.searchsorted(p_f[by_f], np.arange(len(filters) + 1))
        owed_life, owed_seq = [], []
        for life, f in enumerate(self.filt.tolist()):
            if not self.s1[life]:
                continue
            mine = by_f[cuts[f]:cuts[f + 1]]
            ack = self.p_ack[mine]
            ok = ((self.p_send[mine] > self.s1[life]) & (ack > 0)
                  & (ack < self.u0[life]))
            owed_seq.append(self.p_seq[mine[ok]])
            owed_life.append(np.full(int(ok.sum()), life, np.int64))
        cat = (lambda a: np.concatenate(a) if a
               else np.zeros(0, np.int64))
        self.owed_life, self.owed_seq = cat(owed_life), cat(owed_seq)
        self.n_owed = len(self.owed_seq)
        # (connection, filter) -> its lives by SUBSCRIBE instant
        self._lives: dict = {}
        for life in np.lexsort((self.s0, self.filt, self.conn)).tolist():
            at = self._lives.setdefault(
                (int(self.conn[life]), int(self.filt[life])), ([], [])
            )
            at[0].append(float(self.s0[life]))
            at[1].append(life)

    def attribute(self, r_conn, r_seq, r_t) -> np.ndarray:
        """The life each receipt belongs to; -1 where none can claim it."""
        f = self.filter_of[np.asarray(r_seq, dtype=np.int64) % self.n_pool]
        out = np.full(len(f), -1, np.int64)
        for i, (c, fi, t) in enumerate(zip(np.asarray(r_conn).tolist(),
                                           f.tolist(),
                                           np.asarray(r_t).tolist())):
            at = self._lives.get((c, fi))
            if at is not None:
                k = bisect_right(at[0], t)
                if k:
                    out[i] = at[1][k - 1]
        return out

    @staticmethod
    def _pairs(life, seq) -> np.ndarray:
        return np.asarray(life, np.int64) << 32 | np.asarray(seq, np.int64)

    def missing(self, life, r_seq) -> np.ndarray:
        """The owed (life, publish) pairs no receipt answers: a mask
        over ``owed_seq``."""
        got = self._pairs(life, r_seq)[np.asarray(life) >= 0]
        return ~np.isin(self._pairs(self.owed_life, self.owed_seq), got)

    def judge(self, publishers: int, r_conn, r_seq, r_t, r_qos) -> tuple:
        """``(missing, unexpected, duplicated, out_of_order, wrong_qos,
        the sequence numbers of the owed publishes that are missing)``
        over the churned subscriptions."""
        r_seq = np.asarray(r_seq, np.int64)
        r_conn = np.asarray(r_conn, np.int64)
        life = self.attribute(r_conn, r_seq, r_t)
        miss = self.missing(life, r_seq)
        mine = life >= 0
        # nobody's receipts: unexpected once a (connection, publish)
        stray = np.unique(self._pairs(r_conn[~mine], r_seq[~mine]))
        lf, sq = life[mine], r_seq[mine]
        pos = np.minimum(np.searchsorted(self.p_seq, sq),
                         max(len(self.p_seq) - 1, 0))
        sent = (len(self.p_seq) > 0) & (self.p_seq[pos] == sq)
        ack, send = self.p_ack[pos], self.p_send[pos]
        band = sent & ((ack > self.s0[lf]) | (ack == 0)) & (send < self.u1[lf])
        pairs = self._pairs(lf, sq)
        uniq = np.unique(pairs)
        unexpected = len(stray) + len(np.unique(pairs[~band]))
        dups = len(pairs) - len(uniq)
        # publish order per publisher and topic within one life, as the
        # session sees it: the receipts in arrival order
        order = np.argsort(np.asarray(r_t)[mine], kind="stable")
        key = ((lf * self.n_pool + sq % self.n_pool) * publishers
               + sq % publishers)[order]
        by = np.argsort(key, kind="stable")
        hs, ks = sq[order][by], key[by]
        disorder = int(((np.diff(hs) <= 0) & (np.diff(ks) == 0)).sum()) - dups
        # granted QoS: min(publish QoS 1, subscription QoS)
        bad = np.asarray(r_qos)[mine] != np.minimum(self.qos[lf], 1)
        wrong_qos = len(np.unique(lf[bad]))
        return (int(miss.sum()), unexpected, dups, max(disorder, 0),
                wrong_qos, self.owed_seq[miss])


def _diff(have: np.ndarray, want: np.ndarray):
    """``(missing, unexpected, duplicates)`` of ``have`` against the
    sorted, duplicate-free ``want``; the first two as arrays."""
    uniq, counts = np.unique(have, return_counts=True)
    missing = np.setdiff1d(want, uniq, assume_unique=True)
    unexpected = np.setdiff1d(uniq, want, assume_unique=True)
    return missing, unexpected, int((counts - 1).sum())


def judge(exp: Expected, publishers: int, acked: np.ndarray,
          received: list, qos_seen: list, fired_rule: np.ndarray,
          fired_seq: np.ndarray, device: dict, churn_got=None) -> tuple:
    """The numbers compared, each ``(name, value, limit)``, and the
    sequence numbers of the publishes that failed (a PUBACK, a delivery
    or a firing they owe is missing).

    ``acked``: the sequence numbers whose PUBACK came back;
    ``received[j]``: subscriber j's deliveries in arrival order;
    ``qos_seen[j]``: bit mask of the QoS its deliveries came at;
    ``fired_*``: every rule firing the actions saw; ``device``: the
    counts that say which steps the device served; ``churn_got``: the
    churned connections' receipts ``(conn, seq, instant, qos)``, judged
    by `Churned.judge` where ``exp`` has a churn, whose unanswered lives
    add to ``device``'s ``client_errors``.

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
    if exp.churn is not None:
        m, u, d, o, q, lost = exp.churn.judge(publishers, *churn_got)
        failed.append(lost)
        missing, unexpected, dups = missing + m, unexpected + u, dups + d
        disorder, wrong_qos = disorder + o, wrong_qos + q
        if exp.churn.unanswered:
            device = dict(device, client_errors=exp.churn.unanswered
                          + device.get("client_errors", 0))
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
