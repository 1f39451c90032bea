"""Fan-in through MQTT 5 shared subscriptions (MQTT 5.0 section 4.8.2;
EMQX's emqtt-bench shared-subscription scenario): a fleet of devices
publishes into a few streams, and pools of back-end consumers each take
a stream load-balanced, one member a message.

Topics are ``fanin/s<stream>/d<device>``.  A group is a name, a filter
and a member count: members ``<name>-<k>`` each hold
``$share/<name>/<filter>``.  Two groups may share a filter (two pools
each owed the whole stream: ingest and archive), a filter may be a
wildcard, and a client may hold plain filters beside its shared one.
The data file says which; `referee.py` holds the live set to one rule:
no pool topic matches two filters of one client, plain or shared.

Standard library and numpy only (README.md, "A generator").
"""

ROOT = "fanin"


def live(groups: list, plain: list = ()):
    """``groups``: ``[[name, filter, members], ...]``; ``plain``:
    ``[[filter, [clientid, ...]], ...]``, a plain subscription for each
    client named: a member's id puts it beside the member's shared
    filter, another id is a subscriber of its own, added in the order
    named.  QoS 0/1 alternating in client order.  Returns
    ``[(clientid, [filters], qos)]``."""
    clients: dict = {}
    for name, flt, members in groups:
        if members < 1:
            raise ValueError(f"group {name!r}: {members} members")
        for k in range(members):
            cid = f"{name}-{k}"
            if cid in clients:
                raise ValueError(f"group {name!r} is named twice")
            clients[cid] = [f"$share/{name}/{flt}"]
    for flt, holders in plain:
        for cid in holders:
            clients.setdefault(cid, []).append(flt)
    return [(cid, flts, j % 2)
            for j, (cid, flts) in enumerate(clients.items())]


def pool(rng, pops, pool: int, streams: int, devices: int):
    """``pool`` topics, stream and device each drawn uniformly (every
    device publishes at the same rate); ``pops`` is the table's and
    unused: the fleet's ids are the data file's to say."""
    if pool < 1 or streams < 1 or devices < 1:
        raise ValueError(
            f"pool, streams, devices: {pool}, {streams}, {devices}"
        )
    s = rng.integers(0, streams, size=pool).tolist()
    d = rng.integers(0, devices, size=pool).tolist()
    return [f"{ROOT}/s{a}/d{b}" for a, b in zip(s, d)]
