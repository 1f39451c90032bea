"""The benchmark's one general generator: tables, rules, live
subscribers, topic pools, churned filters and schedules, all from
parameters in the configuration's and the cell's data files and from
``--seed``.

A data file's ``table``, ``live``, ``topics`` or ``churn`` group names
its ``generator``; the group's other keys are that generator's
arguments (a ``churn`` group's schedule keys apart: `CHURN_KEYS`).
`generator` finds it by name: the seven built-ins below first
(`TABLES`, `LIVE`, `POOLS`, `CHURNS`), then ``generators/<name>.py``
and its function ``table``, ``live``, ``pool`` or ``churn`` (README.md,
"A generator" and "A churn group", has the contract).  So a later
deployment brings its generators as a new file.

Imports nothing of the program (`emqx_tpu`) and no JAX, and neither
does a generator file.  The fleet generators are copies of
`bench.make_filters` / `bench.make_topics` and of `chip_smoke.rule_sql`
/ `live_filters` / `payload_of` (see PERF.md, Open questions: the
originals are a later PR's to delete).

Every seed gets the SAME multiset of topics, gaps and sizes in another
order: pools are drawn from the fixed ``pool_seed`` of the data file,
``--seed`` only permutes them.  So two seeds do the same work.
"""

import importlib.util
import inspect
import os
import re

import numpy as np

SEQ_AT = 7          # payload[SEQ_AT:SEQ_AT + SEQ_W] is the sequence number
SEQ_W = 10


# ------------------------------------------------------------- payload

def payload_of(seq: int) -> bytes:
    """The ~70 B JSON reading of `chip_smoke.payload_of`, every field at
    a fixed width (space-padded numbers are valid JSON), so the sequence
    number can be sliced out without parsing."""
    return (
        b'{"seq":%10d,"temp":%2d,"hum":%2d,"dev":"d%d","ok":%s}'
        % (seq, seq * 7 % 50, seq * 13 % 100, seq % 7,
           b"true " if seq % 3 == 0 else b"false")
    )


# -------------------------------------------------------------- tables

def table_fleet_families(subscriptions: int, fanout: int):
    """`bench.make_filters`: four fleet-telemetry wildcard families,
    ``fanout`` subscriptions sharing each filter.  Returns
    ``[(filter, fid)]`` and the id populations the topic pool needs."""
    n = subscriptions
    n_vehicles = max(n // (2 * fanout), 1)
    n_dev = max(n // (5 * fanout), 1)
    n_site = max(n // (5 * fanout), 1)
    n_alert = max(n // (10 * fanout), 1)
    pairs = []
    for i in range(n):
        kind = i % 10
        if kind < 5:
            pairs.append((f"vehicles/v{i % n_vehicles}/sensors/#", i))
        elif kind < 7:
            pairs.append((f"dev/g{i % n_dev}/+/d{i % 7}", i))
        elif kind < 9:
            pairs.append((f"site/+/floor/f{i % n_site}/#", i))
        else:
            pairs.append((f"alerts/z{i % n_alert}/+/+", i))
    return pairs, (n_vehicles, n_dev, n_site, n_alert)


def table_none(**_):
    return [], (1, 1, 1, 1)


TABLES = {"fleet_families": table_fleet_families, "none": table_none}


# --------------------------------------------------------------- rules

def rule_sql(i: int) -> str:
    """`chip_smoke.rule_sql`: five arithmetic-free WHERE templates."""
    kind = i % 5
    if kind == 0:
        return (f'SELECT payload.seq as seq FROM "vehicles/+/sensors/#" '
                f"WHERE payload.temp > {i % 40}")
    if kind == 1:
        return (f'SELECT * FROM "dev/#" WHERE payload.dev = \'d{i % 7}\' '
                f"and payload.hum <= {20 + i % 60}")
    if kind == 2:
        return (f'SELECT topic FROM "site/+/floor/#" WHERE '
                f"payload.temp >= {i % 30} or not (payload.hum < {i % 50})")
    if kind == 3:
        return (f'SELECT clientid FROM "vehicles/#" WHERE '
                f"payload.dev in ('d{i % 7}', 'd{(i + 3) % 7}') "
                f"and is_not_null(payload.hum)")
    return (f'SELECT payload FROM "#" WHERE payload.temp = {i % 50} '
            f"and payload.dev != 'd{i % 7}'")


# ---------------------------------------------------- live subscribers

def live_fleet(subscribers: int, filters_each: int):
    """`chip_smoke.live_filters`: subscriber ``j`` holds ``filters_each``
    wildcard filters, disjoint from one another; QoS 0/1 alternating.
    Returns ``[(clientid, [filters], qos)]``."""
    out = []
    stride = subscribers // 5 + 1
    for j in range(subscribers):
        kind, flts = j % 5, []
        for n in range(filters_each):
            k = j // 5 + 1 + n * stride
            if kind == 0:
                flts.append(f"vehicles/v{k}/sensors/#")
            elif kind == 1:
                flts.append(f"dev/g{k}/+/d{k % 7}")
            elif kind == 2:
                flts.append(f"site/+/floor/f{k}/#")
            elif kind == 4:
                flts.append(f"vehicles/v{k}/#")
            elif n:
                flts.append(f"site/+/floor/f{k}/a")
            else:
                flts.append(
                    "vehicles/+/sensors/temp" if k % 2 else "dev/+/x/+"
                )
        out.append((f"sub{j}", flts, j % 2))
    return out


def live_exact_fanout(subscribers: int, topics: int, qos=None):
    """emqtt-bench's fan-out scenario: one exact-match subscription a
    connection, ``subscribers / topics`` on each topic; QoS 0/1
    alternating unless ``qos`` fixes it."""
    return [
        (f"sub{j}", [f"fanout/t{j % topics}"], j % 2 if qos is None else qos)
        for j in range(subscribers)
    ]


LIVE = {"fleet_live": live_fleet, "exact_fanout": live_exact_fanout}


# ---------------------------------------------------------- topic pools

def pool_fleet_zipf(rng, pool: int, pops, zipf: float = 1.3):
    """`bench.make_topics`: 60% vehicle readings with Zipf ids, 20% dev,
    10% site, 10% that match nothing."""
    n_vehicles, n_dev, n_site, _ = pops
    z = rng.zipf(zipf, size=pool) % max(n_vehicles, 1)
    out = []
    for i in range(pool):
        k = i % 10
        if k < 6:
            out.append(f"vehicles/v{z[i]}/sensors/temp")
        elif k < 8:
            out.append(f"dev/g{i % n_dev}/x/d{i % 7}")
        elif k < 9:
            out.append(f"site/s{i % 7}/floor/f{i % n_site}/a")
        else:
            out.append(f"nomatch/q{i}")
    return out


def pool_exact(rng, pool: int, pops):
    return [f"fanout/t{k}" for k in range(pool)]


POOLS = {"fleet_zipf": pool_fleet_zipf, "exact_topics": pool_exact}


# ----------------------------------------------------- churned filters

def churn_fleet(rng, pops, filters: int, first_id: int):
    """The fleet's own three families over ids from ``first_id`` on,
    which the live set does not hold (`live_fleet`'s ids end at
    ``(subscribers - 1) // 5 + 1 + (filters_each - 1) * (subscribers //
    5 + 1)``, 243 for the fleet's 300 x 4): ``filters`` distinct
    wildcard filters, a third each of
    ``vehicles/v<k>/sensors/#``, ``dev/g<k>/+/d<k % 7>`` and
    ``site/+/floor/f<k>/#``, disjoint on any pool.  The ids the pool
    draws (`pool_fleet_zipf`: Zipf vehicle ids, device groups and sites
    below the table's populations) are the only ones that receive."""
    if filters < 1 or first_id < 0:
        raise ValueError("filters must be >= 1 and first_id >= 0")
    out = []
    for n in range(filters):
        k = first_id + n // 3
        if n % 3 == 0:
            out.append(f"vehicles/v{k}/sensors/#")
        elif n % 3 == 1:
            out.append(f"dev/g{k}/+/d{k % 7}")
        else:
            out.append(f"site/+/floor/f{k}/#")
    return out


CHURNS = {"fleet_churn": churn_fleet}
# a churn group's keys that are the schedule's, not the generator's
CHURN_KEYS = ("clients", "rate", "dwell_s", "qos", "churn_children")


# ------------------------------------------------- generators by name

GENERATORS = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                          "generators")
BUILT_IN = {"table": TABLES, "live": LIVE, "pool": POOLS, "churn": CHURNS}


class BadGenerator(Exception):
    """A data file names a generator nobody has, or hands one an argument
    it does not take; `run.py` refuses the run."""


def generator(kind: str, name: str):
    """The ``kind`` (``table``, ``live``, ``pool`` or ``churn``)
    generator a data file calls ``name``: the built-in of that name,
    else the function ``kind`` of ``generators/<name>.py``.  A file may
    not bear a built-in's name: a new file never changes what a cell
    that is there runs."""
    path = os.path.join(GENERATORS, f"{name}.py")
    on_file = re.fullmatch(r"\w+", str(name)) and os.path.exists(path)
    if on_file and any(name in d for d in BUILT_IN.values()):
        raise BadGenerator(
            f"{path} bears the name of a built-in generator of "
            f"traffic.py: give the file another name"
        )
    if name in BUILT_IN[kind]:
        return BUILT_IN[kind][name]
    if not on_file:
        raise BadGenerator(
            f"no {kind} generator {name!r}: not among traffic.py's "
            f"{sorted(BUILT_IN[kind])}, and there is no {path}"
        )
    spec = importlib.util.spec_from_file_location("generator_" + name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    if not callable(getattr(mod, kind, None)):
        raise BadGenerator(f"{path} has no function {kind!r}")
    return getattr(mod, kind)


def generate(kind: str, group: dict, *args, **kwargs):
    """What the generator a data file's ``group`` names makes of the
    group's other keys.  A key it does not take is refused, not left to
    run as the default; so is a value it raises `ValueError` over."""
    group = dict(group)
    name = group.pop("generator")
    fn = generator(kind, name)
    try:
        inspect.signature(fn).bind(*args, **kwargs, **group)
    except TypeError as e:
        raise BadGenerator(f"{kind} generator {name!r}: {e}") from None
    try:
        return fn(*args, **kwargs, **group)
    except ValueError as e:
        raise BadGenerator(f"{kind} generator {name!r}: {e}") from e


def topic_pool(spec: dict, pops, seed: int, publishers: int):
    """The cell's topic pool in this seed's order.  Publish ``seq`` goes
    out on connection ``seq % publishers`` with topic
    ``pool[seq % len(pool)]``.  A pool no longer than the publisher
    count keeps its order (one topic a publisher, as the exact cell
    wants); any other is permuted by the seed."""
    spec = dict(spec)
    pool_seed = spec.pop("pool_seed", 1)
    pool = generate("pool", spec, np.random.default_rng(pool_seed), pops=pops)
    if len(pool) > publishers:
        order = np.random.default_rng(seed).permutation(len(pool))
        pool = [pool[i] for i in order]
    return pool


def churn_filters(spec: dict, pops, seed: int):
    """A ``churn`` group's filters in this seed's order: the generator
    draws from the group's fixed ``pool_seed`` (default 1) and
    ``--seed`` only permutes the list, as `topic_pool` does, so every
    seed churns the same filters."""
    spec = {k: v for k, v in spec.items() if k not in CHURN_KEYS}
    pool_seed = spec.pop("pool_seed", 1)
    flts = generate("churn", spec, np.random.default_rng(pool_seed),
                    pops=pops)
    if len(set(flts)) != len(flts):
        raise BadGenerator("churn generator: its filters are not distinct")
    order = np.random.default_rng(seed).permutation(len(flts))
    return [flts[i] for i in order]


def poisson_schedule(rate: float, seconds: float, seed: int,
                     pool_seed: int = 1):
    """Due offsets (seconds after the window opens) of an open-loop
    Poisson stream: the gaps are drawn once from ``pool_seed``, scaled
    to fill the window exactly, and permuted by ``seed`` — every seed
    offers the same number of publishes with the same gaps."""
    n = int(round(rate * seconds))
    gaps = np.random.default_rng(pool_seed).exponential(1.0, size=n + 1)
    gaps *= seconds / gaps.sum()
    gaps = gaps[np.random.default_rng(seed).permutation(n + 1)]
    return np.cumsum(gaps)[:n]
