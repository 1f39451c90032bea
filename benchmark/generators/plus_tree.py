"""A deployment of single-level wildcards and nothing else: the tree of
BASELINE.json configs[1] ("100K subs with single-level '+' wildcards,
uniform publish").

Topics are ``tele/<id_1>/.../<id_k>`` under one literal root (so no
filter starts with a wildcard and the ``$`` rule never applies), ``k``
id levels with a population each (``levels``; level ``l``'s ids are
``a0..``, ``b0..``, ...).  A filter is a topic of the tree with the
levels of one *mask* replaced by ``+``: a mask is a string of ``k``
letters ``L`` (literal) and ``+``, at least one ``+``, never ``#``.
Depth, populations and masks are the configuration file's to say: they
decide how wide the matcher's frontier grows and how many filters a
topic hits.

Standard library and numpy only (README.md, "A generator").
"""

from math import prod

ROOT = "tele"
NO_MATCH = "nomatch"


def _check(levels, masks) -> None:
    if not levels or len(levels) > 26 or any(
            not isinstance(n, int) or n < 1 for n in levels):
        raise ValueError(f"levels: 1 to 26 populations of 1 or more: {levels}")
    if not masks:
        raise ValueError("masks: none given")
    for mask in masks:
        if len(mask) != len(levels) or set(mask) - {"L", "+"} \
                or "+" not in mask:
            raise ValueError(
                f"mask {mask!r}: {len(levels)} letters of 'L' and '+', "
                f"at least one '+'"
            )


def _room(levels, mask) -> int:
    """How many distinct filters a mask has in the tree."""
    return prod(n for n, m in zip(levels, mask) if m == "L")


def _filter(levels, mask, code: int) -> str:
    """The mask's filter number ``code``: its literal levels are the
    digits of ``code`` in the mixed radix of their populations, the first
    literal level turning fastest.  Codes below `_room` give distinct
    filters, each matched by every topic that has those ids."""
    words = [ROOT]
    for at, (n, m) in enumerate(zip(levels, mask)):
        if m == "+":
            words.append("+")
        else:
            code, digit = divmod(code, n)
            words.append(f"{chr(97 + at)}{digit}")
    return "/".join(words)


def table(subscriptions: int, levels: list, masks: list, fanout: int = 1):
    """``subscriptions`` pairs ``(filter, fid)``, ``fanout`` of them on
    each filter, and the populations the pool draws from.  ``masks`` is
    ``[[mask, weight], ...]``: the distinct filters are shared out by
    weight, a mask's being its codes ``0 .. share - 1``."""
    _check(levels, [m for m, _w in masks])
    if subscriptions < 1 or fanout < 1 or any(w <= 0 for _m, w in masks):
        raise ValueError("subscriptions, fanout and every weight are positive")
    n_filters = -(-subscriptions // fanout)
    total = sum(w for _m, w in masks)
    shares = [n_filters * w // total for _m, w in masks]
    shares[0] += n_filters - sum(shares)
    filters = []
    for (mask, _w), share in zip(masks, shares):
        if share > _room(levels, mask):
            raise ValueError(
                f"mask {mask!r} has {_room(levels, mask)} distinct filters "
                f"in this tree, its share is {share}"
            )
        filters += [_filter(levels, mask, c) for c in range(share)]
    return [(filters[i % n_filters], i) for i in range(subscriptions)], \
        tuple(levels)


def live(subscribers: int, filters_each: int, levels: list, masks: list,
         qos=None):
    """Subscriber ``j`` holds ``filters_each`` filters of mask
    ``masks[j % len(masks)]`` with consecutive codes: they differ in a
    literal level, so no topic matches two of them.  QoS 0/1 alternating
    unless ``qos`` fixes it.  Returns ``[(clientid, [filters], qos)]``."""
    _check(levels, masks)
    rooms = [_room(levels, mask) for mask in masks]
    for mask, room in zip(masks, rooms):
        if filters_each > room:
            raise ValueError(
                f"mask {mask!r} has {room} distinct filters in this tree, "
                f"a subscriber wants {filters_each}"
            )
    out = []
    for j in range(subscribers):
        mask, room = masks[j % len(masks)], rooms[j % len(masks)]
        first = j // len(masks) * filters_each
        out.append((
            f"sub{j}",
            [_filter(levels, mask, (first + n) % room)
             for n in range(filters_each)],
            j % 2 if qos is None else qos,
        ))
    return out


def pool(rng, pops, pool: int, nomatch: float = 0.0):
    """``pool`` topics, each id level drawn uniformly from its
    population (the source's "uniform publish"); the last ``nomatch`` of
    them (a share, 0 to 1) lie under another root and match nothing."""
    if not 0.0 <= nomatch <= 1.0:
        raise ValueError(f"nomatch is a share of the pool: {nomatch}")
    inside = pool - int(round(pool * nomatch))
    ids = [rng.integers(0, n, size=inside).tolist() for n in pops]
    out = ["/".join([ROOT] + [f"{chr(97 + at)}{col[i]}"
                              for at, col in enumerate(ids)])
           for i in range(inside)]
    return out + [f"{NO_MATCH}/q{i}" for i in range(inside, pool)]
