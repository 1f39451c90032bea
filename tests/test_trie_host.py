"""HostTrie oracle tests: hand cases + randomized equivalence against the
brute-force word matcher (the property-test pattern the reference applies
to its matchers, e.g. emqx_trie_search semantics cases)."""

import random

import pytest

from emqx_tpu import topic as T
from emqx_tpu.ops.trie_host import HostTrie


def build(filters):
    t = HostTrie()
    for i, f in enumerate(filters):
        t.insert(f, i)
    return t


def ids(t, name):
    return t.match(name)


def test_basic_match():
    t = build(["a/b/c", "a/+/c", "a/#", "#", "x/y"])
    assert ids(t, "a/b/c") == {0, 1, 2, 3}
    assert ids(t, "a/z/c") == {1, 2, 3}
    assert ids(t, "a") == {2, 3}
    assert ids(t, "x/y") == {3, 4}
    assert ids(t, "q") == {3}


def test_dollar_exclusion():
    t = build(["#", "+/broker", "$SYS/#", "$SYS/+"])
    assert ids(t, "$SYS/broker") == {2, 3}
    assert ids(t, "other/broker") == {0, 1}
    assert ids(t, "$SYS") == {2}


def test_hash_parent():
    t = build(["sport/#"])
    assert ids(t, "sport") == {0}
    assert ids(t, "sport/tennis/x") == {0}
    assert ids(t, "sports") == set()


def test_empty_levels():
    t = build(["a/+/c", "+/b", "a/+", "#"])
    assert ids(t, "a//c") == {0, 3}
    assert ids(t, "/b") == {1, 3}
    assert ids(t, "a/") == {2, 3}


def test_delete_and_replace():
    t = HostTrie()
    t.insert("a/+", "s1")
    t.insert("a/#", "s2")
    assert t.match("a/b") == {"s1", "s2"}
    assert t.delete_id("s1")
    assert t.match("a/b") == {"s2"}
    assert not t.delete_id("s1")
    # replace same id with a new filter
    t.insert("c/d", "s2")
    assert t.match("a/b") == set()
    assert t.match("c/d") == {"s2"}
    assert len(t) == 1


def test_prune_keeps_shared_prefixes():
    t = HostTrie()
    t.insert("a/b/c", 1)
    t.insert("a/b", 2)
    t.delete_id(1)
    assert t.match("a/b") == {2}
    t.delete_id(2)
    assert t.match("a/b") == set()
    assert len(t._root.children) == 0


WORDS = ["a", "b", "c", "dev", "42", "", "$SYS", "$x", "longish-word"]


def rand_filter(rng):
    n = rng.randint(1, 6)
    ws = []
    for i in range(n):
        r = rng.random()
        if r < 0.2:
            ws.append("+")
        elif r < 0.3 and i == n - 1:
            ws.append("#")
        else:
            ws.append(rng.choice(WORDS))
    return "/".join(ws)


def rand_name(rng):
    n = rng.randint(1, 6)
    return "/".join(rng.choice(WORDS) for _ in range(n))


@pytest.mark.parametrize("seed", range(8))
def test_randomized_equivalence(seed):
    rng = random.Random(seed)
    filters = [rand_filter(rng) for _ in range(300)]
    t = build(filters)
    for _ in range(300):
        name = rand_name(rng)
        assert t.match(name) == t.match_brute(name), name


def test_randomized_with_deletions():
    rng = random.Random(99)
    t = HostTrie()
    alive = {}
    for step in range(2000):
        op = rng.random()
        if op < 0.55 or not alive:
            fid = rng.randint(0, 500)
            f = rand_filter(rng)
            t.insert(f, fid)
            alive[fid] = f
        else:
            fid = rng.choice(list(alive))
            assert t.delete_id(fid)
            del alive[fid]
        if step % 100 == 0:
            name = rand_name(rng)
            assert t.match(name) == t.match_brute(name)
    assert len(t) == len(alive)


# ---------------------------------------------------------------- native

def _native_or_skip():
    from emqx_tpu.ops.trie_native import NativeTrie, load

    if load() is None:
        import pytest

        pytest.skip("native hosttrie unavailable")
    return NativeTrie()


@pytest.mark.parametrize("seed", range(6))
def test_native_trie_equivalence(seed):
    """NativeTrie (C++) must agree with HostTrie (the Python oracle) on
    randomized insert/delete/match churn, including '$'-topics, empty
    levels, and fid reuse across different filters."""
    import random

    from emqx_tpu import topic as T
    from emqx_tpu.ops.trie_host import HostTrie

    rng = random.Random(7000 + seed)
    native = _native_or_skip()
    py = HostTrie()
    words = ["a", "b", "c", "dev", "x1", "", "$SYS", "+", "#"]
    live = set()
    for step in range(1500):
        op = rng.random()
        if op < 0.55 or not live:
            depth = rng.randint(1, 4)
            ws = [rng.choice(words) for _ in range(depth)]
            flt = "/".join(ws)
            try:
                T.validate_filter(flt)
            except ValueError:
                continue
            fid = rng.choice(
                ["s%d" % rng.randint(0, 300), rng.randint(0, 300),
                 ("rule", rng.randint(0, 50))]
            )
            native.insert(flt, fid)
            py.insert(flt, fid)
            live.add(fid)
        else:
            fid = rng.choice(sorted(live, key=str))
            assert native.delete_id(fid) == py.delete_id(fid)
            live.discard(fid)
        if step % 100 == 99:
            assert len(native) == len(py)
            for _ in range(30):
                depth = rng.randint(1, 5)
                t = "/".join(
                    rng.choice(["a", "b", "c", "dev", "x1", "", "$SYS", "q9"])
                    for _ in range(depth)
                )
                assert native.match(t) == py.match_words(T.words(t)), t


def test_native_trie_large_matchset_grows_buffer():
    native = _native_or_skip()
    for i in range(5000):
        native.insert("big/#", i)
    got = native.match("big/one/two")
    assert got == set(range(5000))


def test_make_trie_python_fallback(monkeypatch):
    """The Python HostTrie serves when the native lib is unavailable
    (a failed build or load) — the fallback path must survive the
    C++17 rewrite making the native trie available everywhere."""
    from emqx_tpu.ops import nativelib, trie_native
    from emqx_tpu.ops.trie_host import HostTrie

    native = trie_native.load()
    with monkeypatch.context() as m:
        m.setitem(nativelib._libs, "hosttrie", None)
        t = trie_native.make_trie()
        assert isinstance(t, HostTrie)
        t.insert("a/+/c", "f1")
        t.insert("a/#", "f2")
        assert t.match("a/b/c") == {"f1", "f2"}
    if native is not None:
        assert not isinstance(trie_native.make_trie(), HostTrie)
