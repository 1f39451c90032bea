"""Equivalence tests: device automaton matcher vs the host-trie oracle.

Mirrors the reference's oracle pattern (`emqx_ds_storage_reference` as a
trivially-correct stand-in, and the emqx_trie_search property suites):
randomized filter/topic sets over a tiny alphabet maximize wildcard
overlap and structural edge cases ('$'-topics, empty levels, '#'-parent
matching, deep '+' chains)."""

import random

import pytest

from emqx_tpu import topic as T
from emqx_tpu.engine import MatchEngine
from emqx_tpu.ops.automaton import build_automaton
from emqx_tpu.ops.dictionary import TokenDict
from emqx_tpu.ops.trie_host import HostTrie

WORDS = ["a", "b", "c", "", "dev", "$SYS", "$share-ish", "x"]


def random_filter(rng: random.Random) -> str:
    depth = rng.randint(1, 6)
    ws = []
    for i in range(depth):
        r = rng.random()
        if r < 0.18:
            ws.append("+")
        elif r < 0.28 and i == depth - 1:
            ws.append("#")
        else:
            ws.append(rng.choice(WORDS))
    return "/".join(ws)


def random_topic(rng: random.Random) -> str:
    depth = rng.randint(1, 7)
    return "/".join(rng.choice(WORDS) for _ in range(depth))


def check_engine_vs_oracle(engine, oracle_trie, exact_map, topics):
    got = engine.match_batch(topics)
    for t, g in zip(topics, got):
        ws = T.words(t)
        want = set(exact_map.get(t, set())) | oracle_trie.match_words(ws)
        assert g == want, (t, sorted(map(str, g)), sorted(map(str, want)))


@pytest.mark.parametrize("seed", range(8))
def test_randomized_equivalence(seed):
    rng = random.Random(seed)
    engine = MatchEngine(max_levels=8, f_width=8, m_cap=64)
    oracle = HostTrie()
    exact = {}
    for fid in range(300):
        flt = random_filter(rng)
        try:
            T.validate_filter(flt)
        except ValueError:
            continue
        engine.insert(flt, fid)
        if T.is_wildcard(flt):
            oracle.insert(flt, fid)
        else:
            exact.setdefault(flt, set()).add(fid)
    engine.rebuild()
    topics = [random_topic(rng) for _ in range(200)]
    # include every filter's concrete-ized form to force exact hits
    for _, ws in list(oracle.filters())[:50]:
        concrete = [rng.choice(WORDS) if w in "+#" else w for w in ws]
        topics.append("/".join(concrete))
    check_engine_vs_oracle(engine, oracle, exact, topics)


@pytest.mark.parametrize("seed", range(4))
def test_churn_delta_and_delete(seed):
    """Mutations after rebuild must be visible without a rebuild."""
    rng = random.Random(1000 + seed)
    engine = MatchEngine(max_levels=8, rebuild_threshold=10**9)
    oracle = HostTrie()
    exact = {}
    fid = 0
    live = {}
    for round_ in range(4):
        for _ in range(120):
            flt = random_filter(rng)
            try:
                T.validate_filter(flt)
            except ValueError:
                continue
            engine.insert(flt, fid)
            live[fid] = flt
            if T.is_wildcard(flt):
                oracle.insert(flt, fid)
            else:
                exact.setdefault(flt, set()).add(fid)
            fid += 1
        if round_ == 1:
            engine.rebuild()
        # delete a third of live filters
        for del_fid in list(live)[:: 3]:
            flt = live.pop(del_fid)
            engine.delete(del_fid)
            if T.is_wildcard(flt):
                oracle.delete_id(del_fid)
            else:
                exact[flt].discard(del_fid)
        topics = [random_topic(rng) for _ in range(80)]
        check_engine_vs_oracle(engine, oracle, exact, topics)


def test_dollar_topic_rules():
    engine = MatchEngine()
    engine.insert("#", 1)
    engine.insert("+/monitor", 2)
    engine.insert("$SYS/monitor", 3)
    engine.insert("$SYS/#", 4)
    engine.insert("$SYS/+", 5)
    engine.rebuild()
    assert engine.match("$SYS/monitor") == {3, 4, 5}
    assert engine.match("a/monitor") == {1, 2}
    assert engine.match("$SYS") == {4}


def test_hash_matches_parent_level():
    engine = MatchEngine()
    engine.insert("sport/tennis/#", 1)
    engine.rebuild()
    assert engine.match("sport/tennis") == {1}
    assert engine.match("sport/tennis/player1/score") == {1}
    assert engine.match("sport") == set()


def test_empty_levels():
    engine = MatchEngine()
    engine.insert("a//b", 1)
    engine.insert("a/+/b", 2)
    engine.insert("/+", 3)
    engine.rebuild()
    assert engine.match("a//b") == {1, 2}
    assert engine.match("/x") == {3}
    assert engine.match("/") == {3}  # ('', '')


def test_frontier_overflow_falls_back():
    """More live branches than f_width must still return exact results
    via the host fallback (overflow flag path)."""
    engine = MatchEngine(max_levels=8, f_width=2, m_cap=4)
    # many '+'-chains all alive at once
    for i in range(12):
        pat = ["+"] * 4
        pat[i % 4] = "w%d" % (i % 3)
        engine.insert("/".join(pat), i)
    engine.insert("w0/+/+/+", 100)
    engine.rebuild()
    topic = "w0/w1/w2/w0"
    want = {
        fid
        for fid, ws in engine._wild.filters()
        if T.match_words(T.words(topic), ws)
    }
    assert engine.match(topic) == want


def test_too_deep_topic_falls_back():
    engine = MatchEngine(max_levels=4)
    engine.insert("a/#", 1)
    engine.rebuild()
    deep = "a/" + "/".join("x%d" % i for i in range(10))
    assert engine.match(deep) == {1}


def test_automaton_structure_small():
    td = TokenDict()
    aut = build_automaton(
        [(1, ("a", "b")), (2, ("a", "#")), (3, ("a", "+"))], td, max_levels=4
    )
    # nodes: root, a, a/b, a/+  -> 4
    assert aut.n_nodes == 4
    assert (aut.node_rows[:, 1] > 0).sum() == 1
    assert (aut.node_rows[:, 2] > 0).sum() == 2  # a/b and a/+
    assert (aut.node_rows[:, 0] != 2**31 - 1).sum() == 1
    assert aut.kernel_levels == 3  # deepest body (2) + 1


def test_forced_hash_size_for_sharding():
    td = TokenDict()
    aut = build_automaton([(1, ("a", "b"))], td, hash_buckets=256)
    assert len(aut.fp_rows) == 256


def test_reinsert_changed_filter_after_rebuild():
    """ADVICE r1 (high): re-registering a fid with a different filter
    after a rebuild must not unmask the stale device entry."""
    eng = MatchEngine(use_device=True)
    eng.insert("a/+", 1)
    eng.rebuild()
    eng.insert("b/+", 1)
    assert eng.match("a/x") == set()
    assert eng.match("b/x") == {1}
    eng.rebuild()
    assert eng.match("a/x") == set()
    assert eng.match("b/x") == {1}


def test_delete_then_reinsert_same_filter_after_rebuild():
    eng = MatchEngine(use_device=True)
    eng.insert("a/+", 1)
    eng.rebuild()
    eng.delete(1)
    assert eng.match("a/x") == set()
    eng.insert("a/+", 1)
    assert eng.match("a/x") == {1}


def test_full_depth_filter_does_not_match_deeper_topic():
    """ADVICE r1 (high): body depth == max_levels must still scan one
    level past the body so deeper topics cannot falsely exact-match."""
    eng = MatchEngine(max_levels=4, use_device=True)
    eng.insert("a/b/c/+", 1)
    eng.rebuild()
    assert eng.match("a/b/c/d") == {1}
    assert eng.match("a/b/c/d/e") == set()
    assert eng.match("a/b/c") == set()
    # hash filter at full depth still matches arbitrarily deep
    eng.insert("a/b/c/#", 2)
    eng.rebuild()
    assert eng.match("a/b/c/d/e/f") == {2}


def test_background_rebuild_no_stop_the_world():
    """Mutations during a background rebuild stay correct through the
    swap (emqx_router_syncer-style batching, no synchronous rebuild)."""
    rng = random.Random(7)
    eng = MatchEngine(
        use_device=True, background_rebuild=True, rebuild_threshold=64
    )
    live = {}
    fid = 0
    for round_ in range(6):
        for _ in range(100):
            flt = random_filter(rng)
            try:
                T.validate_filter(flt)
            except ValueError:
                continue
            eng.insert(flt, fid)
            live[fid] = flt
            fid += 1
        # delete a few while a build may be in flight
        for victim in rng.sample(sorted(live), 10):
            eng.delete(victim)
            del live[victim]
        topics = [random_topic(rng) for _ in range(20)]
        got = eng.match_batch(topics)
        for t, g in zip(topics, got):
            want = {
                f for f, w in live.items() if T.match_words(T.words(t), T.words(w))
            }
            assert g == want, (round_, t, g, want)
    # drain: wait for any in-flight build and check again post-swap
    import time

    for _ in range(200):
        if eng._built is not None or not eng._building:
            break
        time.sleep(0.05)
    topics = [random_topic(rng) for _ in range(50)]
    got = eng.match_batch(topics)
    for t, g in zip(topics, got):
        want = {f for f, w in live.items() if T.match_words(T.words(t), T.words(w))}
        assert g == want, (t, g, want)


@pytest.mark.parametrize("seed", range(4))
def test_delta_automaton_churn_equivalence(seed):
    """With a tiny delta-automaton threshold, sustained churn runs
    through the two-tier device path (base automaton + delta automaton
    + host residual) and must stay oracle-equal, including deletes of
    delta-resident filters and a big rebuild dropping the delta tier."""
    rng = random.Random(2000 + seed)
    engine = MatchEngine(
        max_levels=8,
        rebuild_threshold=10**9,
        delta_aut_threshold=32,
    )
    oracle = HostTrie()
    exact = {}
    fid = 0
    live = {}
    built_delta = False
    for round_ in range(5):
        for _ in range(100):
            flt = random_filter(rng)
            try:
                T.validate_filter(flt)
            except ValueError:
                continue
            engine.insert(flt, fid)
            live[fid] = flt
            if T.is_wildcard(flt):
                oracle.insert(flt, fid)
            else:
                exact.setdefault(flt, set()).add(fid)
            fid += 1
        # folds are async and now warm the kernel BEFORE committing;
        # join so the round's checks (and the exercised-path assert)
        # see the committed delta automaton deterministically
        t = engine._fold_thread
        if t is not None and t.is_alive():
            t.join(60)
        built_delta = built_delta or engine._dtier[0] is not None
        if round_ == 0:
            engine.rebuild()  # establish a base; later rounds churn
        if round_ == 3:
            # deletes hitting base AND delta-automaton entries
            for del_fid in list(live)[::2]:
                flt = live.pop(del_fid)
                engine.delete(del_fid)
                if T.is_wildcard(flt):
                    oracle.delete_id(del_fid)
                else:
                    exact[flt].discard(del_fid)
        topics = [random_topic(rng) for _ in range(60)]
        check_engine_vs_oracle(engine, oracle, exact, topics)
    assert built_delta  # the two-tier path was actually exercised
    # a big rebuild folds everything and drops the delta tier
    engine.rebuild()
    assert engine._dtier[0] is None
    topics = [random_topic(rng) for _ in range(60)]
    check_engine_vs_oracle(engine, oracle, exact, topics)


def test_delta_fold_residual_bound():
    """The host residual stays geometrically bounded while the delta
    folds into the device tier (the churn cliff from VERDICT r2 weak
    #4), and table capacity classes keep the compiled-shape set small."""
    engine = MatchEngine(
        max_levels=8, rebuild_threshold=10**9, delta_aut_threshold=64
    )
    engine._fold_async = False  # strict bound needs inline folds
    shapes = set()
    for i in range(4000):
        engine.insert(f"churn/{i % 97}/+/x{i}", i)
        assert engine._residual_count <= max(64, len(engine._delta) // 2), i
        if engine._dtier[0] is not None:
            shapes.add(
                (
                    engine._dtier[0].node_rows.shape,
                    engine._dtier[0].kernel_levels,
                )
            )
    assert engine._dtier[0] is not None
    assert len(engine._daut_fids) + engine._residual_count >= 4000 - 64
    # pow2 node-capacity classes bound the traced-shape set
    assert len(shapes) <= 4


def test_async_fold_churn_equivalence():
    """Randomized churn with ASYNC folds (the production mode): after
    all in-flight folds drain, every match must agree with the oracle —
    covers the delete/reinsert-during-fold tombstone races."""
    import time as _t

    rng = random.Random(1234)
    engine = MatchEngine(
        max_levels=8, rebuild_threshold=10**9, delta_aut_threshold=32
    )
    oracle = HostTrie()
    live = {}
    fid = 0
    for step in range(3000):
        r = rng.random()
        if r < 0.70 or not live:
            flt = random_filter(rng)
            try:
                T.validate_filter(flt)
            except ValueError:
                continue
            fid += 1
            engine.insert(flt, fid)
            if fid in live:
                oracle.delete_id(fid)
            oracle.insert(flt, fid)
            live[fid] = flt
        elif r < 0.85:
            victim = rng.choice(list(live))
            engine.delete(victim)
            oracle.delete_id(victim)
            del live[victim]
        else:  # re-point an existing fid (delete+insert via replace)
            victim = rng.choice(list(live))
            flt = random_filter(rng)
            try:
                T.validate_filter(flt)
            except ValueError:
                continue
            engine.insert(flt, victim)
            oracle.delete_id(victim)
            oracle.insert(flt, victim)
            live[victim] = flt
    # drain in-flight folds
    from tests_fakes import drain_folds

    drain_folds(engine, timeout=20)
    topics = [random_topic(rng) for _ in range(200)]
    check_engine_vs_oracle(engine, oracle, {}, topics)
    assert engine._dtier[0] is not None  # async folds actually ran


def test_reinserted_fid_survives_fold():
    """A fid deleted and re-inserted with a different filter must keep
    matching after the delta fold: tombstones are per-generation (the
    base's stale entry is masked; the fold's current entry is not)."""
    engine = MatchEngine(
        max_levels=8, rebuild_threshold=10**9, delta_aut_threshold=16
    )
    engine._fold_async = False  # deterministic fold points
    for i in range(40):
        engine.insert(f"seed/{i}/+", i)
    engine.rebuild()  # all 40 in the base
    # re-point fid 7 at a different filter (delete+insert via replace)
    engine.insert("moved/here/#", 7)
    assert engine.match("moved/here/x") == {7}
    assert 7 not in engine.match("seed/7/q")
    # force folds until fid 7 lives in the delta automaton
    for i in range(100, 140):
        engine.insert(f"churn/{i}/+", i)
    assert engine._dtier[0] is not None and 7 in engine._daut_fids
    assert engine.match("moved/here/x") == {7}  # the r3 review regression
    assert 7 not in engine.match("seed/7/q")
    # and a deleted fid stays deleted across the fold
    engine.delete(8)
    for i in range(200, 240):
        engine.insert(f"churn2/{i}/+", i)
    assert 8 not in engine.match("seed/8/q")


def test_insert_many_equivalence():
    """insert_many must land in exactly the same state as per-item
    insert: same matches across exact/wild/deep/replaced entries."""
    import random

    rng = random.Random(99)
    pairs = []
    fid = 0
    for _ in range(400):
        flt = random_filter(rng)
        try:
            T.validate_filter(flt)
        except ValueError:
            continue
        pairs.append((flt, fid))
        fid += 1
    # replacements: re-list some fids with different filters
    for i in range(0, len(pairs), 7):
        if "#" not in pairs[i][0]:  # '#/x' would be invalid
            pairs.append((pairs[i][0] + "/x", pairs[i][1]))
    deep = "/".join(f"l{i}" for i in range(12)) + "/+"
    pairs.append((deep, 10_001))  # deep (max_levels=8) path

    one = MatchEngine(max_levels=8, rebuild_threshold=10**9,
                      delta_aut_threshold=10**9)
    many = MatchEngine(max_levels=8, rebuild_threshold=10**9,
                       delta_aut_threshold=10**9)
    for flt, f in pairs:
        one.insert(flt, f)
    for i in range(0, len(pairs), 64):  # windowed, as the syncer does
        many.insert_many(pairs[i:i + 64])

    topics = [random_topic(rng) for _ in range(200)]
    topics.append("l0/l1/l2/l3/l4/l5/l6/l7/l8/l9/l10/l11/zz")
    assert one.match_batch(topics) == many.match_batch(topics)
    assert one.index_stats()["exact"] == many.index_stats()["exact"]

    # an invalid filter anywhere in the window rejects the WHOLE
    # window before any mutation (atomic validation) — no half-applied
    # batches
    import pytest as _pytest
    with _pytest.raises(ValueError):
        many.insert_many([("ok/+", 20_000), ("bad/#/mid", 20_001)])
    assert 20_000 not in many._by_fid
    assert many.match("ok/x") == one.match("ok/x")


def test_insert_many_duplicate_fid_last_wins():
    """A fid listed twice in ONE window must end exactly as per-item
    inserts would: the LAST filter wins everywhere."""
    eng = MatchEngine(max_levels=8, rebuild_threshold=10**9,
                      delta_aut_threshold=10**9)
    eng.insert_many([("a/+", 1), ("b/+", 1)])
    assert eng.match("a/x") == set()
    assert eng.match("b/x") == {1}
    assert eng._by_fid[1] == "b/+"
    # and with a pre-existing registration in the same engine
    eng.insert_many([("c/+", 1), ("d/+", 1), ("e/+", 2)])
    assert eng.match("b/x") == set()
    assert eng.match("c/x") == set()
    assert eng.match("d/x") == {1}
    assert eng.match("e/x") == {2}


def test_compact_clip_rematches_dense_and_steps_the_ladder():
    """A window whose hits outgrow the compact buffer is matched
    again on the dense kernel, exactly, and the capacity multiplier
    doubles so that the next such window fits."""
    engine = MatchEngine(
        max_levels=8, f_width=32, m_cap=64, use_device=True
    )
    oracle = HostTrie()
    # twelve filters that every topic g/a/b/<x> matches
    plus = [
        "g/a/b/+", "+/a/b/+", "g/+/b/+", "g/a/+/+", "+/+/b/+",
        "+/a/+/+", "g/+/+/+", "+/+/+/+",
    ]
    hashes = ["#", "g/#", "g/a/#", "g/a/b/#"]
    for fid, flt in enumerate(plus + hashes):
        engine.insert(flt, fid)
        oracle.insert(flt, fid)
    engine.rebuild()
    first = engine._ccap_mult
    topics = [f"g/a/b/c{i}" for i in range(16)]
    # 16 unique topics x 12 hits each: 192 codes, past the first
    # rung's first * 16 and inside the next
    assert first * 16 < 12 * len(topics) <= 2 * first * 16
    check_engine_vs_oracle(engine, oracle, {}, topics)
    assert engine._ccap_mult == 2 * first
    # the next rung holds the same window: no clip, no further step
    check_engine_vs_oracle(engine, oracle, {}, topics)
    assert engine._ccap_mult == 2 * first
