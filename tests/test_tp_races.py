"""Deterministic race reproduction via tracepoints (SURVEY §5.2 — the
snabbkaffe ?tp / ?force_ordering role): pin the async-fold adoption
into exact windows of a concurrent match and assert oracle equality,
instead of hoping a wall-clock stress test hits the interleaving."""

import random
import threading
import time

from emqx_tpu import topic as T
from emqx_tpu import tp
from emqx_tpu.engine import MatchEngine
from emqx_tpu.ops.trie_host import HostTrie


def build_engine(n=400, threshold=64):
    eng = MatchEngine(
        max_levels=8, rebuild_threshold=10**9,
        delta_aut_threshold=threshold,
        # pinned: these tests force interleavings on the DEVICE match
        # path (snapshot/overlay vs fold adoption); auto would route
        # the small windows to the host and never reach them
        use_device=True,
    )
    oracle = HostTrie()
    for i in range(n):
        eng.insert(f"seed/{i % 23}/+/s{i}", i)
        oracle.insert(f"seed/{i % 23}/+/s{i}", i)
    eng.rebuild()
    return eng, oracle


def oracle_check(eng, oracle, topics):
    got = eng.match_batch(topics)
    for t, g in zip(topics, got):
        want = oracle.match_words(T.words(t))
        assert g == want, (t, sorted(map(str, g)), sorted(map(str, want)))


def churn(eng, oracle, start, count):
    for i in range(start, start + count):
        eng.insert(f"churn/{i % 97}/+/c{i}", i)
        oracle.insert(f"churn/{i % 97}/+/c{i}", i)


from tests_fakes import drain_folds  # noqa: E402  (shared drain util)


def test_fold_adopts_inside_match_window():
    """The adoption is forced to land between a match's snapshot and
    its overlay — the exact interleaving where a count-based residual
    skip-check once dropped filters folded mid-batch."""
    eng, oracle = build_engine()
    churn(eng, oracle, 1000, 200)  # enough residual to trigger a fold
    drain_folds(eng)
    topics = [f"churn/{i % 97}/x/y" for i in range(60)] + [
        f"seed/{i % 23}/q/r" for i in range(40)
    ]
    with tp.collect() as trace, tp.force_ordering(
        after="match_overlay", block="fold_adopt"
    ):
        # the fold assembles concurrently but may only adopt once the
        # match below has passed its overlay tracepoint.  Churn until a
        # fold actually captures: the geometric threshold depends on
        # where the previous fold's watermark landed.
        for round_ in range(50):
            if tp.events_of(trace, "fold_capture"):
                break
            churn(eng, oracle, 2000 + round_ * 100, 100)
        else:
            raise AssertionError("fold never captured")
        oracle_check(eng, oracle, topics)
        drain_folds(eng)
    tp.assert_present(trace, "fold_commit")
    tp.assert_order(trace, "match_overlay", "fold_commit")
    # and matches AFTER adoption are equally correct
    oracle_check(eng, oracle, topics)


def test_fold_adopts_before_overlay_of_older_snapshot():
    """Mirror image: a match snapshots, the fold adopts, THEN the
    match overlays against its (older) snapshot — entries between the
    two watermarks must come from the residual view, not be lost."""
    eng, oracle = build_engine()
    churn(eng, oracle, 1000, 200)
    drain_folds(eng)
    topics = [f"churn/{i % 97}/x/y" for i in range(60)]

    adopted = threading.Event()

    def matcher():
        oracle_check(eng, oracle, topics)

    with tp.collect() as trace:
        with tp.force_ordering(after="match_snapshot", block="fold_adopt"):
            with tp.force_ordering(after="fold_commit", block="match_overlay"):
                t = threading.Thread(target=matcher)
                for round_ in range(50):
                    if tp.events_of(trace, "fold_capture"):
                        break
                    churn(eng, oracle, 2000 + round_ * 100, 100)
                else:
                    raise AssertionError("fold never captured")
                t.start()
                t.join(30)
                assert not t.is_alive()
        drain_folds(eng)
    tp.assert_present(trace, "fold_commit")
    tp.assert_order(trace, "match_snapshot", "fold_commit")
    tp.assert_order(trace, "fold_commit", "match_overlay")
    oracle_check(eng, oracle, topics)


def test_base_swap_discards_inflight_fold():
    """A base rebuild swapping mid-fold must discard the fold (its
    inputs predate the new base), and matching stays oracle-equal."""
    eng, oracle = build_engine()
    eng.background_rebuild = True
    eng.rebuild_threshold = 250
    topics = [f"churn/{i % 97}/x/y" for i in range(60)]
    with tp.collect() as trace:
        with tp.force_ordering(after="daut_drop", block="fold_assemble_done"):
            # cross BOTH thresholds: a fold starts, then the base
            # rebuild (threshold 250) starts and swaps while the fold
            # is pinned pre-adoption
            churn(eng, oracle, 3000, 400)
            import time
            deadline = time.time() + 15
            while time.time() < deadline and not tp.events_of(
                trace, "daut_drop"
            ):
                eng.match_batch(["churn/1/x/y"])  # polls the swap
                time.sleep(0.02)
        drain_folds(eng)
    tp.assert_present(trace, "daut_drop")
    tp.assert_present(trace, "fold_discard")
    tp.assert_absent(
        trace, "fold_commit",
        gen=tp.assert_present(trace, "fold_discard")["gen"],
    )
    oracle_check(eng, oracle, topics)


def rebuild_under_a_committing_fold(eng, oracle):
    """``eng.rebuild()`` arrives while a fold thread sits inside its
    commit, past the generation check and before its stores (it holds
    ``_mlock`` there).  `build_engine` reaches this by chance — seed
    folds are still in flight when it calls ``rebuild()`` — and an
    unlocked ``rebuild()`` then cleared the delta tier between the
    fold's stores: a snapshot with an automaton and no fid array (the
    TypeError in `_overlay`), or a stale fold adopted over the new
    base.  Here the interleaving is pinned, every run."""
    drain_folds(eng)
    with tp.collect() as trace, tp.force_ordering(
        after="released_by_hand", block="fold_commit"
    ) as commit_gate, tp.force_ordering(
        after="released_by_hand", block="fold_adopt"
    ) as adopt_gate:
        # the fold may not take _mlock while this thread still inserts
        for round_ in range(50):
            if tp.events_of(trace, "fold_capture"):
                break
            churn(eng, oracle, 9000 + round_ * 100, 100)
        else:
            raise AssertionError("fold never captured")
        adopt_gate.set()
        assert wait_for(lambda: tp.events_of(trace, "fold_commit"), 15.0)
        r = threading.Thread(target=eng.rebuild)
        r.start()
        # a rebuild that does not wait for the lock drops the tier
        # now, under the fold's feet
        dropped = wait_for(lambda: tp.events_of(trace, "daut_drop"), 1.5)
        commit_gate.set()
        r.join(15.0)
        assert not r.is_alive()
        drain_folds(eng)
    assert not dropped, "rebuild() swapped state inside a fold's commit"
    tp.assert_order(trace, "fold_commit", "daut_drop")
    assert eng._dtier == (None, None, None)


def wait_for(cond, timeout):
    """Whether ``cond()`` came to hold within ``timeout`` seconds."""
    deadline = time.monotonic() + timeout
    while not cond():
        if time.monotonic() > deadline:
            return False
        time.sleep(0.005)
    return True


def test_fold_failure_injection_keeps_matching():
    """An injected crash in the fold thread must leave matching on the
    residual overlay, oracle-equal, and a later fold recovers."""
    eng, oracle = build_engine()
    rebuild_under_a_committing_fold(eng, oracle)
    topics = [f"churn/{i % 97}/x/y" for i in range(60)]
    with tp.collect() as trace:
        with tp.inject("fold_assemble_done", RuntimeError("injected")):
            churn(eng, oracle, 1000, 200)
            drain_folds(eng)
            oracle_check(eng, oracle, topics)
        # next fold (no injection) recovers the device tier
        churn(eng, oracle, 5000, 200)
        drain_folds(eng)
    assert eng._dtier[0] is not None
    oracle_check(eng, oracle, topics)
