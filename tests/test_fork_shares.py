"""ISSUE 52: a fork of the fold state copies when it first writes.

``SweepBuilder.fork`` binds the source's in-place-written arrays
(``_STATE_COPIED``) read-only instead of copying them; a builder — the
fork, or a source that goes on after a live fork — takes its own copy of
what it still shares before its first in-place write (``_own_state``),
and a rebind (``_grow``, a fresh pair's insert) leaves it owning the new
array with no second copy. Neither side ever sees the other advance, a
cached checkpoint's bytes never change, and a fork that never writes —
the engine's own on the served Range route — never copies: ``/statusz``
``log_index`` counts ``forks`` and ``fork_copies``."""

import numpy as np
import pytest

from raphtory_tpu.core import sweep as cs
from raphtory_tpu.core.service import TemporalGraph
from raphtory_tpu.core.sweep import SweepBuilder, fold_pool, fork_status
from raphtory_tpu.engine.hopbatch import HopBatchedPageRank
from raphtory_tpu.jobs.manager import AnalysisManager, RangeQuery
from raphtory_tpu.jobs.rest import _statusz

from test_fold_parallel import _payloads_equal
from test_index_growth import (BUILDERS, KNOWN, _assert_same_builder,
                               _base_log, _events, _ids_everywhere)
import test_stage_spans
from test_stage_spans import _deferred_seeds, _named, _pagerank, _spans
from test_sweep import random_log

traced = test_stage_spans.traced        # the fixture: tracing on, no batching

SEEDS = ("checkpoint", "live", "pristine")


def _log(seed):
    return random_log(np.random.default_rng(seed), n_events=500, n_ids=14,
                      t_span=60)


def _state(holder):
    """The eight in-place-written arrays of a builder, or of a
    checkpoint's ``state``."""
    get = holder.__getitem__ if isinstance(holder, dict) \
        else lambda k: getattr(holder, k)
    return {k: get(k) for k in cs._STATE_COPIED}


def _shared(a, b):
    # two empty arrays share no byte; what a fork binds is the very array
    return a is b or bool(np.shares_memory(a, b))


def _seeded_fork(seed, log, kw):
    """``(source builder, fork, what holds the arrays the fork shares,
    the time both stand at)`` for each way a fold unit is seeded."""
    sw = SweepBuilder(log, **kw)
    if seed == "pristine":          # no one has advanced yet: t_prev None
        return sw, sw.fork(), sw, None
    sw._advance(20)
    if seed == "live":
        return sw, sw.fork(), sw, 20
    cp = sw.checkpoint()
    sw._advance(33)                 # the source went on past its checkpoint
    return sw, sw.fork(cp), cp.state, 20


def _fresh_at(log, kw, *times):
    sw = SweepBuilder(log, **kw)
    for t in times:
        if t is not None:
            sw._advance(t)
    return sw


@pytest.mark.parametrize("builder", sorted(BUILDERS))
@pytest.mark.parametrize("seed", SEEDS)
def test_a_fork_shares_its_state_until_its_first_write(seed, builder):
    kw, log = BUILDERS[builder], _log(52)
    was = fork_status()
    _, fork, holder, at = _seeded_fork(seed, log, kw)
    assert fork.t_prev == at
    source = _state(holder)
    want = {k: a.copy() for k, a in source.items()}
    for k, a in _state(fork).items():
        assert _shared(a, source[k]), k
        assert not a.flags.writeable and not source[k].flags.writeable, k
    # a writer the change missed raises: it cannot reach the shared bytes
    with pytest.raises(ValueError, match="read-only"):
        fork.v_lat[:1] = 0
    now = fork_status()
    assert now["forks"] - was["forks"] == 1
    assert now["fork_copies"] == was["fork_copies"]
    # an advance that folds no row copies nothing
    fork._advance(-1 if at is None else at)
    assert all(_shared(a, source[k]) for k, a in _state(fork).items())
    assert fork_status()["fork_copies"] == was["fork_copies"]

    fork._advance(41)
    for k, a in _state(fork).items():
        assert a.flags.writeable and not _shared(a, source[k]), k
        np.testing.assert_array_equal(source[k], want[k], err_msg=k)
    now = fork_status()
    assert now["fork_copies"] - was["fork_copies"] == 1
    assert now["fork_copied_bytes"] - was["fork_copied_bytes"] \
        == sum(a.nbytes for a in want.values())
    _assert_same_builder(fork, _fresh_at(log, kw, at, 41), f"{seed} fork")
    # owned now: the next advance copies nothing more
    fork._advance(55)
    assert fork_status()["fork_copies"] - was["fork_copies"] == 1
    _assert_same_builder(fork, _fresh_at(log, kw, at, 41, 55),
                         f"{seed} fork")


@pytest.mark.parametrize("builder", sorted(BUILDERS))
@pytest.mark.parametrize("seed", ["live", "pristine"])
def test_a_source_that_advances_leaves_its_fork_as_it_was(seed, builder):
    kw, log = BUILDERS[builder], _log(53)
    was = fork_status()
    sw, fork, _, at = _seeded_fork(seed, log, kw)
    held = _state(fork)
    want = {k: a.copy() for k, a in held.items()}
    sw._advance(41)                 # the SOURCE writes first: it copies
    assert fork_status()["fork_copies"] - was["fork_copies"] == 1
    for k, a in _state(sw).items():
        assert a.flags.writeable and not _shared(a, held[k]), k
    for k, a in _state(fork).items():
        assert a is held[k] and not a.flags.writeable, k
        np.testing.assert_array_equal(a, want[k], err_msg=k)
    assert fork.t_prev == at
    _assert_same_builder(sw, _fresh_at(log, kw, at, 41), "the source")
    # the fork goes its own way from where it was taken
    fork._advance(30)
    fork._advance(50)
    assert fork_status()["fork_copies"] - was["fork_copies"] == 2
    _assert_same_builder(fork, _fresh_at(log, kw, at, 30, 50), "the fork")
    _assert_same_builder(sw, _fresh_at(log, kw, at, 41), "the source")


@pytest.mark.parametrize("seed", [2, 9])
def test_forks_of_one_checkpoint_fold_side_by_side_as_the_serial_fold(
        monkeypatch, seed):
    """N units fork one cached checkpoint ON the pool's threads and fold
    at once: every payload is the serial fold's, bit for bit, and the
    checkpoint is as it was."""
    monkeypatch.setenv("RTPU_FOLD_WORKERS", "4")
    monkeypatch.setenv("RTPU_FOLD_CACHE_MB", "0")
    log = random_log(np.random.default_rng(seed), n_events=900, n_ids=40,
                     t_span=1000)
    hb = HopBatchedPageRank(log)
    hops = [450, 600, 750, 900]
    serial = hb.sw.fork()
    serial._advance(300)
    cp = serial.checkpoint()
    kept = {k: a.copy() for k, a in cp.state.items()}
    want = hb._fold_deltas_fork(serial, hops, True, None)

    def unit():
        sw = hb.sw.fork(cp)
        return sw, hb._fold_deltas_fork(sw, hops, True, None)

    was = fork_status()
    done = [f.result() for f in [fold_pool().submit(unit)
                                 for _ in range(6)]]
    for sw, got in done:
        assert _payloads_equal(got, want)
        _assert_same_builder(sw, serial, "a unit's builder")
        assert all(a.flags.writeable for a in _state(sw).values())
    now = fork_status()
    assert (now["forks"] - was["forks"],
            now["fork_copies"] - was["fork_copies"]) == (6, 6)
    for k, a in cp.state.items():
        np.testing.assert_array_equal(a, kept[k], err_msg=k)
    # the engine's own builder was a carrier all along: never written
    assert hb.sw.t_prev is None
    assert not any(a.flags.writeable for a in _state(hb.sw).values())


@pytest.mark.parametrize("advanced", [False, True],
                         ids=["pristine", "advanced"])
@pytest.mark.parametrize("builder", sorted(BUILDERS))
def test_a_growth_on_a_fork_that_has_not_written_copies_nothing_twice(
        builder, advanced):
    """``_grow`` rebinds what it grows: the fork owns those arrays from
    then on, its first advance copies only what it still shares, and the
    source keeps the arrays it had."""
    rng = np.random.default_rng(52)
    kw, log = BUILDERS[builder], _base_log(rng)
    sw = SweepBuilder(log, **kw)
    hops = [30, 55] if advanced else []
    for T in hops:
        sw._advance(T)
    fork = sw.fork()
    source = _state(sw)
    want = {k: a.copy() for k, a in source.items()}
    # a suffix with new ids everywhere and new pairs among them
    _events(log, rng, _ids_everywhere(rng), 56, 68, 40)
    was = fork_status()
    assert fork.repin(log) == "grown"
    assert len(fork.uv) > len(KNOWN) >= len(sw.uv)
    assert fork_status() == was             # growth is no deferred copy
    still = [k for k, a in _state(fork).items() if not a.flags.writeable]
    assert not any(k.startswith("v_") for k in still)   # rebound: owned
    # a preseeded builder took new pairs in too; the view builder's pair
    # state is grown by the fold itself, so it still shares it
    assert bool(still) == (builder == "views")
    assert all(_shared(getattr(fork, k), source[k]) for k in still)
    fork._advance(62)
    now = fork_status()
    assert now["fork_copies"] - was["fork_copies"] == bool(still)
    assert now["fork_copied_bytes"] - was["fork_copied_bytes"] \
        == sum(want[k].nbytes for k in still)
    assert all(a.flags.writeable for a in _state(fork).values())
    _assert_same_builder(fork, _fresh_at(log, kw, *hops, 62), "grown fork")
    for k, a in _state(sw).items():         # the source never moved
        assert a is source[k], k
        np.testing.assert_array_equal(a, want[k], err_msg=k)
    assert len(sw._t) < len(fork._t)


@pytest.mark.parametrize("cache_mb", ["0", "64"],
                         ids=["from_start", "from_checkpoints"])
def test_a_served_range_copies_once_a_unit_and_never_for_the_engine(
        traced, monkeypatch, cache_mb):
    monkeypatch.setenv("RTPU_FOLD_WORKERS", "2")
    monkeypatch.setenv("RTPU_FOLD_CACHE_MB", cache_mb)
    if cs.fold_cache() is not None:
        cs.fold_cache().clear()
    log = random_log(np.random.default_rng(54), n_events=6000, n_ids=300,
                     t_span=1000)
    mgr = AnalysisManager(TemporalGraph(log))
    for start in (500, 600):    # the second finds the index, and at
        # 64 MB the first one's checkpoints
        was = _statusz(mgr)["log_index"]
        spans = _spans(mgr.submit(_pagerank(), RangeQuery(
            start=start, end=start + 400, jump=100, windows=(1000, 300))))
        folds = _named(spans, "hop.fold")
        assert len(folds) == 2                              # two units
        now = _statusz(mgr)["log_index"]
        assert now["forks"] - was["forks"] == len(folds) + 1
        assert now["fork_copies"] - was["fork_copies"] == len(folds)
        copies = [own for f in folds for own in _deferred_seeds(spans, f)]
        assert len(copies) == len(folds)                    # one a unit
        assert now["fork_copied_bytes"] - was["fork_copied_bytes"] \
            == sum(c["args"]["nbytes"] for c in copies)
        # and none on the job thread, where the engine was built
        (build,) = _named(spans, "engine.build")
        (fork,) = _named(spans, "index.fork")
        assert fork["args"]["nbytes"] == 0 and fork["tid"] == build["tid"]
        assert all(c["tid"] != build["tid"] for c in copies)
