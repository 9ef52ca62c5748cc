"""A fold checkpoint holds, and the fold cache charges, only the state
the checkpoint owns. On a PRESEEDED builder (the columnar engines' —
``engine/device_sweep.LogIndex.prototype``) the sorted pair tables
``e_enc`` / ``e_enc_dst`` hold every pair of the log from ``__init__``
on and no advance rebinds them: they stay with the builder that
``fork(cp)`` is called on, and ``cp.nbytes`` is what holding ``cp`` keeps
in memory. On a builder that is not preseeded the tables grow with the
fold and stay in the checkpoint, charged. Either way a fold seeded from
a checkpoint is the fold from the log's first event, bit for bit."""

import gc
import weakref

import numpy as np
import pytest

from raphtory_tpu.core.sweep import (_PAIR_TABLES, _STATE_COPIED,
                                     _STATE_SHARED, FoldCache, SweepBuilder,
                                     log_fingerprint)
from raphtory_tpu.engine.device_sweep import LogIndex

from test_sweep import random_log

HOPS = (8, 15, 22, 23, 31, 40, 49, 60)
DELTA = ("v_idx", "v_lat", "v_alive", "v_first",
         "e_enc", "e_lat", "e_alive", "e_first")


def _log(n_events=3000):
    # vertex deletes among the events: the incident joins read both
    # pair tables, so a fork with the wrong ones would fold differently
    return random_log(np.random.default_rng(5), n_events=n_events, n_ids=40,
                      t_span=50)


def _proto(preseed, log=None):
    return SweepBuilder(_log() if log is None else log, track_rows=False,
                        preseed_pairs=preseed)


def _assert_same_fold(a, b):
    assert a.t_prev == b.t_prev
    for k in _STATE_COPIED + _STATE_SHARED + _PAIR_TABLES:
        x, y = getattr(a, k), getattr(b, k)
        assert x.dtype == y.dtype and np.array_equal(x, y), k
    for k in DELTA:
        assert np.array_equal(a.last_delta[k], b.last_delta[k]), k


# ------------------------------------------------- (a) what it holds


@pytest.mark.parametrize("preseed", [True, False], ids=["preseeded", "grown"])
def test_nbytes_is_the_bytes_of_the_arrays_the_checkpoint_holds(preseed):
    proto = _proto(preseed)
    assert proto._preseeded is preseed
    sw = proto.fork()
    sw._advance(25)
    cp = sw.checkpoint()
    assert cp.nbytes == sum(a.nbytes for a in cp.state.values()) > 0
    # whole arrays, none a window on a larger buffer the count would miss
    assert all(a.base is None for a in cp.state.values())
    held = set(_STATE_COPIED + _STATE_SHARED)
    assert set(cp.state) == (held if preseed else held | set(_PAIR_TABLES))
    # the in-place state is the checkpoint's own copy
    for k in _STATE_COPIED:
        assert not np.shares_memory(cp.state[k], getattr(sw, k)), k
    pairs = len(sw.e_lat)
    assert pairs > 0
    owned = 18 * pairs + 18 * len(sw.uv)     # two int64 + two flags each
    # grown tables come with the delete history their new-pair join reads
    grown = 16 * pairs + sw.dh_v.nbytes + sw.dh_t.nbytes
    assert (sw.dh_v.nbytes > 0) is (not preseed)
    assert cp.nbytes == owned + (0 if preseed else grown)


def test_a_preseeded_checkpoint_shares_nothing_with_the_index():
    idx = LogIndex(_log())
    proto = idx.prototype
    assert proto._preseeded and len(proto.e_enc) == len(proto.e_enc_dst) > 0
    sw = proto.fork()
    assert sw.e_enc is proto.e_enc and sw.e_enc_dst is proto.e_enc_dst
    sw._advance(25)
    cp = sw.checkpoint()
    # the advance rebound neither table, and the checkpoint took neither
    assert sw.e_enc is proto.e_enc and sw.e_enc_dst is proto.e_enc_dst
    for k in _PAIR_TABLES:
        assert k not in cp.state
        for name, a in cp.state.items():
            assert not np.shares_memory(a, getattr(proto, k)), (name, k)
    # the index counts the tables it owns (``/statusz`` ``log_index``)
    assert idx.nbytes >= proto.e_enc.nbytes + proto.e_enc_dst.nbytes
    # a second checkpoint of the same log shares no array with the first
    sw._advance(30)
    cp2 = sw.checkpoint()
    assert cp2.nbytes == cp.nbytes
    assert not any(np.shares_memory(a, b) for a in cp.state.values()
                   for b in cp2.state.values() if a.size and b.size)
    # what the cache charges is what the entry keeps alive: with the
    # index and every builder gone, the tables are freed under the cache
    cache = FoldCache(max_bytes=cp.nbytes)
    assert cache.put_checkpoint(log_fingerprint(proto.log), cp) is True
    assert cache.stats()["bytes"] == cp.nbytes
    tables = [weakref.ref(getattr(proto, k)) for k in _PAIR_TABLES]
    del idx, proto, sw, cp2
    gc.collect()
    assert [w() for w in tables] == [None, None]
    assert cp.nbytes == sum(a.nbytes for a in cp.state.values())


def test_a_checkpoint_of_grown_pair_tables_carries_them():
    sw = _proto(False).fork()
    sw._advance(25)
    cp = sw.checkpoint()
    for k in _PAIR_TABLES:
        assert cp.state[k] is getattr(sw, k) and len(cp.state[k]) > 0
    before = {k: cp.state[k].copy() for k in _PAIR_TABLES}
    n = len(sw.e_enc)
    sw._advance(49)                  # fresh pairs: the tables are rebound
    assert len(sw.e_enc) > n
    for k in _PAIR_TABLES:
        assert cp.state[k] is not getattr(sw, k)
        assert np.array_equal(cp.state[k], before[k])


# ----------------------------------- (b) forked from it, the same fold


@pytest.mark.parametrize("preseed", [True, False], ids=["preseeded", "grown"])
def test_fork_from_a_checkpoint_folds_as_from_the_start_at_every_hop(preseed):
    proto = _proto(preseed)
    straight = proto.fork()
    cps = []
    for t in HOPS:
        straight._advance(t)
        cps.append(straight.checkpoint())
    # another builder over the same content: checkpoints pass between
    # builders of one log, the pair tables come from the one forked
    other = _proto(preseed)
    for i, cp in enumerate(cps[:-1]):
        assert cp.t_prev == HOPS[i]
        before = {k: a.copy() for k, a in cp.state.items()}
        for base in (proto, other):
            fork = base.fork(cp)
            assert fork.t_prev == HOPS[i]
            if preseed:
                assert fork.e_enc is base.e_enc
                assert fork.e_enc_dst is base.e_enc_dst
            ref = proto.fork()
            for t in HOPS[:i + 1]:
                ref._advance(t)
            for t in HOPS[i + 1:]:
                fork._advance(t)
                ref._advance(t)
                _assert_same_fold(fork, ref)
        # the forks' advances wrote nothing into the cached checkpoint
        for k, a in cp.state.items():
            assert np.array_equal(a, before[k]), k
    # the pristine builders never moved
    assert proto.t_prev is None and other.t_prev is None


@pytest.mark.parametrize("preseed", [True, False], ids=["preseeded", "grown"])
def test_fork_without_a_checkpoint_is_the_builder_as_it_stands(preseed):
    sw = _proto(preseed).fork()
    sw._advance(22)
    fork = sw.fork()
    # the in-place-written state is shared read-only until either side
    # writes (ISSUE 52: tests/test_fork_shares.py), the rest for good
    for k in _STATE_COPIED + _STATE_SHARED + _PAIR_TABLES:
        assert getattr(fork, k) is getattr(sw, k), k
    assert not any(getattr(sw, k).flags.writeable for k in _STATE_COPIED)
    fork._advance(40)
    sw._advance(40)
    _assert_same_fold(fork, sw)
    for k in _STATE_COPIED:
        assert not np.shares_memory(getattr(fork, k), getattr(sw, k)), k


def test_fork_across_an_incompatible_config_still_raises():
    log = _log()
    pre, grown = _proto(True, log), _proto(False, log)
    a, b = pre.fork(), grown.fork()
    a._advance(25)
    b._advance(25)
    with pytest.raises(ValueError, match="incompatible SweepBuilder"):
        grown.fork(a.checkpoint())   # it holds no pair tables to give
    with pytest.raises(ValueError, match="incompatible SweepBuilder"):
        pre.fork(b.checkpoint())
    # another log's tables are not this checkpoint's: refused by its size
    # (the fold cache keys by the content's fingerprint besides)
    with pytest.raises(ValueError, match="incompatible SweepBuilder"):
        _proto(True, _log(2990)).fork(a.checkpoint())
    with pytest.raises(ValueError, match="incompatible SweepBuilder"):
        SweepBuilder(log, preseed_pairs=True).fork(a.checkpoint())
