"""Generic (non-combiner) message exchange: segment_mode + LabelPropagation.

The sum/min/max combiners cannot express a per-label histogram; the
sort-based custom-exchange path must — against a pure-host reference with
identical tie-breaking, on both engines."""

import jax.numpy as jnp
import numpy as np
import pytest

from raphtory_tpu import EventLog, build_view
from raphtory_tpu.algorithms import LabelPropagation
from raphtory_tpu.engine import bsp
from raphtory_tpu.ops.segment import segment_mode
from raphtory_tpu.parallel import sharded


# ---------------------------------------------------------------- primitive


def test_segment_mode_basic():
    vals = jnp.asarray([5, 5, 7, 7, 7, 2], jnp.int32)
    segs = jnp.asarray([0, 0, 0, 1, 1, 1], jnp.int32)
    out = segment_mode(vals, segs, 3)
    # seg0: {5:2, 7:1} -> 5; seg1: {7:2, 2:1} -> 7; seg2: empty -> -1
    np.testing.assert_array_equal(np.asarray(out), [5, 7, -1])


def test_segment_mode_tie_breaks_to_smallest():
    vals = jnp.asarray([9, 3, 3, 9], jnp.int32)
    segs = jnp.asarray([0, 0, 0, 0], jnp.int32)
    assert int(segment_mode(vals, segs, 1)[0]) == 3


def test_segment_mode_mask_and_default():
    vals = jnp.asarray([1, 1, 8], jnp.int32)
    segs = jnp.asarray([0, 0, 1], jnp.int32)
    mask = jnp.asarray([False, True, False])
    out = segment_mode(vals, segs, 2, mask, default=-7)
    np.testing.assert_array_equal(np.asarray(out), [1, -7])


def test_segment_mode_out_of_range_values_degrade_to_no_message():
    """Values outside [0, 2**31) must not alias into other segments through
    the packed sort key — they degrade to 'no message' for their segment."""
    vals = np.array([5, -3, 2**31 + 1, 5], np.int64)
    segs = np.array([0, 1, 1, 2], np.int32)
    out = np.asarray(segment_mode(jnp.asarray(vals), jnp.asarray(segs), 3,
                                  default=-1))
    assert out.tolist() == [5, -1, 5]  # seg 1 sees only bad rows -> default


def test_segment_mode_randomised_vs_host():
    rng = np.random.default_rng(0)
    for _ in range(10):
        m, n = 300, 40
        vals = rng.integers(0, 15, m).astype(np.int32)
        segs = rng.integers(0, n, m).astype(np.int32)
        mask = rng.random(m) < 0.8
        got = np.asarray(segment_mode(
            jnp.asarray(vals), jnp.asarray(segs), n, jnp.asarray(mask)))
        for s in range(n):
            rows = vals[(segs == s) & mask]
            if len(rows) == 0:
                assert got[s] == -1
            else:
                counts = np.bincount(rows)
                best = counts.max()
                want = int(np.flatnonzero(counts == best)[0])  # smallest
                assert got[s] == want, (s, rows, got[s], want)


# ------------------------------------------------------------------ LPA


def _host_lpa(view, steps, window=None):
    """Synchronous LPA with the program's exact rule: adopt the most
    frequent in-neighbour label (ties -> smallest), keep when inbox empty."""
    if window is None:
        vm = np.asarray(view.v_mask)
        em = np.asarray(view.e_mask)
    else:
        vm, em = view.window_masks([window])
        vm, em = vm[0], em[0]
    labels = np.where(vm, np.arange(view.n_pad), np.iinfo(np.int32).max)
    src = view.e_src[em]
    dst = view.e_dst[em]
    for _ in range(steps):
        new = labels.copy()
        changed = False
        for v in np.flatnonzero(vm):
            inbox = labels[src[dst == v]]
            if len(inbox) == 0:
                continue
            counts = np.bincount(inbox)
            best = counts.max()
            pick = int(np.flatnonzero(counts == best)[0])
            new[v] = pick
        changed = (new != labels).any()
        labels = new
        if not changed:
            break
    return labels


def _lpa_log(seed, n_ids=40, n_events=300):
    rng = np.random.default_rng(seed)
    log = EventLog()
    for _ in range(n_events):
        t = int(rng.integers(0, 100))
        a, b = (int(x) for x in rng.integers(0, n_ids, 2))
        if rng.random() < 0.85:
            log.add_edge(t, a, b)
        else:
            log.delete_edge(t, a, b)
    return log


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_lpa_matches_host_reference(seed):
    view = build_view(_lpa_log(seed), 90)
    prog = LabelPropagation(max_steps=8)
    got, steps = bsp.run(prog, view)
    want = _host_lpa(view, 8)
    np.testing.assert_array_equal(
        np.asarray(got)[view.v_mask], want[view.v_mask])


def test_lpa_windowed_matches_host_reference():
    view = build_view(_lpa_log(3), 90)
    prog = LabelPropagation(max_steps=6)
    got, _ = bsp.run(prog, view, window=30)
    want = _host_lpa(view, 6, window=30)
    vm = view.window_masks([30])[0][0]
    np.testing.assert_array_equal(np.asarray(got)[vm], want[vm])


@pytest.mark.parametrize("comm", ["halo", "all_gather"])
def test_lpa_sharded_matches_single(comm):
    import jax

    view = build_view(_lpa_log(4), 90)
    prog = LabelPropagation(max_steps=8)
    mesh = sharded.make_mesh(8, 1, devices=jax.devices()[:8])
    got, _ = sharded.run(prog, view, mesh, comm=comm)
    want, _ = bsp.run(prog, view)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_custom_combiner_rejects_direction_both():
    class Bad(LabelPropagation):
        direction = "both"

    view = build_view(_lpa_log(5), 90)
    with pytest.raises(ValueError, match="custom"):
        bsp.run(Bad(), view)
    import jax

    mesh = sharded.make_mesh(8, 1, devices=jax.devices()[:8])
    with pytest.raises(ValueError, match="custom"):
        sharded.run(Bad(), view, mesh)


def test_lpa_reduce_shape():
    view = build_view(_lpa_log(6), 90)
    prog = LabelPropagation(max_steps=8)
    got, _ = bsp.run(prog, view)
    out = prog.reduce(got, view)
    assert out["vertices"] > 0
    assert out["communities"] >= 1
    assert sum(out["top5"]) <= out["vertices"]
