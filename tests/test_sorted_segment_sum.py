"""``ops/segment.sorted_segment_sum``: the destination sum of a PageRank
superstep as a segmented scan over the dst-sorted rows and one gather of
``num_segments`` rows — against a float64 numpy reference and against
``jax.ops.segment_sum`` (the scatter it replaces), on both sides of the
column constant."""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from raphtory_tpu.ops import segment as seg
from raphtory_tpu.ops.segment import (SCAN_MAX_COLUMNS, segment_combine,
                                      segment_ends_pos, sorted_segment_sum,
                                      sum_route)

WIDE = SCAN_MAX_COLUMNS + 8   # one column count past the constant


def _reference(data, ids, n):
    """float64 sum a segment, one row at a time."""
    want = np.zeros((n,) + data.shape[1:], np.float64)
    np.add.at(want, ids, data.astype(np.float64))
    return want


def _ids(rng, m, n, *, pad=0, empty=()):
    """Sorted ids over ``n`` segments with ``pad`` padding rows carrying
    ``n - 1`` (what the pair tables pad ``e_dst`` with); ``empty``
    segments get no row."""
    live = np.setdiff1d(np.arange(n - 1), np.asarray(empty, np.int64))
    ids = np.sort(rng.choice(live, m - pad)).astype(np.int32)
    return np.concatenate([ids, np.full(pad, n - 1, np.int32)])


def _check(data, ids, n, *, mask=None):
    """The scan against float64 and against the scatter: as close to the
    reference as the scatter is (both add float32 in some order; the bound
    is a few ulp of the segment's sum of magnitudes), never farther than
    the scatter by more than that bound."""
    d = data if mask is None else np.where(
        mask.reshape(mask.shape + (1,) * (data.ndim - 1)), data, 0)
    want = _reference(d, ids, n)
    got = np.asarray(segment_combine(
        jnp.asarray(data), jnp.asarray(ids), n, "sum",
        None if mask is None else jnp.asarray(mask), True))
    old = np.asarray(jax.ops.segment_sum(
        jnp.asarray(d), jnp.asarray(ids), num_segments=n,
        indices_are_sorted=True))
    assert got.shape == old.shape and got.dtype == old.dtype
    scale = _reference(np.abs(d), ids, n) + 1e-30
    err_new = np.max(np.abs(got - want) / scale)
    err_old = np.max(np.abs(old - want) / scale)
    rows = np.bincount(ids, minlength=n).max()
    # pairwise inside a segment: log2(rows) roundings, not rows
    assert err_new <= 6e-8 * (np.log2(max(rows, 2)) + 2), (err_new, err_old)
    assert err_new <= max(err_old, 2.5e-7), (err_new, err_old)
    return got, want


@pytest.mark.parametrize("cols", [None, 1, 3, 6, 12, WIDE],
                         ids=lambda c: f"C{c}")
def test_matches_float64_and_segment_sum(cols):
    rng = np.random.default_rng(cols or 0)
    m, n = 5000, 300
    ids = _ids(rng, m, n, pad=37, empty=(0, 5, 6, 150))
    shape = (m,) if cols is None else (m, cols)
    data = rng.random(shape).astype(np.float32)
    data[-37:] = 0.0
    got, want = _check(data, ids, n)
    assert not got[[0, 5, 6, 150]].any()          # empty segments
    assert sum_route(cols or 1) == ("scatter" if cols == WIDE else "scan")


@pytest.mark.parametrize("cols", [None, 6], ids=["flat", "C6"])
def test_one_row_segments_and_a_hub_longer_than_any_block(cols):
    """Every other segment one row long, one hub of 2^17 + 5 rows (longer
    than a block, than a block of blocks, than 2^17), padding rows carrying
    ``n - 1``, a third of the rows masked out."""
    rng = np.random.default_rng(3)
    hub = (1 << 17) + 5
    n = 1024
    ids = np.concatenate([np.arange(400), np.full(hub, 400),
                          np.arange(401, 1000), np.full(200, n - 1)]
                         ).astype(np.int32)
    m = len(ids)
    shape = (m,) if cols is None else (m, cols)
    data = rng.random(shape).astype(np.float32)
    mask = rng.random(m) < 0.66
    mask[-200:] = False
    got, _ = _check(data, ids, n, mask=mask)
    assert not got[1000:n].any()                  # padding adds nothing


@pytest.mark.parametrize("m", [1, 2, 127, 128, 129, 16384, 16385 + 128])
def test_every_length_around_a_block(m):
    rng = np.random.default_rng(m)
    n = 7
    ids = np.sort(rng.integers(0, n, m)).astype(np.int32)
    _check(rng.random((m, 2)).astype(np.float32), ids, n)
    _check(rng.random(m).astype(np.float32), ids, n)


def test_window_major_flat_ids_of_the_mask_runner():
    """``bsp.make_mask_runner`` sums k windows as ONE flat graph: ids offset
    by ``kk * n``, each window's block dst-sorted, padding rows carrying
    ``n - 1`` of their window. The scan sees k * n segments."""
    rng = np.random.default_rng(5)
    k, n, m = 3, 64, 700
    e_dst = _ids(rng, m, n, pad=11, empty=(2, 40))
    flat = (e_dst[None, :] + (np.arange(k, dtype=np.int32) * n)[:, None]
            ).reshape(-1)
    assert (np.diff(flat) >= 0).all()
    data = rng.random(k * m).astype(np.float32)
    mask = rng.random(k * m) < 0.5
    got, want = _check(data, flat, k * n, mask=mask)
    ends, pos = segment_ends_pos(jnp.asarray(flat), k * n)
    again = np.asarray(sorted_segment_sum(
        jnp.asarray(np.where(mask, data, 0)), jnp.asarray(flat), k * n,
        ends=ends, pos=pos))
    assert np.array_equal(got, again)             # handing the plan in
    # int32 ones, the runner's in-degrees: exact
    deg = np.asarray(segment_combine(
        jnp.ones((k * m,), jnp.int32), jnp.asarray(flat), k * n, "sum",
        jnp.asarray(mask), True))
    assert deg.dtype == np.int32
    assert np.array_equal(deg, np.bincount(flat[mask], minlength=k * n))


def test_ends_and_pos_describe_the_segments():
    ids = np.asarray([1, 1, 1, 4, 6, 6, 7, 7, 7, 7], np.int32)
    ends, pos = segment_ends_pos(jnp.asarray(ids), 8)
    assert np.asarray(ends).tolist() == [-1, 2, -1, -1, 3, -1, 5, 9]
    assert np.asarray(pos).tolist() == [0, 1, 2, 0, 0, 1, 0, 1, 2, 3]


@pytest.mark.parametrize("cols", [None, 6], ids=["flat", "C6"])
def test_a_small_segment_beside_a_hub_does_not_cancel(cols):
    """A hub of 2^17 rows summing to about 1 next to a two-row segment
    summing to 1e-9: each segment adds its own rows only, so the small sum
    is float32-exact. A global ``cumsum`` differenced at the segment ends
    carries the prefix's 6e-8 into it and misses by tens of per cent."""
    hub = 1 << 17
    rng = np.random.default_rng(9)
    ids = np.concatenate([np.zeros(hub), [1, 1], np.full(30, 3)]
                         ).astype(np.int32)
    col = np.concatenate([rng.random(hub) * 2 / hub, [4e-10, 6e-10],
                          np.zeros(30)]).astype(np.float32)
    data = col if cols is None else np.repeat(col[:, None], cols, axis=1)
    got = np.asarray(sorted_segment_sum(jnp.asarray(data),
                                        jnp.asarray(ids), 4))
    small = float(col[hub].astype(np.float64) + col[hub + 1])
    assert abs(got[0].flat[0] - 1.0) < 0.02
    assert np.all(np.abs(got[1] - small) <= 1e-6 * small)
    differenced = np.diff(np.concatenate(
        [[0], np.cumsum(col, dtype=np.float32)[[hub - 1, hub + 1]]]))[1]
    assert abs(differenced - small) > 1e-3 * small   # what is refused


def test_the_constant_has_two_sides():
    """At or under ``SCAN_MAX_COLUMNS`` columns no scatter, past it the
    scatter alone — the one switch between the two sums."""
    ids = jnp.zeros((256,), jnp.int32)

    def prims(cols):
        jaxpr = jax.make_jaxpr(lambda d: sorted_segment_sum(d, ids, 4))(
            jnp.zeros((256, cols), jnp.float32))
        return {e.primitive.name for e in jaxpr.jaxpr.eqns}

    assert not any(p.startswith("scatter")
                   for p in prims(SCAN_MAX_COLUMNS))
    assert prims(SCAN_MAX_COLUMNS + 1) >= {"scatter-add"}
    assert "cumsum" not in prims(SCAN_MAX_COLUMNS)


@pytest.mark.parametrize("op", ["min", "max"])
def test_min_and_max_keep_the_scatter(op):
    rng = np.random.default_rng(1)
    ids = np.sort(rng.integers(0, 9, 200)).astype(np.int32)
    data = rng.random(200).astype(np.float32)
    got = np.asarray(segment_combine(jnp.asarray(data), jnp.asarray(ids),
                                     9, op))
    want = getattr(jax.ops, f"segment_{op}")(data, ids, num_segments=9)
    assert np.array_equal(got, np.asarray(want))


def test_an_unsorted_sum_keeps_the_scatter():
    rng = np.random.default_rng(2)
    ids = rng.integers(0, 9, 200).astype(np.int32)
    data = rng.random(200).astype(np.float32)
    got = np.asarray(segment_combine(jnp.asarray(data), jnp.asarray(ids),
                                     9, "sum", indices_are_sorted=False))
    assert np.allclose(got, _reference(data, ids, 9), rtol=1e-6)


def test_block_size_is_not_part_of_the_answer(monkeypatch):
    """The blocked scan is the flat scan: another block length moves the
    last bits at most (the order inside a segment), never a segment."""
    rng = np.random.default_rng(4)
    ids = _ids(rng, 3000, 40, pad=9)
    data = rng.random((3000, 3)).astype(np.float32)
    base = np.asarray(sorted_segment_sum(jnp.asarray(data),
                                         jnp.asarray(ids), 40))
    monkeypatch.setattr(seg, "_SCAN_BLOCK", 8)
    small = np.asarray(sorted_segment_sum(jnp.asarray(data),
                                          jnp.asarray(ids), 40))
    assert np.allclose(base, small, rtol=1e-6, atol=0)
    assert np.allclose(small, _reference(data, ids, 40), rtol=2e-6)
