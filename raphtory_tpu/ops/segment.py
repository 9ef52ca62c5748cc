"""Segment combiners — the TPU-native replacement for vertex message passing.

The reference delivers typed point-to-point actor messages per vertex
(``VertexVisitor.scala:99-161`` → ``ReaderWorker.scala:137-157`` appending to
``VertexMutliQueue``). Here, a superstep's messages are a flat per-edge payload
array combined at the destination with an associative-commutative reduction —
one fused gather/segment-reduce the XLA scheduler can tile, instead of 2M-deep
actor mailboxes.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

_NEUTRAL = {
    "sum": lambda dt: jnp.zeros((), dt),
    "min": lambda dt: (jnp.array(jnp.iinfo(dt).max, dt)
                       if jnp.issubdtype(dt, jnp.integer) else jnp.array(jnp.inf, dt)),
    "max": lambda dt: (jnp.array(jnp.iinfo(dt).min, dt)
                       if jnp.issubdtype(dt, jnp.integer) else jnp.array(-jnp.inf, dt)),
}

_SEG = {
    "sum": jax.ops.segment_sum,
    "min": jax.ops.segment_min,
    "max": jax.ops.segment_max,
}


def neutral(op: str, dtype) -> jnp.ndarray:
    return _NEUTRAL[op](jnp.dtype(dtype))


def segment_combine(
    data: jnp.ndarray,
    segment_ids: jnp.ndarray,
    num_segments: int,
    op: str,
    mask: jnp.ndarray | None = None,
    indices_are_sorted: bool = True,
    ends: jnp.ndarray | None = None,
    pos: jnp.ndarray | None = None,
):
    """Combine per-edge payloads at their destination vertex.

    `data` may have trailing feature dims; `mask` rows are replaced with the
    combiner's neutral element so padded edges are no-ops. `indices_are_sorted`
    may only be True when ids are sorted INCLUDING padding rows — the snapshot
    builder pads e_dst with n_pad-1 (the max id) to preserve the promise.
    A sorted sum is ``sorted_segment_sum``: a caller whose ids do not change
    between calls (the supersteps of one dispatch) computes ``ends`` / ``pos``
    once (``segment_ends_pos``) and passes them, the way ``counts`` reaches
    ``segment_mode``.
    """
    if op not in _SEG:
        raise ValueError(f"unknown combiner {op!r}; use one of {sorted(_SEG)}")
    if mask is not None:
        m = mask.reshape(mask.shape + (1,) * (data.ndim - mask.ndim))
        data = jnp.where(m, data, neutral(op, data.dtype))
    if op == "sum" and indices_are_sorted:
        return sorted_segment_sum(data, segment_ids, num_segments,
                                  ends=ends, pos=pos)
    return _SEG[op](
        data, segment_ids, num_segments=num_segments,
        indices_are_sorted=indices_are_sorted,
    )


# A scan costs per ELEMENT, XLA's scatter-add per ROW up to 128 lanes: past
# this many columns the scatter wins (docs/KERNELS.md holds the readings).
SCAN_MAX_COLUMNS = 16
_SCAN_BLOCK = 128   # rows a block: one lane row


def sum_route(cols: int) -> str:
    """Which sum ``sorted_segment_sum`` runs for ``cols`` columns a row:
    ``scan`` or ``scatter``."""
    return "scan" if cols <= SCAN_MAX_COLUMNS else "scatter"


def _rows_upto(ids: jnp.ndarray, queries: jnp.ndarray):
    """For each query, how many of the SORTED ``ids`` are <= it
    (``searchsorted(side="right")``) as a search tree 128 wide: the blocks
    whose last id is <= the query count whole (the same question of the
    blocks' last ids), then ONE row gather of the first block that is not
    and a count along its lanes. Three levels at 4M ids where a binary
    search is 22 flat gathers, which cost four times a row gather each."""
    m = ids.shape[0]
    B = _SCAN_BLOCK
    if m <= 4 * B:
        return jnp.sum(ids[None, :] <= queries[:, None], axis=1,
                       dtype=jnp.int32)
    nb = -(-m // B)
    blocks = jnp.pad(ids, (0, nb * B - m),
                     constant_values=jnp.iinfo(ids.dtype).max).reshape(nb, B)
    whole = _rows_upto(blocks[:, -1], queries)      # blocks all <= query
    part = jnp.sum(blocks[jnp.minimum(whole, nb - 1), :] <= queries[:, None],
                   axis=1, dtype=jnp.int32)
    return jnp.where(whole == nb, m, whole * B + part)


# jitted, like ``_segmented_scan``: inside a caller's trace each is ONE
# cached call, not a hundred jnp wrappers traced anew — a program that is
# re-traced a request (the mesh column route) would otherwise fill the
# flight recorder's ring with ``xla.trace`` events.
@functools.partial(jax.jit, static_argnums=1)
def segment_ends_pos(segment_ids: jnp.ndarray, num_segments: int):
    """What ``sorted_segment_sum`` needs of the SORTED ids alone: ``ends
    [num_segments]``, each segment's last row (-1 for an empty segment),
    and ``pos [m]``, each row's offset inside its segment. With sorted ids
    "row i - d is in row i's segment" is exactly ``pos[i] >= d``. No
    scatter over the rows: a running maximum of the segment starts (the
    scan below over one segment, with ``maximum``: ``lax.cummax`` computes
    the same and takes the TPU's compiler 4.7 s at 4M rows where this
    takes 0.6) and a search of the ids (``_rows_upto``)."""
    m = segment_ids.shape[0]
    idx = jnp.arange(m, dtype=jnp.int32)
    start = jnp.concatenate(
        [jnp.ones((1,), bool), segment_ids[1:] != segment_ids[:-1]])
    pos = idx - _segmented_scan(jnp.where(start, idx, 0), idx, jnp.maximum)
    upto = _rows_upto(segment_ids,
                      jnp.arange(num_segments, dtype=segment_ids.dtype))
    before = jnp.concatenate([jnp.zeros((1,), jnp.int32), upto[:-1]])
    return jnp.where(upto > before, upto - 1, -1), pos


def _shift(x: jnp.ndarray, d: int):
    """``x`` moved ``d`` places up its last axis, zeros moving in."""
    return jnp.pad(x[..., :-d], [(0, 0)] * (x.ndim - 1) + [(d, 0)])


@functools.partial(jax.jit, static_argnums=2)
def _segmented_scan(x: jnp.ndarray, pos: jnp.ndarray, op=jnp.add):
    """Inclusive scan (``op``: add, or maximum over non-negative values —
    0 is the neutral element) along the LAST axis of ``x [..., m]`` inside
    the segments ``pos [m]`` describes. Contiguous shifted steps ``x[i] =
    op(x[i], x[i - d])`` where ``pos[i] >= d``, d = 1, 2, 4, ...: a row
    takes in rows of its own segment only, pairwise. Blocked: the steps
    inside blocks of one lane row, the same scan over the block carries (a
    block's last row lies ``pos // block`` blocks into its segment), one
    pass to bring the carry to the rows whose segment began in an earlier
    block."""
    m = x.shape[-1]
    B = _SCAN_BLOCK
    if m <= B:
        d = 1
        while d < m:
            x = op(x, jnp.where(pos >= d, _shift(x, d), 0))
            d *= 2
        return x
    nb = -(-m // B)
    if nb * B != m:   # a pad row is a segment of its own
        x = jnp.pad(x, [(0, 0)] * (x.ndim - 1) + [(0, nb * B - m)])
        pos = jnp.pad(pos, (0, nb * B - m))
    xb = x.reshape(x.shape[:-1] + (nb, B))
    pb = pos.reshape(nb, B)
    d = 1
    while d < B:
        xb = op(xb, jnp.where(pb >= d, _shift(xb, d), 0))
        d *= 2
    carry = _shift(_segmented_scan(xb[..., -1], pb[:, -1] // B, op), 1)
    lane = jnp.arange(B, dtype=pos.dtype)
    xb = op(xb, jnp.where(pb > lane, carry[..., None], 0))
    return xb.reshape(x.shape)[..., :m]


def sorted_segment_sum(
    data: jnp.ndarray,
    segment_ids: jnp.ndarray,
    num_segments: int,
    *,
    ends: jnp.ndarray | None = None,
    pos: jnp.ndarray | None = None,
):
    """``jax.ops.segment_sum`` for ids SORTED including padding rows, with
    no scatter over the rows: a segmented inclusive scan along the rows,
    then one gather of ``num_segments`` rows at the segments' last rows.

    XLA's scatter-add walks the rows one by one whether or not the ids are
    sorted (9.5 ns a row on a TPU v5e); the scan is about ten lane-dense
    passes. Each segment adds only its own rows, pairwise — float32-accurate
    with no cancellation (a global ``cumsum`` differenced at the segment ends
    would carry the whole prefix's rounding into a small segment). ``data``
    is ``[m]`` or ``[m, ...]``; with columns the scan runs with the rows on
    the minor axis (``[C, m]``), never on the lane-padded ``[m, C]`` buffer.
    Past ``SCAN_MAX_COLUMNS`` columns the scatter is the cheaper one and
    serves. ``ends`` / ``pos`` (``segment_ends_pos``) depend on the ids
    alone: a caller inside a loop computes them outside it."""
    m = data.shape[0]
    cols = data.size // max(m, 1)
    if m == 0 or sum_route(cols) == "scatter":
        return jax.ops.segment_sum(data, segment_ids,
                                   num_segments=num_segments,
                                   indices_are_sorted=True)
    if ends is None or pos is None:
        ends, pos = segment_ends_pos(segment_ids, num_segments)
    if data.ndim == 1:
        return segment_sums_at(data, ends, pos)
    # the two layout changes keep the scopes they had inside the scan
    # and the pick: a device op's ``op_name`` says which pass it is
    with jax.named_scope("combine.scan"):
        x = data.reshape(m, cols).T
    out = segment_sums_at(x, ends, pos)
    with jax.named_scope("combine.pick"):
        return out.T.reshape((num_segments,) + data.shape[1:])


def rows_upto(segment_ids: jnp.ndarray, num_segments: int):
    """``upto [num_segments]``: how many of the SORTED ids are <= each
    segment — its rows end there and the next segment's begin
    (``segment_ends_pos`` reads its ``ends`` off the same search)."""
    return _rows_upto(segment_ids,
                      jnp.arange(num_segments, dtype=segment_ids.dtype))


def integer_segment_sums(x: jnp.ndarray, upto: jnp.ndarray):
    """``sorted_segment_sum`` for INTEGER data with the rows on the minor
    axis, ``x [..., m]`` -> ``[..., num_segments]``, ``upto`` from
    ``rows_upto``: one running sum along the rows, differenced at the
    segments' ends. Exact: integer addition wraps, so the difference of
    two prefix sums is the segment's sum whatever the prefix grew to, as
    long as the segment's own sum fits the type — the rounding that makes
    a float sum scan inside its segments (``_segmented_scan``) does not
    exist here. On a TPU v5e a running sum costs 0.13 ns an element where
    the segmented scan costs 0.5 (PERF.md section 6, PR 39)."""
    assert jnp.issubdtype(x.dtype, jnp.integer)
    with jax.named_scope("combine.scan"):
        total = jnp.cumsum(x, axis=-1)
    with jax.named_scope("combine.pick"):
        at_end = jnp.where(upto > 0, total[..., jnp.maximum(upto - 1, 0)], 0)
        return at_end - _shift(at_end, 1)


def segment_sums_at(x: jnp.ndarray, ends: jnp.ndarray, pos: jnp.ndarray):
    """``sorted_segment_sum``'s scan and pick for data that already lies
    with the rows on the minor axis: ``x [..., m]`` -> ``[...,
    num_segments]``, ``ends`` / ``pos`` from ``segment_ends_pos``. A
    caller that builds ``[cols, m]`` itself (``ops/triangles``: 16
    million rows a tile) never holds the lane-padded ``[m, cols]``."""
    with jax.named_scope("combine.scan"):
        x = _segmented_scan(x, pos)
    with jax.named_scope("combine.pick"):
        return jnp.where(ends >= 0, x[..., jnp.maximum(ends, 0)], 0)


_V_BITS = 31  # segment_mode value budget: non-negative ints < 2**31 - 1
_V_NONE = (1 << _V_BITS) - 1   # a masked row's value: last in its segment


def segment_counts(segment_ids: jnp.ndarray, num_segments: int):
    """Rows per segment, masked rows included — the part of
    ``segment_mode``'s work that is a function of the ids alone. A caller
    whose ids do not change between calls (the rounds of one dispatch)
    computes it once and passes it as ``counts``."""
    return jax.ops.segment_sum(jnp.ones(segment_ids.shape, jnp.int32),
                               segment_ids, num_segments=num_segments)


def segment_counts_at(ends: jnp.ndarray, pos: jnp.ndarray):
    """``segment_counts`` of SORTED ids off the plan a caller already holds
    (``segment_ends_pos``): a segment's last row lies ``pos`` rows past
    its first, so it holds ``pos[ends] + 1`` rows — one gather of
    ``num_segments`` elements where the count of unsorted ids is a scatter
    over the rows (9.5 ns a row on a TPU v5e, like every scatter-add)."""
    return jnp.where(ends >= 0, pos[jnp.maximum(ends, 0)] + 1, 0)


def segment_mode(
    values: jnp.ndarray,
    segment_ids: jnp.ndarray,
    num_segments: int,
    mask: jnp.ndarray | None = None,
    default: int = -1,
    counts: jnp.ndarray | None = None,
):
    """Most frequent value per segment; ties break to the SMALLEST value.

    The sort-based generic-inbox path (SURVEY §7.3 "message-passing
    generality"): where the reference hands each vertex a mailbox of
    arbitrary messages (``VertexMutliQueue``), algorithms needing the full
    inbox — label histograms, majority votes — sort the flat (segment,
    value) pairs, measure the equal-value runs and pick each segment's
    longest. One sort of a packed 64-bit key, four running maxima and
    two gathers of ``num_segments`` rows; static shapes, no per-vertex
    loops and no scatter over the rows (on the TPU a sorted segment
    reduction of the rows costs four times the sort: PERF.md section 6).
    Values must be non-negative and below ``2**31 - 1``; segment ids lie
    in ``[0, num_segments)``; ``len(values)`` stays under ``2**30``.

    A masked (or out-of-range) row keeps its segment and sorts last in
    it, so where each segment lies in the sorted order depends on the ids
    alone: ``counts`` (``segment_counts``) says where, and a caller that
    has it passes it. Segments with no (unmasked) rows get ``default``.
    """
    m = len(values)
    idx = jnp.arange(m, dtype=jnp.int32)
    if counts is None:
        counts = segment_counts(segment_ids, num_segments)
    last = jnp.cumsum(counts) - 1            # a segment's last sorted row
    first = last - counts + 1
    # Out-of-range values would alias into neighbouring segments through
    # the packed key; park them with the masked rows so violations degrade
    # to "no message" instead of corrupting other segments' histograms.
    ok = (values >= 0) & (values < _V_NONE)
    if mask is not None:
        ok = ok & mask
    v = jnp.where(ok, values, _V_NONE).astype(jnp.int64)
    with jax.named_scope("mode.sort"):
        # one 64-bit operand: on the TPU it sorts as fast as two int32
        # keys (and four times faster on the CPU the tests run on). Equal
        # keys are the same row twice, so the order among them is nobody's
        # to keep: a stable sort carries an iota as a third operand, and
        # the TPU's sort costs about a nanosecond a row and operand
        ks = jax.lax.sort((segment_ids.astype(jnp.int64) << _V_BITS) | v,
                          is_stable=False)
    with jax.named_scope("mode.runs"):
        vs = (ks & _V_NONE).astype(jnp.int32)
        true = jnp.ones((1,), bool)
        new_seg = jnp.concatenate(
            [true, (ks[1:] >> _V_BITS) != (ks[:-1] >> _V_BITS)])
        start = jnp.concatenate([true, ks[1:] != ks[:-1]])  # (seg, value) run
        counted = jnp.concatenate([start[1:], true]) & (vs != _V_NONE)
        run_first = jax.lax.cummax(jnp.where(start, idx, 0))
        seg_first = jax.lax.cummax(jnp.where(new_seg, idx, 0))
        # a run's length, read at its last row, above its segment's first
        # row: no later segment's value is under an earlier one's, so ONE
        # running maximum is each segment's own
        reach = seg_first + jnp.where(counted, idx - run_first + 1, 0)
        best = jax.lax.cummax(reach)
        # runs ascend in value: a run strictly longer than every run before
        # it in the segment is a record, and the segment's LAST record is
        # its longest run of the smallest value
        record = counted & (reach > jnp.concatenate(
            [jnp.zeros((1,), jnp.int32), best[:-1]]))
        last_record = jax.lax.cummax(jnp.where(record, idx, -1))
    with jax.named_scope("mode.pick"):
        pos = last_record[jnp.maximum(last, 0)]
        found = (counts > 0) & (pos >= first)
        out = jnp.where(found, vs[jnp.maximum(pos, 0)], default)
    return out.astype(values.dtype)
