"""Segment combiners — the TPU-native replacement for vertex message passing.

The reference delivers typed point-to-point actor messages per vertex
(``VertexVisitor.scala:99-161`` → ``ReaderWorker.scala:137-157`` appending to
``VertexMutliQueue``). Here, a superstep's messages are a flat per-edge payload
array combined at the destination with an associative-commutative reduction —
one fused gather/segment-reduce the XLA scheduler can tile, instead of 2M-deep
actor mailboxes.
"""

from __future__ import annotations

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
):
    """Combine per-edge payloads at their destination vertex.

    `data` may have trailing feature dims; `mask` rows are replaced with the
    combiner's neutral element so padded edges are no-ops. `indices_are_sorted`
    may only be True when ids are sorted INCLUDING padding rows — the snapshot
    builder pads e_dst with n_pad-1 (the max id) to preserve the promise.
    """
    if op not in _SEG:
        raise ValueError(f"unknown combiner {op!r}; use one of {sorted(_SEG)}")
    if mask is not None:
        m = mask.reshape(mask.shape + (1,) * (data.ndim - mask.ndim))
        data = jnp.where(m, data, neutral(op, data.dtype))
    return _SEG[op](
        data, segment_ids, num_segments=num_segments,
        indices_are_sorted=indices_are_sorted,
    )


_V_BITS = 31  # segment_mode value budget: non-negative ints < 2**31 - 1
_V_NONE = (1 << _V_BITS) - 1   # a masked row's value: last in its segment


def segment_counts(segment_ids: jnp.ndarray, num_segments: int):
    """Rows per segment, masked rows included — the part of
    ``segment_mode``'s work that is a function of the ids alone. A caller
    whose ids do not change between calls (the rounds of one dispatch)
    computes it once and passes it as ``counts``."""
    return jax.ops.segment_sum(jnp.ones(segment_ids.shape, jnp.int32),
                               segment_ids, num_segments=num_segments)


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
