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


_V_BITS = 31  # segment_mode value budget: non-negative ints < 2**31


def segment_mode(
    values: jnp.ndarray,
    segment_ids: jnp.ndarray,
    num_segments: int,
    mask: jnp.ndarray | None = None,
    default: int = -1,
):
    """Most frequent value per segment; ties break to the SMALLEST value.

    The sort-based generic-inbox path (SURVEY §7.3 "message-passing
    generality"): where the reference hands each vertex a mailbox of
    arbitrary messages (``VertexMutliQueue``), algorithms needing the full
    inbox — label histograms, majority votes — sort the flat (segment,
    value) pairs, count equal-value runs with one segment-sum, and reduce
    runs per segment with one segment-max. Three XLA ops, static shapes, no
    per-vertex loops. Values must be non-negative int32-range (< 2**31).

    Segments with no (unmasked) rows get ``default``.
    """
    m = len(values)
    v = values.astype(jnp.int64)
    s = segment_ids.astype(jnp.int64)
    # Out-of-range values would alias into neighbouring segments through the
    # packed key; park them with the masked rows so violations degrade to
    # "no message" instead of corrupting other segments' histograms.
    in_range = (v >= 0) & (v < (1 << _V_BITS))
    if mask is not None:
        in_range = in_range & mask
    s = jnp.where(in_range, s, num_segments)  # park bad rows at the end
    v = jnp.where(in_range, v, 0)
    key = (s << _V_BITS) | v
    ks = jnp.sort(key)
    ss = ks >> _V_BITS
    vs = ks & ((1 << _V_BITS) - 1)
    start = jnp.concatenate(
        [jnp.ones((1,), bool), ks[1:] != ks[:-1]])  # (seg,val) run starts
    run_id = jnp.cumsum(start) - 1
    run_len = jax.ops.segment_sum(
        jnp.ones((m,), jnp.int64), run_id, num_segments=m,
        indices_are_sorted=True)
    # one candidate per run (its start row): score = count ⊕ inverted value,
    # so segment-max = (max count, then min value)
    inv_v = ((1 << _V_BITS) - 1) - vs
    score = run_len[run_id] * (1 << _V_BITS) + inv_v
    score = jnp.where(start, score, -1)
    seg_of_row = jnp.where(ss < num_segments, ss, num_segments)
    best = jax.ops.segment_max(
        score, seg_of_row, num_segments=num_segments + 1,
        indices_are_sorted=True)[:num_segments]
    val = ((1 << _V_BITS) - 1) - (best & ((1 << _V_BITS) - 1))
    return jnp.where(best > 0, val, default).astype(values.dtype)
