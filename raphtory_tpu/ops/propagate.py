"""F-wide feature propagation over the dst-sorted pair table — the kernel of
the columnar kind ``sgc`` (``engine/hopbatch.HopBatchedSGC``).

SGC's graph part (``algorithms/propagation.SGC``) is ``Y = S^K X`` with
``S = D~^-1/2 (A + A^T + I) D~^-1/2``: a vertex's state is a ROW of F
features, not a scalar, and a round moves such a row along every alive
pair, both ways, and keeps the vertex's own. Per round and column:

    g_v  = h_v / sqrt(d~_v)
    h'_v = (g_v + sum over (u -> v) of g_u + sum over (v -> u) of g_u)
           / sqrt(d~_v)

**One table, three kinds of row.** Every term of ``A + A^T + I`` is a row
``(to, frm, ent)`` of one per-log table (``build_table``): the pair table's
rows as they are, the same rows turned round, and one row a vertex, sorted
by receiver. ``ent`` says whose mask the row listens to: a pair's (both of
its rows) or a vertex's (its own row). So a round is ONE kind of pass —
gather the senders' rows, sum them at the receivers, sorted — there is no
combine at the source end of an unsorted table, and ``d~`` is the count of
a vertex's alive rows. The pass is a loop over steps of ``step_rows`` rows
(``[rows, F]`` never exists): one row gather a step and one sorted
scatter-add of the step into the accumulator. XLA's scatter-add passes
over its whole operand a call, whatever it adds — a millisecond a step on
a 336 MB accumulator — so a large table's step is large: 65,536 rows,
whose gathered 602 floats are 168 MB (a constant of the table's size; no
knob sizes it).

**Columns are walked, not packed.** A column's block is ``[n_pad, F]``, so
the C columns of a dispatch take their turns (``lax.map``) over the same
X, table and accumulator; their masks ride one bit a column in a 32-bit
word a row, gathered to the table's rows once a dispatch (once more for
every 32 columns past the first). A column walks the rows alive in it,
not the table (``_walked``: one sort a column brings them to the front,
the loop's trip count follows them): a day window's column costs a few
per cent of a month's.

**What leaves the device is small** (``summarise``): the F column sums
and the F column sums of squares (the host adds those in float64 for the
Frobenius norm: one float32 sum over all of Y scatters 3e-8 on the chip),
the ten vertices of largest ``d~`` and their rows, and the table rows the
column walked — nothing of size ``n x F``.
"""

from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from .segment import integer_segment_sums

#: rows a step of the pass, by the table's rows (``step_rows``)
STEP_SMALL, STEP_LARGE, LARGE_ROWS = 4096, 65536, 1 << 20
#: columns whose mask bits ride one word a row
WORD_COLUMNS = 32
#: the hubs whose rows are served
LEAD = 10
#: rows a block of the norm's sums (``_sums_of_squares``)
SQUARE_BLOCK = 4096


def fmix32(h):
    """murmur3's 32-bit finaliser, uint32 -> uint32, wrapping."""
    h = h ^ (h >> 16)
    h = h * jnp.uint32(0x85EBCA6B)
    h = h ^ (h >> 13)
    h = h * jnp.uint32(0xC2B2AE35)
    return h ^ (h >> 16)


def features(vids, dim: int, seed: int):
    """``X[v, j] = float32(fmix32(uint32(vid * dim + j) ^ uint32(seed))
    >> 8) * 2^-23 - 1``, float32 ``[len(vids), dim]`` in [-1, 1): a
    function of the GLOBAL vertex id, so no layout changes it, and every
    value is exact (24 bits) — bit-equal to numpy's."""
    with jax.named_scope("sgc.features"):
        v = vids.astype(jnp.uint32)[:, None]
        key = v * jnp.uint32(dim) + jnp.arange(dim, dtype=jnp.uint32)
        bits = fmix32(key ^ jnp.uint32(int(seed) & 0xFFFFFFFF))
        return (bits >> 8).astype(jnp.float32) * jnp.float32(2.0 ** -23) \
            - jnp.float32(1.0)


class PropagationTable(NamedTuple):
    """``A + A^T + I`` of a log's pair table as rows sorted by receiver
    (made on the host and put on the device once a log): ``to`` / ``frm``
    ``[rows]`` int32, ``ent [rows]`` = the entity whose mask bit the row
    listens to (a position of the pair table, ``m_pad +`` a vertex, or
    ``m_pad + n_pad``: a padding row, never alive), ``upto [n_pad]`` =
    where each receiver's rows end (what ``ops/segment.rows_upto``
    computes: the degree pass's structure), ``inv_sqrt`` float32 =
    ``1 / sqrt(d)`` for every ``d~`` a vertex of this table can have (0
    at 0), rounded from float64. A scale is off the same way for every
    vertex of its degree, and most of ``Y``'s norm lies with vertices of
    a handful of small degrees, so its error does not average out of
    the norm: the chip's own ``rsqrt`` is off by up to two ulp (rms
    4.2e-8 over d = 1..60,000 where the rounded value's is 2.7e-8), and
    four scalings by it read 1e-7 of one sign there (PERF.md section 6,
    PR 44)."""

    to: jax.Array
    frm: jax.Array
    ent: jax.Array
    upto: jax.Array
    inv_sqrt: jax.Array


def step_rows(rows: int) -> int:
    """Rows a step of the pass over a table of ``rows`` rows (padded or
    not: the answer is the same). The scatter-add of a step passes over
    the whole ``[n_pad, F]`` accumulator, so on a large table a step is
    large (on a TPU v5e 288 / 92 / 43 ns a row at steps of 4,096 /
    16,384 / 65,536 rows of 602 floats over 131,072 vertices: PERF.md
    section 6, PR 44); on a small one the accumulator is small too and
    a large step would only be padding."""
    return STEP_LARGE if rows >= LARGE_ROWS else STEP_SMALL


def table_rows(m_pad: int, n_pad: int) -> int:
    """Rows of the table over a pair table of ``m_pad`` rows and ``n_pad``
    vertices: both directions and one row a vertex, padded to whole
    steps."""
    rows = 2 * m_pad + n_pad
    return -(-rows // step_rows(rows)) * step_rows(rows)


def build_table(e_src, e_dst, n_pad: int) -> PropagationTable:
    """The table of ``(e_src, e_dst)``, the (dst, src)-sorted pair table
    with its padding rows (which stay rows of pairs that are never
    alive), as HOST arrays: one numpy sort of ``2 * m_pad + n_pad`` packed
    ``(receiver, sender)`` keys, about a second at 7.6M rows (the same
    sort as a device program takes the TPU's compiler a minute)."""
    e_src, e_dst = np.asarray(e_src, np.int64), np.asarray(e_dst, np.int64)
    m_pad = len(e_src)
    own = np.arange(n_pad, dtype=np.int64)
    pair = np.arange(m_pad, dtype=np.int32)
    key = np.concatenate([e_dst << 32 | e_src, e_src << 32 | e_dst,
                          own << 32 | own])
    order = np.argsort(key)
    key = key[order]
    ent = np.concatenate([pair, pair, m_pad + own.astype(np.int32)])[order]
    pad = table_rows(m_pad, n_pad) - len(key)
    to = np.pad((key >> 32).astype(np.int32), (0, pad),
                constant_values=n_pad - 1)
    frm = np.pad((key & 0xFFFFFFFF).astype(np.int32), (0, pad),
                 constant_values=n_pad - 1)
    ent = np.pad(ent, (0, pad), constant_values=m_pad + n_pad)
    upto = np.searchsorted(to, np.arange(n_pad), side="right")
    # a power of two of entries, so that logs of one shape share a program
    most = 1 << int(np.diff(upto, prepend=0).max()).bit_length()
    inv_sqrt = 1.0 / np.sqrt(np.maximum(np.arange(most), 1.0))
    inv_sqrt[0] = 0.0
    return PropagationTable(to, frm, ent, upto.astype(np.int32),
                            inv_sqrt.astype(np.float32))


def _walked(table: PropagationTable, alive):
    """The rows a column walks, first and in the table's order: its
    ``alive`` rows — ``(frm, to, steps)``, ``steps`` the steps of the pass
    that hold them. One sort by a key that sends the rest behind, their
    receiver set to the last vertex so that the receivers stay sorted and
    their sender past the senders (they read 0 and add nothing); a day
    window walks a few per cent of what a month does."""
    rows = table.to.shape[0]
    n_pad = table.upto.shape[0]
    idx = jnp.arange(rows, dtype=jnp.int32)
    _, frm, to = jax.lax.sort(
        (jnp.where(alive, idx, idx + rows),
         jnp.where(alive, table.frm, n_pad),
         jnp.where(alive, table.to, n_pad - 1)),
        num_keys=1, is_stable=False)
    steps = -(-jnp.sum(alive.astype(jnp.int32)) // step_rows(rows))
    return frm, to, steps


def _accumulate(g, frm, to, steps):
    """``acc[to] += g[frm]`` over the first ``steps`` steps of the walked
    rows: ``[n_pad, F]``. A step is one row gather (a sender past the
    last vertex, a row that is not alive, reads 0) and one sorted
    scatter-add into the accumulator."""
    n_pad, F = g.shape
    step = step_rows(frm.shape[0])

    def one(i, acc):
        def cut(a):
            return jax.lax.dynamic_slice(a, (i * step,), (step,))

        with jax.named_scope("sgc.gather"):
            rows = g.at[cut(frm), :].get(mode="fill", fill_value=0.0)
        with jax.named_scope("sgc.combine"):
            return acc.at[cut(to), :].add(rows, indices_are_sorted=True)

    return jax.lax.fori_loop(0, steps, one, jnp.zeros((n_pad, F), g.dtype))


def inv_sqrt_degree(deg):
    """``1 / sqrt(d~)`` as a float32 column ``[n, 1]``, 0 where ``d~`` is
    0 (a vertex that is not alive)."""
    return jnp.where(deg > 0, 1.0 / jnp.sqrt(
        jnp.maximum(deg, 1).astype(jnp.float32)), 0.0)[:, None]


def propagate(X, table: PropagationTable, alive, v_alive, rounds: int):
    """``(Y, d~)`` of one column: ``Y = S^rounds X`` ``[n_pad, F]`` (rows
    of vertices that are not alive are 0) and ``d~ [n_pad]`` int32 (0
    there). ``alive [rows]`` / ``v_alive [n_pad]``: the column's masks of
    the table's rows and of the vertices."""
    with jax.named_scope("sgc.degree"):
        deg = integer_segment_sums(alive.astype(jnp.int32), table.upto)
    with jax.named_scope("sgc.walked"):
        frm, to, steps = _walked(table, alive)
    with jax.named_scope("sgc.scale"):
        s = table.inv_sqrt[deg][:, None]
        h0 = jnp.where(v_alive[:, None], X, 0.0)

    def one_round(_, h):
        with jax.named_scope("sgc.scale"):
            g = h * s
        acc = _accumulate(g, frm, to, steps)
        with jax.named_scope("sgc.scale"):
            return acc * s

    return jax.lax.fori_loop(0, rounds, one_round, h0), deg


def _sums_of_squares(Y):
    """``[F]`` float32 column sums of ``Y * Y``, block by block of
    ``SQUARE_BLOCK`` rows and then over the blocks: no running sum adds
    more than a block's terms, so its rounding stays far under the one
    long sum's, whatever order the compiler gives a reduction."""
    n, F = Y.shape
    if n % SQUARE_BLOCK or n < 2 * SQUARE_BLOCK:
        return jnp.sum(Y * Y, axis=0)
    return jnp.sum(jax.lax.map(
        lambda b: jnp.sum(b * b, axis=0),
        Y.reshape(n // SQUARE_BLOCK, SQUARE_BLOCK, F)), axis=0)


def summarise(Y, deg, v_alive, edges):
    """What is served of a column, on the device: ``col_sum`` / ``col_sq
    [F]`` (the column sums of Y and of its squares: the squared
    Frobenius norm is ``col_sq``'s sum, which the host takes in float64 —
    ``_sums_of_squares``: short float32 sums whose errors average out,
    where one sum over all of Y scattered 3e-8 on the chip), ``top_idx``
    / ``top_deg [LEAD]`` = the vertices of largest ``d~`` (the smaller
    index first among equals; a vertex that is not alive has ``d~`` 0
    and comes last) and ``probe [LEAD, F]`` = their rows of Y, ``vertices`` and
    ``edges`` (the alive counts), and ``walked`` = the rows of ``A + A^T
    + I`` alive in the column, each moved once a round (``d~`` is a
    vertex's count of them, so this is its sum; the ledger's
    ``device.feature_rows`` reads it)."""
    with jax.named_scope("sgc.summary"):
        lead = min(LEAD, Y.shape[0])
        neg, idx = jax.lax.sort(
            (-deg, jnp.arange(Y.shape[0], dtype=jnp.int32)), num_keys=2)
        top = idx[:lead]
        return {"col_sum": jnp.sum(Y, axis=0),
                "col_sq": _sums_of_squares(Y),
                "top_idx": top, "top_deg": -neg[:lead], "probe": Y[top, :],
                "vertices": jnp.sum(v_alive.astype(jnp.int32)),
                "edges": jnp.asarray(edges, jnp.int32),
                "walked": jnp.sum(deg)}


def _words(mask):
    """``[rows, C]`` bool -> ``[rows]`` uint32, bit c = column c."""
    C = mask.shape[1]
    return jnp.sum(mask.astype(jnp.uint32)
                   << jnp.arange(C, dtype=jnp.uint32)[None, :],
                   axis=1, dtype=jnp.uint32)


def sgc_columns(me, mv, rounds: int, X, table: PropagationTable):
    """Every (hop, window) column of a dispatch: ``me [m_pad, C]`` / ``mv
    [n_pad, C]`` the columns' masks over the pair table and the vertices
    -> ``summarise``'s pytree with a leading ``[C]``. The columns share
    X, the table and the pass; they differ in their masks, which ride
    ``WORD_COLUMNS`` to a word: past that many columns the table's rows
    gather another word."""
    def group(me, mv):
        with jax.named_scope("sgc.masks"):
            we, wv = _words(me), _words(mv)
            # a row's word: its pair's, its vertex's, a padding row's 0
            wr = jnp.concatenate([we, wv, jnp.zeros((1,), jnp.uint32)])[
                table.ent]

        def column(c):
            def bit(w):
                return ((w >> c) & 1).astype(bool)

            v_alive = bit(wv)
            Y, deg = propagate(X, table, bit(wr), v_alive, rounds)
            return summarise(Y, deg, v_alive,
                             jnp.sum(bit(we).astype(jnp.int32)))

        return jax.lax.map(column,
                           jnp.arange(me.shape[1], dtype=jnp.uint32))

    outs = [group(me[:, c:c + WORD_COLUMNS], mv[:, c:c + WORD_COLUMNS])
            for c in range(0, me.shape[1], WORD_COLUMNS)]
    return jax.tree_util.tree_map(
        lambda *xs: jnp.concatenate(xs, axis=0), *outs) \
        if len(outs) > 1 else outs[0]
