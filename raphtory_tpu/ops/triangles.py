"""Neighbour-set intersection: the triangles of a log's all-pairs graph as
a static table, and the count of the ones a view keeps.

Every columnar engine runs a view as a MASK over the one dst-sorted pair
table (``engine/device_sweep.GlobalTables``): positions never change
across a sweep, the fold only says which rows are alive. This module
takes that design one order up. The undirected edges of the all-pairs
graph (a pair and its reverse are one edge, a self-loop is none) and the
triangles among them are functions of the log alone; a view's local
clustering coefficients are sums over the triangles whose three edges
the view keeps.

Orientation. Vertices are ranked by (degree in the all-pairs graph, dense
id); an undirected edge points from its end of lower rank (``lo``) to the
other (``hi``), so a hub keeps few out-neighbours and every triangle
``v1 < v2 < v3`` is listed once, from ``v1``, with its edges ``e1 = (v1,
v2)``, ``e2 = (v1, v3)``, ``e3 = (v2, v3)``. Edges are numbered in (lo,
hi) order; triangle rows ascend by (e1, e2).

What a view adds (``lcc_columns``). ``word[e]`` packs, for up to 16
columns at once, whether the pair ``lo -> hi`` (bits 0-15) and ``hi ->
lo`` (bits 16-31) is alive in each column: one lookup a triangle edge
serves every column of the dispatch. An edge is *adjacent* in a column if
either bit is set, a triangle is alive if its three edges are, and an
alive triangle hands each corner the directed pairs of the side opposite
to it (1 or 2): ``tri(v) = |{(u, w) in E : u, w in N(v)}|``.

Tiles. Rows are cut into tiles at ``v1`` boundaries, so that the edges a
tile credits (``e1`` and ``e2``, both out of ``v1``) are one contiguous
range of at most ``tile_edges`` ids: the corner sums are sorted segment
sums inside a tile — ``v1`` and ``v2`` over the rows as they lie, ``v3``
after one sort of the tile's rows by ``e2`` — and the working set is a
tile's, whatever the log's size. The counts are integers, so a tile's
sums are a running sum differenced at the segments' ends
(``ops/segment.integer_segment_sums``); the sums of the edges at their
vertices, once a dispatch, are ``sorted_segment_sum``'s scan. No gather
of single elements and no scatter runs over the rows: ``e1``'s word is
spread along its segment, ``e2``'s and ``e3``'s are lane-row lookups.
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

from ..native import lib as _native
from .segment import (integer_segment_sums, rows_upto, segment_ends_pos,
                      segment_sums_at)

#: a tile's rows and the edge ids it may credit, at most (a vertex whose
#: own triangles or out-edges pass them makes the tiles that much larger)
TILE_ROWS = 1 << 21
TILE_EDGES = 1 << 17
#: columns a word holds: a bit each for the pair and for its reverse
WORD_COLUMNS = 16


def _pad_pow2(n: int, floor: int) -> int:
    return max(floor, 1 << int(np.ceil(np.log2(max(n, 1)))))


def _pad_step(n: int, step: int) -> int:
    return -(-max(n, 1) // step) * step


@dataclasses.dataclass(frozen=True, eq=False)
class TriangleTable:
    """The static half of an intersection over one pair table (host
    arrays, int32; ``U`` undirected edges, ``T`` triangles):

    - ``u_fwd`` / ``u_bwd`` ``[u_pad]``: the pair-table rows of an edge's
      ``lo -> hi`` and ``hi -> lo`` pairs, ``m_pad`` where the log holds
      no such pair; ``u_lo`` ``[u_pad]``: its lower end's rank (ascending,
      padding included);
    - ``hi_order`` / ``hi_sorted`` ``[u_pad]``: the edges by their higher
      end's rank, and that rank;
    - ``rank_of`` ``[n_pad]``: a dense vertex's rank;
    - ``rows`` ``[3, tiles, tile_rows]``: a triangle's ``e1``, ``e2``,
      ``e3``, padded with edge ``U`` (no pair: alive in no column);
    - ``tile_lo`` ``[tiles]``: the first edge id a tile credits.
    """

    u_fwd: np.ndarray
    u_bwd: np.ndarray
    u_lo: np.ndarray
    hi_order: np.ndarray
    hi_sorted: np.ndarray
    rank_of: np.ndarray
    rows: np.ndarray
    tile_lo: np.ndarray
    edges: int
    triangles: int
    tile_edges: int

    ARRAYS = ("u_fwd", "u_bwd", "u_lo", "hi_order", "hi_sorted", "rank_of",
              "rows", "tile_lo")

    @property
    def walked_rows(self) -> int:
        """Triangle rows a dispatch walks a word (padding included)."""
        return int(self.rows.shape[1] * self.rows.shape[2])

    @property
    def nbytes(self) -> int:
        return int(sum(getattr(self, k).nbytes for k in self.ARRAYS))

    def device_args(self) -> tuple:
        """The arrays ``lcc_columns`` takes, in its order: the edge
        tables, then the rows' three columns, ``[tiles, tile_rows]``
        each, then ``tile_lo``."""
        return (self.u_fwd, self.u_bwd, self.u_lo, self.hi_order,
                self.hi_sorted, self.rank_of, *self.rows, self.tile_lo)


def _triangles_numpy(offsets: np.ndarray, nbr: np.ndarray):
    """``native.triangles`` in numpy: every edge with each later
    out-neighbour of its lower end is a wedge, a wedge whose two far ends
    are joined is a triangle."""
    n, U = len(offsets) - 1, len(nbr)
    lo = np.repeat(np.arange(n, dtype=np.int64), np.diff(offsets))
    key = lo * n + nbr                  # ascending: edge id = position
    later = offsets[lo + 1] - 1 - np.arange(U)
    ends = np.cumsum(later)
    out = [[], [], []]
    at = 0
    while at < U:
        stop = max(int(np.searchsorted(
            ends, (ends[at - 1] if at else 0) + (1 << 23), side="right")),
            at + 1)
        cnt = later[at:stop]
        first = np.repeat(np.arange(at, stop), cnt)
        second = first + 1 + (np.arange(len(first))
                              - np.repeat(np.cumsum(cnt) - cnt, cnt))
        q = nbr[first].astype(np.int64) * n + nbr[second]
        third = np.minimum(np.searchsorted(key, q), U - 1)
        hit = key[third] == q
        for rows, e in zip(out, (first, second, third)):
            rows.append(e[hit].astype(np.int32))
        at = stop
    e1, e2, e3 = (np.concatenate(r) if r else np.empty(0, np.int32)
                  for r in out)
    return np.bincount(lo[e1], minlength=n).astype(np.int64), e1, e2, e3


def build_table(e_src: np.ndarray, e_dst: np.ndarray, m: int, n: int,
                n_pad: int, m_pad: int, *, tile_rows: int = TILE_ROWS,
                tile_edges: int = TILE_EDGES,
                native: bool = True) -> TriangleTable:
    """The table of a pair table's first ``m`` rows (``e_src`` / ``e_dst``
    dense vertex ids below ``n``). ``native=False`` lists the triangles in
    numpy (the tests hold the two to each other)."""
    s, d = e_src[:m].astype(np.int64), e_dst[:m].astype(np.int64)
    keep = np.flatnonzero(s != d)       # a self-loop is no edge
    s, d = s[keep], d[keep]
    uk, inv = np.unique(np.minimum(s, d) * n_pad + np.maximum(s, d),
                        return_inverse=True)
    U = len(uk)
    ab = np.full(U, m_pad, np.int64)    # pair rows by direction in id order
    ba = np.full(U, m_pad, np.int64)
    ab[inv[s < d]] = keep[s < d]
    ba[inv[s > d]] = keep[s > d]
    ua, ub = uk // n_pad, uk % n_pad
    deg = np.bincount(ua, minlength=n_pad) + np.bincount(ub, minlength=n_pad)
    rank = np.arange(n_pad, dtype=np.int64)
    rank[np.lexsort((np.arange(n), deg[:n]))] = np.arange(n)
    ra, rb = rank[ua], rank[ub]
    lo, hi = np.minimum(ra, rb), np.maximum(ra, rb)
    fwd, bwd = np.where(ra < rb, ab, ba), np.where(ra < rb, ba, ab)
    order = np.lexsort((hi, lo))
    lo, hi, fwd, bwd = lo[order], hi[order], fwd[order], bwd[order]
    offsets = np.searchsorted(lo, np.arange(n_pad + 1)).astype(np.int64)

    cnt = _native.triangles(offsets, hi) if native else None
    slow = None
    if cnt is None:
        slow = _triangles_numpy(offsets, hi)
        cnt = slow[0]
    row_end = np.cumsum(cnt)
    T = int(row_end[-1]) if len(row_end) else 0

    # tiles: whole v1 blocks, at most B rows and E edge ids each
    B = max(min(tile_rows, _pad_pow2(T, 1024)), int(cnt.max(initial=0)))
    E = max(min(tile_edges, _pad_pow2(U, 128)),
            int(np.diff(offsets).max(initial=0)))
    B, E = _pad_step(B, 1024), _pad_step(E, 128)
    last = int(np.searchsorted(row_end, T)) + 1 if T else 1   # v1's with rows
    cuts, v = [0], 0
    while v < last:
        base_r = int(row_end[v - 1]) if v else 0
        nxt = min(int(np.searchsorted(row_end, base_r + B, side="right")),
                  int(np.searchsorted(offsets, offsets[v] + E,
                                      side="right")) - 1, last)
        v = max(nxt, v + 1)
        cuts.append(v)
    cuts = np.asarray(cuts)
    tiles = len(cuts) - 1
    u_pad = _pad_step(U + 1, 1 << 10)
    # a vertex's rows lie from row_off[v] on: its tile's start plus what
    # the tile's earlier vertices hold; each tile's tail is padding
    row_start = row_end - cnt
    tile_of = np.searchsorted(cuts, np.arange(len(cnt)), side="right") - 1
    tile_of = np.minimum(tile_of, tiles - 1)
    row_off = tile_of * B + row_start - row_start[cuts[tile_of]]
    rows = np.empty((3, tiles, B), np.int32)
    flat = rows.reshape(3, tiles * B)
    fill = np.append(row_start, T)[cuts[1:]] - row_start[cuts[:-1]]
    for k in range(tiles):
        rows[:, k, fill[k]:] = U
    if slow is None:
        _native.triangles(offsets, hi, row_off, tuple(flat))
    else:
        for k in range(tiles):
            r0 = int(row_start[cuts[k]])
            for a in range(3):
                rows[a, k, : fill[k]] = slow[1 + a][r0:r0 + fill[k]]
    tile_lo = offsets[cuts[:-1]].astype(np.int32)

    def padded(a, fill, size=u_pad):
        out = np.full(size, fill, np.int32)
        out[: len(a)] = a
        return out

    by_hi = np.argsort(hi, kind="stable")
    rank_of = rank.astype(np.int32)
    return TriangleTable(
        u_fwd=padded(fwd, m_pad), u_bwd=padded(bwd, m_pad),
        u_lo=padded(lo, n_pad - 1),
        hi_order=padded(by_hi, U), hi_sorted=padded(hi[by_hi], n_pad - 1),
        rank_of=rank_of, rows=rows, tile_lo=tile_lo,
        edges=U, triangles=T, tile_edges=E)


def _bit_counts(words, cols):
    """``[len(cols), rows]`` int32: how many of a word's two pair bits a
    column holds (0, 1 or 2)."""
    w = words[None, :]
    c = cols[:, None]
    return (((w >> c) & 1) + ((w >> (c + WORD_COLUMNS)) & 1)).astype(
        jnp.int32)


def _adjacent(words):
    """A word's columns in which either pair is alive (low 16 bits)."""
    return (words | (words >> WORD_COLUMNS)) & ((1 << WORD_COLUMNS) - 1)


def _segment_sums(x, ids, num_segments: int):
    """``sorted_segment_sum`` of ``x [cols, rows]`` by sorted ``ids``."""
    ends, pos = segment_ends_pos(ids, num_segments)
    return segment_sums_at(x, ends, pos)


def _lookup(table, e):
    """``table.reshape(-1)[e]`` for ``table [rows, 128]``: a gather of
    whole 128-lane rows and a pick of the lane, not a gather of single
    elements — 2.8 ns a lookup on a TPU v5e where the flat gather takes
    8.7 (PERF.md section 6, PR 39)."""
    rows = table[e >> 7, :]
    lane = jnp.arange(128, dtype=e.dtype)[None, :]
    return jnp.sum(jnp.where(lane == (e & 127)[:, None], rows, 0), axis=1,
                   dtype=table.dtype)


def _spread(values, upto, rows: int):
    """``values[segment of row]`` for every row of sorted segments
    (``upto`` from ``rows_upto``) with no lookup over the rows: each
    segment's difference from the one before is added at its first row
    and a running sum carries it along (unsigned, wrapping: a row's sum
    telescopes to its own segment's value; an empty segment starts where
    the next one does, and both differences land there)."""
    start = jnp.concatenate([jnp.zeros((1,), upto.dtype), upto[:-1]])
    step = values - jnp.concatenate(
        [jnp.zeros((1,), values.dtype), values[:-1]])
    seeded = jnp.zeros((rows,), values.dtype).at[start].add(step,
                                                           mode="drop")
    return jnp.cumsum(seeded)


def _word_counts(me, n_pad: int, tile_edges: int, u_fwd, u_bwd, u_lo,
                 hi_order, hi_sorted, rank_of, rows_e1, rows_e2, rows_e3,
                 tile_lo):
    """``(tri, deg)`` ``[C, n_pad]`` int32 for the ``C <= 16`` columns of
    ``me [m_pad, C]``."""
    C = me.shape[1]
    u_pad = u_fwd.shape[0]
    E = tile_edges
    cols = jnp.arange(C, dtype=jnp.uint32)
    with jax.named_scope("lcc.adjacent"):
        # a pair's columns as bits, then an edge's word: its pair's bits
        # low, its reverse pair's high; row m_pad is "no such pair"
        bits = jnp.sum(me.astype(jnp.uint32) << cols[None, :], axis=1,
                       dtype=jnp.uint32)
        bits = jnp.concatenate([bits, jnp.zeros((1,), jnp.uint32)])
        word = bits[u_fwd] | (bits[u_bwd] << WORD_COLUMNS)       # [u_pad]
        adj = _adjacent(word)
        # a tile reads the E words from its first edge on; the rows look
        # words up in lane rows (u_pad and E are multiples of 128)
        word = jnp.concatenate([word, jnp.zeros((E,), jnp.uint32)])
        word_rows = word.reshape(-1, 128)

    def tile(acc, xs):
        acc12, acc3 = acc
        e1, e2, e3, e_lo = xs
        with jax.named_scope("lcc.close"):
            # rows lie in e1's order: e1's word is spread along its
            # segment, e2's and e3's are looked up
            seg1 = jnp.minimum(e1 - e_lo, E - 1)
            upto1 = rows_upto(seg1, E)
            w1 = _spread(jax.lax.dynamic_slice(word, (e_lo,), (E,)), upto1,
                         e1.shape[0])
            w2, w3 = _lookup(word_rows, e2), _lookup(word_rows, e3)
            alive = _adjacent(w1) & _adjacent(w2) & _adjacent(w3)
            live = ((alive[None, :] >> cols[:, None]) & 1).astype(jnp.int32)
            # v1 is handed side e3's pairs, v2 side e2's: both ride the
            # rows' own order, which is e1's
            x12 = jnp.concatenate([live * _bit_counts(w3, cols),
                                   live * _bit_counts(w2, cols)])
            # v3 is handed side e1's, keyed by e2: the tile's rows sorted
            # by e2, their 2 bits a column in one word
            c1 = _bit_counts(w1, cols) * live
            packed = jnp.sum(c1.astype(jnp.uint32) << (2 * cols[:, None]),
                             axis=0, dtype=jnp.uint32)
            seg2, packed = jax.lax.sort(
                (jnp.minimum(e2 - e_lo, E - 1), packed), num_keys=1,
                is_stable=False)
            x3 = ((packed[None, :] >> (2 * cols[:, None])) & 3).astype(
                jnp.int32)
        with jax.named_scope("lcc.sum"):
            s12 = integer_segment_sums(x12, upto1)             # [2C, E]
            s3 = integer_segment_sums(x3, rows_upto(seg2, E))  # [C, E]
        zero = jnp.zeros((), e_lo.dtype)
        return (jax.lax.dynamic_update_slice(acc12, s12, (zero, e_lo)),
                jax.lax.dynamic_update_slice(acc3, s3, (zero, e_lo))), None

    # a tile writes the E ids from its first; the next tile's range starts
    # inside that and is written after it, so what stays is each tile's own
    acc0 = (jnp.zeros((2 * C, u_pad + E), jnp.int32),
            jnp.zeros((C, u_pad + E), jnp.int32))
    (acc12, acc3), _ = jax.lax.scan(
        tile, acc0, (rows_e1, rows_e2, rows_e3, tile_lo))
    with jax.named_scope("lcc.sum"):
        # edges to vertices, tri and deg side by side: the scanned sum
        # PageRank's destination combine runs
        near = ((adj[None, :] >> cols[:, None]) & 1).astype(jnp.int32)
        at_lo = jnp.concatenate([acc12[:C, :u_pad], near])
        at_hi = jnp.concatenate(
            [acc12[C:, :u_pad] + acc3[:, :u_pad], near])[:, hi_order]
        both = _segment_sums(at_lo, u_lo, n_pad) \
            + _segment_sums(at_hi, hi_sorted, n_pad)          # by rank
        both = both[:, rank_of]                               # by dense id
    return both[:C], both[C:]


def lcc_columns(me, n_pad: int, tile_edges: int, *table):
    """``[C, 2, n_pad]`` int32: per column and dense vertex, ``tri`` (the
    directed pairs among its neighbours) and ``deg`` (its undirected
    neighbours), under the pair masks ``me [m_pad, C]``. ``table`` is
    ``TriangleTable.device_args()``. Past ``WORD_COLUMNS`` columns the
    triangle rows are walked once a word."""
    out = []
    for c0 in range(0, me.shape[1], WORD_COLUMNS):
        tri, deg = _word_counts(me[:, c0:c0 + WORD_COLUMNS], n_pad,
                                tile_edges, *table)
        out.append(jnp.stack([tri, deg], axis=1))
    return jnp.concatenate(out) if len(out) > 1 else out[0]
