"""Local clustering coefficient (LCC) as LDBC Graphalytics v1.0 specifies
it for a directed graph (Iosup et al., VLDB 2016), from the
specification's equations and nothing of the program:

    N(v)   = N_in(v) | N_out(v), v itself excluded
    LCC(v) = 0                                        if |N(v)| <= 1
             |{(u, w): u, w in N(v), (u, w) in E}|
             / (|N(v)| * (|N(v)| - 1))                otherwise

The neighbourhood is undirected (a neighbour joined both ways is one
neighbour); the edges counted among the neighbours are directed (u -> w
and w -> u are two). Per vertex the answer is two integers, ``deg =
|N(v)|`` and ``tri`` = the numerator, and the coefficient.

Departures from the specification, each also under ``assumed`` in the
configuration's file: the graph is the store's fold at T under a window
(the specification's graph is static); a self-loop is no neighbour and
no counted edge; repeated events of a pair are one edge (the fold's
graph is simple). numpy only.

How it counts. The definition walks every pair of neighbours of every
vertex, the sum of ``deg^2`` (5.2e9 pairs on the cell's graph); this
module lists each triangle of the undirected graph once instead, from
its vertex of lowest (degree, id) — every undirected edge points from
the lower to the higher, so a hub keeps few out-neighbours — and hands
each corner the directed edges on the side opposite to it.
``tests/test_lcc.py`` holds it to the definition's own walk of Python
sets at a small size.
"""

from __future__ import annotations

import numpy as np

#: the served row's checksums are taken modulo this prime (2^61 - 1)
P61 = (1 << 61) - 1

#: wedges tested at a time (bounds the working set, not the answer)
_CHUNK = 1 << 24


def counts(vm, src, dst, directions: str = "directed"):
    """``(deg, tri)``, int64 ``[len(vm)]`` each. ``directions``
    ``"undirected"`` counts every edge among the neighbours once,
    whatever its directions: the wrong computation the control holds
    ``compare`` against."""
    n = len(vm)
    src, dst = np.asarray(src, np.int64), np.asarray(dst, np.int64)
    keep = src != dst                           # a self-loop is no edge
    directed = np.unique(src[keep] * n + dst[keep])
    s, d = directed // n, directed % n
    und = np.unique(np.minimum(s, d) * n + np.maximum(s, d))
    a, b = und // n, und % n

    def present(x, y):                          # is x -> y an edge
        at = np.minimum(np.searchsorted(directed, x * n + y),
                        max(len(directed) - 1, 0))
        return directed[at] == x * n + y if len(directed) else \
            np.zeros(len(x), bool)

    both = present(a, b).astype(np.int64) + present(b, a)
    if directions == "undirected":
        both = np.ones_like(both)
    deg = np.bincount(a, minlength=n) + np.bincount(b, minlength=n)
    # every undirected edge from its end of lower (degree, id) to the other
    rank = np.empty(n, np.int64)
    rank[np.lexsort((np.arange(n), deg))] = np.arange(n)
    lo, hi = np.minimum(rank[a], rank[b]), np.maximum(rank[a], rank[b])
    order = np.lexsort((hi, lo))
    lo, hi, both = lo[order], hi[order], both[order]
    key = lo * n + hi                           # sorted: the edge set
    offsets = np.searchsorted(lo, np.arange(n + 1))
    # an edge u -> w with each later out-neighbour x of u is a wedge
    later = offsets[lo + 1] - 1 - np.arange(len(lo))
    tri = np.zeros(n, np.float64)               # exact under 2^53
    ends = np.cumsum(later)
    at = 0
    while at < len(lo):
        stop = max(int(np.searchsorted(
            ends, (ends[at - 1] if at else 0) + _CHUNK, side="right")),
            at + 1)
        cnt = later[at:stop]
        first = np.repeat(np.arange(at, stop), cnt)
        second = first + 1 + (np.arange(len(first))
                              - np.repeat(np.cumsum(cnt) - cnt, cnt))
        q = hi[first] * n + hi[second]          # does w -> x close it
        third = np.minimum(np.searchsorted(key, q), len(key) - 1)
        hit = key[third] == q
        first, second, third = first[hit], second[hit], third[hit]
        # each corner is handed the edges of the side opposite to it
        tri += np.bincount(lo[first], both[third], n)
        tri += np.bincount(hi[first], both[second], n)
        tri += np.bincount(hi[second], both[first], n)
        at = stop
    return deg, tri.astype(np.int64)[rank]


def coefficients(deg, tri):
    """float64 ``tri / (deg * (deg - 1))``, 0 where ``deg <= 1``."""
    pairs = deg * (deg - 1)
    return np.where(pairs > 0, tri / np.maximum(pairs, 1), 0.0)


def checksum(vids, values) -> int:
    """sum((vid * value) mod P61) mod P61 in Python's integers."""
    total = 0
    for v, x in zip(vids.tolist(), values.tolist()):
        total = (total + v * x % P61) % P61
    return int(total)


def summary(deg, tri, vm, lead: int = 10) -> dict:
    """Counts in the shape of the served row, over the alive vertices:
    the sums, the Watts-Strogatz average and the largest coefficient,
    the ``lead`` vertices of most ``tri`` as ``[vid, tri, deg]`` (the
    smaller id first among equals), and the two checksums."""
    alive = np.flatnonzero(vm)
    d, t = deg[alive], tri[alive]
    lcc = coefficients(d, t)
    order = np.lexsort((alive, -t))[:lead]
    return {"vertices": int(len(alive)),
            "edges_among_neighbours": int(t.sum()),
            "neighbour_pairs": int((d * (d - 1)).sum()),
            "lcc_mean": float(lcc.mean()) if len(alive) else 0.0,
            "lcc_max": float(lcc.max()) if len(alive) else 0.0,
            "top10": [[int(alive[i]), int(t[i]), int(d[i])] for i in order],
            "tri_checksum": checksum(alive, t),
            "deg_checksum": checksum(alive, d)}


def served_like(deg, tri, vm, steps: int = 1) -> dict:
    """A row as the program serves it — how the control is put in the
    program's place."""
    return {"steps": int(steps), "result": summary(deg, tri, vm)}


def reference(vm, src, dst, alg: dict) -> dict:
    return summary(*counts(vm, src, dst), vm)


def control(vm, src, dst, alg: dict) -> dict:
    """The nearest wrong computation: the triangles of the undirected
    graph, each closing edge counted once whatever its directions."""
    return served_like(*counts(vm, src, dst, "undirected"), vm)


def stated(vm, src, dst, alg: dict) -> dict:
    """Counts are integers: there is no lower precision to state, and
    the stated computation is the reference."""
    return served_like(*counts(vm, src, dst), vm)


#: integers, compared exactly; and the two floats
COMPARED = ("vertices_err", "edges_among_neighbours_err",
            "neighbour_pairs_err", "top10_mismatched",
            "tri_checksum_mismatch", "deg_checksum_mismatch",
            "lcc_mean_rel_err", "lcc_max_rel_err")


def compare(row: dict, want: dict, limits: dict, alg: dict) -> dict:
    """A served row (``steps`` and ``result``) against ``reference``'s
    answer. The integers are exact, so their limits are 0:

    - ``vertices_err``, ``edges_among_neighbours_err``,
      ``neighbour_pairs_err``: |served - reference| (a part of the graph
      left out; a direction or a neighbour counted twice);
    - ``top10_mismatched``: positions of the ten vertices of most
      ``tri`` whose ``[vid, tri, deg]`` differs, a shorter or longer
      list counted by its missing places;
    - ``tri_checksum_mismatch`` / ``deg_checksum_mismatch``: 1 unless
      the checksum is equal (one wrong count anywhere);
    - ``lcc_mean_rel_err`` / ``lcc_max_rel_err``: relative (the program
      divides in float32 and averages in float64);
    - ``steps``: one pass, exactly the configuration's iterations."""
    got = row["result"]
    served = [[int(x) for x in r] for r in got.get("top10", [])]
    out = {"steps": row["steps"]}
    for key in ("vertices", "edges_among_neighbours", "neighbour_pairs"):
        out[key + "_err"] = abs(int(got[key]) - want[key])
    out["top10_mismatched"] = abs(len(served) - len(want["top10"])) + sum(
        1 for a, b in zip(served, want["top10"]) if a != b)
    for key in ("tri", "deg"):
        out[key + "_checksum_mismatch"] = int(
            int(got[key + "_checksum"]) != want[key + "_checksum"])
    for key in ("lcc_mean", "lcc_max"):
        out[key + "_rel_err"] = abs(float(got[key]) - want[key]) \
            / max(abs(want[key]), 1e-30)
    out["ok"] = out["steps"] == alg["iterations"] and all(
        out[k] <= limits[k] for k in COMPARED)
    return out


def least_bytes(columns, alg: dict) -> int:
    """Least HBM bytes of one dispatch over ``columns``, the (alive
    vertices, alive pairs) of each (hop, window) view it serves. A LEAST
    count — what no implementation could avoid — so a share of the
    roofline computed from it cannot pass 100 %: the int32 (src, dst) of
    the alive pairs read once (one table as large as the largest
    column's), a mask byte per pair and column, and ``tri`` and ``deg``
    (two int32) written per alive vertex and column. The triangle rows
    are the implementation's own and are not counted."""
    widest = max(m for _, m in columns)
    return 8 * widest + len(columns) * widest + sum(8 * n for n, _ in columns)
