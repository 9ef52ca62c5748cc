"""Community detection by label propagation (CDLP) as LDBC Graphalytics
v1.0 specifies it (Iosup et al., VLDB 2016; after Raghavan et al. 2007),
from the specification's equations and nothing of the program:

    L_0(v) = v
    L_i(v) = min(argmax_l (|{u in N_in(v):  L_{i-1}(u) = l}|
                         + |{u in N_out(v): L_{i-1}(u) = l}|))

A vertex counts the labels of its in- AND out-neighbours in one
histogram (a neighbour joined both ways counts twice), takes the most
frequent, the smallest among equals; a vertex without neighbours keeps
its label; all vertices update at once; exactly ``iterations`` rounds.
Labels are vertex ids.

Departures from the specification, each also under ``assumed`` in the
configuration's file: the graph is the store's fold at T under a window
(the specification's graph is static); a self-loop counts as the
formula reads, once as v's own in-neighbour and once as its
out-neighbour; repeated events of a pair are one edge (the fold's graph
is simple). numpy only.
"""

from __future__ import annotations

import numpy as np

#: the served row's checksum is taken modulo this prime (2^61 - 1)
P61 = (1 << 61) - 1


def cdlp(vm, src, dst, iterations: int, directions: str = "both",
         tie: str = "smallest"):
    """Labels [n_ids] after ``iterations`` synchronous rounds (a dead
    vertex keeps its id and is never read). ``directions`` ``"in"``
    counts in-neighbours only and ``tie`` ``"largest"`` breaks ties to
    the larger label: the two wrong computations the tests and the
    control hold ``compare`` against."""
    n_ids = len(vm)
    lab = np.arange(n_ids, dtype=np.int64)
    src, dst = np.asarray(src, np.int64), np.asarray(dst, np.int64)
    if directions == "both":
        recv, send = np.concatenate([dst, src]), np.concatenate([src, dst])
    else:
        recv, send = dst, src
    sign = 1 if tie == "smallest" else -1
    for _ in range(int(iterations)):
        # the histogram: how often each (receiver, label) occurs
        pair, count = np.unique(recv * n_ids + lab[send], return_counts=True)
        r, l = pair // n_ids, pair % n_ids
        # per receiver: the largest count first, then the tie rule
        order = np.lexsort((sign * l, -count, r))
        r, l = r[order], l[order]
        lead = np.concatenate([[True], r[1:] != r[:-1]])
        new = lab.copy()
        new[r[lead]] = l[lead]
        lab = new
    return lab


def summary(lab, vm, lead: int = 10) -> dict:
    """Labels in the shape of the served row: alive vertices,
    communities, the largest, the ``lead`` largest as (label, size) —
    larger first, the smaller label first among equals — and the
    checksum sum((v * L(v)) mod P61) mod P61 over alive vertices."""
    alive = np.flatnonzero(vm)
    labels, sizes = np.unique(lab[alive], return_counts=True)
    order = np.lexsort((labels, -sizes))[:lead]
    check = 0
    for v, l in zip(alive.tolist(), lab[alive].tolist()):
        check = (check + v * l % P61) % P61
    return {"vertices": int(len(alive)), "communities": int(len(labels)),
            "biggest": int(sizes.max()) if len(sizes) else 0,
            "top10": [[int(labels[i]), int(sizes[i])] for i in order],
            "label_checksum": int(check)}


def served_like(lab, vm, steps: int) -> dict:
    """A row as the program serves it — how the control is put in the
    program's place."""
    return {"steps": int(steps), "result": summary(lab, vm)}


def reference(vm, src, dst, alg: dict) -> dict:
    return summary(cdlp(vm, src, dst, alg["iterations"]), vm)


def control(vm, src, dst, alg: dict) -> dict:
    """The nearest wrong computation the repo itself holds: the same
    rounds over in-neighbours only (``LabelPropagation``'s histogram)."""
    return served_like(cdlp(vm, src, dst, alg["iterations"], "in"), vm,
                       alg["iterations"])


def stated(vm, src, dst, alg: dict) -> dict:
    """Labels are integers: there is no lower precision to state, and
    the stated computation is the reference."""
    return served_like(cdlp(vm, src, dst, alg["iterations"]), vm,
                       alg["iterations"])


COMPARED = ("vertices_err", "communities_err", "biggest_err",
            "top10_mismatched", "checksum_mismatch")


def compare(row: dict, want: dict, limits: dict, alg: dict) -> dict:
    """A served row (``steps`` and ``result`` = ``{vertices, communities,
    biggest, top10, label_checksum}``) against ``reference``'s answer.
    Exact — labels are integers and the specification validates by exact
    match, so every limit is 0:

    - ``vertices_err``, ``communities_err``, ``biggest_err``: |served -
      reference| (a part of the graph left out; a merge or split);
    - ``top10_mismatched``: positions of the ten largest communities
      whose (label, size) pair differs, a shorter or longer list counted
      by its missing places (a size off by one; a wrong tie rule);
    - ``checksum_mismatch``: 1 unless ``label_checksum`` is equal (one
      wrong label anywhere);
    - ``steps``: the rounds the row took; exactly the configuration's
      iterations (a route that halts early is another computation)."""
    got = row["result"]
    served = [[int(l), int(s)] for l, s in got.get("top10", [])]
    out = {"steps": row["steps"]}
    for key in ("vertices", "communities", "biggest"):
        out[key + "_err"] = abs(int(got[key]) - want[key])
    out["top10_mismatched"] = abs(len(served) - len(want["top10"])) + sum(
        1 for a, b in zip(served, want["top10"]) if a != b)
    out["checksum_mismatch"] = int(
        int(got["label_checksum"]) != want["label_checksum"])
    out["ok"] = out["steps"] == alg["iterations"] and all(
        out[k] <= limits[k] for k in COMPARED)
    return out


def least_bytes(columns, alg: dict) -> int:
    """Least HBM bytes of one dispatch: ``iterations`` rounds over
    ``columns``, the (alive vertices, alive pairs) of each (hop, window)
    view it serves. A LEAST count — what no implementation could avoid —
    so a share of the roofline computed from it cannot pass 100 %. Every
    round reads and writes one int32 label per alive vertex and column
    (a perfect cache would serve both per-edge gathers of neighbour
    labels from that one read, and hold the histogram), and reads the
    int32 (src, dst) of the alive pairs: either each column's own, or
    one table as large as the largest column's once for all columns plus
    a mask byte per pair and column — whichever is less."""
    labels = sum(8 * n for n, _ in columns)
    widest = max(m for _, m in columns)
    edges = min(sum(8 * m for _, m in columns),
                8 * widest + len(columns) * widest)
    return int(alg["iterations"]) * (labels + edges)
