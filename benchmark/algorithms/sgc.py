"""SGC's feature propagation (Wu, Souza, Zhang, Fifty, Yu, Weinberger,
"Simplifying Graph Convolutional Networks", ICML 2019), from the paper's
equations and nothing of the program: the whole graph part of the model
is the parameter-free

    Y = S^K X ,   S = D~^-1/2 A~ D~^-1/2 ,   A~ = A + I

computed once before a logistic regression. Here, for a view (T, w) with
alive vertices V and alive directed pairs E (the store's fold: repeated
events of a pair are one pair, a self-loop is a pair (v, v)), with
``A[u, v] = 1`` where ``(u -> v)`` is in E:

    A~   = A + A^T + I                  (a pair joined both ways weighs 2)
    d~_v = 1 + out_deg(v) + in_deg(v)   (the row sum of A~; A~_vv = 3
                                         where v has a self-loop)
    Y    = S^K X ,   K = 2

No weight matrix, no nonlinearity, no row normalisation. X takes the
place of weights and is a function of the GLOBAL vertex id, made from a
seed without a file (``features``): murmur3's 32-bit finaliser over
``vid * dim + j``, 24 bits of it as a float in [-1, 1), exact in
float32.

Departures from the paper, each also under ``assumed`` in the
configuration's file: the symmetrisation of a directed log without
coalescing, the graph as the store's fold at T under a window, hashed
features in place of a dataset's. numpy only, float64.

How it sums. Every term of A~ is a row ``(to, from)`` of one table —
the pairs, the pairs reversed, one row a vertex — so a round of one
feature column is a gather of the senders' values and one weighted
``np.bincount`` over the receivers (the whole ``[rows, F]`` gather would
be 37 GB at the cell's size), blocks of columns on a few threads. ``tests/test_sgc.py`` holds it to ``S^K X`` with dense numpy
matrices at a small size.
"""

from __future__ import annotations

import os
from concurrent.futures import ThreadPoolExecutor

import numpy as np

#: feature columns a thread takes at a time (no part of the answer)
_BLOCK = 16


def fmix32(h):
    """murmur3's 32-bit finaliser, uint32 -> uint32, wrapping."""
    h = np.asarray(h, np.uint32)
    h = h ^ (h >> np.uint32(16))
    h = h * np.uint32(0x85EBCA6B)
    h = h ^ (h >> np.uint32(13))
    h = h * np.uint32(0xC2B2AE35)
    return h ^ (h >> np.uint32(16))


def features(vids, dim: int, seed: int):
    """``X[v, j] = float32(fmix32(uint32(vid * dim + j) ^ uint32(seed))
    >> 8) * 2^-23 - 1`` as float32 ``[len(vids), dim]``: in [-1, 1), every
    value exact (24 bits)."""
    with np.errstate(over="ignore"):
        v = np.asarray(vids, np.int64).astype(np.uint32)
        key = v[:, None] * np.uint32(dim) + np.arange(dim, dtype=np.uint32)
        bits = fmix32(key ^ np.uint32(int(seed) & 0xFFFFFFFF))
    return (bits >> np.uint32(8)).astype(np.float32) \
        * np.float32(2.0 ** -23) - np.float32(1.0)


def table(vm, src, dst, *, transpose: bool = True, self_term: bool = True,
          coalesce: bool = False):
    """A~ of the view as rows over the alive vertices' ranks: ``(alive,
    to, frm, deg)`` — ``alive`` the alive global ids ascending, ``to`` /
    ``frm`` each row's receiver and sender, sorted by receiver, ``deg`` =
    d~, the rows a receiver has (its own among them). The keywords leave
    a term out or coalesce a pair joined both ways to weight 1: the
    wrong computations ``tests/benchmark/test_benchmark_sgc.py`` holds
    ``compare`` against."""
    alive = np.flatnonzero(vm)
    n = len(alive)
    rank = np.full(len(vm), -1, np.int64)
    rank[alive] = np.arange(n)
    s, d = rank[np.asarray(src, np.int64)], rank[np.asarray(dst, np.int64)]
    if n and len(s) and (s.min() < 0 or d.min() < 0):
        raise ValueError("an alive pair joins a vertex that is not alive")
    to, frm = [d], [s]                      # A: u -> v lands at v
    if transpose:
        to.append(s), frm.append(d)         # A^T
    to, frm = np.concatenate(to), np.concatenate(frm)
    if coalesce:
        key = np.unique(to * max(n, 1) + frm)
        to, frm = key // max(n, 1), key % max(n, 1)
    if self_term:
        own = np.arange(n, dtype=np.int64)
        to, frm = np.concatenate([to, own]), np.concatenate([frm, own])
    order = np.argsort(to, kind="stable")
    to, frm = to[order], frm[order]
    return alive, to, frm, np.bincount(to, minlength=n)


def propagate(vm, src, dst, alg: dict, *, rounds: int | None = None,
              store=np.float64, **terms):
    """``(alive, deg, Y)``: ``Y = S^K X`` over the alive vertices, float64
    ``[len(alive), dim]``. ``store`` is the type X and every round's H
    are kept in (float64: the reference; float32: the stated precision;
    ``ml_dtypes.bfloat16``: the control); sums accumulate in float64
    whatever it is. ``terms``: ``table``'s keywords."""
    p = alg["params"]
    dim, K = int(p["dim"]), int(p["rounds"] if rounds is None else rounds)
    alive, to, frm, deg = table(vm, src, dst, **terms)
    n = len(alive)
    scale = 1.0 / np.sqrt(np.maximum(deg, 1).astype(np.float64))

    def kept(x):
        return x.astype(store).astype(np.float64)

    X = features(alive, dim, int(p["feature_seed"]))

    def block(j0):
        # one gather buffer a block, not a fresh ``[rows]`` array a column
        # and round (3,600 of 125 MB a view at the cell's size): a
        # sandboxed machine counts freed pages until it hands them back,
        # more slowly than 13 threads free them
        out, sent = [], np.empty(len(frm), np.float64)
        for j in range(j0, min(j0 + _BLOCK, dim)):
            h = kept(X[:, j].astype(np.float64))
            for _ in range(K):
                np.take(h * scale, frm, out=sent, mode="clip")
                h = kept(np.bincount(to, weights=sent, minlength=n)
                         * scale)
            out.append(h)
        return np.stack(out, axis=1)

    with ThreadPoolExecutor(min(16, os.cpu_count() or 1)) as pool:
        cols = list(pool.map(block, range(0, dim, _BLOCK)))
    Y = np.concatenate(cols, axis=1) if cols else np.zeros((n, 0))
    return alive, deg, Y


def summary(alive, deg, Y, edges: int, lead: int = 10) -> dict:
    """The served row's shape: ``vertices``, ``edges`` (alive pairs),
    ``dim``, ``col_sum`` (the F column sums of Y over V), ``frob``
    (Frobenius norm of Y), ``top10`` = the ``lead`` vertices of largest
    d~ as ``[vid, d~, norm of y_v]`` (the smaller id first among equals:
    the hubs, whose rows sum the most terms) and ``probe`` = those rows
    of Y."""
    order = np.lexsort((alive, -deg))[:lead]
    return {"vertices": int(len(alive)), "edges": int(edges),
            "dim": int(Y.shape[1]),
            "col_sum": Y.sum(axis=0).tolist(),
            "frob": float(np.sqrt((Y * Y).sum())),
            "top10": [[int(alive[i]), int(deg[i]),
                       float(np.sqrt((Y[i] * Y[i]).sum()))] for i in order],
            "probe": Y[order].tolist()}


def served_like(alive, deg, Y, edges: int, steps: int) -> dict:
    """A row as the program serves it — how the control is put in the
    program's place."""
    return {"steps": int(steps), "result": summary(alive, deg, Y, edges)}


def reference(vm, src, dst, alg: dict) -> dict:
    return summary(*propagate(vm, src, dst, alg), len(src))


def control(vm, src, dst, alg: dict) -> dict:
    """The precision below the stated one: X and every round's H kept in
    bfloat16 (float32 features, float32 sums are stated)."""
    import ml_dtypes

    return served_like(*propagate(vm, src, dst, alg,
                                  store=ml_dtypes.bfloat16),
                       len(src), alg["params"]["rounds"])


def stated(vm, src, dst, alg: dict) -> dict:
    return served_like(*propagate(vm, src, dst, alg, store=np.float32),
                       len(src), alg["params"]["rounds"])


#: two counts and the hubs, compared exactly; and three floats
COMPARED = ("vertices_err", "edges_err", "top10_mismatched",
            "probe_rel_err", "col_sum_rel_err", "frob_rel_err")


def _rel(got, want) -> float:
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    if got.shape != want.shape:
        return float("inf")
    return float(np.sqrt(((got - want) ** 2).sum())
                 / max(np.sqrt((want ** 2).sum()), 1e-300))


def compare(row: dict, want: dict, limits: dict, alg: dict) -> dict:
    """A served row (``steps`` and ``result``) against ``reference``'s
    answer:

    - ``vertices_err``, ``edges_err``: |served - reference| (a part of
      the graph left out), limit 0;
    - ``top10_mismatched``: places of the ten vertices of largest d~
      whose ``[vid, d~]`` differs, a shorter or longer list counted by
      its missing places (a term of A~ left out or counted twice), limit
      0;
    - ``probe_rel_err``: the largest over the ten probe rows of
      ``||served - reference||_2 / ||reference||_2`` (the hubs' rows sum
      the most terms: lower precision, a wrong weight);
    - ``col_sum_rel_err``: the same over the F column sums;
    - ``frob_rel_err``: relative, of the Frobenius norm (all of Y);
    - ``steps``: exactly the configuration's iterations (K rounds)."""
    got = row["result"]
    served = [[int(r[0]), int(r[1])] for r in got.get("top10", [])]
    hubs = [[int(r[0]), int(r[1])] for r in want["top10"]]
    out = {"steps": row["steps"]}
    for key in ("vertices", "edges"):
        out[key + "_err"] = abs(int(got[key]) - want[key])
    out["top10_mismatched"] = abs(len(served) - len(hubs)) + sum(
        1 for a, b in zip(served, hubs) if a != b)
    probe = got.get("probe", [])
    out["probe_rel_err"] = float("inf") if len(probe) != len(
        want["probe"]) else max(
        (_rel(a, b) for a, b in zip(probe, want["probe"])), default=0.0)
    out["col_sum_rel_err"] = _rel(got["col_sum"], want["col_sum"])
    out["frob_rel_err"] = abs(float(got["frob"]) - want["frob"]) \
        / max(abs(want["frob"]), 1e-300)
    out["ok"] = out["steps"] == alg["iterations"] and all(
        out[k] <= limits[k] for k in COMPARED)
    return out


def least_bytes(columns, alg: dict) -> int:
    """Least HBM bytes of one dispatch over ``columns``, the (alive
    vertices, alive pairs) of each (hop, window) view it serves. A LEAST
    count — what no implementation could avoid — so a share of the
    roofline computed from it cannot pass 100 %: per view and round H
    read and written once for the alive vertices (``2 x 4F`` bytes a
    vertex: a perfect cache would serve every gather of a sender's row
    from that one read); the int32 (src, dst) of the alive pairs read
    once a dispatch (one table as large as the largest column's) and a
    mask byte a pair and column; the probe (ten rows), the column sums
    and the norm written a view. X costs nothing: it is a function of
    the id."""
    p = alg["params"]
    F, K = int(p["dim"]), int(p["rounds"])
    widest = max(m for _, m in columns)
    return (sum(K * 2 * 4 * F * n for n, _ in columns)
            + 8 * widest + len(columns) * widest
            + len(columns) * 4 * (11 * F + 1))
