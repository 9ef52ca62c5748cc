"""PageRank as LDBC Graphalytics specifies it: damping 0.85, uniform
dangling redistribution, a fixed number of iterations from the uniform
start, in float64. Copied from ``chip_smoke.py`` (PR 22) and cut to what
the cells serve.
"""

from __future__ import annotations

import numpy as np


def pagerank(vm, src, dst, iterations: int, damping: float = 0.85,
             store=np.float64):
    """``iterations`` damped power iterations from the uniform start with
    uniform dangling redistribution. ``store`` is the type the rank
    vector is kept in between iterations: float64 is the reference;
    ``ml_dtypes.bfloat16`` is the lower-precision control (sums still
    accumulate in float64, so the control errs on the small side)."""
    n_ids = len(vm)
    n = max(int(vm.sum()), 1)
    out_deg = np.bincount(src, minlength=n_ids).astype(np.float64)
    inv = 1.0 / np.maximum(out_deg, 1.0)
    dangling_v = vm & (out_deg == 0)

    def kept(x):
        return x.astype(store).astype(np.float64)

    r = kept(np.where(vm, 1.0 / n, 0.0))
    for _ in range(int(iterations)):
        agg = np.bincount(dst, weights=(r * inv)[src], minlength=n_ids)
        r = kept(np.where(
            vm, (1.0 - damping) / n
            + damping * (agg + r[dangling_v].sum() / n), 0.0))
    return r


def summary(r, lead: int = 64) -> dict:
    """A rank vector in the shape the served rows have, plus room: rank
    mass, live-vertex count, the ``lead`` leading (vertex, rank) pairs."""
    top = np.argsort(r)[::-1][:lead]
    return {"sum": float(r.sum()), "positive": int((r > 0).sum()),
            "lead": [(int(v), float(r[v])) for v in top if r[v] > 0]}


def served_like(r, alg: dict) -> dict:
    """A row as the program serves it — how the control is put in the
    program's place."""
    s = summary(r, 10)
    return {"steps": alg["iterations"],
            "result": {"sum": s["sum"], "top10": s["lead"]}}


def reference(vm, src, dst, alg: dict) -> dict:
    return summary(pagerank(vm, src, dst, alg["iterations"], alg["damping"]))


def control(vm, src, dst, alg: dict) -> dict:
    import ml_dtypes

    return served_like(pagerank(vm, src, dst, alg["iterations"],
                                alg["damping"], store=ml_dtypes.bfloat16),
                       alg)


def stated(vm, src, dst, alg: dict) -> dict:
    return served_like(pagerank(vm, src, dst, alg["iterations"],
                                alg["damping"], store=np.float32), alg)


def compare(row: dict, want: dict, limits: dict, alg: dict) -> dict:
    """A served row (``steps`` and ``result`` = ``{sum, top10}``) against
    ``reference``'s answer. Returns each number compared and ``ok``:

    - ``mass_err``: |served rank mass - reference's| (catches a part of
      the graph left out);
    - ``rank_rel_err``: the worst over the served top-10 of
      |served - reference| / reference (catches lower precision);
    - ``rows_missing``: served top-10 rows fewer than the reference has;
    - ``top10_misplaced``: served vertices the reference does not lead
      with, plus reference vertices clearly above the served 10th place
      that are not served (exact: limit 0);
    - ``steps``: the supersteps the row took; exactly the
      configuration's iterations (a route that halts early or
      warm-starts is another computation)."""
    got = row["result"]
    top, ref = got["top10"], dict(want["lead"])
    out = {"steps": row["steps"],
           "mass_err": abs(got["sum"] - want["sum"]),
           "rows_missing": abs(len(top) - min(10, want["positive"])),
           "rank_rel_err": 0.0, "top10_misplaced": 0}
    rel = limits["rank_rel_err"]
    for vid, rank in top:
        if int(vid) not in ref:
            out["top10_misplaced"] += 1
            continue
        want_r = ref[int(vid)]
        out["rank_rel_err"] = max(out["rank_rel_err"],
                                  abs(rank - want_r) / want_r)
    if top:
        served = {int(v) for v, _ in top}
        floor = min(rank for _, rank in top)
        out["top10_misplaced"] += sum(
            1 for v, r_v in want["lead"][:20]
            if v not in served and r_v > floor * (1.0 + 2.0 * rel))
    out["ok"] = out["steps"] == alg["iterations"] and all(
        out[k] <= limits[k] for k in
        ("mass_err", "rank_rel_err", "rows_missing", "top10_misplaced"))
    return out


def least_bytes(columns, alg: dict) -> int:
    """Least HBM bytes of one dispatch: ``iterations`` supersteps over
    ``columns``, the (alive vertices, alive pairs) of each (hop, window)
    view it serves. A LEAST count — what no implementation could avoid —
    so a share of the roofline computed from it cannot pass 100 %. Every
    superstep reads and writes one float32 rank per alive vertex and
    column (a perfect cache would serve the per-edge gather of source
    ranks from that one read), and reads the int32 (src, dst) of the
    alive pairs: either each column's own, or one table as large as the
    largest column's once for all columns plus a mask byte per pair and
    column — whichever is less."""
    ranks = sum(8 * n for n, _ in columns)
    widest = max(m for _, m in columns)
    edges = min(sum(8 * m for _, m in columns),
                8 * widest + len(columns) * widest)
    return int(alg["iterations"]) * (ranks + edges)
