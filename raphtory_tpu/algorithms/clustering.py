"""Local clustering coefficient — the algorithm that looks at two hops at
once.

LDBC Graphalytics' LCC (specification v1.0) on a directed graph: with
``N(v) = N_in(v) | N_out(v)`` (v itself excluded), ``LCC(v) = |{(u, w) :
u, w in N(v), (u, w) in E}| / (|N(v)| * (|N(v)| - 1))``, and 0 where
``|N(v)| <= 1``. The neighbourhood is undirected — a neighbour joined both
ways is one neighbour — and the edges counted among the neighbours are
directed: ``u -> w`` and ``w -> u`` are two. A self-loop is no neighbour
and no counted edge; repeated events of a pair are one edge.

This is not a message along an edge and a combiner at its end: a vertex
needs to know which of its neighbours are neighbours of each other. The
vertex-program contract (``engine/program.py``) cannot say that, and no
``bsp`` superstep computes it. ``LCC`` is served by the columnar engine
alone (``engine/hopbatch.HopBatchedLCC``: a view is a mask over the log's
triangle table, ``ops/triangles.py``), one pass, no rounds: a Range is
one dispatch of every (hop, window) column, a View the same engine at one
hop. ``columnar_only`` tells the job layer so.
"""

from __future__ import annotations

from dataclasses import dataclass

import numpy as np

from ..engine.program import VertexProgram
from .lpa import _label_checksum


@dataclass(frozen=True)
class LCC(VertexProgram):
    """Per alive vertex two integers — ``deg = |N(v)|`` and ``tri``, the
    directed pairs among its neighbours — and ``tri / (deg * (deg - 1))``
    in float32."""

    max_steps: int = 1
    columnar_only = True
    reduce_shell_safe = True     # reduce reads vids / v_mask / windows
    needs_vids = False
    needs_vertex_times = False
    needs_edge_times = False

    def reduce(self, result, view, window=None):
        """The served row over the alive vertices: ``vertices``,
        ``edges_among_neighbours`` (the sum of ``tri``) and
        ``neighbour_pairs`` (of ``deg * (deg - 1)``) in int64,
        ``lcc_mean`` (the Watts-Strogatz average: float32 coefficients,
        averaged in float64) and ``lcc_max``, ``top10`` = ``[vid, tri,
        deg]`` of the ten vertices of most ``tri`` (the smaller id first
        among equals), and ``tri_checksum`` / ``deg_checksum`` = the sum
        of ``(vid * count) mod (2^61 - 1)``: one wrong count anywhere
        changes them. ``result`` is the engine's ``[2, n_pad]``."""
        counts = np.asarray(result)
        if window is None:
            mask = np.asarray(view.v_mask)
        else:
            mask = view.window_masks([window])[0][0]
        vids = np.asarray(view.vids)[mask]
        tri = counts[0][mask].astype(np.int64)
        deg = counts[1][mask].astype(np.int64)
        pairs = deg * (deg - 1)
        lcc = np.where(pairs > 0, tri.astype(np.float32)
                       / np.maximum(pairs, 1).astype(np.float32),
                       np.float32(0.0))
        order = np.lexsort((vids, -tri))[:10]
        return {
            "vertices": int(len(vids)),
            "edges_among_neighbours": int(tri.sum()),
            "neighbour_pairs": int(pairs.sum()),
            "lcc_mean": float(lcc.mean(dtype=np.float64)) if len(vids)
            else 0.0,
            "lcc_max": float(lcc.max()) if len(vids) else 0.0,
            "top10": [[int(vids[i]), int(tri[i]), int(deg[i])]
                      for i in order],
            "tri_checksum": _label_checksum(vids, tri),
            "deg_checksum": _label_checksum(vids, deg),
        }
