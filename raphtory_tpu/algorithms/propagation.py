"""SGC's feature propagation — the program whose state is a row of
features a vertex, not a scalar.

SGC (Wu, Souza, Zhang, Fifty, Yu, Weinberger, "Simplifying Graph
Convolutional Networks", ICML 2019) takes the nonlinearities out of a
graph convolutional network: what is left of the graph is the
parameter-free ``Y = S^K X``, ``S = D~^-1/2 A~ D~^-1/2``, ``A~ = A + I``,
computed once before a logistic regression. On a temporal log, for a view
(T, w) with alive vertices V and alive directed pairs E (repeated events
of a pair are one pair; a self-loop is a pair (v, v)) and ``A[u, v] = 1``
where ``(u -> v)`` is in E:

    A~   = A + A^T + I                  (a pair joined both ways weighs 2)
    d~_v = 1 + out_deg(v) + in_deg(v)   (A~_vv = 3 where v has a self-loop)
    g_v  = h_v / sqrt(d~_v)
    h'_v = (g_v + sum over (u -> v) of g_u + sum over (v -> u) of g_u)
           / sqrt(d~_v)

``rounds`` times from ``h = X``. No weight matrix, no nonlinearity, no row
normalisation. **X** takes the place of weights and is a function of the
GLOBAL vertex id (``ops/propagate.features``: murmur3's finaliser over
``vid * dim + j``), so no layout or engine changes it; rows of vertices
that are not alive are 0.

Two engines run it. ``bsp`` runs it as written below (``sum`` along
``both`` directions, state ``[n, dim]``): the library's surface and the
tests' second opinion — it gathers ``[windows x pairs, dim]``, 27 GB at
the size the benchmark serves. The job layer serves it on the columnar
route alone (``columnar_only``: ``engine/hopbatch.HopBatchedSGC``,
``ops/propagate.py``, which never holds ``[pairs, dim]``), as a Range and,
one column, as a View. Either engine ends in ``ops/propagate.summarise``
on the device: nothing of size ``n x dim`` crosses to the host.
"""

from __future__ import annotations

from dataclasses import dataclass

import jax.numpy as jnp
import numpy as np

from ..engine.program import Context, Edges, VertexProgram
from ..ops import propagate


def _scale(ctx: Context):
    """``(1 / sqrt(d~) [n, 1], d~ [n])`` under the view's mask, both 0
    for a vertex that is not alive."""
    deg = jnp.where(ctx.v_mask, 1 + ctx.out_deg + ctx.in_deg, 0)
    return propagate.inv_sqrt_degree(deg), deg


@dataclass(frozen=True)
class SGC(VertexProgram):
    """``Y = S^rounds X`` over ``dim`` hashed features a vertex; served as
    its column sums, its norm and the rows of the ten vertices of largest
    ``d~``."""

    rounds: int = 2
    dim: int = 602
    feature_seed: int = 0
    combiner = "sum"
    direction = "both"           # A and A^T; the vertex's own term is I
    columnar_only = True
    reduce_shell_safe = True     # reduce reads vids only
    needs_vertex_times = False
    needs_edge_times = False

    @property
    def max_steps(self) -> int:
        return self.rounds

    def init(self, ctx: Context):
        s, _ = _scale(ctx)
        h = jnp.where(ctx.v_mask[:, None], propagate.features(
            ctx.vids, self.dim, self.feature_seed), 0.0)
        return {"h": h, "g": h * s}

    def message(self, src_state, edge: Edges):
        return src_state["g"]

    def update(self, state, agg, ctx: Context):
        s, _ = _scale(ctx)
        h = (state["g"] + agg) * s
        # a fixed number of rounds: nobody votes to halt
        return {"h": h, "g": h * s}, jnp.zeros_like(ctx.v_mask)

    def finalize(self, state, ctx: Context):
        _, deg = _scale(ctx)
        return propagate.summarise(state["h"], deg, ctx.v_mask,
                                   jnp.sum(ctx.in_deg))

    def reduce(self, result, view, window=None):
        """The served row: alive ``vertices`` and ``edges`` (pairs),
        ``dim``, ``col_sum`` (the column sums of Y over V), ``frob``
        (its Frobenius norm), ``top10`` = the ten vertices of largest
        ``d~`` as ``[vid, d~, norm of y_v]`` (the smaller id first among
        equals: ids ascend with the dense index) and ``probe`` = those
        rows of Y. ``result`` is ``summarise``'s pytree of one column."""
        r = {k: np.asarray(v) for k, v in result.items()}
        held = r["top_deg"] > 0         # fewer than ten alive vertices
        vids = np.asarray(view.vids)[r["top_idx"][held]]
        probe = r["probe"][held].astype(np.float64)
        return {
            "vertices": int(r["vertices"]),
            "edges": int(r["edges"]),
            "dim": int(self.dim),
            "col_sum": r["col_sum"].astype(np.float64).tolist(),
            "frob": float(np.sqrt(r["col_sq"].astype(np.float64).sum())),
            "top10": [[int(v), int(d), float(np.sqrt((y * y).sum()))]
                      for v, d, y in zip(vids, r["top_deg"][held], probe)],
            "probe": probe.tolist(),
        }
