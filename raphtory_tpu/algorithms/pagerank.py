"""PageRank as a pull-combine vertex program — the flagship benchmark kernel.

The reference ships a deprecated 10-step push PageRank
(``examples/random/depricated/PageRank.scala:21-45``). This is the proper
power-iteration formulation: each superstep every vertex pulls
``rank/out_deg`` along in-edges (sum combiner), applies damping with a
dangling-mass correction, and votes to halt when its rank moved less than
``tol``. f32 on device; windowed sweeps batch as a leading vmap axis.

The division is done once a vertex (``share``, formed in ``init`` and
``update``), not once an edge in ``message``: a superstep costs per edge row
touched, and every state leaf ``message`` reads is one more gather an edge
(docs/KERNELS.md). ``share`` is ``rank / max(out_deg, 1)`` exactly — the same
division of the same operands, so the ranks are bit-identical to dividing
per edge; a reciprocal multiply would not be.
"""

from __future__ import annotations

from dataclasses import dataclass

import jax.numpy as jnp

from ..engine.program import Context, Edges, VertexProgram


def _share(rank, ctx: Context):
    """What one out-edge of each vertex carries: rank / max(out_deg, 1)."""
    return rank / jnp.maximum(ctx.out_deg.astype(jnp.float32), 1.0)


@dataclass(frozen=True)
class PageRank(VertexProgram):
    damping: float = 0.85
    tol: float = 1e-6
    max_steps: int = 50
    combiner = "sum"
    direction = "out"   # payload flows src→dst, combined at dst = pull at dst
    reduce_shell_safe = True   # reducer reads vids/v_mask only
    needs_vids = False
    needs_vertex_times = False
    needs_edge_times = False

    def init(self, ctx: Context):
        n = jnp.maximum(ctx.num_vertices, 1.0)
        rank = jnp.where(ctx.v_mask, 1.0 / n, 0.0).astype(jnp.float32)
        return {"rank": rank, "share": _share(rank, ctx)}

    def message(self, src_state, edge: Edges):
        return src_state["share"]

    def update(self, state, agg, ctx: Context):
        n = jnp.maximum(ctx.num_vertices, 1.0)
        # dangling vertices redistribute their mass uniformly (global scalar —
        # a psum across shards when running on a mesh)
        dangling = ctx.global_sum(
            jnp.where(ctx.v_mask & (ctx.out_deg == 0), state["rank"], 0.0)
        )
        new = (1.0 - self.damping) / n + self.damping * (agg + dangling / n)
        new = jnp.where(ctx.v_mask, new, 0.0).astype(jnp.float32)
        votes = jnp.abs(new - state["rank"]) < self.tol
        return {"rank": new, "share": _share(new, ctx)}, votes

    def finalize(self, state, ctx: Context):
        return state["rank"]

    def reduce(self, result, view, window=None):
        import numpy as np

        ranks = np.asarray(result)
        order = np.argsort(ranks)[::-1][:10]
        return {
            "sum": float(ranks.sum()),
            "top10": [
                (int(view.vids[i]), float(ranks[i])) for i in order if ranks[i] > 0
            ],
        }
