"""Label propagation (community detection) — the generic-inbox algorithm.

Synchronous LPA (Raghavan et al.): every vertex starts in its own community
and repeatedly adopts the MOST FREQUENT label among its in-neighbours (ties
break to the smallest label; a vertex with no in-neighbours keeps its label),
halting when no label changes. The per-vertex label histogram is exactly the
inbox-style aggregation the reference's arbitrary typed vertex messages allow
(``VertexVisitor.scala:99-161``) and an elementwise sum/min/max combiner
cannot express — here it rides the sort-based ``segment_mode`` routing path
through ``combiner='custom'``.

Labels are GLOBAL PADDED vertex indices (i32), mesh-consistent like
ConnectedComponents'.

``CDLP`` is LDBC Graphalytics' community detection (specification v1.0,
after the same paper): the histogram is over a vertex's in- AND
out-neighbours together — a neighbour joined both ways counts twice, a
self-loop counts as the formula reads, v as its own in- and
out-neighbour — and the algorithm runs exactly ``max_steps`` rounds
instead of halting at quiescence. It shares ``init``, ``message``,
``exchange`` and ``update``'s label rule with ``LabelPropagation``.
"""

from __future__ import annotations

from dataclasses import dataclass

import jax.numpy as jnp
import numpy as np

from ..engine.program import Context, Edges, VertexProgram
from ..ops.segment import segment_mode

_I32_MAX = np.int32(np.iinfo(np.int32).max)


@dataclass(frozen=True)
class LabelPropagation(VertexProgram):
    max_steps: int = 30
    combiner = "custom"
    direction = "out"            # labels flow src -> dst; histogram at dst
    needs_vids = False
    needs_vertex_times = False
    needs_edge_times = False
    #: ``exchange`` is ``segment_mode`` and takes its ``counts``: the
    #: engines compute them once a dispatch (``engine/program``), and a
    #: route that counts the rows it hands to the sort (the ledger's
    #: ``device.mode_rows``) reads this
    exchange_is_mode = True

    def init(self, ctx: Context):
        return jnp.where(ctx.v_mask, ctx.global_index(), _I32_MAX)

    def message(self, src_state, edge: Edges):
        return src_state

    def exchange(self, payload, seg_ids, num_segments, mask, counts=None):
        # mode of the inbox per destination; -1 marks "no messages"
        return segment_mode(payload, seg_ids, num_segments, mask, default=-1,
                            counts=counts)

    def update(self, state, agg, ctx: Context):
        new = jnp.where((agg >= 0) & ctx.v_mask, agg, state)
        new = jnp.where(ctx.v_mask, new, _I32_MAX)
        return new, new == state

    def reduce(self, result, view, window=None):
        """Community stats (same shape as ConnectedComponents.reduce)."""
        labels = np.asarray(result)
        if window is None:
            mask = np.asarray(view.v_mask)
        else:
            mask = view.window_masks([window])[0][0]
        lab = labels[mask]
        if len(lab) == 0:
            return {"vertices": 0, "communities": 0, "biggest": 0, "top5": []}
        uniq, counts = np.unique(lab, return_counts=True)
        counts.sort()
        return {
            "vertices": int(len(lab)),
            "communities": int(len(uniq)),
            "biggest": int(counts[-1]),
            "top5": counts[::-1][:5].tolist(),
        }


#: the served checksum is taken modulo this prime
_P61 = (1 << 61) - 1


def _label_checksum(vids: np.ndarray, labels: np.ndarray) -> int:
    """sum((vid * label) mod P) mod P, P = 2^61 - 1, over the alive
    vertices: one wrong label anywhere changes it. Ids under 2^31 (every
    product under 2^62) take the vectorised path, summed in two 32-bit
    halves so that no int64 sum overflows; wider ids go through Python's
    integers."""
    if len(vids) == 0:
        return 0
    if int(vids.min()) >= 0 and int(max(vids.max(), labels.max())) < 1 << 31:
        prod = (vids * labels) % _P61
        return ((int((prod >> 32).sum()) << 32)
                + int((prod & 0xFFFFFFFF).sum())) % _P61
    return sum(v * l % _P61
               for v, l in zip(vids.tolist(), labels.tolist())) % _P61


@dataclass(frozen=True)
class CDLP(LabelPropagation):
    """LDBC Graphalytics CDLP: ``L_i(v) = min(argmax_l(|{u in N_in(v):
    L_{i-1}(u) = l}| + |{u in N_out(v): L_{i-1}(u) = l}|))`` from
    ``L_0(v) = v``, all vertices at once, exactly ``max_steps`` rounds."""

    max_steps: int = 10
    direction = "both"           # one histogram of in- and out-neighbours
    exchange_joint = True
    reduce_shell_safe = True     # reduce reads vids / v_mask / windows

    def update(self, state, agg, ctx: Context):
        new, _ = super().update(state, agg, ctx)
        # a fixed number of rounds: nobody votes to halt
        return new, jnp.zeros_like(ctx.v_mask)

    def reduce(self, result, view, window=None):
        """The served row: alive ``vertices``, ``communities``, the
        ``biggest``, ``top10`` = the ten largest as ``[label, size]`` with
        the label as a VERTEX ID (larger first, the smaller label first
        among equals), and ``label_checksum``. Labels are ranks of the
        sorted ids (``view.vids``), so the smallest rank is the smallest
        id and the engines' tie rule is the specification's."""
        labels = np.asarray(result)
        if window is None:
            mask = np.asarray(view.v_mask)
        else:
            mask = view.window_masks([window])[0][0]
        vids = np.asarray(view.vids)
        lab = vids[labels[mask]]
        uniq, counts = np.unique(lab, return_counts=True)
        order = np.lexsort((uniq, -counts))[:10]
        return {
            "vertices": int(len(lab)),
            "communities": int(len(uniq)),
            "biggest": int(counts.max()) if len(counts) else 0,
            "top10": [[int(uniq[i]), int(counts[i])] for i in order],
            "label_checksum": _label_checksum(vids[mask], lab),
        }
