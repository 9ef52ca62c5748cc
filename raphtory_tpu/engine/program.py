"""The vertex-program contract — TPU-native ``Analyser`` equivalent.

The reference's user algorithm contract is the ``Analyser`` trait
(``core/analysis/API/Analyser.scala:30-63``): ``setup()``, ``analyse()`` (one
superstep of per-vertex code sending point-to-point messages), result
reducers, ``defineMaxSteps()``. Here an algorithm is a frozen dataclass of
pure array functions over the WHOLE vertex/edge set at once:

    init(ctx)                  -> state pytree          (Analyser.setup)
    message(src_state, edge)   -> payload pytree        (messageNeighbour)
    update(state, agg, ctx)    -> (state, halt_votes)   (Analyser.analyse + voteToHalt)
    finalize(state, ctx)       -> result pytree         (returnResults)

Being a frozen dataclass makes the program hashable, so the engine passes it
to jit as a static argument: one compiled superstep program per
(algorithm, hyperparams, padded shapes) — reused across every hop of a range
sweep (the reference re-runs the whole actor handshake per hop,
``RangeAnalysisTask.scala:18-35``).

Messages always flow along edges; ``direction`` picks out-edges ('out':
src→dst), in-edges ('in': dst→src), or 'both'. Aggregation at the receiver is
an associative-commutative ``combiner`` ('sum' | 'min' | 'max') — the
narrowing of the reference's arbitrary typed messages that makes vertex
messaging a segment reduction (SURVEY.md §2.9) — OR ``'custom'``: the
program's ``exchange`` hook receives the raw flat payloads with their
destination segment ids and reduces them itself (sort-based routing — see
``ops.segment.segment_mode``), recovering inbox-style algorithms (label
histograms, majority votes) the elementwise combiners cannot express
(``VertexVisitor.scala:99-161`` generality).
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Any

import jax
import jax.numpy as jnp


@dataclass(frozen=True)
class Edges:
    """Per-edge arrays visible to ``message`` (masked rows are neutralised by
    the engine). ``time``/``first_time`` are the latest/earliest history
    points — the temporal columns that power time-aware algorithms.

    ``src``/``dst`` are GLOBAL padded vertex indices in every engine (on a
    single device global == local). Programs may compare them (e.g. drop
    self-loops) but must not index local per-shard arrays with them."""

    src: jnp.ndarray          # i32[m] global padded source index
    dst: jnp.ndarray          # i32[m] global padded destination index
    mask: jnp.ndarray         # bool[m] (already window-restricted)
    time: jnp.ndarray         # i64[m] latest activity <= T (occurrence time
                              #        for needs_occurrences programs)
    first_time: jnp.ndarray   # i64[m]
    props: dict[str, jnp.ndarray]   # f32[m] per requested key
    step: jnp.ndarray = 0     # i32 scalar: current superstep (for
                              # counter-based randomness etc.)


@dataclass(frozen=True)
class Context:
    """Per-superstep global context visible to ``init``/``update``/``finalize``.

    The analogue of the reference's injected ``sysSetup(context, managerCount,
    proxy: GraphLens, workerID)`` (``Analyser.scala:37-42``) — but the "lens"
    is just arrays.
    """

    n: int                    # LOCAL padded vertex count (static; = global on 1 device)
    time: jnp.ndarray         # i64 scalar: view timestamp
    window: jnp.ndarray       # i64 scalar: window size (-1 = none)
    v_mask: jnp.ndarray       # bool[n] in-view/in-window vertices (local rows)
    vids: jnp.ndarray         # i64[n] global ids (-1 pad)
    v_latest_time: jnp.ndarray
    v_first_time: jnp.ndarray
    out_deg: jnp.ndarray      # i32[n] under current mask
    in_deg: jnp.ndarray       # i32[n]
    n_active: jnp.ndarray     # i32 scalar: GLOBAL active vertex count
    step: jnp.ndarray         # i32 scalar: current superstep
    vprops: dict[str, jnp.ndarray]
    # Sharding context. On a sharded mesh, a program sees only its device's
    # rows; `v_offset` is the global index of local row 0 and `axis_name` the
    # mesh axis for cross-shard reductions. Programs that need global scalars
    # (e.g. PageRank's dangling mass) MUST use ctx.global_sum — on one device
    # it degrades to a plain jnp.sum.
    v_offset: jnp.ndarray = 0      # i32 scalar
    axis_name: str | None = None   # static

    @property
    def num_vertices(self) -> jnp.ndarray:
        """GLOBAL active vertex count as f32 (PageRank-style normalisers)."""
        return self.n_active.astype(jnp.float32)

    def global_sum(self, x: jnp.ndarray) -> jnp.ndarray:
        s = jnp.sum(x)
        if self.axis_name is not None:
            s = jax.lax.psum(s, self.axis_name)
        return s

    def global_max(self, x: jnp.ndarray) -> jnp.ndarray:
        s = jnp.max(x)
        if self.axis_name is not None:
            s = jax.lax.pmax(s, self.axis_name)
        return s

    def global_index(self) -> jnp.ndarray:
        """i32[n]: global padded index of each local row (CC labels etc.)."""
        return jnp.asarray(self.v_offset, jnp.int32) + jnp.arange(self.n, dtype=jnp.int32)


class VertexProgram:
    """Base class; subclass as @dataclass(frozen=True) with hyperparams as
    fields. Class attributes configure the engine."""

    combiner: str = "sum"
    direction: str = "out"          # 'out' | 'in' | 'both'
    max_steps: int = 20
    edge_props: tuple[str, ...] = ()
    vertex_props: tuple[str, ...] = ()
    needs_occurrences: bool = False  # multigraph temporal algorithms
    # Array-requirement declarations. Defaults are conservative (everything
    # ships to the device); a program that never reads ctx.vids /
    # ctx.v_{latest,first}_time / edge.{time,first_time} on device should
    # set the matching flag False — the engine then skips staging and
    # transferring those arrays entirely (a large share of per-hop H2D bytes
    # in range sweeps). With a flag False the corresponding ctx/edge fields
    # hold pad defaults (-1 / INT64_MIN) on device.
    needs_vids: bool = True
    needs_vertex_times: bool = True
    needs_edge_times: bool = True
    # True when the program's overridden ``reduce`` reads only the
    # vertex-side view fields (vids / v_mask / v_latest_time /
    # window_masks()[0]) — the amortised sweep engines hand reducers a
    # lightweight shell without edge masks or property joins. Programs whose
    # reducers touch edges or properties keep the default False and run on
    # the full per-view path. (A non-overridden reduce is pass-through and
    # always safe.)
    reduce_shell_safe: bool = False
    # Monotone min-merge declaration — the eligibility gate for the sparse
    # frontier comm route (``parallel/frontier.py``). True asserts ALL of:
    #   * ``combiner == "min"`` and state is a SINGLE array leaf;
    #   * ``update(state, agg, ctx)`` is elementwise
    #     ``where(v_mask, min(state, agg), pad)`` for a fixed pad constant
    #     equal to the min-identity of the state dtype — so merging
    #     per-owner partial updates elementwise-min reproduces the dense
    #     result bitwise, and a no-message superstep is a fixed point;
    #   * halt votes are exactly ``new == state`` (quiescence == no change);
    #   * ``init``/``update``/``finalize`` never read ``ctx.out_deg`` /
    #     ``ctx.in_deg`` (the sparse route computes degrees from the local
    #     edge subset only — see docs/COMM.md "monotone-min contract").
    # ConnectedComponents and SSSP/BFS satisfy this; PageRank-style dense
    # fixpoints must keep the default False.
    monotone_min: bool = False
    # combiner='custom' with direction='both': True declares that
    # ``exchange`` reduces the MULTISET of the payloads a vertex receives,
    # whichever way each came along (a label histogram), so the engines
    # hand it the out- and in-payloads concatenated, in ONE call
    # (``custom_exchange``). False: the pair is refused — two custom
    # aggregates have no merge.
    exchange_joint: bool = False
    # combiner='custom': True declares that ``exchange`` is
    # ``ops.segment.segment_mode`` of the payload and takes its
    # ``counts=`` — the rows of every segment, a function of the segment
    # ids alone, which the engines compute ONCE a dispatch, outside the
    # superstep loop, and hand to every round's ``exchange``
    # (``custom_exchange``). A route that counts the rows it hands to the
    # sort (the ledger's ``device.mode_rows``) reads the flag too. False:
    # ``exchange`` is called with its four arguments and no more.
    exchange_is_mode: bool = False
    # True for a program the job layer serves on the columnar engine or
    # fails by the route's name (``jobs/manager.Job._run_columnar_only``),
    # never through ``bsp``: an algorithm that is no message along an
    # edge and has no init / message / update
    # (``algorithms/clustering.LCC`` intersects neighbour sets), or one
    # whose state is a row of features a vertex
    # (``algorithms/propagation.SGC``: ``bsp`` runs it as a library call
    # at a small size and would gather ``[pairs, dim]`` at a served one).
    columnar_only: bool = False

    @property
    def cost_label(self) -> str:
        """Algorithm label the resource ledger files this program's cost
        under (``raphtory_query_cost_*{algorithm=...}`` metrics, /costz
        recent-query rows, kernel names in the registry). Class name by
        default; override when one class serves several user-facing
        algorithms."""
        return type(self).__name__

    # -- pure array functions --

    def init(self, ctx: Context) -> Any:
        raise NotImplementedError

    def message(self, src_state: Any, edge: Edges) -> Any:
        """Payload sent along each edge, computed from the SENDER's state.
        For direction='in' the "sender" is the edge's dst vertex; for 'both'
        it's called once per direction."""
        raise NotImplementedError

    def exchange(self, payload: Any, seg_ids: jnp.ndarray,
                 num_segments: int, mask: jnp.ndarray) -> Any:
        """combiner='custom' only: reduce the flat per-edge ``payload``
        pytree (leaves [m, ...]) into per-vertex aggregates (leaves
        [num_segments, ...]). ``seg_ids[m]`` is each payload's destination
        segment; rows with ``mask`` False must not contribute. Runs inside
        the compiled superstep on every engine (single-chip and mesh) —
        use static-shape segment ops (``segment_combine``, ``segment_mode``)
        only. Direction 'both' needs ``exchange_joint`` (merging two
        custom aggregations is not well-defined; one aggregation of both
        directions' payloads is).

        A program that declares ``exchange_is_mode`` takes one keyword
        more, ``counts=None``: ``i32[num_segments]``, how many of the
        ``m`` rows carry each segment id — EVERY row, masked rows and the
        padding rows at a block's end included, so it depends on
        ``seg_ids`` alone and not on the round. The engines compute it
        once a dispatch, before the superstep loop (off the sorted ids'
        plan where they hold one: no scatter over the rows), and the
        program passes it on to ``segment_mode(..., counts=counts)``,
        which would otherwise count the rows anew every round. A program
        without the flag is never handed it."""
        raise NotImplementedError

    def update(self, state: Any, agg: Any, ctx: Context):
        """Fold the combined inbox into new state; return (state, halt_votes)
        with halt_votes bool[n] True where the vertex votes to halt."""
        raise NotImplementedError

    def finalize(self, state: Any, ctx: Context) -> Any:
        return state

    # -- host-side reduction (Analyser.processResults analogue) --

    def reduce(self, result, view, window=None):
        """Turn device results into the job-level answer (host code).
        Default: pass through."""
        return result


def check_custom_direction(program: VertexProgram) -> None:
    """The one refusal every engine makes of a custom combine."""
    if (program.combiner == "custom" and program.direction == "both"
            and not program.exchange_joint):
        raise ValueError(
            "combiner='custom' requires direction 'out' or 'in' — merging "
            "two custom aggregations is not well-defined (a program whose "
            "exchange reduces both directions' payloads as one multiset "
            "declares exchange_joint)")


def custom_exchange(program: VertexProgram, parts, num_segments: int,
                    counts=None):
    """``program.exchange`` over ``parts`` = one ``(payload, seg_ids,
    mask)`` per direction the program listens on. Two directions
    (``exchange_joint``) are ONE exchange over both payloads together —
    a histogram of in- and out-neighbours' messages, not two aggregates
    to be merged. ``counts`` (``takes_mode_counts``) is the caller's,
    computed before its superstep loop; only a program that declares
    ``exchange_is_mode`` is handed it."""
    if len(parts) == 1:
        payload, ids, mask = parts[0]
    else:
        def cat(*xs):
            return jnp.concatenate(xs)

        payload = jax.tree_util.tree_map(cat, *(p[0] for p in parts))
        ids, mask = cat(*(p[1] for p in parts)), cat(*(p[2] for p in parts))
    if counts is None or not program.exchange_is_mode:
        return program.exchange(payload, ids, num_segments, mask)
    return program.exchange(payload, ids, num_segments, mask, counts=counts)


def takes_mode_counts(program: VertexProgram) -> bool:
    """Whether an engine computes ``counts`` for ``custom_exchange``
    before its superstep loop: one count of the rows per direction the
    program listens on, added — the ids of the concatenated payloads are
    the parts' ids one after another, so a segment's rows there are the
    sum of its rows in the parts."""
    return program.combiner == "custom" and program.exchange_is_mode
