"""The BSP superstep engine — one jit-compiled SPMD program per algorithm.

Replaces the reference's actor-driven superstep machinery: the
``AnalysisTask`` coordinator counting ``Ready``/``EndStep`` acks and probing
message quiescence (``AnalysisTask.scala:197-283``), ``ReaderWorker``
executing ``analyse()`` per shard (``ReaderWorker.scala:159-219``), and the
``VertexMutliQueue`` double-buffered mailboxes. In the compiled model the
barrier is implicit (it's one XLA program), quiescence/vote counting is a
reduction, and the message exchange is a gather + segment-combine.

Batched windows (``ReaderWorker.scala:180-187`` running the algorithm once
per window against a shrinking lens) become a leading window axis driven by
``jax.vmap`` — every window advances in the same compiled superstep, and
halted windows freeze via ``jnp.where``.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from ..core.snapshot import GraphView
from ..obs import ledger as _ledger
from ..obs.trace import TRACER, block_steps
from ..ops.gather import gather_pack, packed_elements, row_and_slot
from ..ops.segment import (segment_combine, segment_counts,
                           segment_counts_at, segment_ends_pos)
from .program import (Context, Edges, VertexProgram, check_custom_direction,
                      custom_exchange, takes_mode_counts)

_elem = {"sum": jnp.add, "min": jnp.minimum, "max": jnp.maximum}


def _merge_aggs(op: str, a, b):
    return jax.tree_util.tree_map(_elem[op], a, b)


def _unpack_bits(packed: jnp.ndarray, n: int) -> jnp.ndarray:
    """u8[k, n//8] (little bit order) → bool[k, n]. Window masks ship to the
    device bit-packed: on a host with few cores, H2D staging competes with
    the snapshot builds of a range sweep, so bytes on the wire matter."""
    bits = (packed[:, :, None] >> jnp.arange(8, dtype=packed.dtype)) & 1
    return bits.reshape(packed.shape[0], n).astype(bool)


def make_runner(program: VertexProgram, n: int, m: int, k: int):
    """The raw (unjitted) superstep program for given padded shapes — the
    jittable forward step of the framework; see also ``__graft_entry__``.

    The returned fn takes BIT-PACKED masks (u8[k, n//8] / u8[k, m//8],
    little bit order). Arrays a program opts out of (``needs_vids`` /
    ``needs_vertex_times`` / ``needs_edge_times`` False) may be passed as
    1-element dummies — the runner substitutes pad defaults on device, so
    the host never stages or transfers them."""
    core = make_mask_runner(program, n, m, k)

    def run(v_masks_p, e_masks_p, vids, v_latest, v_first,
            e_src, e_dst, e_latest, e_first,
            time, windows, eprops, vprops):
        return core(_unpack_bits(v_masks_p, n), _unpack_bits(e_masks_p, m),
                    vids, v_latest, v_first, e_src, e_dst, e_latest, e_first,
                    time, windows, eprops, vprops)

    return run


@functools.lru_cache(maxsize=256)
def state_pack(program: VertexProgram, n: int, k: int) -> int:
    """Vertices a row of the table a ``make_mask_runner(program, n, _, k)``
    program gathers its one-element state leaves from (``ops/gather``),
    read off the shapes ``program.init`` gives: 1 where it has none, and
    nothing packs. ``hop.compute`` and ``bsp.dispatch`` report it as
    ``gather_pack``."""
    def init(v_mask, i64, i32, t, c, vprops):
        return program.init(Context(
            n=n, time=t, window=t, v_mask=v_mask, vids=i64,
            v_latest_time=i64, v_first_time=i64, out_deg=i32, in_deg=i32,
            n_active=c, step=c, vprops=vprops))

    S = jax.ShapeDtypeStruct
    state = jax.eval_shape(
        init, S((n,), bool), S((n,), jnp.int64), S((n,), jnp.int32),
        S((), jnp.int64), S((), jnp.int32),
        {name: S((n,), jnp.float32) for name in program.vertex_props})
    # init's leaves are one window's: [n] here is [k, n] in the runner
    one = any(a.ndim == 1 for a in jax.tree_util.tree_leaves(state))
    return gather_pack(k * n, 1) if one else 1


def make_mask_runner(program: VertexProgram, n: int, m: int, k: int):
    """The superstep core over UNPACKED bool masks (v_masks[k,n],
    e_masks[k,m]) — shared by the bit-packed host path (``make_runner``) and
    the device-resident sweep engine (``device_sweep.py``), which computes
    the masks on device and so never packs.

    The window batch is evaluated as ONE FLAT graph of k*n vertices / k*m
    edges (per-window segment ids offset by kk*n) rather than vmapping the
    gather/segment-combine per window: one scatter instead of k batched
    scatters. This is also a deliberate dodge of a TPU backend miscompile
    observed with [vmapped scatter inside a while_loop whose condition
    depends on carried state] — with the flat layout the halt-early
    condition is safe (verified against host references in
    tests/test_engine_algorithms.py::
    test_pagerank_batched_windows_match_single)."""

    def run(v_masks, e_masks, vids, v_latest, v_first,
            e_src, e_dst, e_latest, e_first,
            time, windows, eprops, vprops):
        if not program.needs_vids:
            vids = jnp.full((n,), -1, jnp.int64)
        if not program.needs_vertex_times:
            v_latest = jnp.full((n,), jnp.iinfo(jnp.int64).min, jnp.int64)
            v_first = v_latest
        if not program.needs_edge_times:
            e_latest = jnp.full((m,), jnp.iinfo(jnp.int64).min, jnp.int64)
            e_first = e_latest

        # flat (window-major) edge space: ids offset by kk*n
        voffs = (jnp.arange(k, dtype=jnp.int32) * n)[:, None]
        flat_dst = (e_dst[None, :] + voffs).reshape(-1)   # [k*m]; dst-sorted
        flat_src = (e_src[None, :] + voffs).reshape(-1)   # per window block
        em_flat = e_masks.reshape(-1)

        def tile_e(a):
            return jnp.broadcast_to(a[None, :], (k,) + a.shape).reshape(
                (k * m,) + a.shape[1:])

        # a sum over the dst-sorted rows is a segmented scan and one gather
        # at the segments' last rows (ops/segment.sorted_segment_sum):
        # where those lie depends on flat_dst alone — once, before the
        # superstep loop, the way ``counts`` reaches ``segment_mode``
        ends, pos = segment_ends_pos(flat_dst, k * n)
        # and so ``counts`` does reach a mode exchange: flat_dst's off the
        # plan, flat_src's (not sorted) by ONE scatter a dispatch
        mode_counts = None
        if takes_mode_counts(program):
            by_part = []    # in ``step_all``'s order of the parts
            if program.direction in ("out", "both"):
                by_part.append(segment_counts_at(ends, pos))
            if program.direction in ("in", "both"):
                by_part.append(segment_counts(flat_src, k * n))
            mode_counts = sum(by_part)
        # a state leaf of one element a vertex is gathered P vertices a
        # row, never element by element (ops/gather): which row and slot a
        # pair reads depends on its id alone — once, here, like the plan
        P = state_pack(program, n, k)
        at_src = (flat_src, *row_and_slot(flat_src, P))
        at_dst = (flat_dst, *row_and_slot(flat_dst, P))

        def combine_flat(tree_flat, ids, sorted_):
            # No branch here may depend on the backend's name: the chip
            # must run the combine the CPU tests run.
            # the plan is flat_dst's: the sorted ids are never any other
            def leaf(x):
                out = segment_combine(x, ids, k * n, program.combiner,
                                      em_flat, indices_are_sorted=sorted_,
                                      ends=ends if sorted_ else None,
                                      pos=pos if sorted_ else None)
                return out.reshape((k, n) + x.shape[1:])
            return jax.tree_util.tree_map(leaf, tree_flat)

        # per-window degrees: one flat segment-sum over the masked edge set
        ones_flat = jnp.ones((k * m,), jnp.int32)
        in_deg = segment_combine(ones_flat, flat_dst, k * n, "sum",
                                 em_flat, True, ends=ends,
                                 pos=pos).reshape(k, n)
        out_deg = segment_combine(ones_flat, flat_src, k * n, "sum",
                                  em_flat, False).reshape(k, n)

        def mk_ctx(kk, step):
            return Context(
                n=n, time=time, window=windows[kk], v_mask=v_masks[kk],
                vids=vids, v_latest_time=v_latest, v_first_time=v_first,
                out_deg=out_deg[kk], in_deg=in_deg[kk],
                n_active=jnp.sum(v_masks[kk].astype(jnp.int32)),
                step=step, vprops=vprops,
            )

        def init_k(kk):
            return program.init(mk_ctx(kk, jnp.int32(0)))

        state0 = jax.vmap(init_k)(jnp.arange(k))

        def flat_edges(step):
            # Edges contract: src/dst are the per-window vertex indices
            # (programs compare them, e.g. self-loop drops) — NOT offset
            return Edges(src=tile_e(e_src), dst=tile_e(e_dst), mask=em_flat,
                         time=tile_e(e_latest), first_time=tile_e(e_first),
                         props=jax.tree_util.tree_map(tile_e, eprops),
                         step=step)

        def gather_flat(state, at):
            ids, row, slot = at

            def leaf(a):
                if a.ndim == 2 and P > 1:
                    return packed_elements(a.reshape(k * n), row, slot, P)
                return a.reshape((k * n,) + a.shape[2:])[ids]
            return jax.tree_util.tree_map(leaf, state)

        def step_all(st, step):
            ek = flat_edges(step)
            custom = program.combiner == "custom"
            agg, parts = None, []
            if program.direction in ("out", "both"):
                with jax.named_scope("combine.gather"):
                    payload = program.message(gather_flat(st, at_src), ek)
                if custom:
                    parts.append((payload, flat_dst, em_flat))
                else:
                    agg = combine_flat(payload, flat_dst, True)
            if program.direction in ("in", "both"):
                payload = program.message(gather_flat(st, at_dst), ek)
                if custom:
                    parts.append((payload, flat_src, em_flat))
                else:
                    agg_in = combine_flat(payload, flat_src, False)
                    agg = agg_in if agg is None else _merge_aggs(
                        program.combiner, agg, agg_in)
            if custom:
                agg = jax.tree_util.tree_map(
                    lambda a: a.reshape((k, n) + a.shape[1:]),
                    custom_exchange(program, parts, k * n, mode_counts))

            def upd_k(kk, stk, aggk):
                new, votes = program.update(stk, aggk, mk_ctx(kk, step))
                return new, jnp.all(votes | ~v_masks[kk])

            return jax.vmap(upd_k, in_axes=(0, 0, 0))(jnp.arange(k), st, agg)

        if program.max_steps > 0:
            def cond(carry):
                step, _, halted = carry
                return (step < program.max_steps) & ~jnp.all(halted)

            def body(carry):
                step, st, halted = carry
                new_st, new_halt = step_all(st, step)
                # freeze halted windows
                st = jax.tree_util.tree_map(
                    lambda old, new: jnp.where(
                        halted.reshape((k,) + (1,) * (new.ndim - 1)), old, new),
                    st, new_st)
                return step + 1, st, halted | new_halt

            steps, state, halted = jax.lax.while_loop(
                cond, body, (jnp.int32(0), state0, jnp.zeros((k,), bool)))
        else:
            steps, state = jnp.int32(0), state0

        def fin_k(kk, st):
            return program.finalize(st, mk_ctx(kk, steps))

        result = jax.vmap(fin_k, in_axes=(0, 0))(jnp.arange(k), state)
        return result, steps

    return run


@functools.lru_cache(maxsize=256)
def _compiled_runner(program: VertexProgram, n: int, m: int, k: int,
                     prop_keys: tuple, vprop_keys: tuple):
    """One compiled program per (algorithm instance, padded shapes, #windows).

    Range sweeps at the same bucketed shape hit this cache — the amortisation
    the reference never had (fresh handshake per hop,
    ``RangeAnalysisTask.scala:18-35``)."""
    return _ledger.instrument(
        f"bsp.superstep.{type(program).__name__}",
        jax.jit(make_runner(program, n, m, k)),
        traffic=_ledger.edge_traffic_model(m, k, n))


def _gather_props(view: GraphView, keys, kind: str):
    out = {}
    for name in keys:
        if kind == "occ":
            arr = view.occ_prop(name)  # per-occurrence (per-event) values
        elif kind == "e":
            arr = view.edge_prop(name)
        else:
            arr = view.vertex_prop(name)
        out[name] = jnp.asarray(arr, jnp.float32)
    return out


def run_async(
    program: VertexProgram,
    view: GraphView,
    *,
    window: int | None = None,
    windows=None,
):
    """Dispatch a vertex program against a view WITHOUT waiting for the
    device: returns (result, steps) as device arrays. Range sweeps use this
    to pipeline host snapshot builds with device compute — hop i+1's
    snapshot folds while hop i's supersteps run.

    window=None, windows=None → plain view ({View,Range}AnalysisTask).
    window=w                  → single window (Windowed*).
    windows=[w0 > w1 > ...]   → batched windows, one result per window
                                (BWindowed*; leading axis on the result).
    """
    batched = windows is not None
    check_custom_direction(program)
    if windows is not None and len(windows) == 0:
        raise ValueError("windows must be a non-empty list of window sizes")
    if windows is None:
        windows = [window if window is not None else -1]
    wlist = list(windows)
    k = len(wlist)

    # Occurrence-based temporal programs (EthereumTaintTracking-style) run
    # over the multigraph of edge-add events rather than deduped edges —
    # the analogue of iterating raw edge history via
    # ``getOutgoingNeighborsAfter`` (VertexVisitor.scala:33).
    if program.needs_occurrences:
        if view.occ_src is None:
            raise ValueError(
                "program needs occurrences: build the view with "
                "include_occurrences=True")
        e_src, e_dst = view.occ_src, view.occ_dst
        e_latest = e_first = view.occ_time
        e_base_mask = view.occ_mask  # dst-sorted, like the deduped edges
    else:
        e_src, e_dst = view.e_src, view.e_dst
        e_latest, e_first = view.e_latest_time, view.e_first_time
        e_base_mask = view.e_mask
    m_pad = len(e_src)

    v_masks = np.empty((k, view.n_pad), bool)
    e_masks = np.empty((k, m_pad), bool)
    for i, w in enumerate(wlist):
        if w is None or w < 0:
            v_masks[i] = view.v_mask
            e_masks[i] = e_base_mask
        else:
            vm, _ = view.window_masks([w])
            v_masks[i] = vm[0]
            e_masks[i] = e_base_mask & (e_latest >= view.time - w)

    runner = _compiled_runner(
        program, view.n_pad, m_pad, k,
        tuple(program.edge_props), tuple(program.vertex_props),
    )
    eprops = _gather_props(
        view, program.edge_props,
        "occ" if program.needs_occurrences else "e")
    vprops = _gather_props(view, program.vertex_props, "v")
    win_arr = jnp.asarray([(-1 if w is None else int(w)) for w in wlist], jnp.int64)

    dummy64 = jnp.zeros((1,), jnp.int64)
    with TRACER.span("bsp.dispatch", n=int(view.n_pad), m=int(m_pad),
                        windows=k, time=int(view.time),
                        program=type(program).__name__,
                        gather_pack=state_pack(program, view.n_pad, k)):
        result, steps = runner(
            jnp.asarray(np.packbits(v_masks, axis=1, bitorder="little")),
            jnp.asarray(np.packbits(e_masks, axis=1, bitorder="little")),
            jnp.asarray(view.vids) if program.needs_vids else dummy64,
            (jnp.asarray(view.v_latest_time)
             if program.needs_vertex_times else dummy64),
            (jnp.asarray(view.v_first_time)
             if program.needs_vertex_times else dummy64),
            jnp.asarray(e_src), jnp.asarray(e_dst),
            jnp.asarray(e_latest) if program.needs_edge_times else dummy64,
            jnp.asarray(e_first) if program.needs_edge_times else dummy64,
            jnp.asarray(view.time, jnp.int64), win_arr, eprops, vprops,
        )
    if not batched:
        result = jax.tree_util.tree_map(lambda a: a[0], result)
    return result, steps


def run(
    program: VertexProgram,
    view: GraphView,
    *,
    window: int | None = None,
    windows=None,
):
    """Blocking ``run_async``: waits for the device and returns
    (result, int steps)."""
    result, steps = run_async(program, view, window=window, windows=windows)
    _, steps = block_steps(lambda: (None, steps))
    return result, steps
