"""Hop-batched columnar PageRank — the whole range sweep in one dispatch.

The per-hop engines (``bsp``, ``device_sweep``) pay the device's
per-element random-access rate once per (hop, window, iteration): scalar
ranks move 4 bytes per edge endpoint. This runner instead evaluates EVERY
(hop, window) view of a range sweep simultaneously as COLUMNS of one
program: the per-edge access becomes a C-wide row move (row-tile gathers
and row segment-sums are meant to run at bandwidth, not at the
per-element rate — not measured on the chip at HEAD), the per-iteration
dispatch overhead is paid once for the whole sweep, and the temporal
dimension is captured
up-front as per-hop fold-state COLUMNS (hop-major ``lat[j]`` /
``alive[j]`` rows of ``[H, m_pad]``/``[H, n_pad]`` arrays) built
incrementally by the host fold — deletes and revivals included, not an
add-only approximation.

This is the windowed-PageRank-specific engine behind the headline
benchmark; semantics match ``algorithms/pagerank.py`` exactly
(power iteration with dangling redistribution and tol-based halting) and
are tested column-against-``bsp.run`` per (hop, window).

Reference contrast: one compiled program per RANGE QUERY, where the
reference runs its full actor handshake once per hop
(``RangeAnalysisTask.scala:18-35``).
"""

from __future__ import annotations

import functools
import threading as _threading
import time as _time

import jax
import jax.numpy as jnp
import numpy as np

import logging

from ..core.events import EventLog
from ..core.sweep import (fold_cache, fold_pool, fold_workers, seeded_fork,
                          log_fingerprint, prefetch_map)
from ..obs import ledger as _ledger
from ..obs.trace import TRACER
from ..ops.gather import (gather_pack as _gather_pack, packed_rows,
                          row_and_slot)
from ..ops.segment import (segment_counts, segment_ends_pos, segment_mode,
                           sorted_segment_sum, sum_route)
from ..ops import propagate as _propagate
from ..ops.triangles import lcc_columns
from ..utils.transfer import _metrics
from .device_sweep import (GlobalTables, _device_edges, _device_features,
                           _device_triangles, log_index, log_triangles,
                           normalize_windows, sweep_phase_summary)

_log = logging.getLogger(__name__)


def _column_masks(tdt, e_lat, e_alive, v_lat, v_alive,
                  hop_of_col, T_col, w_col):
    """Per-column alive masks from the hop-major ``[H, ...]`` fold columns,
    transposed into the kernels' entity-major ``[..., C]`` layout — the ONE
    place the windowing test (``latest >= T - w``, ``w < 0`` = unwindowed)
    is written for all three compiled engines."""
    info = jnp.iinfo(tdt)
    lo = jnp.clip(T_col - w_col, info.min, info.max).astype(tdt)   # [C]
    nowin = w_col < 0
    me = (e_alive[hop_of_col] & (nowin[:, None]
                                 | (e_lat[hop_of_col] >= lo[:, None]))).T
    mv = (v_alive[hop_of_col] & (nowin[:, None]
                                 | (v_lat[hop_of_col] >= lo[:, None]))).T
    return me, mv


def _masks_from_deltas(tdt, H: int, W: int,
                       be_lat, be_alive, bv_lat, bv_alive,
                       de_pos, de_lat, de_alive,
                       dv_pos, dv_lat, dv_alive, T_col, w_col,
                       h0: bool = False):
    """Device-side fold-column rebuild: hop 0's full state plus per-hop
    touched-entity deltas (scatter-SET in hop order — delete-wins and
    revivals are already resolved by the host fold, so the delta VALUES
    are exact) replace the ``[H, m_pad]`` host-built columns. A sweep
    ships O(base + Σ delta) bytes instead of O(H · m_pad) — the term that
    made the host fold+transfer the binding cost of the headline sweep.
    ``h0=True`` additionally applies delta[0] BEFORE hop 0's column: the
    base args are then the previous dispatch's device-resident advanced
    state and delta[0] is the inter-batch catch-up, so a follow-on batch
    ships only deltas (the host→device term of a chunked sweep).
    Same windowing test as ``_column_masks``; pad rows carry a huge
    positive index and are dropped by the scatter. Returns the masks plus
    the ADVANCED base (state after the last hop) for the next dispatch."""
    info = jnp.iinfo(tdt)
    lo = jnp.clip(T_col - w_col, info.min, info.max).astype(tdt)   # [C]
    nowin = w_col < 0

    def build(b_lat, b_alive, d_pos, d_lat, d_alive):
        cur_l, cur_a, cols = b_lat, b_alive, []
        for h in range(H):     # H static and small: unrolled 1D scatters
            if h or h0:
                cur_l = cur_l.at[d_pos[h]].set(d_lat[h], mode="drop")
                cur_a = cur_a.at[d_pos[h]].set(d_alive[h], mode="drop")
            sl = slice(h * W, (h + 1) * W)
            cols.append(cur_a[:, None]
                        & (nowin[sl][None, :]
                           | (cur_l[:, None] >= lo[sl][None, :])))
        # [len, H*W] hop-major + the post-last-hop state
        return jnp.concatenate(cols, axis=1), cur_l, cur_a

    me, fe_lat, fe_alive = build(be_lat, be_alive, de_pos, de_lat, de_alive)
    mv, fv_lat, fv_alive = build(bv_lat, bv_alive, dv_pos, dv_lat, dv_alive)
    return me, mv, (fe_lat, fe_alive, fv_lat, fv_alive)


def _tile_budget_bytes() -> int:
    """Resolved ``RTPU_TILE_BUDGET_MB`` in bytes. Every columnar dispatcher
    resolves this ONCE per call and threads it into the lru_cached compiled
    factories, so the budget is part of the program cache key — changing
    the env var mid-process recompiles instead of silently reusing
    programs tiled for the old budget."""
    import os

    return int(os.environ.get("RTPU_TILE_BUDGET_MB", 256)) << 20


def _edge_tile_for(m_pad: int, C: int, budget_bytes: int) -> int | None:
    """Edge-tile length for the columnar kernels, or None for single-shot.

    The per-iteration payload ``[m_pad, C] f32`` is the scale limiter: at
    28M pairs x 128 columns it is ~14 GB — over a v5e's HBM — and the
    resulting spill is catastrophic. When the payload would exceed
    ``budget_bytes`` (``_tile_budget_bytes()``, resolved by every dispatch
    site so the knob lands in the program cache key), the edge dimension
    is processed as a
    ``lax.scan`` over equal tiles (plus one remainder slice, so no
    divisibility gymnastics) whose transient is ``tile * C * 4`` bytes."""
    if budget_bytes is None:
        # an env read here would happen at TRACE time, inside lru_cached
        # factories whose key would not carry the knob (rtpulint RT001) —
        # fail fast instead of silently caching programs tiled for a
        # budget the env var no longer holds
        raise ValueError(
            "tile budget unresolved — dispatch sites must pass "
            "_tile_budget_bytes() so RTPU_TILE_BUDGET_MB stays part of "
            "the compiled-program cache key")
    if m_pad * C * 4 <= budget_bytes or m_pad <= (1 << 16):
        return None
    step = 1 << 16
    target = max(step, budget_bytes // (C * 4))
    return min((target // step) * step, m_pad)


def _combine_route(m_pad: int, C: int, tile_budget: int) -> str:
    """How a PageRank dispatch of ``C`` columns over ``m_pad`` rows sums at
    the destination: ``scan`` (``ops/segment.sorted_segment_sum``) or
    ``scatter`` (the edge-tiled path and the wide dispatches) —
    ``hop.compute``'s ``combine``."""
    tiled = _edge_tile_for(m_pad, C, tile_budget) is not None
    return "scatter" if tiled else sum_route(C)


def _pagerank_pack(n_pad: int, m_pad: int, C: int, tile_budget: int) -> int:
    """Vertices a row of that dispatch's gather table (``ops/gather``; 1:
    the plain ``[n_pad, C]`` table, all the scatter's arms read) —
    ``hop.compute``'s ``gather_pack``."""
    if _combine_route(m_pad, C, tile_budget) != "scan":
        return 1
    return _gather_pack(n_pad, C)


def _pagerank_columns(me, mv, e_src, e_dst, n_pad: int, damping: float,
                      tol: float, max_steps: int, r_init=None,
                      tile_budget: int | None = None):
    """Power iteration over per-column masks ``me [m_pad, C]`` /
    ``mv [n_pad, C]`` — dangling redistribution, tol halting with
    converged-column freeze; semantics of ``algorithms/pagerank.py``.
    Shared by the general columnar kernel and the scale (device-built
    columns) kernel.

    ``r_init`` (optional ``[n_pad, C]``) warm-starts the iteration: the
    update is a contraction, so ANY masked positive start converges to the
    SAME fixed point — a near-solution (the previous hop's ranks) just
    gets there in a few steps instead of max_steps. Each column is masked
    to its own alive set, floored so newly-alive vertices get mass, and
    renormalised.

    ``e_src``/``e_dst`` are the (dst, src)-sorted pair table, so every
    combine at the destination is a sorted segment reduction."""
    C = me.shape[1]
    # Edge traffic is tiled past the payload budget (_edge_tile_for): the
    # f32 view of the mask and the per-iteration gather payload are both
    # [m_pad, C] transients that at 28M pairs x 128 columns outgrow a
    # v5e's HBM — the resulting spill, not compute, bound the scale sweep.
    tile = _edge_tile_for(e_src.shape[0], C, tile_budget)
    if tile is not None:
        n_main = (e_src.shape[0] // tile) * tile
        main = (e_src[:n_main].reshape(-1, tile),
                e_dst[:n_main].reshape(-1, tile),
                me[:n_main].reshape(-1, tile, C))
        rem = (e_src[n_main:], e_dst[n_main:], me[n_main:])
        # carry seed rides the mask's varying axes: under the
        # column-sharded shard_map(check_vma=True) the accumulator must
        # enter the scan column-varying, like the while_loop seeds below
        acc0 = (jnp.zeros((n_pad, C), jnp.float32)
                + (mv[0] & False).astype(jnp.float32)[None, :])

        def tiled_sum(payload_of, by_dst):
            def step(acc, inp):
                es, ed, mk = inp
                return acc + jax.ops.segment_sum(
                    payload_of(es, mk), ed if by_dst else es,
                    num_segments=n_pad,
                    # tiles are contiguous slices of the globally
                    # (dst, src)-sorted order
                    indices_are_sorted=by_dst), None

            acc, _ = jax.lax.scan(step, acc0, main)
            if rem[0].shape[0]:
                es, ed, mk = rem
                acc = acc + jax.ops.segment_sum(
                    payload_of(es, mk), ed if by_dst else es,
                    num_segments=n_pad,
                    indices_are_sorted=by_dst)
            return acc

        out_deg = tiled_sum(
            lambda es, mk: mk.astype(jnp.float32), by_dst=False)
    else:
        # out-degree per column: combine at src (unsorted scatter, once);
        # the f32 view of the mask fuses into the scatter-add
        out_deg = jax.ops.segment_sum(me.astype(jnp.float32), e_src,
                                      num_segments=n_pad)
    n_act = jnp.maximum(jnp.sum(mv.astype(jnp.float32), axis=0), 1.0)
    r0 = jnp.where(mv, 1.0 / n_act[None, :], 0.0).astype(jnp.float32)
    if r_init is not None:
        warm = jnp.where(mv, jnp.maximum(r_init, 0.0), 0.0)
        warm = warm + jnp.where(mv, 0.1 / n_act[None, :], 0.0)
        warm = warm / jnp.maximum(jnp.sum(warm, axis=0, keepdims=True),
                                  1e-30)
        r0 = warm.astype(jnp.float32)
    inv_deg = 1.0 / jnp.maximum(out_deg, 1.0)
    dangling_mask = mv & (out_deg == 0)
    ends, pos = None, None
    if _combine_route(e_src.shape[0], C, tile_budget) == "scan":
        # where each destination's rows end and how far into its segment
        # a row lies: functions of e_dst alone, once a dispatch, outside
        # the superstep loop
        ends, pos = segment_ends_pos(e_dst, n_pad)
    # the gather table holds P vertices a row, so that it stays in fast
    # memory and is never a table of single elements: a pair reads row
    # e_src // P and keeps slot e_src % P
    P = _pagerank_pack(n_pad, e_src.shape[0], C, tile_budget)
    e_row, e_slot = row_and_slot(e_src, P)

    def body(carry):
        step, r, halted = carry
        rd = r * inv_deg
        if tile is not None:
            agg = tiled_sum(
                lambda es, mk: jnp.where(mk, rd[es, :], 0.0), by_dst=True)
        else:
            with jax.named_scope("combine.gather"):
                # a row gather [m, P * C], the row's slot [m, C]; the
                # bool mask gates via where — only it stays live across
                # the loop
                payload = jnp.where(
                    me, packed_rows(rd, e_row, e_slot, P), 0.0)
            # a segmented scan along the rows and one gather of n_pad
            # rows, no scatter over the m rows; past SCAN_MAX_COLUMNS
            # columns the scatter, which then costs less (docs/KERNELS.md)
            agg = sorted_segment_sum(payload, e_dst, n_pad, ends=ends,
                                     pos=pos)
        dangling = jnp.sum(jnp.where(dangling_mask, r, 0.0), axis=0)
        new = ((1.0 - damping) / n_act[None, :]
               + damping * (agg + dangling[None, :] / n_act[None, :]))
        new = jnp.where(mv, new, 0.0).astype(jnp.float32)
        col_done = jnp.all((jnp.abs(new - r) < tol) | ~mv, axis=0)
        # freeze converged columns
        new = jnp.where(halted[None, :], r, new)
        return step + 1, new, halted | col_done

    def cond(carry):
        step, _, halted = carry
        return (step < max_steps) & ~jnp.all(halted)

    # seed the non-array carry components from mv (numeric no-ops): under
    # shard_map(check_vma=True) on a column-sharded mesh the loop carry
    # must enter with the same varying-axes type it leaves with, and both
    # step and halted become column-varying through the halting logic
    seed_false = mv[0] & False                                 # all-False
    step0 = jnp.int32(0) + (mv[0, 0] & False).astype(jnp.int32)
    steps, r, _ = jax.lax.while_loop(
        cond, body, (step0, r0, seed_false))
    return r.T, steps   # [C, n_pad], hop-major columns


@functools.lru_cache(maxsize=64)
def _compiled(n_pad: int, m_pad: int, H: int, C: int, damping: float,
              tol: float, max_steps: int, tdt: str, warm: bool = False,
              tile_budget: int | None = None):
    tdt = jnp.dtype(tdt)

    def run(e_src, e_dst, e_lat, e_alive, v_lat, v_alive,
            hop_of_col, T_col, w_col, *rest):
        me, mv = _column_masks(tdt, e_lat, e_alive, v_lat, v_alive,
                               hop_of_col, T_col, w_col)
        # warm arg: previous chunk's full [C, n_pad] output; tail slice +
        # per-hop tile in-program (see _compiled_delta)
        W = C // H
        r0 = jnp.tile(rest[0][-W:], (H, 1)).T if warm else None
        return _pagerank_columns(me, mv, e_src, e_dst, n_pad,
                                 damping, tol, max_steps, r_init=r0,
                                 tile_budget=tile_budget)

    return _ledger.instrument(
        "hopbatch.pagerank_cols", jax.jit(run),
        traffic=_ledger.edge_traffic_model(m_pad, C, n_pad))


@functools.lru_cache(maxsize=64)
def _compiled_delta(kind: str, n_pad: int, m_pad: int, H: int, W: int,
                    U_e: int, U_v: int, tdt: str, warm: bool,
                    algo_args: tuple, weighted: bool = False,
                    U_w: int = 0, h0: bool = False,
                    tile_budget: int | None = None):
    """Delta-fed columnar kernels: masks rebuilt on device from base state
    + per-hop deltas (``_masks_from_deltas``), then the shared algorithm
    body. ``kind``: pagerank | cc | cdlp | lcc | sgc | bfs (``weighted``
    adds a per-pair weight state rebuilt the same way); ``algo_args`` is
    the algorithm's static parameter tuple (``lcc``: its triangle table's
    ``tile_edges``, the table's arrays follow the column descriptors;
    ``sgc``: its rounds, the feature block and the propagation table
    follow them).
    ``h0=True`` is the resident-base variant: the base inputs are the
    previous dispatch's advanced state, delta[0] is applied before hop 0.
    Every variant returns ``(result, steps, advanced_base)`` so the
    caller can keep the fold state on device."""
    tdt_ = jnp.dtype(tdt)

    def run(e_src, e_dst, be_lat, be_alive, bv_lat, bv_alive,
            de_pos, de_lat, de_alive, dv_pos, dv_lat, dv_alive,
            T_col, w_col, *rest):
        me, mv, adv = _masks_from_deltas(
            tdt_, H, W, be_lat, be_alive, bv_lat, bv_alive,
            de_pos, de_lat, de_alive, dv_pos, dv_lat, dv_alive,
            T_col, w_col, h0=h0)
        if kind == "pagerank":
            damping, tol, max_steps = algo_args
            # warm arg is the previous chunk's FULL output [C, n_pad]; the
            # tail slice + per-hop tile happen in-program (host-side array
            # ops would be extra dispatches between the kernels)
            r0 = jnp.tile(rest[0][-W:], (H, 1)).T if warm else None
            out, steps = _pagerank_columns(
                me, mv, e_src, e_dst, n_pad, damping, tol, max_steps,
                r_init=r0, tile_budget=tile_budget)
            return out, steps, adv
        if kind == "cc":
            (max_steps,) = algo_args
            l0 = jnp.tile(rest[0][-W:], (H, 1)).T if warm else None
            out, steps = _cc_columns(me, mv, e_src, e_dst, n_pad, max_steps,
                                     tile_budget=tile_budget, l_init=l0)
            return out, steps, adv
        if kind == "cdlp":
            (max_steps,) = algo_args
            out, steps = _cdlp_columns(me, mv, e_src, e_dst, n_pad,
                                       max_steps)
            return out, steps, adv
        if kind == "lcc":
            # one pass, no rounds: a view is a mask over the log's
            # triangle table as it is over its pair table
            (tile_edges,) = algo_args
            return (lcc_columns(me, n_pad, tile_edges, *rest),
                    jnp.int32(1), adv)
        if kind == "sgc":
            # a column is an [n_pad, F] block: the columns are walked,
            # and what comes back of each is its summary, not the block
            (rounds,) = algo_args
            X, *table = rest
            return (_propagate.sgc_columns(
                me, mv, rounds, X, _propagate.PropagationTable(*table)),
                jnp.int32(rounds), adv)
        max_steps, directed = algo_args
        ew = 1.0
        nxt = 1   # rest[0] is the seed mask; weights then warm follow
        if weighted:
            w_base, dw_pos, dw_val = rest[nxt], rest[nxt + 1], rest[nxt + 2]
            nxt += 3
            cur_w, cols = w_base, []
            for h in range(H):   # same unrolled rebuild as the masks
                if h or h0:
                    cur_w = cur_w.at[dw_pos[h]].set(dw_val[h], mode="drop")
                cols.append(jnp.broadcast_to(
                    cur_w[:, None], (cur_w.shape[0], W)))
            ew = jnp.concatenate(cols, axis=1)   # [m_pad, C] hop-major
            adv = adv + (cur_w,)
        d0 = jnp.tile(rest[nxt][-W:], (H, 1)).T if warm else None
        out, steps = _bfs_columns(me, mv, e_src, e_dst, n_pad, max_steps,
                                  directed, rest[0], ew,  # rest[0]: seeds
                                  tile_budget=tile_budget, d_init=d0)
        return out, steps, adv

    return _ledger.instrument(
        f"hopbatch.delta.{kind}", jax.jit(run),
        traffic=_ledger.edge_traffic_model(m_pad, H * W, n_pad))


def _pad_hop_deltas(deltas, H: int, tdt):
    """Pad per-hop (pos, lat, alive) delta lists to a fixed ``[H, U]``
    shape (hop 0 is empty: its state IS the base). Pad index 2^31-1 is
    dropped by the device scatter."""
    longest = max((len(p) for p, _, _ in deltas), default=1)
    U = max(256, 1 << int(np.ceil(np.log2(max(longest, 1)))))
    pos = np.full((H, U), 2**31 - 1, np.int32)
    lat = np.zeros((H, U), tdt)
    alive = np.zeros((H, U), bool)
    for h, (p, l, a) in enumerate(deltas):
        pos[h, : len(p)] = p
        lat[h, : len(l)] = l
        alive[h, : len(a)] = a
    return U, pos, lat, alive


def run_columns_delta(kind, tables, base, deltas_e, deltas_v, hop_times,
                      windows, *, algo_args: tuple, seed_mask=None,
                      e_src_dev=None, e_dst_dev=None, r_init=None,
                      weight_base=None, weight_deltas=None,
                      h0_delta: bool = False, ship_counter=None,
                      static_tables: tuple = ()):
    """Dispatch a delta-fed columnar kernel (``kind``:
    pagerank|cc|cdlp|lcc|sgc|bfs) over ``_HopBatched._fold_deltas``
    output; returns ``(result, steps, advanced_base)``. ``static_tables``
    are device-resident per-log arrays the kind's body takes after the
    column descriptors (``lcc``: the triangle table; ``sgc``: the feature
    block and the propagation table). ``weight_base`` +
    ``weight_deltas`` ([(pos, val)] per hop) turn bfs into weighted SSSP
    with the weight state rebuilt on device too. ``h0_delta=True`` means
    ``base`` (and ``weight_base``) are the previous dispatch's
    device-resident advanced state and delta[0] carries the inter-batch
    catch-up — the sweep then ships O(Σ delta) bytes with no full-table
    upload at all."""
    H, C, _, T_col, w_col = _column_layout(hop_times, windows)
    W = C // H
    be_lat, be_alive, bv_lat, bv_alive = base
    tdt = tables.tdtype
    weighted = weight_base is not None
    U_w = 0
    # the dispatch's payload brought into the kernel's shapes on the host:
    # deltas padded to fixed shapes. ``cached``: the base is already
    # device-resident, only O(sum delta) work is left
    with TRACER.span("engine.layout", stage="payload", cached=h0_delta):
        U_e, de_pos, de_lat, de_alive = _pad_hop_deltas(deltas_e, H, tdt)
        U_v, dv_pos, dv_lat, dv_alive = _pad_hop_deltas(deltas_v, H, tdt)
        if weighted:
            longest = max((len(p) for p, _ in weight_deltas), default=1)
            U_w = max(256, 1 << int(np.ceil(np.log2(max(longest, 1)))))
            dw_pos = np.full((H, U_w), 2**31 - 1, np.int32)
            dw_val = np.zeros((H, U_w), np.float32)
            for h, (p, v) in enumerate(weight_deltas):
                dw_pos[h, : len(p)] = p
                dw_val[h, : len(v)] = v
    tile_budget = _tile_budget_bytes()
    runner = _compiled_delta(kind, tables.n_pad, tables.m_pad, H, W,
                             U_e, U_v, np.dtype(tdt).name,
                             r_init is not None, tuple(algo_args),
                             weighted, U_w, h0_delta, tile_budget)
    if ship_counter is not None:
        # FOLD-STATE host→device payload of THIS dispatch (padded shapes;
        # device-resident inputs — h0 base, cached tables — ship nothing).
        # O(C) column descriptors and per-dispatch seed masks are excluded
        # on BOTH fold paths, so host-vs-delta numbers compare like for
        # like (engine ship_bytes docstring).
        shipped = [de_pos, de_lat, de_alive, dv_pos, dv_lat, dv_alive]
        if not h0_delta:
            shipped += [a for a in base]
        if weighted:
            shipped += [dw_pos, dw_val]
            if not h0_delta:
                shipped.append(weight_base)
        ship_counter(int(sum(a.nbytes for a in shipped)))
    extra = []
    if seed_mask is not None:
        extra.append(seed_mask)
    if weighted:
        extra.extend((weight_base, dw_pos, dw_val))
    if r_init is not None:
        extra.append(r_init)
    extra.extend(static_tables)
    # the whole dispatch payload ships through the pipelined engine: array
    # k+1 stages while k is on the wire, each slice retried on transport
    # errors (device-resident inputs pass through untouched)
    from ..utils.transfer import shared_engine

    # how the dispatch combines at the destination: PageRank's sum is a
    # scan or (tiled, wide) a scatter; min / max scatter; CDLP sorts; LCC
    # intersects neighbour sets over the triangle table; SGC sums F-wide
    # rows a step of the walked table at a time (ops/propagate)
    combine = {"pagerank": _combine_route(tables.m_pad, C, tile_budget),
               "cdlp": "sort", "lcc": "intersect",
               "sgc": "rows"}.get(kind, "scatter")
    pack = (_pagerank_pack(tables.n_pad, tables.m_pad, C, tile_budget)
            if kind == "pagerank" else 1)
    with TRACER.span("hop.compute", kind=kind, hops=H, cols=H * W,
                        resident_base=h0_delta, combine=combine,
                        gather_pack=pack):
        return runner(*shared_engine().put_many([
            e_src_dev if e_src_dev is not None else tables.e_src,
            e_dst_dev if e_dst_dev is not None else tables.e_dst,
            be_lat, be_alive, bv_lat, bv_alive,
            de_pos, de_lat, de_alive, dv_pos, dv_lat, dv_alive,
            T_col, w_col, *extra]))


def _edge_accumulate(seg, payload_of, combine, init, e_from, e_to, me, ew,
                     n_pad: int, tile, sorted_: bool):
    """``combine(acc, seg(payload_of(ef, mk, ex), et))`` over the edge
    dimension — single-shot when ``tile`` is None, else a ``lax.scan``
    over equal tiles plus a remainder slice (transient bounded at
    tile*C). ``ew`` is an optional per-edge [m_pad, C] operand (weighted
    traversal), sliced alongside. ``init`` must carry the vma the caller's
    loop state carries (see the while_loop seeds)."""
    C = me.shape[1]

    def one(ef, et, mk, ex):
        return seg(payload_of(ef, mk, ex), et, num_segments=n_pad,
                   indices_are_sorted=sorted_)

    if tile is None:
        return combine(init, one(e_from, e_to, me, ew))
    n_main = (e_from.shape[0] // tile) * tile
    xs = (e_from[:n_main].reshape(-1, tile),
          e_to[:n_main].reshape(-1, tile),
          me[:n_main].reshape(-1, tile, C)) + (
        (ew[:n_main].reshape(-1, tile, C),) if ew is not None else ())

    def step(acc, inp):
        ef, et, mk = inp[:3]
        ex = inp[3] if len(inp) > 3 else None
        return combine(acc, one(ef, et, mk, ex)), None

    acc, _ = jax.lax.scan(step, init, xs)
    if n_main < e_from.shape[0]:
        acc = combine(acc, one(e_from[n_main:], e_to[n_main:], me[n_main:],
                               ew[n_main:] if ew is not None else None))
    return acc


def _cc_columns(me, mv, e_src, e_dst, n_pad: int, max_steps: int,
                tile_budget: int | None = None, l_init=None):
    """Columnar min-label propagation — connected components for every
    (hop, window) column at once (semantics of
    ``algorithms/connected_components.py``: undirected min over both
    directions, labels are global padded indices). Shared by the
    single-device kernel and the column-sharded mesh runner.

    ``l_init`` ([n_pad, C] i32) warm-starts the propagation from a
    previous epoch's labels: the start is ``min(own index, l_init)``.
    The fixed point of min-label propagation is the min over each
    component of the START values, so the warm result equals the cold
    one iff every warm label is an index of a vertex in the same
    component — true when the graph only GAINED edges/vertices since the
    labels were computed (components only merge; a vertex's old label
    indexes a vertex of its old component ⊆ its new component). Callers
    enforce that monotonicity gate (``jobs/live.py``)."""
    I32_MAX = jnp.iinfo(jnp.int32).max
    lab0 = jnp.where(mv, jnp.arange(n_pad, dtype=jnp.int32)[:, None],
                     I32_MAX)
    if l_init is not None:
        lab0 = jnp.where(mv, jnp.minimum(lab0, l_init), I32_MAX)
    tile = _edge_tile_for(e_src.shape[0], me.shape[1], tile_budget)
    max0 = jnp.full_like(lab0, I32_MAX) \
        + (mv[0] & False).astype(jnp.int32)[None, :]   # vma-seeded

    def body(carry):
        step, lab, halted = carry

        def pull(idx_from, idx_to, sorted_):
            return _edge_accumulate(
                jax.ops.segment_min,
                lambda ef, mk, _: jnp.where(mk, lab[ef, :], I32_MAX),
                jnp.minimum, max0, idx_from, idx_to, me, None,
                n_pad, tile, sorted_)

        agg = jnp.minimum(pull(e_src, e_dst, True),
                          pull(e_dst, e_src, False))
        new = jnp.where(mv, jnp.minimum(lab, agg), I32_MAX)
        col_done = jnp.all(new == lab, axis=0)
        new = jnp.where(halted[None, :], lab, new)
        return step + 1, new, halted | col_done

    def cond(carry):
        step, _, halted = carry
        return (step < max_steps) & ~jnp.all(halted)

    # vma-safe carry seeds, as in _pagerank_columns
    steps, lab, _ = jax.lax.while_loop(
        cond, body,
        (jnp.int32(0) + (mv[0, 0] & False).astype(jnp.int32),
         lab0, mv[0] & False))
    return lab.T, steps   # [C, n_pad]


def _cdlp_columns(me, mv, e_src, e_dst, n_pad: int, max_steps: int):
    """Columnar CDLP (``algorithms/lpa.CDLP``: LDBC Graphalytics'
    community detection) for every (hop, window) column at once: exactly
    ``max_steps`` synchronous rounds in which a vertex takes the most
    frequent label among its in- and out-neighbours, the smallest among
    equals, and keeps its own without neighbours. Labels are global
    padded indices.

    The combine is a histogram, not an elementwise reduction: a round
    hands the ``2 * m_pad * C`` (segment, label) rows of both directions
    to ``ops.segment.segment_mode`` — the implementation ``bsp`` and the
    mesh run — with segment ``vertex * C + column``, which is the
    row-major order of the ``[m_pad, C]`` gathers. A masked row keeps its
    segment, so a segment's rows are the vertex's in- plus out-degree in
    the table, whatever the column: counted once a dispatch. No warm
    start: a warm round is another computation, not a nearer start."""
    C = me.shape[1]
    I32_MAX = jnp.iinfo(jnp.int32).max
    cols = jnp.arange(C, dtype=jnp.int32)[None, :]
    # every [m_pad, C] block is flattened BEFORE the two directions are
    # joined: the TPU pads C lanes to 128, so a [2 * m_pad, C] buffer is
    # 21 times its bytes (3.8 GB at m_pad 3.7M), a flat one is not
    seg = jnp.concatenate([(e_dst[:, None] * C + cols).reshape(-1),
                           (e_src[:, None] * C + cols).reshape(-1)])
    alive = jnp.tile(me.reshape(-1), 2)
    counts = jnp.repeat(
        segment_counts(jnp.concatenate([e_dst, e_src]), n_pad), C)
    lab0 = jnp.where(mv, jnp.arange(n_pad, dtype=jnp.int32)[:, None],
                     I32_MAX)

    def body(_, lab):
        with jax.named_scope("cdlp.gather"):
            sent = jnp.concatenate([lab[e_src, :].reshape(-1),
                                    lab[e_dst, :].reshape(-1)])
        agg = segment_mode(sent, seg, n_pad * C, alive,
                           default=-1, counts=counts).reshape(n_pad, C)
        return jnp.where(mv, jnp.where(agg >= 0, agg, lab), I32_MAX)

    lab = jax.lax.fori_loop(0, max_steps, body, lab0)
    return lab.T, jnp.int32(max_steps)   # [C, n_pad]


@functools.lru_cache(maxsize=64)
def _compiled_cc(n_pad: int, m_pad: int, H: int, C: int, max_steps: int,
                 tdt: str, tile_budget: int | None = None):
    tdt = jnp.dtype(tdt)

    def run(e_src, e_dst, e_lat, e_alive, v_lat, v_alive,
            hop_of_col, T_col, w_col):
        me, mv = _column_masks(tdt, e_lat, e_alive, v_lat, v_alive,
                               hop_of_col, T_col, w_col)
        return _cc_columns(me, mv, e_src, e_dst, n_pad, max_steps,
                           tile_budget=tile_budget)

    return _ledger.instrument(
        "hopbatch.cc_cols", jax.jit(run),
        traffic=_ledger.edge_traffic_model(m_pad, C, n_pad))


def _bfs_columns(me, mv, e_src, e_dst, n_pad: int, max_steps: int,
                 directed: bool, seed_mask, ew,
                 tile_budget: int | None = None, d_init=None):
    """Columnar min-plus traversal (``algorithms/traversal.SSSP``
    semantics); ``ew`` is 1.0 for hop counting or [m_pad, C] f32 weights.
    Shared by the single-device kernel and the column-sharded runner.

    ``d_init`` ([n_pad, C] f32) warm-starts the relaxation with
    ``min(cold seed, d_init)``: valid whenever every finite ``d_init``
    entry is a REALIZABLE path length in the current graph — true when
    edges/vertices were only ADDED (at unit/unchanged weight) since the
    distances were computed, so old shortest paths still exist and
    relaxation can only tighten them. Callers enforce the gate
    (``jobs/live.py``); weighted SSSP never warm-starts (a re-add can
    RAISE a pair's weight, leaving stale under-estimates)."""
    INF = jnp.float32(jnp.inf)
    d0 = jnp.where(mv & seed_mask[:, None], 0.0, INF)
    if d_init is not None:
        d0 = jnp.where(mv, jnp.minimum(d0, d_init), INF)
    tile = _edge_tile_for(e_src.shape[0], me.shape[1], tile_budget)
    ew_arr = None if not hasattr(ew, "shape") or ew.ndim == 0 else ew
    inf0 = jnp.full_like(d0, INF) \
        + (mv[0] & False).astype(jnp.float32)[None, :]   # vma-seeded

    def body(carry):
        step, dist, halted = carry

        def pull(idx_from, idx_to, sorted_):
            return _edge_accumulate(
                jax.ops.segment_min,
                lambda ef, mk, ex: jnp.where(
                    mk, dist[ef, :] + (ew if ex is None else ex), INF),
                jnp.minimum, inf0, idx_from, idx_to, me, ew_arr,
                n_pad, tile, sorted_)

        agg = pull(e_src, e_dst, True)
        if not directed:
            agg = jnp.minimum(agg, pull(e_dst, e_src, False))
        new = jnp.where(mv, jnp.minimum(dist, agg), INF)
        col_done = jnp.all(new == dist, axis=0)
        new = jnp.where(halted[None, :], dist, new)
        return step + 1, new, halted | col_done

    def cond(carry):
        step, _, halted = carry
        return (step < max_steps) & ~jnp.all(halted)

    # vma-safe carry seeds, as in _pagerank_columns
    steps, dist, _ = jax.lax.while_loop(
        cond, body,
        (jnp.int32(0) + (mv[0, 0] & False).astype(jnp.int32),
         d0, mv[0] & False))
    return dist.T, steps   # [C, n_pad]


@functools.lru_cache(maxsize=64)
def _compiled_bfs(n_pad: int, m_pad: int, H: int, C: int, max_steps: int,
                  directed: bool, tdt: str, weighted: bool = False,
                  tile_budget: int | None = None):
    tdt = jnp.dtype(tdt)

    def run(e_src, e_dst, e_lat, e_alive, v_lat, v_alive,
            hop_of_col, T_col, w_col, seed_mask, *rest):
        me, mv = _column_masks(tdt, e_lat, e_alive, v_lat, v_alive,
                               hop_of_col, T_col, w_col)
        ew = rest[0][hop_of_col].T if weighted else 1.0   # [m_pad, C]
        return _bfs_columns(me, mv, e_src, e_dst, n_pad, max_steps,
                            directed, seed_mask, ew,
                            tile_budget=tile_budget)

    return _ledger.instrument(
        "hopbatch.bfs_cols", jax.jit(run),
        traffic=_ledger.edge_traffic_model(m_pad, C, n_pad))


def _seed_mask(tables, seed_vids) -> np.ndarray:
    """Global dense-space seed mask from external vertex ids (absent ids
    ignored)."""
    seed_mask = np.zeros(tables.n_pad, bool)
    seeds = np.asarray(sorted({int(v) for v in seed_vids}), np.int64)
    if len(seeds) and len(tables.uv):
        pos = np.clip(np.searchsorted(tables.uv, seeds), 0,
                      len(tables.uv) - 1)
        ok = tables.uv[pos] == seeds
        seed_mask[pos[ok]] = True
    return seed_mask


def run_bfs_columns(tables, e_lat, e_alive, v_lat, v_alive, hop_times,
                    windows, seed_vids, *, directed: bool = False,
                    max_steps: int = 100, e_src_dev=None, e_dst_dev=None,
                    weight_cols=None):
    """Columnar min-plus traversal over prebuilt fold columns;
    ``seed_vids`` are external vertex ids looked up in the global dense
    space (absent ids ignored). ``weight_cols`` ([H, m_pad] f32, missing
    folded to 1.0) turns hop counting into weighted SSSP."""
    H, C, hop_of_col, T_col, w_col = _column_layout(hop_times, windows)
    seed_mask = _seed_mask(tables, seed_vids)
    runner = _compiled_bfs(tables.n_pad, tables.m_pad, H, C, int(max_steps),
                           bool(directed), np.dtype(tables.tdtype).name,
                           weight_cols is not None, _tile_budget_bytes())
    extra = (seed_mask,) if weight_cols is None \
        else (seed_mask, weight_cols)
    return _dispatch_columns(runner, tables,
                             (e_lat, e_alive, v_lat, v_alive),
                             hop_of_col, T_col, w_col, e_src_dev, e_dst_dev,
                             *extra)


def run_cc_columns(tables, e_lat, e_alive, v_lat, v_alive, hop_times,
                   windows, *, max_steps: int = 100,
                   e_src_dev=None, e_dst_dev=None):
    """Columnar connected components over prebuilt per-hop fold columns."""
    H, C, hop_of_col, T_col, w_col = _column_layout(hop_times, windows)
    runner = _compiled_cc(tables.n_pad, tables.m_pad, H, C, int(max_steps),
                          np.dtype(tables.tdtype).name, _tile_budget_bytes())
    return _dispatch_columns(runner, tables,
                             (e_lat, e_alive, v_lat, v_alive),
                             hop_of_col, T_col, w_col, e_src_dev, e_dst_dev)


def _payload_nbytes(obj) -> int:
    """Recursive numpy-array byte count of a fold payload — what the
    bounded fold cache accounts an entry at."""
    if obj is None:
        return 0
    if isinstance(obj, np.ndarray):
        return obj.nbytes
    if isinstance(obj, (list, tuple)):
        return sum(_payload_nbytes(x) for x in obj)
    return 8   # scalars (hop times in vshell rows)


def _join_columns(outs):
    """The chunks' results as one, hop-major: each is an array whose
    leading axis is its columns, or (``sgc``) a pytree of such."""
    return jax.tree_util.tree_map(
        lambda *xs: jnp.concatenate(xs, axis=0), *outs)


class _HopBatched:
    """Shared incremental fold → per-hop state columns (deletes included).

    ``run(hop_times, windows, chunks=k)`` splits the sweep into ``k``
    equal hop groups and pipelines them: group ``i+1``'s HOST fold +
    staging run in the lookahead prefetch worker (``RTPU_PREFETCH=0``
    disables) while group ``i``'s payload ships through the pipelined
    transfer engine and its supersteps run on DEVICE — fold → stage →
    ship → compute, the pipelining a one-dispatch sweep can't have.
    Equal group sizes reuse one compiled program. Results match
    ``chunks=1`` (hop-major concatenation; tested — bitwise for the
    integer/min-plus kernels, within solver tolerance for PageRank,
    whose differently-shaped chunk programs may round f32 reductions
    differently on some XLA versions)."""

    def __init__(self, log: EventLog):
        # the half of an engine that is a function of the log alone comes
        # from the log's cached index: a private fork of its pristine fold
        # builder, and its global tables by reference (read-only here).
        # ``index_status`` says what the lookup cost: "hit" (a fork),
        # "extended" (a suffix adopted first) or "miss" (built here)
        self.sw, self.tables, self.index_status = log_index(log)
        # cache key for the per-log caches (index, device edge tables):
        # the CALLER's log object — sw.log is the index's pin,
        # replaced whenever the log grows
        self._log = log
        #: host seconds spent folding + writing columns in the LAST run()
        #: (callers report it as snapshot-build time; under the lookahead
        #: prefetcher this is WORKER time, overlapped with device compute)
        self.fold_seconds = 0.0
        #: the LAST run()'s fold seconds split by pipeline mode
        #: (serial / parallel / cache_hit replay) — the resource ledger's
        #: fold breakdown. Single writer per mode within one run (the one
        #: prefetch worker, or the dispatch thread's consume), and read
        #: only after the run's folds have drained.
        self.fold_mode_seconds: dict = {}
        #: seconds the LAST run()'s dispatch loop spent WAITING on the
        #: lookahead fold — 0 means the fold hid entirely behind compute
        self.fold_stall_seconds = 0.0
        #: the part of the LAST run()'s ``fold_seconds`` the dispatch
        #: loop's own thread folded inline (one group, or prefetch off) —
        #: with the stall, the ``fold`` phase of the run's wall; the rest
        #: of ``fold_seconds`` overlapped the device on a worker
        self.fold_inline_seconds = 0.0
        #: host→device FOLD-STATE payload bytes of the LAST run() — the
        #: quantity the resident-base design exists to minimise. Excluded
        #: on both fold paths, so comparisons are like for like: the
        #: per-log static tables (ship once per log), O(C) column
        #: descriptors, and per-dispatch seed masks.
        self.ship_bytes = 0
        #: the LAST run()'s fold/stage/ship/compute breakdown
        #: (``device_sweep.sweep_phase_summary``)
        self.last_phase_seconds: dict = {}
        # static edge tables upload LAZILY on the first dispatch (callers
        # that only use the host fold — e.g. the column-sharded mesh
        # route — never pay the device transfer), then cache
        self._edges = None
        # running host base for the delta-fold path (built on first use)
        self._delta_base = None
        # device-resident advanced base: the last delta dispatch's
        # post-final-hop fold state, fed back as the next dispatch's base
        # so follow-on chunks/batches ship only deltas over the
        # host→device link
        self._dev_base = None
        # cross-epoch warm seed (run(..., warm_state=...)): initialises
        # the FIRST dispatch's iteration from a previous run's output —
        # the live epoch engine's warm-start channel (jobs/live.py)
        self._epoch_seed = None

    @property
    def _e_src(self):
        if self._edges is None:
            self._edges = _device_edges(self._log, self.tables)
        return self._edges[0]

    @property
    def _e_dst(self):
        if self._edges is None:
            self._edges = _device_edges(self._log, self.tables)
        return self._edges[1]

    def _drop_residency(self) -> None:
        """Forget the device-resident advanced base AND retire its
        resident-gauge row (obs/device.py) — every site that invalidates
        residency goes through here, or /devicez keeps reporting device
        bytes the backend already freed."""
        self._dev_base = None
        from ..obs import device as _obs_device

        _obs_device.RESIDENT.drop(self, "advanced_base")

    def _delta_base_args(self, ship_base):
        """(base_for_dispatch, h0_delta): the device-resident advanced
        state when the fold shipped no base snapshot, else the host
        snapshot (first batch, or residency was invalidated)."""
        if ship_base is None:
            return tuple(self._dev_base[:4]), True
        return ship_base, False

    def _count_ship(self, nbytes: int) -> None:
        self.ship_bytes += int(nbytes)

    def _run_delta(self, fn):
        """Run a delta dispatch and keep its advanced base device-resident;
        any dispatch-time failure drops residency so the next batch falls
        back to shipping a fresh base snapshot (execute-time failures are
        the jobs layer's concern — it rebuilds the engine)."""
        from ..obs import device as _obs_device

        try:
            out, steps, adv = fn()
        except Exception:
            self._drop_residency()
            raise
        self._dev_base = adv
        # resident-buffer gauge (obs/device.py): the advanced base is
        # what the next batch scatters onto instead of shipping a full
        # snapshot — a live row, re-upserted per delta dispatch
        _obs_device.RESIDENT.track(self, "advanced_base",
                                   _obs_device.nbytes_tree(adv))
        return out, steps

    #: whether ``repin`` can follow a suffix that grows the dense
    #: dictionaries. Set False by subclasses that hold more in the old
    #: dense space than ``_forget_tables`` forgets (SSSP's weight stream,
    #: LCC's triangle table, SGC's feature block) — for them a growth is
    #: a rebuild
    supports_growth = True

    def repin(self) -> str:
        """Adopt rows appended to the live log since this engine's pin
        (``SweepBuilder.repin``). Returns:

        * ``"noop"``.
        * ``"extended"`` — every piece of engine state stays valid: the
          dense dictionaries and pair table are unchanged, so
          ``GlobalTables``, the cached device edge tables, the host delta
          base AND the device-resident advanced base all keep describing
          the same coordinate space, and the next ``run`` folds exactly
          the appended suffix.
        * ``"grown"`` — the suffix brought new ids or pairs and the
          engine followed IN PLACE: its builder's dictionaries grew with
          the fold state and ``t_prev`` carried, ``tables`` is made anew
          over them (this engine's own: the log's index is not asked and
          not touched — the next request's lookup grows it by whatever
          the log holds then), and what sat in the old dense space is
          forgotten (``_forget_tables``) — the next ``run`` folds the
          suffix, puts the edge tables and ships a base snapshot. The
          padded sizes may have stepped up: a caller that guards memory
          by them checks again. A result row of before the growth is in
          the OLD space: the caller drops its warm seed. One
          ``engine.build`` span (``reason`` ``growth``) holds the stages
          and counts the growth; its seconds are the ledger's ``build``
          phase.
        * ``"rebuild"`` — the engine must be DISCARDED and rebuilt over
          the live log."""
        sfx = self.sw.suffix(self._log)
        if isinstance(sfx, str):
            return sfx
        if not sfx.grows:
            if not self.tables.holds_times(sfx.t):
                return "rebuild"   # suffix overflows the narrowed dtype
            return self.sw.adopt(sfx)
        if not self.supports_growth:
            return "rebuild"
        with _ledger.engine_build("growth", sfx.log) as sp:
            self.sw.adopt(sfx)
            with TRACER.span("index.tables", grow=True):
                self.tables = GlobalTables(self.sw)
            self._forget_tables()
            sp.set(**_ledger.built(self))
        return "grown"

    def _forget_tables(self) -> None:
        """Forget what was derived from the tables a growth replaced:
        the device edge tables, the host delta base and the resident
        advanced base (subclasses: what else they hold)."""
        self._edges = None
        self._delta_base = None
        self._drop_residency()

    #: set True by subclasses whose iteration is a contraction (safe to
    #: warm-start from the previous chunk's solution)
    supports_warm_start = False

    #: whether a warm-started chunk can halt in fewer supersteps than a
    #: cold one: never without ``supports_warm_start``, and PageRank only
    #: with ``tol > 0`` — with a fixed superstep count chunking buys no
    #: superstep (``jobs/manager._range_chunks``)
    warm_start_saves_steps = False

    #: subclasses whose kernel has a delta-fed variant (device-side mask
    #: rebuild, ``_masks_from_deltas``; SSSP additionally rebuilds its
    #: weight state from base + per-hop deltas)
    supports_delta_fold = False

    #: subclasses whose DELTA kernel accepts a cross-epoch warm seed
    #: (``run(..., warm_state=...)``) under the caller-enforced monotone
    #: gate — CC/BFS min-merge warm init. Contraction engines
    #: (``supports_warm_start``) accept the seed on every path instead.
    supports_epoch_warm = False

    #: set False by subclasses whose fold threads extra SEQUENTIAL state
    #: through the engine (SSSP's weight cursor) — they keep the serial
    #: shared-builder pipeline regardless of ``RTPU_FOLD_WORKERS``
    supports_parallel_fold = True

    def _use_delta_fold(self) -> bool:
        import os

        if not self.supports_delta_fold:
            return False
        return os.environ.get("RTPU_FOLD", "delta") != "host"

    def host_column_bytes(self, n_hops: int) -> int:
        """Host bytes the fold will materialise for an ``n_hops`` sweep —
        O(base) on the delta path, O(H · (m_pad + n_pad)) on the
        host-column path. Routing layers size their admission guards from
        THIS, not from engine internals."""
        t = self.tables
        per_row = np.dtype(t.tdtype).itemsize + 1   # lat + alive
        if self._use_delta_fold():
            return (t.m_pad + t.n_pad) * per_row
        return n_hops * (t.m_pad + t.n_pad) * per_row

    def device_mask_bytes(self, n_cols: int) -> int:
        """Device bytes of the [m_pad+n_pad, C] bool masks every columnar
        kernel holds across its superstep loop."""
        return (self.tables.m_pad + self.tables.n_pad) * n_cols

    def dispatch_columns_ok(self, C: int) -> bool:
        """Whether ONE dispatch of ``C`` columns stays on this engine's
        fast path: its ``[m_pad, C]`` payload untiled under the tile
        budget. The job layer sizes a Range's dispatches by this
        (``jobs/manager._range_chunks``)."""
        return _edge_tile_for(self.tables.m_pad, C,
                              _tile_budget_bytes()) is None

    def count_result(self, out) -> None:
        """Ledger counters that only a result knows (``out``: ``run``'s,
        fetched to the host) — none but ``sgc``'s rows walked."""

    def _dispatch_cols(self, cols, hop_times, windows, r_init=None):
        raise NotImplementedError

    def _dispatch_deltas(self, payload, hop_times, windows, r_init=None):
        raise NotImplementedError

    def _reset_run_counters(self) -> None:
        """The per-call accounting ("the LAST run()'s ...") starts over."""
        self.fold_seconds = 0.0
        self.fold_mode_seconds = {}
        self.fold_stall_seconds = 0.0
        self.fold_inline_seconds = 0.0
        self.ship_bytes = 0

    def run(self, hop_times, windows, chunks: int = 1,
            warm_start: bool = False, hop_callback=None, warm_state=None,
            chunk_rule: str = "caller"):
        """``chunks=k`` pipelines the sweep; ``warm_start=True``
        additionally initialises each chunk's columns from the previous
        chunk's LAST-hop ranks (same fixed point, reached in far fewer
        steps when consecutive hops differ little). Warm-started results
        agree with cold ones to the solver tolerance, not bitwise.

        ``warm_state`` (a previous ``run``'s output, ``[C_prev, n_pad]``
        with the SAME window count) seeds the FIRST dispatch the same
        way — the cross-epoch warm channel of the live epoch engine.
        Contraction engines (PageRank) accept it unconditionally; for
        CC/BFS the min-merge warm init is only equivalent under the
        monotone (add-only, unwindowed) gate the CALLER must enforce
        (``jobs/live.py``; kernel docstrings state the argument), and it
        is ignored on the host-column path, which has no warm plumbing.

        With ``RTPU_FOLD_WORKERS`` > 1 the chunk folds run CONCURRENTLY
        on forked builders (bit-identical payloads — docs/FOLD.md), and
        ``hop_callback`` may fire from worker threads in any hop order —
        key captures by the hop time argument, never by call order. An
        exact (log, hop grid) repeat serves its fold from the bounded
        cross-request fold cache (``RTPU_FOLD_CACHE_MB``); on a hit the
        callback replays from cached per-hop vertex state and
        ``fold_seconds`` stays ~0.

        ``chunk_rule`` says who chose ``chunks`` and why (the job layer's
        ``_range_chunks``: ``one_dispatch`` / ``fit`` / ``warm_start`` /
        ``ladder``); it rides the ``sweep.columnar`` span and the
        ledger's ``device`` block beside ``columns``, a dispatch's C."""
        self._reset_run_counters()
        if warm_start and not self.supports_warm_start:
            raise ValueError(
                f"{type(self).__name__} cannot warm-start: its superstep "
                "is not a contraction (stale state would be wrong, not "
                "just slower)")
        self._epoch_seed = None
        if warm_state is not None and (
                self.supports_warm_start
                or (self.supports_epoch_warm and self._use_delta_fold())):
            self._epoch_seed = warm_state
        hop_times = [int(x) for x in hop_times]
        chunks = max(1, min(int(chunks), len(hop_times)))
        # C of one dispatch; an unequal split runs as one group
        # (``_run_chunks``)
        groups = 1 if len(hop_times) % chunks else chunks
        columns = len(hop_times) // groups * len(windows)
        led = _ledger.current()
        if led is not None:
            led.note_chunks(chunks, columns, chunk_rule)
        from ..utils.transfer import shared_engine

        before = shared_engine().stats.as_dict()
        t_start = _time.perf_counter()
        try:
            with TRACER.span("sweep.columnar",
                                engine=type(self).__name__,
                                hops=len(hop_times), chunks=chunks,
                                columns=columns,
                                chunk_rule=chunk_rule) as sp:
                out = self._run_chunks(hop_times, windows, chunks,
                                       warm_start, hop_callback)
                self.last_phase_seconds = sweep_phase_summary(
                    sp, _time.perf_counter() - t_start, self.fold_seconds,
                    self.fold_stall_seconds,
                    shared_engine().stats.delta_since(before),
                    self.ship_bytes, len(hop_times),
                    fold_modes=self.fold_mode_seconds,
                    fold_inline_seconds=self.fold_inline_seconds)
            return out
        except Exception:
            # ANY mid-run failure (fold, hop_callback, dispatch) may leave
            # the host fold ahead of the device-resident base — drop
            # residency so the next batch ships a fresh snapshot instead
            # of silently scattering onto a stale device state. The HOST
            # base must go too: an advance that aborted after consuming
            # events but before _apply_delta_to_base leaves it missing
            # that window (last_delta only spans the latest advance), so
            # the next batch must re-materialise from the sweep's full
            # state, not snapshot the stale running base.
            self._drop_residency()
            self._delta_base = None
            raise

    def _use_prefetch(self) -> bool:
        import os

        return os.environ.get("RTPU_PREFETCH", "1") != "0"

    def _observe_fold(self, seconds: float, mode: str) -> None:
        # the per-mode split feeds the resource ledger (fold_seconds
        # itself stays the modes' sum EXCEPT cache_hit replay, which is
        # accounted as a mode but never as fold time — a hit's fold cost
        # is, by contract, 0)
        self.fold_mode_seconds[mode] = (
            self.fold_mode_seconds.get(mode, 0.0) + float(seconds))
        m = _metrics()
        if m is not None:
            m.fold_seconds.labels(mode).observe(float(seconds))

    def _fold_token(self):
        """Engine-specific component of the fold-cache key. The base fold
        payload depends only on the log and the hop grid — PageRank, CC
        and BFS over the same log SHARE cached payloads; engines whose
        fold carries extra state (SSSP weights) must disambiguate."""
        return None

    def _cache_key(self, cache, delta: bool, hop_times, n_groups: int):
        if cache is None:
            return None
        if self.sw.t_prev is not None and hop_times[0] < self.sw.t_prev:
            return None   # the fold path owns the backward-batch refusal
        if len(set(hop_times)) != len(hop_times):
            return None   # duplicate hops: capture order is ambiguous
        # the per-hop vertex-state capture (shell replay) alone would
        # outgrow the bound at scale — don't materialise H*n*17 bytes the
        # put would only refuse
        if len(hop_times) * len(self.sw.uv) * 17 > cache.max_bytes:
            return None
        return ("fold", log_fingerprint(self.sw.log), self._fold_token(),
                "delta" if delta else "cols", tuple(hop_times),
                int(n_groups))

    @staticmethod
    def _capture_cb(hop_callback, cap):
        """Wrap ``hop_callback`` to ALSO capture the per-hop vertex fold
        state (the reducer-shell inputs) into ``cap`` — what a fold-cache
        hit replays so callback-bearing jobs can skip folding too."""
        if cap is None:
            return hop_callback

        def cb(T, sw):
            cap.append((int(T), sw.v_lat.copy(), sw.v_alive.copy(),
                        sw.v_first.copy()))
            if hop_callback is not None:
                hop_callback(T, sw)
        return cb

    @staticmethod
    def _replay_vshells(vshells, hop_callback) -> None:
        from types import SimpleNamespace

        for T, vl, va, vf in vshells:
            hop_callback(T, SimpleNamespace(v_lat=vl, v_alive=va,
                                            v_first=vf))

    def _maybe_cache(self, cache, key, payloads, cap, delta) -> None:
        """Insert this sweep's fold output into the cross-request cache.
        Delta payloads are only replayable on a fresh engine when group 0
        shipped a full base snapshot (a resident fold's payload assumes
        THIS engine's device state)."""
        if cache is None or key is None or any(
                p is None for p in payloads):
            return
        if delta and payloads[0][0] is None:
            return
        vshells = sorted(cap, key=lambda r: r[0]) if cap else None
        nbytes = _payload_nbytes(payloads) + _payload_nbytes(vshells)
        cache.put(key, (list(payloads), vshells), nbytes)

    def _dispatch_group(self, payload, group, windows, delta, warm_start,
                        outs, steps_box) -> None:
        r_init = None
        if warm_start and outs:
            # previous chunk's FULL output; the kernel slices its last
            # hop's W windowed rows and tiles them per hop of this
            # group IN-PROGRAM — no extra host-issued device ops
            # between dispatches
            r_init = outs[-1]                              # [per*W, n_pad]
        elif not outs and self._epoch_seed is not None:
            # first dispatch of an epoch run: seed from the PREVIOUS
            # run's output (same tail-slice-and-tile contract as the
            # intra-run warm chunks; jobs/live.py owns the validity gate)
            r_init = self._epoch_seed
        if delta:
            out, st = self._dispatch_deltas(payload, group, windows,
                                            r_init=r_init)  # async
        else:
            out, st = self._dispatch_cols(payload, group, windows,
                                          r_init=r_init)   # async
        outs.append(out)
        steps_box[0] = jnp.maximum(steps_box[0], st)

    def _check_forward(self, hop_times) -> None:
        """The incremental fold only moves forward: a backward batch on
        the advanced clock would silently fold nothing (DeviceSweep
        raises for the same reason)."""
        if sorted(hop_times) != hop_times:
            raise ValueError("hop_times must ascend")
        if self.sw.t_prev is not None and hop_times[0] < self.sw.t_prev:
            raise ValueError(
                f"hop_times must continue forward from the previous batch "
                f"(got {hop_times[0]} < {self.sw.t_prev}); build a fresh "
                f"{type(self).__name__} to go back in history")

    def _run_chunks(self, hop_times, windows, chunks, warm_start,
                    hop_callback):
        self._check_forward(hop_times)
        delta = self._use_delta_fold()
        if chunks == 1 or len(hop_times) % chunks:
            # unequal groups would compile one program per distinct size —
            # pipeline only when the split is clean
            if warm_start and chunks > 1:
                _log.warning(
                    "%d hops do not split into %d equal chunks — running "
                    "one cold dispatch (warm_start has no effect)",
                    len(hop_times), chunks)
            groups = [list(hop_times)]
        else:
            per = len(hop_times) // chunks
            groups = [hop_times[c * per: (c + 1) * per]
                      for c in range(chunks)]

        # ---- cross-request fold cache: an exact (log, hop grid) repeat
        # skips folding entirely (the repeated-REST-range serving story)
        cache = fold_cache()
        key = self._cache_key(cache, delta, hop_times, len(groups))
        if key is not None:
            hit = cache.get(key)
            if hit is not None:
                payloads, vshells = hit
                if hop_callback is None or vshells is not None:
                    # the warm path still emits a hop.fold span (near-zero
                    # duration, mode=cache_hit): a traced sweep's phase
                    # timeline must show WHERE the fold went — "served
                    # from cache" — not silently omit the phase, and the
                    # ledger's fold breakdown records the replay the same
                    # way (fold_seconds stays 0: a hit's fold cost IS 0)
                    f0 = _time.perf_counter()
                    with TRACER.span("hop.fold", hops=len(hop_times),
                                        engine=type(self).__name__,
                                        mode="cache_hit"):
                        if hop_callback is not None:
                            self._replay_vshells(vshells, hop_callback)
                    self._observe_fold(_time.perf_counter() - f0,
                                       "cache_hit")
                    led = _ledger.current()
                    if led is not None:
                        led.fold_cache_event(hit=True)
                    outs, steps_box = [], [jnp.int32(0)]
                    for c, g in enumerate(groups):
                        self._dispatch_group(payloads[c], g, windows,
                                             delta, warm_start, outs,
                                             steps_box)
                    # the hit advanced the DEVICE base to the cached
                    # grid's last hop while the host fold clock (self.sw)
                    # never moved — a later resident batch would scatter
                    # an older catch-up delta onto that newer state. Drop
                    # residency: the next batch ships a base from the
                    # host clock, which is always consistent.
                    self._drop_residency()
                    return _join_columns(outs), steps_box[0]
                # cached without shells but this job needs them: refold
            led = _ledger.current()
            if led is not None:
                # a None hit AND the shell-less-entry refold both cost
                # this query a full fold — the ledger counts both as
                # misses (the global FoldCache stats count raw lookups)
                led.fold_cache_event(hit=False)

        workers = fold_workers()
        if (workers > 1 and self.supports_parallel_fold
                and self._use_prefetch() and len(hop_times) > 1):
            return self._fold_dispatch_parallel(
                groups, windows, warm_start, hop_callback, delta,
                cache, key, workers)
        return self._fold_dispatch_serial(
            groups, windows, warm_start, hop_callback, delta, cache, key)

    def _fold_dispatch_serial(self, groups, windows, warm_start,
                              hop_callback, delta, cache, key):
        """The shared-builder pipeline: groups fold one at a time on the
        single prefetch worker (``RTPU_PREFETCH_DEPTH`` of them queued
        ahead) while earlier groups ship and compute — today's behaviour,
        and the only safe shape for engines whose fold mutates shared
        state (``supports_parallel_fold = False``)."""
        cap = [] if key is not None else None
        cb = self._capture_cb(hop_callback, cap)
        payloads = [None] * len(groups)
        outs, steps_box = [], [jnp.int32(0)]

        def fold(c, group, lookahead: bool):
            return c, group, self._fold_group_serial(group, cb, delta,
                                                     lookahead)

        def dispatch(fold_out, stall):
            c, group, payload = fold_out
            self.fold_stall_seconds += stall
            if stall > 0:
                TRACER.complete("fold.stall", stall, hops=len(group))
            payloads[c] = payload
            self._dispatch_group(payload, group, windows, delta,
                                 warm_start, outs, steps_box)

        if self._use_prefetch() and len(groups) > 1:
            # hop-lookahead prefetch: group c+1's host fold + staging run
            # in the prefetch worker while group c's payload ships and its
            # columnar program runs on device — fold → stage → ship →
            # compute. Dispatch (result order) stays on THIS thread.
            prefetch_map(
                (functools.partial(fold, c, g, c > 0)
                 for c, g in enumerate(groups)),
                dispatch)
        else:
            for c, g in enumerate(groups):
                f0 = self.fold_seconds
                folded = fold(c, g, False)     # on THIS thread: wall
                self.fold_inline_seconds += self.fold_seconds - f0
                dispatch(folded, 0.0)
        self._maybe_cache(cache, key, payloads, cap, delta)
        return _join_columns(outs), steps_box[0]

    def _fold_group_serial(self, group, hop_callback, delta: bool,
                           lookahead: bool):
        """One dispatch group folded on the engine's OWN builder, on the
        calling thread, under a ``hop.fold`` span. A lookahead fold runs
        BEFORE the previous group's delta dispatch is issued — it must
        assume that dispatch will leave a device-resident base
        (assume_resident), or chunk 2+ would re-ship a full base
        snapshot the serial loop never ships."""
        with TRACER.span("hop.fold", hops=len(group),
                            engine=type(self).__name__):
            t0 = _time.perf_counter()
            if delta:
                _, p = self._fold_deltas(group, hop_callback,
                                         assume_resident=lookahead)
            else:
                _, p = self._fold_columns(group, hop_callback)
            self._observe_fold(_time.perf_counter() - t0, "serial")
        return p

    def fold_payloads(self, hop_times, chunks: int = 1, *, delta=None,
                      hop_callback=None):
        """Fold the sweep's chunk payloads WITHOUT dispatching: what
        ``run(hop_times, ..., chunks=chunks)`` folds, minus the fold
        cache's whole-payload entries and the dispatch. Returns
        ``(groups, payloads)``, one payload per dispatch group.

        Two callers. The serial/parallel fold equivalence tests take the
        engine's own payload kind (``delta=None``: ``RTPU_FOLD``). The
        column-sharded mesh route
        (``jobs/manager._try_range_mesh_columns``) asks for
        full host columns (``delta=False``: what ``parallel/columns.py``
        replicates) and hands in the reducer shells' ``hop_callback``,
        which — as in ``run()`` — may fire from worker threads in any
        hop order.

        Honours ``RTPU_FOLD_WORKERS`` exactly like ``run()``: above 1 (and
        more than one hop, on an engine that ``supports_parallel_fold``)
        the fold units run on forked builders on ``fold_pool()``, seeded
        from the fold cache's nearest checkpoint and recording theirs
        back, while this thread waits (``fold_stall_seconds``); otherwise
        the groups fold on this thread from the engine's live builder
        (``fold_inline_seconds``). Payload entries stay out of the cache
        (``key=None``): a caller of this surface never repeats a hop
        grid, and a mesh request's columns (hops x (m_pad + n_pad) x 5 B)
        would push the checkpoints out of the bound."""
        self._reset_run_counters()
        hop_times = [int(x) for x in hop_times]
        self._check_forward(hop_times)
        chunks = max(1, min(int(chunks), len(hop_times)))
        if chunks > 1 and len(hop_times) % chunks:
            chunks = 1
        per = len(hop_times) // chunks
        groups = [hop_times[c * per:(c + 1) * per] for c in range(chunks)]
        if delta is None:
            delta = self._use_delta_fold()
        workers = fold_workers()
        if (workers > 1 and self.supports_parallel_fold
                and len(hop_times) > 1):
            # checkpoints participate: repeated folds seed their forks at
            # the boundaries and skip the prefix re-fold — the serving
            # steady state
            payloads, _ = self._fold_groups_parallel(
                groups, hop_callback, delta, fold_cache(), None, workers,
                lambda c, p: None)
            return groups, payloads
        payloads = []
        for c, g in enumerate(groups):
            # chunks 1+ fold all-delta exactly like the pipelined run
            # (the previous chunk's dispatch leaves a resident base);
            # chunk 0 ships the base snapshot
            payloads.append(self._fold_group_serial(g, hop_callback, delta,
                                                    lookahead=c > 0))
        self.fold_inline_seconds = self.fold_seconds
        return groups, payloads

    def _fold_dispatch_parallel(self, groups, windows, warm_start,
                                hop_callback, delta, cache, key, workers):
        outs, steps_box = [], [jnp.int32(0)]

        def on_payload(c, payload):
            self._dispatch_group(payload, groups[c], windows, delta,
                                 warm_start, outs, steps_box)

        payloads, cap = self._fold_groups_parallel(
            groups, hop_callback, delta, cache, key, workers, on_payload)
        self._maybe_cache(cache, key, payloads, cap, delta)
        return _join_columns(outs), steps_box[0]

    def _fold_groups_parallel(self, groups, hop_callback, delta, cache,
                              key, workers, on_payload):
        """Parallel chunk folds: every fold unit runs on an INDEPENDENT
        fork of the sweep's builder (seeded by one bulk advance to the
        previous unit's boundary — or a cached checkpoint), concurrently
        on the sized ``fold_pool``. A single dispatch group additionally
        sub-splits across workers (every column row / delta list is
        absolute state, so parts just concatenate). ``on_payload(c,
        payload)`` fires on THIS thread as each dispatch group completes,
        in group order; results are bit-identical to the serial fold
        (tested per engine). ``hop_callback`` runs on worker threads and
        may interleave across units — callers key their capture by hop
        time, not call order."""
        if len(groups) == 1 and len(groups[0]) >= 2:
            hops0 = groups[0]
            n_sub = min(workers, len(hops0))
            per = -(-len(hops0) // n_sub)
            units = [{"c": 0, "hops": hops0[u * per:(u + 1) * per],
                      "off": u * per} for u in range(n_sub)]
            units = [u for u in units if u["hops"]]
        else:
            units = [{"c": c, "hops": g, "off": 0}
                     for c, g in enumerate(groups)]
        left_in_group = [0] * len(groups)
        for u in units:
            left_in_group[u["c"]] += 1

        fp = log_fingerprint(self.sw.log) if cache is not None else None
        cfg = self.sw._config()
        resident0 = delta and self._dev_base is not None
        cols_out = None
        if not delta:
            # the host-column path advances the fold WITHOUT maintaining
            # the running delta base — residency must drop here exactly
            # like serial ``_fold_columns``, or a later delta batch would
            # scatter onto a device state frozen several batches back
            self._delta_base = None
            self._drop_residency()
            cols_out = [self._alloc_columns(len(g)) for g in groups]
        cap = [] if key is not None else None
        cb = self._capture_cb(hop_callback, cap)

        def make_task(u: int):
            unit = units[u]
            if u > 0:
                boundary = int(units[u - 1]["hops"][-1])
            elif delta and resident0:
                # the resident chain pins unit 0 to the live engine
                # clock: its catch-up delta must cover exactly
                # (engine clock, first hop] — a checkpoint seed ahead of
                # the clock would drop updates the device never saw
                boundary = None
            else:
                # non-resident unit 0 emits ABSOLUTE state (base snapshot
                # / column rows) — seed it at its own first hop so a warm
                # checkpoint store removes the hop-0 bulk fold too
                boundary = int(unit["hops"][0])

            def task():
                t0 = _time.perf_counter()
                # worker attr: the pool thread's name on the span itself,
                # so /tracez?trace_id= shows WHICH fold worker ran each
                # unit without joining against thread metadata (the span
                # still joins the request's trace via the pool-handoff
                # context adopted by prefetch_map — core/sweep.py)
                with TRACER.span("hop.fold", hops=len(unit["hops"]),
                                    engine=type(self).__name__,
                                    mode="parallel",
                                    worker=_threading.current_thread(
                                        ).name):
                    sw = self._seed_fork(boundary, cache, fp, cfg)
                    if delta:
                        ship = unit["c"] == 0 and unit["off"] == 0 \
                            and not resident0
                        part = self._fold_deltas_fork(sw, unit["hops"],
                                                      ship, cb)
                    else:
                        part = None
                        self._fold_columns_fork(sw, unit["hops"], cb,
                                                cols_out[unit["c"]],
                                                unit["off"])
                return u, sw, part, _time.perf_counter() - t0
            return task

        pending: dict[int, list] = {}
        payloads = [None] * len(groups)
        last_sw = [None]

        def consume(res, stall):
            u, sw, part, dt = res
            self.fold_seconds += dt
            self._observe_fold(dt, "parallel")
            self.fold_stall_seconds += stall
            if stall > 0:
                TRACER.complete("fold.stall", stall,
                                   hops=len(units[u]["hops"]))
            last_sw[0] = sw
            c = units[u]["c"]
            pending.setdefault(c, []).append(part)
            left_in_group[c] -= 1
            if left_in_group[c]:
                return
            parts = pending.pop(c)
            if delta:
                payload = parts[0] if len(parts) == 1 \
                    else self._merge_delta_parts(parts)
            else:
                payload = cols_out[c]
                self.ship_bytes += sum(a.nbytes for a in payload)
            payloads[c] = payload
            on_payload(c, payload)

        prefetch_map([make_task(u) for u in range(len(units))], consume,
                     depth=len(units), pool=fold_pool())
        # adopt the final fork: the engine's host fold clock ends at the
        # sweep's last hop, exactly like the serial path. The running
        # host base was never advanced — drop it (resident batches
        # re-materialise it lazily from the adopted builder's state).
        self.sw = last_sw[0]
        self._delta_base = None
        return payloads, cap

    def _seed_fork(self, boundary, cache, fp, cfg):
        """``core/sweep.seeded_fork`` of this engine's builder."""
        return seeded_fork(self.sw, boundary, cache, fp, cfg)

    @staticmethod
    def _merge_delta_parts(parts):
        """Concatenate sub-unit delta payloads of ONE dispatch group:
        part 0 may carry the base; per-hop delta lists append in hop
        order (each sub-unit's hop 0 is the catch-up delta from the
        previous unit's boundary — exactly the serial fold's windows)."""
        base = parts[0][0]
        deltas_e, deltas_v = [], []
        for p in parts:
            deltas_e.extend(p[1])
            deltas_v.extend(p[2])
        return (base, deltas_e, deltas_v)

    def _alloc_columns(self, H: int):
        t = self.tables
        return (np.full((H, t.m_pad), t.tmin, t.tdtype),
                np.zeros((H, t.m_pad), bool),
                np.full((H, t.n_pad), t.tmin, t.tdtype),
                np.zeros((H, t.n_pad), bool))

    def _fold_columns_fork(self, sw, group, hop_callback, out,
                           off: int) -> None:
        """Column fold of one unit on a FORKED builder, written into
        ``out`` rows [off, off+len): every row is absolute fold state, so
        units fold independently and the assembled arrays are
        bit-identical to the serial ``_fold_columns``."""
        t = self.tables
        e_lat, e_alive, v_lat, v_alive = out
        row_bytes = sum(a[0].nbytes for a in out)
        for j, T in enumerate(group):
            sw._advance(T)
            if hop_callback is not None:
                hop_callback(T, sw)
            r = off + j
            # hop 0 writes the full fold state, every later hop memcpys
            # the previous row (contiguous in this layout) and scatters
            # only the hop's exact touched-entity delta
            # (``sweep.last_delta``)
            with TRACER.span("fold.payload", base=j == 0, bytes=row_bytes):
                if j == 0:
                    pos = t.eng_pos(sw.e_enc)
                    e_lat[r, pos] = t.cast_times(sw.e_lat)
                    e_alive[r, pos] = sw.e_alive
                    nv = len(sw.uv)
                    v_lat[r, :nv] = t.cast_times(sw.v_lat)
                    v_alive[r, :nv] = sw.v_alive
                else:
                    e_lat[r] = e_lat[r - 1]
                    e_alive[r] = e_alive[r - 1]
                    v_lat[r] = v_lat[r - 1]
                    v_alive[r] = v_alive[r - 1]
                    d = sw.last_delta
                    if len(d["e_enc"]):
                        dpos = t.eng_pos(d["e_enc"])
                        e_lat[r, dpos] = t.cast_times(d["e_lat"])
                        e_alive[r, dpos] = d["e_alive"]
                    if len(d["v_idx"]):
                        v_lat[r, d["v_idx"]] = t.cast_times(d["v_lat"])
                        v_alive[r, d["v_idx"]] = d["v_alive"]

    def _fold_deltas_fork(self, sw, group, ship_base: bool, hop_callback):
        """Delta fold of one unit on a FORKED builder — the parallel twin
        of ``_fold_deltas``: no engine state is touched, so any number of
        units fold concurrently. ``ship_base`` makes hop 0 a full base
        snapshot (the first unit of a non-resident sweep); otherwise
        every hop ships as a delta, hop 0 being the catch-up from the
        previous unit's boundary — the same windows the serial fold
        produces, so the assembled payload is bit-identical."""
        tdt = self.tables.tdtype
        deltas_e, deltas_v = [], []
        base = None
        empty = (np.empty(0, np.int32), np.empty(0, tdt),
                 np.empty(0, bool))
        for j, T in enumerate(group):
            sw._advance(T)
            if hop_callback is not None:
                hop_callback(T, sw)
            with TRACER.span("fold.payload") as sp:
                if j == 0 and ship_base:
                    base = self._materialise_base(sw)
                    de = dv = empty
                    sp.set(base=True, bytes=_payload_nbytes(base))
                else:
                    de, dv = self._delta_eng(sw.last_delta)
                    sp.set(base=False, bytes=_payload_nbytes((de, dv)))
                deltas_e.append(de)
                deltas_v.append(dv)
        return (base, deltas_e, deltas_v)

    def _materialise_base(self, sw):
        """Full engine-coordinate base arrays from a builder's fold state
        (the delta path's hop-0 snapshot)."""
        t = self.tables
        tdt = t.tdtype
        be_lat = np.full(t.m_pad, t.tmin, tdt)
        be_alive = np.zeros(t.m_pad, bool)
        pos = t.eng_pos(sw.e_enc)
        be_lat[pos] = t.cast_times(sw.e_lat)
        be_alive[pos] = sw.e_alive
        bv_lat = np.full(t.n_pad, t.tmin, tdt)
        bv_alive = np.zeros(t.n_pad, bool)
        nv = len(sw.uv)
        bv_lat[:nv] = t.cast_times(sw.v_lat)
        bv_alive[:nv] = sw.v_alive
        return (be_lat, be_alive, bv_lat, bv_alive)

    def _fold_columns(self, hop_times, hop_callback=None):
        f0 = _time.perf_counter()
        # this path advances the shared SweepBuilder WITHOUT updating the
        # running delta base — a later delta-fold call must rebuild it or
        # it would scatter one hop's delta onto a stale base
        self._delta_base = None
        self._drop_residency()
        hop_times = [int(x) for x in hop_times]
        self._check_forward(hop_times)
        # host fold -> hop-major state columns [H, m_pad]/[H, n_pad], on
        # the engine's own builder: one O(m) scatter, then an O(m)
        # contiguous memcpy plus an O(delta) scatter per hop, instead of
        # an O(m) scattered write per hop
        cols = self._alloc_columns(len(hop_times))
        self._fold_columns_fork(self.sw, hop_times, hop_callback, cols, 0)
        self.fold_seconds += _time.perf_counter() - f0
        self.ship_bytes += sum(a.nbytes for a in cols)
        return hop_times, cols

    def _delta_eng(self, d):
        """``sweep.last_delta`` → engine-coordinate (pos, lat, alive)
        triples — shared by the running-base scatter and the forked
        parallel fold."""
        t = self.tables
        de = (t.eng_pos(d["e_enc"]).astype(np.int32),
              t.cast_times(d["e_lat"]), d["e_alive"].astype(bool))
        dv = (d["v_idx"].astype(np.int32), t.cast_times(d["v_lat"]),
              d["v_alive"].astype(bool))
        return de, dv

    def _apply_delta_to_base(self):
        """Scatter the sweep's last delta into the RUNNING host base
        (O(delta)); returns the delta in engine coordinates."""
        de, dv = self._delta_eng(self.sw.last_delta)
        be_lat, be_alive, bv_lat, bv_alive = self._delta_base
        be_lat[de[0]] = de[1]
        be_alive[de[0]] = de[2]
        bv_lat[dv[0]] = dv[1]
        bv_alive[dv[0]] = dv[2]
        return de, dv

    def _fold_deltas(self, hop_times, hop_callback=None,
                     assume_resident: bool = False):
        """Delta-fold: the state at each batch's first hop (the base) plus
        per-hop touched-entity (pos, lat, alive) lists — the device
        rebuilds the hop columns (``_masks_from_deltas``). Host work and
        H2D bytes are O(base + Σ delta) instead of O(H · m_pad): the cost
        that made the host fold the binding term of the headline sweep.
        The base is a RUNNING array updated by O(delta) scatters, so
        chunked (pipelined) sweeps pay the full-table materialisation
        once, not per chunk. ``assume_resident=True`` is the lookahead
        prefetcher's promise that the PREVIOUS group's delta dispatch will
        have left a device-resident advanced base by the time this
        payload dispatches (the fold runs before that dispatch is issued;
        a dispatch failure aborts the sweep before the payload is used)."""
        f0 = _time.perf_counter()
        t = self.tables
        hop_times = [int(x) for x in hop_times]
        self._check_forward(hop_times)
        tdt = t.tdtype
        deltas_e, deltas_v = [], []
        ship_base = None
        # a live device-resident base makes this batch all-delta: hop 0's
        # catch-up ships in the delta[0] slot instead of a base snapshot
        resident = assume_resident or self._dev_base is not None
        if resident and self._delta_base is None \
                and self.sw.t_prev is not None:
            # a parallel fold adopted a forked builder and dropped the
            # running base — rebuild it at the adopted clock (the same
            # time the device-resident state sits at) so the resident
            # all-delta contract survives across batch styles
            with TRACER.span("fold.payload", base=True) as sp:
                self._delta_base = list(self._materialise_base(self.sw))
                sp.set(bytes=_payload_nbytes(self._delta_base))
        resident = resident and self._delta_base is not None
        empty = (np.empty(0, np.int32), np.empty(0, tdt),
                 np.empty(0, bool))
        for j, T in enumerate(hop_times):
            self.sw._advance(T)
            if hop_callback is not None:
                hop_callback(T, self.sw)
            with TRACER.span("fold.payload") as sp:
                de = dv = empty
                if self._delta_base is None:
                    # first batch, first hop: materialise from the full
                    # fold
                    self._delta_base = list(
                        self._materialise_base(self.sw))
                else:
                    de, dv = self._apply_delta_to_base()
                if j == 0 and not resident:
                    # snapshot the running base as this batch's upload
                    # (the arrays keep mutating through later hops;
                    # jnp.asarray is async, so the copy must be taken
                    # now); hop 0's delta is in it already
                    ship_base = tuple(a.copy() for a in self._delta_base)
                    de = dv = empty
                    sp.set(base=True, bytes=_payload_nbytes(ship_base))
                else:
                    sp.set(base=False, bytes=_payload_nbytes((de, dv)))
                deltas_e.append(de)
                deltas_v.append(dv)
        self.fold_seconds += _time.perf_counter() - f0
        return hop_times, (ship_base, deltas_e, deltas_v)


class HopBatchedPageRank(_HopBatched):
    """Windowed PageRank over a full hop sweep in one device call.

    ``run(hop_times, windows)`` returns ``(ranks, steps)`` with ranks
    ``[H*W, n_pad]`` ordered hop-major (hop 0's windows first), rows in the
    global dense vertex space (``self.tables.uv``).
    """

    supports_warm_start = True   # power iteration is a contraction
    supports_delta_fold = True

    def __init__(self, log: EventLog, damping: float = 0.85,
                 tol: float = 1e-7, max_steps: int = 20):
        super().__init__(log)
        self.damping, self.tol, self.max_steps = damping, tol, max_steps

    @property
    def warm_start_saves_steps(self) -> bool:
        # with tol == 0 no column halts before max_steps, whatever it
        # starts from
        return self.tol > 0

    def dispatch_columns_ok(self, C: int) -> bool:
        """Untiled AND narrow enough for the segmented scan: a tiled or
        wide dispatch sums by the scatter (``_combine_route``)."""
        return _combine_route(self.tables.m_pad, C,
                              _tile_budget_bytes()) == "scan"

    def _dispatch_cols(self, cols, hop_times, windows, r_init=None):
        return run_columns(
            self.tables, *cols, hop_times, windows,
            damping=self.damping, tol=self.tol, max_steps=self.max_steps,
            e_src_dev=self._e_src, e_dst_dev=self._e_dst, r_init=r_init)

    def _dispatch_deltas(self, payload, hop_times, windows, r_init=None):
        base, deltas_e, deltas_v = payload
        base, h0 = self._delta_base_args(base)
        return self._run_delta(lambda: run_columns_delta(
            "pagerank", self.tables, base, deltas_e, deltas_v,
            hop_times, windows,
            algo_args=(float(self.damping), float(self.tol),
                       int(self.max_steps)),
            e_src_dev=self._e_src, e_dst_dev=self._e_dst, r_init=r_init,
            h0_delta=h0, ship_counter=self._count_ship))


class HopBatchedBFS(_HopBatched):
    """Windowed BFS hop counting over a full sweep in one call; distances
    are f32 with inf for unreached (SSSP-with-unit-weights semantics)."""

    supports_delta_fold = True
    supports_epoch_warm = True   # min-merge seed (gate: _bfs_columns)

    def __init__(self, log: EventLog, seeds, directed: bool = False,
                 max_steps: int = 100):
        super().__init__(log)
        self._seeds = tuple(seeds)
        self.directed = directed
        self.max_steps = max_steps
        # seeds are fixed per engine: upload the dense seed mask once so
        # chunked/resident sweeps don't re-ship an n_pad bool per dispatch
        self._seed_dev = None

    @property
    def seeds(self):
        """Seed vertex ids — fixed at construction (the device seed mask
        is cached; build a new engine for different seeds)."""
        return self._seeds

    def _forget_tables(self) -> None:
        super()._forget_tables()
        self._seed_dev = None   # the seeds' rows moved

    @property
    def _seed(self):
        if self._seed_dev is None:
            self._seed_dev = jnp.asarray(_seed_mask(self.tables,
                                                    self.seeds))
        return self._seed_dev

    def _dispatch_cols(self, cols, hop_times, windows, r_init=None):
        assert r_init is None   # guarded by supports_warm_start
        return run_bfs_columns(
            self.tables, *cols, hop_times, windows, self.seeds,
            directed=self.directed, max_steps=self.max_steps,
            e_src_dev=self._e_src, e_dst_dev=self._e_dst)

    def _dispatch_deltas(self, payload, hop_times, windows, r_init=None):
        # r_init is the cross-epoch warm seed (min-merged distances);
        # validity is gated by the caller (_bfs_columns docstring)
        base, deltas_e, deltas_v = payload
        base, h0 = self._delta_base_args(base)
        return self._run_delta(lambda: run_columns_delta(
            "bfs", self.tables, base, deltas_e, deltas_v,
            hop_times, windows,
            algo_args=(int(self.max_steps), bool(self.directed)),
            seed_mask=self._seed, r_init=r_init,
            e_src_dev=self._e_src, e_dst_dev=self._e_dst, h0_delta=h0,
            ship_counter=self._count_ship))


class HopBatchedSSSP(HopBatchedBFS):
    """Weighted min-plus traversal over a full sweep in one call.

    Per-pair weights are the LATEST numeric value of ``weight_prop`` at
    each hop (``_materialise_prop`` semantics incl. the (time, event-row)
    tie-break, ``snapshot.py``), folded incrementally into hop-major
    ``[H, m_pad]`` columns next to the alive/lat columns; pairs that never
    set the key weigh 1.0 (``SSSP.message``'s NaN rule). Immutable keys
    (earliest-wins) are refused — the ascending fold is last-wins."""

    supports_delta_fold = True   # weights rebuild on device too
    supports_growth = False      # the weight stream holds pair positions
    #: a weight update can RAISE a pair's weight — old distances become
    #: stale under-estimates, so SSSP never takes a cross-epoch seed
    supports_epoch_warm = False

    #: the weight fold advances a SEQUENTIAL cursor over the sorted
    #: update stream — chunk folds cannot fork it independently yet
    supports_parallel_fold = False

    def _fold_token(self):
        # weighted payloads carry per-pair weight state — never share a
        # cache entry with the weightless engines (or other weight keys)
        return ("sssp", self.weight_prop, bool(self.directed))

    def host_column_bytes(self, n_hops: int) -> int:
        extra = self.tables.m_pad * 4   # weight base (delta path)
        if not self._use_delta_fold():
            extra = n_hops * self.tables.m_pad * 4   # [H, m_pad] f32 cols
        return super().host_column_bytes(n_hops) + extra

    def device_mask_bytes(self, n_cols: int) -> int:
        # the kernel holds a persistent [m_pad, C] f32 ew next to the
        # bool masks — 4 extra bytes per (pair, column)
        return (super().device_mask_bytes(n_cols)
                + self.tables.m_pad * n_cols * 4)

    def __init__(self, log: EventLog, seeds, weight_prop: str,
                 directed: bool = False, max_steps: int = 100):
        super().__init__(log, seeds, directed=directed, max_steps=max_steps)
        log = self.sw.log
        if weight_prop in log.props._key_ids \
                and log.props.is_immutable(log.props._key_ids[weight_prop]):
            raise ValueError(
                f"{weight_prop!r} is an immutable (earliest-wins) key — "
                "the incremental weight fold is last-wins; use the "
                "per-view path")
        self.weight_prop = weight_prop
        t = self.tables
        # all numeric rows of the key on EDGE_ADD events, sorted by
        # (time, event-row) — the same order _materialise_prop's lexsort
        # picks "latest" from — plus a running per-pair state row
        self._w_state = np.ones(t.m_pad, np.float32)
        if weight_prop in log.props._key_ids:
            kid = log.props._key_ids[weight_prop]
            pe = log.props.column("event")
            sel = ((log.props.column("key") == kid)
                   & (log.props.column("tag") == log.props.NUM_TAG))
            ev = pe[sel]
            kinds = log.column("kind")[ev]
            from ..core.events import EDGE_ADD
            ev = ev[kinds == EDGE_ADD]
            val = log.props.column("num")[sel][kinds == EDGE_ADD]
            # stored NaNs weigh 1.0 exactly like missing values
            # (``SSSP.message``'s rule) — raw NaN would poison the whole
            # column through the min-plus relaxation
            val = np.where(np.isnan(val), 1.0, val)
            tt = log.column("time")[ev]
            order = np.lexsort((ev, tt))
            self._w_t = tt[order]
            self._w_val = val[order].astype(np.float32)
            enc = self.sw._pack(self.sw._dense(log.column("src")[ev]),
                                self.sw._dense(log.column("dst")[ev]))
            self._w_pos = t.eng_pos(enc)[order]
        else:
            self._w_t = np.empty(0, np.int64)
            self._w_val = np.empty(0, np.float32)
            self._w_pos = np.empty(0, np.int64)
        self._w_cursor = 0

    def repin(self) -> str:
        n_old = len(self.sw._t)
        status = super().repin()
        if status != "extended":
            return status
        # extend the sorted weight-update stream with the suffix's
        # props. The consumed prefix [:_w_cursor] is immutable history
        # (times ≤ t_prev); the unconsumed tail re-sorts against the new
        # updates, whose times interleave past the cursor (both are >
        # t_prev — SweepBuilder.repin's watermark guard). A STABLE sort
        # by time alone reproduces the (time, event-row) lexsort order:
        # each block is already in it, and every suffix event row is
        # greater than every pinned one.
        log = self.sw.log
        if self.weight_prop not in log.props._key_ids:
            return "extended"
        kid = log.props._key_ids[self.weight_prop]
        if log.props.is_immutable(kid):
            return "rebuild"   # key turned earliest-wins: __init__ refuses
        pe = log.props.column("event")
        sel = ((pe >= n_old) & (log.props.column("key") == kid)
               & (log.props.column("tag") == log.props.NUM_TAG))
        ev = pe[sel]
        if not len(ev):
            return "extended"
        from ..core.events import EDGE_ADD

        kinds = log.column("kind")[ev]
        val = log.props.column("num")[sel][kinds == EDGE_ADD]
        ev = ev[kinds == EDGE_ADD]
        if not len(ev):
            return "extended"
        val = np.where(np.isnan(val), 1.0, val).astype(np.float32)
        tt = log.column("time")[ev]
        order = np.lexsort((ev, tt))
        enc = self.sw._pack(self.sw._dense(log.column("src")[ev]),
                            self.sw._dense(log.column("dst")[ev]))
        pos = self.tables.eng_pos(enc)
        cur = self._w_cursor
        t_cat = np.concatenate([self._w_t[cur:], tt[order]])
        v_cat = np.concatenate([self._w_val[cur:], val[order]])
        p_cat = np.concatenate([self._w_pos[cur:], pos[order]])
        tail = np.argsort(t_cat, kind="stable")
        self._w_t = np.concatenate([self._w_t[:cur], t_cat[tail]])
        self._w_val = np.concatenate([self._w_val[:cur], v_cat[tail]])
        self._w_pos = np.concatenate([self._w_pos[:cur], p_cat[tail]])
        return "extended"

    def _weight_cols(self, hop_times):
        t = self.tables
        H = len(hop_times)
        W = np.empty((H, t.m_pad), np.float32)
        for j, T in enumerate(hop_times):
            hi = int(np.searchsorted(self._w_t, T, side="right"))
            if hi > self._w_cursor:
                # ascending (time, row) order: last write = latest value
                self._w_state[self._w_pos[self._w_cursor:hi]] = \
                    self._w_val[self._w_cursor:hi]
                self._w_cursor = hi
            W[j] = self._w_state
        return W

    def _fold_columns(self, hop_times, hop_callback=None):
        hop_times, cols = super()._fold_columns(hop_times, hop_callback)
        wcols = self._weight_cols(hop_times)
        self.ship_bytes += wcols.nbytes
        return hop_times, (*cols, wcols)

    def _weight_deltas(self, hop_times, resident: bool = False):
        """Per-hop (pos, val) weight updates + the running state at hop 0
        of this batch — the delta twin of ``_weight_cols``. ``resident``
        mirrors the mask fold's decision: hop 0's catch-up ships as
        delta[0] against the device-held weight state, w_base is None."""
        wd = []
        w_base = None
        for j, T in enumerate(hop_times):
            hi = int(np.searchsorted(self._w_t, T, side="right"))
            pos = self._w_pos[self._w_cursor:hi].astype(np.int32)
            val = self._w_val[self._w_cursor:hi]
            if (j > 0 or resident) and len(pos):
                # last-wins per pair WITHIN the hop: XLA scatter order is
                # undefined for duplicate indices, so the dedup must happen
                # here (the host fold's sequential assignment is last-wins
                # by construction). Hop 0's slice — the bulk of a cold
                # sweep — folds into the base instead, no dedup needed.
                u_last = np.unique(pos[::-1], return_index=True)[1]
                sel = np.sort(len(pos) - 1 - u_last)
                pos, val = pos[sel], val[sel]
            if hi > self._w_cursor:
                self._w_state[self._w_pos[self._w_cursor:hi]] = \
                    self._w_val[self._w_cursor:hi]
                self._w_cursor = hi
            if j == 0 and not resident:
                # updates at/before hop 0 belong to the base
                w_base = self._w_state.copy()
                wd.append((pos[:0], val[:0]))
            else:
                wd.append((pos, val))
        return w_base, wd

    def _fold_deltas(self, hop_times, hop_callback=None,
                     assume_resident: bool = False):
        hop_times, payload = super()._fold_deltas(hop_times, hop_callback,
                                                  assume_resident)
        # payload[0] is None exactly when the mask fold went all-delta
        # against the device-resident base — the weight fold must match
        return hop_times, (*payload,
                           *self._weight_deltas(hop_times,
                                                resident=payload[0] is None))

    def _dispatch_cols(self, cols, hop_times, windows, r_init=None):
        assert r_init is None   # guarded by supports_warm_start
        *base, wcols = cols
        return run_bfs_columns(
            self.tables, *base, hop_times, windows, self.seeds,
            directed=self.directed, max_steps=self.max_steps,
            e_src_dev=self._e_src, e_dst_dev=self._e_dst,
            weight_cols=wcols)

    def _dispatch_deltas(self, payload, hop_times, windows, r_init=None):
        # never warm-started: a weight update can RAISE a pair's weight,
        # making old distances stale under-estimates (_bfs_columns
        # docstring) — the live engine always iterates SSSP cold
        assert r_init is None
        base, deltas_e, deltas_v, w_base, w_deltas = payload
        base, h0 = self._delta_base_args(base)
        if h0:
            w_base = self._dev_base[4]   # device-resident weight state
        return self._run_delta(lambda: run_columns_delta(
            "bfs", self.tables, base, deltas_e, deltas_v, hop_times,
            windows, algo_args=(int(self.max_steps), bool(self.directed)),
            seed_mask=self._seed,
            e_src_dev=self._e_src, e_dst_dev=self._e_dst,
            weight_base=w_base, weight_deltas=w_deltas, h0_delta=h0,
            ship_counter=self._count_ship))


class HopBatchedCC(_HopBatched):
    """Windowed connected components over a full hop sweep in one call;
    labels decode via ``tables.uv[label]`` (min vid of the component)."""

    supports_delta_fold = True
    supports_epoch_warm = True   # min-merge seed (gate: _cc_columns)

    def __init__(self, log: EventLog, max_steps: int = 100):
        super().__init__(log)
        self.max_steps = max_steps

    def _dispatch_deltas(self, payload, hop_times, windows, r_init=None):
        # r_init is the cross-epoch warm seed (min-merged labels);
        # validity is gated by the caller (_cc_columns docstring)
        base, deltas_e, deltas_v = payload
        base, h0 = self._delta_base_args(base)
        return self._run_delta(lambda: run_columns_delta(
            "cc", self.tables, base, deltas_e, deltas_v,
            hop_times, windows, algo_args=(int(self.max_steps),),
            r_init=r_init,
            e_src_dev=self._e_src, e_dst_dev=self._e_dst, h0_delta=h0,
            ship_counter=self._count_ship))

    def _dispatch_cols(self, cols, hop_times, windows, r_init=None):
        assert r_init is None   # guarded by supports_warm_start
        return run_cc_columns(
            self.tables, *cols, hop_times, windows,
            max_steps=self.max_steps,
            e_src_dev=self._e_src, e_dst_dev=self._e_dst)


class HopBatchedCDLP(_HopBatched):
    """Windowed CDLP (LDBC Graphalytics community detection, a fixed
    number of rounds) over a full hop sweep in one call; labels decode
    via ``tables.uv[label]``. Delta-fed only: the masks are rebuilt on
    the device, there is no host-column variant to fall back to. No warm
    start of either kind — a warm round is another computation."""

    supports_delta_fold = True

    def __init__(self, log: EventLog, max_steps: int = 10):
        super().__init__(log)
        self.max_steps = max_steps

    def _use_delta_fold(self) -> bool:
        return True

    def _dispatch_deltas(self, payload, hop_times, windows, r_init=None):
        assert r_init is None   # neither warm channel is declared
        base, deltas_e, deltas_v = payload
        base, h0 = self._delta_base_args(base)
        led = _ledger.current()
        if led is not None:
            # rows handed to the mode's sort: both directions of every
            # pair row, every column, every round — from the dispatch's
            # shapes, no device read-back
            led.count_mode_rows(2 * self.tables.m_pad * len(hop_times)
                                * len(windows) * int(self.max_steps))
        return self._run_delta(lambda: run_columns_delta(
            "cdlp", self.tables, base, deltas_e, deltas_v,
            hop_times, windows, algo_args=(int(self.max_steps),),
            e_src_dev=self._e_src, e_dst_dev=self._e_dst, h0_delta=h0,
            ship_counter=self._count_ship))


class HopBatchedLCC(_HopBatched):
    """Windowed local clustering coefficient (LDBC Graphalytics LCC) over
    a full hop sweep in one call: per column ``[2, n_pad]`` int32, ``tri``
    (the directed pairs among a vertex's neighbours) and ``deg`` (its
    undirected neighbours), in the global dense space. One pass over the
    log's triangle table (``ops/triangles``), which this engine asks the
    log's index for — built on the first ask, resident on the device with
    the pair table. Delta-fed only; nothing to warm-start."""

    supports_delta_fold = True
    supports_growth = False      # the triangle table is the old pairs'

    def __init__(self, log: EventLog):
        super().__init__(log)
        #: ``triangles_status``: "built" (this engine's build made the
        #: table) or "held" (the log's index had it) — ``engine.build``'s
        #: ``triangles`` attribute
        self.triangles, self.triangles_status = log_triangles(
            log, self.tables)

    def _use_delta_fold(self) -> bool:
        return True

    def _dispatch_deltas(self, payload, hop_times, windows, r_init=None):
        assert r_init is None   # neither warm channel is declared
        base, deltas_e, deltas_v = payload
        base, h0 = self._delta_base_args(base)
        tt = self.triangles
        columns = len(hop_times) * len(windows)
        led = _ledger.current()
        if led is not None:
            # triangle rows the dispatch walks, padding included, for each
            # of its columns — from the shapes, no device read-back
            led.count_triangle_rows(tt.walked_rows * columns)
        return self._run_delta(lambda: run_columns_delta(
            "lcc", self.tables, base, deltas_e, deltas_v,
            hop_times, windows, algo_args=(int(tt.tile_edges),),
            e_src_dev=self._e_src, e_dst_dev=self._e_dst, h0_delta=h0,
            ship_counter=self._count_ship,
            static_tables=_device_triangles(self._log, tt)))


class HopBatchedSGC(_HopBatched):
    """Windowed SGC feature propagation (``algorithms/propagation.SGC``:
    ``Y = S^K X``, a row of ``dim`` features a vertex) over a full hop
    sweep in one call. A column is an ``[n_pad, dim]`` block, so the
    columns of a dispatch are walked over one feature block, one
    propagation table and one accumulator (``ops/propagate``), which
    this engine keeps resident per log beside the pair table; per column
    the result is ``ops/propagate.summarise``'s small pytree (leading
    axis the columns, hop-major), never the block. Delta-fed only;
    nothing to warm-start."""

    supports_delta_fold = True
    supports_growth = False      # feature block, propagation table

    def __init__(self, log: EventLog, rounds: int = 2, dim: int = 602,
                 feature_seed: int = 0):
        super().__init__(log)
        self.rounds, self.dim = int(rounds), int(dim)
        self.feature_seed = int(feature_seed)
        #: ``features_status``: "built" (this engine's build made the
        #: log's feature block or propagation table) or "held" —
        #: ``engine.build``'s ``features`` attribute
        self._features, self._table, self.features_status = \
            _device_features(log, self.tables, self.dim, self.feature_seed)

    def _use_delta_fold(self) -> bool:
        return True

    def _row_bytes(self) -> int:
        """Device bytes of one vertex's ``dim`` float32 features, padded
        to whole 128-lane tiles."""
        return -(-self.dim // 128) * 512

    def device_mask_bytes(self, n_cols: int) -> int:
        """The masks and what a dispatch holds whatever its columns, four
        ``[n_pad, dim]`` blocks: the features, a round's two states and
        the accumulator."""
        return super().device_mask_bytes(n_cols) \
            + 4 * self.tables.n_pad * self._row_bytes()

    def dispatch_columns_ok(self, C: int) -> bool:
        """Always: the columns are walked, so nothing F-wide grows with
        C — a dispatch holds the feature block, a round's two states,
        the accumulator and one gathered step whatever it serves
        (``device_mask_bytes``; the step is a constant of the table's
        size, ``ops/propagate.step_rows``, which no budget sizes). A
        Range is one dispatch."""
        return True

    def count_result(self, out) -> None:
        """``device.feature_rows``: the F-wide rows the dispatch's
        columns moved — each column's ``walked`` rows of ``A + A^T + I``
        once a round, as the device counted them."""
        led = _ledger.current()
        if led is not None:
            led.count_feature_rows(self.rounds * int(
                np.sum(out["walked"], dtype=np.int64)))

    def _dispatch_deltas(self, payload, hop_times, windows, r_init=None):
        assert r_init is None   # neither warm channel is declared
        base, deltas_e, deltas_v = payload
        base, h0 = self._delta_base_args(base)
        return self._run_delta(lambda: run_columns_delta(
            "sgc", self.tables, base, deltas_e, deltas_v,
            hop_times, windows, algo_args=(self.rounds,),
            e_src_dev=self._e_src, e_dst_dev=self._e_dst, h0_delta=h0,
            ship_counter=self._count_ship,
            static_tables=(self._features, *self._table)))


def _dispatch_columns(runner, tables, cols, hop_of_col, T_col,
                      w_col, e_src_dev, e_dst_dev, *extra,
                      combine: str = "scatter", gather_pack: int = 1):
    """Shared device dispatch for the columnar runners (`extra` appends
    runner-specific trailing args, e.g. the BFS seed mask; ``combine`` is
    how the program combines at the destination). The payload —
    on the host-column path the [H, m_pad] fold columns, the largest
    per-dispatch ship in the system — goes through the pipelined transfer
    engine: array k+1 stages while k is on the wire, per-slice retry."""
    from ..utils.transfer import shared_engine

    with TRACER.span("hop.compute", cols=int(len(T_col)), combine=combine,
                        gather_pack=gather_pack):
        return runner(*shared_engine().put_many([
            e_src_dev if e_src_dev is not None else tables.e_src,
            e_dst_dev if e_dst_dev is not None else tables.e_dst,
            *cols, hop_of_col, T_col, w_col, *extra]))


def _column_layout(hop_times, windows):
    """Hop-major (hop 0's windows first) column layout shared by every
    columnar runner — the ONE place the ordering is defined."""
    H = len(hop_times)
    wlist = normalize_windows(windows)
    hop_of_col = np.repeat(np.arange(H, dtype=np.int32), len(wlist))
    T_col = np.asarray([int(x) for x in hop_times], np.int64)[hop_of_col]
    w_col = np.asarray(wlist * H, np.int64)
    return H, H * len(wlist), hop_of_col, T_col, w_col


def stack_grids(grids):
    """Multi-REQUEST column stacking: merge per-request ``(hop_times,
    windows)`` grids into ONE dispatch grid — the serving scheduler's
    entry point into the columnar engines (jobs/scheduler.py).

    Concurrent requests over the same log and algorithm family differ
    only in WHICH (hop, window) views they want; each view is one column
    of a columnar dispatch, so the batch grid is simply the cross
    product of the hop union and the window union — a superset of every
    member's own grid (extra cells are the coalescing overhead the
    scheduler's column cap bounds). Returns ``(hops, wlist, cols)``:

    * ``hops`` — ascending union of all hop times (ints, deduplicated);
    * ``wlist`` — union of the normalized windows (``None`` → -1, the
      engine convention), first-seen order, deduplicated;
    * ``cols`` — per request, the flat column indices of ITS cells in
      the batch result (hop-major ``_column_layout`` order), listed hops
      ascending × that request's own window order — exactly the order a
      serial per-request dispatch would have emitted them in, so the
      demux is an index gather, never a re-sort.
    """
    hops = sorted({int(t) for ts, _ in grids for t in ts})
    wlist: list[int] = []
    for _, ws in grids:
        for w in normalize_windows(ws):
            if w not in wlist:
                wlist.append(w)
    W = len(wlist)
    hop_idx = {t: j for j, t in enumerate(hops)}
    cols = []
    for ts, ws in grids:
        nws = [wlist.index(w) for w in normalize_windows(ws)]
        cols.append([hop_idx[int(t)] * W + i
                     for t in sorted({int(x) for x in ts}) for i in nws])
    return hops, wlist, cols


def run_columns(tables, e_lat, e_alive, v_lat, v_alive, hop_times, windows,
                *, damping: float = 0.85, tol: float = 1e-7,
                max_steps: int = 20, e_src_dev=None, e_dst_dev=None,
                r_init=None):
    """Dispatch the columnar PageRank over prebuilt per-hop fold columns —
    shared by the incremental-fold class above, the serving scheduler and
    the mesh route. `tables` needs the GlobalTables surface (n_pad, m_pad,
    e_src, e_dst, tdtype). ``r_init``
    (the previous chunk's full ``[C, n_pad]`` hop-major output, device)
    warm-starts the power iteration: the kernel slices its last hop's W
    rows and tiles them per hop IN-PROGRAM — see ``_compiled``."""
    H, C, hop_of_col, T_col, w_col = _column_layout(hop_times, windows)
    tile_budget = _tile_budget_bytes()
    runner = _compiled(tables.n_pad, tables.m_pad, H, C, float(damping),
                       float(tol), int(max_steps),
                       np.dtype(tables.tdtype).name, r_init is not None,
                       tile_budget)
    extra = () if r_init is None else (r_init,)
    return _dispatch_columns(runner, tables,
                             (e_lat, e_alive, v_lat, v_alive),
                             hop_of_col, T_col, w_col, e_src_dev, e_dst_dev,
                             *extra, combine=_combine_route(
                                 tables.m_pad, C, tile_budget),
                             gather_pack=_pagerank_pack(
                                 tables.n_pad, tables.m_pad, C, tile_budget))
