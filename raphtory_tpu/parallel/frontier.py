"""Sparse frontier collectives — the third comm route (``comm="sparse"``).

The two existing routes ship state sized by the GRAPH every superstep:
``all_gather`` replicates the whole vertex column, ``halo`` the whole
referenced remote set — both pay the same bill on superstep 30 of a BFS
whose frontier has collapsed to a handful of vertices. This route ships
state sized by the FRONTIER instead, the "Sparse Allreduce" recipe
(PAPERS.md: exchange only the nonzero slices of power-law-distributed
data) fused with "Node Aware SpMV"'s locality rule (aggregate on the
node before crossing the expensive link):

* Each process runs one jitted superstep over its FULL state replica
  ``[k, n_pad]`` using only the edge blocks of the vertex shards it
  owns. Edges are partitioned by destination (and by source for the
  in-direction), so a row's complete aggregate is computed entirely by
  its owner — the per-process kernel IS the node-aware pre-aggregation
  stage: contributions from every locally-owned shard and both edge
  directions min-merge on the host's device before anything reaches DCN.
* The changed-since-last-superstep rows are compacted host-side into a
  ``(indices, values)`` slice, padded to a bucketed power-of-two length
  (:func:`frontier_bucket`, floor ``RTPU_SPARSE_BUCKETS``) so
  the ``process_allgather`` shape set stays bounded — no compile storm
  as the frontier grows and collapses (rtpulint RT013 discipline for
  collective shapes).
* One tiny uniform counts-allgather per superstep agrees the global
  bucket length and the halting vote, then the compact slices allgather
  and scatter-merge (elementwise min) into every replica. Monotonicity
  makes the merge exact: ``min(stale, owner_new) == owner_new``, so the
  merged replica is BITWISE the dense route's state (the equivalence
  contract tests/test_sparse_route.py pins across process counts).
* When the measured global frontier density crosses the dense crossover
  (slot bytes ≈ 3x raw row bytes), the bucket ladder tops out at the
  dense slice — the fallback is structural, and the superstep is counted
  in ``fallback_supersteps`` so the route chooser sees it.

Eligibility is the ``VertexProgram.monotone_min`` contract (single min
state leaf, update = masked min, votes == unchanged — see
engine/program.py); everything else stays on the dense routes.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from ..engine.bsp import _merge_aggs
from ..engine.program import Context, Edges, VertexProgram
from ..obs import ledger as _ledger
from ..ops.segment import segment_combine

#: global frontier density past which a sparse slot (index + value) moves
#: more bytes than the dense row it encodes — supersteps above it count
#: as fallback supersteps in the dispatch accounting (docs/COMM.md
#: "crossover model")
CROSSOVER_DENSITY = 1.0 / 3.0

#: cold-start density prior the route chooser uses before any measured
#: history exists for an (algorithm, window-batch) key — frontier
#: algorithms are sparse by construction, so the first auto dispatch
#: goes sparse and measures itself
PRIOR_DENSITY = 0.05


#: default floor for sparse-frontier slice buckets (slots). Small enough
#: that a near-quiescent superstep ships ~KBs; large enough that the
#: power-of-two ladder above it has only ~log2(n/floor) rungs, so the
#: collective shape set — and with it the process_allgather compile-key
#: set — stays bounded (docs/COMM.md "bucketed padding").
SPARSE_BUCKET_FLOOR = 256


def sparse_bucket_floor() -> int:
    """Resolved ``RTPU_SPARSE_BUCKETS`` (slot floor for frontier-slice
    buckets). Read HERE, at dispatch time, by the sparse comm route —
    never from inside a compiled-program cache factory (rtpulint RT001);
    the resolved bucket length reaches collective shapes as an argument."""
    import os

    try:
        v = int(os.environ.get("RTPU_SPARSE_BUCKETS", SPARSE_BUCKET_FLOOR))
    except ValueError:
        v = SPARSE_BUCKET_FLOOR
    return max(8, v)


def frontier_bucket(count: int, floor: int | None = None,
                    cap: int | None = None) -> int:
    """Bucketed capacity for a compacted frontier slice: the smallest
    power of two >= ``count``, floored at ``floor`` slots (default: the
    resolved ``RTPU_SPARSE_BUCKETS``), so every frontier size in a
    power-of-two band reuses one collective shape. ``cap`` (when given)
    bounds the bucket from above — the dense-slice size, past which
    padding buys nothing."""
    floor = sparse_bucket_floor() if floor is None else max(1, int(floor))
    b = floor
    while b < count:
        b <<= 1
    if cap is not None:
        b = min(b, max(int(cap), 1))
    return b


def supported(program: VertexProgram) -> bool:
    """Sparse-route eligibility: the program declares the monotone
    min-merge contract (engine/program.py ``monotone_min``)."""
    return (bool(getattr(program, "monotone_min", False))
            and program.combiner == "min")


def _min_identity(dtype):
    dt = np.dtype(dtype)
    if dt.kind == "f":
        return np.asarray(np.inf, dt)
    return np.asarray(np.iinfo(dt).max, dt)


def owned_shards(mesh) -> list[int]:
    """Vertex shards this process owns on ``mesh``. Ownership is the
    process of the shard's first device along the other mesh axes — one
    owner per shard even when a window axis spans processes, so exactly
    one process computes (and publishes) each row's update."""
    from .sharded import V_AXIS

    vi = list(mesh.axis_names).index(V_AXIS)
    devs = np.moveaxis(np.asarray(mesh.devices), vi, -1)
    devs = devs[(0,) * (devs.ndim - 1)]
    me = jax.process_index()
    return [s for s in range(devs.shape[0])
            if devs[s].process_index == me]


@functools.lru_cache(maxsize=128)
def _frontier_runner(program: VertexProgram, k: int, n_pad: int,
                     m_d: int, m_s: int, prop_keys: tuple,
                     vprop_keys: tuple):
    """Compiled pieces of the sparse route for (algorithm, shapes): one
    init, one SINGLE-superstep kernel (the multi-process host loop
    drives supersteps — frontier compaction happens between dispatches),
    one whole-sweep while_loop kernel (the single-process fast path),
    one finalize. Frontier SIZES never reach these shapes, so the
    compile-key set per algorithm is exactly these four entries (the
    compile-ring stability tests/test_sparse_route.py pins)."""
    label = type(program).__name__

    def _flat_ids(idx):
        woffs = (jnp.arange(k, dtype=jnp.int32) * n_pad)[:, None]
        return (idx[None, :] + woffs).reshape(-1)

    def _tile(a, m):
        return jnp.broadcast_to(a[None, :], (k,) + a.shape).reshape(
            (k * m,) + a.shape[1:])

    def _degrees(d_dst, d_masks, s_src, s_masks):
        in_deg = segment_combine(
            jnp.ones((k * m_d,), jnp.int32), _flat_ids(d_dst),
            k * n_pad, "sum", d_masks.reshape(-1),
            True).reshape(k, n_pad)
        out_deg = segment_combine(
            jnp.ones((k * m_s,), jnp.int32), _flat_ids(s_src),
            k * n_pad, "sum", s_masks.reshape(-1),
            True).reshape(k, n_pad)
        return in_deg, out_deg

    def _mk_ctx(kk, step, v_masks, vids, v_latest, v_first,
                in_deg, out_deg, vprops, time, windows):
        # the GLOBAL context: full replica, offset 0, no mesh axis — the
        # cross-shard reductions the sharded runner psums are plain sums
        # here because every row is present
        return Context(
            n=n_pad, time=time, window=windows[kk], v_mask=v_masks[kk],
            vids=vids, v_latest_time=v_latest, v_first_time=v_first,
            out_deg=out_deg[kk], in_deg=in_deg[kk],
            n_active=jnp.sum(v_masks[kk].astype(jnp.int32)),
            step=step, vprops=vprops, v_offset=jnp.int32(0),
            axis_name=None)

    def init_fn(v_masks, vids, v_latest, v_first,
                d_dst, d_masks, s_src, s_masks, vprops, time, windows):
        in_deg, out_deg = _degrees(d_dst, d_masks, s_src, s_masks)

        def init_k(kk):
            return program.init(_mk_ctx(
                kk, jnp.int32(0), v_masks, vids, v_latest, v_first,
                in_deg, out_deg, vprops, time, windows))

        return jax.vmap(init_k)(jnp.arange(k))

    def _superstep(state, owned, v_masks, vids, v_latest, v_first,
                   d_src, d_dst, d_masks, d_time, d_first, d_props,
                   s_dst, s_src, s_masks, s_time, s_first, s_props,
                   vprops, time, windows, step, in_deg, out_deg):
        dm, sm = d_masks.reshape(-1), s_masks.reshape(-1)
        state_flat = jax.tree_util.tree_map(
            lambda a: a.reshape((k * n_pad,) + a.shape[2:]), state)

        def gather(ids):
            return jax.tree_util.tree_map(lambda a: a[ids], state_flat)

        agg = None
        if program.direction in ("out", "both"):
            edges = Edges(src=_tile(d_src, m_d), dst=_tile(d_dst, m_d),
                          mask=dm, time=_tile(d_time, m_d),
                          first_time=_tile(d_first, m_d),
                          props={p: _tile(d_props[p], m_d)
                                 for p in prop_keys},
                          step=step)
            payload = program.message(gather(_flat_ids(d_src)), edges)
            agg = jax.tree_util.tree_map(
                lambda x: segment_combine(
                    x, _flat_ids(d_dst), k * n_pad, program.combiner, dm,
                    indices_are_sorted=True,
                ).reshape((k, n_pad) + x.shape[1:]), payload)
        if program.direction in ("in", "both"):
            edges = Edges(src=_tile(s_src, m_s), dst=_tile(s_dst, m_s),
                          mask=sm, time=_tile(s_time, m_s),
                          first_time=_tile(s_first, m_s),
                          props={p: _tile(s_props[p], m_s)
                                 for p in prop_keys},
                          step=step)
            payload = program.message(gather(_flat_ids(s_dst)), edges)
            agg_in = jax.tree_util.tree_map(
                lambda x: segment_combine(
                    x, _flat_ids(s_src), k * n_pad, program.combiner, sm,
                    indices_are_sorted=True,
                ).reshape((k, n_pad) + x.shape[1:]), payload)
            agg = agg_in if agg is None else _merge_aggs(
                program.combiner, agg, agg_in)

        def upd_k(kk, stk, aggk):
            new_st, votes = program.update(stk, aggk, _mk_ctx(
                kk, step, v_masks, vids, v_latest, v_first,
                in_deg, out_deg, vprops, time, windows))
            # non-owned rows belong to their owners' kernels: keep the
            # replica's merged value no matter what update produced (a
            # monotone program leaves them fixed anyway — this makes the
            # ownership boundary structural, not behavioural)
            new_st = jax.tree_util.tree_map(
                lambda new, old: jnp.where(
                    owned.reshape((n_pad,) + (1,) * (new.ndim - 1)),
                    new, old),
                new_st, stk)
            unhalted = jnp.sum(
                ((~(votes | ~v_masks[kk])) & owned).astype(jnp.int32))
            return new_st, unhalted

        new_state, unhalted_k = jax.vmap(upd_k, in_axes=(0, 0, 0))(
            jnp.arange(k), state, agg)
        changed = jnp.zeros((k, n_pad), bool)
        for new, old in zip(jax.tree_util.tree_leaves(new_state),
                            jax.tree_util.tree_leaves(state)):
            diff = new != old
            if diff.ndim > 2:
                diff = jnp.any(diff, axis=tuple(range(2, diff.ndim)))
            changed = changed | diff
        changed = changed & owned[None, :]
        return new_state, changed, jnp.sum(unhalted_k)

    def step_fn(state, owned, v_masks, vids, v_latest, v_first,
                d_src, d_dst, d_masks, d_time, d_first, d_props,
                s_dst, s_src, s_masks, s_time, s_first, s_props,
                vprops, time, windows, step):
        in_deg, out_deg = _degrees(d_dst, d_masks, s_src, s_masks)
        return _superstep(
            state, owned, v_masks, vids, v_latest, v_first,
            d_src, d_dst, d_masks, d_time, d_first, d_props,
            s_dst, s_src, s_masks, s_time, s_first, s_props,
            vprops, time, windows, step, in_deg, out_deg)

    def sweep_fn(state, owned, v_masks, vids, v_latest, v_first,
                 d_src, d_dst, d_masks, d_time, d_first, d_props,
                 s_dst, s_src, s_masks, s_time, s_first, s_props,
                 vprops, time, windows):
        # the SINGLE-process whole-sweep kernel: with one participating
        # process there is no exchange between supersteps, so the host
        # loop (one dispatch + device sync per superstep) collapses into
        # the dense route's while_loop — dispatch parity with all_gather
        # — while the per-superstep changed counts still come back for
        # the frontier accounting. Same _superstep body as the multi
        # path, so results stay bitwise identical.
        in_deg, out_deg = _degrees(d_dst, d_masks, s_src, s_masks)

        def body(carry):
            st, step, _, counts = carry
            new_state, changed, unhalted = _superstep(
                st, owned, v_masks, vids, v_latest, v_first,
                d_src, d_dst, d_masks, d_time, d_first, d_props,
                s_dst, s_src, s_masks, s_time, s_first, s_props,
                vprops, time, windows, step, in_deg, out_deg)
            counts = counts.at[step].set(
                jnp.sum(changed, dtype=jnp.int32))
            return (new_state, step + jnp.int32(1),
                    unhalted.astype(jnp.int32), counts)

        def cond(carry):
            _, step, unh, _ = carry
            return (step < program.max_steps) & (unh > 0)

        carry = (state, jnp.int32(0), jnp.int32(1),
                 jnp.zeros((max(1, program.max_steps),), jnp.int32))
        st, steps, _, counts = jax.lax.while_loop(cond, body, carry)
        return st, steps, counts

    def finalize_fn(state, v_masks, vids, v_latest, v_first,
                    d_dst, d_masks, s_src, s_masks, vprops, time,
                    windows, steps):
        in_deg, out_deg = _degrees(d_dst, d_masks, s_src, s_masks)

        def fin_k(kk, st):
            return program.finalize(st, _mk_ctx(
                kk, steps, v_masks, vids, v_latest, v_first,
                in_deg, out_deg, vprops, time, windows))

        return jax.vmap(fin_k, in_axes=(0, 0))(jnp.arange(k), state)

    return {
        "init": _ledger.instrument(f"frontier.init.{label}",
                                   jax.jit(init_fn)),
        "step": _ledger.instrument(f"frontier.superstep.{label}",
                                   jax.jit(step_fn)),
        "sweep": _ledger.instrument(f"frontier.sweep.{label}",
                                    jax.jit(sweep_fn)),
        "finalize": _ledger.instrument(f"frontier.finalize.{label}",
                                       jax.jit(finalize_fn)),
    }


def _flat_blocks(sv, owned, wlist, time):
    """Concatenate the owned shards' edge blocks into flat GLOBAL-index
    arrays + per-window masks. Shard-local sorted dst/src plus ascending
    shard offsets keep the flat segment ids sorted — the
    ``indices_are_sorted`` contract of the combine."""
    n_loc = sv.n_loc
    offs = (np.asarray(owned, np.int64) * n_loc).astype(np.int32)
    sel = list(owned)

    def flat(a):
        return a[sel].reshape(-1)

    d_src = flat(sv.d_src_g)
    d_dst = (sv.d_dst_l[sel] + offs[:, None]).reshape(-1)
    d_mask = flat(sv.d_mask)
    d_time = flat(sv.d_time)
    d_first = flat(sv.d_first)
    s_dst = flat(sv.s_dst_g)
    s_src = (sv.s_src_l[sel] + offs[:, None]).reshape(-1)
    s_mask = flat(sv.s_mask)
    s_time = flat(sv.s_time)
    s_first = flat(sv.s_first)
    d_props = {p: flat(a) for p, a in sv.d_props.items()}
    s_props = {p: flat(a) for p, a in sv.s_props.items()}

    k = len(wlist)
    d_masks = np.empty((k, d_mask.size), bool)
    s_masks = np.empty((k, s_mask.size), bool)
    for i, w in enumerate(wlist):
        if w < 0:
            d_masks[i] = d_mask
            s_masks[i] = s_mask
        else:
            lo = time - w
            d_masks[i] = d_mask & (d_time >= lo)
            s_masks[i] = s_mask & (s_time >= lo)
    return {
        "d_src": d_src, "d_dst": d_dst, "d_masks": d_masks,
        "d_time": d_time, "d_first": d_first, "d_props": d_props,
        "s_dst": s_dst, "s_src": s_src, "s_masks": s_masks,
        "s_time": s_time, "s_first": s_first, "s_props": s_props,
    }


def run_sparse(program: VertexProgram, view, mesh, sv, wlist,
               *, multi: bool, msan=None, msite: str = ""):
    """Host-driven sparse-frontier superstep loop. Returns
    ``(result_tree [k, n_pad, ...], steps, acct)`` with ``acct`` the
    exchange accounting the dispatcher folds into ``CollectiveStats``
    and the ledger ``dcn`` block.

    Every cross-process collective here is SPMD-uniform by construction:
    bucket lengths and halting derive from the allgathered per-process
    counts, never from process-local state (the RT012 pragma-free design
    docs/COMM.md documents)."""
    if not supported(program):
        raise ValueError(
            f"{type(program).__name__} is not sparse-route eligible: "
            "comm='sparse' needs the monotone_min contract "
            "(engine/program.py)")
    k = len(wlist)
    n_pad = int(view.n_pad)
    owned = owned_shards(mesh)
    owned_mask = np.zeros(n_pad, bool)
    for s in owned:
        owned_mask[s * sv.n_loc: (s + 1) * sv.n_loc] = True
    blocks = _flat_blocks(sv, owned, wlist, int(view.time))
    m_d = int(blocks["d_src"].size)
    m_s = int(blocks["s_dst"].size)
    fns = _frontier_runner(
        program, k, n_pad, m_d, m_s, tuple(program.edge_props),
        tuple(program.vertex_props))

    v_mask = np.asarray(view.v_mask).reshape(-1)
    v_latest = np.asarray(view.v_latest_time).reshape(-1)
    v_first = np.asarray(view.v_first_time).reshape(-1)
    v_masks = np.empty((k, n_pad), bool)
    for i, w in enumerate(wlist):
        v_masks[i] = v_mask if w < 0 else v_mask & (v_latest >= (view.time - w))
    vids = np.asarray(view.vids).reshape(-1)
    vprops = {p: np.asarray(view.vertex_prop(p), np.float32).reshape(-1)
              for p in program.vertex_props}
    time = np.asarray(view.time, np.int64)
    windows = np.asarray(wlist, np.int64)

    # device-put every loop-invariant operand ONCE: the superstep kernel
    # redispatches per superstep (the host drives the loop), and passing
    # host arrays would re-transfer the multi-MB edge blocks every step
    put = jax.device_put
    v_masks = put(v_masks)
    vids, v_latest, v_first = put(vids), put(v_latest), put(v_first)
    vprops = {p: put(a) for p, a in vprops.items()}
    blocks = {kk: ({p: put(a) for p, a in vv.items()}
                   if isinstance(vv, dict) else put(vv))
              for kk, vv in blocks.items()}
    owned_dev = put(owned_mask)

    ctx_args = (v_masks, vids, v_latest, v_first,
                blocks["d_dst"], blocks["d_masks"],
                blocks["s_src"], blocks["s_masks"],
                vprops, time, windows)
    state = fns["init"](*ctx_args)
    leaves = jax.tree_util.tree_leaves(state)
    if len(leaves) != 1:
        raise ValueError(
            f"{type(program).__name__}.monotone_min promises a single "
            f"state leaf; init() returned {len(leaves)}")
    state_np = np.asarray(leaves[0])
    treedef = jax.tree_util.tree_structure(state)
    identity = _min_identity(state_np.dtype)
    trailing = state_np.shape[2:]
    trail_items = int(np.prod(trailing, dtype=np.int64)) if trailing else 1
    slot_bytes = 8 + state_np.dtype.itemsize * trail_items
    floor = sparse_bucket_floor()
    n_procs = len({d.process_index for d in mesh.devices.flat})

    steps = 0
    halted = False
    rows_total = 0
    bytes_total = 0
    fallback_steps = 0
    density_sum = 0.0
    barrier_wait = 0.0
    if multi:
        from jax.experimental import multihost_utils
    import time as _time

    state_dev = state     # supersteps stay device-resident between rounds
    if not multi:
        # with one participating process there is no exchange between
        # supersteps, so the whole sweep collapses into a single
        # while_loop dispatch — dispatch parity with the dense route —
        # while the per-superstep changed counts come back for the
        # frontier accounting below
        state_dev, steps_dev, step_counts = fns["sweep"](
            state_dev,
            owned_dev, v_masks, vids, v_latest, v_first,
            blocks["d_src"], blocks["d_dst"], blocks["d_masks"],
            blocks["d_time"], blocks["d_first"], blocks["d_props"],
            blocks["s_dst"], blocks["s_src"], blocks["s_masks"],
            blocks["s_time"], blocks["s_first"], blocks["s_props"],
            vprops, time, windows)
        steps = int(steps_dev)
        for cnt in np.asarray(step_counts)[:steps]:
            cnt = int(cnt)
            # single-process dispatches publish their slice slots too —
            # the bytes THIS superstep would put on DCN, so the route's
            # accounting (and the cluster smoke's nonzero-sparse-bytes
            # assertion) is mesh-size independent
            B = frontier_bucket(cnt, floor, cap=k * n_pad)
            rows_total += B
            bytes_total += B * slot_bytes
            density = cnt / float(k * n_pad)
            density_sum += density
            if density > CROSSOVER_DENSITY:
                fallback_steps += 1
    while multi and steps < program.max_steps and not halted:
        new, changed, unhalted = fns["step"](
            state_dev,
            owned_dev, v_masks, vids, v_latest, v_first,
            blocks["d_src"], blocks["d_dst"], blocks["d_masks"],
            blocks["d_time"], blocks["d_first"], blocks["d_props"],
            blocks["s_dst"], blocks["s_src"], blocks["s_masks"],
            blocks["s_time"], blocks["s_first"], blocks["s_props"],
            vprops, time, windows, np.int32(steps))
        ch = np.asarray(changed).reshape(-1)
        loc_idx = np.flatnonzero(ch)
        cnt = int(loc_idx.size)
        unh = int(unhalted)
        new_np = np.asarray(jax.tree_util.tree_leaves(new)[0])
        flat_new = new_np.reshape((k * n_pad,) + trailing)
        # counts first: ONE uniform agreement round fixes the bucket
        # length and the halting vote for every process — the bucket
        # (hence the slice collective's shape) is a pure function of
        # allgathered data, never of local state
        t_bar = _time.perf_counter()
        watch = (msan.barrier_watch(msite, "sparse")
                 if msan is not None else None)
        try:
            counts = multihost_utils.process_allgather(
                np.asarray([cnt, unh], np.int64))
        finally:
            if watch is not None:
                watch.cancel()
        counts = np.asarray(counts).reshape(-1, 2)
        cmax = int(counts[:, 0].max())
        cglobal = int(counts[:, 0].sum())
        unh_g = int(counts[:, 1].sum())
        B = frontier_bucket(cmax, floor, cap=k * n_pad)
        idx = np.zeros(B, np.int64)
        idx[:cnt] = loc_idx
        val = np.full((B,) + trailing, identity, state_np.dtype)
        val[:cnt] = flat_new[loc_idx]
        watch = (msan.barrier_watch(msite, "sparse")
                 if msan is not None else None)
        try:
            slices = multihost_utils.process_allgather(
                {"idx": idx, "val": val})
        finally:
            if watch is not None:
                watch.cancel()
        barrier_wait += _time.perf_counter() - t_bar
        # scatter-merge every process's slice into the replica —
        # elementwise min, so identity pads and own rows are no-ops
        # and merge order cannot matter
        base = state_np.reshape((k * n_pad,) + trailing).copy()
        np.minimum.at(base,
                      np.asarray(slices["idx"]).reshape(-1),
                      np.asarray(slices["val"]).reshape(
                          (-1,) + trailing))
        state_np = base.reshape((k, n_pad) + trailing)
        state_dev = jax.tree_util.tree_unflatten(
            treedef, [put(state_np)])
        rows_step = B * n_procs
        bytes_step = rows_step * slot_bytes + 16 * n_procs
        density = cglobal / float(k * n_pad)
        density_sum += density
        if density > CROSSOVER_DENSITY:
            fallback_steps += 1
        rows_total += rows_step
        bytes_total += bytes_step
        steps += 1
        halted = unh_g == 0

    result = fns["finalize"](
        state_dev,
        v_masks, vids, v_latest, v_first,
        blocks["d_dst"], blocks["d_masks"],
        blocks["s_src"], blocks["s_masks"],
        vprops, time, windows, np.int32(steps))
    result = jax.tree_util.tree_map(np.asarray, result)
    acct = {
        "rows": rows_total,
        "bytes": bytes_total,
        "supersteps": steps,
        "barrier_wait": barrier_wait,
        "density": (density_sum / steps) if steps else 0.0,
        "fallback_supersteps": fallback_steps,
        "processes": n_procs,
        "owned_shards": len(owned),
    }
    return result, steps, acct
