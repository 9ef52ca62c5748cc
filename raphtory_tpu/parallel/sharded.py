"""Sharded BSP engine: SPMD supersteps over a TPU device mesh.

The distributed design the reference implements with hash-sharded partition
managers + point-to-point actor messages + ack counting
(``Utils.scala:32-47`` sharding, ``EntityStorage`` sync protocol,
``AnalysisTask.scala:197-283`` coordinator) re-expressed the TPU way:

* The padded vertex space is range-partitioned over the mesh's ``vertices``
  axis (contiguous slices — not hash: keeps segment ids sorted per shard).
* Edges are materialised twice, partitioned by DST shard (for out-direction
  combine-at-destination) and by SRC shard (for in-direction) — the analogue
  of the reference's src-copy + ``SplitEdge`` dst-mirror, but immutable, so
  the entire ack/sync dance disappears.
* A superstep moves remote neighbour state over ICI by one of two routes,
  chosen per (graph, mesh) by measured exchange volume (``comm="auto"``):
  - **all_gather**: replicate the (small) per-vertex state along the vertex
    axis — best when most shards reference most of the graph (dense or tiny
    graphs, few shards).
  - **halo exchange**: at partition time each shard records exactly which
    REMOTE vertices its edges reference (the halo — the immutable analogue
    of the reference's ``SplitEdge`` dst-mirrors); each superstep exchanges
    only those rows via one ``all_to_all`` over ICI. O(halo) instead of
    O(|V|) bytes — the SURVEY §2.9 row-4 translation (point-to-point vertex
    messages → collective exchange of referenced remote state).
* Votes/quiescence are a ``psum`` — the reference's coordinator counting
  EndStep acks collapses into one collective (SURVEY §2.9).
* Batched windows ride a second mesh axis (``windows``) — window sweeps are
  embarrassingly parallel, so multi-chip scaling multiplies window throughput
  (the reference's analogue of sequence parallelism, SURVEY §5.7).
* Occurrence (temporal multigraph) programs — TaintTracking et al.
  (``EthereumTaintTracking.scala:93-127``) — shard exactly like deduplicated
  edges: the per-event ``occ_*`` arrays are scattered into dst-/src-
  partitioned blocks with per-occurrence times and props.
"""

from __future__ import annotations

import functools
import os
import threading
import time as _time
from collections import deque
from dataclasses import dataclass

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P

from ..analysis.sanitizer import mesh_active
from ..core.snapshot import GraphView, INT64_MIN
from ..engine.bsp import _elem, _merge_aggs
from ..engine.program import (Context, Edges, VertexProgram,
                              check_custom_direction, custom_exchange,
                              takes_mode_counts)
from ..obs.trace import TRACER
from ..ops.segment import (segment_combine, segment_counts_at,
                           segment_ends_pos)

V_AXIS = "vertices"
W_AXIS = "windows"


def _metrics():
    """obs.metrics bundle, or None when prometheus isn't importable —
    collective telemetry must never make prometheus a hard dependency
    of the compute path."""
    try:
        from ..obs.metrics import METRICS

        return METRICS
    except Exception:
        return None


class CollectiveStats:
    """Process-wide accounting of what the cross-shard exchanges moved —
    the measured evidence ROADMAP item 3's sparse third collective route
    will be chosen against ("Sparse Allreduce": exchange only nonzero
    frontier slices; "Node Aware SpMV": aggregate intra-host before
    crossing DCN — both need per-route volume and skew numbers first).

    Thread-safe (concurrent mesh jobs dispatch from their own job
    threads); surfaced at ``/statusz`` and federated by ``/clusterz``.
    """

    def __init__(self):
        self._lock = threading.Lock()
        self._routes: dict[tuple, dict] = {}
        self._skew: dict | None = None
        self._layout: dict | None = None
        self._skew_builds = 0
        self._skew_refreshes = 0
        # route-chooser evidence: measured frontier density per
        # (algorithm, window-batch) key, and the decision log the
        # /statusz route table renders. Densities come from ALLGATHERED
        # per-process counts, so every process records identical history
        # — the chooser staying SPMD-uniform depends on it (COMM.md)
        self._frontier: dict[str, deque] = {}
        self._route_log: deque = deque(maxlen=64)
        self._route_counts: dict[tuple, int] = {}

    def note_partition(self, skew: dict,
                       layout: dict | None = None) -> None:
        """Record the latest partition build's per-shard skew histogram
        (built at ``partition_view`` time — rebuilds overwrite) and, for a
        log's static partition, its ``layout``: ``shards``, the pair
        ``rows`` of both directions, the ``pad_rows`` the blocks hold
        them in (every shard padded to the fullest one's next power of
        two: ``pad_factor`` = pad_rows / rows is what a superstep pays
        for the skew) and the ``halo_rows`` of the halo route."""
        with self._lock:
            self._skew = skew
            self._layout = layout
            self._skew_builds += 1

    def note_exchange(self, route: str, direction: str, *, rows: int,
                      bytes_: int, seconds: float, supersteps: int,
                      barrier_wait: float = 0.0,
                      async_dispatch: bool = False) -> None:
        """One dispatch's exchange accounting. ``rows``/``bytes_`` are
        totals over devices and (known) supersteps; async dispatches
        can't know their superstep count host-side and account exactly
        one superstep, counted separately so the undercount is visible."""
        with self._lock:
            d = self._routes.setdefault((route, direction), {
                "dispatches": 0, "supersteps": 0, "rows": 0, "bytes": 0,
                "seconds": 0.0, "barrier_wait_seconds": 0.0,
                "async_dispatches": 0})
            d["dispatches"] += 1
            d["supersteps"] += int(supersteps)
            d["rows"] += int(rows)
            d["bytes"] += int(bytes_)
            d["seconds"] += float(seconds)
            d["barrier_wait_seconds"] += float(barrier_wait)
            if async_dispatch:
                d["async_dispatches"] += 1
        m = _metrics()
        if m is not None:
            m.collective_seconds.labels(route, direction).inc(
                max(0.0, float(seconds)))
            m.collective_bytes.labels(route, direction).inc(
                max(0, int(bytes_)))
            m.collective_rows.labels(route, direction).inc(
                max(0, int(rows)))
            if barrier_wait > 0.0:
                m.collective_barrier_wait.labels(route).inc(
                    float(barrier_wait))

    def note_skew_refresh(self, skew: dict) -> None:
        """A post-ingest sampled skew recompute (NOT a partition build):
        replaces the published histogram so the route chooser and the
        advisor's shard-skew rule never read day-1 skew after a large
        ingest suffix shifted the load (docs/COMM.md)."""
        with self._lock:
            self._skew = skew
            self._skew_refreshes += 1

    def note_route_decision(self, decision: dict) -> None:
        """One dispatch's route-chooser verdict + evidence — the
        ``/statusz`` route table's feed (journaled by the dispatcher)."""
        algo = str(decision.get("algorithm", "?"))
        route = str(decision.get("route", "?"))
        with self._lock:
            self._route_log.append(dict(decision))
            key = (algo, route)
            self._route_counts[key] = self._route_counts.get(key, 0) + 1
        m = _metrics()
        if m is not None:
            m.route_decisions.labels(algo, route).inc()

    def note_frontier(self, key: str, density: float,
                      supersteps: int) -> None:
        """Measured mean frontier density of one sparse dispatch, keyed
        by (algorithm, window-batch) — the chooser's crossover input."""
        with self._lock:
            dq = self._frontier.setdefault(key, deque(maxlen=32))
            dq.append((float(density), int(supersteps)))

    def frontier_hint(self, key: str) -> float | None:
        """Mean measured frontier density for ``key`` (None = no
        history; the chooser then uses its cold-start prior)."""
        with self._lock:
            dq = self._frontier.get(key)
            if not dq:
                return None
            return sum(d for d, _ in dq) / len(dq)

    def snapshot(self) -> dict:
        with self._lock:
            routes = {f"{r}/{d}": dict(v)
                      for (r, d), v in sorted(self._routes.items())}
            skew = dict(self._skew) if self._skew else None
            layout = dict(self._layout) if self._layout else None
            builds = self._skew_builds
            refreshes = self._skew_refreshes
            density = {k: round(sum(d for d, _ in dq) / len(dq), 6)
                       for k, dq in sorted(self._frontier.items()) if dq}
            table = {
                "counts": {f"{a}/{r}": n for (a, r), n
                           in sorted(self._route_counts.items())},
                "recent": [dict(d) for d in list(self._route_log)[-8:]],
            }
        for v in routes.values():
            v["seconds"] = round(v["seconds"], 6)
            v["barrier_wait_seconds"] = round(
                v["barrier_wait_seconds"], 6)
        return {"routes": routes, "skew": skew, "partition": layout,
                "skew_builds": builds,
                "skew_refreshes": refreshes,
                "frontier_density": density, "route_table": table}

    def clear(self) -> None:
        with self._lock:
            self._routes.clear()
            self._skew = None
            self._layout = None
            self._skew_builds = 0
            self._skew_refreshes = 0
            self._frontier.clear()
            self._route_log.clear()
            self._route_counts.clear()


#: process-wide collective accounting every mesh dispatch records into
COLLECTIVES = CollectiveStats()


def shard_skew(**kinds) -> dict:
    """Per-shard row-count skew summary: for each named kind (an array of
    per-shard counts), the per-shard histogram plus max/mean — the
    power-law imbalance signal. ``skew`` 1.0 = perfectly balanced."""
    out = {}
    for kind, arr in kinds.items():
        a = np.asarray(arr, np.float64).reshape(-1)
        mean = float(a.mean()) if a.size else 0.0
        mx = float(a.max()) if a.size else 0.0
        out[kind] = {
            "per_shard": [int(x) for x in a],
            "max": int(mx),
            "mean": round(mean, 2),
            "skew": round(mx / mean, 4) if mean > 0 else 1.0,
        }
    return out


def note_partition_skew(skew: dict, layout: dict | None = None) -> None:
    """Publish one partition build's skew histogram: COLLECTIVES (the
    /statusz / /clusterz surface; ``layout``: a static partition's padded
    rows, ``CollectiveStats.note_partition``), the prometheus
    gauges/histograms, and a flight-recorder instant — shared by
    ``partition_view`` and the build of a log's static partition
    (``parallel/sweep.StaticPartition``)."""
    COLLECTIVES.note_partition(skew, layout)
    m = _metrics()
    if m is not None:
        for kind, s in skew.items():
            m.partition_skew.labels(kind).set(s["skew"])
            for rows in s["per_shard"]:
                m.shard_rows.labels(kind).observe(float(rows))
    if TRACER.enabled:
        TRACER.instant("comm.partition",
                       process=TRACER.process_index,
                       **{f"{k}_skew": v["skew"] for k, v in skew.items()})


def sampled_skew(sv, max_cols: int = 1 << 16) -> dict:
    """Cheap post-ingest recompute of the per-shard edge skew from the
    CURRENT block masks (the partition-time histogram goes stale the
    moment a large ingest suffix shifts the load — the amortised sweep
    path never rebuilds its partition). Blocks wider than ``max_cols``
    are column-sampled at a deterministic stride and scaled back up; the
    static halo slot histogram is carried over unchanged (halo capacity
    does not move after the build)."""
    def counts(mask):
        m = mask.shape[1]
        step = max(1, m // max_cols)
        c = np.count_nonzero(mask[:, ::step], axis=1).astype(np.float64)
        return c * step

    kinds = {"edges_dst": counts(sv.d_mask), "edges_src": counts(sv.s_mask)}
    skew = shard_skew(**kinds)
    if sv.skew:
        for kind in ("halo_dst", "halo_src"):
            if kind in sv.skew:
                skew[kind] = dict(sv.skew[kind])
    return skew


def refresh_partition_skew(sv) -> dict:
    """Recompute + republish the skew of an EXISTING partition from live
    masks (``sampled_skew``) and stamp it onto the sharded view, so every
    downstream reader — the route chooser's evidence, the advisor's
    shard-skew rule, the ``/statusz`` gauges — sees post-ingest load, not
    the day-1 histogram. Counted separately from partition builds."""
    skew = sampled_skew(sv)
    sv.skew = skew
    COLLECTIVES.note_skew_refresh(skew)
    m = _metrics()
    if m is not None:
        for kind, s in skew.items():
            m.partition_skew.labels(kind).set(s["skew"])
    if TRACER.enabled:
        TRACER.instant("comm.skew_refresh",
                       process=TRACER.process_index,
                       **{f"{k}_skew": v["skew"] for k, v in skew.items()})
    return skew


#: comm routes a dispatch can take (docs/COMM.md route catalogue)
COMM_ROUTES = ("halo", "all_gather", "sparse")


def _dense_auto(sv, view, program, S: int) -> str:
    """The pre-sparse auto rule, unchanged: halo wins when the referenced
    remote rows are fewer than the remote rows all_gather would replicate
    (n_pad - n_loc per device); ties go to all_gather, whose single
    collective schedules better."""
    return ("halo" if S > 1
            and sv.halo_rows(program.direction) < view.n_pad - sv.n_loc
            else "all_gather")


def choose_route(program, view, sv, mesh, requested: str, k: int,
                 multi: bool, *, env: str | None = None,
                 density_hint: float | None = None) -> dict:
    """Measured-driven comm-route decision for one dispatch. Returns the
    decision record (route + evidence) the dispatcher publishes as a
    ``comm.route`` instant, a journal record and a /statusz route-table
    row.

    SPMD-uniformity (the RT012 pragma-free design): every decision input
    is identical on every process by construction — shapes and halo/pad
    sizes come from the replicated partition build, ``multi`` from the
    mesh's global device list, skew from data-replicated ingestion, and
    frontier-density history from ALLGATHERED per-process counts
    (``CollectiveStats.note_frontier`` records the global density).
    Per-process measurements (exchange seconds, barrier wait) are
    carried as *evidence only* and never read by the decision.

    ``env``/``density_hint`` override the environment knob and the
    recorded history for decision-table tests."""
    from . import frontier as _frontier

    if env is None:
        env = os.environ.get("RTPU_COMM_ROUTE", "auto").strip().lower()
    env = env or "auto"
    env_valid = env in COMM_ROUTES + ("auto",)
    S = mesh.shape[V_AXIS]
    label = program.cost_label
    key = f"{label}/k{k}"
    eligible = _frontier.supported(program)
    if density_hint is None:
        density_hint = COLLECTIVES.frontier_hint(key)
    measured = density_hint is not None
    density = _frontier.PRIOR_DENSITY if density_hint is None else density_hint

    # per-superstep byte estimates (the crossover model, docs/COMM.md):
    # dense routes replicate rows to every device each superstep; sparse
    # ships one (index, value) slot per globally-changed row, floored at
    # the bucket length each participating process pads to
    item = 4          # eligible state leaves are i32 labels / f32 dists
    slot = 8 + item   # i64 flat index + value
    n_dev = int(mesh.devices.size)
    n_procs = len({d.process_index for d in mesh.devices.flat})
    est = {
        "all_gather": (view.n_pad - sv.n_loc) * k * item * n_dev,
        "halo": sv.halo_rows(program.direction) * k * item * n_dev,
        "sparse": max(density * k * view.n_pad * slot,
                      _frontier.sparse_bucket_floor() * n_procs * slot),
    }
    dense_pick = _dense_auto(sv, view, program, S)

    route = requested
    reason = "explicit comm= argument"
    if requested == "auto":
        if env != "auto" and env_valid:
            route = env
            reason = "forced by RTPU_COMM_ROUTE"
        elif not env_valid:
            route = "auto"
            reason = f"invalid RTPU_COMM_ROUTE={env!r} ignored"
        else:
            route = "auto"
            reason = "auto"
    if route == "sparse" and not eligible:
        if requested == "sparse":
            raise ValueError(
                f"comm='sparse' requires the monotone_min contract; "
                f"{type(program).__name__} does not declare it")
        route = dense_pick
        reason = ("RTPU_COMM_ROUTE=sparse ignored: "
                  f"{label} is not monotone_min — dense fallback")
    if route == "auto":
        if eligible and multi and est["sparse"] < min(est["all_gather"],
                                                     est["halo"]):
            route = "sparse"
            reason = ("measured density" if measured else "prior density") \
                + " puts sparse below both dense routes"
        else:
            route = dense_pick
            if not eligible:
                reason = "program not monotone_min: dense volume rule"
            elif not multi:
                reason = "single-process mesh: dense volume rule"
            else:
                reason = "frontier density above crossover: dense volume rule"

    skew_max = 0.0
    if sv.skew:
        skew_max = max(float(s.get("skew", 1.0)) for s in sv.skew.values())
    # evidence-only route history (bytes are shape-derived and uniform;
    # seconds/barrier_wait are per-process and deliberately NOT inputs)
    hist = {}
    snap = COLLECTIVES.snapshot()["routes"]
    for rk, v in snap.items():
        r = rk.split("/")[0]
        h = hist.setdefault(r, {"bytes": 0, "supersteps": 0,
                                "barrier_wait_seconds": 0.0})
        h["bytes"] += v["bytes"]
        h["supersteps"] += v["supersteps"]
        h["barrier_wait_seconds"] = round(
            h["barrier_wait_seconds"] + v["barrier_wait_seconds"], 6)
    return {
        "algorithm": label,
        "key": key,
        "requested": requested,
        "env": env if env != "auto" else None,
        "route": route,
        "reason": reason,
        "eligible": eligible,
        "evidence": {
            "n_pad": int(view.n_pad),
            "k": int(k),
            "shards": int(S),
            "processes": int(n_procs),
            "multi": bool(multi),
            "density": round(float(density), 6),
            "density_measured": measured,
            "est_bytes_per_superstep": {r: int(b) for r, b in est.items()},
            "skew_max": round(skew_max, 4),
            "route_history": hist,
        },
    }


def _shard_map(fn, *, mesh, in_specs, out_specs):
    """``jax.shard_map`` with vma checking on — one spelling for both
    parallel runners."""
    return jax.shard_map(fn, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=True)


def make_mesh(n_vertex_shards: int | None = None, n_window_shards: int = 1,
              devices=None) -> Mesh:
    """Build a (windows, vertices) mesh. Defaults to all devices on the
    vertex axis — the common layout for one big graph."""
    devices = np.asarray(devices if devices is not None else jax.devices())
    total = devices.size
    if n_vertex_shards is None:
        n_vertex_shards = total // n_window_shards
    assert n_vertex_shards * n_window_shards == total, (
        f"{n_vertex_shards}x{n_window_shards} != {total} devices")
    return Mesh(devices.reshape(n_window_shards, n_vertex_shards),
                (W_AXIS, V_AXIS))


@dataclass
class ShardedView:
    """Host-side partitioned snapshot: leading axis = vertex shard."""

    n_shards: int
    n_loc: int                 # vertices per shard
    m_loc_d: int               # padded edges per shard (dst partition)
    m_loc_s: int               # padded edges per shard (src partition)
    vids: np.ndarray           # i64[S, n_loc]
    v_mask: np.ndarray         # bool[S, n_loc]
    v_latest: np.ndarray       # i64[S, n_loc]
    v_first: np.ndarray        # i64[S, n_loc]
    # dst partition: combine-at-dst; src index is GLOBAL (gathered state)
    d_src_g: np.ndarray        # i32[S, m_loc_d]
    d_dst_l: np.ndarray        # i32[S, m_loc_d]  local, sorted, pad n_loc-1
    d_mask: np.ndarray         # bool[S, m_loc_d]
    d_time: np.ndarray         # i64[S, m_loc_d]
    d_first: np.ndarray
    # src partition: combine-at-src; dst index is GLOBAL
    s_dst_g: np.ndarray        # i32[S, m_loc_s]
    s_src_l: np.ndarray        # i32[S, m_loc_s]  local, sorted, pad n_loc-1
    s_mask: np.ndarray
    s_time: np.ndarray
    s_first: np.ndarray
    d_props: dict              # name -> f32[S, m_loc_d]
    s_props: dict
    view: GraphView
    occurrences: bool = False  # blocks hold occ_* (multigraph) rows
    # halo structures (one per partition direction): h_* is the per-
    # (requester, owner) slot capacity; *_h remaps the global ref array into
    # [local | halo] space [0, n_loc + S*h); *_send[S, S*h] is each owner
    # device's all_to_all send page (local rows grouped by requester).
    h_d: int = 0
    d_src_h: np.ndarray | None = None   # i32[S, m_loc_d]
    d_send: np.ndarray | None = None    # i32[S, S*h_d]
    h_s: int = 0
    s_dst_h: np.ndarray | None = None
    s_send: np.ndarray | None = None
    #: per-shard degree/halo row-count histogram built at partition time
    #: (``shard_skew`` output) — the power-law imbalance evidence
    skew: dict | None = None
    #: device copies of the blocks no hop of a sweep changes, by (mesh,
    #: name): a static partition's own cache
    #: (``parallel/sweep.StaticPartition.resident``), filled by ``run`` at
    #: the first dispatch on a mesh; None for a per-view partition, whose
    #: every block is the view's
    resident: dict | None = None

    def halo_rows(self, direction: str) -> int:
        """Rows exchanged per device per superstep on the halo path (vs
        ``view.n_pad - n_loc`` received per device for all_gather)."""
        rows = 0
        if direction in ("out", "both"):
            rows += self.n_shards * self.h_d
        if direction in ("in", "both"):
            rows += self.n_shards * self.h_s
        return rows


def _pow2(n: int) -> int:
    return 8 if n <= 8 else 1 << int(np.ceil(np.log2(n)))


def _build_halo(idx_g: np.ndarray, n_loc: int, S: int):
    """Halo layout for one partition direction.

    ``idx_g[S, m_loc]`` holds GLOBAL vertex refs per shard. Returns
    ``(h, idx_h, send, halo_counts)``: per-(requester, owner) slot
    capacity ``h``; ``idx_h[S, m_loc]`` remapping each ref into the
    shard's extended space — local row for own vertices,
    ``n_loc + owner*h + slot`` for remote ones; ``send[S, S*h]`` where
    row ``o`` is owner-device o's all_to_all send page: chunk ``r`` lists
    the local rows requester ``r`` referenced (sorted unique; slot order
    matches the requester's remap); ``halo_counts[S]`` counts each
    requester's unique remote refs (the per-shard halo-skew signal)."""
    idx_h = np.zeros(idx_g.shape, np.int32)
    halo_counts = np.zeros(idx_g.shape[0], np.int64)
    uniq = []  # (requester, u_owner[], u_g[], slot[])
    maxcnt = 1
    for sh in range(S):
        g = idx_g[sh].astype(np.int64)
        owner = g // n_loc
        local = owner == sh
        idx_h[sh, local] = (g[local] - sh * n_loc).astype(np.int32)
        rem = np.flatnonzero(~local)
        if len(rem) == 0:
            continue
        go, oo = g[rem], owner[rem]
        order = np.lexsort((go, oo))
        gs, os_ = go[order], oo[order]
        new = np.ones(len(gs), bool)
        new[1:] = (gs[1:] != gs[:-1]) | (os_[1:] != os_[:-1])
        uid = np.cumsum(new) - 1                      # unique rank per row
        u_owner = os_[new]
        u_g = gs[new]
        # slot within owner group = unique rank − rank at owner's first unique
        o_change = np.ones(len(u_owner), bool)
        o_change[1:] = u_owner[1:] != u_owner[:-1]
        arange_u = np.arange(len(u_g))
        base = np.maximum.accumulate(np.where(o_change, arange_u, 0))
        slot = (arange_u - base).astype(np.int64)
        maxcnt = max(maxcnt, int(slot.max()) + 1)
        halo_counts[sh] = len(u_g)
        # remote-row remap happens in the second pass (slots need final h)
        uniq.append((sh, u_owner, u_g, slot, rem[order], uid))
    h = _pow2(maxcnt)
    send = np.zeros((S, S * h), np.int32)
    for sh, u_owner, u_g, slot, rows, uid in uniq:
        idx_h[sh, rows] = (n_loc + u_owner[uid] * h + slot[uid]).astype(np.int32)
        send[u_owner, sh * h + slot] = (u_g - u_owner * n_loc).astype(np.int32)
    return h, idx_h, send, halo_counts


# build counter — the amortisation witness: range sweeps that re-partition
# per hop (the round-3 regression class) show up as increments here.
# Bumps go through note_partition_build(): concurrent mesh jobs each
# build partitions on their own job thread, and an unguarded += loses
# counts exactly when the witness matters (rtpulint RT010)
PARTITION_BUILDS = 0
_BUILDS_LOCK = threading.Lock()


def note_partition_build() -> None:
    global PARTITION_BUILDS
    with _BUILDS_LOCK:
        PARTITION_BUILDS += 1


def partition_view(view: GraphView, n_shards: int,
                   edge_props: tuple = (),
                   occurrences: bool = False) -> ShardedView:
    """Range-partition the padded vertex space into contiguous shards and
    scatter edges into per-shard blocks (dst- and src-partitioned), plus the
    halo exchange layout. With ``occurrences=True`` the blocks hold the
    multigraph occurrence rows (per-event times/props) instead of the
    deduplicated edges."""
    assert view.n_pad % n_shards == 0, (
        f"vertex shard count {n_shards} must divide the padded vertex count "
        f"{view.n_pad} (pad buckets are powers of two; use a power-of-two "
        f"vertex-axis size)")
    note_partition_build()
    n_loc = view.n_pad // n_shards
    S = n_shards

    if occurrences:
        if view.occ_src is None:
            raise ValueError("program needs occurrences: build the view "
                             "with include_occurrences=True")
        act = view.occ_mask
        esrc = view.occ_src[act].astype(np.int64)
        edst = view.occ_dst[act].astype(np.int64)
        etime = view.occ_time[act]
        efirst = view.occ_time[act]
        props = {k: view.occ_prop(k)[act] for k in edge_props}
    else:
        act = view.e_mask
        esrc = view.e_src[act].astype(np.int64)
        edst = view.e_dst[act].astype(np.int64)
        etime = view.e_latest_time[act]
        efirst = view.e_first_time[act]
        props = {k: view.edge_prop(k)[act] for k in edge_props}

    def _partition(owner_of, local_of, global_of):
        owner = owner_of // n_loc
        order = np.lexsort((local_of, owner))
        counts = np.bincount(owner, minlength=S)
        shard_counts.append(np.asarray(counts[:S], np.int64))
        m_loc = _pow2(int(counts.max()) if len(counts) else 0)
        idx_g = np.full((S, m_loc), view.n_pad - 1, np.int32)
        idx_l = np.full((S, m_loc), n_loc - 1, np.int32)
        mask = np.zeros((S, m_loc), bool)
        tarr = np.full((S, m_loc), INT64_MIN, np.int64)
        farr = np.full((S, m_loc), INT64_MIN, np.int64)
        parr = {k: np.zeros((S, m_loc), np.float32) for k in props}
        off = 0
        for sh in range(S):
            c = int(counts[sh]) if sh < len(counts) else 0
            rows = order[off : off + c]
            off += c
            idx_g[sh, :c] = global_of[rows]
            idx_l[sh, :c] = (owner_of[rows] - sh * n_loc)
            mask[sh, :c] = True
            tarr[sh, :c] = etime[rows]
            farr[sh, :c] = efirst[rows]
            for kk in props:
                parr[kk][sh, :c] = props[kk][rows]
        return m_loc, idx_g, idx_l, mask, tarr, farr, parr

    shard_counts: list = []   # filled by _partition (dst then src)
    m_loc_d, d_src_g, d_dst_l, d_mask, d_time, d_first, d_props = _partition(
        edst, edst % n_loc, esrc)
    m_loc_s, s_dst_g, s_src_l, s_mask, s_time, s_first, s_props = _partition(
        esrc, esrc % n_loc, edst)

    h_d, d_src_h, d_send, halo_d = _build_halo(d_src_g, n_loc, S)
    h_s, s_dst_h, s_send, halo_s = _build_halo(s_dst_g, n_loc, S)

    # per-shard degree/halo histogram — the partition-time skew evidence
    # (power-law graphs concentrate edges and halo refs on few shards)
    skew = shard_skew(edges_dst=shard_counts[0], edges_src=shard_counts[1],
                      halo_dst=halo_d, halo_src=halo_s)
    note_partition_skew(skew)

    rs = lambda a: a.reshape(S, n_loc)
    return ShardedView(
        n_shards=S, n_loc=n_loc, m_loc_d=m_loc_d, m_loc_s=m_loc_s,
        vids=rs(view.vids), v_mask=rs(view.v_mask),
        v_latest=rs(view.v_latest_time), v_first=rs(view.v_first_time),
        d_src_g=d_src_g, d_dst_l=d_dst_l, d_mask=d_mask,
        d_time=d_time, d_first=d_first,
        s_dst_g=s_dst_g, s_src_l=s_src_l, s_mask=s_mask,
        s_time=s_time, s_first=s_first,
        d_props=d_props, s_props=s_props, view=view,
        occurrences=occurrences,
        h_d=h_d, d_src_h=d_src_h, d_send=d_send,
        h_s=h_s, s_dst_h=s_dst_h, s_send=s_send,
        skew=skew,
    )


@functools.lru_cache(maxsize=128)
def _sharded_runner(program: VertexProgram, mesh: Mesh, n_loc: int,
                    m_loc_d: int, m_loc_s: int, k_loc: int, n_pad: int,
                    prop_keys: tuple, comm: str = "all_gather",
                    h_d: int = 0, h_s: int = 0):
    """Compile one SPMD program for (algorithm, shapes, mesh, comm route)."""
    reduce_axes = (W_AXIS, V_AXIS)
    S_v = mesh.shape[V_AXIS]

    def gather_state(state_loc):
        # state leaves are [k_loc, n_loc, ...]: the vertex axis is axis 1
        # (axis 0 is the local window batch) — tiled gather concatenates the
        # contiguous range partitions back into global vertex order
        return jax.tree_util.tree_map(
            lambda a: jax.lax.all_gather(a, V_AXIS, axis=1, tiled=True),
            state_loc)

    def exchange_halo(state_loc, send_idx):
        # halo route: each device ships ONLY the rows its peers reference.
        # send_idx i32[S*h]: chunk r = local rows requester r wants; one
        # tiled all_to_all swaps chunks so chunk o of the result is what
        # owner o sent us — laid out to match the *_h remaps. Result leaves
        # are the extended space [k_loc, n_loc + S*h, ...] (own rows first).
        def leaf(a):
            send = jnp.take(a, send_idx, axis=1)
            recv = jax.lax.all_to_all(
                send, V_AXIS, split_axis=1, concat_axis=1, tiled=True)
            return jnp.concatenate([a, recv], axis=1)
        return jax.tree_util.tree_map(leaf, state_loc)

    def device_fn(v_mask, vids, v_latest, v_first,
                  d_src_g, d_dst_l, d_mask, d_time, d_first,
                  s_dst_g, s_src_l, s_mask, s_time, s_first,
                  halo, d_props, s_props, vprops, time, windows):
        # shapes (per device): v_mask [Kl, n_loc]; d_* [m_loc_d] / masks
        # [Kl, m_loc_d]; windows [Kl]
        v_off = jax.lax.axis_index(V_AXIS).astype(jnp.int32) * n_loc

        # Flat window-major layout: the window batch is ONE graph of
        # k_loc*n_loc local vertices, per-window segment ids offset by
        # kk*n_loc. One scatter for all windows — and no vmapped scatter
        # inside the superstep while_loop, the shape that miscompiles on
        # the TPU backend when the loop condition reads carried state
        # (see engine/bsp.py make_runner).
        woffs_loc = (jnp.arange(k_loc, dtype=jnp.int32) * n_loc)[:, None]
        fl_d_dst = (d_dst_l[None, :] + woffs_loc).reshape(-1)  # sorted/blk
        fl_s_src = (s_src_l[None, :] + woffs_loc).reshape(-1)  # sorted/blk
        if comm == "halo":
            # gather indices live in each shard's [local | halo] space
            ext_d = n_loc + S_v * h_d
            ext_s = n_loc + S_v * h_s
            woffs_d = (jnp.arange(k_loc, dtype=jnp.int32) * ext_d)[:, None]
            woffs_s = (jnp.arange(k_loc, dtype=jnp.int32) * ext_s)[:, None]
            fl_d_src = (halo["d_src_h"][None, :] + woffs_d).reshape(-1)
            fl_s_dst = (halo["s_dst_h"][None, :] + woffs_s).reshape(-1)
        else:
            woffs_pad = (jnp.arange(k_loc, dtype=jnp.int32) * n_pad)[:, None]
            fl_d_src = (d_src_g[None, :] + woffs_pad).reshape(-1)
            fl_s_dst = (s_dst_g[None, :] + woffs_pad).reshape(-1)
        dm_flat = d_mask.reshape(-1)
        sm_flat = s_mask.reshape(-1)

        def tile_d(a):
            return jnp.broadcast_to(a[None, :], (k_loc,) + a.shape).reshape(
                (k_loc * m_loc_d,) + a.shape[1:])

        def tile_s(a):
            return jnp.broadcast_to(a[None, :], (k_loc,) + a.shape).reshape(
                (k_loc * m_loc_s,) + a.shape[1:])

        # both id sets are sorted, so a sum over them is a segmented scan
        # (ops/segment.sorted_segment_sum); where the segments lie depends
        # on the ids alone: once, before the superstep loop
        plan_d = segment_ends_pos(fl_d_dst, k_loc * n_loc)
        plan_s = segment_ends_pos(fl_s_src, k_loc * n_loc)
        # so do the rows of every segment, which the sort of a mode
        # exchange places its segments by: read off the plans of the
        # directions the program listens on, never counted in a round
        mode_counts = None
        if takes_mode_counts(program):
            by_part = []    # in ``step_all``'s order of the parts
            if program.direction in ("out", "both"):
                by_part.append(segment_counts_at(*plan_d))
            if program.direction in ("in", "both"):
                by_part.append(segment_counts_at(*plan_s))
            mode_counts = sum(by_part)

        def combine_flat(tree_flat, ids, msk, plan):
            def leaf(x):
                out = segment_combine(x, ids, k_loc * n_loc, program.combiner,
                                      msk, indices_are_sorted=True,
                                      ends=plan[0], pos=plan[1])
                return out.reshape((k_loc, n_loc) + x.shape[1:])
            return jax.tree_util.tree_map(leaf, tree_flat)

        in_deg = segment_combine(
            jnp.ones((k_loc * m_loc_d,), jnp.int32), fl_d_dst,
            k_loc * n_loc, "sum", dm_flat, True,
            ends=plan_d[0], pos=plan_d[1]).reshape(k_loc, n_loc)
        out_deg = segment_combine(
            jnp.ones((k_loc * m_loc_s,), jnp.int32), fl_s_src,
            k_loc * n_loc, "sum", sm_flat, True,
            ends=plan_s[0], pos=plan_s[1]).reshape(k_loc, n_loc)

        def mk_ctx(kk, step):
            n_act = jnp.sum(v_mask[kk].astype(jnp.int32))
            n_act = jax.lax.psum(n_act, V_AXIS)
            return Context(
                n=n_loc, time=time, window=windows[kk], v_mask=v_mask[kk],
                vids=vids, v_latest_time=v_latest, v_first_time=v_first,
                out_deg=out_deg[kk], in_deg=in_deg[kk], n_active=n_act,
                step=step, vprops=vprops, v_offset=v_off, axis_name=V_AXIS,
            )

        def init_k(kk):
            return program.init(mk_ctx(kk, jnp.int32(0)))

        state0 = jax.vmap(init_k)(jnp.arange(k_loc))

        def gather_flat(st_pool, ids, width):
            return jax.tree_util.tree_map(
                lambda a: a.reshape((k_loc * width,) + a.shape[2:])[ids],
                st_pool)

        def step_all(st, step):
            if comm == "halo":
                pool_d = lambda: exchange_halo(st, halo["d_send"])
                pool_s = lambda: exchange_halo(st, halo["s_send"])
                width_d, width_s = n_loc + S_v * h_d, n_loc + S_v * h_s
            else:
                st_full = gather_state(st)  # [k_loc, n_pad, ...]
                pool_d = pool_s = lambda: st_full
                width_d = width_s = n_pad
            custom = program.combiner == "custom"
            agg, parts = None, []
            if program.direction in ("out", "both"):
                # Edges contract: src/dst are GLOBAL padded indices
                edges = Edges(src=tile_d(d_src_g), dst=tile_d(d_dst_l) + v_off,
                              mask=dm_flat, time=tile_d(d_time),
                              first_time=tile_d(d_first),
                              props=jax.tree_util.tree_map(tile_d, d_props),
                              step=step)
                payload = program.message(
                    gather_flat(pool_d(), fl_d_src, width_d), edges)
                if custom:
                    parts.append((payload, fl_d_dst, dm_flat))
                else:
                    agg = combine_flat(payload, fl_d_dst, dm_flat, plan_d)
            if program.direction in ("in", "both"):
                edges = Edges(src=tile_s(s_src_l) + v_off, dst=tile_s(s_dst_g),
                              mask=sm_flat, time=tile_s(s_time),
                              first_time=tile_s(s_first),
                              props=jax.tree_util.tree_map(tile_s, s_props),
                              step=step)
                payload = program.message(
                    gather_flat(pool_s(), fl_s_dst, width_s), edges)
                if custom:
                    parts.append((payload, fl_s_src, sm_flat))
                else:
                    agg_in = combine_flat(payload, fl_s_src, sm_flat,
                                          plan_s)
                    agg = agg_in if agg is None else _merge_aggs(
                        program.combiner, agg, agg_in)
            if custom:
                # a vertex's owner holds its in-edges (d_*) and its
                # out-edges (s_*): both payloads meet in one exchange
                agg = jax.tree_util.tree_map(
                    lambda a: a.reshape((k_loc, n_loc) + a.shape[1:]),
                    custom_exchange(program, parts, k_loc * n_loc,
                                    mode_counts))

            def upd_k(kk, stk, aggk):
                new_st, votes = program.update(stk, aggk, mk_ctx(kk, step))
                # local vote only — caller makes it global (psum over shards)
                unhalted = jnp.sum((~(votes | ~v_mask[kk])).astype(jnp.int32))
                return new_st, unhalted

            return jax.vmap(upd_k, in_axes=(0, 0, 0))(
                jnp.arange(k_loc), st, agg)


        def vary(x):
            """Promote x to varying over exactly the mesh axes it is missing
            (no-op when already fully varying) — shard_map's check_vma
            requires explicit promotion of shard-invariant values."""
            missing = tuple(a for a in (W_AXIS, V_AXIS)
                            if a not in jax.typeof(x).vma)
            return jax.lax.pcast(x, missing, to="varying") if missing else x

        if program.max_steps > 0:
            def cond(carry):
                step, _, halted = carry
                # halted is per-window and identical on every vertex shard
                # (derived from a psum over V); any unhalted window anywhere
                # keeps every device stepping — SPMD-uniform condition.
                # vary() marks the (possibly vertex-invariant) count varying
                # so the full-mesh psum type-checks under check_vma; summing
                # S_v identical copies only scales the >0 test.
                unhalted = vary(jnp.sum((~halted).astype(jnp.int32)))
                unhalted = jax.lax.psum(unhalted, reduce_axes)
                return (step < program.max_steps) & (unhalted > 0)

            def body(carry):
                step, st, halted = carry
                new_st, unhalted_local = step_all(st, step)
                # per-window GLOBAL quiescence: a window halts only when no
                # shard changed state — freezing must never be shard-local,
                # or a converged shard would stop receiving neighbours'
                # updates. (The reference's coordinator quiescence check,
                # AnalysisTask.scala:237-283, as one psum.)
                unhalted_global = jax.lax.psum(unhalted_local, V_AXIS)
                new_halt = unhalted_global == 0
                st = jax.tree_util.tree_map(
                    lambda old, new: jnp.where(
                        halted.reshape((k_loc,) + (1,) * (new.ndim - 1)),
                        old, new),
                    st, new_st)
                return step + 1, st, halted | new_halt

            # The loop body makes every carry leaf varying over the whole
            # mesh (state via the exchange, halted via the psum), but leaves
            # a program's init() built from constants start invariant —
            # promote each initial leaf to varying over exactly the axes it
            # is missing so the while_loop carry is type-stable.
            halted0 = vary(jnp.zeros((k_loc,), bool))
            state0 = jax.tree_util.tree_map(vary, state0)
            steps, state, _ = jax.lax.while_loop(
                cond, body, (jnp.int32(0), state0, halted0))
        else:
            steps, state = jnp.int32(0), state0

        def fin_k(kk, st):
            return program.finalize(st, mk_ctx(kk, steps))

        result = jax.vmap(fin_k, in_axes=(0, 0))(jnp.arange(k_loc), state)
        return result, steps

    # specs: window-sharded leading axis (if any), vertex-sharded second
    kv = P(W_AXIS, V_AXIS)       # [K, S, ...]: windows on W, shards on V
    v = P(V_AXIS)                # [S, ...]: shard axis 0, replicated over W
    in_specs = (
        kv,            # v_mask [K, S, n_loc]
        v, v, v,       # vids, v_latest, v_first [S, n_loc]
        v, v, kv, v, v,        # d_src_g, d_dst_l, d_mask[K,S,m], d_time, d_first
        v, v, kv, v, v,        # s_dst_g, s_src_l, s_mask, s_time, s_first
        v,             # halo dict (leaves [S, m_loc] / [S, S*h])
        v, v, v,       # edge/vertex prop dicts (leaves [S, m_loc] / [S, n_loc])
        P(),           # time scalar
        P(W_AXIS),     # windows [K]
    )
    out_specs = (P(W_AXIS, V_AXIS), P())

    def squeeze_fn(v_mask, vids, v_latest, v_first,
                   d_src_g, d_dst_l, d_mask, d_time, d_first,
                   s_dst_g, s_src_l, s_mask, s_time, s_first,
                   halo, d_props, s_props, vprops, time, windows):
        # strip the sharded block axes: [Kl, 1, ...] -> [Kl, ...]; [1, ...] -> [...]
        sq_kv = lambda a: a.reshape((a.shape[0],) + a.shape[2:])
        sq_v = lambda a: a.reshape(a.shape[1:])
        result, steps = device_fn(
            sq_kv(v_mask), sq_v(vids), sq_v(v_latest), sq_v(v_first),
            sq_v(d_src_g), sq_v(d_dst_l), sq_kv(d_mask), sq_v(d_time), sq_v(d_first),
            sq_v(s_dst_g), sq_v(s_src_l), sq_kv(s_mask), sq_v(s_time), sq_v(s_first),
            jax.tree_util.tree_map(sq_v, halo),
            jax.tree_util.tree_map(sq_v, d_props),
            jax.tree_util.tree_map(sq_v, s_props),
            jax.tree_util.tree_map(sq_v, vprops),
            time, windows)
        # back to block shape for out_specs [K, S, n_loc, ...]
        result = jax.tree_util.tree_map(
            lambda a: a.reshape((a.shape[0], 1) + a.shape[1:]), result)
        return result, steps

    fn = _shard_map(squeeze_fn, mesh=mesh, in_specs=in_specs,
                    out_specs=out_specs)
    return jax.jit(fn)


def run(program: VertexProgram, view: GraphView, mesh: Mesh, *,
        window: int | None = None, windows=None,
        sharded_view: ShardedView | None = None, comm: str = "auto",
        block: bool = True):
    """Run a vertex program SPMD over the mesh. Same surface as
    ``engine.bsp.run`` plus the mesh. Returns (result, steps) with result
    leading axes [K windows, n_pad] in GLOBAL vertex order.

    ``comm`` picks the cross-shard state route: ``"all_gather"`` replicates
    the state along the vertex axis each superstep, ``"halo"`` exchanges only
    the remote rows each shard's edges reference (one all_to_all),
    ``"sparse"`` ships only the changed-since-last-superstep rows as
    bucketed compact slices (monotone-min programs only —
    ``parallel/frontier.py``), and ``"auto"`` (default) asks the
    measured-driven chooser (``choose_route``; ``RTPU_COMM_ROUTE``
    forces a route for auto dispatches). docs/COMM.md catalogues the
    routes and the crossover model.

    ``block=False`` returns device arrays without waiting (steps stays a
    device scalar) so a range sweep can overlap the next hop's host fold
    with this hop's supersteps — the mesh twin of ``bsp.run_async``.
    Multi-process runs always block (results must allgather to hosts);
    so does the sparse route (its superstep loop is host-driven)."""
    batched = windows is not None
    occurrences = bool(getattr(program, "needs_occurrences", False))
    check_custom_direction(program)
    if windows is not None and len(windows) == 0:
        raise ValueError("windows must be a non-empty list of window sizes")
    if windows is None:
        windows = [window if window is not None else -1]
    wlist = [int(w) if w is not None and w >= 0 else -1 for w in windows]

    W = mesh.shape.get(W_AXIS, 1)
    S = mesh.shape[V_AXIS]
    # pad window count to a multiple of the window-axis size with no-op
    # duplicates of the last window
    k = len(wlist)
    k_pad = ((k + W - 1) // W) * W
    wlist_p = wlist + [wlist[-1]] * (k_pad - k)
    k_loc = k_pad // W

    sv = sharded_view
    if (sv is None or sv.n_shards != S or sv.view is not view
            or sv.occurrences != occurrences
            or not set(program.edge_props) <= set(sv.d_props)):
        sv = partition_view(view, S, tuple(program.edge_props),
                            occurrences=occurrences)

    if comm not in ("auto",) + COMM_ROUTES:
        raise ValueError(
            f"comm must be auto|halo|all_gather|sparse, got {comm!r}")

    # Multi-host gate: the MESH actually spanning processes, not
    # jax.process_count() — a process of a multi-host cluster sweeping
    # its own local devices must not attempt cross-process collectives.
    multi = len({d.process_index for d in mesh.devices.flat}) > 1

    # Route decision: explicit comm= wins; RTPU_COMM_ROUTE (read HERE,
    # at dispatch — rtpulint RT001) steers "auto"; the measured-driven
    # chooser otherwise picks by estimated bytes/superstep. The decision
    # + evidence is published as a comm.route instant, a journal record,
    # and a /statusz route-table row (docs/COMM.md).
    decision = choose_route(program, view, sv, mesh, comm, k, multi)
    comm = decision["route"]
    proc = TRACER.process_index
    COLLECTIVES.note_route_decision(decision)
    if TRACER.enabled:
        ev = decision["evidence"]
        TRACER.instant(
            "comm.route", process=proc, algorithm=decision["algorithm"],
            route=comm, requested=decision["requested"],
            reason=decision["reason"], density=ev["density"],
            skew_max=ev["skew_max"],
            **{f"est_{r}": b
               for r, b in ev["est_bytes_per_superstep"].items()})
    from ..obs import journal as _journal

    if _journal.enabled():
        _journal.emit("comm.route", decision)

    # mesh-divergence sanitizer: fingerprint this dispatch BEFORE issuing
    # it, so a collective that hangs still leaves its record behind for
    # the /clusterz prefix cross-check. The fingerprint includes the
    # ROUTE — processes disagreeing on the chooser's verdict at the same
    # dispatch seq flag as divergence (tests/test_sparse_route.py)
    msan = mesh_active()
    msite = f"parallel.sharded.run/{type(program).__name__}"
    msig = (f"S{S}W{W}k{k_pad}n{view.n_pad}v{sv.n_loc}"
            f"d{sv.m_loc_d}s{sv.m_loc_s}")
    if msan is not None:
        msan.note_dispatch(msite, comm, msig, "i64")

    if comm == "sparse":
        from . import frontier as _frontier

        with TRACER.span("comm.exchange", route="sparse",
                         direction=program.direction, process=proc,
                         shards=S, windows=k) as csp:
            t0 = _time.perf_counter()
            # rtpulint: spmd-uniform — `comm` is choose_route's verdict, whose every input is replicated by construction (shapes/halo sizes from the partition build, `multi` from the global device list, skew from data-replicated ingestion, density from ALLGATHERED counts; per-process seconds are evidence-only) — all processes pick the same route, and the runtime mesh sanitizer fingerprints the route per dispatch to catch any drift
            result, steps, acct = _frontier.run_sparse(
                program, view, mesh, sv, wlist, multi=multi,
                msan=msan, msite=msite)
            seconds = _time.perf_counter() - t0
            csp.set(supersteps=acct["supersteps"], rows=acct["rows"],
                    bytes=acct["bytes"],
                    density=round(acct["density"], 6),
                    fallback_supersteps=acct["fallback_supersteps"],
                    barrier_wait_seconds=round(acct["barrier_wait"], 6))
        COLLECTIVES.note_exchange(
            "sparse", program.direction, rows=acct["rows"],
            bytes_=acct["bytes"], seconds=seconds,
            supersteps=acct["supersteps"],
            barrier_wait=acct["barrier_wait"])
        COLLECTIVES.note_frontier(decision["key"], acct["density"],
                                  acct["supersteps"])
        from ..obs import ledger as _ledger

        led = _ledger.current()
        if led is not None:
            led.add_dcn("sparse", rows=acct["rows"], bytes_=acct["bytes"])
        if not batched:
            result = jax.tree_util.tree_map(lambda a: a[0], result)
        return result, steps

    # window masks, computed from per-shard latest-time arrays
    v_masks = np.empty((k_pad, S, sv.n_loc), bool)
    d_masks = np.empty((k_pad, S, sv.m_loc_d), bool)
    s_masks = np.empty((k_pad, S, sv.m_loc_s), bool)
    for i, w in enumerate(wlist_p):
        if w < 0:
            v_masks[i] = sv.v_mask
            d_masks[i] = sv.d_mask
            s_masks[i] = sv.s_mask
        else:
            lo = view.time - w
            v_masks[i] = sv.v_mask & (sv.v_latest >= lo)
            d_masks[i] = sv.d_mask & (sv.d_time >= lo)
            s_masks[i] = sv.s_mask & (sv.s_time >= lo)

    # h_* only shape the compiled program on the halo route — keep them out
    # of the runner cache key otherwise, or same-bucket sweep hops with
    # different halo populations would recompile for nothing
    runner = _sharded_runner(
        program, mesh, sv.n_loc, sv.m_loc_d, sv.m_loc_s, k_loc, view.n_pad,
        tuple(program.edge_props), comm,
        sv.h_d if comm == "halo" else 0, sv.h_s if comm == "halo" else 0)

    # Multi-host (DCN) runs: every process holds the same full host arrays
    # (data-replicated ingestion — the reference replays every update to
    # every PM's router the same way), so each input becomes a GLOBAL
    # jax.Array by slicing out this process's addressable shards. On one
    # process each input is put where the program reads it, a shard a
    # device, never whole on the default device first.
    put = {"arrays": 0, "bytes": 0, "resident_bytes": 0}

    def dev(x, spec):
        x = np.asarray(x)
        put["arrays"] += 1
        put["bytes"] += x.nbytes
        sh = jax.sharding.NamedSharding(mesh, spec)
        if not multi:
            return jax.device_put(x, sh)
        return jax.make_array_from_callback(x.shape, sh, lambda idx: x[idx])

    held = sv.resident

    def static(name, block):
        """A ``[S, ...]`` block no hop of a sweep changes (an array, or a
        function that makes it): the partition's device copy on this
        mesh, put by the first dispatch that finds none."""
        a = held.get((mesh, name)) if held is not None else None
        if a is None:
            b0 = put["bytes"]
            a = dev(block() if callable(block) else block, v)
            if held is not None:
                held[(mesh, name)] = a
                put["resident_bytes"] += put["bytes"] - b0
        return a

    def edge_times(block, m_loc):
        """A block of edge times: the sweep's, patched this hop — or, for
        a program that reads none (``needs_edge_times`` False: the runner
        drops the argument), one resident block of the shape."""
        if held is None or program.needs_edge_times:
            return dev(block, v)
        return static(f"no_edge_times.{m_loc}", lambda: np.full(
            (S, m_loc), INT64_MIN, np.int64))

    kv, v, rep = P(W_AXIS, V_AXIS), P(V_AXIS), P()

    # Collective telemetry: what THIS dispatch moves across shards per
    # superstep. halo ships each device its referenced remote slot pages
    # (padded slot capacity — what is actually on the wire); all_gather
    # replicates the (n_pad - n_loc) remote rows to every device once per
    # superstep, shared by both directions. Byte width is estimated from
    # the result state leaves (the exchanged state tree for every vertex
    # program this engine runs; a program with wider internal state
    # under-counts — documented in docs/OBSERVABILITY.md).
    n_devices = int(mesh.devices.size)
    if comm == "halo":
        rows_dev = sv.halo_rows(program.direction)
    else:
        rows_dev = view.n_pad - sv.n_loc
    rows_step = rows_dev * k_loc * n_devices
    with TRACER.span("comm.exchange", route=comm,
                     direction=program.direction, process=proc,
                     shards=S, windows=k_pad,
                     rows_per_superstep=rows_step) as csp:
        if takes_mode_counts(program):
            # the runner reads a mode exchange's row counts off its plans,
            # once a dispatch (``_sharded_runner``): no round counts rows
            csp.set(mode_counts="plan")
        # the dispatch of the puts: what this hop ships (``bytes``: the
        # window masks and whatever else the hop's fold changed) and, at a
        # static partition's first dispatch on this mesh, the blocks that
        # stay (``resident_bytes``). device_put returns before the bytes
        # have moved: the transfer falls where the host next waits
        with TRACER.span("comm.put") as psp:
            halo = {}
            if comm == "halo":
                halo = {name: static(name, getattr(sv, name)) for name in (
                    "d_src_h", "d_send", "s_dst_h", "s_send")}
            st = {name: static(name, getattr(sv, name)) for name in (
                "vids", "d_src_g", "d_dst_l", "s_dst_g", "s_src_l")}
            args = (
                dev(v_masks, kv), st["vids"], dev(sv.v_latest, v),
                dev(sv.v_first, v),
                st["d_src_g"], st["d_dst_l"], dev(d_masks, kv),
                edge_times(sv.d_time, sv.m_loc_d),
                edge_times(sv.d_first, sv.m_loc_d),
                st["s_dst_g"], st["s_src_l"], dev(s_masks, kv),
                edge_times(sv.s_time, sv.m_loc_s),
                edge_times(sv.s_first, sv.m_loc_s),
                halo,
                {kk: dev(vv, v) for kk, vv in sv.d_props.items()},
                {kk: dev(vv, v) for kk, vv in sv.s_props.items()},
                {kk: dev(
                    np.asarray(view.vertex_prop(kk),
                               np.float32).reshape(S, sv.n_loc),
                    v)
                 for kk in program.vertex_props},
                dev(np.asarray(view.time, np.int64), rep),
                dev(np.asarray(wlist_p, np.int64), P(W_AXIS)),
            )
            psp.set(arrays=put["arrays"],
                    bytes=put["bytes"] - put["resident_bytes"],
                    resident_bytes=put["resident_bytes"])
        result, steps = runner(*args)
        t_disp = _time.perf_counter()
        row_bytes = sum(
            np.dtype(a.dtype).itemsize
            * int(np.prod(a.shape[3:], dtype=np.int64))
            for a in jax.tree_util.tree_leaves(result))
        block_wait = barrier_wait = 0.0
        if block or multi:
            # local program completion: device compute + in-program
            # collectives — the host-side "collective window"
            with TRACER.span("comm.block_wait", route=comm, process=proc):
                jax.block_until_ready(result)
            block_wait = _time.perf_counter() - t_disp
        # rtpulint: spmd-uniform — `multi` derives from the mesh's device set, which every process builds from the same global device list; all processes take the same arm
        if multi:
            # replicate the (cross-host sharded) result back to every
            # host — job reducers are host code and expect the full
            # arrays. Local compute is DONE here, so this wait is the
            # per-process straggler signal: a process stuck behind a
            # slow peer spends it in this span.
            from jax.experimental import multihost_utils

            t_bar = _time.perf_counter()
            # stall watchdog: divergence shows up as THIS wait never
            # returning (a peer skipped the collective) — the watchdog
            # reports from its timer thread while we are still hung
            watch = (msan.barrier_watch(msite, comm)
                     if msan is not None else None)
            try:
                with TRACER.span("comm.barrier_wait", route=comm,
                                 process=proc):
                    result = multihost_utils.process_allgather(
                        result, tiled=True)
            finally:
                if watch is not None:
                    watch.cancel()
            barrier_wait = _time.perf_counter() - t_bar
            block = True
        # superstep count is a device scalar on async dispatches — those
        # account exactly one superstep (visible as async_dispatches in
        # the COLLECTIVES snapshot) rather than blocking the overlap the
        # async path exists for
        n_steps = int(steps) if block else 1
        rows_total = rows_step * n_steps
        bytes_total = rows_total * row_bytes
        csp.set(supersteps=(n_steps if block else "async"),
                rows=rows_total, bytes=bytes_total,
                barrier_wait_seconds=round(barrier_wait, 6))
    COLLECTIVES.note_exchange(
        comm, program.direction, rows=rows_total, bytes_=bytes_total,
        seconds=block_wait, supersteps=n_steps,
        barrier_wait=barrier_wait, async_dispatch=not block)
    from ..obs import ledger as _ledger

    led = _ledger.current()
    if led is not None:
        led.add_dcn(comm, rows=rows_total, bytes_=bytes_total)
    # merge shard axis back into global vertex order: [K, S, n_loc] -> [K, n]
    to_host = np.asarray if block else (lambda a: a)
    result = jax.tree_util.tree_map(
        lambda a: to_host(a).reshape((k_pad, view.n_pad) + a.shape[3:]),
        result)
    result = jax.tree_util.tree_map(lambda a: a[:k], result)
    if not batched:
        result = jax.tree_util.tree_map(lambda a: a[0], result)
    return result, (int(steps) if block else steps)
