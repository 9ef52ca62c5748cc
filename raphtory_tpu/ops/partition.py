"""Partition-centric (PCPM) edge layout — destination-binned segments.

The resource ledger's roofline harvest (PR 6) classified the hot columnar
kernels ``hbm_bound``: their supersteps are destination-random gathers and
scatter-adds over the whole ``[n_pad, C]`` state, so every edge touches a
cache line the next edge evicts. "Accelerating PageRank using
Partition-Centric Processing" (PCPM, PAPERS.md) is the fix this module
implements: bin edges by DESTINATION PARTITION — a contiguous ``n_per``-row
slice of the dense vertex space sized so a partition's accumulator block
stays cache-resident — and combine messages from one source into a
per-partition bucket BEFORE they cross into the partition ("Node Aware
SpMV"'s aggregate-before-crossing). The scatter side then updates a
resident slice instead of streaming cache lines from HBM, and the gather
side reads each (partition, source) row ONCE instead of once per edge.

The layout is built once per (log, partition count) on the host and cached
next to the device edge tables; compiled kernels receive its arrays as
ordinary traced operands and its :class:`PartitionSpec` as part of their
``lru_cache`` key — both knobs (``RTPU_PCPM``, ``RTPU_PARTITIONS``) are
resolved at DISPATCH time and travel into every compiled-program cache key
through the spec, never read inside a cached factory (rtpulint RT001).

Within each partition, edges sort by (src, dst): the pre-aggregation
bucket reads stream sequentially, and the residual in-partition scatter
lands in the cache-resident slice. Binned float reductions sum in a
different order than the (dst, src)-sorted route — integer/min-plus
results stay bitwise equal, float sums agree to reduction-order tolerance
(docs/KERNELS.md).

The layout is built only where it is asked for by name (``RTPU_PCPM=1``).
Every other value, unset included, keeps every kernel on the dst-sorted
pair table — the route the mesh dispatch always runs. The premise above
is a cache between the accumulator and DRAM; a TPU has none, and there a
superstep costs per row touched: the cap-padded bins are 1.68 x the rows
of the sorted table on the benchmark's graph and the bucket level is a
second gather (PERF.md section 6, PR 29). Nothing in a request's input
tells a device with such a cache from one without, so no code chooses
the layout.
"""

from __future__ import annotations

import threading
import weakref
from typing import NamedTuple

import numpy as np

#: alignment of the per-partition block capacities — keeps pad overhead
#: ~0.1% instead of the up-to-2x a power-of-two pad would cost
_ALIGN = 64

#: modelled last-level cache a partition's accumulator slice must fit in,
#: and the DRAM access granularity — the two constants of the traffic
#: model below (PCPM §3 uses the same shape of model)
CACHE_BYTES = 2 << 20
CACHELINE = 64

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
    resolved ``RTPU_SPARSE_BUCKETS``) — the same shape-stabilising move
    as ``_ALIGN``/``PartitionSpec.cap`` for the binned exchange, applied
    to the DCN slice so every frontier size in a power-of-two band reuses
    one collective shape. ``cap`` (when given) bounds the bucket from
    above — the dense-slice size, past which padding buys nothing."""
    floor = sparse_bucket_floor() if floor is None else max(1, int(floor))
    b = floor
    while b < count:
        b <<= 1
    if cap is not None:
        b = min(b, max(int(cap), 1))
    return b


class PartitionSpec(NamedTuple):
    """Static shape descriptor of a built layout — the hashable component
    every compiled-program cache key carries (``None`` = unbinned)."""

    partitions: int   #: P — destination partitions (contiguous dst ranges)
    n_per: int        #: vertex rows per partition (ceil(n_pad / P))
    cap: int          #: binned edge slots per partition (aligned max load)
    cap_u: int        #: pre-agg bucket slots per partition (aligned max)
    preagg: bool      #: gather through per-(partition, src) buckets


class PartitionLayout:
    """Host arrays of one destination-binned layout + cached device copy.

    Flat binned edge space ``B = P * cap``; slot ``p * cap + i`` is the
    i-th edge of partition ``p`` (edges sorted (src, dst) within the
    partition, cap-padding marked invalid):

    - ``perm [B]``    binned slot → engine edge position (pads → m_pad-1)
    - ``inv [m_pad]`` engine position → binned slot (real edges only)
    - ``b_src [B]``   global src per slot (pads → n_pad-1)
    - ``b_dst [B]``   global dst per slot (pads → n_pad-1)
    - ``valid [B]``   real-edge slots
    - ``slot [B]``    pre-agg bucket per slot, global (p * cap_u + rank)
    - ``u_src [P*cap_u]`` bucket → global src (pads → n_pad-1)
    """

    def __init__(self, spec: PartitionSpec, perm, inv, b_src, b_dst,
                 valid, slot, u_src, n_pad: int, m: int):
        self.spec = spec
        self.perm = perm
        self.inv = inv
        self.b_src = b_src
        self.b_dst = b_dst
        self.valid = valid
        self.slot = slot
        self.u_src = u_src
        self.n_pad = int(n_pad)
        self.m = int(m)
        self._dev = None
        self._lock = threading.Lock()

    def device_args(self) -> tuple:
        """The layout's device operands, uploaded once (chunked + retried
        like the static edge tables) then resident: ``(b_src, b_dst,
        valid, slot, u_src, perm)``. The upload runs OUTSIDE the lock —
        holding it across ``device_put`` would stall every other
        dispatch behind a slow interconnect (the sanitizer's
        lock-across-device-boundary finding); a rare racing duplicate
        upload just gets dropped by the loser."""
        with self._lock:
            dev = self._dev
        if dev is not None:
            return dev
        from ..utils.transfer import device_put_chunked

        dev = tuple(
            device_put_chunked(a) for a in
            (self.b_src, self.b_dst, self.valid, self.slot,
             self.u_src, self.perm))
        with self._lock:
            if self._dev is None:
                self._dev = dev
            return self._dev

    def remap_positions(self, pos: np.ndarray) -> np.ndarray:
        """Engine edge positions → binned slots, preserving the INT32_MAX
        scatter-drop sentinel the padded delta lists use."""
        sentinel = np.int32(2**31 - 1)
        safe = np.clip(pos, 0, len(self.inv) - 1)
        return np.where(pos == sentinel, sentinel,
                        self.inv[safe].astype(np.int32))

    def bin_base(self, lat: np.ndarray, alive: np.ndarray):
        """Engine-order per-pair base state → binned layout (host side, one
        fancy-index each). Invalid (cap-pad) slots are forced dead so the
        kernels never need a separate validity AND."""
        lat_b = lat[self.perm]
        alive_b = alive[self.perm] & self.valid
        return lat_b, alive_b

    def bin_values(self, vals: np.ndarray) -> np.ndarray:
        """Engine-order per-pair values (e.g. SSSP weights) → binned."""
        return vals[self.perm]


class HostTables:
    """Minimal tables surface for :func:`resolve` over a bare edge table
    (engines whose own tables object dropped its host arrays, or a view's
    per-snapshot tables). ``m`` is the REAL row count — the pow2 pad tail
    must become invalid cap-pad slots, never binned edges."""

    __slots__ = ("e_src", "e_dst", "n_pad", "m", "m_pad")

    def __init__(self, e_src, e_dst, n_pad: int, m: int):
        self.e_src = np.asarray(e_src)
        self.e_dst = np.asarray(e_dst)
        self.n_pad = int(n_pad)
        self.m = int(m)
        self.m_pad = len(self.e_src)


def partition_count(n_pad: int, budget_bytes: int,
                    override: int | None = None) -> int:
    """Partitions for an ``n_pad``-row destination space: the override, or
    auto-sized so one partition's f32 accumulator slice (at a reference
    column width of 128) stays within 1/128 of the tile budget — the same
    accounting that sizes the edge tiles (``RTPU_TILE_BUDGET_MB``). For
    the default 256 MB budget that is ``n_per = 2048`` rows."""
    if override is not None and override > 0:
        return max(1, min(int(override), int(n_pad)))
    n_per = max(1024, int(budget_bytes) >> 17)
    return max(1, -(-int(n_pad) // n_per))


def build_layout(e_src: np.ndarray, e_dst: np.ndarray, n_pad: int, m: int,
                 partitions: int) -> PartitionLayout:
    """Build the destination-binned layout for an engine edge table
    (``e_src``/``e_dst`` padded ``[m_pad]``, real edges in ``[0, m)``,
    (dst, src)-sorted). O(m log m) host work, done once per (log, P)."""
    m = int(m)
    m_pad = len(e_dst)
    P = max(1, min(int(partitions), int(n_pad)))
    n_per = -(-int(n_pad) // P)
    src = e_src[:m].astype(np.int64)
    dst = e_dst[:m].astype(np.int64)
    part = dst // n_per
    # (partition, src, dst): bucket reads stream sequentially per partition
    order = np.lexsort((dst, src, part))
    counts = np.bincount(part[order], minlength=P)
    cap = int(max(_ALIGN, -(-int(counts.max(initial=0)) // _ALIGN) * _ALIGN))
    B = P * cap
    off = np.zeros(P + 1, np.int64)
    np.cumsum(counts, out=off[1:])

    part_o = np.repeat(np.arange(P, dtype=np.int64), counts)
    within = np.arange(m, dtype=np.int64) - np.repeat(off[:-1], counts)
    slots = part_o * cap + within                      # binned slot per row

    perm = np.full(B, m_pad - 1, np.int32)
    perm[slots] = order.astype(np.int32)
    inv = np.full(m_pad, B - 1, np.int32)
    inv[order] = slots.astype(np.int32)
    b_src = np.full(B, n_pad - 1, np.int32)
    b_src[slots] = src[order].astype(np.int32)
    b_dst = np.full(B, n_pad - 1, np.int32)
    b_dst[slots] = dst[order].astype(np.int32)
    valid = np.zeros(B, bool)
    valid[slots] = True

    # pre-aggregation buckets: one per (partition, src) run — the
    # (partition, src, dst) sort makes runs contiguous
    keys = part_o * (int(n_pad) + 1) + src[order]
    first = np.ones(m, bool)
    first[1:] = keys[1:] != keys[:-1]
    u_rank = np.cumsum(first) - 1                      # global unique rank
    u_per_part = np.bincount(part_o[first], minlength=P)
    u_off = np.zeros(P + 1, np.int64)
    np.cumsum(u_per_part, out=u_off[1:])
    cap_u = int(max(_ALIGN,
                    -(-int(u_per_part.max(initial=0)) // _ALIGN) * _ALIGN))
    local_rank = u_rank - u_off[part_o]                # rank within part
    slot = np.zeros(B, np.int32)
    slot[slots] = (part_o * cap_u + local_rank).astype(np.int32)
    u_src = np.full(P * cap_u, n_pad - 1, np.int32)
    u_src[(part_o[first] * cap_u + local_rank[first]).astype(np.int64)] = \
        src[order][first].astype(np.int32)

    # the buckets only pay when they are strictly fewer gather rows than
    # the edges themselves (pathological pads can invert that)
    preagg = int(first.sum()) > 0 and P * cap_u < B
    spec = PartitionSpec(P, n_per, cap, cap_u, bool(preagg))
    return PartitionLayout(spec, perm, inv, b_src, b_dst, valid, slot,
                           u_src, n_pad, m)


# ------------------------------------------------------------ resolution

#: per-owner (log / bulk graph / tables) cache of built layouts, keyed by
#: the exact table identity (m, n, P) — the same contract as the device
#: edge-table cache (pairs are never removed from a log, so equal counts
#: mean the identical deterministic table)
_LAYOUTS: "weakref.WeakKeyDictionary" = weakref.WeakKeyDictionary()
_LAYOUTS_LOCK = threading.Lock()


def pcpm_enabled() -> bool:
    """Whether ``RTPU_PCPM`` asks for the binned route by name: only an
    explicit ``"1"`` does. Unset, empty, ``"0"``, ``"auto"`` and typos
    all keep the dst-sorted pair table, at every size (module docstring).
    Read at dispatch time, never inside a cached factory."""
    import os

    return os.environ.get("RTPU_PCPM") == "1"


def tile_budget_bytes() -> int:
    """Resolved ``RTPU_TILE_BUDGET_MB`` in bytes — the ONE parse of the
    budget knob the partition sizing shares with the edge tiling. Always
    called at dispatch time, never inside a cached factory."""
    import os

    return int(os.environ.get("RTPU_TILE_BUDGET_MB", 256)) << 20


def resolve(owner, tables, budget_bytes: int, tag: str = ""):
    """Layout for ``tables`` (GlobalTables / BulkGraph surface: ``e_src``,
    ``e_dst``, ``n_pad``, ``m``, ``m_pad``), or ``None`` unless the binned
    route is asked for by name (``RTPU_PCPM=1``). Reads ``RTPU_PCPM`` /
    ``RTPU_PARTITIONS`` HERE — at dispatch, outside any compiled-program
    factory — so both knobs reach the program cache keys through the
    returned layout's spec. ``owner``
    keys the cross-engine cache (the caller's log object outlives the
    per-engine tables); ``tag`` disambiguates different edge tables of
    one owner (a view's deduped pairs vs its occurrence rows). Runs under
    an ``engine.layout`` span (``stage=resolve``) that says whether the
    cache served it: a miss is an O(m) sort on the caller's thread."""
    from ..obs.trace import TRACER

    with TRACER.span("engine.layout", stage="resolve") as sp:
        layout, cached = _resolve(owner, tables, budget_bytes, tag)
        sp.set(cached=cached,
               partitions=0 if layout is None else layout.spec.partitions)
    return layout


def _resolve(owner, tables, budget_bytes: int, tag: str):
    """``(layout or None, served without building one)``."""
    import os

    if not pcpm_enabled():
        return None, True
    if getattr(tables, "e_src", None) is None:
        return None, True   # host edge tables dropped (device-only surface)
    ov = os.environ.get("RTPU_PARTITIONS")
    P = partition_count(tables.n_pad, budget_bytes,
                        int(ov) if ov else None)
    key = (tag, int(tables.m), int(tables.n_pad), int(P))
    with _LAYOUTS_LOCK:
        try:
            per_owner = _LAYOUTS.get(owner)
            if per_owner is None:
                per_owner = {}
                _LAYOUTS[owner] = per_owner
        except TypeError:
            # unweakrefable or unhashable owner (eq-dataclass views):
            # build uncached — one layout per dispatch, still correct
            per_owner = None
        ent = per_owner.get(key) if per_owner is not None else None
    if ent is not None:
        return ent, True
    layout = build_layout(tables.e_src, tables.e_dst, tables.n_pad,
                          tables.m, P)
    if per_owner is not None:
        with _LAYOUTS_LOCK:
            layout = per_owner.setdefault(key, layout)
    return layout, False


# ---------------------------------------------------------- traffic model


def edge_traffic_model(m_pad: int, C: int, n_pad: int,
                       spec: PartitionSpec | None,
                       itemsize: int = 4) -> dict:
    """Modelled DRAM bytes of ONE message-combine superstep — the
    partition-aware refinement of the ledger's locality-blind XLA
    ``bytes_accessed`` harvest (which counts logical operand bytes and so
    CANNOT see what binning changes; docs/OBSERVABILITY.md). The model is
    the PCPM paper's own accounting: a random access into an operand whose
    working set exceeds :data:`CACHE_BYTES` costs a full
    :data:`CACHELINE`; streamed and cache-resident operands cost their
    payload bytes once.

    Unbinned: every edge gathers a state row at random (all the lines the
    row spans move) and scatter-ADDS a row at random — a read-modify-
    write, so the touched lines move TWICE — over a destination working
    set that outgrows the cache. Binned (``spec``): the gather reads each
    (partition, src) bucket row once, the bucket expansion streams, and
    the scatter lands in a cache-resident ``n_per``-row slice the cache
    absorbs — the payload streams in once and the output writes back
    once.
    """
    row = C * itemsize
    state_bytes = n_pad * row

    def lines(r: int) -> int:            # DRAM bytes one random r-byte
        return -(-r // CACHELINE) * CACHELINE   # row access moves

    rand = lines(row) if state_bytes > CACHE_BYTES else row
    out = {"model": "pcpm_superstep", "columns": int(C)}
    if spec is None:
        streamed = m_pad * (2 * 4 + C)   # ids + bool mask
        # gather: m random row reads; scatter-add: m random r-m-w
        random_bytes = m_pad * rand + 2 * m_pad * rand
        out.update(random_rows=int(2 * m_pad),
                   streamed_bytes=int(streamed),
                   est_hbm_bytes=int(random_bytes + streamed))
        return out
    B = spec.partitions * spec.cap
    slice_bytes = spec.n_per * row
    # gather side: bucket fill (random into the full state) + streamed
    # expansion through the resident bucket
    u_rows = spec.partitions * spec.cap_u if spec.preagg else B
    gather_bytes = u_rows * rand + (B * row if spec.preagg else 0)
    # scatter side: the partition slice lives in cache, so the payload
    # streams in once and the accumulator writes back once
    if slice_bytes <= CACHE_BYTES:
        scatter_bytes = B * row + n_pad * row
    else:                                # partitions mis-sized: random
        scatter_bytes = 2 * B * lines(row)
    streamed = B * (2 * 4 + C)           # ids + bool mask
    out.update(random_rows=int(u_rows),
               streamed_bytes=int(streamed),
               est_hbm_bytes=int(gather_bytes + scatter_bytes + streamed))
    return out
