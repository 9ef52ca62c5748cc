"""Device-resident range sweeps — ship O(delta) bytes per hop, not O(m).

The host-side range sweep (``core/sweep.py`` + ``bsp.run_async``) already
amortises the *fold*: hop T_{i+1} re-folds only the events in (T_i, T_{i+1}].
But it still re-assembles and re-uploads fresh O(m_pad) edge arrays every hop
— per-view local vertex indices change as vertices appear/die, so nothing on
the device can be reused, and the H2D traffic grows with the graph, not
with what changed.

This engine removes the per-hop re-indexing by construction:

* **Global dense index space.** Vertices are indexed by their rank in the
  sorted set of every id the pinned log ever mentions (``SweepBuilder.uv``);
  the edge table is every (src, dst) pair the log ever mentions, sorted once
  by (dst, src). Both are uploaded ONCE. Positions never change across the
  sweep — dead entities are simply masked.
* **Device-resident fold state.** Per-entity ``latest_time / alive /
  first_time`` live in donated device buffers. Each hop ships only the
  touched rows (``SweepBuilder.last_delta``) and scatters them in on device.
* **On-device window masks.** ``in-window(T, W) ⟺ alive ∧ latest ≥ T − W``
  (``Entity.aliveAtWithWindow``, ``Entity.scala:193-201``) is computed on
  device from the resident arrays — masks are never built, packed, or
  transferred by the host.

The reference re-runs its full actor handshake per range hop
(``RangeAnalysisTask.scala:18-35``); the host path amortises the fold; this
engine amortises the *device traffic* too, which is the term that actually
bounds a TPU sweep.

Supported programs: anything that doesn't need occurrence arrays or
edge/vertex properties (property materialisation is a host-side join today —
such programs fall back to the ``bsp`` path, see ``supported()``).
"""

from __future__ import annotations

import contextlib
import copy
import functools
import threading as _threading
import time as _time
import weakref

import jax
import jax.numpy as jnp
import numpy as np

from ..core.events import EDGE_ADD, EDGE_DELETE, EventLog
from ..core.snapshot import INT64_MIN, _pad_bucket
from ..core.sweep import _ENC_MASK, _ENC_SHIFT, SweepBuilder, fork_status
from ..native import lib as _native
from ..obs import ledger as _ledger
from ..obs.trace import TRACER
from ..resilience import faults as _faults
from ..utils.transfer import _metrics
from .bsp import make_mask_runner, state_pack
from .program import VertexProgram


def sweep_phase_summary(sp, elapsed, fold_seconds, fold_stall_seconds,
                        ship_delta, ship_bytes, n_hops, fold_modes=None,
                        fold_inline_seconds=0.0):
    """Per-sweep fold/stage/ship/compute phase breakdown, attached to the
    sweep span AND observed into ``raphtory_sweep_phase_seconds{phase}``
    — shared by both sweep engines. The four phases PARTITION the
    dispatch loop's wall (``elapsed``), all of it seconds of the thread
    that drove the sweep: ``fold`` is what that thread folded inline
    (``fold_inline_seconds``: one group, or prefetch off) plus what it
    stalled waiting on a worker's fold (``fold_stall_seconds``);
    ``stage``/``ship`` are the transfer engine's staging-copy and
    wire-wait stalls accumulated during THIS sweep
    (``TransferStats.delta_since``); ``compute`` is the rest — device
    compute plus Python driving. ``fold_seconds`` is the fold's COST
    (worker-thread time under the lookahead prefetcher or the parallel
    pool, overlapped with the device): it rides the span and, by mode
    (``fold_modes``), the ledger's ``fold`` block, and is no phase of the
    wall. Per-hop numbers are these divided by ``n_hops``. Returns the
    phase dict (engines keep it as ``last_phase_seconds``).

    Attribution caveat: the stage/ship deltas come from the PROCESS-WIDE
    shared transfer engine, so when several jobs sweep concurrently each
    summary includes the others' H2D stalls (and compute, the residual,
    shrinks correspondingly). Serial operation — the bench protocol and
    the common single-job server — attributes exactly; for contended
    timelines read the per-slice ``ship.*`` spans, which carry their own
    thread/track, instead of the summary."""
    stage = float(ship_delta.get("stage_stall_seconds", 0.0))
    wire = float(ship_delta.get("wire_stall_seconds", 0.0))
    fold = float(fold_inline_seconds) + float(fold_stall_seconds)
    phases = {
        "fold": fold,
        "stage": stage,
        "ship": wire,
        "compute": max(float(elapsed) - fold - stage - wire, 0.0),
    }
    m = _metrics()
    if m is not None:
        for ph, sec in phases.items():
            m.sweep_phase_seconds.labels(ph).observe(sec)
    led = _ledger.current()
    if led is not None:
        # per-query cost attribution: the sweep ran on THIS (the job's)
        # thread, so the thread-local ledger is the owning query's
        led.add_sweep(phases, ship_delta, ship_bytes, n_hops,
                      fold_modes=fold_modes)
    sp.set(elapsed_seconds=round(float(elapsed), 6),
           fold_cost_seconds=round(float(fold_seconds), 6),
           fold_stall_seconds=round(float(fold_stall_seconds), 6),
           ship_bytes=int(ship_bytes), n_hops=int(n_hops),
           **{f"{ph}_seconds": round(sec, 6) for ph, sec in phases.items()})
    return phases


def supported(program: VertexProgram) -> bool:
    """True if `program` can run on the device-resident sweep engine."""
    return (not program.needs_occurrences
            and not program.edge_props
            and not program.vertex_props)


def _pad_large(n: int) -> int:
    """Power-of-two buckets up to 2^16 (compile reuse across small logs),
    then 2^16-multiples — pow2 padding would waste up to 2x of every
    per-edge gather at GAB scale and beyond."""
    if n <= (1 << 16):
        return _pad_bucket(n)
    step = 1 << 16
    return ((n + step - 1) // step) * step


#: per-log cache of the device-uploaded static (src, dst) engine tables —
#: a cold engine over an unchanged log reuses the resident arrays instead
#: of re-shipping 2 * m_pad int32 over the host↔device link per query
_DEVICE_EDGES = weakref.WeakKeyDictionary()


def _device_edges(log, tables):
    """Device (e_src, e_dst) for ``tables``, cached per log (the CALLER's
    log object, not the per-engine pin). The (m, n) key is exact: pairs
    and vertices are never removed from a log, so equal counts mean the
    identical deterministic table (same pair set, same dense ranks, same
    (dst, src) sort). Shared by the hop-batched engines and DeviceSweep."""
    ent = _DEVICE_EDGES.get(log)
    if ent is not None and ent[0] == tables.m and ent[1] == tables.n:
        return ent[2], ent[3]
    from ..utils.transfer import device_put_chunked

    # chunked + retried: at 10^8-pair scale these are the largest single
    # transfers in the system, and a monolithic put is all-or-nothing
    # under a transport error
    es = device_put_chunked(tables.e_src)
    ed = device_put_chunked(tables.e_dst)
    _DEVICE_EDGES[log] = (tables.m, tables.n, es, ed)
    # resident-buffer gauge (obs/device.py): the static edge tables are
    # the largest long-lived device allocation — weakref-keyed on the
    # SAME log object as the cache above, so the row dies with the entry
    from ..obs import device as _obs_device

    _obs_device.RESIDENT.track(
        log, "edge_tables",
        _obs_device.nbytes_tree((es, ed)), m=tables.m)
    return es, ed


class GlobalTables:
    """Static global-dense-space graph tables over a pinned log: every
    vertex id the log ever mentions (rank in ``uv`` = dense index) and every
    (src, dst) pair, (dst, src)-sorted. Positions never change across a
    sweep — shared by the single-chip ``DeviceSweep`` and the mesh
    ``parallel.sweep.ShardedSweep``."""

    def __init__(self, sw: SweepBuilder):
        if not sw._ok:
            raise ValueError("log has >= 2^31 distinct vertices — the packed "
                             "pair key space is exhausted; use build_view")
        self.uv = sw.uv
        if sw._preseeded:
            # a preseeded sweep's pair table IS the all-pairs table (and
            # never grows) — no second unique over the edge events
            self.all_enc = sw.e_enc
        else:
            is_e = (sw._k == EDGE_ADD) | (sw._k == EDGE_DELETE)
            if is_e.any():
                enc = ((sw._dense(sw._s[is_e]) << _ENC_SHIFT)
                       | sw._dense(sw._d[is_e]))
                self.all_enc = np.unique(enc)
            else:
                self.all_enc = np.empty(0, np.int64)

        self.n = len(self.uv)
        self.m = len(self.all_enc)
        self.n_pad = _pad_large(self.n)
        self.m_pad = _pad_large(self.m)
        # times narrow to i32 when the whole log fits — halves both the
        # resident fold state and the delta bytes, and skips the TPU's
        # emulated 64-bit compares in the per-hop window masks
        tcol = sw._t
        self.tdtype = (
            np.int32 if len(tcol) == 0
            or (tcol.min() > np.iinfo(np.int32).min // 2
                and tcol.max() < np.iinfo(np.int32).max // 2)
            else np.int64)
        self.tmin = np.iinfo(self.tdtype).min

        # engine edge order: (dst, src) — combine-at-destination segment ops
        # run with indices_are_sorted=True (snapshot.py uses the same order)
        flip = ((self.all_enc & _ENC_MASK) << _ENC_SHIFT) \
            | (self.all_enc >> _ENC_SHIFT)
        order = np.argsort(flip)              # engine pos i ← enc rank
        self.eng_of_rank = np.empty(self.m, np.int64)
        self.eng_of_rank[order] = np.arange(self.m)

        self.e_src = np.full(self.m_pad, self.n_pad - 1, np.int32)
        self.e_dst = np.full(self.m_pad, self.n_pad - 1, np.int32)
        eng_enc = self.all_enc[order]
        self.e_src[: self.m] = (eng_enc >> _ENC_SHIFT).astype(np.int32)
        self.e_dst[: self.m] = (eng_enc & _ENC_MASK).astype(np.int32)
        self.vids = np.full(self.n_pad, -1, np.int64)
        self.vids[: self.n] = self.uv

    def eng_pos(self, enc: np.ndarray) -> np.ndarray:
        """Engine positions of packed pair keys (must exist in the log).
        Packed keys are non-negative (dense<<32|dense), so the sorted i64
        table reinterprets as u64 zero-copy for the native parallel
        searchsorted — the hot per-hop lookup at 10^8-pair scale."""
        if len(enc) > (1 << 16) and _native.available():
            idx = _native.searchsorted_u64(
                self.all_enc.view(np.uint64),
                np.ascontiguousarray(enc).view(np.uint64))
            return self.eng_of_rank[idx]
        return self.eng_of_rank[np.searchsorted(self.all_enc, enc)]

    def holds_times(self, t: np.ndarray) -> bool:
        """True if times ``t`` (an appended suffix's) fit the resident
        time dtype chosen from the log these tables were built over —
        the check every ``repin`` makes before keeping its tables."""
        if self.tdtype == np.int64 or not len(t):
            return True
        return bool(int(t.min()) > np.iinfo(np.int32).min // 2
                    and int(t.max()) < np.iinfo(np.int32).max // 2)

    def cast_times(self, a: np.ndarray) -> np.ndarray:
        """i64 fold times → the narrow resident dtype (INT64_MIN pad maps to
        the narrow dtype's min) — shared by every engine over these tables."""
        if self.tdtype == np.int64:
            return a
        return np.where(a == INT64_MIN, self.tmin, a).astype(self.tdtype)


class LogIndex:
    """What the device engines derive from a log ALONE, built once per
    log and forked per engine: a PRISTINE preseeded ``SweepBuilder``
    (``t_prev is None``; never advanced, never handed out) and the
    ``GlobalTables`` over it. The builder's pin carries the log's
    fold-cache fingerprint (``core/sweep.log_fingerprint`` caches it on
    the pin), so engines forked from one index share it too. Everything
    here is read-only to its users: a fork shares the log-derived arrays
    by reference and copies the fold state (``SweepBuilder.fork``)."""

    __slots__ = ("prototype", "tables", "triangles", "partitions")

    def __init__(self, log: EventLog):
        # fold state only (the engines never emit GraphViews, shells are
        # vertex-side) — no add-row tracking
        self.prototype = SweepBuilder(log, track_rows=False,
                                      preseed_pairs=True)
        with TRACER.span("index.tables"):
            self.tables = GlobalTables(self.prototype)
        #: the pair table's triangles (``ops/triangles.TriangleTable``):
        #: built by the first engine that intersects neighbour sets
        #: (``log_triangles``), never for another program
        self.triangles = None
        #: the pair table's static partitions for the vertex-sharded mesh
        #: route (``parallel/sweep.StaticPartition``), by shard count:
        #: built by the first mesh Range that asks (``log_partition``)
        self.partitions: dict = {}

    @property
    def nbytes(self) -> int:
        """Host bytes this index keeps alive beyond the log's own
        columns (``_t``/``_k``/``_s``/``_d`` are views of those)."""
        own = {k: v for k, v in vars(self.prototype).items()
               if k not in ("_t", "_k", "_s", "_d")}
        arrays = {id(a): a for a in (*own.values(),
                                     *vars(self.tables).values())
                  if isinstance(a, np.ndarray)}
        return int(sum(a.nbytes for a in arrays.values())) + (
            self.triangles.nbytes if self.triangles is not None else 0) \
            + sum(p.nbytes for p in self.partitions.values())

    def adopt(self, log: EventLog) -> str:
        """Bring the index up to ``log``'s current pin, exactly:
        ``"hit"`` — same ``(n, compactions)``, so the pin's content is
        the index's (rows below ``n`` never mutate); ``"extended"`` — the
        log grew by a suffix with no new id or pair and no time past the
        tables' dtype, adopted in O(suffix); ``"grown"`` — the suffix
        brought new ids or pairs and the prototype's dictionaries grew to
        hold them (``SweepBuilder.repin``), the tables made anew over it
        and the triangle table, a function of the pairs, dropped;
        ``"miss"`` — anything else (compaction, shrink, an empty pin):
        the caller builds a new index. The fingerprint is carried over
        a suffix either way. Caller holds the lock."""
        sw = self.prototype
        n_old = len(sw._t)
        status = sw.repin(log)
        if status == "noop":
            return "hit"
        if status == "grown":
            with TRACER.span("index.tables", grow=True):
                self.tables = GlobalTables(sw)
            self.triangles = None
            self.partitions = {}
        elif status != "extended" \
                or not self.tables.holds_times(sw._t[n_old:]):
            return "miss"   # the prototype may be rebound: discard it
        return status


#: per-log cache of the index above, keyed by the CALLER's live log the
#: way ``_DEVICE_EDGES`` is — one entry per log, replaced (never
#: accumulated) when it goes stale, freed with the log
_LOG_INDEXES = weakref.WeakKeyDictionary()
_LOG_INDEX_LOCK = _threading.Lock()
#: lookups by outcome, and ``grown``: the lookups among ``extended`` whose
#: suffix brought new ids or pairs, so the index's dictionaries grew
_LOG_INDEX_COUNTS = {"hit": 0, "extended": 0, "miss": 0, "grown": 0}


def log_index(log: EventLog):
    """``(builder, tables, status)`` for a new engine over ``log``: a
    private fork of the log's cached index, built here on a ``"miss"``
    (``LogIndex.adopt`` names the statuses; a lookup that grew the index
    reads ``"extended"`` here and is counted as ``grown`` besides). One
    lock covers the lookup, a build and the fork — two jobs arriving
    together build the index once, and a fork never sees a half-rebound
    prototype. ``tables`` is SHARED between engines and must not be
    written. A frozen log is
    its own pin, so an entry would keep its weak key alive: such a log
    gets an index of its own every time (status ``"miss"``), uncached.

    The stages are spans (children of the caller's ``engine.build``,
    whose ``index`` attribute is the status): ``index.lookup`` — the wait
    for the lock, which another request's miss holds for its whole build,
    and ``adopt``; on a miss the build's ``index.ids`` / ``index.pairs``
    (``SweepBuilder.__init__``) and ``index.tables``; ``index.fork``. A
    hit writes the first and the last only. A growth writes the three
    build stages too, inside ``index.lookup``, each with ``grow=True``
    (``index.ids``: ``new_ids``; ``index.pairs``: ``new_pairs``), and a
    carried fingerprint a ``fold.fingerprint`` with ``extend=True``."""
    with contextlib.ExitStack() as lookup:
        lookup.enter_context(TRACER.span("index.lookup"))
        with _LOG_INDEX_LOCK:
            idx = _LOG_INDEXES.get(log)
            status = "miss" if idx is None else idx.adopt(log)
            lookup.close()      # the span ends here, the lock is kept
            if status == "miss":
                _LOG_INDEXES.pop(log, None)   # free the stale one first
                idx = LogIndex(log)
                if idx.prototype.log is not log:
                    _LOG_INDEXES[log] = idx
            if status == "grown":
                _LOG_INDEX_COUNTS["grown"] += 1
                status = "extended"
            _LOG_INDEX_COUNTS[status] += 1
            # the fork shares the prototype's fold state and copies it
            # at its first write, if it ever writes (a ``fold.seed`` span
            # with ``deferred=true``): nothing is copied here
            with TRACER.span("index.fork", nbytes=0,
                             shared=idx.prototype.fork_nbytes()):
                return idx.prototype.fork(), idx.tables, status


def log_triangles(log: EventLog, tables: GlobalTables):
    """``(table, status)``: the triangle table
    (``ops/triangles.TriangleTable``) of ``tables``, the pair table an
    engine got from ``log_index(log)``: kept on the log's index
    (``"held"``), built here the first time an engine asks (``"built"``)
    — span ``index.triangles``, a child of that engine's
    ``engine.build``, absent when the index holds the table already. The
    table goes with the pairs it was built from (a log that gains one
    gets new tables, or a new index: ``LogIndex.adopt``). An uncached
    index (a frozen log's) gets a table of its own every time. Holds the
    index lock for the build, as an index miss does."""
    from ..ops.triangles import build_table

    with _LOG_INDEX_LOCK:
        idx = _LOG_INDEXES.get(log)
        if idx is not None and idx.tables is not tables:
            idx = None      # the engine's tables outlived their index
        if idx is not None and idx.triangles is not None:
            return idx.triangles, "held"
        with TRACER.span("index.triangles", pairs=tables.m) as sp:
            tt = build_table(tables.e_src, tables.e_dst, tables.m,
                             tables.n, tables.n_pad, tables.m_pad)
            sp.set(triangles=tt.triangles, rows=tt.walked_rows,
                   nbytes=tt.nbytes)
        if idx is not None:
            idx.triangles = tt
        return tt, "built"


def log_partition(log: EventLog, tables: GlobalTables, n_shards: int,
                  build):
    """``(partition, status)``: ``build(tables, n_shards)`` (the
    vertex-sharded route's ``parallel/sweep.StaticPartition``: the range
    partition of the pair table over ``n_shards`` and its halo layouts)
    of ``tables``, the pair table an engine got from ``log_index(log)``:
    kept on the log's index by shard count (``"held"``), built here the
    first time a mesh Range asks (``"built"``). It goes with the pairs it
    was cut from, as the triangle table does (``log_triangles``); an
    uncached index (a frozen log's) gets one of its own every time.
    Holds the index lock for the build, as an index miss does."""
    with _LOG_INDEX_LOCK:
        idx = _LOG_INDEXES.get(log)
        if idx is not None and idx.tables is not tables:
            idx = None      # the engine's tables outlived their index
        if idx is not None and n_shards in idx.partitions:
            return idx.partitions[n_shards], "held"
        part = build(tables, n_shards)
        if idx is not None:
            idx.partitions[n_shards] = part
        return part, "built"


#: per-log cache of the device copy of the table above, as ``_DEVICE_EDGES``
#: is of the pair table: a new engine over an unchanged log ships nothing
_DEVICE_TRIANGLES = weakref.WeakKeyDictionary()


def _device_triangles(log, tt) -> tuple:
    """Device arrays of ``tt`` in ``ops/triangles.lcc_columns``'s order,
    cached per log while the index keeps that table."""
    ent = _DEVICE_TRIANGLES.get(log)
    if ent is not None and ent[0] is tt:
        return ent[1]
    from ..obs import device as _obs_device
    from ..utils.transfer import device_put_chunked

    dev = tuple(device_put_chunked(a) for a in tt.device_args())
    _DEVICE_TRIANGLES[log] = (tt, dev)
    _obs_device.RESIDENT.track(log, "triangle_table",
                               _obs_device.nbytes_tree(dev),
                               triangles=tt.triangles)
    return dev


#: per-log cache of what the columnar kind ``sgc`` keeps resident beside
#: the pair table: the propagation table (a function of the pair table
#: alone) and ONE feature block, the last (dim, seed) asked for — an
#: immutable ``(key, table, of, X)`` swapped whole, as ``_DEVICE_TRIANGLES``
_DEVICE_FEATURES = weakref.WeakKeyDictionary()


def _device_features(log, tables, dim: int, seed: int):
    """``(X, table, status)`` for ``tables``: the device feature block
    ``[n_pad, dim]`` of the log's vertex ids (``ops/propagate.features``)
    made ON the device, and the propagation table of its pair table
    (``ops/propagate.build_table``: a host sort, put on the device), once
    a log:
    ``status`` is ``"built"`` when this call made either, ``"held"`` when
    the cache had both. Keyed like ``_device_edges`` (equal counts mean
    the identical table). What is returned is what this call read or
    made, never re-read from the cache: two jobs with different seeds on
    one log each serve their own features, whoever publishes last."""
    import jax
    import jax.numpy as jnp

    from ..obs import device as _obs_device
    from ..ops import propagate

    key, of = (tables.m, tables.n), (int(dim), int(seed))
    ent = _DEVICE_FEATURES.get(log)
    _, table, held_of, X = ent if ent is not None and ent[0] == key \
        else (key, None, None, None)
    if table is not None and held_of == of:
        return X, table, "held"
    if table is None:
        from ..utils.transfer import device_put_chunked

        table = propagate.PropagationTable(*(
            device_put_chunked(a) for a in propagate.build_table(
                tables.e_src, tables.e_dst, int(tables.n_pad))))
    if held_of != of:
        # from ``uv``: a DeviceSweep over the same tables frees ``vids``
        vids = np.full(tables.n_pad, -1, np.int64)
        vids[: tables.n] = tables.uv
        X = jax.jit(propagate.features, static_argnums=(1, 2))(
            jnp.asarray(vids), *of)
    _DEVICE_FEATURES[log] = (key, table, of, X)
    _obs_device.RESIDENT.track(
        log, "feature_tables",
        _obs_device.nbytes_tree((X, tuple(table))), dim=int(dim))
    return X, table, "built"


def log_index_status() -> dict:
    """The ``log_index`` block of ``/statusz``: lookups by outcome since
    start (``grown``: the lookups among ``extends`` whose suffix brought
    new ids or pairs, so the index's dictionaries grew where they were
    rebuilt, a miss, until PR 45), the host bytes the live indexes
    hold, and what became of the forks (``core/sweep.fork_status``):
    every ``SweepBuilder.fork`` of the process — an engine's from the
    index, a fold unit's from a checkpoint or a live builder — shares
    the fold state it starts from, and ``fork_copies`` of the ``forks``
    went on to copy it (``fork_copied_bytes``) because they wrote."""
    with _LOG_INDEX_LOCK:
        c = _LOG_INDEX_COUNTS
        return {"hits": c["hit"], "extends": c["extended"],
                "grown": c["grown"], "misses": c["miss"],
                "bytes": sum(i.nbytes for i in _LOG_INDEXES.values()),
                **fork_status()}


def normalize_windows(windows) -> list[int]:
    """window list → int list with -1 for 'no window' (engine convention)."""
    return [(-1 if w is None else int(w)) for w in windows]


@functools.lru_cache(maxsize=32)
def _compiled_apply(cap_v: int, cap_e: int, tdt: str):
    """Scatter one (padded) delta chunk into the six fold-state buffers.
    Chunk capacities are fixed per sweep, so this compiles exactly once;
    pad rows carry index -1 and are dropped by the scatter."""

    def apply(v_lat, v_alive, v_first, e_lat, e_alive, e_first,
              v_idx, vd_lat, vd_alive, vd_first,
              e_idx, ed_lat, ed_alive, ed_first):
        v_lat = v_lat.at[v_idx].set(vd_lat, mode="drop")
        v_alive = v_alive.at[v_idx].set(vd_alive, mode="drop")
        v_first = v_first.at[v_idx].set(vd_first, mode="drop")
        e_lat = e_lat.at[e_idx].set(ed_lat, mode="drop")
        e_alive = e_alive.at[e_idx].set(ed_alive, mode="drop")
        e_first = e_first.at[e_idx].set(ed_first, mode="drop")
        return v_lat, v_alive, v_first, e_lat, e_alive, e_first

    return _ledger.instrument(
        "device_sweep.apply",
        jax.jit(apply, donate_argnums=(0, 1, 2, 3, 4, 5)))


@functools.lru_cache(maxsize=256)
def _compiled_run(program: VertexProgram, n: int, m: int, k: int, tdt: str):
    """Mask-compute + superstep program over the resident fold state —
    one compile per (program, shapes, #windows), shared across hops AND
    across DeviceSweep instances of the same padded size."""
    core = make_mask_runner(program, n, m, k)
    tdt = jnp.dtype(tdt)

    def run(v_lat, v_alive, v_first, e_lat, e_alive, e_first,
            vids, e_src, e_dst, time, windows):
        # window-mask compares run in the narrow time dtype: the resident
        # lat values fit it by construction, and lo clamps into range (a
        # clamped lo only widens the window past every real timestamp)
        info = jnp.iinfo(tdt)
        lo = jnp.clip(time - windows, info.min, info.max).astype(tdt)[:, None]
        nowin = (windows < 0)[:, None]
        v_masks = v_alive[None, :] & (nowin | (v_lat[None, :] >= lo))
        e_masks = e_alive[None, :] & (nowin | (e_lat[None, :] >= lo))
        # the Edges/Context contract is i64 times; only widen when the
        # program actually reads them (pad slots map to INT64_MIN exactly)
        def widen(a):
            if a.dtype == jnp.int64:
                return a
            return jnp.where(a == info.min, jnp.iinfo(jnp.int64).min,
                             a.astype(jnp.int64))
        if program.needs_vertex_times:
            v_lat, v_first = widen(v_lat), widen(v_first)
        if program.needs_edge_times:
            e_lat, e_first = widen(e_lat), widen(e_first)
        return core(v_masks, e_masks, vids, v_lat, v_first,
                    e_src, e_dst, e_lat, e_first, time, windows, {}, {})

    return _ledger.instrument(
        f"device_sweep.superstep.{type(program).__name__}", jax.jit(run))


class DeviceSweep:
    """Ascending-time range sweep with device-resident fold state.

    Drives a ``SweepBuilder`` for the host fold (delta semantics identical to
    ``build_view`` — killList propagation, delete-wins, revival), mirrors the
    touched rows into fixed-position device buffers, and dispatches compiled
    superstep programs whose window masks are derived on device.

    ``run(program, T, ...)`` returns ``(result, steps)`` as device arrays
    (async — block with ``jax.block_until_ready`` when needed). Results are
    in the GLOBAL dense vertex space: row i is vertex ``self.uv[i]``.
    """

    def __init__(self, log: EventLog):
        # the log's shared index: fold builder forked, tables a shallow
        # copy (this sweep drops its references to the host tables below;
        # the index's own stay whole)
        self.sw, tables, self.index_status = log_index(log)
        t = self.tables = copy.copy(tables)
        self.uv = t.uv
        self.all_enc = t.all_enc
        self.n, self.m = t.n, t.m
        self.n_pad, self.m_pad = t.n_pad, t.m_pad
        self._eng_of_rank = t.eng_of_rank

        # static device uploads — shared per log across sweeps (a repeat
        # View/rebuild over an unchanged log must not re-pay the transfer);
        # the host copies are not needed again on the single-chip path —
        # drop them rather than pin O(m_pad + n_pad) numpy for the sweep's
        # lifetime (over a frozen log, whose index is this sweep's alone)
        self.e_src, self.e_dst = _device_edges(log, t)
        self.vids = jnp.asarray(t.vids)
        t.e_src = t.e_dst = t.vids = None

        # fold-state buffers (donated through every delta application), in
        # the narrow time dtype the log fits (tables.tdtype)
        self.tdtype = t.tdtype
        self._tmin = t.tmin
        tdt = jnp.dtype(self.tdtype)
        self._bufs = (
            jnp.full((self.n_pad,), self._tmin, tdt),    # v_lat
            jnp.zeros((self.n_pad,), bool),              # v_alive
            jnp.full((self.n_pad,), self._tmin, tdt),    # v_first
            jnp.full((self.m_pad,), self._tmin, tdt),    # e_lat
            jnp.zeros((self.m_pad,), bool),              # e_alive
            jnp.full((self.m_pad,), self._tmin, tdt),    # e_first
        )
        # resident-buffer gauge (obs/device.py): the fold-state buffers
        # live exactly as long as this sweep — weakref-keyed on self
        from ..obs import device as _obs_device

        _obs_device.RESIDENT.track(
            self, "fold_state",
            _obs_device.nbytes_tree(self._bufs)
            + _obs_device.nbytes_tree((self.vids,)))
        # delta chunk capacities: big enough that a typical hop is one chunk,
        # fixed so the scatter program compiles exactly once per sweep shape
        self.cap_v = max(1024, self.n_pad // 4)
        self.cap_e = max(4096, self.m_pad // 16)
        self.t_now: int | None = None
        #: host seconds spent folding + staging (includes worker-thread time
        #: when run_sweep pipelines) and fold-state bytes staged for H2D
        self.fold_seconds = 0.0
        #: fold seconds split by pipeline mode (serial lane vs forked
        #: parallel folds) — the resource ledger's fold breakdown; single
        #: writer per mode (the one prefetch worker, or the dispatch
        #: thread's consume), like fold_seconds itself
        self.fold_mode_seconds: dict = {}
        self.ship_bytes = 0
        #: run_sweep only: seconds the dispatch loop spent WAITING on the
        #: lookahead fold — 0 means the fold fully hid behind device compute
        self.fold_stall_seconds = 0.0
        #: run_sweep only: the part of ``fold_seconds`` the dispatch loop's
        #: own thread folded inline (no lookahead) — with the stall, the
        #: ``fold`` phase of the sweep's wall
        self.fold_inline_seconds = 0.0
        #: the LAST run_sweep's fold/stage/ship/compute breakdown
        #: (``sweep_phase_summary``) — the per-sweep phase summary
        self.last_phase_seconds: dict = {}
        # a failure between fold and device apply leaves t_now ahead of
        # _bufs (the lookahead fold may even have advanced PAST the failed
        # hop) — the next fold must take the full-refresh path, never the
        # time==t_now noop or a delta scatter onto stale buffers
        self._stale = False

    # ---- incremental re-pin (live serving) ----

    def repin(self, live_log) -> str:
        """Adopt rows appended to ``live_log`` since this sweep's pin
        (``SweepBuilder.repin``). On ``"extended"`` everything stays
        valid — the dense spaces are unchanged, so the static device
        tables, the fold-state buffers and ``t_now`` keep describing the
        same coordinate space, and the next ``advance`` folds exactly
        the appended suffix as one delta instead of a from-scratch
        rebuild. Returns ``"noop"`` / ``"extended"`` / ``"rebuild"``;
        after ``"rebuild"`` the sweep must be DISCARDED."""
        if self._stale:
            return "rebuild"   # buffers behind the clock: re-pin fresh
        sfx = self.sw.suffix(live_log)
        if isinstance(sfx, str):
            return sfx
        if sfx.grows or not self.tables.holds_times(sfx.t):
            # new ids or pairs (the device buffers sit in the old dense
            # space: the re-pin's engine is built over the GROWN index,
            # ``log_index``), or a time past the narrowed dtype
            return "rebuild"
        return self.sw.adopt(sfx)

    # ---- sweep driving ----

    def advance(self, time: int) -> None:
        """Fold events in (t_now, time] on host and mirror the touched rows
        into the device buffers. Times must be non-decreasing."""
        self._apply_staged(self._fold_hop(time))

    def _fold_hop(self, time: int) -> dict:
        """Host half of one hop: fold events in (t_now, time] and STAGE the
        touched rows as padded contiguous arrays, ready to ship. Pure
        numpy — safe to run in the prefetch worker while the previous
        hop's scatter + superstep run on device. The returned payload
        carries its own hop time (``self.t_now`` keeps moving under a
        lookahead fold)."""
        with TRACER.span("hop.fold", time=int(time),
                            engine="device_sweep") as sp:
            payload = self._fold_hop_inner(time)
            sp.set(kind=payload["kind"])
        return payload

    def _fold_hop_inner(self, time: int) -> dict:
        f0 = _time.perf_counter()
        time = int(time)
        if self.t_now is not None and time < self.t_now:
            if not self._stale:
                raise ValueError(
                    f"DeviceSweep times must ascend "
                    f"(got {time} < {self.t_now})")
            # stale REWIND recovery: a mid-sweep failure can leave the
            # lookahead fold (and t_now) PAST the hop a caller retries —
            # how far depends on thread timing, so the ascending
            # contract cannot be enforced against it. The fold only
            # ascends, so rebuild the builder from the (pinned) log and
            # refold to `time`; the stale path below restages the FULL
            # state either way, and the device buffers were already
            # behind the clock.
            self.sw = SweepBuilder(self.sw.log, track_rows=False,
                                   preseed_pairs=True)
            self.t_now = None
        advanced = self.t_now is None or time > self.t_now
        if advanced:
            self.sw._advance(time)
            self.t_now = time
        if self._stale:
            # recover from an aborted earlier hop: re-stage the FULL fold
            # state (the running sw is authoritative; the device buffers
            # are behind by an unknown number of hops). Cleared here —
            # a failed apply re-marks stale before the error propagates.
            self._stale = False
            payload = {"time": time, "kind": "full",
                       "arrays": self._stage_full()}
            self._note_fold(_time.perf_counter() - f0, "serial")
            return payload
        if not advanced:   # repeat hop on healthy buffers: nothing to ship
            return {"time": time, "kind": "noop"}
        payload = self._stage_payload(self.sw, time)
        self._note_fold(_time.perf_counter() - f0, "serial")
        return payload

    def _note_fold(self, seconds: float, mode: str) -> None:
        self.fold_seconds += seconds
        self.fold_mode_seconds[mode] = (
            self.fold_mode_seconds.get(mode, 0.0) + seconds)

    def _stage_payload(self, sw, time: int) -> dict:
        """Staged payload for ``sw``'s LAST advance (to ``time``): noop /
        full-refresh / padded delta chunks. The ONE copy of the staging
        policy — the engine-clock fold (``_fold_hop_inner``) and the
        forked parallel fold (``_fold_hop_fork``) both stage through it,
        so the two paths can never diverge."""
        d = sw.last_delta
        nv, ne = len(d["v_idx"]), len(d["e_enc"])
        if nv == 0 and ne == 0:
            return {"time": time, "kind": "noop"}
        # full-state refresh (first hop, or a delta so large that chunked
        # scatters would ship more than the whole buffers): host-assemble
        # and device_put — one transfer, no scatter program involved
        if nv > self.n_pad // 2 or ne > self.m_pad // 2:
            return {"time": time, "kind": "full",
                    "arrays": self._stage_full(sw)}
        e_pos = self.tables.eng_pos(d["e_enc"])
        n_chunks = max(-(-nv // self.cap_v), -(-ne // self.cap_e), 1)
        chunks = []
        for i in range(n_chunks):
            ov, oe = i * self.cap_v, i * self.cap_e
            # out-of-range slices are empty; pad rows scatter out of
            # bounds and are dropped
            chunks.append(self._stage_chunk(
                d["v_idx"][ov: ov + self.cap_v],
                d["v_lat"][ov: ov + self.cap_v],
                d["v_alive"][ov: ov + self.cap_v],
                d["v_first"][ov: ov + self.cap_v],
                e_pos[oe: oe + self.cap_e],
                d["e_lat"][oe: oe + self.cap_e],
                d["e_alive"][oe: oe + self.cap_e],
                d["e_first"][oe: oe + self.cap_e],
            ))
        return {"time": time, "kind": "chunks", "chunks": chunks}

    def _apply_staged(self, payload: dict) -> None:
        """Device half of one hop: ship the staged arrays and scatter them
        into the donated resident buffers (or swap in a full refresh).
        Runs on the dispatch thread; all device ops are async."""
        kind = payload["kind"]
        if kind == "noop":
            return
        with TRACER.span("hop.ship", kind=kind,
                            time=int(payload["time"])):
            self._apply_staged_inner(payload)

    def _apply_staged_inner(self, payload: dict) -> None:
        kind = payload["kind"]
        from ..utils.transfer import shared_engine

        try:
            if kind == "full":
                arrays = payload["arrays"]
                self.ship_bytes += sum(a.nbytes for a in arrays)
                self._bufs = tuple(shared_engine().put_many(arrays))
                return
            apply_fn = _compiled_apply(self.cap_v, self.cap_e,
                                       np.dtype(self.tdtype).name)
            for chunk in payload["chunks"]:
                self.ship_bytes += sum(a.nbytes for a in chunk)
                # resident state flows through donated buffers
                # (donate_argnums 0-5 in _compiled_apply) — the
                # double-buffer swap XLA gives us for free; only the
                # O(delta) staged rows cross the link
                self._bufs = apply_fn(
                    *self._bufs, *shared_engine().put_many(list(chunk)))
        except BaseException:
            # t_now already reflects this payload's fold but the buffers
            # don't (and a donated apply may have consumed them) — the
            # next fold must take the full-refresh path
            self._stale = True
            raise

    def _cast_t(self, a: np.ndarray) -> np.ndarray:
        return self.tables.cast_times(a)

    def _stage_chunk(self, v_idx, v_lat, v_alive, v_first,
                     e_idx, e_lat, e_alive, e_first) -> tuple:
        """Pad one delta chunk to the fixed scatter capacities — fresh
        contiguous arrays each hop (a reused staging buffer could alias
        the device copy on the CPU backend)."""
        def pad(a, cap, dtype):
            # pad indices with a huge POSITIVE out-of-bounds value — negative
            # indices would wrap Python-style instead of being dropped
            out = np.full(cap, 2**31 - 1 if dtype == np.int32 else 0, dtype)
            out[: len(a)] = a
            return out

        tdt = self.tdtype
        return (
            pad(v_idx, self.cap_v, np.int32),
            pad(self._cast_t(v_lat), self.cap_v, tdt),
            pad(v_alive, self.cap_v, bool),
            pad(self._cast_t(v_first), self.cap_v, tdt),
            pad(e_idx, self.cap_e, np.int32),
            pad(self._cast_t(e_lat), self.cap_e, tdt),
            pad(e_alive, self.cap_e, bool),
            pad(self._cast_t(e_first), self.cap_e, tdt),
        )

    def _apply_chunk(self, v_idx, v_lat, v_alive, v_first,
                     e_idx, e_lat, e_alive, e_first) -> None:
        self._apply_staged({"time": self.t_now, "kind": "chunks",
                            "chunks": [self._stage_chunk(
                                v_idx, v_lat, v_alive, v_first,
                                e_idx, e_lat, e_alive, e_first)]})

    def _stage_full(self, sw=None) -> tuple:
        sw = self.sw if sw is None else sw
        tdt = self.tdtype
        v_lat = np.full(self.n_pad, self._tmin, tdt)
        v_alive = np.zeros(self.n_pad, bool)
        v_first = np.full(self.n_pad, self._tmin, tdt)
        v_lat[: self.n] = self._cast_t(sw.v_lat)
        v_alive[: self.n] = sw.v_alive
        v_first[: self.n] = self._cast_t(sw.v_first)
        e_lat = np.full(self.m_pad, self._tmin, tdt)
        e_alive = np.zeros(self.m_pad, bool)
        e_first = np.full(self.m_pad, self._tmin, tdt)
        pos = self.tables.eng_pos(sw.e_enc)
        e_lat[pos] = self._cast_t(sw.e_lat)
        e_alive[pos] = sw.e_alive
        e_first[pos] = self._cast_t(sw.e_first)
        return (v_lat, v_alive, v_first, e_lat, e_alive, e_first)

    def _refresh_full(self) -> None:
        self._apply_staged({"time": self.t_now, "kind": "full",
                            "arrays": self._stage_full()})

    # ---- program dispatch ----

    def run(self, program: VertexProgram, time: int | None = None, *,
            window: int | None = None, windows=None):
        """Advance to `time` (if given) and dispatch `program` — async, like
        ``bsp.run_async``. Result rows are global dense vertex indices."""
        if not supported(program):
            raise ValueError(
                "program needs occurrences or host-materialised properties — "
                "run it through bsp.run / jobs instead")
        if time is not None:
            self.advance(time)
        if self.t_now is None:
            raise ValueError("call advance(T) (or pass time=) before run()")
        return self._dispatch(program, self.t_now, window, windows)

    def _dispatch(self, program: VertexProgram, T: int, window, windows):
        """Dispatch `program` against the CURRENT resident buffers for hop
        time ``T`` — split from ``run`` so the pipelined sweep can dispatch
        hop *i* while a lookahead fold has already moved ``t_now`` on."""
        batched = windows is not None
        if windows is not None and len(windows) == 0:
            raise ValueError("windows must be a non-empty list")
        if windows is None:
            windows = [window if window is not None else -1]
        wlist = normalize_windows(windows)

        # the device.dispatch failpoint: an injected error propagates
        # through the same except paths a real dispatch failure takes
        # (run_sweep marks _stale; the next hop rewinds through the
        # full-refresh recovery) — chaos runs exercise recovery, not a
        # parallel code path
        _faults.fire("device.dispatch")
        runner = _compiled_run(program, self.n_pad, self.m_pad, len(wlist),
                               np.dtype(self.tdtype).name)
        # a sum at the destination alone is a scan over the sorted rows;
        # every other combine still scatters over them (docs/KERNELS.md)
        scan = program.combiner == "sum" and program.direction == "out"
        with TRACER.span("hop.compute", time=int(T), windows=len(wlist),
                            engine="device_sweep",
                            combine="scan" if scan else "scatter",
                            gather_pack=state_pack(program, self.n_pad,
                                                   len(wlist))):
            result, steps = runner(
                *self._bufs, self.vids, self.e_src, self.e_dst,
                jnp.asarray(int(T), jnp.int64),
                jnp.asarray(wlist, jnp.int64))
        if not batched:
            result = jax.tree_util.tree_map(lambda a: a[0], result)
        return result, steps

    def run_sweep(self, program: VertexProgram, times, *,
                  window: int | None = None, windows=None,
                  prefetch: bool | None = None):
        """Pipelined ascending range sweep: hop *i+1*'s host fold + delta
        staging run in the prefetch worker while hop *i*'s staged rows
        ship and its superstep computes — the fold → stage → ship →
        compute pipeline (``core/sweep._prefetch_pool`` is the fold/stage
        lane; resident state advances through donated device buffers and
        never copies). Returns ``(results, steps_list)`` where
        ``results[i]`` is ``run(program, times[i])``'s result — identical
        to the serial loop (tested) and independent of the pipeline depth.
        ``prefetch=False`` degrades to the serial advance/run loop (the
        bench comparison point); the default follows the ``RTPU_PREFETCH``
        kill-switch (on unless ``0`` — the same knob as the hopbatch
        engine)."""
        if prefetch is None:
            import os

            prefetch = os.environ.get("RTPU_PREFETCH", "1") != "0"
        if not supported(program):
            raise ValueError(
                "program needs occurrences or host-materialised properties — "
                "run it through bsp.run / jobs instead")
        times = [int(t) for t in times]
        if sorted(times) != times:
            raise ValueError("run_sweep times must ascend")
        # per-sweep telemetry (advance() outside run_sweep still
        # accumulates into fold_seconds/ship_bytes; each sweep reports
        # its own numbers, like hopbatch's run())
        self.fold_seconds = 0.0
        self.fold_mode_seconds = {}
        self.fold_stall_seconds = 0.0
        self.fold_inline_seconds = 0.0
        self.ship_bytes = 0
        from ..utils.transfer import shared_engine

        before = shared_engine().stats.as_dict()
        t_start = _time.perf_counter()
        with TRACER.span("sweep.range", engine="device_sweep",
                            hops=len(times),
                            program=type(program).__name__) as sp:
            out = self._run_sweep_impl(program, times, window, windows,
                                       prefetch)
            self.last_phase_seconds = sweep_phase_summary(
                sp, _time.perf_counter() - t_start, self.fold_seconds,
                self.fold_stall_seconds,
                shared_engine().stats.delta_since(before),
                self.ship_bytes, len(times),
                fold_modes=self.fold_mode_seconds,
                fold_inline_seconds=self.fold_inline_seconds)
        return out

    def _run_sweep_impl(self, program, times, window, windows, prefetch):
        results, steps = [], []
        if not prefetch or len(times) <= 1:
            for T in times:
                f0 = self.fold_seconds
                self.advance(T)
                self.fold_inline_seconds += self.fold_seconds - f0
                r, s = self._dispatch(program, T, window, windows)
                results.append(r)
                steps.append(s)
            return results, steps
        import functools as _ft

        from ..core.sweep import fold_workers, prefetch_map

        if fold_workers() > 1 and not self._stale and len(times) >= 2:
            # segment-parallel host folds on forked builders (the sized
            # RTPU_FOLD_WORKERS pool); RTPU_FOLD_WORKERS=1 keeps the
            # single-worker shared-builder pipeline below
            return self._run_sweep_parallel(program, times, window,
                                            windows, results, steps)

        def step(payload, stall):
            self.fold_stall_seconds += stall
            if stall > 0:
                TRACER.complete("fold.stall", stall,
                                   time=int(payload["time"]))
            m = _metrics()
            if m is not None:
                m.h2d_stall_seconds.labels(stage="fold").inc(stall)
            self._apply_staged(payload)
            r, s = self._dispatch(program, payload["time"], window, windows)
            results.append(r)
            steps.append(s)

        try:
            prefetch_map((_ft.partial(self._fold_hop, T) for T in times),
                         step)
        except BaseException:
            # the lookahead fold may have advanced t_now past the hop whose
            # dispatch failed — buffers are behind the clock now
            self._stale = True
            raise
        return results, steps

    def _run_sweep_parallel(self, program, times, window, windows,
                            results, steps):
        """Segment-parallel sweep folds: the hop list splits into up to
        ``fold_workers()`` contiguous segments, each folded + staged on an
        INDEPENDENT fork of the sweep's builder (seeded by one bulk
        advance to the previous segment's boundary) on the sized fold
        pool, while earlier hops ship and compute on this thread. The
        per-hop payloads are identical to the serial fold's (delta
        windows per hop are unchanged), so applied state and results are
        bit-identical. The engine adopts the last segment's builder at
        the end — the host fold clock lands exactly where the serial
        sweep leaves it."""
        from ..core.sweep import fold_pool, fold_workers, prefetch_map

        if self.t_now is not None and times[0] < self.t_now:
            raise ValueError(
                f"DeviceSweep times must ascend "
                f"(got {times[0]} < {self.t_now})")
        n_seg = min(fold_workers(), len(times))
        per = -(-len(times) // n_seg)
        segs = [times[s * per:(s + 1) * per] for s in range(n_seg)]
        segs = [s for s in segs if s]

        def make_task(i: int):
            boundary = int(segs[i - 1][-1]) if i > 0 else None

            def task():
                f0 = _time.perf_counter()
                payloads = []
                # worker attr: see hopbatch._fold_groups_parallel — the
                # span rides the request trace via the pool-handoff
                # context; the attr names the worker without a metadata
                # join
                with TRACER.span("hop.fold", hops=len(segs[i]),
                                    engine="device_sweep",
                                    mode="parallel",
                                    worker=_threading.current_thread(
                                        ).name):
                    sw = self.sw.fork()
                    prev = sw.t_prev
                    if boundary is not None and (prev is None
                                                 or prev < boundary):
                        with TRACER.span("fold.checkpoint",
                                            time=boundary):
                            sw._advance(boundary)
                        prev = boundary
                    for T in segs[i]:
                        payloads.append(self._fold_hop_fork(sw, T, prev))
                        prev = int(T)
                return sw, payloads, _time.perf_counter() - f0
            return task

        last_sw = [self.sw]

        def consume(res, stall):
            sw, payloads, dt = res
            self._note_fold(dt, "parallel")
            self.fold_stall_seconds += stall
            if stall > 0:
                TRACER.complete("fold.stall", stall)
            m = _metrics()
            if m is not None:
                m.h2d_stall_seconds.labels(stage="fold").inc(stall)
                m.fold_seconds.labels("parallel").observe(dt)
            last_sw[0] = sw
            for payload in payloads:
                # t_now and self.sw only move TOGETHER at adoption below —
                # a mid-sweep failure must leave clock == host fold so the
                # stale full-refresh restages a state that covers it
                self._apply_staged(payload)
                r, s = self._dispatch(program, payload["time"], window,
                                      windows)
                results.append(r)
                steps.append(s)

        try:
            prefetch_map([make_task(i) for i in range(len(segs))], consume,
                         depth=len(segs), pool=fold_pool())
        except BaseException:
            # some forked fold/staged payload may be ahead of the applied
            # buffers — recover through the full-refresh path
            self._stale = True
            raise
        # adopt the final fork: self.sw's own clock never moved
        self.sw = last_sw[0]
        self.t_now = int(times[-1])
        return results, steps

    def _fold_hop_fork(self, sw, time: int, prev) -> dict:
        """``_fold_hop_inner`` on a forked builder: fold events in
        (prev, time] and stage the touched rows — engine state (t_now,
        stale flag, telemetry) is the driver's business, not the
        worker's."""
        time = int(time)
        with TRACER.span("hop.fold", time=time,
                            engine="device_sweep") as sp:
            if prev is not None and time <= prev:
                sp.set(kind="noop")
                return {"time": time, "kind": "noop"}
            sw._advance(time)
            payload = self._stage_payload(sw, time)
            sp.set(kind=payload["kind"])
            return payload
