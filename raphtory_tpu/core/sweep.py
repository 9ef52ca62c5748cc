"""Incremental range-sweep view builder — delta-applied snapshots.

The reference re-runs the full per-timestamp handshake for every hop of a
Range query (``Tasks/RangeTasks/RangeAnalysisTask.scala:18-35`` — fresh
``TimeCheck``/``Setup`` per timestamp) and our ``build_view`` likewise
re-folds the whole event log per hop. For an ascending sweep T0 < T1 < ...
over a pinned log that is wasteful: the fold state at T_{i+1} differs from
T_i only by the events with time in (T_i, T_{i+1}].

``SweepBuilder`` keeps the running fold state and applies each hop's delta:

* a fixed dense vertex dictionary is built once from the whole pinned log,
  so vertex fold state lives in flat dense arrays (O(delta) updates, no
  merging), and an edge (s, d) packs into ONE int64 key
  ``dense_s << 32 | dense_d`` — every edge-state merge is a single-key
  searchsorted, and the delta fold runs the native single-key kernel.
* cross-entity tombstones (vertex delete ⇒ incident-edge dead marks,
  ``Edge.killList`` semantics, ``Edge.scala:36-44``) are generated
  incrementally: delta deletes join against all pairs known so far (both
  src- and dst-sorted key arrays are maintained), and pairs first seen in
  this delta join against the full delete history — reproducing exactly the
  all-pairs × all-deletes join of ``build_view``.

Each ``view_at(T)`` emits a ``GraphView`` bit-identical to
``build_view(log, T)`` (tested in ``tests/test_sweep.py``).
"""

from __future__ import annotations

import os
import threading

import numpy as np

from ..analysis.sanitizer import (note_shared as _san_note,
                                  track_shared as _san_track)
from .events import EDGE_ADD, EDGE_DELETE, VERTEX_ADD, VERTEX_DELETE, EventLog
from .snapshot import (
    INT64_MIN,
    GraphView,
    _assemble_view,
    _expand_ranges,
    _fold_latest,
    build_view,
)

_ENC_SHIFT = 32
_ENC_MASK = (1 << _ENC_SHIFT) - 1


def _through(shift: np.ndarray, enc: np.ndarray) -> np.ndarray:
    """Packed keys with both halves taken through the rank map ``shift``
    (monotone: sorted keys stay sorted) — a new array, written a block
    at a time so that the halves' temporaries stay in cache."""
    out = np.empty(len(enc), np.int64)
    block = 1 << 18
    for lo in range(0, len(enc), block):
        part = enc[lo:lo + block]
        hi = np.take(shift, part >> _ENC_SHIFT, mode="clip")
        hi <<= _ENC_SHIFT
        hi |= np.take(shift, part & _ENC_MASK, mode="clip")
        out[lo:lo + block] = hi
    return out


def fold_workers() -> int:
    """Size of the chunk-fold worker pool (``RTPU_FOLD_WORKERS``). The
    default scales with the host — half the cores, capped at 8 — because
    fold workers compete with the XLA CPU backend for the same cores;
    ``1`` degrades every parallel-fold path to the serial pipeline."""
    v = os.environ.get("RTPU_FOLD_WORKERS")
    if v is not None:
        return max(1, int(v))
    return max(1, min(8, (os.cpu_count() or 2) // 2 + 1))


_VFOLD_POOLS: dict = {}
_VFOLD_POOLS_LOCK = threading.Lock()


def _vfold_pool():
    """Process-wide worker pool for the overlapped vertex folds — shared
    so long-lived servers don't pin one idle thread per SweepBuilder.
    Sized alongside the fold pool AND re-keyed when the knob changes
    (like ``fold_pool``): every concurrent chunk fold blocks on one inner
    vertex fold, so fewer workers than chunk folders would serialise the
    overlap the split exists for."""
    from concurrent.futures import ThreadPoolExecutor

    n = max(2, fold_workers())
    with _VFOLD_POOLS_LOCK:
        pool = _VFOLD_POOLS.get(n)
        if pool is None:
            pool = ThreadPoolExecutor(
                max_workers=n, thread_name_prefix="sweep-vfold")
            _VFOLD_POOLS[n] = pool
    return pool


_FOLD_POOLS: dict = {}
_FOLD_POOLS_LOCK = threading.Lock()


def fold_pool():
    """Process-wide sized pool for INDEPENDENT chunk folds (each task owns
    a forked ``SweepBuilder`` — nothing shared, unlike the single-worker
    prefetch lane). Keyed by the resolved ``RTPU_FOLD_WORKERS`` so tests
    (and operators) that change the knob get a correctly-sized pool
    instead of a stale cached one. Deliberately separate from
    ``_vfold_pool``: a chunk fold BLOCKS on its inner vertex fold, and
    sharing a pool would let it occupy the very worker that inner task
    needs (the nested-submit deadlock ``_prefetch_pool`` documents)."""
    from concurrent.futures import ThreadPoolExecutor

    n = fold_workers()
    with _FOLD_POOLS_LOCK:
        pool = _FOLD_POOLS.get(n)
        if pool is None:
            pool = ThreadPoolExecutor(
                max_workers=n, thread_name_prefix="sweep-fold")
            _FOLD_POOLS[n] = pool
    return pool


_PREFETCH_POOL = None
_PREFETCH_POOL_LOCK = threading.Lock()


def _prefetch_pool():
    """Process-wide single worker for hop-lookahead prefetchers
    (``engine/device_sweep.run_sweep``, ``engine/hopbatch._run_chunks``):
    hop *i+1*'s host fold + delta staging runs here while hop *i*'s
    compiled superstep runs on device — the fold → stage → ship → compute
    pipeline. SINGLE worker by design: a fold mutates shared SweepBuilder
    state, so at most one may be in flight. Deliberately separate from
    ``_vfold_pool`` — the fold task BLOCKS on its inner vertex fold, and
    sharing a pool would let it occupy the very worker that inner task
    needs (classic nested-submit deadlock)."""
    global _PREFETCH_POOL
    if _PREFETCH_POOL is None:
        from concurrent.futures import ThreadPoolExecutor

        # locked like the sibling pools: two sweeps racing the lazy init
        # would each get a pool and the single-worker invariant (at most
        # one fold in flight) would silently become two
        with _PREFETCH_POOL_LOCK:
            if _PREFETCH_POOL is None:
                _PREFETCH_POOL = ThreadPoolExecutor(
                    max_workers=1, thread_name_prefix="sweep-prefetch")
    return _PREFETCH_POOL


def prefetch_depth() -> int:
    """Lookahead depth of ``prefetch_map`` (``RTPU_PREFETCH_DEPTH``,
    default 2): how many folds may be queued/in flight ahead of the fold
    the dispatch loop is consuming, so several folds hide behind one long
    device dispatch. On the single prefetch worker depth only QUEUES work
    (folds still run one at a time, in order — safe for folds that share
    a builder); on the sized ``fold_pool`` it is true concurrency."""
    return max(1, int(os.environ.get("RTPU_PREFETCH_DEPTH", 2)))


def prefetch_map(fold_fns, body, *, depth: int | None = None,
                 pool=None) -> None:
    """Drive ``fold_fns`` (zero-arg callables) through a fold worker pool
    with ``depth``-deep lookahead, calling ``body(payload, stall_seconds)``
    for each fold's result while the NEXT folds already run/queue in the
    pool — the body (ship + device dispatch) overlaps the following folds.
    ``stall_seconds`` is how long the driver actually WAITED on the fold
    (0 = it hid entirely behind the previous body). ``depth`` defaults to
    ``prefetch_depth()``; ``pool`` defaults to the SINGLE-worker prefetch
    lane, which serialises execution in submission order — the only safe
    pool for folds that mutate one shared SweepBuilder. Pass
    ``fold_pool()`` only for INDEPENDENT folds (forked builders). If a
    fold or a body raises, every in-flight fold is drained SYNCHRONOUSLY
    before the exception propagates — folds mutate sweep state, and the
    caller's error handler must not reset that state under a
    still-running fold. The single concurrency-pattern copy both sweep
    engines pipeline through (a generator can't give this guarantee: its
    finally would only drain at finalisation, which the propagating
    traceback's frame references delay past the caller's handler)."""
    import collections
    import time as _t

    fns = list(fold_fns)
    if not fns:
        return
    if depth is None:
        depth = prefetch_depth()
    depth = max(1, depth)
    if pool is None:
        pool = _prefetch_pool()
    # trace-context handoff: each fold task adopts the SUBMITTING
    # thread's context (the sweep span of the request being served), so
    # one request's spans stay one trace across the pool boundary even
    # when concurrent requests share these workers (obs/trace.py). A
    # no-op (fns unwrapped) when tracing is off or nothing is open.
    tr = _tracer()
    if tr is not None:
        fns = [tr.carry(fn) for fn in fns]
    inflight = collections.deque(
        pool.submit(fns[i]) for i in range(min(depth, len(fns))))
    nxt = len(inflight)
    try:
        for _ in range(len(fns)):
            fut = inflight.popleft()
            t0 = _t.perf_counter()
            payload = fut.result()
            stall = _t.perf_counter() - t0
            if nxt < len(fns):
                inflight.append(pool.submit(fns[nxt]))
                nxt += 1
            body(payload, stall)
    except BaseException:
        for fut in inflight:   # let every in-flight fold finish first
            fut.exception()
        raise

#: SweepBuilder attributes that are pure functions of the pinned log —
#: forks SHARE them (never written after __init__; ``repin`` REBINDS them
#: on the builder it is called on, so a fork keeps the arrays it took)
_LOG_DERIVED = ("log", "include_occurrences", "pad", "track_rows",
                "_t", "_k", "_s", "_d", "uv", "_ok", "_sd_all", "_dd_all",
                "_t_sorted", "_preseed_asked", "_preseeded")
#: fold-state arrays mutated IN PLACE by _advance — a checkpoint copies
#: them; a fork SHARES them read-only (``flags.writeable`` off: the one
#: record of who owns an array) and a builder takes its own copy of what
#: it does not own before its first in-place write (``_own_state``)
_STATE_COPIED = ("v_lat", "v_alive", "v_first", "v_seen",
                 "e_lat", "e_alive", "e_first", "e_seen")
#: fold-state arrays only ever REBOUND by _advance (np.insert/concatenate
#: build fresh arrays) — a checkpoint can hold the reference
_STATE_SHARED = ("dh_v", "dh_t", "_ea_rows", "_va_rows")
#: the sorted pair tables. On a builder whose pairs are NOT preseeded
#: they are fold state like ``_STATE_SHARED`` (they grow by np.insert as
#: the fold meets fresh pairs) and a checkpoint carries them. On a
#: PRESEEDED builder they hold every pair the log ever mentions from
#: __init__ on and ``_advance`` never rebinds them: they are log-derived,
#: owned by the builder every fork came from (the engines':
#: ``engine/device_sweep.LogIndex.prototype``, counted in ``/statusz``
#: ``log_index.bytes``), and a checkpoint neither holds nor is charged
#: for them — ``fork(cp)`` takes them from the builder it is called on
_PAIR_TABLES = ("e_enc", "e_enc_dst")

#: every ``SweepBuilder.fork`` of the process and the deferred copies
#: they led to (``fork_status``): a fork that never writes never copies
_FORK_COUNTS = {"forks": 0, "fork_copies": 0, "fork_copied_bytes": 0}
_FORK_LOCK = threading.Lock()


def fork_status() -> dict:
    """``forks`` taken since start, ``fork_copies`` — the builders that
    went on to copy the fold state they shared (a fork at its first
    write, or a source that advanced after a fork of it) — and the
    ``fork_copied_bytes`` those copies moved."""
    with _FORK_LOCK:
        return dict(_FORK_COUNTS)


class FoldCheckpoint:
    """Immutable snapshot of a ``SweepBuilder``'s fold state at ``t_prev``
    — the seed of ``SweepBuilder.fork``. Checkpoints from ANY builder over
    the same pinned log content are interchangeable (the dense spaces are
    content-determined), which is what lets the fold cache hand them
    across requests; ``config`` guards against mixing builders with
    different emit/preseed settings.

    ``state`` holds the copied per-vertex / per-pair arrays, the
    rebind-only delete history and row lists and — only when the
    builder's pairs were not preseeded — the pair tables
    (``_PAIR_TABLES``). ``nbytes`` is the bytes of exactly those arrays:
    the fold cache charges what holding the checkpoint keeps alive.
    ``fork(cp)`` copies none of them: the forks share the checkpoint's
    arrays, made read-only at the first fork, until each writes."""

    __slots__ = ("t_prev", "state", "config", "nbytes")

    def __init__(self, t_prev, state: dict, config: tuple):
        self.t_prev = t_prev
        self.state = state
        self.config = config
        self.nbytes = int(sum(a.nbytes for a in state.values()))

_EMPTY_DELTA = {
    "v_idx": np.empty(0, np.int64), "v_lat": np.empty(0, np.int64),
    "v_alive": np.empty(0, bool), "v_first": np.empty(0, np.int64),
    "e_enc": np.empty(0, np.int64), "e_lat": np.empty(0, np.int64),
    "e_alive": np.empty(0, bool), "e_first": np.empty(0, np.int64),
}


class _Suffix:
    """The rows a log appended since a builder's pin
    (``SweepBuilder.suffix``): the new pin, the suffix's columns, the
    ids the builder's ``uv`` lacks, whether adopting it ``grows`` the
    dictionaries, and the suffix's per-row dense ids (``sd`` / ``dd``:
    set by ``suffix`` when nothing grows, by ``_grow`` otherwise)."""

    __slots__ = ("log", "n_old", "t", "s", "d", "is_e", "new_ids",
                 "grows", "sd", "dd")

    def __init__(self, log, n_old: int):
        self.log, self.n_old = log, n_old
        self.t = log.column("time")[n_old:]
        self.s = log.column("src")[n_old:]
        self.d = log.column("dst")[n_old:]
        k = log.column("kind")[n_old:]
        self.is_e = (k == EDGE_ADD) | (k == EDGE_DELETE)


class SweepBuilder:
    """Build views at ascending timestamps over a pinned log, incrementally.

    For out-of-order `view_at` times, or once the dense dictionary would
    overflow the 32-bit pack, it falls back to full ``build_view`` per call.
    """

    def __init__(self, log: EventLog, *, include_occurrences: bool = False,
                 pad: str = "pow2", track_rows: bool = True,
                 preseed_pairs: bool = False):
        if include_occurrences and not track_rows:
            raise ValueError("occurrence views need the add-row lists")
        self.include_occurrences = include_occurrences
        self.pad = pad
        self.track_rows = track_rows
        # stage span (an ``engine.build`` is mostly this constructor): the
        # pin, the id dictionary and the per-row dense ids
        with _span("index.ids") as sp:
            self.log = log.pin()
            self._t = self.log.column("time")
            self._k = self.log.column("kind")
            self._s = self.log.column("src")
            self._d = self.log.column("dst")
            # dense dictionary over every vertex id the log ever mentions.
            # dst is only a vertex id on edge events — vertex events carry
            # a -1 sentinel there, and REAL ids can be negative (assign_id
            # hashes to signed int64), so select by kind, never by sign.
            is_e = (self._k == EDGE_ADD) | (self._k == EDGE_DELETE)
            d_real = self._d[is_e]
            self.uv = np.unique(np.concatenate([self._s, d_real])) \
                if len(self._s) else np.empty(0, np.int64)
            self._ok = len(self.uv) < (1 << 31)
            # per-row dense ids, computed ONCE: per-hop _advance slices
            # these instead of re-running searchsorted over the dictionary
            # for every delta (the dominant host cost of a columnar
            # sweep). Skipped above 2^23 events, where the 16B/event would
            # hurt more than it helps.
            if self._ok and 0 < len(self._s) <= (1 << 23):
                self._sd_all = np.searchsorted(self.uv, self._s)
                self._dd_all = np.zeros(len(self._d), np.int64)
                self._dd_all[is_e] = np.searchsorted(self.uv, d_real)
            else:
                self._sd_all = self._dd_all = None
            sp.set(events=len(self._t), ids=len(self.uv))
        nv = len(self.uv)
        # dense vertex fold state
        self.v_lat = np.full(nv, INT64_MIN, np.int64)
        self.v_alive = np.zeros(nv, bool)
        self.v_first = np.full(nv, INT64_MIN, np.int64)
        self.v_seen = np.zeros(nv, bool)
        # edge fold state keyed by packed (dense_s, dense_d); enc-sorted
        self.e_enc = np.empty(0, np.int64)
        self.e_lat = np.empty(0, np.int64)
        self.e_alive = np.empty(0, bool)
        self.e_first = np.empty(0, np.int64)
        # the same pair keys packed (dense_d, dense_s), kept sorted — the
        # dst-incidence index for tombstone joins
        self.e_enc_dst = np.empty(0, np.int64)
        # preseed: start the pair table with EVERY pair the log ever
        # mentions (alive=False, times at the sentinel). No pair is ever
        # "fresh" afterwards, so the per-hop sorted inserts and the
        # history-vs-new-pair joins vanish; the per-hop incident join over
        # all pairs generates exactly build_view's all-pairs × all-deletes
        # killList marks (a dead mark before a pair's first add loses to
        # the later add in the latest-wins fold — same outcome as the
        # historical join it replaces). The columnar engines opt in;
        # semantics stay bit-identical (tested against build_view).
        self.e_seen = np.empty(0, bool)   # pair has real marks (firsts set)
        self._preseed_asked = bool(preseed_pairs)
        self._preseeded = False
        if preseed_pairs and self._ok and is_e.any():
            with _span("index.pairs") as sp:
                sd_e = np.searchsorted(self.uv, self._s[is_e]) \
                    if self._sd_all is None else self._sd_all[is_e]
                dd_e = np.searchsorted(self.uv, d_real) \
                    if self._dd_all is None else self._dd_all[is_e]
                enc_all = np.unique(self._pack(sd_e, dd_e))
                self.e_enc = enc_all
                self.e_lat = np.full(len(enc_all), INT64_MIN, np.int64)
                self.e_alive = np.zeros(len(enc_all), bool)
                self.e_first = np.full(len(enc_all), INT64_MIN, np.int64)
                self.e_seen = np.zeros(len(enc_all), bool)
                self.e_enc_dst = np.sort(
                    ((enc_all & _ENC_MASK) << _ENC_SHIFT)
                    | (enc_all >> _ENC_SHIFT))
                self._preseeded = True
                sp.set(pairs=len(enc_all))
        # delete history: (dense vertex, time), sorted by vertex
        self.dh_v = np.empty(0, np.int64)
        self.dh_t = np.empty(0, np.int64)
        # in-time add-event row lists (property joins), ascending, grown
        # per delta — deltas are selected by event TIME, so their row
        # indices interleave with earlier hops' and need a sorted merge
        self._ea_rows = np.empty(0, np.int64)
        self._va_rows = np.empty(0, np.int64)
        self.t_prev: int | None = None
        # per-hop row selection: binary search when the log is time-sorted
        # (bulk loads, replayed dumps), O(N) boolean scan otherwise
        self._t_sorted = bool(
            len(self._t) == 0 or bool((self._t[:-1] <= self._t[1:]).all()))
        # last hop's touched-entity delta (dense vertex indices + packed edge
        # keys with their POST-update fold state) — consumed by the
        # device-resident sweep engine (engine/device_sweep.py), which ships
        # only these O(delta) rows to the chip instead of fresh O(m) arrays
        self.last_delta: dict | None = None

    # ---- helpers ----

    def _dense(self, ids: np.ndarray) -> np.ndarray:
        return np.searchsorted(self.uv, ids)

    def _pack(self, ds: np.ndarray, dd: np.ndarray) -> np.ndarray:
        return (ds << _ENC_SHIFT) | dd

    def _incident(self, enc_sorted: np.ndarray, dv: np.ndarray, dt: np.ndarray,
                  flip: bool):
        """Dead marks (enc, t) for pairs in `enc_sorted` whose FIRST packed
        component is in dv. flip=True means enc_sorted is (d, s)-packed and
        results are re-packed as (s, d)."""
        lo = np.searchsorted(enc_sorted, dv << _ENC_SHIFT, side="left")
        hi = np.searchsorted(enc_sorted, (dv + 1) << _ENC_SHIFT, side="left")
        rows, qidx = _expand_ranges(lo, hi)
        enc = enc_sorted[rows]
        if flip:
            enc = ((enc & _ENC_MASK) << _ENC_SHIFT) | (enc >> _ENC_SHIFT)
        return enc, dt[qidx]

    # ---- checkpoint / fork ----

    def _config(self) -> tuple:
        return (self.include_occurrences, self.pad, self.track_rows,
                self._preseeded, len(self.uv), len(self._t))

    def checkpoint(self) -> FoldCheckpoint:
        """Snapshot the fold state at the current ``t_prev``. Arrays that
        ``_advance`` mutates in place are copied; arrays it only ever
        rebinds (delete history, row lists, and the sorted pair/dst
        tables of a builder that is not preseeded) are shared by
        reference — a later advance builds fresh ones and never touches
        the snapshot's. A PRESEEDED builder's pair tables are left out
        (``_PAIR_TABLES``): no reference kept, none counted in ``nbytes``."""
        state = {k: getattr(self, k).copy() for k in _STATE_COPIED}
        shared = _STATE_SHARED if self._preseeded \
            else _STATE_SHARED + _PAIR_TABLES
        state.update({k: getattr(self, k) for k in shared})
        return FoldCheckpoint(self.t_prev, state, self._config())

    def fork(self, cp: FoldCheckpoint | None = None) -> "SweepBuilder":
        """An INDEPENDENT builder over the same pinned log, seeded from
        ``cp`` (or this builder's current state): log-derived arrays are
        shared (immutable after __init__; a preseeded builder's pair
        tables among them, taken from THIS builder whatever ``cp`` is),
        fold state is copied — the fork and the original advance without
        observing each other. This is how a range sweep's chunks fold
        concurrently: each chunk forks from the nearest checkpoint and
        folds its own hop window.
        Equivalence holds because the fold state at T is a function of
        (log, T) alone, not of the hop sequence that reached it (the
        ``view_at ≡ build_view`` contract, tested per hop batching).

        The copy is taken when it is first needed, not here: the fork
        binds the source's ``_STATE_COPIED`` arrays (the checkpoint's, or
        this builder's own) and the source's are made read-only, so
        neither side owns them any more and each copies what it still
        borrows before its first in-place write (``_own_state``) — a
        fork that never advances never copies. Marking the source is
        idempotent: forks may be taken from one source on several
        threads at once, as long as the source does not advance
        meanwhile."""
        if cp is not None and cp.config != self._config():
            raise ValueError(
                "checkpoint was taken from an incompatible SweepBuilder "
                f"(config {cp.config} != {self._config()}) — fold "
                "checkpoints only transfer between builders over the same "
                "pinned log content and emit settings")
        sw = SweepBuilder.__new__(SweepBuilder)
        for k in _LOG_DERIVED:
            setattr(sw, k, getattr(self, k))
        src = cp.state if cp is not None else None
        for k in _STATE_COPIED:
            a = src[k] if src is not None else getattr(self, k)
            a.flags.writeable = False
            setattr(sw, k, a)
        for k in _STATE_SHARED + _PAIR_TABLES:
            # rebind-only arrays: the fork's first rebind leaves the
            # source (live builder or cached checkpoint) untouched. A
            # preseeded builder's pair tables are in no checkpoint
            setattr(sw, k, src[k] if src is not None and k in src
                    else getattr(self, k))
        sw.t_prev = cp.t_prev if cp is not None else self.t_prev
        sw.last_delta = None
        with _FORK_LOCK:
            _FORK_COUNTS["forks"] += 1
        return sw

    def fork_nbytes(self) -> int:
        """Bytes of the fold-state arrays ``_advance`` mutates in place
        (18 B an id + 18 B a pair): what a ``fork`` of this builder
        shares with it, and what the fork's first write copies."""
        return int(sum(getattr(self, k).nbytes for k in _STATE_COPIED))

    def _own_state(self) -> None:
        """Take this builder's own copy of every ``_STATE_COPIED`` array
        it still shares with a fork, a source or a checkpoint (the
        read-only ones) — before an in-place write. The copy is a
        ``fold.seed`` span with ``deferred=true`` on the thread that pays
        it: the seconds a fork's copy cost when ``fork`` made it."""
        borrowed = [k for k in _STATE_COPIED
                    if not getattr(self, k).flags.writeable]
        if not borrowed:
            return
        with _span("fold.seed", deferred=True) as sp:
            nbytes = 0
            for k in borrowed:
                own = getattr(self, k).copy()
                setattr(self, k, own)
                nbytes += own.nbytes
            sp.set(nbytes=nbytes)
        with _FORK_LOCK:
            _FORK_COUNTS["fork_copies"] += 1
            _FORK_COUNTS["fork_copied_bytes"] += nbytes

    # ---- incremental re-pin (live epoch serving) ----

    def repin(self, live_log) -> str:
        """Adopt rows appended to the LIVE log since this builder's pin,
        without refolding history (``suffix`` then ``adopt``). Returns:

        * ``"noop"``     — nothing new; the pin already covers the log.
        * ``"extended"`` — the suffix was adopted in place: fold state,
          ``t_prev`` and the dense vertex/pair dictionaries all remain
          valid, and the next ``_advance`` folds exactly the new rows.
        * ``"grown"``    — the suffix names a vertex id outside ``uv``
          or, on a preseeded builder, a pair outside ``e_enc``: the
          dictionaries GREW to take it in (``_grow``). Fold state and
          ``t_prev`` are carried, but every dense index and pair key a
          caller holds is stale — whatever it derived from the old
          dictionaries (global tables, device buffers, a warm seed, the
          last delta) must be derived again.
        * ``"rebuild"``  — the suffix cannot be adopted; the caller must
          construct a fresh builder (and refold from scratch).

        Adoption is only sound when the pinned snapshot is still a
        PREFIX of the live log, so ``"rebuild"`` is returned when any of
        these hold:

        * the log was compacted (history rewritten — the pin is no
          longer a prefix; detected via ``EventLog.compactions``), or
          shrank;
        * a suffix event lands at or below ``t_prev`` — the watermark
          contract says events at or below the served fence never
          arrive late, so such a row means the fence was not honoured
          and already-folded state is stale;
        * the dictionary cannot hold the suffix: the pin was empty, the
          ids would pass the 31 bits of the pair pack, or pairs were to
          be preseeded and the pin had no edge event to preseed from.
        """
        suffix = self.suffix(live_log)
        return suffix if isinstance(suffix, str) else self.adopt(suffix)

    def suffix(self, live_log) -> "str | _Suffix":
        """Read what ``live_log`` appended since this builder's pin and
        change nothing: ``"noop"`` / ``"rebuild"`` (``repin`` names the
        causes), or the suffix for ``adopt``, whose ``grows`` says
        whether adopting it grows the dictionaries — a caller that
        cannot follow a growth discards the builder before paying for
        one. O(suffix)."""
        new = live_log.pin()
        n_old = len(self._t)
        if (getattr(new, "compactions", 0)
                != getattr(self.log, "compactions", 0)):
            # checked BEFORE the row-count fast path: a compaction can
            # rewrite history to the SAME row count, and "same n" says
            # nothing about row identity across a rewrite
            return "rebuild"
        if new.n == n_old:
            return "noop"
        if new.n < n_old or not self._ok or not len(self.uv):
            return "rebuild"
        sfx = _Suffix(new, n_old)
        if self.t_prev is not None and int(sfx.t.min()) <= self.t_prev:
            return "rebuild"
        if self._preseed_asked and not self._preseeded and sfx.is_e.any():
            return "rebuild"   # the log's first edges: nothing was preseeded
        ids = np.concatenate([sfx.s, sfx.d[sfx.is_e]])
        pos = np.searchsorted(self.uv, ids)
        known = self.uv[np.minimum(pos, len(self.uv) - 1)] == ids
        sfx.new_ids = np.unique(ids[~known])
        if len(self.uv) + len(sfx.new_ids) >= (1 << 31):
            return "rebuild"   # the pair pack's 31 bits an id
        sfx.grows = bool(len(sfx.new_ids))
        if not sfx.grows:
            sfx.sd = pos[: len(sfx.s)]
            sfx.dd = np.zeros(len(sfx.d), np.int64)
            sfx.dd[sfx.is_e] = pos[len(sfx.s):]
            if self._preseeded and sfx.is_e.any():
                enc = self._pack(sfx.sd[sfx.is_e], sfx.dd[sfx.is_e])
                sfx.grows = not bool(self._has_pairs(self.e_enc, enc).all())
        return sfx

    @staticmethod
    def _has_pairs(table: np.ndarray, enc: np.ndarray) -> np.ndarray:
        """Which of the packed keys ``enc`` the sorted ``table`` holds."""
        if not len(table):
            return np.zeros(len(enc), bool)
        return table[np.minimum(np.searchsorted(table, enc),
                                len(table) - 1)] == enc

    def adopt(self, sfx: "_Suffix") -> str:
        """Adopt a suffix ``suffix`` read (nothing appended to this
        builder since): ``"extended"`` or ``"grown"``."""
        if sfx.grows:
            self._grow(sfx)
        else:
            self._append_rows(sfx)
        old_pin, new = self.log, sfx.log
        # rebind the log-derived views; everything else is valid
        self.log = new
        self._t = new.column("time")
        self._k = new.column("kind")
        self._s = new.column("src")
        self._d = new.column("dst")
        self._t_sorted = bool(
            self._t_sorted
            and bool((sfx.t[:-1] <= sfx.t[1:]).all())
            and int(sfx.t[0]) >= int(self._t[sfx.n_old - 1]))
        # the fold-cache key of the new pin, in O(suffix)
        extend_fingerprint(old_pin, new)
        return "grown" if sfx.grows else "extended"

    def _append_rows(self, sfx: "_Suffix", shift=None) -> None:
        """The per-row dense ids with the suffix's appended, the old
        pin's taken through ``shift`` where the ranks moved — each into
        one new array (the old pin's columns are still bound)."""
        if self._sd_all is None:
            return
        n_old = sfx.n_old
        for name, new in (("_sd_all", sfx.sd), ("_dd_all", sfx.dd)):
            old = getattr(self, name)
            out = np.empty(n_old + len(new), np.int64)
            if shift is None:
                out[:n_old] = old
            else:
                np.take(shift, old, out=out[:n_old], mode="clip")
            out[n_old:] = new
            setattr(self, name, out)
        if shift is not None and shift[0]:
            # a vertex event's row holds 0, not the rank of ``uv[0]``
            self._dd_all[:n_old][(self._k != EDGE_ADD)
                                 & (self._k != EDGE_DELETE)] = 0

    def _grow(self, sfx: "_Suffix") -> None:
        """Grow the dense dictionaries to hold ``sfx``'s new ids and (on
        a preseeded builder) new pairs, and carry the fold state across.

        Dense ids are ranks in the sorted ``uv``, so inserting the new
        ids moves every old rank up by the count of new ids below it: a
        MONOTONE map (``shift``). Every sorted structure stays sorted
        under it — the per-row dense ids, the pair keys packed either
        way, the delete history — so each is one gather through
        ``shift``, never a sort or a ``unique`` over the log; the new
        entities take blank slots (what ``__init__`` gives one no
        event has reached) at their insertion points. ``t_prev`` and the
        state at ``t_prev`` stay valid: every suffix event is later
        (``suffix``), so a new id has no event at or before ``t_prev``,
        and a new pair none of its own — only its endpoints' deletes,
        which ``_killed_before`` joins in as the preseeded fold would
        have. The result is bit for bit what ``SweepBuilder`` over the
        grown log builds and advances to ``t_prev``.

        Every grown array is a NEW array, rebound: forks share the
        log-derived arrays and the preseeded pair tables by reference
        and go on reading the ones they took. ``last_delta`` spoke the
        old coordinates and is dropped."""
        uv, n_new = self.uv, len(sfx.new_ids)
        with _span("index.ids", grow=True, events=len(sfx.t)) as sp:
            shift = None
            if n_new:
                v_at = np.searchsorted(uv, sfx.new_ids)
                self.uv = np.insert(uv, v_at, sfx.new_ids)
                # old rank -> new rank: + the new ids inserted at or below
                shift = np.arange(len(uv), dtype=np.int64)
                shift += np.searchsorted(v_at, shift, side="right")
                self.dh_v = shift[self.dh_v]
                blank_t = np.full(n_new, INT64_MIN, np.int64)
                blank_b = np.zeros(n_new, bool)
                self.v_lat = np.insert(self.v_lat, v_at, blank_t)
                self.v_alive = np.insert(self.v_alive, v_at, blank_b)
                self.v_first = np.insert(self.v_first, v_at, blank_t)
                self.v_seen = np.insert(self.v_seen, v_at, blank_b)
                sfx.sd = np.searchsorted(self.uv, sfx.s)
                sfx.dd = np.zeros(len(sfx.d), np.int64)
                sfx.dd[sfx.is_e] = np.searchsorted(self.uv,
                                                   sfx.d[sfx.is_e])
            self._append_rows(sfx, shift)
            sp.set(new_ids=n_new, ids=len(self.uv))
        with _span("index.pairs", grow=True) as sp:
            e_enc, e_enc_dst = self.e_enc, self.e_enc_dst
            if shift is not None:
                e_enc = _through(shift, e_enc)
                e_enc_dst = _through(shift, e_enc_dst)
            n_pairs = 0
            if self._preseeded:
                enc = np.unique(self._pack(sfx.sd[sfx.is_e],
                                           sfx.dd[sfx.is_e]))
                enc = enc[~self._has_pairs(e_enc, enc)]
                n_pairs = len(enc)
            if n_pairs:
                e_at = np.searchsorted(e_enc, enc)
                e_enc = np.insert(e_enc, e_at, enc)
                enc_dst = np.sort(
                    ((enc & _ENC_MASK) << _ENC_SHIFT) | (enc >> _ENC_SHIFT))
                e_enc_dst = np.insert(
                    e_enc_dst, np.searchsorted(e_enc_dst, enc_dst), enc_dst)
                lat, alive, first, seen = self._killed_before(enc, enc_dst)
                self.e_lat = np.insert(self.e_lat, e_at, lat)
                self.e_alive = np.insert(self.e_alive, e_at, alive)
                self.e_first = np.insert(self.e_first, e_at, first)
                self.e_seen = np.insert(self.e_seen, e_at, seen)
            self.e_enc, self.e_enc_dst = e_enc, e_enc_dst
            sp.set(new_pairs=n_pairs, pairs=len(e_enc))
        self.last_delta = None

    def _killed_before(self, enc: np.ndarray, enc_dst: np.ndarray):
        """Fold state at ``t_prev`` of pairs ``enc`` (sorted; ``enc_dst``
        the same pairs dst-major, sorted) that no event of their own has
        reached: blank, but for the dead marks of their endpoints'
        deletes at or before ``t_prev`` — the all-pairs x all-deletes
        killList join (``_fold_rows``) gives a preseeded pair those from
        the log's first event on, so a pair that joins the table later
        is owed them. Keys are in the GROWN dictionary; the rows read
        are the old pin's."""
        lat = np.full(len(enc), INT64_MIN, np.int64)
        alive = np.zeros(len(enc), bool)
        first = lat.copy()
        seen = alive.copy()
        rows = np.flatnonzero(self._k == VERTEX_DELETE)
        if self.t_prev is not None and len(rows):
            rows = rows[self._t[rows] <= self.t_prev]
            dv = self._dense(self._s[rows])
            marks = [self._incident(tab, dv, self._t[rows], flip)
                     for tab, flip in ((enc, False), (enc_dst, True))]
            (u,), ulat, ualive, ufirst = _fold_latest(
                (np.concatenate([m[0] for m in marks]),),
                np.concatenate([m[1] for m in marks]),
                np.zeros(sum(len(m[0]) for m in marks), bool))
            at = np.searchsorted(enc, u)
            lat[at], alive[at], first[at], seen[at] = \
                ulat, ualive, ufirst, True
        return lat, alive, first, seen

    # ---- the sweep ----

    def view_at(self, time: int) -> GraphView:
        time = int(time)
        if not self._ok or (self.t_prev is not None and time < self.t_prev):
            return build_view(self.log, time,
                              include_occurrences=self.include_occurrences,
                              pad=self.pad)
        if self.t_prev is None or time > self.t_prev:
            self._advance(time)
        return self._emit(time)

    def _advance(self, time: int) -> None:
        """Fold the log's rows in ``(t_prev, time]`` into the running
        state — under one ``fold.advance`` span whoever calls (a View's, a
        Range unit's, a Live epoch's or the mesh route's fold; the bulk
        advance to a checkpoint boundary too). A builder that still
        shares fold state copies it first (``_own_state``: a span of its
        own BEFORE this one, never inside it), unless no row is due."""
        rows = self._rows_through(time)
        if len(rows):
            self._own_state()
        with _span("fold.advance", time=int(time)) as sp:
            sp.set(rows=self._fold_rows(time, rows))

    def _rows_through(self, time: int) -> np.ndarray:
        """The log rows with time in ``(t_prev, time]``, ascending."""
        if self._t_sorted:
            lo = 0 if self.t_prev is None \
                else int(np.searchsorted(self._t, self.t_prev, side="right"))
            hi = int(np.searchsorted(self._t, time, side="right"))
            return np.arange(lo, hi)
        sel = self._t <= time
        if self.t_prev is not None:
            sel &= self._t > self.t_prev
        return np.flatnonzero(sel)

    def _fold_rows(self, time: int, rows: np.ndarray) -> int:
        """``_advance``'s work on ``rows`` (``_rows_through(time)``);
        returns how many log rows it folded."""
        self.t_prev = time
        if len(rows) == 0:
            self.last_delta = _EMPTY_DELTA
            return 0
        t = self._t[rows]
        k = self._k[rows]
        s = self._s[rows]
        d = self._d[rows]
        is_va = k == VERTEX_ADD
        is_vd = k == VERTEX_DELETE
        is_ea = k == EDGE_ADD
        is_ed = k == EDGE_DELETE
        uvd = uenc = None  # touched entities, recorded into last_delta below

        if self.track_rows:
            new_ea = rows[is_ea]
            new_va = rows[is_va]
            self._ea_rows = np.insert(
                self._ea_rows, np.searchsorted(self._ea_rows, new_ea), new_ea)
            self._va_rows = np.insert(
                self._va_rows, np.searchsorted(self._va_rows, new_va), new_va)

        if self._sd_all is not None:
            sd, dd = self._sd_all[rows], self._dd_all[rows]
            ds_ea, dd_ea = sd[is_ea], dd[is_ea]
            dv_del = sd[is_vd]
            dv_add = sd[is_va]
            ds_ed, dd_ed = sd[is_ed], dd[is_ed]
        else:
            ds_ea = self._dense(s[is_ea])
            dd_ea = self._dense(d[is_ea])
            dv_del = self._dense(s[is_vd])
            dv_add = self._dense(s[is_va])
            ds_ed = self._dense(s[is_ed])
            dd_ed = self._dense(d[is_ed])
        t_del = t[is_vd]

        # -- vertex delta fold: adds + edge-endpoint revivals vs deletes --
        # runs in a worker thread OVERLAPPED with the edge-side marks+fold
        # below (independent state; ctypes/numpy release the GIL): the two
        # folds are the per-hop host cost of a columnar sweep
        v_ids = np.concatenate([dv_add, ds_ea, dd_ea, dv_del])
        v_t = np.concatenate([t[is_va], t[is_ea], t[is_ea], t_del])
        v_al = np.zeros(len(v_ids), bool)
        v_al[: len(v_ids) - len(dv_del)] = True

        def _vertex_fold():
            if not len(v_ids):
                return None
            (uvd0,), dlat, dalive, dfirst = _fold_latest((v_ids,), v_t, v_al)
            # delta times are strictly later than any prior mark, so the
            # delta's latest wins outright and firsts only fill unseen slots
            self.v_lat[uvd0] = dlat
            self.v_alive[uvd0] = dalive
            self.v_first[uvd0] = np.where(self.v_seen[uvd0],
                                          self.v_first[uvd0], dfirst)
            self.v_seen[uvd0] = True
            return uvd0

        # the inner vertex fold crosses to the vfold pool mid-advance:
        # carry the chunk fold's trace context with it (a no-op wrap
        # when tracing is off)
        tr = _tracer()
        v_fut = _vfold_pool().submit(
            tr.carry(_vertex_fold) if tr is not None else _vertex_fold)

        # -- edge delta marks: own add/delete events --
        enc_ea = self._pack(ds_ea, dd_ea)
        enc_ed = self._pack(ds_ed, dd_ed)
        marks_enc = [enc_ea, enc_ed]
        marks_t = [t[is_ea], t[is_ed]]
        marks_a = [np.ones(len(enc_ea), bool), np.zeros(len(enc_ed), bool)]

        delta_enc = np.unique(np.concatenate([enc_ea, enc_ed])) \
            if (len(enc_ea) or len(enc_ed)) else np.empty(0, np.int64)
        if self._preseeded:
            new_enc = delta_enc[:0]   # every pair is in the table already
        else:
            pos = np.searchsorted(self.e_enc, delta_enc)
            pos_c = np.clip(pos, 0, max(len(self.e_enc) - 1, 0))
            known = (self.e_enc[pos_c] == delta_enc) if len(self.e_enc) \
                else np.zeros(len(delta_enc), bool)
            new_enc = delta_enc[~known]

        if len(dv_del):
            # delta deletes × (pairs known before this hop ∪ NEW delta pairs)
            for enc_arr, flip in ((self.e_enc, False), (self.e_enc_dst, True)):
                enc_ts, t_ts = self._incident(enc_arr, dv_del, t_del, flip)
                marks_enc.append(enc_ts)
                marks_t.append(t_ts)
                marks_a.append(np.zeros(len(enc_ts), bool))
            new_by_dst = np.sort(
                ((new_enc & _ENC_MASK) << _ENC_SHIFT) | (new_enc >> _ENC_SHIFT))
            for enc_arr, flip in ((new_enc, False), (new_by_dst, True)):
                enc_ts, t_ts = self._incident(enc_arr, dv_del, t_del, flip)
                marks_enc.append(enc_ts)
                marks_t.append(t_ts)
                marks_a.append(np.zeros(len(enc_ts), bool))

        if len(new_enc) and len(self.dh_v):
            # historical deletes × pairs first seen in this delta
            ns = new_enc >> _ENC_SHIFT
            nd = new_enc & _ENC_MASK
            for comp in (ns, nd):
                lo = np.searchsorted(self.dh_v, comp, side="left")
                hi = np.searchsorted(self.dh_v, comp, side="right")
                hrows, qidx = _expand_ranges(lo, hi)
                marks_enc.append(new_enc[qidx])
                marks_t.append(self.dh_t[hrows])
                marks_a.append(np.zeros(len(hrows), bool))

        all_enc = np.concatenate(marks_enc)
        epos_known = None
        if len(all_enc):
            all_t = np.concatenate(marks_t)
            all_a = np.concatenate(marks_a)
            (uenc,), elat_d, ealive_d, efirst_d = _fold_latest((all_enc,), all_t, all_a)
            upos = np.searchsorted(self.e_enc, uenc)
            upos_c = np.clip(upos, 0, max(len(self.e_enc) - 1, 0))
            uknown = (self.e_enc[upos_c] == uenc) if len(self.e_enc) \
                else np.zeros(len(uenc), bool)
            # existing pairs: delta marks are strictly later — overwrite
            # (firsts only fill slots that never saw a real mark — preseeded
            # pairs exist in the table before their first event)
            kpos = upos_c[uknown]
            self.e_lat[kpos] = elat_d[uknown]
            self.e_alive[kpos] = ealive_d[uknown]
            self.e_first[kpos] = np.where(self.e_seen[kpos],
                                          self.e_first[kpos],
                                          efirst_d[uknown])
            self.e_seen[kpos] = True
            # new pairs: insert (fold already merged their full history,
            # including historical tombstones, so firsts are exact)
            fresh = ~uknown
            if not fresh.any():
                # positions are final (no inserts shifted them): last_delta
                # reuses them instead of re-searching the whole table
                epos_known = upos_c
            if fresh.any():
                at = upos[fresh]
                self.e_enc = np.insert(self.e_enc, at, uenc[fresh])
                self.e_lat = np.insert(self.e_lat, at, elat_d[fresh])
                self.e_alive = np.insert(self.e_alive, at, ealive_d[fresh])
                self.e_first = np.insert(self.e_first, at, efirst_d[fresh])
                self.e_seen = np.insert(self.e_seen, at,
                                        np.ones(fresh.sum(), bool))
                enc2 = (((uenc[fresh] & _ENC_MASK) << _ENC_SHIFT)
                        | (uenc[fresh] >> _ENC_SHIFT))
                enc2 = np.sort(enc2)
                self.e_enc_dst = np.insert(
                    self.e_enc_dst, np.searchsorted(self.e_enc_dst, enc2), enc2)

        if len(dv_del) and not self._preseeded:
            # the delete history only feeds the new-pair join, which a
            # preseeded table never takes (no pair is ever new)
            self.dh_v = np.concatenate([self.dh_v, dv_del])
            self.dh_t = np.concatenate([self.dh_t, t_del])
            order = np.argsort(self.dh_v, kind="stable")
            self.dh_v = self.dh_v[order]
            self.dh_t = self.dh_t[order]

        uvd = v_fut.result()   # join the overlapped vertex fold

        # Touched-entity delta with POST-update fold state, read back from the
        # running arrays so it is correct no matter which code path (known
        # pair overwrite / fresh insert / tombstone join) produced the value.
        tv = uvd if uvd is not None else np.empty(0, np.int64)
        te = uenc if uenc is not None else np.empty(0, np.int64)
        epos = epos_known if epos_known is not None \
            else np.searchsorted(self.e_enc, te)
        self.last_delta = {
            "v_idx": tv, "v_lat": self.v_lat[tv],
            "v_alive": self.v_alive[tv], "v_first": self.v_first[tv],
            "e_enc": te, "e_lat": self.e_lat[epos],
            "e_alive": self.e_alive[epos], "e_first": self.e_first[epos],
        }
        return len(rows)

    def _emit(self, time: int) -> GraphView:
        if not self.track_rows:
            raise RuntimeError(
                "this SweepBuilder was built with track_rows=False (fold "
                "state only — the columnar/device engines); use a default "
                "one to emit GraphViews")
        act_dense = np.flatnonzero(self.v_alive)
        act_vids = self.uv[act_dense]  # uv ascending ⇒ dense order = id order
        act_latest = self.v_lat[act_dense]
        act_first = self.v_first[act_dense]

        alive = self.e_alive
        enc = self.e_enc[alive]
        ae_s = self.uv[enc >> _ENC_SHIFT]
        ae_d = self.uv[enc & _ENC_MASK]
        ae_latest = self.e_lat[alive]
        ae_first = self.e_first[alive]
        # local endpoint indices via the dense→local LUT (enc order is
        # (src, dst)-major, so one argsort of the flipped packing gives the
        # (dst, src) order _assemble_view needs)
        lut = np.full(len(self.uv), -1, np.int32)
        lut[act_dense] = np.arange(len(act_dense), dtype=np.int32)
        src_loc = lut[enc >> _ENC_SHIFT]
        dst_loc = lut[enc & _ENC_MASK]
        eorder = np.argsort(
            (dst_loc.astype(np.int64) << _ENC_SHIFT) | src_loc, kind="stable")
        locs = (src_loc, dst_loc, eorder)

        eadd_rows = self._ea_rows
        vadd_rows = self._va_rows
        occ = None
        if self.include_occurrences:
            occ = (eadd_rows, self._t[eadd_rows],
                   self._s[eadd_rows], self._d[eadd_rows])
        return _assemble_view(
            self.log, time, act_vids, act_latest, act_first,
            ae_s, ae_d, ae_latest, ae_first, self.pad,
            eadd_rows, vadd_rows, occ, locs,
        )


# ------------------------------------------------------------- fold cache

_METRICS_SENTINEL = object()
_METRICS = _METRICS_SENTINEL


def _metrics():
    """obs.metrics bundle, or None when prometheus isn't importable —
    core must keep working in stripped environments."""
    global _METRICS
    if _METRICS is _METRICS_SENTINEL:
        try:
            from ..obs.metrics import METRICS

            _METRICS = METRICS
        except Exception:
            _METRICS = None
    return _METRICS


def _tracer():
    try:
        from ..obs.trace import TRACER

        return TRACER
    except Exception:
        return None


def _span(name: str, **attrs):
    """A span of the process tracer (a shared no-op with tracing off).
    ``obs/trace.py`` is stdlib-only; imported on use like ``_tracer``."""
    from ..obs.trace import TRACER

    return TRACER.span(name, **attrs)


def log_fingerprint(log) -> tuple:
    """Content identity of a pinned log for fold-cache keys: row count +
    order-sensitive checksums over every column, plus the append version.
    Cached on the (frozen, immutable) pin — repeated REST requests pin
    the same live log and must land on the same key, and two logs that
    merely share a version counter must not collide."""
    fp = getattr(log, "_rtpu_fold_fp", None)
    if fp is not None:
        return fp
    tr = _tracer()
    if tr is None:
        return _fingerprint(log)
    # O(events) on the caller's thread, once per pin. The engines over
    # one unchanged live log share the pin of its per-log index
    # (``engine/device_sweep.log_index``), so a run of requests pays it
    # once, not once a request
    with tr.span("fold.fingerprint", events=int(log.n)):
        return _fingerprint(log)


def extend_fingerprint(old_pin, new_pin) -> None:
    """Carry a cached fingerprint from ``old_pin`` to ``new_pin``, a pin
    of the same log that extends it by a suffix (no compaction between
    them): each checksum is an xor-reduce keyed by the ABSOLUTE row
    index, so the suffix's rows fold onto the prefix's in O(suffix) —
    under a ``fold.fingerprint`` span of its own (``extend``, ``events``
    the suffix's rows). A no-op when ``old_pin`` never computed one —
    ``log_fingerprint`` then computes the new pin's on first use."""
    fp = getattr(old_pin, "_rtpu_fold_fp", None)
    if fp is not None:
        with _span("fold.fingerprint", extend=True,
                   events=int(new_pin.n) - int(fp[0])):
            _fingerprint(new_pin, prefix=fp)


def _fingerprint(log, prefix: tuple | None = None) -> tuple:
    lo = 0 if prefix is None else int(prefix[0])
    t = log.column("time")
    idx = np.arange(lo, len(t), dtype=np.uint64)
    gold = np.uint64(0x9E3779B97F4A7C15)

    def mix(a, seed):
        a = a[lo:]
        if not len(a):
            return seed
        h = a.astype(np.int64, copy=False).view(np.uint64)
        return seed ^ int(
            np.bitwise_xor.reduce((h + gold) * (idx * gold + gold)))

    seeds = (0, 0, 0, 0) if prefix is None else prefix[2:]
    # src and dst stay SEPARATE components: xor-combining them would be
    # symmetric per row, colliding a graph with its (partial) transpose
    fp = (int(len(t)), int(log.version), mix(t, seeds[0]),
          mix(log.column("src"), seeds[1]), mix(log.column("dst"), seeds[2]),
          mix(log.column("kind"), seeds[3]))
    try:
        log._rtpu_fold_fp = fp   # pins are frozen: content never changes
    except AttributeError:
        pass
    return fp


class FoldCache:
    """Bounded, memory-accounted, cross-request fold cache (LRU).

    Two kinds of entries share one byte budget:

    * **payloads** — a columnar engine's complete fold output for an
      exact (log fingerprint, hop grid) — a repeated REST range job skips
      folding entirely (``engine/hopbatch`` integration);
    * **checkpoints** — ``FoldCheckpoint`` states at chunk boundaries,
      so a later sweep over the same log seeds its chunk forks from the
      NEAREST checkpoint instead of re-folding the prefix.

    All mutation is under one lock; values must be treated as immutable
    by callers (payload arrays are never written after insertion — the
    engines copy-on-ship by construction)."""

    def __init__(self, max_bytes: int):
        self.max_bytes = int(max_bytes)
        self._lock = threading.Lock()
        from collections import OrderedDict

        self._entries: "OrderedDict[tuple, tuple]" = OrderedDict()
        self._bytes = 0
        self.hits = 0
        self.misses = 0
        self.evictions = 0
        # entries refused for size (larger than the whole bound), and the
        # largest of them: a refusal evicts nothing, so without these a
        # log whose checkpoints outgrew the bound reads as a quiet cache
        self.refused = 0
        self.refused_bytes = 0
        # (fp, config) -> ascending checkpoint times, for nearest lookup
        self._ckpt_times: dict[tuple, list] = {}
        # lockset-sanitizer registration (None unless RTPU_SANITIZE):
        # cache accesses report their held lockset, so a future unguarded
        # fast path shows up as a shared-state-race finding in tier-1
        self._san_tracker = _san_track("fold_cache")

    def _note_shared(self, write: bool) -> None:
        _san_note(self._san_tracker, write)

    # -- internals (callers hold self._lock) --

    def _evict_until(self, budget: int) -> None:
        while self._bytes > budget and self._entries:
            key, (value, nbytes) = self._entries.popitem(last=False)
            self._bytes -= nbytes
            self.evictions += 1
            if key[0] == "ckpt":
                times = self._ckpt_times.get(key[1:3])
                if times is not None:
                    try:
                        times.remove(key[3])
                    except ValueError:
                        pass
            m = _metrics()
            if m is not None:
                m.fold_cache_evictions.inc()
                m.fold_cache_bytes.set(self._bytes)

    def _note(self, hit: bool, key: tuple, nbytes: int = 0) -> None:
        m = _metrics()
        if m is not None:
            (m.fold_cache_hits if hit else m.fold_cache_misses).inc()
        tr = _tracer()
        if tr is not None:
            tr.instant("fold.cache", hit=hit, kind=str(key[0]),
                       bytes=int(nbytes), cached_bytes=self._bytes)

    def _refuse(self, kind: str, nbytes: int) -> bool:
        """Count an entry larger than the whole bound; always False."""
        with self._lock:
            self._note_shared(write=True)
            self.refused += 1
            self.refused_bytes = max(self.refused_bytes, nbytes)
            cached = self._bytes
        tr = _tracer()
        if tr is not None:
            tr.instant("fold.cache", hit=False, kind=kind, bytes=nbytes,
                       cached_bytes=cached, refused=True)
        return False

    # -- payload entries --

    def get(self, key: tuple):
        """Cached value for ``key`` (LRU-touch) or None — counts a hit or
        a miss either way."""
        with self._lock:
            self._note_shared(write=True)   # LRU touch mutates order
            ent = self._entries.get(key)
            if ent is None:
                self.misses += 1
                self._note(False, key)
                return None
            self._entries.move_to_end(key)
            self.hits += 1
            self._note(True, key, ent[1])
            return ent[0]

    def put(self, key: tuple, value, nbytes: int) -> bool:
        """Insert (or refresh) ``key``; evicts LRU entries past the byte
        bound. Values larger than the whole bound are refused (False,
        counted in ``stats()``) — one oversized sweep must not flush
        every other tenant."""
        nbytes = int(nbytes)
        if nbytes > self.max_bytes:
            return self._refuse(str(key[0]), nbytes)
        with self._lock:
            self._note_shared(write=True)
            old = self._entries.pop(key, None)
            if old is not None:
                self._bytes -= old[1]
            self._entries[key] = (value, nbytes)
            self._bytes += nbytes
            self._evict_until(self.max_bytes)
            m = _metrics()
            if m is not None:
                m.fold_cache_bytes.set(self._bytes)
        return True

    # -- checkpoint entries --

    def put_checkpoint(self, fp: tuple, cp: FoldCheckpoint) -> bool:
        if cp.t_prev is None:
            return False
        if cp.nbytes > self.max_bytes:
            return self._refuse("ckpt", cp.nbytes)
        key = ("ckpt", fp, cp.config, int(cp.t_prev))
        with self._lock:
            self._note_shared(write=True)
            if key in self._entries:
                self._entries.move_to_end(key)
                return True
            self._entries[key] = (cp, cp.nbytes)
            self._bytes += cp.nbytes
            times = self._ckpt_times.setdefault((fp, cp.config), [])
            import bisect

            bisect.insort(times, int(cp.t_prev))
            self._evict_until(self.max_bytes)
            m = _metrics()
            if m is not None:
                m.fold_cache_bytes.set(self._bytes)
        return True

    def nearest_checkpoint(self, fp: tuple, config: tuple,
                           time: int) -> FoldCheckpoint | None:
        """Latest cached checkpoint at or before ``time`` for this log —
        the fork seed that minimises the prefix re-fold."""
        import bisect

        with self._lock:
            self._note_shared(write=True)   # hit path LRU-touches
            times = self._ckpt_times.get((fp, config))
            if not times:
                self.misses += 1
                self._note(False, ("ckpt", fp))
                return None
            i = bisect.bisect_right(times, int(time))
            if i == 0:
                self.misses += 1
                self._note(False, ("ckpt", fp))
                return None
            key = ("ckpt", fp, config, times[i - 1])
            ent = self._entries.get(key)
            if ent is None:   # index raced an eviction
                self.misses += 1
                self._note(False, key)
                return None
            self._entries.move_to_end(key)
            self.hits += 1
            self._note(True, key, ent[1])
            return ent[0]

    def stats(self) -> dict:
        with self._lock:
            self._note_shared(write=False)
            return {"entries": len(self._entries), "bytes": self._bytes,
                    "max_bytes": self.max_bytes, "hits": self.hits,
                    "misses": self.misses, "evictions": self.evictions,
                    "refused": self.refused,
                    "refused_bytes": self.refused_bytes}

    def clear(self) -> None:
        with self._lock:
            self._entries.clear()
            self._ckpt_times.clear()
            self._bytes = 0


def seeded_fork(live, boundary, cache, fp, cfg):
    """Fork the builder ``live`` at ``boundary`` (exclusive upper time
    of every earlier unit's hops): nearest cached checkpoint when one
    is ahead of the live builder, else the live state, then one bulk
    advance — recorded back as a checkpoint for the next request.
    The lookup and the fork are one ``fold.seed`` span (``nbytes``:
    what the fork copied, 0 — it shares the ``shared`` bytes of fold
    state it starts from and copies them at its first write, a
    second ``fold.seed`` span with ``deferred=true``), the advance
    the ``fold.checkpoint`` beside it. One seeding rule for every
    engine that folds a Range: the columnar engines' fold units
    (``engine/hopbatch._HopBatched._seed_fork``) and the vertex-sharded
    mesh sweep's first hop (``parallel/sweep.ShardedSweep``)."""
    with _span("fold.seed") as ssp:
        cp = cache.nearest_checkpoint(fp, cfg, boundary) \
            if cache is not None and boundary is not None else None
        t0 = live.t_prev
        if cp is not None and (t0 is None or cp.t_prev > t0):
            sw, seed = live.fork(cp), "checkpoint"
        else:
            sw = live.fork()
            # "start": neither the cache nor the live builder holds a
            # state, so this unit advances from the log's first event
            seed = "start" if sw.t_prev is None else "live"
        ssp.set(seed=seed, nbytes=0, shared=sw.fork_nbytes())
    if boundary is None:
        return sw
    if sw.t_prev is None or sw.t_prev < boundary:
        with _span("fold.checkpoint", time=int(boundary),
                   seeded_from=(-1 if sw.t_prev is None
                                else int(sw.t_prev)),
                   seed=seed) as sp:
            sw._advance(boundary)
            if cache is not None:
                # inside the span, so its args say what became of
                # the state this advance reached: refused for size
                # (stored=False), or stored at nbytes so near the
                # bound that the next insert evicts it — either way
                # the NEXT request's units read seed="start" again
                cp = sw.checkpoint()
                stored = cache.put_checkpoint(fp, cp)
                sp.set(stored=stored, nbytes=cp.nbytes)
    return sw


_FOLD_CACHE = None
_FOLD_CACHE_LOCK = threading.Lock()


def fold_cache() -> FoldCache | None:
    """Process-wide fold cache, sized by ``RTPU_FOLD_CACHE_MB`` (default
    256; ``0`` disables). The bound is re-read per call so tests and
    operators can resize/disable without a restart — a size change swaps
    in a fresh cache (the old one drains by GC)."""
    global _FOLD_CACHE
    mb = int(os.environ.get("RTPU_FOLD_CACHE_MB", 256))
    if mb <= 0:
        return None
    with _FOLD_CACHE_LOCK:
        if _FOLD_CACHE is None or _FOLD_CACHE.max_bytes != mb << 20:
            _FOLD_CACHE = FoldCache(mb << 20)
        return _FOLD_CACHE
