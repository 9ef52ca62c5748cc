"""TemporalGraph — the user-facing handle tying log, ingestion and views.

The single-process equivalent of the whole reference deployment
(``SingleNodeSetup.scala``): storage + ingestion + analysis access behind one
object. The watermark fence reproduces the ``TimeCheck``/``TimeResponse``
gate (``AnalysisTask.scala:162-195``): a view at T is only served as *exact*
once every source's watermark has passed T; otherwise the caller opts into
waiting or a best-effort (live) view.
"""

from __future__ import annotations

import collections
import threading
import time as _time

from ..ingestion.watermark import WatermarkRegistry
from ..obs.metrics import METRICS
from ..obs.trace import TRACER
from .events import EventLog
from .snapshot import GraphView, build_view


class StaleViewError(RuntimeError):
    pass


class TemporalGraph:
    def __init__(self, log: EventLog | None = None,
                 watermarks: WatermarkRegistry | None = None,
                 cache_size: int = 8):
        self.log = log if log is not None else EventLog()
        self.watermarks = watermarks if watermarks is not None else WatermarkRegistry()
        self._cache: collections.OrderedDict = collections.OrderedDict()
        self._cache_size = cache_size
        self._cache_lock = threading.Lock()  # jobs share one graph
        # warm View engine: one resident DeviceSweep shared by View/Live
        # dispatches (engine/device_sweep keeps fold state ON device) —
        # a repeat view is a delta-advance + one dispatch, not a full
        # host fold + O(m) upload (ReaderWorker.scala:293-352 rebuilds a
        # lens per job; this is the thing that beats it)
        self._resident = None
        self._resident_lock = threading.Lock()
        self._resident_version = -1
        self._resident_n = 0            # rows scanned for post-pin events
        self._post_pin_min = 2**62      # min event time appended after pin
        self._resident_broken = False   # e.g. >2^31 vertices: stop retrying

    # ---- time bounds ----

    @property
    def earliest_time(self) -> int:
        return self.log.min_time

    @property
    def latest_time(self) -> int:
        return self.log.max_time

    def safe_time(self) -> int:
        """Largest timestamp no in-flight source can still mutate."""
        return min(self.watermarks.safe_time(), 2**62)

    # ---- views (the GraphLens surface) ----

    def view_at(self, time: int, *, exact: bool = True,
                wait_timeout: float = 0.0,
                include_occurrences: bool = False) -> GraphView:
        """Snapshot at `time`. exact=True enforces the watermark fence,
        optionally polling up to wait_timeout seconds (the reference re-checks
        every 10 s — AnalysisTask.scala:183-189); exact=False serves a
        best-effort live view."""
        if exact:
            if not self.watermarks.wait_for(time, timeout=wait_timeout):
                raise StaleViewError(
                    f"view at {time} not yet safe: watermark="
                    f"{self.safe_time()} ({self.watermarks.snapshot()})")
        version = self.log.version
        key = (version, int(time), include_occurrences)
        with self._cache_lock:
            hit = self._cache.get(key)
            if hit is not None:
                self._cache.move_to_end(key)
                return hit
        t0 = _time.perf_counter()
        with TRACER.span("snapshot.fold", time=int(time),
                         occurrences=bool(include_occurrences)):
            view = build_view(self.log, int(time),
                              include_occurrences=include_occurrences)
        METRICS.snapshot_build_seconds.observe(_time.perf_counter() - t0)
        self.cache_put(int(time), view, include_occurrences, version=version)
        return view

    def cache_put(self, time: int, view: GraphView,
                  include_occurrences: bool = False, *,
                  version: int | None = None) -> None:
        """Insert an externally built view (e.g. a SweepBuilder hop) into the
        shared cache so later view_at calls reuse it. `version` must be the
        log version the view was BUILT from (a sweep's pinned log), not the
        current one — a compaction between build and insert would otherwise
        file a pre-compaction view under the post-compaction key, undoing
        invalidate_cache()."""
        METRICS.view_vertices.set(view.n_active)
        METRICS.view_edges.set(view.m_active)
        if version is None:
            version = self.log.version
        key = (version, int(time), include_occurrences)
        with self._cache_lock:
            self._cache[key] = view
            while len(self._cache) > self._cache_size:
                self._cache.popitem(last=False)

    def resident_acquire(self, time: int):
        """Acquire the shared resident DeviceSweep for a warm View dispatch
        at ``time``; returns ``(sweep, held_lock)`` — the caller MUST
        release the lock — or None when the resident path cannot serve:

        * ``time`` behind the sweep's clock (DeviceSweep only ascends; the
          cold path's view cache handles out-of-order timestamps), or
        * the log's id space overflows the packed-key engine.

        A pin is replaced (not declined) when events appended after it
        land at or before ``time`` — exact, via an incremental min over
        the post-pin rows.

        The caller is responsible for the watermark fence (only ask for
        ``time`` ≤ ``safe_time()``)."""
        if self._resident_broken:
            return None
        self._resident_lock.acquire()
        try:
            sweep = self._resident
            if sweep is not None:
                if self.log.version != self._resident_version:
                    # the pinned fold can't see events appended after the
                    # pin — an incremental min over the new rows tells
                    # EXACTLY whether any lands at or before `time`
                    # (watermarks alone can't: direct log appends are
                    # legal and unfenced). pin() captures (n, version)
                    # atomically, so rows landing after this scan bump the
                    # live version past the one stored here.
                    pinned = self.log.pin()
                    if self._resident_n < pinned.n:
                        tcol = pinned.column("time")
                        self._post_pin_min = min(
                            self._post_pin_min,
                            int(tcol[self._resident_n:pinned.n].min()))
                        self._resident_n = pinned.n
                    self._resident_version = pinned.version
                # checked on EVERY acquire, not only when the version just
                # moved — an earlier small-time acquire may have recorded
                # the post-pin min and synced the version already
                if int(time) >= self._post_pin_min:
                    # post-pin events land at or before `time`: ADOPT the
                    # appended suffix in place (DeviceSweep.repin) so the
                    # next advance folds exactly the new rows — the
                    # incremental live-serving path. Only a genuine
                    # rebuild condition (compaction, new vertex/pair,
                    # out-of-order arrival, dtype overflow) re-pins from
                    # scratch.
                    if sweep.repin(self.log) == "extended":
                        # invariant restored: the sweep's (frozen) pin
                        # captured (n, version) atomically and now covers
                        # every scanned row
                        self._resident_n = sweep.sw.log.n
                        self._resident_version = sweep.sw.log.version
                        self._post_pin_min = 2**62
                    else:
                        sweep = None   # stale for this time: re-pin below
            if sweep is None:
                from ..engine.device_sweep import DeviceSweep

                from ..obs import ledger as _ledger

                pinned = self.log.pin()   # (n, version) atomic with rows
                with _ledger.engine_build("pin", pinned) as sp:
                    sweep = DeviceSweep(pinned)
                    sp.set(**_ledger.built(sweep))
                self._resident = sweep
                self._resident_version = pinned.version
                self._resident_n = pinned.n
                self._post_pin_min = 2**62
            if sweep.t_now is not None and int(time) < sweep.t_now:
                self._resident_lock.release()
                return None
            return sweep, self._resident_lock
        except ValueError:
            self._resident_broken = True
            self._resident_lock.release()
            return None
        except BaseException:
            self._resident_lock.release()
            raise

    def resident_discard(self, log_replaced: bool = False) -> None:
        """Drop the resident sweep. Callers that hit device trouble
        mid-dispatch MUST call this while still holding the acquired lock:
        a partially applied delta leaves the device buffers inconsistent
        with the host fold, and the next acquire must re-pin.
        ``log_replaced`` also clears the broken latch — overflow is a
        property of the log, not of the graph object."""
        self._resident = None
        self._resident_version = -1
        self._resident_n = 0
        self._post_pin_min = 2**62
        if log_replaced:
            self._resident_broken = False

    # ---- maintenance ----

    def swap_log(self, new_log: EventLog) -> None:
        """Replace the log object; invalidates the view cache. NOTE: any
        ingestion pipeline holding the old log keeps writing there — prefer
        ``EventLog.compact_to`` (in-place) for live graphs."""
        self.log = new_log
        self.invalidate_cache()

    def invalidate_cache(self) -> None:
        with self._cache_lock:
            self._cache.clear()
        with self._resident_lock:
            # a swapped log may reuse version ids, and a previously
            # oversized log's broken latch must not outlive it
            self.resident_discard(log_replaced=True)

    def checkpoint(self, path: str) -> None:
        from ..persist.checkpoint import save_log

        save_log(self.log, path)

    @classmethod
    def restore(cls, path: str, **kw) -> "TemporalGraph":
        from ..persist.checkpoint import load_log

        return cls(log=load_log(path), **kw)

    def live_view(self, include_occurrences: bool = False) -> GraphView:
        """View at the current safe watermark (LiveAnalysisTask semantics:
        timestamp = min over workers' watermarks, LiveAnalysisTask.scala:55-105)."""
        t = min(self.safe_time(), self.latest_time)
        return self.view_at(t, exact=False,
                            include_occurrences=include_occurrences)
