"""Serving scheduler — cross-request query batching, ledger-priced
admission control, and per-request deadlines (ROADMAP item 1).

The fold cache and resident engines made *repeat* queries cheap; this
module makes *concurrent distinct* queries cheap. Today every REST
request is its own job thread dispatching its own sweep, even when ten
in-flight requests want overlapping (hop, window) views of the same
graph. Their views are just more COLUMNS — ``engine/hopbatch.py``
already batches columns — so the scheduler sits between ``jobs/rest.py``
and the engines and does three things:

* **Coalescing.** A short collect window (``RTPU_BATCH_WINDOW_MS``,
  default 3 ms; ``0`` restores today's behaviour exactly) groups
  compatible concurrent jobs — same graph log, same algorithm family
  and parameters, View/Range queries whose (hop, window) grids can
  stack — into ONE shared columnar dispatch
  (``hopbatch.stack_grids``), demultiplexing per-request results and
  splitting the shared phase seconds by column share
  (``Ledger.absorb_share``) afterwards. Incompatible jobs (meshes,
  live queries, non-columnar programs, tight deadlines, ``batch:false``
  or ``priority >= 8`` requests) pass through unbatched on exactly the
  pre-scheduler path; a window that collects only ONE job also declines
  to batch, so an idle server's per-request behaviour is unchanged.
  Fold checkpoints and fold-cache entries are shared across tenants
  exactly as the content-addressed ``FoldCache`` already permits.

* **Admission control** (``RTPU_ADMISSION=1``). Before a job is even
  created, the request is priced from the ledger's recent
  per-algorithm cost history (an EWMA seconds-per-view book fed by
  every completed job) times its view count, and judged against the
  live backlog of admitted-but-unfinished cost: over-budget requests,
  deadline-infeasible requests, over-share tenants and — while some
  SLO error budget is burning — the top-cost tenant (the advisor's
  ``queue-burn-shed-top-tenant`` recommendation, actuated) are shed
  with HTTP 429 + ``Retry-After`` and the evidence that justified it.

* **Deadlines.** Requests may carry ``deadline_ms``; a job whose
  deadline passes while it waits in a collect window fails fast with
  status ``expired`` — it never dispatches — and a job whose deadline
  is too tight for the collect window is never batched behind one.

Concurrency contract (rtpulint RT009/RT010/RT011): one lock guards the
queue + admission counters; no engine, device, or cross-module call
ever runs under it (batch dispatch, budget evaluation and workload
reads all happen outside); the queue, the price book and the per-tenant
live table are all explicitly bounded. The dispatcher thread is lazy —
started on first enqueue, exits after an idle period — so short-lived
managers in tests never leak threads.

Surfaces: a ``scheduler`` block in ``/statusz``, ``raphtory_scheduler_*``
Prometheus metrics, ``sched.batch`` / ``sched.shed`` / ``sched.deadline``
flight-recorder instants, and ``RTPU_SCHED_DUMP`` (full scheduler state
written at interpreter exit — the CI failure artifact). Design doc:
docs/SERVING.md.
"""

from __future__ import annotations

import itertools
import logging
import math
import os
import threading
import time as _time
import weakref

from ..analysis.sanitizer import (note_shared as _san_note,
                                  track_shared as _san_track)
from ..obs import budget as _budget
from ..obs import journal as _journal
from ..obs import ledger as _ledger
from ..obs import workload as _workload
from ..obs.metrics import METRICS
from ..obs.trace import TRACER
from ..resilience import faults as _faults

_log = logging.getLogger(__name__)

#: requests at or above this priority bypass the collect window — the
#: client's "latency over throughput" escape hatch (docs/SERVING.md)
PRIORITY_BYPASS = 8
#: per-request (hop, window) grid cell cap — matches the jobs layer's
#: columnar-route guard, so a request the scheduler would take is one
#: the solo path would also have taken columnar
MAX_REQUEST_CELLS = 256
#: jobs collected into one batch before it dispatches early
MAX_BATCH_JOBS = 128
#: distinct algorithm labels the price book tracks (dynamic ``rawFile``
#: programs could otherwise mint unbounded keys — RT011)
MAX_PRICE_KEYS = 128
#: default seconds-per-view before any cost history exists
DEFAULT_PRICE_S = 0.05

#: live scheduler instances (weak — a dead manager's scheduler must be
#: collectable) for the process-wide gauges and the RTPU_SCHED_DUMP /
#: tier-1 failure artifact
_INSTANCES: "weakref.WeakSet[ServingScheduler]" = weakref.WeakSet()
_BATCH_IDS = itertools.count()


# ------------------------------------------------------------------ knobs


def window_ms() -> float:
    """Collect-window length. Re-read per enqueue so the bench A/B (and
    operators) can flip coalescing without a restart; ``0`` disables the
    scheduler entirely — bit-identical to the pre-scheduler path."""
    try:
        return max(0.0, float(os.environ.get("RTPU_BATCH_WINDOW_MS", "3")
                              or 3.0))
    except ValueError:
        return 3.0


def admission_enabled() -> bool:
    return os.environ.get("RTPU_ADMISSION", "0") not in ("", "0", "false")


def admission_budget_s() -> float:
    try:
        return max(0.1, float(
            os.environ.get("RTPU_ADMISSION_BUDGET_S", "60") or 60.0))
    except ValueError:
        return 60.0


def admission_max_jobs() -> int:
    try:
        return max(1, int(
            os.environ.get("RTPU_ADMISSION_MAX_JOBS", "512") or 512))
    except ValueError:
        return 512


def tenant_share() -> float:
    """Max fraction of the admitted-job cap one tenant may hold."""
    try:
        return min(1.0, max(0.01, float(
            os.environ.get("RTPU_SCHED_TENANT_SHARE", "0.5") or 0.5)))
    except ValueError:
        return 0.5


def queue_cap() -> int:
    """Total jobs waiting in collect windows; past it, new jobs pass
    through unbatched (never dropped) — the queue is provably bounded."""
    try:
        return max(1, int(
            os.environ.get("RTPU_SCHED_QUEUE_CAP", "1024") or 1024))
    except ValueError:
        return 1024


def max_gate_ms() -> float:
    """Upper bound on how long backpressure gating may hold a waiting
    member behind its family's in-flight batch. Gating is what GROWS
    batches under load (the next batch collects while the current one
    runs), but unbounded gating puts a whole dispatch duration into the
    tail — past this bound the batch spills and dispatches concurrently
    instead (docs/SERVING.md "Backpressure")."""
    try:
        return max(0.0, float(
            os.environ.get("RTPU_SCHED_MAX_GATE_MS", "300") or 300.0))
    except ValueError:
        return 300.0


def max_cols() -> int:
    """Column cap of one coalesced dispatch (the batch grid is the hop
    union × window union cross product; overflow members start the next
    batch)."""
    try:
        return max(2, int(
            os.environ.get("RTPU_SCHED_MAX_COLS", "1024") or 1024))
    except ValueError:
        return 1024


# ------------------------------------------------- request classification


def family_of(program):
    """The batch-compatibility key of a program, or None when it has no
    columnar engine: programs coalesce ONLY when the whole tuple —
    family and every result-affecting parameter — matches, so a shared
    dispatch can never change any member's semantics."""
    from ..algorithms import ConnectedComponents as _CC
    from ..algorithms import PageRank as _PR
    from ..algorithms.traversal import SSSP as _SSSP

    p = program
    if type(p) is _PR:
        return ("pagerank", float(p.damping), float(p.tol),
                int(p.max_steps))
    if type(p) is _CC:
        return ("cc", int(p.max_steps))
    if type(p) is _SSSP:
        return ("sssp" if p.weight_prop else "bfs",
                tuple(sorted(int(s) for s in p.seeds)),
                p.weight_prop, bool(p.directed), int(p.max_steps))
    return None


def request_grid(query):
    """``(hops, windows)`` of a View/Range query — the request's own
    (hop, window) grid in the EXACT order a serial columnar dispatch
    would emit it (hops ascending, the request's window order), shared
    by the batch packer and the member-side demux
    (``Job._emit_coalesced``). None for live queries and over-cap
    grids."""
    from .manager import RangeQuery, ViewQuery

    if isinstance(query, ViewQuery):
        hops = [int(query.timestamp)]
    elif isinstance(query, RangeQuery):
        # COUNT before materialising: this runs on the REST submit
        # thread for every request, and a hostile (start, end, jump)
        # span must be rejected arithmetically, not after allocating
        # the hop list
        n_hops = _range_hop_count(query)
        windows_n = (len(query.windows) if query.windows is not None
                     else 1)
        if not n_hops or n_hops * windows_n > MAX_REQUEST_CELLS:
            return None
        hops = list(range(int(query.start), int(query.end) + 1,
                          int(query.jump)))
    else:
        return None
    windows = list(query.windows) if query.windows is not None \
        else [query.window]
    if not hops or len(hops) * len(windows) > MAX_REQUEST_CELLS:
        return None
    return hops, windows


def _range_hop_count(query) -> int:
    start, end, jump = int(query.start), int(query.end), int(query.jump)
    if end < start or jump <= 0:
        return 0
    return (end - start) // jump + 1


def views_of(query) -> int:
    """View count a query will emit — the admission price multiplier.
    Computed ARITHMETICALLY (never via request_grid): the biggest
    requests are exactly the ones admission exists to price, so an
    over-cap range must be priced at its real view count, not fall
    through to 1. Live queries estimate a bounded number of runs (they
    are unbatchable and long-lived; admission prices their near-term
    cost, not eternity)."""
    from .manager import LiveQuery, RangeQuery, ViewQuery

    if isinstance(query, ViewQuery):
        return len(query.windows) if query.windows is not None else 1
    if isinstance(query, RangeQuery):
        w = len(query.windows) if query.windows is not None else 1
        return max(1, _range_hop_count(query) * w)
    if isinstance(query, LiveQuery):
        per_run = len(query.windows) if query.windows is not None else 1
        runs = query.max_runs if query.max_runs is not None else 20
        return per_run * max(1, min(int(runs), 20))
    return 1


class AdmissionDenied(Exception):
    """A request shed by admission control — ``jobs/rest.py`` maps it to
    HTTP 429 with a ``Retry-After`` header and the evidence body. NOT a
    ValueError subclass: the REST layer's 400 mapping must never
    swallow a shed into a client-error response."""

    def __init__(self, message: str, retry_after_s: float,
                 evidence: dict):
        super().__init__(message)
        self.retry_after_s = max(1.0, float(retry_after_s))
        self.evidence = dict(evidence)


class _Pending:
    """One job waiting in a collect window. The job's OWN thread blocks
    on ``done`` and performs all result emission; the scheduler thread
    only computes the shared arrays and hands them over — result/ledger
    ownership never crosses threads."""

    __slots__ = ("job", "grid", "enqueued", "deadline", "done",
                 "outcome", "payload")

    def __init__(self, job, grid):
        self.job = job
        self.grid = grid
        self.enqueued = _time.monotonic()
        self.deadline = job.deadline
        self.done = threading.Event()
        #: "ok" | "declined" | "expired" | "killed" — set before done
        self.outcome = None
        self.payload = None

    def finish(self, outcome: str, payload: dict | None = None) -> None:
        self.outcome = outcome
        self.payload = payload
        self.done.set()


class ServingScheduler:
    """Per-manager coalescing queue + process-shared admission state.

    One instance per ``AnalysisManager`` (one graph per manager, so the
    "same graph log" compatibility rule is structural); the admission
    counters, price book and metrics are per instance but surfaced
    process-wide via the weak instance registry."""

    def __init__(self, graph):
        self._graph = graph
        self._cond = threading.Condition(threading.Lock())
        #: family key -> [_Pending] in arrival order (bounded: queue_cap)
        self._queues: dict[tuple, list[_Pending]] = {}
        #: family key -> monotonic time its CURRENT window opened
        self._opened: dict[tuple, float] = {}
        #: family key -> batches IN FLIGHT: while nonzero the family's
        #: queue keeps collecting (backpressure grows the next batch
        #: under load — the amortisation the whole subsystem exists
        #: for) while other families dispatch concurrently; the gate is
        #: bounded by max_gate_ms so a member never waits a whole
        #: dispatch duration into the tail
        self._dispatching: dict[tuple, int] = {}
        self._thread: threading.Thread | None = None
        # admission state (same lock): ledger-priced cost admitted but
        # not yet completed, per-tenant live job counts (entries pop at
        # zero, so the table is bounded by the live-job cap)
        self._live_cost_s = 0.0
        self._live_jobs = 0
        self._tenant_live: dict[str, int] = {}
        #: algorithm label -> (ewma seconds per view, observations)
        self._prices: dict[str, tuple[float, int]] = {}
        self._stats = {"batches": 0, "coalesced_jobs": 0,
                       "deadline_expired": 0, "solo_passthrough": 0,
                       "queue_overflow_passthrough": 0,
                       "batch_declined": 0}
        self._shed: dict[str, int] = {}
        self._batch_sizes: dict[int, int] = {}
        self._san_tracker = _san_track("scheduler_queue")
        _INSTANCES.add(self)

    # ------------------------------------------------------- coalescing

    def _eligible(self, job):
        """(family, grid) when ``job`` may join a collect window, else
        None (pass through on today's path)."""
        if window_ms() <= 0.0:
            return None
        if job.mesh is not None or job.no_batch:
            return None
        if job.priority >= PRIORITY_BYPASS:
            return None
        fam = family_of(job.program)
        if fam is None:
            return None
        grid = request_grid(job.query)
        if grid is None:
            return None
        try:
            if self._graph.safe_time() < max(grid[0]):
                return None   # the cold path owns the fence wait
        except Exception:
            return None
        if job.deadline is not None:
            # never batch a tight-deadline job behind a collect window:
            # the worst queueing a batched job can see is the window
            # PLUS the backpressure gate (its family's in-flight
            # dispatch, bounded by max_gate_ms) — a deadline without
            # slack for BOTH must take the solo path, where it
            # dispatches the moment its thread runs
            slack = job.deadline - _time.monotonic()
            worst_queue_s = (2.0 * window_ms() + max_gate_ms()) / 1000.0
            if slack < worst_queue_s + 0.005:
                return None
        return fam, grid

    def offer(self, job) -> bool:
        """Enqueue ``job`` into its family's collect window; returns
        False (job passes through unbatched) for ineligible jobs and
        when the bounded queue is full — the scheduler sheds WORK into
        the solo path, never drops it."""
        elig = self._eligible(job)
        if elig is None:
            return False
        fam, grid = elig
        pend = _Pending(job, grid)
        with self._cond:
            _san_note(self._san_tracker, True)
            if sum(len(q) for q in self._queues.values()) >= queue_cap():
                self._stats["queue_overflow_passthrough"] += 1
                return False
            q = self._queues.get(fam)
            if q is None:
                q = self._queues[fam] = []
                self._opened[fam] = _time.monotonic()
            q.append(pend)
            self._ensure_thread_locked()
            self._cond.notify_all()
        job._coalesce = pend
        return True

    def _ensure_thread_locked(self) -> None:
        if self._thread is None or not self._thread.is_alive():
            self._thread = threading.Thread(
                target=self._loop, name="sched-dispatch", daemon=True)
            self._thread.start()

    def _pop_due_locked(self, now: float):
        """(due batches, seconds until the next window closes)."""
        win_s = window_ms() / 1000.0
        gate_s = max_gate_ms() / 1000.0
        due, wait = [], None
        for key in list(self._queues):
            if self._dispatching.get(key):
                # this family's previous batch is still on the device:
                # keep collecting (the next batch grows — backpressure
                # IS the batching signal) UNTIL the oldest waiter has
                # been gated max_gate_ms — then spill and dispatch
                # concurrently, so gating never puts a whole dispatch
                # duration into the latency tail
                oldest = self._queues[key][0].enqueued
                spill_at = oldest + gate_s
                if now < spill_at:
                    left = spill_at - now
                    wait = left if wait is None else min(wait, left)
                    continue
            opened = self._opened.get(key, now)
            if (now >= opened + win_s
                    or len(self._queues[key]) >= MAX_BATCH_JOBS):
                due.append((key, self._queues.pop(key)))
                self._opened.pop(key, None)
                self._dispatching[key] = (
                    self._dispatching.get(key, 0) + 1)
            else:
                left = opened + win_s - now
                wait = left if wait is None else min(wait, left)
        return due, wait

    def _loop(self) -> None:
        idle_exit = max(1.0, 20.0 * window_ms() / 1000.0)
        while True:
            with self._cond:
                _san_note(self._san_tracker, True)
                due, wait = self._pop_due_locked(_time.monotonic())
                if not due:
                    if not self._queues:
                        # idle: wait for work, exit after the grace so
                        # short-lived managers never leak a thread
                        if not self._cond.wait(timeout=idle_exit) \
                                and not self._queues:
                            self._thread = None
                            return
                        continue
                    self._cond.wait(timeout=wait)
                    continue
            for key, pendings in due:   # OUTSIDE the lock (RT009)
                # one short-lived thread per batch: dispatching inline
                # would park the NEXT family's members behind this whole
                # device dispatch — a cross-family tail the off arm
                # doesn't have. Thread count is bounded by batches in
                # flight, each of which replaced >= 2 would-be job
                # dispatch threads, so this is strictly fewer threads
                # than the pre-scheduler path under the same load.
                threading.Thread(
                    target=self._dispatch_safe, args=(key, pendings),
                    name="sched-batch", daemon=True).start()

    def _dispatch_safe(self, key, pendings) -> None:
        try:
            self._dispatch(key, pendings)
        except Exception as e:   # a batch bug must not wedge
            _log.warning(        # every member forever
                "scheduler dispatch crashed (%s: %s) — members "
                "fall back to their own paths",
                type(e).__name__, e)
            for p in pendings:
                if not p.done.is_set():
                    p.finish("declined")
        finally:
            with self._cond:
                left = self._dispatching.get(key, 1) - 1
                if left > 0:
                    self._dispatching[key] = left
                else:
                    self._dispatching.pop(key, None)
                if self._queues:
                    # arrivals accumulated during the dispatch; the
                    # dispatcher may have idle-exited meanwhile
                    self._ensure_thread_locked()
                self._cond.notify_all()   # re-evaluate this family's
                # window: accumulated arrivals are (usually) already
                # past it and pop immediately

    def _requeue_front_locked(self, key, pendings) -> None:
        q = self._queues.get(key)
        if q is None:
            q = self._queues[key] = []
        q[0:0] = pendings
        # overflow members open an already-expired window: they dispatch
        # on the very next loop pass instead of waiting a fresh window.
        # The dispatcher may have idle-exited while this batch ran — the
        # requeue must restart it or these members would sit until the
        # next offer()
        self._opened[key] = _time.monotonic() - window_ms() / 1000.0
        self._ensure_thread_locked()

    def _dispatch(self, key, pendings) -> None:
        import numpy as np

        now = _time.monotonic()
        live = []
        for p in pendings:
            if p.job._kill.is_set():
                p.finish("killed")
            elif p.deadline is not None and now > p.deadline:
                # expired in queue: fail fast, never dispatch
                self._count("deadline_expired")
                METRICS.scheduler_deadline_expired.inc()
                TRACER.instant("sched.deadline", job_id=p.job.id,
                               waited_seconds=round(now - p.enqueued, 4))
                if _journal.enabled():
                    _journal.emit("sched", {
                        "decision": "deadline_expired",
                        "where": "queue", "job_id": p.job.id,
                        "waited_seconds": round(now - p.enqueued, 4)},
                        trace_id=getattr(p.job, "trace_id", None))
                p.finish("expired")
            else:
                live.append(p)
        if not live:
            return
        if len(live) == 1:
            # a window that collected one job has nothing to amortise:
            # decline so the solo path behaves exactly as pre-scheduler
            self._count("solo_passthrough")
            live[0].finish("declined")
            return
        # greedy pack under the column cap; overflow re-queues and
        # dispatches as the next batch immediately
        cap = max_cols()
        hop_set: set = set()
        win_set: set = set()
        take, rest = [], []
        from ..engine.device_sweep import normalize_windows
        from ..engine.hopbatch import stack_grids

        for p in live:
            ts, ws = p.grid
            nh = hop_set | {int(t) for t in ts}
            nw = win_set | set(normalize_windows(ws))
            if take and len(nh) * len(nw) > cap:
                rest.append(p)
                continue
            hop_set, win_set = nh, nw
            take.append(p)
        if rest:
            with self._cond:
                _san_note(self._san_tracker, True)
                self._requeue_front_locked(key, rest)
                self._cond.notify_all()
        if len(take) == 1:
            self._count("solo_passthrough")
            take[0].finish("declined")
            return

        grids = [p.grid for p in take]
        hops, wlist, cols = stack_grids(grids)
        total_cols = len(hops) * len(wlist)
        leader = take[0].job
        try:
            # on the scheduler's thread, before the batch's dispatch
            # starts: the members wait it out in their `sched_wait`, so
            # the span is its record and no ledger gets a `build` phase
            with _ledger.engine_build("request", leader.graph.log) as sp:
                hb = leader._columnar_builder()
                sp.set(**_ledger.built(hb))
            # the same memory guards the solo columnar route applies —
            # an over-guard batch declines rather than misrouting
            if (hb.device_mask_bytes(total_cols) > 1 << 32
                    or hb.host_column_bytes(len(hops)) > 1 << 29):
                raise MemoryError("batch grid exceeds the columnar "
                                  "memory guards")
        except Exception as e:
            _log.info("coalesced dispatch declined (%s: %s) — %d members "
                      "take their own paths", type(e).__name__, e,
                      len(take))
            self._count("batch_declined")
            for p in take:
                p.finish("declined")
            return

        from .manager import _shell_from_fold

        shells: dict = {}

        def grab_shell(T, sw):
            shells[int(T)] = _shell_from_fold(hb.tables, sw, int(T))

        batch_id = f"batch_{next(_BATCH_IDS)}"
        fam_name = key[0]
        led = _ledger.Ledger(batch_id, fam_name)
        dispatch_started = _time.monotonic()
        t0 = _time.perf_counter()
        try:
            with TRACER.span("sched.dispatch", batch=batch_id,
                             family=fam_name, jobs=len(take),
                             hops=len(hops), windows=len(wlist),
                             cols=total_cols), \
                    _ledger.activate(led):
                # the sched.dispatch failpoint: an injected failure
                # rides the existing decline path — every member falls
                # back to its solo route, availability costs nothing
                _faults.fire("sched.dispatch")
                ranks, steps = hb.run(hops, wlist, chunks=1,
                                      hop_callback=grab_shell)
                ranks = np.asarray(ranks)
                steps = int(steps)
        except Exception as e:
            # a failed shared dispatch must cost availability nothing:
            # every member falls back to its own (pre-scheduler) path
            _log.warning("coalesced dispatch failed (%s: %s) — %d "
                         "members fall back to their own paths",
                         type(e).__name__, e, len(take))
            self._count("batch_declined")
            for p in take:
                p.finish("declined")
            return
        elapsed = _time.perf_counter() - t0
        METRICS.supersteps.inc(max(steps, 0))
        METRICS.scheduler_batches.labels(fam_name).inc()
        METRICS.scheduler_coalesced_jobs.observe(len(take))
        with self._cond:
            self._stats["batches"] += 1
            self._stats["coalesced_jobs"] += len(take)
            self._batch_sizes[len(take)] = (
                self._batch_sizes.get(len(take), 0) + 1)
        TRACER.instant(
            "sched.batch", batch=batch_id, family=fam_name,
            jobs=len(take), hops=len(hops), windows=len(wlist),
            cols=total_cols, elapsed_seconds=round(elapsed, 6),
            fold_seconds=round(float(hb.fold_seconds), 6))
        if _journal.enabled():
            _journal.emit("sched", {
                "decision": "batch", "batch": batch_id,
                "family": fam_name, "jobs": len(take),
                "cols": total_cols,
                "elapsed_seconds": round(elapsed, 6),
                "fold_seconds": round(float(hb.fold_seconds), 6)})
        snap = led.as_dict()
        fold_s = float(hb.fold_seconds)
        # a column REQUESTED BY SEVERAL members splits its cost among
        # them (identical concurrent requests are the headline case —
        # each must absorb 1/N of their shared column, not 100% of the
        # batch); cells nobody asked for are the coalescing overhead
        # and stay unattributed, so member shares sum to <= 1 exactly
        # as absorb_share's conservation contract promises
        requesters: dict[int, int] = {}
        for mycols in cols:
            for c in mycols:
                requesters[c] = requesters.get(c, 0) + 1
        for p, mycols in zip(take, cols):
            share = (sum(1.0 / requesters[c] for c in mycols)
                     / max(total_cols, 1))
            p.finish("ok", payload={
                "ranks": ranks, "steps": steps, "shells": shells,
                "cols": mycols, "elapsed": elapsed,
                "fold_seconds": fold_s, "share": share,
                "total_cols": total_cols,
                "dispatch_started": dispatch_started,
                "snap": snap,
                "batch": {"batch_id": batch_id, "family": fam_name,
                          "jobs": len(take), "columns": len(mycols),
                          "total_columns": total_cols,
                          "share": round(share, 4)},
            })

    def _count(self, what: str, n: int = 1) -> None:
        with self._cond:
            self._stats[what] = self._stats.get(what, 0) + n

    # -------------------------------------------------------- admission

    def price(self, program, query) -> float:
        """Estimated cost seconds of a request: its view count × the
        algorithm's EWMA seconds-per-view from completed-job history
        (``DEFAULT_PRICE_S`` before any history exists). Live
        subscriptions price from the ``live:`` book when the epoch
        engine has fed it — an incremental epoch costs O(delta), not
        the O(m) a one-shot view of the same algorithm implies, so the
        admission book must not overcharge standing subscriptions."""
        from .manager import LiveQuery

        alg = getattr(program, "cost_label", type(program).__name__)
        views = views_of(query)
        with self._cond:
            per = self._prices.get(alg, (DEFAULT_PRICE_S, 0))[0]
            if isinstance(query, LiveQuery):
                live = self._prices.get(f"live:{alg}")
                if live is not None:
                    per = live[0]
        return views * per

    def note_live_epoch(self, algorithm: str, seconds: float) -> None:
        """One live epoch served in ``seconds``: EWMA it into the
        ``live:<algorithm>`` price-book key so admission prices standing
        subscriptions from measured epoch cost rather than the one-shot
        view price (same 0.7/0.3 fold as ``complete()``)."""
        alg = f"live:{algorithm}"
        per = max(0.0, float(seconds))
        with self._cond:
            _san_note(self._san_tracker, True)
            prev = self._prices.get(alg)
            if prev is None:
                if len(self._prices) >= MAX_PRICE_KEYS:
                    return   # bounded book (RT011)
                self._prices[alg] = (per, 1)
            else:
                ewma, n = prev
                self._prices[alg] = (0.7 * ewma + 0.3 * per, n + 1)

    def admit(self, program, query, tenant: str,
              deadline_ms=None) -> float:
        """Price the request and either register its cost into the live
        backlog (returns the estimate — the caller must ``complete()``
        or ``cancel()`` it) or shed it with :class:`AdmissionDenied`.
        With ``RTPU_ADMISSION`` off the backlog is still tracked (so
        flipping admission on mid-run starts with honest state) but
        nothing is ever shed."""
        est = self.price(program, query)
        tenant = _workload.normalize_tenant(tenant)
        shed = None
        if admission_enabled():
            # budget/workload reads take their own locks: OUTSIDE ours
            burning_top = None
            try:
                if _budget.BUDGET.status_block()["grade"] == "burning":
                    top = _workload.WORKLOAD.top_by_cost(1)
                    if top:
                        burning_top = top[0]["tenant"]
            except Exception:
                burning_top = None
            budget_s = admission_budget_s()
            cap = admission_max_jobs()
            t_cap = max(1, int(cap * tenant_share()))
            # decide AND register in ONE critical section (the decision
            # is pure arithmetic on our own counters): a burst of K
            # concurrent submits must not all read depth = cap-1 and
            # register together past every advertised bound — the burst
            # is exactly when admission matters
            with self._cond:
                _san_note(self._san_tracker, True)
                depth = self._live_jobs
                backlog = self._live_cost_s
                t_live = self._tenant_live.get(tenant, 0)
                if depth >= cap:
                    shed = ("queue_full",
                            f"{depth} admitted jobs >= cap {cap}",
                            max(1.0, backlog / max(depth, 1) * 4))
                elif t_live >= t_cap:
                    shed = ("tenant_share",
                            f"tenant {tenant!r} holds {t_live} live "
                            f"jobs >= its bounded share {t_cap}",
                            max(1.0, backlog / max(depth, 1) * 2))
                elif burning_top is not None and tenant == burning_top:
                    # the advisor's queue-burn-shed-top-tenant finding,
                    # actuated: while some error budget burns, the
                    # top-cost tenant's NEW work is shed until it drops
                    shed = ("shed_top_tenant",
                            f"SLO error budget burning and tenant "
                            f"{tenant!r} holds the top attributed cost",
                            max(2.0, min(30.0, backlog)))
                elif backlog + est > budget_s:
                    shed = ("over_budget",
                            f"priced backlog {backlog:.2f}s + this "
                            f"request {est:.2f}s exceeds "
                            f"RTPU_ADMISSION_BUDGET_S={budget_s:g}",
                            math.ceil(max(1.0, backlog + est - budget_s)))
                elif (deadline_ms is not None
                      and backlog + est > float(deadline_ms) / 1000.0):
                    shed = ("deadline_infeasible",
                            f"deadline_ms={deadline_ms:g} cannot be "
                            f"met: projected wait {backlog:.2f}s + cost "
                            f"{est:.2f}s already exceeds it",
                            math.ceil(max(1.0, backlog)))
                if shed is None:
                    self._live_cost_s += est
                    self._live_jobs += 1
                    self._tenant_live[tenant] = t_live + 1
                else:
                    self._shed[shed[0]] = self._shed.get(shed[0], 0) + 1
            if shed is not None:
                reason, why, retry_after = shed
                evidence = {
                    "reason": reason, "tenant": tenant,
                    "queue_depth": depth,
                    "backlog_seconds": round(backlog, 3),
                    "priced_cost_seconds": round(est, 4),
                    "budget_seconds": budget_s,
                    "retry_after_s": float(retry_after),
                }
                if deadline_ms is not None:
                    evidence["deadline_ms"] = float(deadline_ms)
                if burning_top is not None:
                    evidence["burning_top_tenant"] = burning_top
                METRICS.scheduler_shed.labels(reason).inc()
                TRACER.instant("sched.shed", reason=reason,
                               tenant=tenant, queue_depth=depth,
                               backlog_seconds=round(backlog, 3),
                               priced_cost_seconds=round(est, 4))
                if _journal.enabled():
                    _journal.emit("sched", dict(
                        evidence, decision="shed"), tenant=tenant)
                raise AdmissionDenied(f"admission shed ({reason}): {why}",
                                      retry_after, evidence)
            return est
        with self._cond:
            _san_note(self._san_tracker, True)
            self._live_cost_s += est
            self._live_jobs += 1
            self._tenant_live[tenant] = (
                self._tenant_live.get(tenant, 0) + 1)
        return est

    def cancel(self, est: float, tenant: str) -> None:
        """Roll back a registered admission when job creation failed
        after ``admit()`` succeeded."""
        self._release(est, _workload.normalize_tenant(tenant))

    def _release(self, est, tenant: str) -> None:
        with self._cond:
            _san_note(self._san_tracker, True)
            if est is not None:
                self._live_cost_s = max(0.0, self._live_cost_s - est)
            self._live_jobs = max(0, self._live_jobs - 1)
            left = self._tenant_live.get(tenant, 0) - 1
            if left > 0:
                self._tenant_live[tenant] = left
            else:
                self._tenant_live.pop(tenant, None)

    def complete(self, job) -> None:
        """Completion hook (``Job._publish_ledger``): release the job's
        admitted cost and fold its measured cost into the price book."""
        est = getattr(job, "_admitted_cost_s", None)
        if est is None:
            return
        job._admitted_cost_s = None
        self._release(est, job.tenant)
        led = job.ledger
        if led.status != "done" or led.views <= 0:
            # only SUCCESSFUL jobs price the book: an expired-in-queue
            # burst (views=0, seconds~0) would EWMA the price toward 0
            # and silently disable shedding exactly under overload,
            # while a mid-dispatch failure would record its sunk cost
            # against zero views and 429 healthy traffic
            return
        # price from the job's ATTRIBUTED work (its column share of a
        # coalesced dispatch via absorb_share, its own phases solo) —
        # never from member wall clock, which includes collect-window
        # and gate waits: pricing queueing into the book would make
        # load inflate prices inflate shedding, a positive feedback
        # loop exactly where admission must stay calm
        with led._lock:
            ph = dict(led.phase_seconds)
        seconds = max(0.0, sum(ph.values()) - ph.get("sched_wait", 0.0)
                      - ph.get("other", 0.0))
        views = max(1, led.views)
        alg = led.algorithm or "unknown"
        per = seconds / views
        with self._cond:
            _san_note(self._san_tracker, True)
            prev = self._prices.get(alg)
            if prev is None:
                if len(self._prices) >= MAX_PRICE_KEYS:
                    return   # bounded book: dynamic programs can't grow it
                self._prices[alg] = (per, 1)
            else:
                ewma, n = prev
                self._prices[alg] = (0.7 * ewma + 0.3 * per, n + 1)

    # --------------------------------------------------------- surfaces

    def queue_depth(self) -> int:
        with self._cond:
            _san_note(self._san_tracker, False)
            return sum(len(q) for q in self._queues.values())

    def backlog_seconds(self) -> float:
        with self._cond:
            _san_note(self._san_tracker, False)
            return self._live_cost_s

    def status_block(self) -> dict:
        """The ``scheduler`` block of /statusz (and the CI failure
        artifact): queue depth by class, batches formed, the
        coalesced-jobs histogram, shed/deadline counters, the admission
        state and the price book."""
        with self._cond:
            _san_note(self._san_tracker, False)
            by_class: dict[str, int] = {}
            for k, q in self._queues.items():
                # aggregate by family NAME: two parameterisations of
                # one algorithm are distinct batch keys but one class
                by_class[k[0]] = by_class.get(k[0], 0) + len(q)
            stats = dict(self._stats)
            shed = dict(self._shed)
            sizes = {str(k): v
                     for k, v in sorted(self._batch_sizes.items())}
            live_jobs = self._live_jobs
            backlog = self._live_cost_s
            tenants = dict(self._tenant_live)
            prices = {a: round(p, 6)
                      for a, (p, _) in self._prices.items()}
        return {
            "enabled": window_ms() > 0.0,
            "window_ms": window_ms(),
            "admission": admission_enabled(),
            "queue_depth": sum(by_class.values()),
            "queue_by_class": by_class,
            "batches_formed": stats["batches"],
            "jobs_coalesced": stats["coalesced_jobs"],
            "coalesced_jobs_hist": sizes,
            "solo_passthrough": stats["solo_passthrough"],
            "batch_declined": stats["batch_declined"],
            "queue_overflow_passthrough":
                stats["queue_overflow_passthrough"],
            "deadline_expired": stats["deadline_expired"],
            "shed": shed,
            "admitted_live_jobs": live_jobs,
            "backlog_seconds": round(backlog, 3),
            "tenant_live_jobs": tenants,
            "prices_seconds_per_view": prices,
            "caps": {"queue": queue_cap(),
                     "admitted_jobs": admission_max_jobs(),
                     "budget_seconds": admission_budget_s(),
                     "tenant_share": tenant_share(),
                     "batch_cols": max_cols(),
                     "max_gate_ms": max_gate_ms()},
        }


    def clear_stats(self) -> None:
        """Reset the counter tables (tests, operator resets). Their key
        spaces are small by construction — ``_stats`` a fixed literal
        set, ``_shed`` the five admission reasons, ``_batch_sizes`` at
        most MAX_BATCH_JOBS distinct sizes — and this reset is the
        explicit shrink that keeps a long-lived server's scheduler
        state restartable without a process bounce. The learned
        admission price book is deliberately NOT cleared: resetting
        counters must never revert pricing to the cold default."""
        with self._cond:
            self._stats = {"batches": 0, "coalesced_jobs": 0,
                           "deadline_expired": 0, "solo_passthrough": 0,
                           "queue_overflow_passthrough": 0,
                           "batch_declined": 0}
            self._shed = {}
            self._batch_sizes = {}


# -------------------------------------------------- process-wide helpers


def note_deadline_expired(job) -> None:
    """Count a deadline that expired before the job thread ever
    dispatched (the non-batched twin of the scheduler-queue expiry)."""
    METRICS.scheduler_deadline_expired.inc()
    TRACER.instant("sched.deadline", job_id=job.id, where="job_start")
    if _journal.enabled():
        _journal.emit("sched", {
            "decision": "deadline_expired", "where": "job_start",
            "job_id": job.id},
            trace_id=getattr(job, "trace_id", None))
    sched = getattr(job, "_sched", None)
    if sched is not None:
        sched._count("deadline_expired")


def total_queue_depth() -> float:
    """Sum over live schedulers — the Prometheus gauge callback."""
    return float(sum(s.queue_depth() for s in list(_INSTANCES)))


def total_backlog_seconds() -> float:
    return float(sum(s.backlog_seconds() for s in list(_INSTANCES)))


def schedulerz() -> dict:
    """Every live scheduler's status block — the RTPU_SCHED_DUMP
    document (tier-1 failure artifact, next to the flight recorder)."""
    return {"schedulers": [s.status_block() for s in list(_INSTANCES)]}


_sched_dump = os.environ.get("RTPU_SCHED_DUMP")
if _sched_dump:
    import json as _json

    from ..obs import exitdump as _exitdump

    def _dump_sched(path=_sched_dump):
        with open(path, "w") as f:
            _json.dump(schedulerz(), f, indent=1)

    _exitdump.register("sched", _dump_sched)
