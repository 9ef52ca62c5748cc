"""Job orchestration: Live/View/Range analysis × window variants.

The reference spawns 1-of-9 ``AnalysisTask`` actors per request —
{Live, View, Range} × {plain, windowed, batch-windowed}
(``AnalysisManager.scala:72-167``, ``Tasks/``) — each driving the actor BSP
handshake per timestamp. Here a job is a host thread sweeping timestamps and
invoking the compiled engine; the 9-way matrix collapses into one loop with
a window parameter, and the per-hop handshake disappears (compiled runner +
snapshot cache are reused across hops).
"""

from __future__ import annotations

import itertools
import os
import threading
import time as _time
import traceback
from dataclasses import dataclass

from ..analysis.sanitizer import (note_shared as _san_note,
                                  track_shared as _san_track)
from ..core.service import TemporalGraph
from ..engine import bsp
from ..engine.program import VertexProgram
from ..obs import advisor as _advisor
from ..obs import freshness as _fresh
from ..obs import journal as _journal
from ..obs import ledger as _ledger
from ..obs import slo as _slo
from ..obs import workload as _workload
from ..obs.metrics import METRICS
from ..obs.trace import TRACER, block_steps as _block_steps
from ..resilience import degrade as _degrade
from ..resilience.policy import default_classify as _transient

import logging

_jobs_log = logging.getLogger(__name__)


def _wait_steps(steps) -> int:
    """A hop's wait for the devices: the superstep count of its dispatch,
    read under ``superstep.block``."""
    return _block_steps(lambda: (None, steps))[1]


def declinable(e: BaseException) -> bool:
    """May a device route that raised ``e`` decline to the next rung?

    Only for a failure the next rung can get around: a transport error
    or an injected ``FaultError`` (the rung's device state is rebuilt
    from the host fold) or memory exhaustion (the next rung holds O(1)
    device memory per hop). Anything
    else — a program the compiler refuses, a runtime ``INTERNAL``, a bug —
    fails the job with the error: a kernel that does not run on the
    device must not look like a slow ``done`` job."""
    return (_transient(e) or isinstance(e, MemoryError)
            or "RESOURCE_EXHAUSTED" in str(e))


@dataclass(frozen=True)
class ViewQuery:
    """One timestamp (ViewAnalysisTask)."""
    timestamp: int
    window: int | None = None
    windows: tuple | None = None


@dataclass(frozen=True)
class RangeQuery:
    """Timestamp sweep start..end step jump (RangeAnalysisTask.scala:18-35)."""
    start: int
    end: int
    jump: int
    window: int | None = None
    windows: tuple | None = None

    def __post_init__(self):
        if int(self.jump) <= 0:
            # jump=0 would spin every sweep loop forever (REST bodies pass
            # raw ints straight through) — refuse at construction
            raise ValueError(f"jump must be positive, got {self.jump}")


@dataclass(frozen=True)
class LiveQuery:
    """Repeating analysis at the moving watermark (LiveAnalysisTask).
    event_time=False: re-run every repeat seconds of processing time;
    event_time=True: advance the target timestamp by `repeat` event-time
    units and wait for the watermark (LiveAnalysisTask.scala:34-52)."""
    repeat: float = 1.0
    event_time: bool = False
    max_runs: int | None = None   # None = until killed
    window: int | None = None
    windows: tuple | None = None


Query = ViewQuery | RangeQuery | LiveQuery


class Job:
    def __init__(self, job_id: str, program: VertexProgram, query: Query,
                 graph: TemporalGraph, mesh=None, wait_timeout: float = 30.0,
                 explain: bool = False, tenant: str | None = None,
                 deadline_ms=None, priority: int = 0,
                 no_batch: bool = False):
        self.id = job_id
        self.program = program
        self.query = query
        self.graph = graph
        self.mesh = mesh
        self.wait_timeout = wait_timeout
        #: client deadline (jobs/scheduler.py): absolute monotonic
        #: seconds, or None. An expired-in-queue job fails fast with
        #: status "expired" and never dispatches.
        self.deadline_ms = None if deadline_ms is None else float(deadline_ms)
        self.deadline = (None if deadline_ms is None
                         else _time.monotonic() + float(deadline_ms) / 1e3)
        if self.deadline_ms is not None and not self.deadline_ms > 0:
            raise ValueError(
                f"deadline_ms must be positive, got {deadline_ms!r}")
        #: >= scheduler.PRIORITY_BYPASS skips the coalescing collect
        #: window entirely (latency over throughput)
        self.priority = int(priority or 0)
        #: per-request coalescing opt-out (REST `batch: false`)
        self.no_batch = bool(no_batch)
        #: _Pending handle while waiting in a scheduler collect window
        self._coalesce = None
        #: the manager's ServingScheduler (admission/price hooks); None
        #: for directly-constructed jobs
        self._sched = None
        self._admitted_cost_s = None
        #: per-query resource ledger — always collected (cheap dict
        #: accounting); ``explain`` additionally returns it with the
        #: results over REST (obs/ledger.py)
        self.explain = bool(explain)
        self.ledger = _ledger.Ledger(
            job_id, getattr(program, "cost_label", type(program).__name__))
        #: normalized tenant identity (obs/workload.py): the account this
        #: job's closed ledger rolls into. Normalization NEVER raises —
        #: a malformed tenant header must not fail the request it rode
        self.tenant = _workload.normalize_tenant(tenant)
        self.ledger.tenant = self.tenant
        # trace-context handoff: a Job is constructed on the SUBMITTING
        # thread (the REST handler's rest.request span is still open),
        # and the job thread adopts this context in _run — so one REST
        # request and its job share one trace id end to end. None when
        # tracing is off or nothing is open (adopt degrades to a no-op).
        self._trace_ctx = TRACER.capture()
        #: trace id of this job's `job` span once it runs (None untraced)
        #: — the SLO exemplar and the /AnalysisResults correlation key
        self.trace_id: str | None = None
        self._submitted = _time.perf_counter()
        # ResultSink | None — attached by AnalysisManager.submit (the only
        # path, so every sink went through the path jail + in-use check)
        self.sink = None
        self.results: list[dict] = []
        # live jobs emit forever; an uncapped result list is the classic
        # serving slow leak (rtpulint RT011). Oldest rows roll off past
        # the cap — the sink (file) keeps the full history, the REST
        # surface reports how many rolled off. 0 disables. The trim
        # SHRINKS the list, so readers must take results_snapshot()
        # under the same lock (append-only was prefix-safe to iterate;
        # a shrink mid-serialization is not).
        self._results_cap = max(
            0, int(os.environ.get("RTPU_RESULT_ROWS", 10_000)))
        self._results_mu = threading.Lock()
        self.results_dropped = 0
        self.status = "pending"
        self.error: str | None = None
        #: degraded serving (resilience/degrade.py): a range sweep whose
        #: deadline or retry budget expired MID-sweep ships the hops it
        #: covered, status "done", with these three fields telling the
        #: client exactly how much of the range the answer covers
        self.degraded = False
        self.covered_time: int | None = None
        self.degraded_reason: str | None = None
        self._kill = threading.Event()
        self._thread: threading.Thread | None = None
        self._done = threading.Event()

    # ---- lifecycle ----

    def start(self) -> "Job":
        self._thread = threading.Thread(
            target=self._run, name=f"job-{self.id}", daemon=True)
        self.status = "running"
        self._thread.start()
        return self

    def kill(self) -> None:
        self._kill.set()

    def wait(self, timeout: float | None = None) -> bool:
        return self._done.wait(timeout)

    def results_snapshot(self) -> list[dict]:
        """Stable copy of the result rows for readers on other threads —
        the cap trim shrinks the live list, so serializing it directly
        would race the job thread."""
        with self._results_mu:
            return list(self.results)

    # ---- execution ----

    def _run(self) -> None:
        METRICS.jobs_started.labels(type(self.query).__name__).inc()
        # queue wait = submit → job thread actually running (today that is
        # thread-spawn latency; an admission-controlled scheduler will put
        # real queueing here, and the ledger field is where it shows up)
        self.ledger.queue_wait_seconds = max(
            0.0, _time.perf_counter() - self._submitted)
        job_ctx = None
        try:
            with TRACER.adopt(self._trace_ctx), \
                    TRACER.span("job", job_id=self.id,
                                kind=type(self.query).__name__,
                                program=type(self.program).__name__) as jsp, \
                    _ledger.activate(self.ledger):
                self.trace_id = jsp.trace or None
                self.ledger.trace_id = self.trace_id or ""
                job_ctx = TRACER.capture()
                self._run_query()
                jsp.set(status=self.status)
            # wall is submit → done, so it CONTAINS the queue wait and
            # finish()'s residual (wall - queue_wait - phases) is exactly
            # the unattributed run time — the queue_wait + Σphases ==
            # wall invariant holds even once real admission queueing
            # exists. Publishing runs after the ledger's wall is taken
            # and before the waiter wakes: it is in the client's latency
            # and outside the ledger, so the span (a child of `job`, in
            # the job's trace) is its only record.
            wall = _time.perf_counter() - self._submitted
            with TRACER.adopt(job_ctx), \
                    TRACER.span("job.publish", job_id=self.id,
                                status=self.status):
                self._publish_ledger(wall)
        finally:
            # _done fires LAST: a waiter woken by wait() must observe the
            # published SLO/exemplar/queue-wait/ledger state — publishing
            # after the wakeup raced every /slz-after-wait reader
            self._done.set()

    def _publish_ledger(self, wall_seconds: float) -> None:
        """Close the job's ledger and fan it out: per-algorithm
        ``raphtory_query_cost_*`` metrics, the /costz recent-query ring,
        and a ``ledger.query`` flight-recorder instant. With
        ``RTPU_LEDGER=0`` the ledger closes quietly (explain still shows
        the jobs-layer timings) but publishes NOTHING — disabling
        collection must silence every ledger surface, not just the
        engine-side hooks."""
        led = self.ledger
        led.finish(wall_seconds, status=self.status)
        # SLO surface (obs/slo.py): end-to-end latency + per-phase
        # seconds into the exemplar histograms, keyed by this job's
        # trace id so a p99 bucket resolves to an actual trace. Fed from
        # the JOBS-layer timings, which RTPU_LEDGER=0 still collects —
        # the SLO histograms have their own knob (RTPU_SLO), because the
        # serving SLO must survive turning cost accounting off. The
        # queue-wait distribution ships alongside (measured since PR 6,
        # never exported as a histogram until now).
        alg = led.algorithm or "unknown"
        if self.status == "done":
            # only SUCCESSFUL jobs land in the latency SLI: a burst of
            # fast failures would otherwise IMPROVE p99 while the service
            # errors, and a minutes-late kill would inflate the tail for
            # healthy traffic. Error/kill RATES live in
            # jobs_completed_total{status}; their latency is not an SLO.
            _slo.SLO.observe(alg, "e2e", led.wall_seconds,
                             trace_id=self.trace_id)
            for ph, sec in dict(led.phase_seconds).items():
                _slo.SLO.observe(alg, ph, sec, trace_id=self.trace_id)
        # queue wait is an ADMISSION signal, valid whatever the outcome
        METRICS.job_queue_wait_seconds.observe(led.queue_wait_seconds)
        # per-tenant workload account (obs/workload.py): its own knob
        # (RTPU_WORKLOAD), independent of RTPU_LEDGER — the jobs-layer
        # phase timings above are collected either way, and attribution
        # must survive turning the engine-side cost harvest off
        _workload.WORKLOAD.record(led, status=self.status)
        # advisor evidence ring (obs/advisor.py): jobs-layer data that,
        # like the SLO and workload surfaces above, must survive
        # RTPU_LEDGER=0 — otherwise every query-windowed rule silently
        # goes inert in a supported config. Gated on the advisor's own
        # knob so the bench off-arm pays nothing.
        if _advisor.enabled():
            _advisor.note_query(led.as_dict())
        # durable journal (obs/journal.py): every COMPLETED query's
        # ledger lands on disk — like the SLO/workload surfaces this
        # survives RTPU_LEDGER=0 (the jobs-layer timings are collected
        # either way), so a postmortem can always price the final sweep
        if _journal.enabled():
            snap_j = led.as_dict()
            snap_j["job_id"] = self.id
            snap_j["status"] = self.status
            _journal.emit("ledger", snap_j, trace_id=self.trace_id,
                          tenant=led.tenant or None)
        # serving-scheduler completion hook (jobs/scheduler.py): release
        # this job's admitted cost from the live backlog and fold its
        # measured seconds-per-view into the admission price book —
        # always, whatever the outcome (an admitted job that failed
        # still left the backlog)
        if self._sched is not None:
            self._sched.complete(self)
        if not _ledger.collection_enabled():
            return
        METRICS.query_cost_queries.labels(alg, led.bound()).inc()
        METRICS.query_cost_seconds.labels(alg, "queue_wait").observe(
            led.queue_wait_seconds)
        snap = led.as_dict()
        for ph, sec in snap["phase_seconds"].items():
            METRICS.query_cost_seconds.labels(alg, ph).observe(sec)
        METRICS.query_cost_est_flops.labels(alg).inc(
            snap["device"]["est_flops"])
        METRICS.query_cost_est_hbm_bytes.labels(alg).inc(
            snap["device"]["est_bytes_accessed"])
        METRICS.query_cost_h2d_bytes.labels(alg).inc(snap["h2d"]["bytes"])
        if snap["dcn"]["bytes"]:
            METRICS.query_cost_dcn_bytes.labels(alg).inc(
                snap["dcn"]["bytes"])
        _ledger.note_completed(led)

    def _run_query(self) -> None:
        try:
            q = self.query
            if self.deadline is not None \
                    and _time.monotonic() > self.deadline:
                # fail fast BEFORE any dispatch: the client has already
                # given up on this answer (jobs/scheduler.py deadlines)
                self.status = "expired"
                self.error = (f"DeadlineExpired: deadline_ms="
                              f"{self.deadline_ms:g} passed before the "
                              "job dispatched")
                if self._coalesce is None:
                    # a queued job's expiry is counted ONCE, by the
                    # scheduler at batch formation — counting here too
                    # would report one expired request as two
                    from . import scheduler as _sched

                    _sched.note_deadline_expired(self)
                return
            if self._coalesce is not None and self._run_coalesced(q):
                return   # status set by the coalesced path
            self._coalesce = None   # declined/timed out: own path
            if self.program.columnar_only:
                self._run_columnar_only(q)
            elif isinstance(q, ViewQuery):
                self._run_at(q.timestamp, q)
            elif isinstance(q, RangeQuery):
                # When the whole range is already safe, sweep incrementally
                # (delta-applied snapshots, core/sweep.py) instead of
                # re-folding the log per hop; otherwise hop-by-hop behind the
                # watermark fence like the reference (RangeAnalysisTask).
                # Qualifying programs take the amortised engines: on a mesh
                # the static global-space partition (parallel/sweep.py), on
                # one device the device-resident sweep (engine/device_sweep)
                # — fold state stays on the chip, hops ship O(delta) bytes.
                if not (self._try_range_mesh_columns(q)
                        or self._try_range_mesh(q)
                        or self._try_range_hopbatch(q)
                        or self._try_range_device(q)):
                    sweep = None
                    if self.graph.safe_time() >= q.end:
                        from ..core.sweep import SweepBuilder

                        with _ledger.engine_build(
                                "request", self.graph.log,
                                self.ledger) as sp:
                            sweep = SweepBuilder(
                                self.graph.log, include_occurrences=self
                                .program.needs_occurrences)
                            sp.set(engine="SweepBuilder")
                    t = q.start
                    while t <= q.end and not self._kill.is_set():
                        self._run_at(t, q, sweep=sweep)
                        t += q.jump
            elif isinstance(q, LiveQuery):
                self._run_live(q)
            self.status = "done" if not self._kill.is_set() else "killed"
        except Exception as e:  # job errors surface via status, like the
            self.status = "failed"  # reference's per-phase catches
            self.error = f"{type(e).__name__}: {e}\n{traceback.format_exc()}"
        finally:
            if self.sink is not None:
                self.sink.close()   # flush partial output on kill/failure too
            METRICS.jobs_completed.labels(self.status).inc()
            # _done is set by _run AFTER _publish_ledger — wait()
            # returning guarantees the telemetry has landed

    def _run_live(self, q: LiveQuery) -> None:
        """The live loop is a thin pacer over the epoch engine
        (jobs/live.LiveEpochState): each iteration computes the target
        timestamp exactly as before, then lets the epoch engine decide
        HOW to serve it — incremental delta fold over the standing
        columnar engine, full re-sweep fallback, or (wall mode, nothing
        moved) a skip. Emission, freshness and pricing all happen
        inside ``epoch()``; the wall-mode wait adapts to the staleness
        budget (``next_wait``)."""
        from .live import LiveEpochState

        live = LiveEpochState(self)
        runs = 0
        t_target = None
        while not self._kill.is_set():
            if q.event_time:
                if t_target is None:
                    t_target = min(self.graph.safe_time(),
                                   self.graph.latest_time)
                else:
                    # advance in event time and wait for the watermark to
                    # catch up (never clamped back: LiveAnalysisTask.scala:
                    # 34-52 event-time mode); sub-1 repeats still advance
                    t_target += max(1, int(q.repeat))
                # condition-variable fence wait (chunked so kill() still
                # interrupts promptly even with no watermark traffic)
                deadline = _time.monotonic() + self.wait_timeout
                while (not self._kill.is_set()
                       and _time.monotonic() < deadline
                       and not self.graph.watermarks.wait_for(
                           t_target,
                           timeout=min(0.5, max(
                               0.0, deadline - _time.monotonic())))):
                    pass
                t = t_target
            else:
                t = min(self.graph.safe_time(), self.graph.latest_time)
            live.epoch(q, int(t))
            runs += 1
            if q.max_runs is not None and runs >= q.max_runs:
                break
            if q.event_time:
                # all sources finished and the target has passed the end of
                # history: nothing new can ever arrive — finish rather than
                # busy-spin past the end of the stream (unless the caller
                # asked for an exact number of runs)
                if (q.max_runs is None
                        and self.graph.watermarks.safe_time() >= 2**62
                        and t_target >= self.graph.latest_time):
                    break
            else:
                self._kill.wait(live.next_wait(q))

    def _run_coalesced(self, q) -> bool:
        """Wait on this job's scheduler collect-window handle and, when
        the batch dispatched, demux + emit THIS job's columns on THIS
        thread (result/ledger ownership never crosses threads). Returns
        False when the scheduler declined (solo window, incompatible
        pack, failed dispatch) — the caller falls through to the normal
        per-job routes, so coalescing can only ever ADD latency equal to
        the collect window, never lose a request."""
        pend = self._coalesce
        limit = max(float(self.wait_timeout), 600.0)
        w0 = _time.monotonic()
        while not pend.done.wait(0.05):
            if self._kill.is_set():
                self.status = "killed"
                return True
            if _time.monotonic() - w0 > limit:
                _jobs_log.warning(
                    "coalesced wait timed out for %s after %.0fs — "
                    "falling back to the solo path", self.id, limit)
                self.ledger.add_phase("sched_wait", _time.monotonic() - w0)
                return False
        if pend.outcome == "declined":
            # a solo window still cost this thread the collect window:
            # the same phase a dispatched batch's queueing lands in
            self.ledger.add_phase("sched_wait", _time.monotonic() - w0)
            return False
        if pend.outcome == "killed":
            self.status = "killed"
            return True
        if pend.outcome == "expired":
            self.status = "expired"
            self.error = (f"DeadlineExpired: deadline_ms="
                          f"{self.deadline_ms:g} expired in the "
                          "scheduler queue (never dispatched)")
            return True
        pay = pend.payload
        # collect-window queueing the scheduler ADDED, measured from
        # THIS THREAD's wait start (w0) — not pend.enqueued, which
        # predates the thread and overlaps queue_wait_seconds; the
        # dispatch itself is attributed by column share via
        # absorb_share, so queue_wait + sched_wait + phases never
        # double-count an interval
        self.ledger.add_phase("sched_wait", max(
            0.0, pay["dispatch_started"] - w0))
        self.ledger.absorb_share(pay["snap"], pay["share"],
                                 coalesced=pay["batch"])
        self._emit_coalesced(pend.grid, pay)
        self.status = "done" if not self._kill.is_set() else "killed"
        return True

    def _emit_coalesced(self, grid, pay) -> None:
        """Emit this job's result rows from a shared batch dispatch:
        ``grid`` is the SAME (hops, windows) tuple the scheduler packed
        this job's columns from (``pend.grid`` — never re-derived, so
        the demux can't drift from the packing), in serial emission
        order. ``viewTime`` is the amortised per-column share of the
        batch dispatch — the same rule ``_emit_columnar`` applies
        within one job's sweep, extended across requests."""
        hops, windows = grid
        ranks, steps = pay["ranks"], int(pay["steps"])
        shells, cols = pay["shells"], pay["cols"]
        per_row = pay["elapsed"] / max(pay["total_cols"], 1)
        for _ in hops:
            METRICS.snapshot_build_seconds.observe(
                pay["fold_seconds"] * pay["share"] / max(len(hops), 1))
        self.ledger.count_supersteps(steps)
        i = 0
        with TRACER.span("job.emit", rows=len(cols)):
            for T in sorted({int(t) for t in hops}):
                for w in windows:
                    if self._kill.is_set():
                        return
                    self._emit(T, w, ranks[cols[i]], shells[int(T)], steps,
                               _time.perf_counter() - per_row)
                    i += 1

    def _device_engine_ok(self) -> bool:
        """Shared eligibility gate for the device-resident engines (warm
        View, single-device Range, mesh Range): the program must run
        without occurrences/property joins (``device_sweep.supported``)
        and its reduce must accept the vertex-side shell view."""
        from ..engine.device_sweep import supported

        if not supported(self.program):
            return False
        return (type(self.program).reduce is VertexProgram.reduce
                or self.program.reduce_shell_safe)

    def _try_range_mesh(self, q: RangeQuery) -> bool:
        """Amortised mesh range sweep: one static partition for the whole
        range, per-hop O(delta) updates, hop i+1's host fold overlapped with
        hop i's device supersteps (``sharded.run(block=False)``). Returns
        False when the query/program must use the per-hop path."""
        if self.mesh is None or self.graph.safe_time() < q.end:
            return False
        from ..parallel import sharded as _sh
        from ..parallel.sweep import ShardedSweep

        if not self._device_engine_ok():
            return False
        try:
            with _ledger.engine_build("request", self.graph.log,
                                      self.ledger) as sp:
                sweep = ShardedSweep(self.graph.log,
                                     self.mesh.shape[_sh.V_AXIS])
                sp.set(**_ledger.built(sweep))
        except ValueError:
            return False  # e.g. shard count does not divide the global pad

        def run(windows):
            return sweep.run(self.program, mesh=self.mesh, window=q.window,
                             windows=windows, block=False)

        rows_a_round = sweep.mode_rows(
            self.program, self.mesh,
            len(q.windows) if q.windows is not None else 1)

        def wait(steps) -> int:
            # every hop is dispatched with block=False, so the route's
            # wait for the chips is here, under the name sharded.run
            # gives its own; with the superstep count it reads, the rows
            # the hop's rounds handed to the mode's sort (0 for a program
            # whose exchange is no histogram)
            with TRACER.span("comm.block_wait",
                             process=TRACER.process_index) as sp:
                steps = int(steps)
                sp.set(steps=steps)
            self.ledger.count_mode_rows(max(steps, 0) * rows_a_round)
            return steps

        self._range_amortised(q, sweep.advance, run, sweep.reduce_view,
                              wait)
        return True

    def _run_columnar_only(self, q) -> None:
        """A program the columnar engine alone serves (``LCC``: it is no
        message along an edge; ``SGC``: its state is a row of features a
        vertex, and ``bsp`` would gather one such row a pair). A Range is
        ``_try_range_hopbatch``'s; a View is the same engine at one hop,
        one column a window. What that route does not take — a mesh, a
        time past the watermark's fence, a Live subscription — fails the
        job with the route's name instead of falling to ``bsp``, which
        cannot run the program, or not at a deployment's size."""
        name = type(self.program).__name__
        if isinstance(q, ViewQuery):
            q = RangeQuery(int(q.timestamp), int(q.timestamp), 1,
                           window=q.window, windows=q.windows)
        if not isinstance(q, RangeQuery):
            raise NotImplementedError(
                f"{name} is served by the columnar Range route "
                f"(hopbatch.delta.{name.lower()}) as a Range or a View; "
                "a Live subscription of it is not")
        if not self._try_range_hopbatch(q):
            raise NotImplementedError(
                f"{name} is served by the columnar Range route "
                f"(hopbatch.delta.{name.lower()}) alone: one chip, a "
                "range behind the watermark's fence, at most 1024 views")

    def _columnar_builder(self):
        """Construct the hop-batched columnar engine for this job's
        program (raises for programs without one — the caller treats any
        failure as \'route declined\'). Seven kinds: PageRank
        (``pagerank``: finalize is the raw rank vector and the power
        iteration warm-starts safely); ConnectedComponents (``cc``: labels
        are global padded indices in both engines); BFS and SSSP (``bfs``,
        and ``bfs`` with a weight state: the columnar distances are
        exactly finalize's output; weighted traversal folds per-hop weight
        columns, immutable weight keys raise); CDLP (``cdlp``: labels are
        global padded indices in both engines and the rounds are fixed,
        so the columns are ``bsp``'s answer); LCC (``lcc``: ``tri`` and
        ``deg`` per vertex, which no other engine computes); SGC
        (``sgc``: per column the small pytree ``SGC.finalize`` returns
        on ``bsp``, never the ``[n_pad, dim]`` block)."""
        from ..algorithms import CDLP as _CDLP
        from ..algorithms import SGC as _SGC
        from ..algorithms import ConnectedComponents as _CC
        from ..algorithms import LCC as _LCC
        from ..algorithms import PageRank as _PR
        from ..algorithms.traversal import SSSP as _SSSP
        from ..engine.hopbatch import (HopBatchedBFS, HopBatchedCC,
                                       HopBatchedCDLP, HopBatchedLCC,
                                       HopBatchedPageRank, HopBatchedSGC,
                                       HopBatchedSSSP)

        p = self.program
        if type(p) is _PR:
            return HopBatchedPageRank(self.graph.log, damping=p.damping,
                                      tol=p.tol, max_steps=p.max_steps)
        if type(p) is _CC:
            return HopBatchedCC(self.graph.log, max_steps=p.max_steps)
        if type(p) is _CDLP:
            return HopBatchedCDLP(self.graph.log, max_steps=p.max_steps)
        if type(p) is _LCC:
            return HopBatchedLCC(self.graph.log)
        if type(p) is _SGC:
            return HopBatchedSGC(self.graph.log, rounds=p.rounds,
                                 dim=p.dim, feature_seed=p.feature_seed)
        if type(p) is _SSSP:
            if p.weight_prop:
                return HopBatchedSSSP(self.graph.log, p.seeds,
                                      p.weight_prop, directed=p.directed,
                                      max_steps=p.max_steps)
            return HopBatchedBFS(self.graph.log, p.seeds,
                                 directed=p.directed,
                                 max_steps=p.max_steps)
        raise TypeError(f"no columnar engine for {type(p).__name__}")

    def _columnar_range_prep(self, q: RangeQuery):
        """Shared eligibility + construction for the columnar range routes
        (single-device hopbatch and column-sharded mesh). Returns
        ``(hops, windows, hb)`` or None; enumerable construction failures
        (TypeError: no columnar engine for the program; ValueError:
        immutable weight key / >2^31 vertex packing; MemoryError) decline
        the route rather than failing the job."""
        hops = list(range(int(q.start), int(q.end) + 1, int(q.jump)))
        windows = list(q.windows) if q.windows is not None else [q.window]
        if not hops or len(hops) * len(windows) > 1024:
            return None   # the cheap guard — before paying for tables
        with _ledger.engine_build("request", self.graph.log,
                                  self.ledger) as sp:
            try:
                hb = self._columnar_builder()
            except (TypeError, ValueError, MemoryError) as e:
                _jobs_log.info("columnar range route declined: %s: %s",
                               type(e).__name__, e)
                sp.set(declined=type(e).__name__)
                return None
            sp.set(**_ledger.built(hb))
            # memory guards, sized by the ENGINE's own accounting (the
            # fold strategy — delta vs host columns — changes what the
            # host materialises). Oversized ranges stay on the
            # O(1)-memory-per-hop paths (which rebuild their own tables;
            # a rejected range pays the table build twice, acceptable
            # next to the sweep it avoids misrouting).
            if hb.device_mask_bytes(len(hops) * len(windows)) > 1 << 32 \
                    or hb.host_column_bytes(len(hops)) > 1 << 29:
                sp.set(declined="memory_guard")
                return None
        return hops, windows, hb

    def _try_range_hopbatch(self, q: RangeQuery) -> bool:
        """Whole-range columnar dispatch for qualifying Range queries:
        every (hop, window) view of the range is a COLUMN of one compiled
        program (``engine/hopbatch``), in as few dispatches as
        ``_range_chunks`` finds worth their pass over the table —
        against the reference's full per-hop actor handshake
        (``RangeAnalysisTask.scala:18-35``). Routes: PageRank (finalize is
        the raw rank vector; the power iteration warm-starts safely),
        ConnectedComponents (labels are global padded indices in both
        engines; no warm start — min-propagation is not a contraction on a
        changing edge set), SSSP/BFS (unit or mutable-numeric-weighted;
        no warm start), CDLP (a fixed number of rounds of a histogram
        combine; no warm start), LCC (one pass over the log's triangle
        table; nothing to warm-start), and SGC (a fixed number of rounds
        over F-wide rows, the columns walked; per column a small pytree,
        not an array)."""
        import jax
        import numpy as np

        if self.mesh is not None or self.graph.safe_time() < q.end:
            return False
        prep = self._columnar_range_prep(q)
        if prep is None:
            return False
        hops, windows, hb = prep
        if self._kill.is_set():
            return True

        # keyed by hop time, not call order: with parallel chunk folds
        # (and fold-cache replays) the callback may fire from worker
        # threads, interleaved across chunk groups
        shells = {}

        def grab_shell(T, sw):
            shells[int(T)] = _shell_from_fold(hb.tables, sw, int(T))

        chunks, rule = _range_chunks(hb, len(hops), len(windows))
        t0 = _time.perf_counter()
        try:
            ranks, steps = hb.run(hops, windows, chunks=chunks,
                                  warm_start=chunks > 1
                                  and hb.supports_warm_start,
                                  hop_callback=grab_shell, chunk_rule=rule)
            b0 = _time.perf_counter()
            ranks, steps = _block_steps(lambda: (
                jax.tree_util.tree_map(np.asarray, ranks), steps))
            self.ledger.add_phase("device_wait",
                                  _time.perf_counter() - b0)
            hb.count_result(ranks)
        except Exception as e:
            # a transport failure or OOM mid-dispatch falls back to the
            # O(1)-memory-per-hop device-resident route (which rebuilds
            # its own state) instead of failing the job
            if not declinable(e):
                raise
            _jobs_log.warning("columnar range route failed (%s: %s) — "
                              "falling back to the per-hop path",
                              type(e).__name__, e)
            return False
        self._emit_columnar(hops, windows, ranks, shells,
                            int(steps), _time.perf_counter() - t0,
                            hb.fold_seconds)
        return True

    def _emit_columnar(self, hops, windows, ranks, shells, steps, elapsed,
                       fold_seconds) -> None:
        """Emit one result row per (hop, window) column of a whole-range
        dispatch: viewTime is the AMORTISED share of the dispatch (plus
        that row's own reduce), snapshot-build is the per-hop share of the
        measured incremental fold. ``shells`` is keyed by hop time (the
        fold callback may fire out of hop order under parallel folds).
        ``ranks``: an array whose leading axis is the columns, or a
        pytree of such (``sgc``)."""
        import jax

        W = len(windows)
        per_row = elapsed / max(len(hops) * W, 1)
        for _ in hops:
            METRICS.snapshot_build_seconds.observe(
                fold_seconds / max(len(hops), 1))
        METRICS.supersteps.inc(max(steps, 0))
        self.ledger.count_supersteps(steps)
        with TRACER.span("job.emit", rows=len(hops) * W):
            for j, T in enumerate(hops):
                if self._kill.is_set():
                    return
                for i, w in enumerate(windows):
                    col = jax.tree_util.tree_map(
                        lambda a: a[j * W + i], ranks)
                    self._emit(T, w, col, shells[int(T)],
                               steps, _time.perf_counter() - per_row)

    def _try_range_mesh_columns(self, q: RangeQuery) -> bool:
        """View-axis mesh parallelism for qualifying Range queries: the
        (hop, window) columns spread COLLECTIVE-FREE over every device of
        the mesh (``parallel/columns.py``) — the graph tables replicate,
        so this route takes ranges whose graph fits one chip; bigger
        graphs fall through to the vertex-sharded ``_try_range_mesh``.
        The full host columns come from the engine's own fold
        (``fold_payloads``): forked units seeded from the fold cache's
        checkpoints on the fold pool, as the one-chip route folds, while
        this thread waits; an engine whose fold is sequential (weighted
        SSSP) or ``RTPU_FOLD_WORKERS=1`` folds them here, inline."""
        import numpy as np

        from ..engine.hopbatch import (HopBatchedCC, HopBatchedPageRank,
                                       HopBatchedSSSP)
        from ..parallel.columns import run_columns_sharded

        if self.mesh is None or self.graph.safe_time() < q.end:
            return False
        if self.program.combiner == "custom":
            # the column-sharded runner has the elementwise kinds only; a
            # histogram combine (CDLP) takes the vertex-sharded route
            return False
        prep = self._columnar_range_prep(q)
        if prep is None:
            return False
        hops, windows, hb = prep
        if self._kill.is_set():
            return True

        if isinstance(hb, HopBatchedPageRank):
            kw = dict(kind="pagerank", damping=hb.damping, tol=hb.tol,
                      max_steps=hb.max_steps)
        elif isinstance(hb, HopBatchedCC):
            kw = dict(kind="cc", max_steps=hb.max_steps)
        else:
            kw = dict(kind="bfs", seeds=hb.seeds, directed=hb.directed,
                      max_steps=hb.max_steps)

        shells = {}

        def grab_shell(T, sw):
            shells[int(T)] = _shell_from_fold(hb.tables, sw, int(T))

        t0 = _time.perf_counter()
        _, (cols,) = hb.fold_payloads(hops, delta=False,
                                      hop_callback=grab_shell)
        # the phase is what THIS thread waited for the units or folded
        # inline; ``hb.fold_seconds`` is the units' worker seconds
        self.ledger.add_phase(
            "fold", hb.fold_stall_seconds + hb.fold_inline_seconds)
        if isinstance(hb, HopBatchedSSSP):
            *cols, kw["weight_cols"] = cols
        try:
            ranks, steps = run_columns_sharded(
                hb.tables, *cols, hops, windows,
                self.mesh.devices.ravel(), **kw)
            b0 = _time.perf_counter()
            ranks, steps = _block_steps(
                lambda: (np.asarray(ranks), steps))
            self.ledger.add_phase("device_wait",
                                  _time.perf_counter() - b0)
        except Exception as e:
            # replicating the tables can exhaust one chip's HBM on graphs
            # the host-side guard admits — fall through to the
            # vertex-sharded route instead of failing the job
            if not declinable(e):
                raise
            _jobs_log.warning("column-sharded mesh route failed (%s: %s) — "
                              "falling back to vertex sharding",
                              type(e).__name__, e)
            return False
        self._emit_columnar(hops, windows, ranks, shells,
                            int(steps), _time.perf_counter() - t0,
                            hb.fold_seconds)
        return True

    def _try_range_device(self, q: RangeQuery) -> bool:
        """Single-device amortised range sweep: device-resident fold state,
        O(delta) per-hop uploads, pipelined emit (engine/device_sweep)."""
        if self.mesh is not None or self.graph.safe_time() < q.end:
            return False
        from ..engine.device_sweep import DeviceSweep

        if not self._device_engine_ok():
            return False
        try:
            with _ledger.engine_build("request", self.graph.log,
                                      self.ledger) as sp:
                sweep = DeviceSweep(self.graph.log)
                sp.set(**_ledger.built(sweep))
        except ValueError:
            return False  # >2^31 distinct vertices: packed keys exhausted
        shell = _DeviceShell(sweep)

        def run(windows):
            return sweep.run(self.program, window=q.window, windows=windows)

        self._range_amortised(q, sweep.advance, run, shell.freeze)
        return True

    def _range_amortised(self, q: RangeQuery, advance, run, freeze_rv,
                         wait=_wait_steps) -> None:
        """The shared amortised-sweep hop loop: advance the fold, dispatch
        async, emit the PREVIOUS hop while this one computes (hop i+1's host
        fold overlaps hop i's device supersteps). ``wait(steps) -> int`` is
        a hop's wait for the devices: it reads the superstep count the
        dispatch returned (``_wait_steps``: under ``superstep.block``).

        Degraded serving (docs/RESILIENCE.md): a deadline that expires or
        a transient failure that exhausts its retry budget MID-sweep stops
        the loop but ships every hop already covered — the job finishes
        "done" with ``degraded: true`` and ``covered_time`` instead of
        discarding paid-for work. Pre-dispatch expiry (nothing covered)
        still fails fast in ``_run_query``, and non-transient errors still
        fail the job: a wrong answer is not a degraded answer."""
        pending = None
        covered = None
        reason = None
        t = q.start
        while t <= q.end and not self._kill.is_set():
            if (self.deadline is not None
                    and _time.monotonic() > self.deadline
                    and (pending is not None or covered is not None)):
                reason = "deadline"
                break
            t0 = _time.perf_counter()
            s0 = _time.perf_counter()
            try:
                advance(int(t))
                METRICS.snapshot_build_seconds.observe(
                    _time.perf_counter() - s0)
                self.ledger.add_phase("fold", _time.perf_counter() - s0)
                windows = list(q.windows) if q.windows is not None else None
                c0 = _time.perf_counter()
                result, steps = run(windows)
                rv = freeze_rv()
                self.ledger.add_phase("compute", _time.perf_counter() - c0)
            except Exception as e:
                if (_transient(e)
                        and (pending is not None or covered is not None)):
                    reason = "retry_budget"
                    break
                raise
            t_disp = _time.perf_counter()
            if pending is not None:
                self._emit_mesh(*pending, wait)
                covered = pending[0]
            pending = (t, q, rv, result, steps, t0, t_disp)
            t += q.jump
        if pending is not None:
            try:
                self._emit_mesh(*pending, wait)
                covered = pending[0]
            except Exception as e:
                # the tail hop's buffers may be poisoned by the same
                # transient failure that stopped the loop — a degraded
                # answer keeps the PRIOR covered hops rather than dying
                # on the flush; a healthy run still propagates
                if reason is None or not _transient(e):
                    raise
        if reason is not None:
            self._mark_degraded(reason, covered)

    def _mark_degraded(self, reason: str, covered) -> None:
        """Record a partial answer: job-side fields the REST payload
        surfaces, plus the process-wide ledger /healthz and /faultz grade
        from. Never fails the job it is marking."""
        self.degraded = True
        self.covered_time = None if covered is None else int(covered)
        self.degraded_reason = reason
        try:
            _degrade.DEGRADED.note(self.id, reason,
                                   covered_time=self.covered_time)
        except Exception:   # telemetry must not fail a served answer
            pass

    def _emit_mesh(self, t, q, rv, result, steps, t0, t_disp, wait) -> None:
        import jax
        import numpy as np

        # viewTime must mean "this hop's fold+dispatch + its device wait +
        # reduce" — not the NEXT hop's host work that ran in the overlap gap.
        # Shift t0 forward by the time spent between this hop's dispatch and
        # now (the pipelined hop's fold) so _emit's end-to-end clock reads
        # dispatch-window + blocking tail only.
        t0 = t0 + (_time.perf_counter() - t_disp)
        b0 = _time.perf_counter()
        steps = wait(steps)
        self.ledger.add_phase("device_wait", _time.perf_counter() - b0)
        METRICS.supersteps.inc(max(steps, 0))
        self.ledger.count_supersteps(steps)
        with TRACER.span("job.emit",
                         rows=len(q.windows) if q.windows is not None else 1):
            if q.windows is not None:
                for i, w in enumerate(q.windows):
                    r_i = jax.tree_util.tree_map(
                        lambda a: np.asarray(a[i]), result)
                    self._emit(t, w, r_i, rv, steps, t0)
            else:
                result = jax.tree_util.tree_map(np.asarray, result)
                self._emit(t, q.window, result, rv, steps, t0)

    def _try_view_resident(self, t: int, q) -> bool:
        """Warm View/Live dispatch through the graph's shared resident
        DeviceSweep: delta-advance + one compiled dispatch instead of a
        full host fold + O(m) upload per request (the cold ``view_at``
        path; ref builds a fresh lens per job, ReaderWorker.scala:293-352).
        Returns False when the query/program must use the cold path."""
        import jax
        import numpy as np

        p = self.program
        if self.mesh is not None or self.graph.safe_time() < int(t):
            return False   # the cold path owns the fence wait
        if not self._device_engine_ok():
            return False
        try:
            acq = self.graph.resident_acquire(int(t))
        except Exception as e:
            # a transport failure or OOM while uploading the one-time
            # tables: the cold path must still serve
            if not declinable(e):
                raise
            _jobs_log.warning("resident sweep build failed (%s: %s) — "
                              "falling back to the cold path",
                              type(e).__name__, e)
            return False
        if acq is None:
            return False
        sweep, lock = acq
        t0 = _time.perf_counter()
        try:
            s0 = _time.perf_counter()
            sweep.advance(int(t))
            METRICS.snapshot_build_seconds.observe(_time.perf_counter() - s0)
            self.ledger.add_phase("fold", _time.perf_counter() - s0)
            windows = list(q.windows) if q.windows is not None else None
            c0 = _time.perf_counter()
            result, steps = sweep.run(p, window=q.window, windows=windows)
            rv = _DeviceShell(sweep).freeze()
            b0 = _time.perf_counter()
            self.ledger.add_phase("compute", b0 - c0)
            result, steps = _block_steps(lambda: (
                jax.tree_util.tree_map(np.asarray, result), steps))
            self.ledger.add_phase("device_wait",
                                  _time.perf_counter() - b0)
        except Exception as e:
            # device trouble mid-dispatch: a partially applied delta (or a
            # failed donated-buffer call) can leave the device state
            # inconsistent with the host fold — drop the sweep while the
            # lock is still held, then decline to the cold path
            self.graph.resident_discard()
            if not declinable(e):
                raise
            _jobs_log.warning("resident view route failed (%s: %s) — "
                              "falling back to the cold path",
                              type(e).__name__, e)
            return False
        finally:
            lock.release()
        METRICS.supersteps.inc(max(steps, 0))
        self.ledger.count_supersteps(steps)
        with TRACER.span("job.emit",
                         rows=len(windows) if windows is not None else 1):
            if windows is not None:
                for i, w in enumerate(windows):
                    r_i = jax.tree_util.tree_map(lambda a: a[i], result)
                    self._emit(t, w, r_i, rv, steps, t0)
            else:
                self._emit(t, q.window, result, rv, steps, t0)
        return True

    def _run_at(self, t: int, q, exact: bool = True, sweep=None) -> None:
        if sweep is None and self._try_view_resident(t, q):
            return
        t0 = _time.perf_counter()
        if sweep is not None:
            s0 = _time.perf_counter()
            view = sweep.view_at(int(t))
            METRICS.snapshot_build_seconds.observe(_time.perf_counter() - s0)
            self.graph.cache_put(
                int(t), view, self.program.needs_occurrences,
                version=sweep.log.version)
        else:
            s0 = _time.perf_counter()
            view = self.graph.view_at(
                int(t), exact=exact, wait_timeout=self.wait_timeout,
                include_occurrences=self.program.needs_occurrences)
        self.ledger.add_phase("fold", _time.perf_counter() - s0)
        windows = q.windows
        c0 = _time.perf_counter()
        if windows is not None:
            result, steps = self._execute(view, windows=list(windows))
            steps = int(steps)   # device barrier for the superstep count
            self.ledger.add_phase("compute", _time.perf_counter() - c0)
            METRICS.supersteps.inc(max(steps, 0))  # once per device run
            self.ledger.count_supersteps(steps)
            with TRACER.span("job.emit", rows=len(windows)):
                for i, w in enumerate(windows):
                    import jax

                    r_i = jax.tree_util.tree_map(lambda a: a[i], result)
                    self._emit(t, w, r_i, view, steps, t0)
        else:
            result, steps = self._execute(view, window=q.window)
            steps = int(steps)
            self.ledger.add_phase("compute", _time.perf_counter() - c0)
            METRICS.supersteps.inc(max(steps, 0))
            self.ledger.count_supersteps(steps)
            with TRACER.span("job.emit", rows=1):
                self._emit(t, q.window, result, view, steps, t0)

    def _execute(self, view, window=None, windows=None):
        if self.mesh is not None:
            from ..parallel import sharded

            return sharded.run(self.program, view, self.mesh,
                               window=window, windows=windows)
        return bsp.run(self.program, view, window=window, windows=windows)

    def _emit(self, t, window, result, view, steps, t0) -> None:
        e0 = _time.perf_counter()
        reduced = self.program.reduce(result, view, window=window)
        # counted only after the host reduce: viewTime is END-TO-END (device
        # compute + reduce), and a failed reduce is not a computed view
        METRICS.views_computed.inc()
        METRICS.view_seconds.observe(_time.perf_counter() - t0)
        self.ledger.add_phase("emit", _time.perf_counter() - e0)
        self.ledger.count_views()
        row = {
            "time": int(t),
            "windowsize": int(window) if window is not None else None,
            "viewTime": round((_time.perf_counter() - t0) * 1000.0, 3),
            "steps": int(steps),
            "result": reduced,
        }
        with self._results_mu:
            self.results.append(row)
            if self._results_cap and len(self.results) > self._results_cap:
                drop = len(self.results) - self._results_cap
                del self.results[:drop]
                self.results_dropped += drop
        if self.sink is not None:
            self.sink.write(row)


def _range_chunks(hb, n_hops: int, n_windows: int) -> tuple[int, str]:
    """How many dispatches a columnar Range of ``n_hops`` x ``n_windows``
    views is, and why: ``(chunks, chunk_rule)``. Chunks hide the host
    fold behind the device and warm-start the next chunk's iteration;
    each is another pass over the pair table and pays the dispatch's
    fixed work again. So:

    - ``warm_start``: the engine's warm-started chunks can halt in fewer
      supersteps (PageRank with ``tol > 0``) — the ladder, equal chunks
      of at least two hops, most first;
    - ``one_dispatch``: they cannot, and all the columns in one dispatch
      stay on the engine's fast path (``dispatch_columns_ok``);
    - ``fit``: the fewest chunks (of 2, 3, 4, dividing the hops) whose
      columns do;
    - ``ladder``: none does."""
    ladder = next((k for k in (4, 3, 2)
                   if n_hops >= 2 * k and n_hops % k == 0), 1)
    if hb.warm_start_saves_steps:
        return ladder, "warm_start"
    C = n_hops * n_windows
    for k in (1, 2, 3, 4):
        if n_hops % k == 0 and hb.dispatch_columns_ok(C // k):
            return k, "one_dispatch" if k == 1 else "fit"
    return ladder, "ladder"


def _shell_from_fold(tables, sw, T):
    """Reducer-facing vertex shell from a SweepBuilder's fold state at T
    (vertex-side fields only — gated by ``reduce_shell_safe``)."""
    import numpy as np

    from ..core.snapshot import INT64_MIN
    from ..parallel.sweep import _Shell

    n, n_pad = tables.n, tables.n_pad
    vids = tables.vids
    if vids is None:   # DeviceSweep frees the host copy after upload
        vids = getattr(tables, "_shell_vids", None)
        if vids is None:   # rebuild once per sweep, not once per hop
            vids = np.full(n_pad, -1, np.int64)
            vids[:n] = tables.uv
            tables._shell_vids = vids
    vm = np.zeros(n_pad, bool)
    vm[:n] = sw.v_alive
    vl = np.full(n_pad, INT64_MIN, np.int64)
    vl[:n] = sw.v_lat
    vf = np.full(n_pad, INT64_MIN, np.int64)
    vf[:n] = sw.v_first
    return _Shell(time=int(T), n_pad=n_pad, vids=vids, v_mask=vm,
                  v_latest_time=vl, v_first_time=vf)


class _DeviceShell:
    """Reducer-facing view shells over a DeviceSweep's HOST fold state
    (the device buffers' numpy twin lives in the SweepBuilder)."""

    def __init__(self, sweep):
        self.sweep = sweep

    def freeze(self):
        ds = self.sweep
        return _shell_from_fold(ds.tables, ds.sw, ds.t_now)


class AnalysisManager:
    """Job registry + submission surface (``AnalysisManager.scala:49-70``
    job tracking for RequestResults/KillTask)."""

    def __init__(self, graph: TemporalGraph, mesh=None, sink_dir: str = "",
                 sink_format: str = "jsonl"):
        from .scheduler import ServingScheduler

        self.graph = graph
        self.mesh = mesh
        self.sink_dir = sink_dir       # "" disables file sinks (ref: unset
        self.sink_format = sink_format  # env path in Utils.scala:107-126)
        #: serving scheduler (jobs/scheduler.py): cross-request
        #: coalescing collect windows + ledger-priced admission control
        #: + deadlines. Always constructed — RTPU_BATCH_WINDOW_MS=0 and
        #: RTPU_ADMISSION=0 make every path identical to pre-scheduler.
        self.scheduler = ServingScheduler(graph)
        self._jobs: dict[str, Job] = {}
        self._counter = itertools.count()
        self._lock = threading.Lock()
        # finished jobs are retained for /AnalysisResults but evicted
        # oldest-first past the cap — an always-up job server must not
        # grow its job table with every request served. 0 disables.
        self._table_cap = max(
            0, int(os.environ.get("RTPU_JOB_TABLE_CAP", 4096)))
        # lockset-sanitizer registration (None unless RTPU_SANITIZE): job
        # table accesses report their held lockset; an unguarded access
        # path surfaces as a shared-state-race finding in tier-1
        self._san_tracker = _san_track("job_table")

    def _note_table(self, write: bool = False) -> None:
        _san_note(self._san_tracker, write)

    def _evict_done_locked(self) -> None:
        """Drop oldest FINISHED jobs past the table cap (caller holds
        ``_lock``). Running jobs are never evicted — the cap bounds
        retention, not concurrency (admission control is ROADMAP #1)."""
        if not self._table_cap or len(self._jobs) <= self._table_cap:
            return
        excess = len(self._jobs) - self._table_cap
        for jid in [jid for jid, j in self._jobs.items()
                    if j._done.is_set()][:excess]:
            del self._jobs[jid]

    def submit(self, program: VertexProgram, query: Query,
               job_id: str | None = None, mesh=None,
               wait_timeout: float = 30.0, sink_name: str | None = None,
               sink_format: str | None = None,
               explain: bool = False, tenant: str | None = None,
               deadline_ms=None, priority: int = 0,
               batch=None) -> Job:
        from .sink import ResultSink, resolve_sink_path

        # a malformed deadline is the CALLER's error and must raise as
        # one — validated BEFORE admission, or an admission-enabled
        # server would misreport it as a deadline_infeasible shed (a
        # capacity signal) and pollute the shed metrics
        if deadline_ms is not None and not float(deadline_ms) > 0:
            raise ValueError(
                f"deadline_ms must be positive, got {deadline_ms!r}")
        # admission BEFORE the job exists: an over-budget / over-share /
        # deadline-infeasible request is shed here with AdmissionDenied
        # (REST maps it to 429 + Retry-After) and never touches the job
        # table. The returned estimate is registered into the live
        # backlog; complete() (via _publish_ledger) or the failure path
        # below releases it.
        est = self.scheduler.admit(program, query, tenant,
                                   deadline_ms=deadline_ms)
        with self._lock:
            if job_id is None:
                job_id = f"{type(program).__name__}_{next(self._counter)}"
            if job_id in self._jobs:
                self.scheduler.cancel(est, tenant)
                raise KeyError(f"job {job_id!r} already exists")
            try:
                job = Job(job_id, program, query, self.graph,
                          mesh=mesh if mesh is not None else self.mesh,
                          wait_timeout=wait_timeout, explain=explain,
                          tenant=tenant, deadline_ms=deadline_ms,
                          priority=priority,
                          no_batch=batch is False)
            except BaseException:
                self.scheduler.cancel(est, tenant)
                raise
            job._sched = self.scheduler
            job._admitted_cost_s = est
            self._jobs[job_id] = job
            self._note_table(write=True)
            self._evict_done_locked()
        sink = None
        try:
            # disk I/O (mkdirs + open) stays OUTSIDE the registry lock;
            # the job is registered but not started, so the sink attaches
            # before any emit. Format rides the resolved suffix.
            path = resolve_sink_path(self.sink_dir, job_id,
                                     requested=sink_name,
                                     fmt=sink_format or self.sink_format)
            if path is not None:
                sink = ResultSink(path)
                with self._lock:
                    # no two LIVE jobs share one file (interleaved rows);
                    # sequential append to a finished job's file is fine.
                    # Sinks only attach under this lock, so the check and
                    # the attach are atomic.
                    for other in self._jobs.values():
                        if (other is not job and other.sink is not None
                                and other.sink.path == sink.path
                                and not other._done.is_set()):
                            raise ValueError(
                                f"sink path in use by job {other.id!r}")
                    job.sink = sink
        except BaseException:
            if sink is not None:
                sink.close()
            with self._lock:
                del self._jobs[job_id]
            self.scheduler.cancel(est, tenant)
            raise
        # coalescing: an eligible job joins its family's collect window
        # BEFORE its thread starts (the thread's first act is to wait on
        # the window handle); ineligible jobs — and every job when
        # RTPU_BATCH_WINDOW_MS=0 — take exactly the pre-scheduler path
        try:
            self.scheduler.offer(job)
            return job.start()
        except BaseException:
            # thread exhaustion is exactly when admission matters: a
            # failed start must not leave a never-running "running" job
            # in the table nor its cost stuck in the admission backlog.
            # Kill first: offer() may have enqueued a _Pending, and a
            # dead job's pending must be dropped at batch formation
            # (the dispatch loop checks _kill), not dispatched for a
            # result nobody will read
            job.kill()
            if sink is not None:
                sink.close()
            with self._lock:
                self._jobs.pop(job_id, None)
            self.scheduler.cancel(est, tenant)
            raise

    def get(self, job_id: str) -> Job:
        # under the registry lock like every other table access: a bare
        # dict read racing submit's insert/evict is exactly the unguarded
        # shape the lockset sanitizer flags (rtpulint v2)
        with self._lock:
            job = self._jobs.get(job_id)
            self._note_table()
        if job is None:
            raise KeyError(f"unknown job {job_id!r}")
        return job

    def results(self, job_id: str) -> list[dict]:
        return self.get(job_id).results_snapshot()

    def kill(self, job_id: str) -> None:
        self.get(job_id).kill()

    def jobs(self) -> dict[str, str]:
        with self._lock:
            self._note_table()
            return {jid: j.status for jid, j in self._jobs.items()}
