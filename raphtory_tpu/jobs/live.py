"""Live epoch engine: incremental delta maintenance for standing queries.

A Live subscription used to pay a FULL re-sweep per tick — `_run_at`
rebuilt the view (or re-advanced the resident sweep) and re-ran the
whole algorithm even when one event arrived since the last tick. The
epoch engine keeps ONE columnar hop-batched engine (engine/hopbatch)
alive per subscription, device-resident base included, and serves each
tick ("epoch") by:

* adopting the log suffix appended since the last epoch in place
  (``SweepBuilder.repin`` — same coordinate space, so fold state, the
  device-resident advanced base and the host delta base all stay
  valid; a suffix that brings new vertex ids or pairs GROWS the dense
  dictionaries under the standing engine instead, the fold state
  carried: that epoch re-ships the base and solves cold, and folds no
  more than any other),
* folding ONLY the events in ``(t_prev, t]`` and shipping O(Σdelta)
  bytes through ``run_columns_delta``'s delta path, and
* warm-starting the solve from the previous epoch's output — PageRank
  unconditionally (contraction), CC/BFS by min-merge under the
  monotone gate (add-only epoch delta, unwindowed — the kernel
  docstrings in engine/hopbatch state the equivalence argument), SSSP
  never (a weight update can raise distances).

Every epoch falls back to the legacy full re-sweep (``Job._run_at``)
when the incremental path cannot serve — non-columnar program, memory
guards, a transport failure or OOM mid-dispatch (any other error fails
the job, ``jobs/manager.declinable``) — so the fallback IS the
correctness oracle: both paths emit through ``Job._emit`` with
identical row shapes. Every ``RTPU_LIVE_RESYNC`` epochs the engine
drops device residency and the warm seed ("resync"): the next epoch
re-ships the base from the exact integer host fold state, bounding
f32 warm-seed drift without rebuilding host state.

Epoch modes (the ``raphtory_live_epochs_total{algorithm,mode}`` label
set, closed):

* ``incremental`` — suffix adopted, delta folded, warm-seeded solve
                    (span attribute ``repin``: ``grown`` when the suffix
                    grew the dictionaries — a cold solve on a re-shipped
                    base, the fold still the delta's)
* ``rebase``      — fresh engine built (first epoch, or repin refused:
                    compaction / out-of-order / dtype overflow / an
                    engine that cannot follow a growth, weighted SSSP);
                    full base ships once
* ``resync``      — scheduled residency + warm-seed drop (drift bound)
* ``resweep``     — legacy full re-sweep fallback
* ``skipped``     — wall-clock mode, neither safe_time nor the log
                    moved: the previous result is still THE result at
                    t, so no work is re-run (freshness still recorded)
"""

from __future__ import annotations

import os
import time as _time

import numpy as np

from ..obs import freshness as _fresh
from ..obs import journal as _journal
from ..obs import ledger as _ledger
from ..obs.metrics import METRICS
from ..obs.trace import TRACER, block_steps as _block_steps

import logging

_live_log = logging.getLogger(__name__)

#: device/host admission guards for the standing engine — same bounds
#: the columnar range route applies per request (jobs/manager.py
#: ``_columnar_range_prep``); a subscription holds them for its lifetime
MAX_DEVICE_MASK_BYTES = 1 << 32
MAX_HOST_COLUMN_BYTES = 1 << 29


def live_enabled() -> bool:
    """``RTPU_LIVE=0`` restores the legacy full-re-sweep-per-tick live
    loop (the bench A/B off arm). Re-read per epoch — flipping it
    mid-stream is legal and lands on the next epoch (the standing
    engine is dropped, not leaked)."""
    return os.environ.get("RTPU_LIVE", "1") not in ("", "0", "false")


def epoch_floor_s() -> float:
    """Minimum inter-epoch wait in wall-clock mode (``RTPU_LIVE_EPOCH_MS``,
    milliseconds): the cadence floor a burning staleness budget is
    allowed to reach. Unparseable values fall back to the default."""
    try:
        v = float(os.environ.get("RTPU_LIVE_EPOCH_MS", "") or 25.0)
    except ValueError:
        v = 25.0
    return max(0.0, v) / 1000.0


def resync_every() -> int:
    """Scheduled full-resync period in epochs (``RTPU_LIVE_RESYNC``):
    every N incremental epochs the engine drops device residency and
    the warm seed, bounding f32 warm-start drift. 0 disables."""
    try:
        v = int(os.environ.get("RTPU_LIVE_RESYNC", "") or 64)
    except ValueError:
        v = 64
    return max(0, v)


class LiveEpochState:
    """Per-subscription epoch state: the standing columnar engine, the
    previous epoch's raw output (the warm seed), and the skip-gate
    bookkeeping. Owned and driven by ONE job thread (``Job._run_live``)
    — no locking; the engine's own device state is job-private."""

    def __init__(self, job):
        self.job = job
        self.hb = None                  # standing hop-batched engine
        self._builder_failed = False    # program has no columnar engine
        self.last_t: int | None = None
        self.last_log_n = -1
        self.last_out = None            # [W, n_pad] previous raw output
        self.served = 0                 # epochs that emitted rows
        self.since_resync = 0
        self.mode_counts: dict[str, int] = {}

    # ---- the epoch ----

    def epoch(self, q, t: int) -> str:
        """Serve one epoch at event time ``t``; returns the epoch mode.
        Emission, ledger phases and telemetry all happen inside — the
        caller (``_run_live``) only computes ``t`` and paces."""
        t = int(t)
        t0 = _time.perf_counter()
        alg = (self.job.ledger.algorithm
               or type(self.job.program).__name__)
        log = self.job.graph.log
        log_n = int(log.n)

        if (not q.event_time and self.served > 0
                and self.last_t == t and self.last_log_n == log_n):
            # wall-clock skip gate (belt and braces: the watermark
            # contract alone implies an unchanged t has an unchanged
            # fold, but a direct log append is legal and unfenced, so
            # the row count is checked too): neither the safe time nor
            # the log moved since the last served epoch — the previous
            # result IS the result at t. Serve it from the results
            # buffer by doing nothing; staleness is still recorded
            # (the data aged even if the graph didn't change).
            TRACER.instant("live.epoch", mode="skipped", time=t,
                           algorithm=alg)
            self._finish("skipped", t, alg, delta_rows=0, ship_bytes=0,
                         seconds=_time.perf_counter() - t0, priced=False)
            return "skipped"

        # ONE span for the whole epoch — engine build, repin, the delta
        # statistics, dispatch, emit and the epoch's telemetry — so its
        # duration is what a subscriber waits for; ``mode`` (and the
        # engine's padded sizes) are set once known
        with TRACER.span("live.epoch", time=t, algorithm=alg) as sp:
            mode = self._epoch(q, t, alg, t0, log_n, sp)
            sp.set(mode=mode)
        return mode

    def _epoch(self, q, t: int, alg: str, t0: float, log_n: int, sp) -> str:
        led = self.job.ledger
        if not live_enabled():
            self.hb = None          # flipping the knob drops the engine
            self.last_out = None
            return self._resweep(q, t, alg, t0)

        mode = "incremental"
        if self.hb is not None:
            r0 = _time.perf_counter()
            status = self.hb.repin()
            if status == "grown":
                # the suffix brought new ids or pairs and the engine grew
                # its dictionaries to hold them, fold state and t_prev
                # carried (an ``engine.build``: the seconds are in the
                # build phase already). The previous output's rows are
                # the OLD dense space's: this epoch solves cold
                self.last_out = None
            else:
                # adopting the appended suffix is the incremental fold's
                # first half (the log scan that decides what to fold)
                led.add_phase("fold", _time.perf_counter() - r0)
            sp.set(repin=status)
            if status == "rebuild":
                # the adopted-suffix invariants broke (compaction,
                # out-of-order arrival past t_prev, dtype overflow, an
                # engine that cannot follow a growth): discard it
                # wholesale and rebase
                self.hb = None
                self.last_out = None    # n_pad may change under a rebuild
            elif status == "grown" and self._over_guard(self.hb, q):
                # the padded sizes stepped up past a memory guard: what
                # a rebase over this log would be refused for
                sp.set(declined="memory_guard")
                self.hb = None
                self._builder_failed = True
        if self.hb is None:
            if self._builder_failed:
                return self._resweep(q, t, alg, t0)
            with _ledger.engine_build("rebase", self.job.graph.log,
                                      led) as bsp:
                try:
                    hb = self.job._columnar_builder()
                except (TypeError, ValueError, MemoryError) as e:
                    _live_log.info("live epoch engine declined: %s: %s",
                                   type(e).__name__, e)
                    bsp.set(declined=type(e).__name__)
                    hb = None
                else:
                    bsp.set(**_ledger.built(hb))
                    if self._over_guard(hb, q):
                        bsp.set(declined="memory_guard")
                        hb = None   # a guard is a property of the
                        #             graph's size
            if hb is None:
                self._builder_failed = True
                return self._resweep(q, t, alg, t0)
            self.hb = hb
            mode = "rebase"
        hb = self.hb
        sp.set(n_pad=int(hb.tables.n_pad), m_pad=int(hb.tables.m_pad))

        if hb.sw.t_prev is not None and t < int(hb.sw.t_prev):
            # time went backward (watermark regression is a caller bug,
            # but never serve a wrong answer for it): the hop engine
            # only ascends — full re-sweep and rebuild next epoch
            self.hb = None
            self.last_out = None
            return self._resweep(q, t, alg, t0)

        if (mode == "incremental" and resync_every() > 0
                and self.since_resync >= resync_every()):
            # scheduled drift bound: drop residency AND the warm seed —
            # the next dispatch re-ships the base from the exact
            # integer host fold state and solves cold, so only this
            # epoch pays O(base) ship; host fold state is NOT rebuilt
            mode = "resync"
            hb._drop_residency()
            self.last_out = None
            self.since_resync = 0

        d0 = _time.perf_counter()
        delta_rows, add_only = self._delta_stats(hb, t)
        led.add_phase("fold", _time.perf_counter() - d0)
        windows = list(q.windows) if q.windows is not None else [q.window]
        warm = None
        if self.last_out is not None and mode == "incremental":
            if hb.supports_warm_start:
                warm = self.last_out        # contraction: always valid
            elif (hb.supports_epoch_warm and add_only
                    and windows == [None]):
                # min-merge warm init is only equivalent when the graph
                # monotonically grew since the seed was computed and no
                # window can drop edges (kernel docstrings argue this)
                warm = self.last_out
        sp.set(mode=mode, delta_rows=int(delta_rows),
               warm=warm is not None)

        shells = {}

        def grab_shell(T, sw):
            shells[int(T)] = _manager()._shell_from_fold(
                hb.tables, sw, int(T))

        try:
            ranks, steps = hb.run([t], windows, chunks=1,
                                  hop_callback=grab_shell,
                                  warm_state=warm)
            b0 = _time.perf_counter()
            ranks, steps = _block_steps(
                lambda: (np.asarray(ranks), steps))
            led.add_phase("device_wait", _time.perf_counter() - b0)
        except Exception as e:
            # a transport failure or OOM on the incremental path falls
            # back to the oracle path for THIS epoch and rebuilds the
            # engine on the next — a live job must keep serving. Any
            # other error (jobs/manager.declinable) fails the job.
            self.hb = None
            self.last_out = None
            if not _manager().declinable(e):
                raise
            _live_log.warning("live epoch failed (%s: %s) — falling "
                              "back to full re-sweep",
                              type(e).__name__, e)
            return self._resweep(q, t, alg, t0)

        ship = int(hb.ship_bytes)
        elapsed = _time.perf_counter() - t0
        METRICS.snapshot_build_seconds.observe(hb.fold_seconds)
        METRICS.supersteps.inc(max(int(steps), 0))
        led.count_supersteps(int(steps))
        per_row = elapsed / max(len(windows), 1)
        with TRACER.span("job.emit", rows=len(windows)):
            for i, w in enumerate(windows):
                if self.job._kill.is_set():
                    break
                self.job._emit(t, w, ranks[i], shells[t], int(steps),
                               _time.perf_counter() - per_row)
        self.last_out = ranks
        self.last_t = t
        self.last_log_n = log_n
        self.served += 1
        self.since_resync += 1
        self._finish(mode, t, alg, delta_rows=delta_rows,
                     ship_bytes=ship,
                     seconds=_time.perf_counter() - t0)
        return mode

    # ---- cadence ----

    def next_wait(self, q) -> float:
        """Wall-clock inter-epoch wait, adapted to the staleness budget:
        a burning budget serves back-to-back at the ``RTPU_LIVE_EPOCH_MS``
        floor, a degraded one halves the requested repeat, an ok one
        coalesces at the requested repeat (never below the floor)."""
        floor = epoch_floor_s()
        alg = (self.job.ledger.algorithm
               or type(self.job.program).__name__)
        grade = _fresh.FRESH.live_grade(alg)
        if grade == "burning":
            return floor
        if grade == "degraded":
            return max(floor, float(q.repeat) / 2.0)
        return max(floor, float(q.repeat))

    # ---- internals ----

    @staticmethod
    def _over_guard(hb, q) -> bool:
        """Whether ``hb``'s padded sizes put an epoch's device masks or
        host columns past the memory guards — asked wherever the sizes
        can have changed: a build, and a growth."""
        windows = list(q.windows) if q.windows is not None else [q.window]
        return (hb.device_mask_bytes(len(windows)) > MAX_DEVICE_MASK_BYTES
                or hb.host_column_bytes(1) > MAX_HOST_COLUMN_BYTES)

    def _delta_stats(self, hb, t: int):
        """(rows folded this epoch, add-only?) — BY TIME over the full
        pinned log, not by pin growth: event-time mode can fold OLD
        pinned rows (t advanced past them), and the add-only warm gate
        must see every row entering the fold window ``(t_prev, t]``."""
        sw = hb.sw
        tcol, kcol = sw._t, sw._k
        t_prev = sw.t_prev
        if not len(tcol):
            return 0, True
        if sw._t_sorted:
            lo = 0 if t_prev is None else int(
                np.searchsorted(tcol, t_prev, side="right"))
            hi = int(np.searchsorted(tcol, t, side="right"))
            kinds = kcol[lo:hi]
            n = hi - lo
        else:
            m = tcol <= t
            if t_prev is not None:
                m &= tcol > t_prev
            kinds = kcol[m]
            n = int(m.sum())
        from ..core.events import EDGE_DELETE, VERTEX_DELETE

        add_only = not bool(((kinds == VERTEX_DELETE)
                             | (kinds == EDGE_DELETE)).any())
        return n, add_only

    def _resweep(self, q, t: int, alg: str, t0: float) -> str:
        """The legacy full re-sweep — the oracle path every degraded
        epoch takes (``exact=False`` mirrors the pre-epoch live loop)."""
        self.job._run_at(t, q, exact=False)
        self.last_t = t
        self.last_log_n = int(self.job.graph.log.n)
        self.served += 1
        self._finish("resweep", t, alg, delta_rows=-1, ship_bytes=-1,
                     seconds=_time.perf_counter() - t0)
        return "resweep"

    def _finish(self, mode: str, t: int, alg: str, *, delta_rows: int,
                ship_bytes: int, seconds: float,
                priced: bool = True) -> None:
        """Per-epoch telemetry, identical across modes: staleness into
        the freshness plane (returned staleness feeds the subscription
        table), the bounded epochs counter, and the ``live:`` admission
        price (skipped epochs are free and never priced — an EWMA of
        zeros would undercharge the epochs that do work)."""
        # keyed by the closed epoch-mode set (incremental / rebase /
        # resweep / skipped / resync — the docs/LIVE.md table and the
        # metric label), so at most five entries for the subscription's
        # lifetime.  # rtpulint: disable=unbounded-growth-on-request-path
        self.mode_counts[mode] = self.mode_counts.get(mode, 0) + 1
        try:
            head = int(self.job.graph.latest_time)
        except Exception:       # empty log has no latest time
            head = None
        staleness = _fresh.FRESH.note_live_result(
            alg, t, head_time=head, trace_id=self.job.trace_id)
        _fresh.FRESH.note_live_epoch(
            self.job.id, algorithm=alg, mode=mode,
            delta_rows=delta_rows, ship_bytes=ship_bytes,
            staleness_s=staleness, result_time=t)
        METRICS.live_epochs.labels(alg, mode).inc()
        if _journal.enabled():
            _journal.emit("epoch", {
                "job_id": self.job.id, "algorithm": alg, "mode": mode,
                "result_time": t, "delta_rows": delta_rows,
                "ship_bytes": ship_bytes,
                "staleness_s": (round(staleness, 6)
                                if staleness is not None else None),
                "seconds": round(seconds, 6), "served": self.served},
                trace_id=self.job.trace_id)
        if priced and self.job._sched is not None:
            try:
                self.job._sched.note_live_epoch(alg, seconds)
            except Exception:   # pricing never fails a live job
                pass


def _manager():
    # late import: jobs/manager imports THIS module inside _run_live
    from . import manager

    return manager
