"""Pipelined, resilient host→device transfers.

A monolithic multi-hundred-MB ``device_put`` makes a transport failure
all-or-nothing; uploading in bounded slices with per-slice retry turns a
transient transport error (``UNAVAILABLE``, a reset connection) into a
pause instead. Only transport errors retry: a compile or runtime error
the device reports (``INTERNAL``, ``RESOURCE_EXHAUSTED``, …) surfaces on
the first attempt.

A serial slice loop leaves the host memcpy, the wire, and the device
taking turns idling — each slice blocks (``block_until_ready``) before
the next ``ascontiguousarray`` staging copy even starts.
``TransferEngine`` pipelines the stages in the
bulk-synchronous *pseudo-streaming* style (arXiv:1608.07200): a bounded
window (default 2) of in-flight ``device_put`` futures, so slice *i+1*'s
host-side staging overlaps slice *i*'s wire time. Completion (and
therefore per-slice retry) happens only when the window is full or at
drain; the staged host buffer stays alive until its slice completes, so a
transport flap re-ships exactly that slice and the upload resumes
mid-array.

This is transport plumbing, not semantics: results are bit-identical to
``jax.device_put`` (same concatenate-on-device shape/dtype/values). The
reference has no analogue (its graph lives in the same JVM as the compute
— SURVEY.md §1 L3); this is the TPU-native cost of a disaggregated
accelerator.

Knobs and telemetry
-------------------
* ``RTPU_TRANSFER_DEPTH`` — in-flight window depth (default 2; 1 is the
  old fully-serial behaviour, kept as the bench comparison point).
* ``TransferEngine.stats`` / ``shared_engine().stats`` — bytes shipped,
  slice count, retries, per-stage stall seconds (``stage`` = host copy,
  ``wire`` = blocked on an in-flight put), window high-water mark.
* Mirrored into Prometheus when ``obs.metrics`` is importable:
  ``raphtory_h2d_bytes_total``, ``raphtory_h2d_slices_total``,
  ``raphtory_h2d_retries_total``, ``raphtory_h2d_stall_seconds_total
  {stage}``, ``raphtory_h2d_inflight_depth``.
* Per-slice spans in the flight recorder when ``obs.trace`` is importable
  and tracing is on (``RTPU_TRACE``): ``ship.stage`` / ``ship.wire`` /
  ``ship.retry`` with byte counts — stalls as timeline children of the
  sweep, not just counters (docs/OBSERVABILITY.md).
"""

from __future__ import annotations

import logging
import os
import threading
import time
from collections import deque
from dataclasses import dataclass, field

import numpy as np

from ..analysis.sanitizer import (note_shared as _san_note,
                                  track_shared as _san_track)
from ..resilience import faults as _faults
from ..resilience.policy import (RetryPolicy,
                                 is_transient_message as _is_transient_message,
                                 note_attempt)

_log = logging.getLogger(__name__)


def _is_transient(e: BaseException) -> bool:
    """True only for transport-flavoured failures (retry). Everything
    else re-raises immediately — an ``XlaRuntimeError`` whose status is
    not a transport one (``INTERNAL``, a compiler refusal, real OOM) is
    the device's answer, not a flap."""
    return _is_transient_message(str(e)) is True


def _default_depth() -> int:
    return max(1, int(os.environ.get("RTPU_TRANSFER_DEPTH", 2)))


_METRICS_SENTINEL = object()
_METRICS = _METRICS_SENTINEL


def _metrics():
    """obs.metrics bundle, or None when prometheus isn't importable —
    the transfer layer must work in stripped environments."""
    global _METRICS
    if _METRICS is _METRICS_SENTINEL:
        try:
            from ..obs.metrics import METRICS

            _METRICS = METRICS
        except Exception:
            _METRICS = None
    return _METRICS


_TRACER = None


def _tracer():
    """The process tracer (``obs.trace.TRACER``) — imported lazily so the
    transfer layer stays import-light. ``obs.trace`` is stdlib-only and
    ``obs/__init__`` guards its prometheus/jax imports, so this works in
    the same stripped environments ``_metrics()`` degrades in."""
    global _TRACER
    if _TRACER is None:
        from ..obs.trace import TRACER

        _TRACER = TRACER
    return _TRACER


@dataclass
class TransferStats:
    """Cumulative pipeline telemetry for one engine (or the shared one).

    Mutation goes through :meth:`bump` under the stats' own lock: the
    SHARED engine is driven by every concurrent job thread, and unguarded
    ``+=`` on these counters loses updates under load (the rtpulint v2
    lockset detector catches exactly this shape at runtime)."""

    bytes_shipped: int = 0
    slices: int = 0
    retries: int = 0
    stage_seconds: float = 0.0   # host-side ascontiguousarray staging
    wire_seconds: float = 0.0    # blocked on an in-flight put (window full
    #                              or drain) — the wire stall the pipeline
    #                              exists to hide
    depth_high_water: int = 0
    _mu: threading.Lock = field(default_factory=threading.Lock,
                                repr=False, compare=False)
    #: lockset-sanitizer handle — attached by shared_engine() ONLY (a
    #: tracker registration is permanent, and device_put_chunked builds
    #: a throwaway engine per call)
    _san_tracker: object = field(default=None, repr=False, compare=False)

    def bump(self, **deltas) -> None:
        """Atomically add ``deltas`` to counters; ``depth_high_water`` is
        a max, not a sum. Returns nothing — readers use ``as_dict``."""
        with self._mu:
            for k, v in deltas.items():
                if k == "depth_high_water":
                    if v > self.depth_high_water:
                        self.depth_high_water = v
                else:
                    setattr(self, k, getattr(self, k) + v)
            self._note_shared_write()

    def _note_shared_write(self) -> None:
        """Lockset-sanitizer hook (no-op unless RTPU_SANITIZE installed a
        tracker): every mutation reports under the stats lock, so a
        future unguarded write path shows up as a race finding."""
        _san_note(self._san_tracker, write=True)

    def as_dict(self) -> dict:
        with self._mu:
            return {
                "bytes_shipped": int(self.bytes_shipped),
                "slices": int(self.slices),
                "retries": int(self.retries),
                "stage_stall_seconds": round(self.stage_seconds, 4),
                "wire_stall_seconds": round(self.wire_seconds, 4),
                "inflight_depth_high_water": int(self.depth_high_water),
            }

    def totals(self) -> dict:
        """Cheap cumulative snapshot for periodic samplers (the /slz
        series ring diffs consecutive samples into per-interval rates):
        bytes shipped and combined stage+wire stall seconds."""
        with self._mu:
            return {
                "bytes_shipped": int(self.bytes_shipped),
                "stall_seconds": round(
                    self.stage_seconds + self.wire_seconds, 6),
            }

    def delta_since(self, prior: dict) -> dict:
        """Stats accumulated since a ``prior`` ``as_dict()`` snapshot —
        how benches attribute shared-engine traffic to one timed region.
        The high-water depth is a max, not a counter — reported absolute."""
        now = self.as_dict()
        out = {k: round(now[k] - prior.get(k, 0), 4)
               if isinstance(now[k], float) else now[k] - prior.get(k, 0)
               for k in now}
        out["inflight_depth_high_water"] = now["inflight_depth_high_water"]
        return out


class TransferEngine:
    """Bounded-depth pipelined chunked ``device_put``.

    ``put`` slices along axis 0 (row groups sized to ``chunk_bytes``),
    stages each slice into a contiguous host buffer, issues the put
    WITHOUT blocking, and only completes (blocks + retries) the oldest
    slice when the in-flight window is full — so staging slice *i+1*
    overlaps slice *i*'s wire time. ``depth=1`` reproduces the old serial
    stage→ship→block loop exactly.
    """

    def __init__(self, *, depth: int | None = None,
                 chunk_bytes: int = 32 << 20, retries: int = 4,
                 backoff: float = 10.0, device=None):
        self.depth = max(1, int(depth if depth is not None
                                else _default_depth()))
        self.chunk_bytes = int(chunk_bytes)
        self.retries = int(retries)
        self.backoff = float(backoff)
        self.device = device
        self.stats = TransferStats()
        # the shared policy supplies CAPPED, FULL-JITTER backoff waits:
        # N engines retrying the same dead link no longer wake in
        # lockstep and re-stampede it (docs/RESILIENCE.md)
        self.policy = RetryPolicy(attempts=self.retries,
                                  base_s=self.backoff,
                                  classify=_is_transient)

    # ---- slice lifecycle ----

    def _record_depth(self, n: int) -> None:
        if n > self.stats.depth_high_water:   # racy fast-path read only —
            self.stats.bump(depth_high_water=n)  # bump re-checks locked
            m = _metrics()
            if m is not None:
                m.h2d_inflight_depth.set(n)

    def _stage(self, a):
        """Contiguous host copy of one slice (no-op view when already
        contiguous) — the pipeline's host-memcpy stage."""
        t0 = time.perf_counter()
        with _tracer().span("ship.stage", bytes=int(a.nbytes)):
            staged = np.ascontiguousarray(a)
        dt = time.perf_counter() - t0
        self.stats.bump(stage_seconds=dt)
        m = _metrics()
        if m is not None:
            m.h2d_stall_seconds.labels(stage="stage").inc(dt)
        return staged

    def _issue(self, staged):
        """Non-blocking ``device_put``; a transport error AT ISSUE falls
        back to the blocking retry loop for this slice only."""
        import jax

        self.stats.bump(slices=1, bytes_shipped=staged.nbytes)
        m = _metrics()
        if m is not None:
            m.h2d_bytes.inc(staged.nbytes)
            m.h2d_slices.inc()
        try:
            _faults.fire("transfer.wire")
            return jax.device_put(staged, self.device), staged
        except Exception as e:  # noqa: BLE001 — classified below
            if not _is_transient(e):
                raise
            return self._retry(staged, e), None   # completed synchronously

    def _retry(self, staged, first_err):
        """Blocking re-put of one staged slice under the shared policy's
        capped full-jitter backoff — attempt 1 (the pipelined issue)
        already failed."""
        import jax

        err = first_err
        for attempt in range(1, self.retries):
            wait = self.policy.backoff_s(attempt)
            _log.warning(
                "device_put of %.1f MB failed (%s); retry %d/%d in %.1fs",
                staged.nbytes / 2**20, err, attempt, self.retries - 1, wait)
            note_attempt("transfer.wire", "retry", attempt, wait)
            time.sleep(wait)
            self.stats.bump(retries=1)
            m = _metrics()
            if m is not None:
                m.h2d_retries.inc()
            try:
                with _tracer().span("ship.retry", attempt=attempt,
                                    bytes=int(staged.nbytes)):
                    _faults.fire("transfer.wire")
                    x = jax.device_put(staged, self.device)
                    x.block_until_ready()   # surface transport errors HERE
                return x
            except Exception as e:  # noqa: BLE001 — classified below
                if not _is_transient(e):
                    note_attempt("transfer.wire", "fatal", attempt, 0.0)
                    raise
                err = e
        note_attempt("transfer.wire", "exhausted", self.retries, 0.0)
        raise err

    def _complete(self, item):
        """Block on one in-flight slice; transport failure re-ships it
        from the still-live staged buffer (the upload resumes mid-array)."""
        x, staged = item
        t0 = time.perf_counter()
        if staged is not None:   # None: already completed at issue time
            with _tracer().span("ship.wire", bytes=int(staged.nbytes)):
                try:
                    _faults.fire("transfer.wire")
                    x.block_until_ready()
                except Exception as e:  # noqa: BLE001 — classified below
                    if not _is_transient(e):
                        raise
                    x = self._retry(staged, e)
        dt = time.perf_counter() - t0
        self.stats.bump(wire_seconds=dt)
        m = _metrics()
        if m is not None:
            m.h2d_stall_seconds.labels(stage="wire").inc(dt)
        return x

    # ---- public API ----

    def _slices_of(self, a) -> list:
        """Row-group slices of ``a`` sized to ``chunk_bytes`` (the whole
        array when it fits)."""
        if a.ndim == 0 or a.nbytes <= self.chunk_bytes:
            return [a]
        n = a.shape[0]
        per_row = max(1, a.nbytes // n)
        rows = max(1, int(self.chunk_bytes // per_row))
        return [a[lo: lo + rows] for lo in range(0, n, rows)]

    def put(self, a):
        """``jax.device_put(a)``, pipelined: bit-identical result, bounded
        in-flight window, per-slice retry. Device arrays pass through."""
        import jax
        import jax.numpy as jnp

        if isinstance(a, jax.Array):
            return a
        a = np.asarray(a)
        parts = self._pump([(0, s) for s in self._slices_of(a)])[0]
        if len(parts) == 1:
            return parts[0]
        return jnp.concatenate(parts, axis=0)

    def put_many(self, arrays):
        """Pipelined puts of a LIST of arrays — the in-flight window spans
        array boundaries, so array k+1's staging overlaps array k's wire
        time (the per-dispatch payload ship of the sweep engines). Device
        arrays pass through untouched; order is preserved."""
        import jax
        import jax.numpy as jnp

        plan, out = [], [None] * len(arrays)
        for k, a in enumerate(arrays):
            if isinstance(a, jax.Array):
                out[k] = a
                continue
            plan.extend((k, s) for s in self._slices_of(np.asarray(a)))
        parts = self._pump(plan)
        for k, ps in parts.items():
            out[k] = ps[0] if len(ps) == 1 else jnp.concatenate(ps, axis=0)
        return out

    def _pump(self, plan):
        """Drive the stage→issue→complete pipeline over ``plan`` (a list
        of (key, slice)); returns {key: [device parts in order]}."""
        inflight: deque = deque()
        parts: dict[int, list] = {}
        for key, sl in plan:
            parts.setdefault(key, [])
            while len(inflight) >= self.depth:
                k0, item = inflight.popleft()
                parts[k0].append(self._complete(item))
            staged = self._stage(sl)
            inflight.append((key, self._issue(staged)))
            self._record_depth(len(inflight))
        while inflight:
            k0, item = inflight.popleft()
            parts[k0].append(self._complete(item))
        return parts


_SHARED: TransferEngine | None = None
_SHARED_LOCK = threading.Lock()


def shared_engine() -> TransferEngine:
    """Process-wide engine (env-configured depth) used by the sweep
    engines' payload ships — one stats bundle for the whole process.
    Creation is locked: two REST threads racing the lazy init would
    otherwise each get an engine and split the process stats between
    them (rtpulint RT010)."""
    global _SHARED
    if _SHARED is None:
        with _SHARED_LOCK:
            if _SHARED is None:
                eng = TransferEngine()
                # lockset-sanitizer registration (None unless
                # RTPU_SANITIZE): the SHARED engine's stats are driven by
                # every job thread, so each mutation reports its held
                # lockset. Only here — a registration is permanent, and
                # device_put_chunked builds a throwaway engine per call.
                eng.stats._san_tracker = _san_track("transfer_stats")
                _SHARED = eng
    return _SHARED


def _put_retry(a, retries: int, backoff: float, device):
    """Serial staged put with retry — kept for callers that want one
    blocking slice; transport-error classification shared with the
    engine (programming errors re-raise immediately)."""
    eng = TransferEngine(depth=1, retries=retries, backoff=backoff,
                         device=device)
    staged = eng._stage(np.asarray(a))
    return eng._complete(eng._issue(staged))


def device_put_chunked(a, *, chunk_bytes: int = 32 << 20, retries: int = 4,
                       backoff: float = 10.0, device=None,
                       depth: int | None = None):
    """``jax.device_put`` in bounded slices with per-slice retry and a
    pipelined in-flight window.

    Slices along axis 0 (row groups sized to ``chunk_bytes``), keeps up to
    ``depth`` puts in flight (default ``RTPU_TRANSFER_DEPTH``, 2) so the
    next slice's host staging overlaps the current slice's wire time,
    retries each slice with exponential backoff on TRANSPORT errors only,
    concatenates on device. ``depth=1`` is the old serial loop. 0-d and
    tiny arrays go straight through (still retried)."""
    return TransferEngine(depth=depth, chunk_bytes=chunk_bytes,
                          retries=retries, backoff=backoff,
                          device=device).put(a)
