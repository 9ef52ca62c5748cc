"""Device runtime plane — the MEASURED half of the ledger.

Everything the ledger (``obs/ledger.py``) knows about the device is a
compile-time estimate: XLA ``cost_analysis()`` FLOPs/bytes and the edge
traffic model, never a clock or a memory counter. This module is the
counterpart that measures — the instrument the adaptive runtime
(ROADMAP item 4) needs before it can trust the model it actuates on.
Four pieces, all surfaced at ``/devicez`` and federated per process by
``/clusterz``:

* **Measured kernel latency** (``TIMING``). ``instrument()``'s dispatch
  wrapper samples timed dispatches: a sampled dispatch blocks until the
  result is ready and records wall device seconds into a bounded
  per-(kernel, shape-sig) window. Sampling (``RTPU_DEVICE_TIMING``, a
  rate in (0, 1]) because an always-on sync would destroy the PR 2/5
  pipelining — and the measured number therefore includes dispatch
  overhead and any pipeline drain the sync forces (docs/OBSERVABILITY.md
  "Device runtime" spells out the caveat). The first dispatch of every
  (kernel, sig) is always timed (recorded separately as the COLD sample
  — it may include compile when the AOT harvest is off), the second is
  always timed (so every kernel dispatched twice has a warm p50), then
  every 1/rate-th. Each kernel row joins measured p50/p99 seconds,
  achieved FLOP/s and bytes/s, a measured-vs-estimated divergence ratio
  (measured p50 over the roofline model's predicted seconds), and a
  ``bound_measured`` re-classification next to the estimate-side
  ``bound`` / ``bound_refined``.
* **Device memory** (``memory_snapshot``). ``memory_stats()`` read off
  the first device, tolerant of backends that return None or raise
  (this CPU rig): the degrade is ``{"available": False}`` — never an
  exception out of a sampler thread, never a 500 off ``/devicez``. The
  PR 9 series ring samples bytes-in-use at 1 Hz, sampled dispatches max
  bytes-in-use into the active query ledger (``peak_device_bytes``),
  and the resident-buffer registry (``RESIDENT``) makes the engines'
  device-resident base tables a live gauge.
* **Resident-buffer registry** (``RESIDENT``). Weakref-keyed: an entry
  lives exactly as long as the engine (or log) that owns the buffer, so
  the gauge can never leak a dead engine's bytes (RT011 by
  construction). ``engine/hopbatch.py`` and ``engine/device_sweep.py``
  feed it at their upload sites.
* **Compile observability** (``note_compile``). Every
  ``lower().compile()`` in the kernel registry runs under an
  ``xla.compile`` span and lands here: per-kernel compile counts /
  seconds / last shape sig (joined into ``/statusz.compile_caches``),
  ``raphtory_compile{s,_seconds}_total{kernel}`` counters, and a
  bounded recent-compile ring whose density is the compile-storm signal
  (new shape sigs under request load recompiling faster than they can
  amortise) the advisor's ``device-pressure`` rule reads. The AOT
  harvest is the observation point, so ``RTPU_LEDGER_XLA=0`` (or an
  analyses-incapable backend) darkens this plane with the estimates.
* **Programs JAX builds** (``watch_jax_builds``). One ``jax.monitoring``
  listener, on the compiling thread, turns JAX's own trace / lower /
  backend-compile durations into ``xla.trace`` / ``xla.lower`` /
  ``xla.backend_compile`` events of the ambient trace and counts them
  (with the persistent cache's hits and misses) into
  ``/statusz.compile_caches.jax`` — every program, however it was
  built, not only the registry's one harvest per (kernel, shape). Rides
  the flight recorder: with tracing off the listener returns after one
  dictionary lookup and counts nothing.

Knobs
-----
* ``RTPU_DEVICE_TIMING`` — sampled timed-dispatch rate in (0, 1]
  (default 0.05; ``0`` disables; ``1`` times every dispatch). Rides the
  ledger plane: ``RTPU_LEDGER=0`` disables it too.
* ``RTPU_KERNEL_REGISTRY_CAP`` — (kernel, shape-sig) entry cap shared
  with the ledger's ``KernelRegistry`` (oldest evicted; ``0`` disables).
* ``RTPU_DEVICE_DUMP`` — file path; the full ``/devicez`` document is
  written there at interpreter exit (the CI failure-artifact hook).
"""

from __future__ import annotations

import json
import os
import threading
import time
import weakref
from collections import deque

from ..analysis.sanitizer import (note_shared as _san_note,
                                  track_shared as _san_track)
from .trace import TRACER as _TRACER

DEFAULT_RATE = 0.05
#: bounded warm-sample window per (kernel, sig) — recent-biased, like
#: the flight recorder: the p50 should describe the CURRENT regime
SAMPLE_WINDOW = 128
#: recent-compile ring bound (the compile-storm evidence window)
COMPILE_RING = 256
DEFAULT_REGISTRY_CAP = 512
#: measured seconds beyond this multiple of the model's predicted
#: seconds re-classify as overhead_bound — the time is real but the
#: roofline terms don't explain it (dispatch overhead, sync drain)
OVERHEAD_FACTOR = 4.0


def timing_rate() -> float:
    """``RTPU_DEVICE_TIMING`` resolved to a sampling rate in [0, 1] —
    re-read per dispatch (one getenv, the ledger-gate pattern) so the
    bench A/B and operators can flip it without a restart."""
    raw = os.environ.get("RTPU_DEVICE_TIMING")
    if raw is None or raw == "":
        return DEFAULT_RATE
    if raw in ("0", "false"):
        return 0.0
    try:
        return min(1.0, max(0.0, float(raw)))
    except ValueError:
        return DEFAULT_RATE


def registry_cap() -> int:
    """``RTPU_KERNEL_REGISTRY_CAP`` — the (kernel, shape-sig) entry cap
    the ledger's KernelRegistry and this module's timing table share
    (shape-diverse request traffic must not grow either without bound —
    rtpulint RT011). 0 disables."""
    try:
        return max(0, int(os.environ.get("RTPU_KERNEL_REGISTRY_CAP",
                                         DEFAULT_REGISTRY_CAP)))
    except ValueError:
        return DEFAULT_REGISTRY_CAP


def evict_past_cap(table: dict, cap: int, keep) -> list:
    """Shrink ``table`` to ``cap`` entries by evicting from the FRONT of
    the dict — the single bounded-registry policy the kernel registry
    and the timing table share. Callers re-insert a key at the BACK on
    every touch, so front-of-dict means least-recently-used, not
    first-registered: a hot kernel's row is never the one to go. The
    just-inserted ``keep`` key is never evicted (a cap below 1 live
    entry must not thrash it). Returns the evicted keys; the caller
    holds the table's lock and runs any cross-table hooks AFTER
    releasing it."""
    evicted = []
    while cap and len(table) > cap:
        oldest = next(iter(table))
        if oldest == keep:
            break
        del table[oldest]
        evicted.append(oldest)
    return evicted


def _metrics():
    """obs.metrics bundle, or None when prometheus isn't importable."""
    try:
        from .metrics import METRICS

        return METRICS
    except Exception:
        return None


def _peaks():
    """(peak FLOP/s, peak B/s) of the probed device kind — the ledger's
    one table; an unknown kind raises there."""
    from . import ledger as _ledger

    return _ledger.device_peaks()


def estimated_seconds(flops, hbm_bytes) -> float | None:
    """The roofline model's predicted per-dispatch seconds:
    max(flops / peak FLOP/s, bytes / peak bandwidth) — None without
    harvested estimates. The divergence ratio divides measured p50 by
    THIS, so it is a judgement on the whole model (XLA harvest + traffic
    model + platform anchors), not on one term."""
    if not flops and not hbm_bytes:
        return None
    pf, bw = _peaks()
    return max(float(flops or 0.0) / pf, float(hbm_bytes or 0.0) / bw)


# --------------------------------------------------------- kernel timing


class _Timing:
    """Warm-sample window + lifetime counters for one (kernel, sig)."""

    __slots__ = ("samples", "count", "sum_seconds", "min_seconds",
                 "max_seconds", "cold_seconds", "last_unix")

    def __init__(self):
        self.samples: deque = deque(maxlen=SAMPLE_WINDOW)
        self.count = 0          # warm timed dispatches, lifetime
        self.sum_seconds = 0.0
        self.min_seconds = float("inf")
        self.max_seconds = 0.0
        self.cold_seconds = None   # the always-timed first dispatch
        self.last_unix = 0.0

    def observe(self, seconds: float, cold: bool) -> None:
        self.last_unix = time.time()
        if cold:
            self.cold_seconds = seconds
            return
        self.samples.append(seconds)
        self.count += 1
        self.sum_seconds += seconds
        self.min_seconds = min(self.min_seconds, seconds)
        self.max_seconds = max(self.max_seconds, seconds)

    def summary(self) -> dict:
        out: dict = {"samples": self.count,
                     "last_unix": round(self.last_unix, 3)}
        if self.cold_seconds is not None:
            out["cold_seconds"] = round(self.cold_seconds, 6)
        vals = sorted(self.samples)
        if vals:
            out["p50_seconds"] = round(
                vals[(len(vals) - 1) // 2], 6)
            out["p99_seconds"] = round(
                vals[min(len(vals) - 1, int(0.99 * len(vals)))], 6)
            out["min_seconds"] = round(self.min_seconds, 6)
            out["max_seconds"] = round(self.max_seconds, 6)
            out["mean_seconds"] = round(
                self.sum_seconds / max(1, self.count), 6)
        elif self.cold_seconds is not None:
            # dispatched once, ever: the cold sample is all there is —
            # flagged so readers don't mistake compile for execute
            out["p50_seconds"] = round(self.cold_seconds, 6)
            out["cold_only"] = True
        return out


class DeviceTiming:
    """Process-wide sampled-dispatch timing table, keyed like the kernel
    registry by (kernel name, joined shape sig). Bounded by the SAME
    ``RTPU_KERNEL_REGISTRY_CAP`` (oldest evicted) and additionally
    pruned by the registry's own evictions (``evict``)."""

    def __init__(self):
        self._lock = threading.Lock()
        self._timings: dict[tuple, _Timing] = {}
        self._counters: dict[tuple, int] = {}
        self.evictions = 0
        self._san_tracker = _san_track("device_timing")

    def should_sample(self, name: str, sig: tuple) -> tuple[bool, bool]:
        """(timed, cold) decision for the dispatch about to run: first
        dispatch of a (kernel, sig) is always timed as the cold sample,
        the second always timed warm, then every 1/rate-th."""
        rate = timing_rate()
        if rate <= 0.0:
            return False, False
        key = (name, "×".join(sig))
        with self._lock:
            _san_note(self._san_tracker, True)
            n = self._counters.get(key, 0) + 1
            self._counters[key] = n
        if n == 1:
            return True, True
        if n == 2:
            return True, False
        interval = max(1, round(1.0 / rate))
        return n % interval == 0, False

    def observe(self, name: str, sig: tuple, seconds: float,
                cold: bool = False) -> None:
        key = (name, "×".join(sig))
        with self._lock:
            _san_note(self._san_tracker, True)
            t = self._timings.get(key)
            if t is None:
                t = self._timings[key] = _Timing()
                # counters share this lock: dropping an evicted key's
                # counter OUTSIDE it would race a concurrent
                # should_sample re-creating the key and delete the
                # fresh count (a phantom second cold sample)
                for old in evict_past_cap(self._timings,
                                          registry_cap(), key):
                    self.evictions += 1
                    self._counters.pop(old, None)
            else:
                # LRU touch: re-insert at the back so the cap evicts
                # the coldest (kernel, sig), never the hottest
                self._timings[key] = self._timings.pop(key)
            t.observe(float(seconds), cold)
        m = _metrics()
        if m is not None and not cold:
            m.device_kernel_seconds.labels(name).observe(float(seconds))

    def evict(self, key: tuple) -> None:
        """Registry-eviction hook: (name, sig tuple) keys from the
        ledger's KernelRegistry cap drop their timing rows too."""
        k = (key[0], "×".join(key[1]))
        with self._lock:
            _san_note(self._san_tracker, True)
            self._timings.pop(k, None)
            self._counters.pop(k, None)

    def summaries(self) -> dict[tuple, dict]:
        with self._lock:
            _san_note(self._san_tracker, False)
            return {k: t.summary() for k, t in self._timings.items()}

    def totals(self) -> dict:
        with self._lock:
            _san_note(self._san_tracker, False)
            return {"kernels_measured": len(self._timings),
                    "warm_samples": sum(t.count
                                        for t in self._timings.values()),
                    "evictions": self.evictions}

    def clear(self) -> None:
        with self._lock:
            self._timings.clear()
            self._counters.clear()
            self.evictions = 0


TIMING = DeviceTiming()


def block_ready(out) -> bool:
    """Block until ``out`` (any pytree of device arrays) is computed —
    the sampled-dispatch sync. Never raises: a backend losing the race
    mid-sync must cost a sample, not the dispatch that produced it.
    Returns False on a failed sync so the caller SKIPS the observation
    — an unsynced duration is enqueue time, and recording it would
    poison the percentiles the divergence/bound_measured math reads."""
    try:
        import jax

        jax.block_until_ready(out)
        return True
    except Exception:
        return False


def measured_row(rec: dict, timing: dict | None) -> dict:
    """Join one kernel-registry record with its measured timing summary:
    achieved FLOP/s / bytes/s at the measured p50, the divergence ratio
    over the roofline model's predicted seconds, and the
    ``bound_measured`` re-classification — ``overhead_bound`` when the
    measured time is more than ``OVERHEAD_FACTOR``x what BOTH roofline
    terms predict (the model does not explain where the time goes),
    else whichever predicted term dominates."""
    out = {"kernel": rec.get("kernel"), "sig": rec.get("sig"),
           "dispatches": rec.get("dispatches"),
           "bound": rec.get("bound"),
           "bound_refined": rec.get("bound_refined"),
           "bound_measured": "unknown",
           "measured": timing or {}}
    p50 = (timing or {}).get("p50_seconds")
    if not p50 or p50 <= 0:
        return out
    flops = rec.get("flops") or 0.0
    nbytes = rec.get("bytes_accessed") or 0.0
    hbm = rec.get("est_hbm_bytes") or nbytes
    if flops:
        out["achieved_flops_per_s"] = round(flops / p50, 1)
    if nbytes:
        out["achieved_bytes_per_s"] = round(nbytes / p50, 1)
    if hbm:
        out["achieved_hbm_bytes_per_s"] = round(hbm / p50, 1)
    est = estimated_seconds(flops, hbm)
    if est and est > 0:
        out["est_seconds"] = round(est, 9)
        out["divergence"] = round(p50 / est, 4)
        pf, bw = _peaks()
        compute_t = float(flops) / pf
        mem_t = float(hbm) / bw
        if p50 > OVERHEAD_FACTOR * max(compute_t, mem_t):
            out["bound_measured"] = "overhead_bound"
        else:
            out["bound_measured"] = ("compute_bound"
                                     if compute_t >= mem_t
                                     else "hbm_bound")
    return out


def measured_table() -> list[dict]:
    """Every registered kernel joined with its measured stats, most
    measured-time-covered first — the ``/devicez`` kernel table."""
    from . import ledger as _ledger

    summaries = TIMING.summaries()
    rows = []
    for rec in _ledger.REGISTRY.snapshot():
        t = summaries.get((rec.get("kernel"), rec.get("sig")))
        rows.append(measured_row(rec, t))
    rows.sort(key=lambda r: -(r["measured"].get("p50_seconds") or 0.0)
              * (r.get("dispatches") or 0))
    return rows


# --------------------------------------------------------- device memory


def memory_snapshot() -> dict:
    """``memory_stats()`` of the first device, degrade-tolerant: backends
    that return None or raise (the CPU backend) yield
    ``{"available": False}`` — the ``/devicez`` memory block and every
    sampler must keep serving through that, never crash or 500."""
    try:
        import jax

        dev = jax.devices()[0]
        stats = dev.memory_stats()
    except Exception as e:
        return {"available": False,
                "error": f"{type(e).__name__}: {e}"[:200]}
    if not stats:
        return {"available": False,
                "reason": "backend returns no memory_stats"}
    out = {"available": True,
           "bytes_in_use": int(stats.get("bytes_in_use") or 0),
           "peak_bytes_in_use": int(stats.get("peak_bytes_in_use") or 0)}
    limit = int(stats.get("bytes_limit") or 0)
    if limit:
        out["bytes_limit"] = limit
        out["in_use_fraction"] = round(out["bytes_in_use"] / limit, 4)
    return out


def series_bytes_in_use() -> float:
    """Series-ring collector (obs/slo.SERIES): raises when the backend
    has no memory counters so the sample records None — the ring's
    contract for a failing collector (the thread never dies)."""
    snap = memory_snapshot()
    if not snap.get("available"):
        raise RuntimeError("device memory_stats unavailable")
    return float(snap["bytes_in_use"])


def gauge_bytes_in_use() -> float:
    """Prometheus set_function callback — scrape callbacks must never
    raise, so unavailable degrades to 0.0 (the /devicez block is the
    authoritative 'unavailable vs empty' surface)."""
    try:
        snap = memory_snapshot()
        return float(snap.get("bytes_in_use") or 0.0) \
            if snap.get("available") else 0.0
    except Exception:
        return 0.0


# ------------------------------------------------ resident-buffer registry


class ResidentRegistry:
    """Live gauge of device-resident buffers, weakref-keyed by OWNER
    (an engine or a log): ``track(owner, kind, nbytes)`` upserts the
    owner's ``kind`` row, and the row disappears with the owner — the
    registry cannot outlive-leak a dead engine's bytes (RT011 by
    construction, no cap needed)."""

    def __init__(self):
        self._lock = threading.Lock()
        self._by_owner: "weakref.WeakKeyDictionary" = \
            weakref.WeakKeyDictionary()
        self._san_tracker = _san_track("device_resident")

    def track(self, owner, kind: str, nbytes: int, **info) -> None:
        """Upsert ``owner``'s ``kind`` buffer at ``nbytes``. Never
        raises: an owner that doesn't support weakrefs just isn't
        tracked (the gauge is best-effort observability)."""
        row = {"kind": str(kind), "nbytes": max(0, int(nbytes)),
               "owner": type(owner).__name__,
               "unix": round(time.time(), 3), **info}
        try:
            with self._lock:
                _san_note(self._san_tracker, True)
                self._by_owner.setdefault(owner, {})[str(kind)] = row
        except TypeError:
            pass

    def drop(self, owner, kind: str | None = None) -> None:
        try:
            with self._lock:
                _san_note(self._san_tracker, True)
                rows = self._by_owner.get(owner)
                if rows is None:
                    return
                if kind is None:
                    del self._by_owner[owner]
                else:
                    rows.pop(str(kind), None)
        except TypeError:
            pass

    def snapshot(self) -> dict:
        with self._lock:
            _san_note(self._san_tracker, False)
            rows = [dict(r) for rows in self._by_owner.values()
                    for r in rows.values()]
        rows.sort(key=lambda r: -r["nbytes"])
        return {"buffers": rows,
                "total_bytes": sum(r["nbytes"] for r in rows)}

    def clear(self) -> None:
        with self._lock:
            self._by_owner = weakref.WeakKeyDictionary()


RESIDENT = ResidentRegistry()


def nbytes_tree(obj) -> int:
    """Recursive ``nbytes`` sum over a tuple/list tree of (device or
    host) arrays — what the engines account their resident state at."""
    if obj is None:
        return 0
    if isinstance(obj, (list, tuple)):
        return sum(nbytes_tree(x) for x in obj)
    return int(getattr(obj, "nbytes", 0) or 0)


# ------------------------------------------------- compile observability


_COMPILE_LOCK = threading.Lock()
_COMPILES: dict[str, dict] = {}
_COMPILE_RING: deque = deque(maxlen=COMPILE_RING)


def note_compile(kernel: str, sig: str, seconds: float) -> None:
    """One observed ``lower().compile()`` from the kernel registry's
    harvest path (which shares the in-memory XLA cache with the dispatch
    path, so every NEW (kernel, shapes) program lands exactly once
    here). Coverage caveat: the AOT harvest IS the observation point —
    under ``RTPU_LEDGER_XLA=0`` (or on backends whose analyses probe
    unavailable) there is no AOT compile to observe and this plane goes
    dark along with the estimates (documented in OBSERVABILITY.md
    "Device runtime"). Never raises."""
    try:
        now = time.time()
        with _COMPILE_LOCK:
            rec = _COMPILES.get(kernel)
            if rec is None:
                rec = _COMPILES[kernel] = {
                    "compiles": 0, "seconds": 0.0,
                    "last_sig": "", "last_unix": 0.0}
            rec["compiles"] += 1
            rec["seconds"] = round(rec["seconds"] + float(seconds), 4)
            rec["last_sig"] = str(sig)
            rec["last_unix"] = round(now, 3)
            _COMPILE_RING.append({"kernel": kernel, "sig": str(sig),
                                  "seconds": round(float(seconds), 4),
                                  "unix": round(now, 3)})
        m = _metrics()
        if m is not None:
            m.compiles.labels(kernel).inc()
            m.compile_seconds.labels(kernel).inc(float(seconds))
    except Exception:
        pass


def compile_block() -> dict:
    """Per-kernel compile counts/seconds/last-shape-sig — the block
    ``/statusz.compile_caches`` embeds under ``kernels`` next to the
    lru factory stats."""
    with _COMPILE_LOCK:
        return {k: dict(v) for k, v in sorted(_COMPILES.items())}


def recent_compiles(n: int = 32) -> list[dict]:
    with _COMPILE_LOCK:
        snap = list(_COMPILE_RING)
    return snap[-max(0, int(n)):]


#: compile-storm detection window / threshold (the advisor rule's bar)
STORM_WINDOW_S = 60.0


def storm_threshold() -> int:
    """``RTPU_ADVISOR_COMPILE_STORM`` — compile events inside the last
    ``STORM_WINDOW_S`` seconds that count as a storm (default 16; a
    healthy warm-up compiles a handful, shape-diverse request traffic
    recompiling under load hits tens)."""
    try:
        return max(1, int(os.environ.get("RTPU_ADVISOR_COMPILE_STORM",
                                         16)))
    except ValueError:
        return 16


def compile_storm() -> dict:
    """The request-path compile-storm signal: how many compiles (and
    how many DISTINCT shape sigs) landed inside the detection window."""
    cutoff = time.time() - STORM_WINDOW_S
    with _COMPILE_LOCK:
        recent = [e for e in _COMPILE_RING if e["unix"] >= cutoff]
    return {
        "window_seconds": STORM_WINDOW_S,
        "threshold": storm_threshold(),
        "events_in_window": len(recent),
        "distinct_sigs_in_window": len({(e["kernel"], e["sig"])
                                        for e in recent}),
        "seconds_in_window": round(sum(e["seconds"] for e in recent), 4),
        "storm": len(recent) >= storm_threshold(),
    }


def clear_compiles() -> None:
    with _COMPILE_LOCK:
        _COMPILES.clear()
        _COMPILE_RING.clear()
        for rec in _JAX_BUILDS.values():
            rec["count"], rec["seconds"] = 0, 0.0
        _JAX_FUNS.clear()
        _JAX_CACHE.update(hits=0, misses=0)


# ----------------------------------------- programs JAX builds (listener)

#: jax.monitoring duration event -> the stage it times. JAX emits these
#: wherever it traces a function to a jaxpr, lowers a jaxpr to an MLIR
#: module, and asks the backend (or the persistent cache) for an
#: executable — for every jit object, instrumented or not.
_JAX_STAGES = {
    "/jax/core/compile/jaxpr_trace_duration": "trace",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower",
    "/jax/core/compile/backend_compile_duration": "backend_compile",
}
_JAX_CACHE_EVENTS = {
    "/jax/compilation_cache/cache_hits": "hits",
    "/jax/compilation_cache/cache_misses": "misses",
}
_JAX_BUILDS = {stage: {"count": 0, "seconds": 0.0}
               for stage in _JAX_STAGES.values()}
_JAX_FUNS: dict[str, float] = {}
_JAX_FUNS_CAP = 256
_JAX_CACHE = {"hits": 0, "misses": 0}
_JAX_WATCHING = [False]


def _on_jax_duration(event, duration, **kw) -> None:
    stage = _JAX_STAGES.get(event)
    if stage is None or not _TRACER.enabled:
        return
    try:
        fun = str(kw.get("fun_name") or "")
        secs = float(duration)
        _TRACER.complete("xla." + stage, secs, fun=fun)
        with _COMPILE_LOCK:
            rec = _JAX_BUILDS[stage]
            rec["count"] += 1
            rec["seconds"] += secs
            _JAX_FUNS[fun] = _JAX_FUNS.get(fun, 0.0) + secs
            if len(_JAX_FUNS) > _JAX_FUNS_CAP:
                # dynamic function names must not grow the table: keep
                # the half with the most seconds
                keep = sorted(_JAX_FUNS.items(),
                              key=lambda kv: -kv[1])[:_JAX_FUNS_CAP // 2]
                _JAX_FUNS.clear()
                _JAX_FUNS.update(keep)
    except Exception:   # a listener inside jax's compile path never raises
        pass


def _on_jax_event(event, **kw) -> None:
    which = _JAX_CACHE_EVENTS.get(event)
    if which is None or not _TRACER.enabled:
        return
    with _COMPILE_LOCK:
        _JAX_CACHE[which] += 1


def watch_jax_builds() -> bool:
    """Register the two ``jax.monitoring`` listeners, once a process.
    False where jax (or its monitoring module) is missing."""
    with _COMPILE_LOCK:
        if _JAX_WATCHING[0]:
            return True
        try:
            from jax import monitoring
        except Exception:
            return False
        monitoring.register_event_duration_secs_listener(_on_jax_duration)
        monitoring.register_event_listener(_on_jax_event)
        _JAX_WATCHING[0] = True
        return True


def jax_builds_block() -> dict:
    """The ``jax`` block of ``/statusz.compile_caches``: what JAX itself
    traced, lowered and had compiled (or read from the persistent cache)
    while the flight recorder was on — counts and seconds by stage, and
    the ten function names with most seconds."""
    with _COMPILE_LOCK:
        stages = {st: {"count": r["count"],
                       "seconds": round(r["seconds"], 4)}
                  for st, r in _JAX_BUILDS.items()}
        funs = sorted(_JAX_FUNS.items(), key=lambda kv: -kv[1])[:10]
        cache = dict(_JAX_CACHE)
    return {"watching": _JAX_WATCHING[0], "stages": stages,
            "persistent_cache": cache,
            "top_funs": [{"fun": f, "seconds": round(s, 4)}
                         for f, s in funs]}


# ------------------------------------------------------------- surfaces


def status_block() -> dict:
    """The compact ``device`` block /statusz embeds (what /clusterz
    federates per process): counts and gauges only, never the tables."""
    mem = memory_snapshot()
    storm = compile_storm()
    return {
        "timing": {"rate": timing_rate(), **TIMING.totals()},
        "memory": mem if mem.get("available")
        else {"available": False},
        "resident_bytes": RESIDENT.snapshot()["total_bytes"],
        "compile": {"kernels": len(compile_block()),
                    "events_in_window": storm["events_in_window"],
                    "storm": storm["storm"]},
    }


def devicez() -> dict:
    """The full ``/devicez`` document: the measured kernel table
    (estimates joined with sampled timings, divergence, and the
    measured re-classification), the device-memory snapshot (or its
    honest degrade), the resident-buffer registry, and recent compile
    events with the storm signal."""
    mem = memory_snapshot()
    return {
        "timing": {
            "rate": timing_rate(),
            **TIMING.totals(),
            "semantics": (
                "sampled dispatches block until ready and record wall "
                "seconds — dispatch overhead and pipeline drain "
                "included; divergence = measured p50 / roofline-model "
                "predicted seconds; bound_measured is overhead_bound "
                "when measured exceeds "
                f"{OVERHEAD_FACTOR:.0f}x both model terms"),
            "kernels": measured_table(),
        },
        "memory": mem if mem.get("available") else
        {"available": False, "detail": mem,
         "note": "memory: unavailable — backend exposes no "
                 "memory_stats; timing and compile planes unaffected"},
        "resident": RESIDENT.snapshot(),
        "compile": {
            **compile_storm(),
            "kernels": compile_block(),
            "recent": recent_compiles(32),
        },
    }


def advisor_signals() -> dict:
    """The ``device`` block of the advisor's signals dict
    (obs/advisor.gather_signals): measured kernel rows, the memory
    snapshot, and the compile-storm block."""
    return {"timing": measured_table(), "memory": memory_snapshot(),
            "compile": compile_storm()}


def clear() -> None:
    """Reset every device-plane table (tests + bench arms)."""
    TIMING.clear()
    RESIDENT.clear()
    clear_compiles()


watch_jax_builds()

_device_dump = os.environ.get("RTPU_DEVICE_DUMP")
if _device_dump:
    from . import exitdump as _exitdump

    def _dump_devicez(path=_device_dump):
        with open(path, "w") as f:
            json.dump(devicez(), f)

    _exitdump.register("device", _dump_devicez)
