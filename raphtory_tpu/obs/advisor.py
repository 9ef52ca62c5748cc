"""Advisor — the rule-driven judgment layer over the measurement planes.

PRs 6/9/10 measure everything (per-kernel roofline classes, phase
splits, queue wait, collective skew/barrier waits, watermark lag,
fold-cache hit rates, per-tenant cost) but every knob is still hand-set
and every diagnosis is still an operator joining surfaces in their
head. The advisor does the join: a periodic evaluator reads ONLY
existing surfaces and emits evidence-linked findings with concrete knob
recommendations — ``/advisez`` renders them, ``/statusz`` embeds the
compact block, and ``/clusterz`` federation lets one process advise on
the whole mesh.

Design rules:

* **Strictly read-only.** No code path here mutates a knob, an env var,
  or any engine state — this is the evidence-to-decision bridge the
  adaptive runtime (ROADMAP item 4) will later wire to actuators; until
  then a wrong recommendation costs an operator a shrug, not an outage.
  (The read-only property is regression-tested: a tick must leave
  ``os.environ`` unchanged.)
* **Machine-readable findings.** Every finding carries a stable
  ``rule_id``, the ``knob`` it names, and an ``evidence`` block with
  the metric values, trace-id exemplars, and ledger rows that justify
  it — a future actuator (or an operator's jq) needs no prose parsing.
* **Quiet by default.** Rules demand BOTH a dominance signal and an
  evidence floor before firing; a healthy process emits zero findings
  (CI asserts exactly that on every advisor bench run).
* **RT009-clean.** The periodic thread follows the SeriesRing
  generation-stop pattern; rule evaluation and every surface read
  happen OUTSIDE the advisor's own lock, and the federation path does
  its network I/O before any lock is touched.

Knobs
-----
* ``RTPU_ADVISOR`` — the periodic evaluator (default on; the
  ``advisor_overhead`` bench's off arm).
* ``RTPU_ADVISOR_INTERVAL_S`` — tick period (default 30 s).
* ``RTPU_ADVISOR_STALE_S`` — watermark-lag floor (seconds) for the
  staleness + straggler rules (default 30; the cluster smoke lowers it
  to fire the straggler rule in CI time).
"""

from __future__ import annotations

import os
import threading
import time
from collections import deque

from ..analysis.sanitizer import (note_shared as _san_note,
                                  track_shared as _san_track)
from . import budget as _budget
from . import device as _device
from . import freshness as _freshness
from . import journal as _journal
from . import workload as _workload
from .slo import _metrics
from .trace import TRACER

DEFAULT_INTERVAL_S = 30.0
DEFAULT_STALE_S = 30.0
#: finding-history ring bound (RT011: a misbehaving deployment must not
#: grow the advisor's memory with every tick)
HISTORY = 64
#: recent completed-query ledgers a tick reasons over
QUERY_WINDOW = 32
#: rules judged only on a FEDERATED pass (they read /clusterz data);
#: a local tick has no evidence about mesh state, so it carries the
#: last federated verdict instead of zeroing it — otherwise every
#: background tick would clear a live straggler finding and the next
#: federated pass would re-emit it as fresh (flapping gauges + history)
CLUSTER_RULES = frozenset({"cluster-straggler", "shard-skew"})
#: how long a carried cluster finding stays credible without a fresh
#: federated pass confirming it
CLUSTER_RETAIN_S = 600.0


def enabled() -> bool:
    """Re-read per tick so the bench A/B (and operators) can flip the
    advisor without a restart."""
    return os.environ.get("RTPU_ADVISOR", "1") not in ("", "0", "false")


def interval_s() -> float:
    try:
        v = float(os.environ.get("RTPU_ADVISOR_INTERVAL_S", "")
                  or DEFAULT_INTERVAL_S)
        return max(0.05, v)
    except ValueError:
        return DEFAULT_INTERVAL_S


def stale_s() -> float:
    try:
        v = float(os.environ.get("RTPU_ADVISOR_STALE_S", "")
                  or DEFAULT_STALE_S)
        return max(0.1, v)
    except ValueError:
        return DEFAULT_STALE_S


def _finding(rule_id: str, summary: str, knob: str, recommendation: str,
             evidence: dict, severity: str = "advice") -> dict:
    return {"rule_id": rule_id, "severity": severity, "summary": summary,
            "knob": knob, "recommendation": recommendation,
            "evidence": evidence, "unix": round(time.time(), 3)}


#: the advisor's OWN recent-query ring, fed by the jobs layer BEFORE the
#: RTPU_LEDGER publication gate — /costz's ring (obs/ledger._RECENT) is
#: a ledger surface and rightly goes silent under RTPU_LEDGER=0, but the
#: advisor's queue/wall evidence is jobs-layer data that survives that
#: mode (the same contract the SLO histograms and workload accounts
#: follow). Bounded; engine phases are simply absent when nothing
#: measures them, so the phase-split rules stay honestly quiet.
_QUERIES: deque = deque(maxlen=QUERY_WINDOW * 2)
_QUERIES_LOCK = threading.Lock()


def note_query(row: dict) -> None:
    """Record one completed job's ledger snapshot for rule evaluation.
    Called by ``jobs/manager._publish_ledger`` whatever ``RTPU_LEDGER``
    says (gated only on the advisor's own knob); never raises."""
    with _QUERIES_LOCK:
        _QUERIES.append(row)


def recent_query_rows(n: int = QUERY_WINDOW) -> list[dict]:
    with _QUERIES_LOCK:
        snap = list(_QUERIES)
    return snap[-max(0, int(n)):]


def _phase_split(queries: list) -> tuple[dict, float]:
    """Aggregate phase seconds over recent ledgers + their total
    (queue_wait excluded — it is an admission signal, not a phase)."""
    split: dict[str, float] = {}
    for q in queries:
        for ph, sec in q.get("phase_seconds", {}).items():
            split[ph] = split.get(ph, 0.0) + float(sec)
    return split, sum(split.values())


def _exemplars(queries: list, n: int = 3) -> list:
    """The slowest recent queries as evidence rows (id + trace id)."""
    rows = sorted(queries, key=lambda q: -q.get("wall_seconds", 0.0))
    return [{"query_id": q.get("query_id"),
             "algorithm": q.get("algorithm"),
             "tenant": q.get("tenant"),
             "trace_id": q.get("trace_id"),
             "wall_seconds": q.get("wall_seconds")} for q in rows[:n]]


# ------------------------------------------------------------- the rules
#
# Each rule is a pure function signals-dict -> finding-or-None. The
# signals dict is assembled by gather_signals(); tests feed synthetic
# dicts. Threshold constants live beside their rule. docs/OBSERVABILITY
# "Advisor" documents the catalogue row-for-row from RULES below.


def rule_fold_stall_workers(sig: dict) -> dict | None:
    """The host fold dominates the phase split while RTPU_FOLD_WORKERS
    is pinned below the cores available — the docs/OBSERVABILITY worked
    walkthrough (mis-set RTPU_FOLD_WORKERS=1 on a 4-core box)."""
    raw = sig.get("env", {}).get("RTPU_FOLD_WORKERS")
    if raw is None:
        return None            # auto-sized: nothing to advise
    try:
        workers = int(raw)
    except ValueError:
        return None
    auto = max(2, (sig.get("cpu_count") or 2) // 2)
    if workers >= auto:
        return None
    queries = sig.get("queries", [])
    split, total = _phase_split(queries)
    fold = split.get("fold", 0.0)
    if len(queries) < 4 or total < 1.0 or fold < 0.5 * total:
        return None
    return _finding(
        "fold-stall-raise-workers",
        f"the host fold is {fold / total:.0%} of attributed time but "
        f"RTPU_FOLD_WORKERS={workers} caps the fold pool below the "
        f"{auto} workers this host would auto-size",
        "RTPU_FOLD_WORKERS",
        f"raise RTPU_FOLD_WORKERS toward {auto} (or unset for auto); "
        "RTPU_PREFETCH_DEPTH queues folds ahead of dispatch",
        {"fold_fraction": round(fold / total, 3),
         "phase_seconds": {p: round(s, 4) for p, s in split.items()},
         "fold_workers": workers, "auto_workers": auto,
         "fold_stall_seconds": sig.get("transfer", {}).get(
             "fold_stall_seconds"),
         "queries": _exemplars(queries)})


def rule_queue_burn_shed(sig: dict) -> dict | None:
    """Queue wait is material while some SLO budget is burning — the
    admission-control signal pair. Recommends shedding the top-cost
    tenant BY NAME with its ledger rows as the shed-this evidence.
    Since the serving scheduler landed (jobs/scheduler.py) this
    recommendation has an actuator: ``RTPU_ADMISSION=1`` sheds exactly
    this tenant's new requests with 429s while the budget burns."""
    bud = sig.get("budget") or {}
    if bud.get("grade") != "burning":
        return None
    queries = sig.get("queries", [])
    waits = sorted(q.get("queue_wait_seconds", 0.0) for q in queries)
    if len(waits) < 4:
        return None
    p99 = waits[min(len(waits) - 1, int(0.99 * len(waits)))]
    if p99 < 0.1:
        return None            # budget burns for another reason
    top = (sig.get("workload_top") or [{}])[0]
    if not top.get("tenant"):
        return None
    burning = [t for t in bud.get("targets", [])
               if t.get("grade") == "burning"]
    return _finding(
        "queue-burn-shed-top-tenant",
        f"queue-wait p99 {p99:.3f}s while "
        f"{[t['algorithm'] for t in burning]} burn their error budget; "
        f"tenant {top['tenant']!r} holds the top attributed cost",
        "RTPU_ADMISSION",
        f"shed tenant {top['tenant']!r}: set RTPU_ADMISSION=1 so the "
        "serving scheduler sheds its new requests with 429s "
        "automatically (jobs/scheduler.py), or kill its jobs via "
        "/KillTask until the fast burn drops below 1",
        {"queue_wait_p99_seconds": round(p99, 4),
         "burning_targets": burning,
         "top_tenant": {
             "tenant": top.get("tenant"),
             "cost_seconds": top.get("cost_seconds"),
             "queue_wait_seconds": top.get("queue_wait_seconds"),
             "queries_total": top.get("queries_total"),
             "top_queries": top.get("top_queries")},
         "queries": _exemplars(queries)},
        severity="warning")


def rule_h2d_stall_depth(sig: dict) -> dict | None:
    """Transfer stalls (staging + wire waits) rival the useful phase
    time — the H2D window is too shallow for this link. Stall and phase
    time come from the SAME recent-query window: the process-lifetime
    transfer totals would keep a day-1 stall backlog firing this rule
    forever on a long-since-healthy server."""
    queries = sig.get("queries", [])
    stall = 0.0
    for q in queries:
        stalls = (q.get("h2d") or {}).get("stall_seconds") or {}
        stall += sum(float(s or 0.0) for s in stalls.values())
    if stall < 2.0:
        return None
    split, total = _phase_split(queries)
    if len(queries) < 4 or stall < 0.3 * max(total, 1e-9):
        return None
    depth = sig.get("env", {}).get("RTPU_TRANSFER_DEPTH")
    tr = sig.get("transfer") or {}
    return _finding(
        "h2d-stall-raise-depth",
        f"{stall:.1f}s of H2D stage/wire stall against {total:.1f}s of "
        "attributed phase time over the recent-query window — the "
        "in-flight upload window is the bottleneck",
        "RTPU_TRANSFER_DEPTH",
        f"raise RTPU_TRANSFER_DEPTH (currently {depth or 'default 2'})",
        {"stall_seconds": round(stall, 4),
         "window_queries": len(queries),
         "process_stall_seconds": tr.get("stall_seconds"),
         "bytes_shipped": tr.get("bytes_shipped"),
         "phase_seconds_total": round(total, 4),
         "queries": _exemplars(queries)})


def rule_fold_cache_thrash(sig: dict) -> dict | None:
    """The cross-request fold cache is evicting while missing more than
    it hits — the bound is too small for the working set."""
    fc = sig.get("fold_cache") or {}
    hits = int(fc.get("hits") or 0)
    misses = int(fc.get("misses") or 0)
    if (int(fc.get("evictions") or 0) < 10 or hits + misses < 20
            or hits >= misses):
        return None
    return _finding(
        "fold-cache-thrash",
        f"fold cache evicted {fc['evictions']} entries with a "
        f"{hits / (hits + misses):.0%} hit rate — the working set no "
        "longer fits RTPU_FOLD_CACHE_MB",
        "RTPU_FOLD_CACHE_MB",
        "raise RTPU_FOLD_CACHE_MB (bytes in use: "
        f"{fc.get('bytes')}/{fc.get('max_bytes')})",
        {"fold_cache": {k: fc.get(k) for k in
                        ("hits", "misses", "evictions", "bytes",
                         "max_bytes", "entries")}})


def rule_fold_cache_refused(sig: dict) -> dict | None:
    """The fold cache refused an entry larger than its whole bound. A
    refusal evicts nothing, so ``fold-cache-thrash`` never sees it: one
    refused checkpoint is enough, because every later request over that
    log finds none and folds from the log's first event."""
    fc = sig.get("fold_cache") or {}
    refused = int(fc.get("refused") or 0)
    if refused <= 0:
        return None
    return _finding(
        "fold-cache-refused",
        f"a fold checkpoint or payload of {fc.get('refused_bytes')} bytes "
        f"exceeds RTPU_FOLD_CACHE_MB ({fc.get('max_bytes')} bytes; "
        f"{refused} refused): every request folds the log from its start",
        "RTPU_FOLD_CACHE_MB",
        "raise RTPU_FOLD_CACHE_MB above the refused size (several "
        "checkpoints of it, to keep one per chunk boundary)",
        {"fold_cache": {k: fc.get(k) for k in
                        ("refused", "refused_bytes", "max_bytes", "bytes",
                         "entries", "hits", "misses")}})


def rule_watermark_stale(sig: dict) -> dict | None:
    """A live source has held the safe-time fence still past the
    staleness bar — every exact query behind the fence is waiting on it
    (the watermark-lag staleness SLO, PAPERS.md pseudo-streaming)."""
    lag = sig.get("watermark_lag_seconds")
    if lag is None or lag < stale_s():
        return None
    return _finding(
        "watermark-stale",
        f"the global safe time has not advanced for {lag:.1f}s "
        f"(bar: {stale_s():.0f}s) — a live source is stalled",
        "sources",
        "find the stalled source in the watermark snapshot and fix or "
        "finish it; exact-time queries block on this fence",
        {"watermark_lag_seconds": round(lag, 3),
         "watermark_sources": sig.get("watermark_sources"),
         "stale_bar_seconds": stale_s()},
        severity="warning")


# ---- freshness rules: evaluate over the obs/freshness plane ----

#: staged-backlog fraction of the queue bound past which the pipeline
#: writer has lost the race (the saturation oracle the ingest bench
#: reads the same way)
INGEST_BACKLOG_FRAC = 0.8
#: evidence floor before the out-of-order rule may speak
OOO_MIN_EVENTS = 256


def rule_ingest_backlog(sig: dict) -> dict | None:
    """Some staged parse→append queue is pinned near ITS bound — the
    writer has lost the race with the sources and backpressure is
    throttling ingest (the paper's §6.1 saturation oracle, now judged
    continuously instead of only in the bench). Saturation is judged
    per queue: summing backlogs against the max bound would both
    false-fire (two half-full queues) and mask (a small queue behind a
    big bound)."""
    fr = sig.get("freshness") or {}
    queues = fr.get("staged_queues")
    if queues is None:
        # older/synthetic signal shape: fall back to the totals (both
        # keys guarded — the per-queue loop below skips None backlogs)
        queues = ([{"backlog_events": fr.get("backlog_events"),
                    "queue_max_events": fr.get("queue_max_events")}]
                  if fr.get("queue_max_events") else [])
    worst = None
    for q in queues:
        b, qmax = q.get("backlog_events"), q.get("queue_max_events")
        if not qmax or b is None:
            continue
        if worst is None or b / qmax > worst[0] / worst[1]:
            worst = (b, qmax)
    if worst is None or worst[0] < INGEST_BACKLOG_FRAC * worst[1]:
        return None
    backlog, qmax = worst
    srcs = fr.get("sources") or {}
    return _finding(
        "ingest-backlog",
        f"staged ingest backlog at {backlog}/{qmax} events "
        f"({backlog / qmax:.0%} of the queue bound) — the append writer "
        "is saturated and backpressure is throttling every source",
        "RAPHTORY_TPU_INGEST_QUEUE_EVENTS",
        "the writer, not the queue, is the bottleneck: shed or slow "
        "sources, or shard ingest (ingestion/router.py); raising the "
        "queue bound only buys latency, not throughput",
        {"backlog_events": backlog, "queue_max_events": qmax,
         "updates_per_s_by_source": {n: s.get("updates_per_s")
                                     for n, s in srcs.items()},
         "queryable_lag_seconds": fr.get("queryable_lag_seconds")},
        severity="warning")


def rule_ooo_excess(sig: dict) -> dict | None:
    """A source's observed out-of-orderness EXCEEDS its declared
    ``disorder`` bound — the watermark promise ("no event <= w will ever
    be appended") is at risk: an exact view served at the fence may have
    missed late events. The commutative store applies them correctly
    once they land, but 'exact' answers served in between were not."""
    srcs = (sig.get("freshness") or {}).get("sources") or {}
    worst = None
    for name, s in srcs.items():
        if s.get("events", 0) < OOO_MIN_EVENTS:
            continue
        excess = s.get("ooo_max", 0) - max(0, s.get("disorder_bound", 0))
        if excess > 0 and (worst is None or excess > worst[1]):
            worst = (name, excess, s)
    if worst is None:
        return None
    name, excess, s = worst
    return _finding(
        "out-of-order-excess",
        f"source {name!r} emitted events up to {s['ooo_max']} event-time "
        f"units behind its high water, {excess} past its declared "
        f"disorder bound of {s['disorder_bound']} — watermarks promised "
        "completeness they did not have",
        "source.disorder",
        f"raise {name!r}'s declared disorder bound to at least "
        f"{s['ooo_max']} (the watermark then holds back far enough), or "
        "fix the upstream ordering; /freshz carries the full "
        "out-of-order distance histogram",
        {"source": name, "ooo_max": s.get("ooo_max"),
         "declared_disorder": s.get("disorder_bound"),
         "ooo_events": s.get("ooo_events"), "events": s.get("events")},
        severity="warning")


def rule_freshness_burn(sig: dict) -> dict | None:
    """Some RTPU_FRESH_TARGET staleness budget is burning — live
    results are sustainably older than the operator promised. The
    evidence names the stalled ingredient: backlog, queryable lag, or a
    stalled watermark."""
    fr = sig.get("freshness") or {}
    bud = fr.get("budget") or {}
    if bud.get("grade") != "burning":
        return None
    burning = [t for t in bud.get("targets", [])
               if t.get("grade") == "burning"]
    return _finding(
        "freshness-burn",
        f"staleness budgets burning for "
        f"{[t['algorithm'] for t in burning]}: live results are "
        "sustainably staler than RTPU_FRESH_TARGET promises",
        "RTPU_FRESH_TARGET",
        "find the slow ingredient: a stalled source (watermark "
        "snapshot), a saturated staged queue (backlog), or analytics "
        "that can't keep up with ingest (ROADMAP item 3's incremental "
        "live algorithms are the structural fix); or relax the target",
        {"burning_targets": burning,
         "staleness_p99_seconds": fr.get("staleness_p99_seconds"),
         "backlog_events": fr.get("backlog_events"),
         "queryable_lag_seconds": fr.get("queryable_lag_seconds"),
         "watermark_lag_seconds": sig.get("watermark_lag_seconds")},
        severity="warning")


# ---- device rules: evaluate over the obs/device measured plane ----

#: mutual-divergence band for the model-divergence rule: per-kernel
#: measured/predicted ratios spreading wider than this say the cost
#: model RANKS kernels wrongly. Deliberately scale-invariant — the
#: platform peaks are order-of-magnitude anchors, so an absolute
#: measured-vs-predicted gap is expected (and constant-ratio gaps keep
#: the bound classification correct); inconsistent ratios do not.
DIVERGENCE_BAND = 16.0
#: measured evidence floors before the divergence rule may speak
DIVERGENCE_MIN_SAMPLES = 4
DIVERGENCE_MIN_KERNELS = 2


def divergence_band() -> float:
    try:
        v = float(os.environ.get("RTPU_ADVISOR_DIVERGENCE", "")
                  or DIVERGENCE_BAND)
        return max(1.5, v)
    except ValueError:
        return DIVERGENCE_BAND


def rule_model_divergence(sig: dict) -> dict | None:
    """Per-kernel measured-vs-predicted ratios are mutually inconsistent
    past the band — the roofline/traffic model mis-RANKS kernels, so
    ``bound_refined`` (and any controller trusting it) should be
    distrusted until the model is recalibrated against the measured
    table. Scale-invariant on purpose: a constant absolute offset (rough
    platform anchors) never fires this."""
    rows = (sig.get("device") or {}).get("timing") or []
    rated = {}
    for r in rows:
        m = r.get("measured") or {}
        # overhead_bound rows are excluded: when dispatch overhead
        # dominates (small kernels, CPU rigs) the ratio judges the
        # overhead, not the model's ranking — including them would fire
        # this on every healthy host with mixed kernel sizes
        if (m.get("samples", 0) >= DIVERGENCE_MIN_SAMPLES
                and r.get("divergence")
                and r.get("bound_measured") != "overhead_bound"):
            rated[f"{r.get('kernel')}[{r.get('sig')}]"] = \
                float(r["divergence"])
    if len(rated) < DIVERGENCE_MIN_KERNELS:
        return None
    worst = max(rated, key=rated.get)
    best = min(rated, key=rated.get)
    spread = rated[worst] / max(rated[best], 1e-12)
    if spread < divergence_band():
        return None
    return _finding(
        "device-model-divergence",
        f"measured/predicted kernel-seconds ratios spread {spread:.1f}x "
        f"across kernels (band: {divergence_band():.0f}x) — the cost "
        "model mis-ranks kernels; bound_refined is not trustworthy",
        "RTPU_LEDGER_RIDGE",
        "distrust bound_refined until recalibrated: check the measured "
        f"table on /devicez (worst {worst}, best {best}); set "
        "RTPU_LEDGER_RIDGE from measured achieved FLOP/s / bytes/s, or "
        "fix the traffic model for the out-of-band kernel",
        {"divergence_by_kernel": {k: round(v, 3)
                                  for k, v in sorted(rated.items())},
         "spread": round(spread, 3), "band": divergence_band(),
         "worst": worst, "best": best})


def rule_device_pressure(sig: dict) -> dict | None:
    """Device memory near its limit, OR a request-path compile storm
    (new shape sigs recompiling under load faster than they amortise) —
    either way the device runtime is under pressure and a knob exists."""
    dev = sig.get("device") or {}
    mem = dev.get("memory") or {}
    if mem.get("available") and mem.get("bytes_limit"):
        frac = mem["bytes_in_use"] / mem["bytes_limit"]
        if frac >= 0.9:
            return _finding(
                "device-pressure",
                f"device memory at {frac:.0%} of its "
                f"{mem['bytes_limit']} byte limit — the next allocation "
                "spills or OOMs",
                "RTPU_TILE_BUDGET_MB",
                "lower RTPU_TILE_BUDGET_MB (shrinks the columnar edge "
                "tile), or shed resident engines "
                "(see the /devicez resident registry for what is "
                "pinned)",
                {"memory": mem,
                 "resident_bytes": dev.get("resident_bytes")},
                severity="warning")
    comp = dev.get("compile") or {}
    if (comp.get("events_in_window", 0) >= comp.get(
            "threshold", _device.storm_threshold())
            and comp.get("distinct_sigs_in_window", 0)
            >= max(4, int(comp.get("threshold", 16)) // 4)):
        return _finding(
            "device-pressure",
            f"compile storm: {comp['events_in_window']} XLA compiles "
            f"({comp.get('distinct_sigs_in_window')} distinct shape "
            f"sigs) inside the last {comp.get('window_seconds')}s — "
            "request traffic is shape-diverse enough to recompile "
            "faster than programs amortise",
            "JAX_COMPILATION_CACHE_DIR",
            "bucket/pad request shapes upstream so distinct sigs "
            "collapse; the persistent compile cache "
            "(JAX_COMPILATION_CACHE_DIR, default <checkout>/.jax_cache) "
            "only spares the recompiles a restart would repeat; "
            "/devicez lists the recent compile events",
            {"compile": comp},
            severity="warning")
    return None


# ---- cluster rules: evaluate over the /clusterz processes dict ----


def _cluster_rows(cluster: dict | None) -> dict:
    procs = (cluster or {}).get("processes") or {}
    return {name: p for name, p in procs.items() if p.get("reachable")}


def rule_cluster_straggler(sig: dict) -> dict | None:
    """One process's watermark lag towers over the rest of the mesh —
    the straggler holding every fence-gated sweep back. Barrier waits
    ride along as corroborating evidence (in a cross-process collective
    the OTHER processes accumulate the wait)."""
    rows = _cluster_rows(sig.get("cluster"))
    lags = {n: float(p["watermark_lag_seconds"]) for n, p in rows.items()
            if p.get("watermark_lag_seconds") is not None}
    if len(lags) < 2:
        return None
    worst = max(lags, key=lags.get)
    others = [v for n, v in lags.items() if n != worst]
    if lags[worst] < stale_s() or \
            lags[worst] < 3.0 * (max(others) + 1.0):
        return None
    waits = {n: (p.get("collectives") or {}).get("barrier_wait_seconds")
             for n, p in rows.items()}
    return _finding(
        "cluster-straggler",
        f"{worst} lags the mesh: watermark stalled for "
        f"{lags[worst]:.1f}s while the rest sit at "
        f"{max(others):.1f}s or less",
        "cluster",
        f"inspect {worst} (its /statusz watermark sources and "
        "/profilez); a mesh sweep runs at the pace of this process",
        {"process": worst,
         "process_index": rows[worst].get("process_index"),
         "watermark_lag_by_process": {n: round(v, 3)
                                      for n, v in lags.items()},
         "barrier_wait_by_process": waits},
        severity="warning")


def rule_shard_skew(sig: dict) -> dict | None:
    """A shard's row count towers over the mean — power-law skew the
    static partition cannot balance; the sparse-collective route
    (PAPERS.md Sparse Allreduce) exists for exactly this shape."""
    rows = _cluster_rows(sig.get("cluster"))
    worst = None
    for name, p in rows.items():
        skew = (p.get("collectives") or {}).get("skew") or {}
        for kind, val in skew.items():
            # shard_skew() publishes {per_shard, max, mean, skew} rows;
            # tolerate a bare ratio too (synthetic test signals)
            s = val.get("skew") if isinstance(val, dict) else val
            if s is None:
                continue
            if worst is None or float(s) > worst[2]:
                worst = (name, kind, float(s))
    if worst is None or worst[2] < 4.0:
        return None
    name, kind, val = worst
    # route evidence: if the chooser is already taking the sparse route
    # (or frontier densities say it should), say so — the remediation
    # differs between "nothing to turn" and "let the sparse route absorb it"
    route_counts: dict[str, int] = {}
    density: dict[str, float] = {}
    for n, p in rows.items():
        coll = p.get("collectives") or {}
        for key, cnt in ((coll.get("route_table") or {}).get("counts")
                         or {}).items():
            route_counts[key] = route_counts.get(key, 0) + int(cnt)
        for key, d in (coll.get("frontier_density") or {}).items():
            density[key] = max(density.get(key, 0.0), float(d))
    sparse_taken = any(k.endswith("/sparse") for k in route_counts)
    sparse_fits = any(d < 1.0 / 3.0 for d in density.values())
    if sparse_taken:
        fix = ("the sparse frontier route is already absorbing the skew "
               "(docs/COMM.md): no knob re-balances the static vertex "
               "ranges")
    elif sparse_fits:
        fix = ("frontier density is under the sparse crossover — set "
               "RTPU_COMM_ROUTE=auto (or =sparse) so min-merge sweeps "
               "exchange compacted frontiers instead of dense state "
               "(docs/COMM.md)")
    else:
        fix = ("dense frontiers keep the sparse route out of crossover "
               "here (docs/COMM.md), and no knob re-balances the static "
               "vertex ranges: leave RTPU_COMM_ROUTE=auto")
    return _finding(
        "shard-skew",
        f"{name} reports {kind} partition skew {val:.1f}x (max/mean "
        "per-shard rows) — the hot shard serializes every superstep",
        "RTPU_COMM_ROUTE",
        fix,
        {"process": name, "kind": kind, "skew": round(val, 3),
         "route_counts": route_counts,
         "frontier_density": {k: round(v, 4) for k, v in density.items()},
         "skew_by_process": {n: (p.get("collectives") or {}).get("skew")
                             for n, p in rows.items()}})


#: the catalogue: (rule_id, fn, reads, one-line description) — /advisez
#: lists it and docs/OBSERVABILITY.md "Advisor" documents it verbatim
RULES = (
    ("fold-stall-raise-workers", rule_fold_stall_workers,
     "phase split + fold-pool sizing",
     "host fold dominates while RTPU_FOLD_WORKERS is pinned low"),
    ("queue-burn-shed-top-tenant", rule_queue_burn_shed,
     "queue-wait p99 + error budgets + workload accounts",
     "queue wait burns budget; names the top-cost tenant to shed"),
    ("h2d-stall-raise-depth", rule_h2d_stall_depth,
     "per-query H2D stalls + phase split (same recent window)",
     "H2D stage/wire stalls rival useful phase time"),
    ("fold-cache-thrash", rule_fold_cache_thrash,
     "fold-cache hit/miss/eviction stats",
     "fold cache evicts more than it serves"),
    ("fold-cache-refused", rule_fold_cache_refused,
     "fold-cache refused / refused_bytes against its bound",
     "a fold checkpoint larger than the whole cache: no request finds one"),
    ("watermark-stale", rule_watermark_stale,
     "watermark lag + source snapshot",
     "the safe-time fence stopped advancing past the staleness bar"),
    ("ingest-backlog", rule_ingest_backlog,
     "/freshz staged backlog vs the queue bound",
     "the parse→append queue is pinned: the writer lost the race"),
    ("out-of-order-excess", rule_ooo_excess,
     "/freshz per-source out-of-orderness vs the declared disorder",
     "observed disorder exceeds the bound the watermark promise rests "
     "on"),
    ("freshness-burn", rule_freshness_burn,
     "RTPU_FRESH_TARGET staleness budgets + /freshz evidence",
     "live results sustainably staler than the operator promised"),
    ("device-model-divergence", rule_model_divergence,
     "/devicez measured kernel table (sampled timings vs model)",
     "measured/predicted ratios mutually inconsistent past the band — "
     "distrust bound_refined"),
    ("device-pressure", rule_device_pressure,
     "/devicez memory snapshot + compile-storm window",
     "device memory near its limit, or a request-path compile storm"),
    ("cluster-straggler", rule_cluster_straggler,
     "/clusterz per-process watermark lag + barrier waits",
     "one process's lag towers over the mesh"),
    ("shard-skew", rule_shard_skew,
     "/clusterz per-process partition skew",
     "a hot shard serializes the collective supersteps"),
)


def evaluate_rules(signals: dict) -> list[dict]:
    """Run every rule over ``signals``; a crashing rule becomes zero
    findings (the advisor must never take a tick down), surfaced in the
    signals' ``rule_errors`` for the /advisez payload."""
    findings = []
    for rule_id, fn, _, _ in RULES:
        try:
            f = fn(signals)
        except Exception as e:   # noqa: BLE001 — advice must not crash
            signals.setdefault("rule_errors", []).append(
                f"{rule_id}: {type(e).__name__}: {e}"[:200])
            continue
        if f is not None:
            findings.append(f)
    return findings


def gather_signals(manager=None, cluster: dict | None = None) -> dict:
    """Assemble the signals dict from the live surfaces — every read
    goes through the owning surface's own lock; nothing here holds the
    advisor's. ``cluster`` is an already-fetched /clusterz document
    (the caller does the network I/O — never under a lock)."""
    sig: dict = {
        "queries": recent_query_rows(QUERY_WINDOW),
        "budget": _budget.BUDGET.evaluate(),
        "workload_top": _workload.WORKLOAD.top_by_cost(3),
        "cpu_count": os.cpu_count(),
        "env": {k: os.environ.get(k) for k in
                ("RTPU_FOLD_WORKERS", "RTPU_PREFETCH_DEPTH",
                 "RTPU_TRANSFER_DEPTH", "RTPU_FOLD_CACHE_MB")},
        "cluster": cluster,
    }
    try:
        # the measured device plane (obs/device.py): sampled kernel
        # timings joined with estimates, memory snapshot, compile storm
        sig["device"] = _device.advisor_signals()
        sig["device"]["resident_bytes"] = \
            _device.RESIDENT.snapshot()["total_bytes"]
    except Exception:
        sig["device"] = {}
    try:
        from ..utils.transfer import shared_engine

        sig["transfer"] = shared_engine().stats.totals()
    except Exception:
        sig["transfer"] = {}
    try:
        from ..core.sweep import fold_cache

        cache = fold_cache()
        sig["fold_cache"] = cache.stats() if cache is not None else {}
    except Exception:
        sig["fold_cache"] = {}
    try:
        # the freshness plane (obs/freshness.py): per-source stream
        # telemetry, staged backlog, staleness budget — what the
        # ingest-backlog / out-of-order-excess / freshness-burn rules
        # read
        sig["freshness"] = _freshness.FRESH.advisor_signals()
    except Exception:
        sig["freshness"] = {}
    graph = getattr(manager, "graph", None) if manager is not None else None
    if graph is not None:
        try:
            # lag_state separates idle (registered, no traffic — 0.0,
            # never an alarm) from a genuinely stalled active fence
            state, lag = graph.watermarks.lag_state()
            sig["watermark_lag_seconds"] = lag
            sig["watermark_lag_state"] = state
            sig["watermark_sources"] = {
                k: int(v) for k, v in graph.watermarks.snapshot().items()}
        except Exception:
            pass
    return sig


class Advisor:
    """Process-wide periodic rule evaluator. Last-tick findings and a
    bounded history under one lock; gathering, rule evaluation, metric
    mirroring and trace instants all happen OUTSIDE it (RT009)."""

    def __init__(self):
        self._lock = threading.Lock()
        self._findings: list[dict] = []
        self._rule_errors: list[str] = []
        self._history: deque = deque(maxlen=HISTORY)
        self._last_rule_ids: set = set()
        #: unix time of the last pass that saw /clusterz data — gates
        #: how long local ticks keep carrying its cluster findings
        self._cluster_unix = 0.0
        self._manager_ref = None
        self._thread: threading.Thread | None = None
        # per-generation stop event (obs/slo.SeriesRing pattern): a stop
        # racing a concurrent start must only affect its own generation
        self._stop = threading.Event()
        self.ticks = 0
        self.last_tick_unix = 0.0
        self._san_tracker = _san_track("advisor_findings")

    def attach_manager(self, manager) -> None:
        """Weakly attach the serving AnalysisManager — the watermark-lag
        and queue signals come from its graph; the advisor must not pin
        a dead manager (the registry is process-wide)."""
        import weakref

        with self._lock:
            self._manager_ref = weakref.ref(manager)

    def _manager(self):
        with self._lock:
            ref = self._manager_ref
        return ref() if ref is not None else None

    # ---- evaluation ----

    def tick(self, cluster: dict | None = None) -> list[dict]:
        """One evaluation pass: gather → rules → publish. Returns the
        findings. Safe from any thread; never raises."""
        signals = gather_signals(self._manager(), cluster=cluster)
        findings = evaluate_rules(signals)
        now = time.time()
        # a federated pass only counts as mesh EVIDENCE when the scrape
        # actually reached ≥ 2 processes — a transient all-peers-down
        # scrape renders reachable:false everywhere, which must not
        # clear a carried straggler finding (the cluster rules judged
        # nothing) or the finding flaps across every peer outage
        evidential = (cluster is not None
                      and len(_cluster_rows(cluster)) >= 2)
        with self._lock:
            _san_note(self._san_tracker, True)
            if evidential:
                self._cluster_unix = now
            elif now - self._cluster_unix <= CLUSTER_RETAIN_S:
                # no mesh evidence this pass: carry the last evidential
                # pass's cluster findings (bounded by age) — only a pass
                # that saw the mesh may clear or refresh them
                present = {f["rule_id"] for f in findings}
                findings = findings + [f for f in self._findings
                                       if f["rule_id"] in CLUSTER_RULES
                                       and f["rule_id"] not in present]
            new_ids = {f["rule_id"] for f in findings}
            prev_ids = self._last_rule_ids
            fresh = [f for f in findings if f["rule_id"] not in prev_ids]
            self._last_rule_ids = new_ids
            self._findings = findings
            # a crashed rule must look DIFFERENT from a quiet one: the
            # errors ride on /advisez and the /statusz block
            self._rule_errors = signals.get("rule_errors", [])
            self._history.extend(fresh)
            self.ticks += 1
            self.last_tick_unix = now
        m = _metrics()
        if m is not None:
            m.advisor_ticks.inc()
            counts: dict[str, int] = {}
            for f in findings:
                counts[f["rule_id"]] = counts.get(f["rule_id"], 0) + 1
            for rule_id, _, _, _ in RULES:   # zero cleared rules too
                m.advisor_findings.labels(rule_id).set(
                    counts.get(rule_id, 0))
        for f in fresh:                      # instants outside the lock
            TRACER.instant("advisor.finding", rule_id=f["rule_id"],
                           knob=f["knob"], severity=f["severity"],
                           summary=f["summary"])
            # durable journal: FRESH findings only (a standing finding
            # re-journaled every tick would be noise, not evidence)
            if _journal.enabled():
                _journal.emit("advice", f)
        return findings

    # ---- periodic thread ----

    def _loop(self, stop: threading.Event) -> None:
        while not stop.wait(interval_s()):
            if enabled():
                self.tick()

    def start(self) -> "Advisor":
        """Start the periodic evaluator thread (idempotent)."""
        with self._lock:
            if self._thread is not None and self._thread.is_alive():
                return self
            self._stop = stop = threading.Event()
            self._thread = threading.Thread(
                target=self._loop, args=(stop,), name="advisor",
                daemon=True)
            self._thread.start()
        return self

    def maybe_start(self) -> "Advisor":
        return self.start() if enabled() else self

    def stop(self) -> None:
        with self._lock:
            t, self._thread = self._thread, None
            self._stop.set()
        if t is not None:
            t.join(timeout=5.0)

    @property
    def running(self) -> bool:
        t = self._thread
        return t is not None and t.is_alive()

    # ---- export ----

    def findings(self) -> list[dict]:
        with self._lock:
            _san_note(self._san_tracker, False)
            return [dict(f) for f in self._findings]

    def status_block(self) -> dict:
        """The compact ``advisor`` block /statusz embeds (what /clusterz
        federates): counts + rule ids only, never the evidence bodies."""
        with self._lock:
            _san_note(self._san_tracker, False)
            return {"enabled": enabled(), "running": self.running,
                    "ticks": self.ticks,
                    "last_tick_unix": round(self.last_tick_unix, 3),
                    "findings": len(self._findings),
                    "rule_ids": sorted({f["rule_id"]
                                        for f in self._findings}),
                    "rule_errors": list(self._rule_errors)}

    def advisez(self, cluster: dict | None = None) -> dict:
        """The full ``/advisez`` document. When ``cluster`` (a fetched
        /clusterz doc) is supplied the tick evaluates the mesh rules
        too — one process advising the whole mesh."""
        findings = self.tick(cluster=cluster)
        with self._lock:
            history = [dict(f) for f in self._history]
            rule_errors = list(self._rule_errors)
            ticks = self.ticks
        out = {
            "enabled": enabled(), "running": self.running,
            "interval_seconds": interval_s(), "ticks": ticks,
            "findings": findings,
            "rule_errors": rule_errors,
            "history": history,
            "rules": [{"rule_id": rid, "reads": reads, "fires_when": desc}
                      for rid, _, reads, desc in RULES],
            "read_only": ("findings recommend; nothing here mutates a "
                          "knob — the adaptive runtime (ROADMAP 4) "
                          "closes the loop"),
        }
        if cluster is not None:
            out["cluster"] = {
                "processes_reachable": cluster.get("processes_reachable"),
                "peers_configured": cluster.get("peers_configured"),
            }
        return out

    def clear(self) -> None:
        with self._lock:
            self._findings = []
            self._rule_errors = []
            self._history.clear()
            self._last_rule_ids = set()
            self._cluster_unix = 0.0
            self.ticks = 0
            self.last_tick_unix = 0.0
        with _QUERIES_LOCK:
            _QUERIES.clear()


#: the process singleton /advisez and the RestServer tick through
ADVISOR = Advisor()
