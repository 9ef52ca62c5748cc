"""Advisor plane (PR 11): tenant workload accounts + SLO error budgets +
the rule-driven /advisez engine.

Carries the ISSUE-11 acceptance lines testable in one process: tenant
identity normalization can never fail a request or mint unbounded label
cardinality (cap → ``other``, malformed → ``invalid``); two simultaneous
jobs with different tenants land their costs in the right accounts with
no cross-linking; burn-rate math is exact under injected clocks (window
boundaries, empty histograms, target parse errors); ``/healthz`` grades
ok|degraded|burning (503 only under ``RTPU_HEALTH_STRICT=1``); every
advisor rule fires on its synthetic signal shape and stays quiet on a
healthy one; findings are machine-readable and a tick is strictly
read-only; ``/clusterz`` merges per-tenant totals and advisor rules
with per-process attribution.
"""

import json
import os
import urllib.request

import pytest

from raphtory_tpu.obs import budget as bud_mod
from raphtory_tpu.obs import workload as wl_mod
from raphtory_tpu.obs.advisor import ADVISOR, RULES, evaluate_rules
from raphtory_tpu.obs.budget import (BUDGET, BudgetRegistry, healthz,
                                     parse_targets, window_burn)
from raphtory_tpu.obs.ledger import Ledger
from raphtory_tpu.obs.slo import SLO, SLORegistry
from raphtory_tpu.obs.workload import (WORKLOAD, WorkloadRegistry,
                                       normalize_tenant)


def _graph(n=2_000, name="adv", seed=5):
    from raphtory_tpu.core.service import TemporalGraph
    from raphtory_tpu.ingestion.pipeline import IngestionPipeline
    from raphtory_tpu.ingestion.source import RandomSource

    pipe = IngestionPipeline()
    pipe.add_source(RandomSource(n, id_pool=150, seed=seed, name=name))
    pipe.run()
    return TemporalGraph(pipe.log, pipe.watermarks)


def _led(tenant="acme", qid="q1", alg="PR", cost=0.5, wall=1.0,
         queue=0.1):
    led = Ledger(qid, alg)
    led.tenant = tenant
    led.trace_id = f"trace-{qid}"
    led.phase_seconds["fold"] = cost
    led.wall_seconds = wall
    led.queue_wait_seconds = queue
    return led


# ------------------------------------------------- tenant identity rules


def test_normalize_tenant_identity_rules():
    assert normalize_tenant(None) == "anon"
    assert normalize_tenant("") == "anon"
    assert normalize_tenant("   ") == "anon"
    assert normalize_tenant("team-7.staging_x") == "team-7.staging_x"
    assert normalize_tenant("  padded  ") == "padded"
    # malformed values NEVER raise — they land in the shared account
    assert normalize_tenant("x" * 65) == "invalid"          # oversized
    assert normalize_tenant("x" * 64) == "x" * 64           # at the cap
    assert normalize_tenant("tênant") == "invalid"     # non-ASCII
    assert normalize_tenant("a b") == "invalid"             # space
    assert normalize_tenant("a/b") == "invalid"             # slash
    assert normalize_tenant("a\nb") == "invalid"            # control
    assert normalize_tenant(123) == "invalid"               # non-str
    assert normalize_tenant(["x"]) == "invalid"
    # the overflow aggregate cannot be CLAIMED: a client naming itself
    # `other` would merge into the past-cap bucket cap-exempt and
    # without the overflow count — the claim lands in `invalid`
    assert normalize_tenant("other") == "invalid"
    # `anon`/`invalid` claims are semantically idempotent and stay
    assert normalize_tenant("anon") == "anon"
    assert normalize_tenant("invalid") == "invalid"


def test_tenant_cap_overflow_aggregates_into_other(monkeypatch):
    monkeypatch.setenv("RTPU_TENANT_CAP", "2")
    reg = WorkloadRegistry()
    for i in range(5):
        reg.record(_led(tenant=f"t{i}", qid=f"q{i}"))
    assert reg.tenants() == ["other", "t0", "t1"]
    assert reg.overflow_queries == 3
    other = reg.account("other")
    assert other["queries_total"] == 3
    # sentinel accounts ride ABOVE the cap: label cardinality stays
    # provably bounded at cap + 3 names, and a malformed header past the
    # cap still lands in `invalid`, not `other`
    reg.record(_led(tenant="anon", qid="qa"))
    reg.record(_led(tenant="invalid", qid="qi"))
    assert set(reg.tenants()) == {"other", "t0", "t1", "anon", "invalid"}


def test_account_rollup_math_and_bounded_tables():
    reg = WorkloadRegistry()
    reg.record(_led(qid="qa", cost=0.5, wall=2.0, queue=0.1))
    reg.record(_led(qid="qb", cost=0.25, wall=1.0, queue=0.2),
               status="failed")
    acct = reg.account("acme")
    assert acct["queries"] == {"done": 1, "failed": 1}
    assert acct["cost_seconds"] == pytest.approx(0.75)
    assert acct["wall_seconds"] == pytest.approx(3.0)
    assert acct["queue_wait_seconds"] == pytest.approx(0.3)
    assert acct["phase_seconds"]["fold"] == pytest.approx(0.75)
    # exemplars: bounded at TOP_QUERIES, most expensive first, trace ids
    # riding along (the advisor's shed-this-tenant evidence)
    for i in range(10):
        reg.record(_led(qid=f"bulk{i}", wall=float(i)))
    acct = reg.account("acme")
    assert len(acct["top_queries"]) == wl_mod.TOP_QUERIES
    assert acct["top_queries"][0]["query_id"] == "bulk9"
    assert acct["top_queries"][0]["trace_id"] == "trace-bulk9"
    # shape table bounded at MAX_SHAPES with overflow counted
    for i in range(wl_mod.MAX_SHAPES + 7):
        reg.record(_led(qid=f"s{i}", alg=f"Alg{i}"))
    acct = reg.account("acme")
    assert len(acct["shapes_top"]) <= 8
    assert acct["shapes_overflow"] >= 7


def test_top_by_cost_orders_and_bounds():
    """The advisor's shed-candidate ordering: descending attributed
    cost, and the returned list is bounded at ``n`` — record() and the
    advisor tick share the registry lock, so the snapshot work must be
    O(n), never O(table)."""
    reg = WorkloadRegistry()
    for i, cost in enumerate([0.5, 3.0, 1.0, 2.0]):
        reg.record(_led(tenant=f"c{i}", qid=f"q{i}", cost=cost))
    top = reg.top_by_cost(2)
    assert [t["tenant"] for t in top] == ["c1", "c3"]
    assert top[0]["cost_seconds"] == pytest.approx(3.0)
    # n past the table returns everything; degenerate n returns nothing
    assert len(reg.top_by_cost(99)) == 4
    assert reg.top_by_cost(0) == []


def test_workload_disabled_by_env(monkeypatch):
    monkeypatch.setenv("RTPU_WORKLOAD", "0")
    reg = WorkloadRegistry()
    reg.record(_led())
    assert reg.tenants() == []
    assert reg.status_block()["enabled"] is False


def test_workloadz_document_schema():
    reg = WorkloadRegistry()
    reg.record(_led(tenant="big", cost=5.0))
    reg.record(_led(tenant="small", qid="q2", cost=0.1))
    doc = reg.workloadz()
    assert doc["n_tenants"] == 2
    assert doc["header"] == "X-RTPU-Tenant"
    # sorted by attributed cost, schema round-trips through real JSON
    assert [t["tenant"] for t in doc["tenants"]] == ["big", "small"]
    json.dumps(doc)


# --------------------------------------- concurrent multi-tenant isolation


def test_concurrent_jobs_land_in_their_own_tenant_accounts(monkeypatch):
    """Two jobs running concurrently through the SHARED fold pool with
    different tenants: each account gets exactly its own job's cost and
    exemplars — no cross-linking (the PR-9 isolation harness, one level
    up the roll-up)."""
    from raphtory_tpu.algorithms import ConnectedComponents, PageRank
    from raphtory_tpu.jobs.manager import AnalysisManager, RangeQuery

    monkeypatch.setenv("RTPU_FOLD_WORKERS", "2")
    WORKLOAD.clear()
    ga = _graph(3_000, name="adv_iso_a", seed=61)
    gb = _graph(3_000, name="adv_iso_b", seed=62)
    ja = AnalysisManager(ga).submit(PageRank(max_steps=8),
                                    RangeQuery(200, 900, 175),
                                    tenant="tenant_a")
    jb = AnalysisManager(gb).submit(ConnectedComponents(),
                                    RangeQuery(200, 900, 175),
                                    tenant="tenant_b")
    assert ja.wait(180) and ja.status == "done", ja.error
    assert jb.wait(180) and jb.status == "done", jb.error
    assert ja.tenant == "tenant_a" and jb.tenant == "tenant_b"
    a = WORKLOAD.account("tenant_a")
    b = WORKLOAD.account("tenant_b")
    assert a["queries_total"] == 1 and b["queries_total"] == 1
    assert a["cost_seconds"] > 0 and b["cost_seconds"] > 0
    a_ids = {q["query_id"] for q in a["top_queries"]}
    b_ids = {q["query_id"] for q in b["top_queries"]}
    assert a_ids == {ja.id} and b_ids == {jb.id}
    assert a["shapes_top"] and all(
        s.startswith("PageRank/") for s in a["shapes_top"])
    assert all(s.startswith("ConnectedComponents/")
               for s in b["shapes_top"])


# --------------------------------------------------- budget: target parse


def test_parse_targets_grammar_and_errors():
    targets, errors = parse_targets("pagerank=p99:2.5s")
    assert not errors
    t = targets[0]
    assert (t.algorithm, t.quantile, t.threshold_s) == ("pagerank", 0.99,
                                                        2.5)
    assert t.allowed == pytest.approx(0.01)
    targets, _ = parse_targets("a=p95:250ms, b=p50:3")
    assert [(t.algorithm, t.threshold_s) for t in targets] == \
        [("a", 0.25), ("b", 3.0)]
    # operator typos become error strings, never exceptions
    for bad in ("nosep", "x=q99:1s", "x=p0:1s", "x=p100:1s", "x=p99:-1s",
                "x=p99:soon", "=p99:1s"):
        targets, errors = parse_targets(bad)
        assert targets == [] and len(errors) == 1, bad
    _, errors = parse_targets("a=p99:1s,a=p50:2s")
    assert "duplicate" in errors[0]
    many = ",".join(f"alg{i}=p99:1s" for i in range(bud_mod.MAX_TARGETS
                                                    + 3))
    targets, errors = parse_targets(many)
    assert len(targets) == bud_mod.MAX_TARGETS and len(errors) == 3


# ---------------------------------------------- budget: burn-rate math


def _rows(samples):
    """[(unix, obs, bad)] -> series-ring rows for window_burn."""
    return [{"unix": u, "slo_obs_a_total": o, "slo_bad_a_total": b}
            for u, o, b in samples]


def test_window_burn_under_injected_clock():
    rows = _rows([(100.0, 0, 0), (130.0, 50, 0), (160.0, 100, 1)])
    # p99-style target: allowed bad fraction 0.01
    burn = window_burn(rows, "a", now=160.0, window_s=60.0, allowed=0.01)
    # window [100, 160] inclusive at the boundary: 1 breach / 100 obs
    assert burn == pytest.approx(1.0)
    # narrower window excludes the first row: 1/50 over [130, 160]
    burn = window_burn(rows, "a", now=160.0, window_s=30.0, allowed=0.01)
    assert burn == pytest.approx(2.0)
    # fewer than two usable samples: nothing to difference
    assert window_burn(rows, "a", now=160.0, window_s=5.0,
                       allowed=0.01) is None
    assert window_burn([], "a", now=160.0, window_s=60.0,
                       allowed=0.01) is None
    # a window with traffic but zero breaches burns 0
    assert window_burn(_rows([(0.0, 0, 0), (60.0, 10, 0)]), "a",
                       now=60.0, window_s=60.0, allowed=0.01) == 0.0
    # no traffic in the window burns nothing (not a division by zero)
    assert window_burn(_rows([(0.0, 5, 1), (60.0, 5, 1)]), "a",
                       now=60.0, window_s=60.0, allowed=0.01) == 0.0
    # rows missing the collector keys are skipped, not crashed on
    assert window_burn([{"unix": 50.0}, {"unix": 60.0}], "a", now=60.0,
                       window_s=60.0, allowed=0.01) is None


def test_totals_below_threshold_and_case_rules(monkeypatch):
    monkeypatch.setenv("RTPU_SLO_BUCKETS", "0.1,1,10")
    reg = SLORegistry()
    for _ in range(90):
        reg.observe("PageRank", "e2e", 0.05)
    for _ in range(10):
        reg.observe("PageRank", "e2e", 5.0)
    # threshold on a bucket bound: exact
    assert reg.totals_below("PageRank", "e2e", 1.0) == (100, 90)
    # targets are operator-typed: algorithm matching is case-insensitive
    assert reg.totals_below("pagerank", "e2e", 1.0) == (100, 90)
    # a threshold BETWEEN bounds counts its bucket as bad (conservative)
    assert reg.totals_below("PageRank", "e2e", 5.5) == (100, 90)
    assert reg.totals_below("PageRank", "e2e", 10.0) == (100, 100)
    # empty histogram: no observations, no breaches
    assert reg.totals_below("nosuch", "e2e", 1.0) == (0, 0)


def test_budget_grades_under_injected_clock(monkeypatch):
    monkeypatch.setenv("RTPU_SLO_BUCKETS", "0.1,1,10")
    monkeypatch.setenv("RTPU_SLO_TARGET", "gradealg=p90:1s")
    monkeypatch.setenv("RTPU_BUDGET_FAST_S", "60")
    monkeypatch.setenv("RTPU_BUDGET_SLOW_S", "600")
    SLO.clear()
    reg = BudgetRegistry()
    rows = [{"unix": u, "slo_obs_gradealg_total": o,
             "slo_bad_gradealg_total": b}
            for u, o, b in [(0.0, 0, 0), (500.0, 50, 2), (560.0, 100, 2),
                            (620.0, 150, 2)]]
    # fast window [560, 620]: 0/50 breaches -> 0; slow [20, 620]: 0/100
    ev = reg.evaluate(now=620.0, rows=rows)
    assert ev["grade"] == "ok"
    t = ev["targets"][0]
    assert (t["fast_burn"], t["slow_burn"]) == (0.0, 0.0)
    # burn the FAST window only -> degraded (a cliff, not yet sustained)
    rows.append({"unix": 640.0, "slo_obs_gradealg_total": 160,
                 "slo_bad_gradealg_total": 6})
    ev = reg.evaluate(now=640.0, rows=rows)
    assert ev["grade"] == "degraded"
    assert ev["targets"][0]["fast_burn"] >= 1.0
    assert ev["targets"][0]["slow_burn"] < 1.0
    # sustained: both windows over 1 -> burning
    rows = [{"unix": 600.0, "slo_obs_gradealg_total": 0,
             "slo_bad_gradealg_total": 0},
            {"unix": 660.0, "slo_obs_gradealg_total": 10,
             "slo_bad_gradealg_total": 5}]
    ev = reg.evaluate(now=660.0, rows=rows)
    assert ev["grade"] == "burning"


def test_budget_empty_histograms_and_parse_errors(monkeypatch):
    monkeypatch.setenv("RTPU_SLO_TARGET",
                       "cleanalg=p99:1s,broken~p99")
    SLO.clear()
    reg = BudgetRegistry()
    ev = reg.evaluate(now=100.0, rows=[])
    # an empty histogram is grade ok with zero observations — a target
    # on an algorithm that never ran must not page
    assert ev["grade"] == "ok"
    assert ev["targets"][0]["observations"] == 0
    assert ev["targets"][0]["budget_remaining"] == 1.0
    # the typo'd entry is DATA, not an exception
    assert len(ev["errors"]) == 1 and "broken" in ev["errors"][0]


def test_budget_falls_back_to_cumulative_when_ring_dead(monkeypatch):
    monkeypatch.setenv("RTPU_SLO_BUCKETS", "0.1,1,10")
    monkeypatch.setenv("RTPU_SLO_TARGET", "deadring=p90:1s")
    SLO.clear()
    for _ in range(5):
        SLO.observe("deadring", "e2e", 5.0)   # 100% breaches
    reg = BudgetRegistry()
    ev = reg.evaluate(now=10.0, rows=[])      # no usable window rows
    t = ev["targets"][0]
    assert t["fast_burn"] is None and t["slow_burn"] is None
    assert t["cumulative_burn"] == pytest.approx(10.0)
    # all the evidence says overspent: honest grade is burning
    assert ev["grade"] == "burning"
    SLO.clear()


def test_budget_retarget_retires_collectors_and_gauges(monkeypatch):
    """Review hardening: dropping an algorithm from ``RTPU_SLO_TARGET``
    must RETIRE its series-ring collectors and burn gauges — not leave
    dead closures walking histograms at 1 Hz forever while frozen gauges
    mislead dashboards — and ``clear()`` retires everything registered.
    Retirement is not a one-way door: a re-added target re-registers."""
    from raphtory_tpu.obs.slo import SERIES, SeriesRing

    # ring-level contract first: unregister drops the collector, an
    # unknown name is a no-op (retire must tolerate a never-registered
    # algorithm)
    ring = SeriesRing(ring=8, interval=0.01)
    ring.register("gone_total", lambda: 1.0)
    assert "gone_total" in ring.sample_once()
    ring.unregister("gone_total")
    ring.unregister("never_registered")
    assert "gone_total" not in ring.sample_once()

    monkeypatch.setenv("RTPU_SLO_BUCKETS", "0.1,1,10")
    monkeypatch.setenv("RTPU_SLO_TARGET", "reta=p99:1s,retb=p99:1s")
    SLO.clear()
    SLO.observe("retb", "e2e", 0.05)
    reg = BudgetRegistry()
    reg.evaluate(now=10.0, rows=[])
    row = SERIES.sample_once()
    assert {"slo_obs_reta_total", "slo_bad_reta_total",
            "slo_obs_retb_total", "slo_bad_retb_total"} <= set(row)

    def burn_gauge_algs():
        from raphtory_tpu.obs.metrics import METRICS
        return {s.labels.get("algorithm")
                for metric in METRICS.slo_burn_rate.collect()
                for s in metric.samples}

    assert {"reta", "retb"} <= burn_gauge_algs()
    # operator retargets: retb leaves the env -> collectors AND gauges go
    monkeypatch.setenv("RTPU_SLO_TARGET", "reta=p99:1s")
    ev = reg.evaluate(now=20.0, rows=[])
    assert [t["algorithm"] for t in ev["targets"]] == ["reta"]
    row = SERIES.sample_once()
    assert "slo_obs_reta_total" in row
    assert "slo_obs_retb_total" not in row
    assert "slo_bad_retb_total" not in row
    assert "retb" not in burn_gauge_algs()
    # re-adding the target re-registers its collectors
    monkeypatch.setenv("RTPU_SLO_TARGET", "reta=p99:1s,retb=p99:1s")
    reg.evaluate(now=30.0, rows=[])
    assert "slo_obs_retb_total" in SERIES.sample_once()
    # clear() tears down every registration this registry made
    reg.clear()
    row = SERIES.sample_once()
    assert not any("reta" in k or "retb" in k for k in row)
    assert not {"reta", "retb"} & burn_gauge_algs()
    SLO.clear()


def test_budget_threshold_retarget_reregisters_collectors(monkeypatch):
    """Review hardening: tightening an EXISTING target's threshold must
    replace the ring collectors — the closures capture the threshold, so
    stale ones would keep judging breaches against the old target until
    restart while the windowed burns (which gate the /healthz grade)
    read 'ok' through a 100% breach rate."""
    from raphtory_tpu.obs.slo import SERIES

    monkeypatch.setenv("RTPU_SLO_BUCKETS", "0.1,1,10")
    monkeypatch.setenv("RTPU_SLO_TARGET", "retc=p99:1s")
    SLO.clear()
    for _ in range(4):
        SLO.observe("retc", "e2e", 0.5)   # good under 1s, bad under 0.1s
    reg = BudgetRegistry()
    reg.evaluate(now=10.0, rows=[])
    row = SERIES.sample_once()
    assert row["slo_obs_retc_total"] == 4.0
    assert row["slo_bad_retc_total"] == 0.0
    # the operator TIGHTENS the target: same algorithm, new threshold
    monkeypatch.setenv("RTPU_SLO_TARGET", "retc=p99:0.1s")
    ev = reg.evaluate(now=20.0, rows=[])
    assert ev["targets"][0]["threshold_s"] == pytest.approx(0.1)
    row = SERIES.sample_once()
    assert row["slo_bad_retc_total"] == 4.0   # the NEW threshold judges
    reg.clear()
    SLO.clear()


# ------------------------------------------------------- graded /healthz


def test_healthz_grades_and_strict_mode(monkeypatch):
    monkeypatch.setenv("RTPU_SLO_BUCKETS", "0.1,1,10")
    SLO.clear()
    monkeypatch.delenv("RTPU_SLO_TARGET", raising=False)
    code, payload = healthz()
    assert (code, payload["status"]) == (200, "ok")
    assert payload["targets"] == []
    # breach a target hard: cumulative fallback grades it burning
    monkeypatch.setenv("RTPU_SLO_TARGET", "hzalg=p50:0.1s")
    for _ in range(10):
        SLO.observe("hzalg", "e2e", 5.0)
    code, payload = healthz()
    assert payload["status"] == "burning"
    assert code == 200          # default: grade in the body, never 503
    monkeypatch.setenv("RTPU_HEALTH_STRICT", "1")
    code, payload = healthz()
    assert (code, payload["status"]) == (503, "burning")
    SLO.clear()
    BUDGET.clear()


# ------------------------------------------------------- advisor rules


def _queries(n=4, phase="compute", sec=1.0, queue=0.0, h2d_stall=0.0):
    return [{"query_id": f"q{i}", "algorithm": "PR", "tenant": "t",
             "trace_id": f"tr{i}", "wall_seconds": sec,
             "queue_wait_seconds": queue,
             "phase_seconds": {phase: sec},
             "h2d": {"bytes": 0, "stall_seconds":
                     ({"wire": h2d_stall} if h2d_stall else {})}}
            for i in range(n)]


def test_rules_quiet_on_empty_and_healthy_signals():
    assert evaluate_rules({}) == []
    sig = {"env": {}, "queries": _queries(8, "compute", 1.0),
           "kernels": [], "budget": {"grade": "ok", "targets": []},
           "workload_top": [], "transfer": {"stall_seconds": 0.0},
           "fold_cache": {"hits": 100, "misses": 5, "evictions": 0},
           "cpu_count": 4, "watermark_lag_seconds": 0.0, "cluster": None}
    assert evaluate_rules(sig) == []


def test_rule_fold_stall_names_the_workers_knob():
    """The docs/OBSERVABILITY.md worked walkthrough: RTPU_FOLD_WORKERS=1
    mis-set on a 4-core box, fold dominating — the advisor names the
    knob and the auto size it would pick."""
    sig = {"env": {"RTPU_FOLD_WORKERS": "1"}, "cpu_count": 4,
           "queries": _queries(6, "fold", 0.5), "transfer": {}}
    (f,) = evaluate_rules(sig)
    assert f["rule_id"] == "fold-stall-raise-workers"
    assert f["knob"] == "RTPU_FOLD_WORKERS"
    assert f["evidence"]["fold_workers"] == 1
    assert f["evidence"]["auto_workers"] == 2
    assert "2" in f["recommendation"]
    # auto-sized pool: nothing to advise even with the same phase split
    sig["env"] = {}
    assert evaluate_rules(sig) == []


def test_rule_queue_burn_names_top_tenant():
    sig = {"budget": {"grade": "burning",
                      "targets": [{"algorithm": "pagerank",
                                   "grade": "burning"}]},
           "queries": _queries(6, "compute", 1.0, queue=0.5),
           "workload_top": [{"tenant": "acme", "cost_seconds": 9.0,
                             "queue_wait_seconds": 3.0,
                             "queries_total": 6,
                             "top_queries": [{"query_id": "q0"}]}]}
    (f,) = evaluate_rules(sig)
    assert f["rule_id"] == "queue-burn-shed-top-tenant"
    assert f["severity"] == "warning"
    assert "acme" in f["summary"]
    assert f["evidence"]["top_tenant"]["tenant"] == "acme"
    assert f["evidence"]["burning_targets"][0]["algorithm"] == "pagerank"
    # budget ok -> no shed advice no matter the queue
    sig["budget"] = {"grade": "ok", "targets": []}
    assert evaluate_rules(sig) == []


def test_rule_h2d_stall_and_fold_cache_thrash():
    # the stall evidence comes from the SAME recent-query window as the
    # phase split — per-query h2d stalls, not process-lifetime totals
    sig = {"transfer": {"stall_seconds": 3.0, "bytes_shipped": 10_000},
           "queries": _queries(4, "compute", 1.0, h2d_stall=0.75)}
    (f,) = evaluate_rules(sig)
    assert f["rule_id"] == "h2d-stall-raise-depth"
    assert f["knob"] == "RTPU_TRANSFER_DEPTH"
    assert f["evidence"]["stall_seconds"] == pytest.approx(3.0)
    # review hardening: a day-1 stall backlog in the LIFETIME totals
    # with a clean recent window must NOT keep the rule firing forever
    quiet = {"transfer": {"stall_seconds": 50.0},
             "queries": _queries(8, "compute", 1.0)}
    assert evaluate_rules(quiet) == []
    sig = {"fold_cache": {"hits": 5, "misses": 50, "evictions": 20,
                          "bytes": 9, "max_bytes": 10, "entries": 1}}
    (f,) = evaluate_rules(sig)
    assert f["rule_id"] == "fold-cache-thrash"
    assert f["knob"] == "RTPU_FOLD_CACHE_MB"


def test_rule_fold_cache_refused_fires_on_one_refusal_only():
    """A refusal evicts nothing, so the thrash rule (10 evictions) never
    sees a log whose checkpoint outgrew the bound; this one does, names
    the size, and is silent on a cache that refused nothing."""
    fc = {"hits": 0, "misses": 9, "evictions": 0, "bytes": 0, "entries": 0,
          "max_bytes": 256 << 20, "refused": 5,
          "refused_bytes": 301_234_567}
    (f,) = evaluate_rules({"fold_cache": fc})
    assert f["rule_id"] == "fold-cache-refused"
    assert f["knob"] == "RTPU_FOLD_CACHE_MB"
    assert "301234567 bytes exceeds RTPU_FOLD_CACHE_MB" in f["summary"]
    assert "every request folds the log from its start" in f["summary"]
    assert f["evidence"]["fold_cache"]["refused"] == 5
    assert f["evidence"]["fold_cache"]["max_bytes"] == 256 << 20
    for quiet in ({**fc, "refused": 0, "refused_bytes": 0},
                  {k: v for k, v in fc.items() if "refused" not in k}, {}):
        assert evaluate_rules({"fold_cache": quiet}) == []
    # beside a thrashing cache both say so, each with its own id
    both = evaluate_rules({"fold_cache": {**fc, "hits": 5, "misses": 50,
                                          "evictions": 20}})
    assert [f["rule_id"] for f in both] == ["fold-cache-thrash",
                                            "fold-cache-refused"]


def test_rule_watermark_stale_respects_bar(monkeypatch):
    monkeypatch.setenv("RTPU_ADVISOR_STALE_S", "5")
    sig = {"watermark_lag_seconds": 10.0,
           "watermark_sources": {"s": 100}}
    (f,) = evaluate_rules(sig)
    assert f["rule_id"] == "watermark-stale"
    assert f["evidence"]["stale_bar_seconds"] == 5.0
    sig["watermark_lag_seconds"] = 4.0
    assert evaluate_rules(sig) == []


def _cluster(lag0=0.2, lag1=40.0, skew=None):
    return {"processes": {
        "process_0": {"reachable": True, "process_index": 0,
                      "watermark_lag_seconds": lag0,
                      "collectives": {"barrier_wait_seconds": 0.0,
                                      "skew": skew}},
        "process_1": {"reachable": True, "process_index": 1,
                      "watermark_lag_seconds": lag1,
                      "collectives": {"barrier_wait_seconds": 1.5,
                                      "skew": None}},
    }}


def test_rule_cluster_straggler_names_the_process(monkeypatch):
    monkeypatch.setenv("RTPU_ADVISOR_STALE_S", "5")
    (f,) = evaluate_rules({"cluster": _cluster()})
    assert f["rule_id"] == "cluster-straggler"
    assert f["evidence"]["process"] == "process_1"
    assert f["evidence"]["process_index"] == 1
    assert f["evidence"]["watermark_lag_by_process"]["process_1"] == 40.0
    # comparable lags: no straggler (3x bar over the rest + slack)
    assert evaluate_rules({"cluster": _cluster(lag1=0.4)}) == []
    # an unreachable peer contributes nothing
    c = _cluster()
    c["processes"]["process_1"]["reachable"] = False
    assert evaluate_rules({"cluster": c}) == []


def test_rule_shard_skew_reads_published_shape(monkeypatch):
    monkeypatch.setenv("RTPU_ADVISOR_STALE_S", "5")
    # the REAL published shape: shard_skew() rows, not bare floats
    skew = {"edges_dst": {"per_shard": [100, 10], "max": 100,
                          "mean": 55.0, "skew": 5.5},
            "halo_dst": {"per_shard": [4, 4], "max": 4, "mean": 4.0,
                         "skew": 1.0}}
    (f,) = evaluate_rules({"cluster": _cluster(lag1=0.3, skew=skew)})
    assert f["rule_id"] == "shard-skew"
    assert (f["evidence"]["kind"], f["evidence"]["skew"]) == ("edges_dst",
                                                              5.5)
    # balanced partitions: quiet
    skew = {"edges_dst": {"per_shard": [50, 50], "max": 50, "mean": 50.0,
                          "skew": 1.0}}
    assert evaluate_rules({"cluster": _cluster(lag1=0.3,
                                               skew=skew)}) == []


def test_crashing_rule_becomes_error_not_exception():
    # a truthy non-dict budget makes the queue rule raise internally;
    # the evaluator must swallow it into rule_errors and keep going
    sig = {"budget": "not-a-dict",
           "queries": _queries(6, "compute", 1.0, queue=0.5)}
    assert evaluate_rules(sig) == []
    assert len(sig["rule_errors"]) == 1
    assert "queue-burn-shed-top-tenant" in sig["rule_errors"][0]


def test_findings_machine_readable_and_tick_read_only(monkeypatch):
    """Acceptance: stable rule ids, a knob, an evidence block — and a
    live tick is STRICTLY read-only (os.environ unchanged)."""
    rule_ids = {rid for rid, _, _, _ in RULES}
    monkeypatch.setenv("RTPU_ADVISOR_STALE_S", "5")
    findings = evaluate_rules({
        "env": {"RTPU_FOLD_WORKERS": "1"}, "cpu_count": 4,
        "queries": _queries(6, "fold", 0.5), "transfer": {},
        "cluster": _cluster()})
    assert len(findings) == 2
    for f in findings:
        assert f["rule_id"] in rule_ids
        assert f["knob"] and isinstance(f["evidence"], dict)
        assert f["severity"] in ("advice", "warning") and f["unix"] > 0
    json.dumps(findings)
    before = dict(os.environ)
    ADVISOR.tick()
    assert dict(os.environ) == before


def test_advisor_registry_tick_history_and_thread(monkeypatch):
    ADVISOR.clear()
    findings = ADVISOR.tick()
    assert isinstance(findings, list)
    sb = ADVISOR.status_block()
    assert sb["ticks"] == 1 and sb["findings"] == len(findings)
    # a crashed rule must look different from a quiet one: the errors
    # list rides on both surfaces (empty on this healthy tick)
    assert sb["rule_errors"] == []
    doc = ADVISOR.advisez()
    assert doc["ticks"] == 2
    assert doc["rule_errors"] == []
    assert len(doc["rules"]) == len(RULES)
    assert {"rule_id", "reads", "fires_when"} <= set(doc["rules"][0])
    json.dumps(doc)
    # periodic thread: start/stop idempotent, generation-scoped stop
    monkeypatch.setenv("RTPU_ADVISOR_INTERVAL_S", "30")
    ADVISOR.start()
    assert ADVISOR.running
    ADVISOR.start()
    ADVISOR.stop()
    assert not ADVISOR.running
    ADVISOR.stop()


def test_advisor_local_tick_carries_cluster_findings(monkeypatch):
    """Review hardening: a background tick has no /clusterz data, so it
    has no evidence about mesh state — it must CARRY the last federated
    pass's cluster findings instead of zeroing them, or the straggler
    finding (and its gauge) flaps at the tick period and every federated
    pass re-emits it as fresh history."""
    import raphtory_tpu.obs.advisor as adv_mod
    from raphtory_tpu.obs.advisor import Advisor

    monkeypatch.setenv("RTPU_ADVISOR_STALE_S", "5")
    adv = Advisor()
    fed = adv.tick(cluster=_cluster())
    assert "cluster-straggler" in {f["rule_id"] for f in fed}
    hist0 = len(adv._history)
    # local (background) pass: the finding is carried, NOT fresh
    local = adv.tick()
    assert "cluster-straggler" in {f["rule_id"] for f in local}
    assert len(adv._history) == hist0
    # the next federated pass still firing is not fresh either (no
    # duplicate history / advisor.finding instants)
    fed2 = adv.tick(cluster=_cluster())
    assert "cluster-straggler" in {f["rule_id"] for f in fed2}
    assert len(adv._history) == hist0
    # a federated pass whose scrape reached NOBODY (transient peer
    # outage: every row reachable:false) saw no mesh evidence either —
    # it must carry, not clear
    dead = _cluster()
    for p in dead["processes"].values():
        p["reachable"] = False
    out = adv.tick(cluster=dead)
    assert "cluster-straggler" in {f["rule_id"] for f in out}
    assert len(adv._history) == hist0
    # only a pass WITH mesh evidence may clear it — a healthy mesh does
    ok = adv.tick(cluster=_cluster(lag1=0.4))
    assert "cluster-straggler" not in {f["rule_id"] for f in ok}
    # ...and a carried finding expires without federated confirmation
    adv2 = Advisor()
    adv2.tick(cluster=_cluster())
    monkeypatch.setattr(adv_mod, "CLUSTER_RETAIN_S", -1.0)
    stale = adv2.tick()
    assert "cluster-straggler" not in {f["rule_id"] for f in stale}


def test_advisor_query_evidence_survives_ledger_off(monkeypatch):
    """Review hardening: the advisor's recent-query evidence is
    jobs-layer data and must survive ``RTPU_LEDGER=0`` (the same
    contract the SLO histograms and workload accounts follow) — while
    /costz's ring, a LEDGER surface, rightly stays silent."""
    import raphtory_tpu.obs.advisor as adv_mod
    import raphtory_tpu.obs.ledger as led_mod
    from raphtory_tpu.algorithms import DegreeBasic
    from raphtory_tpu.jobs.manager import AnalysisManager, ViewQuery

    monkeypatch.setenv("RTPU_LEDGER", "0")
    ADVISOR.clear()                      # clears the module query ring
    costz_before = len(led_mod.recent_queries(64))
    g = _graph(1_200, name="adv_noled", seed=77)
    mgr = AnalysisManager(g)
    job = mgr.submit(DegreeBasic(), ViewQuery(g.latest_time),
                     tenant="noled")
    assert job.wait(120) and job.status == "done", job.error
    rows = adv_mod.recent_query_rows()
    assert len(rows) == 1
    assert rows[0]["tenant"] == "noled"
    assert rows[0]["wall_seconds"] > 0.0
    # the ledger surface stayed silent: /costz's ring did not grow
    assert len(led_mod.recent_queries(64)) == costz_before
    # the advisor's own knob still gates the feed (bench off-arm)
    monkeypatch.setenv("RTPU_ADVISOR", "0")
    job2 = mgr.submit(DegreeBasic(), ViewQuery(g.latest_time),
                      tenant="noled")
    assert job2.wait(120) and job2.status == "done", job2.error
    assert len(adv_mod.recent_query_rows()) == 1
    ADVISOR.clear()


# ------------------------------------------------- REST surface (live)


def _rest(srv, path, body=None, headers=None):
    url = f"http://127.0.0.1:{srv.port}{path}"
    if body is None:
        return json.loads(urllib.request.urlopen(url, timeout=60).read())
    req = urllib.request.Request(url, data=json.dumps(body).encode(),
                                 headers=headers or {}, method="POST")
    return json.loads(urllib.request.urlopen(req, timeout=60).read())


def _wait_done(mgr, job_id, timeout=120):
    job = mgr.get(job_id)
    assert job.wait(timeout) and job.status == "done", job.error
    return job


def test_rest_tenant_header_body_and_malformed_never_fail(monkeypatch):
    """Satellite: the observability header can never fail a request —
    non-ASCII and oversized X-RTPU-Tenant values normalize to `invalid`
    while the job itself succeeds; valid headers win over body fields;
    the body field backs the header up."""
    from raphtory_tpu.jobs.manager import AnalysisManager
    from raphtory_tpu.jobs.rest import RestServer

    WORKLOAD.clear()
    g = _graph(1_200, name="adv_rest", seed=71)
    mgr = AnalysisManager(g)
    srv = RestServer(mgr, port=0).start()
    try:
        t = g.latest_time
        base = {"analyserName": "DegreeBasic", "timestamp": t}
        # header wins over body
        r = _rest(srv, "/ViewAnalysisRequest",
                  {**base, "jobID": "t_hdr", "tenant": "from_body"},
                  headers={"X-RTPU-Tenant": "from_header"})
        assert r["tenant"] == "from_header"
        _wait_done(mgr, "t_hdr")
        # body field backs it up
        r = _rest(srv, "/ViewAnalysisRequest",
                  {**base, "jobID": "t_body", "tenant": "from_body"})
        assert r["tenant"] == "from_body"
        _wait_done(mgr, "t_body")
        # no identity at all -> anon
        r = _rest(srv, "/ViewAnalysisRequest", {**base, "jobID": "t_anon"})
        assert r["tenant"] == "anon"
        _wait_done(mgr, "t_anon")
        # a present-but-BLANK header must not suppress the body field
        r = _rest(srv, "/ViewAnalysisRequest",
                  {**base, "jobID": "t_blank", "tenant": "from_body"},
                  headers={"X-RTPU-Tenant": " "})
        assert r["tenant"] == "from_body"
        _wait_done(mgr, "t_blank")
        # malformed: non-ASCII (latin-1 survives the HTTP layer) and
        # oversized — BOTH requests succeed and land in `invalid`
        r = _rest(srv, "/ViewAnalysisRequest", {**base, "jobID": "t_na"},
                  headers={"X-RTPU-Tenant": "tênant"})
        assert r["tenant"] == "invalid"
        _wait_done(mgr, "t_na")
        r = _rest(srv, "/ViewAnalysisRequest", {**base, "jobID": "t_big"},
                  headers={"X-RTPU-Tenant": "x" * 65})
        assert r["tenant"] == "invalid"
        _wait_done(mgr, "t_big")

        wz = _rest(srv, "/workloadz")
        by_name = {t["tenant"]: t for t in wz["tenants"]}
        assert by_name["from_header"]["queries_total"] == 1
        # t_body + t_blank (the blank header fell through to the body)
        assert by_name["from_body"]["queries_total"] == 2
        assert by_name["anon"]["queries_total"] == 1
        assert by_name["invalid"]["queries_total"] == 2
    finally:
        srv.stop()


def test_rest_advisez_healthz_statusz_surfaces(monkeypatch):
    from raphtory_tpu.algorithms import DegreeBasic
    from raphtory_tpu.jobs.manager import AnalysisManager, ViewQuery
    from raphtory_tpu.jobs.rest import RestServer

    monkeypatch.delenv("RTPU_SLO_TARGET", raising=False)
    g = _graph(1_200, name="adv_rest2", seed=73)
    mgr = AnalysisManager(g)
    job = mgr.submit(DegreeBasic(), ViewQuery(g.latest_time),
                     tenant="surface_t")
    assert job.wait(120) and job.status == "done", job.error
    srv = RestServer(mgr, port=0).start()
    try:
        hz = _rest(srv, "/healthz")
        assert hz["status"] == "ok" and hz["strict"] is False
        az = _rest(srv, "/advisez?cluster=0")
        assert az["enabled"] is True
        assert isinstance(az["findings"], list)
        assert "cluster" not in az           # ?cluster=0 stays local
        assert az["read_only"].startswith("findings recommend")
        sz = _rest(srv, "/statusz")
        assert "surface_t" in sz["workload"]["tenants"]
        assert sz["budget"]["grade"] in ("ok", "degraded", "burning")
        assert {"enabled", "ticks", "findings",
                "rule_ids"} <= set(sz["advisor"])
        json.dumps(sz)
    finally:
        srv.stop()


# --------------------------------------------- /clusterz federation math


def test_clusterz_merges_workload_and_advisor_blocks():
    from raphtory_tpu.obs.cluster import _merge_advisor, _merge_workload

    procs = {
        "process_0": {"reachable": True, "workload": {"tenants": {
            "acme": {"queries": 2, "cost_seconds": 1.0,
                     "queue_wait_seconds": 0.1},
            "zeta": {"queries": 1, "cost_seconds": 0.2,
                     "queue_wait_seconds": 0.0}}},
            "advisor": {"findings": 1, "rule_ids": ["watermark-stale"]}},
        "process_1": {"reachable": True, "workload": {"tenants": {
            "acme": {"queries": 3, "cost_seconds": 2.0,
                     "queue_wait_seconds": 0.4}}},
            "advisor": {"findings": 2,
                        "rule_ids": ["watermark-stale", "shard-skew"]}},
        "process_2": {"reachable": False,
                      "workload": {"tenants": {"ghost": {
                          "queries": 9, "cost_seconds": 9.0,
                          "queue_wait_seconds": 9.0}}},
                      "advisor": {"findings": 5, "rule_ids": ["x"]}},
    }
    wl = _merge_workload(procs)
    assert wl["n_tenants"] == 2           # the dead peer contributes 0
    acme = wl["tenants"]["acme"]
    assert acme["queries"] == 5
    assert acme["cost_seconds"] == pytest.approx(3.0)
    assert acme["queue_wait_seconds"] == pytest.approx(0.5)
    assert set(acme["by_process"]) == {"process_0", "process_1"}
    # ordered by mesh-wide cost
    assert list(wl["tenants"]) == ["acme", "zeta"]
    adv = _merge_advisor(procs)
    assert adv["findings"] == 3
    assert adv["rules"] == {
        "shard-skew": ["process_1"],
        "watermark-stale": ["process_0", "process_1"]}
