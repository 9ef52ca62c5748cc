"""perfwatch — the perf-regression sentinel over the BENCH_* trajectory.

The repo has committed one benchmark artifact per round since PR 2, but
the history was write-only: nothing READ the JSON, so a regression only
surfaced if a human happened to diff numbers across rounds. perfwatch
closes the loop (stdlib-only, like the rest of ``raphtory_tpu.analysis``
— ``tools/perfwatch`` loads it with zero runtime deps):

1. **Collect** — every ``BENCH_*.json`` artifact is parsed tolerantly
   (the formats drifted across rounds: ``{row}``, ``{rows}``,
   ``{parsed}``, suite ``{rows}``, and raw bench JSONL output), keyed by
   the row's ``config`` (fallback: metric string), ordered by the round
   number in the filename.
2. **Fit** — per metric, a noise band around the history median. The
   band floor depends on the UNIT class, because the trajectory spans
   different machines (dev container, CI runners, the TPU rig):
   *ratio-like* metrics (``percent_*``, ``x_*`` speedups) are
   machine-portable and get tight bands; *absolute* metrics
   (``views/sec``, ``seconds``, ``updates/sec``) drift with the host and
   get wide bands. Spread widens the band further (median absolute
   deviation, scaled).
3. **Judge** — the head value (an explicit ``--head`` file, or the
   highest-round artifact when absent) regresses when it falls outside
   the band in the unit's "worse" direction. Exit 1 on any regression;
   ``--report`` writes the full judgement JSON for the CI artifact.

The ledger snapshot ``bench.py --config ledger_overhead`` embeds in its
row (``detail.ledger``) rides through the same machinery: its phase
seconds surface as extra watchable series once two rounds carry them.

``--selftest`` runs the built-in calibration (a synthetic 2x slowdown
must flag; a within-noise head must pass) — the cheap CI step that
proves the sentinel can actually fire before it is trusted to gate.
"""

from __future__ import annotations

import argparse
import glob as _glob
import json
import os
import re
import statistics
import sys

#: per-unit-class (direction, relative-band floor). Direction is which
#: way "worse" points; the floor is the minimum relative deviation that
#: counts as a regression (wide for machine-dependent absolutes, tight
#: for portable ratios). ``percent`` units use an ABSOLUTE band in
#: percentage points instead (a 1% → 3% overhead move is +2pp, not 3x).
_UNIT_CLASSES = (
    # improvement-direction percents (percent_faster_*) must match BEFORE
    # the generic lower-is-better percent rule — a measured speedup
    # coming in ABOVE its trajectory is good news, not a regression
    ("percent_faster", ("higher", None)),
    ("percent", ("lower", None)),        # absolute band, see _PERCENT_PP
    ("x_", ("higher", 0.30)),
    ("views/sec", ("higher", 0.45)),
    ("updates/sec", ("higher", 0.45)),
    ("seconds", ("lower", 0.45)),
    ("error", (None, None)),             # never judged
)
_PERCENT_PP = 10.0    # percentage-point band floor for percent units
_MAD_SCALE = 4.0      # band widens by this many scaled MADs


def _unit_rule(unit: str):
    unit = (unit or "").lower()
    for prefix, rule in _UNIT_CLASSES:
        if unit.startswith(prefix) or prefix in unit:
            return rule
    return (None, None)


#: round assigned to artifacts with no rNN in the filename
#: (BENCH_SUITE_LATEST.json): "undated" artifacts are the newest run by
#: convention, so they sort after every numbered round
_ROUND_LATEST = 10**6


def _round_of(path: str) -> int:
    m = re.search(r"r(\d+)", os.path.basename(path))
    return int(m.group(1)) if m else _ROUND_LATEST


def _is_row(obj) -> bool:
    return (isinstance(obj, dict) and "value" in obj
            and ("metric" in obj or "config" in obj))


def journal_rows(directory: str) -> list[dict]:
    """Bench-shaped rows derived from a telemetry-journal directory
    (obs/journal.py segments): per-algorithm per-phase median seconds
    from the journaled query ledgers, plus per-span-name duration
    medians. A journal dir passed as trajectory or ``--head`` thereby
    rides the same band machinery as a committed BENCH artifact — the
    postmortem plane's evidence doubles as a perf series."""
    from . import postmortem

    profile = postmortem._run_profile(
        postmortem.merge_records(postmortem.load_segments([directory])))
    rows = []
    for prefix, table in (("journal_phase", "phase_seconds"),
                          ("journal_span", "span_seconds")):
        for key, st in sorted(profile[table].items()):
            rows.append({"config": f"{prefix}:{key}",
                         "value": st["median"], "unit": "seconds",
                         "detail": {"n": st["n"]}})
    return rows


def load_rows(path: str) -> list[dict]:
    """Bench rows from one artifact, across every format the repo has
    committed: ``{row}``, ``{rows}``, ``{parsed}``, a bare row, a list of
    rows, or bench.py's raw JSONL stdout. A DIRECTORY is read as a
    telemetry-journal dir (``journal_rows``)."""
    if os.path.isdir(path):
        return journal_rows(path)
    with open(path) as f:
        text = f.read()
    try:
        doc = json.loads(text)
    except json.JSONDecodeError:
        rows = []
        for line in text.splitlines():
            line = line.strip()
            if not line:
                continue
            try:
                obj = json.loads(line)
            except json.JSONDecodeError:
                continue
            if _is_row(obj):
                rows.append(obj)
        return rows
    if isinstance(doc, list):
        return [r for r in doc if _is_row(r)]
    if not isinstance(doc, dict):
        return []
    if _is_row(doc):
        return [doc]
    out = []
    for key in ("row", "parsed"):
        if _is_row(doc.get(key)):
            out.append(doc[key])
    for r in doc.get("rows") or []:
        if _is_row(r):
            out.append(r)
    return out


def _key_of(row: dict) -> str:
    return str(row.get("config") or row.get("metric"))


def collect_series(paths) -> dict:
    """{metric_key: [(round, value, unit), ...]} over the artifacts,
    ascending by round (ties keep file order). Non-numeric and
    error-unit rows are dropped."""
    series: dict[str, list] = {}
    for path in sorted(paths, key=_round_of):
        rnd = _round_of(path)
        try:
            rows = load_rows(path)
        except OSError:
            continue
        for row in rows:
            val = row.get("value")
            unit = str(row.get("unit") or "")
            if not isinstance(val, (int, float)) or "error" in unit:
                continue
            series.setdefault(_key_of(row), []).append(
                (rnd, float(val), unit))
    return series


def judge(history: list[float], head: float, unit: str) -> dict:
    """One metric's verdict: fit the noise band over ``history`` and
    place ``head`` against it. Returns a judgement dict with
    ``regressed`` set; non-judgeable units / empty history report
    ``skipped`` with the reason."""
    direction, rel_floor = _unit_rule(unit)
    out = {"unit": unit, "head": head, "n_history": len(history),
           "regressed": False}
    if direction is None:
        out["skipped"] = f"unit {unit!r} not judged"
        return out
    if not history:
        out["skipped"] = "no history"
        return out
    base = statistics.median(history)
    mad = (statistics.median(abs(h - base) for h in history)
           if len(history) > 1 else 0.0)
    out["baseline_median"] = round(base, 6)
    out["history_mad"] = round(mad, 6)
    if "percent" in (unit or "").lower():
        band = max(_PERCENT_PP, _MAD_SCALE * mad)
        worse_by = (head - base) if direction == "lower" else (base - head)
        out["band_abs_pp"] = round(band, 3)
        out["worse_by_pp"] = round(worse_by, 3)
        out["regressed"] = worse_by > band
        return out
    scale = max(abs(base), 1e-12)
    band = max(rel_floor, _MAD_SCALE * mad / scale)
    worse_by = ((head - base) if direction == "lower"
                else (base - head)) / scale
    out["band_rel"] = round(band, 4)
    out["worse_by_rel"] = round(worse_by, 4)
    out["regressed"] = worse_by > band
    return out


def check(trajectory_paths, head_path: str | None = None,
          min_points: int = 1) -> dict:
    """The full sentinel pass. With ``head_path``, its rows are judged
    against the whole trajectory. Without it (audit mode — what the test
    suite runs over the committed repo files), every series' LATEST
    point is judged against that series' own earlier points, so each
    metric is covered regardless of which round's artifact carries it.
    """
    paths = list(trajectory_paths)
    judgements = {}
    regressions = []

    def judge_one(key, hist, head_val, unit):
        if len(hist) < min_points:
            judgements[key] = {
                "unit": unit, "head": head_val,
                "n_history": len(hist), "regressed": False,
                "skipped": f"history has {len(hist)} < {min_points} points"}
            return
        j = judge(hist, head_val, unit)
        judgements[key] = j
        if j["regressed"]:
            regressions.append(key)

    if head_path is not None:
        history = collect_series(paths)
        heads = collect_series([head_path])
        if not heads:
            # an empty/crashed head must FAIL the gate, not sail through
            # with zero judgements — the sentinel's own failure mode
            raise ValueError(
                f"no judgeable bench rows in head {head_path!r} — did the "
                "bench run crash? (error-unit rows are excluded)")
        for key, pts in heads.items():
            hist = [v for _, v, _ in history.get(key, [])]
            judge_one(key, hist, pts[-1][1], pts[-1][2])
    else:
        for key, pts in collect_series(paths).items():
            if len(pts) < 2:
                judgements[key] = {
                    "unit": pts[-1][2], "head": pts[-1][1],
                    "n_history": 0, "regressed": False,
                    "skipped": "single point — nothing to judge against"}
                continue
            last_round = max(r for r, _, _ in pts)
            head_pts = [p for p in pts if p[0] == last_round]
            hist = [v for r, v, _ in pts if r != last_round]
            judge_one(key, hist, head_pts[-1][1], head_pts[-1][2])
    return {
        "head": [head_path] if head_path else "per-series latest round",
        "trajectory": paths,
        "judgements": judgements,
        "regressions": sorted(regressions),
        "ok": not regressions,
    }


def selftest() -> int:
    """Calibration: the sentinel must FLAG a synthetic 2x slowdown and
    PASS a within-noise head, for both a throughput unit and a percent
    unit. Returns 0 on success (the CI gate runs this before trusting
    the real comparison)."""
    cases = [
        # (history, head, unit, must_flag)
        ([10.0, 10.3, 9.8], 5.0, "views/sec", True),     # 2x slowdown
        ([10.0, 10.3, 9.8], 9.6, "views/sec", False),    # noise
        ([1.2, 3.8], 100.0, "percent_overhead", True),   # 2x-slowdown arm
        ([1.2, 3.8], 6.0, "percent_overhead", False),    # noisy CI runner
        ([35.0, 40.0], 2.0, "percent_faster_with_scheduler", True),   # lost
        ([35.0, 40.0], 55.0, "percent_faster_with_scheduler", False),  # won
        ([1.6], 0.9, "x_fold_speedup", True),            # speedup lost
        ([0.02, 0.025], 0.05, "seconds", True),          # 2x slower view
        ([0.02, 0.025], 0.024, "seconds", False),
    ]
    failed = []
    for hist, head, unit, must_flag in cases:
        j = judge(hist, head, unit)
        if bool(j["regressed"]) != must_flag:
            failed.append((hist, head, unit, must_flag, j))
    for case in failed:
        print(f"perfwatch selftest FAILED: {case}", file=sys.stderr)
    print(f"perfwatch selftest: {len(cases) - len(failed)}/{len(cases)} "
          f"calibration cases behaved")
    return 1 if failed else 0


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        prog="perfwatch",
        description="perf-regression sentinel over BENCH_*.json artifacts")
    ap.add_argument("trajectory", nargs="*",
                    help="trajectory artifacts/globs "
                         "(default: BENCH_*.json in cwd)")
    ap.add_argument("--head", default=None,
                    help="candidate artifact (bench JSON/JSONL); without "
                         "it the highest-round trajectory file is judged "
                         "against the earlier rounds")
    ap.add_argument("--report", default=None,
                    help="write the full judgement JSON here (CI artifact)")
    ap.add_argument("--min-points", type=int, default=1,
                    help="history points required before judging a metric")
    ap.add_argument("--selftest", action="store_true",
                    help="run the built-in band calibration and exit")
    args = ap.parse_args(argv)

    if args.selftest:
        return selftest()

    patterns = args.trajectory or ["BENCH_*.json"]
    paths = []
    for pat in patterns:
        hits = sorted(_glob.glob(pat))
        paths.extend(hits if hits else ([pat] if os.path.exists(pat)
                                        else []))
    if not paths:
        print("perfwatch: no trajectory artifacts found", file=sys.stderr)
        return 2
    try:
        result = check(paths, head_path=args.head,
                       min_points=args.min_points)
    except (ValueError, OSError) as e:
        print(f"perfwatch: {e}", file=sys.stderr)
        return 2
    if args.report:
        with open(args.report, "w") as f:
            json.dump(result, f, indent=1)
    judged = [k for k, j in result["judgements"].items()
              if "skipped" not in j]
    print(f"perfwatch: {len(judged)} metrics judged, "
          f"{len(result['judgements']) - len(judged)} skipped, "
          f"{len(result['regressions'])} regressions")
    for key in result["regressions"]:
        j = result["judgements"][key]
        worse = j.get("worse_by_rel", j.get("worse_by_pp"))
        print(f"  REGRESSION {key}: head={j['head']} vs "
              f"median={j.get('baseline_median')} ({j['unit']}, "
              f"worse_by={worse})", file=sys.stderr)
    return 1 if result["regressions"] else 0


if __name__ == "__main__":
    sys.exit(main())
