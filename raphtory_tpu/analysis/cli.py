"""rtpulint command line — scan, baseline-diff, report.

``tools/rtpulint raphtory_tpu/`` is the CI entry point: exit 0 when every
finding is covered by the checked-in baseline, exit 1 on new findings (or
parse errors), exit 2 on usage errors. ``--write-baseline`` refreshes the
baseline after a reviewed change; ``--format json`` emits the machine
report CI uploads as an artifact.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

from .findings import Baseline
from .rules import RULES, analyze_project

DEFAULT_BASELINE = os.path.join("tools", "rtpulint_baseline.json")
DEFAULT_DOCS = os.path.join("docs", "OPERATIONS.md")


def _is_python_script(path: str) -> bool:
    """Extensionless executables with a python shebang (tools/rtpulint,
    tools/rtpu-postmortem) are source too — the tools/ scan must not skip the
    linter's own drivers."""
    try:
        with open(path, "rb") as fh:
            first = fh.readline(120)
        return first.startswith(b"#!") and b"python" in first
    except OSError:
        return False


def _iter_py_files(paths: list[str]) -> list[str]:
    out = []
    for p in paths:
        if os.path.isfile(p):
            out.append(p)
            continue
        for dirpath, dirnames, filenames in os.walk(p):
            dirnames[:] = sorted(d for d in dirnames
                                 if d not in ("__pycache__", ".git"))
            for f in sorted(filenames):
                full = os.path.join(dirpath, f)
                if f.endswith(".py") or \
                        ("." not in f and _is_python_script(full)):
                    out.append(full)
    return out


def _load(path: str, root: str) -> tuple[str, str]:
    rel = os.path.relpath(path, root)
    with open(path, encoding="utf-8") as fh:
        return rel.replace(os.sep, "/"), fh.read()


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(
        prog="rtpulint",
        description="project-specific static analysis for raphtory_tpu "
                    "(rule catalogue: docs/STATIC_ANALYSIS.md)")
    ap.add_argument("paths", nargs="+", help="files or directories to scan")
    ap.add_argument("--root", default=".",
                    help="repo root findings are reported relative to "
                         "(default: cwd)")
    ap.add_argument("--baseline", default=None,
                    help=f"baseline json (default: <root>/{DEFAULT_BASELINE} "
                         f"when present)")
    ap.add_argument("--no-baseline", action="store_true",
                    help="ignore any baseline: report every finding as new")
    ap.add_argument("--write-baseline", action="store_true",
                    help="accept the current findings as the new baseline")
    ap.add_argument("--docs", default=None,
                    help=f"knob-table doc for undocumented-knob "
                         f"(default: <root>/{DEFAULT_DOCS})")
    ap.add_argument("--rule", action="append", default=None,
                    metavar="RULE", help="only run the named rule(s) "
                    "(id or slug; repeatable)")
    ap.add_argument("--format", choices=("text", "json"), default="text")
    ap.add_argument("--output", default=None,
                    help="also write the json report here (any --format)")
    ap.add_argument("--fix", action="store_true",
                    help="apply mechanical autofixes in place (RT008 "
                         "unused-import; idempotent, pragma-respecting) "
                         "before reporting")
    ap.add_argument("--fix-diff", default=None, metavar="PATH",
                    help="write the unified diff --fix WOULD apply to "
                         "PATH without modifying any file (the CI "
                         "suggestion artifact)")
    ap.add_argument("--timings", action="store_true",
                    help="report per-rule wall seconds (text: stderr "
                         "table; always included in the json report)")
    ap.add_argument("--budget-seconds", type=float, default=None,
                    metavar="S", help="fail (exit 1) when the analysis "
                    "itself takes longer than S seconds — the CI proof "
                    "that the interprocedural pass stays fast")
    args = ap.parse_args(argv)

    root = os.path.abspath(args.root)
    files = _iter_py_files(args.paths)
    if not files:
        print("rtpulint: no python files under " + ", ".join(args.paths),
              file=sys.stderr)
        return 2

    docs_path = args.docs or os.path.join(root, DEFAULT_DOCS)
    docs_text = ""
    if os.path.exists(docs_path):
        with open(docs_path, encoding="utf-8") as fh:
            docs_text = fh.read()
    docs_name = os.path.relpath(docs_path, root).replace(os.sep, "/")

    rules = None
    if args.rule:
        rules = set()
        slugs = {v: k for k, v in RULES.items()}
        for r in args.rule:
            if r not in RULES and r not in slugs:
                print(f"rtpulint: unknown rule {r!r} "
                      f"(known: {', '.join(sorted(RULES))} / "
                      f"{', '.join(sorted(slugs))})", file=sys.stderr)
                return 2
            rules.add(RULES.get(r, r))
            rules.add(slugs.get(r, r))

    sources = [_load(f, root) for f in files]

    fixed_names = 0
    if args.fix or args.fix_diff:
        from .fixes import fix_files, unified_diff

        fixed, fixed_names = fix_files(sources)
        if args.fix_diff:
            with open(args.fix_diff, "w", encoding="utf-8") as fh:
                for rel in sorted(fixed):
                    old = next(s for r, s in sources if r == rel)
                    fh.write(unified_diff(rel, old, fixed[rel]))
            print(f"rtpulint: wrote fix suggestions for {len(fixed)} "
                  f"file(s) ({fixed_names} import(s)) to {args.fix_diff}",
                  file=sys.stderr)
        if args.fix:
            by_rel = dict(zip([r for r, _ in sources], files))
            for rel, new_src in sorted(fixed.items()):
                with open(by_rel[rel], "w", encoding="utf-8") as fh:
                    fh.write(new_src)
            if fixed:
                print(f"rtpulint: fixed {fixed_names} unused import(s) "
                      f"in {len(fixed)} file(s)", file=sys.stderr)
            # report on the FIXED sources — --fix then exits by what's left
            sources = [(r, fixed.get(r, s)) for r, s in sources]

    timings: dict = {}
    findings = analyze_project(sources,
                               docs_text=docs_text, docs_name=docs_name,
                               rules=rules, timings=timings)
    analysis_seconds = sum(timings.values())

    baseline_path = args.baseline or os.path.join(root, DEFAULT_BASELINE)
    if args.write_baseline:
        if args.rule:
            # a filtered run only saw a slice of the findings — writing it
            # would silently drop every other rule's accepted entries
            print("rtpulint: refusing --write-baseline with --rule; "
                  "run the full rule set to regenerate the baseline",
                  file=sys.stderr)
            return 2
        Baseline.from_findings(findings).save(baseline_path)
        print(f"rtpulint: wrote {len(findings)} finding(s) to "
              f"{baseline_path}")
        return 0

    baseline = Baseline()
    baseline_used = False
    if not args.no_baseline and os.path.exists(baseline_path):
        baseline = Baseline.load(baseline_path)
        baseline_used = True
    new, accepted, stale = baseline.split(findings)

    report = {
        "tool": "rtpulint",
        "files_scanned": len(files),
        "rules": sorted(RULES.values()),
        "baseline": baseline_path if baseline_used else None,
        "total": len(findings),
        "new": [f.as_dict() for f in new],
        "accepted": [f.as_dict() for f in accepted],
        "stale_baseline_entries": stale,
        "timings_seconds": {k: round(v, 3)
                            for k, v in sorted(timings.items())},
        "analysis_seconds": round(analysis_seconds, 3),
        "autofixed_imports": fixed_names if args.fix else 0,
    }
    if args.output:
        with open(args.output, "w") as fh:
            json.dump(report, fh, indent=1)
            fh.write("\n")

    if args.format == "json":
        json.dump(report, sys.stdout, indent=1)
        sys.stdout.write("\n")
    else:
        for f in new:
            print(f.render())
        tail = (f"rtpulint: {len(files)} files, {len(findings)} finding(s): "
                f"{len(new)} new, {len(accepted)} baselined")
        if stale:
            tail += (f", {stale} stale baseline entr"
                     f"{'y' if stale == 1 else 'ies'} (consider "
                     f"--write-baseline)")
        print(tail)
    if args.timings:
        for rule_id, sec in sorted(timings.items()):
            print(f"rtpulint:   {rule_id:<8} {sec:7.3f}s", file=sys.stderr)
        print(f"rtpulint:   total    {analysis_seconds:7.3f}s",
              file=sys.stderr)
    if args.budget_seconds is not None and \
            analysis_seconds > args.budget_seconds:
        print(f"rtpulint: analysis took {analysis_seconds:.1f}s — over "
              f"the {args.budget_seconds:.0f}s budget", file=sys.stderr)
        return 1
    return 1 if new else 0


if __name__ == "__main__":
    sys.exit(main())
