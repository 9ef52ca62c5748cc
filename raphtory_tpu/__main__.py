"""``python -m raphtory_tpu`` — the single-node server entrypoint.

The reference deploys as a JVM binary whose role and wiring come from env
vars (``Server.scala:28-62`` reading SPOUTCLASS/ROUTERCLASS etc.); the
TPU-native equivalent boots a ``NodeRuntime`` (ingestion + storage +
analysis + REST + metrics + archivist) from the same env-var ergonomics
(``RAPHTORY_TPU_*`` — utils/config.Settings) plus a couple of CLI flags:

    python -m raphtory_tpu serve --csv edges.csv
    python -m raphtory_tpu serve --random 100000

``serve`` starts the REST job API (:8081) and Prometheus metrics (:11600),
ingests the given sources, and then keeps serving queries until SIGINT.
"""

from __future__ import annotations

import argparse
import signal
import sys
import threading


def _serve(args) -> int:
    if args.platform:
        import jax   # must precede any backend use

        from .utils.config import configure_compile_cache

        jax.config.update("jax_platforms", args.platform)
        configure_compile_cache()   # a CPU-pinned process keeps no cache
    from .cluster.runtime import NodeRuntime
    from .ingestion.parser import (CsvEdgeListParser, IntCsvEdgeListParser,
                                   JsonUpdateParser)
    from .ingestion.source import FileSource, RandomSource
    from .utils.config import Settings

    settings = Settings.from_env()
    rt = NodeRuntime(settings=settings)
    parsers = {
        "int-csv": IntCsvEdgeListParser,
        "csv": CsvEdgeListParser,
        "json": JsonUpdateParser,
    }
    for path in args.csv or []:
        rt.add_source(FileSource(path, skip_header=args.skip_header),
                      parsers[args.format]())
    if args.random:
        rt.add_source(RandomSource(args.random, seed=args.seed))
    rt.start(rest=True, metrics=True)
    print(f"raphtory_tpu node up: REST :{settings.rest_port} "
          f"metrics :{settings.metrics_port}", flush=True)

    def _ingest_summary(aborted=lambda: False):
        # the event-TIME range is the operator's cheapest sanity check: a
        # CSV parsed with the wrong column order (e.g. time,src,dst fed to
        # the src,dst,time parser) ingests "successfully" with vertex ids
        # as timestamps, and latest_time gives it away at a glance.
        # earliest/latest are O(1) maintained marks, not column scans
        n = sum(rt.pipeline.counts.values())
        rng = (f"event times [{rt.graph.earliest_time}, "
               f"{rt.graph.latest_time}], " if len(rt.graph.log)
               else "empty log, ")
        word = "aborted" if aborted() else "done"
        print(f"ingest {word}: {n} updates, {rng}"
              f"safe_time={rt.graph.safe_time()}", flush=True)

    rt.ingest(wait=False)
    if args.ingest_only:
        # default signal behaviour stays in place: Ctrl-C / SIGTERM abort
        # the blocking join instead of being swallowed by a no-op handler
        rt.pipeline.join()
        _ingest_summary()
    else:
        stop = threading.Event()
        threading.Thread(
            target=lambda: (rt.pipeline.join(),
                            _ingest_summary(aborted=stop.is_set)),
            name="ingest-summary", daemon=True).start()
        signal.signal(signal.SIGINT, lambda *a: stop.set())
        signal.signal(signal.SIGTERM, lambda *a: stop.set())
        stop.wait()
    rt.stop()
    return 0


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(prog="raphtory_tpu")
    sub = ap.add_subparsers(dest="cmd", required=True)
    sv = sub.add_parser("serve", help="run a single-node analysis server")
    sv.add_argument("--csv", action="append",
                    help="ingest a CSV edge-list file (repeatable)")
    sv.add_argument("--format", choices=["int-csv", "csv", "json"],
                    default="int-csv")
    sv.add_argument("--skip-header", action="store_true")
    sv.add_argument("--random", type=int, default=0,
                    help="also ingest N synthetic updates (RandomSource)")
    sv.add_argument("--seed", type=int, default=0)
    sv.add_argument("--ingest-only", action="store_true",
                    help="exit after sources drain (batch import mode)")
    sv.add_argument("--platform", default=None,
                    help="force a JAX platform (e.g. cpu) before backend init")
    return _serve(ap.parse_args(argv))


if __name__ == "__main__":
    sys.exit(main())
