#!/usr/bin/env python3
"""One run of one benchmark cell:

    python3 benchmark/run.py --workload <config>.<traffic> --seed N \
        --seconds S --trace 0|1

One process that owns the chip(s): it builds the same ``NodeRuntime`` +
``RestServer`` as ``python -m raphtory_tpu serve``, in-process, ingests
the bulk log made from ``--seed``, warms the cell's own shapes (all of
that is ``setup_s``), then drives the cell's traffic over REST on
localhost for ``--seconds`` seconds, and afterwards checks a seeded
sample of the served rows against the plain numpy reference
(``reference.py``). Earlier lines are free-form JSON; the last line of
stdout is the result object of the builder's contract. ``--trace 0``
reports the cell's end-to-end metrics, ``--trace 1`` its per-layer
metrics (a profiler trace is taken over whole requests or epochs inside
the window). A window ends at ``--seconds`` (the request in flight is
killed and not counted) or, in a closed loop, where the traffic file's
schedule holds no further request (``schedule_used_up`` on the work
line; nothing is in flight): either way only what completed counts, and
a rate runs to the last completion.

Without a TPU (or with fewer chips than the cell asks for) it exits
non-zero and prints no result. ``--rehearsal`` runs a tiny size on the
CPU (virtual devices for a four-chip cell): a plumbing check that says
``cpu`` on every line and can never print ``"correct": true``.

A cell is an entry of ``BENCHMARK.json``'s ``workloads``; its
configuration, traffic mix and each metric are one JSON file each under
``configs/``, ``traffic/``, ``end_to_end/`` and ``layer_metrics/``,
found by name; the configuration's ``algorithm.module`` names its
reference under ``algorithms/`` and the traffic's ``loop`` its driver
under ``loops/`` — adding a cell, a metric, an algorithm or a kind of
loop adds files and edits none.

What a later PR's cell is made of (``tests/benchmark`` holds a made-up
one to every rule, ``benchmark_rules.py``):
  files     ``configs/<c>.json`` (its sizes, ``source``, ``reduced``,
            guarantees and ``correct.limits``); ``traffic/<t>.json``
            only for a new mix; ``layer_metrics/<m>.json`` (a reducer of
            ``layers.py`` and its parameters) for each new metric.
  entries   appended to BENCHMARK.json: the configuration, the workload
            ``<c>.<t>``, the cell's name on the ``workloads`` list of
            every metric it reports (``setup.*`` among them; a per-layer
            metric's ``moves`` must be an end-to-end metric the cell
            reports), new ``per_layer`` entries last, each with its list.
  pinned    a traffic file's ``routes.*.kernels`` is checked by
            ``correct``: a cell of another algorithm or engine needs a
            traffic file of its own.
Nothing else is edited: no code, no test, no file that is there.
"""

from __future__ import annotations

import argparse
import json
import os
import shutil
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

# the flight recorder supplies the route evidence and the spans the
# per-layer metrics read; it is on in every run, traced or not, so both
# kinds of run do the same work. Must be set before the package creates
# its tracer.
os.environ.setdefault("RTPU_TRACE", "1")

import numpy as np  # noqa: E402

from benchmark import (BenchFailure, algorithms, client, gen,  # noqa: E402
                       layers, loops, reference, xplane)

#: the host spans a device idle gap is named by (the innermost one that
#: covers it, ``xplane.gaps_by_span``); no metric reads the names. From
#: ``fold.seed`` on: the stages the program writes under ``hop.fold``,
#: ``engine.build`` and ``comm.exchange``.
HOST_SPANS = ("rest.request", "job", "sweep.columnar", "hop.fold",
              "hop.ship", "hop.compute", "ship.stage", "ship.wire",
              "superstep.block", "snapshot.fold", "bsp.dispatch",
              "live.epoch", "comm.exchange", "xla.compile", "fold.stall",
              "ingest.append", "engine.build", "engine.layout",
              "comm.block_wait", "job.emit", "job.publish",
              "fold.fingerprint",
              "fold.seed", "fold.advance", "fold.payload", "fold.checkpoint",
              "index.ids", "index.pairs", "index.tables", "index.fork",
              "index.lookup", "index.triangles", "comm.put")


def load_json(*parts):
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def load_cell(workload: str, root: str = ROOT, here: str = HERE) -> dict:
    """Everything the cell's data files say: the workload entry, its
    configuration and traffic, and the spec of every metric the cell
    reports, each found by name."""
    bench = load_json(root, "BENCHMARK.json")
    cell = next((w for w in bench["workloads"] if w["name"] == workload),
                None)
    if cell is None:
        raise BenchFailure(f"no workload {workload!r} in BENCHMARK.json")

    def mine(metric):
        return workload in metric.get("workloads", [workload])

    def specs(kind, folder):
        out = []
        for m in bench[kind]:
            if mine(m):
                out.append({**load_json(here, folder, m["name"] + ".json"),
                            "name": m["name"], "unit": m["unit"]})
        return out

    return {"bench": bench, "cell": cell,
            "config": load_json(here, "configs", cell["config"] + ".json"),
            "traffic": load_json(here, "traffic", cell["traffic"] + ".json"),
            "end_to_end": specs("end_to_end", "end_to_end"),
            "per_layer": specs("per_layer", "layer_metrics")}


def merge(base: dict, over: dict) -> dict:
    out = dict(base)
    for k, v in over.items():
        out[k] = merge(out[k], v) if isinstance(v, dict) \
            and isinstance(out.get(k), dict) else v
    return out


def say(label: str, phase: str, **kw):
    print(json.dumps({"phase": phase, "device": label, **kw}), flush=True)


# --------------------------------------------------------------- the run


class Run:
    def __init__(self, args, loaded: dict, label: str, device: dict):
        self.args, self.label, self.device = args, label, device
        self.cell = loaded["cell"]
        self.cfg, self.traffic = loaded["config"], loaded["traffic"]
        self.seed = int(args.seed)
        self.rt = self.rest = None
        self.rec: dict = {"setup": {}}
        self.algo = algorithms.load(self.cfg["algorithm"]["module"])
        self.loop = loops.load(self.traffic["loop"])(self)
        # a traced run's profile: a directory of this process's own, so
        # two traced runs of one checkout never empty each other's
        self.trace_dir = os.path.join(ROOT, ".bench_trace", str(os.getpid()))

    def say(self, phase, **kw):
        say(self.label, phase, **kw)

    # -- set-up ---------------------------------------------------------

    def source(self, name: str, batches):
        """A source of the program's around a generator of column batches."""
        from raphtory_tpu.ingestion.source import Source

        class Columns(Source):
            disorder = 0

            def iter_batches(self):
                return batches()

        src = Columns()
        src.name = name
        return src

    def program_kinds(self, kinds):
        """The program's event codes for gen.py's (VADD, VDEL, EADD, EDEL)."""
        from raphtory_tpu.core import events as ev

        return np.array([ev.VERTEX_ADD, ev.VERTEX_DELETE, ev.EDGE_ADD,
                         ev.EDGE_DELETE], np.uint8)[kinds]

    def boot(self):
        import jax

        from raphtory_tpu.cluster.runtime import NodeRuntime
        from raphtory_tpu.utils.config import Settings

        t0 = time.perf_counter()
        times, src, dst = gen.bulk_log(self.cfg, self.seed)
        self.columns = (times, np.full(len(times), gen.EADD, np.uint8),
                        src, dst)
        kinds = self.program_kinds(self.columns[1])
        self.rec["setup"]["generate_s"] = time.perf_counter() - t0

        def bulk(batch: int = 1 << 22):
            for off in range(0, len(times), batch):
                sl = slice(off, off + batch)
                yield times[sl], kinds[sl], src[sl], dst[sl]

        mesh = None
        if self.cfg.get("mesh"):
            from raphtory_tpu.parallel import sharded

            m = self.cfg["mesh"]
            mesh = sharded.make_mesh(n_vertex_shards=m["vertices"],
                                     n_window_shards=m["windows"],
                                     devices=jax.devices()[:self.cell["chips"]])
        self.rt = NodeRuntime(settings=Settings(rest_port=0, metrics_port=0),
                              mesh=mesh)
        self.rt.add_source(self.source("bulk", bulk))
        self.rt.start(rest=True, metrics=False)
        self.rest = client.Rest(self.rt._rest.port)
        t0 = time.perf_counter()
        self.rt.ingest(wait=True)
        dt = time.perf_counter() - t0
        n = self.rt.pipeline.counts["bulk"]
        if self.rt.pipeline.errors or n != len(times):
            raise BenchFailure(f"bulk ingest: {n} of {len(times)} events, "
                               f"errors {self.rt.pipeline.errors}")
        self.rec["setup"].update(bulk_ingest_s=dt,
                                 bulk_ingest_updates_per_s=n / dt)
        self.loop.boot()

    def compile_table(self) -> dict:
        k = self.rest.get("/statusz")["compile_caches"].get("kernels", {})
        return {"compiles": sum(v["compiles"] for v in k.values()),
                "seconds": sum(v["seconds"] for v in k.values())}

    # -- profiler trace, over whole requests or epochs ----------------------

    tracing = False

    def trace_start(self):
        import jax

        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 2
        jax.profiler.start_trace(self.trace_dir, profiler_options=opts)
        self.tracing, self._trace_t0 = True, time.perf_counter()

    def trace_stop(self, items):
        """``items``: the requests or epochs that ran inside the trace."""
        if not self.tracing:
            return
        import jax

        window_s = time.perf_counter() - self._trace_t0
        jax.profiler.stop_trace()
        self.tracing = False
        self.rec["traced"] = {"window_s": window_s, "items": list(items)}

    def reduce_trace(self):
        tr = self.rec.get("traced")
        if not tr:
            return
        path = xplane.newest_trace(self.trace_dir)
        # one dispatch per request or epoch is the fewest times the
        # tables are read, and the vertices and pairs alive in each of
        # its views (the reference's fold, no padding) the least any
        # layout holds
        dispatches = [[self.alive(row) for row in self.loop.rows(it)]
                      for it in tr["items"]]
        least = {"least_bytes": sum(self.algo.least_bytes(
            cols, self.cfg["algorithm"]) for cols in dispatches),
            "traced_views": sum(len(cols) for cols in dispatches)}
        if self.device["platform"] != "tpu":
            # a CPU trace has no device plane: device numbers are not
            # measured there, and the line leaves them out
            self.say("trace_not_reduced", trace_bytes=os.path.getsize(path),
                     traced_s=round(tr["window_s"], 3),
                     traced_items=len(tr["items"]), **least)
            return
        self.rec["xplane"] = {
            **xplane.reduce_trace(path, HOST_SPANS, tr["window_s"]), **least}

    # -- after the window ------------------------------------------------------

    def collect(self, compiles_at_setup: dict):
        import jax

        rec = self.rec
        after = self.compile_table()
        rec["window_compiles"] = after["compiles"] \
            - compiles_at_setup["compiles"]
        self.loop.collect()
        ring = self.rest.get("/statusz").get("trace") or {}
        if ring.get("dropped", 0) > 0:
            raise BenchFailure(
                f"the flight recorder's ring wrapped: /statusz trace.dropped "
                f"is {ring['dropped']} of {ring.get('recorded')} events "
                f"(ring_size {ring.get('ring_size')}), so spans of the "
                "window are lost and every span metric would read low")
        rec["shapes"] = shapes_of(self.rest.get("/costz")["kernels"],
                                  self.route_kernels())
        mem = [int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
               for d in jax.devices()[:self.cell["chips"]]]
        rec["memory"] = {"peak_bytes": max(mem), "peak_by_device": mem}
        peaks = rec.get("peaks")
        if peaks:
            rec["memory"]["peak_hbm_share"] = \
                100.0 * max(mem) / peaks["hbm_bytes"]

    def route_kernels(self):
        r = self.traffic["routes"]
        return (r.get("mesh") if self.cfg.get("mesh") and "mesh" in r
                else r["one_chip"])

    def route_failures(self) -> list[str]:
        """The route that served each of the window's jobs, from what the
        program records: the ledger's kernel table and collective routes
        and the job's spans."""
        want, bad = self.route_kernels(), []
        for j in self.loop.jobs():
            led = j["ledger"] or {}
            kernels = sorted((led.get("device") or {}).get("kernels") or {})
            names = {s["name"] for s in j["spans"]}
            dcn = set((led.get("dcn") or {}).get("routes") or {}) | {
                s["args"].get("route") for s in j["spans"]
                if s["name"] == "comm.exchange"}
            for k in want.get("kernels", []):
                if not any(n.startswith(k) for n in kernels):
                    bad.append(f"request {j['k']}: kernel {k}* not in {kernels}")
            for s in want.get("spans", []):
                if s not in names:
                    bad.append(f"request {j['k']}: no span {s}")
            for r in want.get("dcn", []):
                if not any(str(x).startswith(r) for x in dcn):
                    bad.append(f"request {j['k']}: collective route {r}* "
                               f"not in {sorted(map(str, dcn))}")
        return bad

    # -- correctness, outside the window ------------------------------------------

    def sample(self) -> list[dict]:
        """The rows compared: a seeded sample of the window's rows, the
        last one served always among them, one per window size first."""
        n = int(self.traffic["sample_rows"])
        rows = self.served_rows()
        if not rows:
            return []
        rng = np.random.default_rng([self.seed, 0x53414D50])
        picked = [len(rows) - 1]
        by_w: dict = {}
        for i in rng.permutation(len(rows) - 1):
            by_w.setdefault(rows[int(i)]["windowsize"], []).append(int(i))
        pools = [p for w, p in sorted(by_w.items(), key=lambda kv: str(kv[0]))
                 if w != rows[-1]["windowsize"]] + \
            [by_w.get(rows[-1]["windowsize"], [])]
        while len(picked) < min(n, len(rows)) and any(pools):
            for p in pools:
                if p and len(picked) < n:
                    picked.append(p.pop())
        return [rows[i] for i in picked]

    def served_rows(self) -> list[dict]:
        return [row for it in self.loop.done() for row in self.loop.rows(it)]

    def alive(self, row: dict):
        """(vertices, pairs) alive in the view a served row is of."""
        vm, src, _ = self.ref.fold(row["time"], row["windowsize"])
        return int(vm.sum()), len(src)

    def check(self) -> dict:
        """Every number compared, beside its limit."""
        limits, alg = self.cfg["correct"]["limits"], self.cfg["algorithm"]
        rows = self.sample()
        t0 = time.perf_counter()
        cols, n_ids = self.columns, int(self.cfg["graph"]["id_space"])
        more = self.loop.events()
        if more is not None:
            cols = [np.concatenate(pair) for pair in zip(cols, more)]
            n_ids = max(n_ids, int(self.cfg["tail"]["id_pool"]))
        self.ref = reference.RefEvents(*cols, n_ids)
        self.graph_counts = {"pairs": len(self.ref.us), "ids": int(len(
            np.unique(np.concatenate(
                [cols[2], cols[3][cols[1] >= gen.EADD]]))))}
        out, ok = [], bool(rows)
        for row in rows:
            cmp_ = self.algo.compare(row, self.algo.reference(
                *self.ref.fold(row["time"], row["windowsize"]), alg),
                limits, alg)
            ok &= cmp_["ok"]
            out.append({"time": row["time"], "window": row["windowsize"],
                        **cmp_})
        return {"ok": ok, "rows": out, "limits": limits,
                "reference_s": time.perf_counter() - t0}

    def stop(self):
        self.loop.stop()
        if self.rt is not None:
            self.rt.stop()

    def drop_trace(self):
        """The profile goes with the run that took it, however it ends."""
        shutil.rmtree(self.trace_dir, ignore_errors=True)
        try:
            os.rmdir(os.path.dirname(self.trace_dir))   # if no other run's
        except OSError:
            pass


def shapes_of(costz_kernels: list, want: dict) -> dict:
    """Padded shapes of the cell's kernel, from the program's own
    ``/costz`` signatures: every distinct 1-D length (``m_pad``, the
    rows of the dst-sorted pair table a superstep runs, is the largest)
    and the 2-D shapes of the padded per-hop deltas.
    ``n_pad`` is filled in later: the smallest length that holds the
    graph's vertex ids."""
    import re

    names = tuple(want.get("kernels", [])) or ("hopbatch.", "device_sweep.")
    sigs = sorted({k["sig"] for k in costz_kernels
                   if k["kernel"].startswith(names)})
    dims1 = sorted({int(d) for s in sigs
                    for d in re.findall(r"\[(\d+)\]", s)}, reverse=True)
    dims2 = sorted({d for s in sigs for d in re.findall(r"\[(\d+, \d+)\]", s)})
    return {"programs": len(sigs),
            "m_pad": dims1[0] if dims1 else None,
            "lengths_1d": dims1[:8], "deltas_2d": dims2}


# ------------------------------------------------------------------ main


def device_or_fail(cell: dict, rehearsal: bool, require_chip: bool):
    import jax

    if rehearsal:
        jax.config.update("jax_platforms", "cpu")
        jax.config.update("jax_num_cpu_devices", int(cell["chips"]))
    devs = jax.devices()
    dev = devs[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devs)}
    if require_chip and not rehearsal and (
            dev.platform != "tpu" or len(devs) < int(cell["chips"])):
        raise BenchFailure(
            f"cell {cell['name']} needs {cell['chips']} TPU chip(s); jax "
            f"found {device} (--rehearsal is a CPU plumbing check that "
            "never passes)")
    return device


def run_cell(args, *, require_chip: bool = True, tiny: bool = False) -> int:
    """``require_chip=False`` and ``tiny`` are for the tests under
    ``tests/benchmark``: the rest of a run, at the rehearsal's size, on
    whatever device the test process holds."""
    loaded = load_cell(args.workload)
    if args.rehearsal or tiny:
        small = load_json(HERE, "rehearsal.json")
        loaded["config"] = merge(loaded["config"], small["config"])
        loaded["traffic"] = merge(loaded["traffic"], small["traffic"])
    t_setup = time.perf_counter()
    device = device_or_fail(loaded["cell"], args.rehearsal, require_chip)
    import raphtory_tpu  # noqa: F401 — x64 + the compile cache wiring
    from raphtory_tpu.native import lib as native
    from raphtory_tpu.utils import config

    cache_dir = config.configure_compile_cache()
    label = device["platform"] + (" (--rehearsal)" if args.rehearsal else "")
    say(label, "start", workload=args.workload, seed=int(args.seed),
        seconds=args.seconds, trace=args.trace, device=device,
        compile_cache_dir=cache_dir,
        compile_cache_entries=len(os.listdir(cache_dir))
        if cache_dir and os.path.isdir(cache_dir) else 0)
    if not args.rehearsal and not native.available():
        raise BenchFailure("native C++ fold kernels unavailable (g++ build "
                           "failed): the numpy fallback is another program")
    run = Run(args, loaded, label, device)
    try:
        return measure(run, loaded, require_chip, t_setup)
    finally:
        run.drop_trace()


def measure(run: Run, loaded: dict, require_chip: bool,
            t_setup: float) -> int:
    """Set-up, the window, the check and the result line of one run."""
    args, device = run.args, run.device
    peaks = load_json(HERE, "peaks.json")["by_device_kind"]
    if device["platform"] == "tpu":
        if device["kind"] not in peaks:
            raise BenchFailure(f"no peak rates for device kind "
                               f"{device['kind']!r} in benchmark/peaks.json")
        run.rec["peaks"] = peaks[device["kind"]]
    try:
        run.boot()
        run.loop.warm()
        at_setup = run.compile_table()
        run.rec["setup"].update(
            setup_s=time.perf_counter() - t_setup,
            compile_s=at_setup["seconds"], compiles=at_setup["compiles"])
        run.rec["setup_s"] = run.rec["setup"]["setup_s"]
        run.say("setup", **{k: round(v, 4) if isinstance(v, float) else v
                            for k, v in run.rec["setup"].items()})
        run.loop.window()
        run.collect(at_setup)
        routes_bad = run.route_failures()
    finally:
        run.stop()
    rec = run.rec
    done = run.loop.done()
    if len(done) < 2:
        raise BenchFailure(f"only {len(done)} request(s) or epoch(s) ended "
                           "inside the window; two must")
    chk = run.check()
    rec["shapes"]["n_pad"] = min(
        (n for n in rec["shapes"]["lengths_1d"]
         if n >= run.graph_counts["ids"]), default=None)
    if args.trace:
        run.reduce_trace()

    # the work line: what a later spread can be traced to
    run.say("work", n_pad=rec["shapes"]["n_pad"], m_pad=rec["shapes"]["m_pad"],
            graph=run.graph_counts, shapes=rec["shapes"],
            comm_exchange=sorted({(s["args"].get("route"),
                                   s["args"].get("rows"),
                                   s["args"].get("bytes"))
                                  for s in rec["spans"]
                                  if s["name"] == "comm.exchange"}) or None,
            supersteps=sorted({row["steps"] for row in run.served_rows()}),
            attempted=rec["attempted"], failed=rec["failed"],
            window_s=round(rec["window_s"], 3),
            window_compiles=rec["window_compiles"],
            peak_bytes_in_use=rec["memory"]["peak_by_device"],
            **run.loop.work())
    for row in chk["rows"]:
        run.say("check", **row)
    run.say("check_summary", ok=chk["ok"], limits=chk["limits"],
            rows_compared=len(chk["rows"]),
            reference_seconds=round(chk["reference_s"], 2),
            route_failures=routes_bad)

    metrics = {}
    for spec in loaded["per_layer" if args.trace else "end_to_end"]:
        v = layers.reduce_metric(spec, rec)
        if v is not None:
            metrics[spec["name"]] = {"value": v, "unit": spec["unit"]}
    failed = rec["failed"] + sum(1 for r in chk["rows"] if not r["ok"])
    correct = bool(chk["ok"] and not routes_bad and rec["failed"] == 0
                   and not args.rehearsal
                   and (device["platform"] == "tpu" or not require_chip))
    dev_out = {**device, "memory_peak_bytes": rec["memory"]["peak_bytes"]}
    result = {"correct": correct, "attempted": rec["attempted"],
              "failed": failed, "metrics": metrics, "device": dev_out}
    if args.trace and rec.get("xplane"):
        x = rec["xplane"]
        dev_out.update(busy_s=x["busy_s"], window_s=x["window_s"])
        result["breakdown"] = {"device_ops": x["device_ops"],
                               "idle_gaps": x["idle_gaps"]}
    if args.rehearsal:
        result["rehearsal"] = "cpu: a plumbing check, never a result"
    # each number `correct` compared, the worst over the rows, beside its
    # limit: last on standard error and last in the result's line
    rows = chk["rows"]
    result["compared"] = {
        **{k: {"value": max((r[k] for r in rows if k in r), default=None),
               "limit": lim} for k, lim in chk["limits"].items()},
        "steps": {"value": sorted({r["steps"] for r in rows}),
                  "limit": run.cfg["algorithm"]["iterations"]},
        "requests_failed": {"value": rec["failed"], "limit": 0},
        "route_failures": {"value": len(routes_bad), "limit": 0}}
    for name, c in result["compared"].items():
        sys.stderr.write(f"compared {name} {c['value']} limit {c['limit']}\n")
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=48.0)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearsal", action="store_true",
                    help="tiny size on the CPU backend; never passes")
    args = ap.parse_args(argv)
    try:
        return run_cell(args)
    except BenchFailure as e:
        sys.stderr.write(f"benchmark/run.py: {e}\n")
        return 1


if __name__ == "__main__":
    code = main()
    sys.stdout.flush()
    sys.stderr.flush()
    # everything the run started is stopped and its result is out; leave
    # without the interpreter's teardown, where a daemon thread of the
    # program still inside C++ can abort the process (seen once, PR 24:
    # "FATAL: exception not rethrown", exit -6 after the result line)
    os._exit(code)
