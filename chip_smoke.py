#!/usr/bin/env python3
"""chip_smoke.py — the quickest proof that the served path runs on the chip.

Drives the system's main path once — ingest → bitemporal log → served
View / Range / Live queries — through the entry points a user calls, at
the repo's scale-cell size, and checks every answer against a plain numpy
reference that shares no code with the path under test.

**One process on the chip.** This script IS the chip-owning process: it
builds the same ``NodeRuntime`` + ``RestServer`` that ``python -m
raphtory_tpu serve`` builds (``raphtory_tpu/__main__.py``), in-process,
and queries it over REST on localhost. The only children it starts are
the reference workers (``RefPool``), started with the ``spawn`` context:
they import numpy and nothing else — never jax, never the package, whose
import touches ``jax.config`` — so no child can need the chip, and all of
them are stopped before the script exits. With >= 4 devices the same
process drives all of them through a second ``NodeRuntime`` on a mesh.

Deployment: BASELINE.json's Twitter-2010 windowed-PageRank config in the
shape ``bench_scale_pagerank`` defaults to — ``gab_like_arrays(5.3M
vertex ids, 2^25 = 33.5M edge events, t_span 2.6M s)``, halved twice
(``SIZE_CUT`` below says why) — plus a live tail in the reference paper's
worst-case mix (``RandomSource(id_pool=1M, mix=(0.3, 0.4, 0.1, 0.2))``,
paper §6.1), so tombstones and revivals cross the device fold. All data
comes from ``--seed``; nothing outside the checkout is read.

Contract: exits 0 and prints, as the last stdout line,
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}``
only when every phase and every comparison passed ON A TPU. Without an
accelerator it exits non-zero and prints no result. ``--cpu-rehearsal``
runs the same phases at a tiny size on the CPU (every line says
``device: cpu``) and can never print the pass line.

PageRank tolerance: the served ranks are f32 iterates that stopped when no
vertex moved more than ``tol``; the reference is the f64 fixed point. An
iterate whose error contracts by the damping factor d per step and that
moved < tol is within d/(1-d)·tol = 5.7·tol of the fixed point; f32
segment sums against f64 add ~1e-4 relative. So a served rank must lie
within ``6·tol + 2e-4·rank`` of the reference. The generator's degrees
are nearly uniform (top rank ~7x the mean 1/n), so ``tol`` is set from
the size, 5e-4/n — 1e-10 at 5.3M vertices — or the absolute term would
swallow the ranks it is meant to check. ConnectedComponents and degree
answers must match exactly.
"""

from __future__ import annotations

import argparse
import json
import logging
import multiprocessing
import os
import shutil
import sys
import tempfile
import time
import urllib.request

# the flight recorder supplies the route evidence (/tracez); it must be on
# before the package creates its tracer
os.environ.setdefault("RTPU_TRACE", "1")

import numpy as np  # noqa: E402

T_SPAN = 2_600_000                  # a month of seconds (BASELINE config)
WINDOWS = (2_600_000, 604_800, 86_400)          # month / week / day
TAIL_MIX = (0.3, 0.4, 0.1, 0.2)     # paper §6.1 worst case
TAIL_POOL = 1_000_000
NEG = -(2 ** 62)

#: full size (bench_scale_pagerank's) and every cut applied to it, with
#: its cause — never shrunk silently, never because the device is a CPU.
#: The tail streams for 400 s so that it outlasts the Live subscription.
FULL = {"n_vertices": 5_300_000, "n_edges": 1 << 25, "n_tail": 800_000,
        "tail_rate": 2_000.0}
SIZE_CUT_HALVINGS = 2
SIZE_CUT = (
    "vertex ids and edge events halved twice (5.3M / 33.5M -> 1.325M / "
    "8.39M), the size ISSUE 22 fixed. Cause: this smoke's 1,200 s limit "
    "and the one-chip machine's host memory. ISSUE 22 quotes PR 21's "
    "unlanded chip runs (not re-measured): full size 2,193 s with 8.9 GiB "
    "of 45 left on the host; half size would take about twice this run")
REHEARSAL = {"n_vertices": 3_000, "n_edges": 1 << 15, "n_tail": 30_000,
             "tail_rate": 3_000.0}


def pagerank_params(size: dict) -> dict:
    return {"tol": 5e-4 / size["n_vertices"], "max_steps": 100}


class SmokeFailure(AssertionError):
    """A phase or a comparison failed: the run fails."""


def check(cond, msg: str) -> None:
    if not cond:
        raise SmokeFailure(msg)


# --------------------------------------------------------------- reference
#
# Written from the semantics the store documents (core/snapshot.py header,
# SURVEY §0), not from its code: an entity is alive at T by its latest
# history point <= T, a delete wins a tie, an edge add is a history point
# of both endpoints, a vertex delete kills every incident edge, a re-add
# revives, and a window w keeps what was last active in [T - w, T].
# Ids are dense below ``n_ids``, so the fold is a handful of scatter-max
# passes instead of the store's sort-based fold.


class RefEvents:
    """The whole event stream, split by kind; each kind is in time order
    (the bulk is time-sorted and the tail follows it), so "events <= T"
    is a prefix of every array."""

    def __init__(self, t, is_vadd, is_vdel, is_eadd, is_edel, s, d, n_ids):
        self.n_ids = int(n_ids)
        check(bool(np.all(t[1:] >= t[:-1])), "event stream not time-sorted")
        edge = is_eadd | is_edel
        ukeys, edge_of = np.unique(s[edge] * np.int64(n_ids) + d[edge],
                                   return_inverse=True)
        self.us, self.ud = ukeys // n_ids, ukeys % n_ids
        self.va = (t[is_vadd], s[is_vadd])
        self.vd = (t[is_vdel], s[is_vdel])
        self.ea = (t[is_eadd], s[is_eadd], d[is_eadd],
                   edge_of[is_eadd[edge]])
        self.ed = (t[is_edel], edge_of[is_edel[edge]])

    def fold(self, T: int, window: int | None):
        """(vertex mask [n_ids], src, dst) of the graph at T under window."""
        def upto(cols):
            k = int(np.searchsorted(cols[0], T, side="right"))
            return tuple(c[:k] for c in cols)

        va_t, va_s = upto(self.va)
        vd_t, vd_s = upto(self.vd)
        ea_t, ea_s, ea_d, ea_e = upto(self.ea)
        ed_t, ed_e = upto(self.ed)
        v_live = np.full(self.n_ids, NEG, np.int64)
        np.maximum.at(v_live, va_s, va_t)
        np.maximum.at(v_live, ea_s, ea_t)      # an edge add is a history
        np.maximum.at(v_live, ea_d, ea_t)      # point of both endpoints
        v_dead = np.full(self.n_ids, NEG, np.int64)
        np.maximum.at(v_dead, vd_s, vd_t)
        v_alive = v_live > v_dead                  # delete wins a tie
        e_live = np.full(len(self.us), NEG, np.int64)
        np.maximum.at(e_live, ea_e, ea_t)
        e_dead = np.full(len(self.us), NEG, np.int64)
        np.maximum.at(e_dead, ed_e, ed_t)
        # a vertex delete kills every incident edge
        e_dead = np.maximum(e_dead, np.maximum(v_dead[self.us],
                                               v_dead[self.ud]))
        e_alive = e_live > e_dead
        if window is not None:
            v_alive &= v_live >= T - window
            e_alive &= e_live >= T - window
        return v_alive, self.us[e_alive], self.ud[e_alive]


def ref_pagerank(vm, src, dst, damping=0.85):
    """f64 fixed point of the damped power iteration with uniform
    dangling redistribution (algorithms/pagerank.py docstring)."""
    n_ids = len(vm)
    n = max(int(vm.sum()), 1)
    out_deg = np.bincount(src, minlength=n_ids).astype(np.float64)
    inv = 1.0 / np.maximum(out_deg, 1.0)
    dangling_v = vm & (out_deg == 0)
    r = np.where(vm, 1.0 / n, 0.0)
    for _ in range(500):
        agg = np.bincount(dst, weights=(r * inv)[src], minlength=n_ids)
        new = np.where(
            vm, (1.0 - damping) / n
            + damping * (agg + r[dangling_v].sum() / n), 0.0)
        delta = np.abs(new - r).max()
        r = new
        if delta < 1e-9 / n:      # far below the comparison's 6·tol
            break
    return r


def ref_components(vm, src, dst):
    """Component label per vertex (min member id) by min-label hooking
    with pointer jumping over the undirected edge set."""
    n_ids = len(vm)
    lab = np.arange(n_ids, dtype=np.int64)
    a, b = src, dst
    while True:
        la, lb = lab[a], lab[b]
        lo = np.minimum(la, lb)
        nxt = lab.copy()
        np.minimum.at(nxt, la, lo)
        np.minimum.at(nxt, lb, lo)
        while True:                      # pointer jumping to the roots
            j = nxt[nxt]
            if np.array_equal(j, nxt):
                break
            nxt = j
        if np.array_equal(nxt, lab):
            break
        lab = nxt
    return lab


def expect_pagerank(vm, src, dst):
    """The reference answer in the shape a comparison needs: rank mass,
    vertex count and the 64 leading (vertex, rank) pairs — compact enough
    to come back from a worker process."""
    r = ref_pagerank(vm, src, dst)
    lead = np.argsort(r)[::-1][:64]
    return {"sum": float(r.sum()), "positive": int((r > 0).sum()),
            "lead": [(int(v), float(r[v])) for v in lead if r[v] > 0]}


def expect_cc(vm, src, dst):
    lab = ref_components(vm, src, dst)[vm]
    if not len(lab):
        return {"vertices": 0, "clusters": 0, "biggest": 0, "islands": 0,
                "proportion": 0.0, "top5": []}
    sizes = np.sort(np.unique(lab, return_counts=True)[1])
    return {"vertices": int(len(lab)), "clusters": int(len(sizes)),
            "biggest": int(sizes[-1]), "islands": int((sizes == 1).sum()),
            "proportion": float(sizes[-1] / len(lab)),
            "top5": sizes[::-1][:5].tolist()}


def expect_degree(vm, src, dst):
    n_ids = len(vm)
    ind = np.bincount(dst, minlength=n_ids)
    outd = np.bincount(src, minlength=n_ids)
    n = int(vm.sum())
    return {"vertices": n, "total_in": int(ind.sum()),
            "total_out": int(outd.sum()), "max_in": int(ind.max(initial=0)),
            "max_out": int(outd.max(initial=0)),
            "avg_degree": float((ind.sum() + outd.sum()) / max(n, 1))}


def compare_pagerank(got: dict, want: dict, tol: float, what: str):
    """Served {sum, top10} against ``expect_pagerank``'s summary; returns
    the worst |error| as a share of its allowance."""
    atol, rtol = 6.0 * tol, 2e-4
    check(abs(got["sum"] - want["sum"]) <= 1e-3,
          f"{what}: rank mass {got['sum']} vs reference {want['sum']}")
    top, ref = got["top10"], dict(want["lead"])
    check(len(top) == min(10, want["positive"]),
          f"{what}: top10 has {len(top)} rows")
    worst = 0.0
    for vid, rank in top:
        check(vid in ref, f"{what}: served top10 vertex {vid} is not among "
                          f"the reference's leading {len(ref)}")
        allow = atol + rtol * ref[vid]
        worst = max(worst, abs(rank - ref[vid]) / allow)
        check(abs(rank - ref[vid]) <= allow,
              f"{what}: vertex {vid} rank {rank} vs reference {ref[vid]} "
              f"(|err| {abs(rank - ref[vid]):.3e} > {allow:.3e})")
    # the served top-10 must BE the top-10: nothing the reference ranks
    # clearly above the served 10th place may be missing from it
    if top:
        served = {int(v) for v, _ in top}
        floor = min(rank for _, rank in top)
        for v, r_v in want["lead"][:20]:
            check(v in served or r_v <= floor + 2 * atol + rtol * r_v,
                  f"{what}: reference rank {r_v} of vertex {v} is above "
                  f"the served 10th place {floor} but not in the top10")
    return worst


def compare_exact(got: dict, want: dict, what: str):
    got, want = dict(got), dict(want)
    for k in ("proportion", "avg_degree"):
        if k in got:                    # a ratio of exact integers
            check(abs(got.pop(k) - want.pop(k)) < 1e-9,
                  f"{what}: {k} differs")
    check(got == want, f"{what}: served {got} != reference {want}")
    return 0.0


EXPECT = {"pagerank": expect_pagerank, "cc": expect_cc,
          "degree": expect_degree}

# ------------------------------------------------------- reference workers

_REF: RefEvents | None = None       # a worker process's event stream


def _ref_worker_init(path: str, n_ids: int):
    """Worker start-up: map the event columns the parent saved and build
    the fold index. Imports nothing but numpy."""
    global _REF
    cols = {c: np.load(os.path.join(path, c + ".npy"), mmap_mode="r")
            for c in ("t", "k", "s", "d")}
    k = np.asarray(cols["k"])
    _REF = RefEvents(np.asarray(cols["t"]), k == 0, k == 1, k == 2, k == 3,
                     np.asarray(cols["s"]), np.asarray(cols["d"]), n_ids)


def _ref_task(kind: str, T: int, window):
    t0 = time.perf_counter()
    out = EXPECT[kind](*_REF.fold(T, window))
    return out, time.perf_counter() - t0


class RefPool:
    """The plain reference, computed beside the served path in worker
    processes so that checking every row does not cost the run its time
    limit. Workers never import jax: they cannot touch the chip."""

    def __init__(self, columns: dict, n_ids: int):
        shm = "/dev/shm" if os.path.isdir("/dev/shm") else None
        self.dir = tempfile.mkdtemp(prefix="chip_smoke_ref_", dir=shm)
        for name, col in columns.items():
            np.save(os.path.join(self.dir, name + ".npy"), col)
        self.workers = max(1, min(3, (os.cpu_count() or 4) // 4))
        self.pool = multiprocessing.get_context("spawn").Pool(
            self.workers, initializer=_ref_worker_init,
            initargs=(self.dir, n_ids))
        self.asked: dict = {}

    def want(self, kind: str, T: int, window):
        """Ask for (or re-use) the reference answer of one view."""
        key = (kind, int(T), window)
        if key not in self.asked:
            self.asked[key] = self.pool.apply_async(_ref_task, key)
        return self.asked[key]

    def get(self, kind: str, T: int, window):
        return self.want(kind, T, window).get(timeout=1200)[0]

    def seconds(self) -> float:
        """CPU seconds the finished reference tasks took, all workers."""
        return sum(r.get()[1] for r in self.asked.values() if r.ready())

    def close(self):
        self.pool.terminate()
        self.pool.join()
        shutil.rmtree(self.dir, ignore_errors=True)


# ------------------------------------------------------------------- REST


class Rest:
    def __init__(self, port: int):
        self.base = f"http://127.0.0.1:{port}"

    def get(self, path: str):
        with urllib.request.urlopen(self.base + path, timeout=120) as r:
            return json.loads(r.read())

    def post(self, path: str, body: dict):
        req = urllib.request.Request(
            self.base + path, data=json.dumps(body).encode(),
            headers={"Content-Type": "application/json"})
        with urllib.request.urlopen(req, timeout=120) as r:
            return json.loads(r.read())

    def run(self, path: str, body: dict, timeout_s: float = 1500.0):
        """POST a query, wait for the job; returns (result doc, wall s,
        this job's spans). Fails the run unless the job ends ``done``."""
        t0 = time.perf_counter()
        out = self.post(path, {**body, "explain": 1})
        jid = out["jobID"]
        while True:
            res = self.get(f"/AnalysisResults?jobID={jid}")
            if res["status"] not in ("pending", "running"):
                break
            check(time.perf_counter() - t0 < timeout_s,
                  f"job {jid} still {res['status']} after {timeout_s}s")
            time.sleep(0.05)
        wall = time.perf_counter() - t0
        check(res["status"] == "done" and not res.get("degraded"),
              f"job {jid}: status {res['status']} degraded="
              f"{res.get('degraded')} error={res.get('error')}")
        spans = self.get(f"/tracez?trace_id={res['traceID']}")["spans"]
        return res, wall, spans


def route_of(res: dict, spans: list) -> dict:
    """Which route served a job, from what the program already records:
    the ledger's kernel table and collective routes (explain=1) and the
    job's spans in the flight recorder."""
    led = res["ledger"]
    names = sorted({s["name"] for s in spans})
    live = [s["args"].get("mode") for s in spans
            if s["name"] == "live.epoch"]
    comm = {s["args"].get("route") for s in spans
            if s["name"] == "comm.exchange"}
    return {"kernels": sorted(led["device"]["kernels"]),
            "comm_routes": sorted(comm | set(led["dcn"]["routes"])),
            "spans": [n for n in names if n.startswith(
                ("sweep.", "hop.", "bsp.", "snapshot.", "live.", "comm."))],
            **({"live_modes": live} if live else {})}


def expect_route(route: dict, what: str, *, kernels=(), spans=(),
                 no_spans=(), dcn=()):
    for k in kernels:
        check(any(n.startswith(k) for n in route["kernels"]),
              f"{what}: expected kernel {k}*, ledger has {route['kernels']}")
    for s in spans:
        check(s in route["spans"],
              f"{what}: expected span {s}, trace has {route['spans']}")
    for s in no_spans:
        check(s not in route["spans"],
              f"{what}: span {s} means another route served it "
              f"({route['spans']})")
    for r in dcn:
        check(any(x.startswith(r) for x in route["comm_routes"]),
              f"{what}: expected collective route {r}, the job recorded "
              f"{route['comm_routes']}")


class DeclineWatch(logging.Handler):
    """Every WARNING+ the package logs during the smoke. A route that
    declined after an exception says "falling back": that is a failure of
    the smoke, not a degraded pass."""

    def __init__(self):
        super().__init__(logging.WARNING)
        self.records: list[str] = []

    def emit(self, record):
        self.records.append(f"{record.name}: {record.getMessage()}")

    def assert_clean(self, what: str):
        bad = [r for r in self.records if "falling back" in r
               or "fell back" in r or "failed" in r]
        check(not bad, f"{what}: a route declined after an exception: {bad}")


# ---------------------------------------------------------------- sources


def make_sources(size: dict, seed: int):
    """(bulk source, tail source, the whole stream as plain columns for
    the reference: t, k in {0 vadd, 1 vdel, 2 eadd, 3 edel}, s, d)."""
    from raphtory_tpu.core import events as ev
    from raphtory_tpu.ingestion.source import RandomSource, RateLimited, Source
    from raphtory_tpu.utils.synth import gab_like_arrays

    src, dst, times = gab_like_arrays(
        n_vertices=size["n_vertices"], n_edges=size["n_edges"],
        seed=seed + 11, t_span=T_SPAN)
    kinds = np.full(len(times), ev.EDGE_ADD, np.uint8)

    class Bulk(Source):
        name, disorder = "bulk", 0

        def iter_batches(self, batch: int = 1 << 22):
            for off in range(0, len(times), batch):
                sl = slice(off, off + batch)
                yield times[sl], kinds[sl], src[sl], dst[sl]

    tail_gen = RandomSource(size["n_tail"], id_pool=TAIL_POOL, seed=seed,
                            mix=TAIL_MIX, name="tail-inner")
    tail_batches = [tuple(a.copy() for a in b)
                    for b in tail_gen.iter_batches()]
    for b in tail_batches:           # the tail follows the bulk in time
        b[0][:] += T_SPAN

    class Tail(Source):
        name, disorder = "tail", 0

        def iter_batches(self):
            return iter(tail_batches)

    tt, tk, ts, td = (np.concatenate([b[i] for b in tail_batches])
                      for i in range(4))
    k_all = np.concatenate([kinds, tk])
    code = np.full(len(k_all), 255, np.uint8)
    for i, kind in enumerate((ev.VERTEX_ADD, ev.VERTEX_DELETE, ev.EDGE_ADD,
                              ev.EDGE_DELETE)):
        code[k_all == kind] = i
    columns = {"t": np.concatenate([times, tt]), "k": code,
               "s": np.concatenate([src, ts]),
               "d": np.concatenate([dst, np.maximum(td, 0)])}
    tail = RateLimited(Tail(), rate=size["tail_rate"])
    tail.name = "tail"
    return Bulk(), tail, columns


# ----------------------------------------------------------------- phases

T1, T0 = int(0.9 * T_SPAN), int(0.6 * T_SPAN)      # View timestamps
R_LO = int(0.7 * T_SPAN)                            # Range start
R_JUMP = int(0.08 * T_SPAN)                         # 4 hops reach 0.94
VIEW = {"timestamp": T1, "windowType": "single", "windowSize": WINDOWS[0]}
RANGE4 = {"start": R_LO, "end": R_LO + 3 * R_JUMP, "jump": R_JUMP}
RANGE2 = {"start": R_LO, "end": R_LO + R_JUMP, "jump": R_JUMP}
# one-hour hops (the scale cell's own): each hop's delta is small against
# the resident buffers, so it ships as padded chunks through the donated
# six-buffer scatter, not as a full refresh
HOURLY = {"start": R_LO, "end": R_LO + 2 * 3_600, "jump": 3_600}


def hops_of(span: dict) -> list[int]:
    return list(range(span["start"], span["end"] + 1, span["jump"]))


class Smoke:
    """One deployment (a NodeRuntime behind REST, with or without a mesh)
    and the served queries against it."""

    def __init__(self, size: dict, cuts: list, label: str, sources, ref,
                 mesh=None):
        self.size, self.cuts, self.label, self.mesh = size, cuts, label, mesh
        self.bulk, self.tail = sources
        self.ref = ref
        self.pr = pagerank_params(size)
        self.rt = None
        self.answers: dict = {}     # phase -> served rows

    def say(self, phase: str, **kw):
        print(json.dumps({"phase": phase, "device": self.label, **kw}),
              flush=True)

    # -- deployment ------------------------------------------------------

    def boot(self):
        from raphtory_tpu.cluster.runtime import NodeRuntime
        from raphtory_tpu.utils.config import Settings

        self.rt = NodeRuntime(settings=Settings(rest_port=0, metrics_port=0),
                              mesh=self.mesh)
        self.rt.add_source(self.bulk)
        self.rt.start(rest=True, metrics=False)
        self.rest = Rest(self.rt._rest.port)
        t0 = time.perf_counter()
        self.rt.ingest(wait=True)
        check(not self.rt.pipeline.errors,
              f"ingest errors: {self.rt.pipeline.errors}")
        n = self.rt.pipeline.counts["bulk"]
        check(n == self.size["n_edges"] == len(self.rt.graph.log),
              f"bulk ingested {n} of {self.size['n_edges']} events, log "
              f"holds {len(self.rt.graph.log)}")
        self.say("ingest_bulk", updates=n,
                 safe_time=int(self.rt.graph.safe_time()),
                 latest_time=int(self.rt.graph.latest_time),
                 ingest_seconds=round(time.perf_counter() - t0, 2),
                 vertex_ids=self.size["n_vertices"], cuts=self.cuts,
                 mesh=None if self.mesh is None else dict(self.mesh.shape))

    # -- one served query + its comparison -------------------------------

    def served(self, phase, path, body, kind, *, route_kw, rows_expected):
        """Run one query over REST, establish the route that served it,
        and compare EVERY row with the reference."""
        res, wall, spans = self.rest.run(path, body)
        rows = res["results"]
        check(len(rows) == rows_expected,
              f"{phase}: {len(rows)} result rows, expected {rows_expected}")
        route = route_of(res, spans)
        expect_route(route, phase, **route_kw)
        for row in rows:        # ask for all, then wait: workers overlap
            self.ref.want(kind, row["time"], row["windowsize"])
        t0 = time.perf_counter()
        worst = 0.0
        for row in rows:
            T, w = row["time"], row["windowsize"]
            want = self.ref.get(kind, T, w)
            what = f"{phase} T={T} w={w}"
            worst = max(worst, compare_pagerank(
                row["result"], want, self.pr["tol"], what)
                if kind == "pagerank" else
                compare_exact(row["result"], want, what))
        self.answers[phase] = rows
        led = res["ledger"]
        self.say(phase, wall_seconds=round(wall, 2), rows=len(rows),
                 rows_checked=len(rows),
                 waited_for_reference_seconds=round(
                     time.perf_counter() - t0, 2),
                 steps=rows[0]["steps"], route=route,
                 phase_seconds=led["phase_seconds"],
                 h2d_bytes=led["h2d"]["bytes"],
                 peak_device_bytes=led["device"]["peak_device_bytes"],
                 **({"pagerank_worst_err_over_allowance": round(worst, 3)}
                    if kind == "pagerank" else {}))
        return wall

    def views(self):
        body = {"analyserName": "PageRank", "params": self.pr, **VIEW}
        resident = dict(kernels=("device_sweep.superstep.PageRank",),
                        spans=("hop.compute",), no_spans=("bsp.dispatch",))
        # the first request pins the graph's resident DeviceSweep (table
        # build + upload + compile); the same request again is the warm
        # path: a no-op delta-advance and one dispatch.
        first = self.served("view_first", "/ViewAnalysisRequest", body,
                            "pagerank", route_kw=resident, rows_expected=1)
        again = self.served("view_warm", "/ViewAnalysisRequest", body,
                            "pagerank", route_kw=resident, rows_expected=1)
        self.say("view_compile_vs_steady",
                 first_call_seconds=round(first, 2),
                 second_call_seconds=round(again, 2))
        # behind the resident sweep's clock: the cold path — a full host
        # fold of the log plus the generic bsp runner over a fresh upload.
        # ConnectedComponents, so the generic runner's min combine runs on
        # the chip too (its sum combine already ran in the resident View).
        self.served("view_cold_bsp", "/ViewAnalysisRequest",
                    {"analyserName": "ConnectedComponents", **VIEW,
                     "timestamp": T0}, "cc", rows_expected=1,
                    route_kw=dict(
                        kernels=("bsp.superstep.ConnectedComponents",),
                        spans=("snapshot.fold", "bsp.dispatch")))

    def ranges(self):
        mesh = self.mesh is not None
        columns = dict(spans=("comm.exchange",), dcn=("replicate",))
        self.served(
            "range_pagerank", "/RangeAnalysisRequest",
            {"analyserName": "PageRank", "params": self.pr, **RANGE4,
             "windowType": "batched", "windowSet": list(WINDOWS)},
            "pagerank", rows_expected=12,
            route_kw=columns if mesh else dict(
                kernels=("hopbatch.delta.pagerank",),
                spans=("sweep.columnar", "hop.compute")))
        self.served(
            "range_cc", "/RangeAnalysisRequest",
            {"analyserName": "ConnectedComponents", **RANGE2,
             "windowType": "batched", "windowSet": list(WINDOWS[:2])},
            "cc", rows_expected=4,
            route_kw=columns if mesh else dict(
                kernels=("hopbatch.delta.cc",), spans=("sweep.columnar",)))
        self.served(
            "range_degree", "/RangeAnalysisRequest",
            {"analyserName": "DegreeBasic", **HOURLY,
             "windowType": "single", "windowSize": WINDOWS[1]},
            "degree", rows_expected=3,
            route_kw=dict(spans=("comm.exchange",)) if mesh else dict(
                kernels=("device_sweep.apply",
                         "device_sweep.superstep.DegreeBasic"),
                spans=("hop.ship", "hop.compute"),
                no_spans=("sweep.columnar", "bsp.dispatch")))

    def live(self, n_runs: int = 5):
        """A Live PageRank WHILE the worst-case-mix tail streams in."""
        rt = self.rt
        rt.add_source(self.tail)
        rt.ingest(wait=False)            # starts the late joiner alone
        # the first tail events must be behind the fence before the
        # subscription's first epoch
        check(rt.graph.watermarks.wait_for(T_SPAN + 1, timeout=120),
              "tail never advanced the watermark")
        res, wall, spans = self.rest.run(
            "/LiveAnalysisRequest",
            {"analyserName": "PageRank", "params": self.pr,
             "repeatTime": 0.5, "maxRuns": n_runs})
        streaming_at_end = rt.graph.safe_time() < 2 ** 62
        rows = res["results"]
        check(len(rows) == n_runs,
              f"live: {len(rows)} rows of {n_runs} (an epoch that sees "
              f"neither the fence nor the log move is skipped: the tail "
              f"ended before the subscription did? streaming at the end: "
              f"{streaming_at_end})")
        times = [r["time"] for r in rows]
        check(times == sorted(times) and len(set(times)) >= 3
              and times[0] > T_SPAN,
              f"live: epoch times {times} did not advance with the tail")
        route = route_of(res, spans)
        expect_route(route, "live", kernels=("hopbatch.delta.pagerank",),
                     spans=("live.epoch",))
        check("resweep" not in route["live_modes"],
              f"live: an epoch fell back to the full re-sweep: "
              f"{route['live_modes']}")
        for row in rows:
            self.ref.want("pagerank", row["time"], None)
        rt.pipeline.stop()
        check(not rt.pipeline.errors,
              f"ingest errors: {rt.pipeline.errors}")
        tail_n = len(rt.graph.log) - self.size["n_edges"]
        check(0 < tail_n <= self.size["n_tail"],
              f"log holds {len(rt.graph.log)} events, bulk was "
              f"{self.size['n_edges']}")
        kinds = self.rest.get("/freshz")["sources"]["tail"]["kinds"]
        check(kinds.get("vertex_delete", 0) > 0
              and kinds.get("edge_delete", 0) > 0,
              f"tail carried no tombstones: {kinds}")
        t0 = time.perf_counter()
        worst = max(compare_pagerank(
            row["result"], self.ref.get("pagerank", row["time"], None),
            self.pr["tol"], f"live T={row['time']}") for row in rows)
        self.say("live_under_ingest", wall_seconds=round(wall, 2),
                 epochs=n_runs, epoch_times=times,
                 epoch_modes=route["live_modes"], rows_checked=len(rows),
                 waited_for_reference_seconds=round(
                     time.perf_counter() - t0, 2), route=route,
                 tail_events_ingested=int(tail_n), tail_kinds_sampled=kinds,
                 tail_streaming_at_last_epoch=bool(streaming_at_end),
                 phase_seconds=res["ledger"]["phase_seconds"],
                 pagerank_worst_err_over_allowance=round(worst, 3))

    def kernel_memory(self):
        """What the compiler says each served kernel holds on the device
        (``compiled.memory_analysis()`` as the ledger harvested it,
        /costz) — the truth about the ``[rows, C]`` layouts."""
        rows = [{"kernel": k["kernel"], "sig": k["sig"][:200],
                 "dispatches": k["dispatches"],
                 **{f: k.get(f) for f in ("argument_bytes", "output_bytes",
                                          "temp_bytes")}}
                for k in self.rest.get("/costz")["kernels"]]
        self.say("kernel_memory", kernels=rows)

    def stop(self):
        if self.rt is not None:
            self.rt.stop()


# ------------------------------------------------------------- four chips


def mesh_phases(one: "Smoke"):
    """The same deployment behind the same REST calls on a mesh over all
    devices: mesh-columns for the columnar Ranges, ShardedSweep for the
    rest, and the vertex-sharded route under each comm for Views."""
    import jax

    from raphtory_tpu.parallel import sharded

    mesh = sharded.make_mesh(devices=jax.devices())
    sm = Smoke(one.size, one.cuts, one.label, (one.bulk, one.tail), one.ref,
               mesh=mesh)
    try:
        sm.boot()
        _mesh_queries(sm, mesh, one.answers)
    finally:
        os.environ.pop("RTPU_COMM_ROUTE", None)
        sm.stop()


def _mesh_queries(sm, mesh, one_chip: dict):
    import jax

    from raphtory_tpu.algorithms import ConnectedComponents
    from raphtory_tpu.parallel import sharded

    sm.ranges()
    for phase in ("range_cc", "range_degree"):
        got = [r["result"] for r in sm.answers[phase]]
        want = [r["result"] for r in one_chip[phase]]
        check(got == want, f"mesh {phase} differs from the one-chip answer")
    for comm in ("halo", "all_gather", "sparse"):
        os.environ["RTPU_COMM_ROUTE"] = comm
        sm.served(f"mesh_view_cc_{comm}", "/ViewAnalysisRequest",
                  {"analyserName": "ConnectedComponents", **VIEW},
                  "cc", rows_expected=1,
                  route_kw=dict(spans=("comm.exchange",), dcn=(comm,)))
        if comm != "sparse":     # sparse needs the monotone_min contract
            sm.served(f"mesh_view_pagerank_{comm}", "/ViewAnalysisRequest",
                      {"analyserName": "PageRank", "params": sm.pr, **VIEW},
                      "pagerank", rows_expected=1,
                      route_kw=dict(spans=("comm.exchange",), dcn=(comm,)))
    del os.environ["RTPU_COMM_ROUTE"]
    cc = {c: sm.answers[f"mesh_view_cc_{c}"][0]["result"]
          for c in ("halo", "all_gather", "sparse")}
    check(cc["halo"] == cc["all_gather"] == cc["sparse"],
          f"CC differs across comm routes: {cc}")
    # the work must really be spread. REST returns reduced rows, so this
    # one evidence read dispatches the engine directly on the served
    # graph's (cached) view and looks at the un-gathered result arrays
    n_dev = len(jax.devices())
    out, _ = sharded.run(ConnectedComponents(), sm.rt.graph.view_at(T1),
                         mesh, window=WINDOWS[0], block=False)
    spread = sorted({len(a.sharding.device_set)
                     for a in jax.tree_util.tree_leaves(out)})
    check(spread == [n_dev], f"result arrays live on {spread} devices, "
                             f"not on all {n_dev}")
    mem = {str(d): {k: int((d.memory_stats() or {}).get(k, 0))
                    for k in ("bytes_in_use", "peak_bytes_in_use")}
           for d in jax.devices()}
    check(sm.label == "cpu" or all(m["bytes_in_use"] > (1 << 20)
                                   for m in mem.values()),
          f"bytes_in_use is trivial on some device: {mem}")
    sm.say("mesh_spread", devices=n_dev,
           result_sharding_device_set=spread, memory=mem,
           collectives=sharded.COLLECTIVES.snapshot().get("routes"))


# ------------------------------------------------------------------- main


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--cpu-rehearsal", action="store_true",
                    help="tiny size on the CPU backend; never prints the "
                         "pass line")
    ap.add_argument("--halvings", type=int, default=0,
                    help="debugging on the chip: halve the size this many "
                         "MORE times (printed as a cut; the driver runs "
                         "without it)")
    ap.add_argument("--cpu-devices", type=int, default=1,
                    help="virtual CPU devices for --cpu-rehearsal (>= 4 "
                         "rehearses the four-chip phases too)")
    args = ap.parse_args(argv)

    import jax
    import jaxlib

    import raphtory_tpu  # noqa: F401 — x64 + the compile cache wiring
    from raphtory_tpu.native import lib as native
    from raphtory_tpu.obs import device as obs_device
    from raphtory_tpu.utils import config

    if args.cpu_rehearsal:
        jax.config.update("jax_platforms", "cpu")
        jax.config.update("jax_num_cpu_devices", args.cpu_devices)
    dev = jax.devices()[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices())}
    if dev.platform != "tpu" and not args.cpu_rehearsal:
        sys.stderr.write(
            f"chip_smoke: no TPU — jax found {device}; the smoke runs on "
            "the chip only (--cpu-rehearsal is a plumbing check that "
            "never passes)\n")
        return 1
    label = dev.platform
    if args.cpu_rehearsal:
        size = REHEARSAL
        cuts = ["--cpu-rehearsal: a tiny plumbing check, not a size"]
    else:
        cut = SIZE_CUT_HALVINGS + args.halvings
        size = {**FULL, "n_vertices": FULL["n_vertices"] >> cut,
                "n_edges": FULL["n_edges"] >> cut}
        cuts = [SIZE_CUT]
        if args.halvings:      # every line of a debugging run says so
            cuts.append(f"--halvings {args.halvings}: a debugging run, "
                        f"halved {args.halvings} more time(s)")
            label += f" (--halvings {args.halvings}: a debugging run)"
    try:
        import libtpu
        libtpu_v = getattr(libtpu, "__version__", "unknown")
    except ImportError:
        libtpu_v = None
    cache_dir = config.configure_compile_cache()

    def cache_entries() -> int:    # a CPU rehearsal keeps no cache
        return len(os.listdir(cache_dir)) \
            if cache_dir and os.path.isdir(cache_dir) else 0

    cache_before = cache_entries()
    print(json.dumps({
        "phase": "start", "device": label, "device_kind": dev.device_kind,
        "device_count": device["count"],
        "default_backend": jax.default_backend(),
        "jax": jax.__version__, "jaxlib": jaxlib.__version__,
        "libtpu": libtpu_v, "numpy": np.__version__, "seed": args.seed,
        "size": size, "cuts": cuts, "compile_cache_dir": cache_dir,
        "compile_cache_entries_at_start": cache_before}), flush=True)
    check(native.available(),
          "native C++ fold kernels unavailable (g++ build failed): the "
          "numpy fallback at this size is a different program")

    watch = DeclineWatch()
    logging.getLogger("raphtory_tpu").addHandler(watch)
    t_run = time.perf_counter()
    *sources, columns = make_sources(size, args.seed)
    ref = RefPool(columns, n_ids=max(size["n_vertices"], TAIL_POOL))
    del columns
    sm = Smoke(size, cuts, label, sources, ref)
    try:
        # everything the plan already knows is asked for now, so the
        # workers compute while the chip serves
        for T, w in [(T1, WINDOWS[0])] + [
                (T, w) for T in hops_of(RANGE4) for w in WINDOWS]:
            ref.want("pagerank", T, w)
        for T, w in [(T0, WINDOWS[0]), (T1, WINDOWS[0])] + [
                (T, w) for T in hops_of(RANGE2) for w in WINDOWS[:2]]:
            ref.want("cc", T, w)
        for T in hops_of(HOURLY):
            ref.want("degree", T, WINDOWS[1])
        sm.say("data_ready", seconds=round(time.perf_counter() - t_run, 2),
               reference_workers=ref.workers)
        try:
            sm.boot()
            sm.views()
            sm.ranges()
            sm.live()
            sm.kernel_memory()
        finally:
            sm.stop()
        if device["count"] >= 4:
            mesh_phases(sm)
        else:
            sm.say("mesh_phases_skipped",
                   reason=f"{device['count']} device(s): the four-chip "
                          "phases need >= 4")
        watch.assert_clean("smoke")
        ref_seconds = ref.seconds()
    finally:
        ref.close()

    peaks = {str(d): int((d.memory_stats() or {}).get(
        "peak_bytes_in_use", 0)) for d in jax.devices()}
    comp = obs_device.compile_block()
    sm.say("end", total_seconds=round(time.perf_counter() - t_run, 1),
           size=size, cuts=cuts, peak_bytes_in_use=peaks,
           xla_compiles=sum(k["compiles"] for k in comp.values()),
           xla_compile_seconds=round(
               sum(k["seconds"] for k in comp.values()), 2),
           xla_compile_seconds_by_kernel={
               name: round(k["seconds"], 2) for name, k in comp.items()},
           compile_cache_entries_at_start=cache_before,
           compile_cache_entries_at_end=cache_entries(),
           reference_cpu_seconds=round(ref_seconds, 1),
           warnings_logged=len(watch.records))
    if args.cpu_rehearsal:
        print(json.dumps({"ok": False, "device": device,
                          "rehearsal": "cpu — every phase ran and agreed, "
                                       "which proves the plumbing only"}))
        return 0
    print(json.dumps({"ok": True, "device": device}))
    return 0


if __name__ == "__main__":
    code = main()
    sys.stdout.flush()
    sys.stderr.flush()
    # as benchmark/run.py leaves: everything the smoke started is stopped
    # and its last line is out; the interpreter's teardown can abort the
    # process where a daemon thread of the program is still inside C++
    # ("FATAL: exception not rethrown", exit -6 after the last line — one
    # rehearsal in four at the parent of PR 37, under load)
    os._exit(code)
