#!/usr/bin/env python3
"""N-process localhost cluster smoke.

Driver (default mode) spawns ``RTPU_SMOKE_N`` worker processes
(default 2; CI also runs the 4-process leg) that form a real
`jax.distributed` cluster on localhost (CPU backend, 2 local devices
each), each serving REST on a port-strided listener (ISSUE-10 port
striding — worker i listens on rest_base + i). The smoke then proves
the ISSUE-10 acceptance path end to end:

* every worker runs one ConnectedComponents sweep over the SPARSE
  frontier route (ISSUE 20) before serving, so each process's
  ``/statusz`` — and the merged ``/clusterz`` route roll-up — must
  show nonzero sparse-route collective bytes;
* worker 0 submits a sharded sweep to ITSELF, forwards the SAME request
  to every peer with the ``X-RTPU-Trace`` header — one REST-initiated
  sweep, ONE trace id across all N processes;
* ``/tracez?trace_id=`` on the origin process shows the local half;
* ``/clusterz`` on worker 0 must show ALL N members reachable, watchdog
  membership, per-process watermark lag, nonzero per-route collective
  bytes, per-shard halo skew, and barrier-wait fields;
* ``/clusterz?trace_id=`` must reassemble the trace with spans from
  EVERY process;
* each worker's job carries its own ``X-RTPU-Tenant`` identity and the
  merged ``/clusterz`` workload view must show every tenant account
  with per-process attribution (ISSUE-11);
* finally worker 1 is DELAYED (a live source advances once then stops
  feeding, stalling its watermark fence — ACTIVE-stalled, not idle,
  per the ISSUE-15 lag_state semantics) and one federated ``/advisez``
  pass on worker 0 must fire the ``cluster-straggler`` rule naming
  process 1 (ISSUE-11: the advisor's distributed story);
* the merged ``/clusterz`` freshness block (ISSUE-15) must carry both
  processes' safe times + watermark spread, and the delayed worker's
  source must MOVE the merged min-watermark to its stalled fence;
* finally the mesh-divergence leg (ISSUE 19, ``RTPU_SMOKE_DIVERGE``,
  on by default): both workers issue one more sweep
  at the same dispatch seq with DIFFERENT window sets, and the merged
  ``/clusterz`` mesh block must report the injected divergence naming
  that exact superstep with both processes' fingerprints
  (DIVERGENCE_OK) — without hanging, because each worker's mesh is
  process-local and the fingerprint prefix check, not a stuck
  collective, is the detector.

The federated snapshot is written to ``--out`` (the CI failure
artifact). Exit 0 prints CLUSTERZ_OK; any assertion prints the evidence
and exits 1. A jax whose CPU client cannot even form the distributed
handshake exits 0 with SKIPPED (the capability under test is the
observability plane, not the collectives — each process sweeps its own
LOCAL 2-device mesh, so cross-process device collectives are not
required; on jaxes that lack them the smoke still proves everything).
"""

from __future__ import annotations

import argparse
import json
import os
import socket
import subprocess
import sys
import tempfile
import time
import urllib.request

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

_SKIP_MARKERS = (
    "Multiprocess computations aren't implemented on the CPU backend",
    "distributed initialization failed",
)


# ----------------------------------------------------------------- worker

def _http_json(url, body=None, headers=None, timeout=30.0):
    data = json.dumps(body).encode() if body is not None else None
    req = urllib.request.Request(url, data=data, headers=headers or {})
    if data is not None:
        req.add_header("Content-Type", "application/json")
    with urllib.request.urlopen(req, timeout=timeout) as r:
        return json.loads(r.read().decode())


def _wait_http(url, timeout_s=90.0):
    deadline = time.monotonic() + timeout_s
    while time.monotonic() < deadline:
        try:
            return _http_json(url, timeout=5.0)
        except OSError:   # URLError/refused/timeout: server still coming up
            time.sleep(0.25)
    raise TimeoutError(f"no answer from {url} within {timeout_s}s")


def _wait_done(base, job_id, timeout_s=300.0):
    deadline = time.monotonic() + timeout_s
    while time.monotonic() < deadline:
        r = _http_json(f"{base}/AnalysisResults?jobID={job_id}",
                       timeout=10.0)
        if r["status"] in ("done", "failed", "killed"):
            if r["status"] != "done":
                raise RuntimeError(f"job {job_id}: {r['status']} "
                                   f"{r.get('error')}")
            return r
        time.sleep(0.2)
    raise TimeoutError(f"job {job_id} not done in {timeout_s}s")


def worker(idx: int, n: int, coord_port: int, rest_base: int, tmpdir: str,
           cheap: bool, out: str | None) -> None:
    import jax

    jax.config.update("jax_platforms", "cpu")
    jax.config.update("jax_num_cpu_devices", 2)

    import numpy as np

    from raphtory_tpu.cluster.bootstrap import bootstrap
    from raphtory_tpu.cluster.watchdog import WatchDog
    from raphtory_tpu.core.service import TemporalGraph
    from raphtory_tpu.ingestion.pipeline import IngestionPipeline
    from raphtory_tpu.ingestion.source import IterableSource
    from raphtory_tpu.ingestion.updates import EdgeAdd
    from raphtory_tpu.jobs.manager import AnalysisManager
    from raphtory_tpu.jobs.rest import RestServer
    from raphtory_tpu.obs.trace import TRACER, TraceContext
    from raphtory_tpu.parallel import sharded

    assert bootstrap(coordinator_address=f"127.0.0.1:{coord_port}",
                     num_processes=n, process_id=idx)
    assert TRACER.process_index == idx

    # identical synthetic stream on both processes (the reference's
    # data-replicated ingestion); a LIVE unfinished source keeps the
    # watermark fence meaningful so lag_seconds is a real signal
    n_ev = 50_000 if cheap else 120_000
    n_vert = 2048 if cheap else 4096
    rng = np.random.default_rng(7)
    ups = [EdgeAdd(int(t), int(a), int(b))
           for t, a, b in zip(np.sort(rng.integers(0, 1000, n_ev)),
                              rng.integers(0, n_vert, n_ev),
                              rng.integers(0, n_vert, n_ev))]
    pipe = IngestionPipeline()
    pipe.add_source(IterableSource(ups, name="smoke"))
    pipe.run()
    graph = TemporalGraph(pipe.log, pipe.watermarks)

    # each process sweeps its own LOCAL 2-device mesh: the halo /
    # all_gather collective routes (and their telemetry) run on every
    # jax; cross-process reassembly happens at the REST layer
    mesh = sharded.make_mesh(2, 1,
                             devices=np.asarray(jax.local_devices()))
    wd = WatchDog()
    wd.join("shard")
    wd.join("job-server")
    mgr = AnalysisManager(graph, mesh=mesh)
    srv = RestServer(mgr, port=rest_base, watchdog=wd).start()
    me = f"http://127.0.0.1:{srv.port}"
    peers = [f"http://127.0.0.1:{rest_base + j}"
             for j in range(n) if j != idx]
    print(f"worker {idx} rest on {srv.port}", flush=True)

    # ---- sparse frontier route leg (ISSUE 20): every worker — at the
    # SAME dispatch seq, so the mesh sanitizer prefixes stay level —
    # runs one min-merge sweep over comm="sparse". The compacted-slice
    # accounting publishes nonzero sparse-route bytes on each process's
    # /statusz even on a process-local mesh, which the driver-side
    # merged /clusterz route roll-up must then show.
    from raphtory_tpu import build_view
    from raphtory_tpu.algorithms.connected_components import (
        ConnectedComponents)

    sharded.run(ConnectedComponents(max_steps=10),
                build_view(pipe.log, int(graph.latest_time)), mesh,
                comm="sparse")

    _wait_http(f"{me}/healthz")
    for peer in peers:
        _wait_http(f"{peer}/healthz")
    sentinel = os.path.join(tmpdir, "driver_done")

    if idx != 0:
        # serve until worker 0 finishes its assertions; worker 1 (only)
        # additionally becomes the DELAYED member when asked — a live
        # source that never feeds holds this process's watermark fence
        # still, so its lag grows while every peer's stays 0 (what the
        # advisor's cluster-straggler rule reads, bar lowered to CI
        # time via RTPU_ADVISOR_STALE_S); workers 2+ just serve.
        deadline = time.monotonic() + 600
        injected = False
        diverged = False
        while not os.path.exists(sentinel):
            if time.monotonic() > deadline:
                raise TimeoutError("no driver_done sentinel")
            if idx == 1 and not diverged and os.path.exists(
                    os.path.join(tmpdir, "make_diverge")):
                # mesh-divergence injection (ISSUE 19): issue a sweep
                # shaped like nothing worker 0 runs — worker 0 issues its
                # original body concurrently, so both processes advance
                # one dispatch seq but with DIFFERENT (window-count →
                # k_pad) compile-shape fingerprints. Local 2-device
                # meshes mean no cross-process collective can hang; the
                # fingerprint prefix check is the detector.
                # the straggler phase pinned THIS process's safe time at
                # 10 via the stalled source — a sweep at latest_time
                # would wait on that fence forever instead of reaching
                # the mesh. The straggler assertions are all done by the
                # time worker 0 asks for divergence, so retire it.
                if injected:
                    graph.watermarks.finish("stalled-smoke")
                dbody = {"analyserName": "PageRank",
                         "timestamp": int(graph.latest_time),
                         "windowType": "batched", "windowSet": [400],
                         "params": {"max_steps": 10, "tol": 0.0}}
                dsub = _http_json(f"{me}/ViewAnalysisRequest", dbody,
                                  headers={"X-RTPU-Tenant": "smoke-w1"})
                _wait_done(me, dsub["jobID"])
                diverged = True
                with open(os.path.join(tmpdir, "diverge_up"), "w") as f:
                    f.write("ok")
            if idx == 1 and not injected and os.path.exists(
                    os.path.join(tmpdir, "make_straggler")):
                # a source that advanced ONCE then stalls: under the
                # idle/active watermark semantics (ISSUE-15) a
                # registered-but-never-advanced source is IDLE (no
                # traffic ≠ stalled) and must not alarm — the straggler
                # has to have streamed. The single low advance also
                # drags this process's safe_time down to 10, which is
                # exactly what must move the merged /clusterz
                # min-watermark.
                graph.watermarks.register("stalled-smoke")
                graph.watermarks.advance("stalled-smoke", 10)
                assert graph.watermarks.lag_state()[0] == "active"
                injected = True
                with open(os.path.join(tmpdir, "straggler_up"), "w") as f:
                    f.write("ok")
            time.sleep(0.25)
        srv.stop()
        print(f"worker {idx} ok", flush=True)
        return

    # ---- worker 0: the REST-initiated cross-process sweep ----
    latest = int(graph.latest_time)
    body = {"analyserName": "PageRank", "timestamp": latest,
            "windowType": "batched", "windowSet": [800, 200],
            "params": {"max_steps": 10, "tol": 0.0}}
    sub0 = _http_json(f"{me}/ViewAnalysisRequest", body,
                      headers={"X-RTPU-Tenant": "smoke-w0"})
    tid = sub0.get("traceID")
    assert tid, f"no traceID in submit response: {sub0}"
    assert sub0.get("tenant") == "smoke-w0", sub0
    # forward the hop to EVERY peer: the SAME trace id crosses each
    # process boundary, under that peer's own tenant identity (the
    # merged workload view must attribute each account to its process)
    wire = TraceContext(tid, 0, origin=idx).to_wire()
    peer_subs = []
    for j, peer in zip(range(1, n), peers):
        subj = _http_json(f"{peer}/ViewAnalysisRequest", body,
                          headers={TraceContext.HEADER: wire,
                                   "X-RTPU-Tenant": f"smoke-w{j}"})
        assert subj.get("traceID") == tid, (
            f"peer {j} opened its own trace: {subj} != {tid}")
        assert subj.get("tenant") == f"smoke-w{j}", subj
        peer_subs.append((peer, subj))
    _wait_done(me, sub0["jobID"])
    for peer, subj in peer_subs:
        _wait_done(peer, subj["jobID"])

    # ---- collect the evidence FIRST (the CI failure artifact must
    # show what the cluster looked like even when an assertion fires)
    tz = _http_json(f"{me}/tracez?trace_id={tid}")
    cz = _http_json(f"{me}/clusterz?refresh=1")
    czt = _http_json(f"{me}/clusterz?trace_id={tid}&refresh=1")
    if out:
        with open(out, "w") as f:
            json.dump({"clusterz": cz, "trace": czt["trace"],
                       "trace_id": tid}, f, indent=1, default=str)

    # ---- acceptance assertions ----
    assert tz["spans"], "origin /tracez?trace_id= has no spans"
    assert any(s["name"] == "comm.exchange" for s in tz["spans"]), \
        "no comm.exchange span in the origin trace"
    procs = cz["processes"]
    assert cz["processes_reachable"] == n, procs
    assert {p.get("process_index") for p in procs.values()} == \
        set(range(n)), procs
    shard_members = cz["members"].get("shard", {})
    assert shard_members.get("count") == n, cz["members"]
    for name, p in procs.items():
        routes = p["collectives"]["routes"]
        assert routes and any(r["bytes"] > 0 for r in routes.values()), \
            f"{name}: no collective bytes: {routes}"
        assert any(k.startswith("sparse/") and r["bytes"] > 0
                   for k, r in routes.items()), \
            f"{name}: no sparse-route bytes: {routes}"
        skew = p["collectives"]["skew"]
        assert skew and "halo_dst" in skew and "edges_dst" in skew, \
            f"{name}: no halo/degree skew: {skew}"
        assert "barrier_wait_seconds" in p["collectives"], name
        assert p.get("watermark_lag_seconds") is not None, name
        assert "queue_depth" in p, name
    # the merged route roll-up (ISSUE 20): sparse-route bytes summed
    # over the cluster, plus the chooser's verdict counts
    rt = (cz.get("routes") or {}).get("totals") or {}
    assert any(k.startswith("sparse/") and r["bytes"] > 0
               for k, r in rt.items()), f"no merged sparse bytes: {rt}"
    decisions = (cz.get("routes") or {}).get("decision_counts") or {}
    assert any(k.endswith("/sparse") for k in decisions), decisions

    with_spans = czt["trace"]["processes_with_spans"]
    assert set(with_spans) >= {f"process_{j}" for j in range(n)}, (
        f"trace {tid} not reassembled from all processes: {with_spans}")

    # ---- freshness plane in the MERGED view (ISSUE-15): both
    # processes' ingest telemetry federates — per-process safe times,
    # watermark spread, and a merged min-watermark (moved by the
    # straggler phase below)
    fz = cz["freshness"]
    assert {f"process_{j}" for j in range(n)} <= set(
        fz["watermark_lag_by_process"]), fz
    assert "watermark_spread_seconds" in fz, fz
    # both replays finished: every fence sits at the all-done sentinel,
    # which the merge renders as null (not 4611686018427387904)
    assert fz["min_safe_time"] is None, fz
    for name, p in procs.items():
        fr = p.get("freshness") or {}
        assert fr.get("sources", 0) >= 1, (name, fr)
        assert "queryable_lag_seconds" in fr, (name, fr)

    # ---- per-tenant accounts in the MERGED mesh view (ISSUE-11):
    # each worker's job landed in its own tenant account, attributed to
    # its own process, summed cluster-wide by /clusterz. A job's REST
    # status flips to done BEFORE its ledger publishes into the account
    # (jobs/manager.py ordering), so re-scrape briefly rather than read
    # one racy snapshot
    deadline = time.monotonic() + 30
    while True:
        tenants = (cz.get("workload") or {}).get("tenants") or {}
        if {f"smoke-w{j}" for j in range(n)} <= set(tenants):
            break
        if time.monotonic() > deadline:
            raise AssertionError(f"tenant accounts never federated: "
                                 f"{tenants}")
        time.sleep(0.5)
        cz = _http_json(f"{me}/clusterz?refresh=1")
    assert "process_0" in tenants["smoke-w0"]["by_process"], tenants
    assert "process_1" in tenants["smoke-w1"]["by_process"], tenants
    assert tenants["smoke-w0"]["queries"] >= 1, tenants
    assert tenants["smoke-w0"]["cost_seconds"] > 0, tenants

    # ---- straggler injection (ISSUE-11): worker 1 delays — its
    # watermark fence stops advancing — and a federated /advisez pass
    # HERE must fire the cluster-straggler rule naming process 1. The
    # bar is CI-sized via RTPU_ADVISOR_STALE_S=2 (driver env); worker
    # 1's lag clock starts at its ingestion end, so the signal towers
    # over the bar the moment the stalled source registers.
    with open(os.path.join(tmpdir, "make_straggler"), "w") as f:
        f.write("go")
    deadline = time.monotonic() + 60
    while not os.path.exists(os.path.join(tmpdir, "straggler_up")):
        if time.monotonic() > deadline:
            raise TimeoutError("worker 1 never injected its straggler")
        time.sleep(0.2)
    az = finding = None
    deadline = time.monotonic() + 90
    while finding is None and time.monotonic() < deadline:
        az = _http_json(f"{me}/advisez?refresh=1", timeout=30.0)
        finding = next((f for f in az["findings"]
                        if f["rule_id"] == "cluster-straggler"), None)
        if finding is None:
            time.sleep(1.0)
    if out:   # the snapshot grows the advisor's verdict (or its absence)
        with open(out, "w") as f:
            json.dump({"clusterz": cz, "trace": czt["trace"],
                       "trace_id": tid, "advisez": az}, f, indent=1,
                      default=str)
    assert finding is not None, (
        f"cluster-straggler never fired: {az and az['findings']}")
    ev = finding["evidence"]
    assert ev["process"] == "process_1", ev
    assert ev["process_index"] == 1, ev
    assert ev["watermark_lag_by_process"]["process_1"] > \
        ev["watermark_lag_by_process"]["process_0"], ev
    print("STRAGGLER_OK", flush=True)

    # ---- the delayed worker's source MOVES the merged min-watermark
    # (ISSUE-15): worker 1's stalled source advanced once to 10, so its
    # safe_time — and therefore the cluster's merged min — is 10, and
    # the per-process watermark spread shows the lagging ingest shard
    # the barrier-wait straggler signals cannot see
    cz2 = _http_json(f"{me}/clusterz?refresh=1")
    fz2 = cz2["freshness"]
    # the stalled source MOVED the merged min-watermark: null (all
    # done) → the delayed worker's finite fence
    assert fz2["min_safe_time"] == 10, fz2
    assert fz2["min_safe_process"] == "process_1", fz2
    assert fz2["watermark_spread_seconds"] > 0, fz2
    if out:   # the artifact keeps the moved-min-watermark evidence too
        with open(out, "w") as f:
            json.dump({"clusterz": cz, "trace": czt["trace"],
                       "trace_id": tid, "advisez": az,
                       "clusterz_post_straggler": cz2}, f, indent=1,
                      default=str)
    print("FRESHNESS_OK", flush=True)

    # ---- mesh-divergence leg (ISSUE 19): on by default, disabled by
    # RTPU_SMOKE_DIVERGE=0. Both workers issue one more sweep at the same
    # dispatch seq but with different window sets — different compile
    # shapes, so the /clusterz prefix cross-check must name that seq as
    # the first divergent superstep.
    if os.environ.get("RTPU_SMOKE_DIVERGE", "1") not in ("", "0", "false"):
        mz = cz2.get("mesh") or {}
        assert mz.get("processes_enabled") == n, (
            f"mesh sanitizer not armed on all workers: {mz}")
        # the main phase ran the SAME body on every process: prefixes
        # must agree and dispatch counts must be level before injection
        assert mz.get("divergence") is None, mz
        counts = mz.get("dispatches_by_process") or {}
        assert len(set(counts.values())) == 1, counts
        seq_expected = counts["process_0"]
        with open(os.path.join(tmpdir, "make_diverge"), "w") as f:
            f.write("go")
        div0 = _http_json(f"{me}/ViewAnalysisRequest", body,
                          headers={"X-RTPU-Tenant": "smoke-w0"})
        _wait_done(me, div0["jobID"])
        deadline = time.monotonic() + 90
        while not os.path.exists(os.path.join(tmpdir, "diverge_up")):
            if time.monotonic() > deadline:
                raise TimeoutError("worker 1 never injected divergence")
            time.sleep(0.2)
        div = cz3 = None
        deadline = time.monotonic() + 30
        while div is None and time.monotonic() < deadline:
            cz3 = _http_json(f"{me}/clusterz?refresh=1")
            div = (cz3.get("mesh") or {}).get("divergence")
            if div is None:
                time.sleep(0.5)
        assert div is not None, (
            f"injected divergence never detected: {cz3.get('mesh')}")
        # the report must NAME the first divergent superstep and carry
        # both processes' fingerprints side by side
        assert div["seq"] == seq_expected, (div, seq_expected)
        assert div["fingerprint_a"] and div["fingerprint_b"], div
        assert div["fingerprint_a"] != div["fingerprint_b"], div
        assert {div["process_a"], div["process_b"]} == {
            "process_0", "process_1"}, div
        if out:   # the artifact keeps the divergence verdict too
            with open(out, "w") as f:
                json.dump({"clusterz": cz, "trace": czt["trace"],
                           "trace_id": tid, "advisez": az,
                           "clusterz_post_straggler": cz2,
                           "mesh_divergence": cz3.get("mesh")}, f,
                          indent=1, default=str)
        print("DIVERGENCE_OK", flush=True)

    with open(sentinel, "w") as f:
        f.write("ok")
    srv.stop()
    print("CLUSTERZ_OK", flush=True)


# ----------------------------------------------------------------- driver

def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def _free_port_run(n: int) -> int:
    """A base port with base+1..base+n-1 also free (the strided REST
    listeners — worker i binds rest_base + i)."""
    for _ in range(64):
        base = _free_port()
        try:
            for j in range(1, n):
                with socket.socket() as s:
                    s.bind(("127.0.0.1", base + j))
            return base
        except OSError:
            continue
    raise RuntimeError(f"no free run of {n} adjacent ports")


def run_cluster(out: str | None = None, cheap: bool = False,
                timeout_s: float = 600.0, n: int | None = None) -> dict:
    """Spawn the N-worker cluster (``n`` or RTPU_SMOKE_N, default 2);
    returns {skipped, outputs}. Raises on real failures (assertions
    inside a worker, timeouts)."""
    if n is None:
        try:
            n = int(os.environ.get("RTPU_SMOKE_N", "2"))
        except ValueError:
            n = 2
    n = max(2, n)
    coord = _free_port()
    rest_base = _free_port_run(n)
    tmpdir = tempfile.mkdtemp(prefix="rtpu_cluster_smoke_")
    env = dict(os.environ)
    env["PYTHONPATH"] = _REPO + os.pathsep + env.get("PYTHONPATH", "")
    env.pop("JAX_PLATFORMS", None)     # workers pin their own backend
    env.pop("XLA_FLAGS", None)
    env["RTPU_TRACE"] = "1"
    # forced, not setdefault: the worker's peer-URL math is rest_base +
    # j, i.e. stride 1 — an inherited RTPU_PORT_STRIDE=2 would bind
    # worker j two-j ports up and the smoke would poll dead ports
    env["RTPU_PORT_STRIDE"] = "1"
    env.pop("RTPU_CLUSTER_PEERS", None)   # derive from the topology
    # CI-sized staleness bar for the straggler phase: worker 1's stalled
    # fence must clear it in smoke time, not the 30 s production default
    env["RTPU_ADVISOR_STALE_S"] = "2"
    # mesh-divergence leg (ISSUE 19): on by default (RTPU_SMOKE_DIVERGE=0
    # disables). The workers' local meshes never span processes, so the
    # injected divergence cannot hang a collective — the fingerprint
    # prefix check is the detector, and the barrier watchdog rides along
    # armed.
    diverge = os.environ.get("RTPU_SMOKE_DIVERGE", "1") not in (
        "", "0", "false")
    if diverge:
        env["RTPU_SANITIZE"] = "1"
        env.setdefault("RTPU_SANITIZE_BARRIER_S", "5")
        env["RTPU_SMOKE_DIVERGE"] = "1"
    else:
        env["RTPU_SMOKE_DIVERGE"] = "0"
    procs = []
    for i in range(n):
        cmd = [sys.executable, os.path.abspath(__file__),
               "--worker", str(i), "--n", str(n),
               "--coord-port", str(coord),
               "--rest-base", str(rest_base), "--tmpdir", tmpdir]
        if cheap:
            cmd.append("--cheap")
        if out and i == 0:
            cmd += ["--out", out]
        procs.append(subprocess.Popen(
            cmd, env=env, stdout=subprocess.PIPE,
            stderr=subprocess.STDOUT, text=True))
    outs = [""] * n
    try:
        for i, p in enumerate(procs):
            outs[i], _ = p.communicate(timeout=timeout_s)
    finally:
        for p in procs:
            if p.poll() is None:
                p.kill()
    if any(m in o for o in outs for m in _SKIP_MARKERS):
        return {"skipped": True, "outputs": outs}
    for i, (p, o) in enumerate(zip(procs, outs)):
        if p.returncode != 0:
            # both sides of the story: a worker-0 timeout is usually the
            # SYMPTOM of the peer dying mid-handshake, so the peer's
            # traceback is the one that matters
            other = "\n".join(
                f"--- worker {j} output ---\n{oo[-2000:]}"
                for j, oo in enumerate(outs) if j != i)
            raise RuntimeError(
                f"worker {i} failed (rc={p.returncode}):\n{o[-4000:]}"
                f"\n{other}")
    if "CLUSTERZ_OK" not in outs[0]:
        raise RuntimeError(f"worker 0 missing CLUSTERZ_OK:\n"
                           f"{outs[0][-4000:]}")
    if diverge and "DIVERGENCE_OK" not in outs[0]:
        raise RuntimeError(f"worker 0 missing DIVERGENCE_OK:\n"
                           f"{outs[0][-4000:]}")
    return {"skipped": False, "outputs": outs}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--worker", type=int, default=None)
    ap.add_argument("--n", type=int, default=0,
                    help="cluster size (driver: RTPU_SMOKE_N, default 2)")
    ap.add_argument("--coord-port", type=int, default=0)
    ap.add_argument("--rest-base", type=int, default=0)
    ap.add_argument("--tmpdir", default="")
    ap.add_argument("--cheap", action="store_true")
    ap.add_argument("--out", default=None,
                    help="write the federated snapshot JSON here")
    args = ap.parse_args(argv)
    if args.worker is not None:
        worker(args.worker, max(2, args.n), args.coord_port,
               args.rest_base, args.tmpdir, args.cheap, args.out)
        return 0
    res = run_cluster(out=args.out, cheap=args.cheap, n=args.n or None)
    if res["skipped"]:
        print("SKIPPED: this jax cannot form a localhost "
              "jax.distributed cluster")
        return 0
    print("cluster smoke ok")
    return 0


if __name__ == "__main__":
    sys.exit(main())
