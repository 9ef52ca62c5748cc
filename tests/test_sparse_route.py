"""Sparse frontier collectives (parallel/frontier.py + the route chooser
in parallel/sharded.py): min-merge programs must be BITWISE identical
across every comm route and shard count over adversarial delete/tombstone
logs; bucketed padding must keep the compile-key set frozen while
frontier sizes vary; the chooser's decision table must be reproducible
from injected evidence; and processes disagreeing on the route at the
same dispatch seq must flag as mesh divergence (docs/COMM.md)."""

import os
import socket
import subprocess
import sys

import jax
import numpy as np
import pytest

from raphtory_tpu.algorithms import ConnectedComponents, PageRank
from raphtory_tpu.algorithms.traversal import BFS, SSSP
from raphtory_tpu.analysis.sanitizer import (MeshSanitizer,
                                             mesh_prefix_divergence)
from raphtory_tpu.core.snapshot import build_view
from raphtory_tpu.obs import device as obs_device
from raphtory_tpu.parallel import frontier, sharded
from raphtory_tpu.parallel.frontier import (frontier_bucket,
                                            sparse_bucket_floor)
from raphtory_tpu.parallel.sweep import ShardedSweep

from test_sweep import random_log

SEEDS = (1, 5, 9)


@pytest.fixture(scope="module")
def graph():
    """One adversarial log (deletes, tombstones, duplicate timestamps,
    weighted edges) shared by the whole matrix — heavy id reuse so every
    program revisits resurrected rows."""
    rng = np.random.default_rng(20)
    log = random_log(rng, n_events=700, n_ids=48, t_span=80, props=True)
    return log, build_view(log, 60)


def _mesh(shards):
    return sharded.make_mesh(shards, devices=jax.devices()[:shards])


def _bitwise(a, b):
    la = jax.tree_util.tree_leaves(a)
    lb = jax.tree_util.tree_leaves(b)
    assert len(la) == len(lb)
    return all(np.array_equal(np.asarray(x), np.asarray(y), equal_nan=True)
               for x, y in zip(la, lb))


# ---------------------------------------------------------- equivalence


@pytest.mark.parametrize("windows", [None, [70, 25]],
                         ids=["single", "windowed"])
@pytest.mark.parametrize("prog", [
    ConnectedComponents(max_steps=40),
    BFS(seeds=SEEDS, directed=False, max_steps=40),
    SSSP(seeds=SEEDS, weight_prop="w", max_steps=40),
], ids=["cc", "bfs", "sssp"])
def test_routes_bitwise_identical_four_shards(graph, prog, windows):
    """The contract the route chooser relies on: for monotone min-merge
    programs every route computes the SAME bits, so route choice is purely
    a performance decision (ISSUE 20 acceptance)."""
    _, view = graph
    mesh = _mesh(4)
    dense, s_dense = sharded.run(prog, view, mesh, windows=windows,
                                 comm="all_gather")
    sparse, s_sparse = sharded.run(prog, view, mesh, windows=windows,
                                   comm="sparse")
    assert int(s_dense) == int(s_sparse)
    assert _bitwise(dense, sparse)


@pytest.mark.parametrize("shards", [1, 2, 4])
def test_routes_bitwise_identical_across_shard_counts(graph, shards):
    """Same bits at every process/shard count — P=1 exercises the
    whole-sweep while_loop fast path, P>1 the compact-exchange-merge
    loop; halo rides along as the third route where it exists."""
    _, view = graph
    mesh = _mesh(shards)
    prog = ConnectedComponents(max_steps=40)
    dense, s_d = sharded.run(prog, view, mesh, windows=[70, 25],
                             comm="all_gather")
    sparse, s_s = sharded.run(prog, view, mesh, windows=[70, 25],
                              comm="sparse")
    halo, s_h = sharded.run(prog, view, mesh, windows=[70, 25],
                            comm="halo")
    assert int(s_d) == int(s_s)
    assert _bitwise(dense, sparse)
    assert _bitwise(dense, halo)


def test_multi_branch_exchange_merge_bitwise(graph):
    """The cross-process branch of run_sparse (count agreement round,
    bucketed slice allgather, scatter min-merge) driven in-process with
    ``multi=True`` — process_allgather over one process is the exchange
    machinery with n_procs=1, so the merge path itself is what's under
    test, not the transport."""
    _, view = graph
    mesh = _mesh(4)
    prog = ConnectedComponents(max_steps=40)
    wlist = [-1, 70]
    sv = sharded.partition_view(view, 4)
    res, steps, acct = frontier.run_sparse(
        prog, view, mesh, sv, wlist, multi=True)
    dense, s_d = sharded.run(prog, view, mesh, windows=[None, 70],
                             comm="all_gather")
    assert int(s_d) == steps
    assert _bitwise(dense, res)
    assert acct["supersteps"] == steps
    assert acct["bytes"] > 0 and acct["rows"] > 0
    assert 0.0 <= acct["density"] <= 1.0


def test_sparse_route_rejects_non_monotone_programs(graph):
    _, view = graph
    with pytest.raises(ValueError, match="monotone_min"):
        sharded.run(PageRank(max_steps=5), view, _mesh(2), comm="sparse")


# ------------------------------------------------- bucketed padding


def test_frontier_bucket_ladder():
    floor = 16
    assert frontier_bucket(0, floor) == floor
    assert frontier_bucket(floor, floor) == floor
    assert frontier_bucket(floor + 1, floor) == 2 * floor
    assert frontier_bucket(1000, floor) == 1024
    assert frontier_bucket(1000, floor, cap=300) == 300
    # the ladder is monotone and bounded: every count in a power-of-two
    # band maps to ONE capacity, so the collective shape set stays tiny
    buckets = {frontier_bucket(c, floor, cap=4096) for c in range(4097)}
    assert len(buckets) <= int(np.log2(4096 // floor)) + 2


def test_bucket_floor_env_knob(monkeypatch, graph):
    monkeypatch.setenv("RTPU_SPARSE_BUCKETS", "32")
    assert sparse_bucket_floor() == 32
    monkeypatch.setenv("RTPU_SPARSE_BUCKETS", "junk")
    assert sparse_bucket_floor() == 256
    monkeypatch.setenv("RTPU_SPARSE_BUCKETS", "2")
    assert sparse_bucket_floor() == 8   # floored at 8 slots
    # the knob only rescales the exchange buckets — results are bit-equal
    _, view = graph
    mesh = _mesh(2)
    prog = ConnectedComponents(max_steps=40)
    monkeypatch.setenv("RTPU_SPARSE_BUCKETS", "16")
    small, _ = sharded.run(prog, view, mesh, comm="sparse")
    monkeypatch.setenv("RTPU_SPARSE_BUCKETS", "1024")
    big, _ = sharded.run(prog, view, mesh, comm="sparse")
    assert _bitwise(small, big)


def test_compile_keys_stable_across_frontier_sizes(graph):
    """Bucketed padding keeps frontier SIZES out of compiled shapes: the
    per-(algorithm, shapes) kernel set is exactly init/superstep/sweep/
    finalize, and re-dispatching with different frontier evolutions adds
    no new compile-ring entries (the PR-12 compile plane is the
    witness)."""
    _, view = graph
    mesh = _mesh(4)
    prog = BFS(seeds=SEEDS, directed=False, max_steps=40)
    sharded.run(prog, view, mesh, comm="sparse")            # warm
    info0 = frontier._frontier_runner.cache_info()
    block0 = {k: v["compiles"] for k, v in
              obs_device.compile_block().items()
              if k.startswith("frontier.")}
    # different seed sets drive very different frontier evolutions, but
    # the compiled pieces are cached per (program, shapes) — and a
    # REPEAT of the same program must not even miss the runner cache
    sharded.run(prog, view, mesh, comm="sparse")
    for seeds in [(2,), (3, 7, 11, 13), tuple(range(20))]:
        sharded.run(BFS(seeds=seeds, directed=False, max_steps=40),
                    view, mesh, comm="sparse")
    info1 = frontier._frontier_runner.cache_info()
    assert info1.misses == info0.misses + 3   # one per NEW program only
    block1 = {k: v["compiles"] for k, v in
              obs_device.compile_block().items()
              if k.startswith("frontier.")}
    # the observed kernel names factor as {init,superstep,sweep,finalize}
    # x algorithm labels; repeat dispatches of an already-seen program
    # recompiled nothing
    stems = {k.split(".")[1] for k in block1}
    assert stems <= {"init", "superstep", "sweep", "finalize"}
    for k, n in block0.items():
        assert block1.get(k, n) == n, k


# ------------------------------------------------- the route chooser


def _chooser_fixture(graph, shards=4):
    _, view = graph
    mesh = _mesh(shards)
    sv = sharded.partition_view(view, shards)
    return view, sv, mesh


def test_choose_route_decision_table(graph, monkeypatch):
    view, sv, mesh = _chooser_fixture(graph)
    cc = ConnectedComponents(max_steps=40)
    pr = PageRank(max_steps=5)
    # the byte model floors the sparse estimate at one bucket per
    # process; on this deliberately tiny graph the default 256-slot
    # floor alone would out-weigh the dense routes, which is correct
    # but not what this table exercises — shrink it
    monkeypatch.setenv("RTPU_SPARSE_BUCKETS", "8")

    def pick(prog, requested, multi, env="auto", hint=None):
        return sharded.choose_route(prog, view, sv, mesh, requested, 2,
                                    multi, env=env, density_hint=hint)

    # explicit comm= always wins
    d = pick(cc, "all_gather", True, hint=0.001)
    assert d["route"] == "all_gather"
    assert d["reason"] == "explicit comm= argument"
    # RTPU_COMM_ROUTE steers auto dispatches only
    d = pick(cc, "auto", True, env="sparse")
    assert d["route"] == "sparse" and "RTPU_COMM_ROUTE" in d["reason"]
    d = pick(cc, "halo", True, env="sparse")
    assert d["route"] == "halo"
    # env-forced sparse on an ineligible program falls back dense
    d = pick(pr, "auto", True, env="sparse")
    assert d["route"] in ("halo", "all_gather")
    assert "not monotone_min" in d["reason"]
    # explicit sparse on an ineligible program is a hard error
    with pytest.raises(ValueError, match="monotone_min"):
        pick(pr, "sparse", True)
    # measured density below the crossover -> sparse (multi only)
    d = pick(cc, "auto", True, hint=0.01)
    assert d["route"] == "sparse"
    assert d["reason"].startswith("measured density")
    assert d["evidence"]["density_measured"] is True
    # dense frontier -> the pre-sparse dense volume rule (at density 1.0
    # a sparse slot costs strictly more than the dense item it replaces)
    d = pick(cc, "auto", True, hint=1.0)
    assert d["route"] in ("halo", "all_gather")
    assert "dense volume rule" in d["reason"]
    # single-process meshes never pay the host-driven loop
    d = pick(cc, "auto", False, hint=0.01)
    assert d["route"] in ("halo", "all_gather")
    assert "single-process" in d["reason"]
    # ineligible program under plain auto
    d = pick(pr, "auto", True, hint=0.01)
    assert "not monotone_min" in d["reason"]
    # cold start: the optimistic sparse prior decides, flagged unmeasured
    d = pick(cc, "auto", True, hint=None)
    if d["route"] == "sparse":
        assert d["reason"].startswith("prior density") \
            or d["evidence"]["density_measured"]
    # evidence carries the full byte model + uniform inputs
    ev = d["evidence"]
    assert set(ev["est_bytes_per_superstep"]) == {"halo", "all_gather",
                                                  "sparse"}
    assert ev["n_pad"] == int(view.n_pad) and ev["shards"] == 4


def test_choose_route_measured_history_feeds_back(graph):
    """A sparse dispatch records its allgathered mean density under the
    (algorithm, window-batch) key; the NEXT auto decision for that key is
    measured, not prior-driven."""
    view, sv, mesh = _chooser_fixture(graph)
    prog = ConnectedComponents(max_steps=40)
    key = sharded.choose_route(prog, view, sv, mesh, "auto", 1,
                               True)["key"]
    sharded.run(prog, view, mesh, comm="sparse")
    assert sharded.COLLECTIVES.frontier_hint(key) is not None
    d = sharded.choose_route(prog, view, sv, mesh, "auto", 1, True)
    assert d["evidence"]["density_measured"] is True


def test_route_decision_published_to_statusz_table(graph):
    _, view = graph
    mesh = _mesh(2)
    before = sharded.COLLECTIVES.snapshot()["route_table"]["counts"]
    sharded.run(ConnectedComponents(max_steps=40), view, mesh,
                comm="sparse")
    after = sharded.COLLECTIVES.snapshot()["route_table"]["counts"]
    key = "ConnectedComponents/sparse"
    assert after.get(key, 0) == before.get(key, 0) + 1
    recent = sharded.COLLECTIVES.snapshot()["route_table"]["recent"]
    mine = [r for r in recent if r["route"] == "sparse"
            and r["algorithm"] == "ConnectedComponents"]
    assert mine and mine[-1]["reason"] == "explicit comm= argument"


# ------------------------------------- mesh sanitizer: route divergence


def test_msan_flags_mixed_route_dispatch_divergence():
    """Two processes whose choosers disagree at the same dispatch seq is
    exactly the divergence the fingerprint (which includes the ROUTE)
    exists to catch — same site, same shapes, different collective."""
    p0, p1 = MeshSanitizer(), MeshSanitizer()
    site, sig = "parallel.sharded.run/ConnectedComponents", "S4W1k1n64"
    p0.note_dispatch(site, "sparse", sig, "i64")
    p1.note_dispatch(site, "sparse", sig, "i64")
    assert mesh_prefix_divergence({0: p0.ring(), 1: p1.ring()}) is None
    p0.note_dispatch(site, "sparse", sig, "i64")
    p1.note_dispatch(site, "all_gather", sig, "i64")
    div = mesh_prefix_divergence({0: p0.ring(), 1: p1.ring()})
    assert div is not None and div["seq"] == 1
    assert "sparse" in div["fingerprint_a"]
    assert "all_gather" in div["fingerprint_b"]


# ----------------------------------------------- skew refresh (round-7)


def test_sharded_sweep_refreshes_stale_skew():
    """Round-7 finding: ``sv.skew`` was computed once at the static build
    and never again. A skew-INVERTING ingest suffix (early events hammer
    the low shards, the suffix hammers the high shards) must flip the
    published per-shard histogram once enough rows churn."""
    from raphtory_tpu.core.events import EventLog

    log = EventLog()
    n_ids = 64   # 4 shards x 16 vids: shard of vid v is v // 16
    low, high = range(16), range(48, 64)
    # epoch 1: the full low x low pair block (256 distinct pairs -> the
    # refresh threshold max(256, m/4) is reachable in one advance)
    for i, (a, b) in enumerate((a, b) for a in low for b in low):
        log.add_edge(int(i % 50), a, b)
    # epoch 2: tombstone every epoch-1 pair and aim the same load HIGH
    for i, (a, b) in enumerate((a, b) for a in low for b in low):
        log.delete_edge(50 + int(i % 40), a, b)
    for i, (a, b) in enumerate((a, b) for a in high for b in high):
        log.add_edge(50 + int(i % 40), a, b)
    sweep = ShardedSweep(log, 4)
    refreshes0 = sharded.COLLECTIVES.snapshot()["skew_refreshes"]
    sweep.advance(49)
    assert sharded.COLLECTIVES.snapshot()["skew_refreshes"] > refreshes0
    early_dst = sweep.sv.skew["edges_dst"]["per_shard"]
    # epoch 1 live load concentrates in the FIRST shard (the static
    # build-time histogram saw both epochs and is balanced — exactly the
    # staleness the refresh replaces)
    assert early_dst[0] == max(early_dst) and early_dst[0] > early_dst[-1]
    sweep.advance(100)
    # the published histogram followed the ingest: the LAST shard now
    # carries the peak the route chooser and advisor read
    late_dst = sweep.sv.skew["edges_dst"]["per_shard"]
    assert late_dst[-1] == max(late_dst) and late_dst[-1] > late_dst[0]


# ------------------------------------------- 2-process subprocess leg


WORKER = r'''
import sys

import jax

jax.config.update("jax_platforms", "cpu")
jax.config.update("jax_num_cpu_devices", 2)

pid, port = int(sys.argv[1]), sys.argv[2]

from raphtory_tpu.cluster.bootstrap import bootstrap

assert bootstrap(coordinator_address=f"127.0.0.1:{port}",
                 num_processes=2, process_id=pid)

import numpy as np

from raphtory_tpu.algorithms import ConnectedComponents
from raphtory_tpu.core.events import EventLog
from raphtory_tpu.core.snapshot import build_view
from raphtory_tpu.engine import bsp
from raphtory_tpu.parallel import sharded

rng = np.random.default_rng(2)
log = EventLog()
for _ in range(500):
    t = int(rng.integers(0, 100))
    a, b = (int(x) for x in rng.integers(0, 40, 2))
    if rng.random() < 0.15:
        log.delete_edge(t, a, b)
    else:
        log.add_edge(t, a, b)
view = build_view(log, 100)

mesh = sharded.make_mesh(4, 1, devices=jax.devices())
cc = ConnectedComponents(max_steps=40)
got, steps = sharded.run(cc, view, mesh, windows=[100, 30], comm="sparse")
with jax.default_device(jax.local_devices()[0]):
    want, _ = bsp.run(cc, view, windows=[100, 30])
assert np.array_equal(np.asarray(got), np.asarray(want)), "sparse != bsp"
snap = sharded.COLLECTIVES.snapshot()["routes"]
key = f"sparse/{cc.direction}"
assert snap[key]["bytes"] > 0 and snap[key]["supersteps"] == int(steps)
print(f"proc {pid} sparse ok steps={int(steps)}", flush=True)
'''


def test_two_process_sparse_exchange_bitwise(tmp_path):
    """The REAL cross-process frontier exchange: 2 localhost processes,
    4-device global mesh, sparse CC vs the single-device bsp reference —
    bitwise. Skips where the CPU client lacks multiprocess computations
    (the same gate as tests/test_multiprocess.py)."""
    worker = tmp_path / "worker.py"
    worker.write_text(WORKER)
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        port = s.getsockname()[1]
    env = dict(os.environ)
    env["PYTHONPATH"] = (os.path.dirname(os.path.dirname(__file__))
                         + os.pathsep + env.get("PYTHONPATH", ""))
    env.pop("JAX_PLATFORMS", None)
    env.pop("XLA_FLAGS", None)
    procs = [
        subprocess.Popen(
            [sys.executable, str(worker), str(i), str(port)], env=env,
            stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True)
        for i in (0, 1)
    ]
    outs = []
    try:
        for p in procs:
            out, _ = p.communicate(timeout=240)
            outs.append(out)
    finally:
        for p in procs:
            if p.poll() is None:
                p.kill()
    if any("Multiprocess computations aren't implemented on the CPU "
           "backend" in out for out in outs):
        pytest.skip("CPU backend lacks multiprocess computations "
                    "on this jax version")
    for i, (p, out) in enumerate(zip(procs, outs)):
        assert p.returncode == 0, f"proc {i} failed:\n{out[-3000:]}"
        assert f"proc {i} sparse ok steps=" in out, out[-2000:]
