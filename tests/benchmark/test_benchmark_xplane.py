"""The trace reduction on the small trace recorded on a TPU v5e in PR 24
(``benchmark/tools/record_trace.py``), and on hand-made intervals."""

import os

import pytest

from benchmark import run, xplane
from benchmark.algorithms import pagerank

TRACE = os.path.join(os.path.dirname(xplane.__file__), "testdata",
                     "recorded_v5e.xplane.pb")
SPANS = ("hop.fold", "hop.compute", "hop.ship")


def test_recorded_trace_reduces_to_known_numbers():
    r = xplane.reduce_trace(TRACE, SPANS)
    assert r["chips"] == 1
    # three runs of jit_run (four fusions each) and of jit_apply
    ops = r["op_seconds"]
    assert sorted(ops) == ["jit_apply/broadcast_add_fusion",
                           "jit_run/fusion.11", "jit_run/fusion.14",
                           "jit_run/fusion.17", "jit_run/fusion.8"]
    assert r["busy_s"] == pytest.approx(0.000474085, rel=1e-6)
    assert r["window_s"] == pytest.approx(0.110209231, rel=1e-6)
    assert sum(ops.values()) == pytest.approx(r["busy_s"], rel=1e-9)
    progs = r["program_seconds"]
    assert sorted(progs) == ["jit_apply", "jit_run"]
    assert progs["jit_run"] == pytest.approx(
        sum(s for n, s in ops.items() if n.startswith("jit_run/")), rel=1e-9)
    assert r["device_ops"][0][0] == "jit_run/fusion.17"
    assert r["device_ops"][0][1] == pytest.approx(0.000112073, rel=1e-6)
    gaps = dict(r["idle_gaps"])
    # the recorder slept 3 x 20 ms under hop.fold and 3 x 10 ms under
    # hop.ship; the device ran under hop.compute, so little of it idles
    assert gaps["hop.fold"] == pytest.approx(0.0618, abs=2e-3)
    assert gaps["hop.ship"] == pytest.approx(0.0337, abs=3e-3)
    assert gaps["hop.compute"] < 0.005
    assert sum(gaps.values()) + r["busy_s"] == pytest.approx(
        r["trace_span_s"], rel=1e-6)
    # a stated window (the host's clock around the trace) is taken as is
    assert xplane.reduce_trace(TRACE, SPANS, 0.5)["window_s"] == 0.5


def test_union_and_gaps_on_hand_made_intervals():
    assert xplane.union([(5, 7), (0, 2), (1, 3), (7, 8)]) == [[0, 3], [5, 8]]
    busy = [[2e9, 3e9], [6e9, 7e9]]
    host = [("job", 0.0, 10e9), ("hop.fold", 3e9, 5e9),
            ("other", 0.0, 10e9)]
    gaps = xplane.gaps_by_span(busy, 0.0, 10e9, host, {"job", "hop.fold"})
    # [0,2] job, [3,5] hop.fold (innermost), [5,6] job, [7,10] job
    assert gaps == {"job": pytest.approx(6.0), "hop.fold": pytest.approx(2.0)}
    assert xplane.gaps_by_span(busy, 0.0, 10e9, [], set()) == {
        "no_span": pytest.approx(8.0)}


def test_a_gap_is_named_by_the_innermost_span_the_harness_lists():
    """An engine build inside a job: its seconds are `engine.build`'s
    where the harness lists that span, and `job`'s where it does not."""
    busy = [[4e9, 6e9]]
    host = [("job", 0.0, 10e9), ("engine.build", 1e9, 3e9),
            ("comm.block_wait", 5e9, 9e9), ("job.emit", 9e9, 9.5e9)]
    for name in ("engine.build", "engine.layout", "comm.block_wait",
                 "job.emit", "job.publish", "fold.fingerprint"):
        assert name in run.HOST_SPANS
    gaps = xplane.gaps_by_span(busy, 0.0, 10e9, host, set(run.HOST_SPANS))
    # [0,1] job, [1,3] engine.build, [3,4] job, [6,9] comm.block_wait,
    # [9,9.5] job.emit, [9.5,10] job
    assert gaps == {"job": pytest.approx(2.5),
                    "engine.build": pytest.approx(2.0),
                    "comm.block_wait": pytest.approx(3.0),
                    "job.emit": pytest.approx(0.5)}
    before = xplane.gaps_by_span(busy, 0.0, 10e9, host, {"job"})
    assert before == {"job": pytest.approx(8.0)}


def test_names():
    assert xplane.op_label("%fusion.8 = f32[8]{0} fusion(%x), kind=kLoop") \
        == "fusion.8"
    assert xplane.program_label("jit_run(2384715398708818389)") == "jit_run"


def test_a_trace_without_a_device_plane_is_refused(tmp_path):
    with pytest.raises(FileNotFoundError):
        xplane.newest_trace(str(tmp_path))


def test_least_bytes_of_the_superstep():
    # 20 supersteps, one column: its alive pairs' (src, dst) once a step
    # and a rank read and written a vertex; no mask
    alg, n, m = {"iterations": 20}, 393_216, 7_995_392
    assert pagerank.least_bytes([(n, m)], alg) == 20 * (8 * m + 8 * n)
    # columns of one dispatch share the widest one's table under a mask
    # byte a pair and column, where that is less than a table each
    wide = [(n, m)] * 12
    assert pagerank.least_bytes(wide, alg) == 20 * (
        8 * m + 12 * m + 12 * 8 * n)
    thin = [(n, m), (n // 30, m // 30)]
    assert pagerank.least_bytes(thin, alg) == 20 * (
        8 * (n + n // 30) + 8 * (m + m // 30))
