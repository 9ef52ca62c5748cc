"""``graph500_lcc``: the configuration keeps ``graph500_cdlp.json``'s graph
shapes but ``scale``, its traffic's request list does not depend on the
seed, the comparison catches each fault it is there for, and the least
bytes are under any table the kernel reads."""

import numpy as np
import pytest

from benchmark import client, gen, reference, run
from benchmark.algorithms import lcc

CFG = run.load_json(run.HERE, "configs", "graph500_lcc.json")
TRAFFIC = run.load_json(run.HERE, "traffic", "range_clustering.json")
LIMITS, ALG = CFG["correct"]["limits"], CFG["algorithm"]
EXACT = [k for k in lcc.COMPARED if not k.endswith("rel_err")]


def test_the_configuration_keeps_the_graph_shapes_but_scale():
    cd = run.load_json(run.HERE, "configs", "graph500_cdlp.json")
    ours, theirs = dict(CFG["graph"]), dict(cd["graph"])
    for g in (ours, theirs):
        g.pop("note")
    assert ours.pop("scale") in (15, 16, 17) and theirs.pop("scale") == 17
    assert ours == theirs                 # R-MAT, edge factor, seed, span
    assert (CFG["windows"], CFG["hop_s"]) == (cd["windows"], cd["hop_s"])
    assert {k: v for k, v in CFG["guarantees"].items() if k != "precision"} \
        == {k: v for k, v in cd["guarantees"].items() if k != "precision"}
    assert CFG["guarantees"]["precision"] \
        == "int32 counts, exact; float32 coefficients"
    assert ALG == {**ALG, "module": "lcc", "analyserName": "LCC",
                   "params": {}, "iterations": 1}
    # the integers are exact; the two floats have the one loose limit
    assert set(LIMITS) == set(lcc.COMPARED)
    assert all(LIMITS[k] == 0 for k in EXACT)
    assert LIMITS["lcc_mean_rel_err"] == LIMITS["lcc_max_rel_err"] == 1e-6
    assert sorted(CFG["reduced"]) == ["events", "ids"]
    assert {"formula", "self_loops", "repeated_pairs", "windows"} \
        <= set(CFG["assumed"])
    assert len(CFG["source"]) <= 200
    for key in ("why", "control", "readings"):       # each limit's reason
        assert len(CFG["correct"][key]) > 80


def test_request_list_does_not_depend_on_the_seed():
    bodies = [client.request_body(CFG, TRAFFIC, k) for k in range(-1, 8)]
    times = [client.hop_times(CFG, TRAFFIC, k) for k in range(-1, 8)]
    flat = [t for ts in times for t in ts]
    assert flat == sorted(flat) and set(np.diff(flat)) == {CFG["hop_s"]}
    assert times[1][0] == int(0.70 * CFG["graph"]["t_span"])    # request 0
    assert all(len(ts) == 2 for ts in times)
    assert all(b["analyserName"] == "LCC" and b["params"] == {}
               and b["windowSet"] == CFG["windows"] for b in bodies)
    assert client.rows_expected(CFG, TRAFFIC) == 6
    assert TRAFFIC["routes"]["one_chip"]["kernels"] == ["hopbatch.delta.lcc"]
    # the schedule outlasts a window at the fastest request seen
    last = client.hop_times(CFG, TRAFFIC, 107)
    assert last[-1] <= CFG["graph"]["t_span"]


def _view(seed=3, window=200000):
    small = run.merge(CFG, run.load_json(run.HERE, "rehearsal.json")["config"])
    t, s, d = gen.bulk_log(small, seed)
    ref = reference.RefEvents(t, np.full(len(t), gen.EADD, np.uint8), s, d,
                              int(small["graph"]["id_space"]))
    return ref.fold(int(0.7 * small["graph"]["t_span"]), window)


def _check(deg, tri, vm, want, steps=1):
    return lcc.compare(lcc.served_like(deg, tri, vm, steps), want, LIMITS,
                       ALG)


def test_a_sound_row_passes_and_the_control_does_not():
    vm, src, dst = _view()
    want = lcc.reference(vm, src, dst, ALG)
    assert want["edges_among_neighbours"] > 1000 and len(want["top10"]) == 10
    assert _check(*lcc.counts(vm, src, dst), vm, want)["ok"]
    assert lcc.compare(lcc.stated(vm, src, dst, ALG), want, LIMITS,
                       ALG)["ok"]
    out = lcc.compare(lcc.control(vm, src, dst, ALG), want, LIMITS, ALG)
    assert not out["ok"] and out["tri_checksum_mismatch"] == 1
    assert out["edges_among_neighbours_err"] > 0
    assert out["deg_checksum_mismatch"] == 0     # the neighbourhoods are right
    # two passes said, or none: another computation
    assert not _check(*lcc.counts(vm, src, dst), vm, want, steps=2)["ok"]


def _tri_off_by_one(vm, src, dst):
    deg, tri = lcc.counts(vm, src, dst)
    tri = tri.copy()
    tri[np.flatnonzero(vm & (tri > 0))[-1]] += 1   # not a top-10 vertex's
    return deg, tri


def _no_reverse_or(vm, src, dst):
    """``N(v)`` taken as the in-neighbours alone: the reverse pair's OR
    dropped, so a neighbour joined only outwards is none."""
    n = len(vm)
    deg, tri = lcc.counts(vm, src, dst)
    keep = src != dst
    seen = np.unique(src[keep].astype(np.int64) * n + dst[keep])
    return np.bincount(seen % n, minlength=n), tri


def _self_loop_counted(vm, src, dst):
    """A self-loop taken as a neighbour: ``deg`` one more where v -> v."""
    deg, tri = lcc.counts(vm, src, dst)
    loops = np.unique(src[src == dst])
    assert len(loops)
    deg = deg.copy()
    deg[loops] += 1
    return deg, tri


def _closing_edge_once(vm, src, dst):
    return lcc.counts(vm, src, dst, "undirected")


@pytest.mark.parametrize("fault,caught_by", [
    (_tri_off_by_one, "tri_checksum_mismatch"),
    (_no_reverse_or, "deg_checksum_mismatch"),
    (_self_loop_counted, "deg_checksum_mismatch"),
    (_closing_edge_once, "tri_checksum_mismatch")])
def test_comparison_refuses_each_altered_answer(fault, caught_by):
    vm, src, dst = _view()
    want = lcc.reference(vm, src, dst, ALG)
    out = _check(*fault(vm, src, dst), vm, want)
    assert not out["ok"] and out[caught_by] == 1


def test_a_float_off_by_more_than_rounding_is_caught_and_rounding_is_not():
    vm, src, dst = _view()
    want = lcc.reference(vm, src, dst, ALG)
    row = lcc.stated(vm, src, dst, ALG)
    row["result"]["lcc_mean"] *= 1 + 3e-8         # float32 coefficients
    assert lcc.compare(row, want, LIMITS, ALG)["ok"]
    row["result"]["lcc_mean"] *= 1 + 3e-6
    out = lcc.compare(row, want, LIMITS, ALG)
    assert not out["ok"] and all(out[k] == 0 for k in EXACT)


def test_least_bytes_is_under_the_bytes_of_any_table_the_kernel_reads():
    cols = [(1000, 30_000), (400, 8_000), (90, 900)]
    # the widest column's pairs once, a mask byte a pair and column, and
    # two int32 written per alive vertex and column
    assert lcc.least_bytes(cols, ALG) \
        == 8 * 30_000 + 3 * 30_000 + 8 * (1000 + 400 + 90)
    assert lcc.least_bytes(cols[:1], ALG) == 9 * 30_000 + 8 * 1000
    # at the cell's own shapes: under the pair table's two int32 a padded
    # row plus the masks, and far under the triangle rows (12 B each)
    from raphtory_tpu.engine.device_sweep import LogIndex
    from raphtory_tpu.core.events import EventLog
    from raphtory_tpu.core import events as ev
    from raphtory_tpu.ops import triangles

    vm, src, dst = _view(window=2600000)
    log = EventLog()
    log.append_batch(np.zeros(len(src), np.int64),
                     np.full(len(src), ev.EDGE_ADD, np.uint8), src, dst)
    t = LogIndex(log.freeze()).tables
    tt = triangles.build_table(t.e_src, t.e_dst, t.m, t.n, t.n_pad, t.m_pad)
    least = lcc.least_bytes([(int(vm.sum()), len(src))] * 6, ALG)
    assert least <= 8 * t.m_pad + 6 * t.m_pad + 8 * 6 * t.n_pad
    assert least < tt.rows.nbytes and tt.triangles > 10_000
