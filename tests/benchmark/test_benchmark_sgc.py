"""``reddit_sgc``: the configuration keeps ``twitter_wpr.json``'s graph
shapes but ``scale`` and Reddit's widths uncut, its traffic's request list
does not depend on the seed, the comparison catches each wrong
computation it is there for, and the least bytes are under what any
kernel moves."""

import numpy as np
import pytest

from benchmark import client, gen, reference, run
from benchmark.algorithms import sgc

CFG = run.load_json(run.HERE, "configs", "reddit_sgc.json")
TRAFFIC = run.load_json(run.HERE, "traffic", "range_propagation.json")
LIMITS, ALG = CFG["correct"]["limits"], CFG["algorithm"]
EXACT = ("vertices_err", "edges_err", "top10_mismatched")
FLOATS = ("probe_rel_err", "col_sum_rel_err", "frob_rel_err")
CELL = "reddit_sgc.range_propagation"


def test_the_configuration_keeps_the_graph_shapes_but_scale():
    tw = run.load_json(run.HERE, "configs", "twitter_wpr.json")
    ours, theirs = dict(CFG["graph"]), dict(tw["graph"])
    for g in (ours, theirs):
        g.pop("note")
    assert ours.pop("scale") in (16, 17, 18) and theirs.pop("scale") == 17
    assert ours == theirs                 # R-MAT, edge factor, seed, span
    assert (CFG["windows"], CFG["hop_s"]) == (tw["windows"], tw["hop_s"])
    assert {k: v for k, v in CFG["guarantees"].items() if k != "precision"} \
        == {k: v for k, v in tw["guarantees"].items() if k != "precision"}
    assert CFG["guarantees"]["precision"] == "float32 features and sums"
    # the published widths are not cut
    assert ALG == {**ALG, "module": "sgc", "analyserName": "SGC",
                   "params": {"rounds": 2, "dim": 602, "feature_seed": 2017},
                   "iterations": 2}
    assert CFG["architecture"] is None
    assert set(LIMITS) == set(sgc.COMPARED) == set(EXACT + FLOATS)
    assert all(LIMITS[k] == 0 for k in EXACT)
    assert all(0 < LIMITS[k] <= 1e-4 for k in FLOATS)
    # the density is a cut too: twitter_wpr.json's 32 events an id, not
    # Reddit's 492 directed edges a node
    assert sorted(CFG["reduced"]) == ["edge_factor", "events", "ids"]
    assert "492" in CFG["reduced"]["edge_factor"]
    assert {"symmetrisation", "graph", "features", "topology",
            "reddit_counts"} <= set(CFG["assumed"])
    assert len(CFG["source"]) <= 200
    for key in ("why", "control", "readings"):       # each limit's reason
        assert len(CFG["correct"][key]) > 80


@pytest.mark.parametrize("key,under", [
    ("probe_rel_err", 10), ("col_sum_rel_err", 10), ("frob_rel_err", 2.5)])
def test_a_float_limit_lies_between_its_two_readings(key, under):
    """At least 3 x the sound runs' largest reading on the chip and
    under the bfloat16 control's smallest — by ISSUE 44's tenth where
    the control moves the number that far, by 2.5 x for the norm, which
    it moves by 5.6e-7 only and a float32 scale by 6e-8 — so a downgrade
    of the stated precision passes by none of the three."""
    sound = CFG["correct"]["sound_largest"][key]
    control = CFG["correct"]["control_smallest"][key]
    assert 0 < 3 * sound <= LIMITS[key] <= control / under
    for reading in (sound, control):     # the prose gives them too
        assert f"{reading:.2e}".replace("e-0", "e-") in \
            CFG["correct"]["readings"]


def test_the_cell_is_on_the_lists_the_lcc_cell_is_on_and_one_more():
    bench = run.load_json(run.ROOT, "BENCHMARK.json")
    lcc = "graph500_lcc.range_clustering"
    for m in bench["end_to_end"] + bench["per_layer"]:
        on = m.get("workloads", [])
        if lcc in on and not m["name"].endswith(
                ("triangle_rows_per_view", "index_triangles_share")):
            assert CELL in on, m["name"]    # the fold's stage shares too
    (mine,) = [m for m in bench["per_layer"]
               if m["name"] == "range.feature_rows_per_view"]
    assert mine["workloads"] == [CELL] and mine["moves"] == "views_per_s"
    (cell,) = [w for w in bench["workloads"] if w["name"] == CELL]
    assert cell["chips"] == 1 and len(cell["why"]) <= 200
    assert bench["workloads"][-1] == cell and bench["per_layer"][-1] == mine
    spec = run.load_json(run.HERE, "layer_metrics",
                         "range.feature_rows_per_view.json")
    assert (spec["reducer"], spec["path"], spec["per"]) == \
        ("ledger_sum_per", "device.feature_rows", "views")


def test_request_list_does_not_depend_on_the_seed():
    bodies = [client.request_body(CFG, TRAFFIC, k) for k in range(-1, 8)]
    times = [client.hop_times(CFG, TRAFFIC, k) for k in range(-1, 8)]
    flat = [t for ts in times for t in ts]
    assert flat == sorted(flat) and set(np.diff(flat)) == {CFG["hop_s"]}
    assert times[1][0] == int(0.70 * CFG["graph"]["t_span"])    # request 0
    assert all(len(ts) == 2 for ts in times)
    assert all(b["analyserName"] == "SGC" and b["params"] == ALG["params"]
               and b["windowSet"] == CFG["windows"] for b in bodies)
    assert client.rows_expected(CFG, TRAFFIC) == 6
    assert TRAFFIC["routes"]["one_chip"]["kernels"] == ["hopbatch.delta.sgc"]
    same = run.load_json(run.HERE, "traffic", "range_communities.json")
    for key in ("loop", "clients", "endpoint", "window_type",
                "hops_per_request", "start_frac", "warmup_requests",
                "sample_rows", "trace_requests"):
        assert TRAFFIC[key] == same[key], key
    # the schedule holds 108 requests; the cell completes a tenth of them
    assert client.hop_times(CFG, TRAFFIC, 107)[-1] <= CFG["graph"]["t_span"]
    with pytest.raises(ValueError):
        client.hop_times(CFG, TRAFFIC, 108)


def _view(seed=3, window=200000):
    small = run.merge(CFG, run.load_json(run.HERE, "rehearsal.json")["config"])
    t, s, d = gen.bulk_log(small, seed)
    ref = reference.RefEvents(t, np.full(len(t), gen.EADD, np.uint8), s, d,
                              int(small["graph"]["id_space"]))
    return ref.fold(int(0.7 * small["graph"]["t_span"]), window)


def _served(vm, src, dst, steps=2, **how):
    return sgc.served_like(*sgc.propagate(vm, src, dst, ALG, **how),
                           len(src), steps)


def test_a_sound_row_passes_and_the_bfloat16_control_does_not():
    vm, src, dst = _view()
    want = sgc.reference(vm, src, dst, ALG)
    assert want["vertices"] > 500 and len(want["top10"]) == 10
    assert want["dim"] == 602 and len(want["col_sum"]) == 602
    assert np.asarray(want["probe"]).shape == (10, 602)
    pairs = set(zip(src.tolist(), dst.tolist()))
    assert any((b, a) in pairs for a, b in pairs if a != b)   # both ways
    assert sgc.compare(_served(vm, src, dst), want, LIMITS, ALG)["ok"]
    out = sgc.compare(sgc.stated(vm, src, dst, ALG), want, LIMITS, ALG)
    assert out["ok"] and 0 < out["probe_rel_err"] < LIMITS[
        "probe_rel_err"] / 10                   # float32 storage is sound
    out = sgc.compare(sgc.control(vm, src, dst, ALG), want, LIMITS, ALG)
    assert not out["ok"] and all(out[k] == 0 for k in EXACT)
    assert out["probe_rel_err"] > 10 * LIMITS["probe_rel_err"]
    assert out["col_sum_rel_err"] > 10 * LIMITS["col_sum_rel_err"]
    # three rounds said, or one: another computation
    assert not sgc.compare(_served(vm, src, dst, steps=3), want, LIMITS,
                           ALG)["ok"]


def _probe_entry_off(vm, src, dst):
    row = _served(vm, src, dst)
    j = int(np.argmax(np.abs(row["result"]["probe"][3])))
    row["result"]["probe"][3][j] *= 1 + 1e-3
    return row


@pytest.mark.parametrize("fault,caught_by", [
    (lambda *v: sgc.control(*v, ALG), "probe_rel_err"),
    (lambda *v: _served(*v, rounds=1), "frob_rel_err"),
    (lambda *v: _served(*v, transpose=False), "top10_mismatched"),
    (lambda *v: _served(*v, self_term=False), "top10_mismatched"),
    (lambda *v: _served(*v, coalesce=True), "probe_rel_err"),
    (_probe_entry_off, "probe_rel_err")],
    ids=["bfloat16", "one_round", "no_transpose", "no_self_term",
         "both_ways_coalesced", "one_probe_entry_1e-3"])
def test_comparison_refuses_each_wrong_computation(fault, caught_by):
    vm, src, dst = _view()
    want = sgc.reference(vm, src, dst, ALG)
    out = sgc.compare(fault(vm, src, dst), want, LIMITS, ALG)
    assert not out["ok"] and out[caught_by] > LIMITS[caught_by]
    assert out["vertices_err"] == out["edges_err"] == 0   # the fold's


def test_rounding_of_a_sum_is_not_caught():
    vm, src, dst = _view()
    want = sgc.reference(vm, src, dst, ALG)
    row = sgc.stated(vm, src, dst, ALG)
    row["result"]["frob"] *= 1 + 3e-8
    row["result"]["col_sum"] = [x * (1 + 3e-8)
                                for x in row["result"]["col_sum"]]
    assert sgc.compare(row, want, LIMITS, ALG)["ok"]
    short = sgc.stated(vm, src, dst, ALG)
    short["result"]["probe"] = short["result"]["probe"][:9]
    assert not sgc.compare(short, want, LIMITS, ALG)["ok"]


def test_least_bytes_is_under_what_any_kernel_moves():
    cols = [(1000, 30_000), (400, 8_000), (90, 900)]
    F, K = 602, 2
    # H read and written once a round for the alive vertices, the widest
    # column's pairs once, a mask byte a pair and column, and the probe,
    # the sums and the norm written a view
    assert sgc.least_bytes(cols, ALG) == (
        K * 2 * 4 * F * (1000 + 400 + 90) + 8 * 30_000 + 3 * 30_000
        + 3 * 4 * (11 * F + 1))
    # at the cell's own shapes: one gathered row a pair and direction
    # alone is more
    vm, src, dst = _view(window=2600000)
    n, m = int(vm.sum()), len(src)
    assert sgc.least_bytes([(n, m)] * 6, ALG) < 6 * K * 2 * m * 4 * F
    assert sgc.least_bytes([(n, m)], ALG) > K * 2 * 4 * F * n
