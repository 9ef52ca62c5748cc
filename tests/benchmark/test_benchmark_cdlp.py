"""``graph500_cdlp``: the configuration keeps ``twitter_wpr.json``'s graph
shapes but ``scale``, its traffic's request list does not depend on the
seed, and the exact comparison catches each fault it is there for."""

import numpy as np

from benchmark import client, gen, reference, run
from benchmark.algorithms import cdlp

CFG = run.load_json(run.HERE, "configs", "graph500_cdlp.json")
TRAFFIC = run.load_json(run.HERE, "traffic", "range_communities.json")
LIMITS, ALG = CFG["correct"]["limits"], CFG["algorithm"]


def test_the_configuration_keeps_the_graph_shapes_but_scale():
    tw = run.load_json(run.HERE, "configs", "twitter_wpr.json")
    ours, theirs = dict(CFG["graph"]), dict(tw["graph"])
    for g in (ours, theirs):
        g.pop("note")
    assert ours.pop("scale") >= 15 and theirs.pop("scale") == 17
    assert ours == theirs                 # R-MAT, edge factor, seed, span
    assert (CFG["windows"], CFG["hop_s"]) == (tw["windows"], tw["hop_s"])
    assert {k: v for k, v in CFG["guarantees"].items() if k != "precision"} \
        == {k: v for k, v in tw["guarantees"].items() if k != "precision"}
    assert CFG["guarantees"]["precision"] == "int32 labels, exact"
    assert ALG == {**ALG, "module": "cdlp", "analyserName": "CDLP",
                   "params": {"max_steps": 10}, "iterations": 10}
    # exact: every limit is 0, and every number compared has one
    assert LIMITS == dict.fromkeys(cdlp.COMPARED, 0)
    assert sorted(CFG["reduced"]) == ["events", "ids"]
    assert {"self_loops", "repeated_pairs"} <= set(CFG["assumed"])
    assert len(CFG["source"]) <= 200


def test_request_list_does_not_depend_on_the_seed():
    bodies = [client.request_body(CFG, TRAFFIC, k) for k in range(-1, 8)]
    times = [client.hop_times(CFG, TRAFFIC, k) for k in range(-1, 8)]
    flat = [t for ts in times for t in ts]
    assert flat == sorted(flat) and set(np.diff(flat)) == {CFG["hop_s"]}
    assert times[1][0] == int(0.70 * CFG["graph"]["t_span"])    # request 0
    assert all(len(ts) == 2 for ts in times)
    assert all(b["analyserName"] == "CDLP"
               and b["params"] == {"max_steps": 10}
               and b["windowSet"] == CFG["windows"] for b in bodies)
    assert client.rows_expected(CFG, TRAFFIC) == 6
    assert TRAFFIC["routes"]["one_chip"]["kernels"] == ["hopbatch.delta.cdlp"]


def _view(seed=3):
    small = run.merge(CFG, run.load_json(run.HERE, "rehearsal.json")["config"])
    t, s, d = gen.bulk_log(small, seed)
    ref = reference.RefEvents(t, np.full(len(t), gen.EADD, np.uint8), s, d,
                              int(small["graph"]["id_space"]))
    # a sparse window: many communities of several sizes (under the
    # week window this tiny graph is one community)
    return ref.fold(int(0.7 * small["graph"]["t_span"]), 20000)


def test_comparison_catches_each_fault_it_is_there_for():
    vm, src, dst = _view()
    lab = cdlp.cdlp(vm, src, dst, 10)
    want = cdlp.reference(vm, src, dst, ALG)
    assert len(want["top10"]) == 10 and want["communities"] > 10

    def check(labels, steps=10):
        return cdlp.compare(cdlp.served_like(labels, vm, steps), want,
                            LIMITS, ALG)

    assert check(lab)["ok"]
    # one vertex of the largest community carries a neighbour's label
    off = lab.copy()
    big, other = want["top10"][0][0], want["top10"][1][0]
    off[np.flatnonzero(vm & (lab == big))[-1]] = other
    out = check(off)
    assert not out["ok"] and out["checksum_mismatch"] == 1
    assert out["top10_mismatched"] == 2 and out["biggest_err"] == 1
    # the same sizes, one label off by one vertex: only the checksum sees
    # two members of different communities swapping labels
    swap = lab.copy()
    a = np.flatnonzero(vm & (lab == big))[-1]
    b = np.flatnonzero(vm & (lab == other))[-1]
    swap[a], swap[b] = lab[b], lab[a]
    out = check(swap)
    assert not out["ok"] and out["top10_mismatched"] == 0 \
        and out["checksum_mismatch"] == 1
    # a size off by one: a served row that miscounts one community
    row = cdlp.served_like(lab, vm, 10)
    row["result"]["top10"][3][1] += 1
    out = cdlp.compare(row, want, LIMITS, ALG)
    assert not out["ok"] and out["top10_mismatched"] == 1
    # nine rounds: the answer of nine, and ten's answer said to be nine's
    assert not check(cdlp.cdlp(vm, src, dst, 9), steps=9)["ok"]
    assert not check(lab, steps=9)["ok"]
    assert not check(cdlp.cdlp(vm, src, dst, 9))["ok"]
    # ties broken to the larger label
    assert not check(cdlp.cdlp(vm, src, dst, 10, tie="largest"))["ok"]
    # in-neighbours only: the control
    assert not cdlp.compare(cdlp.control(vm, src, dst, ALG), want, LIMITS,
                            ALG)["ok"]
    assert cdlp.compare(cdlp.stated(vm, src, dst, ALG), want, LIMITS,
                        ALG)["ok"]


def test_least_bytes_is_a_least_count():
    cols = [(1000, 30_000), (400, 8_000), (90, 900)]
    # per round: a label read and written per alive vertex and column,
    # and the cheaper of per-column pairs or one table plus mask bytes
    labels = 8 * (1000 + 400 + 90)
    edges = min(8 * (30_000 + 8_000 + 900), 8 * 30_000 + 3 * 30_000)
    assert cdlp.least_bytes(cols, ALG) == 10 * (labels + edges)
    assert cdlp.least_bytes(cols[:1], ALG) == 10 * (8 * 1000 + 8 * 30_000)
