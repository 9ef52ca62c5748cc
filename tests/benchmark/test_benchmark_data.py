"""The data a seed makes, the reference against the engine on
tombstone-heavy logs, and the lower-precision control."""

import numpy as np
import pytest

from benchmark import client, gen, reference, run
from benchmark.algorithms import pagerank


CFG = run.merge(run.load_json(run.HERE, "configs", "twitter_wpr.json"),
                run.load_json(run.HERE, "rehearsal.json")["config"])
LIMITS, ALG = CFG["correct"]["limits"], CFG["algorithm"]


def test_same_seed_same_data_other_seed_same_shapes():
    a, b = gen.bulk_log(CFG, 2**31 + 5), gen.bulk_log(CFG, 2**31 + 5)
    c = gen.bulk_log(CFG, 12)
    assert all(np.array_equal(x, y) for x, y in zip(a, b))
    assert not np.array_equal(a[0], c[0]) and not np.array_equal(a[1], c[1])
    n_ids = int(CFG["graph"]["id_space"])

    def shape(log):
        t, s, d = log
        return (len(t), len(np.unique(np.concatenate([s, d]))),
                len(np.unique(s * n_ids + d)))

    assert shape(a) == shape(c)          # events, vertex ids, pairs
    assert np.all(np.diff(a[0]) >= 0) and a[0].max() < CFG["graph"]["t_span"]
    ta, tc = gen.tail_events(CFG, 3, 5000), gen.tail_events(CFG, 4, 5000)
    assert np.array_equal(ta[0], tc[0]) and not np.array_equal(ta[2], tc[2])
    assert np.array_equal(gen.tail_events(CFG, 3, 5000)[1], ta[1])
    mix = np.bincount(ta[1], minlength=4) / 5000
    assert np.allclose(mix, CFG["tail"]["mix"], atol=0.03)
    assert np.all(ta[3][(ta[1] == gen.VADD) | (ta[1] == gen.VDEL)] == -1)


@pytest.mark.parametrize("traffic", ["range_windows", "view_asof"])
def test_request_list_does_not_depend_on_the_seed(traffic):
    tr = run.load_json(run.HERE, "traffic", traffic + ".json")
    cfg = run.load_json(run.HERE, "configs", "twitter_wpr.json")
    bodies = [client.request_body(cfg, tr, k) for k in range(-2, 6)]
    times = [client.hop_times(cfg, tr, k) for k in range(-2, 6)]
    flat = [t for ts in times for t in ts]
    assert flat == sorted(flat) and len(set(np.diff(flat))) == 1
    assert times[2][0] == int(0.70 * 2_600_000)       # request 0
    assert all(b["params"] == {"tol": 0, "max_steps": 20} for b in bodies)
    with pytest.raises(ValueError):
        client.hop_times(cfg, tr, 10_000)


def _tombstone_log(seed, n_events=900, n_ids=16, t_span=80):
    """Heavy id reuse, duplicate timestamps, vertex and edge deletes,
    revivals: what the live tail sends, denser."""
    rng = np.random.default_rng(seed)
    k = rng.choice(4, n_events, p=[0.2, 0.1, 0.5, 0.2]).astype(np.uint8)
    t = np.sort(rng.integers(0, t_span, n_events)).astype(np.int64)
    s = rng.integers(0, n_ids, n_events).astype(np.int64)
    d = rng.integers(0, n_ids, n_events).astype(np.int64)
    d[(k == gen.VADD) | (k == gen.VDEL)] = -1
    return t, k, s, d, n_ids


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_reference_agrees_with_the_engine_on_tombstone_heavy_logs(seed):
    from raphtory_tpu.algorithms import PageRank
    from raphtory_tpu.core import events as ev
    from raphtory_tpu.core.events import EventLog
    from raphtory_tpu.core.snapshot import build_view
    from raphtory_tpu.engine import bsp

    t, k, s, d, n_ids = _tombstone_log(seed)
    code = np.array([ev.VERTEX_ADD, ev.VERTEX_DELETE, ev.EDGE_ADD,
                     ev.EDGE_DELETE], np.uint8)
    log = EventLog()
    log.append_batch(t, code[k], s, d)
    ref = reference.RefEvents(t, k, s, np.maximum(d, 0), n_ids)
    prog = PageRank(tol=0, max_steps=20)
    dead_edges = 0
    for T in (20, 45, 79):
        view = build_view(log, T)
        for w in (None, 25, 5):
            vm, src, dst = ref.fold(T, w)
            if w is None:
                v_mask, e_mask = view.v_mask, view.e_mask
            else:
                (v_mask,), (e_mask,) = view.window_masks([w])
            assert sorted(view.vids[v_mask]) == sorted(np.flatnonzero(vm))
            got = sorted(zip(view.vids[view.e_src[e_mask]],
                             view.vids[view.e_dst[e_mask]]))
            assert got == sorted(zip(src, dst))
            dead_edges += int((~e_mask[:view.m_active]).sum()) \
                if hasattr(view, "m_active") else 0
            res, steps = bsp.run(prog, view, window=w)
            assert int(steps) == 20
            served = {"steps": int(steps), "result": prog.reduce(
                np.asarray(res), view, window=w)}
            cmp_ = pagerank.compare(served, pagerank.reference(
                vm, src, dst, ALG), LIMITS, ALG)
            assert cmp_["ok"], (T, w, cmp_)


@pytest.mark.parametrize("seed", [5, 6, 7])
def test_lower_precision_pagerank_fails_the_comparison(seed):
    """The control: the reference with its rank vector kept in bfloat16,
    put in the program's place, must come out not correct — and float32
    storage, the precision the configuration states, must pass."""
    t, s, d = gen.bulk_log(CFG, seed)
    n_ids = int(CFG["graph"]["id_space"])
    ref = reference.RefEvents(t, np.full(len(t), gen.EADD, np.uint8), s, d,
                              n_ids)
    vm, src, dst = ref.fold(int(0.7 * CFG["graph"]["t_span"]), 604800)
    want = pagerank.reference(vm, src, dst, ALG)
    bad = pagerank.compare(pagerank.control(vm, src, dst, ALG), want,
                           LIMITS, ALG)
    assert not bad["ok"] and bad["rank_rel_err"] > LIMITS["rank_rel_err"]
    assert pagerank.compare(pagerank.stated(vm, src, dst, ALG), want,
                            LIMITS, ALG)["ok"]


def test_comparison_catches_each_fault_it_is_there_for():
    want = {"sum": 1.0, "positive": 12,
            "lead": [(i, 0.2 - 0.01 * i) for i in range(12)]}
    top = [(i, 0.2 - 0.01 * i) for i in range(10)]

    def ok(steps=20, **result):
        row = {"steps": steps, "result": {"sum": 1.0, "top10": top, **result}}
        return pagerank.compare(row, want, LIMITS, ALG)

    assert ok()["ok"]
    assert ok(top10=top[:9])["rows_missing"] == 1
    assert not ok(sum=0.9)["ok"]                 # part of the graph left out
    assert ok(top10=top[:9] + [(11, 0.09)])["top10_misplaced"] >= 1
    assert not ok(top10=[(0, 0.2 * 1.002)] + top[1:])["ok"]
    assert not ok(steps=19)["ok"]                # halted early
