"""The per-layer metrics ISSUE 37 added: the stage spans inside
``engine.build``, ``hop.fold`` and ``comm.exchange`` (and a span that
was there with no reader, ``fold.fingerprint``), each read by one
``span_share`` data file. Each is in BENCHMARK.json's ``per_layer`` once
(later PRs append after them), sits in a layer PERF.md names, reduces
a hand-made record to the number its definition says, is left out where
the program writes no such span (the parent commit), and comes out a
number on a ``--trace 1`` rehearsal of one of its cells — one rehearsal
a cell, shared by that cell's cases. (``--trace 0`` reports the
end-to-end metrics and no other: ``test_benchmark_run.py``.)"""

import json
import os
import subprocess
import sys

import pytest

pytest.register_assert_rewrite("benchmark_rules")

import benchmark_rules as rules  # noqa: E402

from benchmark import layers, run  # noqa: E402

ROOT = run.ROOT
BENCH = rules.load_bench(ROOT)

RANGE = ["twitter_wpr.range_windows", "twitter_wpr_big.range_windows",
         "graph500_cdlp.range_communities", "graph500_lcc.range_clustering",
         "reddit_sgc.range_propagation"]
LIVE = ["twitter_wpr.live_tail"]
MESH = ["twitter_wpr_x4.range_windows"]

#: name -> (span, layer, cells, moves), in the order they were appended
STAGE_METRICS = {
    "live.index_ids_share": ("index.ids", "engines", LIVE,
                             "live_staleness_p50_s"),
    "live.index_pairs_share": ("index.pairs", "engines", LIVE,
                               "live_staleness_p50_s"),
    "live.index_tables_share": ("index.tables", "engines", LIVE,
                                "live_staleness_p50_s"),
    "live.fingerprint_share": ("fold.fingerprint", "host fold", LIVE,
                               "live_staleness_p50_s"),
    "live.fold_advance_share": ("fold.advance", "host fold", LIVE,
                                "live_staleness_p50_s"),
    "live.fold_payload_share": ("fold.payload", "host fold", LIVE,
                                "live_staleness_p50_s"),
    "mesh_range.fold_advance_share": ("fold.advance", "host fold", MESH,
                                      "mesh_views_per_s"),
    "mesh_range.fold_payload_share": ("fold.payload", "host fold", MESH,
                                      "mesh_views_per_s"),
    "mesh_range.table_put_share": ("comm.put", "mesh", MESH,
                                   "mesh_views_per_s"),
    "range.fold_seed_share": ("fold.seed", "host fold", RANGE,
                              "views_per_s"),
    "range.fold_advance_share": ("fold.advance", "host fold", RANGE,
                                 "views_per_s"),
    "range.fold_payload_share": ("fold.payload", "host fold", RANGE,
                                 "views_per_s"),
}
NAMES = list(STAGE_METRICS)
#: the cell each metric is rehearsed in: the first of its list
REHEARSED = sorted({cells[0] for _, _, cells, _ in STAGE_METRICS.values()})


def _spec(name):
    cell = STAGE_METRICS[name][2][0]
    (spec,) = [s for s in run.load_cell(cell)["per_layer"]
               if s["name"] == name]
    return spec


def test_the_stage_metrics_are_there_once_and_in_order():
    names = [m["name"] for m in BENCH["per_layer"]]
    # present, unique, in this relative order: entries may follow them
    assert [n for n in names if n in STAGE_METRICS] == NAMES
    assert len(names) == len(set(names))
    rules.every_rule(BENCH, ROOT)       # unedited, and it passes


@pytest.mark.parametrize("name", NAMES)
def test_stage_metric_is_one_span_share_file_in_its_layer(name):
    span, layer, cells, moves = STAGE_METRICS[name]
    (entry,) = [m for m in BENCH["per_layer"] if m["name"] == name]
    assert entry == {"name": name, "unit": "%", "better": "lower",
                     "source": "program_span", "layer": layer,
                     "moves": moves, "workloads": cells}
    spec = run.load_json(run.HERE, "layer_metrics", name + ".json")
    assert set(spec) == {"reducer", "span", "what"}
    assert (spec["reducer"], spec["span"]) == ("span_share", span)
    assert span in spec["what"]
    if name.startswith(("range.fold_", "mesh_range.fold_")):
        # worker seconds over the client's wall: the file says so
        assert "worker seconds" in spec["what"]
    with open(f"{ROOT}/PERF.md") as f:
        perf = f.read()
    assert f"`{name}`" in perf and f"`{span}`" in perf
    for cell in cells:          # every cell on the list finds and reads it
        assert name in {s["name"] for s in run.load_cell(cell)["per_layer"]}


def _span(name, dur_s, **args):
    return {"name": name, "dur": dur_s * 1e6, "ts": 0.0, "args": args}


@pytest.mark.parametrize("name", NAMES)
def test_stage_metric_reduces_a_hand_made_record(name):
    span = STAGE_METRICS[name][0]
    rec = {"work_wall_s": 16.0, "spans": [
        _span(span, 0.5), _span(span, 0.3), _span("job", 15.0),
        _span("hop.fold", 2.0), _span("engine.build", 1.0)]}
    assert layers.reduce_metric(_spec(name), rec) == pytest.approx(5.0)
    # the parent commit writes no such span: left out, and never raises
    old = {"work_wall_s": 16.0, "spans": [_span("job", 15.0),
                                          _span("hop.fold", 2.0)]}
    assert layers.reduce_metric(_spec(name), old) is None
    assert layers.reduce_metric(_spec(name), {}) is None


@pytest.fixture(scope="module")
def rehearsed():
    """One ``--trace 1`` rehearsal a cell, run when first asked for, from
    the checkout itself: ``run.py`` keeps a traced run's profile in a
    directory of the process's own (``.bench_trace/<pid>``), so another
    worker's traced rehearsals (``test_benchmark_run.py``) do not meet
    these."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    lines = {}

    def line(cell):
        if cell not in lines:
            p = subprocess.run(
                [sys.executable, os.path.join(run.HERE, "run.py"),
                 "--workload", cell, "--seed", str(2**31 + 37),
                 "--seconds", "10" if cell in MESH else "4",
                 "--trace", "1", "--rehearsal"],
                cwd=ROOT, env=env, capture_output=True, text=True,
                timeout=240)
            assert p.returncode == 0, p.stderr[-2000:]
            lines[cell] = json.loads(p.stdout.strip().splitlines()[-1])
        return lines[cell]
    return line


@pytest.mark.parametrize("name", NAMES)
def test_stage_metric_comes_out_a_number_on_a_traced_rehearsal(rehearsed,
                                                               name):
    out = rehearsed(STAGE_METRICS[name][2][0])
    assert out["failed"] == 0 and out["attempted"] >= 2
    got = out["metrics"][name]
    assert got["unit"] == "%" and isinstance(got["value"], float)
    assert 0.0 < got["value"] < 100.0


@pytest.mark.parametrize("cell", REHEARSED)
def test_stages_stay_inside_what_times_them_from_outside(rehearsed, cell):
    """The shares a parent span's stages add up to do not pass the share
    the parent is read by (job-thread spans only: a Range's fold units,
    one chip or mesh, run on workers, beside the wall)."""
    m = {k: v["value"] for k, v in rehearsed(cell)["metrics"].items()}
    if cell in LIVE:
        assert m["live.fold_advance_share"] + m["live.fold_payload_share"] \
            <= 100.0
        assert m["live.index_ids_share"] + m["live.index_pairs_share"] \
            + m["live.index_tables_share"] <= 100.0
    elif cell in MESH:
        assert m["mesh_range.table_put_share"] \
            <= m["mesh_range.comm_exchange_share"]
        # since PR 38 the mesh route's fold units are worker seconds
        # too: the one-chip arm's rule, each alone under the wall
        assert max(m["mesh_range.fold_advance_share"],
                   m["mesh_range.fold_payload_share"]) < 100.0
    else:
        # worker seconds beside the wall, so no sum is bounded by the
        # job thread's ``range.fold_share``; each alone is under the wall
        assert max(m["range.fold_seed_share"], m["range.fold_advance_share"],
                   m["range.fold_payload_share"]) < 100.0
