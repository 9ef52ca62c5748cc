"""The per-layer metrics ISSUE 25 added (its table D,
``benchmark_rules.TABLE_D``): each is one data file on a reducer
`benchmark/layers.py` already had, in a layer PERF.md names, reported by
the cell the table gives (first in its list; a later PR's cell may
follow); and each reduces a hand-made record to the number its
definition says."""

import pytest

pytest.register_assert_rewrite("benchmark_rules")

import benchmark_rules as rules  # noqa: E402
from benchmark_rules import TABLE_D  # noqa: E402

from benchmark import layers, run  # noqa: E402

ROOT = run.ROOT
BENCH = rules.load_bench(ROOT)
CELLS = rules.cells_of(BENCH)


def _span(name, dur_s, **args):
    return {"name": name, "dur": dur_s * 1e6, "ts": 0.0, "args": args}


def _ledger(**phases):
    return {"phase_seconds": {**phases, "other": 0.5}}


#: one window, made by hand: two requests of 10 s and 6 s (or, for the
#: subscription, one ledger over 4 epochs whose spans sum to 16 s)
RECORD = {
    "work_wall_s": 16.0,
    "spans": [
        _span("engine.layout", 0.08, cached=True, partitions=16),
        _span("engine.layout", 0.24, cached=False, partitions=16),
        _span("comm.block_wait", 3.2, route="replicate", shards=4),
        _span("xla.backend_compile", 0.4, fun="jit(block)"),
        _span("xla.backend_compile", 0.4, fun="jit(block)"),
        _span("xla.backend_compile", 0.8, fun="jit(run)"),
        _span("xla.lower", 0.32, fun="block"),
        _span("job.publish", 0.016, status="done"),
        _span("job.publish", 0.016, status="done"),
        _span("job", 15.0),
    ],
    "ledgers": [
        {"wall_s": 10.0, "views": 3, "ledger": _ledger(
            build=2.5, fold=0.5, compute=4.0, device_wait=2.0, emit=0.1)},
        {"wall_s": 6.0, "views": 1, "ledger": _ledger(
            build=1.5, fold=0.3, compute=2.0, device_wait=1.6, emit=0.06)},
    ],
}
EXPECTED = {
    "range.build_share": 25.0,                # (2.5 + 1.5) / 16
    "range.emit_share": 1.0,                  # 0.16 / 16
    "range.layout_share": 2.0,                # 0.32 / 16
    "range.program_builds": 3.0,
    "mesh_range.build_share": 25.0,
    "mesh_range.compute_share": 60.0,         # (4 + 2 + 2 + 1.6) / 16
    "mesh_range.block_wait_share": 20.0,      # 3.2 / 16
    "mesh_range.program_builds": 3.0,
    "mesh_range.program_build_share": 10.0,   # 1.6 / 16
    "mesh_range.program_lower_share": 2.0,    # 0.32 / 16
    "live.build_s_per_epoch": 1.0,            # 4.0 s / 4 rows
    "live.jobs_other_share": 9.0,             # 100 - 14.56 / 16
    "live.program_builds": 3.0,
    "live.program_build_share": 10.0,
    "view.publish_share": 0.2,                # 0.032 / 16
    "view.program_builds": 3.0,
}


def test_table_d_is_there_once_and_in_its_order():
    rules.table_d_is_there_once_and_in_order(BENCH)


@pytest.mark.parametrize("name", list(TABLE_D))
def test_new_metric_resolves_to_a_file_a_reducer_and_a_layer(name):
    rules.table_d_metric_resolves(BENCH, ROOT, name)


@pytest.mark.parametrize("cell", CELLS)
def test_every_cell_loads_and_reports_its_share_of_table_d(cell):
    rules.cell_reports_its_share_of_table_d(BENCH, ROOT, cell)


@pytest.mark.parametrize("name", list(TABLE_D))
def test_new_metric_reduces_a_hand_made_record(name):
    (spec,) = [s for s in run.load_cell(TABLE_D[name][0])["per_layer"]
               if s["name"] == name]
    assert layers.reduce_metric(spec, RECORD) == pytest.approx(EXPECTED[name])


@pytest.mark.parametrize("name", list(TABLE_D))
def test_new_metric_is_left_out_where_the_program_records_nothing(name):
    """The parent commit has none of these spans or phases: its record
    reduces to nothing (or, for a count the harness did look for, 0) and
    never raises."""
    (spec,) = [s for s in run.load_cell(TABLE_D[name][0])["per_layer"]
               if s["name"] == name]
    old = {"work_wall_s": 16.0,
           "spans": [_span("job", 15.0)],
           "ledgers": [{"wall_s": 16.0, "views": 4, "ledger": {
               "phase_seconds": {"fold": 1.0, "compute": 12.0,
                                 "other": 3.0}}}]}
    got = layers.reduce_metric(spec, old)
    reducer = TABLE_D[name][1]
    if reducer == "span_count":
        assert got == 0.0
    elif reducer == "ledger_phase_share":
        # a phase the ledger lacks is 0 % of the wall; phases it has
        # count as before, and the complement is what they left
        had = {"fold": 1.0, "compute": 12.0}
        share = 100.0 * sum(v for k, v in had.items()
                            if k in spec.get("phases", had)) / 16.0
        assert got == pytest.approx(
            100.0 - share if spec.get("complement") else share)
    else:
        assert got is None
    assert layers.reduce_metric(spec, {}) is None
