"""The harness end to end on the CPU: every cell's --rehearsal, no result
without a chip, and `correct` coming out false when the timed path is
broken underneath."""

import argparse
import json
import os
import subprocess
import sys

import pytest

from benchmark import run

BENCH = run.load_json(run.ROOT, "BENCHMARK.json")
CELLS = [w["name"] for w in BENCH["workloads"]]


def _cli(*argv, timeout=240):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    return subprocess.run(
        [sys.executable, os.path.join(run.HERE, "run.py"), *argv],
        cwd=run.ROOT, env=env, capture_output=True, text=True,
        timeout=timeout)


@pytest.mark.parametrize("trace", [0, 1])
@pytest.mark.parametrize("cell", CELLS)
def test_rehearsal_runs_every_cell_and_never_passes(cell, trace):
    chips = next(w["chips"] for w in BENCH["workloads"] if w["name"] == cell)
    # two requests must end inside the window with every worker of the
    # test run busy: the mesh's four virtual devices take the longest
    p = _cli("--workload", cell, "--seed", str(2**31 + 11),
             "--seconds", "10" if chips == 4 else "4",
             "--trace", str(trace), "--rehearsal")
    assert p.returncode == 0, p.stderr[-2000:]
    lines = p.stdout.strip().splitlines()
    out = json.loads(lines[-1])
    assert set(out) >= {"correct", "attempted", "failed", "metrics", "device"}
    assert out["correct"] is False and "rehearsal" in out
    assert out["device"]["platform"] == "cpu"
    assert out["device"]["count"] == chips
    assert out["attempted"] >= 2 and out["failed"] == 0
    kind = "per_layer" if trace else "end_to_end"
    known = {m["name"]: m["unit"] for m in BENCH[kind]
             if cell in m.get("workloads", [cell])}
    assert out["metrics"], "a line reports at least one metric"
    for name, m in out["metrics"].items():
        assert known[name] == m["unit"] and isinstance(m["value"], float)
    if not trace:
        assert set(out["metrics"]) == set(known)    # all end-to-end ones
    else:       # no device numbers from a CPU: never under a device name
        assert not any("device_idle" in n or "roofline" in n
                       for n in out["metrics"])
        assert "busy_s" not in out["device"]
        # the fold's stage spans are written in every Range cell listed
        for name in ("range.fold_seed_share", "range.fold_advance_share",
                     "range.fold_payload_share"):
            assert (name in known) == (name in out["metrics"]), name
        traced = next(json.loads(ln) for ln in lines
                      if '"trace_not_reduced"' in ln)
        assert traced["least_bytes"] > 0 < traced["traced_views"]
    phases = [json.loads(ln) for ln in lines[:-1]]
    assert all("cpu" in str(ph["device"]) for ph in phases[1:])
    work = next(ph for ph in phases if ph["phase"] == "work")
    # the cell's own configuration says how many supersteps a row takes
    alg = run.load_cell(cell)["config"]["algorithm"]
    assert work["supersteps"] == [alg["iterations"]] and work["failed"] == 0
    if "epochs_completed" in work:      # a subscription: where a run's
        med = work["span_median_seconds"]       # level comes from
        assert med["live.epoch"] >= med["engine.build"] > 0
    else:
        assert work["schedule_used_up"] is False
    summary = next(ph for ph in phases if ph["phase"] == "check_summary")
    assert summary["ok"] and summary["rows_compared"] >= 3
    assert not summary["route_failures"]


def test_without_a_chip_no_result_is_printed():
    p = _cli("--workload", CELLS[0], "--seed", "1", "--seconds", "2",
             "--trace", "0")
    assert p.returncode != 0
    assert '"correct"' not in p.stdout and "TPU" in p.stderr
    p = _cli("--workload", "no.such_cell", "--seed", "1", "--seconds", "2",
             "--trace", "0", "--rehearsal")
    assert p.returncode != 0 and '"correct"' not in p.stdout


def _args(cell, seed):
    return argparse.Namespace(workload=cell, seed=seed, seconds=2.0, trace=0,
                              rehearsal=False)


def _last(capsys):
    return json.loads(capsys.readouterr().out.strip().splitlines()[-1])


def test_a_broken_timed_path_comes_out_not_correct(capsys, monkeypatch):
    """The rest of a run with the look for a chip skipped: sound, it is
    correct; with an answer altered where it is produced, or a hop
    served at the wrong time, it is not."""
    from raphtory_tpu.algorithms import PageRank

    cell = "twitter_wpr.view_asof"
    assert run.run_cell(_args(cell, 31), require_chip=False, tiny=True) == 0
    sound = _last(capsys)
    assert sound["correct"] is True and sound["failed"] == 0

    reduce_ = PageRank.reduce

    def skewed(self, result, view, window=None):
        out = reduce_(self, result, view, window=window)
        vid, rank = out["top10"][0]
        out["top10"][0] = (vid, rank * 1.003)     # a bf16-sized error
        return out

    monkeypatch.setattr(PageRank, "reduce", skewed)
    assert run.run_cell(_args(cell, 31), require_chip=False, tiny=True) == 0
    broken = _last(capsys)
    assert broken["correct"] is False and broken["failed"] >= 1


def test_a_step_that_returns_its_state_unchanged_is_caught(capsys,
                                                           monkeypatch):
    from raphtory_tpu.algorithms import PageRank

    def stuck(self, state, agg, ctx):
        return state, state["rank"] < 0          # ranks never move

    from raphtory_tpu.engine import device_sweep

    monkeypatch.setattr(PageRank, "update", stuck)
    device_sweep._compiled_run.cache_clear()     # or a sound program is reused
    cell = "twitter_wpr.view_asof"
    assert run.run_cell(_args(cell, 32), require_chip=False, tiny=True) == 0
    assert _last(capsys)["correct"] is False
    device_sweep._compiled_run.cache_clear()     # nor the stuck one later
