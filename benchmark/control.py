#!/usr/bin/env python3
"""The control of ``correct``: the reference, put in the program's
place and computed in the precision below the one the configuration
states (the algorithm module's ``control``: for PageRank the rank vector
kept in bfloat16 instead of float32), at the cell's own size, on the views the cell's traffic asks for. It has to
come out NOT correct; float32 storage, the stated precision, has to
pass. Needs no chip and nothing of the program:

    python3 benchmark/control.py --workload twitter_wpr.view_asof --seed 7

Prints each number compared beside its limit; exits 0 when the control
failed the comparison on every row (as it must), 1 when a row passed.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np  # noqa: E402

from benchmark import algorithms, client, gen, reference, run  # noqa: E402


def views_of(cfg, traffic, seed: int, requests: int, rows: int):
    """(T, window) of ``rows`` views out of the first ``requests``
    requests of the cell's schedule, drawn from the seed."""
    if traffic["loop"] == "subscription":       # epochs along the tail
        rate = traffic["tail_rate_per_s"]
        return [(cfg["graph"]["t_span"] + 1 + int(rate * s), None)
                for s in (15, 40, 65)][:rows]
    wins = cfg["windows"] if traffic["window_type"] == "batched" \
        else [cfg["windows"][0]]
    every = [(t, w) for k in range(requests)
             for t in client.hop_times(cfg, traffic, k) for w in wins]
    rng = np.random.default_rng([int(seed), 0x4354524C])
    pick = rng.choice(len(every), min(rows, len(every)), replace=False)
    return [every[int(i)] for i in pick]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--requests", type=int, default=4)
    ap.add_argument("--rows", type=int, default=3)
    ap.add_argument("--tiny", action="store_true",
                    help="the tests' size (rehearsal.json)")
    args = ap.parse_args(argv)
    loaded = run.load_cell(args.workload)
    cfg, traffic = loaded["config"], loaded["traffic"]
    if args.tiny:
        cfg = run.merge(cfg, run.load_json(run.HERE, "rehearsal.json")["config"])
    limits = cfg["correct"]["limits"]
    t, s, d = gen.bulk_log(cfg, args.seed)
    k = np.full(len(t), gen.EADD, np.uint8)
    n_ids = int(cfg["graph"]["id_space"])
    views = views_of(cfg, traffic, args.seed, args.requests, args.rows)
    if traffic["loop"] == "subscription":
        n_tail = max(T for T, _ in views) - cfg["graph"]["t_span"]
        tt, tk, ts, td = gen.tail_events(cfg, args.seed, n_tail)
        t, k = np.concatenate([t, tt]), np.concatenate([k, tk])
        s, d = np.concatenate([s, ts]), np.concatenate([d, np.maximum(td, 0)])
        n_ids = max(n_ids, cfg["tail"]["id_pool"])
    ref = reference.RefEvents(t, k, s, d, n_ids)
    alg = cfg["algorithm"]
    algo = algorithms.load(alg["module"])
    control_failed = stated_passed = True
    for T, w in views:
        vm, src, dst = ref.fold(T, w)
        want = algo.reference(vm, src, dst, alg)
        out = {"time": T, "window": w, "limits": limits}
        for name, served in (("control", algo.control),
                             ("stated", algo.stated)):
            out[name] = algo.compare(served(vm, src, dst, alg), want,
                                     limits, alg)
        control_failed &= not out["control"]["ok"]
        stated_passed &= out["stated"]["ok"]
        print(json.dumps(out), flush=True)
    print(json.dumps({"workload": args.workload, "seed": args.seed,
                      "control_not_correct": control_failed,
                      "stated_precision_correct": stated_passed}))
    return 0 if control_failed and stated_passed else 1


if __name__ == "__main__":
    sys.exit(main())
