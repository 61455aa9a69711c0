#!/usr/bin/env python3
"""The lower-precision control of a cell's comparison.

    python bench/control.py --workload megagrid.fresh --seeds 1 2 3

The configuration states float32. The control is the plain reference
computed in bfloat16 and put in the program's place: for each seed it
answers the cells that a run of the cell would compare (the same
stratified sample of fresh sweeps, or the cells that the open loop asks
for) and counts the answers that differ from the float32 reference, the
number a run compares against its limit of 0. The benchmark's own runs
never run it.
"""

from __future__ import annotations

import argparse
import os
import sys
import time

import ml_dtypes
import numpy as np

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import grids  # noqa: E402
import reference  # noqa: E402
import run as harness  # noqa: E402
from loads import open_loop, sweeps  # noqa: E402


def cells_compared(cfg: dict, traffic: dict, seed: int, n_sweeps: int,
                   seconds: float):
    """The cells a run of ``seed`` compares: every sweep's stratified
    sample, or the first ``check_hits + check_misses`` distinct cells
    of the open loop."""
    if traffic["load"] == "sweeps":
        out = []
        for k in range(1, n_sweeps + 1):
            cells = grids.grid(cfg["axes"], grids.sweep_seeds(
                seed, k, int(traffic["seeds_per_sweep"])))
            pick = sweeps.check_sample(
                cells, int(traffic["check_per_stratum"]),
                np.random.default_rng([seed, k, 2]))
            out += [cells[i] for i in pick]
        return out
    universe = grids.grid(cfg["universe"], cfg["universe"]["seeds"])
    _, pos = open_loop.schedule(traffic, seed, seconds, len(universe))
    n = int(traffic["check_hits"]) + int(traffic["check_misses"])
    return list(dict.fromkeys(universe[i] for i in pos))[:n]


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--sweeps", type=int, default=4,
                    help="sweeps per run whose samples are compared")
    ap.add_argument("--seconds", type=float,
                    help="window of the open loop (default: run_seconds)")
    args = ap.parse_args()
    bm = harness.load_json(harness.ROOT, "BENCHMARK.json")
    cell = harness.find_cell(bm, args.workload)
    cfg = harness.load_json(harness.BENCH, "configs",
                            cell["config"] + ".json")
    traffic = harness.load_json(harness.BENCH, "traffic",
                                cell["traffic"] + ".json")
    seconds = args.seconds or float(bm["run_seconds"])
    for seed in args.seeds:
        cells = cells_compared(cfg, traffic, seed, args.sweeps, seconds)
        t0 = time.perf_counter()
        want = reference.answers(cells, cfg)
        control = reference.answers(cells, cfg, dtype=ml_dtypes.bfloat16)
        bad = reference.mismatches(control, want)
        print(f"control {args.workload} seed {seed}: {bad} of {len(cells)} "
              f"answers differ (limit 0), "
              f"{time.perf_counter() - t0:.1f} s", flush=True)


if __name__ == "__main__":
    main()
