#!/usr/bin/env python3
"""The lower-precision control of the directory cell's comparison.

    python bench/control_directory.py --workload directory.fresh --seeds 1 2 3

The directory counterpart of ``bench/control.py``: for each seed it
answers the cells that a run of the cell would compare (every sweep's
stratified sample) with ``bench/reference_directory.py`` in bfloat16,
and counts the answers that differ from the float32 reference, the
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
import reference_directory as ref  # noqa: E402
import run as harness  # noqa: E402
from loads import directory_sweeps  # noqa: E402


def cells_compared(cfg: dict, traffic: dict, seed: int, n_sweeps: int):
    """The stratified samples of sweeps 1..``n_sweeps`` of run ``seed``."""
    out = []
    for k in range(1, n_sweeps + 1):
        cells = directory_sweeps.grid(cfg["axes"], grids.sweep_seeds(
            seed, k, int(traffic["seeds_per_sweep"])))
        pick = directory_sweeps.check_sample(
            cells, int(traffic["check_per_stratum"]),
            np.random.default_rng([seed, k, 2]))
        out += [cells[i] for i in pick]
    return out


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", default="directory.fresh")
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--sweeps", type=int, default=4,
                    help="sweeps per run whose samples are compared")
    args = ap.parse_args()
    bm = harness.load_json(harness.ROOT, "BENCHMARK.json")
    cell = harness.find_cell(bm, args.workload)
    cfg = harness.load_json(harness.BENCH, "configs",
                            cell["config"] + ".json")
    traffic = harness.load_json(harness.BENCH, "traffic",
                                cell["traffic"] + ".json")
    for seed in args.seeds:
        cells = cells_compared(cfg, traffic, seed, args.sweeps)
        t0 = time.perf_counter()
        want = ref.answers(cells, cfg)
        control = ref.answers(cells, cfg, dtype=ml_dtypes.bfloat16)
        bad = reference.mismatches(control, want)
        loaded = [i for i, c in enumerate(cells) if c.directory_load > 0.0]
        bad_loaded = reference.mismatches([control[i] for i in loaded],
                                          [want[i] for i in loaded])
        print(f"control {args.workload} seed {seed}: {bad} of {len(cells)} "
              f"answers differ (limit 0), {bad_loaded} of {len(loaded)} "
              f"at a nonzero directory load, "
              f"{time.perf_counter() - t0:.1f} s", flush=True)


if __name__ == "__main__":
    main()
