"""Closed-loop sweeps: one architect sweeping new design spaces back to
back.

Each sweep is the configuration's whole grid (its ``axes``) at a fresh
triple of trace seeds drawn from the run's seed and the sweep's index,
so the trace bank, its max-plus rows and its device placement are built
anew every time. A sweep is ``scenarios.grid_bank(specs)`` (timed on its
own: the bank build) and then ``scenarios.run_sweep(specs)``; set-up runs
one sweep at index 0, which the window never uses, and builds the specs
of the window's sweeps, so the window times the program alone.

Traffic parameters (``bench/traffic/<name>.json``): ``seeds_per_sweep``
(how many trace seeds replace the configuration's ``seeds`` axis) and
``check_per_stratum`` (answers compared per commit rule x SB depth x
workload, in every sweep) and ``trace_seconds`` (the window of a traced
run, ``bench/run.py``).
"""

from __future__ import annotations

import math
import statistics
import sys
import time
from typing import Dict, List

import numpy as np

import reference
import work
from grids import grid, sweep_seeds, to_spec


def check_sample(cells: List[reference.Cell], per: int,
                 rng: np.random.Generator) -> List[int]:
    """``per`` positions from every (rule, SB depth, workload) stratum."""
    strata: Dict[tuple, List[int]] = {}
    for i, c in enumerate(cells):
        strata.setdefault((c.config, c.sb_size, c.workload), []).append(i)
    picked: List[int] = []
    for key in sorted(strata):
        m = strata[key]
        picked += rng.choice(m, size=min(per, len(m)),
                             replace=False).tolist()
    return sorted(picked)


class Load:
    """Sweeps of the configuration's grid at fresh seeds."""

    def __init__(self, cfg: dict, traffic: dict, seed: int, devices):
        self.cfg = cfg
        self.traffic = traffic
        self.seed = int(seed)
        self.n_stores = int(cfg["n_stores"])
        self.n_shards = int(cfg["n_shards"])
        self.sweeps: List[dict] = []
        self.planned: Dict[int, tuple] = {}
        self.t_start = self.t_end = 0.0

    def _cells(self, index: int) -> List[reference.Cell]:
        return grid(self.cfg["axes"], sweep_seeds(
            self.seed, index, int(self.traffic["seeds_per_sweep"])))

    def _plan(self, index: int) -> tuple:
        """Sweep ``index``'s specs and the positions of its compared
        sample. The strata sit at the same positions in every sweep, so
        the sample is drawn from any sweep's cells."""
        cells = self._cells(index)
        pick = check_sample(cells, int(self.traffic["check_per_stratum"]),
                            np.random.default_rng([self.seed, index, 2]))
        return [to_spec(c) for c in cells], pick

    def _sweep(self, index: int, specs: list, pick: List[int]) -> dict:
        import jax
        from repro.core.scenarios import grid_bank, run_sweep

        t0 = time.perf_counter()
        with jax.profiler.TraceAnnotation("bench/grid_bank"):
            grid_bank(specs, cluster=self.cluster, n_stores=self.n_stores)
        t1 = time.perf_counter()
        with jax.profiler.TraceAnnotation("bench/run_sweep"):
            got = run_sweep(specs, cluster=self.cluster,
                            n_stores=self.n_stores, n_shards=self.n_shards)
        t2 = time.perf_counter()
        return {"index": index, "t0": t0, "t_bank": t1, "t_end": t2,
                "cells": len(specs),
                "answered": sum(r is not None for r in got),
                "sample": [(i, got[i] if i < len(got) else None)
                           for i in pick]}

    def setup(self, seconds: float) -> None:
        """One sweep at index 0, then the specs of the sweeps the window
        can hold (twice as many as sweeps of the set-up sweep's length),
        so that the window holds only the program's work."""
        from repro.configs.recxl_paper import ClusterConfig
        from repro.core import engine

        self.cluster = ClusterConfig(**self.cfg["cluster"])
        first = self._sweep(0, *self._plan(0))
        self.est_s = first["t_end"] - first["t0"]
        n = math.ceil(2.0 * seconds / self.est_s) + 1
        self.planned = {k: self._plan(k) for k in range(1, n + 1)}
        print(f"bench: set-up sweep {first['cells']} cells, "
              f"{work.scan_lanes(self._cells(0))} scan lanes (engine "
              f"counted {engine.bank_stats().get('scan_lanes')}), "
              f"{self.est_s:.3f} s; {n} sweeps planned",
              file=sys.stderr, flush=True)

    def measure(self, seconds: float) -> None:
        """Sweeps back to back from the window's start; a sweep starts
        only while one of the usual length still fits in the window."""
        self.t_start = time.perf_counter()
        self.t_end = self.t_start + seconds
        index = 1
        while True:
            left = self.t_end - time.perf_counter()
            if left <= 0 or (self.sweeps and left < self.est_s):
                break
            plan = self.planned.pop(index, None) or self._plan(index)
            self.sweeps.append(self._sweep(index, *plan))
            index += 1
            self.est_s = statistics.median(s["t_end"] - s["t0"]
                                           for s in self.sweeps)
        self.planned = {}

    def drain(self) -> None:
        """Sweeps block until answered: nothing is outstanding."""

    def outcome(self) -> dict:
        done = [s for s in self.sweeps if s["t_end"] <= self.t_end]
        metrics = {}
        if done:
            span = done[-1]["t_end"] - self.t_start
            metrics["sweep_cells_per_s"] = \
                sum(s["answered"] for s in done) / span
        for s in self.sweeps:
            print(f"bench: sweep {s['index']}: bank "
                  f"{s['t_bank'] - s['t0']:.3f} s, sweep "
                  f"{s['t_end'] - s['t_bank']:.3f} s, {s['answered']} of "
                  f"{s['cells']} cells answered", file=sys.stderr, flush=True)
        attempted = sum(s["cells"] for s in self.sweeps)
        lanes = sum(work.scan_lanes(self._cells(s["index"]))
                    for s in self.sweeps)
        return {"metrics": metrics, "attempted": attempted,
                "failed": attempted - sum(s["answered"]
                                          for s in self.sweeps),
                "records": {
                    "sweeps": len(self.sweeps),
                    "bank_build_s": [s["t_bank"] - s["t0"]
                                     for s in self.sweeps],
                    "scan_bytes": lanes * self.n_stores
                    * work.BYTES_PER_LANE_STORE}}

    def release(self) -> None:
        """Drop the program's banks, placements and compiled programs."""
        from repro.core.simulator import clear_sim_caches

        clear_sim_caches()

    def check(self) -> Dict[str, tuple]:
        """Sampled answers of every sweep against the reference; a cell
        with no answer counts as missing."""
        sample = []
        for s in self.sweeps:
            cells = self._cells(s["index"])
            sample += [(cells[i], r) for i, r in s["sample"]]
        missing = sum(s["cells"] - s["answered"] for s in self.sweeps)
        have = [(c, r) for c, r in sample if r is not None]
        t0 = time.perf_counter()
        want = reference.answers([c for c, _ in have], self.cfg)
        got = [{f: getattr(r, f) for f in reference.FIELDS}
               for _, r in have]
        bad = reference.mismatches(got, want)
        print(f"bench: reference compared {len(have)} sampled cells of "
              f"{len(self.sweeps)} sweeps in "
              f"{time.perf_counter() - t0:.3f} s", file=sys.stderr, flush=True)
        return {"mismatched_cells": (bad, 0), "missing_cells": (missing, 0),
                "empty_window": (0 if self.sweeps else 1, 0)}
