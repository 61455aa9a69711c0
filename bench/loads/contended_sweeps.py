"""Closed-loop sweeps of a contended pod: the ``sweeps`` load's protocol
on the contention axes.

Each sweep is the configuration's whole contention cross-product (its
``axes``: workloads x rules x trace seeds x N_r x link x CNs x SB x read
share x conflict rate x persist schedule) at a fresh set of trace seeds
drawn from the run's seed and the sweep's index, timed as
``scenarios.grid_bank(specs)`` and then ``scenarios.run_sweep(specs)``,
exactly as ``bench/loads/sweeps.py`` times them. The compared sample
holds ``check_per_stratum`` cells of every rule x conflict rate x read
share x schedule stratum, so every contended corner is checked in every
sweep against ``bench/reference_contention.py``; the lanes of
``scan_bytes`` are counted by ``bench/work_contention.py``.

Traffic parameters (``bench/traffic/<name>.json``): ``seeds_per_sweep``,
``check_per_stratum`` and ``trace_seconds``, as for ``sweeps``.
"""

from __future__ import annotations

import itertools
import math
import sys
import time
from typing import Dict, List

import numpy as np

import reference
import reference_contention as ref
import work
import work_contention
from grids import sweep_seeds
from loads import sweeps


def grid(axes: Dict, seeds: List[int]) -> List[ref.Cell]:
    """The grid's cells, in ``scenarios.sweep_grid``'s order, at
    ``seeds``."""
    return [ref.Cell(w, c, s, nr, bw, ncn, sb, read_share=rs,
                     conflict_rate=cr, schedule=cs)
            for w, c, s, nr, bw, ncn, sb, rs, cr, cs in itertools.product(
                axes["workloads"], axes["configs"], seeds,
                axes["n_replicas"], axes["link_bw_gbps"], axes["n_cns"],
                axes["sb_sizes"], axes["read_shares"],
                axes["conflict_rates"], axes["schedules"])]


def to_spec(c: ref.Cell):
    from repro.core.simulator import ScenarioSpec

    return ScenarioSpec(c.workload, c.config, seed=c.seed,
                        n_replicas=c.n_replicas,
                        link_bw_gbps=c.link_bw_gbps, n_cns=c.n_cns,
                        sb_size=c.sb_size, coalescing=c.coalescing,
                        read_share=c.read_share,
                        conflict_rate=c.conflict_rate,
                        consistency_schedule=c.schedule)


def check_sample(cells: List[ref.Cell], per: int,
                 rng: np.random.Generator) -> List[int]:
    """``per`` positions from every (rule, conflict rate, read share,
    schedule) stratum."""
    strata: Dict[tuple, List[int]] = {}
    for i, c in enumerate(cells):
        key = (c.config, c.conflict_rate, c.read_share, c.schedule)
        strata.setdefault(key, []).append(i)
    picked: List[int] = []
    for key in sorted(strata):
        m = strata[key]
        picked += rng.choice(m, size=min(per, len(m)),
                             replace=False).tolist()
    return sorted(picked)


class Load(sweeps.Load):
    """Sweeps of the contended grid at fresh seeds."""

    def _cells(self, index: int) -> List[ref.Cell]:
        return grid(self.cfg["axes"], sweep_seeds(
            self.seed, index, int(self.traffic["seeds_per_sweep"])))

    def _plan(self, index: int) -> tuple:
        cells = self._cells(index)
        pick = check_sample(cells, int(self.traffic["check_per_stratum"]),
                            np.random.default_rng([self.seed, index, 2]))
        return [to_spec(c) for c in cells], pick

    def _lanes(self, index: int) -> int:
        return work_contention.scan_lanes(self._cells(index),
                                          self.cfg["contention"])

    def setup(self, seconds: float) -> None:
        """One sweep at index 0, then the specs of the sweeps the window
        can hold, as ``sweeps.Load.setup``."""
        from repro.configs.recxl_paper import ClusterConfig
        from repro.core import engine

        self.cluster = ClusterConfig(**self.cfg["cluster"])
        first = self._sweep(0, *self._plan(0))
        self.est_s = first["t_end"] - first["t0"]
        n = math.ceil(2.0 * seconds / self.est_s) + 1
        self.planned = {k: self._plan(k) for k in range(1, n + 1)}
        print(f"bench: set-up sweep {first['cells']} cells, "
              f"{self._lanes(0)} scan lanes by the semantics (engine "
              f"scanned {engine.bank_stats().get('scan_lanes')}), "
              f"{self.est_s:.3f} s; {n} sweeps planned",
              file=sys.stderr, flush=True)

    def outcome(self) -> dict:
        """``sweeps.Load.outcome`` with the scan bytes of the contended
        lanes."""
        out = super().outcome()
        lanes = sum(self._lanes(s["index"]) for s in self.sweeps)
        out["records"]["scan_bytes"] = \
            lanes * self.n_stores * work.BYTES_PER_LANE_STORE
        return out

    def check(self) -> Dict[str, tuple]:
        """Sampled answers of every sweep against the contended
        reference; a cell with no answer counts as missing."""
        sample = []
        for s in self.sweeps:
            cells = self._cells(s["index"])
            sample += [(cells[i], r) for i, r in s["sample"]]
        missing = sum(s["cells"] - s["answered"] for s in self.sweeps)
        have = [(c, r) for c, r in sample if r is not None]
        t0 = time.perf_counter()
        want = ref.answers([c for c, _ in have], self.cfg)
        got = [{f: getattr(r, f) for f in reference.FIELDS}
               for _, r in have]
        bad = reference.mismatches(got, want)
        print(f"bench: contended reference compared {len(have)} sampled "
              f"cells of {len(self.sweeps)} sweeps in "
              f"{time.perf_counter() - t0:.3f} s", file=sys.stderr,
              flush=True)
        return {"mismatched_cells": (bad, 0), "missing_cells": (missing, 0),
                "empty_window": (0 if self.sweeps else 1, 0)}
