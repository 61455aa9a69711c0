"""Closed-loop sweeps of a directory-coupled pod: the ``sweeps`` load's
protocol on the directory axes.

Each sweep is the configuration's whole directory cross-product (its
``axes``: workloads x rules x trace seeds x N_r x link x CNs x SB x
directory load) at a fresh set of trace seeds drawn from the run's seed
and the sweep's index, timed as ``scenarios.grid_bank(specs)`` and then
``scenarios.run_sweep(specs)``, exactly as ``bench/loads/sweeps.py``
times them; set-up and the scan bytes are ``contended_sweeps``'s. The
compared sample holds ``check_per_stratum`` cells of every rule x CN
count x load stratum, so the clamped 4-CN census and every load are
checked in every sweep against ``bench/reference_directory.py``; the
lanes of ``scan_bytes`` are counted by ``bench/work_directory.py``.

Traffic parameters (``bench/traffic/<name>.json``): ``seeds_per_sweep``,
``check_per_stratum`` and ``trace_seconds``, as for ``sweeps``.
"""

from __future__ import annotations

import itertools
import sys
import time
from typing import Dict, List

import numpy as np

import reference
import reference_directory as ref
import work_directory
from grids import sweep_seeds
from loads import contended_sweeps


def grid(axes: Dict, seeds: List[int]) -> List[ref.Cell]:
    """The grid's cells, in ``scenarios.sweep_grid``'s order, at
    ``seeds``."""
    return [ref.Cell(w, c, s, nr, bw, ncn, sb, directory_load=dl)
            for w, c, s, nr, bw, ncn, sb, dl in itertools.product(
                axes["workloads"], axes["configs"], seeds,
                axes["n_replicas"], axes["link_bw_gbps"], axes["n_cns"],
                axes["sb_sizes"], axes["directory_loads"])]


def to_spec(c: ref.Cell):
    from repro.core.simulator import ScenarioSpec

    return ScenarioSpec(c.workload, c.config, seed=c.seed,
                        n_replicas=c.n_replicas,
                        link_bw_gbps=c.link_bw_gbps, n_cns=c.n_cns,
                        sb_size=c.sb_size, coalescing=c.coalescing,
                        directory_load=c.directory_load)


def check_sample(cells: List[ref.Cell], per: int,
                 rng: np.random.Generator) -> List[int]:
    """``per`` positions from every (rule, CN count, load) stratum."""
    strata: Dict[tuple, List[int]] = {}
    for i, c in enumerate(cells):
        strata.setdefault((c.config, c.n_cns, c.directory_load),
                          []).append(i)
    picked: List[int] = []
    for key in sorted(strata):
        m = strata[key]
        picked += rng.choice(m, size=min(per, len(m)),
                             replace=False).tolist()
    return sorted(picked)


class Load(contended_sweeps.Load):
    """Sweeps of the directory grid at fresh seeds: the contended load's
    set-up and scan bytes, over this grid, its sample and its lanes."""

    def _cells(self, index: int) -> List[ref.Cell]:
        return grid(self.cfg["axes"], sweep_seeds(
            self.seed, index, int(self.traffic["seeds_per_sweep"])))

    def _plan(self, index: int) -> tuple:
        cells = self._cells(index)
        pick = check_sample(cells, int(self.traffic["check_per_stratum"]),
                            np.random.default_rng([self.seed, index, 2]))
        return [to_spec(c) for c in cells], pick

    def _lanes(self, index: int) -> int:
        return work_directory.scan_lanes(self._cells(index),
                                         self.cfg["directory"])

    def check(self) -> Dict[str, tuple]:
        """Sampled answers of every sweep against the directory
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
        print(f"bench: directory reference compared {len(have)} sampled "
              f"cells of {len(self.sweeps)} sweeps in "
              f"{time.perf_counter() - t0:.3f} s", file=sys.stderr,
              flush=True)
        return {"mismatched_cells": (bad, 0), "missing_cells": (missing, 0),
                "empty_window": (0 if self.sweeps else 1, 0)}
