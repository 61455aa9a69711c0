"""Closed-loop re-sweeps of one design space over new pod sizes: the
``sweeps`` load's protocol with the traces held and the CN axis drawn
anew.

Every sweep is the configuration's whole grid at the set-up sweep's
trace seeds (``sweep_seeds(seed, 0, seeds_per_sweep)``), with the
``n_cns`` axis replaced by ``cns_per_sweep`` distinct counts drawn from
``cn_counts`` by ``[seed, index]``; set-up (index 0) sweeps the
configuration's own CN axis. A sweep's set of counts is never that of
either of the two sweeps before it, so no sweep finds the grid of an
earlier one among the program's two memoized banks. What an architect
does who has a design space's traces and re-sweeps it over new pod
sizes (Fig. 18 weak scaling): only the CN count changes, and the
program's bank keys see a new grid. The sample and the reference are
``sweeps``'s (``bench/reference.py``).

Traffic parameters (``bench/traffic/<name>.json``): ``seeds_per_sweep``,
``check_per_stratum`` and ``trace_seconds`` as for ``sweeps``, plus
``cn_counts`` and ``cns_per_sweep``.
"""

from __future__ import annotations

import sys
import time
from typing import Dict, List

import numpy as np

import reference
from grids import grid, sweep_seeds
from loads import sweeps

#: Sweeps whose samples the reference answers at once: it holds about
#: 2 MB of host arrays a cell at 50 000 stores, and a window of these
#: short sweeps compares over 10 000 cells.
CHECK_SWEEPS = 20


def cn_axes(seed: int, n: int, first: List[int], pool: List[int],
            k: int) -> List[List[int]]:
    """The CN axes of sweeps 0..``n - 1``: ``first`` at index 0, then
    ``k`` distinct counts of ``pool`` (largest first) drawn by ``[seed,
    index]`` until their set differs from those of the two sweeps
    before."""
    out = [list(first)]
    for index in range(1, n):
        rng = np.random.default_rng([seed, index, 3])
        recent = [set(a) for a in out[-2:]]
        while True:
            axis = sorted((int(x) for x in rng.choice(pool, k,
                                                      replace=False)),
                          reverse=True)
            if set(axis) not in recent:
                break
        out.append(axis)
    return out


class Load(sweeps.Load):
    """Re-sweeps of the grid over fresh CN counts, at set-up's traces."""

    def __init__(self, cfg: dict, traffic: dict, seed: int, devices):
        super().__init__(cfg, traffic, seed, devices)
        self.seeds = sweep_seeds(self.seed, 0,
                                 int(traffic["seeds_per_sweep"]))
        self.axes: List[List[int]] = []

    def _cn_axis(self, index: int) -> List[int]:
        if index >= len(self.axes):
            self.axes = cn_axes(self.seed, index + 1,
                                self.cfg["axes"]["n_cns"],
                                self.traffic["cn_counts"],
                                int(self.traffic["cns_per_sweep"]))
        return self.axes[index]

    def _cells(self, index: int) -> List[reference.Cell]:
        axes: Dict = dict(self.cfg["axes"], n_cns=self._cn_axis(index))
        return grid(axes, self.seeds)

    def check(self) -> Dict[str, tuple]:
        """``sweeps.Load.check``, with the reference answering
        :data:`CHECK_SWEEPS` sweeps' samples at a time."""
        bad = 0
        t0 = time.perf_counter()
        for k in range(0, len(self.sweeps), CHECK_SWEEPS):
            have = []
            for s in self.sweeps[k:k + CHECK_SWEEPS]:
                cells = self._cells(s["index"])
                have += [(cells[i], r) for i, r in s["sample"]
                         if r is not None]
            want = reference.answers([c for c, _ in have], self.cfg)
            bad += reference.mismatches(
                [{f: getattr(r, f) for f in reference.FIELDS}
                 for _, r in have], want)
        missing = sum(s["cells"] - s["answered"] for s in self.sweeps)
        print(f"bench: reference compared the samples of "
              f"{len(self.sweeps)} sweeps in "
              f"{time.perf_counter() - t0:.3f} s", file=sys.stderr,
              flush=True)
        return {"mismatched_cells": (bad, 0), "missing_cells": (missing, 0),
                "empty_window": (0 if self.sweeps else 1, 0)}
