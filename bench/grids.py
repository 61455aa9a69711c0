"""Scenario cells of a configuration's grids, and the program's specs
for them."""

from __future__ import annotations

import itertools
from typing import Dict, List

import numpy as np

import reference


def sweep_seeds(seed: int, index: int, n: int) -> List[int]:
    """``n`` distinct trace seeds of sweep ``index`` of run ``seed``."""
    rng = np.random.default_rng([seed, index, 1])
    while True:
        out = [int(x) for x in rng.integers(0, 2**31 - 1, n)]
        if len(set(out)) == n:
            return out


def grid(axes: Dict, seeds: List[int]) -> List[reference.Cell]:
    """The grid's cells, in the program's sweep order, at ``seeds``."""
    return [reference.Cell(w, c, s, nr, bw, ncn, sb)
            for w, c, s, nr, bw, ncn, sb in itertools.product(
                axes["workloads"], axes["configs"], seeds,
                axes["n_replicas"], axes["link_bw_gbps"], axes["n_cns"],
                axes["sb_sizes"])]


def to_spec(c: reference.Cell):
    from repro.core.simulator import ScenarioSpec

    return ScenarioSpec(c.workload, c.config, seed=c.seed,
                        n_replicas=c.n_replicas,
                        link_bw_gbps=c.link_bw_gbps, n_cns=c.n_cns,
                        sb_size=c.sb_size, coalescing=c.coalescing)
