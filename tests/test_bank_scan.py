"""The banked blocked scan against the serial oracle, bit for bit.

``_timeline_banked`` is the in-jit bank gather (``_bank_gather``) plus
the shared ``_scan_wv`` core: the program every banked tile runs, on
every backend. On real bank columns its per-cell results must equal the
serial ``simulate()`` oracle ``==``. Chunk sizes sweep a one-store
block, a ragged tail, chunk == SB, and a chunk past SB that the tile
planner clamps to SB.
"""

import numpy as np
import pytest

import jax.numpy as jnp

from repro.core.engine import plan_tiles
from repro.core.simulator import (
    CONFIGS,
    PAPER_CLUSTER,
    ScenarioSpec,
    _banked_inputs,
    _finish_result,
    _timeline_banked,
    get_trace_bank,
    simulate_spec,
)

N = 500                                  # ragged vs every chunk below
SB = 24


@pytest.fixture(scope="module")
def banked_grid():
    specs = tuple(ScenarioSpec(w, c, seed=s, sb_size=SB)
                  for w in ("ycsb", "canneal", "barnes")
                  for c in CONFIGS for s in (0, 1))
    (cells, cell_lane, n_lanes, tr, wv, sb_arr, sb_max, _,
     sb_uniform) = _banked_inputs(specs, N, PAPER_CLUSTER)
    bank = get_trace_bank(specs, N, PAPER_CLUSTER)
    assert sb_uniform == SB
    assert n_lanes == len(specs)         # all-distinct lanes in this grid
    args = tuple(jnp.asarray(x) for x in
                 (bank.arrivals, bank.w, bank.v, bank.pr_nc)) + tuple(
        jnp.asarray(x) for x in (tr, wv, sb_arr))
    oracle = [simulate_spec(s, n_stores=N) for s in specs]
    return specs, cells, cell_lane, args, sb_max, oracle


@pytest.mark.parametrize("chunk", [1, 7, SB, 4 * SB])
def test_banked_scan_matches_serial_oracle(banked_grid, chunk):
    specs, cells, cell_lane, args, sb_max, oracle = banked_grid
    # a block may not look past the SB ring: the planner clamps to SB
    (eff,) = {t.sig.chunk for t in plan_tiles(specs, n_stores=N,
                                              chunk_size=chunk)}
    assert eff == min(chunk, SB)
    exec_ns, at_head, sb_full = (np.asarray(x) for x in _timeline_banked(
        *args, sb_max, eff, SB))
    got = [_finish_result(c, exec_ns[j], int(at_head[j]), int(sb_full[j]))
           for c, j in zip(cells, cell_lane)]
    assert got == oracle, f"chunk={chunk} (effective {eff})"
