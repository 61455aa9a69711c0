"""The tile program against the serial oracle, bit for bit.

``run_grid``'s tile program is the in-jit bank gather (``_bank_gather``)
plus the blocked scan ``_scan_wv``: the program every tile runs, on
every backend. On real bank columns its per-cell results must equal the
serial ``simulate()`` oracle ``==``. Chunk sizes sweep a one-store
block, a ragged tail, chunk == SB, and a chunk past SB that the tile
planner clamps to SB.
"""

import pytest

from repro.core.engine import plan_tiles, run_grid
from repro.core.simulator import CONFIGS, ScenarioSpec, simulate_spec

N = 500                                  # ragged vs every chunk below
SB = 24


@pytest.fixture(scope="module")
def banked_grid():
    specs = tuple(ScenarioSpec(w, c, seed=s, sb_size=SB)
                  for w in ("ycsb", "canneal", "barnes")
                  for c in CONFIGS for s in (0, 1))
    oracle = [simulate_spec(s, n_stores=N) for s in specs]
    return specs, oracle


@pytest.mark.parametrize("chunk", [1, 7, SB, 4 * SB])
def test_banked_scan_matches_serial_oracle(banked_grid, chunk):
    specs, oracle = banked_grid
    # a block may not look past the SB ring: the planner clamps to SB
    (eff,) = {t.sig.chunk for t in plan_tiles(specs, n_stores=N,
                                              chunk_size=chunk)}
    assert eff == min(chunk, SB)
    got = run_grid(specs, n_stores=N, chunk_size=chunk, n_shards=1,
                   tile_cells=16)
    assert {r.meta["chunk"] for r in got} == {eff}
    assert got == oracle, f"chunk={chunk} (effective {eff})"
