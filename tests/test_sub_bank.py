"""Per-shard sub-bank partitioning: differential + layout tests.

The sub-bank contract (PR 8, ``engine.run_grid(bank_partition="sub")``
-- the default): the three max-plus bank planes are partitioned over
the ``cells`` mesh (wv row ``r`` owned by shard ``r % n_shards`` at
local index ``r // n_shards``), scan lanes are scheduled into their
owner shard's slot block by ``plan_tiles(owners=...)``, and the in-jit
gather runs against shard-resident rows only -- while every answer
stays bit-identical (``==``) to the replicated layout and the serial
oracle, for ragged mixed-SB grids with the contention and directory
axes on. Measured resident device bytes
(``bank_stats()``) must actually drop to ~1/n_shards.
"""

import jax
import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.core import engine as E
from repro.core.scenarios import mega_grid
from repro.core.simulator import (
    CONFIGS,
    PAPER_CLUSTER,
    ScenarioSpec,
    bank_row_maps,
    clear_sim_caches,
    simulate_spec,
    sub_bank_rows,
)

N = 700
WORKLOAD_POOL = ("ycsb", "canneal", "barnes", "raytrace", "ocean_ncp")
FLOAT_FIELDS = ("exec_time_ns", "repl_at_head_frac", "sb_full_frac",
                "max_log_bytes", "cxl_mem_bw_gbps", "log_dump_bw_gbps")

SHARD_COUNTS = sorted({1, min(8, jax.device_count())})


def _assert_bit_identical(got, want, ctx):
    assert len(got) == len(want)
    for a, b in zip(got, want):
        for f in FLOAT_FIELDS:
            assert getattr(a, f) == getattr(b, f), (ctx, a.meta, f)


@st.composite
def ragged_grids(draw):
    """Ragged mixed-SB grids over every serve axis, including the
    PR-5 contention and PR-6 directory knobs (which add bank rows of
    their own, so ownership interleaves non-trivially)."""
    n = draw(st.integers(min_value=1, max_value=14))
    specs = []
    for _ in range(n):
        specs.append(ScenarioSpec(
            draw(st.sampled_from(WORKLOAD_POOL)),
            draw(st.sampled_from(CONFIGS)),
            seed=draw(st.integers(min_value=0, max_value=2)),
            n_replicas=draw(st.sampled_from((None, 2, 3))),
            link_bw_gbps=draw(st.sampled_from((None, 40.0))),
            sb_size=draw(st.sampled_from((None, 16, 48))),
            coalescing=draw(st.booleans()),
            read_share=draw(st.sampled_from((None, 0.3))),
            conflict_rate=draw(st.sampled_from((None, 0.05))),
            directory_load=draw(st.sampled_from((None, 0.5)))))
    return specs


@settings(max_examples=6, deadline=None)
@given(ragged_grids())
def test_sub_bank_bitident_across_shards_planes_partitions(grid):
    """Differential core: sub vs replicated vs the serial oracle, at 1
    and 8 shards, on ragged contention/directory grids."""
    oracle = [simulate_spec(s, n_stores=N) for s in grid]
    for n_shards in SHARD_COUNTS:
        sub = E.run_grid(grid, n_stores=N, tile_cells=16,
                         n_shards=n_shards)
        assert E.bank_stats()["bank_partition"] == "sub"
        _assert_bit_identical(sub, oracle, ("sub", n_shards))
        rep = E.run_grid(grid, n_stores=N, tile_cells=16,
                         n_shards=n_shards, bank_partition="replicated")
        _assert_bit_identical(rep, oracle, ("replicated", n_shards))


def test_plan_tiles_owner_partitioning():
    """The owner-aware scheduler must place every lane exactly once, in
    its owning shard's slot block, with per-tile padded shapes still
    full-size (b_pad divisible by n_shards)."""
    n_shards = 4
    specs = [ScenarioSpec(w, c, seed=s)
             for w in WORKLOAD_POOL for c in CONFIGS for s in (0, 1)]
    rng = np.random.default_rng(0)
    owners = [int(rng.integers(n_shards)) for _ in specs]
    tiles = E.plan_tiles(specs, n_stores=N, tile_cells=16,
                         n_shards=n_shards, owners=owners)
    seen = sorted(i for t in tiles for i in t.indices)
    assert seen == list(range(len(specs)))
    for t in tiles:
        assert t.slots is not None
        assert len(t.slots) == len(t.indices) == len(t.specs)
        assert len(set(t.slots)) == len(t.slots)          # no collisions
        assert t.sig.b_pad % n_shards == 0
        per = t.sig.b_pad // n_shards
        for i, pos in zip(t.indices, t.slots):
            assert 0 <= pos < t.sig.b_pad
            # the slot block index IS the owning shard
            assert pos // per == owners[i], (i, pos, per)
    # owners=None (or one shard) keeps the legacy identity layout
    legacy = E.plan_tiles(specs, n_stores=N, tile_cells=16,
                          n_shards=n_shards)
    assert all(t.slots is None for t in legacy)
    single = E.plan_tiles(specs, n_stores=N, tile_cells=16, n_shards=1,
                          owners=[0] * len(specs))
    assert all(t.slots is None for t in single)


def test_sub_bank_rows_and_host_layout():
    """sub_bank_rows / TraceBank.sub_bank_host: ceil-divided local
    count (floored at one row), owner ``r % n``, local ``r // n``,
    zero-padded ragged tails -- the layout every shard gathers from."""
    assert sub_bank_rows(8, 4) == 2
    assert sub_bank_rows(9, 4) == 3
    assert sub_bank_rows(1, 8) == 1
    assert sub_bank_rows(0, 8) == 1               # never an empty plane
    from repro.core.simulator import get_trace_bank
    specs = [ScenarioSpec(w, c) for w in WORKLOAD_POOL for c in CONFIGS]
    bank = get_trace_bank(specs, N, PAPER_CLUSTER)
    n = 4
    a, w, v, p = bank.sub_bank_host(n)
    assert a is bank.arrivals                     # replicated, not copied
    p_loc = sub_bank_rows(bank.wv_rows, n)
    assert w.shape == v.shape == p.shape == (n, p_loc, N)
    for r in range(bank.wv_rows):
        assert np.array_equal(w[r % n, r // n], bank.w[r])
        assert np.array_equal(v[r % n, r // n], bank.v[r])
        assert np.array_equal(p[r % n, r // n], bank.pr_nc[r])
    # ragged tail rows stay zero
    for s in range(n):
        local = len(bank.w[s::n])
        assert not w[s, local:].any()


@pytest.mark.parametrize("n_shards", [1, 4])
def test_sub_bank_host_copies_only_when_the_layout_moves_rows(n_shards):
    """At one shard the sub-bank layout is the identity, so
    ``sub_bank_host`` hands back views of the bank's own columns; at
    several shards it is the zero-padded round-robin copy. Both equal
    the layout built row by row, and an empty wv plane still gets its
    one zero padding row."""
    import dataclasses
    from repro.core.simulator import get_trace_bank
    specs = [ScenarioSpec(w, c) for w in WORKLOAD_POOL for c in CONFIGS]
    bank = get_trace_bank(specs, N, PAPER_CLUSTER)
    p_loc = sub_bank_rows(bank.wv_rows, n_shards)
    cols = (bank.w, bank.v, bank.pr_nc)
    host = bank.sub_bank_host(n_shards)
    assert host[0] is bank.arrivals
    for got, col in zip(host[1:], cols):
        want = np.zeros((n_shards, p_loc, N), col.dtype)
        for r in range(bank.wv_rows):
            want[r % n_shards, r // n_shards] = col[r]
        assert got.dtype == col.dtype
        assert np.array_equal(got, want)
        assert np.shares_memory(got, col) == (n_shards == 1)

    empty = dataclasses.replace(
        bank, w=bank.w[:0], v=bank.v[:0], pr_nc=bank.pr_nc[:0],
        wv_row={}, _device={})
    for got, col in zip(empty.sub_bank_host(n_shards)[1:], cols):
        assert got.shape == (n_shards, 1, N) and got.dtype == col.dtype
        assert not got.any()


def test_measured_sub_bytes_cut_vs_replicated():
    """The point of the PR: measured per-shard resident bytes under the
    sub partition stay within ~1.1x of bank/n_shards + the replicated
    arrivals, and the fleet total is ~flat instead of x n_shards."""
    n_shards = min(8, jax.device_count())
    if n_shards < 2:
        pytest.skip("needs >= 2 devices to partition")
    grid = [ScenarioSpec(w, c, seed=s, n_replicas=r)
            for w in WORKLOAD_POOL for c in CONFIGS
            for s in (0, 1) for r in (None, 2, 3)]
    clear_sim_caches()
    E.run_grid(grid, n_stores=N, tile_cells=16, n_shards=n_shards)
    sub = E.bank_stats()
    clear_sim_caches()
    E.run_grid(grid, n_stores=N, tile_cells=16, n_shards=n_shards,
               bank_partition="replicated")
    rep = E.bank_stats()
    assert sub["bank_bytes"] == rep["bank_bytes"] > 0
    # replicated pins the exact products; sub must genuinely partition
    assert rep["bank_dev_bytes"] == rep["bank_bytes"] * n_shards
    assert rep["bank_dev_bytes_per_shard"] == rep["bank_bytes"]
    bank = E.get_trace_bank(grid, N)
    a, w, v, p = bank.sub_bank_host(n_shards)
    stacks = w.nbytes + v.nbytes + p.nbytes       # padded, one fleet copy
    assert sub["bank_dev_bytes"] == n_shards * a.nbytes + stacks
    assert sub["bank_dev_bytes"] < rep["bank_dev_bytes"]
    # per-shard: its stack slice + the replicated arrivals, nothing more
    assert 0 < sub["bank_dev_bytes_per_shard"] \
        <= a.nbytes + stacks // n_shards
    # only arrivals replicate over the fabric under sub
    assert sub["bank_fabric_bytes"] == a.nbytes * (n_shards - 1)
    assert rep["bank_fabric_bytes"] == \
        rep["bank_bytes"] * (n_shards - 1)


def test_mega_grid_bank_keys_and_lanes_unchanged():
    """Partitioning must not move a single bank row or lane: the
    12 960-cell mega-grid keeps its 27 + 1298 rows and 2 700 lanes."""
    mega = mega_grid()
    trace_map, wv_map = bank_row_maps(mega)
    assert len(trace_map) == 27
    assert len(wv_map) == 1298
    from repro.core.simulator import _plane_keys
    lanes = {(s.sb_size if s.sb_size is not None
              else PAPER_CLUSTER.store_buffer,)
             + _plane_keys(s, PAPER_CLUSTER) for s in mega}
    assert len(lanes) == 2700
    # local row counts cover every wv row exactly once at 8 shards
    owners = [r % 8 for r in wv_map.values()]
    assert sub_bank_rows(len(wv_map), 8) == -(-len(wv_map) // 8)
    assert sum(owners.count(s) for s in range(8)) == len(wv_map)
