"""Sharded streaming engine tier: differential + scheduling tests.

The contract (engine.py module docstring): ``run_grid`` -- tiled,
cell-sharded over the local devices (8 host devices here, set up by
conftest.py), double-buffered -- must be **bit-identical** (``==``) to
the one-shot blocked batch and to the serial ``simulate()`` oracle, for
ragged grids whose cell count divides neither the device count nor the
tile size, and must reuse one compiled program per
:class:`TileSignature` across all tiles (the compile cache is observed
through ``trace_count()``).
"""

import jax
import numpy as np
import pytest

from repro.core import engine as E
from repro.core.simulator import (
    AUTO_CHUNK_WIDE_CELLS,
    CONFIGS,
    DEFAULT_CHUNK_SIZE,
    ScenarioSpec,
    auto_chunk,
    clear_sim_caches,
    simulate,
    simulate_batch,
)

N = 2500                       # N % chunk != 0 -> ragged store tail too
FLOAT_FIELDS = ("exec_time_ns", "repl_at_head_frac", "sb_full_frac",
                "max_log_bytes", "cxl_mem_bw_gbps", "log_dump_bw_gbps")

# 37 cells: not a multiple of the 8 host devices, the tile size used
# below, or the canonical pad sizes; mixed SB depths force two schedule
# groups; seeds/knobs exercise the reduced-key prep sharing.
RAGGED_GRID = (
    [ScenarioSpec(w, c, seed=s)
     for w in ("ycsb", "raytrace", "canneal") for c in CONFIGS
     for s in (0, 1)]
    + [
        ScenarioSpec("barnes", "proactive", sb_size=16),
        ScenarioSpec("ycsb", "parallel", sb_size=16),
        ScenarioSpec("bodytrack", "baseline"),
        ScenarioSpec("ocean_cp", "wt"),
        ScenarioSpec("fluidanimate", "proactive", n_replicas=4),
        ScenarioSpec("streamcluster", "wb"),
        ScenarioSpec("ocean_ncp", "proactive", coalescing=False),
    ]
)


def _assert_bit_identical(specs, got, want, ctx):
    assert len(got) == len(want) == len(specs)
    for spec, a, b in zip(specs, got, want):
        assert (a.workload, a.config) == (spec.workload, spec.config), ctx
        assert a.n_repl_msgs == b.n_repl_msgs, (ctx, spec)
        for f in FLOAT_FIELDS:
            assert getattr(a, f) == getattr(b, f), (ctx, spec, f)


@pytest.fixture(scope="module")
def blocked_results():
    return simulate_batch(RAGGED_GRID, n_stores=N)


def test_stream_bit_identical_to_blocked_and_serial(blocked_results):
    out = E.run_grid(RAGGED_GRID, n_stores=N, tile_cells=16)
    _assert_bit_identical(RAGGED_GRID, out, blocked_results, "stream-vs-blocked")
    # spot-check straight against the serial oracle as well
    for i in (0, 7, 17, 30, 36):
        s = RAGGED_GRID[i]
        rs = simulate(s.workload, s.config, n_stores=N, seed=s.seed,
                      n_replicas=s.n_replicas, link_bw_gbps=s.link_bw_gbps,
                      n_cns=s.n_cns, sb_size=s.sb_size,
                      coalescing=s.coalescing)
        for f in FLOAT_FIELDS:
            assert getattr(out[i], f) == getattr(rs, f), (s, f)


def test_stream_single_shard_matches_sharded(blocked_results):
    if jax.device_count() < 2:
        pytest.skip("needs >= 2 devices for a sharded run")
    sharded = E.run_grid(RAGGED_GRID, n_stores=N, tile_cells=16,
                         n_shards=min(8, jax.device_count()))
    single = E.run_grid(RAGGED_GRID, n_stores=N, tile_cells=16, n_shards=1)
    _assert_bit_identical(RAGGED_GRID, sharded, single, "sharded-vs-single")
    _assert_bit_identical(RAGGED_GRID, single, blocked_results,
                          "single-vs-blocked")
    assert sharded[0].meta["engine"] == "sharded"
    assert sharded[0].meta["n_shards"] > 1
    assert single[0].meta["engine"] == "streamed"
    assert single[0].meta["n_shards"] == 1


@pytest.mark.parametrize("n_shards", [1, 8])
def test_bank_placement_layout_bytes_and_oracle(n_shards):
    """Sub-bank placement copies host bytes only where the layout moves
    rows: ``bank/layout_bytes`` reads 0 at one shard (the bank's own
    columns are uploaded) and the padded stacks' bytes on the 8-device
    mesh, while every answer stays ``==`` the serial oracle."""
    from repro.core import telemetry as tm
    if jax.device_count() < n_shards:
        pytest.skip(f"needs {n_shards} devices")
    grid = RAGGED_GRID[:6] + RAGGED_GRID[-3:]
    clear_sim_caches()                       # force a fresh placement
    with tm.recording() as rec:
        out = E.run_grid(grid, n_stores=N, tile_cells=16,
                         n_shards=n_shards)
        counters = rec.summary()["counters"]
    bank = E.get_trace_bank(grid, N)
    stacks = 0 if n_shards == 1 else sum(
        x.nbytes for x in bank.sub_bank_host(n_shards)[1:])
    assert counters["bank/layout_bytes"] == stacks
    for s, got in zip(grid, out):
        rs = simulate(s.workload, s.config, n_stores=N, seed=s.seed,
                      n_replicas=s.n_replicas, link_bw_gbps=s.link_bw_gbps,
                      n_cns=s.n_cns, sb_size=s.sb_size,
                      coalescing=s.coalescing)
        assert got.n_repl_msgs == rs.n_repl_msgs, s
        for f in FLOAT_FIELDS:
            assert getattr(got, f) == getattr(rs, f), (s, f)


def test_compile_cache_hits_across_tiles():
    """One signature's program must be traced at most once however many
    tiles reuse it, and a second grid with the same shapes must not
    trace at all."""
    clear_sim_caches()           # drop compiled tile programs -> cold
    grid_a = [ScenarioSpec(w, c, seed=s)
              for w in ("ycsb", "raytrace") for c in CONFIGS
              for s in range(8)]                      # 80 cells, one SB
    t0 = E.trace_count()
    E.run_grid(grid_a, n_stores=N, tile_cells=16)     # 5 tiles, 1 sig
    first = E.trace_count() - t0
    assert first <= 2, f"expected one-ish trace for one signature, got {first}"

    # different specs, same tile shapes -> pure cache hits
    grid_b = [ScenarioSpec(w, c, seed=s)
              for w in ("barnes", "canneal") for c in CONFIGS
              for s in range(8, 16)]
    t1 = E.trace_count()
    E.run_grid(grid_b, n_stores=N, tile_cells=16)
    assert E.trace_count() - t1 == 0, "same-signature tiles re-traced"


def test_plan_tiles_partitions_and_canonical_shapes():
    tiles = E.plan_tiles(RAGGED_GRID, n_stores=N, tile_cells=16, n_shards=8)
    # every original index exactly once
    seen = sorted(i for t in tiles for i in t.indices)
    assert seen == list(range(len(RAGGED_GRID)))
    sigs = {t.sig for t in tiles}
    # canonical padding: at most two pad sizes per SB group
    for sb in {t.sig.sb_uniform for t in tiles}:
        pads = {t.sig.b_pad for t in tiles if t.sig.sb_uniform == sb}
        assert len(pads) <= 2, (sb, pads)
    for t in tiles:
        assert len(t.specs) <= t.sig.b_pad
        assert t.sig.b_pad % 8 == 0 and t.sig.b_pad % t.sig.n_shards == 0
        # tiles are SB-uniform by construction
        for s in t.specs:
            sb = s.sb_size if s.sb_size is not None else 72
            assert sb == t.sig.sb_uniform
        assert t.sig.chunk <= t.sig.sb_uniform
    # mixed-SB grid -> one signature set per depth, still a handful
    assert 2 <= len(sigs) <= 4


def test_clear_sim_caches_resets_engine_and_results_stable():
    before = E.run_grid(RAGGED_GRID[:10], n_stores=N, tile_cells=16)
    assert len(E._TILE_FNS) > 0
    clear_sim_caches()
    assert len(E._TILE_FNS) == 0
    after = E.run_grid(RAGGED_GRID[:10], n_stores=N, tile_cells=16)
    _assert_bit_identical(RAGGED_GRID[:10], after, before, "post-clear")


def test_auto_chunk_heuristic_and_meta():
    # wide regime (n_cells=None or >= 256): capped, divisor-preferring
    assert auto_chunk(50_000, 72) == 40        # largest divisor <= 48
    assert auto_chunk(30_000, 72) == 48        # 48 divides 30000
    assert auto_chunk(1 <<  14, 72) == 32      # 32 divides 2^14
    assert auto_chunk(50_000, 16) == 16        # clamped by SB depth
    assert auto_chunk(10, 72) == 10            # clamped by trace length
    assert auto_chunk(0, 72) == 1
    for n_cells in (1, 8, AUTO_CHUNK_WIDE_CELLS - 1):
        # narrow regime: deepest legal block (scan steps dominate)
        assert auto_chunk(50_000, 72, n_cells) == 72
        assert auto_chunk(50_000, 200, n_cells) == DEFAULT_CHUNK_SIZE
    assert auto_chunk(50_000, 72, AUTO_CHUNK_WIDE_CELLS) == 40

    specs = [ScenarioSpec("ycsb", "proactive")]
    (r,) = simulate_batch(specs, n_stores=N)
    want = {"engine": "blocked", "chunk": auto_chunk(N, 72, 8),
            "auto_chunk": True, "data_plane": "bank"}
    assert want.items() <= r.meta.items()
    assert r.meta["bank_rows"] == 2 and r.meta["h2d_bytes"] > 0
    (r,) = simulate_batch(specs, n_stores=N, chunk_size=7)
    assert {"engine": "blocked", "chunk": 7,
            "auto_chunk": False}.items() <= r.meta.items()
    (r,) = simulate_batch(specs, n_stores=N, chunk_size=0)
    assert r.meta["engine"] == "perstep"
    assert r.meta["data_plane"] == "stacked"
    assert simulate("ycsb", "proactive", n_stores=N).meta == {
        "engine": "serial", "data_plane": "stacked",
        "bank_partition": None}
    # the narrow-SB cell bounds the auto chunk of the whole batch
    (r, _) = simulate_batch([ScenarioSpec("ycsb", "proactive", sb_size=8),
                             ScenarioSpec("ycsb", "wb")], n_stores=N)
    assert r.meta["chunk"] == 8
    # ...but the streaming tier groups by SB, so the wide group keeps
    # its own chunk
    out = E.run_grid([ScenarioSpec("ycsb", "proactive", sb_size=8),
                      ScenarioSpec("ycsb", "wb")], n_stores=N, tile_cells=16)
    assert out[0].meta["chunk"] == 8
    assert out[1].meta["chunk"] == auto_chunk(N, 72, 16)


def test_tier_selection_and_validation():
    small = RAGGED_GRID[:4]
    out = E.simulate_grid(small, n_stores=N)
    assert out[0].meta["engine"] == "blocked"
    out = E.simulate_grid(small, n_stores=N, engine="stream", tile_cells=16)
    assert out[0].meta["engine"] in ("sharded", "streamed")
    out = E.simulate_grid(small, n_stores=N, engine="serial")
    assert out[0].meta["engine"] == "serial"
    assert E.simulate_grid([], n_stores=N) == []
    with pytest.raises(ValueError):
        E.simulate_grid(small, n_stores=N, engine="nosuch")
    with pytest.raises(ValueError):
        E.run_grid(small, n_stores=N, chunk_size=0)   # no per-step tier
    with pytest.raises(ValueError):
        E.run_grid(small, n_stores=N, n_shards=jax.device_count() + 1)
    with pytest.raises(ValueError):
        E.run_grid([ScenarioSpec("ycsb", "nosuch")], n_stores=N)


def test_run_sweep_routes_through_engine():
    from repro.core.scenarios import run_sweep

    specs = RAGGED_GRID[:6]
    got = run_sweep(specs, n_stores=N)
    want = simulate_batch(specs, n_stores=N)
    _assert_bit_identical(specs, got, want, "run_sweep")
    got = run_sweep(specs, n_stores=N, engine="stream", tile_cells=16)
    _assert_bit_identical(specs, got, want, "run_sweep-stream")


def test_stacked_plane_bit_identical_and_observable(blocked_results):
    """The PR-3 stacked plane stays available (``data_plane="stacked"``)
    and bit-identical to the banked default, for both the streaming and
    one-shot tiers; meta + bank_stats() record which plane ran."""
    out = E.run_grid(RAGGED_GRID, n_stores=N, tile_cells=16,
                     data_plane="stacked")
    _assert_bit_identical(RAGGED_GRID, out, blocked_results,
                          "stacked-vs-banked")
    assert out[0].meta["data_plane"] == "stacked"
    assert out[0].meta["bank_rows"] == 0
    stats = E.bank_stats()
    assert stats["data_plane"] == "stacked"
    assert stats["dedup_ratio"] == 1.0
    assert stats["h2d_bytes"] == stats["stacked_h2d_bytes"]

    one_shot = simulate_batch(RAGGED_GRID, n_stores=N, data_plane="stacked")
    _assert_bit_identical(RAGGED_GRID, one_shot, blocked_results,
                          "oneshot-stacked-vs-banked")
    assert one_shot[0].meta["data_plane"] == "stacked"

    with pytest.raises(ValueError):
        E.run_grid(RAGGED_GRID[:2], n_stores=N, data_plane="nosuch")
    with pytest.raises(ValueError):
        simulate_batch(RAGGED_GRID[:2], n_stores=N, data_plane="nosuch")
    with pytest.raises(ValueError):    # the per-step engine has no bank
        simulate_batch(RAGGED_GRID[:2], n_stores=N, chunk_size=0,
                       data_plane="bank")


def test_bank_stats_and_meta_on_banked_run():
    """bank_stats() reports the last run's data-plane accounting --
    MEASURED resident device bytes from the live buffers, sub vs
    replicated -- and the banked plane ships measurably fewer H2D bytes
    than stacking."""
    out = E.run_grid(RAGGED_GRID, n_stores=N, tile_cells=16)
    meta = out[0].meta
    assert meta["data_plane"] == "bank"
    assert meta["bank_partition"] == "sub"
    stats = E.bank_stats()
    n_shards = stats["n_shards"]
    assert stats["cells"] == len(RAGGED_GRID)
    assert stats["bank_partition"] == "sub"
    assert stats["bank_rows"] == stats["trace_rows"] + stats["wv_rows"]
    assert meta["bank_rows"] == stats["bank_rows"] > 0
    assert meta["h2d_bytes"] == stats["h2d_bytes"] > 0
    # dedup: 37 cells share 12 traces / far fewer wv rows than cells
    assert stats["h2d_bytes"] < stats["stacked_h2d_bytes"]
    assert stats["dedup_ratio"] > 1.0
    # measured sub-bank residency: arrivals replicated + one padded
    # copy of each max-plus row fleet-wide. Bound per-shard bytes by
    # arrivals + padded wv share, total by n_shards x that.
    bank = E.get_trace_bank(RAGGED_GRID, N)
    a, w, v, p = bank.sub_bank_host(n_shards)
    per_shard_cap = a.nbytes + (w.nbytes + v.nbytes + p.nbytes) // n_shards
    assert 0 < stats["bank_dev_bytes_per_shard"] <= per_shard_cap
    assert stats["bank_dev_bytes"] == \
        n_shards * a.nbytes + w.nbytes + v.nbytes + p.nbytes
    assert stats["bank_dev_bytes"] < stats["bank_bytes"] * n_shards \
        or n_shards == 1
    # only the arrivals staging replicates over the fabric
    assert stats["bank_fabric_bytes"] == a.nbytes * (n_shards - 1)
    assert stats["dev_mem_hwm_bytes"] >= stats["bank_dev_bytes"]

    # replicated baseline: measured bytes really are ~bank x n_shards
    clear_sim_caches()
    out = E.run_grid(RAGGED_GRID, n_stores=N, tile_cells=16,
                     bank_partition="replicated")
    rep = E.bank_stats()
    assert rep["bank_partition"] == "replicated"
    assert out[0].meta["bank_partition"] == "replicated"
    assert rep["bank_dev_bytes"] == rep["bank_bytes"] * n_shards
    assert rep["bank_dev_bytes_per_shard"] == rep["bank_bytes"]
    assert rep["bank_fabric_bytes"] == rep["bank_bytes"] * (n_shards - 1)
    with pytest.raises(ValueError):
        E.run_grid(RAGGED_GRID[:2], n_stores=N, bank_partition="nosuch")
    with pytest.raises(ValueError):   # partition is a stream-tier knob
        E.simulate_grid(RAGGED_GRID[:2], n_stores=N, engine="blocked",
                        bank_partition="sub")


def test_stream_threshold_routes_large_grids():
    """simulate_grid(auto) must stream at or above the threshold; checked
    via meta on a synthetic just-over-threshold grid of tiny traces."""
    n_cells = E.STREAM_THRESHOLD
    specs = [ScenarioSpec("ycsb", CONFIGS[i % len(CONFIGS)], seed=i % 4)
             for i in range(n_cells)]
    out = E.simulate_grid(specs, n_stores=64, tile_cells=512)
    assert out[0].meta["engine"] in ("sharded", "streamed")
    assert len(out) == n_cells
    # sampled cells against the one-shot blocked path
    sample = [0, 7, n_cells - 1]
    want = simulate_batch([specs[i] for i in sample], n_stores=64)
    for i, w in zip(sample, want):
        assert out[i].exec_time_ns == w.exec_time_ns
