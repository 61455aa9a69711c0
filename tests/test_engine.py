"""Sharded streaming engine: differential + scheduling tests.

The contract (engine.py module docstring): ``run_grid`` -- tiled,
cell-sharded over the local devices (8 host devices here, set up by
conftest.py), double-buffered -- must be **bit-identical** (``==``) to
the serial ``simulate()`` oracle, for ragged grids whose cell count
divides neither the device count nor the tile size and for grids in
which some shards own no rows, and must reuse one compiled program per
:class:`TileSignature` across all tiles (the compile cache is observed
through ``trace_count()``).
"""

import jax
import pytest

from repro.core import engine as E
from repro.core.scenarios import run_sweep
from repro.core.simulator import (
    AUTO_CHUNK_WIDE_CELLS,
    CONFIGS,
    DEFAULT_CHUNK_SIZE,
    ScenarioSpec,
    auto_chunk,
    clear_sim_caches,
    simulate,
    simulate_spec,
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
def serial_results():
    return [simulate_spec(s, n_stores=N) for s in RAGGED_GRID]


def test_stream_bit_identical_to_blocked_and_serial(serial_results):
    out = E.run_grid(RAGGED_GRID, n_stores=N, tile_cells=16)
    _assert_bit_identical(RAGGED_GRID, out, serial_results,
                          "stream-vs-serial")


def test_stream_single_shard_matches_sharded(serial_results):
    if jax.device_count() < 2:
        pytest.skip("needs >= 2 devices for a sharded run")
    sharded = E.run_grid(RAGGED_GRID, n_stores=N, tile_cells=16,
                         n_shards=min(8, jax.device_count()))
    single = E.run_grid(RAGGED_GRID, n_stores=N, tile_cells=16, n_shards=1)
    _assert_bit_identical(RAGGED_GRID, sharded, single, "sharded-vs-single")
    _assert_bit_identical(RAGGED_GRID, single, serial_results,
                          "single-vs-serial")
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
    _assert_bit_identical(grid, out, [simulate_spec(s, n_stores=N)
                                      for s in grid], "layout")


#: Distinct cells with distinct max-plus rows, so every cell is its own
#: lane and rows spread over the shards round-robin.
SIZE_POOL = [ScenarioSpec(w, c, seed=s, n_replicas=nr)
             for nr in (None, 2) for s in (0, 1)
             for w in ("ycsb", "raytrace", "canneal", "barnes", "ocean_cp")
             for c in CONFIGS]


@pytest.mark.parametrize("n_shards", [1, 8])
@pytest.mark.parametrize("n_cells", [1, 7, 9, 65])
def test_grid_sizes_match_serial(n_cells, n_shards):
    """Grids of 1, 7, 9 and 65 cells at 1 and 8 shards ``==`` the
    serial oracle: at 8 shards the 1- and 7-cell grids leave shards
    with no rows, and 9 and 65 cells leave every tile ragged."""
    if jax.device_count() < n_shards:
        pytest.skip(f"needs {n_shards} devices")
    grid = SIZE_POOL[:n_cells]
    out = E.run_grid(grid, n_stores=N, tile_cells=16, n_shards=n_shards)
    assert E.bank_stats()["n_shards"] == n_shards
    _assert_bit_identical(grid, out, [simulate_spec(s, n_stores=N)
                                      for s in grid], (n_cells, n_shards))


@pytest.mark.parametrize("n_shards", [1, 8])
def test_default_tile_fits_small_grids(serial_results, monkeypatch,
                                       n_shards):
    """Without ``tile_cells`` a grid whose SB groups each fit one default
    tile runs one tile per group, padded to the widest group rather than
    the full default tile; a group wider than the default tile keeps the
    default width. Answers stay ``==`` the serial oracle either way."""
    from repro.core import telemetry as tm
    if jax.device_count() < n_shards:
        pytest.skip(f"needs {n_shards} devices")
    n_groups = len({s.sb_size for s in RAGGED_GRID})
    with tm.recording() as rec:
        out = E.run_grid(RAGGED_GRID, n_stores=N, n_shards=n_shards)
        tiles = rec.summary()["counters"]["engine/tiles"]
    assert tiles == n_groups
    (width,) = {r.meta["tile_cells"] for r in out}
    assert width % 8 == 0 and width < E._default_tile_cells(N)
    assert len(RAGGED_GRID) // n_groups <= width
    for r, s in zip(out, RAGGED_GRID):
        sb = s.sb_size or 72
        assert r.meta["chunk"] == auto_chunk(N, sb, width)
    _assert_bit_identical(RAGGED_GRID, out, serial_results, "narrowed")

    monkeypatch.setattr(E, "_default_tile_cells", lambda n_stores: 16)
    with tm.recording() as rec:
        out = E.run_grid(RAGGED_GRID, n_stores=N, n_shards=n_shards)
        tiles = rec.summary()["counters"]["engine/tiles"]
    assert tiles > n_groups
    assert {r.meta["tile_cells"] for r in out} == {16}
    _assert_bit_identical(RAGGED_GRID, out, serial_results, "default")


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
    (r,) = E.run_grid(specs, n_stores=N, tile_cells=16, n_shards=1)
    want = {"engine": "streamed", "chunk": auto_chunk(N, 72, 16),
            "auto_chunk": True, "data_plane": "bank"}
    assert want.items() <= r.meta.items()
    assert r.meta["bank_rows"] == 2 and r.meta["h2d_bytes"] > 0
    (r,) = E.run_grid(specs, n_stores=N, tile_cells=16, n_shards=1,
                      chunk_size=7)
    assert {"engine": "streamed", "chunk": 7,
            "auto_chunk": False}.items() <= r.meta.items()
    assert simulate("ycsb", "proactive", n_stores=N).meta == {
        "engine": "serial", "data_plane": "stacked",
        "bank_partition": None}
    # the engine groups by SB, so a narrow-SB cell clamps only its own
    # group's chunk and the wide group keeps its own
    out = E.run_grid([ScenarioSpec("ycsb", "proactive", sb_size=8),
                      ScenarioSpec("ycsb", "wb")], n_stores=N, tile_cells=16)
    assert out[0].meta["chunk"] == 8
    assert out[1].meta["chunk"] == auto_chunk(N, 72, 16)


def test_tier_selection_and_validation():
    small = RAGGED_GRID[:4]
    out = run_sweep(small, n_stores=N, tile_cells=16)
    assert out[0].meta["engine"] in ("sharded", "streamed")
    out = run_sweep(small, n_stores=N, engine="stream", tile_cells=16)
    assert out[0].meta["engine"] in ("sharded", "streamed")
    out = run_sweep(small, n_stores=N, engine="serial")
    assert out[0].meta["engine"] == "serial"
    assert run_sweep([], n_stores=N) == []
    with pytest.raises(ValueError):
        run_sweep(small, n_stores=N, engine="nosuch")
    with pytest.raises(ValueError):       # engine knobs are not serial's
        run_sweep(small, n_stores=N, engine="serial", tile_cells=16)
    with pytest.raises(ValueError):
        E.run_grid(small, n_stores=N, n_shards=jax.device_count() + 1)
    with pytest.raises(ValueError):
        E.run_grid([ScenarioSpec("ycsb", "nosuch")], n_stores=N)


@pytest.mark.parametrize("engine", ["blocked", "perstep"])
def test_removed_engines_rejected(engine):
    """The one-shot batch and per-step engines are gone: asking for
    them is an error, not a silent fallback to the stream engine."""
    with pytest.raises(ValueError, match=engine):
        run_sweep(RAGGED_GRID[:2], n_stores=N, engine=engine)


def test_run_sweep_routes_through_engine(serial_results):
    specs = RAGGED_GRID[:6]
    want = serial_results[:6]
    got = run_sweep(specs, n_stores=N, n_shards=1)
    _assert_bit_identical(specs, got, want, "run_sweep")
    got = run_sweep(specs, n_stores=N, engine="stream", tile_cells=16,
                    n_shards=1)
    _assert_bit_identical(specs, got, want, "run_sweep-stream")
    got = run_sweep(specs, n_stores=N, engine="serial")
    _assert_bit_identical(specs, got, want, "run_sweep-serial")


def test_bank_stats_and_meta_on_banked_run():
    """bank_stats() reports the last run's bank accounting -- MEASURED
    resident device bytes from the live buffers, sub vs replicated --
    and the scanned lanes never outnumber the cells."""
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
    # dedup: 37 cells share 12 traces and fewer wv rows than cells
    assert stats["trace_rows"] < stats["wv_rows"] < stats["cells"]
    assert stats["scan_lanes"] <= stats["cells"]
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
    with pytest.raises(ValueError):   # replica sets need the sub layout
        E.run_grid(RAGGED_GRID[:2], n_stores=N,
                   bank_partition="replicated", k_replicas=2)
