"""Differential tests: the sweep engine (``run_sweep`` -> ``run_grid``)
vs the serial ``simulate()`` oracle, over a grid spanning every config
and every sensitivity knob.

The contract (simulator.py module docstring): the blocked scan shares
trace synthesis + cost derivation with the serial oracle and applies
identical f32 arithmetic, so the two agree **bit-for-bit**, for every
chunk size including ragged tails and for grids that mix store-buffer
depths (the tile planner gives each depth its own SB-uniform tiles).
The exactness tests below assert ``==``; the older grid tests keep the
(looser) documented 1e-5 band.
"""

import numpy as np
import pytest

from repro.core.engine import run_grid
from repro.core.scenarios import run_sweep
from repro.core.simulator import (
    CONFIGS,
    DEFAULT_CHUNK_SIZE,
    ScenarioSpec,
    geomean_slowdowns,
    simulate,
    slowdown_table,
)

N = 6_000
RTOL = 1e-5
FLOAT_FIELDS = ("exec_time_ns", "repl_at_head_frac", "sb_full_frac",
                "max_log_bytes", "cxl_mem_bw_gbps", "log_dump_bw_gbps")

# every config x a workload spread, plus one cell per sensitivity knob
GRID = (
    [ScenarioSpec(w, c)
     for w in ("ycsb", "raytrace", "ocean_ncp", "streamcluster")
     for c in CONFIGS]
    + [
        ScenarioSpec("canneal", "proactive", seed=7),
        ScenarioSpec("barnes", "proactive", n_replicas=4),
        ScenarioSpec("bodytrack", "baseline", link_bw_gbps=20.0),
        ScenarioSpec("fluidanimate", "proactive", n_cns=4),
        ScenarioSpec("ycsb", "parallel", sb_size=16),
        ScenarioSpec("ocean_cp", "proactive", coalescing=False),
        ScenarioSpec("ycsb", "wt", seed=2),
    ]
)


def _sweep(specs, **kw):
    """One engine sweep at the test size, on one shard."""
    return run_sweep(specs, n_stores=kw.pop("n_stores", N), n_shards=1,
                     tile_cells=16, **kw)


def _serial(spec: ScenarioSpec):
    return simulate(spec.workload, spec.config, n_stores=N, seed=spec.seed,
                    n_replicas=spec.n_replicas,
                    link_bw_gbps=spec.link_bw_gbps, n_cns=spec.n_cns,
                    sb_size=spec.sb_size, coalescing=spec.coalescing)


@pytest.fixture(scope="module")
def batch_results():
    return _sweep(GRID)


def test_batch_matches_serial_on_grid(batch_results):
    assert len(batch_results) == len(GRID)
    for spec, rb in zip(GRID, batch_results):
        rs = _serial(spec)
        assert rb.workload == spec.workload and rb.config == spec.config
        assert rb.n_stores == rs.n_stores == N
        assert rb.n_repl_msgs == rs.n_repl_msgs, spec
        for f in FLOAT_FIELDS:
            a, b = getattr(rs, f), getattr(rb, f)
            np.testing.assert_allclose(b, a, rtol=RTOL, err_msg=f"{spec} {f}")


def test_batch_results_preserve_spec_order(batch_results):
    for spec, r in zip(GRID, batch_results):
        assert (r.workload, r.config) == (spec.workload, spec.config)


def test_batch_deterministic(batch_results):
    again = _sweep(GRID)
    for a, b in zip(batch_results, again):
        assert a.exec_time_ns == b.exec_time_ns
        assert a.repl_at_head_frac == b.repl_at_head_frac


def test_odd_batch_sizes_padded_correctly():
    """Grids smaller than a tile must pad internally without leaking
    padding lanes into the output."""
    specs = [ScenarioSpec("ycsb", "proactive"),
             ScenarioSpec("raytrace", "wb"),
             ScenarioSpec("barnes", "wt", seed=1)]
    out = _sweep(specs)
    assert len(out) == 3
    for spec, rb in zip(specs, out):
        rs = _serial(spec)
        np.testing.assert_allclose(rb.exec_time_ns, rs.exec_time_ns,
                                   rtol=RTOL)


def test_single_cell_batch_matches_serial():
    spec = ScenarioSpec("ocean_ncp", "proactive", sb_size=24)
    (rb,) = _sweep([spec])
    rs = _serial(spec)
    np.testing.assert_allclose(rb.exec_time_ns, rs.exec_time_ns, rtol=RTOL)
    np.testing.assert_allclose(rb.sb_full_frac, rs.sb_full_frac, rtol=RTOL)


def test_empty_batch():
    assert _sweep([]) == []


def test_invalid_specs_rejected():
    with pytest.raises(ValueError):
        _sweep([ScenarioSpec("ycsb", "nosuch")])
    with pytest.raises(ValueError):
        _sweep([ScenarioSpec("nosuch", "wb")])
    with pytest.raises(ValueError):
        _sweep([ScenarioSpec("ycsb", "wb", sb_size=0)])
    with pytest.raises(ValueError):
        _sweep([ScenarioSpec("ycsb", "wb", n_replicas=0)])
    with pytest.raises(ValueError):
        _sweep([ScenarioSpec("ycsb", "wb", n_cns=0)])
    with pytest.raises(ValueError):
        _sweep([ScenarioSpec("ycsb", "wb", link_bw_gbps=0.0)])


# ---------------------------------------------------------------------------
# Blocked-scan differential tests: engine vs serial oracle, bit-identical
# across chunk sizes (ragged tails included)
# ---------------------------------------------------------------------------

# one SB depth -> one tile group; N % 72 != 0 exercises the tail
UNIFORM_GRID = [ScenarioSpec(w, c)
                for w in ("ycsb", "raytrace", "ocean_ncp")
                for c in CONFIGS] + [ScenarioSpec("canneal", "proactive",
                                                  seed=3)]
# mixed SB depths -> one tile group per depth, each clamping the chunk
# to its own depth
MIXED_GRID = UNIFORM_GRID[:6] + [
    ScenarioSpec("ycsb", "parallel", sb_size=16),
    ScenarioSpec("barnes", "proactive", sb_size=24),
    ScenarioSpec("bodytrack", "proactive", n_replicas=4),
]


@pytest.fixture(scope="module")
def serial_by_spec():
    cache = {}

    def get(spec, n=N):
        key = (spec, n)
        if key not in cache:
            cache[key] = simulate(
                spec.workload, spec.config, n_stores=n, seed=spec.seed,
                n_replicas=spec.n_replicas, link_bw_gbps=spec.link_bw_gbps,
                n_cns=spec.n_cns, sb_size=spec.sb_size,
                coalescing=spec.coalescing)
        return cache[key]

    return get


def _assert_bit_identical(specs, batch, oracle, ctx):
    for spec, rb in zip(specs, batch):
        rs = oracle(spec)
        assert rb.n_repl_msgs == rs.n_repl_msgs, (ctx, spec)
        for f in FLOAT_FIELDS:
            assert getattr(rb, f) == getattr(rs, f), (ctx, spec, f)


@pytest.mark.parametrize("chunk", [1, 7, 72, 4 * DEFAULT_CHUNK_SIZE])
def test_uniform_sb_engines_bit_identical(chunk, serial_by_spec):
    """The blocked scan vs serial, ``==``.

    chunk=72 divides nothing evenly at N=6000 (83 blocks + 24-store
    tail); chunk > sb clamps to the SB depth; chunk=1 degenerates to
    per-store blocks.
    """
    out = run_grid(UNIFORM_GRID, n_stores=N, chunk_size=chunk, n_shards=1,
                   tile_cells=16)
    _assert_bit_identical(UNIFORM_GRID, out, serial_by_spec, f"chunk={chunk}")


@pytest.mark.parametrize("chunk", [1, 7, 64])
def test_mixed_sb_engines_bit_identical(chunk, serial_by_spec):
    """Per-cell SB depths (one tile group per depth) vs serial, ``==``."""
    out = run_grid(MIXED_GRID, n_stores=N, chunk_size=chunk, n_shards=1,
                   tile_cells=16)
    _assert_bit_identical(MIXED_GRID, out, serial_by_spec, f"chunk={chunk}")


def test_short_trace_edge_cases(serial_by_spec):
    """n_stores below / barely above the SB depth: the block clamp and
    the tail-only path must still be exact."""
    specs = [ScenarioSpec("ycsb", "proactive"),
             ScenarioSpec("raytrace", "baseline")]
    for n in (50, 100):
        out = _sweep(specs, n_stores=n)
        for spec, rb in zip(specs, out):
            rs = serial_by_spec(spec, n)
            for f in FLOAT_FIELDS:
                assert getattr(rb, f) == getattr(rs, f), (n, spec, f)


def test_blocked_chunk_size_validation():
    with pytest.raises(ValueError):
        _sweep([ScenarioSpec("ycsb", "wb")], chunk_size=-1)


def test_slowdown_table_batched_matches_serial():
    workloads = ("ycsb", "raytrace")
    t_batched = slowdown_table(workloads=workloads, n_stores=N, batched=True)
    t_serial = slowdown_table(workloads=workloads, n_stores=N, batched=False)
    assert set(t_batched) == set(t_serial)
    for w in workloads:
        for c in CONFIGS:
            np.testing.assert_allclose(t_batched[w][c], t_serial[w][c],
                                       rtol=RTOL, err_msg=f"{w}/{c}")
    gm_b = geomean_slowdowns(t_batched)
    gm_s = geomean_slowdowns(t_serial)
    for c in CONFIGS:
        np.testing.assert_allclose(gm_b[c], gm_s[c], rtol=RTOL)
