"""Property tests for the columnar trace-bank data plane.

The bank contract (simulator.py "columnar trace-bank data plane"):
gathering a cell's columns out of the bank must reconstruct its
per-cell inputs **bit-exactly** -- arrivals verbatim, and the host-
precollapsed ``(w, v, pr_nc)`` columns equal to the device
``_blocked_precompute`` of the per-cell arrays -- for arbitrary ragged
mixed-SB grids; and ``clear_sim_caches()`` must drop the bank cache
including its device placements (no leaked device buffers).
"""

import dataclasses
import gc
import weakref

import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

import jax.numpy as jnp

from repro.core import engine as E
from repro.core import simulator as S
from repro.core.simulator import (
    CONFIGS,
    PAPER_CLUSTER,
    ScenarioSpec,
    clear_sim_caches,
    get_trace_bank,
    simulate_spec,
)

N = 700                                 # N % 72 != 0: ragged store tail
WORKLOAD_POOL = ("ycsb", "canneal", "barnes", "raytrace", "ocean_ncp")
FLOAT_FIELDS = ("exec_time_ns", "repl_at_head_frac", "sb_full_frac",
                "max_log_bytes", "cxl_mem_bw_gbps", "log_dump_bw_gbps")


@st.composite
def ragged_grids(draw):
    """Random mixed-SB grids spanning every dedup axis of the bank."""
    n = draw(st.integers(min_value=1, max_value=24))
    specs = []
    for _ in range(n):
        specs.append(ScenarioSpec(
            draw(st.sampled_from(WORKLOAD_POOL)),
            draw(st.sampled_from(CONFIGS)),
            seed=draw(st.integers(min_value=0, max_value=2)),
            n_replicas=draw(st.sampled_from((None, 2, 4))),
            link_bw_gbps=draw(st.sampled_from((None, 40.0))),
            n_cns=draw(st.sampled_from((None, 8))),
            sb_size=draw(st.sampled_from((None, 16, 24))),
            coalescing=draw(st.booleans())))
    return specs


#: Ragged grids for the result-scalar record: WB and WT (coalescing on
#: and off), the three replicating rules over replica / link / CN / SB
#: knobs, the contention axes, and directory loads.
SCALAR_GRIDS = {
    "wb_wt": [ScenarioSpec(w, c, seed=s, n_replicas=nr, n_cns=ncn,
                           coalescing=co)
              for w in ("ycsb", "barnes") for c in ("wb", "wt")
              for s in (0, 2) for nr in (None, 4) for ncn in (None, 4)
              for co in (True, False)],
    "replicating": [ScenarioSpec("canneal", c, seed=s, n_replicas=nr,
                                 link_bw_gbps=bw, n_cns=ncn, sb_size=sb,
                                 coalescing=co)
                    for c in ("baseline", "parallel", "proactive")
                    for s in (0, 1) for nr in (None, 1, 4)
                    for bw in (None, 20.0) for ncn in (None, 2)
                    for sb in (None, 24) for co in (True, False)],
    "contention": [ScenarioSpec(w, c, seed=1, n_replicas=nr, n_cns=ncn,
                                read_share=rs, conflict_rate=cr,
                                consistency_schedule=cs)
                   for w in ("ycsb", "raytrace")
                   for c in ("wb", "wt", "baseline", "proactive")
                   for nr in (None, 2) for ncn in (None, 8)
                   for rs, cr, cs in ((0.3, None, None),
                                      (None, 0.2, "eager"),
                                      (0.5, 0.1, "epoch"))],
    "directory": [ScenarioSpec(w, c, n_replicas=nr, link_bw_gbps=bw,
                               n_cns=ncn, directory_load=dl,
                               coalescing=co)
                  for w in ("ocean_ncp", "barnes") for c in CONFIGS
                  for nr in (None, 3) for bw in (None, 40.0)
                  for ncn in (None, 4) for dl in (0.0, 0.4)
                  for co in (True, False)],
}


@pytest.mark.parametrize("grid", sorted(SCALAR_GRIDS))
def test_cell_scalars_equal_prepared_cell_fields(grid):
    """The bank plane's scalar-only record (``_cell_scalars``) is ``==``
    to the same fields of ``_prepare_cell``'s full inputs, and its
    coalesced-store count agrees with the per-store coalesce array the
    serial oracle scans; the per-trace memo holds one entry per (trace,
    coalescing class)."""
    specs = SCALAR_GRIDS[grid]
    clear_sim_caches()
    scalars = [S._cell_scalars(s, N, PAPER_CLUSTER) for s in specs]
    assert len(S._TRACE_SCALAR_CACHE) == len(
        {(s.workload, s.seed, s.coalescing and s.config != "wt")
         for s in specs})
    assert len(S._CELL_ARRAY_CACHE) == 0
    clear_sim_caches()
    names = [f.name for f in dataclasses.fields(S._CellScalars)]
    for s, sc in zip(specs, scalars):
        cell = S._prepare_cell(
            s, S._trace_cached(s.workload, N, s.seed, PAPER_CLUSTER), N,
            PAPER_CLUSTER)
        for f in names:
            assert getattr(sc, f) == getattr(cell, f), (s, f)
        want_repl = (N - int(cell.coalesce.sum())
                     if s.config in ("baseline", "parallel", "proactive")
                     else 0)
        assert sc.n_repl_msgs == want_repl, s


@settings(max_examples=10, deadline=None)
@given(ragged_grids())
def test_bank_gather_reconstructs_stacked_inputs(specs):
    cells = [S._prepare_cell(
        s, S._trace_cached(s.workload, N, s.seed, PAPER_CLUSTER), N,
        PAPER_CLUSTER) for s in specs]
    # the per-cell inputs stacked time-major (n, B), as the device
    # precompute takes them
    arrivals, coalesce, exposed, t_repl_i, svc_i = (
        np.stack([getattr(c, f) for c in cells], axis=1)
        for f in ("arrivals", "coalesce", "exposed", "t_repl_i", "svc_i"))
    config_idx = np.asarray([S._CONFIG_IDX[c.spec.config] for c in cells],
                            np.int32)
    costs = S._commit_cost_ns("proactive", PAPER_CLUSTER)
    w_dev, v_dev, p_dev = S._blocked_precompute(
        jnp.asarray(coalesce), jnp.asarray(exposed), jnp.asarray(t_repl_i),
        jnp.asarray(svc_i), jnp.asarray(config_idx),
        costs["t_l1"], costs["t_wt"])

    bank = get_trace_bank(specs, N)
    rows = [bank.rows_for(c.spec) for c in cells]
    tr = np.asarray([r[0] for r in rows])
    wv = np.asarray([r[1] for r in rows])

    # arrivals verbatim; w/v/pr_nc: host precollapse == device precompute
    # (the stack is time-major (n, B); bank rows store-contiguous)
    assert np.array_equal(bank.arrivals[tr], arrivals.T)
    assert np.array_equal(bank.w[wv], np.asarray(w_dev).T)
    assert np.array_equal(bank.v[wv], np.asarray(v_dev).T)
    assert np.array_equal(bank.pr_nc[wv], np.asarray(p_dev).T)
    # dedup is real: never more columns than cells, usually far fewer
    assert bank.trace_rows <= len(specs)
    assert bank.wv_rows <= len(specs)


@settings(max_examples=6, deadline=None)
@given(ragged_grids())
def test_banked_engines_match_stacked_on_random_grids(specs):
    """The engine over the bank ``==`` the serial oracle over the
    per-cell arrays, on random ragged grids."""
    got = E.run_grid(specs, n_stores=N, tile_cells=16, n_shards=1)
    for s, b in zip(specs, got):
        want = simulate_spec(s, n_stores=N)
        for f in FLOAT_FIELDS:
            assert getattr(b, f) == getattr(want, f), (b.meta, f)


def test_clear_sim_caches_drops_bank_device_buffers():
    specs = [ScenarioSpec(w, c) for w in WORKLOAD_POOL for c in CONFIGS]
    E.run_grid(specs, n_stores=N, tile_cells=16)      # uploads the bank
    assert len(S._BANK_CACHE) > 0
    bank = get_trace_bank(specs, N)                   # cache hit
    assert bank._device, "engine run should leave the bank device-resident"
    key = next(iter(bank._device))
    entry = bank._device[key]
    # sub placements memoize (rows, arrays); flat placements just arrays
    arrays = entry[1] if isinstance(entry[0], tuple) else entry
    buf_ref = weakref.ref(arrays[0])
    host_ref = weakref.ref(bank)
    del bank, entry, arrays
    clear_sim_caches()
    gc.collect()
    assert len(S._BANK_CACHE) == 0
    assert len(S._WV_ROW_CACHE) == 0
    assert buf_ref() is None, "bank device buffer leaked past cache clear"
    assert host_ref() is None, "bank host columns leaked past cache clear"


@settings(max_examples=10, deadline=None)
@given(ragged_grids(), ragged_grids())
def test_bank_extend_matches_from_scratch_merged_build(base, delta):
    """Append-only extension is byte-identical to a from-scratch build
    of the merged grid (the serving daemon's incremental-diff
    contract): same row maps, same column bytes, old indices intact."""
    bank = S._make_trace_bank(tuple(base), N, PAPER_CLUSTER)
    t0, p0 = bank.trace_rows, bank.wv_rows
    old_rows = {s: bank.rows_for(s) for s in base}
    nt, nw = bank.extend(delta)
    merged = S._make_trace_bank(tuple(base) + tuple(delta), N, PAPER_CLUSTER)
    assert (nt, nw) == (merged.trace_rows - t0, merged.wv_rows - p0)
    assert bank.trace_row == merged.trace_row
    assert bank.wv_row == merged.wv_row
    assert bank.arrivals.tobytes() == merged.arrivals.tobytes()
    assert bank.w.tobytes() == merged.w.tobytes()
    assert bank.v.tobytes() == merged.v.tobytes()
    assert bank.pr_nc.tobytes() == merged.pr_nc.tobytes()
    # indices handed out before the extension stay valid forever
    assert all(bank.rows_for(s) == r for s, r in old_rows.items())
    # idempotent: re-extending with the same specs appends nothing
    assert bank.extend(delta) == (0, 0)
    assert bank.arrivals.tobytes() == merged.arrivals.tobytes()


def test_bank_device_diff_upload_ships_only_new_rows():
    """A resident placement is refreshed incrementally after extend():
    only the appended rows cross host->device, and the refreshed device
    arrays equal the full (merged) host columns."""
    base = [ScenarioSpec("ycsb", c) for c in CONFIGS]
    bank = S._make_trace_bank(tuple(base), N, PAPER_CLUSTER)
    up0, _ = bank.device_args("serve")
    assert up0 == bank.nbytes                       # cold: full upload
    assert bank.device_args("serve")[0] == 0        # resident: no bytes
    nbytes0 = bank.nbytes
    delta = [ScenarioSpec("barnes", "proactive", seed=2),
             ScenarioSpec("ycsb", "proactive", n_replicas=4)]
    nt, nw = bank.extend(delta)
    assert nt == 1 and nw == 2
    up1, dev = bank.device_args("serve")
    assert up1 == bank.nbytes - nbytes0 > 0         # just the diff
    assert np.array_equal(np.asarray(dev[0]), bank.arrivals)
    assert np.array_equal(np.asarray(dev[1]), bank.w)
    assert np.array_equal(np.asarray(dev[2]), bank.v)
    assert np.array_equal(np.asarray(dev[3]), bank.pr_nc)
    assert bank.device_args("serve")[0] == 0        # resident again


def test_wb_wt_rows_collapse_to_constants():
    """Every WB (and WT) cell of a grid shares one constant column."""
    specs = [ScenarioSpec(w, c, seed=s, n_replicas=nr)
             for w in WORKLOAD_POOL for c in ("wb", "wt")
             for s in (0, 1) for nr in (None, 4)]
    bank = get_trace_bank(specs, N)
    assert bank.wv_rows == 2
    rows = {bank.rows_for(s)[1] for s in specs}
    assert len(rows) == 2
    with pytest.raises(KeyError):      # cells outside the build grid
        bank.rows_for(ScenarioSpec("ycsb", "proactive"))
