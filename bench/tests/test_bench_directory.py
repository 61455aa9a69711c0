"""The directory-coupled pod's cell on the CPU at small sizes: its plain
reference against the program's sweep and serial oracle, its sharer
census, the lanes it counts, the lower-precision control, a planted
fault, and the cell through ``bench/run.py``."""

import dataclasses
import json

import ml_dtypes
import numpy as np
import pytest

import benchkit
import reference
import reference_directory as ref
import work_directory
from loads import directory_sweeps as load

N = 2000                                  # 15 whole epochs and a ragged one
CFG = json.loads((benchkit.ROOT / "bench" / "configs" /
                  "directory.json").read_text())
K = CFG["directory"]
#: Two bursty workloads and two seeds, one of them above 32 bits; every
#: rule, CN count, replica count, load and SB depth.
AXES = dict(CFG["axes"], workloads=["ycsb", "canneal"])
SEEDS = [3, 2**31 + 5]
TINY = dict(CFG, n_stores=N)


@pytest.fixture(scope="module")
def cells():
    return load.grid(AXES, SEEDS)


@pytest.fixture(scope="module")
def swept(cells):
    from repro.core.scenarios import run_sweep
    from repro.core.simulator import clear_sim_caches

    clear_sim_caches()
    got = run_sweep([load.to_spec(c) for c in cells], n_stores=N,
                    n_shards=1)
    clear_sim_caches()
    return [{f: getattr(r, f) for f in reference.FIELDS} for r in got]


def _strata(cells):
    """One cell of every rule x CN count x load stratum."""
    return load.check_sample(cells, 1, np.random.default_rng(7))


def test_grid_is_the_directory_mega_grid():
    """The configuration's axes give ``scenarios.directory_mega_grid``:
    the same cells in the same order, with the same bank rows."""
    from repro.core.scenarios import directory_mega_grid
    from repro.core.simulator import bank_row_maps

    specs = [load.to_spec(c) for c in load.grid(CFG["axes"], [5, 9])]
    want = directory_mega_grid(seeds=(5, 9))
    assert len(specs) == len(want) == 2592

    def knobs(s):
        return (s.workload, s.config, s.seed, s.n_replicas, s.n_cns,
                s.sb_size, s.directory_load)

    assert [knobs(s) for s in specs] == [knobs(s) for s in want]
    trace_row, wv_row = bank_row_maps(specs)
    assert (len(trace_row), len(wv_row)) == (18, 1080)
    assert (trace_row, wv_row) == bank_row_maps(want)


def test_reference_equals_the_sweep_and_the_oracle(cells, swept):
    """Every answer of the banked sweep, and the program's serial oracle
    on every rule x CN x load stratum, is ``==`` the plain reference on
    every field."""
    from repro.core.simulator import simulate_spec

    want = ref.answers(cells, TINY)
    assert reference.mismatches(swept, want) == 0
    pick = _strata(cells)
    assert len(pick) == 36
    for i in pick:
        r = simulate_spec(load.to_spec(cells[i]), n_stores=N)
        assert {f: getattr(r, f) for f in reference.FIELDS} == want[i], \
            cells[i]


def test_load_zero_is_the_axis_off_cell(cells, swept):
    """At load 0 the reference is ``bench/reference.py``'s answer and
    the program's is its axis-off answer; every replicating rule pays
    the directory at some nonzero load."""
    from repro.core.simulator import simulate_spec

    zero = [i for i, c in enumerate(cells) if c.directory_load == 0.0]
    plain = [reference.Cell(*[getattr(cells[i], f) for f in (
        "workload", "config", "seed", "n_replicas", "link_bw_gbps",
        "n_cns", "sb_size")]) for i in zero]
    assert ref.answers([cells[i] for i in zero], TINY) \
        == reference.answers(plain, TINY)
    assert reference.mismatches([swept[i] for i in zero],
                                reference.answers(plain, TINY)) == 0
    for i in zero[::37]:
        off = simulate_spec(dataclasses.replace(
            load.to_spec(cells[i]), directory_load=None), n_stores=N)
        assert {f: getattr(off, f) for f in reference.FIELDS} == swept[i]
    # the load axis is the innermost: cell i's load-0 twin is i - i % 4
    twin = [swept[i - i % 4]["exec_time_ns"] for i in range(len(cells))]
    slower = {c.config for c, got, t in zip(cells, swept, twin)
              if got["exec_time_ns"] > t}
    assert slower == set(CFG["axes"]["configs"])


@pytest.mark.parametrize("n_cns,n_replicas,pool",
                         [(16, 1, 8), (16, 3, 15), (8, 1, 7), (8, 3, 7),
                          (4, 1, 3), (4, 3, 3)])
def test_census_and_background_load_are_the_programs(n_cns, n_replicas,
                                                     pool):
    """The reference's sharer pool and background utilization are the
    directory's own, clamped to ``n_cns - 1`` peers at 4 CNs."""
    from repro.core.directory import resolve_directory_load, sharer_pool

    assert sharer_pool(n_cns, n_replicas) == pool
    for dl in CFG["axes"]["directory_loads"]:
        c = ref.Cell("ycsb", "baseline", 0, n_replicas, 160.0, n_cns, 72,
                     directory_load=dl)
        assert ref.rho_bg(c, K) == \
            resolve_directory_load(dl, n_cns, n_replicas).rho_bg


def test_semantic_lanes_are_the_engine_lanes(cells):
    """The lanes the semantics count are the lanes the engine scans, on
    the small grid and on the configuration's: 2 160 of 2 592 cells."""
    from repro.core import engine
    from repro.core.simulator import clear_sim_caches

    clear_sim_caches()
    for grid, n in ((cells, 480), (load.grid(CFG["axes"], [5, 9]), 2160)):
        engine.run_grid([load.to_spec(c) for c in grid], n_stores=64,
                        n_shards=1)
        assert engine.bank_stats()["scan_lanes"] == n
        assert work_directory.scan_lanes(grid, K) == n
    clear_sim_caches()


def test_bfloat16_control_differs_in_most_sampled_cells(cells):
    """The reference in bfloat16, put in the program's place, differs
    from the float32 reference in most of a sweep's compared sample."""
    sample = [cells[i] for i in _strata(cells)]
    want = ref.answers(sample, TINY)
    control = ref.answers(sample, TINY, dtype=ml_dtypes.bfloat16)
    assert reference.mismatches(control, want) > len(sample) // 2


def test_a_dropped_epoch_backlog_reads_not_correct(monkeypatch):
    """The program with one epoch's carried backlog dropped from every
    loaded delay row (the epoch that carries the most) fails the cell's
    comparison."""
    from repro.configs.recxl_paper import ClusterConfig
    from repro.core import simulator

    cl = reference.Cluster(CFG["cluster"])
    orig = simulator._directory_delay_row
    dropped = []

    def faulty(arrivals, tx_mask, dirp, cluster, congestion):
        row = orig(arrivals, tx_mask, dirp, cluster, congestion)
        if dirp.rho_bg <= 0.0:
            return row
        lo, hi, q, wq = max(ref.epoch_terms(arrivals, tx_mask, dirp.rho_bg,
                                            cl, K), key=lambda t: t[2])
        if q > 0.0:
            dropped.append(q)
            row = row.copy()
            row[lo:hi] = np.where(tx_mask[lo:hi], wq * congestion, 0.0)
        return row

    simulator.clear_sim_caches()
    monkeypatch.setattr(simulator, "_directory_delay_row", faulty)
    try:
        sweeper = load.Load(dict(TINY, axes=AXES),
                            {"seeds_per_sweep": 2, "check_per_stratum": 1},
                            2**33 + 1, None)
        sweeper.cluster = ClusterConfig(**CFG["cluster"])
        sweeper.sweeps.append(sweeper._sweep(1, *sweeper._plan(1)))
        checks = sweeper.check()
    finally:
        simulator.clear_sim_caches()
    assert dropped
    assert checks["mismatched_cells"][0] > 0
    assert checks["missing_cells"][0] == 0


@pytest.fixture()
def run(monkeypatch, tmp_path):
    """``bench/run.py`` on the CPU with the configurations at 128
    stores; the directory grid keeps all its axes (2 592 cells)."""
    mod = benchkit.load_run()
    orig = mod.load_json
    benchkit.tiny(mod, monkeypatch, tmp_path)      # the CPU, no cache

    def load_json(*parts):
        d = orig(*parts)
        if "configs" in parts:
            d["n_stores"] = 128
        return d

    monkeypatch.setattr(mod, "load_json", load_json)
    return mod


def test_traced_cell_is_correct_and_reads_its_directory_rows(run, capsys):
    res, err = benchkit.run_cell(run, capsys, "directory.fresh", trace=1,
                                 seconds=4.0)
    assert res["correct"] is True and res["failed"] == 0
    assert res["attempted"] % 2592 == 0 and res["attempted"] > 0
    assert all(v["value"] == 0 for v in res["checks"].values())
    line = next(x for x in err.splitlines() if "XLA compiles" in x)
    assert " 0 in the window" in line, line
    assert "2160 scan lanes by the semantics (engine scanned 2160)" in err
    bm = json.loads((benchkit.ROOT / "BENCHMARK.json").read_text())
    # a CPU trace holds no device plane
    want = {m["name"] for m in bm["per_layer"]
            if "directory.fresh" in m.get("workloads", ["directory.fresh"])
            and not m["name"].startswith(("device.", "scan_roofline"))}
    assert "directory.rows_s" in want
    assert set(res["metrics"]) == want
    assert all(m["value"] > 0 for m in res["metrics"].values())
