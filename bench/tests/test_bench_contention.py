"""The contended pod's cell on the CPU at tiny sizes: its plain reference
against the program's oracle and sweep, its sharer pools, the lanes it
counts, the lower-precision control, and the cell through
``bench/run.py``."""

import json

import ml_dtypes
import pytest

import benchkit
import reference
import reference_contention as ref
import work_contention
from loads import contended_sweeps as load

N = 200                                   # N % 72 != 0: ragged SB tail
CFG = json.loads((benchkit.ROOT / "bench" / "configs" /
                  "contention.json").read_text())
#: Two workloads and two seeds, one of them above 32 bits; every
#: contention corner, both CN counts and both replica counts.
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
                    engine="stream", n_shards=1)
    return [{f: getattr(r, f) for f in reference.FIELDS} for r in got]


def test_grid_is_the_contention_mega_grid():
    """The configuration's axes give ``scenarios.contention_mega_grid``:
    the same cells in the same order, with the same bank rows."""
    from repro.core.scenarios import contention_mega_grid
    from repro.core.simulator import bank_row_maps

    specs = [load.to_spec(c) for c in load.grid(CFG["axes"], [5, 9])]
    want = contention_mega_grid(seeds=(5, 9))
    assert len(specs) == len(want) == 2592

    def knobs(s):
        return (s.workload, s.config, s.seed, s.n_replicas, s.n_cns,
                s.read_share, s.conflict_rate, s.consistency_schedule)

    assert [knobs(s) for s in specs] == [knobs(s) for s in want]
    trace_row, wv_row = bank_row_maps(specs)
    assert (len(trace_row), len(wv_row)) == (18, 973)
    assert (trace_row, wv_row) == bank_row_maps(want)


def test_reference_equals_the_sweep_and_the_oracle(cells, swept):
    """Every answer of the banked sweep, and of the program's serial
    contention oracle, is ``==`` the plain reference on every field."""
    from repro.configs.recxl_paper import PAPER_CLUSTER
    from repro.core.contention import serial_oracle

    want = ref.answers(cells, TINY)
    assert reference.mismatches(swept, want) == 0
    for i, c in enumerate(cells):
        if c.seed != SEEDS[1] or c.n_cns != 8:
            continue
        r = serial_oracle(load.to_spec(c), N, PAPER_CLUSTER)
        assert {f: getattr(r, f) for f in reference.FIELDS} == want[i], c


@pytest.mark.parametrize("n_cns,n_replicas", [(16, 1), (16, 3), (8, 1),
                                              (8, 3)])
def test_sharer_pools_are_the_directory_census(n_cns, n_replicas):
    from repro.core.directory import sharer_pool

    got = ref.sharer_pool(n_cns, n_replicas,
                          CFG["contention"]["dir_buckets"])
    assert got == sharer_pool(n_cns, n_replicas)
    assert got == {(16, 1): 8, (16, 3): 15, (8, 1): 7,
                   (8, 3): 7}[n_cns, n_replicas]


def test_semantic_lanes_are_the_engine_lanes_less_zero_delay_rows(cells):
    """The lanes counted by the semantics are the engine's scan lanes
    less those of rows with conflict rate 0 and a read share, whose
    delay rows are zero; and no fewer than the lanes of distinct
    contents in the engine's bank."""
    from repro.configs.recxl_paper import PAPER_CLUSTER
    from repro.core import engine
    from repro.core.simulator import _plane_keys, get_trace_bank

    specs = [load.to_spec(c) for c in cells]
    engine.run_grid(specs, n_stores=N, n_shards=1)
    bank = get_trace_bank(specs, N, PAPER_CLUSTER)
    lanes, zero_delay, contents = set(), set(), set()
    for c, s in zip(cells, specs):
        tk, wk = _plane_keys(s, PAPER_CLUSTER)
        lane = (c.sb_size, bank.trace_row[tk], bank.wv_row[wk])
        lanes.add(lane)
        if c.config == "proactive" and c.conflict_rate == 0.0 \
                and c.read_share > 0.0:
            zero_delay.add(lane)
        r = bank.wv_row[wk]
        contents.add((c.sb_size, bank.trace_row[tk], bank.w[r].tobytes(),
                      bank.v[r].tobytes(), bank.pr_nc[r].tobytes()))
    assert engine.bank_stats()["scan_lanes"] == len(lanes)
    semantic = work_contention.scan_lanes(cells, CFG["contention"])
    assert semantic == len(lanes) - len(zero_delay)
    assert semantic >= len(contents)
    # at the configuration's axes: 990 engine lanes, 216 of zero delay
    full = load.grid(CFG["axes"], [5, 9])
    assert work_contention.scan_lanes(full, CFG["contention"]) == 774


def test_lower_precision_and_the_uncontended_reference_differ(cells):
    """On contended proactive cells the float32 limit of 0 tells the
    reference from its bfloat16 control and from the reference without
    contention semantics."""
    hot = [c for c in cells if c.config == "proactive"
           and (c.conflict_rate > 0.0 or c.schedule != "lazy")]
    want = ref.answers(hot, TINY)
    assert reference.mismatches(
        ref.answers(hot, TINY, dtype=ml_dtypes.bfloat16), want) > 0
    plain = reference.answers(
        [reference.Cell(c.workload, c.config, c.seed, c.n_replicas,
                        c.link_bw_gbps, c.n_cns, c.sb_size) for c in hot],
        TINY)
    assert reference.mismatches(plain, want) > 0


def test_conflict_draws_follow_the_rate():
    """Hot episodes cover about the conflict rate of the stores, and
    nothing is drawn outside them."""
    k = CFG["contention"]
    retries, sharers = ref.conflict_draws(20000, 7, 0.5, 0.6, 15, k)
    hot = (retries > 0) | (sharers > 0)
    assert 0.3 < hot.mean() < 0.6
    assert sharers.max() <= 15
    r0, s0 = ref.conflict_draws(20000, 7, 0.0, 0.6, 15, k)
    assert not r0.any() and not s0.any()


@pytest.fixture()
def run(monkeypatch, tmp_path):
    """``bench/run.py`` on the CPU with the configurations at 128
    stores; the contended grid keeps all its axes (2 592 cells, the
    streaming banked tier it takes on the chip)."""
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


@pytest.mark.parametrize("trace", [0, 1])
def test_cell_runs_correct_and_compiles_nothing_in_the_window(
        run, capsys, trace):
    res, err = benchkit.run_cell(run, capsys, "contention.fresh",
                                 trace=trace, seconds=1.5)
    assert res["correct"] is True and res["failed"] == 0
    assert res["attempted"] % 2592 == 0 and res["attempted"] > 0
    assert all(v["value"] == 0 for v in res["checks"].values())
    line = next(x for x in err.splitlines() if "XLA compiles" in x)
    assert " 0 in the window" in line, line
    assert "774 scan lanes by the semantics (engine scanned 990)" in err
    bm = json.loads((benchkit.ROOT / "BENCHMARK.json").read_text())
    group = bm["per_layer"] if trace else bm["end_to_end"]
    want = {m["name"] for m in group
            if "contention.fresh" in m.get("workloads",
                                           ["contention.fresh"])}
    # a CPU trace holds no device plane
    if trace:
        want = {m for m in want
                if not m.startswith(("device.", "scan_roofline"))}
        assert "contention.rows_s" in want
    assert set(res["metrics"]) == want
    assert all(m["value"] > 0 for m in res["metrics"].values())
