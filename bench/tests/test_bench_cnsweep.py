"""The ``megagrid.cnsweep`` traffic: CN axes that never repeat within
three sweeps, a plain reference that holds at every CN count it draws,
and the cell through ``bench/run.py``."""

import json

import pytest

import benchkit
import grids
import reference


CNSWEEP = json.loads((benchkit.ROOT / "bench" / "traffic" /
                      "cnsweep.json").read_text())


@pytest.mark.parametrize("seed", [0, 2**31 + 3, 2**33 + 9])
def test_cn_sweeps_never_repeat_a_set_within_three_sweeps(seed):
    from loads import cn_sweeps

    axes = cn_sweeps.cn_axes(seed, 200, [16, 8, 4], CNSWEEP["cn_counts"],
                             CNSWEEP["cns_per_sweep"])
    assert axes[0] == [16, 8, 4]
    assert axes == cn_sweeps.cn_axes(seed, 200, [16, 8, 4],
                                     CNSWEEP["cn_counts"], 3)
    for a in axes[1:]:
        assert len(set(a)) == 3 and set(a) <= set(CNSWEEP["cn_counts"])
    for i in range(len(axes) - 2):
        assert len({frozenset(a) for a in axes[i:i + 3]}) == 3
    assert len({tuple(a) for a in axes}) > 20


def test_reference_matches_the_oracle_at_the_drawn_cn_counts():
    """``bench/reference.py`` agrees with the program at every CN count
    the ``cn_sweeps`` load draws, the uneven work scales among them."""
    from repro.core.simulator import simulate_spec

    cfg = json.loads((benchkit.ROOT / "bench" / "configs" /
                      "megagrid.json").read_text())
    cfg = dict(cfg, n_stores=600)
    cells = [reference.Cell(w, c, 2**31 + 7, 3, 80.0, n, 48)
             for n in CNSWEEP["cn_counts"]
             for w, c in (("ycsb", "proactive"), ("barnes", "baseline"))]
    want = reference.answers(cells, cfg)
    got = [{f: getattr(simulate_spec(grids.to_spec(c), n_stores=600), f)
            for f in reference.FIELDS} for c in cells]
    assert reference.mismatches(got, want) == 0


def test_traced_cnsweep_cell_is_correct_and_compiles_nothing(
        monkeypatch, tmp_path, capsys):
    run = benchkit.load_run()
    benchkit.tiny(run, monkeypatch, tmp_path)
    res, err = benchkit.run_cell(run, capsys, "megagrid.cnsweep", trace=1,
                                 seconds=4.0)
    assert res["correct"] is True and res["failed"] == 0
    assert res["attempted"] > 0
    line = next(x for x in err.splitlines() if "XLA compiles" in x)
    assert " 0 in the window" in line, line
    assert "bank.build_s" in res["metrics"]


def test_chunked_check_counts_every_altered_answer(monkeypatch):
    """The ``cn_sweeps`` check answers a few sweeps at a time and still
    counts an answer moved by one ulp, in every chunk."""
    import numpy as np
    from repro.configs.recxl_paper import ClusterConfig
    from repro.core import engine
    from repro.core.simulator import clear_sim_caches
    from loads import cn_sweeps

    cfg = json.loads((benchkit.ROOT / "bench" / "configs" /
                      "megagrid.json").read_text())
    cfg = dict(cfg, n_stores=96, axes=dict(
        cfg["axes"], workloads=["ycsb", "barnes"], link_bw_gbps=[40.0],
        n_replicas=[3]))
    finish = engine._finish_result

    def nudged(cell, exec_ns, *a, **kw):
        return finish(cell, np.nextafter(np.float32(exec_ns),
                                         np.float32(np.inf)), *a, **kw)

    monkeypatch.setattr(cn_sweeps, "CHECK_SWEEPS", 2)
    clear_sim_caches()
    load = cn_sweeps.Load(cfg, CNSWEEP, 2**31 + 17, None)
    load.cluster = ClusterConfig(**cfg["cluster"])
    load.sweeps = [load._sweep(k, *load._plan(k)) for k in range(3)]
    assert load.check()["mismatched_cells"][0] == 0
    monkeypatch.setattr(engine, "_finish_result", nudged)
    load.sweeps = [load._sweep(k, *load._plan(k)) for k in range(3, 6)]
    clear_sim_caches()
    checks = load.check()
    assert checks["mismatched_cells"][0] == 3 * 20
    assert checks["missing_cells"][0] == 0
