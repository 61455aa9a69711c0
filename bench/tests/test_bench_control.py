"""What decides ``correct``: the plain reference agrees with the program,
the lower-precision control fails the comparison, and a run whose timed
path is broken underneath comes out not correct."""

import json

import ml_dtypes
import numpy as np
import pytest

import benchkit
import grids
import reference
from loads import sweeps

CFG = json.loads((benchkit.ROOT / "bench" / "configs" /
                  "megagrid.json").read_text())


def _sample(n_stores: int, seed: int):
    cfg = dict(CFG, n_stores=n_stores)
    cells = grids.grid(cfg["axes"], grids.sweep_seeds(seed, 1, 3))
    pick = sweeps.check_sample(cells, 1, np.random.default_rng(seed))
    return cfg, [cells[i] for i in pick]


def test_reference_matches_the_serial_oracle():
    from repro.core.simulator import simulate_spec

    cfg, cells = _sample(1_500, 2**32 + 3)
    want = reference.answers(cells, cfg)
    got = [{f: getattr(simulate_spec(grids.to_spec(c), n_stores=1_500), f)
            for f in reference.FIELDS} for c in cells]
    assert reference.mismatches(got, want) == 0


@pytest.mark.parametrize("seed", [1, 2**31 + 11, 2**33 + 5])
def test_bfloat16_control_fails(seed):
    """The reference in bfloat16, put in the program's place, is not
    correct: the comparison's limit is 0 mismatched answers."""
    cfg, cells = _sample(1_500, seed)
    want = reference.answers(cells, cfg)
    control = reference.answers(cells, cfg, dtype=ml_dtypes.bfloat16)
    assert reference.mismatches(control, want) > len(cells) // 2


def _nudged(finish):
    """``_finish_result`` with each answer's timeline moved by one ulp."""
    def wrapped(cell, exec_ns, *a, **kw):
        return finish(cell, np.nextafter(np.float32(exec_ns),
                                         np.float32(np.inf)), *a, **kw)
    return wrapped


@pytest.fixture()
def run(monkeypatch, tmp_path):
    from repro.core.simulator import clear_sim_caches

    clear_sim_caches()
    mod = benchkit.load_run()
    benchkit.tiny(mod, monkeypatch, tmp_path,
                  traffic={"rate_qps": 20.0, "drain_s": 20.0})
    yield mod
    clear_sim_caches()


def _break(monkeypatch, fault: str) -> str:
    from repro.core import engine, scenarios, serving

    if fault == "answer_altered":
        monkeypatch.setattr(engine, "_finish_result",
                            _nudged(engine._finish_result))
    elif fault == "state_unchanged":
        # the scan returns its initial carry: every commit time 0
        def scan(a, *args, **kw):
            n_b = a.shape[1]
            return (np.zeros(n_b, np.float32), np.zeros(n_b, np.int32),
                    np.zeros(n_b, np.int32))
        monkeypatch.setattr(engine, "_scan_wv", scan)
    elif fault == "half_left_out":
        sweep = scenarios.run_sweep

        def half(specs, **kw):
            got = sweep(specs, **kw)
            return got[:len(got) // 2] + [None] * (len(got) - len(got) // 2)
        monkeypatch.setattr(scenarios, "run_sweep", half)
    elif fault == "served_answer_altered":
        monkeypatch.setattr(serving, "_finish_result",
                            _nudged(serving._finish_result))
        return "daemon.zipf80"
    return "megagrid.fresh"


@pytest.mark.parametrize("fault", ["answer_altered", "state_unchanged",
                                   "half_left_out", "served_answer_altered"])
def test_broken_timed_path_is_not_correct(run, capsys, monkeypatch, fault):
    workload = _break(monkeypatch, fault)
    res, _ = benchkit.run_cell(run, capsys, workload, seconds=1.5)
    assert res["correct"] is False
    assert any(c["value"] > c["limit"] for c in res["checks"].values())
