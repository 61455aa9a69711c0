"""The benchmark harness on the CPU at tiny sizes: its result line, its
refusal of a machine without a TPU, how it finds cells, configurations,
traffic and metrics by name, and the work it counts."""

import json
import os
import shutil
import subprocess
import sys

import pytest

import benchkit

LINE_KEYS = ["correct", "attempted", "failed", "metrics", "device"]
DEVICE_KEYS = {"platform", "kind", "count", "memory_peak_bytes"}


@pytest.fixture()
def run(monkeypatch, tmp_path):
    mod = benchkit.load_run()
    benchkit.tiny(mod, monkeypatch, tmp_path,
                  traffic={"rate_qps": 20.0, "drain_s": 20.0})
    return mod


@pytest.mark.parametrize("workload", ["megagrid.fresh", "daemon.zipf80"])
@pytest.mark.parametrize("trace", [0, 1])
def test_last_line_has_the_contract_keys(run, capsys, workload, trace):
    res, err = benchkit.run_cell(run, capsys, workload, trace=trace,
                                 seconds=1.5)
    keys = LINE_KEYS + (["breakdown"] if trace else []) + ["checks"]
    assert list(res) == keys
    assert res["correct"] is True and res["failed"] == 0
    assert res["attempted"] > 0
    dev = res["device"]
    assert set(dev) == DEVICE_KEYS | ({"busy_s", "window_s"} if trace
                                      else set())
    assert dev["platform"] == "cpu" and dev["count"] == 1
    bm = benchkit.with_daemon_cell(
        json.loads((benchkit.ROOT / "BENCHMARK.json").read_text()))
    group = bm["per_layer"] if trace else bm["end_to_end"]
    want = {m["name"] for m in group
            if workload in m.get("workloads", [workload])}
    # the device trace of a CPU run holds no device plane: only the
    # readers of host records and spans find something
    if trace:
        want = {m for m in want
                if not m.startswith(("device.", "scan_roofline"))}
    assert set(res["metrics"]) == want
    for m in res["metrics"].values():
        assert set(m) == {"value", "unit"} and m["value"] > 0
    # the numbers compared are the last lines of stderr, in order
    tail = err.strip().splitlines()[-len(res["checks"]):]
    assert tail == [f"check {k}: {v['value']} (limit {v['limit']})"
                    for k, v in res["checks"].items()]


@pytest.mark.parametrize("workload", ["megagrid.fresh", "daemon.zipf80"])
def test_window_compiles_nothing(run, capsys, workload):
    """Set-up compiles every program the window runs."""
    _, err = benchkit.run_cell(run, capsys, workload, seconds=1.5)
    line = next(x for x in err.splitlines() if "XLA compiles" in x)
    assert " 0 in the window" in line, line


def test_exits_nonzero_without_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run([sys.executable, "bench/run.py", "--workload",
                        "megagrid.fresh", "--seed", "1", "--seconds", "1"],
                       cwd=benchkit.ROOT, env=env, capture_output=True,
                       text=True, timeout=120)
    assert p.returncode != 0
    assert "needs a TPU" in p.stderr
    assert p.stdout.strip() == ""


def test_new_cell_is_found_by_file_names(run, capsys, monkeypatch,
                                         tmp_path):
    """A configuration, a traffic mix and a per-layer metric added as new
    files, with their entries in BENCHMARK.json, run without an edit to
    any existing file."""
    root = tmp_path / "checkout"
    shutil.copytree(benchkit.ROOT / "bench", root / "bench",
                    ignore=shutil.ignore_patterns("tests", "__pycache__"))
    bm = json.loads((benchkit.ROOT / "BENCHMARK.json").read_text())
    b = root / "bench"
    shutil.copy(b / "configs" / "megagrid.json",
                b / "configs" / "megagrid_b.json")
    (b / "traffic" / "fresh_b.json").write_text(json.dumps(
        {"load": "sweeps", "seeds_per_sweep": 2,
         "check_per_stratum": 1}))
    (b / "metrics" / "bank.build_max_s.py").write_text(
        "def read(run):\n"
        "    xs = run.records.get('bank_build_s')\n"
        "    return max(xs) if xs else None\n")
    bm["configs"].append(dict(bm["configs"][0], name="megagrid_b",
                              file="bench/configs/megagrid_b.json"))
    bm["workloads"].append(dict(bm["workloads"][0], name="megagrid_b.fresh_b",
                                config="megagrid_b", traffic="fresh_b"))
    bm["per_layer"].append(dict(bm["per_layer"][0], name="bank.build_max_s",
                                workloads=["megagrid_b.fresh_b"]))
    (root / "BENCHMARK.json").write_text(json.dumps(bm))
    monkeypatch.setattr(run, "ROOT", str(root))
    monkeypatch.setattr(run, "BENCH", str(b))
    res, _ = benchkit.run_cell(run, capsys, "megagrid_b.fresh_b", trace=1)
    assert res["correct"] is True
    assert res["metrics"]["bank.build_max_s"]["value"] > 0
    assert "bank.build_s" not in res["metrics"]


def test_lane_count_matches_the_engine():
    """The benchmark's own lane count is the engine's scan-lane count."""
    import work
    from grids import grid, to_spec
    from repro.core import engine

    axes = json.loads((benchkit.ROOT / "bench" / "configs" /
                       "megagrid.json").read_text())["axes"]
    axes = dict(axes, workloads=["ycsb", "barnes"], n_replicas=[1, 2, 4])
    cells = grid(axes, [3, 2**31 + 5])
    engine.run_grid([to_spec(c) for c in cells], n_stores=64, n_shards=1)
    assert engine.bank_stats()["scan_lanes"] == work.scan_lanes(cells)
