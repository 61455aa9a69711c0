"""Drives ``bench/run.py`` on the CPU at tiny sizes for the tests.

The run's look for a chip is replaced by the CPU devices, and the
configurations are cut down as they are loaded; nothing else of the run
changes. JAX's persistent compilation cache stays off: the call that
turns it on is replaced for the test.
"""

from __future__ import annotations

import importlib.util
import json
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
for p in (ROOT / "src", ROOT / "bench"):
    if str(p) not in sys.path:
        sys.path.insert(0, str(p))

#: Small grids: the sweep grid keeps >= 2 048 cells, so ``run_sweep``
#: takes the streaming banked tier it takes on the chip.
SWEEP_AXES = {"seeds": [0, 1, 2], "n_replicas": [1, 3],
              "link_bw_gbps": [160.0, 40.0], "n_cns": [16, 4],
              "sb_sizes": [72, 48]}
UNIVERSE_AXES = {"seeds": [0, 1, 2], "n_replicas": [3],
                 "link_bw_gbps": [160.0, 40.0], "n_cns": [16, 8],
                 "sb_sizes": [72, 48]}


#: The open-loop daemon cell, which the benchmark does not run yet (its
#: window has not been measured on the chip); the tests drive its load.
DAEMON = {
    "config": {"name": "daemon", "source": "https://arxiv.org/abs/2602.08271",
               "file": "bench/configs/daemon.json", "reduced": [],
               "why": "a ScenarioServer holding the mega-grid's bank"},
    "workload": {"name": "daemon.zipf80", "config": "daemon",
                 "traffic": "zipf80", "chips": 1,
                 "why": "open-loop Zipf(0.99) queries with bursts"},
    "end_to_end": [
        {"name": n, "unit": u, "better": b, "bound": 0.25,
         "source": "host_clock", "workloads": ["daemon.zipf80"]}
        for n, u, b in (("query_p50_ms", "ms", "lower"),
                        ("query_p90_ms", "ms", "lower"),
                        ("queries_per_s", "queries/s", "higher"))],
    "per_layer": [
        {"name": n, "unit": u, "better": "lower", "source": src,
         "layer": layer, "moves": "query_p90_ms",
         "workloads": ["daemon.zipf80"]}
        for n, u, src, layer in (
            ("serve.miss_p50_ms", "ms", "host_clock", "daemon"),
            ("serve.scan_ms", "ms", "program_span", "daemon miss path"),
            ("device.idle_share.serve", "%", "device_trace", "device"))],
}


def with_daemon_cell(bm: dict) -> dict:
    """``bm`` with the daemon cell, unless it already has one."""
    if any(w["name"] == "daemon.zipf80" for w in bm["workloads"]):
        return bm
    bm = dict(bm)
    bm["configs"] = bm["configs"] + [DAEMON["config"]]
    bm["workloads"] = bm["workloads"] + [DAEMON["workload"]]
    bm["end_to_end"] = bm["end_to_end"] + DAEMON["end_to_end"]
    bm["per_layer"] = bm["per_layer"] + DAEMON["per_layer"]
    return bm


def load_run():
    spec = importlib.util.spec_from_file_location(
        "bench_run_under_test", ROOT / "bench" / "run.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def tiny(run, monkeypatch, tmp_path, n_stores: int = 128,
         traffic: dict = None):
    """Point ``run`` at the CPU and at cut-down configurations."""
    import jax
    from repro import compile_cache

    orig = run.load_json

    def load(*parts):
        d = orig(*parts)
        if parts[-1] == "BENCHMARK.json":
            d = with_daemon_cell(d)
        if "configs" in parts:
            d["n_stores"] = n_stores
            for key in ("axes", "universe"):
                if key in d:
                    d[key].update(SWEEP_AXES if key == "axes"
                                  else UNIVERSE_AXES)
        if "traffic" in parts and traffic:
            d.update(traffic)
        return d

    monkeypatch.setattr(run, "load_json", load)
    monkeypatch.setattr(run, "require_devices",
                        lambda n: jax.devices("cpu")[:n])
    monkeypatch.setattr(compile_cache, "use_compile_cache",
                        lambda: str(tmp_path / "jc"))


def run_cell(run, capsys, workload: str, seed: int = 11,
             seconds: float = 1.0, trace: int = 0):
    """One run; returns its result line, parsed, and its stderr."""
    assert run.main(["--workload", workload, "--seed", str(seed),
                     "--seconds", str(seconds), "--trace", str(trace)]) == 0
    out, err = capsys.readouterr()
    return json.loads(out.strip().splitlines()[-1]), err
