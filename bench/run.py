#!/usr/bin/env python3
"""Benchmark of the ReCXL scenario simulator on the chip.

    python bench/run.py --workload megagrid.fresh --seed 7 --seconds 30 --trace 0

Runs one cell of ``BENCHMARK.json``: the cell names a configuration
(``bench/configs/<name>.json``) and a traffic mix
(``bench/traffic/<name>.json``); the mix names the load that offers it
(``bench/loads/<load>.py``). The load sets up the system under
test, offers the traffic for ``--seconds``, and afterwards compares a
sample of what the timed path answered with the plain reference
(``bench/reference.py``). ``--trace 1`` records the window with the JAX
profiler and the program's flight recorder and reports the cell's
per-layer metrics, each read by ``bench/metrics/<metric>.py``;
``--trace 0`` reports its end-to-end metrics.

The last line of standard output is one JSON object: ``correct``,
``attempted``, ``failed``, ``metrics``, ``device`` (with ``--trace 1``
also ``breakdown``), and last ``checks``, each number compared beside
its limit. The same comparisons are the last lines of standard error.
Without a TPU, or with fewer chips than the cell asks for, the run exits
non-zero before it prints a result.
"""

from __future__ import annotations

import time

PROCESS_T0 = time.perf_counter()

import argparse  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from typing import Dict, List, Optional  # noqa: E402

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
if BENCH not in sys.path:
    sys.path.insert(0, BENCH)


class BenchError(RuntimeError):
    """The benchmark cannot run this cell here."""


def load_json(*parts: str) -> dict:
    path = os.path.join(*parts)
    if not os.path.isfile(path):
        raise BenchError(f"missing {os.path.relpath(path, ROOT)}")
    with open(path) as f:
        return json.load(f)


def load_module(kind: str, name: str):
    """``bench/<kind>/<name>.py``, found by its name."""
    path = os.path.join(BENCH, kind, name + ".py")
    if not os.path.isfile(path):
        raise BenchError(f"missing bench/{kind}/{name}.py")
    spec = importlib.util.spec_from_file_location(
        f"bench_{kind}_{name.replace('.', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def find_cell(bm: dict, workload: str) -> dict:
    for w in bm["workloads"]:
        if w["name"] == workload:
            return w
    raise BenchError(f"no workload {workload!r} in BENCHMARK.json")


def cell_metrics(bm: dict, workload: str, trace: bool) -> List[dict]:
    """The metrics this cell reports: its end-to-end metrics untraced,
    its per-layer metrics traced."""
    group = bm["per_layer"] if trace else bm["end_to_end"]
    return [m for m in group if workload in m.get("workloads", [workload])]


def use_checkout_cache() -> str:
    """JAX's persistent compilation cache in this checkout's own fixed
    directory (``repro.compile_cache``), never one the environment
    names. Call before the first compile."""
    os.environ.pop("JAX_COMPILATION_CACHE_DIR", None)
    src = os.path.join(ROOT, "src")
    if src not in sys.path:
        sys.path.insert(0, src)
    from repro.compile_cache import use_compile_cache

    return use_compile_cache()


def require_devices(n_chips: int):
    """The first ``n_chips`` JAX devices, if they are TPUs; exits
    otherwise (never falls back to another backend)."""
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise SystemExit(f"bench: needs a TPU, JAX found "
                         f"{devs[0].platform!r} devices")
    if len(devs) < n_chips:
        raise SystemExit(f"bench: the cell needs {n_chips} TPU chips, "
                         f"JAX found {len(devs)}")
    return devs[:n_chips]


def memory_peak(devs) -> Optional[int]:
    peaks = [d.memory_stats().get("peak_bytes_in_use")
             for d in devs if d.memory_stats()]
    peaks = [p for p in peaks if p is not None]
    return max(peaks) if peaks else None


class RunView:
    """What a per-layer metric reader sees of a traced run: the cell,
    the load's own records, the flight recorder and the device
    trace."""

    def __init__(self, cell: dict, cfg: dict, records: dict,
                 telemetry, trace, device_kind: str):
        self.cell = cell
        self.cfg = cfg
        self.records = records
        self.telemetry = telemetry
        self.trace = trace
        self.device_kind = device_kind


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def main(argv: Optional[List[str]] = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    if args.seed < 0:
        raise SystemExit("bench: --seed must be >= 0")

    bm = load_json(ROOT, "BENCHMARK.json")
    cell = find_cell(bm, args.workload)
    cfg = load_json(BENCH, "configs", cell["config"] + ".json")
    traffic = load_json(BENCH, "traffic", cell["traffic"] + ".json")
    metrics = cell_metrics(bm, cell["name"], bool(args.trace))
    readers = {m["name"]: load_module("metrics", m["name"])
               for m in metrics} if args.trace else {}
    load_mod = load_module("loads", traffic["load"])

    use_checkout_cache()
    t_import = time.perf_counter()
    devs = require_devices(int(cell["chips"]))
    t_devices = time.perf_counter()

    import jax
    from repro.core import telemetry

    compiles: List[float] = []

    def on_compile(event: str, secs: float, **kw) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            compiles.append(secs)

    from trace_reduce import WINDOW, Trace, breakdown, find_xplane

    jax.monitoring.register_event_duration_secs_listener(on_compile)
    rec = trace = None
    tmp = tempfile.TemporaryDirectory() if args.trace else None
    # a traced run records a window of its own, no longer than the
    # traffic's ``trace_seconds``: the profiler's cost grows with the
    # device operations it records
    window = min(args.seconds, float(traffic.get("trace_seconds",
                                                 args.seconds))) \
        if args.trace else args.seconds
    try:
        load = load_mod.Load(cfg, traffic, args.seed, devs)
        load.setup(window)
        n_setup = len(compiles)
        log(f"bench: set-up phases: imports {t_import - PROCESS_T0:.3f} s, "
            f"devices {t_devices - t_import:.3f} s, load "
            f"{time.perf_counter() - t_devices:.3f} s")
        if args.trace:
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            jax.profiler.start_trace(tmp.name, profiler_options=opts)
            rec = telemetry.enable()
        try:
            setup_s = time.perf_counter() - PROCESS_T0
            log(f"bench: {cell['name']} seed {args.seed}: set-up "
                f"{setup_s:.3f} s, window {window} s")
            with jax.profiler.TraceAnnotation(WINDOW):
                load.measure(window)
        finally:
            if args.trace:
                telemetry.disable()
                t0 = time.perf_counter()
                jax.profiler.stop_trace()
        if args.trace:
            t1 = time.perf_counter()
            xplane = find_xplane(tmp.name)
            trace = Trace.from_file(xplane)
            log(f"bench: trace of {os.path.getsize(xplane)} bytes written "
                f"in {t1 - t0:.3f} s, read in "
                f"{time.perf_counter() - t1:.3f} s")
        log(f"bench: XLA compiles: {n_setup} in set-up "
            f"({sum(compiles[:n_setup]):.3f} s), "
            f"{len(compiles) - n_setup} in the window "
            f"({sum(compiles[n_setup:]):.3f} s)")
    finally:
        jax.monitoring.unregister_event_duration_listener(on_compile)
        if tmp is not None:
            tmp.cleanup()
    load.drain()
    peak = memory_peak(devs)
    outcome = load.outcome()
    load.release()
    checks = load.check()

    values: Dict[str, float] = dict(outcome["metrics"], setup_s=setup_s)
    if args.trace:
        view = RunView(cell, cfg, outcome["records"], rec, trace,
                       devs[0].device_kind)
        values = {}
        for m in metrics:
            v = readers[m["name"]].read(view)
            if v is not None:
                values[m["name"]] = v
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs), "memory_peak_bytes": peak}
    if args.trace:
        device["busy_s"] = trace.busy_s()
        device["window_s"] = trace.window_s
    result = {
        "correct": all(v <= lim for v, lim in checks.values()),
        "attempted": outcome["attempted"],
        "failed": outcome["failed"],
        "metrics": {m["name"]: {"value": values[m["name"]],
                                "unit": m["unit"]}
                    for m in metrics if m["name"] in values},
        "device": device,
    }
    if args.trace:
        t0 = time.perf_counter()
        result["breakdown"] = breakdown(trace)
        log(f"bench: breakdown in {time.perf_counter() - t0:.3f} s")
    result["checks"] = {k: {"value": v, "limit": lim}
                        for k, (v, lim) in checks.items()}
    for k, (v, lim) in checks.items():
        log(f"check {k}: {v} (limit {lim})")
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
