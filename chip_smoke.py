#!/usr/bin/env python3
"""Smoke run of the simulator's main path on a TPU.

    python chip_smoke.py            # one chip: phase A (sweep), B (serving)
    python chip_smoke.py --chips 4  # four chips: the sharded path only

Phase A sweeps the 12 960-cell mega-grid through ``scenarios.run_sweep``
at its default 50 000 stores per cell (streaming banked tier, sub-bank
partition), cold and then warm, and checks a seeded sample of cells
covering every commit rule and both store-buffer depths against the
serial ``simulate()`` oracle with ``==``.

Phase B starts a ``ScenarioServer`` at its default 50 000 stores, warms
it on the daemon launcher's sweep grid, serves the launcher's mixed
hit/miss stream (60 queries) and checks every answer ``==`` the serial
``simulate()`` oracle.

``--chips 4`` runs the mega-grid on a 4-shard ``cells`` mesh and
compares it ``==`` with one shard in the same process, then loses a
shard mid-grid under ``chaos.inject`` and checks that the recovered run
is ``==`` the fault-free one with no new compiles.

Every phase raises on a failed check. The last line of standard output
is one JSON object naming the device JAX ran on; it is printed only when
every phase passed. Without a TPU the script exits non-zero before any
phase runs.
"""

from __future__ import annotations

import argparse
import json
import math
import os
import sys
import time
from typing import Dict, List, Sequence

ROOT = os.path.dirname(os.path.abspath(__file__))


class SmokeError(RuntimeError):
    """A smoke-phase check failed."""


def require_tpu(n_chips: int):
    """The JAX devices, if they are at least ``n_chips`` TPUs; raises
    ``SystemExit`` otherwise (never carries on on another backend)."""
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise SystemExit(f"chip_smoke: needs a TPU, JAX found "
                         f"{devs[0].platform!r} devices")
    if len(devs) < n_chips:
        raise SystemExit(f"chip_smoke: needs {n_chips} TPU chips, JAX "
                         f"found {len(devs)}")
    return devs


class _CacheEvents:
    """Counts JAX's persistent-compilation-cache hits and misses."""

    def __init__(self):
        self.hits = self.misses = 0

    def __call__(self, event: str, **kwargs) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.misses += 1


def _mismatches(got: Sequence, want: Sequence) -> int:
    if len(got) != len(want):
        raise SmokeError(f"{len(got)} results against {len(want)} expected")
    return sum(g != w for g, w in zip(got, want))


def oracle_sample(specs: Sequence, n_min: int, seed: int,
                  sb_default: int) -> List[int]:
    """Seeded sample of at least ``n_min`` grid positions, the same
    number from every (commit rule, store-buffer depth) stratum."""
    import numpy as np

    strata: Dict[tuple, List[int]] = {}
    for i, s in enumerate(specs):
        sb = s.sb_size if s.sb_size is not None else sb_default
        strata.setdefault((s.config, sb), []).append(i)
    per = math.ceil(n_min / len(strata))
    rng = np.random.default_rng(seed)
    picked: List[int] = []
    for key in sorted(strata):
        members = strata[key]
        picked += rng.choice(members, size=min(per, len(members)),
                             replace=False).tolist()
    return sorted(picked)


def phase_sweep(specs: Sequence, n_stores: int, n_oracle: int = 32,
                seed: int = 0) -> Dict[str, object]:
    """Phase A: ``run_sweep`` cold then warm; a stratified sample ``==``
    the serial oracle. Prints one line per measurement."""
    from repro.configs.recxl_paper import PAPER_CLUSTER
    from repro.core import engine
    from repro.core.scenarios import run_sweep
    from repro.core.simulator import simulate_spec

    out: Dict[str, object] = {"cells": len(specs)}
    results = None
    for run in ("cold", "warm"):
        tc0 = engine.trace_count()
        t0 = time.perf_counter()
        got = run_sweep(specs, n_stores=n_stores)
        wall = time.perf_counter() - t0
        compiles = engine.trace_count() - tc0
        stats = engine.bank_stats()
        print(f"A {run} grid: {len(specs)} cells, {stats['scan_lanes']} "
              f"scan lanes, {compiles} compiled programs, "
              f"{stats['n_shards']} shard(s), engine "
              f"{got[0].meta['engine']}, plane {got[0].meta['data_plane']}"
              f"/{got[0].meta['bank_partition']}")
        print(f"A {run} wall: {wall:.3f} s, {len(specs) / wall:.1f} cells/s")
        print(f"A {run} bank: {stats['bank_dev_bytes']} resident device "
              f"bytes ({stats['bank_dev_bytes_per_shard']} per shard)")
        out[f"{run}_s"] = wall
        out[f"{run}_compiles"] = compiles
        if results is None:
            results = got
        elif _mismatches(got, results):
            raise SmokeError("warm sweep differs from the cold sweep")
    if out["warm_compiles"]:
        raise SmokeError(f"warm sweep compiled {out['warm_compiles']} "
                         f"programs (expected 0)")
    sample = oracle_sample(specs, n_oracle, seed, PAPER_CLUSTER.store_buffer)
    t0 = time.perf_counter()
    bad = _mismatches([results[i] for i in sample],
                      [simulate_spec(specs[i], n_stores=n_stores)
                       for i in sample])
    print(f"A oracle: {len(sample)} sampled cells vs serial simulate(), "
          f"{bad} mismatches ({time.perf_counter() - t0:.1f} s)")
    if bad:
        raise SmokeError(f"{bad} of {len(sample)} sampled cells differ "
                         f"from the serial oracle")
    out["oracle_cells"] = len(sample)
    return out


def phase_serve(n_stores: int, n_queries: int = 60,
                seed: int = 0) -> Dict[str, object]:
    """Phase B: warm a ``ScenarioServer``, serve the launcher's mixed
    stream, check 0 steady-state compiles and ``==`` the serial
    oracle."""
    import numpy as np

    from repro.core.engine import trace_count
    from repro.core.serving import ScenarioServer
    from repro.core.simulator import simulate_spec
    from repro.launch.serve_scenarios import query_stream

    warm_grid, stream = query_stream(n_queries, seed)
    with ScenarioServer(n_stores=n_stores) as srv:
        t0 = time.perf_counter()
        srv.warm(warm_grid)
        t_warm = time.perf_counter() - t0
        print(f"B warm: {len(warm_grid)} cells, "
              f"{srv.stats()['compiled_programs']} programs, "
              f"{t_warm:.3f} s")
        srv.reset_stats()
        tc0 = trace_count()
        lat = []
        t0 = time.perf_counter()
        for spec in stream:
            t1 = time.perf_counter()
            srv.query(spec)
            lat.append(time.perf_counter() - t1)
        wall = time.perf_counter() - t0
        compiles = trace_count() - tc0
        st = srv.stats()
        lat_ms = np.sort(np.asarray(lat)) * 1e3
        p50 = float(lat_ms[len(lat_ms) // 2])
        p99 = float(lat_ms[int(len(lat_ms) * 0.99)])
        print(f"B serve: {len(stream)} queries, p50 {p50:.3f} ms, "
              f"p99 {p99:.3f} ms, {len(stream) / wall:.1f} q/s, "
              f"hit ratio {st['hit_ratio']:.3f}, steady-state compiles "
              f"{compiles}")
        if compiles:
            raise SmokeError(f"serving compiled {compiles} programs in "
                             f"steady state (expected 0)")
        served = srv.query_batch(stream)
    t0 = time.perf_counter()
    by_spec = {s: simulate_spec(s, n_stores=n_stores)
               for s in dict.fromkeys(stream)}
    bad = _mismatches(served, [by_spec[s] for s in stream])
    print(f"B check: {len(stream)} answers vs serial simulate(), "
          f"{bad} mismatches ({time.perf_counter() - t0:.1f} s)")
    if bad:
        raise SmokeError(f"{bad} of {len(stream)} served answers differ "
                         f"from the serial oracle")
    return {"p50_ms": p50, "p99_ms": p99, "qps": len(stream) / wall,
            "hit_ratio": st["hit_ratio"], "compiles": compiles}


def phase_shards(specs: Sequence, n_stores: int,
                 n_shards: int = 4) -> Dict[str, object]:
    """``--chips 4``: the grid on ``n_shards`` sub-banked shards ``==``
    one shard; then a shard loss mid-grid recovers ``==`` with no new
    compiles."""
    from repro.core import chaos, engine

    r1 = engine.run_grid(specs, n_stores=n_stores, n_shards=1)
    per1 = engine.bank_stats()["bank_dev_bytes_per_shard"]
    t0 = time.perf_counter()
    rn = engine.run_grid(specs, n_stores=n_stores, n_shards=n_shards)
    wall = time.perf_counter() - t0
    stats = engine.bank_stats()
    pern = stats["bank_dev_bytes_per_shard"]
    bad = _mismatches(rn, r1)
    print(f"C shards: {len(specs)} cells on {n_shards} shards vs 1 shard, "
          f"{bad} mismatches ({wall:.3f} s, partition "
          f"{stats['bank_partition']})")
    print(f"C bank: {pern} device bytes per shard at {n_shards} shards, "
          f"{per1} at 1 shard, ratio {pern / per1:.4f}")
    if bad:
        raise SmokeError(f"{bad} cells differ between {n_shards} shards "
                         f"and 1 shard")
    if stats["bank_partition"] != "sub" or stats["n_shards"] != n_shards:
        raise SmokeError(f"ran {stats['n_shards']} shards with partition "
                         f"{stats['bank_partition']!r}")

    # armed far out first: the clean pass inside the scope compiles the
    # replica-set layout's programs, then the loss hits the second pass
    lost = n_shards - 1
    with chaos.inject(chaos.ChaosConfig(lose_shard=lost,
                                        lose_at_dispatch=1 << 30)) as cs:
        clean = engine.run_grid(specs, n_stores=n_stores, n_shards=n_shards)
        cs.arm_after(2)
        tc0 = engine.trace_count()
        recovered = engine.run_grid(specs, n_stores=n_stores,
                                    n_shards=n_shards)
        compiles = engine.trace_count() - tc0
    rec = cs.report()["recoveries"]
    bad = _mismatches(recovered, rn) + _mismatches(clean, rn)
    src = rec[0]["source"] if rec else None
    ms = rec[0]["ms"] if rec else float("nan")
    print(f"C chaos: shard {lost} lost, {len(rec)} recoveries from {src} "
          f"in {ms:.1f} ms, {compiles} recompiles, {bad} mismatches vs "
          f"the fault-free run")
    if not rec or bad or compiles:
        raise SmokeError("shard-loss recovery did not match the "
                         "fault-free run without recompiling")
    return {"bytes_ratio": pern / per1, "recovery_ms": ms}


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the four-chip sharded path")
    args = ap.parse_args()

    sys.path.insert(0, os.path.join(ROOT, "src"))
    from repro.compile_cache import use_compile_cache

    devs = require_tpu(args.chips)
    import jax

    cache_dir = use_compile_cache()
    events = _CacheEvents()
    jax.monitoring.register_event_listener(events)
    print(f"device: {devs[0].platform} {devs[0].device_kind} x{len(devs)}, "
          f"jax {jax.__version__}, compile cache {cache_dir}")

    from repro.core.scenarios import mega_grid

    t0 = time.perf_counter()
    if args.chips == 4:
        phase_shards(mega_grid(), n_stores=50_000, n_shards=4)
    else:
        phase_sweep(mega_grid(), n_stores=50_000)
        phase_serve(n_stores=50_000)
    print(f"compile cache: {events.hits} hits, {events.misses} misses; "
          f"total {time.perf_counter() - t0:.1f} s")
    print(json.dumps({"ok": True, "device": {
        "platform": devs[0].platform, "kind": devs[0].device_kind,
        "count": len(devs)}}))


if __name__ == "__main__":
    main()
