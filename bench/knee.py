#!/usr/bin/env python3
"""One-off sweep of offered rates for an open-loop cell, to find its knee.

    python bench/knee.py --workload daemon.zipf80 --rates 2 4 8 16 --seeds 1 2

For each rate and seed, in one process, a fresh server is set up as
the cell's set-up does and offered the cell's traffic at that mean rate
for ``--seconds``. Printed per rate: queries offered; the share of those
due in the window's first two thirds that were answered by its close
(the last third is left out, so that a latency shorter than it is not
counted as a shortfall); p50 and p90 latency from the scheduled time;
the backlog (queries sent and not yet answered) at each third of the
window; and how late the generator sent a query at worst. A rate is
sustained where that share is at least 0.95 and the backlog at the
close exceeds the larger of the two earlier readings by no more than 5%
of a third of the queries offered; the knee is the highest rate
sustained on every seed. Needs the chip, like
``bench/run.py``.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import run as harness  # noqa: E402


def backlog(t_sub: np.ndarray, t_done: np.ndarray, at: float) -> int:
    return int(np.sum(t_sub <= at) - np.sum(t_done <= at))


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", type=float, nargs="+", required=True)
    ap.add_argument("--seconds", type=float, default=30.0)
    ap.add_argument("--seeds", type=int, nargs="+", default=[1])
    ap.add_argument("--drain", type=float, default=5.0,
                    help="seconds past the close to wait for answers")
    ap.add_argument("--out", help="also write the rows here as JSON")
    args = ap.parse_args()
    bm = harness.load_json(harness.ROOT, "BENCHMARK.json")
    cell = harness.find_cell(bm, args.workload)
    cfg = harness.load_json(harness.BENCH, "configs",
                            cell["config"] + ".json")
    traffic = harness.load_json(harness.BENCH, "traffic",
                                cell["traffic"] + ".json")
    load_mod = harness.load_module("loads", traffic["load"])
    harness.use_checkout_cache()
    devs = harness.require_devices(int(cell["chips"]))
    rows = []
    for rate, seed in ((r, s) for r in args.rates for s in args.seeds):
        load = load_mod.Load(cfg, dict(traffic, rate_qps=rate,
                                       drain_s=args.drain), seed, devs)
        load.setup(args.seconds)
        load.measure(args.seconds)
        load.drain()
        out = load.outcome()
        load.release()
        rec = out["records"]
        lat = rec["latency_ms"]
        s = args.seconds
        due = load.times < s * 2 / 3
        done = np.isfinite(lat) & (rec["t_done"] <= s)
        row = {"rate_qps": rate, "seed": seed, "offered": int(lat.size),
               "answered_share": float(np.sum(done & due)
                                       / max(np.sum(due), 1)),
               "p50_ms": out["metrics"].get("query_p50_ms"),
               "p90_ms": out["metrics"].get("query_p90_ms"),
               "misses": int(rec["miss"].sum()),
               "generator_late_max_ms": float(np.nanmax(
                   (load.t_sub - load.t0 - load.times) * 1e3)),
               "backlog": [backlog(rec["t_sub"], rec["t_done"], s * f)
                           for f in (1 / 3, 2 / 3, 1.0)]}
        # growing: the backlog at the close exceeds the larger of the
        # earlier two by more than 5% of a third of the window's queries
        row["sustained"] = bool(
            row["answered_share"] >= 0.95
            and row["backlog"][2] <= max(row["backlog"][:2])
            + 0.05 * lat.size / 3)
        rows.append(row)
        print(json.dumps(row), flush=True)
    if args.out:
        with open(args.out, "w") as f:
            json.dump(rows, f, indent=1)


if __name__ == "__main__":
    main()
