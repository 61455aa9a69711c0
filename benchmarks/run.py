"""Benchmark driver: one function per paper table/figure + the framework
and roofline benches. Prints ``name,us_per_call,derived`` CSV and
appends the run to the ``BENCH_protocol.json`` trajectory.

Sections:
  fig2/*        WB vs WT (paper Fig. 2)
  fig10/*       five configurations + geomeans vs paper claims (Fig. 10),
                plus fig10/sweep/* engine wall-clock tracking (serial
                oracle vs PR-1 per-step scan vs blocked scan) and
                fig10/megagrid/* (streaming sharded tier vs one-shot
                blocked on the full sensitivity cross-product)
  fig9/recovery/*  SS VII-E downtime estimates from the batched
                failure-time x node recovery sweep
  fig11..18/*   characterization + sensitivity (Figs. 11-18)
  fig17/contention/*  contention & crash-consistency axes on the
                streaming banked tier (scenarios.contention_mega_grid;
                see benchmarks/bench_contention.py + docs/contention.md)
  fig17/directory/*  queueing-coupled directory model (two-level
                max-plus recurrence): geomean slowdown vs offered load,
                oracle bit-identity and lane dedup on the streaming
                directory mega-grid (benchmarks/bench_directory.py)
  serve/telemetry/*  flight-recorder observability tier
                (repro.core.telemetry): per-stage time breakdown of the
                streaming mega-grid, serving p50/p99 reproduced from
                telemetry histograms, chaos recovery span timeline and
                the telemetry-off/on overhead ratio
                (benchmarks/bench_telemetry.py; docs/observability.md)
  serve/latency/*  scenario-serving daemon (repro.core.serving):
                p50/p99 query latency, throughput, lane-cache hit
                ratio, steady-state compile count (must be 0) and the
                marginal h2d bytes of incremental bank diffs vs a cold
                full-bank upload (benchmarks/bench_serving.py;
                see docs/serving.md)
  framework/*   jitted step wall times per ReCXL variant, Logging-Unit op
                latencies, log-compressor throughput
  roofline/*    per (arch x shape) single-pod roofline terms from the
                dry-run artifacts (see benchmarks/roofline.py; requires
                `python -m repro.launch.dryrun` to have produced
                benchmarks/artifacts/)

``--quick`` (or RECXL_BENCH_QUICK=1) is the CI smoke mode: protocol
benches only, at a reduced store count (including a shrunken megagrid
smoke so the shard_map tier cannot rot).

``--trace`` enables the flight recorder (``repro.core.telemetry``) for
the whole run and appends its merged summary -- per-stage span
histograms, simulated protocol counters, gauges -- to the history entry
as a ``"telemetry"`` key (docs/observability.md); pass
``--trace-out <path.jsonl>`` too to also export the Chrome trace-event
JSONL for Perfetto.

Perf history: every run appends ``{ts, quick, argv, rows}`` to
``benchmarks/BENCH_protocol.json`` (override the path with
``RECXL_BENCH_HISTORY=<path>``, disable with ``RECXL_BENCH_HISTORY=off``),
so engine speedups are comparable across PRs. Row schema in
benchmarks/README.md.
"""

import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))
sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

HISTORY_DEFAULT = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                               "BENCH_protocol.json")


def _load_history(path: str) -> list:
    """Best-effort read of the existing trajectory. A missing, truncated
    or concurrently-rewritten file degrades to an empty/partial list --
    corrupt *entries* (non-dict items from an interrupted writer) are
    skipped with a stderr warning instead of poisoning the append."""
    try:
        with open(path) as f:
            hist = json.load(f)
    except FileNotFoundError:
        return []
    except (OSError, ValueError) as e:
        print(f"# bench history unreadable, restarting ({path}: {e})",
              file=sys.stderr)
        return []
    if not isinstance(hist, list):
        print(f"# bench history malformed (not a list), restarting ({path})",
              file=sys.stderr)
        return []
    kept = [e for e in hist if isinstance(e, dict)]
    if len(kept) != len(hist):
        print(f"# bench history: skipped {len(hist) - len(kept)} corrupt "
              f"entr(ies) in {path}", file=sys.stderr)
    return kept


def append_history(rows, quick: bool, telemetry=None) -> str:
    """Append one run's rows to the JSON trajectory; returns the path
    ('' when disabled or unwritable). The file is a list of run
    entries, oldest first. History is best-effort telemetry: an
    unreadable/corrupt file is restarted, corrupt entries are skipped
    with a warning, and an unwritable path is reported on stderr --
    neither may fail a bench run that already completed. The rewrite
    goes through a same-directory tmp file + ``os.replace`` so a
    concurrent reader (or a crash mid-write) never observes a
    truncated trajectory."""
    path = os.environ.get("RECXL_BENCH_HISTORY", HISTORY_DEFAULT)
    if path.lower() in ("", "0", "off", "none"):
        return ""
    hist = _load_history(path)
    entry = {
        "ts": time.strftime("%Y-%m-%dT%H:%M:%S%z"),
        "quick": quick,
        "argv": sys.argv[1:],
        "rows": rows,
    }
    if telemetry:
        entry["telemetry"] = telemetry
    hist.append(entry)
    tmp = f"{path}.tmp.{os.getpid()}"
    try:
        with open(tmp, "w") as f:
            json.dump(hist, f, indent=1, sort_keys=True, default=str)
            f.write("\n")
        os.replace(tmp, path)
    except OSError as e:
        print(f"# bench history not written ({path}: {e})", file=sys.stderr)
        try:
            os.unlink(tmp)
        except OSError:
            pass
        return ""
    return path


def main() -> None:
    if "--quick" in sys.argv[1:]:
        os.environ["RECXL_BENCH_QUICK"] = "1"
    quick = os.environ.get("RECXL_BENCH_QUICK", "") not in ("", "0")
    from repro.compile_cache import use_compile_cache
    use_compile_cache()
    traced = "--trace" in sys.argv[1:]
    trace_out = None
    if "--trace-out" in sys.argv[1:]:
        traced = True
        trace_out = sys.argv[sys.argv.index("--trace-out") + 1]
    if traced:
        from repro.core import telemetry
        telemetry.enable()

    from benchmarks.bench_chaos import bench_chaos
    from benchmarks.bench_contention import bench_contention
    from benchmarks.bench_directory import bench_directory
    from benchmarks.bench_serving import bench_serving
    from benchmarks.bench_telemetry import bench_telemetry
    from benchmarks.protocol_benches import ALL_PROTOCOL_BENCHES

    benches = list(ALL_PROTOCOL_BENCHES) + [bench_contention,
                                            bench_directory,
                                            bench_serving,
                                            bench_chaos,
                                            bench_telemetry]
    if not quick:
        from benchmarks.framework_benches import ALL_FRAMEWORK_BENCHES
        benches += ALL_FRAMEWORK_BENCHES

    print("name,us_per_call,derived")
    rows = []
    for bench in benches:
        try:
            rows.extend(bench())
        except Exception as e:  # noqa: BLE001
            rows.append({"name": f"ERROR/{bench.__name__}",
                         "us_per_call": 0.0,
                         "derived": f"{type(e).__name__}:{e}"})
    if not quick:
        from benchmarks.roofline import bench_roofline
        try:
            rows.extend(bench_roofline())
        except Exception as e:  # noqa: BLE001
            rows.append({"name": "ERROR/bench_roofline", "us_per_call": 0.0,
                         "derived": f"{type(e).__name__}:{e}"})

    for r in rows:
        extra = f",paper={r['paper_claim']}" if "paper_claim" in r else ""
        derived = str(r["derived"]).replace(",", ";")
        print(f"{r['name']},{r['us_per_call']},{derived}{extra}")

    summ = None
    if traced:
        from repro.core import telemetry
        summ = telemetry.summary()
        if trace_out:
            n = telemetry.export_chrome(trace_out)
            print(f"# wrote {n} trace events to {trace_out}",
                  file=sys.stderr)
    path = append_history(rows, quick, telemetry=summ)
    if path:
        print(f"# appended {len(rows)} rows to {path}", file=sys.stderr)


if __name__ == "__main__":
    main()
