"""Median ``serve/scan`` span of the flight recorder: the miss-lane
scan of one flush (tile planning, index upload, tile program and the
read-back of its outputs)."""

import statistics


def read(run):
    if run.telemetry is None:
        return None
    ev = run.telemetry.span_events("serve/scan")
    opened, durs = {}, []
    for ph, t, name, tid in ev:
        if name != "serve/scan":
            continue
        if ph == "B":
            opened[tid] = t
        elif tid in opened:
            durs.append((t - opened.pop(tid)) * 1e-6)
    return statistics.median(durs) if durs else None
