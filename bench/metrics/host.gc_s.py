"""Python's cyclic garbage collector per sweep: the summed ``host/gc``
spans of the flight recorder (one per collection on a recording
thread), divided by the sweeps in the traced window."""


def read(run):
    n = run.records.get("sweeps")
    if run.telemetry is None or not n:
        return None
    st = run.telemetry.summary()["spans"].get("host/gc")
    return st["total"] * 1e-3 / n if st else None
