"""Engine host pipeline per sweep: the summed ``tile/prep`` spans of
the flight recorder (banked tile prep on the prefetch thread), divided
by the sweeps in the traced window."""


def read(run):
    n = run.records.get("sweeps")
    if run.telemetry is None or not n:
        return None
    prep = run.telemetry.summary()["spans"].get("tile/prep")
    return prep["total"] * 1e-3 / n if prep else None
