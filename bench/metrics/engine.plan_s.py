"""Engine planning per sweep: the summed ``engine/plan`` spans of the
flight recorder (spec validation, lane dedup, bank row maps and tile
plan in ``run_grid``, before its dispatch loop), divided by the sweeps
in the traced window."""


def read(run):
    n = run.records.get("sweeps")
    if run.telemetry is None or not n:
        return None
    st = run.telemetry.summary()["spans"].get("engine/plan")
    return st["total"] * 1e-3 / n if st else None
