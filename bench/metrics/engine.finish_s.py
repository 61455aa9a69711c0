"""Engine result scatter per sweep: the summed ``tile/finish`` spans of
the flight recorder (each drained tile's lane outputs turned into its
member cells' results), divided by the sweeps in the traced window."""


def read(run):
    n = run.records.get("sweeps")
    if run.telemetry is None or not n:
        return None
    st = run.telemetry.summary()["spans"].get("tile/finish")
    return st["total"] * 1e-3 / n if st else None
