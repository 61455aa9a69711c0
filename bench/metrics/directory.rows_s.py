"""Directory delay rows per sweep: the summed ``directory/rows`` spans
of the flight recorder (each nonzero row of
``simulator._directory_delay_row`` built for the bank's extra plane,
inside ``bank/rows``), divided by the sweeps in the traced window. A
program without the span gives nothing."""


def read(run):
    n = run.records.get("sweeps")
    if run.telemetry is None or not n:
        return None
    st = run.telemetry.summary()["spans"].get("directory/rows")
    return st["total"] * 1e-3 / n if st else None
