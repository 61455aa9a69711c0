"""Contention delay rows per sweep: the summed ``contention/rows`` spans
of the flight recorder (each delay row built on a miss of the
``contention.contention_arrays`` memo, inside ``bank/rows``), divided
by the sweeps in the traced window. A program without the span gives
nothing."""


def read(run):
    n = run.records.get("sweeps")
    if run.telemetry is None or not n:
        return None
    st = run.telemetry.summary()["spans"].get("contention/rows")
    return st["total"] * 1e-3 / n if st else None
