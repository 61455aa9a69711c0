"""Trace-bank max-plus rows per sweep: the summed ``bank/rows`` spans of
the flight recorder (each unique row through the ``_wv_row`` memo,
collapsed by ``_make_wv_row`` on a miss), divided by the sweeps in the
traced window."""


def read(run):
    n = run.records.get("sweeps")
    if run.telemetry is None or not n:
        return None
    st = run.telemetry.summary()["spans"].get("bank/rows")
    return st["total"] * 1e-3 / n if st else None
