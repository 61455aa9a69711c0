"""Trace-bank synthesis per sweep: the summed ``bank/synth`` spans of the flight
recorder (the arrivals rows of the bank's traces, ``_trace_cached``),
divided by the sweeps in the traced window."""


def read(run):
    n = run.records.get("sweeps")
    if run.telemetry is None or not n:
        return None
    st = run.telemetry.summary()["spans"].get("bank/synth")
    return st["total"] * 1e-3 / n if st else None
