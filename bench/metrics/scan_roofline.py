"""Share of its roofline that the banked tile program reaches, in %.

The program is bound by bytes: it reads each scan lane's arrivals,
``w``, ``v`` (f32) and proactive mask (bool) once per store, and does
two adds and two maxima per 13 bytes, far below the chip's ops per
byte. So its least time is the sweeps' lane bytes (``bench/work.py``)
over the chip's HBM bandwidth (``bench/peaks.json``), and the share is
that over the device time of the tile programs' runs in the trace.
The tile program is the anonymous ``jax.jit(run)`` of
``engine._build_bank_tile_fn``, whose module is named ``jit_run``."""

from trace_reduce import peak

MODULE = "jit_run"


def read(run):
    nbytes = run.records.get("scan_bytes")
    if run.trace is None or not nbytes:
        return None
    t = run.trace.module_s(MODULE)
    if t <= 0:
        return None
    bound = nbytes / peak(run.device_kind, "hbm_bytes_per_s")
    return 100.0 * bound / t
