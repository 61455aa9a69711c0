"""Device idle share over the traced window, in %: one minus the union
of the intervals in which an operation ran on the device, over the
window."""


def read(run):
    if run.trace is None:
        return None
    idle = run.trace.idle_share()
    return None if idle is None else 100.0 * idle
