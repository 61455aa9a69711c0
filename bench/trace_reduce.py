"""From a JAX profiler trace to device busy time, program time and the
breakdown of where the device waited.

The trace is the ``.xplane.pb`` that ``jax.profiler`` writes, read with
``jax.profiler.ProfileData``. Device planes are named ``/device:<KIND>:<n>``;
on each, the line ``XLA Ops`` holds one event per operation run and the
line ``XLA Modules`` one per compiled program run. Host planes
(``/host:...``) hold the host threads' trace events, among them the
benchmark's own ``TraceAnnotation`` spans. The measured window is the
host span named :data:`WINDOW`.
"""

from __future__ import annotations

import glob
import heapq
import json
import os
from collections import defaultdict
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

#: Host span that brackets the measured window in a traced run.
WINDOW = "bench/window"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"

Interval = Tuple[float, float, str]

_PEAKS = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                      "peaks.json")


def peak(device_kind: str, key: str) -> float:
    """A published peak of one chip from ``peaks.json``; a device that
    is not in the table is an error, never a default."""
    with open(_PEAKS) as f:
        table = json.load(f)["devices"]
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"peaks.json (known: {sorted(table)})")
    return float(table[device_kind][key])


def find_xplane(log_dir: str) -> str:
    """The one ``.xplane.pb`` the profiler wrote under ``log_dir``."""
    paths = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if len(paths) != 1:
        raise FileNotFoundError(f"expected one .xplane.pb under {log_dir}, "
                                f"found {len(paths)}")
    return paths[0]


def _events(line) -> List[Interval]:
    return [(float(e.start_ns), float(e.start_ns) + float(e.duration_ns),
             e.name) for e in line.events]


def op_name(hlo: str) -> str:
    """``%fusion.12`` of an op event named by its whole HLO text."""
    return hlo.split(" = ", 1)[0]


def union_length(intervals: Iterable[Interval], lo: float, hi: float
                 ) -> Tuple[float, List[Tuple[float, float]]]:
    """Length of the union of ``intervals`` clipped to ``[lo, hi]``, and
    the merged busy spans."""
    spans = sorted((max(a, lo), min(b, hi)) for a, b, _ in intervals
                   if b > lo and a < hi)
    merged: List[List[float]] = []
    for a, b in spans:
        if merged and a <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], b)
        else:
            merged.append([a, b])
    return sum(b - a for a, b in merged), [(a, b) for a, b in merged]


class Trace:
    """One traced window: device operations and host spans, in ns on
    the profiler's clock."""

    def __init__(self, profile, window: str = WINDOW):
        self.devices: List[Dict[str, List[Interval]]] = []
        self.host: List[Interval] = []
        for plane in profile.planes:
            if plane.name.startswith("/device:"):
                lines = {ln.name: _events(ln) for ln in plane.lines}
                if OPS_LINE in lines or MODULES_LINE in lines:
                    self.devices.append(lines)
            elif plane.name.startswith("/host:"):
                for ln in plane.lines:
                    self.host += _events(ln)
        marks = [(a, b) for a, b, n in self.host if n == window]
        if not marks:
            raise ValueError(f"no host span {window!r} in the trace")
        self.lo, self.hi = marks[0]

    @classmethod
    def from_file(cls, path: str, window: str = WINDOW) -> "Trace":
        from jax.profiler import ProfileData

        return cls(ProfileData.from_file(path), window)

    @property
    def window_s(self) -> float:
        return (self.hi - self.lo) * 1e-9

    def _ops(self, dev: Dict[str, List[Interval]]) -> List[Interval]:
        return dev.get(OPS_LINE) or dev.get(MODULES_LINE, [])

    def busy_s(self) -> float:
        """Seconds in which an operation ran on the device, inside the
        window, averaged over the traced chips."""
        if not self.devices:
            return 0.0
        tot = sum(union_length(self._ops(d), self.lo, self.hi)[0]
                  for d in self.devices)
        return tot / len(self.devices) * 1e-9

    def idle_share(self) -> Optional[float]:
        """``1 - busy / window``; ``None`` where no device was traced."""
        if not self.devices:
            return None
        return 1.0 - self.busy_s() / self.window_s

    def module_s(self, prefix: str) -> float:
        """Device seconds of the compiled programs whose name starts with
        ``prefix`` (their runs inside the window), averaged over chips."""
        if not self.devices:
            return 0.0
        tot = 0.0
        for d in self.devices:
            tot += sum(min(b, self.hi) - max(a, self.lo)
                       for a, b, n in d.get(MODULES_LINE, [])
                       if n.startswith(prefix) and b > self.lo
                       and a < self.hi)
        return tot / len(self.devices) * 1e-9

    def device_ops(self, top: int = 10) -> List[List]:
        """The operations that took most device time, in seconds summed
        over the window and averaged over chips."""
        by: Dict[str, float] = defaultdict(float)
        for d in self.devices:
            for a, b, n in self._ops(d):
                if b > self.lo and a < self.hi:
                    by[op_name(n)] += (min(b, self.hi)
                                       - max(a, self.lo)) * 1e-9
        n_dev = max(len(self.devices), 1)
        ranked = sorted(by.items(), key=lambda kv: -kv[1])[:top]
        return [[name, s / n_dev] for name, s in ranked]

    def idle_gaps(self, top: int = 10) -> List[List]:
        """Device idle time inside the window, summed by what the host
        was doing: the innermost host span that covers the middle of each
        gap (``host idle`` where none does), largest first."""
        if not self.devices:
            return []
        host = sorted((h for h in self.host if h[2] != WINDOW
                       and h[1] > self.lo and h[0] < self.hi),
                      key=lambda h: h[0])
        by: Dict[str, float] = defaultdict(float)
        for d in self.devices:
            _, busy = union_length(self._ops(d), self.lo, self.hi)
            edges = [self.lo] + [x for ab in busy for x in ab] + [self.hi]
            # sweep the gaps in time order: ``live`` holds the host spans
            # that started before the gap's middle, keyed by their end
            live: List[Tuple[float, float, str]] = []
            nxt = 0
            for a, b in zip(edges[0::2], edges[1::2]):
                if b <= a:
                    continue
                mid = 0.5 * (a + b)
                while nxt < len(host) and host[nxt][0] <= mid:
                    h = host[nxt]
                    heapq.heappush(live, (h[1], h[1] - h[0], h[2]))
                    nxt += 1
                while live and live[0][0] < mid:
                    heapq.heappop(live)
                label = min(live, key=lambda h: h[1])[2] if live \
                    else "host idle"
                by[label] += (b - a) * 1e-9
        n_dev = len(self.devices)
        ranked = sorted(by.items(), key=lambda kv: -kv[1])[:top]
        return [[name, s / n_dev] for name, s in ranked]


def breakdown(trace: Trace, top: int = 10) -> Dict[str, Sequence]:
    """The result line's ``breakdown``: top device operations and the
    longest idle gaps by host activity."""
    return {"device_ops": trace.device_ops(top),
            "idle_gaps": trace.idle_gaps(top)}
