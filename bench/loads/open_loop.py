"""Open-loop queries: independent architects asking a shared
``ScenarioServer`` one "what if" cell at a time.

The universe is the configuration's ``universe`` grid. Set-up loads the
trace bank of the whole universe into the server and compiles its serve
tiles (``ScenarioServer.warm(populate=False)``), then serves the
``warm`` grid, whose lanes are cache hits from then on; every other lane
is a miss, scanned against the resident bank when first asked.

Arrivals do not wait for answers: one generator thread submits every
query at its scheduled time through ``ScenarioServer.submit``, and a
query's latency runs from its scheduled time to its answer, so a stall
also delays the queries behind it.

Traffic parameters (``bench/traffic/<name>.json``):

- ``rate_qps``: mean offered rate over a whole burst period
  (``knee_qps`` and ``load_of_knee`` record where it came from:
  ``bench/knee.py``'s sweep on the chip);
- ``burst_every_s``, ``burst_start_s``, ``burst_s``, ``burst_factor``:
  within every period of ``burst_every_s`` seconds the rate is
  ``burst_factor`` times the base rate from ``burst_start_s`` for
  ``burst_s`` seconds, and the base rate elsewhere;
- ``zipf_s``, ``rotate_every_s``, ``rotate_by``: popularity is Zipf with
  exponent ``zipf_s`` over a seeded permutation of the universe, and the
  permutation rotates by ``rotate_by`` positions every
  ``rotate_every_s`` seconds, so the hot set moves;
- ``drain_s``: how long after the window's close unanswered queries are
  still waited for;
- ``check_hits``, ``check_misses``: answered lane-cache hits and misses
  compared with the reference;
- ``trace_seconds``: the window of a traced run (``bench/run.py``).

Every seed offers the same number of queries in every segment of a
period (the expected count, rounded on the cumulative rate); the seed
draws their times within the segment and the cells they ask for.
"""

from __future__ import annotations

import functools
import math
import sys
import threading
import time
from typing import Dict, List, Tuple

import numpy as np

import reference
from grids import grid, to_spec


def schedule(traffic: dict, seed: int, seconds: float, n_cells: int
             ) -> Tuple[np.ndarray, np.ndarray]:
    """Send times (s from the window's start, sorted) and the universe
    positions asked for, for ``seconds`` of traffic."""
    rate = float(traffic["rate_qps"])
    period = float(traffic["burst_every_s"])
    b0, blen = float(traffic["burst_start_s"]), float(traffic["burst_s"])
    factor = float(traffic["burst_factor"])
    base = rate * period / (period - blen + blen * factor)

    def expected(t: float) -> float:
        full, r = divmod(t, period)
        in_burst = min(max(r - b0, 0.0), blen)
        return base * (full * period + r) \
            + base * (factor - 1.0) * (full * blen + in_burst)

    edges = sorted({0.0, seconds} | {
        x for k in range(int(seconds // period) + 1)
        for x in (k * period, k * period + b0, k * period + b0 + blen)
        if 0.0 < x < seconds})
    rng = np.random.default_rng([seed, 3])
    times: List[np.ndarray] = []
    for a, b in zip(edges[:-1], edges[1:]):
        n = round(expected(b)) - round(expected(a))
        times.append(np.sort(rng.uniform(a, b, n)))
    t = np.concatenate(times) if times else np.zeros(0)
    w = 1.0 / np.arange(1, n_cells + 1, dtype=np.float64) \
        ** float(traffic["zipf_s"])
    ranks = rng.choice(n_cells, size=t.shape[0], p=w / w.sum())
    perm = np.random.default_rng([seed, 4]).permutation(n_cells)
    shift = (t // float(traffic["rotate_every_s"])).astype(np.int64) \
        * int(traffic["rotate_by"])
    return t, perm[(ranks + shift) % n_cells]


def nearest_rank(xs: np.ndarray, q: float) -> float:
    """The ``q`` quantile by nearest rank (``inf`` entries sort last)."""
    xs = np.sort(xs)
    return float(xs[max(0, math.ceil(q * xs.shape[0]) - 1)])


class Load:
    """An open loop of queries against one warmed ``ScenarioServer``."""

    def __init__(self, cfg: dict, traffic: dict, seed: int, devices):
        self.cfg = cfg
        self.traffic = traffic
        self.seed = int(seed)
        self.n_stores = int(cfg["n_stores"])
        self.n_shards = int(cfg["n_shards"])
        self.universe = grid(cfg["universe"], cfg["universe"]["seeds"])

    def setup(self, seconds: float) -> None:
        from repro.configs.recxl_paper import ClusterConfig
        from repro.core.serving import ScenarioServer

        srv_cfg = self.cfg["server"]
        self.srv = ScenarioServer(
            cluster=ClusterConfig(**self.cfg["cluster"]),
            n_stores=self.n_stores, n_shards=self.n_shards,
            batch_cells=int(srv_cfg["batch_cells"]),
            batch_window_ms=float(srv_cfg["batch_window_ms"]),
            row_pad=int(srv_cfg["row_pad"]))
        # the universe's trace bank is resident from the start, and its
        # serve tiles compiled: a miss scans it and compiles nothing
        self.srv.warm([to_spec(c) for c in self.universe], populate=False)
        warm = [to_spec(c) for c in grid(self.cfg["warm"],
                                         self.cfg["warm"]["seeds"])]
        self.srv.warm(warm)
        self.srv.submit(warm[0]).result()     # starts the daemon thread

    def measure(self, seconds: float) -> None:
        from repro.core import engine

        self.seconds = float(seconds)
        self.times, pos = schedule(self.traffic, self.seed, self.seconds,
                                   len(self.universe))
        self.cells = [self.universe[i] for i in pos]
        specs = [to_spec(c) for c in self.cells]
        n = len(specs)
        self.t_sub = np.full(n, np.nan)
        self.t_done = np.full(n, np.nan)
        self.res: List[object] = [None] * n
        self.err: List[object] = [None] * n
        self.pending = n
        self._lock = threading.Lock()
        traces0 = engine.trace_count()
        self.t0 = time.perf_counter()
        gen = threading.Thread(target=self._generate, args=(specs,),
                               name="bench-generator", daemon=True)
        gen.start()
        time.sleep(max(0.0, self.t0 + self.seconds - time.perf_counter()))
        gen.join()
        self.window_traces = engine.trace_count() - traces0

    def _generate(self, specs) -> None:
        for i, spec in enumerate(specs):
            d = self.t0 + self.times[i] - time.perf_counter()
            if d > 0:
                time.sleep(d)
            self.t_sub[i] = time.perf_counter()
            try:
                fut = self.srv.submit(spec)
            except Exception as e:       # refused: an answer never comes
                self._finish(i, None, repr(e))
                continue
            fut.add_done_callback(functools.partial(self._done, i))

    def _done(self, i: int, fut) -> None:
        exc = fut.exception()
        self._finish(i, None if exc else fut.result(),
                     repr(exc) if exc else None)

    def _finish(self, i: int, res, err) -> None:
        self.t_done[i] = time.perf_counter()
        self.res[i], self.err[i] = res, err
        with self._lock:
            self.pending -= 1

    def drain(self) -> None:
        """Wait for the answers still due, at most ``drain_s`` past the
        window's close."""
        end = self.t0 + self.seconds + float(self.traffic["drain_s"])
        while self.pending and time.perf_counter() < end:
            time.sleep(0.01)

    def latencies_ms(self) -> np.ndarray:
        """Scheduled send to answer, per query; ``inf`` for a query that
        failed or was never answered."""
        lat = (self.t_done - (self.t0 + self.times)) * 1e3
        ok = np.array([r is not None for r in self.res], bool)
        return np.where(ok & np.isfinite(lat), lat, np.inf)

    def outcome(self) -> dict:
        lat = self.latencies_ms()
        n = lat.shape[0]
        ok = np.isfinite(lat)
        close = self.t0 + self.seconds
        in_window = int(np.sum(ok & (self.t_done <= close)))
        miss = np.array([r is not None and r.meta["cache"] == "miss"
                         for r in self.res], bool)
        late = (self.t_sub - (self.t0 + self.times)) * 1e3
        late = late[np.isfinite(late)]
        print(f"bench: {n} queries offered, {int(ok.sum())} answered "
              f"({in_window} inside the window), {int(miss.sum())} "
              f"misses; generator late by p50 "
              f"{np.median(late) if late.size else 0.0:.3f} ms, max "
              f"{late.max() if late.size else 0.0:.3f} ms; tile-program "
              f"traces in the window {self.window_traces}",
              file=sys.stderr, flush=True)
        errors = [e for e in self.err if e]
        if errors:
            print(f"bench: {len(errors)} queries failed, the first with "
                  f"{errors[0]}", file=sys.stderr, flush=True)
        metrics = {}
        if n:
            metrics = {"query_p50_ms": nearest_rank(lat, 0.50),
                       "query_p90_ms": nearest_rank(lat, 0.90),
                       "queries_per_s": in_window / self.seconds}
        return {"metrics": metrics, "attempted": n,
                "failed": int(n - ok.sum()),
                "records": {"latency_ms": lat, "miss": miss,
                            "t_sub": self.t_sub - self.t0,
                            "t_done": self.t_done - self.t0}}

    def release(self) -> None:
        """Stop the daemon and drop the program's banks and programs."""
        from repro.core.simulator import clear_sim_caches

        self.srv.close()
        self.srv = None
        clear_sim_caches()

    def check(self) -> Dict[str, tuple]:
        """A seeded sample of the answered hits and misses against the
        reference; a query that failed or was never answered counts as
        unanswered."""
        unanswered = sum(r is None for r in self.res)
        rng = np.random.default_rng([self.seed, 5])
        picked: List[int] = []
        for kind, n_max in (("hit", self.traffic["check_hits"]),
                            ("miss", self.traffic["check_misses"])):
            first: Dict[reference.Cell, int] = {}
            for i, r in enumerate(self.res):
                if r is not None and r.meta["cache"] == kind:
                    first.setdefault(self.cells[i], i)
            idx = list(first.values())
            picked += rng.permutation(idx)[:int(n_max)].tolist()
        t0 = time.perf_counter()
        want = reference.answers([self.cells[i] for i in picked], self.cfg)
        got = [{f: getattr(self.res[i], f) for f in reference.FIELDS}
               for i in picked]
        bad = reference.mismatches(got, want)
        print(f"bench: reference compared {len(picked)} answered queries "
              f"in {time.perf_counter() - t0:.3f} s",
              file=sys.stderr, flush=True)
        return {"mismatched_answers": (bad, 0),
                "unanswered_queries": (unanswered, 0),
                "empty_window": (0 if picked else 1, 0)}
