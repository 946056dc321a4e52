"""The one traffic generator. A traffic mix is a data file
(``bench/traffic/<name>.json``) that this module reads; the configuration's
``System`` turns each item the generator draws into real work.

Two loops:

- ``"closed"``: one caller, sending its next job when the previous one
  returned. Each job takes a substitution parameter drawn
  without replacement from ``[low, high]`` (a query's parameter, say), so
  no two jobs of a run are the same work; ``warmup`` of the values run
  before the window and never inside it. A window that would need more
  jobs than there are values fails rather than repeat one.
- ``"open"``: requests sent on a schedule, whether or not earlier ones have
  finished. ``rate_per_s`` × the window's seconds requests, with Poisson
  gaps and log-uniform sizes over ``[size_low, size_high]``. Every seed
  sends the same set of gaps and sizes (their quantiles), in its own order
  and with its own data, so seeds differ in content and not in load.
  Warm-up sends one request of each size in ``warmup_sizes``, one at a
  time. After the window the generator waits up to ``drain_s`` for the
  answers still out; an answer that comes late is late, not lost.

Every job or request becomes an ``Outcome`` with its scheduled, sent and
done times on ``time.perf_counter``.
"""
from __future__ import annotations

import dataclasses
import time
from typing import Any, List, Optional

import numpy as np


class WindowExhausted(RuntimeError):
    """The window needed more distinct jobs than the traffic provides."""


@dataclasses.dataclass
class Outcome:
    item: Any
    scheduled: float
    sent: float
    done: Optional[float] = None
    output: Any = None
    error: Optional[BaseException] = None
    in_window: bool = True


@dataclasses.dataclass
class Window:
    t0: float                 # perf_counter at the window's start
    t1: float                 # perf_counter at its end (seconds later)
    wall0: float              # the same instants on the wall clock
    wall1: float              # wall clock when the last outcome was in
    outcomes: List[Outcome]

    @property
    def attempted(self) -> int:
        return len(self.outcomes)

    @property
    def failed(self) -> int:
        return sum(o.error is not None or o.done is None
                   for o in self.outcomes)


def _stratified(n: int, rng: np.random.Generator) -> np.ndarray:
    """n quantile levels, one per stratum, in the seed's order."""
    return rng.permutation((np.arange(n) + 0.5) / n)


class Generator:
    def __init__(self, traffic: dict, seed: int, system):
        self.traffic = traffic
        self.system = system
        self.loop = traffic["loop"]
        if self.loop not in ("closed", "open"):
            raise ValueError(f"unknown loop {self.loop!r}")
        # one stream for the schedule, one for the items' content
        ss = np.random.SeedSequence(seed)
        self.rng_order, self.rng_items = (np.random.default_rng(s)
                                          for s in ss.spawn(2))
        self.warm: list = []
        self.items: list = []
        self.gaps = None

    # -- set-up ---------------------------------------------------------
    def prepare(self, seconds: float) -> None:
        t = self.traffic
        if self.loop == "closed":
            values = self.rng_order.permutation(
                np.arange(t["low"], t["high"] + 1))
            self.warm = [self.system.prepare(int(v), self.rng_items)
                         for v in values[:t["warmup"]]]
            self.items = [self.system.prepare(int(v), self.rng_items)
                          for v in values[t["warmup"]:]]
            return
        n = max(int(round(t["rate_per_s"] * seconds)), 1)
        self.gaps = -np.log1p(-_stratified(n, self.rng_order)) / t["rate_per_s"]
        lo, hi = np.log(t["size_low"]), np.log(t["size_high"])
        sizes = np.exp(lo + _stratified(n, self.rng_order) * (hi - lo))
        sizes = np.clip(np.round(sizes), t["size_low"], t["size_high"])
        self.warm = [self.system.prepare(int(s), self.rng_items)
                     for s in t["warmup_sizes"]]
        self.items = [self.system.prepare(int(s), self.rng_items)
                      for s in sizes]

    def warm_up(self) -> None:
        for item in self.warm:
            if self.loop == "closed":
                self.system.run(item)
            else:
                self.system.submit(item).wait(self.traffic["drain_s"])

    # -- the window -----------------------------------------------------
    def measure(self, seconds: float) -> Window:
        self.system.begin_window()
        if self.loop == "closed":
            return self._closed(seconds)
        return self._open(seconds)

    def _closed(self, seconds: float) -> Window:
        outcomes: List[Outcome] = []
        pool = iter(self.items)
        wall0, t0 = time.time(), time.perf_counter()
        end = t0 + seconds
        while time.perf_counter() < end:
            item = next(pool, None)
            if item is None:
                raise WindowExhausted(
                    f"the window needs more than {len(self.items)} distinct "
                    "jobs; none is repeated")
            o = Outcome(item, time.perf_counter(), time.perf_counter())
            try:
                o.output = self.system.run(item)
                o.done = time.perf_counter()
            except Exception as e:  # noqa: BLE001 — counted as failed
                o.error = e
            o.in_window = o.done is not None and o.done <= end
            outcomes.append(o)
        return Window(t0, end, wall0, time.time(), outcomes)

    def _open(self, seconds: float) -> Window:
        sched = np.cumsum(self.gaps) - self.gaps[0]
        outcomes: List[Outcome] = []
        pending = []
        wall0, t0 = time.time(), time.perf_counter()
        for item, at in zip(self.items, sched):
            due = t0 + float(at)
            while True:
                now = time.perf_counter()
                if now >= due:
                    break
                time.sleep(min(due - now, 0.005))
            o = Outcome(item, due, time.perf_counter())
            try:
                pending.append((o, self.system.submit(item)))
            except Exception as e:  # noqa: BLE001 — refused at the door
                o.error = e
            outcomes.append(o)
        deadline = max(time.perf_counter(), t0 + seconds) \
            + self.traffic["drain_s"]
        for o, p in pending:
            try:
                o.done, o.output = p.wait(max(deadline - time.perf_counter(),
                                              0.0))
            except Exception as e:  # noqa: BLE001 — failed or never came
                o.error = e
        return Window(t0, t0 + seconds, wall0, time.time(), outcomes)
