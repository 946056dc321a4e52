"""From a profiler trace to the benchmark's device numbers.

A traced run records the measured window with the JAX profiler (the Python
tracer off, so the host pays little for it), reads the ``.xplane.pb`` back
with nothing but JAX, and keeps a small neutral form of it: the profile's
start on the wall clock, and for each device plane the events of its op
and module lines, ``[name, start_ns, duration_ns]`` with the start counted
from the profile's start. The reduction below works on that form only, so it can be
checked on a recorded trace (``bench/tests/fixtures``).

What it gives:

- device busy time: the union of the intervals in which an XLA op ran on a
  device, inside the window, averaged over the devices used;
- the time of named XLA modules (a jitted function's whole program);
- the device operations that took most time, and the longest idle gaps,
  each named by a function of the gap's midpoint on the wall clock (the
  caller names them by what the host was doing then).
"""
from __future__ import annotations

import bisect
import contextlib
import glob
import os
import re
from typing import Callable, Dict, List, Optional, Sequence, Tuple

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"

Interval = Tuple[float, float]


@contextlib.contextmanager
def capture(log_dir: str):
    """Trace the body with the JAX profiler; the Python tracer stays off
    (it would time every Python call of a host-bound program)."""
    import jax

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    jax.profiler.start_trace(log_dir, profiler_options=opts)
    try:
        yield
    finally:
        jax.profiler.stop_trace()


def load(log_dir: str) -> Dict:
    """The neutral form of the newest trace under `log_dir`."""
    from jax._src.profiler import ProfileData

    paths = glob.glob(os.path.join(log_dir, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    data = ProfileData.from_file(max(paths, key=os.path.getmtime))
    start_ns = None
    planes = []
    for plane in data.planes:
        if plane.name == "Task Environment":
            stats = dict(plane.stats)
            start_ns = int(stats["profile_start_time"])
            continue
        if not DEVICE_PLANE.match(plane.name):
            continue
        lines = [{"name": line.name,
                  "events": [[e.name, float(e.start_ns), float(e.duration_ns)]
                             for e in line.events]}
                 for line in plane.lines
                 if line.name in (OPS_LINE, MODULES_LINE)]
        planes.append({"name": plane.name, "lines": lines})
    if start_ns is None:
        raise ValueError("trace has no profile start time")
    return {"profile_start_ns": start_ns, "planes": planes}


def _union(intervals: Sequence[Interval]) -> List[Interval]:
    out: List[Interval] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def module_name(event_name: str) -> str:
    """``jit_groupby_aggregate(1280657...)`` -> ``groupby_aggregate``."""
    name = event_name.split("(", 1)[0]
    return name[4:] if name.startswith("jit_") else name


def op_name(event_name: str) -> str:
    """``%compact.5 = s32[...] custom-call(...)`` -> ``compact.5``."""
    return event_name.split(" = ", 1)[0].lstrip("%").strip()


class Reduced:
    """A trace cut to the window [t0, t1], given in wall-clock seconds."""

    def __init__(self, trace: Dict, t0: float, t1: float,
                 devices: Optional[Sequence[int]] = None):
        # times are kept in seconds since the profile's start, where they
        # are exact; the window and the namer's argument are wall clock
        self.base = trace["profile_start_ns"] / 1e9
        self.t0, self.t1 = t0 - self.base, t1 - self.base
        self.ops: Dict[int, List[Tuple[str, float, float]]] = {}
        self.modules: List[Tuple[str, float, float]] = []
        for plane in trace["planes"]:
            m = DEVICE_PLANE.match(plane["name"])
            if not m:
                continue
            for line in plane["lines"]:
                for name, start, dur in line["events"]:
                    s, e = start / 1e9, (start + dur) / 1e9
                    if e <= self.t0 or s >= self.t1:
                        continue
                    s, e = max(s, self.t0), min(e, self.t1)
                    dev = int(m.group(1))
                    if devices is not None and dev not in devices:
                        continue
                    if line["name"] == OPS_LINE:
                        self.ops.setdefault(dev, []).append((name, s, e))
                    elif line["name"] == MODULES_LINE:
                        self.modules.append((module_name(name), s, e))
        self.modules.sort(key=lambda m: m[1])
        self.busy = {d: _union([(s, e) for _, s, e in evs])
                     for d, evs in self.ops.items()}

    def _module_at(self, t: float) -> str:
        i = bisect.bisect_right([s for _, s, _ in self.modules], t) - 1
        if i >= 0 and self.modules[i][1] <= t <= self.modules[i][2]:
            return self.modules[i][0]
        return "?"

    @property
    def window_s(self) -> float:
        return self.t1 - self.t0

    @property
    def busy_s(self) -> float:
        """Device busy seconds in the window, averaged over the devices
        that ran anything."""
        if not self.busy:
            return 0.0
        return sum(sum(e - s for s, e in iv)
                   for iv in self.busy.values()) / len(self.busy)

    @property
    def idle_share(self) -> float:
        return 1.0 - self.busy_s / self.window_s

    def module_seconds(self, names: Sequence[str]) -> float:
        wanted = set(names)
        return sum(e - s for n, s, e in self.modules if n in wanted)

    def top_ops(self, k: int = 10) -> List[List]:
        """Device time by op, named ``<module>:<op>`` (the module whose
        program ran the op), most first."""
        total: Dict[str, float] = {}
        for evs in self.ops.values():
            for name, s, e in evs:
                key = f"{self._module_at(s)}:{op_name(name)}"
                total[key] = total.get(key, 0.0) + (e - s)
        return [[n, t] for n, t in
                sorted(total.items(), key=lambda kv: -kv[1])[:k]]

    def idle_gaps(self, namer: Callable[[float], str],
                  k: int = 10) -> List[List]:
        """The k longest gaps in which the first device ran nothing, each
        named by ``namer(midpoint)``."""
        if self.busy:
            busy = self.busy[min(self.busy)]
        else:
            busy = []
        gaps, t = [], self.t0
        for s, e in busy:
            if s > t:
                gaps.append((t, s))
            t = max(t, e)
        if t < self.t1:
            gaps.append((t, self.t1))
        gaps.sort(key=lambda g: g[0] - g[1])
        return [[namer(self.base + (s + e) / 2), e - s] for s, e in gaps[:k]]
