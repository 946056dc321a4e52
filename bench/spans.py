"""Per-run readings from the program's own spans (``core/spans.py``).

A run's ``spans`` Events carry span records: ``name``, ``run``, ``task``,
``parent`` (an index into the same event's list, -1 for a root),
``start`` (wall clock, ``time.time()``), ``wall`` and ``cpu`` seconds,
and ``attrs``. A worker sends one such event per task, the engine one for
the run's planning (``run.plan``) and one per task attempt
(``task.wait``). A program without spans sends none, and every reading
here is then None.

The join with the device trace works on the wall clock: a span's start
minus the profile's start (``Reduced.base``) is its place on the trace's
own time line.
"""
from __future__ import annotations

import bisect
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

from bench.stats import mean
from bench.trace import _union

Interval = Tuple[float, float]
# the children a task's time is split into; the rest is the task's own
PHASES = ("task.scan", "task.input", "task.env", "task.user", "task.output")


def events_spans(events) -> List[List[Dict]]:
    """The span lists of a run's ``spans`` events, one list per event."""
    return [e.payload["spans"] for e in events if e.kind == "spans"]


def records(run_record) -> List[Dict]:
    return [s for group in events_spans(run_record["events"]) for s in group]


def total_s(run_record, name: str) -> Optional[float]:
    """The wall seconds of every span called `name` in the run, summed;
    None when the run has no spans at all."""
    spans = records(run_record)
    if not spans:
        return None
    return sum(s["wall"] for s in spans if s["name"] == name)


def mean_total_s(runs: Iterable, name: str) -> Optional[float]:
    """`total_s` of each run that has spans, mean over those runs."""
    totals = [total_s(r, name) for r in runs]
    return mean(t for t in totals if t is not None)


def _op_tasks(run_record) -> List[Dict]:
    return [s for s in records(run_record)
            if s["name"] == "task" and s["attrs"].get("kind") != "scan"]


def offcpu_share(run_record) -> Optional[float]:
    """1 - the CPU seconds over the wall seconds of the run's tasks other
    than scans: the share of their time their threads did not run (waiting
    for the GIL, the device, a lock or I/O)."""
    tasks = _op_tasks(run_record)
    wall = sum(s["wall"] for s in tasks)
    if not wall:
        return None
    return 1.0 - sum(s["cpu"] for s in tasks) / wall


def phase_coverage(run_records: Iterable) -> Optional[float]:
    """The share of ``task`` wall time that its direct children in
    `PHASES` account for, over all the runs' tasks."""
    covered = whole = 0.0
    for r in run_records:
        for group in events_spans(r["events"]):
            for i, s in enumerate(group):
                if s["name"] != "task":
                    continue
                whole += s["wall"]
                covered += sum(c["wall"] for c in group
                               if c["parent"] == i and c["name"] in PHASES)
    return covered / whole if whole else None


# ---------------------------------------------------------------------------
# the join with the device trace
# ---------------------------------------------------------------------------


def _overlap(a: Interval, union: Sequence[Interval],
             starts: Sequence[float]) -> float:
    """Length of `a` inside the sorted, disjoint `union`."""
    s, e = a
    i = max(bisect.bisect_right(starts, s) - 1, 0)
    got = 0.0
    while i < len(union) and union[i][0] < e:
        got += max(0.0, min(e, union[i][1]) - max(s, union[i][0]))
        i += 1
    return got


def trace_intervals(reduced, run_records: Iterable,
                    name: str) -> List[Interval]:
    """The spans called `name` on the trace's time line, cut to its
    window."""
    out = []
    for r in run_records:
        for s in records(r):
            if s["name"] != name:
                continue
            a = s["start"] - reduced.base
            a, b = max(a, reduced.t0), min(a + s["wall"], reduced.t1)
            if b > a:
                out.append((a, b))
    return out


def device_share(reduced, run_records: Iterable) -> Optional[float]:
    """The share of the time inside ``op.kernel`` spans during which the
    device ran an XLA op: each span's overlap with the union of the op
    intervals of every device, summed, over the spans' summed time."""
    spans = trace_intervals(reduced, run_records, "op.kernel")
    busy = _union(iv for ivs in reduced.busy.values() for iv in ivs)
    whole = sum(b - a for a, b in spans)
    if not whole or not busy:
        return None
    starts = [s for s, _ in busy]
    return sum(_overlap(iv, busy, starts) for iv in spans) / whole


def module_share_inside(reduced, run_records: Iterable,
                        modules: Sequence[str]) -> Optional[float]:
    """The share of the named XLA modules' device time that falls inside
    ``op.kernel`` spans: near 1 when the two clocks agree."""
    inside = _union(trace_intervals(reduced, run_records, "op.kernel"))
    wanted = set(modules)
    mods = [(s, e) for n, s, e in reduced.modules if n in wanted]
    whole = sum(e - s for s, e in mods)
    if not whole:
        return None
    starts = [s for s, _ in inside]
    return sum(_overlap(iv, inside, starts) for iv in mods) / whole
