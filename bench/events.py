"""Per-run readings from the program's own event stream: the ``Event``s a
``Client`` collects (``core/runtime.py``), each with a wall-clock ``ts``,
and the run's physical plan."""
from __future__ import annotations

from typing import Dict, Optional


def first(events, kind: str) -> Dict[str, float]:
    out: Dict[str, float] = {}
    for e in events:
        if e.kind == kind and e.task_id not in out:
            out[e.task_id] = e.ts
    return out


def plan_ts(events) -> Optional[float]:
    for e in events:
        if e.kind == "plan":
            return e.ts
    return None


def dispatch_wait_s(run_record) -> Optional[float]:
    """Sum over the run's tasks of the wait from ready to first start. A
    task is ready when its last input is: a parent's ``task_done``, or its
    first ``stream_chunk`` on the edge the task streams from; a root is
    ready at the ``plan`` event."""
    events, plan = run_record["events"], run_record["plan"]
    t_plan = plan_ts(events)
    if t_plan is None:
        return None
    start = first(events, "task_start")
    done = first(events, "task_done")
    chunk = first(events, "stream_chunk")
    total = 0.0
    for tid, task in plan.tasks.items():
        if tid not in start:
            continue
        ready = t_plan
        stream_param = getattr(task, "stream_param", "")
        for edge in getattr(task, "inputs", ()):
            p = edge.parent_task
            t = (chunk.get(p) if stream_param and edge.param == stream_param
                 else None)
            if t is None:
                t = done.get(p)
            if t is not None:
                ready = max(ready, t)
        total += max(0.0, start[tid] - ready)
    return total


def busy_s(run_record, scans: bool) -> float:
    """Sum of ``task_done`` seconds of the run's scan tasks (scans=True)
    or of its other tasks: functions, partials and combines."""
    from repro.core.physical import ScanTask

    plan = run_record["plan"]
    total = 0.0
    for e in run_record["events"]:
        if e.kind != "task_done" or e.task_id not in plan.tasks:
            continue
        if isinstance(plan.tasks[e.task_id], ScanTask) == scans:
            total += float(e.payload.get("seconds", 0.0))
    return total
