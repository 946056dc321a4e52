"""Engine dispatch (core/engine.py): per run, the sum over its tasks of
``task_start`` minus the moment the task became ready; mean over runs."""
from bench.events import dispatch_wait_s
from bench.stats import mean


def read(run):
    waits = [dispatch_wait_s(r) for r in run.program.get("runs", [])]
    return mean(1e3 * w for w in waits if w is not None)
