"""Engine dispatch (core/engine.py): per run, the sum of its ``task.wait``
spans, each from the moment a task was ready to its start on a worker's
pool thread; mean over the window's runs."""
from bench.spans import mean_total_s


def read(run):
    s = mean_total_s(run.program.get("runs", []), "task.wait")
    return None if s is None else 1e3 * s
