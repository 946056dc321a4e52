"""Worker scans (core/runtime.py): the sum of ``task_done`` seconds of a
run's scan tasks, mean over the window's runs."""
from bench.events import busy_s
from bench.stats import mean


def read(run):
    return mean(busy_s(r, scans=True) for r in run.program.get("runs", []))
