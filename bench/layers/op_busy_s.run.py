"""Worker operators (columnar/compute.py): the sum of ``task_done``
seconds of a run's function, partial and combine tasks, mean over runs."""
from bench.events import busy_s
from bench.stats import mean


def read(run):
    return mean(busy_s(r, scans=False) for r in run.program.get("runs", []))
