"""Planning (core/runtime.py ``submit_run``): the wall time of the run's
``run.plan`` span, project to logical to physical plan, mean over the
window's runs."""
from bench.spans import mean_total_s


def read(run):
    s = mean_total_s(run.program.get("runs", []), "run.plan")
    return None if s is None else 1e3 * s
