"""Planning (core/physical.py): the engine's ``plan`` event minus the
moment the benchmark submitted the run, mean over the window's runs."""
from bench.events import plan_ts
from bench.stats import mean


def read(run):
    return mean(1e3 * (plan_ts(r["events"]) - r["submit"])
                for r in run.program.get("runs", [])
                if plan_ts(r["events"]) is not None)
