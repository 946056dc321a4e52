"""The gateway (serving/gateway.py): requests admitted per pipeline run,
from the ``requests`` and ``runs`` counters of ``Gateway.metrics()`` as
deltas over the window."""


def read(run):
    c = run.program.get("counters", {})
    if not c.get("runs"):
        return None
    return c.get("requests", 0) / c["runs"]
