"""The load generator (bench/loadgen.py): the 95th percentile of how late
each request was sent, its send time minus its scheduled time. A high
reading is a starved generator, not a slow server."""
from bench.stats import percentile


def read(run):
    return percentile([1e3 * (o.sent - o.scheduled) for o in run.outcomes],
                      95)
