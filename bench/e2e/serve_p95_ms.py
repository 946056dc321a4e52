"""The 95th percentile of request latency over every request of the
window, from its scheduled send to its full response."""
from bench.stats import latencies_ms, percentile


def read(run):
    return percentile(latencies_ms(run.outcomes), 95)
