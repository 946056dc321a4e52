"""Kernels against their host calls: the share of the window's
``op.kernel`` span time during which the device ran an XLA op, in
percent. The spans are put on the trace's time line by the wall clock and
cut with the union of the op intervals of the traced window."""
from bench.spans import device_share


def read(run):
    if run.trace is None or not run.trace.busy:
        return None
    share = device_share(run.trace, run.program.get("runs", []))
    return None if share is None else 100.0 * share
