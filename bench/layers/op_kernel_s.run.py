"""Kernel entry points, host side (kernels/ops.py, columnar/compute.py):
per run, the sum of its ``op.kernel`` spans, each a device call with its
host pad, copy in, dispatch, wait and copy back; mean over the window's
runs."""
from bench.spans import mean_total_s


def read(run):
    return mean_total_s(run.program.get("runs", []), "op.kernel")
