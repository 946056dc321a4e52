"""The filter compaction kernels (kernels/ops.py ``compact``,
kernels/filter_compact.py): the least time the chip needs for the
query's compaction work, over the device time of the ``compact`` XLA
module in the trace, percent.

The work comes from the data and each run's window, not from the calls
the program makes or their padded sizes: every row delivered to the
filter is read once as a 4-byte mask element and every row that
survives has its index written once as 4 bytes (the configuration's
``compact_work``, per run, in ``run.program``). It is bandwidth-bound on
any chip in the peaks table.
"""
from bench.peaks import roofline_share

# the XLA module of the compaction wrapper (jax.jit of kernels/ops.py)
MODULES = ("compact",)


def read(run):
    work = run.program.get("compact_work")
    if run.trace is None or not work:
        return None
    nbytes = sum(4 * rows_in + 4 * rows_out for rows_in, rows_out in work)
    return roofline_share(0, nbytes, run.trace.module_seconds(MODULES),
                          run.device_kind)
