"""The group-by kernels (kernels/ops.py, kernels/groupby_agg.py): the
least time the chip needs for the query's aggregation work, over the
device time of the group-by wrappers' XLA modules in the trace, percent.

The work comes from the query, not from the calls the program makes, so
another kernel reads the same work: every row that passes the filter is
read once as a 4-byte key code and once as a 4-byte value per distinct
aggregated expression, and each group's aggregates are written once as
4-byte values; one add per value. It is bandwidth-bound on any chip in
the peaks table.
"""
from bench.peaks import roofline_share

# the XLA modules of the group-by wrappers (jax.jit of kernels/ops.py)
MODULES = ("groupby_aggregate", "combine_aggregate")


def read(run):
    if run.trace is None:
        return None
    seconds = run.trace.module_seconds(MODULES)
    q = run.config["query"]
    rows = groups = 0
    for o in run.outcomes:
        if o.output is None:
            continue
        rows += sum(g["count_order"] for g in o.output["rows"].values())
        groups += len(o.output["rows"])
    exprs = q["aggregated_expressions"]
    nbytes = rows * (4 + 4 * exprs) + groups * 4 * q["aggregates"]
    share = roofline_share(rows * exprs, nbytes, seconds, run.device_kind)
    return share
