"""Worker operators, edge delivery and pushdown (core/runtime.py
``_deliver_edge``, ``_edge_chunks``, ``_fetch_parts``): per run, the sum
of its ``task.input`` spans, the transport get with the edge's filter and
projection (``input.filter``) inside; mean over the window's runs. A
streamed edge's span also holds the wait for the producer's next chunk."""
from bench.spans import mean_total_s


def read(run):
    return mean_total_s(run.program.get("runs", []), "task.input")
