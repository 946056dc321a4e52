"""Worker operators, filter gather (columnar/compute.py ``filter_table``,
the device path): per run, the sum of its ``op.gather`` spans, the host
``ColumnTable.take`` of the rows the compaction kept; mean over the
window's runs. None where the program opens no such span."""
from bench.spans import mean_total_s, records


def read(run):
    runs = run.program.get("runs", [])
    if not any(s["name"] == "op.gather" for r in runs for s in records(r)):
        return None
    return mean_total_s(runs, "op.gather")
