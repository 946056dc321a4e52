"""Worker operators, filter predicate (columnar/compute.py
``filter_table``): per run, the sum of its ``op.mask`` spans, each the
evaluation of a filter's predicate to a row mask; mean over the window's
runs. An edge's pushed-down filter (inside ``input.filter``) opens one
too, so fig1's date window is in it beside ``euro_selection``'s utf8
``country IN (...)``. None where the program opens no such span."""
from bench.spans import mean_total_s, records


def read(run):
    runs = run.program.get("runs", [])
    if not any(s["name"] == "op.mask" for r in runs for s in records(r)):
        return None
    return mean_total_s(runs, "op.mask")
