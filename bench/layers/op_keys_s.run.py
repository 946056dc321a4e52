"""Worker operators, group-by keys (columnar/compute.py): per run, the sum
of its ``op.keys`` spans, the keys' encoding to dense codes and the take
of each group's key; mean over the window's runs."""
from bench.spans import mean_total_s


def read(run):
    return mean_total_s(run.program.get("runs", []), "op.keys")
