"""Worker scans, pushdown pruning (core/physical.py file pruning by column
stats, core/runtime.py ``_run_scan``): per run, the data files its scan
tasks read, the ``files`` of its ``task.scan`` spans summed; mean over
the window's runs. A program whose scans do not count files reads
nothing."""
from bench.spans import records
from bench.stats import mean


def _files(run_record):
    scans = [s["attrs"] for s in records(run_record)
             if s["name"] == "task.scan"]
    if not scans or any("files" not in a for a in scans):
        return None
    return sum(a["files"] for a in scans)


def read(run):
    files = [_files(r) for r in run.program.get("runs", [])]
    return mean(f for f in files if f is not None)
