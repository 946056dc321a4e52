"""Worker threads: per run, 1 minus the CPU seconds over the wall seconds
of its ``task`` spans other than scans, in percent: the share of the
operators' time their threads did not run (GIL, device, I/O waits); mean
over the window's runs."""
from bench.spans import offcpu_share
from bench.stats import mean


def read(run):
    shares = [offcpu_share(r) for r in run.program.get("runs", [])]
    m = mean(s for s in shares if s is not None)
    return None if m is None else 100.0 * m
