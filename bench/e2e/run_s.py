"""Mean submit-to-result seconds of the pipeline runs that completed in
the window (a closed loop): the sum of their times over their count."""
from bench.stats import mean


def read(run):
    return mean(o.done - o.sent for o in run.outcomes
                if o.in_window and o.error is None)
