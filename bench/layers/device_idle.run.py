"""The device (TPU): the share of the traced window in which no XLA op
ran, 1 minus the union of the op intervals over the window, in percent."""


def read(run):
    if run.trace is None or not run.trace.busy:
        return None
    return 100.0 * run.trace.idle_share
