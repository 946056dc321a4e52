"""Faults that the harness's CPU tests plant in a cell's timed path, to see
its ``correct`` come out false. Each configuration names the faults its
timed path can have under ``"faults"`` in its JSON file; each name is a
planter that takes pytest's ``monkeypatch``.

The planters here break the program's own entry points, never the plain
reference, so the answer goes wrong where it is produced:

- ``answer_altered``: ``ops.groupby_aggregate_rows`` returns every
  aggregate scaled by 1 + 1e-3;
- ``half_left_out``: ``ops.groupby_aggregate_rows`` aggregates the first
  half of its rows and scales the result up to the whole, the mean taken
  over the rest;
- ``rows_dropped``: ``ops.compact_indices``, which a filter uses to keep
  the rows whose mask is true, keeps every second one of them.

A configuration whose timed path can break in a way of its own (a join
that drops matches, say) adds ``plant_<name>(monkeypatch)`` to its own
``bench/configs/<config>.py`` and ``<name>`` to its ``"faults"``: a name is
looked up here first, then there, so no file here changes for it.
"""


def answer_altered(monkeypatch):
    from repro.kernels import ops

    agg = ops.groupby_aggregate_rows
    monkeypatch.setattr(ops, "groupby_aggregate_rows",
                        lambda v, c, n, fn="sum": agg(v, c, n, fn)
                        * (1 + 1e-3))


def half_left_out(monkeypatch):
    from repro.kernels import ops

    agg = ops.groupby_aggregate_rows

    def half(values, codes, n_groups, fn="sum"):
        k = (len(values) + 1) // 2
        out = agg(values[:k], codes[:k], n_groups, fn)
        # the mean of the rows kept, scaled back up to the whole
        return out * (len(values) / k)
    monkeypatch.setattr(ops, "groupby_aggregate_rows", half)


def rows_dropped(monkeypatch):
    from repro.kernels import ops

    compact = ops.compact_indices
    monkeypatch.setattr(ops, "compact_indices",
                        lambda mask: compact(mask)[::2])


PLANTERS = {f.__name__: f for f in (answer_altered, half_left_out,
                                     rows_dropped)}


def planter(name, config_module):
    """The planter of fault `name`: one of this module's, else
    ``plant_<name>`` in the configuration's own module; None where neither
    has it."""
    return PLANTERS.get(name) or getattr(config_module, "plant_" + name,
                                         None)
