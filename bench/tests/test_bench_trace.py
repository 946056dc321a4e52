"""The trace reduction, checked on a trace recorded on one TPU v5e: three
rounds of the group-by, compaction and combine wrappers inside one
``bench.window`` host span (the fixture's host plane gives the window;
the reduction reads only the device plane)."""
import json
import os

import pytest

from bench import trace as tr

FIXTURE = os.path.join(os.path.dirname(__file__), "fixtures",
                       "tpu_v5e_trace.json")


@pytest.fixture(scope="module")
def recorded():
    with open(FIXTURE) as f:
        return json.load(f)


def _wall(recorded, ns):
    return (recorded["profile_start_ns"] + ns) / 1e9


def _line(recorded, plane, line):
    for p in recorded["planes"]:
        if p["name"] == plane:
            for ln in p["lines"]:
                if ln["name"] == line:
                    return ln["events"]
    raise KeyError((plane, line))


def _window(recorded):
    (name, start, dur), = _line(recorded, "/host:CPU", "python")
    assert name == "bench.window"
    return _wall(recorded, start), _wall(recorded, start + dur)


def test_busy_time_is_the_union_of_op_intervals(recorded):
    t0, t1 = _window(recorded)
    red = tr.Reduced(recorded, t0, t1)
    ops = _line(recorded, "/device:TPU:0", "XLA Ops")
    # an independent union: mark each nanosecond boundary pair, sweep once
    edges = sorted([(s, 1) for _, s, d in ops] + [(s + d, -1)
                                                 for _, s, d in ops])
    depth, busy_ns, opened = 0, 0.0, None
    for t, step in edges:
        if depth == 0 and step == 1:
            opened = t
        depth += step
        if depth == 0:
            busy_ns += t - opened
    assert red.busy_s == pytest.approx(busy_ns / 1e9, rel=1e-9)
    assert red.window_s == pytest.approx(t1 - t0)
    assert 0.0 < red.busy_s < red.window_s
    assert red.idle_share == pytest.approx(1 - red.busy_s / red.window_s)


def test_module_time_of_the_groupby_wrappers(recorded):
    t0, t1 = _window(recorded)
    red = tr.Reduced(recorded, t0, t1)
    mods = _line(recorded, "/device:TPU:0", "XLA Modules")
    want = sum(d for n, _, d in mods
               if n.startswith(("jit_groupby_aggregate(",
                                "jit_combine_aggregate("))) / 1e9
    got = red.module_seconds(["groupby_aggregate", "combine_aggregate"])
    assert got == pytest.approx(want, rel=1e-9)
    # three rounds, each one group-by of 131072 rows: about 154 us apiece
    assert 3 * 150e-6 < red.module_seconds(["groupby_aggregate"]) < 3 * 160e-6


def test_window_clips_what_lies_outside(recorded):
    t0, t1 = _window(recorded)
    mods = _line(recorded, "/device:TPU:0", "XLA Modules")
    # a window that ends inside the first group-by sees only its start
    name, start, dur = mods[0]
    cut = _wall(recorded, start + dur / 2)
    red = tr.Reduced(recorded, t0, cut)
    # the window's ends are wall-clock floats, good to about a microsecond
    assert red.module_seconds(["groupby_aggregate"]) == pytest.approx(
        dur / 2 / 1e9, abs=1e-6)
    assert tr.Reduced(recorded, t0, t1, devices=[1]).busy_s == 0.0


def test_breakdown_lists(recorded):
    t0, t1 = _window(recorded)
    red = tr.Reduced(recorded, t0, t1)
    ops = red.top_ops()
    assert 0 < len(ops) <= 10
    assert [t for _, t in ops] == sorted((t for _, t in ops), reverse=True)
    # the compaction's index scatter is the costliest op of these rounds
    assert ops[0][0] == "compact:fusion"
    assert all(":" in name and not name.startswith("?") for name, _ in ops)
    # each gap is named from its midpoint on the wall clock
    gaps = red.idle_gaps(lambda t: "inside" if t0 < t < t1 else "outside")
    assert 0 < len(gaps) <= 10
    assert all(name == "inside" for name, _ in gaps)
    assert [g for _, g in gaps] == sorted((g for _, g in gaps), reverse=True)
    idle = red.window_s - red.busy_s
    assert sum(g for _, g in gaps) <= idle + 1e-12


def test_names():
    assert tr.module_name("jit_groupby_aggregate(1280657)") == \
        "groupby_aggregate"
    assert tr.op_name("%compact.5 = s32[1,131072]{1,0} custom-call(x)") == \
        "compact.5"
