"""The span readers (bench/spans.py, bench/layers/*), checked on a q1-sf1
window recorded on one TPU v5e: every run's events, spans included, and
the device trace of the same window (the fixture's first line says how it
was recorded). The join of the two on the wall clock is the proof that
the program's clock and the profiler's agree."""
import json
import os
import types

import pytest

from bench import harness
from bench import spans as bs
from bench import trace as tr

FIXTURE = os.path.join(os.path.dirname(__file__), "fixtures",
                       "tpu_v5e_q1_spans.json")
SPAN_METRICS = {"plan_span_ms.run": ("run.plan", 1e3),
                "task_wait_ms.run": ("task.wait", 1e3),
                "op_input_s.run": ("task.input", 1.0),
                "op_keys_s.run": ("op.keys", 1.0),
                "op_kernel_s.run": ("op.kernel", 1.0)}
MODULES = ("groupby_aggregate", "combine_aggregate")


@pytest.fixture(scope="module")
def recorded():
    with open(FIXTURE) as f:
        data = json.load(f)
    runs = [dict(r, events=[types.SimpleNamespace(**e) for e in r["events"]])
            for r in data["runs"]]
    reduced = tr.Reduced(data["trace"], *data["window"], devices=[0])
    return runs, reduced


def _run(runs, reduced):
    return types.SimpleNamespace(program={"runs": runs}, trace=reduced)


def _reader(name):
    return harness._load_module(os.path.join(harness.ROOT, "bench", "layers",
                                             name + ".py"))


def _spans(run):
    return [s for e in run["events"] if e.kind == "spans"
            for s in e.payload["spans"]]


def test_the_fixture_holds_runs_with_spans(recorded):
    runs, reduced = recorded
    assert len(runs) >= 3
    for r in runs:
        names = [s["name"] for s in _spans(r)]
        assert names.count("run.plan") == 1
        assert {"task", "task.wait", "task.input", "op.keys",
                "op.kernel"} <= set(names)
    assert reduced.busy and reduced.module_seconds(MODULES) > 0


@pytest.mark.parametrize("metric", sorted(SPAN_METRICS))
def test_sums_per_run(recorded, metric):
    runs, reduced = recorded
    name, scale = SPAN_METRICS[metric]
    per_run = [scale * sum(s["wall"] for s in _spans(r) if s["name"] == name)
               for r in runs]
    got = _reader(metric).read(_run(runs, reduced))
    assert got == pytest.approx(sum(per_run) / len(per_run), rel=1e-12)
    assert got > 0


def test_the_operator_phases_fit_inside_the_operator_tasks(recorded):
    runs, reduced = recorded
    for r in runs:
        parts = sum(bs.total_s(r, n) for n in ("op.keys", "op.kernel"))
        ops = [s for s in _spans(r) if s["name"] == "task"
               and s["attrs"]["kind"] != "scan"]
        assert parts <= sum(s["wall"] for s in ops)


def test_the_off_cpu_share(recorded):
    runs, reduced = recorded
    shares = []
    for r in runs:
        ops = [s for s in _spans(r) if s["name"] == "task"
               and s["attrs"]["kind"] != "scan"]
        assert ops
        shares.append(1 - sum(s["cpu"] for s in ops)
                      / sum(s["wall"] for s in ops))
    got = _reader("op_offcpu.run").read(_run(runs, reduced))
    assert got == pytest.approx(100 * sum(shares) / len(shares), rel=1e-12)
    assert 0 < got < 100


def _covered(a, b, intervals):
    """Length of [a, b] inside the union of `intervals`, by brute force:
    cut at every end point and keep the pieces some interval holds."""
    cuts = sorted({a, b} | {x for s, e in intervals for x in (s, e)
                            if a < x < b})
    got = 0.0
    for lo, hi in zip(cuts, cuts[1:]):
        mid = (lo + hi) / 2
        if any(s <= mid <= e for s, e in intervals):
            got += hi - lo
    return got


def test_the_wall_clock_join(recorded):
    runs, reduced = recorded
    ops = [iv for ivs in reduced.busy.values() for iv in ivs]
    whole = inside = 0.0
    for r in runs:
        for s in _spans(r):
            if s["name"] != "op.kernel":
                continue
            a = max(s["start"] - reduced.base, reduced.t0)
            b = min(s["start"] - reduced.base + s["wall"], reduced.t1)
            if b <= a:
                continue
            whole += b - a
            near = [(x, y) for x, y in ops if y > a and x < b]
            inside += _covered(a, b, near)
    got = _reader("kernel_device_share.run").read(_run(runs, reduced))
    assert got == pytest.approx(100 * inside / whole, rel=1e-9)
    assert 0 < got <= 100


def _moved(runs, shift=0.0, widen=0.0):
    """The runs with every span started `shift` s later and `widen` s
    longer at each end."""
    def move(s):
        return dict(s, start=s["start"] + shift - widen,
                    wall=s["wall"] + 2 * widen)
    return [dict(r, events=[
        types.SimpleNamespace(**dict(vars(e), payload={
            "spans": [move(s) for s in e.payload["spans"]]}))
        if e.kind == "spans" else e for e in r["events"]]) for r in runs]


def test_the_spans_start_on_the_profilers_host_clock(recorded):
    """Each op.kernel span, placed on the trace by its wall-clock start,
    starts within 0.1 ms of the TraceAnnotation the profiler recorded for
    it on the host plane (entered just after the span's clock reading)."""
    runs, reduced = recorded
    with open(FIXTURE) as f:
        notes = sorted(s / 1e9 for s, _ in json.load(f)["host_op_kernel"])
    starts = sorted(s["start"] - reduced.base for r in runs
                    for s in _spans(r) if s["name"] == "op.kernel")
    assert len(starts) == len(notes)
    for span_start, note in zip(starts, notes):
        assert 0 <= note - span_start < 1e-4


def test_the_clocks_agree(recorded):
    """At least 99% of the group-by and combine modules' device time lies
    inside the host's op.kernel spans put on the trace by the wall clock,
    and every module lies within 1 ms of one. Shifted by 5 ms either way,
    the spans hold less."""
    runs, reduced = recorded
    share = bs.module_share_inside(reduced, runs, MODULES)
    assert share >= 0.99
    assert bs.module_share_inside(reduced, _moved(runs, widen=1e-3),
                                  MODULES) == pytest.approx(1.0)
    for shift in (-5e-3, 5e-3):
        assert bs.module_share_inside(reduced, _moved(runs, shift),
                                      MODULES) < share


def test_the_phases_cover_the_tasks(recorded):
    runs, _ = recorded
    assert bs.phase_coverage(runs) >= 0.90


def test_a_program_without_spans_reads_nothing(recorded):
    """A program that sends no spans gets None from every reader,
    and the device share needs a trace besides."""
    runs, reduced = recorded
    bare = [dict(r, events=[e for e in r["events"] if e.kind != "spans"])
            for r in runs]
    for metric in list(SPAN_METRICS) + ["op_offcpu.run",
                                        "kernel_device_share.run"]:
        assert _reader(metric).read(_run(bare, reduced)) is None, metric
    assert _reader("kernel_device_share.run").read(_run(runs, None)) is None
