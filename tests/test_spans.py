"""Spans inside the run path (core/spans.py): nesting, the ids every span
carries, how a task's spans travel ahead of its task_done, the compile
counter, the no-op outside a task, and the wall clock the spans share
with the JAX profiler's trace."""
import glob
import os

import numpy as np
import pytest

import repro as bp
from repro.columnar import Catalog, ColumnTable, ObjectStore, compute
from repro.core import Client, LocalCluster, spans
from repro.core.runtime import execute_run

PHASES = {"task.scan", "task.input", "task.env", "task.user", "task.output"}


def test_nesting_and_parent_indices():
    with spans.recording("r1", "t1", "task", kind="function") as rec:
        with spans.span("a", rows=3) as a:
            with spans.span("b"):
                pass
            a.set(extra=1)
        with spans.span("c"):
            pass
        assert spans.finish(rows=9) is not None
        out = rec.drain()
    assert [s["name"] for s in out] == ["task", "a", "b", "c"]
    assert [s["parent"] for s in out] == [-1, 0, 1, 0]
    assert out[1]["attrs"] == {"rows": 3, "extra": 1}
    assert out[0]["attrs"] == {"kind": "function", "rows": 9}
    for s in out:
        assert (s["run"], s["task"]) == ("r1", "t1")
        assert s["wall"] >= 0 and s["cpu"] >= 0
    # a parent holds its children
    assert out[0]["wall"] >= out[1]["wall"] >= out[2]["wall"]
    assert out[1]["start"] <= out[2]["start"]
    assert spans.current() is None


def test_closing_a_parent_closes_what_is_open_inside():
    with spans.recording("r", "t", "task") as rec:
        inner = spans.span("left_open").__enter__()
        spans.finish()
        out = rec.drain()
    assert inner.wall is not None
    assert [s["name"] for s in out] == ["task", "left_open"]


def test_spans_outside_a_task_are_no_ops():
    assert spans.current() is None
    with spans.span("op.keys", rows=1) as sp:
        sp.set(groups=2)
    assert spans.finish() is None
    t = ColumnTable.from_pydict({"k": ["a", "b", "a"], "v": [1.0, 2.0, 3.0]})
    out = compute.group_by(t, ["k"], {"s": ("v", "sum")}, backend="jax")
    assert out.column("s").to_numpy().tolist() == [4.0, 2.0]
    assert spans.current() is None


def _transactions():
    return ColumnTable.from_pydict({
        "id": np.arange(6, dtype=np.int64),
        "usd": [1.0, 2.0, 3.0, 4.0, 5.0, 6.0],
        "country": ["IT", "US", "FR", "JP", "IT", "GB"],
    })


def test_the_device_filter_opens_mask_kernel_and_gather_spans():
    t = _transactions()
    with spans.recording("r", "t", "task") as rec:
        with spans.span("task.user"):
            out = compute.filter_table(t, "country IN ('IT','FR')",
                                       backend="jax")
        spans.finish()
        got = rec.drain()
    assert out.column("id").to_numpy().tolist() == [0, 2, 4]
    names = [s["name"] for s in got]
    assert names == ["task", "task.user", "op.mask", "op.kernel",
                     "op.gather"]
    # siblings, in that order, under the caller's span
    assert [s["parent"] for s in got[2:]] == [1, 1, 1]
    assert got[2]["start"] <= got[3]["start"] <= got[4]["start"]
    mask, kernel, gather = (s["attrs"] for s in got[2:])
    assert mask == {"rows_in": 6, "rows_out": 3, "columns": ["country"]}
    assert kernel["kernel"] == "compact"
    assert gather == {"rows": 3, "bytes": out.nbytes}


def test_the_host_filter_opens_a_mask_span_only():
    t = _transactions()
    with spans.recording("r", "t", "task") as rec:
        out = compute.filter_table(t, "usd > 2.5")
        spans.finish()
        got = rec.drain()
    assert out.num_rows == 4
    assert [s["name"] for s in got] == ["task", "op.mask"]
    assert got[1]["attrs"] == {"rows_in": 6, "rows_out": 4,
                               "columns": ["usd"]}


def test_the_filter_records_nothing_outside_a_task():
    assert spans.current() is None
    out = compute.filter_table(_transactions(), "country IN ('GB')",
                               backend="jax")
    assert out.column("id").to_numpy().tolist() == [5]
    assert spans.current() is None


def test_compiles_counts_on_the_first_call_of_a_shape_only():
    from repro.kernels import ops

    # a shape no other test uses: 3,000 rows (bucket 4,096), 1,000 groups
    rng = np.random.default_rng(0)
    vals = rng.random(3000)
    codes = rng.integers(0, 1000, 3000)
    with spans.recording("r", "t", "task") as rec:
        ops.groupby_aggregate_rows(vals, codes, 1000, "max")
        ops.groupby_aggregate_rows(vals, codes, 1000, "max")
        spans.finish()
        kernels = [s for s in rec.drain() if s["name"] == "op.kernel"]
    assert [k["attrs"]["kernel"] for k in kernels] == \
        ["groupby_aggregate"] * 2
    assert kernels[0]["attrs"]["compiles"] > 0
    assert kernels[1]["attrs"]["compiles"] == 0
    assert kernels[0]["attrs"]["padded_rows"] == 4096


# ---------------------------------------------------------------------------
# a sharded group-by run on a LocalCluster
# ---------------------------------------------------------------------------


def _project():
    proj = bp.Project("spans-test")
    aggs = {"q": ("qty", "sum"), "p": ("price", "mean"),
            "n": ("qty", "count")}

    @proj.model(rowwise=True)
    def priced(data=bp.Model("items", columns=["flag", "qty", "price"],
                             filter="qty <= 40")):
        return data.with_column("price", data.column("price").data * 2)

    @proj.model(combinable=bp.GroupByCombine(["flag"], aggs, backend="jax"))
    def by_flag(data=bp.Model("priced")):
        return compute.group_by(data, ["flag"], aggs, backend="jax")

    return proj


@pytest.fixture(scope="module")
def sharded_run(tmp_path_factory):
    tmp = tmp_path_factory.mktemp("spans_run")
    c = Catalog(ObjectStore(str(tmp / "s3")))
    rng = np.random.default_rng(1)
    n = 8000
    c.write_table("items", ColumnTable.from_pydict({
        "flag": [("A", "N", "R")[i] for i in rng.integers(0, 3, n)],
        "qty": rng.integers(1, 50, n).astype(np.float64),
        "price": rng.random(n) * 100,
    }), rows_per_file=1000)
    cluster = LocalCluster(c, c.store, str(tmp / "dp"), n_workers=4)
    try:
        client = Client()
        res = execute_run(_project(), cluster=cluster, client=client,
                          shard_threshold_bytes=1, max_shards=4,
                          chunk_rows=700)
        return res, list(client.events)
    finally:
        cluster.close()


def _groups(events):
    return [(e, e.payload["spans"]) for e in events if e.kind == "spans"]


def test_the_run_was_sharded_and_streamed(sharded_run):
    res, events = sharded_run
    kinds = {s["attrs"].get("kind") for _, g in _groups(events) for s in g
             if s["name"] == "task"}
    assert {"scan", "function", "partial", "combine"} <= kinds
    assert any(e.kind == "stream_chunk" for e in events)
    names = {s["name"] for _, g in _groups(events) for s in g}
    assert {"run.plan", "task.wait", "task", "task.scan", "task.input",
            "input.filter", "task.env", "task.user", "task.output",
            "op.keys", "op.kernel"} <= names


def test_every_span_carries_its_run_and_task(sharded_run):
    res, events = sharded_run
    groups = _groups(events)
    assert groups
    for ev, group in groups:
        for s in group:
            assert s["run"] == res.run_id
            assert s["task"] == ev.task_id
            assert s["task"] in res.plan.tasks or s["name"] == "run.plan"
    plan_spans = [s for _, g in groups for s in g if s["name"] == "run.plan"]
    assert len(plan_spans) == 1
    assert plan_spans[0]["task"] == res.plan.plan_id
    assert plan_spans[0]["attrs"]["tasks"] == len(res.plan.order)
    # one wait per attempt, one task span per completed task
    waits = [s["task"] for _, g in groups for s in g
             if s["name"] == "task.wait"]
    assert sorted(waits) == sorted(res.plan.order)


def test_a_tasks_spans_come_just_before_its_task_done(sharded_run):
    res, events = sharded_run
    for tid in res.plan.order:
        mine = [(i, e.kind) for i, e in enumerate(events)
                if e.task_id == tid and e.kind in ("spans", "task_done")
                and not (e.kind == "spans"
                         and e.payload["spans"][0]["name"] == "task.wait")]
        assert [k for _, k in mine] == ["spans", "task_done"], tid


def test_task_wall_is_task_done_seconds(sharded_run):
    res, events = sharded_run
    done = {e.task_id: e.payload["seconds"] for e in events
            if e.kind == "task_done"}
    roots = {s["task"]: s for _, g in _groups(events) for s in g
             if s["name"] == "task"}
    assert set(done) == set(roots)
    for tid, s in roots.items():
        assert abs(s["wall"] - done[tid]) < 1e-3, tid
        assert s["parent"] == -1
        assert s["attrs"]["rows"] >= 0 and s["attrs"]["bytes"] >= 0


def test_children_cover_the_task(sharded_run):
    _, events = sharded_run
    covered = whole = 0.0
    for _, group in _groups(events):
        for i, s in enumerate(group):
            if s["name"] == "task":
                whole += s["wall"]
                covered += sum(c["wall"] for c in group
                               if c["parent"] == i and c["name"] in PHASES)
    # the children nest inside the task; on a shared CPU the task's own
    # book-keeping may take a visible share, hence the loose bound
    assert 0.5 * whole <= covered <= whole * 1.0001


def test_kernel_and_key_spans_sit_inside_the_operators(sharded_run):
    _, events = sharded_run
    for _, group in _groups(events):
        for s in group:
            if s["name"] in ("op.kernel", "op.keys"):
                parent = group[s["parent"]]
                assert parent["name"] in ("task.user", "task.input"), parent
            if s["name"] == "op.kernel":
                a = s["attrs"]
                assert a["kernel"] in ("groupby_aggregate",
                                       "combine_aggregate")
                assert a["bytes_in"] > 0 and a["compiles"] >= 0
            if s["name"] == "input.filter":
                a = s["attrs"]
                assert a["rows_out"] <= a["rows_in"]
                assert group[s["parent"]]["name"] == "task.input"
            if s["name"] == "task.scan":
                assert s["attrs"]["hits"] + s["attrs"]["misses"] > 0


def test_the_scan_spans_count_the_files_read(sharded_run):
    res, events = sharded_run
    scans = [s for _, g in _groups(events) for s in g
             if s["name"] == "task.scan"]
    assert len(scans) > 1
    # each scan shard reads its own files; together the table's 8
    assert sum(s["attrs"]["files"] for s in scans) == 8
    for s in scans:
        assert s["attrs"]["files"] == len(res.plan.tasks[s["task"]].files)


def test_the_unread_events_are_gone(sharded_run):
    _, events = sharded_run
    kinds = {e.kind for e in events}
    assert not kinds & {"cache_probe", "env_built"}
    env = [s for _, g in _groups(events) for s in g if s["name"] == "task.env"]
    assert env and all("cache_hit" in s["attrs"] for s in env
                       if s["attrs"]["phase"] == "build")


# ---------------------------------------------------------------------------
# the clock the spans share with the profiler
# ---------------------------------------------------------------------------


def test_the_profiler_sees_the_span_on_the_same_clock(tmp_path):
    import jax
    from jax._src.profiler import ProfileData

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        with spans.recording("r", "t", "task") as rec:
            with spans.span("clock.probe"):
                jax.numpy.ones(8).sum().block_until_ready()
            spans.finish()
            out = {s["name"]: s for s in rec.drain()}
    finally:
        jax.profiler.stop_trace()
    path, = glob.glob(os.path.join(str(tmp_path), "plugins", "profile", "*",
                                   "*.xplane.pb"))
    data = ProfileData.from_file(path)
    start_ns = None
    found = []
    for plane in data.planes:
        if plane.name == "Task Environment":
            start_ns = int(dict(plane.stats)["profile_start_time"])
        for line in plane.lines:
            found += [(plane.name, e.start_ns) for e in line.events
                      if e.name == "clock.probe"]
    assert start_ns is not None
    (plane, rel_ns), = found
    assert plane.startswith("/host:")
    assert abs((start_ns + rel_ns) / 1e9 - out["clock.probe"]["start"]) < 1e-3
