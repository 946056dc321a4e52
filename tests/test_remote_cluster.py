"""Process-isolated remote worker runtime: the cluster/worker contract,
control-plane RPC, log streaming over the control channel, heartbeat-based
failure detection, and process-kill recovery (per-shard + transitive)."""
import os
import signal
import time

import numpy as np
import pytest

from repro.columnar import Catalog, ColumnTable, ObjectStore
from repro.core import Client, LocalCluster
from repro.core.contract import ClusterLike, TransportLike, WorkerLike
from repro.core.physical import WorkerProfile
from repro.core.remote import RemoteCluster, load_project_spec
from repro.core.runtime import execute_run, submit_run

PROJECT_SRC = '''
import time

import numpy as np

import repro as bp


def build():
    proj = bp.Project("remote-test")

    @proj.model(rowwise=True)
    def doubled(data=bp.Model("src", columns=["a"])):
        print("doubling", data.num_rows, "rows")
        time.sleep(0.15)
        return {"a": np.asarray(data.column("a").to_numpy()) * 2.0}

    @proj.model()
    def total(data=bp.Model("doubled")):
        a = np.asarray(data.column("a").to_numpy())
        return {"total": np.array([a.sum()]),
                "rows": np.array([float(len(a))])}

    return proj
'''

EXPECTED_TOTAL = np.arange(4000.0).sum() * 2


@pytest.fixture
def project_spec(tmp_path):
    p = tmp_path / "remote_project.py"
    p.write_text(PROJECT_SRC)
    return f"{p}:build"


@pytest.fixture
def cat(tmp_path):
    store = ObjectStore(str(tmp_path / "s3"))
    c = Catalog(store)
    c.write_table("src", ColumnTable.from_pydict({"a": np.arange(4000.0)}),
                  rows_per_file=500)
    return c


@pytest.fixture
def rcluster(cat, tmp_path, project_spec):
    c = RemoteCluster(cat, cat.store, str(tmp_path / "dp"), n_workers=2,
                      project=project_spec, heartbeat_interval_s=0.2)
    yield c
    c.close()


def _wait_for(pred, timeout=30.0, interval=0.02):
    deadline = time.time() + timeout
    while time.time() < deadline:
        if pred():
            return True
        time.sleep(interval)
    return False


# ---------------------------------------------------------------------------
# the explicit contract: LocalCluster and RemoteCluster are interchangeable
# ---------------------------------------------------------------------------


def test_clusters_satisfy_the_contract(rcluster, cat, tmp_path):
    local = LocalCluster(cat, cat.store, str(tmp_path / "ldp"), n_workers=1)
    try:
        for cluster in (local, rcluster):
            assert isinstance(cluster, ClusterLike)
            for w in cluster.workers.values():
                assert isinstance(w, WorkerLike)
                assert isinstance(w.transport, TransportLike)
    finally:
        local.close()


def test_unknown_worker_raises(rcluster):
    with pytest.raises(KeyError):
        rcluster.get("nope")


# ---------------------------------------------------------------------------
# happy path: sharded remote run == single-process run, logs stream back
# ---------------------------------------------------------------------------


def test_remote_sharded_run_matches_local(rcluster, cat, tmp_path,
                                          project_spec):
    proj = load_project_spec(project_spec)
    local = LocalCluster(cat, cat.store, str(tmp_path / "ldp"), n_workers=1)
    try:
        base = execute_run(proj, cluster=local,
                           shard_threshold_bytes=1 << 60)
        base_out = base.read("doubled", local)
    finally:
        local.close()

    client = Client()
    res = execute_run(proj, cluster=rcluster, client=client,
                      shard_threshold_bytes=1, max_shards=2)
    out = res.read("doubled", rcluster)
    assert out.equals(base_out)                       # byte-identical
    tot = res.read("total", rcluster).column("total").to_numpy()[0]
    assert tot == EXPECTED_TOTAL
    # shards actually spread across the two worker *processes*
    shard_workers = {w for t, w in res.placements.items() if "#" in t}
    assert len(shard_workers) == 2
    # user prints crossed the control channel as real-time log events
    assert any("doubling" in line for line in client.logs())


def test_remote_workers_ship_their_spans(rcluster, project_spec):
    """A worker process records its task's spans and sends them inside the
    events it already streams back, ahead of the task's task_done."""
    client = Client()
    res = execute_run(load_project_spec(project_spec), cluster=rcluster,
                      client=client, shard_threshold_bytes=1, max_shards=2)
    remote = {w.worker_id for w in rcluster.workers.values()}
    for tid in res.plan.order:
        roots = [s for e in client.of_kind("spans") if e.task_id == tid
                 for s in e.payload["spans"] if s["name"] == "task"]
        done = [e for e in client.of_kind("task_done") if e.task_id == tid]
        assert len(roots) == len(done) == 1, tid
        assert done[0].worker in remote
        assert roots[0]["run"] == res.run_id and roots[0]["task"] == tid
        assert abs(roots[0]["wall"] - done[0].payload["seconds"]) < 1e-3
    users = [s for e in client.of_kind("spans") for s in e.payload["spans"]
             if s["name"] == "task.user"]
    assert users and all(s["cpu"] is not None for s in users)


def test_describe_heartbeat_and_cancel_rpcs(rcluster):
    w = next(iter(rcluster.workers.values()))
    hb = w.heartbeat()
    assert hb["ok"] and hb["alive"]
    d = w.describe()
    assert d["worker_id"] == w.worker_id
    assert d["pid"] == w.proc.pid
    assert "transport_stats" in d and "scan_cache" in d
    assert w.cancel("some-run", "func:doubled")["cancelled"]


def test_stale_daemon_code_is_refused(rcluster, cat, tmp_path):
    """A joinable daemon may outlive its project source: a plan whose
    code_hash disagrees with the daemon's loaded function must error, not
    silently publish old-code results under the new cache key."""
    from repro.core import TaskError

    edited = tmp_path / "remote_project_v2.py"
    edited.write_text(PROJECT_SRC.replace("* 2.0", "* 3.0"))
    proj = load_project_spec(f"{edited}:build")     # client plans new code
    with pytest.raises(TaskError, match="stale code"):
        execute_run(proj, cluster=rcluster, shard_threshold_bytes=1 << 60)


def test_provision_spawns_a_process(rcluster, cat):
    before = set(rcluster.workers)
    w = rcluster.get("ondemand-9")          # late binding may reference one
    assert w.worker_id == "ondemand-9"
    assert set(rcluster.workers) - before == {"ondemand-9"}
    assert w.proc.poll() is None            # a real, live OS process
    assert w.heartbeat()["ok"]


# ---------------------------------------------------------------------------
# failure: SIGKILL a worker process mid-run -> per-shard recovery
# ---------------------------------------------------------------------------


def test_sigkill_worker_midrun_recovers(rcluster, cat, project_spec):
    proj = load_project_spec(project_spec)
    client = Client()
    handle = submit_run(proj, rcluster, client=client,
                        shard_threshold_bytes=1, max_shards=2)

    victim = {}

    def first_shard_done():
        for e in client.of_kind("task_done"):
            if "#" in e.task_id:
                victim["worker"] = e.worker
                return True
        return False

    assert _wait_for(first_shard_done), "no shard completed in time"
    rcluster.kill_worker(victim["worker"])          # real SIGKILL
    res = handle.wait(timeout=180)
    assert res.read("total", rcluster).column("total").to_numpy()[0] \
        == EXPECTED_TOTAL
    # something was re-executed on the survivor
    assert max(res.task_attempts.values()) > 1
    assert rcluster.workers[victim["worker"]].proc.poll() is not None


COMBINE_PROJECT_SRC = '''
import time

import numpy as np

import repro as bp
from repro.columnar import compute

AGGS = {"total": ("v", "sum"), "avg": ("v", "mean"), "n": ("v", "count")}


def build():
    proj = bp.Project("remote-combine")

    def part(data):
        # stagger the shards so shard 0's state lands while shard 1 is
        # still in flight — a real window for the chaos kill
        first = float(np.asarray(data.column("idx").to_numpy())[0])
        time.sleep(0.05 if first < 2000 else 1.0)
        return compute.partial_group_by(data, ["k"], AGGS)

    def merge(parts):
        return compute.combine_group_by(parts, ["k"], AGGS)

    @proj.model(combinable=bp.combinable(part, merge))
    def by_k(data=bp.Model("kv", columns=["k", "v", "idx"])):
        return compute.group_by(data, ["k"], AGGS)

    return proj
'''


GB_PROJECT_SRC = '''
import numpy as np

import repro as bp
from repro.columnar import compute

AGGS = {"total": ("v", "sum")}


def build():
    proj = bp.Project("remote-gb")

    @proj.model(combinable=bp.GroupByCombine(["k"], AGGS))
    def by_k(data=bp.Model("kv", columns=["k", "v"])):
        return compute.group_by(data, ["k"], AGGS)

    return proj
'''


def test_stale_combine_contract_is_refused(cat, tmp_path):
    """A contract-only edit (AGGS global changed, body identical) is
    invisible to code_hash; a joinable daemon loaded with the old contract
    must refuse the dispatch rather than publish old-aggregation results
    under the plan's new contract-folded cache keys."""
    from repro.core import TaskError

    rng = np.random.default_rng(31)
    n = 4000
    cat.write_table("kv", ColumnTable.from_pydict({
        "k": rng.integers(0, 7, n).astype(np.float64),
        "v": rng.integers(0, 100, n).astype(np.float64)}),
        rows_per_file=n // 8)
    v1 = tmp_path / "gb_project.py"
    v1.write_text(GB_PROJECT_SRC)
    v2 = tmp_path / "gb_project_v2.py"
    v2.write_text(GB_PROJECT_SRC.replace('"sum"', '"max"'))
    proj_v2 = load_project_spec(f"{v2}:build")
    proj_v1 = load_project_spec(f"{v1}:build")
    # same body, different contract — exactly what code_hash can't see
    assert (proj_v1.functions["by_k"].code_hash
            == proj_v2.functions["by_k"].code_hash)
    rcluster = RemoteCluster(cat, cat.store, str(tmp_path / "gdp"),
                             n_workers=2, project=f"{v1}:build",
                             heartbeat_interval_s=0.2)
    try:
        with pytest.raises(TaskError, match="stale combine contract"):
            execute_run(proj_v2, cluster=rcluster, shard_threshold_bytes=1,
                        max_shards=2)
        # the daemon still serves plans that match its loaded contract
        res = execute_run(proj_v1, cluster=rcluster, shard_threshold_bytes=1,
                          max_shards=2)
        assert res.read("by_k", rcluster).num_rows == 7
    finally:
        rcluster.close()


def test_sigkill_partial_holder_recovers_combine(cat, tmp_path):
    """Map-side combine across worker PROCESSES: SIGKILL the worker that
    produced the first partial state while its sibling is still running.
    The CombineTask maps the lost part back to exactly that partial, the
    survivor re-executes it, and the merged aggregate matches the
    single-process unsharded run byte for byte."""
    rng = np.random.default_rng(29)
    n = 4000
    cat.write_table("kv", ColumnTable.from_pydict({
        "k": rng.integers(0, 11, n).astype(np.float64),
        "v": rng.integers(0, 1000, n).astype(np.float64),
        "idx": np.arange(float(n))}),
        rows_per_file=n // 8)
    spec_path = tmp_path / "remote_combine_project.py"
    spec_path.write_text(COMBINE_PROJECT_SRC)
    spec = f"{spec_path}:build"
    proj = load_project_spec(spec)

    local = LocalCluster(cat, cat.store, str(tmp_path / "ldp"), n_workers=1)
    try:
        base = execute_run(proj, cluster=local, shard_threshold_bytes=1 << 60)
        want = base.read("by_k", local)
    finally:
        local.close()

    rcluster = RemoteCluster(cat, cat.store, str(tmp_path / "rdp"),
                             n_workers=2, project=spec,
                             heartbeat_interval_s=0.2)
    try:
        client = Client()
        handle = submit_run(proj, rcluster, client=client,
                            shard_threshold_bytes=1, max_shards=2)
        victim = {}

        def first_partial_done():
            for e in client.of_kind("task_done"):
                if e.task_id.startswith("func:by_k#"):
                    victim["worker"] = e.worker
                    victim["task"] = e.task_id
                    return True
            return False

        assert _wait_for(first_partial_done), "no partial completed in time"
        rcluster.kill_worker(victim["worker"])          # real SIGKILL
        res = handle.wait(timeout=180)
        from repro.core import CombineTask
        assert isinstance(res.plan.tasks["func:by_k"], CombineTask)
        got = res.read("by_k", rcluster)
        assert got.column_names == want.column_names
        for c in got.column_names:
            assert got.column(c).data.tobytes() == \
                want.column(c).data.tobytes(), c
        # the killed partial (or its chain) re-executed on the survivor
        assert res.task_attempts[victim["task"]] >= 2
        assert rcluster.workers[victim["worker"]].proc.poll() is not None
    finally:
        rcluster.close()


SHUFFLE_PROJECT_SRC = '''
import time

import numpy as np

import repro as bp
from repro.columnar import compute

AGGS = {"total": ("v", "sum"), "n": ("v", "count")}


def build():
    proj = bp.Project("remote-shuffle")

    def part(data):
        # hold every partition open long enough for the chaos kill to land
        # while the writers' part files are still the only copy
        time.sleep(1.5)
        return compute.group_by(data, ["k"], AGGS)

    @proj.model(exchange=bp.exchangeable(part, keys=["k"], merge="keys"))
    def by_k(data=bp.Model("kv", columns=["k", "v"])):
        return compute.group_by(data, ["k"], AGGS)

    return proj
'''


def test_sigkill_shuffle_writer_holder_recovers(cat, tmp_path):
    """Partition exchange across worker PROCESSES: SIGKILL the worker whose
    shuffle writer completed first, while every partition consumer is still
    sleeping. Its part files die with the process; consumers trip
    ShardUnavailable, the engine re-executes exactly that writer's chain on
    the survivor, sibling writers run once, and the merged aggregation
    matches the single-process unsharded run byte for byte."""
    rng = np.random.default_rng(17)
    n = 4000
    cat.write_table("kv", ColumnTable.from_pydict({
        "k": rng.integers(0, 13, n).astype(np.float64),
        "v": rng.integers(0, 1000, n).astype(np.float64)}),
        rows_per_file=n // 8)
    spec_path = tmp_path / "remote_shuffle_project.py"
    spec_path.write_text(SHUFFLE_PROJECT_SRC)
    spec = f"{spec_path}:build"
    proj = load_project_spec(spec)

    local = LocalCluster(cat, cat.store, str(tmp_path / "ldp"), n_workers=1)
    try:
        base = execute_run(proj, cluster=local, shard_threshold_bytes=1 << 60)
        want = base.read("by_k", local)
    finally:
        local.close()

    rcluster = RemoteCluster(cat, cat.store, str(tmp_path / "rdp"),
                             n_workers=2, project=spec,
                             heartbeat_interval_s=0.2)
    try:
        client = Client()
        handle = submit_run(proj, rcluster, client=client,
                            shard_threshold_bytes=1, max_shards=4)
        victim = {}

        def first_writer_done():
            for e in client.of_kind("task_done"):
                if e.task_id.startswith("shuffle:by_k/data#"):
                    victim["worker"] = e.worker
                    victim["task"] = e.task_id
                    return True
            return False

        assert _wait_for(first_writer_done), "no shuffle writer completed"
        rcluster.kill_worker(victim["worker"])          # real SIGKILL
        res = handle.wait(timeout=180)
        got = res.read("by_k", rcluster)
        assert got.column_names == want.column_names
        for c in got.column_names:
            assert got.column(c).data.tobytes() == \
                want.column(c).data.tobytes(), c
        # the killed writer's chain re-executed on the survivor; at least
        # one sibling writer (whose parts survived) ran exactly once
        assert res.task_attempts[victim["task"]] >= 2
        siblings = [t for t in res.task_attempts
                    if t.startswith("shuffle:by_k/data#")
                    and t != victim["task"]]
        assert siblings and any(res.task_attempts[t] == 1 for t in siblings)
        assert rcluster.workers[victim["worker"]].proc.poll() is not None
    finally:
        rcluster.close()


def test_heartbeat_detects_external_process_death(rcluster, cat,
                                                  project_spec):
    wid, proxy = sorted(rcluster.workers.items())[0]
    os.kill(proxy.proc.pid, signal.SIGKILL)         # not via kill_worker
    assert _wait_for(
        lambda: [w.worker_id for w in rcluster.healthy_workers()] != []
        and proxy.alive is False, timeout=15), \
        "heartbeat never marked the dead worker down"
    assert {w.worker_id for w in rcluster.healthy_workers()} \
        == set(rcluster.workers) - {wid}
    # the fleet still serves runs
    proj = load_project_spec(project_spec)
    res = execute_run(proj, cluster=rcluster, shard_threshold_bytes=1 << 60)
    assert res.read("total", rcluster).column("total").to_numpy()[0] \
        == EXPECTED_TOTAL
