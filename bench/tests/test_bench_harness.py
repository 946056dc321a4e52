"""The harness on the CPU: it refuses to measure off the chip, and with
the look for a chip skipped it drives whole runs of each cell at small
sizes, whose ``correct`` holds for the program and falls for each fault
the cells can have, planted where the answer is produced."""
import json
import os
import shutil
import subprocess
import sys
import time

import numpy as np
import pytest

from bench import harness

ROOT = harness.ROOT
# each configuration at a size a test run holds (keyed by configuration)
SMALL = {
    "tpch_q1_sf1": {"rows": 6000, "rows_per_file": 1000,
                    "shard_threshold_bytes": 1024},
    "fig1_paper": {},
}
# the faults each configuration's timed path can have
FAULTS = {
    "tpch_q1_sf1": ["answer_altered", "half_left_out"],
    "fig1_paper": ["answer_altered", "half_left_out", "rows_dropped"],
}
SEED = 2**31 + 12345     # larger than a signed 32-bit int


# BENCHMARK.json and the cells that wait in bench/pending.json
SPEC = harness.load_spec(pending=True)
CELLS = {w["name"]: w["config"] for w in SPEC["workloads"]}


def _cell(name):
    return harness.Cell(name, spec=SPEC)


def _run_cli(cwd, env_extra=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH="")
    env.update(env_extra or {})
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "q1-sf1", "--seed",
         str(SEED), "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def _no_result(stdout):
    for line in stdout.splitlines():
        try:
            json.loads(line)
        except ValueError:
            continue
        return False
    return True


def test_refuses_a_platform_that_is_not_a_tpu():
    proc = _run_cli(ROOT)
    assert proc.returncode != 0
    assert "JAX found no TPU" in proc.stderr
    assert _no_result(proc.stdout)


def test_refuses_without_the_program(tmp_path):
    """A checkout that holds only the benchmark cannot run it."""
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(os.path.join(ROOT, "bench"), tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    proc = _run_cli(tmp_path)
    assert proc.returncode != 0
    assert _no_result(proc.stdout)


def _run(cell_name, seconds=1.5, trace=False):
    import jax

    cell = _cell(cell_name)
    return harness.run_cell(cell, SEED, seconds, trace, jax.devices(),
                            time.perf_counter(),
                            overrides=SMALL[CELLS[cell_name]])


@pytest.mark.parametrize("cell_name", sorted(CELLS))
def test_a_run_of_the_cell_is_correct(cell_name):
    res = _run(cell_name)
    assert res["correct"], res["checks"]
    assert res["attempted"] > 0 and res["failed"] == 0
    assert set(res["metrics"]) == {m["name"] for m in
                                   _cell(cell_name).end_to_end}
    assert list(res)[-1] == "checks"
    for check in res["checks"].values():
        assert check["value"] <= check["limit"]


@pytest.mark.parametrize("cell_name", sorted(CELLS))
def test_a_traced_run_reports_the_layers(cell_name):
    res = _run(cell_name, trace=True)
    assert res["correct"], res["checks"]
    names = set(res["metrics"])
    # the CPU has no device trace: what reads the device reports nothing
    device = {m["name"] for m in _cell(cell_name).per_layer
              if m["source"] == "device_trace"}
    assert names == {m["name"] for m in _cell(cell_name).per_layer
                     } - device
    assert res["device"]["window_s"] > 0
    assert "breakdown" in res


def _plant(monkeypatch, fault):
    """Break the timed path under the program's own entry points."""
    from repro.kernels import ops

    agg, compact = ops.groupby_aggregate_rows, ops.compact_indices
    if fault == "answer_altered":
        monkeypatch.setattr(ops, "groupby_aggregate_rows",
                            lambda v, c, n, fn="sum": agg(v, c, n, fn)
                            * (1 + 1e-3))
    elif fault == "half_left_out":
        def half(values, codes, n_groups, fn="sum"):
            k = (len(values) + 1) // 2
            out = agg(values[:k], codes[:k], n_groups, fn)
            # the mean of the rows kept, scaled back up to the whole
            return out * (len(values) / k)
        monkeypatch.setattr(ops, "groupby_aggregate_rows", half)
    elif fault == "rows_dropped":
        monkeypatch.setattr(ops, "compact_indices",
                            lambda mask: compact(mask)[::2])


@pytest.mark.parametrize("cell_name,fault", [
    (cell, fault) for cell in sorted(CELLS) for fault in FAULTS[CELLS[cell]]])
def test_a_planted_fault_makes_the_run_incorrect(monkeypatch, cell_name,
                                                 fault):
    _plant(monkeypatch, fault)
    res = _run(cell_name)
    assert not res["correct"], res["checks"]


@pytest.mark.parametrize("cell_name", sorted(CELLS))
def test_the_control_fails_the_comparison(monkeypatch, cell_name):
    """The reference in bfloat16, put in the program's place on the timed
    path, makes the harness's own check come out not correct."""
    from bench import loadgen

    measure = loadgen.Generator.measure

    def control_in_place(self, seconds):
        window = measure(self, seconds)
        window.outcomes[:] = harness.with_control(self.system,
                                                  window.outcomes)
        return window

    monkeypatch.setattr(loadgen.Generator, "measure", control_in_place)
    res = _run(cell_name)
    assert not res["correct"], res["checks"]
    err = res["checks"]["max_rel_err"]
    assert err["value"] > 10 * err["limit"], err


def test_stratified_traffic_is_the_same_load_for_every_seed():
    from bench import loadgen

    class Stub:
        def prepare(self, size, rng):
            return size

    with open(os.path.join(ROOT, "bench", "traffic",
                           "open_poisson.json")) as f:
        traffic = json.load(f)
    loads = []
    for seed in (1, SEED):
        g = loadgen.Generator(traffic, seed, Stub())
        g.prepare(5.0)
        loads.append((sorted(g.items), np.sort(g.gaps)))
    assert loads[0][0] == loads[1][0]
    np.testing.assert_allclose(loads[0][1], loads[1][1])
    assert min(loads[0][0]) >= traffic["size_low"]
    assert max(loads[0][0]) <= traffic["size_high"]
