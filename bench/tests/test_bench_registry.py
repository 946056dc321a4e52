"""BENCHMARK.json against the benchmark's contract, and the harness
finding every piece by name: cells, configurations, traffic mixes and
metric readers, including ones added later as new files and entries."""
import hashlib
import json
import os
import re
import shutil
import subprocess
import sys

import pytest

from bench import harness
from bench.tests import faults

ROOT = harness.ROOT
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


@pytest.fixture(scope="module")
def spec():
    return harness.load_spec()


def _line(text):
    return 1 <= len(text) <= 200 and "\n" not in text and "\t" not in text


def test_top_level_keys_and_command(spec):
    assert set(spec) == {"command", "paths", "run_seconds", "configs",
                         "workloads", "end_to_end", "per_layer"}
    assert spec["command"] == ["python3", "bench/run.py"]
    assert spec["paths"] == ["bench"]
    assert 1 <= spec["run_seconds"] <= 51
    assert os.path.getsize(os.path.join(ROOT, "BENCHMARK.json")) <= 64 << 10


def test_configs(spec):
    used = {w["config"] for w in spec["workloads"]}
    files = set()
    for c in spec["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert NAME.match(c["name"]) and c["name"] in used
        assert _line(c["source"]) and _line(c["why"])
        assert c["file"].startswith("bench/") and c["file"] not in files
        files.add(c["file"])
        with open(os.path.join(ROOT, c["file"])) as f:
            config = json.load(f)
        assert len(c["reduced"]) <= 16
        for key in c["reduced"]:
            assert NAME.match(key)
            # every cut is stated in the file with its reason
            assert key in config["reduced"], key
    # every configuration file, measured or pending, also says how the CPU
    # tests run it: at which sizes, and with which faults planted
    full = harness.load_spec(pending=True)
    for c in full["configs"]:
        with open(os.path.join(ROOT, c["file"])) as f:
            config = json.load(f)
        for key in ("source", "reduced", "assumed", "guarantees", "limits",
                    "test_sizes", "faults"):
            assert key in config, f"{c['file']} has no {key!r}"
        # a misspelt size would leave a test at the full size
        for key in config["test_sizes"]:
            assert key in config, f"{c['file']}: test size {key!r} is no key"
        assert config["faults"], f"{c['file']} names no fault"
        cell = harness.Cell(next(w["name"] for w in full["workloads"]
                                 if w["config"] == c["name"]), spec=full)
        for fault in config["faults"]:
            assert faults.planter(fault, cell.config_module), \
                f"{c['file']}: no planter for fault {fault!r}"


def test_workloads(spec):
    pairs = set()
    names = [w["name"] for w in spec["workloads"]]
    assert len(names) == len(set(names)) and 1 <= len(names) <= 24
    for w in spec["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["name"]) and NAME.match(w["traffic"])
        assert w["chips"] in (1, 4) and _line(w["why"])
        assert (w["config"], w["traffic"]) not in pairs
        pairs.add((w["config"], w["traffic"]))
    assert sum(w["chips"] == 4 for w in spec["workloads"]) <= max(
        1, len(names) // 2)


def test_metrics(spec):
    e2e = {m["name"]: m for m in spec["end_to_end"]}
    names = list(e2e) + [m["name"] for m in spec["per_layer"]]
    assert len(names) == len(set(names))
    assert "setup_s" in e2e and e2e["setup_s"]["bound"] <= 0.25
    for m in spec["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound",
                                          "source"}
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    layers = {}
    for m in spec["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "source",
                                          "layer", "moves"}
        assert m["source"] in SOURCES and _line(m["layer"])
        assert m["moves"] in e2e
        layers.setdefault(m["layer"], m["layer"])
        if m["name"].endswith("_roofline"):
            assert m["unit"] == "%"
    for m in spec["end_to_end"] + spec["per_layer"]:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["better"] in ("lower", "higher")
    cells = {w["name"] for w in spec["workloads"]}
    for w in cells:
        mine = [m for m in spec["end_to_end"]
                if w in m.get("workloads", [w])]
        assert "setup_s" in {m["name"] for m in mine} and len(mine) >= 2
        layer = [m for m in spec["per_layer"]
                 if w in m.get("workloads", [w])]
        assert layer
        for m in layer:     # a metric's cells report what it moves
            assert w in e2e[m["moves"]].get("workloads", [w])


@pytest.mark.parametrize("cell_name", sorted(
    w["name"] for w in harness.load_spec()["workloads"]))
def test_every_piece_resolves_by_name(cell_name):
    cell = harness.Cell(cell_name)
    assert hasattr(cell.config_module, "System")
    assert cell.traffic["loop"] in ("closed", "open")
    for m in cell.end_to_end:
        if m["name"] != "setup_s":
            assert callable(cell.reader(m, "e2e").read)
    for m in cell.per_layer:
        assert callable(cell.reader(m, "layers").read)


def test_pending_cells_resolve_only_for_the_tools():
    """Cells waiting in bench/pending.json resolve as BENCHMARK.json's do
    for the tools and tests, and never for a measured run."""
    spec = harness.load_spec()
    full = harness.load_spec(pending=True)
    measured = {w["name"] for w in spec["workloads"]}
    waiting = {w["name"] for w in full["workloads"]} - measured
    assert len(full["workloads"]) == len(measured) + len(waiting)
    for name in waiting:
        cell = harness.Cell(name, spec=full)
        assert {m["name"] for m in cell.end_to_end} >= {"setup_s"}
        assert len(cell.end_to_end) >= 2 and cell.per_layer
        with pytest.raises(harness.BenchError):
            harness.Cell(name)
    for name in measured:
        assert [m["name"] for m in harness.Cell(name, spec=full).end_to_end] \
            == [m["name"] for m in harness.Cell(name).end_to_end]


def _digest(root):
    out = {}
    for d, _, files in os.walk(root):
        for f in files:
            if "__pycache__" in d:
                continue
            p = os.path.join(d, f)
            with open(p, "rb") as fh:
                out[os.path.relpath(p, root)] = hashlib.sha256(
                    fh.read()).hexdigest()
    return out


def _add_a_cell(root):
    """Copy the benchmark to `root` and add to the copy what a later change
    adds: a configuration, a traffic mix, a per-layer metric and a cell,
    as new files and new entries. Returns the copy's digest before, the
    cell that was copied, the new configuration's stem and the end-to-end
    metrics the new cell reports."""
    shutil.copytree(os.path.join(ROOT, "bench"), root / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    before = _digest(root / "bench")
    spec = harness.load_spec()
    old = spec["workloads"][0]
    cfg = next(c for c in spec["configs"] if c["name"] == old["config"])
    stem = os.path.splitext(cfg["file"])[0]
    for ext in (".json", ".py"):
        shutil.copy(root / (stem + ext), root / (stem + "_copy" + ext))
    traffic_dir = root / "bench" / "traffic"
    with open(traffic_dir / (old["traffic"] + ".json")) as f:
        traffic = json.load(f)
    traffic["note"] = "a new mix"
    with open(traffic_dir / "new_mix.json", "w") as f:
        json.dump(traffic, f)
    with open(root / "bench" / "layers" / "answered.new.py", "w") as f:
        f.write("def read(run):\n"
                "    return sum(o.done is not None for o in run.outcomes)\n")
    spec["configs"].append(dict(cfg, name=cfg["name"] + "_copy",
                                file=stem + "_copy.json"))
    spec["workloads"].append({"name": "new-cell",
                              "config": cfg["name"] + "_copy",
                              "traffic": "new_mix", "chips": 1,
                              "why": "a cell added by files and entries"})
    moves = []
    for m in spec["end_to_end"]:
        if old["name"] in m.get("workloads", []):
            m["workloads"].append("new-cell")
            moves.append(m["name"])
    spec["per_layer"].append({"name": "answered.new", "unit": "req",
                              "better": "higher", "source": "host_clock",
                              "layer": "load generator", "moves": moves[0],
                              "workloads": ["new-cell"]})
    with open(root / "BENCHMARK.json", "w") as f:
        json.dump(spec, f)
    return before, old, stem, moves


def test_new_pieces_are_taken_up_from_files_and_entries(tmp_path):
    """A later change adds a configuration, a traffic mix and a per-layer
    metric as new files and new entries, and edits no file."""
    before, old, stem, moves = _add_a_cell(tmp_path)

    cell = harness.Cell("new-cell", root=str(tmp_path))
    assert cell.traffic["note"] == "a new mix"
    assert cell.config_module.__file__ == str(tmp_path / (stem + "_copy.py"))
    assert [m["name"] for m in cell.per_layer] == ["answered.new"]
    assert {m["name"] for m in cell.end_to_end} == set(moves) | {"setup_s"}
    reader = cell.reader(cell.per_layer[0], "layers")
    assert reader.read(type("R", (), {"outcomes": []})) == 0
    after = _digest(tmp_path / "bench")
    assert {k: v for k, v in after.items() if k in before} == before
    # the cells that were there resolve as before
    assert harness.Cell(old["name"], root=str(tmp_path)).traffic == \
        harness.Cell(old["name"]).traffic


# a fault of the new configuration's own, planted from its own module
OWN_FAULT = '''

def plant_answer_doubled(monkeypatch):
    from repro.kernels import ops

    agg = ops.groupby_aggregate_rows
    monkeypatch.setattr(ops, "groupby_aggregate_rows",
                        lambda v, c, n, fn="sum": agg(v, c, n, fn) * 2)
'''


def _pytest_in(root, *args):
    """pytest in the copy at `root`, on the CPU, with this checkout's
    program; the outer run's pytest settings (an xdist worker's) left out."""
    env = {k: v for k, v in os.environ.items()
           if not k.startswith("PYTEST_")}
    env.update(JAX_PLATFORMS="cpu", PYTHONPATH=os.path.join(ROOT, "src"))
    return subprocess.run(
        [sys.executable, "-m", "pytest", "-q", "-p", "no:cacheprovider",
         *args], cwd=root, env=env, capture_output=True, text=True,
        timeout=300)


def test_the_harness_tests_take_up_a_new_cell(tmp_path):
    """The harness's CPU tests find a new cell's sizes and faults in its
    configuration's files, collect its runs, faults and control, and run it
    correct, with no file that was there edited."""
    before, old, stem, _ = _add_a_cell(tmp_path)
    with open(tmp_path / (stem + "_copy.json")) as f:
        config = json.load(f)
    config["faults"].append("answer_doubled")
    with open(tmp_path / (stem + "_copy.json"), "w") as f:
        json.dump(config, f)
    with open(tmp_path / (stem + "_copy.py"), "a") as f:
        f.write(OWN_FAULT)
    module = "bench/tests/test_bench_harness.py"

    listed = _pytest_in(tmp_path, "--collect-only", module)
    assert listed.returncode == 0, listed.stdout + listed.stderr
    ids = {line.split("::", 1)[1] for line in listed.stdout.splitlines()
           if line.startswith(module + "::")}
    want = {f"{test}[new-cell]" for test in (
        "test_a_run_of_the_cell_is_correct",
        "test_a_traced_run_reports_the_layers",
        "test_the_control_fails_the_comparison")}
    want |= {f"test_a_planted_fault_makes_the_run_incorrect[new-cell-{f}]"
             for f in config["faults"]}
    assert want <= ids, sorted(want - ids)
    # the cells that were there keep their tests
    assert any(i.endswith(f"[{old['name']}]") for i in ids)

    ran = _pytest_in(
        tmp_path, module + "::test_a_run_of_the_cell_is_correct[new-cell]",
        module + "::test_a_planted_fault_makes_the_run_incorrect"
        "[new-cell-answer_doubled]")
    assert ran.returncode == 0, ran.stdout[-4000:] + ran.stderr[-4000:]
    assert "2 passed" in ran.stdout, ran.stdout[-4000:]
    after = _digest(tmp_path / "bench")
    assert {k: v for k, v in after.items() if k in before} == before
