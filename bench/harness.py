"""The benchmark's harness: finds every piece of a cell by the name that
``BENCHMARK.json`` gives it, runs the cell once, and builds the result.

Pieces, each in a file of its own, so that a new cell, configuration,
traffic mix or metric is a new file and a new entry, never an edit:

- a configuration: ``bench/configs/<config>.json`` (sizes, source, cuts,
  guarantees, limits) and ``bench/configs/<config>.py`` beside it (data
  generator, the user's DAG, the system as deployed, the plain reference);
- a traffic mix: ``bench/traffic/<traffic>.json``, read by the one general
  generator in ``bench/loadgen.py``;
- an end-to-end metric: ``bench/e2e/<metric>.py``; a per-layer metric:
  ``bench/layers/<metric>.py``. Each holds ``read(run)``, which returns the
  number or None when the run has nothing for it to read.

``setup_s`` is the harness's own: process start to window start.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
import math
import os
import shutil
import sys
import tempfile
import time
import types
from typing import Dict, List, Optional

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
# JAX's persistent compilation cache: a fixed directory inside the checkout,
# so every run of a cell after the first finds its programs there
CACHE_DIR = os.path.join(ROOT, ".jax_cache")


class BenchError(RuntimeError):
    """The run cannot be measured; it exits non-zero and prints no result."""


# ---------------------------------------------------------------------------
# registry: every piece found by name
# ---------------------------------------------------------------------------


def load_spec(root: str = ROOT, pending: bool = False) -> Dict:
    """BENCHMARK.json; with `pending`, also the cells of
    ``bench/pending.json``: cells whose pieces are all here but which wait
    outside BENCHMARK.json (PERF.md says why). The tools that set a cell's
    rate and limits, and the tests, reach them this way; a measured run
    never does."""
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        spec = json.load(f)
    path = os.path.join(root, "bench", "pending.json")
    if not pending or not os.path.exists(path):
        return spec
    with open(path) as f:
        extra = json.load(f)
    for key in ("configs", "workloads", "per_layer"):
        spec[key] += extra.get(key, [])
    e2e = {m["name"]: m for m in spec["end_to_end"]}
    for m in extra.get("end_to_end", []):
        if m["name"] not in e2e:
            spec["end_to_end"].append(m)
        elif "workloads" in e2e[m["name"]]:   # else it holds in every cell
            e2e[m["name"]]["workloads"].extend(m["workloads"])
    return spec


def _by_name(entries: List[Dict], name: str, what: str) -> Dict:
    for e in entries:
        if e["name"] == name:
            return e
    raise BenchError(f"no {what} named {name!r} in BENCHMARK.json; have "
                     f"{sorted(e['name'] for e in entries)}")


def _load_module(path: str) -> types.ModuleType:
    if not os.path.exists(path):
        raise BenchError(f"missing benchmark file {path}")
    mod_name = "bench_piece_" + "".join(
        c if c.isalnum() else "_" for c in os.path.relpath(path, ROOT))
    spec = importlib.util.spec_from_file_location(mod_name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


class Cell:
    """One workload of BENCHMARK.json with its pieces resolved."""

    def __init__(self, name: str, root: str = ROOT,
                 spec: Optional[Dict] = None):
        self.root = root
        spec = spec or load_spec(root)
        workload = _by_name(spec["workloads"], name, "workload")
        self.chips = int(workload["chips"])
        cfg = _by_name(spec["configs"], workload["config"], "configuration")
        with open(os.path.join(root, cfg["file"])) as f:
            self.config = json.load(f)
        self.config_module = _load_module(
            os.path.join(root, os.path.splitext(cfg["file"])[0] + ".py"))
        with open(os.path.join(root, "bench", "traffic",
                               workload["traffic"] + ".json")) as f:
            self.traffic = json.load(f)
        self.end_to_end = [m for m in spec["end_to_end"]
                           if name in m.get("workloads", [name])]
        self.per_layer = [m for m in spec["per_layer"]
                          if name in m.get("workloads", [name])]

    def reader(self, metric: Dict, kind: str) -> types.ModuleType:
        return _load_module(os.path.join(self.root, "bench", kind,
                                         metric["name"] + ".py"))


# ---------------------------------------------------------------------------
# the chip, the compile cache, compilations inside the window
# ---------------------------------------------------------------------------


def use_compile_cache() -> None:
    """Point JAX (and the program, which honours the variable) at the
    checkout's cache, and cache every program however quick its compile.
    Call before JAX is imported."""
    os.environ["JAX_COMPILATION_CACHE_DIR"] = CACHE_DIR
    os.environ["JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS"] = "0"
    os.environ["JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES"] = "0"


def look_for_chips(chips: int):
    """The devices the cell runs on; BenchError without enough TPUs."""
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise BenchError(f"JAX found no TPU (platform {devs[0].platform!r});"
                         " the benchmark measures only on the chip")
    if len(devs) < chips:
        raise BenchError(f"the cell needs {chips} chips, JAX found "
                         f"{len(devs)}")
    return devs[:chips]


class CompileCounter:
    """Counts XLA compilations (and persistent-cache loads) while armed."""

    EVENTS = ("/jax/core/compile/backend_compile_duration",
              "/jax/compilation_cache/cache_hits")

    def __init__(self):
        import jax

        self.armed = False
        self.count = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, name, secs, **kw):
        if self.armed and name in self.EVENTS:
            self.count += 1

    def _event(self, name, **kw):
        if self.armed and name in self.EVENTS:
            self.count += 1


def with_control(system, outcomes) -> list:
    """The outcomes with the control's answer in place of the program's:
    the plain reference in the next precision down, which the check has to
    find not correct."""
    return [dataclasses.replace(o, output=system.control_output(o))
            if o.output is not None else o for o in outcomes]


def memory_peak_bytes(devices) -> int:
    peak = 0
    for d in devices:
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return peak


# ---------------------------------------------------------------------------
# one run of one cell
# ---------------------------------------------------------------------------


class Run:
    """What a metric reader sees: the configuration, the window and every
    outcome, what the program reported (events, counters), the device kind
    and, in a traced run, the reduced trace."""

    def __init__(self, config: Dict, system, window, trace, device_kind: str):
        self.config = config
        self.window = window
        self.outcomes = window.outcomes
        self.program = system.observations()
        self.trace = trace
        self.device_kind = device_kind


def _read_metrics(cell: Cell, run: Run, metrics: List[Dict],
                  kind: str) -> Dict:
    out = {}
    for m in metrics:
        if kind == "e2e" and m["name"] == "setup_s":
            continue
        value = cell.reader(m, kind).read(run)
        if value is None:
            continue
        value = float(value)
        if not math.isfinite(value):
            raise BenchError(f"metric {m['name']} read {value}")
        out[m["name"]] = {"value": value, "unit": m["unit"]}
    return out


def _note(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def run_cell(cell: Cell, seed: int, seconds: float, trace: bool,
             devices, t_start: float,
             overrides: Optional[Dict] = None) -> Dict:
    """Set up, warm up, measure for `seconds`, check, and return the result
    line's object. `overrides` replaces configuration keys (tests run the
    cells at small sizes on the CPU with it)."""
    from bench import loadgen
    from bench import trace as tr

    config = dict(cell.config, **(overrides or {}))
    workdir = tempfile.mkdtemp(prefix="bench_")
    counter = CompileCounter()
    system = None
    try:
        t0 = time.perf_counter()
        system = cell.config_module.System(config, seed, workdir)
        t1 = time.perf_counter()
        gen = loadgen.Generator(cell.traffic, seed, system)
        gen.prepare(seconds)
        t2 = time.perf_counter()
        gen.warm_up()
        trace_dir = os.path.join(workdir, "trace")
        setup_s = time.perf_counter() - t_start
        _note(f"set-up {setup_s:.3f}s: start to system {t0 - t_start:.3f}s, "
              f"system {t1 - t0:.3f}s, traffic {t2 - t1:.3f}s, warm-up "
              f"{t_start + setup_s - t2:.3f}s")
        counter.armed = True
        if trace:
            with tr.capture(trace_dir):
                window = gen.measure(seconds)
        else:
            window = gen.measure(seconds)
        counter.armed = False
        peak = memory_peak_bytes(devices)
        _note("job seconds from the scheduled send, in send order: " + " ".join(
            f"{o.done - o.scheduled:.3f}" if o.done is not None else "-"
            for o in window.outcomes))
        if counter.count:
            _note(f"window compiled {counter.count} programs: the set-up did "
                  "not warm every shape the window used")
        reduced = None
        if trace:
            reduced = tr.Reduced(tr.load(trace_dir), window.wall0,
                                 window.wall1,
                                 devices=[d.id for d in devices])
        run = Run(config, system, window, reduced, devices[0].device_kind)
        hits = run.program.get("cache_hits", 0)
        if hits:
            raise BenchError(f"{hits} intermediate-cache hits in the window:"
                             " a run reused a result instead of computing")
        metrics = (_read_metrics(cell, run, cell.per_layer, "layers") if trace
                   else _read_metrics(cell, run, cell.end_to_end, "e2e"))
        if not trace:
            metrics["setup_s"] = {"value": setup_s, "unit": "s"}
        system.close()       # the program's state goes before the reference
        checks, correct = system.check(window.outcomes)
        dev = devices[0]
        result = {
            "correct": bool(correct),
            "attempted": window.attempted,
            "failed": window.failed,
            "metrics": metrics,
            "device": {"platform": dev.platform, "kind": dev.device_kind,
                       "count": len(devices), "memory_peak_bytes": peak},
        }
        if trace:
            result["device"]["busy_s"] = reduced.busy_s
            result["device"]["window_s"] = reduced.window_s
            result["breakdown"] = {
                "device_ops": reduced.top_ops(),
                "idle_gaps": reduced.idle_gaps(
                    lambda t: system.host_activity(t, run))}
        result["checks"] = checks
        return result
    finally:
        if system is not None:
            system.close()
        shutil.rmtree(workdir, ignore_errors=True)
