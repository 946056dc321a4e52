"""Run one cell of the benchmark once, on the chip.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Loads the cell's configuration and traffic by the names ``BENCHMARK.json``
gives them, sets the system up from the seed, warms up every shape the
window will use, measures for ``--seconds`` seconds, checks what the timed
path produced against the plain reference, and prints one JSON object as
the last line of standard output. ``--trace 0`` reports the cell's
end-to-end metrics, ``--trace 1`` its per-layer metrics from a profiler
trace of the window.

Without a TPU, or with fewer chips than the cell needs, it exits non-zero
before the window and prints no result.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    from bench import harness

    try:
        cell = harness.Cell(args.workload)
        harness.use_compile_cache()
        import repro  # noqa: F401  (no program, no run)

        devices = harness.look_for_chips(cell.chips)
        result = harness.run_cell(cell, args.seed, args.seconds,
                                  bool(args.trace), devices, T_START)
    except harness.BenchError as e:
        print(f"bench: {e}", file=sys.stderr)
        return 2
    for name, c in result["checks"].items():
        print(f"check {name} {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
