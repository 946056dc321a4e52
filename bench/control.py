"""Readings that set a cell's limits, on the chip, in one process.

    python3 bench/control.py --workload <cell> --seeds 1 2 3 ... --seconds <s>

For each seed: set the cell up, warm it up, run a short window at the
cell's own load and size, then check what the program produced against
the plain reference (the program's readings), and check the same
outcomes with the control's answers in the program's place: the
reference computed in bfloat16 (the control's readings, which have to
come out not correct). One JSON line per seed. The limit of each number lies between the largest
program reading and the smallest control reading (see PERF.md). The
benchmark's own runs never run the control.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)

    from bench import harness, loadgen

    cell = harness.Cell(args.workload,
                        spec=harness.load_spec(pending=True))
    harness.use_compile_cache()
    harness.look_for_chips(cell.chips)
    for seed in args.seeds:
        workdir = tempfile.mkdtemp(prefix="bench_control_")
        system = cell.config_module.System(cell.config, seed, workdir)
        try:
            gen = loadgen.Generator(cell.traffic, seed, system)
            gen.prepare(args.seconds)
            gen.warm_up()
            window = gen.measure(args.seconds)
        finally:
            system.close()
            shutil.rmtree(workdir, ignore_errors=True)
        checks, correct = system.check(window.outcomes)
        control, control_correct = system.check(
            harness.with_control(system, window.outcomes))
        print(json.dumps({
            "workload": args.workload, "seed": seed,
            "compared": window.attempted - window.failed,
            "correct": correct,
            "program": {k: c["value"] for k, c in checks.items()},
            "control_correct": control_correct,
            "control": {k: c["value"] for k, c in control.items()},
        }), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
