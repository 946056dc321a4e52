"""Find the knee of an open-loop cell once, on the chip.

    python3 bench/sweep.py --workload fig1-serve --rates 20 30 40 --seconds 10

Sets the cell up once, then offers each rate in turn with the cell's own
traffic mix (fresh requests per step) and prints one JSON line per rate:
offered and completed rates, the backlog (requests sent and not yet
answered) at each quarter of the window, failures, and latency
percentiles from the scheduled send. The knee is the highest rate whose
backlog does not grow through the window; the cell runs at about four
fifths of it, a number written into its traffic file.
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
    ap.add_argument("--rates", type=float, nargs="+", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seed", type=int, default=1)
    args = ap.parse_args(argv)

    from bench import harness, loadgen
    from bench.stats import latencies_ms, percentile

    cell = harness.Cell(args.workload,
                        spec=harness.load_spec(pending=True))
    if cell.traffic["loop"] != "open":
        raise SystemExit("a sweep needs an open-loop cell")
    harness.use_compile_cache()
    harness.look_for_chips(cell.chips)
    workdir = tempfile.mkdtemp(prefix="bench_sweep_")
    system = cell.config_module.System(cell.config, args.seed, workdir)
    try:
        for i, rate in enumerate(args.rates):
            traffic = dict(cell.traffic, rate_per_s=rate)
            gen = loadgen.Generator(traffic, args.seed + i, system)
            gen.prepare(args.seconds)
            if i == 0:
                gen.warm_up()
            w = gen.measure(args.seconds)
            done = [o.done for o in w.outcomes if o.done is not None]
            backlog = []
            for q in (0.25, 0.5, 0.75, 1.0):
                t = w.t0 + q * args.seconds
                backlog.append(sum(1 for o in w.outcomes if o.sent <= t
                                   and (o.done is None or o.done > t)))
            lat = latencies_ms(w.outcomes)
            print(json.dumps({
                "rate_offered": len(w.outcomes) / args.seconds,
                "rate_completed_in_window":
                    sum(d <= w.t1 for d in done) / args.seconds,
                "backlog_at_quarters": backlog,
                "failed": w.failed, "attempted": w.attempted,
                "p50_ms": percentile(lat, 50), "p95_ms": percentile(lat, 95),
                "gen_late_p95_ms": percentile(
                    [1e3 * (o.sent - o.scheduled) for o in w.outcomes], 95),
            }), flush=True)
    finally:
        system.close()
        shutil.rmtree(workdir, ignore_errors=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
