"""Run one cell of the benchmark once and print its result as one JSON line.

    python3 bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Run from the root of a checkout that holds BENCHMARK.json. Needs the chips
the cell asks for (exit 3 and no result otherwise). ``--trace 1`` reports
the cell's per-layer metrics, read from a profiler trace of the window;
``--trace 0`` its end-to-end metrics.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
sys.path[:0] = [BENCH, os.path.join(ROOT, "src")]


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=[0, 1], default=0)
    args = ap.parse_args(argv)
    if not os.path.isdir(os.path.join(ROOT, "src", "repro")):
        print(f"bench: no program under test at {ROOT}/src/repro",
              file=sys.stderr)
        return 3
    from harness.runner import NoChip, process_start, run_cell
    try:
        run_cell(os.getcwd(), args.workload, args.seed, args.seconds,
                 bool(args.trace), t_start=min(T_START, process_start()))
    except (NoChip, FileNotFoundError) as e:
        print(f"bench: {e}", file=sys.stderr)
        return 3
    return 0


if __name__ == "__main__":
    sys.exit(main())
