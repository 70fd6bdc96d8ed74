"""Several runs of one cell in one process, for the measurements that set
the benchmark up: the knee sweep (``--rates``), the readings of the
program and of its int8 control that the limits are set from
(``--control 1``, which also prints the control's verdict against the
cell's limits as ``control_correct``), and a look at the trace (``--trace 1``). One JSON line
per run goes to stdout and to ``--out``.

    python3 bench/tools/probe.py --workload <name> --seeds 1,2,3 \\
        --seconds 20 [--rates 1.5,2,2.5] [--control 1] [--trace 1]
"""
import argparse
import io
import json
import os
import sys

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [BENCH, os.path.join(os.path.dirname(BENCH), "src")]


def backlog_trend(run):
    """Mean queue wait of the window's last third over its first third."""
    from harness.readers import window_requests
    reqs = sorted(window_requests(run), key=lambda r: r.arrival_s)
    waits = [run.admitted.get(r.rid, run.end) - r.arrival_s for r in reqs]
    n = len(waits) // 3
    if n == 0:
        return None
    first, last = sum(waits[:n]) / n, sum(waits[-n:]) / n
    return last / first if first > 0 else None


def gap_histogram(run, width_ms=10, bins=30):
    """Counts of the gaps between consecutive tokens of the window's
    requests, in ``width_ms`` bins (the last bin holds the rest), with
    their 50th/90th/99th percentiles."""
    from harness.readers import window_requests
    from harness.stats import percentile
    gaps = [1e3 * (b - a) for r in window_requests(run)
            for a, b in zip(r.token_times, r.token_times[1:])]
    counts = [0] * bins
    for g in gaps:
        counts[min(int(g // width_ms), bins - 1)] += 1
    return {"width_ms": width_ms, "counts": counts, "n": len(gaps),
            "p50": percentile(gaps, 50), "p90": percentile(gaps, 90),
            "p99": percentile(gaps, 99)}


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rates", default="")
    ap.add_argument("--set", action="append", default=[],
                    help="traffic override key=json-value, e.g. slots=16")
    ap.add_argument("--drain", type=float, default=-1,
                    help="drain limit in s (default: the traffic file's)")
    ap.add_argument("--control", type=int, default=0)
    ap.add_argument("--check", type=int, default=1)
    ap.add_argument("--trace", type=int, default=0)
    ap.add_argument("--excerpt", default="",
                    help="keep the first 2 s of the first traced window here")
    ap.add_argument("--out", default="")
    ap.add_argument("--cpu", type=int, default=0,
                    help="rehearse on the CPU (no device metrics)")
    args = ap.parse_args()
    from harness.readers import (output_tokens_per_s, queue_wait_p90_ms,
                                 tpot_p90_ms, ttft_p90_ms)
    from harness.runner import run_cell
    seeds = [int(s) for s in args.seeds.split(",")]
    rates = [float(r) for r in args.rates.split(",")] if args.rates else [None]
    root = os.getcwd()
    out = open(args.out, "a") if args.out else None
    for rate in rates:
        for seed in seeds:
            over = {}
            if rate is not None:
                over["rate_rps"] = rate
            for kv in args.set:
                k, v = kv.split("=", 1)
                over[k] = json.loads(v)
            if args.drain >= 0:
                over["drain_limit_s"] = args.drain
            full = (args.excerpt + ".full" if args.trace and args.excerpt
                    else None)
            buf = io.StringIO()
            res, run, compared = run_cell(
                root, args.workload, seed, args.seconds, bool(args.trace),
                stdout=buf, stderr=buf, traffic_overrides=over,
                control=bool(args.control), check_output=bool(args.check),
                trace_out=full, require_tpu=not args.cpu)
            line = {"workload": args.workload, "seed": seed, "rate": rate,
                    "correct": res["correct"], "attempted": res["attempted"],
                    "failed": res["failed"], "metrics": res["metrics"],
                    "device": res["device"],
                    "control_correct": res.get("control_correct"),
                    "compared": compared,
                    "ttft_p90_ms": ttft_p90_ms(run),
                    "tpot_p90_ms": tpot_p90_ms(run),
                    "queue_wait_p90_ms": queue_wait_p90_ms(run),
                    "output_tokens_per_s": output_tokens_per_s(run),
                    "backlog_trend": backlog_trend(run),
                    "gaps": gap_histogram(run),
                    "setup_s": run.setup_s,
                    "log": buf.getvalue().splitlines()[:3]}
            if "breakdown" in res:
                line["breakdown"] = res["breakdown"]
            if full and not os.path.exists(args.excerpt):
                from harness.trace_reduce import Trace
                with open(full) as f:
                    tr = Trace.from_json(json.load(f))
                with open(args.excerpt, "w") as f:
                    json.dump(tr.excerpt(2.0).to_json(), f)
                os.remove(full)
            text = json.dumps(line)
            print(text, flush=True)
            if out:
                out.write(text + "\n")
                out.flush()


if __name__ == "__main__":
    main()
