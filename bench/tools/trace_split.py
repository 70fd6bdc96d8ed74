"""Where a traced window's time goes, by the program's own spans and scopes.

    python3 bench/tools/trace_split.py --workload <name> --seed <n> \\
        [--seconds 51] [--out split.json]

Runs the cell once with the profiler on, as ``bench/run.py --trace 1``
does (its result line is printed first), keeps the trace's ``serve.*``
spans and each device op's scope path, and prints one JSON line (also
written to ``--out``): the run's end-to-end and per-layer metrics; the
host step split, milliseconds a step in each ``serve.*`` span, and the
part of ``serve.batch`` that gathers the next-token logits; idle time by
the innermost span (``idle_by_span``); device self time by top-level scope
(``device_by_scope``); the top ops with their scope paths; the device time
of ``dispatch`` a chunk step; ``serve.step`` against the benchmark's span
around the same engine call; and the program names in the window.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [BENCH, os.path.join(os.path.dirname(BENCH), "src")]


def split(run, spans, ops, k=10):
    """The numbers of one traced run (``run.trace`` aligned to the window);
    ``spans`` and ``ops`` as ``program_trace.load`` gives them."""
    from harness import program_trace as pt
    from harness import trace_reduce as trr
    lo, hi = run.trace.window
    spans = [s for s in spans if s[1] >= lo and s[2] <= hi]
    host = {n: pt.per_step_ms(spans, (n,), self_time=True)
            for n in pt.SPANS}
    busy = trr.busy_s(run.trace)
    timed = pt.op_self_times(pt.clip_ops(ops, lo, hi))
    by_op = {}
    for name, _, _, path, self_s in timed:
        t = by_op.setdefault(name, [name, 0.0, path])
        t[1] += self_s
    top_ops = sorted(by_op.values(), key=lambda x: -x[1])[:k]
    chunk = [s[2] - s[1] for s in pt.steps(spans, "chunk")]
    # ``serve.batch`` right after a chunk step: the next-token gather
    outer = [s for s in sorted(spans, key=lambda s: s[1]) if s[3] == 0]
    gather = [b[2] - b[1] for a, b in zip(outer, outer[1:])
              if a[0] == "serve.step" and a[4].get("kind") == "chunk"
              and b[0] == "serve.batch"]
    engine = [b - a for n, a, b in run.trace.host
              if n == "engine.prefill_rows" and a >= lo and b <= hi]
    out = {
        "window_s": hi - lo, "busy_s": busy, "idle_s": hi - lo - busy,
        "steps": len(pt.steps(spans)), "chunk_steps": len(chunk),
        "host_ms_per_step": host,
        "scheduler_host_ms": pt.per_step_ms(spans, pt.SCHEDULER_SPANS),
        "gather_ms_per_chunk_step": (1e3 * sum(gather) / len(chunk)
                                     if chunk else None),
        "dispatch_useful_share": pt.useful_share(spans),
        "idle_by_span": pt.idle_by_span(ops, spans, lo, hi),
        "device_by_scope": pt.device_by_scope(ops, lo, hi),
        "dispatch_device_ms": pt.scope_ms_per_step(ops, spans, "dispatch"),
        "top_ops": top_ops,
        "serve_step_over_engine_span": (
            (sum(chunk) / len(chunk)) / (sum(engine) / len(engine))
            if chunk and engine else None),
        "modules": sorted({pt.bare_name(n) for n, a, b in run.trace.modules
                           if b > lo and a < hi}),
    }
    return out


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=51.0)
    ap.add_argument("--out", default="")
    ap.add_argument("--require-tpu", type=int, default=1)
    args = ap.parse_args(argv)

    from harness import program_trace as pt
    from harness import trace_reduce as trr
    from harness.runner import process_start, run_cell
    from harness.spec import Spec

    kept = {}
    load = trr.load

    def load_both(log_dir):
        # the program's spans and the ops' scopes, before the run deletes
        # its trace directory
        kept["spans"], kept["ops"], kept["programs"] = pt.load(log_dir)
        return load(log_dir)

    trr.load = load_both
    try:
        res, run, _ = run_cell(os.getcwd(), args.workload, args.seed,
                               args.seconds, True,
                               require_tpu=bool(args.require_tpu),
                               t_start=min(T_START, process_start()))
    finally:
        trr.load = load
    spec = Spec(os.getcwd())
    cell = spec.workload(args.workload)
    metrics = {m: spec.reader(m)(run)
               for m in spec.cell_metrics(cell, "end_to_end")}
    metrics.update({m: v["value"] for m, v in res["metrics"].items()})
    out = {"workload": args.workload, "seed": args.seed,
           "correct": res["correct"], "metrics": metrics,
           **split(run, kept["spans"], kept["ops"]),
           "programs_with_hlo": kept["programs"]}
    line = json.dumps(out)
    print(line, flush=True)
    if args.out:
        os.makedirs(os.path.dirname(os.path.abspath(args.out)),
                    exist_ok=True)
        with open(args.out, "w") as f:
            f.write(line + "\n")
    return 0


if __name__ == "__main__":
    sys.exit(main())
