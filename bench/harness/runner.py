"""One run of one cell: set-up, traffic, the measured window, the drain,
the metrics, and the comparison with the reference.

Set-up builds the weights and buddy tables from the seed, builds the engine
as ``launch/serve.py`` builds it, and runs every program the window uses
once at the window's shapes. Traffic then runs through the program's
``ContinuousScheduler`` for ``warmup_s`` before the window opens, so the
slots are in steady state. Requests due in the window are followed to
their end (with the traffic still arriving); in a closed backlog the run
stops at the window's end.
"""
from __future__ import annotations

import dataclasses
import gc
import json
import os
import shutil
import sys
import tempfile
import time

import numpy as np

from harness.spec import Spec
from harness.traffic import make_items

clock = time.perf_counter


def process_start() -> float:
    """The process's start on the ``perf_counter`` clock."""
    try:
        with open("/proc/self/stat") as f:
            ticks = int(f.read().rsplit(")", 1)[1].split()[19])
        with open("/proc/uptime") as f:
            up = float(f.read().split()[0])
        return clock() - (up - ticks / os.sysconf("SC_CLK_TCK"))
    except (OSError, ValueError, IndexError):
        return clock()


@dataclasses.dataclass
class Run:
    """What the metric readers read."""
    requests: list
    window_rids: set
    admitted: dict
    emitted: list
    w0: float
    w1: float
    end: float
    setup_s: float
    flops_window: float
    trace: object = None
    peak: dict = None
    chips: int = 1


class NoChip(RuntimeError):
    pass


def devices_for(chips: int, require_tpu: bool):
    import jax
    devs = jax.devices()
    if require_tpu and (devs[0].platform != "tpu" or len(devs) < chips):
        raise NoChip(f"this cell needs {chips} TPU chip(s); JAX finds "
                     f"{len(devs)} {devs[0].platform} device(s)")
    return devs[:chips]


def build_engine(cfg_file: dict, traffic: dict, seed: int):
    """The engine as ``launch/serve.py`` builds it (policy, cache, prefetch
    predictor and budget), on the benchmark's weights and tables."""
    from repro.configs.base import ModelConfig, MoEConfig
    from repro.core import BuddyPolicy
    from repro.core.buddies import BuddyTables
    from repro.runtime.cache import ExpertCache
    from repro.runtime.prefetch import PrevStepPredictor

    from harness.model import buddy_tables, build_params
    from harness.serving import BenchEngine
    m = cfg_file["model"]
    cfg = ModelConfig(
        arch_id=cfg_file["name"], family="moe", source=cfg_file["source"],
        num_layers=m["num_layers"], d_model=m["d_model"],
        num_heads=m["num_heads"], num_kv_heads=m["num_kv_heads"],
        d_ff=m["d_ff"], vocab_size=m["vocab_size"], head_dim=m["head_dim"],
        moe=MoEConfig(**m["moe"]), sliding_window=m["sliding_window"],
        rope_theta=m["rope_theta"], norm_eps=m["norm_eps"], dtype=m["dtype"])
    params = build_params(seed, m)
    r = cfg_file["buddy"]["list_len"]
    table, q = buddy_tables(seed, m, r)
    tables = BuddyTables(table, q, np.full(table.shape[:2], r, np.int32))
    policy = BuddyPolicy(mode=traffic["policy"],
                         **cfg_file["buddy"]["policy"])
    n_l, e = m["num_layers"], m["moe"]["num_experts"]
    cache = ExpertCache(n_l, e, traffic["cache_rate"])
    eng = BenchEngine(cfg, params, tables=tables, policy=policy, cache=cache,
                      predictor=PrevStepPredictor(n_l, e),
                      prefetch_k=max(1, cache.capacity // 2), lookahead=1)
    return eng, table


def warm_up(eng, slots: int, chunk: int, ctx: int):
    """Run each program of the window once at its shapes (the prefill
    chunk, the decode step, sampling, the admission's cache reset for every
    count of rows), then give the engine a fresh runtime state. Returns the
    KV cache's leaf types."""
    import jax
    import jax.numpy as jnp
    b = slots
    caches = eng.init_caches(b, ctx)
    rows = np.ones(b, bool)
    logits, caches = eng.prefill_rows(
        jnp.asarray(np.zeros((b, chunk), np.int64), jnp.int32), rows, caches,
        base_pos=np.zeros(b, np.int32), tok_valid=np.ones((b, chunk), bool))
    n_feed = np.full(b, chunk, np.int32)
    eng.sample_tokens(logits[jnp.arange(b), jnp.maximum(n_feed - 1, 0)],
                      True)
    logits, caches = eng.step(jnp.asarray(np.zeros(b, np.int64), jnp.int32),
                              caches, np.full(b, chunk, np.int32),
                              active=rows)
    eng.sample_tokens(logits, True)
    for n in range(1, b + 1):
        caches = eng.reset_rows(caches, list(range(n)))
    jax.block_until_ready(caches)
    cache_dtypes = [x.dtype for x in jax.tree.leaves(caches)]
    del caches, logits
    eng.reset_runtime()
    return cache_dtypes


def step_flops(m: dict, steps: list, w0: float, w1: float) -> float:
    from harness.flops import chunk_flops
    total = 0.0
    for st in steps:
        if not (w0 <= st["t1"] < w1):
            continue
        for base, n in zip(st["pos"], st["counts"]):
            if n:
                total += chunk_flops(m, int(base), int(n))
    return total


def run_cell(root, name: str, seed: int, seconds: float, trace: bool, *,
             require_tpu: bool = True, fault: str = None,
             stdout=sys.stdout, stderr=sys.stderr, t_start: float = None,
             traffic_overrides: dict = None, control: bool = False,
             check_output: bool = True, trace_out: str = None):
    """Run ``name`` once and print its result line. Returns the result,
    the ``Run`` its metrics were read from, and the compared numbers.

    For the scripts in ``bench/tools`` and the tests: ``traffic_overrides``
    replace keys of the traffic file, ``control`` also reads the int8
    control (its verdict goes into the result as ``control_correct``),
    ``check_output`` False skips the comparison, ``trace_out`` keeps the
    trace's events in that JSON file, and ``fault`` plants a fault."""
    t_start = process_start() if t_start is None else t_start
    spec = Spec(root)
    cell = spec.workload(name)
    cfg_file, traffic = spec.config(cell), spec.traffic(cell)
    traffic.update(traffic_overrides or {})
    devs = devices_for(cell["chips"], require_tpu)

    import jax
    from jax import monitoring
    from repro.serving.scheduler import FINISHED, ServeRequest

    from harness import check
    from harness import trace_reduce as trr
    from harness.peaks import peak
    from harness.serving import BenchScheduler, Recorder, WallQueue

    if require_tpu:
        from repro.launch.compile_cache import enable_compile_cache
        enable_compile_cache()
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    compiles = []

    def on_event(ev, dur, **kw):
        if "backend_compile" in ev:
            compiles.append(clock())
    monitoring.register_event_duration_secs_listener(on_event)

    m = cfg_file["model"]
    slots, chunk = int(traffic["slots"]), int(traffic["prefill_chunk"])
    eng, table = build_engine(cfg_file, traffic, seed)
    eng.fault = fault
    if fault == "narrow_storage":        # the norm scales in bfloat16
        eng.params = jax.tree.map(
            lambda x: x.astype("bfloat16") if x.ndim == 1 else x, eng.params)
    rec = Recorder()
    eng.rec = rec
    rec.rows = lambda: [-1] * slots
    items = make_items(traffic, seed, seconds, m["vocab_size"])
    ctx = max(len(it.prompt) + it.max_new for it in items)
    in_use = [(devs[0].memory_stats() or {}).get("bytes_in_use", 0)]
    cache_dtypes = warm_up(eng, slots, chunk, ctx)
    narrow = check.narrow_leaves([eng.params, cache_dtypes], m["dtype"])
    in_use.append((devs[0].memory_stats() or {}).get("bytes_in_use", 0))
    print(f"set-up: {clock() - t_start:.3f} s, device bytes in use with the "
          f"weights {in_use[0]}, after warm-up {in_use[1]}", file=stdout)

    tdir = None
    if trace:
        tdir = tempfile.mkdtemp(prefix="bench-trace-")
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(tdir, profiler_options=opts)
    mark = len(rec.spans)

    t0 = clock()
    reqs = [ServeRequest(rid=i, prompt=it.prompt, max_new_tokens=it.max_new,
                         arrival_s=t0 + it.offset_s)
            for i, it in enumerate(items)]
    window_rids = {i for i, it in enumerate(items) if it.in_window}
    w0 = t0 + float(traffic["warmup_s"])
    w1 = w0 + seconds
    closed = traffic["arrivals"] == "closed"
    drain_end = w1 + float(traffic.get("drain_limit_s", 0))

    def done(now):
        if now < w1:
            return False
        if closed or now >= drain_end:
            return True
        return all(reqs[i].state == FINISHED for i in window_rids)

    queue = WallQueue(reqs, sim_now=lambda: eng.scheduler.now)
    sched = BenchScheduler(eng, slots, prefill_chunk=chunk, done=done,
                           rec=rec)
    rec.keep = True
    sched.run(queue)
    end = clock()
    rec.keep = False
    if trace:
        jax.profiler.stop_trace()

    monitoring.unregister_event_duration_listener(on_event)
    in_window = [t for t in compiles if w0 <= t < w1]
    mem = [d.memory_stats() or {} for d in devs]
    mem_peak = max(s.get("peak_bytes_in_use", 0) for s in mem)
    attempted = (sum(1 for r in reqs if r.admitted_s >= 0) if closed
                 else len(window_rids))
    failed = (0 if closed else
              sum(1 for i in window_rids if reqs[i].state != FINISHED))
    run = Run(requests=reqs, window_rids=window_rids,
              admitted=queue.admitted, emitted=sched.emitted, w0=w0, w1=w1,
              end=end, setup_s=w0 - t_start,
              flops_window=step_flops(m, rec.steps, w0, w1),
              chips=len(devs))
    kind = devs[0].device_kind
    try:
        run.peak = peak(kind)
    except KeyError:
        if require_tpu:
            raise
    if trace:
        run.trace = trr.load(tdir)
        trr.align(run.trace, rec.spans[mark:], w0, w1)
        shutil.rmtree(tdir, ignore_errors=True)
        if trace_out:
            with open(trace_out, "w") as f:
                json.dump(run.trace.to_json(), f)

    done_window = [reqs[i] for i in sorted(window_rids)
                   if reqs[i].state == FINISHED]
    print(f"window: {seconds} s, requests due {len(window_rids) if not closed else '-'}, "
          f"completed {len(done_window)}, failed {failed}, tokens "
          f"{sum(1 for t in sched.emitted if w0 <= t < w1)}, steps "
          f"{sum(1 for st in rec.steps if w0 <= st['t1'] < w1)}, "
          f"compiles in window {len(in_window)}, drain "
          f"{max(0.0, end - w1):.3f} s", file=stdout)

    kind_metrics = "per_layer" if trace else "end_to_end"
    metrics = {}
    for mname in spec.cell_metrics(cell, kind_metrics):
        v = spec.reader(mname)(run)
        if v is not None:
            metrics[mname] = {"value": float(v),
                              "unit": spec.metrics[mname]["unit"]}

    # -- the comparison, once the program's state is freed -------------
    steps = rec.steps
    del sched, queue, eng, rec
    gc.collect()
    sample = check.sample_requests(done_window, seed,
                                   int(traffic["check_tokens"]), steps)
    if not check_output:
        sample = []
    t_check = clock()
    compared = check.compare(cfg_file, spec.root, seed, sample, steps, table,
                             control=control, pad_to=ctx)
    compared["narrow_leaves"] = narrow
    limits, at_least = cfg_file["limits"], traffic.get("at_least", {})
    print(f"check: {compared['requests']} requests, {compared['tokens']} "
          f"served tokens, {compared['positions']} positions, "
          f"{clock() - t_check:.3f} s", file=stdout)
    rows = check.report(compared, limits, at_least, stream=stderr)

    device = {"platform": devs[0].platform, "kind": kind, "count": len(devs),
              "memory_peak_bytes": int(mem_peak)}
    result = {"correct": bool(check.passes(compared, limits, at_least)),
              "attempted": int(attempted), "failed": int(failed),
              "metrics": metrics, "device": device}
    if trace:
        lo, hi = run.trace.window
        device["busy_s"] = trr.busy_s(run.trace)
        device["window_s"] = hi - lo
        result["breakdown"] = {"device_ops": trr.top_ops(run.trace),
                               "idle_gaps": trr.idle_gaps(run.trace)}
    if control:
        result["control_correct"] = check.control_passes(compared, limits)
    result["compared"] = rows
    print(json.dumps(result), file=stdout, flush=True)
    return result, run, compared
