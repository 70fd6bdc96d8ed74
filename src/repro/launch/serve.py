"""Serving launcher — BuddyMoE engine over a trained (or random) checkpoint.

    # static one-shot batch (the paper's harness)
    PYTHONPATH=src python -m repro.launch.serve --arch deepseek-v2-lite-buddy \
        --reduced --cache-rate 0.5 --policy buddy --steps 64

    # continuous batching under Poisson load with SLOs + adaptive prefetch
    PYTHONPATH=src python -m repro.launch.serve --reduced --mode continuous \
        --num-requests 16 --arrival-rate 500 --slots 4 \
        --slo-ttft-ms 5 --slo-tpot-ms 1 --adaptive-prefetch

    # chunked prefill: joining prompts ingested 8 tokens per fused step
    PYTHONPATH=src python -m repro.launch.serve --reduced --mode continuous \
        --num-requests 16 --arrival-rate 500 --slots 4 --prefill-chunk 8

    # tiered expert store: int8 replicas of every expert stay resident, so a
    # buddy-less miss computes degraded instead of stalling on PCIe
    PYTHONPATH=src python -m repro.launch.serve --reduced --cache-rate 0.5 \
        --quant-tier int8 --steps 64

    # workload replay: arrivals + per-request token budgets from a JSONL
    # trace of {t_arrival, prompt_len, max_new_tokens} rows
    PYTHONPATH=src python -m repro.launch.serve --reduced --mode continuous \
        --trace trace.jsonl --slots 4

    # expert-parallel mesh: experts sharded across 4 devices; misses on
    # peer-owned experts borrow over ICI instead of waiting on host PCIe
    PYTHONPATH=src python -m repro.launch.serve --reduced --cache-rate 0.5 \
        --n-devices 4 --steps 64

    # flight recorder: metrics + calibration in the summary, and a Perfetto
    # trace of the run (load serve_trace.json at https://ui.perfetto.dev)
    PYTHONPATH=src python -m repro.launch.serve --reduced --mode continuous \
        --num-requests 16 --slots 4 --telemetry on --trace-out serve_trace.json

    # paged KV + radix prefix cache: shared-prefix requests adopt the
    # donated block chain and prefill only their novel suffix
    PYTHONPATH=src python -m repro.launch.serve --reduced --mode continuous \
        --num-requests 16 --slots 4 --paged-kv --kv-block 16 \
        --prefix-cache --prefill-chunk 8

    # live placement: tier coverage + replication track live traffic
    # instead of the profiling draw (runtime/placement.py)
    PYTHONPATH=src python -m repro.launch.serve --reduced --mode continuous \
        --num-requests 16 --slots 4 --quant-tier int8 --tier-coverage 0.5 \
        --placement live --placement-interval-ms 1
"""
from __future__ import annotations

import argparse
import json

import jax
import numpy as np

from repro.configs.base import get_config, get_reduced
from repro.core import BuddyPolicy, CoactivationRecorder, build_buddy_lists
from repro.launch.compile_cache import enable_compile_cache
from repro.models import transformer
from repro.runtime.cache import ExpertCache
from repro.runtime.placement import PlacementController
from repro.runtime.prefetch import (AdaptiveBudgetController,
                                    CrossLayerPredictor, PrevStepPredictor,
                                    TopFreqPredictor)
from repro.runtime.telemetry import Telemetry
from repro.runtime.tiers import TIER_BITS, TieredExpertStore
from repro.runtime.trace import export_trace
from repro.serving.engine import ServeEngine
from repro.serving.scheduler import (BurstyArrivals, ContinuousScheduler,
                                     PoissonArrivals, RequestQueue, SLOConfig,
                                     make_requests, requests_from_trace)
from repro.training.data import MarkovLM

PREDICTORS = {
    "prev-step": PrevStepPredictor,
    "top-freq": TopFreqPredictor,
    "cross-layer": CrossLayerPredictor,
}


def profile_buddies(cfg, params, lm, *, steps: int = 4, batch: int = 4,
                    seq: int = 64, alpha: float = 0.9, k_max: int = 8):
    """Offline phase: router traces -> co-activation -> CFT buddy lists."""
    import jax.numpy as jnp
    n_moe = sum(r for k, r in cfg.stack() if k == "attn_moe")
    rec = CoactivationRecorder(n_moe, cfg.moe.num_experts)
    fwd = jax.jit(lambda p, t: transformer.forward_train(p, cfg, t, record=True))
    for _ in range(steps):
        toks = jnp.asarray(lm.sample(batch, seq))
        _, aux = fwd(params, toks)
        per = aux["recorded"][0]
        for l in range(n_moe):
            rec.update(l, np.asarray(per["indices"][l]),
                       np.asarray(per["probs"][l]))
        rec.step_done()
    q = np.stack([rec.conditional(l) for l in range(n_moe)])
    return build_buddy_lists(q, alpha=alpha, k_max=k_max, activity=rec.A), rec


def parse_args(argv=None):
    """The launcher's command line (``argv`` None: ``sys.argv``)."""
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="deepseek-v2-lite-buddy")
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--checkpoint", default=None)
    ap.add_argument("--cache-rate", type=float, default=0.5)
    ap.add_argument("--policy", choices=["buddy", "random", "none"],
                    default="buddy")
    ap.add_argument("--tau", type=float, default=0.2)
    ap.add_argument("--beta", type=float, default=0.8)
    ap.add_argument("--rho", type=int, default=3)
    ap.add_argument("--alpha", type=float, default=0.9)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--steps", type=int, default=32)
    ap.add_argument("--predictor", choices=sorted(PREDICTORS),
                    default="prev-step")
    ap.add_argument("--prefetch-k", type=int, default=-1,
                    help="-1: half the cache capacity")
    ap.add_argument("--lookahead", type=int, default=1,
                    help="issue layer l+k prefetches while layer l computes")
    # -- continuous serving under load ---------------------------------
    ap.add_argument("--mode", choices=["batch", "continuous"],
                    default="batch")
    ap.add_argument("--num-requests", type=int, default=16)
    ap.add_argument("--slots", type=int, default=4,
                    help="decode slots (continuous batch width)")
    ap.add_argument("--arrival-rate", type=float, default=0.0,
                    help="requests per SIMULATED second (0: sized to ~70%% "
                         "of MEASURED decode capacity, stalls included)")
    ap.add_argument("--arrivals", choices=["poisson", "bursty"],
                    default="poisson")
    ap.add_argument("--slo-ttft-ms", type=float, default=0.0,
                    help="TTFT objective in modeled ms (0: disabled)")
    ap.add_argument("--slo-tpot-ms", type=float, default=0.0,
                    help="per-token objective in modeled ms (0: disabled)")
    ap.add_argument("--deadline-ms", type=float, default=0.0,
                    help="end-to-end deadline; with --admission slo, doomed "
                         "requests are shed instead of admitted")
    ap.add_argument("--admission", choices=["fcfs", "slo"], default="fcfs")
    ap.add_argument("--prefill-chunk", type=int, default=1,
                    help="prompt tokens ingested per fused step when a "
                         "request joins (1: token-by-token through decode)")
    ap.add_argument("--adaptive-prefetch", action="store_true",
                    help="resize prefetch budget from queue depth + stall "
                         "attribution instead of the fixed --prefetch-k")
    ap.add_argument("--trace", default=None,
                    help="JSONL serving trace ({t_arrival, prompt_len, "
                         "max_new_tokens} rows) replayed with per-request "
                         "token budgets (--mode continuous)")
    # -- tiered expert store (compressed resident replicas) -------------
    ap.add_argument("--quant-tier", choices=["off", "int8", "int4"],
                    default="off",
                    help="keep a low-precision replica of EVERY expert "
                         "resident so a buddy-less miss computes degraded "
                         "instead of stalling; the tier displaces full-"
                         "precision cache slots from the --cache-rate budget")
    ap.add_argument("--tier-stall-per-fidelity", type=float, default=0.05,
                    help="seconds of expected stall that justify one unit "
                         "of relative quantization error when deciding "
                         "degrade-vs-wait on a miss (precedence mode)")
    ap.add_argument("--tier-coverage", type=float, default=1.0,
                    help="fraction of experts per layer holding a resident "
                         "replica (top-P(use) from the profiling activity "
                         "stats); the freed bytes become full cache slots")
    # -- unified expected-cost miss policy (runtime/costs.py) -----------
    ap.add_argument("--miss-policy", choices=["precedence", "cost"],
                    default="precedence",
                    help="'precedence': fixed buddy->degraded->fetch/drop "
                         "chain; 'cost': per-slot argmin of the unified "
                         "expected-cost model — buddy Psi loss, replica "
                         "fidelity, fetch ETA, and drop loss scored on one "
                         "stall-seconds scale")
    ap.add_argument("--stall-per-quality", type=float, default=0.05,
                    help="the single exchange rate: seconds of stall worth "
                         "one unit of quality loss (generalizes "
                         "--tier-stall-per-fidelity across all outcomes)")
    ap.add_argument("--drop-loss", type=float, default=1.0,
                    help="quality units lost by dropping a routed slot "
                         "(cost mode's drop outcome)")
    ap.add_argument("--upgrade-degraded", choices=["auto", "on", "off"],
                    default="auto",
                    help="degraded-then-upgrade: background-fetch the true "
                         "expert after serving its slot from the quant tier "
                         "(auto: on exactly when --miss-policy cost and a "
                         "tier is attached)")
    ap.add_argument("--fused-dispatch", action="store_true",
                    help="single-dispatch hot path: compute full-precision,"
                         " buddy, and degraded slots in ONE grouped step "
                         "(kernels/grouped_ffn.py) instead of three "
                         "dispatches; off = bit-identical pre-fused graph")
    # -- paged KV + radix-tree prefix cache (runtime/paged_kv.py) --------
    ap.add_argument("--paged-kv", action="store_true",
                    help="block-pooled KV cache: rows map fixed-size blocks "
                         "through per-row tables (ref-counted, copy-on-"
                         "write); off = the ring layout, bit-identical")
    ap.add_argument("--kv-block", type=int, default=16,
                    help="tokens per KV block (--paged-kv)")
    ap.add_argument("--kv-blocks", type=int, default=0,
                    help="pool size in blocks (0: exact ring-cache "
                         "footprint, so paged vs ring runs at equal HBM)")
    ap.add_argument("--prefix-cache", action="store_true",
                    help="radix-tree prefix cache over the paged pool: "
                         "retiring requests donate their block chains; "
                         "admitted requests adopt the longest cached prefix "
                         "and prefill only the novel suffix (requires "
                         "--paged-kv)")
    ap.add_argument("--adaptive-chunk", action="store_true",
                    help="per-request prefill chunk policy: halve the "
                         "chunk while the estimated chunk-step time would "
                         "blow co-resident decode rows' TPOT budget")
    # -- expert-parallel mesh (peer-HBM borrowing over ICI) --------------
    ap.add_argument("--n-devices", type=int, default=1,
                    help="expert-parallel mesh size (1-8): experts shard "
                         "round-robin across devices; a miss on an expert a "
                         "peer holds borrows it over that device's ICI link "
                         "— the fifth miss outcome (1: single-device, "
                         "bit-identical to the pre-mesh engine)")
    ap.add_argument("--ici-gbps", type=float, default=0.0,
                    help="per-ICI-link bandwidth in GB/s (0: hardware "
                         "model default)")
    ap.add_argument("--no-peer-borrow", action="store_true",
                    help="mesh ablation: shard experts but resolve misses "
                         "with the four single-device outcomes only")
    # -- live placement (runtime/placement.py) ---------------------------
    ap.add_argument("--placement", choices=["off", "live"], default="off",
                    help="live traffic->placement loop: every refresh "
                         "window of SIMULATED time, re-pick the quant "
                         "tier's covered experts from live activity EMAs, "
                         "background-replicate persistently-hot experts "
                         "('replicate' cause, prefetch priority), and on a "
                         "mesh push hot experts to underloaded peers "
                         "('off' is the exact pre-placement code path — "
                         "bit-identical)")
    ap.add_argument("--placement-interval-ms", type=float, default=1.0,
                    help="simulated ms between placement ticks")
    ap.add_argument("--placement-hot-windows", type=int, default=3,
                    help="hysteresis: consecutive hot windows an expert "
                         "needs before it earns a replica")
    ap.add_argument("--placement-top-k", type=int, default=0,
                    help="experts per layer counted as hot each window "
                         "(0: half the cache capacity)")
    # -- observability (runtime/telemetry.py + runtime/trace.py) ---------
    ap.add_argument("--telemetry", choices=["off", "on"], default="off",
                    help="attach the flight recorder: metrics registry, "
                         "miss-cost calibration, and prefetch meters in the "
                         "final summary ('off' runs the exact pre-telemetry "
                         "code path — bit-identical outputs and timeline)")
    ap.add_argument("--trace-out", default=None, metavar="PATH",
                    help="write the event log here after the run (implies "
                         "--telemetry on): '*.jsonl' = lossless JSONL, "
                         "anything else = Chrome/Perfetto trace_event JSON "
                         "— load it at https://ui.perfetto.dev or "
                         "chrome://tracing")
    ap.add_argument("--prefetch-min-saving", type=float, default=-1.0,
                    help="cost-ranked prefetch: skip candidates whose "
                         "expected stall saved (P(use) x miss cost) is at "
                         "or below this many seconds (<0: auto = 1%% of a "
                         "full expert transfer)")
    args = ap.parse_args(argv)
    if args.lookahead < 1:
        ap.error("--lookahead must be >= 1 (layers ahead to prefetch)")
    if args.prefill_chunk < 1:
        ap.error("--prefill-chunk must be >= 1 (prompt tokens per fused step)")
    if args.trace and args.mode != "continuous":
        ap.error("--trace replays a request stream: use --mode continuous")
    if not 1 <= args.n_devices <= 8:
        ap.error("--n-devices must be in 1..8")
    if args.prefix_cache and not args.paged_kv:
        ap.error("--prefix-cache shares KV at block granularity: it "
                 "requires --paged-kv")
    return args


def main():
    args = parse_args()
    enable_compile_cache()
    cfg = get_reduced(args.arch) if args.reduced else get_config(args.arch)
    serve(cfg, args)


def serve(cfg, args):
    """Build the engine for ``cfg`` (random weights from seed 0, or
    ``--checkpoint``), profile buddies, and serve as ``args`` say.

    Returns (engine, summary, outputs): the scheduler's summary in
    continuous mode and the engine's in batch mode; ``outputs`` holds the
    generated token ids of each request (continuous) or batch row."""
    assert cfg.is_moe, "serving engine targets MoE archs"
    key = jax.random.PRNGKey(0)
    params = transformer.init_params(cfg, key)
    if args.checkpoint:
        from repro.checkpoint.io import load_pytree
        params = load_pytree(args.checkpoint, params)

    lm = MarkovLM(cfg.vocab_size, seed=0)
    tables, rec = profile_buddies(cfg, params, lm, alpha=args.alpha)
    n_moe = sum(r for k, r in cfg.stack() if k == "attn_moe")
    policy = BuddyPolicy(tau=args.tau, beta=args.beta, rho=args.rho,
                         mode=args.policy, quant_tier=args.quant_tier,
                         miss_policy=args.miss_policy,
                         stall_per_quality=args.stall_per_quality,
                         drop_loss=args.drop_loss,
                         use_fused_dispatch=args.fused_dispatch)
    tier = None
    if args.quant_tier != "off":
        tier = TieredExpertStore(
            n_moe, cfg.moe.num_experts, args.cache_rate,
            bits=TIER_BITS[args.quant_tier], d_model=cfg.d_model,
            d_ff=cfg.moe.d_ff,
            stall_per_fidelity=args.tier_stall_per_fidelity,
            coverage=args.tier_coverage)
        if args.tier_coverage < 1.0:
            # partial coverage: replicate the top-P(use) experts per layer,
            # ranked by the profiling run's activation counts
            tier.set_coverage(rec.A)
        cache = tier.cache
        print(f"[serve] quant tier {args.quant_tier}: "
              f"{tier.budget_split()}")
    else:
        cache = ExpertCache(n_moe, cfg.moe.num_experts, args.cache_rate)
    prefetch_k = (max(1, cache.capacity // 2) if args.prefetch_k < 0
                  else args.prefetch_k)
    predictor = PREDICTORS[args.predictor](n_moe, cfg.moe.num_experts)
    upgrade = {"auto": None, "on": True, "off": False}[args.upgrade_degraded]
    tele = None
    if args.telemetry == "on" or args.trace_out:
        make = Telemetry.with_trace if args.trace_out else Telemetry
        tele = make(predictor_label=args.predictor, num_layers=n_moe,
                    num_experts=cfg.moe.num_experts)
    placement = None
    if args.placement == "live":
        placement = PlacementController(
            refresh_interval_s=args.placement_interval_ms * 1e-3,
            hot_windows=args.placement_hot_windows,
            hot_top_k=args.placement_top_k or None)
    eng = ServeEngine(cfg, params, tables=tables, policy=policy,
                      cache=None if tier is not None else cache, tier=tier,
                      predictor=predictor, prefetch_k=prefetch_k,
                      lookahead=args.lookahead, upgrade_degraded=upgrade,
                      prefetch_min_saving=(None if args.prefetch_min_saving
                                           < 0 else args.prefetch_min_saving),
                      telemetry=tele,
                      n_devices=args.n_devices,
                      ici_gbps=args.ici_gbps if args.ici_gbps > 0 else None,
                      peer_borrow=not args.no_peer_borrow,
                      paged_kv=args.paged_kv, kv_block=args.kv_block,
                      kv_blocks=args.kv_blocks if args.kv_blocks > 0 else None,
                      prefix_cache=args.prefix_cache, placement=placement)

    if args.mode == "continuous":
        s, outputs = _serve_continuous(args, cfg, eng, lm, prefetch_k)
        return eng, s, outputs

    prompts = lm.sample(args.batch, 8)
    out = eng.generate(prompts, max_new_tokens=args.steps)
    s = eng.summary()
    print(json.dumps(s, indent=1, default=str))
    bd = s["stall_breakdown"]
    print(f"stalls: demand {bd['demand_stall_s']*1e3:.2f}ms  "
          f"late-prefetch {bd['late_prefetch_stall_s']*1e3:.2f}ms  "
          f"overlapped {bd['overlapped_s']*1e3:.2f}ms")
    if "tier" in s:
        t = s["tier"]
        print(f"tier: {t['degraded_tokens']} degraded slots at "
              f"{t['bits']}-bit, {t['quant_bytes']/1e6:.1f}MB resident, "
              f"{t['tier_budget_split']['cache_slots_per_layer']} full "
              f"slots/layer left")
    _report_mesh(s)
    _report_placement(s)
    print("sample output tokens:", out[0, -16:].tolist())
    _report_telemetry(eng.telemetry, args.trace_out)
    return eng, s, list(out[:, prompts.shape[1]:])


def _report_mesh(s):
    """Per-link utilization digest for mesh runs (no-op at n_devices=1)."""
    if "mesh" not in s:
        return
    m = s["mesh"]
    print(f"[mesh] {m['n_devices']} devices, peer-borrow "
          f"{'on' if m['peer_borrow'] else 'off'}: "
          f"{m['n_peer_borrow']} borrows ({m['peer_share']*100:.1f}% of "
          f"served slots), peer stall {m['peer_stall_s']*1e3:.2f}ms")
    for link in m["links"]:
        by = ", ".join(f"{k} {v/1e6:.2f}MB"
                       for k, v in link["bytes_by_cause"].items())
        print(f"[mesh]   {link['name']}: busy {link['busy_s']*1e3:.2f}ms, "
              f"queue {link['queue_depth']}, {by or 'idle'}")


def _report_placement(s):
    """Live-placement digest (absent on placement=off engines)."""
    if "placement" not in s:
        return
    p = s["placement"]
    print(f"[placement] {p['n_ticks']} ticks every "
          f"{p['refresh_interval_s']*1e3:.2f}ms: "
          f"{p['coverage_repicks']} coverage re-picks, "
          f"{p['replicas_issued']} replicas issued "
          f"({p['active_replicas']} live, "
          f"{p['replicas_reclaimed']} reclaimed), "
          f"{p['peer_pushes']} peer pushes")


def _report_telemetry(tele, trace_out):
    """One-line calibration + prefetch digest, then the --trace-out export
    (the full nested summary is already inside the engine summary JSON)."""
    if tele is None:
        return
    cal = tele.calibration.summary()
    parts = []
    for o, c in cal.items():
        p = f"{o} n={c['n']}"
        if c["n"]:
            p += f" |resid| {c['residual_abs_mean_s']*1e3:.3f}ms"
        parts.append(p)
    print("[telemetry] calibration: " + "; ".join(parts))
    pf = tele.prefetch.summary()
    print(f"[telemetry] prefetch[{pf['predictor']}]: precision "
          f"{pf['precision']:.3f} recall {pf['recall']:.3f} issued "
          f"{pf['issued']} used-in-time {pf['used_in_time']} late "
          f"{pf['late']} expected-saved "
          f"{pf['expected_stall_saved_s']*1e3:.2f}ms")
    if trace_out:
        n = export_trace(tele.trace, trace_out)
        kind = "JSONL" if trace_out.endswith(".jsonl") else "Perfetto"
        print(f"[telemetry] wrote {n} {kind} trace events to {trace_out}")


def _serve_continuous(args, cfg, eng, lm, prefetch_k):
    """Drive the engine with continuously arriving requests + SLOs.
    Returns (summary, generated token ids of each completed request)."""
    slo = SLOConfig(
        ttft_s=args.slo_ttft_ms * 1e-3 if args.slo_ttft_ms > 0 else None,
        tpot_s=args.slo_tpot_ms * 1e-3 if args.slo_tpot_ms > 0 else None,
        deadline_s=args.deadline_ms * 1e-3 if args.deadline_ms > 0 else None)
    if args.trace:
        # workload replay: recorded arrivals + per-request token budgets
        reqs = requests_from_trace(args.trace,
                                   lambda n: lm.sample(1, max(1, n))[0], slo)
        print(f"[serve] replaying {len(reqs)} requests from {args.trace}")
    else:
        rng = np.random.default_rng(1)
        prompts = [lm.sample(1, int(rng.integers(4, 9)))[0]
                   for _ in range(args.num_requests)]
        rate = args.arrival_rate
        if rate <= 0:
            # ~70% of MEASURED capacity: probe an unloaded generate so the
            # step time includes transfer stalls (the compute-only estimate
            # is far too optimistic in the transfer-bound regime), then
            # reset the engine's runtime state for the real run
            eng.generate(lm.sample(args.slots, 4), max_new_tokens=8)
            step_s = eng.stats.sim_time_s / max(1, eng.stats.steps)
            eng.reset_runtime()
            per_req = (8 + args.steps) * step_s
            rate = 0.7 * args.slots / per_req
            print(f"[serve] auto arrival rate: {rate:.1f} req/s "
                  f"(measured step {step_s*1e3:.3f}ms)")
        proc = (PoissonArrivals(rate, seed=2) if args.arrivals == "poisson"
                else BurstyArrivals(rate, seed=2))
        reqs = make_requests(prompts, proc, args.steps, slo)
    queue = RequestQueue(reqs, admission=args.admission)
    ctrl = None
    if args.adaptive_prefetch and prefetch_k > 0:
        ctrl = AdaptiveBudgetController(
            prefetch_k=prefetch_k, lookahead=args.lookahead,
            max_k=max(2 * prefetch_k, 4),
            max_lookahead=max(4, args.lookahead))
    sched = ContinuousScheduler(eng, slots=args.slots, controller=ctrl,
                                prefill_chunk=args.prefill_chunk,
                                adaptive_chunk=args.adaptive_chunk)
    s = sched.run(queue)
    print(json.dumps(s, indent=1, default=str))
    print(f"completed {s['completed']}/{s['num_requests']} "
          f"(rejected {s['rejected']})  "
          f"TTFT p50/p99 {s['ttft_s']['p50']*1e3:.2f}/"
          f"{s['ttft_s']['p99']*1e3:.2f}ms  "
          f"goodput {s['goodput_rps']:.1f} req/s  "
          f"SLO-met {s['slo_met_frac']*100:.0f}%")
    _report_mesh(s.get("engine", eng.summary()))
    _report_prefix(s.get("engine", {}))
    _report_placement(s.get("engine", {}))
    _report_telemetry(eng.telemetry, args.trace_out)
    return s, [np.asarray(r.tokens) for r in sched.completed]


def _report_prefix(s):
    """Pool/CoW/tree digest for paged-KV runs (absent on ring engines)."""
    if "prefix" not in s:
        return
    px = s["prefix"]
    occ = px["pool"]
    line = (f"[paged-kv] block {px['kv_block']}: "
            f"{occ['used_blocks']}/{occ['n_blocks']} blocks used, "
            f"{occ['cow_copies']} CoW copies, {occ['evictions']} evictions")
    if px.get("tree") is not None:
        line += (f"; prefix cache: {px['hits']} hits, "
                 f"{px['hit_tokens']} tokens adopted / "
                 f"{px['novel_tokens']} novel, tree "
                 f"{px['tree']['nodes']} nodes")
    print(line)


if __name__ == "__main__":
    main()
