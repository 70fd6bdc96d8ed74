"""Chip smoke test: BuddyMoE serving at DeepSeek-V2-Lite width on one TPU.

    python3 chip_smoke.py

One process, no subprocesses. The phases, in order:

  device   fail unless JAX's first device is a TPU
  kernels  the five MoE Pallas kernels, compiled by Mosaic at real widths,
           against the jnp oracles of kernels/ref.py
  serve    deepseek-v2-lite-buddy cut to 4 layers (every width as
           published, float32, random weights from seed 0) through the code
           of `python -m repro.launch.serve --mode continuous --cache-rate
           0.5 --policy buddy`: 8 requests over 4 slots, 16 new tokens each
  timing   wall time of the served decode step: its first call at a new
           shape (compile) and steady steps, each ended by block_until_ready
  check    decode-step logits against forward_train logits on one prompt at
           full expert residency, both at "highest" matmul precision

Numbers from the engine's simulated clock are printed under ``sim_`` names;
they are a model, not a measurement. The last line of stdout is one JSON
object, ``{"ok": true, "device": {...}}``; any failure exits non-zero
before it is printed.
"""
from __future__ import annotations

import contextlib
import dataclasses
import io
import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

ARCH = "deepseek-v2-lite-buddy"
NUM_LAYERS = 4              # of the published 27: what one 16 GB chip holds
SLOTS, REQUESTS, NEW_TOKENS = 4, 8, 16
SERVE_ARGV = ["--arch", ARCH, "--mode", "continuous", "--cache-rate", "0.5",
              "--policy", "buddy", "--num-requests", str(REQUESTS),
              "--slots", str(SLOTS), "--steps", str(NEW_TOKENS)]
KERNEL_TOKENS = 8
TIMING_CONTEXT = 64         # KV positions of the timed decode step
TIMED_STEPS = 16
CHECK_LEN = 12              # prompt tokens of the logits check

# Tolerances. The FFN kernels' error is max|kernel - ref| / max|ref|: an
# f32 matmul on the MXU may run as bf16 passes (~2^-9 relative per
# operand), while a tiling or indexing fault errs by O(1). The gate's and
# the substitution's integer outputs must match exactly; the gate's
# probabilities and entropy in [0, 1] may differ by the TPU's approximate
# exp, log and reciprocal (3.5e-5 seen on a v5e), a wrong slot by O(0.1).
FFN_TOL = 1e-2
GATE_TOL = 1e-4
# Decode vs forward_train, both at "highest" precision: only the order of
# f32 sums differs (~1e-6 relative). One-pass bf16 matmuls on either side
# err by ~1e-3 relative, and a KV-cache or attention fault by O(1).
LOGITS_TOL = 1e-4


class SmokeFailure(RuntimeError):
    pass


def _require(ok: bool, what: str) -> None:
    if not ok:
        raise SmokeFailure(what)


def check_device() -> dict:
    devs = jax.devices()
    d = devs[0]
    if d.platform != "tpu":
        raise SmokeFailure(f"no TPU: JAX's first device is {d.platform!r}")
    print(f"[device] platform {d.platform}, kind {d.device_kind}, "
          f"count {len(devs)}")
    return {"platform": d.platform, "kind": d.device_kind, "count": len(devs)}


def smoke_config():
    from repro.configs.base import get_config
    cfg = dataclasses.replace(get_config(ARCH), num_layers=NUM_LAYERS)
    m = cfg.moe
    print(f"[config] {ARCH}: num_layers {cfg.num_layers} of "
          f"{get_config(ARCH).num_layers} (cut to fit one chip); as "
          f"published: d_model {cfg.d_model}, heads {cfg.num_heads}, "
          f"{m.num_experts} experts x d_ff {m.d_ff}, top-{m.top_k}, "
          f"{m.num_shared_experts} shared, vocab {cfg.vocab_size}; "
          f"{cfg.dtype}; random weights, seed 0")
    return cfg


# ---------------------------------------------------------------------------
# kernels
# ---------------------------------------------------------------------------
def _rel_err(got, want) -> float:
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    return float(np.abs(got - want).max() / max(np.abs(want).max(), 1e-30))


def _report(name: str, err: float, tol: float, what: str) -> None:
    print(f"[kernel] {name}: {what} {err:.3e} (tol {tol:.0e})")
    _require(err <= tol, f"kernel {name}: {what} {err:.3e} > tol {tol:.0e}")


def _buddy_inputs(rng, t, e, k, r):
    s = np.stack([rng.choice(e, k, replace=False) for _ in range(t)])
    table = np.full((e, r), -1, np.int32)
    q = np.zeros((e, r), np.float32)
    for i in range(e):
        n = int(rng.integers(1, r + 1))
        table[i, :n] = rng.choice([x for x in range(e) if x != i], n,
                                  replace=False)
        q[i, :n] = np.sort(rng.random(n))[::-1]
    return (s.astype(np.int32), rng.random(t) < 0.7, rng.random(e) < 0.5,
            table, q)


def run_kernels(cfg, *, tokens: int = KERNEL_TOKENS,
                interpret: bool = False) -> dict:
    """Each MoE kernel once at the config's widths, checked against its
    oracle computed at "highest" matmul precision. Returns the errors."""
    from repro.kernels import ref
    from repro.kernels.buddy_substitute import buddy_substitute_pallas
    from repro.kernels.expert_ffn import expert_ffn_pallas
    from repro.kernels.grouped_ffn import grouped_ffn_pallas
    from repro.kernels.quant_ffn import quant_ffn_pallas
    from repro.kernels.topk_gate import topk_gate_pallas

    e, d, f, k = (cfg.moe.num_experts, cfg.d_model, cfg.moe.d_ff,
                  cfg.moe.top_k)
    t = tokens
    errs = {}
    hi = jax.default_matmul_precision("highest")

    s, gate, resident, table, q = _buddy_inputs(np.random.default_rng(0),
                                                t, e, k, min(8, e - 1))
    got = buddy_substitute_pallas(jnp.asarray(s), jnp.asarray(gate),
                                  jnp.asarray(resident), jnp.asarray(table),
                                  jnp.asarray(q), h=8, rho=3,
                                  interpret=interpret)
    want = ref.ref_buddy_substitute(s, gate, resident, table, q, h=8, rho=3)
    errs["buddy_substitute"] = float(sum(
        int((np.asarray(g) != np.asarray(w)).sum())
        for g, w in zip(got, want)))
    _report("buddy_substitute", errs["buddy_substitute"], 0,
            "mismatched entries")

    key = jax.random.PRNGKey(0)
    ks = jax.random.split(key, 12)
    z = jax.random.normal(ks[0], (t, e), jnp.float32)
    got = topk_gate_pallas(z, 0.2, k=k, interpret=interpret)
    want = ref.ref_topk_gate(z, 0.2, k=k)
    exact = sum(int((np.asarray(got[i]) != np.asarray(want[i])).sum())
                for i in (0, 1, 4))
    _report("topk_gate", float(exact), 0, "mismatched ids/values/gates")
    errs["topk_gate"] = max(
        float(np.abs(np.asarray(got[i]) - np.asarray(want[i])).max())
        for i in (2, 3))
    _report("topk_gate", errs["topk_gate"], GATE_TOL, "max abs err (probs, tae)")

    def normal(kk, shape, fan_in):
        return jax.random.normal(kk, shape, jnp.float32) * fan_in ** -0.5

    def quant(kk, shape):
        return jax.random.randint(kk, shape, -127, 128, jnp.int32).astype(
            jnp.int8)

    x = jax.random.normal(ks[1], (e, t, d), jnp.float32)
    w1, w3 = normal(ks[2], (e, d, f), d), normal(ks[3], (e, d, f), d)
    w2 = normal(ks[4], (e, f, d), f)
    got = expert_ffn_pallas(x, w1, w3, w2, interpret=interpret)
    with hi:
        want = ref.ref_expert_ffn(x, w1, w3, w2)
    errs["expert_ffn"] = _rel_err(got, want)
    _report("expert_ffn", errs["expert_ffn"], FFN_TOL, "max err / max|ref|")

    w1q, w3q, w2q = (quant(ks[5], (e, d, f)), quant(ks[6], (e, d, f)),
                     quant(ks[7], (e, f, d)))
    s1 = jnp.full((e, f), d ** -0.5 / 127, jnp.float32)
    s3 = jnp.full((e, f), d ** -0.5 / 127, jnp.float32)
    s2 = jnp.full((e, d), f ** -0.5 / 127, jnp.float32)
    got = quant_ffn_pallas(x, w1q, s1, w3q, s3, w2q, s2, interpret=interpret)
    with hi:
        want = ref.ref_quant_ffn(x, w1q, s1, w3q, s3, w2q, s2)
    errs["quant_ffn"] = _rel_err(got, want)
    _report("quant_ffn", errs["quant_ffn"], FFN_TOL, "max err / max|ref|")

    xg = jax.random.normal(ks[8], (2 * e, t, d), jnp.float32)
    got = grouped_ffn_pallas(xg, w1, w3, w2, w1q, s1, w3q, s3, w2q, s2,
                             interpret=interpret)
    with hi:
        want = ref.ref_grouped_ffn(xg, w1, w3, w2, w1q, s1, w3q, s3, w2q, s2)
    errs["grouped_ffn"] = _rel_err(got, want)
    _report("grouped_ffn", errs["grouped_ffn"], FFN_TOL, "max err / max|ref|")
    return errs


# ---------------------------------------------------------------------------
# serve, timing, logits check
# ---------------------------------------------------------------------------
def run_serve(cfg, argv=SERVE_ARGV):
    """The launcher's continuous-serving path on ``cfg``. Returns the
    engine and what was measured."""
    from repro.launch import serve
    args = serve.parse_args(argv)
    log = io.StringIO()
    t0 = time.perf_counter()
    with contextlib.redirect_stdout(log):
        eng, s, outputs = serve.serve(cfg, args)
    wall = time.perf_counter() - t0
    print("[serve] " + log.getvalue().strip().splitlines()[-1])
    n_sub = s["engine"]["stats"]["n_sub"]
    ids = np.concatenate([np.asarray(o).reshape(-1) for o in outputs])
    print(f"[serve] completed {s['completed']}/{s['num_requests']}, rejected "
          f"{s['rejected']}, substitutions {n_sub}, {ids.size} tokens "
          f"generated; wall_s {wall:.3f} (weights init, buddy profiling, "
          f"compiles and serving)")
    print(f"[serve] simulated clock (a model, not a measurement): "
          f"sim_ttft_p50_s {s['ttft_s']['p50']:.6f}, sim_tpot_p50_s "
          f"{s['tpot_s']['p50']:.6f}, sim_tokens_per_s "
          f"{s['throughput_tok_s']:.1f}")
    _require(s["completed"] == args.num_requests and s["rejected"] == 0,
             f"serve: {s['completed']}/{args.num_requests} completed, "
             f"{s['rejected']} rejected")
    _require(all(len(o) == args.steps for o in outputs),
             "serve: a request ended short of its token budget")
    _require(bool(((ids >= 0) & (ids < cfg.vocab_size)).all()),
             "serve: a generated token id lies outside [0, vocab)")
    _require(n_sub > 0, "serve: the buddy mechanism never substituted")
    return eng, {"completed": s["completed"], "substitutions": n_sub}


def time_steps(eng, cfg, *, context: int = TIMING_CONTEXT,
               steps: int = TIMED_STEPS) -> dict:
    """Wall time of ``ServeEngine.step`` over the serving slots, each call
    ended by block_until_ready: the first call at a new KV shape (trace,
    compile and one step), then ``steps`` steady steps."""
    slots = SLOTS
    caches = eng.init_caches(slots, context)
    tok = jnp.asarray(np.random.default_rng(3).integers(
        0, cfg.vocab_size, slots), jnp.int32)
    times = []
    for pos in range(steps + 1):
        t0 = time.perf_counter()
        logits, caches = eng.step(tok, caches, np.full(slots, pos, np.int32))
        logits.block_until_ready()
        times.append(time.perf_counter() - t0)
        tok = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    steady = np.asarray(times[1:])
    out = {"compile_s": times[0], "step_s_median": float(np.median(steady)),
           "step_s_min": float(steady.min()),
           "step_s_max": float(steady.max())}
    print(f"[timing] decode step, {slots} slots x {context} KV positions: "
          f"compile_s {out['compile_s']:.3f} (first call: trace, compile, "
          f"one step); steady step_s median {out['step_s_median']:.6f}, "
          f"min {out['step_s_min']:.6f}, max {out['step_s_max']:.6f} over "
          f"{steps} steps (engine host work included)")
    return out


def check_logits(eng, cfg, *, length: int = CHECK_LEN) -> float:
    """Decode one prompt token by token through ``ServeEngine.step`` at full
    residency and compare every step's logits with forward_train's on the
    whole prompt, both at "highest" matmul precision. Returns the error."""
    from repro.models import transformer
    from repro.runtime.cache import ExpertCache
    from repro.training.data import MarkovLM
    eng.reset_runtime(cache=ExpertCache(eng.num_moe_layers,
                                        cfg.moe.num_experts, 1.0))
    prompt = MarkovLM(cfg.vocab_size, seed=5).sample(1, length)
    with jax.default_matmul_precision("highest"):
        caches = eng.init_caches(1, length)
        rows = []
        for pos in range(length):
            logits, caches = eng.step(jnp.asarray(prompt[:, pos], jnp.int32),
                                      caches, np.full(1, pos, np.int32))
            rows.append(np.asarray(logits[0]))
        want, _ = jax.jit(lambda p, t: transformer.forward_train(
            p, cfg, t, dropless=True))(eng.params,
                                       jnp.asarray(prompt, jnp.int32))
    err = _rel_err(np.stack(rows), np.asarray(want[0]))
    print(f"[check] decode vs forward_train logits, {length} positions at "
          f"full residency: max err / max|ref| {err:.3e} (tol "
          f"{LOGITS_TOL:.0e})")
    _require(err <= LOGITS_TOL, f"check: logits err {err:.3e} > tol "
             f"{LOGITS_TOL:.0e}")
    return err


def main() -> None:
    device = check_device()
    from repro.launch.compile_cache import enable_compile_cache
    cache = enable_compile_cache()
    warm = len(os.listdir(cache)) if os.path.isdir(cache) else 0
    print(f"[cache] compilation cache at {cache}: {warm} entries before "
          f"this run ({'warm' if warm else 'cold'})")
    cfg = smoke_config()
    run_kernels(cfg)
    eng, _ = run_serve(cfg)
    time_steps(eng, cfg)
    check_logits(eng, cfg)
    stats = jax.devices()[0].memory_stats() or {}
    print(f"[memory] peak_bytes_in_use {stats.get('peak_bytes_in_use')} "
          f"of bytes_limit {stats.get('bytes_limit')}")
    print(json.dumps({"ok": True, "device": device}))


if __name__ == "__main__":
    main()
