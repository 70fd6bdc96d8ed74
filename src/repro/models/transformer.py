"""Composable decoder stack for all six assigned families.

The stack is compiled from ``cfg.stack()`` into scan *groups*: params for each
group are stacked on a leading axis and the group executes as one
``lax.scan`` — HLO size stays ~constant in depth (essential for the 512-device
dry-run compiles).

Three entry points (shared layer code):
  forward_train(params, cfg, tokens, ...)  -> logits [B, S, V], aux
  prefill(params, cfg, tokens, ...)        -> logits [B, S, V], caches
  decode_step(params, cfg, token, caches, pos, ...) -> logits [B, V], caches

Serving state for MoE archs: ``buddies`` is a BuddyState with leading layer
axis [L_moe, ...]; ``policy`` (static) selects Original / Random / BuddyMoE.

Named scopes (``jax.named_scope``, the ops' ``op_name`` metadata): ``embed``,
``attention`` (with its KV write), the MoE layer's ``router``,
``substitute``, ``dispatch`` and ``shared_experts`` (models/moe.py), and
``lm_head``.
"""
from __future__ import annotations

import functools
from typing import Any, NamedTuple, Optional

import jax
import jax.numpy as jnp

from repro.configs.base import (ATTN_DENSE, ATTN_MOE, CROSS_DENSE, MAMBA2,
                                RWKV, ModelConfig)
from repro.core.policy import BuddyPolicy
from repro.models import attention as attn
from repro.models import mamba as mb
from repro.models import moe as moe_mod
from repro.models import rwkv as rw
from repro.models.common import dense_init, embed_init, rmsnorm, shard, swiglu


# ===========================================================================
# Init
# ===========================================================================
def _init_dense_ffn(key, d_model, d_ff, dtype):
    k1, k3, k2 = jax.random.split(key, 3)
    return {"w1": dense_init(k1, d_model, d_ff, dtype),
            "w3": dense_init(k3, d_model, d_ff, dtype),
            "w2": dense_init(k2, d_ff, d_model, dtype)}


def _init_attn_block(key, cfg: ModelConfig, dtype, moe: bool):
    ka, kf = jax.random.split(key)
    p = {"ln1": jnp.ones((cfg.d_model,), jnp.float32),
         "ln2": jnp.ones((cfg.d_model,), jnp.float32),
         "attn": attn.init_attn(ka, cfg.d_model, cfg.num_heads,
                                cfg.num_kv_heads, cfg.head_dim, dtype)}
    if moe:
        p["moe"] = moe_mod.init_moe(kf, cfg.d_model, cfg.moe, dtype)
    else:
        p["ffn"] = _init_dense_ffn(kf, cfg.d_model, cfg.d_ff, dtype)
    return p


def _init_block(key, kind: str, cfg: ModelConfig, dtype):
    if kind in (ATTN_DENSE, CROSS_DENSE):
        return _init_attn_block(key, cfg, dtype, moe=False)
    if kind == ATTN_MOE:
        return _init_attn_block(key, cfg, dtype, moe=True)
    if kind == RWKV:
        s = cfg.ssm
        p = rw.init_rwkv(key, cfg.d_model, s.num_heads, s.head_dim, cfg.d_ff, dtype)
        p["ln1"] = jnp.ones((cfg.d_model,), jnp.float32)
        p["ln2"] = jnp.ones((cfg.d_model,), jnp.float32)
        return p
    if kind == MAMBA2:
        p = {"ln": jnp.ones((cfg.d_model,), jnp.float32),
             "mamba": mb.init_mamba(key, cfg.d_model, cfg.ssm, dtype)}
        return p
    if kind == "hybrid_super":
        keys = jax.random.split(key, cfg.attn_every)
        return {"mamba": _stack([_init_block(k, MAMBA2, cfg, dtype) for k in keys]),
                "ln_attn": jnp.ones((cfg.d_model,), jnp.float32)}
    if kind == "vlm_super":
        n_self = cfg.cross_attn_every - 1
        keys = jax.random.split(key, n_self + 1)
        return {"self": _stack([_init_block(k, ATTN_DENSE, cfg, dtype)
                                for k in keys[:n_self]]),
                "cross": _init_block(keys[-1], CROSS_DENSE, cfg, dtype)}
    raise ValueError(kind)


def _stack(trees):
    return jax.tree.map(lambda *xs: jnp.stack(xs), *trees)


@functools.partial(jax.jit, static_argnums=0)
def init_params(cfg: ModelConfig, key) -> dict:
    """Seeded random parameters, each group's blocks stacked on a leading
    axis. Jitted, and each group built stacked under vmap, so that only the
    stacked arrays are ever materialised: no per-layer copy sits beside
    them. On the CPU the values equal a per-layer eager build's bit for bit,
    save the last bit of some upcycled expert weights (``upcycle_noise``)."""
    dtype = jnp.dtype(cfg.dtype)
    keys = jax.random.split(key, 8)
    params: dict = {"embed": embed_init(keys[0], cfg.vocab_size, cfg.d_model, dtype)}
    if cfg.num_cond_tokens:
        params["cond_proj"] = dense_init(keys[1], cfg.cond_dim, cfg.d_model, dtype)
    groups = []
    for gi, (kind, repeat) in enumerate(cfg.stack()):
        gkey = jax.random.fold_in(keys[2], gi)
        groups.append(jax.vmap(
            lambda i: _init_block(jax.random.fold_in(gkey, i), kind, cfg,
                                  dtype))(jnp.arange(repeat)))
    params["groups"] = tuple(groups)
    if cfg.family == "hybrid":
        # zamba2 shared attention block — ONE param set reused at every
        # application (the defining trick of the arch)
        params["shared_attn"] = _init_attn_block(keys[3], cfg, dtype, moe=False)
    params["final_norm"] = jnp.ones((cfg.d_model,), jnp.float32)
    if not cfg.tie_embeddings:
        params["lm_head"] = dense_init(keys[4], cfg.d_model, cfg.vocab_size, dtype)
    return params


# ===========================================================================
# Block forward (mode: "full" = train/prefill over S tokens; "step" = decode)
# ===========================================================================
class StepCtx(NamedTuple):
    cfg: ModelConfig
    mode: str                      # "full" | "step" | "chunk"
    window: int                    # effective attention window (0 = full)
    policy: Optional[BuddyPolicy]
    positions: Any                 # [B, S] (full), scalar/[B] pos (step),
    #                                or [B] per-row base positions (chunk)
    rng: Any                       # router jitter key or None
    record: bool
    remat: bool = False            # checkpoint each scanned block (training)
    tok_valid: Any = None          # [B, C] prefix validity mask (chunk mode),
    #                                or [B, 1] live rows (step mode)
    block_tables: Any = None       # [B, MB] paged-KV block table (None = ring)
    dropless: bool = False         # full mode: no expert capacity limit
    layer: Any = 0                 # the block's index in its group's stack


def _attn_kwargs(cfg: ModelConfig):
    return dict(num_heads=cfg.num_heads, num_kv_heads=cfg.num_kv_heads,
                head_dim=cfg.head_dim, rope_theta=cfg.rope_theta)


def _self_attn(p, x, cache, ctx: StepCtx):
    if ctx.mode == "full":
        y = attn.attn_forward(p, x, ctx.positions, window=ctx.window,
                              **_attn_kwargs(ctx.cfg))
        return y, cache
    if ctx.mode == "chunk":
        if ctx.block_tables is not None:
            return attn.attn_prefill_chunk_paged(
                p, x, cache, ctx.positions, ctx.tok_valid, ctx.block_tables,
                **_attn_kwargs(ctx.cfg))
        return attn.attn_prefill_chunk(p, x, cache, ctx.positions,
                                       ctx.tok_valid, window=ctx.window,
                                       **_attn_kwargs(ctx.cfg))
    if ctx.block_tables is not None:
        return attn.attn_decode_paged(p, x, cache, ctx.positions,
                                      ctx.block_tables,
                                      **_attn_kwargs(ctx.cfg))
    y, cache = attn.attn_decode(p, x, cache, ctx.positions,
                                window=ctx.window, **_attn_kwargs(ctx.cfg))
    return y, cache


def _moe_dispatch_args(mode: str, dropless: bool = False) -> dict:
    """``moe_forward``'s capacity factor and dropless flag in each mode."""
    return {"capacity_factor": 2.0 if mode == "step" else 1.25,
            "dropless": dropless or mode == "chunk"}


def _moe_plan(cfg: ModelConfig, policy: Optional[BuddyPolicy], mode: str,
              tokens_shape, dropless: bool = False) -> tuple:
    """The dispatch plan ``moe_forward`` takes for tokens of
    ``tokens_shape`` in ``mode``."""
    return moe_mod.dispatch_plan(policy, tokens_shape, cfg.moe.num_experts,
                                 cfg.moe.top_k,
                                 **_moe_dispatch_args(mode, dropless))


def moe_slots_computed(cfg: ModelConfig, policy: Optional[BuddyPolicy],
                       mode: str, tokens_shape,
                       n_live: Optional[int] = None) -> int:
    """Expert-FFN slots one serving step's dispatch computes, summed over
    the MoE layers: ``tokens_shape`` is [B, 1] for a decode step ("step")
    and [B, C] for a prefill chunk ("chunk"); the path is the one
    ``moe_forward`` takes for it. The grouped matmul computes only the
    step's ``n_live`` live tokens' slots."""
    e, k = cfg.moe.num_experts, cfg.moe.top_k
    plan = _moe_plan(cfg, policy, mode, tokens_shape)
    n_moe = sum(r for kind, r in cfg.stack() if kind == ATTN_MOE)
    return n_moe * moe_mod.dispatch_slots(plan, tokens_shape, e, k,
                                          live_tokens=n_live)


def _zero_moe_aux(cfg: ModelConfig):
    e = cfg.moe.num_experts if cfg.is_moe else 1
    return {"lb": jnp.zeros((), jnp.float32),
            "n_sub": jnp.zeros((), jnp.int32),
            "n_miss": jnp.zeros((), jnp.int32),
            "n_drop": jnp.zeros((), jnp.int32),
            "n_degraded": jnp.zeros((), jnp.int32),
            "n_miss_drop": jnp.zeros((), jnp.int32),
            "n_peer": jnp.zeros((), jnp.int32),
            "miss_per_expert": jnp.zeros((e,), jnp.int32)}


def _moe_aux_dict(cfg, aux: moe_mod.MoEAux, record: bool):
    d = {"lb": aux.lb_loss, "n_sub": aux.n_substituted.astype(jnp.int32),
         "n_miss": aux.n_missed.astype(jnp.int32),
         "n_drop": aux.n_dropped.astype(jnp.int32),
         "n_degraded": aux.n_degraded.astype(jnp.int32),
         "n_miss_drop": aux.n_miss_drop.astype(jnp.int32),
         "n_peer": aux.n_peered.astype(jnp.int32),
         "miss_per_expert": aux.miss_per_expert}
    if record:
        d["indices"] = aux.orig_indices
        d["probs"] = aux.topk_probs
        d["substituted"] = aux.sub_slots
        d["missed"] = aux.miss_slots
        d["degraded"] = aux.deg_slots
        d["dropped"] = aux.drop_slots
        d["peered"] = aux.peer_slots
    return d


def block_forward(kind: str, p, x, cache, ctx: StepCtx, buddy=None,
                  shared_attn_params=None):
    """Returns (x_out, new_cache, aux_dict_or_None)."""
    cfg = ctx.cfg
    aux = None
    if kind in (ATTN_DENSE, ATTN_MOE):
        with jax.named_scope("attention"):
            h, cache_kv = _self_attn(p["attn"],
                                     rmsnorm(x, p["ln1"], cfg.norm_eps),
                                     cache["kv"] if cache else None, ctx)
        x = x + h
        xn = rmsnorm(x, p["ln2"], cfg.norm_eps)
        if kind == ATTN_MOE:
            y, moe_aux = moe_mod.moe_forward(
                p["moe"], xn, cfg.moe, policy=ctx.policy, buddy=buddy,
                jitter_key=ctx.rng, live=ctx.tok_valid, layer=ctx.layer,
                **_moe_dispatch_args(ctx.mode, ctx.dropless))
            aux = _moe_aux_dict(cfg, moe_aux, ctx.record)
        else:
            y = swiglu(xn, p["ffn"]["w1"], p["ffn"]["w3"], p["ffn"]["w2"])
        x = x + y
        return x, {"kv": cache_kv} if cache else None, aux

    if kind == CROSS_DENSE:
        raise ValueError("cross-attn blocks run inside vlm_super groups")

    if kind == RWKV:
        if ctx.mode == "full":
            st = cache or rw.init_rwkv_state(x.shape[0], cfg.ssm.num_heads,
                                             cfg.ssm.head_dim, cfg.d_model)
        else:
            st = cache
        h, wkv, x_tm = rw.rwkv_time_mix(
            p, rmsnorm(x, p["ln1"], cfg.norm_eps), st["wkv"],
            st["x_tm"].astype(x.dtype), num_heads=cfg.ssm.num_heads,
            head_dim=cfg.ssm.head_dim)
        x = x + h
        h, x_cm = rw.rwkv_channel_mix(p, rmsnorm(x, p["ln2"], cfg.norm_eps),
                                      st["x_cm"].astype(x.dtype))
        x = x + h
        new_cache = {"wkv": wkv, "x_tm": x_tm.astype(jnp.float32),
                     "x_cm": x_cm.astype(jnp.float32)}
        return x, new_cache, aux

    if kind == MAMBA2:
        st = cache or mb.init_mamba_state(x.shape[0], cfg.d_model, cfg.ssm)
        h, new_st = mb.mamba_forward(p["mamba"], rmsnorm(x, p["ln"], cfg.norm_eps),
                                     st, cfg.ssm, cfg.d_model)
        return x + h, new_st, aux

    raise ValueError(kind)


# ===========================================================================
# Group execution (scan over stacked blocks)
# ===========================================================================
def _run_group(kind: str, gparams, x, gcache, ctx: StepCtx, gbuddy=None,
               shared_attn=None, cross_embeds=None):
    """Scan one homogeneous group. gparams stacked [R, ...]."""
    cfg = ctx.cfg
    has_cache = gcache is not None

    if kind == "hybrid_super":
        def body(carry, inp):
            x = carry
            lp, lc = inp
            mcaches = []
            for i in range(cfg.attn_every):
                blk = jax.tree.map(lambda a: a[i], lp["mamba"])
                mc = jax.tree.map(lambda a: a[i], lc["mamba"]) if has_cache else None
                x, nc, _ = block_forward(MAMBA2, blk, x, mc, ctx)
                mcaches.append(nc if has_cache else None)
            # shared attention application
            with jax.named_scope("attention"):
                h, kv = _self_attn(shared_attn["attn"],
                                   rmsnorm(x, lp["ln_attn"], cfg.norm_eps),
                                   lc["kv"] if has_cache else None, ctx)
            x = x + h
            xn = rmsnorm(x, shared_attn["ln2"], cfg.norm_eps)
            x = x + swiglu(xn, shared_attn["ffn"]["w1"], shared_attn["ffn"]["w3"],
                           shared_attn["ffn"]["w2"])
            new_cache = None
            if has_cache:
                new_cache = {"mamba": jax.tree.map(lambda *xs: jnp.stack(xs), *mcaches),
                             "kv": kv}
            return x, new_cache

        if ctx.remat:
            body = jax.checkpoint(body)
        x, new_caches = jax.lax.scan(body, x, (gparams, gcache))
        return x, new_caches, _zero_moe_aux(cfg)

    if kind == "vlm_super":
        n_self = cfg.cross_attn_every - 1

        def body(carry, inp):
            x = carry
            lp, lc = inp

            # inner scan over the self-attn layers (a python loop slicing
            # the stacked caches makes GSPMD gather them — §Perf B7)
            def self_body(xc, sinp):
                blk, skv = sinp
                xc, nc, _ = block_forward(
                    ATTN_DENSE, blk, xc,
                    {"kv": skv} if has_cache else None, ctx)
                return xc, (nc["kv"] if has_cache else None)

            x, new_self_kv = jax.lax.scan(
                self_body, x,
                (lp["self"], lc["self_kv"] if has_cache else None),
                length=n_self)
            # cross-attention block
            cp = lp["cross"]
            cross_kv = lc.get("cross_kv") if has_cache else None
            x = _cross_block(cp, x, ctx, cross_embeds, cross_kv)
            new_cache = None
            if has_cache:
                new_cache = {"self_kv": new_self_kv,
                             "cross_kv": lc["cross_kv"]}
            return x, new_cache

        if ctx.remat:
            body = jax.checkpoint(body)
        x, new_caches = jax.lax.scan(body, x, (gparams, gcache))
        return x, new_caches, _zero_moe_aux(cfg)

    # homogeneous group. On the ragged MoE plan the expert tables stay out
    # of the scanned inputs: the grouped matmul reads the whole stack,
    # flattened to [R*E, ...] (a bitcast), at the layer's offset, so no
    # per-layer copy of a table is made.
    tables = {}
    if kind == ATTN_MOE and \
            _moe_plan(cfg, ctx.policy, ctx.mode, x.shape[:-1],
                      ctx.dropless)[0] == "ragged":
        moe_p = gparams["moe"]
        tables = {n: moe_p[n].reshape((-1,) + moe_p[n].shape[2:])
                  for n in ("w1", "w3", "w2")}
        gparams = {**gparams, "moe": {n: v for n, v in moe_p.items()
                                      if n not in tables}}

    def body(carry, inp):
        x, rng = carry
        lp, lc, lb, li = inp
        lctx = ctx._replace(rng=jax.random.fold_in(rng, li) if rng is not None else None,
                            layer=li)
        if tables:
            lp = {**lp, "moe": {**lp["moe"], **tables}}
        x, nc, aux = block_forward(kind, lp, x, lc, lctx, buddy=lb)
        if aux is None:
            aux = _zero_moe_aux(cfg)
        return (x, rng), (nc, aux)

    if ctx.remat:
        body = jax.checkpoint(body)
    r = jax.tree.leaves(gparams)[0].shape[0]
    li = jnp.arange(r)
    (x, _), (new_caches, auxs) = jax.lax.scan(
        body, (x, ctx.rng), (gparams, gcache, gbuddy, li))
    # reduce aux over layers; keep per-layer stacks when recording
    red = {k: auxs[k].sum(0) for k in
           ("lb", "n_sub", "n_miss", "n_drop", "n_degraded", "n_miss_drop",
            "n_peer", "miss_per_expert")}
    if ctx.record:
        red["per_layer"] = {k: v for k, v in auxs.items()
                            if k in ("indices", "probs", "n_sub", "n_miss",
                                     "miss_per_expert", "substituted",
                                     "missed", "degraded", "dropped",
                                     "peered")}
    return x, new_caches, red


def _cross_block(cp, x, ctx: StepCtx, cross_embeds, cross_kv):
    cfg = ctx.cfg
    xn = rmsnorm(x, cp["ln1"], cfg.norm_eps)
    if ctx.mode == "full":
        h = attn.attn_forward(cp["attn"], xn, ctx.positions,
                              cross_embeds=cross_embeds, **_attn_kwargs(cfg))
    else:
        h, _ = attn.attn_decode(cp["attn"], xn, None, ctx.positions,
                                cross_kv=cross_kv, **_attn_kwargs(cfg))
    x = x + h
    xn = rmsnorm(x, cp["ln2"], cfg.norm_eps)
    return x + swiglu(xn, cp["ffn"]["w1"], cp["ffn"]["w3"], cp["ffn"]["w2"])


# ===========================================================================
# Cache init
# ===========================================================================
def effective_window(cfg: ModelConfig, seq_len: int,
                     long_context: bool = False) -> int:
    """Attention window for decode: native SWA if set; long-context decode on
    full-attention archs falls back to the SWA variant (DESIGN.md §4)."""
    if cfg.sliding_window:
        return cfg.sliding_window
    if long_context:
        return 8192
    return 0


def _kv_capacity(cfg: ModelConfig, seq_len: int, window: int) -> int:
    total = seq_len + cfg.num_cond_tokens
    return min(total, window) if window else total


def init_caches(cfg: ModelConfig, batch: int, seq_len: int, *,
                window: int = 0, dtype=None, cond_embeds=None, params=None):
    """Decode caches for every group (stacked on group's repeat axis)."""
    dtype = dtype or jnp.dtype(cfg.dtype)
    cap = _kv_capacity(cfg, seq_len, window)

    def kv(b=batch):
        return attn.init_kv_cache(b, cap, cfg.num_kv_heads, cfg.head_dim, dtype)

    caches = []
    for kind, repeat in cfg.stack():
        if kind in (ATTN_DENSE, ATTN_MOE):
            c = {"kv": _stack_n(kv, repeat)}
        elif kind == RWKV:
            c = _stack_n(lambda: rw.init_rwkv_state(
                batch, cfg.ssm.num_heads, cfg.ssm.head_dim, cfg.d_model), repeat)
        elif kind == MAMBA2:
            c = _stack_n(lambda: mb.init_mamba_state(batch, cfg.d_model, cfg.ssm), repeat)
        elif kind == "hybrid_super":
            c = {"mamba": _stack_n(lambda: _stack_n(
                    lambda: mb.init_mamba_state(batch, cfg.d_model, cfg.ssm),
                    cfg.attn_every), repeat),
                 "kv": _stack_n(kv, repeat)}
        elif kind == "vlm_super":
            n_self = cfg.cross_attn_every - 1
            nc = cfg.num_cond_tokens
            cross_kv = (jnp.zeros((batch, nc, cfg.num_kv_heads, cfg.head_dim), dtype),
                        jnp.zeros((batch, nc, cfg.num_kv_heads, cfg.head_dim), dtype))
            c = {"self_kv": _stack_n(lambda: _stack_n(kv, n_self), repeat),
                 "cross_kv": _stack_n(lambda: cross_kv, repeat)}
        else:
            raise ValueError(kind)
        caches.append(c)
    return tuple(caches)


def init_paged_caches(cfg: ModelConfig, n_blocks: int, block_size: int, *,
                      dtype=None):
    """Paged decode caches: one shared [P, bs, KV, hd] block pool per layer
    (stacked on the group repeat axis like init_caches), addressed through
    the host-side PagedKVPool block tables instead of a per-row ring.
    Attention-only stacks (same restriction as chunked prefill — SSM state
    is sequential and has no pages)."""
    assert all(k in (ATTN_DENSE, ATTN_MOE) for k, _ in cfg.stack()), \
        f"paged KV supports attention stacks only, got {cfg.stack()}"
    dtype = dtype or jnp.dtype(cfg.dtype)

    def kv():
        return attn.init_paged_kv_cache(n_blocks, block_size,
                                        cfg.num_kv_heads, cfg.head_dim, dtype)

    return tuple({"kv": _stack_n(kv, repeat)} for _, repeat in cfg.stack())


def _stack_n(fn, n):
    return jax.tree.map(lambda *xs: jnp.stack(xs), *[fn() for _ in range(n)])


# ===========================================================================
# Entry points
# ===========================================================================
def _embed(params, cfg: ModelConfig, tokens, cond_embeds):
    with jax.named_scope("embed"):
        x = params["embed"][tokens]
        x = shard(x, "batch", None, None)
    if cfg.num_cond_tokens and cfg.family == "audio":
        # audio: stubbed codec frame embeddings as a causal prefix
        pre = (cond_embeds @ params["cond_proj"]).astype(x.dtype)
        x = jnp.concatenate([pre, x], axis=1)
    return x


def _project_cross(params, cfg, cond_embeds):
    if cfg.family == "vlm" and cond_embeds is not None:
        return (cond_embeds @ params["cond_proj"]).astype(jnp.dtype(cfg.dtype))
    return None


def _logits(params, cfg: ModelConfig, x):
    with jax.named_scope("lm_head"):
        x = rmsnorm(x, params["final_norm"], cfg.norm_eps)
        head = params["embed"].T if cfg.tie_embeddings else params["lm_head"]
        logits = jnp.einsum("...d,dv->...v", x, head,
                            preferred_element_type=jnp.float32)
        return shard(logits, "batch", *([None] * (logits.ndim - 2)),
                     "vocab")


def _iter_groups(params, cfg, caches, buddies):
    """Yields (kind, gparams, gcache, gbuddy) with moe buddy slices."""
    moe_off = 0
    for gi, (kind, repeat) in enumerate(cfg.stack()):
        gp = params["groups"][gi]
        gc = caches[gi] if caches is not None else None
        gb = None
        if kind == ATTN_MOE and buddies is not None:
            gb = jax.tree.map(lambda a: a[moe_off:moe_off + repeat], buddies)
            moe_off += repeat
        elif kind == ATTN_MOE:
            gb = _stack_n(lambda: moe_mod.full_residency(cfg.moe.num_experts), repeat)
        yield kind, gp, gc, gb


def forward_train(params, cfg: ModelConfig, tokens, *, cond_embeds=None,
                  policy: Optional[BuddyPolicy] = None, buddies=None,
                  rng=None, record: bool = False, window: int = -1,
                  remat: bool = False, dropless: bool = False):
    """Full-sequence forward. Returns (logits [B, S_tok, V], aux).

    ``dropless``: give every expert room for every routed slot, as decode
    has; without it a crowded expert drops the slots past its capacity."""
    if window < 0:
        window = cfg.sliding_window
    b, s = tokens.shape
    x = _embed(params, cfg, tokens, cond_embeds)
    positions = jnp.broadcast_to(jnp.arange(x.shape[1]), (b, x.shape[1]))
    cross = _project_cross(params, cfg, cond_embeds)
    ctx = StepCtx(cfg, "full", window, policy, positions, rng, record, remat,
                  dropless=dropless)

    total_aux = _zero_moe_aux(cfg)
    rec = []
    for kind, gp, gc, gb in _iter_groups(params, cfg, None, buddies):
        x, _, aux = _run_group(kind, gp, x, None, ctx, gbuddy=gb,
                               shared_attn=params.get("shared_attn"),
                               cross_embeds=cross)
        if aux:
            for k in total_aux:
                total_aux[k] = total_aux[k] + aux.get(k, 0)
            if record and aux.get("per_layer"):
                rec.append(aux["per_layer"])
    if cfg.family == "audio" and cfg.num_cond_tokens:
        x = x[:, cfg.num_cond_tokens:]
    logits = _logits(params, cfg, x)
    if record:
        total_aux["recorded"] = rec
    return logits, total_aux


def decode_step(params, cfg: ModelConfig, token, caches, pos, *,
                cond_embeds=None, policy: Optional[BuddyPolicy] = None,
                buddies=None, rng=None, window: int = -1,
                record: bool = False, block_tables=None, tok_valid=None):
    """One-token decode. token [B] int32; pos int32 — a scalar (lockstep
    batch) or a [B] vector of per-row absolute positions (continuous
    batching), including any audio conditioning prefix. block_tables
    [B, MB] routes attention through the paged-KV pool instead of the
    per-row ring (pos must then be [B]). tok_valid [B] bool marks the live
    rows (default: every row); the MoE grouped matmul skips the others'
    slots. Returns (logits [B, V], new_caches, aux)."""
    if window < 0:
        window = cfg.sliding_window
    with jax.named_scope("embed"):
        x = params["embed"][token][:, None, :]        # [B, 1, D]
    if cfg.family == "audio" and cfg.num_cond_tokens:
        pos = pos + cfg.num_cond_tokens
    ctx = StepCtx(cfg, "step", window, policy, pos, rng, record,
                  tok_valid=None if tok_valid is None else tok_valid[:, None],
                  block_tables=block_tables)

    total_aux = _zero_moe_aux(cfg)
    rec = []
    new_caches = []
    for gi, (kind, gp, gc, gb) in enumerate(
            _iter_groups(params, cfg, caches, buddies)):
        if kind == "vlm_super":
            x, nc, aux = _run_group(kind, gp, x, gc, ctx)
        else:
            x, nc, aux = _run_group(kind, gp, x, gc, ctx, gbuddy=gb,
                                    shared_attn=params.get("shared_attn"))
        new_caches.append(nc)
        if aux:
            for k in total_aux:
                total_aux[k] = total_aux[k] + aux.get(k, 0)
            if record and aux.get("per_layer"):
                rec.append(aux["per_layer"])
    logits = _logits(params, cfg, x[:, 0])
    if record:
        total_aux["recorded"] = rec
    return logits, tuple(new_caches), total_aux


def prefill_chunk(params, cfg: ModelConfig, tokens, caches, base_pos,
                  tok_valid, *, policy: Optional[BuddyPolicy] = None,
                  buddies=None, rng=None, window: int = -1,
                  record: bool = False, block_tables=None):
    """Fused multi-token step for chunked prefill (continuous batching).

    tokens [B, C] int32; base_pos [B] int32 — absolute position of each
    row's first chunk token; tok_valid [B, C] bool PREFIX mask — row b's
    tokens j < count(b) are live, the rest ride the fixed-shape graph and
    write nothing. A decode row joins the step as a 1-valid-token chunk, so
    one launch serves prefill and decode rows together (no barrier).

    Returns (logits [B, C, V], new_caches, aux). aux token axes are the
    flattened [B*C] chunk (row-major) — mask host-side with tok_valid.
    MoE dispatch is dropless in this mode, so per-token outputs (and the
    cache entries derived from them) are independent of chunk size.
    """
    assert all(k in (ATTN_DENSE, ATTN_MOE) for k, _ in cfg.stack()), \
        "chunked prefill supports attention stacks only (KV caches; " \
        f"SSM/hybrid/VLM states are sequential), got {cfg.stack()}"
    if window < 0:
        window = cfg.sliding_window
    with jax.named_scope("embed"):
        x = params["embed"][tokens]                   # [B, C, D]
        x = shard(x, "batch", None, None)
    base_pos = jnp.asarray(base_pos, jnp.int32)
    if cfg.family == "audio" and cfg.num_cond_tokens:
        base_pos = base_pos + cfg.num_cond_tokens
    ctx = StepCtx(cfg, "chunk", window, policy, base_pos, rng, record,
                  tok_valid=tok_valid, block_tables=block_tables)

    total_aux = _zero_moe_aux(cfg)
    rec = []
    new_caches = []
    for kind, gp, gc, gb in _iter_groups(params, cfg, caches, buddies):
        x, nc, aux = _run_group(kind, gp, x, gc, ctx, gbuddy=gb)
        new_caches.append(nc)
        if aux:
            for k in total_aux:
                total_aux[k] = total_aux[k] + aux.get(k, 0)
            if record and aux.get("per_layer"):
                rec.append(aux["per_layer"])
    logits = _logits(params, cfg, x)                  # [B, C, V]
    if record:
        total_aux["recorded"] = rec
    return logits, tuple(new_caches), total_aux


def prefill(params, cfg: ModelConfig, tokens, *, cond_embeds=None,
            window: int = -1):
    """Full-sequence forward (the prefill compute). Decode-cache
    construction is handled by the serving engine, which prefills prompts
    through decode_step (engine.py) — the monolithic fused
    prefill+cache-build is what prefill_32k dry-runs lower via
    forward_train."""
    if window < 0:
        window = cfg.sliding_window
    logits, _ = forward_train(params, cfg, tokens, cond_embeds=cond_embeds,
                              window=window)
    return logits
