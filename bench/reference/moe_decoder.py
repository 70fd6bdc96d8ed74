"""Plain reference of a decoder of attention + mixture-of-experts blocks.

Straightforward ``jax.numpy`` over one whole sequence: RMSNorm, grouped-
query attention with RoPE over the whole head (causal, optionally windowed),
a router that keeps the top-k experts and mixes them with the softmax of
their logits, SwiGLU experts, always-on shared experts, and an LM head. It
imports nothing of the program.

Two decisions are discrete and flip on rounding: which experts the router
keeps, and which slots BuddyMoE substitutes. The reference takes both from
the served run and checks them (see ``layer``): the program's top-k set has
to be the reference's top-k up to rounding (``route_gap``), and a slot the
program substituted has to be a miss with an eligible buddy. The buddy is
then picked by Algorithm 1 of the paper: the first buddy in the table that
is resident at that step and not yet among the token's experts.

The reference runs in float32 at "highest" matmul precision. With
``q8`` every linear layer takes its weights rounded to int8 per output
channel and its input rounded to int8 per row: the control, one step below
the precision the configurations state (float32 storage, matmuls in one
bfloat16 pass).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np


def rmsnorm(x, g, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * g


def rope(x, theta):
    """x [S, heads, hd]; rotate pairs (i, i + hd/2) by position * freq_i."""
    s, _, hd = x.shape
    freq = 1.0 / theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * freq[None]
    cos, sin = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
    a, b = jnp.split(x, 2, -1)
    return jnp.concatenate([a * cos - b * sin, a * sin + b * cos], -1)


def int8(a, axis):
    """``a`` rounded to int8 with one symmetric scale per slice along
    ``axis`` (returned in float32)."""
    scale = jnp.max(jnp.abs(a), axis=axis, keepdims=True) / 127.0
    scale = jnp.where(scale > 0, scale, 1.0)
    return jnp.round(a / scale).clip(-127, 127) * scale


def lin(x, w, q8=False):
    if q8:
        return int8(x, -1) @ int8(w, 0)
    return x @ w


def attention(p, x, m, q8=False):
    s, d = x.shape
    h, kv, hd = m["num_heads"], m["num_kv_heads"], m["head_dim"]
    q = rope(lin(x, p["wq"], q8).reshape(s, h, hd), m["rope_theta"])
    k = rope(lin(x, p["wk"], q8).reshape(s, kv, hd), m["rope_theta"])
    v = lin(x, p["wv"], q8).reshape(s, kv, hd)
    k = jnp.repeat(k, h // kv, axis=1)
    v = jnp.repeat(v, h // kv, axis=1)
    sc = jnp.einsum("qhd,khd->hqk", q, k) * hd ** -0.5
    i = jnp.arange(s)
    mask = i[None, :] <= i[:, None]
    if m["sliding_window"]:
        mask &= i[None, :] > i[:, None] - m["sliding_window"]
    pr = jax.nn.softmax(jnp.where(mask[None], sc, -jnp.inf), -1)
    o = jnp.einsum("hqk,khd->qhd", pr, v).reshape(s, h * hd)
    return lin(o, p["wo"], q8)


def swiglu(x, w1, w3, w2, q8=False):
    return lin(jax.nn.silu(lin(x, w1, q8)) * lin(x, w3, q8), w2, q8)


def pick_buddies(route, sub, resid, table):
    """Algorithm 1 on the host: [S, K] expert ids after substitution, and
    the number of substituted slots that were not legal (orig resident, or
    no resident unused buddy). route/sub [S, K]; resid [S, E]; table [E, R]."""
    final = np.array(route)
    bad = 0
    for t in np.flatnonzero(sub.any(1)):
        for k in range(route.shape[1]):
            if not sub[t, k]:
                continue
            e = route[t, k]
            if resid[t, e]:
                bad += 1
                continue
            cand = [b for b in table[e] if b >= 0 and resid[t, b]
                    and b not in final[t]]
            if not cand:
                bad += 1
                continue
            final[t, k] = cand[0]
    return final, bad


@jax.jit
def route_gap(logits, route):
    """How far the kept experts' logits lie below the k-th best, over the
    spread of the logits; [S]."""
    k = route.shape[1]
    kth = jax.lax.top_k(logits, k)[0][:, -1]
    kept = jnp.take_along_axis(logits, route, 1)
    return jnp.max(jnp.maximum(kth[:, None] - kept, 0.0), 1) \
        / jnp.std(logits, -1)


def layer(p, x, route, final, m, q8=False):
    """One block. route/final [S, K] expert ids before/after substitution.
    Returns (x_out, router logits [S, E], own top-k [S, K])."""
    xa = rmsnorm(x, p["ln1"], m["norm_eps"])
    x = x + attention(p["attn"], xa, m, q8)
    xn = rmsnorm(x, p["ln2"], m["norm_eps"])
    mp = p["moe"]
    logits = lin(xn, mp["router"], q8)
    own = jax.lax.top_k(logits, route.shape[1])[1]
    w = jax.nn.softmax(jnp.take_along_axis(logits, route, 1), -1)   # [S, K]
    mix = jnp.zeros((x.shape[0], mp["w1"].shape[0]), jnp.float32).at[
        jnp.arange(x.shape[0])[:, None], final].add(w)              # [S, E]

    def expert(acc, xs):
        w1, w3, w2, col = xs
        return acc + swiglu(xn, w1, w3, w2, q8) * col[:, None], None
    y, _ = jax.lax.scan(expert, jnp.zeros(x.shape, jnp.float32),
                        (mp["w1"], mp["w3"], mp["w2"], mix.T))
    if "shared" in mp:
        sh = mp["shared"]
        y = y + swiglu(xn, sh["w1"], sh["w3"], sh["w2"], q8)
    return x + y, logits, own


def head(o, x, m, q8=False):
    return lin(rmsnorm(x, o["final_norm"], m["norm_eps"]), o["lm_head"], q8)
