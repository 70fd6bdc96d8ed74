"""RWKV6 ("Finch") block — attention-free time-mix with data-dependent decay
[arXiv:2404.05892], plus the RWKV channel-mix FFN.

Per head (dk = dv = head_dim), with data-dependent per-channel decay
w_t in (0,1):
    S_t = diag(w_t) S_{t-1} + k_t^T v_t
    o_t = r_t (S_{t-1} + diag(u) k_t^T v_t)
Train/prefill use lax.scan over time; decode is a single state update.
State per layer: [B, H, dk, dv] (O(1) in sequence length — native long_500k).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.models.common import dense_init, rmsnorm, scaled_normal, shard


def init_rwkv(key, d_model: int, num_heads: int, head_dim: int, d_ff: int,
              dtype) -> dict:
    dh = num_heads * head_dim
    ks = jax.random.split(key, 10)
    return {
        "mu": (jax.random.uniform(ks[0], (5, d_model)) * 0.5 + 0.25).astype(jnp.float32),
        "wr": dense_init(ks[1], d_model, dh, dtype),
        "wk": dense_init(ks[2], d_model, dh, dtype),
        "wv": dense_init(ks[3], d_model, dh, dtype),
        "wg": dense_init(ks[4], d_model, dh, dtype),
        "ww": dense_init(ks[5], d_model, dh, dtype),
        "w_bias": jnp.zeros((dh,), jnp.float32),
        "u": scaled_normal(ks[6], (num_heads, head_dim), 0.1).astype(jnp.float32),
        "wo": dense_init(ks[7], dh, d_model, dtype),
        # channel mix
        "mu_c": (jax.random.uniform(ks[8], (2, d_model)) * 0.5 + 0.25).astype(jnp.float32),
        "ck": dense_init(ks[9], d_model, d_ff, dtype),
        "cr": dense_init(jax.random.fold_in(key, 11), d_model, d_model, dtype),
        "cv": dense_init(jax.random.fold_in(key, 12), d_ff, d_model, dtype),
        "ln_x": jnp.ones((dh,), jnp.float32),
    }


def _token_shift(x, x_prev):
    """shift(x)[t] = x[t-1]; x_prev is the last token of the previous chunk
    ([B, 1, D]) or zeros."""
    return jnp.concatenate([x_prev, x[:, :-1]], axis=1)


def _time_mix_projections(params, x, x_shift, num_heads, head_dim):
    mu = params["mu"]
    def mix(i):
        return x * mu[i] + x_shift * (1.0 - mu[i])
    b, s, _ = x.shape
    r = (mix(0) @ params["wr"]).reshape(b, s, num_heads, head_dim)
    k = (mix(1) @ params["wk"]).reshape(b, s, num_heads, head_dim)
    v = (mix(2) @ params["wv"]).reshape(b, s, num_heads, head_dim)
    g = (mix(3) @ params["wg"]).reshape(b, s, num_heads, head_dim)
    w_raw = (mix(4) @ params["ww"]).astype(jnp.float32) + params["w_bias"]
    # data-dependent decay in (0, 1): exp(-softplus(.)) — bounded, stable
    w = jnp.exp(-jax.nn.softplus(w_raw)).reshape(b, s, num_heads, head_dim)
    return r, k, v, g, w


def wkv_chunked(r, k, v, w, u, state, chunk: int = 32):
    """Chunkwise-parallel WKV (flash-linear-attention style) — §Perf it. 4.

    The sequential scan writes the [B, H, dk, dv] state every step; the
    chunked form factorizes the data-dependent decay so each chunk is two
    MXU matmuls + one state update, cutting state HBM traffic by ~chunk x:

      within chunk (la_t = cumulative log-decay, la_0 = 0):
        r~_t = r_t * exp(la_{t-1})         k~_s = k_s * exp(-la_s)
        o_t  = r~_t @ S_0  +  [lower(r~ k~^T) + diag(r.(u*k))] @ v
        S'   = exp(la_C) * S_0 + (exp(la_C - la_s) k_s)^T v

    exp(-la_s) grows within a chunk; chunk=32 with the softplus-bounded
    decay keeps it in f32 range (validated against the scan oracle).
    """
    b, s, h, dk = r.shape
    assert s % chunk == 0, f"seq {s} % chunk {chunk}"
    n = s // chunk
    f32 = jnp.float32

    def resh(x):
        return x.astype(f32).reshape(b, n, chunk, h, dk).transpose(1, 0, 3, 2, 4)

    rc, kc, vc, wc = resh(r), resh(k), resh(v), resh(w)   # [N, B, H, C, dk]
    la = jnp.cumsum(jnp.log(jnp.maximum(wc, 1e-8)), axis=3)   # [N,B,H,C,dk]
    la_prev = la - jnp.log(jnp.maximum(wc, 1e-8))             # la_{t-1}
    la_end = la[:, :, :, -1:, :]                              # [N,B,H,1,dk]

    r_t = rc * jnp.exp(la_prev)
    k_t = kc * jnp.exp(-la)
    k_end = kc * jnp.exp(la_end - la)                         # for state update
    diag_term = jnp.einsum("nbhcd,nbhcd->nbhc", rc,
                           u[None, None, :, None, :] * kc)

    mask = jnp.tril(jnp.ones((chunk, chunk), f32), k=-1)      # strictly lower

    def step(S, inp):
        r_, k_, v_, ke_, laE, dg = inp
        o_inter = jnp.einsum("bhcd,bhde->bhce", r_, S)
        scores = jnp.einsum("bhcd,bhsd->bhcs", r_, k_) * mask[None, None]
        o_intra = jnp.einsum("bhcs,bhse->bhce", scores, v_) \
            + dg[..., None] * v_
        S_new = jnp.exp(laE[:, :, 0])[..., None] * S \
            + jnp.einsum("bhsd,bhse->bhde", ke_, v_)
        return S_new, o_inter + o_intra

    state, out = jax.lax.scan(step, state.astype(f32),
                              (r_t, k_t, vc, k_end, la_end, diag_term))
    out = out.transpose(1, 0, 3, 2, 4).reshape(b, s, h, dk)
    return out, state


def wkv_scan(r, k, v, w, u, state):
    """Sequential WKV recurrence. r,k,v,w: [B, S, H, dk]; u: [H, dk];
    state: [B, H, dk, dv]. Returns (out [B, S, H, dv], new_state)."""
    rt = r.swapaxes(0, 1).astype(jnp.float32)
    kt = k.swapaxes(0, 1).astype(jnp.float32)
    vt = v.swapaxes(0, 1).astype(jnp.float32)
    wt = w.swapaxes(0, 1).astype(jnp.float32)

    def step(s, inp):
        r_, k_, v_, w_ = inp                       # [B, H, dk] / [B, H, dv]
        kv = k_[..., :, None] * v_[..., None, :]   # [B, H, dk, dv]
        out = jnp.einsum("bhk,bhkv->bhv", r_, s + u[..., None] * kv)
        s_new = w_[..., None] * s + kv
        return s_new, out

    state, out = jax.lax.scan(step, state.astype(jnp.float32), (rt, kt, vt, wt))
    return out.swapaxes(0, 1), state


def rwkv_time_mix(params, x, state, x_prev, *, num_heads, head_dim):
    """x: [B, S, D]; state [B, H, dk, dv]; x_prev [B, 1, D].
    Returns (y, new_state, new_x_prev)."""
    b, s, d = x.shape
    x_shift = _token_shift(x, x_prev)
    r, k, v, g, w = _time_mix_projections(params, x, x_shift, num_heads, head_dim)
    r = shard(r, "batch", None, "heads", None)
    k = shard(k, "batch", None, "heads", None)
    if s % 32 == 0 and s > 1:
        out, new_state = wkv_chunked(r, k, v, w, params["u"], state)
    else:
        out, new_state = wkv_scan(r, k, v, w, params["u"], state)
    out = out.reshape(b, s, num_heads * head_dim)
    out = rmsnorm(out.astype(x.dtype), params["ln_x"])
    out = out * jax.nn.silu(g.reshape(b, s, -1)).astype(x.dtype)
    y = (out @ params["wo"]).astype(x.dtype)
    return y, new_state.astype(jnp.float32), x[:, -1:]


def rwkv_channel_mix(params, x, x_prev):
    """RWKV channel mix: squared-relu FFN with token shift."""
    mu = params["mu_c"]
    x_shift = _token_shift(x, x_prev)
    xk = x * mu[0] + x_shift * (1.0 - mu[0])
    xr = x * mu[1] + x_shift * (1.0 - mu[1])
    k = jnp.square(jax.nn.relu(xk @ params["ck"]))
    k = shard(k, "batch", None, "dff")
    return (jax.nn.sigmoid(xr @ params["cr"]) * (k @ params["cv"])).astype(x.dtype), x[:, -1:]


def init_rwkv_state(batch: int, num_heads: int, head_dim: int, d_model: int):
    return {
        "wkv": jnp.zeros((batch, num_heads, head_dim, head_dim), jnp.float32),
        "x_tm": jnp.zeros((batch, 1, d_model), jnp.float32),
        "x_cm": jnp.zeros((batch, 1, d_model), jnp.float32),
    }
