"""Mamba2 (SSD) block for the zamba2 hybrid [arXiv:2411.15242 / Mamba2].

Scalar-per-head decay SSD recurrence with causal depthwise conv and gating:
    h_t = a_t * h_{t-1} + dt_t * (x_t outer B_t)        a_t = exp(-softplus(A) dt_t)
    y_t = h_t @ C_t + D * x_t ;  y = y * silu(z)
State per layer: conv tail [B, conv_dim-1, inner] + SSM state [B, H, hd, N].
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.configs.base import SSMConfig
from repro.models.common import dense_init, scaled_normal, shard


def init_mamba(key, d_model: int, cfg: SSMConfig, dtype) -> dict:
    inner = cfg.expand * d_model
    n_heads = inner // cfg.head_dim
    ks = jax.random.split(key, 6)
    return {
        "in_proj": dense_init(ks[0], d_model, 2 * inner + 2 * cfg.state_dim + n_heads, dtype),
        "conv_w": scaled_normal(ks[1], (cfg.conv_dim, inner), 0.1).astype(dtype),
        "A_log": jnp.zeros((n_heads,), jnp.float32),
        "D": jnp.ones((n_heads,), jnp.float32),
        "dt_bias": jnp.zeros((n_heads,), jnp.float32),
        "out_proj": dense_init(ks[2], inner, d_model, dtype),
    }


def _split_proj(z, inner, state_dim, n_heads):
    xz, b, c, dt = jnp.split(z, [2 * inner, 2 * inner + state_dim,
                                 2 * inner + 2 * state_dim], axis=-1)
    x, gate = jnp.split(xz, 2, axis=-1)
    return x, gate, b, c, dt


def _causal_conv(x, conv_w, tail):
    """Depthwise causal conv. x [B, S, inner]; conv_w [W, inner];
    tail [B, W-1, inner] (previous inputs). Returns (y, new_tail)."""
    w = conv_w.shape[0]
    xp = jnp.concatenate([tail.astype(x.dtype), x], axis=1)
    y = sum(xp[:, i:i + x.shape[1]] * conv_w[i] for i in range(w))
    return jax.nn.silu(y), xp[:, -(w - 1):]


def ssd_chunked(a, xh, bt, ct, dt, h0, chunk: int = 32):
    """Chunkwise-parallel SSD scan (Mamba2) — §Perf iteration 4.

    a [B,S,H] per-head scalar decay; xh [B,S,H,hd]; bt/ct [B,S,N];
    dt [B,S,H]; h0 [B,H,hd,N]. Returns (y_ssm [B,S,H,hd], hN).

      la_t = cumsum log a;  c~_t = c_t exp(la_t);  b~_s = b_s dt_s exp(-la_s)
      o_t = exp(la_t) (h_0 @ c_t) + [lower_incl(c~ b~^T)] @ x
      h'  = exp(la_C) h_0 + (exp(la_C - la_s) dt_s b_s)^T x_s
    """
    b_sz, s_len, h_n = a.shape
    hd = xh.shape[-1]
    n_dim = bt.shape[-1]
    n = s_len // chunk
    f32 = jnp.float32

    ac = a.reshape(b_sz, n, chunk, h_n).transpose(1, 0, 3, 2)        # [N,B,H,C]
    dtc = dt.reshape(b_sz, n, chunk, h_n).transpose(1, 0, 3, 2)
    xc = xh.reshape(b_sz, n, chunk, h_n, hd).transpose(1, 0, 3, 2, 4)
    bc = bt.reshape(b_sz, n, chunk, n_dim).transpose(1, 0, 2, 3)     # [N,B,C,Nd]
    cc = ct.reshape(b_sz, n, chunk, n_dim).transpose(1, 0, 2, 3)

    la = jnp.cumsum(jnp.log(jnp.maximum(ac, 1e-12)), axis=3)         # [N,B,H,C]
    la_end = la[:, :, :, -1:]

    # decay-weighted b/c (b/c are head-shared; decay is per-head -> expand)
    c_dec = cc[:, :, None] * jnp.exp(la)[..., None]                  # [N,B,H,C,Nd]
    b_dec = bc[:, :, None] * (dtc * jnp.exp(-la))[..., None]
    b_end = bc[:, :, None] * (dtc * jnp.exp(la_end - la))[..., None]

    mask = jnp.tril(jnp.ones((chunk, chunk), f32))                   # incl diag

    def step(h, inp):
        c_d, b_d, b_e, x_, laE = inp          # laE [B,H]
        o_inter = jnp.einsum("bhdn,bhcn->bhcd", h, c_d)
        scores = jnp.einsum("bhcn,bhsn->bhcs", c_d, b_d) * mask[None, None]
        o_intra = jnp.einsum("bhcs,bhsd->bhcd", scores, x_)
        h_new = jnp.exp(laE)[..., None, None] * h \
            + jnp.einsum("bhsn,bhsd->bhdn", b_e, x_)
        return h_new, o_inter + o_intra

    hN, out = jax.lax.scan(step, h0,
                           (c_dec, b_dec, b_end, xc, la_end[:, :, :, 0]))
    out = out.transpose(1, 0, 3, 2, 4).reshape(b_sz, s_len, h_n, hd)
    return out, hN


def mamba_forward(params, x, state, cfg: SSMConfig, d_model: int):
    """x: [B, S, D]; state: dict(conv [B,W-1,inner], ssm [B,H,hd,N]).
    Returns (y [B,S,D], new_state)."""
    inner = cfg.expand * d_model
    n_heads = inner // cfg.head_dim
    b_sz, s_len, _ = x.shape

    z = x @ params["in_proj"]
    xi, gate, b, c, dt = _split_proj(z, inner, cfg.state_dim, n_heads)
    xi, conv_tail = _causal_conv(xi, params["conv_w"], state["conv"])
    xi = shard(xi, "batch", None, "dff")

    dt = jax.nn.softplus(dt.astype(jnp.float32) + params["dt_bias"])   # [B,S,H]
    a = jnp.exp(-jax.nn.softplus(params["A_log"]) * dt)               # [B,S,H]
    xh = xi.reshape(b_sz, s_len, n_heads, cfg.head_dim).astype(jnp.float32)
    bt = b.astype(jnp.float32)                                        # [B,S,N]
    ct = c.astype(jnp.float32)

    h0 = state["ssm"].astype(jnp.float32)
    if s_len % 32 == 0 and s_len > 1:
        y_ssm, hN = ssd_chunked(a, xh, bt, ct, dt, h0)
    else:
        def step(h, inp):
            a_, x_, b_, dt_ = inp   # [B,H], [B,H,hd], [B,N], [B,H]
            dx = (dt_[..., None] * x_)[..., None] * b_[:, None, None, :]
            h_new = a_[..., None, None] * h + dx
            return h_new, h_new

        hN, hs = jax.lax.scan(step, h0,
                              (a.swapaxes(0, 1), xh.swapaxes(0, 1),
                               bt.swapaxes(0, 1), dt.swapaxes(0, 1)))
        y_ssm = jnp.einsum("sbhdn,bsn->bshd", hs, ct)
    # y_t = h_t @ C_t + D * x_t
    y = y_ssm + params["D"][:, None] * xh
    y = y.reshape(b_sz, s_len, inner).astype(x.dtype)
    y = y * jax.nn.silu(gate).astype(x.dtype)
    out = (y @ params["out_proj"]).astype(x.dtype)
    return out, {"conv": conv_tail.astype(jnp.float32), "ssm": hN}


def init_mamba_state(batch: int, d_model: int, cfg: SSMConfig):
    inner = cfg.expand * d_model
    n_heads = inner // cfg.head_dim
    return {
        "conv": jnp.zeros((batch, cfg.conv_dim - 1, inner), jnp.float32),
        "ssm": jnp.zeros((batch, n_heads, cfg.head_dim, cfg.state_dim), jnp.float32),
    }
