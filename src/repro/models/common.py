"""Shared model building blocks (functional, no flax).

Sharding: model code annotates activations with *logical* axis names via
``shard(x, ...names)``. ``launch/sharding.py`` installs a mapping from logical
names to mesh axes with ``axis_rules(...)``; outside that context the calls
are no-ops, so smoke tests / CPU runs never touch device state.
"""
from __future__ import annotations

import contextlib
import threading
from typing import Optional, Sequence

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

_STATE = threading.local()


def _rules():
    return getattr(_STATE, "rules", None)


@contextlib.contextmanager
def axis_rules(rules: dict):
    """rules: logical axis name -> mesh axis name (or tuple) or None."""
    prev = _rules()
    _STATE.rules = rules
    try:
        yield
    finally:
        _STATE.rules = prev


def logical_spec(names: Sequence[Optional[str]]) -> P:
    rules = _rules() or {}
    return P(*[rules.get(n) if n is not None else None for n in names])


def shard(x: jax.Array, *names: Optional[str]) -> jax.Array:
    """Apply a sharding constraint by logical axis names (no-op w/o rules)."""
    rules = _rules()
    if rules is None:
        return x
    spec = logical_spec(names)
    if all(s is None for s in spec):
        return x
    return jax.lax.with_sharding_constraint(x, spec)


# ---------------------------------------------------------------------------
# Initializers
# ---------------------------------------------------------------------------
def scaled_normal(key, shape, scale: float) -> jax.Array:
    """``jax.random.normal(key, shape) * scale`` with the same bits under jit
    as eagerly: the samples pass an optimization barrier, so XLA cannot fuse
    the scaling into the sampler's arithmetic (which then rounds
    differently)."""
    return jax.lax.optimization_barrier(jax.random.normal(key, shape)) * scale


def dense_init(key, in_dim: int, out_dim: int, dtype) -> jax.Array:
    scale = (2.0 / (in_dim + out_dim)) ** 0.5
    return scaled_normal(key, (in_dim, out_dim), scale).astype(dtype)


def embed_init(key, vocab: int, dim: int, dtype) -> jax.Array:
    return scaled_normal(key, (vocab, dim), 0.02).astype(dtype)


# ---------------------------------------------------------------------------
# Norms
# ---------------------------------------------------------------------------
def rmsnorm(x: jax.Array, gamma: jax.Array, eps: float = 1e-5) -> jax.Array:
    dt = x.dtype
    x32 = x.astype(jnp.float32)
    var = jnp.mean(x32 * x32, axis=-1, keepdims=True)
    return (x32 * jax.lax.rsqrt(var + eps) * gamma.astype(jnp.float32)).astype(dt)


# ---------------------------------------------------------------------------
# RoPE
# ---------------------------------------------------------------------------
def rope_freqs(head_dim: int, theta: float) -> jax.Array:
    return 1.0 / (theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim))


def apply_rope(x: jax.Array, positions: jax.Array, theta: float) -> jax.Array:
    """x: [..., seq, heads, head_dim]; positions: [..., seq]."""
    hd = x.shape[-1]
    freqs = rope_freqs(hd, theta)                       # [hd/2]
    angles = positions[..., None].astype(jnp.float32) * freqs  # [..., seq, hd/2]
    cos = jnp.cos(angles)[..., None, :]                 # [..., seq, 1, hd/2]
    sin = jnp.sin(angles)[..., None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return out.astype(x.dtype)


def swiglu(x: jax.Array, w1: jax.Array, w3: jax.Array, w2: jax.Array) -> jax.Array:
    """SwiGLU FFN: (silu(x@w1) * (x@w3)) @ w2, with f32 accumulation."""
    h = jax.nn.silu(jnp.einsum("...d,df->...f", x, w1,
                               preferred_element_type=jnp.float32))
    g = jnp.einsum("...d,df->...f", x, w3, preferred_element_type=jnp.float32)
    h = (h * g).astype(x.dtype)
    h = shard(h, *([None] * (h.ndim - 1)), "dff")
    return jnp.einsum("...f,fd->...d", h, w2,
                      preferred_element_type=jnp.float32).astype(x.dtype)
