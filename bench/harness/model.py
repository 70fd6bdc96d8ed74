"""The system under test, built from a configuration file and ``--seed``:
the default builder, for a stack of identical attention + MoE blocks.

A configuration file may name a builder of its own (``"builder":
"bench/<path>.py"``, loaded by ``spec.load_builder``); one that names none is
built by this module. A builder gives:

- ``model_config(cfg_file)``: the program's ``ModelConfig``;
- ``layer_kinds(m)``: the stack in order, each ``"moe"`` or another kind;
  the reference runs a layer of another kind with its ``block``;
- ``build_params(seed, m)``: the program's parameter tree, with its groups,
  made on the device in one jitted call;
- ``layer_weights(key, m, kind)`` and ``outer_weights(key, m)``: layer
  ``l``'s weights from ``layer_key(seed, l)`` (``l`` counts every layer of
  the stack) and the embedding, final norm and head from
  ``outer_key(seed)``, bit for bit as ``build_params`` places them;
- ``moe_shape(m)``: (MoE layers, router width), which sizes the expert
  cache, the prefetch predictor and the buddy tables;
- ``token_flops(m, context)``: the operations of one token
  (``harness/flops.py``).

``m`` is the configuration file's ``"model"``. The weights and the buddy
tables are the benchmark's own, drawn from the seed, so that the plain
reference can draw the same ones again, one layer at a time, without taking
anything from the program.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from harness.flops import token_flops  # noqa: F401  (the builder's)
from harness.traffic import seed_rng


def base_key(seed: int):
    seed = int(seed) % 2**64
    return jax.random.fold_in(jax.random.PRNGKey(seed % 2**32), seed >> 32)


def _normal(key, shape, scale, dtype):
    return (jax.random.normal(key, shape, jnp.float32) * scale).astype(dtype)


def _dense(key, fan_in, fan_out, dtype, lead=()):
    return _normal(key, (*lead, fan_in, fan_out),
                   (2.0 / (fan_in + fan_out)) ** 0.5, dtype)


def model_config(cfg_file: dict):
    """The ``ModelConfig`` that ``launch/serve.py`` would build."""
    from repro.configs.base import ModelConfig, MoEConfig
    m = cfg_file["model"]
    return ModelConfig(
        arch_id=cfg_file["name"], family="moe", source=cfg_file["source"],
        num_layers=m["num_layers"], d_model=m["d_model"],
        num_heads=m["num_heads"], num_kv_heads=m["num_kv_heads"],
        d_ff=m["d_ff"], vocab_size=m["vocab_size"], head_dim=m["head_dim"],
        moe=MoEConfig(**m["moe"]), sliding_window=m["sliding_window"],
        rope_theta=m["rope_theta"], norm_eps=m["norm_eps"], dtype=m["dtype"])


def layer_kinds(m: dict) -> list:
    return ["moe"] * m["num_layers"]


def moe_shape(m: dict) -> tuple:
    return m["num_layers"], m["moe"]["num_experts"]


def layer_weights(key, m: dict, kind: str = "moe") -> dict:
    """One attention + MoE block, in the program's layout (no layer axis);
    every layer here is of kind ``"moe"``."""
    d, h, kv, hd = m["d_model"], m["num_heads"], m["num_kv_heads"], m["head_dim"]
    e, f, s = m["moe"]["num_experts"], m["moe"]["d_ff"], \
        m["moe"]["num_shared_experts"]
    dt = jnp.dtype(m["dtype"])
    k = jax.random.split(key, 12)
    p = {"ln1": 1.0 + _normal(k[0], (d,), 0.1, jnp.float32),
         "ln2": 1.0 + _normal(k[1], (d,), 0.1, jnp.float32),
         "attn": {"wq": _dense(k[2], d, h * hd, dt),
                  "wk": _dense(k[3], d, kv * hd, dt),
                  "wv": _dense(k[4], d, kv * hd, dt),
                  "wo": _dense(k[5], h * hd, d, dt)},
         "moe": {"router": _dense(k[6], d, e, jnp.float32),
                 "w1": _dense(k[7], d, f, dt, (e,)),
                 "w3": _dense(k[8], d, f, dt, (e,)),
                 "w2": _dense(k[9], f, d, dt, (e,))}}
    if s:
        ks = jax.random.split(k[10], 3)
        p["moe"]["shared"] = {"w1": _dense(ks[0], d, s * f, dt),
                              "w3": _dense(ks[1], d, s * f, dt),
                              "w2": _dense(ks[2], s * f, d, dt)}
    return p


def outer_weights(key, m: dict) -> dict:
    """Embedding, final norm and LM head."""
    d, v = m["d_model"], m["vocab_size"]
    dt = jnp.dtype(m["dtype"])
    k = jax.random.split(key, 3)
    return {"embed": _normal(k[0], (v, d), 0.02, dt),
            "final_norm": 1.0 + _normal(k[1], (d,), 0.1, jnp.float32),
            "lm_head": _dense(k[2], d, v, dt)}


def layer_key(seed: int, layer: int):
    return jax.random.fold_in(jax.random.fold_in(base_key(seed), 1), layer)


def outer_key(seed: int):
    return jax.random.fold_in(base_key(seed), 0)


def build_params(seed: int, m: dict) -> dict:
    """The program's parameter pytree, made on the device in one call."""
    def make(key):
        layers = jax.vmap(lambda i: layer_weights(
            jax.random.fold_in(jax.random.fold_in(key, 1), i), m))(
                jnp.arange(m["num_layers"]))
        out = outer_weights(jax.random.fold_in(key, 0), m)
        out["groups"] = (layers,)
        return out
    return jax.jit(make)(base_key(seed))


def buddy_tables(seed: int, shape: tuple, list_len: int):
    """[L, E, R] buddy lists for ``shape`` = (MoE layers L, experts E): for
    every expert a ranked list of ``list_len`` other experts with strictly
    falling q, dealt from the seed. (Profiling that makes them from router
    traces is the offline phase of the paper; the reference needs tables
    that the program did not make.)"""
    l_n, e = shape
    rng = seed_rng(seed, 3)
    table = np.full((l_n, e, list_len), -1, np.int32)
    q = np.zeros((l_n, e, list_len), np.float32)
    for l in range(l_n):
        for i in range(e):
            others = np.delete(np.arange(e), i)
            table[l, i] = rng.permutation(others)[:list_len]
            w = np.sort(rng.dirichlet(np.ones(list_len)))[::-1]
            q[l, i] = w + np.linspace(1e-3, 0.0, list_len)   # strictly falls
    return table, q
