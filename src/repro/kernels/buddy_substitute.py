"""Pallas TPU kernel for Algorithm 1 — Buddy Expert Substitution.

TPU adaptation of the paper's CUDA kernel (see DESIGN.md §3): the paper maps
one thread block per token and one thread per top-k slot, with shared-memory +
atomic CAS for the uniqueness set. The TPU has no independent threads or
atomics, so we invert the parallelization:

  * the TOKEN axis is tiled across the Pallas grid and fully vectorized
    across VPU lanes within a block;
  * the K slots (<= 8) and buddy ranks (<= H <= 16) are *statically unrolled
    sequential* loops inside the kernel body — because slot k+1 sees slot k's
    substitution in VREGs, the uniqueness constraint needs no CAS at all;
  * expert-indexed lookups (residency M[e], buddy row B[e, r]) are expressed
    as one-hot matmul selects over the (small, E <= 256) expert axis so the
    whole body lowers to VPU ops — no dynamic gathers.

The residency mask, buddy profile and q-values fit in VMEM for every
assigned arch (E <= 64, R <= 16: < 10 KiB).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

TOKEN_BLOCK = 256


def _onehot_select(ids, table_col):
    """Vectorized gather table_col[ids] via one-hot matmul.

    ids: [T] int32 in [0, E); table_col: [E] (f32). Returns [T] f32.
    """
    e = table_col.shape[0]
    onehot = (ids[:, None] == jax.lax.broadcasted_iota(jnp.int32, (1, e), 1))
    return jnp.sum(onehot.astype(jnp.float32) * table_col[None, :], axis=1)


def _kernel(s_ref, gate_ref, m_ref, b_ref, q_ref, out_ref, sub_ref, miss_ref,
            *, k_n: int, h_n: int, rho: int):
    s = s_ref[...]                      # [T, K] int32
    gate = gate_ref[...]                # [T] int32 (1 = substitution allowed)
    m = m_ref[...].astype(jnp.float32)  # [E] residency (1 = GPU)
    b = b_ref[...]                      # [E, R] int32 buddy ids (-1 pad)
    q = q_ref[...].astype(jnp.float32)  # [E, R]

    t_n = s.shape[0]
    budget = jnp.where(gate > 0, rho, 0).astype(jnp.int32)   # [T]
    new_s = s
    sub = jnp.zeros((t_n, k_n), jnp.int32)
    miss = jnp.zeros((t_n, k_n), jnp.int32)
    # columns are read and written through an iota mask: Mosaic refuses the
    # zero-width slices a concatenate-based column update makes at k == 0
    col = jax.lax.broadcasted_iota(jnp.int32, (t_n, k_n), 1)

    for k in range(k_n):
        at_k = col == k                                       # [T, K]
        e = jnp.sum(jnp.where(at_k, new_s, 0).astype(jnp.float32),
                    axis=1).astype(jnp.int32)                 # [T] column k
        res_e = _onehot_select(e, m) > 0.5                    # [T]
        need = (~res_e) & (gate > 0) & (budget > 0)           # [T]

        best_psi = jnp.full((t_n,), -jnp.inf, jnp.float32)
        best_b = jnp.full((t_n,), -1, jnp.int32)
        for r in range(h_n):
            b_r = _onehot_select(e, b[:, r].astype(jnp.float32)).astype(jnp.int32)
            q_r = _onehot_select(e, q[:, r])
            valid = b_r >= 0
            b_safe = jnp.maximum(b_r, 0)
            res_b = _onehot_select(b_safe, m) > 0.5
            in_row = jnp.sum((new_s == b_safe[:, None]).astype(jnp.float32),
                             axis=1) > 0.5
            elig = valid & res_b & (~in_row)
            psi = q_r - r * 1e-7                              # rank tie-break
            better = elig & (psi > best_psi)
            best_psi = jnp.where(better, psi, best_psi)
            best_b = jnp.where(better, b_safe, best_b)

        do_sub = need & (best_b >= 0)
        new_col = jnp.where(do_sub, best_b, e)
        miss_col = (~res_e) & (~do_sub)
        new_s = jnp.where(at_k, new_col[:, None], new_s)
        sub = jnp.where(at_k, do_sub.astype(jnp.int32)[:, None], sub)
        miss = jnp.where(at_k, miss_col.astype(jnp.int32)[:, None], miss)
        budget = budget - do_sub.astype(jnp.int32)

    out_ref[...] = new_s
    sub_ref[...] = sub
    miss_ref[...] = miss


@functools.partial(jax.jit, static_argnames=("h", "rho", "interpret"))
def buddy_substitute_pallas(s, gate, resident, table, q, *, h: int = 8,
                            rho: int = 3, interpret: bool = False):
    """s [T, K] int32; gate [T] bool; resident [E] bool;
    table [E, R] int32; q [E, R] f32.
    Returns (s' [T, K], substituted [T, K] bool, missed [T, K] bool)."""
    t_n, k_n = s.shape
    e_n, r_n = table.shape
    h_n = min(h, r_n)

    tb = min(TOKEN_BLOCK, t_n)
    pad = (-t_n) % tb
    sp = jnp.pad(s, ((0, pad), (0, 0)))
    gp = jnp.pad(gate.astype(jnp.int32), (0, pad))
    grid = (sp.shape[0] // tb,)

    kernel = functools.partial(_kernel, k_n=k_n, h_n=h_n, rho=rho)
    out, sub, miss = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((tb, k_n), lambda i: (i, 0)),
            pl.BlockSpec((tb,), lambda i: (i,)),
            pl.BlockSpec((e_n,), lambda i: (0,)),
            pl.BlockSpec((e_n, r_n), lambda i: (0, 0)),
            pl.BlockSpec((e_n, r_n), lambda i: (0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((tb, k_n), lambda i: (i, 0)),
            pl.BlockSpec((tb, k_n), lambda i: (i, 0)),
            pl.BlockSpec((tb, k_n), lambda i: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct(sp.shape, jnp.int32),
            jax.ShapeDtypeStruct(sp.shape, jnp.int32),
            jax.ShapeDtypeStruct(sp.shape, jnp.int32),
        ],
        interpret=interpret,
    )(sp, gp, resident.astype(jnp.int32), table, q.astype(jnp.float32))
    return out[:t_n], sub[:t_n].astype(bool), miss[:t_n].astype(bool)
