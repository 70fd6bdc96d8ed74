"""Jit'd public wrappers for the Pallas kernels.

On TPU the kernels compile via Mosaic. On CPU (the test suite) they execute
in interpret mode — the kernel body runs in Python/XLA-CPU for correctness
validation. Any other backend raises: interpret mode there would hide that
the kernels are not running on an accelerator.
"""
from __future__ import annotations

import jax

from repro.kernels.buddy_substitute import buddy_substitute_pallas
from repro.kernels.expert_ffn import expert_ffn_pallas
from repro.kernels.grouped_ffn import grouped_ffn_pallas
from repro.kernels.quant_ffn import quant_ffn_pallas
from repro.kernels.topk_gate import topk_gate_pallas
from repro.kernels.wkv_chunk import wkv_chunk_pallas


def _interpret() -> bool:
    backend = jax.default_backend()
    if backend not in ("tpu", "cpu"):
        raise RuntimeError(f"Pallas kernels run on tpu (Mosaic) or cpu "
                           f"(interpret mode), not on {backend!r}")
    return backend == "cpu"


def buddy_substitute(s, gate, resident, table, q, *, h: int = 8, rho: int = 3):
    return buddy_substitute_pallas(s, gate, resident, table, q, h=h, rho=rho,
                                   interpret=_interpret())


def topk_gate(logits, tau, *, k: int):
    return topk_gate_pallas(logits, tau, k=k, interpret=_interpret())


def expert_ffn(x, w1, w3, w2, *, block_c: int = 128, block_f: int = 256):
    return expert_ffn_pallas(x, w1, w3, w2, block_c=block_c, block_f=block_f,
                             interpret=_interpret())


def quant_ffn(x, w1_q, w1_s, w3_q, w3_s, w2_q, w2_s, *,
              block_c: int = 128, block_f: int = 256):
    """Fused dequant + grouped SwiGLU over int8/int4 tier replicas."""
    return quant_ffn_pallas(x, w1_q, w1_s, w3_q, w3_s, w2_q, w2_s,
                            block_c=block_c, block_f=block_f,
                            interpret=_interpret())


def grouped_ffn(x, w1, w3, w2, w1_q, w1_s, w3_q, w3_s, w2_q, w2_s, *,
                block_c: int = 128, block_f: int = 256):
    """Single-dispatch four-way miss outcome: x [2E, C, D] binned by
    (resolved expert, outcome class) — groups [0, E) full-precision/buddy,
    [E, 2E) degraded (quant replica, post-matmul dequant). Dropped slots
    are never binned. Returns [2E, C, D]."""
    return grouped_ffn_pallas(x, w1, w3, w2, w1_q, w1_s, w3_q, w3_s,
                              w2_q, w2_s, block_c=block_c, block_f=block_f,
                              interpret=_interpret())


def wkv_chunk(rt, kt, v, ke, lae, dg, s0):
    return wkv_chunk_pallas(rt, kt, v, ke, lae, dg, s0,
                            interpret=_interpret())
