"""Operations of the model's forward pass per processed token, counted once
from the configuration's sizes (a multiply-add is two operations)."""
from __future__ import annotations


def token_flops(m: dict, context: int) -> float:
    """One token at a position that attends over ``context`` keys (itself
    included): q/k/v/o projections, attention scores and mixing over the
    context, the router, the kept and the shared experts, and the LM head."""
    d, h, kv, hd = m["d_model"], m["num_heads"], m["num_kv_heads"], m["head_dim"]
    moe = m["moe"]
    if m.get("sliding_window"):
        context = min(context, m["sliding_window"])
    proj = 2 * d * h * hd * 2 + 2 * d * kv * hd * 2
    attn = 4 * h * hd * context
    router = 2 * d * moe["num_experts"]
    experts = (moe["top_k"] + moe["num_shared_experts"]) * 3 * 2 * d * moe["d_ff"]
    return m["num_layers"] * (proj + attn + router + experts) \
        + 2 * d * m["vocab_size"]


def chunk_flops(m: dict, base: int, n: int) -> float:
    """``n`` tokens fed at positions base .. base+n-1."""
    fixed = token_flops(m, 0)
    per_key = token_flops(m, 1) - fixed
    if m.get("sliding_window"):
        return sum(token_flops(m, base + j + 1) for j in range(n))
    return n * fixed + per_key * (n * base + n * (n + 1) // 2)
