"""MoE layer: top-k router, expert dispatch (a grouped matmul over the
routed slots wherever no slot can be dropped, capacity buffers where one
can), expert SwiGLU compute, and the BuddyMoE substitution hook (the paper's
runtime layer between the router and expert execution, §3.4).

Expert parallelism model: experts are tensor-parallel over the `model` mesh
axis (d_ff sharded); tokens are data-parallel. Dispatch is therefore local to
each data shard — no all-to-all on the baseline path.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Any, NamedTuple, Optional

import jax
import jax.numpy as jnp

from repro.configs.base import MoEConfig
from repro.core.policy import BuddyPolicy
from repro.core.substitute import SubstituteResult, substitute
from repro.kernels.ref import dequant_swiglu
from repro.models.common import dense_init, scaled_normal, shard, swiglu


class BuddyState(NamedTuple):
    """Per-layer runtime state for BuddyMoE (all replicated, tiny)."""
    resident: jax.Array   # [E] bool — GPU residency mask M
    table: jax.Array      # [E, R] int32 — buddy profile B (rank-ordered, -1 pad)
    q: jax.Array          # [E, R] f32 — q_{j|i} per entry
    hop: jax.Array        # [E] int32 — ICI hops to each expert's cache slot
    quant_ok: Any = None  # [E] bool — misses the runtime routed to the
    #                       resident quant-replica tier this step (None when
    #                       no tier is attached; see runtime/tiers.py)
    fid_cost: Any = None  # [E] f32 — stall-equivalent cost of the degraded
    #                       outcome (runtime/costs.py; miss_policy='cost')
    fetch_cost: Any = None  # [E] f32 — expected stall of fetching this step
    #                         (in-flight ETA or modeled cold transfer)
    peer_ok: Any = None   # [E] bool — experts resident in a peer device's
    #                       HBM (precedence mode routes their misses to an
    #                       ICI borrow; None on single-device meshes)
    peer_cost: Any = None  # [E] f32 — expected stall of the peer borrow
    #                        (MissCostModel.peer_eta; miss_policy='cost')


def full_residency(num_experts: int, r_max: int = 8) -> BuddyState:
    return BuddyState(
        resident=jnp.ones((num_experts,), bool),
        table=jnp.full((num_experts, r_max), -1, jnp.int32),
        q=jnp.zeros((num_experts, r_max), jnp.float32),
        hop=jnp.zeros((num_experts,), jnp.int32),
    )


def init_moe(key, d_model: int, cfg: MoEConfig, dtype) -> dict:
    kr, k1, k3, k2, ks = jax.random.split(key, 5)
    e, f = cfg.num_experts, cfg.d_ff
    if cfg.upcycle_noise > 0:
        # sparse upcycling: shared base FFN + per-expert perturbation
        n = cfg.upcycle_noise

        def up(k, shape_in, shape_out, transpose=False):
            base = dense_init(jax.random.fold_in(k, 0), shape_in, shape_out,
                              jnp.float32)
            noise = scaled_normal(jax.random.fold_in(k, 1),
                                  (e, shape_in, shape_out), n) \
                * (2.0 / (shape_in + shape_out)) ** 0.5
            return (base[None] + noise).astype(dtype)

        p = {
            "router": dense_init(kr, d_model, e, jnp.float32),
            "w1": up(k1, d_model, f),
            "w3": up(k3, d_model, f),
            "w2": up(k2, f, d_model),
        }
    else:
        p = {
            "router": dense_init(kr, d_model, e, jnp.float32),
            "w1": dense_init(k1, d_model, e * f, dtype).reshape(d_model, e, f).transpose(1, 0, 2),
            "w3": dense_init(k3, d_model, e * f, dtype).reshape(d_model, e, f).transpose(1, 0, 2),
            "w2": dense_init(k2, e * f, d_model, dtype).reshape(e, f, d_model),
        }
    if cfg.num_shared_experts:
        fs = cfg.d_ff * cfg.num_shared_experts
        a, b, c = jax.random.split(ks, 3)
        p["shared"] = {
            "w1": dense_init(a, d_model, fs, dtype),
            "w3": dense_init(b, d_model, fs, dtype),
            "w2": dense_init(c, fs, d_model, dtype),
        }
    return p


class MoEAux(NamedTuple):
    lb_loss: jax.Array        # scalar load-balance loss (Switch-style)
    indices: jax.Array        # [T, K] final expert assignment (post-substitution)
    orig_indices: jax.Array   # [T, K] router's assignment
    topk_probs: jax.Array     # [T, K] renormalized probs
    n_substituted: jax.Array  # [] substituted slots
    n_missed: jax.Array       # [] non-resident slots with no buddy
    n_dropped: jax.Array      # [] tokens dropped by capacity
    miss_per_expert: jax.Array  # [E] miss counts (-> fetch bytes in the ledger)
    sub_slots: jax.Array      # [T, K] bool — per-slot substitution mask (lets
    miss_slots: jax.Array     # [T, K] bool — the serving engine mask out
    #                           inactive batch rows under continuous batching)
    n_degraded: jax.Array     # [] slots served from the quant-replica tier
    deg_slots: jax.Array      # [T, K] bool — per-slot degraded mask
    n_miss_drop: jax.Array    # [] misses the cost argmin dropped
    drop_slots: jax.Array     # [T, K] bool — per-slot cost-drop mask
    #                           (weights renormalized; no transfer, no stall)
    n_peered: jax.Array = None  # [] misses served by a peer-HBM borrow
    peer_slots: jax.Array = None  # [T, K] bool — per-slot peer-borrow mask
    #                               (full weight, fp compute at the true id;
    #                               the engine stalls on the ICI transfer)


def router_topk(router_w, x_flat, top_k: int, jitter_key=None, jitter=0.0):
    """Returns logits [T, E], topk indices [T, K], topk logits, renorm probs."""
    logits = jnp.einsum("td,de->te", x_flat.astype(jnp.float32), router_w)
    if jitter_key is not None and jitter > 0:
        logits = logits + jax.random.uniform(
            jitter_key, logits.shape, minval=-jitter, maxval=jitter)
    topk_logits, topk_idx = jax.lax.top_k(logits, top_k)
    probs = jax.nn.softmax(topk_logits, axis=-1)       # renormalized over S
    return logits, topk_idx.astype(jnp.int32), topk_logits, probs


def _degraded_outputs(quant: dict, x_flat: jax.Array, e_flat: jax.Array):
    """Per-slot SwiGLU against the resident quant-replica tier: [T*K, D] f32.

    Gathers each slot's TRUE expert from the int8/int4 tier so a miss is
    computed immediately at degraded fidelity instead of stalling on PCIe.
    The math lives in kernels/ref.dequant_swiglu — ONE reference shared with
    the quant_ffn / grouped_ffn oracles, so the in-model fallback and the
    kernel oracles cannot drift."""
    xr = jnp.repeat(x_flat.astype(jnp.float32),
                    e_flat.shape[0] // x_flat.shape[0], axis=0)  # [T*K, D]
    return dequant_swiglu(xr[:, None, :],
                          quant["w1_q"][e_flat], quant["w1_s"][e_flat],
                          quant["w3_q"][e_flat], quant["w3_s"][e_flat],
                          quant["w2_q"][e_flat], quant["w2_s"][e_flat])[:, 0]


def _fused_dispatch(params: dict, x_flat, new_idx, degraded, skip,
                    run_degraded: bool, use_kernel: bool, cap: int):
    """The single-dispatch hot path: per-slot outputs [T*K, D] for ALL
    outcome classes in one compute step.

    new_idx [T, K] — resolved expert ids (buddy slots already rewritten to
    the substituted id, so full-precision and buddy slots are the same
    class); degraded [T, K] — slots served from the quant replica at their
    TRUE id; skip [T, K] — slots whose mixture weight is zero (cost-argmin
    drops and fallback='drop' misses): they are never binned/computed.

    use_kernel=False: the jnp megastep — gather each slot's operands once,
    SELECTED by outcome class (fp table at the resolved id, or the dequant-
    scaled replica at the true id), then one SwiGLU einsum chain. This
    replaces fp-compute-over-all-slots PLUS quant-compute-over-all-slots
    with exactly one compute per slot.

    use_kernel=True: bin slots by (expert, class) into a [2E, cap, D]
    buffer and run kernels/grouped_ffn.py — one pallas_call, one scatter,
    one gather. Returns (y_rep [T*K, D], n_capacity_dropped [])."""
    t_n, d = x_flat.shape
    k_n = new_idx.shape[1]
    e_n = params["w1"].shape[0]
    e_flat = new_idx.reshape(-1)                                   # [N]
    n = e_flat.shape[0]
    deg_f = degraded.reshape(-1) if run_degraded \
        else jnp.zeros((n,), bool)
    skip_f = skip.reshape(-1)

    if not use_kernel:
        # -- jnp megastep: weights-as-operands by outcome class ---------
        xr = jnp.repeat(x_flat, k_n, axis=0)                       # [N, D]
        w1s = params["w1"][e_flat]                                 # [N, D, F]
        w3s = params["w3"][e_flat]
        w2s = params["w2"][e_flat]
        if run_degraded:
            q = params["quant"]
            sel = deg_f[:, None, None]
            # per-output-channel scales commute with the contraction, so
            # dequantizing the operand pre-matmul == the reference's
            # post-matmul placement (kernels/ref.dequant_swiglu)
            w1s = jnp.where(sel, (q["w1_q"][e_flat].astype(jnp.float32)
                                  * q["w1_s"][e_flat][:, None, :]
                                  ).astype(w1s.dtype), w1s)
            w3s = jnp.where(sel, (q["w3_q"][e_flat].astype(jnp.float32)
                                  * q["w3_s"][e_flat][:, None, :]
                                  ).astype(w3s.dtype), w3s)
            w2s = jnp.where(sel, (q["w2_q"][e_flat].astype(jnp.float32)
                                  * q["w2_s"][e_flat][:, None, :]
                                  ).astype(w2s.dtype), w2s)
        h = jax.nn.silu(jnp.einsum("td,tdf->tf", xr, w1s,
                                   preferred_element_type=jnp.float32))
        g = jnp.einsum("td,tdf->tf", xr, w3s,
                       preferred_element_type=jnp.float32)
        hg = (h * g).astype(x_flat.dtype)
        hg = shard(hg, None, "dff")
        y_rep = jnp.einsum("tf,tfd->td", hg, w2s,
                           preferred_element_type=jnp.float32
                           ).astype(x_flat.dtype)
        # skipped slots carry zero mixture weight; zero the output too so
        # the megastep's per-slot provenance matches the kernel path
        y_rep = jnp.where(skip_f[:, None], 0.0, y_rep)
        return y_rep, jnp.zeros((), jnp.int32)

    # -- Pallas grouped kernel: bin by (resolved expert, class) ---------
    grp = jnp.where(deg_f, e_flat + e_n, e_flat)
    grp = jnp.where(skip_f, 2 * e_n, grp)          # out of range: unbinned
    onehot = jax.nn.one_hot(grp, 2 * e_n, dtype=jnp.float32)       # [N, 2E]
    pos = (jnp.cumsum(onehot, axis=0) * onehot).sum(-1).astype(jnp.int32) - 1
    kept = (pos >= 0) & (pos < cap)
    n_cap_dropped = (pos >= cap).sum()
    pos_safe = jnp.where(kept, pos, cap)
    xr = jnp.repeat(x_flat, k_n, axis=0)                           # [N, D]
    buf = jnp.zeros((2 * e_n, cap, d), x_flat.dtype) \
        .at[grp, pos_safe].set(xr, mode="drop")
    if run_degraded:
        q = params["quant"]
        qargs = (q["w1_q"], q["w1_s"], q["w3_q"], q["w3_s"],
                 q["w2_q"], q["w2_s"])
    else:
        # no tier attached: the degraded half of the grid is empty; feed
        # zero replicas (constant-folded) so the kernel signature is static
        f_n = params["w1"].shape[2]
        qargs = (jnp.zeros((e_n, d, f_n), jnp.int8),
                 jnp.ones((e_n, f_n), jnp.float32),
                 jnp.zeros((e_n, d, f_n), jnp.int8),
                 jnp.ones((e_n, f_n), jnp.float32),
                 jnp.zeros((e_n, f_n, d), jnp.int8),
                 jnp.ones((e_n, d), jnp.float32))
    from repro.kernels import ops as kops
    out_buf = kops.grouped_ffn(buf, params["w1"], params["w3"],
                               params["w2"], *qargs)
    y_rep = out_buf.at[grp, pos_safe].get(mode="fill", fill_value=0)
    return y_rep.astype(x_flat.dtype), n_cap_dropped.astype(jnp.int32)


def dispatch_plan(policy: Optional[BuddyPolicy], tokens_shape, num_experts: int,
                  top_k: int, *, dropless: bool = False,
                  capacity_factor: float = 1.25) -> tuple:
    """Which dispatch ``moe_forward`` runs for tokens of ``tokens_shape``
    ([B, S] or [T]) and each expert's capacity: ("fused", cap),
    ("gather", 0), ("ragged", 0) where no slot can be dropped (every
    expert's capacity would be a row's S*K slots), else ("capacity", cap).
    Static: shapes and flags only."""
    t_n = math.prod(tokens_shape)
    one_token = len(tokens_shape) == 2 and tokens_shape[1] == 1
    if policy is not None and policy.use_fused_dispatch:
        if dropless or one_token:
            return "fused", t_n * top_k           # decode / chunked prefill
        cap = int(max(top_k, t_n * top_k / num_experts * capacity_factor))
        return "fused", min(t_n * top_k, -(-cap // 8) * 8)
    if not dropless and one_token and t_n * top_k < num_experts:
        return "gather", 0
    if dropless:
        return "ragged", 0
    rows = tokens_shape[0] if len(tokens_shape) == 2 else 1
    s_k = t_n // rows * top_k
    cap = int(max(top_k, s_k / num_experts * capacity_factor))
    cap = min(s_k, -(-cap // 8) * 8)
    return ("ragged", 0) if cap == s_k else ("capacity", cap)


def dispatch_slots(plan: tuple, tokens_shape, num_experts: int, top_k: int,
                   use_kernel: bool = False,
                   live_tokens: Optional[int] = None) -> int:
    """Expert-FFN slots one layer's dispatch computes under ``plan``: every
    expert's capacity on every row on the capacity path, every (expert,
    class) group's capacity in the grouped kernel, the ``live_tokens``' slots
    (default: every token's) in the grouped matmul, else one per slot."""
    path, cap = plan
    if path == "capacity":
        rows = tokens_shape[0] if len(tokens_shape) == 2 else 1
        return rows * num_experts * cap
    if path == "fused" and use_kernel:
        return 2 * num_experts * cap
    if path == "ragged" and live_tokens is not None:
        return live_tokens * top_k
    return math.prod(tokens_shape) * top_k


def moe_forward(params: dict, x: jax.Array, cfg: MoEConfig, *,
                policy: Optional[BuddyPolicy] = None,
                buddy: Optional[BuddyState] = None,
                capacity_factor: float = 1.25,
                jitter_key=None,
                use_kernel: bool = False,
                dropless: bool = False,
                live: Optional[jax.Array] = None,
                layer: Any = 0) -> tuple:
    """x: [B, S, D] (or [T, D]). Returns (y, MoEAux).

    ``dropless``: never drop a slot (the grouped matmul over every routed
    slot, no tiny-batch gather shortcut) — chunked prefill needs per-token
    outputs independent of which other tokens share the chunk, so C=1 and
    C=8 chunks produce identical per-token results.

    On the ragged plan (``dispatch_plan``) the expert FFN is one grouped
    matmul over the routed slots sorted by expert. ``live`` ([B, S] bool,
    default every token) marks the tokens whose slots it computes: the
    others' expert outputs are zero. ``params["w1"]`` / ``w3`` / ``w2`` may
    then be a stack of layers' tables flattened to [L*E, ...], and
    ``layer`` the index of this layer's block in it, so the matmul reads
    the experts in place.

    Tiered degraded fallback: when ``policy.quant_tier`` is on, the params
    carry a ``quant`` sub-dict, and ``buddy.quant_ok`` marks an expert, a
    missed slot computes against the resident low-precision replica in the
    SAME fused step (mixed-precision dispatch) — zero transfer, bounded
    fidelity loss. With quant_tier='off' this path is compiled out entirely
    and the graph is bit-identical to the pre-tier engine.

    Named scopes: ``router``, ``substitute``, ``dispatch`` (scatter or
    gather, the expert FFN and the combine, on every path) and
    ``shared_experts``."""
    orig_shape = x.shape
    d = x.shape[-1]
    x_flat = x.reshape(-1, d)
    t_n = x_flat.shape[0]
    e_n, k_n = cfg.num_experts, cfg.top_k

    use_tier = (policy is not None and policy.quant_tier != "off"
                and "quant" in params)
    quant_ok = buddy.quant_ok if (use_tier and buddy is not None) else None
    tier_fid_cost = (buddy.fid_cost
                     if (use_tier and buddy is not None) else None)
    run_degraded = use_tier and (quant_ok is not None
                                 or tier_fid_cost is not None)
    path, cap = dispatch_plan(policy, x.shape[:-1], e_n, k_n,
                              dropless=dropless,
                              capacity_factor=capacity_factor)

    with jax.named_scope("router"):
        logits, idx, topk_logits, probs = router_topk(
            params["router"], x_flat, k_n, jitter_key, cfg.router_jitter)

    with jax.named_scope("substitute"):
        new_idx, substituted, missed, degraded, dropped, peered = \
            _substitute(idx, topk_logits, logits, buddy, policy, quant_ok,
                        tier_fid_cost)
        weights = probs
        if policy is not None and policy.fallback == "drop":
            # missed slots are skipped; renormalize over the surviving set
            weights = jnp.where(missed, 0.0, weights)
            weights = weights / jnp.maximum(weights.sum(-1, keepdims=True),
                                            1e-9)
        if policy is not None and policy.miss_policy == "cost":
            # slots the cost argmin chose to drop: skip + renormalize
            # (per-slot counterpart of the global fallback='drop' above)
            weights = jnp.where(dropped, 0.0, weights)
            weights = weights / jnp.maximum(weights.sum(-1, keepdims=True),
                                            1e-9)

    with jax.named_scope("dispatch"):
        if path == "fused":
            # slots whose mixture weight was zeroed above — never computed
            skip = dropped
            if policy.fallback == "drop":
                skip = skip | missed
            y_rep, n_dropped = _fused_dispatch(
                params, x_flat, new_idx, degraded, skip,
                run_degraded, use_kernel, cap)
            yk = y_rep.reshape(t_n, k_n, d)
        elif path == "gather":
            yk = _gather_dispatch(params, x_flat, new_idx, degraded,
                                  run_degraded).reshape(t_n, k_n, d)
            n_dropped = jnp.zeros((), jnp.int32)
        elif path == "ragged":
            yk = _ragged_dispatch(params, x_flat, new_idx, live, layer,
                                  degraded, run_degraded, e_n)
            n_dropped = jnp.zeros((), jnp.int32)
        else:
            yk, n_dropped = _capacity_dispatch(
                params, x, x_flat, new_idx, degraded, run_degraded,
                use_kernel, cap)
        y = (yk * weights[..., None].astype(x.dtype)).sum(1)

    if cfg.num_shared_experts and "shared" in params:
        with jax.named_scope("shared_experts"):
            y = y + swiglu(x_flat, params["shared"]["w1"],
                           params["shared"]["w3"], params["shared"]["w2"])

    # ---------------- load-balance loss (Switch-style) ----------------
    p_mean = jax.nn.softmax(logits, axis=-1).mean(0)               # [E]
    onehot = jax.nn.one_hot(new_idx.reshape(-1), e_n, dtype=jnp.float32)
    f_frac = onehot.reshape(t_n, k_n, e_n).sum(1).mean(0)          # [E]
    lb = e_n * jnp.sum(f_frac * p_mean)

    miss_per_expert = jnp.zeros((e_n,), jnp.int32).at[idx.reshape(-1)].add(
        missed.reshape(-1).astype(jnp.int32))

    aux = MoEAux(lb, new_idx, idx, probs,
                 substituted.sum(), missed.sum(), n_dropped, miss_per_expert,
                 substituted, missed, degraded.sum(), degraded,
                 dropped.sum(), dropped, peered.sum(), peered)
    return y.reshape(orig_shape), aux


def _substitute(idx, topk_logits, logits, buddy, policy, quant_ok,
                tier_fid_cost):
    """Algorithm 1's outcome for every slot. Returns (new_idx, substituted,
    missed, degraded, dropped, peered)."""
    if policy is not None and buddy is not None:
        # substitute() owns the four-way miss split for EVERY mode,
        # including mode='none' (no rerouting, but misses still route to
        # the degraded tier before the fetch/drop fallback). In cost mode
        # the per-expert cost vectors replace the quant_ok precedence mask.
        # tier_fid_cost (not raw buddy.fid_cost): the degraded COMPUTE path
        # is gated on use_tier, so the argmin's degraded option must
        # be too — a finite fid_cost without quant params would mark slots
        # degraded and then silently compute them at full precision
        res: SubstituteResult = substitute(
            idx, topk_logits, buddy.resident, buddy.table, buddy.q, policy,
            router_logits=logits, hop=buddy.hop, quant_ok=quant_ok,
            fid_cost=tier_fid_cost, fetch_cost=buddy.fetch_cost,
            peer_ok=buddy.peer_ok, peer_cost=buddy.peer_cost)
        missed = res.missed
        zero = jnp.zeros_like(missed)
        return (res.indices, res.substituted, missed, res.degraded,
                res.dropped if res.dropped is not None else zero,
                res.peered if res.peered is not None else zero)
    if buddy is not None:           # no policy: raw residency miss count
        missed = ~buddy.resident[idx]
        zero = jnp.zeros_like(missed)
        return idx, zero, missed, zero, zero, zero
    zero = jnp.zeros(idx.shape, bool)
    return idx, zero, zero, zero, zero, zero


def _gather_dispatch(params, x_flat, new_idx, degraded, run_degraded):
    """Active-expert gather (tiny-batch decode): per-slot outputs [T*K, D].

    When the whole batch selects fewer expert-slots than there are experts
    (long-context decode, B*K < E), gathering the selected experts' weight
    rows reads only the ACTIVE experts from HBM — the dense dispatch path
    streams all E experts' weights every step. §Perf iteration 6."""
    k_n = new_idx.shape[1]
    e_flat = new_idx.reshape(-1)                                   # [T*K]
    w1s = params["w1"][e_flat]                                     # [T*K, D, F]
    w3s = params["w3"][e_flat]
    w2s = params["w2"][e_flat]
    xr = jnp.repeat(x_flat, k_n, axis=0)                           # [T*K, D]
    h = jax.nn.silu(jnp.einsum("td,tdf->tf", xr, w1s,
                               preferred_element_type=jnp.float32))
    g = jnp.einsum("td,tdf->tf", xr, w3s,
                   preferred_element_type=jnp.float32)
    hg = (h * g).astype(x_flat.dtype)
    hg = shard(hg, None, "dff")
    y_rep = jnp.einsum("tf,tfd->td", hg, w2s,
                       preferred_element_type=jnp.float32).astype(x_flat.dtype)
    if run_degraded:
        y_deg = _degraded_outputs(params["quant"], x_flat, e_flat)
        y_rep = jnp.where(degraded.reshape(-1)[:, None],
                          y_deg.astype(x_flat.dtype), y_rep)
    return y_rep


def _ragged_dispatch(params, x_flat, new_idx, live, layer, degraded,
                     run_degraded, num_experts: int):
    """Dropless dispatch as one grouped matmul: per-slot outputs [T, K, D].

    Each live slot goes to group ``layer * E + expert`` of the tables'
    [G, ...] stack; the slots are sorted by group (stable) and
    ``jax.lax.ragged_dot`` runs the three SwiGLU matmuls over the groups'
    contiguous rows, reading each expert's weights in place. Slots of
    tokens that are not ``live`` sort past the last group and are never
    computed: their outputs are zero."""
    t_n, d = x_flat.shape
    k_n = new_idx.shape[1]
    g_n = params["w1"].shape[0]
    gid = new_idx.reshape(-1) + layer * num_experts                 # [N]
    if live is not None:
        gid = jnp.where(jnp.repeat(live.reshape(-1), k_n), gid, g_n)
    order = jnp.argsort(gid, stable=True)
    sizes = jnp.bincount(gid, length=g_n).astype(jnp.int32)         # [G]
    xs = x_flat[order // k_n]                                       # [N, D]
    h = jax.nn.silu(jax.lax.ragged_dot(xs, params["w1"], sizes,
                                       preferred_element_type=jnp.float32))
    g = jax.lax.ragged_dot(xs, params["w3"], sizes,
                           preferred_element_type=jnp.float32)
    hg = (h * g).astype(x_flat.dtype)
    hg = shard(hg, None, "dff")
    ys = jax.lax.ragged_dot(hg, params["w2"], sizes,
                            preferred_element_type=jnp.float32)
    # rows past the last group are left unspecified by the grouped matmul
    computed = jnp.arange(ys.shape[0]) < sizes.sum()
    ys = jnp.where(computed[:, None], ys, 0.0).astype(x_flat.dtype)
    yk = jnp.zeros_like(ys).at[order].set(ys, unique_indices=True)
    yk = yk.reshape(t_n, k_n, d)
    if run_degraded:
        y_deg = _degraded_outputs(params["quant"], x_flat,
                                  new_idx.reshape(-1))
        yk = jnp.where(degraded[..., None],
                       y_deg.reshape(t_n, k_n, d).astype(x_flat.dtype), yk)
    return yk


def _capacity_dispatch(params, x, x_flat, new_idx, degraded, run_degraded,
                       use_kernel: bool, cap: int):
    """Capacity-based dispatch (row-local), for plans that can drop a slot
    (``cap`` < S*K): per-slot outputs [T, K, D] and the count of slots past
    an expert's capacity.

    Dispatch independently per batch row so that with the batch sharded
    over `data` the scatter/gather and expert compute are collective-free
    (tokens never cross data shards; experts are TP-sharded on d_ff)."""
    d = x.shape[-1]
    t_n = x_flat.shape[0]
    e_n = params["w1"].shape[0]
    k_n = new_idx.shape[1]
    rows = x.shape[0] if x.ndim == 3 else 1
    s_n = t_n // rows
    row_e = new_idx.reshape(rows, s_n * k_n)                        # [B, S*K]
    onehot = jax.nn.one_hot(row_e, e_n, dtype=jnp.float32)          # [B, S*K, E]
    pos = (jnp.cumsum(onehot, axis=1) * onehot).sum(-1).astype(jnp.int32) - 1
    kept = pos < cap
    n_dropped = (~kept).sum()
    pos_safe = jnp.where(kept, pos, cap)                            # cap -> dropped

    x_rep = jnp.repeat(x_flat.reshape(rows, s_n, d), k_n, axis=1)   # [B, S*K, D]

    def _row_scatter(xr, er, pr):
        return jnp.zeros((e_n, cap, d), x.dtype).at[er, pr].set(xr, mode="drop")

    # vmap -> scatter with operand batching dims: GSPMD keeps it data-local
    buf = jax.vmap(_row_scatter)(x_rep, row_e, pos_safe)            # [B, E, C, D]
    buf = shard(buf, "batch", "expert", None, None)

    if use_kernel:
        from repro.kernels import ops as kops
        flat = buf.transpose(1, 0, 2, 3).reshape(e_n, rows * cap, d)
        out = kops.expert_ffn(flat, params["w1"], params["w3"], params["w2"])
        out_buf = out.reshape(e_n, rows, cap, d).transpose(1, 0, 2, 3)
    else:
        h = jax.nn.silu(jnp.einsum("becd,edf->becf", buf, params["w1"],
                                   preferred_element_type=jnp.float32))
        g = jnp.einsum("becd,edf->becf", buf, params["w3"],
                       preferred_element_type=jnp.float32)
        hg = (h * g).astype(x.dtype)
        hg = shard(hg, "batch", "expert", None, "dff")
        out_buf = jnp.einsum("becf,efd->becd", hg, params["w2"],
                             preferred_element_type=jnp.float32).astype(x.dtype)

    def _row_gather(ob, er, pr):
        return ob.at[er, pr].get(mode="fill", fill_value=0)

    y_rep = jax.vmap(_row_gather)(out_buf, row_e, pos_safe)         # [B, S*K, D]
    yk = y_rep.reshape(t_n, k_n, d)                                 # [T, K, D]
    if run_degraded:
        y_deg = _degraded_outputs(params["quant"], x_flat,
                                  new_idx.reshape(-1))
        yk = jnp.where(degraded[..., None],
                       y_deg.reshape(t_n, k_n, d).astype(x.dtype), yk)
    return yk, n_dropped
