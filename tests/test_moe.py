"""MoE layer: dispatch correctness vs dense per-expert reference, capacity
behavior, shared experts, and the buddy hook."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import MoEConfig
from repro.core.policy import BuddyPolicy
from repro.models import moe as M


def _dense_ref(params, x, cfg: MoEConfig):
    """Reference: run every expert on every token, combine by top-k weights."""
    xf = np.asarray(x, np.float64).reshape(-1, x.shape[-1])
    logits = xf @ np.asarray(params["router"], np.float64)
    order = np.argsort(-logits, axis=1, kind="stable")[:, :cfg.top_k]
    picked = np.take_along_axis(logits, order, axis=1)
    w = np.exp(picked - picked.max(1, keepdims=True))
    w /= w.sum(1, keepdims=True)
    y = np.zeros_like(xf)
    for e in range(cfg.num_experts):
        w1 = np.asarray(params["w1"][e], np.float64)
        w3 = np.asarray(params["w3"][e], np.float64)
        w2 = np.asarray(params["w2"][e], np.float64)
        h = (xf @ w1) * (1 / (1 + np.exp(-(xf @ w1)))) * (xf @ w3)
        ye = h @ w2
        for k in range(cfg.top_k):
            mask = (order[:, k] == e)
            y[mask] += w[mask, k][:, None] * ye[mask]
    if cfg.num_shared_experts and "shared" in params:
        s = params["shared"]
        hx = xf @ np.asarray(s["w1"], np.float64)
        h = hx * (1 / (1 + np.exp(-hx))) * (xf @ np.asarray(s["w3"], np.float64))
        y += h @ np.asarray(s["w2"], np.float64)
    return y.reshape(x.shape)


@pytest.mark.parametrize("shared", [0, 1])
def test_moe_matches_dense_reference(shared):
    cfg = MoEConfig(num_experts=4, top_k=2, d_ff=32, num_shared_experts=shared)
    key = jax.random.PRNGKey(0)
    params = M.init_moe(key, 24, cfg, jnp.float32)
    x = jax.random.normal(jax.random.fold_in(key, 1), (2, 9, 24)) * 0.5
    y, aux = M.moe_forward(params, x, cfg, capacity_factor=4.0)
    ref = _dense_ref(params, np.asarray(x), cfg)
    assert int(aux.n_dropped) == 0
    np.testing.assert_allclose(np.asarray(y), ref, rtol=1e-4, atol=1e-4)


def test_capacity_drops_tokens():
    cfg = MoEConfig(num_experts=2, top_k=1, d_ff=8)
    key = jax.random.PRNGKey(2)
    params = M.init_moe(key, 8, cfg, jnp.float32)
    # force all tokens to one expert by biasing the router
    params["router"] = jnp.zeros_like(params["router"]).at[:, 0].set(10.0)
    x = jax.random.normal(key, (1, 64, 8))
    y, aux = M.moe_forward(params, x, cfg, capacity_factor=0.25)
    assert int(aux.n_dropped) > 0


def test_buddy_substitution_changes_indices():
    cfg = MoEConfig(num_experts=4, top_k=2, d_ff=16)
    key = jax.random.PRNGKey(3)
    params = M.init_moe(key, 16, cfg, jnp.float32)
    x = jax.random.normal(key, (1, 20, 16))
    table = jnp.asarray([[1, 2, 3], [0, 2, 3], [3, 0, 1], [2, 0, 1]],
                        jnp.int32)
    q = jnp.full((4, 3), 0.33, jnp.float32)
    buddy = M.BuddyState(resident=jnp.asarray([True, False, True, False]),
                         table=table, q=q, hop=jnp.zeros((4,), jnp.int32))
    pol = BuddyPolicy(tau=0.0, beta=1.1, rho=2, H=3)
    y, aux = M.moe_forward(params, x, cfg, policy=pol, buddy=buddy,
                           capacity_factor=4.0)
    final = np.asarray(aux.indices)
    # all final experts must be resident (every expert has resident buddies)
    assert np.isin(final, [0, 2]).all()
    assert int(aux.n_substituted) > 0
    assert int(aux.n_missed) == 0


def test_original_policy_counts_misses():
    cfg = MoEConfig(num_experts=4, top_k=2, d_ff=16)
    key = jax.random.PRNGKey(4)
    params = M.init_moe(key, 16, cfg, jnp.float32)
    x = jax.random.normal(key, (1, 20, 16))
    buddy = M.BuddyState(resident=jnp.asarray([True, False, True, False]),
                         table=jnp.full((4, 3), -1, jnp.int32),
                         q=jnp.zeros((4, 3)), hop=jnp.zeros((4,), jnp.int32))
    y, aux = M.moe_forward(params, x, cfg, policy=BuddyPolicy(mode="none"),
                           buddy=buddy, capacity_factor=4.0)
    orig = np.asarray(aux.orig_indices)
    expected_misses = np.isin(orig, [1, 3]).sum()
    assert int(aux.n_missed) == expected_misses
    assert int(aux.n_substituted) == 0
    # fetch fallback computes the true experts: output matches full residency
    y_full, _ = M.moe_forward(params, x, cfg, capacity_factor=4.0)
    np.testing.assert_allclose(np.asarray(y), np.asarray(y_full), rtol=1e-5)


def test_lb_loss_uniform_router_is_one():
    cfg = MoEConfig(num_experts=8, top_k=2, d_ff=8)
    key = jax.random.PRNGKey(5)
    params = M.init_moe(key, 16, cfg, jnp.float32)
    params["router"] = jnp.zeros_like(params["router"])
    x = jax.random.normal(key, (1, 256, 16))
    _, aux = M.moe_forward(params, x, cfg, capacity_factor=4.0)
    # With a uniform router, E * sum(f_e * P_e) = E * E * (k/E) * (1/E) = k
    assert abs(float(aux.lb_loss) - cfg.top_k) < 0.2


# ---------------------------------------------------------------------------
# Ragged plan: the grouped matmul over the routed slots, sorted by expert
# ---------------------------------------------------------------------------
def _ragged_setup(shared=0, layers=1, seed=6, shape=(3, 8, 24)):
    cfg = MoEConfig(num_experts=8, top_k=2, d_ff=32,
                    num_shared_experts=shared)
    key = jax.random.PRNGKey(seed)
    ps = [M.init_moe(jax.random.fold_in(key, i), shape[-1], cfg, jnp.float32)
          for i in range(layers)]
    x = jax.random.normal(jax.random.fold_in(key, 99), shape) * 0.5
    return cfg, ps, x


@pytest.mark.parametrize("shared", [0, 1])
def test_ragged_plan_matches_dense_reference(shared):
    cfg, (params,), x = _ragged_setup(shared)
    assert M.dispatch_plan(None, x.shape[:-1], cfg.num_experts, cfg.top_k,
                           dropless=True) == ("ragged", 0)
    y, aux = M.moe_forward(params, x, cfg, dropless=True)
    assert int(aux.n_dropped) == 0
    np.testing.assert_allclose(np.asarray(y),
                               _dense_ref(params, np.asarray(x), cfg),
                               rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("layer", [0, 2])
def test_ragged_per_slot_equals_capacity_dispatch(layer):
    """Per-slot outputs at the first and last layer of a stacked group,
    read at the layer's offset in the flattened [L*E, ...] tables, equal
    the capacity dispatch's at capacity S*K on that layer's own tables."""
    cfg, ps, x = _ragged_setup(layers=3)
    e_n, k_n = cfg.num_experts, cfg.top_k
    stacked = {n: jnp.concatenate([p[n] for p in ps]) for n in
               ("w1", "w3", "w2")}
    x_flat = x.reshape(-1, x.shape[-1])
    _, idx, _, _ = M.router_topk(ps[layer]["router"], x_flat, k_n)
    no = jnp.zeros(idx.shape, bool)
    s_k = x.shape[1] * k_n
    want, n_dropped = M._capacity_dispatch(ps[layer], x, x_flat, idx, no,
                                           False, False, s_k)
    got = M._ragged_dispatch(stacked, x_flat, idx, None, jnp.int32(layer),
                             no, False, e_n)
    assert int(n_dropped) == 0
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_ragged_skips_tokens_that_are_not_live():
    """Slots of padding tokens are never computed (zero rows), and what the
    padding holds leaves the live tokens' outputs as they are."""
    cfg, (params,), x = _ragged_setup()
    live = jnp.arange(x.shape[1])[None, :] < jnp.asarray([[3], [8], [0]])
    noisy = jnp.where(live[..., None], x, 7.0 * x[::-1] + 3.0)
    x_flat, n_flat = (a.reshape(-1, a.shape[-1]) for a in (x, noisy))
    _, idx, _, _ = M.router_topk(params["router"], x_flat, cfg.top_k)
    _, n_idx, _, _ = M.router_topk(params["router"], n_flat, cfg.top_k)
    n_idx = jnp.where(live.reshape(-1, 1), idx, n_idx)
    no = jnp.zeros(idx.shape, bool)
    args = (None, jnp.int32(0), no, False, cfg.num_experts)
    full = M._ragged_dispatch(params, x_flat, idx, *args)
    got = M._ragged_dispatch(params, n_flat, n_idx, live, *args[1:])
    lv = np.asarray(live.reshape(-1))
    assert not np.asarray(got)[~lv].any()
    np.testing.assert_array_equal(np.asarray(got)[lv], np.asarray(full)[lv])
    # through moe_forward: a padding token's expert output is zero
    y, _ = M.moe_forward(params, noisy, cfg, dropless=True, live=live)
    assert not np.asarray(y)[~np.asarray(live)].any()


def _chunked(params, x, cfg, chunk):
    return jnp.concatenate(
        [M.moe_forward(params, x[:, i:i + chunk], cfg, dropless=True)[0]
         for i in range(0, x.shape[1], chunk)], axis=1)


@pytest.mark.parametrize("chunk", [3, 8])
def test_ragged_token_output_independent_of_chunk(chunk):
    """Dropless: a token's output is the same in a chunk of 3 or 8 tokens
    as alone (chunk 1)."""
    cfg, (params,), x = _ragged_setup(shared=1)
    np.testing.assert_allclose(np.asarray(_chunked(params, x, cfg, chunk)),
                               np.asarray(_chunked(params, x, cfg, 1)),
                               rtol=1e-5, atol=1e-6)


def test_ragged_reads_each_layer_of_the_stack():
    """Through the model: the scan hands the grouped matmul the whole stack
    of tables and each layer's index; the logits equal the fused dispatch's,
    which gathers every slot's weights from the layer's own tables."""
    from repro.configs.deepseek_v2_lite_buddy import reduced
    from repro.models import transformer
    cfg = reduced()
    assert cfg.num_layers > 1
    params = transformer.init_params(cfg, jax.random.PRNGKey(7))
    tokens = jax.random.randint(jax.random.PRNGKey(8), (2, 6), 0,
                                cfg.vocab_size)
    want, _ = transformer.forward_train(
        params, cfg, tokens, dropless=True,
        policy=BuddyPolicy(use_fused_dispatch=True))
    got, _ = transformer.forward_train(params, cfg, tokens, dropless=True,
                                       policy=BuddyPolicy())
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


def test_decode_step_skips_rows_that_are_not_live():
    """A decode step's grouped matmul computes only the live rows' slots:
    their logits and KV writes are those of a step where every row is live."""
    from repro.configs.deepseek_v2_lite_buddy import reduced
    from repro.models import transformer
    cfg = reduced()
    b = 4
    assert transformer._moe_plan(cfg, None, "step", (b, 1))[0] == "ragged"
    params = transformer.init_params(cfg, jax.random.PRNGKey(9))
    caches = transformer.init_caches(cfg, b, 8)
    token = jnp.asarray([3, 5, 7, 11], jnp.int32)
    pos = jnp.zeros((b,), jnp.int32)
    live = jnp.asarray([True, False, True, False])
    want, want_c, _ = transformer.decode_step(params, cfg, token, caches, pos)
    got, got_c, _ = transformer.decode_step(params, cfg, token, caches, pos,
                                            tok_valid=live)
    np.testing.assert_array_equal(np.asarray(got)[np.asarray(live)],
                                  np.asarray(want)[np.asarray(live)])
    for g, w in zip(jax.tree.leaves(got_c), jax.tree.leaves(want_c)):
        np.testing.assert_array_equal(np.asarray(g)[:, np.asarray(live)],
                                      np.asarray(w)[:, np.asarray(live)])
    assert not np.allclose(np.asarray(got)[1], np.asarray(want)[1])
