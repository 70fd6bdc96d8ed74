"""Ahead-of-time compiles for a described TPU v5e (no chip attached).

Mosaic refuses kernels that interpret mode runs happily (a zero-width slice
did exactly that), and the chip's compiler refuses programs that do not fit
its memory. These tests compile the MoE kernels at DeepSeek-V2-Lite widths
and the full-width decode step for one v5e chip, so such faults surface
without a chip. The topology is described inside a fixture: loading the TPU
compiler at import time would break multi-worker collection.
"""
import dataclasses
import functools
import os

import jax
import jax.numpy as jnp
import pytest

from repro.configs.base import get_config
from repro.core.policy import BuddyPolicy
from repro.kernels.buddy_substitute import buddy_substitute_pallas
from repro.kernels.expert_ffn import expert_ffn_pallas
from repro.kernels.grouped_ffn import grouped_ffn_pallas
from repro.kernels.quant_ffn import quant_ffn_pallas
from repro.kernels.topk_gate import topk_gate_pallas
from repro.models import transformer
from repro.models.moe import BuddyState

CFG = get_config("deepseek-v2-lite-buddy")
E, D, F, K = CFG.moe.num_experts, CFG.d_model, CFG.moe.d_ff, CFG.moe.top_k
T = 8                   # decode tokens per kernel call
R = 8                   # buddy table width (build_buddy_lists' k_max)
HBM_BYTES = 16e9        # one v5e chip


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure means no compiler
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache but
    # cannot be read back without one: keep the cache out of these tests
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)


def _shape(sharding, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _kernel_case(name):
    f32, i8, i32 = jnp.float32, jnp.int8, jnp.int32
    fp = [((E, D, F), f32), ((E, D, F), f32), ((E, F, D), f32)]
    q = [((E, D, F), i8), ((E, F), f32), ((E, D, F), i8), ((E, F), f32),
         ((E, F, D), i8), ((E, D), f32)]
    return {
        "buddy_substitute": (
            functools.partial(buddy_substitute_pallas, h=8, rho=3,
                              interpret=False),
            [((T, K), i32), ((T,), jnp.bool_), ((E,), jnp.bool_),
             ((E, R), i32), ((E, R), f32)]),
        "topk_gate": (
            functools.partial(topk_gate_pallas, k=K, interpret=False),
            [((T, E), f32), ((), f32)]),
        "expert_ffn": (functools.partial(expert_ffn_pallas, interpret=False),
                       [((E, T, D), f32)] + fp),
        "quant_ffn": (functools.partial(quant_ffn_pallas, interpret=False),
                      [((E, T, D), f32)] + q),
        "grouped_ffn": (functools.partial(grouped_ffn_pallas,
                                          interpret=False),
                        [((2 * E, T, D), f32)] + fp + q),
    }[name]


@pytest.mark.parametrize("name", ["buddy_substitute", "topk_gate",
                                  "expert_ffn", "quant_ffn", "grouped_ffn"])
def test_kernel_compiles_for_v5e(one_chip, name):
    fn, specs = _kernel_case(name)
    args = [_shape(one_chip, s, d) for s, d in specs]
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text(), \
        f"{name}: no Mosaic kernel in the compiled program"


def test_decode_step_compiles_for_v5e(one_chip):
    """The served decode step (buddy policy, 4 slots x 64 KV positions) at
    full width with one MoE layer fits one chip's memory."""
    cfg = dataclasses.replace(CFG, num_layers=1)
    slots, ctx = 4, 64

    def place(tree):
        return jax.tree.map(lambda s: _shape(one_chip, s.shape, s.dtype), tree)

    params = place(jax.eval_shape(transformer.init_params, cfg,
                                  jax.random.PRNGKey(0)))
    caches = place(jax.eval_shape(
        lambda: transformer.init_caches(cfg, slots, ctx)))
    layers = cfg.num_layers
    buddies = BuddyState(
        resident=_shape(one_chip, (layers, E), jnp.bool_),
        table=_shape(one_chip, (layers, E, R), jnp.int32),
        q=_shape(one_chip, (layers, E, R), jnp.float32),
        hop=_shape(one_chip, (layers, E), jnp.int32))
    step = jax.jit(functools.partial(
        transformer.decode_step, cfg=cfg, policy=BuddyPolicy(mode="buddy"),
        record=True, window=-1))
    vec = _shape(one_chip, (slots,), jnp.int32)
    compiled = step.lower(params=params, token=vec, caches=caches, pos=vec,
                          buddies=buddies,
                          rng=_shape(one_chip, (2,), jnp.uint32)).compile()
    mem = compiled.memory_analysis()
    used = mem.argument_size_in_bytes + mem.temp_size_in_bytes
    assert 0 < used < HBM_BYTES, f"decode step needs {used / 1e9:.2f} GB"
