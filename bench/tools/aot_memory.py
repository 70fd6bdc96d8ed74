"""Compile a cell's decode step and prefill chunk for a described TPU v5e
(no chip needed) and print what each needs in device memory.

    JAX_PLATFORMS=cpu python3 bench/tools/aot_memory.py --workload <name>
"""
import argparse
import functools
import os
import sys

os.environ.setdefault("TPU_LOG_DIR", "disabled")
BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [BENCH, os.path.join(os.path.dirname(BENCH), "src")]


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--ctx", type=int, default=0,
                    help="KV positions per slot (0: the mix's longest request)")
    ap.add_argument("--slots", type=int, default=0)
    ap.add_argument("--chunk", type=int, default=0)
    args = ap.parse_args()
    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    from harness.model import build_params
    from harness.spec import Spec
    from repro.configs.base import ModelConfig, MoEConfig
    from repro.core import BuddyPolicy
    from repro.models import transformer
    from repro.models.moe import BuddyState

    jax.config.update("jax_enable_compilation_cache", False)
    spec = Spec(os.path.dirname(BENCH))
    cell = spec.workload(args.workload)
    cf, tf = spec.config(cell), spec.traffic(cell)
    m = cf["model"]
    cfg = ModelConfig(
        arch_id=cf["name"], family="moe", source="", num_layers=m["num_layers"],
        d_model=m["d_model"], num_heads=m["num_heads"],
        num_kv_heads=m["num_kv_heads"], d_ff=m["d_ff"],
        vocab_size=m["vocab_size"], head_dim=m["head_dim"],
        moe=MoEConfig(**m["moe"]), sliding_window=m["sliding_window"],
        rope_theta=m["rope_theta"], norm_eps=m["norm_eps"], dtype=m["dtype"])
    b = args.slots or tf["slots"]
    c = args.chunk or tf["prefill_chunk"]
    ctx = args.ctx or (tf["prompt_len"]["max"] + tf["output_len"]["max"])
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    one = SingleDeviceSharding(topo.devices[0])

    def place(tree):
        return jax.tree.map(
            lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=one),
            tree)

    def sds(shape, dt):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one)

    params = place(jax.eval_shape(lambda: build_params(0, m)))
    caches = place(jax.eval_shape(
        lambda: transformer.init_caches(cfg, b, ctx)))
    n_l, e = m["num_layers"], m["moe"]["num_experts"]
    r = cf["buddy"]["list_len"]
    buddies = BuddyState(resident=sds((n_l, e), jnp.bool_),
                         table=sds((n_l, e, r), jnp.int32),
                         q=sds((n_l, e, r), jnp.float32),
                         hop=sds((n_l, e), jnp.int32))
    policy = BuddyPolicy(mode=tf["policy"], **cf["buddy"]["policy"])
    rng = sds((2,), jnp.uint32)
    step = jax.jit(functools.partial(transformer.decode_step, cfg=cfg,
                                     policy=policy, record=True, window=-1))
    chunk = jax.jit(functools.partial(transformer.prefill_chunk, cfg=cfg,
                                      policy=policy, record=True, window=-1))
    vec = sds((b,), jnp.int32)
    progs = {
        "decode_step": step.lower(params=params, token=vec, caches=caches,
                                  pos=vec, buddies=buddies, rng=rng),
        "prefill_chunk": chunk.lower(params=params, tokens=sds((b, c),
                                                               jnp.int32),
                                     caches=caches, base_pos=vec,
                                     tok_valid=sds((b, c), jnp.bool_),
                                     buddies=buddies, rng=rng),
    }
    for name, low in progs.items():
        try:
            mem = low.compile().memory_analysis()
        except Exception as e:                    # noqa: BLE001
            print(f"{name}: slots {b}, ctx {ctx}, chunk {c}: "
                  f"{str(e).splitlines()[0][:200]}", flush=True)
            continue
        total = (mem.argument_size_in_bytes + mem.output_size_in_bytes
                 + mem.temp_size_in_bytes - mem.alias_size_in_bytes)
        print(f"{name}: slots {b}, ctx {ctx}, chunk {c}: arguments "
              f"{mem.argument_size_in_bytes / 1e9:.3f} GB, outputs "
              f"{mem.output_size_in_bytes / 1e9:.3f} GB, temporaries "
              f"{mem.temp_size_in_bytes / 1e9:.3f} GB, aliased "
              f"{mem.alias_size_in_bytes / 1e9:.3f} GB, sum "
              f"{total / 1e9:.3f} GB", flush=True)


if __name__ == "__main__":
    main()
