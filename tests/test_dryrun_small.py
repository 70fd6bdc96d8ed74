"""Dry-run machinery on a small fake-device mesh (subprocess because the
device count must be fixed before jax initializes)."""
import json
import os
import subprocess
import sys

import pytest

SRC = os.path.join(os.path.dirname(__file__), "..", "src")

SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import json
import jax
from jax.sharding import PartitionSpec as P
import dataclasses
from repro.configs.base import get_reduced
from repro.launch.sharding import (ShardingProfile, activation_rules,
                                   param_specs, sanitize_specs)
from repro.launch import roofline as rl
from repro.models import transformer
from repro.models.common import axis_rules
from repro.training.optimizer import AdamWConfig
from repro.training.train_loop import make_train_step

arch = "ARCH"
cfg = dataclasses.replace(get_reduced(arch), dtype="bfloat16")
mesh = jax.make_mesh((4, 2), ("data", "model"),
                     axis_types=(jax.sharding.AxisType.Auto,) * 2)
prof = ShardingProfile()
rules = activation_rules(prof, cfg, 2)

key = jax.random.PRNGKey(0)
p_struct = jax.eval_shape(lambda k: transformer.init_params(cfg, k), key)
p_spec = param_specs(cfg, p_struct, prof)

import jax.numpy as jnp
toks = jax.ShapeDtypeStruct((8, 32), jnp.int32)
step = make_train_step(cfg, AdamWConfig(), remat=True)

def fn(params, tokens, targets, rng):
    with axis_rules(rules):
        return step(params, None, tokens, targets, rng)[2]["ce"]

# loss-only lowering (opt state skipped for speed)
def fn2(params, tokens, targets, rng):
    from repro.training.train_loop import lm_loss
    with axis_rules(rules):
        return lm_loss(params, cfg, tokens, targets, rng=rng)[0]

from jax.sharding import NamedSharding
def ns(tree, structs):
    return jax.tree.map(lambda s, x: NamedSharding(mesh, s),
                        sanitize_specs(tree, structs, mesh), structs,
                        is_leaf=lambda x: isinstance(x, P))

with mesh:
    jitted = jax.jit(fn2, in_shardings=(
        ns(p_spec, p_struct),
        NamedSharding(mesh, P("data", None)),
        NamedSharding(mesh, P("data", None)), NamedSharding(mesh, P())))
    lowered = jitted.lower(p_struct, toks, toks, key)
    compiled = lowered.compile()
    hlo = compiled.as_text()
r = rl.analyze_hlo(hlo, 8)
print(json.dumps({"flops": r["flops"], "coll": r["coll_bytes"],
                  "loops": r["loops"]}))
"""


@pytest.mark.parametrize("arch", ["internlm2-1.8b", "mixtral-8x7b",
                                  "rwkv6-1.6b"])
def test_small_mesh_lowering(arch):
    env = dict(os.environ, PYTHONPATH=SRC)
    out = subprocess.run([sys.executable, "-c", SCRIPT.replace("ARCH", arch)],
                         capture_output=True, text=True, env=env, timeout=420)
    assert out.returncode == 0, out.stderr[-3000:]
    r = json.loads(out.stdout.strip().splitlines()[-1])
    assert r["flops"] > 0
    assert r["loops"], "expected a scan-over-layers while loop"


def test_roofline_parsers():
    from repro.launch import roofline as rl
    hlo = """
HloModule m

%body (p: (s32[], f32[8,8])) -> (s32[], f32[8,8]) {
  %p = (s32[], f32[8,8]) parameter(0)
  %d = f32[8,8] dot(%a, %b), lhs_contracting_dims={1}, rhs_contracting_dims={0}
  %ar = f32[8,8] all-reduce(%d), replica_groups=[4,2]<=[8]
}

ENTRY %main () -> f32[8,8] {
  %a = f32[8,8] parameter(0)
  %w = (s32[], f32[8,8]) while(%t), condition=%c, body=%body, backend_config={"known_trip_count":{"n":"5"}}
}
"""
    r = rl.analyze_hlo(hlo, 8)
    assert r["loops"] == [{"comp": "main", "trip": 5}]
    # all-reduce: 2 * (1/2) * 256 bytes * 5 trips = 1280
    assert abs(r["coll_bytes"] - 2 * 0.5 * 256 * 5) < 1e-6
    # dot: 2*64*8 * 5 = 5120 flops (contract dim read from %a's shape)
    assert abs(r["flops"] - 2 * 64 * 8 * 5) < 1e-6


def test_shape_bytes():
    from repro.launch.roofline import _shape_bytes
    assert _shape_bytes("f32[2,3]") == 24
    assert _shape_bytes("bf16[10]{0}") == 20
    assert _shape_bytes("(f32[2], s32[4])") == 24
    assert _shape_bytes("pred[]") == 1


def test_sanitize_spec():
    import jax
    from jax.sharding import PartitionSpec as P
    from repro.launch.sharding import sanitize_spec
    mesh = jax.make_mesh((1,), ("model",))

    class FakeMesh:
        shape = {"model": 16, "data": 4}
    m = FakeMesh()
    assert sanitize_spec(P("model"), (32,), m) == P("model")
    assert sanitize_spec(P("model"), (5,), m) == P(None)
    assert sanitize_spec(P(("data", "model")), (64,), m) == P(("data", "model"))
    assert sanitize_spec(P(("data", "model")), (8,), m) == P("data")
