"""A configuration's model builder (``harness/model.py``, ``spec.load_builder``):
the default builds what the harness built before builders existed, a
configuration that names a builder file runs a cell end to end, and the
comparison with the reference follows the builder's stack of layer kinds."""
import hashlib
import io
import json
import os
import sys
import types
from pathlib import Path

os.environ.setdefault("JAX_PLATFORMS", "cpu")
sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

import jax  # noqa: E402
import numpy as np  # noqa: E402
import pytest  # noqa: E402

import tiny  # noqa: E402
from harness import check  # noqa: E402
from harness.model import buddy_tables, layer_key  # noqa: E402
from harness.runner import run_cell, step_flops  # noqa: E402
from harness.spec import load_builder  # noqa: E402

ROOT = Path(__file__).resolve().parents[2]
TINY = {"name": "tiny", "source": "bench/tests/tiny.py",
        "model": tiny.TINY_MODEL}


def _cfg(name):
    if name == "tiny":
        return TINY
    return json.loads((ROOT / f"bench/configs/{name}.json").read_text())


def _hard_coded_model_config(cf):
    """The ``ModelConfig`` the runner built before builders existed."""
    from repro.configs.base import ModelConfig, MoEConfig
    m = cf["model"]
    return ModelConfig(
        arch_id=cf["name"], family="moe", source=cf["source"],
        num_layers=m["num_layers"], d_model=m["d_model"],
        num_heads=m["num_heads"], num_kv_heads=m["num_kv_heads"],
        d_ff=m["d_ff"], vocab_size=m["vocab_size"], head_dim=m["head_dim"],
        moe=MoEConfig(**m["moe"]), sliding_window=m["sliding_window"],
        rope_theta=m["rope_theta"], norm_eps=m["norm_eps"], dtype=m["dtype"])


def _hand_layout(m):
    """The parameter tree's shapes and dtypes, by hand: one scan group of
    ``num_layers`` attention + MoE blocks."""
    n, d, v = m["num_layers"], m["d_model"], m["vocab_size"]
    qd, kvd = m["num_heads"] * m["head_dim"], m["num_kv_heads"] * m["head_dim"]
    e, f, s = (m["moe"][k] for k in ("num_experts", "d_ff",
                                     "num_shared_experts"))
    dt, f32 = m["dtype"], "float32"
    moe = {"router": ((n, d, e), f32), "w1": ((n, e, d, f), dt),
           "w3": ((n, e, d, f), dt), "w2": ((n, e, f, d), dt)}
    if s:
        moe["shared"] = {"w1": ((n, d, s * f), dt), "w3": ((n, d, s * f), dt),
                         "w2": ((n, s * f, d), dt)}
    return {"embed": ((v, d), dt), "final_norm": ((d,), f32),
            "lm_head": ((d, v), dt),
            "groups": ({"ln1": ((n, d), f32), "ln2": ((n, d), f32),
                        "attn": {"wq": ((n, d, qd), dt), "wk": ((n, d, kvd), dt),
                                 "wv": ((n, d, kvd), dt),
                                 "wo": ((n, qd, d), dt)},
                        "moe": moe},)}


# token_flops at contexts 0, 1 and 1024, and chunk_flops of 16 tokens from
# position 100, as the harness counted them before builders existed
FLOPS = {"tiny": ([157696, 158208, 681984], 3411968),
         "dsv2lite-3l-f32": ([936116224, 936140800, 961282048], 15020523520),
         "mixtral8x7b-1l-f32": ([1050738688, 1050755072, 1067515904],
                                16840261632)}


@pytest.mark.parametrize("name", sorted(FLOPS))
def test_default_builder_builds_as_before(name):
    cf = _cfg(name)
    m = cf["model"]
    b = load_builder(ROOT, cf)
    assert b.model_config(cf) == _hard_coded_model_config(cf)
    assert b.layer_kinds(m) == ["moe"] * m["num_layers"]
    assert b.moe_shape(m) == (m["num_layers"], m["moe"]["num_experts"])
    got = jax.eval_shape(lambda: b.build_params(0, m))
    assert jax.tree.map(lambda s: (s.shape, str(s.dtype)), got) == \
        _hand_layout(m)
    per_ctx, chunk = FLOPS[name]
    assert [b.token_flops(m, c) for c in (0, 1, 1024)] == per_ctx
    steps = [{"t1": 0.5, "pos": [100, 7], "counts": [16, 0]},
             {"t1": 2.0, "pos": [0], "counts": [9]}]      # outside [0, 1)
    assert step_flops(b, m, steps, 0.0, 1.0) == chunk


def _digest(tree):
    h = hashlib.sha256()
    for path, leaf in jax.tree_util.tree_flatten_with_path(tree)[0]:
        a = np.asarray(leaf)
        for part in (jax.tree_util.keystr(path), str(a.dtype), str(a.shape)):
            h.update(part.encode())
        h.update(a.tobytes())
    return h.hexdigest()


def test_default_builder_tiny_weights_bit_identical():
    """The tiny weights and buddy tables, against digests taken from the
    harness before builders existed; and layer by layer through the
    builder's ``layer_weights(key, m, kind)``."""
    seed, m = 2**31 + 77, tiny.TINY_MODEL
    b = load_builder(ROOT, TINY)
    full = b.build_params(seed, m)
    assert _digest(full) == \
        "f936adcb0fe220e96ea7c0f760846a11bbefbb6fbc772f844e5c27d60016162e"
    t, q = buddy_tables(seed, b.moe_shape(m), 4)
    assert hashlib.sha256(t.tobytes() + q.tobytes()).hexdigest() == \
        "a5816933b6824a4047473f50c7dfd3b24eecaa5f6d1562082e3535c04321eee0"
    for l, kind in enumerate(b.layer_kinds(m)):
        one = jax.jit(lambda k: b.layer_weights(k, m, kind))(layer_key(seed, l))
        got = jax.tree.map(lambda a: a[l], full["groups"][0])
        assert _digest(got) == _digest(one)


def test_cell_runs_through_a_named_builder(tmp_path, monkeypatch):
    """``tiny-built-chat`` names a copy of the default builder as a new
    file: the run loads it, is correct, and its compared numbers equal the
    default builder's on the same served tokens."""
    root = tiny.make_root(tmp_path)
    real, seen = check.compare, {}

    def spy(cfg_file, root_, seed, reqs, steps, table, builder, **kw):
        seen["builder"] = builder
        default = {k: v for k, v in cfg_file.items() if k != "builder"}
        seen["default"] = real(default, root_, seed, reqs, steps, table,
                               load_builder(root_, default), **kw)
        return real(cfg_file, root_, seed, reqs, steps, table, builder, **kw)

    monkeypatch.setattr(check, "compare", spy)
    res, run, compared = run_cell(root, tiny.CELL_BUILT, 2**31 + 5, 2.0,
                                  False, require_tpu=False,
                                  stdout=io.StringIO(), stderr=io.StringIO())
    assert Path(seen["builder"].__file__) == root / tiny.BUILDER
    assert res["correct"], res["compared"]
    assert compared["tokens"] > 0 and run.flops_window > 0
    del compared["narrow_leaves"]          # set by the runner, not compared
    assert compared == seen["default"]


STUB_BUILDER = '''
import jax.numpy as jnp

def layer_kinds(m):
    return ["dense", "moe", "moe"]

def moe_shape(m):
    return 2, m["experts"]

def layer_weights(key, m, kind):
    if kind == "dense":
        return {"b": 10.0 * jnp.eye(m["d"])[3]}
    return {"r": jnp.zeros(m["experts"])}

def outer_weights(key, m):
    return {"embed": jnp.zeros((m["d"], m["d"]))}
'''

STUB_REFERENCE = '''
import jax.numpy as jnp
import numpy as np

CALLS = []          # (route, table) of every MoE layer's buddy pick

def pick_buddies(route, sub, resid, table):
    CALLS.append((np.array(route), np.array(table)))
    return np.array(route), 0

def layer(p, x, route, final, m, q8=False):
    return x, jnp.zeros((x.shape[0], m["experts"])), route

def route_gap(logits, route):
    return route[:, 0].astype(jnp.float32)   # the route's own expert id

def block(p, x, m, kind, q8=False):
    return x + p["b"]

def head(o, x, m, q8=False):
    return x + 0.01 * jnp.arange(x.shape[-1])
'''


def test_compare_follows_the_layer_kinds(tmp_path):
    """A stack ``["dense", "moe", "moe"]``: MoE layer i reads route i and
    ``table[i]``, the dense layer runs its ``block`` and is not routed, and
    ``route_gap_mean`` is over the 2 MoE layers."""
    (tmp_path / "builder.py").write_text(STUB_BUILDER)
    (tmp_path / "ref.py").write_text(STUB_REFERENCE)
    m = {"d": 8, "experts": 4}
    cfg = {"model": m, "reference": "ref.py", "builder": "builder.py"}
    t_n, k = 4, 2
    idx = np.stack([np.full((t_n, k), 1), np.full((t_n, k), 3)]).astype(np.int32)
    no = np.zeros((2, t_n, k), bool)
    steps = [{"rows": [0], "counts": [t_n], "pos": [0],
              "resid": np.ones((2, m["experts"]), bool),
              "route": (idx, no, no, no, no)}]
    req = types.SimpleNamespace(rid=0, prompt=[1, 2, 3], tokens=[3, 3])
    table = np.arange(2 * m["experts"] * 2).reshape(2, m["experts"], 2)
    builder = load_builder(tmp_path, cfg)
    out = check.compare(cfg, tmp_path, 7, [req], steps, table, builder)
    ref = check._fns(str(tmp_path / "ref.py"), builder,
                     json.dumps(m, sort_keys=True))["ref"]
    assert len(ref.CALLS) == 2                       # the dense layer: none
    for i, (route, tab) in enumerate(ref.CALLS):
        assert (route[:t_n] == idx[i, 0, 0]).all()
        np.testing.assert_array_equal(tab, table[i])
    assert out["positions"] == t_n and out["tokens"] == 2
    assert out["decision_errors"] == 0
    assert out["route_gap"] == 3.0
    assert out["route_gap_mean"] == pytest.approx((1 + 3) / 2)
    # token 3 is the best only where the dense block added its 10
    assert out["token_gap"] == 0.0
