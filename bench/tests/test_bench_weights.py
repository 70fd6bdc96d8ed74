"""The benchmark's weights and buddy tables: one jitted build for the
program, the same bits again one layer at a time for the reference."""
import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

import jax  # noqa: E402
import numpy as np  # noqa: E402

from harness.model import (buddy_tables, build_params, layer_key,  # noqa: E402
                           layer_weights, moe_shape, outer_key, outer_weights)
from tiny import TINY_MODEL as M  # noqa: E402


def test_layer_by_layer_equals_the_stacked_build():
    seed = 2**31 + 77
    full = build_params(seed, M)
    for l in range(M["num_layers"]):
        one = jax.jit(lambda k: layer_weights(k, M))(layer_key(seed, l))
        got = jax.tree.map(lambda a: a[l], full["groups"][0])
        for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(one)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    o = jax.jit(lambda k: outer_weights(k, M))(outer_key(seed))
    for k in ("embed", "final_norm", "lm_head"):
        np.testing.assert_array_equal(np.asarray(full[k]), np.asarray(o[k]))


def test_seeds_give_other_weights():
    a = build_params(1, M)["lm_head"]
    b = build_params(2, M)["lm_head"]
    assert not np.array_equal(np.asarray(a), np.asarray(b))


def test_buddy_tables_are_ranked_lists_of_other_experts():
    table, q = buddy_tables(5, moe_shape(M), 4)
    l_n, e_n = M["num_layers"], M["moe"]["num_experts"]
    assert table.shape == (l_n, e_n, 4)
    for l in range(l_n):
        for e in range(e_n):
            row = table[l, e]
            assert len(set(row)) == 4 and e not in row
            assert (row >= 0).all() and (row < e_n).all()
            assert (np.diff(q[l, e]) < 0).all()
