"""The timed path broken underneath, on the tiny cell: each fault this
cell can have makes ``correct`` false (weights stored in bfloat16 where
the configuration states float32 among them). (A serving cell on one chip has no
exchange between chips to leave out.)"""
import io
import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

import pytest  # noqa: E402

import tiny  # noqa: E402
from harness.runner import run_cell  # noqa: E402


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return tiny.make_root(tmp_path_factory.mktemp("checkout"))


@pytest.mark.parametrize("fault", ["state_unchanged", "half_batch",
                                   "token_altered", "narrow_storage"])
def test_fault_makes_correct_false(root, fault):
    res, _, _ = run_cell(root, "tiny-chat", 21, 1.5, False,
                         require_tpu=False, fault=fault,
                         stdout=io.StringIO(), stderr=io.StringIO())
    assert not res["correct"], res["compared"]


def test_no_substitution_makes_correct_false(root):
    """A program that stops substituting (every miss fetched) serves the
    right tokens, and fails the mix's lower limit on substituted slots."""
    res, _, c = run_cell(root, "tiny-chat", 21, 1.5, False,
                         require_tpu=False,
                         traffic_overrides={"policy": "none"},
                         stdout=io.StringIO(), stderr=io.StringIO())
    assert c["substituted"] == 0 and c["decision_errors"] == 0, c
    assert not res["correct"], res["compared"]
