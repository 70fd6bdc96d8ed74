"""chip_smoke.py's phases at the reduced config on the CPU, so the chip's
smoke test cannot rot between chip runs; and its refusal to run off-chip."""
import importlib.util
import os

import pytest

from repro.configs.base import get_reduced

ROOT = os.path.join(os.path.dirname(__file__), "..")


@pytest.fixture(scope="module")
def smoke():
    spec = importlib.util.spec_from_file_location(
        "chip_smoke", os.path.join(ROOT, "chip_smoke.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_refuses_a_cpu(smoke):
    with pytest.raises(smoke.SmokeFailure, match="no TPU"):
        smoke.check_device()


def test_kernels_match_oracles(smoke):
    errs = smoke.run_kernels(get_reduced("deepseek-v2-lite-buddy"),
                             interpret=True)
    assert set(errs) == {"buddy_substitute", "topk_gate", "expert_ffn",
                         "quant_ffn", "grouped_ffn"}


def test_serve_time_and_check(smoke, capsys):
    cfg = get_reduced("deepseek-v2-lite-buddy")
    eng, served = smoke.run_serve(cfg)
    assert served["completed"] == smoke.REQUESTS
    assert served["substitutions"] > 0
    timing = smoke.time_steps(eng, cfg, context=16, steps=2)
    assert timing["compile_s"] > 0 and timing["step_s_median"] > 0
    assert smoke.check_logits(eng, cfg) <= smoke.LOGITS_TOL
    out = capsys.readouterr().out
    assert "sim_tokens_per_s" in out and '"ok"' not in out
