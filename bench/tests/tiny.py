"""A checkout-shaped directory with one tiny cell, for tests on the CPU.

It copies ``bench/`` and adds a configuration, a traffic mix and a cell
as new files and entries only: the same way a later cell is added."""
import json
import shutil
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]
ROOT = BENCH.parent

TINY_MODEL = {
    "num_layers": 2, "d_model": 64, "num_heads": 4, "num_kv_heads": 2,
    "head_dim": 16, "d_ff": 128, "vocab_size": 256, "rope_theta": 10000.0,
    "norm_eps": 1e-5, "sliding_window": 0, "dtype": "float32",
    "moe": {"num_experts": 8, "top_k": 2, "d_ff": 32,
            "num_shared_experts": 1}}

TINY_TRAFFIC = {
    "why": "tiny open-loop mix for tests on the CPU",
    "arrivals": "poisson", "rate_rps": 6.0, "warmup_s": 1.0,
    "drain_limit_s": 60.0,
    "prompt_len": {"dist": "lognormal", "median": 12, "sigma": 0.5,
                   "min": 4, "max": 40},
    "output_len": {"dist": "uniform", "min": 3, "max": 10},
    "shape_seed": 0, "order": "fixed", "slots": 4, "prefill_chunk": 8,
    "cache_rate": 0.5, "policy": "buddy", "check_tokens": 40,
    "at_least": {"substituted": 1}}


def make_root(tmp: Path, limits=None, traffic=None) -> Path:
    """``tmp`` becomes a checkout: bench/ copied, the tiny cell added."""
    root = Path(tmp)
    (root / "src").symlink_to(ROOT / "src")
    shutil.copytree(BENCH, root / "bench",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    cfg = {"name": "tiny", "source": "bench/tests/tiny.py",
           "model": TINY_MODEL,
           "buddy": {"list_len": 4,
                     "policy": {"tau": 0.2, "beta": 0.8, "rho": 3}},
           "reference": "bench/reference/moe_decoder.py",
           "limits": limits or {"token_gap": 1e-3, "route_gap": 1e-3,
                                "decision_errors": 0, "narrow_leaves": 0}}
    (root / "bench/configs/tiny.json").write_text(json.dumps(cfg))
    (root / "bench/traffic/tiny-chat.json").write_text(
        json.dumps(traffic or TINY_TRAFFIC))
    spec["configs"].append({"name": "tiny", "source": "bench/tests/tiny.py",
                            "file": "bench/configs/tiny.json",
                            "reduced": [], "why": "tests"})
    spec["workloads"].append({"name": "tiny-chat", "config": "tiny",
                              "traffic": "tiny-chat", "chips": 1,
                              "why": "tests"})
    for m in spec["end_to_end"] + spec["per_layer"]:
        if "workloads" in m and "dsv2lite-chat-miss50" in m["workloads"]:
            m["workloads"].append("tiny-chat")
    (root / "BENCHMARK.json").write_text(json.dumps(spec))
    return root
