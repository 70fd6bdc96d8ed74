"""A whole run of a tiny cell on the CPU, past the harness's look for a
chip: the program's tokens agree with the reference, and the int8 control
put in its place does not."""
import io
import os
import subprocess
import sys
from pathlib import Path

os.environ.setdefault("JAX_PLATFORMS", "cpu")
sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

import pytest  # noqa: E402

import tiny  # noqa: E402
from harness.runner import run_cell  # noqa: E402

ROOT = Path(__file__).resolve().parents[2]


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return tiny.make_root(tmp_path_factory.mktemp("checkout"))


def test_tiny_cell_correct_and_control_fails(root):
    out, err = io.StringIO(), io.StringIO()
    res, run, c = run_cell(root, "tiny-chat", 2**31 + 5, 2.0, False,
                           require_tpu=False, stdout=out, stderr=err,
                           control=True)
    assert res["correct"], res["compared"]
    assert res["failed"] == 0 and res["attempted"] > 0
    assert set(res["metrics"]) == {"ttft_p90_ms", "tpot_p90_ms", "setup_s"}
    assert list(res)[-1] == "compared"
    tail = err.getvalue().strip().splitlines()[-5:]
    assert all(line.startswith("compared ") for line in tail), tail
    assert tail[-1].startswith("compared substituted")
    assert c["tokens"] >= 20 and c["decision_errors"] == 0
    assert c["substituted"] > 0 and c["narrow_leaves"] == 0
    # the int8 control, held to the cell's own limits, is not correct
    assert res["control_correct"] is False, c


def test_traced_run_reports_per_layer_metrics(root):
    res, _, _ = run_cell(root, "tiny-chat", 11, 1.5, True,
                         require_tpu=False, stdout=io.StringIO(),
                         stderr=io.StringIO())
    assert res["correct"]
    assert "queue_wait_p90_ms.chat" in res["metrics"]
    assert {"busy_s", "window_s"} <= set(res["device"])
    assert set(res["breakdown"]) == {"device_ops", "idle_gaps"}


def test_no_chip_no_result(tmp_path):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run([sys.executable, str(ROOT / "bench/run.py"),
                        "--workload", "dsv2lite-chat-miss50", "--seed", "1",
                        "--seconds", "1", "--trace", "0"], cwd=ROOT, env=env,
                       capture_output=True, text=True, timeout=120)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "TPU" in p.stderr


def test_bare_checkout_no_result(tmp_path):
    """A directory with only BENCHMARK.json and bench/ has no program to
    run: a non-zero exit and no result line."""
    import shutil
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run([sys.executable, "bench/run.py", "--workload",
                        "dsv2lite-chat-miss50", "--seed", "1", "--seconds",
                        "1", "--trace", "0"], cwd=tmp_path, env=env,
                       capture_output=True, text=True, timeout=120)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "no program under test" in p.stderr
