"""BENCHMARK.json against the files it names and the rules it keeps, and
a new cell added as new files and entries only."""
import json
import os
import re
import sys
from pathlib import Path

import pytest

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

from harness.spec import Spec  # noqa: E402

ROOT = Path(__file__).resolve().parents[2]
SPEC = json.loads((ROOT / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")


def test_keys_and_names():
    assert set(SPEC) == {"command", "paths", "run_seconds", "configs",
                         "workloads", "end_to_end", "per_layer"}
    names = [x["name"] for k in ("configs", "workloads", "end_to_end",
                                 "per_layer") for x in SPEC[k]]
    assert len(names) == len(set(names))
    for n in names:
        assert NAME.match(n), n
    assert any(m["name"] == "setup_s" for m in SPEC["end_to_end"])
    for m in SPEC["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")


def test_every_named_file_exists():
    spec = Spec(ROOT)
    for c in SPEC["configs"]:
        cfg = json.loads((ROOT / c["file"]).read_text())
        assert (ROOT / cfg["reference"]).is_file()
        assert c["file"].startswith(tuple(p + "/" for p in SPEC["paths"]))
    for w in SPEC["workloads"]:
        assert spec.traffic_path(w["traffic"]).is_file(), w["traffic"]
        assert w["config"] in spec.configs
    for m in SPEC["end_to_end"] + SPEC["per_layer"]:
        assert spec.metric_path(m["name"]).is_file(), m["name"]
        assert callable(spec.reader(m["name"]))


def test_metric_workloads_report_what_they_move():
    spec = Spec(ROOT)
    e2e = {m["name"]: m for m in SPEC["end_to_end"]}
    for m in SPEC["per_layer"]:
        assert m["moves"] in e2e
        for w in m["workloads"]:
            cell = spec.workload(w)
            assert m["moves"] in spec.cell_metrics(cell, "end_to_end"), \
                (m["name"], w)
    for w in SPEC["workloads"]:
        got = spec.cell_metrics(w, "end_to_end")
        assert "setup_s" in got and len(got) >= 2
        assert spec.cell_metrics(w, "per_layer")


def test_reduced_keys_differ_only_as_listed():
    """Each configuration's file keeps every key it does not list in
    ``reduced``, and names no width there."""
    for c in SPEC["configs"]:
        cfg = json.loads((ROOT / c["file"]).read_text())
        for k in c["reduced"]:
            assert k in cfg and k in cfg["reduced_why"]
            assert not k.endswith(("_dim", "_rank", "_size"))
            assert k != "num_experts_per_tok"


def test_limits_set():
    for c in SPEC["configs"]:
        cfg = json.loads((ROOT / c["file"]).read_text())
        assert all(v is not None for v in cfg["limits"].values()), c["name"]


def test_a_new_cell_is_files_and_entries(tmp_path):
    """The tiny cell of ``tiny.py`` adds a configuration, a traffic mix and
    entries, edits no file, and is found with its metrics."""
    sys.path.insert(0, os.path.dirname(__file__))
    import tiny
    before = {p: p.read_bytes() for p in (ROOT / "bench").rglob("*")
              if p.is_file() and "__pycache__" not in p.parts}
    root = tiny.make_root(tmp_path)
    # a dummy per-layer metric, as a later PR would add it
    (root / "bench/metrics/dummy_ms.chat.py").write_text(
        "def read(run):\n    return 1.5\n")
    spec = json.loads((root / "BENCHMARK.json").read_text())
    spec["per_layer"].append({"name": "dummy_ms.chat", "unit": "ms",
                              "better": "lower", "source": "program_span",
                              "layer": "scheduler", "moves": "ttft_p90_ms",
                              "workloads": ["tiny-chat"]})
    (root / "BENCHMARK.json").write_text(json.dumps(spec))
    s = Spec(root)
    cell = s.workload("tiny-chat")
    assert s.config(cell)["name"] == "tiny"
    assert s.traffic(cell)["arrivals"] == "poisson"
    assert "dummy_ms.chat" in s.cell_metrics(cell, "per_layer")
    assert s.reader("dummy_ms.chat")(None) == 1.5
    for p, b in before.items():
        q = root / p.relative_to(ROOT)
        if "tests" not in p.relative_to(ROOT / "bench").parts:
            assert q.read_bytes() == b, f"{p} changed"


def test_unknown_workload_is_an_error():
    with pytest.raises(KeyError):
        Spec(ROOT).workload("no-such-cell")
