"""The readers of the program's own spans, counters and scopes
(``harness/program_trace.py``, the metrics that read them, and
``tools/trace_split.py``), on hand-made spans and ops and on a tiny cell;
and the readers that were there before, unchanged on the recorded v5e
excerpt."""
import json
import os
import sys
import types
from pathlib import Path

import pytest

os.environ.setdefault("JAX_PLATFORMS", "cpu")
sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

import tiny  # noqa: E402
from harness import program_trace as pt  # noqa: E402
from harness import trace_reduce as tr  # noqa: E402
from harness.spec import load_module  # noqa: E402

BENCH = Path(__file__).resolve().parents[1]
DATA = BENCH / "tests" / "data"


def _spans():
    """Two steps of the serving loop: a chunk step and a decode step, the
    scheduler's work around the first, and a wait between them."""
    acc1 = {"slots_routed": 12, "slots_computed": 384}
    acc2 = {"slots_routed": 4, "slots_computed": 128}
    return [
        ("serve.admit", 0.0, 0.1, 0, {}),
        ("serve.batch", 0.1, 0.15, 0, {}),
        ("serve.step", 0.15, 1.15, 0, {"kind": "chunk"}),
        ("serve.buddy_state", 0.2, 0.3, 1, {}),
        ("serve.launch", 0.3, 0.4, 1, {}),
        ("serve.readback", 0.4, 0.9, 1, {}),
        ("serve.account", 0.9, 1.1, 1, acc1),
        ("serve.batch", 1.15, 1.2, 0, {}),
        ("serve.sample", 1.2, 1.3, 0, {}),
        ("serve.emit", 1.3, 1.35, 0, {}),
        ("serve.feedback", 1.35, 1.4, 0, {}),
        ("serve.wait", 1.4, 2.0, 0, {}),
        ("serve.step", 2.0, 2.5, 0, {"kind": "decode"}),
        ("serve.buddy_state", 2.0, 2.05, 1, {}),
        ("serve.launch", 2.05, 2.1, 1, {}),
        ("serve.readback", 2.1, 2.4, 1, {}),
        ("serve.account", 2.4, 2.5, 1, acc2),
    ]


def _ops():
    return [("%while.1 f32[]", 0.42, 0.88, "jit(prefill_chunk)/while"),
            ("%fusion.1 f32[8]", 0.45, 0.65,
             "jit(prefill_chunk)/while/body/dispatch/dot_general"),
            ("%fusion.2 f32[8]", 0.65, 0.85,
             "jit(prefill_chunk)/while/body/attention/add"),
            ("%gather.3 f32[4]", 1.16, 1.18, "jit(_getitem)/gather"),
            ("%fusion.4 f32[4]", 2.15, 2.35, "jit(decode_step)/lm_head/dot")]


def test_per_step_host_times():
    s = _spans()
    assert pt.per_step_ms(s, ("serve.buddy_state",)) == pytest.approx(75.0)
    assert pt.per_step_ms(s, pt.SCHEDULER_SPANS) == pytest.approx(150.0)
    # the step's own time: 1.0 - 0.9 and 0.5 - 0.5
    assert pt.per_step_ms(s, ("serve.step",), self_time=True) == \
        pytest.approx(50.0)
    assert pt.per_step_ms(s, ("serve.account",), self_time=True) == \
        pytest.approx(150.0)
    assert pt.per_step_ms([], ("serve.account",)) is None
    assert [x[4]["kind"] for x in pt.steps(s, "chunk")] == ["chunk"]


def test_useful_share_sums_the_counters():
    assert pt.useful_share(_spans()) == pytest.approx(100.0 * 16 / 512)
    assert pt.useful_share(_spans()[:6]) is None


def test_idle_by_innermost_span():
    gaps = dict(pt.idle_by_span(_ops(), _spans(), 0.0, 3.0))
    assert gaps == pytest.approx({"serve.buddy_state": 0.42,
                                  "serve.account": 0.28,
                                  "serve.wait": 0.97, "outside": 0.65})
    assert sum(gaps.values()) == pytest.approx(3.0 - 0.68)


def test_device_time_by_scope():
    by = dict(pt.device_by_scope(_ops(), 0.0, 3.0))
    assert by == pytest.approx({"dispatch": 0.2, "attention": 0.2,
                                "lm_head": 0.2, "unscoped": 0.08})
    assert pt.scope_ms_per_step(_ops(), _spans(), "dispatch") == \
        pytest.approx(200.0)
    assert pt.scope_ms_per_step(_ops(), _spans(), "lm_head", "decode") == \
        pytest.approx(200.0)
    assert pt.scope_ms_per_step(_ops(), _spans()[:2], "dispatch") is None


def test_top_scope():
    assert pt.top_scope("jit(f)/while/body/dispatch/dot_general") == \
        "dispatch"
    assert pt.top_scope("jit(f)/while/body/attention/dispatch/x") == \
        "attention"
    assert pt.top_scope("jit(f)/while/body/add") == "unscoped"
    assert pt.top_scope("") == "unscoped"


def test_scope_paths_from_the_traces_hlo(tmp_path):
    """A profiled program's ops find their scopes in the HLO protos the
    trace keeps; a fusion without its own takes its fused ops'."""
    import glob

    import jax
    import jax.numpy as jnp

    def decode_step(x):
        with jax.named_scope("attention"):
            y = jnp.sin(x) @ x
        with jax.named_scope("dispatch"):
            return jnp.cos(y).sum()

    f = jax.jit(decode_step)
    x = jnp.ones((64, 64))
    f(x).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    f(x).block_until_ready()
    jax.profiler.stop_trace()
    path = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    names = pt.hlo_op_names(path[0])
    prog = [n for n in names if n.startswith("jit_decode_step(")]
    assert len(prog) == 1
    ops = names[prog[0]]
    scopes = {pt.top_scope(p) for p in ops.values()}
    assert {"attention", "dispatch"} <= scopes
    assert pt.top_scope(ops["dot_general.1"]) == "attention"
    fusions = [n for n in ops if n.startswith("wrapped_cos")]
    assert fusions and all(pt.top_scope(ops[n]) == "dispatch"
                           for n in fusions)


def test_protobuf_fields():
    # field 1 varint 150, field 2 bytes "ab", field 3 packed [1, 300]
    msg = bytes([0x08, 0x96, 0x01, 0x12, 0x02, 0x61, 0x62,
                 0x1A, 0x03, 0x01, 0xAC, 0x02])
    f = list(pt._fields(memoryview(msg)))
    assert [n for n, _ in f] == [1, 2, 3]
    assert f[0][1] == 150 and bytes(f[1][1]) == b"ab"
    assert pt._ints(f[2][1]) == [1, 300]


def test_depth_of_nested_spans():
    evs = [("serve.sample", 1.0, 1.1, {}), ("serve.step", 0.0, 1.0, {}),
           ("serve.launch", 0.1, 0.2, {})]
    assert [(n, d) for n, _, _, d, _ in pt._with_depth(evs)] == \
        [("serve.step", 0), ("serve.launch", 1), ("serve.sample", 0)]


def test_readers_read_the_programs_log(monkeypatch):
    from repro.runtime import trace as rtrace
    monkeypatch.setattr(rtrace, "HOST_SPAN_LOG", list(_spans()))
    run = types.SimpleNamespace(trace=object(), w0=0.0, w1=3.0)
    read = {m: load_module(BENCH / "metrics" / f"{m}.py", m).read
            for m in ("buddy_state_ms", "account_ms", "scheduler_host_ms",
                      "dispatch_useful_share")}
    assert read["buddy_state_ms"](run) == pytest.approx(75.0)
    assert read["account_ms"](run) == pytest.approx(150.0)
    assert read["scheduler_host_ms"](run) == pytest.approx(150.0)
    assert read["dispatch_useful_share"](run) == pytest.approx(3.125)
    # spans that end past the window are not the window's
    run.w1 = 2.45
    assert read["buddy_state_ms"](run) == pytest.approx(100.0)
    # an untraced run, and a program that keeps no log, read nothing
    assert read["account_ms"](types.SimpleNamespace(trace=None)) is None
    monkeypatch.setitem(sys.modules, "repro.runtime.trace",
                        types.ModuleType("repro.runtime.trace"))
    assert pt.log_spans(0.0, 3.0) == []
    assert all(r(run) is None for r in read.values())


def test_existing_readers_unchanged_on_the_recorded_excerpt():
    """The numbers the readers gave on the excerpt before the program's
    spans existed (``bench/tools/probe.py --trace 1 --excerpt``)."""
    with open(DATA / "v5e_batch_excerpt.json") as f:
        t = tr.Trace.from_json(json.load(f))
    assert t.window == pytest.approx((10.04279653499998, 10.54279653499998))
    assert tr.busy_s(t) == pytest.approx(0.3566803480000207, rel=1e-12)
    assert tr.top_ops(t, 3) == [
        ["%fusion.31 bf16[64,8,32,14336]", pytest.approx(0.12354061100000457)],
        ["%fusion.25 f32[64,8,32,14336]", pytest.approx(0.09519973199999754)],
        ["%fusion.36 f32[64,8,32,4096]", pytest.approx(0.07603487099999562)]]
    assert tr.idle_gaps(t) == [
        ["engine.prefill_rows", pytest.approx(0.06557829599999643)],
        ["other", pytest.approx(0.06023737099997817)],
        ["admit", pytest.approx(0.015231748000003265)],
        ["sample", pytest.approx(0.0022350339999999136)],
        ["between_ops", pytest.approx(3.7203000001539976e-05)]]
    assert tr.span_device_ms(t, "engine.step") is None
    assert tr.span_device_ms(t, "engine.prefill_rows") == \
        pytest.approx(44.08131700000251, rel=1e-12)
    assert tr.host_ms_per_step(t) == pytest.approx(15.43139012499739,
                                                   rel=1e-12)


@pytest.fixture(scope="module")
def split_line(tmp_path_factory):
    """One traced run of the tiny cell through ``tools/trace_split.py``."""
    root = tiny.make_root(tmp_path_factory.mktemp("checkout"))
    out = root / "split.json"
    cwd = os.getcwd()
    os.chdir(root)
    try:
        split = load_module(BENCH / "tools" / "trace_split.py", "split")
        assert split.main(["--workload", "tiny-chat", "--seed",
                           str(2**31 + 77), "--seconds", "1.5",
                           "--require-tpu", "0", "--out", str(out)]) == 0
    finally:
        os.chdir(cwd)
    return json.loads(out.read_text())


def test_tiny_traced_run_reports_the_program_metrics(split_line):
    m = split_line["metrics"]
    assert split_line["correct"]
    for stem in ("buddy_state_ms", "account_ms", "scheduler_host_ms"):
        assert m[f"{stem}.chat"] > 0, stem
    # every tiny step takes the dropless grouped matmul (T·K >= E: 4 slots
    # x top-2 against 8 experts), which computes only the routed slots
    assert m["dispatch_useful_share.chat"] == pytest.approx(100.0, abs=1e-6)
    assert {"ttft_p90_ms", "tpot_p90_ms", "setup_s"} <= set(m)


def test_split_reads_the_same_spans_from_the_trace(split_line):
    s = split_line
    host = s["host_ms_per_step"]
    assert s["steps"] > 0 and s["chunk_steps"] > 0
    from repro.runtime.trace import SERVE_SPANS
    assert set(host) == set(pt.SPANS) == set(SERVE_SPANS)
    assert all(v is not None for v in host.values())
    # the trace's spans and the program's log time the same work
    assert host["serve.buddy_state"] == pytest.approx(
        s["metrics"]["buddy_state_ms.chat"], rel=0.25)
    assert s["dispatch_useful_share"] == pytest.approx(
        s["metrics"]["dispatch_useful_share.chat"], rel=0.2)
    assert 0.5 < s["serve_step_over_engine_span"] <= 1.0
    assert 0 < s["gather_ms_per_chunk_step"] * s["chunk_steps"] <= \
        host["serve.batch"] * s["steps"]
    idle = dict(s["idle_by_span"])
    assert sum(idle.values()) == pytest.approx(s["idle_s"])
