"""The reduction from a profiler trace to numbers, on hand-made events and
on an excerpt of a trace recorded on a TPU v5e."""
import json
import os
import sys

import pytest

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

from harness import trace_reduce as tr  # noqa: E402

DATA = os.path.join(os.path.dirname(__file__), "data")


def _hand():
    # two decode programs with nested ops; host spans around them
    ops = [("%while.1 f32[]", 1.0, 1.4), ("%fusion.2 f32[8]", 1.1, 1.2),
           ("%fusion.3 f32[8]", 1.25, 1.35), ("%while.1 f32[]", 2.0, 2.4),
           ("%fusion.2 f32[8]", 2.1, 2.3)]
    modules = [("jit_decode_step(1)", 1.0, 1.4), ("jit_decode_step(1)", 2.0, 2.4)]
    host = [("admit", 0.5, 0.6), ("engine.step", 0.9, 1.4),
            ("sample", 1.4, 1.5), ("engine.prefill_rows", 1.5, 2.5),
            ("sample", 2.5, 2.6)]
    return tr.Trace(ops, modules, host, (0.5, 3.0))


def test_busy_is_the_union():
    assert tr.busy_s(_hand()) == pytest.approx(0.8)


def test_self_times_subtract_nested_ops():
    st = tr.self_times(_hand().ops)
    assert st["%while.1 f32[]"] == pytest.approx(0.8 - 0.2 - 0.2)
    assert st["%fusion.2 f32[8]"] == pytest.approx(0.3)
    top = tr.top_ops(_hand())
    assert top[0][0] == "%while.1 f32[]"


def test_idle_gaps_by_host_span():
    gaps = dict(tr.idle_gaps(_hand()))
    assert gaps["engine.prefill_rows"] == pytest.approx(0.6)   # 1.4-2.0
    assert gaps["other"] == pytest.approx(0.5 + 0.6)            # 0.5-1, 2.4-3
    assert sum(gaps.values()) == pytest.approx(2.5 - 0.8)


def test_module_and_host_time_per_step():
    t = _hand()
    assert tr.span_device_ms(t, "engine.step") == pytest.approx(400.0)
    assert tr.span_device_ms(t, "engine.prefill_rows") == \
        pytest.approx(400.0)
    assert tr.span_device_ms(t, "admit") == pytest.approx(0.0)
    # step 1: 0.9-1.5 less 0.4 busy; step 2: 1.5-2.6 less 0.4 busy
    assert tr.host_ms_per_step(t) == pytest.approx(450.0)


def test_align_maps_the_wall_window():
    t = _hand()
    wall = [(n, a - 100.0, b - 100.0) for n, a, b in t.host]
    tr.align(t, wall, -99.0, -98.0)
    assert t.window == pytest.approx((1.0, 2.0))


def test_op_names():
    assert tr.op_name("%fusion.575 = bf16[8,64,192,1408]{3,2,1,0} fusion(")\
        == "%fusion.575 bf16[8,64,192,1408]"


def test_recorded_v5e_excerpt():
    """Half a second of a traced `mixtral-batch-miss50` window, recorded on
    a TPU v5e (``bench/tools/probe.py --trace 1 --excerpt``)."""
    path = os.path.join(DATA, "v5e_batch_excerpt.json")
    with open(path) as f:
        t = tr.Trace.from_json(json.load(f))
    lo, hi = t.window
    busy = tr.busy_s(t)
    assert 0 < busy <= hi - lo
    gaps = tr.idle_gaps(t)
    assert sum(s for _, s in gaps) == pytest.approx(hi - lo - busy)
    assert any(n in ("engine.step", "engine.prefill_rows") for n, _ in gaps)
    assert tr.span_device_ms(t, "engine.step") or \
        tr.span_device_ms(t, "engine.prefill_rows")
    assert tr.host_ms_per_step(t) > 0
