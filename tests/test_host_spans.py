"""Host spans on the profiler's clock, the counters of ``serve.account``,
and the named scopes and names of the step programs (runtime/trace.py
``host_span``, serving/engine.py, serving/scheduler.py, models/)."""
import glob
import os

import jax
import numpy as np
import pytest
from jax.profiler import ProfileData

from repro.configs.deepseek_v2_lite_buddy import reduced
from repro.core import BuddyPolicy, build_buddy_lists
from repro.models import moe as moe_mod
from repro.models import transformer
from repro.runtime import trace as rtrace
from repro.runtime.cache import ExpertCache
from repro.runtime.prefetch import PrevStepPredictor
from repro.serving.engine import ServeEngine
from repro.serving.scheduler import ContinuousScheduler, RequestQueue, \
    ServeRequest

STEP_CHILDREN = ("serve.buddy_state", "serve.launch", "serve.readback",
                 "serve.account")
SCOPES = ("embed", "attention", "router", "substitute", "dispatch",
          "shared_experts", "lm_head")


class RecordingEngine(ServeEngine):
    """Keeps each step's routing record, as read back by ``_account``."""

    def _account(self, aux, active):
        super()._account(aux, active)
        self.records.append((aux["recorded"], np.asarray(active, bool)))


@pytest.fixture(scope="module")
def setup():
    cfg = reduced()
    params = transformer.init_params(cfg, jax.random.PRNGKey(0))
    e = cfg.moe.num_experts
    q = np.random.default_rng(0).random((cfg.num_layers, e, e))
    tables = build_buddy_lists(q, alpha=0.95, k_max=e - 1)
    return cfg, params, tables


def _engine(setup):
    cfg, params, tables = setup
    n_l, e = cfg.num_layers, cfg.moe.num_experts
    eng = RecordingEngine(cfg, params, tables=tables,
                          policy=BuddyPolicy(tau=0.1, beta=0.9, rho=3),
                          cache=ExpertCache(n_l, e, 0.5, seed=0),
                          predictor=PrevStepPredictor(n_l, e), prefetch_k=1)
    eng.records = []
    return eng


def _serve(setup, chunk, slots=3):
    """Six requests through the continuous loop; the first arrives after
    the loop starts, so it waits once."""
    eng = _engine(setup)
    rng = np.random.default_rng(1)
    reqs = [ServeRequest(rid=i, prompt=rng.integers(0, 512, 3 + 2 * i),
                         max_new_tokens=3, arrival_s=0.01 + 0.02 * i)
            for i in range(6)]
    sched = ContinuousScheduler(eng, slots, prefill_chunk=chunk)
    queue = RequestQueue(reqs)
    summary = sched.run(queue)
    return eng, sched, reqs, summary


def _profiled(setup, chunk, tmp_path, slots=3):
    rtrace.HOST_SPAN_LOG.clear()
    jax.profiler.start_trace(str(tmp_path))
    try:
        out = _serve(setup, chunk, slots)
    finally:
        jax.profiler.stop_trace()
    return out, list(rtrace.HOST_SPAN_LOG)


def _children(log, step):
    _, a, b, depth, _ = step
    return [e for e in log if e[3] == depth + 1 and a <= e[1] and e[2] <= b]


@pytest.mark.parametrize("chunk", [1, 4])
def test_profiled_run_names_every_span_nested(setup, chunk, tmp_path):
    (eng, sched, _, _), log = _profiled(setup, chunk, tmp_path)
    assert {e[0] for e in log} == set(rtrace.SERVE_SPANS)
    steps = [e for e in log if e[0] == "serve.step"]
    assert len(steps) == sched.steps == eng.stats.steps
    for st in steps:
        assert st[3] == 0
        assert [c[0] for c in _children(log, st)] == list(STEP_CHILDREN)
        assert st[4]["kind"] in ("decode", "chunk")
        assert st[4]["tokens"] >= st[4]["rows"] >= 1
    for name in ("serve.sample", "serve.admit", "serve.batch", "serve.emit",
                 "serve.feedback", "serve.wait"):
        assert all(e[3] == 0 for e in log if e[0] == name), name
    if chunk > 1:
        kinds = {st[4]["kind"] for st in steps}
        assert "chunk" in kinds
    # the same spans, with their stats, in the profiler's trace
    path = glob.glob(os.path.join(tmp_path, "**", "*.xplane.pb"),
                     recursive=True)
    events = [e for p in ProfileData.from_file(path[0]).planes
              if p.name.startswith("/host:") for ln in p.lines
              for e in ln.events if e.name.startswith("serve.")]
    assert sorted(e.name for e in events) == sorted(e[0] for e in log)
    acc = [dict(e.stats) for e in events if e.name == "serve.account"]
    assert all(set(rtrace.STEP_COUNTERS) <= set(s) for s in acc)


@pytest.mark.parametrize("chunk,slots", [(4, 3), (1, 1)])
def test_account_counters_match_stats_and_masks(setup, chunk, slots,
                                                tmp_path):
    (eng, _, _, _), log = _profiled(setup, chunk, tmp_path, slots)
    acc = [e[4] for e in log if e[0] == "serve.account"]
    total = {k: sum(a[k] for a in acc) for k in rtrace.STEP_COUNTERS}
    assert total["hits"] == eng.stats.n_hit
    assert total["substituted"] == eng.stats.n_sub > 0
    # the bench reads the same host copies after ``_account``
    assert len(eng.records) == len(acc)
    masks = {"substituted": 0, "missed": 0, "degraded": 0, "dropped": 0}
    routed = slots_routed = 0
    k, n_l = eng.cfg.moe.top_k, eng.num_moe_layers
    for recorded, active in eng.records:
        for rec in recorded:
            assert all(isinstance(v, np.ndarray) for v in rec.values())
            for m in masks:
                masks[m] += int(rec[m][:, active].sum())
            routed += sum(len(np.unique(li))
                          for li in rec["indices"][:, active])
        slots_routed += int(active.sum()) * k * n_l
    assert total["substituted"] == masks["substituted"]
    assert total["fetched"] == masks["missed"]
    assert total["degraded"] == masks["degraded"]
    assert total["dropped"] == masks["dropped"]
    assert total["routed"] == routed
    assert total["slots_routed"] == slots_routed
    assert total["hits"] <= total["routed"]
    for a in acc:
        assert a["slots_routed"] <= a["slots_computed"]


def test_slots_computed_follows_the_dispatch_path():
    pol = BuddyPolicy()
    # dropless chunk: the grouped matmul over the live tokens' slots
    plan = moe_mod.dispatch_plan(pol, (16, 8), 64, 6, dropless=True)
    assert plan == ("ragged", 0)
    assert moe_mod.dispatch_slots(plan, (16, 8), 64, 6) == 16 * 8 * 6
    assert moe_mod.dispatch_slots(plan, (16, 8), 64, 6, live_tokens=30) \
        == 30 * 6
    # capacity path, where a slot can be dropped: every expert's capacity
    # on every row
    plan = moe_mod.dispatch_plan(pol, (16, 128), 64, 6)
    assert plan == ("capacity", 16)
    assert moe_mod.dispatch_slots(plan, (16, 128), 64, 6) == 16 * 64 * 16
    # decode at 16 rows: capacity K = S*K, so nothing can drop
    plan = moe_mod.dispatch_plan(pol, (16, 1), 64, 6, capacity_factor=2.0)
    assert plan == ("ragged", 0)
    assert moe_mod.dispatch_slots(plan, (16, 1), 64, 6, live_tokens=9) \
        == 9 * 6
    # one row of top-2 over 8 experts: the active-expert gather
    plan = moe_mod.dispatch_plan(pol, (1, 1), 8, 2, capacity_factor=2.0)
    assert plan == ("gather", 0)
    assert moe_mod.dispatch_slots(plan, (1, 1), 8, 2) == 2
    fused = BuddyPolicy(use_fused_dispatch=True)
    plan = moe_mod.dispatch_plan(fused, (4, 8), 64, 6, dropless=True)
    assert plan == ("fused", 4 * 8 * 6)
    assert moe_mod.dispatch_slots(plan, (4, 8), 64, 6) == 4 * 8 * 6
    assert moe_mod.dispatch_slots(plan, (4, 8), 64, 6, use_kernel=True) \
        == 2 * 64 * 4 * 8 * 6
    cfg = reduced()
    n_moe, k = cfg.num_layers, cfg.moe.top_k
    # chunk and decode steps compute their live tokens' slots
    assert transformer.moe_slots_computed(cfg, pol, "chunk", (3, 4),
                                          n_live=5) == n_moe * 5 * k
    assert transformer.moe_slots_computed(cfg, pol, "step", (3, 1),
                                          n_live=2) == n_moe * 2 * k
    # one decode row: the gather path computes its slots whatever is live
    assert transformer.moe_slots_computed(cfg, pol, "step", (1, 1),
                                          n_live=0) == n_moe * 1 * k


def test_step_programs_named_and_scoped(setup):
    cfg, params, _ = setup
    eng = _engine(setup)
    b, c = 2, 4
    caches = eng.init_caches(b, 16)
    buddies = eng._buddy_state()
    key = jax.random.PRNGKey(0)
    texts = {
        "decode_step": eng._step_fn.lower(
            params=eng.params, token=np.zeros(b, np.int32), caches=caches,
            pos=np.zeros(b, np.int32), buddies=buddies, rng=key),
        "prefill_chunk": eng._chunk_fn.lower(
            params=eng.params, tokens=np.zeros((b, c), np.int32),
            caches=caches, base_pos=np.zeros(b, np.int32),
            tok_valid=np.ones((b, c), bool), buddies=buddies, rng=key),
    }
    for name, lowered in texts.items():
        hlo = lowered.compile().as_text()
        assert f"HloModule jit_{name}" in hlo
        for scope in SCOPES:
            assert f'op_name="jit({name})/' in hlo
            assert f"/{scope}/" in hlo, (name, scope)


def test_outputs_and_summary_identical_profiler_on_off(setup, tmp_path):
    _, _, reqs_off, sum_off = _serve(setup, 4)
    (_, _, reqs_on, sum_on), log = _profiled(setup, 4, tmp_path)
    assert log
    assert [r.tokens for r in reqs_on] == [r.tokens for r in reqs_off]
    assert sum_on == sum_off


def test_host_span_off_records_nothing():
    rtrace.HOST_SPAN_LOG.clear()
    with rtrace.host_span("serve.step", kind="decode") as span:
        assert not span.on
    assert not rtrace.HOST_SPAN_LOG
