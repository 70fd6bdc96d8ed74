"""Percentiles, the window's edges, the latency readers and the FLOP
count, on hand-made numbers."""
import os
import sys

import pytest

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

from harness import readers  # noqa: E402
from harness.flops import chunk_flops, token_flops  # noqa: E402
from harness.runner import Run  # noqa: E402
from harness.stats import in_window, percentile  # noqa: E402


class Req:
    def __init__(self, rid, arrival, stamps, finished=True):
        self.rid, self.arrival_s = rid, arrival
        self.token_times = list(stamps)
        self.tokens = [0] * len(stamps)
        self.first_token_s = stamps[0] if stamps else -1.0
        self.finished_s = stamps[-1] if finished and stamps else -1.0

    def tpot(self):
        t = self.token_times
        return (t[-1] - t[0]) / (len(t) - 1)


def _run(reqs, window_rids, emitted=(), admitted=None):
    return Run(requests=reqs, window_rids=set(window_rids),
               admitted=admitted or {}, emitted=list(emitted), w0=10.0,
               w1=20.0, end=30.0, setup_s=5.0, flops_window=0.0)


def test_percentile_linear():
    assert percentile([1, 2, 3, 4, 5, 6, 7, 8, 9, 10], 90) == \
        pytest.approx(9.1)
    assert percentile([], 90) is None


def test_window_edges():
    assert in_window(10.0, 10.0, 20.0)
    assert not in_window(20.0, 10.0, 20.0)
    assert not in_window(9.999, 10.0, 20.0)


def test_ttft_tpot_over_window_requests():
    reqs = [Req(0, 9.0, [9.5, 9.6]),                     # due before window
            Req(1, 10.0, [10.5, 10.6, 10.7]),            # ttft 0.5, tpot .1
            Req(2, 11.0, [12.0, 12.4, 12.8]),            # ttft 1.0, tpot .4
            Req(3, 19.9, [], finished=False)]            # never served
    run = _run(reqs, [1, 2, 3])
    # request 3 counts with its wait until the run's end (30 - 19.9)
    assert readers.ttft_p90_ms(run) == \
        pytest.approx(1e3 * percentile([0.5, 1.0, 10.1], 90))
    assert readers.tpot_p90_ms(run) == \
        pytest.approx(1e3 * percentile([0.1, 0.4], 90))


def test_tokens_per_s_counts_window_stamps_only():
    run = _run([], [], emitted=[9.9, 10.0, 15.0, 19.99, 20.0, 25.0])
    assert readers.output_tokens_per_s(run) == pytest.approx(3 / 10.0)


def test_queue_wait():
    reqs = [Req(1, 10.0, [11.0]), Req(2, 12.0, [13.0])]
    run = _run(reqs, [1, 2], admitted={1: 10.2, 2: 12.6})
    assert readers.queue_wait_p90_ms(run) == \
        pytest.approx(1e3 * percentile([0.2, 0.6], 90))


M = {"num_layers": 2, "d_model": 8, "num_heads": 2, "num_kv_heads": 1,
     "head_dim": 4, "vocab_size": 10, "sliding_window": 0,
     "moe": {"num_experts": 4, "top_k": 2, "d_ff": 6,
             "num_shared_experts": 1}}


def test_token_flops_hand_count():
    # per layer: q,o 2*8*8 each; k,v 2*8*4 each; scores+mix 4*2*4*ctx;
    # router 2*8*4; (2 kept + 1 shared) x 3 matmuls x 2*8*6; head 2*8*10
    per_layer = 128 + 128 + 64 + 64 + 32 * 3 + 64 + 3 * 3 * 96
    assert token_flops(M, 3) == 2 * per_layer + 160


def test_chunk_flops_sums_contexts():
    assert chunk_flops(M, 5, 3) == sum(token_flops(M, c) for c in (6, 7, 8))
    w = dict(M, sliding_window=6)
    assert chunk_flops(w, 5, 3) == 3 * token_flops(M, 6)
