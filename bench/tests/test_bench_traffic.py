"""The traffic generator: the seed fixes the requests, and every seed gets
the same work in the window."""
import json
import os
import sys

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

from harness.traffic import make_items  # noqa: E402

TRAFFIC = os.path.join(os.path.dirname(__file__), "..", "traffic")


def _load(name):
    with open(os.path.join(TRAFFIC, name + ".json")) as f:
        return json.load(f)


def _window(items):
    return sorted((len(i.prompt), i.max_new) for i in items if i.in_window)


def test_same_seed_same_requests():
    t = _load("chat-miss50")
    a = make_items(t, 2147483701, 40, 1000)
    b = make_items(t, 2147483701, 40, 1000)
    assert [(i.offset_s, i.max_new, i.in_window) for i in a] == \
        [(i.offset_s, i.max_new, i.in_window) for i in b]
    assert all(np.array_equal(x.prompt, y.prompt) for x, y in zip(a, b))


def test_seeds_share_the_window_work():
    """In a mix of fixed order only the tokens depend on the seed: every
    seed sends the same sizes at the same times."""
    t = _load("chat-miss50")
    a = make_items(t, 1, 40, 1000)
    b = make_items(t, 9_000_000_001, 40, 1000)
    assert [(i.offset_s, len(i.prompt), i.max_new, i.in_window) for i in a] \
        == [(i.offset_s, len(i.prompt), i.max_new, i.in_window) for i in b]
    assert sum(i.in_window for i in a) == round(t["rate_rps"] * 40)
    assert not np.array_equal(a[0].prompt, b[0].prompt)


def test_seed_order_deals_the_same_sizes():
    """``"order": "seed"`` deals one multiset of sizes in each seed's
    order."""
    t = _load("batch-miss50")
    assert t["order"] == "seed"
    a = make_items(t, 1, 40, 500)
    b = make_items(t, 9_000_000_001, 40, 500)
    assert _window(a) == _window(b)
    assert [i.max_new for i in a] != [i.max_new for i in b]


def test_window_arrivals_inside_window():
    t = _load("chat-miss50")
    items = make_items(t, 5, 40, 1000)
    w0, w1 = t["warmup_s"], t["warmup_s"] + 40
    for i in items:
        assert (w0 <= i.offset_s < w1) == i.in_window
    offs = [i.offset_s for i in items]
    assert offs == sorted(offs)


def test_lengths_within_bounds():
    for name in ("chat-miss50", "batch-miss50"):
        t = _load(name)
        for i in make_items(t, 3, 10, 500):
            assert t["prompt_len"]["min"] <= len(i.prompt) <= \
                t["prompt_len"]["max"]
            assert t["output_len"]["min"] <= i.max_new <= \
                t["output_len"]["max"]
            assert i.prompt.max() < 500


def test_closed_backlog_all_due_at_start():
    t = _load("batch-miss50")
    items = make_items(t, 3, 40, 500)
    assert all(i.offset_s == 0.0 for i in items)
    assert len(items) > t["slots"] * 10
