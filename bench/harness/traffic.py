"""The one traffic generator: a traffic file's parameters and ``--seed`` in,
requests out.

Every seed gets the same work: the lengths of prompts and answers, the
gaps between arrivals and their order are drawn once from the file's
``shape_seed``, and ``--seed`` fills the prompts with other tokens (and the
weights with other numbers). An open-loop mix is built in three segments
(warm-up, window, after the window), each with a fixed count of arrivals.

``"order": "seed"`` in a traffic file lets ``--seed`` deal the same sizes
out in another order instead. It suits a closed backlog, which has no
arrival times for long requests to bunch in: the order only decides which
of the mix's requests the window holds.
"""
from __future__ import annotations

import dataclasses
import math

import numpy as np


@dataclasses.dataclass
class Item:
    offset_s: float        # due time after the traffic starts
    prompt: np.ndarray     # [P] token ids
    max_new: int
    in_window: bool


def seed_rng(seed: int, stream: int) -> np.random.Generator:
    """A generator for ``--seed`` (any whole number) and a named stream."""
    return np.random.default_rng([int(seed) % 2**64, stream])


def draw_lengths(spec: dict, n: int, rng: np.random.Generator) -> np.ndarray:
    lo, hi = int(spec["min"]), int(spec["max"])
    if spec["dist"] == "uniform":
        x = rng.integers(lo, hi + 1, n)
    elif spec["dist"] == "lognormal":
        x = np.round(spec["median"] * np.exp(spec["sigma"]
                                             * rng.standard_normal(n)))
    else:
        raise ValueError(f"unknown length distribution {spec['dist']!r}")
    return np.clip(x, lo, hi).astype(np.int64)


def _segment(rate: float, length_s: float, shape: np.random.Generator,
             deal: np.random.Generator) -> np.ndarray:
    """Offsets in [0, length_s) of round(rate * length_s) Poisson-like
    arrivals: the gaps are drawn from ``shape``, their order from
    ``deal``."""
    n = int(round(rate * length_s))
    if n == 0:
        return np.zeros(0)
    gaps = shape.exponential(1.0, n + 1)
    gaps = gaps / gaps.sum() * length_s      # n arrivals strictly inside
    return np.cumsum(deal.permutation(gaps)[:n])


def make_items(traffic: dict, seed: int, seconds: float,
               vocab: int) -> list:
    """The requests of one run, in order of due time."""
    shape = np.random.default_rng(int(traffic["shape_seed"]))
    order_by = traffic["order"]
    if order_by not in ("fixed", "seed"):
        raise ValueError(f"unknown order {order_by!r}")
    deal = shape if order_by == "fixed" else seed_rng(seed, 1)
    tokens = seed_rng(seed, 2)
    if traffic["arrivals"] == "poisson":
        rate = float(traffic["rate_rps"])
        warm, drain = float(traffic["warmup_s"]), float(traffic["drain_limit_s"])
        segs = [(0.0, warm, False), (warm, seconds, True),
                (warm + seconds, drain, False)]
        offsets, flags = [], []
        for start, length, in_window in segs:
            off = start + _segment(rate, length, shape, deal)
            offsets.append(off)
            flags += [in_window] * len(off)
        offsets = np.concatenate(offsets)
    elif traffic["arrivals"] == "closed":
        # a backlog that no run can drain (even at 4000 output tokens/s and
        # the shortest answers): every slot stays busy
        n = int(traffic["slots"]) + math.ceil(
            (traffic["warmup_s"] + seconds) * 4000
            / traffic["output_len"]["min"])
        offsets = np.zeros(n)
        flags = [True] * n
    else:
        raise ValueError(f"unknown arrivals {traffic['arrivals']!r}")
    n = len(offsets)
    # one fixed multiset of (prompt, answer) lengths per segment, dealt by
    # ``deal``
    plen = draw_lengths(traffic["prompt_len"], n, shape)
    olen = draw_lengths(traffic["output_len"], n, shape)
    order = np.arange(n)
    flags = np.asarray(flags)
    for f in (False, True):
        idx = np.flatnonzero(flags == f)
        order[idx] = idx[deal.permutation(len(idx))]
    return [Item(float(offsets[i]), tokens.integers(0, vocab, plen[order[i]]),
                 int(olen[order[i]]), bool(flags[i])) for i in range(n)]
