"""From a profiler trace to numbers: device busy and idle time, the device
operations that took most time, the step programs' device time, and the
idle gaps labelled by the host span around them.

``load`` turns the ``.xplane.pb`` that ``jax.profiler`` writes into plain
events; everything else works on those, so that a small recorded trace
(``bench/tests/data``) checks the arithmetic without a chip.
"""
from __future__ import annotations

import bisect
import dataclasses
import glob
import os
import re

import numpy as np

HOST_SPANS = ("engine.step", "engine.prefill_rows", "sample", "admit")
_OP = re.compile(r"(%[\w.\-]+) = \(?([a-z0-9]+\[[0-9,]*\])")


def op_name(hlo: str) -> str:
    """``%fusion.575 bf16[8,64,192,1408]`` from an op's HLO text."""
    m = _OP.match(hlo)
    return f"{m.group(1)} {m.group(2)}" if m else hlo[:80]


@dataclasses.dataclass
class Trace:
    ops: list          # (name, start_s, end_s) on the device's op line
    modules: list      # (name, start_s, end_s) on the device's program line
    host: list         # (name, start_s, end_s) of the benchmark's host spans
    window: tuple      # (start_s, end_s) of the traced window
    chips: int = 1
    lines: dict = None  # plane name -> its line names, as recorded

    def to_json(self) -> dict:
        return dataclasses.asdict(self)

    @classmethod
    def from_json(cls, d: dict) -> "Trace":
        d = dict(d)
        for k in ("ops", "modules", "host"):
            d[k] = [tuple(e) for e in d[k]]
        d["window"] = tuple(d["window"])
        return cls(**d)

    def excerpt(self, seconds: float) -> "Trace":
        """The first ``seconds`` of the window, events clipped to it."""
        lo = self.window[0]
        hi = lo + seconds
        return Trace(clip(self.ops, lo, hi), clip(self.modules, lo, hi),
                     clip(self.host, lo, hi), (lo, hi), self.chips,
                     self.lines)


def load(log_dir: str) -> Trace:
    """Events of the first device and the benchmark's host spans, in
    seconds on the trace's clock; the window is set by ``align``."""
    from jax.profiler import ProfileData
    paths = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    data = ProfileData.from_file(sorted(paths)[-1])
    ops, modules, host = [], [], []
    devices = [p for p in data.planes if p.name.startswith("/device:")
               and not p.name.startswith("/device:CUSTOM")]
    devices.sort(key=lambda p: p.name)
    for plane in data.planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name in HOST_SPANS:
                        host.append((e.name, e.start_ns * 1e-9,
                                     (e.start_ns + e.duration_ns) * 1e-9))
    if devices:
        for line in devices[0].lines:
            dest = {"XLA Ops": ops, "XLA Modules": modules}.get(line.name)
            if dest is None:
                continue
            for e in line.events:
                dest.append((op_name(e.name) if dest is ops else e.name,
                             e.start_ns * 1e-9,
                             (e.start_ns + e.duration_ns) * 1e-9))
    return Trace(sorted(ops, key=_start), sorted(modules, key=_start),
                 sorted(host, key=_start), (0.0, 0.0),
                 chips=max(1, len(devices)),
                 lines={p.name: [ln.name for ln in p.lines]
                        for p in data.planes})


def _start(e):
    return e[1]


def align(tr: Trace, wall_spans: list, w0: float, w1: float) -> None:
    """Put the window [w0, w1) of the host's clock onto the trace's clock:
    the benchmark's host spans are on both, in the same order."""
    pairs = [(a[1] - b[1]) for a, b in zip(tr.host, wall_spans)
             if a[0] == b[0]][:512]
    if not pairs:
        raise ValueError("no host span of the benchmark in the trace")
    off = float(np.median(pairs))
    tr.window = (w0 + off, w1 + off)


def clip(events, lo, hi):
    return [(n, max(a, lo), min(b, hi)) for n, a, b in events
            if b > lo and a < hi]


def union(intervals):
    """Merged, sorted (start, end) pairs of the given intervals."""
    out = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [tuple(x) for x in out]


def busy_s(tr: Trace) -> float:
    lo, hi = tr.window
    return sum(b - a for a, b in union((a, b) for _, a, b in
                                       clip(tr.ops, lo, hi)))


def busy_within(merged, lo, hi) -> float:
    """Busy seconds of the merged (sorted, disjoint) intervals in [lo, hi]."""
    i = max(0, bisect.bisect_right(merged, (lo, float("inf"))) - 1)
    out = 0.0
    while i < len(merged) and merged[i][0] < hi:
        a, b = merged[i]
        out += max(0.0, min(b, hi) - max(a, lo))
        i += 1
    return out


def self_times(events):
    """{name: seconds} of each op's own time: its duration less the ops
    nested inside it on the same line."""
    out = {}
    stack = []                       # [name, end, child_time]
    for n, a, b in sorted(events, key=lambda e: (e[1], -e[2])):
        while stack and stack[-1][1] <= a:
            _close(stack, out)
        if stack:
            stack[-1][2] += b - a
        stack.append([n, b, 0.0, a])
    while stack:
        _close(stack, out)
    return out


def _close(stack, out):
    n, b, child, a = stack.pop()
    out[n] = out.get(n, 0.0) + (b - a) - child


def top_ops(tr: Trace, k: int = 10):
    lo, hi = tr.window
    st = self_times(clip(tr.ops, lo, hi))
    return sorted(([n, s] for n, s in st.items()), key=lambda x: -x[1])[:k]


def idle_gaps(tr: Trace, k: int = 10):
    """Idle seconds in the window by what the host was doing: the
    benchmark's host span at the middle of each gap, ``between_ops`` inside
    a running program, else ``other``."""
    lo, hi = tr.window
    merged = union((a, b) for _, a, b in clip(tr.ops, lo, hi))
    mods = union((a, b) for _, a, b in clip(tr.modules, lo, hi))
    gaps = []
    t = lo
    for a, b in merged:
        if a > t:
            gaps.append((t, a))
        t = max(t, b)
    if hi > t:
        gaps.append((t, hi))
    host = sorted(tr.host, key=lambda e: e[1])
    h_start = [e[1] for e in host]
    m_start = [a for a, _ in mods]
    out = {}
    for a, b in gaps:
        mid = 0.5 * (a + b)
        i = bisect.bisect_right(m_start, mid) - 1
        j = bisect.bisect_right(h_start, mid) - 1
        if i >= 0 and mid < mods[i][1]:
            label = "between_ops"
        elif j >= 0 and mid < host[j][2]:
            label = host[j][0]      # the benchmark's host spans do not nest
        else:
            label = "other"
        out[label] = out.get(label, 0.0) + (b - a)
    return sorted(([n, s] for n, s in out.items()), key=lambda x: -x[1])[:k]


def span_device_ms(tr: Trace, span: str):
    """Mean device-busy milliseconds inside the host spans named ``span``
    that lie in the window: the device time of one engine call, which
    returns only once its step's outputs are on the host. None where no
    such span lies in the window. (The step programs are jitted partials,
    which the trace names ``jit__unknown``, so the host span tells them
    apart.)"""
    lo, hi = tr.window
    merged = union((a, b) for _, a, b in clip(tr.ops, lo, hi))
    d = [busy_within(merged, a, b) for n, a, b in tr.host
         if n == span and a >= lo and b <= hi]
    return 1e3 * sum(d) / len(d) if d else None


def host_ms_per_step(tr: Trace):
    """Mean, over the steps in the window, of each step's host span (from
    the engine call to the end of sampling its tokens) less the device's
    busy time inside it."""
    lo, hi = tr.window
    merged = union((a, b) for _, a, b in clip(tr.ops, lo, hi))
    host = sorted(tr.host, key=lambda e: e[1])
    vals = []
    start = None
    for n, a, b in host:
        if n in ("engine.step", "engine.prefill_rows"):
            start = a
        elif n == "sample" and start is not None:
            if start >= lo and b <= hi:
                vals.append((b - start) - busy_within(merged, start, b))
            start = None
    return 1e3 * sum(vals) / len(vals) if vals else None
