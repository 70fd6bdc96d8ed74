"""The program's own spans, counters and scopes, read two ways.

The serving loop names its host work with ``serve.*`` spans
(``repro.runtime.trace.host_span``): each is a ``TraceAnnotation`` in the
profiler's trace, and, while a trace runs, an entry of the program's
``HOST_SPAN_LOG`` on the host's ``perf_counter`` clock (the clock of the
window's ``w0`` and ``w1``). Its step programs name their layers with
``jax.named_scope``, which the device's ops carry as their ``op_name``.

A span is ``(name, start_s, end_s, depth, stats)``; ``depth`` counts the
``serve.*`` spans open around it. The per-layer readers in ``bench/metrics``
read the log (``log_spans``); ``load`` reads the same spans, and each device
op's scope path, out of a trace directory, for ``idle_by_span`` and
``device_by_scope``. A program without these spans gives an empty list, and
every reader then None.
"""
from __future__ import annotations

import bisect
import glob
import os
import re

from harness import trace_reduce as trr

SPANS = ("serve.step", "serve.buddy_state", "serve.launch", "serve.readback",
         "serve.account", "serve.sample", "serve.admit", "serve.batch",
         "serve.emit", "serve.feedback", "serve.wait")
SCHEDULER_SPANS = ("serve.admit", "serve.batch", "serve.emit",
                   "serve.feedback")
SCOPES = ("embed", "attention", "router", "substitute", "dispatch",
          "shared_experts", "lm_head")
_INSTR = re.compile(r"%?([\w.\-]+)")


def log_spans(lo: float, hi: float) -> list:
    """The program's logged spans that lie inside [lo, hi] of the host's
    clock; [] where the program keeps no such log."""
    try:
        from repro.runtime.trace import HOST_SPAN_LOG
    except ImportError:
        return []
    return [s for s in list(HOST_SPAN_LOG) if s[1] >= lo and s[2] <= hi]


def steps(spans, kind=None) -> list:
    return [s for s in spans if s[0] == "serve.step"
            and (kind is None or s[4].get("kind") == kind)]


def per_step_ms(spans, names, self_time=False):
    """Milliseconds a step of the spans named ``names`` (their own time,
    less the spans directly inside them, with ``self_time``), over the
    steps among ``spans``; a span inside another counts where it lies in
    one of those steps. None without a step."""
    st = sorted(steps(spans), key=_start)
    if not st:
        return None
    starts = [s[1] for s in st]

    def counted(s):
        i = bisect.bisect_right(starts, s[1]) - 1
        return s[3] == 0 or (i >= 0 and s[2] <= st[i][2])

    timed = (op_self_times(spans) if self_time
             else [s + (s[2] - s[1],) for s in spans])
    total = sum(s[5] for s in timed if s[0] in names and counted(s))
    return 1e3 * total / len(st)


def useful_share(spans):
    """Slots routed over slots computed by the dispatch, in %, summed over
    the steps' ``serve.account`` counters; None without them."""
    acc = [s[4] for s in spans if s[0] == "serve.account"
           and "slots_computed" in s[4]]
    computed = sum(a["slots_computed"] for a in acc)
    if not computed:
        return None
    return 100.0 * sum(a["slots_routed"] for a in acc) / computed


# -- the profiler's trace ------------------------------------------------
def load(log_dir: str):
    """(spans, ops, programs) of the trace under ``log_dir`` on the trace's
    clock: the ``serve.*`` host spans with their stats and depth, the first
    device's ops as (name, start_s, end_s, scope_path), and the names of
    the programs whose HLO the trace holds."""
    from jax.profiler import ProfileData
    paths = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                      recursive=True)
    path = sorted(paths)[-1]
    data = ProfileData.from_file(path)
    spans, ops = [], []
    devices = sorted((p for p in data.planes if p.name.startswith("/device:")
                      and not p.name.startswith("/device:CUSTOM")),
                     key=lambda p: p.name)
    for plane in data.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            evs = [(e.name, e.start_ns * 1e-9,
                    (e.start_ns + e.duration_ns) * 1e-9, dict(e.stats))
                   for e in line.events if e.name.startswith("serve.")]
            spans += _with_depth(evs)
    if devices:
        lines = {ln.name: ln for ln in devices[0].lines}
        mods = sorted((e.start_ns, e.start_ns + e.duration_ns, e.name)
                      for e in (lines["XLA Modules"].events
                                if "XLA Modules" in lines else ()))
        starts = [m[0] for m in mods]
        names = hlo_op_names(path)
        # a program's events and its HLO share a name such as
        # ``jit_prefill_chunk(<id>)``; else match on the bare name
        bare = {}
        for n, v in names.items():
            bare.setdefault(bare_name(n), []).append(v)
        found = {}
        for _, _, n in mods:
            same = bare.get(bare_name(n), [])
            found[n] = names.get(n, same[0] if len(same) == 1 else {})
        for e in (lines["XLA Ops"].events if "XLA Ops" in lines else ()):
            i = bisect.bisect_right(starts, e.start_ns) - 1
            module = found[mods[i][2]] if i >= 0 else {}
            m = _INSTR.match(e.name)
            ops.append((trr.op_name(e.name), e.start_ns * 1e-9,
                        (e.start_ns + e.duration_ns) * 1e-9,
                        module.get(m.group(1), "") if m else ""))
    else:
        names = {}
    return (sorted(spans, key=_start), sorted(ops, key=_start),
            sorted(names))


def _start(e):
    return e[1]


def bare_name(program: str) -> str:
    """A program's name without its id: ``jit_prefill_chunk``."""
    return re.sub(r"\(\d+\)$", "", program)


def _with_depth(events):
    """Spans of one thread's line, each with the count of spans open
    around it."""
    out, open_ends = [], []
    for n, a, b, st in sorted(events, key=lambda e: (e[1], -e[2])):
        while open_ends and open_ends[-1] <= a:
            open_ends.pop()
        out.append((n, a, b, len(open_ends), st))
        open_ends.append(b)
    return out


def hlo_op_names(path: str) -> dict:
    """{program: {instruction: op_name}} of every program in the trace at
    ``path``. The ops' events carry no scope: it is in each instruction's
    ``op_name`` metadata, in the HLO protos that the trace keeps in its
    ``/host:metadata`` plane under the program's name (the name of its
    ``XLA Modules`` events). A fusion with no ``op_name`` of its own takes
    the first of the instructions fused into it."""
    with open(path, "rb") as f:
        space = memoryview(f.read())
    out = {}
    for plane in _sub(space, 1):                         # XSpace.planes
        if bytes(next(iter(_sub(plane, 2)), b"")) != b"/host:metadata":
            continue
        for entry in _sub(plane, 4):                     # event_metadata
            for meta in _sub(entry, 2):
                name = bytes(next(iter(_sub(meta, 2)), b"")).decode()
                for stat in _sub(meta, 5):               # the HLO proto
                    for proto in _sub(stat, 6):
                        for module in _sub(proto, 1):
                            out[name] = _module_op_names(module)
    return out


def _module_op_names(module) -> dict:
    comps, calls, own = {}, {}, {}
    for comp in _sub(module, 3):                         # computations
        cid = next(iter(_sub(comp, 5)), None)
        comps[cid] = []
        for ins in _sub(comp, 2):                        # instructions
            name = bytes(_sub(ins, 1)[0]).decode()
            op = "".join(bytes(v).decode() for md in _sub(ins, 7)
                         for v in _sub(md, 2))
            own[name] = op
            comps[cid].append(name)
            calls[name] = [c for v in _sub(ins, 38) for c in _ints(v)]
    out = {}
    for name, op in own.items():
        if not op:                  # a fusion: the ops fused into it
            op = next((own[n] for c in calls[name] for n in comps.get(c, ())
                       if own[n]), "")
        out[name] = op
    return out


def _varint(b, i):
    r = s = 0
    while True:
        c = b[i]
        i += 1
        r |= (c & 0x7F) << s
        s += 7
        if c < 0x80:
            return r, i


def _fields(b):
    """(field number, value) of a protobuf message: an int for a varint,
    the bytes for any other wire type."""
    i, n = 0, len(b)
    while i < n:
        key, i = _varint(b, i)
        wire = key & 7
        if wire == 0:
            v, i = _varint(b, i)
        else:
            if wire == 2:
                ln, i = _varint(b, i)
            else:                                        # fixed 64 / 32
                ln = 8 if wire == 1 else 4
            v, i = b[i:i + ln], i + ln
        yield key >> 3, v


def _sub(msg, no):
    return [v for f, v in _fields(msg) if f == no]


def _ints(v):
    """The values of a repeated integer field: packed bytes or one int."""
    if isinstance(v, int):
        return [v]
    out, i = [], 0
    while i < len(v):
        x, i = _varint(v, i)
        out.append(x)
    return out


def top_scope(path: str) -> str:
    """The outermost named scope on an op's path, else ``unscoped``."""
    for part in path.split("/"):
        if part in SCOPES:
            return part
    return "unscoped"


def op_self_times(events):
    """Each event of one line with its own time appended: its duration
    less the events nested directly inside it. Ops are (name, start_s,
    end_s, scope_path); spans work too."""
    out = []
    stack = []                       # [index into out, end, child time]
    for ev in sorted(events, key=lambda e: (e[1], -e[2])):
        while stack and stack[-1][1] <= ev[1]:
            _close(stack, out)
        if stack:
            stack[-1][2] += ev[2] - ev[1]
        out.append(list(ev) + [0.0])
        stack.append([len(out) - 1, ev[2], 0.0])
    while stack:
        _close(stack, out)
    return [tuple(o) for o in out]


def _close(stack, out):
    i, _, child = stack.pop()
    out[i][-1] = out[i][2] - out[i][1] - child


def device_by_scope(ops, lo, hi):
    """Device self seconds in [lo, hi] by top-level scope, largest first."""
    out = {}
    for _, _, _, path, self_s in op_self_times(clip_ops(ops, lo, hi)):
        k = top_scope(path)
        out[k] = out.get(k, 0.0) + self_s
    return sorted(([k, v] for k, v in out.items()), key=lambda x: -x[1])


def scope_ms_per_step(ops, spans, scope, kind="chunk"):
    """Mean device self milliseconds under ``scope`` inside each
    ``serve.step`` of ``kind``; None without such a step."""
    st = steps(spans, kind)
    if not st:
        return None
    timed = op_self_times(ops)
    starts = [o[1] for o in timed]
    total = 0.0
    for _, a, b, _, _ in st:
        i = bisect.bisect_left(starts, a)
        while i < len(timed) and timed[i][1] < b:
            if timed[i][2] <= b and top_scope(timed[i][3]) == scope:
                total += timed[i][4]
            i += 1
    return 1e3 * total / len(st)


def idle_by_span(ops, spans, lo, hi):
    """Idle seconds in [lo, hi] by the innermost ``serve.*`` span at each
    gap's middle, else ``outside``; largest first."""
    merged = trr.union((a, b) for _, a, b, _ in clip_ops(ops, lo, hi))
    gaps, t = [], lo
    for a, b in merged:
        if a > t:
            gaps.append((t, a))
        t = max(t, b)
    if hi > t:
        gaps.append((t, hi))
    spans = sorted(spans, key=_start)
    starts = [s[1] for s in spans]
    out = {}
    for a, b in gaps:
        label = innermost(spans, starts, 0.5 * (a + b))
        out[label] = out.get(label, 0.0) + (b - a)
    return sorted(([k, v] for k, v in out.items()), key=lambda x: -x[1])


def innermost(spans, starts, t) -> str:
    """Name of the innermost span open at ``t``, else ``outside``. Spans of
    one thread nest: scanning back from the last to start at or before
    ``t``, the first that holds ``t`` is the innermost, and an outermost
    span that does not ends the search."""
    j = bisect.bisect_right(starts, t) - 1
    while j >= 0:
        n, a, b, depth, _ = spans[j]
        if a <= t < b:
            return n
        if depth == 0:
            break
        j -= 1
    return "outside"


def clip_ops(ops, lo, hi):
    return [(n, max(a, lo), min(b, hi), p) for n, a, b, p in ops
            if b > lo and a < hi]
