"""What decides ``correct``: the served tokens against the plain reference.

Once the window has closed and the program's state is freed, a sample of
the finished requests, drawn from the seed and always holding the longest,
is run through the configuration's reference (``"reference"``, e.g.
``bench/reference/moe_decoder.py``), one layer at a time with the weights
drawn again from the seed by the configuration's builder
(``harness/model.py``). An MoE layer runs the reference's ``layer`` with the
program's recorded route of that MoE layer; a layer of another kind runs its
``block``, which a reference defines only where its builder has such
layers. The numbers compared:

- ``token_gap``: the widest gap, over the sample's served tokens, by which
  a served token's reference logit lies below the reference's best at that
  position, over the spread (std) of the reference's logits there.
- ``route_gap``: the same for the router, over every fed position and MoE
  layer:
  how far the least of the program's kept experts lies below the
  reference's k-th best router logit, over the spread of the router logits.
- ``decision_errors``: fed positions with no record, slots substituted that
  were no miss or had no eligible buddy, and slots resolved by an outcome
  this deployment does not have (degraded, dropped, peer).
- ``substituted``: the substituted slots compared (positions x layers x
  kept experts). A traffic file's ``at_least`` gives it a lower limit where
  the mix has misses, so that a program that stops substituting fails.
- ``narrow_leaves``: leaves of the engine's weights and KV cache stored in
  a narrower float type than the configuration states (set by the runner).

With ``control`` the same positions are also read by the int8 control
(``bench/tools/probe.py --control 1``): the gap of the token that it puts
first, and of the experts it keeps. ``control_passes`` holds those readings
to the cell's limits: the control has to fail them.
"""
from __future__ import annotations

import functools
import json
import sys

import jax
import jax.numpy as jnp
import numpy as np

from harness.model import layer_key, outer_key
from harness.spec import load_module
from harness.traffic import seed_rng

BUCKET = 256


def sample_requests(done: list, seed: int, tokens: int,
                    rec_steps: list = ()) -> list:
    """The longest finished request, one served in each slot, then others,
    all drawn from the seed, until the sample holds ``tokens`` served
    tokens."""
    if not done:
        return []
    done = sorted(done, key=lambda r: r.rid)
    slot_of = {}
    for st in rec_steps:
        for i, rid in enumerate(st["rows"]):
            slot_of.setdefault(rid, i)
    longest = max(done, key=lambda r: len(r.prompt) + len(r.tokens))
    pick, seen = [longest], {slot_of.get(longest.rid)}
    order = [done[i] for i in seed_rng(seed, 4).permutation(len(done))]
    for r in order:
        if r is not longest and slot_of.get(r.rid) not in seen:
            pick.append(r)
            seen.add(slot_of.get(r.rid))
    n = sum(len(r.tokens) for r in pick)
    for r in order:
        if n >= tokens:
            break
        if r not in pick:
            pick.append(r)
            n += len(r.tokens)
    return pick


def teacher_forced(rec_steps: list, req, n_layers: int):
    """Per fed position of ``req``: routed experts [S, L, K], substituted
    [S, L, K], residency [S, L, E], and the count of decision errors. ``L``
    counts the MoE layers, which the program records."""
    s_len = len(req.prompt) + len(req.tokens) - 1
    route = sub = resid = None
    seen = np.zeros(s_len, bool)
    errors = 0
    for st in rec_steps:
        if req.rid not in st["rows"] or "route" not in st:
            continue
        i = st["rows"].index(req.rid)
        n = int(st["counts"][i])
        if n == 0:
            continue
        idx, sb, dg, dr, pr = st["route"]
        b = len(st["rows"])
        c = idx.shape[1] // b
        if route is None:
            k, e = idx.shape[2], st["resid"].shape[1]
            route = np.zeros((s_len, n_layers, k), np.int32)
            sub = np.zeros((s_len, n_layers, k), bool)
            resid = np.zeros((s_len, n_layers, e), bool)
        for j in range(n):
            p = int(st["pos"][i]) + j
            t = i * c + j
            if p >= s_len or seen[p]:
                errors += 1
                continue
            seen[p] = True
            route[p] = idx[:, t]
            sub[p] = sb[:, t]
            resid[p] = st["resid"]
            errors += int((dg[:, t] | dr[:, t] | pr[:, t]).sum())
    if route is None:
        return None
    errors += int((~seen).sum())
    return route, sub, resid, errors


@functools.lru_cache(maxsize=None)
def _fns(ref_path: str, builder, m_json: str):
    """Jitted pieces of the reference, each compiled once: every sequence
    is padded to one length, so no shape depends on a request. The weights
    are drawn by ``builder``'s ``layer_weights`` (one per layer kind) and
    ``outer_weights``."""
    ref = load_module(ref_path, "reference_" + str(abs(hash(ref_path))))
    m = json.loads(m_json)
    fns = {"ref": ref,
           "gen_outer": jax.jit(lambda k: builder.outer_weights(k, m)),
           "embed": jax.jit(lambda o, t: o["embed"][t])}
    kinds = set(builder.layer_kinds(m))
    for kind in kinds:
        fns["gen_layer", kind] = jax.jit(
            lambda k, kind=kind: builder.layer_weights(k, m, kind))
    for q8 in (False, True):
        fns["layer", q8] = jax.jit(
            lambda p, x, r, f, q8=q8: ref.layer(p, x, r, f, m, q8))
        for kind in kinds - {"moe"}:
            fns["block", kind, q8] = jax.jit(
                lambda p, x, kind=kind, q8=q8: ref.block(p, x, m, kind, q8))

    def tail(o, x, nxt, xc=None):
        """Per position: the reference's best logit, its spread, its logit
        of the next token and, given the control's state ``xc``, its logit
        of the token the control puts first."""
        lg = ref.head(o, x, m)
        out = [jnp.max(lg, -1), jnp.std(lg, -1),
               jnp.take_along_axis(lg, nxt[:, None], 1)[:, 0]]
        if xc is not None:
            top = jnp.argmax(ref.head(o, xc, m, True), -1)
            out.append(jnp.take_along_axis(lg, top[:, None], 1)[:, 0])
        return out
    fns["tail"] = jax.jit(tail)
    return fns


def _pad(a, n, fill=0):
    pad = [(0, n - a.shape[0])] + [(0, 0)] * (a.ndim - 1)
    return np.pad(a, pad, constant_values=fill)


def compare(cfg_file: dict, root, seed: int, reqs: list, rec_steps: list,
            table: np.ndarray, builder, control: bool = False,
            pad_to: int = 0) -> dict:
    """The compared numbers over the sampled requests ``reqs``; with
    ``control`` also the int8 control's, under ``control_*``. ``table`` is
    [MoE layers, E, R]; ``builder`` the configuration's
    (``spec.load_builder``). Sequences
    are padded to ``pad_to`` (the traffic's longest request) so that one
    compile serves every run."""
    m = cfg_file["model"]
    fns = _fns(str(root / cfg_file["reference"]), builder,
               json.dumps(m, sort_keys=True))
    ref = fns["ref"]
    kinds = builder.layer_kinds(m)
    n_moe = kinds.count("moe")
    out = {"token_gap": 0.0, "route_gap": 0.0, "decision_errors": 0,
           "token_gap_mean": 0.0, "route_gap_mean": 0.0, "substituted": 0,
           "requests": len(reqs), "tokens": 0, "positions": 0}
    sides = [("", False)] + ([("control_", True)] if control else [])
    for pre, _ in sides[1:]:
        out.update({pre + k: 0.0 for k in ("token_gap", "route_gap",
                                           "token_gap_mean",
                                           "route_gap_mean")})
    data = []
    for r in reqs:
        tf = teacher_forced(rec_steps, r, n_moe)
        if tf is None:
            out["decision_errors"] += 1
            continue
        route, sub, resid, err = tf
        out["decision_errors"] += err
        out["substituted"] += int(sub.sum())
        seq = np.concatenate([np.asarray(r.prompt),
                              np.asarray(r.tokens)]).astype(np.int32)
        s_len = len(seq) - 1
        data.append({"req": r, "seq": seq, "s": s_len, "route": route,
                     "sub": sub, "resid": resid})
        out["positions"] += s_len
        out["tokens"] += len(r.tokens)
    if not data:
        return out
    s_pad = max([pad_to] + [d["s"] + 1 for d in data])
    s_pad = -(-s_pad // BUCKET) * BUCKET
    for d in data:
        for k, fill in (("route", 0), ("sub", False), ("resid", True)):
            d[k] = _pad(d[k], s_pad, fill)
    sums = {pre: [0.0, 0.0] for pre, _ in sides}    # route, token gap sums
    with jax.default_matmul_precision("highest"):
        o = fns["gen_outer"](outer_key(seed))
        for d in data:
            x = fns["embed"](o, jnp.asarray(_pad(d["seq"][:-1], s_pad)))
            d["x"] = {pre: x for pre, _ in sides}
        del o
        for l, kind in enumerate(kinds):
            w = fns["gen_layer", kind](layer_key(seed, l))
            if kind != "moe":
                for d in data:
                    for pre, q8 in sides:
                        d["x"][pre] = fns["block", kind, q8](w, d["x"][pre])
                del w
                continue
            i = kinds[:l].count("moe")      # the program records MoE layers
            for d in data:
                route = jnp.asarray(d["route"][:, i])
                final, bad = ref.pick_buddies(d["route"][:, i],
                                              d["sub"][:, i],
                                              d["resid"][:, i], table[i])
                out["decision_errors"] += bad
                final = jnp.asarray(final)
                logits = None
                for pre, q8 in sides:
                    d["x"][pre], lg, own = fns["layer", q8](
                        w, d["x"][pre], route, final)
                    if logits is None:
                        logits = lg      # the reference's router logits
                    gap = np.asarray(ref.route_gap(logits, own if q8
                                                   else route))[:d["s"]]
                    out[pre + "route_gap"] = max(out[pre + "route_gap"],
                                                 float(gap.max()))
                    sums[pre][0] += float(gap.sum())
            del w
        o = fns["gen_outer"](outer_key(seed))
        for d in data:
            p, n = len(d["req"].prompt), len(d["req"].tokens)
            nxt = jnp.asarray(_pad(d["seq"][1:], s_pad))
            best, spread, *at = (
                np.asarray(a)[p - 1:p - 1 + n] for a in fns["tail"](
                    o, d["x"][""], nxt, d["x"].get("control_")))
            for (pre, _), a in zip(sides, at):
                gap = (best - a) / spread
                out[pre + "token_gap"] = max(out[pre + "token_gap"],
                                             float(gap.max()))
                sums[pre][1] += float(gap.sum())
    for pre, _ in sides:
        out[pre + "route_gap_mean"] = sums[pre][0] / (out["positions"]
                                                      * n_moe)
        out[pre + "token_gap_mean"] = sums[pre][1] / out["tokens"]
    return out


def narrow_leaves(trees, dtype: str) -> int:
    """Floating leaves of ``trees`` (arrays or dtypes) narrower than
    ``dtype``."""
    want = np.dtype(dtype).itemsize
    n = 0
    for leaf in jax.tree.leaves(trees):
        dt = np.dtype(getattr(leaf, "dtype", leaf))
        n += int(jnp.issubdtype(dt, jnp.floating) and dt.itemsize < want)
    return n


def report(compared: dict, limits: dict, at_least: dict,
           stream=sys.stderr) -> dict:
    """Each number the configuration's ``limits`` (upper) and the traffic's
    ``at_least`` (lower) name, beside its limit; the last lines on
    stderr."""
    rows = {}
    for name, lim in limits.items():
        rows[name] = {"value": compared[name], "limit": lim}
        print(f"compared {name} {compared[name]!r} limit {lim!r}",
              file=stream)
    for name, lim in at_least.items():
        rows[name] = {"value": compared[name], "at_least": lim}
        print(f"compared {name} {compared[name]!r} at least {lim!r}",
              file=stream)
    return rows


def passes(compared: dict, limits: dict, at_least: dict) -> bool:
    """Every named number within its limit, and at least one served token
    compared."""
    return compared["tokens"] > 0 and all(
        lim is not None and compared[k] <= lim
        for k, lim in limits.items()) and all(
        compared[k] >= lim for k, lim in at_least.items())


def control_passes(compared: dict, limits: dict) -> bool:
    """The control's verdict: its readings (``control_*``) against the
    same limits. Numbers the control has no reading of (its decisions are
    the reference's own) are left out."""
    named = {k: lim for k, lim in limits.items()
             if "control_" + k in compared}
    return compared["tokens"] > 0 and bool(named) and all(
        compared["control_" + k] <= lim for k, lim in named.items())
