"""The program's own serving loop, driven and timed from outside.

``ContinuousScheduler`` admits and stamps on the engine's simulated clock.
These subclasses change only what the loop reads as time and add the
benchmark's spans: arrivals are released by the host's wall clock, each
emitted token is stamped with the wall time at which its step's sampled ids
reached the host, and the engine calls are wrapped in host spans (which the
profiler records as ``jax.profiler.TraceAnnotation`` events). Admission,
batching and every engine step are the program's.
"""
from __future__ import annotations

import time

import numpy as np
from jax.profiler import TraceAnnotation

from repro.serving.engine import ServeEngine
from repro.serving.scheduler import ContinuousScheduler, RequestQueue

clock = time.perf_counter


class Recorder:
    """What the window needs afterwards: host spans of every step, and,
    while ``keep`` is on, each step's routing record and residency mask for
    the comparison with the reference."""

    def __init__(self):
        self.spans = []          # (name, t0, t1)
        self.steps = []          # dicts, one per engine step
        self.keep = False
        self.rows = None         # () -> [rid or -1] per slot, set by the loop

    def span(self, name, t0, t1):
        self.spans.append((name, t0, t1))


class BenchEngine(ServeEngine):
    """``ServeEngine`` with host spans around ``step``, ``prefill_rows`` and
    ``sample_tokens``, and a per-step record of what the reference needs."""

    rec: Recorder
    fault = None                 # name of a planted fault (tests only)

    def _note(self, kind, resid, pos, counts):
        if self.rec.keep:
            self.rec.steps.append({"kind": kind, "resid": resid,
                                   "pos": pos, "counts": counts,
                                   "rows": self.rec.rows()})

    def _done_step(self, t0, name):
        t1 = clock()
        self.rec.span(name, t0, t1)
        if self.rec.keep:
            self.rec.steps[-1]["t1"] = t1

    def step(self, token, caches, pos, active=None):
        t0 = clock()
        with TraceAnnotation("engine.step"):
            resid = self.cache.resident.copy() if self.rec.keep else None
            pos = np.asarray(pos)
            counts = (np.ones(len(pos), np.int32) if active is None
                      else np.asarray(active, np.int32))
            self._note("decode", resid, pos.copy(), counts)
            logits, new = super().step(token, caches, pos, active)
            if self.fault == "state_unchanged":
                new = caches
        self._done_step(t0, "engine.step")
        return logits, new

    def prefill_rows(self, tokens, rows, caches, base_pos, tok_valid=None):
        t0 = clock()
        with TraceAnnotation("engine.prefill_rows"):
            resid = self.cache.resident.copy() if self.rec.keep else None
            base = np.asarray(base_pos)
            counts = (np.asarray(tok_valid).sum(1) if tok_valid is not None
                      else np.where(rows, tokens.shape[1], 0))
            self._note("chunk", resid, base.copy(), counts.astype(np.int32))
            logits, new = super().prefill_rows(tokens, rows, caches, base_pos,
                                               tok_valid)
            if self.fault == "state_unchanged":
                new = caches
        self._done_step(t0, "engine.prefill_rows")
        return logits, new

    def _account(self, aux, active):
        super()._account(aux, active)
        if self.rec.keep and self.rec.steps:
            # host copies: ``_account`` has just read these back already
            r = aux["recorded"][0]
            self.rec.steps[-1]["route"] = tuple(
                np.asarray(r[k]) for k in ("indices", "substituted",
                                           "degraded", "dropped", "peered"))

    def sample_tokens(self, logits, greedy, temperature=1.0):
        t0 = clock()
        with TraceAnnotation("sample"):
            ids = super().sample_tokens(logits, greedy, temperature)
            if self.fault == "token_altered":
                ids = (ids + 1) % self.cfg.vocab_size
            elif self.fault == "half_batch":
                ids = ids.copy()            # odd rows get their neighbour's
                ids[1::2] = ids[0::2][:len(ids) // 2]
        self.rec.span("sample", t0, clock())
        return ids


class WallQueue(RequestQueue):
    """FCFS backlog whose requests fall due by the host's wall clock
    (``arrival_s`` holds wall times). Stamps each admission."""

    def __init__(self, requests, sim_now):
        super().__init__(requests)
        self._sim_now = sim_now
        self.admitted = {}       # rid -> wall time

    def release_until(self, now):
        super().release_until(clock())

    def pop(self, now, est_service_fn=None):
        r = super().pop(now, est_service_fn)
        if r is not None:
            self.admitted[r.rid] = clock()
        return r

    def next_arrival(self):
        """Called by the loop when every slot is empty: wait for the next
        request to fall due, then let the loop admit it."""
        if not self._future:
            return None
        wait = self._future[0].arrival_s - clock()
        if wait > 0:
            time.sleep(wait)
        return self._sim_now()


class BenchScheduler(ContinuousScheduler):
    """``ContinuousScheduler`` whose tokens carry wall-clock stamps, and
    which stops after the step at which ``done(now)`` says so."""

    def __init__(self, engine, slots, *, prefill_chunk, done,
                 rec: Recorder):
        super().__init__(engine, slots, prefill_chunk=prefill_chunk)
        self._done = done
        self._stamp_step = -1
        self._stamp = 0.0
        self.rec = rec
        rec.rows = lambda: [r.rid if r is not None else -1
                            for r in self._slot]
        self.emitted = []        # wall stamp of every emitted token

    def _admit(self, queue, slot, pos, tok, caches):
        t0 = clock()
        with TraceAnnotation("admit"):
            out = super()._admit(queue, slot, pos, tok, caches)
        self.rec.span("admit", t0, clock())
        return out

    def _emit(self, slot, i, nxt, t1, tok):
        if self._stamp_step != self.steps:
            self._stamp_step = self.steps
            self._stamp = clock()
        self.emitted.append(self._stamp)
        super()._emit(slot, i, nxt, self._stamp, tok)

    def _feedback(self, queue):
        super()._feedback(queue)
        if self._done(clock()):
            self.max_steps = self.steps
