"""Arithmetic shared by the metric readers in ``bench/metrics``.

Each reader takes a ``harness.runner.Run`` and returns a number, or None
where its run has nothing to read (a reader of the trace in an untraced
run, a prefill time in a cell with no prefill)."""
from __future__ import annotations

from harness import trace_reduce as tr
from harness.stats import in_window, percentile


def window_requests(run):
    return [r for r in run.requests if r.rid in run.window_rids]


def ttft_s(run, r):
    end = r.first_token_s if r.first_token_s >= 0 else run.end
    return end - r.arrival_s


def tpot_s(r):
    return r.tpot() if len(r.tokens) > 1 and r.finished_s >= 0 else None


def ttft_p90_ms(run):
    v = percentile([ttft_s(run, r) for r in window_requests(run)], 90)
    return None if v is None else 1e3 * v


def tpot_p90_ms(run):
    xs = [tpot_s(r) for r in window_requests(run)]
    v = percentile([x for x in xs if x is not None], 90)
    return None if v is None else 1e3 * v


def output_tokens_per_s(run):
    n = sum(1 for t in run.emitted if in_window(t, run.w0, run.w1))
    return n / (run.w1 - run.w0)


def queue_wait_p90_ms(run):
    xs = [run.admitted[r.rid] - r.arrival_s for r in window_requests(run)
          if r.rid in run.admitted]
    v = percentile(xs, 90)
    return None if v is None else 1e3 * v


def step_device_ms(run, span):
    return None if run.trace is None else tr.span_device_ms(run.trace, span)


def host_ms_per_step(run):
    return None if run.trace is None else tr.host_ms_per_step(run.trace)


def device_idle_share(run):
    if run.trace is None:
        return None
    lo, hi = run.trace.window
    return 100.0 * (1.0 - tr.busy_s(run.trace) / (hi - lo))


def step_mfu(run):
    """Model operations of every token processed in the window, over the
    window and the chip's bf16 peak, in percent."""
    if run.peak is None:
        return None
    return 100.0 * run.flops_window / (run.w1 - run.w0) \
        / (run.peak["bf16_flops"] * run.chips)
