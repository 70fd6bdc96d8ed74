"""Scheduler: mean milliseconds a step of the serving loop's own host work
(``serve.admit``, ``serve.batch``, ``serve.emit``, ``serve.feedback``), in
the traced window."""
from harness.program_trace import SCHEDULER_SPANS, log_spans, per_step_ms


def read(run):
    if run.trace is None:
        return None
    return per_step_ms(log_spans(run.w0, run.w1), SCHEDULER_SPANS)
