"""Engine host path: mean milliseconds a step spends in ``serve.buddy_state``
(building the step's residency, buddy tables and costs for the device), in
the traced window."""
from harness.program_trace import log_spans, per_step_ms


def read(run):
    if run.trace is None:
        return None
    return per_step_ms(log_spans(run.w0, run.w1), ("serve.buddy_state",))
