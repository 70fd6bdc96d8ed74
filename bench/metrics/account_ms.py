"""Engine host path: mean milliseconds a step spends in ``serve.account``'s
own time (the replay of the step's routing on the transfer timeline, after
the read-back), in the traced window."""
from harness.program_trace import log_spans, per_step_ms


def read(run):
    if run.trace is None:
        return None
    return per_step_ms(log_spans(run.w0, run.w1), ("serve.account",),
                       self_time=True)
