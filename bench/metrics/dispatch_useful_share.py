"""Model step on the device: the expert slots that carried a routed token
over the slots the dispatch computed, in %, summed over the traced window's
steps (the program's ``serve.account`` counters)."""
from harness.program_trace import log_spans, useful_share


def read(run):
    if run.trace is None:
        return None
    return useful_share(log_spans(run.w0, run.w1))
