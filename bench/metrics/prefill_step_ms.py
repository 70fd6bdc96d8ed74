"""Model step on the device: mean device time of one prefill-chunk step (the
busy time inside the host span of ``ServeEngine.prefill_rows``) in the
traced window."""
from harness.readers import step_device_ms


def read(run):
    return step_device_ms(run, "engine.prefill_rows")
