"""Scheduler: 90th percentile of the wall time from when a request was due
to its admission into a slot, over the requests due in the window."""
from harness.readers import queue_wait_p90_ms as read  # noqa: F401
