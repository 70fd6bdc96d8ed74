"""90th percentile, over the requests due in the window, of the wall time
from when a request was due to its first token reaching the host."""
from harness.readers import ttft_p90_ms as read  # noqa: F401
