"""90th percentile, over the requests due in the window, of each request's
(last token - first token) / (tokens - 1), on the wall clock."""
from harness.readers import tpot_p90_ms as read  # noqa: F401
