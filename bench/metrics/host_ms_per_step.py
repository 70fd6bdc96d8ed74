"""Engine host path: mean over the window's steps of the host span from the
engine call to the end of sampling, less the device's busy time inside it."""
from harness.readers import host_ms_per_step as read  # noqa: F401
