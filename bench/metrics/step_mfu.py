"""The whole step against the chip's peak: model operations of every token
processed in the window, over the window and the bf16 peak, in %."""
from harness.readers import step_mfu as read  # noqa: F401
