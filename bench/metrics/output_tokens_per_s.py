"""Tokens that reached the host in the window, over its length."""
from harness.readers import output_tokens_per_s as read  # noqa: F401
