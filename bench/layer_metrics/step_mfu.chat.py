"""Useful model operations of the window over the chip's bf16 peak (%).

Counted from the client log: a prompt when its first token arrives in the
window (every prompt row at its context, the LM head on its last row only)
and each later token received in the window (one row at its context, with
the head), divided by the window and the peak."""
from harness.layer import step_mfu as read  # noqa: F401
