"""Useful model operations of the window over the time its dispatches took
and the chip's bf16 peak (%).

Counted from the client log (a prompt when its first token arrives, each
later token as one row at its context), divided by the summed wall time of
the window's dispatch spans and the peak.  Under
an open loop the window's work is the offered load, so the step time, not
the window, is the denominator."""
from harness.layer import step_mfu_in_steps as read  # noqa: F401
