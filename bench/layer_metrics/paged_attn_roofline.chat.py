"""The paged-attention kernel's share of its roofline (%).

The least time the chip could take for the decode rows of the window — the
larger of their attention operations over the bf16 peak and their valid
K/V bytes over the HBM bandwidth, each decode token at its own context from
the client log — over the kernel's device time in the trace."""
from harness.layer import paged_attn_roofline as read  # noqa: F401
