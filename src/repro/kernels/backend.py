"""The one rule for when a Pallas kernel runs in the interpreter."""
from __future__ import annotations

import jax

__all__ = ["resolve_interpret"]


def resolve_interpret(interpret: bool | None = None) -> bool:
    """``interpret=None`` ⇒ compiled on a TPU, interpreted everywhere else.

    Every kernel wrapper routes its ``interpret`` argument through here, so
    nothing silently interprets on the chip and CPU runs need no flag.
    """
    if interpret is None:
        return jax.default_backend() != "tpu"
    return bool(interpret)
