"""The one rule every Pallas wrapper uses to pick interpret mode."""

from __future__ import annotations

import jax

__all__ = ["default_interpret", "resolve_interpret"]


def default_interpret() -> bool:
    """Interpret only on the ``cpu`` backend.

    The kernels use TPU-only Pallas features (scalar prefetch, VMEM
    scratch, Mosaic compiler params). On the CPU the interpreter stands
    in for them in tests; on a TPU they compile, and nothing falls back.
    """
    return jax.default_backend() == "cpu"


def resolve_interpret(interpret: bool | None) -> bool:
    """``interpret`` as given, or :func:`default_interpret` for None."""
    return default_interpret() if interpret is None else interpret
