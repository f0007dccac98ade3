"""The one place that decides whether a Pallas kernel runs interpreted.

Every kernel entry point takes ``interpret: Optional[bool] = None``;
``None`` resolves here.  Interpret mode is for CPU hosts only (tests and
examples): on any accelerator the kernel is compiled, and a kernel the
compiler refuses fails loudly instead of falling back to the interpreter.
"""

from __future__ import annotations

from typing import Optional

import jax

__all__ = ["resolve_interpret"]


def resolve_interpret(interpret: Optional[bool] = None) -> bool:
    """``interpret`` if given, else True only on the CPU backend."""
    if interpret is not None:
        return bool(interpret)
    return jax.default_backend() == "cpu"
