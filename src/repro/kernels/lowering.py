"""Where a Pallas kernel runs compiled and where it is interpreted.

Every kernel of this package is built through ``pallas_call`` below.  The
choice is made at lowering time from the platform the program is lowered
for: Mosaic compiles the kernel wherever that is a TPU, and Pallas'
interpreter runs it only where the program is lowered for the CPU (the
test suite's ``JAX_PLATFORMS=cpu`` runs).  A process on a chip can
therefore never reach the interpreter, and a program compiled ahead of
time for a described TPU (``jax.experimental.topologies``) carries the
Mosaic kernel even when the compiling process itself runs on the CPU.
"""
from __future__ import annotations

from typing import Any, Callable

import jax
from jax.experimental import pallas as pl


def pallas_call(kernel: Callable[..., None], **kwargs: Any) -> Callable:
    """``pl.pallas_call(kernel, **kwargs)``: compiled by Mosaic, or
    interpreted where the enclosing program is lowered for the CPU."""
    compiled = pl.pallas_call(kernel, **kwargs)
    kwargs.pop("compiler_params", None)
    interpreted = pl.pallas_call(kernel, interpret=True, **kwargs)

    def call(*args):
        return jax.lax.platform_dependent(
            *args, cpu=interpreted, default=compiled)

    return call
