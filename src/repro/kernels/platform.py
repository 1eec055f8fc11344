"""The one rule for how a Pallas kernel runs: compiled on a TPU, interpreted
everywhere else.

The rule is applied when the program is lowered for its platform
(``jax.lax.platform_dependent``), never when a module is imported: a kernel
inside a program placed on the TPU is compiled by Mosaic, and the same kernel
inside a program placed on the CPU (tests, a CPU reference run in the same
process) runs through the Pallas interpreter.  Only the chosen branch is
emitted into the lowered program.
"""
from __future__ import annotations

import jax
from jax.experimental import pallas as pl


def pallas_call(kernel, *, interpret: bool | None = None, **kwargs):
    """``pl.pallas_call`` under the platform rule.  ``interpret=None`` picks
    the mode from the platform the program is lowered for; ``True`` or
    ``False`` forces it (tests, and compiles for a described chip)."""
    if interpret is not None:
        return pl.pallas_call(kernel, interpret=interpret, **kwargs)
    compiled = pl.pallas_call(kernel, interpret=False, **kwargs)
    interpreted = pl.pallas_call(kernel, interpret=True, **kwargs)

    def call(*args):
        return jax.lax.platform_dependent(*args, tpu=compiled,
                                          default=interpreted)

    return call
