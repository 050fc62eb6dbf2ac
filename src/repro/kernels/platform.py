"""How a Pallas kernel runs: compiled by Mosaic when the program is
lowered for a TPU, interpreted when it is lowered for the CPU.

The choice is staged with ``jax.lax.platform_dependent``, so it follows
the platform the program is lowered FOR — not the process's default
backend.  An AOT compile for a described (unattached) TPU therefore
takes the compiled branch, and a TPU run never falls back to the
interpreter.  It is not a user option: every kernel wrapper in
``repro.kernels`` builds its call through :func:`pallas_call`.
"""

from __future__ import annotations

import jax
from jax.experimental import pallas as pl


def pallas_call(kernel, **kwargs):
    """``pl.pallas_call(kernel, **kwargs)`` with the interpret flag taken
    from the lowering platform (interpreted on CPU, compiled elsewhere)."""
    compiled = pl.pallas_call(kernel, interpret=False, **kwargs)
    interpreted = pl.pallas_call(kernel, interpret=True, **kwargs)

    def call(*args):
        return jax.lax.platform_dependent(*args, cpu=interpreted,
                                          default=compiled)
    return call
