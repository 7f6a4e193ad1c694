"""Public entry point for the Jacobi stencil kernel."""
from __future__ import annotations

import jax.numpy as jnp

from .kernel import jacobi_sweep_pallas


def jacobi_sweep(f: jnp.ndarray, c: float = 1.0 / 6.0, di: int = 10,
                 dj: int = 8, interpret: bool = False) -> jnp.ndarray:
    """One Jacobi sweep through the Pallas kernel.

    The default (10, 8) block is accepted by the TPU compiler at the
    paper's 2400x600x600 grid.  Tests on the CPU pass ``interpret=True``.
    """
    return jacobi_sweep_pallas(f, c, di=di, dj=dj, interpret=interpret)
