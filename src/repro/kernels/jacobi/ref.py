"""Pure-jnp oracle for the blocked 3D six-point Jacobi sweep (paper §1.4).

F_{t+1}(i,j,k) = c * [ F_t(i-1,j,k) + F_t(i+1,j,k)
                     + F_t(i,j-1,k) + F_t(i,j+1,k)
                     + F_t(i,j,k-1) + F_t(i,j,k+1) ]

Dirichlet boundary: sites outside the lattice are zero.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp


@jax.jit
def jacobi_sweep_ref(f: jnp.ndarray, c: float | jnp.ndarray = 1.0 / 6.0) -> jnp.ndarray:
    """One whole-lattice Jacobi sweep on a (Ni, Nj, Nk) array.

    Jitted: run op by op, the pad and the six slices would each hold a
    lattice-sized array on the device.
    """
    p = jnp.pad(f, 1)
    out = (p[:-2, 1:-1, 1:-1] + p[2:, 1:-1, 1:-1]
           + p[1:-1, :-2, 1:-1] + p[1:-1, 2:, 1:-1]
           + p[1:-1, 1:-1, :-2] + p[1:-1, 1:-1, 2:])
    return (c * out).astype(f.dtype)


def jacobi_block_ref(f: jnp.ndarray, i0: int, j0: int, di: int, dj: int,
                     c: float = 1.0 / 6.0) -> jnp.ndarray:
    """Jacobi update of one (di, dj, Nk) block of the full lattice — the
    paper's ``jacobi_sweep_block()`` — with global boundary conditions."""
    return jacobi_sweep_ref(f, c)[i0:i0 + di, j0:j0 + dj, :]
