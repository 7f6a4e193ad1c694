"""Plain reference for the six-point Jacobi sweep (arXiv:1101.0093, §1.4).

    F'(i,j,k) = c * [F(i-1,j,k) + F(i+1,j,k) + F(i,j-1,k) + F(i,j+1,k)
                     + F(i,j,k-1) + F(i,j,k+1)],   c = 1/6,

with every site outside the lattice held at zero (Dirichlet faces).  It
imports nothing of the program.  The comparisons are fused with the sweep,
so no reference lattice is ever held on the device.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

C = 1.0 / 6.0


def sweep(f: jax.Array) -> jax.Array:
    """One sweep in the dtype of ``f``."""
    p = jnp.pad(f, 1)
    s = (p[:-2, 1:-1, 1:-1] + p[2:, 1:-1, 1:-1]
         + p[1:-1, :-2, 1:-1] + p[1:-1, 2:, 1:-1]
         + p[1:-1, 1:-1, :-2] + p[1:-1, 1:-1, 2:])
    return s * jnp.asarray(C, f.dtype)


def _rel(y: jax.Array, r: jax.Array) -> jax.Array:
    return jnp.max(jnp.abs(y - r)) / jnp.max(jnp.abs(r))


@jax.jit
def rel_err(x: jax.Array, y: jax.Array) -> jax.Array:
    """max |y - sweep(x)| / max |sweep(x)|, in float32."""
    return _rel(y.astype(jnp.float32), sweep(x.astype(jnp.float32)))


@jax.jit
def rel_err_rows(x_rows: jax.Array, y_rows: jax.Array) -> jax.Array:
    """As ``rel_err`` for the interior rows of a block of rows: ``x_rows``
    holds rows ``a .. a+h+1`` of a sweep's input and ``y_rows`` the same
    rows of its output; rows ``a+1 .. a+h`` saw their true neighbours."""
    r = sweep(x_rows.astype(jnp.float32))[1:-1]
    return _rel(y_rows[1:-1].astype(jnp.float32), r)


@jax.jit
def control_sweep(x: jax.Array) -> jax.Array:
    """The control: the reference computed in bfloat16, the nearest
    precision below the configuration's float32, returned in the dtype of
    ``x`` so that it can stand in the program's place."""
    return sweep(x.astype(jnp.bfloat16)).astype(x.dtype)


@jax.jit
def control_rel_err(x: jax.Array) -> jax.Array:
    """The control's reading: ``control_sweep`` read as ``rel_err``."""
    return _rel(control_sweep(x).astype(jnp.float32),
                sweep(x.astype(jnp.float32)))
