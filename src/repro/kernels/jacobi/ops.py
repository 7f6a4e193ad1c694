"""Public entry point for the Jacobi stencil kernel."""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental.layout import Layout

from .kernel import jacobi_sweep_pallas


def stored_order(f) -> tuple[int, ...]:
    """The axes of ``f`` from major to minor, as its device stores them.

    A concrete array reports its own layout.  A tracer, or a
    ``ShapeDtypeStruct`` without a layout, is given the layout its device's
    client would store it in: the device of its sharding, else JAX's first
    device.  The TPU client stores the paper's 2400x600x600 float32 lattice
    as (1, 2, 0), i-minor; the CPU client stores every array row-major.
    """
    layout = getattr(getattr(f, "format", None), "layout", None)
    if isinstance(layout, Layout):
        return tuple(layout.major_to_minor)
    sharding = getattr(f, "sharding", None)
    device = (min(sharding.device_set, key=lambda d: d.id)
              if sharding is not None else jax.devices()[0])
    default = device.client.get_default_layout(jnp.dtype(f.dtype), f.shape,
                                               device)
    return tuple(Layout.from_pjrt_layout(default).major_to_minor)


def jacobi_sweep(f: jnp.ndarray, c: float = 1.0 / 6.0, di: int = 10,
                 dj: int = 8, interpret: bool = False) -> jnp.ndarray:
    """One Jacobi sweep through the Pallas kernel, on ``f`` in the order its
    device stores it.

    The (di, dj) block tiles the two stored-major axes and the stored minor
    axis stays whole on the lanes, so XLA needs no relayout of the lattice
    on either side of the kernel.  The TPU client stores the paper's
    2400x600x600 float32 lattice i-minor, ``{0,2,1:T(8,128)}``, so there
    the default (10, 8) block tiles (j, k) with i whole: 10x8x2432 f32,
    778 kB a block, 9.3 MB for six blocks double-buffered, which the TPU
    compiler accepts.  Tests on the CPU pass ``interpret=True``.
    """
    return jacobi_sweep_pallas(f, c, di=di, dj=dj, interpret=interpret,
                               order=stored_order(f))
