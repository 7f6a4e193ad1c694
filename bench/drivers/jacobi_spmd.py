"""Sweeps of the lattice over the cell's chips, each feeding the next:
``repro.stencil.jacobi.make_contiguous_sweep``, each chip owning one
contiguous block of rows and trading its two halo planes with its
neighbours by ``ppermute``, jitted with its output sharded by rows over a
mesh of the cell's chips (the mesh of ``chip_smoke.four_chips``)."""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding
from jax.sharding import PartitionSpec as P

from bench import stencil


def setup(ctx):
    from repro.stencil.jacobi import JacobiGridConfig, make_contiguous_sweep
    cfg = ctx.cell.config
    shape = (cfg["ni"], cfg["nj"], cfg["nk"])
    devs = jax.devices()[:ctx.cell.chips]
    mesh = jax.make_mesh((len(devs),), ("data",), devices=devs,
                         axis_types=(jax.sharding.AxisType.Auto,))
    rows = NamedSharding(mesh, P("data", None, None))
    sweep = jax.jit(make_contiguous_sweep(JacobiGridConfig(*shape)),
                    out_shardings=rows)
    c = jnp.float32(1 / 6)

    def step(x):
        with jax.set_mesh(mesh):
            return sweep(x, c)

    x0 = jax.device_put(stencil.lattice(shape, ctx.seed), rows)
    return stencil.SweepCell(ctx, step, x0)
