"""Sweeps of the lattice over the cell's chips under locality-oblivious slab
placement, each feeding the next: ``repro.stencil.jacobi.make_scattered_sweep``,
the lattice cut into ``chips * blocks_per_dev`` slabs of rows, slab g on
chip g % chips, every slab's halo planes all-gathered.  The lattice is kept
in the sweep's device-major row order (``scatter_lattice``), each chip's
slabs one after another, sharded by rows over a mesh of the cell's chips.

The check reads the lattice in its own order: the window's last sweep is
mapped back through ``reassemble_scattered``, and the sampled block of
rows is drawn inside one slab, where device-major rows are lattice rows.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding
from jax.sharding import PartitionSpec as P

from bench import harness as H
from bench import stencil


def setup(ctx):
    from repro.stencil.jacobi import (JacobiGridConfig, make_scattered_sweep,
                                      reassemble_scattered, scatter_lattice)
    cfg, tr = ctx.cell.config, ctx.cell.traffic
    shape = (cfg["ni"], cfg["nj"], cfg["nk"])
    devs = jax.devices()[:ctx.cell.chips]
    n, bpd = len(devs), int(tr["blocks_per_dev"])
    slab = cfg["ni"] // (n * bpd)
    if slab * n * bpd != cfg["ni"] or slab < tr["sample_rows"] + 2:
        raise ValueError(f"{cfg['ni']} rows do not cut into {n * bpd} slabs "
                         f"of at least {tr['sample_rows'] + 2} rows")
    mesh = jax.make_mesh((n,), ("data",), devices=devs,
                         axis_types=(jax.sharding.AxisType.Auto,))
    rows = NamedSharding(mesh, P("data", None, None))
    sweep = jax.jit(make_scattered_sweep(JacobiGridConfig(*shape), bpd),
                    out_shardings=rows)
    to_lattice = jax.jit(lambda x: reassemble_scattered(x, n, bpd),
                         out_shardings=rows)
    c = jnp.float32(1 / 6)

    def step(x):
        with jax.set_mesh(mesh):
            return sweep(x, c)

    x0 = jax.jit(lambda x: scatter_lattice(x, n, bpd), out_shardings=rows)(
        stencil.lattice(shape, ctx.seed))
    return ScatteredCell(ctx, step, x0, to_lattice, n, bpd, slab)


class ScatteredCell(stencil.SweepCell):
    def __init__(self, ctx: H.Context, step, x0, to_lattice, n: int, bpd: int,
                 slab: int):
        super().__init__(ctx, step, x0)
        self.to_lattice = to_lattice
        # the sampled rows, drawn inside one slab of the lattice, at their
        # place in device-major order
        rng = np.random.default_rng([ctx.seed, 1, 1])
        g = int(rng.integers(0, n * bpd))
        a = g * slab + int(rng.integers(0, slab - self.rows_n + 1))
        self.rows_a = (g % n) * bpd * slab + (g // n) * slab + a % slab

    def release(self) -> None:
        """Frees the program and maps the last sweep back to lattice order,
        the order the check and the control read."""
        super().release()
        x, y = self.last
        self.last = (self.to_lattice(x), self.to_lattice(y))
