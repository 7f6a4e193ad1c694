"""Sweeps through the Pallas kernel, ``repro.kernels.jacobi.ops.jacobi_sweep``,
each feeding the next, on one chip."""
from __future__ import annotations

from bench import stencil


def setup(ctx):
    from repro.kernels.jacobi.ops import jacobi_sweep
    cfg = ctx.cell.config
    di, dj = cfg["kernel_block"]

    def step(x):
        return jacobi_sweep(x, di=di, dj=dj, interpret=ctx.interpret)

    x0 = stencil.lattice((cfg["ni"], cfg["nj"], cfg["nk"]), ctx.seed)
    return stencil.SweepCell(ctx, step, x0)
