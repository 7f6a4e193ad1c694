"""Pallas TPU kernel for the blocked six-point Jacobi sweep.

TPU adaptation of the paper's hot loop (paper §1.4).  The paper's cache
blocking (600x10x10 blocks sized for L2/L3) becomes VMEM blocking over the
lattice as the device stores it: the grid tiles the two stored-major axes
with (di, dj) blocks, and the stored minor axis stays whole inside a block,
on the lanes.  That is the paper's dk = Nk rule ("to make best use of the
hardware prefetching") applied to the axis the hardware streams.

Which axis is minor is read from the lattice, not chosen here.  The TPU
client stores the paper's 2400x600x600 float32 lattice i-minor,
``f32[2400,600,600]{0,2,1:T(8,128)}`` (2400 pads to 2432 lanes, where 600
would pad to 640), so the sweep runs on it as (j, k, i): the block is
(di, dj, 2400) over (j, k), and the transposes on either side of the kernel
are bitcasts.  A kernel pinned to (i, j, k) order instead makes XLA relayout
the whole lattice into and out of the kernel each sweep.  On the CPU, and
on the chip for a lattice whose last axis is lane-aligned, the stored order
is row-major and the permutation is the identity.

Halos: Pallas BlockSpecs tile disjointly, so each invocation reads its centre
block plus the four neighbouring blocks (N/S along stored axis 0, W/E along
stored axis 1) of the same array via shifted, clamped index maps, and
assembles the +-1 element shifts in VMEM.  This trades a 5x VMEM read
footprint for strictly sequential HBM streams, the TPU-native equivalent of
the paper's "one load + one store per site" streaming bound.  Lattice
boundaries are Dirichlet-zero, applied by masking the clamped neighbour
contributions.  The six neighbour terms are summed in logical (i, j, k)
order whatever the stored order, so every order gives the same bits.

Block shape: the chip's compiler requires the last two block dims (dj and
the minor extent) to be multiples of (8, 128) or equal to the array's dims,
so the paper's 10 is refused for dj and the default block is (10, 8), the
nearest legal one; the whole minor extent is always legal.  VMEM budget at
the paper's grid (block 10x8x2400 f32, lane-padded to 2432): 778 kB a
block, 6 blocks x 2 buffers = 9.3 MB < 16 MB.  Interpret mode
(``interpret=True``, for CPU tests) accepts any block that divides the
stored lattice.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _jacobi_kernel(c_ref, center_ref, north_ref, south_ref, west_ref,
                   east_ref, out_ref, *, nbi: int, nbj: int,
                   order: tuple[int, int, int]):
    """One (di, dj, n) output block of the lattice in stored order.

    north/south are the -1/+1 neighbour blocks along stored axis 0;
    west/east along stored axis 1.  Index maps clamp at the lattice edge;
    masks zero the out-of-domain contributions (Dirichlet).  Stored axis s
    is logical axis ``order[s]``.
    """
    bi = pl.program_id(0)
    bj = pl.program_id(1)
    c = c_ref[0]

    centre = center_ref[...]
    di, dj, n = centre.shape
    dtype = centre.dtype

    # stored axis 0: previous row comes from centre shifted, with row 0
    # filled from the north block's last row (or zero at the edge).
    north_last = north_ref[di - 1, :, :]
    north_last = jnp.where(bi == 0, jnp.zeros_like(north_last), north_last)
    up = jnp.concatenate([north_last[None], centre[:-1]], axis=0)

    south_first = south_ref[0, :, :]
    south_first = jnp.where(bi == nbi - 1, jnp.zeros_like(south_first),
                            south_first)
    down = jnp.concatenate([centre[1:], south_first[None]], axis=0)

    # stored axis 1.
    west_last = west_ref[:, dj - 1, :]
    west_last = jnp.where(bj == 0, jnp.zeros_like(west_last), west_last)
    left = jnp.concatenate([west_last[:, None], centre[:, :-1]], axis=1)

    east_first = east_ref[:, 0, :]
    east_first = jnp.where(bj == nbj - 1, jnp.zeros_like(east_first),
                           east_first)
    right = jnp.concatenate([centre[:, 1:], east_first[:, None]], axis=1)

    # stored axis 2, the minor one: shifts stay inside the block (its
    # extent is whole, paper §1.4).
    zcol = jnp.zeros((di, dj, 1), dtype)
    back = jnp.concatenate([zcol, centre[:, :, :-1]], axis=2)
    front = jnp.concatenate([centre[:, :, 1:], zcol], axis=2)

    # the (-1, +1) pair of each logical axis, added in (i, j, k) order
    by_axis = dict(zip(order, ((up, down), (left, right), (back, front))))
    (im, ip), (jm, jp), (km, kp) = (by_axis[a] for a in range(3))
    out_ref[...] = (c * (im + ip + jm + jp + km + kp)).astype(dtype)


@functools.partial(jax.jit,
                   static_argnames=("di", "dj", "interpret", "order"))
def jacobi_sweep_pallas(f: jnp.ndarray, c: jnp.ndarray | float = 1.0 / 6.0,
                        di: int = 10, dj: int = 8, interpret: bool = False,
                        order: tuple[int, int, int] = (0, 1, 2)
                        ) -> jnp.ndarray:
    """One Jacobi sweep over a (Ni, Nj, Nk) lattice stored in ``order``.

    ``order`` lists the lattice's axes from major to minor as the device
    stores them (``ops.stored_order``).  The kernel runs on the lattice
    transposed to that order, with (di, dj) blocks over its first two axes
    and its last axis whole, and the result is transposed back; where
    ``order`` is the stored order both transposes are bitcasts.  Runs the
    compiled kernel; ``interpret=True`` executes the kernel body in Python
    instead (CPU tests).
    """
    g = jnp.transpose(f, order)
    n0, n1, n2 = g.shape
    if n0 % di or n1 % dj:
        raise ValueError(f"lattice {f.shape}, stored as {g.shape}, not "
                         f"divisible by block ({di},{dj})")
    nbi, nbj = n0 // di, n1 // dj

    def centre_map(bi, bj):
        return (bi, bj, 0)

    def north_map(bi, bj):
        return (jnp.maximum(bi - 1, 0), bj, 0)

    def south_map(bi, bj):
        return (jnp.minimum(bi + 1, nbi - 1), bj, 0)

    def west_map(bi, bj):
        return (bi, jnp.maximum(bj - 1, 0), 0)

    def east_map(bi, bj):
        return (bi, jnp.minimum(bj + 1, nbj - 1), 0)

    block = (di, dj, n2)
    # scalar c as a (1,) operand broadcast to every grid cell
    c_arr = jnp.asarray(c, dtype=f.dtype).reshape(1)
    in_specs = [
        pl.BlockSpec((1,), lambda bi, bj: (0,)),
        pl.BlockSpec(block, centre_map),
        pl.BlockSpec(block, north_map),
        pl.BlockSpec(block, south_map),
        pl.BlockSpec(block, west_map),
        pl.BlockSpec(block, east_map),
    ]
    kern = functools.partial(_jacobi_kernel, nbi=nbi, nbj=nbj, order=order)
    out = pl.pallas_call(
        kern,
        grid=(nbi, nbj),
        in_specs=in_specs,
        out_specs=pl.BlockSpec(block, centre_map),
        out_shape=jax.ShapeDtypeStruct(g.shape, g.dtype),
        interpret=interpret,
    )(c_arr, g, g, g, g, g)
    return jnp.transpose(out, tuple(order.index(a) for a in range(3)))
