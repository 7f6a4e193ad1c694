"""Pallas TPU kernel for the blocked six-point Jacobi sweep.

TPU adaptation of the paper's hot loop (paper §1.4).  The paper's cache
blocking (600x10x10 blocks sized for L2/L3) becomes VMEM blocking: the grid
is tiled over (i-blocks, j-blocks); the k extent stays whole inside a block
(the paper keeps dk = Nk "to make best use of the hardware prefetching" — on
TPU the analogue is keeping the innermost, lane-mapped dimension long and
contiguous for efficient VREG utilisation).

Halos: Pallas BlockSpecs tile disjointly, so each invocation reads its centre
block plus the four neighbouring blocks (N/S/W/E) of the same array via
shifted, clamped index maps, and assembles the +-1 element shifts in VMEM.
This trades a 5x VMEM read footprint for strictly sequential HBM streams —
the TPU-native equivalent of the paper's "one load + one store per site"
streaming bound, since the five streams are all contiguous and
prefetch-friendly.  Lattice boundaries are Dirichlet-zero, applied by masking
the clamped neighbour contributions.

Block shape: the chip's compiler requires the last two block dims (dj, nk)
to be multiples of (8, 128) or equal to the array's dims, so the paper's
dj = 10 is refused and the default block is (10, 8, nk), the nearest legal
one.  nk = Nk is always legal.  VMEM budget at the paper's grid (block
10x8x600, f32): 6 blocks x 2 buffers x 192 kB = 2.3 MB << 16 MB.  Interpret
mode (``interpret=True``, for CPU tests) accepts any block that divides the
lattice.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _jacobi_kernel(c_ref, center_ref, north_ref, south_ref, west_ref,
                   east_ref, out_ref, *, nbi: int, nbj: int):
    """One (di, dj, nk) output block.

    north/south are the -1/+1 neighbour blocks along i; west/east along j.
    Index maps clamp at the lattice edge; masks zero the out-of-domain
    contributions (Dirichlet).
    """
    bi = pl.program_id(0)
    bj = pl.program_id(1)
    c = c_ref[0]

    centre = center_ref[...]
    di, dj, nk = centre.shape
    dtype = centre.dtype

    # i-direction neighbours: previous row comes from centre shifted, with
    # row 0 filled from the north block's last row (or zero at the edge).
    north_last = north_ref[di - 1, :, :]
    north_last = jnp.where(bi == 0, jnp.zeros_like(north_last), north_last)
    up = jnp.concatenate([north_last[None], centre[:-1]], axis=0)

    south_first = south_ref[0, :, :]
    south_first = jnp.where(bi == nbi - 1, jnp.zeros_like(south_first),
                            south_first)
    down = jnp.concatenate([centre[1:], south_first[None]], axis=0)

    # j-direction neighbours.
    west_last = west_ref[:, dj - 1, :]
    west_last = jnp.where(bj == 0, jnp.zeros_like(west_last), west_last)
    left = jnp.concatenate([west_last[:, None], centre[:, :-1]], axis=1)

    east_first = east_ref[:, 0, :]
    east_first = jnp.where(bj == nbj - 1, jnp.zeros_like(east_first),
                           east_first)
    right = jnp.concatenate([centre[:, 1:], east_first[:, None]], axis=1)

    # k-direction shifts stay inside the block (dk == Nk, paper §1.4).
    zcol = jnp.zeros((di, dj, 1), dtype)
    back = jnp.concatenate([zcol, centre[:, :, :-1]], axis=2)
    front = jnp.concatenate([centre[:, :, 1:], zcol], axis=2)

    out_ref[...] = (c * (up + down + left + right + back + front)).astype(dtype)


@functools.partial(jax.jit, static_argnames=("di", "dj", "interpret"))
def jacobi_sweep_pallas(f: jnp.ndarray, c: jnp.ndarray | float = 1.0 / 6.0,
                        di: int = 10, dj: int = 8,
                        interpret: bool = False) -> jnp.ndarray:
    """One Jacobi sweep over a (Ni, Nj, Nk) lattice with (di, dj, Nk) blocks.

    Runs the compiled kernel; ``interpret=True`` executes the kernel body
    in Python instead (CPU tests).
    """
    ni, nj, nk = f.shape
    if ni % di or nj % dj:
        raise ValueError(f"lattice {f.shape} not divisible by block ({di},{dj})")
    nbi, nbj = ni // di, nj // dj

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

    block = (di, dj, nk)
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
    kern = functools.partial(_jacobi_kernel, nbi=nbi, nbj=nbj)
    return pl.pallas_call(
        kern,
        grid=(nbi, nbj),
        in_specs=in_specs,
        out_specs=pl.BlockSpec(block, centre_map),
        out_shape=jax.ShapeDtypeStruct(f.shape, f.dtype),
        interpret=interpret,
    )(c_arr, f, f, f, f, f)
