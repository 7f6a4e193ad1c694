"""Temporally-blocked Jacobi Pallas kernel — the paper's own §4 outlook.

The paper closes with: "Further potentials ... may be found in the
possibility to implement temporal blocking (doing more than one time step
on a block to reduce pressure on the memory subsystem)".  This kernel does
exactly that on the TPU memory hierarchy: TWO Jacobi sweeps per HBM pass.

Each grid cell loads a (di+4, dj+4, nk) extended tile (assembled in VMEM
from the centre block, its 4 edge neighbours and 4 corner neighbours via
clamped index maps + Dirichlet masks), computes sweep 1 on the inner
(di+2, dj+2) region and sweep 2 on the (di, dj) interior, and stores one
output block.  HBM traffic per site stays ~one load + one store while the
FLOPs double — arithmetic intensity 2x, which converts the paper's
memory-bound 8/3 B/flop kernel toward the compute roofline.  Generalizes
to s steps with a 2s-deep halo (VMEM budget: (di+2s)(dj+2s)nk * 4 B).

No global barrier is needed between the two steps — the paper's locality
queues are what make this safe dynamically ("no frequent global barriers
would be required", §4): a block's 2-step update depends only on its
2-halo, which the owning domain already holds.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _sweep_interior(x: jnp.ndarray, c) -> jnp.ndarray:
    """One Jacobi step on the interior (trims one i/j ring; k uses zero
    boundaries — dk == Nk spans the whole lattice)."""
    dtype = x.dtype
    up = x[:-2, 1:-1]
    down = x[2:, 1:-1]
    left = x[1:-1, :-2]
    right = x[1:-1, 2:]
    zcol = jnp.zeros_like(x[1:-1, 1:-1, :1])
    back = jnp.concatenate([zcol, x[1:-1, 1:-1, :-1]], axis=2)
    front = jnp.concatenate([x[1:-1, 1:-1, 1:], zcol], axis=2)
    return (c * (up + down + left + right + back + front)).astype(dtype)


def _temporal_kernel(c_ref, cc, nn, ss, ww, ee, nw, ne, sw, se, out_ref, *,
                     di: int, dj: int, nbi: int, nbj: int):
    bi = pl.program_id(0)
    bj = pl.program_id(1)
    c = c_ref[0]
    nk = cc.shape[2]
    h = 2  # halo depth for 2 steps

    # assemble the (di+4, dj+4, nk) extended tile from the 9 blocks
    left = jnp.concatenate([nw[0][-h:, -h:], ww[0][:, -h:], sw[0][:h, -h:]],
                           axis=0)
    mid = jnp.concatenate([nn[0][-h:, :], cc[0], ss[0][:h, :]], axis=0)
    right = jnp.concatenate([ne[0][-h:, :h], ee[0][:, :h], se[0][:h, :h]],
                            axis=0)
    ext = jnp.concatenate([left, mid, right], axis=1)

    # Dirichlet mask: zero everything outside the global lattice
    gi = bi * di - h + jax.lax.broadcasted_iota(jnp.int32, ext.shape, 0)
    gj = bj * dj - h + jax.lax.broadcasted_iota(jnp.int32, ext.shape, 1)
    inside = (gi >= 0) & (gi < nbi * di) & (gj >= 0) & (gj < nbj * dj)
    ext = jnp.where(inside, ext, jnp.zeros_like(ext))

    t1 = _sweep_interior(ext, c)        # (di+2, dj+2, nk)
    # Dirichlet holds at every time step: re-zero t1 entries that lie
    # outside the global lattice before they feed sweep 2
    gi1 = bi * di - 1 + jax.lax.broadcasted_iota(jnp.int32, t1.shape, 0)
    gj1 = bj * dj - 1 + jax.lax.broadcasted_iota(jnp.int32, t1.shape, 1)
    inside1 = (gi1 >= 0) & (gi1 < nbi * di) & (gj1 >= 0) & (gj1 < nbj * dj)
    t1 = jnp.where(inside1, t1, jnp.zeros_like(t1))
    t2 = _sweep_interior(t1, c)         # (di,   dj,   nk)
    out_ref[0] = t2


@functools.partial(jax.jit, static_argnames=("di", "dj", "interpret"))
def jacobi_two_step_pallas(f: jnp.ndarray, c: jnp.ndarray | float = 1.0 / 6.0,
                           di: int = 10, dj: int = 10,
                           interpret: bool = False) -> jnp.ndarray:
    """TWO Jacobi sweeps in one HBM pass over a (Ni, Nj, Nk) lattice.

    Requires di, dj >= 2 (2-deep halo must fit inside one neighbour block).
    """
    ni, nj, nk = f.shape
    if ni % di or nj % dj:
        raise ValueError(f"lattice {f.shape} not divisible by ({di},{dj})")
    if di < 2 or dj < 2:
        raise ValueError("temporal blocking needs di, dj >= 2")
    nbi, nbj = ni // di, nj // dj

    def clamp(i, n):
        return jnp.clip(i, 0, n - 1)

    block = (1, di, dj, nk)
    f4 = f[None]

    def mk(di_off, dj_off):
        def idx(bi, bj):
            return (0, clamp(bi + di_off, nbi), clamp(bj + dj_off, nbj), 0)
        return pl.BlockSpec(block, idx)

    c_arr = jnp.asarray(c, dtype=f.dtype).reshape(1)
    kern = functools.partial(_temporal_kernel, di=di, dj=dj, nbi=nbi, nbj=nbj)
    out = pl.pallas_call(
        kern,
        grid=(nbi, nbj),
        in_specs=[
            pl.BlockSpec((1,), lambda bi, bj: (0,)),
            mk(0, 0),                     # centre
            mk(-1, 0), mk(1, 0),          # N, S
            mk(0, -1), mk(0, 1),          # W, E
            mk(-1, -1), mk(-1, 1),        # NW, NE
            mk(1, -1), mk(1, 1),          # SW, SE
        ],
        out_specs=mk(0, 0),
        out_shape=jax.ShapeDtypeStruct((1, ni, nj, nk), f.dtype),
        interpret=interpret,
    )(c_arr, f4, f4, f4, f4, f4, f4, f4, f4, f4)
    return out[0]
