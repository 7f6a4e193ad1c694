"""Pallas TPU kernel for the RWKV-6 WKV chunked recurrence.

TPU adaptation: the (hd x hd) state matrix per (batch, head) is the working
set; it stays resident in VMEM scratch across the sequential time-chunk
grid axis while (r, k, v, w) chunks stream HBM→VMEM.  A naive XLA scan
spills the state to HBM every step (T x hd² bytes of traffic); the kernel's
traffic is the streaming inputs plus one state spill per chunk — the same
insight as the paper's blocked Jacobi (keep the hot working set in the
near memory tier, stream the rest).

The matmul form of chunked linear attention (turning the inner loop into
MXU matmuls with decay-ratio matrices) requires log-space normalization to
avoid exp overflow with data-dependent decay; we keep the exact sequential
inner loop (VPU) and note the matmul variant as a further optimization in
EXPERIMENTS.md §Perf.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _wkv_kernel(r_ref, k_ref, v_ref, w_ref, u_ref, o_ref, s_final_ref,
                s_scr, *, chunk: int, nc: int):
    ic = pl.program_id(1)

    @pl.when(ic == 0)
    def _init():
        s_scr[...] = jnp.zeros_like(s_scr)

    u = u_ref[0]                                       # (hd,)

    def body(i, s):
        rt = r_ref[0, i]
        kt = k_ref[0, i]
        vt = v_ref[0, i]
        wt = w_ref[0, i]
        kv = kt[:, None] * vt[None, :]                 # (hd, hd)
        s_eff = s + u[:, None] * kv
        o_ref[0, i] = jnp.einsum("ij,i->j", s_eff, rt)
        return wt[:, None] * s + kv

    s = jax.lax.fori_loop(0, chunk, body, s_scr[...])
    s_scr[...] = s

    @pl.when(ic == nc - 1)
    def _write_state():
        s_final_ref[0] = s


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def wkv6_pallas(r, k, v, w, u, chunk: int = 64, interpret: bool = False):
    """r,k,v,w: (B, T, H, hd) f32; u: (H, hd). Returns (o, sT).

    Zero initial state (the model folds carried state outside the kernel).
    """
    b, t, h, hd = r.shape
    if t % chunk:
        raise ValueError(f"T={t} not divisible by chunk={chunk}")
    nc = t // chunk
    bh = b * h

    def to_bh(x):
        return x.transpose(0, 2, 1, 3).reshape(bh, t, hd)

    rr, kk, vv, ww = (to_bh(x.astype(jnp.float32)) for x in (r, k, v, w))
    uu = jnp.broadcast_to(u.astype(jnp.float32)[None], (b, h, hd)).reshape(bh, hd)

    def idx(ibh, ic):
        return (ibh, ic, 0)

    def u_idx(ibh, ic):
        return (ibh, 0)

    def s_idx(ibh, ic):
        return (ibh, 0, 0)

    o, s_final = pl.pallas_call(
        functools.partial(_wkv_kernel, chunk=chunk, nc=nc),
        grid=(bh, nc),
        in_specs=[pl.BlockSpec((1, chunk, hd), idx),
                  pl.BlockSpec((1, chunk, hd), idx),
                  pl.BlockSpec((1, chunk, hd), idx),
                  pl.BlockSpec((1, chunk, hd), idx),
                  pl.BlockSpec((1, hd), u_idx)],
        out_specs=[pl.BlockSpec((1, chunk, hd), idx),
                   pl.BlockSpec((1, hd, hd), s_idx)],
        out_shape=[jax.ShapeDtypeStruct((bh, t, hd), jnp.float32),
                   jax.ShapeDtypeStruct((bh, hd, hd), jnp.float32)],
        scratch_shapes=[pltpu.VMEM((hd, hd), jnp.float32)],
        interpret=interpret,
    )(rr, kk, vv, ww, uu)

    o = o.reshape(b, h, t, hd).transpose(0, 2, 1, 3)
    return o, s_final.reshape(b, h, hd, hd)
