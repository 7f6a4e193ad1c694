"""Pallas TPU kernel for the RG-LRU linear recurrence (chunked scan).

TPU adaptation: the recurrence h_t = a_t h_{t-1} + b_t is sequential in t,
but only the (W,)-wide carry crosses chunk boundaries.  The grid iterates
(batch, time-chunks) with the time axis innermost-sequential on TPU, so the
carry lives in a VMEM scratch that persists across chunk steps — the HBM
traffic is exactly one read of (a, b) and one write of h (the memory-bound
optimum), where a naive XLA scan materializes the carry to HBM every step.
Within a chunk a log-depth blocked doubling recurrence would also work; the
simple fori_loop over rows keeps the kernel exact and VPU-friendly since W
(the lane axis) is the wide dimension.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _rglru_kernel(a_ref, b_ref, o_ref, h_scr, *, chunk: int):
    ic = pl.program_id(1)

    @pl.when(ic == 0)
    def _init():
        h_scr[...] = jnp.zeros_like(h_scr)

    def body(i, h):
        h = a_ref[0, i] * h + b_ref[0, i]
        o_ref[0, i] = h
        return h

    h = jax.lax.fori_loop(0, chunk, body, h_scr[...])
    h_scr[...] = h


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def rglru_scan_pallas(a: jnp.ndarray, b: jnp.ndarray, chunk: int = 128,
                      interpret: bool = False) -> jnp.ndarray:
    """a, b: (B, T, W) f32; h0 = 0. Returns h (B, T, W)."""
    bt, t, w = a.shape
    if t % chunk:
        raise ValueError(f"T={t} not divisible by chunk={chunk}")
    nc = t // chunk

    def idx(ib, ic):
        return (ib, ic, 0)

    return pl.pallas_call(
        functools.partial(_rglru_kernel, chunk=chunk),
        grid=(bt, nc),
        in_specs=[pl.BlockSpec((1, chunk, w), idx),
                  pl.BlockSpec((1, chunk, w), idx)],
        out_specs=pl.BlockSpec((1, chunk, w), idx),
        out_shape=jax.ShapeDtypeStruct((bt, t, w), a.dtype),
        scratch_shapes=[pltpu.VMEM((w,), jnp.float32)],
        interpret=interpret,
    )(a, b)
