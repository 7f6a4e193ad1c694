"""Pallas TPU flash attention (GQA, causal, sliding window).

TPU adaptation notes: the kernel follows the classic FlashAttention-2
online-softmax recurrence, but the blocking is chosen for the MXU/VMEM
rather than for CUDA SMs — q/k blocks are multiples of 128 on the
lane-mapped (head_dim) and sublane (sequence) axes, the (bq x bk) logits
tile feeds the 128x128 systolic array directly, and the running (m, l, acc)
state lives in VMEM scratch that persists across the *sequential* TPU grid
(the innermost grid dimension on TPU iterates in order on one core, so no
atomics/semaphores are needed, unlike the GPU formulation).

Grid: (batch*q_heads, num_q_blocks, num_k_blocks) — k innermost.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1.0e38


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr, *,
                  bq: int, bk: int, nk: int, causal: bool, window: int,
                  q_offset: int, scale: float):
    iq = pl.program_id(1)
    ik = pl.program_id(2)

    @pl.when(ik == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q_pos = q_offset + iq * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
    k_pos = ik * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)

    # skip fully-masked blocks (upper triangle / outside the window)
    def block_needed():
        if not causal:
            return jnp.bool_(True)
        first_q = q_offset + iq * bq
        last_q = first_q + bq - 1
        first_k = ik * bk
        last_k = first_k + bk - 1
        need = first_k <= last_q
        if window > 0:
            need &= last_k > first_q - window
        return need

    @pl.when(block_needed())
    def _compute():
        q = q_ref[0].astype(jnp.float32) * scale        # (bq, hd)
        k = k_ref[0].astype(jnp.float32)                # (bk, hd)
        v = v_ref[0].astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())))  # (bq, bk)
        if causal:
            ok = k_pos <= q_pos
            if window > 0:
                ok &= k_pos > q_pos - window
            s = jnp.where(ok, s, NEG_INF)

        m_prev = m_scr[...]
        m_new = jnp.maximum(m_prev, s.max(axis=1))
        p = jnp.exp(s - m_new[:, None])
        alpha = jnp.exp(m_prev - m_new)
        l_scr[...] = l_scr[...] * alpha + p.sum(axis=1)
        acc_scr[...] = acc_scr[...] * alpha[:, None] + \
            jax.lax.dot_general(p, v, (((1,), (0,)), ((), ())))
        m_scr[...] = m_new

    @pl.when(ik == nk - 1)
    def _finalize():
        l = l_scr[...]
        o_ref[0] = (acc_scr[...] / jnp.maximum(l, 1e-37)[:, None]).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=(
    "causal", "window", "q_offset", "bq", "bk", "interpret"))
def flash_attention(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray, *,
                    causal: bool = True, window: int = 0, q_offset: int = 0,
                    bq: int = 256, bk: int = 256,
                    interpret: bool = False) -> jnp.ndarray:
    """q (B, Hq, Tq, hd); k, v (B, Hkv, Tk, hd) -> (B, Hq, Tq, hd).

    Requires Tq % bq == 0 and Tk % bk == 0 (pad upstream if needed).
    """
    b, hq, tq, hd = q.shape
    hkv, tk = k.shape[1], k.shape[2]
    g = hq // hkv
    if tq % bq or tk % bk:
        raise ValueError(f"seq lens ({tq},{tk}) not divisible by blocks ({bq},{bk})")
    nq, nk = tq // bq, tk // bk
    bh = b * hq

    qr = q.reshape(bh, tq, hd)
    # expand kv heads to q heads via index map (no materialized broadcast)
    kr = k.reshape(b * hkv, tk, hd)
    vr = v.reshape(b * hkv, tk, hd)

    def q_map(h, iq, ik):
        return (h, iq, 0)

    def kv_map(h, iq, ik):
        # h enumerates (batch, q_head); its kv row is batch*hkv + q_head//g
        return ((h // hq) * hkv + (h % hq) // g, ik, 0)

    kern = functools.partial(
        _flash_kernel, bq=bq, bk=bk, nk=nk, causal=causal, window=window,
        q_offset=q_offset, scale=hd ** -0.5)

    out = pl.pallas_call(
        kern,
        grid=(bh, nq, nk),
        in_specs=[
            pl.BlockSpec((1, bq, hd), q_map),
            pl.BlockSpec((1, bk, hd), kv_map),
            pl.BlockSpec((1, bk, hd), kv_map),
        ],
        out_specs=pl.BlockSpec((1, bq, hd), q_map),
        out_shape=jax.ShapeDtypeStruct((bh, tq, hd), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq,), jnp.float32),
            pltpu.VMEM((bq,), jnp.float32),
            pltpu.VMEM((bq, hd), jnp.float32),
        ],
        interpret=interpret,
    )(qr, kr, vr)
    return out.reshape(b, hq, tq, hd)
