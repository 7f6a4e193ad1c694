"""Public entry for flash attention: kernel on TPU, oracle elsewhere."""
from __future__ import annotations

import jax.numpy as jnp

from .kernel import flash_attention
from .ref import mha_ref


def fused_attention(q, k, v, *, causal: bool = True, window: int = 0,
                    q_offset: int = 0, use_pallas: bool = True,
                    interpret: bool = False) -> jnp.ndarray:
    """(B, Hq, Tq, hd) x (B, Hkv, Tk, hd) -> (B, Hq, Tq, hd)."""
    if use_pallas:
        return flash_attention(q, k, v, causal=causal, window=window,
                               q_offset=q_offset, interpret=interpret)
    return mha_ref(q, k, v, causal=causal, window=window, q_offset=q_offset)
