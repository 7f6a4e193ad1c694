"""Public entry for the RG-LRU scan."""
from __future__ import annotations

import jax
import jax.numpy as jnp

from .kernel import rglru_scan_pallas
from .ref import rglru_scan_ref


def rglru_scan(a: jnp.ndarray, b: jnp.ndarray, use_pallas: bool = True,
               interpret: bool = False, chunk: int = 128) -> jnp.ndarray:
    if use_pallas and a.shape[1] % chunk == 0:
        return rglru_scan_pallas(a, b, chunk=chunk, interpret=interpret)
    # associative-scan fallback (what the model layer uses on CPU)
    def combine(c1, c2):
        a1, b1 = c1
        a2, b2 = c2
        return a1 * a2, a2 * b1 + b2
    _, h = jax.lax.associative_scan(combine, (a, b), axis=1)
    return h
