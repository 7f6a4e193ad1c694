"""Public entry for the RWKV-6 WKV recurrence."""
from __future__ import annotations

import jax.numpy as jnp

from .kernel import wkv6_pallas
from .ref import wkv6_ref


def wkv6(r, k, v, w, u, use_pallas: bool = True, interpret: bool = False,
         chunk: int = 64):
    """(o, sT) for the RWKV-6 recurrence with zero initial state."""
    if use_pallas and r.shape[1] % chunk == 0:
        return wkv6_pallas(r, k, v, w, u, chunk=chunk, interpret=interpret)
    return wkv6_ref(r, k, v, w, u)
