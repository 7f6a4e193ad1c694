"""Shared building blocks for the model zoo (pure-pytree, functional).

No flax/haiku — parameters are nested dicts of jnp arrays, layers are pure
functions.  Everything takes an explicit PRNG key at init and is
shape-polymorphic so the same code serves reduced smoke configs and the
full assigned architectures (which are only ever lowered abstractly).
"""
from __future__ import annotations

import math
from typing import Any

import jax
import jax.numpy as jnp

Params = dict[str, Any]


# ---------------------------------------------------------------------------
# initializers
# ---------------------------------------------------------------------------

def dense_init(key: jax.Array, d_in: int, d_out: int, dtype=jnp.float32,
               scale: float | None = None) -> jnp.ndarray:
    """Truncated-normal fan-in init (matches common LM practice)."""
    std = scale if scale is not None else 1.0 / math.sqrt(d_in)
    return (jax.random.truncated_normal(key, -3, 3, (d_in, d_out)) * std).astype(dtype)


def embed_init(key: jax.Array, vocab: int, d: int, dtype=jnp.float32) -> jnp.ndarray:
    return (jax.random.truncated_normal(key, -3, 3, (vocab, d)) * 0.02).astype(dtype)


def split_keys(key: jax.Array, n: int) -> list[jax.Array]:
    return list(jax.random.split(key, n))


# ---------------------------------------------------------------------------
# norms
# ---------------------------------------------------------------------------

def rms_norm_init(d: int, dtype=jnp.float32) -> Params:
    return {"scale": jnp.zeros((d,), dtype)}


def rms_norm(p: Params, x: jnp.ndarray, eps: float = 1e-6) -> jnp.ndarray:
    """RMSNorm with (1 + scale) parameterization (gemma/llama style).

    Statistics in f32, application in the input dtype: keeping the (B,T,D)
    tensor (and hence its cotangent, and hence every cross-shard psum of
    the residual stream) in bf16 halves TP wire traffic vs upcasting x
    wholesale."""
    var = jnp.mean(jnp.square(x.astype(jnp.float32)), axis=-1, keepdims=True)
    mult = (jax.lax.rsqrt(var + eps)
            * (1.0 + p["scale"].astype(jnp.float32))).astype(x.dtype)
    return x * mult


def layer_norm_init(d: int, dtype=jnp.float32) -> Params:
    return {"scale": jnp.ones((d,), dtype), "bias": jnp.zeros((d,), dtype)}


def layer_norm(p: Params, x: jnp.ndarray, eps: float = 1e-5) -> jnp.ndarray:
    xf = x.astype(jnp.float32)
    mu = jnp.mean(xf, axis=-1, keepdims=True)
    var = jnp.var(xf, axis=-1, keepdims=True)
    mult = (jax.lax.rsqrt(var + eps) * p["scale"].astype(jnp.float32))
    return (x - mu.astype(x.dtype)) * mult.astype(x.dtype) + \
        p["bias"].astype(x.dtype)


# ---------------------------------------------------------------------------
# rotary / positional embeddings
# ---------------------------------------------------------------------------

def rope_freqs(head_dim: int, theta: float) -> jnp.ndarray:
    return 1.0 / (theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim))


def yarn_mscale(factor: float, mscale: float) -> float:
    """YaRN's attention temperature factor for a context ``factor`` times
    the original."""
    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


def yarn_correction_range(dim: int, theta: float, original_len: int,
                          beta_fast: float, beta_slow: float) -> tuple[int, int]:
    """The frequency indices between which YaRN blends: below ``low`` a
    frequency turns more than ``beta_fast`` times over the original
    context and is kept; from ``high`` on it is interpolated."""
    def index(rotations):
        return (dim * math.log(original_len / (rotations * 2 * math.pi))
                / (2 * math.log(theta)))
    low = math.floor(index(beta_fast))
    high = math.ceil(index(beta_slow))
    return max(low, 0), min(high, dim - 1)


def yarn_freqs(dim: int, theta: float, scaling) -> jnp.ndarray:
    """YaRN's inverse frequencies (``scaling``: a ``RopeScaling``): the
    plain ones below the correction range, the plain ones over
    ``scaling.factor`` above it, and a linear ramp between."""
    plain = rope_freqs(dim, theta)
    low, high = yarn_correction_range(
        dim, theta, scaling.original_max_position_embeddings,
        scaling.beta_fast, scaling.beta_slow)
    high = high if high > low else low + 0.001
    ramp = jnp.clip((jnp.arange(dim // 2, dtype=jnp.float32) - low)
                    / (high - low), 0.0, 1.0)
    return plain / scaling.factor * ramp + plain * (1.0 - ramp)


def apply_rope(x: jnp.ndarray, positions: jnp.ndarray, inv_freq: jnp.ndarray,
               interleaved: bool = False) -> jnp.ndarray:
    """x: (..., T, H, hd); positions: broadcastable to (..., T); inv_freq:
    (hd/2,).  Dims i and i + hd/2 rotate together by ``inv_freq[i]``, or
    with ``interleaved`` dims 2i and 2i+1 (DeepSeek-V2's published
    layout)."""
    ang = positions[..., None].astype(jnp.float32) * inv_freq   # (..., T, hd/2)
    cos = jnp.cos(ang)[..., None, :]                    # (..., T, 1, hd/2)
    sin = jnp.sin(ang)[..., None, :]
    xf = x.astype(jnp.float32)
    if interleaved:
        x1, x2 = xf[..., 0::2], xf[..., 1::2]
        out = jnp.stack([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                        axis=-1).reshape(x.shape)
    else:
        x1, x2 = jnp.split(xf, 2, axis=-1)
        out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


def sinusoidal_positions(n: int, d: int) -> jnp.ndarray:
    """Whisper-style sinusoidal embeddings (n, d)."""
    log_timescale = math.log(10000) / (d // 2 - 1)
    inv = jnp.exp(-log_timescale * jnp.arange(d // 2, dtype=jnp.float32))
    t = jnp.arange(n, dtype=jnp.float32)[:, None] * inv[None, :]
    return jnp.concatenate([jnp.sin(t), jnp.cos(t)], axis=1)


# ---------------------------------------------------------------------------
# activations / losses
# ---------------------------------------------------------------------------

def matmul_lowp(a: jnp.ndarray, b: jnp.ndarray) -> jnp.ndarray:
    """Row-parallel projection matmul with low-precision partials.

    When the contraction dim is sharded, XLA keeps each shard's partial dot
    in f32 and all-reduces f32 — doubling the wire bytes of every TP
    projection.  Requesting a bf16 result dtype makes the partials (and the
    all-reduce) bf16; the MXU still accumulates each local dot in f32
    internally, so only the ≤16-way cross-shard addition runs in bf16.
    """
    if a.dtype == jnp.bfloat16 and b.dtype == jnp.bfloat16:
        return jnp.matmul(a, b, preferred_element_type=jnp.bfloat16)
    return a @ b


def act_fn(name: str):
    return {
        "silu": jax.nn.silu,
        "gelu": lambda x: jax.nn.gelu(x, approximate=True),
        "gelu_exact": lambda x: jax.nn.gelu(x, approximate=False),
        "relu2": lambda x: jnp.square(jax.nn.relu(x)),
    }[name]


def cross_entropy(logits: jnp.ndarray, labels: jnp.ndarray,
                  mask: jnp.ndarray | None = None) -> jnp.ndarray:
    """Mean token cross-entropy; logits (..., V) in any float dtype.

    Written so XLA's SPMD partitioner never gathers the (possibly
    vocab-sharded) logits: the max / sum-exp / gold-pick are all plain
    reductions over the vocab axis, which lower to local partials plus a
    tiny (B, S)-sized all-reduce — the vocab-parallel cross-entropy of
    Megatron, in SPMD-native form.
    """
    logits = logits.astype(jnp.float32)
    v = logits.shape[-1]
    m = jax.lax.stop_gradient(jnp.max(logits, axis=-1, keepdims=True))
    shifted = logits - m
    z = jnp.sum(jnp.exp(shifted), axis=-1)                      # psum
    vocab_iota = jax.lax.broadcasted_iota(jnp.int32, logits.shape,
                                          logits.ndim - 1)
    gold_shifted = jnp.sum(
        jnp.where(vocab_iota == labels[..., None], shifted, 0.0), axis=-1)
    nll = jnp.log(z) - gold_shifted
    if mask is not None:
        return jnp.sum(nll * mask) / jnp.maximum(jnp.sum(mask), 1.0)
    return jnp.mean(nll)
