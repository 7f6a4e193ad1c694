"""Attention: GQA (optional bias / sliding window / cross), train + decode.

Three execution paths, one semantics:
  * direct    — materialized scores; smoke tests, short seqs, decode.
  * chunked   — lax.scan over q- and k-chunks with an online softmax
                (flash-attention at the XLA level); bounds activation
                memory for 32k prefill.  The Pallas flash kernel
                (repro.kernels.flash_attention) is the TPU-optimized
                drop-in with identical semantics.
  * decode    — one query token against a (possibly ring-buffered,
                possibly sequence-sharded) KV cache.

GQA is expressed by reshaping q to (B, T, KV, G, hd) and broadcasting k/v;
KV heads stay replicated across the model axis (they are almost always
fewer than the axis size), q heads or q sequence shard instead — see
distributed/sharding.py.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from ..configs.base import ModelConfig
from ..distributed.sharding import shard
from .common import (Params, apply_rope, dense_init, matmul_lowp, rope_freqs,
                     split_keys)

NEG_INF = -2.0e38


def attn_init(key: jax.Array, cfg: ModelConfig, d_model: Optional[int] = None,
              num_heads: Optional[int] = None, num_kv: Optional[int] = None,
              dtype=jnp.float32) -> Params:
    d = d_model or cfg.d_model
    h = num_heads or cfg.num_heads
    kv = num_kv or cfg.num_kv_heads
    hd = cfg.head_dim
    ks = split_keys(key, 4)
    p: Params = {
        "wq": dense_init(ks[0], d, h * hd, dtype),
        "wk": dense_init(ks[1], d, kv * hd, dtype),
        "wv": dense_init(ks[2], d, kv * hd, dtype),
        "wo": dense_init(ks[3], h * hd, d, dtype),
    }
    if cfg.qkv_bias:
        p["bq"] = jnp.zeros((h * hd,), dtype)
        p["bk"] = jnp.zeros((kv * hd,), dtype)
        p["bv"] = jnp.zeros((kv * hd,), dtype)
    return p


def _project_qkv(p: Params, x: jnp.ndarray, xkv: jnp.ndarray, cfg: ModelConfig,
                 num_heads: int, num_kv: int):
    hd = cfg.head_dim
    q = x @ p["wq"]
    k = xkv @ p["wk"]
    v = xkv @ p["wv"]
    if "bq" in p:
        q, k, v = q + p["bq"], k + p["bk"], v + p["bv"]
    b, t = x.shape[:2]
    tk = xkv.shape[1]
    q = q.reshape(b, t, num_heads, hd)
    k = k.reshape(b, tk, num_kv, hd)
    v = v.reshape(b, tk, num_kv, hd)
    return q, k, v


def _gqa_scores(q: jnp.ndarray, k: jnp.ndarray) -> jnp.ndarray:
    """q (B,Tq,KV,G,hd) x k (B,Tk,KV,hd) -> (B,KV,G,Tq,Tk)."""
    return jnp.einsum("bqkgh,bskh->bkgqs", q, k)


def _gqa_out(w: jnp.ndarray, v: jnp.ndarray) -> jnp.ndarray:
    """w (B,KV,G,Tq,Tk) x v (B,Tk,KV,hd) -> (B,Tq,KV,G,hd)."""
    return jnp.einsum("bkgqs,bskh->bqkgh", w, v)


def _causal_mask(tq: int, tk: int, q_offset, window: int = 0) -> jnp.ndarray:
    """(tq, tk) additive mask. q position = q_offset + row index."""
    qi = q_offset + jnp.arange(tq)[:, None]
    ki = jnp.arange(tk)[None, :]
    ok = ki <= qi
    if window > 0:
        ok &= ki > qi - window
    return jnp.where(ok, 0.0, NEG_INF).astype(jnp.float32)


def direct_attention(q, k, v, mask) -> jnp.ndarray:
    """q (B,Tq,H,hd), k/v (B,Tk,KV,hd), mask (Tq,Tk) or (B,1,1,Tq,Tk)."""
    b, tq, h, hd = q.shape
    kvh = k.shape[2]
    g = h // kvh
    qg = q.reshape(b, tq, kvh, g, hd) * (hd ** -0.5)
    s = _gqa_scores(qg, k).astype(jnp.float32)
    s = s + (mask if mask.ndim > 2 else mask[None, None, None])
    w = jax.nn.softmax(s, axis=-1).astype(q.dtype)
    o = _gqa_out(w, v)
    return o.reshape(b, tq, h * hd)


def chunked_attention(q, k, v, q_offset: int, window: int = 0,
                      q_chunk: int = 512, k_chunk: int = 1024) -> jnp.ndarray:
    """Flash-style online-softmax attention via nested lax.scan.

    Memory is O(q_chunk * k_chunk) per head instead of O(Tq * Tk); this is
    the XLA-level equivalent of the Pallas flash kernel and its oracle.
    v may have a different head dim than q/k (MLA).
    """
    b, tq, h, hd = q.shape
    tk = k.shape[1]
    kvh = k.shape[2]
    hv = v.shape[3]
    g = h // kvh
    nq = -(-tq // q_chunk)
    nk = -(-tk // k_chunk)
    pq = nq * q_chunk - tq
    pk = nk * k_chunk - tk
    qp = jnp.pad(q, ((0, 0), (0, pq), (0, 0), (0, 0)))
    kp = jnp.pad(k, ((0, 0), (0, pk), (0, 0), (0, 0)))
    vp = jnp.pad(v, ((0, 0), (0, pk), (0, 0), (0, 0)))
    qs = qp.reshape(b, nq, q_chunk, kvh, g, hd).transpose(1, 0, 2, 3, 4, 5)
    ks = kp.reshape(b, nk, k_chunk, kvh, hd).transpose(1, 0, 2, 3, 4)
    vs = vp.reshape(b, nk, k_chunk, kvh, hv).transpose(1, 0, 2, 3, 4)
    scale = hd ** -0.5

    def outer(_, qi_and_idx):
        qi, iq = qi_and_idx
        q_pos = q_offset + iq * q_chunk + jnp.arange(q_chunk)

        def inner(carry, ki_vi_idx):
            m, l, acc = carry
            ki, vi, ik = ki_vi_idx
            k_pos = ik * k_chunk + jnp.arange(k_chunk)
            s = jnp.einsum("bqkgh,bskh->bkgqs", qi * scale, ki).astype(jnp.float32)
            ok = k_pos[None, :] <= q_pos[:, None]
            ok &= k_pos[None, :] < tk                     # k padding
            if window > 0:
                ok &= k_pos[None, :] > q_pos[:, None] - window
            s = jnp.where(ok[None, None, None], s, NEG_INF)
            m_new = jnp.maximum(m, s.max(axis=-1))
            p = jnp.exp(s - m_new[..., None])
            alpha = jnp.exp(m - m_new)
            l_new = l * alpha + p.sum(axis=-1)
            acc_new = acc * alpha[..., None] + jnp.einsum(
                "bkgqs,bskh->bkgqh", p.astype(qi.dtype), vi).astype(jnp.float32)
            return (m_new, l_new, acc_new), None

        m0 = jnp.full((b, kvh, g, q_chunk), NEG_INF, jnp.float32)
        l0 = jnp.zeros((b, kvh, g, q_chunk), jnp.float32)
        a0 = jnp.zeros((b, kvh, g, q_chunk, hv), jnp.float32)
        # checkpoint the k-chunk body: backward recomputes the (bq x bk)
        # score tile instead of saving one per chunk pair — this is what
        # makes the scan-based formulation actually flash-like in memory.
        (m, l, acc), _ = jax.lax.scan(
            jax.checkpoint(inner,
                           policy=jax.checkpoint_policies.nothing_saveable),
            (m0, l0, a0), (ks, vs, jnp.arange(nk)))
        o = acc / jnp.maximum(l[..., None], 1e-37)
        # (b,kv,g,qc,hd) -> (b,qc,h*hd)
        o = o.transpose(0, 3, 1, 2, 4).reshape(b, q_chunk, h * hv)
        return None, o.astype(qi.dtype)

    _, outs = jax.lax.scan(outer, None, (qs, jnp.arange(nq)))
    out = outs.transpose(1, 0, 2, 3).reshape(b, nq * q_chunk, h * hv)
    return out[:, :tq]


def banded_attention(q, k, v, q_offset: int, window: int) -> jnp.ndarray:
    """Sliding-window attention computed as a band: each q chunk attends to
    its own and the previous k chunk only (chunk >= window), so compute is
    O(T * window) instead of the O(T^2) full scan — the reason local
    attention layers are sub-quadratic at 32k+ prefill."""
    b, tq, h, hd = q.shape
    kvh = k.shape[2]
    g = h // kvh
    chunk = max(512, window)
    n = -(-tq // chunk)
    pq = n * chunk - tq
    qp = jnp.pad(q, ((0, 0), (0, pq), (0, 0), (0, 0)))
    kp = jnp.pad(k, ((0, 0), (0, pq), (0, 0), (0, 0)))
    vp = jnp.pad(v, ((0, 0), (0, pq), (0, 0), (0, 0)))
    qs = qp.reshape(b, n, chunk, kvh, g, hd).transpose(1, 0, 2, 3, 4, 5)
    ks = kp.reshape(b, n, chunk, kvh, hd).transpose(1, 0, 2, 3, 4)
    vs = vp.reshape(b, n, chunk, kvh, hd).transpose(1, 0, 2, 3, 4)
    k_prev = jnp.concatenate([jnp.zeros_like(ks[:1]), ks[:-1]], axis=0)
    v_prev = jnp.concatenate([jnp.zeros_like(vs[:1]), vs[:-1]], axis=0)
    scale = hd ** -0.5

    def one(carry, xs):
        qi, kb, kpv, vb, vpv, i = xs
        kk = jnp.concatenate([kpv, kb], axis=1)       # (b, 2*chunk, kvh, hd)
        vv = jnp.concatenate([vpv, vb], axis=1)
        q_pos = q_offset + i * chunk + jnp.arange(chunk)
        k_pos = q_offset + (i - 1) * chunk + jnp.arange(2 * chunk)
        s = jnp.einsum("bqkgh,bskh->bkgqs",
                       qi.reshape(b, chunk, kvh, g, hd) * scale,
                       kk).astype(jnp.float32)
        ok = (k_pos[None, :] <= q_pos[:, None]) & \
             (k_pos[None, :] > q_pos[:, None] - window) & \
             (k_pos[None, :] >= q_offset)
        s = jnp.where(ok[None, None, None], s, NEG_INF)
        w = jax.nn.softmax(s, axis=-1).astype(qi.dtype)
        o = jnp.einsum("bkgqs,bskh->bqkgh", w, vv)
        return carry, o.reshape(b, chunk, h * hd)

    one = jax.checkpoint(one, policy=jax.checkpoint_policies.nothing_saveable)
    _, outs = jax.lax.scan(
        one, None,
        (qs.reshape(n, b, chunk, h, hd), ks, k_prev, vs, v_prev,
         jnp.arange(n)))
    out = outs.transpose(1, 0, 2, 3).reshape(b, n * chunk, h * hd)
    return out[:, :tq]


def _windowed_seq_local(q_local, k, v, offset, window: int) -> jnp.ndarray:
    """Local-window attention for one sequence shard: q_local (B,Tl,H,hd)
    holds global positions [offset, offset+Tl); k/v are the full (replicated)
    sequence.  Only rows [offset-window, offset+Tl) of k/v can contribute,
    so slice exactly those (front-padded by `window` to keep the slice
    in-bounds) — compute is O(Tl * (Tl + window)), not O(Tl * S)."""
    b, tl, h, hd = q_local.shape
    kvh = k.shape[2]
    kp = jnp.pad(k, ((0, 0), (window, 0), (0, 0), (0, 0)))
    vp = jnp.pad(v, ((0, 0), (window, 0), (0, 0), (0, 0)))
    ks = jax.lax.dynamic_slice_in_dim(kp, offset, tl + window, axis=1)
    vs = jax.lax.dynamic_slice_in_dim(vp, offset, tl + window, axis=1)
    q_pos = offset + jnp.arange(tl)[:, None]
    k_pos = offset - window + jnp.arange(tl + window)[None, :]
    ok = (k_pos <= q_pos) & (k_pos > q_pos - window) & (k_pos >= 0)
    mask = jnp.where(ok, 0.0, NEG_INF).astype(jnp.float32)
    return direct_attention(q_local, ks, vs, mask)


def seq_parallel_attention(q, k, v, *, pos_offset, window: int,
                           rules) -> Optional[jnp.ndarray]:
    """Sequence-parallel (context-parallel) attention over the model axis.

    Used when q heads cannot shard across the model axis: q's sequence is
    sharded instead, k/v stay replicated (they are small — kv_heads ≤ 2 for
    these archs), and each device computes attention only for its own
    sequence shard inside a shard_map.  This is the piece plain
    jit+constraints cannot express: ``lax.scan`` cannot iterate a sharded
    axis, so without shard_map XLA gathers q and every model-rank computes
    every chunk (16x redundant flops + full-size score traffic).
    Returns None when not applicable (caller falls back).
    """
    if rules is None:
        return None
    axis = rules.rules.get("seq_q")
    if axis is None or not isinstance(axis, str):
        return None
    n = rules.mesh.shape[axis]
    b, tq, h, hd = q.shape
    if n <= 1 or tq % n or (tq // n) % 128:
        return None
    from jax.sharding import PartitionSpec as P
    q_spec = rules.spec("batch", "seq_q", None, None)
    kv_spec = rules.spec("batch", None, "kv_heads", None)
    out_spec = rules.spec("batch", "seq_q", None)

    def local(qk, kk, vv):
        idx = jax.lax.axis_index(axis)
        t_local = qk.shape[1]
        offset = pos_offset + idx * t_local
        if window > 0:
            return _windowed_seq_local(qk, kk, vv, offset, window)
        # q_chunk never larger than the local shard: avoids padding the
        # flash tiles 2x when T/n < 512 (train_4k at 16-way SP)
        return chunked_attention(qk, kk, vv, q_offset=offset,
                                 q_chunk=min(512, t_local))

    return jax.shard_map(local, mesh=rules.mesh,
                         in_specs=(q_spec, kv_spec, kv_spec),
                         out_specs=out_spec, check_vma=False)(q, k, v)


def decode_attention(q, k_cache, v_cache, cache_len, ring: bool = False,
                     window: int = 0) -> jnp.ndarray:
    """One-token decode: q (B,1,H,hd) vs cache (B,S,KV,hd).

    ``cache_len`` = number of tokens already written (including the one for
    this step).  For ring buffers every slot < window is valid once the ring
    has wrapped.  The KV-cache sequence axis may be sharded over the model
    axis ("kv_seq"); XLA lowers the masked softmax with a partial reduction
    + small all-reduce (flash-decode).
    """
    b, _, h, hd = q.shape
    s = k_cache.shape[1]
    kvh = k_cache.shape[2]
    g = h // kvh
    qg = q.reshape(b, 1, kvh, g, hd) * (hd ** -0.5)
    logits = jnp.einsum("bqkgh,bskh->bkgqs", qg, k_cache).astype(jnp.float32)
    slot = jnp.arange(s)[None, None, None, None, :]
    if ring:
        valid = slot < jnp.minimum(cache_len, s)
    else:
        valid = slot < cache_len
    logits = jnp.where(valid, logits, NEG_INF)
    w = jax.nn.softmax(logits, axis=-1).astype(q.dtype)
    o = jnp.einsum("bkgqs,bskh->bqkgh", w, v_cache)
    return o.reshape(b, 1, h * hd)


def attention_block(p: Params, x: jnp.ndarray, cfg: ModelConfig, *,
                    kind: str, pos_offset=0, theta: Optional[float] = None,
                    cache: Optional[Params] = None,
                    cross_x: Optional[jnp.ndarray] = None,
                    causal: bool = True,
                    num_heads: Optional[int] = None,
                    num_kv: Optional[int] = None):
    """Full attention sub-block: project → rope → attend → out-project.

    Returns (out, new_cache).  ``cache=None`` means train/prefill without
    cache retention; a dict cache triggers the decode path when Tq == 1.
    kind: "full" | "local"; cross-attention passes ``cross_x`` (no rope,
    not causal).
    """
    h = num_heads or cfg.num_heads
    kv = num_kv or cfg.num_kv_heads
    hd = cfg.head_dim
    window = cfg.attn_window if kind == "local" else 0
    is_cross = cross_x is not None
    theta = cfg.rope_theta if theta is None else theta

    if is_cross or (cache is not None and "xk" in cache):
        if cross_x is None:
            # decode: cross K/V were cached at prefill
            k, v = cache["xk"], cache["xv"]
            q = x @ p["wq"]
            if "bq" in p:
                q = q + p["bq"]
            q = q.reshape(x.shape[0], x.shape[1], h, hd)
            new_cache = {"xk": k, "xv": v}
        else:
            q, k, v = _project_qkv(p, x, cross_x, cfg, h, kv)
            new_cache = {"xk": k, "xv": v} if cache is not None else None
        b, tq = q.shape[:2]
        if tq == 1:
            out = decode_attention(q, k, v, jnp.asarray(k.shape[1]))
        else:
            mask = jnp.zeros((tq, k.shape[1]), jnp.float32)
            out = direct_attention(q, k, v, mask)
        return matmul_lowp(out, p["wo"]), new_cache

    q, k, v = _project_qkv(p, x, x, cfg, h, kv)
    b, tq = q.shape[:2]
    positions = pos_offset + jnp.arange(tq)
    if theta:
        inv_freq = rope_freqs(q.shape[-1], theta)
        q = apply_rope(q, jnp.broadcast_to(positions, (b, tq)), inv_freq)
        k = apply_rope(k, jnp.broadcast_to(positions, (b, tq)), inv_freq)

    if cache is not None and tq == 1:
        # decode: append to (ring) cache, attend against it
        s_cache = cache["k"].shape[1]
        ring = window > 0 and s_cache <= window
        slot = (pos_offset % s_cache) if ring else pos_offset
        k_cache = jax.lax.dynamic_update_slice(
            cache["k"], k.astype(cache["k"].dtype), (0, slot, 0, 0))
        v_cache = jax.lax.dynamic_update_slice(
            cache["v"], v.astype(cache["v"].dtype), (0, slot, 0, 0))
        k_cache = shard(k_cache, "batch", "kv_seq", "kv_heads", None)
        v_cache = shard(v_cache, "batch", "kv_seq", "kv_heads", None)
        out = decode_attention(q, k_cache, v_cache, pos_offset + 1,
                               ring=ring, window=window)
        new_cache = {"k": k_cache, "v": v_cache}
        return out @ p["wo"], new_cache

    # train / prefill
    q = shard(q, "batch", "seq_q", "heads", None)
    k = shard(k, "batch", None, "kv_heads", None)
    v = shard(v, "batch", None, "kv_heads", None)
    if tq <= 2048:
        mask = _causal_mask(tq, tq, pos_offset, window) if causal else \
            jnp.zeros((tq, tq), jnp.float32)
        out = direct_attention(q, k, v, mask)
    else:
        out = None
        if causal:
            from ..distributed.sharding import current_rules
            out = seq_parallel_attention(q, k, v, pos_offset=pos_offset,
                                         window=window, rules=current_rules())
        if out is None:
            if window > 0 and window <= tq // 2:
                out = banded_attention(q, k, v, pos_offset, window)
            else:
                out = chunked_attention(q, k, v, pos_offset, window)

    new_cache = None
    if cache is not None:
        s_cache = cache["k"].shape[1]
        ring = window > 0 and s_cache <= window
        if ring:
            # place the last s_cache tokens at their ring slots (slot of
            # position p is p % s_cache)
            take = min(tq, s_cache)
            kk = k[:, -take:].astype(cache["k"].dtype)
            vv = v[:, -take:].astype(cache["v"].dtype)
            p0 = pos_offset + tq - take
            kbuf = jnp.zeros_like(cache["k"]).at[:, :take].set(kk)
            vbuf = jnp.zeros_like(cache["v"]).at[:, :take].set(vv)
            shift = p0 % s_cache
            new_cache = {
                "k": jnp.roll(kbuf, shift, axis=1),
                "v": jnp.roll(vbuf, shift, axis=1),
            }
        else:
            new_cache = {
                "k": jax.lax.dynamic_update_slice(
                    cache["k"], k.astype(cache["k"].dtype), (0, pos_offset, 0, 0)),
                "v": jax.lax.dynamic_update_slice(
                    cache["v"], v.astype(cache["v"].dtype), (0, pos_offset, 0, 0)),
            }
    return matmul_lowp(out, p["wo"]), new_cache
