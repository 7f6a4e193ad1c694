"""Multi-head Latent Attention (MiniCPM3 / DeepSeek-V2 style).

Queries come from a low-rank down/up projection, or (``q_lora_rank`` 0 or
None, DeepSeek-V2-Lite) from one direct projection; keys/values from a
shared compressed latent ``c_kv`` (kv_lora_rank) plus a single shared
rotary key.  The rotary dims rotate in adjacent pairs, DeepSeek-V2's
published layout, at YaRN's frequencies where the config scales rotary
positions; YaRN's temperature then multiplies the softmax scale.
The decode cache stores only (c_kv, k_rope) — (kv_lora + rope_dim) floats
per token instead of 2 * H * hd: for MiniCPM3-4B that is 288 vs 5120 per
token, an ~18x KV-cache reduction, which is the arch's whole point.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from ..configs.base import MLAConfig, ModelConfig
from ..distributed.sharding import shard
from .common import (Params, apply_rope, dense_init, rms_norm, rms_norm_init,
                     rope_freqs, split_keys, yarn_freqs, yarn_mscale)
from .attention import NEG_INF, _causal_mask


def mla_init(key: jax.Array, cfg: ModelConfig, dtype=jnp.float32) -> Params:
    m = cfg.mla
    d, h = cfg.d_model, cfg.num_heads
    qk = m.qk_nope_head_dim + m.qk_rope_head_dim
    ks = split_keys(key, 7)
    if m.q_lora_rank:
        q = {"w_dq": dense_init(ks[0], d, m.q_lora_rank, dtype),
             "q_norm": rms_norm_init(m.q_lora_rank, dtype),
             "w_uq": dense_init(ks[1], m.q_lora_rank, h * qk, dtype)}
    else:
        q = {"w_q": dense_init(ks[0], d, h * qk, dtype)}
    return {
        **q,
        "w_dkv": dense_init(ks[2], d, m.kv_lora_rank, dtype),
        "kv_norm": rms_norm_init(m.kv_lora_rank, dtype),
        "w_uk": dense_init(ks[3], m.kv_lora_rank, h * m.qk_nope_head_dim, dtype),
        "w_uv": dense_init(ks[4], m.kv_lora_rank, h * m.v_head_dim, dtype),
        "w_kr": dense_init(ks[5], d, m.qk_rope_head_dim, dtype),
        "wo": dense_init(ks[6], h * m.v_head_dim, d, dtype),
    }


def rope_and_scale(cfg: ModelConfig):
    """(inverse frequencies of the rotary dims, the factor on the rotated
    dims, the softmax scale)."""
    m, sc = cfg.mla, cfg.rope_scaling
    dr = m.qk_rope_head_dim
    scale = (m.qk_nope_head_dim + dr) ** -0.5
    if sc is None:
        return rope_freqs(dr, cfg.rope_theta), 1.0, scale
    factor = yarn_mscale(sc.factor, sc.mscale) / yarn_mscale(sc.factor, sc.mscale_all_dim)
    if sc.mscale_all_dim:
        scale *= yarn_mscale(sc.factor, sc.mscale_all_dim) ** 2
    return yarn_freqs(dr, cfg.rope_theta, sc), factor, scale


def _absorbed_chunked_local(q_lat, q_rope, ckv, kr, q_offset, scale,
                            q_chunk: int = 512, k_chunk: int = 1024):
    """Online-softmax attention in latent space: scores against (B,S,r)
    ckv/kr, output accumulated as (B,T,H,r).  q_offset may be traced."""
    b, t, h, r = q_lat.shape
    s = ckv.shape[1]
    dr = q_rope.shape[-1]
    nq = -(-t // q_chunk)
    nk = -(-s // k_chunk)
    qp = jnp.pad(q_lat, ((0, 0), (0, nq * q_chunk - t), (0, 0), (0, 0)))
    qr = jnp.pad(q_rope, ((0, 0), (0, nq * q_chunk - t), (0, 0), (0, 0)))
    cp = jnp.pad(ckv, ((0, 0), (0, nk * k_chunk - s), (0, 0)))
    kp = jnp.pad(kr, ((0, 0), (0, nk * k_chunk - s), (0, 0)))
    qs = qp.reshape(b, nq, q_chunk, h, r).transpose(1, 0, 2, 3, 4)
    qrs = qr.reshape(b, nq, q_chunk, h, dr).transpose(1, 0, 2, 3, 4)
    cs = cp.reshape(b, nk, k_chunk, r).transpose(1, 0, 2, 3)
    krs = kp.reshape(b, nk, k_chunk, dr).transpose(1, 0, 2, 3)

    def outer(_, xs):
        ql, qrl, iq = xs
        q_pos = q_offset + iq * q_chunk + jnp.arange(q_chunk)

        def inner(carry, ys):
            mm, ll, acc = carry
            cc, kk, ik = ys
            k_pos = ik * k_chunk + jnp.arange(k_chunk)
            sc = (jnp.einsum("bqhr,bsr->bhqs", ql, cc)
                  + jnp.einsum("bqhd,bsd->bhqs", qrl, kk)
                  ).astype(jnp.float32) * scale
            ok = (k_pos[None, :] <= q_pos[:, None]) & (k_pos[None, :] < s)
            sc = jnp.where(ok[None, None], sc, NEG_INF)
            m_new = jnp.maximum(mm, sc.max(-1))
            pw = jnp.exp(sc - m_new[..., None])
            alpha = jnp.exp(mm - m_new)
            ll = ll * alpha + pw.sum(-1)
            acc = acc * alpha[..., None] + jnp.einsum(
                "bhqs,bsr->bhqr", pw.astype(ql.dtype), cc).astype(jnp.float32)
            return (m_new, ll, acc), None

        m0 = jnp.full((b, h, q_chunk), NEG_INF, jnp.float32)
        l0 = jnp.zeros((b, h, q_chunk), jnp.float32)
        a0 = jnp.zeros((b, h, q_chunk, r), jnp.float32)
        (mm, ll, acc), _ = jax.lax.scan(
            jax.checkpoint(inner,
                           policy=jax.checkpoint_policies.nothing_saveable),
            (m0, l0, a0), (cs, krs, jnp.arange(nk)))
        o = acc / jnp.maximum(ll[..., None], 1e-37)
        return None, o.transpose(0, 2, 1, 3).astype(ql.dtype)  # (b,qc,h,r)

    _, outs = jax.lax.scan(outer, None, (qs, qrs, jnp.arange(nq)))
    out = outs.transpose(1, 0, 2, 3, 4).reshape(b, nq * q_chunk, h, r)
    return out[:, :t]


def _absorbed_chunked(q_lat, q_rope, ckv, kr, q_offset, scale):
    """Sequence-parallel wrapper: shard q's sequence over the model axis
    (shard_map — a scan cannot iterate a sharded axis, see attention.py)."""
    from ..distributed.sharding import current_rules
    rules = current_rules()
    axis = rules.rules.get("seq_q") if rules is not None else None
    b, t, h, r = q_lat.shape
    if isinstance(axis, str):
        n = rules.mesh.shape[axis]
        if n > 1 and t % n == 0 and (t // n) % 512 == 0:
            def local(ql, qr, c, k):
                idx = jax.lax.axis_index(axis)
                off = q_offset + idx * ql.shape[1]
                return _absorbed_chunked_local(ql, qr, c, k, off, scale)
            return jax.shard_map(
                local, mesh=rules.mesh,
                in_specs=(rules.spec("batch", "seq_q", None, None),
                          rules.spec("batch", "seq_q", None, None),
                          rules.spec("batch", None, None),
                          rules.spec("batch", None, None)),
                out_specs=rules.spec("batch", "seq_q", None, None),
                check_vma=False)(q_lat, q_rope, ckv, kr)
    return _absorbed_chunked_local(q_lat, q_rope, ckv, kr, q_offset, scale)


def mla_block(p: Params, x: jnp.ndarray, cfg: ModelConfig, *,
              pos_offset=0, cache: Optional[Params] = None):
    """Returns (out, new_cache). Cache = {"ckv": (B,S,r), "kr": (B,S,dr)}."""
    m = cfg.mla
    h = cfg.num_heads
    b, t, _ = x.shape

    if "w_q" in p:
        q = x @ p["w_q"]
    else:
        q = rms_norm(p["q_norm"], x @ p["w_dq"]) @ p["w_uq"]
    q = q.reshape(b, t, h, m.qk_nope_head_dim + m.qk_rope_head_dim)
    q_nope, q_rope = jnp.split(q, [m.qk_nope_head_dim], axis=-1)

    ckv_new = rms_norm(p["kv_norm"], x @ p["w_dkv"])          # (B,T,r)
    kr_new = x @ p["w_kr"]                                     # (B,T,dr)

    inv_freq, rope_factor, scale = rope_and_scale(cfg)
    positions = jnp.broadcast_to(pos_offset + jnp.arange(t), (b, t))
    q_rope = apply_rope(q_rope, positions, inv_freq, interleaved=True)
    kr_new = apply_rope(kr_new[:, :, None, :], positions, inv_freq,
                        interleaved=True)[:, :, 0]
    if rope_factor != 1.0:
        q_rope, kr_new = q_rope * rope_factor, kr_new * rope_factor

    new_cache = None
    if cache is not None:
        ckv = jax.lax.dynamic_update_slice(
            cache["ckv"], ckv_new.astype(cache["ckv"].dtype), (0, pos_offset, 0))
        kr = jax.lax.dynamic_update_slice(
            cache["kr"], kr_new.astype(cache["kr"].dtype), (0, pos_offset, 0))
        ckv = shard(ckv, "batch", "kv_seq", None)
        kr = shard(kr, "batch", "kv_seq", None)
        new_cache = {"ckv": ckv, "kr": kr}
        s = ckv.shape[1]
    else:
        ckv, kr = ckv_new, kr_new
        s = t

    if t > 2048 and cache is None:
        # TRAINING at long seq: the absorbed form's r=288 contraction costs
        # ~3x the score flops and its backward re-pays it twice more —
        # expansion + sequence-parallel attention is cheaper.
        from .attention import seq_parallel_attention, chunked_attention
        from ..distributed.sharding import current_rules
        q_full = jnp.concatenate([q_nope, q_rope], axis=-1)
        # these paths scale scores by head_dim^-0.5: fold in the rest
        q_full = q_full * jnp.asarray(scale * q_full.shape[-1] ** 0.5, q_full.dtype)
        k_full = jnp.concatenate(
            [(ckv @ p["w_uk"]).reshape(b, s, h, m.qk_nope_head_dim),
             jnp.broadcast_to(kr[:, :, None, :], (b, s, h, m.qk_rope_head_dim))],
            axis=-1)
        v_full = (ckv @ p["w_uv"]).reshape(b, s, h, m.v_head_dim)
        o = seq_parallel_attention(q_full, k_full, v_full,
                                   pos_offset=pos_offset, window=0,
                                   rules=current_rules())
        if o is None:
            q_full = shard(q_full, "batch", "seq_q", None, None)
            o = chunked_attention(q_full, k_full, v_full, pos_offset)
        return o @ p["wo"], new_cache

    if (cache is not None) and (t > 2048 or t == 1):
        # ABSORBED attention: fold W_uk into the query and W_uv
        # out of the value sum, so scores and the output accumulate against
        # the (B,S,r) latent directly — the per-head (B,S,H,*) K/V are never
        # materialized.  This is the arch's whole point at inference (the
        # cache *is* the latent) and the decisive memory win at 32k prefill.
        w_uk = p["w_uk"].reshape(m.kv_lora_rank, h, m.qk_nope_head_dim)
        w_uv = p["w_uv"].reshape(m.kv_lora_rank, h, m.v_head_dim)
        q_lat = jnp.einsum("bthd,rhd->bthr", q_nope, w_uk)
        if cache is not None and t == 1:
            logits = (jnp.einsum("bthr,bsr->bhts", q_lat, ckv)
                      + jnp.einsum("bthd,bsd->bhts", q_rope, kr)
                      ).astype(jnp.float32) * scale
            valid = jnp.arange(s)[None, None, None, :] < (pos_offset + 1)
            logits = jnp.where(valid, logits, NEG_INF)
            w = jax.nn.softmax(logits, axis=-1).astype(x.dtype)
            o_lat = jnp.einsum("bhts,bsr->bthr", w, ckv)
        else:
            q_lat = shard(q_lat, "batch", "seq_q", None, None)
            q_rope_s = shard(q_rope, "batch", "seq_q", None, None)
            o_lat = _absorbed_chunked(q_lat, q_rope_s, ckv, kr, pos_offset,
                                      scale)
        o = jnp.einsum("bthr,rhv->bthv", o_lat, w_uv)
        return o.reshape(b, t, h * m.v_head_dim) @ p["wo"], new_cache

    # reference (expansion) form for short sequences — the oracle the
    # absorbed form is tested against (decode-consistency tests)
    k_nope = (ckv @ p["w_uk"]).reshape(b, s, h, m.qk_nope_head_dim)
    v = (ckv @ p["w_uv"]).reshape(b, s, h, m.v_head_dim)
    logits = (jnp.einsum("bthd,bshd->bhts", q_nope, k_nope)
              + jnp.einsum("bthd,bsd->bhts", q_rope, kr)).astype(jnp.float32)
    logits = logits * scale

    if cache is not None and t == 1:
        valid = jnp.arange(s)[None, None, None, :] < (pos_offset + 1)
        logits = jnp.where(valid, logits, NEG_INF)
    else:
        logits = logits + _causal_mask(t, s, pos_offset)[None, None]

    w = jax.nn.softmax(logits, axis=-1).astype(x.dtype)
    o = jnp.einsum("bhts,bshd->bthd", w, v).reshape(b, t, h * m.v_head_dim)
    return o @ p["wo"], new_cache
