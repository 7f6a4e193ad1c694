"""Plain reference for DeepSeek-V2-Lite (arXiv:2405.04434) on one chip's
expert-parallel share, and the weights from a seed.

DeepSeek-V2-Lite as published (``config.json`` of
huggingface.co/deepseek-ai/DeepSeek-V2-Lite): token embedding; per layer a
pre-norm residual block of latent attention (MLA) and a feed-forward part,
each behind an RMSNorm; a final RMSNorm; logits by an untied head.

- MLA with no query low-rank: ``q = x W_q`` (heads of ``qk_nope_head_dim``
  + ``qk_rope_head_dim``); the latent ``c = RMSNorm(x W_dkv)`` of
  ``kv_lora_rank`` and one shared rotary key ``x W_kr``; per-head keys
  ``c W_uk`` and values ``c W_uv``; causal softmax attention in the
  expanded form (no absorption), scale ``(dn + dr)^-0.5 * mscale^2``;
  output ``o W_o``.  Rotary positions on the rotary dims, adjacent pairs
  (2i, 2i+1) rotating together, at YaRN's frequencies (``rope_scaling``).
- Layers below ``first_k_dense_replace``: a SwiGLU of
  ``intermediate_size``.
- The others: softmax routing over all ``published.n_routed_experts``
  experts, greedy top ``num_experts_per_tok``, the weights renormalised
  only under ``norm_topk_prob`` and multiplied by
  ``routed_scaling_factor``; the output is the share of the experts held
  here (``n_routed_experts`` of them from ``expert_offset``), each over
  the tokens weighted by its gate, zero where it was not chosen, plus the
  shared experts, one SwiGLU of ``n_shared_experts *
  moe_intermediate_size``, once.

Departures from the published description, each also the program's:
the experts and the vocabulary are this chip's share (the configuration's
``reduced``), so the absent experts' part of each MoE layer is left out and
the logits are over the vocabulary slice; an RMSNorm weight is stored as
``scale`` and applied as ``1 + scale``; the published joint projections
``kv_a_proj_with_mqa`` and ``kv_b_proj`` are stored split, as ``W_dkv`` /
``W_kr`` and ``W_uk`` / ``W_uv``.

It imports nothing of the program.  Every matmul runs in float32 at the
``highest`` precision, except in the control, which quantizes both operands
of every projection, router and head matmul to float8 (e4m3, one scale per
tensor).  Attention runs in blocks of queries and each layer's weights are
upcast inside the scan over layers, so a check at thousands of positions
holds one layer's float32 weights and one block's scores at a time.

``make_params`` makes the weights from the seed on the device in one call,
laid out as the program's parameter tree, rounded to the served bfloat16
(the router is kept as float32 values of bfloat16 numbers, as the program
stores it), so the reference computes the model that is served.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST
Q_BLOCK = 512


def dims(sizes: dict) -> dict:
    """The sizes the reference reads, from the configuration file."""
    y = sizes["rope_scaling"]
    return dict(
        d=sizes["hidden_size"], f=sizes["intermediate_size"],
        fe=sizes["moe_intermediate_size"], v=sizes["vocab_size"],
        layers=sizes["num_hidden_layers"], dense=sizes["first_k_dense_replace"],
        h=sizes["num_attention_heads"], r=sizes["kv_lora_rank"],
        dn=sizes["qk_nope_head_dim"], dr=sizes["qk_rope_head_dim"],
        dv=sizes["v_head_dim"], e=sizes["published"]["n_routed_experts"],
        held=sizes["n_routed_experts"], offset=sizes["expert_offset"],
        k=sizes["num_experts_per_tok"], shared=sizes["n_shared_experts"],
        norm_topk=bool(sizes["norm_topk_prob"]),
        routed_scale=float(sizes["routed_scaling_factor"]),
        theta=float(sizes["rope_theta"]), eps=float(sizes["rms_norm_eps"]),
        y_factor=float(y["factor"]), y_orig=int(y["original_max_position_embeddings"]),
        y_fast=float(y["beta_fast"]), y_slow=float(y["beta_slow"]),
        y_mscale=float(y["mscale"]), y_mscale_all=float(y["mscale_all_dim"]))


def _key(sizes: dict) -> tuple:
    return tuple(sorted(dims(sizes).items()))


@functools.partial(jax.jit, static_argnums=(0,))
def _make(sz: tuple, key):
    s = dict(sz)
    d, h, r, dn, dr, dv = s["d"], s["h"], s["r"], s["dn"], s["dr"], s["dv"]
    fe, n_moe = s["fe"], s["layers"] - s["dense"]
    ks = iter(jax.random.split(key, 32))

    def w(shape, std, dtype=jnp.bfloat16):
        x = (jax.random.normal(next(ks), shape, jnp.float32) * std)
        return x.astype(jnp.bfloat16).astype(dtype)

    def attn(n):
        return {"w_q": w(n + (d, h * (dn + dr)), d ** -0.5),
                "w_dkv": w(n + (d, r), d ** -0.5),
                "kv_norm": {"scale": w(n + (r,), 0.1)},
                "w_uk": w(n + (r, h * dn), r ** -0.5),
                "w_uv": w(n + (r, h * dv), r ** -0.5),
                "w_kr": w(n + (d, dr), d ** -0.5),
                "wo": w(n + (h * dv, d), (h * dv) ** -0.5)}

    def swiglu(n, f):
        return {"w_up": w(n + (d, f), d ** -0.5), "w_gate": w(n + (d, f), d ** -0.5),
                "w_down": w(n + (f, d), f ** -0.5)}

    def layer(n, ffn):
        return {"ln1": {"scale": w(n + (d,), 0.1)}, "attn": attn(n),
                "ln2": {"scale": w(n + (d,), 0.1)}, **ffn}

    lead = [layer((), {"mlp": swiglu((), s["f"])}) for _ in range(s["dense"])]
    n = (n_moe,)
    moe = {"router": w(n + (d, s["e"]), d ** -0.5, jnp.float32),
           "w_gate": w(n + (s["held"], d, fe), d ** -0.5),
           "w_up": w(n + (s["held"], d, fe), d ** -0.5),
           "w_down": w(n + (s["held"], fe, d), fe ** -0.5)}
    if s["shared"]:
        moe["shared"] = swiglu(n, s["shared"] * fe)
    stack = {"groups": [layer(n, {"moe": moe})], "remainder": []}
    if lead:
        stack["lead"] = lead
    return {"tok": w((s["v"], d), 0.02), "head": w((d, s["v"]), d ** -0.5),
            "final_norm": {"scale": w((d,), 0.1)}, "stack": stack}


def make_params(sizes: dict, key, dtype=jnp.bfloat16):
    """The weights for ``key``: the served weights (bfloat16, the router
    float32), or with ``dtype=float32`` the same values as float32."""
    p = _make(_key(sizes), key)
    return p if dtype == jnp.bfloat16 else jax.tree.map(
        lambda a: a.astype(dtype), p)


def _mm(a, b):
    return jnp.matmul(a, b, precision=HIGHEST)


def _fp8(x):
    s = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / 448.0
    return (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


def _mm_fp8(a, b):
    return _mm(_fp8(a), _fp8(b))


def _rms(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * (1.0 + scale)


def yarn_mscale(factor: float, mscale: float) -> float:
    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


def yarn_inv_freq(s: dict):
    """YaRN's inverse frequencies of the rotary dims: DeepSeek-V2's
    ``DeepseekV2YarnRotaryEmbedding``."""
    dim, base = s["dr"], s["theta"]

    def correction(rotations):
        return dim * math.log(s["y_orig"] / (rotations * 2 * math.pi)) / (2 * math.log(base))

    low = max(math.floor(correction(s["y_fast"])), 0)
    high = min(math.ceil(correction(s["y_slow"])), dim - 1)
    if low == high:
        high += 0.001
    extra = 1.0 / base ** (jnp.arange(0, dim, 2, dtype=jnp.float32) / dim)
    inter = extra / s["y_factor"]
    ramp = jnp.clip((jnp.arange(dim // 2, dtype=jnp.float32) - low) / (high - low), 0, 1)
    return inter * ramp + extra * (1 - ramp)


def softmax_scale(s: dict) -> float:
    scale = (s["dn"] + s["dr"]) ** -0.5
    if s["y_mscale_all"]:
        scale *= yarn_mscale(s["y_factor"], s["y_mscale_all"]) ** 2
    return scale


def _rope(x, pos, s):
    """x: (T, H, dr); adjacent pairs rotate together."""
    ang = pos[:, None].astype(jnp.float32) * yarn_inv_freq(s)       # (T, dr/2)
    cos, sin = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
    x1, x2 = x[..., 0::2], x[..., 1::2]
    out = jnp.stack([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1).reshape(x.shape)
    return out * (yarn_mscale(s["y_factor"], s["y_mscale"])
                  / yarn_mscale(s["y_factor"], s["y_mscale_all"]))


def _attention(x, a, s, mm):
    t, h, dn, dr = x.shape[0], s["h"], s["dn"], s["dr"]
    pos = jnp.arange(t)
    q = mm(x, a["w_q"]).reshape(t, h, dn + dr)
    c = _rms(mm(x, a["w_dkv"]), a["kv_norm"]["scale"], s["eps"])
    k_pe = _rope(mm(x, a["w_kr"])[:, None], pos, s)                  # (T, 1, dr)
    q = jnp.concatenate([q[..., :dn], _rope(q[..., dn:], pos, s)], -1)
    k = jnp.concatenate([mm(c, a["w_uk"]).reshape(t, h, dn),
                         jnp.broadcast_to(k_pe, (t, h, dr))], -1)
    v = mm(c, a["w_uv"]).reshape(t, h, s["dv"])
    nb = -(-t // Q_BLOCK)
    qb = jnp.pad(q, ((0, nb * Q_BLOCK - t), (0, 0), (0, 0)))
    scale = softmax_scale(s)

    def block(i):
        qi = jax.lax.dynamic_slice_in_dim(qb, i * Q_BLOCK, Q_BLOCK)
        sc = jnp.einsum("qhd,khd->hqk", qi, k, precision=HIGHEST) * scale
        causal = pos[None, :] <= (i * Q_BLOCK + jnp.arange(Q_BLOCK))[:, None]
        w = jax.nn.softmax(jnp.where(causal, sc, -jnp.inf), axis=-1)
        return jnp.einsum("hqk,khd->qhd", w, v, precision=HIGHEST)

    o = jax.lax.map(block, jnp.arange(nb)).reshape(nb * Q_BLOCK, -1)[:t]
    return mm(o, a["wo"])


def _swiglu(x, p, mm):
    return mm(jax.nn.silu(mm(x, p["w_gate"])) * mm(x, p["w_up"]), p["w_down"])


def experts(x, m, s: dict, mm=_mm):
    """One MoE layer's output over tokens ``x`` (T, d): the held experts'
    share and the shared experts."""
    gates = jax.nn.softmax(mm(x, m["router"]), axis=-1)             # (T, E)
    topv, topi = jax.lax.top_k(gates, s["k"])
    if s["norm_topk"]:
        topv = topv / (topv.sum(-1, keepdims=True) + 1e-20)
    topv = topv * s["routed_scale"]
    held = s["offset"] + jnp.arange(s["held"])
    w = jnp.sum(jnp.where(topi[..., None] == held, topv[..., None], 0.0), 1)
    up = jnp.stack([mm(x, m["w_up"][j]) for j in range(s["held"])], 1)
    gate = jnp.stack([mm(x, m["w_gate"][j]) for j in range(s["held"])], 1)
    hid = jax.nn.silu(gate) * up * w[..., None]                     # (T, held, fe)
    out = sum(mm(hid[:, j], m["w_down"][j]) for j in range(s["held"]))
    if "shared" in m:
        out = out + _swiglu(x, m["shared"], mm)
    return out


def _block(x, p, s, mm):
    p = jax.tree.map(lambda a: a.astype(jnp.float32), p)
    x = x + _attention(_rms(x, p["ln1"]["scale"], s["eps"]), p["attn"], s, mm)
    y = _rms(x, p["ln2"]["scale"], s["eps"])
    return x + (experts(y, p["moe"], s, mm) if "moe" in p else _swiglu(y, p["mlp"], mm))


def hidden(params, tokens, s: dict, mm=_mm):
    """Final-normed hidden states (T, d) of one sequence."""
    x = params["tok"][tokens].astype(jnp.float32)
    for p in params["stack"].get("lead", []):
        x = _block(x, p, s, mm)
    x, _ = jax.lax.scan(lambda x, p: (_block(x, p, s, mm), None), x,
                        params["stack"]["groups"][0])
    return _rms(x, params["final_norm"]["scale"].astype(jnp.float32), s["eps"])


def _logits(params, tokens, at, s, mm=_mm):
    return mm(hidden(params, tokens, s, mm)[at], params["head"].astype(jnp.float32))


@functools.partial(jax.jit, static_argnums=(4,))
def _gaps(params, tokens, at, target, sz: tuple):
    logits = _logits(params, tokens, at, dict(sz))
    picked = jnp.take_along_axis(logits, target[:, None], axis=1)[:, 0]
    return jnp.max(logits, axis=1) - picked


@functools.partial(jax.jit, static_argnums=(3,))
def _control_gaps(params, tokens, at, sz: tuple):
    s = dict(sz)
    logits = _logits(params, tokens, at, s)
    first = jnp.argmax(_logits(params, tokens, at, s, _mm_fp8), axis=1)
    picked = jnp.take_along_axis(logits, first[:, None], axis=1)[:, 0]
    return jnp.max(logits, axis=1) - picked


@functools.partial(jax.jit, static_argnums=(2,))
def _full_logits(params, tokens, sz: tuple):
    return _logits(params, tokens, jnp.arange(tokens.shape[0]), dict(sz))


def gaps(params, sizes: dict, tokens, at, target):
    """At each position ``at``, how far the reference's logit of
    ``target`` (the token served after that position) lies below its
    best."""
    return _gaps(params, tokens, at, target, _key(sizes))


def control_gaps(params, sizes: dict, tokens, at):
    """The control: at each position ``at``, how far the reference's logit
    of the token that the float8 model puts first lies below its best."""
    return _control_gaps(params, tokens, at, _key(sizes))


def logits(params, sizes: dict, tokens):
    """The reference's logits (T, vocab) at every position of ``tokens``."""
    return _full_logits(params, tokens, _key(sizes))
