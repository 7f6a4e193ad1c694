"""Plain reference for Qwen2 (arXiv:2407.10671), and the weights from a seed.

Qwen2 as published: token embedding; per layer a pre-norm residual block of
grouped-query attention (bias on q, k and v, none on the output, rotary
positions with rotate-half, theta from the config) and a SwiGLU MLP, each
behind an RMSNorm; a final RMSNorm; logits by the tied embedding.  It
imports nothing of the program.  Every matmul runs in float32 at the
``highest`` precision, except in the control, which quantizes both
operands of every matmul to float8 (e4m3, one scale per tensor).

``make_params`` makes the weights from the seed on the device in one call,
laid out as the program's parameter tree.  An RMSNorm weight is stored as
``scale`` and applied as ``1 + scale``, the program's convention.  The
reference makes its own copy from the same seed, rounded to the served
bfloat16, so it computes the model that is served.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST


def dims(sizes: dict) -> dict:
    d = sizes["hidden_size"]
    h, kv = sizes["num_attention_heads"], sizes["num_key_value_heads"]
    return dict(d=d, f=sizes["intermediate_size"], v=sizes["vocab_size"],
                layers=sizes["num_hidden_layers"], h=h, kv=kv, hd=d // h,
                theta=float(sizes["rope_theta"]), eps=float(sizes["rms_norm_eps"]))


@functools.partial(jax.jit, static_argnums=(0, 2))
def _make(sz: tuple, key, dtype):
    s = dict(sz)
    d, f, v, n, hd = s["d"], s["f"], s["v"], s["layers"], s["hd"]
    q, kv = s["h"] * hd, s["kv"] * hd
    ks = iter(jax.random.split(key, 16))

    def w(shape, std):
        return (jax.random.normal(next(ks), shape, jnp.float32) * std).astype(dtype)

    layer = {
        "ln1": {"scale": w((n, d), 0.1)},
        "attn": {"wq": w((n, d, q), d ** -0.5), "wk": w((n, d, kv), d ** -0.5),
                 "wv": w((n, d, kv), d ** -0.5), "wo": w((n, q, d), q ** -0.5),
                 "bq": w((n, q), 0.1), "bk": w((n, kv), 0.1),
                 "bv": w((n, kv), 0.1)},
        "ln2": {"scale": w((n, d), 0.1)},
        "mlp": {"w_up": w((n, d, f), d ** -0.5), "w_gate": w((n, d, f), d ** -0.5),
                "w_down": w((n, f, d), f ** -0.5)},
    }
    return {"tok": w((v, d), 0.02), "final_norm": {"scale": w((d,), 0.1)},
            "stack": {"groups": [layer], "remainder": []}}


def make_params(sizes: dict, key, dtype=jnp.bfloat16):
    """The weights for ``key`` in ``dtype``: the served weights in
    bfloat16, or (``dtype=float32``) the same values as float32."""
    p = _make(tuple(sorted(dims(sizes).items())), key, jnp.bfloat16)
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


def _rope(x, pos, theta):
    hd = x.shape[-1]
    inv = 1.0 / theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)
    ang = pos[:, None].astype(jnp.float32) * inv            # (T, hd/2)
    cos = jnp.concatenate([jnp.cos(ang)] * 2, -1)[:, None]
    sin = jnp.concatenate([jnp.sin(ang)] * 2, -1)[:, None]
    x1, x2 = x[..., :hd // 2], x[..., hd // 2:]
    return x * cos + jnp.concatenate([-x2, x1], -1) * sin


def hidden(params, tokens, s: dict, mm=_mm):
    """Final-normed hidden states (T, d) of one sequence."""
    t = tokens.shape[0]
    pos = jnp.arange(t)
    causal = pos[None, :] <= pos[:, None]
    g = s["h"] // s["kv"]

    def block(x, p):
        a, m = p["attn"], p["mlp"]
        y = _rms(x, p["ln1"]["scale"], s["eps"])
        q = (mm(y, a["wq"]) + a["bq"]).reshape(t, s["h"], s["hd"])
        k = (mm(y, a["wk"]) + a["bk"]).reshape(t, s["kv"], s["hd"])
        v = (mm(y, a["wv"]) + a["bv"]).reshape(t, s["kv"], s["hd"])
        q, k = _rope(q, pos, s["theta"]), _rope(k, pos, s["theta"])
        k, v = jnp.repeat(k, g, axis=1), jnp.repeat(v, g, axis=1)
        sc = jnp.einsum("qhd,khd->hqk", q, k, precision=HIGHEST) * s["hd"] ** -0.5
        w = jax.nn.softmax(jnp.where(causal, sc, -jnp.inf), axis=-1)
        o = jnp.einsum("hqk,khd->qhd", w, v, precision=HIGHEST).reshape(t, -1)
        x = x + mm(o, a["wo"])
        y = _rms(x, p["ln2"]["scale"], s["eps"])
        return x + mm(jax.nn.silu(mm(y, m["w_gate"])) * mm(y, m["w_up"]),
                      m["w_down"]), None

    x = params["tok"][tokens]
    x, _ = jax.lax.scan(block, x, params["stack"]["groups"][0])
    return _rms(x, params["final_norm"]["scale"], s["eps"])


@functools.partial(jax.jit, static_argnums=(4,))
def _gaps(params, tokens, at, target, sz: tuple):
    s = dict(sz)
    h = hidden(params, tokens, s)[at]
    logits = _mm(h, params["tok"].T)
    picked = jnp.take_along_axis(logits, target[:, None], axis=1)[:, 0]
    return jnp.max(logits, axis=1) - picked


def _control_first(params, tokens, at, s: dict):
    low = _mm_fp8(hidden(params, tokens, s, _mm_fp8)[at], params["tok"].T)
    return jnp.argmax(low, axis=1)


@functools.partial(jax.jit, static_argnums=(3,))
def _control_gaps(params, tokens, at, sz: tuple):
    s = dict(sz)
    logits = _mm(hidden(params, tokens, s)[at], params["tok"].T)
    picked = jnp.take_along_axis(
        logits, _control_first(params, tokens, at, s)[:, None], axis=1)[:, 0]
    return jnp.max(logits, axis=1) - picked


@functools.partial(jax.jit, static_argnums=(3,))
def _control_tokens(params, tokens, at, sz: tuple):
    return _control_first(params, tokens, at, dict(sz))


def gaps(params, sizes: dict, tokens, at, target):
    """At each position ``at``, how far the reference's logit of
    ``target`` (the token served after that position) lies below its
    best."""
    return _gaps(params, tokens, at, target, tuple(sorted(dims(sizes).items())))


def control_gaps(params, sizes: dict, tokens, at):
    """The control: at each position ``at``, how far the reference's logit
    of the token that the float8 model puts first lies below its best."""
    return _control_gaps(params, tokens, at, tuple(sorted(dims(sizes).items())))


def control_tokens(params, sizes: dict, tokens, at):
    """The token that the float8 model puts first after each position
    ``at``: the control's greedy choice, for decoding in the program's
    place."""
    return _control_tokens(params, tokens, at, tuple(sorted(dims(sizes).items())))
