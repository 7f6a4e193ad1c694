"""Pattern-scanned decoder stack covering every assigned family.

Layers are grouped by the config's repeating ``pattern`` (e.g. gemma3's
5 local + 1 global, recurrentgemma's rglru/rglru/local, llama-vision's
4 self + 1 cross); parameters of each pattern position are stacked over
repeats and the stack is applied with ``lax.scan``, so HLO size (and
compile time) is independent of depth.  The non-divisible remainder is
unrolled, and so are the leading dense layers of an MoE config
(``first_k_dense``, under ``"lead"``), which carry a dense MLP of width
``d_ff`` in place of the experts.  ``jax.checkpoint`` (full remat) wraps
the scanned body for training.

Caches mirror the parameter structure: one stacked pytree per pattern
position plus per-remainder-layer (and per-lead-layer) entries.  An MoE
layer's cache also counts, per batch row, the routed (token, choice)
pairs it has seen (``expert_choices``) and those that landed on an expert
it holds (``held_expert_choices``), so the counts accumulate on the
device with the cache and cost no transfer until the caller reads them.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Optional

import jax
import jax.numpy as jnp

from ..configs.base import ModelConfig
from ..distributed.sharding import shard
from .attention import attn_init, attention_block
from .common import (Params, layer_norm, layer_norm_init, rms_norm,
                     rms_norm_init, split_keys)
from .mla import mla_block, mla_init
from .mlp import mlp, mlp_init
from .moe import moe_block, moe_init
from .rglru import rglru_block, rglru_init
from .rwkv import rwkv_channel_mix, rwkv_init, rwkv_time_mix


def _norm_init(cfg: ModelConfig, d: int, dtype):
    return rms_norm_init(d, dtype) if cfg.norm == "rms" else layer_norm_init(d, dtype)


def _norm(cfg: ModelConfig, p: Params, x):
    return rms_norm(p, x) if cfg.norm == "rms" else layer_norm(p, x)


# ---------------------------------------------------------------------------
# one residual block per kind
# ---------------------------------------------------------------------------

def block_init(key: jax.Array, cfg: ModelConfig, kind: str,
               dtype=jnp.float32) -> Params:
    d = cfg.d_model
    ks = split_keys(key, 4)
    p: Params = {"ln1": _norm_init(cfg, d, dtype)}
    if kind in ("full", "local", "cross"):
        if cfg.mla is not None:
            p["attn"] = mla_init(ks[0], cfg, dtype)
        else:
            p["attn"] = attn_init(ks[0], cfg, dtype=dtype)
        p["ln2"] = _norm_init(cfg, d, dtype)
        if cfg.moe is not None:
            p["moe"] = moe_init(ks[1], cfg, dtype)
        else:
            gated = cfg.act in ("silu", "gelu")
            p["mlp"] = mlp_init(ks[1], d, cfg.d_ff, gated=gated, dtype=dtype)
        if kind == "cross":
            p["ln_x"] = _norm_init(cfg, d, dtype)
            p["xattn"] = attn_init(ks[2], cfg, dtype=dtype)
            if cfg.family == "vlm":        # llama-vision gates cross layers
                p["gate_x"] = jnp.zeros((), dtype)
                p["gate_m"] = jnp.zeros((), dtype)
    elif kind == "rglru":
        p["rec"] = rglru_init(ks[0], cfg, dtype)
        p["ln2"] = _norm_init(cfg, d, dtype)
        p["mlp"] = mlp_init(ks[1], d, cfg.d_ff, gated=True, dtype=dtype)
    elif kind == "rwkv":
        p["tmix"] = rwkv_init(ks[0], cfg, dtype)
        p["ln2"] = _norm_init(cfg, d, dtype)
        # channel-mix params live inside tmix dict (shared init fn)
    else:
        raise ValueError(f"unknown layer kind {kind!r}")
    return p


def block_cache_spec(cfg: ModelConfig, kind: str, batch: int, max_seq: int,
                     dtype) -> Optional[dict[str, Any]]:
    """ShapeDtypeStructs for one layer's decode cache."""
    d = cfg.d_model
    if kind in ("full", "local", "cross"):
        if cfg.mla is not None:
            m = cfg.mla
            spec = {"ckv": jax.ShapeDtypeStruct((batch, max_seq, m.kv_lora_rank), dtype),
                    "kr": jax.ShapeDtypeStruct((batch, max_seq, m.qk_rope_head_dim), dtype)}
        else:
            s = min(cfg.attn_window, max_seq) if (kind == "local" and cfg.attn_window) else max_seq
            kvd = (batch, s, cfg.num_kv_heads, cfg.head_dim)
            spec = {"k": jax.ShapeDtypeStruct(kvd, dtype),
                    "v": jax.ShapeDtypeStruct(kvd, dtype)}
        if cfg.moe is not None:
            spec["expert_choices"] = jax.ShapeDtypeStruct((batch,), jnp.int32)
            spec["held_expert_choices"] = jax.ShapeDtypeStruct((batch,), jnp.int32)
        if kind == "cross":
            n_kv = (cfg.vision.num_image_tokens if cfg.vision
                    else cfg.encoder.num_frames)
            kvd = (batch, n_kv, cfg.num_kv_heads, cfg.head_dim)
            spec["xk"] = jax.ShapeDtypeStruct(kvd, dtype)
            spec["xv"] = jax.ShapeDtypeStruct(kvd, dtype)
        return spec
    if kind == "rglru":
        return {"h": jax.ShapeDtypeStruct((batch, d), dtype),
                "conv": jax.ShapeDtypeStruct((batch, 3, d), dtype)}
    if kind == "rwkv":
        h = d // cfg.rwkv_head_dim
        return {"s": jax.ShapeDtypeStruct((batch, h, cfg.rwkv_head_dim, cfg.rwkv_head_dim),
                                          jnp.float32),
                "x_tm": jax.ShapeDtypeStruct((batch, d), dtype),
                "x_cm": jax.ShapeDtypeStruct((batch, d), dtype)}
    raise ValueError(kind)


def _gather_fsdp(params: Params) -> Params:
    """Re-constrain FSDP-sharded weights to their TP-only sharding at the
    point of use: one small per-layer weight all-gather (ZeRO-3) instead of
    letting the partitioner psum (B,T,D)-sized activation partials, which
    it otherwise prefers and which dominates the collective term."""
    from ..distributed.sharding import current_rules
    rules = current_rules()
    if rules is None or rules.rules.get("fsdp") is None:
        return params
    from .model import _leaf_axes

    def fix(path, leaf):
        axes = _leaf_axes(path, leaf)
        axes = tuple(None if a == "fsdp" else a for a in axes)
        return jax.lax.with_sharding_constraint(leaf, rules.sharding(*axes))

    return jax.tree_util.tree_map_with_path(fix, params)


def apply_block(p: Params, x: jnp.ndarray, cfg: ModelConfig, kind: str, *,
                pos_offset, cache: Optional[Params] = None,
                cross_x: Optional[jnp.ndarray] = None, causal: bool = True):
    """Pre-norm residual block. Returns (x, new_cache, aux_loss)."""
    aux = jnp.zeros((), jnp.float32)
    x = shard(x, "batch", None, None)
    # NOTE: _gather_fsdp (explicit per-layer ZeRO-3 weight gathering) was
    # tried here and REMOVED: it never improved the collective term (the
    # partitioner already schedules the equivalent exchange), regressed
    # B=1 decode 48x, and ballooned vision-90B multi-pod train memory by
    # forcing whole-stack gathers.

    if kind == "rwkv":
        sub_cache = None if cache is None else \
            {"s": cache["s"], "x_tm": cache["x_tm"]}
        h, c1 = rwkv_time_mix(p["tmix"], _norm(cfg, p["ln1"], x), cfg,
                              cache=sub_cache)
        x = x + h
        sub_cache2 = None if cache is None else {"x_cm": cache["x_cm"]}
        h, c2 = rwkv_channel_mix(p["tmix"], _norm(cfg, p["ln2"], x), cfg,
                                 cache=sub_cache2)
        x = x + h
        new_cache = None if cache is None else {**c1, **c2}
        return x, new_cache, aux

    if kind == "rglru":
        h, c1 = rglru_block(p["rec"], _norm(cfg, p["ln1"], x), cfg,
                            cache=None if cache is None else
                            {"h": cache["h"], "conv": cache["conv"]})
        x = x + h
        x = x + mlp(p["mlp"], _norm(cfg, p["ln2"], x), cfg.act)
        return x, c1, aux

    # attention kinds
    attn_cache = None
    if cache is not None:
        attn_cache = {k: v for k, v in cache.items() if k in ("k", "v", "ckv", "kr")}
    if cfg.mla is not None:
        h, c_attn = mla_block(p["attn"], _norm(cfg, p["ln1"], x), cfg,
                              pos_offset=pos_offset, cache=attn_cache or None)
    else:
        h, c_attn = attention_block(
            p["attn"], _norm(cfg, p["ln1"], x), cfg, kind="local" if kind == "local" else "full",
            pos_offset=pos_offset, cache=attn_cache, causal=causal)
    x = x + h

    new_cache: Optional[dict[str, Any]] = None
    if cache is not None:
        new_cache = dict(c_attn or {})

    if kind == "cross":
        xc = _norm(cfg, p["ln_x"], x)
        x_cache = None
        if cache is not None:
            x_cache = {k: v for k, v in cache.items() if k in ("xk", "xv")}
            if not x_cache:
                x_cache = None
        h, c_x = attention_block(p["xattn"], xc, cfg, kind="full",
                                 cross_x=cross_x,
                                 cache=x_cache if x_cache else (
                                     {} if cache is not None else None))
        if "gate_x" in p:
            h = jnp.tanh(p["gate_x"]) * h
        x = x + h
        if cache is not None and c_x:
            new_cache.update(c_x)

    h2 = _norm(cfg, p["ln2"], x)
    if cfg.moe is not None:
        h2, aux, held = moe_block(p["moe"], h2, cfg)
        if cache is not None:
            new_cache["expert_choices"] = (cache["expert_choices"]
                                           + x.shape[1] * cfg.moe.top_k)
            new_cache["held_expert_choices"] = cache["held_expert_choices"] + held
    else:
        h2 = mlp(p["mlp"], h2, cfg.act)
    if kind == "cross" and "gate_m" in p:
        h2 = jnp.tanh(p["gate_m"]) * h2
    x = x + h2
    return x, new_cache, aux


# ---------------------------------------------------------------------------
# the scanned stack
# ---------------------------------------------------------------------------

def _layout(cfg: ModelConfig):
    """(config of the leading dense layers, their count, scanned repeats of
    the pattern, the remainder's kinds)."""
    lead = cfg.first_k_dense
    n = cfg.num_layers - lead
    reps = n // len(cfg.pattern)
    return (dataclasses.replace(cfg, moe=None), lead, reps,
            list(cfg.pattern[:n - reps * len(cfg.pattern)]))


def stack_init(key: jax.Array, cfg: ModelConfig, dtype=jnp.float32) -> Params:
    pattern = cfg.pattern
    dense_cfg, lead, reps, rem_kinds = _layout(cfg)
    keys = split_keys(key, len(pattern) + len(rem_kinds))

    groups = []
    for i, kind in enumerate(pattern):
        rep_keys = jnp.stack(split_keys(keys[i], reps))
        stacked = jax.vmap(lambda k, kd=kind: block_init(k, cfg, kd, dtype))(rep_keys)
        groups.append(stacked)
    remainder = [block_init(keys[len(pattern) + j], cfg, kind, dtype)
                 for j, kind in enumerate(rem_kinds)]
    out = {"groups": groups, "remainder": remainder}
    if lead:
        out["lead"] = [block_init(k, dense_cfg, "full", dtype)
                       for k in split_keys(jax.random.fold_in(key, 1), lead)]
    return out


def stack_cache_specs(cfg: ModelConfig, batch: int, max_seq: int, dtype):
    pattern = cfg.pattern
    dense_cfg, lead, reps, rem_kinds = _layout(cfg)

    def stacked_spec(kind):
        spec = block_cache_spec(cfg, kind, batch, max_seq, dtype)
        return jax.tree.map(
            lambda s: jax.ShapeDtypeStruct((reps,) + s.shape, s.dtype), spec)

    out = {"groups": [stacked_spec(k) for k in pattern],
           "remainder": [block_cache_spec(cfg, k, batch, max_seq, dtype)
                         for k in rem_kinds]}
    if lead:
        out["lead"] = [block_cache_spec(dense_cfg, "full", batch, max_seq, dtype)
                       for _ in range(lead)]
    return out


def apply_stack(params: Params, x: jnp.ndarray, cfg: ModelConfig, *,
                pos_offset, caches: Optional[Params] = None,
                cross_x: Optional[jnp.ndarray] = None, causal: bool = True):
    """Returns (x, new_caches, total_aux)."""
    pattern = cfg.pattern
    dense_cfg, lead, reps, rem_kinds = _layout(cfg)
    aux_total = jnp.zeros((), jnp.float32)

    new_lead = []
    for j in range(lead):
        c = None if caches is None else caches["lead"][j]
        x, nc, _ = apply_block(params["lead"][j], x, dense_cfg, "full",
                               pos_offset=pos_offset, cache=c,
                               cross_x=cross_x, causal=causal)
        new_lead.append(nc)

    def super_block(x, group_params, group_caches):
        """One pass through all pattern positions (one 'super layer')."""
        new_caches = []
        aux_sum = jnp.zeros((), jnp.float32)
        for pos, kind in enumerate(pattern):
            c = None if group_caches is None else group_caches[pos]
            x, nc, aux = apply_block(group_params[pos], x, cfg, kind,
                                     pos_offset=pos_offset, cache=c,
                                     cross_x=cross_x, causal=causal)
            new_caches.append(nc)
            aux_sum = aux_sum + aux
        return x, new_caches, aux_sum

    if reps > 0:
        def scan_body(carry, xs):
            x, aux = carry
            if caches is None:
                gp = xs
                x, _, a = super_block(x, gp, None)
                return (x, aux + a), None
            gp, gc = xs
            x, ncs, a = super_block(x, gp, gc)
            return (x, aux + a), ncs

        body = scan_body
        if cfg.remat and caches is None:
            body = jax.checkpoint(scan_body,
                                  policy=jax.checkpoint_policies.nothing_saveable)

        xs = tuple(params["groups"]) if caches is None else \
            (tuple(params["groups"]), tuple(caches["groups"]))
        (x, aux_total), new_group_caches = jax.lax.scan(
            body, (x, aux_total), xs)
    else:
        new_group_caches = None
        if caches is not None:
            new_group_caches = caches["groups"]

    new_rem = []
    for j, kind in enumerate(rem_kinds):
        c = None if caches is None else caches["remainder"][j]
        x, nc, aux = apply_block(params["remainder"][j], x, cfg, kind,
                                 pos_offset=pos_offset, cache=c,
                                 cross_x=cross_x, causal=causal)
        new_rem.append(nc)
        aux_total = aux_total + aux

    new_caches = None
    if caches is not None:
        new_caches = {"groups": list(new_group_caches), "remainder": new_rem}
        if lead:
            new_caches["lead"] = new_lead
    return x, new_caches, aux_total
