"""Model: the public API over the pattern-scanned stack.

One class serves all 10 assigned architectures; family differences
(whisper's encoder, the VLM's vision tokens, tied heads, learned vs rotary
positions) are handled here so that launch/dryrun, train, serving, tests
and benchmarks all speak one interface:

    model = build_model(cfg)
    params = model.init_params(key)                  # or eval_shape'd
    loss, metrics = model.loss_fn(params, batch)
    logits, caches = model.prefill(params, batch, caches)
    logits, caches = model.decode_step(params, tokens, pos, caches)
"""
from __future__ import annotations

import dataclasses
from typing import Any, Optional

import jax
import jax.numpy as jnp

from ..configs.base import ModelConfig, ShapeConfig
from ..distributed.sharding import shard
from .common import (Params, cross_entropy, embed_init, layer_norm,
                     layer_norm_init, rms_norm, rms_norm_init,
                     sinusoidal_positions, split_keys)
from .transformer import apply_stack, stack_cache_specs, stack_init

_DTYPES = {"bfloat16": jnp.bfloat16, "float32": jnp.float32}


def _enc_cfg(cfg: ModelConfig) -> ModelConfig:
    e = cfg.encoder
    return dataclasses.replace(
        cfg, num_layers=e.num_layers, d_model=e.d_model,
        num_heads=e.num_heads, num_kv_heads=e.num_heads,
        head_dim=e.d_model // e.num_heads, d_ff=e.d_ff,
        pattern=("full",), moe=None, mla=None, vision=None,
        qkv_bias=False, rope_theta=0.0)


class Model:
    def __init__(self, cfg: ModelConfig, max_pos: int = 4096):
        self.cfg = cfg
        self.max_pos = max_pos
        self.dtype = _DTYPES[cfg.dtype]

    # -- parameters ---------------------------------------------------------
    def init_params(self, key: jax.Array) -> Params:
        cfg = self.cfg
        dt = self.dtype
        ks = split_keys(key, 5)
        p: Params = {
            "tok": embed_init(ks[0], cfg.vocab_padded(), cfg.d_model, dt),
            "final_norm": (rms_norm_init(cfg.d_model, dt) if cfg.norm == "rms"
                           else layer_norm_init(cfg.d_model, dt)),
            "stack": stack_init(ks[1], cfg, dt),
        }
        if not cfg.tie_embeddings:
            p["head"] = embed_init(ks[2], cfg.vocab_padded(), cfg.d_model, dt).T
        if cfg.encoder is not None:
            ecfg = _enc_cfg(cfg)
            p["encoder"] = {
                "stack": stack_init(ks[3], ecfg, dt),
                "final_norm": layer_norm_init(ecfg.d_model, dt)
                if cfg.norm == "layer" else rms_norm_init(ecfg.d_model, dt),
            }
            # whisper decoder uses learned absolute positions
            p["dec_pos"] = (jax.random.normal(ks[4], (self.max_pos, cfg.d_model)) * 0.01).astype(dt)
        return p

    def abstract_params(self) -> Params:
        return jax.eval_shape(lambda k: self.init_params(k),
                              jax.random.key(0))

    # -- encoder (whisper) ----------------------------------------------------
    def _encode(self, params: Params, frames: jnp.ndarray) -> jnp.ndarray:
        cfg = self.cfg
        ecfg = _enc_cfg(cfg)
        pos = sinusoidal_positions(frames.shape[1], ecfg.d_model).astype(frames.dtype)
        x = frames + pos[None]
        x, _, _ = apply_stack(params["encoder"]["stack"], x, ecfg,
                              pos_offset=0, causal=False)
        if cfg.norm == "layer":
            return layer_norm(params["encoder"]["final_norm"], x)
        return rms_norm(params["encoder"]["final_norm"], x)

    # -- forward --------------------------------------------------------------
    def forward(self, params: Params, tokens: jnp.ndarray, *,
                extras: Optional[dict[str, jnp.ndarray]] = None,
                pos_offset=0, caches: Optional[Params] = None,
                last_only: bool = False):
        """Returns (logits, new_caches, aux)."""
        cfg = self.cfg
        x = jnp.take(params["tok"], tokens, axis=0)
        if cfg.family in ("dense",) and cfg.name.startswith("gemma") or \
                cfg.family == "hybrid":
            x = x * jnp.asarray(cfg.d_model ** 0.5, x.dtype)
        x = shard(x, "batch", None, None)

        cross_x = None
        if cfg.encoder is not None:
            if extras is not None and "frames" in extras:
                cross_x = self._encode(params, extras["frames"])
            t = tokens.shape[1]
            pos_ids = pos_offset + jnp.arange(t)
            x = x + jnp.take(params["dec_pos"], pos_ids, axis=0)[None]
        elif cfg.vision is not None and extras is not None and "vision" in extras:
            cross_x = extras["vision"]

        x, new_caches, aux = apply_stack(
            params["stack"], x, cfg, pos_offset=pos_offset, caches=caches,
            cross_x=cross_x)

        if cfg.norm == "rms":
            x = rms_norm(params["final_norm"], x)
        else:
            x = layer_norm(params["final_norm"], x)
        if last_only:
            x = x[:, -1:]
        head = params["head"] if not cfg.tie_embeddings else params["tok"].T
        logits = x @ head.astype(x.dtype)
        logits = shard(logits, "batch", None, "vocab")
        return logits, new_caches, aux

    # -- train ---------------------------------------------------------------
    def loss_fn(self, params: Params, batch: dict[str, jnp.ndarray]):
        logits, _, aux = self.forward(params, batch["tokens"],
                                      extras=batch)
        ce = cross_entropy(logits, batch["labels"])
        return ce + aux, {"ce": ce, "aux": aux}

    # -- serve ---------------------------------------------------------------
    def prefill(self, params: Params, batch: dict[str, jnp.ndarray],
                caches: Params):
        logits, caches, _ = self.forward(params, batch["tokens"],
                                         extras=batch, pos_offset=0,
                                         caches=caches, last_only=True)
        return logits, caches

    def decode_step(self, params: Params, tokens: jnp.ndarray,
                    pos, caches: Params):
        """tokens (B, 1); pos = number of tokens already in the cache."""
        logits, caches, _ = self.forward(params, tokens, pos_offset=pos,
                                         caches=caches)
        return logits, caches

    # -- specs (abstract inputs for dry-run / compile) -------------------------
    def input_specs(self, shape: ShapeConfig) -> dict[str, Any]:
        cfg = self.cfg
        b = shape.global_batch
        s = shape.seq_len
        i32 = jnp.int32
        dt = self.dtype
        if shape.kind == "train":
            specs = {"tokens": jax.ShapeDtypeStruct((b, s), i32),
                     "labels": jax.ShapeDtypeStruct((b, s), i32)}
        elif shape.kind == "prefill":
            specs = {"tokens": jax.ShapeDtypeStruct((b, s), i32)}
        else:  # decode
            specs = {"tokens": jax.ShapeDtypeStruct((b, 1), i32)}
        if cfg.encoder is not None and shape.kind != "decode":
            e = cfg.encoder
            specs["frames"] = jax.ShapeDtypeStruct((b, e.num_frames, e.d_model), dt)
        if cfg.vision is not None and shape.kind != "decode":
            specs["vision"] = jax.ShapeDtypeStruct(
                (b, cfg.vision.num_image_tokens, cfg.d_model), dt)
        return specs

    def cache_specs(self, shape: ShapeConfig):
        return stack_cache_specs(self.cfg, shape.global_batch, shape.seq_len,
                                 self.dtype)

    def init_cache(self, batch: int, max_seq: int) -> Params:
        shp = ShapeConfig("adhoc", max_seq, batch, "decode")
        specs = stack_cache_specs(self.cfg, batch, max_seq, self.dtype)
        return jax.tree.map(lambda sp: jnp.zeros(sp.shape, sp.dtype), specs)


def build_model(cfg: ModelConfig, max_pos: int = 4096) -> Model:
    return Model(cfg, max_pos=max_pos)


# ---------------------------------------------------------------------------
# parameter logical axes (for NamedSharding via distributed.sharding rules)
# ---------------------------------------------------------------------------

_LEAF_AXES: dict[str, tuple] = {
    "tok": ("vocab", None),
    "head": (None, "vocab"),
    "dec_pos": (None, None),
    "wq": ("fsdp", "qheads"),
    "wk": ("fsdp", None),
    "wv": ("fsdp", None),
    "wo": ("qheads", "fsdp"),
    "bq": ("qheads",), "bk": (None,), "bv": (None,),
    "w_up": ("fsdp", "ffn"), "w_gate": ("fsdp", "ffn"),
    "w_down": ("ffn", "fsdp"),
    "router": (None, None),
    # MLA
    "w_q": ("fsdp", "qheads"),
    "w_dq": ("fsdp", None), "w_uq": (None, "qheads"),
    "w_dkv": ("fsdp", None), "w_uk": (None, "qheads"),
    "w_uv": (None, "qheads"), "w_kr": (None, None),
    # RG-LRU
    "w_gate_branch": ("fsdp", "lru"), "w_x_branch": ("fsdp", "lru"),
    "conv_w": (None, "lru"), "conv_b": ("lru",),
    # block-diagonal gates: block dim sharded like the lru channels, so the
    # per-block matmuls contract entirely within a shard (no collective)
    "w_a": ("lru_blocks", None, None), "b_a": ("lru",),
    "w_i": ("lru_blocks", None, None), "b_i": ("lru",),
    "lam": ("lru",), "w_out": ("lru", "fsdp"),
    # RWKV
    "w_r": ("fsdp", None), "w_k": ("fsdp", None), "w_v": ("fsdp", None),
    "w_g": ("fsdp", None), "w_o": ("fsdp", None),
    "decay_lora_a": ("fsdp", None), "decay_lora_b": (None, None),
    "mix_lora_a": ("fsdp", None), "mix_lora_b": (None, None),
    "mix_base": (None, None), "decay_base": (None,),
    "u": (None, None), "gn_scale": (None,), "gn_bias": (None,),
    "w_ck": ("fsdp", "rwkv_ffn"), "w_cv": ("rwkv_ffn", "fsdp"),
    "w_cr": ("fsdp", None),
    "cmix_k": (None,), "cmix_r": (None,),
}

_MOE_LEAF_AXES = {
    "w_gate": ("experts", "fsdp", None),
    "w_up": ("experts", "fsdp", None),
    "w_down": ("experts", None, "fsdp"),
}


def _leaf_axes(path, leaf) -> tuple:
    names = [getattr(k, "key", None) for k in path]
    name = names[-1]
    in_moe = "moe" in names and "shared" not in names
    table = _MOE_LEAF_AXES if (in_moe and name in _MOE_LEAF_AXES) else _LEAF_AXES
    axes = table.get(name)
    nd = len(leaf.shape)
    if axes is None:
        return (None,) * nd
    if len(axes) < nd:                 # stacked (scan) leading axes
        return (None,) * (nd - len(axes)) + tuple(axes)
    return tuple(axes[:nd])


def param_logical_axes(cfg: ModelConfig, params: Params):
    """Tree of logical-axis tuples matching the params tree."""
    return jax.tree_util.tree_map_with_path(_leaf_axes, params)


def param_shardings(cfg: ModelConfig, params: Params, rules):
    """NamedShardings for every param leaf under the given rules."""
    return jax.tree_util.tree_map_with_path(
        lambda path, leaf: rules.sharding(*_leaf_axes(path, leaf)), params)
