"""Mixture-of-Experts with expert parallelism and locality-biased routing.

Two paths, chosen by the config's ``capacity_factor``:

- **Capacity** (a number; qwen3-moe, phi3.5-moe): training semantics.
  Each expert takes at most ``tokens * top_k / num_experts * factor``
  tokens of a group, and the rest fall through to the residual.
- **Dropless** (None; DeepSeek-V2-Lite): every (token, choice) pair is
  computed.  The layer is told which experts it holds (``expert_offset``,
  ``experts_held``): it routes over all ``num_experts``, and computes the
  part of the output its own experts give, each held expert over the
  tokens weighted by its gate, zero where it was not chosen.  Shared
  experts (one SwiGLU) are added once.

Both return, beside the output and the load-balance loss, how many of the
routed (token, choice) pairs of each batch row landed on a held expert.

The capacity path's dispatch/combine are Switch-Transformer-style one-hot einsums over
per-device token groups; experts are sharded over the ``model`` axis (EP),
so the dispatched activations move through an all-to-all that XLA's SPMD
partitioner inserts between the group-sharded and expert-sharded einsums.

**Locality-biased routing — the paper's technique as a first-class
feature**: each token group (= device) has a set of *local* experts (those
resident on the same model-axis coordinate when dispatch is EP-local, or
the same pod in multi-pod meshes).  A bias is added to the router logits of
local experts, exactly like the paper's locality queues prefer the home
domain's tasks; the capacity limit plays the role of bounded work stealing
(overflow tokens spill to remote experts), and the auxiliary load-balance
loss enforces the paper's balance-over-locality priority.  The measurable
effect is a smaller all-to-all (collective roofline term) at equal step
semantics.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from ..configs.base import ModelConfig
from ..distributed.sharding import current_rules, shard
from .common import Params, act_fn, dense_init, split_keys
from .mlp import mlp, mlp_init


def moe_init(key: jax.Array, cfg: ModelConfig, dtype=jnp.float32) -> Params:
    m = cfg.moe
    d, e, f = cfg.d_model, m.held, m.d_ff_expert
    ks = split_keys(key, 5)
    scale = 1.0 / jnp.sqrt(d)
    shared = ({"shared": mlp_init(ks[4], d, m.num_shared * f, dtype=dtype)}
              if m.num_shared else {})
    return {
        **shared,
        "router": dense_init(ks[0], d, m.num_experts, jnp.float32),
        "w_gate": (jax.random.truncated_normal(ks[1], -3, 3, (e, d, f)) * scale).astype(dtype),
        "w_up": (jax.random.truncated_normal(ks[2], -3, 3, (e, d, f)) * scale).astype(dtype),
        "w_down": (jax.random.truncated_normal(ks[3], -3, 3, (e, f, d)) * (1.0 / jnp.sqrt(f))).astype(dtype),
    }


def _local_expert_bias(num_groups: int, num_experts: int,
                       bias: float) -> jnp.ndarray:
    """(G, E) bias favoring experts co-resident with each token group.

    Group g's tokens live on model-axis coordinate (g % A) when groups are
    laid out batch-major over a (data, model)-flattened device order; expert
    e lives on coordinate (e // (E/A)).  The bias is the paper's "local
    queue first" preference in logit space.
    """
    rules = current_rules()
    a = 1
    if rules is not None:
        model_axis = rules.rules.get("experts")
        if model_axis is not None:
            a = rules.mesh.shape[model_axis]
    if a <= 1 or num_experts % a:
        return jnp.zeros((num_groups, num_experts), jnp.float32)
    per = num_experts // a
    g_coord = jnp.arange(num_groups) % a
    e_coord = jnp.arange(num_experts) // per
    return jnp.where(g_coord[:, None] == e_coord[None, :], bias, 0.0)


GROUP_TOKENS = 512   # dispatch/combine one-hots are O(T_g^2): keep T_g small


def _top_k(gates: jnp.ndarray, m) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Greedy top-k of the gates: (weights, expert ids)."""
    topv, topi = jax.lax.top_k(gates, m.top_k)
    if m.norm_topk_prob:
        topv = topv / jnp.maximum(topv.sum(-1, keepdims=True), 1e-9)
    return topv * m.routed_scaling_factor, topi


def _balance_loss(gates, topi, m) -> jnp.ndarray:
    """Switch's load-balance loss, E * sum_e f_e * P_e, over every token."""
    e = m.num_experts
    chosen = jax.nn.one_hot(topi, e, dtype=jnp.float32).sum(-2)
    density = chosen.reshape(-1, e).mean(0)
    prob = gates.reshape(-1, e).mean(0)
    return (density * prob).sum() * e * m.router_aux_weight


def moe_block(p: Params, x: jnp.ndarray, cfg: ModelConfig,
              num_groups: Optional[int] = None):
    """x: (B, T, D) -> (out, aux_loss, held_choices (B,) int32)."""
    if cfg.moe.capacity_factor is None:
        out, aux, held = _dropless(p, x, cfg)
    else:
        out, aux, held = _capacity(p, x, cfg, num_groups)
    if "shared" in p:
        out = out + mlp(p["shared"], x, cfg.act)
    return out, aux, held


def _dropless(p: Params, x: jnp.ndarray, cfg: ModelConfig):
    """Every (token, choice) on a held expert, computed; nothing dropped."""
    m = cfg.moe
    logits = jnp.einsum("btd,de->bte", x.astype(jnp.float32),
                        p["router"].astype(jnp.float32))
    gates = jax.nn.softmax(logits, axis=-1)
    topv, topi = _top_k(gates, m)                            # (B,T,k)
    held = m.expert_offset + jnp.arange(m.held)
    hit = topi[..., None] == held                            # (B,T,k,held)
    w = jnp.sum(jnp.where(hit, topv[..., None], 0.0), axis=-2)
    act = act_fn(cfg.act)
    h = act(jnp.einsum("btd,edf->btef", x, p["w_gate"])) \
        * jnp.einsum("btd,edf->btef", x, p["w_up"])
    h = h * w[..., None].astype(h.dtype)
    out = jnp.einsum("btef,efd->btd", h, p["w_down"])
    held_choices = hit.sum(axis=(1, 2, 3), dtype=jnp.int32)
    return out.astype(x.dtype), _balance_loss(gates, topi, m), held_choices


def _capacity(p: Params, x: jnp.ndarray, cfg: ModelConfig,
              num_groups: Optional[int]):
    """Capacity-limited dispatch over every expert, which it holds.

    Tokens are reshaped to (G, T', D) groups riding the data-parallel batch
    sharding.  The per-group (T', E, C) dispatch tensor scales as T'^2·k/E,
    so groups are capped at GROUP_TOKENS tokens (a sort-based dispatch
    would avoid the one-hot entirely).
    """
    m = cfg.moe
    if m.held != m.num_experts:
        raise ValueError("the capacity path holds every expert; a share of "
                         "them needs capacity_factor=None")
    b, t, d = x.shape
    e, k = m.num_experts, m.top_k
    if num_groups is None:
        per_seq = max(t // GROUP_TOKENS, 1)
        num_groups = b * per_seq
    g = num_groups
    xg = x.reshape(g, (b * t) // g, d)
    tokens = xg.shape[1]

    logits = (xg @ p["router"].astype(xg.dtype)).astype(jnp.float32)  # (G,T,E)
    if m.locality_bias:
        logits = logits + _local_expert_bias(g, e, m.locality_bias)[:, None, :]

    gates = jax.nn.softmax(logits, axis=-1)
    topv, topi = _top_k(gates, m)                            # (G,T,k)

    # capacity per expert per group (bounded stealing: overflow is dropped
    # to the residual path, the SPMD analogue of re-queueing)
    cap = max(int(tokens * k / e * m.capacity_factor), 1)

    # position of each (token, choice) in its expert's queue
    onehot = jax.nn.one_hot(topi, e, dtype=jnp.int32)        # (G,T,k,E)
    flat = onehot.reshape(g, tokens * k, e)
    pos = jnp.cumsum(flat, axis=1) - 1                       # arrival order
    pos = pos.reshape(g, tokens, k, e)
    in_cap = (pos < cap) & (onehot > 0)

    # combine weights (G,T,E,cap); dispatch = nonzero mask
    pos_oh = jax.nn.one_hot(jnp.where(in_cap, pos, -1), cap, dtype=xg.dtype)
    combine = jnp.einsum("gtke,gtkec,gtk->gtec", onehot.astype(xg.dtype),
                         pos_oh, topv.astype(xg.dtype))
    dispatch = combine > 0

    expert_in = jnp.einsum("gtec,gtd->gecd", dispatch.astype(xg.dtype), xg)
    expert_in = shard(expert_in, "batch", "experts", None, None)

    h = jax.nn.silu(jnp.einsum("gecd,edf->gecf", expert_in, p["w_gate"])) \
        * jnp.einsum("gecd,edf->gecf", expert_in, p["w_up"])
    expert_out = jnp.einsum("gecf,efd->gecd", h, p["w_down"])
    expert_out = shard(expert_out, "batch", "experts", None, None)

    out = jnp.einsum("gtec,gecd->gtd", combine, expert_out)

    # load-balance aux loss (Switch): E * sum_e f_e * P_e
    density = dispatch.any(-1).astype(jnp.float32).mean(axis=1)   # (G,E) frac tokens
    router_prob = gates.mean(axis=1)                              # (G,E)
    aux = (density * router_prob).sum(-1).mean() * e * m.router_aux_weight

    held_choices = jnp.full((b,), t * k, jnp.int32)
    return out.reshape(b, t, d), aux, held_choices
