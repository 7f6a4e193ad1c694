"""MoE dispatch semantics: conservation, capacity, locality bias."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config, reduce_config
from repro.models.moe import moe_block, moe_init


def _cfg(**kw):
    cfg = reduce_config(get_config("qwen3-moe-30b-a3b"))
    if kw:
        cfg = dataclasses.replace(cfg, moe=dataclasses.replace(cfg.moe, **kw))
    return cfg


KEY = jax.random.key(0)


class TestDispatch:
    def test_output_shape_and_finite(self):
        cfg = _cfg()
        p = moe_init(KEY, cfg)
        x = jax.random.normal(jax.random.key(1), (2, 16, cfg.d_model))
        out, aux, _ = moe_block(p, x, cfg)
        assert out.shape == x.shape
        assert jnp.isfinite(out).all() and jnp.isfinite(aux)

    def test_no_drop_equals_dense_expert_mix(self):
        """With capacity for everyone, the MoE output equals the explicit
        per-token top-k expert mixture computed naively."""
        cfg = _cfg(capacity_factor=16.0)
        m = cfg.moe
        p = moe_init(KEY, cfg)
        x = jax.random.normal(jax.random.key(1), (1, 8, cfg.d_model)) * 0.3
        out, _, _ = moe_block(p, x, cfg)

        logits = (x @ p["router"].astype(x.dtype)).astype(jnp.float32)
        gates = jax.nn.softmax(logits, -1)
        topv, topi = jax.lax.top_k(gates, m.top_k)
        topv = topv / topv.sum(-1, keepdims=True)
        ref = jnp.zeros_like(x)
        for b in range(x.shape[0]):
            for t in range(x.shape[1]):
                acc = jnp.zeros((cfg.d_model,), x.dtype)
                for j in range(m.top_k):
                    e = int(topi[b, t, j])
                    h = jax.nn.silu(x[b, t] @ p["w_gate"][e]) * (x[b, t] @ p["w_up"][e])
                    acc = acc + topv[b, t, j].astype(x.dtype) * (h @ p["w_down"][e])
                ref = ref.at[b, t].set(acc)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=2e-5, rtol=1e-3)

    def test_capacity_drops_tokens(self):
        """Tiny capacity factor ⇒ overflow tokens get zero expert output
        (residual passthrough happens in the caller)."""
        cfg = _cfg(capacity_factor=0.01)
        p = moe_init(KEY, cfg)
        x = jax.random.normal(jax.random.key(1), (1, 64, cfg.d_model))
        out, _, _ = moe_block(p, x, cfg)
        norms = jnp.linalg.norm(out[0], axis=-1)
        assert (norms < 1e-6).any(), "expected dropped tokens with cap=1"

    def test_locality_bias_shifts_assignment(self):
        cfg0 = _cfg(locality_bias=0.0)
        cfg1 = _cfg(locality_bias=50.0)   # crank it: all tokens go local
        p = moe_init(KEY, cfg0)
        x = jax.random.normal(jax.random.key(1), (4, 16, cfg0.d_model))

        def top1(cfg):
            logits = (x.reshape(4, 16, -1) @ p["router"].astype(x.dtype)
                      ).astype(jnp.float32)
            from repro.models.moe import _local_expert_bias
            if cfg.moe.locality_bias:
                logits = logits + _local_expert_bias(
                    4, cfg.moe.num_experts, cfg.moe.locality_bias)[:, None, :]
            return jnp.argmax(logits, -1)

        a0, a1 = top1(cfg0), top1(cfg1)
        # without a mesh there is one locality group — bias is a no-op
        np.testing.assert_array_equal(np.asarray(a0), np.asarray(a1))

    def test_aux_loss_decreases_with_balance(self):
        """A uniform router gives the minimal aux loss (≈ weight)."""
        cfg = _cfg()
        p = moe_init(KEY, cfg)
        # uniform logits
        p2 = dict(p)
        p2["router"] = jnp.zeros_like(p["router"])
        x = jax.random.normal(jax.random.key(1), (2, 32, cfg.d_model))
        _, aux_uniform, _ = moe_block(p2, x, cfg)
        # biased router: all mass on expert 0
        p3 = dict(p)
        p3["router"] = jnp.zeros_like(p["router"]).at[:, 0].set(20.0)
        _, aux_biased, _ = moe_block(p3, x, cfg)
        assert float(aux_biased) > float(aux_uniform)
