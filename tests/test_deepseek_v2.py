"""DeepSeek-V2-Lite's latent attention and shared-plus-routed expert layers
against the plain float32 reference (``bench/reference/deepseek_v2.py``),
on seeded random weights at a small size: widths of 64, one dense layer
and two MoE layers, 16 routed experts of which a share of 4 is held, top-3,
one shared expert."""
import dataclasses
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import harness as H
from bench.drivers.serve_mla_moe import program_model
from bench.reference import deepseek_v2 as ref
from repro.configs import get_config
from repro.models import mla as mla_mod
from repro.models.common import rope_freqs, yarn_correction_range, yarn_freqs
from repro.models.model import build_model
from repro.models.moe import moe_block
from repro.serving.engine import Request, ServingEngine

F32 = dict(atol=2e-4, rtol=2e-3)
SMALL = {"hidden_size": 64, "intermediate_size": 96, "moe_intermediate_size": 32,
         "num_hidden_layers": 3, "first_k_dense_replace": 1,
         "num_attention_heads": 4, "num_key_value_heads": 4,
         "kv_lora_rank": 32, "qk_nope_head_dim": 16, "qk_rope_head_dim": 16,
         "v_head_dim": 16, "n_routed_experts": 4, "num_experts_per_tok": 3,
         "n_shared_experts": 1, "vocab_size": 256, "expert_offset": 4,
         "published": {"n_routed_experts": 16, "vocab_size": 2048}}


def conf(**changes) -> dict:
    c = H.load_json(H.ROOT / "bench" / "configs" / "deepseek-v2-lite-ep8.json")
    c.update(SMALL)
    c.update(changes)
    return c


def f32_model(c: dict):
    model = program_model(c)
    return build_model(dataclasses.replace(model.cfg, dtype="float32"))


@pytest.fixture(scope="module")
def small():
    c = conf()
    return c, f32_model(c), ref.make_params(c, jax.random.key(3), jnp.float32)


def test_served_decode_agrees_with_the_reference_forward(small):
    c, model, params = small
    toks = np.asarray(jax.random.randint(jax.random.key(4), (20,), 0, 256), np.int32)
    want = np.asarray(ref.logits(params, c, jnp.asarray(toks)))
    caches = model.init_cache(1, 32)
    lg, caches = model.prefill(params, {"tokens": toks[None, :12]}, caches)
    np.testing.assert_allclose(lg[0, -1], want[11], **F32)
    for i in range(12, 20):
        lg, caches = model.decode_step(params, toks[None, i:i + 1], i, caches)
        np.testing.assert_allclose(lg[0, 0], want[i], **F32)
    # through the engine: every served token is the reference's best
    engine = ServingEngine(model, params, num_replicas=2, max_seq=32)
    engine.submit(Request(uid=0, tokens=toks[:12], max_new=6))
    (req,) = engine.run_until_drained()
    seq = np.concatenate([toks[:12], req.out_tokens])
    full = np.asarray(ref.logits(params, c, jnp.asarray(seq)))
    best = full[11:11 + len(req.out_tokens)]
    picked = best[np.arange(len(req.out_tokens)), req.out_tokens]
    assert np.max(best.max(-1) - picked) <= 1e-4
    # each of the two MoE layers routed every prompt and decoded token
    t = req.timing
    assert t.expert_choices == 2 * 3 * (12 + len(req.out_tokens) - 1)
    assert 0 < t.held_expert_choices < t.expert_choices


def test_absorbed_decode_and_chunked_prefill_agree_with_expanded_form(small):
    c, model, params = small
    cfg = model.cfg
    p = params["stack"]["lead"][0]["attn"]
    t = 2304                                 # > 2048: the chunked absorbed path
    x = jax.random.normal(jax.random.key(5), (1, t + 1, 64)) * 0.5
    cache = {"ckv": jnp.zeros((1, 2560, 32)), "kr": jnp.zeros((1, 2560, 16))}
    absorbed, cache = mla_mod.mla_block(p, x[:, :t], cfg, pos_offset=0, cache=cache)
    expanded, _ = mla_mod.mla_block(p, x, cfg)
    np.testing.assert_allclose(absorbed, expanded[:, :t], **F32)
    step, _ = mla_mod.mla_block(p, x[:, t:], cfg, pos_offset=t, cache=cache)
    np.testing.assert_allclose(step[:, 0], expanded[:, t], **F32)


def _layer(c: dict, offset: int, held: int, params_all):
    """The program's MoE layer 0 holding experts [offset, offset + held)."""
    model = f32_model(dict(c, n_routed_experts=held, expert_offset=offset))
    m = jax.tree.map(lambda a: a[0], params_all["stack"]["groups"][0]["moe"])
    share = dict(m, **{k: m[k][offset:offset + held]
                       for k in ("w_gate", "w_up", "w_down")})
    return model.cfg, share


def test_expert_shares_add_up_to_the_uncut_layer():
    uncut = conf(n_routed_experts=16, expert_offset=0)
    params = ref.make_params(uncut, jax.random.key(6), jnp.float32)
    x = jax.random.normal(jax.random.key(7), (1, 24, 64))
    want = ref.experts(x[0], jax.tree.map(lambda a: a[0],
                                          params["stack"]["groups"][0]["moe"]),
                       ref.dims(uncut))
    parts, held = [], 0
    for offset in (0, 4, 8, 12):
        cfg, share = _layer(uncut, offset, 4, params)
        out, _, n = moe_block(share, x, cfg)
        parts.append(out[0])
        held += int(n[0])
    sh = share["shared"]
    mm = ref._mm
    shared = mm(jax.nn.silu(mm(x[0], sh["w_gate"])) * mm(x[0], sh["w_up"]), sh["w_down"])
    # each share adds the shared expert: count it once
    np.testing.assert_allclose(sum(parts) - 3 * shared, want, **F32)
    assert held == 24 * 3            # every choice landed on exactly one share


def test_one_expert_taking_every_token_drops_none():
    c = conf(n_routed_experts=4, expert_offset=0,
             published={"n_routed_experts": 4, "vocab_size": 2048})
    params = ref.make_params(c, jax.random.key(8), jnp.float32)
    m = jax.tree.map(lambda a: a[0], params["stack"]["groups"][0]["moe"])
    m["router"] = jnp.zeros_like(m["router"])   # ties: experts 0, 1, 2 for all
    x = jax.random.normal(jax.random.key(9), (1, 64, 64))
    cfg, _ = _layer(c, 0, 4, params)
    out, _, held = moe_block(m, x, cfg)
    want = ref.experts(x[0], m, ref.dims(c))
    np.testing.assert_allclose(out[0], want, **F32)
    assert int(held[0]) == 64 * 3
    # the capacity path would have dropped tokens past expert 0's capacity
    capped_cfg = dataclasses.replace(cfg, moe=dataclasses.replace(
        cfg.moe, capacity_factor=1.25, experts_held=None))
    capped, _, _ = moe_block(m, x, capped_cfg)
    assert not np.allclose(capped[0], want, **F32)


def test_yarn_frequencies_and_scale_at_published_numbers():
    cfg = get_config("deepseek-v2-lite")
    sc = cfg.rope_scaling
    assert yarn_correction_range(64, 1e4, 4096, 32, 1) == (10, 23)
    inv = np.asarray(yarn_freqs(64, 1e4, sc))
    plain = np.asarray(rope_freqs(64, 1e4))
    np.testing.assert_allclose(inv[:11], plain[:11], rtol=1e-6)
    np.testing.assert_allclose(inv[23:], plain[23:] / 40, rtol=1e-6)
    assert np.all((inv[11:23] < plain[11:23]) & (inv[11:23] > plain[11:23] / 40))
    freqs, factor, scale = mla_mod.rope_and_scale(cfg)
    assert factor == 1.0
    assert scale == pytest.approx(192 ** -0.5 * (0.1 * 0.707 * math.log(40) + 1) ** 2)
    assert round(scale, 6) == 0.114721
    published = H.load_json(H.ROOT / "bench" / "configs" / "deepseek-v2-lite-ep8.json")
    s = ref.dims(published)
    np.testing.assert_allclose(ref.yarn_inv_freq(s), freqs, rtol=1e-6)
    assert ref.softmax_scale(s) == pytest.approx(scale)


def test_parameter_counts_at_published_sizes():
    cfg = get_config("deepseek-v2-lite")
    assert round(cfg.num_params() / 1e9, 2) == 15.71
    share = program_model(H.load_json(
        H.ROOT / "bench" / "configs" / "deepseek-v2-lite-ep8.json")).cfg
    assert share.num_params() == 2_743_971_840
    want = jax.eval_shape(build_model(share).init_params, jax.random.key(0))
    # the tree also holds each layer's latent norm and the final norm
    assert sum(a.size for a in jax.tree.leaves(want)) == \
        share.num_params() + 27 * 512 + 2048
