"""Document sessions, open loop, through ``repro.serving.engine.ServingEngine``
on a DeepSeek-V2-Lite share: latent attention and shared-plus-routed
expert layers, eight of the 64 routed experts held on this chip.

The window, its end-to-end metrics and the check are those of
``bench/drivers/serve.py`` (``ServeCell``), with the model, its weights and
its float32 reference (``bench/reference/deepseek_v2.py``) in place of
Qwen2's.  The greedy prefill and decode programs are shared by every
replica, so set-up warms each prompt length once, on one replica.  The
reference holds the served bfloat16 weights and upcasts one layer at a
time, so the check at the cache's full length fits after the program is
released.
"""
from __future__ import annotations

import dataclasses

import numpy as np

from bench import harness as H
from bench.drivers.serve import ServeCell, _same_layout, served_positions
from bench.reference import deepseek_v2 as ref


def program_model(conf: dict):
    """The program's model at the configuration's sizes and share."""
    from repro.configs import MLAConfig, RopeScaling, get_config
    from repro.models.model import build_model
    cfg = get_config(conf["program_arch"])
    y = conf["rope_scaling"]
    moe = dataclasses.replace(
        cfg.moe, num_experts=conf["published"]["n_routed_experts"],
        top_k=conf["num_experts_per_tok"], d_ff_expert=conf["moe_intermediate_size"],
        num_shared=conf["n_shared_experts"], norm_topk_prob=conf["norm_topk_prob"],
        routed_scaling_factor=float(conf["routed_scaling_factor"]),
        experts_held=conf["n_routed_experts"], expert_offset=conf["expert_offset"])
    mla = MLAConfig(q_lora_rank=conf["q_lora_rank"] or 0,
                    kv_lora_rank=conf["kv_lora_rank"],
                    qk_nope_head_dim=conf["qk_nope_head_dim"],
                    qk_rope_head_dim=conf["qk_rope_head_dim"],
                    v_head_dim=conf["v_head_dim"])
    rope = RopeScaling(
        factor=float(y["factor"]),
        original_max_position_embeddings=y["original_max_position_embeddings"],
        beta_fast=float(y["beta_fast"]), beta_slow=float(y["beta_slow"]),
        mscale=float(y["mscale"]), mscale_all_dim=float(y["mscale_all_dim"]))
    return build_model(dataclasses.replace(
        cfg, num_layers=conf["num_hidden_layers"], d_model=conf["hidden_size"],
        num_heads=conf["num_attention_heads"],
        num_kv_heads=conf["num_key_value_heads"], d_ff=conf["intermediate_size"],
        vocab_size=conf["vocab_size"], rope_theta=float(conf["rope_theta"]),
        first_k_dense=conf["first_k_dense_replace"],
        tie_embeddings=conf["tie_word_embeddings"], rope_scaling=rope,
        mla=mla, moe=moe))


def setup(ctx):
    return MlaMoeCell(ctx)


class MlaMoeCell(ServeCell):
    def __init__(self, ctx: H.Context):
        from repro import spec
        from repro.serving.engine import Request, ServingEngine
        self.ctx = ctx
        conf, tr = ctx.cell.config, ctx.cell.traffic
        self.model = program_model(conf)
        params = ref.make_params(conf, H.prng_key(ctx.seed))
        _same_layout(params, self.model)
        self.engine = ServingEngine(self.model, params,
                                    spec=spec.RuntimeSpec.from_dict(tr["spec"]))
        self.grab_start: dict[int, float] = {}
        for rep in self.engine.replicas:
            rep.run_batch = self._timed_grab(rep.run_batch)
        # warm-up: every prompt length the traffic can send, with the decode
        # step; the replicas share these programs
        zeros = np.zeros(tr["cap"], np.int32)
        rep = self.engine.replicas[0]
        for n in range(tr["round_to"], tr["cap"] + 1, tr["round_to"]):
            rep.run(Request(uid=-1, tokens=zeros[:n], max_new=2))
        self.requests: list = []

    def _max_gap(self, gaps_of) -> tuple[float, int]:
        conf, tr = self.ctx.cell.config, self.ctx.cell.traffic
        params = ref.make_params(conf, H.prng_key(self.ctx.seed))
        max_seq, out_max = tr["spec"]["serving"]["max_seq"], tr["output"]["max"]
        worst, n_tokens = 0.0, 0
        for r in self._sample():
            toks, at, target = served_positions(r.tokens, list(r.out_tokens),
                                                max_seq, out_max)
            g = np.asarray(gaps_of(params, conf, toks, at, target))
            gap = float(np.max(g[:len(r.out_tokens)]))
            # a token the reference cannot place reads not-a-number: no
            # limit admits it
            worst = max(worst, gap if np.isfinite(gap) else 1e300)
            n_tokens += len(r.out_tokens)
        return worst, n_tokens

    def control(self) -> dict[str, float]:
        """The control's reading: at the same positions of the same
        requests, the gap of the token the float8 reference puts first."""
        worst, _ = self._max_gap(
            lambda p, c, toks, at, target: ref.control_gaps(p, c, toks, at))
        return {"max_logit_gap": worst}

    def check(self) -> dict[str, tuple[float, float]]:
        worst, n_tokens = self._max_gap(ref.gaps)
        H.log(f"serve: checked {n_tokens} served tokens against the float32 "
              f"reference")
        limit = float(self.ctx.cell.config["limits"]["max_logit_gap"])
        return {"max_logit_gap": (worst, limit)}
