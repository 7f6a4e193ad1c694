"""The DeepSeek-V2-Lite serving cell, whole, at a size the CPU holds: its
own copy of the configuration and the traffic shrunk, through the same
harness, engine and reference as on the chip."""
from __future__ import annotations

import pytest

from bench import harness as H
from bench import work_mla_moe
from bench.tests import tiny

CELL = "serve_mla_moe_docs"
MLA_MOE = {"hidden_size": 64, "intermediate_size": 96, "moe_intermediate_size": 32,
           "num_hidden_layers": 3, "first_k_dense_replace": 1,
           "num_attention_heads": 4, "num_key_value_heads": 4,
           "kv_lora_rank": 16, "qk_nope_head_dim": 16, "qk_rope_head_dim": 8,
           "v_head_dim": 16, "n_routed_experts": 4, "num_experts_per_tok": 3,
           "n_shared_experts": 1, "vocab_size": 512, "expert_offset": 4,
           "published": {"n_routed_experts": 16, "vocab_size": 4096},
           "limits": {"max_logit_gap": 0.05}}


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    root = tiny.tiny_root(tmp_path_factory.mktemp("mla_moe"))
    tiny._patch(root / "bench" / "configs" / "deepseek-v2-lite-ep8.json", MLA_MOE)
    docs = root / "bench" / "traffic" / "doc_sessions.json"
    spec = H.load_json(docs)["spec"]
    spec["serving"]["max_seq"] = 80      # cap + the longest answer
    tiny._patch(docs, dict(tiny.SESSIONS, spec=spec, turns=[3, 5]))
    return root


def test_cell_runs_and_is_correct(root):
    out = tiny.run_cell(root, CELL, seed=2**33 + 9, seconds=1.0)
    assert out["correct"], out
    assert out["attempted"] > 0 and out["failed"] == 0
    assert set(out["metrics"]) == {"ttft_p90_ms", "tpot_p90_ms", "setup_s"}
    assert out["checks"]["max_logit_gap"]["value"] <= 0.05


def test_traced_run_reports_the_expert_counter_readers(root):
    out = tiny.run_cell(root, CELL, seed=11, seconds=3.0, trace=1)
    assert out["correct"], out
    m = out["metrics"]
    # the CPU trace holds no device plane: the roofline stays silent
    assert "mla_moe_step_roofline" not in m
    assert 0 < m["mla_moe_serve_mfu"]["value"] <= 100
    # 4 of 16 experts held, 3 chosen a token: 0.75 in expectation
    assert 0.2 < m["held_expert_hits.mla_moe"]["value"] < 1.5
    assert m["itl_p90_ms.serve"]["value"] > 0


def test_decode_step_reads_each_weight_of_the_served_tree_once():
    """At the published share with every held expert chosen, the bytes of
    a decode step at context 0 are the program's whole parameter tree less
    the embedding rows not looked up, the router read as float32."""
    import jax
    from bench.drivers.serve_mla_moe import program_model
    conf = H.load_json(H.ROOT / "bench" / "configs" / "deepseek-v2-lite-ep8.json")
    model = program_model(conf)
    tree = jax.eval_shape(model.init_params, jax.random.key(0))
    total = sum(a.size for a in jax.tree.leaves(tree))
    d, v = conf["hidden_size"], conf["vocab_size"]
    router = ((conf["num_hidden_layers"] - conf["first_k_dense_replace"])
              * d * conf["published"]["n_routed_experts"])
    flops, nbytes = work_mla_moe.decode_step(conf, 0, conf["n_routed_experts"])
    assert nbytes == 2 * (total - v * d + d) + 2 * router
    # at context 0 a step is two operations a weight of its matmuls
    assert flops < 2 * total
    # each cached position adds its latent row and rotary key, every layer
    _, more = work_mla_moe.decode_step(conf, 1, conf["n_routed_experts"])
    assert more - nbytes == 2 * conf["num_hidden_layers"] * (
        conf["kv_lora_rank"] + conf["qk_rope_head_dim"])
