"""The work a step needs, from the algorithm's shapes alone.

Operations and bytes here are what the algorithm needs, never what a
kernel happens to move, so a kernel that moves fewer bytes shows as a
higher share of its roofline.  ``model_flops_infer``'s 2·N per token is
copied from the program's ``roofline/analysis.py``.
"""
from __future__ import annotations


def jacobi_sweep(sites: int, itemsize: int = 4) -> tuple[float, float]:
    """(flops, bytes) of one six-point sweep: five adds and one multiply
    per site; one read and one write of the lattice."""
    return 6.0 * sites, 2.0 * itemsize * sites


def least_time(flops: float, nbytes: float, peaks: dict,
               chips: int = 1) -> float:
    """The least time the chips could take: the larger of operations over
    peak FLOP/s and bytes over peak bandwidth."""
    return max(flops / (chips * peaks["flops_bf16"]),
               nbytes / (chips * peaks["hbm_bytes_per_s"]))


def qwen2_params(sizes: dict) -> tuple[int, int]:
    """(matmul weights, all parameters) of a Qwen2 decoder with tied
    embeddings: the embedding table is also the output head."""
    d, f, v = sizes["hidden_size"], sizes["intermediate_size"], sizes["vocab_size"]
    hd = d // sizes["num_attention_heads"]
    q = sizes["num_attention_heads"] * hd
    kv = sizes["num_key_value_heads"] * hd
    layer_mm = d * (q + 2 * kv) + q * d + 3 * d * f
    layer_other = q + 2 * kv + 2 * d                 # qkv bias, two norms
    n_layers = sizes["num_hidden_layers"]
    mm = n_layers * layer_mm + v * d
    return mm, mm + n_layers * layer_other + d


def qwen2_decode_step(sizes: dict, context: int,
                      itemsize: int = 2) -> tuple[float, float]:
    """(flops, bytes) of one batch-1 decode step that attends over
    ``context`` cached positions: every weight read once, the cache's
    keys and values up to ``context`` read once."""
    mm, total = qwen2_params(sizes)
    d = sizes["hidden_size"]
    hd = d // sizes["num_attention_heads"]
    n_layers = sizes["num_hidden_layers"]
    attn_flops = n_layers * 4 * sizes["num_attention_heads"] * hd * context
    kv_bytes = (n_layers * 2 * sizes["num_key_value_heads"] * hd * context
                * itemsize)
    return (model_flops_infer(mm, 1) + attn_flops,
            float(total * itemsize + kv_bytes))


def model_flops_infer(n_active_params: int, tokens: int) -> float:
    """2·N·D for inference."""
    return 2.0 * n_active_params * tokens
