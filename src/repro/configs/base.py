"""Model/config registry for the assigned architectures.

Every architecture is a ``ModelConfig``; reduced smoke variants share the
same code paths with tiny dimensions.  Input-shape sets (train_4k /
prefill_32k / decode_32k / long_500k) are defined here too, so dryrun,
benchmarks and tests agree on every (arch x shape) cell.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Callable, Optional


@dataclasses.dataclass(frozen=True)
class MoEConfig:
    num_experts: int                # the router's outputs
    top_k: int
    d_ff_expert: int
    # None: no capacity, every (token, choice) is computed (dropless);
    # a number: tokens past ``tokens * top_k / num_experts * factor`` per
    # expert are dropped to the residual path
    capacity_factor: Optional[float] = 1.25
    locality_bias: float = 0.0      # paper-technique: bias router toward
                                    # experts resident on the token's devices
    router_aux_weight: float = 0.01
    num_shared: int = 0             # shared experts, one SwiGLU of
                                    # num_shared * d_ff_expert, on every token
    norm_topk_prob: bool = True     # renormalise the top-k gates to sum 1
    routed_scaling_factor: float = 1.0
    # expert parallelism: this chip holds experts
    # [expert_offset, expert_offset + experts_held); None holds them all
    experts_held: Optional[int] = None
    expert_offset: int = 0

    @property
    def held(self) -> int:
        return self.num_experts if self.experts_held is None else self.experts_held


@dataclasses.dataclass(frozen=True)
class MLAConfig:
    q_lora_rank: Optional[int]      # 0 or None: queries projected directly
    kv_lora_rank: int
    qk_nope_head_dim: int
    qk_rope_head_dim: int
    v_head_dim: int


@dataclasses.dataclass(frozen=True)
class RopeScaling:
    """YaRN rotary scaling (arXiv:2309.00071) as DeepSeek-V2 publishes it
    under ``rope_scaling``."""
    factor: float
    original_max_position_embeddings: int
    beta_fast: float = 32.0
    beta_slow: float = 1.0
    mscale: float = 1.0
    mscale_all_dim: float = 0.0


@dataclasses.dataclass(frozen=True)
class EncoderConfig:
    """Encoder for enc-dec archs (whisper). Frontend is a stub: input_specs
    provide precomputed frame embeddings (post-conv)."""
    num_layers: int
    num_frames: int                 # padded to a lane-friendly multiple
    d_model: int
    num_heads: int
    d_ff: int


@dataclasses.dataclass(frozen=True)
class VisionConfig:
    """Vision frontend stub for VLMs: precomputed patch embeddings,
    already projected to the decoder width."""
    num_image_tokens: int
    cross_every: int                # one cross-attn layer per this many


@dataclasses.dataclass(frozen=True)
class ModelConfig:
    name: str
    family: str                     # dense | moe | hybrid | ssm | encdec | vlm
    num_layers: int
    d_model: int
    num_heads: int
    num_kv_heads: int
    head_dim: int
    d_ff: int
    vocab_size: int
    # layer pattern: repeating kinds; remainder unrolled.
    #   kinds: "full" global attn, "local" sliding-window attn,
    #          "rglru" recurrent block, "cross" self+cross-attn, "rwkv"
    pattern: tuple[str, ...] = ("full",)
    attn_window: int = 0            # sliding window for "local" layers
    qkv_bias: bool = False
    rope_theta: float = 1e4
    rope_scaling: Optional[RopeScaling] = None
    act: str = "silu"
    norm: str = "rms"               # rms | layer
    tie_embeddings: bool = True
    moe: Optional[MoEConfig] = None
    first_k_dense: int = 0          # leading layers with a dense d_ff MLP
                                    # in place of the MoE
    mla: Optional[MLAConfig] = None
    encoder: Optional[EncoderConfig] = None
    vision: Optional[VisionConfig] = None
    # rwkv
    rwkv_head_dim: int = 64
    # distribution hints
    fsdp: bool = False              # shard weights over the data axis too
    remat: bool = True
    microbatches: int = 1           # grad-accumulation steps per train step
    dtype: str = "bfloat16"
    # long_500k applicability (sub-quadratic attention path exists)
    subquadratic: bool = False

    # -- derived -----------------------------------------------------------
    def vocab_padded(self, multiple: int = 128) -> int:
        return math.ceil(self.vocab_size / multiple) * multiple

    @property
    def q_dim(self) -> int:
        return self.num_heads * self.head_dim

    @property
    def kv_dim(self) -> int:
        return self.num_kv_heads * self.head_dim

    def layer_kinds(self) -> list[str]:
        """Expanded per-layer kind list of length num_layers: the leading
        dense layers, then the pattern repeated."""
        n = self.num_layers - self.first_k_dense
        reps = n // len(self.pattern)
        rem = n - reps * len(self.pattern)
        return (["full"] * self.first_k_dense + list(self.pattern) * reps
                + list(self.pattern[:rem]))

    def _attn_params(self) -> int:
        d = self.d_model
        if self.mla is None:
            return d * (self.q_dim + 2 * self.kv_dim) + self.q_dim * d
        m, h = self.mla, self.num_heads
        qk = h * (m.qk_nope_head_dim + m.qk_rope_head_dim)
        q = d * m.q_lora_rank + m.q_lora_rank * qk if m.q_lora_rank else d * qk
        return (q + d * (m.kv_lora_rank + m.qk_rope_head_dim)
                + m.kv_lora_rank * h * (m.qk_nope_head_dim + m.v_head_dim)
                + h * m.v_head_dim * d)

    def _expert_params(self) -> int:
        """One routed expert's SwiGLU."""
        return 3 * self.d_model * self.moe.d_ff_expert

    def num_params(self) -> int:
        """Approximate parameter count (embeddings + blocks), of the
        experts this configuration holds."""
        d, f, v = self.d_model, self.d_ff, self.vocab_padded()
        n_attn = self._attn_params()
        n_mlp = 3 * d * f if self.act in ("silu",) else 2 * d * f
        total = v * d * (1 if self.tie_embeddings else 2)
        for i, kind in enumerate(self.layer_kinds()):
            if kind in ("full", "local", "cross"):
                total += n_attn + n_mlp + 2 * d
                if kind == "cross":
                    total += n_attn + d
            elif kind == "rglru":
                total += 2 * d * d + d * d + n_mlp + 2 * d   # branches+proj
            elif kind == "rwkv":
                total += 5 * d * d + 2 * d * f + 4 * d
            if self.is_moe_layer(i, kind):
                m = self.moe
                total += (-n_mlp + m.held * self._expert_params()
                          + m.num_shared * self._expert_params()
                          + d * m.num_experts)
        return total

    def is_moe_layer(self, i: int, kind: str) -> bool:
        return (self.moe is not None and kind in ("full", "local")
                and i >= self.first_k_dense)

    def active_params(self) -> int:
        """Active parameters per token (MoE: only top-k of the held experts
        count, and the shared experts)."""
        if self.moe is None:
            return self.num_params()
        m = self.moe
        inactive = max(m.held - m.top_k, 0) * self._expert_params()
        n_moe = sum(self.is_moe_layer(i, k)
                    for i, k in enumerate(self.layer_kinds()))
        return self.num_params() - n_moe * inactive


# ---------------------------------------------------------------------------
# input shapes (assigned): every LM arch carries these four cells
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class ShapeConfig:
    name: str
    seq_len: int
    global_batch: int
    kind: str                       # "train" | "prefill" | "decode"


SHAPES: dict[str, ShapeConfig] = {
    "train_4k": ShapeConfig("train_4k", 4096, 256, "train"),
    "prefill_32k": ShapeConfig("prefill_32k", 32768, 32, "prefill"),
    "decode_32k": ShapeConfig("decode_32k", 32768, 128, "decode"),
    "long_500k": ShapeConfig("long_500k", 524288, 1, "decode"),
}


_REGISTRY: dict[str, Callable[[], ModelConfig]] = {}


def register(name: str):
    def deco(fn: Callable[[], ModelConfig]):
        _REGISTRY[name] = fn
        return fn
    return deco


def get_config(name: str) -> ModelConfig:
    if name not in _REGISTRY:
        # import the arch modules lazily on first miss
        from . import _load_all  # noqa: F401  (populates the registry)
        _load_all()
    if name not in _REGISTRY:
        raise KeyError(f"unknown arch {name!r}; known: {sorted(_REGISTRY)}")
    return _REGISTRY[name]()


def list_archs() -> list[str]:
    from . import _load_all
    _load_all()
    return sorted(_REGISTRY)


def cell_is_applicable(cfg: ModelConfig, shape: ShapeConfig) -> tuple[bool, str]:
    """Whether an (arch x shape) cell runs; reason if skipped (DESIGN §5)."""
    if shape.name == "long_500k" and not cfg.subquadratic:
        return False, "pure full-attention arch: long_500k needs sub-quadratic attention"
    return True, ""
