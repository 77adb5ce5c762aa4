"""Model/shape/run configuration dataclasses + the arch registry."""

from __future__ import annotations

import dataclasses
from typing import Optional, Tuple

import jax.numpy as jnp


@dataclasses.dataclass(frozen=True)
class ModelConfig:
    """Architecture hyperparameters (one instance per assigned arch)."""

    name: str
    family: str     # dense | moe | hybrid | hybrid_moe | vlm | ssm | audio
    num_layers: int
    d_model: int
    num_heads: int                   # query heads; 0 for attention-free
    num_kv_heads: int
    d_ff: int
    vocab_size: int
    head_dim: int = 0                # 0 → d_model // num_heads

    # attention details
    rope_theta: float = 1e4
    attn_bias: bool = False          # qwen1.5-style QKV bias
    sliding_window: int = 0          # 0 = full attention; >0 = SWA width

    rope: bool = True                # rotary embedding on q and k

    # MLP / head variants
    mlp_type: str = "swiglu"         # swiglu (3 mats) | gelu (2 mats)
    tie_embeddings: bool = False     # lm_head = embedᵀ

    # MoE
    num_experts: int = 0
    num_experts_per_tok: int = 0
    capacity_factor: float = 1.25
    # dropless expert layer (hybrid_moe): this chip's share of the routed
    # experts is [experts_first, experts_first + experts_held); the router
    # still scores all num_experts. 0 = every expert held.
    experts_held: int = 0
    experts_first: int = 0
    routed_scale: float = 1.0        # multiplies the normalised top-k weights
    shared_d_ff: int = 0             # width of the one shared expert; 0 = none

    # SSM (Mamba2 / SSD)
    ssm_state: int = 0
    ssm_headdim: int = 64
    ssm_expand: int = 2
    ssm_chunk: int = 64
    ssm_conv: int = 4
    ssm_nheads: int = 0              # 0 → d_inner = ssm_expand · d_model
    ssm_groups: int = 1              # B/C groups; head h reads h // (H/G)
    ssm_conv_bias: bool = False

    # hybrid (zamba2-style shared attention block cadence)
    attn_every: int = 0              # 0 = no shared block
    # hybrid_moe: one mixer per pre-norm block, M = Mamba2, E = MoE,
    # * = attention (Nemotron-H's hybrid_override_pattern)
    layer_pattern: str = ""

    # modality frontend stub
    frontend: str = "none"           # none | vision | audio

    norm_eps: float = 1e-5
    param_dtype: str = "bfloat16"    # big configs; smoke tests use float32
    remat: bool = True               # checkpoint the layer-scan body
    # √L nested remat: outer scan over G groups × inner scan over L/G
    # layers, both checkpointed → G + L/G live boundary activations instead
    # of L (88-layer granite: 74 GB → ~16 GB/device; EXPERIMENTS §Perf
    # it.6) at the cost of one extra forward recompute.
    nested_remat: bool = True

    # provenance
    source: str = ""                 # citation / hf id [tier]

    @property
    def resolved_head_dim(self) -> int:
        if self.head_dim:
            return self.head_dim
        return self.d_model // max(self.num_heads, 1)

    @property
    def padded_vocab(self) -> int:
        """Vocab padded to 256 so embed/lm_head always TP-shard (standard
        production practice; padded logits are masked to −inf in the loss).
        param_count() stays unpadded — the pad is honest compute overhead
        visible in the MODEL_FLOPS/HLO ratio."""
        return -(-self.vocab_size // 256) * 256

    @property
    def d_inner(self) -> int:        # mamba2 inner width
        if self.ssm_nheads:
            return self.ssm_nheads * self.ssm_headdim
        return self.ssm_expand * self.d_model

    @property
    def ssm_heads(self) -> int:
        return self.d_inner // self.ssm_headdim

    @property
    def held_experts(self) -> int:
        return self.experts_held or self.num_experts

    @property
    def is_subquadratic(self) -> bool:
        """Eligible for the long_500k cell (DESIGN §4 skip rationale)."""
        return (self.family in ("ssm", "hybrid", "hybrid_moe")
                or self.sliding_window > 0)

    @property
    def dtype(self):
        return jnp.dtype(self.param_dtype)

    def _mamba_params(self) -> int:
        D, di, H = self.d_model, self.d_inner, self.ssm_heads
        conv_ch = di + 2 * self.ssm_groups * self.ssm_state
        return (D * (di + conv_ch + H)               # in_proj
                + self.ssm_conv * conv_ch            # depthwise conv
                + (conv_ch if self.ssm_conv_bias else 0)
                + 3 * H + di + di * D)   # A_log, D, dt_bias, norm, out_proj

    def _pattern_param_count(self) -> int:
        """hybrid_moe: the held experts of each E block are counted."""
        D, V = self.d_model, self.vocab_size
        Hq, Hkv, Dh = self.num_heads, self.num_kv_heads, self.resolved_head_dim
        per = {
            "M": self._mamba_params(),
            "E": (D * self.num_experts
                  + self.held_experts * 2 * D * self.d_ff
                  + 2 * D * self.shared_d_ff),
            "*": D * Hq * Dh + 2 * D * Hkv * Dh + Hq * Dh * D,
        }
        n = sum(D + per[k] for k in self.layer_pattern)
        return n + V * D + D + (0 if self.tie_embeddings else D * V)

    def param_count(self) -> int:
        """Analytic parameter count (embedding + layers + head)."""
        if self.layer_pattern:
            return self._pattern_param_count()
        D, F, V = self.d_model, self.d_ff, self.vocab_size
        Hq, Hkv, Dh = self.num_heads, self.num_kv_heads, self.resolved_head_dim
        n = V * D                                    # embed
        attn = D * Hq * Dh + 2 * D * Hkv * Dh + Hq * Dh * D
        if self.attn_bias:
            attn += (Hq + 2 * Hkv) * Dh
        mats = 3 if self.mlp_type == "swiglu" else 2
        mlp = mats * D * F
        moe_mlp = self.num_experts * mats * D * F + D * self.num_experts
        ssm = 0
        if self.family in ("ssm", "hybrid"):
            ssm = self._mamba_params()
        per_layer = 2 * D  # norms
        if self.family == "moe":
            per_layer += attn + moe_mlp
        elif self.family == "ssm":
            per_layer = D + ssm
        elif self.family == "hybrid":
            per_layer = D + ssm
        else:
            per_layer += attn + mlp
        n += self.num_layers * per_layer
        if self.family == "hybrid" and self.attn_every:
            n += attn + mlp + 2 * D                  # one shared block
        n += D                                       # final norm
        if not self.tie_embeddings:
            n += D * V                               # untied lm head
        return n

    def active_param_count(self) -> int:
        """Params touched per token (MoE: top-k of E experts)."""
        if self.family != "moe" or not self.num_experts:
            return self.param_count()
        D, F = self.d_model, self.d_ff
        mats = 3 if self.mlp_type == "swiglu" else 2
        inactive = (self.num_experts - self.num_experts_per_tok) * mats * D * F
        return self.param_count() - self.num_layers * inactive


@dataclasses.dataclass(frozen=True)
class ShapeSpec:
    """One input-shape cell from the assignment."""

    name: str                        # train_4k | prefill_32k | decode_32k | long_500k
    seq_len: int
    global_batch: int
    kind: str                        # train | prefill | decode

    @property
    def is_decode(self) -> bool:
        return self.kind == "decode"


SHAPES: dict[str, ShapeSpec] = {
    "train_4k": ShapeSpec("train_4k", 4096, 256, "train"),
    "prefill_32k": ShapeSpec("prefill_32k", 32768, 32, "prefill"),
    "decode_32k": ShapeSpec("decode_32k", 32768, 128, "decode"),
    "long_500k": ShapeSpec("long_500k", 524288, 1, "decode"),
}


def shape_cells(cfg: ModelConfig) -> list[str]:
    """The shape cells this arch runs (long_500k needs sub-quadratic attn)."""
    cells = ["train_4k", "prefill_32k", "decode_32k"]
    if cfg.is_subquadratic:
        cells.append("long_500k")
    return cells
