"""Config system: model architectures, input shapes, runtime knobs.

Every assigned architecture is a ``ModelConfig`` in ``repro.configs.<id>``;
``repro.configs.registry`` maps ``--arch`` ids to configs.  Shape cells
(train_4k / prefill_32k / decode_32k / long_500k) are ``ShapeConfig`` entries
in ``SHAPES``.
"""

from __future__ import annotations

import dataclasses
import math
from dataclasses import dataclass, field


@dataclass(frozen=True)
class MoEConfig:
    n_experts: int = 0
    top_k: int = 0
    # 1 = every layer is MoE; 2 = every other layer (alternating), etc.
    layer_period: int = 1
    # Arctic: dense residual MLP in parallel with the expert MLP.
    dense_residual: bool = False
    dense_residual_ff: int = 0
    # Token-dropping capacity factor for the einsum dispatch path.
    capacity_factor: float = 1.25
    # Router softmax over experts; jitter etc. omitted (inference-focused).
    router_dtype: str = "float32"
    # "softmax": softmax over the experts, top-k. "sigmoid" (DeepSeek-V3's
    # noaux_tc): sigmoid scores; a correction bias steers selection only;
    # the top ``topk_groups`` of ``n_groups`` groups (each scored by its two
    # best) are kept, top-k inside them; weights are the chosen scores,
    # normalized to sum 1, times ``routed_scaling``.
    scoring: str = "softmax"
    n_groups: int = 1
    topk_groups: int = 1
    routed_scaling: float = 1.0
    n_shared: int = 0  # shared experts, each ``expert_ff`` wide, on every token
    expert_ff: int = 0  # 0: the model's d_ff
    # Expert parallelism: this chip holds experts [first_held, first_held +
    # n_held) of the n_experts the router scores, and the layer adds only
    # what they give. 0: all of them.
    n_held: int = 0
    first_held: int = 0

    def __post_init__(self):
        # the held share is computed by ``moe._held_share`` alone, which
        # routes as "sigmoid" does; the softmax path indexes all experts
        if self.n_held and self.scoring != "sigmoid":
            raise ValueError("a held share of the experts needs scoring='sigmoid'")

    @property
    def enabled(self) -> bool:
        return self.n_experts > 0

    @property
    def held(self) -> int:
        return self.n_held or self.n_experts


@dataclass(frozen=True)
class YarnConfig:
    """YaRN rope scaling (DeepSeek-V3's ``rope_scaling``)."""

    factor: float
    original_len: int
    beta_fast: float = 32.0
    beta_slow: float = 1.0
    mscale: float = 1.0
    mscale_all_dim: float = 1.0


@dataclass(frozen=True)
class SSMConfig:
    """Mamba-2 (SSD) hyperparameters."""

    d_state: int = 128
    d_conv: int = 4
    expand: int = 2
    head_dim: int = 64
    n_groups: int = 1
    chunk_size: int = 256

    def d_inner(self, d_model: int) -> int:
        return self.expand * d_model

    def n_heads(self, d_model: int) -> int:
        return self.d_inner(d_model) // self.head_dim


@dataclass(frozen=True)
class ModelConfig:
    name: str
    family: str  # dense | moe | hybrid | ssm | audio | vlm
    n_layers: int
    d_model: int
    n_heads: int
    n_kv_heads: int
    d_ff: int
    vocab_size: int
    d_head: int = 0  # 0 -> d_model // n_heads
    moe: MoEConfig = field(default_factory=MoEConfig)
    ssm: SSMConfig = field(default_factory=SSMConfig)
    # hybrid (Jamba): one attention layer every `attn_period` layers; the rest
    # are Mamba layers. 0 = pure attention stack; n_layers -> pure SSM.
    attn_period: int = 0
    # frontends for audio/vlm: stub providing precomputed embeddings.
    frontend: str = "none"  # none | audio_stub | vision_stub
    n_frontend_tokens: int = 0  # e.g. image patches prepended to the sequence
    qkv_bias: bool = False  # qwen1.5
    attn_out_bias: bool = False
    mlp_bias: bool = False
    nonparametric_ln: bool = False  # olmo
    rope_theta: float = 10000.0
    norm_eps: float = 1e-5
    tie_embeddings: bool = False
    act: str = "silu"  # silu | gelu
    dtype: str = "bfloat16"
    # activations' dtype where it is not the weights' ``dtype``: "float32"
    # keeps the residual stream and every activation in float32, matrix
    # products taking the bf16 weights at ``Precision.HIGH``, so that a
    # routed model picks the experts a float32 model picks
    act_dtype: str = ""
    # leading layers with a dense d_ff MLP before the MoE layers (DeepSeek-V3)
    first_k_dense: int = 0
    # multi-head latent attention (MLA), on when kv_lora_rank > 0: queries
    # through a q_lora_rank bottleneck; one cached latent row a token and
    # layer, kv_lora_rank wide, beside a rotary key qk_rope_head_dim wide
    # shared by all heads
    q_lora_rank: int = 0
    kv_lora_rank: int = 0
    qk_nope_head_dim: int = 0
    qk_rope_head_dim: int = 0
    v_head_dim: int = 0
    yarn: YarnConfig | None = None
    # --- notes for DESIGN.md §Arch-applicability / padding ---
    source: str = ""

    # ------------------------------------------------------------------
    @property
    def head_dim(self) -> int:
        return self.d_head if self.d_head else self.d_model // self.n_heads

    @property
    def is_mla(self) -> bool:
        return self.kv_lora_rank > 0

    @property
    def latent_width(self) -> int:
        """One cached MLA row: the latent and the shared rotary key."""
        return self.kv_lora_rank + self.qk_rope_head_dim

    @property
    def is_attention_free(self) -> bool:
        return self.family == "ssm"

    @property
    def has_ssm_layers(self) -> bool:
        return self.family in ("ssm", "hybrid")

    def attn_layer_ids(self) -> list[int]:
        """Indices of attention layers in the stack."""
        if self.family == "ssm":
            return []
        if self.attn_period and self.attn_period > 1:
            # Jamba: attention at position (attn_period - 1) of each period.
            return [
                i
                for i in range(self.n_layers)
                if i % self.attn_period == self.attn_period - 1
            ]
        return list(range(self.n_layers))

    def moe_layer_ids(self) -> list[int]:
        if not self.moe.enabled:
            return []
        p = self.moe.layer_period
        return [i for i in range(self.first_k_dense, self.n_layers) if (i % p) == (p - 1)]

    # ---------------- padding for TP divisibility -----------------------
    def padded_heads(self, tp: int) -> int:
        return _round_up(self.n_heads, tp)

    def padded_kv_heads(self, tp: int) -> int:
        # KV heads are replicated up to min(tp, n_heads) shards; when
        # n_kv_heads < tp we *replicate* KV per group rather than pad
        # (standard GQA TP). For layout purposes we keep the true count.
        return self.n_kv_heads

    def padded_vocab(self, tp: int) -> int:
        return _round_up(self.vocab_size, tp * 8)

    # ---------------- parameter counts ---------------------------------
    def param_count(self) -> int:
        """True (unpadded) parameter count."""
        return _param_count(self, active_only=False)

    def active_param_count(self) -> int:
        """Active params per token (MoE: top_k experts only)."""
        return _param_count(self, active_only=True)

    def kv_bytes_per_token(self, dtype_bytes: int = 2) -> int:
        """KVCache bytes per token across all attention layers."""
        n_attn = len(self.attn_layer_ids())
        if self.is_mla:
            return n_attn * self.latent_width * dtype_bytes
        return n_attn * 2 * self.n_kv_heads * self.head_dim * dtype_bytes

    def ssm_state_bytes(self, dtype_bytes: int = 4) -> int:
        if not self.has_ssm_layers:
            return 0
        n_ssm = self.n_layers - len(self.attn_layer_ids())
        nh = self.ssm.n_heads(self.d_model)
        conv_dim = self.ssm.d_inner(self.d_model) + 2 * self.ssm.n_groups * self.ssm.d_state
        per_layer = (
            nh * self.ssm.head_dim * self.ssm.d_state  # SSD state
            + conv_dim * (self.ssm.d_conv - 1)  # conv tail
        )
        return n_ssm * per_layer * dtype_bytes


def _round_up(x: int, m: int) -> int:
    return ((x + m - 1) // m) * m


def _param_count(cfg: ModelConfig, active_only: bool) -> int:
    d = cfg.d_model
    hd = cfg.head_dim
    attn_ids = set(cfg.attn_layer_ids())
    moe_ids = set(cfg.moe_layer_ids())
    total = cfg.vocab_size * d  # embed
    if not cfg.tie_embeddings:
        total += cfg.vocab_size * d  # lm head
    for i in range(cfg.n_layers):
        # mixer
        if i in attn_ids:
            total += d * cfg.n_heads * hd  # q
            total += 2 * d * cfg.n_kv_heads * hd  # k, v
            total += cfg.n_heads * hd * d  # o
        elif cfg.has_ssm_layers:
            ssm = cfg.ssm
            di = ssm.d_inner(d)
            nh = ssm.n_heads(d)
            conv_dim = di + 2 * ssm.n_groups * ssm.d_state
            total += d * (2 * di + 2 * ssm.n_groups * ssm.d_state + nh)  # in_proj
            total += conv_dim * ssm.d_conv  # conv
            total += nh * 2  # A_log, D
            total += di  # dt_bias ~ nh actually; negligible
            total += di * d  # out_proj
        # mlp
        if i in moe_ids:
            e = cfg.moe.top_k if active_only else cfg.moe.n_experts
            total += e * 3 * d * cfg.d_ff  # gate/up/down per expert
            total += d * cfg.moe.n_experts  # router
            if cfg.moe.dense_residual:
                total += 3 * d * cfg.moe.dense_residual_ff
        else:
            total += 3 * d * cfg.d_ff
        # norms
        if not cfg.nonparametric_ln:
            total += 2 * d
    return total


# ---------------------------------------------------------------------------
# Shape cells
# ---------------------------------------------------------------------------


@dataclass(frozen=True)
class ShapeConfig:
    name: str
    seq_len: int
    global_batch: int
    kind: str  # train | prefill | decode

    @property
    def is_decode(self) -> bool:
        return self.kind == "decode"


SHAPES: dict[str, ShapeConfig] = {
    "train_4k": ShapeConfig("train_4k", 4096, 256, "train"),
    "prefill_32k": ShapeConfig("prefill_32k", 32768, 32, "prefill"),
    "decode_32k": ShapeConfig("decode_32k", 32768, 128, "decode"),
    "long_500k": ShapeConfig("long_500k", 524288, 1, "decode"),
}


def shape_applicable(model: ModelConfig, shape: ShapeConfig) -> tuple[bool, str]:
    """long_500k only for sub-quadratic archs (ssm/hybrid), per DESIGN.md."""
    if shape.name == "long_500k" and model.family not in ("ssm", "hybrid"):
        return False, (
            f"{model.name} is a pure full-attention arch; long_500k requires "
            "sub-quadratic attention (skip recorded in DESIGN.md §5)"
        )
    return True, ""


# ---------------------------------------------------------------------------
# Runtime / parallelism knobs
# ---------------------------------------------------------------------------


@dataclass(frozen=True)
class RuntimeConfig:
    """Knobs that do not change model math, only execution."""

    remat: str = "full"  # none | full | dots (checkpoint policy for train)
    attn_chunk_q: int = 512
    attn_chunk_kv: int = 1024
    # decode KV strategy: "replicated" (paper-faithful baseline: KV heads
    # replicated across TP) or "pool_interleaved" (beyond-paper: sequence
    # blocks interleaved across chips, LSE-merge flash decode = Beluga O9).
    decode_kv: str = "pool_interleaved"
    moe_dispatch: str = "einsum"  # einsum | ragged | a2a (shard_map EP)
    # row-parallel matmuls: psum bf16 partials via shard_map (halves the TP
    # all-reduce bytes vs the partitioner's f32 reduction) — §Perf iter 4
    rowp_bf16_psum: bool = False
    # beluga pool
    pool_block_tokens: int = 16
    pool_blocks_per_shard: int = 4096
    use_fp8_kv: bool = False


def describe(cfg: ModelConfig) -> str:
    n = cfg.param_count()
    a = cfg.active_param_count()
    parts = [
        f"{cfg.name}: {cfg.family}",
        f"{cfg.n_layers}L d={cfg.d_model} H={cfg.n_heads}/{cfg.n_kv_heads}kv",
        f"ff={cfg.d_ff} vocab={cfg.vocab_size}",
        f"params={n/1e9:.1f}B",
    ]
    if cfg.moe.enabled:
        parts.append(
            f"moe={cfg.moe.n_experts}e top{cfg.moe.top_k} active={a/1e9:.1f}B"
        )
    return " ".join(parts)
