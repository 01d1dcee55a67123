"""Architecture config system.

One frozen dataclass describes every supported family (dense GQA / MoE /
MLA / SSM / hybrid / encoder-only / modality-backbone).  Configs are pure
data — models/ builds the parameter tree and step functions from them, and
launch/ derives sharding and input specs.
"""

from __future__ import annotations

import dataclasses
from typing import Optional


@dataclasses.dataclass(frozen=True)
class ArchConfig:
    name: str
    family: str                  # dense | moe | ssm | hybrid | vlm | audio
    n_layers: int
    d_model: int
    n_heads: int
    n_kv_heads: int
    d_ff: int
    vocab: int

    head_dim: Optional[int] = None
    qkv_bias: bool = False
    mlp: str = "swiglu"          # swiglu | squared_relu | gelu
    causal: bool = True          # False → encoder-only (no decode step)
    norm: str = "rmsnorm"        # rmsnorm | layernorm
    tie_embeddings: bool = False
    rope_theta: float = 1e4
    norm_eps: float = 1e-6       # RMSNorm epsilon (published rms_norm_eps)

    # MoE
    n_experts: int = 0
    n_shared_experts: int = 0
    top_k: int = 0
    moe_d_ff: int = 0            # per-expert FFN width (0 → use d_ff)
    moe_every: int = 1           # MoE layer cadence (1 = every layer)
    first_dense: int = 0         # leading dense layers (deepseek style)

    # MLA (deepseek)
    mla: bool = False
    kv_lora_rank: int = 0
    qk_rope_dim: int = 64
    v_head_dim: int = 0

    # SSM
    ssm: str = ""                # "" | mamba1 | mamba2
    ssm_state: int = 0
    d_conv: int = 4
    expand: int = 2              # d_inner = expand * d_model
    ssm_heads: int = 0           # mamba2 multihead (0 → derived)

    # hybrid (zamba2): one shared attention+MLP block applied every k blocks
    attn_every: int = 0
    window: int = 0              # sliding-window size for long-context attn

    # modality frontend stub: inputs are precomputed embeddings
    frontend: str = ""           # "" | vision | audio

    # MoE capacity (training dispatch); decode uses the exact dense path
    capacity_factor: float = 1.25

    # numerics / training
    remat: bool = True
    optimizer_dtype: str = "float32"   # moment dtype; "bfloat16" for 100B+
    # decode KV-cache storage dtype — "float8_e4m3fn" halves HBM bytes for
    # the KV-bound decode cells (the on-chip analogue of the paper's
    # elastic precision fetch; values upcast to bf16 inside attention)
    kv_dtype: str = "bfloat16"
    # §Perf lever: FSDP the params' d_model dim over the data axis (True)
    # or replicate params across data (False — pure TP/EP).  FSDP's weight
    # -grad backward all-gathers GLOBAL activations per layer under SPMD;
    # for models whose TP-sharded params fit HBM, turning it off trades
    # param memory for a large collective-volume cut.
    fsdp: bool = True
    # §Perf lever (decode): "batch_dp" shards the request batch over data
    # (weights FSDP-gathered per step); "replicated" replicates the tiny
    # decode batch and keeps weights 2D-stationary (params/256 per chip,
    # psum on activations) — the right layout for 100B+ decode.
    decode_layout: str = "batch_dp"
    # §Perf lever: shard the embedding TABLE's vocab dim over the model
    # axis (True) or leave vocab unsharded and FSDP only the d_model dim
    # (False).  vocab-sharded tables force an all-gather feeding the token
    # gather, which XLA schedules cross-pod on the 2×16×16 mesh.
    embed_vocab_shard: bool = True

    # --- derived -------------------------------------------------------------
    @property
    def hd(self) -> int:
        return self.head_dim or (self.d_model // max(self.n_heads, 1))

    @property
    def d_inner(self) -> int:
        return self.expand * self.d_model

    @property
    def is_encoder_only(self) -> bool:
        return not self.causal

    @property
    def supports_long_context(self) -> bool:
        """Sub-quadratic decode: SSM state or sliding-window hybrid."""
        return self.family in ("ssm", "hybrid")

    @property
    def uses_tokens(self) -> bool:
        return self.frontend == ""

    def param_count(self) -> float:
        """Approximate parameter count (for roofline MODEL_FLOPS)."""
        d, L, V = self.d_model, self.n_layers, self.vocab
        emb = V * d * (1 if self.tie_embeddings else 2)
        if self.ssm == "mamba1":
            di, N = self.d_inner, self.ssm_state
            dt_rank = max(d // 16, 1)
            per = (d * 2 * di + di * self.d_conv + di * (dt_rank + 2 * N)
                   + dt_rank * di + di * N + di + di * d)
            return emb + L * per
        att = 0.0
        if self.mla:
            r, hd = self.kv_lora_rank, self.hd
            vh = self.v_head_dim or hd
            att = (d * self.n_heads * (hd + self.qk_rope_dim)
                   + d * (r + self.qk_rope_dim)
                   + r * self.n_heads * (hd + vh)
                   + self.n_heads * vh * d)
        elif self.n_heads:
            att = (d * self.n_heads * self.hd + 2 * d * self.n_kv_heads * self.hd
                   + self.n_heads * self.hd * d)
        mlp_mult = 3 if self.mlp == "swiglu" else 2
        f = self.d_ff
        dense_mlp = mlp_mult * d * f
        per_layer = att + dense_mlp
        total = emb + L * per_layer
        if self.n_experts:
            fe = self.moe_d_ff or f
            n_moe = max((L - self.first_dense) // max(self.moe_every, 1), 0)
            moe = n_moe * (self.n_experts + self.n_shared_experts) * mlp_mult * d * fe
            total = emb + self.first_dense * per_layer + n_moe * att + moe
            if self.family == "hybrid":
                pass
        if self.family == "hybrid":
            # mamba2 backbone + one shared attention block
            di, N = self.d_inner, self.ssm_state
            per_m = d * 2 * di + di * self.d_conv + di * N + di + di * d
            shared = att + dense_mlp
            total = emb + L * per_m + shared
        return float(total)

    def active_param_count(self) -> float:
        """Params touched per token (MoE: top-k + shared experts only)."""
        if not self.n_experts:
            return self.param_count()
        full = self.param_count()
        fe = self.moe_d_ff or self.d_ff
        mlp_mult = 3 if self.mlp == "swiglu" else 2
        n_moe = max((self.n_layers - self.first_dense) // max(self.moe_every, 1), 0)
        routed_all = n_moe * self.n_experts * mlp_mult * self.d_model * fe
        routed_active = n_moe * self.top_k * mlp_mult * self.d_model * fe
        return full - routed_all + routed_active  # shared experts stay in

    def kv_bytes_per_token(self, elem_bytes: int = 2) -> float:
        if self.ssm == "mamba1":
            return 0.0  # constant-size state, not per-token
        if self.mla:
            return self.n_layers * (self.kv_lora_rank + self.qk_rope_dim) * elem_bytes
        n_attn = self.n_layers
        if self.attn_every:
            n_attn = self.n_layers // self.attn_every
        return n_attn * 2 * self.n_kv_heads * self.hd * elem_bytes


@dataclasses.dataclass(frozen=True)
class ShapeConfig:
    """One input-shape cell (assigned shapes pool)."""

    name: str
    seq_len: int
    global_batch: int
    kind: str                    # train | prefill | decode

    @property
    def is_decode(self) -> bool:
        return self.kind == "decode"


TRAIN_4K = ShapeConfig("train_4k", 4096, 256, "train")
PREFILL_32K = ShapeConfig("prefill_32k", 32768, 32, "prefill")
DECODE_32K = ShapeConfig("decode_32k", 32768, 128, "decode")
LONG_500K = ShapeConfig("long_500k", 524288, 1, "decode")
SHAPES = {s.name: s for s in (TRAIN_4K, PREFILL_32K, DECODE_32K, LONG_500K)}


def applicable_shapes(cfg: ArchConfig) -> list[ShapeConfig]:
    """Shape cells that are well-defined for this architecture.

    * encoder-only archs have no decode step → skip decode shapes;
    * ``long_500k`` needs sub-quadratic attention → SSM / hybrid only
      (skips recorded in DESIGN.md §Arch-applicability).
    """
    out = [TRAIN_4K, PREFILL_32K]
    if not cfg.is_encoder_only:
        out.append(DECODE_32K)
        if cfg.supports_long_context:
            out.append(LONG_500K)
    return out


def smoke_config(cfg: ArchConfig) -> ArchConfig:
    """Reduced same-family config for CPU smoke tests."""
    return dataclasses.replace(
        cfg,
        n_layers=min(cfg.n_layers, 2 if not cfg.attn_every else cfg.attn_every),
        d_model=128,
        n_heads=4,
        n_kv_heads=min(cfg.n_kv_heads, 4) or 4,
        head_dim=32,
        d_ff=256,
        vocab=512,
        n_experts=min(cfg.n_experts, 4),
        n_shared_experts=min(cfg.n_shared_experts, 1),
        top_k=min(cfg.top_k, 2),
        moe_d_ff=64 if cfg.moe_d_ff else 0,
        kv_lora_rank=64 if cfg.mla else 0,
        qk_rope_dim=16 if cfg.mla else 64,
        v_head_dim=32 if cfg.mla else 0,
        ssm_state=min(cfg.ssm_state, 16) if cfg.ssm_state else 0,
        ssm_heads=4 if cfg.ssm == "mamba2" else 0,
        first_dense=min(cfg.first_dense, 1),
        capacity_factor=4.0,   # smoke: capacity can never drop → decode==forward
        window=min(cfg.window, 64) if cfg.window else 0,
        remat=False,
    )
