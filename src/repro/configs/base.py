"""Config system: architecture, input-shape, and run configuration dataclasses.

Every assigned architecture gets one ``repro/configs/<id>.py`` exporting ``ARCH``
(exact assigned hyperparameters, source cited) and ``SMOKE`` (a reduced variant of
the same family for CPU tests). ``repro.configs.registry`` resolves ``--arch`` ids.
"""

from __future__ import annotations

import dataclasses
from typing import Optional, Tuple

__all__ = ["ArchConfig", "ShapeConfig", "CompressionSettings", "RunConfig", "SHAPES"]


@dataclasses.dataclass(frozen=True)
class ArchConfig:
    """Architecture hyperparameters (transformer backbone granularity).

    arch_type: dense | moe | ssm | hybrid | vlm | audio
    """

    name: str
    arch_type: str
    n_layers: int
    d_model: int
    n_heads: int
    n_kv_heads: int
    d_ff: int
    vocab: int
    head_dim: Optional[int] = None

    # --- MoE ---
    # An expert layer may hold a share of the router's experts (expert
    # parallelism): it routes over all ``router_experts`` and computes the part
    # of the result that its own ``n_experts``, from ``first_expert`` on, give.
    n_experts: int = 0  # experts held here
    moe_topk: int = 0
    router_experts: int = 0  # the router's width; 0: every expert is held here
    first_expert: int = 0
    expert_d_ff: int = 0  # one expert's width; 0: d_ff
    n_shared_experts: int = 0  # experts every token passes through, as one SwiGLU
    # softmax: load-balance and z losses; sigmoid: DeepSeek-V3's noaux_tc, a
    # correction bias that picks the experts but does not weigh them
    router: str = "softmax"
    routed_scale: float = 1.0  # on the chosen experts' weights, normalised to sum to 1
    first_dense_layers: int = 0  # leading layers with a dense MLP, then the MoE stack

    # --- multi-head latent attention (DeepSeek-V2/V3), on when kv_lora_rank > 0 ---
    kv_lora_rank: int = 0
    qk_nope_head_dim: int = 0
    qk_rope_head_dim: int = 0
    v_head_dim: int = 0

    # --- attention flavour ---
    qkv_bias: bool = False
    rope_theta: float = 10_000.0
    sliding_window: Optional[int] = None  # applied to *all* attn layers if set

    # --- hybrid (RecurrentGemma): repeating block pattern, e.g. ("rec","rec","attn")
    hybrid_pattern: Tuple[str, ...] = ()
    local_window: int = 2048  # hybrid local-attention window
    conv_width: int = 4  # temporal conv in recurrent blocks
    rglru_c: float = 8.0

    # --- ssm (RWKV6) ---
    ssm_head_dim: int = 64

    # --- encoder-decoder (whisper) ---
    encoder_layers: int = 0
    encoder_seq: int = 1500  # stub frame-embedding count

    # --- vlm ---
    vision_tokens: int = 0  # stub patch-embedding count prepended to text

    # --- numerics ---
    norm: str = "rmsnorm"  # rmsnorm | layernorm
    norm_eps: Optional[float] = None  # None: 1e-6 for rmsnorm, 1e-5 for layernorm
    tie_embeddings: bool = False
    citation: str = ""

    @property
    def hd(self) -> int:
        return self.head_dim if self.head_dim is not None else self.d_model // self.n_heads

    @property
    def is_encdec(self) -> bool:
        return self.encoder_layers > 0

    @property
    def attention_free(self) -> bool:
        return self.arch_type == "ssm"

    @property
    def subquadratic(self) -> bool:
        """Can this arch decode at 500k context without a full KV cache?"""
        return self.arch_type in ("ssm", "hybrid") or self.sliding_window is not None

    @property
    def expert_ff(self) -> int:
        return self.expert_d_ff or self.d_ff

    @property
    def n_router(self) -> int:
        return self.router_experts or self.n_experts

    def _attn_params(self) -> int:
        D, H, KV, hd = self.d_model, self.n_heads, self.n_kv_heads, self.hd
        if self.kv_lora_rank:
            R, nope, rope = self.kv_lora_rank, self.qk_nope_head_dim, self.qk_rope_head_dim
            return (D * H * (nope + rope) + D * (R + rope) + R
                    + R * H * (nope + self.v_head_dim) + H * self.v_head_dim * D)
        attn = D * (H * hd) + 2 * D * (KV * hd) + (H * hd) * D
        if self.qkv_bias:
            attn += (H + 2 * KV) * hd
        return attn

    def _moe_params(self, routed_experts: float) -> float:
        """An expert layer's MLP with ``routed_experts`` of its routed experts."""
        D, E = self.d_model, self.n_router
        expert = 3 * D * self.expert_ff
        router = D * E + (E if self.router == "sigmoid" else 0)
        return router + routed_experts * expert + self.n_shared_experts * expert

    def param_count(self, routed_experts: Optional[float] = None) -> int:
        """Analytical parameter count (embedding + blocks + head); an expert
        layer counts ``routed_experts`` of its experts (default: those held)."""
        D, F, V, L = self.d_model, self.d_ff, self.vocab, self.n_layers
        H, KV, hd = self.n_heads, self.n_kv_heads, self.hd
        emb = V * D * (1 if self.tie_embeddings else 2)
        total = emb
        if self.arch_type == "ssm":  # RWKV6
            tm = D * (4 * D) + D * D  # r,k,v,g (+ output)
            lora = 6 * (D * 64 + 64 * D)  # ddlerp/decay low-rank adapters (approx)
            cm = 2 * D * F
            total += L * (tm + lora + cm + 2 * D)
            return total
        attn = self._attn_params()
        norm = 2 * D if self.norm == "layernorm" else D
        # the MLP the model builds: GELU with biases under layernorm, else SwiGLU
        dense = 2 * D * F + F + D if self.norm == "layernorm" else 3 * D * F
        total += norm  # final norm
        if self.n_experts:
            held = self.n_experts if routed_experts is None else routed_experts
            moe = self._moe_params(held)
            n_dense = self.first_dense_layers
            return int(total + L * (attn + 2 * norm) + n_dense * dense + (L - n_dense) * moe)
        if self.arch_type == "hybrid":
            n_attn = sum(1 for _ in self._layer_kinds() if _ == "attn")
            n_rec = L - n_attn
            rec = 2 * D * D + D * self.conv_width + 3 * D  # rg-lru block approx
            total += n_attn * (attn + dense + 2 * D) + n_rec * (rec + dense + 2 * D)
            return total
        layers = L if not self.is_encdec else L + self.encoder_layers
        cross = D * (H * hd) + 2 * D * (KV * hd) + (H * hd) * D if self.is_encdec else 0
        total += layers * (attn + dense + 2 * norm) + self.n_layers * cross
        return total

    def active_param_count(self) -> int:
        """Per-token active parameters: of an expert layer's routed experts, the
        ``moe_topk`` a token picks, times the share of them held here."""
        if not self.n_experts:
            return self.param_count()
        return self.param_count(self.moe_topk * self.n_experts / self.n_router)

    def _layer_kinds(self) -> Tuple[str, ...]:
        """Per-layer kinds for hybrid archs; uniform otherwise."""
        if self.arch_type == "hybrid" and self.hybrid_pattern:
            reps = -(-self.n_layers // len(self.hybrid_pattern))
            return tuple((self.hybrid_pattern * reps)[: self.n_layers])
        if self.arch_type == "ssm":
            return ("ssm",) * self.n_layers
        if self.n_experts:
            n_dense = self.first_dense_layers
            return ("attn",) * n_dense + ("moe",) * (self.n_layers - n_dense)
        return ("attn",) * self.n_layers


@dataclasses.dataclass(frozen=True)
class ShapeConfig:
    """Assigned input shape."""

    name: str
    seq_len: int
    global_batch: int
    kind: str  # train | prefill | decode

    @property
    def is_decode(self) -> bool:
        return self.kind == "decode"


SHAPES = {
    "train_4k": ShapeConfig("train_4k", 4_096, 256, "train"),
    "prefill_32k": ShapeConfig("prefill_32k", 32_768, 32, "prefill"),
    "decode_32k": ShapeConfig("decode_32k", 32_768, 128, "decode"),
    "long_500k": ShapeConfig("long_500k", 524_288, 1, "decode"),
}


@dataclasses.dataclass(frozen=True)
class CompressionSettings:
    """ScaleCom knobs exposed at run level (mirrors core.ScaleComConfig)."""

    compressor: str = "clt_k"
    chunk: int = 64
    topm: int = 1
    beta: float = 0.1
    min_size: int = 2048
    residue_dtype: str = "fp32"
    groups: Optional[int] = None
    warmup_steps: int = 0
    enabled: bool = True


@dataclasses.dataclass(frozen=True)
class RunConfig:
    """One training/serving run: arch x shape x mesh x compression."""

    arch: ArchConfig
    shape: ShapeConfig
    sharding_policy: str = "tp"  # tp | fsdp
    param_dtype: str = "float32"
    compute_dtype: str = "bfloat16"
    compression: CompressionSettings = CompressionSettings()
    # optimizer
    optimizer: str = "sgdm"  # sgdm | adam | rmsprop
    lr: float = 0.1
    momentum: float = 0.9
    weight_decay: float = 0.0
    warmup_pct: float = 0.0
    seed: int = 0
    remat: bool = True
    loss_chunk: int = 512  # sequence chunking for the vocab-sharded xent
