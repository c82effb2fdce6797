"""Model configurations for the decoder LM family and embedding encoders.

The flagship serving targets come from BASELINE.md's benchmark matrix:
Llama-3-8B (v5e-1 / v5e-8), Llama-3-70B / Qwen2-72B (v5p-16), and a
BGE-large-class encoder for the anomaly detector's embedding path.
"""

from __future__ import annotations

import dataclasses
from typing import Optional


@dataclasses.dataclass(frozen=True)
class LayerSpec:
    """What one decoder layer is made of (``ModelConfig.layer_spec``): the
    code that builds, quantizes, shards and runs a layer asks this, not the
    family a preset came from.

    mixer: ``"full"`` (per-head K and V, GQA) | ``"latent"`` (DeepSeek-V3
      multi-head latent attention: one compressed latent and one rotated key
      per token).
    mlp: ``"dense"`` | ``"routed"`` (top-k experts) | ``"shared+routed"``.
    cache: ``"kv"`` (two page arrays, ``kv_heads * head_dim`` lanes) |
      ``"latent"`` (one page array, ``ModelConfig.latent_page_width`` lanes).
    """

    mixer: str
    mlp: str
    cache: str


@dataclasses.dataclass(frozen=True)
class ModelConfig:
    """Architecture hyperparameters for the decoder LM families served.

    The families covered:
      - Llama-3:  GQA, RoPE (high theta), SwiGLU MLP, RMSNorm, no biases.
      - Qwen2:    same skeleton + QKV projection biases.
      - Mixtral:  softmax top-k routed experts in every layer.
      - DeepSeek-V3 block: latent attention, leading dense layers, then
        sigmoid-scored routed experts beside a shared MLP.
    """

    name: str = "tiny"
    vocab_size: int = 256
    hidden_size: int = 64
    intermediate_size: int = 128
    num_layers: int = 2
    num_heads: int = 4
    num_kv_heads: int = 2
    head_dim: Optional[int] = None  # defaults to hidden_size // num_heads
    rope_theta: float = 500_000.0
    # HF-style rope_scaling dict (e.g. Llama-3.1's {"rope_type": "llama3",
    # "factor": 8.0, ...}); None = unscaled.  See ops/rope.py.
    rope_scaling: Optional[dict] = None
    rms_norm_eps: float = 1e-5
    max_seq_len: int = 8192
    qkv_bias: bool = False          # True for Qwen2
    tie_embeddings: bool = False
    dtype: str = "bfloat16"
    # KV cache dtype ('' = same as dtype).  "float8_e4m3fn" halves the KV
    # pool and the decode-attention DMA traffic; Q stays bf16 and the
    # kernel/softmax run f32, so logits track the bf16-KV model closely
    # (tested).  Opt-in: accuracy headroom is workload-dependent.
    kv_dtype: str = ""
    # W8A8: dynamically quantize activations (per-token symmetric int8) at
    # every linear so the matmul runs s8 x s8 on the MXU's int8 path —
    # above the bf16 matmul rate on v5e (measured ~1.4x end-to-end on
    # dense prefill shapes), i.e. faster prefill for
    # int8-quantized weights.  Requires kernel_q weights
    # (utils/quantize.py).  Attention, norms, and residuals stay bf16.
    act_quant: bool = False
    # Mixture-of-experts MLP (Mixtral family): > 0 replaces every layer's
    # SwiGLU with num_experts expert FFNs behind a top-k router (GShard
    # capacity dispatch, models/llama.py:_moe_mlp).  Expert weights carry a
    # leading [num_experts] axis sharded over the mesh's ``model`` axis —
    # expert parallelism rides the same axis tensor parallelism uses, and
    # XLA inserts the dispatch/combine all-to-alls from the shardings.
    num_experts: int = 0
    num_experts_per_tok: int = 2
    # Per-expert token capacity = ceil(tokens * top_k * capacity_factor /
    # num_experts); overflow tokens skip the MLP (residual passes through).
    capacity_factor: float = 1.25
    # --- Gemma-2 family knobs (defaults = Llama conventions) -----------
    # MLP activation: "silu" (SwiGLU) or "gelu_tanh" (Gemma GeGLU).
    mlp_activation: str = "silu"
    # Sandwich norms: extra RMSNorm on the attention and MLP OUTPUTS
    # (post_attn_norm / post_mlp_norm) before the residual add; the
    # existing post_norm plays Gemma's pre_feedforward role.
    sandwich_norms: bool = False
    # Gemma RMSNorm convention: stored weight is a zero-centered delta,
    # effective scale = 1 + w (ops/norms.py unit_offset).
    rmsnorm_unit_offset: bool = False
    # tanh soft caps (0 = off): attention logits and final lm logits.
    attn_logit_softcap: float = 0.0
    final_logit_softcap: float = 0.0
    # Query scale = query_pre_attn_scalar**-0.5 (None = head_dim**-0.5).
    query_pre_attn_scalar: Optional[float] = None
    # Multiply embeddings by sqrt(hidden_size) (Gemma).
    embed_scale: bool = False
    # Sliding-window attention: window size (0 = global) and the per-layer
    # pattern ("sliding_attention"/"full_attention" per layer; None = all
    # sliding when sliding_window > 0).
    sliding_window: int = 0
    layer_types: Optional[tuple] = None
    # --- per-layer description (LayerSpec) ------------------------------
    # Mixer of every layer: "full" | "latent".  The latent sizes are the
    # published DeepSeek-V3 keys; q_lora_rank is not supported (the query
    # projection is direct, as in the configurations served so far).
    mixer: str = "full"
    kv_lora_rank: int = 0
    qk_nope_head_dim: int = 0
    qk_rope_head_dim: int = 0
    v_head_dim: int = 0
    # Expert layers: the first ``first_dense_layers`` layers keep a dense MLP
    # of ``intermediate_size``; the rest route ``num_experts_per_tok`` of
    # ``num_experts`` experts of width ``moe_intermediate_size`` (0 =
    # ``intermediate_size``, Mixtral) beside ``n_shared_experts`` shared ones
    # (one MLP of n_shared x that width; 0 = none).
    first_dense_layers: int = 0
    moe_intermediate_size: int = 0
    n_shared_experts: int = 0
    # Scoring: "softmax" (Mixtral: softmax over experts, top-k of it) |
    # "sigmoid+bias" (DeepSeek-V3: s = sigmoid(logits); the choice is the
    # top-k of s + e_bias, the weights are s of the chosen).
    moe_scoring: str = "softmax"
    norm_topk_prob: bool = True
    routed_scaling_factor: float = 1.0

    def layer_spec(self, i: int) -> LayerSpec:
        """Mixer, MLP and cache kind of layer ``i``."""
        if self.num_experts > 0 and i >= self.first_dense_layers:
            mlp = "shared+routed" if self.n_shared_experts > 0 else "routed"
        else:
            mlp = "dense"
        return LayerSpec(mixer=self.mixer, mlp=mlp,
                         cache="latent" if self.mixer == "latent" else "kv")

    @property
    def latent(self) -> bool:
        return self.mixer == "latent"

    @property
    def expert_layers(self) -> int:
        """Layers whose MLP routes (0 for a dense model)."""
        if self.num_experts <= 0:
            return 0
        return max(0, self.num_layers - self.first_dense_layers)

    @property
    def expert_width(self) -> int:
        return self.moe_intermediate_size or self.intermediate_size

    @property
    def qk_head_dim(self) -> int:
        """Width of one head's score: nope + rope parts (latent mixer)."""
        return self.qk_nope_head_dim + self.qk_rope_head_dim

    @property
    def latent_rope_width(self) -> int:
        """Lanes the rotated key takes in a latent page: its width padded to
        a whole 128-lane tile (zeros), so that a page row is lane-aligned
        for the chip's DMA and what the pool costs is what its shape says."""
        return -(-self.qk_rope_head_dim // 128) * 128

    @property
    def latent_page_width(self) -> int:
        """Lanes of one cached token in one layer of a latent pool:
        ``[latent (kv_lora_rank) | rotated key | zeros]``."""
        return self.kv_lora_rank + self.latent_rope_width

    def kv_token_bytes(self, itemsize: int = 2) -> int:
        """Bytes one cached token costs over all layers (scales excluded)."""
        if self.latent:
            return self.num_layers * self.latent_page_width * itemsize
        return 2 * self.num_layers * self.num_kv_heads * self.head_dim_ * itemsize

    @property
    def attn_scale(self) -> Optional[float]:
        """Explicit query scale, or None for the default head_dim**-0.5."""
        if self.query_pre_attn_scalar is not None:
            return float(self.query_pre_attn_scalar) ** -0.5
        return None

    def layer_window(self, i: int) -> int:
        """Sliding-window size for layer ``i`` (0 = global attention)."""
        if self.sliding_window <= 0:
            return 0
        if self.layer_types is not None:
            return (self.sliding_window
                    if self.layer_types[i] == "sliding_attention" else 0)
        return self.sliding_window

    @property
    def has_attn_extras(self) -> bool:
        """True when attention needs non-Llama parameters threaded (forces
        the gather attention impls — ops/attention.py selection gates)."""
        return bool(self.attn_logit_softcap or self.sliding_window
                    or self.query_pre_attn_scalar is not None)

    @property
    def head_dim_(self) -> int:
        return self.head_dim if self.head_dim is not None else self.hidden_size // self.num_heads

    @property
    def q_per_kv(self) -> int:
        return self.num_heads // self.num_kv_heads


# ---------------------------------------------------------------------------
# Presets.  Shapes follow the public architecture cards for each model family.
# ---------------------------------------------------------------------------

TINY = ModelConfig(name="tiny")

TINY_QWEN = ModelConfig(name="tiny-qwen", qkv_bias=True)

# 8 experts so the expert axis divides TP-8 like the production MoE preset.
TINY_MOE = ModelConfig(name="tiny-moe", num_experts=8, num_experts_per_tok=2)

# The DeepSeek-V3 block at test size: latent attention (rope part narrower
# than the nope part, value width its own), one leading dense layer, then
# sigmoid-scored experts beside a shared MLP.  vocab >= 259 so the byte
# tokenizer's ids fit (chip_smoke / harness rehearsals).
TINY_LATENT_MOE = ModelConfig(
    name="tiny-latent-moe", vocab_size=320, hidden_size=64,
    intermediate_size=96, num_layers=3, num_heads=4, num_kv_heads=4,
    rope_theta=10_000.0, rms_norm_eps=1e-6,
    mixer="latent", kv_lora_rank=32, qk_nope_head_dim=16,
    qk_rope_head_dim=8, v_head_dim=16, head_dim=24,
    num_experts=8, num_experts_per_tok=3, first_dense_layers=1,
    moe_intermediate_size=24, n_shared_experts=2,
    moe_scoring="sigmoid+bias", norm_topk_prob=True,
    routed_scaling_factor=2.448)

LLAMA3_8B = ModelConfig(
    name="llama3-8b",
    vocab_size=128_256,
    hidden_size=4096,
    intermediate_size=14_336,
    num_layers=32,
    num_heads=32,
    num_kv_heads=8,
    rope_theta=500_000.0,
    max_seq_len=8192,
)

LLAMA3_70B = ModelConfig(
    name="llama3-70b",
    vocab_size=128_256,
    hidden_size=8192,
    intermediate_size=28_672,
    num_layers=80,
    num_heads=64,
    num_kv_heads=8,
    rope_theta=500_000.0,
    max_seq_len=8192,
)

MIXTRAL_8X7B = ModelConfig(
    name="mixtral-8x7b",
    vocab_size=32_000,
    hidden_size=4096,
    intermediate_size=14_336,
    num_layers=32,
    num_heads=32,
    num_kv_heads=8,
    rope_theta=1_000_000.0,
    max_seq_len=32_768,
    num_experts=8,
    num_experts_per_tok=2,
)

def gemma2_layer_types(n_layers: int) -> tuple:
    """Gemma-2's attention pattern: alternating sliding/full, sliding
    first.  The ONE definition shared by the presets and the HF-config
    fallback (utils/checkpoint.py) so they cannot drift."""
    return tuple("sliding_attention" if i % 2 == 0 else "full_attention"
                 for i in range(n_layers))


def _gemma2(name: str, *, hidden: int, inter: int, layers: int, heads: int,
            kv: int, qpas: float) -> ModelConfig:
    return ModelConfig(
        name=name,
        vocab_size=256_000,
        hidden_size=hidden,
        intermediate_size=inter,
        num_layers=layers,
        num_heads=heads,
        num_kv_heads=kv,
        head_dim=256,
        rope_theta=10_000.0,
        rms_norm_eps=1e-6,
        max_seq_len=8192,
        tie_embeddings=True,
        mlp_activation="gelu_tanh",
        sandwich_norms=True,
        rmsnorm_unit_offset=True,
        attn_logit_softcap=50.0,
        final_logit_softcap=30.0,
        query_pre_attn_scalar=qpas,
        embed_scale=True,
        sliding_window=4096,
        layer_types=gemma2_layer_types(layers),
    )


GEMMA2_2B = _gemma2("gemma2-2b", hidden=2304, inter=9216, layers=26,
                    heads=8, kv=4, qpas=256.0)
GEMMA2_9B = _gemma2("gemma2-9b", hidden=3584, inter=14_336, layers=42,
                    heads=16, kv=8, qpas=256.0)

# Mistral-7B (v0.3+: no sliding window, full GQA) — same skeleton as
# Llama-3 with 32k vocab and theta 1e6; loads from HF safetensors via the
# same key map (utils/checkpoint.py).
MISTRAL_7B = ModelConfig(
    name="mistral-7b",
    vocab_size=32_768,
    hidden_size=4096,
    intermediate_size=14_336,
    num_layers=32,
    num_heads=32,
    num_kv_heads=8,
    rope_theta=1_000_000.0,
    max_seq_len=32_768,
)

QWEN2_7B = ModelConfig(
    name="qwen2-7b",
    vocab_size=152_064,
    hidden_size=3584,
    intermediate_size=18_944,
    num_layers=28,
    num_heads=28,
    num_kv_heads=4,
    rope_theta=1_000_000.0,
    max_seq_len=32_768,
    qkv_bias=True,
)

QWEN2_72B = ModelConfig(
    name="qwen2-72b",
    vocab_size=152_064,
    hidden_size=8192,
    intermediate_size=29_568,
    num_layers=80,
    num_heads=64,
    num_kv_heads=8,
    rope_theta=1_000_000.0,
    max_seq_len=32_768,
    qkv_bias=True,
)

# kakaocorp/kanana-2-30b-a3b-instruct-2601 (config.json, model_type
# deepseek_v3), cut in depth alone: the leading dense layer and 11 of the 47
# expert layers (48 -> 12), every width, all 128 experts and the whole
# vocabulary as published.  One pipeline stage of four, with the head
# (benchmarks/configs/kanana2-30b-a3b-w8a8.json has the reckoning).
KANANA2_30B_A3B_12L = ModelConfig(
    name="kanana-2-30b-a3b-12l",
    vocab_size=128_256,
    hidden_size=2048,
    intermediate_size=6144,
    num_layers=12,
    num_heads=32,
    num_kv_heads=32,
    head_dim=192,
    rope_theta=1_000_000.0,
    rms_norm_eps=1e-6,
    max_seq_len=32_768,
    mixer="latent",
    kv_lora_rank=512,
    qk_nope_head_dim=128,
    qk_rope_head_dim=64,
    v_head_dim=128,
    num_experts=128,
    num_experts_per_tok=6,
    first_dense_layers=1,
    moe_intermediate_size=768,
    n_shared_experts=2,
    moe_scoring="sigmoid+bias",
    norm_topk_prob=True,
    routed_scaling_factor=2.448,
)

# A ~1.1B config used for single-chip benchmarks when full 8B weights would not
# leave headroom for the KV cache on a 16 GB v5e chip with random-init weights.
LLAMA_1B = ModelConfig(
    name="llama-1b",
    vocab_size=128_256,
    hidden_size=2048,
    intermediate_size=8192,
    num_layers=16,
    num_heads=32,
    num_kv_heads=8,
    head_dim=64,
    rope_theta=500_000.0,
    max_seq_len=8192,
)

PRESETS = {
    c.name: c
    for c in [TINY, TINY_QWEN, TINY_MOE, TINY_LATENT_MOE, LLAMA3_8B,
              LLAMA3_70B, MISTRAL_7B, MIXTRAL_8X7B, QWEN2_7B, QWEN2_72B,
              GEMMA2_2B, GEMMA2_9B, LLAMA_1B, KANANA2_30B_A3B_12L]
}


@dataclasses.dataclass(frozen=True)
class EncoderConfig:
    """BERT-family bidirectional encoder (BGE-large) for embeddings.

    Used by the anomaly detector (analysis/anomaly.py) to embed log lines and
    cluster events; BASELINE.md config #3.
    """

    name: str = "tiny-encoder"
    vocab_size: int = 512
    hidden_size: int = 64
    intermediate_size: int = 128
    num_layers: int = 2
    num_heads: int = 4
    max_position_embeddings: int = 512
    type_vocab_size: int = 2
    layer_norm_eps: float = 1e-12
    dtype: str = "float32"

    @property
    def head_dim(self) -> int:
        return self.hidden_size // self.num_heads


TINY_ENCODER = EncoderConfig()

BGE_LARGE = EncoderConfig(
    name="bge-large",
    vocab_size=30_522,
    hidden_size=1024,
    intermediate_size=4096,
    num_layers=24,
    num_heads=16,
    max_position_embeddings=512,
)

# bf16 variant for TPU serving: ~2x the matmul rate and half the weight
# traffic; pooling/normalization stay f32 (models/encoder.py), so cosine
# rankings track the f32 encoder closely.
BGE_LARGE_BF16 = dataclasses.replace(
    BGE_LARGE, name="bge-large-bf16", dtype="bfloat16")

ENCODER_PRESETS = {c.name: c for c in [TINY_ENCODER, BGE_LARGE,
                                       BGE_LARGE_BF16]}
