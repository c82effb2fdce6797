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
      per token) | ``"mamba2"`` (a state-space mixer: no cached tokens, one
      recurrent state a lane) | ``"mamba1"`` (Mamba-1's selective scan: a
      decay per channel and state; no cached tokens, one recurrent state a
      lane) | ``"none"`` (the layer is a feed-forward alone).
    mlp: ``"dense"`` | ``"routed"`` (top-k experts) | ``"shared+routed"`` |
      ``"none"`` (the layer is a mixer alone).
    cache: ``"kv"`` (two page arrays, ``kv_heads * head_dim`` lanes) |
      ``"latent"`` (one page array, the geometry's ``page_width`` lanes, and
      beside it an index-key page array where the geometry has an indexer)
      | ``"window"`` (a latent mixer under a sliding window: a ring of the
      last ``window`` rows a decode lane, whatever the context) |
      ``"state"`` (a row of the per-lane state pool, whatever the context)
      | ``"none"``.

    A latent mixer's sizes are its layer's ``ModelConfig.latent_geometry``.
    """

    mixer: str
    mlp: str
    cache: str


@dataclasses.dataclass(frozen=True)
class LatentGeometry:
    """The sizes of one latent mixer (``ModelConfig.latent_geometry(i)``): a
    model may have two, chosen by ``layer_types``.  ``q_lora_rank`` 0 = the
    query projection is direct; ``window`` 0 = every earlier key is allowed;
    ``index_topk`` > 0 = a learned indexer (``index_heads`` heads of
    ``index_dim``) picks that many of the allowed keys."""

    num_heads: int
    q_lora_rank: int
    kv_lora_rank: int
    qk_nope_head_dim: int
    qk_rope_head_dim: int
    v_head_dim: int
    rope_theta: float
    window: int = 0
    index_topk: int = 0
    index_heads: int = 0
    index_dim: int = 0
    gate: str = ""              # "headwise": one sigmoid gate a head
    q_scale: float = 1.0        # on the normed query latent
    kv_scale: float = 1.0       # on the normed key/value latent

    @property
    def qk_head_dim(self) -> int:
        """Width of one head's score: nope + rope parts."""
        return self.qk_nope_head_dim + self.qk_rope_head_dim

    @property
    def rope_width(self) -> int:
        """Lanes the rotated key takes in a page row: its width padded to a
        whole 128-lane tile (zeros), so that a row is lane-aligned for the
        chip's DMA and what the pool costs is what its shape says."""
        return -(-self.qk_rope_head_dim // 128) * 128

    @property
    def page_width(self) -> int:
        """Lanes of one cached token: ``[latent | rotated key | zeros]``."""
        return self.kv_lora_rank + self.rope_width

    @property
    def indexed(self) -> bool:
        return self.index_topk > 0


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
    # MLP activation: "silu" (SwiGLU), "gelu_tanh" (Gemma GeGLU) or "relu2"
    # (relu(x)^2, nemotron_h's un-gated MLPs: ``mlp_gated`` False).
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
    # published DeepSeek-V3 keys; ``q_lora_rank`` 0 = the query projection is
    # direct.  ``latent_geometry(i)`` is what the code asks.
    mixer: str = "full"
    kv_lora_rank: int = 0
    qk_nope_head_dim: int = 0
    qk_rope_head_dim: int = 0
    v_head_dim: int = 0
    q_lora_rank: int = 0
    # A latent model's ``sliding_attention`` layers (``layer_types``) have a
    # geometry of their own (the published ``swa_*`` keys) and keep a
    # window-bounded store instead of pages (LayerSpec.cache "window").
    swa_num_heads: int = 0
    swa_q_lora_rank: int = 0
    swa_kv_lora_rank: int = 0
    swa_qk_nope_head_dim: int = 0
    swa_qk_rope_head_dim: int = 0
    swa_v_head_dim: int = 0
    swa_rope_theta: float = 0.0
    # "headwise": the attention output of head j is scaled by
    # sigmoid(W_g h)_j, one scalar a head from the layer's normed input
    # (both geometries).
    attn_gate: str = ""
    # The normed latents are scaled by sqrt(hidden / rank) (per geometry).
    lora_rescale: bool = False
    # A learned indexer on the full-attention layers of a latent model:
    # ``index_n_heads`` query heads of ``index_head_dim`` off the query
    # latent, one key a token; the ``index_topk`` best-scored earlier keys
    # are the ones attention sees (0 = no indexer).
    index_n_heads: int = 0
    index_head_dim: int = 0
    index_topk: int = 0
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
    # The experts this chip holds of an expert-parallel layer: ``experts_held``
    # (0 = all ``num_experts``) from ``expert_start``.  The router keeps its
    # width and its experts per token; the layer sums the chosen experts it
    # holds, with weights normalised over all the chosen, and what the
    # absent ones would add is left out (models/llama.py:_moe_mlp_share).
    experts_held: int = 0
    expert_start: int = 0
    # Routed experts live in a latent of this width between a down- and an
    # up-projection of the hidden size (0 = they read the hidden size).
    moe_latent_size: int = 0
    # Width of the shared MLP (0 = n_shared_experts x the expert width).
    moe_shared_intermediate_size: int = 0
    # False: an MLP is two kernels, ``down(act(up(x)))``, with no gate
    # (``mlp_activation`` "relu2": relu(x)^2).
    mlp_gated: bool = True
    # False: attention layers rotate nothing (position reaches the model
    # through its recurrent layers).
    use_rope: bool = True
    # --- one sub-block a layer (nemotron_h) -----------------------------
    # One letter a layer: ``M`` a Mamba-2 mixer, ``*`` an attention mixer,
    # ``E`` an expert feed-forward — each alone, under one norm and one
    # residual.  None = every layer is a mixer and an MLP.
    layer_pattern: Optional[str] = None
    mamba_num_heads: int = 0
    mamba_head_dim: int = 0
    ssm_state_size: int = 0
    mamba_n_groups: int = 1
    conv_kernel: int = 4
    ssm_chunk_size: int = 128
    # --- a mixer and an MLP a layer, attention every so many (jamba) -------
    # > 0: layer i is attention where ``i % attn_layer_period ==
    # attn_layer_offset`` and a Mamba-1 mixer elsewhere, each followed by the
    # layer's MLP.  A Mamba-1 mixer has ``mamba_expand x hidden`` channels, a
    # state of ``ssm_state_size`` a channel, a step size of rank
    # ``mamba_dt_rank`` and a convolution of ``conv_kernel`` with a bias.
    attn_layer_period: int = 0
    attn_layer_offset: int = 0
    mamba_expand: int = 2
    mamba_dt_rank: int = 0

    def layer_spec(self, i: int) -> LayerSpec:
        """Mixer, MLP and cache kind of layer ``i``."""
        if self.num_experts > 0 and i >= self.first_dense_layers:
            mlp = "shared+routed" if self.n_shared_experts > 0 else "routed"
        else:
            mlp = "dense"
        if self.attn_layer_period:
            if i % self.attn_layer_period == self.attn_layer_offset:
                return LayerSpec(mixer="full", mlp=mlp, cache="kv")
            return LayerSpec(mixer="mamba1", mlp=mlp, cache="state")
        if self.layer_pattern is not None:
            kind = self.layer_pattern[i]
            if kind == "M":
                return LayerSpec(mixer="mamba2", mlp="none", cache="state")
            if kind == "*":
                return LayerSpec(mixer="full", mlp="none", cache="kv")
            if kind == "E":
                return LayerSpec(mixer="none", mlp=mlp, cache="none")
            raise ValueError(f"layer_pattern[{i}] = {kind!r} (M | * | E)")
        if self.mixer != "latent":
            return LayerSpec(mixer=self.mixer, mlp=mlp, cache="kv")
        return LayerSpec(mixer="latent", mlp=mlp,
                         cache="window" if self.layer_window(i) else "latent")

    def latent_geometry(self, i: int) -> LatentGeometry:
        """The sizes of layer ``i``'s latent mixer."""
        window = self.layer_window(i)
        scale = lambda rank: ((self.hidden_size / rank) ** 0.5     # noqa: E731
                              if self.lora_rescale and rank else 1.0)
        if window:
            return LatentGeometry(
                num_heads=self.swa_num_heads,
                q_lora_rank=self.swa_q_lora_rank,
                kv_lora_rank=self.swa_kv_lora_rank,
                qk_nope_head_dim=self.swa_qk_nope_head_dim,
                qk_rope_head_dim=self.swa_qk_rope_head_dim,
                v_head_dim=self.swa_v_head_dim,
                rope_theta=self.swa_rope_theta, window=window,
                gate=self.attn_gate, q_scale=scale(self.swa_q_lora_rank),
                kv_scale=scale(self.swa_kv_lora_rank))
        return LatentGeometry(
            num_heads=self.num_heads, q_lora_rank=self.q_lora_rank,
            kv_lora_rank=self.kv_lora_rank,
            qk_nope_head_dim=self.qk_nope_head_dim,
            qk_rope_head_dim=self.qk_rope_head_dim,
            v_head_dim=self.v_head_dim, rope_theta=self.rope_theta,
            index_topk=self.index_topk, index_heads=self.index_n_heads,
            index_dim=self.index_head_dim, gate=self.attn_gate,
            q_scale=scale(self.q_lora_rank),
            kv_scale=scale(self.kv_lora_rank))

    def layers_with(self, cache: str) -> list[int]:
        """The layers whose cache is of kind ``cache``, in order: a pool has
        one entry for each (``KVPages``)."""
        return [i for i in range(self.num_layers)
                if self.layer_spec(i).cache == cache]

    @property
    def latent(self) -> bool:
        return self.mixer == "latent"

    @property
    def recurrent(self) -> bool:
        """Some layer carries a recurrent state from token to token."""
        return bool(self.layers_with("state"))

    @property
    def state_mixer(self) -> str:
        """The recurrent layers' mixer, ``"mamba2"`` or ``"mamba1"`` (one
        kind a description); "" without recurrent layers."""
        layers = self.layers_with("state")
        return self.layer_spec(layers[0]).mixer if layers else ""

    @property
    def lane_state(self) -> bool:
        """Some layer keeps something a decode lane (not a block): recurrent
        state, or a window-bounded store.  Such a description is admitted
        whole into the lanes a call names (no cached prefix, no chunks)."""
        return bool(self.recurrent or self.layers_with("window"))

    def window_rows(self, block_size: int) -> int:
        """Rows one lane's ring of a window layer takes: the window, in
        whole blocks."""
        return -(-self.sliding_window // block_size) * block_size

    def window_lane_bytes(self, block_size: int, itemsize: int = 2) -> int:
        """Bytes one lane of the window store holds over all window layers,
        whatever the context."""
        return sum(self.window_rows(block_size)
                   * self.latent_geometry(i).page_width * itemsize
                   for i in self.layers_with("window"))

    @property
    def expert_layers(self) -> int:
        """Layers whose MLP routes (0 for a dense model)."""
        if self.num_experts <= 0:
            return 0
        return sum(self.layer_spec(i).mlp in ("routed", "shared+routed")
                   for i in range(self.num_layers))

    @property
    def experts_held_(self) -> int:
        """Experts whose kernels this chip holds."""
        return self.experts_held or self.num_experts

    @property
    def expert_share(self) -> bool:
        """The expert layer is the share-aware one: a share of the experts,
        a latent, or an MLP without a gate."""
        return bool(self.experts_held or self.moe_latent_size
                    or not self.mlp_gated)

    @property
    def shared_width(self) -> int:
        return (self.moe_shared_intermediate_size
                or self.n_shared_experts * self.expert_width)

    @property
    def mamba_inner(self) -> int:
        """Channels of a Mamba mixer's ``x`` and ``z``: heads x head width
        (Mamba-2), ``mamba_expand`` x the hidden size (Mamba-1)."""
        if self.mamba_num_heads:
            return self.mamba_num_heads * self.mamba_head_dim
        return self.mamba_expand * self.hidden_size

    @property
    def mamba_conv_dim(self) -> int:
        """Channels the short convolution runs over: ``[x | B | C]``
        (Mamba-2), ``x`` alone (Mamba-1)."""
        if not self.mamba_num_heads:
            return self.mamba_inner
        return (self.mamba_inner
                + 2 * self.mamba_n_groups * self.ssm_state_size)

    def state_lane_bytes(self, tail_itemsize: int = 2) -> int:
        """Bytes one lane of the state pool holds over all recurrent layers:
        the float32 state and the convolution's tail of ``conv_kernel - 1``
        un-convolved rows."""
        return len(self.layers_with("state")) * (
            self.mamba_inner * self.ssm_state_size * 4
            + (self.conv_kernel - 1) * self.mamba_conv_dim * tail_itemsize)

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
            # A paged latent layer's row, and its index key where it has one
            # (a window layer's rows are per lane: ``window_lane_bytes``).
            return sum((self.latent_geometry(i).page_width
                        + self.latent_geometry(i).index_dim) * itemsize
                       for i in self.layers_with("latent"))
        return (2 * len(self.layers_with("kv")) * self.num_kv_heads
                * self.head_dim_ * itemsize)

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
        return bool(self.attn_logit_softcap
                    or (self.sliding_window and not self.latent)
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

# The nemotron_h block at test size: all three letters, more groups than one,
# fewer experts held than routed (the second quarter of 32: 8, so that the
# expert axis divides TP-8 like every preset's), heads packed four to a row
# of the state pool.  vocab >= 259 as above.
TINY_NEMOTRON_H = ModelConfig(
    name="tiny-nemotron-h", vocab_size=320, hidden_size=64,
    intermediate_size=24, num_layers=6, num_heads=4, num_kv_heads=2,
    head_dim=16, rope_theta=10_000.0, rms_norm_eps=1e-5, use_rope=False,
    layer_pattern="ME*EME", mamba_num_heads=8, mamba_head_dim=8,
    ssm_state_size=16, mamba_n_groups=2, conv_kernel=4, ssm_chunk_size=8,
    num_experts=32, num_experts_per_tok=5, experts_held=8, expert_start=8,
    moe_intermediate_size=24, moe_latent_size=32, n_shared_experts=1,
    moe_shared_intermediate_size=40, mlp_gated=False, mlp_activation="relu2",
    moe_scoring="sigmoid+bias", norm_topk_prob=True,
    routed_scaling_factor=5.0)

# The jamba block at test size: four layers, the third attention by
# offset / period, each mixer followed by a dense MLP; Mamba-1 with the
# published state size (16) and a step-size rank below the hidden size; MQA
# with a query-head count that is no multiple of 8 over one key/value head,
# as the served model's 20:1.  vocab >= 259 as above.
TINY_JAMBA = ModelConfig(
    name="tiny-jamba", vocab_size=320, hidden_size=64,
    intermediate_size=96, num_layers=4, num_heads=5, num_kv_heads=1,
    head_dim=16, rope_theta=10_000.0, rms_norm_eps=1e-6, use_rope=False,
    tie_embeddings=True, attn_layer_period=4, attn_layer_offset=2,
    mamba_expand=2, mamba_dt_rank=8, ssm_state_size=16, conv_kernel=4)

# The dots3_note block at test size: both latent geometries (two full layers
# with low-rank queries and an indexer whose top-k is smaller than a test
# prompt, three window layers whose window is smaller still and no multiple
# of a block), head-wise gates, one leading dense layer, fewer experts held
# than routed (the second half of 16: 8, so that the expert axis divides
# TP-8 like every preset's).  vocab >= 259 as above.
TINY_DOTS3_NOTE = ModelConfig(
    name="tiny-dots3-note", vocab_size=320, hidden_size=64,
    intermediate_size=96, num_layers=5, num_heads=4, num_kv_heads=4,
    head_dim=24, rope_theta=10_000.0, rms_norm_eps=1e-5,
    mixer="latent", q_lora_rank=24, kv_lora_rank=32, qk_nope_head_dim=16,
    qk_rope_head_dim=8, v_head_dim=16,
    swa_num_heads=2, swa_q_lora_rank=24, swa_kv_lora_rank=48,
    swa_qk_nope_head_dim=24, swa_qk_rope_head_dim=8, swa_v_head_dim=16,
    swa_rope_theta=500.0, sliding_window=13,
    layer_types=("full_attention", "full_attention", "sliding_attention",
                 "sliding_attention", "sliding_attention"),
    attn_gate="headwise", lora_rescale=True,
    index_n_heads=4, index_head_dim=16, index_topk=12,
    num_experts=16, num_experts_per_tok=3, experts_held=8, expert_start=8,
    first_dense_layers=1, moe_intermediate_size=24, n_shared_experts=1,
    moe_scoring="sigmoid+bias", norm_topk_prob=True,
    routed_scaling_factor=1.0)

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

# nvidia/NVIDIA-Nemotron-3-Super-120B-A12B-BF16 (config.json, model_type
# nemotron_h), one chip's share of the first of four pipeline stages: the
# first 22 of 88 layers (two whole periods of the pattern, 5 M : 5 E : 1 *),
# experts 0-127 of each layer's 512 (the router keeps 512 and top 22), rows
# 0-32,767 of the 131,072-row vocabulary; every width as published
# (benchmarks/configs/nemotron3-super-120b-a12b-w8a8.json has the
# reckoning).  ``rope_theta`` and ``intermediate_size`` are the file's keys;
# nothing rotates (``use_rope``) and no layer has a dense MLP.
NEMOTRON3_SUPER_22L = ModelConfig(
    name="nemotron3-super-120b-a12b-22l",
    vocab_size=32_768,
    hidden_size=4096,
    intermediate_size=2688,
    num_layers=22,
    num_heads=32,
    num_kv_heads=2,
    head_dim=128,
    rope_theta=10_000.0,
    rms_norm_eps=1e-5,
    max_seq_len=262_144,
    use_rope=False,
    layer_pattern="MEMEMEM*EMEMEMEM*EMEME",
    mamba_num_heads=128,
    mamba_head_dim=64,
    ssm_state_size=128,
    mamba_n_groups=8,
    conv_kernel=4,
    ssm_chunk_size=128,
    num_experts=512,
    num_experts_per_tok=22,
    experts_held=128,
    expert_start=0,
    moe_intermediate_size=2688,
    moe_latent_size=1024,
    n_shared_experts=1,
    moe_shared_intermediate_size=5376,
    mlp_gated=False,
    mlp_activation="relu2",
    moe_scoring="sigmoid+bias",
    norm_topk_prob=True,
    routed_scaling_factor=5.0,
)

# dots-studio/dots3-note-prev (config.json, model_type dots3_note), one
# chip's share of the first of eight pipeline stages: layers 0-4 of 46 (the
# leading dense layer and one whole period: full, sliding x 3), experts 0-63
# of each layer's 256 (the router keeps 256 and top 8), rows 0-38,015 of the
# 152,064-row vocabulary; every width as published
# (benchmarks/configs/dots3-note-prev-w8a8.json has the reckoning).
DOTS3_NOTE_PREV_5L = ModelConfig(
    name="dots3-note-prev-5l",
    vocab_size=38_016,
    hidden_size=5120,
    intermediate_size=13_824,
    num_layers=5,
    num_heads=128,
    num_kv_heads=128,
    head_dim=192,
    rope_theta=80_000_000.0,
    rms_norm_eps=1e-5,
    max_seq_len=524_288,
    mixer="latent",
    q_lora_rank=1024,
    kv_lora_rank=512,
    qk_nope_head_dim=128,
    qk_rope_head_dim=64,
    v_head_dim=128,
    swa_num_heads=64,
    swa_q_lora_rank=1024,
    swa_kv_lora_rank=1024,
    swa_qk_nope_head_dim=192,
    swa_qk_rope_head_dim=64,
    swa_v_head_dim=128,
    swa_rope_theta=50_000.0,
    sliding_window=513,
    layer_types=("full_attention", "full_attention", "sliding_attention",
                 "sliding_attention", "sliding_attention"),
    attn_gate="headwise",
    lora_rescale=True,
    index_n_heads=64,
    index_head_dim=128,
    index_topk=2048,
    num_experts=256,
    num_experts_per_tok=8,
    experts_held=64,
    expert_start=0,
    first_dense_layers=1,
    moe_intermediate_size=1536,
    n_shared_experts=1,
    moe_scoring="sigmoid+bias",
    norm_topk_prob=True,
    routed_scaling_factor=1.0,
)

# ai21labs/AI21-Jamba2-3B (config.json, model_type jamba), whole: 28 layers,
# attention in layers 7 and 21 (attn_layer_offset 7, attn_layer_period 14),
# Mamba-1 in the other 26, a dense MLP in every layer (num_experts 1), tied
# embeddings (benchmarks/configs/jamba2-3b-w8a8.json has the reckoning).
# ``rope_theta`` is the file's key, and nothing rotates (``use_rope``).
JAMBA2_3B = ModelConfig(
    name="jamba2-3b",
    vocab_size=65_536,
    hidden_size=2560,
    intermediate_size=8192,
    num_layers=28,
    num_heads=20,
    num_kv_heads=1,
    head_dim=128,
    rope_theta=10_000.0,
    rms_norm_eps=1e-6,
    max_seq_len=262_144,
    tie_embeddings=True,
    use_rope=False,
    attn_layer_period=14,
    attn_layer_offset=7,
    mamba_expand=2,
    mamba_dt_rank=160,
    ssm_state_size=16,
    conv_kernel=4,
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
    for c in [TINY, TINY_QWEN, TINY_MOE, TINY_LATENT_MOE, TINY_NEMOTRON_H,
              TINY_DOTS3_NOTE, TINY_JAMBA, DOTS3_NOTE_PREV_5L, JAMBA2_3B,
              LLAMA3_8B, LLAMA3_70B, MISTRAL_7B, MIXTRAL_8X7B, QWEN2_7B,
              QWEN2_72B, GEMMA2_2B, GEMMA2_9B, LLAMA_1B, KANANA2_30B_A3B_12L,
              NEMOTRON3_SUPER_22L]
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
