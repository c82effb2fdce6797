"""Llama-3 / Qwen2-family decoder LM, pure-functional JAX.

Design notes (TPU-first, not a port):
  - Params are a plain pytree (nested dicts + per-layer list).  Linear kernels
    are stored ``[in_features, out_features]`` so the forward pass is a single
    ``x @ W`` that XLA tiles onto the MXU; HF checkpoints are transposed once
    at load time (utils/checkpoint.py).
  - Three entry points, all shape-static and jittable:
      * ``forward_full``  — dense causal forward (training / logit parity).
      * ``prefill``       — padded-batch prompt ingestion that scatters K/V
                            into a paged block cache and returns last-token
                            logits.
      * ``decode_step``   — one-token step over the paged cache.
  - The paged KV cache is a pytree of per-layer page arrays
    ``[num_blocks, block_size, kv_heads * head_dim]`` (fused lane layout —
    see ``KVPages``).  Block id 0 is reserved
    as the null block: masked/inactive lanes scatter their writes there, which
    keeps every write shape-static without corrupting live sequences
    (serving/kv_cache.py never allocates block 0).

Capability context: this model is the Analysis Engine backend the reference
only configured but never implemented (reference internal/config/config.go:
141-145 holds the entire LLM integration; README.md:89-95 documents the
/api/v1/query endpoint that cmd/server/main.go never registers).
"""

from __future__ import annotations

import functools
from typing import Any, NamedTuple, Optional

import jax
import jax.numpy as jnp

from k8s_llm_monitor_tpu.models.config import LatentGeometry, ModelConfig
from k8s_llm_monitor_tpu.ops import grouped, sparse
from k8s_llm_monitor_tpu.ops.attention import (
    blockwise_attention,
    causal_attention,
    gather_pages,
    index_scores_decode,
    paged_decode_attention,
    paged_decode_attention_quant,
)
from k8s_llm_monitor_tpu.ops.norms import rms_norm
from k8s_llm_monitor_tpu.ops.rope import apply_rope, rope_angles
from k8s_llm_monitor_tpu.ops.ssm import (
    pack_state,
    ssm_chunk_scan,
    ssm_decode_update_xla,
    state_pack,
)

Params = dict[str, Any]


class KVPages(NamedTuple):
    """Paged KV cache: per-layer lists of page arrays.

    k[i], v[i]: [num_blocks, block_size, kv_heads * head_dim]

    The kv-heads and head-dim axes are stored FUSED.  This is the Pallas
    decode kernel's native DMA layout (128-lane-aligned page rows); keeping
    the resident arrays in that layout means the per-step attention call
    consumes them directly.  Storing [..., KVH, D] instead costs a physical
    relayout copy of every page array on every decode step (~4.6 GB/step
    for 8B at 2200 blocks — measured as 64 materialized reshapes in the
    compiled HLO, and most of the decode step time).

    Mesh execution: the fused lane dim is kv-head-MAJOR (``reshape(KVH*D)``
    of ``[..., KVH, D]``), so sharding it ``model``-ways when tp divides
    KVH is exactly a per-chip contiguous slice of ``KVH/tp`` whole heads —
    ``SpecLayout.kv_pages`` (parallel/sharding.py) relies on this, and it
    is why head-sharded paged attention needs no resharding collective at
    the page boundary.  The page/block axes are NEVER sharded: block ids
    stay global (serving/kv_cache.py module docstring), every chip
    scatters/gathers with the same block table, and the host allocator
    stays mesh-agnostic.

    Latent page kind (``ModelConfig.layer_spec(i).cache == "latent"``): a
    cached token is ONE row per layer with no kv-head axis,
    ``k[i]: [num_blocks, block_size, cfg.latent_page_width]`` holding
    ``[normalised latent c (kv_lora_rank) | rotated key (qk_rope_head_dim) |
    zeros]`` — the rotated key's lanes are padded to a whole 128-lane tile so
    that a row is lane-aligned for the decode kernel's page DMA and the
    pool's cost is what its shape says.  ``v`` is an empty list: the value of
    a row is its own first ``kv_lora_rank`` lanes.  Block ids, block tables
    and the allocator are the same as for the kv kind.

    A pool whose layers differ in kind (``ModelConfig.layer_pattern``): ``k``
    and ``v`` hold one entry for each layer whose cache is of kind ``"kv"``,
    in layer order (``cfg.layers_with("kv")``) — a layer without cached
    tokens has no pages.  ``ssm`` / ``conv`` are the **state pool**, one
    entry for each layer of kind ``"state"``: a Mamba-2 mixer's recurrent
    state ``[lanes, H / pack, N, pack * P]`` float32 (ops/ssm.py has the
    layout) and the last ``conv_kernel - 1`` un-convolved rows of its short
    convolution ``[lanes, conv_kernel - 1, channels]``.  It is indexed by
    decode lane, not by block: a lane's state costs the same whatever its
    context holds, is overwritten whole when a fresh prompt is admitted to
    the lane, and is updated in place by the programs that are given the
    pool (donated with the pages).  Empty tuples (the default) for a model
    without recurrent layers: no extra leaves, the same treedef as ever.
    """

    k: list[jnp.ndarray]
    v: list[jnp.ndarray]
    # Quantized-KV tier (serving/kv_tier.py, docs/serving.md): per-layer
    # scale arrays [num_blocks, block_size, kv_heads] float32 — one
    # symmetric scale per (token, head).  Empty tuples (the default) mean
    # an unquantized pool: no extra pytree leaves, so every pre-existing
    # jitted program keeps its exact treedef and donation layout.
    k_scale: tuple | list = ()
    v_scale: tuple | list = ()
    ssm: tuple | list = ()
    conv: tuple | list = ()
    idx: tuple | list = ()
    win: tuple | list = ()

    @property
    def num_blocks(self) -> int:
        return self.k[0].shape[0]

    @property
    def block_size(self) -> int:
        return self.k[0].shape[1]

    @property
    def quantized(self) -> bool:
        return len(self.k_scale) > 0


def kv_quant_spec(kv_quant: str) -> tuple[Any, float]:
    """(storage dtype, qmax) for a KV quantization mode.

    ``fp8`` is float8_e4m3fn, anything else int8.
    """
    if kv_quant == "fp8":
        return jnp.dtype(jnp.float8_e4m3fn), 448.0
    return jnp.dtype(jnp.int8), 127.0


def quantize_kv(x: jnp.ndarray, num_kv_heads: int, qdtype,
                qmax: float) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Per-(token, head) symmetric quantization of fused-lane KV rows.

    x [..., KVH*D] -> (x_q [..., KVH*D] qdtype, scale [..., KVH] float32).
    Mirrors ``_quant_act``'s amax/qmax idiom; int8 rounds-and-clips, fp8
    casts (saturating on TPU).
    """
    shp = x.shape
    D = shp[-1] // num_kv_heads
    xr = x.astype(jnp.float32).reshape(*shp[:-1], num_kv_heads, D)
    amax = jnp.max(jnp.abs(xr), axis=-1)
    scale = jnp.maximum(amax / qmax, 1e-8)
    xq = xr / scale[..., None]
    if jnp.dtype(qdtype) == jnp.int8:
        xq = jnp.clip(jnp.round(xq), -qmax, qmax)
    return xq.astype(qdtype).reshape(shp), scale


def dequantize_kv(x_q: jnp.ndarray, scale: jnp.ndarray,
                  dtype=jnp.float32) -> jnp.ndarray:
    """Inverse of ``quantize_kv``: x_q [..., KVH*D] + scale [..., KVH]
    -> float rows [..., KVH*D]."""
    shp = x_q.shape
    KVH = scale.shape[-1]
    xr = x_q.astype(jnp.float32).reshape(*shp[:-1], KVH, shp[-1] // KVH)
    return (xr * scale[..., None]).reshape(shp).astype(dtype)


def init_kv_pages(cfg: ModelConfig, num_blocks: int, block_size: int,
                  kv_quant: str = "", state_lanes: int = 0) -> KVPages:
    """Allocate the paged KV pool.  ``kv_quant`` ("int8"/"fp8") selects the
    quantized tier: page arrays in the storage dtype plus per-(token, head)
    float32 scale arrays; "" keeps the historical unquantized layout.
    The page kind is the description's (``cfg.layer_spec(i).cache``): a
    latent pool is one array a layer, a layer without cached tokens has no
    pages, and a recurrent layer has a row for each of ``state_lanes`` decode
    lanes in the state pool (see :class:`KVPages`)."""
    kv_layers = len(cfg.layers_with("kv"))
    state = {}
    if cfg.recurrent:
        if kv_quant:
            raise ValueError(
                f"a pool beside recurrent state is not built for "
                f"kv_dtype={kv_quant!r}")
        Hm, P, N = cfg.mamba_num_heads, cfg.mamba_head_dim, cfg.ssm_state_size
        pack = state_pack(Hm, cfg.mamba_n_groups, P)
        n = len(cfg.layers_with("state"))
        state = dict(
            ssm=[jnp.zeros((state_lanes, Hm // pack, N, pack * P),
                           jnp.float32) for _ in range(n)],
            conv=[jnp.zeros((state_lanes, cfg.conv_kernel - 1,
                             cfg.mamba_conv_dim), jnp.dtype(cfg.dtype))
                  for _ in range(n)])
    if cfg.latent:
        if kv_quant:
            raise ValueError(
                f"latent pages are not built for kv_dtype={kv_quant!r}")
        dtype = jnp.dtype(cfg.kv_dtype or cfg.dtype)
        paged = [cfg.latent_geometry(i) for i in cfg.layers_with("latent")]
        extra = {}
        if any(g.indexed for g in paged):
            extra["idx"] = [jnp.zeros((num_blocks, block_size, g.index_dim),
                                      dtype) for g in paged if g.indexed]
        if cfg.layers_with("window"):
            ring = 1 + state_lanes * (cfg.window_rows(block_size)
                                      // block_size)
            extra["win"] = [
                jnp.zeros((ring, block_size,
                           cfg.latent_geometry(i).page_width), dtype)
                for i in cfg.layers_with("window")]
        return KVPages(k=[jnp.zeros((num_blocks, block_size, g.page_width),
                                    dtype) for g in paged], v=[], **extra)
    shape = (num_blocks, block_size, cfg.num_kv_heads * cfg.head_dim_)
    if kv_quant:
        qdtype, _ = kv_quant_spec(kv_quant)
        sshape = (num_blocks, block_size, cfg.num_kv_heads)
        return KVPages(
            k=[jnp.zeros(shape, qdtype) for _ in range(kv_layers)],
            v=[jnp.zeros(shape, qdtype) for _ in range(kv_layers)],
            k_scale=[jnp.zeros(sshape, jnp.float32)
                     for _ in range(kv_layers)],
            v_scale=[jnp.zeros(sshape, jnp.float32)
                     for _ in range(kv_layers)],
        )
    dtype = jnp.dtype(cfg.kv_dtype or cfg.dtype)
    return KVPages(
        k=[jnp.zeros(shape, dtype) for _ in range(kv_layers)],
        v=[jnp.zeros(shape, dtype) for _ in range(kv_layers)],
        **state,
    )


# ---------------------------------------------------------------------------
# Init
# ---------------------------------------------------------------------------


def init_params(rng: jax.Array, cfg: ModelConfig) -> Params:
    """Random-init parameters (truncated-normal-ish scaled normals)."""
    dtype = jnp.dtype(cfg.dtype)
    H, D = cfg.hidden_size, cfg.head_dim_
    nH, nKV, I = cfg.num_heads, cfg.num_kv_heads, cfg.intermediate_size

    def dense(key, in_f, out_f, bias):
        w = jax.random.normal(key, (in_f, out_f), jnp.float32) * (in_f ** -0.5)
        p = {"kernel": w.astype(dtype)}
        if bias:
            p["bias"] = jnp.zeros((out_f,), dtype)
        return p

    def expert_dense(key, in_f, out_f):
        # Stacked expert kernels [E, in, out], E the experts held here;
        # leading axis shards over the mesh's ``model`` axis (expert
        # parallelism).
        w = jax.random.normal(
            key, (cfg.experts_held_, in_f, out_f), jnp.float32) * (in_f ** -0.5)
        return {"kernel": w.astype(dtype)}

    def mlp(keys, width):
        return {"gate": dense(keys[0], H, width, False),
                "up": dense(keys[1], H, width, False),
                "down": dense(keys[2], width, H, False)}

    # Gemma stores norm weights as zero-centered deltas (effective scale
    # 1 + w), so identity-init is zeros there, ones elsewhere.
    norm_init = jnp.zeros if cfg.rmsnorm_unit_offset else jnp.ones

    keys = jax.random.split(rng, 2 + cfg.num_layers)
    layers = []
    for i in range(cfg.num_layers):
        # 8 keys as ever (a dense or Mixtral layer's weights do not move);
        # what a shared+routed layer adds draws from a stream of its own.
        lk = jax.random.split(keys[2 + i], 8)
        xk = jax.random.split(jax.random.fold_in(keys[2 + i], 1), 4)
        spec = cfg.layer_spec(i)
        if cfg.layer_pattern is not None:
            layers.append(init_single_layer(keys[2 + i], cfg, spec, dense,
                                            expert_dense))
            continue
        layer = {
            "input_norm": norm_init((H,), dtype),
            "post_norm": norm_init((H,), dtype),
        }
        if spec.mixer == "latent" and _low_rank_mixer(cfg, i):
            layer.update(init_latent_mixer(
                jax.random.fold_in(keys[2 + i], 2), cfg,
                cfg.latent_geometry(i), dense,
                lambda key, in_f, out_f: dense(key, in_f, out_f, False)))
        elif spec.mixer == "latent":
            R, dn, dr = (cfg.kv_lora_rank, cfg.qk_nope_head_dim,
                         cfg.qk_rope_head_dim)
            layer["q"] = dense(lk[0], H, nH * (dn + dr), False)
            layer["kv_a"] = dense(lk[1], H, R + dr, False)
            layer["kv_norm"] = norm_init((R,), dtype)
            layer["kv_b"] = dense(lk[2], R, nH * (dn + cfg.v_head_dim), False)
            layer["o"] = dense(lk[3], nH * cfg.v_head_dim, H, False)
        else:
            layer["q"] = dense(lk[0], H, nH * D, cfg.qkv_bias)
            layer["k"] = dense(lk[1], H, nKV * D, cfg.qkv_bias)
            layer["v"] = dense(lk[2], H, nKV * D, cfg.qkv_bias)
            layer["o"] = dense(lk[3], nH * D, H, False)
        if cfg.sandwich_norms:
            layer["post_attn_norm"] = norm_init((H,), dtype)
            layer["post_mlp_norm"] = norm_init((H,), dtype)
        if spec.mlp == "dense":
            layer.update(mlp(lk[4:7], I))
        else:
            Ie = cfg.expert_width
            layer["router"] = dense(lk[7], H, cfg.num_experts, False)
            if cfg.moe_scoring == "sigmoid+bias":
                # Published code scores in float32; the selection bias is
                # zeros plus a small normal (a trained one is not reachable),
                # used for the choice only.
                layer["router"] = {
                    "kernel": layer["router"]["kernel"].astype(jnp.float32),
                    "e_bias": 0.01 * jax.random.normal(
                        xk[0], (cfg.num_experts,), jnp.float32)}
            layer["gate_e"] = expert_dense(lk[4], H, Ie)
            layer["up_e"] = expert_dense(lk[5], H, Ie)
            layer["down_e"] = expert_dense(lk[6], Ie, H)
            if spec.mlp == "shared+routed":
                layer["shared"] = mlp(xk[1:4], cfg.n_shared_experts * Ie)
        layers.append(layer)
    params: Params = {
        "embed": {
            "weight": (
                jax.random.normal(keys[0], (cfg.vocab_size, H), jnp.float32) * 0.02
            ).astype(dtype)
        },
        "layers": layers,
        "final_norm": norm_init((H,), dtype),
    }
    if not cfg.tie_embeddings:
        params["lm_head"] = dense(keys[1], H, cfg.vocab_size, False)
    return params


def _low_rank_mixer(cfg: ModelConfig, i: int) -> bool:
    """Layer ``i``'s latent mixer is the one ``init_latent_mixer`` builds:
    low-rank queries, with whatever gate and indexer its geometry has."""
    g = cfg.latent_geometry(i)
    if not g.q_lora_rank and (g.gate or g.indexed):
        raise NotImplementedError(
            "a gate or an indexer on a latent mixer with direct queries: the "
            "indexer reads the query latent")
    return bool(g.q_lora_rank)


def init_latent_mixer(key: jax.Array, cfg: ModelConfig, g: LatentGeometry,
                      dense, wide) -> Params:
    """The leaves of a latent mixer with low-rank queries (``q_a``, its
    norm, ``q_b``), a head-wise gate and an indexer where the geometry has
    them.  ``dense(key, in, out, bias)`` builds a projection in the caller's
    form (int8 in utils/quantize.py), ``wide(key, in, out)`` one that stays
    in the activation dtype under every quantisation (``W_kvb``: ``_kv_b``
    says why); norms are built here."""
    H, dtype = cfg.hidden_size, jnp.dtype(cfg.dtype)
    nH, R, Rq = g.num_heads, g.kv_lora_rank, g.q_lora_rank
    dn, dr, dv = g.qk_nope_head_dim, g.qk_rope_head_dim, g.v_head_dim
    k = jax.random.split(key, 9)
    layer = {"q_a": dense(k[0], H, Rq, False),
             "q_norm": jnp.ones((Rq,), dtype),
             "q_b": dense(k[1], Rq, nH * (dn + dr), False),
             "kv_a": dense(k[2], H, R + dr, False),
             "kv_norm": jnp.ones((R,), dtype),
             "kv_b": wide(k[3], R, nH * (dn + dv)),
             "o": dense(k[4], nH * dv, H, False)}
    if g.gate:
        layer["attn_gate"] = dense(k[5], H, nH, False)
    if g.indexed:
        layer["idx_q"] = dense(k[6], Rq, g.index_heads * g.index_dim, False)
        layer["idx_k"] = dense(k[7], H, g.index_dim, False)
        layer["idx_k_norm"] = {"weight": jnp.ones((g.index_dim,), dtype),
                               "bias": jnp.zeros((g.index_dim,), dtype)}
        layer["idx_w"] = dense(k[8], H, g.index_heads, False)
    return layer


def mamba_init(key: jax.Array, cfg: ModelConfig) -> Params:
    """What a Mamba-2 mixer holds beside its two projections, seeded the
    family's usual way (a trained one is not reachable): the depthwise
    convolution (float32, uniform in +-conv_kernel**-0.5) and its bias
    (zeros), ``dt_bias`` such that ``softplus(dt_bias)`` is log-uniform in
    0.001-0.1 (the published ``time_step_min`` / ``time_step_max``),
    ``A_log`` = log of a uniform 1-16, ``D`` ones, the gated norm ones."""
    k = jax.random.split(key, 3)
    Hm, Kc = cfg.mamba_num_heads, cfg.conv_kernel
    dt = jnp.exp(jax.random.uniform(k[0], (Hm,), jnp.float32)
                 * (jnp.log(0.1) - jnp.log(0.001)) + jnp.log(0.001))
    return {
        "conv": {"kernel": jax.random.uniform(
                     k[1], (Kc, cfg.mamba_conv_dim), jnp.float32,
                     -(Kc ** -0.5), Kc ** -0.5),
                 "bias": jnp.zeros((cfg.mamba_conv_dim,), jnp.float32)},
        "dt_bias": dt + jnp.log(-jnp.expm1(-dt)),     # softplus's inverse
        "A_log": jnp.log(jax.random.uniform(k[2], (Hm,), jnp.float32,
                                            1.0, 16.0)),
        "D": jnp.ones((Hm,), jnp.float32),
        "ssm_norm": jnp.ones((cfg.mamba_inner,), jnp.dtype(cfg.dtype)),
    }


def init_single_layer(key: jax.Array, cfg: ModelConfig, spec, dense,
                      expert_dense) -> Params:
    """A layer that is one sub-block under one norm
    (``ModelConfig.layer_pattern``).  ``dense(key, in, out, bias)`` and
    ``expert_dense(key, in, out)`` build a projection and a stack of the
    held experts' kernels in the caller's form (wide here, int8 in
    utils/quantize.py:init_params_quantized); what stays wide under every
    quantisation is built here."""
    H, D = cfg.hidden_size, cfg.head_dim_
    k = jax.random.split(key, 8)
    layer = {"input_norm": jnp.ones((H,), jnp.dtype(cfg.dtype))}
    if spec.mixer == "mamba2":
        layer["in_proj"] = dense(
            k[0], H, cfg.mamba_inner + cfg.mamba_conv_dim
            + cfg.mamba_num_heads, False)
        layer["out_proj"] = dense(k[1], cfg.mamba_inner, H, False)
        layer.update(mamba_init(k[2], cfg))
    elif spec.mixer == "full":
        layer["q"] = dense(k[0], H, cfg.num_heads * D, cfg.qkv_bias)
        layer["k"] = dense(k[1], H, cfg.num_kv_heads * D, cfg.qkv_bias)
        layer["v"] = dense(k[2], H, cfg.num_kv_heads * D, cfg.qkv_bias)
        layer["o"] = dense(k[3], cfg.num_heads * D, H, False)
    else:
        if cfg.moe_scoring != "sigmoid+bias" or cfg.mlp_gated:
            raise NotImplementedError(
                "a feed-forward layer of a layer_pattern is the un-gated "
                "sigmoid-routed one")
        L, Ie = cfg.moe_latent_size or H, cfg.expert_width
        # Scored in float32; the selection bias is zeros plus a small
        # normal (a trained one is not reachable), used for the choice only.
        layer["router"] = {
            "kernel": jax.random.normal(k[0], (H, cfg.num_experts),
                                        jnp.float32) * H ** -0.5,
            "e_bias": 0.01 * jax.random.normal(
                k[1], (cfg.num_experts,), jnp.float32)}
        if cfg.moe_latent_size:
            layer["latent_down"] = dense(k[2], H, L, False)
            layer["latent_up"] = dense(k[3], L, H, False)
        layer["up_e"] = expert_dense(k[4], L, Ie)
        layer["down_e"] = expert_dense(k[5], Ie, L)
        if spec.mlp == "shared+routed":
            layer["shared"] = {"up": dense(k[6], H, cfg.shared_width, False),
                               "down": dense(k[7], cfg.shared_width, H,
                                             False)}
    return layer


# ---------------------------------------------------------------------------
# Building blocks
# ---------------------------------------------------------------------------


def _quant_act(x: jnp.ndarray) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Dynamic per-token symmetric int8: (x_q int8, scale f32 [..., 1])."""
    amax = jnp.max(jnp.abs(x.astype(jnp.float32)), axis=-1, keepdims=True)
    scale = jnp.maximum(amax / 127.0, 1e-8)
    x_q = jnp.clip(jnp.round(x.astype(jnp.float32) / scale),
                   -127, 127).astype(jnp.int8)
    return x_q, scale


def _linear(p: Params, x: jnp.ndarray, act_quant: bool = False) -> jnp.ndarray:
    if act_quant and "kernel_q" not in p:
        # Trace-time check: act_quant requires int8 weights; silently
        # running bf16 matmuls would hide the misconfiguration behind
        # benchmarks that show no speedup.
        import warnings

        warnings.warn(
            "act_quant=True but weights are not int8-quantized "
            "(no kernel_q); running the bf16 path — quantize the params "
            "(utils/quantize.py) to get the s8 x s8 MXU speedup",
            stacklevel=2)
    if "kernel_q" in p:
        if act_quant:
            # W8A8: s8 x s8 -> s32 on the MXU int8 path (measured ~1.4x the bf16
            # rate on v5e); both scales factor out of the contraction.
            x_q, xs = _quant_act(x)
            y32 = jax.lax.dot_general(
                x_q, p["kernel_q"],
                (((x.ndim - 1,), (0,)), ((), ())),
                preferred_element_type=jnp.int32,
            )
            y = (y32.astype(jnp.float32) * xs
                 * p["scale"]).astype(x.dtype)
        else:
            # Weight-only int8 (utils/quantize.py): per-output-channel
            # scale commutes with the contraction, so dequant is a
            # [out]-vector multiply on the result, never a materialized
            # bf16 weight.  The int8->activation-dtype cast fuses into
            # the MXU operand read.
            y = ((x @ p["kernel_q"].astype(x.dtype))
                 * p["scale"].astype(x.dtype))
    else:
        y = x @ p["kernel"]
    if "bias" in p:
        y = y + p["bias"]
    return y


def row_parallel_partial(p: Params, x: jnp.ndarray, act_quant: bool,
                         axis_name: str):
    """Shard-local half of a row-parallel ``_linear`` for hand-staged
    reduction under ``shard_map`` (parallel/overlap.py).

    Returns ``(partial, finish)``: ``partial`` is this shard's
    un-reduced contribution [..., out] (int32 under W8A8 — integer
    addition is associative, so reducing the raw dot output across
    shards is bit-exact in any order); ``finish`` maps the reduced (or
    reduce-scattered) array back to activation dtype, slicing the
    per-out-channel dequant scale to the shard's chunk when the caller
    hands it a scattered slice.

    Exactness contract vs the GSPMD-auto psum of ``_linear``:
      * W8A8: the per-token amax is GLOBAL over the contraction dim —
        GSPMD computes it on the replicated activation, so the shard-local
        amax must be ``pmax``-combined (max is order-independent, exact)
        before quantizing, and the int32 partials must be reduced BEFORE
        the float scales apply, in the same multiply order.
      * weight-only int8: per-out-channel scales commute with the
        contraction, so they apply after the reduce, sliced to the chunk.
    Row projections never carry a bias in the supported model families
    (``overlap_supported`` gates on it): a bias must be added exactly
    once, not once per shard.
    """
    if "kernel_q" in p and act_quant:
        amax = jnp.max(jnp.abs(x.astype(jnp.float32)), axis=-1,
                       keepdims=True)
        amax = jax.lax.pmax(amax, axis_name)
        scale = jnp.maximum(amax / 127.0, 1e-8)
        x_q = jnp.clip(jnp.round(x.astype(jnp.float32) / scale),
                       -127, 127).astype(jnp.int8)
        part = jax.lax.dot_general(
            x_q, p["kernel_q"],
            (((x.ndim - 1,), (0,)), ((), ())),
            preferred_element_type=jnp.int32,
        )

        def finish(y: jnp.ndarray) -> jnp.ndarray:
            ws = _out_chunk(p["scale"], y.shape[-1], axis_name)
            return (y.astype(jnp.float32) * scale * ws).astype(x.dtype)
    elif "kernel_q" in p:
        part = x @ p["kernel_q"].astype(x.dtype)

        def finish(y: jnp.ndarray) -> jnp.ndarray:
            ws = _out_chunk(p["scale"], y.shape[-1], axis_name)
            return y * ws.astype(y.dtype)
    else:
        part = x @ p["kernel"]

        def finish(y: jnp.ndarray) -> jnp.ndarray:
            return y
    return part, finish


def _out_chunk(vec: jnp.ndarray, chunk: int, axis_name: str) -> jnp.ndarray:
    """This shard's contiguous chunk of a replicated per-out-channel
    vector (row-parallel o/down scales replicate under partition_rules —
    no regex matches them — so each shard slices its own piece)."""
    if vec.shape[0] == chunk:
        return vec
    idx = jax.lax.axis_index(axis_name)
    return jax.lax.dynamic_slice_in_dim(vec, idx * chunk, chunk, axis=0)


def _embed_lookup(params: Params, cfg: ModelConfig,
                  tokens: jnp.ndarray) -> jnp.ndarray:
    """Token embedding lookup, handling int8-quantized tables."""
    emb = params["embed"]
    dtype = jnp.dtype(cfg.dtype)
    with jax.named_scope("embed"):
        if "weight_q" in emb:
            rows = emb["weight_q"][tokens].astype(dtype)
            rows = rows * emb["scale"][tokens][..., None].astype(dtype)
        else:
            rows = emb["weight"][tokens]
        if cfg.embed_scale:   # Gemma: sqrt(H) normalizer, rounded to dtype
            rows = rows * jnp.asarray(cfg.hidden_size ** 0.5, rows.dtype)
    return rows


def _qkv_proj(layer: Params, cfg: ModelConfig, x: jnp.ndarray):
    """Projections only (no rope).  x: [B, S, H] -> q [B,S,nH,D],
    k/v [B,S,nKV,D].  The fused decode kernel takes these raw and applies
    rope in-kernel; every other path ropes via ``_qkv``."""
    B, S, _ = x.shape
    D = cfg.head_dim_
    aq = cfg.act_quant
    with jax.named_scope("qkv"):
        q = _linear(layer["q"], x, aq).reshape(B, S, cfg.num_heads, D)
        k = _linear(layer["k"], x, aq).reshape(B, S, cfg.num_kv_heads, D)
        v = _linear(layer["v"], x, aq).reshape(B, S, cfg.num_kv_heads, D)
    return q, k, v


def _qkv(layer: Params, cfg: ModelConfig, x: jnp.ndarray, cos, sin):
    """Project + rope.  x: [B, S, H] -> q [B,S,nH,D], k/v [B,S,nKV,D]."""
    q, k, v = _qkv_proj(layer, cfg, x)
    if not cfg.use_rope:
        return q, k, v
    with jax.named_scope("qkv"):
        q = apply_rope(q, cos, sin)
        k = apply_rope(k, cos, sin)
    return q, k, v


def _rope_kind(cfg: ModelConfig, li: int) -> tuple[int, float]:
    """(lanes, theta) of the rotation layer ``li`` makes: the whole head at
    ``rope_theta``, or its latent geometry's rope part at its own theta."""
    if not cfg.latent:
        return cfg.head_dim_, cfg.rope_theta
    g = cfg.latent_geometry(li)
    return g.qk_rope_head_dim, g.rope_theta


def _rope_tables(cfg: ModelConfig, positions: jnp.ndarray) -> dict:
    """(cos, sin) of ``positions`` for every rotation the model makes, by
    ``_rope_kind`` (one table a model, two where its geometries differ)."""
    kinds = dict.fromkeys(_rope_kind(cfg, li) for li in range(cfg.num_layers))
    return {kind: rope_angles(positions, *kind, scaling=cfg.rope_scaling)
            for kind in kinds}


def _rope_of(cfg: ModelConfig, tables: dict, li: int):
    """Layer ``li``'s (cos, sin) out of ``_rope_tables``."""
    return tables[_rope_kind(cfg, li)]


def _latent_qkv(layer: Params, cfg: ModelConfig, g: LatentGeometry,
                x: jnp.ndarray, cos, sin):
    """Latent mixer, what is computed per token.  x [B, S, H] ->
    q_nope [B,S,nH,dn], q_rope [B,S,nH,dr] (rotated), c [B,S,R] (the
    normalised latent), k_rope [B,S,dr] (one rotated key shared by all
    heads) and the query latent ``cq`` [B,S,Rq] (None where the query
    projection is direct).  ``c`` and ``k_rope`` are all that is cached."""
    B, S, _ = x.shape
    R, dn = g.kv_lora_rank, g.qk_nope_head_dim
    aq = cfg.act_quant
    with jax.named_scope("qkv"):
        cq = None
        if g.q_lora_rank:
            cq = rms_norm(_linear(layer["q_a"], x, aq), layer["q_norm"],
                          cfg.rms_norm_eps, cfg.rmsnorm_unit_offset)
            if g.q_scale != 1.0:
                cq = cq * jnp.asarray(g.q_scale, cq.dtype)
            q = _linear(layer["q_b"], cq, aq).reshape(B, S, g.num_heads, -1)
        else:
            q = _linear(layer["q"], x, aq).reshape(B, S, g.num_heads, -1)
        kva = _linear(layer["kv_a"], x, aq)                 # [B, S, R + dr]
        c = rms_norm(kva[..., :R], layer["kv_norm"], cfg.rms_norm_eps,
                     cfg.rmsnorm_unit_offset)
        if g.kv_scale != 1.0:
            c = c * jnp.asarray(g.kv_scale, c.dtype)
        q_rope = apply_rope(q[..., dn:], cos, sin)
        k_rope = apply_rope(kva[..., None, R:], cos, sin)[:, :, 0]
    return q[..., :dn], q_rope, c, k_rope, cq


def _index_qk(layer: Params, cfg: ModelConfig, g: LatentGeometry,
              x: jnp.ndarray, cq: jnp.ndarray, cos, sin):
    """The indexer, what is computed per token.  x [B, S, H] (the layer's
    normed input), cq [B, S, Rq] -> its queries qI [B,S,Hi,Di] off the
    query latent, its one key a token kI [B,S,Di] (a LayerNorm of a
    projection of x) — both with their first ``qk_rope_head_dim`` lanes
    rotated — and the heads' weights w [B,S,Hi] float32, with the score's
    1/sqrt(Hi) and 1/sqrt(Di) folded in.  ``kI`` is all that is cached."""
    B, S, _ = x.shape
    Hi, Di, dr = g.index_heads, g.index_dim, g.qk_rope_head_dim
    aq = cfg.act_quant
    with jax.named_scope("attn/indexer"):
        qI = _linear(layer["idx_q"], cq, aq).reshape(B, S, Hi, Di)
        k32 = _linear(layer["idx_k"], x, aq).astype(jnp.float32)
        mu = jnp.mean(k32, axis=-1, keepdims=True)
        var = jnp.mean(jnp.square(k32 - mu), axis=-1, keepdims=True)
        n = layer["idx_k_norm"]
        kI = ((k32 - mu) * jax.lax.rsqrt(var + cfg.rms_norm_eps)
              * n["weight"].astype(jnp.float32)
              + n["bias"].astype(jnp.float32)).astype(x.dtype)
        qI = jnp.concatenate(
            [apply_rope(qI[..., :dr], cos, sin), qI[..., dr:]], axis=-1)
        kI = jnp.concatenate(
            [apply_rope(kI[..., None, :dr], cos, sin)[:, :, 0], kI[..., dr:]],
            axis=-1)
        w = (_linear(layer["idx_w"], x, aq).astype(jnp.float32)
             * (Hi ** -0.5 * Di ** -0.5))
    return qI, kI, w


def _page_width(g: LatentGeometry, latent: jnp.ndarray,
                rope: jnp.ndarray) -> jnp.ndarray:
    """``[latent part | rope part | zeros]`` at the latent page's width: the
    layout of a cached row, and of the absorbed query that meets it."""
    pad = g.page_width - latent.shape[-1] - rope.shape[-1]
    zeros = jnp.zeros((*latent.shape[:-1], pad), latent.dtype)
    return jnp.concatenate([latent, rope, zeros], axis=-1)


def _latent_rows(g: LatentGeometry, c: jnp.ndarray,
                 k_rope: jnp.ndarray) -> jnp.ndarray:
    """The page row of each token: ``[c | k_rope | zeros]`` [B, S, 1, F]."""
    return _page_width(g, c, k_rope)[:, :, None, :]


def _kv_b(layer: Params, g: LatentGeometry):
    """``W_kvb`` by head: (W_UK [R, nH, dn], W_UV [R, nH, dv]).  It stays in
    the activation dtype under every quantisation (utils/quantize.py): the
    absorbed form multiplies queries and outputs by it, not the cached
    latent, so an activation-quantised product would differ between the two
    forms."""
    w = layer["kv_b"]["kernel"].reshape(g.kv_lora_rank, g.num_heads, -1)
    return w[..., :g.qk_nope_head_dim], w[..., g.qk_nope_head_dim:]


def window_tables(lanes: jnp.ndarray, pool_blocks: int,
                  ring_blocks: int) -> jnp.ndarray:
    """The block table of each row's ring in the window store
    (``KVPages.win``): lane l's blocks are ``1 + l * ring_blocks ...``; a
    lane past the pool (an idle row) gets the null block.  [B] -> [B,
    ring_blocks] int32."""
    first = 1 + lanes.astype(jnp.int32) * ring_blocks
    table = first[:, None] + jnp.arange(ring_blocks, dtype=jnp.int32)[None, :]
    return jnp.where(table < pool_blocks, table, 0)


def _latent_attend_expanded(layer: Params, cfg: ModelConfig,
                            g: LatentGeometry, q_nope, q_rope,
                            c, k_rope, positions, kv_len, attn_fn=None,
                            kernel=None, view: Optional["RowView"] = None,
                            index=None, sel_stats: Optional[list] = None,
                            selection: Optional[list] = None):
    """Expanded form over the batch's own tokens: per-head keys
    ``[k_nope | k_rope]`` and values from ``c W_kvb``.  ``kernel``: the
    Pallas kernel of a fresh prefill (positions are indices,
    ops/pallas_attention.py:latent_prefill_attention_pallas); ``attn_fn``:
    the dense oracle (``causal_attention``); neither: blockwise XLA
    operations.  ``view``: the inputs are a packed stream; keys and values
    are expanded on it (per token) and attention sees its rows
    (``positions`` are then the rows').

    A geometry with a window or an indexer (``index`` = the indexer's
    (qI, kI, w) of these tokens) restricts the keys a query may see: the
    band, or the indexer's top ``index_topk`` of the earlier keys
    (ops/sparse.py).  The kernel takes both as options; without one the
    mask is built whole ([rows, S, S]: the CPU's path).  ``sel_stats``
    receives the layer's counts (``_sel_counts``: at admission what the
    lengths imply, the keep mask stays inside the kernel's wrapper);
    ``selection`` (rows, not a stream) an indexed layer's (scores [B, S, S]
    float32, keep [B, S, S] bool) as the path taken computed them."""
    B, S = c.shape[:2]
    w_uk, w_uv = _kv_b(layer, g)
    k_nope = jnp.einsum("bsr,rhd->bshd", c, w_uk)
    v = jnp.einsum("bsr,rhd->bshd", c, w_uv)
    k = jnp.concatenate(
        [k_nope, jnp.broadcast_to(k_rope[:, :, None, :],
                                  (B, S, g.num_heads, k_rope.shape[-1]))],
        axis=-1)
    q = jnp.concatenate([q_nope, q_rope], axis=-1)
    scale = g.qk_head_dim ** -0.5
    restricted = bool(g.window or g.indexed)
    if restricted and sel_stats is not None:
        seen = jnp.where(
            jnp.arange(S if view is None else view.take.shape[1],
                       dtype=jnp.int32)[None, :] < kv_len[:, None],
            positions + 1, 0)
        sel_stats.append(_sel_counts(g, seen,
                                     jnp.minimum(seen, g.index_topk)))
    if restricted and kernel is not None:
        extra = dict(window=g.window, topk=g.index_topk,
                     index=None if index is None else tuple(
                         t[0] if view is not None else t for t in index))
        if view is not None:
            return _packed_form(kernel)(
                q[0], k[0], v[0], view.offset, kv_len, scale=scale,
                row_len=view.take.shape[1], **extra)[None]
        if selection is not None and g.indexed:
            attn, scores, keep = kernel(q, k, v, kv_len, scale=scale,
                                        probe=True, **extra)
            selection.append((scores, keep))
            return attn
        return kernel(q, k, v, kv_len, scale=scale, **extra)
    if view is not None and kernel is not None:
        # The kernel's blocks straight from the stream, no row views.
        return _packed_form(kernel)(
            q[0], k[0], v[0], view.offset, kv_len, scale=scale,
            row_len=view.take.shape[1])[None]
    q, k, v = (_rows(view, t) for t in (q, k, v))
    if restricted:
        if attn_fn is not None and not g.indexed:
            attn = attn_fn(q, k, v, q_positions=positions, scale=scale,
                           window=g.window)
            return _stream(view, attn)
        keep = sparse.allowed_keys(
            positions, jnp.full((q.shape[0],), q.shape[1], jnp.int32)
            if kv_len is None else kv_len, q.shape[1], g.window)
        if g.indexed:
            qI, kI, wI = (_rows(view, t) for t in index)
            with jax.named_scope("attn/select"):
                scores = sparse.index_scores(qI, wI, kI)
                keep = sparse.topk_keep(scores, keep, g.index_topk)
            if selection is not None:
                selection.append((scores, keep))
        attn = sparse.masked_attention(q, k, v, keep, scale=scale)
    elif attn_fn is not None:
        attn = attn_fn(q, k, v, q_positions=positions, scale=scale)
    elif kernel is not None:
        attn = kernel(q, k, v, kv_len, scale=scale)
    else:
        attn = blockwise_attention(q, k, v, q_positions=positions,
                                   kv_len=kv_len, scale=scale)
    return _stream(view, attn)


def _sel_counts(g: LatentGeometry, seen: jnp.ndarray,
                kept: Optional[jnp.ndarray] = None) -> jnp.ndarray:
    """float32[3] = (index keys scored, keys selected, window rows read) of
    one layer.  ``seen``: the keys each query has before it (its position +
    1, 0 for padding or an idle lane) — what an indexed layer scores, and,
    capped at ``window``, the rows a window layer's kernel is told to read;
    ``kept``: the keys each query of an indexed layer attends to (at a
    decode step the sum of the keep mask that was applied)."""
    zero = jnp.zeros((), jnp.float32)
    total = lambda n: jnp.sum(n.astype(jnp.float32))             # noqa: E731
    if g.indexed:
        return jnp.stack([total(seen), total(kept), zero])
    return jnp.stack([zero, zero, total(jnp.minimum(seen, g.window))])


def _latent_absorb(layer: Params, g: LatentGeometry, q_nope,
                   q_rope) -> jnp.ndarray:
    """Queries of the absorbed form, against page rows: ``[q_nope W_UK^T |
    q_rope | zeros] / sqrt(dn + dr)`` [B, S, nH, F]."""
    w_uk, _ = _kv_b(layer, g)
    q_abs = _page_width(
        g, jnp.einsum("bshd,rhd->bshr", q_nope, w_uk), q_rope)
    return q_abs * jnp.asarray(g.qk_head_dim ** -0.5, q_abs.dtype)


def _latent_unabsorb(layer: Params, g: LatentGeometry,
                     o_lat: jnp.ndarray) -> jnp.ndarray:
    """``(P c) W_UV`` by head: [B, S, nH, R] -> [B, S, nH, dv]."""
    _, w_uv = _kv_b(layer, g)
    return jnp.einsum("bshr,rhd->bshd", o_lat, w_uv)


def _head_gate(layer: Params, cfg: ModelConfig, g: LatentGeometry,
               h: jnp.ndarray, attn: jnp.ndarray) -> jnp.ndarray:
    """The head-wise output gate: head j's output [B, S, nH, dv] times
    ``sigmoid(W_g h)_j``, one scalar a head from the layer's normed input;
    ``attn`` as it is where the geometry has no gate."""
    if not g.gate:
        return attn
    with jax.named_scope("attn/gate"):
        gate = jax.nn.sigmoid(
            _linear(layer["attn_gate"], h, cfg.act_quant).astype(jnp.float32))
        return (attn.astype(jnp.float32) * gate[..., None]).astype(attn.dtype)


def _marked(attn_impl, mark: str) -> bool:
    """True when ``attn_impl``, or the function a functools.partial wraps
    (tests and CPU runs bind interpret=True that way), carries ``mark``."""
    return bool(getattr(attn_impl, mark, False)
                or getattr(getattr(attn_impl, "func", None), mark, False))


def is_latent_prefill_impl(attn_impl) -> bool:
    """True when ``attn_impl`` is the latent mixer's fresh-prefill kernel
    (expanded form; ops/attention.py:select_prefill_impl)."""
    return _marked(attn_impl, "latent_prefill")


def is_latent_decode_impl(attn_impl) -> bool:
    """True when ``attn_impl`` reads latent pages in the absorbed form
    (ops/attention.py:latent_decode_attention and its Pallas kernel)."""
    return _marked(attn_impl, "latent")


def is_fused_decode_impl(attn_impl) -> bool:
    """True when ``attn_impl`` uses the fused decode calling convention
    (ops/pallas_attention.py:paged_decode_attention_fused — raw q/k/v +
    rope angles in, attention + updated pages out).  Survives a
    functools.partial wrap (tests bind interpret=True that way)."""
    return _marked(attn_impl, "fused_decode")


def is_fused_quant_decode_impl(attn_impl) -> bool:
    """True when ``attn_impl`` is the quantized-KV fused decode kernel
    (ops/pallas_attention.py:paged_decode_attention_fused_quant — takes
    page scales, returns updated scales).  A fused impl WITHOUT this marker
    must never be handed a quantized pool; decode_step falls back to the
    gather/dequant path in that case."""
    return _marked(attn_impl, "quant_kv")


def _packed_form(attn_impl):
    """A prefill kernel's form for a packed stream
    (ops/pallas_attention.py:flash_prefill_attention_packed,
    latent_prefill_attention_packed), with what a functools.partial bound
    (interpret=True)."""
    fn = getattr(attn_impl, "func", attn_impl)
    if fn is attn_impl:
        return fn.packed
    return functools.partial(fn.packed, **attn_impl.keywords)


def is_flash_prefill_impl(attn_impl) -> bool:
    """True when ``attn_impl`` is the flash paged-prefill kernel
    (ops/pallas_attention.py:flash_prefill_attention — tiled online
    softmax reading K/V straight from the pool, scale planes as kwargs
    for quantized pools).  Survives a functools.partial wrap (CPU runs
    bind interpret=True that way)."""
    return _marked(attn_impl, "flash_prefill")


def _expert_weights(p: Params, dtype, act_quant: bool = False):
    """Expert kernel stack for einsum use: bf16 passthrough, or the int8
    stack (cast fuses into the MXU operand read) + its [E, out] scales."""
    if act_quant and "kernel_q" not in p:
        # Trace-time check, mirroring _linear: the MoE MLP is the dominant
        # FLOPs — silently running it bf16 under act_quant would hide the
        # misconfiguration behind benchmarks showing no W8A8 speedup.
        import warnings

        warnings.warn(
            "act_quant=True but expert stacks are not int8-quantized "
            "(no kernel_q); MoE MLP runs the bf16 path — quantize the "
            "params (utils/quantize.py) for the s8 x s8 MXU speedup",
            stacklevel=2)
    if "kernel_q" in p:
        return p["kernel_q"].astype(dtype), p["scale"]
    return p["kernel"], None


def _moe_mlp(layer: Params, cfg: ModelConfig,
             x: jnp.ndarray) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Mixture-of-experts SwiGLU with GShard capacity dispatch.

    x [B, S, H] -> (y [B, S, H], aux scalar).  Everything is expressed as
    dense einsums over a static per-expert capacity C, so the computation
    is one fixed XLA program: with the stacked expert kernels sharded
    [E over ``model``] and the dispatched activations [E, C, H] sharded the
    same way, GSPMD inserts the token all-to-alls automatically — expert
    parallelism with zero manual collectives, the same way the TP specs
    work (parallel/sharding.py).  Overflow beyond C skips the MLP: the
    residual connection passes those tokens through unchanged (standard
    GShard/Switch behavior).

    ``aux`` is the Switch-style load-balancing loss (num_experts * sum of
    mean router probability x mean dispatch fraction per expert, computed
    over the top-1 choice); forward_full folds it out for training.
    """
    B, S, H = x.shape
    T = B * S
    E, K = cfg.num_experts, cfg.num_experts_per_tok
    # GShard token grouping: dispatch within fixed-size groups so the
    # one-hot tensors stay O(T) — ungrouped, [T, E, C] with C ~ T*K/E is
    # quadratic in T and OOMs at long-context training shapes.
    Tg = next(g for g in (512, 256, 128, 64, 32, 16, 8, 4, 2, 1)
              if T % g == 0)
    G = T // Tg
    C = max(1, -(-Tg * K * int(100 * cfg.capacity_factor) // (100 * E)))
    xt = x.reshape(G, Tg, H)

    logits = _linear(layer["router"], xt)                      # [G, Tg, E]
    probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
    topv, topi = jax.lax.top_k(probs, K)                       # [G, Tg, K]
    topv = topv / jnp.sum(topv, axis=-1, keepdims=True)        # renorm

    # Capacity assignment per choice rank within each group: tokens claim
    # slots in index order; a token whose expert is full at its rank is
    # dropped (for that choice only).  dispatch [G, Tg, E, C] one-hot;
    # combine adds the router weight.
    dispatch = jnp.zeros((G, Tg, E, C), jnp.float32)
    combine = jnp.zeros((G, Tg, E, C), jnp.float32)
    used = jnp.zeros((G, E), jnp.int32)     # slots claimed by earlier ranks
    for j in range(K):
        mask_j = jax.nn.one_hot(topi[..., j], E, dtype=jnp.float32)
        pos_j = (jnp.cumsum(mask_j, axis=1) - 1.0
                 + used[:, None, :].astype(jnp.float32))
        keep = (pos_j < C) & (mask_j > 0)
        slot = jax.nn.one_hot(pos_j.astype(jnp.int32), C,
                              dtype=jnp.float32) * keep[..., None]
        dispatch = dispatch + mask_j[..., None] * slot
        combine = combine + (topv[..., j][..., None, None]
                             * mask_j[..., None] * slot)
        used = used + jnp.sum(mask_j * keep, axis=1).astype(jnp.int32)

    xs = jnp.einsum("gtec,gth->gech", dispatch.astype(x.dtype), xt)
    if cfg.act_quant and "kernel_q" in layer["gate_e"]:
        # W8A8 experts: s8 x s8 -> s32 on the MXU int8 path, same contract
        # as _linear (activation scale per token row, weight scale per
        # (expert, out-channel), both factor out of the contraction).
        xs_q, xs_s = _quant_act(xs)
        gate = (jnp.einsum("gech,ehi->geci", xs_q,
                           layer["gate_e"]["kernel_q"],
                           preferred_element_type=jnp.int32)
                .astype(jnp.float32) * xs_s
                * layer["gate_e"]["scale"][None, :, None, :]).astype(x.dtype)
        up = (jnp.einsum("gech,ehi->geci", xs_q,
                         layer["up_e"]["kernel_q"],
                         preferred_element_type=jnp.int32)
              .astype(jnp.float32) * xs_s
              * layer["up_e"]["scale"][None, :, None, :]).astype(x.dtype)
        h2 = jax.nn.silu(gate) * up
        h2_q, h2_s = _quant_act(h2)
        ys = (jnp.einsum("geci,eih->gech", h2_q,
                         layer["down_e"]["kernel_q"],
                         preferred_element_type=jnp.int32)
              .astype(jnp.float32) * h2_s
              * layer["down_e"]["scale"][None, :, None, :]).astype(x.dtype)
    else:
        gk, gs = _expert_weights(layer["gate_e"], x.dtype, cfg.act_quant)
        uk, us = _expert_weights(layer["up_e"], x.dtype)
        dk, ds = _expert_weights(layer["down_e"], x.dtype)
        gate = jnp.einsum("gech,ehi->geci", xs, gk)
        up = jnp.einsum("gech,ehi->geci", xs, uk)
        if gs is not None:   # weight-only int8: dequant on the result
            gate = gate * gs[None, :, None, :].astype(gate.dtype)
            up = up * us[None, :, None, :].astype(up.dtype)
        ys = jnp.einsum("geci,eih->gech", jax.nn.silu(gate) * up, dk)
        if ds is not None:
            ys = ys * ds[None, :, None, :].astype(ys.dtype)
    y = jnp.einsum("gtec,gech->gth", combine.astype(x.dtype), ys)

    # Load balance on the top-1 assignment (Switch Transformer eq. 4).
    top1 = jax.nn.one_hot(topi[..., 0].reshape(-1), E, dtype=jnp.float32)
    aux = E * jnp.sum(jnp.mean(top1, axis=0)
                      * jnp.mean(probs.reshape(-1, E), axis=0))
    return y.reshape(B, S, H), aux


def _moe_mlp_dropless(layer: Params, cfg: ModelConfig,
                      x: jnp.ndarray) -> jnp.ndarray:
    """Dropless MoE for inference: every token gets its full top-k experts.

    The capacity dispatch above is a TRAINING convention — at inference a
    capacity drop would make a request's output depend on what else is
    co-batched (and diverge from HF Mixtral, which is dropless).  This
    path runs every expert's SwiGLU on all tokens as STACKED einsums over
    the expert axis and contracts against the scattered router weights —
    E/K more MLP FLOPs than routed dispatch, which decode never notices
    (it is bound by streaming the expert weights, paid identically either
    way).  Keeping E as an einsum axis (never a Python-loop index) is what
    preserves expert parallelism on a serving mesh: each device computes
    only its local expert shard over the (model-replicated) activations,
    and the final contraction over E becomes the GSPMD psum — a per-expert
    slice loop would instead all-gather every expert's kernel to every
    device.  The [E, B, S, I] transient is per-device E/tp-sliced; on a
    single chip it bounds the dropless chunk size (tiny test configs and
    decode shapes are fine — Mixtral-class weights need a mesh anyway).
    """
    B, S, H = x.shape
    E = cfg.num_experts
    topi, topv = _route(layer, cfg, x)                         # [B, S, K]
    # Router weights scattered back to [B, S, E] (zero for unchosen).
    w = jnp.sum(jax.nn.one_hot(topi, E, dtype=jnp.float32)
                * topv[..., None], axis=2)
    if cfg.act_quant and "kernel_q" in layer["gate_e"]:
        # W8A8 experts (see _moe_mlp): s8 x s8 MXU path for the dominant
        # MLP FLOPs — without this, quantize=w8a8 on MoE models would
        # silently run bf16 expert matmuls.
        x_q, x_s = _quant_act(x)
        gate = (jnp.einsum("bsh,ehi->ebsi", x_q,
                           layer["gate_e"]["kernel_q"],
                           preferred_element_type=jnp.int32)
                .astype(jnp.float32) * x_s[None]
                * layer["gate_e"]["scale"][:, None, None, :]).astype(x.dtype)
        up = (jnp.einsum("bsh,ehi->ebsi", x_q,
                         layer["up_e"]["kernel_q"],
                         preferred_element_type=jnp.int32)
              .astype(jnp.float32) * x_s[None]
              * layer["up_e"]["scale"][:, None, None, :]).astype(x.dtype)
        h2 = jax.nn.silu(gate) * up
        h2_q, h2_s = _quant_act(h2)
        ys = (jnp.einsum("ebsi,eih->ebsh", h2_q,
                         layer["down_e"]["kernel_q"],
                         preferred_element_type=jnp.int32)
              .astype(jnp.float32) * h2_s
              * layer["down_e"]["scale"][:, None, None, :]).astype(x.dtype)
    else:
        gk, gs = _expert_weights(layer["gate_e"], x.dtype, cfg.act_quant)
        uk, us = _expert_weights(layer["up_e"], x.dtype)
        dk, ds = _expert_weights(layer["down_e"], x.dtype)
        gate = jnp.einsum("bsh,ehi->ebsi", x, gk)
        up = jnp.einsum("bsh,ehi->ebsi", x, uk)
        if gs is not None:   # weight-only int8: dequant on the result
            gate = gate * gs[:, None, None, :].astype(gate.dtype)
            up = up * us[:, None, None, :].astype(up.dtype)
        ys = jnp.einsum("ebsi,eih->ebsh", jax.nn.silu(gate) * up, dk)
        if ds is not None:
            ys = ys * ds[:, None, None, :].astype(ys.dtype)
    y = jnp.einsum("ebsh,bse->bsh", ys, w.astype(x.dtype))
    if "shared" in layer:
        y = y + _dense_mlp(layer["shared"], cfg, x)
    return y


def _route(layer: Params, cfg: ModelConfig,
           x: jnp.ndarray) -> tuple[jnp.ndarray, jnp.ndarray]:
    """The router of an expert layer: x [..., H] -> (chosen experts
    [..., K] int32, their weights [..., K] float32), by the description's
    scoring rule.  ``softmax``: top-k of the softmax, renormalised
    (Mixtral).  ``sigmoid+bias``: s = sigmoid(logits); the choice is the
    top-k of ``s + e_bias``, the weights are s of the chosen, normalised
    (``norm_topk_prob``) and scaled by ``routed_scaling_factor``
    (DeepSeek-V3 with one group).  A float32 router kernel is multiplied in
    float32 at full precision, as the published code does: near-ties among
    many experts must not be decided by the activation dtype."""
    r = layer["router"]
    K = cfg.num_experts_per_tok
    with jax.named_scope("router"):
        if r["kernel"].dtype == jnp.float32:
            logits = jnp.dot(x.astype(jnp.float32), r["kernel"],
                             precision=jax.lax.Precision.HIGHEST)
        else:
            logits = _linear(r, x).astype(jnp.float32)
        if cfg.moe_scoring == "sigmoid+bias":
            scores = jax.nn.sigmoid(logits)
            _, topi = jax.lax.top_k(scores + r["e_bias"], K)
            # The chosen experts' scores by a one-hot contraction, not a
            # gather: K x tokens single-element gathers are ~30 ns each on
            # the chip (3 ms a layer at 16k tokens), this is one pass.
            topv = jnp.einsum(
                "...ke,...e->...k",
                jax.nn.one_hot(topi, cfg.num_experts, dtype=jnp.float32),
                scores)
        elif cfg.moe_scoring == "softmax":
            topv, topi = jax.lax.top_k(jax.nn.softmax(logits, axis=-1), K)
        else:
            raise ValueError(f"unknown moe_scoring {cfg.moe_scoring!r}")
        if cfg.norm_topk_prob:
            topv = topv / (jnp.sum(topv, axis=-1, keepdims=True) + 1e-20)
        if cfg.routed_scaling_factor != 1.0:
            topv = topv * cfg.routed_scaling_factor
    return topi, topv


def _expert_rows(p: Params, rows: jnp.ndarray, row_scale, rows_e: jnp.ndarray,
                 group_sizes: jnp.ndarray, dtype) -> jnp.ndarray:
    """One grouped matrix product over the experts that were chosen: row i
    of the expert-sorted assignments against the kernel of its expert;
    nothing is computed for an expert with no rows.  Under W8A8 ``rows`` are
    int8 and ``row_scale`` [rows, 1] their per-row scales (``_linear``'s
    contract: both scales factor out of the contraction), and the int8 x
    int8 -> int32 product takes the form its shape asks for
    (``ops/grouped.py``: at a decode step's few rows an expert a kernel that
    streams each hit expert's kernel once; at an admission call's many a
    row-tiled kernel that dequantises as well, the same expression in its
    epilogue; else ``jax.lax.ragged_dot``, on TPU the compiler's own
    grouped-product kernel); else ``rows`` are wide, ``row_scale`` is None
    and the product is ``ragged_dot``."""
    if row_scale is not None:
        shape = (rows.shape[0], *p["kernel_q"].shape, rows.dtype)
        if grouped.product_form(*shape) == "tiles":
            return grouped.grouped_tiles_product(
                rows, p["kernel_q"], group_sizes, row_scale, p["scale"],
                dtype=jnp.dtype(dtype))
        product = grouped.select_grouped_product(*shape)
        y32 = product(rows, p["kernel_q"], group_sizes)
        return (y32.astype(jnp.float32) * row_scale
                * p["scale"][rows_e]).astype(dtype)
    if "kernel_q" in p:      # weight-only int8: dequantise on the result
        y = jax.lax.ragged_dot(rows, p["kernel_q"].astype(dtype), group_sizes)
        return y * p["scale"][rows_e].astype(dtype)
    return jax.lax.ragged_dot(rows, p["kernel"], group_sizes)


def expert_product_form(cfg: ModelConfig, params: Params, tokens: int) -> str:
    """``"stream"``, ``"tiles"`` or ``"compiler"``: the form the serving
    expert layers' products take in a program of ``tokens`` tokens (a decode
    step's lanes, an admission call's stream) — ``_expert_rows``'s choice,
    from the same predicate on the same shapes."""
    p = next(layer for layer in params["layers"] if "router" in layer)["up_e"]
    if not (cfg.act_quant and "kernel_q" in p):
        return "compiler"
    rows = tokens * cfg.num_experts_per_tok
    if cfg.expert_share:
        rows = min(rows, _EXPERT_WINDOW_ROWS)
    return grouped.product_form(rows, *p["kernel_q"].shape, jnp.int8)


def _moe_mlp_routed(layer: Params, cfg: ModelConfig, x: jnp.ndarray,
                    valid: Optional[jnp.ndarray] = None,
                    ) -> tuple[jnp.ndarray, jnp.ndarray]:
    """The serving expert layer: only the chosen experts are computed.

    x [B, S, H] -> (y [B, S, H], counts float32[4]).  Token-expert
    assignments are sorted by expert; each projection is one grouped
    product over the sorted rows; the results go back to their tokens
    weighted by the router.  No token is dropped, and an expert nobody chose
    costs nothing.  ``valid`` [B, S] marks real tokens: the assignments of
    padding (a prefill bucket's tail, an idle decode lane) sort behind every
    expert's group and are not computed at all.  A shared MLP, where the
    layer has one, runs beside the routed experts through ``_linear``.

    ``counts`` = (assignments computed, experts with at least one row, the
    fullest expert's rows, experts in the layer): the engine sums them over
    layers and steps onto the call's ``engine.call`` span.
    """
    B, S, H = x.shape
    T, E, K = B * S, cfg.num_experts, cfg.num_experts_per_tok
    xt = x.reshape(T, H)
    topi, topv = _route(layer, cfg, xt)                         # [T, K]
    with jax.named_scope("routed"):
        flat_e = topi.reshape(T * K)
        if valid is not None:
            flat_e = jnp.where(jnp.repeat(valid.reshape(T), K), flat_e, E)
        # (act_quant without int8 kernels runs wide, as in _linear, which
        # warns about it on this layer's other projections.)
        aq = cfg.act_quant and "kernel_q" in layer["gate_e"]
        xq, xs = _quant_act(xt) if aq else (None, None)
        # One stable sort by expert carries what each row needs with it
        # (its place in token order, its router weight, its token's
        # activation scale): single-element gathers and scatters are what
        # the chip does worst, so none is left here — the counts per expert
        # are a one-hot sum, the way back is a second sort.
        iota = jnp.arange(T * K, dtype=jnp.int32)
        carried = [flat_e, iota, topv.reshape(T * K)]
        if aq:
            carried.append(jnp.repeat(xs[:, 0], K))
        rows_e, order, w_rows, *rs = jax.lax.sort(
            carried, num_keys=1, is_stable=True)
        live = (rows_e < E)[:, None]            # rows of real assignments
        rows_e = jnp.minimum(rows_e, E - 1)
        tok = order // K
        group_sizes = jnp.sum(
            flat_e[:, None] == jnp.arange(E, dtype=flat_e.dtype)[None, :],
            axis=0, dtype=jnp.int32)
        rows, rs = (xq[tok], rs[0][:, None]) if aq else (xt[tok], None)
        gate = _expert_rows(layer["gate_e"], rows, rs, rows_e, group_sizes,
                            x.dtype)
        up = _expert_rows(layer["up_e"], rows, rs, rows_e, group_sizes,
                          x.dtype)
        h = jax.nn.silu(gate) * up                              # [T*K, I]
        w_rows = w_rows[:, None]
        if aq:      # the router's weight rides on the dequantisation
            hq, hs = _quant_act(h)
            ys = _expert_rows(layer["down_e"], hq, hs * w_rows, rows_e,
                              group_sizes, x.dtype)
        else:
            ys = _expert_rows(layer["down_e"], h, None, rows_e, group_sizes,
                              x.dtype)
            ys = (ys.astype(jnp.float32) * w_rows).astype(x.dtype)
        # Rows behind the last group are whatever the product left there.
        ys = jnp.where(live, ys, jnp.zeros((), ys.dtype))
        # Back to token order: row inv[t * K + k] is token t's k-th expert.
        _, inv = jax.lax.sort([order, iota], num_keys=1)
        y = jnp.sum(ys[inv].reshape(T, K, H).astype(jnp.float32),
                    axis=1).astype(x.dtype)
        counts = jnp.stack([
            jnp.sum(group_sizes), jnp.sum(group_sizes > 0),
            jnp.max(group_sizes), jnp.asarray(E, jnp.int32),
        ]).astype(jnp.float32)
    y = y.reshape(B, S, H)
    if "shared" in layer:
        with jax.named_scope("shared"):
            y = y + _dense_mlp(layer["shared"], cfg, x)
    return y, counts


# Rows of expert-sorted assignments one pass of ``_moe_mlp_share`` computes:
# what bounds its buffers ([rows, expert width] in int32 and in the
# activation dtype) whatever the call holds.
_EXPERT_WINDOW_ROWS = 16_384


def _moe_mlp_share(layer: Params, cfg: ModelConfig, x: jnp.ndarray,
                   valid: Optional[jnp.ndarray] = None,
                   ) -> tuple[jnp.ndarray, jnp.ndarray]:
    """The serving expert layer of a chip that holds a share of the experts
    (``cfg.experts_held`` from ``cfg.expert_start``; all of them when 0).

    x [B, S, H] -> (y [B, S, H], counts float32[5]).  The router scores all
    ``cfg.num_experts`` and keeps its experts per token; the weights are
    normalised over all the chosen, held or not.  An assignment to an expert
    that is not held sorts behind every group, exactly as padding's does,
    and computes nothing: what the absent experts would add is left out.
    The held assignments are computed in windows of ``_EXPERT_WINDOW_ROWS``
    sorted rows — as many as there are held rows, so the work and the
    buffers follow what is held, not tokens x experts per token, and no
    assignment to a held expert is dropped.  With ``latent_down`` /
    ``latent_up`` the experts read and write a latent between the two
    projections; without ``gate_e`` an expert is ``down(act(up(x)))``.  The
    projections and the shared MLP are what every chip computes alike.

    ``counts`` = (held assignments computed, held experts with at least one
    row, the fullest held expert's rows, experts held, assignments of the
    real tokens held or not): the engine sums them over layers and steps
    onto the call's ``engine.call`` span.
    """
    B, S, H = x.shape
    T, K = B * S, cfg.num_experts_per_tok
    E, e0 = cfg.experts_held_, cfg.expert_start
    xt = x.reshape(T, H)
    topi, topv = _route(layer, cfg, xt)                         # [T, K]
    if "latent_down" in layer:
        with jax.named_scope("latent_down"):
            xl = _linear(layer["latent_down"], xt, cfg.act_quant)
    else:
        xl = xt
    Lw = xl.shape[-1]
    with jax.named_scope("routed"):
        real = (jnp.ones((T,), bool) if valid is None
                else valid.reshape(T))
        local = topi - e0
        held = (local >= 0) & (local < E) & real[:, None]
        flat_e = jnp.where(held, local, E).reshape(T * K)
        aq = cfg.act_quant and "kernel_q" in layer["up_e"]
        xq, xs = _quant_act(xl) if aq else (None, None)
        # One stable sort by expert carries what a row needs with it, as in
        # ``_moe_mlp_routed``.
        iota = jnp.arange(T * K, dtype=jnp.int32)
        carried = [flat_e, iota, topv.reshape(T * K)]
        if aq:
            carried.append(jnp.repeat(xs[:, 0], K))
        rows_e, order, w_rows, *rs = jax.lax.sort(
            carried, num_keys=1, is_stable=True)
        group_sizes = jnp.sum(
            flat_e[:, None] == jnp.arange(E, dtype=flat_e.dtype)[None, :],
            axis=0, dtype=jnp.int32)
        ends = jnp.cumsum(group_sizes)
        starts, n_held = ends - group_sizes, ends[-1]
        RW = min(T * K, _EXPERT_WINDOW_ROWS)
        windows = -(-T * K // RW)
        fit = lambda a: jnp.pad(a, (0, windows * RW - T * K))  # noqa: E731
        rows_e, tok, w_rows = (fit(jnp.minimum(rows_e, E - 1)),
                               fit(order // K), fit(w_rows))
        rs = [fit(r) for r in rs]

        def window(w, ys):
            at = w * RW
            cut = lambda a: jax.lax.dynamic_slice_in_dim(a, at, RW)  # noqa: E731
            e_w, tok_w, wr_w = cut(rows_e), cut(tok), cut(w_rows)[:, None]
            sizes = (jnp.clip(ends - at, 0, RW)
                     - jnp.clip(starts - at, 0, RW))
            rows, rs_w = ((xq[tok_w], cut(rs[0])[:, None]) if aq
                          else (xl[tok_w], None))
            h = _expert_rows(layer["up_e"], rows, rs_w, e_w, sizes, x.dtype)
            if "gate_e" in layer:
                h = _mlp_act(cfg, _expert_rows(
                    layer["gate_e"], rows, rs_w, e_w, sizes, x.dtype)) * h
            else:
                h = _mlp_act(cfg, h)
            if aq:      # the router's weight rides on the dequantisation
                hq, hs = _quant_act(h)
                ys_w = _expert_rows(layer["down_e"], hq, hs * wr_w, e_w,
                                    sizes, x.dtype)
            else:
                ys_w = _expert_rows(layer["down_e"], h, None, e_w, sizes,
                                    x.dtype)
                ys_w = (ys_w.astype(jnp.float32) * wr_w).astype(x.dtype)
            # Rows behind the last group are whatever the product left.
            live = (at + jnp.arange(RW) < n_held)[:, None]
            ys_w = jnp.where(live, ys_w, jnp.zeros((), ys_w.dtype))
            return jax.lax.dynamic_update_slice_in_dim(ys, ys_w, at, 0)

        ys = jnp.zeros((windows * RW, Lw), x.dtype)
        if windows == 1:
            ys = window(0, ys)
        else:
            ys = jax.lax.fori_loop(0, -(-n_held // RW), window, ys)
        # Back to token order: row inv[t * K + k] is token t's k-th expert.
        _, inv = jax.lax.sort([order, iota], num_keys=1)
        y = jnp.sum(ys[inv].reshape(T, K, Lw).astype(jnp.float32),
                    axis=1).astype(x.dtype)
        counts = jnp.stack([
            n_held, jnp.sum(group_sizes > 0), jnp.max(group_sizes),
            jnp.asarray(E, jnp.int32), K * jnp.sum(real, dtype=jnp.int32),
        ]).astype(jnp.float32)
    if "latent_up" in layer:
        with jax.named_scope("latent_up"):
            y = _linear(layer["latent_up"], y, cfg.act_quant)
    y = y.reshape(B, S, H)
    if "shared" in layer:
        with jax.named_scope("shared"):
            y = y + _dense_mlp(layer["shared"], cfg, x)
    return y, counts


# ---------------------------------------------------------------------------
# Mamba-2 mixer
# ---------------------------------------------------------------------------


def _mamba_in(layer: Params, cfg: ModelConfig, h: jnp.ndarray):
    """``[z | xBC | dt] = in_proj(h)`` with ``dt = softplus(dt + dt_bias)``
    (float32; the published ``time_step_limit`` is (0, inf): no clamp)."""
    d_in, C = cfg.mamba_inner, cfg.mamba_conv_dim
    zxbcdt = _linear(layer["in_proj"], h, cfg.act_quant)
    dt = jax.nn.softplus(zxbcdt[..., d_in + C:].astype(jnp.float32)
                         + layer["dt_bias"])
    return zxbcdt[..., :d_in], zxbcdt[..., d_in:d_in + C], dt


def _mamba_heads(cfg: ModelConfig, xbc: jnp.ndarray):
    """The convolved ``xBC`` as ``x [..., H, P]``, ``B`` and ``C``
    ``[..., G, N]``."""
    d_in, G, N = cfg.mamba_inner, cfg.mamba_n_groups, cfg.ssm_state_size
    lead = xbc.shape[:-1]
    return (xbc[..., :d_in].reshape(*lead, cfg.mamba_num_heads,
                                    cfg.mamba_head_dim),
            xbc[..., d_in:d_in + G * N].reshape(*lead, G, N),
            xbc[..., d_in + G * N:].reshape(*lead, G, N))


def _mamba_out(layer: Params, cfg: ModelConfig, y: jnp.ndarray,
               x: jnp.ndarray, z: jnp.ndarray) -> jnp.ndarray:
    """``out_proj(group_rms_norm((y + D x) * silu(z)) * w)``: y, x
    [..., H, P], z [..., d_in]; the arithmetic is float32."""
    G, d_in = cfg.mamba_n_groups, cfg.mamba_inner
    lead = z.shape[:-1]
    y = (y.astype(jnp.float32)
         + layer["D"][:, None] * x.astype(jnp.float32)).reshape(*lead, d_in)
    g = (y * jax.nn.silu(z.astype(jnp.float32))).reshape(*lead, G, d_in // G)
    g = g * jax.lax.rsqrt(jnp.mean(g * g, axis=-1, keepdims=True)
                          + cfg.rms_norm_eps)
    g = g.reshape(*lead, d_in) * layer["ssm_norm"].astype(jnp.float32)
    return _linear(layer["out_proj"], g.astype(z.dtype), cfg.act_quant)


def _mamba2_prefill(layer: Params, cfg: ModelConfig, h: jnp.ndarray,
                    positions: jnp.ndarray, valid: jnp.ndarray,
                    last: jnp.ndarray):
    """A Mamba-2 mixer over whole sequences from a zero state: rows
    ``[B, S]``, or one packed stream ``[1, T]`` whose segments start where
    ``positions`` is 0 — neither the convolution nor the state crosses a
    start, and padding (``valid`` False) leaves the state as the last real
    token left it.  ``last`` [R]: flat indices of each sequence's last real
    token.  Returns (out [B, S, hidden], the state after each ``last`` in
    the pool's layout [R, H / pack, N, pack * P] float32, the un-convolved
    ``xBC`` rows ending there [R, conv_kernel - 1, channels])."""
    Bt, S = positions.shape
    Kc = cfg.conv_kernel
    with jax.named_scope("mixer"), jax.named_scope("mamba2"):
        z, xbc, dt = _mamba_in(layer, cfg, h)
        # Causal depthwise convolution: a source before the sequence's start
        # is zero.
        src = jnp.pad(xbc, ((0, 0), (Kc - 1, 0), (0, 0)))
        w = layer["conv"]["kernel"]
        conv = layer["conv"]["bias"]
        for k in range(Kc):
            reach = (positions >= Kc - 1 - k)[..., None]
            conv = conv + w[k] * jnp.where(
                reach, src[:, k:k + S].astype(jnp.float32), 0.0)
        # The scan's products take the activation dtype (float32 sums).
        x, Bm, Cm = _mamba_heads(cfg, jax.nn.silu(conv).astype(h.dtype))
        y, states = ssm_chunk_scan(
            x, jnp.where(valid[..., None], dt, 0.0), -jnp.exp(layer["A_log"]),
            Bm, Cm, positions == 0, last, chunk=cfg.ssm_chunk_size)
        out = _mamba_out(layer, cfg, y, x, z)
        back = jnp.arange(Kc - 1, dtype=jnp.int32) - (Kc - 2)   # -2, -1, 0
        rows = xbc.reshape(Bt * S, -1)[
            jnp.maximum(last[:, None] + back[None, :], 0)]
        seen = positions.reshape(-1)[last][:, None] + back[None, :] >= 0
        tail = jnp.where(seen[..., None], rows, jnp.zeros((), rows.dtype))
        # The tail is an output of the program alone: left to itself the
        # compiler gathers it last and keeps every layer's whole ``xBC``
        # alive until then (160 MB a layer at 8,192 tokens).
        out, tail = jax.lax.optimization_barrier((out, tail))
    pack = state_pack(cfg.mamba_num_heads, cfg.mamba_n_groups,
                      cfg.mamba_head_dim)
    return out, pack_state(states, pack), tail


def _mamba2_decode(layer: Params, cfg: ModelConfig, h: jnp.ndarray,
                   active: jnp.ndarray, ssm: jnp.ndarray, conv: jnp.ndarray,
                   lanes: Optional[jnp.ndarray], update):
    """One token a row through a Mamba-2 mixer, against the state pool.
    h [B, 1, hidden]; ``active`` [B] (an idle row leaves its lane's state
    and tail as they were); ``lanes`` [B] the pool lane of each row, None =
    row b is lane b of a pool of B lanes.  Returns (out [B, 1, hidden], the
    layer's ``ssm`` and ``conv`` pools, updated)."""
    B = h.shape[0]
    with jax.named_scope("mixer"), jax.named_scope("mamba2"):
        z, xbc, dt = _mamba_in(layer, cfg, h[:, 0])
        tail = conv if lanes is None else conv[lanes]
        seen = jnp.concatenate([tail, xbc[:, None].astype(tail.dtype)], axis=1)
        convd = layer["conv"]["bias"] + jnp.sum(
            layer["conv"]["kernel"] * seen.astype(jnp.float32), axis=1)
        x, Bm, Cm = _mamba_heads(cfg, jax.nn.silu(convd))
        decay = jnp.where(active[:, None],
                          jnp.exp(-dt * jnp.exp(layer["A_log"])), 1.0)
        dtx = jnp.where(active[:, None, None], dt[..., None] * x, 0.0)
        y, ssm = update(
            ssm, jnp.arange(B, dtype=jnp.int32) if lanes is None else lanes,
            decay, dtx, Bm, Cm)
        out = _mamba_out(layer, cfg, y, x, z)[:, None]
        tail = jnp.where(active[:, None, None], seen[:, 1:], tail)
        conv = tail if lanes is None else conv.at[lanes].set(tail, mode="drop")
    return out, ssm, conv


def _mlp_act(cfg: ModelConfig, x: jnp.ndarray) -> jnp.ndarray:
    if cfg.mlp_activation == "gelu_tanh":      # Gemma GeGLU
        return jax.nn.gelu(x, approximate=True)
    if cfg.mlp_activation == "relu2":
        return jnp.square(jax.nn.relu(x))
    return jax.nn.silu(x)


def _dense_mlp(p: Params, cfg: ModelConfig, x: jnp.ndarray) -> jnp.ndarray:
    """Gated MLP over ``p``'s gate/up/down: a dense layer's own, or the
    shared experts of an expert layer.  Without a gate (``mlp_gated``
    False) it is ``down(act(up(x)))``."""
    aq = cfg.act_quant
    if "gate" not in p:
        return _linear(p["down"], _mlp_act(cfg, _linear(p["up"], x, aq)), aq)
    gate = _linear(p["gate"], x, aq)
    up = _linear(p["up"], x, aq)
    return _linear(p["down"], _mlp_act(cfg, gate) * up, aq)


def _mlp(layer: Params, cfg: ModelConfig, x: jnp.ndarray,
         valid: Optional[jnp.ndarray] = None,
         moe_stats: Optional[list] = None) -> jnp.ndarray:
    """The layer's MLP at inference.  Which kind a layer has is the
    description's (``cfg.layer_spec(i).mlp``), and its parameters were built
    from it: a layer with a router routes."""
    if "router" in layer:
        y, counts = (_moe_mlp_share if cfg.expert_share
                     else _moe_mlp_routed)(layer, cfg, x, valid)
        if moe_stats is not None:
            moe_stats.append(counts)
        return y
    return _dense_mlp(layer, cfg, x)


def _residual_tail(layer: Params, cfg: ModelConfig, x: jnp.ndarray,
                   o: jnp.ndarray, collect_aux: bool = False,
                   valid: Optional[jnp.ndarray] = None,
                   moe_stats: Optional[list] = None,
                   ) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Everything after the attention output projection: the (optionally
    sandwich-normed) attention residual, the pre-MLP norm, the MLP (or MoE
    path), and the MLP residual.  The ONE definition shared by
    layer_block, _prefill_impl, and decode_step so the serving loops can
    never drift from the dense reference.  Returns (x, aux).  ``valid``
    ([B, S], real tokens) and ``moe_stats`` (a list each expert layer
    appends its counts to) are the serving expert layer's
    (``_moe_mlp_routed``)."""
    uo = cfg.rmsnorm_unit_offset
    if cfg.sandwich_norms:
        o = rms_norm(o, layer["post_attn_norm"], cfg.rms_norm_eps, uo)
    x = x + o
    h = rms_norm(x, layer["post_norm"], cfg.rms_norm_eps, uo)
    with jax.named_scope("mlp"):
        if "router" in layer and collect_aux:
            if cfg.moe_scoring != "softmax" or "shared" in layer:
                raise NotImplementedError(
                    "the training dispatch (_moe_mlp) is softmax top-k "
                    "without shared experts; this description is served, "
                    "not trained")
            y, aux = _moe_mlp(layer, cfg, h)
        else:
            y, aux = (_mlp(layer, cfg, h, valid, moe_stats),
                      jnp.zeros((), jnp.float32))
    if cfg.sandwich_norms:
        y = rms_norm(y, layer["post_mlp_norm"], cfg.rms_norm_eps, uo)
    return x + y, aux


def _attn_extras(cfg: ModelConfig, layer_idx: int) -> dict:
    """Per-layer attention kwargs for Gemma-style models; {} for the Llama
    conventions (so stub/custom attention impls never see surprises)."""
    if not cfg.has_attn_extras:
        return {}
    return {"scale": cfg.attn_scale,
            "logit_softcap": cfg.attn_logit_softcap,
            "window": cfg.layer_window(layer_idx)}


def _attn_out(layer: Params, cfg: ModelConfig,
              attn: jnp.ndarray) -> jnp.ndarray:
    """The attention output projection.  attn: [B, S, nH*D] -> [B, S, H]."""
    with jax.named_scope("attn_out"):
        return _linear(layer["o"], attn, cfg.act_quant)


@jax.named_scope("lm_head")
def _unembed(params: Params, cfg: ModelConfig, x: jnp.ndarray) -> jnp.ndarray:
    x = rms_norm(x, params["final_norm"], cfg.rms_norm_eps,
                 cfg.rmsnorm_unit_offset)
    if cfg.tie_embeddings:
        emb = params["embed"]
        if "weight_q" in emb:
            logits = ((x @ emb["weight_q"].T.astype(x.dtype))
                      * emb["scale"].astype(x.dtype))
        else:
            logits = x @ emb["weight"].T
    else:
        # The vocab projection stays weight-only even under act_quant:
        # int8 noise on the pre-logits hidden state flips near-tied argmax
        # (and the tied-embeddings path is weight-only too) — standard
        # W8A8 practice excludes the head.
        logits = _linear(params["lm_head"], x)
    logits = logits.astype(jnp.float32)
    if cfg.final_logit_softcap:
        logits = cfg.final_logit_softcap * jnp.tanh(
            logits / cfg.final_logit_softcap)
    return logits


# ---------------------------------------------------------------------------
# Dense forward (training / parity)
# ---------------------------------------------------------------------------


def layer_block(
    layer: Params,
    cfg: ModelConfig,
    x: jnp.ndarray,
    cos: jnp.ndarray,
    sin: jnp.ndarray,
    positions: jnp.ndarray,
    attn_fn=None,
    collect_aux: bool = False,
    layer_idx: int = 0,
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """One transformer layer (norm/QKV/attention/residual/MLP) — the single
    definition shared by forward_full and the pipeline stage scan
    (parallel/pipeline.py), so the layer semantics cannot drift between
    the dense and pipelined paths.

    ``collect_aux`` selects the TRAINING MoE path (capacity dispatch +
    load-balance aux); otherwise MoE configs run the dropless inference
    path.  ``layer_idx`` feeds the per-layer sliding-window pattern
    (Gemma-2 alternates local/global).  Returns (x, aux scalar — 0.0
    unless collecting).
    """
    if attn_fn is None:
        attn_fn = causal_attention
    B, S = x.shape[:2]
    h = rms_norm(x, layer["input_norm"], cfg.rms_norm_eps,
                 cfg.rmsnorm_unit_offset)
    spec = cfg.layer_spec(layer_idx)
    if spec.mixer in ("mamba2", "none"):
        # One sub-block under one norm and one residual.
        if spec.mixer == "none":
            with jax.named_scope("mlp"):
                return x + _mlp(layer, cfg, h), jnp.zeros((), jnp.float32)
        last = jnp.arange(B, dtype=jnp.int32) * S + S - 1
        out, _, _ = _mamba2_prefill(layer, cfg, h, positions,
                                    jnp.ones((B, S), bool), last)
        return x + out, jnp.zeros((), jnp.float32)
    if cfg.latent:
        g = cfg.latent_geometry(layer_idx)
        q_nope, q_rope, c, k_rope, cq = _latent_qkv(layer, cfg, g, h, cos,
                                                    sin)
        index = (_index_qk(layer, cfg, g, h, cq, cos, sin) if g.indexed
                 else None)
        with jax.named_scope("attention"), jax.named_scope("latent"):
            attn = _latent_attend_expanded(
                layer, cfg, g, q_nope, q_rope, c, k_rope, positions, None,
                attn_fn=attn_fn, index=index)
        attn = _head_gate(layer, cfg, g, h, attn)
    else:
        q, k, v = _qkv(layer, cfg, h, cos, sin)
        with jax.named_scope("attention"):
            attn = attn_fn(q, k, v, q_positions=positions,
                           **_attn_extras(cfg, layer_idx))
    o = _attn_out(layer, cfg, attn.reshape(B, S, -1))
    if spec.mlp == "none":
        return x + o, jnp.zeros((), jnp.float32)
    return _residual_tail(layer, cfg, x, o, collect_aux)


def forward_full(
    params: Params,
    cfg: ModelConfig,
    tokens: jnp.ndarray,
    *,
    positions: Optional[jnp.ndarray] = None,
    attn_fn=None,
    return_aux: bool = False,
) -> jnp.ndarray:
    """Dense causal forward.  tokens [B, S] -> logits [B, S, V] (float32).

    ``attn_fn`` swaps the attention implementation (default dense
    ``causal_attention``; pass ``parallel.ring_attention.make_ring_attention``
    output for sequence-parallel long-context training).

    ``return_aux`` additionally returns the mean MoE load-balancing loss
    over layers (0.0 for dense models) — the training path folds it into
    the objective.  It also selects the MoE TRAINING dispatch (GShard
    capacity, tokens can drop); without it MoE runs dropless (inference
    semantics, HF parity).
    """
    B, S = tokens.shape
    x = _embed_lookup(params, cfg, tokens)
    if positions is None:
        positions = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32), (B, S))
    ropes = _rope_tables(cfg, positions)
    aux_total = jnp.zeros((), jnp.float32)
    for li, layer in enumerate(params["layers"]):
        cos, sin = _rope_of(cfg, ropes, li)
        x, aux = layer_block(layer, cfg, x, cos, sin, positions,
                             attn_fn=attn_fn, collect_aux=return_aux,
                             layer_idx=li)
        aux_total = aux_total + aux
    logits = _unembed(params, cfg, x)
    if return_aux:
        return logits, aux_total / max(len(params["layers"]), 1)
    return logits


# ---------------------------------------------------------------------------
# Paged-cache scatter
# ---------------------------------------------------------------------------


def _scatter_pages(
    pages: jnp.ndarray,
    vals: jnp.ndarray,
    block_table: jnp.ndarray,
    positions: jnp.ndarray,
    valid: jnp.ndarray,
) -> jnp.ndarray:
    """Write vals[b, s] to pages[block_table[b, pos//bs], pos%bs].

    Invalid lanes are redirected to the null block 0.

    pages: [num_blocks, bs, KVH*D] (fused lane layout); vals: [B, S, KVH, D];
    block_table: [B, max_blocks]; positions/valid: [B, S].
    """
    bs = pages.shape[1]
    B, S = positions.shape
    raw_blk = positions // bs                        # [B, S] index into table
    blk_idx = jnp.clip(raw_blk, 0, block_table.shape[1] - 1)
    block_ids = jnp.take_along_axis(block_table, blk_idx, axis=1)  # [B, S]
    # Positions past the table redirect to the null block rather than
    # clipping into the lane's LAST real block: a speculative verify at the
    # capacity boundary writes rejected-draft K/V beyond the per-seq cap,
    # and a clip would overwrite live cache there (silent wrong logits).
    block_ids = jnp.where(valid & (raw_blk < block_table.shape[1]),
                          block_ids, 0)
    offs = positions % bs
    flat_blocks = block_ids.reshape(-1)
    flat_offs = offs.reshape(-1)
    flat_vals = vals.reshape(B * S, -1)              # fuse [KVH, D] -> lanes
    # Explicit cast: fp8 KV pages (ModelConfig.kv_dtype) have no implicit
    # promotion path from the bf16 projections.
    return pages.at[flat_blocks, flat_offs].set(
        flat_vals.astype(pages.dtype))


def _qmax_for(dtype) -> float:
    return 127.0 if jnp.dtype(dtype) == jnp.int8 else 448.0


def _scatter_pages_quant(
    pages: jnp.ndarray,
    spages: jnp.ndarray,
    vals: jnp.ndarray,
    block_table: jnp.ndarray,
    positions: jnp.ndarray,
    valid: jnp.ndarray,
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Quantize-on-append twin of ``_scatter_pages`` for the quantized KV
    tier: per-(token, head) symmetric quantization of ``vals`` [B, S, KVH, D]
    into the storage-dtype pages plus a parallel scatter of the float32
    scales into ``spages`` [num_blocks, bs, KVH].  Values are rounded before
    the int8 cast (``.astype`` alone truncates toward zero)."""
    qmax = _qmax_for(pages.dtype)
    xf = vals.astype(jnp.float32)
    amax = jnp.max(jnp.abs(xf), axis=-1)
    scale = jnp.maximum(amax / qmax, 1e-8)
    xq = xf / scale[..., None]
    if jnp.dtype(pages.dtype) == jnp.int8:
        xq = jnp.clip(jnp.round(xq), -qmax, qmax)
    return (_scatter_pages(pages, xq, block_table, positions, valid),
            _scatter_pages(spages, scale, block_table, positions, valid))


# ---------------------------------------------------------------------------
# Prefill
# ---------------------------------------------------------------------------


class RowView(NamedTuple):
    """A packed token stream's segments seen as rows, and the way back.

    A packed call lays its prompts end to end in one stream ``[1, T, ...]``;
    everything computed per token runs on the stream.  Attention is per
    prompt, so it sees ``[R, S, ...]`` rows cut out of the stream: row r is
    the S stream tokens from where segment r starts.  A row's tail past its
    length holds whatever follows it in the stream and is masked by the
    row's length, as a bucket's padding is.
    """

    take: jnp.ndarray      # [R, S] stream index of row r's s-th token
    back: jnp.ndarray      # [T] each stream token's place in the R*S rows
    seg: jnp.ndarray       # [T] the segment (row) each stream token is of
    offset: jnp.ndarray    # [R] where each segment starts in the stream


def _rows(view: Optional[RowView], x: jnp.ndarray) -> jnp.ndarray:
    """A stream ``[1, T, ...]`` as the view's rows ``[R, S, ...]``; without
    a view ``x`` is rows already."""
    return x if view is None else x[0][view.take]


def _stream(view: Optional[RowView], y: jnp.ndarray) -> jnp.ndarray:
    """Rows ``[R, S, ...]`` back in the stream's order ``[1, T, ...]``."""
    if view is None:
        return y
    return y.reshape(-1, *y.shape[2:])[view.back][None]


def _prefill_impl(
    params: Params,
    cfg: ModelConfig,
    tokens: jnp.ndarray,
    positions: jnp.ndarray,
    valid: jnp.ndarray,
    lengths: jnp.ndarray,
    kv_len: jnp.ndarray,
    pages: KVPages,
    block_tables: jnp.ndarray,
    attend_to_pages: bool,
    return_all_logits: bool = False,
    paged_attn_fn=None,
    moe_stats: Optional[list] = None,
    hidden: Optional[list] = None,
    view: Optional[RowView] = None,
    lanes: Optional[jnp.ndarray] = None,
    sel_stats: Optional[list] = None,
    selection: Optional[list] = None,
) -> tuple[jnp.ndarray, KVPages]:
    """Shared prefill layer loop.

    ``attend_to_pages`` selects the attention K/V source: False = the chunk's
    own in-flight k/v (first chunk, positions start at 0); True = gather the
    paged cache after scattering (continuation chunks attending to a cached
    prefix).  Everything else — embed, qkv+rope, scatter, residual/MLP,
    last-valid-token unembed — is identical and lives here exactly once.

    ``return_all_logits`` switches the unembed from the last valid token
    ([B, V]) to every position ([B, S, V]) — the speculative-decode verify
    pass needs per-position logits to score its draft tokens.

    A latent mixer (``cfg.latent``) caches one row a token and attends in
    one of two forms, neither with an ``[S, T]`` score tensor: the expanded
    form over a fresh batch's own tokens (``paged_attn_fn``: its Pallas
    kernel, or None for blockwise XLA operations), the absorbed form over
    gathered pages for continuation chunks and prefix hits (blockwise).
    ``moe_stats``: see ``_residual_tail``.  ``hidden``: a list that receives
    the residual stream before each layer and after the last ([B, S, H]
    each) — what a layer-by-layer comparison with a reference feeds on
    (``InferenceEngine.score_logits``).

    ``view`` (``prefill_packed``): ``tokens`` / ``positions`` / ``valid``
    are one packed stream ``[1, T]`` (a token's position is its index in its
    own segment) while ``lengths`` / ``kv_len`` / ``block_tables`` stay per
    row.  Embedding, norms, projections, rope, the page scatter and the MLP
    are per token and run on the stream; attention alone goes through the
    view's rows and comes back.

    ``lanes`` [rows]: the state-pool lane each row's recurrent state is
    written to (a description with recurrent layers; a lane past the pool
    drops the write, as an idle row's must).  The state starts from zero:
    continuing one (``attend_to_pages``) is not built.  A description with
    window layers names its lanes the same way: the last ``window`` rows of
    each prompt go to its lane's ring of the window store
    (``KVPages.win``).  ``sel_stats``: a list each window or indexed layer
    appends its counts to (``_sel_counts``); ``selection``: a list each
    indexed layer appends its (scores, keep) to (``_latent_attend_expanded``;
    rows, not a packed stream).
    """
    B, S = tokens.shape
    if cfg.lane_state and (attend_to_pages or lanes is None):
        raise ValueError(
            "recurrent layers and window layers are prefilled whole, from "
            "nothing, into the lanes the call names: chunked prefill, a "
            "cached prefix and the verify pass are not built for them")
    if cfg.recurrent:
        # Where each row's last real token lies in the flattened tokens.
        last = jnp.maximum(lengths - 1, 0) + (
            jnp.arange(B, dtype=jnp.int32) * S if view is None
            else view.offset)
        last = jnp.minimum(last, B * S - 1)
    kv_of = {li: n for n, li in enumerate(cfg.layers_with(
        "latent" if cfg.latent else "kv"))}
    if cfg.latent and paged_attn_fn is not None \
            and not is_latent_prefill_impl(paged_attn_fn):
        raise ValueError(
            "a latent mixer's prefill takes its own kernel or none "
            f"(ops/attention.py:select_prefill_impl); got {paged_attn_fn!r}")
    ropes = _rope_tables(cfg, positions)
    if view is None:
        row_pos, sc_tables, sc_pos = positions, block_tables, positions
    else:
        # The scatter is per token: segment r's table is columns
        # [r * W, (r + 1) * W) of one flat table, and a token's place in it
        # is its own position past its segment's columns.
        R, W = block_tables.shape
        row_pos = jnp.broadcast_to(
            jnp.arange(view.take.shape[1], dtype=jnp.int32), view.take.shape)
        sc_tables = block_tables.reshape(1, R * W)
        sc_pos = view.seg[None] * (W * pages.block_size) + positions
    if pages.win:
        # A window layer keeps a prompt's last ``window`` rows: the row of
        # position p at ring row p % window of the row's lane.
        Wn, bs = cfg.sliding_window, pages.block_size
        ring = cfg.window_rows(bs) // bs
        w_tables = window_tables(lanes, pages.win[0].shape[0], ring)
        row_len = lengths[:, None] if view is None else lengths[view.seg][None]
        w_valid = valid & (positions >= row_len - Wn)
        w_pos = positions % Wn
        if view is not None:
            w_tables = w_tables.reshape(1, -1)
            w_pos = view.seg[None] * (ring * bs) + w_pos

    x = _embed_lookup(params, cfg, tokens)
    uo = cfg.rmsnorm_unit_offset
    quant = pages.quantized
    new_k, new_v = [], []
    new_ks, new_vs = [], []
    new_ssm, new_conv = [], []
    new_idx, new_win = [], []
    for li, layer in enumerate(params["layers"]):
        if hidden is not None:
            hidden.append(x)
        h = rms_norm(x, layer["input_norm"], cfg.rms_norm_eps, uo)
        spec = cfg.layer_spec(li)
        cos, sin = _rope_of(cfg, ropes, li)
        if spec.mixer == "mamba2":
            out, state, tail = _mamba2_prefill(layer, cfg, h, positions,
                                               valid, last)
            n = len(new_ssm)
            new_ssm.append(pages.ssm[n].at[lanes].set(state, mode="drop"))
            new_conv.append(pages.conv[n].at[lanes].set(
                tail.astype(pages.conv[n].dtype), mode="drop"))
            x = x + out
            continue
        if spec.mixer == "none":
            with jax.named_scope("mlp"):
                x = x + _mlp(layer, cfg, h, valid, moe_stats)
            continue
        if cfg.latent:
            g = cfg.latent_geometry(li)
            q_nope, q_rope, c, k_rope, cq = _latent_qkv(layer, cfg, g, h,
                                                        cos, sin)
            index = None
            if g.indexed:
                index = _index_qk(layer, cfg, g, h, cq, cos, sin)
                new_idx.append(_scatter_pages(
                    pages.idx[len(new_idx)], index[1][:, :, None, :],
                    sc_tables, sc_pos, valid))
            with jax.named_scope("attention"), jax.named_scope("latent"):
                if spec.cache == "window":
                    new_win.append(_scatter_pages(
                        pages.win[len(new_win)], _latent_rows(g, c, k_rope),
                        w_tables, w_pos, w_valid))
                else:
                    pk = _scatter_pages(
                        pages.k[kv_of[li]], _latent_rows(g, c, k_rope),
                        sc_tables, sc_pos, valid)
                    new_k.append(pk)
                if attend_to_pages:
                    rows = gather_pages(pk, block_tables)[:, :, None, :]
                    o_lat = blockwise_attention(
                        _latent_absorb(layer, g, q_nope, q_rope), rows,
                        rows[..., :g.kv_lora_rank], q_positions=positions,
                        kv_len=kv_len, scale=1.0)
                    attn = _latent_unabsorb(layer, g, o_lat)
                else:
                    attn = _latent_attend_expanded(
                        layer, cfg, g, q_nope, q_rope, c, k_rope, row_pos,
                        kv_len, kernel=paged_attn_fn, view=view, index=index,
                        sel_stats=sel_stats, selection=selection)
            attn = _head_gate(layer, cfg, g, h, attn)
            o = _attn_out(layer, cfg, attn.reshape(B, S, -1))
            x, _ = _residual_tail(layer, cfg, x, o, valid=valid,
                                  moe_stats=moe_stats)
            continue
        q, k, v = _qkv(layer, cfg, h, cos, sin)
        ki = kv_of[li]
        # KV append and attention together: the fused decode kernel does
        # both in one call, so the scope means the same on every path.
        with jax.named_scope("attention"):
            if quant:
                pk, psk = _scatter_pages_quant(
                    pages.k[ki], pages.k_scale[ki], k, sc_tables,
                    sc_pos, valid)
                pv, psv = _scatter_pages_quant(
                    pages.v[ki], pages.v_scale[ki], v, sc_tables,
                    sc_pos, valid)
                new_ks.append(psk)
                new_vs.append(psv)
            else:
                pk = _scatter_pages(pages.k[ki], k, sc_tables, sc_pos,
                                    valid)
                pv = _scatter_pages(pages.v[ki], v, sc_tables, sc_pos,
                                    valid)
            new_k.append(pk)
            new_v.append(pv)
            if (paged_attn_fn is not None
                    and is_flash_prefill_impl(paged_attn_fn)):
                # Flash paged prefill: the scatter above already wrote this
                # chunk's K/V into the pages, so fresh prefill (positions
                # start at 0) and continuation chunks are the same kernel
                # call — no gather_pages round-trip, no [S, T] score matrix.
                # Quantized pools hand the kernel their scale planes and
                # dequantize in-kernel; the pool never widens in HBM.
                scales = dict(k_scale=psk, v_scale=psv) if quant else {}
                if view is not None:
                    # A packed stream: the kernel's tiles straight from the
                    # stream, no row view of the queries.
                    attn = _packed_form(paged_attn_fn)(
                        q[0], pk, pv, block_tables, view.offset, lengths,
                        **scales)[None]
                else:
                    attn = paged_attn_fn(q, pk, pv, block_tables,
                                         positions[:, 0], lengths, **scales)
            elif attend_to_pages and paged_attn_fn is not None and not quant:
                # Page-streaming path (Pallas verify kernel): queries are
                # contiguous at positions[:, 0] + i, which both verify_step
                # and prefill_chunk guarantee.  (select_verify_impl returns
                # None for attn-extras models, so no kwargs needed here.
                # Quantized pools take the gather branch below instead — the
                # verify kernel has no scale inputs; the engine mirrors this
                # by dropping its verify impl under kv quant.)
                attn = paged_attn_fn(q, pk, pv, block_tables,
                                     positions[:, 0], lengths)
            else:
                if attend_to_pages and quant:
                    # Dequantize-on-read: gather pages AND scales, apply the
                    # per-(token, head) scale on the small gathered activation
                    # (never the resident pool).
                    ks = gather_pages(psk, block_tables)       # [B, T, KVH]
                    vs = gather_pages(psv, block_tables)
                    kk = (gather_pages(pk, block_tables).astype(jnp.float32)
                          .reshape(B, -1, cfg.num_kv_heads, cfg.head_dim_)
                          * ks[..., None]).astype(k.dtype)
                    vv = (gather_pages(pv, block_tables).astype(jnp.float32)
                          .reshape(B, -1, cfg.num_kv_heads, cfg.head_dim_)
                          * vs[..., None]).astype(v.dtype)
                elif attend_to_pages:
                    # Gathered view is [B, T, KVH*D]; unfuse for attention (the
                    # reshape touches the small gathered activation, never the
                    # resident page arrays).
                    kk = gather_pages(pk, block_tables).reshape(
                        B, -1, cfg.num_kv_heads, cfg.head_dim_)
                    vv = gather_pages(pv, block_tables).reshape(
                        B, -1, cfg.num_kv_heads, cfg.head_dim_)
                else:
                    kk, vv = k, v
                # Attention is per prompt: a packed stream's q, k and v go
                # through the rows (fresh calls alone pack, so kk, vv are
                # the stream's own) and the result comes back.
                attn = _stream(view, causal_attention(
                    _rows(view, q), _rows(view, kk), _rows(view, vv),
                    q_positions=row_pos, kv_len=kv_len,
                    **_attn_extras(cfg, li)))
        o = _attn_out(layer, cfg, attn.reshape(B, S, -1))
        if spec.mlp == "none":
            x = x + o
            continue
        x, _ = _residual_tail(layer, cfg, x, o, valid=valid,
                              moe_stats=moe_stats)

    if hidden is not None:
        hidden.append(x)
    out_pages = KVPages(k=new_k, v=new_v,
                        k_scale=new_ks if quant else (),
                        v_scale=new_vs if quant else (),
                        **(dict(ssm=new_ssm, conv=new_conv)
                           if cfg.recurrent else {}),
                        **({"idx": new_idx} if pages.idx else {}),
                        **({"win": new_win} if pages.win else {}))
    if return_all_logits:
        return _unembed(params, cfg, x), out_pages
    last_idx = jnp.maximum(lengths - 1, 0)
    if view is None:
        x_last = jnp.take_along_axis(x, last_idx[:, None, None], axis=1)
    else:       # each row's last token, where it lies in the stream
        x_last = x[0][jnp.take_along_axis(
            view.take, last_idx[:, None], axis=1)]
    logits = _unembed(params, cfg, x_last)[:, 0, :]      # x_last [B, 1, H]
    return logits, out_pages


def prefill(
    params: Params,
    cfg: ModelConfig,
    tokens: jnp.ndarray,
    lengths: jnp.ndarray,
    pages: KVPages,
    block_tables: jnp.ndarray,
    *,
    attn_impl=None,
    moe_stats: Optional[list] = None,
    hidden: Optional[list] = None,
    lanes: Optional[jnp.ndarray] = None,
    sel_stats: Optional[list] = None,
    selection: Optional[list] = None,
) -> tuple[jnp.ndarray, KVPages]:
    """Ingest padded prompts, writing K/V into the paged cache.

    Args:
      tokens: [B, S_pad] int32 (right-padded).
      lengths: [B] int32 true prompt lengths (0 = inactive lane).
      pages: paged KV cache.
      block_tables: [B, max_blocks] int32.
      attn_impl: optional flash paged-prefill kernel (ops/attention.py:
        select_prefill_impl); None = dense in-flight attention.  The
        scatter-before-attention order makes the two equivalent: the
        pages already hold exactly this call's K/V when attention runs.
      lanes: [B] int32, the state-pool lane of each row (recurrent layers;
        see ``_prefill_impl``).
      selection: a list each indexed layer appends (scores [B, S, S]
        float32, keep [B, S, S] bool) to, as this call computed them.

    Returns:
      (last_logits [B, V] float32, updated pages)
    """
    B, S = tokens.shape
    positions = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32), (B, S))
    valid = positions < lengths[:, None]
    return _prefill_impl(params, cfg, tokens, positions, valid, lengths,
                         lengths, pages, block_tables, attend_to_pages=False,
                         paged_attn_fn=attn_impl, moe_stats=moe_stats,
                         hidden=hidden, lanes=lanes, sel_stats=sel_stats,
                         selection=selection)


def prefill_packed(
    params: Params,
    cfg: ModelConfig,
    tokens: jnp.ndarray,
    offset: jnp.ndarray,
    lengths: jnp.ndarray,
    pages: KVPages,
    block_tables: jnp.ndarray,
    *,
    row_len: int,
    attn_impl=None,
    moe_stats: Optional[list] = None,
    lanes: Optional[jnp.ndarray] = None,
    sel_stats: Optional[list] = None,
) -> tuple[jnp.ndarray, KVPages]:
    """``prefill`` of prompts laid end to end in one token stream: what is
    computed per token is computed for the stream's ``T`` positions, not for
    rows x bucket, and a prompt's numbers do not depend on what else is in
    the call.

    Args:
      tokens: [T] int32 — segment r is ``tokens[offset[r]:offset[r] +
        lengths[r]]``; whatever lies past the last segment is padding.
      offset: [R] int32, ascending; an idle row's is the end of the real
        tokens.
      lengths: [R] int32 (0 = idle row), each at most ``row_len``.
      block_tables: [R, max_blocks] int32.
      row_len: the width S of attention's row view (``RowView``), static.
      attn_impl, lanes: as for ``prefill``.

    Returns:
      (last_logits [R, V] float32, updated pages)
    """
    (T,), R = tokens.shape, offset.shape[0]
    t = jnp.arange(T, dtype=jnp.int32)
    seg = jnp.clip(jnp.sum(t[:, None] >= offset[None, :], axis=1) - 1,
                   0, R - 1).astype(jnp.int32)
    positions = t - offset[seg]
    valid = positions < lengths[seg]
    view = RowView(
        take=jnp.minimum(
            offset[:, None] + jnp.arange(row_len, dtype=jnp.int32)[None, :],
            T - 1),
        back=seg * row_len + jnp.minimum(positions, row_len - 1),
        seg=seg, offset=offset)
    return _prefill_impl(params, cfg, tokens[None], positions[None],
                         valid[None], lengths, lengths, pages, block_tables,
                         attend_to_pages=False, paged_attn_fn=attn_impl,
                         moe_stats=moe_stats, view=view, lanes=lanes,
                         sel_stats=sel_stats)


def prefill_chunk(
    params: Params,
    cfg: ModelConfig,
    tokens: jnp.ndarray,
    start: jnp.ndarray,
    lengths: jnp.ndarray,
    pages: KVPages,
    block_tables: jnp.ndarray,
    *,
    attn_impl=None,
    moe_stats: Optional[list] = None,
    hidden: Optional[list] = None,
) -> tuple[jnp.ndarray, KVPages]:
    """Continuation prefill: ingest a chunk of a prompt whose first ``start``
    tokens are already in the paged cache.

    Used for (a) prompts longer than the largest prefill bucket and (b)
    re-admission after recompute-preemption, where the folded prompt can
    exceed any single bucket.  Unlike ``prefill``, attention here runs
    against the paged cache (prefix + chunk) rather than the in-flight
    buffer, masked causally by absolute position.

    Args:
      tokens: [B, S] chunk tokens (right-padded).
      start: [B] int32 — tokens already in the cache for each sequence.
      lengths: [B] int32 — valid tokens in this chunk (0 = inactive lane).
      pages / block_tables: paged cache state.
      attn_impl: optional flash paged-prefill kernel — skips the dense
        ``gather_pages`` prefix materialization entirely.

    Returns:
      (last-chunk-token logits [B, V] float32, updated pages)
    """
    B, S = tokens.shape
    offs = jnp.arange(S, dtype=jnp.int32)
    positions = start[:, None] + offs[None, :]
    valid = offs[None, :] < lengths[:, None]
    return _prefill_impl(params, cfg, tokens, positions, valid, lengths,
                         start + lengths, pages, block_tables,
                         attend_to_pages=True, paged_attn_fn=attn_impl,
                         moe_stats=moe_stats, hidden=hidden)


def verify_step(
    params: Params,
    cfg: ModelConfig,
    tokens: jnp.ndarray,
    start: jnp.ndarray,
    lengths: jnp.ndarray,
    pages: KVPages,
    block_tables: jnp.ndarray,
    *,
    attn_impl=None,
    moe_stats: Optional[list] = None,
) -> tuple[jnp.ndarray, KVPages]:
    """Speculative-decode verify pass: score ``S`` candidate tokens at once.

    Identical cache semantics to ``prefill_chunk`` (tokens land at absolute
    positions ``start..start+lengths-1``, attention runs against the paged
    prefix + the chunk itself) but returns the logits of **every** position,
    [B, S, V] — position ``i``'s logits are the model's distribution for the
    token *after* ``tokens[:, i]``.  The caller accepts the longest draft
    prefix whose tokens match these distributions and advances
    ``context_lens`` by the accepted count; K/V written for rejected
    positions stays beyond ``context_lens`` and is masked out of every
    later attention read, then overwritten when real tokens arrive — so
    rejection needs no cache rollback.

    In greedy acceptance (token must equal the argmax) any draft source is
    correctness-neutral: the accepted prefix is exactly what step-by-step
    greedy decode would have produced.

    ``attn_impl``: optional paged multi-query attention (the Pallas verify
    kernel, ops/attention.py:select_verify_impl); None = XLA gather.
    """
    B, S = tokens.shape
    offs = jnp.arange(S, dtype=jnp.int32)
    positions = start[:, None] + offs[None, :]
    valid = offs[None, :] < lengths[:, None]
    return _prefill_impl(params, cfg, tokens, positions, valid, lengths,
                         start + lengths, pages, block_tables,
                         attend_to_pages=True, return_all_logits=True,
                         paged_attn_fn=attn_impl, moe_stats=moe_stats)


# ---------------------------------------------------------------------------
# Decode
# ---------------------------------------------------------------------------


def decode_step(
    params: Params,
    cfg: ModelConfig,
    tokens: jnp.ndarray,
    context_lens: jnp.ndarray,
    pages: KVPages,
    block_tables: jnp.ndarray,
    *,
    attn_impl=paged_decode_attention,
    moe_stats: Optional[list] = None,
    hidden: Optional[list] = None,
    lanes: Optional[jnp.ndarray] = None,
    ssm_update=ssm_decode_update_xla,
    sel_stats: Optional[list] = None,
    index_scores=index_scores_decode,
    selection: Optional[list] = None,
) -> tuple[jnp.ndarray, KVPages]:
    """One decode step for a batch of slots.

    Args:
      tokens: [B] int32 — token to feed per slot.
      context_lens: [B] int32 — tokens already in cache (new token's position).
        0 means the slot is inactive (its writes go to the null block).
      pages / block_tables: paged cache state.
      attn_impl: paged attention implementation (XLA fallback or Pallas).
      lanes: [B] int32, the state-pool lane of each slot (recurrent layers)
        and its ring of the window store (window layers); None = slot b is
        lane b, the pool has B lanes.
      sel_stats: a list each window or indexed layer appends its counts to
        (``_sel_counts``; the keys selected are the sum of the keep mask
        the attention kernel is handed).
      ssm_update: the recurrent layers' one-step state update
        (ops/ssm.py:ssm_decode_update, or its XLA form).
      index_scores: the indexer's scores against a lane's index-key pages
        (ops/attention.py:select_index_scores_impl: the Pallas kernel, or
        its XLA form).
      selection: a list each indexed layer appends (scores [B, T] float32,
        keep [B, T] bool) to, as this step computed them
        (``InferenceEngine.score_logits``).

    Returns:
      (logits [B, V] float32, updated pages)
    """
    B = tokens.shape[0]
    positions = context_lens[:, None]  # [B, 1]
    active = (context_lens > 0)[:, None]
    ropes = _rope_tables(cfg, positions)
    if not cfg.use_rope:
        # The fused kernels rotate inside, by the tables they are handed:
        # ones and zeros make the rotation the identity.
        ropes = {kind: (jnp.ones_like(cos), jnp.zeros_like(sin))
                 for kind, (cos, sin) in ropes.items()}
    kv_of = {li: n for n, li in enumerate(cfg.layers_with(
        "latent" if cfg.latent else "kv"))}
    quant = pages.quantized
    if cfg.latent and not is_latent_decode_impl(attn_impl):
        raise ValueError(
            "a latent pool is read only by a latent decode impl "
            "(ops/attention.py:select_decode_impl picks one from the "
            f"description); got {attn_impl!r}")
    fused_q = quant and is_fused_quant_decode_impl(attn_impl)
    # A fused impl without scale support must not touch a quantized pool;
    # fall through to the gather/dequant path instead.
    fused = is_fused_decode_impl(attn_impl) and (fused_q or not quant)

    x = _embed_lookup(params, cfg, tokens)[:, None, :]  # [B, 1, H]
    uo = cfg.rmsnorm_unit_offset
    new_lens = context_lens + 1
    new_k, new_v = [], []
    new_ks, new_vs = [], []
    new_ssm, new_conv = [], []
    new_idx, new_win = [], []
    if pages.win:
        # A window layer's rows: the ring of the slot's lane, the row of
        # position p at ring row p % window — so the rows a query may see
        # are the ring's first min(context, window).
        Wn, bs = cfg.sliding_window, pages.block_size
        w_tables = window_tables(
            jnp.arange(B, dtype=jnp.int32) if lanes is None else lanes,
            pages.win[0].shape[0], cfg.window_rows(bs) // bs)
        w_lens = jnp.where(active[:, 0], jnp.minimum(new_lens, Wn), 0)
    for li, layer in enumerate(params["layers"]):
        if hidden is not None:      # see _prefill_impl
            hidden.append(x)
        h = rms_norm(x, layer["input_norm"], cfg.rms_norm_eps, uo)
        spec = cfg.layer_spec(li)
        cos, sin = _rope_of(cfg, ropes, li)
        if spec.mixer == "mamba2":
            n = len(new_ssm)
            out, ssm, conv = _mamba2_decode(
                layer, cfg, h, active[:, 0], pages.ssm[n], pages.conv[n],
                lanes, ssm_update)
            new_ssm.append(ssm)
            new_conv.append(conv)
            x = x + out
            continue
        if spec.mixer == "none":
            with jax.named_scope("mlp"):
                x = x + _mlp(layer, cfg, h, active, moe_stats)
            continue
        if cfg.latent:
            # Absorbed form: append this token's row, then every head reads
            # the lane's rows once (the kernel, or its XLA reference).
            g = cfg.latent_geometry(li)
            q_nope, q_rope, c, k_rope, cq = _latent_qkv(layer, cfg, g, h,
                                                        cos, sin)
            how = {}
            if g.indexed:
                # The selection: this token's index key joins the lane's,
                # the indexer scores every cached one, and attention is told
                # which ``index_topk`` of them to keep.
                qI, kI, wI = _index_qk(layer, cfg, g, h, cq, cos, sin)
                pidx = _scatter_pages(
                    pages.idx[len(new_idx)], kI[:, :, None, :], block_tables,
                    positions, active)
                new_idx.append(pidx)
                with jax.named_scope("attn/select"):
                    scores = index_scores(qI, wI, pidx, block_tables,
                                          new_lens)
                    seen = (jnp.arange(scores.shape[1], dtype=jnp.int32)[None]
                            < new_lens[:, None])
                    keep = sparse.topk_keep(scores, seen, g.index_topk)
                    how = dict(keep=keep,
                               name="sparse_latent_decode_attention")
                if selection is not None:
                    selection.append((scores, keep))
            with jax.named_scope("attention"), jax.named_scope("latent"):
                if spec.cache == "window":
                    pw = _scatter_pages(
                        pages.win[len(new_win)], _latent_rows(g, c, k_rope),
                        w_tables, positions % Wn, active)
                    new_win.append(pw)
                    o_lat = attn_impl(
                        _latent_absorb(layer, g, q_nope, q_rope), pw,
                        w_tables, w_lens, v_width=g.kv_lora_rank,
                        name="window_latent_decode_attention",
                        burst=w_tables.shape[1])
                else:
                    pk = _scatter_pages(
                        pages.k[kv_of[li]], _latent_rows(g, c, k_rope),
                        block_tables, positions, active)
                    new_k.append(pk)
                    o_lat = attn_impl(
                        _latent_absorb(layer, g, q_nope, q_rope), pk,
                        block_tables, new_lens, v_width=g.kv_lora_rank,
                        **how)
                attn = _latent_unabsorb(layer, g, o_lat)
            if sel_stats is not None and (g.window or g.indexed):
                sel_stats.append(_sel_counts(
                    g, jnp.where(active[:, 0], new_lens, 0),
                    jnp.sum(keep & active, axis=-1) if g.indexed else None))
            attn = _head_gate(layer, cfg, g, h, attn)
            o = _attn_out(layer, cfg, attn.reshape(B, 1, -1))
            x, _ = _residual_tail(layer, cfg, x, o, valid=active,
                                  moe_stats=moe_stats)
            continue
        ki = kv_of[li]
        # The fused kernels rope in-kernel and take the raw projections.
        if fused_q or fused:
            q, k, v = _qkv_proj(layer, cfg, h)
        else:
            q, k, v = _qkv(layer, cfg, h, cos, sin)
        with jax.named_scope("attention"):
            if fused_q:
                # Quantized fused fast-path: rope + quantize-on-append +
                # dequantize-in-kernel attention in one Pallas call; pages
                # AND scales are updated in place (aliased outputs).
                attn, pk, pv, psk, psv = attn_impl(
                    q, k, v, cos, sin, pages.k[ki], pages.v[ki],
                    pages.k_scale[ki], pages.v_scale[ki],
                    block_tables, context_lens)
            elif fused:
                # Fused fast-path: rope + KV append + attention in one
                # Pallas call; the kernel owns the scatter (in-place page
                # update) and the query/new-k rotary math.  Extras models
                # never select this path (ops/attention.py gates on
                # has_attn_extras).
                attn, pk, pv = attn_impl(q, k, v, cos, sin,
                                         pages.k[ki], pages.v[ki],
                                         block_tables, context_lens)
            elif quant:
                pk, psk = _scatter_pages_quant(
                    pages.k[ki], pages.k_scale[ki], k, block_tables,
                    positions, active)
                pv, psv = _scatter_pages_quant(
                    pages.v[ki], pages.v_scale[ki], v, block_tables,
                    positions, active)
                attn = paged_decode_attention_quant(
                    q, pk, pv, psk, psv, block_tables, new_lens,
                    **_attn_extras(cfg, li))
            else:
                pk = _scatter_pages(pages.k[ki], k, block_tables, positions,
                                    active)
                pv = _scatter_pages(pages.v[ki], v, block_tables, positions,
                                    active)
                # Extras models are guaranteed the gather impl
                # (select_attn_impl), which accepts the per-layer kwargs;
                # default models pass none so custom/Pallas impls keep
                # their fixed signature.
                attn = attn_impl(q, pk, pv, block_tables, new_lens,
                                 **_attn_extras(cfg, li))
        new_k.append(pk)
        new_v.append(pv)
        if quant:
            new_ks.append(psk)
            new_vs.append(psv)
        o = _attn_out(layer, cfg, attn.reshape(B, 1, -1))
        if spec.mlp == "none":
            x = x + o
            continue
        x, _ = _residual_tail(layer, cfg, x, o, valid=active,
                              moe_stats=moe_stats)

    if hidden is not None:
        hidden.append(x)
    logits = _unembed(params, cfg, x)[:, 0, :]
    return logits, KVPages(k=new_k, v=new_v,
                           k_scale=new_ks if quant else (),
                           v_scale=new_vs if quant else (),
                           **(dict(ssm=new_ssm, conv=new_conv)
                              if cfg.recurrent else {}),
                           **({"idx": new_idx} if pages.idx else {}),
                           **({"win": new_win} if pages.win else {}))
