"""Weight-only int8 quantization for the decoder LM.

Why weight-only: TPU decode is HBM-bandwidth-bound — every decode step
streams the full weight set through the MXU for one token per lane.  Halving
weight bytes (bf16 -> int8 + per-channel scales) both halves that traffic and
makes the Llama-3-8B target (~8 GB quantized) fit a 16 GB v5e chip next to
the paged KV pool, which bf16 weights (~16 GB) cannot.  Activations and the
KV cache stay bf16: their traffic is small next to weights at serving batch
sizes, and keeping them wide preserves accuracy.

Scheme: symmetric per-output-channel int8.

    w_q[i, o]  = round(w[i, o] / scale[o]),  scale[o] = max_i |w[i, o]| / 127

The forward pass never materializes a dequantized weight matrix: because the
scale is per *output* channel it commutes with the contraction,

    x @ (w_q * scale) == (x @ w_q) * scale

so ``models/llama.py:_linear`` runs the matmul on the int8 kernel (upcast to
the activation dtype on the fly — a cast XLA fuses into the MXU operand
read, so HBM still only moves int8 bytes) and applies the scale to the
[.., out] result.  int8 values are exact in bfloat16 (|v| <= 127 < 2^8), so
the upcast loses nothing.

Embedding / unembedding use the same scheme per vocab row (the embed matrix
is its own transpose-partner when tied).

Quantized pytree leaves replace their bf16 counterparts in place:

    linear:  {"kernel": [in, out] bf16}        -> {"kernel_q": int8, "scale": f32 [out]}
    embed:   {"weight": [vocab, H] bf16}       -> {"weight_q": int8, "scale": f32 [vocab]}

``bias`` entries (Qwen2 QKV) stay in the activation dtype.

Capability context: the reference's LLM layer is config-only (reference
internal/config/config.go:141-145); serving the real Llama-3-8B target on a
single 16 GB chip is a north-star obligation (BASELINE.md configs #2/#4),
and this module is what makes the geometry fit.
"""

from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from k8s_llm_monitor_tpu.models.config import ModelConfig

Params = dict[str, Any]

_EPS = 1e-12


def quantize_array(w: np.ndarray, axis: int = 0) -> tuple[np.ndarray, np.ndarray]:
    """Symmetric int8 quantization of ``w`` with scales over ``axis``.

    Host-side numpy (streaming checkpoint load must not touch the device).
    Returns (w_q int8 same shape, scale float32 with ``axis`` reduced).
    """
    w = np.asarray(w, np.float32)
    amax = np.max(np.abs(w), axis=axis)
    scale = np.maximum(amax / 127.0, _EPS).astype(np.float32)
    w_q = np.rint(w / np.expand_dims(scale, axis)).astype(np.int8)
    return w_q, scale


def quantize_linear(p: Params) -> Params:
    """{"kernel": [in, out], ...} -> {"kernel_q", "scale", ...}."""
    w_q, scale = quantize_array(np.asarray(p["kernel"]), axis=0)
    out: Params = {"kernel_q": jnp.asarray(w_q), "scale": jnp.asarray(scale)}
    if "bias" in p:
        out["bias"] = p["bias"]
    return out


def quantize_embed(p: Params) -> Params:
    """{"weight": [vocab, H]} -> {"weight_q", "scale"} (per-row scales)."""
    w_q, scale = quantize_array(np.asarray(p["weight"]), axis=1)
    return {"weight_q": jnp.asarray(w_q), "scale": jnp.asarray(scale)}


def quantize_expert_stack(p: Params) -> Params:
    """{"kernel": [E, in, out]} -> {"kernel_q" int8, "scale" [E, out]}.

    Per-expert per-output-channel symmetric int8 — the exact analogue of
    quantize_linear with the expert axis carried through; dequant stays a
    per-(expert, out) multiply on the einsum result (models/llama.py).
    """
    w_q, scale = quantize_array(np.asarray(p["kernel"]), axis=1)
    return {"kernel_q": jnp.asarray(w_q), "scale": jnp.asarray(scale)}


def quantize_params(params: Params) -> Params:
    """Quantize a full llama param pytree (see models/llama.py layout).

    Norm vectors stay in their original dtype — they are O(hidden) bytes and
    scale-sensitive.
    """
    layers = []
    for layer in params["layers"]:
        if "post_norm" not in layer:
            layers.append(_quantize_single_layer(layer))
            continue
        ql: Params = {
            "input_norm": layer["input_norm"],
            "post_norm": layer["post_norm"],
        }
        for name in ("post_attn_norm", "post_mlp_norm"):  # Gemma sandwich
            if name in layer:
                ql[name] = layer[name]
        if "kv_a" in layer:
            # Latent mixer: q (or q_a and q_b), kv_a, o, the gate and the
            # indexer's projections quantize like any projection; the
            # norms and W_kvb stay wide (models/llama.py:_kv_b: the
            # absorbed form multiplies queries and outputs by W_kvb, so it
            # has no per-token activation to quantize against).
            for name in _LATENT_LINEARS:
                if name in layer:
                    ql[name] = quantize_linear(layer[name])
            for name in ("kv_norm", "kv_b", "q_norm", "idx_k_norm"):
                if name in layer:
                    ql[name] = layer[name]
        else:
            for name in ("q", "k", "v", "o"):
                ql[name] = quantize_linear(layer[name])
        if "router" in layer:
            # MoE layers: the router (and its selection bias) stays wide
            # (tiny, routing-decision sensitive); expert stacks quantize
            # per-expert-per-channel; shared experts like a dense MLP.
            ql["router"] = layer["router"]
            for name in ("gate_e", "up_e", "down_e"):
                ql[name] = quantize_expert_stack(layer[name])
            if "shared" in layer:
                ql["shared"] = {name: quantize_linear(layer["shared"][name])
                                for name in ("gate", "up", "down")}
        else:
            for name in ("gate", "up", "down"):
                ql[name] = quantize_linear(layer[name])
        layers.append(ql)
    out: Params = {
        "embed": quantize_embed(params["embed"]),
        "layers": layers,
        "final_norm": params["final_norm"],
    }
    if "lm_head" in params:
        out["lm_head"] = quantize_linear(params["lm_head"])
    return out


# A latent mixer's projections that take int8 kernels.
_LATENT_LINEARS = ("q", "q_a", "q_b", "kv_a", "o", "attn_gate", "idx_q",
                   "idx_k", "idx_w")

# What a one-sub-block layer (``ModelConfig.layer_pattern``) keeps wide: the
# norms, the router, and a Mamba-2 mixer's convolution and per-head vectors
# (float32: they shape the recurrence's decay, not a matrix product).
_SINGLE_WIDE = ("input_norm", "router", "conv", "dt_bias", "A_log", "D",
                "ssm_norm")


def _quantize_single_layer(layer: Params) -> Params:
    ql: Params = {}
    for name, p in layer.items():
        if name in _SINGLE_WIDE:
            ql[name] = p
        elif name in ("up_e", "down_e"):
            ql[name] = quantize_expert_stack(p)
        elif name == "shared":
            ql[name] = {k: quantize_linear(v) for k, v in p.items()}
        else:       # in_proj, out_proj, q, k, v, o, latent_down, latent_up
            ql[name] = quantize_linear(p)
    return ql


# ---------------------------------------------------------------------------
# Direct quantized random init (benchmarks)
# ---------------------------------------------------------------------------


def init_params_quantized(rng: jax.Array, cfg: ModelConfig) -> Params:
    """Random-init parameters directly in int8 + scales.

    The 8B-class bench configs cannot materialize bf16 weights first (16 GB
    on a 16 GB chip) — this builds each tensor already quantized, with scales
    matching the magnitude ``models/llama.py:init_params`` would produce
    (kernel std in**-0.5, embed std 0.02), so activations have realistic
    dynamic range.
    """
    dtype = jnp.dtype(cfg.dtype)
    H, D = cfg.hidden_size, cfg.head_dim_
    nH, nKV, I = cfg.num_heads, cfg.num_kv_heads, cfg.intermediate_size

    def qdense(key, in_f, out_f, bias):
        # ~N(0, in**-0.5) truncated at 3 sigma -> amax ~= 3 * std.
        w_q = jax.random.randint(key, (in_f, out_f), -127, 128, jnp.int8)
        scale = jnp.full((out_f,), 3.0 * (in_f ** -0.5) / 127.0, jnp.float32)
        p: Params = {"kernel_q": w_q, "scale": scale}
        if bias:
            p["bias"] = jnp.zeros((out_f,), dtype)
        return p

    if cfg.num_experts > 0 and not cfg.latent and not cfg.n_shared_experts:
        # Mixtral-style MoE: bf16 init then quantize (the direct-int8 trick
        # below skips the bf16 materialization, but expert stacks need the
        # real value distribution for per-expert scales; the transient bf16
        # peak is fine at dev/random-init scales — real MoE checkpoints
        # stream through convert_hf_state_dict(quantize=True)
        # tensor-by-tensor).
        from k8s_llm_monitor_tpu.models.llama import init_params

        return quantize_params(init_params(rng, cfg))

    def qexperts(key, in_f, out_f):
        # The direct-int8 trick per expert: 128 experts at published widths
        # cannot pass through bf16 on one chip either.
        E = cfg.experts_held_
        return {"kernel_q": jax.random.randint(
                    key, (E, in_f, out_f), -127, 128, jnp.int8),
                "scale": jnp.full((E, out_f), 3.0 * (in_f ** -0.5) / 127.0,
                                  jnp.float32)}

    def wide(key, in_f, out_f, dt):
        return {"kernel": (jax.random.normal(key, (in_f, out_f), jnp.float32)
                           * (in_f ** -0.5)).astype(dt)}

    def qmlp(keys, width):
        return {"gate": qdense(keys[0], H, width, False),
                "up": qdense(keys[1], H, width, False),
                "down": qdense(keys[2], width, H, False)}

    keys = jax.random.split(rng, 2 + cfg.num_layers)
    layers = []
    for i in range(cfg.num_layers):
        lk = jax.random.split(keys[2 + i], 7)
        spec = cfg.layer_spec(i)
        if cfg.layer_pattern is not None:
            from k8s_llm_monitor_tpu.models.llama import init_single_layer

            layers.append(init_single_layer(keys[2 + i], cfg, spec, qdense,
                                            qexperts))
            continue
        layer: Params = {"input_norm": jnp.ones((H,), dtype),
                         "post_norm": jnp.ones((H,), dtype)}
        if spec.mixer == "latent" and cfg.latent_geometry(i).q_lora_rank:
            from k8s_llm_monitor_tpu.models.llama import init_latent_mixer

            layer.update(init_latent_mixer(
                jax.random.fold_in(keys[2 + i], 2), cfg,
                cfg.latent_geometry(i), qdense,
                lambda key, in_f, out_f: wide(key, in_f, out_f, dtype)))
        elif spec.mixer == "latent":
            R, dn, dr = (cfg.kv_lora_rank, cfg.qk_nope_head_dim,
                         cfg.qk_rope_head_dim)
            layer["q"] = qdense(lk[0], H, nH * (dn + dr), False)
            layer["kv_a"] = qdense(lk[1], H, R + dr, False)
            layer["kv_norm"] = jnp.ones((R,), dtype)
            # Wide under every quantisation (quantize_params says why).
            layer["kv_b"] = wide(lk[2], R, nH * (dn + cfg.v_head_dim), dtype)
            layer["o"] = qdense(lk[3], nH * cfg.v_head_dim, H, False)
        else:
            layer["q"] = qdense(lk[0], H, nH * D, cfg.qkv_bias)
            layer["k"] = qdense(lk[1], H, nKV * D, cfg.qkv_bias)
            layer["v"] = qdense(lk[2], H, nKV * D, cfg.qkv_bias)
            layer["o"] = qdense(lk[3], nH * D, H, False)
        if spec.mlp == "dense":
            layer.update(qmlp(lk[4:7], I))
        else:
            xk = jax.random.split(jax.random.fold_in(keys[2 + i], 1), 5)
            Ie = cfg.expert_width
            f32 = cfg.moe_scoring == "sigmoid+bias"
            layer["router"] = wide(xk[0], H, cfg.num_experts,
                                   jnp.float32 if f32 else dtype)
            if f32:
                layer["router"]["e_bias"] = 0.01 * jax.random.normal(
                    xk[1], (cfg.num_experts,), jnp.float32)
            layer["gate_e"] = qexperts(lk[4], H, Ie)
            layer["up_e"] = qexperts(lk[5], H, Ie)
            layer["down_e"] = qexperts(lk[6], Ie, H)
            if spec.mlp == "shared+routed":
                layer["shared"] = qmlp(xk[2:5], cfg.n_shared_experts * Ie)
        layers.append(layer)
    params: Params = {
        "embed": {
            "weight_q": jax.random.randint(
                keys[0], (cfg.vocab_size, H), -127, 128, jnp.int8),
            "scale": jnp.full((cfg.vocab_size,), 3.0 * 0.02 / 127.0,
                              jnp.float32),
        },
        "layers": layers,
        "final_norm": jnp.ones((H,), dtype),
    }
    if not cfg.tie_embeddings:
        params["lm_head"] = qdense(keys[1], H, cfg.vocab_size, False)
    return params


def param_bytes(params: Params) -> int:
    """Total weight bytes as stored (int8 kernels count 1 byte/element)."""
    return sum(x.size * x.dtype.itemsize
               for x in jax.tree_util.tree_leaves(params))
