"""Plain reference of the DeepSeek-V3 decoder block, as published.

What the serving path (models/llama.py: latent attention in two forms, a
sorted grouped expert product, a paged cache, kernels) is compared with:
the same equations written the slow and obvious way.

* float32 throughout, ``jax.default_matmul_precision("highest")``;
* attention in the expanded form only — per-head keys and values from the
  latent, a causal softmax over the whole sequence, no cache;
* the expert layer as a Python loop over the experts that were chosen;
* no batching: one sequence ``[S]`` at a time, the whole forward in one go
  (in blocks of query positions so that it fits at published widths; the
  weights of one layer — of one expert — are widened at a time).

It takes the *served* parameters (the pytree ``init_params`` /
``init_params_quantized`` / ``quantize_params`` build): int8 kernels times
their scales, widened to float32.  The configuration is a plain mapping with
the published ``config.json`` keys (``config_of`` makes one from a
``ModelConfig``), so the file stands alone: ``benchmarks/references/`` holds
a copy that imports nothing of the program.

Two noted departures from the published code, neither an approximation:

1. ``rope_interleave``: the checkpoint pairs rotary lanes (2i, 2i+1); this
   file, like ``ops/rope.py:apply_rope``, pairs (i, i + d/2).  The two differ
   by one fixed permutation of the rotary lanes applied to queries and keys
   alike, so every score is the same; only a real checkpoint's ``W_q`` /
   ``W_kva`` columns would need that permutation at load time.
2. ``act_quant=True`` (the configuration's stated arithmetic, w8a8): where
   the served path rounds a projection's input to per-token symmetric int8
   (every ``_linear`` with ``act_quant``: q, kv_a, o, the dense, shared and
   expert MLPs; not the router, not ``W_kvb``, not the head), the reference
   applies the same rounding, in float32.  It is the model being served, not
   an error for a tolerance to absorb.

Three switches exist only so that a comparison can prove it bites
(``act_quant`` may be ``4``: activations rounded to 4 bits instead of 8;
``cache_int8``; ``drop_rope_score``): the reference computed one precision
lower, or with a term left out, has to come out as not correct.
"""

from __future__ import annotations

from typing import Any, Mapping, Optional

import jax
import jax.numpy as jnp
import numpy as np

F32 = jnp.float32


def config_of(cfg: Any) -> dict:
    """The published keys of a ``ModelConfig`` with a latent mixer."""
    return {
        "hidden_size": cfg.hidden_size,
        "num_hidden_layers": cfg.num_layers,
        "num_attention_heads": cfg.num_heads,
        "kv_lora_rank": cfg.kv_lora_rank,
        "qk_nope_head_dim": cfg.qk_nope_head_dim,
        "qk_rope_head_dim": cfg.qk_rope_head_dim,
        "v_head_dim": cfg.v_head_dim,
        "n_routed_experts": cfg.num_experts,
        "num_experts_per_tok": cfg.num_experts_per_tok,
        "n_shared_experts": cfg.n_shared_experts,
        "moe_intermediate_size": cfg.expert_width,
        "first_k_dense_replace": cfg.first_dense_layers,
        "norm_topk_prob": cfg.norm_topk_prob,
        "routed_scaling_factor": cfg.routed_scaling_factor,
        "rms_norm_eps": cfg.rms_norm_eps,
        "rope_theta": cfg.rope_theta,
    }


# ---------------------------------------------------------------------------
# Pieces
# ---------------------------------------------------------------------------


def widen(p: Mapping[str, Any]) -> jnp.ndarray:
    """A projection's kernel in float32: int8 values times their
    per-output-channel scales, or the stored kernel."""
    if "kernel_q" in p:
        return p["kernel_q"].astype(F32) * p["scale"].astype(F32)[..., None, :]
    return p["kernel"].astype(F32)


def round_int8(x: jnp.ndarray, bits: int = 8) -> jnp.ndarray:
    """Per-token symmetric integer rounding, kept in float32 (departure 2;
    ``bits=4`` is the lower-precision control)."""
    qmax = float(2 ** (bits - 1) - 1)
    scale = jnp.maximum(jnp.max(jnp.abs(x), axis=-1, keepdims=True) / qmax,
                        1e-8)
    return jnp.clip(jnp.round(x / scale), -qmax, qmax) * scale


def linear(p: Mapping[str, Any], x: jnp.ndarray, act_quant: bool,
           kernel: Optional[jnp.ndarray] = None) -> jnp.ndarray:
    w = widen(p) if kernel is None else kernel
    if act_quant and "kernel_q" in p:   # True = 8 bits; 4 = the control
        x = round_int8(x, 8 if act_quant is True else int(act_quant))
    y = x @ w
    if "bias" in p:
        y = y + p["bias"].astype(F32)
    return y


def rms_norm(x: jnp.ndarray, w: jnp.ndarray, eps: float) -> jnp.ndarray:
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * w.astype(F32)


def rope(x: jnp.ndarray, positions: jnp.ndarray, theta: float) -> jnp.ndarray:
    """x [S, ..., d] rotated by position, lanes paired (i, i + d/2)."""
    d = x.shape[-1]
    half = d // 2
    inv = 1.0 / (theta ** (np.arange(half, dtype=np.float32) / half))
    ang = positions.astype(F32)[:, None] * jnp.asarray(inv)        # [S, half]
    ang = jnp.concatenate([ang, ang], axis=-1)
    shape = (x.shape[0],) + (1,) * (x.ndim - 2) + (d,)
    cos, sin = jnp.cos(ang).reshape(shape), jnp.sin(ang).reshape(shape)
    rot = jnp.concatenate([-x[..., half:], x[..., :half]], axis=-1)
    return x * cos + rot * sin


def swiglu(p: Mapping[str, Any], x: jnp.ndarray, act_quant: bool) -> jnp.ndarray:
    gate = linear(p["gate"], x, act_quant)
    up = linear(p["up"], x, act_quant)
    return linear(p["down"], jax.nn.silu(gate) * up, act_quant)


def attention(layer: Mapping[str, Any], cfg: Mapping[str, Any], x: jnp.ndarray,
              act_quant: bool, *, block: int = 512,
              drop_rope_score: bool = False,
              cache_int8: bool = False) -> jnp.ndarray:
    """Latent attention, expanded form, over one whole sequence.
    x [S, H] (already normed) -> [S, H].  Two negative controls, for the
    comparisons to prove they bite: ``drop_rope_score`` leaves the rotary
    part out of the score; ``cache_int8`` rounds what a cache would hold
    (the latent and the rotated key) to per-token int8 — the nearest
    precision below the bfloat16 cache the configurations state."""
    S = x.shape[0]
    nH, R = cfg["num_attention_heads"], cfg["kv_lora_rank"]
    dn, dr, dv = (cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                  cfg["v_head_dim"])
    pos = jnp.arange(S)
    q = linear(layer["q"], x, act_quant).reshape(S, nH, dn + dr)
    kva = linear(layer["kv_a"], x, act_quant)
    c = rms_norm(kva[:, :R], layer["kv_norm"], cfg["rms_norm_eps"])
    k_rope = rope(kva[:, R:], pos, cfg["rope_theta"])               # [S, dr]
    q_nope, q_rope = q[..., :dn], rope(q[..., dn:], pos, cfg["rope_theta"])
    if cache_int8:
        c, k_rope = round_int8(c), round_int8(k_rope)
    kv = (c @ widen(layer["kv_b"])).reshape(S, nH, dn + dv)
    k_nope, v = kv[..., :dn], kv[..., dn:]
    out = []
    for s0 in range(0, S, block):     # blocks of query positions
        s1 = min(S, s0 + block)
        score = jnp.einsum("shd,thd->hst", q_nope[s0:s1], k_nope[:s1])
        if not drop_rope_score:
            score = score + jnp.einsum("shd,td->hst", q_rope[s0:s1],
                                       k_rope[:s1])
        score = score / np.sqrt(dn + dr)
        seen = pos[None, s0:s1, None] >= pos[None, None, :s1]
        prob = jax.nn.softmax(jnp.where(seen, score, -jnp.inf), axis=-1)
        out.append(jnp.einsum("hst,thd->shd", prob, v[:s1]))
    o = jnp.concatenate(out, axis=0).reshape(S, nH * dv)
    return linear(layer["o"], o, act_quant)


def route(layer: Mapping[str, Any], cfg: Mapping[str, Any],
          x: jnp.ndarray) -> tuple[np.ndarray, jnp.ndarray]:
    """(chosen experts [S, K] on the host, their weights [S, K])."""
    r = layer["router"]
    s = jax.nn.sigmoid(x @ r["kernel"].astype(F32))
    chosen = np.argsort(-np.asarray(s + r["e_bias"].astype(F32)),
                        axis=-1, kind="stable")[:, :cfg["num_experts_per_tok"]]
    w = jnp.take_along_axis(s, jnp.asarray(chosen), axis=-1)
    if cfg["norm_topk_prob"]:
        w = w / (jnp.sum(w, axis=-1, keepdims=True) + 1e-20)
    return chosen, w * cfg["routed_scaling_factor"]


def expert_mlp(layer: Mapping[str, Any], cfg: Mapping[str, Any],
               x: jnp.ndarray, act_quant: bool,
               ) -> tuple[jnp.ndarray, np.ndarray]:
    """Shared + routed MLP: a loop over the experts that were chosen.
    x [S, H] -> (y [S, H], chosen [S, K])."""
    chosen, w = route(layer, cfg, x)
    y = jnp.zeros_like(x)
    for e in np.unique(chosen):
        tok, slot = np.nonzero(chosen == e)
        expert = {name: {k: v[e] for k, v in layer[f"{name}_e"].items()}
                  for name in ("gate", "up", "down")}
        y = y.at[tok].add(w[tok, slot][:, None]
                          * swiglu(expert, x[tok], act_quant))
    if "shared" in layer:
        y = y + swiglu(layer["shared"], x, act_quant)
    return y, chosen


def layer_forward(layer: Mapping[str, Any], cfg: Mapping[str, Any],
                  x: jnp.ndarray, act_quant: bool, **attn_kw,
                  ) -> tuple[jnp.ndarray, Optional[np.ndarray]]:
    """One decoder layer on a given input.  x [S, H] -> (x [S, H], the
    experts each token chose [S, K], or None for a dense layer)."""
    eps = cfg["rms_norm_eps"]
    x = x + attention(layer, cfg, rms_norm(x, layer["input_norm"], eps),
                      act_quant, **attn_kw)
    h = rms_norm(x, layer["post_norm"], eps)
    if "router" in layer:
        y, chosen = expert_mlp(layer, cfg, h, act_quant)
    else:
        y, chosen = swiglu(layer, h, act_quant), None
    return x + y, chosen


def embed(params: Mapping[str, Any], tokens) -> jnp.ndarray:
    """Rows of the embedding table (``tokens=slice(None)``: all of it)."""
    e = params["embed"]
    if "weight_q" in e:
        return (e["weight_q"][tokens].astype(F32)
                * e["scale"][tokens].astype(F32)[:, None])
    return e["weight"][tokens].astype(F32)


def forward(params: Mapping[str, Any], cfg: Mapping[str, Any], tokens,
            *, act_quant: bool = False, logit_positions=None,
            **attn_kw) -> tuple[np.ndarray, list]:
    """The whole model on one sequence.  tokens [S] -> (logits
    [len(logit_positions), V] float32 — every position when None — and, per
    layer, the experts each token chose or None)."""
    with jax.default_matmul_precision("highest"):
        tokens = jnp.asarray(tokens, jnp.int32)
        x = embed(params, tokens)
        routing = []
        for layer in params["layers"]:          # one layer's weights at a time
            x, chosen = layer_forward(layer, cfg, x, act_quant, **attn_kw)
            routing.append(chosen)
        if logit_positions is not None:
            x = x[jnp.asarray(logit_positions)]
        x = rms_norm(x, params["final_norm"], cfg["rms_norm_eps"])
        # The head is weight-only in the served path too (no rounding).
        head = (widen(params["lm_head"]) if "lm_head" in params
                else embed(params, slice(None)).T)
        return np.asarray(x @ head, np.float32), routing
