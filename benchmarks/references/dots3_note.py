"""Plain reference of the dots3_note decoder block (dots3-note-prev).

What the serving path (models/llama.py: two latent geometries, an indexer
and its selection, a window-bounded store, index-key pages, kernels) is
compared with: the same equations written the slow and obvious way.

* float32 throughout, ``jax.default_matmul_precision("highest")``;
* attention in the expanded form only — per-head keys and values from the
  latent, a softmax over the keys a query is allowed, no cache;
* the indexer's scores as one ``[queries, heads, keys]`` product, the
  selection by a stable sort of each query's scores (the program counts, it
  does not sort: both keep the ``index_topk`` highest-scored earlier keys,
  the lower position among equals);
* the expert layer as a Python loop over the experts that were chosen and
  are held (``expert_start`` of the configuration and the leading axis of
  the expert stacks: the chip's share; the router scores all the published
  experts, its kernel's width);
* one sequence ``[S]`` at a time, in blocks of query positions.

It takes the *served* parameters (``init_params`` / ``init_params_quantized``
/ ``quantize_params``): int8 kernels times their scales, widened to float32.
The configuration is a plain mapping with the published ``config.json`` keys
(``config_of`` makes one from a ``ModelConfig``).  A layer's geometry is told
from its own leaves: the layers that attend to everything have an indexer
(``idx_k``), the sliding layers have none.

The layer, as published (h = RMSNorm(x); sizes by geometry: the plain keys
for full layers, the ``swa_`` keys for sliding ones):

    cq = s_q RMSNorm(W_qa h);  q = W_qb cq -> [nH, dn + dr], last dr rotated
    [ckv | kr] = W_kva h;  c = s_kv RMSNorm(ckv);  k_rope = rope(kr)
    k_j = [W_UK,j c | k_rope],  v_j = W_UV,j c,  score q_j.k_j / sqrt(dn + dr)
    o_j <- sigmoid(W_g h)_j o_j  (head-wise gate);  out = W_o [o_j]
    keys allowed at t: sliding  s <= t and t - s < window;  full  s in S_t
    indexer: qI = W_Iq cq -> [Hi, Di], kI_s = LayerNorm(W_Ik h_s), both with
      their first dr lanes rotated; w = W_Iw h_t;
      I(t, s) = sum_j (w_j / sqrt(Hi)) relu(qI_j . kI_s) / sqrt(Di);
      S_t = the index_topk largest I(t, s) over s <= t (all when t + 1 <= it)

Noted departures and inferences, none an approximation:

1. rotary lanes are paired (i, i + d/2), as ``ops/rope.py`` pairs them (a
   fixed permutation of lanes on queries and keys alike; see
   ``deepseek_v3.py``).
2. ``act_quant=True`` (w8a8): where the served path rounds a projection's
   input to per-token int8, so does the reference, in float32.
3. ``apply_mla_qkv_lora_rescale`` is read as LongCat-Flash's convention:
   ``s_q = sqrt(hidden / q_lora_rank)``, ``s_kv = sqrt(hidden /
   kv_lora_rank)`` on the normed latents, per geometry (an inference from
   the key's name, listed under ``assumed`` in the configuration's file).
4. The window's edge: ``t - s < sliding_window_size`` (the other
   assumption listed there).
5. The published indexer keeps its operands in fp8 behind a Hadamard
   rotation: storage choices, left out here and in the program.
6. The vision and audio towers and the MTP head are not part of the
   language model's ``config`` and are left out.

Switches that exist so that a comparison can prove it bites: ``act_quant``
may be ``4``; ``cache_int8`` rounds what a cache would hold (latent, rotated
key, index key) to per-token int8; ``select_all`` leaves the selection out
(every earlier key); ``selected`` hands the full layers a selection computed
elsewhere (the engine's), to separate *which keys* from *what attention
does with them*.
"""

from __future__ import annotations

from typing import Any, Mapping, Optional

import jax
import jax.numpy as jnp
import numpy as np

F32 = jnp.float32


def config_of(cfg: Any) -> dict:
    """The published keys of a ``ModelConfig`` of this family."""
    return {
        "hidden_size": cfg.hidden_size,
        "num_hidden_layers": cfg.num_layers,
        "num_attention_heads": cfg.num_heads,
        "q_lora_rank": cfg.q_lora_rank,
        "kv_lora_rank": cfg.kv_lora_rank,
        "qk_nope_head_dim": cfg.qk_nope_head_dim,
        "qk_rope_head_dim": cfg.qk_rope_head_dim,
        "v_head_dim": cfg.v_head_dim,
        "rope_theta": cfg.rope_theta,
        "swa_num_attention_heads": cfg.swa_num_heads,
        "swa_q_lora_rank": cfg.swa_q_lora_rank,
        "swa_kv_lora_rank": cfg.swa_kv_lora_rank,
        "swa_qk_nope_head_dim": cfg.swa_qk_nope_head_dim,
        "swa_qk_rope_head_dim": cfg.swa_qk_rope_head_dim,
        "swa_v_head_dim": cfg.swa_v_head_dim,
        "swa_rope_theta": cfg.swa_rope_theta,
        "sliding_window_size": cfg.sliding_window,
        "index_n_heads": cfg.index_n_heads,
        "index_head_dim": cfg.index_head_dim,
        "index_topk": cfg.index_topk,
        "apply_mla_qkv_lora_rescale": cfg.lora_rescale,
        "n_routed_experts": cfg.experts_held_,
        "expert_start": cfg.expert_start,
        "num_experts_per_tok": cfg.num_experts_per_tok,
        "n_shared_experts": cfg.n_shared_experts,
        "moe_intermediate_size": cfg.expert_width,
        "first_k_dense_replace": cfg.first_dense_layers,
        "norm_topk_prob": cfg.norm_topk_prob,
        "routed_scaling_factor": cfg.routed_scaling_factor,
        "rms_norm_eps": cfg.rms_norm_eps,
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


def linear(p: Mapping[str, Any], x: jnp.ndarray, act_quant) -> jnp.ndarray:
    if act_quant and "kernel_q" in p:   # True = 8 bits; 4 = the control
        x = round_int8(x, 8 if act_quant is True else int(act_quant))
    return x @ widen(p)


def rms_norm(x: jnp.ndarray, w: jnp.ndarray, eps: float) -> jnp.ndarray:
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * w.astype(F32)


def layer_norm(x: jnp.ndarray, p: Mapping[str, Any], eps: float) -> jnp.ndarray:
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean((x - mu) ** 2, axis=-1, keepdims=True)
    return ((x - mu) * jax.lax.rsqrt(var + eps) * p["weight"].astype(F32)
            + p["bias"].astype(F32))


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


def swiglu(p: Mapping[str, Any], x: jnp.ndarray, act_quant) -> jnp.ndarray:
    gate = linear(p["gate"], x, act_quant)
    up = linear(p["up"], x, act_quant)
    return linear(p["down"], jax.nn.silu(gate) * up, act_quant)


def geometry(layer: Mapping[str, Any], cfg: Mapping[str, Any]) -> dict:
    """The sizes of this layer's mixer: the plain keys where it has an
    indexer (a full layer), the ``swa_`` keys where it has none."""
    full = "idx_k" in layer
    pre = "" if full else "swa_"
    g = {name: cfg[pre + name] for name in (
        "q_lora_rank", "kv_lora_rank", "qk_nope_head_dim", "qk_rope_head_dim",
        "v_head_dim", "rope_theta")}
    g["heads"] = cfg[("" if full else "swa_") + "num_attention_heads"]
    g["window"] = 0 if full else cfg["sliding_window_size"]
    rescale = cfg.get("apply_mla_qkv_lora_rescale", False)
    g["s_q"] = np.sqrt(cfg["hidden_size"] / g["q_lora_rank"]) if rescale else 1.0
    g["s_kv"] = np.sqrt(cfg["hidden_size"] / g["kv_lora_rank"]) if rescale else 1.0
    return g


def index_scores(layer: Mapping[str, Any], cfg: Mapping[str, Any],
                 x: jnp.ndarray, cq: jnp.ndarray, act_quant, theta: float,
                 cache_int8: bool = False) -> jnp.ndarray:
    """The indexer's scores I(t, s) [S, S] float32 (every pair; the caller
    looks at s <= t)."""
    S = x.shape[0]
    Hi, Di, dr = (cfg["index_n_heads"], cfg["index_head_dim"],
                  cfg["qk_rope_head_dim"])
    pos = jnp.arange(S)
    qI = linear(layer["idx_q"], cq, act_quant).reshape(S, Hi, Di)
    kI = layer_norm(linear(layer["idx_k"], x, act_quant), layer["idx_k_norm"],
                    cfg["rms_norm_eps"])
    qI = jnp.concatenate([rope(qI[..., :dr], pos, theta), qI[..., dr:]], -1)
    kI = jnp.concatenate([rope(kI[..., :dr], pos, theta), kI[..., dr:]], -1)
    if cache_int8:
        kI = round_int8(kI)
    w = linear(layer["idx_w"], x, act_quant) / np.sqrt(Hi) / np.sqrt(Di)
    out = []
    for s0 in range(0, S, 256):       # blocks of query positions
        dots = jnp.einsum("shd,td->sht", qI[s0:s0 + 256], kI)
        out.append(jnp.sum(jax.nn.relu(dots) * w[s0:s0 + 256, :, None], axis=1))
    return jnp.concatenate(out, axis=0)


def selection(layer: Mapping[str, Any], cfg: Mapping[str, Any], x: jnp.ndarray,
              act_quant, cache_int8: bool = False) -> tuple[np.ndarray, np.ndarray]:
    """The indexer alone, on a full layer's normed input x [S, H]: (the
    scores I(t, s) [S, S] float32, S_t as a mask [S, S])."""
    g = geometry(layer, cfg)
    cq = g["s_q"] * rms_norm(linear(layer["q_a"], x, act_quant),
                             layer["q_norm"], cfg["rms_norm_eps"])
    scores = np.asarray(index_scores(layer, cfg, x, cq, act_quant,
                                     g["rope_theta"], cache_int8))
    return scores, select(scores, cfg["index_topk"])


def select(scores: np.ndarray, topk: int) -> np.ndarray:
    """S_t as a mask [S, S]: the ``topk`` largest I(t, s) over s <= t, all
    of them when t + 1 <= topk; ties go to the lower s (a stable sort of
    the negated scores)."""
    scores = np.asarray(scores, np.float32)
    S = scores.shape[0]
    keep = np.tril(np.ones((S, S), bool))
    for t in range(topk, S):
        order = np.argsort(-scores[t, :t + 1], kind="stable")[:topk]
        keep[t] = False
        keep[t, order] = True
    return keep


def attention(layer: Mapping[str, Any], cfg: Mapping[str, Any], x: jnp.ndarray,
              act_quant, *, block: int = 256, cache_int8: bool = False,
              select_all: bool = False, selected: Optional[np.ndarray] = None,
              probe: Optional[dict] = None) -> jnp.ndarray:
    """Latent attention of either geometry, expanded form, over one whole
    sequence.  x [S, H] (already normed) -> [S, H].  ``selected`` [S, S]
    bool: the keys each query of a full layer sees, in place of the
    reference's own selection; ``probe`` receives the reference's own
    indexer scores and selection (``scores``, ``keep``) whichever is used."""
    S = x.shape[0]
    g = geometry(layer, cfg)
    nH, R = g["heads"], g["kv_lora_rank"]
    dn, dr, dv = g["qk_nope_head_dim"], g["qk_rope_head_dim"], g["v_head_dim"]
    eps, theta = cfg["rms_norm_eps"], g["rope_theta"]
    pos = jnp.arange(S)
    cq = g["s_q"] * rms_norm(linear(layer["q_a"], x, act_quant),
                             layer["q_norm"], eps)
    q = linear(layer["q_b"], cq, act_quant).reshape(S, nH, dn + dr)
    kva = linear(layer["kv_a"], x, act_quant)
    c = g["s_kv"] * rms_norm(kva[:, :R], layer["kv_norm"], eps)
    k_rope = rope(kva[:, R:], pos, theta)                           # [S, dr]
    q_nope, q_rope = q[..., :dn], rope(q[..., dn:], pos, theta)
    if cache_int8:
        c, k_rope = round_int8(c), round_int8(k_rope)
    kv = (c @ widen(layer["kv_b"])).reshape(S, nH, dn + dv)
    k_nope, v = kv[..., :dn], kv[..., dn:]

    allowed = np.tril(np.ones((S, S), bool))
    if g["window"]:
        allowed &= (np.arange(S)[:, None] - np.arange(S)[None, :]
                    < g["window"])
    elif not select_all:
        if selected is None or probe is not None:
            scores, own = selection(layer, cfg, x, act_quant, cache_int8)
            if probe is not None:
                probe.update(scores=scores, keep=own)
        allowed = np.asarray(selected, bool) if selected is not None else own
    allowed = jnp.asarray(allowed)

    out = []
    for s0 in range(0, S, block):     # blocks of query positions
        s1 = min(S, s0 + block)
        score = (jnp.einsum("shd,thd->hst", q_nope[s0:s1], k_nope[:s1])
                 + jnp.einsum("shd,td->hst", q_rope[s0:s1], k_rope[:s1]))
        score = score / np.sqrt(dn + dr)
        prob = jax.nn.softmax(
            jnp.where(allowed[None, s0:s1, :s1], score, -jnp.inf), axis=-1)
        out.append(jnp.einsum("hst,thd->shd", prob, v[:s1]))
    o = jnp.concatenate(out, axis=0)                                # [S, nH, dv]
    gate = jax.nn.sigmoid(linear(layer["attn_gate"], x, act_quant))  # [S, nH]
    o = (o * gate[..., None]).reshape(S, nH * dv)
    return linear(layer["o"], o, act_quant)


def route(layer: Mapping[str, Any], cfg: Mapping[str, Any],
          x: jnp.ndarray) -> tuple[np.ndarray, jnp.ndarray]:
    """(chosen experts [S, K] on the host, their weights [S, K]): the top K
    of ``sigmoid(logits) + bias`` over all published experts (no group
    limit), weights the sigmoids of the chosen, normalised over all K."""
    r = layer["router"]
    s = jax.nn.sigmoid(x @ r["kernel"].astype(F32))
    chosen = np.argsort(-np.asarray(s + r["e_bias"].astype(F32)),
                        axis=-1, kind="stable")[:, :cfg["num_experts_per_tok"]]
    w = jnp.take_along_axis(s, jnp.asarray(chosen), axis=-1)
    if cfg["norm_topk_prob"]:
        w = w / (jnp.sum(w, axis=-1, keepdims=True) + 1e-20)
    return chosen, w * cfg["routed_scaling_factor"]


def expert_mlp(layer: Mapping[str, Any], cfg: Mapping[str, Any],
               x: jnp.ndarray, act_quant, shared: bool = True,
               ) -> tuple[jnp.ndarray, np.ndarray]:
    """Shared + routed MLP: a loop over the chosen experts this chip holds
    (``expert_start`` + the leading axis of the expert stacks); what the
    absent experts would add is left out.  x [S, H] -> (y [S, H], chosen
    [S, K])."""
    chosen, w = route(layer, cfg, x)
    e0 = int(cfg.get("expert_start", 0))
    held = layer["up_e"]["kernel_q" if "kernel_q" in layer["up_e"]
                         else "kernel"].shape[0]
    y = jnp.zeros_like(x)
    for e in np.unique(chosen):
        if not e0 <= e < e0 + held:
            continue
        tok, slot = np.nonzero(chosen == e)
        expert = {name: {k: v[e - e0] for k, v in layer[f"{name}_e"].items()}
                  for name in ("gate", "up", "down")}
        y = y.at[tok].add(w[tok, slot][:, None]
                          * swiglu(expert, x[tok], act_quant))
    if shared and "shared" in layer:
        y = y + swiglu(layer["shared"], x, act_quant)
    return y, chosen


def layer_forward(layer: Mapping[str, Any], cfg: Mapping[str, Any],
                  x: jnp.ndarray, act_quant, **attn_kw,
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
            *, act_quant=False, logit_positions=None,
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
