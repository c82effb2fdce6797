"""Plain reference of the ``nemotron_h`` decoder, as published.

What the serving path (models/llama.py: a chunked state-space scan at
admission and a Pallas state update at decode over a per-lane state pool,
paged GQA attention in one layer of eleven, a sorted grouped expert product
over the experts this chip holds) is compared with: the same equations
written the slow and obvious way.

* float32 throughout, ``jax.default_matmul_precision("highest")``;
* every layer is ONE sub-block: ``x <- x + f(rms_norm(x, w, eps))`` with
  ``f`` a Mamba-2 mixer (the layer has ``in_proj``), an expert feed-forward
  (it has ``router``) or attention (neither);
* the Mamba-2 recurrence as written, **a sequential scan over tokens**
  (``S[t] = exp(dt A) S[t-1] + dt x (x) B``, ``y = S C + D x``), never the
  chunked form — so the served scan and this one are independent;
* attention: GQA, causal softmax over the whole sequence, **no rotation and
  no other positional term** (the published modelling code rotates nothing),
  no cache;
* the expert layer as a Python loop over the chosen experts **that are held**
  (the layer's stacks hold ``n_routed_experts`` experts from
  ``expert_start``; the router scores the published count, and the weights
  are normalised over all the chosen, held or not): what the absent experts
  would add is left out, as in the served layer;
* no batching: one sequence ``[S]`` at a time.

It takes the *served* parameters (``init_params`` / ``init_params_quantized``
/ ``quantize_params``): int8 kernels times their scales, widened to float32.
The configuration is a plain mapping with the published ``config.json`` keys
(``config_of`` makes one from a ``ModelConfig``); the file imports nothing of
the program.

One noted departure, not an approximation: ``act_quant=True`` (the
configuration's stated arithmetic, w8a8) — where the served path rounds a
projection's input to per-token symmetric int8 (every ``_linear`` with
``act_quant``: ``in_proj``, ``out_proj``, q / k / v / o, both latent
projections, the shared and the routed experts' kernels; not the router, not
the head), the reference applies the same rounding, in float32.

Two switches exist only so that a comparison can prove it bites:
``act_quant=4`` (activations rounded to 4 bits instead of 8) and
``cache_int8`` (the keys and values an attention layer would cache rounded
to int8 a token a head; a no-op in the other layers, whose state is float32
and whose feed-forward caches nothing).
"""

from __future__ import annotations

from typing import Any, Mapping, Optional

import jax
import jax.numpy as jnp
import numpy as np

F32 = jnp.float32


def config_of(cfg: Any) -> dict:
    """The published keys of a ``ModelConfig`` with a ``layer_pattern``."""
    return {
        "hidden_size": cfg.hidden_size,
        "num_hidden_layers": cfg.num_layers,
        "num_attention_heads": cfg.num_heads,
        "num_key_value_heads": cfg.num_kv_heads,
        "head_dim": cfg.head_dim_,
        "mamba_num_heads": cfg.mamba_num_heads,
        "mamba_head_dim": cfg.mamba_head_dim,
        "ssm_state_size": cfg.ssm_state_size,
        "n_groups": cfg.mamba_n_groups,
        "conv_kernel": cfg.conv_kernel,
        "n_routed_experts": cfg.experts_held_,
        "expert_start": cfg.expert_start,
        "num_experts_per_tok": cfg.num_experts_per_tok,
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
    """Per-token symmetric integer rounding, kept in float32 (``bits=4`` is
    the lower-precision control)."""
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


def relu2_mlp(p: Mapping[str, Any], x: jnp.ndarray, act_quant) -> jnp.ndarray:
    """Two kernels, no gate: ``W2 relu(W1 x)^2``."""
    return linear(p["down"], jnp.square(jax.nn.relu(
        linear(p["up"], x, act_quant))), act_quant)


def mamba2(layer: Mapping[str, Any], cfg: Mapping[str, Any], u: jnp.ndarray,
           act_quant) -> jnp.ndarray:
    """A Mamba-2 mixer over one whole sequence from a zero state, token by
    token.  u [S, hidden] (already normed) -> [S, hidden]."""
    S = u.shape[0]
    H, P, N = cfg["mamba_num_heads"], cfg["mamba_head_dim"], cfg["ssm_state_size"]
    G, Kc = cfg["n_groups"], cfg["conv_kernel"]
    d_in = H * P
    zxbcdt = linear(layer["in_proj"], u, act_quant)
    z, xbc, dt = (zxbcdt[:, :d_in], zxbcdt[:, d_in:2 * d_in + 2 * G * N],
                  zxbcdt[:, 2 * d_in + 2 * G * N:])
    # Causal depthwise convolution over the last conv_kernel tokens.
    padded = jnp.concatenate([jnp.zeros((Kc - 1, xbc.shape[1]), F32), xbc])
    w = layer["conv"]["kernel"].astype(F32)
    conv = layer["conv"]["bias"].astype(F32) + sum(
        w[k] * padded[k:k + S] for k in range(Kc))
    xbc = jax.nn.silu(conv)
    x = xbc[:, :d_in].reshape(S, H, P)
    Bm = jnp.repeat(xbc[:, d_in:d_in + G * N].reshape(S, G, N), H // G, axis=1)
    Cm = jnp.repeat(xbc[:, d_in + G * N:].reshape(S, G, N), H // G, axis=1)
    dt = jax.nn.softplus(dt + layer["dt_bias"].astype(F32))       # [S, H]
    A = -jnp.exp(layer["A_log"].astype(F32))

    def step(state, t):
        x_t, b_t, c_t, dt_t = t
        state = (jnp.exp(dt_t * A)[:, None, None] * state
                 + (dt_t[:, None] * x_t)[:, :, None] * b_t[:, None, :])
        return state, jnp.sum(state * c_t[:, None, :], axis=-1)

    _, y = jax.lax.scan(step, jnp.zeros((H, P, N), F32), (x, Bm, Cm, dt))
    y = (y + layer["D"].astype(F32)[:, None] * x).reshape(S, d_in)
    g = (y * jax.nn.silu(z)).reshape(S, G, d_in // G)
    g = g * jax.lax.rsqrt(jnp.mean(g * g, axis=-1, keepdims=True)
                          + cfg["rms_norm_eps"])
    g = g.reshape(S, d_in) * layer["ssm_norm"].astype(F32)
    return linear(layer["out_proj"], g, act_quant)


def attention(layer: Mapping[str, Any], cfg: Mapping[str, Any], x: jnp.ndarray,
              act_quant, *, block: int = 512,
              cache_int8: bool = False) -> jnp.ndarray:
    """GQA over one whole sequence, causal, scale head_dim^-0.5, nothing
    rotated.  x [S, hidden] (already normed) -> [S, hidden]."""
    S = x.shape[0]
    nH, nKV, D = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                  cfg["head_dim"])
    q = linear(layer["q"], x, act_quant).reshape(S, nKV, nH // nKV, D)
    k = linear(layer["k"], x, act_quant).reshape(S, nKV, D)
    v = linear(layer["v"], x, act_quant).reshape(S, nKV, D)
    if cache_int8:
        k, v = round_int8(k), round_int8(v)
    pos = jnp.arange(S)
    out = []
    for s0 in range(0, S, block):     # blocks of query positions
        s1 = min(S, s0 + block)
        score = jnp.einsum("sgqd,tgd->gqst", q[s0:s1], k[:s1]) / np.sqrt(D)
        seen = pos[None, None, s0:s1, None] >= pos[None, None, None, :s1]
        prob = jax.nn.softmax(jnp.where(seen, score, -jnp.inf), axis=-1)
        out.append(jnp.einsum("gqst,tgd->sgqd", prob, v[:s1]))
    o = jnp.concatenate(out, axis=0).reshape(S, nH * D)
    return linear(layer["o"], o, act_quant)


def route(layer: Mapping[str, Any], cfg: Mapping[str, Any],
          x: jnp.ndarray) -> tuple[np.ndarray, jnp.ndarray]:
    """(chosen experts [S, K] on the host, their weights [S, K]): sigmoid
    scores over every expert the router knows, the choice by score + bias,
    the weights normalised over all the chosen and scaled."""
    r = layer["router"]
    s = jax.nn.sigmoid(x @ r["kernel"].astype(F32))
    chosen = np.argsort(-np.asarray(s + r["e_bias"].astype(F32)),
                        axis=-1, kind="stable")[:, :cfg["num_experts_per_tok"]]
    w = jnp.take_along_axis(s, jnp.asarray(chosen), axis=-1)
    if cfg["norm_topk_prob"]:
        w = w / (jnp.sum(w, axis=-1, keepdims=True) + 1e-20)
    return chosen, w * cfg["routed_scaling_factor"]


def routed_part(layer: Mapping[str, Any], cfg: Mapping[str, Any],
                x: jnp.ndarray, act_quant) -> tuple[jnp.ndarray, np.ndarray]:
    """What the held experts give: ``(sum over the chosen experts held of
    w_e expert_e(x W_down)) W_up``.  x [S, hidden] -> (y [S, hidden],
    chosen [S, K])."""
    chosen, w = route(layer, cfg, x)
    xl = linear(layer["latent_down"], x, act_quant) if "latent_down" in layer else x
    e0 = int(cfg.get("expert_start", 0))
    held = layer["up_e"].get("kernel_q", layer["up_e"].get("kernel")).shape[0]
    y = jnp.zeros_like(xl)
    for e in np.unique(chosen):
        if not e0 <= e < e0 + held:
            continue                      # another chip's expert
        tok, slot = np.nonzero(chosen == e)
        expert = {name: {k: v[e - e0] for k, v in layer[f"{name}_e"].items()}
                  for name in ("up", "down")}
        y = y.at[tok].add(w[tok, slot][:, None]
                          * relu2_mlp(expert, xl[tok], act_quant))
    if "latent_up" in layer:
        y = linear(layer["latent_up"], y, act_quant)
    return y, chosen


def layer_forward(layer: Mapping[str, Any], cfg: Mapping[str, Any],
                  x: jnp.ndarray, act_quant, cache_int8: bool = False,
                  ) -> tuple[jnp.ndarray, Optional[np.ndarray]]:
    """One layer on a given input.  x [S, hidden] -> (x [S, hidden], the
    experts each token chose [S, K], or None)."""
    h = rms_norm(x, layer["input_norm"], cfg["rms_norm_eps"])
    if "in_proj" in layer:
        return x + mamba2(layer, cfg, h, act_quant), None
    if "router" in layer:
        y, chosen = routed_part(layer, cfg, h, act_quant)
        if "shared" in layer:
            y = y + relu2_mlp(layer["shared"], h, act_quant)
        return x + y, chosen
    return x + attention(layer, cfg, h, act_quant, cache_int8=cache_int8), None


def embed(params: Mapping[str, Any], tokens) -> jnp.ndarray:
    e = params["embed"]
    if "weight_q" in e:
        return (e["weight_q"][tokens].astype(F32)
                * e["scale"][tokens].astype(F32)[:, None])
    return e["weight"][tokens].astype(F32)


def forward(params: Mapping[str, Any], cfg: Mapping[str, Any], tokens,
            *, act_quant=False, logit_positions=None,
            cache_int8: bool = False) -> tuple[np.ndarray, list]:
    """The whole model on one sequence.  tokens [S] -> (logits
    [len(logit_positions), V] float32 — every position when None — and, per
    layer, the experts each token chose or None)."""
    with jax.default_matmul_precision("highest"):
        x = embed(params, jnp.asarray(tokens, jnp.int32))
        routing = []
        for layer in params["layers"]:          # one layer's weights at a time
            x, chosen = layer_forward(layer, cfg, x, act_quant, cache_int8)
            routing.append(chosen)
        if logit_positions is not None:
            x = x[jnp.asarray(logit_positions)]
        x = rms_norm(x, params["final_norm"], cfg["rms_norm_eps"])
        # The head is weight-only in the served path too (no rounding).
        return np.asarray(x @ widen(params["lm_head"]), np.float32), routing
