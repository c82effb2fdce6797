"""Plain reference of the ``jamba`` decoder (AI21-Jamba2-3B), as published.

What the serving path (models/llama.py: a Pallas selective scan at
admission and a Pallas state update at decode over a per-lane state pool,
paged MQA attention in two layers of 28, int8 projections) is compared with:
the same equations written the slow and obvious way.

* float32 throughout, ``jax.default_matmul_precision("highest")``;
* every layer is TWO sub-blocks: ``x <- x + mixer(rms_norm(x, w_in))``, then
  ``x <- x + mlp(rms_norm(x, w_ff))`` with the dense SwiGLU MLP (the
  published ``num_experts`` is 1, so every layer builds ``JambaMLP``);
* the mixer is attention where ``i % attn_layer_period ==
  attn_layer_offset`` (the layer has ``q``) and Mamba-1 elsewhere:
  ``[u | z] = in_proj(h)``, a causal depthwise convolution of ``u`` with its
  bias, silu; ``[d | B | C] = x_proj(u)`` each through its RMSNorm;
  ``dt = softplus(dt_proj(d) + dt_bias)``; the recurrence as written, **a
  sequential scan over tokens** with a decay per channel and state
  (``S[t] = exp(dt A) S[t-1] + dt B u``, ``y = S . C + D u``), never the
  kernel's blocking; ``out_proj(y * silu(z))``;
* attention: MQA (one key/value head), causal softmax over the whole
  sequence at scale head_dim^-0.5, **no rotation and no other positional
  term** (the published ``JambaAttention`` rotates nothing: position reaches
  the model through the Mamba layers), no cache;
* the head is the embedding, tied; no batching: one sequence ``[S]`` at a
  time.

It takes the *served* parameters (``init_params`` / ``init_params_quantized``
/ ``quantize_params``): int8 kernels times their scales, widened to float32.
``A_log`` is served laid ``[N, channels]`` (the state pool's layout); this
file uses it as published, ``[channels, N]``.  The configuration is a plain
mapping with the published ``config.json`` keys (``config_of`` makes one
from a ``ModelConfig``); the file imports nothing of the program.

One noted departure, not an approximation: ``act_quant=True`` (the
configuration's stated arithmetic, w8a8) — where the served path rounds a
projection's input to per-token symmetric int8 (every ``_linear`` with
``act_quant``: ``in_proj``, ``x_proj``, ``dt_proj``, ``out_proj``, q / k / v
/ o, the MLP's three kernels; not the head), the reference applies the same
rounding, in float32.

Two switches exist only so that a comparison can prove it bites:
``act_quant=4`` (activations rounded to 4 bits instead of 8) and
``cache_int8`` (the keys and values an attention layer would cache rounded
to int8 a token a head; a no-op in the Mamba layers, whose state is float32).
"""

from __future__ import annotations

from typing import Any, Mapping, Optional

import jax
import jax.numpy as jnp
import numpy as np

F32 = jnp.float32


def config_of(cfg: Any) -> dict:
    """The published keys of a ``ModelConfig`` with an ``attn_layer_period``."""
    return {
        "hidden_size": cfg.hidden_size,
        "num_hidden_layers": cfg.num_layers,
        "num_attention_heads": cfg.num_heads,
        "num_key_value_heads": cfg.num_kv_heads,
        "head_dim": cfg.head_dim_,
        "mamba_expand": cfg.mamba_expand,
        "mamba_d_state": cfg.ssm_state_size,
        "mamba_d_conv": cfg.conv_kernel,
        "mamba_dt_rank": cfg.mamba_dt_rank,
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


def mlp(layer: Mapping[str, Any], x: jnp.ndarray, act_quant) -> jnp.ndarray:
    """SwiGLU: ``down(silu(gate(x)) * up(x))``."""
    return linear(layer["down"], jax.nn.silu(linear(layer["gate"], x, act_quant))
                  * linear(layer["up"], x, act_quant), act_quant)


def _step(carry, t):
    """One token of the recurrence: ``S = exp(dt A) S + dt u B``, ``y = S C``
    (a function of the module, so that every layer's scan is one program)."""
    (state, A), (u_t, dt_t, b_t, c_t) = carry, t
    state = (jnp.exp(dt_t[:, None] * A) * state
             + (dt_t * u_t)[:, None] * b_t[None, :])
    return (state, A), state @ c_t


def mamba1(layer: Mapping[str, Any], cfg: Mapping[str, Any], h: jnp.ndarray,
           act_quant) -> jnp.ndarray:
    """A Mamba-1 mixer over one whole sequence from a zero state, token by
    token.  h [S, hidden] (already normed) -> [S, hidden]."""
    S = h.shape[0]
    C = cfg["mamba_expand"] * cfg["hidden_size"]
    N, Kc, R = cfg["mamba_d_state"], cfg["mamba_d_conv"], cfg["mamba_dt_rank"]
    eps = cfg["rms_norm_eps"]
    uz = linear(layer["in_proj"], h, act_quant)
    u, z = uz[:, :C], uz[:, C:]
    # Causal depthwise convolution over the last d_conv tokens.
    padded = jnp.concatenate([jnp.zeros((Kc - 1, C), F32), u])
    w = layer["conv"]["kernel"].astype(F32)
    conv = layer["conv"]["bias"].astype(F32) + sum(
        w[k] * padded[k:k + S] for k in range(Kc))
    u = jax.nn.silu(conv)
    dbc = linear(layer["x_proj"], u, act_quant)
    d = rms_norm(dbc[:, :R], layer["dt_norm"], eps)
    Bm = rms_norm(dbc[:, R:R + N], layer["B_norm"], eps)
    Cm = rms_norm(dbc[:, R + N:], layer["C_norm"], eps)
    dt = jax.nn.softplus(linear(layer["dt_proj"], d, act_quant)
                         + layer["dt_bias"].astype(F32))           # [S, C]
    A = -jnp.exp(layer["A_log"].astype(F32)).T                      # [C, N]
    _, y = jax.lax.scan(_step, (jnp.zeros((C, N), F32), A), (u, dt, Bm, Cm))
    y = (y + layer["D"].astype(F32) * u) * jax.nn.silu(z)
    return linear(layer["out_proj"], y, act_quant)


def attention(layer: Mapping[str, Any], cfg: Mapping[str, Any], x: jnp.ndarray,
              act_quant, *, block: int = 512,
              cache_int8: bool = False) -> jnp.ndarray:
    """MQA / GQA over one whole sequence, causal, scale head_dim^-0.5,
    nothing rotated.  x [S, hidden] (already normed) -> [S, hidden]."""
    S = x.shape[0]
    nH, nKV = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    # The published config gives no head_dim: hidden / heads (128).
    D = cfg.get("head_dim") or cfg["hidden_size"] // nH
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


def layer_forward(layer: Mapping[str, Any], cfg: Mapping[str, Any],
                  x: jnp.ndarray, act_quant, cache_int8: bool = False,
                  ) -> tuple[jnp.ndarray, Optional[np.ndarray]]:
    """One layer on a given input.  x [S, hidden] -> (x [S, hidden], None:
    no layer routes)."""
    eps = cfg["rms_norm_eps"]
    h = rms_norm(x, layer["input_norm"], eps)
    if "x_proj" in layer:
        x = x + mamba1(layer, cfg, h, act_quant)
    else:
        x = x + attention(layer, cfg, h, act_quant, cache_int8=cache_int8)
    return x + mlp(layer, rms_norm(x, layer["post_norm"], eps), act_quant), None


def embed(params: Mapping[str, Any], tokens) -> jnp.ndarray:
    e = params["embed"]
    if "weight_q" in e:
        return (e["weight_q"][tokens].astype(F32)
                * e["scale"][tokens].astype(F32)[:, None])
    return e["weight"][tokens].astype(F32)


def head(params: Mapping[str, Any]) -> jnp.ndarray:
    """The tied head: the embedding, [hidden, vocab] float32."""
    e = params["embed"]
    if "weight_q" in e:
        return (e["weight_q"].astype(F32) * e["scale"].astype(F32)[:, None]).T
    return e["weight"].astype(F32).T


def forward(params: Mapping[str, Any], cfg: Mapping[str, Any], tokens,
            *, act_quant=False, logit_positions=None,
            cache_int8: bool = False) -> tuple[np.ndarray, list]:
    """The whole model on one sequence.  tokens [S] -> (logits
    [len(logit_positions), V] float32 — every position when None — and, per
    layer, None: no layer routes)."""
    with jax.default_matmul_precision("highest"):
        x = embed(params, jnp.asarray(tokens, jnp.int32))
        for layer in params["layers"]:          # one layer's weights at a time
            x, _ = layer_forward(layer, cfg, x, act_quant, cache_int8)
        if logit_positions is not None:
            x = x[jnp.asarray(logit_positions)]
        x = rms_norm(x, params["final_norm"], cfg["rms_norm_eps"])
        # The head is weight-only in the served path too (no rounding).
        return (np.asarray(x @ head(params), np.float32),
                [None] * len(params["layers"]))
