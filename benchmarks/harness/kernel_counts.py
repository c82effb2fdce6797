"""The least a kernel must do in one call, from the call's ``engine.call``
attributes and the configuration file: (operations, bytes).

Beside ``flops.py`` and under the same rule: kept with the benchmark, so that
no PR that claims a gain can change what a roofline share is a share of.
Each function returns None when the span lacks an attribute it reads (a
program without that counter).  ``PEAK_OF`` names the ``peaks.json`` key the
operations are held against; bytes are held against ``hbm_gbs``.
"""

from __future__ import annotations

from typing import Optional

# What each count reads of an engine.call span (tests hold these to the
# program's SPAN_CATALOG).
READS = {
    "latent_decode_attention": ("steps", "lanes", "ctx_tokens"),
    "latent_prefill_attention": ("real_tokens", "prompts"),
}
PEAK_OF = {
    "latent_decode_attention": "bf16_tflops",
    "latent_prefill_attention": "bf16_tflops",
}


def _attrs(attrs: dict, name: str) -> Optional[list[float]]:
    values = [attrs.get(key) for key in READS[name]]
    return None if any(v is None for v in values) else [float(v) for v in values]


def latent_decode_attention(cfg: dict, attrs: dict) -> Optional[tuple[float, float]]:
    """Absorbed-form decode attention over latent pages, one decode call of
    ``steps`` steps: at step s every live lane holds its context plus the s+1
    tokens the call appended, and each cached token's row — latent and
    rotated key, unpadded, 2 bytes a value — is read once a layer; a head
    does 2 x (row width) operations for the score and 2 x (latent width) for
    the value, per cached token."""
    got = _attrs(attrs, "latent_decode_attention")
    if got is None:
        return None
    steps, lanes, ctx_tokens = got
    layers = cfg["num_hidden_layers"]
    row = cfg["kv_lora_rank"] + cfg["qk_rope_head_dim"]
    tokens = sum(ctx_tokens + (s + 1) * lanes for s in range(int(steps)))
    ops = tokens * layers * cfg["num_attention_heads"] * 2 * (row + cfg["kv_lora_rank"])
    return ops, tokens * layers * row * 2.0


def latent_prefill_attention(cfg: dict, attrs: dict) -> Optional[tuple[float, float]]:
    """Expanded-form causal attention over a fresh batch's own tokens, one
    admission call: the span gives the call's real tokens and its prompts,
    not each prompt's length, and the causal triangles of n prompts that sum
    to T tokens are least when the prompts are equal — n x (T/n)(T/n + 1)/2
    pairs — so that is what is counted.  A pair costs a head 2 x (nope +
    rope width) operations for the score and 2 x (value width) for the
    value; queries, keys, values and the result cross HBM once."""
    got = _attrs(attrs, "latent_prefill_attention")
    if got is None or got[1] <= 0:
        return None
    tokens, prompts = got
    each = tokens / prompts
    pairs = prompts * each * (each + 1) / 2
    heads, layers = cfg["num_attention_heads"], cfg["num_hidden_layers"]
    dk = cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]
    dv = cfg["v_head_dim"]
    ops = layers * heads * pairs * 2 * (dk + dv)
    return ops, layers * heads * tokens * (2 * dk + 2 * dv) * 2.0
