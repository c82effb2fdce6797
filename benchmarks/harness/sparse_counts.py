"""The least selected attention and window attention must do in one call,
from the call's ``engine.call`` attributes and the configuration file:
(operations, bytes).

Beside ``kernel_counts.py``, ``state_counts.py`` and ``expert_counts.py`` and
under their rule (kept with the benchmark; None when the span lacks an
attribute the count reads; ``PEAK_OF`` names the ``peaks.json`` key
operations are held against, bytes are held against ``hbm_gbs``).  A file of
its own because none of those may be edited outside a ``benchmark`` PR;
``readers/sparse_kernel_roofline.py`` binds ``readers/kernel_roofline.py``'s
reduction to this one.

Each count is the least ANY implementation must do, so that the two forms of
selected attention — stream every page and mask (built), fetch the selected
rows alone (not built) — are held to one yardstick: the index key of every
token in context has to be scored, but only the selected rows have to be
read.
"""

from __future__ import annotations

from typing import Optional

# What each count reads of an engine.call span (tests hold these to the
# program's SPAN_CATALOG).
READS = {
    "sparse_latent_decode_attention": ("index_tokens", "sel_tokens"),
    "window_latent_decode_attention": ("window_tokens",),
    "sparse_latent_prefill_attention": ("real_tokens", "prompts"),
}
PEAK_OF = {
    "sparse_latent_decode_attention": "bf16_tflops",
    "window_latent_decode_attention": "bf16_tflops",
    "sparse_latent_prefill_attention": "bf16_tflops",
}


def _attrs(attrs: dict, name: str) -> Optional[list[float]]:
    values = [attrs.get(key) for key in READS[name]]
    return None if any(v is None for v in values) else [float(v) for v in values]


def _full_layers(cfg: dict) -> int:
    return cfg["layer_types"][:cfg["num_hidden_layers"]].count("full_attention")


def sparse_latent_decode_attention(cfg: dict, attrs: dict) -> Optional[tuple[float, float]]:
    """Selected decode attention on the full layers, one decode call.  The
    program counts on the device, over full layers, live lanes and steps:
    ``index_tokens`` — the tokens in context, each of whose index keys (one
    of ``index_head_dim`` values, 2 bytes a value) is read and scored by
    ``index_n_heads`` heads — and ``sel_tokens`` — min(context, index_topk),
    the rows attention needs (latent and rotated key, unpadded, 2 bytes a
    value), each costing a head 2 x (row width) operations for the score and
    2 x (latent width) for the value."""
    got = _attrs(attrs, "sparse_latent_decode_attention")
    if got is None:
        return None
    index_tokens, sel_tokens = got
    row = cfg["kv_lora_rank"] + cfg["qk_rope_head_dim"]
    ops = (index_tokens * cfg["index_n_heads"] * 2 * cfg["index_head_dim"]
           + sel_tokens * cfg["num_attention_heads"] * 2
           * (row + cfg["kv_lora_rank"]))
    return ops, index_tokens * cfg["index_head_dim"] * 2.0 + sel_tokens * row * 2.0


def window_latent_decode_attention(cfg: dict, attrs: dict) -> Optional[tuple[float, float]]:
    """Decode attention over the window store, one decode call:
    ``window_tokens`` — min(context, sliding_window_size), counted on the
    device over sliding layers, live lanes and steps — rows of the sliding
    geometry's latent and rotated key, unpadded, 2 bytes a value, each read
    once; a head does 2 x (row width) + 2 x (latent width) operations a
    row."""
    got = _attrs(attrs, "window_latent_decode_attention")
    if got is None:
        return None
    (window_tokens,) = got
    row = cfg["swa_kv_lora_rank"] + cfg["swa_qk_rope_head_dim"]
    ops = (window_tokens * cfg["swa_num_attention_heads"] * 2
           * (row + cfg["swa_kv_lora_rank"]))
    return ops, window_tokens * row * 2.0


def sparse_latent_prefill_attention(cfg: dict, attrs: dict) -> Optional[tuple[float, float]]:
    """Selected fresh-prefill attention on the full layers, one admission
    call.  The span gives the call's real tokens and its prompts, not each
    prompt's length; both sums below are least when the prompts are equal
    (``kernel_counts.py`` argues the same for the causal triangle), so an
    even split is what is counted.  A query at position t scores t + 1 index
    keys (``index_n_heads`` x ``index_head_dim`` x 2 operations a pair) and
    attends to min(t + 1, index_topk) keys (a head does 2 x (nope + rope
    width) for the score and 2 x (value width) for the value, a pair);
    queries, keys, values, the indexer's operands and the result cross HBM
    once."""
    got = _attrs(attrs, "sparse_latent_prefill_attention")
    if got is None or got[1] <= 0:
        return None
    tokens, prompts = got
    each, topk = tokens / prompts, float(cfg["index_topk"])
    scored = each * (each + 1) / 2
    attended = (scored if each <= topk
                else topk * (topk + 1) / 2 + (each - topk) * topk)
    heads, layers = cfg["num_attention_heads"], _full_layers(cfg)
    dk = cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]
    dv = cfg["v_head_dim"]
    ops = layers * prompts * (
        scored * cfg["index_n_heads"] * cfg["index_head_dim"] * 2
        + attended * heads * 2 * (dk + dv))
    nbytes = layers * tokens * 2.0 * (
        heads * (2 * dk + 2 * dv)
        + (cfg["index_n_heads"] + 1) * cfg["index_head_dim"])
    return ops, nbytes
