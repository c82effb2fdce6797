"""The least the Mamba-1 kernels (jamba's mixer, ops/ssm.py) must do in
one call, from the call's ``engine.call`` attributes and the configuration
file: (operations, bytes).

Beside ``kernel_counts.py``, ``state_counts.py`` and ``scan_counts.py`` and
under their rule (kept with the benchmark; None when the span lacks an
attribute the count reads; ``PEAK_OF`` names the ``peaks.json`` key
operations are held against, bytes are held against ``hbm_gbs``).  A file
of its own because none of those may be edited outside a ``benchmark`` PR;
``readers/mamba1_kernel_roofline.py`` binds ``readers/kernel_roofline.py``'s
reduction to this one.
"""

from __future__ import annotations

from typing import Optional

# What each count reads of an engine.call span (tests hold these to the
# program's SPAN_CATALOG).
READS = {
    "mamba1_decode_update": ("steps", "lanes"),
    "mamba1_scan": ("real_tokens", "prompts"),
}
PEAK_OF = {
    "mamba1_decode_update": "bf16_tflops",
    "mamba1_scan": "bf16_tflops",
}
# Bytes of an element of the scan's input u, its result y and the low-rank
# step input: bfloat16, the activation dtype on the chip.  B, C and the
# states are float32.
ACT_BYTES = 2


def _widths(cfg: dict) -> tuple[int, int, int]:
    """(Mamba-1 layers, channels, state size): a layer is attention where
    ``i % attn_layer_period == attn_layer_offset``, Mamba-1 elsewhere."""
    period, offset = cfg["attn_layer_period"], cfg["attn_layer_offset"]
    layers = sum(i % period != offset for i in range(cfg["num_hidden_layers"]))
    return (layers, cfg["mamba_expand"] * cfg["hidden_size"],
            cfg["mamba_d_state"])


def mamba1_decode_update(cfg: dict, attrs: dict) -> Optional[tuple[float, float]]:
    """The Mamba-1 decode state update, one decode call of ``steps`` steps:
    every live lane's float32 state — channels x state size, 327,680 B at
    jamba's widths — is read once and written once a Mamba-1 layer a step;
    an element costs a decay's product and exponential, an input term and a
    read-out: 5 operations, nothing beside its 8 bytes."""
    values = [attrs.get(key) for key in READS["mamba1_decode_update"]]
    if any(v is None for v in values):
        return None
    steps, lanes = (float(v) for v in values)
    layers, C, N = _widths(cfg)
    elements = steps * lanes * layers * C * N
    return 5.0 * elements, 2 * 4.0 * elements


def mamba1_scan(cfg: dict, attrs: dict) -> Optional[tuple[float, float]]:
    """The selective scan of the Mamba-1 layers, one admission call.  Each
    real token moves, the least any form must: its input ``u`` in and its
    ``y`` out (channels each), its low-rank step input (``mamba_dt_rank``,
    from which any form can make the step sizes) and its float32 ``B`` and
    ``C`` (state size each) — 20,928 B a token-layer at jamba's widths; each
    prompt's float32 final state (channels x state size, 327,680 B) goes out
    once a layer.  Operations: the recurrence token by token, 5 an element
    of the state a token, below the bytes' time.  None without the span's
    attributes."""
    values = [attrs.get(key) for key in READS["mamba1_scan"]]
    if any(v is None for v in values):
        return None
    tokens, prompts = (float(v) for v in values)
    layers, C, N = _widths(cfg)
    token = ACT_BYTES * (2 * C + cfg["mamba_dt_rank"]) + 4 * 2 * N
    return (5.0 * tokens * layers * C * N,
            layers * (tokens * token + prompts * 4.0 * C * N))
