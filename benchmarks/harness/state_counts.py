"""The least a recurrent-state kernel must do in one call, from the call's
``engine.call`` attributes and the configuration file: (operations, bytes).

Beside ``kernel_counts.py`` and under its rule (kept with the benchmark;
None when the span lacks an attribute the count reads; ``PEAK_OF`` names the
``peaks.json`` key operations are held against, bytes are held against
``hbm_gbs``).  A file of its own because ``kernel_counts.py`` may be edited
by a ``benchmark`` PR alone; ``readers/state_kernel_roofline.py`` binds
``readers/kernel_roofline.py``'s reduction to this one.
"""

from __future__ import annotations

from typing import Optional

# What each count reads of an engine.call span (tests hold these to the
# program's SPAN_CATALOG).
READS = {
    "ssm_decode_update": ("steps", "lanes"),
}
PEAK_OF = {
    "ssm_decode_update": "bf16_tflops",
}


def ssm_decode_update(cfg: dict, attrs: dict) -> Optional[tuple[float, float]]:
    """The Mamba-2 decode state update, one decode call of ``steps`` steps:
    every live lane's float32 state — heads x head width x state size — is
    read once and written once a Mamba-2 layer a step (the layers run are
    the first ``num_hidden_layers`` letters of the published pattern); an
    element costs a decay, an outer-product term and a read-out: 5
    operations, nothing beside its 8 bytes."""
    values = [attrs.get(key) for key in READS["ssm_decode_update"]]
    if any(v is None for v in values):
        return None
    steps, lanes = (float(v) for v in values)
    layers = cfg["hybrid_override_pattern"][:cfg["num_hidden_layers"]].count("M")
    state = cfg["mamba_num_heads"] * cfg["mamba_head_dim"] * cfg["ssm_state_size"]
    elements = steps * lanes * layers * state
    return 5.0 * elements, 2 * 4.0 * elements
