"""Operations and bytes a call needs, computed from shapes.

Kept with the benchmark so that a later kernel metric has its arithmetic
waiting and no PR that claims a gain can change it.  Today they feed one
information line (an end-to-end utilisation), not a metric.

Counting rules: a matrix multiplication of [m, k] x [k, n] is 2*m*k*n
operations; attention counts QK^T and PV over the causal triangle; norms,
rotary embedding, activation functions and sampling are left out (they are
a few percent and bound by bandwidth, not by the MXU).
"""

from __future__ import annotations


def _dims(cfg: dict) -> tuple[int, int, int, int, int, int, int]:
    h = cfg["hidden_size"]
    nq = cfg["num_attention_heads"]
    nkv = cfg["num_key_value_heads"]
    d = cfg.get("head_dim") or h // nq
    return (h, nq, nkv, d, cfg["intermediate_size"],
            cfg["num_hidden_layers"], cfg["vocab_size"])


def linear_flops_per_token(cfg: dict) -> int:
    """Matrix-multiplication operations one token costs in the layers."""
    h, nq, nkv, d, ffn, layers, _ = _dims(cfg)
    per_layer = 2 * h * (nq * d + 2 * nkv * d) + 2 * nq * d * h + 3 * 2 * h * ffn
    return layers * per_layer


def weight_bytes(cfg: dict, bytes_per_weight: float = 1.0) -> float:
    """Bytes of layer and head weights a decode step must stream (int8: 1)."""
    h, nq, nkv, d, ffn, layers, vocab = _dims(cfg)
    per_layer = h * (nq * d + 2 * nkv * d) + nq * d * h + 3 * h * ffn
    return (layers * per_layer + vocab * h) * bytes_per_weight


def kv_bytes_per_token(cfg: dict, bytes_per_value: float = 2.0) -> float:
    _, _, nkv, d, _, layers, _ = _dims(cfg)
    return 2 * layers * nkv * d * bytes_per_value


def prefill_flops(cfg: dict, prompt_len: int) -> float:
    """One prompt of ``prompt_len`` tokens, logits for its last position."""
    h, nq, _, d, _, layers, vocab = _dims(cfg)
    attn = layers * 2 * 2 * nq * d * prompt_len * (prompt_len + 1) / 2
    return prompt_len * linear_flops_per_token(cfg) + attn + 2 * h * vocab


def decode_flops(cfg: dict, context_len: int) -> float:
    """One new token attending to ``context_len`` cached tokens."""
    h, nq, _, d, _, layers, vocab = _dims(cfg)
    attn = layers * 2 * 2 * nq * d * context_len
    return linear_flops_per_token(cfg) + attn + 2 * h * vocab


def decode_step_bytes(cfg: dict, context_lens: list[int],
                      bytes_per_weight: float = 1.0,
                      bytes_per_kv: float = 2.0) -> float:
    """One decode step over a batch: every weight once, every lane's pages."""
    return (weight_bytes(cfg, bytes_per_weight)
            + sum(context_lens) * kv_bytes_per_token(cfg, bytes_per_kv))
