"""Ring attention: causal attention sequence-sharded over the ``seq`` mesh
axis (SURVEY §5.7 / §7 step 7 — long-context beyond one chip's HBM).

Each device holds one sequence shard of Q, K and V.  K/V shards rotate
around the ring with ``jax.lax.ppermute`` (nearest-neighbor ICI traffic, no
all-gather) while every device folds each visiting chunk into a running
online-softmax accumulator for its local queries — the memory footprint per
device stays O(S/n) regardless of total sequence length, and the ppermute
for chunk t+1 overlaps the matmuls for chunk t in XLA's schedule.

Composes with the other mesh axes: inside ``shard_map`` the block math is
purely local over ``data`` (batch) and ``model`` (heads), so the same
function runs on any data x seq x model mesh.  Drop-in for
``ops.attention.causal_attention`` via ``llama.forward_full(attn_fn=...)``;
``training.make_train_step(..., mesh=...)`` selects it when the mesh has a
nontrivial ``seq`` axis and config asks for it.

The reference has no model execution at all (its "long context" concern is
prompt-size config, reference internal/config/config.go:94); this is part of
the new TPU serving/training obligation.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from k8s_llm_monitor_tpu.ops.attention import NEG_INF, _repeat_kv

from jax.sharding import Mesh, PartitionSpec as P



def _block_update(q, k, v, q_pos, kv_pos, kv_len, m, l, acc):
    """Fold one K/V chunk into the online-softmax state.

    q: [b, sq, h, d]; k/v: [b, sk, kvh, d] (GQA: kvh divides h); q_pos:
    [b, sq]; kv_pos: [sk]; kv_len: [b] or None; m/l: [b, h, sq, 1];
    acc: [b, sq, h, d] (f32).
    """
    k = _repeat_kv(k, q.shape[2] // k.shape[2])
    v = _repeat_kv(v, q.shape[2] // v.shape[2])
    scale = 1.0 / (q.shape[-1] ** 0.5)
    logits = jnp.einsum("bshd,bthd->bhst", q.astype(jnp.float32),
                        k.astype(jnp.float32)) * scale       # [b, h, sq, sk]
    causal = q_pos[:, :, None] >= kv_pos[None, None, :]       # [b, sq, sk]
    if kv_len is not None:
        causal = causal & (kv_pos[None, None, :] < kv_len[:, None, None])
    logits = jnp.where(causal[:, None, :, :], logits, NEG_INF)

    m_new = jnp.maximum(m, jnp.max(logits, axis=-1, keepdims=True))
    # Fully-masked-so-far rows keep m == NEG_INF; exponentiate against 0 so
    # they contribute exact zeros instead of NaNs.
    m_safe = jnp.where(m_new <= NEG_INF, 0.0, m_new)
    p = jnp.exp(logits - m_safe)                              # [b, h, sq, sk]
    alpha = jnp.exp(jnp.where(m <= NEG_INF, NEG_INF, m - m_safe))
    l_new = alpha * l + jnp.sum(p, axis=-1, keepdims=True)
    pv = jnp.einsum("bhst,bthd->bshd", p, v.astype(jnp.float32))
    acc_new = alpha.transpose(0, 2, 1, 3) * acc + pv          # [b, sq, h, d]
    return m_new, l_new, acc_new


def make_ring_attention(mesh: Mesh, axis: str = "seq"):
    """Build a ``causal_attention``-compatible fn that rings over ``axis``.

    Returned signature: ``fn(q, k, v, *, q_positions=None, kv_len=None)``
    with q/k/v ``[B, S, H, D]`` where S is the *global* sequence (sharded
    over ``axis`` by GSPMD) and H may be sharded over ``model``.
    """
    n = mesh.shape[axis]

    def local(q, k, v, q_pos, kv_len):
        # Shapes here are per-device shards.
        b, s_loc, h, d = q.shape
        idx = jax.lax.axis_index(axis)

        m = jnp.full((b, h, s_loc, 1), NEG_INF, jnp.float32)
        l = jnp.zeros((b, h, s_loc, 1), jnp.float32)
        acc = jnp.zeros((b, s_loc, h, d), jnp.float32)

        kv = (k, v)
        for step in range(n):
            src = (idx - step) % n                 # owner of the visiting kv
            kv_pos = src * k.shape[1] + jnp.arange(k.shape[1],
                                                   dtype=jnp.int32)
            m, l, acc = _block_update(q, kv[0], kv[1], q_pos, kv_pos,
                                      kv_len, m, l, acc)
            if step + 1 < n:
                kv = jax.lax.ppermute(
                    kv, axis, perm=[(i, (i + 1) % n) for i in range(n)])

        out = acc / jnp.maximum(l.transpose(0, 2, 1, 3), 1e-30)
        return out.astype(q.dtype)

    def ring_attention(q, k, v, *, q_positions=None, kv_len=None):
        if n == 1:
            from k8s_llm_monitor_tpu.ops.attention import causal_attention

            return causal_attention(q, k, v, q_positions=q_positions,
                                    kv_len=kv_len)
        B, S = q.shape[0], q.shape[1]
        T = k.shape[1]
        if q_positions is None:
            q_positions = jnp.broadcast_to(
                jnp.arange(S, dtype=jnp.int32)[None, :] + (T - S), (B, S))
        if kv_len is None:
            kv_len = jnp.full((B,), T, jnp.int32)
        qkv_spec = P("data", axis, "model", None)
        fn = jax.shard_map(
            local, mesh=mesh,
            in_specs=(qkv_spec, qkv_spec, qkv_spec,
                      P("data", axis), P("data")),
            out_specs=qkv_spec,
        )
        return fn(q, k, v, q_positions, kv_len)

    return ring_attention
