"""Learned sparse attention: the indexer's scores and the top-k selection.

A full-attention layer with an indexer (``LatentGeometry.indexed``) lets a
query at position t see the ``index_topk`` earlier keys its indexer scores
highest, ``I(t, s) = sum_j w_j relu(qI_j . kI_s)`` over the indexer's heads,
all of them when there are no more than that; ties go to the lower s.  This
file holds the XLA forms (the CPU path and the oracles of the kernels in
ops/pallas_attention.py), the selection itself — the k-th largest score of a
row is found by 32 counting passes over an order-preserving integer view of
the float32 scores, never by a sort (XLA here; at admission a kernel that
keeps a block of rows in VMEM for all 32).  Selected attention has one form,
the *mask form*: every page of a lane is read and the unselected keys are
dropped before the softmax.  A *gather form* (fetch the selected rows alone)
is reckoned in PERF.md section 6 (PR 33) and not built; a predicate on the
call's shapes (as ops/grouped.py:product_form) comes with it, from a
measurement of both.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

NEG_INF = -1e30

def index_scores(q: jnp.ndarray, w: jnp.ndarray,
                 keys: jnp.ndarray) -> jnp.ndarray:
    """The indexer's scores, XLA form.  q [B, S, Hi, Di] (rotated), w
    [B, S, Hi] float32 (the heads' weights, scaled), keys [B, T, Di] ->
    [B, S, T] float32: ``sum_j w_j relu(q_j . key)``.  Operands stay in
    their dtype; products accumulate in float32."""
    s = jnp.einsum("bshd,btd->bsht", q, keys,
                   preferred_element_type=jnp.float32)
    return jnp.sum(jax.nn.relu(s) * w[..., None], axis=2)


_INT_MIN = -2 ** 31


def ordered_keys(scores: jnp.ndarray, allowed: jnp.ndarray) -> jnp.ndarray:
    """float32 scores -> int32 keys with the same order (-0.0 below +0.0);
    a key that is not ``allowed`` gets the least int32, below every score."""
    bits = jax.lax.bitcast_convert_type(scores.astype(jnp.float32), jnp.int32)
    signed = jnp.where(bits < 0, bits ^ jnp.int32(0x7FFFFFFF), bits)
    return jnp.where(allowed, jnp.maximum(signed, _INT_MIN + 1),
                     jnp.int32(_INT_MIN))


def kth_largest(keys: jnp.ndarray, k: int) -> jnp.ndarray:
    """The k-th largest of each row of int32 ``keys`` [..., T] (the least
    int32 where a row has fewer than k above it), built bit by bit from the
    top: 32 passes that each count the keys at or above a candidate — no row
    is sorted.  The XLA form (decode steps, the CPU) of
    ops/pallas_attention.py:kth_largest_pallas."""
    top = jnp.int32(_INT_MIN)

    def bit(i, thr):      # thr: the threshold's bits, offset by 2**31
        cand = thr | jnp.left_shift(jnp.int32(1), jnp.int32(31) - i)
        enough = jnp.sum(keys >= (cand ^ top)[..., None], axis=-1,
                         dtype=jnp.int32) >= k
        return jnp.where(enough, cand, thr)

    thr = jax.lax.fori_loop(0, 32, bit, jnp.zeros(keys.shape[:-1], jnp.int32))
    return thr ^ top


def keep_from_threshold(keys: jnp.ndarray, thr: jnp.ndarray,
                        k: int) -> jnp.ndarray:
    """bool [..., T]: the keys above each row's threshold and, of those equal
    to it, the first (lowest index) that fill the row up to ``k``.  The
    running count that ranks equal keys is only computed when some row has
    more of them than it has room for: with real-valued scores the k-th key
    is alone."""
    live = keys > jnp.int32(_INT_MIN)
    above = keys > thr[..., None]
    tie = (keys == thr[..., None]) & live
    room = k - jnp.sum(above, axis=-1, dtype=jnp.int32)
    crowded = jnp.any(jnp.sum(tie, axis=-1, dtype=jnp.int32) > room)

    def ranked(_):
        first = jnp.cumsum(tie, axis=-1, dtype=jnp.int32) <= room[..., None]
        return above | (tie & first)

    return jax.lax.cond(crowded, ranked, lambda _: above | tie, None)


def topk_keep(scores: jnp.ndarray, allowed: jnp.ndarray,
              k: int) -> jnp.ndarray:
    """The selection: bool [..., T], True on the ``k`` highest-scored of the
    ``allowed`` keys of each row (all of them when there are no more than
    k); among equal scores the lower index wins."""
    keys = ordered_keys(scores, allowed)
    return keep_from_threshold(keys, kth_largest(keys, k), k)


def masked_attention(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
                     keep: jnp.ndarray, *, scale: float) -> jnp.ndarray:
    """Dense attention under a key mask, XLA form (the CPU path and the
    oracle of the kernels).  q, k [B, S, H, Dk], v [B, S, H, Dv], keep
    [B, S, S] bool (query x key) -> [B, S, H, Dv] in q.dtype.  A query that
    may see nothing (padding) comes back finite and meaningless."""
    logits = jnp.einsum("bshd,bthd->bhst", q.astype(jnp.float32),
                        k.astype(jnp.float32)) * scale
    logits = jnp.where(keep[:, None], logits, NEG_INF)
    probs = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("bhst,bthd->bshd", probs, v.astype(jnp.float32))
    return out.astype(q.dtype)


def allowed_keys(q_positions: jnp.ndarray, kv_len: jnp.ndarray, T: int,
                 window: int = 0) -> jnp.ndarray:
    """bool [B, S, T]: key t is allowed to query s — no later than it, of
    the sequence's ``kv_len`` tokens and, under a window, one of the last
    ``window`` positions (``q - t < window``)."""
    t = jnp.arange(T, dtype=jnp.int32)[None, None, :]
    ok = (t <= q_positions[:, :, None]) & (t < kv_len[:, None, None])
    if window:
        ok = ok & (q_positions[:, :, None] - t < window)
    return ok
