"""Attention primitives: dense causal prefill, contiguous-cache decode, and
paged-KV decode.

All variants are GQA-aware (``num_heads`` query heads grouped over
``num_kv_heads`` KV heads) and run the softmax in float32.

Layout conventions (chosen for TPU):
  activations  [batch, seq, heads, head_dim]
  paged KV     [num_blocks, block_size, kv_heads * head_dim] — the fused
               lane layout of models/llama.py:KVPages (128-lane-aligned
               page rows the Pallas kernel DMAs directly)
  block table  [batch, max_blocks_per_seq] int32 (block ids; entries past a
               sequence's pages are 0, the reserved null block)

The pure-XLA paged path here is the reference implementation and the CPU/test
fallback; the Pallas TPU kernel lives in ops/pallas_attention.py and is
selected by ``select_attn_impl`` (used by serving/engine.py).
"""

from __future__ import annotations

import functools
import logging

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from k8s_llm_monitor_tpu.ops import sparse
from k8s_llm_monitor_tpu.ops.pallas_attention import (
    flash_prefill_attention,
    index_scores_decode_pallas,
    latent_decode_attention_pallas,
    latent_prefill_attention_pallas,
    paged_decode_attention_fused,
    paged_decode_attention_fused_quant,
    paged_decode_attention_pallas,
    paged_verify_attention_pallas,
)

logger = logging.getLogger("k8s_llm_monitor_tpu.ops")

NEG_INF = -0.7 * float(jnp.finfo(jnp.float32).max)


def _repeat_kv(x: jnp.ndarray, q_per_kv: int) -> jnp.ndarray:
    """[..., kv_heads, d] -> [..., kv_heads * q_per_kv, d]."""
    if q_per_kv == 1:
        return x
    return jnp.repeat(x, q_per_kv, axis=-2)


def causal_attention(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    *,
    q_positions: jnp.ndarray | None = None,
    kv_len: jnp.ndarray | None = None,
    scale: float | None = None,
    logit_softcap: float = 0.0,
    window: int = 0,
) -> jnp.ndarray:
    """Dense causal attention for prefill.

    Args:
      q: [B, S, H, D].
      k, v: [B, T, KVH, D] with T >= S (T may include a cached prefix).
      q_positions: [B, S] absolute position of each query token; defaults to
        arange(S) + (T - S) (i.e. queries are the last S positions of kv).
      kv_len: [B] valid kv length per sequence (keys at index >= kv_len are
        masked out).  Defaults to T.
      scale: query scale; defaults to D**-0.5 (Gemma-2 uses
        query_pre_attn_scalar**-0.5 instead).
      logit_softcap: tanh soft cap on attention logits (Gemma-2; 0 = off).
      window: sliding-window size — queries attend only to keys within the
        last ``window`` positions (0 = global).  Static per call/layer.

    Returns:
      [B, S, H, D] in q.dtype.
    """
    B, S, H, D = q.shape
    T, KVH = k.shape[1], k.shape[2]
    q_per_kv = H // KVH

    k = _repeat_kv(k, q_per_kv)
    v = _repeat_kv(v, q_per_kv)

    if scale is None:
        scale = 1.0 / (D ** 0.5)
    logits = jnp.einsum("bshd,bthd->bhst", q.astype(jnp.float32), k.astype(jnp.float32))
    logits *= scale
    if logit_softcap:
        logits = logit_softcap * jnp.tanh(logits / logit_softcap)

    if q_positions is None:
        q_positions = jnp.arange(S, dtype=jnp.int32)[None, :] + (T - S)
        q_positions = jnp.broadcast_to(q_positions, (B, S))
    kv_positions = jnp.arange(T, dtype=jnp.int32)
    causal = q_positions[:, :, None] >= kv_positions[None, None, :]  # [B, S, T]
    if kv_len is not None:
        causal = causal & (kv_positions[None, None, :] < kv_len[:, None, None])
    if window > 0:
        causal = causal & (kv_positions[None, None, :]
                           > q_positions[:, :, None] - window)
    logits = jnp.where(causal[:, None, :, :], logits, NEG_INF)

    probs = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("bhst,bthd->bshd", probs, v.astype(jnp.float32))
    return out.astype(q.dtype)


def decode_attention(
    q: jnp.ndarray,
    k_cache: jnp.ndarray,
    v_cache: jnp.ndarray,
    lengths: jnp.ndarray,
    *,
    scale: float | None = None,
    logit_softcap: float = 0.0,
    window: int = 0,
) -> jnp.ndarray:
    """Single-token decode against a contiguous KV cache.

    Args:
      q: [B, 1, H, D].
      k_cache, v_cache: [B, T, KVH, D].
      lengths: [B] int32 — number of valid KV entries per sequence (the new
        token's K/V must already be written at index lengths-1).
      scale / logit_softcap / window: as in ``causal_attention`` (the
      query position is lengths-1, so the window keeps keys in
      ``(lengths-1-window, lengths)``).
    """
    B, _, H, D = q.shape
    T, KVH = k_cache.shape[1], k_cache.shape[2]
    q_per_kv = H // KVH

    k = _repeat_kv(k_cache, q_per_kv)
    v = _repeat_kv(v_cache, q_per_kv)

    if scale is None:
        scale = 1.0 / (D ** 0.5)
    logits = jnp.einsum("bshd,bthd->bhst", q.astype(jnp.float32), k.astype(jnp.float32))
    logits *= scale
    if logit_softcap:
        logits = logit_softcap * jnp.tanh(logits / logit_softcap)
    kv_positions = jnp.arange(T, dtype=jnp.int32)[None, :]
    valid = kv_positions < lengths[:, None]                          # [B, T]
    if window > 0:
        valid = valid & (kv_positions > (lengths - 1)[:, None] - window)
    logits = jnp.where(valid[:, None, None, :], logits, NEG_INF)
    probs = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("bhst,bthd->bshd", probs, v.astype(jnp.float32))
    return out.astype(q.dtype)


def gather_pages(
    pages: jnp.ndarray, block_table: jnp.ndarray
) -> jnp.ndarray:
    """Gather a sequence's KV pages into a contiguous view.

    Args:
      pages: [num_blocks, block_size, KVH*D] (fused lane layout — see
        models/llama.py:KVPages).
      block_table: [B, max_blocks] int32 (entries may be -1 / garbage past the
        sequence's length — callers mask by length).

    Returns:
      [B, max_blocks * block_size, KVH*D].
    """
    B, max_blocks = block_table.shape
    bs = pages.shape[1]
    safe = jnp.maximum(block_table, 0)
    g = pages[safe]  # [B, max_blocks, bs, KVH*D]
    return g.reshape(B, max_blocks * bs, g.shape[3])


def paged_decode_attention(
    q: jnp.ndarray,
    k_pages: jnp.ndarray,
    v_pages: jnp.ndarray,
    block_table: jnp.ndarray,
    lengths: jnp.ndarray,
    *,
    scale: float | None = None,
    logit_softcap: float = 0.0,
    window: int = 0,
) -> jnp.ndarray:
    """Single-token decode against a paged (block) KV cache — XLA reference.

    Gathers each sequence's blocks into a contiguous [B, max_blocks*bs, F]
    view then runs masked decode attention (unfusing F -> [KVH, D] on the
    gathered activation only).  The Pallas kernel avoids the gather by
    streaming pages HBM->VMEM per block; this version is the semantics
    reference, the CPU fallback, and the only impl carrying the Gemma-2
    extras (custom scale / logit softcap / sliding window).
    """
    B = q.shape[0]
    D = q.shape[-1]
    k = gather_pages(k_pages, block_table).reshape(B, -1, k_pages.shape[2] // D, D)
    v = gather_pages(v_pages, block_table).reshape(B, -1, v_pages.shape[2] // D, D)
    return decode_attention(q, k, v, lengths, scale=scale,
                            logit_softcap=logit_softcap, window=window)


def paged_decode_attention_quant(
    q: jnp.ndarray,
    k_pages: jnp.ndarray,
    v_pages: jnp.ndarray,
    k_scale: jnp.ndarray,
    v_scale: jnp.ndarray,
    block_table: jnp.ndarray,
    lengths: jnp.ndarray,
    *,
    scale: float | None = None,
    logit_softcap: float = 0.0,
    window: int = 0,
) -> jnp.ndarray:
    """Quantized-KV twin of ``paged_decode_attention`` — XLA reference.

    ``k_pages``/``v_pages`` hold int8/fp8 rows; ``k_scale``/``v_scale``
    are the per-(token, head) float32 scales [num_blocks, bs, KVH]
    (models/llama.py:KVPages).  Scales are gathered alongside the pages
    and applied on the small gathered activation — dequantize-on-read,
    so the resident pool never materializes in float.  Under a GSPMD
    mesh this partitions automatically when pages and scales both shard
    their kv-head axis (parallel/sharding.py emits matching specs), which
    is why the mesh path needs no quant-aware shard_map kernel.
    """
    B = q.shape[0]
    D = q.shape[-1]
    KVH = k_pages.shape[2] // D
    ks = gather_pages(k_scale, block_table)            # [B, T, KVH]
    vs = gather_pages(v_scale, block_table)
    k = (gather_pages(k_pages, block_table).astype(jnp.float32)
         .reshape(B, -1, KVH, D) * ks[..., None])
    v = (gather_pages(v_pages, block_table).astype(jnp.float32)
         .reshape(B, -1, KVH, D) * vs[..., None])
    return decode_attention(q, k.astype(q.dtype), v.astype(q.dtype),
                            lengths, scale=scale,
                            logit_softcap=logit_softcap, window=window)


def blockwise_attention(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    *,
    q_positions: jnp.ndarray,
    kv_len: jnp.ndarray,
    scale: float,
    block_q: int = 512,
    block_k: int = 512,
) -> jnp.ndarray:
    """Causal attention by blocks with an online softmax, in XLA operations:
    no ``[S, T]`` score tensor is ever held, and key blocks a query block
    cannot see are not visited (a dynamic trip count: serving only).

    Args:
      q: [B, S, H, Dk].
      k: [B, T, Hk, Dk], v: [B, T, Hk, Dv] with ``Hk == H`` (the expanded
        latent form: per-head keys of nope + rope width, values of their own
        width) or ``Hk == 1`` (one row shared by all heads: the absorbed
        form over gathered latent pages).
      q_positions: [B, S] absolute position of each query; key ``t`` is seen
        by the query at position ``p`` when ``t <= p`` and ``t < kv_len[b]``.
      kv_len: [B] valid keys.
      scale: multiplies the scores.

    Returns:
      [B, S, H, Dv] in q.dtype.  Operands stay in their dtype; products
      accumulate and the softmax runs in float32.
    """
    B, S, H, _ = q.shape
    T, Hk, Dv = k.shape[1], k.shape[2], v.shape[-1]
    bq, bk = min(block_q, S), min(block_k, T)
    nq, nk = -(-S // bq), -(-T // bk)
    if nq * bq != S:
        q = jnp.pad(q, ((0, 0), (0, nq * bq - S), (0, 0), (0, 0)))
        q_positions = jnp.pad(q_positions, ((0, 0), (0, nq * bq - S)),
                              constant_values=-1)
    if nk * bk != T:
        pad = ((0, 0), (0, nk * bk - T), (0, 0), (0, 0))
        k, v = jnp.pad(k, pad), jnp.pad(v, pad)
    # One shared row: contract without a head axis on the key side.
    qk = "bshd,btd->bhst" if Hk == 1 else "bshd,bthd->bhst"
    pv = "bhst,btd->bhsd" if Hk == 1 else "bhst,bthd->bhsd"
    if Hk == 1:
        k, v = k[:, :, 0], v[:, :, 0]
    nk_live = jnp.minimum(nk, (jnp.max(kv_len) + bk - 1) // bk)

    def q_block(i):
        qi = jax.lax.dynamic_slice_in_dim(q, i * bq, bq, axis=1)
        pi = jax.lax.dynamic_slice_in_dim(q_positions, i * bq, bq, axis=1)
        hi = jnp.clip((jnp.max(pi) + bk) // bk, 1, jnp.maximum(nk_live, 1))

        def kv_block(j, carry):
            m, l, acc = carry
            kj = jax.lax.dynamic_slice_in_dim(k, j * bk, bk, axis=1)
            vj = jax.lax.dynamic_slice_in_dim(v, j * bk, bk, axis=1)
            s = jnp.einsum(qk, qi, kj,
                           preferred_element_type=jnp.float32) * scale
            tpos = j * bk + jnp.arange(bk, dtype=jnp.int32)
            seen = ((tpos[None, None, :] <= pi[:, :, None])
                    & (tpos[None, None, :] < kv_len[:, None, None]))
            s = jnp.where(seen[:, None], s, NEG_INF)
            m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
            alpha = jnp.exp(m - m_new)
            p = jnp.exp(s - m_new)
            l = alpha * l + jnp.sum(p, axis=-1, keepdims=True)
            acc = alpha * acc + jnp.einsum(
                pv, p.astype(vj.dtype), vj,
                preferred_element_type=jnp.float32)
            return m_new, l, acc

        init = (jnp.full((B, H, bq, 1), NEG_INF, jnp.float32),
                jnp.zeros((B, H, bq, 1), jnp.float32),
                jnp.zeros((B, H, bq, Dv), jnp.float32))
        _, l, acc = jax.lax.fori_loop(0, hi, kv_block, init)
        return (acc / l).astype(q.dtype)                  # [B, H, bq, Dv]

    out = jax.lax.map(q_block, jnp.arange(nq, dtype=jnp.int32))
    out = out.transpose(1, 0, 3, 2, 4).reshape(B, nq * bq, H, Dv)
    return out[:, :S]


def latent_decode_attention(
    q: jnp.ndarray,
    pages: jnp.ndarray,
    block_table: jnp.ndarray,
    lengths: jnp.ndarray,
    *,
    v_width: int,
    keep: jnp.ndarray | None = None,
    name: str = "",
    burst: int = 0,
) -> jnp.ndarray:
    """Single-token decode over a latent pool, absorbed form — XLA reference
    (the CPU path, and the oracle of the Pallas kernel).

    q: [B, 1, H, F] absorbed and scaled queries; pages: [num_blocks, bs, F]
    rows ``[latent | rotated key | zeros]``; the value of a row is its first
    ``v_width`` lanes.  ``keep`` [B, T] bool: the selected keys of each lane
    (the mask form of selected attention: every row is read, the unselected
    are dropped before the softmax); ``name`` and ``burst`` are the kernel's,
    unused here.
    Returns [B, 1, H, v_width] (``P c``, before W_UV).
    """
    del name, burst
    rows = gather_pages(pages, block_table).astype(jnp.float32)   # [B, T, F]
    logits = jnp.einsum("bshf,btf->bhst", q.astype(jnp.float32), rows)
    seen = (jnp.arange(rows.shape[1], dtype=jnp.int32)[None, :]
            < lengths[:, None])
    if keep is not None:
        seen = seen & keep[:, :rows.shape[1]]
    logits = jnp.where(seen[:, None, None, :], logits, NEG_INF)
    probs = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("bhst,btr->bshr", probs, rows[..., :v_width])
    return out.astype(q.dtype)


def index_scores_decode(q: jnp.ndarray, w: jnp.ndarray, pages: jnp.ndarray,
                        block_table: jnp.ndarray,
                        lengths: jnp.ndarray) -> jnp.ndarray:
    """The indexer's scores of one decode step against every cached index
    key — XLA reference (the CPU path, and the oracle of the Pallas kernel).
    q [B, 1, Hi, Di], w [B, 1, Hi] float32, pages [num_blocks, bs, Di] (the
    index-key pages), block_table [B, NB] -> [B, NB * bs] float32; what lies
    at or past a lane's ``lengths`` is meaningless (the selection masks
    it)."""
    del lengths
    return sparse.index_scores(q, w, gather_pages(pages, block_table))[:, 0]


latent_decode_attention.latent = True


def paged_verify_attention(
    q: jnp.ndarray,
    k_pages: jnp.ndarray,
    v_pages: jnp.ndarray,
    block_table: jnp.ndarray,
    start: jnp.ndarray,
    lengths: jnp.ndarray,
) -> jnp.ndarray:
    """Multi-query paged attention — XLA gather reference for the Pallas
    verify kernel (speculative decode's k+1-token scoring pass).

    Query token ``i`` sits at absolute position ``start[b] + i`` and
    attends causally through itself over the gathered pages.  ``lengths``
    counts valid query tokens (0 = inactive lane; output rows garbage,
    discarded by the caller).  A thin wrapper over gather_pages +
    causal_attention so the serving path and this Pallas-parity reference
    can never drift apart.
    """
    B, S, H, D = q.shape
    KVH = k_pages.shape[2] // D
    kk = gather_pages(k_pages, block_table).reshape(B, -1, KVH, D)
    vv = gather_pages(v_pages, block_table).reshape(B, -1, KVH, D)
    positions = start[:, None] + jnp.arange(S, dtype=jnp.int32)[None, :]
    return causal_attention(q, kk, vv, q_positions=positions,
                            kv_len=start + lengths)


# Table width (tokens) above which the Pallas verify kernel beats the XLA
# gather for the spec verify pass.  The gather reads the FULL static table
# width per lane per layer, so its cost is O(max_blocks*bs) regardless of
# live context; the kernel streams only real pages but serializes its
# batch tile per program.  Measured on v5e-1 / 8B int8 spec decode:
# 336-token tables gather wins (235 vs 175 tok/s); 2048-token tables the
# kernel wins (76 vs 72 tok/s) and its margin grows with table width.
VERIFY_KERNEL_MIN_TABLE_TOKENS = 2048


def select_verify_impl(platform: str | None = None, cfg=None, mesh=None,
                       max_table_tokens: int | None = None):
    """Pick the verify (multi-query paged) attention implementation.

    Mirrors ``select_attn_impl``: single-chip TPU with kernel-compatible
    geometry gets the Pallas verify kernel; meshes and CPU get the XLA
    gather reference (which partitions under GSPMD automatically).
    ``max_table_tokens`` (the engine's per-seq capacity) gates the kernel
    to long-table configs where its O(real ctx) streaming beats the
    gather's O(table width) reads.
    Returns a callable (q, k_pages, v_pages, table, start, lengths).
    """
    if platform is None:
        platform = jax.default_backend()
    if cfg is not None and getattr(cfg, "has_attn_extras", False):
        # Extras models use _prefill_impl's own gather branch, which
        # threads the per-layer parameters (models/llama.py).
        return None
    if mesh is not None or platform != "tpu":
        return paged_verify_attention
    if (max_table_tokens is not None
            and max_table_tokens < VERIFY_KERNEL_MIN_TABLE_TOKENS):
        return paged_verify_attention
    if cfg is not None and not _pallas_geometry_ok(cfg, 1):
        logger.warning(
            "Pallas verify kernel unavailable for %s (geometry gate); "
            "speculative verify uses the XLA gather fallback",
            getattr(cfg, "name", "model"))
        return paged_verify_attention
    return paged_verify_attention_pallas


def make_tp_paged_attention(mesh, cfg, interpret: bool = False):
    """Pallas paged decode attention under a GSPMD mesh, via ``shard_map``.

    Paged decode attention is embarrassingly tensor-parallel when the KV
    pages shard on kv-head boundaries (parallel/sharding.py): every query
    head's output depends only on its own kv group's pages, so each device
    runs the kernel on its local head/page shard and NO collective is
    needed — the sharded outputs are exactly the sharded o-projection
    inputs.  Requires ``tp | num_kv_heads`` (the same condition under which
    the pages shard at all); the block-diagonal GQA trick is per-kv-group
    and group boundaries align with the shard cuts.

    ``interpret`` runs the kernel in the Pallas interpreter per shard — the
    CPU-mesh path used by tests and the driver's virtual-device dryrun.
    """
    qspec = P(None, None, "model", None)       # query heads over TP
    pspec = P(None, None, "model")             # fused kv lanes over TP

    @functools.partial(
        jax.shard_map, mesh=mesh,
        in_specs=(qspec, pspec, pspec, P(None, None), P(None)),
        out_specs=qspec, check_vma=False)
    def attn(q, k_pages, v_pages, block_table, lengths):
        return paged_decode_attention_pallas(
            q, k_pages, v_pages, block_table, lengths, interpret=interpret)

    return attn


def _pallas_geometry_ok(cfg, tp: int) -> bool:
    """Mosaic lane-alignment gate for the (per-shard) fused page rows."""
    fused_local = cfg.num_kv_heads * cfg.head_dim_ // tp
    return fused_local % 128 == 0 and cfg.head_dim_ <= 128


def select_attn_impl(platform: str | None = None, cfg=None, mesh=None):
    """Pick the paged-decode attention implementation for the backend.

    Single device on TPU gets the Pallas kernel (block-table-driven
    HBM->VMEM streaming, ops/pallas_attention.py); a GSPMD ``mesh`` gets
    the kernel wrapped in ``shard_map`` over the ``model`` axis (compiled
    on TPU, interpreter on the CPU-mesh test/dryrun path); everything else
    gets the XLA gather fallback above.

    ``cfg`` (a ModelConfig) gates on kernel geometry: the kernel DMAs pages
    as [block_size, kv_heads*head_dim] rows, and Mosaic requires that fused
    lane dim to be 128-aligned (and head_dim <= 128).  Models that fail the
    gate (tiny test configs) get the XLA path with a logged warning — never
    a silent compile-time crash or a quiet performance cliff.
    """
    if platform is None:
        platform = jax.default_backend()

    if cfg is not None and getattr(cfg, "has_attn_extras", False):
        # Gemma-2-style extras (query scale / softcap / sliding window)
        # live only in the gather reference; the Pallas kernel has no
        # cap/window support (Gemma's head_dim=256 fails its geometry
        # gate anyway).
        return paged_decode_attention

    if mesh is not None:
        tp = mesh.shape.get("model", 1)
        if cfg is None or tp < 1 or cfg.num_kv_heads % tp != 0:
            # Pages replicate in this regime (see kv_pages_partition_specs);
            # the gather fallback partitions under GSPMD automatically.
            if cfg is not None:
                logger.warning(
                    "TP=%d does not divide %d KV heads; paged attention "
                    "uses the XLA gather fallback with replicated pages",
                    tp, cfg.num_kv_heads)
            return paged_decode_attention
        interpret = platform != "tpu"
        if not interpret and not _pallas_geometry_ok(cfg, tp):
            logger.warning(
                "Pallas kernel geometry gate failed for %s at TP=%d "
                "(per-shard fused lanes not 128-aligned); using the XLA "
                "gather fallback", getattr(cfg, "name", "model"), tp)
            return paged_decode_attention
        return make_tp_paged_attention(mesh, cfg, interpret=interpret)

    if platform != "tpu":
        return paged_decode_attention
    if cfg is not None and not _pallas_geometry_ok(cfg, 1):
        logger.warning(
            "Pallas paged-attention kernel unavailable for %s "
            "(kv_heads*head_dim=%d not 128-aligned or head_dim>128); "
            "using the XLA gather fallback — O(B*max_ctx) HBM traffic "
            "per decode step", getattr(cfg, "name", "model"),
            cfg.num_kv_heads * cfg.head_dim_)
        return paged_decode_attention
    return paged_decode_attention_pallas


def select_index_scores_impl(platform: str | None = None,
                             mode: str = "auto"):
    """The indexer's decode-step scores for the backend and
    ``EngineConfig.decode_path``, as ``select_decode_impl`` picks the latent
    attention beside it: the Pallas kernel over the index-key pages on a TPU
    (through the interpreter elsewhere when ``mode`` asks for it), the XLA
    form otherwise."""
    if platform is None:
        platform = jax.default_backend()
    if mode == "gather" or (mode == "auto" and platform != "tpu"):
        return index_scores_decode
    if platform != "tpu":
        return functools.partial(index_scores_decode_pallas, interpret=True)
    return index_scores_decode_pallas


def select_decode_impl(platform: str | None = None, cfg=None, mesh=None,
                       mode: str = "auto", kv_quant: str = ""):
    """Pick the decode-step attention path, including the fused fast-path.

    ``mode`` (EngineConfig.decode_path):
      * ``"auto"``   — the fused RoPE+append+attention kernel
        (ops/pallas_attention.py:paged_decode_attention_fused) on a
        single TPU chip when the model passes the geometry gate;
        otherwise whatever ``select_attn_impl`` picks.
      * ``"fused"``  — force the fused kernel (interpreter off-TPU; used
        by parity tests).  Raises if the model can't take it (extras
        models, odd head_dim) rather than silently falling back — the
        caller asked for a specific path.
      * ``"gather"`` — force the XLA gather fallback (the numerics
        oracle; also what the fused path is diffed against in tests).
      * ``"pallas"`` — force the split kernel pipeline (Pallas attention
        with the XLA rope/scatter around it).

    ``kv_quant`` ("int8"/"fp8", EngineConfig.kv_dtype) selects the
    quantized-KV tier: the fused fast-path becomes the quantized fused
    kernel (quantize-on-append + dequantize-in-kernel, marked
    ``is_fused_quant_decode_impl``); the split "pallas" pipeline has no
    scale support and degrades to the gather/dequant reference with a
    warning.  Non-fused returns are sentinels only — decode_step routes a
    quantized pool through its own gather/dequant branch.

    Returns an attention impl for models/llama.py:decode_step; fused
    impls are marked (``is_fused_decode_impl``) and use the extended
    calling convention (raw q/k/v + angles in, pages out).
    """
    if platform is None:
        platform = jax.default_backend()

    if cfg is not None and getattr(cfg, "latent", False):
        # A latent pool has one page kind and one kernel: the absorbed-form
        # decode attention over ``[latent | rotated key]`` rows.  Nothing
        # else can read those pages, so what is not built raises.
        if mesh is not None:
            raise ValueError(
                "latent attention is not built for a mesh: the pool has no "
                "kv-head axis to shard (ROADMAP Queue 2)")
        if kv_quant:
            raise ValueError(
                f"latent pages are not built for kv_dtype={kv_quant!r}: the "
                "latent kernel has no scale planes (ROADMAP Queue 2)")
        if mode == "gather" or (mode == "auto" and platform != "tpu"):
            return latent_decode_attention
        if mode in ("auto", "pallas"):
            if platform != "tpu":      # CPU tests: the Pallas interpreter
                impl = functools.partial(latent_decode_attention_pallas,
                                         interpret=True)
                impl.latent = True
                return impl
            return latent_decode_attention_pallas
        raise ValueError(
            f"decode_path {mode!r} does not exist for latent attention "
            "(auto | pallas | gather): rope and append are not fused into "
            "its kernel")

    def _fused_ok():
        return (mesh is None
                and cfg is not None
                and not getattr(cfg, "has_attn_extras", False)
                and cfg.head_dim_ % 2 == 0
                and _pallas_geometry_ok(cfg, 1))

    def _fused():
        impl = (paged_decode_attention_fused_quant if kv_quant
                else paged_decode_attention_fused)
        if platform != "tpu":      # CPU tests: the Pallas interpreter
            return functools.partial(impl, interpret=True)
        return impl

    if mode == "gather":
        return paged_decode_attention
    if mode == "pallas":
        if kv_quant:
            logger.warning(
                "decode_path='pallas' has no quantized-KV support; the "
                "split kernel is bypassed for the gather/dequant reference")
            return paged_decode_attention
        return select_attn_impl(platform, cfg=cfg, mesh=mesh)
    if mode == "fused":
        if not _fused_ok():
            raise ValueError(
                "decode_path='fused' but the model/mesh can't take the "
                "fused kernel (mesh, attn extras, odd head_dim, or lane "
                "alignment); use decode_path='auto' for gated selection")
        return _fused()
    if mode != "auto":
        raise ValueError(f"unknown decode_path {mode!r}; expected "
                         "'auto', 'fused', 'gather', or 'pallas'")

    if platform == "tpu" and _fused_ok():
        return _fused()
    if kv_quant:
        # Mesh or gather regime: decode_step's quant branch gathers pages
        # AND scales (paged_decode_attention_quant) — GSPMD partitions it
        # when both shard their kv-head axis.
        return paged_decode_attention
    return select_attn_impl(platform, cfg=cfg, mesh=mesh)


def make_tp_flash_prefill(mesh, cfg, interpret: bool = False,
                          kv_quant: str = ""):
    """Flash paged prefill under a GSPMD mesh, via ``shard_map``.

    Same TP story as ``make_tp_paged_attention``: the pages shard on
    kv-head boundaries, page ids stay GLOBAL (every chip reads the same
    block-table rows and its own head-slice of each page), queries shard
    their head axis, and no collective is needed — each shard's kernel
    output is exactly its o-projection input.  The per-shard kernel sees
    KVH/tp groups and H/tp heads, so the heads-per-group ratio (and the
    group-major q reshape) is invariant under the split.

    ``kv_quant`` adds the scale planes, sharded exactly with the pages
    (SpecLayout.kv_scales: the kv-heads axis splits when the fused lane
    dim does).
    """
    qspec = P(None, None, "model", None)       # query heads over TP
    pspec = P(None, None, "model")             # fused kv lanes / scale heads
    tspec = P(None, None)                      # block tables: global ids

    if kv_quant:
        @functools.partial(
            jax.shard_map, mesh=mesh,
            in_specs=(qspec, pspec, pspec, pspec, pspec, tspec, P(None),
                      P(None)),
            out_specs=qspec, check_vma=False)
        def _attn_sharded(q, k_pages, v_pages, k_scale, v_scale, table,
                          start, lengths):
            return flash_prefill_attention(
                q, k_pages, v_pages, table, start, lengths,
                k_scale=k_scale, v_scale=v_scale, interpret=interpret)

        def attn(q, k_pages, v_pages, table, start, lengths, *,
                 k_scale, v_scale):
            return _attn_sharded(q, k_pages, v_pages, k_scale, v_scale,
                                 table, start, lengths)
    else:
        @functools.partial(
            jax.shard_map, mesh=mesh,
            in_specs=(qspec, pspec, pspec, tspec, P(None), P(None)),
            out_specs=qspec, check_vma=False)
        def _attn_sharded(q, k_pages, v_pages, table, start, lengths):
            return flash_prefill_attention(
                q, k_pages, v_pages, table, start, lengths,
                interpret=interpret)

        def attn(q, k_pages, v_pages, table, start, lengths):
            return _attn_sharded(q, k_pages, v_pages, table, start, lengths)

    attn.flash_prefill = True
    return attn


def select_prefill_impl(platform: str | None = None, cfg=None, mesh=None,
                        mode: str = "auto", kv_quant: str = ""):
    """Pick the prefill-family attention path (fresh / chunk / verify).

    ``mode`` (EngineConfig.prefill_path):
      * ``"auto"``  — the flash paged-prefill kernel
        (ops/pallas_attention.py:flash_prefill_attention) on TPU when the
        geometry passes; the dense XLA path everywhere else.
      * ``"flash"`` — force the kernel (interpreter off-TPU; parity tests
        and traceguard).  Raises when the model or mesh can't take it
        rather than silently falling back.
      * ``"dense"`` — force the dense XLA oracle: in-flight
        ``causal_attention`` for fresh prefill, ``gather_pages`` + dense
        attention for chunks and verify.

    ``kv_quant`` ("int8"/"fp8", EngineConfig.kv_dtype) only changes the
    mesh wrapper's signature — the kernel itself keys on the scale planes
    it is handed and dequantizes in-kernel, so the quantized pool never
    widens in HBM (the dense chunk path dequantizes the full gathered
    prefix instead).

    Returns ``None`` for the dense path (models/llama.py keeps its
    existing branches — the correctness oracle every flash output is
    tested against) or an impl marked ``is_flash_prefill_impl`` with the
    ``flash_prefill_attention`` calling convention.
    """
    if platform is None:
        platform = jax.default_backend()

    if mode not in ("auto", "flash", "dense"):
        raise ValueError(f"unknown prefill_path {mode!r}; expected "
                         "'auto', 'flash', or 'dense'")
    if cfg is not None and getattr(cfg, "latent", False):
        # The latent mixer has two prefill forms (models/llama.py): the
        # expanded one over a fresh batch's own tokens, which this impl
        # computes — the Pallas kernel ("flash"; "auto" on TPU) or, for
        # None, blockwise XLA operations ("dense"; "auto" elsewhere) — and
        # the absorbed one over pages (continuation chunks, prefix hits),
        # blockwise XLA operations either way.
        if mesh is not None:
            raise ValueError(
                "latent attention is not built for a mesh: the pool has no "
                "kv-head axis to shard (ROADMAP Queue 2)")
        if mode == "dense" or (mode == "auto" and platform != "tpu"):
            return None
        if platform != "tpu":          # CPU tests: the Pallas interpreter
            impl = functools.partial(latent_prefill_attention_pallas,
                                     interpret=True)
            impl.latent_prefill = True
            return impl
        return latent_prefill_attention_pallas
    if mode == "dense":
        return None

    tp = mesh.shape.get("model", 1) if mesh is not None else 1

    def _flash_ok():
        if cfg is None or getattr(cfg, "has_attn_extras", False):
            return False   # softcap / sliding window live only in dense
        if mesh is not None and (tp < 1 or cfg.num_kv_heads % tp != 0):
            return False   # pages replicate; dense partitions automatically
        if platform != "tpu":
            return True    # interpreter has no lane-alignment constraints
        # Hardware: the kernel DMAs each kv group's own D-lane slice of
        # the fused page rows, so the slice offset g*D must itself be
        # lane-aligned — head_dim must be exactly 128 on top of the
        # fused-row gate (the decode kernels avoid this by copying whole
        # [bs, F] rows, which prefill can't afford at KVH x the traffic).
        return _pallas_geometry_ok(cfg, tp) and cfg.head_dim_ == 128

    def _build():
        if mesh is not None:
            return make_tp_flash_prefill(
                mesh, cfg, interpret=platform != "tpu", kv_quant=kv_quant)
        if platform != "tpu":
            return functools.partial(flash_prefill_attention, interpret=True)
        return flash_prefill_attention

    if mode == "flash":
        if not _flash_ok():
            raise ValueError(
                "prefill_path='flash' but the model/mesh can't take the "
                "flash kernel (attn extras, head_dim != 128 on TPU, or a "
                "TP degree that doesn't divide the KV heads); use "
                "prefill_path='auto' for gated selection")
        return _build()

    # auto: flash on TPU when the geometry allows; CPU always stays dense
    # (the interpreter would be a de-optimization, not a fast path) and
    # remains the oracle the flash path is diffed against in tests.
    if platform != "tpu" or not _flash_ok():
        return None
    return _build()


latent_prefill_attention_pallas.latent_prefill = True
