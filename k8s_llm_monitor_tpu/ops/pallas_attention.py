"""Pallas TPU kernels: attention straight off the paged KV pool.

The XLA fallbacks (ops/attention.py) gather every sequence's pages into a
contiguous ``[B, max_blocks*bs, KVH, D]`` array per layer per step: HBM
traffic of the table's capacity, whatever the contexts hold.  The kernels
here leave the page arrays in HBM (``memory_space=ANY``), walk each lane's
block table and stream exactly the pages it uses through VMEM in
double-buffered *windows*, with online (flash-style) softmax accumulation.
What they share:

  * a program handles ``TB`` lanes (8 where the batch allows, at least two
    programs so that a two-core chip keeps both busy);
  * GQA without in-kernel head splitting: pages are DMA'd as ``[bs, KVH*D]``
    rows (the fused lane dim keeps HBM slices 128-aligned for D < 128) and
    queries enter **block-diagonal** — head h occupies its kv group's
    D-slice of a ``[H, KVH*D]`` matrix, zeros elsewhere — so
    ``q_bd @ page.T`` and ``p @ page`` are single MXU products whose
    cross-head terms vanish; the wrapper slices each head's own group out;
  * rows past a lane's position are masked by position, so what a window
    fetches beyond the lane's pages only has to be finite.

The kernels:

  * ``paged_decode_attention_fused`` (``_fused_decode_kernel``) — the decode
    step of every dense one-chip model with a bf16 or f32 pool
    (ops/attention.py:select_decode_impl): RoPE, the KV append (an aligned
    read-modify-write of the tile that holds the new row) and attention over
    the cached pages in one call, the page arrays updated in place.  Its
    DMAs are ONE pipeline across a program's lanes: every lane's append tile
    is read at program start, window 0 of lane t+1 is in flight while lane
    t multiplies its last window, windows are ``_FUSED_WINDOW`` = 32 pages
    fetched in groups of 8 with one wait a group, and groups past a lane's
    pages are skipped.  The hazard argument is in the kernel's docstring.
    On a v5e at the served Qwen2-7B cell's shape (64 lanes of ~590 cached
    tokens, 28 q / 4 kv heads x 128, bf16 pool) it takes 2.4-2.5 us a
    lane, 52-54% of the time 819 GB/s needs for the live pages; the
    pipeline it replaced (a lane at a time, each waiting alone for its
    append tile and its window 0, 8-page windows, a wait a copy) took
    4.0-4.2 us, 31-32% (PERF.md section 6, PR 29).
  * ``paged_decode_attention_fused_quant`` — its twin for int8 / fp8 pools
    (quantize on append, dequantize in kernel); still a lane at a time.
  * ``paged_decode_attention_pallas`` / ``paged_verify_attention_pallas``
    (``_paged_attn_kernel``) — the split path: the new rows are already in
    the pages; one query token a lane, or a few (speculative verify).
  * ``latent_decode_attention_pallas`` / ``latent_prefill_attention_*`` —
    the latent (compressed-KV) mixer's decode and fresh prefill.
  * ``flash_prefill_attention*`` — tiled prefill off the pool, row and
    packed-stream forms, plain and quantized.

Selected by ops/attention.py (``select_attn_impl``, ``select_decode_impl``,
``select_prefill_impl``) on a TPU; CPU tests run them in the interpreter for
parity with the XLA references, and tests/test_chip_compile.py puts each to
the chip's compiler at the served shapes.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -0.7 * float(jnp.finfo(jnp.float32).max)


# Pages DMA'd per burst by the split decode / verify kernel, the quantized
# fused decode kernel and flash prefill: a window's copies are issued together
# and double-buffered against the previous window's products, so per-copy HBM
# latency overlaps within the burst.  Eight pages (128 keys a softmax block)
# predates any chip trace; the two decode kernels that have been traced took
# deeper windows of their own (below, and ``_LATENT_WINDOW``).
_WINDOW = 8

# The fused decode kernel's own depth, and the pages of one fetch group.  A
# window's cost on the chip is its copies' descriptors (scalar work) plus its
# two products, one after the other; the copies themselves hide behind both.
# Per key the products get cheaper as the window deepens (a lane's softmax
# chain has fewer links), and dead pages cost descriptors and bandwidth, so:
# deep windows, fetched in groups, dead groups skipped.  Measured at the served
# cell's shape (64 lanes of ~590 cached tokens, PR 29, us a lane), fetched
# whole: 16 pages 2.78, 32 2.70; in groups of 8: 16 pages 2.67, 32 2.40, 48
# 2.73 (32 in groups of 4: 3.02); the parent's pipeline at 8 pages 4.07.
_FUSED_WINDOW = 32
_FUSED_GROUP = 8


def _paged_attn_kernel(
    QS,                    # static: query tokens per sequence (1 = decode)
    H,                     # static: query heads per token
    # scalar prefetch
    tables_ref,            # [B, NB] int32 block ids
    starts_ref,            # [B] int32 cached tokens before this chunk
    qlens_ref,             # [B] int32 query tokens this call (<= QS)
    # inputs
    q_ref,                 # [TB, QS*H, F] block-diagonal queries (VMEM)
    k_hbm,                 # [num_blocks, bs, KVH*D] (ANY/HBM, whole array)
    v_hbm,                 # same
    # out
    o_ref,                 # [TB, QS*H, F]
):
    """One program handles TB sequences; each sequence streams its pages
    ONCE for all QS query tokens.  Query token i (rows i*H..i*H+H-1)
    attends causally through absolute position ``starts[b] + i`` — the
    verify/chunk semantics; QS=1 with starts = lengths-1 is exactly the
    decode case.  K/V for the chunk's own tokens must already be written
    into the pages (models/llama.py scatters before attention)."""
    TB = q_ref.shape[0]                                    # seqs per program
    b0 = pl.program_id(0) * TB
    bs = k_hbm.shape[1]
    F = q_ref.shape[2]                                     # KVH * D
    NB = tables_ref.shape[1]
    W = min(_WINDOW, NB)
    # Row r of the [QS*H, F] tile belongs to query token r // H.
    row_q = jax.lax.broadcasted_iota(jnp.int32, (QS * H, 1), 0) // H

    def scoped(k_buf, v_buf, sem):
        # k_buf/v_buf: [2, W*bs, F] double-buffered page slabs, reused
        # across the program's TB sequences; sem: [2, W, 2] one DMA
        # semaphore pair per page slot.
        def start_window(slot, b, w):
            # Issue all W page copies of window ``w`` back-to-back; table
            # indices past the sequence's pages clamp to a duplicate id
            # (rows are masked by position later), so the burst shape is
            # static and every wait has a matching start.
            for i in range(W):
                j = jnp.minimum(w * W + i, NB - 1)
                blk = tables_ref[b, j]
                pltpu.make_async_copy(
                    k_hbm.at[blk], k_buf.at[slot, pl.ds(i * bs, bs)],
                    sem.at[slot, i, 0]).start()
                pltpu.make_async_copy(
                    v_hbm.at[blk], v_buf.at[slot, pl.ds(i * bs, bs)],
                    sem.at[slot, i, 1]).start()

        def wait_window(slot, b, w):
            for i in range(W):
                j = jnp.minimum(w * W + i, NB - 1)
                blk = tables_ref[b, j]
                pltpu.make_async_copy(
                    k_hbm.at[blk], k_buf.at[slot, pl.ds(i * bs, bs)],
                    sem.at[slot, i, 0]).wait()
                pltpu.make_async_copy(
                    v_hbm.at[blk], v_buf.at[slot, pl.ds(i * bs, bs)],
                    sem.at[slot, i, 1]).wait()

        # Static unroll over the tile's sequences: one program amortizes
        # grid startup over TB sequences' attention.  Each sequence still
        # pays its own window-0 DMA stall (the shared double buffers make
        # cross-sequence prefetch non-trivial; measured immaterial on v5e).
        for t in range(TB):
            b = b0 + t
            start = starts_ref[b]
            # Stream every page the chunk's last token can see.  Inactive
            # lanes (qlen 0) stream ONE masked window, not their whole dead
            # context: a lane that finished in round 1 of a multi-round
            # spec call would otherwise re-stream ctx pages per layer per
            # remaining round just to produce discarded rows.
            length = jnp.where(qlens_ref[b] > 0, start + qlens_ref[b], 1)
            n_blocks = (length + bs - 1) // bs             # >= 1
            n_windows = (n_blocks + W - 1) // W
            start_window(0, b, 0)
            q = q_ref[t].astype(jnp.float32)           # [QS*H, F] block-diag

            def body(w, carry, b=b, start=start, n_windows=n_windows):
                m, l, acc = carry          # [QS*H, 1], [QS*H, 1], [QS*H, F]
                slot = jax.lax.rem(w, 2)

                @pl.when(w + 1 < n_windows)
                def _prefetch():
                    start_window(1 - slot, b, w + 1)

                wait_window(slot, b, w)
                pos = (w * (W * bs)
                       + jax.lax.broadcasted_iota(jnp.int32, (1, W * bs), 1))
                # Per-row causal bound: query token i sits at absolute
                # position start + i, attending through itself.
                valid = pos < start + 1 + row_q             # [QS*H, W*bs]
                kblk = k_buf[slot].astype(jnp.float32)      # [W*bs, F]
                vblk = v_buf[slot].astype(jnp.float32)

                # Block-diagonal q makes this one dot per window: head h
                # only overlaps its own kv group's D-slice, so cross-head
                # products are zero.
                s = jax.lax.dot_general(
                    q, kblk, (((1,), (1,)), ((), ())),
                    preferred_element_type=jnp.float32,
                )                                           # [QS*H, W*bs]
                s = jnp.where(valid, s, NEG_INF)

                m_cur = jnp.max(s, axis=-1, keepdims=True)
                m_new = jnp.maximum(m, m_cur)
                alpha = jnp.exp(m - m_new)
                p = jnp.exp(s - m_new)                      # [QS*H, W*bs]
                l_new = alpha * l + jnp.sum(p, axis=-1, keepdims=True)
                pv = jax.lax.dot_general(
                    p, vblk, (((1,), (0,)), ((), ())),
                    preferred_element_type=jnp.float32,
                )                                           # [QS*H, F]
                return m_new, l_new, alpha * acc + pv

            m0 = jnp.full((QS * H, 1), NEG_INF, jnp.float32)
            l0 = jnp.zeros((QS * H, 1), jnp.float32)
            acc0 = jnp.zeros((QS * H, F), jnp.float32)
            _, l, acc = jax.lax.fori_loop(0, n_windows, body, (m0, l0, acc0))
            # acc rows carry the head's output in its kv-group slice (plus
            # group-mates' contributions in other slices, sliced away by
            # the caller).
            o_ref[t] = (acc / l).astype(o_ref.dtype)

    pl.run_scoped(
        scoped,
        k_buf=pltpu.VMEM((2, W * bs, F), k_hbm.dtype),
        v_buf=pltpu.VMEM((2, W * bs, F), v_hbm.dtype),
        sem=pltpu.SemaphoreType.DMA((2, W, 2)),
    )


def _run_paged_attn(q, k_pages, v_pages, block_table, starts, qlens,
                    interpret):
    """Shared wrapper: block-diagonalize queries, tile the batch, run the
    unified kernel, extract each head's kv-group slice.

    q: [B, QS, H, D] — QS query tokens per sequence at absolute positions
    ``starts[b] + i``; returns [B, QS, H, D].
    """
    B, QS, H, D = q.shape
    nblk, bs, F = k_pages.shape
    assert F % D == 0 and D <= 128, (F, D)
    KVH = F // D
    q_per_kv = H // KVH

    # Block-diagonal queries (scaled): head h lives in its kv group's
    # D-slice of the F lane dim, zeros elsewhere — see _paged_attn_kernel.
    group = jnp.arange(H, dtype=jnp.int32) // q_per_kv            # [H]
    onehot = jax.nn.one_hot(group, KVH, dtype=q.dtype)            # [H, KVH]
    q_bd = (q[:, :, :, None, :] * (D ** -0.5)
            * onehot[None, None, :, :, None]).reshape(B, QS * H, F)

    # Batch-tile: TB sequences per program amortize per-program grid
    # startup — at B=128 this is 16 programs instead of 128, 8 per
    # megacore half.  (Measured neutral vs grid=(B,) on v5e at B=128; the
    # decode-attention cost there is dependency-serialization against the
    # surrounding matmuls, not program count.)  Keep at least 2 programs
    # so both megacore halves stay busy at small B, and bound the q/o VMEM
    # tiles to ~4 MiB for multi-query (verify) calls.
    budget = 4 * 2**20 // max(QS * H * F * q.dtype.itemsize, 1)
    TB = next(tb for tb in (8, 4, 2, 1)
              if B % tb == 0 and (B // tb >= 2 or B == 1)
              and (tb <= budget or tb == 1))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(B // TB,),
        in_specs=[
            pl.BlockSpec((TB, QS * H, F), lambda p, tbl, st, ql: (p, 0, 0)),
            pl.BlockSpec(memory_space=pl.ANY),   # K pages stay in HBM
            pl.BlockSpec(memory_space=pl.ANY),   # V pages stay in HBM
        ],
        out_specs=pl.BlockSpec((TB, QS * H, F),
                               lambda p, tbl, st, ql: (p, 0, 0)),
    )

    out_full = pl.pallas_call(
        functools.partial(_paged_attn_kernel, QS, H),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, QS * H, F), q.dtype),
        compiler_params=pltpu.CompilerParams(
            # Programs touch disjoint q/o tiles and only read pages: the
            # tile grid is safely parallel (megacore splits it).
            dimension_semantics=("parallel",),
        ),
        interpret=interpret,
        # The kernel's name in a device trace (one query a lane = decode).
        name="paged_decode_attention" if QS == 1 else "paged_verify_attention",
    )(block_table, starts, qlens, q_bd, k_pages, v_pages)

    # Extract each head's own kv-group slice.
    out = jnp.take_along_axis(
        out_full.reshape(B, QS, H, KVH, D),
        group[None, None, :, None, None], axis=3)[:, :, :, 0, :]
    return out


@functools.partial(jax.jit, static_argnames=("interpret",))
def paged_decode_attention_pallas(
    q: jnp.ndarray,
    k_pages: jnp.ndarray,
    v_pages: jnp.ndarray,
    block_table: jnp.ndarray,
    lengths: jnp.ndarray,
    *,
    interpret: bool = False,
) -> jnp.ndarray:
    """Single-token paged decode attention (drop-in for the XLA fallback).

    Args:
      q: [B, 1, H, D].
      k_pages, v_pages: [num_blocks, bs, KVH*D] — the resident fused-lane
        layout (models/llama.py:KVPages), consumed directly with no
        per-step relayout.
      block_table: [B, max_blocks_per_seq] int32 (entries past the sequence's
        pages must be 0, the null block — serving/kv_cache.py guarantees it).
      lengths: [B] int32 valid kv length (>= 1 for active lanes; the new
        token's K/V must already be written at index lengths-1).
      interpret: run in the Pallas interpreter (CPU parity tests).

    Returns:
      [B, 1, H, D] in q.dtype.
    """
    B, S, H, D = q.shape
    assert S == 1, f"decode kernel expects one query token, got {S}"
    starts = jnp.maximum(lengths - 1, 0).astype(jnp.int32)
    qlens = jnp.minimum(lengths, 1).astype(jnp.int32)
    return _run_paged_attn(q, k_pages, v_pages, block_table, starts, qlens,
                           interpret)


# ---------------------------------------------------------------------------
# Latent (compressed-KV) decode attention: one shared row per token
# ---------------------------------------------------------------------------

# Pages per burst of the latent kernel.  A latent pool holds one row per
# token for all heads, so a lane's whole context is one stream and the
# window can be wide: 32 pages of 16 tokens are 512 keys a softmax block.
_LATENT_WINDOW = 32


def _latent_decode_kernel(
    R,                     # static: value width (the latent part of a row)
    burst,                 # static: pages a burst (_LATENT_WINDOW)
    masked,                # static: a keep_ref follows q_ref
    # scalar prefetch
    tables_ref,            # [B, NB] int32 block ids
    lens_ref,              # [B] int32 valid cached tokens (new one included)
    # inputs
    q_ref,                 # [TB, H, F] absorbed queries, scaled (VMEM)
    *refs,                 # (keep_ref [TB, 1, bursts * W * bs] int32,) then:
    # kv_hbm                 [num_blocks, bs, F] latent pages (ANY/HBM)
    # out
    # o_ref                  [TB, H, R]
):
    """One program handles TB lanes.  Every head of a lane reads the same
    rows ``[latent | rotated key | zeros]``: the score is one
    ``[H, F] x [F, keys]`` product against the whole row and the value is
    the row's first R lanes, so a page is streamed once for all heads.
    Operands stay in the pool's dtype (bf16 on the chip); products
    accumulate in float32.

    ``masked`` (selected attention, mask form): every page of the lane is
    streamed all the same, and a key whose ``keep`` is 0 is dropped before
    the softmax — its weight is set to zero outright, so a burst in which
    nothing is kept adds nothing whatever the running maximum is."""
    if masked:
        keep_ref, kv_hbm, o_ref = refs
    else:
        kv_hbm, o_ref = refs
    TB, H, F = q_ref.shape
    b0 = pl.program_id(0) * TB
    bs = kv_hbm.shape[1]
    NB = tables_ref.shape[1]
    W = min(burst, NB)

    def scoped(buf, sem):
        # buf: [2, W*bs, F] double-buffered slab; sem: [2, W].
        def copies(slot, b, w):
            for i in range(W):
                j = jnp.minimum(w * W + i, NB - 1)
                yield pltpu.make_async_copy(
                    kv_hbm.at[tables_ref[b, j]],
                    buf.at[slot, pl.ds(i * bs, bs)], sem.at[slot, i])

        def start_window(slot, b, w):
            for c in copies(slot, b, w):
                c.start()

        def wait_window(slot, b, w):
            for c in copies(slot, b, w):
                c.wait()

        for t in range(TB):
            b = b0 + t
            # An inactive lane (0 cached tokens) streams one masked window
            # of the null block.
            length = jnp.maximum(lens_ref[b], 1)
            n_windows = ((length + bs - 1) // bs + W - 1) // W
            start_window(0, b, 0)
            q = q_ref[t]                                       # [H, F]

            def body(w, carry, b=b, length=length, n_windows=n_windows,
                     q=q):
                m, l, acc = carry            # [H, 1], [H, 1], [H, R]
                slot = jax.lax.rem(w, 2)

                @pl.when(w + 1 < n_windows)
                def _prefetch():
                    start_window(1 - slot, b, w + 1)

                wait_window(slot, b, w)
                pos = (w * (W * bs)
                       + jax.lax.broadcasted_iota(jnp.int32, (1, W * bs), 1))
                rows = buf[slot]                               # [W*bs, F]
                s = jax.lax.dot_general(
                    q, rows, (((1,), (1,)), ((), ())),
                    preferred_element_type=jnp.float32)        # [H, W*bs]
                if masked:
                    at = pl.multiple_of(w * (W * bs), W * bs)
                    seen = (pos < length) & (
                        keep_ref[t, :, pl.ds(at, W * bs)] != 0)
                    s = jnp.where(seen, s, NEG_INF)
                else:
                    s = jnp.where(pos < length, s, NEG_INF)
                m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
                alpha = jnp.exp(m - m_new)
                p = jnp.exp(s - m_new)
                if masked:
                    p = jnp.where(seen, p, 0.0)
                l_new = alpha * l + jnp.sum(p, axis=-1, keepdims=True)
                pv = jax.lax.dot_general(
                    p.astype(rows.dtype), rows[:, :R],
                    (((1,), (0,)), ((), ())),
                    preferred_element_type=jnp.float32)        # [H, R]
                return m_new, l_new, alpha * acc + pv

            m0 = jnp.full((H, 1), NEG_INF, jnp.float32)
            l0 = jnp.zeros((H, 1), jnp.float32)
            acc0 = jnp.zeros((H, R), jnp.float32)
            _, l, acc = jax.lax.fori_loop(0, n_windows, body, (m0, l0, acc0))
            if masked:      # an idle lane keeps nothing
                l = jnp.where(l == 0.0, 1.0, l)
            o_ref[t] = (acc / l).astype(o_ref.dtype)

    pl.run_scoped(
        scoped,
        buf=pltpu.VMEM((2, W * bs, F), kv_hbm.dtype),
        sem=pltpu.SemaphoreType.DMA((2, W)),
    )


@functools.partial(jax.jit, static_argnames=("v_width", "interpret", "name",
                                             "burst"))
def latent_decode_attention_pallas(
    q: jnp.ndarray,
    pages: jnp.ndarray,
    block_table: jnp.ndarray,
    lengths: jnp.ndarray,
    *,
    v_width: int,
    interpret: bool = False,
    keep: jnp.ndarray | None = None,
    name: str = "latent_decode_attention",
    burst: int = _LATENT_WINDOW,
) -> jnp.ndarray:
    """Single-token decode attention over a latent pool, absorbed form
    (drop-in for ops/attention.py:latent_decode_attention).

    Args:
      q: [B, 1, H, F] absorbed queries ``[q_nope W_UK^T | q_rope | 0]``,
        already scaled by 1/sqrt(qk_head_dim).
      pages: [num_blocks, bs, F] rows ``[latent | rotated key | zeros]``
        (models/llama.py:init_kv_pages, latent page kind).
      block_table: [B, max_blocks_per_seq] int32.
      lengths: [B] int32 valid cached tokens, the new token's row included
        (0 = inactive lane).
      v_width: the latent width R; the value of a row is its first R lanes.
      keep: [B, >= lengths] bool — the selected keys of each lane (the mask
        form of selected attention: every page is streamed, a key that is
        not kept is dropped before the softmax); None = every key.
      name: the kernel's name in a device trace (a full layer's selected
        attention and a window layer's ring have their own: the benchmark
        prices them apart).
      burst: pages fetched together (a window layer's ring is one burst).

    Returns:
      [B, 1, H, v_width] in q.dtype — per-head ``P c``, before ``W_UV``.
    """
    B, S, H, F = q.shape
    assert S == 1, f"decode kernel expects one query token, got {S}"
    assert pages.shape[2] == F and v_width <= F, (pages.shape, F, v_width)
    TB = next(tb for tb in (8, 4, 2, 1)
              if B % tb == 0 and (B // tb >= 2 or B == 1))
    in_specs = [pl.BlockSpec((TB, H, F), lambda p, tbl, ln: (p, 0, 0))]
    operands = [q.reshape(B, H, F).astype(pages.dtype)]
    if keep is not None:
        # Whole bursts of keys a lane: the kernel slices a burst's flags.
        span = min(burst, block_table.shape[1]) * pages.shape[1]
        width = -(-block_table.shape[1] * pages.shape[1] // span) * span
        flags = keep[:, :width].astype(jnp.int32)
        flags = jnp.pad(flags, ((0, 0), (0, width - flags.shape[1])))
        in_specs.append(pl.BlockSpec((TB, 1, width),
                                     lambda p, tbl, ln: (p, 0, 0)))
        operands.append(flags[:, None, :])
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B // TB,),
        in_specs=in_specs + [
            pl.BlockSpec(memory_space=pl.ANY),   # pages stay in HBM
        ],
        out_specs=pl.BlockSpec((TB, H, v_width),
                               lambda p, tbl, ln: (p, 0, 0)),
    )
    out = pl.pallas_call(
        functools.partial(_latent_decode_kernel, v_width, burst,
                          keep is not None),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, H, v_width), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",)),
        interpret=interpret,
        name=name,
    )(block_table, lengths.astype(jnp.int32), *operands, pages)
    return out[:, None]


def _index_scores_decode_kernel(
    burst,                 # static: pages a burst
    # scalar prefetch
    tables_ref,            # [B, NB] int32 block ids
    lens_ref,              # [B] int32 valid cached tokens
    # inputs
    q_ref,                 # [TB, Hi, Di] the indexer's queries (VMEM)
    w_ref,                 # [TB, Hi, 1] float32 the heads' weights
    idx_hbm,               # [num_blocks, bs, Di] index-key pages (ANY/HBM)
    # out
    o_ref,                 # [TB, 1, bursts * W * bs] float32 scores
):
    """One program handles TB lanes: a lane's index keys are streamed once,
    a burst of pages at a time, and scored against all of the indexer's
    heads — ``sum_j w_j relu(q_j . key)`` — as one ``[Hi, Di] x [Di, keys]``
    product, a relu, and a weighted sum down the heads.  What lies past a
    lane's last burst is left zero (the selection masks it)."""
    TB, Hi, Di = q_ref.shape
    b0 = pl.program_id(0) * TB
    bs = idx_hbm.shape[1]
    NB = tables_ref.shape[1]
    W = min(burst, NB)

    def scoped(buf, sem):
        def copies(slot, b, w):
            for i in range(W):
                j = jnp.minimum(w * W + i, NB - 1)
                yield pltpu.make_async_copy(
                    idx_hbm.at[tables_ref[b, j]],
                    buf.at[slot, pl.ds(i * bs, bs)], sem.at[slot, i])

        for t in range(TB):
            b = b0 + t
            length = jnp.maximum(lens_ref[b], 1)
            n_bursts = ((length + bs - 1) // bs + W - 1) // W
            for c in copies(0, b, 0):
                c.start()
            q, wt = q_ref[t], w_ref[t]                 # [Hi, Di], [Hi, 1]
            o_ref[t] = jnp.zeros(o_ref.shape[1:], o_ref.dtype)

            def body(w, carry, b=b, n_bursts=n_bursts, q=q, wt=wt, t=t):
                slot = jax.lax.rem(w, 2)

                @pl.when(w + 1 < n_bursts)
                def _prefetch():
                    for c in copies(1 - slot, b, w + 1):
                        c.start()

                for c in copies(slot, b, w):
                    c.wait()
                s = jax.lax.dot_general(
                    q, buf[slot], (((1,), (1,)), ((), ())),
                    preferred_element_type=jnp.float32)        # [Hi, W*bs]
                at = pl.multiple_of(w * (W * bs), W * bs)
                o_ref[t, :, pl.ds(at, W * bs)] = jnp.sum(
                    jnp.maximum(s, 0.0) * wt, axis=0, keepdims=True)
                return carry

            jax.lax.fori_loop(0, n_bursts, body, 0)

    pl.run_scoped(
        scoped,
        buf=pltpu.VMEM((2, W * bs, Di), idx_hbm.dtype),
        sem=pltpu.SemaphoreType.DMA((2, W)),
    )


# Pages per burst of the index-score kernel: an index key is a fifth of a
# latent row, so a burst takes twice the pages.
_INDEX_BURST = 64


@functools.partial(jax.jit, static_argnames=("interpret",))
def index_scores_decode_pallas(
    q: jnp.ndarray,
    w: jnp.ndarray,
    pages: jnp.ndarray,
    block_table: jnp.ndarray,
    lengths: jnp.ndarray,
    *,
    interpret: bool = False,
) -> jnp.ndarray:
    """The indexer's scores of one decode step against every cached index
    key (drop-in for ops/attention.py:index_scores_decode).

    Args:
      q: [B, 1, Hi, Di] the indexer's queries, rotated.
      w: [B, 1, Hi] float32 the heads' weights, scaled.
      pages: [num_blocks, bs, Di] index-key pages.
      block_table: [B, NB] int32; lengths: [B] int32 cached tokens.

    Returns:
      [B, >= NB * bs] float32; what lies at or past a lane's ``lengths`` is
      meaningless (the selection masks it).
    """
    B, S, Hi, Di = q.shape
    assert S == 1, f"decode kernel expects one query token, got {S}"
    NB, bs = block_table.shape[1], pages.shape[1]
    span = min(_INDEX_BURST, NB) * bs
    width = -(-NB * bs // span) * span
    TB = next(tb for tb in (8, 4, 2, 1)
              if B % tb == 0 and (B // tb >= 2 or B == 1))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B // TB,),
        in_specs=[
            pl.BlockSpec((TB, Hi, Di), lambda p, tbl, ln: (p, 0, 0)),
            pl.BlockSpec((TB, Hi, 1), lambda p, tbl, ln: (p, 0, 0)),
            pl.BlockSpec(memory_space=pl.ANY),   # pages stay in HBM
        ],
        out_specs=pl.BlockSpec((TB, 1, width), lambda p, tbl, ln: (p, 0, 0)),
    )
    out = pl.pallas_call(
        functools.partial(_index_scores_decode_kernel, _INDEX_BURST),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, 1, width), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",)),
        interpret=interpret,
        name="sparse_latent_decode_index_scores",
    )(block_table, lengths.astype(jnp.int32),
      q.reshape(B, Hi, Di).astype(pages.dtype),
      w.reshape(B, Hi, 1).astype(jnp.float32), pages)
    return out[:, 0]


latent_decode_attention_pallas.latent = True


def _latent_prefill_kernel(
    stream,                # static: blocks of a packed stream (below)
    window,                # static: keys a query sees (0 = all before it)
    masked,                # static: a keep_ref follows v_ref
    # scalar prefetch
    lens_ref,              # [B] int32 valid tokens of each row
    *refs,                 # (tile_row_ref, tile_t_ref, first_ref,) then:
    # inputs (one batch row, one head)
    #   q_ref                [1, 1, bq, Dk], already scaled
    #   k_ref                [1, 1, bk, Dk]
    #   v_ref                [1, 1, bk, Dv]
    # out
    #   o_ref                [1, 1, bq, Dv]
    # scratch, kept across the key blocks of one query block
    #   m_scr, l_scr         [bq, 128] float32, lane-replicated
    #   acc_scr              [bq, Dv] float32
):
    """Causal attention of one query block against one key block of the
    SAME sequence (a fresh prefill: positions are indices), online softmax
    across the key blocks of grid axis 3.  A query block past the row's
    length, and a key block above the diagonal or past the length, do
    nothing (and are not fetched: the index map clamps them to the last
    block that is needed).  Only a block the diagonal or the length cuts
    through builds a mask: the softmax's elementwise passes, not the
    products, bound this kernel on a chip without bf16 vector units.

    ``stream`` (``latent_prefill_attention_packed``): q, k and v are one
    block-aligned stream ``[H, NT*bq, D]`` of several sequences' blocks end
    to end, the grid is (H, NT, key blocks of the longest row), and two
    prefetched arrays say which sequence query block i' is of and which of
    that sequence's blocks it is (a third, where each sequence's blocks
    start, is the index maps').  The block's arithmetic is the same.

    ``window`` (stream form): a query sees the ``window`` positions that end
    with its own; the key axis of the grid is then the few blocks a query
    block's band crosses, counted from the first of them, and a block wholly
    before the band is as dead as one above the diagonal.  ``masked``
    (stream form): ``keep_ref`` [bq, bk] int8 says which keys each query
    sees (selected attention, the mask form; it holds the diagonal and the
    length already) — a key that is not kept gets no weight whatever the
    running maximum is."""
    if stream:
        tile_row_ref, tile_t_ref, _, q_ref, k_ref, v_ref, *refs = refs
        if masked:
            keep_ref, *refs = refs
        o_ref, m_scr, l_scr, acc_scr = refs
        b, i = (tile_row_ref[pl.program_id(1)], tile_t_ref[pl.program_id(1)])
        j, last_j = pl.program_id(2), pl.num_programs(2) - 1
        blk = lambda ref: ref[0]                               # noqa: E731
    else:
        q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr = refs
        b, i, j = pl.program_id(0), pl.program_id(2), pl.program_id(3)
        last_j = pl.num_programs(3) - 1
        blk = lambda ref: ref[0, 0]                            # noqa: E731
    bq, bk = q_ref.shape[-2], k_ref.shape[-2]
    Dv = v_ref.shape[-1]
    length = lens_ref[b]
    jrel = j
    if window:      # the key axis counts from the band's first block
        j = j + jnp.maximum(i * bq - (window - 1), 0) // bk
    live = (i * bq < length) & (j * bk < length) & (j * bk <= i * bq + bq - 1)
    whole = (j * bk + bk - 1 <= i * bq) & (j * bk + bk <= length)
    if window:
        live = live & (j * bk + bk - 1 > i * bq - window)
        whole = whole & (i * bq + bq - 1 - j * bk < window)

    @pl.when(jrel == 0)
    def _init():
        m_scr[...] = jnp.full(m_scr.shape, NEG_INF, jnp.float32)
        l_scr[...] = jnp.zeros(l_scr.shape, jnp.float32)
        acc_scr[...] = jnp.zeros(acc_scr.shape, jnp.float32)

    def block(cut: bool):
        k, v = blk(k_ref), blk(v_ref)
        s = jax.lax.dot_general(
            blk(q_ref), k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)                 # [bq, bk]
        if masked:
            kept = keep_ref[...].astype(jnp.int32) != 0
            s = jnp.where(kept, s, NEG_INF)
        elif cut:
            rows = i * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
            cols = j * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
            seen = (cols <= rows) & (cols < length)
            if window:
                seen = seen & (rows - cols < window)
            s = jnp.where(seen, s, NEG_INF)
        m_prev = m_scr[...]                                     # [bq, 128]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - _lanes(m_new, bk))
        if masked:
            p = jnp.where(kept, p, 0.0)
        l_scr[...] = alpha * l_scr[...] + jnp.sum(p, axis=1, keepdims=True)
        acc_scr[...] = _lanes(alpha, Dv) * acc_scr[...] + jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_scr[...] = m_new

    if masked:
        pl.when(live)(lambda: block(False))
    else:
        pl.when(live & whole)(lambda: block(False))
        pl.when(live & jnp.logical_not(whole))(lambda: block(True))

    @pl.when(jrel == last_j)
    def _store():
        l = l_scr[...]
        l = jnp.where(l == 0.0, 1.0, l)          # rows past the length
        out = (acc_scr[...] / _lanes(l, Dv)).astype(o_ref.dtype)
        if stream:
            o_ref[0] = out
        else:
            o_ref[0, 0] = out


def _lanes(x, n: int):
    """A lane-replicated [rows, 128] statistic at width ``n``."""
    if n == x.shape[1]:
        return x
    if n % x.shape[1] == 0:
        return pltpu.repeat(x, n // x.shape[1], axis=1)
    return jnp.broadcast_to(x[:, :1], (x.shape[0], n))


@functools.partial(jax.jit, static_argnames=("scale", "block", "interpret",
                                             "window", "topk", "probe"))
def latent_prefill_attention_pallas(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    lengths: jnp.ndarray,
    *,
    scale: float,
    block: int = 512,
    interpret: bool = False,
    window: int = 0,
    topk: int = 0,
    index=None,
    probe: bool = False,
) -> jnp.ndarray:
    """Causal attention over a fresh prefill batch's own tokens, the
    expanded form of a latent mixer: per-head keys wider than the values.

    Args:
      q, k: [B, S, H, Dk]; v: [B, S, H, Dv] (Dk = nope + rope width, padded
        here to whole 128-lane tiles; Dv its own).  Token ``s`` sits at
        position ``s``.
      lengths: [B] int32 valid tokens (0 = idle row).  Query blocks wholly
        past a row's length are not computed and come back zero; other rows
        past it are garbage the caller masks, as everywhere.
      scale: multiplies the scores (folded into q here).

      window, topk, index: a geometry that restricts the keys a query sees
        (``latent_prefill_attention_packed`` says how); the rows are then
        laid end to end and take the stream form.
      probe: with ``index``, also return what the selection's kernels
        computed (a comparison's, never a served program's).

    Returns:
      [B, S, H, Dv] in q.dtype.  No [S, S] score tensor leaves VMEM; key
      blocks above the diagonal or past a row's length are skipped.  With
      ``probe``: (that, scores [B, S, S] float32, keep [B, S, S] bool).
    """
    B, S0, H, Dk = q.shape
    Dv = v.shape[-1]
    if window or index is not None:
        flat = lambda x: x.reshape(B * S0, *x.shape[2:])       # noqa: E731
        out = latent_prefill_attention_packed(
            flat(q), flat(k), flat(v),
            jnp.arange(B, dtype=jnp.int32) * S0, lengths, scale=scale,
            row_len=S0, block=block, interpret=interpret, window=window,
            topk=topk,
            index=None if index is None else tuple(map(flat, index)),
            probe=probe)
        if probe and index is not None:
            out, scores, keep = out
            return (out.reshape(B, S0, H, Dv),
                    scores[:, :S0].reshape(B, S0, S0),
                    keep[:, :S0].reshape(B, S0, S0) != 0)
        return out.reshape(B, S0, H, Dv)
    bq = bk = min(block, S0)
    pad, tail = -Dk % 128, -S0 % bq       # whole lane tiles, whole blocks
    q = q * jnp.asarray(scale, q.dtype)
    if pad or tail:
        q = jnp.pad(q, ((0, 0), (0, tail), (0, 0), (0, pad)))
        k = jnp.pad(k, ((0, 0), (0, tail), (0, 0), (0, pad)))
        v = jnp.pad(v, ((0, 0), (0, tail), (0, 0), (0, 0)))
    S = S0 + tail
    q, k, v = (x.transpose(0, 2, 1, 3) for x in (q, k, v))    # [B, H, S, D]
    nq = nk = S // bq

    def kv_map(b, h, i, j, lens):
        # Blocks that will do nothing repeat the last one that does: the
        # pipeline does not fetch a block whose index did not change.
        last = jnp.minimum((i * bq + bq - 1) // bk,
                           jnp.maximum(lens[b] - 1, 0) // bk)
        return (b, h, jnp.minimum(j, last), 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(B, H, nq, nk),
        in_specs=[
            pl.BlockSpec((1, 1, bq, Dk + pad), lambda b, h, i, j, lens: (b, h, i, 0)),
            pl.BlockSpec((1, 1, bk, Dk + pad), kv_map),
            pl.BlockSpec((1, 1, bk, Dv), kv_map),
        ],
        out_specs=pl.BlockSpec((1, 1, bq, Dv),
                               lambda b, h, i, j, lens: (b, h, i, 0)),
        scratch_shapes=[pltpu.VMEM((bq, 128), jnp.float32),
                        pltpu.VMEM((bq, 128), jnp.float32),
                        pltpu.VMEM((bq, Dv), jnp.float32)],
    )
    out = pl.pallas_call(
        functools.partial(_latent_prefill_kernel, False, 0, False),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, H, S, Dv), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary")),
        interpret=interpret,
        name="latent_prefill_attention",
    )(lengths.astype(jnp.int32), q, k, v)
    return out.transpose(0, 2, 1, 3)[:, :S0]


def _stream_tiles(offset, lengths, T: int, tile: int):
    """How a packed stream's R segments lie in a tile-aligned stream in
    which every segment starts a tile of its own (``T/tile + R`` tiles hold
    any packing).  Returns (NT, tile_row [NT] the segment tile i is of,
    tile_t [NT] which of its segment's tiles it is, first [R] each
    segment's first tile, src [NT*tile] the stream token in each aligned
    slot — a tile's tail past its segment's end reads whatever follows and
    is masked by the segment's length — and back [T] each stream token's
    aligned slot)."""
    i32 = jnp.int32
    R = offset.shape[0]
    NT = -(-T // tile) + R
    ntile = (lengths + tile - 1) // tile
    first = (jnp.cumsum(ntile) - ntile).astype(i32)
    idx = jnp.arange(NT, dtype=i32)
    tile_row = jnp.clip(
        jnp.sum(idx[:, None] >= first[None, :], axis=1) - 1, 0, R - 1
    ).astype(i32)
    tile_t = idx - first[tile_row]
    src = ((offset[tile_row] + tile_t * tile)[:, None]
           + jnp.arange(tile, dtype=i32))
    src = jnp.clip(src.reshape(NT * tile), 0, T - 1)
    tok = jnp.arange(T, dtype=i32)
    seg = jnp.clip(jnp.sum(tok[:, None] >= offset[None, :], axis=1) - 1,
                   0, R - 1)
    back = jnp.clip(first[seg] * tile + tok - offset[seg], 0, NT * tile - 1)
    return NT, tile_row, tile_t, first, src, back


def _index_scores_prefill_kernel(
    # scalar prefetch
    lens_ref,              # [R] int32 valid tokens of each segment
    tile_row_ref,          # [NT] the segment query tile i is of
    tile_t_ref,            # [NT] which of its segment's tiles it is
    _first_ref,            # [R] (the index maps')
    # inputs (one query tile, one key tile, one of the indexer's heads)
    q_ref,                 # [1, bq, Di]
    k_ref,                 # [bk, Di]
    w_ref,                 # [1, bq, 1] float32 that head's weight a query
    # out, kept across the heads of grid axis 2
    o_ref,                 # [bq, bk] float32
):
    """The indexer's scores of one query tile against one key tile of the
    SAME segment, ``sum_j w_j relu(q_j . key)``, one head a grid step.  A
    tile pair above the diagonal or past the segment's length stays zero
    (and fetches nothing new: the index map clamps it)."""
    i, j, h = pl.program_id(0), pl.program_id(1), pl.program_id(2)
    b, t = tile_row_ref[i], tile_t_ref[i]
    bq, bk = q_ref.shape[1], k_ref.shape[0]
    length = lens_ref[b]
    live = (t * bq < length) & (j * bk < length) & (j * bk <= t * bq + bq - 1)

    @pl.when(h == 0)
    def _init():
        o_ref[...] = jnp.zeros(o_ref.shape, o_ref.dtype)

    @pl.when(live)
    def _score():
        s = jax.lax.dot_general(
            q_ref[0], k_ref[...], (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)                 # [bq, bk]
        o_ref[...] += jnp.maximum(s, 0.0) * w_ref[0]


def _kth_largest_kernel(k, keys_ref, thr_ref):
    """The k-th largest int32 key of each row of the block, built bit by bit
    from the top (ops/sparse.py:kth_largest): the block stays in VMEM for
    all 32 counting passes.  keys_ref [rows, T] int32; thr_ref [rows, 128]
    int32, lane-replicated."""
    keys = keys_ref[...]
    top = jnp.int32(-2 ** 31)

    def bit(i, thr):                                   # thr [rows, 1]
        cand = thr | jnp.left_shift(jnp.int32(1), jnp.int32(31) - i)
        count = jnp.sum((keys >= (cand ^ top)).astype(jnp.int32), axis=1,
                        keepdims=True)
        return jnp.where(count >= k, cand, thr)

    thr = jax.lax.fori_loop(0, 32, bit,
                            jnp.zeros((keys.shape[0], 1), jnp.int32))
    thr_ref[...] = jnp.broadcast_to(thr ^ top, thr_ref.shape)


def kth_largest_pallas(keys: jnp.ndarray, k: int, *, rows: int = 64,
                       interpret: bool = False) -> jnp.ndarray:
    """``ops/sparse.py:kth_largest`` of int32 ``keys`` [N, T] (N a multiple
    of ``rows``): each row is read from HBM once, not 32 times."""
    N, T = keys.shape
    out = pl.pallas_call(
        functools.partial(_kth_largest_kernel, k),
        grid=(N // rows,),
        in_specs=[pl.BlockSpec((rows, T), lambda i: (i, 0))],
        out_specs=pl.BlockSpec((rows, 128), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((N, 128), jnp.int32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",)),
        interpret=interpret,
        name="sparse_latent_prefill_select",
    )(keys)
    return out[:, 0]


def _selected_keys(index, lengths, tiles, *, topk: int, bq: int, nk: int,
                   interpret: bool) -> tuple[jnp.ndarray, jnp.ndarray]:
    """(int8, float32) [NT * bq, nk * bq]: which keys of its own segment
    each query of the tile-aligned stream sees, and the scores that say so — the indexer's ``topk`` best of those no
    later than it (ops/sparse.py:topk_keep; all of them when there are no
    more).  The scores and each query's ``topk``-th score are kernels';
    what is kept of the keys at that score is XLA."""
    from k8s_llm_monitor_tpu.ops import sparse

    NT, tile_row, tile_t, first, src, _ = tiles
    qI, kI, wI = index                  # [T, Hi, Di], [T, Di], [T, Hi]
    Hi, Di = qI.shape[1:]

    def kv_map(i, j, h, lens, rows, ts, firsts):
        b, t = rows[i], ts[i]
        last = jnp.minimum((t * bq + bq - 1) // bq,
                           jnp.maximum(lens[b] - 1, 0) // bq)
        return (firsts[b] + jnp.minimum(j, last), 0)

    scores = pl.pallas_call(
        _index_scores_prefill_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4,
            grid=(NT, nk, Hi),
            in_specs=[
                pl.BlockSpec((1, bq, Di), lambda i, j, h, *_: (h, i, 0)),
                pl.BlockSpec((bq, Di), kv_map),
                pl.BlockSpec((1, bq, 1), lambda i, j, h, *_: (h, i, 0)),
            ],
            out_specs=pl.BlockSpec((bq, bq), lambda i, j, h, *_: (i, j)),
        ),
        out_shape=jax.ShapeDtypeStruct((NT * bq, nk * bq), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
        name="sparse_latent_prefill_index_scores",
    )(lengths, tile_row, tile_t, first,
      qI[src].transpose(1, 0, 2), kI[src].astype(qI.dtype),
      wI[src].astype(jnp.float32).T[..., None])
    pos = (tile_t[:, None] * bq
           + jnp.arange(bq, dtype=jnp.int32)[None, :]).reshape(NT * bq)
    row_len = jnp.repeat(lengths[tile_row], bq)
    cols = jnp.arange(nk * bq, dtype=jnp.int32)[None, :]
    allowed = (cols <= pos[:, None]) & (cols < row_len[:, None])
    keys = sparse.ordered_keys(scores, allowed)
    thr = kth_largest_pallas(keys, topk, rows=min(64, bq),
                             interpret=interpret)
    return sparse.keep_from_threshold(keys, thr, topk).astype(jnp.int8), scores


@functools.partial(jax.jit,
                   static_argnames=("scale", "row_len", "block", "interpret",
                                    "window", "topk", "probe"))
def latent_prefill_attention_packed(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    offset: jnp.ndarray,
    lengths: jnp.ndarray,
    *,
    scale: float,
    row_len: int,
    block: int = 512,
    interpret: bool = False,
    window: int = 0,
    topk: int = 0,
    index=None,
    probe: bool = False,
) -> jnp.ndarray:
    """``latent_prefill_attention_pallas`` of a fresh call's packed stream
    (models/llama.py:prefill_packed): the same kernel over the same blocks,
    without ``[R, S]`` row views of q, k and v in between (12 + 12 + 8 KB a
    row token a layer at the kanana widths: a fifth of a T = 16,384 call,
    PERF.md section 6, PR 27).  The stream is gathered into a block-aligned
    one (``_stream_tiles``) and the grid runs over its blocks.

    Args:
      q, k: [T, H, Dk]; v: [T, H, Dv]; segment r at ``[offset[r], offset[r]
        + lengths[r])``, its token i at position i.
      offset, lengths: [R] int32 (0 = idle row).
      row_len: the longest a segment can be (static): sets the block and
        the key-block axis of the grid.
      window: a query sees the ``window`` positions that end with its own
        (0 = every earlier one): the key axis of the grid shrinks to the
        blocks a query block's band crosses, and blocks wholly before the
        band are dead tiles like those above the diagonal.  The kernel is
        then ``window_latent_prefill_attention`` in a device trace.
      index, topk: the indexer's (queries [T, Hi, Di], keys [T, Di], head
        weights [T, Hi]) of the stream's tokens — selected attention, the
        mask form: the indexer's scores come from a kernel of their own
        (``sparse_latent_prefill_index_scores``), the ``topk`` best earlier
        keys of each query are found without a sort (ops/sparse.py), and the
        attention kernel (``sparse_latent_prefill_attention``) visits every
        causal block and drops what was not selected before the softmax.
      probe: with ``index``, also return each stream token's scores and
        keep mask over its own segment's positions.

    Returns:
      [T, H, Dv] in q.dtype; rows of no segment are garbage.  With
      ``probe``: (that, scores [T, >= row_len] float32, keep int8 likewise).
    """
    T, H, Dk = q.shape
    Dv = v.shape[-1]
    bq = bk = min(block, row_len)
    pad = -Dk % 128
    offset, lengths = offset.astype(jnp.int32), lengths.astype(jnp.int32)
    NT, tile_row, tile_t, first, src, back = _stream_tiles(
        offset, lengths, T, bq)
    keep = scores = None
    if index is not None:
        keep, scores = _selected_keys(
            index, lengths, (NT, tile_row, tile_t, first, src, back),
            topk=topk, bq=bq, nk=-(-row_len // bk), interpret=interpret)
    q = q * jnp.asarray(scale, q.dtype)
    if pad:
        q = jnp.pad(q, ((0, 0), (0, 0), (0, pad)))
        k = jnp.pad(k, ((0, 0), (0, 0), (0, pad)))
    q, k, v = (x[src].transpose(1, 0, 2) for x in (q, k, v))  # [H, NT*bq, D]
    nk = -(-row_len // bk)

    def q_map(h, i, j, lens, rows, ts, firsts):
        return (h, i, 0)

    def key_block(i, j, lens, rows, ts):
        # As in the row form: blocks that will do nothing repeat the last
        # one that does.  Under a window the axis counts from the band's
        # first block.
        b, t = rows[i], ts[i]
        last = jnp.minimum((t * bq + bq - 1) // bk,
                           jnp.maximum(lens[b] - 1, 0) // bk)
        if window:
            j = j + jnp.maximum(t * bq - (window - 1), 0) // bk
        return b, jnp.minimum(j, last)

    def kv_map(h, i, j, lens, rows, ts, firsts):
        b, j = key_block(i, j, lens, rows, ts)
        return (h, firsts[b] + j, 0)

    in_specs = [
        pl.BlockSpec((1, bq, Dk + pad), q_map),
        pl.BlockSpec((1, bk, Dk + pad), kv_map),
        pl.BlockSpec((1, bk, Dv), kv_map),
    ]
    operands = [q, k, v]
    if keep is not None:
        in_specs.append(pl.BlockSpec(
            (bq, bk), lambda h, i, j, lens, rows, ts, firsts:
            (i, key_block(i, j, lens, rows, ts)[1])))
        operands.append(keep)
    if window:      # the blocks a query block's band crosses
        nk = min(nk, -(-(window - 1) // bk) + 1)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,
        grid=(H, NT, nk),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, bq, Dv), q_map),
        scratch_shapes=[pltpu.VMEM((bq, 128), jnp.float32),
                        pltpu.VMEM((bq, 128), jnp.float32),
                        pltpu.VMEM((bq, Dv), jnp.float32)],
    )
    out = pl.pallas_call(
        functools.partial(_latent_prefill_kernel, True, window,
                          keep is not None),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((H, NT * bq, Dv), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
        name=("window_latent_prefill_attention" if window
              else "sparse_latent_prefill_attention" if keep is not None
              else "latent_prefill_attention"),
    )(lengths, tile_row, tile_t, first, *operands)
    out = out.transpose(1, 0, 2)[back]
    if probe and keep is not None:
        return out, scores[back], keep[back]
    return out


latent_prefill_attention_pallas.packed = latent_prefill_attention_packed


# ---------------------------------------------------------------------------
# Fused decode fast-path: RoPE + KV append + paged attention in one kernel
# ---------------------------------------------------------------------------


def _rotate_half_fused(x, D):
    """rotate_half within each D-slice of a fused-lane [..., KVH*D] array.

    For lane j with r = j mod D: first half (r < D/2) takes -x[j + D/2],
    second half takes x[j - D/2].  Both reads stay inside j's D-slice, so
    two full-axis rolls + a half-mask select implement the per-slice
    rotate without any lane-offset slicing (which Mosaic restricts).
    """
    F = x.shape[-1]
    lane = jax.lax.broadcasted_iota(jnp.int32, (1, F), 1)
    first_half = jax.lax.rem(lane, D) < (D // 2)
    fwd = pltpu.roll(x, D // 2, 1)          # x[j - D/2]
    bwd = pltpu.roll(x, (F - D // 2) % F, 1)  # x[j + D/2]
    return jnp.where(first_half, -bwd, fwd)


def _append_tile_rows(bs: int, dtype) -> int:
    """Rows of the aligned page tile the fused kernels read-modify-write to
    append one token.  Mosaic refuses a one-row DMA into a tiled HBM page
    ("slice shape ... must be aligned to tiling"); the smallest slice it
    takes along the token axis is one sublane tile — 8 rows of 32-bit,
    16 of 16-bit, 32 of 8-bit — or the whole page when that is smaller."""
    rows = 8 * 4 // jnp.dtype(dtype).itemsize
    return bs if bs <= rows or bs % rows else rows


def _append_rows(pages_out, tiles, blk, off, sems):
    """Append one token row to each of ``pages_out`` (HBM page arrays) at
    ``[blk, off]`` by an aligned read-modify-write of the tile that holds
    the row.  Returns ``(reads, merge)``: ``reads`` are the copies of the
    tile into its VMEM buffer in ``tiles``, for the caller to start and wait
    on (a kernel may start every lane's reads together); ``merge(new_rows)``
    then overwrites row ``off`` with ``new_rows[i]`` [1, F], starts the
    write-back and returns its copies, which the caller waits on before its
    program ends.

    The tile's other rows are written back unchanged, so a concurrent page
    stream may read them; the merge is a full-tile select (no dynamic
    single-row store into packed sublanes), and its float32 round trip is
    exact for every pool dtype.
    """
    bs = pages_out[0].shape[1]
    rows = tiles[0].shape[0]
    if rows == bs:
        dsts, row = [p.at[blk] for p in pages_out], off
    else:
        r0 = pl.multiple_of((off // rows) * rows, rows)
        dsts, row = [p.at[blk, pl.ds(r0, rows)] for p in pages_out], off - r0
    reads = [pltpu.make_async_copy(d, t, sems.at[i])
             for i, (d, t) in enumerate(zip(dsts, tiles))]

    def merge(new_rows):
        own = jax.lax.broadcasted_iota(jnp.int32, (rows, 1), 0) == row
        for t, new in zip(tiles, new_rows):
            t[...] = jnp.where(own, new.astype(jnp.float32),
                               t[...].astype(jnp.float32)).astype(t.dtype)
        writes = [pltpu.make_async_copy(t, d, sems.at[i])
                  for i, (d, t) in enumerate(zip(dsts, tiles))]
        for c in writes:
            c.start()
        return writes

    return reads, merge


def _fused_decode_kernel(
    H,                     # static: query heads per token
    D,                     # static: head dim
    # scalar prefetch
    tables_ref,            # [B, NB] int32 block ids
    pos_ref,               # [B] int32 new-token position (0 = inactive lane)
    # inputs
    q_ref,                 # [TB, H, F] raw (unroped) block-diagonal queries
    kn_ref,                # [TB, 1, F] raw fused-lane new-token k
    vn_ref,                # [TB, 1, F] fused-lane new-token v
    cos_ref,               # [TB, 1, F] rope cos, tiled per kv group
    sin_ref,               # [TB, 1, F]
    k_hbm,                 # [num_blocks, bs, F] (ANY/HBM; aliased to k_out)
    v_hbm,
    # outputs
    o_ref,                 # [TB, H, F]
    k_out,                 # aliased page arrays (ANY/HBM)
    v_out,
):
    """Decode step for TB lanes: RoPE the query and the new token's k
    in-kernel, merge the roped k / raw v row into the aligned tile of its
    page, stream the CACHED pages (positions < pos) with online softmax,
    and fold the current token in as one extra softmax update from VMEM —
    so the appended row is never read back from HBM and the append DMA can
    land any time before the program ends.

    All arithmetic is float32: pages are widened as they are used.  (PR 29
    fed the MXU the pool's bf16 instead and measured nothing for it on the
    chip, 2.690 against 2.695 us a lane: the products are not what a
    window waits for.  PERF.md section 6.)

    The program's DMAs form ONE pipeline over its TB lanes:

      * the append-tile reads of all TB lanes are started together at
        program start and waited once; each lane merges its row and starts
        its write-back in its turn; all write-backs are waited once, before
        the program ends;
      * the double buffer runs over the program's windows as one sequence.
        While lane t computes its last window (or at once, when it has
        none) window 0 of lane t+1 is copied into the free slot; ``slot0``
        carries the slot parity across lanes;
      * a window is ``_FUSED_WINDOW`` pages deep, fetched in groups of
        ``_FUSED_GROUP`` with one wait a group and array; groups past the
        lane's pages are neither fetched nor waited for.

    Why nothing races.  A lane appends to its tail block, which it alone
    owns, and prefix blocks that lanes share are never appended to: the
    pages lane t+1 reads early are not the page lane t writes back.  The
    write-back rewrites the tile's other rows with the bytes they had, so
    the lane's own stream may read them meanwhile, and the row at ``pos``
    is masked in the stream (``p_idx < pos``) and folded in from VMEM.
    Inactive lanes (pos == 0) and positions past the table write only rows
    of the null block 0, in no order among themselves; every reader masks
    those rows.  A slot is refilled, or the rows of its unfetched groups
    zeroed, only after the window it held has been multiplied (program
    order); a window's copies are started and waited for over one trip
    count of groups, so every started copy is waited for.

    Inactive lanes stream nothing and write their row to the null block,
    matching models/llama.py:_scatter_pages; their output is finite
    garbage (only the current-token term) that the engine discards.
    """
    TB = q_ref.shape[0]
    b0 = pl.program_id(0) * TB
    bs = k_hbm.shape[1]
    F = q_ref.shape[2]
    NB = tables_ref.shape[1]
    W = min(_FUSED_WINDOW, NB)
    R = _append_tile_rows(bs, k_hbm.dtype)

    def scoped(k_buf, v_buf, k_tiles, v_tiles, sem, append_sem):
        G = _FUSED_GROUP if W % _FUSED_GROUP == 0 else W   # pages a group

        def group_rows(gi):
            return pl.ds(pl.multiple_of(gi * (G * bs), G * bs), G * bs)

        def live_groups(w, n_blk):
            # Groups of window ``w`` that hold pages of a lane with
            # ``n_blk`` pages (at least one: callers ask for windows that
            # exist).
            return jax.lax.min(jax.lax.div(n_blk - w * W + (G - 1), G), W // G)

        def start_window(slot, b, w, n_blk):
            # The page copies of window ``w`` of lane ``b``, both arrays, a
            # group of G a turn of a rolled loop (its trip count is the
            # window's live groups; the body is the parent's 8-page burst,
            # so the kernel's text, which is lowered 28 x 8 times a decode
            # program, does not grow with the window).  A group past the
            # lane's pages is not fetched; its V rows are zeroed instead,
            # because a probability of exactly 0 times whatever an earlier
            # window left there must stay 0 (K's stale rows give scores
            # that the position mask replaces).  A slot's K copies signal
            # one semaphore and its V copies another.
            live = live_groups(w, n_blk)

            def fetch(gi, _):
                j0, row0 = w * W + gi * G, gi * (G * bs)
                for i in range(G):
                    j = j0 + i
                    blk = tables_ref[b, j if NB % W == 0 else
                                     jax.lax.min(j, NB - 1)]
                    rows = pl.ds(pl.multiple_of(row0 + i * bs, bs), bs)
                    pltpu.make_async_copy(k_hbm.at[blk], k_buf.at[slot, rows],
                                          sem.at[slot, 0]).start()
                    pltpu.make_async_copy(v_hbm.at[blk], v_buf.at[slot, rows],
                                          sem.at[slot, 1]).start()
                return 0

            def clear(gi, _):
                v_buf[slot, group_rows(gi), :] = jnp.zeros((G * bs, F),
                                                           v_buf.dtype)
                return 0

            jax.lax.fori_loop(0, live, fetch, 0)
            jax.lax.fori_loop(live, W // G, clear, 0)

        def wait_window(slot, w, n_blk):
            # A DMA semaphore counts what has arrived: one wait sized as a
            # group's rows takes its G page copies' signals (a wait a copy
            # was 2 W scalar-core stalls a window, serial with the window's
            # products).  The same trip count as the start: every started
            # copy is waited for, and nothing else.
            def arrived(gi, _):
                for i, buf in enumerate((k_buf, v_buf)):
                    view = buf.at[slot, group_rows(gi)]
                    pltpu.make_async_copy(view, view, sem.at[slot, i]).wait()
                return 0

            jax.lax.fori_loop(0, live_groups(w, n_blk), arrived, 0)

        pos = [pos_ref[b0 + t] for t in range(TB)]   # cached before this one
        # Pages and windows a lane streams: 0 for an inactive lane.
        n_blocks = [jax.lax.div(p + (bs - 1), bs) for p in pos]
        n_windows = [jax.lax.div(n + (W - 1), W) for n in n_blocks]

        def start_next(slot, t, w):
            # The window after window ``w`` of lane ``t`` (-1: the lane has
            # none) in the program's one sequence goes into ``slot``: this
            # lane's next, or window 0 of lane t + 1, if there is one.
            more = w + 1 < n_windows[t]
            if t + 1 == TB:
                go, lane, n_blk = more, t, n_blocks[t]
            else:
                go = more | (n_windows[t + 1] > 0)
                lane = jax.lax.select(more, jnp.int32(t), jnp.int32(t + 1))
                n_blk = jax.lax.select(more, n_blocks[t], n_blocks[t + 1])

            @pl.when(go)
            def _start():
                nxt = jax.lax.select(more, jnp.int32(w + 1), jnp.int32(0))
                start_window(slot, b0 + lane, nxt, n_blk)

        # --- every lane's append tile: read now, waited once --------------
        merges, reads = [], []
        for t in range(TB):
            raw_blk = pos[t] // bs
            blk = jnp.where(
                (pos[t] > 0) & (raw_blk < NB),
                tables_ref[b0 + t, jnp.minimum(raw_blk, NB - 1)], 0)
            lane_reads, merge = _append_rows(
                (k_out, v_out), (k_tiles.at[t], v_tiles.at[t]), blk,
                jax.lax.rem(pos[t], bs), append_sem.at[t])
            reads += lane_reads
            merges.append(merge)
        for c in reads:
            c.start()

        @pl.when(n_windows[0] > 0)
        def _first():
            start_window(0, b0, 0, n_blocks[0])

        for c in reads:
            c.wait()

        writes = []
        slot0 = jnp.int32(0)           # slot of this lane's window 0
        for t in range(TB):
            n_win = n_windows[t]

            # --- in-kernel RoPE (f32, like ops/rope.py) -------------------
            cos = cos_ref[t].astype(jnp.float32)          # [1, F]
            sin = sin_ref[t].astype(jnp.float32)
            q = q_ref[t].astype(jnp.float32)              # [H, F] block-diag
            # Per-D-slice rotate: a head's slice-g support stays in slice
            # g and zeros rope to zeros, so roping the block-diagonal
            # matrix equals block-diagonalizing the roped heads.
            qf = q * cos + _rotate_half_fused(q, D) * sin
            kn = kn_ref[t].astype(jnp.float32)            # [1, F]
            kf = kn * cos + _rotate_half_fused(kn, D) * sin

            # --- KV append: the write-back overlaps the attention math ----
            writes += merges[t]((kf, vn_ref[t]))

            # --- stream the cached pages (positions < pos) ----------------
            if t + 1 < TB:
                # A lane with no window hands the free slot on at once.
                @pl.when(n_win == 0)
                def _skip(t=t, slot0=slot0):
                    start_next(slot0, t, -1)

            def body(w, carry, t=t, pos=pos[t], slot0=slot0, qf=qf):
                m, l, acc = carry
                slot = jax.lax.rem(slot0 + w, 2)
                start_next(1 - slot, t, w)
                wait_window(slot, w, n_blocks[t])
                p_idx = (w * (W * bs)
                         + jax.lax.broadcasted_iota(jnp.int32, (1, W * bs), 1))
                # The row being appended (p_idx == pos) is masked, so the
                # in-flight append DMA can never race a row we consume.
                valid = p_idx < pos
                kblk = k_buf[slot].astype(jnp.float32)
                vblk = v_buf[slot].astype(jnp.float32)
                s = jax.lax.dot_general(
                    qf, kblk, (((1,), (1,)), ((), ())),
                    preferred_element_type=jnp.float32)
                s = jnp.where(valid, s, NEG_INF)
                m_cur = jnp.max(s, axis=-1, keepdims=True)
                m_new = jnp.maximum(m, m_cur)
                alpha = jnp.exp(m - m_new)
                p = jnp.exp(s - m_new)
                l_new = alpha * l + jnp.sum(p, axis=-1, keepdims=True)
                pv = jax.lax.dot_general(
                    p, vblk, (((1,), (0,)), ((), ())),
                    preferred_element_type=jnp.float32)
                return m_new, l_new, alpha * acc + pv

            m0 = jnp.full((H, 1), NEG_INF, jnp.float32)
            l0 = jnp.zeros((H, 1), jnp.float32)
            acc0 = jnp.zeros((H, F), jnp.float32)
            m, l, acc = jax.lax.fori_loop(0, n_win, body, (m0, l0, acc0))
            slot0 = jax.lax.rem(slot0 + n_win, 2)

            # --- current token: one more online-softmax update from VMEM --
            # Always included (even for inactive lanes) so l > 0 and the
            # output stays finite without the cached window the old
            # gather path borrowed from the null block.
            s_cur = jax.lax.dot_general(
                qf, kf, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32)       # [H, 1]
            m_new = jnp.maximum(m, s_cur)
            alpha = jnp.exp(m - m_new)
            p_cur = jnp.exp(s_cur - m_new)
            l = alpha * l + p_cur
            vf = vn_ref[t].astype(jnp.float32)            # [1, F]
            acc = alpha * acc + p_cur * vf
            o_ref[t] = (acc / l).astype(o_ref.dtype)

        for c in writes:
            c.wait()

    pl.run_scoped(
        scoped,
        k_buf=pltpu.VMEM((2, W * bs, F), k_hbm.dtype),
        v_buf=pltpu.VMEM((2, W * bs, F), v_hbm.dtype),
        k_tiles=pltpu.VMEM((TB, R, F), k_hbm.dtype),
        v_tiles=pltpu.VMEM((TB, R, F), v_hbm.dtype),
        sem=pltpu.SemaphoreType.DMA((2, 2)),
        append_sem=pltpu.SemaphoreType.DMA((TB, 2)),
    )


def paged_decode_attention_fused(
    q: jnp.ndarray,
    k_new: jnp.ndarray,
    v_new: jnp.ndarray,
    cos: jnp.ndarray,
    sin: jnp.ndarray,
    k_pages: jnp.ndarray,
    v_pages: jnp.ndarray,
    block_table: jnp.ndarray,
    positions: jnp.ndarray,
    *,
    interpret: bool = False,
) -> tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Fused decode step: RoPE + KV append + paged attention in one call.

    Replaces the decode-path sequence apply_rope -> _scatter_pages ->
    paged_decode_attention (models/llama.py) with a single Pallas kernel:
    the query/new-k rotary embedding runs in-kernel, the new token's K/V
    row is DMA'd into its page from VMEM (no XLA scatter over the full
    page arrays), and attention streams only the CACHED pages, folding
    the current token in from registers.  The page outputs alias the
    inputs (in-place update) so the engine's donated KV buffers are
    never copied.

    Args:
      q: [B, 1, H, D] raw (unroped) queries.
      k_new, v_new: [B, 1, KVH, D] raw new-token projections (k unroped).
      cos, sin: [B, 1, D] rope angle tables at each lane's position
        (ops/rope.py:rope_angles of ``positions``).
      k_pages, v_pages: [num_blocks, bs, KVH*D] resident page arrays.
      block_table: [B, max_blocks_per_seq] int32 (0 = null block).
      positions: [B] int32 — tokens already cached per lane, i.e. the new
        token's absolute position; 0 marks an inactive lane whose write
        is redirected to the null block (same as _scatter_pages).
      interpret: run in the Pallas interpreter (CPU parity tests).

    Returns:
      (attn [B, 1, H, D], updated k_pages, updated v_pages).
    """
    B, S, H, D = q.shape
    assert S == 1, f"fused decode kernel expects one query token, got {S}"
    nblk, bs, F = k_pages.shape
    assert F % D == 0 and D % 2 == 0 and D <= 128, (F, D)
    KVH = F // D
    q_per_kv = H // KVH

    group = jnp.arange(H, dtype=jnp.int32) // q_per_kv
    onehot = jax.nn.one_hot(group, KVH, dtype=q.dtype)
    # Raw block-diagonal queries; RoPE commutes with the D**-0.5 scale and
    # acts within each D-slice, so roping this matrix in-kernel is exact.
    q_bd = (q[:, 0, :, None, :] * (D ** -0.5)
            * onehot[None, :, :, None]).reshape(B, H, F)
    kn = k_new.reshape(B, 1, F)
    vn = v_new.reshape(B, 1, F)
    cos_f = jnp.tile(cos.astype(jnp.float32), (1, 1, KVH))     # [B, 1, F]
    sin_f = jnp.tile(sin.astype(jnp.float32), (1, 1, KVH))

    budget = 4 * 2**20 // max(H * F * q.dtype.itemsize, 1)
    TB = next(tb for tb in (8, 4, 2, 1)
              if B % tb == 0 and (B // tb >= 2 or B == 1)
              and (tb <= budget or tb == 1))
    lane_spec = lambda p, tbl, pos: (p, 0, 0)  # noqa: E731
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B // TB,),
        in_specs=[
            pl.BlockSpec((TB, H, F), lane_spec),
            pl.BlockSpec((TB, 1, F), lane_spec),
            pl.BlockSpec((TB, 1, F), lane_spec),
            pl.BlockSpec((TB, 1, F), lane_spec),
            pl.BlockSpec((TB, 1, F), lane_spec),
            pl.BlockSpec(memory_space=pl.ANY),   # K pages stay in HBM
            pl.BlockSpec(memory_space=pl.ANY),   # V pages stay in HBM
        ],
        out_specs=[
            pl.BlockSpec((TB, H, F), lane_spec),
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
    )

    out_full, k_out, v_out = pl.pallas_call(
        functools.partial(_fused_decode_kernel, H, D),
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((B, H, F), q.dtype),
            jax.ShapeDtypeStruct(k_pages.shape, k_pages.dtype),
            jax.ShapeDtypeStruct(v_pages.shape, v_pages.dtype),
        ],
        # Page arrays update in place: inputs 7/8 (after the 2 scalar-
        # prefetch operands) alias outputs 1/2.
        input_output_aliases={7: 1, 8: 2},
        compiler_params=pltpu.CompilerParams(
            # Lanes append to blocks they own (the allocator hands out
            # distinct tail blocks; only never-read null-block rows race),
            # so the tile grid stays megacore-parallel like the decode
            # kernel.
            dimension_semantics=("parallel",),
        ),
        interpret=interpret,
        name="paged_decode_attention_fused",
    )(block_table, positions.astype(jnp.int32), q_bd, kn, vn, cos_f, sin_f,
      k_pages, v_pages)

    out = jnp.take_along_axis(
        out_full.reshape(B, 1, H, KVH, D),
        group[None, None, :, None, None], axis=3)[:, :, :, 0, :]
    return out, k_out, v_out


# Marker consumed by models/llama.py:decode_step to select the fused
# calling convention (raw q/k/v + angles in, pages out).
paged_decode_attention_fused.fused_decode = True


# ---------------------------------------------------------------------------
# Quantized-KV fused decode: quantize-on-append + dequantize-in-kernel
# ---------------------------------------------------------------------------


def _window_scales(scale, block_table, W):
    """Gather a scale plane through the block table into per-window slabs.

    scale [num_blocks, bs, KVH] f32 -> [B, NWIN, KVH, W*bs]: window ``w`` of
    lane ``b`` holds the scales of table slots ``w*W .. w*W+W-1`` with the
    token axis on lanes.  The kernels take these as ordinary VMEM blocks:
    a ``[bs, KVH]`` scale page (KVH lanes of 128) is not a shape the chip's
    DMA slices, and the scales are 1/(2*D) of the page bytes, so the XLA
    gather costs noise next to the page stream it sits beside.
    """
    B, NB = block_table.shape
    _, bs, KVH = scale.shape
    nwin = -(-NB // W)
    table = jnp.pad(block_table, ((0, 0), (0, nwin * W - NB)))
    g = scale[table]                                  # [B, nwin*W, bs, KVH]
    return g.reshape(B, nwin, W * bs, KVH).transpose(0, 1, 3, 2)


def _fused_decode_quant_kernel(
    H,                     # static: query heads per token
    D,                     # static: head dim
    KVH,                   # static: kv heads (= F // D)
    qmax,                  # static: quant range (127 int8 / 448 fp8)
    is_int8,               # static: round+clip vs saturating fp8 cast
    # scalar prefetch
    tables_ref,            # [B, NB] int32 block ids
    pos_ref,               # [B] int32 new-token position (0 = inactive lane)
    # inputs
    q_ref,                 # [TB, H, F] raw (unroped) block-diagonal queries
    kn_ref,                # [TB, 1, F] raw fused-lane new-token k
    vn_ref,                # [TB, 1, F]
    cos_ref,               # [TB, 1, F]
    sin_ref,               # [TB, 1, F]
    ks_ref,                # [TB, NWIN, KVH, W*bs] f32 cached-token k scales
    vs_ref,                # same (see _window_scales)
    k_hbm,                 # [num_blocks, bs, F] quantized (aliased to k_out)
    v_hbm,
    # outputs
    o_ref,                 # [TB, H, F]
    ksn_ref,               # [TB, KVH, 1] f32 new-token k scales
    vsn_ref,
    k_out,
    v_out,
):
    """Quantized twin of ``_fused_decode_kernel``.

    Dequantization never expands scales to the F lane dim for the cached
    pages: per-(token, head) K scales factor out of ``q @ k^T`` (the
    block-diagonal q restricts head h to its own kv group's lanes), so the
    score matrix is rescaled by ``scale_bd[h, j] = ks[group(h), j]``.  V
    scales fold into the probabilities the same way: ``acc += (p * vs_bd)
    @ v_q`` is exact for each head's own group slice (other slices carry
    garbage the caller slices away) while the softmax denominator uses the
    unscaled ``p``.

    The appended token is quantized in-kernel (per-head amax over its
    D-slice) and folded into the softmax as dequantize(quantize(k)) — bit
    parity with the gather path, which reads the row back dequantized.
    Its codes merge into the page tile like the unquantized kernel's row;
    its scales leave as a small output the wrapper scatters into the
    scale planes.
    """
    TB = q_ref.shape[0]
    b0 = pl.program_id(0) * TB
    bs = k_hbm.shape[1]
    F = q_ref.shape[2]
    NB = tables_ref.shape[1]
    W = min(_WINDOW, NB)
    R = _append_tile_rows(bs, k_hbm.dtype)
    # Constant index maps: lane j belongs to kv group j // D; head h reads
    # group h // (H // KVH).
    lane_group = jax.lax.broadcasted_iota(jnp.int32, (KVH, F), 1) // D
    grp_row = jax.lax.broadcasted_iota(jnp.int32, (KVH, F), 0)
    own_lane = lane_group == grp_row                            # [KVH, F]
    head_grp = (jax.lax.broadcasted_iota(jnp.int32, (H, 1), 0)
                // max(H // KVH, 1))                            # [H, 1]

    def _per_head(win):
        """[KVH, W*bs] group scales -> [H, W*bs], head h taking its
        group's row (a select chain: exact, and no KVH-deep MXU dot)."""
        out = jnp.zeros((H, win.shape[1]), jnp.float32)
        for g in range(KVH):
            out = jnp.where(head_grp == g, win[g:g + 1, :], out)
        return out

    def _quantize_row(xf):
        """xf [1, F] float -> (codes [1, F] float pre-cast, scale [KVH, 1],
        dequantized [1, F] f32)."""
        amax = jnp.max(jnp.where(own_lane, jnp.abs(xf), 0.0), axis=1,
                       keepdims=True)                           # [KVH, 1]
        scale = jnp.maximum(amax / qmax, 1e-8)
        # Lane-expand: scale_lane[0, j] = scale[group(j)].
        scale_lane = jnp.sum(jnp.where(own_lane, scale, 0.0), axis=0,
                             keepdims=True)                     # [1, F]
        xq = xf / scale_lane
        if is_int8:
            xq = jnp.clip(jnp.round(xq), -qmax, qmax)
        else:
            # The stored code is the fp8-rounded value: dequantize that,
            # not the unrounded quotient.
            xq = xq.astype(k_hbm.dtype).astype(jnp.float32)
        return xq, scale, xq * scale_lane

    def scoped(k_buf, v_buf, k_tile, v_tile, sem, append_sem):
        def start_window(slot, b, w):
            for i in range(W):
                j = jnp.minimum(w * W + i, NB - 1)
                blk = tables_ref[b, j]
                pltpu.make_async_copy(
                    k_hbm.at[blk], k_buf.at[slot, pl.ds(i * bs, bs)],
                    sem.at[slot, i, 0]).start()
                pltpu.make_async_copy(
                    v_hbm.at[blk], v_buf.at[slot, pl.ds(i * bs, bs)],
                    sem.at[slot, i, 1]).start()

        def wait_window(slot, b, w):
            for i in range(W):
                j = jnp.minimum(w * W + i, NB - 1)
                blk = tables_ref[b, j]
                pltpu.make_async_copy(
                    k_hbm.at[blk], k_buf.at[slot, pl.ds(i * bs, bs)],
                    sem.at[slot, i, 0]).wait()
                pltpu.make_async_copy(
                    v_hbm.at[blk], v_buf.at[slot, pl.ds(i * bs, bs)],
                    sem.at[slot, i, 1]).wait()

        for t in range(TB):
            b = b0 + t
            pos = pos_ref[b]
            active = pos > 0

            cos = cos_ref[t].astype(jnp.float32)
            sin = sin_ref[t].astype(jnp.float32)
            q = q_ref[t].astype(jnp.float32)
            qf = q * cos + _rotate_half_fused(q, D) * sin
            kn = kn_ref[t].astype(jnp.float32)
            kf = kn * cos + _rotate_half_fused(kn, D) * sin
            vf = vn_ref[t].astype(jnp.float32)

            # --- quantize-on-append (per-head amax over the D-slice) ------
            kq, k_scale, kdeq = _quantize_row(kf)
            vq, v_scale, vdeq = _quantize_row(vf)
            ksn_ref[t] = k_scale
            vsn_ref[t] = v_scale

            raw_blk = pos // bs
            in_table = raw_blk < NB
            blk = jnp.where(active & in_table,
                            tables_ref[b, jnp.minimum(raw_blk, NB - 1)], 0)
            off = jax.lax.rem(pos, bs)
            reads, merge = _append_rows((k_out, v_out), (k_tile, v_tile),
                                        blk, off, append_sem)
            for c in reads:
                c.start()
            for c in reads:
                c.wait()
            appends = merge((kq, vq))

            n_blocks = (pos + bs - 1) // bs
            n_windows = (n_blocks + W - 1) // W

            @pl.when(n_windows > 0)
            def _first():
                start_window(0, b, 0)

            def body(w, carry, b=b, t=t, pos=pos, n_windows=n_windows):
                m, l, acc = carry
                slot = jax.lax.rem(w, 2)

                @pl.when(w + 1 < n_windows)
                def _prefetch():
                    start_window(1 - slot, b, w + 1)

                wait_window(slot, b, w)
                p_idx = (w * (W * bs)
                         + jax.lax.broadcasted_iota(jnp.int32, (1, W * bs), 1))
                valid = p_idx < pos
                kblk = k_buf[slot].astype(jnp.float32)      # quantized codes
                vblk = v_buf[slot].astype(jnp.float32)
                # K scales factor out of the contraction: scale_bd[h, j] =
                # ks[group(h), j].
                ks_bd = _per_head(ks_ref[t, w])             # [H, W*bs]
                vs_bd = _per_head(vs_ref[t, w])
                s = jax.lax.dot_general(
                    qf, kblk, (((1,), (1,)), ((), ())),
                    preferred_element_type=jnp.float32) * ks_bd
                s = jnp.where(valid, s, NEG_INF)
                m_cur = jnp.max(s, axis=-1, keepdims=True)
                m_new = jnp.maximum(m, m_cur)
                alpha = jnp.exp(m - m_new)
                p = jnp.exp(s - m_new)
                l_new = alpha * l + jnp.sum(p, axis=-1, keepdims=True)
                # V scales fold into p; exact on each head's own group
                # slice, garbage elsewhere (sliced away by the caller).
                pv = jax.lax.dot_general(
                    p * vs_bd, vblk, (((1,), (0,)), ((), ())),
                    preferred_element_type=jnp.float32)
                return m_new, l_new, alpha * acc + pv

            m0 = jnp.full((H, 1), NEG_INF, jnp.float32)
            l0 = jnp.zeros((H, 1), jnp.float32)
            acc0 = jnp.zeros((H, F), jnp.float32)
            m, l, acc = jax.lax.fori_loop(0, n_windows, body, (m0, l0, acc0))

            # Current token folded as dequant(quant(.)) — parity with the
            # gather path reading the row back.
            s_cur = jax.lax.dot_general(
                qf, kdeq, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32)         # [H, 1]
            m_new = jnp.maximum(m, s_cur)
            alpha = jnp.exp(m - m_new)
            p_cur = jnp.exp(s_cur - m_new)
            l = alpha * l + p_cur
            acc = alpha * acc + p_cur * vdeq

            for c in appends:
                c.wait()
            o_ref[t] = (acc / l).astype(o_ref.dtype)

    pl.run_scoped(
        scoped,
        k_buf=pltpu.VMEM((2, W * bs, F), k_hbm.dtype),
        v_buf=pltpu.VMEM((2, W * bs, F), v_hbm.dtype),
        k_tile=pltpu.VMEM((R, F), k_hbm.dtype),
        v_tile=pltpu.VMEM((R, F), v_hbm.dtype),
        sem=pltpu.SemaphoreType.DMA((2, W, 2)),
        append_sem=pltpu.SemaphoreType.DMA((2,)),
    )


def paged_decode_attention_fused_quant(
    q: jnp.ndarray,
    k_new: jnp.ndarray,
    v_new: jnp.ndarray,
    cos: jnp.ndarray,
    sin: jnp.ndarray,
    k_pages: jnp.ndarray,
    v_pages: jnp.ndarray,
    k_scale: jnp.ndarray,
    v_scale: jnp.ndarray,
    block_table: jnp.ndarray,
    positions: jnp.ndarray,
    *,
    interpret: bool = False,
) -> tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Quantized-KV fused decode step (see ``paged_decode_attention_fused``).

    Identical calling convention plus the per-(token, head) float32 scale
    arrays ``k_scale``/``v_scale`` [num_blocks, bs, KVH].  The code pages
    alias their outputs and update in place inside the kernel; the scale
    planes are read through an XLA gather (``_window_scales``) and take the
    new token's scales by an XLA scatter, which is equally in place on the
    engine's donated pool — traceguard asserts the rebinding exactly as for
    the fp16 pool.

    Returns:
      (attn [B, 1, H, D], k_pages, v_pages, k_scale, v_scale) — the four
      pool arrays updated in place.
    """
    B, S, H, D = q.shape
    assert S == 1, f"fused decode kernel expects one query token, got {S}"
    nblk, bs, F = k_pages.shape
    assert F % D == 0 and D % 2 == 0 and D <= 128, (F, D)
    KVH = F // D
    q_per_kv = H // KVH
    NB = block_table.shape[1]
    W = min(_WINDOW, NB)
    qmax = 127.0 if jnp.dtype(k_pages.dtype) == jnp.int8 else 448.0
    is_int8 = jnp.dtype(k_pages.dtype) == jnp.int8
    positions = positions.astype(jnp.int32)

    group = jnp.arange(H, dtype=jnp.int32) // q_per_kv
    onehot = jax.nn.one_hot(group, KVH, dtype=q.dtype)
    q_bd = (q[:, 0, :, None, :] * (D ** -0.5)
            * onehot[None, :, :, None]).reshape(B, H, F)
    kn = k_new.reshape(B, 1, F)
    vn = v_new.reshape(B, 1, F)
    cos_f = jnp.tile(cos.astype(jnp.float32), (1, 1, KVH))
    sin_f = jnp.tile(sin.astype(jnp.float32), (1, 1, KVH))
    ks_win = _window_scales(k_scale, block_table, W)
    vs_win = _window_scales(v_scale, block_table, W)

    budget = 4 * 2**20 // max(H * F * q.dtype.itemsize, 1)
    TB = next(tb for tb in (8, 4, 2, 1)
              if B % tb == 0 and (B // tb >= 2 or B == 1)
              and (tb <= budget or tb == 1))
    lane_spec = lambda p, tbl, pos: (p, 0, 0)  # noqa: E731
    win_spec = pl.BlockSpec((TB,) + ks_win.shape[1:],
                            lambda p, tbl, pos: (p, 0, 0, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B // TB,),
        in_specs=[
            pl.BlockSpec((TB, H, F), lane_spec),
            pl.BlockSpec((TB, 1, F), lane_spec),
            pl.BlockSpec((TB, 1, F), lane_spec),
            pl.BlockSpec((TB, 1, F), lane_spec),
            pl.BlockSpec((TB, 1, F), lane_spec),
            win_spec,                            # gathered K scales (VMEM)
            win_spec,                            # gathered V scales (VMEM)
            pl.BlockSpec(memory_space=pl.ANY),   # K pages stay in HBM
            pl.BlockSpec(memory_space=pl.ANY),   # V pages stay in HBM
        ],
        out_specs=[
            pl.BlockSpec((TB, H, F), lane_spec),
            pl.BlockSpec((TB, KVH, 1), lane_spec),
            pl.BlockSpec((TB, KVH, 1), lane_spec),
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
    )

    out_full, ks_new, vs_new, k_out, v_out = pl.pallas_call(
        functools.partial(_fused_decode_quant_kernel, H, D, KVH, qmax,
                          is_int8),
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((B, H, F), q.dtype),
            jax.ShapeDtypeStruct((B, KVH, 1), jnp.float32),
            jax.ShapeDtypeStruct((B, KVH, 1), jnp.float32),
            jax.ShapeDtypeStruct(k_pages.shape, k_pages.dtype),
            jax.ShapeDtypeStruct(v_pages.shape, v_pages.dtype),
        ],
        # Code pages update in place: inputs 9/10 (after the 2 scalar-
        # prefetch operands) alias outputs 3/4.
        input_output_aliases={9: 3, 10: 4},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",),
        ),
        interpret=interpret,
        name="paged_decode_attention_fused_quant",
    )(block_table, positions, q_bd, kn, vn, cos_f, sin_f, ks_win, vs_win,
      k_pages, v_pages)

    # New-token scales land where the kernel put the codes: the lane's
    # tail block at ``pos % bs``, the null block for inactive lanes and
    # positions past the table (models/llama.py:_scatter_pages).
    raw_blk = positions // bs
    blk = jnp.take_along_axis(
        block_table, jnp.clip(raw_blk, 0, NB - 1)[:, None], axis=1)[:, 0]
    blk = jnp.where((positions > 0) & (raw_blk < NB), blk, 0)
    off = positions % bs
    ks_out = k_scale.at[blk, off].set(ks_new[:, :, 0])
    vs_out = v_scale.at[blk, off].set(vs_new[:, :, 0])

    out = jnp.take_along_axis(
        out_full.reshape(B, 1, H, KVH, D),
        group[None, None, :, None, None], axis=3)[:, :, :, 0, :]
    return out, k_out, v_out, ks_out, vs_out


# Markers: fused calling convention + quantized-pool variant
# (models/llama.py:is_fused_decode_impl / is_fused_quant_decode_impl).
paged_decode_attention_fused_quant.fused_decode = True
paged_decode_attention_fused_quant.quant_kv = True


@functools.partial(jax.jit, static_argnames=("interpret",))
def paged_verify_attention_pallas(
    q: jnp.ndarray,
    k_pages: jnp.ndarray,
    v_pages: jnp.ndarray,
    block_table: jnp.ndarray,
    start: jnp.ndarray,
    lengths: jnp.ndarray,
    *,
    interpret: bool = False,
) -> jnp.ndarray:
    """Multi-query paged attention for speculative verify / small chunks.

    Query token ``i`` of sequence ``b`` sits at absolute position
    ``start[b] + i`` and attends causally through itself; the chunk's K/V
    must already be scattered into the pages.  Streams each sequence's
    pages ONCE for all S queries — vs the XLA gather fallback's
    O(B * max_blocks * bs) traffic, and vs S separate decode-kernel calls'
    S-fold re-streaming.

    Args:
      q: [B, S, H, D] (S small — the spec draft length + 1).
      start: [B] int32 tokens already cached before this chunk.
      lengths: [B] int32 valid query tokens (0 = inactive lane; its rows
        compute against the null block and are discarded by the caller).

    Returns:
      [B, S, H, D] in q.dtype.
    """
    return _run_paged_attn(q, k_pages, v_pages, block_table,
                           start.astype(jnp.int32),
                           lengths.astype(jnp.int32), interpret)


# ---------------------------------------------------------------------------
# Flash paged prefill: tiled online softmax straight off the paged pool
# ---------------------------------------------------------------------------


def _flash_prefill_kernel(
    TQ,                    # static: query tokens per tile
    D,                     # static: head dim
    KVH,                   # static: kv heads (= F // D)
    qpk,                   # static: query heads per kv group
    quant,                 # static: dequantize-in-kernel from scale planes
    stream,                # static: query tiles of a packed stream (below)
    # scalar prefetch
    tables_ref,            # [B, NB] int32 block ids
    starts_ref,            # [B] int32 cached tokens before this chunk
    qlens_ref,             # [B] int32 valid query tokens (0 = inactive lane)
    *refs,                 # (tile_row_ref, tile_t_ref,) q_ref, k_hbm, v_hbm,
                           # (ks_ref, vs_ref,) o_ref
):
    """One program: one query tile of one sequence for one kv group.

    Unlike the decode/verify kernels, whose [QS*H, F] block-diagonal query
    costs KVH x redundant MXU work per extra query row, prefill has TQ up
    to 128 query rows live at once — so the grid splits the kv-head axis
    instead (grid = (B, KVH, n_tiles)) and each program DMAs only its own
    group's D-lane slice of every page row.  The group's qpk query heads
    stack on the sublane axis ([qpk*TQ, D]), giving dense MXU dots with no
    cross-head waste at any GQA ratio.

    Scores for a [TQ, W*bs] window tile are reduced into running
    (max, sum, acc) online-softmax carries — the [S, T] score matrix is
    never materialized, which is what lets 8k/32k buckets fit where the
    dense path's [B, H, S, T] float32 logits cannot.

    ``quant``: pages hold int8/fp8 codes; the per-(token, head) scales
    arrive gathered through the block table as VMEM window slabs
    ([1, NWIN, KVH, W*bs], see ``_window_scales``) and this group's row is
    picked with a select.  K scales factor out of ``q @ k^T`` onto the
    score tile; V scales fold into the probabilities, exactly the
    ``_fused_decode_quant_kernel`` convention.

    ``stream`` (``flash_prefill_attention_packed``): the queries are one
    tile-aligned stream ``[KVH, NT*TQ, qpk*D]`` of several sequences' tiles
    end to end and the grid is (KVH, NT); two more prefetched arrays say
    which sequence tile i is of (``tile_row_ref`` [NT]) and which of that
    sequence's tiles it is (``tile_t_ref`` [NT]).  The tile's arithmetic is
    the same.  Else q_ref is [1, 1, TQ, qpk*D], this (seq, group, tile)
    slab of ``[B, KVH, S, qpk*D]``.
    """
    if stream:
        tile_row_ref, tile_t_ref, q_ref, k_hbm, v_hbm, *rest = refs
    else:
        q_ref, k_hbm, v_hbm, *rest = refs
    if quant:
        ks_ref, vs_ref, o_ref = rest
    else:
        (o_ref,) = rest
    if stream:
        g = pl.program_id(0)
        b = tile_row_ref[pl.program_id(1)]
        t = tile_t_ref[pl.program_id(1)]
    else:
        b = pl.program_id(0)
        g = pl.program_id(1)                     # kv group this program owns
        t = pl.program_id(2)                     # query tile index
    bs = k_hbm.shape[1]
    NB = tables_ref.shape[1]
    W = min(_WINDOW, NB)
    R = qpk * TQ                                 # stacked query rows
    start = starts_ref[b]
    qlen = qlens_ref[b]

    # Row r of the stacked [R, D] query tile is head r // TQ at tile-local
    # offset r % TQ; its causal horizon is the absolute query position.
    row_off = jax.lax.rem(
        jax.lax.broadcasted_iota(jnp.int32, (R, 1), 0), TQ)
    q_bound = start + t * TQ + row_off

    # Pages to stream: everything through this tile's last valid query
    # position.  Dead tiles (inactive lane, or wholly past qlen) stream
    # exactly one page so every wait has a matching start; their rows are
    # garbage the caller never reads.
    live = (qlen > 0) & (t * TQ < qlen)
    ctx = jnp.where(live, start + jnp.minimum((t + 1) * TQ, qlen), 1)
    n_blocks = (ctx + bs - 1) // bs
    n_windows = (n_blocks + W - 1) // W

    if quant:
        own_row = jax.lax.broadcasted_iota(jnp.int32, (KVH, 1), 0) == g

        def _group_scales(win):                  # [KVH, W*bs] -> [1, W*bs]
            return jnp.sum(jnp.where(own_row, win, 0.0), axis=0,
                           keepdims=True)

    qt = (q_ref[0] if stream else q_ref[0, 0]).astype(jnp.float32)
    # [TQ, qpk*D]
    q2 = jnp.concatenate(
        [qt[:, j * D:(j + 1) * D] for j in range(qpk)], axis=0)  # [R, D]

    def scoped(k_buf, v_buf, sem):
        # k_buf/v_buf: [2, W*bs, D] double-buffered page-slice slabs —
        # only this group's D lanes ever leave HBM.
        def start_window(slot, w):
            for i in range(W):
                j = jnp.minimum(w * W + i, NB - 1)
                blk = tables_ref[b, j]
                pltpu.make_async_copy(
                    k_hbm.at[blk, :, pl.ds(g * D, D)],
                    k_buf.at[slot, pl.ds(i * bs, bs)],
                    sem.at[slot, i, 0]).start()
                pltpu.make_async_copy(
                    v_hbm.at[blk, :, pl.ds(g * D, D)],
                    v_buf.at[slot, pl.ds(i * bs, bs)],
                    sem.at[slot, i, 1]).start()

        def wait_window(slot, w):
            for i in range(W):
                j = jnp.minimum(w * W + i, NB - 1)
                blk = tables_ref[b, j]
                pltpu.make_async_copy(
                    k_hbm.at[blk, :, pl.ds(g * D, D)],
                    k_buf.at[slot, pl.ds(i * bs, bs)],
                    sem.at[slot, i, 0]).wait()
                pltpu.make_async_copy(
                    v_hbm.at[blk, :, pl.ds(g * D, D)],
                    v_buf.at[slot, pl.ds(i * bs, bs)],
                    sem.at[slot, i, 1]).wait()

        start_window(0, 0)                       # n_windows >= 1 always

        def body(w, carry):
            m, l, acc = carry
            slot = jax.lax.rem(w, 2)

            @pl.when(w + 1 < n_windows)
            def _prefetch():
                start_window(1 - slot, w + 1)

            wait_window(slot, w)
            p_idx = (w * (W * bs)
                     + jax.lax.broadcasted_iota(jnp.int32, (1, W * bs), 1))
            valid = p_idx <= q_bound             # causal, absolute positions
            kblk = k_buf[slot].astype(jnp.float32)          # [W*bs, D]
            vblk = v_buf[slot].astype(jnp.float32)
            s = jax.lax.dot_general(
                q2, kblk, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32)         # [R, W*bs]
            if quant:
                s = s * _group_scales(ks_ref[0, w])
            s = jnp.where(valid, s, NEG_INF)
            m_cur = jnp.max(s, axis=-1, keepdims=True)
            m_new = jnp.maximum(m, m_cur)
            alpha = jnp.exp(m - m_new)
            p = jnp.exp(s - m_new)
            l_new = alpha * l + jnp.sum(p, axis=-1, keepdims=True)
            if quant:
                p = p * _group_scales(vs_ref[0, w])
            pv = jax.lax.dot_general(
                p, vblk, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)         # [R, D]
            return m_new, l_new, alpha * acc + pv

        m0 = jnp.full((R, 1), NEG_INF, jnp.float32)
        l0 = jnp.zeros((R, 1), jnp.float32)
        acc0 = jnp.zeros((R, D), jnp.float32)
        m, l, acc = jax.lax.fori_loop(0, n_windows, body, (m0, l0, acc0))
        # Position 0 is always causally visible, so l > 0 on every row;
        # the guard only hardens against a fully-degenerate table.
        out = acc / jnp.where(l > 0.0, l, 1.0)
        for j in range(qpk):
            if stream:
                o_ref[0, :, j * D:(j + 1) * D] = out[
                    j * TQ:(j + 1) * TQ].astype(o_ref.dtype)
            else:
                o_ref[0, 0, :, j * D:(j + 1) * D] = out[
                    j * TQ:(j + 1) * TQ].astype(o_ref.dtype)

    pl.run_scoped(
        scoped,
        k_buf=pltpu.VMEM((2, W * bs, D), k_hbm.dtype),
        v_buf=pltpu.VMEM((2, W * bs, D), v_hbm.dtype),
        sem=pltpu.SemaphoreType.DMA((2, W, 2)),
    )


def flash_prefill_attention(
    q: jnp.ndarray,
    k_pages: jnp.ndarray,
    v_pages: jnp.ndarray,
    block_table: jnp.ndarray,
    start: jnp.ndarray,
    lengths: jnp.ndarray,
    *,
    k_scale: jnp.ndarray | None = None,
    v_scale: jnp.ndarray | None = None,
    interpret: bool = False,
) -> jnp.ndarray:
    """Causal prefill attention reading K/V straight from the paged pool.

    Query token ``i`` of sequence ``b`` sits at absolute position
    ``start[b] + i`` and attends causally through itself — the same
    geometry contract as ``paged_verify_attention_pallas``, but tiled for
    bucket-sized S: queries split into TQ-token tiles (largest power of two
    in 8..128 dividing S, after padding S to a multiple of 8), scores
    reduce through online-softmax carries, and the ``[S, T]`` score matrix
    is never materialized.  The chunk's own K/V
    must already be scattered into the pages (models/llama.py scatters
    before attention), which is what collapses fresh prefill
    (``start = 0``), continuation chunks, and spec verify into one kernel.

    ``k_scale``/``v_scale`` ([num_blocks, bs, KVH] float32) switch on
    in-kernel dequantization of int8/fp8 pages — the quantized pool never
    widens in HBM (only the scales are gathered ahead of the kernel).

    Args:
      q: [B, S, H, D] (S = prefill bucket).
      start: [B] int32 tokens already cached before this chunk (0 = fresh).
      lengths: [B] int32 valid query tokens (0 = inactive lane; its rows
        compute against the null block and are discarded by the caller).

    Returns:
      [B, S, H, D] in q.dtype.
    """
    B, S, H, D = q.shape
    nblk, bs, F = k_pages.shape
    assert F % D == 0 and D <= 128, (F, D)
    KVH = F // D
    assert H % KVH == 0, (H, KVH)
    qpk = H // KVH
    quant = k_scale is not None
    # Mosaic tiles the last two block dims (8, 128): the query tile must
    # span a multiple of 8 rows, so a bucket no power of two >= 8 divides
    # (spec verify's S = k+1) pads up to the next multiple of 8.  Pad rows
    # sit past ``lengths`` — dead rows the slice below drops.
    Sp = -(-S // 8) * 8
    TQ = next(tt for tt in (128, 64, 32, 16, 8) if Sp % tt == 0)
    NQ = Sp // TQ

    # Head order is group-major (head h serves kv group h // qpk), so a
    # plain reshape lands each group's qpk heads on contiguous D-lane
    # slices; the kv-group axis then moves ahead of the token axis so the
    # block's last two dims are (TQ, qpk*D) — a size-1 KVH block in the
    # second-to-last place is not a shape the TPU lowering accepts.
    qg = (q * (D ** -0.5)).reshape(B, S, KVH, qpk * D).transpose(0, 2, 1, 3)
    if Sp != S:
        qg = jnp.pad(qg, ((0, 0), (0, 0), (0, Sp - S), (0, 0)))

    def qmap(b, g, t, *_):
        return (b, g, t, 0)

    scale_ops, scale_specs = [], []
    if quant:
        W = min(_WINDOW, block_table.shape[1])
        scale_ops = [_window_scales(k_scale, block_table, W),
                     _window_scales(v_scale, block_table, W)]
        scale_specs = [pl.BlockSpec((1,) + scale_ops[0].shape[1:],
                                    lambda b, g, t, *_: (b, 0, 0, 0))] * 2

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(B, KVH, NQ),
        in_specs=[
            pl.BlockSpec((1, 1, TQ, qpk * D), qmap),
            pl.BlockSpec(memory_space=pl.ANY),   # K pages stay in HBM
            pl.BlockSpec(memory_space=pl.ANY),   # V pages stay in HBM
        ] + scale_specs,
        out_specs=pl.BlockSpec((1, 1, TQ, qpk * D), qmap),
    )

    operands = [block_table, start.astype(jnp.int32),
                lengths.astype(jnp.int32), qg, k_pages, v_pages] + scale_ops
    out = pl.pallas_call(
        functools.partial(_flash_prefill_kernel, TQ, D, KVH, qpk, quant,
                          False),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, KVH, Sp, qpk * D), q.dtype),
        compiler_params=pltpu.CompilerParams(
            # Programs are fully independent (read-only pages, disjoint
            # output tiles): megacore may split any grid axis.
            dimension_semantics=("parallel", "parallel", "parallel"),
        ),
        interpret=interpret,
        name="flash_prefill_attention_quant" if quant
        else "flash_prefill_attention",
    )(*operands)
    return out[:, :, :S].transpose(0, 2, 1, 3).reshape(B, S, H, D)


def flash_prefill_attention_packed(
    q: jnp.ndarray,
    k_pages: jnp.ndarray,
    v_pages: jnp.ndarray,
    block_table: jnp.ndarray,
    offset: jnp.ndarray,
    lengths: jnp.ndarray,
    *,
    k_scale: jnp.ndarray | None = None,
    v_scale: jnp.ndarray | None = None,
    interpret: bool = False,
) -> jnp.ndarray:
    """``flash_prefill_attention`` of a fresh call's packed stream
    (models/llama.py:prefill_packed): the same kernel over the same query
    tiles, with no ``[R, S]`` row view of the queries in between.

    The stream's queries are gathered into a tile-aligned stream — every
    segment starts a TQ-token tile of its own, ``T/TQ + R`` tiles hold any
    packing — and the grid runs over that stream's tiles: a tile is of one
    sequence, which a prefetched array names, so the only dead tiles are
    the stream's own tail.  A row view costs R x S query rows of copies and
    R x S / TQ grid steps a layer whatever the call holds (at Qwen2-7B
    widths: half of a T = 2,048 call's time, PERF.md section 6, PR 27);
    this costs two gathers of T rows.

    Args:
      q: [T, H, D], segment r at ``q[offset[r]:offset[r] + lengths[r]]``
        (token i of it at position i; its K/V already in the pages).
      block_table: [R, NB]; offset, lengths: [R] int32 (0 = idle row).

    Returns:
      [T, H, D] in q.dtype; rows of no segment are garbage.
    """
    T, H, D = q.shape
    nblk, bs, F = k_pages.shape
    assert F % D == 0 and D <= 128, (F, D)
    KVH = F // D
    assert H % KVH == 0, (H, KVH)
    qpk = H // KVH
    quant = k_scale is not None
    R = offset.shape[0]
    Tp = -(-T // 8) * 8
    TQ = next(tt for tt in (128, 64, 32, 16, 8) if Tp % tt == 0)
    i32 = jnp.int32
    offset, lengths = offset.astype(i32), lengths.astype(i32)
    NT, tile_row, tile_t, _, src, back = _stream_tiles(offset, lengths, T, TQ)

    qg = (q * (D ** -0.5)).reshape(T, KVH, qpk * D)[src].transpose(1, 0, 2)

    def qmap(g, i, *_):
        return (g, i, 0)

    scale_ops, scale_specs = [], []
    if quant:
        W = min(_WINDOW, block_table.shape[1])
        scale_ops = [_window_scales(k_scale, block_table, W),
                     _window_scales(v_scale, block_table, W)]
        scale_specs = [pl.BlockSpec(
            (1,) + scale_ops[0].shape[1:],
            lambda g, i, tables, starts, qlens, rows, ts: (rows[i], 0, 0, 0)
        )] * 2

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=5,
        grid=(KVH, NT),
        in_specs=[
            pl.BlockSpec((1, TQ, qpk * D), qmap),
            pl.BlockSpec(memory_space=pl.ANY),   # K pages stay in HBM
            pl.BlockSpec(memory_space=pl.ANY),   # V pages stay in HBM
        ] + scale_specs,
        out_specs=pl.BlockSpec((1, TQ, qpk * D), qmap),
    )
    out = pl.pallas_call(
        functools.partial(_flash_prefill_kernel, TQ, D, KVH, qpk, quant,
                          True),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((KVH, NT * TQ, qpk * D), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel"),
        ),
        interpret=interpret,
        name="flash_prefill_attention_quant" if quant
        else "flash_prefill_attention",
    )(block_table, jnp.zeros((R,), i32), lengths, tile_row, tile_t,
      qg, k_pages, v_pages, *scale_ops)
    return out.transpose(1, 0, 2)[back].reshape(T, H, D)


# Marker consumed by models/llama.py:is_flash_prefill_impl — the prefill
# family routes all three geometries (fresh/chunk/verify) through this
# calling convention, passing scale planes for quantized pools.  ``packed``:
# the same kernel's form for a fresh call's packed stream.
flash_prefill_attention.flash_prefill = True
flash_prefill_attention.packed = flash_prefill_attention_packed
