"""Fused decode fast-path (ops/pallas_attention.py:paged_decode_attention_fused).

Covers the three tentpole layers:

  * kernel numerics in Pallas interpreter mode against the gather oracle
    (apply_rope -> _scatter_pages -> paged_decode_attention), including
    page boundaries, ragged lanes, the null-block inactive encoding, past-
    table redirect, and bf16; the pipeline across a program's 8 lanes and
    several windows a lane, and the served cell's geometry;
  * path selection (ops/attention.py:select_decode_impl mode gating) and
    greedy token-stream identity fused-vs-gather through
    models/llama.py:decode_step;
  * bounded on-device sampling (ops/sampling.py:sample_tokens_bounded)
    against the full-vocab distribution, and the pipelined engine
    (dispatch-ahead step()) preserving per-request streams under
    cancel/preemption.
"""

import functools

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from k8s_llm_monitor_tpu.models import llama
from k8s_llm_monitor_tpu.models.config import ModelConfig
from k8s_llm_monitor_tpu.ops.attention import (
    paged_decode_attention,
    select_decode_impl,
)
from k8s_llm_monitor_tpu.ops.pallas_attention import (
    paged_decode_attention_fused,
)
from k8s_llm_monitor_tpu.ops.rope import apply_rope, rope_angles
from k8s_llm_monitor_tpu.ops.sampling import (
    filtered_scaled_logits,
    sample_tokens_bounded,
)
from k8s_llm_monitor_tpu.serving.engine import (
    EngineConfig,
    GenerationRequest,
    InferenceEngine,
    SamplingParams,
)

THETA = 10_000.0

# Tiny engine config (head_dim 8: rope-compatible but fails the Mosaic
# 128-lane gate) and a fused-eligible one (KVH * D = 2 * 64 = 128).
CFG = ModelConfig(name="t", vocab_size=300, hidden_size=32,
                  intermediate_size=64, num_layers=2, num_heads=4,
                  num_kv_heads=2, dtype="float32", rope_theta=THETA)
CFG_FUSED_OK = ModelConfig(name="g", vocab_size=128, hidden_size=256,
                           intermediate_size=256, num_layers=1, num_heads=4,
                           num_kv_heads=2, dtype="float32", rope_theta=THETA)


@pytest.fixture(scope="module")
def params():
    return llama.init_params(jax.random.PRNGKey(0), CFG)


def _naive_greedy(params, prompt, n):
    seq = list(prompt)
    for _ in range(n):
        logits = llama.forward_full(params, CFG, jnp.asarray([seq], jnp.int32))
        seq.append(int(jnp.argmax(logits[0, -1])))
    return seq[len(prompt):]


# ---------------------------------------------------------------------------
# Kernel numerics vs the gather oracle
# ---------------------------------------------------------------------------


def _fused_case(rng, B, H, KVH, D, bs, max_blocks, positions,
                dtype=jnp.float32):
    """Random decode state with explicit per-lane positions.

    Lanes with position 0 are inactive (all-zero table row, the engine's
    encoding); active lanes get distinct non-null blocks covering their
    append target (mirrors serving/kv_cache.py).
    """
    num_blocks = B * max_blocks + 2
    q = jnp.asarray(rng.standard_normal((B, 1, H, D)), dtype)
    k_new = jnp.asarray(rng.standard_normal((B, 1, KVH, D)), dtype)
    v_new = jnp.asarray(rng.standard_normal((B, 1, KVH, D)), dtype)
    k_pages = jnp.asarray(
        rng.standard_normal((num_blocks, bs, KVH * D)), dtype)
    v_pages = jnp.asarray(
        rng.standard_normal((num_blocks, bs, KVH * D)), dtype)
    table = np.zeros((B, max_blocks), np.int32)
    next_free = 1
    for b in range(B):
        used = min(int(positions[b]) // bs + 1, max_blocks)
        if positions[b] > 0:
            table[b, :used] = np.arange(next_free, next_free + used)
            next_free += used
    assert next_free <= num_blocks, "test sized the pool too small"
    return (q, k_new, v_new, k_pages, v_pages, jnp.asarray(table),
            jnp.asarray(np.asarray(positions, np.int32)))


def _gather_reference(q, k_new, v_new, k_pages, v_pages, table, positions):
    """The split path exactly as models/llama.py:decode_step runs it."""
    D = q.shape[-1]
    pos = positions[:, None]
    active = (positions > 0)[:, None]
    cos, sin = rope_angles(pos, D, THETA)
    q_r = apply_rope(q, cos, sin)
    k_r = apply_rope(k_new, cos, sin)
    pk = llama._scatter_pages(k_pages, k_r, table, pos, active)
    pv = llama._scatter_pages(v_pages, v_new, table, pos, active)
    attn = paged_decode_attention(q_r, pk, pv, table, positions + 1)
    return attn, pk, pv


def _run_fused(q, k_new, v_new, k_pages, v_pages, table, positions):
    D = q.shape[-1]
    cos, sin = rope_angles(positions[:, None], D, THETA)
    return paged_decode_attention_fused(
        q, k_new, v_new, cos, sin, k_pages, v_pages, table, positions,
        interpret=True)


@pytest.mark.parametrize("B,H,KVH,D,bs,max_blocks", [
    (4, 8, 8, 64, 16, 4),     # MHA
    (4, 8, 2, 64, 16, 4),     # GQA 4:1
    (2, 16, 4, 128, 8, 6),    # GQA, D=128
    (1, 4, 1, 32, 4, 3),      # MQA-ish, tiny
])
def test_fused_matches_gather_reference(B, H, KVH, D, bs, max_blocks):
    rng = np.random.default_rng(B * 1000 + H + KVH + D)
    positions = rng.integers(1, max_blocks * bs - 1, size=(B,))
    if B >= 4:
        positions[1] = 0                       # one inactive lane
    case = _fused_case(rng, B, H, KVH, D, bs, max_blocks, positions)

    want, wk, wv = _gather_reference(*case)
    got, gk, gv = _run_fused(*case)

    act = np.asarray(positions) > 0
    np.testing.assert_allclose(np.asarray(got)[act], np.asarray(want)[act],
                               rtol=2e-5, atol=2e-5)
    # The append must land identically everywhere — including the
    # inactive lane's null-block redirect.
    np.testing.assert_allclose(np.asarray(gk), np.asarray(wk),
                               rtol=2e-5, atol=2e-5)
    np.testing.assert_allclose(np.asarray(gv), np.asarray(wv),
                               rtol=2e-5, atol=2e-5)


def test_fused_page_boundaries_and_null_redirect():
    """Positions straddling every block edge, the inactive encoding, and a
    past-table lane whose append must redirect to the null block."""
    B, H, KVH, D, bs, max_blocks = 8, 8, 4, 64, 8, 4
    rng = np.random.default_rng(7)
    #            inactive | first | block edges      | last row | past table
    positions = np.array([0, 1, 7, 8, 15, 16, bs * max_blocks - 1,
                          bs * max_blocks])
    case = _fused_case(rng, B, H, KVH, D, bs, max_blocks, positions)
    # Give the past-table lane a full table (its append overflows it).
    table = np.asarray(case[5]).copy()
    table[7, :] = np.arange(40, 40 + max_blocks)
    case = case[:5] + (jnp.asarray(table), case[6])

    want, wk, wv = _gather_reference(*case)
    got, gk, gv = _run_fused(*case)

    # Attention: active, table-covered lanes (the past-table lane's gather
    # reference would read beyond its table).
    cmp = (positions > 0) & (positions < bs * max_blocks)
    assert not np.any(np.isnan(np.asarray(got)[positions > 0]))
    np.testing.assert_allclose(np.asarray(got)[cmp], np.asarray(want)[cmp],
                               rtol=2e-5, atol=2e-5)
    np.testing.assert_allclose(np.asarray(gk), np.asarray(wk),
                               rtol=2e-5, atol=2e-5)
    np.testing.assert_allclose(np.asarray(gv), np.asarray(wv),
                               rtol=2e-5, atol=2e-5)


def test_fused_bf16():
    B, H, KVH, D, bs, max_blocks = 4, 8, 2, 64, 16, 4
    rng = np.random.default_rng(3)
    positions = rng.integers(1, max_blocks * bs - 1, size=(B,))
    case = _fused_case(rng, B, H, KVH, D, bs, max_blocks, positions,
                       dtype=jnp.bfloat16)

    want, wk, wv = _gather_reference(*case)
    got, gk, gv = _run_fused(*case)

    np.testing.assert_allclose(
        np.asarray(got, np.float32), np.asarray(want, np.float32),
        rtol=2e-2, atol=2e-2)
    np.testing.assert_allclose(
        np.asarray(gk, np.float32), np.asarray(wk, np.float32),
        rtol=2e-2, atol=2e-2)


# The append is an aligned read-modify-write of the sublane tile holding the
# new row (PR 21: Mosaic refuses a one-row DMA into a tiled page).  Rows:
# 8 of 32-bit, 16 of 16-bit, capped at the page — so (f32, bs=16) and
# (bf16, bs=32) take the partial-tile branch with a dynamic aligned offset,
# and (bf16, bs=16) writes the whole page back.
@pytest.mark.parametrize("dtype,bs,rows", [
    (jnp.float32, 16, 8),
    (jnp.bfloat16, 32, 16),
    (jnp.bfloat16, 16, 16),
], ids=["f32-bs16-tile8", "bf16-bs32-tile16", "bf16-bs16-page"])
def test_fused_append_tile_read_modify_write_is_byte_exact(dtype, bs, rows):
    """``off`` at the first and last row of a tile, on both sides of a tile
    boundary and of a block boundary: the appended row lands byte-for-byte
    where the gather oracle's scatter puts it, and every other row of every
    page comes back untouched."""
    from k8s_llm_monitor_tpu.ops.pallas_attention import _append_tile_rows

    assert _append_tile_rows(bs, dtype) == rows
    B, H, KVH, D, max_blocks = 8, 4, 2, 64, 3
    rng = np.random.default_rng(rows * 100 + bs)
    #          tile first | tile last | next tile | block last | next block
    positions = np.array([0, 1, rows - 1, rows, bs - 1, bs, bs + rows - 1,
                          2 * bs])
    positions[0] = bs + 1          # and one interior row (no inactive lane)
    case = _fused_case(rng, B, H, KVH, D, bs, max_blocks, positions,
                       dtype=dtype)
    want, wk, wv = _gather_reference(*case)
    got, gk, gv = _run_fused(*case)

    # V is appended unroped: byte-exact everywhere.  K is roped in-kernel
    # in float32 like ops/rope.py, so its appended rows agree to the last
    # bit in float32 and to one rounding in bfloat16; all other rows are
    # the read-modify-write's untouched bytes.
    assert np.array_equal(np.asarray(gv, np.float32),
                          np.asarray(wv, np.float32))
    k_pages, table = np.asarray(case[3], np.float32), np.asarray(case[5])
    gk32, wk32 = np.asarray(gk, np.float32), np.asarray(wk, np.float32)
    touched = np.zeros(gk32.shape[:2], bool)
    for b, pos in enumerate(positions):
        touched[table[b, pos // bs], pos % bs] = True
    assert np.array_equal(gk32[~touched], k_pages[~touched])
    tol = 2e-5 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(gk32[touched], wk32[touched],
                               rtol=tol, atol=tol)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               rtol=tol, atol=tol)


# ---------------------------------------------------------------------------
# The pipeline the served cell runs: 8 lanes a program, several windows a lane
# ---------------------------------------------------------------------------

# Small widths keep the interpreter quick; the pipeline's shape is the cell's:
# 16 lanes = two programs of TB = 8, a table of 2 W + 2 blocks, so lanes run
# one, two or three windows and the double buffer's second slot, the prefetch
# across a lane boundary and the slot parity a lane hands on are all taken.
_PIPE_B, _PIPE_H, _PIPE_KVH, _PIPE_D, _PIPE_BS = 16, 4, 2, 64, 4


def _pipe_geometry():
    from k8s_llm_monitor_tpu.ops import pallas_attention as pa

    W = pa._FUSED_WINDOW
    return W, 2 * W + 2                       # W, max_blocks (>= 2 W + 1)


def _windows_to_positions(rng, counts, span, cap):
    """A position that makes a lane stream ``n`` windows of ``span`` tokens:
    anywhere in ``((n - 1) * span, n * span]`` below the table's capacity;
    0 windows = inactive."""
    return [0 if n == 0 else int(rng.integers(
        (n - 1) * span + 1, min(n * span + 1, cap))) for n in counts]


def _pipe_positions(name, rng):
    W, NB = _pipe_geometry()
    span, cap = W * _PIPE_BS, NB * _PIPE_BS
    some = lambda n: list(rng.integers(1, cap - 1, size=n))  # noqa: E731
    if name == "inactive-between-active":
        pos = [span + 3, 0, 2 * span + 1, 0, 0, 5] + some(10)
        pos[9], pos[11] = 0, 0
    elif name == "inactive-program-first":
        pos = [0] * 8 + some(8)
    elif name == "inactive-program-last":
        pos = some(8) + [0] * 8
    elif name == "one-cached-token":
        pos = [1, 1, span, 1] + some(3) + [1] + [1] * 4 + some(4)
    elif name == "window-edges":
        pos = [span - 1, span, span + 1, 2 * span - 1, 2 * span,
               2 * span + 1, 1, span] + some(8)
    elif name == "table-end":
        # The append lands in the last table slot (first and last row) and
        # one past it: raw_blk == NB, redirected to the null block.
        pos = some(3) + [cap - _PIPE_BS, cap - 1, cap] + some(7) + [
            cap, 0, cap - 1]
    elif name == "slot-parity":
        # Odd and even window counts side by side, a lane without windows
        # in between: each lane starts in the slot the last one left.
        pos = _windows_to_positions(
            rng, [1, 2, 1, 3, 2, 2, 3, 1, 3, 3, 1, 1, 2, 0, 2, 1], span, cap)
    else:
        raise AssertionError(name)
    assert len(pos) == _PIPE_B
    return np.asarray(pos, np.int64)


# Jitted once: a dtype is one trace, the position patterns are data.
_FUSED_INTERPRETED = jax.jit(functools.partial(paged_decode_attention_fused,
                                               interpret=True))


@pytest.mark.parametrize("dtype,tol", [(jnp.float32, 2e-5),
                                       (jnp.bfloat16, 2e-2)],
                         ids=["f32", "bf16"])
@pytest.mark.parametrize("pattern", [
    "inactive-between-active", "inactive-program-first",
    "inactive-program-last", "one-cached-token", "window-edges", "table-end",
    "slot-parity"])
def test_fused_pipeline_across_lanes_and_windows(pattern, dtype, tol):
    """Attention on active lanes and BOTH page arrays everywhere agree with
    the split path when a program's DMAs are one pipeline over its lanes.
    (Lanes that write the null block all write its row 0 here, in lane
    order in the interpreter as in the oracle's scatter; on the chip those
    writes are unordered, and nothing reads them unmasked.)"""
    W, NB = _pipe_geometry()
    rng = np.random.default_rng(sum(map(ord, pattern)))
    positions = _pipe_positions(pattern, rng)
    cap = NB * _PIPE_BS
    case = _fused_case(rng, _PIPE_B, _PIPE_H, _PIPE_KVH, _PIPE_D, _PIPE_BS,
                       NB, positions, dtype=dtype)
    assert _PIPE_B % 8 == 0 and NB >= 2 * W + 1

    want, wk, wv = _gather_reference(*case)
    q, k_new, v_new, k_pages, v_pages, table, pos = case
    cos, sin = rope_angles(pos[:, None], _PIPE_D, THETA)
    got, gk, gv = _FUSED_INTERPRETED(q, k_new, v_new, cos, sin, k_pages,
                                     v_pages, table, pos)

    # A lane past its table has no oracle for its attention (the gather
    # would read beyond the table); its append is compared like the rest.
    cmp = (positions > 0) & (positions < cap)
    got32 = np.asarray(got, np.float32)
    assert np.isfinite(got32).all()
    np.testing.assert_allclose(got32[cmp], np.asarray(want, np.float32)[cmp],
                               rtol=tol, atol=tol)
    # K is roped in float32 and stored in the pool's dtype on both sides
    # (one rounding apart in bf16); V is stored as it came: bit-equal.
    np.testing.assert_allclose(np.asarray(gk, np.float32),
                               np.asarray(wk, np.float32), rtol=tol, atol=tol)
    assert np.array_equal(np.asarray(gv, np.float32),
                          np.asarray(wv, np.float32))


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32],
                         ids=["bf16", "f32"])
def test_fused_at_the_cell_geometry_against_the_float32_oracle(dtype):
    """Qwen2-7B's heads and the served cell's pool geometry (28 q / 4 kv x
    128, blocks of 16, 96 table slots = 1,536 tokens; lanes of one window,
    of three, of a dead fetch group, and with one cached token) against the
    gather oracle run in FLOAT32 on the same values.

    Tolerance for a bf16 pool, 2e-2 absolute plus 2e-2 relative.  The
    kernel widens pages as it uses them and does its arithmetic in float32;
    between it and the oracle stand three bf16 roundings (each at most
    2**-9 = 0.002 relative): the wrapper scales the query by D**-0.5 in the
    model's dtype (scores of unit variance move by ~1e-3, the softmax
    weights by ~0.1%), and the result and the appended key row are stored.
    On a lane with one cached token the result is of order 1 and all of
    that shows: 0.007 absolute is what this case reads, a third of the
    bound.  A wrong mask, page, head slice, slot or fetch group is an
    error of order 0.1 to 1.  A float32 pool holds today's 2e-5."""
    from k8s_llm_monitor_tpu.ops import pallas_attention as pa

    B, H, KVH, D, bs, NB = 4, 28, 4, 128, 16, 96
    rng = np.random.default_rng(29)
    span = bs * min(pa._FUSED_WINDOW, NB)
    positions = np.asarray([NB * bs - 1, 1, 700, span + 1])
    case = _fused_case(rng, B, H, KVH, D, bs, NB, positions, dtype=dtype)
    want, wk, wv = _gather_reference(*(
        x.astype(jnp.float32) if x.dtype == dtype else x for x in case))
    q, k_new, v_new, k_pages, v_pages, table, pos = case
    cos, sin = rope_angles(pos[:, None], D, THETA)
    got, gk, gv = _FUSED_INTERPRETED(q, k_new, v_new, cos, sin, k_pages,
                                     v_pages, table, pos)

    rtol = atol = 2e-2 if dtype == jnp.bfloat16 else 2e-5
    np.testing.assert_allclose(np.asarray(got, np.float32), np.asarray(want),
                               rtol=rtol, atol=atol)
    np.testing.assert_allclose(np.asarray(gk, np.float32), np.asarray(wk),
                               rtol=rtol, atol=atol)
    assert np.array_equal(np.asarray(gv, np.float32), np.asarray(wv))


def _product_operand_dtypes(jaxpr, inside_loop=False):
    """``(inside a loop, operand dtypes)`` of every ``dot_general`` under
    ``jaxpr``, sub-jaxprs (the kernel, its scope, its loops) included."""
    found = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "dot_general":
            found.append((inside_loop,
                          tuple(v.aval.dtype for v in eqn.invars)))
        looped = inside_loop or eqn.primitive.name in ("while", "scan")
        for sub in jax.core.jaxprs_in_params(eqn.params):
            found += _product_operand_dtypes(sub, looped)
    return found


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32],
                         ids=["bf16", "f32"])
def test_fused_kernel_window_products_take_float32_operands(dtype):
    """What the MXU is fed, read off the kernel's jaxpr: both products of a
    window (the ``dot_general``s inside the window loop) take float32
    operands for a bf16 pool as for a float32 one.  PR 29 fed them the
    pool's bf16 and measured nothing for it on the chip (2.695 against
    2.690 us a lane; PERF.md section 6), so the arithmetic stayed what the
    parity tests above hold.  Whoever changes the operands changes this
    test with a chip measurement in hand."""
    case = _fused_case(np.random.default_rng(0), 2, 4, 2, 64, 16, 4,
                       np.asarray([17, 40]), dtype=dtype)
    q, k_new, v_new, k_pages, v_pages, table, pos = case
    cos, sin = rope_angles(pos[:, None], 64, THETA)
    jaxpr = jax.make_jaxpr(paged_decode_attention_fused)(
        q, k_new, v_new, cos, sin, k_pages, v_pages, table, pos).jaxpr
    window = [ops for looped, ops in _product_operand_dtypes(jaxpr)
              if looped]
    assert window and len(window) % 2 == 0, window   # two a lane of a program
    assert all(ops == (jnp.float32, jnp.float32) for ops in window), window


# ---------------------------------------------------------------------------
# Path selection + decode_step stream identity
# ---------------------------------------------------------------------------


def test_select_decode_impl_modes():
    assert select_decode_impl(cfg=CFG_FUSED_OK, mode="gather") \
        is paged_decode_attention
    fused = select_decode_impl(cfg=CFG_FUSED_OK, mode="fused")
    assert llama.is_fused_decode_impl(fused)
    # auto on the CPU backend never picks fused (interpret in a scan).
    auto = select_decode_impl(cfg=CFG_FUSED_OK, mode="auto")
    assert not llama.is_fused_decode_impl(auto)
    with pytest.raises(ValueError):
        select_decode_impl(cfg=CFG, mode="fused")        # lane misalignment
    with pytest.raises(ValueError):
        select_decode_impl(cfg=CFG_FUSED_OK, mesh=object(), mode="fused")
    with pytest.raises(ValueError):
        select_decode_impl(cfg=CFG_FUSED_OK, mode="nope")


def test_greedy_stream_identity_fused_vs_gather(params):
    """decode_step over several steps (crossing a page boundary, reading
    back rows the kernel itself appended) must emit the same greedy stream
    on both paths — the ISSUE's acceptance assertion."""
    B, bs, width, n_steps = 4, 4, 6, 8
    fused_impl = functools.partial(paged_decode_attention_fused,
                                   interpret=True)
    assert llama.is_fused_decode_impl(fused_impl)

    rng = np.random.default_rng(5)
    table = jnp.asarray(
        np.arange(1, 1 + B * width).reshape(B, width).astype(np.int32))
    tokens0 = jnp.asarray(rng.integers(3, 300, size=(B,)), jnp.int32)

    streams, finals = {}, {}
    for name, impl in (("fused", fused_impl),
                       ("gather", paged_decode_attention)):
        pages = llama.init_kv_pages(CFG, 1 + B * width + 1, bs)
        ctx = jnp.ones((B,), jnp.int32)
        tokens = tokens0
        out = []
        for _ in range(n_steps):
            logits, pages = llama.decode_step(
                params, CFG, tokens, ctx, pages, table, attn_impl=impl)
            tokens = jnp.argmax(logits, -1).astype(jnp.int32)
            ctx = ctx + 1
            out.append(np.asarray(tokens))
        streams[name] = np.stack(out)
        finals[name] = pages

    np.testing.assert_array_equal(streams["fused"], streams["gather"])
    for fk, gk in zip(finals["fused"].k, finals["gather"].k):
        np.testing.assert_allclose(np.asarray(fk), np.asarray(gk),
                                   rtol=2e-5, atol=2e-5)


# ---------------------------------------------------------------------------
# Bounded on-device sampling
# ---------------------------------------------------------------------------


def test_sample_tokens_bounded_matches_full_distribution():
    """Empirical frequencies of the k_cap-bounded sampler must match the
    full-vocab filtered distribution; greedy lanes stay exact argmax."""
    B, V, cap, n_draws = 3, 64, 8, 4000
    rng = np.random.default_rng(0)
    logits = jnp.asarray(rng.standard_normal((B, V)) * 3.0, jnp.float32)
    temp = jnp.asarray([0.7, 1.3, 0.0], jnp.float32)
    topk = jnp.asarray([5, 8, 4], jnp.int32)
    topp = jnp.asarray([0.8, 1.0, 0.9], jnp.float32)

    want = jax.nn.softmax(filtered_scaled_logits(
        logits, temperature=temp, top_k=topk, top_p=topp), axis=-1)
    keys = jax.random.split(jax.random.PRNGKey(0), n_draws)
    draws = np.asarray(jax.vmap(
        lambda k: sample_tokens_bounded(
            k, logits, temperature=temp, top_k=topk, top_p=topp, k_cap=cap)
    )(keys))

    assert (draws[:, 2] == int(jnp.argmax(logits[2]))).all()
    for b in (0, 1):
        counts = np.bincount(draws[:, b], minlength=V) / n_draws
        wp = np.asarray(want[b])
        # Support containment: the bounded sampler can never emit a token
        # the full filter assigns zero mass.
        assert set(np.nonzero(counts)[0]) <= set(np.nonzero(wp > 0)[0])
        np.testing.assert_allclose(counts, wp, atol=0.03)


def test_engine_bounded_sampling_reproducible(params):
    """top_k within sample_topk_cap routes decode through the bounded
    program; two engines with the same seed must emit identical streams."""
    rng = np.random.default_rng(3)
    prompts = [list(rng.integers(3, 300, size=6)) for _ in range(2)]
    sp = SamplingParams(max_tokens=6, temperature=0.8, top_k=5, top_p=0.9)
    outs = []
    for _ in range(2):
        eng = InferenceEngine(
            CFG, params,
            EngineConfig(max_slots=2, num_blocks=64, block_size=8,
                         max_blocks_per_seq=16, prefill_buckets=(16,),
                         sample_topk_cap=8),
            eos_id=-1, seed=7)
        res = eng.generate(prompts, sp)
        assert all(0 <= t < CFG.vocab_size
                   for r in res for t in r.token_ids)
        # White-box: the bounded variant actually compiled.  Decode keys
        # are (n_steps, sampled, bounded, constrained); spec programs use
        # ("spec", ...) keys.
        assert any(key[1] and key[2]
                   for key in eng._decode_cache if key[0] != "spec")
        outs.append([r.token_ids for r in res])
    assert outs[0] == outs[1]


# ---------------------------------------------------------------------------
# Pipelined engine: streams survive cancel + preemption
# ---------------------------------------------------------------------------


def test_pipelined_step_preserves_streams_under_cancel_and_preemption(params):
    """Dispatch-ahead step() (max_inflight=2, opportunistic ready-drain)
    with a page pool tight enough to force preemption and a mid-flight
    cancel: every surviving request's stream must equal naive greedy, and
    the cancelled request's partial stream must be a prefix of it."""
    eng = InferenceEngine(
        CFG, params,
        EngineConfig(max_slots=3, num_blocks=14, block_size=4,
                     max_blocks_per_seq=16, prefill_buckets=(16,),
                     max_inflight=2,
                     # No prefix cache: retained prefixes would make the
                     # final no-leak accounting non-strict.
                     prefix_cache_entries=0),
        eos_id=-1)
    assert eng.ecfg.max_inflight >= 2
    rng = np.random.default_rng(2)
    prompts = [list(rng.integers(3, 300, size=7)) for _ in range(5)]
    n_gen = 24
    for i, p in enumerate(prompts):
        eng.submit(GenerationRequest(
            request_id=f"r{i}", prompt_ids=p,
            sampling=SamplingParams(max_tokens=n_gen)))

    def _slot(rid):
        return next((s for s in eng._slots
                     if s is not None and s.req.request_id == rid), None)

    # Step until r1 is mid-decode (some tokens reconciled, not finished),
    # then cancel it while decode calls for it may still be in flight.
    for _ in range(50):
        eng.step()
        s = _slot("r1")
        if s is not None and len(s.generated) >= 1:
            break
    assert eng.cancel("r1")
    while eng.has_work:
        eng.step()

    assert eng.preemptions > 0, "pool was not tight enough to preempt"
    for i, p in enumerate(prompts):
        res = eng.poll(f"r{i}")
        assert res is not None
        naive = _naive_greedy(params, p, n_gen)
        if i == 1:
            assert res.finish_reason != "error" or res.token_ids == []
            assert res.token_ids == naive[:len(res.token_ids)], \
                "cancelled stream is not a naive-greedy prefix"
        else:
            assert res.finish_reason == "length"
            assert res.token_ids == naive, f"r{i} diverged from naive"
    assert eng.allocator.free_blocks == eng.allocator.num_blocks - 1
