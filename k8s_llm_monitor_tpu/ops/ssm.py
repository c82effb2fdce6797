"""Mamba-2 state-space mixing: the recurrence in its two serving forms.

The recurrence, per head h (group g = h // (H / G)), state ``S_h`` [P, N]:

    S_h[t] = exp(dt_h[t] * A_h) * S_h[t-1] + dt_h[t] * x_h[t] (x) B_g[t]
    y_h[t] = S_h[t] . C_g[t]                       (the D * x term is the caller's)

* ``ssm_chunk_scan`` — admission: the chunked form (products inside chunks of
  ``chunk`` tokens, a short carry of one state a chunk across them), over
  rows or over one packed stream whose segments start where ``first`` says.
  XLA operations only (einsums the compiler tiles itself), so it has no
  kernel name and no roofline share.
* ``ssm_decode_update`` — decode: one token a lane against the per-lane state
  pool, a Pallas kernel that reads and writes each lane's state once, in
  place (``input_output_aliases``).  ``ssm_decode_update_xla`` is the same in
  XLA operations: the oracle, and what runs off the TPU.

**The pool's layout.**  A lane's state is kept transposed and ``pack`` heads
to a row: ``[lanes, H / pack, N, pack * P]`` float32 (``pack_state``), the
same bytes as ``[lanes, H, P, N]``.  With N on sublanes and two 64-wide heads
across the 128 lanes, everything the update needs per row is in its natural
register form: ``dt * x`` and the decay are rows (``[1, pack * P]``,
broadcast down the sublanes), the result ``y`` is a sum down the sublanes
that lands as a row, and only ``B`` and ``C`` — one pair a *group*, shared by
its 16 heads — have to be turned into columns.  In ``[H, P, N]`` each head
needs ``dt * x`` as a column and yields ``y`` as one: a lane reduction and a
lane broadcast for each of the state's 1,024 registers a lane-layer.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

F32 = jnp.float32


def state_pack(heads: int, groups: int, head_dim: int) -> int:
    """Heads kept side by side in one row of the pool: the most that fit
    128 lanes, a power of two that divides a group's heads (a row never
    straddles two groups' ``B`` and ``C``)."""
    pack = 1
    while (pack * 2 * head_dim <= 128
           and (heads // groups) % (pack * 2) == 0):
        pack *= 2
    return pack


def pack_state(s: jnp.ndarray, pack: int) -> jnp.ndarray:
    """``[..., H, P, N]`` (the recurrence as written) -> the pool's
    ``[..., H / pack, N, pack * P]``."""
    *lead, H, P, N = s.shape
    s = s.reshape(*lead, H // pack, pack, P, N)
    s = jnp.moveaxis(s, -1, -3)                      # [..., R, N, pack, P]
    return s.reshape(*lead, H // pack, N, pack * P)


def unpack_state(s: jnp.ndarray, pack: int) -> jnp.ndarray:
    """The inverse of ``pack_state``."""
    *lead, R, N, W = s.shape
    s = s.reshape(*lead, R, N, pack, W // pack)
    s = jnp.moveaxis(s, -3, -1)                      # [..., R, pack, P, N]
    return s.reshape(*lead, R * pack, W // pack, N)


# ---------------------------------------------------------------------------
# Admission: the chunked scan
# ---------------------------------------------------------------------------


def ssm_chunk_scan(x, dt, A, Bm, Cm, first, last, *, chunk: int,
                   block_chunks: int = 8):
    """The recurrence over whole sequences, from a zero state.

    Args:
      x:  [Bt, S, H, P] inputs.  Their dtype is the products' operand dtype
        (bfloat16 on the chip: one pass of the MXU, float32 sums); the decays
        and the carried state are float32 whatever it is.
      dt: [Bt, S, H] float32 step sizes, **0 at padding** (a step of 0 decays
        nothing and adds nothing: padding leaves the state as the last real
        token left it).
      A:  [H] float32, negative.
      Bm, Cm: [Bt, S, G, N].
      first: [Bt, S] bool — the token starts a sequence (its state starts
        from zero): position 0 of a row, of a packed segment.
      last: [R] int32 flat indices into ``Bt * S`` — where each wanted final
        state is taken (a sequence's last real token).
      chunk: tokens a chunk.
      block_chunks: chunks worked on at once.  The products inside chunks
        are ``[chunks, chunk, chunk, H]`` float32: a ``lax.scan`` over blocks
        of chunks bounds them (and every other temporary of the scan) by
        the block, not by the call's tokens.

    Returns:
      (y [Bt, S, H, P] in x's dtype, states [R, H, P, N] float32).
    """
    Bt, S, H, P = x.shape
    G, N = Bm.shape[-2:]
    K, R, dtype = H // G, last.shape[0], x.dtype
    Q = min(chunk, S)
    nC = -(-S // Q)
    cb = min(block_chunks, nC)
    nB = -(-nC // cb)
    St = nB * cb * Q                                   # tokens, padded
    b_r, s_r = last // S, last % S                     # before any padding
    if St != S:
        widen = lambda a: jnp.pad(  # noqa: E731
            a, [(0, 0), (0, St - S)] + [(0, 0)] * (a.ndim - 2))
        x, dt, Bm, Cm, first = (widen(a) for a in (x, dt, Bm, Cm, first))
    # Tokens i and j share a sequence when no sequence starts in (j, i].
    seq = jnp.cumsum(first.astype(jnp.int32), axis=1)
    blocks = lambda a, *tail: jnp.moveaxis(  # noqa: E731
        a.reshape(Bt, nB, cb, Q, *tail), 1, 0)
    xs = (blocks(x, G, K, P), blocks(dt.astype(F32), G, K), blocks(Bm, G, N),
          blocks(Cm, G, N), blocks(seq), jnp.arange(nB))
    A = A.astype(F32).reshape(G, K)
    iota = jnp.arange(Q)
    c_r, i_r = s_r // Q, s_r % Q
    at = jnp.arange(R)

    def block(carry, xs_b):
        state, seq_before, states = carry   # [Bt,G,K,P,N], [Bt], [R,G,K,P,N]
        x_b, dt_b, B_b, C_b, seq_b, nb = xs_b
        cum = jnp.cumsum(dt_b * A, axis=2)          # log decay, in the chunk
        # Inside a chunk: y_i += sum_j exp(cum_i - cum_j) (C_i . B_j) dt_j x_j.
        same = ((seq_b[:, :, :, None] == seq_b[:, :, None, :])
                & (iota[:, None] >= iota[None, :]))          # [Bt,cb,Q,Q]
        decay = jnp.exp(jnp.where(
            same[..., None, None],
            cum[:, :, :, None] - cum[:, :, None, :], -jnp.inf))
        cb_ = jnp.einsum("bcign,bcjgn->bcijg", C_b, B_b,
                         preferred_element_type=F32)
        w = (decay * cb_[..., None] * dt_b[:, :, None]).astype(dtype)
        y = jnp.einsum("bcijgk,bcjgkp->bcigkp", w, x_b,
                       preferred_element_type=F32)
        # A chunk's own contribution to the state at its end (of the
        # sequence its last token belongs to), what it lets through of the
        # carry, and the carry into each chunk of the block.
        seq_end = seq_b[:, :, -1]                               # [Bt, cb]
        seq_in = jnp.concatenate([seq_before[:, None], seq_end[:, :-1]],
                                 axis=1)
        w_end = jnp.exp(jnp.where(
            (seq_b == seq_end[:, :, None])[..., None, None],
            cum[:, :, -1:] - cum, -jnp.inf)) * dt_b
        local = jnp.einsum("bcjgkp,bcjgn->bcgkpn",
                           (w_end[..., None] * x_b).astype(dtype), B_b,
                           preferred_element_type=F32)
        through = jnp.exp(cum[:, :, -1]) * (seq_end == seq_in)[..., None, None]
        s_in = []
        for c in range(cb):
            s_in.append(state)
            state = through[:, c, :, :, None, None] * state + local[:, c]
        s_in = jnp.stack(s_in, axis=1)                   # [Bt,cb,G,K,P,N]
        # The carried state's part of y, for tokens of the sequence it is of.
        carried = (seq_b == seq_in[:, :, None])[..., None, None]
        y = y + jnp.einsum("bcign,bcgkpn->bcigkp", C_b, s_in.astype(dtype),
                           preferred_element_type=F32) * (
            jnp.exp(cum) * carried)[..., None]
        # The state at each wanted token of this block: its chunk's carry
        # and the chunk's tokens up to it.
        here = (c_r // cb) == nb
        c_l = c_r % cb
        take = lambda a: a[b_r, c_l]  # noqa: E731
        cum_r, seq_r = take(cum), take(seq_b)           # [R,Q,G,K], [R,Q]
        upto = ((seq_r == seq_r[at, i_r][:, None])
                & (iota[None, :] <= i_r[:, None]))
        cum_i = cum_r[at, i_r]                                  # [R, G, K]
        w_r = jnp.exp(jnp.where(upto[..., None, None],
                                cum_i[:, None] - cum_r, -jnp.inf)) * take(dt_b)
        found = jnp.einsum("rjgkp,rjgn->rgkpn",
                           (w_r[..., None] * take(x_b)).astype(dtype),
                           take(B_b), preferred_element_type=F32)
        keep = (seq_r[at, i_r] == take(seq_in))[:, None, None]
        found = found + (jnp.exp(cum_i) * keep)[..., None, None] * take(s_in)
        states = jnp.where(here[:, None, None, None, None], found, states)
        return (state, seq_end[:, -1], states), y.astype(dtype)

    carry = (jnp.zeros((Bt, G, K, P, N), F32), jnp.zeros((Bt,), jnp.int32),
             jnp.zeros((R, G, K, P, N), F32))
    (_, _, states), y = jax.lax.scan(block, carry, xs)
    y = jnp.moveaxis(y, 0, 1).reshape(Bt, St, H, P)[:, :S]
    return y, states.reshape(R, H, P, N)


# ---------------------------------------------------------------------------
# Decode: one token a lane against the pool
# ---------------------------------------------------------------------------


def _decode_operands(decay, dtx, pack: int):
    """``[B, H]`` decays and ``[B, H, P]`` inputs as the pool's rows
    ``[B, H / pack, pack * P]``."""
    B, H, P = dtx.shape
    decay = jnp.broadcast_to(decay[..., None], (B, H, P))
    return (decay.reshape(B, H // pack, pack * P).astype(F32),
            dtx.reshape(B, H // pack, pack * P).astype(F32))


def ssm_decode_update_xla(pool, lanes, decay, dtx, Bm, Cm):
    """``ssm_decode_update`` in XLA operations (gathers the lanes' states and
    scatters them back): the oracle, and the path off the TPU."""
    L, R, N, W = pool.shape
    B, H, P = dtx.shape
    pack = H // R
    decay, dtx = _decode_operands(decay, dtx, pack)
    rows = R // Bm.shape[1]                           # rows of one group
    b_rows = jnp.repeat(Bm.astype(F32), rows, axis=1)             # [B, R, N]
    c_rows = jnp.repeat(Cm.astype(F32), rows, axis=1)
    s = (pool[lanes] * decay[:, :, None, :]
         + b_rows[..., None] * dtx[:, :, None, :])
    y = jnp.sum(s * c_rows[..., None], axis=2)                    # [B, R, W]
    return y.reshape(B, H, P), pool.at[lanes].set(s, mode="drop")


def _ssm_decode_kernel(lanes_ref, decay_ref, dtx_ref, b_ref, c_ref, s_ref,
                       y_ref, o_ref, bcol, ccol, *, group_rows: int):
    """One lane's rows ``[RB, N, W]``: ``s <- s * decay + B (x) dtx``,
    ``y = sum_n s * C``.  ``bcol`` / ``ccol``: ``B`` and ``C`` of each group
    of the block as columns ``[N, W]`` (a group's rows share them)."""
    del lanes_ref                                   # the index maps' alone
    RB, N, W = s_ref.shape[1:]
    g0 = pl.program_id(1) * (RB // group_rows)

    def columns(g, _):
        bcol[g] = jnp.broadcast_to(b_ref[0, pl.ds(g0 + g, 1), :], (W, N)).T
        ccol[g] = jnp.broadcast_to(c_ref[0, pl.ds(g0 + g, 1), :], (W, N)).T
        return 0

    jax.lax.fori_loop(0, RB // group_rows, columns, 0)

    # A rolled loop: the kernel's text is lowered layers x steps times a
    # decode program, so its body holds one row's arithmetic, once.
    def row(j, _):
        g = j // group_rows
        s = (s_ref[0, j] * decay_ref[0, pl.ds(j, 1), :]
             + bcol[g] * dtx_ref[0, pl.ds(j, 1), :])
        o_ref[0, j] = s
        y_ref[0, pl.ds(j, 1), :] = jnp.sum(s * ccol[g], axis=0, keepdims=True)
        return 0

    jax.lax.fori_loop(0, RB, row, 0)


@functools.partial(jax.jit, static_argnames=("block_rows", "interpret"))
def ssm_decode_update(pool, lanes, decay, dtx, Bm, Cm, *,
                      block_rows: int = 0, interpret: bool = False):
    """One decode step of the recurrence for ``B`` lanes, on the pool in
    place.

    Args:
      pool: [L, H / pack, N, pack * P] float32 — the layer's state pool
        (``pack_state``'s layout); donated by the caller's program, aliased
        to the second result.
      lanes: [B] int32 — the pool lane of each batch row (distinct).
      decay: [B, H] — ``exp(dt * A)``; **1 for an idle row**.
      dtx: [B, H, P] — ``dt * x``; **0 for an idle row** (with decay 1 the
        row's state is written back as it was read).
      Bm, Cm: [B, G, N].
      block_rows: pool rows a grid step (0 = a whole lane).

    Returns:
      (y [B, H, P] float32 without the ``D * x`` term, the pool).
    """
    L, R, N, W = pool.shape
    B, H, P = dtx.shape
    G = Bm.shape[1]
    decay, dtx = _decode_operands(decay, dtx, H // R)
    group_rows = R // G
    RB = block_rows or R
    if R % RB or RB % group_rows:
        raise ValueError(f"block_rows={RB} must divide the pool's {R} rows "
                         f"in whole groups of {group_rows}")
    row_spec = pl.BlockSpec((1, RB, W), lambda b, r, lanes: (b, r, 0))
    group_spec = pl.BlockSpec((1, G, N), lambda b, r, lanes: (b, 0, 0))
    pool_spec = pl.BlockSpec((1, RB, N, W),
                             lambda b, r, lanes: (lanes[b], r, 0, 0))
    block_bytes = RB * N * W * 4
    y, pool = pl.pallas_call(
        functools.partial(_ssm_decode_kernel, group_rows=group_rows),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(B, R // RB),
            in_specs=[row_spec, row_spec, group_spec, group_spec, pool_spec],
            out_specs=[row_spec, pool_spec],
            scratch_shapes=[pltpu.VMEM((RB // group_rows, N, W), F32),
                            pltpu.VMEM((RB // group_rows, N, W), F32)],
        ),
        out_shape=[jax.ShapeDtypeStruct((B, R, W), F32),
                   jax.ShapeDtypeStruct(pool.shape, pool.dtype)],
        # The pool (input 5, after the scalar-prefetch operand) is result 1.
        input_output_aliases={5: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            # A block in and out, each double-buffered, the columns, slack.
            vmem_limit_bytes=(4 * block_bytes
                              + 2 * (RB // group_rows) * N * W * 4
                              + (8 << 20))),
        interpret=interpret,
        name="ssm_decode_update",
    )(lanes.astype(jnp.int32), decay, dtx, Bm.astype(F32), Cm.astype(F32),
      pool)
    return y.reshape(B, H, P), pool


def select_ssm_update(platform: str | None = None):
    """The decode-step state update for the backend: the kernel on a TPU,
    its XLA form elsewhere (tests put the kernel through the interpreter
    themselves)."""
    if (platform or jax.default_backend()) == "tpu":
        return ssm_decode_update
    return ssm_decode_update_xla
