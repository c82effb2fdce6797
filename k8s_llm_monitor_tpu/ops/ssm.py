"""State-space mixing (Mamba-2 and Mamba-1): each recurrence in its two
serving forms.

The recurrence, per head h (group g = h // (H / G)), state ``S_h`` [P, N]:

    S_h[t] = exp(dt_h[t] * A_h) * S_h[t-1] + dt_h[t] * x_h[t] (x) B_g[t]
    y_h[t] = S_h[t] . C_g[t]                       (the D * x term is the caller's)

* ``ssm_chunk_scan`` — admission: the chunked form (products inside chunks of
  ``chunk`` tokens, a short carry of one state a chunk across them), over
  rows or over one packed stream whose segments start where ``first`` says,
  in XLA operations (einsums the compiler tiles itself): the oracle, and
  what runs off the TPU.  ``ssm_chunk_scan_kernel`` is the same as one Pallas
  kernel (``ssm_chunk_scan`` in a trace) that keeps a chunk's decay tiles
  and the carried state in VMEM; ``scan_form`` picks between the two from
  the call's shapes and the backend.
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

**Mamba-1** (``mamba1_*``, jamba's mixer) decays each channel c and state n
by its own ``exp(dt[c] A[n, c])``, so no chunked matrix form exists: both
forms run the recurrence token by token.  A lane's state is ``[N, C]``
float32 — the N = 16 states on sublanes, the channels on lanes — so a
token's step size and input are rows (broadcast down the sublanes), its
``y`` is a sum down them, and only ``B`` and ``C`` (one N-vector a token)
have to be columns: the admission kernel turns a chunk's whole ``[N, Q]``
tile of them into columns once, ahead of its channels.
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


# The kernel's tiles: a chunk of this many tokens, and ``pack`` heads of a
# group side by side across this many lanes (the pool's rows).
SCAN_LANES = 128
# The most bytes of wanted states a group the kernel keeps in VMEM (its
# output block, resident over the call): 16 states at nemotron's widths.
SCAN_STATES_VMEM_BYTES = 8 << 20


def scan_form(heads: int, head_dim: int, state: int, groups: int,
              chunk: int, wanted: int, platform: str | None = None) -> str:
    """``"kernel"`` or ``"xla"``: which form the admission scan of a call
    that wants ``wanted`` final states takes.  The kernel is for the TPU, a
    chunk of ``SCAN_LANES`` tokens, heads that fill the pool's rows of
    ``SCAN_LANES`` lanes (``state_pack``), a state of whole lane tiles, and
    at most ``SCAN_STATES_VMEM_BYTES`` of wanted states a group."""
    pack = state_pack(heads, groups, head_dim)
    per_group = heads // groups
    if ((platform or jax.default_backend()) == "tpu"
            and chunk == SCAN_LANES and pack * head_dim == SCAN_LANES
            and state % SCAN_LANES == 0
            and wanted * per_group * head_dim * state * 4
            <= SCAN_STATES_VMEM_BYTES):
        return "kernel"
    return "xla"


def _scan_kernel(wb_ref, wc_ref, wi_ref, sin_ref, send_ref,
                 x_ref, b_ref, c_ref, cum_ref, dt_ref, seq_ref,
                 y_ref, st_ref, state, *, pack: int, chunks: int):
    """One chunk of one row for one group's heads (``ssm_chunk_scan``'s
    arithmetic and roundings).  ``state`` [K / pack, N, pack * P] float32:
    the group's carried state in the pool's layout, across the chunks of a
    row.  A token's scalars (log decay, step size, sequence) come as rows
    ``[1, Q]`` and are worked on as rows; the few that must run down the
    tokens of a tile become columns by a transpose."""
    b, c = pl.program_id(1), pl.program_id(2)
    K, Q = cum_ref.shape[2:]
    W = x_ref.shape[-1] // (K // pack)                  # lanes of a pair
    P = W // pack
    dtype = x_ref.dtype

    @pl.when(c == 0)
    def _():
        state[...] = jnp.zeros(state.shape, F32)

    at = jax.lax.broadcasted_iota(jnp.int32, (1, Q), 1)
    pick = lambda row, t: jnp.sum(jnp.where(at == t, row, 0.0), axis=1,  # noqa: E731
                                  keepdims=True)
    head = jax.lax.broadcasted_iota(jnp.int32, (1, W), 1) // P
    sub = jax.lax.broadcasted_iota(jnp.int32, (W, 1), 0) // P

    def down(*rows):
        """Rows ``[1, Q]``, one a head of a pair, as the pair's ``[Q, W]``
        multiplier: token i's value of head s in that head's lanes."""
        m = jnp.broadcast_to(rows[0], (W, Q))
        for s, row in enumerate(rows[1:], 1):
            m = jnp.where(sub == s, row, m)
        return m.T

    seq_row = seq_ref[0]
    seq_in = sin_ref[b * chunks + c].astype(F32)
    Bc, Cc = b_ref[0], c_ref[0]
    pair_x = lambda j: x_ref[0, :, pl.ds(pl.multiple_of(j * W, W), W)]  # noqa: E731
    cum_row = lambda k: cum_ref[0, 0, pl.ds(k, 1), :]  # noqa: E731
    dt_row = lambda k: dt_ref[0, 0, pl.ds(k, 1), :]  # noqa: E731

    def advance(j, t, seq_t):
        """Pair ``j``'s state after token ``t`` (of sequence ``seq_t``):
        the carry's decay to it, and the chunk's tokens up to it."""
        upto = (seq_row == seq_t) & (at <= t)
        ws, decay = [], jnp.zeros((1, W), F32)
        for s in range(pack):
            k = j * pack + s
            cum_t = pick(cum_row(k), t)
            ws.append(jnp.exp(jnp.where(upto, cum_t - cum_row(k), -jnp.inf))
                      * dt_row(k))
            decay = jnp.where(head == s, jnp.exp(cum_t) * (seq_t == seq_in),
                              decay)
        local = jax.lax.dot_general(
            Bc, (down(*ws) * pair_x(j)).astype(dtype),
            (((0,), (0,)), ((), ())), preferred_element_type=F32)
        return local + decay * state[j]

    # The wanted states in this chunk, from the carry into it.
    def wanted(r, _):
        @pl.when((wb_ref[r] == b) & (wc_ref[r] == c))
        def _():
            t = wi_ref[r]
            seq_t = pick(seq_row, t)

            def pair(j, _):
                st_ref[r, 0, j] = advance(j, t, seq_t)
                return 0

            jax.lax.fori_loop(0, K // pack, pair, 0)
        return 0

    jax.lax.fori_loop(0, st_ref.shape[0], wanted, 0)

    cb = jax.lax.dot_general(Cc, Bc, (((1,), (1,)), ((), ())),
                             preferred_element_type=F32)          # [Q, Q]
    # Tokens i and j of one sequence, j <= i; and the tokens the carry is of.
    seq_col = jnp.broadcast_to(seq_row, (Q, Q)).T
    same = (seq_col == seq_row) & (at.T >= at)
    carried = (seq_row == seq_in).astype(F32)
    seq_end = send_ref[b * chunks + c].astype(F32)

    # A rolled loop over the group's pairs of heads: the kernel's text is
    # lowered once a Mamba-2 layer and rung.
    def pair(j, _):
        xj = pair_x(j)
        y, grow = jnp.zeros((Q, W), F32), []
        for s in range(pack):
            k = j * pack + s
            cum = cum_row(k)
            tile = jnp.where(same, jnp.broadcast_to(cum, (Q, Q)).T - cum,
                             -jnp.inf)
            w = ((jnp.exp(tile) * cb) * dt_row(k)).astype(dtype)
            y = jnp.where(head == s, jnp.dot(w, xj, preferred_element_type=F32),
                          y)
            grow.append(jnp.exp(cum) * carried)
        y = y + jnp.dot(Cc, state[j].astype(dtype),
                        preferred_element_type=F32) * down(*grow)
        y_ref[0, :, pl.ds(pl.multiple_of(j * W, W), W)] = y.astype(y_ref.dtype)
        state[j] = advance(j, Q - 1, seq_end)
        return 0

    jax.lax.fori_loop(0, K // pack, pair, 0)


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def ssm_chunk_scan_kernel(x, dt, A, Bm, Cm, first, last, *, chunk: int,
                          interpret: bool = False):
    """``ssm_chunk_scan`` as one Pallas kernel (``scan_form`` says where it
    runs): the same arguments and roundings; the states come in the pool's
    layout ``[R, H / pack, N, pack * P]`` (``pack_state`` of
    ``ssm_chunk_scan``'s), as the kernel makes them and the prefill
    program takes them.  A grid step is one chunk of one row for one group;
    the chunk's decay tiles, ``C . B`` and the carried state live in VMEM
    alone."""
    Bt, S, H, P = x.shape
    G, N = Bm.shape[-2:]
    K, R, Q = H // G, last.shape[0], chunk
    pack = state_pack(H, G, P)
    nC = -(-S // Q)
    St = nC * Q
    if St != S:
        widen = lambda a: jnp.pad(  # noqa: E731
            a, [(0, 0), (0, St - S)] + [(0, 0)] * (a.ndim - 2))
        x, dt, Bm, Cm, first = (widen(a) for a in (x, dt, Bm, Cm, first))
    seq = jnp.cumsum(first.astype(jnp.int32), axis=1)              # [Bt, St]
    ends = seq.reshape(Bt, nC, Q)[:, :, -1]
    seq_in = jnp.concatenate([jnp.zeros((Bt, 1), jnp.int32), ends[:, :-1]],
                             axis=1)
    dt = dt.astype(F32)
    cum = jnp.cumsum((dt * A.astype(F32)).reshape(Bt, nC, Q, H), axis=2)
    by_head = lambda a: a.reshape(Bt, St, G, K).transpose(0, 2, 3, 1)  # noqa: E731
    last = jnp.clip(last, 0, Bt * S - 1)
    b_r, s_r = last // S, last % S
    x_spec = pl.BlockSpec((1, Q, K * P), lambda g, b, c, *_: (b, c, g))
    g_spec = pl.BlockSpec((1, Q, N), lambda g, b, c, *_: (b, c, g))
    h_spec = pl.BlockSpec((1, 1, K, Q), lambda g, b, c, *_: (b, g, 0, c))
    s_spec = pl.BlockSpec((1, 1, Q), lambda g, b, c, *_: (b, 0, c))
    w_spec = pl.BlockSpec((R, 1, K // pack, N, pack * P),
                          lambda g, b, c, *_: (0, g, 0, 0, 0))
    isz = jnp.dtype(x.dtype).itemsize
    state_bytes = K * P * N * 4
    y, states = pl.pallas_call(
        functools.partial(_scan_kernel, pack=pack, chunks=nC),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=5,
            grid=(G, Bt, nC),
            in_specs=[x_spec, g_spec, g_spec, h_spec, h_spec, s_spec],
            out_specs=[x_spec, w_spec],
            scratch_shapes=[pltpu.VMEM((K // pack, N, pack * P), F32)],
        ),
        out_shape=[jax.ShapeDtypeStruct((Bt, St, H * P), x.dtype),
                   jax.ShapeDtypeStruct((R, G, K // pack, N, pack * P), F32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary", "arbitrary"),
            # x and y blocks, B and C, the rows, the wanted states, each
            # double-buffered; the carried state; the chunk's tiles; slack.
            vmem_limit_bytes=(4 * Q * K * P * isz + 4 * Q * N * isz
                              + 4 * (2 * K + 1) * Q * 4
                              + (2 * R + 1) * state_bytes + (16 << 20))),
        interpret=interpret,
        name="ssm_chunk_scan",
    )(b_r.astype(jnp.int32), (s_r // Q).astype(jnp.int32),
      (s_r % Q).astype(jnp.int32), seq_in.reshape(-1), ends.reshape(-1),
      x.reshape(Bt, St, H * P), Bm.reshape(Bt, St, G * N),
      Cm.reshape(Bt, St, G * N), by_head(cum.reshape(Bt, St, H)), by_head(dt),
      seq.astype(F32)[:, None, :])
    y = y.reshape(Bt, St, H, P)[:, :S]
    return y, states.reshape(R, H // pack, N, pack * P)


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


def select_ssm_update(platform: str | None = None, mixer: str = "mamba2"):
    """The decode-step state update of a ``mixer`` (``"mamba2"`` |
    ``"mamba1"``) for the backend: the kernel on a TPU, its XLA form
    elsewhere (tests put the kernel through the interpreter themselves)."""
    tpu = (platform or jax.default_backend()) == "tpu"
    if mixer == "mamba1":
        return mamba1_decode_update if tpu else mamba1_decode_update_xla
    return ssm_decode_update if tpu else ssm_decode_update_xla


# ---------------------------------------------------------------------------
# Mamba-1: a decay per channel and state
# ---------------------------------------------------------------------------


def mamba1_scan(u, dt, A, Bm, Cm, first, last):
    """Mamba-1's selective scan over whole sequences from a zero state, token
    by token (a ``lax.scan``): the oracle, and what runs off the TPU.

        S[t][n, c] = exp(dt[t][c] A[n, c]) S[t-1][n, c] + dt[t][c] B[t][n] u[t][c]
        y[t][c]    = sum_n S[t][n, c] C[t][n]      (the D * u term is the caller's)

    Args:
      u: [Bt, S, C] inputs, in the activation dtype (the arithmetic is
        float32).
      dt: [Bt, S, C] float32 step sizes, **0 at padding** (padding leaves
        the state as the last real token left it).
      A: [N, C] float32, negative (the pool's layout).
      Bm, Cm: [Bt, S, N].
      first: [Bt, S] bool — the token starts a sequence (its state starts
        from zero).
      last: [R] int32 flat indices into ``Bt * S`` of the wanted final
        states.

    Returns:
      (y [Bt, S, C] in u's dtype, states [R, N, C] float32).
    """
    Bt, S, C = u.shape
    N, R = A.shape[0], last.shape[0]
    b_r, s_r = last // S, last % S
    A = A.astype(F32)

    def step(carry, xs):
        state, states = carry
        u_t, dt_t, b_t, c_t, first_t, t = xs
        state = (jnp.where(first_t[:, None, None], 0.0, state)
                 * jnp.exp(dt_t[:, None, :] * A)
                 + b_t.astype(F32)[:, :, None]
                 * (dt_t * u_t.astype(F32))[:, None, :])
        y = jnp.sum(state * c_t.astype(F32)[:, :, None], axis=1)
        states = jnp.where((s_r == t)[:, None, None], state[b_r], states)
        return (state, states), y.astype(u.dtype)

    steps = lambda a: jnp.moveaxis(a, 1, 0)  # noqa: E731
    (_, states), y = jax.lax.scan(
        step, (jnp.zeros((Bt, N, C), F32), jnp.zeros((R, N, C), F32)),
        (steps(u), steps(dt.astype(F32)), steps(Bm), steps(Cm), steps(first),
         jnp.arange(S)))
    return steps(y), states


# The admission kernel's tiles: tokens a grid step, channels a register
# block (the state's [16, 512] float32 is eight registers), tokens whose
# rows are loaded and stored together (one bfloat16 tile of sublanes).
MAMBA1_CHUNK = 128
MAMBA1_LANES = 512
MAMBA1_GROUP = 16
# The most bytes of wanted states the kernel keeps in VMEM (its output
# block, resident over the call): 25 lanes at jamba's widths.
MAMBA1_STATES_VMEM_BYTES = 8 << 20


def mamba1_scan_form(channels: int, state: int, wanted: int,
                     platform: str | None = None) -> str:
    """``"kernel"`` or ``"xla"``: which form the Mamba-1 admission scan of a
    call that wants ``wanted`` final states takes.  The kernel is for the
    TPU, channels in whole lane tiles, a state of whole sublane tiles, and at
    most ``MAMBA1_STATES_VMEM_BYTES`` of wanted states."""
    if ((platform or jax.default_backend()) == "tpu"
            and channels % 128 == 0 and state % 8 == 0
            and wanted * state * channels * 4 <= MAMBA1_STATES_VMEM_BYTES):
        return "kernel"
    return "xla"


def _mamba1_scan_kernel(code_ref, u_ref, dt_ref, bt_ref, ct_ref, a_ref,
                        y_ref, st_ref, state, bcol, ccol, *, lanes: int,
                        wanted: int):
    """One chunk of one row, every channel (``mamba1_scan``'s arithmetic).
    ``state`` [N, C] float32 carries the row's state across its chunks;
    ``code_ref`` holds a token's ``first`` in bit 0 and, above it, the
    wanted state taken after it (``wanted`` = none)."""
    b, c = pl.program_id(0), pl.program_id(1)
    N, Q = bt_ref.shape[1:]
    C = u_ref.shape[-1]
    G = MAMBA1_GROUP
    base = (b * pl.num_programs(1) + c) * Q

    @pl.when((b == 0) & (c == 0))
    def _():
        st_ref[...] = jnp.zeros(st_ref.shape, F32)

    @pl.when(c == 0)
    def _():
        state[...] = jnp.zeros(state.shape, F32)

    # Every token's B and C as columns [N, 128], from the chunk's [N, Q]
    # tiles at once, ahead of the channels that all use them.
    at = jax.lax.broadcasted_iota(jnp.int32, (1, Q), 1)
    b_tile, c_tile = bt_ref[0].astype(F32), ct_ref[0].astype(F32)

    def columns(t, _):
        pick = at == t
        for tile, col in ((b_tile, bcol), (c_tile, ccol)):
            col[t] = jnp.broadcast_to(
                jnp.sum(jnp.where(pick, tile, 0.0), axis=1, keepdims=True),
                (N, 128))
        return 0

    jax.lax.fori_loop(0, Q, columns, 0)
    wide = lambda col: jnp.concatenate([col] * (lanes // 128), axis=1)  # noqa: E731
    sub = jax.lax.broadcasted_iota(jnp.int32, (G, 1), 0)

    # Rolled loops over the channels' blocks and the chunk's groups of
    # tokens: the kernel's text is lowered once a Mamba-1 layer and rung.
    def block(j, _):
        here = pl.ds(pl.multiple_of(j * lanes, lanes), lanes)
        a = a_ref[:, here]                                   # [N, lanes]

        def group(g, s):
            t0 = pl.multiple_of(g * G, G)
            dts = dt_ref[0, pl.ds(t0, G), here]               # [G, lanes]
            dtu = dts * u_ref[0, pl.ds(t0, G), here].astype(F32)
            ys = jnp.zeros((G, lanes), F32)
            for i in range(G):
                code = code_ref[base + t0 + i]
                s = (jnp.where((code & 1) == 1, 0.0, s)
                     * jnp.exp(a * dts[i:i + 1])
                     + wide(bcol[t0 + i]) * dtu[i:i + 1])
                ys = jnp.where(sub == i, jnp.sum(
                    s * wide(ccol[t0 + i]), axis=0, keepdims=True), ys)
                r = code >> 1

                @pl.when(r < wanted)
                def _(s=s, r=r):
                    st_ref[r, :, here] = s

            y_ref[0, pl.ds(t0, G), here] = ys.astype(y_ref.dtype)
            return s

        state[:, here] = jax.lax.fori_loop(0, Q // G, group, state[:, here])
        return 0

    jax.lax.fori_loop(0, C // lanes, block, 0)


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def mamba1_scan_kernel(u, dt, A, Bm, Cm, first, last, *,
                       chunk: int = MAMBA1_CHUNK, interpret: bool = False):
    """``mamba1_scan`` as one Pallas kernel (``mamba1_scan`` in a trace;
    ``mamba1_scan_form`` says where it runs): the same arguments, results
    and roundings.  A grid step is one chunk of ``chunk`` tokens of one row
    over every channel; the state and the chunk's columns of ``B`` and ``C``
    live in VMEM alone, the state in registers while a block of channels
    runs down the chunk's tokens."""
    Bt, S, C = u.shape
    N, R = A.shape[0], last.shape[0]
    Q = chunk
    lanes = next(w for w in (MAMBA1_LANES, 256, 128) if C % w == 0)
    nC = -(-S // Q)
    St = nC * Q
    if St != S:
        widen = lambda a: jnp.pad(  # noqa: E731
            a, [(0, 0), (0, St - S)] + [(0, 0)] * (a.ndim - 2))
        u, dt, Bm, Cm, first = (widen(a) for a in (u, dt, Bm, Cm, first))
    # Where the wanted states are taken: the first of the rows that want a
    # token's state is written (an idle row's index may repeat a real one).
    at = jnp.clip(last, 0, Bt * S - 1)
    at = (at // S) * St + at % S
    slot = jnp.full((Bt * St,), R, jnp.int32).at[at].min(
        jnp.arange(R, dtype=jnp.int32))
    code = slot * 2 + first.reshape(-1).astype(jnp.int32)
    row = pl.BlockSpec((1, Q, C), lambda b, c, *_: (b, c, 0))
    col = pl.BlockSpec((1, N, Q), lambda b, c, *_: (b, 0, c))
    isz = jnp.dtype(u.dtype).itemsize
    y, states = pl.pallas_call(
        functools.partial(_mamba1_scan_kernel, lanes=lanes, wanted=R),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(Bt, nC),
            in_specs=[row, row, col, col,
                      pl.BlockSpec((N, C), lambda b, c, *_: (0, 0))],
            out_specs=[row, pl.BlockSpec((R, N, C),
                                         lambda b, c, *_: (0, 0, 0))],
            scratch_shapes=[pltpu.VMEM((N, C), F32),
                            pltpu.VMEM((Q, N, 128), F32),
                            pltpu.VMEM((Q, N, 128), F32)],
        ),
        out_shape=[jax.ShapeDtypeStruct((Bt, St, C), u.dtype),
                   jax.ShapeDtypeStruct((R, N, C), F32)],
        compiler_params=pltpu.CompilerParams(
            # The wanted states are one block over the whole grid.
            dimension_semantics=("arbitrary", "arbitrary"),
            # u, y and dt blocks, B and C, A, the wanted states, each
            # double-buffered; the state and the columns; slack.
            vmem_limit_bytes=(2 * Q * C * (2 * isz + 4) + 4 * N * Q * 4
                              + 2 * (R + 1) * N * C * 4 + N * C * 4
                              + 2 * Q * N * 128 * 4 + (16 << 20))),
        interpret=interpret,
        name="mamba1_scan",
    )(code, u, dt.astype(F32), jnp.swapaxes(Bm, 1, 2).astype(F32),
      jnp.swapaxes(Cm, 1, 2).astype(F32), A.astype(F32))
    return y[:, :S], states


def mamba1_decode_update_xla(pool, lanes, dt, dtu, A, Bm, Cm):
    """``mamba1_decode_update`` in XLA operations (gathers the lanes' states
    and scatters them back): the oracle, and the path off the TPU."""
    s = (pool[lanes] * jnp.exp(dt[:, None, :] * A)
         + Bm.astype(F32)[:, :, None] * dtu[:, None, :])
    y = jnp.sum(s * Cm.astype(F32)[:, :, None], axis=1)
    return y, pool.at[lanes].set(s, mode="drop")


def _mamba1_decode_kernel(lanes_ref, dt_ref, dtu_ref, b_ref, c_ref, a_ref,
                          s_ref, y_ref, o_ref, *, lanes: int):
    """One lane's state ``[N, C]``: ``s <- s * exp(dt A) + B (x) dtu``,
    ``y = sum_n s * C``, a block of channels at a time.  ``b_ref`` /
    ``c_ref``: the lane's ``B`` and ``C`` as columns ``[N, 128]``."""
    del lanes_ref                                   # the index maps' alone
    C = s_ref.shape[-1]
    wide = lambda col: jnp.concatenate([col] * (lanes // 128), axis=1)  # noqa: E731
    bcol, ccol = wide(b_ref[0]), wide(c_ref[0])

    def block(j, _):
        here = pl.ds(pl.multiple_of(j * lanes, lanes), lanes)
        s = (s_ref[0, :, here] * jnp.exp(a_ref[:, here] * dt_ref[0, :, here])
             + bcol * dtu_ref[0, :, here])
        o_ref[0, :, here] = s
        y_ref[0, :, here] = jnp.sum(s * ccol, axis=0, keepdims=True)
        return 0

    jax.lax.fori_loop(0, C // lanes, block, 0)


@functools.partial(jax.jit, static_argnames=("interpret",))
def mamba1_decode_update(pool, lanes, dt, dtu, A, Bm, Cm, *,
                         interpret: bool = False):
    """One decode step of Mamba-1's recurrence for ``B`` lanes, on the pool
    in place.

    Args:
      pool: [L, N, C] float32 — the layer's state pool; donated by the
        caller's program, aliased to the second result.
      lanes: [B] int32 — the pool lane of each batch row (distinct).
      dt: [B, C] float32 step sizes; **0 for an idle row**.
      dtu: [B, C] float32 ``dt * u``; **0 for an idle row** (with ``dt`` 0
        the row's state is written back as it was read).
      A: [N, C] float32.
      Bm, Cm: [B, N].

    Returns:
      (y [B, C] float32 without the ``D * u`` term, the pool).
    """
    L, N, C = pool.shape
    B = dt.shape[0]
    lanes_c = next(w for w in (MAMBA1_LANES, 256, 128) if C % w == 0)
    columns = lambda m: jnp.broadcast_to(  # noqa: E731
        m.astype(F32)[:, :, None], (B, N, 128))
    row_spec = pl.BlockSpec((1, 1, C), lambda b, lanes: (b, 0, 0))
    col_spec = pl.BlockSpec((1, N, 128), lambda b, lanes: (b, 0, 0))
    pool_spec = pl.BlockSpec((1, N, C), lambda b, lanes: (lanes[b], 0, 0))
    lane_bytes = N * C * 4
    y, pool = pl.pallas_call(
        functools.partial(_mamba1_decode_kernel, lanes=lanes_c),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(B,),
            in_specs=[row_spec, row_spec, col_spec, col_spec,
                      pl.BlockSpec((N, C), lambda b, lanes: (0, 0)),
                      pool_spec],
            out_specs=[row_spec, pool_spec],
        ),
        out_shape=[jax.ShapeDtypeStruct((B, 1, C), F32),
                   jax.ShapeDtypeStruct(pool.shape, pool.dtype)],
        # The pool (input 6, after the scalar-prefetch operand) is result 1.
        input_output_aliases={6: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            # A lane in and out and A, each double-buffered; slack.
            vmem_limit_bytes=6 * lane_bytes + (8 << 20)),
        interpret=interpret,
        name="mamba1_decode_update",
    )(lanes.astype(jnp.int32), dt.astype(F32)[:, None], dtu.astype(F32)[:, None],
      columns(Bm), columns(Cm), A.astype(F32), pool)
    return y[:, 0], pool
