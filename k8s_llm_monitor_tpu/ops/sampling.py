"""Token sampling: greedy / temperature / top-k / top-p, jit-friendly.

All parameters are per-sequence arrays so a continuously-batched decode step
can mix greedy and sampled requests in one compiled program (no recompilation
per sampling config — shapes and dtypes are static).

Top-k and top-p are both expressed as *rank* cutoffs over one descending
argsort: ranks are unique even when logits tie, so a tied distribution can
never defeat the nucleus mask (a strict value-threshold comparison would keep
every tied token and make ``top_p=0.1`` a no-op on uniform logits).

That sort, and the scatter that turns it into ranks, run only in a call
where some lane that samples has a filter on (``_filter_logits``: a
``lax.cond`` on the [B] parameters, the same program either way).  The
product's own requests — ``temperature=0.1``, no top-k, no top-p — draw
straight from the temperature-scaled logits: the same tokens for the same
key, without 152k-wide sorts on every step.  The engine reports which
branch a call took as ``sampler_filter`` on its ``engine.call`` span and in
``engine_sampler_filter_calls_total``.

``greedy_tokens`` is the sort-free fast path — serving/engine.py dispatches
to it when every active lane in a decode step is greedy (a pure argmax, no
[B, V] sort traffic).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


# Every public entry runs under the scope ``sampler`` (trace-time metadata
# only), so a device trace can add up what sampling costs whichever entry a
# program calls; ``filtered_scaled_logits`` is ``sampler/filter`` inside it.


def _argmax_tokens(logits: jnp.ndarray) -> jnp.ndarray:
    return jnp.argmax(logits, axis=-1).astype(jnp.int32)


@jax.named_scope("sampler")
def greedy_tokens(logits: jnp.ndarray) -> jnp.ndarray:
    """Argmax sampling, [B, V] -> [B] int32. No sorting, no rng."""
    return _argmax_tokens(logits)


@jax.named_scope("sampler")
def filtered_scaled_logits(
    logits: jnp.ndarray,
    *,
    temperature: jnp.ndarray,
    top_k: jnp.ndarray,
    top_p: jnp.ndarray,
) -> jnp.ndarray:
    """Temperature-scale then top-k/top-p-mask logits: the SINGLE
    definition of the sampling distribution, shared by ``sample_tokens``
    and the speculative-decode acceptance (serving/spec.py) so the
    speculated and sequential chains target the identical distribution.

    Args: logits [B, V]; temperature/top_k/top_p [B] (semantics as in
    ``sample_tokens``).  Returns [B, V] f32, filtered entries -inf; with
    no filter on any sampling lane, the scaled logits as they are.
    """
    return _filter_logits(logits, temperature=temperature, top_k=top_k,
                          top_p=top_p)


@jax.named_scope("filter")
def _filter_logits(
    logits: jnp.ndarray,
    *,
    temperature: jnp.ndarray,
    top_k: jnp.ndarray,
    top_p: jnp.ndarray,
) -> jnp.ndarray:
    """``filtered_scaled_logits`` for callers already inside ``sampler``.

    The rank filter runs only when some sampling lane of the call has a
    filter on — a ``lax.cond`` on the parameters the program already
    receives, so no caller needs a second program.  With both filters off
    on every sampling lane ``_rank_filter`` keeps every token and returns
    ``scaled`` unchanged, after a sort and a scatter of the whole
    vocabulary; the other branch returns the same array without them.  A
    greedy lane's filters do not raise the predicate: ``sample_tokens``
    never reads that lane's draw.  One filtered lane sends the whole call
    through the rank filter, so every lane's row is what it always was.
    Do not ``vmap`` this: a batched ``cond`` is a ``select`` that runs both
    branches.
    """
    logits = logits.astype(jnp.float32)
    temp = jnp.maximum(temperature, 1e-6)[:, None]
    scaled = logits / temp
    need = jnp.any((temperature > 0.0) & ((top_k > 0) | (top_p < 1.0)))
    return jax.lax.cond(need, _rank_filter, lambda s, k, p: s,
                        scaled, top_k, top_p)


def _rank_filter(scaled: jnp.ndarray, top_k: jnp.ndarray,
                 top_p: jnp.ndarray) -> jnp.ndarray:
    """Top-k then top-p over temperature-scaled logits [B, V] f32, as rank
    cutoffs; filtered entries -inf."""
    B, V = scaled.shape

    # One descending argsort serves both filters.  order[b, r] = token id with
    # rank r; rank[b, t] = rank of token t.
    order = jnp.argsort(-scaled, axis=-1)
    sorted_vals = jnp.take_along_axis(scaled, order, axis=-1)
    rows = jnp.arange(B, dtype=jnp.int32)[:, None]
    rank = jnp.zeros((B, V), jnp.int32).at[rows, order].set(
        jnp.broadcast_to(jnp.arange(V, dtype=jnp.int32)[None, :], (B, V))
    )

    # top-k: keep ranks < k (k <= 0 disables).
    k = jnp.where(top_k > 0, jnp.clip(top_k, 1, V), V)[:, None]

    # top-p over the top-k-filtered distribution: keep the smallest rank
    # prefix whose cumulative mass reaches top_p (always >= 1 token).
    sorted_masked = jnp.where(
        jnp.arange(V, dtype=jnp.int32)[None, :] < k, sorted_vals, -jnp.inf
    )
    probs_sorted = jax.nn.softmax(sorted_masked, axis=-1)
    cum_before = jnp.cumsum(probs_sorted, axis=-1) - probs_sorted
    n_keep = jnp.sum(cum_before < top_p[:, None], axis=-1, dtype=jnp.int32)
    n_keep = jnp.where(top_p < 1.0, jnp.maximum(n_keep, 1), V)[:, None]

    keep = rank < jnp.minimum(k, n_keep)
    return jnp.where(keep, scaled, -jnp.inf)


@jax.named_scope("sampler")
def sample_tokens_bounded(
    rng: jax.Array,
    logits: jnp.ndarray,
    *,
    temperature: jnp.ndarray,
    top_k: jnp.ndarray,
    top_p: jnp.ndarray,
    k_cap: int,
) -> jnp.ndarray:
    """``sample_tokens`` restricted to the top ``k_cap`` logits per lane.

    Samples the EXACT ``filtered_scaled_logits`` distribution whenever every
    sampling lane has ``0 < top_k <= k_cap`` (the dispatcher checks this
    before selecting the bounded program): top-k keeps at most ``k_cap``
    tokens, and top-p here filters *within* the top-k distribution, so no
    token outside the top ``k_cap`` can ever carry probability.  The win is
    replacing the full-vocab descending argsort (V is 128k on the 8B
    target — the sort dominates the on-device sampling cost inside the
    fused decode scan) with one ``lax.top_k`` over ``k_cap`` lanes.

    Ties resolve identically to the full path (lowest token id first, both
    via stable ordering), but the categorical draw uses a [B, k_cap] gumbel
    shape instead of [B, V] — same distribution, different stream for a
    given key.  Greedy lanes (temperature <= 0) take the argmax exactly as
    in ``sample_tokens``.
    """
    B, V = logits.shape
    logits = logits.astype(jnp.float32)
    greedy = _argmax_tokens(logits)
    temp = jnp.maximum(temperature, 1e-6)[:, None]
    scaled = logits / temp

    vals, idx = jax.lax.top_k(scaled, k_cap)            # [B, k_cap], sorted
    ranks = jnp.arange(k_cap, dtype=jnp.int32)[None, :]
    k = jnp.clip(top_k, 1, k_cap)[:, None]
    masked = jnp.where(ranks < k, vals, -jnp.inf)
    probs = jax.nn.softmax(masked, axis=-1)
    cum_before = jnp.cumsum(probs, axis=-1) - probs
    n_keep = jnp.sum(cum_before < top_p[:, None], axis=-1, dtype=jnp.int32)
    n_keep = jnp.where(top_p < 1.0,
                       jnp.maximum(n_keep, 1), k_cap)[:, None]
    keep = ranks < jnp.minimum(k, n_keep)
    filtered = jnp.where(keep, masked, -jnp.inf)

    choice = jax.random.categorical(rng, filtered, axis=-1)   # [B] < k_cap
    sampled = jnp.take_along_axis(idx, choice[:, None], axis=-1)[:, 0]
    return jnp.where(temperature <= 0.0, greedy,
                     sampled.astype(jnp.int32))


# Large-negative instead of -inf for FSM-disallowed entries: a fully
# finite row keeps softmax/categorical NaN-free even before the grammar's
# >=1-allowed-token guarantee kicks in, and survives the /temperature
# scaling in both samplers without overflow (1e9 / 1e-6 = 1e15 << f32 max).
_FSM_NEG = -1e9


def fsm_allowed_mask(fsm_state: jnp.ndarray, fsm_trans: jnp.ndarray,
                     vocab: int) -> jnp.ndarray:
    """Per-lane allowed-token mask from a grammar FSM.

    Args:
      fsm_state: [B] int32 — per-lane state; 0 is the FREE state (lane is
        unconstrained, everything allowed).
      fsm_trans: [S, Vg] int32 — dense transition table (diagnosis.grammar
        ``TokenFSM.trans``); entry >= 0 allowed, -1 disallowed.
      vocab: model vocab size V (>= Vg); tokens past the grammar vocab are
        disallowed for constrained lanes.

    Returns: [B, V] bool.
    """
    rows = fsm_trans[jnp.clip(fsm_state, 0, fsm_trans.shape[0] - 1)]
    allowed = rows >= 0
    if vocab > fsm_trans.shape[1]:
        pad = jnp.zeros(
            (allowed.shape[0], vocab - fsm_trans.shape[1]), dtype=bool)
        allowed = jnp.concatenate([allowed, pad], axis=-1)
    return allowed | (fsm_state <= 0)[:, None]


@jax.named_scope("sampler")
def fsm_mask_logits(logits: jnp.ndarray, fsm_state: jnp.ndarray,
                    fsm_trans: jnp.ndarray) -> jnp.ndarray:
    """Mask grammar-disallowed tokens to a large negative BEFORE sampling.

    Masking ahead of ``sample_tokens``/``sample_tokens_bounded`` (rather
    than inside them) keeps one distribution definition: greedy lanes
    (temperature <= 0) take the argmax of the *masked* logits, so a
    constrained-greedy lane is exact too.
    """
    allowed = fsm_allowed_mask(fsm_state, fsm_trans, logits.shape[-1])
    return jnp.where(allowed, logits.astype(jnp.float32), _FSM_NEG)


@jax.named_scope("sampler")
def fsm_advance(fsm_state: jnp.ndarray, fsm_trans: jnp.ndarray,
                tokens: jnp.ndarray) -> jnp.ndarray:
    """Next per-lane FSM state after ``tokens`` ([B] int32).

    FREE lanes stay at 0 by table construction (row 0 is all-zero); token
    ids beyond the grammar vocab are clipped — a constrained lane can never
    sample one (they are masked), and for free lanes any index reads row
    entries that all map to 0.
    """
    state = jnp.clip(fsm_state, 0, fsm_trans.shape[0] - 1)
    tok = jnp.clip(tokens, 0, fsm_trans.shape[1] - 1)
    return fsm_trans[state, tok].astype(jnp.int32)


@jax.named_scope("sampler")
def sample_tokens(
    rng: jax.Array,
    logits: jnp.ndarray,
    *,
    temperature: jnp.ndarray,
    top_k: jnp.ndarray,
    top_p: jnp.ndarray,
) -> jnp.ndarray:
    """Sample next tokens from final-position logits.

    Args:
      rng: PRNG key.
      logits: [B, V] float.
      temperature: [B] float; <= 0 means greedy (argmax).
      top_k: [B] int32; <= 0 disables top-k.
      top_p: [B] float; >= 1.0 disables nucleus filtering.

    Returns:
      [B] int32 token ids.
    """
    greedy = _argmax_tokens(logits)
    filtered = _filter_logits(
        logits, temperature=temperature, top_k=top_k, top_p=top_p)
    sampled = jax.random.categorical(rng, filtered, axis=-1).astype(jnp.int32)
    return jnp.where(temperature <= 0.0, greedy, sampled)
