"""Sampling op: greedy/temperature/top-k/top-p semantics."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from k8s_llm_monitor_tpu.ops.sampling import (
    filtered_scaled_logits,
    sample_tokens,
)


def _sample(logits, temperature, top_k, top_p, seed=0):
    B = logits.shape[0]
    return np.asarray(sample_tokens(
        jax.random.PRNGKey(seed), jnp.asarray(logits, jnp.float32),
        temperature=jnp.full((B,), temperature, jnp.float32),
        top_k=jnp.full((B,), top_k, jnp.int32),
        top_p=jnp.full((B,), top_p, jnp.float32),
    ))


def test_greedy():
    logits = np.array([[0.1, 3.0, -1.0], [2.0, 0.0, 1.9]])
    out = _sample(logits, temperature=0.0, top_k=0, top_p=1.0)
    assert out.tolist() == [1, 0]


def test_top_k_restricts_support():
    rng = np.random.default_rng(0)
    logits = rng.normal(size=(1, 50)).astype(np.float32)
    top2 = set(np.argsort(logits[0])[-2:].tolist())
    seen = set()
    for seed in range(50):
        seen.add(int(_sample(logits, 1.0, 2, 1.0, seed=seed)[0]))
    assert seen <= top2
    assert len(seen) == 2  # both top-2 tokens reachable


def test_top_p_restricts_support():
    # one dominant token (p ~ .97) -> top_p=0.9 keeps only it
    logits = np.zeros((1, 10), np.float32)
    logits[0, 3] = 5.0
    for seed in range(30):
        assert int(_sample(logits, 1.0, 0, 0.9, seed=seed)[0]) == 3


def test_top_p_keeps_minimum_one_token():
    logits = np.zeros((1, 4), np.float32)  # uniform: every token has mass .25
    outs = {int(_sample(logits, 1.0, 0, 0.1, seed=s)[0]) for s in range(20)}
    # cum-before < 0.1 keeps exactly the single largest-sorted entry
    assert len(outs) == 1


def test_mixed_batch_greedy_and_sampled():
    logits = np.array([[0.0, 4.0, 0.0, 0.0]] * 2, np.float32)
    out = np.asarray(sample_tokens(
        jax.random.PRNGKey(0), jnp.asarray(logits),
        temperature=jnp.asarray([0.0, 1.0], jnp.float32),
        top_k=jnp.asarray([0, 0], jnp.int32),
        top_p=jnp.asarray([1.0, 1.0], jnp.float32),
    ))
    assert out[0] == 1  # greedy lane
    assert 0 <= out[1] < 4


def test_temperature_sharpens():
    # At temp 0.01 the top-1 margin (~0.08 for this rng draw) scales to ~8
    # nats, so honest sampling picks argmax with p > 0.999 — 20 seeds must
    # all agree.  (Temp 0.05 only scales the margin to ~1.7 nats, where a
    # correct sampler legitimately misses argmax ~20% of the time.)
    rng = np.random.default_rng(1)
    logits = rng.normal(size=(1, 20)).astype(np.float32)
    best = int(np.argmax(logits[0]))
    cold = [int(_sample(logits, 0.01, 0, 1.0, seed=s)[0]) for s in range(20)]
    assert all(t == best for t in cold)
    warm = {int(_sample(logits, 2.0, 0, 1.0, seed=s)[0]) for s in range(20)}
    assert len(warm) > 1  # hot sampling actually spreads


# -- the rank filter runs only when a sampling lane has a filter -------------


def _filter_before_pr25(logits, temperature, top_k, top_p):
    """``_filter_logits`` as it stood before the predicate: the rank filter
    on every call.  A copy, so the comparison does not lean on the code under
    test."""
    B, V = logits.shape
    scaled = logits.astype(jnp.float32) / jnp.maximum(temperature,
                                                      1e-6)[:, None]
    order = jnp.argsort(-scaled, axis=-1)
    sorted_vals = jnp.take_along_axis(scaled, order, axis=-1)
    rows = jnp.arange(B, dtype=jnp.int32)[:, None]
    rank = jnp.zeros((B, V), jnp.int32).at[rows, order].set(
        jnp.broadcast_to(jnp.arange(V, dtype=jnp.int32)[None, :], (B, V)))
    k = jnp.where(top_k > 0, jnp.clip(top_k, 1, V), V)[:, None]
    sorted_masked = jnp.where(
        jnp.arange(V, dtype=jnp.int32)[None, :] < k, sorted_vals, -jnp.inf)
    probs_sorted = jax.nn.softmax(sorted_masked, axis=-1)
    cum_before = jnp.cumsum(probs_sorted, axis=-1) - probs_sorted
    n_keep = jnp.sum(cum_before < top_p[:, None], axis=-1, dtype=jnp.int32)
    n_keep = jnp.where(top_p < 1.0, jnp.maximum(n_keep, 1), V)[:, None]
    return jnp.where(rank < jnp.minimum(k, n_keep), scaled, -jnp.inf)


def _sample_before_pr25(key, logits, temperature, top_k, top_p):
    filtered = _filter_before_pr25(logits, temperature, top_k, top_p)
    sampled = jax.random.categorical(key, filtered, axis=-1).astype(jnp.int32)
    greedy = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    return jnp.where(temperature <= 0.0, greedy, sampled)


def _logits(B, V, seed):
    return jnp.asarray(
        np.random.default_rng(seed).normal(size=(B, V)) * 3.0, jnp.float32)


# (temperature, top_k, top_p) per lane; every case keeps the filter off on
# every lane that samples.
_UNFILTERED = {
    "product": [(0.1, 0, 1.0)] * 4,
    "warm": [(1.0, 0, 1.0), (0.7, 0, 1.0), (2.0, 0, 1.0)],
    "with-greedy-lanes": [(0.0, 0, 1.0), (0.1, 0, 1.0), (0.0, 0, 1.0)],
    "greedy-lanes-carry-filters": [(0.0, 0, 0.9), (0.1, 0, 1.0),
                                   (0.0, 5, 1.0), (-1.0, 3, 0.5)],
    "top_p-above-one": [(0.5, 0, 1.5), (0.5, -1, 1.0)],
}
# ... and these have at least one sampling lane with a filter on.
_FILTERED = {
    "one-top_p-lane": [(0.1, 0, 1.0), (0.1, 0, 0.9), (0.1, 0, 1.0)],
    "one-top_k-lane": [(1.0, 0, 1.0), (1.0, 7, 1.0), (0.0, 0, 1.0)],
    "all-filtered": [(0.8, 40, 0.95)] * 3,
    "mixed-with-greedy": [(0.0, 0, 0.9), (0.7, 3, 0.5), (1.3, 0, 1.0),
                          (0.0, 0, 1.0)],
}


def _params(lanes):
    t, k, p = zip(*lanes)
    return (jnp.asarray(t, jnp.float32), jnp.asarray(k, jnp.int32),
            jnp.asarray(p, jnp.float32))


@pytest.mark.parametrize("seed", [0, 2147483659])
@pytest.mark.parametrize("case", sorted(_UNFILTERED))
def test_filters_off_is_categorical_over_the_scaled_logits(case, seed):
    """Token for token, for one key: what ``jax.random.categorical`` draws
    from ``logits / temperature`` — and what the definition before the
    predicate drew."""
    t, k, p = _params(_UNFILTERED[case])
    logits = _logits(len(t), 257, seed)
    key = jax.random.PRNGKey(seed)
    got = np.asarray(sample_tokens(key, logits, temperature=t, top_k=k,
                                   top_p=p))
    direct = np.asarray(jax.random.categorical(
        key, logits / jnp.maximum(t, 1e-6)[:, None], axis=-1))
    samples = np.asarray(t) > 0.0
    assert samples.any()
    assert got[samples].tolist() == direct[samples].tolist()
    assert got[~samples].tolist() == np.asarray(
        jnp.argmax(logits, axis=-1))[~samples].tolist()
    assert got.tolist() == np.asarray(
        _sample_before_pr25(key, logits, t, k, p)).tolist()


@pytest.mark.parametrize("seed", [0, 2147483659])
@pytest.mark.parametrize("case", sorted(_FILTERED))
def test_one_filtered_lane_keeps_every_lane_as_it_was(case, seed):
    """One sampling lane with a filter sends the whole call through the rank
    filter: every lane's token, and every row of the distribution, is what
    the definition before the predicate gives."""
    t, k, p = _params(_FILTERED[case])
    logits = _logits(len(t), 257, seed)
    key = jax.random.PRNGKey(seed)
    got = sample_tokens(key, logits, temperature=t, top_k=k, top_p=p)
    want = _sample_before_pr25(key, logits, t, k, p)
    assert np.asarray(got).tolist() == np.asarray(want).tolist()
    np.testing.assert_array_equal(
        np.asarray(filtered_scaled_logits(logits, temperature=t, top_k=k,
                                          top_p=p)),
        np.asarray(_filter_before_pr25(logits, t, k, p)))


@pytest.mark.parametrize("case", sorted(_UNFILTERED))
def test_filters_off_returns_the_scaled_logits_untouched(case):
    """``filtered_scaled_logits`` with no filter on a sampling lane: every
    row finite and equal to ``logits / temperature`` — a greedy lane's
    ``top_p=0.9`` included, which the rank filter would have cut to -inf
    had it raised the predicate."""
    t, k, p = _params(_UNFILTERED[case])
    logits = _logits(len(t), 257, 3)
    out = np.asarray(filtered_scaled_logits(logits, temperature=t, top_k=k,
                                            top_p=p))
    assert out.dtype == np.float32 and np.isfinite(out).all()
    np.testing.assert_array_equal(
        out, np.asarray(logits / jnp.maximum(t, 1e-6)[:, None]))


@pytest.mark.parametrize("case", sorted(_UNFILTERED) + sorted(_FILTERED))
def test_jitted_scan_of_three_steps_matches_the_old_definition(case):
    """The fused decode's shape: the sampler inside a ``lax.scan`` under
    ``jax.jit``, the key split every step, each token fed back."""
    lanes = {**_UNFILTERED, **_FILTERED}[case]
    t, k, p = _params(lanes)
    logits = _logits(len(t), 131, 5)

    def run(sample):
        def body(carry, _):
            key, lg = carry
            key, sub = jax.random.split(key)
            tok = sample(sub, lg)
            return (key, jnp.roll(lg, 1, axis=-1) + tok[:, None] * 0.01), tok
        return jax.jit(lambda key, lg: jax.lax.scan(
            body, (key, lg), None, length=3)[1])(jax.random.PRNGKey(11),
                                                 logits)

    got = run(lambda key, lg: sample_tokens(
        key, lg, temperature=t, top_k=k, top_p=p))
    want = run(lambda key, lg: _sample_before_pr25(key, lg, t, k, p))
    assert got.shape == (3, len(lanes))
    assert np.asarray(got).tolist() == np.asarray(want).tolist()


def _primitives(jaxpr, skip=()):
    """Names of every primitive in ``jaxpr`` and the jaxprs nested in its
    equations' parameters, except under the equations in ``skip``."""
    names = []
    for eqn in jaxpr.eqns:
        names.append(eqn.primitive.name)
        if any(eqn is s for s in skip):
            continue
        for value in eqn.params.values():
            for sub in value if isinstance(value, (tuple, list)) else (value,):
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    names += _primitives(sub, skip)
    return names


def test_the_predicate_is_one_conditional_in_the_program():
    """The filter is a branch of the program, not a second program: the
    jaxpr holds one ``cond``; the sort and the scatter stand in one of its
    branches and nowhere else."""
    t, k, p = _params(_UNFILTERED["product"])
    jaxpr = jax.make_jaxpr(lambda key, lg: sample_tokens(
        key, lg, temperature=t, top_k=k, top_p=p))(
            jax.random.PRNGKey(0), _logits(4, 64, 0)).jaxpr
    conds = [e for e in jaxpr.eqns if e.primitive.name == "cond"]
    assert len(conds) == 1
    false_branch, true_branch = (
        _primitives(b.jaxpr) for b in conds[0].params["branches"])
    assert "sort" in true_branch and "scatter" in true_branch
    assert false_branch == []
    outside = _primitives(jaxpr, skip=conds)
    assert "sort" not in outside and "scatter" not in outside
