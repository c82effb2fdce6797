"""The DeepSeek-V3 block through the serving path, against its plain
reference (benchmarks/references/deepseek_v3.py), at the tiny preset on the CPU.

Exactness is claimed in float32 only; every tolerance says why it is what it
is, and a negative control shows that it bites.
"""

import dataclasses
import io
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.references import deepseek_v3 as ref
from k8s_llm_monitor_tpu.models import llama
from k8s_llm_monitor_tpu.models.config import PRESETS, ModelConfig
from k8s_llm_monitor_tpu.ops import attention as ops
from k8s_llm_monitor_tpu.ops.pallas_attention import (
    latent_decode_attention_pallas,
    latent_prefill_attention_pallas,
)
from k8s_llm_monitor_tpu.serving.engine import (
    MOE_COUNTS,
    SPAN_CATALOG,
    EngineConfig,
    InferenceEngine,
    SamplingParams,
)
from k8s_llm_monitor_tpu.utils.quantize import quantize_params

CFG = dataclasses.replace(PRESETS["tiny-latent-moe"], dtype="float32")
RCFG = ref.config_of(CFG)
# float32 logits of size ~4 after 3 layers: the system and the reference add
# the same terms in another order (blockwise online softmax against one
# softmax, sorted grouped products against a loop over experts, the absorbed
# form's (q W_UK^T) c against q (W_UK c)), which moves the last few bits of a
# float32: errors measured here are 2e-6 to 6e-6.  5e-5 leaves a factor of
# ten and is still three orders under what leaving a term out does
# (test_negative_controls_fail: 0.1 to 1).
ATOL = 5e-5
ENGINE = dict(max_slots=4, num_blocks=64, block_size=8, max_blocks_per_seq=16,
              prefill_buckets=(16, 32))


@pytest.fixture(scope="module")
def params():
    return llama.init_params(jax.random.PRNGKey(0), CFG)


@pytest.fixture(scope="module")
def engine(params):
    return InferenceEngine(CFG, params, EngineConfig(**ENGINE), eos_id=-1)


def _prompt(n, seed=0):
    return np.random.default_rng(seed).integers(0, CFG.vocab_size, n).tolist()


def _reference_rows(params, prompt, rows, cfg=RCFG, act_quant=False, **kw):
    """The reference's logits at the positions score_logits reports, from one
    full forward over the prompt plus the tokens the steps were fed."""
    fed = [int(np.argmax(r)) for r in rows[:-1]]
    seq = prompt + fed
    want, routing = ref.forward(
        params, cfg, seq, act_quant=act_quant,
        logit_positions=list(range(len(prompt) - 1, len(seq))), **kw)
    return want, routing


# -- (a) system against reference, on logits ---------------------------------


@pytest.mark.parametrize("how,length", [("fresh", 21), ("chunked", 45)])
def test_score_logits_matches_reference(engine, params, how, length):
    """Prefill, then 12 decode steps through the paged latent cache, against
    ONE full forward of the reference.  21 tokens take one bucket (the
    expanded form); 45 exceed the top bucket (32) and stream in chunks, the
    second attending to pages in the absorbed form."""
    prompt = _prompt(length, seed=length)
    rows = engine.score_logits(prompt, 12)
    assert rows.shape == (13, CFG.vocab_size) and rows.dtype == np.float32
    want, _ = _reference_rows(params, prompt, rows)
    np.testing.assert_allclose(rows, want, atol=ATOL, rtol=0)


def test_score_logits_after_a_prefix_hit(engine, params):
    """A generation registers its prompt's pages; a longer prompt then starts
    from the shared blocks and ingests only its suffix, over pages."""
    base = _prompt(24, seed=7)
    engine.generate([base], SamplingParams(max_tokens=2))
    hits = engine.prefix_cache.lookup(base + [5, 6, 7], tenant="public")
    engine.allocator.free(hits[0])
    assert hits[1] >= 16, "the prefix was not registered"
    prompt = base + _prompt(9, seed=8)
    rows = engine.score_logits(prompt, 12)
    want, _ = _reference_rows(params, prompt, rows)
    np.testing.assert_allclose(rows, want, atol=ATOL, rtol=0)


def test_hidden_states_layer_by_layer(engine, params):
    """score_logits(hidden=True): the residual stream of every position
    before each layer and after the last, over a chunked prefill and three
    steps through the cache — each reference layer, fed the engine's own
    input to it, gives the engine's next state.  The comparison that does
    not compound (benchmarks/compare_reference.py runs it on the chip)."""
    prompt = _prompt(45, seed=45)
    rows, states = engine.score_logits(prompt, 3, hidden=True)
    assert states.shape == (CFG.num_layers + 1, 48, CFG.hidden_size)
    np.testing.assert_array_equal(rows, engine.score_logits(prompt, 3))
    with jax.default_matmul_precision("highest"):
        for li, layer in enumerate(params["layers"]):
            want, _ = ref.layer_forward(layer, RCFG, jnp.asarray(states[li]), False)
            np.testing.assert_allclose(states[li + 1], want, atol=ATOL, rtol=0)


@pytest.mark.parametrize("control", ["drop_rope_score", "cache_int8"])
def test_negative_controls_fail(engine, params, control):
    """The tolerance bites: a reference that leaves the rotary part out of
    the score, or rounds the cached latent and rotated key to int8 (the
    nearest precision below the cache's), is far outside it."""
    prompt = _prompt(21, seed=21)
    rows = engine.score_logits(prompt, 12)
    wrong, _ = _reference_rows(params, prompt, rows, **{control: True})
    err = np.abs(rows - wrong).max(axis=-1)
    assert err.min() > 20 * ATOL, err


def test_w8a8_matches_reference_with_the_same_rounding(params):
    """quantize=w8a8 is the configuration's arithmetic: the reference rounds
    activations where the served path does, and then agrees to float32
    rounding; without that rounding it is 0.1 away."""
    cfg = dataclasses.replace(CFG, act_quant=True)
    qp = quantize_params(params)
    eng = InferenceEngine(cfg, qp, EngineConfig(**ENGINE), eos_id=-1)
    prompt = _prompt(21, seed=3)
    rows = eng.score_logits(prompt, 12)
    want, _ = _reference_rows(qp, prompt, rows, act_quant=True)
    # One int8 step of an activation is 1/127 of its row's largest value: a
    # float32 difference that lands a value on the other side of .5 moves a
    # logit by ~1e-3 here.  It happened in none of these 13 rows; 2e-3
    # admits one such flip a row and is 50x under the unrounded reference.
    np.testing.assert_allclose(rows, want, atol=2e-3, rtol=0)
    plain, _ = _reference_rows(qp, prompt, rows, act_quant=False)
    assert np.abs(rows - plain).max() > 0.02


def test_engine_generates_the_argmax_chain_of_its_logits(engine):
    """Submit -> admission -> prefill -> the fused multi-step decode ->
    sampler, against the logits hook (which the tests above hold to the
    reference): greedy generation is the argmax of each row.  5, 20 and 45
    tokens: one bucket, the other, and the chunked path, admitted together."""
    prompts = [_prompt(n, seed=n) for n in (5, 20, 45)]
    out = engine.generate(prompts, SamplingParams(max_tokens=6))
    for prompt, res in zip(prompts, out):
        rows = engine.score_logits(prompt, 5)
        assert res.token_ids == [int(np.argmax(r)) for r in rows]


def test_a_single_reference_layer_on_a_given_input(params):
    x = jnp.asarray(np.random.default_rng(1).standard_normal((9, 64)), jnp.float32)
    with jax.default_matmul_precision("highest"):
        y, chosen = ref.layer_forward(params["layers"][1], RCFG, x, False)
        y0, none = ref.layer_forward(params["layers"][0], RCFG, x, False)
    assert y.shape == x.shape and chosen.shape == (9, 3) and none is None
    pos = jnp.broadcast_to(jnp.arange(9), (1, 9))
    cos, sin = llama.rope_angles(pos, 8, CFG.rope_theta)
    for li, want in ((1, y), (0, y0)):
        got, _ = llama.layer_block(params["layers"][li], CFG, x[None], cos,
                                   sin, pos, layer_idx=li)
        np.testing.assert_allclose(got[0], want, atol=ATOL, rtol=0)


# -- (b) absorbed form equals expanded form ----------------------------------


def _latent_inputs(S=37, seed=0):
    rng = np.random.default_rng(seed)
    nH, dn, dr, R = (CFG.num_heads, CFG.qk_nope_head_dim,
                     CFG.qk_rope_head_dim, CFG.kv_lora_rank)
    f = lambda *s: jnp.asarray(rng.standard_normal(s), jnp.float32)
    return f(1, S, nH, dn), f(1, S, nH, dr), f(1, S, R), f(1, S, dr)


def test_absorbed_equals_expanded(params):
    layer = params["layers"][1]
    q_nope, q_rope, c, k_rope = _latent_inputs()
    S = c.shape[1]
    pos = jnp.arange(S)[None]
    lens = jnp.asarray([S])
    g = CFG.latent_geometry(1)
    expanded = llama._latent_attend_expanded(
        layer, CFG, g, q_nope, q_rope, c, k_rope, pos, lens)
    dense = llama._latent_attend_expanded(
        layer, CFG, g, q_nope, q_rope, c, k_rope, pos, lens,
        attn_fn=ops.causal_attention)
    rows = llama._latent_rows(g, c, k_rope)
    o_lat = ops.blockwise_attention(
        llama._latent_absorb(layer, g, q_nope, q_rope), rows,
        rows[..., :CFG.kv_lora_rank], q_positions=pos, kv_len=lens, scale=1.0)
    absorbed = llama._latent_unabsorb(layer, g, o_lat)
    np.testing.assert_allclose(expanded, dense, atol=2e-5, rtol=0)
    np.testing.assert_allclose(absorbed, dense, atol=2e-5, rtol=0)


@pytest.mark.parametrize("shared", [False, True], ids=["per-head", "one-row"])
def test_blockwise_attention_over_several_blocks(shared):
    """Blocks of 16 over 70 keys and 50 queries with a cached prefix of 20:
    ragged last blocks, key blocks past a query block skipped, a lane whose
    keys end early."""
    rng = np.random.default_rng(2)
    B, S, T, H, Dk, Dv = 2, 50, 70, 3, 12, 8
    f = lambda *s: jnp.asarray(rng.standard_normal(s), jnp.float32)
    q, k, v = f(B, S, H, Dk), f(B, T, 1 if shared else H, Dk), f(B, T, 1 if shared else H, Dv)
    pos = jnp.arange(S)[None] + jnp.asarray([[20], [3]])
    lens = jnp.asarray([70, 41])
    got = ops.blockwise_attention(q, k, v, q_positions=pos, kv_len=lens,
                                  scale=0.3, block_q=16, block_k=16)
    want = ops.causal_attention(q, k, v, q_positions=pos, kv_len=lens,
                                scale=0.3)
    np.testing.assert_allclose(got, want, atol=2e-5, rtol=0)


def test_latent_prefill_kernel_equals_the_dense_oracle():
    """The Pallas kernel of the expanded form (interpreter), blocks of 16
    over 70 tokens (a ragged last block), keys wider than values and not a
    whole lane tile, a row that ends early and an idle row."""
    rng = np.random.default_rng(4)
    B, S, H, Dk, Dv = 3, 70, 2, 24, 16
    f = lambda *s: jnp.asarray(rng.standard_normal(s), jnp.float32)
    q, k, v = f(B, S, H, Dk), f(B, S, H, Dk), f(B, S, H, Dv)
    lens = jnp.asarray([70, 37, 0], jnp.int32)
    pos = jnp.broadcast_to(jnp.arange(S), (B, S))
    want = ops.causal_attention(q, k, v, q_positions=pos, kv_len=lens, scale=0.2)
    got = latent_prefill_attention_pallas(q, k, v, lens, scale=0.2, block=16,
                                          interpret=True)
    for b, n in enumerate((70, 37)):
        np.testing.assert_allclose(got[b, :n], want[b, :n], atol=2e-5, rtol=0)
    assert not np.asarray(got[2]).any()


def test_the_kernels_through_the_engine_equal_the_reference(params):
    """prefill_path=flash, decode_path=pallas: both Pallas kernels (the
    interpreter here) through score_logits, against the reference."""
    eng = InferenceEngine(
        CFG, params, EngineConfig(**ENGINE, prefill_path="flash",
                                  decode_path="pallas"), eos_id=-1)
    assert (eng.prefill_path, eng.decode_path) == ("flash", "pallas")
    prompt = _prompt(27, seed=27)
    rows = eng.score_logits(prompt, 3)
    want, _ = _reference_rows(params, prompt, rows)
    np.testing.assert_allclose(rows, want, atol=ATOL, rtol=0)


def test_latent_decode_kernel_equals_its_reference():
    """The Pallas kernel (interpreter) against the XLA gather form: lanes of
    1, 17 and 300 cached tokens over windows of 32 pages, and an idle lane."""
    rng = np.random.default_rng(3)
    B, H, R, F, bs, NB = 4, 4, 32, 160, 8, 40
    pages = jnp.asarray(rng.standard_normal((B * NB + 1, bs, F)), jnp.float32)
    q = jnp.asarray(rng.standard_normal((B, 1, H, F)), jnp.float32) * 0.2
    table = jnp.asarray(1 + rng.permutation(B * NB).reshape(B, NB), jnp.int32)
    lens = jnp.asarray([1, 17, 300, 0], jnp.int32)
    want = ops.latent_decode_attention(q, pages, table, lens, v_width=R)
    got = latent_decode_attention_pallas(q, pages, table, lens, v_width=R,
                                         interpret=True)
    np.testing.assert_allclose(got[:3], want[:3], atol=2e-5, rtol=0)
    assert np.isfinite(np.asarray(got)).all()


# -- (c) the routed layer -----------------------------------------------------


def _per_token_loop(layer, cfg, x):
    """Each token alone: its router row, its top-k, its experts one by one."""
    B, S, H = x.shape
    out = np.zeros((B * S, H), np.float64)
    xt = np.asarray(x, np.float64).reshape(-1, H)
    for t in range(xt.shape[0]):
        topi, topv = llama._route(layer, cfg, jnp.asarray(xt[t:t + 1], jnp.float32))
        for e, w in zip(np.asarray(topi)[0], np.asarray(topv, np.float64)[0]):
            g = xt[t] @ np.asarray(layer["gate_e"]["kernel"][e], np.float64)
            u = xt[t] @ np.asarray(layer["up_e"]["kernel"][e], np.float64)
            out[t] += w * ((g / (1 + np.exp(-g)) * u)
                           @ np.asarray(layer["down_e"]["kernel"][e], np.float64))
        if "shared" in layer:
            sh = {k: np.asarray(v["kernel"], np.float64)
                  for k, v in layer["shared"].items()}
            g, u = xt[t] @ sh["gate"], xt[t] @ sh["up"]
            out[t] += (g / (1 + np.exp(-g)) * u) @ sh["down"]
    return out.reshape(B, S, H)


SOFTMAX = ModelConfig(name="tm", vocab_size=200, hidden_size=32,
                      intermediate_size=48, num_layers=2, num_heads=4,
                      num_kv_heads=2, dtype="float32", rope_theta=10_000.0,
                      num_experts=4, num_experts_per_tok=2)


def _bias(layer, **at):
    bias = layer["router"]["e_bias"]
    for e, b in at.items():
        bias = bias.at[int(e[1:])].set(b)
    return {**layer, "router": {**layer["router"], "e_bias": bias}}


@pytest.mark.parametrize("case", ["sigmoid", "one-expert-gets-all",
                                  "an-expert-gets-none", "softmax", "padded"])
def test_routed_layer_equals_oracle_and_per_token_loop(params, case):
    rng = np.random.default_rng(5)
    cfg, layer = CFG, params["layers"][1]
    valid = None
    if case == "softmax":       # Mixtral-style: what it gave, it gives
        cfg = SOFTMAX
        layer = llama.init_params(jax.random.PRNGKey(0), cfg)["layers"][0]
    elif case == "one-expert-gets-all":
        layer = _bias(layer, e2=50.0)
    elif case == "an-expert-gets-none":
        layer = _bias(layer, e5=-50.0, e0=-50.0)
    x = jnp.asarray(rng.standard_normal((2, 9, cfg.hidden_size)) * 0.5, jnp.float32)
    if case == "padded":
        valid = jnp.arange(9)[None] < jnp.asarray([[9], [4]])
    got, counts = llama._moe_mlp_routed(layer, cfg, x, valid)
    oracle = llama._moe_mlp_dropless(layer, cfg, x)
    loop = _per_token_loop(layer, cfg, x)
    keep = np.ones((2, 9), bool) if valid is None else np.asarray(valid)
    # Sums of 2-3 experts' outputs of size ~1 in another order: float32
    # rounding, measured 1e-7 to 6e-7; the loop is float64.
    np.testing.assert_allclose(np.asarray(got)[keep], np.asarray(oracle)[keep],
                               atol=5e-6, rtol=0)
    np.testing.assert_allclose(np.asarray(got)[keep], loop[keep], atol=5e-6, rtol=0)
    chosen = np.asarray(llama._route(layer, cfg, x)[0])[keep]
    per_expert = np.bincount(chosen.reshape(-1), minlength=cfg.num_experts)
    assert list(np.asarray(counts)) == [
        chosen.size, (per_expert > 0).sum(), per_expert.max(), cfg.num_experts]
    if case == "one-expert-gets-all":
        assert per_expert[2] == 18
    if case == "an-expert-gets-none":
        assert per_expert[5] == per_expert[0] == 0 and counts[1] == 6
    if case == "padded":
        assert counts[0] == 13 * cfg.num_experts_per_tok


def test_routed_layer_w8a8_equals_the_oracles_int8_branch(params):
    cfg = dataclasses.replace(CFG, act_quant=True)
    layer = quantize_params(params)["layers"][1]
    x = jnp.asarray(np.random.default_rng(6).standard_normal((2, 7, 64)) * 0.5,
                    jnp.float32)
    got, _ = llama._moe_mlp_routed(layer, cfg, x)
    want = llama._moe_mlp_dropless(layer, cfg, x)
    # The same integer products, scaled and summed in another order.
    np.testing.assert_allclose(got, want, atol=5e-6, rtol=0)


@pytest.mark.parametrize("case", ["all-real", "padded"])
def test_routed_layer_w8a8_stream_form_is_the_compiler_form_exactly(
        params, monkeypatch, case):
    """The expert products through the stream kernel (ops/grouped.py, in the
    interpreter; the predicate picks it on a TPU at a decode step's rows):
    the same int32, so the same layer output bit for bit."""
    import functools

    from k8s_llm_monitor_tpu.ops import grouped

    cfg = dataclasses.replace(CFG, act_quant=True)
    layer = quantize_params(params)["layers"][1]
    x = jnp.asarray(np.random.default_rng(7).standard_normal((8, 1, 64)) * 0.5,
                    jnp.float32)
    valid = (jnp.arange(8) % 3 != 1)[:, None] if case == "padded" else None
    want, want_counts = llama._moe_mlp_routed(layer, cfg, x, valid)
    taken = []

    def stream(m, g, k, n, dtype, platform=None):
        taken.append((m, g, k, n, jnp.dtype(dtype)))
        return functools.partial(grouped.grouped_rows_product, interpret=True)

    monkeypatch.setattr(grouped, "select_grouped_product", stream)
    got, counts = llama._moe_mlp_routed(layer, cfg, x, valid)
    E, top, H, I = (cfg.num_experts, cfg.num_experts_per_tok, cfg.hidden_size,
                    cfg.expert_width)
    assert taken == [(8 * top, E, H, I, jnp.int8)] * 2 + [
        (8 * top, E, I, H, jnp.int8)]
    np.testing.assert_array_equal(got, want)
    np.testing.assert_array_equal(counts, want_counts)


def _force_tiles(monkeypatch, taken=None):
    """Steer every W8A8 expert product to the tiles kernel, interpreted (the
    predicate picks it on a TPU at an admission call's rows)."""
    import functools

    from k8s_llm_monitor_tpu.ops import grouped

    kernel = grouped.grouped_tiles_product

    def form(m, g, k, n, dtype, platform=None):
        if taken is not None:
            taken.append((m, g, k, n, jnp.dtype(dtype)))
        return "tiles"

    monkeypatch.setattr(grouped, "product_form", form)
    monkeypatch.setattr(grouped, "grouped_tiles_product",
                        functools.partial(kernel, interpret=True))


@pytest.mark.parametrize("case", ["all-real", "padded"])
def test_routed_layer_w8a8_tiles_form_is_the_compiler_form_exactly(
        params, monkeypatch, case):
    """The expert products through the tiles kernel (ops/grouped.py, in the
    interpreter), which dequantises too — the down product with the router's
    weight on its row scales: the same layer output bit for bit."""
    cfg = dataclasses.replace(CFG, act_quant=True)
    layer = quantize_params(params)["layers"][1]
    x = jnp.asarray(np.random.default_rng(8).standard_normal((3, 50, 64)) * 0.5,
                    jnp.float32)
    valid = (jnp.arange(150).reshape(3, 50) % 7 != 1) if case == "padded" else None
    want, want_counts = llama._moe_mlp_routed(layer, cfg, x, valid)
    taken = []
    _force_tiles(monkeypatch, taken)
    got, counts = llama._moe_mlp_routed(layer, cfg, x, valid)
    E, top, H, I = (cfg.num_experts, cfg.num_experts_per_tok, cfg.hidden_size,
                    cfg.expert_width)
    assert taken == [(150 * top, E, H, I, jnp.int8)] * 2 + [
        (150 * top, E, I, H, jnp.int8)]
    np.testing.assert_array_equal(got, want)
    np.testing.assert_array_equal(counts, want_counts)


# -- (d) the routing counts of a call ----------------------------------------


def test_routing_counts_of_calls_equal_a_host_recount(params):
    """The counts a call brings back (taken on the device, inside the
    program) against a recount from the reference's routing: an admission of
    one prompt, then decode calls of 4, 2 and 1 steps over that lane."""
    from k8s_llm_monitor_tpu.observability.tracing import (
        Tracer,
        get_tracer,
        set_tracer,
    )

    before = get_tracer()
    set_tracer(Tracer(ring_size=4096, sample=1.0))
    try:
        eng = InferenceEngine(CFG, params, EngineConfig(**ENGINE), eos_id=-1)
        prompt = _prompt(21, seed=11)
        (res,) = eng.generate([prompt], SamplingParams(max_tokens=8))
        calls = [s["attrs"] for s in get_tracer().snapshot()
                 if s["name"] == "engine.call"]
    finally:
        set_tracer(before)
    _, routing = ref.forward(params, RCFG, prompt + res.token_ids[:-1])
    E, K = CFG.num_experts, CFG.num_experts_per_tok

    def recount(positions_by_step):
        want = dict.fromkeys(MOE_COUNTS, 0.0)
        for positions in positions_by_step:
            for chosen in routing[CFG.first_dense_layers:]:
                per = np.bincount(chosen[positions].reshape(-1), minlength=E)
                want["moe_assignments"] += len(positions) * K
                want["moe_expert_layer_steps_hit"] += (per > 0).sum()
                want["moe_max_rows"] += per.max()
                want["moe_expert_layer_steps"] += E
        want["moe_mean_rows"] = want["moe_assignments"] / E
        return want

    admit, *decodes = calls
    assert admit["kind"] == "admit" and all(c["kind"] == "decode" for c in decodes)
    got = {k: admit[k] for k in recount([[0]])}
    assert got == recount([list(range(21))])
    at = 21
    for call in decodes:
        steps = [[at + i] for i in range(call["steps"])]
        assert {k: call[k] for k in got} == recount(steps), call["program"]
        assert call["ctx_tokens"] == at and call["kv_token_bytes"] == 3 * 160 * 4
        at += call["steps"]
    assert at == 21 + 7
    total = sum(c["moe_assignments"] for c in calls)
    assert eng.moe_totals["assignments"] == total == 28 * K * 2
    assert set(got) | {"ctx_tokens", "kv_token_bytes"} <= set(SPAN_CATALOG["engine.call"])


def test_the_calls_say_which_form_their_products_took(params, monkeypatch):
    """``moe_product_form`` on ``engine.call`` and its counter twin: the
    predicate's choice for the call's shape.  On the CPU every call takes the
    compiler's product; with the predicate steered to the stream kernel at
    the decode program's rows (in the interpreter — inside the fused scan),
    the decode calls say so and the tokens are the same."""
    import functools

    from k8s_llm_monitor_tpu.monitor import exporter
    from k8s_llm_monitor_tpu.observability.tracing import (
        Tracer,
        get_tracer,
        set_tracer,
    )
    from k8s_llm_monitor_tpu.ops import grouped

    cfg = dataclasses.replace(CFG, act_quant=True)
    qparams = quantize_params(params)
    prompts = [_prompt(21, seed=12), _prompt(9, seed=13)]

    def run():
        before = get_tracer()
        set_tracer(Tracer(ring_size=4096, sample=1.0))
        try:
            eng = InferenceEngine(cfg, qparams, EngineConfig(**ENGINE),
                                  eos_id=-1)
            out = eng.generate(prompts, SamplingParams(max_tokens=6))
            calls = [s["attrs"] for s in get_tracer().snapshot()
                     if s["name"] == "engine.call"]
        finally:
            set_tracer(before)
        return eng, [r.token_ids for r in out], calls

    eng, want, calls = run()
    assert {c["moe_product_form"] for c in calls} == {"compiler"}
    assert eng.moe_product_calls == {"stream": 0, "tiles": 0,
                                     "compiler": len(calls)}

    decode_rows = ENGINE["max_slots"] * cfg.num_experts_per_tok

    def form(m, g, k, n, dtype, platform=None):
        return "stream" if m <= decode_rows else "compiler"

    def select(m, g, k, n, dtype, platform=None):
        if form(m, g, k, n, dtype) == "stream":
            return functools.partial(grouped.grouped_rows_product,
                                     interpret=True)
        return grouped.grouped_rows_product_xla

    monkeypatch.setattr(grouped, "product_form", form)
    monkeypatch.setattr(grouped, "select_grouped_product", select)
    eng, got, calls = run()
    assert got == want
    assert {c["kind"]: c["moe_product_form"] for c in calls} == {
        "admit": "compiler", "decode": "stream"}
    decodes = sum(c["kind"] == "decode" for c in calls)
    assert eng.moe_product_calls == {"stream": decodes, "tiles": 0,
                                     "compiler": len(calls) - decodes}
    w = exporter._Writer()
    exporter._loop_metrics(w, eng)
    assert (f'k8s_llm_monitor_engine_moe_product_calls_total{{form="stream"}} '
            f"{decodes}") in "\n".join(w.lines)

    # The chip's choice: admission through the tiles kernel, decode through
    # the stream kernel.
    tiles = functools.partial(grouped.grouped_tiles_product, interpret=True)
    monkeypatch.setattr(
        grouped, "product_form", lambda m, g, k, n, dtype, platform=None: (
            "stream" if m <= decode_rows else "tiles"))
    monkeypatch.setattr(grouped, "grouped_tiles_product", tiles)
    eng, got, calls = run()
    assert got == want
    assert {c["kind"]: c["moe_product_form"] for c in calls} == {
        "admit": "tiles", "decode": "stream"}
    assert eng.moe_product_calls == {"stream": decodes,
                                     "tiles": len(calls) - decodes,
                                     "compiler": 0}
    w = exporter._Writer()
    exporter._loop_metrics(w, eng)
    assert (f'k8s_llm_monitor_engine_moe_product_calls_total{{form="tiles"}} '
            f"{len(calls) - decodes}") in "\n".join(w.lines)


def test_a_dense_models_programs_return_what_they_always_did():
    """No count, no wrapper, no extra output for a model that does not route."""
    cfg = dataclasses.replace(PRESETS["tiny-qwen"], dtype="float32")
    eng = InferenceEngine(cfg, llama.init_params(jax.random.PRNGKey(0), cfg),
                          EngineConfig(**ENGINE), eos_id=-1)
    assert type(eng._prefill_sample).__name__ == "PjitFunction"
    eng.generate([[5, 6, 7]], SamplingParams(max_tokens=3))
    assert eng.moe_totals == {"assignments": 0, "experts_hit": 0, "expert_slots": 0}
    assert all(type(p).__name__ == "PjitFunction" for p in eng._decode_cache.values())


# -- (e) what is not built is refused ----------------------------------------


def _mesh():
    from k8s_llm_monitor_tpu.parallel.mesh import MeshConfig, create_mesh
    return create_mesh(MeshConfig(data=4, seq=1, model=2))


@pytest.mark.parametrize("what,kwargs,engine_kw", [
    ("a mesh", {}, {"mesh": "make"}),
    ("tp_overlap", {"tp_overlap": "on"}, {}),
    ("kv_dtype", {"kv_dtype": "int8"}, {}),
    ("kv_dtype", {"kv_dtype": "fp8"}, {}),
    ("host_spill_bytes", {"host_spill_bytes": 1 << 20}, {}),
    ("spec_k", {"spec_k": 4}, {}),
])
def test_unbuilt_combinations_raise_at_construction(params, what, kwargs, engine_kw):
    if engine_kw.get("mesh") == "make":
        engine_kw = {"mesh": _mesh()}
    with pytest.raises(ValueError, match=f"{what}.*not built for a latent"):
        InferenceEngine(CFG, params, EngineConfig(**{**ENGINE, **kwargs}),
                        eos_id=-1, **engine_kw)


@pytest.mark.parametrize("call", ["export_prefix", "install_prefix"])
def test_kvx1_calls_raise(engine, call):
    arg = [1, 2, 3] if call == "export_prefix" else b"KVX1"
    with pytest.raises(ValueError, match="KVX1.*not built for a latent"):
        getattr(engine, call)(arg)


def test_selectors_and_gates_know_the_description():
    from k8s_llm_monitor_tpu.parallel.overlap import overlap_supported

    mesh = _mesh()
    for select in (ops.select_decode_impl, ops.select_prefill_impl):
        with pytest.raises(ValueError, match="mesh"):
            select("cpu", cfg=CFG, mesh=mesh)
    with pytest.raises(ValueError, match="kv_dtype"):
        ops.select_decode_impl("cpu", cfg=CFG, kv_quant="int8")
    with pytest.raises(ValueError, match="latent"):
        ops.select_decode_impl("tpu", cfg=CFG, mode="fused")
    assert ops.select_prefill_impl("tpu", cfg=CFG) is latent_prefill_attention_pallas
    assert ops.select_prefill_impl("cpu", cfg=CFG) is None
    assert ops.select_prefill_impl("tpu", cfg=CFG, mode="dense") is None
    assert llama.is_latent_prefill_impl(
        ops.select_prefill_impl("cpu", cfg=CFG, mode="flash"))
    with pytest.raises(ValueError, match="its own kernel or none"):
        llama.prefill(None, CFG, jnp.zeros((1, 8), jnp.int32), jnp.ones((1,), jnp.int32),
                      llama.init_kv_pages(CFG, 4, 8), jnp.zeros((1, 2), jnp.int32),
                      attn_impl=ops.flash_prefill_attention)
    assert ops.select_decode_impl("tpu", cfg=CFG) is latent_decode_attention_pallas
    assert ops.select_decode_impl("cpu", cfg=CFG) is ops.latent_decode_attention
    assert "latent" in overlap_supported(CFG, mesh)
    with pytest.raises(ValueError, match="latent decode impl"):
        llama.decode_step(None, CFG, jnp.zeros((1,), jnp.int32),
                          jnp.ones((1,), jnp.int32),
                          llama.init_kv_pages(CFG, 4, 8), jnp.zeros((1, 2), jnp.int32))
    with pytest.raises(ValueError, match="kv_dtype"):
        llama.init_kv_pages(CFG, 4, 8, kv_quant="int8")
    with pytest.raises(NotImplementedError, match="served, not trained"):
        llama.forward_full(llama.init_params(jax.random.PRNGKey(0), CFG), CFG,
                           jnp.zeros((1, 4), jnp.int32), return_aux=True)
