"""The dots3_note block (latent attention in two geometries chosen by
``layer_types``: full layers whose keys a learned indexer picks the top-k of,
window layers on a latent of their own in a window-bounded store; low-rank
queries, head-wise gates; an expert layer that holds a share of its experts
beside a shared one) against its plain reference
(``benchmarks/references/dots3_note.py``: float32, no cache, a stable sort
for the selection, a loop over the chosen experts), at the tiny preset on the
CPU in float32, to 5e-5 as ``tests/test_latent_moe.py``.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.references import dots3_note as ref
from k8s_llm_monitor_tpu.models import llama
from k8s_llm_monitor_tpu.models.config import PRESETS, LayerSpec
from k8s_llm_monitor_tpu.ops import attention as ops
from k8s_llm_monitor_tpu.ops import pallas_attention as pa
from k8s_llm_monitor_tpu.ops import sparse
from k8s_llm_monitor_tpu.serving.engine import (
    SEL_COUNTS,
    EngineConfig,
    GenerationRequest,
    InferenceEngine,
    SamplingParams,
)
from k8s_llm_monitor_tpu.utils.quantize import (
    init_params_quantized,
    quantize_params,
)

TOL = 5e-5
CFG = dataclasses.replace(PRESETS["tiny-dots3-note"], dtype="float32")
RC = ref.config_of(CFG)
TOPK, WINDOW = CFG.index_topk, CFG.sliding_window          # 12, 13
# block 8 x 8 = a sequence's 64 tokens = the largest bucket: nothing chunks.
ENGINE = dict(max_slots=4, num_blocks=64, block_size=8, max_blocks_per_seq=8,
              prefill_buckets=(16, 32, 64), max_prefills_per_step=2)


@pytest.fixture(scope="module", autouse=True)
def _highest():
    with jax.default_matmul_precision("highest"):
        yield


@pytest.fixture(scope="module")
def params():
    return llama.init_params(jax.random.PRNGKey(0), CFG)


def _ids(n, seed=0):
    return [int(t) for t in
            np.random.default_rng(seed).integers(0, CFG.vocab_size, size=n)]


def _engine(params, cfg=CFG, **over):
    return InferenceEngine(cfg, params, EngineConfig(**{**ENGINE, **over}),
                           eos_id=-1)


def _greedy(params, prompt, toks, cfg=RC):
    want, _ = ref.forward(params, cfg, prompt + toks)
    return [int(np.argmax(want[len(prompt) - 1 + i]))
            for i in range(len(toks))]


# -- the description -----------------------------------------------------------


def test_the_layer_types_are_the_description():
    specs = [CFG.layer_spec(i) for i in range(CFG.num_layers)]
    assert specs == [
        LayerSpec("latent", "dense", "latent"),
        LayerSpec("latent", "shared+routed", "latent"),
        LayerSpec("latent", "shared+routed", "window"),
        LayerSpec("latent", "shared+routed", "window"),
        LayerSpec("latent", "shared+routed", "window")]
    full, sliding = CFG.latent_geometry(1), CFG.latent_geometry(2)
    assert (full.num_heads, full.kv_lora_rank, full.qk_nope_head_dim,
            full.window, full.index_topk, full.rope_theta) == (
                4, 32, 16, 0, 12, 10_000.0)
    assert (sliding.num_heads, sliding.kv_lora_rank, sliding.qk_nope_head_dim,
            sliding.window, sliding.index_topk, sliding.rope_theta) == (
                2, 48, 24, 13, 0, 500.0)
    assert full.gate == sliding.gate == "headwise"
    assert np.isclose(full.kv_scale, (64 / 32) ** 0.5)
    assert CFG.lane_state and not CFG.recurrent and not CFG.has_attn_extras
    # Three kinds of cache in one model.
    pool = llama.init_kv_pages(CFG, 10, 8, state_lanes=3)
    assert [a.shape for a in pool.k] == [(10, 8, 32 + 128)] * 2
    assert [a.shape for a in pool.idx] == [(10, 8, 16)] * 2
    assert [a.shape for a in pool.win] == [(1 + 3 * 2, 8, 48 + 128)] * 3
    assert pool.v == [] and pool.ssm == ()
    assert CFG.kv_token_bytes() == 2 * (160 + 16) * 2
    assert CFG.window_lane_bytes(8) == 3 * 16 * 176 * 2
    # The published preset: the cut of benchmarks/configs.
    big = PRESETS["dots3-note-prev-5l"]
    assert [big.layer_spec(i).cache for i in range(5)] == [
        "latent", "latent", "window", "window", "window"]
    assert (big.latent_geometry(0).page_width,
            big.latent_geometry(2).page_width) == (640, 1152)
    assert big.kv_token_bytes() == 2 * (1280 + 256)
    assert big.window_lane_bytes(16) == 3 * 528 * 2304
    # The models that were there read as they did.
    kanana = PRESETS["kanana-2-30b-a3b-12l"]
    assert kanana.kv_token_bytes() == 15_360 and not kanana.lane_state
    assert kanana.latent_geometry(3).q_lora_rank == 0
    assert PRESETS["gemma2-2b"].has_attn_extras


# -- against the reference -------------------------------------------------------


@pytest.mark.parametrize("layer", range(CFG.num_layers))
def test_every_layer_kind_against_the_reference(params, layer):
    S = 40                                  # past the window and the top-k
    x = jnp.asarray(np.random.default_rng(layer).standard_normal(
        (S, CFG.hidden_size)), jnp.float32)
    pos = jnp.arange(S, dtype=jnp.int32)[None]
    cos, sin = llama._rope_of(CFG, llama._rope_tables(CFG, pos), layer)
    got, _ = llama.layer_block(params["layers"][layer], CFG, x[None], cos,
                               sin, pos, layer_idx=layer)
    want, _ = ref.layer_forward(params["layers"][layer], RC, x, False)
    np.testing.assert_allclose(got[0], want, atol=TOL, rtol=TOL)


def test_the_whole_model_against_the_reference(params):
    ids = _ids(45, seed=3)
    got = llama.forward_full(params, CFG, jnp.asarray([ids]))
    want, routing = ref.forward(params, RC, ids)
    np.testing.assert_allclose(got[0], want, atol=2e-4, rtol=2e-4)
    assert routing[0] is None and routing[1].shape == (45, 3)


@pytest.mark.parametrize("form", ["w8a8", "weight_only"])
def test_the_quantised_forms_against_the_reference(params, form):
    """int8 kernels, with and without activation rounding: the reference
    takes the same served parameters and rounds where ``_linear`` does — one
    layer at a time on the same input (a whole model's discrete choices, the
    router's and the selection's, flip on a rounding at a near-tie)."""
    aq = form == "w8a8"
    cfg = dataclasses.replace(CFG, act_quant=aq)
    rc = ref.config_of(cfg)
    qparams = quantize_params(params)
    layer = qparams["layers"][1]
    assert layer["kv_b"]["kernel"].dtype == jnp.float32        # stays wide
    assert layer["router"]["kernel"].dtype == jnp.float32
    assert {"q_a", "q_b", "kv_a", "o", "attn_gate", "idx_q", "idx_k",
            "idx_w", "gate_e"} <= {k for k, v in layer.items()
                                   if "kernel_q" in v}
    S = 30
    # (a seed without a near-tie among the 30 tokens' discrete choices)
    x = jnp.asarray(np.random.default_rng(13).standard_normal((1, S, 64)),
                    jnp.float32)
    pos = jnp.arange(S, dtype=jnp.int32)[None]
    ropes = llama._rope_tables(cfg, pos)
    for li, layer in enumerate(qparams["layers"]):
        got, _ = llama.layer_block(layer, cfg, x, *llama._rope_of(cfg, ropes, li),
                                   pos, layer_idx=li)
        want, _ = ref.layer_forward(layer, rc, x[0], aq)
        np.testing.assert_allclose(got[0], want, atol=2e-4, rtol=2e-4)
    # The seeded int8 weights the benchmark serves, through the engine.
    served = init_params_quantized(jax.random.PRNGKey(1), cfg)
    assert jax.tree.structure(served) == jax.tree.structure(qparams)
    prompt = _ids(25, seed=12)
    rows, states = _engine(served, cfg).score_logits(prompt, 3, hidden=True)
    for li, layer in enumerate(served["layers"]):
        want, _ = ref.layer_forward(layer, rc, jnp.asarray(states[li]), aq)
        np.testing.assert_allclose(states[li + 1], want, atol=2e-4, rtol=2e-4)


# -- the indexer and the selection -------------------------------------------------


def _indexer(params, S, seed=0):
    layer = params["layers"][1]
    g = CFG.latent_geometry(1)
    h = jnp.asarray(np.random.default_rng(seed).standard_normal(
        (1, S, CFG.hidden_size)), jnp.float32)
    pos = jnp.arange(S, dtype=jnp.int32)[None]
    cos, sin = llama._rope_of(CFG, llama._rope_tables(CFG, pos), 1)
    *_, cq = llama._latent_qkv(layer, CFG, g, h, cos, sin)
    qI, kI, w = llama._index_qk(layer, CFG, g, h, cq, cos, sin)
    return layer, h[0], cq[0], sparse.index_scores(qI, w, kI)[0]


@pytest.mark.parametrize("S", [9, 12, 13, 37], ids=lambda s: f"{s}-tokens")
def test_the_indexers_scores_and_selection_against_the_reference(params, S):
    """Contexts shorter than, equal to and past ``index_topk``."""
    layer, h, cq, scores = _indexer(params, S)
    want = ref.index_scores(layer, RC, h, cq, False, CFG.rope_theta)
    np.testing.assert_allclose(scores, want, atol=TOL, rtol=TOL)
    allowed = sparse.allowed_keys(jnp.arange(S)[None], jnp.asarray([S]), S)
    keep = sparse.topk_keep(scores[None], allowed, TOPK)[0]
    np.testing.assert_array_equal(keep, ref.select(want, TOPK))
    assert (np.asarray(keep).sum(1) == np.minimum(np.arange(S) + 1, TOPK)).all()


def test_the_selection_without_a_sort_is_the_stable_sorts():
    """Ties go to the lower position; signed scores, zeros of both signs,
    rows with fewer allowed keys than k, a row with none."""
    rng = np.random.default_rng(0)
    scores = rng.integers(-3, 4, size=(6, 50)).astype(np.float32) / 2
    scores[0, :10] = -0.0
    allowed = rng.random((6, 50)) < 0.7
    allowed[1, 5:] = False                  # fewer than k
    allowed[2] = False                      # none
    keep = np.asarray(sparse.topk_keep(jnp.asarray(scores),
                                       jnp.asarray(allowed), 7))
    for r in range(6):
        idx = np.nonzero(allowed[r])[0]
        order = idx[np.argsort(-scores[r, idx], kind="stable")][:7]
        want = np.zeros(50, bool)
        want[order] = True
        np.testing.assert_array_equal(keep[r], want, err_msg=f"row {r}")
    # Real-valued scores: against the reference's selection.
    real = rng.standard_normal((40, 40)).astype(np.float32)
    tri = sparse.allowed_keys(jnp.arange(40)[None], jnp.asarray([40]), 40)
    np.testing.assert_array_equal(
        sparse.topk_keep(jnp.asarray(real)[None], tri, 9)[0],
        ref.select(real, 9))


def test_a_tie_goes_to_the_lower_position_in_program_and_reference(params):
    """Two tokens with the same index key and the same score: whichever the
    k-th is, the earlier one is kept first."""
    scores = np.zeros((1, 20), np.float32)
    scores[0, [3, 8, 15]] = 1.0
    keep = sparse.topk_keep(jnp.asarray(scores), jnp.ones((1, 20), bool), 5)[0]
    assert np.nonzero(np.asarray(keep))[0].tolist() == [0, 1, 3, 8, 15]
    square = np.tile(scores, (20, 1))
    assert np.nonzero(ref.select(square, 5)[19])[0].tolist() == [0, 1, 3, 8, 15]


def test_the_selection_is_not_a_no_op(params):
    """Past ``index_topk`` the selected layer differs from the layer with
    every key (the reference's ``select_all``): leaving the selection out
    is a different model."""
    x = jnp.asarray(np.random.default_rng(2).standard_normal(
        (40, CFG.hidden_size)), jnp.float32)
    with_sel, _ = ref.layer_forward(params["layers"][1], RC, x, False)
    without, _ = ref.layer_forward(params["layers"][1], RC, x, False,
                                   select_all=True)
    diff = np.abs(np.asarray(with_sel - without)).max(axis=-1)
    assert diff[:TOPK].max() == 0 and diff[TOPK + 4:].min() > 1e-3
    # ... and handing the reference its own selection changes nothing.
    probe = {}
    ref.attention(params["layers"][1], RC, x, False, probe=probe)
    again, _ = ref.layer_forward(params["layers"][1], RC, x, False,
                                 selected=probe["keep"])
    np.testing.assert_array_equal(again, with_sel)


# -- through the pools -------------------------------------------------------------


@pytest.mark.parametrize("length", [5, 12, 13, 14, 30, 47])
def test_prefill_then_decode_through_the_pools(params, length):
    """Prompts below, at and past the window (13) and the top-k (12), then
    decode steps that cross both: every logit row is the reference's full
    forward of the same tokens."""
    ids = _ids(length + 10, seed=length)
    want, _ = ref.forward(params, RC, ids)
    pages = llama.init_kv_pages(CFG, 16, 8, state_lanes=3)
    tables = jnp.asarray([[3, 4, 5, 6, 7, 8, 9, 0]], jnp.int32)
    row = np.zeros((1, 48), np.int32)
    row[0, :length] = ids[:length]
    lane = jnp.asarray([2], jnp.int32)
    logits, pages = llama.prefill(params, CFG, jnp.asarray(row),
                                  jnp.asarray([length]), pages, tables,
                                  lanes=lane)
    np.testing.assert_allclose(logits[0], want[length - 1], atol=2e-4, rtol=2e-4)
    for i in range(length, length + 10):
        logits, pages = llama.decode_step(
            params, CFG, jnp.asarray([ids[i]]), jnp.asarray([i]), pages,
            tables, attn_impl=ops.latent_decode_attention, lanes=lane)
        np.testing.assert_allclose(logits[0], want[i], atol=2e-4, rtol=2e-4)
    # The window store is a ring of the lane's last 13 rows: nothing was
    # written outside lane 2's two blocks, and nothing past ring row 12.
    for store in pages.win:
        store = np.asarray(store)
        assert not store[[1, 2, 3, 4]].any()      # block 0: dropped writes
        ring = store[5:7].reshape(16, -1)
        assert ring[:min(length + 10, WINDOW)].any(axis=1).all()
        assert not ring[WINDOW:].any()


def test_the_engine_serves_it_and_the_kernels_too(params):
    prompts = [_ids(n, seed=n) for n in (20, 45, 9, 33, 50)]
    eng = _engine(params)
    results = eng.generate(prompts, SamplingParams(max_tokens=10))
    for p, r in zip(prompts, results):
        assert r.token_ids == _greedy(params, p, r.token_ids)
    assert eng.prefix_cache is None and eng.decode_path == "gather"
    assert eng.attn_select_calls["mask"] > 0 and len(eng.attn_select_calls) == 1
    # The same through the Pallas kernels (interpreted): same tokens.
    kernel = _engine(params, decode_path="pallas", prefill_path="flash")
    assert kernel.decode_path == "pallas" and kernel.prefill_path == "flash"
    again = kernel.generate(prompts[:3], SamplingParams(max_tokens=10))
    assert [r.token_ids for r in again] == [r.token_ids for r in results[:3]]
    rows, states = kernel.score_logits(prompts[1], 3, hidden=True)
    want, _ = ref.forward(params, RC, prompts[1] + [
        int(np.argmax(r)) for r in rows[:-1]])
    np.testing.assert_allclose(rows, want[len(prompts[1]) - 1:], atol=2e-4,
                               rtol=2e-4)
    assert states.shape == (CFG.num_layers + 1, len(prompts[1]) + 3,
                            CFG.hidden_size)


def test_a_packed_call_is_its_prompts_alone(params):
    """Unequal prompts laid end to end, an idle row, padding behind: each
    row's logits, its pages, its index keys and its lane's ring are what the
    prompt gets alone."""
    lens, W = [29, 5, 17], 8
    prompts = [_ids(n, seed=10 + n) for n in lens]
    tables = np.zeros((4, W), np.int32)
    tables[0, :4], tables[1, :1], tables[2, :3] = [1, 2, 3, 4], [5], [7, 8, 9]
    stream = np.zeros((64,), np.int32)
    stream[:sum(lens)] = sum(prompts, [])
    offset = np.asarray([0, 29, 34, 51], np.int32)
    lanes = jnp.asarray([2, 0, 3, 4], jnp.int32)      # lane 4: no such lane
    packed, pool = llama.prefill_packed(
        params, CFG, jnp.asarray(stream), jnp.asarray(offset),
        jnp.asarray(lens + [0], jnp.int32),
        llama.init_kv_pages(CFG, 16, 8, state_lanes=4), jnp.asarray(tables),
        row_len=32, lanes=lanes)
    for j, prompt in enumerate(prompts):
        row = np.zeros((1, 32), np.int32)
        row[0, :len(prompt)] = prompt
        alone, one = llama.prefill(
            params, CFG, jnp.asarray(row), jnp.asarray([len(prompt)]),
            llama.init_kv_pages(CFG, 16, 8, state_lanes=1),
            jnp.asarray(tables[j:j + 1]), lanes=jnp.zeros((1,), jnp.int32))
        np.testing.assert_allclose(packed[j], alone[0], atol=TOL, rtol=TOL)
        lane = int(lanes[j])
        for got, want in zip(pool.win, one.win):
            np.testing.assert_allclose(got[1 + 2 * lane:3 + 2 * lane],
                                       want[1:3], atol=TOL, rtol=TOL)
        blocks = tables[j][tables[j] > 0]
        n = len(prompt)
        for got, want in zip(pool.k + pool.idx, one.k + one.idx):
            np.testing.assert_allclose(
                got[blocks].reshape(-1, got.shape[-1])[:n],
                want[blocks].reshape(-1, want.shape[-1])[:n],
                atol=TOL, rtol=TOL)
    assert not np.asarray(pool.win[0][3:5]).any()      # lane 1: never named


# -- the window store --------------------------------------------------------------


def test_the_window_store_is_bounded_by_the_window(params):
    """Whatever the context, a sliding layer holds one ring a lane: the
    store's size does not depend on the pool of pages or on what is cached,
    and the engine's pool is lanes x ring."""
    small, large = (_engine(params, num_blocks=n).pages for n in (40, 64))
    assert [a.shape for a in small.win] == [a.shape for a in large.win] == [
        (1 + 4 * 2, 8, 176)] * 3
    eng = _engine(params)
    before = [a.shape for a in eng.pages.win]
    (r,) = eng.generate([_ids(50, seed=1)], SamplingParams(max_tokens=12))
    assert len(r.token_ids) == 12 and [a.shape for a in eng.pages.win] == before
    for store in eng.pages.win:         # rows past ring row 12: never written
        rings = np.asarray(store)[1:].reshape(4, 16, -1)
        assert not rings[:, WINDOW:].any()


def test_a_preempted_lane_is_requeued_and_answers_the_same(params):
    """A pool too small for three lanes' answers: the evicted lane's pages
    and index keys are dropped, its ring is simply overwritten when its
    prompt (with what it generated) is prefilled again — every answer is the
    undisturbed one."""
    prompts = [_ids(20, seed=20 + i) for i in range(3)]
    calm = _engine(params).generate(prompts, SamplingParams(max_tokens=12))
    tight = _engine(params, max_slots=3, num_blocks=11)
    results = tight.generate(prompts, SamplingParams(max_tokens=12))
    assert tight.preemptions > 0, "the pool was not tight enough to preempt"
    assert [r.token_ids for r in results] == [r.token_ids for r in calm]
    # A pipeline reset requeues every lane the same way.
    eng = _engine(params)
    for i, p in enumerate(prompts):
        eng.submit(GenerationRequest(f"r{i}", list(p),
                                     SamplingParams(max_tokens=12)))
    eng.step()                      # admitted, 8 of 12 tokens on their way
    eng._reconcile_all()
    eng._reset_pipeline("test")
    assert eng.requeues == 3
    while eng.has_work:
        eng.step()
    assert [eng.poll(f"r{i}").token_ids for i in range(3)] == [
        r.token_ids for r in calm]


def test_cancel_and_retire_leave_the_lane_clean(params):
    """A cancelled lane's successor — a shorter prompt, so the ring still
    holds the predecessor's rows past its own — starts from its own prompt
    alone; so does the successor of a lane that retired."""
    eng = _engine(params, max_slots=1)
    a, b, c = _ids(40, seed=30), _ids(6, seed=31), _ids(9, seed=32)
    eng.submit(GenerationRequest("a", list(a), SamplingParams(max_tokens=20)))
    for _ in range(2):
        eng.step()
    assert eng.cancel("a")
    eng.submit(GenerationRequest("b", list(b), SamplingParams(max_tokens=12)))
    while eng.has_work:
        eng.step()
    got = eng.poll("b").token_ids
    assert got == _greedy(params, b, got)
    (after,) = eng.generate([c], SamplingParams(max_tokens=8))
    assert after.token_ids == _greedy(params, c, after.token_ids)


def test_a_repeated_prompt_is_prefilled_again(params):
    """Nothing snapshots a ring, so the prefix cache is not consulted: the
    same prompt twice (the harness's probe) is two fresh prefills."""
    eng = _engine(params, prefix_cache_entries=64)
    assert eng.prefix_cache is None
    prompt = _ids(17, seed=50)
    first, second = (eng.generate([prompt], SamplingParams(max_tokens=4))[0]
                     for _ in range(2))
    assert first.token_ids == second.token_ids
    assert eng.prefill_tokens == {"real": 34, "padded": 64, "cached": 0}


# -- the kernels, interpreted, against their XLA forms -------------------------------


def _pool(rng, B, NB, bs, F, lens):
    pages = jnp.asarray(rng.standard_normal((1 + B * NB, bs, F)), jnp.float32)
    tables = np.zeros((B, NB), np.int32)
    for b in range(B):
        n = -(-lens[b] // bs)
        tables[b, :n] = 1 + b * NB + np.arange(n)
    return pages, jnp.asarray(tables)


@pytest.mark.parametrize("masked", [False, True], ids=["window", "selected"])
def test_the_decode_kernel_with_a_mask_and_a_burst_is_its_xla_form(masked):
    """The latent decode kernel at a second width: one burst over a ring
    (a window layer), or every page with a keep mask over several bursts,
    a burst in which nothing is kept and an idle lane (a selected layer)."""
    rng = np.random.default_rng(1)
    B, H, F, R, bs, NB = 4, 3, 48, 32, 4, 9
    lens = np.asarray([33, 0, 7, 20], np.int32)
    pages, tables = _pool(rng, B, NB, bs, F, lens)
    q = jnp.asarray(rng.standard_normal((B, 1, H, F)), jnp.float32)
    kw = dict(v_width=R, name="k")
    if masked:
        keep = rng.random((B, NB * bs)) < 0.4
        keep[0, :16] = False                      # a whole burst of nothing
        keep[:, 0] |= lens > 0
        kw.update(keep=jnp.asarray(keep), burst=4)
    else:
        kw.update(burst=NB)
    want = ops.latent_decode_attention(q, pages, tables, jnp.asarray(lens), **kw)
    got = pa.latent_decode_attention_pallas(q, pages, tables, jnp.asarray(lens),
                                            interpret=True, **kw)
    live = lens > 0
    np.testing.assert_allclose(np.asarray(got)[live], np.asarray(want)[live],
                               atol=TOL, rtol=TOL)
    assert np.isfinite(np.asarray(got)).all()


def test_the_index_score_decode_kernel_is_its_xla_form():
    rng = np.random.default_rng(2)
    B, Hi, Di, bs, NB = 4, 3, 16, 4, 70          # two bursts of 64 pages
    lens = np.asarray([270, 0, 7, 130], np.int32)
    pages, tables = _pool(rng, B, NB, bs, Di, lens)
    q = jnp.asarray(rng.standard_normal((B, 1, Hi, Di)), jnp.float32)
    w = jnp.asarray(rng.standard_normal((B, 1, Hi)), jnp.float32)
    want = ops.index_scores_decode(q, w, pages, tables, jnp.asarray(lens))
    got = pa.index_scores_decode_pallas(q, w, pages, tables, jnp.asarray(lens),
                                        interpret=True)
    assert got.shape[1] >= NB * bs
    for b in range(B):
        np.testing.assert_allclose(got[b, :lens[b]], want[b, :lens[b]],
                                   atol=TOL, rtol=TOL)


@pytest.mark.parametrize("geometry", ["window", "selected"])
@pytest.mark.parametrize("block", [16, 512], ids=["blocks-of-16", "one-block"])
def test_the_prefill_kernels_are_their_xla_forms(geometry, block):
    """Expanded-form prefill under a band (dead tiles before the band) and
    under the selection (index-score kernel, counting selection, masked
    attention), packed and in rows, against the dense masked form."""
    rng = np.random.default_rng(3)
    H, Dk, Dv, Hi, Di, S = 2, 24, 16, 3, 16, 64
    lens = np.asarray([61, 0, 22], np.int32)
    f = lambda *s: jnp.asarray(rng.standard_normal(s), jnp.float32)  # noqa: E731
    q, k, v = f(3, S, H, Dk), f(3, S, H, Dk), f(3, S, H, Dv)
    index = (f(3, S, Hi, Di), f(3, S, Di), f(3, S, Hi))
    window, topk = (13, 0) if geometry == "window" else (0, 12)
    pos = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32), (3, S))
    keep = sparse.allowed_keys(pos, jnp.asarray(lens), S, window)
    if topk:
        keep = sparse.topk_keep(
            sparse.index_scores(index[0], index[2], index[1]), keep, topk)
    want = sparse.masked_attention(q, k, v, keep, scale=0.2)
    kw = dict(scale=0.2, block=block, interpret=True, window=window, topk=topk,
              index=index if topk else None)
    rows = pa.latent_prefill_attention_pallas(q, k, v, jnp.asarray(lens), **kw)
    # Packed: rows 0 and 2 end to end, an idle row, padding behind.
    cat = lambda x: jnp.concatenate(                              # noqa: E731
        [x[0, :61], x[2, :22], jnp.zeros((13, *x.shape[2:]), x.dtype)])
    packed = pa.latent_prefill_attention_packed(
        cat(q), cat(k), cat(v), jnp.asarray([0, 61, 83], jnp.int32),
        jnp.asarray([61, 22, 0], jnp.int32), row_len=S,
        **dict(kw, index=tuple(map(cat, index)) if topk else None))
    for r, n, at in ((0, 61, 0), (2, 22, 61)):
        np.testing.assert_allclose(rows[r, :n], want[r, :n], atol=TOL, rtol=TOL)
        np.testing.assert_allclose(packed[at:at + n], want[r, :n], atol=TOL,
                                   rtol=TOL)


# -- the share ------------------------------------------------------------------


@pytest.mark.parametrize("shares", [1, 2, 4, 8, 16])
def test_the_shares_add_up(params, shares):
    """Every way of dividing the 16 experts into equal shares: the routed
    parts of all the shares, with what every chip computes alike (the shared
    expert) counted once, are the uncut reference layer's MLP."""
    layer = params["layers"][1]
    whole = dict(layer)
    rng = jax.random.PRNGKey(7)
    for name, shape in (("gate_e", (16, 64, 24)), ("up_e", (16, 64, 24)),
                        ("down_e", (16, 24, 64))):
        rng, sub = jax.random.split(rng)
        whole[name] = {"kernel": jax.random.normal(sub, shape) * shape[1] ** -0.5}
    h = jnp.asarray(np.random.default_rng(8).standard_normal((21, 64)),
                    jnp.float32)
    full = ref.config_of(dataclasses.replace(CFG, experts_held=0,
                                             expert_start=0))
    uncut, _ = ref.expert_mlp(whole, full, h, False)
    held = 16 // shares
    total = ref.swiglu(whole["shared"], h, False)
    for s in range(shares):
        cfg = dataclasses.replace(CFG, experts_held=held, expert_start=s * held)
        part = dict(whole, **{name: {"kernel": whole[name]["kernel"][
            s * held:(s + 1) * held]} for name in ("gate_e", "up_e", "down_e")})
        routed, _ = ref.expert_mlp(part, ref.config_of(cfg), h, False,
                                   shared=False)
        total = total + routed
        # The served layer of this share is the reference's of this share.
        got, counts = llama._moe_mlp_share(
            {k: v for k, v in part.items() if k != "shared"}, cfg, h[None])
        np.testing.assert_allclose(got[0], routed, atol=TOL, rtol=TOL)
        assert counts[3] == held and counts[4] == 21 * 3
    np.testing.assert_allclose(total, uncut, atol=TOL, rtol=TOL)


# -- counts ---------------------------------------------------------------------


def _traced_calls(params):
    """(engine, its ``engine.call`` spans' attributes) of two prompts of 20
    and 45 tokens answered with 9."""
    from k8s_llm_monitor_tpu.observability.tracing import (
        Tracer,
        get_tracer,
        set_tracer,
    )

    before = get_tracer()
    set_tracer(Tracer(ring_size=1024, sample=1.0))
    try:
        eng = _engine(params)
        eng.generate([_ids(20, seed=1), _ids(45, seed=2)],
                     SamplingParams(max_tokens=9))
        return eng, [s["attrs"] for s in get_tracer().snapshot()
                     if s["name"] == "engine.call"]
    finally:
        set_tracer(before)


def test_the_counts_come_back_with_the_call(params):
    eng, calls = _traced_calls(params)
    admit = next(c for c in calls if c["kind"] == "admit")
    tri = lambda n, cap: sum(min(t + 1, cap) for t in range(n))   # noqa: E731
    assert admit["index_tokens"] == 2 * (tri(20, 99) + tri(45, 99))
    assert admit["sel_tokens"] == 2 * (tri(20, TOPK) + tri(45, TOPK))
    assert admit["window_tokens"] == 3 * (tri(20, WINDOW) + tri(45, WINDOW))
    assert admit["attn_select_form"] == "mask"
    first = next(c for c in calls if c["kind"] == "decode")
    assert first["steps"] == 8 and first["lanes"] == 2
    assert first["index_tokens"] == 2 * sum(21 + s + 46 + s for s in range(8))
    assert first["sel_tokens"] == 2 * 8 * 2 * TOPK
    assert first["window_tokens"] == 3 * 8 * 2 * WINDOW
    assert set(SEL_COUNTS) <= set(first) and "moe_assignments_all" in first
    assert eng.sel_totals["sel_tokens"] == sum(c["sel_tokens"] for c in calls)
    from k8s_llm_monitor_tpu.monitor import exporter

    w = exporter._Writer()
    exporter._engine_metrics(w, eng)
    exporter._loop_metrics(w, eng)
    text = "\n".join(w.lines)
    for line in (f"engine_index_tokens_total {eng.sel_totals['index_tokens']}",
                 f"engine_sel_tokens_total {eng.sel_totals['sel_tokens']}",
                 f"engine_window_tokens_total {eng.sel_totals['window_tokens']}",
                 f"engine_window_store_bytes {4 * 3 * 16 * 176 * 4}",
                 'engine_attn_select_calls_total{form="mask"} '
                 f"{eng.attn_select_calls['mask']}"):
        assert f"k8s_llm_monitor_{line}" in text, line
    assert "engine_state_pool_bytes" not in text


def test_the_kept_count_is_of_the_mask_that_was_applied(params, monkeypatch):
    """``sel_tokens`` of a decode call sums the keep mask the attention
    kernel is handed, not what the lengths imply: a selection that keeps one
    key too few shows."""
    real = sparse.topk_keep
    monkeypatch.setattr(sparse, "topk_keep",
                        lambda scores, allowed, k: real(scores, allowed, k - 1))
    _, calls = _traced_calls(params)
    first = next(c for c in calls if c["kind"] == "decode")
    assert first["sel_tokens"] == 2 * 8 * 2 * (TOPK - 1)
    assert first["index_tokens"] == 2 * sum(21 + s + 46 + s for s in range(8))


@pytest.mark.parametrize("path", ["gather", "pallas"])
def test_score_logits_returns_the_selection_its_programs_made(params, path):
    """``score_logits(selection=True)``: each indexed layer's scores and
    keep mask as the prefill and the decode steps computed them — through
    the XLA forms and through the kernels (interpreted) the same keys, the
    reference's own."""
    over = ({} if path == "gather"
            else dict(decode_path="pallas", prefill_path="flash"))
    eng = _engine(params, **over)
    ids, steps = _ids(30, seed=5), 4
    rows, states, chosen = eng.score_logits(ids, steps, hidden=True,
                                            selection=True)
    S = len(ids) + steps
    assert sorted(chosen) == [0, 1] and states.shape[:2] == (6, S)
    for li, (scores, keep) in chosen.items():
        assert scores.shape == keep.shape == (S, S)
        assert [int(k) for k in keep.sum(1)] == [min(t + 1, TOPK)
                                                 for t in range(S)]
        assert not np.triu(keep, 1).any()
        layer = params["layers"][li]
        h = ref.rms_norm(jnp.asarray(states[li]), layer["input_norm"],
                         CFG.rms_norm_eps)
        want, own = ref.selection(layer, RC, h, False)
        tri = np.tril(np.ones((S, S), bool))
        np.testing.assert_allclose(np.where(tri, scores, 0),
                                   np.where(tri, want, 0), atol=TOL, rtol=TOL)
        assert (keep == own).all()
    with pytest.raises(ValueError, match="goes with hidden=True"):
        eng.score_logits(ids, 1, selection=True)


def test_the_index_scores_are_picked_as_the_attention_beside_them():
    assert ops.select_index_scores_impl("cpu") is ops.index_scores_decode
    assert ops.select_index_scores_impl("tpu") is pa.index_scores_decode_pallas
    assert ops.select_index_scores_impl("tpu", "gather") is ops.index_scores_decode
    interpreted = ops.select_index_scores_impl("cpu", "pallas")
    assert (interpreted.func is pa.index_scores_decode_pallas
            and interpreted.keywords == {"interpret": True})


def test_a_round_is_one_packed_call(params):
    """A description that keeps something a decode lane admits a round as
    ONE packed call of at most the rung that holds two sequences of the
    largest bucket: the round ends before the prompt that would pass it, and
    that prompt leads the next step's round."""
    eng = _engine(params, max_prefills_per_step=4, max_slots=8,
                  max_admission_rounds=1)
    assert eng._round_tokens == 128 == eng._token_rung(2 * 64)
    for i, n in enumerate((40, 40, 40, 30, 20, 5)):
        eng.submit(GenerationRequest(f"r{i}", _ids(n, seed=60 + i),
                                     SamplingParams(max_tokens=2)))
    rounds, calls = [], []
    while eng.has_work:
        before = eng.prefills, sum(eng.prefill_bucket_rounds.values())
        eng.step()
        if eng.prefills > before[0]:
            rounds.append(eng.prefills - before[0])
            calls.append(sum(eng.prefill_bucket_rounds.values()) - before[1])
    assert rounds == [3, 3] and calls == [1, 1]   # 120 (+30 > 128) | 55
    assert all(len(eng.poll(f"r{i}").token_ids) == 2 for i in range(6))


# -- what is not built is refused --------------------------------------------------


def test_what_is_not_built_is_refused(params):
    reason = InferenceEngine._unbuilt_reason(CFG)
    assert ("window-bounded store" in reason and "index-key pages" in reason
            and "latent" in reason and "share of its experts" in reason)
    for over, what in ((dict(spec_k=2), "spec_k=2"),
                       (dict(host_spill_bytes=1 << 20), "host KV tier"),
                       (dict(kv_dtype="int8"), "kv_dtype='int8'"),
                       (dict(kv_dtype="fp8"), "kv_dtype='fp8'"),
                       (dict(tp_overlap="on"), "tp_overlap"),
                       (dict(max_blocks_per_seq=16, num_blocks=128),
                        "chunked prefill")):
        with pytest.raises(ValueError, match="is not built") as exc:
            _engine(params, **over)
        assert what in str(exc.value), str(exc.value)
    from jax.sharding import Mesh

    mesh = Mesh(np.asarray(jax.devices()[:1]).reshape(1, 1, 1),
                ("data", "seq", "model"))
    with pytest.raises(ValueError, match="a mesh is not built for"):
        InferenceEngine(CFG, params, EngineConfig(**ENGINE), eos_id=-1, mesh=mesh)
    eng = _engine(params)
    with pytest.raises(ValueError, match="export_prefix is not built for"):
        eng.export_prefix(_ids(9), tenant="t")
    with pytest.raises(ValueError, match="install_prefix is not built for"):
        eng.install_prefix(b"KVX1", expected_tenant="t")
    # The model functions refuse what the engine never sends them.
    pages = llama.init_kv_pages(CFG, 8, 8, state_lanes=1)
    tok, one = jnp.zeros((1, 8), jnp.int32), jnp.ones((1,), jnp.int32)
    table = jnp.zeros((1, 8), jnp.int32)
    with pytest.raises(ValueError, match="chunked prefill, a cached prefix"):
        llama.prefill_chunk(params, CFG, tok, one, one, pages, table)
    with pytest.raises(ValueError, match="chunked prefill, a cached prefix"):
        llama.prefill(params, CFG, tok, one, pages, table)
    with pytest.raises(ValueError, match="not built for kv_dtype"):
        llama.init_kv_pages(CFG, 8, 8, kv_quant="int8", state_lanes=1)
    direct = dataclasses.replace(CFG, q_lora_rank=0)
    with pytest.raises(NotImplementedError, match="direct queries"):
        llama.init_params(jax.random.PRNGKey(0), direct)
