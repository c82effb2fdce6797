"""The nemotron_h block (Mamba-2 layers over a per-lane state pool, GQA
attention without rotation in a pool with pages for its layers alone, an
expert feed-forward in a latent that holds a share of its experts) against
its plain reference (``benchmarks/references/nemotron_h.py``: float32, the
recurrence token by token, a loop over the chosen experts), at the tiny
preset on the CPU in float32, to 5e-5 as ``tests/test_latent_moe.py``.
"""

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.references import nemotron_h as ref
from k8s_llm_monitor_tpu.models import llama
from k8s_llm_monitor_tpu.models.config import PRESETS
from k8s_llm_monitor_tpu.ops import ssm
from k8s_llm_monitor_tpu.serving.engine import (
    MOE_SHARE_COUNTS,
    SPAN_CATALOG,
    EngineConfig,
    InferenceEngine,
    SamplingParams,
)
from k8s_llm_monitor_tpu.utils.quantize import (
    init_params_quantized,
    quantize_params,
)

TOL = 5e-5
CFG = dataclasses.replace(PRESETS["tiny-nemotron-h"], dtype="float32")
RC = ref.config_of(CFG)
# block 4 x 8 = a sequence's 32 tokens = the largest bucket: nothing chunks.
ENGINE = dict(max_slots=4, num_blocks=64, block_size=4, max_blocks_per_seq=8,
              prefill_buckets=(8, 16, 32), max_prefills_per_step=2)


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


def test_the_pattern_is_the_description():
    specs = [CFG.layer_spec(i) for i in range(CFG.num_layers)]
    assert [(s.mixer, s.mlp, s.cache) for s in specs] == [
        ("mamba2", "none", "state"), ("none", "shared+routed", "none"),
        ("full", "none", "kv"), ("none", "shared+routed", "none"),
        ("mamba2", "none", "state"), ("none", "shared+routed", "none")]
    assert CFG.recurrent and CFG.expert_layers == 3 and CFG.expert_share
    assert CFG.layers_with("kv") == [2] and CFG.layers_with("state") == [0, 4]
    # Pages for the attention layer only; a state row a lane a Mamba layer.
    assert CFG.kv_token_bytes(4) == 2 * 1 * 2 * 16 * 4
    pages = llama.init_kv_pages(CFG, 8, 4, state_lanes=3)
    pack = ssm.state_pack(8, 2, 8)
    assert pack == 4 and len(pages.k) == len(pages.v) == 1
    assert [a.shape for a in pages.ssm] == [(3, 8 // pack, 16, pack * 8)] * 2
    assert [a.shape for a in pages.conv] == [(3, 3, 64 + 2 * 2 * 16)] * 2
    assert CFG.state_lane_bytes(4) == 2 * (8 * 8 * 16 * 4 + 3 * 128 * 4)
    served = PRESETS["nemotron3-super-120b-a12b-22l"]
    assert served.kv_token_bytes() == 2_048
    assert served.state_lane_bytes() == 10 * (128 * 64 * 128 * 4 + 3 * 10_240 * 2)
    assert ssm.state_pack(128, 8, 64) == 2
    assert not PRESETS["qwen2-7b"].recurrent
    assert llama.init_kv_pages(PRESETS["tiny-qwen"], 8, 4).ssm == ()


@pytest.mark.parametrize("layer", range(CFG.num_layers))
def test_every_layer_kind_against_the_reference(params, layer):
    x = jnp.asarray(np.random.default_rng(layer).standard_normal(
        (2, 19, CFG.hidden_size)), jnp.float32)
    pos = jnp.broadcast_to(jnp.arange(19, dtype=jnp.int32), (2, 19))
    got, _ = llama.layer_block(params["layers"][layer], CFG, x, None, None,
                               pos, layer_idx=layer)
    for b in range(2):
        want, _ = ref.layer_forward(params["layers"][layer], RC, x[b], False)
        np.testing.assert_allclose(got[b], want, atol=TOL, rtol=TOL)


def test_the_whole_model_against_the_reference(params):
    toks = _ids(23)
    got = llama.forward_full(params, CFG, jnp.asarray(toks)[None])[0]
    want, routing = ref.forward(params, RC, toks)
    np.testing.assert_allclose(got, want, atol=TOL, rtol=TOL)
    assert [r is not None for r in routing] == [False, True, False, True,
                                                False, True]


@pytest.mark.parametrize("block_chunks", [1, 2, 8])
def test_the_chunked_scan_is_the_recurrence(block_chunks):
    """Rows and a packed stream of the same sequences, lengths that are not
    multiples of the chunk and straddle it, padding behind each; blocks of
    one, two and all of a call's chunks (a sequence's state crosses blocks,
    and the wanted states lie in different ones)."""
    rng = np.random.default_rng(3)
    H, P, N, G, Q = 8, 8, 16, 2, 8
    lens, S = [5, 8, 19, 9], 24
    A = -np.abs(rng.standard_normal(H)) - 0.5
    seqs = [dict(x=rng.standard_normal((n, H, P)),
                 dt=np.abs(rng.standard_normal((n, H))) * 0.3,
                 B=rng.standard_normal((n, G, N)),
                 C=rng.standard_normal((n, G, N))) for n in lens]

    def recurrence(s):
        state, ys = np.zeros((H, P, N)), []
        for t in range(len(s["x"])):
            Bt, Ct = (np.repeat(s[k][t], H // G, axis=0) for k in "BC")
            state = (np.exp(s["dt"][t] * A)[:, None, None] * state
                     + (s["dt"][t][:, None] * s["x"][t])[:, :, None]
                     * Bt[:, None, :])
            ys.append(np.einsum("hpn,hn->hp", state, Ct))
        return np.asarray(ys), state

    def laid(key, shape, at):
        out = np.zeros(shape)
        for s, (b, t) in zip(seqs, at):
            out[b, t:t + len(s[key])] = s[key]
        return jnp.asarray(out, jnp.float32)

    rows_at = [(b, 0) for b in range(len(lens))]
    off = np.cumsum([0] + lens)
    stream_at = [(0, int(o)) for o in off[:-1]]
    for Bt, St, at in ((len(lens), S, rows_at), (1, 48, stream_at)):
        first = np.zeros((Bt, St), bool)
        for b, t in at:
            first[b, t] = True
        first[0, min(off[-1], St - 1)] |= Bt == 1    # padding starts its own
        last = jnp.asarray([b * St + t + n - 1
                            for (b, t), n in zip(at, lens)], jnp.int32)
        y, states = ssm.ssm_chunk_scan(
            laid("x", (Bt, St, H, P), at), laid("dt", (Bt, St, H), at),
            jnp.asarray(A, jnp.float32), laid("B", (Bt, St, G, N), at),
            laid("C", (Bt, St, G, N), at), jnp.asarray(first), last, chunk=Q,
            block_chunks=block_chunks)
        for s, (b, t), state in zip(seqs, at, states):
            want_y, want_state = recurrence(s)
            np.testing.assert_allclose(y[b, t:t + len(want_y)], want_y,
                                       atol=TOL, rtol=TOL)
            np.testing.assert_allclose(state, want_state, atol=TOL, rtol=TOL)


def test_the_decode_kernel_is_its_xla_form():
    """``ssm_decode_update`` in the interpreter: a lane order that is not the
    identity, idle rows (their state comes back bit-equal), whole lanes and
    half lanes a grid step; and the pool's layout is a relabelling."""
    rng = np.random.default_rng(4)
    H, P, N, G, L = 8, 8, 16, 2, 6
    pack = ssm.state_pack(H, G, P)
    state = jnp.asarray(rng.standard_normal((L, H, P, N)), jnp.float32)
    pool = ssm.pack_state(state, pack)
    np.testing.assert_array_equal(ssm.unpack_state(pool, pack), state)
    lanes = jnp.asarray([4, 0, 5, 2], jnp.int32)
    idle = np.asarray([False, True, False, False])
    decay = jnp.where(idle[:, None], 1.0, jnp.asarray(
        np.exp(-np.abs(rng.standard_normal((4, H)))), jnp.float32))
    dtx = jnp.where(idle[:, None, None], 0.0, jnp.asarray(
        rng.standard_normal((4, H, P)), jnp.float32))
    Bm, Cm = (jnp.asarray(rng.standard_normal((4, G, N)), jnp.float32)
              for _ in range(2))
    want_y, want_pool = ssm.ssm_decode_update_xla(pool, lanes, decay, dtx, Bm, Cm)
    heads = lambda a: np.repeat(np.asarray(a), H // G, axis=1)  # noqa: E731
    new = (np.asarray(state)[np.asarray(lanes)] * np.asarray(decay)[..., None, None]
           + np.einsum("bhp,bhn->bhpn", dtx, heads(Bm)))
    np.testing.assert_allclose(want_y, np.einsum("bhpn,bhn->bhp", new, heads(Cm)),
                               atol=TOL, rtol=TOL)
    np.testing.assert_allclose(
        np.asarray(ssm.unpack_state(want_pool, pack))[np.asarray(lanes)], new,
        atol=TOL, rtol=TOL)
    for rows in (0, 1):
        y, got = ssm.ssm_decode_update(pool, lanes, decay, dtx, Bm, Cm,
                                       block_rows=rows, interpret=True)
        np.testing.assert_allclose(y, want_y, atol=TOL, rtol=TOL)
        np.testing.assert_allclose(got, want_pool, atol=TOL, rtol=TOL)
        np.testing.assert_array_equal(got[0], pool[0])      # the idle row's
        np.testing.assert_array_equal(got[1], pool[1])      # no row's lane


# -- through the pools -----------------------------------------------------------


@pytest.mark.parametrize("length", [5, 8, 13, 17])
def test_prefill_then_decode_through_the_pools(params, length):
    """Prompt lengths below, at, between and above multiples of the chunk
    (8): ``score_logits`` is the engine's own prefill into its pools and its
    own decode steps against them."""
    eng = _engine(params)
    prompt = _ids(length, seed=length)
    rows, states = eng.score_logits(prompt, 6, hidden=True)
    fed = [int(np.argmax(r)) for r in rows[:-1]]
    want, _ = ref.forward(params, RC, prompt + fed,
                          logit_positions=list(range(length - 1, length + 6)))
    np.testing.assert_allclose(rows, want, atol=TOL, rtol=TOL)
    assert states.shape == (CFG.num_layers + 1, length + 6, CFG.hidden_size)
    # One reference layer at a time on the engine's own input to it, prompt
    # and decode positions together (what compare_reference.py does).
    for li, layer in enumerate(params["layers"]):
        want, _ = ref.layer_forward(layer, RC, jnp.asarray(states[li]), False)
        np.testing.assert_allclose(states[li + 1], want, atol=TOL, rtol=TOL)


def test_the_engine_serves_it_and_the_kernel_too(params):
    prompts = [_ids(n, seed=n) for n in (5, 11, 17, 9, 3)]
    eng = _engine(params)
    results = eng.generate(prompts, SamplingParams(max_tokens=7))
    for p, r in zip(prompts, results):
        assert r.token_ids == _greedy(params, p, r.token_ids)
    assert eng.prefix_cache is None and eng.decode_path == "gather"
    # The same through the Pallas kernel (interpreted): same tokens.
    kernel = _engine(params)
    kernel._ssm_update = functools.partial(ssm.ssm_decode_update, interpret=True)
    again = kernel.generate(prompts[:3], SamplingParams(max_tokens=7))
    assert [r.token_ids for r in again] == [r.token_ids for r in results[:3]]


def test_a_packed_call_is_its_prompts_alone(params):
    """Unequal prompts laid end to end, an idle row, padding behind: each
    row's logits, its pages and its lane of the state pool are what the
    prompt gets alone — neither the convolution nor the state leaks."""
    lens, W = [13, 5, 9], 8
    prompts = [_ids(n, seed=10 + n) for n in lens]
    tables = np.zeros((4, W), np.int32)
    tables[0, :4], tables[1, :2], tables[2, :3] = [1, 2, 3, 4], [5, 6], [7, 8, 9]
    stream = np.zeros((32,), np.int32)
    stream[:sum(lens)] = sum(prompts, [])
    offset = np.asarray([0, 13, 18, 27], np.int32)
    lanes = jnp.asarray([2, 0, 3, 4], jnp.int32)      # lane 4: no such lane
    packed, pool = llama.prefill_packed(
        params, CFG, jnp.asarray(stream), jnp.asarray(offset),
        jnp.asarray(lens + [0], jnp.int32),
        llama.init_kv_pages(CFG, 16, 4, state_lanes=4), jnp.asarray(tables),
        row_len=16, lanes=lanes)
    for j, prompt in enumerate(prompts):
        row = np.zeros((1, 16), np.int32)
        row[0, :len(prompt)] = prompt
        alone, one = llama.prefill(
            params, CFG, jnp.asarray(row), jnp.asarray([len(prompt)]),
            llama.init_kv_pages(CFG, 16, 4, state_lanes=1),
            jnp.asarray(tables[j:j + 1]), lanes=jnp.zeros((1,), jnp.int32))
        np.testing.assert_allclose(packed[j], alone[0], atol=TOL, rtol=TOL)
        lane = int(lanes[j])
        for got, want in zip(pool.ssm + pool.conv, one.ssm + one.conv):
            np.testing.assert_allclose(got[lane], want[0], atol=TOL, rtol=TOL)
        blocks = tables[j][tables[j] > 0]
        n = len(prompt)
        for got, want in zip(pool.k + pool.v, one.k + one.v):
            np.testing.assert_allclose(
                got[blocks].reshape(-1, got.shape[-1])[:n],
                want[blocks].reshape(-1, want.shape[-1])[:n],
                atol=TOL, rtol=TOL)
    assert not np.asarray(pool.ssm[0][1]).any()       # lane 1: never named


def test_a_preempted_lane_is_requeued_and_answers_the_same(params):
    """A pool too small for three lanes' answers: the evicted lane's state is
    dropped with its pages and its prompt (with what it generated) is
    prefilled again — every answer is the undisturbed one."""
    prompts = [_ids(7, seed=20 + i) for i in range(3)]
    calm = _engine(params).generate(prompts, SamplingParams(max_tokens=12))
    tight = _engine(params, max_slots=3, num_blocks=14)
    results = tight.generate(prompts, SamplingParams(max_tokens=12))
    assert tight.preemptions > 0, "the pool was not tight enough to preempt"
    assert [r.token_ids for r in results] == [r.token_ids for r in calm]
    # A pipeline reset requeues every lane the same way.
    from k8s_llm_monitor_tpu.serving.engine import GenerationRequest

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
    """A cancelled lane's successor starts from its own prompt alone: the
    lane's state is overwritten at admission, never carried over."""
    from k8s_llm_monitor_tpu.serving.engine import GenerationRequest

    eng = _engine(params, max_slots=1)
    a, b = _ids(9, seed=30), _ids(6, seed=31)
    eng.submit(GenerationRequest("a", list(a), SamplingParams(max_tokens=20)))
    for _ in range(2):
        eng.step()
    assert eng.cancel("a")
    eng.submit(GenerationRequest("b", list(b), SamplingParams(max_tokens=6)))
    while eng.has_work:
        eng.step()
    got = eng.poll("b").token_ids
    assert got == _greedy(params, b, got)


# -- the share ------------------------------------------------------------------


@pytest.mark.parametrize("shares", [1, 2, 4, 8, 16, 32])
def test_the_shares_add_up(params, shares):
    """Every way of dividing the 32 experts into equal shares: the routed
    parts of all the shares, with what every chip computes alike (the
    shared expert) counted once, are the uncut reference layer."""
    layer = params["layers"][1]
    full = dataclasses.replace(CFG, experts_held=0, expert_start=0)
    whole = jax.tree.map(lambda a: a, layer)
    rng = jax.random.PRNGKey(7)
    for name, shape in (("up_e", (32, 32, 24)), ("down_e", (32, 24, 32))):
        rng, sub = jax.random.split(rng)
        whole[name] = {"kernel": jax.random.normal(sub, shape) * shape[1] ** -0.5}
    x = jnp.asarray(np.random.default_rng(8).standard_normal((21, 64)),
                    jnp.float32)
    h = ref.rms_norm(x, whole["input_norm"], CFG.rms_norm_eps)
    uncut, _ = ref.layer_forward(whole, ref.config_of(full), x, False)
    held = 32 // shares
    total = x + ref.relu2_mlp(whole["shared"], h, False)
    for s in range(shares):
        cfg = dataclasses.replace(CFG, experts_held=held, expert_start=s * held)
        part = dict(whole, **{name: {"kernel": whole[name]["kernel"][
            s * held:(s + 1) * held]} for name in ("up_e", "down_e")})
        routed, _ = ref.routed_part(part, ref.config_of(cfg), h, False)
        total = total + routed
        # The served layer of this share is the reference's of this share.
        got, counts = llama._moe_mlp_share(
            {k: v for k, v in part.items() if k != "shared"}, cfg, h[None])
        np.testing.assert_allclose(got[0], routed, atol=TOL, rtol=TOL)
        assert counts[3] == held and counts[4] == 21 * 5
    np.testing.assert_allclose(total, uncut, atol=TOL, rtol=TOL)


def test_the_windows_drop_no_assignment(params, monkeypatch):
    """Held assignments computed in windows of sorted rows: the same layer
    at a window smaller than its rows, with padding tokens between."""
    layer = params["layers"][3]
    x = jnp.asarray(np.random.default_rng(9).standard_normal((2, 24, 64)),
                    jnp.float32)
    valid = jnp.asarray(np.random.default_rng(9).random((2, 24)) < 0.8)
    want, want_counts = llama._moe_mlp_share(layer, CFG, x, valid)
    monkeypatch.setattr(llama, "_EXPERT_WINDOW_ROWS", 16)
    got, counts = llama._moe_mlp_share(layer, CFG, x, valid)
    np.testing.assert_allclose(got, want, atol=TOL, rtol=TOL)
    np.testing.assert_array_equal(counts, want_counts)
    assert counts[0] > 16 and counts[4] == 5 * int(valid.sum())


@pytest.mark.parametrize("window", [16_384, 32], ids=["one-window", "windows"])
def test_share_layer_w8a8_stream_form_is_the_compiler_form_exactly(
        params, monkeypatch, window):
    """The held experts' products through the stream kernel (ops/grouped.py,
    in the interpreter; the predicate picks it on a TPU at a decode step's
    rows), whole and in windows whose group sizes are clipped: the same
    int32, so the same layer output bit for bit."""
    import functools

    from k8s_llm_monitor_tpu.ops import grouped

    cfg = dataclasses.replace(CFG, act_quant=True)
    layer = quantize_params(params)["layers"][3]
    x = jnp.asarray(np.random.default_rng(10).standard_normal((24, 1, 64)),
                    jnp.float32)
    valid = jnp.asarray(np.random.default_rng(10).random((24, 1)) < 0.8)
    monkeypatch.setattr(llama, "_EXPERT_WINDOW_ROWS", window)
    want, want_counts = llama._moe_mlp_share(layer, cfg, x, valid)
    taken = []

    def stream(m, g, k, n, dtype, platform=None):
        taken.append((m, g, k, n, jnp.dtype(dtype)))
        return functools.partial(grouped.grouped_rows_product, interpret=True)

    monkeypatch.setattr(grouped, "select_grouped_product", stream)
    got, counts = llama._moe_mlp_share(layer, cfg, x, valid)
    rows = min(24 * cfg.num_experts_per_tok, window)
    L, I = cfg.moe_latent_size, cfg.expert_width
    assert taken == [(rows, cfg.experts_held_, L, I, jnp.int8),
                     (rows, cfg.experts_held_, I, L, jnp.int8)]
    np.testing.assert_array_equal(got, want)
    np.testing.assert_array_equal(counts, want_counts)


@pytest.mark.parametrize("window", [16_384, 64], ids=["one-window", "windows"])
def test_share_layer_w8a8_tiles_form_is_the_compiler_form_exactly(
        params, monkeypatch, window):
    """The held experts' products through the tiles kernel (ops/grouped.py,
    in the interpreter; the predicate picks it on a TPU at an admission
    call's rows), which dequantises too, whole and in windows whose group
    sizes are clipped: the same layer output bit for bit."""
    import functools

    from k8s_llm_monitor_tpu.ops import grouped

    cfg = dataclasses.replace(CFG, act_quant=True)
    layer = quantize_params(params)["layers"][3]
    x = jnp.asarray(np.random.default_rng(12).standard_normal((2, 60, 64)),
                    jnp.float32)
    valid = jnp.asarray(np.random.default_rng(12).random((2, 60)) < 0.8)
    monkeypatch.setattr(llama, "_EXPERT_WINDOW_ROWS", window)
    want, want_counts = llama._moe_mlp_share(layer, cfg, x, valid)
    taken = []

    def form(m, g, k, n, dtype, platform=None):
        taken.append((m, g, k, n, jnp.dtype(dtype)))
        return "tiles"

    monkeypatch.setattr(grouped, "product_form", form)
    monkeypatch.setattr(grouped, "grouped_tiles_product", functools.partial(
        grouped.grouped_tiles_product, interpret=True))
    got, counts = llama._moe_mlp_share(layer, cfg, x, valid)
    rows = min(120 * cfg.num_experts_per_tok, window)
    L, I = cfg.moe_latent_size, cfg.expert_width
    assert taken == [(rows, cfg.experts_held_, L, I, jnp.int8),
                     (rows, cfg.experts_held_, I, L, jnp.int8)]
    np.testing.assert_array_equal(got, want)
    np.testing.assert_array_equal(counts, want_counts)
    assert counts[0] > 2 * 64   # the windowed case takes three windows


@pytest.mark.parametrize("form", ["w8a8", "weight_only"])
def test_the_quantised_forms_against_the_reference(params, form):
    """int8 kernels, with and without activation rounding: the reference
    takes the same served parameters and rounds where ``_linear`` does."""
    aq = form == "w8a8"
    cfg = dataclasses.replace(CFG, act_quant=aq)
    qparams = quantize_params(params)
    assert qparams["layers"][0]["in_proj"]["kernel_q"].dtype == jnp.int8
    assert qparams["layers"][1]["up_e"]["kernel_q"].shape == (8, 32, 24)
    assert qparams["layers"][1]["router"]["kernel"].dtype == jnp.float32
    assert qparams["layers"][0]["conv"]["kernel"].dtype == jnp.float32
    x = jnp.asarray(np.random.default_rng(11).standard_normal((1, 15, 64)),
                    jnp.float32)
    pos = jnp.arange(15, dtype=jnp.int32)[None]
    for li, layer in enumerate(qparams["layers"]):
        got, _ = llama.layer_block(layer, cfg, x, None, None, pos, layer_idx=li)
        want, _ = ref.layer_forward(layer, RC, x[0], aq)
        np.testing.assert_allclose(got[0], want, atol=2e-4, rtol=2e-4)
    # The seeded int8 weights the benchmark serves, through the engine.
    served = init_params_quantized(jax.random.PRNGKey(1), cfg)
    assert jax.tree.structure(served) == jax.tree.structure(qparams)
    prompt = _ids(10, seed=12)
    rows, states = _engine(served, cfg).score_logits(prompt, 3, hidden=True)
    for li, layer in enumerate(served["layers"]):
        want, _ = ref.layer_forward(layer, RC, jnp.asarray(states[li]), aq)
        np.testing.assert_allclose(states[li + 1], want, atol=2e-4, rtol=2e-4)


# -- what is counted --------------------------------------------------------------


def test_the_counts_and_the_census(params):
    from k8s_llm_monitor_tpu.observability.tracing import (
        Tracer,
        get_tracer,
        set_tracer,
    )

    before = get_tracer()
    set_tracer(Tracer(ring_size=4096, sample=1.0))
    try:
        eng = _engine(params)
        eng.generate([_ids(9, seed=40), _ids(6, seed=41)],
                     SamplingParams(max_tokens=5))
        calls = [s["attrs"] for s in get_tracer().snapshot()
                 if s["name"] == "engine.call"]
    finally:
        set_tracer(before)
    assert {c["kind"] for c in calls} == {"admit", "decode"}
    lane = CFG.state_lane_bytes(4)
    for c in calls:
        assert set(c) <= set(SPAN_CATALOG["engine.call"]), set(c) - set(
            SPAN_CATALOG["engine.call"])
        assert set(MOE_SHARE_COUNTS) <= set(c)
        assert c["state_lane_bytes"] == lane
        assert c["state_pool_bytes"] == lane * ENGINE["max_slots"]
        assert 0 < c["state_live_lanes"] <= 2
        assert c["moe_assignments"] <= c["moe_assignments_all"]
        if c["kind"] == "admit":
            assert c["moe_assignments_all"] == c["real_tokens"] * 5 * 3
            assert c["moe_expert_layer_steps"] == 8 * 3
        else:       # experts held x expert layers x steps
            assert c["moe_expert_layer_steps"] == 8 * 3 * c["steps"]
    totals = eng.moe_totals
    assert totals["assignments_all"] == sum(c["moe_assignments_all"] for c in calls)
    assert 0 < totals["assignments"] < totals["assignments_all"]
    # The exporter's twins.
    from k8s_llm_monitor_tpu.monitor import exporter

    w = exporter._Writer()
    exporter._engine_metrics(w, eng)
    exporter._loop_metrics(w, eng)
    text = "\n".join(w.lines)
    for line in (f"engine_state_pool_bytes {lane * ENGINE['max_slots']}",
                 f"engine_state_lane_bytes {lane}",
                 f"engine_moe_assignments_all_total {totals['assignments_all']}",
                 f"engine_moe_assignments_total {totals['assignments']}"):
        assert f"k8s_llm_monitor_{line}" in text, line


def test_state_counts_read_catalogued_attributes():
    import json
    import pathlib

    from benchmarks.harness import state_counts

    root = pathlib.Path(__file__).resolve().parents[1]
    config = json.loads((root / "benchmarks/configs/"
                         "nemotron3-super-120b-a12b-w8a8.json").read_text())
    for name, reads in state_counts.READS.items():
        assert set(reads) <= set(SPAN_CATALOG["engine.call"]), name
        assert getattr(state_counts, name)(config, {}) is None
        assert name in state_counts.PEAK_OF
    ops_, nbytes = state_counts.ssm_decode_update(config, {"steps": 8, "lanes": 60})
    assert nbytes == 8 * 60 * 10 * 2 * 128 * 64 * 128 * 4
    assert ops_ == 5 * nbytes / 8


# -- what is not built is refused --------------------------------------------------


def test_what_is_not_built_is_refused(params):
    reason = InferenceEngine._unbuilt_reason(CFG)
    assert "recurrent state" in reason and "share of its experts" in reason
    for over, what in ((dict(spec_k=2), "spec_k=2"),
                       (dict(host_spill_bytes=1 << 20), "host KV tier"),
                       (dict(kv_dtype="int8"), "kv_dtype='int8'"),
                       (dict(kv_dtype="fp8"), "kv_dtype='fp8'"),
                       (dict(tp_overlap="on"), "tp_overlap"),
                       (dict(max_blocks_per_seq=16), "chunked prefill")):
        with pytest.raises(ValueError, match="is not built for") as exc:
            _engine(params, **over)
        assert what in str(exc.value) and reason in str(exc.value)
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
    pages = llama.init_kv_pages(CFG, 8, 4, state_lanes=1)
    tok, one = jnp.zeros((1, 8), jnp.int32), jnp.ones((1,), jnp.int32)
    with pytest.raises(ValueError, match="chunked prefill, a cached prefix"):
        llama.prefill_chunk(params, CFG, tok, one, one, pages,
                            jnp.zeros((1, 8), jnp.int32))
    with pytest.raises(ValueError, match="chunked prefill, a cached prefix"):
        llama.prefill(params, CFG, tok, one, pages, jnp.zeros((1, 8), jnp.int32))
    with pytest.raises(ValueError, match="not built for kv_dtype"):
        llama.init_kv_pages(CFG, 8, 4, kv_quant="int8", state_lanes=1)


def test_a_repeated_prompt_is_prefilled_again(params):
    """No state snapshot exists, so the prefix cache is not consulted: the
    same prompt twice (the harness's probe) is two fresh prefills, same
    token."""
    eng = _engine(params, prefix_cache_entries=64)
    assert eng.prefix_cache is None
    prompt = _ids(17, seed=50)
    first, second = (eng.generate([prompt], SamplingParams(max_tokens=4))[0]
                     for _ in range(2))
    assert first.token_ids == second.token_ids
    assert eng.prefill_tokens == {"real": 34, "padded": 64, "cached": 0}
