"""The jamba block (Mamba-1 selective-scan layers over a per-lane state
pool, MQA attention without rotation in a layer chosen by offset and
period, a dense MLP after every mixer) against its plain reference
(``benchmarks/references/jamba.py``: float32, the recurrence token by token),
at the tiny preset on the CPU in float32, to 5e-5 as
``tests/test_nemotron_h.py``; and both Mamba-1 kernels in the interpreter
against their XLA forms.
"""

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.references import jamba as ref
from k8s_llm_monitor_tpu.models import llama
from k8s_llm_monitor_tpu.models.config import PRESETS
from k8s_llm_monitor_tpu.ops import ssm
from k8s_llm_monitor_tpu.serving.engine import (
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
CFG = dataclasses.replace(PRESETS["tiny-jamba"], dtype="float32")
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


def _greedy(params, prompt, toks):
    want, _ = ref.forward(params, RC, prompt + toks)
    return [int(np.argmax(want[len(prompt) - 1 + i]))
            for i in range(len(toks))]


# -- the description -----------------------------------------------------------


def test_the_layers_are_the_description():
    """Attention where i % period == offset, Mamba-1 elsewhere, a dense MLP
    after every mixer; the served preset's layers 7 and 21 attend."""
    specs = [CFG.layer_spec(i) for i in range(CFG.num_layers)]
    assert [(s.mixer, s.mlp, s.cache) for s in specs] == [
        ("mamba1", "dense", "state"), ("mamba1", "dense", "state"),
        ("full", "dense", "kv"), ("mamba1", "dense", "state")]
    assert CFG.recurrent and CFG.state_mixer == "mamba1"
    assert CFG.num_heads % 8 and CFG.num_kv_heads == 1
    assert CFG.mamba_dt_rank < CFG.hidden_size and CFG.ssm_state_size == 16
    assert (CFG.mamba_inner, CFG.mamba_conv_dim) == (128, 128)
    # Pages for the attention layer only; a state row a lane a Mamba layer.
    pages = llama.init_kv_pages(CFG, 8, 4, state_lanes=3)
    assert len(pages.k) == len(pages.v) == 1
    assert [a.shape for a in pages.ssm] == [(3, 16, 128)] * 3
    assert [a.shape for a in pages.conv] == [(3, 3, 128)] * 3
    assert CFG.state_lane_bytes(4) == 3 * (128 * 16 * 4 + 3 * 128 * 4)
    served = PRESETS["jamba2-3b"]
    assert served.layers_with("kv") == [7, 21]
    assert len(served.layers_with("state")) == 26
    assert served.kv_token_bytes() == 1_024
    assert served.state_lane_bytes() == 26 * (327_680 + 30_720) == 9_318_400
    assert PRESETS["nemotron3-super-120b-a12b-22l"].state_mixer == "mamba2"
    assert PRESETS["qwen2-7b"].state_mixer == ""


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
    want, _ = ref.forward(params, RC, toks)
    np.testing.assert_allclose(got, want, atol=TOL, rtol=TOL)


# -- the two forms of each kernel ------------------------------------------------


def _scan_operands(C, N, Q, dtype, layout, seed=0):
    """Mamba-1 scan operands over a packed stream (segments that start
    inside a chunk and inside a group of the kernel's tokens, two that end in
    one chunk, padding — step 0 — between two and behind the last) or over
    rows of unequal lengths with padding behind each.  Returns the operands
    and which tokens are real."""
    rng = np.random.default_rng(seed)
    if layout == "stream":
        Bt, S = 1, 3 * Q + Q // 2 + 5
        a, b = Q // 2 + 3, Q + 7
        segs = [(0, a), (a, b), (b + 2, 2 * Q + 9), (2 * Q + 20, S - 6)]
    else:
        S = Q + Q // 2 + 3
        segs = [(r * S, r * S + n) for r, n in enumerate([S, Q + 5, 9])]
        Bt = len(segs)
    first, real = np.zeros(Bt * S, bool), np.zeros(Bt * S, bool)
    first[::S] = True
    for a, b in segs:
        first[a], real[a:b] = True, True
    dt = np.where(real[:, None], np.log1p(np.exp(
        rng.standard_normal((Bt * S, C)) - 2.0)), 0.0).reshape(Bt, S, C)
    normal = lambda *shape: jnp.asarray(  # noqa: E731
        rng.standard_normal(shape), jnp.float32)
    A = -np.exp(np.log(np.arange(1, N + 1))[:, None]
                + 0.1 * rng.standard_normal((N, C)))
    return (normal(Bt, S, C).astype(dtype), jnp.asarray(dt, jnp.float32),
            jnp.asarray(A, jnp.float32), normal(Bt, S, N), normal(Bt, S, N),
            jnp.asarray(first.reshape(Bt, S)),
            jnp.asarray([b - 1 for _, b in segs], jnp.int32)), real.reshape(Bt, S)


def test_the_scan_is_the_recurrence():
    """``mamba1_scan`` (the XLA form) against the recurrence written in numpy,
    sequence by sequence, over the packed stream: ``y`` on every real token
    and every wanted state."""
    args, real = _scan_operands(8, 4, 16, jnp.float32, "stream", seed=1)
    u, dt, A, Bm, Cm, first, last = (np.asarray(a, np.float64) for a in args)
    y, states = ssm.mamba1_scan(*args)
    starts = list(np.flatnonzero(first[0])) + [u.shape[1]]
    got_states = dict(zip(np.asarray(last), np.asarray(states)))
    for a, b in zip(starts[:-1], starts[1:]):
        s = np.zeros(A.shape)
        for t in range(a, b):
            s = (np.exp(dt[0, t][None] * A) * s
                 + Bm[0, t][:, None] * (dt[0, t] * u[0, t])[None])
            np.testing.assert_allclose(y[0, t], s.T @ Cm[0, t], atol=TOL,
                                       rtol=TOL)
            if t in got_states:
                np.testing.assert_allclose(got_states[t], s, atol=TOL, rtol=TOL)


@pytest.mark.parametrize("layout", ["stream", "rows"])
@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_the_scan_kernel_is_its_xla_form(dtype, layout):
    """``mamba1_scan_kernel`` in the interpreter against ``mamba1_scan`` at
    256 channels (two register blocks of 128) and the published state size,
    chunks of 32 tokens (two groups of 16): the same arithmetic in the same
    order, so ``y`` on every real token and every wanted state agree to
    float32's rounding — ``y`` in the operands' dtype."""
    args, real = _scan_operands(256, 16, 32, jnp.dtype(dtype), layout)
    want_y, want_states = ssm.mamba1_scan(*args)
    y, states = ssm.mamba1_scan_kernel(*args, chunk=32, interpret=True)
    assert y.dtype == want_y.dtype and states.dtype == jnp.float32
    f = lambda a: np.asarray(a, np.float32)  # noqa: E731
    tol = 2.0 ** -8 if dtype == "bfloat16" else 1e-5
    scale = np.abs(f(want_y)[real]).max()
    assert np.abs(f(y) - f(want_y))[real].max() <= tol * scale
    np.testing.assert_allclose(states, want_states, rtol=1e-5,
                               atol=1e-5 * np.abs(want_states).max())


@pytest.mark.parametrize("channels,state,wanted,platform,form", [
    (5120, 16, 8, "tpu", "kernel"),
    (5120, 16, 25, "tpu", "kernel"),
    (5120, 16, 8, "cpu", "xla"),
    (5120, 16, 26, "tpu", "xla"),
    (5000, 16, 8, "tpu", "xla"),
    (5120, 12, 8, "tpu", "xla"),
], ids=["the-cell", "most-states", "off-the-tpu", "too-many-states",
        "channels-off-the-lanes", "state-off-the-sublanes"])
def test_mamba1_scan_form_by_shape_and_platform(channels, state, wanted,
                                                platform, form):
    """The kernel on a TPU where the channels fill whole lane tiles, the
    state whole sublane tiles and the wanted states their VMEM block; the
    XLA form elsewhere."""
    assert ssm.mamba1_scan_form(channels, state, wanted,
                                platform=platform) == form


def test_the_decode_kernel_is_its_xla_form():
    """``mamba1_decode_update`` in the interpreter: a lane order that is not
    the identity, an idle row (step 0: its state comes back bit-equal), a
    lane no row names (untouched)."""
    rng = np.random.default_rng(4)
    C, N, L = 256, 16, 6
    pool = jnp.asarray(rng.standard_normal((L, N, C)), jnp.float32)
    lanes = jnp.asarray([4, 0, 5, 2], jnp.int32)
    idle = np.asarray([False, True, False, False])
    dt = jnp.where(idle[:, None], 0.0, jnp.asarray(
        np.abs(rng.standard_normal((4, C))) * 0.1, jnp.float32))
    dtu = dt * jnp.asarray(rng.standard_normal((4, C)), jnp.float32)
    A = jnp.asarray(-np.exp(rng.uniform(0, 2.5, (N, C))), jnp.float32)
    Bm, Cm = (jnp.asarray(rng.standard_normal((4, N)), jnp.float32)
              for _ in range(2))
    want_y, want_pool = ssm.mamba1_decode_update_xla(pool, lanes, dt, dtu, A,
                                                     Bm, Cm)
    new = (np.asarray(pool)[np.asarray(lanes)]
           * np.exp(np.asarray(dt)[:, None] * np.asarray(A))
           + np.asarray(Bm)[:, :, None] * np.asarray(dtu)[:, None])
    np.testing.assert_allclose(want_y, np.einsum("bnc,bn->bc", new, Cm),
                               atol=TOL, rtol=TOL)
    y, got = ssm.mamba1_decode_update(pool, lanes, dt, dtu, A, Bm, Cm,
                                      interpret=True)
    np.testing.assert_allclose(y, want_y, atol=TOL, rtol=TOL)
    np.testing.assert_allclose(got, want_pool, atol=TOL, rtol=TOL)
    np.testing.assert_array_equal(got[0], pool[0])      # the idle row's
    np.testing.assert_array_equal(got[1], pool[1])      # no row's lane


# -- through the pools -----------------------------------------------------------


@pytest.mark.parametrize("length", [5, 13])
def test_prefill_then_decode_through_the_pools(params, length):
    """``score_logits`` is the engine's own prefill into its pools and its
    own decode steps against them: logits and one reference layer at a time
    on the engine's own input (what compare_layers.py does)."""
    eng = _engine(params)
    prompt = _ids(length, seed=length)
    rows, states = eng.score_logits(prompt, 5, hidden=True)
    fed = [int(np.argmax(r)) for r in rows[:-1]]
    want, _ = ref.forward(params, RC, prompt + fed,
                          logit_positions=list(range(length - 1, length + 5)))
    np.testing.assert_allclose(rows, want, atol=TOL, rtol=TOL)
    for li, layer in enumerate(params["layers"]):
        want, _ = ref.layer_forward(layer, RC, jnp.asarray(states[li]), False)
        np.testing.assert_allclose(states[li + 1], want, atol=TOL, rtol=TOL)


def test_the_engine_serves_it_and_the_kernels_too(params):
    """Greedy answers through the engine are the reference's; the same
    through the decode kernel (interpreted): same tokens."""
    prompts = [_ids(n, seed=n) for n in (5, 11, 17, 9, 3)]
    results = _engine(params).generate(prompts, SamplingParams(max_tokens=7))
    for p, r in zip(prompts, results):
        assert r.token_ids == _greedy(params, p, r.token_ids)
    kernel = _engine(params)
    kernel._ssm_update = functools.partial(ssm.mamba1_decode_update,
                                           interpret=True)
    again = kernel.generate(prompts[:3], SamplingParams(max_tokens=7))
    assert [r.token_ids for r in again] == [r.token_ids for r in results[:3]]


def test_admission_through_the_scan_kernel(params, monkeypatch):
    """An engine whose admission scan takes the kernel (the predicate told
    so, the kernel interpreted) emits the tokens the XLA form's does, and
    every admission call says which form its program took."""
    prompts = [_ids(n, seed=50 + n) for n in (5, 11, 17)]
    want = _engine(params).generate(prompts, SamplingParams(max_tokens=6))
    from k8s_llm_monitor_tpu.monitor import exporter
    from k8s_llm_monitor_tpu.observability.tracing import (
        Tracer,
        get_tracer,
        set_tracer,
    )

    monkeypatch.setattr(ssm, "mamba1_scan_form",
                        lambda *a, platform=None: "kernel")
    monkeypatch.setattr(ssm, "mamba1_scan_kernel", functools.partial(
        ssm.mamba1_scan_kernel, chunk=16, interpret=True))
    before = get_tracer()
    set_tracer(Tracer(ring_size=4096, sample=1.0))
    try:
        eng = _engine(params)
        got = eng.generate(prompts, SamplingParams(max_tokens=6))
        calls = [s["attrs"] for s in get_tracer().snapshot()
                 if s["name"] == "engine.call"]
    finally:
        set_tracer(before)
    assert [r.token_ids for r in got] == [r.token_ids for r in want]
    admits = [c for c in calls if c["kind"] == "admit"]
    assert admits and all(c["ssm_scan_form"] == "kernel" for c in admits)
    assert all(set(c) <= set(SPAN_CATALOG["engine.call"]) for c in calls)
    assert eng.ssm_scan_calls == {"kernel": len(admits), "xla": 0}
    w = exporter._Writer()
    exporter._loop_metrics(w, eng)
    assert (f'k8s_llm_monitor_engine_ssm_scan_calls_total{{form="kernel"}} '
            f'{len(admits)}') in "\n".join(w.lines)


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
    assert not np.asarray(pool.ssm[0][1]).any()       # lane 1: never named


def test_cancel_leaves_the_lane_clean(params):
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


@pytest.mark.parametrize("form", ["w8a8", "weight_only"])
def test_the_quantised_forms_against_the_reference(params, form):
    """int8 projections, with and without activation rounding: the reference
    takes the same served parameters and rounds where ``_linear`` does; the
    seeded int8 weights the benchmark serves, through the engine."""
    aq = form == "w8a8"
    cfg = dataclasses.replace(CFG, act_quant=aq)
    qparams = quantize_params(params)
    mixer = qparams["layers"][0]
    assert all(mixer[k]["kernel_q"].dtype == jnp.int8
               for k in ("in_proj", "x_proj", "dt_proj", "out_proj", "gate"))
    assert all(mixer[k].dtype == jnp.float32 for k in ("A_log", "D", "dt_bias"))
    assert mixer["conv"]["kernel"].dtype == jnp.float32
    x = jnp.asarray(np.random.default_rng(11).standard_normal((1, 15, 64)),
                    jnp.float32)
    pos = jnp.arange(15, dtype=jnp.int32)[None]
    for li, layer in enumerate(qparams["layers"]):
        got, _ = llama.layer_block(layer, cfg, x, None, None, pos, layer_idx=li)
        want, _ = ref.layer_forward(layer, RC, x[0], aq)
        np.testing.assert_allclose(got[0], want, atol=2e-4, rtol=2e-4)
    served = init_params_quantized(jax.random.PRNGKey(1), cfg)
    assert jax.tree.structure(served) == jax.tree.structure(qparams)
    prompt = _ids(10, seed=12)
    rows, states = _engine(served, cfg).score_logits(prompt, 3, hidden=True)
    for li, layer in enumerate(served["layers"]):
        want, _ = ref.layer_forward(layer, RC, jnp.asarray(states[li]), aq)
        np.testing.assert_allclose(states[li + 1], want, atol=2e-4, rtol=2e-4)


# -- what is not built is refused --------------------------------------------------


@pytest.mark.parametrize("over,what", [
    (dict(spec_k=2), "spec_k=2"),
    (dict(host_spill_bytes=1 << 20), "host KV tier"),
    (dict(kv_dtype="int8"), "kv_dtype='int8'"),
    (dict(kv_dtype="fp8"), "kv_dtype='fp8'"),
    (dict(tp_overlap="on"), "tp_overlap"),
    (dict(max_blocks_per_seq=16), "chunked prefill"),
    ("mesh", "a mesh"),
], ids=["spec", "host-tier", "int8-pages", "fp8-pages", "tp-overlap",
        "chunked-prefill", "mesh"])
def test_what_is_not_built_is_refused(params, over, what):
    """Each refusal names the Mamba-1 mixer's state pool and ROADMAP Queue
    2; nothing falls back in silence."""
    reason = InferenceEngine._unbuilt_reason(CFG)
    assert reason == "recurrent state (a per-lane state pool of Mamba-1 layers)"
    with pytest.raises(ValueError, match="is not built for") as exc:
        if over == "mesh":
            from jax.sharding import Mesh

            mesh = Mesh(np.asarray(jax.devices()[:1]).reshape(1, 1, 1),
                        ("data", "seq", "model"))
            InferenceEngine(CFG, params, EngineConfig(**ENGINE), eos_id=-1,
                            mesh=mesh)
        else:
            _engine(params, **over)
    assert what in str(exc.value) and reason in str(exc.value)
    assert "ROADMAP Queue 2" in str(exc.value)


def test_no_prefix_cache_and_no_continuation(params):
    """No state snapshot exists, so the prefix cache is not consulted, and
    the model functions refuse chunked prefill and a cached prefix."""
    eng = _engine(params, prefix_cache_entries=64)
    assert eng.prefix_cache is None
    with pytest.raises(ValueError, match="export_prefix is not built for"):
        eng.export_prefix(_ids(9), tenant="t")
    pages = llama.init_kv_pages(CFG, 8, 4, state_lanes=1)
    tok, one = jnp.zeros((1, 8), jnp.int32), jnp.ones((1,), jnp.int32)
    with pytest.raises(ValueError, match="chunked prefill, a cached prefix"):
        llama.prefill_chunk(params, CFG, tok, one, one, pages,
                            jnp.zeros((1, 8), jnp.int32))
    with pytest.raises(ValueError, match="not built for kv_dtype"):
        llama.init_kv_pages(CFG, 8, 4, kv_quant="int8", state_lanes=1)


def test_mamba1_counts_read_catalogued_attributes():
    import json
    import pathlib

    from benchmarks.harness import mamba1_counts

    root = pathlib.Path(__file__).resolve().parents[1]
    config = json.loads((root / "benchmarks/configs/"
                         "jamba2-3b-w8a8.json").read_text())
    for name, reads in mamba1_counts.READS.items():
        assert set(reads) <= set(SPAN_CATALOG["engine.call"]), name
        assert getattr(mamba1_counts, name)(config, {}) is None
        assert name in mamba1_counts.PEAK_OF
    ops_, nbytes = mamba1_counts.mamba1_decode_update(
        config, {"steps": 8, "lanes": 250})
    assert nbytes == 8 * 250 * 26 * 2 * 327_680
    assert ops_ == 5 * nbytes / 8
    ops_, nbytes = mamba1_counts.mamba1_scan(
        config, {"real_tokens": 1_600, "prompts": 4})
    assert nbytes == 26 * (1_600 * 20_928 + 4 * 327_680)
    # Bandwidth-bound at the chip's peaks (benchmarks/peaks.json: v5e).
    assert ops_ / 197e12 < nbytes / 819e9
