"""The packed form of a fresh admission call (PR 27): a round's prompts end to
end in one token stream, what is computed per token computed on the stream,
attention alone through rows (models/llama.py:prefill_packed, RowView;
serving/engine.py:_dispatch_admit, _admit_groups).

The row functions (``llama.prefill`` + ``decode_step``) stay for the chunk
and prefix-hit paths and are what every packed result is held to; no switch
turns the packed form off, so the engine's outputs are compared with the row
functions run by hand on each prompt alone.
"""

import dataclasses
import hashlib
import itertools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from k8s_llm_monitor_tpu.models import llama
from k8s_llm_monitor_tpu.models.config import PRESETS, ModelConfig
from k8s_llm_monitor_tpu.serving.engine import (
    EngineConfig,
    InferenceEngine,
    SamplingParams,
)
from k8s_llm_monitor_tpu.utils.quantize import quantize_params

BS, NBLK, W = 8, 96, 8            # pages of 8 tokens; a row holds 64
R, TOP = 4, 32                    # rows of a call, the top bucket
ENGINE = dict(max_slots=4, num_blocks=NBLK, block_size=BS,
              max_blocks_per_seq=W, prefill_buckets=(16, TOP),
              max_prefills_per_step=R, prefix_cache_entries=0)


def _w8a8(cfg: ModelConfig, params):
    return dataclasses.replace(cfg, act_quant=True), quantize_params(params)


def _model(name: str, dtype: str = ""):
    """(cfg, params, atol): the three families the packed form serves.
    Tolerances are the ones these models' own files use for two layouts of
    one computation: float32 paths agree to reduction order
    (tests/test_latent_moe.py: 5e-5; tests/test_paged_cache.py: 1e-4);
    ``tiny-qwen`` runs w8a8 on bfloat16 activations, where an activation
    that lands one bfloat16 step away moves a rounding of the next
    projection's int8 input.  ``dtype`` overrides the activations' type."""
    cfg, atol = {"qwen-w8a8": (PRESETS["tiny-qwen"], 2e-2),
                 "latent-moe": (dataclasses.replace(
                     PRESETS["tiny-latent-moe"], dtype="float32"), 5e-5),
                 "dense-bf16": (PRESETS["tiny"], 2e-2)}[name]
    if dtype:
        cfg = dataclasses.replace(cfg, dtype=dtype)
    params = llama.init_params(jax.random.PRNGKey(0), cfg)
    if name == "qwen-w8a8":
        cfg, params = _w8a8(cfg, params)
    return cfg, params, atol


MODELS = ["qwen-w8a8", "latent-moe", "dense-bf16"]


@pytest.fixture(scope="module", params=MODELS)
def model(request):
    return _model(request.param)


def _prompts(cfg, lengths, seed=0):
    rng = np.random.default_rng(seed)
    return [rng.integers(1, cfg.vocab_size, n).astype(np.int32)
            for n in lengths]


def _tables(lengths):
    """Disjoint blocks for each prompt (and the token after it), from 1."""
    tables, nxt = np.zeros((R, W), np.int32), 1
    for j, n in enumerate(lengths):
        nb = -(-(n + 1) // BS)
        tables[j, :nb] = np.arange(nxt, nxt + nb)
        nxt += nb
    return tables


def _row_call(cfg, params, prompts, tables, attn_impl=None):
    tok = np.zeros((R, TOP), np.int32)
    lens = np.zeros((R,), np.int32)
    for j, p in enumerate(prompts):
        tok[j, :len(p)], lens[j] = p, len(p)
    return llama.prefill(params, cfg, jnp.asarray(tok), jnp.asarray(lens),
                         llama.init_kv_pages(cfg, NBLK, BS),
                         jnp.asarray(tables), attn_impl=attn_impl)


def _packed_call(cfg, params, prompts, tables, T, attn_impl=None):
    tok = np.zeros((T,), np.int32)
    off = np.full((R,), sum(len(p) for p in prompts), np.int32)
    lens = np.zeros((R,), np.int32)
    at = 0
    for j, p in enumerate(prompts):
        tok[at:at + len(p)], off[j], lens[j] = p, at, len(p)
        at += len(p)
    return llama.prefill_packed(
        params, cfg, jnp.asarray(tok), jnp.asarray(off), jnp.asarray(lens),
        llama.init_kv_pages(cfg, NBLK, BS), jnp.asarray(tables),
        row_len=min(T, TOP), attn_impl=attn_impl)


# 1, 3 and R prompts of unequal lengths; the last fills its stream exactly
# (no padding token) and holds a prompt as long as the row view.
CALLS = {"one": ([21], 32), "three": ([13, 5, 30], 64),
         "full-house": ([32, 7, 16, 9], 64)}


@pytest.mark.parametrize("call", list(CALLS))
def test_packed_call_equals_the_row_call(model, call):
    cfg, params, atol = model
    lengths, T = CALLS[call]
    prompts, tables = _prompts(cfg, lengths), _tables(lengths)
    want, want_pages = _row_call(cfg, params, prompts, tables)
    got, got_pages = _packed_call(cfg, params, prompts, tables, T)
    n = len(prompts)
    np.testing.assert_allclose(np.asarray(got[:n]), np.asarray(want[:n]),
                               atol=atol, rtol=0)
    # Pages: every real position holds what the row call wrote there; every
    # block but the null one (which takes the padding's writes in both
    # forms) that no prompt owns is still zero.
    owned = np.zeros((NBLK,), bool)
    owned[tables[tables > 0]] = True
    for a, b in zip(want_pages.k + want_pages.v, got_pages.k + got_pages.v):
        a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
        for j, p in enumerate(prompts):
            rows_a = a[tables[j]].reshape(W * BS, -1)[:len(p)]
            rows_b = b[tables[j]].reshape(W * BS, -1)[:len(p)]
            np.testing.assert_allclose(rows_b, rows_a, atol=atol, rtol=0)
        assert not b[~owned][1:].any()


def test_a_prompt_does_not_depend_on_its_neighbours(model):
    """The same prompt alone and as the third of three: every operation on
    the stream is per token, so the numbers are the same to the bit in
    float32 and to one bfloat16 rounding otherwise."""
    cfg, params, atol = model
    prompts = _prompts(cfg, [13, 5, 30])
    alone, _ = _packed_call(cfg, params, prompts[2:], _tables([30]), 32)
    among, _ = _packed_call(cfg, params, prompts, _tables([13, 5, 30]), 64)
    np.testing.assert_allclose(np.asarray(among[2]), np.asarray(alone[0]),
                               atol=atol, rtol=0)


def _greedy_by_hand(cfg, params, prompt, n_tokens):
    """``llama.prefill`` + ``decode_step`` on one prompt alone."""
    pages = llama.init_kv_pages(cfg, NBLK, BS)
    table = jnp.arange(1, W + 1, dtype=jnp.int32)[None]
    tok = np.zeros((1, TOP), np.int32)
    tok[0, :len(prompt)] = prompt
    from k8s_llm_monitor_tpu.ops.attention import select_decode_impl
    impl = select_decode_impl(cfg=cfg, mesh=None, mode="auto", kv_quant="")
    logits, pages = llama.prefill(params, cfg, jnp.asarray(tok),
                                  jnp.asarray([len(prompt)]), pages, table)
    out, ctx = [int(jnp.argmax(logits[0]))], len(prompt)
    for _ in range(n_tokens - 1):
        logits, pages = llama.decode_step(
            params, cfg, jnp.asarray([out[-1]]), jnp.asarray([ctx]), pages,
            table, attn_impl=impl)
        out.append(int(jnp.argmax(logits[0])))
        ctx += 1
    return out


@pytest.fixture(scope="module", params=MODELS)
def served(request):
    """A seeded mixed-length workload through the engine: ten prompts on
    four lanes, so rounds of four, of one and of a few, each cut into calls
    by the search.  Activations in float32 for every family: with bfloat16
    ones and random weights an engine's greedy tokens differ from a run by
    hand at near-ties on the parent commit too (four lanes decode in one
    batch, the run by hand decodes one)."""
    cfg, params, _ = _model(request.param, dtype="float32")
    eng = InferenceEngine(cfg, params, EngineConfig(**ENGINE), eos_id=-1)
    lengths = [5, 17, 30, 9, 3, 22, 32, 1, 12, 27]
    prompts = [p.tolist() for p in _prompts(cfg, lengths, seed=3)]
    order = []
    eng.token_sink = lambda rid, toks, result: (
        order.append(rid) if toks and rid not in order else None)
    res = eng.generate(prompts, SamplingParams(max_tokens=5))
    return cfg, params, eng, prompts, res, order


def test_engine_greedy_output_equals_the_row_functions(served):
    cfg, params, eng, prompts, res, _ = served
    assert eng._packed_prefill and eng.prefill_tokens["padded"] > 0
    for prompt, r in zip(prompts, res):
        assert r.token_ids == _greedy_by_hand(cfg, params, prompt, 5), (
            len(prompt))


def test_engine_admits_in_the_order_it_was_given(served):
    _, _, eng, prompts, res, order = served
    assert order == [r.request_id for r in res]
    # Every call was packed, none computed more than twice its real tokens
    # (or the smallest rung), and the real tokens are the prompts'.
    assert eng.calls_by_kind.get("chunk", 0) == 0
    assert eng.prefill_tokens["real"] == sum(len(p) for p in prompts)
    assert eng.prefill_tokens["padded"] < 2 * max(
        eng.prefill_tokens["real"], 16 * eng.calls_by_kind["admit"])


# -- the kernels' own packed forms (the interpreter; the chip's compiler is
# asked in tests/test_chip_compile.py) ---------------------------------------


def _segments(lengths, T, rng, *shape):
    """A stream of T rows holding segments of ``lengths`` end to end."""
    x = rng.standard_normal((T, *shape)).astype(np.float32)
    off = np.full((R,), sum(lengths), np.int32)
    lens = np.zeros((R,), np.int32)
    off[:len(lengths)] = np.cumsum([0] + lengths[:-1])
    lens[:len(lengths)] = lengths
    return x, off, lens


def _to_rows(x, off, lens, S):
    rows = np.zeros((R, S, *x.shape[1:]), x.dtype)
    for r in range(R):
        rows[r, :lens[r]] = x[off[r]:off[r] + lens[r]]
    return rows


@pytest.mark.parametrize("kv_quant", ["", "int8"], ids=["bf16", "int8"])
def test_flash_packed_equals_flash_rows(kv_quant):
    """Tiles of 8 (T = 72): segments of three tiles, of a tile and a bit,
    of exactly three, of less than one; an idle row; a padded tail."""
    from k8s_llm_monitor_tpu.ops.pallas_attention import (
        flash_prefill_attention,
        flash_prefill_attention_packed,
    )

    H, KVH, D, S, T = 4, 2, 16, 24, 72
    lengths = [20, 9, 24]
    rng = np.random.default_rng(1)
    q, off, lens = _segments(lengths, T, rng, H, D)
    tables = _tables(lengths)
    cfg = ModelConfig(num_heads=H, num_kv_heads=KVH, head_dim=D,
                      hidden_size=H * D, dtype="float32")
    pages = llama.init_kv_pages(cfg, NBLK, BS, kv_quant=kv_quant)
    kv = {}
    for name in ("k", "v"):      # this call's K/V, scattered as prefill does
        rows = jnp.asarray(_to_rows(
            rng.standard_normal((T, KVH, D)).astype(np.float32),
            off, lens, S))
        pos = jnp.broadcast_to(jnp.arange(S), (R, S))
        valid = pos < jnp.asarray(lens)[:, None]
        page = getattr(pages, name)[0]
        if kv_quant:
            kv[name], kv[name + "_scale"] = llama._scatter_pages_quant(
                page, getattr(pages, name + "_scale")[0], rows,
                jnp.asarray(tables), pos, valid)
        else:
            kv[name] = llama._scatter_pages(page, rows, jnp.asarray(tables),
                                            pos, valid)
    scales = ({"k_scale": kv["k_scale"], "v_scale": kv["v_scale"]}
              if kv_quant else {})
    want = flash_prefill_attention(
        jnp.asarray(_to_rows(q, off, lens, S)), kv["k"], kv["v"],
        jnp.asarray(tables), jnp.zeros((R,), jnp.int32), jnp.asarray(lens),
        interpret=True, **scales)
    got = flash_prefill_attention_packed(
        jnp.asarray(q), kv["k"], kv["v"], jnp.asarray(tables),
        jnp.asarray(off), jnp.asarray(lens), interpret=True, **scales)
    for r, n in enumerate(lengths):
        np.testing.assert_allclose(
            np.asarray(got[off[r]:off[r] + n]), np.asarray(want[r, :n]),
            atol=2e-5, rtol=0)          # tests/test_flash_prefill.py's


def test_latent_packed_equals_latent_rows():
    """Blocks of 8: segments of several key blocks, one cut by its length
    inside a block, one of a single token; keys wider than values."""
    from k8s_llm_monitor_tpu.ops.pallas_attention import (
        latent_prefill_attention_packed,
        latent_prefill_attention_pallas,
    )

    H, Dk, Dv, S, T = 4, 24, 16, 32, 64
    lengths = [30, 13, 1, 16]
    rng = np.random.default_rng(2)
    q, off, lens = _segments(lengths, T, rng, H, Dk)
    k = rng.standard_normal((T, H, Dk)).astype(np.float32)
    v = rng.standard_normal((T, H, Dv)).astype(np.float32)
    want = latent_prefill_attention_pallas(
        *(jnp.asarray(_to_rows(x, off, lens, S)) for x in (q, k, v)),
        jnp.asarray(lens), scale=Dk ** -0.5, block=8, interpret=True)
    got = latent_prefill_attention_packed(
        jnp.asarray(q), jnp.asarray(k), jnp.asarray(v), jnp.asarray(off),
        jnp.asarray(lens), scale=Dk ** -0.5, row_len=S, block=8,
        interpret=True)
    for r, n in enumerate(lengths):
        np.testing.assert_allclose(
            np.asarray(got[off[r]:off[r] + n]), np.asarray(want[r, :n]),
            atol=2e-5, rtol=0)          # tests/test_latent_moe.py's


@pytest.mark.parametrize("name", ["qwen-w8a8", "latent-moe"])
def test_packed_call_through_the_kernels(name):
    """The whole packed call with the path a chip takes — the flash kernel
    (full attention) or the latent kernel, each in its packed form — against
    the row call through the same kernel's row form."""
    from k8s_llm_monitor_tpu.ops.attention import select_prefill_impl

    cfg, params, atol = _model(name, dtype="float32")
    impl = select_prefill_impl(cfg=cfg, mode="flash")
    lengths, T = CALLS["three"]
    prompts, tables = _prompts(cfg, lengths), _tables(lengths)
    want, _ = _row_call(cfg, params, prompts, tables, attn_impl=impl)
    got, _ = _packed_call(cfg, params, prompts, tables, T, attn_impl=impl)
    np.testing.assert_allclose(np.asarray(got[:3]), np.asarray(want[:3]),
                               atol=max(atol, 5e-5), rtol=0)


# -- the partition search ----------------------------------------------------


def _ladder_engine(buckets, rows):
    """An engine object for its host-side admission arithmetic alone."""
    eng = object.__new__(InferenceEngine)
    eng.ecfg = EngineConfig(prefill_buckets=buckets,
                            max_prefills_per_step=rows)
    return eng


# The two cells' ladders (benchmarks/configs): the engine's default buckets
# with 8 rows (Qwen2-7B; prompts of 129-1,024) and 1,024 / 2,048 / 4,096 with
# 4 (kanana; prompts of 1,024-4,096).  ``warm``: the token totals the
# harness's warm-up sends (benchmarks/harness/system.py:warm_up: n in 1, 2,
# 4, 8 prompts of exactly one reachable bucket).
LADDERS = {
    "qwen2": (EngineConfig().prefill_buckets, 8, (129, 1024),
              {b * n for b in (256, 512, 1024) for n in (1, 2, 4, 8)}),
    "kanana": ((1024, 2048, 4096), 4, (1024, 4096),
               {b * n for b in (1024, 2048, 4096) for n in (1, 2, 4)}),
}


def _all_cuts(n):
    for cuts in itertools.product((0, 1), repeat=n - 1):
        sizes, run = [], 1
        for c in cuts:
            if c:
                sizes.append(run)
                run = 0
            run += 1
        yield sizes + [run]


@pytest.mark.parametrize("ladder", list(LADDERS))
def test_partition_search(ladder):
    buckets, rows, (lo, hi), warm = LADDERS[ladder]
    eng = _ladder_engine(buckets, rows)
    rng = np.random.default_rng(11)

    def cost(lengths, sizes):
        at, total = 0, 0
        for n in sizes:
            total += eng._token_rung(sum(lengths[at:at + n]))
            at += n
        return total + eng._CALL_TOKENS * (len(sizes) - 1)

    for _ in range(300):
        n = int(rng.integers(1, rows + 1))
        lengths = [int(x) for x in rng.integers(lo, hi + 1, n)]
        sizes = eng._admit_groups(lengths)
        # Order kept, nothing dropped, at most R prompts a call.
        assert sum(sizes) == n and all(1 <= s <= rows for s in sizes)
        # The least sum of rungs, a call more priced at _CALL_TOKENS; among
        # equals, the fewest calls.
        best = min((cost(lengths, c), len(c)) for c in _all_cuts(n))
        assert (cost(lengths, sizes), len(sizes)) == best
        # Every rung is one the warm-up compiled.
        at = 0
        for s in sizes:
            assert eng._token_rung(sum(lengths[at:at + s])) in warm
            at += s
    # The warm-up's own batches go as one call at exactly their total.
    for total in sorted(warm):
        for n in (1, 2, 4, 8):
            if n <= rows and total // n in buckets and lo <= total // n <= hi:
                assert eng._admit_groups([total // n] * n) == [n]
                assert eng._token_rung(total) == total


def test_partition_examples():
    """ISSUE 27's two: four prompts of 9,000 tokens go as 8,192 + 4,096
    instead of 16,384; three of 1,300 as 1,024 + 512 instead of 2,048."""
    eng = _ladder_engine((1024, 2048, 4096), 4)
    lengths = [3000, 2500, 2500, 1000]
    sizes = eng._admit_groups(lengths)
    assert sizes == [3, 1]
    assert [eng._token_rung(8000), eng._token_rung(1000)] == [8192, 1024]
    eng = _ladder_engine(EngineConfig().prefill_buckets, 8)
    assert eng._admit_groups([600, 400, 300]) == [2, 1]
    assert eng._admit_groups([700, 500, 300, 100]) == [4]      # 1,600: 2,048


# -- what keeps the row form keeps its program -------------------------------

# sha256 of the lowered text of the programs that did not change, taken on
# the parent commit (087b3e6) by this very function under this installation
# (jax 0.9.0, CPU).  A digest that moves means a kept program changed — or
# the installation did: then take them again on a tree without the change.
KEPT = {
    "chunk_sample": "94d3f5be6176bce7",
    "chunk_greedy": "ab5046bcf080fbee",
    "decode_sampled": "1646aeaf11735244",
    "decode_greedy": "92494b8cc237e71c",
    "verify": "a7fb2b6781aa8403",
}


def _kept_programs():
    cfg = PRESETS["tiny-qwen"]
    cfg, params = _w8a8(cfg, llama.init_params(jax.random.PRNGKey(0), cfg))
    eng = InferenceEngine(cfg, params, EngineConfig(**ENGINE), eos_id=-1)
    B = ENGINE["max_slots"]
    zi, f = jnp.zeros((B,), jnp.int32), jnp.ones((B,))
    tok, ln = jnp.zeros((2, 16), jnp.int32), jnp.ones((2,), jnp.int32)
    tb, key = jnp.zeros((2, W), jnp.int32), jax.random.PRNGKey(0)
    eos = jnp.asarray(-1, jnp.int32)
    lowered = {
        "chunk_sample": eng._prefill_chunk_sample.lower(
            eng.params, tok, ln, ln, eng.pages, tb, f[:2], ln, f[:2], key),
        "chunk_greedy": eng._prefill_chunk_greedy.lower(
            eng.params, tok, ln, ln, eng.pages, tb),
        "decode_sampled": eng._decode_program(4, sampled=True).lower(
            eng.params, eng._tok_state, zi, zi, eng.pages,
            jnp.zeros((B, W), jnp.int32), f, zi, f, key, eos),
        "decode_greedy": eng._decode_program(4, sampled=False).lower(
            eng.params, eng._tok_state, zi, zi, eng.pages,
            jnp.zeros((B, W), jnp.int32), eos),
        "verify": jax.jit(
            lambda p, t, s, n, pg, tbl: llama.verify_step(
                p, cfg, t, s, n, pg, tbl)).lower(
                    eng.params, tok[:, :4], ln, ln, eng.pages, tb),
    }
    return {k: hashlib.sha256(v.as_text().encode()).hexdigest()[:16]
            for k, v in lowered.items()}


@pytest.fixture(scope="module")
def kept_programs():
    return _kept_programs()


@pytest.mark.parametrize("program", list(KEPT))
def test_kept_programs_lower_to_the_same_text(kept_programs, program):
    assert kept_programs[program] == KEPT[program]


if __name__ == "__main__":       # prints the digests of the tree it runs in
    print(_kept_programs())
