"""Ask the chip's compiler, with no chip attached.

Interpret-mode tests cannot see what Mosaic refuses (a slice not aligned to
the tiling, a block shape the lowering does not take); PR 21 found both the
fused decode kernel and flash prefill refused that way after passing every
CPU test.  These tests compile each default-path Pallas kernel at Qwen2-7B
widths and the engine's real pool shapes for a *described* TPU v5e — about
two seconds each, no chip time — and the ``shard_map``-wrapped kernels of the
tensor-parallel path on a mesh of the four described devices.

A compile that passes is not a chip run: it says the kernel is accepted, not
that its results or its speed are right (``chip_smoke.py`` checks results on
the chip).

The topology is described inside a fixture, never at import or collection:
only one process may load the TPU library, and every xdist worker imports
every test file.  All of these tests stay in this one file for the same
reason, and compile in the test's own process.
"""

import functools
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, SingleDeviceSharding
from jax.sharding import PartitionSpec as P

from k8s_llm_monitor_tpu.models.config import PRESETS
from k8s_llm_monitor_tpu.ops import attention as ops
from k8s_llm_monitor_tpu.ops import pallas_attention as pa
from k8s_llm_monitor_tpu.ops.sampling import sample_tokens

CFG = PRESETS["qwen2-7b"]
H, KVH, D = CFG.num_heads, CFG.num_kv_heads, CFG.head_dim_
F = KVH * D
# TPULLMConfig / EngineConfig defaults: 512 blocks x 16 tokens, 32 decode
# lanes, 64 blocks per sequence, 8 prefill lanes.
NBLK, BS, NB, LANES, PLANES = 512, 16, 64, 32, 8
BF16, F32, I32 = jnp.bfloat16, jnp.float32, jnp.int32
FP8 = jnp.float8_e4m3fn


@pytest.fixture(scope="module")
def topo():
    import os

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def no_persistent_cache():
    """A compile for a described device is written to the persistent cache
    but cannot be read back without a chip (the next one warns and compiles
    again): keep the cache out of these tests."""
    from jax.experimental.compilation_cache import compilation_cache

    before = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", before)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo, no_persistent_cache):
    sharding = SingleDeviceSharding(topo.devices[0])
    return lambda shape, dtype: jax.ShapeDtypeStruct(shape, dtype,
                                                     sharding=sharding)


@pytest.fixture(scope="module")
def four_chips(topo, no_persistent_cache):
    mesh = Mesh(np.asarray(topo.devices).reshape(1, 1, 4),
                ("data", "seq", "model"))

    def arg(shape, dtype, spec=P()):
        return jax.ShapeDtypeStruct(shape, dtype,
                                    sharding=NamedSharding(mesh, spec))

    return mesh, arg


def _compile(fn, *args, kernel=True):
    """Lower + compile for the described device(s); raises what the chip's
    compiler would raise.  Returns the compiled text."""
    text = jax.jit(fn).lower(*args).compile().as_text()
    if kernel:
        assert "tpu_custom_call" in text, "no Pallas kernel in the program"
    return text


def _decode_args(S, pool_dtype, bs=BS, lanes=LANES, nblk=NBLK):
    return (S((lanes, 1, H, D), BF16), S((lanes, 1, KVH, D), BF16),
            S((lanes, 1, KVH, D), BF16), S((lanes, 1, D), F32),
            S((lanes, 1, D), F32), S((nblk, bs, F), pool_dtype),
            S((nblk, bs, F), pool_dtype))


def test_fused_decode_compiles(one_chip):
    S = one_chip
    _compile(pa.paged_decode_attention_fused, *_decode_args(S, BF16),
             S((LANES, NB), I32), S((LANES,), I32))


def test_fused_decode_compiles_at_the_served_cell(one_chip):
    """The call ``qwen2-7b.loops-saturated`` makes (benchmarks/configs/
    qwen2-7b-w8a8.json: 64 lanes, 96 blocks a sequence, a bf16 pool of
    6,144 blocks): the kernel's VMEM (two slabs of ``_FUSED_WINDOW`` pages
    an array, an append tile a lane of the program) at the size that ships,
    where the defaults above ask for less."""
    S = one_chip
    lanes, nb, nblk = 64, 96, 6144
    _compile(pa.paged_decode_attention_fused,
             *_decode_args(S, BF16, lanes=lanes, nblk=nblk),
             S((lanes, nb), I32), S((lanes,), I32))


@pytest.mark.parametrize("bs,dtype", [(32, BF16), (16, F32)],
                         ids=["bf16-bs32", "f32-bs16"])
def test_fused_decode_partial_tile_append_compiles(one_chip, bs, dtype):
    """Pages taller than one sublane tile append through a dynamic-offset
    aligned tile (``_append_rows``'s ``pl.ds`` branch), not the whole page."""
    S = one_chip
    assert pa._append_tile_rows(bs, dtype) < bs
    _compile(pa.paged_decode_attention_fused, *_decode_args(S, dtype, bs),
             S((LANES, NB), I32), S((LANES,), I32))


@pytest.mark.parametrize("pool", [jnp.int8, FP8], ids=["int8", "fp8"])
def test_fused_quant_decode_compiles(one_chip, pool):
    S = one_chip
    _compile(pa.paged_decode_attention_fused_quant, *_decode_args(S, pool),
             S((NBLK, BS, KVH), F32), S((NBLK, BS, KVH), F32),
             S((LANES, NB), I32), S((LANES,), I32))


def test_split_decode_compiles(one_chip):
    S = one_chip
    _compile(pa.paged_decode_attention_pallas, S((LANES, 1, H, D), BF16),
             S((NBLK, BS, F), BF16), S((NBLK, BS, F), BF16),
             S((LANES, NB), I32), S((LANES,), I32))


def test_verify_kernel_compiles(one_chip):
    S = one_chip
    _compile(pa.paged_verify_attention_pallas, S((LANES, 5, H, D), BF16),
             S((NBLK, BS, F), BF16), S((NBLK, BS, F), BF16),
             S((LANES, NB), I32), S((LANES,), I32), S((LANES,), I32))


# Smallest, a middle and the largest default bucket, and spec verify's
# S = spec_k + 1 = 5 (not a multiple of 8: the wrapper pads it).
@pytest.mark.parametrize("lanes,seq", [(PLANES, 32), (PLANES, 256),
                                       (PLANES, 2048), (LANES, 5)])
@pytest.mark.parametrize("pool", [None, jnp.int8, FP8],
                         ids=["bf16", "int8", "fp8"])
def test_flash_prefill_compiles(one_chip, lanes, seq, pool):
    S = one_chip
    args = [S((lanes, seq, H, D), BF16), S((NBLK, BS, F), pool or BF16),
            S((NBLK, BS, F), pool or BF16), S((lanes, NB), I32),
            S((lanes,), I32), S((lanes,), I32)]
    if pool is None:
        _compile(pa.flash_prefill_attention, *args)
    else:
        _compile(lambda q, k, v, t, s, n, ks, vs: pa.flash_prefill_attention(
            q, k, v, t, s, n, k_scale=ks, v_scale=vs),
            *args, S((NBLK, BS, KVH), F32), S((NBLK, BS, KVH), F32))


def test_select_impls_resolve_to_the_kernels_on_tpu():
    """What ``auto`` picks for this geometry on a TPU, decided before any
    tracing: the fused decode kernel and flash prefill, compiled (never the
    interpreter), one chip or a TP-4 mesh of the kv heads."""
    dec = ops.select_decode_impl("tpu", cfg=CFG, mode="auto")
    assert dec is pa.paged_decode_attention_fused
    decq = ops.select_decode_impl("tpu", cfg=CFG, mode="auto",
                                  kv_quant="int8")
    assert decq is pa.paged_decode_attention_fused_quant
    pre = ops.select_prefill_impl("tpu", cfg=CFG, mode="auto")
    assert pre is pa.flash_prefill_attention
    assert not isinstance(dec, functools.partial)


def test_tp_decode_kernel_compiles_on_four_chips(four_chips):
    mesh, A = four_chips
    attn = ops.make_tp_paged_attention(mesh, CFG)
    heads, lanes = P(None, None, "model", None), P(None, None, "model")
    text = _compile(attn, A((LANES, 1, H, D), BF16, heads),
                    A((NBLK, BS, F), BF16, lanes),
                    A((NBLK, BS, F), BF16, lanes),
                    A((LANES, NB), I32), A((LANES,), I32))
    # Head-sharded paged attention needs no collective.
    assert "all-reduce" not in text and "all-gather" not in text


@pytest.mark.parametrize("kv_quant", ["", "int8"], ids=["bf16", "int8"])
def test_tp_flash_prefill_compiles_on_four_chips(four_chips, kv_quant):
    mesh, A = four_chips
    attn = ops.make_tp_flash_prefill(mesh, CFG, kv_quant=kv_quant)
    heads, lanes = P(None, None, "model", None), P(None, None, "model")
    pool = jnp.int8 if kv_quant else BF16
    args = [A((PLANES, 256, H, D), BF16, heads),
            A((NBLK, BS, F), pool, lanes), A((NBLK, BS, F), pool, lanes),
            A((PLANES, NB), I32), A((PLANES,), I32), A((PLANES,), I32)]
    if kv_quant:
        _compile(lambda q, k, v, t, s, n, ks, vs: attn(
            q, k, v, t, s, n, k_scale=ks, v_scale=vs), *args,
            A((NBLK, BS, KVH), F32, lanes), A((NBLK, BS, KVH), F32, lanes))
    else:
        _compile(attn, *args)


def _computations(text):
    """Compiled HLO text -> {computation name: its instruction lines}."""
    comps, name = {}, None
    for line in text.splitlines():
        head = re.match(r"(?:ENTRY\s+)?%?([\w.\-]+)\s*\(.*\)\s*->.*\{\s*$", line)
        if head:
            name = head.group(1)
            comps[name] = []
        elif line.startswith("}"):
            name = None
        elif name is not None:
            comps[name].append(line)
    return comps


def _reachable(comps, roots):
    """Every computation the instructions of ``roots`` call, transitively
    (fusions, reducers, loop bodies, branches)."""
    seen, todo = set(), list(roots)
    while todo:
        name = todo.pop()
        if name in seen or name not in comps:
            continue
        seen.add(name)
        for line in comps[name]:
            todo += re.findall(r"%([\w.\-]+)", line.split(" metadata=")[0])
    return seen


def test_sampler_sorts_only_inside_the_filter_branch(one_chip):
    """The cell's sampler shape, 64 lanes x Qwen2's 152,064 vocabulary: the
    chip's compiler keeps ``_filter_logits``' ``lax.cond`` a real
    ``conditional`` (not a select over both branches), the branch taken with
    every filter off holds no sort, and no sort or scatter of the vocabulary
    width is left outside the other one.  No Pallas kernel here."""
    S = one_chip
    B, V = 64, CFG.vocab_size
    text = _compile(
        lambda key, logits, t, k, p: sample_tokens(
            key, logits, temperature=t, top_k=k, top_p=p),
        S((2,), jnp.uint32), S((B, V), F32), S((B,), F32), S((B,), I32),
        S((B,), F32), kernel=False)
    comps = _computations(text)
    conds = [(name, line) for name, lines in comps.items() for line in lines
             if re.search(r"\sconditional\(", line)]
    assert len(conds) == 1, [line[:120] for _, line in conds]
    # The predicate reaches the conditional as an index: 0 is the false one.
    off, on = re.search(r"branch_computations=\{%([\w.\-]+), %([\w.\-]+)\}",
                        conds[0][1]).groups()

    def wide(names):
        return [line.strip()[:120] for n in names for line in comps[n]
                if re.search(r"\s(sort|scatter)\(", line)
                and re.search(rf"\b({V}|{B * V})\b", line)]

    assert not [line for n in _reachable(comps, [off]) for line in comps[n]
                if re.search(r"\ssort\(", line)]
    inside = _reachable(comps, [on])
    assert wide(inside), "the rank filter's sort is gone from its branch"
    assert wide(set(comps) - inside) == []


# -- the latent pool's kernel and the expert layer's grouped product, at the
# -- shapes of kanana2-30b-a3b.evidence-loops ---------------------------------

KCFG = PRESETS["kanana-2-30b-a3b-12l"]
K_BLOCKS, K_TABLE = 17_408, 272          # the cell's pool and table width


@pytest.mark.parametrize("lanes", [64, 1], ids=["64-lanes", "score-logits"])
def test_latent_decode_kernel_compiles(one_chip, lanes):
    """64 decode lanes (the fused scan's call) and one (score_logits'), 32
    heads over rows of 640 lanes = [512 latent | 64 rotated key | 64 zeros],
    the whole 17,408-block pool, a table of 272 blocks."""
    S = one_chip
    Fp = KCFG.latent_page_width
    assert Fp == 640
    _compile(functools.partial(pa.latent_decode_attention_pallas,
                               v_width=KCFG.kv_lora_rank),
             S((lanes, 1, KCFG.num_heads, Fp), BF16),
             S((K_BLOCKS, BS, Fp), BF16), S((lanes, K_TABLE), I32),
             S((lanes,), I32))


@pytest.mark.parametrize("rows,bucket", [(4, 4096), (1, 1024)],
                         ids=["4x4096", "1x1024"])
def test_latent_prefill_kernel_compiles(one_chip, rows, bucket):
    """The expanded form of a fresh prefill at the cell's largest and
    smallest admission shapes: 32 heads, keys of 128 + 64 lanes (padded to
    256 inside), values of 128."""
    S = one_chip
    nH, dk, dv = KCFG.num_heads, KCFG.qk_head_dim, KCFG.v_head_dim
    assert (dk, dv) == (192, 128)
    _compile(functools.partial(pa.latent_prefill_attention_pallas,
                               scale=dk ** -0.5),
             S((rows, bucket, nH, dk), BF16), S((rows, bucket, nH, dk), BF16),
             S((rows, bucket, nH, dv), BF16), S((rows,), I32))


@pytest.mark.parametrize("rows", [64 * 6, 4 * 4096 * 6],
                         ids=["decode-384", "prefill-98304"])
def test_grouped_expert_product_is_a_kernel_over_the_chosen(one_chip, rows):
    """``jax.lax.ragged_dot`` of int8 rows against the 128 int8 expert
    kernels: the chip's compiler lowers it to its own grouped-product kernel
    (no [E, rows, I] tensor, no product for an expert without rows) — what
    the expert layer counts on (models/llama.py:_expert_rows).  Its cost
    analysis says so: the operations are those of the rows alone."""
    import jax.numpy as jnp

    S = one_chip
    E, H_, I_ = KCFG.num_experts, KCFG.hidden_size, KCFG.expert_width
    fn = lambda x, w, g: jax.lax.ragged_dot(
        x, w, g, preferred_element_type=jnp.int32)
    compiled = jax.jit(fn).lower(S((rows, H_), jnp.int8),
                                 S((E, H_, I_), jnp.int8),
                                 S((E,), I32)).compile()
    assert "tpu_custom_call" in compiled.as_text()
    assert compiled.cost_analysis()["flops"] == 2.0 * rows * H_ * I_
    assert compiled.memory_analysis().temp_size_in_bytes < 1 << 20


# -- PR 27: a fresh admission call's packed stream ---------------------------


@pytest.mark.parametrize("tokens", [256, 2048, 8192])
@pytest.mark.parametrize("pool", [None, jnp.int8], ids=["bf16", "int8"])
def test_flash_prefill_packed_compiles(one_chip, tokens, pool):
    """The flash kernel over a packed stream's tiles (8 segments, the cell's
    table of 96 blocks): tiles and their sequences come from prefetched
    arrays, the q block from a [KVH, NT*TQ, qpk*D] stream."""
    S = one_chip
    args = [S((tokens, H, D), BF16), S((NBLK, BS, F), pool or BF16),
            S((NBLK, BS, F), pool or BF16), S((PLANES, 96), I32),
            S((PLANES,), I32), S((PLANES,), I32)]
    if pool is None:
        _compile(pa.flash_prefill_attention_packed, *args)
    else:
        _compile(lambda q, k, v, t, o, n, ks, vs:
                 pa.flash_prefill_attention_packed(
                     q, k, v, t, o, n, k_scale=ks, v_scale=vs),
                 *args, S((NBLK, BS, KVH), F32), S((NBLK, BS, KVH), F32))


@pytest.mark.parametrize("tokens", [1024, 16384])
def test_latent_prefill_packed_compiles(one_chip, tokens):
    """The latent kernel over a packed stream's blocks of 512 (4 segments of
    at most 4,096 tokens)."""
    S = one_chip
    nH, dk, dv = KCFG.num_heads, KCFG.qk_head_dim, KCFG.v_head_dim
    _compile(functools.partial(pa.latent_prefill_attention_packed,
                               scale=dk ** -0.5,
                               row_len=min(tokens, 4096)),
             S((tokens, nH, dk), BF16), S((tokens, nH, dk), BF16),
             S((tokens, nH, dv), BF16), S((4,), I32), S((4,), I32))


# The largest temporaries of the row programs this PR's packed ones replace
# (8 x 1,024 and 4 x 4,096), compiled the same way on the parent commit.
ROW_TEMPS = {"qwen2-7b-w8a8": 708_665_856,
             "kanana2-30b-a3b-w8a8": 2_443_907_072}


@pytest.mark.parametrize("config,tokens,layers", [
    ("qwen2-7b-w8a8", 1024, 2), ("qwen2-7b-w8a8", 8192, None),
    ("kanana2-30b-a3b-w8a8", 16384, None)],
    ids=["qwen2-t1024-2-layers", "qwen2-t8192", "kanana-t16384"])
def test_packed_prefill_program_compiles(one_chip, monkeypatch, config,
                                         tokens, layers):
    """The sampled fresh-prefill program in its packed form
    (serving/engine.py:_prefill_sample_fn), whole, at the cells' engines
    (benchmarks/configs) and their smallest and largest rungs: the chip's
    compiler takes it, its kernels are in it, and its temporaries leave the
    pool rule (PERF.md section 4: pool <= bytes_limit - weights -
    temporaries - 1 GiB) standing — within a twentieth of the row program's
    it replaces.  The expert products take the form the chip's program
    takes (the predicate asks the backend, which is the CPU here: told
    "tpu"), so a routed program holds the tiles kernel and no ``ragged-dot``."""
    import dataclasses
    import json
    import pathlib

    from k8s_llm_monitor_tpu.models import llama
    from k8s_llm_monitor_tpu.serving.engine import EngineConfig
    from k8s_llm_monitor_tpu.utils.quantize import init_params_quantized

    from k8s_llm_monitor_tpu.ops import grouped

    form = grouped.product_form
    monkeypatch.setattr(
        grouped, "product_form",
        lambda m, g, k, n, dtype, platform=None: form(m, g, k, n, dtype, "tpu"))
    S = one_chip
    cell = json.loads((pathlib.Path(__file__).parents[1] / "benchmarks"
                       / "configs" / f"{config}.json").read_text())
    ec = EngineConfig(**cell["assumed"]["engine"])
    R, W, top = (ec.max_prefills_per_step, ec.max_blocks_per_seq,
                 ec.prefill_buckets[-1])
    cfg = dataclasses.replace(PRESETS[cell["preset"]], act_quant=True)
    if layers:
        cfg = dataclasses.replace(cfg, num_layers=layers)
    on_chip = functools.partial(jax.tree.map, lambda x: S(x.shape, x.dtype))
    params = on_chip(jax.eval_shape(
        lambda key: init_params_quantized(key, cfg), jax.random.PRNGKey(0)))
    pages = on_chip(jax.eval_shape(
        lambda: llama.init_kv_pages(cfg, ec.num_blocks, ec.block_size)))
    impl = ops.select_prefill_impl("tpu", cfg=cfg)

    def program(params, toks, seg, pages, tables, temp, topk, topp, key):
        stats = [] if cfg.expert_layers else None
        logits, pages = llama.prefill_packed(
            params, cfg, toks, *seg, pages, tables,
            row_len=min(tokens, top), attn_impl=impl, moe_stats=stats)
        return sample_tokens(key, logits, temperature=temp, top_k=topk,
                             top_p=topp), pages, stats

    compiled = jax.jit(program, donate_argnums=(3,)).lower(
        params, S((tokens,), I32), (S((R,), I32), S((R,), I32)), pages,
        S((R, W), I32), S((R,), F32), S((R,), I32), S((R,), F32),
        S((2,), jnp.uint32)).compile()
    text = compiled.as_text()
    assert text.count("tpu_custom_call") >= cfg.num_layers
    assert ("grouped_tiles_product" in text) == bool(cfg.expert_layers)
    assert "ragged-dot" not in text
    temps = compiled.memory_analysis().temp_size_in_bytes
    if not layers:
        rule = cell["assumed"]["pool_reckoning"]
        assert temps <= (rule["bytes_limit"] - rule["weights_bytes"]
                         - rule["pool_bytes"] - (1 << 30)), temps
        assert temps <= 1.05 * ROW_TEMPS[config], temps


# -- PR 31: the nemotron_h cell's kernels ------------------------------------

NCFG = PRESETS["nemotron3-super-120b-a12b-22l"]


@pytest.mark.parametrize("rows", [0, 32], ids=["a-lane-a-step", "half-a-lane"])
def test_ssm_decode_update_compiles_at_the_served_cell(one_chip, rows):
    """The Mamba-2 state update on the served pool, in place: 64 lanes of
    128 heads x 64 x 128 float32 laid [64, 128, 128] (ops/ssm.py), a 4 MB
    block a lane a grid step — past the default scoped VMEM, so the kernel
    sets its own limit — or half of one; no temporary the size of the pool."""
    from k8s_llm_monitor_tpu.ops import ssm

    S = one_chip
    Hm, P, N, G = (NCFG.mamba_num_heads, NCFG.mamba_head_dim,
                   NCFG.ssm_state_size, NCFG.mamba_n_groups)
    pack = ssm.state_pack(Hm, G, P)
    compiled = jax.jit(
        functools.partial(ssm.ssm_decode_update, block_rows=rows),
        donate_argnums=(0,)).lower(
            S((64, Hm // pack, N, pack * P), F32), S((64,), I32),
            S((64, Hm), F32), S((64, Hm, P), F32), S((64, G, N), F32),
            S((64, G, N), F32)).compile()
    assert "ssm_decode_update" in compiled.as_text()
    assert compiled.memory_analysis().temp_size_in_bytes < 1 << 20


@pytest.mark.parametrize("kernel", ["fused_decode", "flash_prefill_packed"])
def test_attention_kernels_compile_at_sixteen_queries_a_kv_head(one_chip,
                                                                kernel):
    """32 q / 2 kv heads x 128 (no cell had run 16 query heads a kv head): the
    fused decode kernel on the served pool of 14,337 blocks and 224-block
    tables, and the flash kernel over a packed stream of 8,192 tokens."""
    S = one_chip
    nH, nKV, Dh = NCFG.num_heads, NCFG.num_kv_heads, NCFG.head_dim_
    assert ops._pallas_geometry_ok(NCFG, 1)
    pool = S((14_337, 16, nKV * Dh), BF16)
    if kernel == "fused_decode":
        _compile(pa.paged_decode_attention_fused,
                 S((64, 1, nH, Dh), BF16), S((64, 1, nKV, Dh), BF16),
                 S((64, 1, nKV, Dh), BF16), S((64, 1, Dh), F32),
                 S((64, 1, Dh), F32), pool, pool, S((64, 224), I32),
                 S((64,), I32))
    else:
        _compile(pa.flash_prefill_attention_packed, S((8192, nH, Dh), BF16),
                 pool, pool, S((2, 224), I32), S((2,), I32), S((2,), I32))


# -- PR 32: the decode step's expert products ---------------------------------


@pytest.mark.parametrize("product", ["in", "out"])
@pytest.mark.parametrize("cfg", [KCFG, NCFG], ids=["kanana", "nemotron"])
def test_grouped_stream_kernel_compiles_at_the_cells_decode_shapes(
        one_chip, cfg, product):
    """``ops/grouped.py``'s stream kernel on a decode step's sorted rows (64
    lanes x experts per token) against the 128 int8 kernels a chip holds,
    an expert's whole kernel a grid step's block (2.75 MB nemotron, 1.57 MB
    kanana, two in flight): the predicate takes it there, the chip's
    compiler takes it, and it needs no temporary beyond its schedule."""
    from k8s_llm_monitor_tpu.ops import grouped

    S = one_chip
    M, G = 64 * cfg.num_experts_per_tok, cfg.experts_held_
    narrow, wide = cfg.moe_latent_size or cfg.hidden_size, cfg.expert_width
    K, N = (narrow, wide) if product == "in" else (wide, narrow)
    assert grouped.product_form(M, G, K, N, jnp.int8, platform="tpu") == "stream"
    compiled = jax.jit(grouped.grouped_rows_product).lower(
        S((M, K), jnp.int8), S((G, K, N), jnp.int8), S((G,), I32)).compile()
    assert "grouped_rows_product" in compiled.as_text()
    assert "tpu_custom_call" in compiled.as_text()
    assert compiled.memory_analysis().temp_size_in_bytes < 1 << 20


# -- PR 33: the dots3_note cell's kernels --------------------------------------

DCFG = PRESETS["dots3-note-prev-5l"]
D_LANES, D_TABLE = 64, 528               # the cell's lanes and table width
D_BLOCKS = D_LANES * D_TABLE + 1


@pytest.mark.parametrize("kernel", ["selected", "window", "index_scores"])
def test_sparse_decode_kernels_compile_at_the_served_cell(one_chip, kernel):
    """``dots3-note-prev.casefile-loops``'s decode call: the latent decode
    kernel at 128 heads over 640-lane rows with a keep mask a lane (selected
    attention, the mask form), at 64 heads over the window store's 1,152-lane
    rows as one burst of a lane's 33-block ring, and the index-score kernel
    over 128-lane index-key pages — each under the name the benchmark reads."""
    S = one_chip
    full, sliding = DCFG.latent_geometry(1), DCFG.latent_geometry(2)
    assert (full.page_width, sliding.page_width, full.index_dim) == (640, 1152, 128)
    if kernel == "selected":
        text = _compile(
            lambda q, p, t, n, keep: pa.latent_decode_attention_pallas(
                q, p, t, n, v_width=full.kv_lora_rank, keep=keep,
                name="sparse_latent_decode_attention"),
            S((D_LANES, 1, full.num_heads, 640), BF16),
            S((D_BLOCKS, BS, 640), BF16), S((D_LANES, D_TABLE), I32),
            S((D_LANES,), I32), S((D_LANES, D_TABLE * BS), jnp.bool_))
        assert "sparse_latent_decode_attention" in text
    elif kernel == "window":
        ring = DCFG.window_rows(BS) // BS
        assert ring == 33
        text = _compile(
            functools.partial(pa.latent_decode_attention_pallas,
                              v_width=sliding.kv_lora_rank, burst=ring,
                              name="window_latent_decode_attention"),
            S((D_LANES, 1, sliding.num_heads, 1152), BF16),
            S((1 + D_LANES * ring, BS, 1152), BF16), S((D_LANES, ring), I32),
            S((D_LANES,), I32))
        assert "window_latent_decode_attention" in text
    else:
        text = _compile(
            pa.index_scores_decode_pallas,
            S((D_LANES, 1, full.index_heads, 128), BF16),
            S((D_LANES, 1, full.index_heads), F32),
            S((D_BLOCKS, BS, 128), BF16), S((D_LANES, D_TABLE), I32),
            S((D_LANES,), I32))
        assert "sparse_latent_decode_index_scores" in text


@pytest.mark.parametrize("geometry", ["selected", "window"])
def test_sparse_prefill_kernels_compile_at_the_served_cell(one_chip, geometry):
    """The cell's largest admission call (18,432 tokens, 8 rows of up to
    9,216): the packed prefill kernel under a band of 513 (64 heads, keys of
    192 + 64), and under the selection (128 heads, keys of 128 + 64) with
    the index-score kernel and the counting selection in front of it."""
    S = one_chip
    T, R, top = 18_432, 8, 9216
    g = DCFG.latent_geometry(1 if geometry == "selected" else 2)
    args = [S((T, g.num_heads, g.qk_head_dim), BF16),
            S((T, g.num_heads, g.qk_head_dim), BF16),
            S((T, g.num_heads, g.v_head_dim), BF16), S((R,), I32), S((R,), I32)]
    kw = dict(scale=g.qk_head_dim ** -0.5, row_len=top)
    if geometry == "window":
        text = _compile(functools.partial(
            pa.latent_prefill_attention_packed, window=g.window, **kw), *args)
        assert "window_latent_prefill_attention" in text
        return
    text = _compile(
        lambda q, k, v, o, n, qi, ki, wi: pa.latent_prefill_attention_packed(
            q, k, v, o, n, topk=g.index_topk, index=(qi, ki, wi), **kw),
        *args, S((T, g.index_heads, g.index_dim), BF16),
        S((T, g.index_dim), BF16), S((T, g.index_heads), F32))
    assert "sparse_latent_prefill_index_scores" in text
    assert "sparse_latent_prefill_attention" in text
    assert " sort(" not in text      # the 2,048th score is counted, not sorted


# -- PR 34: admission's expert products ----------------------------------------


@pytest.mark.parametrize("product", ["in", "out"])
@pytest.mark.parametrize("cfg,tokens", [(KCFG, 16_384), (NCFG, 12_288),
                                        (DCFG, 18_432)],
                         ids=["kanana", "nemotron", "dots3"])
def test_grouped_tiles_kernel_compiles_at_the_cells_admission_shapes(
        one_chip, cfg, tokens, product):
    """``ops/grouped.py``'s tiles kernel on the sorted rows of each routed
    cell's largest admission call (kanana every assignment: 98,304 rows; the
    share layers a window of 16,384) against the int8 kernels a chip holds,
    dequantised to bf16 in its epilogue: the predicate takes it there, the
    chip's compiler takes its row tile and the column tile ``column_tile``
    picks (dots3's [5120, 1536] kernel in two column tiles), and no int32 or
    gathered-scale temporary is left beside it."""
    from k8s_llm_monitor_tpu.models import llama
    from k8s_llm_monitor_tpu.ops import grouped

    S = one_chip
    M, G = tokens * cfg.num_experts_per_tok, cfg.experts_held_
    if cfg.expert_share:
        M = min(M, llama._EXPERT_WINDOW_ROWS)
    narrow, wide = cfg.moe_latent_size or cfg.hidden_size, cfg.expert_width
    K, N = (narrow, wide) if product == "in" else (wide, narrow)
    assert grouped.product_form(M, G, K, N, jnp.int8, platform="tpu") == "tiles"
    compiled = jax.jit(functools.partial(
        grouped.grouped_tiles_product, dtype=jnp.dtype(BF16))).lower(
        S((M, K), jnp.int8), S((G, K, N), jnp.int8), S((G,), I32),
        S((M, 1), F32), S((G, N), F32)).compile()
    assert "grouped_tiles_product" in compiled.as_text()
    assert "tpu_custom_call" in compiled.as_text()
    assert compiled.memory_analysis().temp_size_in_bytes < 1 << 20
