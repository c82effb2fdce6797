"""GSPMD tensor-parallel execution on the virtual 8-device CPU mesh.

TP-sharded forward/prefill/decode must match single-device results bit-for-
nearly-bit (same program, XLA inserts collectives from the annotations).
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from k8s_llm_monitor_tpu.models import llama
from k8s_llm_monitor_tpu.models.config import ModelConfig
from k8s_llm_monitor_tpu.parallel.mesh import MeshConfig, create_mesh
from k8s_llm_monitor_tpu.parallel.sharding import (
    param_partition_specs,
    shard_params,
)
from k8s_llm_monitor_tpu.serving.engine import (
    EngineConfig,
    InferenceEngine,
    SamplingParams,
)

CFG = ModelConfig(name="t", vocab_size=512, hidden_size=64, intermediate_size=128,
                  num_layers=2, num_heads=8, num_kv_heads=8, dtype="float32",
                  rope_theta=10_000.0)


def test_partition_specs_cover_param_tree():
    params = llama.init_params(jax.random.PRNGKey(0), CFG)
    specs = param_partition_specs(params)
    flat_p = jax.tree_util.tree_leaves(params)
    flat_s = jax.tree_util.tree_leaves(specs, is_leaf=lambda x: isinstance(x, P))
    assert len(flat_p) == len(flat_s)
    # column-parallel q kernel shards axis 1; row-parallel o shards axis 0
    assert specs["layers"][0]["q"]["kernel"] == P(None, "model")
    assert specs["layers"][0]["o"]["kernel"] == P("model", None)
    assert specs["embed"]["weight"] == P("model", None)
    assert specs["final_norm"] == P(None)


def test_tp_forward_matches_single_device(cpu_mesh_devices):
    mesh = create_mesh(MeshConfig(model=8))
    params = llama.init_params(jax.random.PRNGKey(0), CFG)
    tokens = jnp.asarray(
        np.random.default_rng(0).integers(0, 512, size=(2, 12), dtype=np.int32)
    )

    ref = llama.forward_full(params, CFG, tokens)

    sharded = shard_params(params, mesh)
    fwd = jax.jit(lambda p, t: llama.forward_full(p, CFG, t))
    out = fwd(sharded, jax.device_put(tokens, NamedSharding(mesh, P(None, None))))
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=2e-5, atol=2e-5)


def test_tp_engine_generation_matches_unsharded(cpu_mesh_devices):
    mesh = create_mesh(MeshConfig(model=8))
    params = llama.init_params(jax.random.PRNGKey(1), CFG)
    ecfg = EngineConfig(max_slots=2, num_blocks=32, block_size=8,
                        max_blocks_per_seq=8, prefill_buckets=(16,))
    prompts = [[5, 6, 7, 8, 9], [11, 12, 13]]
    sp = SamplingParams(max_tokens=6)

    plain = InferenceEngine(CFG, params, ecfg, eos_id=-1).generate(prompts, sp)
    tp = InferenceEngine(CFG, params, ecfg, eos_id=-1, mesh=mesh).generate(prompts, sp)
    for a, b in zip(plain, tp):
        assert a.token_ids == b.token_ids


def test_seq_sharded_prefill_engine_matches_unsharded(cpu_mesh_devices):
    """Sequence-parallel serve prefill (SURVEY §7 step 5): a mesh with a
    nontrivial ``seq`` axis shards chunked-prefill token batches over it
    (engine._tokens_to_device), splitting one long prompt's ingestion
    FLOPs across chips.  Long prompts (> top bucket) force the chunk-round
    path; output must be token-identical to the unsharded engine."""
    mesh = create_mesh(MeshConfig(data=1, seq=2, model=4))
    params = llama.init_params(jax.random.PRNGKey(2), CFG)
    ecfg = EngineConfig(max_slots=2, num_blocks=32, block_size=8,
                        max_blocks_per_seq=8, prefill_buckets=(16,))
    rng = np.random.default_rng(3)
    prompts = [list(rng.integers(2, 500, size=40)),   # 40 > 16: chunked
               list(rng.integers(2, 500, size=12))]   # dense admission
    sp = SamplingParams(max_tokens=5)

    plain = InferenceEngine(CFG, params, ecfg, eos_id=-1).generate(prompts, sp)
    sq = InferenceEngine(CFG, params, ecfg, eos_id=-1, mesh=mesh)
    assert sq._tok_sharding is not None
    seq = sq.generate(prompts, sp)
    for a, b in zip(plain, seq):
        assert a.token_ids == b.token_ids


def test_seq_mesh_rejects_indivisible_buckets(cpu_mesh_devices):
    mesh = create_mesh(MeshConfig(data=1, seq=2, model=4))
    params = llama.init_params(jax.random.PRNGKey(2), CFG)
    ecfg = EngineConfig(max_slots=2, num_blocks=32, block_size=8,
                        max_blocks_per_seq=8, prefill_buckets=(15,))
    with pytest.raises(ValueError, match="seq"):
        InferenceEngine(CFG, params, ecfg, eos_id=-1, mesh=mesh)


def test_all_presets_are_coherent_and_tp8_shardable():
    """Every serving preset must have integral GQA/head geometry and a
    parameter pytree whose model-sharded axes divide a TP-8 mesh (or fall
    back to replication) — checked via eval_shape, no weights built."""
    from k8s_llm_monitor_tpu.models.config import PRESETS

    for name, cfg in PRESETS.items():
        assert cfg.hidden_size % cfg.num_heads == 0 or cfg.head_dim, name
        assert cfg.num_heads % cfg.num_kv_heads == 0, name
        # A latent mixer's queries are wide by design (nope + rope parts
        # per head); its cache is what is narrow.
        assert (cfg.latent or
                cfg.head_dim_ * cfg.num_heads <= 2 * cfg.hidden_size), name
        shapes = jax.eval_shape(
            lambda rng, c=cfg: llama.init_params(rng, c),
            jax.random.PRNGKey(0))
        specs = param_partition_specs(shapes)

        def check(path, leaf, spec):
            for dim, axis in enumerate(spec):
                if axis == "model":
                    assert leaf.shape[dim] % 8 == 0, (
                        f"{name}: {path} {leaf.shape} axis {dim} "
                        f"not divisible by TP-8")

        jax.tree_util.tree_map_with_path(
            lambda p, l, s: check(p, l, s), shapes, specs)


def test_70b_class_specs_divide_on_tp8_and_tp16():
    """BASELINE config #5 (70B-class GSPMD TP): every parameter's sharded
    axis must divide evenly on TP-8 and TP-16 meshes, and the KV pages fall
    back to replication when TP exceeds the 8 KV heads — checked via
    eval_shape so no 70B weights are materialized."""
    from k8s_llm_monitor_tpu.models.config import PRESETS
    from k8s_llm_monitor_tpu.parallel.sharding import kv_pages_partition_specs

    class _FakeMesh:
        def __init__(self, tp):
            self.shape = {"data": 1, "seq": 1, "model": tp}

    for name in ("llama3-70b", "qwen2-72b"):
        cfg = PRESETS[name]
        shapes = jax.eval_shape(
            lambda rng, c=cfg: llama.init_params(rng, c),
            jax.random.PRNGKey(0))
        specs = param_partition_specs(shapes)

        for tp in (8, 16):
            def check(path, leaf, spec):
                for dim, axis in enumerate(spec):
                    if axis == "model":
                        assert leaf.shape[dim] % tp == 0, (
                            f"{name} tp={tp}: {path} {leaf.shape} "
                            f"axis {dim} not divisible")

            jax.tree_util.tree_map_with_path(
                lambda p, l, s: check(p, l, s), shapes, specs)

        pages_shape = jax.eval_shape(
            lambda c=cfg: llama.init_kv_pages(c, 16, 16))
        kv8 = kv_pages_partition_specs(
            pages_shape, _FakeMesh(8), num_kv_heads=cfg.num_kv_heads)
        assert kv8.k[0] == P(None, None, "model")        # 8 kv heads / tp8
        kv16 = kv_pages_partition_specs(
            pages_shape, _FakeMesh(16), num_kv_heads=cfg.num_kv_heads)
        assert kv16.k[0] == P(None, None, None)          # tp16 > kv -> repl


def test_70b_dims_tp_forward_lowers(cpu_mesh_devices):
    """A 70B-dimensioned (2-layer) model must lower with the TP specs on the
    8-device mesh — catches partitioner rejections (uneven shards, bad
    specs) without allocating 70B weights."""
    from k8s_llm_monitor_tpu.models.config import LLAMA3_70B
    import dataclasses as _dc

    cfg = _dc.replace(LLAMA3_70B, num_layers=2)
    mesh = create_mesh(MeshConfig(model=8))
    shapes = jax.eval_shape(
        lambda rng: llama.init_params(rng, cfg), jax.random.PRNGKey(0))
    specs = param_partition_specs(shapes)
    shaped = jax.tree.map(
        lambda l, s: jax.ShapeDtypeStruct(
            l.shape, l.dtype, sharding=NamedSharding(mesh, s)),
        shapes, specs)
    tok_shape = jax.ShapeDtypeStruct(
        (1, 64), jnp.int32, sharding=NamedSharding(mesh, P(None, None)))
    lowered = jax.jit(
        lambda p, t: llama.forward_full(p, cfg, t)
    ).lower(shaped, tok_shape)
    assert "stablehlo" in lowered.as_text()[:4000].lower()


def test_tp_engine_selects_pallas_kernel_path(cpu_mesh_devices):
    """When TP divides the KV heads, the engine must run the shard_map-
    wrapped Pallas kernel (VERDICT r3 item 3), not the gather fallback;
    when it does not divide, it must fall back."""
    from k8s_llm_monitor_tpu.ops.attention import paged_decode_attention

    params = llama.init_params(jax.random.PRNGKey(1), CFG)
    ecfg = EngineConfig(max_slots=2, num_blocks=32, block_size=8,
                        max_blocks_per_seq=8, prefill_buckets=(16,))
    mesh = create_mesh(MeshConfig(model=8))          # 8 kv heads / tp8
    eng = InferenceEngine(CFG, params, ecfg, eos_id=-1, mesh=mesh)
    assert eng._attn_impl is not paged_decode_attention

    import dataclasses as _dc
    cfg3 = _dc.replace(CFG, num_kv_heads=2, num_heads=8)  # tp8 > 2 kv heads
    eng2 = InferenceEngine(
        cfg3, llama.init_params(jax.random.PRNGKey(1), cfg3),
        ecfg, eos_id=-1, mesh=mesh)
    assert eng2._attn_impl is paged_decode_attention


def test_spec_layout_roles_and_rules():
    """SpecLayout is the single source of the axis layout; the regex rules
    bind its role methods to param paths (first match wins, unmatched
    leaves replicate, list indices drop out of paths)."""
    from k8s_llm_monitor_tpu.parallel.sharding import (
        DEFAULT_LAYOUT,
        SpecLayout,
        match_partition_rules,
        partition_rules,
    )

    lay = DEFAULT_LAYOUT
    assert lay.column_kernel() == P(None, "model")
    assert lay.row_kernel() == P("model", None)
    assert lay.embedding() == P("model", None)
    assert lay.layer_norm() == P(None)
    # KV pages: head-slice only when tp divides the kv-head count; any
    # other degree must replicate (a mid-head lane split is wrong, not
    # just slow).
    assert lay.kv_pages(8, 8) == P(None, None, "model")
    assert lay.kv_pages(8, 16) == P(None, None, None)
    assert lay.kv_pages(8, 3) == P(None, None, None)
    assert lay.kv_pages(8, 1) == P(None, None, None)
    # Page tables never shard: block ids are global (kv_cache.py).
    assert lay.page_table() == P(None, None)

    params = {"layers": [{"q": {"kernel": 0}, "o": {"kernel": 0},
                          "up_e": {"kernel": 0}, "input_norm": 0}],
              "embed": {"weight": 0}, "final_norm": 0, "odd_leaf": 0}
    specs = match_partition_rules(partition_rules(lay), params)
    assert specs["layers"][0]["q"]["kernel"] == P(None, "model")
    assert specs["layers"][0]["o"]["kernel"] == P("model", None)
    assert specs["layers"][0]["up_e"]["kernel"] == P("model", None, None)
    assert specs["layers"][0]["input_norm"] == P(None)
    assert specs["embed"]["weight"] == P("model", None)
    assert specs["odd_leaf"] == P(None)          # unmatched -> replicate

    # Axis names flow from the layout, not from hardcoded strings.
    alt = SpecLayout(model_axis="tp")
    assert alt.column_kernel() == P(None, "tp")
    assert alt.kv_pages(8, 2) == P(None, None, "tp")


def test_page_slice_bytes_divides_heads_not_pages():
    from k8s_llm_monitor_tpu.serving.kv_cache import page_slice_bytes

    full = page_slice_bytes(8, 64, 16, 2, tp=1)
    assert full == 2 * 16 * 8 * 64 * 2
    assert page_slice_bytes(8, 64, 16, 2, tp=8) == full // 8
    # Indivisible/oversubscribed TP replicates: the full page per chip.
    assert page_slice_bytes(8, 64, 16, 2, tp=16) == full
    assert page_slice_bytes(8, 64, 16, 2, tp=3) == full


@pytest.mark.slow  # builds two full engines (~30s on one core); the gate
# still runs in CI via `make tier1-mesh`, which applies no marker filter
def test_tp_mixed_traffic_parity_incl_constrained(cpu_mesh_devices):
    """The ISSUE's parity gate: TP-8 and 1-device engines must produce
    byte-identical greedy token streams over one mixed submission wave —
    a chunked long-prompt admission (> top bucket), dense short prefills,
    multi-round decode, and a grammar-constrained verdict lane sharing
    the batch."""
    from k8s_llm_monitor_tpu.diagnosis.grammar import verdict_fsm
    from k8s_llm_monitor_tpu.serving.engine import GenerationRequest
    from k8s_llm_monitor_tpu.utils.tokenizer import ByteTokenizer

    tok = ByteTokenizer()
    params = llama.init_params(jax.random.PRNGKey(4), CFG)
    ecfg = EngineConfig(max_slots=4, num_blocks=128, block_size=8,
                        max_blocks_per_seq=32, prefill_buckets=(16,),
                        decode_steps_per_iter=4)
    rng = np.random.default_rng(5)
    reqs = [
        ("long", [int(t) for t in rng.integers(2, 250, size=40)],
         SamplingParams(max_tokens=8)),                  # 40 > 16: chunked
        ("short-a", [int(t) for t in rng.integers(2, 250, size=7)],
         SamplingParams(max_tokens=8)),                  # dense admission
        ("short-b", [int(t) for t in rng.integers(2, 250, size=5)],
         SamplingParams(max_tokens=12)),                 # uneven drain
        ("verdict", tok.encode("why is default/web crashlooping?"),
         SamplingParams(max_tokens=1, constrained=True)),  # grammar lane
    ]

    def run(mesh):
        eng = InferenceEngine(CFG, params, ecfg, tokenizer=tok, mesh=mesh)
        eng.set_grammar(verdict_fsm(eos_id=tok.eos_id))
        for rid, prompt, sp in reqs:
            eng.submit(GenerationRequest(
                request_id=rid, prompt_ids=list(prompt), sampling=sp))
        while eng.has_work:
            eng.step()
        out = {}
        for rid, _, _ in reqs:
            res = eng.poll(rid)
            assert res is not None and res.finish_reason != "error", res
            out[rid] = res.token_ids
        return out

    plain = run(None)
    tp = run(create_mesh(MeshConfig(model=8)))
    assert plain == tp
    assert len(tp["verdict"]) > 0


def test_init_multihost_single_host_noop(cpu_mesh_devices):
    """init_multihost on a single host is a safe no-op returning index 0."""
    from k8s_llm_monitor_tpu.parallel.mesh import init_multihost

    assert init_multihost() == 0
    assert init_multihost() == 0  # idempotent
