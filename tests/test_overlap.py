"""Latency-hiding TP decode (parallel/overlap.py) + tier-aware admission.

The overlap schedule replaces GSPMD's auto-inserted post-o/post-down psum
with a hand-staged reduce-scatter -> all-gather pair interleaved with the
next column-parallel matmuls.  Its whole value rests on EXACT parity: the
staged collectives must reproduce the GSPMD reference byte-for-byte
(greedy argmax over identical float math), or the flag is a silent
quality regression.  These tests are that gate, plus the admission /
spec-default satellites that ride the same PR.
"""

import dataclasses
import os

import numpy as np
import pytest

import jax

from k8s_llm_monitor_tpu.models import llama
from k8s_llm_monitor_tpu.models.config import ModelConfig, PRESETS
from k8s_llm_monitor_tpu.parallel.mesh import MeshConfig, create_mesh
from k8s_llm_monitor_tpu.parallel.overlap import overlap_supported
from k8s_llm_monitor_tpu.serving.engine import (
    EngineConfig,
    GenerationRequest,
    InferenceEngine,
    SamplingParams,
)

# Overlap-compatible geometry: 8 heads / 8 KV heads / even hidden and
# intermediate splits under TP-8 (test_sharding.py's CFG, reused so the
# two parity suites gate the same model).
CFG = ModelConfig(name="t", vocab_size=512, hidden_size=64, intermediate_size=128,
                  num_layers=2, num_heads=8, num_kv_heads=8, dtype="float32",
                  rope_theta=10_000.0)

ECFG = EngineConfig(max_slots=4, num_blocks=128, block_size=8,
                    max_blocks_per_seq=32, prefill_buckets=(16,),
                    decode_steps_per_iter=4)


def _engine(params, tp_overlap, mesh, **kw):
    ecfg = dataclasses.replace(ECFG, tp_overlap=tp_overlap, **kw)
    return InferenceEngine(CFG, params, ecfg, eos_id=-1, mesh=mesh)


# -- support gates ------------------------------------------------------------


def test_overlap_supported_gates(cpu_mesh_devices):
    mesh = create_mesh(MeshConfig(model=8))
    assert overlap_supported(CFG, mesh) == ""
    assert "mesh" in overlap_supported(CFG, None)
    # tiny preset: 4 heads / 2 KV heads do not divide TP-8 -> pages
    # would replicate and the per-shard attention contract breaks.
    assert overlap_supported(PRESETS["tiny"], mesh) != ""
    moe = dataclasses.replace(CFG, num_experts=8, num_experts_per_tok=2)
    assert "expert" in overlap_supported(moe, mesh)
    odd = dataclasses.replace(CFG, intermediate_size=129)
    assert overlap_supported(odd, mesh) != ""


def test_auto_mode_falls_back_and_on_mode_raises(cpu_mesh_devices):
    """`auto` silently keeps GSPMD on unsupported geometry; `on` refuses
    to build rather than serve a schedule it cannot honour."""
    tiny = PRESETS["tiny"]
    params = llama.init_params(jax.random.PRNGKey(0), tiny)
    mesh = create_mesh(MeshConfig(model=8))
    ecfg = dataclasses.replace(ECFG, tp_overlap="auto")
    eng = InferenceEngine(tiny, params, ecfg, eos_id=-1, mesh=mesh)
    assert not eng.tp_overlap
    with pytest.raises(ValueError, match="tp_overlap"):
        InferenceEngine(tiny, params,
                        dataclasses.replace(ECFG, tp_overlap="on"),
                        eos_id=-1, mesh=mesh)


def test_unknown_tp_overlap_value_is_refused(cpu_mesh_devices):
    """The field is the one selector: "off" keeps the GSPMD program on a
    geometry the schedule supports, and a value outside auto|on|off is
    refused at construction, on or off a mesh."""
    params = llama.init_params(jax.random.PRNGKey(0), CFG)
    mesh = create_mesh(MeshConfig(model=8))
    assert not _engine(params, "off", mesh).tp_overlap
    for m in (mesh, None):
        with pytest.raises(ValueError, match="unknown tp_overlap 'bogus'"):
            _engine(params, "bogus", m)


# -- parity: the tentpole gate ------------------------------------------------


@pytest.mark.slow  # three full engines; runs in CI via `make tier1-mesh`
def test_overlap_mixed_traffic_parity_incl_constrained(cpu_mesh_devices):
    """Byte-identical greedy streams: overlap vs GSPMD vs 1-device over
    one mixed wave — chunked long prompt, dense short prefills, uneven
    decode drain, and a grammar-constrained verdict lane in the batch."""
    from k8s_llm_monitor_tpu.diagnosis.grammar import verdict_fsm
    from k8s_llm_monitor_tpu.utils.tokenizer import ByteTokenizer

    tok = ByteTokenizer()
    params = llama.init_params(jax.random.PRNGKey(4), CFG)
    rng = np.random.default_rng(5)
    reqs = [
        ("long", [int(t) for t in rng.integers(2, 250, size=40)],
         SamplingParams(max_tokens=8)),                    # 40 > 16: chunked
        ("short-a", [int(t) for t in rng.integers(2, 250, size=7)],
         SamplingParams(max_tokens=8)),
        ("short-b", [int(t) for t in rng.integers(2, 250, size=5)],
         SamplingParams(max_tokens=12)),                   # uneven drain
        ("verdict", tok.encode("why is default/web crashlooping?"),
         SamplingParams(max_tokens=1, constrained=True)),  # grammar lane
    ]

    def run(mesh, tp_overlap):
        ecfg = dataclasses.replace(ECFG, tp_overlap=tp_overlap)
        eng = InferenceEngine(CFG, params, ecfg, tokenizer=tok, mesh=mesh)
        assert eng.tp_overlap == (tp_overlap == "on")
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

    mesh = create_mesh(MeshConfig(model=8))
    overlap = run(mesh, "on")
    gspmd = run(mesh, "off")
    plain = run(None, "auto")
    assert overlap == gspmd == plain
    assert len(overlap["verdict"]) > 0


@pytest.mark.slow  # four engines (two quant variants x on/off)
def test_overlap_quant_parity(cpu_mesh_devices):
    """Quantized pools keep exactness: int8 KV (per-page scales travel
    through the shard_map) and W8A8 (global pmax amax + int32 partial
    reduced BEFORE the float scales, matching GSPMD's multiply order)."""
    from k8s_llm_monitor_tpu.utils.quantize import quantize_params

    params = llama.init_params(jax.random.PRNGKey(6), CFG)
    qparams = quantize_params(params)
    mesh = create_mesh(MeshConfig(model=8))
    prompts = [[5, 6, 7, 8, 9, 10, 11], [9, 8, 7, 6, 5], [11, 12, 13]]
    sp = SamplingParams(max_tokens=10)

    def run(cfg, p, tp_overlap, **kw):
        ecfg = dataclasses.replace(ECFG, tp_overlap=tp_overlap, **kw)
        eng = InferenceEngine(cfg, p, ecfg, eos_id=-1, mesh=mesh)
        assert eng.tp_overlap == (tp_overlap == "on")
        return [r.token_ids for r in eng.generate(prompts, sp)]

    # int8 KV pages
    assert (run(CFG, params, "on", kv_dtype="int8")
            == run(CFG, params, "off", kv_dtype="int8"))
    # W8A8: int8 weights + dynamic int8 activations
    cfg_aq = dataclasses.replace(CFG, act_quant=True)
    assert run(cfg_aq, qparams, "on") == run(cfg_aq, qparams, "off")


# -- traceguard: zero recompiles with overlap on ------------------------------


@pytest.mark.slow  # builds a real engine; also runs via `make lint-trace`
def test_traceguard_overlap_path_zero_recompiles():
    """Warm the overlap engine, rerun same-shaped traffic: program caches
    must not grow, no forbidden host-sync ops, and the donated page-pool /
    token-state buffers must rebind across the shard_map'd decode step."""
    from k8s_llm_monitor_tpu.devtools import traceguard

    report = traceguard.check_path("overlap")
    assert report.warm_compiles > 0
    assert report.repeat_compiles == 0, report.as_dict()
    assert not any(report.forbidden.values()), report.forbidden
    assert report.donated_pages_rebound and report.donated_tokens_rebound
    assert report.ok


# -- tier-aware admission -----------------------------------------------------

BS = 16
SEED_LEN = 64            # publishes shareable_blocks(64,16)=3 blocks each
A_LEN, A_GEN = 120, 8    # burst lane: needs 121 tokens of headroom


def _admission_engine(kv_admission: str, host_bytes: int = 64 << 20):
    """Device pool of 17 usable blocks with 12 pinned by published seed
    prefixes -> 5 free blocks = 80 tokens of device-only headroom."""
    params = llama.init_params(jax.random.PRNGKey(7), CFG)
    ecfg = EngineConfig(
        max_slots=4, num_blocks=18, block_size=BS,
        max_blocks_per_seq=(A_LEN + A_GEN + 1 + BS - 1) // BS,
        prefill_buckets=(64, 128), max_prefills_per_step=2,
        decode_steps_per_iter=4, prefix_cache_entries=64,
        host_spill_bytes=host_bytes, kv_admission=kv_admission)
    eng = InferenceEngine(CFG, params, ecfg, eos_id=-1)
    rng = np.random.default_rng(23)
    for _ in range(4):
        eng.generate([[int(t) for t in rng.integers(4, 500, size=SEED_LEN)]],
                     SamplingParams(max_tokens=1))
    return eng, rng


def test_tier_admission_admits_where_device_only_sheds():
    tier, _ = _admission_engine("tier")
    dev, _ = _admission_engine("device")
    assert tier.allocator.free_blocks == dev.allocator.free_blocks == 5
    need = A_LEN + 1
    # device-only headroom: 5 * 16 = 80 < 121 -> shed
    assert "kv capacity" in dev.should_shed(need_tokens=need)
    # tier headroom adds the 12 spillable blocks: (5 + 12) * 16 = 272
    assert tier.admission_headroom_tokens() == 272
    assert tier.should_shed(need_tokens=need) == ""


def test_tier_admission_sheds_when_host_also_full():
    """A host tier too small for even one block buys no headroom: the
    tier policy degrades to device-only arithmetic, not wishful math."""
    eng, _ = _admission_engine("tier", host_bytes=1024)
    assert eng.admission_headroom_tokens() == 5 * BS
    assert "kv capacity" in eng.should_shed(need_tokens=A_LEN + 1)


def test_tier_mode_without_host_tier_is_legacy():
    """kv_admission="tier" with no host tier configured must not arm the
    capacity clause — there is nothing to spill to, so admission relies
    on the queue + OutOfBlocks pushback exactly as before this PR."""
    params = llama.init_params(jax.random.PRNGKey(7), CFG)
    ecfg = dataclasses.replace(ECFG, kv_admission="tier", host_spill_bytes=0)
    eng = InferenceEngine(CFG, params, ecfg, eos_id=-1)
    assert eng.host_kv_tier is None
    assert eng.should_shed(need_tokens=10**6) == ""


def test_tier_admitted_lanes_lose_zero_tokens_under_eviction_faults():
    """The admitted burst must finish clean with its full token budget
    while lane_eviction faults fire mid-drain: spill/restore through the
    host tier is lossless, so admission-by-spill never costs output."""
    from k8s_llm_monitor_tpu.resilience.faults import get_injector

    eng, rng = _admission_engine("tier")
    admitted = []
    get_injector().reset(seed=1234)
    get_injector().arm("lane_eviction", rate=0.25, times=2)
    try:
        for i in range(4):
            prompt = [int(t) for t in rng.integers(4, 500, size=A_LEN)]
            assert eng.should_shed(need_tokens=len(prompt) + 1) == ""
            eng.submit(GenerationRequest(
                request_id=f"burst-{i}", prompt_ids=prompt,
                sampling=SamplingParams(max_tokens=A_GEN)))
            admitted.append(f"burst-{i}")
        while eng.has_work:
            eng.step()
    finally:
        get_injector().reset()
    for rid in admitted:
        res = eng.poll(rid)
        assert res is not None and res.finish_reason != "error", res
        assert len(res.token_ids) == A_GEN, (rid, res.token_ids)


# -- spec decode default-on ---------------------------------------------------


def test_spec_decode_default_on_with_kill_switch():
    """Monitor presets now draft by default; the AcceptanceEMA floor and
    explicit spec_k=0 opt-out both remain live kill-switches."""
    from k8s_llm_monitor_tpu.monitor.config import TPULLMConfig

    cfg = TPULLMConfig()
    assert cfg.spec_k > 0                    # default-on
    assert cfg.spec_min_accept > 1.0         # EMA floor still armed
    assert TPULLMConfig(spec_k=0).spec_k == 0  # opt-out respected

    # The engine honours the floor: an engine built with drafting on
    # arms the acceptance EMA with the config's floor, and the analysis
    # factory threads the monitor defaults straight into EngineConfig.
    params = llama.init_params(jax.random.PRNGKey(0), CFG)
    ecfg = dataclasses.replace(ECFG, spec_k=cfg.spec_k,
                               spec_min_accept=cfg.spec_min_accept)
    eng = InferenceEngine(CFG, params, ecfg, eos_id=-1)
    assert eng._spec_accept.floor == cfg.spec_min_accept
    assert eng.ecfg.spec_k == cfg.spec_k > 0
