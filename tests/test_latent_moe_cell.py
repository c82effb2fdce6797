"""The new cell's files and the harness's rehearsal of it at the tiny preset
(the driver's command runs ``tests/``; ``benchmarks/tests/test_cells.py`` does
the same for the Qwen2 cell): the configuration file against the catalog and
the program's preset, the kernel counts, the cell traced and untraced, and
``benchmarks/compare_reference.py`` end to end.
"""

import dataclasses
import io
import json
import pathlib

import jax
import numpy as np
import pytest

from benchmarks.references import deepseek_v3 as ref
from k8s_llm_monitor_tpu.models import llama
from k8s_llm_monitor_tpu.models.config import PRESETS
from k8s_llm_monitor_tpu.serving.engine import SPAN_CATALOG, EngineConfig

ROOT = pathlib.Path(__file__).resolve().parents[1]
CFG = dataclasses.replace(PRESETS["tiny-latent-moe"], dtype="float32")


def test_layer_specs_and_the_latent_page():
    cfg = PRESETS["kanana-2-30b-a3b-12l"]
    specs = [cfg.layer_spec(i) for i in range(cfg.num_layers)]
    assert [s.mlp for s in specs] == ["dense"] + ["shared+routed"] * 11
    assert {s.mixer for s in specs} == {"latent"} == {s.cache for s in specs}
    assert cfg.latent_page_width == 640 and cfg.kv_token_bytes() == 15_360
    assert PRESETS["mixtral-8x7b"].layer_spec(0).mlp == "routed"
    assert PRESETS["qwen2-7b"].layer_spec(3) == type(specs[0])("full", "dense", "kv")
    assert PRESETS["qwen2-7b"].kv_token_bytes() == 2 * 28 * 4 * 128 * 2
    pages = llama.init_kv_pages(CFG, 8, 4)
    assert pages.v == [] and pages.k[0].shape == (8, 4, 32 + 128)


def test_the_configuration_file_holds_the_published_widths():
    from benchmarks.harness import system

    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    (entry,) = [c for c in spec["configs"] if c["name"] == "kanana2-30b-a3b-w8a8"]
    config = json.loads((ROOT / entry["file"]).read_text())
    catalog = {
        "hidden_size": 2048, "num_attention_heads": 32, "kv_lora_rank": 512,
        "qk_nope_head_dim": 128, "qk_rope_head_dim": 64, "v_head_dim": 128,
        "n_routed_experts": 128, "moe_intermediate_size": 768,
        "num_experts_per_tok": 6, "n_shared_experts": 2,
        "routed_scaling_factor": 2.448, "intermediate_size": 6144,
        "vocab_size": 128256, "first_k_dense_replace": 1, "q_lora_rank": None}
    assert {k: config[k] for k in catalog} == catalog
    assert entry["reduced"] == config["reduced"] == ["num_hidden_layers"]
    assert config["published"] == {"num_hidden_layers": 48}
    cfg = system.model_config(config)
    assert cfg.act_quant and cfg.num_layers == config["num_hidden_layers"] == 12
    assert ref.config_of(cfg) == {k: config[k] for k in ref.config_of(cfg)}
    # The pool the file reckons is the pool the engine would build.
    eng, reck = config["assumed"]["engine"], config["assumed"]["pool_reckoning"]
    assert eng["num_blocks"] == eng["max_slots"] * eng["max_blocks_per_seq"]
    assert reck["token_bytes"] == cfg.kv_token_bytes()
    assert reck["pool_bytes"] == eng["num_blocks"] * eng["block_size"] * reck["token_bytes"]
    assert EngineConfig(**eng).prefill_buckets == (1024, 2048, 4096)
    shapes = jax.eval_shape(
        lambda k: __import__("k8s_llm_monitor_tpu.utils.quantize", fromlist=["x"])
        .init_params_quantized(k, cfg), jax.random.PRNGKey(0))
    assert reck["weights_bytes"] == sum(
        int(np.prod(x.shape)) * x.dtype.itemsize for x in jax.tree.leaves(shapes))


def test_kernel_counts_read_catalogued_attributes():
    from benchmarks.harness import kernel_counts

    config = json.loads((ROOT / "benchmarks/configs/kanana2-30b-a3b-w8a8.json").read_text())
    for name, reads in kernel_counts.READS.items():
        assert set(reads) <= set(SPAN_CATALOG["engine.call"]), name
        assert getattr(kernel_counts, name)(config, {}) is None
    ops_, nbytes = kernel_counts.latent_decode_attention(
        config, {"steps": 2, "lanes": 3, "ctx_tokens": 100})
    assert nbytes == (103 + 106) * 12 * 1152 and ops_ == (103 + 106) * 12 * 32 * 2 * 1088


# -- (g) the harness's rehearsal of the new cell ------------------------------

WORKLOAD = "kanana2-30b-a3b.evidence-loops"
SMALL = {"prompt_tokens": {"median": 24, "min": 17, "max": 32},
         "max_tokens": {"median": 10, "min": 4, "max": 24}, "lead_in_s": 0.5,
         "clients": 16, "max_rps": 400.0, "stratum": 16,
         "warm_up_answer_tokens": 16}
SMALL_ENGINE = {"max_slots": 8, "num_blocks": 96, "max_blocks_per_seq": 6,
                "prefill_buckets": [32]}


@pytest.mark.parametrize("trace", [False, True], ids=["untraced", "traced"])
def test_the_new_cell_rehearsed_on_the_cpu(trace):
    from benchmarks.harness import cell as harness
    from benchmarks.harness.registry import Registry

    out = io.StringIO()
    result = harness.run_cell(
        WORKLOAD, 2**31 + 5, 1.5, trace, out=out,
        rehearsal=harness.Rehearsal(preset="tiny-latent-moe",
                                    engine=SMALL_ENGINE, traffic=SMALL))
    info = json.loads(out.getvalue().strip().splitlines()[0])
    assert info["compiles_in_window"] == 0 and not any(info["faults"].values())
    assert result["correct"] is True and result["failed"] == 0 < result["attempted"]
    cell = Registry().cell(WORKLOAD)
    if not trace:
        assert set(result["metrics"]) == {m.name for m in cell.end_to_end} == {
            "tokens_per_s", "tpot_p95_ms", "setup_s"}
        return
    # Every span-fed metric the cell lists prints; the device-trace ones
    # (module medians, the kernel's roofline share) need a TPU's trace.
    listed = {m.name for m in cell.per_layer}
    assert {"moe_experts_hit_share", "moe_rows_max_over_mean",
            "latent_decode_attention_roofline", "decode_call_ms"} <= listed
    span_fed = {"busy_lanes_mean", "kv_blocks_peak_share", "compiles_in_window",
                "moe_experts_hit_share", "moe_rows_max_over_mean"}
    assert span_fed <= set(result["metrics"]), sorted(result["metrics"])
    hit = result["metrics"]["moe_experts_hit_share"]["value"]
    assert 0.3 < hit <= 1.0
    assert result["metrics"]["moe_rows_max_over_mean"]["value"] >= 1.0


def test_compare_reference_rehearsed_on_the_cpu():
    """benchmarks/compare_reference.py end to end at the tiny preset (the
    preset's bfloat16, w8a8): one reference layer at a time on the engine's
    own input to it, over the prompt and the decode steps; the whole-model
    logits as information; the 4-bit-activation control over the limit."""
    from benchmarks import compare_reference

    out = compare_reference.compare(
        "kanana2-30b-a3b-w8a8", 2**31 + 9, traffic="evidence-loops", prompts=1,
        decode=3, controls=1, preset="tiny-latent-moe",
        engine={"max_slots": 2, "num_blocks": 64, "max_blocks_per_seq": 12,
                "prefill_buckets": [32, 64]},
        limits={"update_rel_l2_median": 0.1})
    (prompt,) = out["prompts"]
    assert [row["layer"] for row in prompt["layers"]] == [0, 1, 2]
    assert all({"prompt_median", "decode_median"} <= set(row) for row in prompt["layers"])
    assert len(prompt["head_rel_l2"]) == len(prompt["whole_model_rel_l2"]) == 4
    assert all(row["prompt_median"] < 0.1 and row["decode_median"] < 0.1
               for row in prompt["layers"])
    assert max(prompt["head_rel_l2"]) < 0.01 and len(prompt["head_int8_input_rel_l2"]) == 4
    assert prompt["control_caught"] and out["ok"]
