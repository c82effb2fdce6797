"""The jamba cell's files and the harness's rehearsal of it at the tiny
preset (as ``tests/test_nemotron_h_cell.py`` does for the nemotron cell):
the configuration file against the catalog row and the program's preset,
the cell traced and untraced, ``benchmarks/compare_layers.py`` end to end;
and the open-loop Qwen2 mix beside it, rehearsed as the cell it waits to be.
"""

import io
import json
import pathlib

import jax
import numpy as np
import pytest

from benchmarks.references import jamba as ref
from k8s_llm_monitor_tpu.models.config import PRESETS
from k8s_llm_monitor_tpu.serving.engine import EngineConfig

ROOT = pathlib.Path(__file__).resolve().parents[1]
NAME = "jamba2-3b-w8a8"
WORKLOAD = "jamba2-3b.reasoning-loops"
# The catalog's row (architectures.jsonl, AI21-Jamba2-3B), key for key.
CATALOG = {
    "attn_layer_offset": 7, "attn_layer_period": 14, "expert_layer_offset": 1,
    "expert_layer_period": 2, "hidden_act": "silu", "hidden_size": 2560,
    "intermediate_size": 8192, "mamba_conv_bias": True, "mamba_d_conv": 4,
    "mamba_d_state": 16, "mamba_dt_rank": 160, "mamba_expand": 2,
    "mamba_proj_bias": False, "max_position_embeddings": 262144,
    "model_type": "jamba", "num_attention_heads": 20, "num_experts": 1,
    "num_experts_per_tok": 1, "num_hidden_layers": 28,
    "num_key_value_heads": 1, "num_logits_to_keep": 1, "rms_norm_eps": 1e-06,
    "sliding_window": None, "tie_word_embeddings": True,
    "use_mamba_kernels": True, "vocab_size": 65536}


def _config():
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    (entry,) = [c for c in spec["configs"] if c["name"] == NAME]
    return entry, json.loads((ROOT / entry["file"]).read_text())


def test_the_configuration_file_is_the_published_one_and_the_preset():
    from benchmarks.harness import system

    entry, config = _config()
    assert {k: config[k] for k in CATALOG} == CATALOG
    assert entry["reduced"] == config["reduced"] == []
    cfg = system.model_config(config)
    assert cfg.act_quant and cfg.rms_norm_eps == config["rms_norm_eps"]
    assert ref.config_of(cfg) == {
        "hidden_size": 2560, "num_hidden_layers": 28, "num_attention_heads": 20,
        "num_key_value_heads": 1, "head_dim": 128, "mamba_expand": 2,
        "mamba_d_state": 16, "mamba_d_conv": 4, "mamba_dt_rank": 160,
        "rms_norm_eps": 1e-06}
    assert (cfg.attn_layer_period, cfg.attn_layer_offset) == (
        config["attn_layer_period"], config["attn_layer_offset"])
    assert cfg.tie_embeddings and not cfg.use_rope and cfg.num_experts == 0
    # The pools the file reckons are the pools the engine would build.
    eng, reck = config["assumed"]["engine"], config["assumed"]["pool_reckoning"]
    assert eng["num_blocks"] == eng["max_slots"] * eng["max_blocks_per_seq"] + 1
    ec = EngineConfig(**eng)
    assert ec.prefill_buckets[-1] >= eng["max_blocks_per_seq"] * eng["block_size"]
    assert reck["token_bytes"] == cfg.kv_token_bytes() == 1024
    assert reck["page_pool_bytes"] == eng["num_blocks"] * eng["block_size"] * 1024
    assert reck["state_lane_bytes"] == cfg.state_lane_bytes() == 9_318_400
    assert reck["state_pool_bytes"] == eng["max_slots"] * reck["state_lane_bytes"]
    from k8s_llm_monitor_tpu.utils.quantize import init_params_quantized

    shapes = jax.eval_shape(lambda k: init_params_quantized(k, cfg),
                            jax.random.PRNGKey(0))
    assert reck["weights_bytes"] == sum(
        int(np.prod(x.shape)) * x.dtype.itemsize for x in jax.tree.leaves(shapes))
    left = (reck["bytes_limit"] - reck["weights_bytes"] - reck["page_pool_bytes"]
            - reck["state_pool_bytes"] - reck["largest_temporaries_bytes"]
            - 2**30)
    assert left == reck["left_after_the_rule_bytes"] > 0


# -- the harness's rehearsal of the new cell -------------------------------------

SMALL = {"prompt_tokens": {"median": 24, "min": 17, "max": 32},
         "max_tokens": {"median": 10, "min": 4, "max": 24}, "lead_in_s": 0.5,
         "clients": 16, "max_rps": 400.0, "stratum": 16,
         "warm_up_answer_tokens": 16}
SMALL_ENGINE = {"max_slots": 8, "num_blocks": 40, "max_blocks_per_seq": 4,
                "prefill_buckets": [32, 64]}
# What a traced run of either new cell reads off the program's spans and
# samples, and what only a TPU's device trace gives.
SPAN_FED = {"busy_lanes_mean", "kv_blocks_peak_share", "compiles_in_window",
            "admit_tokens_per_device_s", "decode_steps_per_device_s",
            "loop_wait_device_share", "step_host_ms_p50",
            "device_empty_dispatch_share", "decode_slot_use_share",
            "kv_live_blocks_peak_share", "preempt_requeue_in_window"}
DEVICE_FED = {"prefill_call_ms", "decode_call_ms",
              "mamba1_decode_update_roofline", "mamba1_scan_roofline"}


@pytest.mark.parametrize("trace", [False, True], ids=["untraced", "traced"])
def test_the_new_cell_rehearsed_on_the_cpu(trace):
    from benchmarks.harness import cell as harness
    from benchmarks.harness.registry import Registry

    out = io.StringIO()
    result = harness.run_cell(
        WORKLOAD, 2**31 + 5, 1.5, trace, out=out,
        rehearsal=harness.Rehearsal(preset="tiny-jamba", engine=SMALL_ENGINE,
                                    traffic=SMALL))
    info = json.loads(out.getvalue().strip().splitlines()[0])
    assert info["compiles_in_window"] == 0 and not any(info["faults"].values())
    assert result["correct"] is True and result["failed"] == 0 < result["attempted"]
    cell = Registry().cell(WORKLOAD)
    if not trace:
        assert set(result["metrics"]) == {m.name for m in cell.end_to_end} == {
            "tokens_per_s", "tpot_p95_ms", "setup_s"}
        return
    # Every span-fed metric the cell lists prints; the device-trace ones
    # (module medians, the kernels' roofline shares) need a TPU's trace.
    listed = {m.name for m in cell.per_layer}
    assert {"mamba1_decode_update_roofline", "mamba1_scan_roofline",
            "decode_call_ms", "prefill_call_ms"} <= listed
    assert SPAN_FED | {"admit_token_use_share"} <= listed
    assert listed - DEVICE_FED <= set(result["metrics"]), sorted(result["metrics"])
    assert not {"ssm_decode_update_roofline", "ssm_chunk_scan_roofline",
                "prefill_token_use_share"} & listed


def test_compare_layers_rehearsed_on_the_cpu():
    """benchmarks/compare_layers.py end to end at the tiny preset (the
    preset's bfloat16, w8a8): one reference layer at a time on the engine's
    own input to it, over the prompt and the decode steps (a Mamba-1 layer's
    decode positions read the state pool); the 4-bit-activation control over
    the limit, the int8-cache control a no-op outside the attention layer;
    the logit rows against the reference's tied head."""
    from benchmarks import compare_layers

    out = compare_layers.compare(
        NAME, 2**31 + 9, traffic="reasoning-loops", prompts=1, decode=3,
        controls=1, preset="tiny-jamba",
        engine={"max_slots": 2, "num_blocks": 64, "max_blocks_per_seq": 4,
                "prefill_buckets": [32, 64]},
        limits={"update_rel_l2_median": 0.1})
    (prompt,) = out["prompts"]
    assert [row["layer"] for row in prompt["layers"]] == list(range(4))
    assert all(row["prompt_median"] < 0.1 and row["decode_median"] < 0.1
               for row in prompt["layers"])
    assert len(prompt["head_rel_l2"]) == 4 and max(prompt["head_rel_l2"]) < 0.05
    cache = {r["layer"]: r["prompt_median"] for r in prompt["control_cache_int8"]}
    assert cache[2] > 0 and all(v == 0 for li, v in cache.items() if li != 2)
    assert prompt["control_caught"] and out["ok"]


def test_the_limit_lies_between_its_two_readings():
    """The comparison's limit for this configuration, in a file of its own
    beside ``limits.json`` (which only a ``benchmark`` PR may edit): above
    the engine's largest reading on the chip and below the 4-bit control's
    smallest, with room on both sides."""
    limits = json.loads((ROOT / "benchmarks/references" /
                         f"limits.{NAME}.json").read_text())[NAME]
    engine, control = limits["engine_largest"], limits["control_int4_smallest"]
    assert engine * 1.5 < limits["update_rel_l2_median"] < control / 1.5
    assert NAME not in json.loads(
        (ROOT / "benchmarks/references/limits.json").read_text())


def test_the_parent_program_cannot_run_the_new_cell():
    """Without the preset the harness fails at once, on the name (what the
    trial of the new cell on a checkout without the preset must see)."""
    from benchmarks.harness import system

    _, config = _config()
    with pytest.raises(KeyError):
        system.model_config(dict(config, preset="no-such-preset"))
    assert config["preset"] in PRESETS


# -- the open-loop Qwen2 mix (data files only; no cell yet) ------------------------

HELD = "qwen2-7b.diagnose-steady"
# What the cell would list beyond the metrics of every cell: the Qwen2
# cell's loop, batching and KV-pool metrics.
HELD_LISTS = SPAN_FED - {"busy_lanes_mean", "kv_blocks_peak_share",
                         "compiles_in_window", "admit_tokens_per_device_s",
                         "decode_steps_per_device_s"} | {"prefill_token_use_share"}


def _held_registry():
    """The registry with the held cell entered in memory, as a later
    ``BENCHMARK.json`` would enter it once its spread over seeds is
    measured: the Qwen2 configuration under ``diagnose-steady-knee``."""
    from benchmarks.harness.registry import Registry

    reg = Registry()
    assert HELD not in {w["name"] for w in reg.benchmark["workloads"]}
    reg.benchmark["workloads"].append({
        "name": HELD, "config": "qwen2-7b-w8a8",
        "traffic": "diagnose-steady-knee", "chips": 1})
    for m in reg.benchmark["per_layer"]:
        if m["name"] in HELD_LISTS:
            m["workloads"].append(HELD)
    return reg


def test_the_open_loop_cell_is_data_on_the_served_configuration():
    """The open-loop mix runs the Qwen2 configuration the benchmark has
    under a traffic file of its own (the older ``diagnose-steady.json``
    stays as it was): Poisson arrivals at a fixed rate below what the
    saturated cell completes, its sizes the saturated cell's, the same
    sizes at every seed, every program the window can reach warmed."""
    from benchmarks.generators import open_poisson

    reg = _held_registry()
    cell = reg.cell(HELD)
    steady = cell.traffic
    saturated = reg.cell("qwen2-7b.loops-saturated").traffic
    assert cell.config_name == "qwen2-7b-w8a8" and cell.chips == 1
    assert cell.traffic_name == "diagnose-steady-knee"
    assert steady["kind"] == "open_poisson" and steady["rate_rps"] == 10.5
    assert steady["prompt_tokens"] == saturated["prompt_tokens"]
    assert steady["max_tokens"] == saturated["max_tokens"]
    # The offered load is the seed's order of one set of sizes.
    offered = {sum(r.max_tokens for r in open_poisson.plan(
        steady, seed=seed, vocab=152_064, seconds=40.0) if r.due_s >= 0)
        for seed in (2**31 + 1, 2**31 + 2, 7)}
    assert offered == {62_770}
    # Lanes with fewer than 8 tokens to go exist below saturation: the
    # warm-up answers run the fused decode at 8, 4, 2 and 1 steps.
    assert steady["warm_up_answer_tokens"] == 16
    old = json.loads((ROOT / "benchmarks/traffic/diagnose-steady.json")
                     .read_text())
    assert old["rate_rps"] == 1.0


@pytest.mark.parametrize("trace", [False, True], ids=["untraced", "traced"])
def test_the_open_loop_cell_rehearsed_on_the_cpu(trace):
    """The open-loop cell's whole path at the tiny Qwen2 preset: every
    request due inside the window is in its sample, none fails, nothing
    compiles inside the window; the untraced run reports the three
    end-to-end metrics, the traced one every span-fed metric it would
    list."""
    from benchmarks.harness import cell as harness

    reg, rate = _held_registry(), 20.0
    out = io.StringIO()
    result = harness.run_cell(
        HELD, 2**31 + 7, 1.5, trace, out=out, registry=reg,
        rehearsal=harness.Rehearsal(
            preset="tiny-qwen",
            engine={"max_slots": 8, "num_blocks": 96, "max_blocks_per_seq": 6},
            traffic={"prompt_tokens": {"median": 24, "min": 17, "max": 32},
                     "max_tokens": {"median": 10, "min": 4, "max": 24},
                     "lead_in_s": 0.5, "rate_rps": rate}))
    info = json.loads(out.getvalue().strip().splitlines()[0])
    assert info["compiles_in_window"] == 0 and not any(info["faults"].values())
    assert result["correct"] is True and result["failed"] == 0
    assert result["attempted"] == round(rate * 1.5)
    cell = reg.cell(HELD)
    if not trace:
        assert set(result["metrics"]) == {m.name for m in cell.end_to_end}
        return
    listed = {m.name for m in cell.per_layer}
    assert HELD_LISTS <= listed
    assert listed - DEVICE_FED <= set(result["metrics"]), sorted(result["metrics"])
