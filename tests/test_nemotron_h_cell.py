"""The nemotron_h cell's files and the harness's rehearsal of it at the tiny
preset (as ``tests/test_latent_moe_cell.py`` does for the cell before it):
the configuration file against the catalog row and the program's preset,
the cell traced and untraced, ``benchmarks/compare_reference.py`` end to end.
"""

import io
import json
import pathlib

import jax
import numpy as np
import pytest

from benchmarks.references import nemotron_h as ref
from k8s_llm_monitor_tpu.models.config import PRESETS
from k8s_llm_monitor_tpu.serving.engine import EngineConfig

ROOT = pathlib.Path(__file__).resolve().parents[1]
NAME = "nemotron3-super-120b-a12b-w8a8"
WORKLOAD = "nemotron3-super-120b.triage-loops"
PUBLISHED_PATTERN = ("MEMEMEM*EMEMEMEM*EMEMEMEM*EMEMEMEMEM*EMEMEMEMEM*"
                     "EMEMEMEMEM*EMEMEMEMEM*EMEMEMEM*EMEMEMEME")


def test_the_configuration_file_holds_the_published_widths():
    from benchmarks.harness import system

    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    (entry,) = [c for c in spec["configs"] if c["name"] == NAME]
    config = json.loads((ROOT / entry["file"]).read_text())
    catalog = {
        "hidden_size": 4096, "num_attention_heads": 32, "num_key_value_heads": 2,
        "head_dim": 128, "mamba_num_heads": 128, "mamba_head_dim": 64,
        "ssm_state_size": 128, "n_groups": 8, "conv_kernel": 4, "chunk_size": 128,
        "expand": 2, "intermediate_size": 2688, "moe_intermediate_size": 2688,
        "moe_latent_size": 1024, "moe_shared_expert_intermediate_size": 5376,
        "n_shared_experts": 1, "num_experts_per_tok": 22, "n_group": 1,
        "routed_scaling_factor": 5, "layer_norm_epsilon": 1e-05,
        "rope_theta": 10000, "hybrid_override_pattern": PUBLISHED_PATTERN,
        "mlp_hidden_act": "relu2", "model_type": "nemotron_h"}
    assert {k: config[k] for k in catalog} == catalog
    assert entry["reduced"] == config["reduced"] == [
        "num_hidden_layers", "n_routed_experts", "vocab_size"]
    assert config["published"] == {"num_hidden_layers": 88,
                                   "n_routed_experts": 512,
                                   "vocab_size": 131072}
    assert (config["num_hidden_layers"], config["n_routed_experts"],
            config["vocab_size"]) == (22, 128, 32768)
    cfg = system.model_config(config)
    assert cfg.act_quant and cfg.rms_norm_eps == config["layer_norm_epsilon"]
    # The layers run are the first 22 letters of the published pattern: two
    # whole periods, 5 M : 5 E : 1 *.
    assert cfg.layer_pattern == PUBLISHED_PATTERN[:22]
    assert [cfg.layer_pattern.count(c) for c in "ME*"] == [10, 10, 2]
    assert (cfg.num_experts, cfg.experts_held, cfg.expert_start) == (512, 128, 0)
    assert ref.config_of(cfg) == {k: config[k] for k in ref.config_of(cfg)}
    assert (cfg.mamba_inner, cfg.mamba_conv_dim, cfg.moe_latent_size,
            cfg.shared_width, cfg.expert_width) == (8192, 10240, 1024, 5376, 2688)
    # The pools the file reckons are the pools the engine would build.
    eng, reck = config["assumed"]["engine"], config["assumed"]["pool_reckoning"]
    assert eng["num_blocks"] == eng["max_slots"] * eng["max_blocks_per_seq"] + 1
    ec = EngineConfig(**eng)
    assert ec.prefill_buckets[-1] >= eng["max_blocks_per_seq"] * eng["block_size"]
    assert reck["token_bytes"] == cfg.kv_token_bytes() == 2048
    assert reck["page_pool_bytes"] == eng["num_blocks"] * eng["block_size"] * 2048
    assert reck["state_lane_bytes"] == cfg.state_lane_bytes() == 42_557_440
    assert reck["state_pool_bytes"] == eng["max_slots"] * reck["state_lane_bytes"]
    from k8s_llm_monitor_tpu.utils.quantize import init_params_quantized

    shapes = jax.eval_shape(lambda k: init_params_quantized(k, cfg),
                            jax.random.PRNGKey(0))
    assert reck["weights_bytes"] == sum(
        int(np.prod(x.shape)) * x.dtype.itemsize for x in jax.tree.leaves(shapes))
    assert (reck["weights_bytes"] + reck["page_pool_bytes"]
            + reck["state_pool_bytes"] + reck["largest_temporaries_bytes"]
            + 2**30) <= reck["bytes_limit"]


# -- the harness's rehearsal of the new cell -------------------------------------

SMALL = {"prompt_tokens": {"median": 24, "min": 17, "max": 32},
         "max_tokens": {"median": 10, "min": 4, "max": 24}, "lead_in_s": 0.5,
         "clients": 16, "max_rps": 400.0, "stratum": 16,
         "warm_up_answer_tokens": 16}
SMALL_ENGINE = {"max_slots": 8, "num_blocks": 40, "max_blocks_per_seq": 4,
                "prefill_buckets": [32, 64]}


@pytest.mark.parametrize("trace", [False, True], ids=["untraced", "traced"])
def test_the_new_cell_rehearsed_on_the_cpu(trace):
    from benchmarks.harness import cell as harness
    from benchmarks.harness.registry import Registry

    out = io.StringIO()
    result = harness.run_cell(
        WORKLOAD, 2**31 + 5, 1.5, trace, out=out,
        rehearsal=harness.Rehearsal(preset="tiny-nemotron-h",
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
    assert {"ssm_decode_update_roofline", "decode_call_ms", "prefill_call_ms"} <= listed
    span_fed = {"busy_lanes_mean", "kv_blocks_peak_share", "compiles_in_window",
                "held_experts_hit_share", "held_assignments_share",
                "admit_token_use_share"}
    assert span_fed <= set(result["metrics"]), sorted(result["metrics"])
    value = lambda name: result["metrics"][name]["value"]  # noqa: E731
    assert 0.3 < value("held_experts_hit_share") <= 1.0
    assert 0.05 < value("held_assignments_share") < 0.6     # 8 of 32 held
    assert 0.3 < value("admit_token_use_share") <= 1.0


def test_compare_reference_rehearsed_on_the_cpu():
    """benchmarks/compare_reference.py end to end at the tiny preset (the
    preset's bfloat16, w8a8): one reference layer at a time on the engine's
    own input to it, over the prompt and the decode steps (a Mamba-2 layer's
    decode positions read the state pool); the 4-bit-activation control over
    the limit, the int8-cache control a no-op outside the attention layer."""
    from benchmarks import compare_reference

    out = compare_reference.compare(
        NAME, 2**31 + 9, traffic="triage-loops", prompts=1, decode=3,
        controls=1, preset="tiny-nemotron-h",
        engine={"max_slots": 2, "num_blocks": 64, "max_blocks_per_seq": 4,
                "prefill_buckets": [32, 64]},
        limits={"update_rel_l2_median": 0.1})
    (prompt,) = out["prompts"]
    assert [row["layer"] for row in prompt["layers"]] == list(range(6))
    assert all(row["prompt_median"] < 0.1 and row["decode_median"] < 0.1
               for row in prompt["layers"])
    assert len(prompt["head_rel_l2"]) == len(prompt["whole_model_rel_l2"]) == 4
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
    assert 0.104 * 1.5 < limits["update_rel_l2_median"] < 0.386 / 1.5
    assert NAME not in json.loads(
        (ROOT / "benchmarks/references/limits.json").read_text())


def test_the_parent_program_cannot_run_the_new_cell():
    """Without the preset the harness fails at once, on the name (what the
    driver's trial of the new cell on the parent commit must see)."""
    from benchmarks.harness import system

    config = json.loads((ROOT / "benchmarks/configs" / f"{NAME}.json").read_text())
    with pytest.raises(KeyError):
        system.model_config(dict(config, preset="no-such-preset"))
    assert config["preset"] in PRESETS
