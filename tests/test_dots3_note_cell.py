"""The dots3_note cell's files and the harness's rehearsal of it at the tiny
preset (as ``tests/test_nemotron_h_cell.py`` does for the cell before it):
the configuration file against the catalog row and the program's preset, the
cell traced and untraced, ``benchmarks/compare_reference.py`` and
``benchmarks/compare_selection.py`` end to end, the counts behind the new
roofline shares.
"""

import io
import json
import pathlib

import jax
import numpy as np
import pytest

from benchmarks.references import dots3_note as ref
from k8s_llm_monitor_tpu.models.config import PRESETS
from k8s_llm_monitor_tpu.serving.engine import SPAN_CATALOG, EngineConfig

ROOT = pathlib.Path(__file__).resolve().parents[1]
NAME = "dots3-note-prev-w8a8"
WORKLOAD = "dots3-note-prev.casefile-loops"
LAYER_TYPES = (["full_attention"] * 2
               + (["sliding_attention"] * 3 + ["full_attention"]) * 11)


def _config():
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    (entry,) = [c for c in spec["configs"] if c["name"] == NAME]
    return entry, json.loads((ROOT / entry["file"]).read_text())


def test_the_configuration_file_holds_the_published_widths():
    from benchmarks.harness import system

    entry, config = _config()
    catalog = {
        "hidden_size": 5120, "num_attention_heads": 128,
        "num_key_value_heads": 128, "q_lora_rank": 1024, "kv_lora_rank": 512,
        "qk_nope_head_dim": 128, "qk_rope_head_dim": 64, "v_head_dim": 128,
        "rope_theta": 80000000, "swa_num_attention_heads": 64,
        "swa_num_key_value_heads": 64, "swa_q_lora_rank": 1024,
        "swa_kv_lora_rank": 1024, "swa_qk_nope_head_dim": 192,
        "swa_qk_rope_head_dim": 64, "swa_v_head_dim": 128,
        "swa_rope_theta": 50000, "sliding_window_size": 513,
        "index_n_heads": 64, "index_head_dim": 128, "index_topk": 2048,
        "intermediate_size": 13824, "moe_intermediate_size": 1536,
        "n_shared_experts": 1, "num_experts_per_tok": 8,
        "first_k_dense_replace": 1, "routed_scaling_factor": 1,
        "scoring_func": "sigmoid", "topk_method": "noaux_tc",
        "norm_topk_prob": True, "attention_gate_type": "headwise",
        "swa_attention_gate_type": "headwise",
        "apply_mla_qkv_lora_rescale": True, "rms_norm_eps": 1e-05,
        "max_position_embeddings": 524288, "layer_types": LAYER_TYPES,
        "tie_word_embeddings": False, "model_type": "dots3_note"}
    assert {k: config[k] for k in catalog} == catalog
    assert entry["source"] == config["source"] == (
        "https://huggingface.co/dots-studio/dots3-note-prev/blob/main/config.json")
    assert entry["reduced"] == config["reduced"] == [
        "num_hidden_layers", "n_routed_experts", "vocab_size"]
    assert config["published"] == {"num_hidden_layers": 46,
                                   "n_routed_experts": 256,
                                   "vocab_size": 152064}
    assert (config["num_hidden_layers"], config["n_routed_experts"],
            config["vocab_size"]) == (5, 64, 38016)
    assert "chips that share a layer: 4" in config["deployment"]
    assumed = config["assumed"]
    assert "INFERENCE 1" in assumed["apply_mla_qkv_lora_rescale"]
    assert "INFERENCE 2" in assumed["sliding_window_size"]
    cfg = system.model_config(config)
    assert cfg.act_quant and cfg.rms_norm_eps == config["rms_norm_eps"]
    # The layers run are the first five of the published layer_types: the
    # leading dense layer and one whole period (full, sliding x 3).
    assert list(cfg.layer_types) == LAYER_TYPES[:5]
    assert [cfg.layer_spec(i).mlp for i in range(5)] == [
        "dense"] + ["shared+routed"] * 4
    assert (cfg.num_experts, cfg.experts_held, cfg.expert_start) == (256, 64, 0)
    mine = ref.config_of(cfg)
    assert mine == {k: config[k] for k in mine}
    # The pools the file reckons are the pools the engine would build.
    eng, reck = assumed["engine"], assumed["pool_reckoning"]
    assert eng["num_blocks"] == eng["max_slots"] * eng["max_blocks_per_seq"] + 1
    ec = EngineConfig(**eng)
    assert ec.prefill_buckets[-1] >= eng["max_blocks_per_seq"] * eng["block_size"]
    # A round is one packed call of at most two largest buckets' rung: the
    # largest program the pool's reckoning counts.
    assert 2 * ec.prefill_buckets[-1] == 8 * ec.prefill_buckets[0] == 18_432
    assert reck["token_bytes"] == cfg.kv_token_bytes() == 3072
    assert reck["page_pool_bytes"] == eng["num_blocks"] * eng["block_size"] * 3072
    # The window layers' store is bounded by the window: a ring a lane,
    # sixteen times smaller than pages of every cached token would be.
    assert reck["window_lane_bytes"] == cfg.window_lane_bytes(16) == 3 * 528 * 2304
    null = 3 * 16 * 1152 * 2
    assert reck["window_store_bytes"] == eng["max_slots"] * reck["window_lane_bytes"] + null
    assert reck["window_store_unbounded_bytes"] == (
        3 * 2304 * eng["block_size"] * eng["num_blocks"])
    assert reck["window_store_unbounded_bytes"] > 15 * reck["window_store_bytes"]
    from k8s_llm_monitor_tpu.models import llama
    from k8s_llm_monitor_tpu.utils.quantize import init_params_quantized

    pool = jax.eval_shape(lambda: llama.init_kv_pages(
        cfg, eng["num_blocks"], eng["block_size"], state_lanes=eng["max_slots"]))
    size = lambda xs: sum(int(np.prod(x.shape)) * x.dtype.itemsize for x in xs)  # noqa: E731
    assert size(pool.k) + size(pool.idx) == reck["page_pool_bytes"]
    assert size(pool.win) == reck["window_store_bytes"]
    shapes = jax.eval_shape(lambda k: init_params_quantized(k, cfg),
                            jax.random.PRNGKey(0))
    assert reck["weights_bytes"] == size(jax.tree.leaves(shapes))
    used = (reck["weights_bytes"] + reck["page_pool_bytes"]
            + reck["window_store_bytes"] + reck["largest_temporaries_bytes"])
    assert used + 2**30 + reck["left_after_the_rule_bytes"] == reck["bytes_limit"]
    assert reck["left_after_the_rule_bytes"] >= 0
    # Resident (weights and pools alone) well over a quarter of the chip.
    assert (used - reck["largest_temporaries_bytes"]) / 16e9 > 0.5


def test_the_traffic_is_the_issues():
    mix = json.loads((ROOT / "benchmarks/traffic/casefile-loops.json").read_text())
    assert {k: mix[k] for k in ("kind", "clients", "lead_in_s", "stratum",
                                "max_rps", "warm_up_answer_tokens")} == {
        "kind": "closed_loop", "clients": 128, "lead_in_s": 5.0, "stratum": 64,
        "max_rps": 40.0, "warm_up_answer_tokens": 9}
    assert mix["prompt_tokens"] == {"distribution": "lognormal", "median": 3072,
                                    "sigma": 0.5, "min": 2304, "max": 8192}
    assert mix["max_tokens"] == {"distribution": "lognormal", "median": 96,
                                 "sigma": 0.6, "min": 32, "max": 256}
    # Every prompt is longer than index_topk: every decode step selects.
    assert mix["prompt_tokens"]["min"] > _config()[1]["index_topk"]


# -- the harness's rehearsal of the new cell -------------------------------------

SMALL = {"prompt_tokens": {"median": 40, "min": 24, "max": 80},
         "max_tokens": {"median": 10, "min": 4, "max": 24}, "lead_in_s": 0.5,
         "clients": 16, "max_rps": 400.0, "stratum": 16,
         "warm_up_answer_tokens": 16}
SMALL_ENGINE = {"max_slots": 8, "num_blocks": 8 * 14 + 1, "block_size": 8,
                "max_blocks_per_seq": 14, "prefill_buckets": [56, 112],
                "max_prefills_per_step": 4}


@pytest.mark.parametrize("trace", [False, True], ids=["untraced", "traced"])
def test_the_new_cell_rehearsed_on_the_cpu(trace):
    from benchmarks.harness import cell as harness
    from benchmarks.harness.registry import Registry

    out = io.StringIO()
    result = harness.run_cell(
        WORKLOAD, 2**31 + 5, 1.5, trace, out=out,
        rehearsal=harness.Rehearsal(preset="tiny-dots3-note",
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
    # (module medians, the kernels' roofline shares) need a TPU's trace.
    listed = {m.name for m in cell.per_layer}
    assert {"sparse_latent_decode_attention_roofline",
            "window_latent_decode_attention_roofline",
            "sparse_latent_prefill_attention_roofline", "decode_call_ms",
            "prefill_call_ms"} <= listed
    span_fed = {"busy_lanes_mean", "kv_blocks_peak_share", "compiles_in_window",
                "index_selected_share"}
    assert span_fed <= set(result["metrics"]), sorted(result["metrics"])
    # Contexts of 24-104 tokens against a top-k of 12: well under a half.
    assert 0.1 < result["metrics"]["index_selected_share"]["value"] < 0.5


REHEARSAL = dict(traffic="casefile-loops", prompts=1, decode=3, controls=1,
                 preset="tiny-dots3-note",
                 engine={"max_slots": 2, "num_blocks": 64, "block_size": 8,
                         "max_blocks_per_seq": 8, "prefill_buckets": [32, 64]})


def test_compare_reference_rehearsed_on_the_cpu():
    """benchmarks/compare_reference.py end to end at the tiny preset (the
    preset's bfloat16, w8a8), the reference choosing its own keys: one
    reference layer at a time on the engine's own input to it, over the
    prompt and the decode steps; the 4-bit-activation control over the
    limit."""
    from benchmarks import compare_reference

    out = compare_reference.compare(NAME, 2**31 + 9,
                                    limits={"update_rel_l2_median": 0.1},
                                    **REHEARSAL)
    (prompt,) = out["prompts"]
    assert [row["layer"] for row in prompt["layers"]] == list(range(5))
    assert all(row["prompt_median"] < 0.1 and row["decode_median"] < 0.1
               for row in prompt["layers"])
    assert len(prompt["head_rel_l2"]) == len(prompt["whole_model_rel_l2"]) == 4
    assert prompt["control_caught"] and out["ok"]


def test_compare_selection_rehearsed_on_the_cpu():
    """benchmarks/compare_selection.py end to end at the tiny preset: the
    reference's full layers on the selection the engine's programs made
    (``score_logits(selection=True)``), their index scores and the overlap
    of the two selections beside it, and the controls — 4-bit activations
    (update, index scores and overlap) and the selection left out — each
    on the wrong side of its limit."""
    from benchmarks import compare_selection

    limits = {"update_rel_l2_median": 0.1, "index_scores_rel_l2_median": 0.05,
              "selection_overlap_least": 0.9}   # of 12 keys: one may differ
    out = compare_selection.compare(NAME, 2**31 + 9, limits=limits, **REHEARSAL)
    (prompt,) = out["prompts"]
    assert prompt["tokens"] > PRESETS["tiny-dots3-note"].index_topk
    assert [row["layer"] for row in prompt["layers"]] == list(range(5))
    assert all(row["prompt_median"] < 0.1 and row["decode_median"] < 0.1
               for row in prompt["layers"])
    full = [row for row in prompt["layers"] if "selection_overlap_least" in row]
    assert [row["layer"] for row in full] == [0, 1]
    for row in full:
        assert row["selecting_queries"] == prompt["tokens"] + 3 - 12
        assert row["index_scores_rel_l2_median"] < 0.05
        assert 0.9 <= row["selection_overlap_least"] <= row[
            "selection_overlap_median"] <= 1.0
        # On the CPU the programs' selection IS the XLA forms'.
        assert row["xla_form_differs"]["decode_max"] <= 2
    low = {r["layer"]: r for r in prompt["control_act_int4"]}
    assert sorted(low) == list(range(5))
    assert all(r["prompt_median"] > 0.1 for r in low.values())
    for li in (0, 1):
        assert low[li]["index_scores_rel_l2_median"] > 0.05
        assert low[li]["selection_overlap_least"] < 0.9
    sel = {r["layer"]: r["decode_median"] for r in prompt["control_select_all"]}
    assert sorted(sel) == [0, 1] and min(sel.values()) > 0.1
    assert prompt["controls_caught"] and out["ok"]


def test_the_limits_lie_between_their_readings():
    """The comparison's limits for this configuration, in a file of its own
    beside ``limits.json`` (which only a ``benchmark`` PR may edit), each
    between the engine's reading on the chip and what must miss it."""
    limits = json.loads((ROOT / "benchmarks/references" /
                         f"limits.{NAME}.json").read_text())[NAME]
    r = limits["readings_numbers"]
    assert (r["update_engine_largest"] * 1.5 < limits["update_rel_l2_median"]
            < min(r["update_act_int4_smallest"],
                  r["update_select_all_smallest"]) / 1.5)
    assert (r["index_scores_engine_largest_median"] * 1.5
            < limits["index_scores_rel_l2_median"]
            < r["index_scores_act_int4_smallest_median"] / 1.5)
    # An overlap is read by its distance from 1 (the keys that differ); its
    # room is thin (the file says why a fixed number cannot have more).
    assert ((1 - r["selection_overlap_engine_least"]) * 1.4
            < 1 - limits["selection_overlap_least"]
            < (1 - r["selection_overlap_act_int4_largest_least"]) / 1.4)
    assert NAME not in json.loads(
        (ROOT / "benchmarks/references/limits.json").read_text())


@pytest.mark.parametrize("config, traffic, bound", [
    ("nemotron3-super-120b-a12b-w8a8", "triage-loops", 12_288),
    (NAME, "casefile-loops", 18_432)])
def test_the_round_bound_by_cell(config, traffic, bound):
    """A lane-state description's round is one packed call of at most the
    rung of two largest buckets.  The accepted nemotron cell never reaches
    its bound — ``max_prefills_per_step`` of its longest prompts fill it
    exactly — so its admission is what it was; this cell's rounds do."""
    import types

    from k8s_llm_monitor_tpu.serving.engine import InferenceEngine

    ec = EngineConfig(**json.loads(
        (ROOT / f"benchmarks/configs/{config}.json").read_text())["assumed"]["engine"])
    longest = json.loads((ROOT / f"benchmarks/traffic/{traffic}.json"
                          ).read_text())["prompt_tokens"]["max"]
    rung = InferenceEngine._token_rung(types.SimpleNamespace(ecfg=ec),
                                       2 * ec.prefill_buckets[-1])
    assert rung == bound
    assert (ec.max_prefills_per_step * longest <= bound) == (config != NAME)


def test_sparse_counts_read_catalogued_attributes():
    from benchmarks.harness import sparse_counts

    _, config = _config()
    for name, reads in sparse_counts.READS.items():
        assert set(reads) <= set(SPAN_CATALOG["engine.call"]), name
        assert getattr(sparse_counts, name)(config, {}) is None
        assert name in sparse_counts.PEAK_OF
    # One lane at 4,096 cached tokens, one step: both full layers score every
    # index key (256 B) and need 2,048 rows (1,152 B); three sliding layers
    # read 513 rows (2,176 B).
    attrs = {"index_tokens": 2 * 4096, "sel_tokens": 2 * 2048,
             "window_tokens": 3 * 513}
    ops_, nbytes = sparse_counts.sparse_latent_decode_attention(config, attrs)
    assert nbytes == 2 * (4096 * 256 + 2048 * 1152)
    assert ops_ == 2 * (4096 * 64 * 128 * 2 + 2048 * 128 * 2 * (576 + 512))
    ops_, nbytes = sparse_counts.window_latent_decode_attention(config, attrs)
    assert nbytes == 3 * 513 * 2176 and ops_ == 3 * 513 * 64 * 2 * (1088 + 1024)
    # Two prompts of 3,000 tokens: per query t, (t + 1) index pairs and
    # min(t + 1, 2,048) attended pairs, in both full layers.
    ops_, _ = sparse_counts.sparse_latent_prefill_attention(
        config, {"real_tokens": 6000, "prompts": 2})
    scored = sum(t + 1 for t in range(3000))
    attended = sum(min(t + 1, 2048) for t in range(3000))
    assert ops_ == 2 * 2 * (scored * 64 * 128 * 2 + attended * 128 * 2 * 320)
    short, _ = sparse_counts.sparse_latent_prefill_attention(
        config, {"real_tokens": 1000, "prompts": 1})
    assert short == 2 * 500500 * (64 * 128 * 2 + 128 * 2 * 320)


def test_the_new_metrics_name_what_the_program_has():
    """Kernel names and module names the new metric files match exist in the
    program (tests/test_benchmark.py holds every metric file to the span
    catalog; this holds the new kernels' names)."""
    import inspect
    import re

    from k8s_llm_monitor_tpu.ops import pallas_attention as pa

    source = inspect.getsource(pa)
    from k8s_llm_monitor_tpu.models import llama

    names = set(re.findall(r'"((?:sparse|window)_latent_[a-z_]+)"',
                           source + inspect.getsource(llama)))
    assert names == {"sparse_latent_decode_attention",
                     "sparse_latent_decode_index_scores",
                     "window_latent_decode_attention",
                     "sparse_latent_prefill_attention",
                     "sparse_latent_prefill_index_scores",
                     "sparse_latent_prefill_select",
                     "window_latent_prefill_attention"}
    for metric in ("sparse_latent_decode_attention_roofline",
                   "window_latent_decode_attention_roofline",
                   "sparse_latent_prefill_attention_roofline"):
        spec = json.loads((ROOT / "benchmarks/metrics" / f"{metric}.json").read_text())
        rx = re.compile(spec["args"]["kernel"])
        assert any(rx.search(n) for n in names), metric
        # ... and none of them is read by the kanana cell's metrics.
    for old in ("latent_decode_attention_roofline",
                "latent_prefill_attention_roofline"):
        spec = json.loads((ROOT / "benchmarks/metrics" / f"{old}.json").read_text())
        assert not any(re.search(spec["args"]["kernel"], n) for n in names)


def test_the_parent_program_cannot_run_the_new_cell():
    """Without the preset the harness fails at once, on the name (what the
    driver's trial of the new cell on the parent commit must see)."""
    from benchmarks.harness import system

    _, config = _config()
    with pytest.raises(KeyError):
        system.model_config(dict(config, preset="no-such-preset"))
    assert config["preset"] in PRESETS
