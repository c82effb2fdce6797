"""(d) Each cell's code path end to end at a tiny preset on the CPU, with the
real traffic file scaled down; and the proof that a later PR adds a
configuration, a mix, a reader, a metric and a cell as new files plus one
``workloads`` entry, editing nothing."""

import io
import json
import shutil

import pytest

from benchmarks.harness import cell as harness
from benchmarks.harness.registry import BENCH_DIR, REPO_ROOT, Registry

RESULT_KEYS = {"correct", "attempted", "failed", "metrics", "device"}
DEVICE_KEYS = {"platform", "kind", "count", "memory_peak_bytes"}
# One prompt bucket (32) and short answers: 4 prefill + 4 decode + 2 probe
# programs to compile instead of 18.
SMALL = {"prompt_tokens": {"median": 24, "min": 17, "max": 32},
         "max_tokens": {"median": 10, "min": 4, "max": 24}, "lead_in_s": 0.5}
ENGINE = {"max_slots": 8, "num_blocks": 96, "max_blocks_per_seq": 6}
# answers this short can leave every lane with under 8 tokens to go: warm the whole ladder
LOOPS_SMALL = {"clients": 16, "max_rps": 400.0, "stratum": 16,
               "warm_up_answer_tokens": 16}


def rehearse(workload, preset, traffic, trace, registry=None):
    out = io.StringIO()
    result = harness.run_cell(
        workload, 2**31 + 3, 1.5, trace, registry=registry, out=out,
        rehearsal=harness.Rehearsal(preset=preset, engine=ENGINE,
                                    traffic={**SMALL, **traffic}))
    lines = out.getvalue().strip().splitlines()
    assert json.loads(lines[-1]) == result
    info = json.loads(lines[0])
    assert info["compiles_in_window"] == 0 and info["prefix_hits"] == 0
    assert not any(info["faults"].values())
    return result


def check_contract(result, registry, workload, trace):
    assert set(result) - {"breakdown", "modules_top"} == RESULT_KEYS
    assert set(result["device"]) >= DEVICE_KEYS
    assert result["correct"] is True and result["failed"] == 0
    assert result["attempted"] > 0
    cell = registry.cell(workload)
    declared = {m.name: m.unit for m in (cell.per_layer if trace else cell.end_to_end)}
    assert result["metrics"], "a run must report something"
    for name, entry in result["metrics"].items():
        assert entry == {"value": entry["value"], "unit": declared[name]}
        assert isinstance(entry["value"], float)
    if not trace:
        assert set(result["metrics"]) == set(declared)
        assert all(v["value"] > 0 for v in result["metrics"].values())


@pytest.mark.parametrize("trace", [False, True])
def test_the_cell_end_to_end(trace):
    workload = "qwen2-7b.loops-saturated"
    result = rehearse(workload, "tiny-qwen", LOOPS_SMALL, trace=trace)
    check_contract(result, Registry(), workload, trace=trace)
    if trace:  # the device metrics need a TPU's trace; the counted ones are there
        assert {"busy_lanes_mean", "kv_blocks_peak_share",
                "compiles_in_window"} <= set(result["metrics"])
        assert result["metrics"]["busy_lanes_mean"]["value"] > 4.0


def test_no_accelerator_is_a_failure_not_a_cpu_number(capsys):
    from benchmarks import run

    rc = run.main(["--workload", "qwen2-7b.loops-saturated", "--seed", "1",
                   "--seconds", "1", "--trace", "0"])
    assert rc == 2 and capsys.readouterr().out == ""


def test_benchmark_json_agrees_with_the_metric_files():
    reg = Registry()
    e2e = {m["name"] for m in reg.benchmark["end_to_end"]}
    for m in reg.benchmark["per_layer"]:
        spec = json.loads((BENCH_DIR / "metrics" / f"{m['name']}.json").read_text())
        for key in ("layer", "unit", "better", "source", "moves"):
            assert spec[key] == m[key], (m["name"], key)
        assert m["moves"] in e2e
        read, args = reg.metric_reader(m["name"])
        assert callable(read)
    for path in (BENCH_DIR / "configs").glob("*.json"):  # each agrees with the program's preset
        from benchmarks.harness import system
        assert system.model_config(json.loads(path.read_text())).act_quant
    for w in reg.benchmark["workloads"]:
        cell = reg.cell(w["name"])
        assert cell.config["reduced"] == [] and "setup_s" in {m.name for m in cell.end_to_end}
        assert callable(reg.generator(cell.traffic["kind"]).plan)


def test_a_later_pr_adds_files_and_one_entry_and_edits_nothing(tmp_path):
    """Throw-away configuration, mix, reader, metric and cell in a copy.  The
    cell is an open loop, so this is also the open-loop generator's rehearsal
    through the whole harness (no cell of BENCHMARK.json is one yet)."""
    bench = tmp_path / "benchmarks"
    shutil.copytree(BENCH_DIR, bench, ignore=shutil.ignore_patterns("__pycache__"))
    before = {p: p.read_bytes() for p in bench.rglob("*") if p.is_file()}
    # the Mistral file is no cell's yet; as a throw-away's base it is at least read
    config = json.loads((bench / "configs" / "mistral-7b-v03-w8a8.json").read_text())
    config["assumed"]["engine"].update(max_slots=4)
    (bench / "configs" / "throwaway.json").write_text(json.dumps(config))
    mix = json.loads((bench / "traffic" / "diagnose-steady.json").read_text())
    mix.update(rate_rps=20.0)
    (bench / "traffic" / "throwaway-mix.json").write_text(json.dumps(mix))
    (bench / "readers" / "sample_size.py").write_text(
        "def read(ctx, *, scale):\n    return float(len(ctx.window.sample)) * scale\n")
    layer = "load generator (benchmarks/generators)"
    (bench / "metrics" / "sample_size_x2.json").write_text(json.dumps(
        {"layer": layer, "unit": "count", "better": "higher",
         "source": "program_counter", "moves": "ttft_p50_ms",
         "reader": "sample_size", "args": {"scale": 2.0}}))
    spec = json.loads((REPO_ROOT / "BENCHMARK.json").read_text())
    spec["configs"].append({"name": "throwaway", "source": "x",
                            "file": "benchmarks/configs/throwaway.json",
                            "reduced": [], "why": "test"})
    spec["workloads"].append({"name": "throwaway.cell", "config": "throwaway",
                              "traffic": "throwaway-mix", "chips": 1, "why": "test"})
    spec["end_to_end"].append({"name": "ttft_p50_ms", "unit": "ms", "better": "lower",
                               "bound": 0.1, "source": "host_clock",
                               "workloads": ["throwaway.cell"]})
    spec["per_layer"].append({"name": "sample_size_x2", "unit": "count",
                              "better": "higher", "source": "program_counter",
                              "layer": layer, "moves": "ttft_p50_ms",
                              "workloads": ["throwaway.cell"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(spec))

    reg = Registry(root=tmp_path)
    cell = reg.cell("throwaway.cell")
    assert cell.config["assumed"]["engine"]["max_slots"] == 4
    assert {"setup_s", "ttft_p50_ms"} < {m.name for m in cell.end_to_end}
    assert "sample_size_x2" in {m.name for m in cell.per_layer}
    assert "ttft_p50_ms" not in {
        m.name for m in reg.cell("qwen2-7b.loops-saturated").end_to_end}
    result = rehearse("throwaway.cell", "tiny", {}, trace=False, registry=reg)
    check_contract(result, reg, "throwaway.cell", trace=False)
    assert result["attempted"] == 30  # rate x seconds, whatever the seed
    result = rehearse("throwaway.cell", "tiny", {}, trace=True, registry=reg)
    assert result["metrics"]["sample_size_x2"]["value"] == 2.0 * result["attempted"]
    assert {p: p.read_bytes() for p in before} == before  # nothing that was there changed
