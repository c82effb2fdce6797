"""Tier-1 guard on the yardstick (BENCHMARK.json, benchmarks/metrics,
benchmarks/readers): what the benchmark reads from the program must exist in
the program.  A renamed span, attribute or jitted function fails here,
on the CPU, and not in a chip run that prints a line without the metric.

Reads files only; the benchmark's own tests (``benchmarks/tests``) run its
code.
"""

import ast
import json
import pathlib
import re

import pytest

from k8s_llm_monitor_tpu.serving.engine import SPAN_CATALOG

ROOT = pathlib.Path(__file__).resolve().parents[1]
BENCH = ROOT / "benchmarks"
BENCHMARK = json.loads((ROOT / "BENCHMARK.json").read_text())
PER_LAYER = {m["name"]: m for m in BENCHMARK["per_layer"]}
METRIC_FILES = sorted(p.stem for p in (BENCH / "metrics").glob("*.json"))
PACKAGE = ROOT / "k8s_llm_monitor_tpu"


def metric_file(name: str) -> dict:
    return json.loads((BENCH / "metrics" / f"{name}.json").read_text())


@pytest.mark.parametrize("name", sorted(PER_LAYER))
def test_per_layer_entry_resolves_to_a_file_and_a_reader(name):
    entry, spec = PER_LAYER[name], metric_file(name)
    for field in ("unit", "better", "source", "layer", "moves"):
        assert entry[field] == spec[field], field
    reader = BENCH / "readers" / f"{spec['reader']}.py"
    tree = ast.parse(reader.read_text())
    (read,) = [n for n in tree.body
               if isinstance(n, ast.FunctionDef) and n.name == "read"]
    keywords = {a.arg for a in read.args.kwonlyargs}
    assert set(spec.get("args", {})) == keywords, (
        f"{name}: the metric file's args and {reader.name}'s read() differ")
    cells = {w["name"] for w in BENCHMARK["workloads"]}
    assert set(entry.get("workloads", cells)) <= cells
    assert entry["moves"] in {m["name"] for m in BENCHMARK["end_to_end"]}


def _terms(args):
    """(span name, attribute names) for every span a metric's args name:
    a term ``{"span", "where", "under", "value"}`` anywhere in them,
    ``span`` beside ``minus``, or a ``spans`` list."""
    def attrs_of(expr):
        if isinstance(expr, list):
            return {a for part in expr[1:] for a in attrs_of(part)}
        return set() if expr in ("count", "duration_s", None) else {expr}

    if isinstance(args, dict):
        if isinstance(args.get("span"), str):
            yield (args["span"],
                   set(args.get("where", {})) | attrs_of(args.get("value")))
            for child in args.get("minus", []) + [args.get("under")]:
                if child:
                    yield child, set()
        for child in args.get("spans", []):
            yield child, set()
        for value in args.values():
            yield from _terms(value)
    elif isinstance(args, list):
        for value in args:
            yield from _terms(value)


@pytest.mark.parametrize("name", METRIC_FILES)
def test_what_a_metric_reads_is_in_the_program(name):
    spec = metric_file(name)
    args = spec.get("args", {})
    for span, attrs in _terms(args):
        assert span in SPAN_CATALOG, (
            f"{name} reads span {span!r}: not in serving/engine.py "
            f"SPAN_CATALOG")
        assert attrs <= set(SPAN_CATALOG[span]), (
            f"{name} reads {sorted(attrs - set(SPAN_CATALOG[span]))} of "
            f"{span}: not among its catalogued attributes")
    if spec["reader"] == "module_median_ms":
        # An XLA module is named jit_<function>; the functions the engine
        # jits are defined in serving/engine.py.
        engine_src = (PACKAGE / "serving/engine.py").read_text()
        modules = {f"jit_{fn}" for fn in re.findall(r"def (\w+)\(", engine_src)}
        assert any(re.search(args["pattern"], m) for m in modules), (
            f"{name}: no jitted function of the engine matches "
            f"{args['pattern']!r}")
    if spec["reader"] == "span_percentile":
        assert args["span"] in SPAN_CATALOG


def test_the_catalog_is_what_the_document_lists():
    """docs/observability.md's span tables and SPAN_CATALOG name the same
    engine and service spans."""
    text = (ROOT / "docs" / "observability.md").read_text()
    section = text.split("## Span catalog")[1].split("## Context propagation")[0]
    documented = set(re.findall(r"`((?:engine|service|xla)\.[a-z_.]+)`",
                                section))
    assert documented == set(SPAN_CATALOG)


def test_new_entries_came_last_and_the_old_ones_stand():
    names = [m["name"] for m in BENCHMARK["per_layer"]]
    assert names[:5] == ["busy_lanes_mean", "kv_blocks_peak_share",
                         "prefill_call_ms", "decode_call_ms",
                         "compiles_in_window"]
    assert len(names) == len(set(names))


# The files that described or ran the pre-chip script (deleted in PR 28) as a
# way to measure: the build, CI, and each document that named it.
ONCE_NAMED_THE_FORK = ["Makefile", ".github/workflows/ci.yml", "README.md"] + [
    f"docs/{name}.md" for name in (
        "development-guide", "devtools", "diagnosis", "fleet", "observability",
        "remediation", "resilience", "serving")]


@pytest.mark.parametrize("path", ONCE_NAMED_THE_FORK)
def test_one_yardstick_no_second_benchmark_comes_back(path):
    """``benchmarks/`` (BENCHMARK.json's command) is the only code that
    measures speed: no file of the build, CI or the documents names the old
    script, one of its environment variables or one of its make targets."""
    text = (ROOT / path).read_text()
    for pattern in (r"bench\.py", r"BENCH_[A-Z]", r"make bench-"):
        assert not re.search(pattern, text), (path, pattern)
    assert not (ROOT / "bench.py").exists()
