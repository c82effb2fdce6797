"""graftcheck CLI: the single entry point for all five analysis passes.

    python -m k8s_llm_monitor_tpu.devtools.graftcheck [paths...]
        AST lint over the given paths (default: the package itself).
        Exit 0 = clean, 1 = findings.

    python -m k8s_llm_monitor_tpu.devtools.graftcheck --dataflow
        Additionally run the whole-program dataflow pass (call graph +
        taint): blocking-in-hot-path, recompile-hazard,
        lock-order-static.  Analyzes the package as one program, so it
        ignores positional ``paths``.

    python -m k8s_llm_monitor_tpu.devtools.graftcheck --contracts
        Additionally run the contract-drift checkers (routes, metrics,
        env keys) against README.md, docs/ and the Makefile.

    python -m k8s_llm_monitor_tpu.devtools.graftcheck --trace
        Additionally run the trace-time guards (compile-count stability,
        forbidden host-callback ops, donation rebinding) on CPU.  Slower
        (it jit-compiles a tiny engine), so `make lint` runs the static
        passes; the trace pass is enforced by tests/test_graftcheck.py
        in tier-1 and available here for ad-hoc use.

    --json emits one machine-readable document for CI annotation.
    --list-rules documents every rule and its name (the token used in
    `# graftcheck: disable=...` suppressions).
"""

from __future__ import annotations

import argparse
import json
import os
import sys
from pathlib import Path


def _package_root() -> Path:
    return Path(__file__).resolve().parents[1]


def main(argv: list[str] | None = None) -> int:
    parser = argparse.ArgumentParser(
        prog="graftcheck",
        description="JAX-aware static analysis, contract-drift checks + "
                    "trace-time gates (docs/devtools.md)")
    parser.add_argument("paths", nargs="*", type=Path,
                        help="files/dirs to lint (default: the package)")
    parser.add_argument("--json", action="store_true", dest="as_json",
                        help="machine-readable output")
    parser.add_argument("--dataflow", action="store_true",
                        help="also run the interprocedural dataflow rules "
                             "(call graph over the whole package)")
    parser.add_argument("--contracts", action="store_true",
                        help="also run the contract-drift checkers "
                             "(routes/metrics/env vs README, docs/, "
                             "Makefile)")
    parser.add_argument("--trace", action="store_true",
                        help="also run the trace-time guards (jit-compiles "
                             "a tiny engine on CPU; slower)")
    parser.add_argument("--trace-paths",
                        default="gather,fused,mesh,quant,flash_prefill",
                        help="comma-separated engine paths for --trace "
                             "(default: gather,fused,mesh,quant,"
                             "flash_prefill)")
    parser.add_argument("--list-rules", action="store_true",
                        help="print every rule and exit")
    args = parser.parse_args(argv)

    # Pin CPU before anything imports jax: the lint itself imports the
    # package (for FAULT_POINTS) and --trace builds an engine; neither
    # must grab a real TPU out from under a serving process.
    os.environ.setdefault("JAX_PLATFORMS", "cpu")

    from k8s_llm_monitor_tpu.devtools import astlint

    if args.list_rules:
        from k8s_llm_monitor_tpu.devtools import contracts, dataflow

        for rule in astlint.default_rules():
            print(f"{rule.name}: {rule.description}")
        print("blocking-in-hot-path: blocking call reachable from a "
              "serving hot entry (--dataflow)")
        print("recompile-hazard: host read / device sync / mutable "
              "capture in jit-traced flow (--dataflow)")
        print("lock-order-static: static lock acquisition-order cycle "
              "(--dataflow)")
        print("route-contract: routes registered vs documented, both "
              "directions (--contracts)")
        print("metrics-contract: exporter families vs docs inventory "
              "(--contracts)")
        print("env-contract: env reads vs ENV_KEYS registry vs docs "
              "(--contracts)")
        assert set(dataflow.DATAFLOW_RULE_NAMES) <= {
            "blocking-in-hot-path", "recompile-hazard",
            "lock-order-static"}
        assert set(contracts.CONTRACT_RULE_NAMES) <= {
            "route-contract", "metrics-contract", "env-contract"}
        return 0

    paths = args.paths or [_package_root()]
    findings = astlint.lint_paths(paths)

    dataflow_findings = None
    if args.dataflow:
        from k8s_llm_monitor_tpu.devtools import dataflow

        dataflow_findings = dataflow.analyze_paths([_package_root()])

    contract_findings = None
    if args.contracts:
        from k8s_llm_monitor_tpu.devtools import contracts

        contract_findings = contracts.run_contracts(
            _package_root().parent)

    trace_report = None
    if args.trace:
        from k8s_llm_monitor_tpu.devtools import traceguard

        traceguard.force_cpu()
        trace_report = traceguard.run_traceguard(
            tuple(p.strip() for p in args.trace_paths.split(",")
                  if p.strip()))

    ok = (not findings
          and not dataflow_findings
          and not contract_findings
          and (trace_report is None or trace_report["ok"]))
    if args.as_json:
        doc = {
            "astlint": {
                "findings": [f.as_dict() for f in findings],
                "count": len(findings),
            },
            "dataflow": None if dataflow_findings is None else {
                "findings": [f.as_dict() for f in dataflow_findings],
                "count": len(dataflow_findings),
            },
            "contracts": None if contract_findings is None else {
                "findings": [f.as_dict() for f in contract_findings],
                "count": len(contract_findings),
            },
            "traceguard": trace_report,
            "ok": ok,
        }
        print(json.dumps(doc, indent=2))
    else:
        print(astlint.render(findings))
        if dataflow_findings is not None:
            from k8s_llm_monitor_tpu.devtools import dataflow

            print(dataflow.render(dataflow_findings))
        if contract_findings is not None:
            from k8s_llm_monitor_tpu.devtools import contracts

            print(contracts.render(contract_findings))
        if trace_report is not None:
            for path, rep in trace_report["paths"].items():
                status = "ok" if rep["ok"] else "FAIL"
                print(f"graftcheck traceguard[{path}]: {status} "
                      f"(warm compiles={rep['warm_compiles']}, "
                      f"repeat compiles={rep['repeat_compiles']}, "
                      f"forbidden ops="
                      f"{sum(map(len, rep['forbidden'].values()))}, "
                      f"donation rebound="
                      f"{rep['donated_pages_rebound'] and rep['donated_tokens_rebound'] and rep['donated_scales_rebound']})")
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
