"""Contract-drift suite (graftcheck --contracts).

Each checker runs against deliberately drifted fixture sources/docs to
prove both directions fire, against reconciled fixtures to prove it goes
quiet, and finally against the live repo — the assertion that every
route, metric family and env key the docs promise actually
exists (and vice versa), with zero suppressions.
"""

from __future__ import annotations

import textwrap
from pathlib import Path

from k8s_llm_monitor_tpu.devtools import contracts
from k8s_llm_monitor_tpu.devtools.contracts import (
    _norm_route, check_env, check_metrics, check_routes, derived_env_keys,
    extract_agent_routes, extract_exporter_metrics,
    extract_server_routes, run_contracts)

REPO_ROOT = Path(__file__).resolve().parents[1]


def dedent(s: str) -> str:
    return textwrap.dedent(s)


# -- fixture sources ---------------------------------------------------------

SERVER_SRC = dedent("""
    class Handler:
        _ROUTES: dict = {
            ("GET", "/health"): "h_health",
            ("POST", "/api/v1/query"): "h_query",
            ("GET", "/api/v1/metrics/cluster"): "h_cluster",
        }

        def _dispatch(self, method, path):
            if path.startswith("/api/v1/metrics/nodes/"):
                return "h_node"
            if path.startswith("/api/v1/remediations/"):
                if method != "POST":
                    return "err405"
                return "h_remediation_action"
    """)

AGENT_SRC = dedent("""
    class AgentHandler:
        def do_GET(self):
            routes = {
                "/health": self.h_health,
                "/api/v1/state": self.h_state,
            }

        def do_POST(self):
            if self.path.startswith("/api/v1/command/"):
                command = self.path.rsplit("/", 1)[-1]
                if command == "arm":
                    pass
                elif command == "land":
                    pass
    """)

GOOD_ROUTE_DOCS = {
    "README.md": dedent("""
        - `GET /health`
        - `POST /api/v1/query`
        - `GET /api/v1/metrics/cluster`
        - `GET /api/v1/metrics/nodes/{name}`
        - `POST /api/v1/remediations/{id}/approve`
        - GET :9090/health
        - GET :9090/api/v1/state
        - POST :9090/api/v1/command/{arm,land}
        """),
}


# -- route normalization -----------------------------------------------------


def test_norm_route_wildcards_and_alternation():
    assert _norm_route("/api/v1/metrics/nodes/{name}") == \
        ["/api/v1/metrics/nodes/*"]
    assert _norm_route("/api/v1/command/{arm,land}") == \
        ["/api/v1/command/arm", "/api/v1/command/land"]
    assert _norm_route("/api/v1/trace/<id>?fmt=json") == ["/api/v1/trace/*"]


def test_extract_server_routes_reads_annassign_table_and_prefixes():
    routes = extract_server_routes(SERVER_SRC)
    assert ("POST", "/api/v1/query") in routes
    assert ("GET", "/api/v1/metrics/nodes/*") in routes  # _dispatch prefix
    # a prefix route's inline `method != "POST"` guard sets its method
    assert ("POST", "/api/v1/remediations/*") in routes
    assert ("GET", "/api/v1/remediations/*") not in routes


def test_extract_agent_routes_reads_get_dict_and_post_commands():
    routes = extract_agent_routes(AGENT_SRC)
    assert ("GET", "/api/v1/state") in routes
    assert ("POST", "/api/v1/command/arm") in routes
    assert ("POST", "/api/v1/command/*") in routes


# -- route-contract ----------------------------------------------------------


def test_routes_reconciled_fixture_is_clean():
    assert check_routes(SERVER_SRC, AGENT_SRC, GOOD_ROUTE_DOCS) == []


def test_routes_flags_documented_but_unregistered():
    docs = {"README.md":
            GOOD_ROUTE_DOCS["README.md"] + "- `POST /api/v1/export`\n"}
    findings = check_routes(SERVER_SRC, AGENT_SRC, docs)
    assert len(findings) == 1
    assert findings[0].rule == "route-contract"
    assert "POST /api/v1/export" in findings[0].message
    assert "not registered" in findings[0].message


def test_routes_flags_registered_but_undocumented():
    docs = {"README.md": GOOD_ROUTE_DOCS["README.md"].replace(
        "- `POST /api/v1/query`\n", "")}
    findings = check_routes(SERVER_SRC, AGENT_SRC, docs)
    assert len(findings) == 1
    assert "POST /api/v1/query" in findings[0].message
    assert "not documented" in findings[0].message


def test_routes_attributes_port_9090_to_agent():
    # the same path exists only on the agent; a bare doc mention without
    # the :9090 marker claims it on the monitor server and must fail
    docs = {"README.md": GOOD_ROUTE_DOCS["README.md"].replace(
        "GET :9090/api/v1/state", "`GET /api/v1/state`")}
    findings = check_routes(SERVER_SRC, AGENT_SRC, docs)
    assert any("'GET /api/v1/state' (monitor server)" in f.message
               for f in findings)


# -- metrics-contract --------------------------------------------------------

EXPORTER_SRC = dedent("""
    _PREFIX = "k8s_llm_monitor"

    def export(w, hist):
        w.metric("engine_queue_depth", "gauge", "depth", [(1.0, {})])
        w.histogram("request_ttft_seconds", "ttft", hist)
        w.lines.append(f"{_PREFIX}_request_e2e_seconds_sum 1.0")
        hists = (
            ("request_queue_wait_seconds", "queue wait before admission", hist),
        )
        for name, help_, h in hists:
            w.histogram(name, help_, h)
    """)

GOOD_OBS = dedent("""
    | metric | type | meaning |
    |---|---|---|
    | `k8s_llm_monitor_engine_queue_depth` | gauge | queue depth |
    | `k8s_llm_monitor_request_ttft_seconds` | histogram | ttft |
    | `k8s_llm_monitor_request_e2e_seconds` | histogram | end to end |
    | `k8s_llm_monitor_request_queue_wait_seconds` | histogram | queue wait |
    """)

def check_m(obs=GOOD_OBS, extra_docs=None):
    docs = {"docs/observability.md": obs}
    docs.update(extra_docs or {})
    return check_metrics(EXPORTER_SRC, obs, docs)


def test_exporter_extraction_covers_all_emission_styles():
    fams = set(extract_exporter_metrics(EXPORTER_SRC))
    # literal metric(), literal histogram(), manual f-string sample
    # (collapsed to the family), and the tuple-table rows
    assert fams == {"engine_queue_depth", "request_ttft_seconds",
                    "request_e2e_seconds", "request_queue_wait_seconds"}


def test_metrics_reconciled_fixture_is_clean():
    assert check_m() == []


def test_metrics_flags_emitted_but_not_inventoried():
    obs = GOOD_OBS.replace(
        "| `k8s_llm_monitor_request_queue_wait_seconds` | histogram | queue wait |\n",
        "")
    findings = check_m(obs=obs)
    assert len(findings) == 1
    assert "request_queue_wait_seconds" in findings[0].message
    assert "does not list it" in findings[0].message


def test_metrics_flags_inventoried_but_never_emitted():
    obs = GOOD_OBS + \
        "| `k8s_llm_monitor_phantom_total` | counter | ghost |\n"
    findings = check_m(obs=obs)
    assert len(findings) >= 1
    assert any("phantom_total" in f.message
               and "never emits" in f.message for f in findings)


def test_metrics_flags_stale_doc_mention():
    # the real drift this rule caught: a doc citing a pre-rename family
    findings = check_m(extra_docs={"docs/usage.md": dedent("""
        Watch `k8s_llm_monitor_ttft_seconds_bucket` for tail latency.
        """)})
    assert len(findings) == 1
    assert findings[0].path == "docs/usage.md"
    assert "never emits" in findings[0].message


# -- env-contract ------------------------------------------------------------

CONFIG_SRC = dedent("""
    ENV_KEYS = {
        "K8SLLM_KV_DTYPE": "EngineConfig.kv_dtype",
        "K8SLLM_FAULTS": "runtime:resilience/faults.py",
    }

    class FleetConfig:
        role: str = "combined"

    class Config:
        fleet: FleetConfig = None
    """)

PY_SOURCES = {
    "k8s_llm_monitor_tpu/serving/engine.py": dedent("""
        import os

        class EngineConfig:
            kv_dtype: str = "bf16"

        def load():
            return os.environ.get("K8SLLM_KV_DTYPE", "bf16")
        """),
    "k8s_llm_monitor_tpu/resilience/faults.py": dedent("""
        import os

        spec = os.getenv("K8SLLM_FAULTS", "")
        """),
}

ENV_DOCS = {"README.md":
            "`K8SLLM_KV_DTYPE` picks the dtype; `K8SLLM_FAULTS` arms "
            "the injector.\n"}


def test_env_reconciled_fixture_is_clean():
    assert check_env(PY_SOURCES, CONFIG_SRC, ENV_DOCS) == []


def test_env_flags_unregistered_read():
    srcs = dict(PY_SOURCES)
    srcs["k8s_llm_monitor_tpu/x.py"] = \
        'import os\nv = os.environ.get("K8SLLM_ROGUE")\n'
    findings = check_env(srcs, CONFIG_SRC, ENV_DOCS)
    assert len(findings) == 1
    assert "K8SLLM_ROGUE" in findings[0].message
    assert findings[0].path == "k8s_llm_monitor_tpu/x.py"


def test_env_flags_dead_and_mismapped_registry_entries():
    cfg = CONFIG_SRC.replace(
        '"K8SLLM_KV_DTYPE": "EngineConfig.kv_dtype",',
        '"K8SLLM_KV_DTYPE": "EngineConfig.kv_dtype",\n'
        '    "K8SLLM_UNUSED": "EngineConfig.nonexistent",')
    docs = {"README.md": ENV_DOCS["README.md"] + "`K8SLLM_UNUSED`\n"}
    msgs = [f.message for f in check_env(PY_SOURCES, cfg, docs)]
    assert any("not a dataclass field" in m for m in msgs)
    assert any("no module reads it" in m for m in msgs)


def test_env_flags_runtime_owner_that_never_reads():
    srcs = {k: v for k, v in PY_SOURCES.items()
            if not k.endswith("faults.py")}
    srcs["k8s_llm_monitor_tpu/resilience/faults.py"] = "spec = ''\n"
    msgs = [f.message for f in check_env(srcs, CONFIG_SRC, ENV_DOCS)]
    assert any("never reads it" in m for m in msgs)


def test_env_flags_undocumented_and_ghost_doc_keys():
    msgs = [f.message for f in check_env(
        PY_SOURCES, CONFIG_SRC,
        {"README.md": "`K8SLLM_KV_DTYPE` and the ghost `K8SLLM_GHOST`\n"})]
    assert any("'K8SLLM_FAULTS' is undocumented" in m for m in msgs)
    assert any("'K8SLLM_GHOST'" in m and "neither in ENV_KEYS" in m
               for m in msgs)


def test_env_derived_keys_walk_the_config_tree():
    assert "FLEET_ROLE" in derived_env_keys(CONFIG_SRC)


# -- run_contracts end-to-end on a mini repo --------------------------------


def mini_repo(tmp_path: Path, readme_extra: str = "") -> Path:
    pkg = tmp_path / "k8s_llm_monitor_tpu" / "monitor"
    pkg.mkdir(parents=True)
    (pkg / "server.py").write_text(SERVER_SRC, encoding="utf-8")
    (pkg / "agent.py").write_text(AGENT_SRC, encoding="utf-8")
    (pkg / "exporter.py").write_text(EXPORTER_SRC, encoding="utf-8")
    (pkg / "config.py").write_text(CONFIG_SRC, encoding="utf-8")
    serving = tmp_path / "k8s_llm_monitor_tpu" / "serving"
    serving.mkdir()
    (serving / "engine.py").write_text(
        PY_SOURCES["k8s_llm_monitor_tpu/serving/engine.py"],
        encoding="utf-8")
    res = tmp_path / "k8s_llm_monitor_tpu" / "resilience"
    res.mkdir()
    (res / "faults.py").write_text(
        PY_SOURCES["k8s_llm_monitor_tpu/resilience/faults.py"],
        encoding="utf-8")
    (tmp_path / "docs").mkdir()
    (tmp_path / "docs" / "observability.md").write_text(
        GOOD_OBS, encoding="utf-8")
    (tmp_path / "README.md").write_text(
        GOOD_ROUTE_DOCS["README.md"] + ENV_DOCS["README.md"]
        + readme_extra, encoding="utf-8")
    return tmp_path


def test_run_contracts_clean_mini_repo(tmp_path):
    assert run_contracts(mini_repo(tmp_path)) == []


def test_run_contracts_reports_drift_across_all_rules(tmp_path):
    root = mini_repo(
        tmp_path,
        "- `POST /api/v1/export`\n"
        "Watch `k8s_llm_monitor_phantom_total`.\n"
        "Set `K8SLLM_GHOST=1` to enable.\n")
    rules = {f.rule for f in run_contracts(root)}
    assert rules == {"route-contract", "metrics-contract", "env-contract"}


def test_run_contracts_honors_suppression_on_anchor_line(tmp_path):
    line = ("- `POST /api/v1/export` "
            "<!-- # graftcheck: disable=route-contract -->\n")
    assert run_contracts(mini_repo(tmp_path, line)) == []


# -- the live repo -----------------------------------------------------------


def test_live_repo_contracts_are_clean():
    findings = run_contracts(REPO_ROOT)
    assert findings == [], contracts.render(findings)


def test_live_repo_has_zero_contract_suppressions():
    # the acceptance bar: drift is reconciled, never suppressed
    hits = []
    for p in [REPO_ROOT / "README.md", REPO_ROOT / "Makefile",
              *sorted((REPO_ROOT / "docs").glob("*.md")),
              *sorted((REPO_ROOT / "k8s_llm_monitor_tpu").rglob("*.py"))]:
        if not p.is_file() or "__pycache__" in p.parts:
            continue
        text = p.read_text(encoding="utf-8")
        for rule in (*contracts.CONTRACT_RULE_NAMES,
                     "blocking-in-hot-path", "recompile-hazard",
                     "lock-order-static"):
            if f"disable={rule}" in text or f"disable-file={rule}" in text:
                hits.append((str(p), rule))
    # the devtools sources and this test mention the rule names, but no
    # real suppression comment may exist outside the fixtures
    assert not [h for h in hits
                if "devtools" not in h[0] and "tests" not in h[0]], hits
