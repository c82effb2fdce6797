"""Self-observability (/metrics Prometheus exporter, /debug/profile gate)
and SSE streaming of /api/v1/query.
"""

import json
import time
import urllib.error
import urllib.request

import pytest

import jax

from k8s_llm_monitor_tpu.models import llama
from k8s_llm_monitor_tpu.models.config import ModelConfig
from k8s_llm_monitor_tpu.monitor.analysis import AnalysisEngine, LocalEngineBackend
from k8s_llm_monitor_tpu.monitor.client import Client
from k8s_llm_monitor_tpu.monitor.cluster import FakeCluster, seed_demo_cluster
from k8s_llm_monitor_tpu.monitor.config import Config, LLMConfig, MetricsConfig
from k8s_llm_monitor_tpu.monitor.manager import Manager
from k8s_llm_monitor_tpu.monitor.server import MonitorServer
from k8s_llm_monitor_tpu.serving.engine import EngineConfig, InferenceEngine
from k8s_llm_monitor_tpu.utils.tokenizer import ByteTokenizer

CFG = ModelConfig(name="tiny", vocab_size=300, hidden_size=32,
                  intermediate_size=64, num_layers=2, num_heads=4,
                  num_kv_heads=2, dtype="float32", rope_theta=1e4)


@pytest.fixture(scope="module")
def engine_server():
    params = llama.init_params(jax.random.PRNGKey(0), CFG)
    tok = ByteTokenizer()
    engine = InferenceEngine(
        CFG, params,
        EngineConfig(max_slots=2, num_blocks=512, block_size=16,
                     max_blocks_per_seq=128, prefill_buckets=(128, 512, 2048),
                     decode_steps_per_iter=4),
        tokenizer=tok,
    )
    backend = LocalEngineBackend(engine, tok)
    fake = seed_demo_cluster(FakeCluster())
    client = Client(fake, namespaces=["default"])
    manager = Manager(client, MetricsConfig(namespaces=["default"]))
    manager.collect()
    analysis = AnalysisEngine(backend, client=client, manager=manager,
                              llm_cfg=LLMConfig(max_tokens=40))
    srv = MonitorServer(config=Config(), client=client, manager=manager,
                        analysis=analysis, port=0)
    srv.start()
    yield srv, engine
    srv.stop()
    backend.service.stop()


def _metrics_text(port):
    with urllib.request.urlopen(f"http://127.0.0.1:{port}/metrics") as r:
        assert r.headers["Content-Type"].startswith("text/plain")
        return r.read().decode()


def _parse(text):
    out = {}
    for line in text.splitlines():
        if line.startswith("#") or not line.strip():
            continue
        name, value = line.rsplit(" ", 1)
        out[name] = float(value)
    return out


def test_prometheus_exporter_gauges(engine_server):
    srv, engine = engine_server
    text = _metrics_text(srv.port)
    vals = _parse(text)
    assert vals['k8s_llm_monitor_build_info{version="1.0.0"}'] == 1
    assert vals["k8s_llm_monitor_collections_total"] >= 1
    assert vals["k8s_llm_monitor_snapshot_nodes"] > 0
    assert vals["k8s_llm_monitor_engine_slots_total"] == 2
    assert vals["k8s_llm_monitor_engine_kv_blocks_total"] == 512
    assert (vals["k8s_llm_monitor_engine_free_kv_blocks"]
            <= vals["k8s_llm_monitor_engine_kv_blocks_total"])


def test_spec_accept_and_overhead_gauges(engine_server):
    """Per-class spec-acceptance gauge appears once a class has a
    measurement; the constrained-decode overhead gauge is ALWAYS present
    on a local-engine backend (0.0 until both decode classes observed);
    an off-mesh engine emits no mesh topology gauges."""
    srv, engine = engine_server
    text = _metrics_text(srv.port)
    assert "k8s_llm_monitor_constrained_decode_overhead_ms" in text
    assert "mesh_axes" not in text                 # single-device engine
    assert "spec_accept_ema" not in text           # no measurement yet
    engine._spec_accept.update("greedy", accepted=4, lane_rounds=4)
    text = _metrics_text(srv.port)
    assert 'k8s_llm_monitor_spec_accept_ema{class="greedy"} 1.0' in text


def test_overhead_gauge_emits_nan_marker_for_nonlocal_backend():
    """Satellite 6: backends that don't measure the constrained-decode tax
    (remote/openai/template) must still emit the gauge — as an explicit
    NaN — so the router's proxied /metrics never silently mixes a
    population that has the series with one that lacks it."""
    from k8s_llm_monitor_tpu.monitor.exporter import (
        _diagnosis_metrics,
        _Writer,
    )

    w = _Writer()
    _diagnosis_metrics(w, None, object())   # backend without the EMA attr
    assert ("k8s_llm_monitor_constrained_decode_overhead_ms NaN"
            in w.render())


def test_mesh_topology_gauges_on_tp_engine():
    """A mesh-native engine exports its axis sizes and the collective-share
    estimate, so dashboards can tell a TP-8 slice from a single chip."""
    from k8s_llm_monitor_tpu.monitor.exporter import _engine_metrics, _Writer
    from k8s_llm_monitor_tpu.parallel.mesh import MeshConfig, create_mesh

    n_dev = len(jax.devices())
    mesh = create_mesh(MeshConfig(model=n_dev))
    # TP-shardable geometry (the module CFG's 300-row vocab doesn't divide
    # the vocab-parallel embedding 8 ways).
    tp_cfg = ModelConfig(name="tp-t", vocab_size=512, hidden_size=64,
                         intermediate_size=128, num_layers=2, num_heads=8,
                         num_kv_heads=8, dtype="float32", rope_theta=1e4)
    params = llama.init_params(jax.random.PRNGKey(1), tp_cfg)
    eng = InferenceEngine(
        tp_cfg, params,
        EngineConfig(max_slots=2, num_blocks=32, block_size=16,
                     max_blocks_per_seq=8, prefill_buckets=(64,)),
        mesh=mesh)
    w = _Writer()
    _engine_metrics(w, eng)
    text = w.render()
    assert f'k8s_llm_monitor_mesh_axes{{axis="model"}} {n_dev}' in text
    assert 'k8s_llm_monitor_mesh_axes{axis="data"} 1' in text
    assert "k8s_llm_monitor_engine_tp_overlap" in text
    assert eng.mesh_axes()["model"] == n_dev


def test_ttft_histogram_counts_queries(engine_server):
    srv, engine = engine_server
    before = engine.hist_ttft.total_count()
    req = urllib.request.Request(
        f"http://127.0.0.1:{srv.port}/api/v1/query",
        data=json.dumps({"question": "what is wrong?"}).encode(),
        headers={"Content-Type": "application/json"})
    with urllib.request.urlopen(req, timeout=300) as r:
        assert json.loads(r.read())["status"] == "success"
    vals = _parse(_metrics_text(srv.port))
    fam = "k8s_llm_monitor_request_ttft_seconds"
    counts = {cls: vals[f'{fam}_count{{class="{cls}"}}']
              for cls in engine.hist_ttft.classes()}
    assert sum(counts.values()) >= before + 1
    for cls, n in counts.items():
        assert vals[f'{fam}_bucket{{class="{cls}",le="+Inf"}}'] == n


def test_sse_streaming_query(engine_server):
    """stream=true must deliver the answer as multiple SSE deltas that
    arrive incrementally (first chunk before generation completes), then a
    done event."""
    srv, engine = engine_server
    req = urllib.request.Request(
        f"http://127.0.0.1:{srv.port}/api/v1/query",
        data=json.dumps({"question": "why crashloop?", "stream": True}).encode(),
        headers={"Content-Type": "application/json"})
    events = []
    arrivals = []
    with urllib.request.urlopen(req, timeout=300) as r:
        assert r.headers["Content-Type"] == "text/event-stream"
        for raw in r:
            line = raw.decode().strip()
            if line.startswith("data: "):
                events.append(json.loads(line[len("data: "):]))
                arrivals.append(time.monotonic())

    assert events[-1].get("done") is True
    deltas = [e["delta"] for e in events if "delta" in e]
    # 40 tokens at <=4 fused steps per wave -> several waves of deltas: the
    # client observably received chunks spread over time, not one blob.
    assert len(deltas) >= 3
    assert "".join(deltas)  # non-empty answer text
    assert arrivals[-1] - arrivals[0] > 0.0
    assert all(e["request_id"] == events[0]["request_id"] for e in events)


def test_profile_endpoint_gated_by_debug(engine_server):
    srv, _ = engine_server
    req = urllib.request.Request(
        f"http://127.0.0.1:{srv.port}/debug/profile",
        data=json.dumps({"seconds": 0.1}).encode(),
        headers={"Content-Type": "application/json"})
    with pytest.raises(urllib.error.HTTPError) as exc:
        urllib.request.urlopen(req, timeout=30)
    assert exc.value.code == 403


def test_live_metrics_pass_self_lint(engine_server):
    """The real exporter output — both Prometheus text and OpenMetrics —
    must pass the in-tree exposition linter, and the render-time check
    must report zero errors for itself."""
    from k8s_llm_monitor_tpu.monitor.exporter import lint_exposition

    srv, _ = engine_server
    text = _metrics_text(srv.port)
    assert lint_exposition(text) == []
    assert "k8s_llm_monitor_exposition_lint_errors 0" in text

    req = urllib.request.Request(
        f"http://127.0.0.1:{srv.port}/metrics",
        headers={"Accept": "application/openmetrics-text"})
    with urllib.request.urlopen(req, timeout=30) as r:
        assert r.headers["Content-Type"].startswith(
            "application/openmetrics-text")
        om = r.read().decode()
    assert lint_exposition(om) == []
