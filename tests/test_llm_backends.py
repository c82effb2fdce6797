"""OpenAI-compatible fallback backend: retry on transient failures,
error-body surfacing on permanent ones (VERDICT r3 weak #5)."""

import json
import threading
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer

import pytest

from k8s_llm_monitor_tpu.monitor.analysis import OpenAICompatBackend
from k8s_llm_monitor_tpu.monitor.config import LLMConfig


class _StubLLM(BaseHTTPRequestHandler):
    fail_times = 0          # 502s to serve before succeeding
    fail_status = 502
    calls = 0

    def log_message(self, *a):  # noqa: D102
        pass

    def do_POST(self):  # noqa: N802
        cls = type(self)
        cls.calls += 1
        n = int(self.headers.get("Content-Length", 0))
        self.rfile.read(n)
        if cls.calls <= cls.fail_times:
            body = json.dumps({"error": "upstream exploded"}).encode()
            self.send_response(cls.fail_status)
        else:
            body = json.dumps({"choices": [
                {"message": {"content": "the pod is OOMKilled"}}]}).encode()
            self.send_response(200)
        self.send_header("Content-Type", "application/json")
        self.send_header("Content-Length", str(len(body)))
        self.end_headers()
        self.wfile.write(body)


@pytest.fixture
def stub():
    _StubLLM.calls = 0
    srv = ThreadingHTTPServer(("127.0.0.1", 0), _StubLLM)
    threading.Thread(target=srv.serve_forever, daemon=True).start()
    yield srv
    srv.shutdown()
    srv.server_close()


def _backend(srv) -> OpenAICompatBackend:
    cfg = LLMConfig(provider="openai", api_key="k", model="m",
                    base_url=f"http://127.0.0.1:{srv.server_address[1]}/v1",
                    timeout=5)
    b = OpenAICompatBackend(cfg)
    b.backoff_s = 0.01  # fast tests
    return b


def test_retries_transient_502(stub):
    _StubLLM.fail_times = 2
    _StubLLM.fail_status = 502
    out = _backend(stub).generate("why crashloop?")
    assert out == "the pod is OOMKilled"
    assert _StubLLM.calls == 3


def test_permanent_error_surfaces_body(stub):
    _StubLLM.fail_times = 99
    _StubLLM.fail_status = 401
    with pytest.raises(RuntimeError) as err:
        _backend(stub).generate("q")
    assert "401" in str(err.value) and "upstream exploded" in str(err.value)
    assert _StubLLM.calls == 1  # 401 is not retried


def test_exhausted_retries_raise(stub):
    _StubLLM.fail_times = 99
    _StubLLM.fail_status = 503
    b = _backend(stub)
    with pytest.raises(RuntimeError) as err:
        b.generate("q")
    assert "503" in str(err.value)
    assert _StubLLM.calls == b.max_retries + 1


def test_non_json_200_is_retried(stub):
    """200 + HTML error page (LB/proxy) is as transient as a 502 and must
    not escape as a raw JSONDecodeError."""
    class _HTML(_StubLLM):
        def do_POST(self):  # noqa: N802
            cls = _StubLLM
            cls.calls += 1
            n = int(self.headers.get("Content-Length", 0))
            self.rfile.read(n)
            if cls.calls <= cls.fail_times:
                body = b"<html>503 Service Unavailable</html>"
                self.send_response(200)
            else:
                body = json.dumps({"choices": [
                    {"message": {"content": "ok"}}]}).encode()
                self.send_response(200)
            self.send_header("Content-Length", str(len(body)))
            self.end_headers()
            self.wfile.write(body)

    stub.RequestHandlerClass = _HTML
    _StubLLM.fail_times = 1
    out = _backend(stub).generate("q")
    assert out == "ok" and _StubLLM.calls == 2


def test_tpu_backend_boot_preflight_warns_on_unfittable_config(caplog):
    """An over-budget llm.tpu config logs the preflight verdict at boot
    (before the weight build could OOM a real chip) and still boots —
    warn-only by contract."""
    import logging

    from k8s_llm_monitor_tpu.monitor.analysis import LocalEngineBackend
    from k8s_llm_monitor_tpu.monitor.config import TPULLMConfig

    cfg = TPULLMConfig(model="tiny", quantize="", kv_blocks=8)
    with caplog.at_level(logging.WARNING):
        backend = LocalEngineBackend.from_config(cfg)
    try:
        assert any("preflight FAILED" in m for m in caplog.messages)
        assert any("raise --kv-blocks" in m for m in caplog.messages)
        assert backend.engine is not None  # boot proceeded regardless
    finally:
        backend.service.stop()


def test_tpu_backend_boot_preflight_tolerates_bogus_quantize(caplog):
    """An unknown llm.tpu.quantize value must neither crash boot (argparse
    SystemExit is contained) nor silently size the wrong dtype: it maps to
    bf16 exactly like the engine build does."""
    import logging

    from k8s_llm_monitor_tpu.monitor.analysis import LocalEngineBackend
    from k8s_llm_monitor_tpu.monitor.config import TPULLMConfig

    cfg = TPULLMConfig(model="tiny", quantize="fp8-bogus", kv_blocks=8)
    with caplog.at_level(logging.WARNING):
        backend = LocalEngineBackend.from_config(cfg)
    try:
        assert any("preflight FAILED" in m for m in caplog.messages)
        # bf16 engine (unknown quantize falls back, matching from_config)
        import jax.numpy as jnp

        q0 = backend.engine.params["layers"][0]["q"]
        assert "kernel_q" not in q0 and q0["kernel"].dtype == jnp.bfloat16
    finally:
        backend.service.stop()


def test_tpu_backend_build_failure_is_a_startup_error(monkeypatch):
    """``provider="tpu"`` with a backend that cannot be built raises — it
    never downgrades to ``TemplateBackend`` behind the operator's back
    (``--llm template`` is the explicit way to run without a model)."""
    from k8s_llm_monitor_tpu.monitor import analysis
    from k8s_llm_monitor_tpu.monitor.config import LLMConfig

    def boom(*_a, **_kw):
        raise RuntimeError("Mosaic failed to compile TPU kernel")

    monkeypatch.setattr(analysis.LocalEngineBackend, "from_config",
                        classmethod(boom))
    with pytest.raises(RuntimeError, match="Mosaic failed"):
        analysis.build_backend(LLMConfig(provider="tpu"))
    # The explicit template provider still builds without a model.
    assert isinstance(analysis.build_backend(LLMConfig(provider="template")),
                      analysis.TemplateBackend)


def test_tpu_backend_first_compile_failure_stops_the_boot(monkeypatch):
    """A kernel the compiler refuses fails at the first compile, inside the
    engine's jitted step — after the engine was built.  The backend's
    start-up gate turns that into a boot error carrying the compiler's
    message, not a server that starts and fails every request; and a
    refused DECODE program ends its requests (bounded requeues) instead of
    re-dispatching forever."""
    from k8s_llm_monitor_tpu.monitor.analysis import LocalEngineBackend
    from k8s_llm_monitor_tpu.monitor.config import TPULLMConfig
    from k8s_llm_monitor_tpu.ops import attention

    def refused(*_a, **_kw):
        raise ValueError("Slice shape along dimension 1 must be aligned "
                         "to tiling (8), but is 1")

    monkeypatch.setattr(attention, "select_decode_impl",
                        lambda **_kw: refused)
    with pytest.raises(RuntimeError, match="first compile.*aligned to tiling"):
        LocalEngineBackend.from_config(
            TPULLMConfig(model="tiny", quantize="", spec_k=0))


def test_compile_cache_helper_leaves_an_env_placed_cache_alone(monkeypatch,
                                                               tmp_path):
    """With JAX_COMPILATION_CACHE_DIR set the helper never touches
    ``jax_compilation_cache_dir``: whoever starts the process places it."""
    import jax

    from k8s_llm_monitor_tpu.utils import compile_cache

    calls = []
    real_update = jax.config.update
    monkeypatch.setattr(
        jax.config, "update",
        lambda k, v: calls.append(k) or real_update(k, v))
    before_min = jax.config.jax_persistent_cache_min_compile_time_secs
    before_size = jax.config.jax_persistent_cache_min_entry_size_bytes
    try:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
        before = jax.config.jax_compilation_cache_dir
        path, warm = compile_cache.configure_compile_cache()
        assert path == str(tmp_path) and warm is False
        assert "jax_compilation_cache_dir" not in calls
        assert jax.config.jax_compilation_cache_dir == before
    finally:
        real_update("jax_persistent_cache_min_compile_time_secs", before_min)
        real_update("jax_persistent_cache_min_entry_size_bytes", before_size)


def test_compile_cache_dir_is_the_same_from_any_working_directory(
        monkeypatch, tmp_path):
    """Unset, the cache is ``<checkout>/.jax_cache`` resolved from the
    package, not from the working directory: two launch directories, one
    absolute path."""
    import pathlib

    from k8s_llm_monitor_tpu.utils import compile_cache

    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    seen = []
    for where in (tmp_path, pathlib.Path(__file__).parent):
        monkeypatch.chdir(where)
        seen.append(compile_cache.compile_cache_dir())
    repo = pathlib.Path(__file__).resolve().parents[1]
    assert seen == [str(repo / ".jax_cache")] * 2
    assert pathlib.Path(seen[0]).is_absolute()
