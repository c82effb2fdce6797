"""Tracing subsystem (observability/): span ring bounds, deterministic
sampling, W3C traceparent round-trips, cross-replica trace merging over a
live router fleet with hedging and a forced mid-stream failover, the
flight recorder's dump-on-failure edges, per-class histogram bucket math,
and the exporter's exposition self-lint.

Unit tests run on scripted fake replicas and bare Tracer instances (no
engines).  Acceptance tests boot real in-process fleets and are marked
``slow`` — ``make chaos-trace`` runs the whole file under
``K8SLLM_LOCKCHECK=1``.
"""

import json
import re
import time
import urllib.request

import numpy as np
import pytest

import jax

from k8s_llm_monitor_tpu.fleet import (
    FleetRouter,
    HedgeConfig,
    LocalReplica,
    ReplicaRegistry,
)
from k8s_llm_monitor_tpu.fleet.frontend import build_router_server
from k8s_llm_monitor_tpu.fleet.replica import Replica
from k8s_llm_monitor_tpu.fleet.registry import ReplicaStats
from k8s_llm_monitor_tpu.models import llama
from k8s_llm_monitor_tpu.models.config import ModelConfig
from k8s_llm_monitor_tpu.monitor.analysis import (
    AnalysisEngine,
    LocalEngineBackend,
)
from k8s_llm_monitor_tpu.monitor.config import Config, LLMConfig
from k8s_llm_monitor_tpu.monitor.exporter import lint_exposition
from k8s_llm_monitor_tpu.monitor.server import MonitorServer
from k8s_llm_monitor_tpu.observability.flight import (
    FlightRecorder,
    get_flight_recorder,
    set_flight_recorder,
)
from k8s_llm_monitor_tpu.observability.metrics import ClassHistogram
from k8s_llm_monitor_tpu.observability.tracing import (
    TraceContext,
    Tracer,
    format_traceparent,
    get_tracer,
    parse_traceparent,
    set_tracer,
)
from k8s_llm_monitor_tpu.resilience.faults import get_injector
from k8s_llm_monitor_tpu.serving.engine import (
    EngineConfig,
    GenerationResult,
    InferenceEngine,
    SamplingParams,
)
from k8s_llm_monitor_tpu.serving.service import EngineService, RequestHandle
from k8s_llm_monitor_tpu.utils.tokenizer import ByteTokenizer

CFG = ModelConfig(name="t", vocab_size=300, hidden_size=32,
                  intermediate_size=64, num_layers=2, num_heads=4,
                  num_kv_heads=2, dtype="float32", rope_theta=10_000.0)
# Same shapes as tests/test_service.py / test_resilience.py so the jit
# cache is shared across the modules.
ECFG = dict(max_slots=4, num_blocks=64, block_size=8, max_blocks_per_seq=16,
            prefill_buckets=(16,), max_prefills_per_step=4,
            decode_steps_per_iter=4, prefix_cache_entries=0)


@pytest.fixture(scope="module")
def params():
    return llama.init_params(jax.random.PRNGKey(0), CFG)


@pytest.fixture(autouse=True)
def _fresh_tracer():
    """Every test gets its own fully-sampled tracer (and leaves the
    process singleton as it found it)."""
    import k8s_llm_monitor_tpu.observability.tracing as tr

    prev = tr._TRACER
    set_tracer(Tracer(sample=1.0, seed=1234))
    yield
    set_tracer(prev)


@pytest.fixture(autouse=True)
def _fault_isolation():
    get_injector().reset(seed=1234)
    yield
    get_injector().reset()


def _wait(cond, timeout=10.0):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if cond():
            return True
        time.sleep(0.005)
    return cond()


def _assert_no_orphans(spans):
    """Every non-root parent_id must be a span id present in the trace."""
    ids = {s["span_id"] for s in spans}
    orphans = [s for s in spans
               if s["parent_id"] and s["parent_id"] not in ids]
    assert not orphans, [(s["name"], s["parent_id"]) for s in orphans]


# ---------------------------------------------------------------------------
# traceparent / identity
# ---------------------------------------------------------------------------


def test_traceparent_round_trip():
    ctx = TraceContext("ab" * 16, "cd" * 8, True)
    parsed = parse_traceparent(format_traceparent(ctx))
    assert parsed == ctx
    unsampled = TraceContext("ab" * 16, "cd" * 8, False)
    assert format_traceparent(unsampled).endswith("-00")
    assert parse_traceparent(format_traceparent(unsampled)).sampled is False


def test_traceparent_rejects_malformed():
    good = f"00-{'ab' * 16}-{'cd' * 8}-01"
    assert parse_traceparent(good) is not None
    for bad in ("", "garbage", good[:-1], good + "0",
                f"ff-{'ab' * 16}-{'cd' * 8}-01",      # reserved version
                f"00-{'0' * 32}-{'cd' * 8}-01",       # zero trace id
                f"00-{'ab' * 16}-{'0' * 16}-01",      # zero span id
                f"00-{'AB' * 16}-{'cd' * 8}"):        # missing flags
        assert parse_traceparent(bad) is None, bad


def test_child_context_keeps_trace_and_links_parent():
    t = get_tracer()
    root = t.new_trace()
    child = Tracer.child(root)
    assert child.trace_id == root.trace_id
    assert child.parent_id == root.span_id
    assert child.span_id != root.span_id
    assert child.sampled == root.sampled


def test_bind_and_lookup_by_request_or_trace_id():
    t = get_tracer()
    ctx = t.new_trace()
    t.bind("req-1", ctx)
    assert t.lookup("req-1") == ctx.trace_id
    assert t.lookup(ctx.trace_id) == ctx.trace_id       # literal hex
    assert t.lookup(ctx.trace_id.upper()) == ctx.trace_id
    assert t.lookup("nonexistent") is None
    # Bounded FIFO: old bindings evict once past capacity.
    for i in range(t._rid_cap + 8):
        t.bind(f"spam-{i}", ctx)
    assert t.lookup("req-1") is None


# ---------------------------------------------------------------------------
# Ring + sampling
# ---------------------------------------------------------------------------


def test_span_ring_is_bounded():
    t = Tracer(ring_size=64, sample=1.0, seed=1)
    ctx = t.new_trace()
    for i in range(500):
        t.record(f"s{i}", 0.0, 1.0, ctx)
    assert t.recorded == 500
    spans = t.snapshot()
    assert len(spans) == 64                     # oldest overwritten
    names = {s["name"] for s in spans}
    assert "s499" in names and "s0" not in names


def test_sampling_is_deterministic_in_trace_id():
    a = Tracer(sample=0.5, seed=1)
    b = Tracer(sample=0.5, seed=999)            # different RNG, same rule
    ids = [a._new_trace_id() for _ in range(400)]
    decisions = [a.sampled(tid) for tid in ids]
    assert decisions == [b.sampled(tid) for tid in ids]
    rate = sum(decisions) / len(decisions)
    assert 0.35 < rate < 0.65                   # rough mass check
    # Seeded tracers replay identical id sequences (test determinism).
    s1 = Tracer(sample=1.0, seed=7)
    s2 = Tracer(sample=1.0, seed=7)
    assert [s1.new_trace() for _ in range(8)] == \
           [s2.new_trace() for _ in range(8)]


def test_sampling_off_records_nothing():
    t = Tracer(sample=0.0, seed=1)
    assert t.new_trace() is None
    with t.span("noop"):
        pass
    assert t.recorded == 0 and t.snapshot() == []


def test_unsampled_trace_counts_attempts_not_spans():
    t = Tracer(sample=0.5, seed=1)
    ctx = TraceContext("f" * 32, "1" * 16, False)
    t.record("x", 0.0, 1.0, ctx)
    assert t.recorded == 0 and t.unsampled == 1


def test_span_scope_sets_thread_local_and_marks_errors():
    t = get_tracer()
    with t.span("outer") as outer:
        assert t.current_traceparent().startswith("00-")
        with t.span("inner"):
            pass
    assert t.current() is None
    with pytest.raises(ValueError):
        with t.span("boom"):
            raise ValueError("x")
    spans = {s["name"]: s for s in t.snapshot()}
    assert spans["inner"]["parent_id"] == outer.span_id
    assert spans["boom"]["status"] == "error"


# ---------------------------------------------------------------------------
# Per-class histograms
# ---------------------------------------------------------------------------


def test_class_histogram_bucket_math_and_units():
    h = ClassHistogram((0.025, 0.1, 0.5))
    h.observe(0.01, "interactive", trace_id="t1")   # le=0.025
    h.observe(0.1, "interactive")                    # le=0.1 (boundary: <=)
    h.observe(0.3, "interactive")                    # le=0.5
    h.observe(9.0, "interactive", trace_id="t2")     # +Inf
    cum, total, count, ex = h.series("interactive")
    assert cum == [1, 2, 3, 4]                       # cumulative le series
    assert count == 4 and total == pytest.approx(9.41)
    assert ex[0][0] == "t1" and ex[3][0] == "t2"
    assert ex[0][1] == pytest.approx(0.01)
    # Classes are independent; unknown class reads as empty.
    h.observe(0.2, "batch")
    assert h.classes() == ["batch", "interactive"]
    assert h.series("standard")[2] == 0
    assert h.total_count() == 5
    q = h.quantile("interactive", 0.5)
    assert 0.025 <= q <= 0.5


# ---------------------------------------------------------------------------
# Flight recorder
# ---------------------------------------------------------------------------


def test_flight_recorder_artifact_format(tmp_path):
    rec = FlightRecorder(capacity=32, dirpath=str(tmp_path))
    for i in range(40):
        rec.note("tick", i=i)
    t = get_tracer()
    with t.span("something"):
        pass
    path = rec.dump("watchdog: decode stuck!", extra={"k": "v"})
    assert path and rec.dumps == 1 and rec.last_dump_path == path
    assert "watchdog" in path and "!" not in path    # reason sanitized
    art = json.loads(open(path).read())
    assert art["version"] == 2
    assert art["signals"] is None    # no telemetry source wired here
    assert art["reason"] == "watchdog: decode stuck!"
    assert art["extra"] == {"k": "v"}
    assert len(art["events"]) == 32                  # ring bounded
    assert art["events"][-1]["i"] == 39
    assert any(s["name"] == "something" for s in art["spans"])


def test_flight_recorder_dump_failure_is_swallowed(tmp_path):
    blocker = tmp_path / "blocked"
    blocker.write_text("a file, not a dir")
    rec = FlightRecorder(dirpath=str(blocker / "sub"))
    assert rec.dump("x") == ""
    assert rec.dump_errors == 1 and rec.dumps == 0


@pytest.mark.chaos
def test_flight_recorder_dumps_on_watchdog_fault(params, tmp_path):
    """A seeded stuck-decode fault trips the dispatch watchdog; the
    pipeline reset dumps a flight artifact carrying both the engine event
    ring and the span ring."""
    rec = FlightRecorder(dirpath=str(tmp_path))
    prev = get_flight_recorder()
    set_flight_recorder(rec)
    try:
        eng = InferenceEngine(CFG, params,
                              EngineConfig(dispatch_timeout_s=0.05, **ECFG),
                              eos_id=-1)
        get_injector().arm("decode_stuck", rate=1.0, times=1)
        results = eng.generate([[5, 6, 7], [8, 9]],
                               SamplingParams(max_tokens=8))
        assert eng.watchdog_trips == 1
        for res in results:
            assert res.finish_reason in ("length", "eos")
    finally:
        set_flight_recorder(prev)
    assert rec.dumps >= 1
    art = json.loads(open(rec.last_dump_path).read())
    assert art["reason"] == "pipeline_reset"
    assert "watchdog" in art["extra"]["cause"]
    assert any(s["name"].startswith("engine.") for s in art["spans"])


# ---------------------------------------------------------------------------
# Scripted fleet: trace threading through hedge and failover (no engines)
# ---------------------------------------------------------------------------


class _ScriptedReplica(Replica):
    """Token-level fake (next = last + 1): emits ``fail_after`` tokens
    then an error result, or stalls forever (hedge bait)."""

    supports_tokens = True

    def __init__(self, rid, fail_after=None, stall=False):
        self.replica_id = rid
        self.fail_after = fail_after
        self.stall = stall
        self.cancelled = []

    def readyz(self):
        return True

    def stats(self):
        return ReplicaStats(total_slots=4)

    def generate(self, prompt_ids, sampling=None, request_id=None,
                 deadline_s=0.0, slo_class="standard", tenant="public"):
        sampling = sampling or SamplingParams()
        h = RequestHandle(request_id or "r", eos_id=-1,
                          cancel_fn=lambda rid: self.cancelled.append(rid))
        if self.stall:
            return h
        start = prompt_ids[-1] if prompt_ids else 0
        toks = [(start + 1 + i) % 997 for i in range(sampling.max_tokens)]
        if self.fail_after is not None:
            emit = toks[: self.fail_after]
            for t in emit:
                h._push([t], None)
            h._push([], GenerationResult(
                request_id=h.request_id, token_ids=list(emit),
                finish_reason="error", ttft_s=0.0, latency_s=0.0,
                error="injected death"))
        else:
            for t in toks:
                h._push([t], None)
            h._push([], GenerationResult(
                request_id=h.request_id, token_ids=list(toks),
                finish_reason="length", ttft_s=0.0, latency_s=0.0))
        return h


def _registry(*reps):
    reg = ReplicaRegistry()
    for r in reps:
        reg.add(r)
    reg.refresh()
    return reg


def test_router_failover_stays_in_one_trace():
    a = _ScriptedReplica("a", fail_after=3)
    b = _ScriptedReplica("b")
    router = FleetRouter(_registry(a, b), policy="round_robin",
                         max_failovers=2)
    h = router.submit([5], SamplingParams(max_tokens=8))
    res = h.result(timeout=10)
    assert res.finish_reason == "length"
    assert _wait(lambda: router.counters()["completed"] == 1)
    t = get_tracer()
    tid = t.lookup(h.request_id)
    assert tid is not None
    spans = t.spans_for(tid)
    names = [s["name"] for s in spans]
    assert "router.dispatch" in names
    assert "router.failover" in names
    assert "router.request" in names
    assert all(s["trace_id"] == tid for s in spans)
    _assert_no_orphans(spans)
    fo = next(s for s in spans if s["name"] == "router.failover")
    assert fo["attrs"]["from"] == "a" and fo["attrs"]["to"] == "b"
    root = next(s for s in spans if s["name"] == "router.request")
    assert root["parent_id"] == ""
    assert root["attrs"]["attempts"] == 1


def test_router_hedge_joins_same_trace():
    a = _ScriptedReplica("a", stall=True)
    b = _ScriptedReplica("b")
    router = FleetRouter(_registry(a, b), policy="round_robin",
                         hedge=HedgeConfig(enabled=True, fixed_delay_s=0.02))
    h = router.submit([5], SamplingParams(max_tokens=4))
    res = h.result(timeout=10)
    assert res.finish_reason == "length"
    assert _wait(lambda: router.counters()["completed"] == 1)
    t = get_tracer()
    tid = t.lookup(h.request_id)
    spans = t.spans_for(tid)
    _assert_no_orphans(spans)
    hedge = next(s for s in spans if s["name"] == "router.hedge")
    assert hedge["attrs"]["winner"] == "b"
    assert hedge["trace_id"] == tid


def test_router_shed_records_terminal_span():
    router = FleetRouter(ReplicaRegistry())        # empty fleet
    from k8s_llm_monitor_tpu.resilience.errors import OverloadedError

    with pytest.raises(OverloadedError) as exc:
        router.submit([1], SamplingParams(max_tokens=2))
    rid = exc.value.request_id
    assert rid
    t = get_tracer()
    tid = t.lookup(rid)
    spans = t.spans_for(tid)
    _assert_no_orphans(spans)
    root = next(s for s in spans if s["name"] == "router.request")
    assert root["status"] == "error"
    assert root["attrs"]["outcome"] == "shed"


def test_router_joins_incoming_traceparent():
    """A caller-established context (the HTTP layer's ``traceparent``
    parse) becomes the parent of the router's request span."""
    a = _ScriptedReplica("a")
    router = FleetRouter(_registry(a), policy="round_robin")
    t = get_tracer()
    with t.span("http.server") as server_span:
        h = router.submit([5], SamplingParams(max_tokens=2))
    res = h.result(timeout=10)
    assert res.finish_reason == "length"
    assert _wait(lambda: router.counters()["completed"] == 1)
    spans = t.spans_for(server_span.trace_id)
    _assert_no_orphans(spans)
    root = next(s for s in spans if s["name"] == "router.request")
    assert root["parent_id"] == server_span.span_id


# ---------------------------------------------------------------------------
# Acceptance: live fleets
# ---------------------------------------------------------------------------


def _local_fleet(params, n=2):
    reps = []
    for i in range(n):
        eng = InferenceEngine(CFG, params, EngineConfig(**ECFG), eos_id=-1)
        reps.append(LocalReplica(f"r{i}", service=EngineService(eng)))
    reg = ReplicaRegistry()
    for r in reps:
        reg.add(r)
    reg.refresh()
    return reg, reps


@pytest.mark.chaos
@pytest.mark.slow  # boots 2 live engines; covered by make chaos-trace
def test_live_fleet_failover_yields_one_merged_trace(params):
    """The ISSUE acceptance gate: live router + 2 replicas with hedging
    enabled and a replica killed mid-decode — every request's spans form
    ONE trace with no orphan parents, covering >= 95% of the measured
    request wall time."""
    reg, reps = _local_fleet(params)
    router = FleetRouter(
        reg, policy="affinity", max_failovers=2,
        hedge=HedgeConfig(enabled=True, fixed_delay_s=0.02))
    rng = np.random.default_rng(33)
    n_req, n_tok = 8, 12
    prompts = [list(rng.integers(3, 300, size=4)) for _ in range(n_req)]
    import threading

    try:
        handles, walls, errors = [], [None] * n_req, []

        def _awaiter(i, h, t0):
            try:
                res = h.result(timeout=120)
                if res.finish_reason != "length":
                    errors.append((i, res.finish_reason, res.error))
                walls[i] = time.monotonic() - t0
            except Exception as exc:  # noqa: BLE001 — surfaced below
                errors.append((i, "exception", repr(exc)))

        waiters = []
        for i, p in enumerate(prompts):
            t0 = time.monotonic()
            h = router.submit(p, SamplingParams(max_tokens=n_tok))
            handles.append(h)
            th = threading.Thread(target=_awaiter, args=(i, h, t0),
                                  daemon=True)
            th.start()
            waiters.append(th)
        victim = reps[0]
        assert _wait(lambda: victim.service.engine.active_slots > 0,
                     timeout=60), "victim never received work"
        victim.kill()
        for th in waiters:
            th.join(timeout=120)
        assert not errors, errors
        assert all(w is not None for w in walls)
        assert _wait(lambda: router.counters()["completed"] == n_req,
                     timeout=60)
        assert router.counters()["failovers"] >= 1

        t = get_tracer()

        def _all_roots_landed():
            return all(
                any(s["name"] == "router.request"
                    for s in t.spans_for(t.lookup(h.request_id) or ""))
                for h in handles)

        assert _wait(_all_roots_landed, timeout=30)
        for h, wall in zip(handles, walls):
            tid = t.lookup(h.request_id)
            assert tid is not None, h.request_id
            spans = t.spans_for(tid)
            assert all(s["trace_id"] == tid for s in spans)
            _assert_no_orphans(spans)
            names = {s["name"] for s in spans}
            assert "router.request" in names
            assert "engine.request" in names        # replica layer joined
            lo = min(s["start_mono"] for s in spans)
            hi = max(s["start_mono"] + s["duration_s"] for s in spans)
            assert (hi - lo) >= 0.95 * wall, \
                (h.request_id, hi - lo, wall, sorted(names))
    finally:
        for r in reps:
            r.close()


@pytest.mark.slow  # boots a 2-engine HTTP fleet; covered by make chaos-trace
def test_http_traceparent_round_trip_and_merged_trace_endpoint(params):
    """W3C propagation over real HTTP: a caller-minted traceparent rides
    client -> router -> replica, and the router's /api/v1/trace/<id>
    returns the stitched timeline."""
    def boot_replica():
        tok = ByteTokenizer()
        engine = InferenceEngine(
            CFG, params,
            EngineConfig(max_slots=2, num_blocks=512, block_size=16,
                         max_blocks_per_seq=128,
                         prefill_buckets=(128, 512, 2048),
                         decode_steps_per_iter=4),
            tokenizer=tok)
        backend = LocalEngineBackend(engine, tok)
        analysis = AnalysisEngine(backend, llm_cfg=LLMConfig(max_tokens=16))
        srv = MonitorServer(config=Config(), analysis=analysis, port=0)
        srv.start()
        return srv, backend

    reps = [boot_replica() for _ in range(2)]
    cfg = Config()
    cfg.server.port = 0
    cfg.fleet.replicas = [f"http://127.0.0.1:{srv.port}" for srv, _ in reps]
    cfg.fleet.probe_interval_s = 0.5
    router_srv = build_router_server(cfg)
    router_srv.start()
    try:
        tid, sid = "ab" * 16, "cd" * 8
        req = urllib.request.Request(
            f"http://127.0.0.1:{router_srv.port}/api/v1/query",
            data=json.dumps({"question": "why"}).encode(),
            headers={"Content-Type": "application/json",
                     "traceparent": f"00-{tid}-{sid}-01"})
        with urllib.request.urlopen(req, timeout=120) as r:
            assert json.loads(r.read())["status"] == "success"

        with urllib.request.urlopen(
                f"http://127.0.0.1:{router_srv.port}/api/v1/trace/{tid}",
                timeout=30) as r:
            payload = json.loads(r.read())
        spans = payload["spans"]
        assert payload["trace_id"] == tid and spans
        assert all(s["trace_id"] == tid for s in spans)
        names = {s["name"] for s in spans}
        # Cross-layer stitch: the router's HTTP ingress, the routing span,
        # and the replica hop's HTTP ingress all joined the caller's trace
        # — the replica one can only be there via the traceparent header.
        assert "http.server" in names
        assert "router.query" in names
        rq = next(s for s in spans if s["name"] == "router.query")
        assert any(s["name"] == "http.server"
                   and s["parent_id"] == rq["span_id"] for s in spans), \
            "replica ingress did not join via the outbound traceparent"
        ids = {s["span_id"] for s in spans}
        orphans = [s for s in spans
                   if s["parent_id"] and s["parent_id"] not in ids
                   and s["parent_id"] != sid]        # caller's own span
        assert not orphans, [(s["name"], s["parent_id"]) for s in orphans]

        with urllib.request.urlopen(
                f"http://127.0.0.1:{router_srv.port}/api/v1/trace?limit=5",
                timeout=30) as r:
            recent = json.loads(r.read())
        assert any(row["trace_id"] == tid for row in recent["traces"])
    finally:
        router_srv.analysis.close()
        router_srv.stop()
        for srv, backend in reps:
            srv.stop()
            try:
                backend.service.stop(timeout=5.0)
            except Exception:  # noqa: BLE001 — teardown best-effort
                pass


# ---------------------------------------------------------------------------
# Exposition self-lint (unit; the live render is linted at render time)
# ---------------------------------------------------------------------------

_GOOD = """\
# HELP k8s_llm_monitor_up is the server up
# TYPE k8s_llm_monitor_up gauge
k8s_llm_monitor_up 1
# HELP k8s_llm_monitor_ttft_seconds ttft
# TYPE k8s_llm_monitor_ttft_seconds histogram
k8s_llm_monitor_ttft_seconds_bucket{class="interactive",le="0.1"} 3
k8s_llm_monitor_ttft_seconds_bucket{class="interactive",le="+Inf"} 4
k8s_llm_monitor_ttft_seconds_sum{class="interactive"} 0.5
k8s_llm_monitor_ttft_seconds_count{class="interactive"} 4
# HELP k8s_llm_monitor_overhead_ms overhead
# TYPE k8s_llm_monitor_overhead_ms gauge
k8s_llm_monitor_overhead_ms NaN
"""


def _with_meta(sample, fam="k8s_llm_monitor_x"):
    return f"# HELP {fam} h\n# TYPE {fam} gauge\n{sample}\n"


def test_lint_accepts_clean_exposition():
    assert lint_exposition(_GOOD) == []


def test_lint_flags_duplicate_family():
    text = _GOOD + "# HELP k8s_llm_monitor_up again\n" \
                   "# TYPE k8s_llm_monitor_up gauge\n"
    errs = lint_exposition(text)
    assert any("duplicate" in e for e in errs)


def test_lint_flags_bad_names_values_and_markers():
    assert lint_exposition(_with_meta("9bad_name 1"))
    errs = lint_exposition(_with_meta("k8s_llm_monitor_x not_a_number"))
    assert any("value" in e for e in errs)
    # Non-canonical NaN/Inf spellings are inconsistent across parsers.
    errs = lint_exposition(_with_meta("k8s_llm_monitor_x nan"))
    assert any("marker" in e for e in errs)
    assert lint_exposition(_with_meta("k8s_llm_monitor_x NaN")) == []


def test_lint_flags_orphan_type_and_help():
    errs = lint_exposition("# TYPE k8s_llm_monitor_x gauge\n")
    assert any("HELP" in e for e in errs)
    errs = lint_exposition("# HELP k8s_llm_monitor_y some help\n")
    assert any("TYPE" in e for e in errs)


def test_lint_flags_bad_label_block():
    errs = lint_exposition(
        _with_meta('k8s_llm_monitor_x{class=interactive} 1'))
    assert any("label" in e for e in errs)


# ---------------------------------------------------------------------------
# The step thread's loop on the record: phase spans, engine.call, xla.compile
# (docs/observability.md, "Span catalog"; serving/engine.py SPAN_CATALOG)
# ---------------------------------------------------------------------------

import contextlib  # noqa: E402
import re  # noqa: E402

import jax.numpy as jnp  # noqa: E402

from k8s_llm_monitor_tpu.monitor import exporter  # noqa: E402
from k8s_llm_monitor_tpu.serving import engine as engine_mod  # noqa: E402

# A shape no other test of this file runs, so its programs compile here.
LOOP_ECFG = dict(ECFG, prefill_buckets=(24,))
LOOP_PROMPTS = [[7 + i + j for j in range(5 + 3 * i)] for i in range(6)]
LOOP_MAX_TOKENS = [3, 9, 5, 12, 1, 7]


@contextlib.contextmanager
def _own_tracer(**kw):
    """A module fixture's own fully-sampled tracer (the autouse one is per
    test), the process singleton put back afterwards."""
    import k8s_llm_monitor_tpu.observability.tracing as tr

    prev = tr._TRACER
    tracer = Tracer(ring_size=1 << 14, sample=1.0, **kw)
    set_tracer(tracer)
    try:
        yield tracer
    finally:
        set_tracer(prev)


@pytest.fixture(scope="module")
def loop_record(params):
    """One traced run through the service: six requests on four lanes, so
    lanes turn over, steps wait for the device and the loop idles before and
    after.  Everything the cases below read, taken once."""
    with _own_tracer(seed=99) as tracer:
        eng = InferenceEngine(CFG, params, EngineConfig(**LOOP_ECFG), eos_id=-1)
        svc = EngineService(eng)
        time.sleep(0.25)  # a stretch of idling: several 50 ms waits
        handles = [svc.submit(p, SamplingParams(max_tokens=m),
                              request_id=f"loop-{i}")
                   for i, (p, m) in enumerate(zip(LOOP_PROMPTS,
                                                  LOOP_MAX_TOKENS))]
        results = [h.result(timeout=120) for h in handles]
        time.sleep(0.12)
        w = exporter._Writer()
        exporter._engine_metrics(w, eng)
        text = w.render()
        svc.stop()
        spans = tracer.snapshot()
    assert tracer.overwritten == 0
    return {"spans": spans, "results": results, "engine": eng,
            "exposition": text,
            "by_name": lambda name: [s for s in spans if s["name"] == name]}


def _loop_case_step_children_have_a_step_parent(rec):
    steps = {s["span_id"] for s in rec["by_name"]("engine.step")}
    kids = [s for s in rec["spans"] if s["name"].startswith("engine.step.")]
    assert steps and len(kids) >= 4 * len(steps)
    assert all(k["parent_id"] in steps for k in kids)
    assert ({k["name"] for k in kids}
            == {n for n in engine_mod.LOOP_PHASES
                if n.startswith("engine.step.")})
    assert set(engine_mod.LOOP_PHASES) <= set(engine_mod.SPAN_CATALOG)


def _loop_case_children_fit_inside_their_step(rec):
    inside: dict[str, float] = {}
    for s in rec["spans"]:
        if s["name"].startswith("engine.step."):
            inside[s["parent_id"]] = (inside.get(s["parent_id"], 0.0)
                                      + s["duration_s"])
    for step in rec["by_name"]("engine.step"):
        assert inside[step["span_id"]] <= step["duration_s"] + 1e-6


def _loop_case_loop_spans_hang_off_the_engine_root(rec):
    (root,) = rec["by_name"]("engine.maintenance")
    for name in ("engine.step", "engine.call", "service.intake",
                 "service.idle"):
        spans = rec["by_name"](name)
        assert spans, name
        assert all(s["parent_id"] == root["span_id"]
                   and s["trace_id"] == root["trace_id"] for s in spans)
    step = rec["by_name"]("engine.step")[0]
    assert set(step["attrs"]) == {"dispatched", "inflight"}


def _loop_case_an_idle_loop_merges_its_waits(rec):
    idle = rec["by_name"]("service.idle")
    # Two stretches (before the burst, after it), not one span a wait.
    assert len(idle) <= 3
    assert max(s["attrs"]["merged"] for s in idle) >= 3
    assert max(s["duration_s"] for s in idle) >= 0.15
    assert len(rec["by_name"]("service.intake")) < 40


def _loop_case_decode_calls_emit_no_more_than_they_compute(rec):
    decodes = [c for c in rec["by_name"]("engine.call")
               if c["attrs"]["kind"] == "decode"]
    assert decodes
    for c in decodes:
        a = c["attrs"]
        assert 0 <= a["emitted"] <= a["slots"] * a["steps"]
        assert 1 <= a["lanes"] <= a["slots"] == LOOP_ECFG["max_slots"]
        assert a["program"] == f"decode_k{a['steps']}_greedy"


def _loop_case_emitted_plus_first_tokens_is_what_clients_got(rec):
    calls = rec["by_name"]("engine.call")
    emitted = sum(c["attrs"]["emitted"] for c in calls
                  if c["attrs"]["kind"] == "decode")
    first = sum(c["attrs"]["prompts"] for c in calls
                if c["attrs"]["kind"] == "admit")
    got = sum(len(r.token_ids) for r in rec["results"])
    assert got == sum(LOOP_MAX_TOKENS)
    assert emitted + first == got
    # The per-lane spans say the same, lane by lane.
    assert emitted == sum(s["attrs"]["emitted"]
                          for s in rec["by_name"]("engine.decode"))


def _loop_case_prompt_tokens_are_all_accounted_for(rec):
    admits = [c["attrs"] for c in rec["by_name"]("engine.call")
              if c["attrs"]["kind"] in ("admit", "chunk")]
    assert (sum(a["real_tokens"] + a["cached_tokens"] for a in admits)
            == sum(len(p) for p in LOOP_PROMPTS))
    for a in admits:     # fresh rounds on one chip: packed calls of T tokens
        assert a["packed"] == 1
        assert a["real_tokens"] <= a["padded_tokens"] < 2 * max(
            a["real_tokens"], 24)
        assert a["prompts"] <= a["rows"] == LOOP_ECFG["max_prefills_per_step"]
        assert a["bucket"] == min(a["padded_tokens"], 24)
        assert a["program"].startswith(f"prefill_t{a['padded_tokens']}_")


def _loop_case_calls_are_numbered_and_say_what_they_found(rec):
    calls = sorted(rec["by_name"]("engine.call"),
                   key=lambda c: c["attrs"]["call_id"])
    assert [c["attrs"]["call_id"] for c in calls] == list(range(len(calls)))
    assert calls[0]["attrs"]["device_empty"] == 1  # nothing was queued yet
    assert all(c["attrs"]["device_empty"] in (0, 1) for c in calls)
    for c in calls:
        a = c["attrs"]
        assert a["kv_blocks"] == LOOP_ECFG["num_blocks"]
        assert 0 < a["kv_live_blocks"] < a["kv_blocks"]
        assert a["kv_cached_blocks"] == 0  # the prefix cache is off here
        assert set(a) <= set(engine_mod.SPAN_CATALOG["engine.call"])


def _loop_case_a_new_shape_names_its_compile_and_its_phase(rec):
    phases = {s["span_id"]: s["name"] for s in rec["spans"]
              if s["name"].startswith("engine.step.")}
    compiles = [s for s in rec["by_name"]("xla.compile")
                if s["attrs"].get("program")]
    by_program = {s["attrs"]["program"]: phases.get(s["parent_id"])
                  for s in compiles}
    assert by_program.get("prefill_t48_greedy") == "engine.step.admit"
    assert by_program.get("decode_k4_greedy") == "engine.step.decode"
    assert all(s["attrs"]["seconds"] > 0 for s in compiles)


def _loop_case_the_exporter_counts_what_the_spans_carry(rec):
    eng, calls = rec["engine"], rec["by_name"]("engine.call")
    kinds = [c["attrs"]["kind"] for c in calls]
    assert eng.calls_by_kind == {k: kinds.count(k) for k in set(kinds)}
    decodes = [c["attrs"] for c in calls if c["attrs"]["kind"] == "decode"]
    assert eng.decode_tokens == sum(a["emitted"] for a in decodes)
    assert eng.decode_slot_steps == sum(a["slots"] * a["steps"]
                                        for a in decodes)
    assert eng.prefill_tokens["real"] == sum(len(p) for p in LOOP_PROMPTS)
    assert eng.dispatch_on_empty_device == sum(
        c["attrs"]["device_empty"] for c in calls)
    # The counter against the spans, by order of the marks alone (whatever
    # the host's load): a phase's clock starts before its span opens and
    # banks after it closes, and every wait of this run lies inside a step,
    # whose span opened before and closes after (the case above on parents).
    waited = sum(s["duration_s"]
                 for s in rec["by_name"]("engine.step.wait_device"))
    stepped = sum(s["duration_s"] for s in rec["by_name"]("engine.step"))
    counted = eng.loop_seconds["engine.step.wait_device"]
    assert 0 < waited - 1e-6 <= counted <= stepped + 1e-6


def _loop_case_device_seconds_add_up_to_the_counter(rec):
    eng, calls = rec["engine"], rec["by_name"]("engine.call")
    summed: dict[str, float] = {}
    for c in calls:
        a = c["attrs"]
        assert a["waited"] in (0, 1)
        # At the head of the queue for no longer than dispatch -> ready.
        assert 0 <= a["device_s"] <= c["duration_s"] + 1e-9
        summed[a["kind"]] = summed.get(a["kind"], 0.0) + a["device_s"]
    assert set(summed) == set(eng.device_seconds) == set(eng.calls_by_kind)
    for kind, seconds in summed.items():
        assert eng.device_seconds[kind] == pytest.approx(seconds, abs=1e-9)
    # The queue is in order: the calls' head times never overlap.
    by_id = sorted(calls, key=lambda c: c["attrs"]["call_id"])
    for before, after in zip(by_id, by_id[1:]):
        ready = before["start_mono"] + before["duration_s"]
        start = after["start_mono"] + after["duration_s"] - after["attrs"][
            "device_s"]
        assert start >= ready - 1e-9


def _loop_case_the_exporter_renders_device_seconds_by_kind(rec):
    text, eng = rec["exposition"], rec["engine"]
    assert lint_exposition(text) == []
    assert ("# TYPE k8s_llm_monitor_engine_device_seconds_total counter"
            in text)
    rendered = {m.group(1): float(m.group(2)) for m in re.finditer(
        r'engine_device_seconds_total\{kind="(\w+)"\} (\S+)', text)}
    assert set(rendered) == {"admit", "decode"}
    for kind, seconds in rendered.items():   # taken before the service stopped
        assert 0 < seconds <= eng.device_seconds[kind] + 1e-6


def _loop_case_the_exporter_prints_the_counters_not_the_gauges(rec):
    text = rec["exposition"]
    assert lint_exposition(text) == []
    for family in ("engine_loop_seconds_total", "engine_calls_total",
                   "engine_decode_slot_steps_total",
                   "engine_decode_tokens_total",
                   "engine_prefill_tokens_total",
                   "engine_dispatch_on_empty_device_total"):
        assert f"# TYPE k8s_llm_monitor_{family} counter" in text
    for phase in ("step", "schedule", "admit", "chunk", "decode",
                  "wait_device", "apply", "intake", "idle"):
        assert f'engine_loop_seconds_total{{phase="{phase}"}}' in text
    assert 'engine_prefill_tokens_total{kind="padded"}' in text
    assert "decode_host_gap" not in text and "prefill_attn_ms" not in text
    # The subtraction profiler's gauges and the histograms the loop counters
    # and request_ttft_seconds superseded (PR 28) stay gone.
    for family in ("engine_decode_attn_ms", "engine_decode_sample_ms",
                   "engine_decode_collective_share",
                   "engine_decode_collective_hidden_share",
                   "decode_step_seconds", "engine_ttft_seconds"):
        assert family not in text
    # Every request here is greedy: both labels stand, at zero.
    for label in ("on", "off"):
        assert (f'engine_sampler_filter_calls_total{{filter="{label}"}} 0'
                in text)
    assert all("sampler_filter" not in c["attrs"]
               for c in rec["by_name"]("engine.call"))


LOOP_CASES = [fn for name, fn in sorted(globals().items())
              if name.startswith("_loop_case_")]


@pytest.mark.parametrize("case", LOOP_CASES,
                         ids=lambda fn: fn.__name__[len("_loop_case_"):])
def test_loop_record(loop_record, case):
    case(loop_record)


# -- which branch of the sampler a call takes (ops/sampling._filter_logits) --

# (per-request sampling, `sampler_filter` on every call of the wave or None
# where the programs are greedy, the decode program's name).  One answer
# length a wave, so every decode call holds every lane of it.
FILTER_WAVES = {
    "greedy": (
        [dict(), dict(top_p=0.9), dict(top_k=5)], None, "decode_k4_greedy"),
    "sampled-no-filter": (
        [dict(temperature=0.1), dict(temperature=0.7)], 0,
        "decode_k4_sampled"),
    "greedy-lane-carries-top_p": (
        [dict(top_p=0.9), dict(temperature=0.7)], 0, "decode_k4_sampled"),
    "one-top_p-lane": (
        [dict(temperature=0.7), dict(temperature=0.7, top_p=0.9),
         dict(temperature=0.7)], 1, "decode_k4_sampled"),
    "top_k-within-the-cap": (
        [dict(temperature=0.7, top_k=5)] * 2, 1, "decode_k4_sampled_bounded"),
}


@pytest.fixture(scope="module")
def filter_waves(params):
    """One traced engine, the waves above one after another: for each, its
    ``engine.call`` attributes and what the two counters gained."""
    out = {}
    with _own_tracer(seed=7) as tracer:
        eng = InferenceEngine(CFG, params, EngineConfig(**ECFG), eos_id=-1)
        for name, (lanes, _, _) in FILTER_WAVES.items():
            seen = len([s for s in tracer.snapshot()
                        if s["name"] == "engine.call"])
            before = dict(eng.sampler_filter_calls)
            for i, sp in enumerate(lanes):
                eng.submit(engine_mod.GenerationRequest(
                    f"{name}-{i}", [5 + i, 6, 7, 8 + i],
                    SamplingParams(max_tokens=9, **sp)))
            while eng.has_work:
                eng.step()
            calls = [s["attrs"] for s in tracer.snapshot()
                     if s["name"] == "engine.call"][seen:]
            out[name] = {
                "calls": calls,
                "gained": {k: eng.sampler_filter_calls[k] - before[k]
                           for k in before}}
        w = exporter._Writer()
        exporter._engine_metrics(w, eng)
        out["exposition"], out["engine"] = w.render(), eng
    assert tracer.overwritten == 0
    return out


@pytest.mark.parametrize("wave", sorted(FILTER_WAVES))
def test_engine_call_says_whether_a_sampling_lane_had_a_filter(
        filter_waves, wave):
    _, flag, decode_program = FILTER_WAVES[wave]
    calls, gained = filter_waves[wave]["calls"], filter_waves[wave]["gained"]
    assert {c["kind"] for c in calls} == {"admit", "decode"}
    assert {c["program"] for c in calls if c["kind"] == "decode"} == {
        decode_program}
    if flag is None:   # greedy programs: no attribute, neither counter
        assert all("sampler_filter" not in c for c in calls)
        assert gained == {"on": 0, "off": 0}
    else:
        assert [c["sampler_filter"] for c in calls] == [flag] * len(calls)
        assert gained == {"on": flag * len(calls),
                          "off": (1 - flag) * len(calls)}


def test_sampler_filter_counter_is_exported(filter_waves):
    text, eng = filter_waves["exposition"], filter_waves["engine"]
    assert lint_exposition(text) == []
    assert ("# TYPE k8s_llm_monitor_engine_sampler_filter_calls_total counter"
            in text)
    for label, n in eng.sampler_filter_calls.items():
        assert n > 0
        assert (f'engine_sampler_filter_calls_total{{filter="{label}"}} {n}'
                in text)
    sampled = sum(len(w["calls"]) for name, w in filter_waves.items()
                  if name in FILTER_WAVES and FILTER_WAVES[name][1] is not None)
    assert sum(eng.sampler_filter_calls.values()) == sampled


def test_loop_is_silent_with_sampling_off(params):
    """At ``sample=0.0`` the engine records nothing and every phase is the
    one shared clock — which still keeps the seconds and the counts."""
    tracer = Tracer(sample=0.0)
    set_tracer(tracer)
    eng = InferenceEngine(CFG, params, EngineConfig(**ECFG), eos_id=-1)
    clock = eng._phases
    assert type(clock) is engine_mod._PhaseClock
    assert eng._phase("engine.step.schedule") is clock
    clock.__exit__(None, None, None)
    eng.generate([[5, 6, 7], [8, 9]], SamplingParams(max_tokens=6))
    assert tracer.recorded == 0 and tracer.overwritten == 0
    assert eng.loop_seconds["engine.step.wait_device"] > 0
    assert eng.calls_by_kind == {"admit": 1, "decode": 2}
    assert eng.decode_tokens == 10 and eng.prefill_tokens["real"] == 5


def test_nested_phase_pauses_the_outer_one():
    """A reconcile inside a dispatch: the outer phase's span ends where the
    inner begins and a second one of its name follows — nothing overlaps,
    nothing is counted twice, and all are children of the step."""
    tracer = get_tracer()
    root = tracer.new_trace()
    phases = engine_mod._PhaseTrace(tracer, root)
    with phases.begin("engine.step", container=True) as step:
        with phases.begin("engine.step.decode"):
            time.sleep(0.01)
            with phases.begin("engine.step.wait_device"):
                assert tracer.current().parent_id  # a span is current
                time.sleep(0.01)
            time.sleep(0.01)
        step.set_attrs(dispatched=1)
    assert tracer.current() is None
    spans = tracer.snapshot()
    (step_span,) = [s for s in spans if s["name"] == "engine.step"]
    kids = [s for s in spans if s["name"] != "engine.step"]
    assert [k["name"] for k in kids] == [
        "engine.step.decode", "engine.step.wait_device", "engine.step.decode"]
    assert all(k["parent_id"] == step_span["span_id"] for k in kids)
    assert step_span["parent_id"] == root.span_id
    assert step_span["attrs"] == {"dispatched": 1}
    for a, b in zip(kids, kids[1:]):
        assert a["start_mono"] + a["duration_s"] <= b["start_mono"] + 1e-6
    assert sum(k["duration_s"] for k in kids) <= step_span["duration_s"] + 1e-6
    # The clock, by lower bounds and order only: a sleep may overrun by any
    # amount on a loaded host, never fall short.  A phase's seconds hold its
    # sleeps and enclose its spans (the clock starts before a span opens and
    # banks after it closes); only phases that ran have seconds at all.
    sec = phases.seconds
    slack = 1e-3
    assert sec["engine.step.decode"] >= 0.02 - slack
    assert sec["engine.step.wait_device"] >= 0.01 - slack
    for name in ("engine.step.decode", "engine.step.wait_device"):
        assert sec[name] >= sum(k["duration_s"] for k in kids
                                if k["name"] == name) - 1e-6
    assert kids[1]["duration_s"] >= 0.01 - slack
    assert {n for n, v in sec.items() if v > 0} <= {
        "engine.step", "engine.step.decode", "engine.step.wait_device"}
    assert sum(sec.values()) >= step_span["duration_s"] - 1e-6
    assert step_span["duration_s"] >= 0.03 - slack


def test_record_merged_stretches_only_an_unbroken_run():
    tracer = Tracer(ring_size=16, sample=1.0)
    root = tracer.new_trace()
    for i in range(3):
        tracer.record_merged("service.idle", float(i), i + 1.0, root)
    tracer.record("service.intake", 3.0, 3.5, root)
    tracer.record_merged("service.idle", 3.5, 4.0, root)
    spans = [(s["name"], s["start_mono"], s["duration_s"], s["attrs"])
             for s in tracer.snapshot()]
    assert ("service.idle", 0.0, 3.0, {"merged": 3}) in spans
    assert ("service.idle", 3.5, 0.5, {"merged": 1}) in spans
    assert tracer.recorded == 3
    tracer.record_merged("service.idle", 4.0, 5.0, None)  # untraced: inert
    assert tracer.recorded == 3


def test_overwritten_counts_what_the_ring_lost():
    tracer = Tracer(ring_size=16, sample=1.0)
    root = tracer.new_trace()
    for i in range(16):
        tracer.record("x", float(i), i + 0.5, root)
    assert tracer.overwritten == 0
    for i in range(5):
        tracer.record("x", float(i), i + 0.5, root)
    assert tracer.recorded == 21 and tracer.overwritten == 5
    assert len(tracer.snapshot()) == 16


def test_kv_census_counts_lanes_and_cache_apart(params):
    """``kv_live_blocks`` is the distinct blocks in live lanes' tables — a
    shared prefix counts once — and falls to 0 after the last request, while
    the allocator's used share stays above 0: the prefix cache keeps pages."""
    eng = InferenceEngine(
        CFG, params, EngineConfig(**dict(ECFG, prefix_cache_entries=64)),
        eos_id=-1)
    census = eng._kv_census
    seen = []

    def checked():
        out = census()
        tables = [s.blocks for s in eng._slots if s is not None]
        distinct = {b for blocks in tables for b in blocks}
        assert out["kv_live_blocks"] == len(distinct)
        seen.append((out, sum(len(t) for t in tables)))
        return out

    eng._kv_census = checked
    shared = list(range(40, 56))  # two full blocks of 8
    eng.generate([shared + [3]], SamplingParams(max_tokens=2))
    eng.generate([shared + [4, 5], shared + [6]],
                 SamplingParams(max_tokens=6))
    assert len(seen) >= 4
    # The second batch's lanes share the first request's two cached blocks.
    assert any(out["kv_live_blocks"] < in_tables for out, in_tables in seen)
    after = census()
    alloc = eng.allocator
    assert after["kv_live_blocks"] == 0 and after["kv_cached_blocks"] >= 2
    assert 1 - alloc.free_blocks / alloc.num_blocks > 1 / alloc.num_blocks
    calls = [s for s in get_tracer().snapshot() if s["name"] == "engine.call"]
    assert sum(c["attrs"].get("cached_tokens", 0) for c in calls) == 32
    assert (sum(c["attrs"].get("real_tokens", 0) for c in calls)
            + 32 == 3 * 16 + 4)


@pytest.fixture(scope="module")
def lowered_programs(params):
    eng = InferenceEngine(CFG, params, EngineConfig(**ECFG), eos_id=-1)
    B, W = ECFG["max_slots"], ECFG["max_blocks_per_seq"]
    zi = jnp.zeros((B,), jnp.int32)
    decode = eng._decode_program(4, sampled=True).lower(
        eng.params, eng._tok_state, zi, zi, eng.pages,
        jnp.zeros((B, W), jnp.int32), jnp.ones((B,)), zi, jnp.ones((B,)),
        jax.random.PRNGKey(0), jnp.asarray(-1, jnp.int32))
    R = eng.ecfg.max_prefills_per_step
    ri = jnp.zeros((R,), jnp.int32)
    prefill = eng._prefill_sample.lower(     # a packed stream of 16 tokens
        eng.params, jnp.zeros((16,), jnp.int32), (ri, ri),
        eng.pages, jnp.zeros((R, W), jnp.int32), jnp.ones((R,)),
        ri, jnp.ones((R,)), jax.random.PRNGKey(0))
    return {"decode": decode.as_text(debug_info=True),
            "prefill": prefill.as_text(debug_info=True)}


@pytest.mark.parametrize("scope", ["embed", "qkv", "attention", "attn_out",
                                   "mlp", "lm_head", "sampler",
                                   "sampler/filter"])
@pytest.mark.parametrize("program", ["decode", "prefill"])
def test_programs_carry_their_scopes(lowered_programs, program, scope):
    """The scopes are trace-time metadata: present in the lowered text of
    the tiny programs, as a path component of an operation's location (a
    scan body's locations are relative, so the scope may lead the path)."""
    text = lowered_programs[program]
    assert re.search(r'loc\("(?:[^"]*/)?' + re.escape(scope) + r'(?:/[^"]*)?"',
                     text), scope
