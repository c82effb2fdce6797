"""Chaos suite: fault injection against the serving + monitor planes.

Every scenario arms the process-global FaultInjector (resilience/faults.py)
instead of waiting for real hardware to misbehave, then asserts the
*recovery contract*: zero hangs, every request ends in exactly one terminal
state (finished | failed-with-cause | shed-retriable), the KV allocator's
free count returns to its idle baseline, and health transitions
HEALTHY -> DEGRADED -> HEALTHY around the fault window.

Run standalone with ``make chaos``; the suite is deterministic (seeded
injector, CPU mesh) and fast enough to ride in tier-1.
"""

import threading
import time

import pytest

import jax

from k8s_llm_monitor_tpu.models import llama
from k8s_llm_monitor_tpu.models.config import ModelConfig
from k8s_llm_monitor_tpu.resilience.faults import (
    FaultError,
    FaultInjector,
    get_injector,
)
from k8s_llm_monitor_tpu.resilience.health import (
    DEGRADED,
    DRAINING,
    HEALTHY,
    UNHEALTHY,
    HealthMonitor,
)
from k8s_llm_monitor_tpu.resilience.retry import (
    Backoff,
    CircuitBreaker,
    CircuitOpen,
)
from k8s_llm_monitor_tpu.serving.engine import (
    EngineConfig,
    GenerationRequest,
    InferenceEngine,
    SamplingParams,
)
from k8s_llm_monitor_tpu.serving.service import EngineService, OverloadedError

pytestmark = pytest.mark.chaos

CFG = ModelConfig(name="t", vocab_size=300, hidden_size=32, intermediate_size=64,
                  num_layers=2, num_heads=4, num_kv_heads=2, dtype="float32",
                  rope_theta=10_000.0)

# Same shapes as tests/test_service.py so the jit cache is shared across the
# modules; prefix cache off so the allocator baseline is exact (cached
# prefixes intentionally pin pages).
ECFG = dict(max_slots=4, num_blocks=64, block_size=8,
            max_blocks_per_seq=16, prefill_buckets=(16,),
            max_prefills_per_step=4, decode_steps_per_iter=4,
            prefix_cache_entries=0)


@pytest.fixture(scope="module")
def params():
    return llama.init_params(jax.random.PRNGKey(0), CFG)


@pytest.fixture(autouse=True)
def _fault_isolation():
    """Every test starts and ends with the global injector disarmed."""
    get_injector().reset(seed=1234)
    yield
    get_injector().reset()


def _mk_engine(params, **overrides):
    cfg = dict(ECFG)
    cfg.update(overrides)
    return InferenceEngine(CFG, params, EngineConfig(**cfg), eos_id=-1)


def _run(eng, max_steps=500):
    """Step the engine to completion with a wedge guard (zero-hang proof)."""
    steps = 0
    while eng.has_work:
        eng.step()
        steps += 1
        assert steps < max_steps, "engine wedged: work left after step budget"


def _wait(predicate, timeout=10.0, interval=0.005):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if predicate():
            return True
        time.sleep(interval)
    return False


# -- injector / retry / health units ----------------------------------------


def test_injector_rate_times_after_determinism():
    inj = FaultInjector(seed=7)
    inj.arm("decode_dispatch", rate=1.0, times=2, after=3)
    fires = [inj.should_fire("decode_dispatch") for _ in range(10)]
    # 3 warm-up evaluations, then exactly `times` firings, then silent.
    assert fires == [False] * 3 + [True, True] + [False] * 5
    assert inj.fired("decode_dispatch") == 2

    # Same seed -> identical probabilistic draw sequence.
    draws = []
    for _ in range(2):
        inj.reset(seed=99)
        inj.arm("kube_http_5xx", rate=0.3)
        draws.append([inj.should_fire("kube_http_5xx") for _ in range(50)])
    assert draws[0] == draws[1]
    assert 0 < sum(draws[0]) < 50

    with pytest.raises(FaultError, match="injected fault: decode_dispatch"):
        inj.arm("decode_dispatch")
        inj.maybe_raise("decode_dispatch")


def test_injector_rejects_unknown_point_and_bad_env(monkeypatch):
    inj = FaultInjector()
    with pytest.raises(ValueError, match="unknown fault point"):
        inj.arm("no_such_point")  # graftcheck: disable=fault-point -- deliberately unknown (tests the registry guard)

    monkeypatch.setenv("K8SLLM_FAULTS", "decode_dispatch:0.5,kube_http_5xx")
    env_inj = FaultInjector()
    assert set(env_inj.armed) == {"decode_dispatch", "kube_http_5xx"}

    monkeypatch.setenv("K8SLLM_FAULTS", "decode_dispatch:lots")
    with pytest.raises(ValueError, match="K8SLLM_FAULTS"):
        FaultInjector()


def test_backoff_delays_bounded_and_jittered():
    import random

    bo = Backoff(base_s=0.2, cap_s=1.0, mult=2.0, jitter=0.2, attempts=6,
                 rng=random.Random(0))
    ds = list(bo.delays())
    assert len(ds) == 5  # attempts counts tries; delays sit between them
    # Each delay stays inside [nominal*(1-j), nominal*(1+j)] with the
    # exponential curve capped at cap_s.
    for i, d in enumerate(ds):
        nominal = min(0.2 * 2.0 ** i, 1.0)
        assert nominal * 0.8 <= d <= nominal * 1.2
    assert max(ds) <= 1.2


def test_breaker_trips_probes_and_recovers():
    t = [0.0]
    br = CircuitBreaker(failure_threshold=3, cooldown_s=5.0,
                        clock=lambda: t[0])
    assert br.state == "closed"
    for _ in range(3):
        br.before_call()
        br.record_failure()
    assert br.state == "open" and br.trips == 1
    with pytest.raises(CircuitOpen):
        br.before_call()
    assert br.rejections == 1

    t[0] = 5.1  # cooldown over: half-open grants exactly one probe
    assert br.state == "half-open"
    br.before_call()
    with pytest.raises(CircuitOpen):
        br.before_call()  # second caller must wait for the probe's verdict
    br.record_failure()  # probe failed -> back to open
    assert br.state == "open" and br.trips == 2

    t[0] = 10.3
    br.before_call()
    br.record_success()  # probe succeeded -> closed
    assert br.state == "closed"
    br.before_call()  # and calls flow again


def test_health_state_machine_transitions():
    t = [0.0]
    h = HealthMonitor(window_s=10.0, degraded_shed_rate=0.5,
                      unhealthy_failures=3, clock=lambda: t[0])
    assert h.state() == HEALTHY

    h.record_dispatch_failure()
    snap = h.snapshot()
    assert snap["state"] == DEGRADED and "dispatch failure" in snap["reason"]
    h.record_dispatch_ok()
    t[0] = 11.0  # event ages out of the window
    assert h.state() == HEALTHY

    # Shed rate crosses the degraded threshold.
    h.record_admit()
    h.record_shed()
    h.record_shed()
    assert h.state() == DEGRADED
    t[0] = 22.0
    assert h.state() == HEALTHY

    h.set_draining(True)
    assert h.state() == DRAINING and not h.snapshot()["ready"]
    h.set_draining(False)

    for _ in range(3):
        h.record_dispatch_failure()
    assert h.state() == UNHEALTHY
    h.record_dispatch_ok()
    t[0] = 33.0
    assert h.state() == HEALTHY

    h.set_dead("step loop exploded")
    snap = h.snapshot()
    assert snap["state"] == UNHEALTHY and "exploded" in snap["reason"]


def test_health_endpoints_report_state_and_503():
    """/health carries the real state + counters; both probes flip to 503
    when the health monitor leaves a ready state."""
    import json
    import urllib.error
    import urllib.request

    from k8s_llm_monitor_tpu.monitor.config import Config
    from k8s_llm_monitor_tpu.monitor.server import MonitorServer

    class _EngStub:
        queue_depth = 2
        active_slots = 1
        watchdog_trips = 1
        dispatch_failures = 3
        consecutive_dispatch_failures = 0
        deadline_expired = 0
        requeues = 1

    class _SvcStub:
        def __init__(self):
            self.health = HealthMonitor()
            self.engine = _EngStub()

    class _Backend:
        def __init__(self):
            self.service = _SvcStub()

    class _Analysis:
        def __init__(self):
            self.backend = _Backend()

    analysis = _Analysis()
    srv = MonitorServer(config=Config(), analysis=analysis, port=0)
    srv.start()
    try:
        def get(path):
            try:
                with urllib.request.urlopen(
                        f"http://127.0.0.1:{srv.port}{path}") as r:
                    return r.status, json.loads(r.read())
            except urllib.error.HTTPError as exc:
                return exc.code, json.loads(exc.read())

        status, body = get("/health")
        assert status == 200 and body["status"] == "healthy"
        assert body["engine"]["watchdog_trips"] == 1
        assert body["engine"]["dispatch_failures"] == 3

        status, body = get("/readyz")
        assert status == 200 and body["ready"] is True

        analysis.backend.service.health.set_dead("chaos")
        status, body = get("/health")
        assert status == 503 and body["status"] == "unhealthy"
        status, body = get("/readyz")
        assert status == 503 and body["ready"] is False
        assert body["reason"] == "chaos"
    finally:
        srv.stop()


# -- engine-level chaos ------------------------------------------------------


def test_decode_dispatch_failure_midstream_recovers(params):
    """One injected decode-dispatch failure mid-stream: the engine rolls the
    dispatch back, keeps serving, and every request still finishes."""
    eng = _mk_engine(params)
    get_injector().arm("decode_dispatch", rate=1.0, times=1, after=1)

    baseline = eng.allocator.free_blocks
    prompts = [[5, 6, 7], [9, 10, 11, 12]]
    results = eng.generate(prompts, SamplingParams(max_tokens=10))

    assert get_injector().fired("decode_dispatch") == 1
    assert eng.dispatch_failures == 1
    assert eng.consecutive_dispatch_failures == 0  # cleared by next success
    for res in results:
        assert res.finish_reason == "length" and len(res.token_ids) == 10
    assert eng.allocator.free_blocks == baseline


def test_prefill_dispatch_failure_exhausts_budget_then_serves(params):
    """A deterministic prefill-dispatch failure burns the requeue budget and
    surfaces to the caller with a cause — then the engine serves normally."""
    eng = _mk_engine(params)
    baseline = eng.allocator.free_blocks
    get_injector().arm("prefill_dispatch", rate=1.0)

    [res] = eng.generate([[3, 4, 5]], SamplingParams(max_tokens=4))
    assert res.finish_reason == "error"
    assert "prefill dispatch failed" in res.error
    assert "gave up after" in res.error
    assert eng.requeues == eng.ecfg.max_requeues
    assert eng.allocator.free_blocks == baseline

    get_injector().disarm("prefill_dispatch")
    [res] = eng.generate([[3, 4, 5]], SamplingParams(max_tokens=4))
    assert res.finish_reason == "length"
    assert eng.allocator.free_blocks == baseline


def test_watchdog_resets_stuck_decode(params):
    """A decode payload that never becomes ready trips the dispatch
    watchdog; the pipeline resets and the requests recompute to
    completion — no hang, no KV leak."""
    eng = _mk_engine(params, dispatch_timeout_s=0.05)
    baseline = eng.allocator.free_blocks
    get_injector().arm("decode_stuck", rate=1.0, times=1)

    results = eng.generate([[5, 6, 7], [8, 9]], SamplingParams(max_tokens=8))

    assert eng.watchdog_trips == 1
    assert eng.requeues >= 1
    for res in results:
        assert res.finish_reason in ("length", "eos")
    assert eng.allocator.free_blocks == baseline


def test_deadline_queue_ttl_and_running(params):
    """Expired queued requests fail at admission (queue TTL); a running
    request with its own deadline is aborted and retires with the cause."""
    eng = _mk_engine(params, queue_ttl_s=0.02)
    baseline = eng.allocator.free_blocks

    eng.submit(GenerationRequest("q1", [5, 6], SamplingParams(max_tokens=4)))
    eng.submit(GenerationRequest("q2", [7, 8], SamplingParams(max_tokens=4)))
    time.sleep(0.05)
    eng.step()
    for rid in ("q1", "q2"):
        res = eng.poll(rid)
        assert res is not None and res.finish_reason == "error"
        assert "deadline exceeded" in res.error and "in queue" in res.error
    assert eng.deadline_expired == 2
    assert not eng.has_work
    assert eng.allocator.free_blocks == baseline

    # Running deadline: generous enough to be admitted and produce tokens,
    # far too small for 300 of them.
    eng.submit(GenerationRequest("r1", [5, 6, 7],
                                 SamplingParams(max_tokens=300),
                                 deadline_s=0.2))
    steps = 0
    while eng.has_work:
        eng.step()
        steps += 1
        assert steps < 500, "deadline abort never retired the slot"
    res = eng.poll("r1")
    assert res.finish_reason == "error"
    assert "deadline exceeded" in res.error and "tokens generated" in res.error
    assert eng.deadline_expired == 3
    assert eng.allocator.free_blocks == baseline


def test_alloc_exhaustion_preempts_then_recovers(params):
    """Injected OutOfBlocks on a decode-time extend forces the recompute
    preemption path; everything still completes and the pool refills."""
    eng = _mk_engine(params)
    baseline = eng.allocator.free_blocks
    # Skip the two admission allocs; fire on the first extend and on its
    # post-release retry so the engine must preempt a victim.  A lane holds
    # whole runs of 8 blocks (64 tokens here), so its first extend that
    # takes blocks comes as it crosses into the second run.
    get_injector().arm("alloc_exhaustion", rate=1.0, times=2, after=2)

    prompts = [[3, 4, 5, 6, 7, 8], [9, 10, 11, 12, 13, 14]]
    results = eng.generate(prompts, SamplingParams(max_tokens=64))

    assert get_injector().fired("alloc_exhaustion") == 2
    assert eng.preemptions >= 1
    for res in results:
        assert res.finish_reason in ("length", "eos")
    assert eng.allocator.free_blocks == baseline


# -- service-level chaos -----------------------------------------------------


def test_load_shedding_and_drain(params):
    """Backlog beyond shed_queue_tokens sheds with a retriable
    OverloadedError carrying the queue evidence; drain stops admission,
    finishes inflight, and flushes every stream."""
    eng = _mk_engine(params, shed_queue_tokens=6, max_admission_rounds=1)
    assert eng.should_shed() == ""
    svc = EngineService(eng)
    try:
        # Fill every slot with slow work, then build queue backlog.
        handles = [svc.submit([3 + i, 4, 5], SamplingParams(max_tokens=40))
                   for i in range(4)]
        handles += [svc.submit([20 + i, 21, 22],
                               SamplingParams(max_tokens=40))
                    for i in range(2)]
        assert _wait(lambda: eng.queue_tokens >= 6), "backlog never built"

        with pytest.raises(OverloadedError) as exc:
            svc.submit([1, 2, 3], SamplingParams(max_tokens=4))
        assert exc.value.retriable
        assert exc.value.queue_depth >= 2 and exc.value.queue_tokens >= 6
        assert svc.shed_count == 1 and svc.health.sheds == 1

        assert svc.drain(timeout=60.0), "drain did not complete"
        assert svc.health.state() == DRAINING
        for h in handles:
            res = h.result(timeout=1.0)  # already flushed by the drain
            assert res.finish_reason in ("length", "eos")

        with pytest.raises(OverloadedError) as exc:
            svc.submit([1, 2], SamplingParams(max_tokens=2))
        assert not exc.value.retriable and "draining" in str(exc.value)
    finally:
        svc.stop()


def test_cancel_while_queued_releases_immediately(params):
    """Cancelling a request that never won a slot must resolve its handle
    right away — not after the running work finishes."""
    eng = _mk_engine(params)
    svc = EngineService(eng)
    try:
        # Occupy all four slots with long generations.
        busy = [svc.submit([3 + i, 4, 5], SamplingParams(max_tokens=60))
                for i in range(4)]
        assert _wait(lambda: eng.active_slots == 4), "slots never filled"

        queued = svc.submit([9, 9, 9], SamplingParams(max_tokens=60))
        queued.cancel()
        res = queued.result(timeout=2.0)
        assert res.finish_reason == "error"
        assert "cancel" in res.error
        # The running work is untouched and still completes.
        assert not busy[0].done or busy[0].result().finish_reason != "error"
        for h in busy:
            assert h.result(timeout=60.0).finish_reason in ("length", "eos")
    finally:
        svc.stop()


@pytest.mark.filterwarnings(
    "ignore::pytest.PytestUnhandledThreadExceptionWarning")
def test_step_loop_death_fails_all_with_cause(params):
    """When the step loop dies, every outstanding handle resolves with the
    cause (no client blocks forever) and the service reports UNHEALTHY."""
    eng = _mk_engine(params)
    svc = EngineService(eng)
    try:
        boom = threading.Event()
        real_step = eng.step

        def step():
            if boom.is_set():
                raise RuntimeError("boom: chaos killed the loop")
            real_step()

        eng.step = step
        h1 = svc.submit([5, 6, 7], SamplingParams(max_tokens=50))
        boom.set()
        res = h1.result(timeout=10.0)
        assert res.finish_reason == "error" and "boom" in res.error
        assert svc.health.state() == UNHEALTHY
        with pytest.raises(RuntimeError, match="dead"):
            svc.submit([1, 2], SamplingParams(max_tokens=2))
    finally:
        svc.stop()


# -- kube client chaos -------------------------------------------------------


def test_kube_5xx_storm_trips_and_recovers_breaker():
    """An apiserver 5xx storm exhausts the retry budget, trips the circuit
    breaker (later calls fail fast), and a half-open probe closes it once
    the storm passes."""
    import json
    from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer

    from k8s_llm_monitor_tpu.monitor.cluster import ClusterError
    from k8s_llm_monitor_tpu.monitor.kube_rest import KubeRestBackend

    class _Stub(BaseHTTPRequestHandler):
        protocol_version = "HTTP/1.1"

        def log_message(self, *a):
            pass

        def do_GET(self):  # noqa: N802
            body = json.dumps({"items": []}).encode()
            self.send_response(200)
            self.send_header("Content-Type", "application/json")
            self.send_header("Content-Length", str(len(body)))
            self.end_headers()
            self.wfile.write(body)

    server = ThreadingHTTPServer(("127.0.0.1", 0), _Stub)
    threading.Thread(target=server.serve_forever, daemon=True).start()
    t = [0.0]
    backend = KubeRestBackend(
        f"http://127.0.0.1:{server.server_address[1]}",
        backoff=Backoff(base_s=0.01, cap_s=0.01, attempts=4),
        breaker=CircuitBreaker(failure_threshold=5, cooldown_s=10.0,
                               clock=lambda: t[0]),
    )
    backend._sleep = lambda s: None  # no real backoff sleeps in tests
    try:
        get_injector().arm("kube_http_5xx", rate=1.0)

        # Storm: the full retry budget fails, the error carries the 503.
        with pytest.raises(ClusterError, match="503"):
            backend.list_nodes()
        assert backend.breaker.state == "closed"  # 4 failures, threshold 5

        # Fifth failure trips the breaker; the remaining attempts are
        # rejected without touching the (injected) apiserver.
        with pytest.raises(ClusterError, match="circuit open"):
            backend.list_nodes()
        assert backend.breaker.state == "open"
        assert backend.breaker.trips == 1
        assert backend.breaker.rejections >= 1

        # While open: fail fast, no retries burned.
        with pytest.raises(ClusterError, match="circuit open"):
            backend.list_nodes()

        # Storm over + cooldown elapsed: half-open probe hits the real
        # stub, succeeds, and the breaker closes.
        get_injector().disarm("kube_http_5xx")
        t[0] = 10.5
        assert backend.list_nodes() == []
        assert backend.breaker.state == "closed"
        assert backend.list_nodes() == []
    finally:
        backend.close()
        server.shutdown()
        server.server_close()


# -- acceptance: mixed chaos workload ---------------------------------------


def test_mixed_chaos_workload(params):
    """ISSUE acceptance: 64 mixed requests under a 5% decode-dispatch fault
    rate plus an allocator-exhaustion burst.  Zero hangs; every request
    ends in exactly one of {finished, failed-with-cause, shed-retriable};
    the allocator returns to baseline; health degrades during the fault
    window and recovers after it."""
    import random

    eng = _mk_engine(params, shed_queue_tokens=160, queue_ttl_s=30.0)
    baseline = eng.allocator.free_blocks
    health = HealthMonitor(window_s=1.5)
    svc = EngineService(eng, health=health)
    rng = random.Random(42)

    get_injector().arm("decode_dispatch", rate=0.05)
    get_injector().arm("alloc_exhaustion", rate=1.0, times=3, after=30)

    outcomes = {"finished": 0, "failed": 0, "shed": 0}
    handles = []
    states_seen = set()
    try:
        for i in range(64):
            prompt = [rng.randrange(3, 300)
                      for _ in range(rng.randrange(3, 11))]
            sampling = SamplingParams(max_tokens=rng.randrange(4, 9))
            deadline = 60.0 if i % 4 == 0 else 0.0
            try:
                handles.append(svc.submit(prompt, sampling,
                                          deadline_s=deadline))
            except OverloadedError as exc:
                assert exc.retriable and exc.queue_tokens > 0
                outcomes["shed"] += 1
            states_seen.add(health.state())

        for h in handles:
            res = h.result(timeout=120.0)  # zero hangs: every handle resolves
            states_seen.add(health.state())
            if res.finish_reason in ("length", "eos"):
                outcomes["finished"] += 1
            else:
                assert res.finish_reason == "error" and res.error
                outcomes["failed"] += 1

        assert sum(outcomes.values()) == 64
        assert outcomes["finished"] >= 32, outcomes
        # The storm actually happened and the state machine saw it.
        assert health.dispatch_failures >= 1
        assert get_injector().fired("alloc_exhaustion") >= 1
        assert DEGRADED in states_seen, states_seen

        # Fault window over: events age out and the state recovers.
        get_injector().reset()
        assert _wait(lambda: health.state() == HEALTHY, timeout=5.0)

        # No KV page leaks across faults, preemptions, and resets.
        assert _wait(lambda: eng.allocator.free_blocks == baseline,
                     timeout=5.0), (
            f"leaked pages: {eng.allocator.free_blocks} != {baseline}")
    finally:
        svc.stop()
