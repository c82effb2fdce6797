"""Crash-safe serving lifecycle: journal (WAL), supervisor, handover.

The acceptance contract (ROADMAP PR 4): a step-loop death mid-decode is
survivable — the supervisor rebuilds the engine, replays every incomplete
request with already-streamed tokens trimmed (zero duplicates, zero
losses), and the KV allocator lands back on its baseline because the
rebuilt engine starts fresh.  A SIGTERM handover drains within the grace
window, seals the journal, and leaves nothing for the next process to
replay; a SIGKILL (journal closed without a seal) leaves exactly the
incomplete requests, which a warm start replays before serving traffic.

Run standalone with ``make chaos-lifecycle``; deterministic (seeded
injector, greedy sampling).  The journal/HTTP/exporter tests are
CPU-fast and ride in tier-1; the end-to-end rebuild scenarios are
marked ``slow`` (every engine rebuild recompiles on CPU) and run in the
chaos suites only.
"""

import logging
import threading
import time
from http.client import HTTPConnection

import pytest

import jax

from k8s_llm_monitor_tpu.models import llama
from k8s_llm_monitor_tpu.models.config import ModelConfig
from k8s_llm_monitor_tpu.resilience.errors import OverloadedError
from k8s_llm_monitor_tpu.resilience.faults import get_injector
from k8s_llm_monitor_tpu.resilience.journal import (
    ADMIT,
    COMPLETE,
    PROGRESS,
    RequestJournal,
    _pack,
    scan_journal,
)
from k8s_llm_monitor_tpu.resilience.retry import Backoff
from k8s_llm_monitor_tpu.resilience.tenancy import TenantGovernor
from k8s_llm_monitor_tpu.serving.engine import (
    EngineConfig,
    InferenceEngine,
    SamplingParams,
)
from k8s_llm_monitor_tpu.serving.supervisor import EngineSupervisor

pytestmark = pytest.mark.chaos

CFG = ModelConfig(name="t", vocab_size=300, hidden_size=32, intermediate_size=64,
                  num_layers=2, num_heads=4, num_kv_heads=2, dtype="float32",
                  rope_theta=10_000.0)

# Same shapes as tests/test_resilience.py so the jit cache is shared across
# the chaos modules; prefix cache off so the allocator baseline is exact.
ECFG = dict(max_slots=4, num_blocks=64, block_size=8,
            max_blocks_per_seq=16, prefill_buckets=(16,),
            max_prefills_per_step=4, decode_steps_per_iter=4,
            prefix_cache_entries=0)


@pytest.fixture(scope="module")
def params():
    return llama.init_params(jax.random.PRNGKey(0), CFG)


@pytest.fixture(autouse=True)
def _fault_isolation():
    get_injector().reset(seed=1234)
    yield
    get_injector().reset()


def _mk_engine(params, **overrides):
    cfg = dict(ECFG)
    cfg.update(overrides)
    return InferenceEngine(CFG, params, EngineConfig(**cfg), eos_id=-1)


def _wait(predicate, timeout=30.0, interval=0.005):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if predicate():
            return True
        time.sleep(interval)
    return False


def _mk_supervisor(params, tmp_path=None, **overrides):
    journal = None
    if tmp_path is not None:
        journal = RequestJournal(tmp_path / "wal", fsync="never")
    kw = dict(journal=journal, max_restarts=4,
              backoff=Backoff(base_s=0.01, cap_s=0.05, jitter=0.0),
              heartbeat_timeout_s=30.0, poll_interval_s=0.02)
    kw.update(overrides)
    return EngineSupervisor(lambda: _mk_engine(params), **kw)


# -- journal units -----------------------------------------------------------


def test_journal_roundtrip_and_seal(tmp_path):
    j = RequestJournal(tmp_path, fsync="always")
    j.log_admit("r1", [1, 2, 3], SamplingParams(max_tokens=5), 2.5, 1000.0)
    j.log_progress("r1", [10, 11])
    j.log_progress("r1", [])  # no-op, must not write a record
    j.log_admit("r2", [4], {"max_tokens": 7, "temperature": 0.3})
    j.log_complete("r2")
    j.seal()

    reqs, sealed = scan_journal(tmp_path)
    assert sealed
    assert set(reqs) == {"r1", "r2"}
    r1 = reqs["r1"]
    assert not r1.completed
    assert r1.prompt_ids == [1, 2, 3]
    assert r1.emitted == [10, 11]
    assert r1.sampling["max_tokens"] == 5
    assert r1.deadline_s == 2.5 and r1.arrival_unix == 1000.0
    assert reqs["r2"].completed

    # A fresh journal over the same dir exposes the incomplete survivor and
    # reports the clean close.
    j2 = RequestJournal(tmp_path, fsync="never")
    assert j2.recovered_sealed
    assert [r.request_id for r in j2.incomplete_recovered] == ["r1"]
    j2.close()


def test_journal_rotation_and_compaction(tmp_path):
    j = RequestJournal(tmp_path, segment_max_bytes=1024, fsync="never")
    for i in range(50):
        j.log_admit(f"r{i}", list(range(20)), {"max_tokens": 4})
        j.log_complete(f"r{i}")
    # Everything is tombstoned: all rolled-over segments hold only history
    # and must have been deleted; only the active segment remains.
    assert j.compacted_segments > 0
    live = sorted(p.name for p in tmp_path.glob("wal-*.log"))
    assert len(live) == 1
    assert j.size_bytes <= 1024 + 256  # active segment only, near-empty
    j.close()

    # An incomplete request pins its segments across rotation.
    j2 = RequestJournal(tmp_path, segment_max_bytes=1024, fsync="never")
    j2.log_admit("pinned", list(range(20)), {"max_tokens": 4})
    for i in range(50):
        j2.log_admit(f"s{i}", list(range(20)), {"max_tokens": 4})
        j2.log_complete(f"s{i}")
    assert any(req.request_id == "pinned" and not req.completed
               for req in scan_journal(tmp_path)[0].values())
    j2.log_complete("pinned")
    j2.close()


def test_journal_torn_tail_fuzzer(tmp_path):
    """Truncate the segment at every byte offset inside the final record:
    the scanner must never raise and never resurrect the torn record."""
    recs = [
        _pack(ADMIT, {"id": "keep", "prompt": [1, 2], "sampling": {},
                      "deadline_s": 0.0, "arrival": 0.0}),
        _pack(PROGRESS, {"id": "keep", "tokens": [5, 6, 7]}),
        _pack(COMPLETE, {"id": "done"}),
        _pack(ADMIT, {"id": "torn", "prompt": list(range(40)),
                      "sampling": {"max_tokens": 9}, "deadline_s": 0.0,
                      "arrival": 0.0}),
    ]
    data = b"".join(recs)
    base = len(data) - len(recs[-1])
    seg = tmp_path / "wal-00000000.log"
    for cut in range(base, len(data)):
        seg.write_bytes(data[:cut])
        reqs, sealed = scan_journal(tmp_path)  # must not raise
        assert not sealed
        assert "torn" not in reqs, f"torn record resurrected at cut={cut}"
        assert reqs["keep"].emitted == [5, 6, 7]
        assert not reqs["keep"].completed
    # The full file scans clean.
    seg.write_bytes(data)
    reqs, _ = scan_journal(tmp_path)
    assert reqs["torn"].prompt_ids == list(range(40))


def test_journal_crc_corruption_drops_rest_of_segment(tmp_path):
    recs = [
        _pack(ADMIT, {"id": "a", "prompt": [1], "sampling": {},
                      "deadline_s": 0.0, "arrival": 0.0}),
        _pack(ADMIT, {"id": "b", "prompt": [2], "sampling": {},
                      "deadline_s": 0.0, "arrival": 0.0}),
        _pack(ADMIT, {"id": "c", "prompt": [3], "sampling": {},
                      "deadline_s": 0.0, "arrival": 0.0}),
    ]
    data = bytearray(b"".join(recs))
    flip = len(recs[0]) + 12  # a payload byte inside record "b"
    data[flip] ^= 0xFF
    (tmp_path / "wal-00000000.log").write_bytes(bytes(data))
    reqs, _ = scan_journal(tmp_path)
    # Everything before the corrupt record applies; nothing after it can be
    # trusted (the framing itself may be gone).
    assert set(reqs) == {"a"}


def test_journal_rejects_unknown_fsync_policy(tmp_path):
    with pytest.raises(ValueError, match="fsync policy"):
        RequestJournal(tmp_path, fsync="sometimes")


# -- supervisor: rebuild-and-replay ------------------------------------------


@pytest.mark.slow  # rebuild recompiles: seconds on CPU; covered by make chaos-lifecycle
def test_double_kill_under_load_replays_without_duplicates(params, tmp_path):
    """The PR acceptance scenario: kill the step loop twice during a
    32-request mixed load.  Zero hangs, zero lost requests, zero duplicated
    tokens, allocator back to baseline, counters consistent."""
    sup = _mk_supervisor(params, tmp_path)
    try:
        baseline = sup.engine.allocator.free_blocks
        n = 32
        budgets = [3 + (i % 6) for i in range(n)]
        handles = [
            sup.submit([(7 * i + j) % 300 for j in range(5 + i % 4)],
                       SamplingParams(max_tokens=budgets[i], temperature=0.0))
            for i in range(n)
        ]
        streamed: list[list[int]] = [[] for _ in range(n)]

        def consume(i):
            for tok in handles[i].stream(timeout=60.0):
                streamed[i].append(tok)

        threads = [threading.Thread(target=consume, args=(i,), daemon=True)
                   for i in range(n)]
        for t in threads:
            t.start()

        for kill in (1, 2):
            get_injector().arm("step_loop_crash", rate=1.0, times=1)
            assert _wait(lambda: sup.restarts == kill), f"kill {kill} missed"
            assert _wait(lambda: sup.state == "serving"), \
                f"rebuild {kill} never finished"

        for t in threads:
            t.join(timeout=60.0)
            assert not t.is_alive(), "stream hung after rebuild"
        results = [h.result(timeout=60.0) for h in handles]

        for i, res in enumerate(results):
            assert res.finish_reason != "error", (i, res.error)
            assert len(res.token_ids) == budgets[i], \
                f"request {i}: lost or duplicated tokens"
            # Stream == final result: replay never re-delivers a token.
            assert streamed[i] == list(res.token_ids), f"request {i}"

        assert sup.restarts == 2
        assert sup.replayed_total >= 1
        assert sup.health.snapshot()["ready"]
        snap = sup.snapshot()
        assert snap["tracked"] == 0 and snap["journal_bytes"] > 0
        assert _wait(lambda: not sup.engine.has_work, timeout=5.0)
        assert sup.engine.allocator.free_blocks == baseline
        # Every journaled request is tombstoned.
        reqs, _ = scan_journal(tmp_path / "wal")
        assert reqs and all(r.completed for r in reqs.values())
    finally:
        sup.shutdown(grace_s=1.0)
    assert scan_journal(tmp_path / "wal")[1], "shutdown must seal the journal"


def test_compiling_step_is_not_a_wedge(params):
    """A step() that compiles several programs back to back is silent far
    longer than the heartbeat timeout — about a minute per program for a 7B
    model on the chip's host (PR 21: the supervisor rebuilt the engine four
    times under its own compiles and gave up).  While the engine is inside
    a program call, and each time it leaves one, the loop counts as alive."""

    class _SlowCompiles:
        """Engine proxy whose first step() spends 4 x 0.25 s inside
        'program calls' (10x the heartbeat timeout in total)."""

        def __init__(self, inner):
            object.__setattr__(self, "_inner", inner)
            object.__setattr__(self, "_compiled", False)

        def __getattr__(self, name):
            return getattr(self._inner, name)

        def __setattr__(self, name, value):
            setattr(self._inner, name, value)

        def step(self):
            if not self._compiled:
                object.__setattr__(self, "_compiled", True)
                for _ in range(4):
                    self._inner.in_program_call = True
                    time.sleep(0.25)
                    self._inner.in_program_call = False
                    self._inner.last_program_call = time.monotonic()
                    time.sleep(0.05)     # host work between two programs
            return self._inner.step()

    sup = EngineSupervisor(
        lambda: _SlowCompiles(_mk_engine(params)), max_restarts=3,
        backoff=Backoff(base_s=0.01, cap_s=0.05, jitter=0.0),
        heartbeat_timeout_s=0.1, poll_interval_s=0.01)
    try:
        h = sup.submit([1, 2, 3], SamplingParams(max_tokens=4))
        # Loosen once the slow step is over: only the compile window is
        # under test, not scheduler hiccups on a loaded CI box.
        assert _wait(lambda: sup.engine._compiled, timeout=10.0)
        time.sleep(1.3)
        sup.heartbeat_timeout_s = 60.0
        res = h.result(timeout=60.0)
        assert res.finish_reason != "error", res.error
        assert sup.restarts == 0, "a compiling step was read as a wedge"
    finally:
        sup.shutdown(grace_s=1.0)


@pytest.mark.slow  # rebuild recompiles: seconds on CPU; covered by make chaos-lifecycle
def test_wedged_loop_detected_by_stale_heartbeat(params):
    """A step() that never returns (no exception) must still trigger a
    rebuild: heartbeat goes stale while work is pending."""
    gate = threading.Event()
    wedge = threading.Event()

    class _Wedgeable:
        """Engine proxy whose step() can be made to block."""

        def __init__(self, inner):
            object.__setattr__(self, "_inner", inner)

        def __getattr__(self, name):
            return getattr(self._inner, name)

        def __setattr__(self, name, value):  # token_sink/health assignment
            setattr(self._inner, name, value)

        def step(self):
            if wedge.is_set():
                gate.wait(timeout=60.0)
            return self._inner.step()

    built = []

    def factory():
        eng = _mk_engine(params)
        built.append(eng)
        return _Wedgeable(eng) if len(built) == 1 else eng

    # Warm the jit cache first: a legitimate (compiling) first step must not
    # read as a wedge once the tight heartbeat timeout is in force.
    from k8s_llm_monitor_tpu.serving.engine import GenerationRequest

    warm = _mk_engine(params)
    warm.submit(GenerationRequest(request_id="warm", prompt_ids=[1, 2, 3],
                                  sampling=SamplingParams(max_tokens=4)))
    while warm.has_work:
        warm.step()

    sup = EngineSupervisor(
        factory, max_restarts=3,
        backoff=Backoff(base_s=0.01, cap_s=0.05, jitter=0.0),
        heartbeat_timeout_s=0.3, poll_interval_s=0.05)
    try:
        wedge.set()
        h = sup.submit([1, 2, 3], SamplingParams(max_tokens=4))
        assert _wait(lambda: sup.restarts >= 1, timeout=10.0), \
            "stale heartbeat never detected"
        # Only the first wedge is under test; don't let scheduler hiccups on
        # the rebuilt loop read as further wedges.
        sup.heartbeat_timeout_s = 60.0
        res = h.result(timeout=30.0)
        assert res.finish_reason != "error", res.error
        assert len(res.token_ids) == 4
        assert len(built) >= 2, "factory must have been called for a rebuild"
    finally:
        gate.set()  # release the wedged thread so it can observe _stop
        sup.shutdown(grace_s=1.0)


def test_restart_budget_exhaustion_fails_survivors_with_cause(params):
    sup = _mk_supervisor(params, max_restarts=0)
    try:
        h = sup.submit([1, 2, 3], SamplingParams(max_tokens=50))
        get_injector().arm("step_loop_crash", rate=1.0, times=1)
        res = h.result(timeout=30.0)
        assert res.finish_reason == "error"
        assert "restart budget exhausted" in res.error
        assert _wait(lambda: sup.state == "failed", timeout=5.0)
        assert not sup.health.snapshot()["ready"]
        with pytest.raises(OverloadedError) as exc_info:
            sup.submit([1], SamplingParams(max_tokens=2))
        assert not exc_info.value.retriable
    finally:
        sup.close()


@pytest.mark.slow  # rebuild recompiles: seconds on CPU; covered by make chaos-lifecycle
def test_admission_refused_while_rebuilding(params):
    release = threading.Event()
    calls = []

    def factory():
        calls.append(1)
        if len(calls) > 1:
            assert release.wait(timeout=30.0)
        return _mk_engine(params)

    sup = EngineSupervisor(
        factory, max_restarts=2,
        backoff=Backoff(base_s=0.01, cap_s=0.05, jitter=0.0),
        poll_interval_s=0.02)
    try:
        get_injector().arm("step_loop_crash", rate=1.0, times=1)
        assert _wait(lambda: sup.state == "rebuilding", timeout=10.0)
        with pytest.raises(OverloadedError) as exc_info:
            sup.submit([1, 2], SamplingParams(max_tokens=2))
        assert exc_info.value.retriable
        assert exc_info.value.retry_after_s > 0
        release.set()
        assert _wait(lambda: sup.state == "serving", timeout=10.0)
        # Back to serving: admission works again, end to end.
        res = sup.submit([1, 2], SamplingParams(max_tokens=2)).result(
            timeout=30.0)
        assert res.finish_reason != "error"
    finally:
        release.set()
        sup.close()


# -- warm start (cross-process replay) ---------------------------------------


def test_warm_start_replays_unsealed_journal(params, tmp_path):
    wal = tmp_path / "wal"
    # Process #1 accepts two requests, streams two tokens of the first,
    # finishes the second, then dies without sealing (SIGKILL shape).
    j = RequestJournal(wal, fsync="never")
    j.log_admit("w1", [1, 2, 3], {"max_tokens": 5, "temperature": 0.0})
    j.log_progress("w1", [7, 8])
    j.log_admit("w2", [4, 5], {"max_tokens": 3})
    j.log_complete("w2")
    j.close()

    # Process #2 warm-starts: w1 is replayed (budget trimmed by the two
    # already-delivered tokens) before any fresh traffic, then tombstoned.
    sup = _mk_supervisor(params, journal=RequestJournal(wal, fsync="never"))
    try:
        assert sup.replayed_total == 1
        assert _wait(lambda: sup.snapshot()["tracked"] == 0, timeout=30.0)
    finally:
        sup.shutdown(grace_s=5.0)
    reqs, sealed = scan_journal(wal)
    assert sealed
    assert all(r.completed for r in reqs.values())
    # Process #3 has nothing to replay.
    j3 = RequestJournal(wal, fsync="never")
    assert j3.incomplete_recovered == []
    j3.close()


# -- tenancy through the WAL --------------------------------------------------


def test_journal_admit_records_carry_tenant(tmp_path):
    j = RequestJournal(tmp_path, fsync="never")
    j.log_admit("t1", [1, 2], {"max_tokens": 4}, slo_class="interactive",
                tenant="team-a")
    j.log_admit("t2", [3], {"max_tokens": 2})       # unlabeled request
    j.close()
    reqs, _ = scan_journal(tmp_path)
    assert reqs["t1"].tenant == "team-a"
    assert reqs["t1"].slo_class == "interactive"
    assert reqs["t2"].tenant == "public"            # pre-tenancy default


def test_torn_tail_never_corrupts_another_tenants_accounting(tmp_path):
    """Tenant B's torn ADMIT vanishes without touching tenant A's
    replayable state: WAL records are per-request and tenant-tagged, so
    the scanner's drop-the-tail rule doubles as accounting isolation —
    quota rebuilt from the scan charges A exactly its own emitted tokens
    and B nothing, at every possible tear offset."""
    recs = [
        _pack(ADMIT, {"id": "a1", "prompt": [1, 2],
                      "sampling": {"max_tokens": 6}, "deadline_s": 0.0,
                      "arrival": 0.0, "tenant": "team-a"}),
        _pack(PROGRESS, {"id": "a1", "tokens": [5, 6]}),
        _pack(ADMIT, {"id": "b1", "prompt": [3, 4],
                      "sampling": {"max_tokens": 9}, "deadline_s": 0.0,
                      "arrival": 0.0, "tenant": "team-b"}),
    ]
    data = b"".join(recs)
    base = len(data) - len(recs[-1])
    seg = tmp_path / "wal-00000000.log"
    for cut in range(base, len(data)):
        seg.write_bytes(data[:cut])
        reqs, _ = scan_journal(tmp_path)            # must not raise
        assert "b1" not in reqs, f"torn admit resurrected at cut={cut}"
        a1 = reqs["a1"]
        assert a1.tenant == "team-a" and a1.emitted == [5, 6]
        gov = TenantGovernor(tokens_per_s=0.001, token_burst=100.0,
                             clock=lambda: 0.0)
        for rec in reqs.values():
            if not rec.completed:
                gov.restore(rec.request_id, rec.tenant,
                            max_tokens=int(rec.sampling.get("max_tokens", 0)),
                            delivered=len(rec.emitted))
        snap = gov.snapshot()
        assert set(snap) == {"team-a"}
        assert snap["team-a"]["inflight"] == 1
        # 6-token budget, 2 already streamed: 4 remain reserved.
        assert snap["team-a"]["quota_remaining"] == 96.0


@pytest.mark.slow  # rebuilds an engine; covered by make chaos-tenant
def test_warm_start_restores_per_tenant_quota(params, tmp_path):
    """A supervisor warm start rebuilds per-tenant quota state from the
    WAL: the incomplete request's remaining budget is re-reserved under
    its recorded tenant, the replay streams the rest, and settlement
    charges exactly the delivered tokens — a crash cannot launder quota."""
    wal = tmp_path / "wal"
    j = RequestJournal(wal, fsync="never")
    j.log_admit("wa", [1, 2, 3], {"max_tokens": 5, "temperature": 0.0},
                tenant="team-a")
    j.log_progress("wa", [7, 8])                    # 2 of 5 streamed
    j.log_admit("wb", [4, 5], {"max_tokens": 3}, tenant="team-b")
    j.log_complete("wb")                            # nothing to replay
    j.close()

    gov = TenantGovernor(tokens_per_s=0.001, token_burst=100.0)
    sup = _mk_supervisor(params, journal=RequestJournal(wal, fsync="never"),
                         governor=gov)
    try:
        assert sup.replayed_total == 1
        assert _wait(lambda: sup.snapshot()["tracked"] == 0, timeout=30.0)
    finally:
        sup.shutdown(grace_s=5.0)
    snap = gov.snapshot()
    assert set(snap) == {"team-a"}                  # completed b never restored
    st = snap["team-a"]
    # Replay regenerated the 3 remaining tokens; with the 2 pre-crash
    # tokens the caller saw 5, and exactly 5 are charged.
    assert st["charged_tokens"] == 5
    assert st["inflight"] == 0
    # The new process's bucket paid only for the replayed remainder (the
    # pre-crash 2 were charged to the dead process's bucket).
    assert 96.0 <= st["quota_remaining"] <= 98.0


# -- SIGTERM graceful handover ------------------------------------------------


class _StubBackend:
    def __init__(self, supervisor=None, service=None):
        self.supervisor = supervisor
        self._service = service

    @property
    def service(self):
        if self.supervisor is not None:
            return self.supervisor.service
        return self._service

    @property
    def engine(self):
        svc = self.service
        return svc.engine if svc is not None else None


class _StubAnalysis:
    def __init__(self, backend=None):
        self.backend = backend


@pytest.mark.slow  # rebuild recompiles: seconds on CPU; covered by make chaos-lifecycle
def test_graceful_shutdown_drains_seals_and_flips_readiness(params, tmp_path):
    from k8s_llm_monitor_tpu.cmd.server import _graceful_shutdown
    from k8s_llm_monitor_tpu.monitor.server import MonitorServer

    sup = _mk_supervisor(params, tmp_path)
    srv = MonitorServer(analysis=_StubAnalysis(_StubBackend(supervisor=sup)))
    assert srv.health_snapshot()["ready"]
    h = sup.submit([1, 2, 3, 4], SamplingParams(max_tokens=6))

    _graceful_shutdown(srv, grace_s=20.0, log=logging.getLogger("test"))

    # The inflight generation finished inside the grace window...
    res = h.result(timeout=1.0)
    assert res.finish_reason != "error"
    assert len(res.token_ids) == 6
    # ...the journal is sealed with nothing left to replay...
    reqs, sealed = scan_journal(tmp_path / "wal")
    assert sealed
    assert all(r.completed for r in reqs.values())
    # ...and readiness reports 503-shape (not ready, with cause).
    snap = srv.health_snapshot()
    assert not snap["ready"]
    assert snap["lifecycle"]["state"] == "stopped"
    assert sup.state == "stopped"
    # Terminating is terminal: no new admissions.
    with pytest.raises(OverloadedError):
        sup.submit([1], SamplingParams(max_tokens=1))


# -- HTTP mapping of OverloadedError ------------------------------------------


class _OverloadedAnalysis:
    backend = None

    def __init__(self, exc):
        self._exc = exc

    def query(self, question, slo_class="interactive", tenant=""):
        raise self._exc


def _post_query(srv):
    conn = HTTPConnection("127.0.0.1", srv.port, timeout=10)
    try:
        conn.request("POST", "/api/v1/query", body='{"question": "why?"}',
                     headers={"Content-Type": "application/json"})
        resp = conn.getresponse()
        body = resp.read()
        return resp, body
    finally:
        conn.close()


def test_http_maps_overload_to_429_with_retry_after():
    import json as _json

    from k8s_llm_monitor_tpu.monitor.server import MonitorServer

    exc = OverloadedError("queue depth over limit", queue_depth=9,
                          queue_tokens=1234, retriable=True,
                          retry_after_s=2.2)
    srv = MonitorServer(analysis=_OverloadedAnalysis(exc),
                        host="127.0.0.1", port=0)
    srv.start()
    try:
        resp, body = _post_query(srv)
        assert resp.status == 429
        assert resp.getheader("Retry-After") == "3"  # ceil(2.2)
        payload = _json.loads(body)
        assert payload["error_kind"] == "overloaded"
        assert payload["queue_depth"] == 9
        assert payload["queue_tokens"] == 1234
        assert payload["retriable"] is True
        assert "queue depth over limit" in payload["error"]
    finally:
        srv.stop()


def test_http_maps_nonretriable_overload_to_503():
    from k8s_llm_monitor_tpu.monitor.server import MonitorServer

    exc = OverloadedError("draining", retriable=False, retry_after_s=0.4)
    srv = MonitorServer(analysis=_OverloadedAnalysis(exc),
                        host="127.0.0.1", port=0)
    srv.start()
    try:
        resp, _ = _post_query(srv)
        assert resp.status == 503
        assert resp.getheader("Retry-After") == "1"  # floor of 1s
    finally:
        srv.stop()


# -- observability -------------------------------------------------------------


class _FakeSupervisor:
    def snapshot(self):
        return {"state": "rebuilding", "restarts": 3, "max_restarts": 4,
                "replayed_total": 7, "tracked": 2, "journal_bytes": 4096}


def test_health_snapshot_reports_lifecycle_not_ready():
    from k8s_llm_monitor_tpu.monitor.server import MonitorServer

    backend = _StubBackend()
    backend.supervisor = _FakeSupervisor()
    srv = MonitorServer(analysis=_StubAnalysis(backend))
    snap = srv.health_snapshot()
    assert snap["ready"] is False
    assert "rebuilding" in snap["reason"]
    assert snap["lifecycle"]["restarts"] == 3


def test_exporter_emits_lifecycle_metrics():
    from k8s_llm_monitor_tpu.monitor.exporter import render_prometheus
    from k8s_llm_monitor_tpu.monitor.server import MonitorServer

    backend = _StubBackend()
    backend.supervisor = _FakeSupervisor()
    srv = MonitorServer(analysis=_StubAnalysis(backend))
    text = render_prometheus(srv)
    assert 'k8s_llm_monitor_lifecycle_state{state="rebuilding"} 1' in text
    assert 'k8s_llm_monitor_lifecycle_state{state="serving"} 0' in text
    assert "k8s_llm_monitor_engine_restarts_total 3" in text
    assert "k8s_llm_monitor_journal_replayed_total 7" in text
    assert "k8s_llm_monitor_journal_bytes 4096" in text
