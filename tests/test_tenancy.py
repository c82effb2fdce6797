"""Multi-tenant hardening acceptance suite (docs/resilience.md, Tenancy).

Covers the three tenancy legs end to end on the CPU mesh:

  * **identity** — ``normalize_tenant`` at the trust boundary, header-wins
    HTTP parsing, tenant-tagged 429s, the ``/api/v1/stats`` tenants block;
  * **admission** — ``TokenBucket`` / ``TenantGovernor`` reservation
    protocol: refusal tagging, the request-token refund on token-quota
    refusal, settle idempotence, warm-start debt, accounting-only mode,
    the ``K8SLLM_TENANT_ENFORCE`` runtime flip, noisy-neighbor isolation,
    and the exact "charged tokens == delivered tokens" invariant across
    hedges, failovers, and a real mid-stream replica kill;
  * **KV isolation** — tenant-namespaced prefix caching on a live engine
    (cross-tenant lookups structurally miss, byte-exact output), the
    ``tenant_mismatch`` install outcome, and per-tenant block accounting,
    including under seeded ``lane_eviction`` faults.

``make chaos-tenant`` runs this module under K8SLLM_LOCKCHECK=1; the
flooding-tenant scenario is the acceptance gate: a tenant blasting 10x its
quota collects tenant-tagged 429s while a within-quota tenant's requests
all admit and complete byte-exactly.
"""

import json
import time
from http.client import HTTPConnection

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from k8s_llm_monitor_tpu.fleet import (
    FleetRouter,
    HedgeConfig,
    LocalReplica,
    ReplicaRegistry,
    ReplicaStats,
)
from k8s_llm_monitor_tpu.fleet.replica import Replica
from k8s_llm_monitor_tpu.models import llama
from k8s_llm_monitor_tpu.models.config import ModelConfig
from k8s_llm_monitor_tpu.monitor.config import Config
from k8s_llm_monitor_tpu.monitor.exporter import render_prometheus
from k8s_llm_monitor_tpu.monitor.models import AnalysisResponse
from k8s_llm_monitor_tpu.monitor.server import MonitorServer
from k8s_llm_monitor_tpu.resilience.errors import OverloadedError
from k8s_llm_monitor_tpu.resilience.faults import get_injector
from k8s_llm_monitor_tpu.resilience.tenancy import (
    DEFAULT_TENANT,
    TenantGovernor,
    TokenBucket,
    normalize_tenant,
    tenant_seed,
)
from k8s_llm_monitor_tpu.serving.engine import (
    EngineConfig,
    GenerationRequest,
    GenerationResult,
    InferenceEngine,
    SamplingParams,
)
from k8s_llm_monitor_tpu.serving.service import EngineService, RequestHandle

CFG = ModelConfig(name="t", vocab_size=300, hidden_size=32,
                  intermediate_size=64, num_layers=2, num_heads=4,
                  num_kv_heads=2, dtype="float32", rope_theta=10_000.0)
ECFG = dict(max_slots=4, num_blocks=64, block_size=8, max_blocks_per_seq=16,
            prefill_buckets=(16,), max_prefills_per_step=4,
            decode_steps_per_iter=4)


@pytest.fixture(scope="module")
def params():
    return llama.init_params(jax.random.PRNGKey(0), CFG)


def _mk_engine(params, **overrides):
    cfg = dict(ECFG)
    cfg.update(overrides)
    return InferenceEngine(CFG, params, EngineConfig(**cfg), eos_id=-1)


def _run(eng, max_steps=500):
    steps = 0
    while eng.has_work:
        eng.step()
        steps += 1
        assert steps < max_steps, "engine wedged: work left after step budget"


def _naive_greedy(params, prompt, n):
    seq = list(prompt)
    for _ in range(n):
        logits = llama.forward_full(params, CFG, jnp.asarray([seq], jnp.int32))
        seq.append(int(jnp.argmax(logits[0, -1])))
    return seq[len(prompt):]


def _wait(cond, timeout=5.0):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if cond():
            return True
        time.sleep(0.01)
    return cond()


# -- identity: normalize_tenant / tenant_seed ---------------------------------


def test_normalize_tenant_defaults_and_canonicalizes():
    assert normalize_tenant("") == DEFAULT_TENANT
    assert normalize_tenant(None) == DEFAULT_TENANT
    assert normalize_tenant("", default="fallback") == "fallback"
    # The slo_class idiom: strip + casefold once at the trust boundary.
    assert normalize_tenant("  Team-A ") == "team-a"
    assert normalize_tenant("a1_b.c-d") == "a1_b.c-d"


def test_normalize_tenant_env_default(monkeypatch):
    monkeypatch.setenv("K8SLLM_TENANT_DEFAULT", "acme")
    assert normalize_tenant("") == "acme"
    assert normalize_tenant(None) == "acme"
    # An explicit default still wins over the env fallback.
    assert normalize_tenant("", default="x") == "x"


@pytest.mark.parametrize("bad", ["two words", "-leading", ".dot", "a" * 65,
                                 "ünïcode", "semi;colon"])
def test_normalize_tenant_rejects_malformed(bad):
    with pytest.raises(ValueError):
        normalize_tenant(bad)


def test_tenant_seed_is_stable_and_disjoint():
    a, b = tenant_seed("team-a"), tenant_seed("team-b")
    assert len(a) == 32 and len(b) == 32
    assert a != b
    assert tenant_seed("team-a") == a
    # The default namespace is a seed like any other, never b"".
    assert tenant_seed(DEFAULT_TENANT) != b""


# -- TokenBucket --------------------------------------------------------------


def test_token_bucket_disabled_when_rate_zero():
    b = TokenBucket(0.0, 10.0)
    assert b.available() == float("inf")
    assert b.try_take(10 ** 9) == 0.0


def test_token_bucket_take_refuse_retry_hint_refill():
    now = [0.0]
    b = TokenBucket(2.0, 4.0, clock=lambda: now[0])
    assert b.try_take(4.0) == 0.0
    # Empty: the hint is the exact time for 2 tokens to refill at 2/s.
    assert b.try_take(2.0) == pytest.approx(1.0)
    assert b.refusals == 1
    now[0] += 1.0
    assert b.try_take(2.0) == 0.0
    assert b.takes == 2


def test_token_bucket_debt_and_refund_clamp():
    now = [0.0]
    b = TokenBucket(1.0, 5.0, clock=lambda: now[0])
    b.force_take(8.0)
    assert b.available() == pytest.approx(-3.0)
    # Refills pay the debt down before admissions succeed again.
    assert b.try_take(1.0) > 0.0
    b.give(100.0)
    assert b.available() == pytest.approx(5.0)  # clamped at burst


# -- TenantGovernor: the reservation protocol ---------------------------------


def _gov(**kw):
    now = [0.0]
    kw.setdefault("clock", lambda: now[0])
    return TenantGovernor(**kw), now


def test_governor_rate_refusal_is_tenant_tagged():
    gov, _ = _gov(requests_per_s=1.0, request_burst=1.0)
    gov.admit("team-a", "r0", max_tokens=4)
    with pytest.raises(OverloadedError) as ei:
        gov.admit("team-a", "r1", max_tokens=4)
    exc = ei.value
    assert exc.tenant == "team-a"
    assert exc.retriable is True
    assert exc.retry_after_s > 0.0
    snap = gov.snapshot()["team-a"]
    assert snap["admitted"] == 1
    assert snap["quota_refusals"] == 1 and snap["sheds"] == 1
    assert snap["inflight"] == 1


def test_governor_token_refusal_refunds_the_request_token():
    gov, _ = _gov(requests_per_s=1.0, request_burst=1.0,
                  tokens_per_s=0.001, token_burst=10.0)
    # The oversized request is refused on token quota — and must hand its
    # request-rate token back, or this refusal would starve the tenant's
    # next (within-quota) request on the rate dimension.
    with pytest.raises(OverloadedError) as ei:
        gov.admit("team-a", "big", max_tokens=50)
    assert "token quota" in str(ei.value)
    gov.admit("team-a", "small", max_tokens=5)  # must not raise
    snap = gov.snapshot()["team-a"]
    assert snap["admitted"] == 1 and snap["quota_refusals"] == 1


def test_governor_settle_refunds_and_is_idempotent():
    gov, _ = _gov(tokens_per_s=0.001, token_burst=100.0)
    gov.admit("team-a", "r0", max_tokens=10, prompt_bytes=33)
    assert gov.quota_remaining("team-a") == pytest.approx(90.0)
    gov.note_delivered("r0", 3)
    gov.note_delivered("r0", 1)
    assert gov.settle("r0") == 4
    assert gov.charged_tokens("team-a") == 4
    # Only delivered tokens stay charged; the reservation's unused 6 refund.
    assert gov.quota_remaining("team-a") == pytest.approx(96.0)
    assert gov.settle("r0") == 0  # idempotent: no double refund, no recharge
    assert gov.charged_tokens("team-a") == 4
    snap = gov.snapshot()["team-a"]
    assert snap["inflight"] == 0 and snap["admitted_bytes"] == 33


def test_governor_restore_re_reserves_into_debt():
    gov, _ = _gov(tokens_per_s=0.001, token_burst=10.0)
    # Warm start: 3 of 8 tokens were already delivered pre-crash; only the
    # remaining 5 are force-taken (the dead process charged the rest).
    gov.restore("wal-0", "team-a", max_tokens=8, delivered=3)
    assert gov.quota_remaining("team-a") == pytest.approx(5.0)
    gov.restore("wal-0", "team-a", max_tokens=8, delivered=3)  # idempotent
    assert gov.quota_remaining("team-a") == pytest.approx(5.0)
    gov.note_delivered("wal-0", 5)  # replay finishes the other 5
    assert gov.settle("wal-0") == 8
    assert gov.charged_tokens("team-a") == 8
    assert gov.quota_remaining("team-a") == pytest.approx(5.0)


def test_governor_accounting_only_mode_never_refuses():
    gov, _ = _gov(tokens_per_s=0.001, token_burst=4.0, enforce=False)
    for i in range(3):
        gov.admit("team-a", f"r{i}", max_tokens=4)  # 12 >> burst 4: no raise
    snap = gov.snapshot()["team-a"]
    assert snap["admitted"] == 3 and snap["quota_refusals"] == 0
    assert snap["quota_remaining"] < 0  # the debt is still visible


def test_governor_env_flips_enforcement_on(monkeypatch):
    gov, _ = _gov(requests_per_s=1.0, request_burst=1.0, enforce=False)
    monkeypatch.setenv("K8SLLM_TENANT_ENFORCE", "1")
    gov.admit("team-a", "r0", max_tokens=1)
    with pytest.raises(OverloadedError):
        gov.admit("team-a", "r1", max_tokens=1)
    monkeypatch.setenv("K8SLLM_TENANT_ENFORCE", "0")  # "0" means off
    gov.admit("team-a", "r2", max_tokens=1)


def test_governor_buckets_are_per_tenant():
    gov, _ = _gov(requests_per_s=0.001, request_burst=2.0)
    gov.admit("noisy", "n0", max_tokens=1)
    gov.admit("noisy", "n1", max_tokens=1)
    with pytest.raises(OverloadedError):
        gov.admit("noisy", "n2", max_tokens=1)
    # The quiet tenant's bucket is untouched by the noisy tenant's flood.
    gov.admit("quiet", "q0", max_tokens=1)
    gov.admit("quiet", "q1", max_tokens=1)
    snap = gov.snapshot()
    assert snap["quiet"]["quota_refusals"] == 0
    assert snap["noisy"]["quota_refusals"] == 1


def test_governor_evicts_idle_tenant_at_cap():
    gov, _ = _gov(max_tenants=2)
    gov.admit("t-idle", "r0", max_tokens=0)
    gov.settle("r0")                          # idle: nothing in flight
    gov.admit("t-busy", "r1", max_tokens=0)   # keeps an open reservation
    gov.admit("t-new", "r2", max_tokens=0)
    snap = gov.snapshot()
    assert set(snap) == {"t-busy", "t-new"}   # LRU-idle evicted, busy kept
    assert snap["t-busy"]["inflight"] == 1


# -- HTTP trust boundary ------------------------------------------------------


class _CaptureAnalysis:
    backend = None

    def __init__(self):
        self.tenants = []

    def query(self, question, slo_class="interactive", tenant=""):
        self.tenants.append(tenant)
        return AnalysisResponse(request_id="t", status="success",
                                result={"answer": "ok"})


class _RefusingAnalysis:
    backend = None

    def __init__(self, exc):
        self._exc = exc

    def query(self, question, slo_class="interactive", tenant=""):
        raise self._exc


def _post_query(srv, body=None, headers=None):
    conn = HTTPConnection("127.0.0.1", srv.port, timeout=10)
    try:
        payload = json.dumps({"question": "why?", **(body or {})})
        conn.request("POST", "/api/v1/query", body=payload,
                     headers={"Content-Type": "application/json",
                              **(headers or {})})
        resp = conn.getresponse()
        return resp, resp.read()
    finally:
        conn.close()


def _get(srv, path):
    conn = HTTPConnection("127.0.0.1", srv.port, timeout=10)
    try:
        conn.request("GET", path)
        resp = conn.getresponse()
        return resp, resp.read()
    finally:
        conn.close()


def test_http_header_wins_over_body_then_defaults():
    analysis = _CaptureAnalysis()
    srv = MonitorServer(analysis=analysis, host="127.0.0.1", port=0)
    srv.start()
    try:
        resp, _ = _post_query(srv, body={"tenant": "team-b"},
                              headers={"X-Tenant-Id": " Team-A "})
        assert resp.status == 200
        resp, _ = _post_query(srv, body={"tenant": "team-b"})
        assert resp.status == 200
        resp, _ = _post_query(srv)
        assert resp.status == 200
    finally:
        srv.stop()
    assert analysis.tenants == ["team-a", "team-b", DEFAULT_TENANT]


def test_http_malformed_tenant_is_400_before_engine_work():
    analysis = _CaptureAnalysis()
    srv = MonitorServer(analysis=analysis, host="127.0.0.1", port=0)
    srv.start()
    try:
        resp, body = _post_query(srv, headers={"X-Tenant-Id": "no spaces"})
        assert resp.status == 400
        assert b"tenant" in body
    finally:
        srv.stop()
    assert analysis.tenants == []  # the backend never saw the request


def test_http_quota_429_names_the_tenant():
    exc = OverloadedError("tenant 'team-a' over token quota",
                          retriable=True, retry_after_s=1.2,
                          tenant="team-a")
    srv = MonitorServer(analysis=_RefusingAnalysis(exc),
                        host="127.0.0.1", port=0)
    srv.start()
    try:
        resp, body = _post_query(srv)
        assert resp.status == 429
        assert resp.getheader("Retry-After") == "2"  # ceil(1.2)
        payload = json.loads(body)
        assert payload["error_kind"] == "overloaded"
        assert payload["tenant"] == "team-a"
    finally:
        srv.stop()


def test_http_stats_exposes_tenant_accounting():
    gov = TenantGovernor()
    gov.admit("team-a", "r0", max_tokens=8)
    gov.note_delivered("r0", 8)
    gov.settle("r0")
    srv = MonitorServer(analysis=_CaptureAnalysis(),
                        host="127.0.0.1", port=0)
    srv.governor = gov
    srv.start()
    try:
        resp, body = _get(srv, "/api/v1/stats")
        assert resp.status == 200
        block = json.loads(body)["tenants"]["team-a"]
        assert block["admitted"] == 1
        assert block["charged_tokens"] == 8
        assert block["inflight"] == 0
    finally:
        srv.stop()


# -- exporter cardinality discipline ------------------------------------------


def test_exporter_caps_tenant_label_at_top_k_plus_other():
    gov = TenantGovernor()
    # t0..t5 admit 1..6 requests; with top_k=3 only t5,t4,t3 get rows.
    for i in range(6):
        for j in range(i + 1):
            rid = f"t{i}-{j}"
            gov.admit(f"t{i}", rid, max_tokens=0)
            gov.settle(rid)
    cfg = Config()
    cfg.tenancy.top_k_metrics = 3
    srv = MonitorServer(config=cfg, analysis=_CaptureAnalysis())
    srv.governor = gov
    text = render_prometheus(srv)

    for family in ("tenant_requests_total", "tenant_shed_total",
                   "tenant_kv_blocks", "tenant_quota_remaining"):
        rows = [ln for ln in text.splitlines()
                if ln.startswith(f"k8s_llm_monitor_{family}{{")]
        # Exactly K named tenants + the aggregate bucket: an abusive
        # client minting fresh ids grows the scrape by exactly nothing.
        assert len(rows) == 4, (family, rows)
        assert any('tenant="other"' in ln for ln in rows), family

    assert 'k8s_llm_monitor_tenant_requests_total{tenant="t5"} 6' in text
    # The spilled tail (t2,t1,t0 = 3+2+1) aggregates, it does not vanish.
    assert 'k8s_llm_monitor_tenant_requests_total{tenant="other"} 6' in text
    # Bucket levels don't sum across tenants: the aggregate is NaN.
    assert ('k8s_llm_monitor_tenant_quota_remaining{tenant="other"} NaN'
            in text)
    # The render passes its own exposition lint.
    assert "k8s_llm_monitor_exposition_lint_errors 0" in text


def test_exporter_tenant_families_absent_without_governor():
    srv = MonitorServer(analysis=_CaptureAnalysis())
    text = render_prometheus(srv)
    assert "tenant_requests_total" not in text
    assert "k8s_llm_monitor_exposition_lint_errors 0" in text


# -- fleet charge placement: scripted fakes (deterministic, fast) -------------


class _TokReplica(Replica):
    """Token-level fake (next = last + 1 mod 997): the replay contract is
    checkable token by token.  ``fail_after=n`` emits n tokens then dies
    (the router's failover trigger); ``stall`` never emits (hedge bait)."""

    supports_tokens = True

    def __init__(self, rid, fail_after=None, stall=False):
        self.replica_id = rid
        self.fail_after = fail_after
        self.stall = stall
        self.calls = []
        self.cancelled = []

    def readyz(self):
        return True

    def stats(self):
        return ReplicaStats(total_slots=4)

    def generate(self, prompt_ids, sampling=None, request_id=None,
                 deadline_s=0.0, slo_class="standard", tenant="public"):
        sampling = sampling or SamplingParams()
        self.calls.append((list(prompt_ids), tenant))
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


def _scripted_fleet(*reps):
    reg = ReplicaRegistry()
    for r in reps:
        reg.add(r)
    reg.refresh()
    return reg


def test_hedge_loser_never_double_charges():
    gov = TenantGovernor(tokens_per_s=0.001, token_burst=100.0)
    a = _TokReplica("a", stall=True)
    b = _TokReplica("b")
    router = FleetRouter(_scripted_fleet(a, b), policy="round_robin",
                         hedge=HedgeConfig(enabled=True, fixed_delay_s=0.05),
                         governor=gov)
    h = router.submit([5, 6, 7], SamplingParams(max_tokens=6),
                      tenant="team-a")
    res = h.result(timeout=10)
    assert res.finish_reason == "length" and len(res.token_ids) == 6
    assert _wait(lambda: router.counters()["hedges_fired"] == 1)
    assert _wait(lambda: gov.snapshot()["team-a"]["inflight"] == 0)
    # One logical request, two dispatches, one charge.
    assert gov.charged_tokens("team-a") == 6
    assert gov.quota_remaining("team-a") == pytest.approx(94.0, abs=0.5)


def test_failover_replay_charges_delivered_exactly_once():
    gov = TenantGovernor(tokens_per_s=0.001, token_burst=100.0)
    a = _TokReplica("a", fail_after=2)
    b = _TokReplica("b")
    router = FleetRouter(_scripted_fleet(a, b), policy="round_robin",
                         max_failovers=2, governor=gov)
    h = router.submit([10, 11, 12], SamplingParams(max_tokens=6),
                      tenant="team-a")
    toks = list(h.stream(timeout=10))
    res = h.result(timeout=10)
    assert res.finish_reason == "length"
    assert toks == [(13 + i) % 997 for i in range(6)]  # no dup, no gap
    assert _wait(lambda: router.counters()["failovers"] == 1)
    assert _wait(lambda: gov.snapshot()["team-a"]["inflight"] == 0)
    # 2 tokens died with replica a, then the replay delivered all 6: the
    # tenant is charged 6, not 8 — the replay rode the same reservation.
    assert gov.charged_tokens("team-a") == 6


def test_router_quota_refusal_precedes_any_dispatch():
    gov = TenantGovernor(requests_per_s=0.001, request_burst=1.0)
    a = _TokReplica("a")
    b = _TokReplica("b")
    router = FleetRouter(_scripted_fleet(a, b), policy="round_robin",
                         governor=gov)
    router.submit([1, 2], SamplingParams(max_tokens=2),
                  tenant="team-a").result(timeout=10)
    with pytest.raises(OverloadedError) as ei:
        router.submit([3, 4], SamplingParams(max_tokens=2), tenant="team-a")
    assert ei.value.tenant == "team-a"
    # The refused request never reached a replica.
    assert len(a.calls) + len(b.calls) == 1


# -- engine-level acceptance (live engines; make chaos-tenant) ----------------


@pytest.mark.chaos
@pytest.mark.slow  # live engine + greedy oracle; covered by make chaos-tenant
def test_flooding_tenant_rate_limited_quiet_tenant_unharmed(params):
    """The acceptance gate: a tenant blasting far past its request-rate
    quota collects tenant-tagged 429s, while a within-quota tenant's
    interactive requests all admit and complete byte-exactly — per-tenant
    buckets mean the flood cannot consume the quiet tenant's budget."""
    gov = TenantGovernor(requests_per_s=0.5, request_burst=4.0)
    svc = EngineService(_mk_engine(params), governor=gov)
    rng = np.random.default_rng(41)
    try:
        flood, refused = [], 0
        for i in range(20):
            p = [int(t) for t in rng.integers(3, 300, size=8)]
            try:
                flood.append(svc.submit(
                    p, SamplingParams(max_tokens=4),
                    request_id=f"noisy{i}", tenant="noisy",
                    slo_class="standard"))
            except OverloadedError as exc:
                refused += 1
                assert exc.tenant == "noisy"
                assert exc.retriable and exc.retry_after_s > 0
        assert refused >= 15  # burst 4 (+ epsilon refill) admitted, rest 429

        for i in range(4):
            p = [int(t) for t in rng.integers(3, 300, size=8)]
            h = svc.submit(p, SamplingParams(max_tokens=4),
                           request_id=f"quiet{i}", tenant="quiet",
                           slo_class="interactive")
            res = h.result(timeout=60)
            assert res.finish_reason == "length"
            assert res.token_ids == _naive_greedy(params, p, 4)
        flood_delivered = sum(len(h.result(timeout=60).token_ids)
                              for h in flood)

        assert _wait(lambda: not any(v["inflight"]
                                     for v in gov.snapshot().values()))
        snap = gov.snapshot()
        assert snap["noisy"]["quota_refusals"] == refused
        assert snap["quiet"]["quota_refusals"] == 0
        assert snap["quiet"]["sheds"] == 0
        # Zero lost tokens under the flood: the settled charge is what each
        # tenant's streams delivered.
        assert gov.charged_tokens("quiet") == 4 * 4
        assert gov.charged_tokens("noisy") == flood_delivered
    finally:
        svc.stop(timeout=10)


@pytest.mark.chaos
@pytest.mark.slow  # 2 live engines + mid-stream kill; make chaos-tenant
def test_chaos_replica_kill_charged_equals_delivered(params):
    """The quota-exactness regression gate (fleet edition): a replica dies
    while actively decoding tenant streams; every stream completes on the
    survivor and the governor's settled charge equals the tokens the
    callers actually received — failover replays ride the original
    reservation, never a second charge."""
    gov = TenantGovernor(tokens_per_s=0.001, token_burst=10_000.0)
    reps = [LocalReplica(f"r{i}", service=EngineService(_mk_engine(params)))
            for i in range(2)]
    reg = ReplicaRegistry()
    for r in reps:
        reg.add(r)
    reg.refresh()
    router = FleetRouter(reg, policy="affinity", max_failovers=2,
                         governor=gov)
    rng = np.random.default_rng(33)
    n_tok = 16
    prompts = [[int(t) for t in rng.integers(3, 300, size=4)]
               for _ in range(16)]
    try:
        handles = [router.submit(p, SamplingParams(max_tokens=n_tok),
                                 tenant="team-a")
                   for p in prompts]
        victim = reps[0]
        assert _wait(lambda: victim.service.engine.active_slots > 0,
                     timeout=60), "victim never received work"
        victim.kill()

        delivered = 0
        for p, h in zip(prompts, handles):
            toks = list(h.stream(timeout=120))
            res = h.result(timeout=120)
            assert res.finish_reason == "length", (res.finish_reason,
                                                   res.error)
            assert toks == res.token_ids
            assert toks == _naive_greedy(params, p, n_tok), \
                "failover duplicated or lost tokens"
            delivered += len(toks)

        assert _wait(lambda: gov.snapshot()["team-a"]["inflight"] == 0)
        assert gov.charged_tokens("team-a") == delivered  # == 16 * 16
        remaining = gov.quota_remaining("team-a")
        assert remaining == pytest.approx(10_000.0 - delivered, abs=1.0)
        assert router.counters()["failovers"] >= 1
    finally:
        for r in reps:
            r.close()


@pytest.mark.slow  # live engine prefix caching; covered by make chaos-tenant
def test_engine_kv_namespace_blocks_cross_tenant_reuse(params):
    """Two tenants submit the identical prompt: the second tenant's lookup
    must structurally miss (disjoint digest chains), both outputs stay
    byte-exact, and the per-tenant block accounting sees both namespaces."""
    eng = _mk_engine(params)
    prompt = [(7 * i) % 290 + 3 for i in range(17)]  # crosses 2 full blocks
    oracle = _naive_greedy(params, prompt, 4)

    def run(rid, tenant):
        eng.submit(GenerationRequest(
            request_id=rid, prompt_ids=list(prompt),
            sampling=SamplingParams(max_tokens=4), tenant=tenant))
        _run(eng)
        return eng._results[rid].token_ids

    assert run("a1", "team-a") == oracle
    misses_after_a = eng.prefix_cache.misses
    assert eng.prefix_cache.hits == 0

    # Same tokens, different tenant: no cross-tenant hit, ever.
    assert run("b1", "team-b") == oracle
    assert eng.prefix_cache.hits == 0
    assert eng.prefix_cache.misses > misses_after_a

    # Same tenant does hit its own namespace.
    assert run("a2", "team-a") == oracle
    assert eng.prefix_cache.hits >= 1

    blocks = eng.kv_tier_stats()["tenant_blocks"]
    assert blocks.get("team-a", 0) > 0 and blocks.get("team-b", 0) > 0


@pytest.mark.slow  # 2 live engines; covered by make chaos-tenant
def test_install_prefix_refuses_tenant_mismatch(params):
    """KVX1 blobs carry their namespace: a receiver expecting another
    tenant refuses the install as a distinct outcome (no silent
    cross-tenant cache pollution on migration paths)."""
    src = _mk_engine(params)
    prompt = [(11 * i) % 290 + 3 for i in range(24)]
    src.submit(GenerationRequest(
        request_id="warm", prompt_ids=list(prompt),
        sampling=SamplingParams(max_tokens=2), tenant="team-a"))
    _run(src)
    blob = src.export_prefix(list(prompt), tenant="team-a")
    assert blob is not None

    dst = _mk_engine(params)
    assert dst.install_prefix(blob, expected_tenant="team-b") == \
        "tenant_mismatch"
    assert dst.prefix_cache.misses == 0 and dst.prefix_cache.hits == 0
    assert dst.install_prefix(blob, expected_tenant="team-a") == "installed"
    # expected_tenant=None: an unpinned install trusts the blob's header.
    assert dst.install_prefix(blob, expected_tenant=None) == "cached"


@pytest.mark.chaos
@pytest.mark.slow  # seeded faults + greedy oracle; make chaos-tenant
def test_mixed_tenant_burst_byte_exact_under_lane_eviction_faults(params):
    """Tenant isolation holds on the failure path too: a slot-starved
    mixed-tenant burst forces a class preemption whose seeded
    ``lane_eviction`` fault fires mid-eviction — every tenant's output
    stays byte-exact and the per-tenant block accounting stays sane."""
    eng = _mk_engine(params, max_slots=2)
    get_injector().reset(seed=1234)
    get_injector().arm("lane_eviction", rate=1.0, times=1)
    try:
        reqs = [("a-b0", "team-a", "batch", [5, 6, 7], 60),
                ("b-b1", "team-b", "batch", [8, 9, 10], 60),
                ("a-i0", "team-a", "interactive", [11, 12, 13], 6)]
        for rid, tenant, cls, p, n in reqs[:2]:
            eng.submit(GenerationRequest(
                request_id=rid, prompt_ids=list(p),
                sampling=SamplingParams(max_tokens=n),
                tenant=tenant, slo_class=cls))
        eng.step()
        eng.step()
        # The interactive arrival preempts a running batch lane; the
        # armed fault fails that eviction mid-flight and the retry
        # (injector exhausted) completes it.
        rid, tenant, cls, p, n = reqs[2]
        eng.submit(GenerationRequest(
            request_id=rid, prompt_ids=list(p),
            sampling=SamplingParams(max_tokens=n),
            tenant=tenant, slo_class=cls))
        _run(eng, max_steps=2000)
        assert get_injector().fired("lane_eviction") == 1
        for rid, tenant, cls, p, n in reqs:
            res = eng._results[rid]
            assert res.finish_reason == "length", (rid, res.finish_reason)
            assert res.token_ids == _naive_greedy(params, p, n), rid
        blocks = eng.kv_tier_stats()["tenant_blocks"]
        assert set(blocks) <= {"team-a", "team-b", DEFAULT_TENANT}
    finally:
        get_injector().reset()
