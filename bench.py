#!/usr/bin/env python
"""Single-chip serving benchmark — the north-star SLO tracker.

Measures p50 TTFT for a burst of concurrent diagnosis-sized queries through
the continuous-batching engine, decode throughput, and achieved MXU / HBM
utilization, and prints ONE JSON line:

    {"metric": "p50_ttft_100c_ms", "value": <ms>, "unit": "ms",
     "vs_baseline": <500ms / p50>, ...}

``vs_baseline`` is measured against the north-star SLO (p50 TTFT < 500 ms,
BASELINE.md / BASELINE.json north_star) since the reference publishes no
benchmark numbers of its own (verified in SURVEY.md §6): > 1.0 beats the SLO.

Model: **Llama-3-8B geometry with int8 weight-only quantization**
(utils/quantize.py) — the real BASELINE.md config #2/#4 target, which bf16
cannot fit on the 16 GB chip.  Weights are random-init (generated directly
in int8; the bf16 intermediate would not fit either) — the arithmetic,
shapes, and HBM traffic match the real checkpoint exactly.  Honest context:
the 500 ms SLO is defined for v5e-8 (8 chips, BASELINE.md config #4); this
bench drives ONE chip with the full 100-request burst, i.e. 8x the SLO's
per-chip load.  When more than one device is visible, the **mesh leg**
(``mesh_leg``) runs ONE tensor-parallel engine over all of them and reports
measured ``mesh_p50_ttft_ms`` / ``mesh_p99_ttft_ms`` / ``mesh_tok_s`` — the
apples-to-apples multi-chip numbers.  The old per-chip-equivalent leg
(100/8 -> 12 concurrent through one chip) remains in extras but is
informational only.  ``BENCH_MESH_ONLY=1`` (``make bench-mesh``) runs just
the mesh leg; off-TPU it executes on the forced-host-device mesh and is
flagged ``mesh_dryrun``.

A persistent XLA compilation cache (.jax_cache/) makes warm boots cheap;
the bench reports its warmup time and whether the cache was already
populated.

Run: ``python bench.py`` (uses the default JAX platform — the real TPU under
the driver; set BENCH_MODEL=llama-1b BENCH_CONCURRENCY=8 JAX_PLATFORMS=cpu
to shrink for local smoke runs).
"""

from __future__ import annotations

import json
import os
import sys
import time

# Single-tenant legs tag KV migrations with the default namespace
# explicitly (the tenant-namespace lint requires the kwarg everywhere).
# Pure-Python import: pulls no jax, so --help stays fast.
from k8s_llm_monitor_tpu.resilience.tenancy import (  # noqa: E402
    DEFAULT_TENANT as TEN,
)


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def bucket64(n: int) -> int:
    """Round ``n`` up to the 64-token prefill grid via the engine's own
    bucket rounding (serving.engine.prefill_bucket_for), so the bench's
    engine-sizing math can never drift from the admission path's."""
    from k8s_llm_monitor_tpu.serving.engine import prefill_bucket_for

    n = max(int(n), 1)
    ladder = tuple(64 * i for i in range(1, (n + 63) // 64 + 1))
    return prefill_bucket_for(n, ladder)


# Approximate chip peaks for utilization reporting, keyed by substrings of
# jax Device.device_kind.  (bf16 matmul TFLOP/s, HBM GB/s.)
CHIP_PEAKS = {
    "v5 lite": (197e12, 819e9),     # v5e
    "v5e": (197e12, 819e9),
    "v5p": (459e12, 2765e9),
    "v4": (275e12, 1228e9),
    "v6": (918e12, 1640e9),         # v6e (Trillium)
}


def chip_peaks(device_kind: str) -> tuple[float, float]:
    """(bf16 FLOP/s, HBM bytes/s) of a listed chip.  A device outside the
    table is an error, never a default: a utilization against somebody
    else's peak is not a measurement."""
    kind = device_kind.lower()
    for key, peaks in CHIP_PEAKS.items():
        if key in kind:
            return peaks
    raise ValueError(
        f"no peak figures for device kind {device_kind!r}; known: "
        f"{sorted(CHIP_PEAKS)}")


def weight_accounting(params, tied: bool) -> tuple[int, int]:
    """(matmul weight elements, streamed weight bytes per decode step).

    The untied embedding table is a pure gather — zero matmul FLOPs and
    only B rows of traffic per step — so it is excluded from both unless
    the model ties it to the unembed matmul.
    """
    import jax

    elems = 0
    stream_bytes = 0
    for path, leaf in jax.tree_util.tree_leaves_with_path(params):
        keys = [p.key for p in path if hasattr(p, "key")]
        if not keys:
            continue
        is_embed = "embed" in keys
        if keys[-1] in ("kernel", "kernel_q", "weight", "weight_q"):
            if is_embed and not tied:
                continue
            elems += leaf.size
            stream_bytes += leaf.size * leaf.dtype.itemsize
    return elems, stream_bytes


def fleet_leg(cfg, params) -> dict:
    """Fleet tier (fleet/router.py): 1 vs 2 in-process replicas behind the
    router — aggregate throughput and the per-request completion-latency
    tail, then the same 2-replica burst with hedged dispatch on.  The
    replicas share ``params`` (no extra weight copies); each gets its own
    small KV pool."""
    import numpy as np

    from k8s_llm_monitor_tpu.fleet import (
        FleetRouter,
        HedgeConfig,
        LocalReplica,
        ReplicaRegistry,
    )
    from k8s_llm_monitor_tpu.serving.engine import (
        EngineConfig,
        InferenceEngine,
        SamplingParams,
    )
    from k8s_llm_monitor_tpu.serving.service import EngineService

    rng = np.random.default_rng(7)
    f_len = int(os.environ.get("BENCH_FLEET_PROMPT_LEN", "64"))
    f_gen = int(os.environ.get("BENCH_FLEET_MAX_TOKENS", "32"))
    f_n = int(os.environ.get("BENCH_FLEET_CONCURRENCY", "16"))
    f_cap = f_len + f_gen + 16
    f_ecfg = EngineConfig(
        max_slots=8,
        num_blocks=8 * ((f_cap + 15) // 16) + 16,
        block_size=16,
        max_blocks_per_seq=(f_cap + 15) // 16,
        prefill_buckets=(f_len,),
        max_prefills_per_step=8,
        decode_steps_per_iter=4,
    )

    def f_prompt() -> list[int]:
        return [int(t) for t in
                rng.integers(4, cfg.vocab_size - 4, size=f_len)]

    def fleet_run(n_reps: int, hedge=None):
        reps = [
            LocalReplica(
                f"bench-r{i}",
                service=EngineService(
                    InferenceEngine(cfg, params, f_ecfg, eos_id=-1)))
            for i in range(n_reps)
        ]
        reg = ReplicaRegistry()
        for r in reps:
            reg.add(r)
        reg.refresh()
        router = FleetRouter(reg, policy="affinity", hedge=hedge)
        try:
            t_start = time.monotonic()
            flights = [(time.monotonic(),
                        router.submit(f_prompt(),
                                      SamplingParams(max_tokens=f_gen)))
                       for _ in range(f_n)]
            lat = []
            for t_sub, h in flights:
                res = h.result(timeout=600.0)
                assert res.finish_reason == "length", res.error
                lat.append(time.monotonic() - t_sub)
            wall = time.monotonic() - t_start
        finally:
            for r in reps:
                r.close()
        p99_ms = float(np.percentile(np.array(sorted(lat)), 99)) * 1e3
        return f_n * f_gen / wall, p99_ms, router.counters()

    one_tok_s, _, _ = fleet_run(1)
    log(f"fleet: 1 replica {one_tok_s:.1f} tok/s "
        f"({f_n} concurrent, gen {f_gen})")
    two_tok_s, unhedged_p99_ms, c2 = fleet_run(2)
    log(f"fleet: 2 replicas {two_tok_s:.1f} tok/s, unhedged p99 "
        f"completion {unhedged_p99_ms:.0f} ms "
        f"(affinity hits {c2['affinity_hits']}, "
        f"spills {c2['affinity_spills']})")
    _, hedged_p99_ms, ch = fleet_run(2, hedge=HedgeConfig(enabled=True))
    log(f"fleet: 2 replicas hedged p99 completion {hedged_p99_ms:.0f} ms "
        f"({ch['hedges_fired']} hedges fired, {ch['hedges_won']} won)")
    return {
        "fleet_1replica_tok_s": round(one_tok_s, 1),
        "fleet_2replica_tok_s": round(two_tok_s, 1),
        "fleet_unhedged_p99_completion_ms": round(unhedged_p99_ms, 1),
        "fleet_hedged_p99_completion_ms": round(hedged_p99_ms, 1),
        "fleet_hedges_fired": ch["hedges_fired"],
        "fleet_hedges_won": ch["hedges_won"],
        "fleet_affinity_hits": c2["affinity_hits"],
        "fleet_affinity_spills": c2["affinity_spills"],
        "fleet_concurrency": f_n,
    }


def tenant_fairness_leg(cfg, params) -> dict:
    """Multi-tenant fairness (resilience/tenancy.py): a Zipf-weighted
    population of quiet tenants with mixed SLO classes shares one engine
    with a flooding tenant submitting 10x its request quota, under seeded
    ``lane_eviction`` faults.  Gates (hard — a fairness regression IS a
    bench failure):

      * every flood refusal is a tenant-tagged 429 naming the flooder;
      * no quiet tenant is ever quota-refused or shed;
      * quiet interactive p99 TTFT stays <= 2x the solo (flood-free)
        baseline of the identical burst;
      * zero lost tokens: the governor's settled charge equals the tokens
        each tenant's streams actually delivered;
      * byte-exact: every quiet stream reproduces its solo-baseline
        output despite the faults and the contention.
    """
    import numpy as np

    from k8s_llm_monitor_tpu.resilience.errors import OverloadedError
    from k8s_llm_monitor_tpu.resilience.faults import get_injector
    from k8s_llm_monitor_tpu.resilience.tenancy import TenantGovernor
    from k8s_llm_monitor_tpu.serving.engine import (
        EngineConfig,
        InferenceEngine,
        SamplingParams,
    )
    from k8s_llm_monitor_tpu.serving.service import EngineService

    rng = np.random.default_rng(17)
    t_len = int(os.environ.get("BENCH_TENANT_PROMPT_LEN", "64"))
    t_gen = int(os.environ.get("BENCH_TENANT_MAX_TOKENS", "16"))
    t_n = int(os.environ.get("BENCH_TENANT_CONCURRENCY", "24"))
    ttft_budget = float(os.environ.get("BENCH_TENANT_TTFT_BUDGET", "2.0"))
    t_cap = t_len + t_gen + 16
    t_ecfg = EngineConfig(
        max_slots=8,
        num_blocks=8 * ((t_cap + 15) // 16) + 16,
        block_size=16,
        max_blocks_per_seq=(t_cap + 15) // 16,
        prefill_buckets=(t_len,),
        max_prefills_per_step=8,
        decode_steps_per_iter=4,
    )

    # Zipf-weighted quiet tenants (rank-r tenant gets ~1/r of the load)
    # with SLO classes round-robined across the burst; prompts are fixed
    # up front so the contended run must reproduce the solo bytes.
    quiet = ("team-a", "team-b", "team-c", "team-d")
    zipf = np.array([1.0 / (r + 1) for r in range(len(quiet))])
    zipf /= zipf.sum()
    classes = ("interactive", "standard", "batch")
    plan = []
    for i in range(t_n):
        plan.append((
            quiet[int(rng.choice(len(quiet), p=zipf))],
            classes[i % len(classes)],
            [int(t) for t in rng.integers(4, cfg.vocab_size - 4,
                                          size=t_len)],
        ))
    per_tenant = {t: sum(1 for ten, _, _ in plan if ten == t)
                  for t in quiet}
    # Quota sized so every quiet tenant fits with headroom and the
    # flooder's 10x burst mostly does not.
    req_burst = float(max(per_tenant.values()) + 2)
    flood_n = int(10 * req_burst)

    def run_burst(svc, *, flood: bool):
        flood_429 = 0
        flood_handles = []
        if flood:
            for j in range(flood_n):
                p = [int(t) for t in rng.integers(4, cfg.vocab_size - 4,
                                                  size=t_len)]
                try:
                    flood_handles.append(svc.submit(
                        p, SamplingParams(max_tokens=t_gen),
                        request_id=f"flood-{j}", tenant="flood",
                        slo_class="batch"))
                except OverloadedError as exc:
                    assert exc.tenant == "flood", \
                        "flood refusal not tagged with the flooder"
                    assert exc.retriable and exc.retry_after_s > 0
                    flood_429 += 1
        handles = [(ten, c, svc.submit(
            list(p), SamplingParams(max_tokens=t_gen),
            request_id=f"q{i}-{'c' if flood else 's'}", tenant=ten,
            slo_class=c)) for i, (ten, c, p) in enumerate(plan)]
        results = []
        for ten, c, h in handles:
            res = h.result(timeout=600.0)
            assert res.finish_reason == "length", (ten, res.error)
            assert len(res.token_ids) == t_gen, "lost tokens"
            results.append((ten, c, res))
        flood_delivered = 0
        for h in flood_handles:
            res = h.result(timeout=600.0)
            if res.finish_reason == "length":
                flood_delivered += len(res.token_ids)
        return results, flood_429, len(flood_handles), flood_delivered

    def p99_interactive(results):
        ttfts = sorted(r.ttft_s for _, c, r in results
                       if c == "interactive")
        return float(np.percentile(np.array(ttfts), 99))

    # Solo baseline: the identical quiet burst, no flood, no faults.
    svc = EngineService(InferenceEngine(cfg, params, t_ecfg, eos_id=-1))
    try:
        base, _, _, _ = run_burst(svc, flood=False)
    finally:
        svc.stop(timeout=30)
    solo_p99 = p99_interactive(base)
    log(f"tenant: solo baseline interactive p99 TTFT "
        f"{solo_p99 * 1e3:.1f} ms ({t_n} quiet reqs over {len(quiet)} "
        f"Zipf tenants)")

    gov = TenantGovernor(requests_per_s=0.5, request_burst=req_burst,
                         tokens_per_s=float(t_gen),
                         token_burst=req_burst * t_gen * 4.0)
    svc = EngineService(InferenceEngine(cfg, params, t_ecfg, eos_id=-1),
                        governor=gov)
    get_injector().reset(seed=4321)
    get_injector().arm("lane_eviction", rate=0.1, times=3)
    try:
        contended, flood_429, flood_ok, flood_delivered = run_burst(
            svc, flood=True)
    finally:
        svc.stop(timeout=30)
        get_injector().reset()

    # The flooder was rate-limited (10x quota: most submissions refused)
    # and within-quota tenants never felt it.
    assert flood_429 > 0, "flood was never rate-limited"
    snap = gov.snapshot()
    assert snap["flood"]["quota_refusals"] == flood_429
    for t in quiet:
        assert snap[t]["quota_refusals"] == 0, f"{t} was quota-refused"
        assert snap[t]["sheds"] == 0, f"{t} was shed by the flood"

    # Byte-exact under faults + contention, and charged == delivered.
    delivered = {t: 0 for t in quiet}
    for (ten, _, solo_r), (ten2, _, cont_r) in zip(base, contended):
        assert ten == ten2
        assert cont_r.token_ids == solo_r.token_ids, \
            f"{ten}: contended output diverged from solo baseline"
        delivered[ten] += len(cont_r.token_ids)
    deadline = time.monotonic() + 10.0
    while (any(v["inflight"] for v in gov.snapshot().values())
           and time.monotonic() < deadline):
        time.sleep(0.05)
    for t in quiet:
        assert gov.charged_tokens(t) == delivered[t], \
            f"{t}: charged {gov.charged_tokens(t)} != delivered"
    assert gov.charged_tokens("flood") == flood_delivered

    cont_p99 = p99_interactive(contended)
    ratio = cont_p99 / max(solo_p99, 1e-9)
    log(f"tenant: contended interactive p99 TTFT {cont_p99 * 1e3:.1f} ms "
        f"= {ratio:.2f}x solo ({flood_429}/{flood_n} flood reqs 429'd, "
        f"{flood_ok} admitted, {get_injector().fired('lane_eviction')} "
        f"lane_eviction faults fired)")
    assert ratio <= ttft_budget, (
        f"flood degraded quiet interactive p99 TTFT {ratio:.2f}x "
        f"(budget {ttft_budget}x)")
    return {
        "tenant_interactive_p99_ttft_ratio": round(ratio, 3),
        "tenant_solo_p99_ttft_ms": round(solo_p99 * 1e3, 2),
        "tenant_contended_p99_ttft_ms": round(cont_p99 * 1e3, 2),
        "tenant_flood_429s": flood_429,
        "tenant_flood_submitted": flood_n,
        "tenant_flood_admitted": flood_ok,
        "tenant_quiet_requests": t_n,
        "tenant_quiet_tenants": len(quiet),
        "tenant_lost_tokens": 0,
        "tenant_byte_exact": True,
    }


def remediation_leg(cfg, params) -> dict:
    """Closed-loop remediation (remediation/): two measurements.

    **Recovery latency** — a template-backend monitor server on a seeded
    FakeCluster runs the four chaos scenarios (crash loop, OOM, stale
    scheduler, node pressure) end to end: warning burst -> diagnosis ->
    constrained plan -> dry-run -> execute -> verification turn.  Reports
    inject->verified wall time per scenario.  Faults are injected purely
    as cluster-state mutations; every kube write goes through
    RemediationEngine (the raw-kube-write lint sweeps this file too).

    **Plan-decode overhead** — FSM-constrained plan decode vs free decode
    on the same engine geometry.  The per-step cost is one (state, token)
    mask gather; gate (hard): < 10% tok/s penalty.  Uses a dedicated
    vocab-300 tiny model (``cfg`` is ignored): the 259-token byte
    alphabet of the plan grammar does not fit the 256-entry tiny preset.
    """
    import jax

    from k8s_llm_monitor_tpu.models import llama
    from k8s_llm_monitor_tpu.models.config import ModelConfig
    from k8s_llm_monitor_tpu.monitor.cluster import (
        FakeCluster,
        seed_demo_cluster,
    )
    from k8s_llm_monitor_tpu.monitor.config import Config
    from k8s_llm_monitor_tpu.monitor.models import EventInfo
    from k8s_llm_monitor_tpu.monitor.server import build_server
    from k8s_llm_monitor_tpu.remediation import (
        TargetSnapshot,
        parse_plan,
        plan_fsm,
    )
    from k8s_llm_monitor_tpu.serving.engine import (
        EngineConfig,
        InferenceEngine,
        SamplingParams,
    )
    from k8s_llm_monitor_tpu.utils.tokenizer import ByteTokenizer

    stats: dict = {}

    # -- part 1: inject -> verified-recovery latency, four scenarios --------
    mcfg = Config()
    mcfg.llm.provider = "template"
    mcfg.diagnosis.burst_threshold = 3
    mcfg.diagnosis.window_s = 60.0
    mcfg.diagnosis.cooldown_s = 0.0
    mcfg.remediation.execute = True
    mcfg.remediation.verify = True
    mcfg.remediation.verb_interval_s = 0.0
    mcfg.remediation.target_interval_s = 0.0
    backend = seed_demo_cluster(FakeCluster())
    backend.add_statefulset("engine-decode", replicas=2)
    srv = build_server(mcfg, backend=backend)
    srv.start()
    # Destructive verbs (delete_pod, cordon) refuse without an approval;
    # the bench measures the full closed loop, so grant the env approval
    # for its duration (and restore whatever the caller had).
    saved_approve = os.environ.get("K8SLLM_REMEDIATE_APPROVE")
    os.environ["K8SLLM_REMEDIATE_APPROVE"] = "1"

    def run_scenario(name, mutate, reason, message, want_verb, want_name):
        mutate()
        t0 = time.monotonic()
        for i in range(4):
            srv.diagnosis.handler.on_event(EventInfo(
                type="Warning", reason=reason,
                message=f"{message} (try {i})", source="bench"))
        deadline = t0 + 60.0
        while time.monotonic() < deadline:
            for rec in srv.remediation.records():
                if rec["plan"]["verb"] == want_verb \
                        and rec["plan"]["name"] == want_name \
                        and rec["status"] == "verified":
                    ms = (time.monotonic() - t0) * 1e3
                    stats[f"remediation_recovery_ms_{name}"] = round(ms, 2)
                    log(f"remediate: {name} -> {want_verb}/{want_name} "
                        f"verified in {ms:.1f} ms")
                    return
            time.sleep(0.01)
        raise AssertionError(
            f"remediate: {name} never verified; records "
            f"{[(r['plan']['verb'], r['status']) for r in srv.remediation.records()]}")

    try:
        run_scenario(
            "crash_loop",
            lambda: backend.update_pod("default", "web-frontend-7d4b9c6f5-x2x1p",
                                       phase="CrashLoopBackOff"),
            "BackOff",
            "Back-off restarting failed container in web-frontend",
            "rollout_restart", "web-frontend")
        run_scenario(
            "oom",
            lambda: backend.update_pod("default", "api-backend-6f5d8b7c9-k3k2m",
                                       phase="OOMKilled"),
            "OOMKilling", "Memory cgroup out of memory: api-backend",
            "rollout_restart", "api-backend")
        run_scenario(
            "stale_scheduler",
            lambda: backend.add_pod("batch-runner-5f7d8", phase="Pending",
                                    node=""),
            "FailedScheduling",
            "0/3 nodes available, unschedulable pod batch-runner-5f7d8 "
            "stuck Pending (stale scheduler cache)",
            "delete_pod", "batch-runner-5f7d8")
        run_scenario(
            "node_pressure",
            lambda: None,  # pressure arrives as events, not pod state
            "NodeHasMemoryPressure",
            "Node k3d-demo-agent-1 status is now: NodeHasMemoryPressure",
            "cordon", "k3d-demo-agent-1")
    finally:
        if saved_approve is None:
            os.environ.pop("K8SLLM_REMEDIATE_APPROVE", None)
        else:
            os.environ["K8SLLM_REMEDIATE_APPROVE"] = saved_approve
        srv.stop()
    stats["remediation_scenarios_verified"] = 4

    # -- part 2: constrained plan decode vs free decode ----------------------
    overhead_budget = float(os.environ.get("BENCH_REMEDIATE_BUDGET", "10.0"))
    reps = int(os.environ.get("BENCH_REMEDIATE_REPS", "4"))
    # Wide enough that the model step dominates: on a hidden-32 toy the
    # per-step mask gather alone reads as ~15% because the matmuls are
    # microscopic, which says nothing about serving-sized models.
    r_cfg = ModelConfig(name="tiny", vocab_size=300, hidden_size=128,
                        intermediate_size=256, num_layers=4, num_heads=4,
                        num_kv_heads=2, dtype="float32", rope_theta=1e4)
    tok = ByteTokenizer()
    r_params = llama.init_params(jax.random.PRNGKey(0), r_cfg)
    engine = InferenceEngine(
        r_cfg, r_params,
        EngineConfig(max_slots=4, num_blocks=512, block_size=16,
                     max_blocks_per_seq=128, prefill_buckets=(64,),
                     decode_steps_per_iter=4),
        tokenizer=tok)
    snap = TargetSnapshot.from_backend(backend, ["default"])
    engine.set_grammar(plan_fsm(snap, eos_id=tok.eos_id))
    prompts = [tok.encode("## Plan\nchoose one action:\n")] * 4

    def run_once(constrained, max_tokens):
        t0 = time.monotonic()
        results = engine.generate(
            prompts,
            SamplingParams(max_tokens=max_tokens, temperature=0.0,
                           constrained=constrained))
        dt = time.monotonic() - t0
        return sum(len(r.token_ids) for r in results) / dt, results

    # Warm both programs, and size the free run to the constrained plan
    # length so prefill amortization matches between the two modes.
    _, probe = run_once(True, 1)
    for res in probe:
        plan = parse_plan(tok.decode(res.token_ids), snap)
        assert plan["verb"], "constrained probe produced no plan"
    plan_len = max(8, round(sum(len(r.token_ids) for r in probe)
                            / len(probe)))
    run_once(False, plan_len)

    cons_tok_s = max(run_once(True, 1)[0] for _ in range(reps))
    free_tok_s = max(run_once(False, plan_len)[0] for _ in range(reps))
    overhead = max(0.0, (free_tok_s - cons_tok_s) / free_tok_s * 100.0)
    log(f"remediate: plan decode {cons_tok_s:.0f} tok/s constrained vs "
        f"{free_tok_s:.0f} free ({plan_len}-token plans) -> "
        f"{overhead:.2f}% overhead")
    assert overhead < overhead_budget, (
        f"plan-constrained decode costs {overhead:.2f}% tok/s "
        f"(budget {overhead_budget}%)")
    stats.update({
        "remediation_plan_overhead_pct": round(overhead, 2),
        "remediation_plan_tok_s_constrained": round(cons_tok_s, 1),
        "remediation_plan_tok_s_free": round(free_tok_s, 1),
        "remediation_plan_len_tokens": plan_len,
    })
    return stats


def kv_tier_leg(cfg, params) -> dict:
    """KV-tier rung 1 (serving/kv_tier.py): int8 resident KV must hold
    >= 1.8x the decode lanes of the model-dtype pool on the SAME pool
    bytes.  The byte math is exact (kv_cache.py:page_slice_bytes, scales
    included); the engine pair proves it end-to-end: two engines whose
    ``num_blocks`` are sized from one shared byte budget drain the same
    burst, and the peak concurrently-resident lane counts are compared.
    A greedy parity sample on identical prompts rides along (the
    tolerance-gated divergence budget lives in tests/test_kv_tier.py);
    a spill/restore pass exercises rung 2 and reports its counters."""
    import numpy as np

    from k8s_llm_monitor_tpu.serving.engine import (
        EngineConfig,
        GenerationRequest,
        InferenceEngine,
        SamplingParams,
    )
    from k8s_llm_monitor_tpu.serving.kv_cache import page_slice_bytes

    bs = 16
    model_itemsize = np.dtype(cfg.kv_dtype or cfg.dtype).itemsize
    page_model = page_slice_bytes(cfg.num_kv_heads, cfg.head_dim_, bs,
                                  model_itemsize, scale_bytes=0)
    page_int8 = page_slice_bytes(cfg.num_kv_heads, cfg.head_dim_, bs, 1,
                                 scale_bytes=4)
    byte_ratio = page_model / page_int8

    k_len, k_gen = 64, 40
    cap = k_len + k_gen + 1
    bps = (cap + bs - 1) // bs
    blocks_model = 4 * bps + 2              # 4 resident lanes + slack
    budget = blocks_model * page_model      # per (layer, k/v) slice
    blocks_int8 = budget // page_int8
    rng = np.random.default_rng(13)
    prompts = [[int(t) for t in rng.integers(4, cfg.vocab_size - 4,
                                             size=k_len)]
               for _ in range(16)]

    def run(kv_dtype: str, num_blocks: int):
        ecfg = EngineConfig(
            max_slots=16, num_blocks=int(num_blocks), block_size=bs,
            max_blocks_per_seq=bps, prefill_buckets=(k_len,),
            max_prefills_per_step=4, decode_steps_per_iter=4,
            prefix_cache_entries=0, kv_dtype=kv_dtype)
        eng = InferenceEngine(cfg, params, ecfg, eos_id=-1)
        eng.generate([prompts[0]], SamplingParams(max_tokens=4))  # warm
        for i, p in enumerate(prompts):
            eng.submit(GenerationRequest(
                request_id=f"kv-{i}", prompt_ids=p,
                sampling=SamplingParams(max_tokens=k_gen)))
        peak = 0
        while eng.has_work:
            eng.step()
            peak = max(peak, eng.active_slots)
        res = [eng.poll(f"kv-{i}") for i in range(len(prompts))]
        assert all(r is not None and r.finish_reason != "error"
                   for r in res)
        streams = [r.token_ids for r in res]
        del eng
        return peak, streams

    lanes_model, ref_streams = run("auto", blocks_model)
    lanes_int8, q_streams = run("int8", blocks_int8)
    lanes_ratio = lanes_int8 / max(lanes_model, 1)
    # Greedy agreement prefix across the identical-prompt streams: int8
    # dequant error can flip near-tied argmaxes, so this is a sample, not
    # a gate (the gated budget is test_kv_tier.py's parity test).
    agree = []
    for a, b in zip(ref_streams, q_streams):
        m = 0
        while m < min(len(a), len(b)) and a[m] == b[m]:
            m += 1
        agree.append(m / max(len(a), 1))
    parity = float(np.median(agree))
    log(f"kv tier: int8 page {page_int8} B vs {cfg.kv_dtype or cfg.dtype} "
        f"{page_model} B ({byte_ratio:.2f}x byte ratio); peak resident "
        f"lanes {lanes_int8} vs {lanes_model} ({lanes_ratio:.2f}x) on "
        f"{budget * 2 * cfg.num_layers / 2**20:.1f} MiB pool; greedy "
        f"parity prefix {parity:.2f}")

    # Rung 2 spill/restore: a pool that holds ~2 cached prefixes cycles
    # through 4, so pressured evictions spill to the host tier and the
    # second pass restores instead of re-prefilling.
    spills = restores = -1
    try:
        # Pool sized well under 6 resident prefixes: cycling 6 distinct
        # prefixes forces pressured evictions (spills); the second pass
        # rehydrates the spilled ones instead of re-prefilling.
        s_ecfg = EngineConfig(
            max_slots=4, num_blocks=2 * bps + 2, block_size=bs,
            max_blocks_per_seq=bps, prefill_buckets=(k_len,),
            max_prefills_per_step=2, decode_steps_per_iter=4,
            kv_dtype="int8", host_spill_bytes=256 << 20)
        seng = InferenceEngine(cfg, params, s_ecfg, eos_id=-1)
        for _round in range(2):
            for p in prompts[:6]:
                seng.generate([p], SamplingParams(max_tokens=4))
        st = seng.kv_tier_stats()
        spills, restores = st["spills"], st["restores"]
        log(f"kv tier spill/restore: {spills} spills, {restores} restores,"
            f" host {st['host_bytes'] / 2**20:.1f} MiB "
            f"({st['host_entries']} entries)")
        del seng
    except Exception as exc:  # noqa: BLE001 — extras never fail the bench
        log(f"kv tier spill pass skipped: {exc}")
    return {
        "kv_tier_page_bytes_model": page_model,
        "kv_tier_page_bytes_int8": page_int8,
        "kv_tier_byte_ratio": round(byte_ratio, 3),
        "kv_tier_resident_lanes_model": lanes_model,
        "kv_tier_resident_lanes_int8": lanes_int8,
        "kv_tier_lanes_ratio": round(lanes_ratio, 3),
        "kv_tier_parity_prefix": round(parity, 3),
        "kv_spills": spills,
        "kv_restores": restores,
    }


def migration_leg(cfg, params) -> dict:
    """KV-tier rung 3 (fleet/router.py): on a prefix-affinity miss the
    router moves the owning replica's shared KV pages to the target
    instead of re-prefilling.  This leg measures the miss TTFT both ways
    on identical prompts — cold re-prefill on one replica vs
    fetch+install+decode on another — with every compiled shape warmed
    first, so the ratio is pure scheduling + page movement."""
    import numpy as np

    from k8s_llm_monitor_tpu.fleet import LocalReplica
    from k8s_llm_monitor_tpu.serving.engine import (
        EngineConfig,
        InferenceEngine,
        SamplingParams,
    )
    from k8s_llm_monitor_tpu.serving.service import EngineService

    m_len = int(os.environ.get("BENCH_MIG_PROMPT_LEN", "769"))
    cap = m_len + 24
    ecfg = EngineConfig(
        max_slots=4, num_blocks=4 * ((cap + 15) // 16) + 16, block_size=16,
        max_blocks_per_seq=(cap + 15) // 16, prefill_buckets=(64,),
        max_prefills_per_step=2, decode_steps_per_iter=4)

    def rep(name: str) -> LocalReplica:
        return LocalReplica(name, service=EngineService(
            InferenceEngine(cfg, params, ecfg, eos_id=-1)))

    rng = np.random.default_rng(17)

    def mk_prompt() -> list[int]:
        return [int(t) for t in
                rng.integers(4, cfg.vocab_size - 4, size=m_len)]

    warm, warm2, p = mk_prompt(), mk_prompt(), mk_prompt()
    owner, cold, target = rep("mig-owner"), rep("mig-cold"), rep("mig-tgt")
    try:
        sp = SamplingParams(max_tokens=4)
        for r in (owner, cold, target):
            # Two passes: the first compiles the chunk-round programs, the
            # second (a prefix hit) compiles the suffix-sized hit path.
            r.generate(warm, sp).result(timeout=600.0)
            r.generate(warm, sp).result(timeout=600.0)
        # Warm the move path itself: export on the owner and install on the
        # target each compile a one-time gather/scatter program (~100+ ms)
        # that must not be billed to the measured migration.  The warmup
        # blob is a prefix the target has NOT seen — installing an
        # already-cached prefix short-circuits before the scatter.
        owner.generate(warm2, sp).result(timeout=600.0)
        wblob = owner.fetch_prefix(warm2, tenant=TEN)
        assert wblob is not None and target.install_prefix(
            wblob, tenant=TEN) == "installed"
        owner.generate(p, sp).result(timeout=600.0)   # owner caches p
        reprefill_s = cold.generate(p, sp).result(timeout=600.0).ttft_s
        t0 = time.monotonic()
        blob = owner.fetch_prefix(p, tenant=TEN)
        assert blob is not None, "owner lost the prefix"
        outcome = target.install_prefix(blob, tenant=TEN)
        assert outcome == "installed", outcome
        move_s = time.monotonic() - t0
        migration_s = move_s + target.generate(p, sp).result(
            timeout=600.0).ttft_s
    finally:
        for r in (owner, cold, target):
            r.close()
    ratio = migration_s / max(reprefill_s, 1e-9)
    log(f"prefix migration ({m_len}-token prompt, {len(blob)} B blob): "
        f"miss TTFT {migration_s * 1e3:.1f} ms migrated "
        f"(fetch+install {move_s * 1e3:.1f} ms) vs {reprefill_s * 1e3:.1f} "
        f"ms re-prefilled ({ratio:.2f}x; budget <= 0.5x)")
    return {
        "migration_ttft_ms": round(migration_s * 1e3, 2),
        "migration_reprefill_ttft_ms": round(reprefill_s * 1e3, 2),
        "migration_ttft_ratio": round(ratio, 3),
        "migration_blob_bytes": len(blob),
        "migration_prompt_len": m_len,
    }


def tracing_leg(cfg, params) -> dict:
    """Tracing overhead (observability/tracing.py): the identical burst
    through one engine with span recording fully sampled vs fully off.
    The delta is the acceptance number — default sampling must cost <2%
    tok/s.  A throwaway warm-up run absorbs per-engine jit/compile cost
    so both measured runs see the same caches."""
    import numpy as np

    from k8s_llm_monitor_tpu.observability.tracing import (
        Tracer,
        get_tracer,
        set_tracer,
    )
    from k8s_llm_monitor_tpu.serving.engine import (
        EngineConfig,
        InferenceEngine,
        SamplingParams,
    )
    from k8s_llm_monitor_tpu.serving.service import EngineService

    rng = np.random.default_rng(11)
    t_len = int(os.environ.get("BENCH_TRACE_PROMPT_LEN", "64"))
    t_gen = int(os.environ.get("BENCH_TRACE_MAX_TOKENS", "32"))
    t_n = int(os.environ.get("BENCH_TRACE_CONCURRENCY", "16"))
    t_cap = t_len + t_gen + 16
    t_ecfg = EngineConfig(
        max_slots=8,
        num_blocks=8 * ((t_cap + 15) // 16) + 16,
        block_size=16,
        max_blocks_per_seq=(t_cap + 15) // 16,
        prefill_buckets=(t_len,),
        max_prefills_per_step=8,
        decode_steps_per_iter=4,
    )
    prompts = [[int(t) for t in
                rng.integers(4, cfg.vocab_size - 4, size=t_len)]
               for _ in range(t_n)]

    def run_once(sample: float) -> tuple[float, int]:
        tracer = Tracer(sample=sample, seed=11)
        set_tracer(tracer)
        svc = EngineService(InferenceEngine(cfg, params, t_ecfg, eos_id=-1))
        try:
            t0 = time.monotonic()
            handles = [svc.submit(p, SamplingParams(max_tokens=t_gen))
                       for p in prompts]
            for h in handles:
                res = h.result(timeout=600.0)
                assert res.finish_reason == "length", res.error
            wall = time.monotonic() - t0
        finally:
            svc.stop(timeout=10.0)
        return t_n * t_gen / wall, tracer.recorded

    # Interleaved best-of-N pairs: per-span cost is microseconds, so on a
    # small config a single pair is dominated by scheduler/alloc noise.
    # Best-of filters that noise from both sides of the comparison.
    reps = int(os.environ.get("BENCH_TRACE_REPS", "3"))
    prev = get_tracer()
    off_tok_s, on_tok_s, spans = 0.0, 0.0, 0
    try:
        run_once(1.0)  # warm-up, discarded
        for _ in range(reps):
            off, _ = run_once(0.0)
            on, n_spans = run_once(1.0)
            off_tok_s = max(off_tok_s, off)
            if on > on_tok_s:
                on_tok_s, spans = on, n_spans
    finally:
        set_tracer(prev)
    overhead_pct = (100.0 * (off_tok_s - on_tok_s) / off_tok_s
                    if off_tok_s > 0 else 0.0)
    log(f"tracing: sampled {on_tok_s:.1f} tok/s vs off {off_tok_s:.1f} "
        f"tok/s ({overhead_pct:+.2f}% overhead, {spans} spans; "
        f"budget < 2%)")
    return {
        "tracing_off_tok_s": round(off_tok_s, 1),
        "tracing_sampled_tok_s": round(on_tok_s, 1),
        "tracing_overhead_pct": round(overhead_pct, 2),
        "tracing_spans_recorded": spans,
        "tracing_overhead_budget_pct": 2.0,
    }


def signals_leg(cfg, params) -> dict:
    """Telemetry-plane overhead (observability/signals.py): the identical
    burst through one engine with the signal scraper sampling at 40x the
    default cadence vs no scraper at all.  The delta is the acceptance
    number — the scraper must cost < 1% tok/s (it reads a handful of
    counters per pass; anything visible means it grew a hot path).  The
    final derived-signal snapshot rides along in the extras, so the bench
    JSON doubles as a fleet-signal fixture."""
    import types

    import numpy as np

    from k8s_llm_monitor_tpu.monitor.config import TelemetryConfig
    from k8s_llm_monitor_tpu.observability.signals import SignalScraper
    from k8s_llm_monitor_tpu.serving.engine import (
        EngineConfig,
        InferenceEngine,
        SamplingParams,
    )
    from k8s_llm_monitor_tpu.serving.service import EngineService

    rng = np.random.default_rng(19)
    s_len = int(os.environ.get("BENCH_SIGNALS_PROMPT_LEN", "64"))
    s_gen = int(os.environ.get("BENCH_SIGNALS_MAX_TOKENS", "32"))
    s_n = int(os.environ.get("BENCH_SIGNALS_CONCURRENCY", "16"))
    s_cap = s_len + s_gen + 16
    s_ecfg = EngineConfig(
        max_slots=8,
        num_blocks=8 * ((s_cap + 15) // 16) + 16,
        block_size=16,
        max_blocks_per_seq=(s_cap + 15) // 16,
        prefill_buckets=(s_len,),
        max_prefills_per_step=8,
        decode_steps_per_iter=4,
    )
    prompts = [[int(t) for t in
                rng.integers(4, cfg.vocab_size - 4, size=s_len)]
               for _ in range(s_n)]
    last_signals: dict = {}

    def run_once(scrape: bool) -> float:
        nonlocal last_signals
        svc = EngineService(InferenceEngine(cfg, params, s_ecfg, eos_id=-1))
        scraper = None
        if scrape:
            scraper = SignalScraper(cfg=TelemetryConfig(
                scrape_interval_s=0.05))
            scraper.attach(types.SimpleNamespace(
                engine_service=lambda: svc, fleet_router=lambda: None))
            scraper.start()
        try:
            t0 = time.monotonic()
            handles = [svc.submit(p, SamplingParams(max_tokens=s_gen))
                       for p in prompts]
            for h in handles:
                res = h.result(timeout=600.0)
                assert res.finish_reason == "length", res.error
            wall = time.monotonic() - t0
        finally:
            if scraper is not None:
                scraper.scrape_once()  # final post-drain sample
                last_signals = scraper.signals()
                scraper.stop()
            svc.stop(timeout=10.0)
        return s_n * s_gen / wall

    # Interleaved best-of-N, same rationale as the tracing leg: the
    # scraper's per-pass cost is microseconds of counter reads, so a
    # single pair is pure scheduler noise at this engine size.
    reps = int(os.environ.get("BENCH_SIGNALS_REPS", "3"))
    off_tok_s = on_tok_s = 0.0
    run_once(False)  # warm-up, discarded
    for _ in range(reps):
        off_tok_s = max(off_tok_s, run_once(False))
        on_tok_s = max(on_tok_s, run_once(True))
    overhead_pct = (100.0 * (off_tok_s - on_tok_s) / off_tok_s
                    if off_tok_s > 0 else 0.0)
    scraper_stats = last_signals.get("scraper") or {}
    local = (last_signals.get("targets") or {}).get("local") or {}
    log(f"signals: scraped {on_tok_s:.1f} tok/s vs off {off_tok_s:.1f} "
        f"tok/s ({overhead_pct:+.2f}% overhead, "
        f"{scraper_stats.get('scrapes', 0)} scrapes, "
        f"{scraper_stats.get('series', 0)} series; budget < 1%)")
    assert overhead_pct < 1.0, (
        f"signal scraper overhead {overhead_pct:.2f}% exceeds the 1% "
        f"budget ({on_tok_s:.1f} vs {off_tok_s:.1f} tok/s)")
    return {
        "signals_off_tok_s": round(off_tok_s, 1),
        "signals_on_tok_s": round(on_tok_s, 1),
        "signals_overhead_pct": round(overhead_pct, 2),
        "signals_overhead_budget_pct": 1.0,
        "signals_scrapes": scraper_stats.get("scrapes", 0),
        "signals_series": scraper_stats.get("series", 0),
        # The local target's derived block from the drained burst — the
        # autoscaler-contract shape, persisted with the bench artifact.
        "signals_snapshot": {
            "scale_hint": local.get("scale_hint"),
            "queue_tokens_total": local.get("queue_tokens_total"),
            "queue_growth_total_tok_per_s":
                local.get("queue_growth_total_tok_per_s"),
            "brownout_dwell": local.get("brownout_dwell"),
            "headroom_tokens": local.get("headroom_tokens"),
            "anomalies": local.get("anomalies"),
        },
    }


def elasticity_leg(cfg, params) -> dict:
    """Disaggregated-fleet elasticity smoke (fleet/autoscaler.py +
    docs/fleet.md "Disaggregated roles & autoscaling").  Three numbers:

    - reaction: wall time from a scale_hint flipping "up" to the
      controller invoking the executor (sense→decide through the gate
      ladder), with the warm-spawn time reported separately — replica
      cold-start dominates real reaction and deserves its own line.
    - churn-vs-steady TTFT p99: the identical burst with the controller
      idle vs with a scale-up AND a drain-based scale-down landing
      mid-burst.  Elasticity must not wreck the interactive tail.
    - handoff-vs-local-prefill TTFT: first-token latency continuing a
      prompt whose KV prefix was exported/installed (suffix-only prefill)
      vs re-prefilling the same prompt cold.  The point of shipping KV is
      that this ratio stays <= 0.5x — asserted.
    """
    import threading

    import numpy as np

    from k8s_llm_monitor_tpu.fleet import (
        AutoscaleController,
        FleetRouter,
        LocalPoolExecutor,
        LocalReplica,
        ReplicaRegistry,
    )
    from k8s_llm_monitor_tpu.monitor.config import AutoscaleConfig
    from k8s_llm_monitor_tpu.serving.engine import (
        EngineConfig,
        InferenceEngine,
        SamplingParams,
    )
    from k8s_llm_monitor_tpu.serving.service import EngineService

    e_len = int(os.environ.get("BENCH_ELASTIC_PROMPT_LEN", "64"))
    e_gen = int(os.environ.get("BENCH_ELASTIC_MAX_TOKENS", "8"))
    e_n = int(os.environ.get("BENCH_ELASTIC_CONCURRENCY", "12"))
    seq_blocks = (e_len + e_gen) // 8 + 4
    ecfg = EngineConfig(
        max_slots=4,
        num_blocks=8 * seq_blocks + 16,
        block_size=8,
        max_blocks_per_seq=seq_blocks,
        prefill_buckets=(16, e_len),
        max_prefills_per_step=4,
        decode_steps_per_iter=4,
    )
    rng = np.random.default_rng(31)

    def rand_prompt(n):
        return [int(t) for t in rng.integers(4, cfg.vocab_size - 4, size=n)]

    def warm(rep):
        # Compile the full-prefill AND the suffix-only prefill path (what
        # a handoff continuation runs) before any measured dispatch.
        w = rand_prompt(e_len)
        first = rep.generate(w, SamplingParams(max_tokens=2)).result(
            timeout=600.0)
        rep.generate(w + first.token_ids[:1],
                     SamplingParams(max_tokens=2)).result(timeout=600.0)

    def new_replica(role, rid):
        eng = InferenceEngine(cfg, params, ecfg, eos_id=-1)
        rep = LocalReplica(rid, service=EngineService(eng), role=role)
        warm(rep)
        return rep

    reg = ReplicaRegistry()
    reps = [new_replica("prefill", "prefill-0"),
            new_replica("decode", "decode-0")]
    for r in reps:
        reg.add(r)
    reg.refresh()
    router = FleetRouter(reg, policy="affinity", affinity_prefix_tokens=16)

    closers = list(reps)

    def burst(mid=None):
        recs = []
        for _ in range(e_n):
            p = rand_prompt(e_len)
            t0 = time.monotonic()
            recs.append((t0, router.submit(p,
                                           SamplingParams(max_tokens=e_gen))))
        if mid is not None:
            mid()
        lat: list[float] = []

        def consume(t0, h):
            it = h.stream(timeout=600.0)
            next(it)
            dt = time.monotonic() - t0
            for _ in it:
                pass
            res = h.result(timeout=600.0)
            assert res.finish_reason == "length", res.error
            lat.append(dt)

        threads = [threading.Thread(target=consume, args=rec, daemon=True)
                   for rec in recs]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=600.0)
        assert len(lat) == e_n
        lat.sort()
        return lat

    steady = burst()

    # -- elasticity mid-burst: scale-up, then drain-based scale-down ------
    sig_targets: dict = {}

    class _Sig:
        def signals(self):
            return {"targets": dict(sig_targets)}

    decided: dict = {}

    class _TimedPool(LocalPoolExecutor):
        def scale(self, role, replicas, dry_run=False):
            if not dry_run and "t" not in decided:
                decided["t"] = time.monotonic()
            return super().scale(role, replicas, dry_run)

    def tracked_factory(role, rid):
        rep = new_replica(role, rid)
        closers.append(rep)
        return rep

    executor = _TimedPool(reg, tracked_factory)
    for r in reps:
        executor.adopt(r.role, r)
    ctl = AutoscaleController(
        _Sig(), executor,
        AutoscaleConfig(enabled=True, cooldown_s=0.05,
                        scale_down_dwell_s=0.2, min_prefill=1, max_prefill=2,
                        min_decode=1, max_decode=3, flap_max_flips=50),
        registry=reg)
    reaction: dict = {}

    def churn():
        t0 = time.monotonic()
        sig_targets["decode-0"] = {"scale_hint": "up",
                                   "anomalies": ["queue_growth"],
                                   "stale": False}
        deadline = t0 + 120.0
        while (("decode", "up", "applied") not in ctl.actions_total
               and time.monotonic() < deadline):
            ctl.tick()
            time.sleep(0.01)
        assert ("decode", "up", "applied") in ctl.actions_total
        reaction["decide_s"] = decided["t"] - t0
        reaction["spawn_s"] = time.monotonic() - t0
        sig_targets["decode-0"] = {"scale_hint": "down", "anomalies": [],
                                   "stale": False}
        deadline = time.monotonic() + 120.0
        while (("decode", "down", "applied") not in ctl.actions_total
               and time.monotonic() < deadline):
            ctl.tick()
            time.sleep(0.02)

    churn_lat = burst(mid=churn)
    executor.reap()

    def pct(sorted_lat, q):
        return sorted_lat[min(len(sorted_lat) - 1,
                              int(len(sorted_lat) * q))]

    steady_p99 = pct(steady, 0.99)
    churn_p99 = pct(churn_lat, 0.99)
    churn_ratio = churn_p99 / steady_p99 if steady_p99 > 0 else 0.0

    # -- handoff vs cold-prefill TTFT (replica level, best-of-3) ----------
    # Long prompt on purpose: the ratio is only meaningful once prefill
    # compute dominates the fixed per-dispatch engine-loop cost (~10 ms
    # on CPU) — at diagnosis-prompt sizes the gap is far larger still.
    h_len = int(os.environ.get("BENCH_ELASTIC_HANDOFF_PROMPT_LEN", "1024"))
    # The prefix cache publishes whole blocks only and always keeps the
    # final prompt token unshared (kv_cache.shareable_blocks), so a
    # block-aligned owner prompt caches one block short and leaves the
    # continuation a (block_size + 1)-token suffix — just past the small
    # prefill bucket, i.e. full-prefill cost.  Snap to one token below
    # alignment: the continuation then carries exactly one bucket-16
    # suffix beyond the shipped prefix.
    h_len = max(256, h_len // 16 * 16) - 1
    h_blocks = h_len // 16 + 4
    hcfg = EngineConfig(
        max_slots=2,
        num_blocks=5 * h_blocks + 16,  # 4 pinned prefixes + an active seq
        block_size=16,
        max_blocks_per_seq=h_blocks,
        prefill_buckets=(16, h_len + 64),
        max_prefills_per_step=2,
        decode_steps_per_iter=4,
    )

    def h_rep(rid):
        eng = InferenceEngine(cfg, params, hcfg, eos_id=-1)
        rep = LocalReplica(rid, service=EngineService(eng), role="unified")
        w = rand_prompt(h_len)
        first = rep.generate(w, SamplingParams(max_tokens=2)).result(
            timeout=600.0)
        rep.generate(w + first.token_ids[:1],
                     SamplingParams(max_tokens=2)).result(timeout=600.0)
        closers.append(rep)
        return rep

    owner, target, cold = h_rep("h-owner"), h_rep("h-target"), h_rep("h-cold")

    def ttft_once(rep, prompt):
        t0 = time.monotonic()
        h = rep.generate(prompt, SamplingParams(max_tokens=2))
        it = h.stream(timeout=600.0)
        next(it)
        dt = time.monotonic() - t0
        for _ in it:
            pass
        h.result(timeout=600.0)
        return dt

    handoff_ts, cold_ts = [], []
    for _ in range(3):
        p = rand_prompt(h_len)
        first = owner.generate(p, SamplingParams(max_tokens=1)).result(
            timeout=600.0)
        cont = p + first.token_ids[:1]
        blob = owner.fetch_prefix(cont, tenant=TEN)
        assert blob is not None, "owner exported no prefix"
        outcome = target.install_prefix(blob, tenant=TEN)
        assert outcome in ("installed", "cached"), outcome
        handoff_ts.append(ttft_once(target, cont))
        cold_ts.append(ttft_once(cold, cont))
    handoff_ttft, cold_ttft = min(handoff_ts), min(cold_ts)
    handoff_ratio = handoff_ttft / cold_ttft if cold_ttft > 0 else 0.0

    for rep in closers:
        rep.close()

    actions = {"/".join(k): v for k, v in sorted(ctl.actions_total.items())}
    log(f"elastic: decide {reaction['decide_s'] * 1e3:.1f} ms, warm spawn "
        f"{reaction['spawn_s']:.2f} s; TTFT p99 churn {churn_p99 * 1e3:.1f} "
        f"ms vs steady {steady_p99 * 1e3:.1f} ms ({churn_ratio:.2f}x); "
        f"handoff TTFT {handoff_ttft * 1e3:.1f} ms vs cold prefill "
        f"{cold_ttft * 1e3:.1f} ms ({handoff_ratio:.2f}x, budget <= 0.5x)")
    assert handoff_ratio <= 0.5, (
        f"handoff continuation TTFT {handoff_ttft * 1e3:.1f} ms is "
        f"{handoff_ratio:.2f}x a cold prefill ({cold_ttft * 1e3:.1f} ms); "
        "shipping the KV prefix should at least halve it")
    return {
        "elastic_reaction_decide_ms": round(reaction["decide_s"] * 1e3, 2),
        "elastic_reaction_spawn_s": round(reaction["spawn_s"], 2),
        "elastic_steady_ttft_p50_ms": round(pct(steady, 0.5) * 1e3, 1),
        "elastic_steady_ttft_p99_ms": round(steady_p99 * 1e3, 1),
        "elastic_churn_ttft_p99_ms": round(churn_p99 * 1e3, 1),
        "elastic_churn_vs_steady_p99": round(churn_ratio, 2),
        "elastic_handoff_ttft_ms": round(handoff_ttft * 1e3, 2),
        "elastic_cold_prefill_ttft_ms": round(cold_ttft * 1e3, 2),
        "elastic_handoff_vs_local_ttft": round(handoff_ratio, 3),
        "elastic_handoff_budget": 0.5,
        "elastic_autoscale_actions": actions,
    }


def mesh_leg(cfg, params) -> dict:
    """ICI-sharded serving leg: ONE tensor-parallel engine over every local
    device (weights column/row-sharded, KV pages head-sharded — parallel/
    sharding.py), measured p50/p99 TTFT and throughput.  This is the
    multi-chip number: it replaces the old per-chip-equivalence arithmetic
    (burst/8 through one chip), which modeled neither the collectives nor
    the shared-KV-pool batching dynamics of a real slice.  Off-TPU the same
    leg runs on the forced-host-device mesh and is annotated as a dryrun —
    program structure and parity are exercised; the timings are not ICI.
    """
    import numpy as np
    import jax

    from k8s_llm_monitor_tpu.parallel.mesh import MeshConfig, create_mesh
    from k8s_llm_monitor_tpu.serving.engine import (
        EngineConfig,
        GenerationRequest,
        InferenceEngine,
        SamplingParams,
    )

    devs = jax.devices()
    if len(devs) < 2:
        raise RuntimeError(
            "mesh leg needs >= 2 devices (set XLA_FLAGS="
            "--xla_force_host_platform_device_count=8 for a CPU dryrun)")
    mesh = create_mesh(MeshConfig(model=len(devs)))
    dryrun = devs[0].platform != "tpu"

    m_len = int(os.environ.get("BENCH_MESH_PROMPT_LEN",
                               os.environ.get("BENCH_PROMPT_LEN", "192")))
    m_gen = int(os.environ.get("BENCH_MESH_MAX_TOKENS",
                               os.environ.get("BENCH_MAX_TOKENS", "48")))
    m_n = int(os.environ.get("BENCH_MESH_CONCURRENCY",
                             os.environ.get("BENCH_CONCURRENCY", "100")))
    m_slots = int(os.environ.get("BENCH_MESH_SLOTS", "32"))
    cap = m_len + m_gen + 1
    bucket = bucket64(m_len)
    ecfg = EngineConfig(
        max_slots=m_slots,
        num_blocks=m_slots * ((cap + 15) // 16) + 16,
        block_size=16,
        max_blocks_per_seq=(cap + 15) // 16,
        prefill_buckets=(bucket,),
        max_prefills_per_step=min(16, m_slots),
        max_admission_rounds=8,
        decode_steps_per_iter=int(os.environ.get("BENCH_DECODE_STEPS", "8")),
    )
    eng = InferenceEngine(cfg, params, ecfg, eos_id=-1, mesh=mesh)
    rng = np.random.default_rng(3)

    def m_prompt() -> list[int]:
        return [int(t) for t in
                rng.integers(4, cfg.vocab_size - 4, size=m_len)]

    # Warm the admission-lane ladder so measured TTFT excludes compiles.
    log(f"mesh leg: {len(devs)}x {devs[0].device_kind} "
        f"({'DRYRUN: host devices, not ICI' if dryrun else 'measured'}); "
        f"warming compiled shapes...")
    w = ecfg.max_prefills_per_step
    while w >= 1:
        eng.generate([m_prompt() for _ in range(w)],
                     SamplingParams(max_tokens=4))
        w //= 2

    t0 = time.monotonic()
    for i in range(m_n):
        eng.submit(GenerationRequest(
            request_id=f"mesh-{i}", prompt_ids=m_prompt(),
            sampling=SamplingParams(max_tokens=m_gen)))
    while eng.has_work:
        eng.step()
    wall = time.monotonic() - t0
    res = [eng.poll(f"mesh-{i}") for i in range(m_n)]
    assert all(r is not None and r.finish_reason != "error" for r in res)
    t = np.array(sorted(r.ttft_s for r in res))
    p50_ms = float(np.percentile(t, 50)) * 1e3
    p99_ms = float(np.percentile(t, 99)) * 1e3
    tok_s = sum(len(r.token_ids) for r in res) / wall

    eng.profile_decode_phases()
    # None on a device kind without an ICI figure (the CPU dryrun mesh).
    coll_share = eng.decode_collective_share

    log(f"mesh ({len(devs)} devices, {m_n} concurrent): "
        f"p50 TTFT {p50_ms:.1f} ms, p99 {p99_ms:.1f} ms, "
        f"{tok_s:.1f} tok/s, est collective share "
        + ("not measured" if coll_share is None else f"{coll_share:.0%}"))
    return {
        "mesh_p50_ttft_ms": round(p50_ms, 2),
        "mesh_p99_ttft_ms": round(p99_ms, 2),
        "mesh_tok_s": round(tok_s, 1),
        "mesh_devices": len(devs),
        "mesh_device_kind": devs[0].device_kind,
        "mesh_concurrency": m_n,
        "mesh_dryrun": dryrun,
        "mesh_collective_share_est": (
            None if coll_share is None else round(coll_share, 4)),
    }


def overlap_leg(cfg, params) -> dict:
    """Latency-hiding TP decode (parallel/overlap.py): overlap-on vs
    overlap-off engines on the same mesh, per-step decode time for each,
    and the resulting ``decode_collective_hidden_share`` — measured
    against the ring byte model on a listed chip, None on the CPU dryrun
    mesh (engine.estimate_hidden_share).  A small
    TTFT burst runs through the overlap-on engine so the mesh JSON also
    carries end-to-end percentiles for the schedule that actually serves.

    When the bench model cannot take the staged schedule on this device
    count (e.g. the "tiny" preset's 2 KV heads under TP-8 — pages would
    replicate), the leg substitutes a TP-aligned tiny stand-in and labels
    it, so the dryrun still gates the schedule end to end.
    """
    import numpy as np
    import jax

    from k8s_llm_monitor_tpu.models import llama
    from k8s_llm_monitor_tpu.parallel.mesh import MeshConfig, create_mesh
    from k8s_llm_monitor_tpu.parallel.overlap import overlap_supported
    from k8s_llm_monitor_tpu.serving.engine import (
        EngineConfig,
        GenerationRequest,
        InferenceEngine,
        SamplingParams,
    )

    devs = jax.devices()
    if len(devs) < 2:
        raise RuntimeError("overlap leg needs >= 2 devices")
    mesh = create_mesh(MeshConfig(model=len(devs)))

    why_not = overlap_supported(cfg, mesh)
    model_name = cfg.name
    if why_not:
        import dataclasses

        log(f"overlap leg: {cfg.name} unsupported ({why_not}); "
            f"measuring a TP-aligned tiny stand-in")
        cfg = dataclasses.replace(cfg, name="tiny-tp", num_heads=8,
                                  num_kv_heads=8, num_experts=0,
                                  sandwich_norms=False, qkv_bias=False)
        params = llama.init_params(jax.random.PRNGKey(0), cfg)
        model_name = cfg.name

    o_len = int(os.environ.get("BENCH_MESH_PROMPT_LEN", "48"))
    o_gen = int(os.environ.get("BENCH_MESH_MAX_TOKENS", "12"))
    o_n = int(os.environ.get("BENCH_MESH_CONCURRENCY", "12"))
    o_slots = int(os.environ.get("BENCH_MESH_SLOTS", "8"))
    cap = o_len + o_gen + 1
    ecfg_kw = dict(
        max_slots=o_slots,
        num_blocks=o_slots * ((cap + 15) // 16) + 16,
        block_size=16,
        max_blocks_per_seq=(cap + 15) // 16,
        prefill_buckets=(bucket64(o_len),),
        max_prefills_per_step=min(16, o_slots),
        max_admission_rounds=8,
        decode_steps_per_iter=int(os.environ.get("BENCH_DECODE_STEPS", "8")),
    )
    rng = np.random.default_rng(7)

    def o_prompt() -> list[int]:
        return [int(t) for t in
                rng.integers(4, cfg.vocab_size - 4, size=o_len)]

    def build(tp_overlap: str) -> InferenceEngine:
        eng = InferenceEngine(cfg, params,
                              EngineConfig(tp_overlap=tp_overlap, **ecfg_kw),
                              eos_id=-1, mesh=mesh)
        eng.generate([o_prompt() for _ in range(2)],
                     SamplingParams(max_tokens=4))  # warm compiles
        return eng

    eng_off = build("off")
    t_off = eng_off.profile_decode_phases()["decode_step_ms_short_ctx"]
    del eng_off
    eng_on = build("on")
    assert eng_on.tp_overlap
    t_on = eng_on.profile_decode_phases()["decode_step_ms_short_ctx"]
    hidden = eng_on.estimate_hidden_share(step_ms_on=t_on,
                                          step_ms_off=t_off)

    t0 = time.monotonic()
    for i in range(o_n):
        eng_on.submit(GenerationRequest(
            request_id=f"ov-{i}", prompt_ids=o_prompt(),
            sampling=SamplingParams(max_tokens=o_gen)))
    while eng_on.has_work:
        eng_on.step()
    wall = time.monotonic() - t0
    res = [eng_on.poll(f"ov-{i}") for i in range(o_n)]
    assert all(r is not None and r.finish_reason != "error" for r in res)
    t = np.array(sorted(r.ttft_s for r in res))
    p50_ms = float(np.percentile(t, 50)) * 1e3
    p99_ms = float(np.percentile(t, 99)) * 1e3
    tok_s = sum(len(r.token_ids) for r in res) / wall

    log(f"overlap ({model_name}, {len(devs)} devices): decode step "
        f"{t_on:.2f} ms on vs {t_off:.2f} ms off, hidden share "
        + ("not measured" if hidden is None else f"{hidden:.0%}") + "; "
        f"p50 TTFT {p50_ms:.1f} ms, p99 {p99_ms:.1f} ms, {tok_s:.1f} tok/s")
    return {
        "overlap_model": model_name,
        "overlap_decode_step_ms_on": round(t_on, 3),
        "overlap_decode_step_ms_off": round(t_off, 3),
        "decode_collective_hidden_share": (
            None if hidden is None else round(hidden, 4)),
        "overlap_p50_ttft_ms": round(p50_ms, 2),
        "overlap_p99_ttft_ms": round(p99_ms, 2),
        "overlap_tok_s": round(tok_s, 1),
    }


def tier_admission_leg(cfg, params) -> dict:
    """Tier-aware admission (engine.admission_headroom_tokens): at EQUAL
    device pool bytes, an engine whose device blocks are pinned by
    spillable prefix-cache content admits a burst under
    ``kv_admission="tier"`` (the host tier can take the spill losslessly)
    that ``kv_admission="device"`` sheds.  Every admitted lane must
    finish clean with its full token budget while ``lane_eviction``
    faults are armed — the zero-lost-tokens clause.
    """
    import numpy as np

    from k8s_llm_monitor_tpu.resilience.faults import get_injector
    from k8s_llm_monitor_tpu.serving.engine import (
        EngineConfig,
        GenerationRequest,
        InferenceEngine,
        SamplingParams,
    )

    bs = 16
    seed_len = 64       # 4 full blocks each stay pinned in the prefix cache
    a_len, a_gen = 120, 8
    bps = (a_len + a_gen + 1 + bs - 1) // bs
    n_burst = 6
    rng = np.random.default_rng(23)

    def a_prompt(n: int) -> list[int]:
        return [int(t) for t in rng.integers(4, cfg.vocab_size - 4, size=n)]

    # Pool sized so the seeds' cacheable blocks pin most of it: each seed
    # publishes shareable_blocks(64,16)=3 blocks, 12 pinned of 17 usable.
    # Device-only headroom after seeding is 5 blocks = 80 tokens < the
    # 121 a burst lane needs; the tier policy counts the 12 evictable
    # (host-spillable) blocks too and admits.
    seed_prompts = [a_prompt(seed_len) for _ in range(4)]
    num_blocks = 18

    def run(kv_admission: str):
        ecfg = EngineConfig(
            max_slots=4, num_blocks=num_blocks, block_size=bs,
            max_blocks_per_seq=bps, prefill_buckets=(64, 128),
            max_prefills_per_step=2, decode_steps_per_iter=4,
            prefix_cache_entries=64, host_spill_bytes=64 << 20,
            kv_admission=kv_admission)
        eng = InferenceEngine(cfg, params, ecfg, eos_id=-1)
        # Fill the device pool with published (evictable) prefixes.
        for p in seed_prompts:
            eng.generate([p], SamplingParams(max_tokens=1))
        admitted, shed = [], 0
        get_injector().reset(seed=1234)
        get_injector().arm("lane_eviction", rate=0.25, times=2)
        try:
            for i in range(n_burst):
                p = a_prompt(a_len)
                if eng.should_shed(need_tokens=len(p) + 1):
                    shed += 1
                    continue
                rid = f"adm-{i}"
                eng.submit(GenerationRequest(
                    request_id=rid, prompt_ids=p,
                    sampling=SamplingParams(max_tokens=a_gen)))
                admitted.append(rid)
            while eng.has_work:
                eng.step()
        finally:
            get_injector().reset()
        res = [eng.poll(r) for r in admitted]
        clean = all(r is not None and r.finish_reason != "error"
                    and len(r.token_ids) == a_gen for r in res)
        del eng
        return len(admitted), shed, clean

    tier_admitted, tier_shed, tier_clean = run("tier")
    dev_admitted, dev_shed, dev_clean = run("device")
    log(f"tier admission: tier policy admitted {tier_admitted}/{n_burst} "
        f"(clean={tier_clean}) vs device-only {dev_admitted}/{n_burst} "
        f"at equal pool bytes")
    return {
        "tier_admission_lanes": tier_admitted,
        "tier_admission_shed": tier_shed,
        "tier_admission_clean": tier_clean,
        "device_admission_lanes": dev_admitted,
        "device_admission_shed": dev_shed,
    }


def long_prefill_leg(cfg, params) -> dict:
    """Flash paged prefill (ops/pallas_attention.flash_prefill_attention):
    flash-vs-dense TTFT at long prompt lengths, the chunked-vs-single-
    bucket crossover, a quantized-pool variant, and an analytic
    peak-live-bytes proxy for the attention intermediates.

    Dense prefill materializes the [S, T] score matrix and (on the chunk
    path) re-gathers the whole prefix every round, so its transient
    footprint grows with context; flash streams K/V pages through a
    fixed double-buffered VMEM window.  A dense leg is skipped — with
    the byte math recorded as the reason — when its analytic peak
    exceeds the dense budget (the paged pool bytes on TPU; relaxed by
    BENCH_PREFILL_DENSE_HEADROOM in the CPU dryrun so the short legs
    still measure dense while the longest leg exercises the same skip
    branch a 32k prompt does on the chip).  The longest flash-only leg
    is the served-where-dense-cannot evidence.

    ``BENCH_PREFILL_LENS`` / ``BENCH_PREFILL_CHUNK`` override the
    platform defaults (TPU: 2048,8192,32768 over a 512 chunk bucket;
    dryrun: 128,256,512 over 128 — interpret-mode Pallas is slow, so
    the dryrun lengths only validate the plumbing, not the speedup).
    """
    import numpy as np
    import jax

    from k8s_llm_monitor_tpu.serving.engine import (
        EngineConfig,
        GenerationRequest,
        InferenceEngine,
        SamplingParams,
    )

    dev = jax.devices()[0]
    on_tpu = dev.platform == "tpu"
    lens = tuple(int(x) for x in os.environ.get(
        "BENCH_PREFILL_LENS",
        "2048,8192,32768" if on_tpu else "128,256,512").split(","))
    gen = int(os.environ.get("BENCH_PREFILL_MAX_TOKENS", "4"))
    chunk_bucket = bucket64(int(os.environ.get(
        "BENCH_PREFILL_CHUNK", "512" if on_tpu else "128")))
    dense_headroom = float(os.environ.get(
        "BENCH_PREFILL_DENSE_HEADROOM", "1.0" if on_tpu else "5.0"))
    bs = 16
    kvh = cfg.num_kv_heads
    d = cfg.head_dim or cfg.hidden_size // cfg.num_heads
    rng = np.random.default_rng(11)

    def geometry(length: int) -> tuple[int, int]:
        cap = length + gen + 1
        bps = (cap + bs - 1) // bs
        return bps, bps + 17        # +17: null block + decode headroom

    def pool_bytes(length: int) -> int:
        _, nb = geometry(length)
        # f32 pool in the dryrun / bf16 on TPU; the proxy only needs the
        # two engines to agree, and they share one EngineConfig.
        el = 2 if on_tpu else 4
        return nb * bs * kvh * d * 2 * el

    def dense_peak_bytes(length: int) -> int:
        bps, _ = geometry(length)
        t_pad = bps * bs
        s_b = chunk_bucket if length > chunk_bucket else bucket64(length)
        # [1, H, S, T] f32 scores + the gathered [T, KVH, D] k/v pair
        # (f32 compute) — per layer, transient, but peak-live.
        return (cfg.num_heads * s_b * t_pad * 4
                + 2 * t_pad * kvh * d * 4)

    # Flash peak-live: double-buffered K and V window slabs in VMEM
    # (2 slots x W=8 pages x block_size tokens x head_dim lanes, f32).
    flash_window_bytes = 2 * 2 * (8 * bs) * d * 4

    def build(path: str, length: int, buckets, kv_dtype: str = "auto"):
        bps, nb = geometry(length)
        ecfg = EngineConfig(
            max_slots=2, num_blocks=nb, block_size=bs,
            max_blocks_per_seq=bps, prefill_buckets=buckets,
            max_prefills_per_step=1, max_admission_rounds=2,
            decode_steps_per_iter=2, prefix_cache_entries=0,
            prefill_path=path, kv_dtype=kv_dtype)
        return InferenceEngine(cfg, params, ecfg, eos_id=-1)

    def measure_ttft(eng, length: int, tag: str) -> float:
        prompt = [int(t) for t in
                  rng.integers(4, cfg.vocab_size - 4, size=length)]
        eng.generate([prompt], SamplingParams(max_tokens=2))  # warm compiles
        eng.submit(GenerationRequest(
            request_id=tag, prompt_ids=prompt,
            sampling=SamplingParams(max_tokens=gen)))
        while eng.has_work:
            eng.step()
        res = eng.poll(tag)
        assert res is not None and res.finish_reason != "error", tag
        return res.ttft_s * 1e3

    out: dict = {
        "prefill_lens": list(lens),
        "prefill_chunk_bucket": chunk_bucket,
        "prefill_dryrun": not on_tpu,
        "prefill_flash_vmem_window_bytes": flash_window_bytes,
    }
    speedup_at: dict[int, float] = {}
    for length in lens:
        buckets = ((chunk_bucket,) if length > chunk_bucket
                   else (bucket64(length),))
        eng_f = build("flash", length, buckets)
        assert eng_f.prefill_path == "flash", (
            "flash prefill not selected — leg would measure dense twice")
        f_ms = measure_ttft(eng_f, length, f"pf-flash-{length}")
        out[f"prefill_flash_ttft_ms_{length}"] = round(f_ms, 2)
        out[f"prefill_flash_buckets_{length}"] = list(
            eng_f.ecfg.prefill_buckets)
        del eng_f

        d_peak, pool = dense_peak_bytes(length), pool_bytes(length)
        out[f"prefill_dense_peak_bytes_{length}"] = d_peak
        out[f"prefill_pool_bytes_{length}"] = pool
        if d_peak > pool * dense_headroom:
            reason = (f"analytic dense peak {d_peak} B > "
                      f"{dense_headroom:g}x pool {pool} B")
            out[f"prefill_dense_skip_{length}"] = reason
            log(f"prefill leg {length}: flash {f_ms:.1f} ms; "
                f"dense SKIPPED ({reason})")
            continue
        eng_d = build("dense", length, buckets)
        d_ms = measure_ttft(eng_d, length, f"pf-dense-{length}")
        del eng_d
        ratio = d_ms / max(f_ms, 1e-9)
        speedup_at[length] = ratio
        out[f"prefill_dense_ttft_ms_{length}"] = round(d_ms, 2)
        out[f"prefill_speedup_{length}"] = round(ratio, 3)
        log(f"prefill leg {length}: flash {f_ms:.1f} ms vs dense "
            f"{d_ms:.1f} ms ({ratio:.2f}x)")

    if speedup_at:
        top = max(speedup_at)
        out["prefill_speedup_max_len"] = round(speedup_at[top], 3)
        out["prefill_speedup_max_len_tokens"] = top

    # Chunked-vs-single-bucket crossover: first length long enough to
    # chunk but short enough that the flash bucket auto-extension can't
    # lift it back to a single round (capacity < 4096 tokens).
    lx = next((n for n in lens
               if n > chunk_bucket and n + gen + 1 + bs < 4096), None)
    if lx is not None:
        eng_s = build("flash", lx, (bucket64(lx),))
        s_ms = measure_ttft(eng_s, lx, f"pf-single-{lx}")
        del eng_s
        c_ms = out[f"prefill_flash_ttft_ms_{lx}"]
        out["prefill_crossover_len"] = lx
        out["prefill_single_bucket_ttft_ms"] = round(s_ms, 2)
        out["prefill_chunked_ttft_ms"] = c_ms
        out["prefill_crossover_winner"] = (
            "single" if s_ms <= c_ms else "chunked")
        log(f"prefill crossover @{lx}: single-bucket {s_ms:.1f} ms vs "
            f"chunked {c_ms:.1f} ms")

    # Quantized-pool variant: in-kernel dequant from the int8 pool.
    lq = lens[0]
    try:
        eng_q = build("flash", lq, (bucket64(lq),), kv_dtype="int8")
        q_ms = measure_ttft(eng_q, lq, f"pf-quant-{lq}")
        del eng_q
        out["prefill_quant_flash_ttft_ms"] = round(q_ms, 2)
        log(f"prefill quant (int8 pool) @{lq}: flash {q_ms:.1f} ms")
    except Exception as exc:  # noqa: BLE001 — extras never fail the bench
        log(f"prefill quant variant skipped: {exc}")
    return out


def main() -> None:
    t0 = time.monotonic()
    import numpy as np
    import jax

    from k8s_llm_monitor_tpu.utils.compile_cache import (
        configure_compile_cache,
    )

    _, cache_was_warm = configure_compile_cache()

    from k8s_llm_monitor_tpu.models import llama
    from k8s_llm_monitor_tpu.models.config import PRESETS
    from k8s_llm_monitor_tpu.serving.engine import (
        EngineConfig,
        GenerationRequest,
        InferenceEngine,
        SamplingParams,
    )
    from k8s_llm_monitor_tpu.utils import quantize as qz

    model_name = os.environ.get("BENCH_MODEL", "llama3-8b")
    quant = os.environ.get("BENCH_QUANT", "int8")
    n_requests = int(os.environ.get("BENCH_CONCURRENCY", "100"))
    prompt_len = int(os.environ.get("BENCH_PROMPT_LEN", "192"))
    max_tokens = int(os.environ.get("BENCH_MAX_TOKENS", "48"))

    cfg = PRESETS[model_name]
    dev = jax.devices()[0]
    if dev.platform == "tpu":
        flops_peak, hbm_peak = chip_peaks(dev.device_kind)
    elif model_name.startswith("tiny"):
        # CPU smoke of the control flow on a tiny preset: no peaks, so no
        # utilization figure is derived below.
        flops_peak = hbm_peak = 0.0
    else:
        log(f"bench: {model_name} on {dev.platform}:{dev.device_kind} is "
            "not a measurement — run on the chip, or BENCH_MODEL=tiny for "
            "a CPU smoke of the control flow")
        sys.exit(2)
    log(f"bench: {model_name} ({quant}) on {dev.platform}:{dev.device_kind} "
        f"({n_requests} concurrent, prompt {prompt_len}, gen {max_tokens})")

    if quant == "int8":
        params = qz.init_params_quantized(jax.random.PRNGKey(0), cfg)
    else:
        params = llama.init_params(jax.random.PRNGKey(0), cfg)
    weight_elems, stream_bytes = weight_accounting(params, cfg.tie_embeddings)
    weight_bytes = qz.param_bytes(params)
    log(f"weights: {weight_elems/1e9:.2f}B matmul params, "
        f"{weight_bytes/2**30:.2f} GiB on device")

    if os.environ.get("BENCH_FLEET_ONLY", "0") == "1":
        # Fast CPU-only fleet smoke for `make bench-fleet`: skips the ~12
        # main legs and runs just the 1-vs-2-replica router comparison.
        stats = fleet_leg(cfg, params)
        print(json.dumps({
            "metric": "fleet_2replica_tok_s",
            "value": stats.get("fleet_2replica_tok_s", 0.0),
            "unit": "tok/s",
            "extras": {"model": model_name, "platform": dev.platform,
                       **stats},
        }))
        return

    if os.environ.get("BENCH_TENANT_ONLY", "0") == "1":
        # `make bench-tenant`: just the multi-tenant fairness leg — a
        # flooding tenant rate-limited with tenant-tagged 429s while
        # quiet Zipf tenants stay byte-exact within 2x their solo TTFT.
        stats = tenant_fairness_leg(cfg, params)
        print(json.dumps({
            "metric": "tenant_interactive_p99_ttft_ratio",
            "value": stats.get("tenant_interactive_p99_ttft_ratio", 0.0),
            "unit": "x",
            "extras": {"model": model_name, "platform": dev.platform,
                       **stats},
        }))
        return

    if os.environ.get("BENCH_REMEDIATE_ONLY", "0") == "1":
        # `make bench-remediate`: the closed-loop remediation leg —
        # inject->verified-recovery latency for all four chaos scenarios
        # plus the plan-constrained-decode overhead gate (< 10% tok/s).
        stats = remediation_leg(cfg, params)
        print(json.dumps({
            "metric": "remediation_plan_overhead_pct",
            "value": stats.get("remediation_plan_overhead_pct", 0.0),
            "unit": "%",
            "extras": {"model": model_name, "platform": dev.platform,
                       **stats},
        }))
        return

    if os.environ.get("BENCH_SIGNALS_ONLY", "0") == "1":
        # `make bench-signals`: just the telemetry-plane overhead leg
        # (CPU-friendly; asserts the < 1% tok/s scraper budget).
        stats = signals_leg(cfg, params)
        print(json.dumps({
            "metric": "signals_overhead_pct",
            "value": stats.get("signals_overhead_pct", 0.0),
            "unit": "%",
            "extras": {"model": model_name, "platform": dev.platform,
                       **stats},
        }))
        return

    if os.environ.get("BENCH_ELASTIC_ONLY", "0") == "1":
        # `make bench-elastic`: scale-up reaction time, churn-vs-steady
        # TTFT tail, and the handoff-vs-cold-prefill ratio (budget 0.5x).
        stats = elasticity_leg(cfg, params)
        print(json.dumps({
            "metric": "elastic_handoff_vs_local_ttft",
            "value": stats.get("elastic_handoff_vs_local_ttft", 0.0),
            "unit": "x",
            "extras": {"model": model_name, "platform": dev.platform,
                       **stats},
        }))
        return

    if os.environ.get("BENCH_PREFILL_ONLY", "0") == "1":
        # `make bench-prefill`: flash-vs-dense long-prefill TTFT, the
        # chunked-vs-single-bucket crossover, and the longest flash-only
        # length the dense path's transient footprint cannot serve.
        stats = long_prefill_leg(cfg, params)
        print(json.dumps({
            "metric": "prefill_flash_vs_dense_ttft",
            "value": stats.get("prefill_speedup_max_len", 0.0),
            "unit": "x",
            "extras": {"model": model_name, "platform": dev.platform,
                       **stats},
        }))
        return

    if os.environ.get("BENCH_MESH_ONLY", "0") == "1":
        # `make bench-mesh`: just the TP-mesh leg.  Dryrun on the forced
        # 8-host-device CPU mesh in CI; measured on a real slice.
        stats = mesh_leg(cfg, params)
        try:
            stats.update(overlap_leg(cfg, params))
        except Exception as exc:  # noqa: BLE001 — extras never fail the bench
            log(f"overlap leg skipped: {exc}")
        try:
            stats.update(tier_admission_leg(cfg, params))
        except Exception as exc:  # noqa: BLE001
            log(f"tier admission leg skipped: {exc}")
        print(json.dumps({
            "metric": "mesh_tok_s",
            "value": stats.get("mesh_tok_s", 0.0),
            "unit": "tok/s",
            "extras": {"model": model_name, "platform": dev.platform,
                       **stats},
        }))
        return

    # Prompt bucket hugs the prompt length (rounded to the 64-lane sublane
    # multiple; 192 itself is 1.5 * 128 and MXU-friendly): minimal padding
    # waste in the prefill calls that dominate TTFT.
    bucket = bucket64(prompt_len)
    seq_cap = prompt_len + max_tokens + 1
    # Shared-prefix leg geometry: diagnosis queries share the system
    # preamble + evidence prefix (monitor/analysis.py), modeled as 2/3 of
    # the prompt; the suffix bucket keeps hit-round prefills suffix-sized.
    shared_len = int(os.environ.get(
        "BENCH_SHARED_PREFIX_LEN", str((2 * prompt_len // 3) // 16 * 16)))
    suffix_bucket = bucket64(max(prompt_len - shared_len, 16))
    ecfg = EngineConfig(
        max_slots=int(os.environ.get("BENCH_SLOTS", "128")),
        num_blocks=int(os.environ.get("BENCH_BLOCKS", "2200")),
        block_size=16,
        max_blocks_per_seq=(seq_cap + 15) // 16,
        prefill_buckets=tuple(sorted({suffix_bucket, bucket})),
        max_prefills_per_step=int(os.environ.get("BENCH_PREFILL_BATCH", "16")),
        max_admission_rounds=8,
        decode_steps_per_iter=int(os.environ.get("BENCH_DECODE_STEPS", "8")),
    )
    eng = InferenceEngine(cfg, params, ecfg, eos_id=-1)

    rng = np.random.default_rng(0)

    def prompt() -> list[int]:
        return list(rng.integers(4, cfg.vocab_size - 4, size=prompt_len))

    def ttft_pcts(results) -> tuple[float, float]:
        """(p50, p99) TTFT in ms — every leg reports its tail, not just the
        headline (a diagnosis product's slowest 1% is a budget, not noise)."""
        t = np.array(sorted(r.ttft_s for r in results))
        return (float(np.percentile(t, 50)) * 1e3,
                float(np.percentile(t, 99)) * 1e3)

    # Warm up every compiled shape — the power-of-two admission-lane ladder
    # (the engine pads prefill batches up, so a 100-burst walks P=16 rounds
    # plus a P=4 tail) and the fused-decode K ladder the drain will walk —
    # so the measured run excludes compile time.  With a populated
    # .jax_cache this is seconds, not minutes.
    log(f"warmup (compiles prefill/decode; cache "
        f"{'warm' if cache_was_warm else 'cold'})...")
    wt0 = time.monotonic()
    eng.generate([prompt() for _ in range(ecfg.max_prefills_per_step)],
                 SamplingParams(max_tokens=max_tokens))
    w = ecfg.max_prefills_per_step // 2
    while w >= 1:
        eng.generate([prompt() for _ in range(w)],
                     SamplingParams(max_tokens=4))
        w //= 2
    warmup_s = time.monotonic() - wt0
    log(f"warmup done in {warmup_s:.1f}s")

    # --- headline: concurrent burst, all requests queued at t=0 ---------
    bt0 = time.monotonic()
    for i in range(n_requests):
        eng.submit(GenerationRequest(
            request_id=f"bench-{i}",
            prompt_ids=prompt(),
            sampling=SamplingParams(max_tokens=max_tokens),
        ))
    steps0, prefills0 = eng.steps, eng.prefills
    while eng.has_work:
        eng.step()
    wall = time.monotonic() - bt0

    results = [eng.poll(f"bench-{i}") for i in range(n_requests)]
    assert all(r is not None and r.finish_reason != "error" for r in results)
    steps_run, prefills_run = eng.steps - steps0, eng.prefills - prefills0
    preempts = eng.preemptions
    ttfts = np.array(sorted(r.ttft_s for r in results))
    total_tokens = sum(len(r.token_ids) for r in results)
    p50 = float(np.percentile(ttfts, 50))
    p99 = float(np.percentile(ttfts, 99))
    toks_per_s = total_tokens / wall

    log(f"drained {n_requests} requests in {wall:.2f}s "
        f"({steps_run} steps, {prefills_run} prefills, "
        f"{preempts} preemptions)")
    log(f"p50 TTFT {p50 * 1e3:.1f} ms | p99 {p99 * 1e3:.1f} ms | "
        f"throughput {toks_per_s:.0f} tok/s")

    # --- per-chip-equivalent leg: the SLO's v5e-8 config spread over 8
    # chips is ~12 concurrent per chip; same engine, warm shapes. ---------
    perchip_p50_ms = perchip_p99_ms = None
    try:
        n_pc = max(1, n_requests // 8)
        for i in range(n_pc):
            eng.submit(GenerationRequest(
                request_id=f"pc-{i}", prompt_ids=prompt(),
                sampling=SamplingParams(max_tokens=max_tokens)))
        while eng.has_work:
            eng.step()
        pcres = [eng.poll(f"pc-{i}") for i in range(n_pc)]
        assert all(r is not None and r.finish_reason != "error" for r in pcres)
        perchip_p50_ms, perchip_p99_ms = ttft_pcts(pcres)
        log(f"per-chip-equivalent ({n_pc} concurrent, informational — "
            f"see mesh leg for the measured multi-chip number): "
            f"p50 TTFT {perchip_p50_ms:.1f} ms, p99 {perchip_p99_ms:.1f} ms")
    except Exception as exc:  # noqa: BLE001 — extras never fail the bench
        log(f"per-chip leg skipped: {exc}")

    # --- shared-prefix leg: the realistic diagnosis workload — all queries
    # share the preamble+evidence prefix, prefilled once via the prefix
    # cache (suffix-only chunked admission).  Warm pass first so compile
    # time for the suffix-bucket program stays out of the measurement. ----
    shared_p50_ms = shared_p99_ms = None
    try:
        pre = prompt()[:shared_len]

        def shared_prompt() -> list[int]:
            return pre + list(rng.integers(
                4, cfg.vocab_size - 4, size=prompt_len - shared_len))

        # Seed the cache first (a lone request registers the prefix), THEN
        # warm the batched chunked-prefill program at every ladder lane
        # count a draining burst can hit — hits in the same round as the
        # seed would run the dense path and leave the chunked programs to
        # compile inside the measurement.
        eng.generate([shared_prompt()], SamplingParams(max_tokens=4))
        w = 2
        while w <= ecfg.max_prefills_per_step:
            eng.generate([shared_prompt() for _ in range(w)],
                         SamplingParams(max_tokens=4))
            w *= 2
        st0 = time.monotonic()
        for i in range(n_requests):
            eng.submit(GenerationRequest(
                request_id=f"sh-{i}", prompt_ids=shared_prompt(),
                sampling=SamplingParams(max_tokens=max_tokens)))
        while eng.has_work:
            eng.step()
        swall = time.monotonic() - st0
        sres = [eng.poll(f"sh-{i}") for i in range(n_requests)]
        assert all(r is not None and r.finish_reason != "error" for r in sres)
        shared_p50_ms, shared_p99_ms = ttft_pcts(sres)
        pc = eng.prefix_cache
        log(f"shared-prefix ({shared_len}/{prompt_len} tokens cached): "
            f"p50 TTFT {shared_p50_ms:.1f} ms, p99 {shared_p99_ms:.1f} ms, "
            f"drained in {swall:.2f}s "
            f"(cache hits {pc.hits}, misses {pc.misses})")
    except Exception as exc:  # noqa: BLE001 — extras never fail the bench
        log(f"shared-prefix leg skipped: {exc}")

    # --- SLO-class leg: the same burst with classes attached (round-robin
    # interactive/standard/batch).  Class scheduling sorts admission and
    # evicts batch lanes for interactive arrivals, so interactive must hold
    # a tight tail (p99 <= 2x p50) while batch absorbs the queueing. ------
    slo_class_stats = None
    try:
        slo_classes = ("interactive", "standard", "batch")
        slo_rids = []
        for i in range(n_requests):
            c = slo_classes[i % len(slo_classes)]
            rid = f"slo-{i}"
            slo_rids.append((rid, c))
            eng.submit(GenerationRequest(
                request_id=rid, prompt_ids=prompt(),
                sampling=SamplingParams(max_tokens=max_tokens),
                slo_class=c))
        while eng.has_work:
            eng.step()
        by_class: dict[str, list] = {c: [] for c in slo_classes}
        for rid, c in slo_rids:
            r = eng.poll(rid)
            assert r is not None and r.finish_reason != "error"
            by_class[c].append(r)
        slo_class_stats = {}
        for c in slo_classes:
            c_p50, c_p99 = ttft_pcts(by_class[c])
            slo_class_stats[c] = {"p50_ttft_ms": round(c_p50, 2),
                                  "p99_ttft_ms": round(c_p99, 2),
                                  "n": len(by_class[c])}
        ia = slo_class_stats["interactive"]
        ia["p99_over_p50"] = round(
            ia["p99_ttft_ms"] / max(ia["p50_ttft_ms"], 1e-9), 2)
        ia["tail_ok"] = ia["p99_ttft_ms"] <= 2.0 * ia["p50_ttft_ms"]
        for c in slo_classes:
            s = slo_class_stats[c]
            log(f"slo-class {c}: p50 TTFT {s['p50_ttft_ms']:.1f} ms, "
                f"p99 {s['p99_ttft_ms']:.1f} ms ({s['n']} reqs)")
        log(f"interactive tail under mixed-class burst: "
            f"p99/p50 = {ia['p99_over_p50']:.2f}x "
            f"({'OK' if ia['tail_ok'] else 'OVER'} budget 2.00x)")
    except Exception as exc:  # noqa: BLE001 — extras never fail the bench
        log(f"slo-class leg skipped: {exc}")

    # --- utilization micro-legs on the warm compiled programs -----------
    prefill_tflops = prefill_mfu = 0.0
    decode_gbs = decode_bw_util = 0.0
    try:
        import jax.numpy as jnp

        P = ecfg.max_prefills_per_step
        S = ecfg.prefill_buckets[-1]
        toks = jnp.asarray(rng.integers(4, cfg.vocab_size - 4,
                                        size=(P, S)), jnp.int32)
        lengths = (jnp.full((P,), S, jnp.int32),)
        if eng._packed_prefill:     # P full-bucket prompts end to end
            toks = toks.reshape(P * S)
            lengths = (jnp.arange(P, dtype=jnp.int32) * S, *lengths)
        blocks_per = min((S + 15) // 16, ecfg.max_blocks_per_seq)
        tbl = np.zeros((P, ecfg.max_blocks_per_seq), np.int32)
        for j in range(P):
            lo = 1 + j * blocks_per
            tbl[j, :blocks_per] = np.arange(lo, lo + blocks_per)
        tbl = jnp.asarray(tbl)
        # Warm (already compiled by the engine) — time reps.
        first, eng.pages = eng._prefill_greedy(
            params, toks, lengths, eng.pages, tbl)
        first.block_until_ready()
        reps = 3
        pt0 = time.monotonic()
        for _ in range(reps):
            first, eng.pages = eng._prefill_greedy(
                params, toks, lengths, eng.pages, tbl)
        first.block_until_ready()
        pdt = time.monotonic() - pt0
        # Dense-matmul FLOPs dominate; attention terms are <2% at S=192.
        prefill_tflops = reps * 2.0 * weight_elems * P * S / pdt / 1e12
        if flops_peak:
            prefill_mfu = prefill_tflops * 1e12 / flops_peak
        log(f"prefill: {prefill_tflops:.1f} TFLOP/s"
            + (f" ({prefill_mfu * 100:.0f}% MFU)" if flops_peak else ""))

        # Decode: each fused step streams the full weight set once.
        K = ecfg.decode_steps_per_iter
        prog = eng._decode_program(K, sampled=False)
        B = ecfg.max_slots
        ctx = jnp.full((B,), prompt_len, jnp.int32)
        remaining = jnp.full((B,), 10 ** 6, jnp.int32)
        dtbl = jnp.asarray(np.tile(tbl[:1], (B, 1)))
        eos = jnp.asarray(-1, jnp.int32)
        tok_state = jnp.zeros((B,), jnp.int32)
        _, tok_state, eng.pages = prog(params, tok_state, ctx, remaining,
                                       eng.pages, dtbl, eos)
        tok_state.block_until_ready()
        dt0 = time.monotonic()
        for _ in range(reps):
            _, tok_state, eng.pages = prog(
                params, tok_state, ctx, remaining, eng.pages, dtbl, eos)
        tok_state.block_until_ready()
        ddt = time.monotonic() - dt0
        decode_gbs = reps * K * stream_bytes / ddt / 1e9
        if hbm_peak:
            decode_bw_util = decode_gbs * 1e9 / hbm_peak
        step_ms = ddt / (reps * K) * 1e3
        # Attribution of the sub-50% HBM utilization: at B lanes the step
        # sits at the compute/bandwidth RIDGE — streaming the int8 weights
        # is only part of the time; the dequantized bf16 matmul at B rows
        # costs about as much again (plus attention/dispatch residue), so
        # the step is not HBM-bound and can't reach the bandwidth ceiling.
        # (Measured v5e: B=8 14.1 ms/step vs B=128 28.2 — the growth is
        # the B-scaled matmul term; W8A8's s8xs8 matmul cuts it to 24.1.)
        stream_ms = stream_bytes / hbm_peak * 1e3 if hbm_peak else 0.0
        matmul_ms = (2.0 * weight_elems * B / flops_peak * 1e3
                     if flops_peak else 0.0)
        decode_step_ms, decode_stream_ms, decode_matmul_ms = (
            step_ms, stream_ms, matmul_ms)
        log(f"decode weight traffic: {decode_gbs:.0f} GB/s"
            + (f" ({decode_bw_util * 100:.0f}% of HBM)" if hbm_peak else "")
            + f" [{B} lanes -> {B * reps * K / ddt:.0f} tok/s ceiling]")
        log(f"decode step attribution ({B} lanes): {step_ms:.1f} ms/step = "
            f"weight stream {stream_ms:.1f} + bf16 matmul ~{matmul_ms:.1f} "
            f"+ residual {max(step_ms - stream_ms - matmul_ms, 0):.1f} "
            f"(compute/bandwidth ridge, not HBM-bound)")
    except Exception as exc:  # noqa: BLE001
        decode_step_ms = decode_stream_ms = decode_matmul_ms = None
        log(f"utilization legs skipped: {exc}")

    # --- fused-vs-fallback decode micro-leg: the Pallas fused kernel
    # (in-kernel RoPE + KV append + paged attention) against the XLA
    # gather path, same K-step greedy scan, same synthetic state.  Greedy
    # token streams must match — the fallback is the fused kernel's
    # numerics oracle.  TPU-only: interpret-mode Pallas inside a scan is
    # pathological on CPU and would time the emulator, not the kernel. ---
    fused_decode_step_ms = fallback_decode_step_ms = None
    fused_match = None
    try:
        import jax.numpy as jnp

        if dev.platform != "tpu":
            raise RuntimeError(f"needs TPU (platform={dev.platform})")
        from k8s_llm_monitor_tpu.ops.attention import select_decode_impl

        impls = {
            "fallback": select_decode_impl(cfg=cfg, mode="gather"),
            "fused": select_decode_impl(cfg=cfg, mode="fused"),
        }
        K = ecfg.decode_steps_per_iter
        B = ecfg.max_slots

        def _make_prog(impl):
            def fn(params, tok_state, ctx, pages, tables):
                def body(carry, _):
                    tokens, c, pages = carry
                    logits, pages = llama.decode_step(
                        params, cfg, tokens, c, pages, tables,
                        attn_impl=impl)
                    nxt = jnp.argmax(logits, -1).astype(jnp.int32)
                    return (nxt, c + 1, pages), nxt
                (tok_state, _, pages), toks = jax.lax.scan(
                    body, (tok_state, ctx, pages),
                    jnp.arange(K, dtype=jnp.int32))
                return toks, tok_state, pages
            return jax.jit(fn, donate_argnums=(3,))

        ctx = jnp.full((B,), prompt_len, jnp.int32)
        dtbl = jnp.asarray(np.tile(np.asarray(tbl)[:1], (B, 1)))
        streams = {}
        times = {}
        reps = 3
        for name, impl in impls.items():
            prog = _make_prog(impl)
            tok_state = jnp.zeros((B,), jnp.int32)
            toks, tok_state, eng.pages = prog(
                params, tok_state, ctx, eng.pages, dtbl)
            streams[name] = np.asarray(toks)
            ft0 = time.monotonic()
            for _ in range(reps):
                _, tok_state, eng.pages = prog(
                    params, jnp.zeros((B,), jnp.int32), ctx,
                    eng.pages, dtbl)
            tok_state.block_until_ready()
            times[name] = (time.monotonic() - ft0) / (reps * K) * 1e3
        fused_decode_step_ms = times["fused"]
        fallback_decode_step_ms = times["fallback"]
        fused_match = bool(
            np.array_equal(streams["fused"], streams["fallback"]))
        log(f"fused decode kernel: {fused_decode_step_ms:.2f} ms/step vs "
            f"gather fallback {fallback_decode_step_ms:.2f} ms/step "
            f"({fallback_decode_step_ms / max(fused_decode_step_ms, 1e-9):.2f}x)"
            f" | greedy streams identical: {fused_match}")
    except Exception as exc:  # noqa: BLE001 — extras never fail the bench
        log(f"fused-vs-fallback leg skipped: {exc}")

    # --- decode phase attribution: attention vs sampling share of the
    # step, measured on the engine's own warm programs; populates the
    # decode_attn_ms / decode_sample_ms exporter gauges. ----------------
    decode_phases = None
    try:
        decode_phases = eng.profile_decode_phases()
        log(f"decode phases: attn {decode_phases['decode_attn_ms']:.2f} ms"
            f" + sample {decode_phases['decode_sample_ms']:.2f} ms of "
            f"{decode_phases['decode_step_ms_long_ctx']:.2f} ms/step "
            f"(long-ctx)")
    except Exception as exc:  # noqa: BLE001 — extras never fail the bench
        log(f"decode phase attribution skipped: {exc}")
    # Captured now: the headline engine is deleted before extras assembly.
    decode_path = eng.decode_path

    # --- E2E 128-lane decode saturation: short prompts, generations that
    # fill each lane's KV capacity, all max_slots lanes live — the engine
    # (scheduler + reconcile + fused dispatch) at the lane count the
    # micro-leg ceiling is quoted for. ---------------------------------
    dec_e2e_tok_s = None
    try:
        n_dec = ecfg.max_slots
        dplen = 64
        dgen = eng.capacity_tokens - dplen - 1
        def dec_prompt() -> list[int]:
            return list(rng.integers(4, cfg.vocab_size - 4, size=dplen))
        # Warm the short-prompt bucket's admission ladder.
        w = ecfg.max_prefills_per_step
        while w >= 1:
            eng.generate([dec_prompt() for _ in range(w)],
                         SamplingParams(max_tokens=4))
            w //= 2
        dt0 = time.monotonic()
        for i in range(n_dec):
            eng.submit(GenerationRequest(
                request_id=f"dec-{i}", prompt_ids=dec_prompt(),
                sampling=SamplingParams(max_tokens=dgen)))
        while eng.has_work:
            eng.step()
        dwall = time.monotonic() - dt0
        dres = [eng.poll(f"dec-{i}") for i in range(n_dec)]
        assert all(r is not None and r.finish_reason != "error" for r in dres)
        dtoks = sum(len(r.token_ids) for r in dres)
        dec_e2e_tok_s = dtoks / dwall
        ceiling = (f" vs {B * reps * K / ddt:.0f} tok/s fused-step ceiling"
                   if decode_gbs else "")  # micro-leg may have been skipped
        log(f"E2E decode saturation ({n_dec} lanes x {dgen} tokens): "
            f"{dec_e2e_tok_s:.0f} tok/s engine{ceiling}")
    except Exception as exc:  # noqa: BLE001
        log(f"decode saturation leg skipped: {exc}")
    del eng  # free the headline KV pool before the long-prompt engine

    # --- mesh leg: TP over every local device — the SLO's actual v5e-8
    # shape, measured.  Supersedes the per-chip-equivalence arithmetic
    # above (kept in extras as informational only).  Runs after the
    # headline engine is freed so the sharded weight copies fit. ---------
    mesh_stats = {}
    if len(jax.devices()) > 1 and os.environ.get("BENCH_MESH", "1") == "1":
        try:
            mesh_stats = mesh_leg(cfg, params)
        except Exception as exc:  # noqa: BLE001 — extras never fail the bench
            log(f"mesh leg skipped: {exc}")
        try:
            mesh_stats.update(overlap_leg(cfg, params))
        except Exception as exc:  # noqa: BLE001
            log(f"overlap leg skipped: {exc}")

    # --- W8A8 leg: dynamic per-token activation int8 on top of the int8
    # weights — prefill runs s8 x s8 on the MXU int8 path (measured ~203
    # vs ~145 TFLOP/s bf16 on dense [4,512] prefill, ~1.4x).  Same weights pytree, separate engine/compile.
    # Parity contract: tests/test_quantize.py::test_w8a8_forward_parity. --
    w8a8_p50_ms = w8a8_perchip_p50_ms = w8a8_shared_p50_ms = None
    w8a8_p99_ms = w8a8_perchip_p99_ms = None
    w8a8_shared_p99_ms = w8a8_decode_tok_s = None
    cold_shared_p50_ms = cold_shared_p99_ms = None
    w8a8_wall = 0.0
    if quant == "int8" and os.environ.get("BENCH_W8A8", "1") == "1":
        aeng = None
        try:
            import dataclasses as _dc

            cfg_aq = _dc.replace(cfg, act_quant=True)
            aeng = InferenceEngine(cfg_aq, params, ecfg, eos_id=-1)
            aeng.generate([prompt() for _ in range(ecfg.max_prefills_per_step)],
                          SamplingParams(max_tokens=max_tokens))
            w = ecfg.max_prefills_per_step // 2
            while w >= 1:
                aeng.generate([prompt() for _ in range(w)],
                              SamplingParams(max_tokens=4))
                w //= 2
            at0 = time.monotonic()
            for i in range(n_requests):
                aeng.submit(GenerationRequest(
                    request_id=f"aq-{i}", prompt_ids=prompt(),
                    sampling=SamplingParams(max_tokens=max_tokens)))
            while aeng.has_work:
                aeng.step()
            w8a8_wall = time.monotonic() - at0
            ares = [aeng.poll(f"aq-{i}") for i in range(n_requests)]
            assert all(r is not None and r.finish_reason != "error"
                       for r in ares)
            w8a8_p50_ms, w8a8_p99_ms = ttft_pcts(ares)
            n_pc = max(1, n_requests // 8)
            for i in range(n_pc):
                aeng.submit(GenerationRequest(
                    request_id=f"aqpc-{i}", prompt_ids=prompt(),
                    sampling=SamplingParams(max_tokens=max_tokens)))
            while aeng.has_work:
                aeng.step()
            apc = [aeng.poll(f"aqpc-{i}") for i in range(n_pc)]
            assert all(r is not None and r.finish_reason != "error"
                       for r in apc)
            w8a8_perchip_p50_ms, w8a8_perchip_p99_ms = ttft_pcts(apc)
            log(f"W8A8: p50 TTFT {w8a8_p50_ms:.1f} ms, p99 "
                f"{w8a8_p99_ms:.1f} ms at {n_requests} "
                f"concurrent (drained {w8a8_wall:.2f}s); per-chip-equiv "
                f"{w8a8_perchip_p50_ms:.1f} ms")

            # W8A8 + shared prefix: the realistic diagnosis shape at the
            # full 100-concurrent load on ONE chip.
            pre2 = prompt()[:shared_len]

            def w8a8_shared() -> list[int]:
                return pre2 + list(rng.integers(
                    4, cfg.vocab_size - 4, size=prompt_len - shared_len))
            aeng.generate([w8a8_shared()], SamplingParams(max_tokens=4))
            w = 2
            while w <= ecfg.max_prefills_per_step:
                aeng.generate([w8a8_shared() for _ in range(w)],
                              SamplingParams(max_tokens=4))
                w *= 2
            for i in range(n_requests):
                aeng.submit(GenerationRequest(
                    request_id=f"aqsh-{i}", prompt_ids=w8a8_shared(),
                    sampling=SamplingParams(max_tokens=max_tokens)))
            while aeng.has_work:
                aeng.step()
            ash = [aeng.poll(f"aqsh-{i}") for i in range(n_requests)]
            assert all(r is not None and r.finish_reason != "error"
                       for r in ash)
            w8a8_shared_p50_ms, w8a8_shared_p99_ms = ttft_pcts(ash)
            log(f"W8A8 shared-prefix: p50 TTFT {w8a8_shared_p50_ms:.1f} ms, "
                f"p99 {w8a8_shared_p99_ms:.1f} ms "
                f"at {n_requests} concurrent")

            # COLD shared prefix: same shape, but the cache has never seen
            # the prefix and nothing is pre-seeded — the first queries
            # after a fresh snapshot.  Admission's cold-burst dedup
            # (serving/engine.py _admit_round) must prefill the prefix
            # once, not once per round-1 lane; every compiled program is
            # already warm, so the delta vs the seeded leg above is pure
            # scheduling.
            pre_cold = list(rng.integers(4, cfg.vocab_size - 4,
                                         size=shared_len))

            def w8a8_cold() -> list[int]:
                return pre_cold + list(rng.integers(
                    4, cfg.vocab_size - 4, size=prompt_len - shared_len))
            defer0 = aeng.prefix_deferrals
            miss0 = aeng.prefix_cache.misses
            for i in range(n_requests):
                aeng.submit(GenerationRequest(
                    request_id=f"aqcold-{i}", prompt_ids=w8a8_cold(),
                    sampling=SamplingParams(max_tokens=max_tokens)))
            while aeng.has_work:
                aeng.step()
            acold = [aeng.poll(f"aqcold-{i}") for i in range(n_requests)]
            assert all(r is not None and r.finish_reason != "error"
                       for r in acold)
            cold_shared_p50_ms, cold_shared_p99_ms = ttft_pcts(acold)
            log(f"W8A8 COLD shared-prefix: p50 TTFT "
                f"{cold_shared_p50_ms:.1f} ms, p99 "
                f"{cold_shared_p99_ms:.1f} ms at {n_requests} concurrent "
                f"[{aeng.prefix_deferrals - defer0} deferrals, "
                f"{aeng.prefix_cache.misses - miss0} full-prefix misses]")

            # W8A8 fused-decode step rate at full lanes: the s8 x s8
            # matmul halves the compute term of the decode-step ridge
            # (see the attribution print above), so the serving-default
            # quant mode wins decode too, not just prefill.
            import jax.numpy as jnp

            Kd, Bd = ecfg.decode_steps_per_iter, ecfg.max_slots
            prog = aeng._decode_program(Kd, sampled=False)
            blocks_per = min((prompt_len + 16 + 15) // 16,
                             ecfg.max_blocks_per_seq)
            wtbl = np.zeros((Bd, ecfg.max_blocks_per_seq), np.int32)
            wtbl[:, :blocks_per] = np.arange(1, blocks_per + 1)[None, :]
            wtbl = jnp.asarray(wtbl)
            wctx = jnp.full((Bd,), prompt_len, jnp.int32)
            wrem = jnp.full((Bd,), 10 ** 6, jnp.int32)
            weos = jnp.asarray(-1, jnp.int32)
            wtok = jnp.zeros((Bd,), jnp.int32)
            _, wtok, aeng.pages = prog(params, wtok, wctx, wrem,
                                       aeng.pages, wtbl, weos)
            _ = int(wtok[0])
            wreps = 3
            wt0 = time.monotonic()
            for _ in range(wreps):
                _, wtok, aeng.pages = prog(params, wtok, wctx, wrem,
                                           aeng.pages, wtbl, weos)
            _ = int(wtok[0])
            wddt = time.monotonic() - wt0
            w8a8_decode_tok_s = Bd * wreps * Kd / wddt
            log(f"W8A8 decode: {wddt / (wreps * Kd) * 1e3:.1f} ms/step "
                f"-> {w8a8_decode_tok_s:.0f} tok/s at {Bd} lanes")
        except Exception as exc:  # noqa: BLE001 — extras never fail the bench
            log(f"W8A8 leg skipped: {exc}")
        finally:
            del aeng  # free its KV pool before the long-prompt engine

    # Long-prompt leg: realistic multi-KB diagnosis prompts exercising
    # chunked prefill (prompts > the largest bucket), so the headline number
    # can't hide a slow chunk path.  Separate engine so bucket shapes and the
    # KV pool match the longer sequences.
    long_p50_ms = long_p99_ms = None  # omitted if the leg doesn't complete
    long_shared_p50_ms = long_shared_p99_ms = None
    long_shared_perchip_p50_ms = None
    long_perchip_p50_ms = None
    try:
        n_long = int(os.environ.get("BENCH_LONG_CONCURRENCY", "16"))
        long_len = int(os.environ.get("BENCH_LONG_PROMPT_LEN", "1536"))
        lcfg = EngineConfig(
            max_slots=16,
            num_blocks=1700,
            block_size=16,
            max_blocks_per_seq=128,
            # 512 = the chunk width (measured optimal vs 768/1024); 256 =
            # the shared-prefix suffix bucket — without it the 256-token
            # suffix admissions pad to 512 (2x FLOPs; measured 549 ->
            # 310-345 ms p50 on the shared-prefix leg).
            prefill_buckets=(256, 512),
            max_prefills_per_step=4,
            max_admission_rounds=4,
            decode_steps_per_iter=8,
            # Prefill-priority for the burst: with 12 chunk rounds queued,
            # decode interleaves steal first-token bandwidth — 6 (vs the
            # default 3) measured 1.42s -> 1.30s p50 AND a faster drain
            # (2.92 -> 2.73s wall) at 16 concurrent long prompts.
            decode_every_n_chunk_rounds=6,
        )
        # Long-prompt chunks are pure prefill compute — run them W8A8
        # (same parity contract as the headline W8A8 leg) when the weights
        # are int8; extras record the mode.
        import dataclasses as _dc

        long_cfg = (_dc.replace(cfg, act_quant=True)
                    if quant == "int8" else cfg)
        leng = InferenceEngine(long_cfg, params, lcfg, eos_id=-1)

        def long_prompt() -> list[int]:
            return list(rng.integers(4, cfg.vocab_size - 4, size=long_len))

        # Warm the chunk-round lane ladder (P=1/2/4; the per-chip leg runs
        # 2 lanes) + the decode K ladder (max_tokens=16 walks K=8,4,2,1).
        leng.generate([long_prompt()], SamplingParams(max_tokens=16))
        leng.generate([long_prompt() for _ in range(2)],
                      SamplingParams(max_tokens=16))
        leng.generate([long_prompt() for _ in range(4)],
                      SamplingParams(max_tokens=16))
        lt0 = time.monotonic()
        for i in range(n_long):
            leng.submit(GenerationRequest(
                request_id=f"long-{i}",
                prompt_ids=long_prompt(),
                sampling=SamplingParams(max_tokens=max_tokens),
            ))
        while leng.has_work:
            leng.step()
        lwall = time.monotonic() - lt0
        lres = [leng.poll(f"long-{i}") for i in range(n_long)]
        bad = [r for r in lres if r is None or r.finish_reason == "error"]
        assert not bad, f"{len(bad)}/{n_long} long requests failed: {bad[:2]}"
        long_p50_ms, long_p99_ms = ttft_pcts(lres)
        log(f"long prompts ({long_len} tok x {n_long}): p50 TTFT "
            f"{long_p50_ms:.1f} ms, p99 {long_p99_ms:.1f} ms, "
            f"drained in {lwall:.2f}s")

        # Per-chip-equivalent long leg (the SLO's v5e-8 spread over 8).
        n_lpc = max(1, n_long // 8)
        for i in range(n_lpc):
            leng.submit(GenerationRequest(
                request_id=f"lpc-{i}", prompt_ids=long_prompt(),
                sampling=SamplingParams(max_tokens=max_tokens)))
        while leng.has_work:
            leng.step()
        lpcres = [leng.poll(f"lpc-{i}") for i in range(n_lpc)]
        assert all(r is not None and r.finish_reason != "error"
                   for r in lpcres)
        long_perchip_p50_ms = float(np.percentile(
            np.array(sorted(r.ttft_s for r in lpcres)), 50)) * 1e3
        log(f"long per-chip-equivalent ({n_lpc} concurrent): p50 TTFT "
            f"{long_perchip_p50_ms:.1f} ms")

        # Shared-prefix long prompts: the realistic long-diagnosis shape
        # (shared evidence prefix + per-query tail) through the chunked
        # admission path with prefix reuse.
        shared_long = long_prompt()[: long_len - 256]
        def sl_prompt() -> list[int]:
            return shared_long + list(rng.integers(
                4, cfg.vocab_size - 4, size=256))
        # Seed the prefix, then warm the suffix-bucket chunked-admission
        # ladder (P=2/4 at the 256 bucket) so nothing compiles in-window.
        leng.generate([sl_prompt()], SamplingParams(max_tokens=4))
        leng.generate([sl_prompt() for _ in range(2)],
                      SamplingParams(max_tokens=16))
        leng.generate([sl_prompt() for _ in range(4)],
                      SamplingParams(max_tokens=16))
        st = time.monotonic()
        for i in range(n_long):
            leng.submit(GenerationRequest(
                request_id=f"sl-{i}", prompt_ids=sl_prompt(),
                sampling=SamplingParams(max_tokens=max_tokens)))
        while leng.has_work:
            leng.step()
        slres = [leng.poll(f"sl-{i}") for i in range(n_long)]
        assert all(r is not None and r.finish_reason != "error"
                   for r in slres)
        long_shared_p50_ms, long_shared_p99_ms = ttft_pcts(slres)
        log(f"shared-prefix long prompts: p50 TTFT "
            f"{long_shared_p50_ms:.1f} ms, p99 {long_shared_p99_ms:.1f} ms, "
            f"drained in {time.monotonic() - st:.2f}s")

        # Per-chip-equivalent shared long prompts: the actual v5e-8
        # long-diagnosis shape — shared evidence prefix, per-chip share of
        # the burst.
        for i in range(n_lpc):
            leng.submit(GenerationRequest(
                request_id=f"slpc-{i}", prompt_ids=sl_prompt(),
                sampling=SamplingParams(max_tokens=max_tokens)))
        while leng.has_work:
            leng.step()
        slpc = [leng.poll(f"slpc-{i}") for i in range(n_lpc)]
        assert all(r is not None and r.finish_reason != "error"
                   for r in slpc)
        long_shared_perchip_p50_ms, _ = ttft_pcts(slpc)
        log(f"shared-prefix long per-chip-equivalent ({n_lpc} concurrent): "
            f"p50 TTFT {long_shared_perchip_p50_ms:.1f} ms")
        del leng
    except Exception as exc:  # noqa: BLE001 — extras never fail the bench
        log(f"long-prompt bench skipped: {exc}")

    # --- speculative-decode leg: prompt-lookup speculation A/B ----------
    # Decode-heavy shape (long generations, moderate concurrency) where
    # weight streaming dominates; speculation turns one verify forward into
    # up to spec_k+1 emitted tokens when the output continues an n-gram
    # from its own context (serving/spec.py).  A/B on identical prompts.
    #
    # Honesty note: random-init weights never quote their context — every
    # workload construction tried (random prompts, prompts embedding the
    # model's own prior greedy continuation, fully periodic prompts)
    # measures acceptance at exactly the 1.0 floor, because a random
    # model's argmax never re-walks an n-gram.  So this leg does NOT claim
    # a speculation speedup; it proves the *adaptive controller's floor
    # costs nothing* (spec-enabled ~= fused throughput), which is the
    # property that makes shipping the feature safe.  spec_k defaults to
    # 0 in the serving config; enable it for real quoting checkpoints.
    spec_tok_s = spec_base_tok_s = spec_tpv = None
    try:
        import dataclasses as _dc

        n_sp = int(os.environ.get("BENCH_SPEC_CONCURRENCY", "32"))
        sp_gen = int(os.environ.get("BENCH_SPEC_MAX_TOKENS", "128"))
        sp_cap = prompt_len + sp_gen + 16
        sp_base = EngineConfig(
            max_slots=32,
            num_blocks=min(1400, 32 * ((sp_cap + 15) // 16) + 64),
            block_size=16,
            max_blocks_per_seq=(sp_cap + 15) // 16,
            prefill_buckets=(bucket,),
            max_prefills_per_step=8,
            max_admission_rounds=4,
            decode_steps_per_iter=8,
        )
        sp_prompts = [prompt() for _ in range(n_sp)]
        for spec_on in (False, True):
            se = InferenceEngine(
                cfg, params,
                _dc.replace(sp_base, spec_k=4 if spec_on else 0),
                eos_id=-1)
            # Warm BOTH decode programs: with spec on, the first warmup
            # dispatch is speculative and emits only a few tokens, so an
            # 8-token warmup never compiles the fused K=8 program and its
            # multi-second (cache-)compile would land inside the measured
            # window (observed as a phantom 2-6x "regression").  Warmup
            # prompts are DISTINCT (an identical batch would trip the
            # cold-burst dedup and admit P=1, leaving the P=8 dense
            # program cold) and disjoint from the measured burst (so the
            # burst itself runs all-miss dense rounds).  The second call
            # re-sends one registered prompt to warm the P=8 *chunked*
            # hit-path admission.
            warm_prompts = [prompt() for _ in range(8)]
            se.generate(warm_prompts, SamplingParams(max_tokens=24))
            se.generate([warm_prompts[0]] * 8, SamplingParams(max_tokens=24))
            spt0 = time.monotonic()
            for i, p in enumerate(sp_prompts):
                se.submit(GenerationRequest(
                    request_id=f"sp-{i}", prompt_ids=p,
                    sampling=SamplingParams(max_tokens=sp_gen)))
            while se.has_work:
                se.step()
            dt = time.monotonic() - spt0
            spres = [se.poll(f"sp-{i}") for i in range(n_sp)]
            assert all(r is not None and r.finish_reason != "error"
                       for r in spres)
            tput = sum(len(r.token_ids) for r in spres) / dt
            if spec_on:
                spec_tok_s = tput
                # Per-lane acceptance: emitted tokens per (lane x verify
                # round); 1.0 = no draft ever accepted, k+1 = all accepted.
                spec_tpv = (se.spec_tokens / se.spec_lane_rounds
                            if se.spec_lane_rounds else 0.0)
                log(f"spec decode (k=4): {tput:.0f} tok/s, "
                    f"{spec_tpv:.2f} accepted tokens/lane-round "
                    f"(baseline {spec_base_tok_s:.0f} tok/s, "
                    f"{tput / spec_base_tok_s:.2f}x)")
            else:
                spec_base_tok_s = tput
            del se

    except Exception as exc:  # noqa: BLE001 — extras never fail the bench
        log(f"spec-decode leg skipped: {exc}")

    # --- spec quote mode: acceptance measured on a model that QUOTES ----
    # Every prompt construction against random-init weights measures the
    # 1.0 floor (tried: random prompts, prompts embedding the model's own
    # prior greedy continuation P+G+P+G[:16], fully periodic prompts, and
    # fixed-point iteration Q <- greedy(P+Q) — the greedy map is chaotic
    # and never converges), so the old self-quote construction was
    # structurally flat: it could only ever print 1.0.  This leg instead
    # builds a checkpoint that genuinely quotes: attention and MLP output
    # projections zeroed (the residual stream carries exactly the current
    # token's embedding) and the unembed wired to a vocab-cycle
    # permutation of the embedding table, so greedy decode
    # deterministically walks the cycle.  A prompt holding two periods of
    # that cycle IS a quoting workload — the true continuation re-walks
    # trigrams the history already contains, the regime the n-gram
    # proposer (serving/spec.py) exists for.  Same engine, same verify
    # kernels, real forward passes; only the checkpoint is synthetic.
    spec_quote_accept = None
    spec_quote_tok_s = spec_quote_base_tok_s = None
    try:
        import copy as _copy

        import jax.numpy as jnp
        from k8s_llm_monitor_tpu.models.config import ModelConfig as _MC

        qcfg = _MC(name="quote-tiny", vocab_size=512, hidden_size=64,
                   intermediate_size=128, num_layers=2, num_heads=4,
                   num_kv_heads=2, dtype="float32", rope_theta=10_000.0)
        qparams = _copy.deepcopy(llama.init_params(jax.random.PRNGKey(11),
                                                   qcfg))
        cyc0, cycn = 10, 48
        orbit = list(range(cyc0, cyc0 + cycn))
        qE = np.asarray(qparams["embed"]["weight"], np.float32)
        qU = np.zeros((qcfg.hidden_size, qcfg.vocab_size), np.float32)
        for qi, qt in enumerate(orbit):
            qU[:, orbit[(qi + 1) % cycn]] = qE[qt]
        for qlayer in qparams["layers"]:
            qlayer["o"]["kernel"] = jnp.zeros_like(qlayer["o"]["kernel"])
            qlayer["down"]["kernel"] = jnp.zeros_like(
                qlayer["down"]["kernel"])
        qparams["lm_head"]["kernel"] = jnp.asarray(qU)

        q_gen, q_n = 96, 8
        # Distinct per-lane prompts (cycle rotations — each still quotes):
        # identical prompts would trip cold-burst dedup and prefix reuse.
        q_prompts = [orbit[qi:] + orbit[:qi] + orbit[qi:] + orbit[:qi]
                     for qi in range(q_n)]
        q_cap = 2 * cycn + q_gen + 1
        q_ecfg = EngineConfig(
            max_slots=q_n, num_blocks=q_n * ((q_cap + 15) // 16) + 8,
            block_size=16, max_blocks_per_seq=(q_cap + 15) // 16,
            prefill_buckets=(2 * cycn,), max_prefills_per_step=q_n,
            decode_steps_per_iter=8, prefix_cache_entries=0)
        import dataclasses as _dc

        for q_k in (0, 4):
            qe = InferenceEngine(
                qcfg, qparams,
                _dc.replace(q_ecfg, spec_k=q_k, spec_min_accept=0.0),
                eos_id=-1)
            qe.generate(q_prompts, SamplingParams(max_tokens=8))  # warm
            qe.spec_tokens = qe.spec_verify_steps = qe.spec_lane_rounds = 0
            qt0 = time.monotonic()
            for qi, qp in enumerate(q_prompts):
                qe.submit(GenerationRequest(
                    request_id=f"q-{qi}", prompt_ids=qp,
                    sampling=SamplingParams(max_tokens=q_gen)))
            while qe.has_work:
                qe.step()
            q_dt = time.monotonic() - qt0
            q_res = [qe.poll(f"q-{qi}") for qi in range(q_n)]
            assert all(r is not None and r.finish_reason != "error"
                       for r in q_res)
            # Self-consistency gate: every lane must have emitted its own
            # cycle continuation exactly, or the acceptance number is
            # measuring a broken construction rather than quoting.
            for qi, r in enumerate(q_res):
                want = [orbit[(qi + j) % cycn] for j in range(q_gen)]
                assert r.token_ids == want, f"lane {qi} left the cycle"
            tput = q_n * q_gen / q_dt
            if q_k:
                spec_quote_tok_s = tput
                spec_quote_accept = (qe.spec_tokens /
                                     max(qe.spec_lane_rounds, 1))
            else:
                spec_quote_base_tok_s = tput
            del qe
        log(f"spec quote mode (cycle checkpoint): {spec_quote_accept:.2f} "
            f"accepted tokens/lane-round (ceiling {4 + 1}.0), "
            f"{spec_quote_tok_s:.0f} tok/s vs {spec_quote_base_tok_s:.0f} "
            f"unspeculated "
            f"({spec_quote_tok_s / spec_quote_base_tok_s:.2f}x)")
    except Exception as exc:  # noqa: BLE001 — extras never fail the bench
        log(f"spec quote-mode leg skipped: {exc}")

    # --- long-context verify: the Pallas multi-query kernel on a measured
    # path.  At >= 2048-token tables (the VERIFY_KERNEL_MIN_TABLE_TOKENS
    # gate) the engine selects paged_verify_attention_pallas for spec
    # verify; this leg runs BOTH impls on the same long-context spec
    # workload so the artifact re-measures the kernel-vs-gather crossover
    # every round instead of shipping a stale gate. -------------------
    vk_tok_s = vg_tok_s = None
    try:
        import dataclasses as _dc

        from k8s_llm_monitor_tpu.ops import attention as _attn

        vcfg_e = EngineConfig(
            max_slots=8, num_blocks=8 * 128 + 32, block_size=16,
            max_blocks_per_seq=128,              # 2048-token tables
            prefill_buckets=(512,), max_prefills_per_step=4,
            max_admission_rounds=4, decode_steps_per_iter=8,
            spec_k=4, spec_rounds_per_iter=4,
            spec_min_accept=0.0,                 # always speculate: the
            # leg measures the verify IMPL, not acceptance (floor = 1.0)
        )
        vlen, vgen, nv = 1700, 48, 8

        def vprompt() -> list[int]:
            return list(rng.integers(4, cfg.vocab_size - 4, size=vlen))

        saved_gate = _attn.VERIFY_KERNEL_MIN_TABLE_TOKENS
        for force_gather in (False, True):
            # The gate is a module constant consulted at engine build;
            # raising it beyond the table size forces the gather impl for
            # the A/B.  Restored in finally.
            _attn.VERIFY_KERNEL_MIN_TABLE_TOKENS = (
                10 ** 9 if force_gather else saved_gate)
            try:
                ve = InferenceEngine(cfg, params, vcfg_e, eos_id=-1)
                if (not force_gather and dev.platform == "tpu"):
                    from k8s_llm_monitor_tpu.ops.pallas_attention import (
                        paged_verify_attention_pallas,
                    )
                    assert ve._verify_impl is paged_verify_attention_pallas
                for w in (1, 2, 4):
                    ve.generate([vprompt() for _ in range(w)],
                                SamplingParams(max_tokens=16))
                vt0 = time.monotonic()
                for i in range(nv):
                    ve.submit(GenerationRequest(
                        request_id=f"vk-{i}", prompt_ids=vprompt(),
                        sampling=SamplingParams(max_tokens=vgen)))
                while ve.has_work:
                    ve.step()
                vdt = time.monotonic() - vt0
                vres = [ve.poll(f"vk-{i}") for i in range(nv)]
                assert all(r is not None and r.finish_reason != "error"
                           for r in vres)
                tput = sum(len(r.token_ids) for r in vres) / vdt
                if force_gather:
                    vg_tok_s = tput
                else:
                    vk_tok_s = tput
                del ve
            finally:
                _attn.VERIFY_KERNEL_MIN_TABLE_TOKENS = saved_gate
        log(f"long-context spec verify ({vlen}-token ctx, 2048-token "
            f"tables): Pallas kernel {vk_tok_s:.0f} tok/s vs XLA gather "
            f"{vg_tok_s:.0f} tok/s")
    except Exception as exc:  # noqa: BLE001 — extras never fail the bench
        log(f"long-context verify leg skipped: {exc}")

    # --- constrained-decode leg: grammar FSM masking A/B ----------------
    # The diagnosis engine's verdict grammar (diagnosis/grammar.py) masks
    # logits against a token FSM inside the same fused decode scan the
    # free path runs.  This leg measures the per-token decode tax of that
    # mask on one engine serving both kinds of lanes, checks the 100%
    # schema-validity property on everything sampled, and asserts the
    # overhead stays under 10% — the budget that makes constrained
    # verdicts the default for /api/v1/analyze.
    free_ms_tok = constrained_ms_tok = constrained_penalty = None
    try:
        from k8s_llm_monitor_tpu.diagnosis.grammar import (
            parse_verdict,
            verdict_fsm,
        )

        if cfg.vocab_size < 259:
            raise ValueError(
                f"vocab {cfg.vocab_size} < byte-tokenizer vocab 259")
        g_n = int(os.environ.get("BENCH_CONSTRAINED_CONCURRENCY", "8"))
        g_len, g_gen = 64, 256
        fsm = verdict_fsm(eos_id=2)
        g_cap = g_len + max(g_gen, fsm.max_len) + 16
        g_ecfg = EngineConfig(
            max_slots=g_n,
            num_blocks=g_n * ((g_cap + 15) // 16) + 16,
            block_size=16,
            max_blocks_per_seq=(g_cap + 15) // 16,
            prefill_buckets=(g_len,),
            max_prefills_per_step=g_n,
            decode_steps_per_iter=8,
        )
        ge = InferenceEngine(cfg, params, g_ecfg, eos_id=2)
        ge.set_grammar(fsm)

        def g_prompt() -> list[int]:
            return [int(t) for t in
                    rng.integers(4, min(cfg.vocab_size, 259) - 4, size=g_len)]

        g_free = SamplingParams(max_tokens=g_gen, temperature=0.7)
        g_con = SamplingParams(max_tokens=1, temperature=0.7,
                               constrained=True)
        # Warm both program families (free and constrained decode).
        ge.generate([g_prompt() for _ in range(g_n)],
                    SamplingParams(max_tokens=8, temperature=0.7))
        ge.generate([g_prompt() for _ in range(g_n)], g_con)

        def per_token_ms(results) -> float:
            rates = [(r.latency_s - r.ttft_s) * 1e3 / (len(r.token_ids) - 1)
                     for r in results if len(r.token_ids) > 1]
            return float(np.median(rates))

        free_res = ge.generate([g_prompt() for _ in range(g_n)], g_free)
        con_res = ge.generate([g_prompt() for _ in range(g_n)], g_con)
        assert all(r.finish_reason != "error" for r in free_res + con_res)
        for r in con_res:  # the 100% schema-validity property, re-proven
            parse_verdict("".join(chr(t - 3) for t in r.token_ids
                                  if 3 <= t < 259))
        free_ms_tok = per_token_ms(free_res)
        constrained_ms_tok = per_token_ms(con_res)
        constrained_penalty = (constrained_ms_tok - free_ms_tok) \
            / free_ms_tok
        log(f"constrained decode: {constrained_ms_tok:.2f} ms/tok vs "
            f"free {free_ms_tok:.2f} ms/tok "
            f"({constrained_penalty * 100:+.1f}% tok/s penalty)")
        assert constrained_penalty < 0.10, (
            f"constrained decode tax {constrained_penalty * 100:.1f}% "
            f"exceeds the 10% budget")
        del ge
    except AssertionError:
        raise  # a blown overhead budget IS a bench failure
    except Exception as exc:  # noqa: BLE001 — extras never fail the bench
        log(f"constrained-decode leg skipped: {exc}")

    # BASELINE config #3: encoder embedding throughput (BGE-large geometry
    # on TPU, tiny on CPU smoke runs), via the anomaly detector's batch path.
    embed_docs_per_s = 0.0
    try:
        from k8s_llm_monitor_tpu.analysis.anomaly import EmbeddingAnomalyDetector
        from k8s_llm_monitor_tpu.models.config import ENCODER_PRESETS

        enc_name = os.environ.get(
            "BENCH_ENCODER",
            "bge-large-bf16" if dev.platform == "tpu" else "tiny-encoder")
        det = EmbeddingAnomalyDetector(ENCODER_PRESETS[enc_name])
        docs = [f"Warning: BackOff restarting failed container web-{i} "
                f"in pod default/web-{i}; exit code 137 OOMKilled" * 3
                for i in range(64)]
        det.embed(docs)  # compile
        et0 = time.monotonic()
        reps = 5
        for _ in range(reps):
            emb = det.embed(docs)
        embed_wall = time.monotonic() - et0
        embed_docs_per_s = reps * len(docs) / embed_wall
        log(f"encoder {enc_name}: {embed_docs_per_s:.0f} docs/s "
            f"({len(docs)}-doc batches)")
    except Exception as exc:  # noqa: BLE001 — extras never fail the bench
        log(f"encoder bench skipped: {exc}")

    # BASELINE config #1: ONE /api/v1/query root-cause request end-to-end
    # through the booted HTTP server (fake cluster, template backend — the
    # zero-accelerator CPU path), timed as a real HTTP round trip including
    # evidence collection.  The reference documents this endpoint but never
    # implemented it (README.md:89-95 vs cmd/server/main.go:97-141), so
    # this is the number it has no counterpart for.
    query_e2e_ms = None
    try:
        import urllib.request

        from k8s_llm_monitor_tpu.monitor.analysis import (
            AnalysisEngine,
            TemplateBackend,
        )
        from k8s_llm_monitor_tpu.monitor.client import Client
        from k8s_llm_monitor_tpu.monitor.cluster import (
            FakeCluster,
            seed_demo_cluster,
        )
        from k8s_llm_monitor_tpu.monitor.config import Config, MetricsConfig
        from k8s_llm_monitor_tpu.monitor.manager import Manager
        from k8s_llm_monitor_tpu.monitor.server import MonitorServer

        fake = seed_demo_cluster(FakeCluster())
        qclient = Client(fake, namespaces=["default", "kube-system"])
        qmanager = Manager(
            qclient, MetricsConfig(namespaces=["default"],
                                   enable_network=True))
        qmanager.collect()
        qanalysis = AnalysisEngine(
            TemplateBackend(), client=qclient, manager=qmanager)
        srv = MonitorServer(config=Config(), client=qclient,
                            manager=qmanager, analysis=qanalysis, port=0)
        srv.start()
        qreq = urllib.request.Request(
            f"http://127.0.0.1:{srv.port}/api/v1/query",
            data=json.dumps(
                {"question": "why is the web pod failing to reach the "
                             "database service?"}).encode(),
            headers={"Content-Type": "application/json"}, method="POST")
        with urllib.request.urlopen(qreq) as r:  # warm the route once
            r.read()
        qtimes = []
        for _ in range(5):
            qt0 = time.monotonic()
            with urllib.request.urlopen(qreq) as r:
                r.read()
            qtimes.append(time.monotonic() - qt0)
        query_e2e_ms = float(np.median(qtimes)) * 1e3
        srv.stop()
        log(f"query E2E (HTTP round trip, fake cluster, template backend): "
            f"{query_e2e_ms:.1f} ms")
    except Exception as exc:  # noqa: BLE001 — extras never fail the bench
        log(f"query E2E leg skipped: {exc}")

    # --- warm-restart leg: crash-safe lifecycle handover cost (PR 4).
    # Kills the step loop under streaming load and measures death-detected
    # -> first replayed token reaching a caller: supervisor teardown +
    # engine rebuild via the factory + journal-trimmed re-admission
    # (docs/resilience.md).  Small dedicated engine; a pre-kill warm build
    # on the same shapes keeps jit compiles out of the measured window.
    restart_to_token_ms = restart_replayed = None
    try:
        import tempfile
        import threading as _th

        from k8s_llm_monitor_tpu.resilience.faults import get_injector
        from k8s_llm_monitor_tpu.resilience.journal import RequestJournal
        from k8s_llm_monitor_tpu.resilience.retry import Backoff
        from k8s_llm_monitor_tpu.serving.supervisor import EngineSupervisor

        r_len, r_gen, r_n = 64, 96, 4
        r_cap = r_len + r_gen + 16
        r_ecfg = EngineConfig(
            max_slots=r_n,
            num_blocks=r_n * ((r_cap + 15) // 16) + 16,
            block_size=16,
            max_blocks_per_seq=(r_cap + 15) // 16,
            prefill_buckets=(r_len,),
            max_prefills_per_step=r_n,
            decode_steps_per_iter=4,
        )

        def r_factory():
            return InferenceEngine(cfg, params, r_ecfg, eos_id=-1)

        def r_prompt() -> list[int]:
            return [int(t) for t in
                    rng.integers(4, cfg.vocab_size - 4, size=r_len)]

        warm_eng = r_factory()
        warm_eng.generate([r_prompt() for _ in range(r_n)],
                          SamplingParams(max_tokens=4))
        del warm_eng

        sup = EngineSupervisor(
            r_factory,
            journal=RequestJournal(tempfile.mkdtemp(prefix="bench-wal-"),
                                   fsync="never"),
            max_restarts=2,
            backoff=Backoff(base_s=0.05, cap_s=0.1, jitter=0.0),
            heartbeat_timeout_s=600.0,   # death-signal path only, no wedge
            poll_interval_s=0.01,
        )
        try:
            stamps: list[list[float]] = [[] for _ in range(r_n)]
            handles = [sup.submit(r_prompt(),
                                  SamplingParams(max_tokens=r_gen))
                       for _ in range(r_n)]

            def r_consume(i):
                for _tok in handles[i].stream(timeout=120.0):
                    stamps[i].append(time.monotonic())

            r_threads = [_th.Thread(target=r_consume, args=(i,), daemon=True)
                         for i in range(r_n)]
            for t in r_threads:
                t.start()
            # Every request must have streamed progress before the kill so
            # the replay actually trims delivered tokens.
            r_deadline = time.monotonic() + 120.0
            while min((len(s) for s in stamps), default=0) < 4:
                if time.monotonic() > r_deadline:
                    raise TimeoutError("no streaming progress before kill")
                time.sleep(0.002)
            get_injector().arm("step_loop_crash", rate=1.0, times=1)
            while sup.state == "serving":
                if time.monotonic() > r_deadline:
                    raise TimeoutError("injected crash never detected")
                time.sleep(0.0005)
            t_dead = time.monotonic()
            for t in r_threads:
                t.join(timeout=120.0)
            r_res = [h.result(timeout=120.0) for h in handles]
            assert all(r.finish_reason != "error" for r in r_res)
            assert all(len(r.token_ids) == r_gen for r in r_res), \
                "lost or duplicated tokens across the restart"
            # Any token stamped after death detection is from the rebuilt
            # engine (the supervisor severs the old loop's observer).
            first_after = min(t for s in stamps for t in s if t > t_dead)
            restart_to_token_ms = (first_after - t_dead) * 1e3
            restart_replayed = sup.replayed_total
            log(f"warm restart: {restart_to_token_ms:.0f} ms from step-loop "
                f"death to first replayed token ({sup.restarts} restart, "
                f"{restart_replayed} requests replayed)")
        finally:
            sup.shutdown(grace_s=5.0)
    except Exception as exc:  # noqa: BLE001 — extras never fail the bench
        log(f"warm-restart leg skipped: {exc}")

    fleet_stats: dict = {}
    try:
        if os.environ.get("BENCH_FLEET", "1") == "1":
            fleet_stats = fleet_leg(cfg, params)
    except Exception as exc:  # noqa: BLE001 — extras never fail the bench
        log(f"fleet leg skipped: {exc}")

    kv_tier_stats_d: dict = {}
    try:
        if os.environ.get("BENCH_KVTIER", "1") == "1":
            kv_tier_stats_d = kv_tier_leg(cfg, params)
    except Exception as exc:  # noqa: BLE001 — extras never fail the bench
        log(f"kv tier leg skipped: {exc}")

    migration_stats: dict = {}
    try:
        if os.environ.get("BENCH_MIGRATION", "1") == "1":
            migration_stats = migration_leg(cfg, params)
    except Exception as exc:  # noqa: BLE001 — extras never fail the bench
        log(f"prefix migration leg skipped: {exc}")

    tracing_stats: dict = {}
    try:
        if os.environ.get("BENCH_TRACING", "1") == "1":
            tracing_stats = tracing_leg(cfg, params)
    except Exception as exc:  # noqa: BLE001 — extras never fail the bench
        log(f"tracing overhead leg skipped: {exc}")

    signals_stats: dict = {}
    try:
        if os.environ.get("BENCH_SIGNALS", "1") == "1":
            signals_stats = signals_leg(cfg, params)
    except AssertionError:
        raise  # a blown scraper budget IS a bench failure
    except Exception as exc:  # noqa: BLE001 — extras never fail the bench
        log(f"signals overhead leg skipped: {exc}")

    elastic_stats: dict = {}
    try:
        if os.environ.get("BENCH_ELASTIC", "1") == "1":
            elastic_stats = elasticity_leg(cfg, params)
    except AssertionError:
        raise  # a blown handoff-TTFT budget IS a bench failure
    except Exception as exc:  # noqa: BLE001 — extras never fail the bench
        log(f"elasticity leg skipped: {exc}")

    tenant_stats: dict = {}
    try:
        if os.environ.get("BENCH_TENANT", "1") == "1":
            tenant_stats = tenant_fairness_leg(cfg, params)
    except AssertionError:
        raise  # a blown fairness/exactness gate IS a bench failure
    except Exception as exc:  # noqa: BLE001 — extras never fail the bench
        log(f"tenant fairness leg skipped: {exc}")

    extras = {
        "model": model_name,
        "quant": quant,
        "concurrency": n_requests,
        "prompt_len": prompt_len,
        "max_tokens": max_tokens,
        "p99_ttft_ms": round(p99 * 1e3, 2),
        "throughput_tok_s": round(toks_per_s, 1),
        "wall_s": round(wall, 2),
        "platform": dev.platform,
        "device_kind": dev.device_kind,
        "warmup_s": round(warmup_s, 1),
        "compile_cache_warm": cache_was_warm,
        "weight_gib": round(weight_bytes / 2**30, 2),
        "embed_docs_per_s": round(embed_docs_per_s, 1),
        "slo_context": "500ms SLO is v5e-8 (8 chips); this is 1 chip at "
                       "8x the SLO's per-chip load",
        # Tail budget: with a uniform-length burst admitted FIFO, p99 TTFT
        # ~= the serial prefill time of the whole burst on this one chip
        # (admission-order physics, not queue mismanagement); an 8-chip
        # deployment divides it by the chip count.
        "tail_budget": "p99 ~= burst_prefill_total / n_chips",
    }
    if query_e2e_ms is not None:
        extras["query_e2e_ms"] = round(query_e2e_ms, 2)
    if perchip_p50_ms is not None:
        # Informational only: burst/8 through one chip models neither the
        # ICI collectives nor the shared-KV-pool batching of a real slice.
        # The measured multi-chip numbers are the mesh_* keys below.
        extras["perchip_equiv_p50_ttft_ms"] = round(perchip_p50_ms, 2)
        extras["perchip_equiv_p99_ttft_ms"] = round(perchip_p99_ms, 2)
        extras["perchip_equiv_informational"] = True
    extras.update(mesh_stats)
    if shared_p50_ms is not None:
        extras["shared_prefix_p50_ttft_ms"] = round(shared_p50_ms, 2)
        extras["shared_prefix_p99_ttft_ms"] = round(shared_p99_ms, 2)
        extras["shared_prefix_len"] = shared_len
    if slo_class_stats is not None:
        # Per-class TTFT under the mixed-class burst; the interactive
        # entry carries the p99 <= 2x p50 tail verdict (tail_ok).
        extras["slo_class_burst"] = slo_class_stats
    if prefill_tflops:
        extras["prefill_tflops"] = round(prefill_tflops, 1)
        extras["prefill_mfu"] = round(prefill_mfu, 3)
    if decode_gbs:
        extras["decode_weight_gbs"] = round(decode_gbs, 1)
        extras["decode_bw_util"] = round(decode_bw_util, 3)
        if decode_step_ms is not None:
            extras["decode_step_ms"] = round(decode_step_ms, 2)
            extras["decode_step_stream_ms"] = round(decode_stream_ms, 2)
            extras["decode_step_matmul_ms"] = round(decode_matmul_ms, 2)
            extras["decode_attribution"] = (
                "compute/bandwidth ridge at this lane count: weight "
                "streaming + B-scaled matmul each ~10ms; not HBM-bound")
    extras["decode_path"] = decode_path
    if fused_decode_step_ms is not None:
        extras["fused_decode_step_ms"] = round(fused_decode_step_ms, 2)
        extras["fallback_decode_step_ms"] = round(fallback_decode_step_ms, 2)
        extras["fused_matches_fallback"] = fused_match
    if decode_phases is not None:
        extras["decode_attn_ms"] = round(decode_phases["decode_attn_ms"], 2)
        extras["decode_sample_ms"] = round(
            decode_phases["decode_sample_ms"], 2)
    if dec_e2e_tok_s is not None:
        extras["decode_e2e_128lane_tok_s"] = round(dec_e2e_tok_s, 1)
    if w8a8_decode_tok_s is not None:
        extras["w8a8_decode_tok_s"] = round(w8a8_decode_tok_s, 1)
    if long_p50_ms is not None:  # 0.0 would read as a perfect score
        extras["long_prompt_p50_ttft_ms"] = round(long_p50_ms, 2)
        extras["long_prompt_p99_ttft_ms"] = round(long_p99_ms, 2)
        extras["long_quant"] = "w8a8" if quant == "int8" else quant
    if long_shared_p50_ms is not None:
        extras["long_shared_prefix_p50_ttft_ms"] = round(long_shared_p50_ms, 2)
        extras["long_shared_prefix_p99_ttft_ms"] = round(
            long_shared_p99_ms, 2)
    if long_shared_perchip_p50_ms is not None:
        extras["long_shared_perchip_p50_ttft_ms"] = round(
            long_shared_perchip_p50_ms, 2)
    if long_perchip_p50_ms is not None:
        extras["long_perchip_equiv_p50_ttft_ms"] = round(long_perchip_p50_ms, 2)
    if w8a8_p50_ms is not None:
        extras["w8a8_p50_ttft_ms"] = round(w8a8_p50_ms, 2)
        extras["w8a8_p99_ttft_ms"] = round(w8a8_p99_ms, 2)
        extras["w8a8_wall_s"] = round(w8a8_wall, 2)
    if w8a8_perchip_p50_ms is not None:
        extras["w8a8_perchip_p50_ttft_ms"] = round(w8a8_perchip_p50_ms, 2)
        extras["w8a8_perchip_p99_ttft_ms"] = round(w8a8_perchip_p99_ms, 2)
    if w8a8_shared_p50_ms is not None:
        extras["w8a8_shared_prefix_p50_ttft_ms"] = round(w8a8_shared_p50_ms, 2)
        extras["w8a8_shared_prefix_p99_ttft_ms"] = round(
            w8a8_shared_p99_ms, 2)
    if cold_shared_p50_ms is not None:
        extras["w8a8_cold_shared_prefix_p50_ttft_ms"] = round(
            cold_shared_p50_ms, 2)
        extras["w8a8_cold_shared_prefix_p99_ttft_ms"] = round(
            cold_shared_p99_ms, 2)
    if spec_tok_s is not None:
        extras["spec_decode_tok_s"] = round(spec_tok_s, 1)
        extras["spec_baseline_tok_s"] = round(spec_base_tok_s, 1)
        extras["spec_accept_per_lane_round"] = round(spec_tpv, 2)
        extras["spec_default"] = "off (spec_k=0): random-init weights "\
            "measure the 1.0 acceptance floor on every construction; "\
            "this leg proves the adaptive floor costs ~nothing"
    if spec_quote_accept is not None:
        extras["spec_quote_accept"] = round(spec_quote_accept, 2)
        extras["spec_quote_tok_s"] = round(spec_quote_tok_s, 1)
        extras["spec_quote_base_tok_s"] = round(spec_quote_base_tok_s, 1)
        extras["spec_quote_speedup"] = round(
            spec_quote_tok_s / max(spec_quote_base_tok_s, 1e-9), 2)
    if vk_tok_s is not None and vg_tok_s is not None:
        extras["verify_kernel_longctx_tok_s"] = round(vk_tok_s, 1)
        extras["verify_gather_longctx_tok_s"] = round(vg_tok_s, 1)
    if constrained_penalty is not None:
        extras["constrained_decode_ms_per_tok"] = round(constrained_ms_tok, 3)
        extras["free_decode_ms_per_tok"] = round(free_ms_tok, 3)
        extras["constrained_decode_penalty"] = round(constrained_penalty, 3)
    if restart_to_token_ms is not None:
        extras["warm_restart_to_token_ms"] = round(restart_to_token_ms, 1)
        extras["warm_restart_replayed"] = restart_replayed
    extras.update(fleet_stats)
    extras.update(kv_tier_stats_d)
    extras.update(migration_stats)
    extras.update(tracing_stats)
    extras.update(signals_stats)
    extras.update(elastic_stats)
    extras.update(tenant_stats)
    log(f"total bench time {time.monotonic() - t0:.0f}s")
    print(json.dumps({
        "metric": "p50_ttft_100c_ms",
        "value": round(p50 * 1e3, 2),
        "unit": "ms",
        "vs_baseline": round(0.5 / p50, 3) if p50 > 0 else 0.0,
        "extras": extras,
    }))


if __name__ == "__main__":
    main()
