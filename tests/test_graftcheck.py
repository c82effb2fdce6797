"""graftcheck suite: AST rules, trace-time guards, lock discipline.

Three layers, mirroring k8s_llm_monitor_tpu/devtools/:

  * astlint — every rule gets a seeded-violation positive and a clean
    negative, plus suppression and parse-error behavior;
  * traceguard — the recompile guard proves zero new compilations across
    same-bucket re-invocations on both decode paths, and (the control)
    that a deliberate bucket miss IS counted;
  * lockcheck — cycle detection, long-hold flagging, guarded-write
    tracking, and the disabled-mode fast path.
"""

from __future__ import annotations

import textwrap
import threading
import time

import pytest

from k8s_llm_monitor_tpu.devtools import astlint, lockcheck


def lint(src: str, rule: str | None = None):
    findings = astlint.lint_source(textwrap.dedent(src), path="snippet.py")
    if rule is not None:
        findings = [f for f in findings if f.rule == rule]
    return findings


# -- astlint: jit-host-read --------------------------------------------------


def test_jit_host_read_flags_time_in_jit_body():
    src = """
    import jax, time

    @jax.jit
    def step(x):
        t = time.time()
        return x + t
    """
    assert len(lint(src, "jit-host-read")) == 1


def test_jit_host_read_flags_env_and_rng_seed():
    src = """
    import jax, os, random

    @jax.jit
    def step(x):
        flag = os.environ["K8SLLM_DEBUG"]
        random.seed(0)
        return x
    """
    assert len(lint(src, "jit-host-read")) == 2


def test_jit_host_read_sees_functools_partial_and_wrapping():
    src = """
    import functools, jax, time

    @functools.partial(jax.jit, donate_argnums=(0,))
    def a(x):
        return x + time.monotonic()

    def b(x):
        return x + time.perf_counter()

    b = jax.jit(b)
    """
    assert len(lint(src, "jit-host-read")) == 2


def test_jit_host_read_clean_outside_jit():
    src = """
    import time

    def host_loop():
        return time.time()
    """
    assert lint(src, "jit-host-read") == []


# -- astlint: lock-blocking-call ---------------------------------------------


def test_lock_blocking_call_flags_sleep_under_lock():
    src = """
    import time

    def f(self):
        with self._lock:
            time.sleep(1.0)
    """
    assert len(lint(src, "lock-blocking-call")) == 1


def test_lock_blocking_call_flags_device_get_and_join():
    src = """
    import jax

    def f(self, t):
        with self._handles_lock:
            x = jax.device_get(t)
            self._thread.join()
        return x
    """
    assert len(lint(src, "lock-blocking-call")) == 2


def test_lock_blocking_call_ignores_nested_defs_and_no_lock():
    src = """
    import time

    def f(self):
        with self._lock:
            def later():
                time.sleep(1.0)   # runs after the lock is gone
            self.cb = later
        time.sleep(0.1)           # not under a lock
    """
    assert lint(src, "lock-blocking-call") == []


# -- astlint: bare-except ----------------------------------------------------


def test_bare_except_flags_bare_and_swallowed_base_exception():
    src = """
    def f():
        try:
            g()
        except:
            pass

    def h():
        try:
            g()
        except BaseException:
            log()
    """
    assert len(lint(src, "bare-except")) == 2


def test_bare_except_allows_reraise_and_narrow():
    src = """
    def f():
        try:
            g()
        except BaseException:
            cleanup()
            raise

    def h():
        try:
            g()
        except Exception:
            pass
    """
    assert lint(src, "bare-except") == []


# -- astlint: mutable-default ------------------------------------------------


def test_mutable_default_flags_literals_and_constructors():
    src = """
    import collections

    def f(a=[], b={}, c=set(), d=collections.defaultdict(list)):
        return a, b, c, d
    """
    assert len(lint(src, "mutable-default")) == 4


def test_mutable_default_allows_none_and_tuples():
    src = """
    def f(a=None, b=(), c="x", d=frozenset()):
        return a, b, c, d
    """
    assert lint(src, "mutable-default") == []


# -- astlint: fault-point ----------------------------------------------------


def test_fault_point_flags_unknown_name():
    src = """
    def f(self):
        self._faults.maybe_raise("decode_dispach")  # typo'd point
    """
    assert len(lint(src, "fault-point")) == 1


def test_fault_point_allows_registered_names():
    src = """
    def f(self, injector):
        self._faults.maybe_raise("decode_dispatch")
        if injector.should_fire("kube_http_5xx"):
            return
        injector.delay_s("slow_host_callback")
    """
    assert lint(src, "fault-point") == []


def test_fault_point_hinted_receivers_only():
    src = """
    def f(fault, parser):
        fault.arm("bogus_point")      # fault-ish receiver: checked
        parser.arm("not_a_fault")     # unrelated .arm(): ignored
    """
    assert len(lint(src, "fault-point")) == 1


# -- astlint: raw-lock -------------------------------------------------------


def test_raw_lock_flags_threading_lock_and_rlock():
    src = """
    import threading

    class S:
        def __init__(self):
            self._lock = threading.Lock()
            self._reentrant = threading.RLock()
    """
    assert len(lint(src, "raw-lock")) == 2


def test_raw_lock_flags_from_imports_and_aliases():
    src = """
    from threading import Lock, RLock as RL

    a = Lock()
    b = RL()
    """
    assert len(lint(src, "raw-lock")) == 2


def test_raw_lock_clean_for_make_lock_and_other_primitives():
    src = """
    import threading

    from k8s_llm_monitor_tpu.devtools.lockcheck import make_lock

    class S:
        def __init__(self):
            self._lock = make_lock("s")
            self._stop = threading.Event()
            self._cv = threading.Condition(self._lock)
    """
    assert lint(src, "raw-lock") == []


def test_raw_lock_exempts_the_lockcheck_factory_itself():
    src = textwrap.dedent("""
    import threading

    def make_lock(name):
        return threading.Lock()
    """)
    findings = astlint.lint_source(src, path="devtools/lockcheck.py")
    assert [f for f in findings if f.rule == "raw-lock"] == []
    findings = astlint.lint_source(src, path="somewhere/else.py")
    assert len([f for f in findings if f.rule == "raw-lock"]) == 1


def test_raw_lock_line_suppression():
    src = """
    import threading

    _probe = threading.Lock()  # graftcheck: disable=raw-lock -- boot probe
    """
    assert lint(src, "raw-lock") == []


# -- astlint: unconstrained-model-parse --------------------------------------


def test_unconstrained_parse_flags_backend_classes():
    src = """
    import json

    class MyBackend:
        def generate(self, prompt):
            raw = self._call(prompt)
            return json.loads(raw)
    """
    assert len(lint(src, "unconstrained-model-parse")) == 1


def test_unconstrained_parse_flags_model_output_markers():
    src = """
    from json import loads

    def handle(answer_text):
        verdict = loads(answer_text)
        return verdict
    """
    assert len(lint(src, "unconstrained-model-parse")) == 1


def test_unconstrained_parse_ignores_request_bodies_and_non_llm():
    src = """
    import json

    class KubeRestBackend:  # no generate(): not an LLM adapter
        def list_pods(self, raw):
            return json.loads(raw)

    def _read_json(handler):
        raw = handler.rfile.read(10)
        return json.loads(raw)
    """
    assert lint(src, "unconstrained-model-parse") == []


def test_unconstrained_parse_exempts_grammar_module():
    src = textwrap.dedent("""
    import json

    def parse_verdict(answer):
        return json.loads(answer)
    """)
    findings = astlint.lint_source(src, path="diagnosis/grammar.py")
    assert [f for f in findings
            if f.rule == "unconstrained-model-parse"] == []
    findings = astlint.lint_source(src, path="monitor/analysis.py")
    assert len([f for f in findings
                if f.rule == "unconstrained-model-parse"]) == 1


def test_unconstrained_parse_line_suppression():
    src = """
    import json

    class CompatBackend:
        def generate(self, prompt):
            data = json.loads(self._post(prompt))  # graftcheck: disable=unconstrained-model-parse -- envelope
            return data["choices"][0]
    """
    assert lint(src, "unconstrained-model-parse") == []


def test_unconstrained_parse_sees_through_strip_chains():
    src = """
    import json

    def f(completion):
        return json.loads(completion.strip())
    """
    assert len(lint(src, "unconstrained-model-parse")) == 1


# -- astlint: tenant-namespace -----------------------------------------------


def test_tenant_namespace_flags_bare_prefix_cache_calls():
    src = """
    def admit(pc, prompt, blocks):
        shared, toks = pc.lookup(prompt)
        pc.register(prompt, blocks)
        digests = self.prefix_cache.digest_chain(prompt, 3)
    """
    assert len(lint(src, "tenant-namespace")) == 3


def test_tenant_namespace_flags_tier_put_and_blob_moves():
    src = """
    def spill(tier, digest, rows, owner, target, blob, prompt):
        tier.put(digest, rows)
        b = owner.fetch_prefix(prompt)
        target.install_prefix(b)
        e = owner.export_prefix(prompt)
    """
    assert len(lint(src, "tenant-namespace")) == 4


def test_tenant_namespace_clean_with_tenant_kwargs():
    src = """
    def admit(pc, tier, owner, target, prompt, blocks, digest, rows, blob):
        shared, toks = pc.lookup(prompt, tenant="a")
        pc.register(prompt, blocks, tenant="a")
        tier.put(digest, rows, tenant="a")
        b = owner.fetch_prefix(prompt, tenant="a")
        target.install_prefix(b, expected_tenant="a")
        e = owner.export_prefix(prompt, tenant="a")
        target.install_prefix(e, **kw)  # splat: assumed threaded
    """
    assert lint(src, "tenant-namespace") == []


def test_tenant_namespace_ignores_unrelated_receivers():
    src = """
    import atexit

    def other(tracer, registry, q):
        trace = tracer.lookup(q)           # not a prefix cache
        atexit.register(close)             # not a prefix cache
        registry.put("k", 1)               # not a KV tier
    """
    assert lint(src, "tenant-namespace") == []


def test_tenant_namespace_exempts_defining_modules():
    src = """
    def digest_chain(self, prompt):
        return self._cache.lookup(prompt)
    """
    import textwrap

    from k8s_llm_monitor_tpu.devtools.astlint import lint_source
    findings = lint_source(textwrap.dedent(src),
                           path="k8s_llm_monitor_tpu/serving/kv_cache.py")
    assert [f for f in findings if f.rule == "tenant-namespace"] == []


def test_tenant_namespace_live_repo_clean_without_suppressions():
    """The privacy invariant's second enforcement layer: every prefix-KV
    call site in the live tree threads the tenant, and none of them hides
    behind a suppression comment."""
    import pathlib

    root = pathlib.Path(astlint.__file__).resolve().parents[2]
    rule = astlint.TenantNamespaceRule()
    offenders = []
    for sub in ("k8s_llm_monitor_tpu", "tests"):
        for p in astlint.iter_py_files(root / sub):
            src = p.read_text(encoding="utf-8")
            per_line, per_file = astlint._suppressions(src)
            suppressed = per_file | set().union(*per_line.values(), set())
            assert rule.name not in suppressed, \
                f"{p}: {rule.name} suppression is not allowed"
            offenders += astlint.lint_source(src, str(p), rules=[rule])
    assert offenders == [], [f.human() for f in offenders]


# -- astlint: raw-kube-write -------------------------------------------------


def test_raw_kube_write_flags_mutation_verbs():
    src = """
    def handler(backend):
        backend.delete_pod("ns", "pod-1")
        backend.cordon_node("node-a")
        backend.rollout_restart("ns", "web")
        backend.scale_statefulset("ns", "db", 3)
        backend.list_pods("ns")  # read: clean
    """
    findings = lint(src, "raw-kube-write")
    assert len(findings) == 4
    assert all("sanctioned" in f.message or "guard" in f.message
               or "RemediationEngine" in f.message for f in findings)


def test_raw_kube_write_flags_raw_rest_writes():
    src = """
    def poke(self):
        self._request("/api/v1/pods/x", None, method="DELETE")
        self._request("/apis/apps/v1/d", None, method="PATCH", body=b"{}")
        self._request("/api/v1/pods", None)           # GET: clean
        self._request("/version", None, method="GET")  # read: clean
    """
    assert len(lint(src, "raw-kube-write")) == 2


def test_raw_kube_write_exempts_executors_backends_and_tests():
    src = textwrap.dedent("""
    def act(backend):
        backend.delete_pod("ns", "p")
    """)
    for path in ("k8s_llm_monitor_tpu/remediation/executor.py",
                 "k8s_llm_monitor_tpu/fleet/autoscaler.py",
                 "k8s_llm_monitor_tpu/monitor/kube_rest.py",
                 "k8s_llm_monitor_tpu/monitor/cluster.py",
                 "tests/test_remediation.py"):
        findings = astlint.lint_source(src, path=path)
        assert [f for f in findings if f.rule == "raw-kube-write"] == [], path
    findings = astlint.lint_source(src, path="monitor/server.py")
    assert [f for f in findings if f.rule == "raw-kube-write"]


def test_raw_kube_write_live_repo_clean_without_suppressions():
    """Satellite acceptance: every cluster mutation in the live tree flows
    through the sanctioned executors, and none hides behind a suppression
    comment."""
    import pathlib

    root = pathlib.Path(astlint.__file__).resolve().parents[2]
    rule = astlint.RawKubeWriteRule()
    offenders = []
    for sub in ("k8s_llm_monitor_tpu", "tests"):
        for p in astlint.iter_py_files(root / sub):
            src = p.read_text(encoding="utf-8")
            per_line, per_file = astlint._suppressions(src)
            suppressed = per_file | set().union(*per_line.values(), set())
            assert rule.name not in suppressed, \
                f"{p}: {rule.name} suppression is not allowed"
            offenders += astlint.lint_source(src, str(p), rules=[rule])
    assert offenders == [], [f.human() for f in offenders]


# -- astlint: suppressions + parse errors ------------------------------------


def test_line_suppression_silences_one_rule():
    src = """
    def f(a=[]):  # graftcheck: disable=mutable-default -- frozen at import
        return a
    """
    assert lint(src) == []


def test_file_suppression_silences_everything():
    src = """
    # graftcheck: disable-file=all
    def f(a=[]):
        try:
            return a
        except:
            pass
    """
    assert lint(src) == []


def test_suppression_of_other_rule_does_not_silence():
    src = """
    def f(a=[]):  # graftcheck: disable=bare-except
        return a
    """
    assert len(lint(src, "mutable-default")) == 1


def test_syntax_error_becomes_parse_error_finding():
    findings = lint("def f(:\n    pass\n")
    assert [f.rule for f in findings] == ["parse-error"]


# -- graftcheck CLI ----------------------------------------------------------


def test_cli_exit_codes(tmp_path, capsys):
    from k8s_llm_monitor_tpu.devtools import graftcheck

    bad = tmp_path / "bad.py"
    bad.write_text("def f(a=[]):\n    return a\n")
    good = tmp_path / "good.py"
    good.write_text("def f(a=None):\n    return a\n")

    assert graftcheck.main([str(good)]) == 0
    assert graftcheck.main([str(bad)]) == 1
    assert graftcheck.main([str(bad), "--json"]) == 1
    out = capsys.readouterr().out
    assert '"mutable-default"' in out
    assert graftcheck.main(["--list-rules"]) == 0


# -- traceguard: recompile guard ---------------------------------------------


@pytest.mark.slow  # builds a real engine (~15s); tier-1 is within ~40s of
# its timeout budget, so the trace gates run via `make lint-trace` + `make test`
@pytest.mark.parametrize("decode_path", ["gather", "fused", "mesh", "quant",
                                         "grammar_swap"])
def test_same_bucket_reinvocation_compiles_nothing(decode_path):
    """The acceptance gate: warm both prefill programs + the decode ladder,
    then rerun same-shaped requests with different content — the program
    caches must not grow and no backend compile may fire.  The "mesh" path
    runs the same gate on a GSPMD TP-8 engine over the forced 8-host-device
    mesh (sharded weights + head-sharded KV pages), proving zero recompiles
    and donated page-pool/token-state rebinding survive sharding.  The
    "quant" path runs it on the int8-KV engine, where the donation set also
    carries the per-page scale leaves."""
    from k8s_llm_monitor_tpu.devtools import traceguard

    report = traceguard.check_path(decode_path)
    assert report.warm_compiles > 0          # warm-up really compiled
    assert report.repeat_compiles == 0, report.as_dict()
    assert not any(report.forbidden.values()), report.forbidden
    assert report.donated_pages_rebound and report.donated_tokens_rebound
    assert report.donated_scales_rebound
    if decode_path == "quant":
        assert report.kv_quant == "int8"
    assert report.ok


@pytest.mark.slow  # builds a real engine; see note above
def test_bucket_miss_is_counted():
    """Control for the zero above: a prompt that lands in the NEXT prefill
    bucket must register as new compilation — proving the counter can see
    compiles at all, so its zero on the repeat pass means something."""
    from k8s_llm_monitor_tpu.devtools import traceguard

    engine = traceguard.build_engine("gather")
    warm_c, _ = traceguard.count_new_compiles(
        engine, lambda: traceguard._drive(engine, 12, greedy=True, tag=1))
    assert warm_c > 0
    miss_c, _ = traceguard.count_new_compiles(
        engine, lambda: traceguard._drive(engine, 20, greedy=True, tag=2))
    assert miss_c > 0, "bucket-32 prefill should have compiled a new program"


def test_forbidden_ops_detects_host_callbacks():
    import jax
    import jax.numpy as jnp
    import numpy as np

    from k8s_llm_monitor_tpu.devtools import traceguard

    def leaky(x):
        return jax.pure_callback(
            np.sin, jax.ShapeDtypeStruct(x.shape, x.dtype), x)

    jaxpr = jax.make_jaxpr(jax.jit(leaky))(jnp.ones((4,), jnp.float32))
    hits = traceguard.forbidden_ops(jaxpr)
    assert any("pure_callback" in h for h in hits)

    jaxpr_clean = jax.make_jaxpr(jax.jit(lambda x: x * 2))(
        jnp.ones((4,), jnp.float32))
    assert traceguard.forbidden_ops(jaxpr_clean) == []


# -- lockcheck ---------------------------------------------------------------


@pytest.fixture
def armed_lockcheck(monkeypatch):
    """Enable instrumentation and hand the test a private registry so the
    session-level gate (conftest.pytest_sessionfinish) never sees the
    violations these tests provoke on purpose."""
    monkeypatch.setenv(lockcheck.ENV_FLAG, "1")
    reg = lockcheck.Registry()

    def make(name, reentrant=False):
        return lockcheck.InstrumentedLock(name, reentrant=reentrant, reg=reg)

    yield make, reg


def test_lock_order_cycle_detected(armed_lockcheck):
    make, reg = armed_lockcheck
    a, b = make("A"), make("B")
    with a:
        with b:
            pass
    with b:
        with a:     # opposite order: the A->B + B->A edges close a cycle
            pass
    assert reg.cycles() == [["A", "B"]]
    assert not reg.report()["ok"]
    with pytest.raises(AssertionError, match="cycle"):
        reg.assert_clean()


def test_consistent_order_is_clean(armed_lockcheck):
    make, reg = armed_lockcheck
    a, b = make("A"), make("B")
    for _ in range(3):
        with a:
            with b:
                pass
    assert reg.cycles() == []
    assert reg.report()["ok"]


def test_long_hold_flagged(armed_lockcheck, monkeypatch):
    make, reg = armed_lockcheck
    monkeypatch.setenv(lockcheck.ENV_HOLD_MS, "1")
    lk = make("slowpoke")
    with lk:
        time.sleep(0.01)
    assert reg.long_holds and reg.long_holds[0].lock == "slowpoke"
    # long holds are advisory: they do not flip ok
    assert reg.report()["ok"]


def test_rlock_reentry_records_no_self_edge(armed_lockcheck):
    make, reg = armed_lockcheck
    lk = make("R", reentrant=True)
    with lk:
        with lk:
            pass
    assert reg.cycles() == []
    assert all(a != b for (a, b) in reg.edges)


def test_release_by_non_owner_raises(armed_lockcheck):
    make, _ = armed_lockcheck
    lk = make("owned")
    lk.acquire()
    err: list[BaseException] = []

    def rogue():
        try:
            lk.release()
        except RuntimeError as e:
            err.append(e)

    t = threading.Thread(target=rogue)
    t.start()
    t.join()
    lk.release()
    assert err and "non-owner" in str(err[0])


def test_guarded_by_catches_unlocked_write(monkeypatch):
    monkeypatch.setenv(lockcheck.ENV_FLAG, "1")
    reg = lockcheck.Registry()

    @lockcheck.guarded_by("_lock", "count")
    class Box:
        def __init__(self):
            self.count = 0  # pre-lock: construction, exempt
            self._lock = lockcheck.InstrumentedLock("box", reg=reg)

        def good(self):
            with self._lock:
                self.count += 1

        def bad(self):
            self.count += 1

    # guarded_by records into the global registry; point it at ours.
    monkeypatch.setattr(lockcheck, "_registry", reg)
    box = Box()
    box.good()
    assert reg.report()["ok"]
    box.bad()
    writes = reg.report()["unguarded_writes"]
    assert writes and writes[0]["attr"] == "count" and writes[0]["cls"] == "Box"


def test_disabled_mode_is_plain_locks(monkeypatch):
    monkeypatch.delenv(lockcheck.ENV_FLAG, raising=False)
    assert not lockcheck.enabled()
    lk = lockcheck.make_lock("plain")
    assert not isinstance(lk, lockcheck.InstrumentedLock)

    @lockcheck.guarded_by("_lock", "x")
    class C:
        pass

    # decorator is an identity when disabled: no __setattr__ wrapper
    assert "__setattr__" not in C.__dict__
