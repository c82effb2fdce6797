"""The Analysis Engine — LLM-backed cluster diagnosis on TPU.

This is the component the reference only sketched: its entire LLM
integration is config keys (``/root/reference/internal/config/config.go:
141-145,174-180``), the ``/api/v1/query`` endpoint is documented
(``README.md:89-95``) but never registered, and the analysis-type enum
(``pkg/models/models.go:87``: pod_communication / anomaly_detection /
root_cause) has no implementation behind it. Here all three types are
implemented, backed by the in-tree JAX/Pallas serving stack
(``k8s_llm_monitor_tpu.serving``) instead of a remote OpenAI call.

Pieces:
- ``LLMBackend`` seam with three implementations: ``LocalEngineBackend``
  (TPU inference via ``InferenceEngine``), ``OpenAICompatBackend`` (the
  reference's remote path, kept for parity), and ``TemplateBackend``
  (deterministic evidence summarizer — dev mode / tests without a model).
- ``EvidenceCollector``: assembles bounded cluster evidence (snapshot +
  events + logs, capped by ``analysis.max_context_events`` like ref
  config.go:94) into prompt sections.
- ``AnalysisEngine``: the three analyzers + free-form ``query``.
"""

from __future__ import annotations

import http.client
import json
import logging
import re
import time
import urllib.error
import urllib.request
import uuid
from typing import Any

from k8s_llm_monitor_tpu.monitor.client import Client
from k8s_llm_monitor_tpu.monitor.cluster import ClusterError
from k8s_llm_monitor_tpu.monitor.config import (
    AnalysisConfig,
    LifecycleConfig,
    LLMConfig,
)
from k8s_llm_monitor_tpu.diagnosis.grammar import (
    GrammarError,
    parse_verdict,
    render_verdict,
)
from k8s_llm_monitor_tpu.diagnosis.session import SessionManager
from k8s_llm_monitor_tpu.resilience.errors import OverloadedError
from k8s_llm_monitor_tpu.monitor.manager import Manager
from k8s_llm_monitor_tpu.monitor.models import (
    ANALYSIS_TYPES,
    AnalysisRequest,
    AnalysisResponse,
    to_jsonable,
    utcnow,
)
from k8s_llm_monitor_tpu.monitor.network import NetworkAnalyzer

logger = logging.getLogger("monitor.analysis")


# ---------------------------------------------------------------------------
# LLM backends
# ---------------------------------------------------------------------------


class LLMBackend:
    name = "base"

    def generate(
        self, prompt: str, max_tokens: int = 512, temperature: float = 0.1,
        slo_class: str = "standard", tenant: str = "",
    ) -> str:
        # ``slo_class`` and ``tenant`` are scheduling/accounting metadata
        # for backends with an admission layer (LocalEngineBackend);
        # remote/template backends accept and ignore them so callers can
        # tag unconditionally.  ``tenant=""`` means the default tenant.
        raise NotImplementedError

    def generate_stream(
        self, prompt: str, max_tokens: int = 512, temperature: float = 0.1,
        slo_class: str = "standard", tenant: str = "",
    ):
        """Yield text chunks.  Backends without true streaming yield the
        whole completion once (keeps the SSE route backend-agnostic)."""
        yield self.generate(prompt, max_tokens=max_tokens,
                            temperature=temperature, slo_class=slo_class,
                            tenant=tenant)

    def generate_constrained(self, prompt: str,
                             temperature: float = 0.0,
                             slo_class: str = "standard",
                             tenant: str = "") -> str:
        """Return Verdict JSON valid under ``diagnosis.grammar``'s schema.

        Default path for backends without token-level masking (remote
        endpoints can't apply per-step logit masks): generate free text and
        fold it into a canonical verdict via ``render_verdict``, so the
        contract — output always parses — holds even when the model
        rambles.  ``LocalEngineBackend`` overrides this with true on-device
        FSM-constrained decoding.
        """
        text = self.generate(prompt, max_tokens=512,
                             temperature=temperature,
                             slo_class=slo_class, tenant=tenant).strip()
        try:
            parse_verdict(text)
            return text
        except GrammarError:
            pass
        low = text.lower()
        if any(w in low for w in ("crash", "oom", "fail", "critical",
                                  "unreachable", "down")):
            severity = "critical"
        elif any(w in low for w in ("warn", "pressure", "restart",
                                    "degrad", "evict")):
            severity = "warning"
        else:
            severity = "info"
        return render_verdict(
            severity, "cluster", text,
            "see root_cause; re-run the diagnosis after remediation", 0.3)

    #: True only for backends that can decode under an arbitrary token FSM
    #: (``LocalEngineBackend`` with the byte tokenizer).  Callers check it
    #: before compiling a grammar nobody will use.
    supports_grammar = False

    def generate_with_grammar(self, prompt: str, fsm,
                              temperature: float = 0.0,
                              slo_class: str = "standard",
                              tenant: str = "") -> str:
        """Decode under a caller-supplied ``TokenFSM`` (the remediation
        plan grammar).  Backends without token-level masking return ""
        so callers fall back to their deterministic renderers — remote
        endpoints cannot apply per-step logit masks, and free text run
        through an arbitrary grammar would almost never parse."""
        return ""


class TemplateBackend(LLMBackend):
    """Deterministic diagnosis text from the prompt's evidence sections.

    Serves dev mode (no model weights) and keeps API tests fast; the output
    shape matches what the LLM path produces (diagnosis + recommendation).
    """

    name = "template"

    def generate(
        self, prompt: str, max_tokens: int = 512, temperature: float = 0.1,
        slo_class: str = "standard", tenant: str = "",
    ) -> str:
        issues = [
            line.strip("- ").strip()
            for line in prompt.splitlines()
            if line.lstrip().startswith("- ") and "##" not in line
        ]
        if issues:
            listed = "; ".join(issues[:5])
            return (
                f"Diagnosis: {len(issues)} finding(s) in the collected evidence: "
                f"{listed}. Recommendation: address the findings above in order; "
                "re-run the analysis after each fix to confirm resolution."
            )
        return (
            "Diagnosis: no anomalies detected in the collected evidence. "
            "The cluster appears healthy; no action required."
        )

    def generate_constrained(self, prompt: str,
                             temperature: float = 0.0,
                             slo_class: str = "standard",
                             tenant: str = "") -> str:
        """Deterministic grammar-valid verdict from the evidence sections —
        same extraction as ``generate``, rendered through the canonical
        serializer so it parses under the verdict grammar by construction."""
        issues = [
            line.strip("- ").strip()
            for line in prompt.splitlines()
            if line.lstrip().startswith("- ") and "##" not in line
        ]
        if not issues:
            return render_verdict(
                "info", "cluster",
                "no anomalies detected in the collected evidence",
                "no action required", 0.9)
        low = " ".join(issues).lower()
        if any(w in low for w in ("crashloop", "crash", "oom", "failed",
                                  "notready", "unreachable")):
            severity = "critical"
        else:
            severity = "warning"
        pod = re.search(r'"pod": "([^"]+)"', prompt)
        component = pod.group(1) if pod else "cluster"
        return render_verdict(
            severity, component,
            f"{len(issues)} finding(s): {'; '.join(issues[:3])}",
            "address the findings in order; re-run the analysis after "
            "each fix", 0.6)


class LocalEngineBackend(LLMBackend):
    """In-process TPU inference through the continuous-batching engine.

    Thread-safe and genuinely concurrent: a background ``EngineService``
    thread owns the engine's step loop, and each generate() call submits a
    request and waits on its handle — so N concurrent HTTP requests share
    prefill batches and decode steps instead of serializing.
    """

    name = "tpu-local"

    # Generations that outlive this are failed (queue + decode worst case).
    GENERATION_TIMEOUT_S = 600.0

    def __init__(self, engine=None, tokenizer=None, *,
                 dev_weights: bool = False, engine_factory=None,
                 lifecycle: LifecycleConfig | None = None,
                 governor=None) -> None:
        """Two construction modes:

        * ``engine=`` (tests, ad-hoc wiring): the service wraps the given
          engine directly — a dead step loop is terminal, exactly the PR 2
          behavior.
        * ``engine_factory=`` (server boot via ``from_config``): an
          ``EngineSupervisor`` owns the service, journals admits when
          ``lifecycle.journal_dir`` is set, and rebuilds + replays on a
          dead/wedged step loop.
        """
        from k8s_llm_monitor_tpu.serving.service import EngineService

        self.tokenizer = tokenizer
        self.supervisor = None
        self._service = None
        # resilience.tenancy.TenantGovernor (or None): per-tenant admission
        # quotas on single-replica roles.  Owned here (above the supervisor)
        # so reservations survive engine rebuilds; the HTTP layer reads it
        # for /api/v1/stats and the tenant_* exporter families.
        self.governor = governor
        if engine_factory is not None:
            from k8s_llm_monitor_tpu.resilience.journal import RequestJournal
            from k8s_llm_monitor_tpu.resilience.retry import Backoff
            from k8s_llm_monitor_tpu.serving.supervisor import EngineSupervisor

            lc = lifecycle or LifecycleConfig()
            journal = None
            if lc.journal_dir:
                journal = RequestJournal(
                    lc.journal_dir,
                    segment_max_bytes=lc.journal_segment_mb << 20,
                    fsync=lc.journal_fsync)
            self.supervisor = EngineSupervisor(
                engine_factory,
                journal=journal,
                max_restarts=lc.max_restarts,
                heartbeat_timeout_s=lc.heartbeat_timeout_s,
                backoff=Backoff(base_s=lc.restart_backoff_s,
                                cap_s=max(lc.restart_backoff_s * 8, 5.0),
                                jitter=0.0),
                governor=governor)
        else:
            assert engine is not None, "engine or engine_factory required"
            self._service = EngineService(engine, governor=governor)
            if getattr(engine, "_grammar", None) is None:
                self._install_verdict_grammar(engine, tokenizer)
        # Decode-rate EMAs (ms/token) for the exporter's
        # constrained_decode_overhead_ms gauge; plain float stores, benign
        # under concurrent generate() threads.
        self._ema_ms_constrained: float | None = None
        self._ema_ms_free: float | None = None
        # Serializes generate_with_grammar()'s set-grammar/decode/restore
        # window against itself.  The diagnosis pipeline worker is the
        # only constrained caller in-process, so a swap never races an
        # in-flight constrained decode.
        from k8s_llm_monitor_tpu.devtools.lockcheck import make_lock

        self._grammar_swap_lock = make_lock("analysis.grammar_swap")
        if dev_weights:
            # Random-init weights + byte tokenizer produce byte soup; make
            # that loud in every API response's `model` field instead of
            # presenting it as a real diagnosis backend.
            self.name = "tpu-local-DEV-RANDOM-WEIGHTS"
            logger.warning(
                "TPU backend running with RANDOM-INIT weights (no "
                "llm.tpu.checkpoint configured) — answers are not "
                "meaningful; set llm.tpu.checkpoint for real diagnosis")

    @property
    def service(self):
        """The live EngineService — the supervisor's current one when
        supervised (it changes across rebuilds), else the pinned one."""
        if self.supervisor is not None:
            return self.supervisor.service
        return self._service

    @property
    def engine(self):
        return self.service.engine

    def _submit(self, prompt_ids, sampling, slo_class: str = "standard",
                tenant: str = ""):
        if self.supervisor is not None:
            return self.supervisor.submit(prompt_ids, sampling,
                                          slo_class=slo_class,
                                          tenant=tenant)
        return self.service.submit(prompt_ids, sampling,
                                   slo_class=slo_class, tenant=tenant)

    def brownout_level(self) -> int:
        """Current brownout rung (0=normal, 1=degraded, 2=draining) from
        the live service's controller; 0 when no service is up."""
        svc = self.service
        if svc is None or getattr(svc, "brownout", None) is None:
            return 0
        return svc.brownout.level()

    @staticmethod
    def _install_verdict_grammar(engine, tokenizer) -> bool:
        """Register the Verdict token-FSM on a fresh engine.

        Byte tokenizer only: the grammar's char→token lift (token =
        byte + 3) is exact for ``ByteTokenizer``; HF/BPE tokenizers would
        need a subword-aware compile, so constrained submits are refused
        for them (``generate_constrained`` falls back to the render path)
        instead of silently emitting garbage.
        """
        from k8s_llm_monitor_tpu.diagnosis.grammar import verdict_fsm
        from k8s_llm_monitor_tpu.utils.tokenizer import ByteTokenizer

        if not isinstance(tokenizer, ByteTokenizer):
            return False
        if engine.cfg.vocab_size < ByteTokenizer.vocab_size:
            return False
        try:
            engine.set_grammar(verdict_fsm(eos_id=tokenizer.eos_id))
        except ValueError as exc:
            logger.warning("verdict grammar not installed: %s", exc)
            return False
        return True

    def _note_decode_ms(self, constrained: bool, n_tokens: int,
                        latency_s: float, ttft_s: float) -> None:
        if n_tokens <= 1:
            return
        ms = max(0.0, latency_s - ttft_s) * 1000.0 / (n_tokens - 1)
        attr = "_ema_ms_constrained" if constrained else "_ema_ms_free"
        prev = getattr(self, attr)
        setattr(self, attr, ms if prev is None else 0.8 * prev + 0.2 * ms)

    @property
    def constrained_decode_overhead_ms(self) -> float:
        """Per-token decode cost of FSM masking: EMA(constrained) −
        EMA(free), clamped at 0; 0.0 until both classes have samples."""
        if self._ema_ms_constrained is None or self._ema_ms_free is None:
            return 0.0
        return max(0.0, self._ema_ms_constrained - self._ema_ms_free)

    @classmethod
    def from_config(cls, tpu_cfg, lifecycle=None,
                    tenancy=None) -> "LocalEngineBackend":
        """Build from ``LLMConfig.tpu``: checkpoint weights or random-init
        dev weights for the named preset.  ``tenancy`` (TenancyConfig)
        arms the per-tenant admission governor and the KV fairness cap."""
        import jax

        # One normalization for the preflight AND the engine build below:
        # 'int8'/'w8a8' are real modes, anything else is bf16.
        qmode = getattr(tpu_cfg, "quantize", "")
        quantize = qmode in ("int8", "w8a8")

        # Fit preflight (cmd/preflight): shapes-only, so it warns about an
        # over-budget config BEFORE the multi-GiB weight build OOMs the
        # chip mid-load.  Warn-only — boot proceeds regardless.
        try:
            import contextlib
            import io

            from k8s_llm_monitor_tpu.cmd.preflight import check as _preflight

            # --quantize is always passed (preflight's own default is
            # w8a8, which would size int8 weights for a bf16 config —
            # exactly the over-budget case this warning exists for).
            # The workload shape mirrors what the engine can actually
            # hold per sequence (EngineConfig default max_blocks_per_seq
            # 64 x block 16 = 1024 tokens; longer requests are truncated
            # at submit), so FAIL here means "cannot serve even one
            # engine-shaped request".
            argv = ["--kv-blocks", str(tpu_cfg.kv_blocks),
                    "--quantize", qmode if quantize else "none",
                    "--prompt-len", "768", "--max-tokens", "256"]
            if tpu_cfg.checkpoint:
                argv += ["--checkpoint", tpu_cfg.checkpoint]
            else:
                argv += ["--model", tpu_cfg.model]
            if tpu_cfg.mesh_shape:
                argv += ["--mesh", tpu_cfg.mesh_shape]
            buf = io.StringIO()
            with contextlib.redirect_stdout(buf), \
                    contextlib.redirect_stderr(buf):
                rc, fails, warns = _preflight(argv)
            if warns:
                # Context even when a FAIL follows (e.g. "fit checks
                # skipped" qualifies what the verdict did NOT cover).
                logger.info("TPU config preflight warnings: %s",
                            "; ".join(warns))
            if rc != 0:
                logger.warning(
                    "TPU config preflight FAILED (boot continues): %s — "
                    "run `python -m k8s_llm_monitor_tpu.cmd.preflight` "
                    "for the full report", "; ".join(fails) or "see report")
        # SystemExit included: argparse exits on bad flag values, and
        # preflight must never block boot.  The debug line keeps a broken
        # preflight observable instead of silently disabling the check.
        except (Exception, SystemExit) as exc:  # noqa: BLE001
            logger.debug("TPU config preflight skipped: %s", exc,
                         exc_info=True)

        from k8s_llm_monitor_tpu.models import llama
        from k8s_llm_monitor_tpu.models.config import PRESETS
        from k8s_llm_monitor_tpu.serving.engine import EngineConfig, InferenceEngine
        from k8s_llm_monitor_tpu.utils.tokenizer import load_tokenizer

        mesh = None
        if tpu_cfg.mesh_shape:
            from k8s_llm_monitor_tpu.parallel.mesh import MeshConfig, create_mesh

            data, seq, model = (int(x) for x in tpu_cfg.mesh_shape.split(","))
            mesh = create_mesh(MeshConfig(data=data, seq=seq, model=model))

        dev_weights = not tpu_cfg.checkpoint
        if tpu_cfg.checkpoint:
            from k8s_llm_monitor_tpu.utils.checkpoint import load_hf_checkpoint

            # int8 streams each tensor through host-side quantization — the
            # only way 8B-class checkpoints fit a 16 GB chip (utils/quantize).
            cfg, params = load_hf_checkpoint(tpu_cfg.checkpoint,
                                             quantize=quantize)
            tokenizer = load_tokenizer(tpu_cfg.checkpoint)
        else:
            cfg = PRESETS[tpu_cfg.model]
            if quantize:
                from k8s_llm_monitor_tpu.utils.quantize import (
                    init_params_quantized as init,
                )
            else:
                init = llama.init_params
            key = jax.random.PRNGKey(0)
            if mesh is None:
                params = init(key, cfg)
            else:
                # Born sharded: the init runs as one program whose outputs
                # carry the engine's own weight shardings, so no device
                # ever holds the whole model (an eager init would park it
                # on the default device, where the supervisor's rebuild
                # closure below would keep it alive).  Same key, same
                # values as the one-chip init.
                from k8s_llm_monitor_tpu.parallel.sharding import (
                    param_named_shardings,
                )

                shardings = param_named_shardings(
                    jax.eval_shape(lambda k: init(k, cfg), key), mesh)
                params = jax.jit(lambda k: init(k, cfg),
                                 out_shardings=shardings)(key)
            tokenizer = load_tokenizer(None)

        if qmode == "w8a8":
            # s8 x s8 prefill on the MXU int8 path (measured ~1.4x prefill rate
            # and the only mode meeting every short-leg SLO);
            # see utils/quantize.py.
            import dataclasses as _dc

            cfg = _dc.replace(cfg, act_quant=True)

        # Factory, not a single engine: the supervisor rebuilds through
        # this closure after a step-loop death, reusing the (expensive)
        # params/tokenizer while the KV allocator and slot table start
        # from baseline by construction.  Weights are jax.Arrays the dead
        # engine never mutates, so reuse is safe.
        max_kv_share = (float(tenancy.max_kv_share)
                        if tenancy is not None else 1.0)

        def engine_factory() -> InferenceEngine:
            engine = InferenceEngine(
                cfg,
                params,
                EngineConfig(max_slots=tpu_cfg.max_batch,
                             num_blocks=tpu_cfg.kv_blocks,
                             spec_k=tpu_cfg.spec_k,
                             spec_min_accept=tpu_cfg.spec_min_accept,
                             kv_max_tenant_share=max_kv_share),
                tokenizer=tokenizer,
                mesh=mesh,
            )
            # Inside the factory, not after it: supervisor rebuilds go
            # through this closure, and a rebuilt engine without the
            # grammar would reject every constrained submit.
            cls._install_verdict_grammar(engine, tokenizer)
            return engine

        governor = None
        if tenancy is not None and tenancy.enabled:
            from k8s_llm_monitor_tpu.resilience.tenancy import TenantGovernor

            governor = TenantGovernor(
                requests_per_s=tenancy.requests_per_s,
                request_burst=tenancy.request_burst,
                tokens_per_s=tenancy.tokens_per_s,
                token_burst=tenancy.token_burst,
                enforce=tenancy.enforce,
                max_tenants=tenancy.max_tenants)

        backend = cls(tokenizer=tokenizer, dev_weights=dev_weights,
                      engine_factory=engine_factory, lifecycle=lifecycle,
                      governor=governor)
        backend._first_compile()
        return backend

    def _first_compile(self) -> None:
        """Start-up gate: one short greedy generation through the normal
        submit path, so the smallest prefill program and the decode program
        compile BEFORE the server takes traffic.  A kernel the chip's
        compiler refuses then stops the boot with the compiler's message,
        instead of a server that starts and fails every request."""
        from k8s_llm_monitor_tpu.serving.engine import SamplingParams

        # Four tokens: a refused decode program requeues its lane through
        # prefill (one more token per requeue, engine max_requeues=2), so
        # a shorter request could finish without ever decoding.
        handle = self._submit(
            self.tokenizer.encode("ok"),
            SamplingParams(max_tokens=4, temperature=0.0),
            slo_class="interactive")
        res = handle.result(timeout=self.GENERATION_TIMEOUT_S)
        if res.finish_reason == "error":
            if self.supervisor is not None:
                self.supervisor.close()
            raise RuntimeError(
                f"TPU backend failed its first compile: {res.error}")

    def generate(
        self, prompt: str, max_tokens: int = 512, temperature: float = 0.1,
        slo_class: str = "standard", tenant: str = "",
    ) -> str:
        from k8s_llm_monitor_tpu.serving.engine import SamplingParams

        handle = self._submit(
            self.tokenizer.encode(prompt),
            SamplingParams(max_tokens=max_tokens, temperature=temperature),
            slo_class=slo_class, tenant=tenant,
        )
        res = handle.result(timeout=self.GENERATION_TIMEOUT_S)
        if res.finish_reason == "error":
            raise RuntimeError(f"generation failed: {res.error}")
        self._note_decode_ms(False, len(res.token_ids),
                             res.latency_s, res.ttft_s)
        return self.tokenizer.decode(res.token_ids)

    def generate_constrained(self, prompt: str,
                             temperature: float = 0.0,
                             slo_class: str = "standard",
                             tenant: str = "") -> str:
        """True grammar-constrained decoding: the verdict FSM's per-step
        logit masks run inside the engine's on-device sampler, so the raw
        token stream IS the verdict JSON — no post-hoc repair.  Falls back
        to the base render path when no grammar is registered (HF
        tokenizer, undersized vocab)."""
        from k8s_llm_monitor_tpu.serving.engine import SamplingParams

        try:
            has_grammar = getattr(self.engine, "_grammar", None) is not None
        except Exception:  # noqa: BLE001 — supervisor mid-rebuild
            has_grammar = False
        if not has_grammar:
            return super().generate_constrained(prompt,
                                                temperature=temperature,
                                                slo_class=slo_class,
                                                tenant=tenant)
        handle = self._submit(
            self.tokenizer.encode(prompt),
            # max_tokens=1 is a floor: submit() raises it to the grammar's
            # max accepting path so the verdict can always close.
            SamplingParams(max_tokens=1, temperature=temperature,
                           constrained=True),
            slo_class=slo_class, tenant=tenant,
        )
        res = handle.result(timeout=self.GENERATION_TIMEOUT_S)
        if res.finish_reason == "error":
            raise RuntimeError(f"constrained generation failed: {res.error}")
        self._note_decode_ms(True, len(res.token_ids),
                             res.latency_s, res.ttft_s)
        return self.tokenizer.decode(res.token_ids).strip()

    @property
    def supports_grammar(self) -> bool:
        """Grammar swaps need an engine that already passed the verdict
        -grammar install gates (byte tokenizer, vocab ≥ 259)."""
        try:
            return getattr(self.engine, "_grammar", None) is not None
        except Exception:  # noqa: BLE001 — supervisor mid-rebuild
            return False

    def generate_with_grammar(self, prompt: str, fsm,
                              temperature: float = 0.0,
                              slo_class: str = "standard",
                              tenant: str = "") -> str:
        """Constrained decode under a caller-supplied FSM (the remediation
        plan grammar): save the installed verdict grammar, swap in the
        plan FSM, decode, restore.  Plan FSMs are padded to one fixed
        table shape (``plans.PLAN_STATE_CAP``), and the engine treats the
        table as a runtime argument — so the swap is recompile-free after
        the first plan decode warms its shape (traceguard ``grammar_swap``
        path proves it)."""
        from k8s_llm_monitor_tpu.serving.engine import SamplingParams

        with self._grammar_swap_lock:
            try:
                engine = self.engine
            except Exception:  # noqa: BLE001 — supervisor mid-rebuild
                return ""
            saved = getattr(engine, "_grammar", None)
            if saved is None:
                return ""  # verdict install already refused this engine
            try:
                engine.set_grammar(fsm)
            except ValueError as exc:
                logger.warning("plan grammar rejected by engine: %s", exc)
                return ""
            try:
                handle = self._submit(
                    self.tokenizer.encode(prompt),
                    # max_tokens=1 is a floor: submit() raises it to the
                    # plan grammar's max accepting path.
                    SamplingParams(max_tokens=1, temperature=temperature,
                                   constrained=True),
                    slo_class=slo_class, tenant=tenant,
                )
                res = handle.result(timeout=self.GENERATION_TIMEOUT_S)
            finally:
                engine.set_grammar(saved)
        if res.finish_reason == "error":
            raise RuntimeError(f"plan generation failed: {res.error}")
        self._note_decode_ms(True, len(res.token_ids),
                             res.latency_s, res.ttft_s)
        return self.tokenizer.decode(res.token_ids).strip()

    def generate_stream(
        self, prompt: str, max_tokens: int = 512, temperature: float = 0.1,
        slo_class: str = "standard", tenant: str = "",
    ):
        """Yield decoded text increments as tokens come off the device.

        Decodes cumulatively and emits suffixes so multi-byte/multi-token
        graphemes never split mid-character.
        """
        from k8s_llm_monitor_tpu.serving.engine import SamplingParams

        handle = self._submit(
            self.tokenizer.encode(prompt),
            SamplingParams(max_tokens=max_tokens, temperature=temperature),
            slo_class=slo_class, tenant=tenant,
        )
        toks: list[int] = []
        emitted = ""
        try:
            for tok in handle.stream(timeout=self.GENERATION_TIMEOUT_S):
                toks.append(tok)
                text = self.tokenizer.decode(toks)
                # Hold back a trailing replacement char: it usually means a
                # multi-byte grapheme is split mid-token and the next token
                # will rewrite it.
                stable = text[:-1] if text.endswith("�") else text
                if len(stable) > len(emitted) and stable.startswith(emitted):
                    yield stable[len(emitted):]
                    emitted = stable
        except GeneratorExit:
            # Consumer abandoned the stream (client disconnect): stop the
            # engine from burning decode steps on a dead request.
            handle.cancel()
            raise
        # Final flush: emit whatever the full decode has beyond (or instead
        # of) what was streamed, so held-back or rewritten tails are never
        # silently dropped.
        if toks:
            text = self.tokenizer.decode(toks)
            if text != emitted:
                common = 0
                limit = min(len(text), len(emitted))
                while common < limit and text[common] == emitted[common]:
                    common += 1
                if common < len(text):
                    yield text[common:]
        res = handle.result(timeout=1.0)
        if res.finish_reason == "error":
            raise RuntimeError(f"generation failed: {res.error}")


class OpenAICompatBackend(LLMBackend):
    """Remote OpenAI-compatible chat endpoint (the reference's configured
    path, config.go:141-145). Kept for deployments that want it; the
    north-star path is LocalEngineBackend.

    Transient failures (HTTP 429/5xx, connection resets, timeouts) are
    retried with exponential backoff so one 502 doesn't fail a diagnosis
    outright; non-transient HTTP errors surface the response body in the
    raised error for debuggability.
    """

    name = "openai"
    max_retries = 3
    backoff_s = 0.5
    _RETRY_STATUS = {429, 500, 502, 503, 504}

    def __init__(self, cfg: LLMConfig) -> None:
        self.cfg = cfg
        if not cfg.base_url:
            raise ValueError("llm.base_url required for the openai provider")

    def _post(self, body: bytes):
        req = urllib.request.Request(
            self.cfg.base_url.rstrip("/") + "/chat/completions",
            data=body,
            headers={
                "Content-Type": "application/json",
                "Authorization": f"Bearer {self.cfg.api_key}",
            },
        )
        return urllib.request.urlopen(req, timeout=self.cfg.timeout)

    def generate(
        self, prompt: str, max_tokens: int = 512, temperature: float = 0.1,
        slo_class: str = "standard", tenant: str = "",
    ) -> str:
        # slo_class/tenant ignored: the remote endpoint has its own
        # admission and accounting.
        body = json.dumps(
            {
                "model": self.cfg.model,
                "messages": [{"role": "user", "content": prompt}],
                "max_tokens": max_tokens,
                "temperature": temperature,
            }
        ).encode()
        last_err: Exception | None = None
        for attempt in range(self.max_retries + 1):
            if attempt:
                time.sleep(self.backoff_s * (2 ** (attempt - 1)))
            try:
                with self._post(body) as resp:
                    raw = resp.read()
                try:
                    # The envelope (choices/usage) is protocol JSON, not
                    # model text; the text itself goes through the
                    # generate_constrained -> parse_verdict funnel.
                    data = json.loads(raw)  # graftcheck: disable=unconstrained-model-parse -- HTTP envelope
                except ValueError as exc:
                    # 200 + non-JSON body (an LB/proxy error page): as
                    # transient as a 502, and must not surface as a
                    # caller-side validation error.
                    raise urllib.error.URLError(
                        f"non-JSON response from LLM endpoint: "
                        f"{raw[:200]!r} ({exc})") from exc
                return data["choices"][0]["message"]["content"]
            except urllib.error.HTTPError as exc:
                detail = ""
                try:
                    detail = exc.read().decode(errors="replace")[:500]
                except Exception:  # noqa: BLE001
                    pass
                last_err = RuntimeError(
                    f"LLM endpoint returned {exc.code}: {detail or exc.reason}")
                if exc.code not in self._RETRY_STATUS:
                    raise last_err from exc
                logger.warning("LLM request failed (%s), attempt %d/%d",
                               exc.code, attempt + 1, self.max_retries + 1)
            except (urllib.error.URLError, TimeoutError, OSError,
                    http.client.HTTPException) as exc:
                # HTTPException covers mid-body failures (IncompleteRead,
                # RemoteDisconnected) that are not OSError subclasses.
                last_err = RuntimeError(f"LLM endpoint unreachable: {exc}")
                logger.warning("LLM request failed (%s), attempt %d/%d",
                               exc, attempt + 1, self.max_retries + 1)
        raise last_err  # type: ignore[misc]


def build_backend(cfg: LLMConfig,
                  lifecycle: LifecycleConfig | None = None,
                  tenancy=None) -> LLMBackend:
    if cfg.provider == "tpu":
        # No downgrade: a TPU backend that cannot be built is a start-up
        # error.  ``--llm template`` is the explicit way to run without a
        # model; answering from a template while the operator asked for
        # the chip hides exactly the failure they need to see.
        return LocalEngineBackend.from_config(cfg.tpu, lifecycle=lifecycle,
                                              tenancy=tenancy)
    if cfg.provider == "openai":
        try:
            return OpenAICompatBackend(cfg)
        except ValueError as exc:
            logger.warning("openai backend misconfigured (%s); using template", exc)
            return TemplateBackend()
    return TemplateBackend()


# ---------------------------------------------------------------------------
# evidence assembly
# ---------------------------------------------------------------------------


class EvidenceCollector:
    """Bounded cluster evidence → prompt sections.

    The bound is ``analysis.max_context_events`` (ref config.go:94) applied
    to the event stream; metric sections are already summaries.
    """

    def __init__(
        self,
        client: Client | None,
        manager: Manager | None,
        cfg: AnalysisConfig | None = None,
    ) -> None:
        self.client = client
        self.manager = manager
        self.cfg = cfg or AnalysisConfig()

    def collect(
        self,
        namespace: str | None = None,
        pod: str | None = None,
        include_logs: bool = False,
    ) -> dict[str, Any]:
        """Structured evidence dict; ``format_prompt`` renders it."""
        ev: dict[str, Any] = {"collected_at": utcnow().isoformat()}
        if self.manager is not None:
            snap = self.manager.get_latest_snapshot()
            if snap.cluster_metrics is not None:
                ev["cluster"] = to_jsonable(snap.cluster_metrics)
            ev["unhealthy_nodes"] = [
                {"node": n.node_name, "conditions": n.conditions,
                 "cpu_pct": round(n.cpu_usage_rate, 1),
                 "mem_pct": round(n.memory_usage_rate, 1)}
                for n in snap.node_metrics.values()
                if not n.healthy or n.is_under_pressure()
            ]
            ev["problem_pods"] = [
                {"pod": key, "phase": p.phase, "ready": p.ready,
                 "restarts": p.restarts,
                 "over_limit": p.is_over_limit()}
                for key, p in snap.pod_metrics.items()
                if p.phase != "Running" or not p.ready or p.is_over_limit()
                or p.restarts > 3
            ]
            ev["network_issues"] = [
                {"pair": f"{m.source_pod} -> {m.target_pod}",
                 "connected": m.connected, "rtt_ms": round(m.rtt_ms, 2),
                 "quality": m.quality(), "error": m.error}
                for m in snap.network_metrics
                if not m.connected or m.quality() in ("fair", "poor")
            ]
            uavs = self.manager.get_uav_metrics()
            low = []
            for node, entry in uavs.items():
                state = entry.get("state") or {}
                batt = state.get("battery", {}) if isinstance(state, dict) else {}
                pct = batt.get("remaining_percent")
                if pct is not None and pct < 20.0:
                    low.append({"node": node, "battery_pct": pct})
            if low:
                ev["low_battery_uavs"] = low
        if self.client is not None:
            events = []
            try:
                for ns in self.client.namespaces():
                    for e in self.client.get_events(
                        ns, limit=self.cfg.max_context_events
                    ):
                        events.append(
                            {"ns": ns, "type": e.type, "reason": e.reason,
                             "message": e.message, "count": e.count}
                        )
            except ClusterError as exc:
                logger.warning("event collection failed: %s", exc)
            warnings = [e for e in events if e["type"] == "Warning"]
            ev["recent_warning_events"] = warnings[-self.cfg.max_context_events :]
            if pod and namespace and include_logs:
                try:
                    ev["pod_logs"] = self.client.get_pod_logs(
                        namespace, pod, tail_lines=40
                    )
                except ClusterError as exc:
                    ev["pod_logs"] = f"<unavailable: {exc}>"
        return ev

    @staticmethod
    def format_prompt(evidence: dict[str, Any]) -> str:
        """Render evidence into the markdown-ish prompt body."""
        lines: list[str] = []
        cluster = evidence.get("cluster")
        if cluster:
            lines.append("## Cluster health")
            lines.append(
                f"status={cluster.get('health_status')} nodes="
                f"{cluster.get('healthy_nodes')}/{cluster.get('total_nodes')} "
                f"pods_running={cluster.get('running_pods')}/{cluster.get('total_pods')} "
                f"cpu={cluster.get('cpu_usage_rate', 0):.1f}% "
                f"mem={cluster.get('memory_usage_rate', 0):.1f}%"
            )
            for issue in cluster.get("issues", []) or []:
                lines.append(f"- {issue}")
        for key, title in (
            ("unhealthy_nodes", "Unhealthy nodes"),
            ("problem_pods", "Problem pods"),
            ("network_issues", "Network issues"),
            ("low_battery_uavs", "Low-battery UAVs"),
            ("recent_warning_events", "Recent warning events"),
        ):
            items = evidence.get(key)
            if items:
                lines.append(f"## {title}")
                for item in items:
                    lines.append(f"- {json.dumps(item, default=str)}")
        logs = evidence.get("pod_logs")
        if logs:
            lines.append("## Pod logs (tail)")
            lines.append(str(logs))
        if len(lines) == 0:
            lines.append("## Cluster health")
            lines.append("No evidence available (cluster unreachable or empty).")
        return "\n".join(lines)


# ---------------------------------------------------------------------------
# the analysis engine
# ---------------------------------------------------------------------------

_SYSTEM_PREAMBLE = (
    "You are a Kubernetes SRE assistant analyzing live cluster monitoring "
    "evidence. Answer with a concise diagnosis and concrete remediation "
    "steps grounded ONLY in the evidence sections below.\n"
)


class AnalysisEngine:
    def __init__(
        self,
        backend: LLMBackend,
        client: Client | None = None,
        manager: Manager | None = None,
        cfg: AnalysisConfig | None = None,
        llm_cfg: LLMConfig | None = None,
        anomaly_detector=None,
    ) -> None:
        self.backend = backend
        self.client = client
        self.manager = manager
        self.cfg = cfg or AnalysisConfig()
        self.llm_cfg = llm_cfg or LLMConfig()
        self.evidence = EvidenceCollector(client, manager, self.cfg)
        # analysis.anomaly.EmbeddingAnomalyDetector (optional): adds
        # content-aware outlier detection over event text to the
        # thresholds-only anomaly signals.
        self.anomaly_detector = anomaly_detector
        # Multi-turn follow-up sessions (diagnosis/session.py); build_server
        # replaces this with one sized from config.diagnosis.
        self.sessions = SessionManager()

    # -- free-form NL question (the missing /api/v1/query) ---------------------

    def query(self, question: str, slo_class: str = "interactive",
              tenant: str = "") -> AnalysisResponse:
        request_id = uuid.uuid4().hex[:12]
        try:
            ev = self.evidence.collect()
            prompt = (
                _SYSTEM_PREAMBLE
                + self.evidence.format_prompt(ev)
                + f"\n## Question\n{question}\n## Answer\n"
            )
            answer = self.backend.generate(
                prompt,
                max_tokens=self.llm_cfg.max_tokens,
                temperature=self.llm_cfg.temperature,
                slo_class=slo_class,
                tenant=tenant,
            )
            return AnalysisResponse(
                request_id=request_id,
                status="success",
                result={
                    "answer": answer,
                    "model": self.backend.name,
                    "evidence": ev,
                },
            )
        except OverloadedError:
            # Admission-control pushback is not an internal failure: let it
            # propagate to the HTTP layer, which maps it to 429/503 with a
            # Retry-After hint and queue evidence.
            raise
        except Exception as exc:  # noqa: BLE001 — API boundary
            logger.exception("query failed")
            return AnalysisResponse(
                request_id=request_id,
                status="error",
                error=str(exc),
                error_kind="internal",
            )

    def query_stream(self, question: str, slo_class: str = "interactive",
                     tenant: str = ""):
        """Streaming variant of query(): returns (request_id, model_name,
        iterator of answer-text chunks).  Evidence collection happens up
        front (before the first chunk); generation streams from the backend
        as tokens come off the device (LocalEngineBackend) or as one chunk
        (backends without true streaming)."""
        request_id = uuid.uuid4().hex[:12]
        ev = self.evidence.collect()
        prompt = (
            _SYSTEM_PREAMBLE
            + self.evidence.format_prompt(ev)
            + f"\n## Question\n{question}\n## Answer\n"
        )
        chunks = self.backend.generate_stream(
            prompt,
            max_tokens=self.llm_cfg.max_tokens,
            temperature=self.llm_cfg.temperature,
            slo_class=slo_class,
            tenant=tenant,
        )
        return request_id, self.backend.name, chunks

    def query_session(self, question: str, session_id: str = "",
                      slo_class: str = "interactive",
                      tenant: str = "") -> AnalysisResponse:
        """Multi-turn variant of ``query``: the cluster context is frozen
        at session creation and replayed verbatim as the prompt prefix on
        every follow-up, so the engine's PrefixCache (and fleet prefix
        affinity) serve the shared context instead of re-prefilling it.
        An empty ``session_id`` mints a new session; the id comes back in
        the result for the next turn."""
        request_id = uuid.uuid4().hex[:12]
        try:
            session, created = self.sessions.get_or_create(
                session_id,
                lambda: self.evidence.format_prompt(
                    self.evidence.collect()) + "\n",
            )
            prompt = session.build_prompt(_SYSTEM_PREAMBLE, question)
            answer = self.backend.generate(
                prompt,
                max_tokens=self.llm_cfg.max_tokens,
                temperature=self.llm_cfg.temperature,
                slo_class=slo_class,
                tenant=tenant,
            )
            session.record(question, answer)
            return AnalysisResponse(
                request_id=request_id,
                status="success",
                result={
                    "answer": answer,
                    "model": self.backend.name,
                    "session_id": session.session_id,
                    "session_created": created,
                    "turn": len(session.turns),
                },
            )
        except OverloadedError:
            raise  # mapped to 429/503 + Retry-After at the HTTP layer
        except Exception as exc:  # noqa: BLE001 — API boundary
            logger.exception("session query failed")
            return AnalysisResponse(
                request_id=request_id,
                status="error",
                error=str(exc),
                error_kind="internal",
            )

    # -- grammar-constrained verdicts -------------------------------------------

    def diagnose(self, question: str, context: str | None = None,
                 slo_class: str = "standard",
                 tenant: str = "") -> dict[str, Any]:
        """One grammar-constrained root-cause verdict as a parsed dict.

        The contract callers (pipeline, ``_analyze_root_cause``) rely on:
        the return value ALWAYS matches ``diagnosis.grammar.VERDICT_SCHEMA``
        — keys severity/component/root_cause/recommendation/confidence.
        ``context`` is pre-rendered evidence text (the pipeline passes its
        assembled burst context); when omitted, live cluster evidence is
        collected.
        """
        if context is None:
            context = self.evidence.format_prompt(self.evidence.collect())
        prompt = (
            _SYSTEM_PREAMBLE
            + context
            + f"\n## Question\n{question}\n"
            "## Verdict\nRespond with exactly one JSON object with keys "
            "severity, component, root_cause, recommendation, confidence:\n"
        )
        text = self.backend.generate_constrained(
            prompt, temperature=self.llm_cfg.temperature,
            slo_class=slo_class, tenant=tenant)
        try:
            return parse_verdict(text)
        except GrammarError as exc:
            # Defense in depth: the FSM makes this unreachable for the
            # constrained engine path, but a misbehaving custom backend
            # must not break the always-parses contract.
            logger.warning("backend emitted grammar-invalid verdict: %s", exc)
            return parse_verdict(render_verdict(
                "warning", "cluster", text,
                "re-run the diagnosis", 0.2))

    # -- typed analyses (ref pkg/models/models.go:85-99) ------------------------

    def analyze(self, request: AnalysisRequest,
                tenant: str = "") -> AnalysisResponse:
        request_id = uuid.uuid4().hex[:12]
        if request.type not in ANALYSIS_TYPES:
            return AnalysisResponse(
                request_id=request_id,
                status="error",
                error=f"unknown analysis type {request.type!r}; "
                f"expected one of {list(ANALYSIS_TYPES)}",
                error_kind="validation",
            )
        try:
            handler = {
                "pod_communication": self._analyze_pod_communication,
                "anomaly_detection": self._analyze_anomalies,
                "root_cause": self._analyze_root_cause,
            }[request.type]
            result = handler(request.parameters or {}, tenant)
            return AnalysisResponse(
                request_id=request_id, status="success", result=result
            )
        except ValueError as exc:  # bad parameters from the caller
            return AnalysisResponse(
                request_id=request_id,
                status="error",
                error=str(exc),
                error_kind="validation",
            )
        except OverloadedError:
            raise  # mapped to 429/503 + Retry-After at the HTTP layer
        except Exception as exc:  # noqa: BLE001 — API boundary
            logger.exception("analysis %s failed", request.type)
            return AnalysisResponse(
                request_id=request_id,
                status="error",
                error=str(exc),
                error_kind="internal",
            )

    def _analyze_pod_communication(self, params: dict[str, Any],
                                   tenant: str = "") -> dict[str, Any]:
        pod_a = params.get("pod_a", "")
        pod_b = params.get("pod_b", "")
        if not pod_a or not pod_b:
            raise ValueError("pod_a and pod_b are required")
        if self.client is None:
            raise ClusterError("cluster client unavailable")
        analysis = NetworkAnalyzer(self.client).analyze_pod_communication(pod_a, pod_b)
        findings = "\n".join(f"- {i}" for i in analysis.issues) or "- no issues found"
        prompt = (
            _SYSTEM_PREAMBLE
            + f"## Pod communication check {pod_a} -> {pod_b}\n"
            + f"status={analysis.status} confidence={analysis.confidence}\n"
            + f"## Findings\n{findings}\n"
            + "## Question\nExplain the most likely root cause of any "
            "communication problem between these pods and how to fix it.\n"
            "## Answer\n"
        )
        diagnosis = self.backend.generate(
            prompt, max_tokens=self.llm_cfg.max_tokens,
            temperature=self.llm_cfg.temperature,
            tenant=tenant,
        )
        return {
            "analysis": to_jsonable(analysis),
            "llm_diagnosis": diagnosis,
            "model": self.backend.name,
        }

    def _analyze_anomalies(self, params: dict[str, Any],
                           tenant: str = "") -> dict[str, Any]:
        ev = self.evidence.collect()
        anomalies: list[str] = []
        anomalies += [
            f"node {n['node']} unhealthy/pressured (cpu {n['cpu_pct']}%, "
            f"mem {n['mem_pct']}%, conditions {n['conditions']})"
            for n in ev.get("unhealthy_nodes", [])
        ]
        anomalies += [
            f"pod {p['pod']} {p['phase']} ready={p['ready']} "
            f"restarts={p['restarts']} over_limit={p['over_limit']}"
            for p in ev.get("problem_pods", [])
        ]
        anomalies += [
            f"network {m['pair']}: connected={m['connected']} "
            f"quality={m['quality']}"
            for m in ev.get("network_issues", [])
        ]
        anomalies += [
            f"UAV on {u['node']} battery {u['battery_pct']}%"
            for u in ev.get("low_battery_uavs", [])
        ]
        embedding_outliers: list[dict[str, Any]] = []
        if self.anomaly_detector is not None:
            events = ev.get("recent_warning_events", [])
            texts = [f"{e.get('reason', '')}: {e.get('message', '')}"
                     for e in events]
            try:
                for idx, score in self.anomaly_detector.flag_outliers(texts):
                    embedding_outliers.append(
                        {"event": texts[idx], "score": round(score, 4)})
                    anomalies.append(
                        f"semantic outlier event (score {score:.2f}): "
                        f"{texts[idx]}")
            except Exception as exc:  # noqa: BLE001 — detector is best-effort
                logger.warning("embedding anomaly scoring failed: %s", exc)
        prompt = (
            _SYSTEM_PREAMBLE
            + self.evidence.format_prompt(ev)
            + "\n## Question\nSummarize the anomalies, rank them by severity, "
            "and recommend the first remediation step for each.\n## Answer\n"
        )
        summary = self.backend.generate(
            prompt, max_tokens=self.llm_cfg.max_tokens,
            temperature=self.llm_cfg.temperature,
            tenant=tenant,
        )
        return {
            "anomalies": anomalies,
            "anomaly_count": len(anomalies),
            "embedding_outliers": embedding_outliers,
            "llm_summary": summary,
            "model": self.backend.name,
        }

    def _analyze_root_cause(self, params: dict[str, Any],
                            tenant: str = "") -> dict[str, Any]:
        namespace = params.get("namespace", "default")
        pod = params.get("pod", "")
        symptom = params.get("symptom", "") or params.get("question", "")
        ev = self.evidence.collect(
            namespace=namespace, pod=pod or None, include_logs=bool(pod)
        )
        target = f"pod {namespace}/{pod}" if pod else "the cluster"
        prompt = (
            _SYSTEM_PREAMBLE
            + self.evidence.format_prompt(ev)
            + f"\n## Question\nPerform a root-cause analysis for {target}."
            + (f" Reported symptom: {symptom}." if symptom else "")
            + " Identify the most probable cause chain and the fix.\n## Answer\n"
        )
        answer = self.backend.generate(
            prompt, max_tokens=self.llm_cfg.max_tokens,
            temperature=self.llm_cfg.temperature,
            tenant=tenant,
        )
        verdict = self.diagnose(
            f"Root-cause analysis for {target}."
            + (f" Reported symptom: {symptom}." if symptom else ""),
            context=self.evidence.format_prompt(ev),
            tenant=tenant,
        )
        return {
            "target": target,
            "root_cause_analysis": answer,
            "verdict": verdict,
            "evidence": ev,
            "model": self.backend.name,
        }
