"""Entry into the program: build the engine and its service as the
product's ``LocalEngineBackend`` factory does, and drive only public calls
(``EngineService.submit`` / ``observer`` / ``call``, ``engine.active_slots``,
``engine.allocator``, ``engine.prefix_cache``, ``program_cache_size``, the
global ``Tracer``).  The server, supervisor, journal and verdict grammar are
skipped: they serve no request of these cells.
"""

from __future__ import annotations

import dataclasses
import queue
import threading
import time
from typing import Optional

import numpy as np

from benchmarks.harness import draw
from benchmarks.harness.stats import Request

# The published keys of a config.json and the ModelConfig field each must equal.
PUBLISHED_KEYS = {
    "hidden_size": "hidden_size",
    "intermediate_size": "intermediate_size",
    "num_hidden_layers": "num_layers",
    "num_attention_heads": "num_heads",
    "num_key_value_heads": "num_kv_heads",
    "vocab_size": "vocab_size",
    "rope_theta": "rope_theta",
}


def model_config(config: dict, preset_override: Optional[str] = None):
    """``PRESETS[config["preset"]]``, checked against the file's published
    sizes so that the file is the configuration as it is run."""
    from k8s_llm_monitor_tpu.models.config import PRESETS

    cfg = PRESETS[preset_override or config["preset"]]
    if preset_override is None:
        for key, field in PUBLISHED_KEYS.items():
            if config[key] != getattr(cfg, field):
                raise ValueError(
                    f"{config['preset']}: file says {key}={config[key]}, the "
                    f"program's preset has {field}={getattr(cfg, field)}")
        if bool(config.get("attention_bias", False)) != cfg.qkv_bias:
            raise ValueError(f"{config['preset']}: attention_bias differs")
        # The preset leaves the epsilon at the dataclass default; the file
        # has the published one, and the file is what runs.
        cfg = dataclasses.replace(cfg, rms_norm_eps=config["rms_norm_eps"])
    if config["assumed"]["quantize"] == "w8a8":
        cfg = dataclasses.replace(cfg, act_quant=True)
    return cfg


def build(config: dict, seed: int, *, preset_override: Optional[str] = None,
          engine_overrides: Optional[dict] = None, log=lambda msg: None):
    """(engine, service).  Weights are made on the device from the seed in
    one jitted call, in the type they are served in."""
    import jax

    from k8s_llm_monitor_tpu.serving.engine import EngineConfig, InferenceEngine
    from k8s_llm_monitor_tpu.serving.service import EngineService
    from k8s_llm_monitor_tpu.utils.quantize import init_params_quantized
    from k8s_llm_monitor_tpu.utils.tokenizer import load_tokenizer

    cfg = model_config(config, preset_override)
    assumed = config["assumed"]
    if assumed["quantize"] not in ("int8", "w8a8"):
        raise ValueError("only the quantized presets are served by these cells")
    t = time.monotonic()
    params = jax.block_until_ready(
        jax.jit(lambda key: init_params_quantized(key, cfg))(
            jax.random.PRNGKey(seed)))
    t_weights = time.monotonic() - t
    fields = dict(assumed["engine"])
    fields.update(engine_overrides or {})
    engine = InferenceEngine(cfg, params, EngineConfig(**fields),
                             tokenizer=load_tokenizer(None), seed=seed % (2**31))
    log(f"weights in {t_weights:.1f}s, engine object in "
        f"{time.monotonic() - t - t_weights:.1f}s")
    return engine, EngineService(engine)


class LoadPort:
    """What a generator sees of the system: ``send``, ``completions``,
    ``stop``.  Its ``observe`` is the service's one observer: it runs on the
    step thread and only stamps the clock."""

    def __init__(self, svc, params: dict, vocab: int, sampling: dict) -> None:
        from k8s_llm_monitor_tpu.serving.engine import SamplingParams

        self._svc = svc
        self._sampling_cls = SamplingParams
        self._sampling = dict(sampling)
        self._vocab = vocab
        self.params = params
        self.stop = threading.Event()
        self.completions: "queue.Queue[Request]" = queue.Queue()
        self.emissions: list[tuple[float, int]] = []
        self._open: dict[str, tuple[Request, object]] = {}
        self._lock = threading.Lock()
        svc.observer = self.observe

    def send(self, req: Request, *, tenant: Optional[str] = None,
             **sampling) -> bool:
        """Submit one request; False if the service refused it."""
        params = self._sampling_cls(max_tokens=req.max_tokens,
                                    **{**self._sampling, **sampling})
        extra = {} if tenant is None else {"tenant": tenant}
        with self._lock:
            self._open[req.rid] = (req, None)
        req.submit_t = time.monotonic()
        if req.due_t is None:
            req.due_t = req.submit_t
        try:
            handle = self._svc.submit(req.prompt, params, request_id=req.rid,
                                      **extra)
        except Exception as exc:  # noqa: BLE001 — a refusal is a result, not a crash
            req.finish, req.error = "refused", repr(exc)
            with self._lock:
                self._open.pop(req.rid, None)
            self.completions.put(req)  # it has ended: a waiting client moves on
            return False
        with self._lock:
            if req.rid in self._open:
                self._open[req.rid] = (req, handle)
        return True

    def observe(self, rid: str, toks: list[int], result) -> None:
        now = time.monotonic()
        entry = self._open.get(rid)
        if entry is None:
            return
        req = entry[0]
        if toks:
            if req.first_t is None:
                req.first_t = now
            req.last_t = now
            req.n_tokens += len(toks)
            req.token_ids.extend(toks)
            if min(toks) < 0 or max(toks) >= self._vocab:
                req.bad_token = True
            self.emissions.append((now, len(toks)))
        if result is not None:
            req.finish, req.error = result.finish_reason, result.error
            req.done_t = now
            with self._lock:
                self._open.pop(rid, None)
            self.completions.put(req)

    def in_flight(self) -> int:
        return len(self._open)

    def cancel_open(self) -> None:
        """Cancel whatever is still running (the closed loop's window end)."""
        with self._lock:
            handles = [h for _, h in self._open.values() if h is not None]
        for handle in handles:
            handle.cancel()

    def run_batch(self, reqs: list[Request], timeout: float, **how) -> None:
        """Submit ``reqs`` atomically on the step thread — so they reach one
        admission round together — and wait until each has ended."""
        self._svc.call(
            lambda _engine: [self.send(r, **how) for r in reqs],
            timeout=timeout)
        deadline = time.monotonic() + timeout
        while any(r.done_t is None and r.finish != "refused" for r in reqs):
            if time.monotonic() > deadline:
                raise TimeoutError(f"warm-up batch not done in {timeout}s")
            time.sleep(0.005)
        bad = [r for r in reqs if not r.ok]
        if bad:
            raise RuntimeError(f"warm-up request failed: {bad[0].finish} "
                               f"{bad[0].error}")


def reachable_buckets(dist: dict, buckets: tuple[int, ...]) -> list[int]:
    """Prefill buckets a clipped prompt-length distribution can land in."""
    from k8s_llm_monitor_tpu.serving.engine import prefill_bucket_for

    lo = prefill_bucket_for(dist["min"], buckets)
    hi = prefill_bucket_for(dist["max"], buckets)
    return [b for b in buckets if lo <= b <= hi]


def warm_up(port: LoadPort, engine, traffic: dict, vocab: int, seed: int,
            timeout: float, log=lambda msg: None) -> int:
    """Run every program the cell's traffic can reach, deterministically:
    for each reachable prompt bucket and each lane count of the admission
    ladder, one batch admitted together.  The first batch answers with the
    mix's ``warm_up_answer_tokens``: 16 run the fused decode at 8, 4, 2 and 1
    steps, 9 at 8 steps alone (a saturated closed loop always has a lane with
    8 tokens to go, and each decode program costs over a minute of set-up
    that no cache keeps); the other batches answer with one token, which is
    the prefill alone.  Returns the number of batches."""
    first_answer = int(traffic["warm_up_answer_tokens"])
    rng = draw.rng_for(seed, 4)
    ec = engine.ecfg
    lanes, p = [], 1
    while p <= min(ec.max_prefills_per_step, ec.max_slots):
        lanes.append(p)
        p *= 2
    batches = 0
    for bucket in reachable_buckets(traffic["prompt_tokens"], ec.prefill_buckets):
        for n in lanes:
            t = time.monotonic()
            lengths = np.full(n, bucket, dtype=np.int64)
            reqs = [Request(rid=f"warm-{bucket}-{n}-{i}", prompt=prompt,
                            max_tokens=1 if batches else first_answer)
                    for i, prompt in enumerate(draw.token_ids(lengths, rng, vocab))]
            port.run_batch(reqs, timeout)
            batches += 1
            log(f"warm-up batch {batches}: {n} x {bucket} tokens in "
                f"{time.monotonic() - t:.1f}s")
    return batches


def probe(port: LoadPort, vocab: int, seed: int, timeout: float) -> dict:
    """One seeded 64-token prompt, its first token by greedy prefill, twice
    on an idle engine: the ids must be identical.  The second run takes
    another tenant, so it recomputes the prompt through the same program
    instead of hitting the prefix cache (which would run the chunked
    program).  No greedy decode call: its program would add 74 s of set-up to
    every run (PERF.md, section 7)."""
    prompt = draw.token_ids(np.array([64]), draw.rng_for(seed, 5), vocab)[0]
    runs = []
    for i in range(2):
        req = Request(rid=f"probe-{i}", prompt=prompt, max_tokens=1)
        port.run_batch([req], timeout, temperature=0.0, tenant=f"probe-{i}")
        runs.append(req.token_ids)
    return {"ok": runs[0] == runs[1] and len(runs[0]) == 1, "ids": runs}


def health_faults(svc, engine) -> dict:
    """Counts that must all be 0 after a clean window."""
    snap = svc.health.snapshot()
    return {
        "dispatch_failures": engine.dispatch_failures,
        "watchdog_trips": engine.watchdog_trips,
        "requeues": engine.requeues,
        "preemptions": engine.preemptions,
        "deadline_expired": engine.deadline_expired,
        "sheds": svc.shed_count,
        "not_healthy": int(snap["state"] != "healthy"),
    }
