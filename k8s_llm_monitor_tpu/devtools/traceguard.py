"""Trace-time correctness gates for the serving engine's hot entry points.

Three guarantees, asserted by *running* the jit machinery on a tiny model
(CPU-friendly shapes, both the fused-interpret and gather decode paths):

  1. **Compile-count stability** — after a warm-up pass, re-invoking the
     engine with same-bucket shapes triggers ZERO new compilations.  A
     silent recompile on the decode hot path costs seconds per occurrence
     in production; this guard turns it into a red gate.  Counted two
     ways: the sum of ``_cache_size()`` over every jitted engine program
     (deterministic, the gating signal) and ``jax.monitoring`` backend
     compile events (supporting evidence in the report).
  2. **No host callbacks in the traced programs** — the jaxprs of the
     decode/prefill/sampling programs must contain no ``pure_callback`` /
     ``io_callback`` / ``debug_callback`` ops: any of those forces a
     device->host round-trip inside what the engine treats as an async
     device call, defeating dispatch-ahead.
  3. **Donated buffers are rebound** — the engine donates KV pages and the
     token buffer into every dispatch; after a step the engine must hold
     the *new* arrays, never a stale alias of a donated input (on TPU that
     alias is a deleted buffer; on CPU it silently reads garbage-to-be).

The report is machine-readable (dict / JSON) and consumed by
tests/test_graftcheck.py and the graftcheck CLI (``--trace``).

Everything imports lazily so the CLI can pin ``JAX_PLATFORMS=cpu`` before
jax initializes.
"""

from __future__ import annotations

import dataclasses
import os
from typing import Any, Callable

FORBIDDEN_PRIMITIVES = frozenset({
    "pure_callback", "io_callback", "debug_callback",
})

#: Decode paths the guard exercises by default.  "fused" runs the Pallas
#: kernel in interpreter mode off-TPU — same trace, same jaxpr, no TPU
#: needed; "gather" is the XLA fallback (and the numerics oracle); "mesh"
#: builds the engine under a GSPMD mesh spanning every local device (the
#: forced-host 8-device CPU mesh in CI) so the SHARDED fused-decode and
#: chunk-prefill programs are gated too — same zero-recompile and
#: donation-rebinding assertions, now over collective-aware programs;
#: "quant" builds the engine with kv_dtype="int8" so the quantize-on-append
#: prefill/decode programs and the widened donation set (page pool PLUS the
#: per-page scale leaves) are held to the same zero-recompile gate;
#: "overlap" is the mesh engine with the hand-staged reduce-scatter/
#: all-gather decode schedule forced on (parallel/overlap.py) — the mesh
#: path itself pins tp_overlap="off" so the GSPMD reference program stays
#: gated alongside the overlap one; "flash_prefill" forces the flash
#: paged-prefill kernel (prefill_path="flash", interpreter on CPU) so the
#: prefill/chunk/verify programs run the tiled online-softmax kernel and
#: are held to the same zero-recompile / donation-rebinding / no-callback
#: gates as the dense programs; "grammar_swap" is the gather engine with a
#: mid-run ``set_grammar`` swap to a *different* same-shape FSM between
#: the warm and repeat passes — the remediation planner swaps per-request
#: plan grammars at runtime, and this path proves the swap is a pure
#: runtime-argument change (zero recompiles) rather than a retrace.
DEFAULT_PATHS = ("gather", "fused", "mesh", "quant", "overlap",
                 "flash_prefill", "grammar_swap")


def force_cpu() -> None:
    """Pin jax to CPU before any backend initializes, and force the
    8-device host platform so the "mesh" path has a real axis to shard
    over; no-op if jax already initialized (the mesh path then uses
    whatever device count exists)."""
    os.environ["JAX_PLATFORMS"] = "cpu"
    flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = (
            flags + " --xla_force_host_platform_device_count=8").strip()
    import jax

    jax.config.update("jax_platforms", "cpu")


def _tiny_cfg(fused: bool, mesh_tp: int = 0):
    """Model configs mirroring tests/test_fused_decode.py: one fails the
    Mosaic 128-lane gate (gather-only), one passes it (KVH*D = 2*64).
    ``mesh_tp`` > 0 selects the TP-shardable config: 8 heads / 8 KV heads
    so every power-of-two device count up to 8 gets head-aligned KV page
    shards (parallel/sharding.py:SpecLayout.kv_pages)."""
    from k8s_llm_monitor_tpu.models.config import ModelConfig

    if mesh_tp:
        return ModelConfig(name="tg-mesh", vocab_size=256, hidden_size=64,
                           intermediate_size=128, num_layers=2, num_heads=8,
                           num_kv_heads=8, dtype="float32",
                           rope_theta=10_000.0)
    if fused:
        return ModelConfig(name="tg-fused", vocab_size=128, hidden_size=256,
                           intermediate_size=256, num_layers=1, num_heads=4,
                           num_kv_heads=2, dtype="float32",
                           rope_theta=10_000.0)
    return ModelConfig(name="tg", vocab_size=256, hidden_size=32,
                       intermediate_size=64, num_layers=2, num_heads=4,
                       num_kv_heads=2, dtype="float32", rope_theta=10_000.0)


def _toy_fsm(variant: int = 0):
    """A hand-built 2-state cycling grammar over a 16-token vocab: states
    1 and 2 allow tokens 3..10 and alternate forever (max_len unbounded, so
    constrained drives terminate by budget — eos_id -1 matches the guard
    engines).  Big enough to exercise every constrained program; far
    smaller than the 259-vocab verdict grammar, which would not fit the
    tiny guard models.

    ``variant=1`` allows a shifted token window (5..12) in the SAME table
    shape — the grammar_swap path installs it mid-run to prove that
    swapping FSM *content* (the remediation planner does this per
    snapshot) never retraces, only rebinding the runtime table argument."""
    import numpy as np

    from k8s_llm_monitor_tpu.diagnosis.grammar import TokenFSM

    lo, hi = (5, 13) if variant else (3, 11)
    trans = np.full((3, 16), -1, dtype=np.int32)
    trans[0, :] = 0
    trans[1, lo:hi] = 2
    trans[2, lo:hi] = 1
    return TokenFSM.from_table(trans, start=1,
                               accept=np.array([False, True, True]),
                               eos_id=-1)


def build_engine(decode_path: str = "gather", seed: int = 0):
    """A tiny engine wired for deterministic compile accounting: prefix
    cache off (a second same-prefix prompt would switch admission to the
    chunked program — a *legitimate* new compile the guard must not count),
    speculation off, two buckets.  A toy grammar is installed so the
    constrained decode/prefill programs join the gated set.

    ``decode_path="mesh"`` builds the SHARDED engine: a GSPMD mesh over
    every local device (TP on ``model``), weights and KV pages device-put
    with the SpecLayout-derived NamedShardings, attention on the XLA
    gather oracle (GSPMD partitions it from the annotations) — the same
    programs the v5e-8 serving config runs, minus real ICI.

    ``decode_path="quant"`` builds the int8-KV engine (kv_dtype="int8"):
    the engine's own impl selection routes decode through the gather/
    dequant reference off-TPU, and the donation set gains the per-page
    scale leaves — the guard asserts those rebind too."""
    import jax

    from k8s_llm_monitor_tpu.models import llama
    from k8s_llm_monitor_tpu.ops.attention import select_decode_impl
    from k8s_llm_monitor_tpu.serving.engine import EngineConfig, InferenceEngine

    mesh = None
    kv_dtype = "auto"
    tp_overlap = "off"
    prefill_path = "auto"
    if decode_path == "flash_prefill":
        # Flash paged prefill forced on (interpreter on CPU) while decode
        # stays on the gather oracle: every prefill/chunk program in the
        # gated set now traces flash_prefill_attention, and the donated
        # page pool rebinds through the kernel's pallas_call instead of
        # the scatter+gather XLA graph.
        cfg = _tiny_cfg(fused=False)
        impl = select_decode_impl(cfg=cfg, mode="gather")
        prefill_path = "flash"
    elif decode_path in ("mesh", "overlap"):
        from k8s_llm_monitor_tpu.parallel.mesh import MeshConfig, create_mesh

        tp = len(jax.devices())
        mesh = create_mesh(MeshConfig(model=tp))
        cfg = _tiny_cfg(fused=False, mesh_tp=tp)
        impl = select_decode_impl(cfg=cfg, mesh=mesh, mode="gather")
        # "mesh" pins the GSPMD-auto program (the correctness reference);
        # "overlap" requires the staged schedule — build fails loudly if
        # the tiny config ever stops clearing overlap_supported().
        if decode_path == "overlap":
            tp_overlap = "on" if tp > 1 else "off"
    elif decode_path == "quant":
        # attn_impl=None: the engine's select_decode_impl call sees the
        # quantized pool and picks the dequantizing path itself — the same
        # branch a production int8 config takes.
        cfg = _tiny_cfg(fused=False)
        impl = None
        kv_dtype = "int8"
    elif decode_path == "grammar_swap":
        # Same engine as "gather"; check_path swaps same-shape grammars
        # between (and inside) the passes.  The FSM table is a runtime
        # argument keyed only by shape, so the swap must not retrace.
        cfg = _tiny_cfg(fused=False)
        impl = select_decode_impl(cfg=cfg, mode="gather")
    else:
        cfg = _tiny_cfg(fused=decode_path == "fused")
        impl = select_decode_impl(cfg=cfg, mode=decode_path)
    params = llama.init_params(jax.random.PRNGKey(seed), cfg)
    ec = EngineConfig(
        max_slots=4, num_blocks=64, block_size=8, max_blocks_per_seq=8,
        prefill_buckets=(16, 32), max_prefills_per_step=2,
        max_admission_rounds=2, decode_steps_per_iter=4, max_inflight=2,
        spec_k=0, prefix_cache_entries=0, sample_topk_cap=8,
        kv_dtype=kv_dtype, tp_overlap=tp_overlap, prefill_path=prefill_path,
    )
    engine = InferenceEngine(cfg, params, engine_cfg=ec, eos_id=-1,
                             attn_impl=impl, mesh=mesh)
    engine.set_grammar(_toy_fsm())
    return engine


# ---------------------------------------------------------------------------
# compile accounting
# ---------------------------------------------------------------------------

def _engine_programs(engine) -> list[Any]:
    progs = [engine._prefill_sample, engine._prefill_greedy,
             engine._prefill_chunk_sample, engine._prefill_chunk_greedy,
             engine._prefill_sample_fsm, engine._prefill_chunk_sample_fsm,
             engine._place_tokens, engine._place_fsm]
    if engine._hist_place is not None:
        progs.append(engine._hist_place)
    progs.extend(engine._decode_cache.values())
    return progs


def program_cache_size(engine) -> int:
    """Total compiled-variant count across every jitted engine program.
    The delta across a workload is the number of new compilations it
    triggered — deterministic, unlike wall-clock or log scraping."""
    total = 0
    for prog in _engine_programs(engine):
        size = getattr(prog, "_cache_size", None)
        if callable(size):
            total += size()
    return total


class CompileEvents:
    """Context manager counting backend-compile events via jax.monitoring
    (supporting evidence beside the cache-size delta; the persistent
    compilation cache can serve hits that still emit cache events, so
    this is reported but not gated on)."""

    _COMPILE_MARKERS = ("compile", "backend_compile")

    def __init__(self):
        self.events: list[str] = []

    def _listener(self, event: str, **kwargs) -> None:
        if any(m in event for m in self._COMPILE_MARKERS):
            self.events.append(event)

    def __enter__(self) -> "CompileEvents":
        import jax.monitoring

        jax.monitoring.register_event_listener(self._listener)
        return self

    def __exit__(self, *exc) -> None:
        try:
            from jax._src import monitoring as _m

            _m._unregister_event_listener_by_callback(self._listener)
        except Exception:
            # jax-internal unregister moved; dropping every listener is
            # acceptable in the CLI/test contexts this runs in.
            import jax.monitoring

            jax.monitoring.clear_event_listeners()

    @property
    def count(self) -> int:
        return len(self.events)


def count_new_compiles(engine, fn: Callable[[], Any]) -> tuple[int, int]:
    """Run ``fn`` and return (new compiled variants, monitoring events).
    The first number is the gate; the second is evidence."""
    before = program_cache_size(engine)
    with CompileEvents() as ev:
        fn()
    return program_cache_size(engine) - before, ev.count


# ---------------------------------------------------------------------------
# jaxpr scanning
# ---------------------------------------------------------------------------

def _iter_eqns(jaxpr):
    """Every equation in ``jaxpr``, descending into sub-jaxprs carried in
    eqn params (pjit bodies, scan bodies, cond branches...)."""
    closed = getattr(jaxpr, "jaxpr", None)
    if closed is not None:           # ClosedJaxpr -> Jaxpr
        jaxpr = closed
    for eqn in jaxpr.eqns:
        yield eqn
        for val in eqn.params.values():
            for sub in (val if isinstance(val, (list, tuple)) else (val,)):
                if hasattr(sub, "eqns") or hasattr(sub, "jaxpr"):
                    yield from _iter_eqns(sub)


def forbidden_ops(jaxpr) -> list[str]:
    return sorted({eqn.primitive.name for eqn in _iter_eqns(jaxpr)
                   if eqn.primitive.name in FORBIDDEN_PRIMITIVES})


def scan_engine_programs(engine) -> dict[str, list[str]]:
    """make_jaxpr every hot entry point (decode greedy + sampled, prefill
    greedy + sampled) with engine-shaped arguments and report any
    forbidden host-callback primitives, keyed by program name."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    ec = engine.ecfg
    B = ec.max_slots
    bucket = ec.prefill_buckets[0]
    W = ec.max_blocks_per_seq
    pages = engine.pages
    params = engine.params
    out: dict[str, list[str]] = {}

    dec_tables = jnp.asarray(np.tile(
        np.arange(1, W + 1, dtype=np.int32)[None, :], (B, 1)))
    tok = jnp.zeros((B,), jnp.int32)
    ctx = jnp.ones((B,), jnp.int32)
    remaining = jnp.full((B,), 8, jnp.int32)
    eos = jnp.asarray(-1, jnp.int32)
    K = ec.decode_steps_per_iter

    greedy = engine._decode_program(K, sampled=False)
    out["decode_greedy"] = forbidden_ops(jax.make_jaxpr(greedy)(
        params, tok, ctx, remaining, pages, dec_tables, eos))

    sampled = engine._decode_program(K, sampled=True,
                                     bounded=ec.sample_topk_cap > 0)
    temp = jnp.full((B,), 0.7, jnp.float32)
    topk = jnp.full((B,), 4, jnp.int32)
    topp = jnp.full((B,), 0.9, jnp.float32)
    rng = jax.random.PRNGKey(0)
    out["decode_sampled"] = forbidden_ops(jax.make_jaxpr(sampled)(
        params, tok, ctx, remaining, pages, dec_tables, temp, topk, topp,
        rng, eos))

    if engine._fsm_trans is not None:
        constrained = engine._decode_program(
            K, sampled=True, bounded=ec.sample_topk_cap > 0,
            constrained=True)
        fstate = jnp.ones((B,), jnp.int32)
        out["decode_constrained"] = forbidden_ops(jax.make_jaxpr(constrained)(
            params, tok, fstate, ctx, remaining, pages, dec_tables,
            engine._fsm_trans, temp, topk, topp, rng, eos))

    # One prompt of ``bucket`` tokens: a packed stream with one live row
    # (one chip), or one row (a mesh) — engine._dispatch_admit.
    if engine._packed_prefill:
        P = ec.max_prefills_per_step
        live = np.arange(P) == 0
        ptoks = jnp.zeros((bucket,), jnp.int32)
        plens = (jnp.asarray(np.where(live, 0, bucket), jnp.int32),
                 jnp.asarray(np.where(live, bucket, 0), jnp.int32))
    else:
        P = 1
        ptoks = jnp.zeros((P, bucket), jnp.int32)
        plens = (jnp.full((P,), bucket, jnp.int32),)
    ptbl = jnp.asarray(np.arange(1, W + 1, dtype=np.int32)[None, :]
                       * (np.arange(P) == 0)[:, None])
    out["prefill_greedy"] = forbidden_ops(jax.make_jaxpr(
        engine._prefill_greedy)(params, ptoks, plens, pages, ptbl))
    out["prefill_sampled"] = forbidden_ops(jax.make_jaxpr(
        engine._prefill_sample)(
            params, ptoks, plens, pages, ptbl,
            jnp.full((P,), 0.7, jnp.float32), jnp.full((P,), 4, jnp.int32),
            jnp.full((P,), 0.9, jnp.float32), rng))
    if engine._fsm_trans is not None:
        out["prefill_constrained"] = forbidden_ops(jax.make_jaxpr(
            engine._prefill_sample_fsm)(
                params, ptoks, plens, pages, ptbl,
                jnp.ones((P,), jnp.int32), engine._fsm_trans,
                jnp.full((P,), 0.7, jnp.float32),
                jnp.full((P,), 4, jnp.int32),
                jnp.full((P,), 0.9, jnp.float32), rng))
    return out


# ---------------------------------------------------------------------------
# the guard
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class PathReport:
    decode_path: str
    warm_compiles: int
    warm_events: int
    repeat_compiles: int
    repeat_events: int
    forbidden: dict[str, list[str]]
    donated_pages_rebound: bool
    donated_tokens_rebound: bool
    donated_fsm_rebound: bool = True
    donated_scales_rebound: bool = True
    kv_quant: str = ""
    prefill_path: str = "dense"

    @property
    def ok(self) -> bool:
        return (self.repeat_compiles == 0
                and not any(self.forbidden.values())
                and self.donated_pages_rebound
                and self.donated_tokens_rebound
                and self.donated_fsm_rebound
                and self.donated_scales_rebound)

    def as_dict(self) -> dict:
        d = dataclasses.asdict(self)
        d["ok"] = self.ok
        return d


def _drive(engine, prompt_len: int, greedy: bool, tag: int,
           constrained: bool = False) -> None:
    """One generation in the first prefill bucket: 4 tokens, distinct
    prompt content per ``tag`` (same shapes, different values — content
    must never matter to the compile count)."""
    from k8s_llm_monitor_tpu.serving.engine import SamplingParams

    prompt = [(tag * 7 + i) % 100 + 1 for i in range(prompt_len)]
    sampling = (SamplingParams(max_tokens=4, constrained=constrained)
                if greedy
                else SamplingParams(max_tokens=4, temperature=0.7, top_k=4,
                                    constrained=constrained))
    res = engine.generate([prompt], sampling)[0]
    assert res.finish_reason in ("eos", "length"), res


def check_path(decode_path: str) -> PathReport:
    engine = build_engine(decode_path)
    swap = decode_path == "grammar_swap"

    # prompt_len 40 > the top bucket (32): forces the chunk-round admission
    # path, so the chunk-prefill programs (plain + FSM) are compiled in the
    # warm pass and gated for zero recompiles in the repeat pass — on the
    # mesh path these are the SHARDED chunk programs.
    def warm():
        _drive(engine, prompt_len=12, greedy=True, tag=1)
        _drive(engine, prompt_len=12, greedy=False, tag=2)
        _drive(engine, prompt_len=12, greedy=False, tag=5, constrained=True)
        _drive(engine, prompt_len=40, greedy=True, tag=7)
        _drive(engine, prompt_len=40, greedy=False, tag=8, constrained=True)

    def repeat():
        # The grammar_swap path installs a different same-shape FSM before
        # each constrained drive (and swaps back once mid-pass): the swap
        # rebinds the runtime table argument, so the compile-count gate
        # below must still read zero.
        if swap:
            engine.set_grammar(_toy_fsm(variant=1))
        _drive(engine, prompt_len=12, greedy=True, tag=3)
        _drive(engine, prompt_len=12, greedy=False, tag=4)
        _drive(engine, prompt_len=12, greedy=False, tag=6, constrained=True)
        if swap:
            engine.set_grammar(_toy_fsm(variant=0))
        _drive(engine, prompt_len=40, greedy=True, tag=9)
        _drive(engine, prompt_len=40, greedy=False, tag=10, constrained=True)

    warm_c, warm_e = count_new_compiles(engine, warm)
    pages_before = engine.pages
    toks_before = engine._tok_state
    fsm_before = engine._fsm_state
    repeat_c, repeat_e = count_new_compiles(engine, repeat)
    # A quantized pool widens the donation set: the per-page scale leaves
    # ride along with k/v into every dispatch and must rebind the same way
    # (a stale scale alias silently dequantizes new pages with old scales).
    scales_rebound = True
    if engine.kv_quant:
        scales_rebound = (
            engine.pages.k_scale[0] is not pages_before.k_scale[0]
            and engine.pages.v_scale[0] is not pages_before.v_scale[0])
    report = PathReport(
        decode_path=decode_path,
        warm_compiles=warm_c, warm_events=warm_e,
        repeat_compiles=repeat_c, repeat_events=repeat_e,
        forbidden=scan_engine_programs(engine),
        # The engine donates pages and the token/FSM-state buffers into
        # every constrained dispatch; after the repeat pass it must hold
        # fresh outputs, not an alias of something it donated away.
        donated_pages_rebound=engine.pages is not pages_before,
        donated_tokens_rebound=engine._tok_state is not toks_before,
        donated_fsm_rebound=engine._fsm_state is not fsm_before,
        donated_scales_rebound=scales_rebound,
        kv_quant=engine.kv_quant,
        prefill_path=engine.prefill_path,
    )
    return report


def run_traceguard(paths=DEFAULT_PATHS) -> dict:
    """The full trace-time gate; returns the machine-readable report the
    CLI prints and tests consume."""
    reports = {p: check_path(p) for p in paths}
    return {
        "paths": {p: r.as_dict() for p, r in reports.items()},
        "ok": all(r.ok for r in reports.values()),
    }
