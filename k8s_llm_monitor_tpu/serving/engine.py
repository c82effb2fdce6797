"""Continuous-batching inference engine.

The decode loop is slot-based: a fixed-width batch of ``max_slots`` lanes is
compiled exactly once (static shapes), and requests are admitted into / retired
from lanes between steps.  Inactive lanes run with context_len=0 and the null
KV block, so the compiled program never changes shape.

Throughput design (the north-star SLO is p50 TTFT < 500 ms at 100 concurrent
diagnosis queries, BASELINE.md):

  * **Batched prefill** — up to ``max_prefills_per_step`` pending prompts are
    ingested in one admission round, and their first tokens are sampled
    inside the same compiled program.  A fresh round on one chip lays its
    prompts end to end in a packed stream of ``T`` tokens (a power-of-two
    rung), so what is computed per token is computed for the round's real
    tokens; the round goes as the calls whose rungs sum to the least
    (``_admit_groups``: mostly one or two).  A round with a prefix hit, and every round
    over a mesh, is ONE ``[P, bucket]`` call (padded lanes are inactive).
  * **Fused multi-step decode** — ``decode_steps_per_iter`` decode steps run
    inside one compiled ``lax.scan`` with on-device token feedback; per-lane
    EOS detection and budget exhaustion are masked on device, so the host
    syncs once per K steps instead of once per token.
  * **Asynchronous reconciliation** — sampled tokens live in a device-resident
    ``[max_slots]`` buffer that feeds the next decode call directly, so the
    host never blocks on token values to keep the device busy.  Dispatched
    calls join an in-flight queue (depth ``max_inflight``); their results are
    fetched via ``copy_to_host_async`` and reconciled (emission, EOS/budget
    retirement, TTFT stamping) behind the dispatch front.  This hides the
    device->host latency that would otherwise serialize every step, and
    buys dispatch/compute overlap.
  * Prompts longer than the largest bucket admit into *prefilling* slots:
    their chunks stream one batched round per scheduler step (depth-first —
    lanes closest to completion go first), so decode dispatches and
    short-prompt admissions interleave between chunk rounds instead of
    stalling behind a serial per-request chunk loop.  Continuation chunks
    attend to the paged prefix.

Speculation note: EOS is only learned at reconcile time, so up to
``max_inflight`` decode calls may keep stepping a finished lane.  Those
zombie steps are confined to the lane's own pre-extended pages and their
outputs are discarded at reconcile; pages of a retired lane are returned to
the pool only after the last in-flight call that references them completes.

Preemption: if the allocator runs out of pages, in-flight work is drained and
the youngest slot is evicted and re-queued with its generated tokens folded
into the prompt (recompute-style preemption), so long-running requests always
make progress.

This engine is the TPU replacement for the reference's never-implemented LLM
path (its entire integration is config keys, reference
internal/config/config.go:141-145).
"""

from __future__ import annotations

import collections
import dataclasses
import logging
import os
import threading
import time
from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np

from k8s_llm_monitor_tpu.models import llama
from k8s_llm_monitor_tpu.models.config import ModelConfig
from k8s_llm_monitor_tpu.observability.flight import get_flight_recorder
from k8s_llm_monitor_tpu.observability.metrics import ClassHistogram
from k8s_llm_monitor_tpu.observability.tracing import get_tracer
from k8s_llm_monitor_tpu.resilience.faults import FaultError, get_injector
from k8s_llm_monitor_tpu.resilience.slo import DEFAULT_CLASS, SLO_RANK
from k8s_llm_monitor_tpu.resilience.tenancy import (
    DEFAULT_TENANT,
    normalize_tenant,
)
from k8s_llm_monitor_tpu.ops.pallas_attention import fetch_groups
from k8s_llm_monitor_tpu.ops.sampling import (
    fsm_advance,
    fsm_mask_logits,
    greedy_tokens,
    sample_tokens,
    sample_tokens_bounded,
)
from k8s_llm_monitor_tpu.serving.kv_cache import (
    BlockAllocator,
    OutOfBlocks,
    PrefixCache,
    page_slice_bytes,
    shareable_blocks,
)
from k8s_llm_monitor_tpu.serving.kv_tier import (
    BlobError,
    HostKVTier,
    SpilledPrefix,
    pack_prefix_blob,
    unpack_prefix_blob,
)
from k8s_llm_monitor_tpu.serving.spec import (
    AcceptanceEMA,
    accept_greedy,
    accept_sampled,
    propose_drafts,
)

logger = logging.getLogger("serving.engine")


@dataclasses.dataclass
class SamplingParams:
    max_tokens: int = 256
    temperature: float = 0.0   # <= 0 -> greedy
    top_k: int = 0             # <= 0 -> disabled
    top_p: float = 1.0         # >= 1 -> disabled
    # Grammar-constrained decoding (diagnosis/grammar.py): every sampled
    # token is masked by the engine's installed TokenFSM so the output is
    # schema-valid by construction.  Requires ``set_grammar()`` before
    # submit; max_tokens is raised to the grammar's max_len so the forced
    # EOS is always reachable.
    constrained: bool = False

    @property
    def filtered(self) -> bool:
        """This lane samples and has top-k or top-p on: the per-lane term
        of the predicate on which ops/sampling.py takes its rank filter (a
        greedy lane's filters are never read)."""
        return self.temperature > 0.0 and (self.top_k > 0
                                           or self.top_p < 1.0)


@dataclasses.dataclass
class GenerationRequest:
    request_id: str
    prompt_ids: list[int]
    sampling: SamplingParams = dataclasses.field(default_factory=SamplingParams)
    submit_time: float = dataclasses.field(default_factory=time.monotonic)
    # Set on first admission; tokens past this index in prompt_ids are
    # generated output folded back in by preemption.
    orig_prompt_len: int = -1
    first_token_time: float = 0.0
    # Cold-burst dedup: set the first time admission holds this request
    # back so a same-prefix lane can publish the shared pages first
    # (engine._admit_round); caps the dense-lane rule at one round and
    # keeps the deferral counter per-request.
    prefix_deferred: bool = False
    # Wall-clock budget from submit (seconds); 0 = none.  Enforced at
    # admission and per step(): an expired request fails with a
    # "deadline exceeded" cause instead of occupying KV pages forever.
    deadline_s: float = 0.0
    # Times this request was recompute-requeued by a pipeline reset
    # (watchdog trip / dispatch failure); bounded by
    # EngineConfig.max_requeues, then the request fails with the cause.
    requeues: int = 0
    # SLO class (resilience/slo.py): "interactive" | "standard" | "batch".
    # Host-side scheduling metadata only — orders admission, shedding, and
    # eviction; never enters a traced program (zero recompiles).
    slo_class: str = DEFAULT_CLASS
    # Tenant namespace (resilience/tenancy.py): seeds this request's
    # prefix-cache digest chain, so its KV reuse is confined to its own
    # tenant by construction.  Host-side scheduling metadata only, like
    # slo_class — never enters a traced program (zero recompiles).
    tenant: str = DEFAULT_TENANT
    # Trace context (observability/tracing.py TraceContext) captured at
    # EngineService.submit; the engine records phase spans against it.
    # Host-side metadata only, like slo_class — never enters a traced
    # program (zero recompiles).  None when the request is untraced.
    trace: Any = None


@dataclasses.dataclass
class GenerationResult:
    request_id: str
    token_ids: list[int]
    finish_reason: str         # "eos" | "length" | "error"
    ttft_s: float              # submit -> first token
    latency_s: float           # submit -> completion
    error: str = ""            # set when finish_reason == "error"


def prefill_bucket_for(n: int, buckets) -> int:
    """Smallest bucket in ``buckets`` covering ``n`` tokens — THE bucket
    rounding, shared by the engine's admission path (``_bucket``) and by
    the benchmark harness's warm-up (``benchmarks/harness/system.py``:
    which buckets a cell's prompt lengths can reach), so the two can't
    silently disagree about which bucket a prompt lands in.  ``buckets``
    must be ascending; ``n`` past the top bucket raises — longer prompts
    go through chunked prefill, never silent clamping."""
    for b in buckets:
        if n <= b:
            return b
    raise ValueError(
        f"{n} tokens exceeds the largest prefill bucket "
        f"{buckets[-1]} — chunk before bucketing"
    )


@dataclasses.dataclass
class EngineConfig:
    max_slots: int = 16
    num_blocks: int = 512
    block_size: int = 16
    max_blocks_per_seq: int = 64
    prefill_buckets: tuple[int, ...] = (32, 64, 128, 256, 512, 1024, 2048)
    # Requests ingested per batched-prefill call (the prefill lane count).
    max_prefills_per_step: int = 8
    # Batched-prefill admission rounds per scheduler step: a burst drains
    # into slots at up to rounds*lanes requests before each decode run,
    # which is TTFT-optimal for bursts while the cap bounds decode stall.
    max_admission_rounds: int = 4
    # Decode steps fused into one device call between host syncs.
    decode_steps_per_iter: int = 8
    # Dispatch-ahead depth: calls in flight before reconciling the oldest.
    max_inflight: int = 2
    # Decode attention path (ops/attention.py:select_decode_impl):
    # "auto" = the fused RoPE+append+attention Pallas kernel on a
    # compatible single TPU chip, split/gather otherwise; "fused",
    # "pallas", "gather" force a path.
    decode_path: str = "auto"
    # Prefill-family attention path (ops/attention.py:select_prefill_impl):
    # "auto" = the flash paged-prefill kernel (tiled online softmax reading
    # K/V straight from the pool) on a compatible TPU chip or mesh, the
    # dense XLA oracle otherwise; "flash"/"dense" force a path.  Serves
    # fresh prefill, continuation chunks, and spec verify alike.
    prefill_path: str = "auto"
    # Resident KV representation (serving/kv_tier.py rung 1): "auto" keeps
    # the model-dtype pool (the flag-selectable fp16/bf16 oracle, same
    # pattern as decode_path); "int8"/"fp8" store pages in the narrow dtype
    # with per-(token, head) f32 dequant scales — roughly doubling resident
    # lanes on the same pool bytes (page_slice_bytes accounting).
    # K8SLLM_KV_DTYPE overrides.
    kv_dtype: str = "auto"
    # Host-RAM spill tier capacity in bytes (rung 2): pressured prefix-cache
    # evictions demote page rows to a HostKVTier of this size instead of
    # dropping them, and the next hit rehydrates without re-prefill.
    # 0 disables (pressured evictions drop, as before).
    host_spill_bytes: int = 0
    # On-device sampling: when every sampling lane of a dispatch has
    # 0 < top_k <= this cap, the decode program samples from the top
    # ``sample_topk_cap`` logits (one lax.top_k) instead of rank-sorting
    # the full vocab each scan step (V=128k on the 8B target).  The
    # bounded program is distribution-exact in that regime
    # (ops/sampling.py:sample_tokens_bounded); 0 disables.  The unbounded
    # program sorts only in a call where some sampling lane has a filter
    # this cap does not cover (top_p alone, or top_k above it); with none
    # it draws straight from the scaled logits (_filter_logits).
    sample_topk_cap: int = 64
    # Prompt-prefix KV reuse (serving/kv_cache.py:PrefixCache): LRU entry
    # cap (one entry per cached prefix *length*; host-side tuples, cheap);
    # 0 disables.  Shared blocks are read-only by construction, so this is
    # refcounting, not copy-on-write.
    prefix_cache_entries: int = 1024
    # Multi-tenant KV fairness (resilience/tenancy.py): the fraction of
    # cached blocks (device prefix cache) / bytes (host tier) one tenant
    # may hold while another tenant is resident — over-share tenants
    # become the preferred eviction victims of THEIR OWN LRU entries.
    # 1.0 disables the cap (single-tenant default).
    kv_max_tenant_share: float = 1.0
    # Prefill-priority: while chunk rounds are pending, decode dispatches
    # only every Nth step — TTFT is completion-order-sensitive and a decode
    # dispatch between chunk rounds would steal ~half the bandwidth from
    # every waiting first token.  N bounds decode starvation for lanes
    # already generating.  1 = strict alternation, large = prefill-first.
    decode_every_n_chunk_rounds: int = 3
    # Deadline-aware chunk-round sizing: while any interactive-class
    # request waits in the pending queue, chunk rounds clamp their token
    # bucket to this size (rounded up to a prefill bucket) so the queued
    # interactive work reaches its admission dispatch sooner — a 2048-token
    # chunk round is a ~2048-token head-of-line block on every admission
    # behind it.  0 disables (full-bucket rounds, the historical cadence).
    interactive_chunk_bucket: int = 0
    # Prompt-lookup speculative decoding (serving/spec.py): draft length per
    # verify pass; 0 disables.  Every sampling mode speculates — greedy by
    # argmax match (bit-identical), sampled (incl. top-k/top-p) by the
    # distribution-exact delta-draft rule.  Decode throughput rises toward
    # (spec_k+1)x when outputs quote their context (the diagnosis
    # workload: answers cite pod names / events / metric lines verbatim)
    # because a verify pass costs the same weight traffic as one decode
    # step.  Tradeoff: emission per call is data-dependent, so spec
    # dispatches reconcile the pipeline first (no decode dispatch-ahead).
    spec_k: int = 0
    # Verify rounds fused into one spec dispatch (device-side scan) — the
    # host-sync amortization knob, the spec analogue of decode_steps_per_iter.
    spec_rounds_per_iter: int = 4
    # Adaptive speculation: a spec dispatch serializes the pipeline and a
    # verify forward costs more than a fused step, so near the acceptance
    # floor speculation loses to the fused path.  The engine tracks an EMA
    # of EMITTED tokens per lane-round — accepted drafts plus the one
    # correction/bonus token, so the metric's floor is 1.0 even with zero
    # drafts accepted — and falls back to the fused path below this
    # threshold, re-probing with one spec dispatch every spec_probe_every
    # decode dispatches in case the workload turned quotable again.  The
    # default sits above the 1.0 floor (where fused wins) with margin for
    # the verify forward's extra cost over a fused step.
    spec_min_accept: float = 1.2
    spec_probe_every: int = 32
    # History window for n-gram matching, per lane (tokens; rounded down to
    # the per-seq capacity).  [max_slots, cap] int32 is KBs, not MBs.
    spec_hist_cap: int = 4096
    # --- resilience (docs/resilience.md) ------------------------------
    # Default time-to-live for requests still waiting in the pending
    # queue (seconds; 0 = none).  A request with its own deadline_s uses
    # that instead.  Queued work past its TTL fails at the next step()
    # instead of occupying the queue (and later KV pages) for a caller
    # that has long since timed out.
    queue_ttl_s: float = 0.0
    # Inflight watchdog: wall-clock budget for the oldest dispatched call
    # to become ready at reconcile time (seconds; 0 = disabled, block
    # forever as before).  On expiry the engine performs a pipeline
    # reset: in-flight results are dropped, affected slots are
    # recompute-requeued (bounded by max_requeues) and the engine keeps
    # serving instead of wedging on a stuck device dispatch.
    dispatch_timeout_s: float = 0.0
    # Recompute-requeue budget per request across pipeline resets;
    # exceeded -> the request fails with the reset cause.
    max_requeues: int = 2
    # Load shedding thresholds (0 = disabled).  should_shed() reports a
    # reason when the pending-queue token backlog or the admission-wait
    # EMA crosses its threshold; EngineService turns that into a
    # retriable OverloadedError at submit time.
    shed_queue_tokens: int = 0
    shed_slot_wait_s: float = 0.0
    # --- SLO classes (resilience/slo.py) ------------------------------
    # Voluntary class-ordered preemptions per step(): with no free slot
    # and a strictly higher-class request queued, the engine evicts the
    # lowest-class running lane (recompute-requeue, byte-exact resumption)
    # up to this budget.  0 disables voluntary eviction; page-pressure
    # eviction inside the decode path still runs.
    max_preemptions: int = 2
    # Brownout clamp on batch-class max_tokens applied at admission while
    # the ladder sits at DEGRADED or worse; 0 disables the clamp.
    brownout_batch_max_tokens: int = 64
    # --- TP collective overlap (parallel/overlap.py) ------------------
    # Decode-step collective schedule under a TP mesh.  "auto" (default):
    # the hand-staged reduce-scatter/all-gather program whenever
    # overlap_supported() clears the (cfg, mesh) — byte-identical to the
    # GSPMD reference, with the per-layer wire time hidden under the next
    # sub-block's weight streaming.  "on": require it (ValueError when
    # unsupported).  "off": always the GSPMD-auto psum program.
    tp_overlap: str = "auto"
    # --- tier-aware admission (ROADMAP item 2 / PR 9 ladder) ----------
    # What counts as KV headroom in should_shed()'s capacity clause:
    # "tier" (default) counts free device blocks PLUS prefix-cache blocks
    # a lossless host spill could reclaim (bounded by HostKVTier free
    # bytes), so admission tracks the capacity the eviction path can
    # actually deliver; "device" counts free device blocks only; "off"
    # disables the clause (pre-PR-12: rely on OutOfBlocks pushback).
    kv_admission: str = "tier"

    def __post_init__(self) -> None:
        # A configuration file hands a JSON list; the ladder is a tuple
        # (hashable, and never edited in place).
        self.prefill_buckets = tuple(self.prefill_buckets)


class _Slot:
    __slots__ = ("req", "blocks", "ctx_len", "generated", "pending_admit",
                 "inflight_decode", "first_token_time", "retired",
                 "cancel_requested", "prefill_pos", "prefilling",
                 "inflight_chunks", "abort_cause", "cached_uncounted")

    def __init__(self, req: GenerationRequest, blocks: list[int]):
        self.req = req
        self.blocks = blocks
        self.ctx_len = 0          # reconciled tokens in the KV cache
        self.generated: list[int] = []   # reconciled sampled tokens
        self.pending_admit = True        # first token not yet reconciled
        self.inflight_decode = 0         # decode steps dispatched, unreconciled
        self.first_token_time = 0.0
        self.retired = False
        self.cancel_requested = False
        # When set, retirement produces an error result with this cause
        # (deadline expiry, pipeline-reset give-up) instead of eos/length.
        self.abort_cause = ""
        # Long-prompt streaming admission: tokens dispatched so far and
        # whether more chunks remain (decode skips prefilling slots).
        self.prefill_pos = 0
        self.prefilling = False
        self.inflight_chunks = 0         # chunk calls dispatched, unreconciled
        # Prefix-cache tokens a streaming admission starts from; its first
        # chunk call reports them as ``cached_tokens`` and zeroes this.
        self.cached_uncounted = 0

    # -- predicted (dispatch-side) state --------------------------------

    @property
    def gen_pred(self) -> int:
        return (len(self.generated) + self.inflight_decode
                + (1 if self.pending_admit else 0))

    @property
    def ctx_pred(self) -> int:
        return self.ctx_len + self.inflight_decode

    @property
    def remaining_pred(self) -> int:
        return self.req.sampling.max_tokens - self.gen_pred


@dataclasses.dataclass
class _Inflight:
    kind: str                     # "admit" | "chunk" | "decode"
    call_id: int
    arr: Any                      # device array (async copy started)
    # admit: [(slot_idx, req)]; chunk: [(row, slot_idx, req)] final lanes;
    # decode: [(slot_idx, slot, steps_i)]
    lanes: list[tuple]
    # chunk: every slot touched by the call (inflight_chunks decrement).
    touched: list = dataclasses.field(default_factory=list)
    # Dispatch (monotonic, taken just before the program is entered): the
    # per-lane phase spans and ``engine.call`` begin here.
    t0: float = 0.0
    # The first moment the step thread saw the result ready (_call_ready,
    # or the return of the blocking fetch); ``engine.call`` ends here.
    t_ready: Optional[float] = None
    # Attributes of the call's ``engine.call`` span, counted where the call
    # is built (InferenceEngine._call_attrs); ``emitted`` joins at reconcile.
    span_attrs: dict = dataclasses.field(default_factory=dict)
    # Routing counts of a routed model's call (device array float32[4],
    # MOE_COUNTS), an output of the same program as ``arr``; else None.
    moe_counts: Any = None


# Every span the engine and its service record, with the attributes a reader
# may rely on (docs/observability.md, "Span catalog").  The benchmark's metric
# files name spans and attributes from here; tests/test_benchmark.py holds
# them to it, so a rename fails tier-1 and not a chip run.
SPAN_CATALOG: dict[str, tuple[str, ...]] = {
    "engine.maintenance": (),
    "engine.request": ("request_id", "class", "finish_reason", "tokens",
                       "ttft_s"),
    "engine.queue_wait": ("request_id", "class"),
    "engine.prefill": ("request_id", "class", "bucket", "lanes", "shared",
                       "constrained"),
    "engine.prefill_chunk": ("request_id", "class", "bucket", "lanes",
                             "constrained"),
    "engine.decode": ("request_id", "class", "steps", "emitted"),
    "engine.spec_decode": ("request_id", "class", "steps", "emitted",
                           "rounds"),
    "engine.preempt": ("request_id", "class", "tokens_folded"),
    "engine.requeue": ("request_id", "class", "cause", "requeues"),
    "engine.kv_spill": ("blocks",),
    "engine.kv_restore": ("request_id", "class", "tokens"),
    # The step thread's loop (InferenceEngine._phase).
    "engine.step": ("dispatched", "inflight"),
    "engine.step.schedule": (),
    "engine.step.admit": (),
    "engine.step.chunk": (),
    "engine.step.decode": (),
    "engine.step.wait_device": (),
    "engine.step.apply": (),
    "service.intake": (),
    "service.idle": ("merged",),
    "service.shed": ("request_id", "class", "reason", "tenant"),
    # One per device call (InferenceEngine._call_attrs).
    "engine.call": ("kind", "program", "call_id", "device_empty",
                    "kv_blocks", "kv_live_blocks", "kv_cached_blocks",
                    "kv_token_bytes",
                    # A description with recurrent layers (the state pool).
                    "state_live_lanes",
                    # The call's seconds at the head of the in-order device
                    # queue, and whether the step thread blocked on it
                    # (InferenceEngine._reconcile_one).
                    "device_s", "waited",
                    "sampler_filter", "steps", "lanes", "slots", "emitted",
                    "ctx_tokens",
                    "bucket", "rows", "prompts", "real_tokens",
                    "padded_tokens", "cached_tokens", "shared", "packed",
                    # Programs of a routed model only (MOE_COUNTS).
                    "moe_assignments", "moe_expert_layer_steps_hit",
                    "moe_expert_layer_steps", "moe_max_rows",
                    "moe_mean_rows",
                    # "stream" | "tiles" | "compiler": the form the call's
                    # program gave its expert products (ops/grouped.py).
                    "moe_product_form",
                    # ... that holds a share of its experts (the four above
                    # are then over the experts held).
                    "moe_assignments_all",
                    # Programs of a description with an indexer or window
                    # layers (SEL_COUNTS), and the form of their selected
                    # attention (ops/sparse.py: the mask form is the one
                    # there is).
                    "index_tokens", "sel_tokens", "window_tokens",
                    "attn_select_form",
                    # Admission and chunk calls of a description with
                    # recurrent layers: "kernel" | "xla", the form of their
                    # admission scan (ops/ssm.py:scan_form for Mamba-2's
                    # chunked scan, mamba1_scan_form for Mamba-1's).
                    "ssm_scan_form",
                    # Decode calls of a model with paged keys and values:
                    # the fused kernel's fetch groups over the lanes' cached
                    # pages, and those fetched as one copy an array
                    # (ops/pallas_attention.py:fetch_groups).
                    "kv_groups", "kv_run_groups"),
    "xla.compile": ("seconds", "program"),
}


# What a program of a routed model returns beside its result, summed on the
# device over its expert layers and steps (models/llama.py:_moe_mlp_routed):
# token-expert assignments computed, experts with at least one row, the
# fullest expert's rows, experts there were.
MOE_COUNTS = ("moe_assignments", "moe_expert_layer_steps_hit", "moe_max_rows",
              "moe_expert_layer_steps")
# The share-aware expert layer (models/llama.py:_moe_mlp_share) counts the
# four above over the experts this chip holds and adds the real tokens'
# assignments held or not (tokens x experts per token).
MOE_SHARE_COUNTS = MOE_COUNTS + ("moe_assignments_all",)


# What a program of a description with an indexer or window layers returns,
# summed on the device over those layers and the call's steps
# (models/llama.py:_sel_counts): index keys scored (every cached token of a
# live lane, an indexed layer), keys the selection kept (at a decode step the
# sum of the keep mask the attention kernel was handed; at admission
# min(position + 1, index_topk) a query), rows the window layers' kernel was
# told to read (min(context, window) a lane).
SEL_COUNTS = ("index_tokens", "sel_tokens", "window_tokens")


def _sum_counts(stats: Optional[list], sel: Optional[list]) -> tuple:
    """One call's (or step's) device counts, a vector a group: the expert
    layers' summed (``stats``), then the window and indexed layers'
    (``sel``); a group the description has not (None) is left out."""
    return tuple(jnp.sum(jnp.stack(parts), axis=0)
                 for parts in (stats, sel) if parts is not None)


def _with_counts(outs: tuple, stats: Optional[list],
                 sel: Optional[list] = None) -> tuple:
    """A program's outputs, with the call's device counts last, an output a
    group (``_sum_counts``; a dense model's programs return exactly what
    they always did)."""
    return (*outs, *_sum_counts(stats, sel))


class _CountedProgram:
    """A jitted program of a description that counts on the device (expert
    layers, window or indexed layers).  Its last outputs are the call's
    counts, one a group (``_sum_counts``): they are set aside for the
    ``engine.call`` span the dispatch is about to build
    (``InferenceEngine._take_moe_counts``), and the caller gets the outputs
    every program of its family returns."""

    def __init__(self, jitted, engine: "InferenceEngine") -> None:
        self._jitted = jitted
        self._engine = engine

    def __call__(self, *args):
        outs = self._jitted(*args)
        groups = self._engine._count_names
        self._engine._moe_counts = dict(zip(groups, outs[-len(groups):]))
        return tuple(outs[:-len(groups)])

    def __getattr__(self, name):      # lower, _cache_size, ...
        return getattr(self._jitted, name)


# The phases of the step thread's loop, in the order a turn takes them.
LOOP_PHASES = (
    "service.intake", "service.idle", "engine.step", "engine.step.schedule",
    "engine.step.admit", "engine.step.chunk", "engine.step.decode",
    "engine.step.wait_device", "engine.step.apply",
)


class _PhaseClock:
    """Where the step thread's time goes, phase by phase.

    ``InferenceEngine._phase(name)`` opens a phase for a with-block.  Phases
    never overlap: one opened inside another (a reconcile inside
    ``_dispatch_decode``) pauses the outer one until it closes, so
    ``seconds`` adds up to the thread's time in phases and no phase is
    counted twice.  This class keeps only that clock — two
    ``time.monotonic()`` calls a phase, no allocation — and is the one
    shared object every phase of an engine whose loop is not traced
    returns; :class:`_PhaseTrace` adds the spans.
    """

    __slots__ = ("seconds", "_names", "_t0")

    def __init__(self) -> None:
        # Every phase from the start: a scrape never meets a new key.
        self.seconds: dict[str, float] = dict.fromkeys(LOOP_PHASES, 0.0)
        self._names: list[str] = []    # open phases, innermost last
        self._t0: list[float] = []     # when each last (re)started

    def begin(self, name: str, container: bool = False,
              merge: bool = False) -> "_PhaseClock":
        now = time.monotonic()
        if self._names:
            self._bank(now)
        self._names.append(name)
        self._t0.append(now)
        return self

    def _bank(self, now: float) -> None:
        self.seconds[self._names[-1]] += now - self._t0[-1]

    def set_attrs(self, **attrs) -> None:
        """Attributes for the innermost open phase's span (none here)."""

    def __enter__(self) -> "_PhaseClock":
        return self

    def __exit__(self, exc_type, exc, tb) -> bool:
        now = time.monotonic()
        self._bank(now)
        self._names.pop()
        self._t0.pop()
        if self._names:
            self._t0[-1] = now
        return False


class _PhaseTrace(_PhaseClock):
    """The clock of an engine whose loop context is sampled: every phase is
    also a :class:`Tracer` span and a ``jax.profiler.TraceAnnotation`` of
    the same name, so a profiler capture shows the host phases on the
    device trace's own clock.

    A paused phase's span ends where the inner one begins and a new span of
    its name opens when it resumes, so spans never overlap either — except
    a ``container`` (``engine.step``), which stays open around its children
    and is their parent.  ``merge`` phases (``service.idle``, waited in
    50 ms slices) extend the span before them instead of adding one.
    Spans set the thread's current context, which parents ``xla.compile``.
    """

    __slots__ = ("_tracer", "_root", "_marks")

    class _Mark:
        """One open phase: what it is, and its span scope and annotation
        while it is not paused."""

        __slots__ = ("name", "container", "merge", "scope", "ann")

        def __init__(self, name: str, container: bool, merge: bool) -> None:
            self.name, self.container, self.merge = name, container, merge
            self.scope = self.ann = None

    def __init__(self, tracer, root) -> None:
        super().__init__()
        self._tracer = tracer
        self._root = root
        self._marks: list[_PhaseTrace._Mark] = []  # beside _names

    def begin(self, name: str, container: bool = False,
              merge: bool = False) -> "_PhaseTrace":
        if self._marks:
            self._close(self._marks[-1], (None, None, None), final=False)
        super().begin(name)
        self._marks.append(self._Mark(name, container, merge))
        self._open(self._marks[-1])
        return self

    def _open(self, mark: "_PhaseTrace._Mark") -> None:
        if mark.ann is not None:
            return  # a container resuming: it never closed
        if not mark.merge:
            parent = next((m.scope.context for m in reversed(self._marks[:-1])
                           if m.container), self._root)
            mark.scope = self._tracer.span(mark.name, parent=parent)
            mark.scope.__enter__()
        mark.ann = jax.profiler.TraceAnnotation(mark.name)
        mark.ann.__enter__()

    def _close(self, mark: "_PhaseTrace._Mark", exc: tuple,
               final: bool) -> None:
        if mark.container and not final:
            return
        mark.ann.__exit__(*exc)
        if mark.merge:
            self._tracer.record_merged(mark.name, self._t0[-1],
                                       time.monotonic(), self._root)
        else:
            mark.scope.__exit__(*exc)
        mark.scope = mark.ann = None

    def set_attrs(self, **attrs) -> None:
        scope = self._marks[-1].scope
        if scope is not None:
            scope.span.attrs.update(attrs)

    def __exit__(self, exc_type, exc, tb) -> bool:
        self._close(self._marks.pop(), (exc_type, exc, tb), final=True)
        super().__exit__(exc_type, exc, tb)
        if self._marks:
            self._open(self._marks[-1])
        return False


# What the calling thread is doing for the compile listener: the program it
# is about to run (set around every program call of the engine).
_calling = threading.local()
_compile_listener_on = False


def _on_compile(event: str, duration: float, **_kw) -> None:
    """``jax.monitoring`` listener: a backend compile becomes an
    ``xla.compile`` span under the compiling thread's current context — on
    the step thread that is the phase that triggered it."""
    if "backend_compile" not in event:
        return
    tracer = get_tracer()
    ctx = tracer.current()
    if ctx is None or not ctx.sampled:
        return
    now = time.monotonic()
    attrs = {"seconds": duration}
    program = getattr(_calling, "program", "")
    if program:
        attrs["program"] = program
    tracer.record("xla.compile", now - duration, now, ctx, attrs=attrs)


def _listen_for_compiles() -> None:
    """Register :func:`_on_compile` once in this process (JAX keeps
    listeners for its lifetime)."""
    global _compile_listener_on
    if not _compile_listener_on:
        _compile_listener_on = True
        jax.monitoring.register_event_duration_secs_listener(_on_compile)


class _StuckPayload:
    """Wraps a dispatched device payload so it never reports ready — the
    deterministic CPU stand-in for a wedged device call (fault point
    ``decode_stuck``).  Conversion raises too, so a run with the watchdog
    disabled fails loudly through the reconcile-reset path instead of
    silently reading the real array."""

    def __init__(self, inner: Any):
        self.inner = inner

    def is_ready(self) -> bool:
        return False

    def __array__(self, *args, **kwargs):
        raise FaultError("decode_stuck")

    def __iter__(self):
        raise FaultError("decode_stuck")


# Sink signature: (request_id, new_token_ids, result_or_none).  ``result`` is
# set exactly once per request, when it completes (or errors); new tokens are
# delivered as they are reconciled, including the EOS token.
TokenSink = Callable[[str, list[int], Optional[GenerationResult]], None]


class InferenceEngine:
    """Single-process engine over jitted batched-prefill + fused-decode steps.

    When ``mesh`` is given, params and KV pages are GSPMD-sharded (TP over the
    ``model`` axis) and the same jitted functions run multi-chip — XLA inserts
    the collectives from the sharding annotations.

    Not thread-safe: one thread owns the engine (see serving/service.py for
    the concurrent front-end).
    """

    def __init__(
        self,
        cfg: ModelConfig,
        params: Any,
        engine_cfg: EngineConfig | None = None,
        tokenizer=None,
        mesh=None,
        eos_id: Optional[int] = None,
        attn_impl=None,
        seed: int = 0,
        host_kv_tier: Optional[HostKVTier] = None,
    ):
        self.cfg = cfg
        self.ecfg = engine_cfg or EngineConfig()
        self.tokenizer = tokenizer
        self.eos_id = eos_id if eos_id is not None else (
            tokenizer.eos_id if tokenizer is not None else -1
        )
        self.mesh = mesh
        self.token_sink: Optional[TokenSink] = None

        ec = self.ecfg
        # Resident-KV representation (kv_tier rung 1), resolved before any
        # pool allocation or program build: ``kv_quant`` is "" for the
        # model-dtype oracle pool and "int8"/"fp8" for the quantized tier.
        kvd = os.environ.get("K8SLLM_KV_DTYPE", ec.kv_dtype) or "auto"
        if kvd in ("auto", "fp16", "bf16", "none"):
            self.kv_quant = ""
        elif kvd in ("int8", "fp8"):
            self.kv_quant = kvd
        else:
            raise ValueError(
                f"unknown kv_dtype {kvd!r} (auto | int8 | fp8)")
        # What is not built for this description is refused here, with the
        # reason, before any pool or program exists (never a hidden
        # fallback): latent pages have no kv-head axis to shard, no scale
        # planes, no host-tier row format and no KVX1 geometry; the verify
        # pass has no latent form; the expert layer beside a shared MLP has
        # no mesh schedule; recurrent state has no snapshot (prefix cache,
        # host tier, KVX1), no roll-back (verify), no continuation (chunked
        # prefill) and no mesh layout.
        self._unbuilt = self._unbuilt_reason(cfg)
        # Something is kept a decode lane (recurrent state, a window-bounded
        # store): prompts are admitted whole into the lanes a call names.
        self._recurrent = cfg.lane_state
        # Such a description's admission round is ONE packed call, of at
        # most the rung that holds two sequences of the largest bucket (which
        # covers a sequence here: nothing chunks) — the least bound under
        # which any two prompts still share a call.  The prompt that would
        # pass it leads the next step's round.  That bounds the largest
        # program's temporaries where prompts are thousands of tokens, and
        # evens out what falls between two decode calls of a running lane.
        self._round_tokens = (self._token_rung(2 * ec.prefill_buckets[-1])
                              if self._recurrent else 0)
        # The form of selected attention (ops/sparse.py): the mask form is
        # the one there is.
        self._select_form = "mask" if cfg.latent and cfg.index_topk else ""
        if self._unbuilt:
            cap = min(ec.max_blocks_per_seq, ec.num_blocks - 1) * ec.block_size
            for what, asked in (
                    (f"chunked prefill (a sequence's {cap} tokens exceed the "
                     f"largest prefill bucket, {max(ec.prefill_buckets)})",
                     self._recurrent and cap > max(ec.prefill_buckets)),
                    ("a mesh", mesh is not None),
                    ("tp_overlap='on'", ec.tp_overlap == "on"),
                    (f"kv_dtype={self.kv_quant!r}", bool(self.kv_quant)),
                    ("host_spill_bytes > 0 / a host KV tier",
                     ec.host_spill_bytes > 0 or host_kv_tier is not None),
                    (f"spec_k={ec.spec_k}", ec.spec_k > 0)):
                if asked:
                    raise ValueError(
                        f"{cfg.name}: {what} is not built for "
                        f"{self._unbuilt} (ROADMAP Queue 2)")
        # Prefill-family attention path, resolved before the bucket ladder
        # is frozen (and before the mesh seq-divisibility check below sees
        # it): the flash kernel's geometry gates live in
        # ops/attention.py:select_prefill_impl; None = dense XLA oracle.
        from k8s_llm_monitor_tpu.ops.attention import select_prefill_impl
        self._prefill_attn = select_prefill_impl(
            cfg=cfg, mesh=mesh, mode=ec.prefill_path,
            kv_quant=self.kv_quant)
        self.prefill_path = ("flash" if self._prefill_attn is not None
                             else "dense")
        if self._prefill_attn is not None:
            # Cash in the flash win: long prompts chunk in 4096/8192-token
            # rounds instead of 2048 — fewer chunk rounds per prompt at the
            # same pool bytes.  Flash-gated because the dense path would
            # materialize [B, H, S, T] float32 score tensors at these S;
            # capacity-capped so small engines (tests, traceguard) keep
            # their ladders byte-for-byte unchanged.
            cap = min(ec.max_blocks_per_seq,
                      ec.num_blocks - 1) * ec.block_size
            extra = tuple(b for b in (4096, 8192)
                          if b > max(ec.prefill_buckets) and b <= cap)
            if extra:
                ec = dataclasses.replace(
                    ec, prefill_buckets=tuple(ec.prefill_buckets) + extra)
                self.ecfg = ec
        pages = llama.init_kv_pages(cfg, ec.num_blocks, ec.block_size,
                                    kv_quant=self.kv_quant,
                                    state_lanes=ec.max_slots)
        # Sequence-sharded prefill (SURVEY §7 step 5): on a mesh with a
        # nontrivial ``seq`` axis, prefill/chunk token batches are placed
        # sharded over ``seq`` — GSPMD then splits the per-position matmul
        # FLOPs across the axis (each device embeds/projects its sequence
        # slice, all-gathers chunk K/V for attention, and the page scatter
        # reassembles) so ONE long prompt's ingestion spreads over chips,
        # e.g. mesh_shape "1,2,4" on a v5e-8.  Decode is untouched: its
        # [B, 1] queries have no sequence axis to split.
        self._tok_sharding = None
        if mesh is not None:
            from jax.sharding import NamedSharding
            from k8s_llm_monitor_tpu.parallel.sharding import (
                kv_pages_partition_specs,
                param_partition_specs,
            )

            seq_deg = mesh.shape.get("seq", 1)
            if seq_deg > 1:
                from jax.sharding import PartitionSpec

                for b in ec.prefill_buckets:
                    if b % seq_deg:
                        raise ValueError(
                            f"prefill bucket {b} is not divisible by the "
                            f"mesh seq axis ({seq_deg}); choose bucket "
                            f"sizes that split evenly")
                self._tok_sharding = NamedSharding(
                    mesh, PartitionSpec(None, "seq"))

            pspecs = param_partition_specs(params)
            params = jax.tree.map(
                lambda x, s: jax.device_put(x, NamedSharding(mesh, s)),
                params, pspecs,
            )
            kvspecs = kv_pages_partition_specs(
                pages, mesh, num_kv_heads=cfg.num_kv_heads)
            pages = llama.KVPages(
                k=[jax.device_put(x, NamedSharding(mesh, s))
                   for x, s in zip(pages.k, kvspecs.k)],
                v=[jax.device_put(x, NamedSharding(mesh, s))
                   for x, s in zip(pages.v, kvspecs.v)],
                # Scale leaves shard their kv-heads axis exactly when the
                # pages' fused lane dim does (SpecLayout.kv_scales).  An
                # unquantized pool keeps the EMPTY-TUPLE containers from
                # init_kv_pages — an empty list here is a different
                # treedef from what prefill/decode return, so the first
                # dispatch would silently fork a second variant of every
                # program that takes pages.
                k_scale=[jax.device_put(x, NamedSharding(mesh, s))
                         for x, s in zip(pages.k_scale, kvspecs.k_scale)]
                if pages.quantized else (),
                v_scale=[jax.device_put(x, NamedSharding(mesh, s))
                         for x, s in zip(pages.v_scale, kvspecs.v_scale)]
                if pages.quantized else (),
            )
        self.params = params
        self.pages = pages
        self.allocator = BlockAllocator(ec.num_blocks, ec.block_size,
                                        table_blocks=ec.max_blocks_per_seq)
        # No lookup for a description with a recurrent layer: a cached
        # block holds a prefix's keys and values, and nothing holds the
        # recurrent state at its end, so a hit could not be continued.
        self.prefix_cache: Optional[PrefixCache] = (
            PrefixCache(self.allocator, ec.prefix_cache_entries,
                        max_tenant_share=ec.kv_max_tenant_share)
            if ec.prefix_cache_entries > 0 and not self._recurrent else None)
        # Cold-burst shared-prefix dedup: requests whose admission waited
        # for an in-flight lane to publish their prefix.
        self.prefix_deferrals = 0
        # Host-RAM spill tier (kv_tier rung 2).  A caller-provided tier
        # (the supervisor's engine_factory closes over one) survives engine
        # rebuilds, so spilled prefixes outlive a crash-recovery cycle.
        if host_kv_tier is None and ec.host_spill_bytes > 0:
            host_kv_tier = HostKVTier(ec.host_spill_bytes,
                                      max_tenant_share=ec.kv_max_tenant_share)
        self.host_kv_tier = host_kv_tier
        # Rehydration scatter programs, one per (leaf dtype, padded row
        # count): leaf.at[idx].set(rows) with donated leaf, so a restore
        # rebinds page leaves in place without changing treedef/sharding.
        self._tier_write_cache: dict = {}

        if attn_impl is None:
            from k8s_llm_monitor_tpu.ops.attention import select_decode_impl
            # Decode path: the fused RoPE+append+attention kernel on a
            # compatible single TPU chip; under a GSPMD mesh the split
            # kernel runs per-shard via shard_map
            # (ops/attention.py:make_tp_paged_attention) when the KV heads
            # divide the TP degree; otherwise the XLA gather path
            # partitions automatically.  A quantized pool routes to the
            # fused-quant kernel or the gather/dequant reference
            # (select_decode_impl kv_quant gate).
            attn_impl = select_decode_impl(cfg=cfg, mesh=mesh,
                                           mode=ec.decode_path,
                                           kv_quant=self.kv_quant)
        self._attn_impl = attn_impl
        # The recurrent layers' one-step state update, of their mixer's
        # kind: the Pallas kernel on the pool in place on a TPU, its XLA
        # form elsewhere.
        from k8s_llm_monitor_tpu.ops.ssm import select_ssm_update
        self._ssm_update = select_ssm_update(
            mixer=cfg.state_mixer or "mamba2")
        # The indexer's decode-step scores over the index-key pages, picked
        # as the latent attention beside them is.
        from k8s_llm_monitor_tpu.ops.attention import select_index_scores_impl
        self._index_scores = select_index_scores_impl(mode=ec.decode_path)
        # "fused" | "pallas" | "gather" — surfaced in /metrics.
        if self.kv_quant and llama.is_fused_quant_decode_impl(attn_impl):
            self.decode_path = "fused"
        elif self.kv_quant:
            # Quantized pool without the quant kernel: decode_step runs its
            # gather/dequant branch regardless of the impl handed in.
            self.decode_path = "gather"
        elif llama.is_fused_decode_impl(attn_impl):
            self.decode_path = "fused"
        elif getattr(attn_impl, "__name__", "") in (
                "paged_decode_attention", "latent_decode_attention"):
            self.decode_path = "gather"
        else:
            self.decode_path = "pallas"
        # TP collective overlap: swap the GSPMD-auto decode program for the
        # hand-staged reduce-scatter/all-gather schedule
        # (parallel/overlap.py).  The step is built once here and captured
        # by _step_core, so the scan programs and their donation/caching
        # behavior are untouched — overlap-on vs overlap-off differ only
        # in the traced layer body.
        self._overlap_step = None
        self.tp_overlap = False
        overlap_mode = ec.tp_overlap
        if overlap_mode not in ("auto", "on", "off"):
            raise ValueError(
                f"unknown tp_overlap {overlap_mode!r} (auto | on | off)")
        if overlap_mode != "off":
            from k8s_llm_monitor_tpu.parallel.overlap import (
                make_overlap_decode_step,
                overlap_supported,
            )

            why_not = overlap_supported(cfg, mesh, params=self.params)
            if not why_not:
                self._overlap_step = make_overlap_decode_step(
                    mesh, cfg, self.params, self.pages,
                    attn_path=self.decode_path)
                self.tp_overlap = True
            elif overlap_mode == "on":
                raise ValueError(
                    f"tp_overlap=on but the overlap schedule cannot serve "
                    f"this (cfg, mesh): {why_not}")
            elif mesh is not None and mesh.shape.get("model", 1) > 1:
                logger.warning("tp_overlap=auto: staying on the GSPMD "
                               "schedule (%s)", why_not)
        # Multi-query attention for the speculative verify pass (Pallas
        # kernel on compatible single-chip TPU; XLA gather otherwise).
        # Quantized pools drop the dedicated verify kernel: llama's
        # prefill/verify gather branch dequantizes in-program instead
        # (models/llama.py _prefill_impl quant gate).
        if self.ecfg.spec_k > 0 and self._prefill_attn is not None:
            # Flash prefill serves verify too (identical geometry contract,
            # all-positions unembed) — including quantized pools, whose
            # scale planes ride as kwargs.  This lifts the historical
            # "quant drops the verify kernel" restriction above.
            self._verify_impl = self._prefill_attn
        elif self.ecfg.spec_k > 0 and not self.kv_quant:
            from k8s_llm_monitor_tpu.ops.attention import select_verify_impl

            self._verify_impl = select_verify_impl(
                cfg=cfg, mesh=mesh,
                max_table_tokens=ec.max_blocks_per_seq * ec.block_size)
        else:
            self._verify_impl = None
        # Captured by the prefill closures below; None keeps llama's
        # dense branches (in-flight attention / gather_pages).
        prefill_attn = self._prefill_attn
        # A routed model's programs return the call's routing counts as
        # their last output (MOE_COUNTS); a dense model's return what they
        # always did: ``_stats()`` is None and ``_with_counts`` adds nothing.
        self._routed = cfg.expert_layers > 0
        # Window and indexed layers count on the device too (SEL_COUNTS), a
        # group of the same output beside the routing counts.
        self._sel_counted = bool(cfg.latent and (cfg.index_topk
                                                 or cfg.sliding_window))
        self._count_names = {
            **({"moe": MOE_SHARE_COUNTS if cfg.expert_share else MOE_COUNTS}
               if self._routed else {}),
            **({"sel": SEL_COUNTS} if self._sel_counted else {})}
        self._moe_counts = None
        routed = self._routed
        sel_counted = self._sel_counted

        def _stats() -> Optional[list]:
            return [] if routed else None

        def _sel() -> Optional[list]:
            return [] if sel_counted else None

        # A fresh admission call (no lane shares a cached prefix) takes the
        # packed form on one chip: ``tokens [T]`` is the call's prompts end
        # to end and ``seg`` = (offset [R], lengths [R]), R =
        # max_prefills_per_step, so what is computed per token is computed
        # for the call's tokens and not for rows x bucket
        # (llama.prefill_packed).  Over a mesh it keeps the row form,
        # ``tokens [P, bucket]`` and ``seg`` = (lengths [P],): the
        # sequence-parallel split and the sharded flash kernel work on rows.
        self._packed_prefill = packed = mesh is None
        top_bucket = ec.prefill_buckets[-1]

        recurrent = self._recurrent

        def _fresh_prefill(params, tokens, seg, pages, tables, stats,
                           sel=None):
            # A description with recurrent layers: ``seg`` ends with the
            # state-pool lane of each row (its slot; max_slots = none).
            kw = {"lanes": seg[-1]} if recurrent else {}
            if sel is not None:
                kw["sel_stats"] = sel
            seg = seg[:-1] if recurrent else seg
            if packed:
                return llama.prefill_packed(
                    params, cfg, tokens, *seg, pages, tables,
                    row_len=min(tokens.shape[0], top_bucket),
                    attn_impl=prefill_attn, moe_stats=stats, **kw)
            return llama.prefill(
                params, cfg, tokens, *seg, pages, tables,
                attn_impl=prefill_attn, moe_stats=stats, **kw
            )

        def _prefill_sample_fn(params, tokens, seg, pages, tables,
                               temp, topk, topp, rng):
            stats, sel = _stats(), _sel()
            logits, pages = _fresh_prefill(params, tokens, seg, pages,
                                           tables, stats, sel)
            first = sample_tokens(
                rng, logits, temperature=temp, top_k=topk, top_p=topp
            )
            return _with_counts((first, pages), stats, sel)

        def _prefill_greedy_fn(params, tokens, seg, pages, tables):
            # Sort-free fast path for all-greedy admission rounds: skips the
            # [P, V] argsort nucleus filtering needs (V is 128k on the 8B
            # target — the sort costs more than the unembed).
            stats, sel = _stats(), _sel()
            logits, pages = _fresh_prefill(params, tokens, seg, pages,
                                           tables, stats, sel)
            return _with_counts((greedy_tokens(logits), pages), stats, sel)

        def _prefill_chunk_sample_fn(params, tokens, start, lengths, pages,
                                     tables, temp, topk, topp, rng):
            # Batched admission over cached prefixes: each lane ingests only
            # its unshared suffix (start = shared tokens, 0 for misses) and
            # samples its first token in the same program.
            stats = _stats()
            logits, pages = llama.prefill_chunk(
                params, cfg, tokens, start, lengths, pages, tables,
                attn_impl=prefill_attn, moe_stats=stats
            )
            first = sample_tokens(
                rng, logits, temperature=temp, top_k=topk, top_p=topp
            )
            return _with_counts((first, pages), stats)

        def _prefill_chunk_greedy_fn(params, tokens, start, lengths, pages,
                                     tables):
            stats = _stats()
            logits, pages = llama.prefill_chunk(
                params, cfg, tokens, start, lengths, pages, tables,
                attn_impl=prefill_attn, moe_stats=stats
            )
            return _with_counts((greedy_tokens(logits), pages), stats)

        def _prefill_sample_fsm_fn(params, tokens, seg, pages, tables,
                                   fstate, ftrans, temp, topk, topp, rng):
            # Grammar-constrained admission: mask the first-token logits by
            # each lane's FSM state (0 = FREE lane, unmasked) BEFORE the
            # shared sampler — greedy lanes take the argmax of the masked
            # logits inside sample_tokens, so constrained-greedy is exact.
            stats, sel = _stats(), _sel()
            logits, pages = _fresh_prefill(params, tokens, seg, pages,
                                           tables, stats, sel)
            masked = fsm_mask_logits(logits, fstate, ftrans)
            first = sample_tokens(
                rng, masked, temperature=temp, top_k=topk, top_p=topp
            )
            return _with_counts(
                (first, fsm_advance(fstate, ftrans, first), pages), stats,
                sel)

        def _prefill_chunk_sample_fsm_fn(params, tokens, start, lengths,
                                         pages, tables, fstate, ftrans,
                                         temp, topk, topp, rng):
            stats = _stats()
            logits, pages = llama.prefill_chunk(
                params, cfg, tokens, start, lengths, pages, tables,
                attn_impl=prefill_attn, moe_stats=stats
            )
            masked = fsm_mask_logits(logits, fstate, ftrans)
            first = sample_tokens(
                rng, masked, temperature=temp, top_k=topk, top_p=topp
            )
            return _with_counts(
                (first, fsm_advance(fstate, ftrans, first), pages), stats)

        def _place_fn(tok_state, first, idx):
            # Scatter freshly sampled first tokens into the device-resident
            # token buffer; padding lanes carry idx == max_slots and drop.
            return tok_state.at[idx].set(first, mode="drop")

        # pages are donated so the scatter-updates happen in place on device.
        self._prefill_sample = self._program(_prefill_sample_fn, (3,))
        self._prefill_greedy = self._program(_prefill_greedy_fn, (3,))
        self._prefill_chunk_sample = self._program(
            _prefill_chunk_sample_fn, (4,))
        self._prefill_chunk_greedy = self._program(
            _prefill_chunk_greedy_fn, (4,))
        self._prefill_sample_fsm = self._program(_prefill_sample_fsm_fn, (3,))
        self._prefill_chunk_sample_fsm = self._program(
            _prefill_chunk_sample_fsm_fn, (4,))
        self._place_tokens = jax.jit(_place_fn, donate_argnums=(0,))
        # The logits hook's programs (score_logits), built on first use.
        self._score_programs: dict[str, Any] = {}
        # Grammar-constrained decoding state (set_grammar): host TokenFSM,
        # its device transition table, and the device-resident per-lane FSM
        # state — data-dependent like _tok_state, so it must live on device
        # to survive dispatch-ahead.  Lane state 0 is FREE (unconstrained);
        # _place_fsm (re)writes lanes at admission, zeroing reused slots.
        self._grammar = None
        self._fsm_trans = None
        self._fsm_state = jnp.zeros((ec.max_slots,), jnp.int32)
        self._place_fsm = jax.jit(
            lambda f, v, idx: f.at[idx].set(v, mode="drop"),
            donate_argnums=(0,))
        # Fused-decode programs, built lazily per (n_steps, sampled).
        self._decode_cache: dict[tuple, Any] = {}

        # Speculative decoding state: per-lane token history for the n-gram
        # proposer.  Rows are (re)written whole at admission, then extended
        # in-program as tokens are accepted.
        if ec.spec_k > 0:
            H = min(self.capacity_tokens, ec.spec_hist_cap)
            self._hist = jnp.full((ec.max_slots, H), -1, jnp.int32)
            self._hist_place = jax.jit(
                lambda h, rows, idx: h.at[idx].set(rows, mode="drop"),
                donate_argnums=(0,))
        else:
            self._hist = None
            self._hist_place = None
        self.spec_tokens = 0         # tokens emitted by spec dispatches
        self.spec_verify_steps = 0   # verify forwards those tokens cost
        self.spec_lane_rounds = 0    # sum of active lanes over those forwards
        # Adaptive speculation state: per-request-class EMA of accepted
        # tokens per lane-round (serving/spec.py:AcceptanceEMA).  No
        # measurement yet -> speculate optimistically; a class whose EMA
        # stays under spec_min_accept has drafting auto-disabled (fused
        # path) except for a probe every spec_probe_every dispatches.
        self._spec_accept = AcceptanceEMA(floor=ec.spec_min_accept,
                                          probe_every=ec.spec_probe_every)

        self._rng = jax.random.PRNGKey(seed)
        self._tok_state = jnp.zeros((ec.max_slots,), jnp.int32)
        self._pending: collections.deque[GenerationRequest] = collections.deque()
        self._slots: list[Optional[_Slot]] = [None] * ec.max_slots
        self._results: dict[str, GenerationResult] = {}
        self._inflight: collections.deque[_Inflight] = collections.deque()
        self._next_call_id = 0
        # Blocks of retired slots still referenced by in-flight calls:
        # released once the tagged call reconciles.
        self._deferred_frees: list[tuple[int, list[int]]] = []
        self.steps = 0
        self.prefills = 0
        self.preemptions = 0
        self.preemptions_by_class: dict[str, int] = {}
        self.brownout_clamps = 0
        self._chunks_since_decode = 0
        # Deadline-aware chunk sizing (interactive_chunk_bucket): rounds
        # clamped because interactive work was queued, and the bucket the
        # most recent chunk round actually used (exporter gauge + tests).
        self.chunk_shrinks = 0
        self.last_chunk_bucket = 0
        # Resilience state (docs/resilience.md).  ``health`` is an optional
        # HealthMonitor attached by EngineService; the engine records
        # watchdog trips and dispatch outcomes into it directly so the
        # state machine sees events the moment they happen.
        self._faults = get_injector()
        self.health = None
        # Optional brownout-level source (callable -> int 0..2), attached
        # by EngineService; consulted host-side only, never traced.
        self.brownout = None
        self.dispatch_failures = 0
        self.consecutive_dispatch_failures = 0
        # True while the step thread is inside a jitted program call, and
        # when it last left one.  The call is asynchronous once compiled,
        # so a long stay means the program's first compile (about a minute
        # for a 7B program on the chip's host, and one step() can make
        # several).  The supervisor's stale-heartbeat detector reads both,
        # so compiling is not mistaken for a wedged loop.
        self.in_program_call = False
        self.last_program_call = 0.0
        self.watchdog_trips = 0
        self.deadline_expired = 0
        self.requeues = 0
        self.constrained_requests = 0
        # EMA of submit->admission wait; a shed signal when slots churn
        # slower than the arrival rate.
        self.slot_wait_ema_s = 0.0
        # Per-class admission-wait and TTFT EMAs (exporter gauges).  Keys
        # appear on first observation, so the exporter can NaN-mark
        # classes that never carried traffic instead of mixing populations.
        self.slot_wait_ema_by_class: dict[str, float] = {}
        self.ttft_ema_by_class: dict[str, float] = {}
        # prefill_bucket_rounds counts dispatched rounds per bucket size,
        # so the signals plane can see which buckets production actually
        # runs (the 4096/8192 rungs exist only on the flash path).
        self.prefill_bucket_rounds: dict[int, int] = {}
        # Counts taken where a device call is built (_call_attrs) and where
        # its result is applied (exporter counters; the same numbers ride
        # on each ``engine.call`` span).  slot-steps are max_slots x steps
        # of every decode call: all lanes compute every step, so
        # decode_tokens / decode_slot_steps is the share that was of use.
        self.calls_by_kind: dict[str, int] = {}
        # Device seconds by kind, summed as calls are reconciled
        # (_reconcile_one: each call's time at the head of the in-order
        # device queue), and the ready stamp of the last call reconciled.
        self.device_seconds: dict[str, float] = {}
        self._last_ready = 0.0
        self.decode_slot_steps = 0
        self.decode_tokens = 0
        self.prefill_tokens = {"real": 0, "padded": 0, "cached": 0}
        self.dispatch_on_empty_device = 0
        # Calls of a sampling program, by whether some sampling lane had
        # top-k or top-p on — the host's copy of the predicate on which
        # ops/sampling.py takes its rank filter.  Greedy programs count in
        # neither.
        self.sampler_filter_calls = {"on": 0, "off": 0}
        # Routing of a routed model's calls, summed as their results are
        # applied (exporter counters; the same numbers ride on each
        # ``engine.call`` span): token-expert assignments computed, experts
        # that had at least one row, experts there were (per layer and step).
        self.moe_totals = {"assignments": 0, "experts_hit": 0,
                           "expert_slots": 0}
        if cfg.expert_share:    # held or not; the three above: held
            self.moe_totals["assignments_all"] = 0
        # A routed model's calls by the form their program's expert products
        # took (ops/grouped.py:product_form on the call's shape, as
        # models/llama.py:_expert_rows asks it), and that form by the call's
        # tokens.
        self.moe_product_calls = {"stream": 0, "tiles": 0, "compiler": 0}
        self._moe_form_of: dict[int, str] = {}
        # Window and indexed layers' counts, summed as results are applied
        # (SEL_COUNTS; exporter counters), and the calls of a description
        # with an indexer by the form of their selected attention.
        self.sel_totals = dict.fromkeys(SEL_COUNTS, 0)
        self.attn_select_calls = {"mask": 0}
        # Admission and chunk calls of a description with recurrent layers
        # by the form of their admission scan (models/llama.py:scan_form on
        # the call's rows: Mamba-2's chunked scan or Mamba-1's selective
        # scan, kernel or XLA).
        self.ssm_scan_calls = {"kernel": 0, "xla": 0}
        self._scan_form_of: dict[int, str] = {}
        # Fused decode calls' fetch groups of 8 pages over the lanes' cached
        # pages (a model with paged keys and values), by whether the group's
        # block ids are consecutive, so the kernel fetches it as one copy
        # ("run") or page by page ("pages"): ops/pallas_attention.py:
        # fetch_groups on the tables each call is dispatched with.
        self.kv_page_groups = {"run": 0, "pages": 0}
        # Request-lifecycle histograms (observability/metrics.py): per-SLO
        # class, with exemplar trace ids, observed on the step thread only.
        # The exporter renders these as real Prometheus histograms.
        _lat = (0.025, 0.05, 0.1, 0.25, 0.5, 1.0, 2.5, 5.0, 10.0, 30.0)
        self.hist_ttft = ClassHistogram(_lat)
        self.hist_e2e = ClassHistogram(_lat)
        self.hist_queue_wait = ClassHistogram(_lat)
        # Tracing (observability/tracing.py): phase spans are recorded
        # host-side at dispatch/reconcile time against each request's
        # captured TraceContext — never inside a traced program.  Engine
        # maintenance work with no owning request (KV spill/restore)
        # records under a per-engine synthetic root span.
        self._tracer = get_tracer()
        self._flight = get_flight_recorder()
        self._maint_ctx = self._tracer.new_trace()
        # The same root carries the step thread's loop: ``engine.step`` and
        # its phases, ``engine.call``, the service's intake and idle.  They
        # are recorded only when this root is sampled; the seconds per
        # phase (``loop_seconds``) are kept either way.
        self._loop_sampled = bool(self._maint_ctx is not None
                                  and self._maint_ctx.sampled)
        if self._loop_sampled:
            t_now = time.monotonic()
            self._tracer.record(
                "engine.maintenance", t_now, t_now, self._maint_ctx,
                span_id=self._maint_ctx.span_id, parent_id="")
            self._phases: _PhaseClock = _PhaseTrace(self._tracer,
                                                    self._maint_ctx)
            _listen_for_compiles()
        else:
            self._phases = _PhaseClock()
        self.loop_seconds = self._phases.seconds

    @staticmethod
    def _unbuilt_reason(cfg: ModelConfig) -> str:
        """"" for a description every serving option is built for; else the
        part of it that some are not (``__init__`` and the KVX1 calls refuse
        those with it)."""
        parts = []
        if cfg.recurrent:
            mixer = {"mamba1": "Mamba-1", "mamba2": "Mamba-2"}[cfg.state_mixer]
            parts.append(f"recurrent state (a per-lane state pool of "
                         f"{mixer} layers)")
        if cfg.latent:
            parts.append("a latent (compressed-KV) pool")
        if cfg.layers_with("window"):
            parts.append("a window-bounded store (a ring a decode lane)")
        if cfg.latent and cfg.index_topk:
            parts.append("index-key pages (selected attention)")
        if any(cfg.layer_spec(i).mlp == "shared+routed"
               for i in range(cfg.num_layers)):
            parts.append("shared + routed expert layers")
        if cfg.experts_held:
            parts.append("an expert layer that holds a share of its experts")
        return " and ".join(parts)

    def _refuse_unbuilt(self, what: str) -> None:
        if self._unbuilt:
            raise ValueError(f"{self.cfg.name}: {what} is not built for "
                             f"{self._unbuilt} (ROADMAP Queue 2)")

    def _program(self, fn, donate: tuple):
        """``jax.jit(fn)`` with ``donate`` donated; for a description that
        counts on the device the program's last output (the counts by
        group) is set aside for the call's span (:class:`_CountedProgram`)."""
        jitted = jax.jit(fn, donate_argnums=donate)
        return _CountedProgram(jitted, self) if self._count_names else jitted

    def _take_moe_counts(self):
        """The device counts of the program call just made (device arrays
        by group, ``_sum_counts``), or None for a description with none."""
        counts, self._moe_counts = self._moe_counts, None
        for arr in (counts or {}).values():
            try:
                arr.copy_to_host_async()
            except AttributeError:
                pass
        return counts

    # ------------------------------------------------------------------
    # public API
    # ------------------------------------------------------------------

    @property
    def capacity_tokens(self) -> int:
        """Max cached tokens for one sequence (per-seq table cap and pool)."""
        ec = self.ecfg
        return min(ec.max_blocks_per_seq, ec.num_blocks - 1) * ec.block_size

    def _cap_request(self, req: GenerationRequest) -> None:
        """Enforce prompt_len + max_tokens <= capacity (submit-time truncation
        prevents the block-table overflow crash and the can_alloc livelock).
        Keeps the prompt *tail* — diagnosis prompts front-load boilerplate —
        and never produces a degenerate slice."""
        cap = self.capacity_tokens
        sp = req.sampling
        if sp.max_tokens >= cap:
            req.sampling = dataclasses.replace(sp, max_tokens=cap - 1)
            sp = req.sampling
        overflow = len(req.prompt_ids) + sp.max_tokens - cap
        if overflow > 0:
            req.prompt_ids = req.prompt_ids[overflow:]
            if req.orig_prompt_len >= 0:
                # Preempted fold being re-capped: the dropped tokens come off
                # the original-prompt prefix, not the generated tail.
                req.orig_prompt_len = max(0, req.orig_prompt_len - overflow)

    def set_grammar(self, fsm) -> None:
        """Install the :class:`~..diagnosis.grammar.TokenFSM` constrained
        requests decode against.  One grammar per engine (the verdict
        schema); the dense table moves to device once, and every program
        variant closes over nothing — the table is a runtime argument, so
        swapping grammars of the same shape costs no recompile."""
        if fsm.vocab_size > self.cfg.vocab_size:
            raise ValueError(
                f"grammar vocab {fsm.vocab_size} exceeds model vocab "
                f"{self.cfg.vocab_size}")
        if fsm.eos_id != self.eos_id:
            raise ValueError(
                f"grammar eos_id {fsm.eos_id} != engine eos_id {self.eos_id}")
        self._grammar = fsm
        self._fsm_trans = jnp.asarray(fsm.trans)

    def _fsm_entry(self, req: GenerationRequest) -> int:
        """FSM state for ``req``'s next sampled token: the grammar start
        state walked through any generated tokens folded back into the
        prompt by preemption / pipeline-reset requeue.  A fold that the
        grammar rejects (only possible if the grammar changed under a
        supervisor rebuild — a documented limitation) restarts from the
        grammar start state rather than silently dropping the constraint."""
        if not req.sampling.constrained or self._grammar is None:
            return 0
        gen = (req.prompt_ids[req.orig_prompt_len:]
               if req.orig_prompt_len >= 0 else [])
        state = self._grammar.walk(gen)
        return state if state > 0 else self._grammar.start

    def submit(self, req: GenerationRequest) -> None:
        if not req.prompt_ids:
            raise ValueError("empty prompt")
        if req.sampling.max_tokens < 1:
            raise ValueError("max_tokens must be >= 1")
        # Defense in depth: the trust boundary (service/HTTP) normalized
        # already, but a raw-engine caller must not smuggle an unvalidated
        # namespace into the digest seeds.
        req.tenant = normalize_tenant(req.tenant, default=DEFAULT_TENANT)
        if req.sampling.constrained:
            if self._grammar is None:
                raise ValueError(
                    "constrained sampling requires set_grammar() first")
            self.constrained_requests += 1
            # Guarantee the forced EOS is reachable within budget: the
            # grammar's longest accepted sequence bounds generation, so
            # raising max_tokens to it never produces more tokens — it only
            # prevents a mid-object "length" truncation.
            ml = self._grammar.max_len
            if ml > 0 and req.sampling.max_tokens < ml:
                req.sampling = dataclasses.replace(
                    req.sampling, max_tokens=ml)
        self._cap_request(req)
        self._pending.append(req)

    def submit_text(self, request_id: str, prompt: str,
                    sampling: SamplingParams | None = None) -> None:
        assert self.tokenizer is not None
        self.submit(GenerationRequest(
            request_id=request_id,
            prompt_ids=self.tokenizer.encode(prompt),
            sampling=sampling or SamplingParams(),
        ))

    def poll(self, request_id: str) -> Optional[GenerationResult]:
        return self._results.pop(request_id, None)

    def cancel(self, request_id: str) -> bool:
        """Stop generating for a request (client went away).

        Pending requests are failed immediately; an active slot is marked
        and retired at its next reconcile (its in-flight device steps finish
        but no new ones are dispatched).  Returns True if found."""
        for i, req in enumerate(self._pending):
            if req.request_id == request_id:
                del self._pending[i]
                self._fail_request(req, "cancelled")
                return True
        for s in self._slots:
            if s is not None and s.req.request_id == request_id:
                s.cancel_requested = True
                return True
        return False

    @property
    def has_work(self) -> bool:
        return (bool(self._pending) or bool(self._inflight)
                or any(s is not None for s in self._slots))

    @property
    def queue_depth(self) -> int:
        return len(self._pending)

    @property
    def queue_tokens(self) -> int:
        """Prompt-token backlog waiting for admission (shed signal)."""
        return sum(len(r.prompt_ids) for r in self._pending)

    def queue_tokens_by_class(self) -> dict[str, int]:
        """Prompt-token backlog per SLO class (fleet stats + class-aware
        shedding).  Only classes with queued work appear as keys."""
        out: dict[str, int] = {}
        for r in self._pending:
            out[r.slo_class] = out.get(r.slo_class, 0) + len(r.prompt_ids)
        return out

    @property
    def active_slots(self) -> int:
        return sum(1 for s in self._slots if s is not None)

    def admission_headroom_tokens(self) -> int:
        """KV capacity (tokens) admission may count on, per the
        ``kv_admission`` policy.

        ``device``/``off``: tokens the free device blocks cover.  ``tier``
        additionally counts prefix-cache blocks a LOSSLESS host spill
        could reclaim — ``evictable_blocks`` bounded by the host tier's
        free bytes — because that is exactly the capacity ``_ensure_free``
        can deliver without destroying cache content.  With no host tier
        configured there is nothing to spill to, so the tier bonus is 0
        (eviction would drop prefixes; the queue + OutOfBlocks pushback
        stay the arbiter, as before this knob existed).  Exported as the
        ``kv_admission_headroom_tokens`` gauge."""
        ec = self.ecfg
        free_blocks = self.allocator.free_blocks
        if (ec.kv_admission == "tier" and self.prefix_cache is not None
                and self.host_kv_tier is not None):
            evictable = self.prefix_cache.evictable_blocks()
            if evictable > 0:
                cfg = self.cfg
                pdtype = np.dtype(self.pages.k[0].dtype)
                blk_bytes = cfg.num_layers * page_slice_bytes(
                    cfg.num_kv_heads, cfg.head_dim_, ec.block_size,
                    pdtype.itemsize, scale_bytes=4 if self.kv_quant else 0)
                st = self.host_kv_tier.stats()
                host_free = max(st["max_bytes"] - st["bytes"], 0)
                free_blocks += min(evictable, host_free // max(blk_bytes, 1))
        return free_blocks * ec.block_size

    def should_shed(self, slo_class: str = DEFAULT_CLASS,
                    need_tokens: int = 0) -> str:
        """Non-empty reason when new work of ``slo_class`` should be shed
        (admission control): queue-token backlog or admission-wait EMA
        above the configured thresholds, or — when the caller passes the
        request's KV footprint as ``need_tokens`` — a footprint the
        tier-aware headroom cannot cover (``kv_admission`` policy).  The
        caller (EngineService.submit) turns this into a retriable
        ``OverloadedError``; the engine itself never rejects — by the time
        work reaches ``submit()`` the caller has already been told to back
        off.

        Shedding is class-ordered: a request is charged only for backlog
        of its own class and above (queued lower-class tokens would be
        admitted *after* it, so they are not load it waits behind), and no
        request is shed while strictly lower-class work is queued — that
        work sheds/evicts first, so ``interactive`` is never refused while
        ``batch`` waits.  With single-class traffic (everything at the
        default) this reduces exactly to the flat thresholds."""
        ec = self.ecfg
        rank = SLO_RANK.get(slo_class, SLO_RANK[DEFAULT_CLASS])
        by_class = self.queue_tokens_by_class()
        ahead = sum(t for c, t in by_class.items()
                    if SLO_RANK.get(c, SLO_RANK[DEFAULT_CLASS]) <= rank)
        lower_queued = any(
            t > 0 and SLO_RANK.get(c, SLO_RANK[DEFAULT_CLASS]) > rank
            for c, t in by_class.items())
        if lower_queued:
            return ""
        if 0 < ec.shed_queue_tokens <= ahead:
            return (f"queue token backlog {ahead} >= "
                    f"{ec.shed_queue_tokens} for class {slo_class}")
        if 0 < ec.shed_slot_wait_s <= self.slot_wait_ema_s:
            return (f"admission wait EMA {self.slot_wait_ema_s:.2f}s >= "
                    f"{ec.shed_slot_wait_s:.2f}s")
        # Capacity clause: checked after the class ordering above so that
        # queued-lower-class eviction/preemption gets first refusal — it
        # can free device blocks the headroom figure does not count.
        # "tier" only arms it when a host tier is actually configured:
        # without one the headroom figure would say nothing the legacy
        # queue + OutOfBlocks pushback does not already handle.
        capacity_armed = (ec.kv_admission == "device"
                          or (ec.kv_admission == "tier"
                              and self.host_kv_tier is not None))
        if need_tokens > 0 and capacity_armed:
            headroom = self.admission_headroom_tokens()
            if need_tokens > headroom:
                return (f"kv capacity: request needs {need_tokens} tokens, "
                        f"admission headroom is {headroom} "
                        f"(kv_admission={ec.kv_admission})")
        return ""

    def generate(self, prompts: list[list[int]],
                 sampling: SamplingParams | None = None) -> list[GenerationResult]:
        """Synchronous batch generation (runs the loop to completion)."""
        ids = [f"gen-{i}" for i in range(len(prompts))]
        for rid, p in zip(ids, prompts):
            self.submit(GenerationRequest(rid, list(p),
                                          sampling or SamplingParams()))
        while self.has_work:
            self.step()
        return [self._results.pop(rid) for rid in ids]

    def generate_text(self, prompt: str,
                      sampling: SamplingParams | None = None) -> str:
        assert self.tokenizer is not None
        res = self.generate([self.tokenizer.encode(prompt)], sampling)[0]
        return self.tokenizer.decode(res.token_ids)

    # ------------------------------------------------------------------
    # engine loop
    # ------------------------------------------------------------------

    def score_logits(self, prompt_ids: list[int], n_decode: int = 0, *,
                     tenant: str = DEFAULT_TENANT, hidden: bool = False,
                     selection: bool = False):
        """The model's logits as the engine computes them, instead of
        sampled tokens: float32 ``[1 + n_decode, vocab]`` — the row of the
        last prompt position, then one row per decode step, each step fed
        the argmax of the row before it (so row ``i``'s argmax is the token
        at position ``len(prompt_ids) + i``, and a reference forward over
        the prompt plus those tokens yields the same rows).

        The prompt goes through the engine's own prefill functions
        (``llama.prefill`` / ``prefill_chunk`` with the engine's prefill
        attention) into the engine's own pages under a real block table — a
        prefix-cache hit starts from the shared blocks, a prompt longer
        than the top bucket streams in chunks — then ``n_decode`` single
        steps run ``llama.decode_step`` with the attention impl the fused
        scan calls.  The blocks are freed afterwards and nothing is
        registered.  The engine must be idle (no queued or running
        request): call it on the step thread, through
        ``EngineService.call(lambda e: e.score_logits(ids, n))``.

        ``hidden=True`` returns ``(rows, states)``: ``states`` float32
        ``[layers + 1, len(prompt_ids) + n_decode, hidden]``, the residual
        stream of every position before each layer and after the last, as
        the engine's programs held it (the prefix cache is not consulted
        then: a cached position's states were never computed).  A reference
        that runs ONE layer on the engine's own input to it
        (``benchmarks/references``) is compared layer by layer with these —
        where whole-model logits of random weights are chaotic in the activation
        precision, one layer is not.

        ``selection=True`` (with ``hidden``; a description with an indexer)
        returns ``(rows, states, chosen)``: ``chosen[layer]`` = (scores
        float32, keep bool), both ``[S, S]`` over ``S = len(prompt_ids) +
        n_decode`` positions — the indexer's scores and the keys kept, as
        these programs' kernels computed them and as attention was handed
        them (row t: the prefill's for a prompt position, the decode step's
        after it; what lies past t is meaningless).
        """
        self._reconcile_all()
        if self.has_work:
            raise RuntimeError("score_logits needs an idle engine")
        ids = [int(t) for t in prompt_ids]
        L = len(ids)
        if L < 1 or L + n_decode + 1 > self.capacity_tokens:
            raise ValueError(
                f"prompt of {L} tokens + {n_decode} steps does not fit a "
                f"sequence's {self.capacity_tokens} cached tokens")
        cfg = self.cfg
        if selection and not (hidden and self._select_form
                              and L <= self.ecfg.prefill_buckets[-1]):
            raise ValueError(
                "selection=True goes with hidden=True, a description with an "
                "indexer and a prompt the largest prefill bucket covers")
        shared, start = ([], 0)
        if self.prefix_cache is not None and not hidden:
            shared, start = self.prefix_cache.lookup(ids, tenant=tenant)
        try:
            if not self._ensure_free(L + n_decode + 1 - start):
                raise OutOfBlocks(
                    f"no room for {L + n_decode + 1 - start} tokens")
            blocks = shared + self.allocator.alloc(L + n_decode + 1 - start,
                                                   at=len(shared))
        except BaseException:
            self.allocator.free(shared)
            raise
        progs = self._score_programs
        if not progs:
            attn, dec = self._prefill_attn, self._attn_impl
            # The engine is idle: the state of the scored sequence lives in
            # lane 0 of the state pool.
            lane0 = ({"lanes": jnp.zeros((1,), jnp.int32)}
                     if self._recurrent else {})

            def scored(fn, **kw):
                # (logits, pages, states [layers + 1, B, S, H] or None, each
                # indexed layer's (scores, keep) or None)
                def run(p, *args, want_hidden, want_selection=False):
                    states = [] if want_hidden else None
                    chosen = [] if want_selection else None
                    extra = {"selection": chosen} if want_selection else {}
                    logits, pages = fn(p, cfg, *args, hidden=states, **kw,
                                       **extra)
                    return logits, pages, (
                        None if states is None
                        else jnp.stack(states).astype(jnp.float32)), chosen
                return run

            static = ("want_hidden", "want_selection")

            progs["prefill"] = jax.jit(
                scored(llama.prefill, attn_impl=attn, **lane0),
                donate_argnums=(3,), static_argnames=static)
            progs["chunk"] = jax.jit(
                scored(llama.prefill_chunk, attn_impl=attn),
                donate_argnums=(4,), static_argnames=static)
            progs["decode"] = jax.jit(
                scored(llama.decode_step, attn_impl=dec,
                       ssm_update=self._ssm_update,
                       index_scores=self._index_scores, **lane0),
                donate_argnums=(3,), static_argnames=static)
        table = np.zeros((1, self.ecfg.max_blocks_per_seq), np.int32)
        table[0, :len(blocks)] = blocks
        table = jnp.asarray(table)
        top = self.ecfg.prefill_buckets[-1]
        rows, states = [], []
        S = L + n_decode
        chosen = ([(np.zeros((S, S), np.float32), np.zeros((S, S), bool))
                   for li in range(cfg.num_layers)
                   if cfg.latent_geometry(li).indexed] if selection else [])
        self.in_program_call = True
        try:
            pos, logits = start, None
            while pos < L:
                n = min(top, L - pos)
                toks = np.zeros((1, self._bucket(n)), np.int32)
                toks[0, :n] = ids[pos:pos + n]
                if pos == 0:
                    logits, self.pages, st, ch = progs["prefill"](
                        self.params, self._tokens_to_device(toks),
                        jnp.asarray([n], jnp.int32), self.pages, table,
                        want_hidden=hidden, want_selection=selection)
                    for (scores, keep), got in zip(chosen, ch or ()):
                        scores[:n, :n] = np.asarray(got[0][0, :n, :n])
                        keep[:n, :n] = np.asarray(got[1][0, :n, :n])
                else:
                    logits, self.pages, st, _ = progs["chunk"](
                        self.params, self._tokens_to_device(toks),
                        jnp.asarray([pos], jnp.int32),
                        jnp.asarray([n], jnp.int32), self.pages, table,
                        want_hidden=hidden)
                if hidden:
                    states.append(np.asarray(st[:, 0, :n]))
                pos += n
            rows.append(np.asarray(logits[0], np.float32))
            for i in range(n_decode):
                tok = jnp.asarray([int(np.argmax(rows[-1]))], jnp.int32)
                logits, self.pages, st, ch = progs["decode"](
                    self.params, tok, jnp.asarray([L + i], jnp.int32),
                    self.pages, table, want_hidden=hidden,
                    want_selection=selection)
                rows.append(np.asarray(logits[0], np.float32))
                if hidden:
                    states.append(np.asarray(st[:, 0]))
                for (scores, keep), got in zip(chosen, ch or ()):
                    scores[L + i] = np.asarray(got[0][0, :S])
                    keep[L + i] = np.asarray(got[1][0, :S])
        finally:
            self.in_program_call = False
            self.last_program_call = time.monotonic()
            self.allocator.free(blocks)
        if selection:
            layers = [li for li in range(cfg.num_layers)
                      if cfg.latent_geometry(li).indexed]
            return (np.stack(rows), np.concatenate(states, axis=1),
                    dict(zip(layers, chosen)))
        if hidden:
            return np.stack(rows), np.concatenate(states, axis=1)
        return np.stack(rows)

    def step(self) -> None:
        """One scheduler iteration: dispatch up to ``max_admission_rounds``
        batched prefills and one fused decode, then reconcile in-flight
        results down to the dispatch-ahead window (or fully, when there is
        nothing left to dispatch)."""
        with self._phase("engine.step", container=True) as step:
            with self._phase("engine.step.schedule"):
                self._enforce_deadlines()
                self._schedule_classes()
            dispatched = 0
            rounds = 0
            with self._phase("engine.step.admit"):
                while (rounds < self.ecfg.max_admission_rounds
                       and self._admit_round()):
                    rounds += 1
                    dispatched += 1
            with self._phase("engine.step.chunk"):
                chunked = self._dispatch_prefill_chunks()
            if chunked:
                dispatched += 1
                self._chunks_since_decode += 1
            if (not chunked or self._chunks_since_decode
                    >= self.ecfg.decode_every_n_chunk_rounds):
                with self._phase("engine.step.decode"):
                    decoded = self._dispatch_decode()
                if decoded:
                    dispatched += 1
                    self._chunks_since_decode = 0
            # Opportunistic drain: results the device already finished cost
            # no host wait, and every reconcile here frees slots/pages one
            # step earlier — admission and chunk prep in the NEXT step()
            # overlap with whatever is still running on device.
            while self._inflight and self._call_ready(self._inflight[0]):
                self._reconcile_one()
            if dispatched:
                while len(self._inflight) > self.ecfg.max_inflight:
                    self._reconcile_one()
            else:
                # Nothing dispatchable: drain so retirements/admissions
                # unblock.
                if self._inflight:
                    self._reconcile_one()
            step.set_attrs(dispatched=dispatched,
                           inflight=len(self._inflight))

    def _phase(self, name: str, container: bool = False,
               merge: bool = False) -> _PhaseClock:
        """Open one phase of the step thread's loop for a with-block (the
        ``engine.step*`` / ``service.*`` rows of the span catalog).  Its
        seconds always go to ``loop_seconds``; when the loop's root context
        is sampled it is a span and a profiler annotation too
        (:class:`_PhaseTrace`), otherwise the shared :class:`_PhaseClock`
        comes back and nothing is recorded or allocated."""
        return self._phases.begin(name, container, merge)

    def _device_empty(self) -> bool:
        """True when every call enqueued so far has finished: the call
        about to be built finds the device with nothing queued — a bubble
        the size of this call's host preparation."""
        return all(self._call_ready(c) for c in self._inflight)

    def _kv_census(self) -> dict[str, int]:
        """Who holds the pool: distinct blocks in live slots' tables, and
        blocks that only the prefix cache still references (allocated, in
        no live table and not awaiting a deferred free).  O(resident
        blocks), so only a sampled loop takes it."""
        live: set[int] = set()
        for s in self._slots:
            if s is not None:
                live.update(s.blocks)
        held = set(live)
        for _, blocks in self._deferred_frees:
            held.update(blocks)
        alloc = self.allocator
        used = alloc.num_blocks - 1 - alloc.free_blocks  # block 0 is null
        census = {"kv_blocks": alloc.num_blocks, "kv_live_blocks": len(live),
                  "kv_cached_blocks": max(0, used - len(held))}
        if self.cfg.latent:
            # What a cached token costs over all layers in this pool: the
            # page kind's own figure, beside the block counts it scales.
            census["kv_token_bytes"] = self.cfg.kv_token_bytes(
                self.pages.k[0].dtype.itemsize)
        if self.cfg.recurrent:
            # Lanes of the state pool a live slot owns (the pool's bytes
            # never change: gauges engine_state_pool_bytes / _lane_bytes).
            census["state_live_lanes"] = sum(s is not None
                                             for s in self._slots)
        return census

    def _call_attrs(self, kind: str, program: str, device_empty: bool,
                    sampler_filter: Optional[bool] = None,
                    **counts: int) -> dict:
        """Count one device call where it is built — the exporter's
        counters, always — and return the attributes of its ``engine.call``
        span (SPAN_CATALOG), which gain the pool census when the loop is
        sampled.  Call it after the program call succeeded and the slots
        are in place, with the call's id still ``_next_call_id``.
        ``sampler_filter``: whether any lane of a sampling program's call
        is ``SamplingParams.filtered``; None for a greedy program."""
        self.calls_by_kind[kind] = self.calls_by_kind.get(kind, 0) + 1
        if device_empty:
            self.dispatch_on_empty_device += 1
        if sampler_filter is not None:
            self.sampler_filter_calls[
                "on" if sampler_filter else "off"] += 1
            counts["sampler_filter"] = int(sampler_filter)
        if kind in ("admit", "chunk"):
            for key in self.prefill_tokens:
                self.prefill_tokens[key] += counts[f"{key}_tokens"]
            tokens = counts["padded_tokens"]
        else:
            self.decode_slot_steps += counts["slots"] * counts["steps"]
            tokens = counts["slots"]
        if self._routed:
            form = self._moe_form_of.get(tokens)
            if form is None:
                form = self._moe_form_of[tokens] = llama.expert_product_form(
                    self.cfg, self.params, tokens)
            self.moe_product_calls[form] += 1
            counts["moe_product_form"] = form
        if self._select_form:
            self.attn_select_calls[self._select_form] += 1
            counts["attn_select_form"] = self._select_form
        if self.cfg.recurrent and kind in ("admit", "chunk"):
            form = self._scan_form_of.get(counts["rows"])
            if form is None:
                form = self._scan_form_of[counts["rows"]] = llama.scan_form(
                    self.cfg, counts["rows"])
            self.ssm_scan_calls[form] += 1
            counts["ssm_scan_form"] = form
        if "kv_groups" in counts:
            self.kv_page_groups["run"] += counts["kv_run_groups"]
            self.kv_page_groups["pages"] += (counts["kv_groups"]
                                             - counts["kv_run_groups"])
        attrs = {"kind": kind, "program": program,
                 "call_id": self._next_call_id,
                 "device_empty": int(device_empty), **counts}
        if self._loop_sampled:
            attrs.update(self._kv_census())
        return attrs

    @staticmethod
    def _call_ready(call: _Inflight) -> bool:
        """True when reconciling ``call`` would not block on the device.
        The first True stamps ``call.t_ready``: every caller checks the
        calls oldest first, so the stamps keep the device's order."""
        if call.t_ready is not None:
            return True
        arrs = call.arr if isinstance(call.arr, tuple) else (call.arr,)
        try:
            ready = all(a.is_ready() for a in arrs)
        except AttributeError:  # non-jax payloads (tests with stub arrays)
            ready = True
        if ready:
            call.t_ready = time.monotonic()
        return ready

    def _reconcile_all(self) -> None:
        while self._inflight:
            self._reconcile_one()

    # -- deadlines / failure recovery -----------------------------------

    def _deadline_of(self, req: GenerationRequest, queued: bool) -> float:
        """Absolute monotonic deadline for ``req``; +inf when unbounded.
        A per-request deadline_s always applies; the config queue TTL only
        bounds time spent *waiting* (a running request already holds its
        pages — killing it at TTL would waste the work done)."""
        if req.deadline_s > 0:
            return req.submit_time + req.deadline_s
        if queued and self.ecfg.queue_ttl_s > 0:
            return req.submit_time + self.ecfg.queue_ttl_s
        return float("inf")

    def _enforce_deadlines(self) -> None:
        """Fail expired queued requests and abort expired running slots.
        Runs at the top of every step(); admission re-checks queued
        candidates so a request never spends KV pages after expiry."""
        now = time.monotonic()
        if self._pending:
            keep: collections.deque[GenerationRequest] = collections.deque()
            for req in self._pending:
                if now > self._deadline_of(req, queued=True):
                    self.deadline_expired += 1
                    self._fail_request(
                        req, f"deadline exceeded after "
                             f"{now - req.submit_time:.2f}s in queue")
                else:
                    keep.append(req)
            self._pending = keep
        for s in self._slots:
            if (s is not None and not s.retired and not s.cancel_requested
                    and now > self._deadline_of(s.req, queued=False)):
                self.deadline_expired += 1
                s.abort_cause = (f"deadline exceeded after "
                                 f"{now - s.req.submit_time:.2f}s "
                                 f"({len(s.generated)} tokens generated)")
                # Reuse the cancel path: no new dispatches; the slot
                # retires once its in-flight steps settle.
                s.cancel_requested = True

    def _record_dispatch_failure(self, exc: BaseException) -> None:
        self.dispatch_failures += 1
        self.consecutive_dispatch_failures += 1
        self._flight.note("dispatch_failure", error=repr(exc)[:200],
                          consecutive=self.consecutive_dispatch_failures)
        if self.health is not None:
            self.health.record_dispatch_failure()

    def _record_dispatch_ok(self) -> None:
        self.consecutive_dispatch_failures = 0
        if self.health is not None:
            self.health.record_dispatch_ok()

    def _note_admission_wait(self, req: GenerationRequest) -> None:
        """Track how long requests sit queued before winning a slot — the
        EMA backs the ``shed_slot_wait_s`` load-shedding signal; the
        per-class EMAs back the exporter's ``queue_wait_ms{class}``."""
        now = time.monotonic()
        wait = now - req.submit_time
        if self.slot_wait_ema_s == 0.0:
            self.slot_wait_ema_s = wait
        else:
            self.slot_wait_ema_s = (
                0.9 * self.slot_wait_ema_s + 0.1 * wait)
        prev = self.slot_wait_ema_by_class.get(req.slo_class)
        self.slot_wait_ema_by_class[req.slo_class] = (
            wait if prev is None else 0.9 * prev + 0.1 * wait)
        self.hist_queue_wait.observe(wait, req.slo_class, self._trace_id(req))
        self._span("engine.queue_wait", req.submit_time, now, req)

    # -- tracing helpers (observability/tracing.py) ----------------------

    @staticmethod
    def _trace_id(req: GenerationRequest) -> str:
        """Exemplar trace id for histograms ('' when untraced/unsampled)."""
        ctx = req.trace
        return ctx.trace_id if ctx is not None and ctx.sampled else ""

    def _span(self, name: str, t0: float, t1: float,
              req: GenerationRequest, status: str = "ok", **attrs) -> None:
        """Record one engine phase span under ``req``'s trace.  No-op for
        untraced or unsampled requests — the hot-path cost is one
        attribute check."""
        ctx = req.trace
        if ctx is None or not ctx.sampled:
            return
        attrs["request_id"] = req.request_id
        attrs["class"] = req.slo_class
        self._tracer.record(name, t0, t1, ctx, attrs=attrs, status=status)

    def _end_request_span(self, req: GenerationRequest, status: str,
                          **attrs) -> None:
        """Close the per-request root span (submit -> terminal outcome).
        Uses the context's own span/parent ids so the phase spans recorded
        along the way nest under it with no orphan parents."""
        ctx = req.trace
        if ctx is None or not ctx.sampled:
            return
        attrs["request_id"] = req.request_id
        attrs["class"] = req.slo_class
        self._tracer.record(
            "engine.request", req.submit_time, time.monotonic(), ctx,
            span_id=ctx.span_id, parent_id=ctx.parent_id,
            attrs=attrs, status=status)

    # -- SLO-class scheduling (resilience/slo.py) ------------------------

    def _brownout_level(self) -> int:
        """Current brownout ladder level; 0 when no controller attached.
        Host-side scheduling input only — never read inside a traced
        program."""
        if self.brownout is None:
            return 0
        try:
            return int(self.brownout())
        except Exception:  # noqa: BLE001 — a dying controller must not wedge the step loop
            return 0

    def _clamp_for_brownout(self, req: GenerationRequest) -> None:
        """At DEGRADED or worse, clamp batch-class generation budgets so
        bulk work stops monopolizing decode bandwidth.  Applied at
        admission — lanes already running keep their budget.  Constrained
        requests are exempt: the grammar's forced EOS needs its max
        accepting path reachable."""
        cap = self.ecfg.brownout_batch_max_tokens
        if (cap <= 0 or req.slo_class != "batch"
                or req.sampling.constrained
                or req.sampling.max_tokens <= cap
                or self._brownout_level() < 1):
            return
        req.sampling = dataclasses.replace(req.sampling, max_tokens=cap)
        self.brownout_clamps += 1

    def _eviction_victim(self, worse_than: int = -1) -> int:
        """Running lane to evict under pressure: lowest SLO class first,
        youngest within a class (so the oldest protected work always makes
        progress).  ``worse_than`` >= 0 restricts candidates to lanes
        strictly underranking it — voluntary preemption must only evict
        lanes a queued request outranks.  Cancelled lanes are skipped
        (preempting one would resurrect a request nobody is waiting for).
        Returns -1 when no lane qualifies."""
        best = -1
        best_key: tuple[int, float] | None = None
        for j, sl in enumerate(self._slots):
            if sl is None or sl.retired or sl.cancel_requested:
                continue
            r = SLO_RANK.get(sl.req.slo_class, SLO_RANK[DEFAULT_CLASS])
            if 0 <= worse_than < r or worse_than < 0:
                key = (r, sl.req.submit_time)
                if best_key is None or key > best_key:
                    best, best_key = j, key
        return best

    def _schedule_classes(self) -> None:
        """Class-priority scheduling, all host-side (nothing traced):
        stable-sort the pending queue by SLO rank (FIFO preserved within a
        class — preempted requests pushed to the queue head stay first in
        their class), then voluntarily evict lower-class running lanes
        while a strictly higher-class request waits with no free slot,
        bounded by ``max_preemptions`` per step."""
        self._sort_pending_by_class()
        budget = self.ecfg.max_preemptions
        preempted = 0
        while preempted < budget and self._pending:
            if any(s is None for s in self._slots):
                return  # a free slot exists; plain admission will fill it
            best = min(SLO_RANK.get(r.slo_class, SLO_RANK[DEFAULT_CLASS])
                       for r in self._pending)
            if self._eviction_victim(worse_than=best) < 0:
                return
            # Recompute-preemption requires reconciled lanes: the folded
            # prompt must contain every sampled token (byte-exactness).
            self._reconcile_all()
            if any(s is None for s in self._slots):
                continue  # the drain freed a slot; no eviction needed
            victim = self._eviction_victim(worse_than=best)
            if victim < 0:
                return
            try:
                self._faults.maybe_raise("lane_eviction")
            except FaultError as exc:
                # Eviction path died mid-ladder: running lanes are
                # untouched and every already-preempted request is safely
                # queued — record the failure and stop evicting this step.
                self._record_dispatch_failure(exc)
                return
            self._preempt(victim)
            # The victim was requeued at the queue head; re-sort so the
            # higher-class request it was evicted for is admitted first
            # (otherwise the victim reclaims its own slot and the next
            # step evicts it again — a preemption livelock).
            self._sort_pending_by_class()
            preempted += 1

    def _sort_pending_by_class(self) -> None:
        """Stable-sort the pending queue by SLO rank (FIFO preserved
        within a class).  Skipped for single-class traffic: order is
        already FIFO and the sort would be pure overhead."""
        if len(self._pending) > 1 and len(
                {r.slo_class for r in self._pending}) > 1:
            self._pending = collections.deque(sorted(
                self._pending,
                key=lambda r: SLO_RANK.get(
                    r.slo_class, SLO_RANK[DEFAULT_CLASS])))

    def _requeue_or_fail(self, slot_idx: int, cause: str) -> None:
        """Recovery path for a slot whose in-flight work was lost (pipeline
        reset): recompute-requeue with generated tokens folded into the
        prompt, bounded by ``max_requeues``, then fail with the cause.
        Caller must have zeroed the slot's inflight counters and released
        any deferred frees first."""
        s = self._slots[slot_idx]
        assert s is not None
        self.allocator.free(s.blocks)
        self._slots[slot_idx] = None
        s.retired = True
        req = s.req
        if s.cancel_requested or req.requeues >= self.ecfg.max_requeues:
            # No caller left to retry for (cancelled / deadline-aborted)
            # or the requeue budget is spent: finish now with the cause.
            # Fold reconciled tokens into the prompt first so the error
            # result still carries the partial output.
            if s.generated:
                req.prompt_ids = req.prompt_ids + s.generated
            if s.cancel_requested:
                self._fail_request(req, s.abort_cause or "cancelled")
            else:
                self._fail_request(
                    req, f"{cause} (gave up after {req.requeues} requeues)")
            return
        req.requeues += 1
        self.requeues += 1
        consumed = len(s.generated)
        if consumed:
            req.prompt_ids = req.prompt_ids + s.generated
            req.sampling = dataclasses.replace(
                req.sampling,
                max_tokens=max(1, req.sampling.max_tokens - consumed))
        self._cap_request(req)
        self._pending.appendleft(req)
        t_now = time.monotonic()
        self._span("engine.requeue", t_now, t_now, req, status="error",
                   cause=cause[:200], requeues=req.requeues)
        self._flight.note("requeue", request_id=req.request_id,
                          cause=cause, requeues=req.requeues)

    def _reset_pipeline(self, cause: str,
                        extra_calls: tuple = ()) -> None:
        """Drop every in-flight call and recover the engine to a clean,
        serving state after a stuck or failed dispatch.

        Device-side page/token-buffer contents are suspect after a lost
        call (later dispatches in the chain consumed the failed call's
        donated buffers), so every live slot recovers by recompute: its
        reconciled tokens fold into the prompt and it re-queues (bounded
        by ``max_requeues``).  Shared prefix pages are dropped for the
        same reason.  The allocator's free count returns to its idle
        baseline — nothing leaks across a reset."""
        # Failure edge: snapshot the span ring + recent events to a flight
        # artifact BEFORE recovery mutates slot state (watchdog fires land
        # here), so the postmortem shows the pipeline as it wedged.
        self._flight.note("pipeline_reset", cause=cause,
                          inflight=len(self._inflight) + len(extra_calls),
                          watchdog_trips=self.watchdog_trips)
        self._flight.dump("pipeline_reset", extra={"cause": cause})
        calls = list(extra_calls) + list(self._inflight)
        self._inflight.clear()
        for call in calls:
            if call.kind in ("decode", "spec"):
                for _, s, _steps in call.lanes:
                    s.inflight_decode = 0
            elif call.kind == "chunk":
                for s in call.touched:
                    s.inflight_chunks = 0
        # No in-flight call references retired pages anymore.
        for _, blocks in self._deferred_frees:
            self.allocator.free(blocks)
        self._deferred_frees.clear()
        # Cached prefix pages may hold partial writes from the lost calls.
        # Deliberately NOT spilled to the host tier first — suspect pages
        # must never be demoted (a poisoned spill would resurface as wrong
        # KV on restore); already-spilled entries are untouched and stay
        # restorable after the reset.
        if self.prefix_cache is not None:
            self.prefix_cache.clear()
        for i, s in enumerate(self._slots):
            if s is None:
                continue
            s.inflight_decode = 0
            s.inflight_chunks = 0
            self._requeue_or_fail(i, cause)

    # -- admission ------------------------------------------------------

    def _bucket(self, n: int) -> int:
        """Smallest prefill bucket covering ``n`` tokens.

        ``n`` must not exceed the largest bucket — longer prompts go through
        chunked prefill, never silent clamping.
        """
        return prefill_bucket_for(n, self.ecfg.prefill_buckets)

    def _free_slots(self) -> list[int]:
        return [i for i, s in enumerate(self._slots) if s is None]

    def _lane_count(self, n: int) -> int:
        """Smallest power-of-two lane count covering ``n`` (capped at
        ``max_prefills_per_step``).  Padded lanes cost real FLOPs — a
        2-candidate round padded to 8 lanes dispatches 4x the needed
        prefill compute — while the pow-2 ladder keeps the compile cache
        at log2(max) entries instead of one per batch size."""
        P = 1
        while P < n:
            P <<= 1
        return min(P, self.ecfg.max_prefills_per_step)

    def _tokens_to_device(self, tokens: np.ndarray):
        """Token batch -> device, sharded over the mesh ``seq`` axis when
        sequence-parallel prefill is active (see __init__)."""
        t = jnp.asarray(tokens)
        if self._tok_sharding is not None:
            t = jax.device_put(t, self._tok_sharding)
        return t

    def _fail_request(self, req: GenerationRequest, msg: str) -> None:
        now = time.monotonic()
        result = GenerationResult(
            request_id=req.request_id,
            token_ids=req.prompt_ids[req.orig_prompt_len:]
            if req.orig_prompt_len >= 0 else [],
            finish_reason="error",
            ttft_s=0.0,
            latency_s=now - req.submit_time,
            error=msg,
        )
        self._results[req.request_id] = result
        self.hist_e2e.observe(result.latency_s, req.slo_class,
                              self._trace_id(req))
        self._end_request_span(req, "error", finish_reason="error",
                               error=msg[:200])
        if self.token_sink is not None:
            self.token_sink(req.request_id, [], result)

    def _emit(self, req: GenerationRequest, toks: list[int]) -> None:
        if self.token_sink is not None and toks:
            self.token_sink(req.request_id, toks, None)

    def _lane_buffers(self, P: int, bucket: int, table_width: int = 0):
        """Host-side lane arrays shared by the admission and chunk-round
        dispatch paths: (tokens, start, lengths, tables, idx, temp, topk,
        topp).  ``idx`` defaults to max_slots so padding / non-final lanes
        scatter their sampled token out of range (dropped).

        ``table_width`` (0 = full ``max_blocks_per_seq``) narrows the block
        table passed to the chunked program: its paged-attention gather
        materializes ``table_width * block_size`` keys per lane per layer
        regardless of real context, so a round early in a long prompt
        would otherwise pay the full-capacity gather (measured on v5e 8B
        W8A8: [4,512] chunk rounds run 221 ms at 2048 gathered keys vs
        171 ms at 1024 — ~25 ms per extra 512 keys)."""
        ec = self.ecfg
        W = table_width or ec.max_blocks_per_seq
        return (np.zeros((P, bucket), np.int32),
                np.zeros((P,), np.int32),
                np.zeros((P,), np.int32),
                np.zeros((P, W), np.int32),
                np.full((P,), ec.max_slots, np.int32),
                np.zeros((P,), np.float32),
                np.zeros((P,), np.int32),
                np.ones((P,), np.float32))

    def _table_width(self, max_tokens_covered: int) -> int:
        """Block-table width bucket for a chunked dispatch: enough blocks
        for the deepest lane's context, rounded up to 32 blocks so compile
        variants stay bounded (<= max_blocks_per_seq/32 widths)."""
        need = (max_tokens_covered + self.ecfg.block_size - 1) \
            // self.ecfg.block_size
        return min(self.ecfg.max_blocks_per_seq, (need + 31) // 32 * 32)

    def _write_hist(self, entries: list[tuple[int, GenerationRequest]]) -> None:
        """Load prompt tokens into the speculation history rows of freshly
        occupied slots (one batched scatter).  Prompts longer than the
        window keep their head — matches past the window just stop
        proposing, which degrades acceptance, never correctness."""
        if self._hist is None or not entries:
            return
        H = self._hist.shape[1]
        # Fixed row counts (1 or the admission lane max) keep the compile
        # cache at two entries; padding rows carry idx == max_slots (drop).
        P = 1 if len(entries) == 1 else self.ecfg.max_prefills_per_step
        rows = np.full((P, H), -1, np.int32)
        idx = np.full((P,), self.ecfg.max_slots, np.int32)
        for j, (slot_idx, req) in enumerate(entries):
            L = min(len(req.prompt_ids), H)
            rows[j, :L] = req.prompt_ids[:L]
            idx[j] = slot_idx
        self._hist = self._hist_place(
            self._hist, jnp.asarray(rows), jnp.asarray(idx))

    def _ensure_free(self, num_tokens: int) -> bool:
        """Make room for ``num_tokens`` of new blocks, evicting LRU prefix
        cache entries if needed.  Eviction drops the cache's reference; a
        block only returns to the free list when no live slot shares it."""
        while not self.allocator.can_alloc(num_tokens):
            if not self._evict_prefix_lru():
                return False
        return True

    # -- host KV tier (spill / restore, serving/kv_tier.py) --------------

    def _evict_prefix_lru(self) -> bool:
        """Pressured prefix-cache eviction, demoting to the host tier.

        With a :class:`HostKVTier` attached, the LRU victim's page rows are
        fetched off-device and stored under its chain digest BEFORE the
        device-side eviction — the next prompt that would have hit it
        rehydrates (``_try_restore``) instead of re-prefilling.  The spill
        is strictly best-effort: any failure degrades to the historical
        drop (the supervisor's replay machinery re-prefills on demand)."""
        pc = self.prefix_cache
        if pc is None:
            return False
        tier = self.host_kv_tier
        if tier is not None:
            peek = pc.peek_lru()
            if peek is not None:
                digest, blocks = peek
                # The victim's namespace follows it to the host tier (the
                # digest is already tenant-seeded; the tag drives the
                # tier's per-tenant byte accounting + max-share cap).
                victim_tenant = pc.peek_lru_tenant() or DEFAULT_TENANT
                t_spill = time.monotonic()
                try:
                    tier.put(digest, self._fetch_rows(blocks),
                             tenant=victim_tenant)
                except Exception as exc:  # noqa: BLE001 — spill must never block eviction
                    logger.warning("KV spill failed (%s); dropping entry",
                                   exc)
                else:
                    # Cache-maintenance work has no owning request; spans
                    # land under the engine's synthetic maintenance root.
                    if (self._maint_ctx is not None
                            and self._maint_ctx.sampled):
                        self._tracer.record(
                            "engine.kv_spill", t_spill, time.monotonic(),
                            self._maint_ctx, attrs={"blocks": len(blocks)})
                    self._flight.note("kv_spill", blocks=len(blocks))
        return pc.evict_lru()

    def _fetch_rows(self, blocks: list[int]) -> SpilledPrefix:
        """Materialize the page rows of ``blocks`` on the host (one gather
        per pytree leaf; syncs on the dispatch chain, which is exactly the
        price of demotion).  Under a mesh the fancy-index gather yields the
        GLOBAL fused-lane rows — page ids are global, so a spilled entry is
        mesh-shape-portable."""
        idx = np.asarray(blocks, np.int64)
        pages = self.pages
        quant = pages.quantized
        layers: list[tuple[np.ndarray, ...]] = []
        for li in range(len(pages.k)):
            leaf = (pages.k[li], pages.v[li])
            if quant:
                leaf += (pages.k_scale[li], pages.v_scale[li])
            layers.append(tuple(np.asarray(a[idx]) for a in leaf))
        return SpilledPrefix(n_blocks=len(blocks), layers=layers)

    def _write_rows(self, blocks: list[int], layers: list[tuple]) -> None:
        """Scatter host rows back into the device pool at ``blocks``,
        rebinding every page leaf through a donated jitted update so the
        pool keeps its treedef, shapes, and sharding (zero recompiles of
        the decode programs).  Rows are padded to a power-of-two count with
        the out-of-range index ``num_blocks`` (mode="drop") — never index
        0, whose null block must stay zero."""
        k = len(blocks)
        P = 1
        while P < k:
            P <<= 1
        idx = np.full((P,), self.ecfg.num_blocks, np.int32)
        idx[:k] = blocks
        idx_dev = jnp.asarray(idx)

        def write(leaf, rows):
            key = (P, np.dtype(leaf.dtype).name)
            prog = self._tier_write_cache.get(key)
            if prog is None:
                prog = jax.jit(
                    lambda lf, r, ix: lf.at[ix].set(
                        r.astype(lf.dtype), mode="drop"),
                    donate_argnums=(0,))
                self._tier_write_cache[key] = prog
            padded = np.zeros((P,) + rows.shape[1:], rows.dtype)
            padded[:k] = rows
            return prog(leaf, jnp.asarray(padded), idx_dev)

        pages = self.pages
        quant = pages.quantized
        new_k, new_v = list(pages.k), list(pages.v)
        new_ks, new_vs = list(pages.k_scale), list(pages.v_scale)
        for li, leaf_rows in enumerate(layers):
            new_k[li] = write(pages.k[li], leaf_rows[0])
            new_v[li] = write(pages.v[li], leaf_rows[1])
            if quant:
                new_ks[li] = write(pages.k_scale[li], leaf_rows[2])
                new_vs[li] = write(pages.v_scale[li], leaf_rows[3])
        self.pages = llama.KVPages(k=new_k, v=new_v,
                                   k_scale=new_ks if quant else (),
                                   v_scale=new_vs if quant else ())

    def _try_restore(self, prompt_ids: list[int], shared: list[int],
                     shared_toks: int, *,
                     tenant: str = DEFAULT_TENANT) -> tuple[list[int], int]:
        """Host-tier lookup behind a device prefix-cache miss (or a
        shorter-than-spilled hit): rehydrate the longest spilled prefix of
        ``prompt_ids`` into freshly allocated blocks, re-register it, and
        return the caller-owned span exactly as ``PrefixCache.lookup``
        would have.  Any failure returns the inputs unchanged — a lost
        spill is just a miss (replay/re-prefill fallback)."""
        tier = self.host_kv_tier
        pc = self.prefix_cache
        if tier is None or pc is None or len(tier) == 0:
            return shared, shared_toks
        bs = self.ecfg.block_size
        n = shareable_blocks(len(prompt_ids), bs)
        have = shared_toks // bs
        if n <= have:
            return shared, shared_toks
        digests = pc.digest_chain(prompt_ids, n, tenant=tenant)
        for k in range(n, have, -1):
            dg = digests[k - 1]
            entry = tier.peek(dg)
            if entry is None or entry.n_blocks != k:
                continue
            if not self._ensure_free(k * bs):
                return shared, shared_toks
            try:
                blocks = self.allocator.alloc(k * bs)
            except OutOfBlocks:
                return shared, shared_toks
            entry = tier.take(dg)
            if entry is None:  # raced away between peek and take
                self.allocator.free(blocks)
                return shared, shared_toks
            try:
                self._write_rows(blocks, entry.layers)
            except Exception as exc:  # noqa: BLE001 — failed restore degrades to a miss
                logger.warning("KV restore failed (%s); falling back to "
                               "re-prefill", exc)
                self.allocator.free(blocks)
                return shared, shared_toks
            # Re-publish for every prefix length.  shareable_blocks
            # guarantees len(prompt_ids) > k*bs, so the +1 slice below is
            # always in range; the extra token only satisfies the
            # shareable-span rule (digests cover whole blocks).
            pc.register(prompt_ids[:k * bs + 1], blocks, tenant=tenant)
            if shared:
                self.allocator.free(shared)
            return blocks, k * bs
        return shared, shared_toks

    # -- cross-replica prefix migration (kv_tier rung 3) -----------------

    def _kv_geometry(self) -> dict:
        """The geometry contract a migration blob must match exactly — a
        mismatched receiver must refuse the install, never write pages."""
        cfg, ec = self.cfg, self.ecfg
        return {
            "model": cfg.name,
            "layers": cfg.num_layers,
            "kv_heads": cfg.num_kv_heads,
            "head_dim": cfg.head_dim_,
            "block_size": ec.block_size,
            "kv_quant": self.kv_quant,
            "page_dtype": np.dtype(self.pages.k[0].dtype).name,
        }

    def export_prefix(self, prompt_ids: list[int], *,
                      tenant: str = DEFAULT_TENANT) -> Optional[bytes]:
        """Frame the longest cached prefix of ``prompt_ids`` (within
        ``tenant``'s namespace) for a replica-to-replica transfer (the
        fleet page-fetch endpoint).  Returns None on a miss.  The blob's
        META carries the tenant, so the receiver can refuse a namespace
        mismatch before touching pages.  The lookup's increfs pin the
        blocks for the duration of the device fetch, then release —
        export never changes cache contents."""
        self._refuse_unbuilt("KVX1 export_prefix")
        pc = self.prefix_cache
        if pc is None:
            return None
        shared, shared_toks = pc.lookup(prompt_ids, tenant=tenant)
        if not shared:
            return None
        try:
            entry = self._fetch_rows(shared)
            meta = dict(
                self._kv_geometry(),
                n_blocks=len(shared),
                tokens=[int(t) for t in prompt_ids[:shared_toks]],
                tenant=tenant)
            return pack_prefix_blob(
                meta, [a for leaf in entry.layers for a in leaf])
        finally:
            self.allocator.free(shared)

    def install_prefix(self, blob: bytes, *,
                       expected_tenant: str | None = None) -> str:
        """Install a migrated prefix blob into the local pool and prefix
        cache (under the blob's own tenant namespace).  Returns an outcome
        string: ``"installed"`` (pages written and registered),
        ``"cached"`` (already resident — no work), ``"incompatible"``
        (geometry contract mismatch), ``"tenant_mismatch"`` (the caller
        expected a different namespace than the blob header claims — the
        pages are refused unseen), or ``"nospace"`` (pool pressure won).
        Framing/CRC damage raises :class:`~..serving.kv_tier.BlobError` —
        the caller treats a torn transfer as a miss, never a partial
        install."""
        self._refuse_unbuilt("KVX1 install_prefix")
        meta, raw = unpack_prefix_blob(blob)
        geo = self._kv_geometry()
        if any(meta.get(key) != geo[key] for key in geo):
            return "incompatible"
        # Blobs packed before tenancy landed carry no tenant header and
        # install into the default namespace (back-compat).
        try:
            blob_tenant = normalize_tenant(
                meta.get("tenant"), default=DEFAULT_TENANT)
        except ValueError:
            return "incompatible"
        if expected_tenant is not None and blob_tenant != expected_tenant:
            return "tenant_mismatch"
        pc = self.prefix_cache
        cfg, ec = self.cfg, self.ecfg
        bs = ec.block_size
        tokens = [int(t) for t in meta.get("tokens", ())]
        k = int(meta.get("n_blocks", 0))
        leaves = 4 if self.kv_quant else 2
        if (pc is None or k <= 0 or len(tokens) != k * bs
                or len(raw) != cfg.num_layers * leaves):
            return "incompatible"
        # The +1 probe/register token never enters a digest (whole blocks
        # only); it just satisfies the shareable-span rule.
        probe = tokens + [0]
        shared, st = pc.lookup(probe, tenant=blob_tenant)
        if shared:
            self.allocator.free(shared)
            if st >= k * bs:
                return "cached"
        F = cfg.num_kv_heads * cfg.head_dim_
        pdtype = np.dtype(self.pages.k[0].dtype)
        layers: list[tuple] = []
        it = iter(raw)
        try:
            for _ in range(cfg.num_layers):
                leaf = (np.frombuffer(next(it), pdtype).reshape(k, bs, F),
                        np.frombuffer(next(it), pdtype).reshape(k, bs, F))
                if self.kv_quant:
                    leaf += (np.frombuffer(next(it), np.float32)
                             .reshape(k, bs, cfg.num_kv_heads),
                             np.frombuffer(next(it), np.float32)
                             .reshape(k, bs, cfg.num_kv_heads))
                layers.append(leaf)
        except ValueError as e:
            raise BlobError(f"ARRAY record does not match geometry: {e}") from e
        if not self._ensure_free(k * bs):
            return "nospace"
        try:
            blocks = self.allocator.alloc(k * bs)
        except OutOfBlocks:
            return "nospace"
        try:
            self._write_rows(blocks, layers)
        except Exception:
            self.allocator.free(blocks)
            raise
        pc.register(probe, blocks, tenant=blob_tenant)
        # The cache entries hold their own references now; dropping the
        # alloc-time ref leaves the pages owned by the cache alone (LRU
        # evictable, host-spillable) exactly like a locally prefilled span.
        self.allocator.free(blocks)
        return "installed"

    def kv_tier_stats(self) -> dict:
        """Tier byte accounting + spill/restore counters for the exporter
        (``kv_tier_bytes{tier}`` etc.) and the fleet registry.  Device
        bytes are the GLOBAL pool (tp=1 view — per-chip slices divide by
        the mesh's model degree, see ``page_slice_bytes``)."""
        cfg, ec = self.cfg, self.ecfg
        pdtype = np.dtype(self.pages.k[0].dtype)
        page_b = page_slice_bytes(
            cfg.num_kv_heads, cfg.head_dim_, ec.block_size, pdtype.itemsize,
            scale_bytes=4 if self.kv_quant else 0)
        out = {
            "kv_quant": self.kv_quant,
            "page_dtype": pdtype.name,
            "device_bytes": cfg.num_layers * ec.num_blocks * page_b,
            "host_bytes": 0,
            "host_entries": 0,
            "spills": 0,
            "restores": 0,
            "host_lost": 0,
        }
        if self.host_kv_tier is not None:
            s = self.host_kv_tier.stats()
            out.update(host_bytes=s["bytes"], host_entries=s["entries"],
                       spills=s["spills"], restores=s["restores"],
                       host_lost=s["lost"],
                       host_tenant_bytes=s["tenant_bytes"])
        # Per-tenant resident-block fairness accounting (exporter
        # ``tenant_kv_blocks``).
        if self.prefix_cache is not None:
            out["tenant_blocks"] = self.prefix_cache.blocks_by_tenant()
        return out

    def _pending_prefix_gain(
        self, cand: list[int], publishers: list[list[int]],
    ) -> int:
        """Tokens of ``cand``'s prefix that become cache-sharable once the
        ``publishers`` prompts register their pages (block-aligned, capped
        at both prompts' shareable spans — kv_cache.shareable_blocks)."""
        bs = self.ecfg.block_size
        cand_blocks = shareable_blocks(len(cand), bs)
        if cand_blocks <= 0:
            return 0
        best = 0
        for other in publishers:
            if cand[:bs] != other[:bs]:
                continue
            # Whole-block slice compares (C-speed) — only full blocks are
            # ever sharable, so per-token resolution buys nothing.
            nb = min(shareable_blocks(len(other), bs), cand_blocks)
            if nb <= 0:
                continue
            k = 1
            while k < nb and cand[k * bs:(k + 1) * bs] == other[k * bs:(k + 1) * bs]:
                k += 1
            best = max(best, k * bs)
        return best

    def _admit_round(self) -> bool:
        """Dispatch one batched prefill+sample call for up to
        ``max_prefills_per_step`` pending prompts.  Returns True if anything
        was dispatched.

        Each candidate first consults the prefix cache; a hit turns its
        prefill into a suffix-only chunked ingestion over the shared pages.
        Rounds where every lane is a miss keep the dense prefill path (no
        page gather); any hit switches the round to the chunked program.

        Cold-burst dedup, two rules sharing one economic gate (the
        published span must cover at least half the candidate's remaining
        prefill work):

        * a candidate sharing a prefix with a *dense lane admitted this
          round* (pages publish at dispatch) is held back exactly one
          round — 100 simultaneous same-evidence diagnosis queries
          prefill their shared prefix once, not max_prefills_per_step
          times;
        * a *chunk-path* candidate (suffix wider than the largest bucket)
          sharing a prefix with a slot still streaming its chunks waits
          until that publisher's final chunk registers the pages — chunk
          rounds advance every step regardless of admissions, so the wait
          is bounded and the candidate then admits suffix-only.  Short
          candidates never wait on a streaming publisher (their own
          prefill costs at most one bucket).
        """
        ec = self.ecfg
        top = ec.prefill_buckets[-1]
        free = self._free_slots()
        admitted_long = 0
        deferred: list[GenerationRequest] = []
        round_prompts: list[list[int]] = []
        # Prompts whose pages will register when their streaming prefill
        # completes: live chunk-path slots + this round's long admissions.
        publishing: list[list[int]] = (
            [s.req.prompt_ids for s in self._slots
             if s is not None and s.prefilling and not s.retired
             and not s.cancel_requested]
            if self.prefix_cache is not None else [])
        # Deferral work per round is bounded: past this many held-back
        # candidates the scan stops (the rest stay pending and hit the
        # cache next round) — a 10k-deep cold queue must not stall the
        # scheduler thread inside one admission round.
        defer_budget = 4 * ec.max_prefills_per_step
        # Entries: (slot_idx, req, blocks, shared_toks)
        batch: list[tuple[int, GenerationRequest, list[int], int]] = []
        while len(batch) < ec.max_prefills_per_step and self._pending and free:
            if len(deferred) >= defer_budget:
                # Stop the scan, not just the deferring: candidates past
                # the budget stay pending (and will hit the cache next
                # round) instead of being admitted into a redundant
                # prefix recompute.
                break
            req = self._pending[0]
            if time.monotonic() > self._deadline_of(req, queued=True):
                self._pending.popleft()
                self.deadline_expired += 1
                self._fail_request(
                    req, f"deadline exceeded after "
                         f"{time.monotonic() - req.submit_time:.2f}s in queue")
                continue
            L = len(req.prompt_ids)
            if (self._round_tokens and batch
                    and sum(len(r.prompt_ids) for _, r, _, _ in batch) + L
                    > self._round_tokens):
                break       # this prompt leads the next step's round
            if L + 1 > self.capacity_tokens:
                # Defensive: submit() caps requests, so this only catches
                # internal misuse; fail loudly instead of livelocking.
                self._pending.popleft()
                self._fail_request(
                    req, f"prompt of {L} tokens exceeds capacity "
                         f"{self.capacity_tokens}")
                continue
            shared: list[int] = []
            shared_toks = 0
            if self.prefix_cache is not None:
                shared, shared_toks = self.prefix_cache.lookup(
                    req.prompt_ids, tenant=req.tenant)
                if self.host_kv_tier is not None:
                    # A spilled entry longer than the device hit rehydrates
                    # here, overlapped with the rest of admission prep —
                    # the scatter is async; the prefill that consumes the
                    # pages queues behind it on the dispatch chain.
                    t_res = time.monotonic()
                    pre_toks = shared_toks
                    shared, shared_toks = self._try_restore(
                        req.prompt_ids, shared, shared_toks,
                        tenant=req.tenant)
                    if shared_toks > pre_toks:
                        self._span("engine.kv_restore", t_res,
                                   time.monotonic(), req,
                                   tokens=shared_toks - pre_toks)
                        self._flight.note(
                            "kv_restore", request_id=req.request_id,
                            tokens=shared_toks - pre_toks)
                suffix = L - shared_toks

                def worth(gain: int) -> bool:
                    # The one economic gate both rules share: the published
                    # span must beat the current hit AND cover at least
                    # half the prefill work still ahead of this candidate.
                    return (gain > shared_toks
                            and 2 * (gain - shared_toks) >= suffix)

                defer = False
                if not req.prefix_deferred and round_prompts:
                    defer = worth(self._pending_prefix_gain(
                        req.prompt_ids, round_prompts))
                if not defer and suffix > top and publishing:
                    # Chunk-path candidate: wait for a streaming publisher
                    # (re-evaluated each round; no flag — the wait ends
                    # when the publisher's final chunk registers, or
                    # immediately if it is preempted or cancelled).
                    defer = worth(self._pending_prefix_gain(
                        req.prompt_ids, publishing))
                if defer:
                    if shared:
                        self.allocator.free(shared)
                    if not req.prefix_deferred:
                        # Counts requests ever deferred, not rounds held —
                        # a chunk-path candidate may wait several rounds
                        # on one streaming publisher.
                        req.prefix_deferred = True
                        self.prefix_deferrals += 1
                    self._pending.popleft()
                    deferred.append(req)
                    continue
            if not self._ensure_free(L + 1 - shared_toks):
                if shared:
                    self.allocator.free(shared)
                break
            self._pending.popleft()
            if self.prefix_cache is not None:
                # Stats count *admissions* (a deferred request's retried
                # lookups must not double-count).
                if shared_toks > 0:
                    self.prefix_cache.hits += 1
                else:
                    self.prefix_cache.misses += 1
            if req.orig_prompt_len < 0:
                req.orig_prompt_len = L
            try:
                blocks = shared + self.allocator.alloc(L + 1 - shared_toks,
                                                       at=len(shared))
            except OutOfBlocks:
                # can_alloc said yes but alloc still failed (injected
                # exhaustion, or a racing sharer): push back, end the scan.
                if shared:
                    self.allocator.free(shared)
                self._pending.appendleft(req)
                break
            self._note_admission_wait(req)
            self._clamp_for_brownout(req)
            if L - shared_toks > top:
                # Long suffix: occupy a slot in *prefilling* state — its
                # chunks stream one batched round per engine step
                # (_dispatch_prefill_chunks), so decode and short-prompt
                # admissions interleave instead of stalling behind a
                # serial chunk loop.
                slot = _Slot(req, blocks)
                slot.ctx_len = L
                slot.prefill_pos = slot.cached_uncounted = shared_toks
                slot.prefilling = True
                slot_idx = free.pop(0)
                self._slots[slot_idx] = slot
                self._write_hist([(slot_idx, req)])
                admitted_long += 1
                if self.prefix_cache is not None:
                    publishing.append(req.prompt_ids)
                continue
            batch.append((free.pop(0), req, blocks, shared_toks))
            round_prompts.append(req.prompt_ids)
        if deferred:
            # Back to the queue head in original order: next round's
            # lookups hit the pages this round's dispatch publishes.
            self._pending.extendleft(reversed(deferred))
        if not batch:
            return admitted_long > 0

        any_shared = any(st > 0 for _, _, _, st in batch)
        # A fresh round on one chip goes as packed calls, one per group of
        # consecutive prompts (_admit_groups); a round with a prefix hit, or
        # over a mesh, as one row call.
        sizes = (self._admit_groups([len(r.prompt_ids) for _, r, _, _ in batch])
                 if self._packed_prefill and not any_shared else [len(batch)])
        done = 0
        for n in sizes:
            exc = self._dispatch_admit(batch[done:done + n], any_shared)
            if exc is not None:
                self._abandon_admit(batch[done:], exc)
                break
            done += n
        return done > 0 or admitted_long > 0

    # What one more call costs a round, in tokens: a call streams the
    # weights once whatever it holds, and a prefill token's arithmetic is two
    # operations a weight byte, so on a chip that does a few hundred
    # operations in the time it reads a byte (v5e: 393 TOP/s int8 over
    # 819 GB/s = 480) one pass over the weights is worth ~240 tokens at the
    # peak.  Measured there (PERF.md section 5): 9.3 ms a pass against
    # ~60 us a prefill token for Qwen2-7B.
    _CALL_TOKENS = 256

    def _token_rung(self, n: int) -> int:
        """Tokens a packed call of ``n`` real tokens computes: the smallest
        bucket, doubled until it covers them.  The rung alone is the
        program's compile-time shape, so a bucket ladder of powers of two
        has one fresh-prefill program per power of two between its smallest
        bucket and ``max_prefills_per_step`` x its largest."""
        T = self.ecfg.prefill_buckets[0]
        while T < n:
            T <<= 1
        return T

    def _admit_groups(self, lengths: list[int]) -> list[int]:
        """Cut a round's prompts, kept in order, into consecutive calls so
        that the calls' rungs sum to the least (3 prompts of 1,300 tokens go
        as 1,024 + 512, not 2,048); each call beyond the first is priced at
        ``_CALL_TOKENS``, which also decides ties for fewer calls.  Returns
        the calls' sizes in prompts."""
        best: list[tuple[int, list[int]]] = [(0, [])]   # of the first i prompts
        for i in range(1, len(lengths) + 1):
            best.append(min(
                ((best[j][0] + self._token_rung(sum(lengths[j:i]))
                  + self._CALL_TOKENS, best[j][1] + [i - j])
                 for j in range(i)), key=lambda c: c[0]))
        return best[-1][1]

    def _abandon_admit(self, batch: list[tuple], exc: Exception) -> None:
        """A round's dispatch failed with host state still pre-dispatch for
        ``batch`` (no slot occupied, no pages registered): release its pages
        and requeue the candidates in order — bounded, so a deterministic
        dispatch failure eventually surfaces to callers instead of
        spinning."""
        requeue: list[GenerationRequest] = []
        for _, req, blocks, _ in batch:
            self.allocator.free(blocks)
            if req.requeues >= self.ecfg.max_requeues:
                self._fail_request(
                    req, f"prefill dispatch failed: {exc} "
                         f"(gave up after {req.requeues} requeues)")
            else:
                req.requeues += 1
                self.requeues += 1
                requeue.append(req)
        self._pending.extendleft(reversed(requeue))

    def _dispatch_admit(self, batch: list[tuple],
                        any_shared: bool) -> Optional[Exception]:
        """One admission call for ``batch`` (``_admit_round``'s entries
        ``(slot_idx, req, blocks, shared_toks)``): packed when the round is
        fresh on one chip, else the row program — the chunked one if any
        lane shares a cached prefix.  Returns the exception if the dispatch
        failed (nothing of ``batch`` has then reached the device or a slot),
        else None."""
        ec = self.ecfg
        packed = self._packed_prefill and not any_shared
        suffix = [len(r.prompt_ids) - st for _, r, _, st in batch]
        if packed:
            # ``start`` is each segment's offset in the stream; an idle
            # row's is the end of the real tokens.
            P, W = ec.max_prefills_per_step, 0
            padded = self._token_rung(sum(suffix))
            bucket = min(padded, ec.prefill_buckets[-1])  # the row view's
            (_, start, lengths, tables, idx,
             temp, topk, topp) = self._lane_buffers(P, 0)
            tokens = np.zeros((padded,), np.int32)
            start[:] = sum(suffix)
        else:
            P = self._lane_count(len(batch))
            bucket = self._bucket(max(suffix))
            padded = bucket * P
            # The chunked program (taken when any lane shares a cached
            # prefix) gathers table_width * block_size keys per lane; narrow
            # it to the deepest prompt.  The dense program never gathers —
            # full width there avoids extra compile shapes.
            W = (self._table_width(max(len(r.prompt_ids)
                                       for _, r, _, _ in batch))
                 if any_shared else 0)
            (tokens, start, lengths, tables, idx,
             temp, topk, topp) = self._lane_buffers(P, bucket, W)
        fstate = np.zeros((P,), np.int32)
        at = 0
        for j, (slot_idx, req, blocks, st) in enumerate(batch):
            L = len(req.prompt_ids)
            if req.orig_prompt_len < 0:
                req.orig_prompt_len = L
            if packed:
                tokens[at:at + L] = req.prompt_ids
                start[j] = at
                at += L
            else:
                tokens[j, : L - st] = req.prompt_ids[st:]
                start[j] = st
            lengths[j] = L - st
            # blocks may cover L+1 tokens (the first decode write); the
            # prefill only reads/writes positions < L, so truncating to the
            # narrowed width is safe — decode uses its own full table.
            nb = min(len(blocks), tables.shape[1])
            tables[j, :nb] = blocks[:nb]
            idx[j] = slot_idx
            sp = req.sampling
            temp[j], topk[j], topp[j] = sp.temperature, sp.top_k, sp.top_p
            if sp.constrained:
                fstate[j] = self._fsm_entry(req)

        all_greedy = all(r.sampling.temperature <= 0.0 for _, r, _, _ in batch)
        # Any constrained lane forces the FSM program family (sampled-shape,
        # masked logits); free lanes ride along at state 0, and greedy lanes
        # stay exact via argmax-of-masked inside the shared sampler.
        constrained = any(r.sampling.constrained for _, r, _, _ in batch)
        fnext = None
        program = _calling.program = (
            (f"prefill_t{padded}" if packed else
             f"prefill{'_chunk' if any_shared else ''}_b{bucket}_r{P}"
             + (f"_w{W}" if any_shared else ""))
            + ("_sample_fsm" if constrained
               else "_greedy" if all_greedy else "_sample"))
        device_empty = self._device_empty()
        try:
            self._faults.maybe_raise("prefill_dispatch")
            self.in_program_call = True
            t_dispatch = time.monotonic()
            if not any_shared:
                # (offset, lengths) of a packed stream; (lengths,) of rows.
                seg = ((jnp.asarray(start), jnp.asarray(lengths)) if packed
                       else (jnp.asarray(lengths),))
                if self._recurrent:     # each row's lane of the state pool
                    seg += (jnp.asarray(idx),)
                if constrained:
                    self._rng, sub = jax.random.split(self._rng)
                    first, fnext, self.pages = self._prefill_sample_fsm(
                        self.params, self._tokens_to_device(tokens), seg,
                        self.pages, jnp.asarray(tables), jnp.asarray(fstate),
                        self._fsm_trans, jnp.asarray(temp),
                        jnp.asarray(topk), jnp.asarray(topp), sub,
                    )
                elif all_greedy:
                    first, self.pages = self._prefill_greedy(
                        self.params, self._tokens_to_device(tokens), seg,
                        self.pages, jnp.asarray(tables),
                    )
                else:
                    self._rng, sub = jax.random.split(self._rng)
                    first, self.pages = self._prefill_sample(
                        self.params, self._tokens_to_device(tokens), seg,
                        self.pages, jnp.asarray(tables), jnp.asarray(temp),
                        jnp.asarray(topk), jnp.asarray(topp), sub,
                    )
            else:
                if constrained:
                    self._rng, sub = jax.random.split(self._rng)
                    first, fnext, self.pages = self._prefill_chunk_sample_fsm(
                        self.params, self._tokens_to_device(tokens), jnp.asarray(start),
                        jnp.asarray(lengths), self.pages, jnp.asarray(tables),
                        jnp.asarray(fstate), self._fsm_trans,
                        jnp.asarray(temp), jnp.asarray(topk),
                        jnp.asarray(topp), sub,
                    )
                elif all_greedy:
                    first, self.pages = self._prefill_chunk_greedy(
                        self.params, self._tokens_to_device(tokens), jnp.asarray(start),
                        jnp.asarray(lengths), self.pages, jnp.asarray(tables),
                    )
                else:
                    self._rng, sub = jax.random.split(self._rng)
                    first, self.pages = self._prefill_chunk_sample(
                        self.params, self._tokens_to_device(tokens), jnp.asarray(start),
                        jnp.asarray(lengths), self.pages, jnp.asarray(tables),
                        jnp.asarray(temp), jnp.asarray(topk),
                        jnp.asarray(topp), sub,
                    )
        except Exception as exc:
            self._record_dispatch_failure(exc)
            return exc
        finally:
            self.in_program_call = False
            self.last_program_call = time.monotonic()
            _calling.program = ""
        self._record_dispatch_ok()
        self.prefill_bucket_rounds[bucket] = (
            self.prefill_bucket_rounds.get(bucket, 0) + 1)
        if self.prefix_cache is not None:
            for slot_idx, req, blocks, st in batch:
                self.prefix_cache.register(req.prompt_ids, blocks,
                                           tenant=req.tenant)
        cached = sum(st for _, _, _, st in batch)
        self._finish_admit_dispatch(
            first, [(s, r, b) for s, r, b, _ in batch], idx, fsm_next=fnext,
            counts={"sampler_filter": (
                        None if all_greedy and not constrained else
                        any(r.sampling.filtered for _, r, _, _ in batch)),
                    "bucket": bucket, "rows": P, "prompts": len(batch),
                    "real_tokens": sum(suffix), "padded_tokens": padded,
                    "cached_tokens": cached, "shared": int(any_shared),
                    "packed": int(packed)},
            program=program, device_empty=device_empty, t0=t_dispatch)
        return None

    def _dispatch_prefill_chunks(self) -> bool:
        """One batched chunk round for slots in prefilling state.

        Lanes are ordered depth-first (fewest remaining tokens first, then
        submit order): finishing a few lanes completely beats advancing all
        of them one chunk — p50 TTFT is completion-order-sensitive while
        total work is fixed.  Each lane ingests its next ``<= top`` tokens
        via the per-lane-start chunked program; lanes whose chunk is final
        sample their first token in the same call (admit semantics at
        reconcile), non-final lanes drop theirs.  One round per engine
        step, so decode dispatches interleave between rounds.
        """
        ec = self.ecfg
        top = ec.prefill_buckets[-1]
        cands = [(i, s) for i, s in enumerate(self._slots)
                 if s is not None and s.prefilling and not s.retired
                 and not s.cancel_requested]
        if not cands:
            return False
        cands.sort(key=lambda t: (len(t[1].req.prompt_ids)
                                  - t[1].prefill_pos,
                                  t[1].req.submit_time))
        cands = cands[:ec.max_prefills_per_step]

        P = self._lane_count(len(cands))
        bucket = self._bucket(min(top, max(
            len(s.req.prompt_ids) - s.prefill_pos for _, s in cands)))
        # Deadline-aware round sizing: queued interactive work shrinks the
        # round so its admission dispatch isn't head-of-line blocked behind
        # a full-bucket chunk.  Total chunk work is unchanged — the long
        # prompt just takes more, shorter rounds while the queue holds
        # interactive requests.
        icb = self.ecfg.interactive_chunk_bucket
        if icb > 0 and any(r.slo_class == "interactive"
                           for r in self._pending):
            small = self._bucket(min(icb, top))
            if small < bucket:
                bucket = small
                self.chunk_shrinks += 1
        self.last_chunk_bucket = bucket
        # Narrow the gathered table to the deepest lane's post-round
        # context: early rounds of a long prompt attend to a fraction of
        # capacity, and the gather cost scales with table width.
        W = self._table_width(max(
            s.prefill_pos + min(bucket, len(s.req.prompt_ids)
                                - s.prefill_pos) for _, s in cands))
        (tokens, start, lengths, tables, idx,
         temp, topk, topp) = self._lane_buffers(P, bucket, W)
        fstate = np.zeros((P,), np.int32)
        lanes: list[tuple] = []
        touched: list[_Slot] = []
        final_greedy = True
        final_constrained = False
        # (slot, chunk_len, became_final) — enough to roll every slot
        # mutation back if the dispatch itself fails.
        muts: list[tuple[_Slot, int, bool]] = []
        to_register: list[_Slot] = []
        for j, (i, s) in enumerate(cands):
            L = len(s.req.prompt_ids)
            n = min(bucket, L - s.prefill_pos)
            tokens[j, :n] = s.req.prompt_ids[s.prefill_pos:s.prefill_pos + n]
            start[j] = s.prefill_pos
            lengths[j] = n
            nb = min(len(s.blocks), tables.shape[1])
            tables[j, :nb] = s.blocks[:nb]
            s.prefill_pos += n
            s.inflight_chunks += 1
            touched.append(s)
            became_final = False
            if s.prefill_pos >= L:
                # Final chunk: its last-token logits produce the first
                # generated token; pages for the whole prompt are now in
                # the dispatch chain, so the prefix becomes publishable
                # (registered below, only after the dispatch succeeds).
                s.prefilling = False
                became_final = True
                sp = s.req.sampling
                temp[j], topk[j], topp[j] = sp.temperature, sp.top_k, sp.top_p
                final_greedy = final_greedy and sp.temperature <= 0.0
                if sp.constrained:
                    final_constrained = True
                    fstate[j] = self._fsm_entry(s.req)
                idx[j] = i
                lanes.append((j, i, s.req))
                if self.prefix_cache is not None:
                    to_register.append(s)
            muts.append((s, n, became_final))

        fnext = None
        program = _calling.program = (
            f"prefill_chunk_b{bucket}_r{P}_w{W}"
            + ("_sample_fsm" if final_constrained
               else "_greedy" if final_greedy else "_sample"))
        device_empty = self._device_empty()
        try:
            self._faults.maybe_raise("prefill_dispatch")
            self.in_program_call = True
            t_dispatch = time.monotonic()
            if final_constrained:
                # Only final lanes sample, so only they consult the FSM;
                # non-final lanes stay at state 0 and drop their token (and
                # state) via the out-of-range idx scatter.
                self._rng, sub = jax.random.split(self._rng)
                first, fnext, self.pages = self._prefill_chunk_sample_fsm(
                    self.params, self._tokens_to_device(tokens), jnp.asarray(start),
                    jnp.asarray(lengths), self.pages, jnp.asarray(tables),
                    jnp.asarray(fstate), self._fsm_trans, jnp.asarray(temp),
                    jnp.asarray(topk), jnp.asarray(topp), sub,
                )
            elif final_greedy:
                first, self.pages = self._prefill_chunk_greedy(
                    self.params, self._tokens_to_device(tokens), jnp.asarray(start),
                    jnp.asarray(lengths), self.pages, jnp.asarray(tables),
                )
            else:
                self._rng, sub = jax.random.split(self._rng)
                first, self.pages = self._prefill_chunk_sample(
                    self.params, self._tokens_to_device(tokens), jnp.asarray(start),
                    jnp.asarray(lengths), self.pages, jnp.asarray(tables),
                    jnp.asarray(temp), jnp.asarray(topk), jnp.asarray(topp),
                    sub,
                )
        except Exception as exc:
            # Nothing reached the device: rewind this round's slot state
            # so the next step re-dispatches the same chunks.
            for s, n, became_final in muts:
                s.prefill_pos -= n
                s.inflight_chunks -= 1
                if became_final:
                    s.prefilling = True
            self._record_dispatch_failure(exc)
            if self.consecutive_dispatch_failures > ec.max_requeues:
                # Same bound as the decode dispatch: a chunk program that
                # fails identically every step must end its requests.
                self._reset_pipeline(f"chunk dispatch failed: {exc!r}")
            return False
        finally:
            self.in_program_call = False
            self.last_program_call = time.monotonic()
            _calling.program = ""
        self._record_dispatch_ok()
        self.prefill_bucket_rounds[bucket] = (
            self.prefill_bucket_rounds.get(bucket, 0) + 1)
        for s in to_register:
            self.prefix_cache.register(s.req.prompt_ids, s.blocks,
                                       tenant=s.req.tenant)
        self.prefills += len(lanes)
        cached = sum(s.cached_uncounted for s in touched)
        for s in touched:
            s.cached_uncounted = 0
        self._queue_inflight(
            "chunk", first, idx, lanes, touched, fsm_next=fnext, t0=t_dispatch,
            span_attrs=self._call_attrs(
                "chunk", program, device_empty,
                sampler_filter=(
                    None if final_greedy and not final_constrained
                    else any(r.sampling.filtered for _, _, r in lanes)),
                bucket=bucket, rows=P,
                prompts=len(cands), real_tokens=sum(n for _, n, _ in muts),
                padded_tokens=bucket * P, cached_tokens=cached, packed=0))
        return True

    def _queue_inflight(self, kind: str, first, idx, lanes,
                        touched=(), fsm_next=None, span_attrs=None, *,
                        t0: float) -> None:
        """Shared dispatch tail: place sampled tokens into the device token
        buffer, start the async host copy, and queue the reconcile entry
        (``t0``: the call's dispatch)."""
        self._tok_state = self._place_tokens(
            self._tok_state, first, jnp.asarray(idx))
        if self._fsm_trans is not None:
            # With a grammar installed, every admission (re)writes its
            # lanes' FSM states: the post-first-token state for constrained
            # lanes, zero for free lanes — which also clears stale state
            # left by a previous constrained occupant of a reused slot.
            # Same ordering argument as _tok_state: the scatter is enqueued
            # after the producing call and before any consuming decode.
            self._fsm_state = self._place_fsm(
                self._fsm_state,
                fsm_next if fsm_next is not None else jnp.zeros_like(first),
                jnp.asarray(idx))
        try:
            first.copy_to_host_async()
        except AttributeError:  # non-jax array (tests with stub impls)
            pass
        self._inflight.append(_Inflight(
            kind=kind, call_id=self._next_call_id, arr=first,
            lanes=list(lanes), touched=list(touched),
            t0=t0, span_attrs=span_attrs or {},
            moe_counts=self._take_moe_counts()))
        self._next_call_id += 1

    def _finish_admit_dispatch(self, first, batch, idx, fsm_next=None, *,
                               counts: dict, program: str,
                               device_empty: bool, t0: float) -> None:
        """Admission tail: occupy slots, then queue via the shared path."""
        lanes = []
        for slot_idx, req, blocks in batch:
            slot = _Slot(req, blocks)
            slot.ctx_len = len(req.prompt_ids)
            self._slots[slot_idx] = slot
            lanes.append((slot_idx, req))
        self.prefills += len(batch)
        self._write_hist(lanes)
        self._queue_inflight(
            "admit", first, idx, lanes, fsm_next=fsm_next, t0=t0,
            span_attrs=self._call_attrs("admit", program, device_empty,
                                        **counts))

    # -- decode ---------------------------------------------------------

    def _decode_program(self, n_steps: int, sampled: bool,
                        bounded: bool = False, constrained: bool = False):
        """Build (and cache) the fused K-step decode program.

        The scan carries (token, ctx, done, pages[, rng]) on device: each
        iteration feeds the previous step's sampled token back in without a
        host round-trip, EOS and per-lane budget exhaustion flip lanes to the
        masked state (writes -> null block), and the emitted [K, B] token
        matrix uses -1 for steps where a lane was not active.  Returns
        (toks [K, B], final token state [B], pages).

        ``bounded`` (static, sampled programs only): sample from the top
        ``sample_topk_cap`` logits per step instead of rank-sorting the
        full vocab — distribution-exact when every sampling lane has
        0 < top_k <= cap, which _dispatch_decode verifies per call.

        ``constrained`` (static, sampled programs only): the scan also
        carries the per-lane grammar FSM state — each step masks logits by
        the lane's allowed-token row before the shared sampler and advances
        the state by the sampled token.  Lanes at state 0 (FREE) are
        untouched, so one constrained program serves mixed batches; the
        transition table is a runtime argument (no recompile per grammar).
        """
        key = (n_steps, sampled, bounded, constrained)
        prog = self._decode_cache.get(key)
        if prog is not None:
            return prog

        cfg = self.cfg
        attn_impl = self._attn_impl
        k_cap = self.ecfg.sample_topk_cap
        overlap_step = self._overlap_step

        routed = self._routed
        # The device counts ride in the scan's carry, a float32 vector a
        # group (``_sum_counts``), summed over the steps: no leaf for a dense
        # model (the program is what it always was).
        cnt0 = tuple(jnp.zeros((len(names),), jnp.float32)
                     for names in self._count_names.values())
        ssm_update = self._ssm_update
        index_scores = self._index_scores
        sel_counted = self._sel_counted

        def _step_core(params, tokens, ctx, act, pages, tables, cnt):
            ctx_eff = jnp.where(act, ctx, 0)
            if overlap_step is not None:
                # Hand-staged TP schedule (parallel/overlap.py): same
                # calling convention minus attn_impl, which the builder
                # resolved from self.decode_path at engine construction.
                logits, pages = overlap_step(
                    params, tokens, ctx_eff, pages, tables)
            else:
                stats = [] if routed else None
                sel = [] if sel_counted else None
                logits, pages = llama.decode_step(
                    params, cfg, tokens, ctx_eff, pages, tables,
                    attn_impl=attn_impl, moe_stats=stats,
                    ssm_update=ssm_update,
                    **({"sel_stats": sel, "index_scores": index_scores}
                       if sel_counted else {}),
                )
                cnt = tuple(a + b for a, b in
                            zip(cnt, _sum_counts(stats, sel)))
            return logits, pages, cnt

        def _outs(outs: tuple, cnt) -> tuple:
            return (*outs, *cnt)

        if sampled and constrained:
            def fn(params, tok_state, fsm_state, ctx, remaining, pages,
                   tables, ftrans, temp, topk, topp, rng, eos):
                active0 = ctx > 0

                def body(carry, i):
                    tokens, fstate, ctx, done, rng, pages, cnt = carry
                    act = active0 & ~done & (i < remaining)
                    logits, pages, cnt = _step_core(
                        params, tokens, ctx, act, pages, tables, cnt)
                    logits = fsm_mask_logits(logits, fstate, ftrans)
                    rng, sub = jax.random.split(rng)
                    if bounded:
                        nxt = sample_tokens_bounded(
                            sub, logits, temperature=temp, top_k=topk,
                            top_p=topp, k_cap=k_cap)
                    else:
                        nxt = sample_tokens(sub, logits, temperature=temp,
                                            top_k=topk, top_p=topp)
                    nxt = jnp.where(act, nxt, tokens)
                    fstate = jnp.where(
                        act, fsm_advance(fstate, ftrans, nxt), fstate)
                    done = done | (act & (nxt == eos))
                    ctx = jnp.where(act, ctx + 1, ctx)
                    out = jnp.where(act, nxt, -1)
                    return (nxt, fstate, ctx, done, rng, pages, cnt), out

                done0 = jnp.zeros_like(active0)
                (tok_state, fsm_state, _, _, _, pages, cnt), toks = (
                    jax.lax.scan(
                        body,
                        (tok_state, fsm_state, ctx, done0, rng, pages, cnt0),
                        jnp.arange(n_steps, dtype=jnp.int32)))
                return _outs((toks, tok_state, fsm_state, pages), cnt)

            prog = self._program(fn, (1, 2, 5))
        elif sampled:
            def fn(params, tok_state, ctx, remaining, pages, tables,
                   temp, topk, topp, rng, eos):
                active0 = ctx > 0

                def body(carry, i):
                    tokens, ctx, done, rng, pages, cnt = carry
                    act = active0 & ~done & (i < remaining)
                    logits, pages, cnt = _step_core(
                        params, tokens, ctx, act, pages, tables, cnt)
                    rng, sub = jax.random.split(rng)
                    if bounded:
                        nxt = sample_tokens_bounded(
                            sub, logits, temperature=temp, top_k=topk,
                            top_p=topp, k_cap=k_cap)
                    else:
                        nxt = sample_tokens(sub, logits, temperature=temp,
                                            top_k=topk, top_p=topp)
                    nxt = jnp.where(act, nxt, tokens)
                    done = done | (act & (nxt == eos))
                    ctx = jnp.where(act, ctx + 1, ctx)
                    out = jnp.where(act, nxt, -1)
                    return (nxt, ctx, done, rng, pages, cnt), out

                done0 = jnp.zeros_like(active0)
                (tok_state, _, _, _, pages, cnt), toks = jax.lax.scan(
                    body, (tok_state, ctx, done0, rng, pages, cnt0),
                    jnp.arange(n_steps, dtype=jnp.int32))
                return _outs((toks, tok_state, pages), cnt)

            prog = self._program(fn, (1, 4))
        else:
            def fn(params, tok_state, ctx, remaining, pages, tables, eos):
                active0 = ctx > 0

                def body(carry, i):
                    tokens, ctx, done, pages, cnt = carry
                    act = active0 & ~done & (i < remaining)
                    logits, pages, cnt = _step_core(
                        params, tokens, ctx, act, pages, tables, cnt)
                    nxt = greedy_tokens(logits)
                    nxt = jnp.where(act, nxt, tokens)
                    done = done | (act & (nxt == eos))
                    ctx = jnp.where(act, ctx + 1, ctx)
                    out = jnp.where(act, nxt, -1)
                    return (nxt, ctx, done, pages, cnt), out

                done0 = jnp.zeros_like(active0)
                (tok_state, _, _, pages, cnt), toks = jax.lax.scan(
                    body, (tok_state, ctx, done0, pages, cnt0),
                    jnp.arange(n_steps, dtype=jnp.int32))
                return _outs((toks, tok_state, pages), cnt)

            prog = self._program(fn, (1, 4))
        self._decode_cache[key] = prog
        return prog

    def mesh_axes(self) -> dict[str, int]:
        """{axis: size} of the serving mesh ({} off-mesh) — the exporter's
        ``mesh_axes`` topology gauge."""
        return dict(self.mesh.shape) if self.mesh is not None else {}

    @staticmethod
    def _spec_class(lanes) -> str:
        """Request class for adaptive speculation: greedy and sampled
        traffic accept at very different rates (diagnosis queries quote
        verbatim under greedy; sampled lanes diverge from the draft), so
        their kill-switches are tracked separately.  A mixed batch is
        scored as its most divergent member."""
        return ("greedy"
                if all(s.req.sampling.temperature <= 0.0 for _, s in lanes)
                else "sampled")

    @property
    def _spec_ema(self) -> Optional[float]:
        """Back-compat scalar view of the per-class acceptance EMAs: the
        best class (a single healthy class keeps the scalar above the
        floor, mirroring the pre-class behavior for one-class traffic)."""
        snap = self._spec_accept.snapshot()
        return max(snap.values()) if snap else None

    def spec_accept_ema(self) -> dict:
        """{request class: accepted-tokens-per-lane-round EMA} for the
        exporter's ``spec_accept_ema`` gauge."""
        return self._spec_accept.snapshot()

    def _spec_program(self, k: int, rounds: int, sampled: bool,
                      filtered: bool = False):
        """Build (and cache) the fused speculative-decode program.

        Each scanned round, entirely on device: write the current token into
        the history row, propose ``k`` draft tokens by n-gram lookup
        (serving/spec.py), verify all ``k+1`` positions in one forward
        (llama.verify_step), accept a draft prefix plus the model's
        correction/bonus token, and advance ctx by the accepted count.
        Rejected positions' K/V stays beyond context_lens — masked, then
        overwritten — so there is no rollback.

        ``sampled=False``: argmax acceptance, bit-identical to the
        sequential greedy path.  ``sampled=True``: the delta-draft
        speculative-sampling rule (spec.accept_sampled) against the same
        temperature/top-k/top-p-filtered distribution sequential decode
        samples from, with greedy lanes handled in the same call.

        Returns (toks [rounds*(k+1), B] with -1 padding, tok_state, pages,
        hist, stats [2] = [verify rounds run, lane-rounds run]).
        """
        key = ("spec", k, rounds, sampled, filtered)
        prog = self._decode_cache.get(key)
        if prog is not None:
            return prog

        cfg = self.cfg
        H = self._hist.shape[1]

        # Named apart from the fused-decode family (``fn`` -> XLA module
        # ``jit_fn``), so a device trace tells the two kinds of call apart.
        def spec_decode_fn(params, tok_state, ctx, quota, pages, tables, hist,
                           temp, topk, topp, rng, eos):
            active0 = ctx > 0
            B = tok_state.shape[0]
            lane = jnp.arange(B, dtype=jnp.int32)

            def body(carry, _):
                tok, ctx, quota, done, rng, pages, hist = carry
                act = active0 & ~done & (quota > 0)
                # Current token enters history at its own position (writes
                # at/after H, or by inactive lanes, are dropped).
                wcol = jnp.where(act & (ctx < H), ctx, H)
                hist = hist.at[lane, wcol].set(tok, mode="drop")
                drafts = propose_drafts(hist, ctx, tok, k)
                toks_in = jnp.concatenate([tok[:, None], drafts], axis=1)
                lengths = jnp.where(act, k + 1, 0).astype(jnp.int32)
                logits, pages = llama.verify_step(
                    params, cfg, toks_in, ctx, lengths, pages, tables,
                    attn_impl=self._verify_impl)
                if sampled:
                    rng, sub = jax.random.split(rng)
                    # `filtered` is a static program property: batches with
                    # no top-k/top-p lane skip the full-vocab rank sort
                    # inside accept_sampled (plain softmax, same dist).
                    emit, out = accept_sampled(
                        sub, logits, drafts, quota, act, eos, temp,
                        top_k=topk if filtered else None,
                        top_p=topp if filtered else None)
                else:
                    greedy = jnp.argmax(logits, axis=-1).astype(jnp.int32)
                    emit, out = accept_greedy(greedy, drafts, quota, act, eos)
                # Accepted tokens extend the history at ctx+1+i.  Padding
                # (-1) columns are redirected to H and dropped.
                cols = (ctx[:, None] + 1
                        + jnp.arange(k + 1, dtype=jnp.int32)[None, :])
                cols = jnp.where((out >= 0) & (cols < H), cols, H)
                hist = hist.at[lane[:, None], cols].set(out, mode="drop")
                last = jnp.take_along_axis(
                    out, jnp.maximum(emit - 1, 0)[:, None], axis=1)[:, 0]
                tok = jnp.where(act & (emit > 0), last, tok)
                # out's -1 padding must not match an unset eos_id of -1.
                done = done | (act & jnp.any((out == eos) & (out >= 0), 1))
                ctx = ctx + jnp.where(act, emit, 0)
                quota = quota - jnp.where(act, emit, 0)
                # Stats row: [rounds that ran a forward, lane-rounds] — the
                # latter divides spec_tokens into true per-lane acceptance.
                stats = jnp.stack([jnp.any(act).astype(jnp.int32),
                                   jnp.sum(act.astype(jnp.int32))])
                return (tok, ctx, quota, done, rng, pages, hist), (out, stats)

            done0 = jnp.zeros_like(active0)
            carry, (outs, stats) = jax.lax.scan(
                body, (tok_state, ctx, quota, done0, rng, pages, hist),
                None, length=rounds)
            tok_state, _, _, _, _, pages, hist = carry
            # [R, B, k+1] -> [R*(k+1), B]: chronological per lane, matching
            # the reconcile contract of the fused decode program.
            toks = jnp.transpose(outs, (0, 2, 1)).reshape(rounds * (k + 1), B)
            return toks, tok_state, pages, hist, jnp.sum(stats, axis=0)

        prog = jax.jit(spec_decode_fn, donate_argnums=(1, 4, 6))
        self._decode_cache[key] = prog
        return prog

    def _decode_lanes(self) -> list[tuple[int, "_Slot"]]:
        """Slots eligible for a decode dispatch right now.  Recomputed after
        any reconcile/preemption point that can retire or admit slots."""
        return [(i, s) for i, s in enumerate(self._slots)
                if s is not None and not s.retired and not s.prefilling
                and s.remaining_pred > 0 and not s.cancel_requested]

    def _dispatch_decode(self) -> bool:
        """Dispatch one fused decode call over lanes with predicted budget.
        Returns True if a call was dispatched."""
        ec = self.ecfg
        B = ec.max_slots

        # Retire cancelled lanes that have fully settled; exclude the rest
        # from new dispatches (their in-flight steps drain via reconcile).
        # A cancelled slot still mid-prefill (prefilling) never reaches the
        # admit reconcile that clears pending_admit, so it settles once its
        # chunk calls drain.
        for i, s in enumerate(self._slots):
            if (s is not None and s.cancel_requested
                    and s.inflight_decode == 0 and s.inflight_chunks == 0
                    and (s.prefilling or not s.pending_admit)):
                self._retire(i)

        lanes = self._decode_lanes()
        if not lanes:
            return False

        if any(c.kind == "spec" for c in self._inflight):
            # A spec call's emission is data-dependent, so ctx_pred for its
            # lanes is an upper bound while it is in flight.  ANY follow-up
            # decode dispatch (spec or not — a sampled admission can flip
            # the batch to the fused path) must wait for reconciled ctx, or
            # it would run lanes at inflated positions whose attention
            # window covers rejected-draft KV.
            self._reconcile_all()
            lanes = self._decode_lanes()
            if not lanes:
                return False

        # Every sampling mode speculates: greedy by argmax match, sampled
        # by the delta-draft rule against the same filtered distribution
        # sequential decode samples from (spec.accept_sampled).  Whether a
        # given dispatch speculates is ADAPTIVE: below the measured
        # acceptance threshold the fused pipelined path wins, so spec runs
        # only as a periodic probe until acceptance recovers.  Grammar-
        # constrained lanes force spec off: the verify pass samples from
        # unmasked positions, so accepted drafts could violate the grammar.
        spec = ec.spec_k > 0 and not any(
            s.req.sampling.constrained for _, s in lanes)
        if spec and self._brownout_level() >= 1:
            # DEGRADED or worse: a verify forward costs more than a fused
            # step and serializes the pipeline — the brownout ladder sheds
            # the speculative gamble before it sheds any request.
            spec = False
        if spec:
            spec = self._spec_accept.should_draft(self._spec_class(lanes))
        if spec:
            # Emission per spec call is data-dependent (1..k+1 per round),
            # so a dispatch-ahead call would run with an overestimated ctx
            # and read unmasked garbage.  Drain the pipeline first: spec
            # trades dispatch-ahead depth for multi-token verify rounds.
            if self._inflight:
                self._reconcile_all()
                lanes = self._decode_lanes()
                if not lanes:
                    return False
            # Per-lane quota: the most a call can emit if every round
            # accepts the full draft.
            K = ec.spec_rounds_per_iter * (ec.spec_k + 1)
        else:
            kmax = min(ec.decode_steps_per_iter,
                       max(s.remaining_pred for _, s in lanes))
            K = 1 << (kmax.bit_length() - 1)

        # Ensure pages for each lane's next min(K, remaining) KV writes.  On
        # pressure, drain in-flight work (so preemption sees reconciled
        # state) and evict the lowest-class, youngest active slot so the
        # oldest protected work always makes progress; the victim may be
        # the failing lane itself, evicting itself.
        for i, s in sorted(lanes, key=lambda t: t[1].req.submit_time):
            if self._slots[i] is not s or s.retired:
                continue  # evicted/retired during the pressure loop below
            steps_i = max(1, min(K, s.remaining_pred))
            while True:
                try:
                    self.allocator.extend(s.blocks, s.ctx_pred + steps_i)
                    break
                except OutOfBlocks:
                    # Cheapest relief first: demote cached prefixes nobody
                    # is actively using to the host tier (or drop them)
                    # before draining/preempting live work.
                    if self._evict_prefix_lru():
                        continue
                    self._reconcile_all()
                    if self._slots[i] is not s or s.retired:
                        break
                    try:
                        self.allocator.extend(s.blocks, s.ctx_pred + steps_i)
                        break
                    except OutOfBlocks:
                        victim = self._eviction_victim()
                        if victim < 0:
                            victim = i  # only cancelled lanes left: self-evict
                        try:
                            self._faults.maybe_raise("lane_eviction")
                        except FaultError as exc:
                            # Mid-eviction failure: fall back to evicting
                            # the requesting lane itself — always safe
                            # (recompute-requeue) and never leaves an
                            # unextended lane in the dispatch.
                            self._record_dispatch_failure(exc)
                            victim = i
                        self._preempt(victim)
                        if victim == i:
                            break

        lanes = self._decode_lanes()
        if not lanes:
            return False

        ctx = np.zeros((B,), np.int32)
        steps_arr = np.zeros((B,), np.int32)
        table = np.zeros((B, ec.max_blocks_per_seq), np.int32)
        temp = np.zeros((B,), np.float32)
        topk = np.zeros((B,), np.int32)
        topp = np.ones((B,), np.float32)
        meta = []
        for i, s in lanes:
            steps_i = min(K, s.remaining_pred)
            ctx[i] = s.ctx_pred
            steps_arr[i] = steps_i
            table[i, : len(s.blocks)] = s.blocks
            sp = s.req.sampling
            temp[i], topk[i], topp[i] = sp.temperature, sp.top_k, sp.top_p
            s.inflight_decode += steps_i
            # Keep the slot object: by reconcile time the index may host a
            # different request (zombie lane whose slot was reused).
            meta.append((i, s, steps_i))

        eos = jnp.asarray(self.eos_id, jnp.int32)
        all_greedy = all(s.req.sampling.temperature <= 0.0 for _, s in lanes)
        # Recomputed from the final lane set (preemption above may have
        # evicted the constrained lane): any constrained lane selects the
        # FSM program; its free co-lanes run masked-by-nothing at state 0.
        constrained = (self._fsm_trans is not None and any(
            s.req.sampling.constrained for _, s in lanes))
        # Filters only matter on lanes that actually sample: a greedy lane
        # carrying top_p (a common client default) never has them read.
        any_filtered = any(s.req.sampling.filtered for _, s in lanes)
        device_empty = self._device_empty()
        try:
            self._faults.maybe_raise("decode_dispatch")
            self.in_program_call = True
            t_dispatch = time.monotonic()
            payload, kind, program = self._dispatch_decode_call(
                spec and not constrained, all_greedy, lanes, K, ctx,
                steps_arr, table, temp, topk, topp, eos,
                constrained=constrained, any_filtered=any_filtered)
        except Exception as exc:
            # Nothing reached the device: undo the in-flight accounting so
            # the same lanes re-dispatch next step (ctx_pred derives from
            # inflight_decode, so it rewinds with it).
            for _, s, steps_i in meta:
                s.inflight_decode -= steps_i
            self._record_dispatch_failure(exc)
            if self.consecutive_dispatch_failures > ec.max_requeues:
                # The same call keeps failing before it reaches the device
                # — a program the compiler refuses fails identically every
                # step.  Bound it like a failed prefill dispatch: requeue
                # the lanes (max_requeues), then fail them with the cause,
                # instead of spinning on it with the callers waiting.
                self._reset_pipeline(f"decode dispatch failed: {exc!r}")
            return False
        finally:
            self.in_program_call = False
            self.last_program_call = time.monotonic()
            _calling.program = ""
        self._record_dispatch_ok()
        if self._faults.should_fire("decode_stuck"):
            payload = _StuckPayload(payload)
        groups = {}
        if kind == "decode" and not self.cfg.latent:
            # What the fused kernel fetches at each step of the call: lane
            # i at ctx[i] + s cached tokens while s < its steps.
            s = np.arange(K)[:, None]
            groups["kv_groups"], groups["kv_run_groups"] = fetch_groups(
                table, np.where(s < steps_arr, ctx + s, 0), ec.block_size)
        self._inflight.append(_Inflight(
            kind=kind, call_id=self._next_call_id, arr=payload, lanes=meta,
            t0=t_dispatch,
            span_attrs=self._call_attrs(
                kind, program, device_empty,
                sampler_filter=(None if all_greedy and not constrained
                                else any_filtered),
                steps=K, lanes=len(lanes), slots=B,
                ctx_tokens=int(ctx.sum()), **groups),
            moe_counts=self._take_moe_counts()))
        self._next_call_id += 1
        return True

    def _dispatch_decode_call(self, spec: bool, all_greedy: bool, lanes,
                              K: int, ctx, steps_arr, table, temp, topk,
                              topp, eos, constrained: bool = False,
                              any_filtered: bool = False):
        """The device-call half of :meth:`_dispatch_decode`, split out so
        the dispatch fault/rollback boundary wraps exactly the program
        call.  Returns ``(payload, kind, program name)``."""
        ec = self.ecfg
        if constrained:
            # Grammar-masked fused decode: always the sampled program family
            # (greedy lanes take argmax-of-masked inside the sampler), FSM
            # state threaded through the scan carry and the device-resident
            # [max_slots] buffer, exactly like _tok_state.
            cap = ec.sample_topk_cap
            bounded = cap > 0 and all(
                0 < s.req.sampling.top_k <= cap
                for _, s in lanes if s.req.sampling.temperature > 0.0)
            prog = self._decode_program(K, sampled=True, bounded=bounded,
                                        constrained=True)
            program = _calling.program = (
                f"decode_k{K}_sampled{'_bounded' if bounded else ''}_fsm")
            self._rng, sub = jax.random.split(self._rng)
            toks, self._tok_state, self._fsm_state, self.pages = prog(
                self.params, self._tok_state, self._fsm_state,
                jnp.asarray(ctx), jnp.asarray(steps_arr), self.pages,
                jnp.asarray(table), self._fsm_trans, jnp.asarray(temp),
                jnp.asarray(topk), jnp.asarray(topp), sub, eos,
            )
            payload: Any = toks
            kind = "decode"
            self.steps += K
            try:
                toks.copy_to_host_async()
            except AttributeError:
                pass
            return payload, kind, program
        if spec:
            # The filtered variant (an extra compile, full-vocabulary sorts
            # every round) only when a lane that samples has a filter —
            # never on an all-greedy call.
            prog = self._spec_program(ec.spec_k, ec.spec_rounds_per_iter,
                                      sampled=not all_greedy,
                                      filtered=any_filtered)
            program = _calling.program = (
                f"spec_k{ec.spec_k}_r{ec.spec_rounds_per_iter}_"
                + ("greedy" if all_greedy
                   else "filtered" if any_filtered else "sampled"))
            self._rng, sub = jax.random.split(self._rng)
            toks, self._tok_state, self.pages, self._hist, nver = prog(
                self.params, self._tok_state, jnp.asarray(ctx),
                jnp.asarray(steps_arr), self.pages, jnp.asarray(table),
                self._hist, jnp.asarray(temp), jnp.asarray(topk),
                jnp.asarray(topp), sub, eos,
            )
            payload: Any = (toks, nver)
            kind = "spec"
        elif all_greedy:
            prog = self._decode_program(K, sampled=False)
            program = _calling.program = f"decode_k{K}_greedy"
            toks, self._tok_state, self.pages = prog(
                self.params, self._tok_state, jnp.asarray(ctx),
                jnp.asarray(steps_arr), self.pages, jnp.asarray(table), eos,
            )
            payload = toks
            kind = "decode"
            self.steps += K
        else:
            # Bounded top-k sampling is exact only when every lane that
            # actually samples keeps at most sample_topk_cap tokens.
            cap = ec.sample_topk_cap
            bounded = cap > 0 and all(
                0 < s.req.sampling.top_k <= cap
                for _, s in lanes if s.req.sampling.temperature > 0.0)
            prog = self._decode_program(K, sampled=True, bounded=bounded)
            program = _calling.program = (
                f"decode_k{K}_sampled{'_bounded' if bounded else ''}")
            self._rng, sub = jax.random.split(self._rng)
            toks, self._tok_state, self.pages = prog(
                self.params, self._tok_state, jnp.asarray(ctx),
                jnp.asarray(steps_arr), self.pages, jnp.asarray(table),
                jnp.asarray(temp), jnp.asarray(topk), jnp.asarray(topp),
                sub, eos,
            )
            payload = toks
            kind = "decode"
            self.steps += K
        try:
            toks.copy_to_host_async()
        except AttributeError:
            pass
        return payload, kind, program

    # -- reconciliation -------------------------------------------------

    def _reconcile_one(self) -> None:
        call = self._inflight.popleft()
        try:
            with self._phase("engine.step.wait_device"):
                # Everything the host waits for the device here, whichever
                # way it waits: the watchdog's poll loop, or the blocking
                # conversion when the watchdog is off.
                if not self._await_call(call):
                    return
                if self._faults.should_fire("slow_host_callback"):
                    time.sleep(self._faults.delay_s("slow_host_callback"))
                waited = not self._call_ready(call)
                arr = self._fetch_call(call)
                if waited:      # the fetch's return is the first sighting
                    call.t_ready = time.monotonic()
                self._count_device_time(call, waited)
            with self._phase("engine.step.apply"):
                self._apply_call(call, arr)
        except Exception as exc:
            # A failed host conversion (device error surfacing, injected
            # stuck payload with the watchdog off) poisons the donated
            # buffer chain: reset and recompute.
            self._record_dispatch_failure(exc)
            self._reset_pipeline(
                f"reconcile of {call.kind} call failed: {exc}",
                extra_calls=(call,))
            return
        # Release deferred frees that no in-flight call references anymore.
        if self._deferred_frees:
            still = []
            for after_id, blocks in self._deferred_frees:
                if after_id <= call.call_id:
                    self.allocator.free(blocks)
                else:
                    still.append((after_id, blocks))
            self._deferred_frees = still

    def _count_device_time(self, call: _Inflight, waited: bool) -> None:
        """The call's seconds at the head of the device queue: the device
        runs calls in the order they were enqueued, so a call holds the
        head from the moment the one before it finished — or from its own
        dispatch, if that came later — until it finishes.  Both ends are
        the step thread's first sightings (``t_ready``), so a call that
        finished unseen lends the time until it was seen to the next one;
        with ``waited`` the end is the blocking fetch's return."""
        device_s = call.t_ready - max(self._last_ready, call.t0)
        self._last_ready = call.t_ready
        self.device_seconds[call.kind] = (
            self.device_seconds.get(call.kind, 0.0) + device_s)
        call.span_attrs.update(device_s=device_s, waited=int(waited))

    def _await_call(self, call: _Inflight) -> bool:
        """Watchdog: poll readiness instead of blocking in np.asarray — a
        wedged device call must trip recovery, not hang the loop.  False
        when the budget ran out and the pipeline was reset."""
        budget = self.ecfg.dispatch_timeout_s
        if budget <= 0:
            return True
        t0 = time.monotonic()
        while not self._call_ready(call):
            if time.monotonic() - t0 >= budget:
                self.watchdog_trips += 1
                if self.health is not None:
                    self.health.record_watchdog_trip()
                self._reset_pipeline(
                    f"dispatch watchdog: {call.kind} call not ready "
                    f"after {budget:.2f}s", extra_calls=(call,))
                return False
            time.sleep(0.002)
        return True

    def _fetch_call(self, call: _Inflight) -> np.ndarray:
        """The call's token matrix on the host (blocks until the device
        has it); a spec call's verify statistics are booked on the way."""
        if call.kind != "spec":
            return np.asarray(call.arr)
        toks, stats = call.arr
        arr = np.asarray(toks)
        ran, lane_rounds = (int(x) for x in np.asarray(stats))
        self.spec_verify_steps += ran
        self.spec_lane_rounds += lane_rounds
        self.steps += ran
        if lane_rounds:
            # Per-class acceptance EMA drives the adaptive spec/fused
            # choice; the class is derived from the slots this call
            # actually ran (meta holds the slot objects, so reuse of
            # the lane index after dispatch cannot misattribute).
            self._spec_accept.update(
                self._spec_class((i, s) for i, s, _ in call.lanes),
                int(np.sum(arr >= 0)), lane_rounds)
        return arr

    def _apply_call(self, call: _Inflight, arr: np.ndarray) -> None:
        """Apply one call's result, now on the host, to its slots (token
        emission, retirement, chunk/decode accounting)."""
        now = time.monotonic()
        attrs = call.span_attrs
        if call.kind in ("admit", "chunk"):
            for s in call.touched:           # chunk calls: drain refcounts
                s.inflight_chunks -= 1
            rows = (enumerate(call.lanes) if call.kind == "admit"
                    else ((row, (slot_idx, req))
                          for row, slot_idx, req in call.lanes))
            span_name = ("engine.prefill" if call.kind == "admit"
                         else "engine.prefill_chunk")
            lane_attrs = {"bucket": attrs["bucket"],
                          "lanes": attrs["prompts"]}
            if call.kind == "admit":
                lane_attrs["shared"] = bool(attrs["shared"])
            for j, (slot_idx, req) in rows:
                s = self._slots[slot_idx]
                if s is None or s.req is not req:
                    continue  # preempted before reconcile
                tok = int(arr[j])
                s.pending_admit = False
                s.generated.append(tok)
                if req.first_token_time == 0.0:
                    req.first_token_time = now
                    self._observe_ttft(now - req.submit_time, req.slo_class,
                                       trace_id=self._trace_id(req))
                s.first_token_time = req.first_token_time
                self._span(span_name, call.t0, now, req,
                           constrained=req.sampling.constrained,
                           **lane_attrs)
                self._emit(req, [tok])
                if self._is_finished(s) or s.cancel_requested:
                    self._retire(slot_idx)
        else:
            span_name = ("engine.spec_decode" if call.kind == "spec"
                         else "engine.decode")
            emitted = 0
            for slot_idx, s, steps_i in call.lanes:
                if self._slots[slot_idx] is not s or s.retired:
                    continue  # lane EOSed in an earlier call; discard zombies
                new = [int(t) for t in arr[:, slot_idx] if t >= 0]
                emitted += len(new)
                s.inflight_decode -= steps_i
                if call.kind == "spec":
                    self.spec_tokens += len(new)
                lane_attrs = {"steps": steps_i, "emitted": len(new)}
                if call.kind == "spec":
                    lane_attrs["rounds"] = self.ecfg.spec_rounds_per_iter
                self._span(span_name, call.t0, now, s.req, **lane_attrs)
                if not new:
                    continue
                s.ctx_len += len(new)
                s.generated.extend(new)
                self._emit(s.req, new)
                if self._is_finished(s) or (s.cancel_requested
                                            and s.inflight_decode == 0):
                    self._retire(slot_idx)
            # Tokens that reached a request: a zombie lane's (module
            # docstring) were computed and are not counted.
            attrs["emitted"] = emitted
            self.decode_tokens += emitted
        if call.moe_counts is not None:
            # Outputs of the same program as ``arr``: on the host already,
            # or a moment behind it.
            counts = {name: float(c)
                      for group, got in call.moe_counts.items()
                      for name, c in zip(self._count_names[group],
                                         np.asarray(got))}
            if self._routed:
                slots = counts["moe_expert_layer_steps"]
                # The mean rows of an expert, summed as the fullest expert's
                # are: each layer and step adds its assignments / experts.
                counts["moe_mean_rows"] = (counts["moe_assignments"]
                                           / self.cfg.experts_held_)
                if "moe_assignments_all" in counts:
                    self.moe_totals["assignments_all"] += int(
                        counts["moe_assignments_all"])
                self.moe_totals["assignments"] += int(
                    counts["moe_assignments"])
                self.moe_totals["experts_hit"] += int(
                    counts["moe_expert_layer_steps_hit"])
                self.moe_totals["expert_slots"] += int(slots)
            for name in SEL_COUNTS:
                if name in counts:
                    self.sel_totals[name] += int(counts[name])
            attrs.update(counts)
        if self._loop_sampled:
            # dispatch -> first seen ready: includes the time queued
            # behind earlier calls; ``device_s`` is the call's own share.
            self._tracer.record("engine.call", call.t0, call.t_ready,
                                self._maint_ctx, attrs=attrs)

    def _observe_ttft(self, ttft_s: float,
                      slo_class: str = DEFAULT_CLASS,
                      trace_id: str = "") -> None:
        self.hist_ttft.observe(ttft_s, slo_class, trace_id)
        prev = self.ttft_ema_by_class.get(slo_class)
        self.ttft_ema_by_class[slo_class] = (
            ttft_s if prev is None else 0.9 * prev + 0.1 * ttft_s)

    def _is_finished(self, s: _Slot) -> bool:
        return bool(s.generated) and (
            s.generated[-1] == self.eos_id
            or len(s.generated) >= s.req.sampling.max_tokens)

    def _retire(self, slot_idx: int) -> None:
        s = self._slots[slot_idx]
        assert s is not None
        now = time.monotonic()
        # Tokens generated before a preemption live in the folded prompt tail.
        toks = s.req.prompt_ids[s.req.orig_prompt_len:] + s.generated
        reason = "eos" if toks and toks[-1] == self.eos_id else "length"
        if reason == "eos":
            toks = toks[:-1]
        error = ""
        if s.abort_cause:
            # Deadline-aborted (or otherwise force-failed) slot: the result
            # carries the cause and whatever tokens were already streamed.
            reason, error = "error", s.abort_cause
        result = GenerationResult(
            request_id=s.req.request_id,
            token_ids=toks,
            finish_reason=reason,
            error=error,
            # A slot cancelled mid-prefill retires with no first token.
            ttft_s=(s.first_token_time - s.req.submit_time
                    if s.first_token_time > 0.0 else 0.0),
            latency_s=now - s.req.submit_time,
        )
        self._results[s.req.request_id] = result
        self.hist_e2e.observe(result.latency_s, s.req.slo_class,
                              self._trace_id(s.req))
        self._end_request_span(
            s.req, "error" if reason == "error" else "ok",
            finish_reason=reason, tokens=len(toks),
            ttft_s=round(result.ttft_s, 6))
        if self.token_sink is not None:
            self.token_sink(s.req.request_id, [], result)
        if self._inflight:
            # In-flight calls may still write into these pages (zombie
            # steps); free only after the newest dispatched call reconciles.
            self._deferred_frees.append(
                (self._next_call_id - 1, s.blocks))
        else:
            self.allocator.free(s.blocks)
        s.retired = True
        self._slots[slot_idx] = None

    def _preempt(self, slot_idx: int) -> None:
        """Evict a slot, folding generated tokens into a new prompt.

        Only called on reconciled state (_dispatch_decode drains in-flight
        work before preempting), so ``generated`` is complete."""
        s = self._slots[slot_idx]
        assert (s is not None and s.inflight_decode == 0
                and s.inflight_chunks == 0)
        self.allocator.free(s.blocks)
        self._slots[slot_idx] = None
        s.retired = True
        req = s.req
        # Already-sampled tokens become prompt; budget shrinks accordingly.
        consumed = len(s.generated)
        req.prompt_ids = req.prompt_ids + s.generated
        req.sampling = dataclasses.replace(
            req.sampling, max_tokens=max(1, req.sampling.max_tokens - consumed)
        )
        self._cap_request(req)  # re-apply the submit-time capacity cap
        self._pending.appendleft(req)
        self.preemptions += 1
        self.preemptions_by_class[req.slo_class] = (
            self.preemptions_by_class.get(req.slo_class, 0) + 1)
        t_now = time.monotonic()
        self._span("engine.preempt", t_now, t_now, req,
                   tokens_folded=consumed)
        self._flight.note("preempt", request_id=req.request_id,
                          slo_class=req.slo_class, tokens_folded=consumed)
