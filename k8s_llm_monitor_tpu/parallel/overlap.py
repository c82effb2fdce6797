"""Latency-hiding tensor-parallel decode: hand-staged collective schedule.

The GSPMD decode path annotates o/down projections row-parallel and lets
XLA insert a blocking ring all-reduce after each one — 2 per layer, each
serializing ``2*(tp-1)/tp`` of a [B, H] activation over ICI before the
next matmul may start.  This module replaces that schedule for the
per-layer decode loop with an explicit ``shard_map`` program that keeps
the residual stream REDUCE-SCATTERED between sub-blocks:

    per layer (all shard-local unless marked):
      x_full   = all_gather(x_scat)                   <- AG half
      h        = rms_norm(x_full, input_norm)
      q,k,v    = column-parallel projections (local head slice) + rope
      pages    = scatter k/v into the LOCAL kv-head page slice
      attn     = paged attention over local heads (no collective — pages
                 shard on kv-head boundaries, parallel/sharding.py)
      o_part   = row-parallel o partial
      o_scat   = psum_scatter(o_part)                 <- RS half
      x_scat  += o_scat
      h        = rms_norm(all_gather(x_scat), post_norm)   <- AG half
      gate,up  = column-parallel (local I slice); act fuse
      d_part   = row-parallel down partial
      x_scat  += psum_scatter(d_part)                 <- RS half
    final: all_gather(x_scat) -> replicated residual for the unembed

Why this hides wire time: a blocking all-reduce is one fused
collective-permute chain the scheduler cannot split, so the weight
streaming (HBM->VMEM) of the NEXT column-parallel matmul — which does not
depend on the in-flight activation — waits behind it.  Decomposed into
reduce-scatter + all-gather, each half lowers to an async
collective-start/done pair, and XLA's latency-hiding scheduler hoists the
data-independent weight prefetch (and the page-scatter DMAs) between
start and done.  Decode is weight-streaming bound, so that window is
expected to be larger than the wire time; how much of it is hidden on a
chip is the device trace's to say (not measured).

Exactness vs the GSPMD reference (the parity tests in
tests/test_overlap.py prove byte-identical greedy tokens):

  * ``all_gather`` is a pure concatenation of a consistent scatter;
    ``dynamic_slice`` of the replicated residual is its inverse.
  * Chunked residual adds commute with slicing elementwise.
  * Column-parallel projections run the SAME shard-local matmul GSPMD
    partitions to (params arrive pre-sharded; per-out-channel int8
    scales shard with the out dim, so ``_linear`` applies unchanged).
  * Row-parallel reductions go through
    ``models/llama.py:row_parallel_partial``: W8A8 combines the global
    per-token amax with ``pmax`` (max is order-independent) and reduces
    the raw int32 partials (integer addition is associative) before the
    float scales apply — the same reduce-then-scale order GSPMD uses.
  * Per-shard paged attention is per-head independent; GQA groups align
    with the shard cuts when ``tp | num_kv_heads`` (the support gate).

Embed lookup and the unembed stay OUTSIDE the shard_map under plain
GSPMD (vocab-parallel, replicated result) — they run once per step, not
per layer, and keeping them on the reference path removes two parity
surfaces for free.

Flag-selectable exactly like the PR 1 decode-path oracle:
``EngineConfig.tp_overlap`` ("auto" | "on" | "off"), with the GSPMD
program kept as the always-available correctness reference.
"""

from __future__ import annotations

import functools

import jax
from jax.sharding import PartitionSpec as P

from k8s_llm_monitor_tpu.models.config import ModelConfig
from k8s_llm_monitor_tpu.models.llama import (
    KVPages,
    _attn_extras,
    _embed_lookup,
    _linear,
    _mlp_act,
    _scatter_pages,
    _scatter_pages_quant,
    _unembed,
    row_parallel_partial,
)
from k8s_llm_monitor_tpu.ops.attention import (
    _pallas_geometry_ok,
    paged_decode_attention,
    paged_decode_attention_quant,
)
from k8s_llm_monitor_tpu.ops.pallas_attention import (
    paged_decode_attention_pallas,
)
from k8s_llm_monitor_tpu.ops.norms import rms_norm
from k8s_llm_monitor_tpu.ops.rope import apply_rope, rope_angles
from k8s_llm_monitor_tpu.parallel.sharding import (
    kv_pages_partition_specs,
    param_partition_specs,
)

#: The TP axis every collective in the staged schedule runs over.
MODEL_AXIS = "model"


def overlap_supported(cfg: ModelConfig, mesh, params=None) -> str:
    """"" when the staged overlap schedule can serve ``(cfg, mesh)``;
    otherwise a human-readable reason.  The engine logs the reason and
    keeps the GSPMD program ("auto"), or raises it ("on") — never a
    silent numerics change.

    The gates mirror the regimes where the hand schedule would NOT be a
    pure re-staging of the GSPMD program:
      * no mesh / model axis 1 — nothing to overlap;
      * TP not dividing the (kv-)head count — pages replicate instead of
        head-sharding (SpecLayout.kv_pages), so per-shard attention is no
        longer collective-free;
      * a latent mixer — its pages have no kv-head axis (the engine
        refuses the mesh itself, serving/engine.py);
      * MoE — the expert layer (models/llama.py:_moe_mlp_routed) is no
        part of the staged schedule, with or without shared experts;
      * sandwich norms — post_attn_norm consumes the FULL o projection
        before the residual add, so the o reduce cannot stay scattered;
      * a bias on a row-parallel projection — it must be added exactly
        once, after the reduce (no supported checkpoint carries one).
    """
    if mesh is None:
        return "no mesh"
    if cfg.latent:
        return ("latent attention: the pool has no kv-head axis to shard, "
                "and the staged schedule's per-shard attention reads one")
    tp = mesh.shape.get(MODEL_AXIS, 1)
    if tp <= 1:
        return "model axis is 1"
    if (tp > cfg.num_kv_heads or cfg.num_kv_heads % tp != 0
            or cfg.num_heads % tp != 0):
        return (f"TP={tp} does not divide {cfg.num_heads} heads / "
                f"{cfg.num_kv_heads} KV heads (pages replicate)")
    if cfg.hidden_size % tp or cfg.intermediate_size % tp:
        return (f"TP={tp} does not divide hidden {cfg.hidden_size} / "
                f"intermediate {cfg.intermediate_size} (uneven scatter)")
    if cfg.num_experts > 0:
        return "MoE layers route through expert all-to-alls"
    if cfg.sandwich_norms:
        return "sandwich norms consume the full o projection pre-residual"
    if params is not None:
        layer0 = params["layers"][0]
        if "bias" in layer0["o"] or "bias" in layer0["down"]:
            return "row-parallel projection carries a bias"
    return ""


def _per_shard_attn(cfg: ModelConfig, tp: int, attn_path: str):
    """Per-shard paged decode attention matching the engine's resolved
    decode path, so overlap-on vs overlap-off differ ONLY in collective
    staging: "gather" keeps the XLA reference; anything else takes the
    Pallas kernel per shard (interpreter off-TPU), exactly what
    ops/attention.py:make_tp_paged_attention wraps for the GSPMD path."""
    if attn_path != "gather" and not cfg.has_attn_extras:
        interpret = jax.default_backend() != "tpu"
        if interpret or _pallas_geometry_ok(cfg, tp):
            return functools.partial(paged_decode_attention_pallas,
                                     interpret=interpret)
    return paged_decode_attention


def make_overlap_decode_step(mesh, cfg: ModelConfig, params, pages: KVPages,
                             *, attn_path: str = "gather"):
    """Build the staged decode step.

    Returns ``step(params, tokens, context_lens, pages, tables) ->
    (logits [B, V] float32, updated KVPages)`` — the exact calling
    convention of ``llama.decode_step`` minus ``attn_impl`` (the per-shard
    attention is resolved here from ``attn_path``), so the engine's
    ``_step_core`` swaps it in without touching the scan programs.

    ``params``/``pages`` are used for spec derivation only (tree
    structure); the returned step traces against whatever arrays the
    jitted caller passes.
    """
    tp = mesh.shape[MODEL_AXIS]
    quant = pages.quantized
    attn_fn = _per_shard_attn(cfg, tp, attn_path)
    aq = cfg.act_quant
    uo = cfg.rmsnorm_unit_offset
    eps = cfg.rms_norm_eps
    Hc = cfg.hidden_size // tp
    n_head_local = cfg.num_heads // tp
    n_kv_local = cfg.num_kv_heads // tp
    D = cfg.head_dim_

    layer_specs = param_partition_specs(params)["layers"]
    kv_specs = kv_pages_partition_specs(pages, mesh,
                                        num_kv_heads=cfg.num_kv_heads)
    rep2, rep3 = P(None, None), P(None, None, None)

    def _layers(layers, x_full, cos, sin, positions, active, new_lens,
                k_pages, v_pages, k_scales, v_scales, tables):
        B = x_full.shape[0]
        idx = jax.lax.axis_index(MODEL_AXIS)
        # Residual enters replicated (embed runs under GSPMD outside);
        # keep it reduce-scattered from here on.
        x_scat = jax.lax.dynamic_slice_in_dim(x_full, idx * Hc, Hc, axis=2)
        new_k, new_v, new_ks, new_vs = [], [], [], []
        for li, layer in enumerate(layers):
            x_full = jax.lax.all_gather(x_scat, MODEL_AXIS, axis=2,
                                        tiled=True)
            h = rms_norm(x_full, layer["input_norm"], eps, uo)
            # Column-parallel projections: params arrive as their local
            # shard, so _linear computes exactly the per-device matmul
            # GSPMD partitions to (out-dim int8 scales shard along).
            # Scope names as in models/llama.py:decode_step, so a device
            # trace reads the same on either schedule.
            with jax.named_scope("qkv"):
                q = _linear(layer["q"], h, aq).reshape(
                    B, 1, n_head_local, D)
                k = _linear(layer["k"], h, aq).reshape(B, 1, n_kv_local, D)
                v = _linear(layer["v"], h, aq).reshape(B, 1, n_kv_local, D)
                q = apply_rope(q, cos, sin)
                k = apply_rope(k, cos, sin)
            with jax.named_scope("attention"):
                if quant:
                    pk, psk = _scatter_pages_quant(
                        k_pages[li], k_scales[li], k, tables, positions,
                        active)
                    pv, psv = _scatter_pages_quant(
                        v_pages[li], v_scales[li], v, tables, positions,
                        active)
                    new_ks.append(psk)
                    new_vs.append(psv)
                    attn = paged_decode_attention_quant(
                        q, pk, pv, psk, psv, tables, new_lens,
                        **_attn_extras(cfg, li))
                else:
                    pk = _scatter_pages(k_pages[li], k, tables, positions,
                                        active)
                    pv = _scatter_pages(v_pages[li], v, tables, positions,
                                        active)
                    attn = attn_fn(q, pk, pv, tables, new_lens,
                                   **_attn_extras(cfg, li))
            new_k.append(pk)
            new_v.append(pv)
            with jax.named_scope("attn_out"):
                part, fin = row_parallel_partial(
                    layer["o"], attn.reshape(B, 1, -1), aq, MODEL_AXIS)
                x_scat = x_scat + fin(jax.lax.psum_scatter(
                    part, MODEL_AXIS, scatter_dimension=2, tiled=True))
            h = rms_norm(
                jax.lax.all_gather(x_scat, MODEL_AXIS, axis=2, tiled=True),
                layer["post_norm"], eps, uo)
            with jax.named_scope("mlp"):
                gate = _linear(layer["gate"], h, aq)
                up = _linear(layer["up"], h, aq)
                part, fin = row_parallel_partial(
                    layer["down"], _mlp_act(cfg, gate) * up, aq, MODEL_AXIS)
                x_scat = x_scat + fin(jax.lax.psum_scatter(
                    part, MODEL_AXIS, scatter_dimension=2, tiled=True))
        x_full = jax.lax.all_gather(x_scat, MODEL_AXIS, axis=2, tiled=True)
        return x_full, new_k, new_v, new_ks, new_vs

    sharded_layers = jax.shard_map(
        _layers, mesh=mesh,
        in_specs=(layer_specs, rep3, rep3, rep3, rep2, rep2, P(None),
                  kv_specs.k, kv_specs.v, list(kv_specs.k_scale),
                  list(kv_specs.v_scale), rep2),
        out_specs=(rep3, kv_specs.k, kv_specs.v, list(kv_specs.k_scale),
                   list(kv_specs.v_scale)),
        check_vma=False)

    def step(params, tokens, context_lens, pages, tables):
        positions = context_lens[:, None]
        active = (context_lens > 0)[:, None]
        cos, sin = rope_angles(positions, cfg.head_dim_, cfg.rope_theta,
                               scaling=cfg.rope_scaling)
        x = _embed_lookup(params, cfg, tokens)[:, None, :]
        x, new_k, new_v, new_ks, new_vs = sharded_layers(
            params["layers"], x, cos, sin,
            positions, active, context_lens + 1,
            pages.k, pages.v, list(pages.k_scale), list(pages.v_scale),
            tables)
        logits = _unembed(params, cfg, x)[:, 0, :]
        # Container canon (KVPages defaults / llama.prefill /
        # llama.decode_step): unquantized pools carry EMPTY TUPLES for
        # the scale leaves; quantized pools carry lists (init_kv_pages'
        # quant path).  Deviating flips the treedef and silently forces
        # a fresh jit variant of every downstream program that takes
        # pages (the traceguard overlap gate catches this).
        return logits, KVPages(k=new_k, v=new_v,
                               k_scale=new_ks if quant else (),
                               v_scale=new_vs if quant else ())

    return step
