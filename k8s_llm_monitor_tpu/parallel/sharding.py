"""Partition specs for the Llama param pytree and KV cache.

Megatron-style tensor parallelism expressed as GSPMD annotations:
  - column-parallel (shard out_features over ``model``): q/k/v, gate/up
  - row-parallel    (shard in_features over ``model``):  o, down
  - vocab-parallel embedding + lm_head
  - norms replicated
XLA inserts the psum after row-parallel matmuls automatically from these
annotations — there is no manual collective in the model code.

The layout is factored two ways (SNIPPETS.md [2]/[3]):

  * ``SpecLayout`` — a frozen dataclass with one method per parameter
    *role* (embedding, column/row projection, expert stack, norm).  It is
    the single place the axis names live; serving and the tests
    derive their ``NamedSharding``s from it.
  * ``partition_rules()`` — the role methods bound to param-path regexes
    (the ``match_partition_rules`` idiom), so a checkpoint pytree maps to
    specs by name without the model code knowing about meshes.

KV pages shard the kv-heads axis over ``model`` when the head count divides
the TP degree.  For Llama-3-8B (8 KV heads) on v5e-8 that is exactly one KV
head per chip.  When TP exceeds the KV head count (70B/72B: 8 KV heads on
v5p-16), the kv-heads axis cannot be partitioned 16 ways — those configs
replicate the KV pages across the model axis instead (``SpecLayout.
kv_pages`` infers the choice) — trading HBM for a spec that compiles;
attention Q-heads remain fully sharded either way.

Page tables and context lengths are NEVER sharded: block ids are global
(serving/kv_cache.py allocates them host-side), every chip indexes the
same table rows and reads its own head-slice of each page.  That is the
invariant that lets ``BlockAllocator``/``PrefixCache`` stay mesh-agnostic,
and what lets the Pallas paged kernels — decode and flash prefill
(ops/attention.py ``make_tp_paged_attention`` / ``make_tp_flash_prefill``)
— run per-shard under ``shard_map`` with no collective: each shard walks
the same block table over its own kv-head slice of the pool.
"""

from __future__ import annotations

import dataclasses
import re
from typing import Any

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from k8s_llm_monitor_tpu.models.config import ModelConfig
from k8s_llm_monitor_tpu.models.llama import KVPages


@dataclasses.dataclass(frozen=True)
class SpecLayout:
    """Axis layout for tensor-parallel serving, one method per param role.

    Frozen so a layout can key caches and be shared across engine builds;
    instantiate with different axis names for exotic meshes (tests use the
    default ``("data", "seq", "model")`` convention from parallel/mesh.py).
    """

    data_axis: str = "data"
    seq_axis: str = "seq"
    model_axis: str = "model"

    # -- parameter roles --------------------------------------------------
    def embedding(self) -> P:
        """Vocab-parallel embedding / lm_head tables: [V, H], shard V."""
        return P(self.model_axis, None)

    def embedding_scale(self) -> P:
        """Per-vocab-row int8 scales ride the sharded vocab axis."""
        return P(self.model_axis)

    def column_kernel(self) -> P:
        """q/k/v/gate/up/lm_head [in, out]: shard out_features (heads /
        MLP hidden) over ``model``."""
        return P(None, self.model_axis)

    def row_kernel(self) -> P:
        """o/down [in, out]: shard in_features; XLA inserts the psum."""
        return P(self.model_axis, None)

    def column_bias(self) -> P:
        """Biases and per-out-channel int8 scales of column-parallel
        projections split with the out dim."""
        return P(self.model_axis)

    def expert_kernel(self) -> P:
        """Stacked MoE kernels [E, in, out]: expert axis rides ``model``
        (GSPMD inserts dispatch/combine all-to-alls)."""
        return P(self.model_axis, None, None)

    def expert_scale(self) -> P:
        """MoE int8 scales [E, out] shard their expert axis the same."""
        return P(self.model_axis, None)

    def layer_norm(self) -> P:
        """Norms (and the MoE router) are O(H): replicate."""
        return P(None)

    def replicated(self) -> P:
        return P(None)

    # -- serving-state roles ----------------------------------------------
    def kv_pages(self, num_kv_heads: int, tp: int) -> P:
        """[num_blocks, block_size, kv_heads*head_dim]: shard the fused
        lane dim on kv-head boundaries when ``tp`` divides the head count
        (the layout is kv-head-major, so a ``tp``-way lane split IS a head
        split); otherwise replicate — a lane split that cuts a head
        mid-``head_dim`` would psum every q·k dot."""
        if tp > 1 and (tp > num_kv_heads or num_kv_heads % tp != 0):
            return P(None, None, None)
        if tp <= 1:
            return P(None, None, None)
        return P(None, None, self.model_axis)

    def kv_scales(self, num_kv_heads: int, tp: int) -> P:
        """Quantized-KV scale arrays [num_blocks, block_size, kv_heads]:
        the kv-heads axis shards exactly when the pages' fused lane dim
        does (same divisibility condition), so each chip holds the scales
        for precisely its own head slice; otherwise replicate."""
        if self.kv_pages(num_kv_heads, tp) == P(None, None, None):
            return P(None, None, None)
        return P(None, None, self.model_axis)

    def page_table(self) -> P:
        """Block tables / context lengths: replicated.  Page ids are
        GLOBAL — each chip reads the same table and its own head-slice of
        every page, so the host allocator needs no mesh awareness."""
        return P(None, None)

    def prefill_tokens(self) -> P:
        """Seq-parallel prefill: token batches [P, bucket] shard their
        sequence axis when the mesh has a nontrivial ``seq`` degree."""
        return P(None, self.seq_axis)

    def batch(self) -> P:
        """Activation batch sharding: batch over ``data``."""
        return P(self.data_axis)


#: The default layout every serving entry point derives its shardings from.
DEFAULT_LAYOUT = SpecLayout()


def partition_rules(
    layout: SpecLayout = DEFAULT_LAYOUT,
) -> tuple[tuple[str, P], ...]:
    """(path-regex, spec) pairs, first match wins; paths join the pytree's
    dict keys with ``/`` (list indices dropped), e.g. ``layers/q/kernel``.
    Expert rules precede column rules so ``up_e`` never matches ``up``; a
    shared MLP beside the experts (``layers/shared/gate/kernel``) shards as
    a dense one does."""
    return (
        (r"(^|/)embed/(weight|weight_q)$", layout.embedding()),
        (r"(^|/)embed/scale$", layout.embedding_scale()),
        # Latent mixer (kv_a, kv_b, kv_norm; with low-rank queries q_a and
        # q_b, a head-wise gate, an indexer) and the router with its
        # selection bias: replicated.  The engine refuses a mesh for a
        # latent pool (no kv-head axis to shard); these rules only keep
        # eval_shape-level checks of every preset meaningful.
        (r"(^|/)(kv_a|kv_b|router|q_a|q_b|attn_gate|idx_q|idx_k|idx_w"
         r"|idx_k_norm)/", layout.replicated()),
        (r"(^|/)(gate_e|up_e|down_e)/scale$", layout.expert_scale()),
        (r"(^|/)(gate_e|up_e|down_e)/", layout.expert_kernel()),
        (r"(^|/)(q|k|v|gate|up|lm_head)/(kernel|kernel_q)$",
         layout.column_kernel()),
        (r"(^|/)(o|down)/(kernel|kernel_q)$", layout.row_kernel()),
        (r"(^|/)(q|k|v|gate|up|lm_head)/(bias|scale)$",
         layout.column_bias()),
        (r".*norm", layout.layer_norm()),
    )


def _param_path_name(path: tuple) -> str:
    return "/".join(
        str(p.key) for p in path if isinstance(p, jax.tree_util.DictKey))


def match_partition_rules(rules, params: Any) -> Any:
    """Map a param pytree to PartitionSpecs by path regex (SNIPPETS.md
    [2] idiom); unmatched leaves replicate."""
    def spec_for(path, _leaf) -> P:
        name = _param_path_name(path)
        for pattern, spec in rules:
            if re.search(pattern, name):
                return spec
        return P(None)

    return jax.tree_util.tree_map_with_path(spec_for, params)


def param_partition_specs(
    params: Any, layout: SpecLayout = DEFAULT_LAYOUT,
) -> Any:
    """PartitionSpec pytree matching a llama param pytree."""
    return match_partition_rules(partition_rules(layout), params)


def param_named_shardings(
    params: Any, mesh: Mesh, layout: SpecLayout = DEFAULT_LAYOUT,
) -> Any:
    """The ``SpecLayout``-derived ``NamedSharding`` pytree the engine
    device-puts weights with."""
    return jax.tree.map(
        lambda s: NamedSharding(mesh, s), param_partition_specs(params, layout),
        is_leaf=lambda x: isinstance(x, P))


def kv_pages_partition_specs(
    pages: KVPages, mesh: Mesh | None, num_kv_heads: int,
    layout: SpecLayout = DEFAULT_LAYOUT,
) -> KVPages:
    """[num_blocks, block_size, kv_heads*head_dim] -> shard the fused lane
    dim on kv-head boundaries (see ``SpecLayout.kv_pages``)."""
    tp = mesh.shape[layout.model_axis] if mesh is not None else 1
    spec = layout.kv_pages(num_kv_heads, tp)
    sspec = layout.kv_scales(num_kv_heads, tp)
    return KVPages(
        k=[spec for _ in pages.k],
        v=[spec for _ in pages.v],
        k_scale=[sspec for _ in pages.k_scale],
        v_scale=[sspec for _ in pages.v_scale],
    )


def shard_params(
    params: Any, mesh: Mesh, layout: SpecLayout = DEFAULT_LAYOUT,
) -> Any:
    """Device-put params with TP sharding over ``mesh``."""
    return jax.tree.map(
        jax.device_put, params, param_named_shardings(params, mesh, layout))


def batch_spec() -> P:
    """Activation batch sharding: batch over ``data``."""
    return DEFAULT_LAYOUT.batch()
