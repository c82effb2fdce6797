"""TPU serving preflight: will this model/quant/mesh fit and shard on the
hardware you have, before you boot the server?

The TPU-plane sibling of ``cmd.test_k8s`` (which preflights cluster
access the way the reference's ``cmd/test-k8s`` does,
reference cmd/test-k8s/main.go:44-185 — the reference has no inference
plane to preflight).  Everything is computed from ``jax.eval_shape`` —
no weights are materialized, so checking a 70B config takes seconds on a
laptop with no accelerator at all.

Usage::

    python -m k8s_llm_monitor_tpu.cmd.preflight --model llama3-8b \
        --quantize w8a8 --mesh 1,1,8
    python -m k8s_llm_monitor_tpu.cmd.preflight --config config.yaml
    python -m k8s_llm_monitor_tpu.cmd.preflight --model llama3-70b \
        --quantize int8 --mesh 1,1,16 --per-chip-hbm-gib 95

Exit code 0 = every check passed (warnings allowed), 1 = at least one
FAIL.
"""

from __future__ import annotations

import argparse
import sys

GIB = 1 << 30

# Fallback per-chip HBM when the runtime does not report a limit (e.g.
# preflighting a TPU deployment from a CPU host).  Sources: public TPU
# system specs.
_HBM_BY_KIND = {
    "TPU v4": 32 * GIB,
    "TPU v5 lite": 16 * GIB,
    "TPU v5e": 16 * GIB,
    "TPU v5": 95 * GIB,
    "TPU v5p": 95 * GIB,
    "TPU v6 lite": 32 * GIB,
    "TPU v6e": 32 * GIB,
}

# Headroom for activations, the XLA workspace, and dispatch buffers at
# serving batch sizes — an estimate (the engine's own peak depends on the
# prefill bucket ladder), deliberately conservative.
_WORKSPACE_BYTES = int(1.5 * GIB)

_DTYPE_BYTES = {"bfloat16": 2, "float32": 4, "float16": 2,
                "float8_e4m3fn": 1, "int8": 1}


class _Report:
    """Collects verdicts as structured lists (consumed by ``check()``)
    while printing the human report."""

    def __init__(self) -> None:
        self.failed = 0
        self.warned = 0
        self.fail_msgs: list[str] = []
        self.warn_msgs: list[str] = []

    def ok(self, msg: str) -> None:
        print(f"  PASS {msg}")

    def warn(self, msg: str) -> None:
        self.warned += 1
        self.warn_msgs.append(msg)
        print(f"  WARN {msg}")

    def fail(self, msg: str) -> None:
        self.failed += 1
        self.fail_msgs.append(msg)
        print(f"  FAIL {msg}")


def _tree_bytes(shapes, specs, model_axis: int,
                leaf_bytes=None) -> tuple[int, int]:
    """(total_bytes, per_chip_bytes) for an eval_shape tree under TP
    sharding: leaves with a ``model`` axis divide across the mesh's model
    dim, everything else is replicated per chip.  ``leaf_bytes``
    overrides the per-leaf byte rule (used by the estimated-int8 path)."""
    import jax
    from jax.sharding import PartitionSpec

    if leaf_bytes is None:
        leaf_bytes = lambda leaf: leaf.size * leaf.dtype.itemsize  # noqa: E731
    total = per_chip = 0
    for leaf, spec in zip(jax.tree_util.tree_leaves(shapes),
                          jax.tree_util.tree_leaves(
                              specs,
                              is_leaf=lambda s: isinstance(s, PartitionSpec))):
        nbytes = leaf_bytes(leaf)
        total += nbytes
        shard = model_axis if any(ax == "model" for ax in spec) else 1
        per_chip += nbytes // shard
    return total, per_chip


def run_preflight(args: argparse.Namespace,
                  r: _Report | None = None) -> int:
    import jax

    from k8s_llm_monitor_tpu.models import llama
    from k8s_llm_monitor_tpu.models.config import PRESETS
    from k8s_llm_monitor_tpu.parallel.sharding import param_partition_specs

    r = r if r is not None else _Report()

    def finish() -> int:
        # Single verdict trailer — printed on early bail-outs too, so
        # wrappers keying on this line always get one.
        print(f"\npreflight: {'FAIL' if r.failed else 'PASS'} "
              f"({r.failed} failed, {r.warned} warnings)")
        return 1 if r.failed else 0

    # -- 1. runtime -----------------------------------------------------
    print("== 1. runtime ==")
    devices = jax.devices()
    kind = devices[0].device_kind
    plat = devices[0].platform
    r.ok(f"jax {jax.__version__}, {len(devices)} x {kind} ({plat})")

    hbm = None
    if args.per_chip_hbm_gib:
        hbm = int(args.per_chip_hbm_gib * GIB)
    else:
        try:
            stats = devices[0].memory_stats() or {}
            hbm = stats.get("bytes_limit")
        except Exception:  # noqa: BLE001 — CPU/older runtimes
            hbm = None
        if not hbm:
            hbm = next((v for k, v in _HBM_BY_KIND.items()
                        if kind.startswith(k)), None)
    if hbm:
        r.ok(f"per-chip HBM budget {hbm / GIB:.0f} GiB"
             + ("" if args.per_chip_hbm_gib else f" (from {kind!r})"))
    else:
        r.warn(f"unknown HBM for device kind {kind!r} - fit checks "
               "skipped (pass --per-chip-hbm-gib)")

    # -- 2. model geometry ----------------------------------------------
    print("== 2. model ==")
    if args.checkpoint:
        import json
        import os

        cfg_path = os.path.join(args.checkpoint, "config.json")
        try:
            from k8s_llm_monitor_tpu.utils.checkpoint import config_from_hf

            with open(cfg_path, encoding="utf-8") as fh:
                cfg = config_from_hf(json.load(fh))
            r.ok(f"checkpoint config {cfg_path}: {cfg.name}")
        except Exception as exc:  # noqa: BLE001 — report, don't crash
            r.fail(f"cannot read checkpoint config {cfg_path}: {exc}")
            return finish()
    else:
        if args.model not in PRESETS:
            r.fail(f"unknown preset {args.model!r}; have "
                   f"{', '.join(sorted(PRESETS))}")
            return finish()
        cfg = PRESETS[args.model]
    if args.quantize == "w8a8":
        import dataclasses

        cfg = dataclasses.replace(cfg, act_quant=True)
    head_dim = cfg.head_dim or cfg.hidden_size // cfg.num_heads
    if cfg.num_heads % cfg.num_kv_heads == 0:
        r.ok(f"{cfg.num_layers}L hidden={cfg.hidden_size} "
             f"heads={cfg.num_heads}/{cfg.num_kv_heads}kv "
             f"head_dim={head_dim} vocab={cfg.vocab_size}"
             + (f" experts={cfg.num_experts}" if cfg.num_experts else "")
             + (f" latent={cfg.kv_lora_rank}+{cfg.qk_rope_head_dim}"
                if cfg.latent else ""))
    else:
        r.fail(f"num_heads {cfg.num_heads} not a multiple of "
               f"num_kv_heads {cfg.num_kv_heads}")

    # -- 3. mesh --------------------------------------------------------
    print("== 3. mesh ==")
    try:
        data, seq, model = (int(x) for x in args.mesh.split(","))
        if data < 1 or seq < 1 or model < 1:
            raise ValueError("mesh dims must be >= 1")
    except Exception:  # noqa: BLE001
        r.fail(f"bad --mesh {args.mesh!r}; expected data,seq,model")
        return finish()
    n_mesh = data * seq * model
    if n_mesh == len(devices):
        r.ok(f"mesh data={data} seq={seq} model={model} "
             f"matches {len(devices)} local device(s)")
    else:
        r.warn(f"mesh needs {n_mesh} device(s), this host sees "
               f"{len(devices)} - fine if deploying elsewhere or "
               "multi-host")
    from k8s_llm_monitor_tpu.serving.engine import InferenceEngine

    unbuilt = InferenceEngine._unbuilt_reason(cfg)
    if unbuilt and n_mesh > 1:
        # The engine refuses this at construction (serving/engine.py): say
        # so here instead of listing divisibilities that will never matter.
        r.fail(f"a mesh is not built for {unbuilt}: serve {cfg.name} on "
               "one chip (mesh 1,1,1)")
    elif model > 1:
        bad = [(nm, dim) for nm, dim in
               [("num_heads", cfg.num_heads),
                ("intermediate_size", cfg.intermediate_size),
                ("vocab_size", cfg.vocab_size)] if dim % model != 0]
        for nm, dim in bad:
            r.fail(f"{nm}={dim} not divisible by model={model}")
        if not bad:
            r.ok(f"q-heads/FFN/vocab all divide model={model}")
        if cfg.num_kv_heads % model == 0:
            r.ok(f"kv_heads={cfg.num_kv_heads} shard {model}-way "
                 "(KV pages split on head boundaries)")
        else:
            r.warn(f"kv_heads={cfg.num_kv_heads} not divisible by "
                   f"model={model} - KV pages replicate per chip "
                   "(parallel/sharding.py kv_pages_partition_specs)")
    if seq > 1:
        # Serve meshes with a seq axis shard prefill token batches; the
        # engine validates bucket divisibility at boot (engine.py).
        r.ok(f"seq={seq}: engine shards prefill chunks (buckets must "
             f"divide by {seq}; checked at boot)")

    # -- 4. weights -----------------------------------------------------
    print("== 4. weights ==")
    quantized = args.quantize in ("int8", "w8a8")
    bf16_shapes = jax.eval_shape(
        lambda: llama.init_params(jax.random.PRNGKey(0), cfg))
    estimated = False
    if quantized:
        try:
            from k8s_llm_monitor_tpu.utils.quantize import (
                init_params_quantized,
            )

            shapes = jax.eval_shape(
                lambda: init_params_quantized(jax.random.PRNGKey(0), cfg))
        except Exception:  # noqa: BLE001 — MoE expert quantizer is
            # host-side (untraceable); estimate from the bf16 tree:
            # every >=2-D leaf stores 1 byte/element as int8 (per-channel
            # f32 scales are <0.1% and ignored).
            shapes = bf16_shapes
            estimated = True
    else:
        shapes = bf16_shapes
    specs = param_partition_specs(shapes)
    total_b, chip_b = _tree_bytes(
        shapes, specs, model,
        leaf_bytes=(lambda leaf: leaf.size * (1 if leaf.ndim >= 2
                                              else leaf.dtype.itemsize))
        if estimated else None)
    r.ok(f"{args.quantize or 'bf16'} weights {total_b / GIB:.2f} GiB total"
         + (f", {chip_b / GIB:.2f} GiB/chip at TP-{model}"
            if model > 1 else "")
         + (" (estimated: int8 bytes from bf16 tree)" if estimated else ""))

    # -- 5. KV cache ----------------------------------------------------
    print("== 5. kv cache ==")
    kv_bytes_per = _DTYPE_BYTES.get(cfg.kv_dtype or cfg.dtype, 2)
    kv_heads_chip = (cfg.num_kv_heads // model
                     if model > 1 and cfg.num_kv_heads % model == 0
                     else cfg.num_kv_heads)
    kv_chip = (args.kv_blocks * args.block_size * cfg.num_layers * 2
               * kv_heads_chip * head_dim * kv_bytes_per)
    if cfg.latent:   # one row a token a layer, no kv-head axis
        kv_chip = (args.kv_blocks * args.block_size
                   * cfg.kv_token_bytes(kv_bytes_per))
    cap_tokens = args.kv_blocks * args.block_size
    r.ok(f"{args.kv_blocks} blocks x {args.block_size} = "
         f"{cap_tokens} tokens capacity, {kv_chip / GIB:.2f} GiB/chip "
         f"({cfg.kv_dtype or cfg.dtype} KV)")
    per_seq = args.prompt_len + args.max_tokens
    if per_seq > 0:
        fit = cap_tokens // per_seq
        msg = (f"~{fit} concurrent sequences at prompt {args.prompt_len} "
               f"+ gen {args.max_tokens}")
        (r.ok if fit >= 1 else r.fail)(
            msg if fit >= 1 else msg + " - raise --kv-blocks")

    # -- 6. fit verdict -------------------------------------------------
    print("== 6. fit ==")
    if hbm:
        need = chip_b + kv_chip + _WORKSPACE_BYTES
        line = (f"per-chip: weights {chip_b / GIB:.2f} + kv "
                f"{kv_chip / GIB:.2f} + workspace "
                f"{_WORKSPACE_BYTES / GIB:.1f} = {need / GIB:.2f} GiB "
                f"of {hbm / GIB:.0f} GiB")
        if need <= 0.92 * hbm:
            r.ok(line)
        elif need <= hbm:
            r.warn(line + " - under 8% headroom")
        else:
            r.fail(line + " - does not fit; shrink --kv-blocks, raise "
                   "TP, or quantize")
    else:
        r.warn("no HBM budget known - skipped")

    # -- 7. optional compile smoke --------------------------------------
    if args.compile:
        print("== 7. compile ==")
        import jax.numpy as jnp

        out = jax.jit(lambda a, b: a @ b)(
            jnp.ones((256, 256), jnp.bfloat16),
            jnp.ones((256, 256), jnp.bfloat16))
        out.block_until_ready()
        r.ok(f"jit matmul on {plat} ok")

    return finish()


def _build_args(argv: list[str] | None) -> argparse.Namespace:
    ap = argparse.ArgumentParser(
        description="TPU serving preflight (no weights materialized)")
    ap.add_argument("--config", default="",
                    help="server YAML; fills any flag not given "
                         "explicitly from llm.tpu.* (explicit flags win)")
    ap.add_argument("--model", default=None)
    ap.add_argument("--checkpoint", default=None,
                    help="HF checkpoint dir (overrides --model)")
    ap.add_argument("--quantize", default=None,
                    choices=["", "none", "int8", "w8a8"])
    ap.add_argument("--mesh", default=None,
                    help="data,seq,model (llm.tpu.mesh_shape)")
    ap.add_argument("--kv-blocks", type=int, default=None)
    ap.add_argument("--block-size", type=int, default=16)
    ap.add_argument("--prompt-len", type=int, default=192)
    ap.add_argument("--max-tokens", type=int, default=256)
    ap.add_argument("--per-chip-hbm-gib", type=float, default=0.0)
    ap.add_argument("--compile", action="store_true",
                    help="run a tiny jit on the backend")
    args = ap.parse_args(argv)
    if args.config:
        # Only flags the user did NOT pass explicitly (still None) are
        # filled from the YAML — an explicit flag always wins.
        from k8s_llm_monitor_tpu.monitor.config import load_config

        c = load_config(args.config)
        if args.model is None:
            args.model = c.llm.tpu.model or None
        if args.checkpoint is None:
            args.checkpoint = c.llm.tpu.checkpoint or None
        if args.quantize is None:
            args.quantize = getattr(c.llm.tpu, "quantize", None)
        if args.mesh is None:
            args.mesh = c.llm.tpu.mesh_shape or None
        if args.kv_blocks is None:
            args.kv_blocks = c.llm.tpu.kv_blocks or None
    # Hard defaults for anything neither flag nor config set.
    if args.model is None:
        args.model = "llama3-8b"
    args.checkpoint = args.checkpoint or ""
    args.quantize = args.quantize if args.quantize is not None else "w8a8"
    if args.quantize == "none":
        args.quantize = ""
    args.mesh = args.mesh or "1,1,1"
    args.kv_blocks = args.kv_blocks or 512
    return args


def check(argv: list[str] | None = None) -> tuple[int, list[str], list[str]]:
    """Programmatic preflight: (exit_code, fail_msgs, warn_msgs).

    Same argv surface as the CLI; callers (monitor/analysis.py boot)
    consume the structured lists instead of scraping printed output."""
    r = _Report()
    rc = run_preflight(_build_args(argv), r)
    return rc, r.fail_msgs, r.warn_msgs


def main(argv: list[str] | None = None) -> int:
    return run_preflight(_build_args(argv))


if __name__ == "__main__":
    sys.exit(main())
