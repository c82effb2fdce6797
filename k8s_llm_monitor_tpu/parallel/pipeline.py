"""GPipe-style pipeline parallelism over a ``pipe`` mesh axis.

Completes the mesh-parallelism portfolio (data / seq / model / **pipe**):
tensor parallelism (sharding.py) splits every layer across chips and pays
a collective per matmul, which is only cheap inside an ICI domain;
pipeline parallelism instead places CONTIGUOUS LAYER BLOCKS on different
chips (or hosts) and moves a single [mb, S, H] activation between
neighbors once per stage — the natural axis to cross slower links (DCN
between hosts; reference counterpart: none, the reference's LLM layer is
config-only, internal/config/config.go:141-145).

Design (the "looped pipeline" of the public scaling playbooks, written
with explicit SPMD collectives):

  * **Stage-stacked params.**  ``stack_pipeline_params`` turns the
    per-layer list into one pytree whose leaves carry a leading
    ``[n_stages, layers_per_stage, ...]`` axis; axis 0 is sharded over
    ``pipe`` (``pipeline_param_specs``), so each device materializes only
    its own block — an 80-layer 70B on pipe-8 holds 10 layers per chip.
    Inside the ``shard_map`` each device ``lax.scan``s its block.
  * **Microbatch rotation.**  The global batch is split into M
    microbatches.  At tick t, stage 0 injects microbatch t while every
    other stage runs the activation it received from its neighbor at
    t-1; activations move stage s -> s+1 with a single ``ppermute``.
    T = M + P - 1 ticks drain the pipe (the P-1 bubble ticks are the
    standard GPipe overhead: efficiency M / (M + P - 1)).
  * **Embed / unembed stay OUTSIDE the shard_map** in plain GSPMD: the
    embedding is computed for all microbatches up front (sharded over
    ``data`` automatically) and the final hidden states come back
    replicated-over-pipe via a ``psum`` of the last stage's output
    buffer.  This keeps replicated-parameter gradients in XLA's hands —
    only the pipe-sharded layer block lives inside manual-collective
    land, where its gradient is purely local.  (The trade: activations
    for all microbatches are resident at once, fine at the scales the
    tests and dryrun run; an embed-on-stage-0 variant saves that memory
    at the cost of hand-written replicated-grad psums.)
  * **Exact gradients.**  GPipe semantics — no weight staleness; autodiff
    flows through ``ppermute``/``psum`` (both have well-defined
    transposes), so ``jax.grad`` of the pipelined loss equals the dense
    model's gradient (parity-tested).

Composes with data parallelism on a ``data x pipe`` mesh
(``create_pp_mesh``); sequence/tensor axes compose the same way but are
kept out of the stage body here — TP-within-stage is the documented
extension, not wired.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np


def _shard_map(f, *, mesh, in_specs, out_specs):
    # Replication checking stays off: the psum-broadcast output pattern
    # (only the last stage holds real values pre-psum) trips it.
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=False)
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from k8s_llm_monitor_tpu.models import llama
from k8s_llm_monitor_tpu.models.config import ModelConfig
from k8s_llm_monitor_tpu.ops.rope import rope_angles


def create_pp_mesh(data: int, pipe: int, devices=None) -> Mesh:
    """Build a ``data x pipe`` mesh.  Device order follows jax.devices():
    consecutive devices land on the ``pipe`` axis, so stage neighbors sit
    on adjacent chips (ICI) and the ``data`` axis crosses the slower
    boundary only once per step (gradient psum)."""
    if devices is None:
        devices = jax.devices()
    if data * pipe != len(devices):
        raise ValueError(f"mesh {data}x{pipe} needs {data * pipe} devices, "
                         f"have {len(devices)}")
    arr = np.asarray(devices).reshape(data, pipe)
    return Mesh(arr, ("data", "pipe"))


def stack_pipeline_params(params: dict, n_stages: int) -> dict:
    """Re-shape the per-layer param list into stage-stacked leaves.

    Returns ``{"embed", "final_norm", ["lm_head"], "layers": pytree with
    leaves [n_stages, layers_per_stage, ...]}``.  Requires the layer count
    to divide evenly (pad upstream if you must)."""
    L = len(params["layers"])
    if L % n_stages:
        raise ValueError(f"{L} layers do not divide {n_stages} stages")
    stacked = jax.tree.map(lambda *xs: jnp.stack(xs), *params["layers"])
    staged = jax.tree.map(
        lambda x: x.reshape(n_stages, L // n_stages, *x.shape[1:]), stacked)
    out = {"embed": params["embed"], "final_norm": params["final_norm"],
           "layers": staged}
    if "lm_head" in params:
        out["lm_head"] = params["lm_head"]
    return out


def pipeline_param_specs(staged: dict) -> dict:
    """PartitionSpecs for the staged pytree: layer leaves shard their
    stage axis over ``pipe``; everything else is replicated."""
    specs = jax.tree.map(lambda _: P(), staged)
    specs["layers"] = jax.tree.map(
        lambda x: P("pipe", *([None] * (x.ndim - 1))), staged["layers"])
    return specs


def place_pipeline_params(staged: dict, mesh: Mesh) -> dict:
    specs = pipeline_param_specs(staged)
    return jax.tree.map(
        lambda x, s: jax.device_put(x, NamedSharding(mesh, s)), staged, specs)


def place_pipeline_opt_state(opt_state, n_stages: int, mesh: Mesh):
    """Place optimizer state (e.g. restored AdamW moments) on the mesh.

    Moment leaves mirror the staged params, so anything shaped
    ``[n_stages, ...]`` with rank >= 3 is a stage-stacked layer moment
    (pipe-sharded); everything else — scalars like the optax step counter,
    embed/norm/head moments — replicates.  Needed because a host-side
    ``optimizer.init``/checkpoint-restore leaves committed single-device
    arrays that a mesh-jitted step would reject.
    """
    def put(x):
        x = jnp.asarray(x)
        if x.ndim >= 3 and x.shape[0] == n_stages:
            spec = P("pipe", *([None] * (x.ndim - 1)))
        else:
            spec = P()
        return jax.device_put(x, NamedSharding(mesh, spec))

    return jax.tree.map(put, opt_state)


def _run_stage(cfg: ModelConfig, stage_layers, x: jnp.ndarray) -> jnp.ndarray:
    """Scan this device's layer block over x [mb, S, H] (dense causal
    attention — stages see whole sequences).  The per-layer math is
    llama.layer_block, shared with forward_full so the pipelined model
    cannot drift from the dense one."""
    mb, S, _ = x.shape
    positions = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32), (mb, S))
    cos, sin = rope_angles(positions, cfg.head_dim_, cfg.rope_theta,
                           scaling=cfg.rope_scaling)

    @jax.checkpoint
    def body(h, lyr):
        h, _ = llama.layer_block(lyr, cfg, h, cos, sin, positions)
        return h, None

    x, _ = jax.lax.scan(body, x, stage_layers)
    return x


def make_pipeline_forward(mesh: Mesh, cfg: ModelConfig):
    """Build the shard_mapped pipeline over the layer stack.

    Returns ``fn(staged_layers, x0) -> hidden`` where ``x0`` is the
    embedded input for all microbatches [M, B, S, H] (B sharded over
    ``data`` by GSPMD) and ``hidden`` is the post-layer-stack activation
    with identical sharding, replicated over ``pipe``.
    """
    if cfg.num_experts > 0:
        raise NotImplementedError(
            "pipeline parallelism does not thread the MoE load-balancing "
            "aux loss yet — train MoE configs on the GSPMD data x model "
            "mesh (expert parallelism, training/train.py) instead")
    if cfg.sliding_window > 0:
        raise NotImplementedError(
            "pipeline parallelism scans a stage's layers with one compiled "
            "body; per-layer sliding-window patterns (Gemma-2) need "
            "per-layer static masks — train these configs on the GSPMD "
            "mesh instead")

    def fn(staged_layers, x0):
        in_layer_specs = jax.tree.map(
            lambda x: P("pipe", *([None] * (x.ndim - 1))), staged_layers)
        act_spec = P(None, "data", None, None)

        @functools.partial(
            _shard_map, mesh=mesh,
            in_specs=(in_layer_specs, act_spec),
            out_specs=act_spec)
        def pipe(layers_local, x0_local):
            # layers_local leaves: [1, Lp, ...] -> [Lp, ...]
            layers_local = jax.tree.map(lambda x: x[0], layers_local)
            s = jax.lax.axis_index("pipe")
            # Static stage count from the mesh (jax.lax.axis_size only
            # exists in newer jax; T below must be static for the scan
            # length anyway).
            P_ = mesh.shape["pipe"]
            M, mb, S, H = x0_local.shape
            T = M + P_ - 1

            def tick(carry, t):
                recv, outbuf = carry
                x_in = jnp.where(s == 0,
                                 x0_local[jnp.clip(t, 0, M - 1)], recv)
                y = _run_stage(cfg, layers_local, x_in)
                widx = jnp.clip(t - (P_ - 1), 0, M - 1)
                write = (s == P_ - 1) & (t >= P_ - 1)
                outbuf = outbuf.at[widx].set(
                    jnp.where(write, y, outbuf[widx]))
                recv = jax.lax.ppermute(
                    y, "pipe", [(i, (i + 1) % P_) for i in range(P_)])
                return (recv, outbuf), None

            recv0 = jnp.zeros((mb, S, H), x0_local.dtype)
            out0 = jnp.zeros((M, mb, S, H), x0_local.dtype)
            (_, outbuf), _ = jax.lax.scan(
                tick, (recv0, out0), jnp.arange(T, dtype=jnp.int32))
            # Only the last stage wrote real values; psum broadcasts them
            # (and its transpose routes the backward activation gradients
            # straight back to the last stage).
            return jax.lax.psum(outbuf, "pipe")

        return pipe(staged_layers, x0)

    return fn


def pipeline_loss(cfg: ModelConfig, pipe_fwd, staged: dict,
                  tokens: jnp.ndarray, n_micro: int) -> jnp.ndarray:
    """Next-token CE of the pipelined model.  tokens [B, S] int32.

    Constraint chain: ``n_micro`` divides B, and the per-microbatch batch
    ``B / n_micro`` must divide the mesh's ``data`` axis (each microbatch
    is itself data-sharded).
    """
    B, S = tokens.shape
    if B % n_micro:
        raise ValueError(f"batch {B} does not divide {n_micro} microbatches")
    toks = tokens.reshape(n_micro, B // n_micro, S)
    x0 = llama._embed_lookup({"embed": staged["embed"]}, cfg,
                             toks.reshape(-1, S)).reshape(
        n_micro, B // n_micro, S, -1)
    hid = pipe_fwd(staged["layers"], x0)
    # _unembed applies the final norm itself.
    logits = llama._unembed(
        {"embed": staged["embed"], "final_norm": staged["final_norm"],
         **({"lm_head": staged["lm_head"]} if "lm_head" in staged else {})},
        cfg, hid.reshape(B, S, -1))
    logp = jax.nn.log_softmax(logits[:, :-1].astype(jnp.float32), axis=-1)
    tgt = tokens[:, 1:]
    nll = -jnp.take_along_axis(logp, tgt[..., None], axis=-1)[..., 0]
    return jnp.mean(nll)


def make_pipeline_train_step(mesh: Mesh, cfg: ModelConfig, optimizer,
                             n_micro: int):
    """Jitted AdamW train step over the ``data x pipe`` mesh.

    Returns ``step(staged_params, opt_state, tokens) -> (staged_params,
    opt_state, loss)``; place params with ``place_pipeline_params`` and
    shard tokens ``P("data", None)`` first.
    """
    import optax

    pipe_fwd = make_pipeline_forward(mesh, cfg)

    def loss_fn(staged, tokens):
        return pipeline_loss(cfg, pipe_fwd, staged, tokens, n_micro)

    @functools.partial(jax.jit, donate_argnums=(0, 1))
    def step(staged, opt_state, tokens):
        loss, grads = jax.value_and_grad(loss_fn)(staged, tokens)
        updates, opt_state = optimizer.update(grads, opt_state, staged)
        staged = optax.apply_updates(staged, updates)
        return staged, opt_state, loss

    return step
