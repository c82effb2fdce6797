"""Device mesh construction.

Axis conventions used across the framework:
  ``data``  — data parallel (batch sharding; gradients psum here)
  ``model`` — tensor parallel (attention heads / MLP hidden; rides ICI)
  ``seq``   — sequence/context parallel (ring attention for long prompts)

Serving meshes are usually 1D ``model``; training meshes 2D ``data × model``;
long-context prefill adds ``seq``.  Axes of size 1 are always present so one
set of PartitionSpecs works on every mesh shape.
"""

from __future__ import annotations

import dataclasses

import jax
import numpy as np
from jax.sharding import Mesh

AXES = ("data", "seq", "model")


def init_multihost(coordinator: str | None = None,
                   num_processes: int | None = None,
                   process_id: int | None = None) -> int:
    """Join a multi-host JAX runtime (DCN between hosts, ICI within).

    On GKE/TPU-VM slices the environment usually carries everything and a
    bare ``jax.distributed.initialize()`` suffices; explicit arguments
    cover manual launches (`JAX_COORDINATOR` / `NUM_PROCESSES` /
    `PROCESS_ID` env vars work too).  Idempotent: repeated calls are
    no-ops.  Returns this host's process index.

    Axis placement rule for multi-host meshes (see SURVEY §5.8 / the
    scaling-book recipe): keep ``model`` (and ``seq`` for ring attention)
    within a host's ICI domain and spread ``data`` across hosts, so the
    per-step psum over ``data`` is the only collective riding DCN.
    ``create_mesh`` preserves that ordering because jax.devices()
    enumerates local devices contiguously per process.
    """
    import logging
    import os

    # Must not touch any API that initializes the XLA backend before
    # initialize() — jax.process_count() does, after which initialize()
    # raises unconditionally.  Only read distributed-client state here.
    if jax.distributed.is_initialized():
        return jax.process_index()
    coordinator = coordinator or os.environ.get("JAX_COORDINATOR")
    num_processes = num_processes or int(os.environ.get("NUM_PROCESSES", 0))
    process_id = (process_id if process_id is not None
                  else int(os.environ.get("PROCESS_ID", -1)))
    if coordinator and num_processes > 1 and process_id >= 0:
        jax.distributed.initialize(
            coordinator_address=coordinator,
            num_processes=num_processes,
            process_id=process_id,
        )
    else:
        try:
            jax.distributed.initialize()  # env/metadata-driven (TPU VM)
        except Exception as exc:  # noqa: BLE001 — single-host runs stay single
            logging.getLogger("k8s_llm_monitor_tpu.parallel").debug(
                "jax.distributed.initialize() not applicable (%s); "
                "continuing single-host", exc)
    return jax.process_index()


@dataclasses.dataclass(frozen=True)
class MeshConfig:
    data: int = 1
    seq: int = 1
    model: int = 1

    @property
    def size(self) -> int:
        return self.data * self.seq * self.model


def create_mesh(
    cfg: MeshConfig | None = None,
    devices: list | None = None,
) -> Mesh:
    """Build a ``data × seq × model`` mesh.

    With no config, all devices go on the ``model`` axis (the serving
    default: TP over ICI).  Device order follows jax.devices(), which on TPU
    enumerates chips in ICI-neighbor order, so the innermost (``model``) axis
    gets the fastest links.
    """
    if devices is None:
        devices = jax.devices()
    if cfg is None:
        cfg = MeshConfig(model=len(devices))
    if cfg.size != len(devices):
        raise ValueError(f"mesh {cfg} needs {cfg.size} devices, have {len(devices)}")
    shape = (cfg.data, cfg.seq, cfg.model)
    try:
        # mesh_utils understands the physical ICI topology (2D/3D torus on
        # TPU) and orders devices so the innermost mesh axis lands on
        # nearest-neighbor links; a naive reshape of jax.devices() does NOT
        # guarantee that beyond 1D.
        from jax.experimental import mesh_utils

        arr = mesh_utils.create_device_mesh(shape, devices=devices)
    except (ValueError, AssertionError, NotImplementedError):
        # Virtual CPU meshes and odd single-host layouts fall back to
        # enumeration order, which is fine off-hardware.
        arr = np.asarray(devices).reshape(shape)
    return Mesh(arr, AXES)
