"""Production meshes.

Single pod: (data=16, model=16) — 256 chips, one ICI domain.
Multi-pod:  (pod=2, data=16, model=16) — 512 chips; the "pod" axis is pure
data parallelism over DCN (weights never shard across pods; only the gradient
all-reduce crosses the DCN boundary, optionally int8-compressed).

Functions, not module constants: importing this module must never touch jax
device state (the dry-run pins XLA_FLAGS before first jax init).
"""

from __future__ import annotations

import jax


def _auto_mesh(shape, axes, devices=None):
    """``jax.make_mesh`` with every axis ``Auto``: the program shards by
    ``with_sharding_constraint`` and GSPMD propagation, which jax >= 0.7's
    default ``Explicit`` axes refuse."""
    from jax.sharding import AxisType

    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes),
                         devices=devices)


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _auto_mesh(shape, axes)


def default_split(n_devices: int, replicas: int = 1) -> tuple[int, int]:
    """(n_target, n_draft) per replica from the device count: the target takes
    the larger half of each replica's devices; a one-device replica colocates
    both models (n_draft = 0)."""
    per = n_devices // replicas
    if per < 1:
        raise ValueError(f"{n_devices} devices cannot host {replicas} replicas")
    return per - per // 2, per // 2


def make_serving_mesh(n_target: int | None = None, n_draft: int | None = None, *,
                      replicas: int = 1, devices=None):
    """Disaggregated serving: disjoint (target, draft) 1-D TP meshes over the
    "model" axis (paper §3.1 GPU allocation), carved ``replicas`` times for
    sharded serving.  Replica i owns devices
    ``[i*(n_target+n_draft), (i+1)*(n_target+n_draft))``, split target-first,
    so no device is shared across replicas or across the draft/target roles.
    ``n_draft = 0`` colocates the draft on the target's devices (one chip per
    replica).  ``None`` for both derives the split from the device count
    (``default_split``).

    Returns one ``(target_mesh, draft_mesh)`` pair for ``replicas == 1`` or a
    list of ``replicas`` pairs.  A split that does not fit the devices raises:
    nothing is ever silently stacked onto one device.
    """
    from jax.sharding import Mesh
    import numpy as np

    if replicas < 1:
        raise ValueError(f"replicas must be >= 1, got {replicas}")
    devs = list(jax.devices() if devices is None else devices)
    if n_target is None and n_draft is None:
        n_target, n_draft = default_split(len(devs), replicas)
    if n_target is None or n_draft is None or n_target < 1 or n_draft < 0:
        raise ValueError(f"need n_target >= 1 and n_draft >= 0, got {n_target} + {n_draft}")
    group = n_target + n_draft
    if len(devs) < group * replicas:
        raise ValueError(
            f"{len(devs)} devices cannot host {replicas} disjoint replica(s) of "
            f"{group} devices ({n_target} target + {n_draft} draft); lower the "
            f"replica count or the per-replica split (n_draft=0 colocates)")

    def carve(i: int):
        base = i * group
        tgt = Mesh(np.array(devs[base : base + n_target]), ("model",))
        if n_draft == 0:
            return tgt, tgt
        return tgt, Mesh(np.array(devs[base + n_target : base + group]), ("model",))

    if replicas == 1:
        return carve(0)
    return [carve(i) for i in range(replicas)]


def host_device_mesh(model: int = 1, data: int = 1):
    """Small (data, model) mesh with Auto axes for tests and the trainer."""
    return _auto_mesh((data, model), ("data", "model"))
