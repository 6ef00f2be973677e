"""Production mesh construction.

Single pod:  (data=16, model=16)            — 256 chips (TPU v5e pod)
Multi-pod:   (pod=2, data=16, model=16)     — 512 chips

Defined as functions so importing this module never touches jax device
state (the dry-run sets XLA_FLAGS before any jax import; tests and smoke
runs must keep seeing 1 CPU device).
"""
from __future__ import annotations

import jax


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes)


def make_debug_mesh(n_data: int = 1, n_model: int = 1):
    """Tiny mesh for CPU integration tests (requires matching device count)."""
    return jax.make_mesh((n_data, n_model), ("data", "model"))


def make_sweep_mesh(n_devices: int | None = None):
    """1-D ``("scenario",)`` mesh over the local devices for embarrassingly
    parallel scenario sweeps (V grids, τ×B grids — every scenario is an
    independent experiment, so the only sharding axis is the grid itself).

    Returns ``None`` on a single device — the sweep drivers
    (``FusedRoundEngine.scan_v_grid``, ``benchmarks/jcsba_solver.py``) take
    that as "fall back to the plain single-device vmap".  Virtual CPU devices
    (``XLA_FLAGS=--xla_force_host_platform_device_count=N``) count like real
    ones, which is how the sharded-vs-single parity tests run on CPU."""
    import numpy as np
    from jax.sharding import Mesh

    devs = jax.devices()
    n = len(devs) if n_devices is None else min(n_devices, len(devs))
    if n <= 1:
        return None
    return Mesh(np.asarray(devs[:n]), ("scenario",))


def make_population_mesh(n_scenario: int | None = None,
                         n_clients: int | None = None):
    """2-D ``("scenario", "clients")`` mesh for population-scale sweeps: the
    scenario axis fans out independent experiments (as in ``make_sweep_mesh``)
    while the clients axis partitions the device-resident client store and
    the per-client randomness, so O(K·N·d) population data scales across
    devices (``launch.sharding.logical_pspec`` + the cohort gather in
    fl/fused_round.py).

    Factor the local device count explicitly (``n_scenario × n_clients``) or
    leave one side None to infer it; with both None all devices go to the
    clients axis (scenario=1).  Returns ``None`` on a single device, like
    ``make_sweep_mesh`` — callers fall back to the unsharded vmap."""
    from jax.sharding import AxisType

    devs = jax.devices()
    total = len(devs)
    if total <= 1:
        return None
    if n_scenario is None and n_clients is None:
        n_scenario, n_clients = 1, total
    elif n_clients is None:
        n_clients = total // n_scenario
    elif n_scenario is None:
        n_scenario = total // n_clients
    n = n_scenario * n_clients
    if n_scenario < 1 or n_clients < 1 or n > total:
        raise ValueError(
            f"mesh {n_scenario}x{n_clients} needs {n} devices, "
            f"have {total}")
    # jax.make_mesh orders the devices along the chips' physical layout
    # (a plain reshape of jax.devices() need not); Auto axes keep the
    # shard_map sweeps' semantics
    return jax.make_mesh((n_scenario, n_clients), ("scenario", "clients"),
                         axis_types=(AxisType.Auto,) * 2, devices=devs[:n])


def data_axes(mesh) -> tuple:
    """Axes the global batch is sharded over."""
    return tuple(a for a in mesh.axis_names if a in ("pod", "data"))


def fsdp_axes(mesh) -> tuple:
    """Axes FSDP-style parameter sharding uses (ZeRO over all data replicas;
    on the multi-pod mesh this includes the pod axis so kimi-k2-scale
    optimizer state fits — DESIGN.md §6)."""
    return data_axes(mesh)


def n_data_shards(mesh) -> int:
    import numpy as np
    return int(np.prod([mesh.shape[a] for a in data_axes(mesh)]))
