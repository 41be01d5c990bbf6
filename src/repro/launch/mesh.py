"""Device meshes: the simulator's ``("users",)`` mesh, the serving tier's
``("shard",)`` mesh, and ``("data", "model")`` meshes for the LM drivers.

Functions, not module constants: importing this module never touches jax
device state (a caller may set XLA_FLAGS before first jax init).
``get_abstract_mesh`` is the one place model code reads the ambient mesh
(set by ``jax.set_mesh``) from.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def get_abstract_mesh():
    """The mesh of the innermost active ``jax.set_mesh`` context, or None when
    there is none — callers treat None as "no sharding constraint" (host
    tests run meshless)."""
    m = jax.sharding.get_abstract_mesh()
    if m is None or not m.axis_names:
        return None     # empty sentinel mesh -> meshless semantics
    return m


def make_mesh(shape, axes):
    """Arbitrary mesh with Auto axis types (tests / small-scale drivers)."""
    shape, axes = tuple(shape), tuple(axes)
    return jax.make_mesh(shape, axes,
                         axis_types=(AxisType.Auto,) * len(axes))


def make_host_mesh(model: int = 1):
    """Whatever this host offers (CPU tests: 1 device -> (1,1) mesh)."""
    n = len(jax.devices())
    return make_mesh((n // model, model), ("data", "model"))


def make_serving_mesh(n_shards: int):
    """1-D ``("shard",)`` mesh for the serving tier's parameter
    partition: sized to ``min(n_shards, n_devices)`` so a host with fewer
    devices than shards still gets a valid mesh (shards wrap around it —
    see ``shard_placement``). A 256-chip pod serves 256 true shards; the
    CPU test host serves them all from one device."""
    if n_shards < 1:
        raise ValueError(f"n_shards must be >= 1, got {n_shards}")
    n = len(jax.devices())
    return make_mesh((min(int(n_shards), n),), ("shard",))


def make_sim_mesh(n_devices: int = 0):
    """1-D ``("users",)`` mesh for the simulator's sharded chunked scan
    (``core/vector_engine.py``): the per-user ``EngineState`` axis is
    partitioned over it while the scheduler scalars stay replicated.
    Sized to ``min(n_devices, available)`` like :func:`make_serving_mesh`
    so an over-asked host still gets a valid mesh; ``n_devices=0`` (the
    ``SimConfig`` default's sentinel) means "all local devices". On a
    CPU-only host, force multiple devices with
    ``XLA_FLAGS=--xla_force_host_platform_device_count=8`` *before* the
    first jax import — the scan's collectives then run as host memcpys,
    and the measured partitioning transfers directly to accelerator
    meshes."""
    if n_devices < 0:
        raise ValueError(f"n_devices must be >= 0, got {n_devices}")
    n = len(jax.devices())
    d = n if n_devices == 0 else min(int(n_devices), n)
    return make_mesh((d,), ("users",))


def shard_placement(n_shards: int, mesh=None) -> list:
    """Device owning each of ``n_shards`` logical shards: round-robin
    over the mesh's ``shard`` axis (or all host devices when ``mesh`` is
    None). More shards than devices is fine — a device then owns several
    shards, the degenerate single-host case being all of them."""
    if n_shards < 1:
        raise ValueError(f"n_shards must be >= 1, got {n_shards}")
    if mesh is not None:
        devs = list(mesh.devices.reshape(-1))
    else:
        devs = list(jax.devices())
    return [devs[i % len(devs)] for i in range(int(n_shards))]
