"""Production mesh construction.

Defined as FUNCTIONS (never module-level constants) so importing this module
never touches jax device state — required because the dry-run must set
XLA_FLAGS before the first jax device query.
"""
from __future__ import annotations

import jax


def _axis_type_kwargs(n_axes: int) -> dict:
    """Every mesh axis is ``Auto``: the compiler places the collectives."""
    return {"axis_types": (jax.sharding.AxisType.Auto,) * n_axes}


def make_production_mesh(*, multi_pod: bool = False):
    """Single pod: (data=16, model=16) = 256 chips.
    Multi-pod:  (pod=2, data=16, model=16) = 512 chips; the 'pod' axis is the
    DCN-crossing data-parallel axis."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes, **_axis_type_kwargs(len(axes)))


def make_local_mesh():
    """Whatever devices exist, as a 1-D 'data' mesh (CPU tests)."""
    n = len(jax.devices())
    return jax.make_mesh((n, 1), ("data", "model"), **_axis_type_kwargs(2))


# ---------------------------------------------------------------------------
# Fold parallelism (cross-validation / stability selection)
# ---------------------------------------------------------------------------

def make_fold_mesh(n_folds: int):
    """1-D 'fold' mesh for K-fold model selection.

    Uses the largest device count that divides ``n_folds`` so every shard
    carries the same number of folds (shard_map needs an even split); on a
    single-device host this degenerates to a 1-chip mesh and the fold sweep
    runs as a plain vmap over the lone shard."""
    n_dev = len(jax.devices())
    d = 1
    for c in range(min(n_folds, n_dev), 0, -1):
        if n_folds % c == 0:
            d = c
            break
    return jax.make_mesh((d,), ("fold",), **_axis_type_kwargs(1))


def make_feature_mesh(n_shards: int):
    """1-D 'feature' mesh of exactly ``n_shards`` devices, or ``None`` when
    the host has fewer devices (the caller then falls back to the vmap
    executor over stacked shard blocks — same math, one device).

    Unlike ``make_fold_mesh`` this does NOT degrade to a divisor of the
    device count: the feature-shard *partition* is already fixed by the
    group-aligned partitioner (``distributed.feature_shard``), so the mesh
    must match the partition, not the other way around."""
    if n_shards <= 1:
        return None
    devs = jax.devices()
    if len(devs) < n_shards:
        return None
    from jax.sharding import Mesh
    import numpy as np
    return Mesh(np.asarray(devs[:n_shards]), ("feature",))


def abstract_feature_mesh(n_shards: int):
    """A 1-D 'feature' ``AbstractMesh`` of ``n_shards`` — enough to TRACE
    the sharded screening / certification programs and extract their
    collective plans without multi-device hardware (the Layer-4 audit
    proves the plan is psum-only; see ``abstract_fold_mesh``)."""
    from jax.sharding import AbstractMesh
    return AbstractMesh((int(n_shards),), ("feature",))


def make_fold_feature_mesh(n_folds: int, n_shards: int):
    """2-D (fold, feature) mesh: the fold axis uses the largest divisor of
    ``n_folds`` that fits the remaining device budget (mirroring
    ``make_fold_mesh``), the feature axis takes exactly ``n_shards``.
    Returns ``None`` when the host cannot supply ``fold_axis * n_shards``
    devices for any fold axis > 1 — callers then compose a plain feature
    mesh with vmapped folds instead."""
    if n_shards <= 1:
        return make_fold_mesh(n_folds)
    n_dev = len(jax.devices())
    d = 0
    for c in range(min(n_folds, n_dev // n_shards), 1, -1):
        if n_folds % c == 0:
            d = c
            break
    if d == 0:
        return None
    from jax.sharding import Mesh
    import numpy as np
    devs = np.asarray(jax.devices()[: d * n_shards]).reshape(d, n_shards)
    return Mesh(devs, ("fold", "feature"))


def abstract_fold_mesh(n_shards: int):
    """A 1-D 'fold' ``AbstractMesh`` of ``n_shards`` — enough to TRACE a
    ``shard_over_folds``-wrapped sweep (and extract its collective plan)
    on a host with no multi-device hardware.  The static resource audit
    (``repro.analysis.resource_audit``) uses this to prove fold sweep
    bodies stay collective-free without ever forcing
    ``xla_force_host_platform_device_count``."""
    from jax.sharding import AbstractMesh
    return AbstractMesh((int(n_shards),), ("fold",))


def fold_axis_size(mesh) -> int:
    """Device count along the 'fold' axis of ``mesh``.

    On a 1-D fold mesh this is ``mesh.size``; on a 2-D folds x features mesh
    only the 'fold' axis counts — the feature axis replicates the fold sweep,
    it never splits the fold rows.  Meshes without a 'fold' axis (including
    test doubles exposing only ``.size``) fall back to total size, preserving
    the historical 1-D behaviour."""
    if mesh is None:
        return 1
    shape = getattr(mesh, "shape", None)
    if shape is not None:
        try:
            if "fold" in shape:
                return int(shape["fold"])
        except TypeError:
            pass
    return int(getattr(mesh, "size", 1))


def fold_shard_compatible(mesh, n_folds: int) -> bool:
    """True when a fold-batched launch of ``n_folds`` rows should shard its
    leading axis over ``mesh``: a real multi-device 'fold' mesh axis whose
    size divides the row count (``shard_map`` needs an even split).  On a
    2-D folds x features mesh only the fold-axis size matters — a 2x4 mesh
    must still accept cohorts of 2 folds (and reject 3), not demand
    divisibility by all 8 devices.

    The elastic fold scheduler re-checks this per cohort launch — cohort
    sizes fluctuate as folds diverge in pace, so a launch falls back to a
    plain vmap whenever its cohort no longer splits evenly, and re-engages
    sharding the moment it does."""
    if mesh is None:
        return False
    d = fold_axis_size(mesh)
    return d > 1 and n_folds % d == 0


def shard_over_folds(fn, mesh, example_args):
    """Wrap a fold-batched function so its leading fold axis is sharded
    across the mesh's 'fold' axis via ``shard_map``.

    ``example_args`` marks which positional arguments carry a fold axis:
    an entry of 0 shards the leading axis, ``None`` replicates.  Falls back
    to ``fn`` unchanged when the mesh has no multi-device fold axis
    (shard_map over one shard adds tracing overhead for nothing)."""
    if mesh is None or fold_axis_size(mesh) == 1:
        return fn
    from jax.sharding import PartitionSpec

    specs = tuple(PartitionSpec("fold") if a == 0 else PartitionSpec()
                  for a in example_args)
    return jax.shard_map(fn, mesh=mesh, in_specs=specs,
                         out_specs=PartitionSpec("fold"), check_vma=False)
