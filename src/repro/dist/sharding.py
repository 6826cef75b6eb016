"""Logical-axis sharding: a thin helper layer over ``jax.sharding``.

Models annotate arrays with *logical* axis names ("batch", "model",
"fsdp", ...).  A rules table maps logical names to physical mesh axes; the
helpers here resolve those rules against the active mesh, with a
per-dimension divisibility fallback to replication (a 25-head tensor on a
4-way model axis silently replicates instead of erroring).

Distributed featurization sweeps
--------------------------------
The sweep engine (``repro.core.predictors.features_sweep``) shards its
slice axis through the logical axis ``"slices"`` (mapped to the physical
``"data"`` axis by :data:`DEFAULT_RULES`).  Activating any mesh whose
``"data"`` extent exceeds 1 makes every sweep entering through the engine
run as a ``shard_map`` over the slice axis (see ``repro.dist.sweep``)::

    # 8 virtual CPU devices: set BEFORE importing jax
    #   XLA_FLAGS=--xla_force_host_platform_device_count=8
    from repro.dist import sharding as S
    from repro.launch import mesh as M
    with S.use_mesh(M.make_sweep_mesh()):          # 1-D ("data",) mesh
        feats = engine.sweep(slices, ebs)          # sharded over slices

Slice counts that don't divide the mesh are padded and the pad rows are
dropped (gather) or masked (sharded-out); see
``repro.dist.sweep.features_sweep_sharded``.
"""
from __future__ import annotations

import contextlib
import threading
from typing import Optional, Sequence, Tuple

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


# Logical axis name -> tuple of physical mesh axes.  A logical axis maps to
# nothing ("layers": the scan dimension) or to one mesh axis; multi-axis
# mappings are supported for meshes that split e.g. data across pods.
DEFAULT_RULES = {
    "batch": ("data",),
    "fsdp": ("data",),
    "model": ("model",),
    "seq_model": ("model",),
    "layers": (),
    # featurization sweeps: the slice axis of a (k, m, n) stack
    "slices": ("data",),
}

_STATE = threading.local()


def current_mesh() -> Optional[Mesh]:
    return getattr(_STATE, "mesh", None)


def current_rules() -> dict:
    return getattr(_STATE, "rules", DEFAULT_RULES)


@contextlib.contextmanager
def use_mesh(mesh: Mesh, rules: Optional[dict] = None):
    """Activate ``mesh`` (and optional rule overrides) for the block.

    Enters the jax mesh context too, so ``with_sharding_constraint`` with
    bare PartitionSpecs resolves inside jit.
    """
    prev = (current_mesh(), current_rules())
    _STATE.mesh = mesh
    _STATE.rules = dict(DEFAULT_RULES, **(rules or {}))
    try:
        with mesh:
            yield mesh
    finally:
        _STATE.mesh, _STATE.rules = prev


def _mesh_extent(mesh: Mesh, axes) -> int:
    """Product of the mesh sizes of ``axes`` (missing axes count as 1)."""
    if isinstance(axes, str):
        axes = (axes,)
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    ext = 1
    for a in axes:
        ext *= sizes.get(a, 1)
    return ext


def _physical_axes(logical: Optional[str], mesh: Mesh) -> Tuple[str, ...]:
    if logical is None:
        return ()
    axes = current_rules().get(logical, ())
    if isinstance(axes, str):
        axes = (axes,)
    return tuple(a for a in axes if a in mesh.axis_names)


def spec_for(shape: Sequence[int], logical_axes: Sequence[Optional[str]],
             mesh: Optional[Mesh] = None) -> P:
    """PartitionSpec for ``shape`` under the logical->physical rules.

    Any dimension whose size does not divide the mapped mesh extent falls
    back to replication (None) for that dimension only.
    """
    mesh = mesh if mesh is not None else current_mesh()
    if mesh is None:
        return P(*([None] * len(shape)))
    parts = []
    for dim, name in zip(shape, logical_axes):
        axes = _physical_axes(name, mesh)
        ext = _mesh_extent(mesh, axes)
        if axes and ext > 1 and dim % ext == 0:
            parts.append(axes[0] if len(axes) == 1 else axes)
        else:
            parts.append(None)
    return P(*parts)


def named_sharding(shape: Sequence[int], logical_axes, mesh: Optional[Mesh] = None
                   ) -> NamedSharding:
    mesh = mesh if mesh is not None else current_mesh()
    if mesh is None:
        raise ValueError("named_sharding needs a mesh (arg or use_mesh)")
    return NamedSharding(mesh, spec_for(shape, logical_axes, mesh))


def in_manual_context() -> bool:
    """True while tracing inside a ``jax.shard_map`` body."""
    return bool(jax.sharding.get_abstract_mesh().manual_axes)


def shard(x, *logical_axes):
    """Constrain ``x`` to the sharding implied by its logical axes; no-op
    when no mesh is active (single-host tests, CPU smoke runs).  Inside a
    ``jax.shard_map`` body the constraint names the tracing-time abstract
    mesh, whose manual axes carry the ``Manual`` type ``x`` varies over."""
    mesh = current_mesh()
    if mesh is None:
        return x
    spec = spec_for(x.shape, logical_axes, mesh)
    if in_manual_context():
        mesh = jax.sharding.get_abstract_mesh()
    return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, spec))


def pvary_manual(tree):
    """Mark ``tree`` as varying over the currently-manual shard_map axes,
    so scan carries created inside a shard_map body type-check."""
    manual = tuple(jax.sharding.get_abstract_mesh().manual_axes)
    if not manual:
        return tree
    return jax.tree.map(lambda a: jax.lax.pcast(a, manual, to="varying"),
                        tree)
