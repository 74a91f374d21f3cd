"""ZeRO-2/3 sharding over a mesh axis — the GSPMD mechanism.

Reference parity: ``fleet/meta_optimizers/sharding_optimizer.py:45,568``
(1,820 LoC of program rewriting: param/grad/optimizer-state partitioning,
broadcast-on-use, CPU offload via ``sharding/offload_helper.py``).

TPU-first: no program rewriting.  The ZeRO stages are *placement
decisions* expressed as PartitionSpecs and one sharding constraint:

- stage 1: optimizer state sharded over the ``sharding`` axis; XLA
  dynamic-slices the (replicated) grads for the update and all-gathers
  updated params — broadcast-on-use, compiler-inserted.
- stage 2: additionally constrain grads to the sharded spec — GSPMD then
  *reduce-scatters* the data-parallel gradient sum instead of
  all-reducing it (the stage-2 memory/traffic saving).
- stage 3: params themselves live sharded; every use inside the forward
  all-gathers transiently (freed after use under scan/remat), so full
  params never sit resident.
- offload: the optimizer-state shardings take
  ``memory_kind='pinned_host'``; the step device_puts them in and out —
  state lives in host RAM between steps (offload_helper semantics).

The ``sharding`` axis also shards the global batch (reference hybrid
topology [dp, pp, sharding, mp]: sharding IS a data-parallel axis whose
gradient reduction is scattered instead of replicated).
"""
from __future__ import annotations

from typing import Optional

import jax
from jax.sharding import NamedSharding, PartitionSpec as P

__all__ = ["add_sharding_axis", "shard_tree", "zero_state_shardings"]


def add_sharding_axis(ns: NamedSharding, shape, axis: str = "sharding",
                      memory_kind: Optional[str] = None) -> NamedSharding:
    """Extend a param's NamedSharding with ``axis`` on the first
    dimension that is currently unsharded and divisible by the axis size
    (the reference shards flattened params by rank; here we keep array
    structure and pick a dimension)."""
    mesh = ns.mesh
    if memory_kind == "pinned_host":
        # offload: raises on a TPU without the space, None (placement
        # no-op) on a host backend without it
        from ....core.place import pinned_host_kind
        memory_kind = pinned_host_kind(next(iter(mesh.devices.flat)))
    n = mesh.shape.get(axis, 1)
    spec = list(ns.spec) + [None] * (len(shape) - len(ns.spec))
    if any(axis == p or (isinstance(p, tuple) and axis in p)
           for p in spec):
        # already sharded over this axis (tp placement) — still honor a
        # requested memory kind (offload must not silently drop)
        if memory_kind and getattr(ns, "memory_kind", None) != memory_kind:
            return NamedSharding(mesh, ns.spec, memory_kind=memory_kind)
        return ns
    if n > 1:
        for i, (p, s) in enumerate(zip(spec, shape)):
            if p is None and s % n == 0 and s >= n:
                spec[i] = axis
                break
    kwargs = {"memory_kind": memory_kind} if memory_kind else {}
    return NamedSharding(mesh, P(*spec), **kwargs)


def shard_tree(shardings_tree, shapes_tree, axis: str = "sharding",
               memory_kind: Optional[str] = None):
    """Map add_sharding_axis over a pytree of NamedShardings."""
    return jax.tree.map(
        lambda ns, shp: add_sharding_axis(ns, shp, axis, memory_kind),
        shardings_tree, shapes_tree)


def zero_state_shardings(param_shardings, param_shapes, *,
                         stage: int = 1, offload: bool = False,
                         axis: str = "sharding"):
    """(param_shardings, state_shardings) for a given ZeRO stage."""
    mk = "pinned_host" if offload else None
    state = shard_tree(param_shardings, param_shapes, axis, mk)
    if stage >= 3:
        param_shardings = shard_tree(param_shardings, param_shapes, axis)
    return param_shardings, state
