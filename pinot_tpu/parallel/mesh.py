"""Device mesh helpers: the single source of truth for mesh axis names.

Reference parity: the scatter axis of Pinot's deployment — segments spread
over servers, replicas over replica-groups (SURVEY.md 2.5).  TPU-native form:
a 2-D ``jax.make_mesh((R, S), (REPLICA_AXIS, SHARD_AXIS))`` whose axes name
the two parallelism strategies:

  shard    - horizontal data partitioning (scatter-gather analog): shards of
             the stacked table live on distinct devices and partial results
             combine in-graph over ICI.
  replica  - replica rows for QPS scaling: each mesh row holds a full copy
             of the data on its own 1-D shard submesh; the router
             (cluster/broker round-robin over rows) picks one per batch.

The legacy single-host form is a 1-D ``SEG_AXIS`` mesh — equivalent to
(R=1) with the shard axis named "seg".  Both spellings flow through the
engines as an *axes tuple* (``data_axes``), ordered outermost-first:
``(REPLICA_AXIS, SHARD_AXIS)``.  Cross-device combines must walk that tuple
innermost-first (``combine_hierarchical``): the shard reduction rides ICI
and shrinks the operand to one partial table per replica row, so the single
outer reduction — the only one that crosses host/DCN boundaries on a
multi-host pod — moves partial-table bytes, not raw rows.

Axis names are exported as constants; kernels must not spell them as bare
string literals at collective call sites (repo_lint W025) so a topology
rename cannot silently desynchronize a kernel from the mesh it runs on.
"""
from __future__ import annotations

from typing import Callable, List, Optional, Sequence, Tuple, Union

import numpy as np

#: QPS axis: replica rows, each a full data copy (cross-host / DCN on pods).
REPLICA_AXIS = "replica"
#: Capacity axis: table shards within one replica row (intra-host / ICI).
SHARD_AXIS = "shard"
#: Legacy 1-D data axis used by the single-host engines since M2.
SEG_AXIS = "seg"

#: Canonical 2-D data-placement axes, outermost (DCN) first.
DATA_AXES: Tuple[str, str] = (REPLICA_AXIS, SHARD_AXIS)

AxisSpec = Union[str, Sequence[str]]


def normalize_axes(axis: AxisSpec) -> Tuple[str, ...]:
    """Coerce a single axis name or a sequence of them to a tuple."""
    if isinstance(axis, str):
        return (axis,)
    return tuple(axis)


def default_mesh(axis: str = SEG_AXIS, num_devices: Optional[int] = None):
    """1-D mesh over all (or the first N) local devices."""
    import jax
    from jax.sharding import Mesh

    devs = jax.devices()
    if num_devices is not None:
        devs = devs[:num_devices]
    return Mesh(np.asarray(devs), (axis,))


def make_mesh2d(
    num_replicas: int = 1,
    num_shards: Optional[int] = None,
    num_devices: Optional[int] = None,
):
    """2-D (REPLICA_AXIS, SHARD_AXIS) mesh: R replica rows of S shards each.

    ``num_shards`` defaults to devices/num_replicas.  Raises with a clear
    message when the device count does not factor (e.g. 8 devices into 3
    replica rows).  Prefers ``jax.make_mesh`` so the device order respects
    the physical interconnect (ICI-contiguous shard rows on real pods).
    """
    import jax

    devs = jax.devices()
    n = len(devs) if num_devices is None else int(num_devices)
    if num_shards is None:
        if n % num_replicas:
            raise ValueError(
                f"{n} devices not divisible into {num_replicas} replica rows"
            )
        num_shards = n // num_replicas
    if num_replicas * num_shards != n:
        raise ValueError(
            f"mesh shape ({num_replicas} replicas x {num_shards} shards) "
            f"needs {num_replicas * num_shards} devices, have {n}"
        )
    from jax.sharding import AxisType, Mesh

    if num_devices is None:
        # Auto axes, like Mesh(): jax.make_mesh defaults to Explicit axes,
        # which put shardings into array types and refuse plain jnp slices
        # of a sharded table (the in-graph sparse merge)
        return jax.make_mesh(
            (num_replicas, num_shards), DATA_AXES,
            axis_types=(AxisType.Auto, AxisType.Auto),
        )

    arr = np.asarray(devs[:n]).reshape(num_replicas, num_shards)
    return Mesh(arr, DATA_AXES)


def data_axes(mesh) -> Tuple[str, ...]:
    """The mesh axes that carry table rows, outermost first.

    ``(SEG_AXIS,)`` for the legacy 1-D mesh, ``(REPLICA_AXIS, SHARD_AXIS)``
    for the 2-D mesh — i.e. every mesh axis, in mesh order.
    """
    return tuple(mesh.axis_names)


def replica_rows(mesh) -> List:
    """One 1-D SHARD_AXIS submesh per replica row of a 2-D mesh.

    Each row sees a disjoint device set, so per-row engines stage disjoint
    full data copies (device caches key on mesh identity) under their own
    residency budgets.  A 1-D mesh is its own single row.
    """
    from jax.sharding import Mesh

    names = tuple(mesh.axis_names)
    if len(names) == 1:
        return [mesh]
    if names != DATA_AXES:
        raise ValueError(f"expected axes {DATA_AXES}, mesh has {names}")
    return [
        Mesh(np.asarray(mesh.devices[r]), (SHARD_AXIS,))
        for r in range(mesh.devices.shape[0])
    ]


def combine_hierarchical(op: Callable, x, axes: AxisSpec):
    """Apply a collective reduction axis-by-axis, innermost first.

    For ``(REPLICA_AXIS, SHARD_AXIS)`` this reduces over SHARD_AXIS (ICI)
    first — collapsing each replica row to one partial — then once over
    REPLICA_AXIS, so the reduction that crosses host/DCN boundaries carries
    partial-table bytes.  Reducing axis-by-axis is value-equal to a single
    reduction over the axes tuple; the split only pins the network order.
    """
    for ax in reversed(normalize_axes(axes)):
        x = op(x, ax)
    return x


def psum_hierarchical(x, axes: AxisSpec):
    from jax import lax

    return combine_hierarchical(lax.psum, x, axes)


def psum_ordered(x, axes: AxisSpec):
    """Order-canonical sum: every device's partial, reduced in GLOBAL device
    order with one fixed-order reduction.

    Integer psum is exact under any association, but FLOAT partial sums are
    not: a flat 8-way psum and a shard-then-replica hierarchy differ by ulps,
    which would break the topology bit-parity contract (a 2x4 run must
    reproduce the 1-D mesh's float BITS).  So float "add" combines gather the
    partials instead — hierarchically, shard/ICI stage first, so the
    replica/DCN stage still moves per-row blocks of partial-table bytes —
    into a [num_devices, ...] array whose leading dim is global (row-major)
    device order on EVERY topology, then left-fold it with an EXPLICIT add
    chain.  Not jnp.sum: XLA pattern-matches all-gather+reduce back into an
    all-reduce whose internal order follows the mesh topology — the exact
    nondeterminism this function exists to kill.  A chain of dependent adds
    cannot be reassociated, so: same operand order + same association = same
    bits, mesh shape be damned.  Costs a transient num_devices x partial
    buffer per device; partials are group tables/scalars, not raw rows, so
    this stays small.
    """
    from jax import lax

    names = normalize_axes(axes)
    for ax in reversed(names):  # innermost/ICI first, like the psum hierarchy
        x = lax.all_gather(x, ax)  # prepends that axis's device dim
    # leading dims stack outermost-first after the loop -> row-major flatten
    # is global device order, identical for ("seg",), (2,4), (4,2), (8,1)
    x = x.reshape((-1,) + x.shape[len(names):])
    total = x[0]
    for i in range(1, x.shape[0]):
        total = total + x[i]
    return total


def pmin_hierarchical(x, axes: AxisSpec):
    from jax import lax

    return combine_hierarchical(lax.pmin, x, axes)


def pmax_hierarchical(x, axes: AxisSpec):
    from jax import lax

    return combine_hierarchical(lax.pmax, x, axes)
