"""Device mesh + sharding layout for the scheduling program.

The reference's intra-process parallelism is a 16-goroutine `Parallelizer`
fanning Filter/Score over nodes (SURVEY.md §2 C6 — [UNVERIFIED], mount
empty); its distributed story is HTTPS to the API server. The TPU-native
equivalents (SURVEY.md §2 parallelism checklist, §5.8): the batched static
phase shards the **pods axis** across mesh devices (data-parallel masks and
scores; XLA inserts ICI collectives where the commit scan needs the full
row), and at 5k-node scale the **nodes axis** can shard on a second mesh
dimension. No NCCL/MPI — `jax.sharding` + XLA collectives only.
"""

from __future__ import annotations

import dataclasses

import numpy as np

# module-level on purpose: mesh_pin runs INSIDE jitted programs, where
# a lazy first import is a trace-safety violation (schedlint TS001).
# Importing jax initialises no backend and takes no chip.
import jax
from jax.sharding import NamedSharding, PartitionSpec

# The mesh-axis name inventory, pinned by schedlint ID008 against the
# collective budget allowlist (parallel/audit.COLLECTIVE_BUDGETS) and
# the README "## Multi-chip and multi-host" budget table: the pods axis
# is the data-parallel batch dimension every [P, ...] array shards on;
# the trailing nodes axis (2-D meshes) stays intra-host (JAX orders
# devices host-major) because the claim path's per-node collectives are
# the latency-critical ones. Renaming an axis without updating the
# budget allowlist would silently un-classify its collectives.
MESH_AXES = ("pods", "nodes")


def mesh_pin(arr, mesh, axes):
    """`with_sharding_constraint` an array onto named mesh axes, one
    per leading dim (None entries and dims beyond `axes` stay
    unconstrained). An axis applies only when the mesh carries it with
    size > 1 AND it divides that dim — otherwise the dim is pinned
    replicated, matching shard_snapshot's fallback. The ONE place the
    "which PartitionSpec does this array get" rule lives: the rounds
    engine's compacted views (ops/rounds.py shard_view) and the carry
    tables (core/cycle.py _constrain_carry) both delegate here, so the
    sharding rule cannot drift between the two layers."""
    spec = [None] * arr.ndim
    for d, axis in enumerate(axes[: arr.ndim]):
        if not axis:
            continue
        size = mesh.shape.get(axis, 1)
        if size > 1 and arr.shape[d] % size == 0:
            spec[d] = axis
    return jax.lax.with_sharding_constraint(
        arr, NamedSharding(mesh, PartitionSpec(*spec))
    )


def initialize_distributed(
    coordinator_address: str | None = None,
    num_processes: int | None = None,
    process_id: int | None = None,
) -> None:
    """Multi-host (DCN) initialization (SURVEY.md §5.8).

    One scheduler process per TPU host; `jax.distributed.initialize` wires
    the hosts into one runtime so `jax.devices()` spans every chip and
    `make_mesh` lays axes over ICI within a host and DCN across hosts
    (JAX orders devices host-major, so the trailing mesh dimension stays
    intra-host — put the collective-heavy 'nodes' axis there). Arguments
    default to the standard JAX env vars (JAX_COORDINATOR_ADDRESS,
    JAX_NUM_PROCESSES, JAX_PROCESS_ID), so launchers that set those can
    call this with no arguments. A no-op on single-process deployments.

    Host-side state (queue/cache, the gRPC shim) stays on process 0 — the
    cluster-facing link is unchanged; only the device program spans hosts.
    """
    import os

    import jax

    if coordinator_address is None:
        coordinator_address = os.environ.get("JAX_COORDINATOR_ADDRESS")
    if coordinator_address is None:
        return  # single host
    jax.distributed.initialize(
        coordinator_address=coordinator_address,
        num_processes=num_processes,
        process_id=process_id,
    )


def make_mesh(devices=None, nodes_axis: int = 1):
    """1-D ('pods',) mesh by default; pass nodes_axis>1 for a 2-D
    ('pods','nodes') mesh at large node counts."""
    import jax
    from jax.sharding import Mesh

    devices = list(devices if devices is not None else jax.devices())
    n = len(devices)
    if nodes_axis > 1:
        assert n % nodes_axis == 0
        arr = np.array(devices).reshape(n // nodes_axis, nodes_axis)
        return Mesh(arr, MESH_AXES)
    return Mesh(np.array(devices), MESH_AXES[:1])


def replicated(mesh):
    """The every-device-holds-all layout on `mesh`; None without one (a
    one-device run places nothing). The packed snapshot buffers are
    uploaded under it when serving sharded: an UNPLACED input lets XLA
    propagate a sharding onto the parameter, and at the 10,000 x 5,000
    regime the partitioning it then picks for the preemption program
    aborts this libtpu's compiler (a check failure in the all-reduce
    fusion emitter, met on four v5e chips in PR 22 and reproduced by
    tests/test_tpu_compile.py's four-chip case). Placed inputs
    leave it no such choice, on the jit path and the AOT path alike."""
    if mesh is None:
        return None
    return NamedSharding(mesh, PartitionSpec())


def shard_snapshot(snap, mesh):
    """Lay out a ClusterSnapshot over the mesh: pod-axis arrays sharded on
    'pods' (and node-axis arrays on 'nodes' when the mesh has that axis);
    everything else replicated. Arrays whose leading dim doesn't divide the
    mesh axis stay replicated (tiny dedup tables are cheaper replicated
    than gathered)."""
    import jax
    from jax.sharding import NamedSharding, PartitionSpec

    pods_size = mesh.shape["pods"]
    nodes_size = mesh.shape.get("nodes", 1)

    # multi-host meshes contain devices this process cannot address:
    # device_put of host data is single-process-only, so each process
    # contributes its local shards from the (replicated) host array —
    # the DCN path proven by tests/test_distributed.py
    me = jax.process_index()
    multiproc = any(
        d.process_index != me for d in np.asarray(mesh.devices).flat
    )

    def put(v, ns):
        if multiproc:
            return jax.make_array_from_callback(
                v.shape, ns, lambda idx: v[idx]
            )
        return jax.device_put(v, ns)

    out = {}
    for f in dataclasses.fields(snap):
        v = getattr(snap, f.name)
        if not isinstance(v, (np.ndarray, jax.Array)):
            out[f.name] = v
            continue
        spec = [None] * v.ndim
        if (
            f.name.startswith("pod_")
            and v.ndim >= 1
            and v.shape[0] % pods_size == 0
        ):
            spec[0] = "pods"
        elif (
            f.name.startswith("node_")
            and nodes_size > 1
            and v.ndim >= 1
            and v.shape[0] % nodes_size == 0
        ):
            spec[0] = "nodes"
        out[f.name] = put(v, NamedSharding(mesh, PartitionSpec(*spec)))
    return dataclasses.replace(snap, **{k: v for k, v in out.items()})
