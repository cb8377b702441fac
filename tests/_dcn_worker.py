"""Worker process for tests/test_distributed.py: one of N
`jax.distributed` CPU processes wired over localhost (the DCN bring-up
path of parallel/mesh.py, SURVEY.md §5.8).

Run:  python tests/_dcn_worker.py <coordinator_port> <process_id> <nproc>

Prints one line per proven stage; the parent test asserts on them.
The worker forces the CPU platform itself, before its first backend use,
whatever the launch environment says (as tests/conftest.py does)."""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax

jax.config.update("jax_platforms", "cpu")


def main() -> int:
    port, pid, nproc = sys.argv[1], int(sys.argv[2]), int(sys.argv[3])

    import numpy as np

    from k8s_scheduler_tpu.parallel.mesh import (
        initialize_distributed,
        make_mesh,
        shard_snapshot,
    )

    # the wrapper under test: wires this process into the multi-host
    # runtime (DCN analogue; localhost gRPC here)
    initialize_distributed(f"127.0.0.1:{port}", nproc, pid)
    assert jax.process_count() == nproc, jax.process_count()
    devs = jax.devices()
    assert len(devs) == 2 * nproc, devs  # 2 local CPU devices per process
    print(f"INIT ok: processes={jax.process_count()} devices={len(devs)}",
          flush=True)

    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec

    # ---- one cross-process collective: sum over a globally sharded axis
    mesh = make_mesh(devs)
    D = len(devs)
    L = 8 * D
    sharding = NamedSharding(mesh, PartitionSpec("pods"))
    global_vals = np.arange(L, dtype=np.float32)
    x = jax.make_array_from_callback(
        (L,), sharding, lambda idx: global_vals[idx]
    )
    total = jax.jit(jnp.sum, out_shardings=NamedSharding(
        mesh, PartitionSpec()
    ))(x)
    got = float(np.asarray(total))
    want = float(global_vals.sum())
    assert got == want, (got, want)
    print(f"PSUM ok: {got}", flush=True)

    # ---- a tiny sharded scheduling cycle across both processes, proven
    # equal to the replicated run of the same snapshot
    from k8s_scheduler_tpu.core import build_cycle_fn
    from k8s_scheduler_tpu.models import MakeNode, MakePod, SnapshotEncoder

    nodes = [
        MakeNode(f"n{i}").capacity({"cpu": "4"}).obj() for i in range(8)
    ]
    pods = [
        MakePod(f"p{i}").req({"cpu": "2"}).created(float(i)).obj()
        for i in range(16)
    ]
    enc = SnapshotEncoder(pad_pods=16 * max(1, D // 2), pad_nodes=8)
    snap = enc.encode(nodes, pods)
    cycle = build_cycle_fn(commit_mode="rounds")

    ref = np.asarray(cycle(snap).assignment)  # replicated inputs
    sharded = shard_snapshot(snap, mesh)
    out = cycle(sharded)
    # replicate the (possibly sharded) result so every process can read
    # the full array
    rep = jax.jit(
        lambda a: a,
        out_shardings=NamedSharding(mesh, PartitionSpec()),
    )(out.assignment)
    got_a = np.asarray(rep)[: ref.size]
    np.testing.assert_array_equal(got_a, ref)
    placed = int((ref >= 0).sum())
    assert placed == 16  # 8 nodes x 4 cpu / 2-cpu pods
    print(f"CYCLE ok: placed={placed} sharded==replicated", flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
