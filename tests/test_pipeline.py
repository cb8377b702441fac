"""Split-phase serving pipeline: latency-program parity, slimmed decision
fetch, dispatch ordering, and forced-sync equivalence.

The contract under test (ISSUE 1 tentpole): the latency cycle program
(`build_cycle_fn(outputs="latency")` and the ServingPipeline that drives
the packed variants) is a SCHEDULING change, not a semantic one — the
decision carry (assignment / node_requested / unschedulable /
gang_dropped) is bit-identical to the monolithic program's in both commit
modes, the preemption chain consumes either interchangeably, and cycle
k's binds always fold into the cache before cycle k+1's encode reads it.
"""

import numpy as np
import pytest

from k8s_scheduler_tpu.config import SchedulerConfiguration
from k8s_scheduler_tpu.core import (
    Scheduler,
    ServingPipeline,
    build_cycle_fn,
    build_decision_slim_fn,
    build_preemption_fn,
)
from k8s_scheduler_tpu.models import MakeNode, MakePod, SnapshotEncoder
from k8s_scheduler_tpu.models.api import PodGroup


def _workload():
    """Nodes near capacity + a gang that can only partially place + a
    preemptor that needs an eviction + an infeasible pod: one snapshot
    that exercises normal placement, gang unwind, the preemption chain,
    and diagnosis-worthy unschedulability at once."""
    nodes = [
        MakeNode(f"n{i}").capacity({"cpu": "4", "memory": "8Gi"})
        .labels({"zone": f"z{i % 2}"}).obj()
        for i in range(4)
    ]
    existing = [
        (MakePod(f"run{i}").req({"cpu": "3"}).priority(0).obj(), f"n{i}")
        for i in range(2)  # n0/n1 nearly full; n2/n3 empty
    ]
    pods = (
        # high-priority, fit on the empty nodes
        [MakePod(f"hi{i}").req({"cpu": "2"}).priority(100)
         .created(float(i)).obj() for i in range(2)]
        # preemptor: nothing free fits 4 cpu, but evicting a prio-0
        # running pod frees a node
        + [MakePod("pre").req({"cpu": "4"}).priority(100)
           .created(5.0).obj()]
        # gang of 3 (minMember 3): at most 2 members fit -> unwind
        + [MakePod(f"g{i}").req({"cpu": "2"}).priority(10)
           .group("job").created(10.0 + i).obj() for i in range(3)]
        # infeasible even with eviction
        + [MakePod("huge").req({"cpu": "64"}).created(99.0).obj()]
    )
    groups = [PodGroup("job", 3)]
    return nodes, pods, existing, groups


@pytest.mark.parametrize("mode", ["scan", "rounds"])
def test_latency_program_parity(mode):
    nodes, pods, existing, groups = _workload()
    enc = SnapshotEncoder(pad_pods=8, pad_nodes=4)
    snap = enc.encode(nodes, pods, existing, pod_groups=groups)
    full = build_cycle_fn(commit_mode=mode)(snap)
    lat = build_cycle_fn(commit_mode=mode, outputs="latency")(snap)
    for f in (
        "assignment", "node_requested", "unschedulable", "gang_dropped"
    ):
        np.testing.assert_array_equal(
            np.asarray(getattr(full, f)),
            np.asarray(getattr(lat, f)),
            err_msg=f"{mode}: {f} diverged between full and latency",
        )
    # the fixture really exercises the paths the parity claim covers
    assert np.asarray(full.gang_dropped).any(), "gang unwind never fired"
    assert np.asarray(full.unschedulable).any()

    # the preemption chain consumes either result interchangeably
    pre_fn = build_preemption_fn()
    a = pre_fn(snap, full)
    b = pre_fn(snap, lat)
    np.testing.assert_array_equal(
        np.asarray(a.nominated), np.asarray(b.nominated)
    )
    np.testing.assert_array_equal(
        np.asarray(a.victims), np.asarray(b.victims)
    )
    assert (np.asarray(a.nominated) >= 0).any(), "preemption never fired"


def test_decision_slim_fetch_roundtrip():
    rng = np.random.default_rng(7)
    P, N = 64, 1000
    assignment = rng.integers(-1, N, size=P).astype(np.int32)
    unsched = rng.random(P) < 0.3
    dropped = rng.random(P) < 0.2
    slim = build_decision_slim_fn(N)
    a, flags = slim(assignment, unsched, dropped)
    a, flags = np.asarray(a), np.asarray(flags)
    assert a.dtype == np.int16  # N < 2**15 narrows exactly
    assert flags.dtype == np.uint8
    np.testing.assert_array_equal(a.astype(np.int32), assignment)
    np.testing.assert_array_equal((flags & 1) != 0, unsched)
    np.testing.assert_array_equal((flags & 2) != 0, dropped)
    # a node axis too wide for i16 keeps i32 (no silent wrap)
    wide = build_decision_slim_fn(1 << 15)
    a32, _ = wide(assignment, unsched, dropped)
    assert np.asarray(a32).dtype == np.int32


def test_pipeline_ordering_guard_and_slim_matches_result():
    nodes = [
        MakeNode(f"n{i}").capacity({"cpu": "4"}).obj() for i in range(3)
    ]
    pods = [MakePod(f"p{i}").req({"cpu": "1"}).obj() for i in range(4)]
    enc = SnapshotEncoder(pad_pods=8, pad_nodes=4)
    wbuf, bbuf, spec, _snap, _dirty = enc.encode_packed(nodes, pods)
    from k8s_scheduler_tpu.core.cycle import (
        build_packed_cycle_fn,
        build_stable_state_fn,
    )

    cyc = build_packed_cycle_fn(spec, commit_mode="scan")
    stable = build_stable_state_fn(spec)(wbuf, bbuf)
    pipe = ServingPipeline(cyc)
    h1 = pipe.dispatch(wbuf, bbuf, stable)
    # strict ordering: cycle k+1 may not dispatch before cycle k's
    # decisions were fetched (binds could not have folded yet)
    with pytest.raises(RuntimeError, match="decisions were fetched"):
        pipe.dispatch(wbuf, bbuf, stable)
    assignment, unsched, dropped = h1.decisions()
    np.testing.assert_array_equal(
        assignment, np.asarray(h1.result.assignment)
    )
    np.testing.assert_array_equal(
        unsched, np.asarray(h1.result.unschedulable)
    )
    np.testing.assert_array_equal(
        dropped, np.asarray(h1.result.gang_dropped)
    )
    assert pipe.stats["fetch_bytes"] > 0
    assert pipe.stats["fetch_bytes"] < pipe.stats["fetch_bytes_full"]
    # after the fetch, the next dispatch proceeds (slot reuse path)
    h2 = pipe.dispatch(wbuf, bbuf, stable)
    a2, _, _ = h2.decisions()
    np.testing.assert_array_equal(a2, assignment)
    # fold-free loops may opt out of the guard
    pipe2 = ServingPipeline(cyc, require_decision_fetch=False)
    pipe2.dispatch(wbuf, bbuf, stable)
    pipe2.dispatch(wbuf, bbuf, stable).decisions()


def test_fold_free_driver_keeps_silent_slot_release():
    """require_decision_fetch=False (fold-free probes/throughput loops)
    opted out of the ordering guard: a dispatch that wraps onto a slot
    whose handle was never fetched releases it silently."""
    nodes = [MakeNode("n0").capacity({"cpu": "4"}).obj()]
    pods = [MakePod("p0").req({"cpu": "1"}).obj()]
    enc = SnapshotEncoder(pad_pods=8, pad_nodes=4)
    wbuf, bbuf, spec, _snap, _dirty = enc.encode_packed(nodes, pods)
    from k8s_scheduler_tpu.core.cycle import (
        build_packed_cycle_fn,
        build_stable_state_fn,
    )

    cyc = build_packed_cycle_fn(spec, commit_mode="scan")
    stable = build_stable_state_fn(spec)(wbuf, bbuf)
    pipe = ServingPipeline(cyc, require_decision_fetch=False)
    first = pipe.dispatch(wbuf, bbuf, stable)
    pipe.dispatch(wbuf, bbuf, stable)
    # the third dispatch wraps onto the first's slot, still unfetched
    last = pipe.dispatch(wbuf, bbuf, stable)
    assert first.result is None  # released for the arena's reuse
    assignment, _unsched, _dropped = last.decisions()
    assert assignment[0] == 0


def test_donate_diagnosis_refuses_preemption_consumer():
    # a donated diagnosis consumes the slot's packed buffers; a
    # preemption program dispatched after it would read freed memory
    with pytest.raises(ValueError, match="donate_diagnosis"):
        ServingPipeline(
            lambda *a: None,
            diag_fn=lambda *a: None,
            preempt_fn=lambda *a: None,
            donate_diagnosis=True,
        )


def test_donated_diagnosis_consumes_slot_buffers():
    """The donation path end to end: the diagnosis program is the slot's
    last consumer, reject counts still attribute, and the slot recycles
    for the next dispatch (fresh device_put per cycle)."""
    from k8s_scheduler_tpu.core.cycle import (
        build_diagnosis_fn,
        build_packed_cycle_fn,
        build_stable_state_fn,
    )

    nodes = [
        MakeNode(f"n{i}").capacity({"cpu": "4"}).obj() for i in range(3)
    ]
    pods = [MakePod(f"p{i}").req({"cpu": "1"}).obj() for i in range(3)]
    pods.append(MakePod("huge").req({"cpu": "64"}).obj())
    enc = SnapshotEncoder(pad_pods=8, pad_nodes=4)
    wbuf, bbuf, spec, _snap, _dirty = enc.encode_packed(nodes, pods)
    cyc = build_packed_cycle_fn(spec, commit_mode="scan")
    stable = build_stable_state_fn(spec)(wbuf, bbuf)
    pipe = ServingPipeline(
        cyc,
        diag_fn=build_diagnosis_fn(spec, donate=True),
        donate_diagnosis=True,
    )
    h = pipe.dispatch(wbuf, bbuf, stable)
    a, unsched, _ = h.decisions()
    assert unsched[3]  # 'huge' found no node
    rc = h.reject_counts()
    assert rc is not None and rc[3].sum() > 0  # attributed off-path
    assert h._wbuf is None  # buffers handed to the diagnosis program
    h2 = pipe.dispatch(wbuf, bbuf, stable)
    h2.decisions()
    np.testing.assert_array_equal(h2.reject_counts(), rc)


def _mini_cluster(s: Scheduler, n_pods: int, prefix: str):
    for i in range(n_pods):
        s.on_pod_add(
            MakePod(f"{prefix}{i}").req({"cpu": "1"}).created(float(i))
            .obj()
        )


def test_binds_fold_before_next_cycle_encodes():
    """Cycle k's binds must be visible (as existing/assumed pods) to the
    encode of cycle k+1 — the pipeline's strict ordering contract at the
    Scheduler level."""
    s = Scheduler()
    seq: list[tuple] = []
    enc = s._encoder
    orig = enc.encode_packed

    def wrapped(nodes, pending, existing, *a, **k):
        seq.append(("encode", sorted(p.name for p, _ in existing)))
        return orig(nodes, pending, existing, *a, **k)

    enc.encode_packed = wrapped
    s.binder = lambda pod, node: seq.append(("bind", pod.name))
    for i in range(2):
        s.on_node_add(
            MakeNode(f"n{i}").capacity({"cpu": "4"}).obj()
        )
    _mini_cluster(s, 3, "a")
    s.schedule_cycle()
    _mini_cluster(s, 2, "b")
    s.schedule_cycle()
    encodes = [e for e in seq if e[0] == "encode"]
    binds_c1 = {
        name for kind, name in seq[: seq.index(encodes[1])]
        if kind == "bind"
    }
    assert binds_c1, "cycle 1 bound nothing; fixture broken"
    assert binds_c1 <= set(encodes[1][1]), (
        "cycle 2 encoded before cycle 1's binds folded into the cache"
    )


def test_apply_failure_releases_guard_and_next_cycle_dispatches():
    """A failure inside the apply phase that is not the device's (here:
    a host plugin raising a plain exception from `reserve`) leaves the
    ordering guard released — the decisions were fetched before the
    bind loop began — so later cycles dispatch and bind."""
    from k8s_scheduler_tpu.framework.host import HostPlugin

    class Boom(HostPlugin):
        name = "Boom"
        fired = False

        def reserve(self, pod, node_name):
            if not Boom.fired:
                Boom.fired = True
                raise RuntimeError("induced host-plugin failure")
            return None

    binds = []
    s = Scheduler(
        binder=lambda p, n: binds.append(p.name), pad_bucket=8,
        host_plugins=[Boom()],
    )
    s.on_node_add(MakeNode("n0").capacity({"cpu": "64"}).obj())
    s.on_pod_add(MakePod("p0").req({"cpu": "1"}).obj())
    with pytest.raises(RuntimeError, match="induced host-plugin"):
        s.schedule_cycle()
    for i in range(1, 3):
        s.on_pod_add(MakePod(f"p{i}").req({"cpu": "1"}).obj())
        s.schedule_cycle()
    assert "p1" in binds and "p2" in binds
    assert all(p.inflight() == 0 for *_fns, p in (
        e["fns"] for e in s._packed.values()
    ))


def test_forced_sync_produces_identical_bindings():
    """forced_sync is an execution-order escape hatch, not a semantic
    switch: the same workload binds identically either way."""
    results = {}
    for sync in (False, True):
        s = Scheduler(
            config=SchedulerConfiguration(forced_sync=sync)
        )
        bound = []
        s.binder = lambda pod, node, bound=bound: bound.append(
            (pod.name, node)
        )
        for i in range(3):
            s.on_node_add(
                MakeNode(f"n{i}").capacity({"cpu": "4"}).obj()
            )
        _mini_cluster(s, 5, "p")
        s.on_pod_add(MakePod("huge").req({"cpu": "64"}).obj())
        st = s.schedule_cycle()
        results[sync] = (sorted(bound), st.scheduled, st.unschedulable)
        # the pipeline really ran and fetched the slimmed payload
        pipes = [v["fns"][6] for v in s._packed.values()]
        assert pipes and pipes[0].fetch_bytes_total > 0
        assert pipes[0].forced_sync is sync
    assert results[False] == results[True]


def test_cpu_backend_arena_copy_guards_deferred_programs():
    """The CPU-backend arena race (PR 4's open note), closed: a dispatch
    fed RAW numpy arena buffers (device_put=False — probe paths and
    K8S_TPU_NO_DEVICE_PUT=1) must take an explicit device copy before
    async dispatch on the CPU backend. The deferred diagnosis/preemption
    programs are forced lazily, possibly AFTER the next encode rewrote
    the arena in place; without the copy they would attribute against
    the NEXT cycle's bytes (jax's CPU backend converts numpy args
    asynchronously / by aliasing, so the rewrite tears them)."""
    import jax

    if jax.default_backend() != "cpu":
        pytest.skip("CPU-backend aliasing guard")
    from k8s_scheduler_tpu.core.cycle import (
        build_diagnosis_fn,
        build_packed_cycle_fn,
        build_stable_state_fn,
    )

    nodes = [
        MakeNode(f"n{i}").capacity({"cpu": "4"}).obj() for i in range(3)
    ]
    pods = [MakePod(f"p{i}").req({"cpu": "1"}).obj() for i in range(3)]
    pods.append(MakePod("huge").req({"cpu": "64"}).obj())
    enc = SnapshotEncoder(pad_pods=8, pad_nodes=4)
    wbuf, bbuf, spec, _snap, _dirty = enc.encode_packed(nodes, pods)
    cyc = build_packed_cycle_fn(
        spec, commit_mode="scan", outputs="latency"
    )
    pipe = ServingPipeline(cyc, diag_fn=build_diagnosis_fn(spec))
    stable = build_stable_state_fn(spec)(wbuf.copy(), bbuf.copy())

    h1 = pipe.dispatch(wbuf, bbuf, stable, device_put=False)
    _, unsched, _ = h1.decisions()
    assert unsched[3]  # 'huge' found no node; diagnosis has work to do
    rc_ref = np.asarray(h1.reject_counts()).copy()
    assert rc_ref[3].sum() > 0

    h2 = pipe.dispatch(wbuf, bbuf, stable, device_put=False)
    h2.decisions()
    # the next encode's in-place arena rewrite, BEFORE the deferred
    # diagnosis is forced — without the explicit copy the diagnosis
    # would read these zeros and attribute nothing
    wbuf[:] = 0
    bbuf[:] = 0
    np.testing.assert_array_equal(
        np.asarray(h2.reject_counts()), rc_ref,
        err_msg="deferred diagnosis read the rewritten arena "
        "(CPU-backend copy guard regressed)",
    )
