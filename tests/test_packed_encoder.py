"""Differential tests for SnapshotEncoder.encode_packed — the delta-arena
fast path must be indistinguishable (field-for-field) from a full
encode()+pack() for ANY snapshot sequence: churn, pending-count changes,
dictionary growth, stable-side changes, in-place nomination updates.

Methodology (SURVEY.md §4, build-side additions): two encoders consume the
identical object sequence; encoder A uses encode_packed (exercising the
delta path wherever its prechecks allow), encoder B always full-encodes.
Unpacking A's arena buffers must reproduce B's snapshot exactly.
"""

import dataclasses

import numpy as np
import pytest

from k8s_scheduler_tpu.models import MakeNode, MakePod, SnapshotEncoder, packing
from k8s_scheduler_tpu.models.api import (
    LabelSelector,
    PodDisruptionBudget,
    PodGroup,
)
from k8s_scheduler_tpu.models.encoding import ClusterSnapshot
from k8s_scheduler_tpu.utils.synth import make_cluster, make_pods


def assert_same_snapshot(got: ClusterSnapshot, ref: ClusterSnapshot):
    for f in dataclasses.fields(ClusterSnapshot):
        rv = getattr(ref, f.name)
        gv = getattr(got, f.name)
        if rv is None and gv is None:
            continue
        if isinstance(rv, np.ndarray) or hasattr(rv, "dtype"):
            ga, ra = np.asarray(gv), np.asarray(rv)
            assert ga.shape == ra.shape, f.name
            eq = (
                np.array_equal(ga, ra, equal_nan=True)
                if ga.dtype.kind == "f"
                else np.array_equal(ga, ra)
            )
            assert eq, f"field {f.name} differs"
        else:
            assert rv == gv, f"aux {f.name}: {gv!r} != {rv!r}"


class Driver:
    """Feeds the same objects to the packed and the reference encoder."""

    def __init__(self, pad_pods=128, pad_nodes=16):
        self.a = SnapshotEncoder(pad_pods=pad_pods, pad_nodes=pad_nodes)
        self.b = SnapshotEncoder(pad_pods=pad_pods, pad_nodes=pad_nodes)

    def step(self, nodes, pending, existing=(), groups=(), mutated=frozenset(),
             **kw):
        w, bb, spec, vsnap, _dirty = self.a.encode_packed(
            nodes, pending, existing, groups, mutated_ids=mutated, **kw
        )
        ref = self.b.encode(nodes, pending, existing, groups, **kw)
        self.ref = ref  # the reference's snapshot of this step
        got = packing.unpack(np.asarray(w), np.asarray(bb), spec)
        assert_same_snapshot(got, ref)
        # the view snapshot must alias the arena (same data, same ids)
        assert vsnap.pod_requested.base is not None
        return spec


def test_packed_equals_full_over_churned_sequence():
    rng = np.random.default_rng(0)
    nodes = make_cluster(10)
    d = Driver()
    pending = make_pods(
        60, seed=1, affinity_fraction=0.3, anti_affinity_fraction=0.2,
        spread_fraction=0.2, selector_fraction=0.3, num_apps=6,
        priorities=(0, 10),
    )
    existing = [(p, f"node-{i % 10}") for i, p in enumerate(
        make_pods(20, seed=2, name_prefix="run", affinity_fraction=0.2,
                  num_apps=6)
    )]
    specs = set()
    for i in range(8):
        # churn ~25% with fresh objects (fresh names/apps grow dictionaries
        # in early rounds -> full path; later rounds hit the delta path)
        k = 15
        idx = rng.choice(len(pending), size=k, replace=False)
        fresh = make_pods(
            k, seed=100 + i, name_prefix=f"p{i}-", affinity_fraction=0.3,
            spread_fraction=0.2, selector_fraction=0.3, num_apps=6,
            priorities=(0, 10),
        )
        for j, f in zip(idx, fresh):
            pending[j] = f
        specs.add(d.step(nodes, pending, existing).key())
    assert len(specs) == 1  # sticky dims: no packed-regime churn


def test_packed_pending_count_changes():
    nodes = make_cluster(4)
    d = Driver()
    pods = make_pods(40, seed=3)
    d.step(nodes, pods)
    d.step(nodes, pods[:25])  # shrink
    d.step(nodes, pods[:25] + make_pods(10, seed=4, name_prefix="n"))  # grow
    d.step(nodes, [])  # empty pending


def test_packed_detects_stable_change():
    d = Driver()
    nodes = make_cluster(4)
    pods = make_pods(20, seed=5)
    d.step(nodes, pods, [(pods[0], "node-0")])
    # node list replaced -> full path, still exact
    nodes2 = make_cluster(5)
    d.step(nodes2, pods, [(pods[0], "node-0")])
    # existing set changed -> full path, still exact
    d.step(nodes2, pods, [(pods[0], "node-1"), (pods[1], "node-2")])


def test_packed_nominated_mutation_reported():
    d = Driver()
    nodes = make_cluster(4)
    pods = make_pods(20, seed=6)
    d.step(nodes, pods)
    # in-place nomination (what the serving driver does after preemption)
    pods[3].nominated_node_name = "node-2"
    d.step(nodes, pods, mutated=frozenset({id(pods[3])}))


def test_packed_gangs_and_ports_and_pins():
    d = Driver()
    nodes = make_cluster(6)
    pods = [
        MakePod(f"g-{i}").req({"cpu": "500m"}).group("job-a")
        .created(float(i)).obj()
        for i in range(4)
    ]
    pods.append(
        MakePod("portpod").req({"cpu": "100m"}).host_port(8080).obj()
    )
    pods.append(MakePod("pinned").req({"cpu": "100m"}).node("node-2").obj())
    groups = [PodGroup("job-a", 3)]
    d.step(nodes, pods, groups=groups)
    # churn the port pod (new distinct port within the sticky Q pad)
    pods[4] = MakePod("portpod2").req({"cpu": "100m"}).host_port(8081).obj()
    d.step(nodes, pods, groups=groups)
    # group min_member change flows through the delta path
    d.step(nodes, pods, groups=[PodGroup("job-a", 4)])


def test_arena_survives_async_dispatch_mutation():
    """The arena reuse contract, as the serving pipeline enforces it: a
    cycle's outputs are FETCHED before the next encode rewrites the
    arena (ServingPipeline.dispatch refuses cycle k+1 until cycle k's
    decisions were fetched; two slots alternate).

    This test originally asserted a stronger property — that JAX copies
    a jit's host (numpy) arguments synchronously at call time, so
    rewriting the arena IMMEDIATELY behind a dispatch is safe. That is
    false on this jaxlib's CPU backend: the host->device copy happens
    asynchronously on the dispatch thread, and a 15-line pure-jax loop
    (mutate a numpy arg right after a jit call, then force the output)
    reproduces torn copies with no repo code involved — which made this
    test an ~coin flip in full-suite runs on ANY tree. What serving
    actually relies on is the fetch-then-rewrite ordering; that is what
    is driven here. (Re-encoding after a mutation re-baselines the
    digest: interning dictionaries are grow-only, so a new pod's name
    legitimately shifts packed bytes.)"""
    import jax

    d = SnapshotEncoder(pad_pods=64, pad_nodes=8)
    nodes = make_cluster(4)
    pods = make_pods(30, seed=7)
    w, b, spec, _, _ = d.encode_packed(nodes, pods)

    @jax.jit
    def digest(wb, bb):
        return (wb % 9973).sum(), (bb.astype("int32")).sum()

    out = digest(w, b)
    ref = (int(np.asarray(out[0])), int(np.asarray(out[1])))
    for i in range(5):
        out = digest(w, b)
        # the decision-fetch analogue: force cycle i's outputs BEFORE
        # the arena may be rewritten for cycle i+1 (the pipeline's
        # require_decision_fetch guard provides this order in serving)
        got = (int(np.asarray(out[0])), int(np.asarray(out[1])))
        assert got == ref  # fetched outputs reflect this cycle's bytes
        # now the rewrite is legal (cycle i+1's delta writes)
        pods2 = list(pods)
        pods2[0] = MakePod(f"mut-{i}").req({"cpu": "250m"}).obj()
        d.encode_packed(nodes, pods2)
        # restore and re-encode for the next iteration's baseline
        w, b, spec, _, _ = d.encode_packed(nodes, pods)
        out = digest(w, b)
        ref = (int(np.asarray(out[0])), int(np.asarray(out[1])))


def test_sticky_dims_do_not_shrink():
    enc = SnapshotEncoder(pad_pods=32, pad_nodes=8)
    nodes = make_cluster(2)
    many_labels = MakePod("lab").labels(
        {f"k{i}": f"v{i}" for i in range(12)}
    ).req({"cpu": "1"}).obj()
    s1 = enc.encode(nodes, [many_labels])
    mpl = s1.pod_label_keys.shape[1]
    s2 = enc.encode(nodes, [MakePod("tiny").req({"cpu": "1"}).obj()])
    assert s2.pod_label_keys.shape[1] == mpl


if __name__ == "__main__":
    import sys

    pytest.main([__file__, "-v"] + sys.argv[1:])


def test_fused_mixed_native_and_fallback_rows():
    """Round-5 fused path (native pod_rows_into): a dirty batch mixing
    natively-written pods with Python-fallback pods (volumes force the
    fallback) must still be byte-identical to the full encode."""
    from k8s_scheduler_tpu import native

    if native.pod_rows_into is None:
        pytest.skip("native extension not built")
    nodes = make_cluster(6)
    d = Driver()
    pending = make_pods(30, seed=11, affinity_fraction=0.3, num_apps=4)
    # volume-bearing pods take the dict fallback inside the fused call
    pending += [
        MakePod(f"vol-{i}").req({"cpu": "250m"}).volume(f"claim-{i}").obj()
        for i in range(4)
    ]
    d.step(nodes, pending)
    # churn BOTH kinds in one dirty batch -> mixed fused/fallback delta
    pending[0] = make_pods(1, seed=99, name_prefix="fresh")[0]
    pending[30] = (
        MakePod("vol-new").req({"cpu": "250m"}).volume("claim-new").obj()
    )
    d.step(nodes, pending)
    # and again so the second delta reuses rows[i] stored by both paths
    pending[1] = make_pods(1, seed=100, name_prefix="fresh2")[0]
    d.step(nodes, pending)


def test_fused_guard_overflow_falls_back_to_full():
    """A dirty pod that overflows an arena dim (here: more labels than
    MPL) must make the fused call report guard_ok=False and the encoder
    take the full path — still exact."""
    from k8s_scheduler_tpu import native

    if native.pod_rows_into is None:
        pytest.skip("native extension not built")
    nodes = make_cluster(4)
    d = Driver()
    pods = make_pods(20, seed=12)
    d.step(nodes, pods)
    pods[3] = (
        MakePod("many-labels")
        .req({"cpu": "100m"})
        .labels({f"key-{j}": f"v-{j}" for j in range(40)})
        .obj()
    )  # blow past the sticky MPL dim
    d.step(nodes, pods)
    # subsequent delta over the grown arena still works
    pods[4] = make_pods(1, seed=101, name_prefix="after")[0]
    d.step(nodes, pods)


def test_fold_existing_append_tail_remove_and_rebase():
    """Round-5 incremental existing-fold: appending bound pods and
    removing a completion batch (tail) must update the stable side IN
    PLACE (no full encode) and stay byte-identical to a from-scratch
    assembly — including the NodePorts used-port lists, the node_pods
    victim table, and an exist_start re-base when an appended pod is
    older than every existing one."""
    from k8s_scheduler_tpu import native

    if native.pod_rows_into is None:
        pytest.skip("native extension not built")
    nodes = make_cluster(8)
    d = Driver()
    pods = make_pods(40, seed=21, affinity_fraction=0.2, num_apps=5)
    # one pending pod with a host port (exercises the port-dirty repair)
    pods[7] = (
        MakePod("portpod").req({"cpu": "100m"}).host_port(8080).obj()
    )
    existing = [
        (p, f"node-{i % 8}")
        for i, p in enumerate(
            make_pods(20, seed=22, name_prefix="run", num_apps=5)
        )
    ]
    d.step(nodes, pods, existing)
    d.step(nodes, pods, existing)  # warm the delta path
    folds0 = getattr(d.a, "fold_hits", 0)
    fulls0 = d.a.full_encodes

    # ---- bindings fold in (append), one of them port-bearing ----
    bound = [(pods[i], f"node-{i % 8}") for i in range(6)]
    bound.append(
        (MakePod("bport").req({"cpu": "100m"}).host_port(9090).obj(), "node-3")
    )
    existing2 = existing + bound
    pending2 = pods[6:] + make_pods(5, seed=31, name_prefix="arr", num_apps=5)
    d.step(nodes, pending2, existing2)
    assert d.a.fold_hits == folds0 + 1
    assert d.a.full_encodes == fulls0

    # ---- completion batch: the appended tail leaves ----
    existing3 = existing2[: len(existing)]
    d.step(nodes, pending2, existing3)
    assert d.a.fold_hits == folds0 + 2
    assert d.a.full_encodes == fulls0

    # ---- re-base: an appended pod OLDER than every existing pod ----
    old_pod = (
        MakePod("ancient").req({"cpu": "100m"}).created(-1000.0).obj()
    )
    existing4 = existing3 + [(old_pod, "node-1")]
    d.step(nodes, pending2, existing4)
    assert d.a.fold_hits == folds0 + 3
    assert d.a.full_encodes == fulls0

    # ---- middle-of-list removal: the rows behind the hole move up
    removed0 = d.a.fold_removed_pods
    existing5 = existing4[1:]
    d.step(nodes, pending2, existing5)
    assert d.a.fold_hits == folds0 + 4
    assert d.a.full_encodes == fulls0
    assert d.a.fold_removed_pods == removed0 + 1
    assert_fold_exact(d)


def test_fold_unfold_float_exactness_under_inexact_requests():
    """f32-rounding stress for the fold/un-fold node_requested recompute:
    0.1-core requests are inexact in float32, so a subtract-based un-fold
    would drift by ULPs from a from-scratch assembly. Repeated
    fold/evict cycles must stay byte-identical (the Driver compares
    every array)."""
    from k8s_scheduler_tpu import native

    if native.pod_rows_into is None:
        pytest.skip("native extension not built")
    nodes = make_cluster(4)
    d = Driver()
    pods = [
        MakePod(f"t-{i}").req({"cpu": "100m", "memory": "100Mi"}).obj()
        for i in range(24)
    ]
    existing = [
        (MakePod(f"r-{i}").req({"cpu": "100m"}).obj(), f"node-{i % 4}")
        for i in range(12)
    ]
    d.step(nodes, pods, existing)
    d.step(nodes, pods, existing)
    for round_ in range(3):
        bound = [
            (pods[round_ * 4 + j], f"node-{j % 4}") for j in range(4)
        ]
        existing = existing + bound
        d.step(nodes, pods, existing)
        existing = existing[:12]  # completion batch
        d.step(nodes, pods, existing)
    assert d.a.fold_hits >= 6


def assert_fold_exact(d):
    """The fold's exactness contract, byte for byte: every `st` array of
    the folding encoder against the reference encoder's from-scratch
    assembly over the same lists, and both arena buffers against the
    reference's freshly packed snapshot."""
    sa, sb = d.a._stable, d.b._stable
    for k, vb in sb.items():
        if isinstance(vb, np.ndarray):
            va = sa[k]
            assert va.dtype == vb.dtype and va.shape == vb.shape, k
            assert va.tobytes() == vb.tobytes(), f"st[{k}] differs"
    assert sa["start_base"] == sb["start_base"]
    assert sa["e_real"] == sb["e_real"]
    ref_w, ref_b = packing.pack(d.ref, d.a._arena_spec)
    assert d.a._arena_w.tobytes() == np.asarray(ref_w).tobytes()
    assert d.a._arena_b.tobytes() == np.asarray(ref_b).tobytes()


_DICT_PATH_PODS = {
    # what the benchmark's score-fidelity probes carry
    "preferred_node_affinity": lambda name: (
        MakePod(name).req({"cpu": "100m"})
        .node_affinity_preferred(10, "zone", ["zone-0"])
    ),
    "required_node_affinity": lambda name: (
        MakePod(name).req({"cpu": "100m"})
        .node_affinity_in("zone", ["zone-0", "zone-1"])
    ),
    "volume": lambda name: (
        MakePod(name).req({"cpu": "100m"}).volume("claim-0")
    ),
}


def _fold_fixture(kind, n_dict=3):
    """A warmed Driver whose pending set holds `n_dict` pods the native
    row writer refuses (they were pending, so their interning is done:
    what the served path sees when such a pod binds)."""
    from k8s_scheduler_tpu import native
    from k8s_scheduler_tpu.models.api import (
        PersistentVolume,
        PersistentVolumeClaim,
    )

    if native.pod_rows_into is None:
        pytest.skip("native extension not built")
    nodes = make_cluster(8)
    d = Driver()
    pods = make_pods(30, seed=41, affinity_fraction=0.2, num_apps=5)
    odd = [
        _DICT_PATH_PODS[kind](f"odd-{i}").labels({"app": f"app-{i}"})
        .created(50.0 + i).obj()
        for i in range(n_dict)
    ]
    assert all(
        r is None for r in (
            native.pod_row(p, SnapshotEncoder()._native_ctx()) for p in odd
        )
    ), "fixture pods must be ones the native parser does not cover"
    pods[10:10 + n_dict] = odd
    existing = [
        (p, f"node-{i % 8}")
        for i, p in enumerate(
            make_pods(20, seed=42, name_prefix="run", num_apps=5)
        )
    ]
    kw = dict(
        pvcs=[PersistentVolumeClaim("claim-0", volume_name="pv-0")],
        pvs=[PersistentVolume("pv-0", claim_ref="default/claim-0")],
    )
    d.step(nodes, pods, existing, **kw)
    d.step(nodes, pods, existing, **kw)  # warm the delta path
    return d, nodes, pods, odd, existing, kw


@pytest.mark.parametrize("kind", sorted(_DICT_PATH_PODS))
def test_fold_falls_back_per_pod_for_rows_the_native_writer_refuses(kind):
    """One newly bound pod with a volume or node affinity used to fail
    the fold for the whole cycle (a full encode of the resident set).
    The append now builds those rows in Python, beside the native ones,
    and their tail removal un-folds them: both byte-identical to a
    from-scratch assembly, with no full encode."""
    d, nodes, pods, odd, existing, kw = _fold_fixture(kind)
    folds0, fulls0 = d.a.fold_hits, d.a.full_encodes
    fb0 = d.a.fold_fallback_pods

    # native and dict-path pods bind in one cycle, interleaved
    bound_idx = [0, 10, 1, 11, 2, 12, 3]
    bound = [(pods[i], f"node-{i % 8}") for i in bound_idx]
    existing2 = existing + bound
    pending2 = [p for i, p in enumerate(pods) if i not in bound_idx]
    d.step(nodes, pending2, existing2, **kw)
    assert d.a.full_encodes == fulls0
    assert d.a.fold_hits == folds0 + 1
    assert d.a.fold_fallback_pods == fb0 + len(odd)
    assert_fold_exact(d)

    # the completion batch: the appended tail leaves again
    d.step(nodes, pending2, existing2[: len(existing)], **kw)
    assert d.a.full_encodes == fulls0
    assert d.a.fold_hits == folds0 + 2
    assert d.a.fold_fallback_pods == fb0 + len(odd)  # parses no pod
    assert_fold_exact(d)


@pytest.mark.parametrize("guard", ["labels_outgrow_MPL", "interning_grows"])
def test_fold_fallback_rows_keep_the_folds_guards(guard):
    """The per-pod fallback is safe because its rows pass the guards the
    native rows pass: a dict-path pod wider than the sticky dims, or one
    whose parse grows an interning table, still takes the full path."""
    d, nodes, pods, _odd, existing, kw = _fold_fixture(
        "preferred_node_affinity"
    )
    if guard == "labels_outgrow_MPL":
        # every string is interned already (two pending pods carry the
        # labels between them, the affinity term is the fixture's), so
        # the ONLY thing wrong with this pod is its label row's width
        keys = [f"k{i}" for i in range(12)]
        halves = [
            MakePod(f"half-{h}").req({"cpu": "100m"})
            .labels({k: "v" for k in keys[h * 6:(h + 1) * 6]}).obj()
            for h in range(2)
        ]
        pods = pods[:-2] + halves
        d.step(nodes, pods, existing, **kw)
        d.step(nodes, pods, existing, **kw)
        newcomer = (
            MakePod("wide").req({"cpu": "100m"})
            .labels({k: "v" for k in keys})
            .node_affinity_preferred(10, "zone", ["zone-0"]).obj()
        )
        assert len(keys) + 1 > d.a._delta_state["dims"]["MPL"]
    else:
        # never pending: its affinity expression is new to the tables
        newcomer = (
            MakePod("stranger").req({"cpu": "100m"})
            .node_affinity_preferred(7, "rack", ["rack-9"]).obj()
        )
    folds0, fulls0 = d.a.fold_hits, d.a.full_encodes
    fb0 = d.a.fold_fallback_pods
    lens0 = d.a._table_lens()
    d.step(nodes, pods, existing + [(newcomer, "node-2")], **kw)
    assert d.a.full_encodes == fulls0 + 1
    assert d.a.fold_hits == folds0
    assert d.a.fold_fallback_pods == fb0
    assert (d.a._table_lens() == lens0) == (guard == "labels_outgrow_MPL")
    assert_fold_exact(d)
    # and the grown arena folds again afterwards
    d.step(
        nodes, pods[1:],
        existing + [(newcomer, "node-2"), (pods[0], "node-1")], **kw
    )
    assert d.a.fold_hits == folds0 + 1
    assert_fold_exact(d)


def _scattered(existing, **_):
    return [e for i, e in enumerate(existing) if i % 3 != 1]


def _scattered_plus_appends(existing, pods, **_):
    return _scattered(existing) + [
        (pods[i], f"node-{i % 8}") for i in (0, 10, 1, 11, 2)
    ]


def _oldest_leaves(existing, **_):
    # the fixture's resident pods are created at 0.0, 1.0, ...: the
    # base of exist_start moves when slot 0 goes
    assert existing[0][0].metadata.creation_timestamp < min(
        p.metadata.creation_timestamp for p, _ in existing[1:]
    )
    return existing[1:]


def _port_pod_leaves(existing, **_):
    assert existing[5][0].host_ports() and existing[13][0].host_ports()
    return existing[:5] + existing[6:]  # node-5 keeps the other's port


def _fallback_row_leaves(existing, odd, **_):
    assert existing[-2][0] is odd[0]
    return existing[:-2] + existing[-1:]


def _a_node_empties(existing, **_):
    return [e for e in existing if e[1] != "node-3"]


def _reordered(existing, **_):
    return existing[:4] + existing[9:12] + existing[4:9] + existing[12:]


def _append_only(existing, pods, **_):
    return existing + [(pods[i], f"node-{i % 8}") for i in (0, 10, 1)]


def _tail_only(existing, **_):
    return existing[:-3]


def _mostly_new(existing, pods, **_):
    # a hole, and more rows changed (6 left, 12 arrived) than stayed
    return existing[::2][:6] + [
        (p, f"node-{i % 8}") for i, p in enumerate(pods[:12])
    ]


_FOLD_SHAPES = {
    "scattered_removals": (_scattered, True),
    "scattered_removals_plus_appends": (_scattered_plus_appends, True),
    "oldest_pod_leaves_rebase": (_oldest_leaves, True),
    "port_bearing_pod_leaves": (_port_pod_leaves, True),
    "python_fallback_row_leaves": (_fallback_row_leaves, True),
    "every_pod_of_a_node_leaves": (_a_node_empties, True),
    # the two ends of the same code: no hole, no tail
    "no_hole_pure_append": (_append_only, True),
    "no_tail_pure_tail_removal": (_tail_only, True),
    # read as removals plus appends of the same pods: exact either way
    "reordered": (_reordered, None),
    # the full path is cheaper, and is still there
    "more_changed_than_stayed": (_mostly_new, False),
}


@pytest.mark.parametrize("shape", sorted(_FOLD_SHAPES))
def test_fold_takes_removals_anywhere_in_the_list(shape):
    """The new list is the old one less pods from anywhere in it, plus
    an appended tail: the fold compacts the surviving rows over the
    holes in place. Byte-identical to a from-scratch assembly, with no
    full encode (a reorder may take either path, and is as exact)."""
    d, nodes, pods, odd, existing, kw = _fold_fixture(
        "preferred_node_affinity"
    )
    # two port-bearing resident pods on one node, and two rows from the
    # Python row builder at the tail (bound the cycle before)
    for i, port in ((5, 8080), (13, 9090)):
        existing[i] = (
            MakePod(f"port-{i}").req({"cpu": "100m"}).host_port(port)
            .created(float(i)).obj(),
            "node-5",
        )
    existing = existing + [(odd[0], "node-2"), (pods[3], "node-3")]
    pods = [p for p in pods if p is not odd[0] and p is not pods[3]]
    d.step(nodes, pods, existing, **kw)
    d.step(nodes, pods, existing, **kw)
    make_list, folds = _FOLD_SHAPES[shape]
    folds0, fulls0 = d.a.fold_hits, d.a.full_encodes
    removed0, declined0 = d.a.fold_removed_pods, d.a.fold_declined
    existing2 = make_list(existing=existing, pods=pods, odd=odd)
    bound = {id(p) for p, _ in existing2}
    pending2 = [p for p in pods if id(p) not in bound]
    d.step(nodes, pending2, existing2, **kw)
    assert_fold_exact(d)
    if folds:
        assert d.a.full_encodes == fulls0
        assert d.a.fold_hits == folds0 + 1
        kept = {id(p) for p, _ in existing} & bound
        assert d.a.fold_removed_pods == removed0 + len(existing) - len(kept)
    elif folds is False:
        assert d.a.full_encodes == fulls0 + 1
        assert d.a.fold_removed_pods == removed0
    # the rule that more changed than stayed, and nothing else, counts
    # as the fold declining (a reorder may read as either)
    if folds is not None:
        assert d.a.fold_declined == declined0 + (not folds)
    if shape == "every_pod_of_a_node_leaves":
        assert (d.a._stable["node_pods"][3] == -1).all()
    # and the folded state folds again: what left comes back at the tail
    back = [e for e in existing if id(e[0]) not in bound]
    d.step(nodes, pending2, existing2 + back, **kw)
    assert_fold_exact(d)
    if folds:
        assert d.a.full_encodes == fulls0


def test_fold_churn_with_inexact_requests_stays_exact():
    """Twelve seeded cycles in which a tenth of the resident set leaves
    from anywhere in the list while as many pods bind at its tail, with
    requests that are inexact in float32 (100m): node_requested is
    re-summed in slot order, never subtracted, so every cycle stays
    byte-identical to a from-scratch assembly and none encodes in full."""
    from k8s_scheduler_tpu import native

    if native.pod_rows_into is None:
        pytest.skip("native extension not built")
    rng = np.random.default_rng(37)
    nodes = make_cluster(8)
    d = Driver(pad_pods=64)
    serial = iter(range(10**6))

    def fresh(n):
        return [
            MakePod(f"c-{next(serial)}")
            .req({"cpu": "100m", "memory": "100Mi"})
            .labels({"app": "ab"[int(rng.integers(0, 2))]})
            .priority(int(rng.integers(0, 3)))
            .created(float(rng.integers(0, 500))).obj()
            for _ in range(n)
        ]

    existing = [
        (p, f"node-{int(rng.integers(0, 8))}") for p in fresh(120)
    ]
    pending = fresh(24)
    # a budget over half the pods: exist_pdb's rows move with the rest
    kw = dict(pdbs=[PodDisruptionBudget(
        "a-pdb", selector=LabelSelector(match_labels={"app": "a"}),
        disruptions_allowed=1,
    )])
    d.step(nodes, pending, existing, **kw)
    d.step(nodes, pending, existing, **kw)
    folds0, fulls0 = d.a.fold_hits, d.a.full_encodes
    removed0 = d.a.fold_removed_pods
    for _cycle in range(12):
        leave = rng.choice(
            len(existing), len(existing) // 10, replace=False
        ).tolist()
        # a bound pod takes the node of one that left: no node outgrows
        # the victim table's width, which would be the full path's case
        existing = [
            e for i, e in enumerate(existing) if i not in set(leave)
        ] + [(p, existing[i][1]) for p, i in zip(pending[:12], leave)]
        pending = pending[12:] + fresh(12)
        d.step(nodes, pending, existing, **kw)
        assert_fold_exact(d)
    assert d.a.full_encodes == fulls0
    assert d.a.fold_hits == folds0 + 12
    assert d.a.fold_removed_pods == removed0 + 12 * 12


@pytest.mark.parametrize("leave, declines", [
    (6, True),   # 6 of the 15 the encoder saw leave, 6 arrive: 12 > 9
    (5, False),  # 10 changed, 10 stayed: not MORE changed than stayed
    (2, False),
], ids=["over_half", "exactly_half", "under_half"])
def test_a_resident_set_that_turns_over_every_cycle(leave, declines):
    """scheduler_perf's SchedulingBasic 500Nodes in small: of the pods
    the encoder saw, `leave` finish from anywhere in the list before
    every cycle while as many bind at its tail. Past half, the fold
    stands aside by its own rule: `fold_declined` rises by one a cycle
    and `full_encodes` with it; under it the fold compacts and appends
    and both stay flat. Either way the arena is a from-scratch encode
    byte for byte."""
    from k8s_scheduler_tpu import native

    if native.pod_rows_into is None:
        pytest.skip("native extension not built")
    nodes = make_cluster(8)
    d = Driver(pad_pods=64)
    serial = iter(range(10**6))

    def fresh(n):
        return [
            MakePod(f"t-{next(serial)}")
            .req({"cpu": "100m", "memory": "500Mi"})
            .labels({"app": "a"}).created(float(next(serial))).obj()
            for _ in range(n)
        ]

    existing = [(p, f"node-{i % 8}") for i, p in enumerate(fresh(15))]
    pending = fresh(8)
    d.step(nodes, pending, existing)
    d.step(nodes, pending, existing)
    fulls0, folds0 = d.a.full_encodes, d.a.fold_hits
    declined0 = d.a.fold_declined
    cycles = 5
    for c in range(1, cycles + 1):
        gone = set(range(1, 2 * leave, 2))  # scattered: never a pure tail
        existing = [e for i, e in enumerate(existing) if i not in gone] + [
            (p, f"node-{(c + j) % 8}") for j, p in enumerate(fresh(leave))
        ]
        d.step(nodes, pending, existing)
        assert_fold_exact(d)
        assert d.a.fold_declined - declined0 == (c if declines else 0)
        assert d.a.full_encodes - fulls0 == (c if declines else 0)
        assert d.a.fold_hits - folds0 == (0 if declines else c)


def test_the_lists_whose_identity_is_remembered_are_kept_alive():
    """The delta path's precheck first compares `id()` and length of each
    stable-side list with the last encode's. A caller that builds a
    fresh list every cycle and drops it (`cache.existing_pods()`) can be
    handed the freed list's address again, and where the resident set is
    held at a target the length is equal too: the encoder therefore
    keeps the lists it remembers alive, through the full path and
    through a fold."""
    import weakref

    class Kept(list):  # a plain list takes no weak reference
        pass

    nodes = make_cluster(4)
    enc = SnapshotEncoder(pad_pods=32, pad_nodes=16)
    pods = [
        MakePod(f"k-{i}").req({"cpu": "100m"}).created(float(i)).obj()
        for i in range(9)
    ]
    pending = pods[6:]
    first = Kept((p, "node-0") for p in pods[:4])
    node_list = Kept(nodes)
    alive = [weakref.ref(first), weakref.ref(node_list)]
    enc.encode_packed(node_list, pending, first)
    del first, node_list
    assert all(r() is not None for r in alive)
    # ... and after a fold the list it folded in, not the one before
    second = Kept((p, "node-0") for p in pods[:5])
    alive.append(weakref.ref(second))
    folds0 = enc.fold_hits
    enc.encode_packed(alive[1](), pending, second)
    del second
    from k8s_scheduler_tpu import native

    if native.pod_rows_into is not None:
        assert enc.fold_hits == folds0 + 1
        assert alive[0]() is None
    assert alive[2]() is not None and alive[1]() is not None


def test_pad_ma_mc_presize_keeps_regime_stable():
    """ADVICE r5: MA/MC bucket by 2, so a mid-serving arrival of a
    3-4-term affinity/spread pod flips the sticky regime (full recompile)
    unless pad_ma/pad_mc pre-size it — mirroring pad_existing/MPN."""
    nodes = [
        MakeNode("n0").capacity({"cpu": "8"}).labels({"app": "x"}).obj()
    ]

    def aff_pod(name, terms):
        p = MakePod(name).req({"cpu": "1"})
        for _ in range(terms):
            p = p.pod_affinity("kubernetes.io/hostname", {"app": "x"})
        return p.spread(1, "kubernetes.io/hostname", {"app": "x"}).obj()

    base = [aff_pod("p0", 1)]  # affinity/spread capability already on
    rich = aff_pod("p1", 4)
    unsized = SnapshotEncoder(pad_pods=8, pad_nodes=4)
    _, _, s1, _, _ = unsized.encode_packed(nodes, base)
    _, _, s1b, _, _ = unsized.encode_packed(nodes, base + [rich])
    assert s1b.key() != s1.key()  # the flip the knob exists to prevent

    sized = SnapshotEncoder(pad_pods=8, pad_nodes=4, pad_ma=4, pad_mc=4)
    assert sized._sticky_dims == {}
    _, _, s2, _, _ = sized.encode_packed(nodes, base)
    assert sized._sticky_dims["MA"] == 4
    assert sized._sticky_dims["MC"] == 4
    _, _, s2b, _, _ = sized.encode_packed(nodes, base + [rich])
    assert s2b.key() == s2.key()
