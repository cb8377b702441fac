"""The default plugin set, mirroring the reference's plugin names
(SURVEY.md §2 C7/C8: NodeUnschedulable, NodeName, NodePorts,
NodeResourcesFit, NodeAffinity, TaintToleration, ImageLocality,
NodeResourcesBalancedAllocation, InterPodAffinity, PodTopologySpread,
DefaultPreemption; expected upstream `framework/plugins/<name>/` —
[UNVERIFIED], mount empty).

Each plugin contributes mask/score fragments to the single fused cycle
program (see interfaces.py for the extension-point mapping)."""

from __future__ import annotations

import jax.numpy as jnp
import numpy as np

from ..models import encoding as enc
from ..ops import images as images_ops
from ..ops import interpod as interpod_ops
from ..ops import labels as labels_ops
from ..ops import ports as ports_ops
from ..ops import preemption as preemption_ops
from ..ops import resources as res_ops
from ..ops import taints as taints_ops
from ..ops import volumes as volumes_ops
from .interfaces import CycleContext, PluginBase


def _score_resource_weights(snap, args: dict) -> jnp.ndarray:
    """score_resources arg -> one-hot f32 [R] weight vector (cpu+memory by
    default, matching upstream defaultRequestedRatioResources). Shared by
    every resource-scoring plugin so the semantics can't drift."""
    score_resources = args.get("score_resources", ("cpu", "memory"))
    w = np.zeros(len(snap.resource_names), np.float32)
    for r in score_resources:
        if r in snap.resource_names:
            w[snap.resource_names.index(r)] = 1.0
    return jnp.asarray(w)


class NodeUnschedulable(PluginBase):
    """Excludes cordoned nodes (`spec.unschedulable`). Upstream admits pods
    tolerating the node.kubernetes.io/unschedulable taint; that refinement
    rides on the toleration tables once the taint is synthesized — for now
    cordoned nodes are excluded unconditionally (oracle matches)."""

    name = "NodeUnschedulable"

    def static_mask(self, ctx: CycleContext):
        snap = ctx.snap
        P = snap.P
        return jnp.broadcast_to(~snap.node_unschedulable[None, :], (P, snap.N))


class NodeName(PluginBase):
    name = "NodeName"

    def static_mask(self, ctx: CycleContext):
        snap = ctx.snap
        pinned = snap.pod_node_name[:, None]  # [P, 1]
        node_ids = jnp.arange(snap.N, dtype=jnp.int32)[None, :]
        mask = jnp.ones((snap.P, snap.N), bool)
        mask = jnp.where(pinned >= 0, node_ids == pinned, mask)
        return jnp.where(pinned == -2, False, mask)  # named node unknown


class NodePorts(PluginBase):
    """hostPort conflicts: against EXISTING pods via the static mask,
    against pods committed earlier in this cycle via a [N, Q] port-claim
    bitmap carried through the commit scan (Q = distinct pending ports) —
    so intra-batch conflicts resolve exactly like the reference's
    sequential NodeInfo updates."""

    name = "NodePorts"

    def static_mask(self, ctx: CycleContext):
        snap = ctx.snap
        return ~ports_ops.ports_conflict_mask(snap.pod_ports, snap.node_used_ports)

    def extra_init(self, ctx: CycleContext):
        snap = ctx.snap
        return jnp.zeros((snap.N, snap.num_distinct_ports), bool)

    def dyn_mask(self, ctx: CycleContext, p, node_requested, extra):
        snap = ctx.snap
        claimed = extra[self.name]  # [N, Q]
        ids = snap.pod_port_ids[p]  # [MPorts]
        want = claimed[:, jnp.clip(ids, 0, claimed.shape[1] - 1)]  # [N, MPorts]
        return ~jnp.any(want & (ids >= 0)[None, :], axis=1)

    def extra_update(self, ctx: CycleContext, extra, p, node, committed):
        snap = ctx.snap
        ids = snap.pod_port_ids[p]
        safe = jnp.clip(ids, 0, extra.shape[1] - 1)
        add = committed & (ids >= 0)
        return extra.at[node, safe].max(add)

    # --- batched (rounds) path ---

    @staticmethod
    def _port_onehot(snap):  # bool [P, Q]
        Q = snap.num_distinct_ports
        P = snap.P
        ids = snap.pod_port_ids  # [P, MPorts]
        oh = jnp.zeros((P, Q), bool)
        pid = jnp.broadcast_to(
            jnp.arange(P, dtype=jnp.int32)[:, None], ids.shape
        )
        return oh.at[pid, jnp.clip(ids, 0, Q - 1)].max(ids >= 0)

    def dyn_mask_batched(self, ctx: CycleContext, node_requested, extra,
                         shared):
        snap = ctx.snap
        claimed = extra[self.name]  # [N, Q]
        oh = shared.setdefault("port_onehot", self._port_onehot(snap))
        conflict = (
            oh.astype(jnp.float32) @ claimed.T.astype(jnp.float32)
        ) > 0.0  # [P, N]
        return ~conflict

    def extra_update_batched(self, ctx: CycleContext, extra, accepted,
                             node_of):
        snap = ctx.snap
        ids = snap.pod_port_ids  # [P, MPorts]
        Q = extra.shape[1]
        nsafe = jnp.clip(node_of, 0, extra.shape[0] - 1)
        nidx = jnp.broadcast_to(nsafe[:, None], ids.shape)
        add = accepted[:, None] & (ids >= 0)
        return extra.at[nidx, jnp.clip(ids, 0, Q - 1)].max(add)


class NodeResourcesFit(PluginBase):
    """Filter: resource fit against the RUNNING allocatable (in-scan).
    Score: the configured scoring strategy (LeastAllocated default,
    MostAllocated for bin-packing), also in-scan."""

    name = "NodeResourcesFit"

    def dyn_mask(self, ctx: CycleContext, p, node_requested, extra):
        snap = ctx.snap
        return res_ops.fit_mask_single(
            snap.pod_requested[p], snap.node_allocatable, node_requested
        )

    def _strategy_fn(self):
        strategy = self.args.get("scoring_strategy", "LeastAllocated")
        return (
            res_ops.most_requested_score
            if strategy == "MostAllocated"
            else res_ops.least_requested_score
        )

    def dyn_score(self, ctx: CycleContext, p, node_requested, extra, feasible):
        snap = ctx.snap
        return self._strategy_fn()(
            snap.pod_requested[p],
            snap.node_allocatable,
            node_requested,
            _score_resource_weights(snap, self.args),
        )

    def dyn_mask_batched(self, ctx: CycleContext, node_requested, extra,
                         shared):
        snap = ctx.snap
        return res_ops.fit_mask(
            snap.pod_requested, snap.node_allocatable, node_requested
        )

    def dyn_score_batched(self, ctx: CycleContext, node_requested, extra,
                          feasible, shared):
        snap = ctx.snap
        return self._strategy_fn()(
            snap.pod_requested[:, None, :],
            snap.node_allocatable,
            node_requested,
            _score_resource_weights(snap, self.args),
        )

    def score_node_anchor(self, ctx: CycleContext, node_requested):
        snap = ctx.snap
        return self._strategy_fn()(
            jnp.zeros_like(snap.node_allocatable[:1, :1]),  # zero pod
            snap.node_allocatable,
            node_requested,
            _score_resource_weights(snap, self.args),
        )


class NodeResourcesBalancedAllocation(PluginBase):
    name = "NodeResourcesBalancedAllocation"

    def dyn_score(self, ctx: CycleContext, p, node_requested, extra, feasible):
        snap = ctx.snap
        return res_ops.balanced_allocation_score(
            snap.pod_requested[p], snap.node_allocatable, node_requested,
            _score_resource_weights(snap, self.args),
        )

    def dyn_score_batched(self, ctx: CycleContext, node_requested, extra,
                          feasible, shared):
        snap = ctx.snap
        return res_ops.balanced_allocation_score(
            snap.pod_requested[:, None, :], snap.node_allocatable,
            node_requested, _score_resource_weights(snap, self.args),
        )

    def score_node_anchor(self, ctx: CycleContext, node_requested):
        snap = ctx.snap
        return res_ops.balanced_allocation_score(
            jnp.zeros_like(snap.node_allocatable[:1, :1]),
            snap.node_allocatable, node_requested,
            _score_resource_weights(snap, self.args),
        )


class NodeAffinity(PluginBase):
    name = "NodeAffinity"

    def static_mask(self, ctx: CycleContext):
        return labels_ops.pod_requirement_mask(ctx.snap, ctx.expr_node_mask)

    def static_score(self, ctx: CycleContext):
        return labels_ops.preferred_score(ctx.snap, ctx.expr_node_mask)


class VolumeBinding(PluginBase):
    """PVC/PV feasibility (ops/volumes.py): bound-PV node affinity,
    static-PV candidacy, and dynamic-provisioning topology for
    WaitForFirstConsumer claims. The static mask covers pre-cycle
    availability; a `pv_claimed` bitmap in the commit engines' extra
    state arbitrates SAME-CYCLE claimants of one static PV (a placed pod
    claims its lowest-index compatible PV; later pods see it taken —
    upstream resolves this one pod later at PreBind via bind failure)."""

    name = "VolumeBinding"

    def static_mask(self, ctx: CycleContext):
        if not ctx.snap.has_volumes:
            return None
        return volumes_ops.volume_mask(ctx.snap, ctx.expr_node_mask)

    def _has_static_claims(self, snap) -> bool:
        # claim tracking only matters when unbound WFC slots AND static
        # PVs exist at all; otherwise the state is dead weight
        return bool(snap.has_volumes and snap.pv_avail.shape[0] > 0)

    def extra_init(self, ctx: CycleContext):
        if not self._has_static_claims(ctx.snap):
            return None
        return jnp.zeros((ctx.snap.pv_avail.shape[0],), bool)

    def dyn_mask(self, ctx: CycleContext, p, node_requested, extra):
        if not self._has_static_claims(ctx.snap):
            return None
        # per-pod ROW form: the scan calls this once per step, and the
        # batched [P, N] form would redo full-set work P times
        return volumes_ops.volume_mask_unbound_row(
            ctx.snap, ctx.expr_node_mask, extra[self.name], p
        )

    def dyn_mask_batched(self, ctx: CycleContext, node_requested, extra,
                         shared):
        if not self._has_static_claims(ctx.snap):
            return None
        return volumes_ops.volume_mask_unbound(
            ctx.snap, ctx.expr_node_mask, extra[self.name]
        )

    def extra_update(self, ctx: CycleContext, extra, p, node, committed):
        if extra is None:
            return extra
        snap = ctx.snap
        claimed = extra
        MVol = snap.pod_vol_mode.shape[1]
        multi = MVol >= 2 and snap.has_multi_volume
        # slots claim in index order; multi-volume pods use the SDR-safe
        # choice (greedy lowest-index claiming can dead-end even when the
        # Hall mask admitted the pod — see ops/volumes.chosen_pv_sdr)
        pending = snap.pod_vol_mode[p] == 1  # [MVol]
        for t in range(MVol):
            if multi:
                ch = volumes_ops.chosen_pv_sdr_row(
                    snap, ctx.expr_node_mask, claimed, node, p, pending, t
                )
            else:
                ch = volumes_ops.chosen_pv_row(
                    snap, ctx.expr_node_mask, claimed, node, p, t
                )
            ch = jnp.where(committed, ch, -1)
            claimed = claimed.at[jnp.clip(ch, 0, claimed.shape[0] - 1)].max(
                ch >= 0
            )
            pending = pending.at[t].set(False)
        return claimed

    def extra_update_batched(self, ctx: CycleContext, extra, accepted,
                             node_of):
        if extra is None:
            return extra
        snap = ctx.snap
        # fixed-point fold: exact for ANY batch (diagnosis replays a
        # whole cycle's placements at once, where same-class claimants
        # contend); under the rounds engine's _RB_PV guard the batch is
        # claim-disjoint and the loop exits after one pass
        return volumes_ops.fold_pv_claims(
            snap, ctx.expr_node_mask, extra, accepted, node_of,
            snap.pod_order.astype("int32"),
        )


class TaintToleration(PluginBase):
    name = "TaintToleration"

    def static_mask(self, ctx: CycleContext):
        return taints_ops.taint_filter_mask(ctx.snap)

    def static_score(self, ctx: CycleContext):
        return taints_ops.taint_score(ctx.snap)


class ImageLocality(PluginBase):
    name = "ImageLocality"

    def static_score(self, ctx: CycleContext):
        return images_ops.image_locality_score(ctx.snap)


# --- shared affinity-state plumbing -----------------------------------------
# InterPodAffinity and PodTopologySpread both consume the per-(selector,
# domain) count state; whichever is initialized FIRST (filter order) owns
# the scan-carried slot and maintains it, the other reads it.

_AFFINITY_OWNER_KEY = "__affinity_state_owner__"


def _claim_affinity_state(ctx: CycleContext, name: str):
    snap = ctx.snap
    if not (snap.has_inter_pod_affinity or snap.has_topology_spread):
        return None
    owner = ctx._cache.get(_AFFINITY_OWNER_KEY)
    if owner is not None and owner != name:
        return None  # someone else owns the slot
    ctx._cache[_AFFINITY_OWNER_KEY] = name
    return ctx.initial_affinity_state()


def _affinity_state(ctx: CycleContext, extra):
    return extra[ctx._cache[_AFFINITY_OWNER_KEY]]


def _update_affinity_state(ctx: CycleContext, name, state, p, node, committed):
    if ctx._cache.get(_AFFINITY_OWNER_KEY) != name:
        return state
    return interpod_ops.affinity_update(
        ctx.snap, state, ctx.matched_pending, p, node, committed
    )


def _update_affinity_state_batched(ctx: CycleContext, name, state, accepted,
                                   node_of):
    if ctx._cache.get(_AFFINITY_OWNER_KEY) != name:
        return state
    return interpod_ops.affinity_update_batched(
        ctx.snap, state, ctx.matched_pending, accepted, node_of
    )


def _shared_cbn(ctx: CycleContext, state, shared):
    """counts-by-node [K*S, N] for the current round, computed once and
    shared between InterPodAffinity and PodTopologySpread."""
    if "cbn" not in shared:
        shared["cbn"] = interpod_ops.counts_by_node(ctx.snap, state)
    return shared["cbn"]


class InterPodAffinity(PluginBase):
    """The quadratic hot path, as counts over (selector, topology-domain)
    instead of pairwise pod comparisons — see ops/interpod.py."""

    name = "InterPodAffinity"

    def extra_init(self, ctx: CycleContext):
        return _claim_affinity_state(ctx, self.name)

    def dyn_mask(self, ctx: CycleContext, p, node_requested, extra):
        if not ctx.snap.has_inter_pod_affinity:
            return None
        return interpod_ops.affinity_dyn_mask(
            ctx.snap, _affinity_state(ctx, extra), ctx.matched_pending, p
        )

    def dyn_score(self, ctx: CycleContext, p, node_requested, extra, feasible):
        if not ctx.snap.has_inter_pod_affinity:
            return None
        return interpod_ops.affinity_dyn_score(
            ctx.snap, _affinity_state(ctx, extra), ctx.matched_pending, p, feasible
        )

    def extra_update(self, ctx: CycleContext, extra, p, node, committed):
        return _update_affinity_state(ctx, self.name, extra, p, node, committed)

    def dyn_mask_batched(self, ctx: CycleContext, node_requested, extra,
                         shared):
        if not ctx.snap.has_inter_pod_affinity:
            return None
        state = _affinity_state(ctx, extra)
        cbn = _shared_cbn(ctx, state, shared)
        return interpod_ops.affinity_mask_batched(
            ctx.snap, state, ctx.matched_pending, cbn
        )

    def dyn_mask_reopens(self, ctx: CycleContext):
        # a required affinity term is met once a matching peer is placed
        # in the domain; anti-affinity, both ways, only ever closes
        if not ctx.snap.has_inter_pod_affinity:
            return None
        return jnp.any(ctx.snap.pod_aff_terms[..., 0] >= 0, axis=1)

    def dyn_score_batched(self, ctx: CycleContext, node_requested, extra,
                          feasible, shared):
        if not ctx.snap.has_inter_pod_affinity:
            return None
        state = _affinity_state(ctx, extra)
        cbn = _shared_cbn(ctx, state, shared)
        return interpod_ops.affinity_score_batched(
            ctx.snap, state, ctx.matched_pending, cbn, feasible
        )

    def extra_update_batched(self, ctx: CycleContext, extra, accepted,
                             node_of):
        return _update_affinity_state_batched(
            ctx, self.name, extra, accepted, node_of
        )


class DefaultPreemption(PluginBase):
    """PostFilter: batched what-if preemption (ops/preemption.py).

    Config args: `budget` (candidates prefiltered per cycle, default
    256) and `scan_budget` (nominations per cycle, default 64) — the
    per-cycle latency budgets; pods beyond them retry next cycle."""

    name = "DefaultPreemption"

    def post_filter(self, ctx: CycleContext, assignment, node_requested,
                    gate_rows, excluded=None):
        # preemption_ops is imported at MODULE scope, never from inside
        # this (traced) body: its module-level jnp constants (_BIG_I32)
        # would otherwise be created under the first trace's context,
        # and a later retrace of the same jitted post_filter (e.g. with
        # a CycleDecision instead of a CycleResult) would read them as
        # escaped tracers of a dead trace (UnexpectedTracerError)
        kw = {}
        if "budget" in self.args:
            kw["budget"] = int(self.args["budget"])
        if "scan_budget" in self.args:
            kw["scan_budget"] = int(self.args["scan_budget"])
        return preemption_ops.run_preemption(
            ctx,
            assignment=assignment,
            node_requested=node_requested,
            gate_rows=gate_rows,
            excluded=excluded,
            **kw,
        )


class PodTopologySpread(PluginBase):
    name = "PodTopologySpread"

    def extra_init(self, ctx: CycleContext):
        return _claim_affinity_state(ctx, self.name)

    def dyn_mask(self, ctx: CycleContext, p, node_requested, extra):
        if not ctx.snap.has_topology_spread:
            return None
        return interpod_ops.spread_dyn_mask(
            ctx.snap, _affinity_state(ctx, extra), p
        )

    def dyn_score(self, ctx: CycleContext, p, node_requested, extra, feasible):
        if not ctx.snap.has_topology_spread:
            return None
        return interpod_ops.spread_dyn_score(
            ctx.snap, _affinity_state(ctx, extra), p, feasible
        )

    def extra_update(self, ctx: CycleContext, extra, p, node, committed):
        return _update_affinity_state(ctx, self.name, extra, p, node, committed)

    def dyn_mask_batched(self, ctx: CycleContext, node_requested, extra,
                         shared):
        if not ctx.snap.has_topology_spread:
            return None
        state = _affinity_state(ctx, extra)
        cbn = _shared_cbn(ctx, state, shared)
        if "spread_minc" not in shared:
            shared["spread_minc"] = interpod_ops.spread_minc(ctx.snap, state)
        return interpod_ops.spread_mask_batched(
            ctx.snap, state, cbn, shared["spread_minc"]
        )

    def dyn_mask_reach_batched(self, ctx: CycleContext, node_requested,
                               extra, shared, active):
        # a round's own acceptances raise the minimum: claims may go to
        # every domain the group's claimants can lift it to
        if not ctx.snap.has_topology_spread:
            return None
        state = _affinity_state(ctx, extra)
        cbn = _shared_cbn(ctx, state, shared)
        reach = interpod_ops.spread_reach(
            ctx.snap, state, interpod_ops.spread_minc(ctx.snap, state),
            active,
        )
        return (
            interpod_ops.spread_mask_batched(ctx.snap, state, cbn, reach),
            *interpod_ops.spread_claim_share(ctx.snap, state, cbn, reach),
        )

    def dyn_mask_reopens(self, ctx: CycleContext):
        # a DoNotSchedule constraint lets a domain in again once the
        # minimum over the domains has risen
        if not ctx.snap.has_topology_spread:
            return None
        tsc = ctx.snap.pod_tsc
        return jnp.any(
            (tsc[..., 0] >= 0) & (tsc[..., 2] == enc.WHEN_DO_NOT_SCHEDULE),
            axis=1,
        )

    def dyn_score_batched(self, ctx: CycleContext, node_requested, extra,
                          feasible, shared):
        if not ctx.snap.has_topology_spread:
            return None
        state = _affinity_state(ctx, extra)
        cbn = _shared_cbn(ctx, state, shared)
        return interpod_ops.spread_score_batched(
            ctx.snap, state, cbn, feasible
        )

    def extra_update_batched(self, ctx: CycleContext, extra, accepted,
                             node_of):
        return _update_affinity_state_batched(
            ctx, self.name, extra, accepted, node_of
        )
