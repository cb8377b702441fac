"""Snapshot encoding: typed Pod/Node objects -> structure-of-arrays tensors.

This is the TPU-native replacement for the reference's `SchedulerCache`
snapshot (`internal/cache/snapshot.go`, `framework/types.go` NodeInfo —
[UNVERIFIED] locations, mount empty; SURVEY.md §2 C4/C5): instead of a list
of per-node `NodeInfo` structs walked by goroutines, the cluster state is a
set of padded, integer-interned device arrays that one jitted program
consumes.

Encoding strategy (SURVEY.md §7 step 1 + "hard parts" (c)):

- **Interning.** Every string (label keys/values, taint keys, namespaces,
  image names, topology keys) becomes an int32 id via `StringInterner`.
- **Dedup + gather.** Pod-side structures that repeat across pods (node
  affinity requirements, toleration sets, label selectors, image sets) are
  deduplicated into small tables; each pod stores table indices. Kernels
  evaluate the small table against all nodes/pods, then a gather expands to
  the pods axis — O(distinct x N) instead of O(P x N x terms).
- **Padding.** Every ragged axis is padded to a bucketed size with -1
  sentinels so shapes are static across cycles and jit caches stay warm.
- **Label expressions** (`In/NotIn/Exists/DoesNotExist/Gt/Lt`) become rows
  of one expression table usable against node labels and pod labels alike;
  `matchFields` (metadata.name) rows resolve to node-index sets at encode
  time (FIELD_IN).

Namespace scoping of pod-affinity selectors is encoded as an extra implicit
expression on a reserved label key (`__namespace__`), which is injected into
every pod's encoded label list.
"""

from __future__ import annotations

import dataclasses
from dataclasses import dataclass
from typing import Any, NamedTuple, Sequence

import numpy as np

from . import api
from .api import (
    Affinity,
    LabelSelector,
    Node,
    NodeSelectorRequirement,
    NodeSelectorTerm,
    Pod,
    PodAffinityTerm,
)

# Operator codes for the expression table.
OP_IN = 0
OP_NOT_IN = 1
OP_EXISTS = 2
OP_DOES_NOT_EXIST = 3
OP_GT = 4
OP_LT = 5
OP_FIELD_IN = 6  # matchFields metadata.name: values are node indices
OP_IMPOSSIBLE = 7  # never matches (malformed requirement, upstream no-match)

_OP_CODE = {
    api.OP_IN: OP_IN,
    api.OP_NOT_IN: OP_NOT_IN,
    api.OP_EXISTS: OP_EXISTS,
    api.OP_DOES_NOT_EXIST: OP_DOES_NOT_EXIST,
    api.OP_GT: OP_GT,
    api.OP_LT: OP_LT,
}

# Taint effect codes.
EFFECT_NO_SCHEDULE = 0
EFFECT_PREFER_NO_SCHEDULE = 1
EFFECT_NO_EXECUTE = 2
_EFFECT_CODE = {
    api.NO_SCHEDULE: EFFECT_NO_SCHEDULE,
    api.PREFER_NO_SCHEDULE: EFFECT_PREFER_NO_SCHEDULE,
    api.NO_EXECUTE: EFFECT_NO_EXECUTE,
}

TOL_OP_EQUAL = 0
TOL_OP_EXISTS = 1

WHEN_DO_NOT_SCHEDULE = 0
WHEN_SCHEDULE_ANYWAY = 1

NAMESPACE_KEY = "__namespace__"
_EMPTY_I32 = np.empty(0, np.int32)
_EMPTY_F32 = np.empty(0, np.float32)


class EncodedFrame(NamedTuple):
    """encode_packed's result: the arena buffers + spec + a snapshot view
    whose fields alias them, plus which pod slots this encode rewrote.
    `dirty` is None after a full (re)build — every row changed — and an
    i32 slot-id array after a delta encode (consumers maintaining device-
    resident per-row state, e.g. the static carry, update those rows)."""

    wbuf: np.ndarray
    bbuf: np.ndarray
    spec: Any
    snap: "ClusterSnapshot"
    dirty: np.ndarray | None


def _i32(xs) -> np.ndarray:
    return np.array(xs, np.int32) if xs else _EMPTY_I32


def _f32(xs) -> np.ndarray:
    return np.array(xs, np.float32) if xs else _EMPTY_F32


HOSTNAME_LABEL = "kubernetes.io/hostname"


class StringInterner:
    """str -> dense int32 id. id 0 is reserved for "" (absent)."""

    def __init__(self) -> None:
        self._ids: dict[str, int] = {"": 0}
        self._strs: list[str] = [""]

    def intern(self, s: str) -> int:
        i = self._ids.get(s)
        if i is None:
            i = len(self._strs)
            self._ids[s] = i
            self._strs.append(s)
        return i

    def lookup(self, i: int) -> str:
        return self._strs[i]

    def get(self, s: str) -> int:
        """Like intern but -1 for unknown (no table growth)."""
        return self._ids.get(s, -1)

    def __len__(self) -> int:
        return len(self._strs)


class _InternTable:
    """Dedup table: hashable row -> dense index, rows in insertion order.
    Every pod-side structure that repeats across pods (requirements,
    toleration sets, selectors, image sets...) goes through one of these."""

    def __init__(self) -> None:
        self.index: dict = {}
        self.rows: list = []

    def intern(self, row) -> int:
        i = self.index.get(row)
        if i is None:
            i = len(self.rows)
            self.index[row] = i
            self.rows.append(row)
        return i

    def __len__(self) -> int:
        return len(self.rows)


def _pad_dim(n: int, bucket: int = 8, minimum: int = 1) -> int:
    """Round up to a bucket multiple so shapes are stable across cycles."""
    n = max(n, minimum)
    return ((n + bucket - 1) // bucket) * bucket


def _pow2_bucket(n: int, minimum: int = 8) -> int:
    """Round up to the next power of two (jit-cache-friendly P/N padding)."""
    n = max(n, minimum)
    return 1 << (n - 1).bit_length()


def _num_or_nan(s: str) -> float:
    try:
        return float(s)
    except ValueError:
        return float("nan")


@dataclass
class ClusterSnapshot:
    """The device-consumable cluster state. All arrays are numpy on the host;
    `jax.device_put` (or simply passing into a jitted function) moves them.

    Axis glossary: N nodes, P pending pods, E existing (assigned/assumed)
    pods, R resources, Ex label expressions, Rq node-affinity requirement
    sets, Pf preferred-node-affinity sets, Tl toleration sets, Ts taint
    sets, S pod label selectors, D flat topology domains, K topology keys,
    I distinct images, Is distinct image sets, G pod groups, MPN max pods
    per node (preemption table).
    """

    # --- names (static aux data, baked into the compiled program) ---
    resource_names: tuple[str, ...]
    topology_keys: tuple[str, ...]  # interned topology key strings, order = K axis
    # padded count of distinct pending host ports (Q axis of the scan's
    # port-claim bitmap; static because it is a shape, bucketed by 4)
    num_distinct_ports: int
    # capability flags (static): when False, the corresponding plugin
    # contributes nothing and its whole kernel is never traced — a cluster
    # without affinity pays zero for the affinity machinery
    has_inter_pod_affinity: bool
    has_topology_spread: bool
    has_volumes: bool
    # some pod really mounts >= 2 PVCs: gates the multi-volume joint-
    # admission machinery (Hall subset matmuls, claim-order permutation)
    # — MVol is a sticky PAD dim (bucket 2), so the dim alone would run
    # that machinery as guaranteed identity work on 1-PVC clusters
    has_multi_volume: bool

    # --- real (unpadded) counts: 0-d arrays, NOT static — a changed pod
    # count must not recompile the cycle (only padded shapes are static) ---
    num_nodes: np.ndarray
    num_pending: np.ndarray
    num_existing: np.ndarray
    num_domains: np.ndarray
    # monotone per-encoder cycle counter (0-d i32): turns the start of
    # each pod's walk over the nodes from cycle to cycle, so
    # percentageOfNodesToScore samples another stretch of its feasible
    # nodes each time (ops/sampling.start_offsets)
    cycle_index: np.ndarray

    # --- nodes [N...] ---
    node_allocatable: np.ndarray  # f32 [N, R]
    node_requested: np.ndarray  # f32 [N, R] aggregated from existing pods
    node_unschedulable: np.ndarray  # bool [N]
    node_taintset: np.ndarray  # i32 [N] -> Ts
    node_label_keys: np.ndarray  # i32 [N, ML]
    node_label_vals: np.ndarray  # i32 [N, ML]
    node_label_num: np.ndarray  # f32 [N, ML] numeric parse of value (nan if not)
    node_domains: np.ndarray  # i32 [N, K] flat domain id (-1 = key absent)
    node_images: np.ndarray  # bool [N, I]
    node_used_ports: np.ndarray  # i32 [N, MPorts] encoded host ports (-1 pad)
    node_valid: np.ndarray  # bool [N] (padding rows are False)

    # --- label expression table [Ex...] ---
    ex_key: np.ndarray  # i32 [Ex]
    ex_op: np.ndarray  # i32 [Ex]
    ex_vals: np.ndarray  # i32 [Ex, MV] (-1 pad); node indices for FIELD_IN
    ex_num: np.ndarray  # f32 [Ex] numeric bound for Gt/Lt

    # --- node-affinity requirement sets (OR over terms of AND over exprs) ---
    rq_exprs: np.ndarray  # i32 [Rq, MT, ME] (-1 pad)

    # --- preferred node affinity [Pf...] (flat weighted AND-terms) ---
    pf_exprs: np.ndarray  # i32 [Pf, MPT, ME]
    pf_weight: np.ndarray  # f32 [Pf, MPT] (0 pad)

    # --- toleration / taint set tables ---
    tl_key: np.ndarray  # i32 [Tl, MTl] (-1 = empty key i.e. match-any + Exists)
    tl_op: np.ndarray  # i32 [Tl, MTl]
    tl_val: np.ndarray  # i32 [Tl, MTl]
    tl_effect: np.ndarray  # i32 [Tl, MTl] (-1 = all effects)
    tl_valid: np.ndarray  # bool [Tl, MTl]
    ts_key: np.ndarray  # i32 [Ts, MTt]
    ts_val: np.ndarray  # i32 [Ts, MTt]
    ts_effect: np.ndarray  # i32 [Ts, MTt]
    ts_valid: np.ndarray  # bool [Ts, MTt]

    # --- pod label selectors [S...] (AND of exprs, incl. namespace expr) ---
    sel_exprs: np.ndarray  # i32 [S, MSE] (-1 pad)

    # --- pending pods [P...] ---
    pod_requested: np.ndarray  # f32 [P, R]
    pod_priority: np.ndarray  # i32 [P]
    pod_order: np.ndarray  # i32 [P] rank by (priority desc, creation ts asc)
    pod_node_name: np.ndarray  # i32 [P] node index pin (-1 none)
    pod_nominated: np.ndarray  # i32 [P] node index (-1 none)
    pod_req_id: np.ndarray  # i32 [P] -> Rq (node affinity required; -1 none)
    pod_sel_req_id: np.ndarray  # i32 [P] -> Rq (nodeSelector; -1 none)
    pod_pref_id: np.ndarray  # i32 [P] -> Pf (-1 none)
    pod_tolset: np.ndarray  # i32 [P] -> Tl
    pod_label_keys: np.ndarray  # i32 [P, MPL]
    pod_label_vals: np.ndarray  # i32 [P, MPL]
    pod_ports: np.ndarray  # i32 [P, MPorts] encoded host ports (-1 pad)
    # same ports as indices into the distinct pending-port axis Q — the
    # commit scan tracks intra-batch port claims as a [N, Q] bitmap
    pod_port_ids: np.ndarray  # i32 [P, MPorts] -> Q (-1 pad)
    pod_aff_terms: np.ndarray  # i32 [P, MA, 2] (sel, topo-key idx) (-1 pad)
    pod_anti_terms: np.ndarray  # i32 [P, MA, 2]
    pod_pref_aff: np.ndarray  # i32 [P, MA, 2] preferred affinity terms
    pod_pref_aff_w: np.ndarray  # f32 [P, MA] weights (anti encoded as negative)
    pod_tsc: np.ndarray  # i32 [P, MC, 3] (topo-key idx, sel, when) (-1 pad)
    pod_tsc_skew: np.ndarray  # i32 [P, MC] max_skew (0 pad)
    pod_group: np.ndarray  # i32 [P] -> G (-1 none)
    pod_imageset: np.ndarray  # i32 [P] -> Is
    pod_can_preempt: np.ndarray  # bool [P] (preemptionPolicy != Never)
    pod_valid: np.ndarray  # bool [P]

    # --- volumes (VolumeBinding): per-pod PVC constraints [P, MVol] and
    # the PV table [V]. mode: -1 pad, 0 bound (vol_req = PV node-affinity
    # requirement id), 1 unbound WaitForFirstConsumer (vol_class/vol_size
    # select static PV candidates; vol_req = dynamic-provisioning
    # allowed-topology requirement id, -1 = anywhere, -2 = no dynamic),
    # 2 impossible (missing PVC / unbound Immediate) ---
    pod_vol_mode: np.ndarray  # i32 [P, MVol]
    pod_vol_req: np.ndarray  # i32 [P, MVol]
    pod_vol_class: np.ndarray  # i32 [P, MVol] interned class name
    pod_vol_size: np.ndarray  # f32 [P, MVol]
    pv_req_id: np.ndarray  # i32 [V] node-affinity requirement (-1 = any)
    pv_class: np.ndarray  # i32 [V] interned class name
    pv_capacity: np.ndarray  # f32 [V]
    pv_avail: np.ndarray  # bool [V] unclaimed

    # --- pod groups [G] ---
    group_min_member: np.ndarray  # i32 [G]
    group_existing_count: np.ndarray  # i32 [G] members already running

    # --- image sets ---
    imgset_sizes: np.ndarray  # f32 [Is, I] size in bytes of image i if in set

    # --- existing pods [E...] ---
    exist_node: np.ndarray  # i32 [E] node index
    exist_priority: np.ndarray  # i32 [E]
    exist_start: np.ndarray  # f32 [E] creation timestamp (victim tie-break)
    exist_pdb: np.ndarray  # i32 [E, MB] selecting PDB ids (-1 pad)
    exist_requested: np.ndarray  # f32 [E, R]
    exist_label_keys: np.ndarray  # i32 [E, MPL]
    exist_label_vals: np.ndarray  # i32 [E, MPL]
    exist_ports: np.ndarray  # i32 [E, MEP] their host ports (-1 pad) —
    # preemption's what-if needs per-victim ports, not just the per-node
    # aggregate, to know whether evicting a prefix frees a port
    exist_anti_terms: np.ndarray  # i32 [E, MA, 2] their required anti-affinity
    exist_pref_aff: np.ndarray  # i32 [E, MA, 2] their preferred (anti) affinity
    exist_pref_aff_w: np.ndarray  # f32 [E, MA] (anti negative)
    exist_valid: np.ndarray  # bool [E]

    # --- per-node existing-pod table for preemption [N, MPN] ---
    # indices into E, sorted ascending by priority (victims are prefixes)
    node_pods: np.ndarray  # i32 [N, MPN] (-1 pad)

    # --- topology domains ---
    domain_key: np.ndarray  # i32 [D] which topology-key axis each domain is under
    # number of nodes per domain (for spread normalization)
    domain_node_count: np.ndarray  # f32 [D]

    # --- PodDisruptionBudgets [GP] (preemption consumes them) ---
    pdb_allowed: np.ndarray  # i32 [GP] status.disruptionsAllowed

    # --- HTTP-extender verdicts (host-computed AFTER encode via
    # dataclasses.replace; None and never traced unless `has_extender`) ---
    has_extender: bool = False
    pod_extender_mask: np.ndarray = None  # bool [P, N]
    pod_extender_score: np.ndarray = None  # f32 [P, N] weighted

    @property
    def P(self) -> int:
        return self.pod_requested.shape[0]

    @property
    def N(self) -> int:
        return self.node_allocatable.shape[0]

    @property
    def E(self) -> int:
        return self.exist_node.shape[0]

    @property
    def R(self) -> int:
        return len(self.resource_names)

    def array_fields(self) -> dict[str, np.ndarray]:
        return {
            f.name: getattr(self, f.name)
            for f in dataclasses.fields(self)
            if isinstance(getattr(self, f.name), np.ndarray)
        }


# Register as a jax pytree with the non-array fields as static aux data, so
# a ClusterSnapshot can be passed straight into jitted kernels.
def _register_pytree() -> None:
    import jax

    data = [f.name for f in dataclasses.fields(ClusterSnapshot)
            if f.type == "np.ndarray"]
    meta = [f.name for f in dataclasses.fields(ClusterSnapshot)
            if f.type != "np.ndarray"]
    jax.tree_util.register_dataclass(
        ClusterSnapshot, data_fields=data, meta_fields=meta
    )


_register_pytree()


class SnapshotEncoder:
    """Builds `ClusterSnapshot`s. Holds interners AND the derived intern
    tables (expressions, selectors, tolerations, taints, requirement sets,
    image sets, groups, topology keys, domains) so every id is stable
    across cycles — which lets per-object encoded rows be CACHED: a pod or
    node object seen before costs one dict lookup plus array writes
    instead of re-running the compile pipeline. Steady-state re-encodes
    (same cluster, fresh pending set) are dominated by row assembly, not
    Python compilation."""

    def __init__(
        self,
        resource_names: Sequence[str] = api.DEFAULT_RESOURCES,
        pad_pods: int | None = None,
        pad_nodes: int | None = None,
        queue_sort=None,  # QueueSortPlugin; None = PrioritySort
        pad_existing: int | None = None,  # pre-size the sticky E pad: a
        # deployment that folds bindings into the existing set should set
        # this to its expected steady-state existing count so the E
        # regime (and the ~100 s cold recompile a regime flip costs)
        # never changes mid-serving
        pad_pods_per_node: int | None = None,  # pre-size the sticky MPN
        # (victim-table) pad the same way: bind-folds deepen hot nodes'
        # pod lists, and an MPN flip is a full regime change too. NOTE
        # the preemption what-if tables scale with MPN — size to the
        # realistic hot-node depth, not the worst case
        pad_ma: int | None = None,  # pre-size the sticky MA pad (max
        # (anti-)affinity/preferred terms per pod axis): MA buckets by 2,
        # so a mid-serving arrival of a 3-4-term pod flips the regime
        # (full ~100 s recompile) unless pre-sized — set to the largest
        # term count the workload can carry (ADVICE r5)
        pad_mc: int | None = None,  # pre-size the sticky MC pad
        # (topology-spread constraints per pod) the same way
        pad_hysteresis_pct: float = 0.0,  # down-step margin for the
        # P/N pad buckets (config padHysteresisPct): a shrinking real
        # count only steps the pad DOWN when it leaves at least this
        # many percent of headroom inside the smaller bucket, so a
        # workload oscillating around a bucket boundary holds the
        # larger regime instead of flip-flopping (each flip risks a
        # full recompile). 0 disables (classic immediate down-step).
    ) -> None:
        self.strings = StringInterner()
        self.resource_names = list(resource_names)
        self.pad_pods = pad_pods
        self.pad_nodes = pad_nodes
        self.pad_existing = pad_existing
        self.pad_pods_per_node = pad_pods_per_node
        self.pad_ma = pad_ma
        self.pad_mc = pad_mc
        self.pad_hysteresis_pct = float(pad_hysteresis_pct)
        # last pad actually used per hysteresis dimension ("P"/"N")
        self._held_pads: dict[str, int] = {}
        # the profile's queueSort plugin (SURVEY §2 C11): owns the
        # pod_order rank both encode paths bake into the snapshot
        if queue_sort is None:
            from ..framework.queuesort import PrioritySort

            queue_sort = PrioritySort()
        self.queue_sort = queue_sort
        # persistent intern tables (grow-only; ids stable across encodes)
        self._exprs_t = _InternTable()  # rows: (key, op, vals, num)
        self._reqs_t = _InternTable()  # rows: tuple of terms (expr-id tuples)
        self._prefs_t = _InternTable()  # rows: tuple of (exprs, weight)
        self._tols_t = _InternTable()  # rows: sorted (key, op, val, effect)
        self._taints_t = _InternTable()  # rows: sorted (key, val, effect)
        self._sels_t = _InternTable()  # rows: tuple of expr ids
        self._imgsets_t = _InternTable()  # rows: sorted image ids
        self._image_ids: dict[str, int] = {}
        self._image_sizes: dict[int, float] = {}
        self._group_ids: dict[str, int] = {}
        self._topo_keys: list[str] = [HOSTNAME_LABEL]
        # index mirrors of the list-shaped tables (shared with the
        # native pod_row builder; kept in sync wherever the list grows)
        self._topo_idx: dict[str, int] = {HOSTNAME_LABEL: 0}
        self._rn_idx: dict[str, int] = {
            n: i for i, n in enumerate(self.resource_names)
        }
        self._domain_map: dict[tuple[int, int], int] = {}
        # per-object row caches, keyed by id(); the tuple holds a strong
        # reference so a live entry's id can never be reused. matchFields
        # expressions bake node INDICES in, so entries carrying them are
        # tagged with the node epoch and recompiled when the node set maps
        # differently.
        self._pod_cache: dict[int, tuple[Any, dict]] = {}
        self._node_cache: dict[int, tuple[Any, dict]] = {}
        self._node_epoch = 0
        self._node_names: tuple[str, ...] = ()
        self._cycle_index = 0  # bumped per encode (sampling rotation)
        # sticky (grow-only) pending-side pad dims and capability flags:
        # without them a pod with the cycle's longest label list LEAVING
        # would shrink a padded dim, change the packed spec, and force a
        # full recompile — the exact regime churn the pad bucketing exists
        # to avoid. Padding rows are semantically inert, so growing-only is
        # safe; it also makes the delta path (encode_packed) applicable.
        self._sticky_dims: dict[str, int] = {}
        self._sticky_flags: dict[str, bool] = {}
        # state for the delta fast path; see encode_packed
        self._delta_state: dict | None = None
        self._arena_spec = None
        # observability: how many encode_packed calls hit the delta path
        self.delta_hits = 0
        self.full_encodes = 0
        # existing-set folds that succeeded (_try_fold_existing), and the
        # appended rows they built in Python because the native writer
        # does not cover the pod
        self.fold_hits = 0
        self.fold_fallback_pods = 0
        self.fold_removed_pods = 0
        # ... and the cycles in which the fold stood aside by its own
        # rule (more of the list changed than stayed): a full encode
        # taken on purpose, which core/observe does not read as a miss
        self.fold_declined = 0
        # per-segment ms of the LAST delta encode (see _encode_delta)
        self.delta_profile: dict[str, float] = {}

    def hysteresis_pad(self, dim: str, candidate: int, real: int) -> int:
        """Regime hysteresis for the externally-bucketed P/N pads: the
        pad a caller should actually use for this encode, given the
        bucket-rounded `candidate` and the `real` count behind it.

        Up-steps are immediate (the candidate no longer fits the held
        regime). A DOWN-step is taken only when the real count leaves at
        least `pad_hysteresis_pct` percent of headroom inside the
        smaller bucket — a count hovering just under the boundary keeps
        the larger (already-compiled) regime, so an oscillating
        workload costs zero regime flips instead of one per crossing.
        With the knob at 0 this is the identity on `candidate`."""
        held = self._held_pads.get(dim, 0)
        pct = self.pad_hysteresis_pct
        if (
            candidate >= held
            or pct <= 0.0
            or real <= candidate * (1.0 - pct / 100.0)
        ):
            self._held_pads[dim] = candidate
            return candidate
        return held

    def _stick(self, key: str, val: int) -> int:
        cur = self._sticky_dims.get(key, 0)
        if val < cur:
            val = cur
        self._sticky_dims[key] = val
        return val

    def _stick_flag(self, key: str, val: bool) -> bool:
        cur = self._sticky_flags.get(key, False) or bool(val)
        self._sticky_flags[key] = cur
        return cur

    def _table_lens(self) -> tuple:
        """Sizes of every grow-only interning structure a cached row can
        reference — if any changes while encoding a pod row, the stable-
        side finalize tables need new entries and the delta path must fall
        back to a full encode."""
        return (
            len(self.strings), len(self.resource_names), len(self._exprs_t),
            len(self._reqs_t), len(self._prefs_t), len(self._tols_t),
            len(self._taints_t), len(self._sels_t), len(self._imgsets_t),
            len(self._image_ids), len(self._group_ids), len(self._topo_keys),
        )

    # -- small helpers -----------------------------------------------------

    def _resources_vec(self, req: dict[str, float]) -> np.ndarray:
        idx = self._rn_idx
        for name in req:
            if name not in idx:
                idx[name] = len(self.resource_names)
                self.resource_names.append(name)
        v = np.zeros(len(self.resource_names), np.float32)
        for name, val in req.items():
            v[idx[name]] = val
        return v

    def _native_ctx(self) -> dict:
        """The persistent interning structures handed to the native
        pod_row builder (native/fastassemble.cc) — built once; every
        entry is a live reference to a grow-only table, so the ctx never
        staleness-invalidates."""
        ctx = getattr(self, "_native_ctx_cache", None)
        if ctx is None:
            ctx = {
                "str_ids": self.strings._ids,
                "str_list": self.strings._strs,
                "exprs_idx": self._exprs_t.index,
                "exprs_rows": self._exprs_t.rows,
                "sels_idx": self._sels_t.index,
                "sels_rows": self._sels_t.rows,
                "reqs_idx": self._reqs_t.index,
                "reqs_rows": self._reqs_t.rows,
                "tols_idx": self._tols_t.index,
                "tols_rows": self._tols_t.rows,
                "imgsets_idx": self._imgsets_t.index,
                "imgsets_rows": self._imgsets_t.rows,
                "image_ids": self._image_ids,
                "group_ids": self._group_ids,
                "topo_idx": self._topo_idx,
                "topo_list": self._topo_keys,
                "rn_idx": self._rn_idx,
                "rn_list": self.resource_names,
                "ns_key": NAMESPACE_KEY,
                "pods_name": api.PODS,
                "effect_codes": dict(_EFFECT_CODE),
                "op_in": OP_IN,
                "op_not_in": OP_NOT_IN,
                "op_exists": OP_EXISTS,
                "op_dne": OP_DOES_NOT_EXIST,
                "tol_eq": TOL_OP_EQUAL,
                "tol_exists": TOL_OP_EXISTS,
                "when_dns": WHEN_DO_NOT_SCHEDULE,
                "when_sa": WHEN_SCHEDULE_ANYWAY,
            }
            self._native_ctx_cache = ctx
        return ctx

    def encode(
        self,
        nodes: Sequence[Node],
        pending: Sequence[Pod],
        existing: Sequence[tuple[Pod, str]] = (),
        pod_groups: Sequence[api.PodGroup] = (),
        pvcs: Sequence[api.PersistentVolumeClaim] = (),
        pvs: Sequence[api.PersistentVolume] = (),
        storage_classes: Sequence[api.StorageClass] = (),
        pdbs: Sequence[api.PodDisruptionBudget] = (),
    ) -> ClusterSnapshot:
        """One-shot encode. `existing` is (pod, node_name) for every pod
        already assigned (bound or assumed)."""
        S = self.strings
        rn = self.resource_names
        # Resource-name discovery happens as rows are built (node_rowdata /
        # pod_rowdata call _resources_vec, which appends unseen names), so
        # the R axis is read only AFTER the row walks below; cached rows
        # from earlier encodes may be shorter and are right-padded.

        n_real, p_real, e_real = len(nodes), len(pending), len(existing)
        self._cycle_index += 1
        # hysteresis applies to the DEFAULT pow2 bucketing here; callers
        # that drive pad_pods/pad_nodes themselves (the scheduler's
        # bucketed pads) route their candidates through hysteresis_pad
        # before assigning, so both paths share one held-regime state
        N = self.pad_nodes or self.hysteresis_pad(
            "N", _pow2_bucket(n_real), n_real
        )
        P = self.pad_pods or self.hysteresis_pad(
            "P", _pow2_bucket(p_real), p_real
        )
        # E is STICKY (like MPL/MA): the incremental existing-fold appends
        # bound pods in place, and a completion batch that shrinks e_real
        # must not flip the packed regime; pad_existing pre-sizes it.
        # The pad folds INTO the pow2 bucket (not max'd after) so a
        # non-power-of-two pad can never leave E below the bucket a
        # grown e_real would demand — that would re-flip the regime
        # mid-run, the exact thing pre-sizing exists to prevent.
        E = self._stick(
            "E",
            _pow2_bucket(max(e_real, self.pad_existing or 0))
            if (e_real or self.pad_existing) else 8,
        )

        node_index = {nd.name: i for i, nd in enumerate(nodes)}
        names_now = tuple(nd.name for nd in nodes)
        if names_now != self._node_names:
            self._node_names = names_now
            self._node_epoch += 1

        # ---- persistent tables (ids stable across encodes) ----
        exprs_t = self._exprs_t
        reqs_t = self._reqs_t
        prefs_t = self._prefs_t
        tols_t = self._tols_t
        taints_t = self._taints_t
        sels_t = self._sels_t
        imgsets_t = self._imgsets_t

        def intern_expr(key: int, op: int, vals: tuple[int, ...], num: float) -> int:
            return exprs_t.intern((key, op, vals, num))

        def compile_req(r: NodeSelectorRequirement) -> int:
            op = _OP_CODE[r.operator]
            vals = tuple(sorted(S.intern(v) for v in r.values))
            num = 0.0
            if op in (OP_GT, OP_LT):
                # upstream treats a missing or non-numeric bound as no-match
                try:
                    num = float(r.values[0])
                except (IndexError, ValueError):
                    return intern_expr(0, OP_IMPOSSIBLE, (), 0.0)
                vals = ()
            return intern_expr(S.intern(r.key), op, vals, num)

        def compile_field_req(r: NodeSelectorRequirement) -> int:
            # metadata.name In [names] -> node index set (FIELD_IN); only
            # In/NotIn are defined for matchFields, anything else no-matches
            if r.operator not in (api.OP_IN, api.OP_NOT_IN):
                return intern_expr(0, OP_IMPOSSIBLE, (), 0.0)
            idxs = tuple(
                sorted(node_index[v] for v in r.values if v in node_index)
            )
            # encode NotIn by op FIELD_IN with complement at kernel level is
            # messy; instead resolve the complement here (node set is known).
            if r.operator == api.OP_NOT_IN:
                idxs = tuple(i for i in range(n_real) if i not in set(idxs))
            return intern_expr(0, OP_FIELD_IN, idxs, 0.0)

        def compile_node_affinity_required(terms: Sequence[NodeSelectorTerm]) -> int:
            compiled = []
            for t in terms:
                exprs = [compile_req(e) for e in t.match_expressions]
                exprs += [compile_field_req(e) for e in t.match_fields]
                compiled.append(tuple(exprs))
            if not compiled:
                return -1
            return reqs_t.intern(tuple(compiled))

        def compile_node_affinity_preferred(
            prefs: Sequence[api.PreferredSchedulingTerm],
        ) -> int:
            rows = []
            for p in prefs:
                exprs = [compile_req(e) for e in p.preference.match_expressions]
                exprs += [compile_field_req(e) for e in p.preference.match_fields]
                rows.append((tuple(exprs), float(p.weight)))
            if not rows:
                return -1
            return prefs_t.intern(tuple(rows))

        def compile_tolerations(tols: Sequence[api.Toleration]) -> int:
            rows = []
            for t in tols:
                key = S.intern(t.key) if t.key else -1
                op = TOL_OP_EXISTS if t.operator == "Exists" else TOL_OP_EQUAL
                val = S.intern(t.value)
                eff = _EFFECT_CODE[t.effect] if t.effect else -1
                rows.append((key, op, val, eff))
            return tols_t.intern(tuple(sorted(rows)))

        def compile_taints(taints: Sequence[api.Taint]) -> int:
            return taints_t.intern(
                tuple(
                    sorted(
                        (S.intern(t.key), S.intern(t.value), _EFFECT_CODE[t.effect])
                        for t in taints
                    )
                )
            )

        topo_keys = self._topo_keys
        topo_idx = self._topo_idx

        def topo_key_idx(key: str) -> int:
            i = topo_idx.get(key)
            if i is None:
                i = len(topo_keys)
                topo_idx[key] = i
                topo_keys.append(key)
            return i

        def compile_selector(sel: LabelSelector, namespaces: tuple[str, ...]) -> int:
            exprs = []
            ns_vals = tuple(sorted(S.intern(n) for n in namespaces))
            exprs.append(intern_expr(S.intern(NAMESPACE_KEY), OP_IN, ns_vals, 0.0))
            for k, v in sorted(sel.match_labels.items()):
                exprs.append(
                    intern_expr(S.intern(k), OP_IN, (S.intern(v),), 0.0)
                )
            for e in sel.match_expressions:
                exprs.append(compile_req(e))
            return sels_t.intern(tuple(exprs))

        def compile_aff_terms(
            terms: Sequence[PodAffinityTerm], own_ns: str
        ) -> list[tuple[int, int]]:
            out = []
            for t in terms:
                ns = t.namespaces or (own_ns,)
                out.append(
                    (compile_selector(t.label_selector, tuple(ns)), topo_key_idx(t.topology_key))
                )
            return out

        image_ids = self._image_ids
        image_sizes = self._image_sizes

        def image_id(name: str) -> int:
            i = image_ids.get(name)
            if i is None:
                i = len(image_ids)
                image_ids[name] = i
            return i

        def compile_imageset(images: Sequence[str]) -> int:
            return imgsets_t.intern(tuple(sorted(image_id(i) for i in images)))

        group_ids = self._group_ids
        declared = {g.name: g.min_member for g in pod_groups}

        def group_id(name: str) -> int:
            if not name:
                return -1
            i = group_ids.get(name)
            if i is None:
                i = len(group_ids)
                group_ids[name] = i
            return i

        # ---- volumes (VolumeBinding inputs) ----
        pvc_map = {c.key: c for c in pvcs}
        pv_map = {v.name: v for v in pvs}
        class_map = {s.name: s for s in storage_classes}
        vol_sig = (
            tuple(sorted(
                (c.key, c.volume_name, c.storage_class, c.request)
                for c in pvcs
            )),
            tuple(sorted(
                (v.name, v.claim_ref, v.storage_class, v.capacity,
                 v.node_affinity)
                for v in pvs
            )),
            tuple(sorted(
                (s.name, s.volume_binding_mode, s.provisioner,
                 s.allowed_topologies)
                for s in storage_classes
            )),
        )
        if vol_sig != getattr(self, "_vol_sig", None):
            self._vol_sig = vol_sig
            self._vol_epoch = getattr(self, "_vol_epoch", 0) + 1
        vol_epoch = getattr(self, "_vol_epoch", 0)

        def _terms_use_fields(terms) -> bool:
            return any(t.match_fields for t in terms)

        def compile_pod_vols(p: Pod) -> tuple[list, bool]:
            """((mode, req_id, class_id, size) per mounted PVC, uses_fields)
            — see the ClusterSnapshot field docs for the row encoding.
            uses_fields marks rows whose compiled requirements bake node
            INDICES in (matchFields), which must invalidate on node-set
            changes."""
            rows: list[tuple[int, int, int, float]] = []
            uses_fields = False
            for claim in p.spec.volumes:
                pvc = pvc_map.get(f"{p.namespace}/{claim}")
                if pvc is None:  # missing PVC: unschedulable (upstream
                    rows.append((2, -1, -1, 0.0))  # UnschedulableAndUnresolvable)
                    continue
                if pvc.volume_name:
                    pv = pv_map.get(pvc.volume_name)
                    if pv is None:
                        rows.append((2, -1, -1, 0.0))
                        continue
                    rid = (
                        compile_node_affinity_required(pv.node_affinity)
                        if pv.node_affinity else -1
                    )
                    uses_fields |= _terms_use_fields(pv.node_affinity)
                    rows.append((0, rid, -1, 0.0))
                    continue
                cls = class_map.get(pvc.storage_class)
                if cls is None or (
                    cls.volume_binding_mode != api.VOLUME_BINDING_WAIT
                ):
                    # unbound Immediate-mode PVC: the volume binder owns
                    # it; the pod stays unschedulable until bound
                    rows.append((2, -1, -1, 0.0))
                    continue
                if cls.provisioner:
                    dyn = (
                        compile_node_affinity_required(cls.allowed_topologies)
                        if cls.allowed_topologies else -1
                    )
                    uses_fields |= _terms_use_fields(cls.allowed_topologies)
                else:
                    dyn = -2
                rows.append(
                    (1, dyn, S.intern(pvc.storage_class), float(pvc.request))
                )
            return rows, uses_fields

        # ---- walk nodes (cached per object) ----
        def node_rowdata(nd: Node) -> dict:
            hit = self._node_cache.get(id(nd))
            if hit is not None and hit[0] is nd:
                return hit[1]
            labels = dict(nd.metadata.labels)
            labels.setdefault(HOSTNAME_LABEL, nd.name)
            imgs = []
            for img in nd.status.images:
                for nm in img.names:
                    ii = image_id(nm)
                    imgs.append(ii)
                    image_sizes[ii] = float(img.size_bytes)
            rows = [
                (S.intern(k), S.intern(v), _num_or_nan(v))
                for k, v in sorted(labels.items())
            ]
            data = {
                "alloc": self._resources_vec(nd.status.allocatable),
                "unsched": nd.spec.unschedulable,
                "taintset": compile_taints(nd.spec.taints),
                "lab_k": np.array([k for k, _, _ in rows], np.int32),
                "lab_v": np.array([v for _, v, _ in rows], np.int32),
                "lab_num": np.array([n for _, _, n in rows], np.float32),
                "label_map": {k: S.intern(v) for k, v in labels.items()},
                "images": imgs,
            }
            self._node_cache[id(nd)] = (nd, data)
            return data

        node_rows = [node_rowdata(nd) for nd in nodes]

        # ---- per-pod row data (cached per object) ----
        from .. import native as _native

        native_pod_row = _native.pod_row
        native_ctx = self._native_ctx() if native_pod_row else None

        def pod_rowdata(p: Pod) -> dict:
            hit = self._pod_cache.get(id(p))
            if hit is not None and hit[0] is p:
                data = hit[1]
                if (
                    data["epoch"] is None or data["epoch"] == self._node_epoch
                ) and (
                    data["vol_epoch"] is None
                    or data["vol_epoch"] == vol_epoch
                ):
                    return data
            if native_pod_row is not None:
                # native fast path (~4x the Python walk); returns None
                # for pods with features it does not cover (volumes,
                # real nodeAffinity, exotic selector operators)
                d = native_pod_row(p, native_ctx)
                if d is not None:
                    self._pod_cache[id(p)] = (p, d)
                    return d
            a = _aff(p)
            req_id = -1
            pref_id = -1
            uses_fields = False
            if a.node_affinity and a.node_affinity.required:
                req_id = compile_node_affinity_required(a.node_affinity.required)
                uses_fields = uses_fields or any(
                    t.match_fields for t in a.node_affinity.required
                )
            if a.node_affinity and a.node_affinity.preferred:
                pref_id = compile_node_affinity_preferred(a.node_affinity.preferred)
                uses_fields = uses_fields or any(
                    t.preference.match_fields for t in a.node_affinity.preferred
                )
            sel_req_id = -1
            if p.spec.node_selector:
                term = NodeSelectorTerm(
                    tuple(
                        NodeSelectorRequirement(k, api.OP_IN, (v,))
                        for k, v in sorted(p.spec.node_selector.items())
                    )
                )
                sel_req_id = compile_node_affinity_required([term])
            ns = p.namespace
            aff: list[tuple[int, int]] = []
            anti: list[tuple[int, int]] = []
            prefs: list[tuple[int, int, float]] = []
            if a.pod_affinity:
                aff = compile_aff_terms(a.pod_affinity.required, ns)
                for w in a.pod_affinity.preferred:
                    (s, k) = compile_aff_terms([w.term], ns)[0]
                    prefs.append((s, k, float(w.weight)))
            if a.pod_anti_affinity:
                anti = compile_aff_terms(a.pod_anti_affinity.required, ns)
                for w in a.pod_anti_affinity.preferred:
                    (s, k) = compile_aff_terms([w.term], ns)[0]
                    prefs.append((s, k, -float(w.weight)))
            tsc = []
            for c in p.spec.topology_spread_constraints:
                when = (
                    WHEN_DO_NOT_SCHEDULE
                    if c.when_unsatisfiable == api.DO_NOT_SCHEDULE
                    else WHEN_SCHEDULE_ANYWAY
                )
                tsc.append((
                    topo_key_idx(c.topology_key),
                    compile_selector(c.label_selector, (ns,)),
                    when,
                    c.max_skew,
                ))
            labels = [(S.intern(NAMESPACE_KEY), S.intern(ns))] + [
                (S.intern(k), S.intern(v))
                for k, v in sorted(p.metadata.labels.items())
            ]
            ports = [
                port * 4 + {"TCP": 0, "UDP": 1, "SCTP": 2}.get(proto, 3)
                for (port, proto, _) in p.host_ports()
            ]
            vols, vol_fields = compile_pod_vols(p)
            # rows are PACKED numpy sections: assembly is a native strided
            # scatter (k8s_scheduler_tpu/native) instead of per-pod Python
            # array writes
            data = {
                "reqvec": self._resources_vec(p.resource_requests()),
                "prio": p.spec.priority,
                "creation": p.metadata.creation_timestamp,
                "req_id": req_id,
                "pref_id": pref_id,
                "sel_req_id": sel_req_id,
                "tolset": compile_tolerations(p.spec.tolerations),
                "lab_k": _i32([k for k, _ in labels]),
                "lab_v": _i32([v for _, v in labels]),
                "ports": _i32(ports),
                "aff": _i32([x for t in aff for x in t]),
                "anti": _i32([x for t in anti for x in t]),
                "pref": _i32([x for s, k, _ in prefs for x in (s, k)]),
                "pref_w": _f32([w for _, _, w in prefs]),
                "tsc": _i32([x for k, s, w, _ in tsc for x in (k, s, w)]),
                "tsc_skew": _i32([sk for _, _, _, sk in tsc]),
                "n_aff": max(len(aff), len(anti), len(prefs)),
                "gid": group_id(p.spec.pod_group),
                "imageset": compile_imageset(p.images()),
                "can_preempt": p.spec.preemption_policy != "Never",
                "vol_mode": _i32([m for m, _, _, _ in vols]),
                "vol_req": _i32([r for _, r, _, _ in vols]),
                "vol_cls": _i32([c for _, _, c, _ in vols]),
                "vol_size": _f32([s for _, _, _, s in vols]),
                "vol_epoch": vol_epoch if p.spec.volumes else None,
                "epoch": (
                    self._node_epoch if (uses_fields or vol_fields) else None
                ),
            }
            self._pod_cache[id(p)] = (p, data)
            return data

        pend_rows = [pod_rowdata(p) for p in pending]
        exist_rows = [pod_rowdata(p) for p, _ in existing]
        all_rows = pend_rows + exist_rows

        # mark-and-sweep the caches against the live object set: memory
        # stays bounded by the cluster without the full-recompile cliff a
        # wholesale clear() would cause
        live_pods = {id(p) for p in pending} | {id(p) for p, _ in existing}
        if len(self._pod_cache) > 2 * max(len(live_pods), 1):
            self._pod_cache = {
                k: v for k, v in self._pod_cache.items() if k in live_pods
            }
        live_nodes = {id(nd) for nd in nodes}
        if len(self._node_cache) > 2 * max(len(live_nodes), 1):
            self._node_cache = {
                k: v for k, v in self._node_cache.items() if k in live_nodes
            }

        # the resource-name axis is final only now (row building above
        # discovered every name, including from cached-and-reused rows'
        # earlier encodes — rn is grow-only)
        R = len(rn)

        # ---- dims the pending AND stable sides share (sticky) ----
        MPL = self._stick(
            "MPL", _pad_dim(max([len(d["lab_k"]) for d in all_rows] + [1]), 8)
        )
        MA = self._stick(
            # bucket 2, not 4: real pods rarely carry >2 terms per axis
            # and every per-slot loop in the dyn kernels (W builds,
            # spread-mask HIGH dots, update matmuls, preemption what-if)
            # pays the pad directly; sticky growth keeps recompiles rare.
            # pad_ma folds INTO the max (like pad_existing into E's
            # bucket) so pre-sizing can never leave MA below what a real
            # pod demands
            "MA", _pad_dim(
                max([d["n_aff"] for d in all_rows]
                    + [1, self.pad_ma or 0]), 2
            )
        )

        from .. import native

        # ---- stable-side cache ----
        # Everything derived from nodes/existing/volumes/PDBs alone is
        # cached wholesale, keyed on object identities plus every
        # grow-only interning dimension the arrays bake in: in steady
        # serving only the pending set changes, and re-assembling the
        # cluster side (existing-pod tables, per-node aggregations,
        # domains, expression tables) dominated warm encode time.
        stable_key = (
            tuple(id(nd) for nd in nodes),
            tuple((id(p), nm) for p, nm in existing),
            vol_sig,
            tuple((id(b), b.disruptions_allowed) for b in pdbs),
            self._node_epoch, N, E, R, MPL, MA,
            len(exprs_t.rows), len(reqs_t.rows), len(prefs_t.rows),
            len(tols_t.rows), len(taints_t.rows), len(sels_t.rows),
            len(imgsets_t.rows), len(image_ids), len(group_ids),
            len(topo_keys),
        )
        if getattr(self, "_stable_key", None) == stable_key:
            st = self._stable
        else:
            # ---- assemble node arrays (native strided scatters) ----

            ML = _pad_dim(max([len(d["lab_k"]) for d in node_rows] + [1]), 8)
            node_alloc = np.zeros((N, R), np.float32)
            node_requested = np.zeros((N, R), np.float32)
            node_unsched = np.zeros(N, bool)
            node_taintset = np.zeros(N, np.int32)
            nl_keys = np.full((N, ML), -1, np.int32)
            nl_vals = np.full((N, ML), -1, np.int32)
            nl_num = np.full((N, ML), np.nan, np.float32)
            node_valid = np.zeros(N, bool)
            node_valid[:n_real] = True

            native.scatter_rows(node_alloc, [d["alloc"] for d in node_rows])
            native.fill_scalars(node_unsched, [d["unsched"] for d in node_rows])
            native.fill_scalars(node_taintset, [d["taintset"] for d in node_rows])
            native.scatter_rows(nl_keys, [d["lab_k"] for d in node_rows])
            native.scatter_rows(nl_vals, [d["lab_v"] for d in node_rows])
            native.scatter_rows(nl_num, [d["lab_num"] for d in node_rows])
            node_image_sets = [d["images"] for d in node_rows]


            V = _pad_dim(len(pvs), 4)
            pv_req_arr = np.full(V, -1, np.int32)
            pv_class_arr = np.full(V, -1, np.int32)
            pv_cap_arr = np.zeros(V, np.float32)
            pv_avail_arr = np.zeros(V, bool)
            claimed_pvs = {c.volume_name for c in pvcs if c.volume_name}
            for i, pv in enumerate(pvs):
                pv_req_arr[i] = (
                    compile_node_affinity_required(pv.node_affinity)
                    if pv.node_affinity else -1
                )
                pv_class_arr[i] = S.intern(pv.storage_class)
                pv_cap_arr[i] = pv.capacity
                pv_avail_arr[i] = not pv.claim_ref and pv.name not in claimed_pvs

            # ---- assemble existing-pod arrays ----
            MB = 2  # PDBs tracked per pod (more than 2 selecting one pod is
            # pathological; extras conservatively protect via the first two)
            GP = max(len(pdbs), 1)
            pdb_allowed = np.zeros(GP, np.int32)
            for gi, pdb in enumerate(pdbs):
                pdb_allowed[gi] = pdb.disruptions_allowed
            exist_pdb = np.full((E, MB), -1, np.int32)
            # start times are stored RELATIVE to the oldest existing pod:
            # float32 at Unix-epoch magnitude (~1.7e9) has ~128s resolution,
            # which would collapse the preemption start-time tie-break; only
            # the within-snapshot ORDER matters
            start_base = min(
                (p.metadata.creation_timestamp for p, _ in existing),
                default=0.0,
            )
            exist_start = np.zeros(E, np.float32)

            exist_node = np.full(E, -1, np.int32)
            exist_prio = np.zeros(E, np.int32)
            exist_req = np.zeros((E, R), np.float32)
            el_keys = np.full((E, MPL), -1, np.int32)
            el_vals = np.full((E, MPL), -1, np.int32)
            MEP = self._stick(
                "MEP",
                _pad_dim(max([len(d["ports"]) for d in exist_rows] + [1]),
                         4),
            )
            exist_ports_arr = np.full((E, MEP), -1, np.int32)
            exist_anti = np.full((E, MA, 2), -1, np.int32)
            exist_pref = np.full((E, MA, 2), -1, np.int32)
            exist_pref_w = np.zeros((E, MA), np.float32)
            exist_valid = np.zeros(E, bool)
            exist_valid[:e_real] = True

            # existing pods' own (non-anti) required affinity is not re-checked
            # against incoming pods (upstream symmetry applies to anti-affinity
            # and preferred terms only), so required-affinity terms are dropped

            exist_group = np.full(E, -1, np.int32)
            # absolute creation timestamps (f64) back the incremental
            # existing-fold: exist_start can be re-based exactly when the
            # oldest pod changes
            exist_creation_abs = np.zeros(E, np.float64)
            if e_real:
                exist_creation_abs[:e_real] = [
                    d["creation"] for d in exist_rows
                ]
            native.fill_scalars(exist_prio, [d["prio"] for d in exist_rows])
            native.fill_scalars(exist_group, [d["gid"] for d in exist_rows])
            native.fill_scalars(
                exist_start, [d["creation"] - start_base for d in exist_rows]
            )
            native.fill_scalars(
                exist_node, [node_index.get(nm, -1) for _, nm in existing]
            )
            native.scatter_rows(exist_req, [d["reqvec"] for d in exist_rows])
            native.scatter_rows(el_keys, [d["lab_k"] for d in exist_rows])
            native.scatter_rows(el_vals, [d["lab_v"] for d in exist_rows])
            native.scatter_rows(
                exist_ports_arr, [d["ports"] for d in exist_rows]
            )
            native.scatter_rows(
                exist_anti.reshape(E, MA * 2), [d["anti"] for d in exist_rows]
            )
            native.scatter_rows(
                exist_pref.reshape(E, MA * 2), [d["pref"] for d in exist_rows]
            )
            native.scatter_rows(exist_pref_w, [d["pref_w"] for d in exist_rows])
            if pdbs:
                for i, (p, _nm) in enumerate(existing):
                    b = 0
                    for gi, pdb in enumerate(pdbs):
                        if b >= MB:
                            break
                        if _pdb_matches(pdb, p):
                            exist_pdb[i, b] = gi
                            b += 1

            # per-node aggregation, vectorized: requested sums, the priority-
            # sorted victim table; used ports stay a sparse residue loop
            en = exist_node[:e_real]
            placed_mask = en >= 0
            np.add.at(
                node_requested, en[placed_mask], exist_req[:e_real][placed_mask]
            )
            used_ports = _used_ports_by_node(
                exist_node, exist_ports_arr, e_real
            )
            MUP = self._stick(
                "MUP",
                _pad_dim(
                    max([len(u) for u in used_ports.values()] + [1]), 4
                ),
            )
            node_used_ports = np.full((N, MUP), -1, np.int32)
            for i, u in used_ports.items():
                node_used_ports[i, : len(u)] = u

            # node_pods [N, MPN]: existing indices per node (_victim_table)
            e_ids = np.flatnonzero(placed_mask)
            if e_ids.size:
                sn, col, se = _victim_table(en, exist_prio, e_ids)
                # the pad folds INTO the bucket-of-8 (like E into its
                # pow2 bucket): a non-multiple-of-8 pad must not leave
                # MPN below the bucket a grown depth would demand
                MPN = self._stick(
                    "MPN",
                    _pad_dim(
                        max(int(col.max()) + 1,
                            self.pad_pods_per_node or 0), 8
                    ),
                )
                node_pods = np.full((N, MPN), -1, np.int32)
                node_pods[sn, col] = se
            else:
                MPN = self._stick(
                    "MPN",
                    _pad_dim(max(1, self.pad_pods_per_node or 0), 8),
                )
                node_pods = np.full((N, MPN), -1, np.int32)

            # ---- topology domains (flat ids across keys) ----
            K = len(topo_keys)
            domain_map: dict[tuple[int, int], int] = {}
            node_domains = np.full((N, K), -1, np.int32)
            for i, nd in enumerate(nodes):
                labels = dict(nd.metadata.labels)
                labels.setdefault(HOSTNAME_LABEL, nd.name)
                for k, key in enumerate(topo_keys):
                    if key in labels:
                        dk = (k, S.intern(labels[key]))
                        if dk not in domain_map:
                            domain_map[dk] = len(domain_map)
                        node_domains[i, k] = domain_map[dk]
            D = _pad_dim(len(domain_map), 8)
            domain_key = np.full(D, -1, np.int32)
            domain_node_count = np.zeros(D, np.float32)
            for (k, _v), d in domain_map.items():
                domain_key[d] = k
            for i in range(n_real):
                for k in range(K):
                    d = node_domains[i, k]
                    if d >= 0:
                        domain_node_count[d] += 1.0

            # ---- finalize tables ----
            Ex = _pad_dim(len(exprs_t.rows), 8)
            MV = _pad_dim(max([len(v) for _, _, v, _ in exprs_t.rows] + [1]), 4)
            ex_key = np.full(Ex, -1, np.int32)
            ex_op = np.full(Ex, -1, np.int32)
            ex_vals = np.full((Ex, MV), -1, np.int32)
            ex_num = np.zeros(Ex, np.float32)
            for i, (k, op, vals, num) in enumerate(exprs_t.rows):
                ex_key[i] = k
                ex_op[i] = op
                ex_vals[i, : len(vals)] = vals
                ex_num[i] = num

            Rq = _pad_dim(len(reqs_t.rows), 4)
            MT = _pad_dim(max([len(r) for r in reqs_t.rows] + [1]), 2)
            ME = _pad_dim(
                max([len(t) for r in reqs_t.rows for t in r] + [1]), 2
            )
            rq_exprs = np.full((Rq, MT, ME), -1, np.int32)
            for i, terms in enumerate(reqs_t.rows):
                for j, t in enumerate(terms):
                    rq_exprs[i, j, : len(t)] = t

            Pf = _pad_dim(len(prefs_t.rows), 2)
            MPT = _pad_dim(max([len(r) for r in prefs_t.rows] + [1]), 2)
            MPE = _pad_dim(
                max([len(t) for r in prefs_t.rows for (t, _w) in r] + [1]), 2
            )
            pf_exprs = np.full((Pf, MPT, MPE), -1, np.int32)
            pf_weight = np.zeros((Pf, MPT), np.float32)
            for i, row in enumerate(prefs_t.rows):
                for j, (exprs, w) in enumerate(row):
                    pf_exprs[i, j, : len(exprs)] = exprs
                    pf_weight[i, j] = w

            Tl = _pad_dim(len(tols_t.rows), 2)
            MTl = _pad_dim(max([len(r) for r in tols_t.rows] + [1]), 4)
            tl_key = np.full((Tl, MTl), 0, np.int32)
            tl_op = np.zeros((Tl, MTl), np.int32)
            tl_val = np.zeros((Tl, MTl), np.int32)
            tl_effect = np.zeros((Tl, MTl), np.int32)
            tl_valid = np.zeros((Tl, MTl), bool)
            for i, row in enumerate(tols_t.rows):
                for j, (k, op, v, e) in enumerate(row):
                    tl_key[i, j] = k
                    tl_op[i, j] = op
                    tl_val[i, j] = v
                    tl_effect[i, j] = e
                    tl_valid[i, j] = True

            Ts = _pad_dim(len(taints_t.rows), 2)
            MTt = _pad_dim(max([len(r) for r in taints_t.rows] + [1]), 4)
            ts_key = np.full((Ts, MTt), -1, np.int32)
            ts_val = np.zeros((Ts, MTt), np.int32)
            ts_effect = np.zeros((Ts, MTt), np.int32)
            ts_valid = np.zeros((Ts, MTt), bool)
            for i, row in enumerate(taints_t.rows):
                for j, (k, v, e) in enumerate(row):
                    ts_key[i, j] = k
                    ts_val[i, j] = v
                    ts_effect[i, j] = e
                    ts_valid[i, j] = True

            Ssel = _pad_dim(len(sels_t.rows), 4)
            MSE = _pad_dim(max([len(r) for r in sels_t.rows] + [1]), 4)
            sel_exprs = np.full((Ssel, MSE), -1, np.int32)
            for i, row in enumerate(sels_t.rows):
                sel_exprs[i, : len(row)] = row

            I = max(len(image_ids), 1)
            Is = _pad_dim(len(imgsets_t.rows), 2)
            imgset_sizes = np.zeros((Is, I), np.float32)
            for i, row in enumerate(imgsets_t.rows):
                for ii in row:
                    imgset_sizes[i, ii] = image_sizes.get(ii, 0.0)
            node_images = np.zeros((N, I), bool)
            for i, imgs in enumerate(node_image_sets):
                for ii in imgs:
                    node_images[i, ii] = True

            G = max(len(group_ids), 1)
            group_existing_count = np.zeros(G, np.int32)
            for g in exist_group[:e_real]:
                if g >= 0:
                    group_existing_count[g] += 1
            num_domains_val = len(domain_map)
            st = {
                "node_alloc": node_alloc,
                "node_requested": node_requested,
                "node_unsched": node_unsched,
                "node_taintset": node_taintset,
                "nl_keys": nl_keys,
                "nl_vals": nl_vals,
                "nl_num": nl_num,
                "node_valid": node_valid,
                "node_images": node_images,
                "pv_req_arr": pv_req_arr,
                "pv_class_arr": pv_class_arr,
                "pv_cap_arr": pv_cap_arr,
                "pv_avail_arr": pv_avail_arr,
                "exist_node": exist_node,
                "exist_prio": exist_prio,
                "exist_req": exist_req,
                "el_keys": el_keys,
                "el_vals": el_vals,
                "exist_ports": exist_ports_arr,
                "exist_anti": exist_anti,
                "exist_pref": exist_pref,
                "exist_pref_w": exist_pref_w,
                "exist_valid": exist_valid,
                "exist_pdb": exist_pdb,
                "exist_start": exist_start,
                "pdb_allowed": pdb_allowed,
                "node_used_ports": node_used_ports,
                "node_pods": node_pods,
                "node_domains": node_domains,
                "domain_key": domain_key,
                "domain_node_count": domain_node_count,
                "num_domains_val": num_domains_val,
                "ex_key": ex_key,
                "ex_op": ex_op,
                "ex_vals": ex_vals,
                "ex_num": ex_num,
                "rq_exprs": rq_exprs,
                "pf_exprs": pf_exprs,
                "pf_weight": pf_weight,
                "tl_key": tl_key,
                "tl_op": tl_op,
                "tl_val": tl_val,
                "tl_effect": tl_effect,
                "tl_valid": tl_valid,
                "ts_key": ts_key,
                "ts_val": ts_val,
                "ts_effect": ts_effect,
                "ts_valid": ts_valid,
                "sel_exprs": sel_exprs,
                "imgset_sizes": imgset_sizes,
                "group_existing_count": group_existing_count,
                # incremental existing-fold support (_try_fold_existing)
                "exist_group": exist_group,
                "exist_creation_abs": exist_creation_abs,
                "start_base": start_base,
                "e_real": e_real,
            }
            # strong refs keep cached id()s from being reused
            st["__refs"] = (list(nodes), [p for p, _ in existing],
                            list(pvs), list(pvcs), list(storage_classes),
                            list(pdbs))
            self._stable_key = stable_key
            self._stable = st

        # the device-carry regime key: the [P,N] static base + [S,P]
        # matched-pending depend on pod rows x node tables x volumes x
        # interning dims — NOT on the existing-pod set or PDBs (the one
        # existing coupling, NodePorts' used-port mask, is repaired by
        # dirty-marking port-bearing pending pods on every existing-fold).
        # Callers key CarryKeeper on THIS instead of _stable_key so a
        # bound-pod fold does not trigger a full carry rebuild.
        self._carry_key = (stable_key[0], stable_key[2]) + stable_key[4:]

        node_alloc = st["node_alloc"]
        node_requested = st["node_requested"]
        node_unsched = st["node_unsched"]
        node_taintset = st["node_taintset"]
        nl_keys = st["nl_keys"]
        nl_vals = st["nl_vals"]
        nl_num = st["nl_num"]
        node_valid = st["node_valid"]
        node_images = st["node_images"]
        pv_req_arr = st["pv_req_arr"]
        pv_class_arr = st["pv_class_arr"]
        pv_cap_arr = st["pv_cap_arr"]
        pv_avail_arr = st["pv_avail_arr"]
        exist_node = st["exist_node"]
        exist_prio = st["exist_prio"]
        exist_req = st["exist_req"]
        el_keys = st["el_keys"]
        el_vals = st["el_vals"]
        exist_ports_arr = st["exist_ports"]
        exist_anti = st["exist_anti"]
        exist_pref = st["exist_pref"]
        exist_pref_w = st["exist_pref_w"]
        exist_valid = st["exist_valid"]
        exist_pdb = st["exist_pdb"]
        exist_start = st["exist_start"]
        pdb_allowed = st["pdb_allowed"]
        node_used_ports = st["node_used_ports"]
        node_pods = st["node_pods"]
        node_domains = st["node_domains"]
        domain_key = st["domain_key"]
        domain_node_count = st["domain_node_count"]
        num_domains_val = st["num_domains_val"]
        ex_key = st["ex_key"]
        ex_op = st["ex_op"]
        ex_vals = st["ex_vals"]
        ex_num = st["ex_num"]
        rq_exprs = st["rq_exprs"]
        pf_exprs = st["pf_exprs"]
        pf_weight = st["pf_weight"]
        tl_key = st["tl_key"]
        tl_op = st["tl_op"]
        tl_val = st["tl_val"]
        tl_effect = st["tl_effect"]
        tl_valid = st["tl_valid"]
        ts_key = st["ts_key"]
        ts_val = st["ts_val"]
        ts_effect = st["ts_effect"]
        ts_valid = st["ts_valid"]
        sel_exprs = st["sel_exprs"]
        imgset_sizes = st["imgset_sizes"]
        group_existing_count = st["group_existing_count"]

        # group_min_member depends on the per-call pod_groups argument
        G = max(len(group_ids), 1)
        group_min_member = np.zeros(G, np.int32)
        for name, gi in group_ids.items():
            group_min_member[gi] = declared.get(name, 0)

        # ---- assemble pending-pod arrays (native strided scatters) ----
        pod_req = np.zeros((P, R), np.float32)
        pod_prio = np.zeros(P, np.int32)
        pod_node_name = np.full(P, -1, np.int32)
        pod_nominated = np.full(P, -1, np.int32)
        pod_req_id = np.full(P, -1, np.int32)
        pod_sel_req_id = np.full(P, -1, np.int32)
        pod_pref_id = np.full(P, -1, np.int32)
        pod_tolset = np.zeros(P, np.int32)
        pod_group_arr = np.full(P, -1, np.int32)
        pod_imageset = np.zeros(P, np.int32)
        pod_can_preempt = np.zeros(P, bool)
        pod_valid = np.zeros(P, bool)
        pod_valid[:p_real] = True

        pl_keys = np.full((P, MPL), -1, np.int32)
        pl_vals = np.full((P, MPL), -1, np.int32)

        MPorts = self._stick(
            "MPorts",
            _pad_dim(max([len(d["ports"]) for d in pend_rows] + [1]), 4),
        )
        pod_ports = np.full((P, MPorts), -1, np.int32)
        pod_port_ids = np.full((P, MPorts), -1, np.int32)
        port_ids_t = _InternTable()  # distinct (port, proto) among pending

        pod_aff_terms = np.full((P, MA, 2), -1, np.int32)
        pod_anti_terms = np.full((P, MA, 2), -1, np.int32)
        pod_pref_aff = np.full((P, MA, 2), -1, np.int32)
        pod_pref_aff_w = np.zeros((P, MA), np.float32)

        MC = self._stick(
            "MC",  # bucket 2 like MA (same per-slot-loop cost argument);
            # pad_mc pre-sizes like pad_ma above
            _pad_dim(
                max([len(d["tsc_skew"]) for d in pend_rows]
                    + [1, self.pad_mc or 0]), 2
            ),
        )
        pod_tsc = np.full((P, MC, 3), -1, np.int32)
        pod_tsc_skew = np.zeros((P, MC), np.int32)

        MVol = self._stick(
            "MVol",
            _pad_dim(max([len(d["vol_mode"]) for d in pend_rows] + [1]), 2),
        )
        pod_vol_mode = np.full((P, MVol), -1, np.int32)
        pod_vol_req = np.full((P, MVol), -1, np.int32)
        pod_vol_class = np.full((P, MVol), -1, np.int32)
        pod_vol_size = np.zeros((P, MVol), np.float32)


        native.scatter_rows(pod_req, [d["reqvec"] for d in pend_rows])
        native.fill_scalars(pod_prio, [d["prio"] for d in pend_rows])
        native.fill_scalars(pod_req_id, [d["req_id"] for d in pend_rows])
        native.fill_scalars(pod_pref_id, [d["pref_id"] for d in pend_rows])
        native.fill_scalars(
            pod_sel_req_id, [d["sel_req_id"] for d in pend_rows]
        )
        native.fill_scalars(pod_tolset, [d["tolset"] for d in pend_rows])
        native.fill_scalars(pod_group_arr, [d["gid"] for d in pend_rows])
        native.fill_scalars(pod_imageset, [d["imageset"] for d in pend_rows])
        native.fill_scalars(
            pod_can_preempt, [d["can_preempt"] for d in pend_rows]
        )
        native.scatter_rows(pl_keys, [d["lab_k"] for d in pend_rows])
        native.scatter_rows(pl_vals, [d["lab_v"] for d in pend_rows])
        native.scatter_rows(pod_ports, [d["ports"] for d in pend_rows])
        native.scatter_rows(
            pod_aff_terms.reshape(P, MA * 2), [d["aff"] for d in pend_rows]
        )
        native.scatter_rows(
            pod_anti_terms.reshape(P, MA * 2), [d["anti"] for d in pend_rows]
        )
        native.scatter_rows(
            pod_pref_aff.reshape(P, MA * 2), [d["pref"] for d in pend_rows]
        )
        native.scatter_rows(pod_pref_aff_w, [d["pref_w"] for d in pend_rows])
        native.scatter_rows(
            pod_tsc.reshape(P, MC * 3), [d["tsc"] for d in pend_rows]
        )
        native.scatter_rows(pod_tsc_skew, [d["tsc_skew"] for d in pend_rows])
        native.scatter_rows(pod_vol_mode, [d["vol_mode"] for d in pend_rows])
        native.scatter_rows(pod_vol_req, [d["vol_req"] for d in pend_rows])
        native.scatter_rows(pod_vol_class, [d["vol_cls"] for d in pend_rows])
        native.scatter_rows(pod_vol_size, [d["vol_size"] for d in pend_rows])
        # sparse per-pod residue: pinned/nominated nodes and the per-cycle
        # distinct-port interning (pods carrying those are rare)
        for i, (p, d) in enumerate(zip(pending, pend_rows)):
            if p.spec.node_name:
                pod_node_name[i] = node_index.get(p.spec.node_name, -2)
            if p.nominated_node_name:
                pod_nominated[i] = node_index.get(p.nominated_node_name, -1)
            if len(d["ports"]):
                for j, enc_port in enumerate(d["ports"]):
                    pod_port_ids[i, j] = port_ids_t.intern(int(enc_port))

        # Pod ordering rank via the profile's queueSort plugin (default
        # PrioritySort: priority desc, creation ts asc, index).
        pod_order = np.full(P, np.iinfo(np.int32).max, np.int32)
        if p_real:
            creation = np.array(
                [d["creation"] for d in pend_rows], np.float64
            )
            pod_order[:p_real] = self.queue_sort.rank(
                pending, pod_prio[:p_real], creation
            )

        snap = ClusterSnapshot(
            resource_names=tuple(rn),
            num_nodes=np.asarray(n_real, np.int32),
            num_pending=np.asarray(p_real, np.int32),
            num_existing=np.asarray(e_real, np.int32),
            num_domains=np.asarray(num_domains_val, np.int32),
            cycle_index=np.asarray(self._cycle_index, np.int32),
            topology_keys=tuple(topo_keys),
            node_allocatable=node_alloc,
            node_requested=node_requested,
            node_unschedulable=node_unsched,
            node_taintset=node_taintset,
            node_label_keys=nl_keys,
            node_label_vals=nl_vals,
            node_label_num=nl_num,
            node_domains=node_domains,
            node_images=node_images,
            node_used_ports=node_used_ports,
            node_valid=node_valid,
            ex_key=ex_key,
            ex_op=ex_op,
            ex_vals=ex_vals,
            ex_num=ex_num,
            rq_exprs=rq_exprs,
            pf_exprs=pf_exprs,
            pf_weight=pf_weight,
            tl_key=tl_key,
            tl_op=tl_op,
            tl_val=tl_val,
            tl_effect=tl_effect,
            tl_valid=tl_valid,
            ts_key=ts_key,
            ts_val=ts_val,
            ts_effect=ts_effect,
            ts_valid=ts_valid,
            sel_exprs=sel_exprs,
            pod_requested=pod_req,
            pod_priority=pod_prio,
            pod_order=pod_order,
            pod_node_name=pod_node_name,
            pod_nominated=pod_nominated,
            pod_req_id=pod_req_id,
            pod_sel_req_id=pod_sel_req_id,
            pod_pref_id=pod_pref_id,
            pod_tolset=pod_tolset,
            pod_label_keys=pl_keys,
            pod_label_vals=pl_vals,
            pod_ports=pod_ports,
            pod_port_ids=pod_port_ids,
            num_distinct_ports=self._stick(
                "Q", _pad_dim(len(port_ids_t), 4)
            ),
            has_inter_pod_affinity=self._stick_flag(
                "aff",
                bool(
                    (pod_aff_terms >= 0).any()
                    or (pod_anti_terms >= 0).any()
                    or (pod_pref_aff >= 0).any()
                    or (exist_anti >= 0).any()
                    or (exist_pref >= 0).any()
                ),
            ),
            has_topology_spread=self._stick_flag(
                "tsc", bool((pod_tsc >= 0).any())
            ),
            has_volumes=self._stick_flag(
                "vol", bool((pod_vol_mode >= 0).any())
            ),
            has_multi_volume=self._stick_flag(
                "mvol",
                bool(((pod_vol_mode >= 0).sum(axis=1) >= 2).any()),
            ),
            pod_vol_mode=pod_vol_mode,
            pod_vol_req=pod_vol_req,
            pod_vol_class=pod_vol_class,
            pod_vol_size=pod_vol_size,
            pv_req_id=pv_req_arr,
            pv_class=pv_class_arr,
            pv_capacity=pv_cap_arr,
            pv_avail=pv_avail_arr,
            pod_aff_terms=pod_aff_terms,
            pod_anti_terms=pod_anti_terms,
            pod_pref_aff=pod_pref_aff,
            pod_pref_aff_w=pod_pref_aff_w,
            pod_tsc=pod_tsc,
            pod_tsc_skew=pod_tsc_skew,
            pod_group=pod_group_arr,
            pod_imageset=pod_imageset,
            pod_can_preempt=pod_can_preempt,
            pod_valid=pod_valid,
            group_min_member=group_min_member,
            group_existing_count=group_existing_count,
            imgset_sizes=imgset_sizes,
            exist_node=exist_node,
            exist_priority=exist_prio,
            exist_start=exist_start,
            exist_pdb=exist_pdb,
            exist_requested=exist_req,
            pdb_allowed=pdb_allowed,
            exist_label_keys=el_keys,
            exist_label_vals=el_vals,
            exist_ports=exist_ports_arr,
            exist_anti_terms=exist_anti,
            exist_pref_aff=exist_pref,
            exist_pref_aff_w=exist_pref_w,
            exist_valid=exist_valid,
            node_pods=node_pods,
            domain_key=domain_key,
            domain_node_count=domain_node_count,
        )

        # ---- stash everything the delta fast path (encode_packed) needs.
        # The stashed pod_rowdata CLOSURE stays valid exactly while the
        # stable side is unchanged: it captures node_index / the volume
        # maps / vol_epoch, all of which are covered by the delta
        # precheck's object-identity comparisons plus _table_lens.
        creation_full = np.zeros(P, np.float64)
        if p_real:
            creation_full[:p_real] = [d["creation"] for d in pend_rows]
        self._delta_state = {
            "pod_rowdata": pod_rowdata,
            "node_index": node_index,
            "pend_ids": [id(p) for p in pending],
            "pend_refs": list(pending),
            "pend_rows": list(pend_rows),
            # slots whose row carries host ports, maintained
            # incrementally: the delta path's port re-interning only
            # walks THESE instead of scanning all P slots per encode
            "port_set": {
                i for i, d in enumerate(pend_rows) if len(d["ports"])
            },
            "creation": creation_full,
            "p_real": p_real,
            "dims": {"R": R, "MPL": MPL, "MA": MA, "MPorts": MPorts,
                     "MC": MC, "MVol": MVol,
                     "Q": snap.num_distinct_ports},
            "pads": (self.pad_pods, self.pad_nodes, P),
            # stable-side argument identity: the fast path first compares
            # LIST identity (0-cost; the contract is that callers keep one
            # list per stable side and replace it on change), and falls
            # back to element-identity tuples when the list was rebuilt.
            # The lists themselves are pinned (`id_lists`, `exist_list`):
            # a caller that builds a fresh list every cycle and drops it
            # (cache.existing_pods()) may get the freed one's address
            # back, and with an equal length (a resident set held at a
            # target) a recycled id() would read as "unchanged"
            "id_lists": (nodes, pvcs, pvs, storage_classes, pdbs),
            "exist_list": existing,
            "nodes_ids": (id(nodes), len(nodes)),
            "nodes_elems": tuple(id(nd) for nd in nodes),
            "exist_ids": (id(existing), len(existing)),
            "exist_elems": tuple((id(p), nm) for p, nm in existing),
            "vol_ids": (id(pvcs), len(pvcs), id(pvs), len(pvs),
                        id(storage_classes), len(storage_classes)),
            "vol_elems": (tuple(id(c) for c in pvcs),
                          tuple(id(v) for v in pvs),
                          tuple(id(s) for s in storage_classes)),
            "pdb_ids": (id(pdbs), len(pdbs)),
            "pdb_elems": (tuple(id(b) for b in pdbs),
                          tuple(b.disruptions_allowed for b in pdbs)),
            "flags": (snap.has_inter_pod_affinity, snap.has_topology_spread,
                      snap.has_volumes, snap.has_multi_volume),
        }
        # a direct encode() call leaves the arena holding the PREVIOUS
        # snapshot's bytes; mark it stale so the next encode_packed takes
        # the full path (_install_arena rewrites everything and re-syncs)
        self._arena_synced = False
        return snap


    # ------------------------------------------------------------------
    # Packed-arena encode: the steady-serving fast path.
    #
    # encode() rebuilds every pending-side array and repacks ~8MB per
    # cycle even when 80% of the pending set carried over — measured
    # 150-180ms at 10k pods with ZERO churn. encode_packed keeps the
    # packed (wbuf, bbuf) pair as a PERSISTENT ARENA whose per-field
    # numpy views alias the buffers, and rewrites only the rows whose pod
    # object changed. The stable side (nodes / existing pods / volumes /
    # PDBs) is covered by object-identity prechecks; any miss falls back
    # to the full encode, which reinstalls the arena.
    #
    # CONTRACT for delta hits: callers keep ONE list object per stable
    # side and replace the list (not mutate it in place) when membership
    # changes; pod objects are immutable once handed to the encoder,
    # except `nominated_node_name`, whose in-place mutation must be
    # reported via `mutated_ids` (id(pod) set).
    # ------------------------------------------------------------------

    # (field name, rowdata key, pad value) for pending-side 2-D arrays
    _PEND_2D = (
        ("pod_requested", "reqvec", 0.0),
        ("pod_label_keys", "lab_k", -1),
        ("pod_label_vals", "lab_v", -1),
        ("pod_ports", "ports", -1),
        ("pod_pref_aff_w", "pref_w", 0.0),
        ("pod_tsc_skew", "tsc_skew", 0),
        ("pod_vol_mode", "vol_mode", -1),
        ("pod_vol_req", "vol_req", -1),
        ("pod_vol_class", "vol_cls", -1),
        ("pod_vol_size", "vol_size", 0.0),
    )
    # pending-side 3-D arrays, written through a [P, -1] reshaped view
    _PEND_3D = (
        ("pod_aff_terms", "aff", -1),
        ("pod_anti_terms", "anti", -1),
        ("pod_pref_aff", "pref", -1),
        ("pod_tsc", "tsc", -1),
    )
    _PEND_SCALAR = (
        ("pod_priority", "prio"),
        ("pod_req_id", "req_id"),
        ("pod_pref_id", "pref_id"),
        ("pod_sel_req_id", "sel_req_id"),
        ("pod_tolset", "tolset"),
        ("pod_group", "gid"),
        ("pod_imageset", "imageset"),
        ("pod_can_preempt", "can_preempt"),
    )
    # pad value per scalar field (matches the full path's array initials)
    _PEND_SCALAR_PAD = {
        "pod_priority": 0, "pod_req_id": -1, "pod_pref_id": -1,
        "pod_sel_req_id": -1, "pod_tolset": 0, "pod_group": -1,
        "pod_imageset": 0, "pod_can_preempt": False,
        "pod_node_name": -1, "pod_nominated": -1,
    }

    def _apply_specs(self, ds) -> list:
        """The (view, key, pad, mode) spec list for the delta arena's
        pending-side fields — built once per arena; shared by apply_rows
        (dict path) and pod_rows_into (fused path)."""
        specs = ds.get("apply_specs")
        if specs is None:
            A = self._arena
            P = ds["pads"][2]
            specs = (
                [(A[n], k, p, 0) for n, k, p in self._PEND_2D]
                + [(A[n].reshape(P, -1), k, p, 0)
                   for n, k, p in self._PEND_3D]
                + [(A[n], k, self._PEND_SCALAR_PAD[n], 1)
                   for n, k in self._PEND_SCALAR]
            )
            ds["apply_specs"] = specs
        return specs

    def _clear_slots(self, sl) -> None:
        """Reset pending-side arena rows to the full path's pad values —
        applied to slots that stop being backed by a pod (pending-set
        shrink), so a delta arena is byte-identical to a full encode."""
        A = self._arena
        for name, _key, pad in self._PEND_2D:
            A[name][sl] = pad
        for name, _key, pad in self._PEND_3D:
            A[name][sl] = pad
        for name, pad in self._PEND_SCALAR_PAD.items():
            A[name][sl] = pad

    def encode_packed(
        self,
        nodes: Sequence[Node],
        pending: Sequence[Pod],
        existing: Sequence[tuple[Pod, str]] = (),
        pod_groups: Sequence[api.PodGroup] = (),
        pvcs: Sequence[api.PersistentVolumeClaim] = (),
        pvs: Sequence[api.PersistentVolume] = (),
        storage_classes: Sequence[api.StorageClass] = (),
        pdbs: Sequence[api.PodDisruptionBudget] = (),
        mutated_ids: frozenset | set = frozenset(),
    ):
        """Encode + pack in one step: returns an EncodedFrame whose
        wbuf/bbuf are the persistent arena buffers (valid until the NEXT
        encode call). Consumers must have FETCHED an in-flight program's
        outputs before the next encode rewrites the arena: jax's CPU
        backend copies a jit's numpy arguments asynchronously on the
        dispatch thread, so a rewrite racing a dispatch can tear the
        copy (reproduced with a 15-line pure-jax loop). The serving
        pipeline provides exactly this ordering — dispatch k+1 is
        refused until cycle k's decisions were fetched
        (ServingPipeline.dispatch). `snap` is a ClusterSnapshot whose
        array fields are views into the buffers, and `dirty` names the
        rewritten pod slots (None = full rebuild)."""
        ds = self._delta_state
        if ds is not None and self._arena_spec is not None:
            ok = self._delta_precheck(
                ds, nodes, existing, pvcs, pvs, storage_classes, pdbs
            )
            if not ok and self._stable_except_existing_ok(
                ds, nodes, pvcs, pvs, storage_classes, pdbs
            ):
                # ONLY the existing set changed — the per-cycle event of
                # real serving (bindings fold in at the tail; pods that
                # finished leave from anywhere in the list). Try the
                # incremental stable fold: it compacts the rows over the
                # holes and appends the tail, and answers False for a row
                # over the sticky dims, a grown interning table or a list
                # of which more changed than stayed — not for a removal
                # in the middle, nor for a bound pod the native parser
                # does not cover.
                import time as _time

                _ft = _time.perf_counter()
                ok = self._try_fold_existing(ds, existing)
                if ok:
                    self._fold_ms = (_time.perf_counter() - _ft) * 1e3
            if ok:
                out = self._encode_delta(ds, pending, pod_groups, mutated_ids)
                if out is not None:
                    self.delta_hits += 1
                    return out
        self.full_encodes += 1
        # a bailed delta leaves partial segment marks behind; an empty
        # profile is the "this encode took the full path" signal
        self.delta_profile = {}
        self.last_changed_slots = None  # full path: everything changed
        snap = self.encode(
            nodes, pending, existing, pod_groups, pvcs, pvs,
            storage_classes, pdbs,
        )
        return self._install_arena(snap)

    def _delta_precheck(
        self, ds, nodes, existing, pvcs, pvs, storage_classes, pdbs
    ) -> bool:
        if not self._stable_except_existing_ok(
            ds, nodes, pvcs, pvs, storage_classes, pdbs
        ):
            return False
        if ds["exist_ids"] != (id(existing), len(existing)):
            new = tuple((id(p), nm) for p, nm in existing)
            if new != ds["exist_elems"]:
                # stash for _try_fold_existing so the fold does not
                # rebuild the same O(E) tuple a second time
                self._exist_probe = (id(existing), new)
                return False
        return True

    def _stable_except_existing_ok(
        self, ds, nodes, pvcs, pvs, storage_classes, pdbs
    ) -> bool:
        if not getattr(self, "_arena_synced", False):
            return False  # a direct encode() superseded the arena contents
        if ds["pads"][:2] != (self.pad_pods, self.pad_nodes):
            return False
        if ds["nodes_ids"] != (id(nodes), len(nodes)):
            if tuple(id(nd) for nd in nodes) != ds["nodes_elems"]:
                return False
        if ds["vol_ids"] != (
            id(pvcs), len(pvcs), id(pvs), len(pvs),
            id(storage_classes), len(storage_classes),
        ):
            if ds["vol_elems"] != (
                tuple(id(c) for c in pvcs),
                tuple(id(v) for v in pvs),
                tuple(id(s) for s in storage_classes),
            ):
                return False
        # PDB disruptionsAllowed is status (may be refreshed in place on
        # the same object), so values are compared every cycle
        pdb_vals = tuple(b.disruptions_allowed for b in pdbs)
        if ds["pdb_ids"] != (id(pdbs), len(pdbs)):
            if tuple(id(b) for b in pdbs) != ds["pdb_elems"][0]:
                return False
        if pdb_vals != ds["pdb_elems"][1]:
            return False
        return True

    def _try_fold_existing(self, ds, existing) -> bool:
        """Incremental existing-set fold (SURVEY §4 realism; VERDICT r4
        item 3): bring the cached stable side up to date IN PLACE when
        the new existing list is an order-preserving SUBSEQUENCE of the
        old one followed by an APPENDED TAIL — pods left from anywhere
        in the list (completions come in no particular order) and pods
        bound since the last cycle arrived at its end, both in one fold.
        The surviving rows are compacted over the holes (one vectorised
        move per per-slot array, no per-pod Python), the vacated slots
        get the full path's pad values back, and the tail is appended
        behind them. A pure append is the fold with no hole and a pure
        tail removal the fold with no tail: the same code, with nothing
        to move. A reordered list reads as removals plus appends of the
        same pods, and is as exact. Slot ORDER is kept because it is
        semantic (the victim table breaks priority ties by slot,
        node_requested is an f32 sum in ascending slot order).

        Anything else — node/volume/PDB changes, an interning table that
        grew, a row wider than the sticky dims (MPL / MA / the
        existing-pod port width / R), affinity terms while flag_aff is
        off, the E pad exhausted, a node outgrowing its used-port or
        victim-table width, more than 256 nodes touched by port-bearing
        pods, or a list with holes of which more changed than stayed —
        returns False and the caller takes the full encode (which
        rebuilds the stable cache from scratch, so partial st mutations
        on a failed fold are discarded wholesale along with the stale
        _stable_key).

        An appended pod the native row writer does not cover (volumes /
        nodeAffinity / exotic operators) does NOT fail the fold: its row
        comes from the Python row builder and is written through
        apply_rows, per pod, under the same guards as the native rows
        (`fold_fallback_pods` counts them). `fold_removed_pods` counts
        the rows that left, `fold_declined` the calls that answered
        False because more changed than stayed (and for no other reason).

        Exactness contract: after a successful fold, every st array is
        byte-identical to what a from-scratch assembly over the new
        existing list would produce (the packed-encoder differential
        tests drive exactly this equivalence), and _stable_key is updated
        so a later full encode with the same inputs REUSES the folded st.
        The device carry stays valid (keyed on _carry_key, which excludes
        the existing set); the one static coupling — NodePorts' used-port
        mask — is repaired by marking every port-bearing pending slot
        dirty, which the carry-update program then recomputes."""
        from .. import native

        if native.pod_rows_into is None:
            return False
        st = getattr(self, "_stable", None)
        if st is None or "exist_creation_abs" not in st:
            return False
        old = ds["exist_elems"]
        probe = getattr(self, "_exist_probe", None)
        if probe is not None and probe[0] == id(existing):
            new = probe[1]
            self._exist_probe = None
        else:
            new = tuple((id(p), nm) for p, nm in existing)
        if new == old:  # same elements, rebuilt list object
            ds["exist_ids"] = (id(existing), len(existing))
            ds["exist_list"] = existing
            return True
        n_old, n_new = len(old), len(new)
        exist_req = st["exist_req"]
        E = exist_req.shape[0]
        if n_new > E:
            return False  # E pad exhausted: full path grows the regime

        # ---- diff once: `keep` = the old slots that survive, ascending
        # (the longest prefix of `new` that is a subsequence of `old`);
        # what follows it in `new` is the appended tail. The old list's
        # pods are still pinned by st["__refs"][1], so an equal id() is
        # the same pod ----
        first = n_kept = min(n_old, n_new)
        if new[:first] == old[:first]:
            gone = np.arange(n_kept, n_old)  # no hole: nothing moves
        else:
            walk = []
            j, want = 0, new[0]
            for i, e in enumerate(old):
                if e == want:
                    walk.append(i)
                    j += 1
                    want = new[j] if j < n_new else None
            n_kept = j
            if n_old + n_new - 2 * n_kept > n_kept:
                self.fold_declined += 1
                return False  # more changed than stayed: full is cheaper
            keep = np.asarray(walk, np.int64)
            left = np.ones(n_old, bool)
            left[keep] = False
            gone = np.flatnonzero(left)
            # the first hole: every kept row behind it moves up
            first = min(int(gone[0]), n_kept)

        dims = ds["dims"]
        exist_node = st["exist_node"]
        exist_ports = st["exist_ports"]
        exist_group = st["exist_group"]
        ca = st["exist_creation_abs"]
        nr = st["node_requested"]
        # nodes whose victim-table row is stale: those that lose or gain
        # a pod and, node_pods holding SLOT numbers, every node with a
        # pod at or after the first hole
        stale = np.zeros(nr.shape[0], bool)
        port_nodes: set[int] = set()
        n_fallback = 0  # appended rows built in Python (counted on commit)

        if gone.size:  # ---- removal: compact over the holes ----
            en = exist_node[gone]
            lost = en[en >= 0]
            g = exist_group[gone]
            np.subtract.at(st["group_existing_count"], g[g >= 0], 1)
            port_nodes.update(
                int(n) for n in en[(exist_ports[gone, 0] >= 0) & (en >= 0)]
            )
            for name, pad, _arena in _EXIST_SLOT_ARRAYS:
                a = st[name]
                if first < n_kept:
                    a[first:n_kept] = a[keep[first:]]
                # restore full-path pad values so the arena stays
                # byte-identical to a fresh assembly
                a[n_kept:n_old] = pad
            # node_requested: f32 subtract is NOT the exact inverse of
            # the full path's slot-ascending add accumulation — recompute
            # the sums of the nodes that lost a pod from their remaining
            # member rows in the same ascending-slot order, so the result
            # stays bitwise equal to a from-scratch assembly
            if lost.size:
                stale[lost] = True
                nr[stale] = 0.0
                en_k = exist_node[:n_kept]
                mem = np.flatnonzero(stale[en_k] & (en_k >= 0))
                if mem.size:
                    np.add.at(nr, en_k[mem], exist_req[mem])

        L = n_kept
        if n_new > L:  # ---- append behind the kept rows ----
            slots = np.arange(L, n_new, dtype=np.int64)
            app = existing[L:]
            specs = ds.get("exist_specs")
            if specs is None or specs[0][0] is not exist_req:
                specs = [
                    (exist_req, "reqvec", 0.0, 0),
                    (st["el_keys"], "lab_k", -1, 0),
                    (st["el_vals"], "lab_v", -1, 0),
                    (exist_ports, "ports", -1, 0),
                    (st["exist_anti"].reshape(E, -1), "anti", -1, 0),
                    (st["exist_pref"].reshape(E, -1), "pref", -1, 0),
                    (st["exist_pref_w"], "pref_w", 0.0, 0),
                    (st["exist_prio"], "prio", 0, 1),
                    (exist_group, "gid", 0, 1),
                    (ca, "creation", 0.0, 1),
                ]
                ds["exist_specs"] = specs
            flag_aff, flag_tsc, _fv, _fm = ds["flags"]
            limits = {
                "MPL": dims["MPL"], "MA": dims["MA"],
                # MEP (existing-pod port width), not the pending MPorts
                "MPorts": exist_ports.shape[1],
                "MC": 1 << 30,  # exist rows carry no tsc columns
                "R": dims["R"],
                "flag_aff": int(flag_aff),
                # spread counts come from labels, not the existing pod's
                # own constraints — tsc-bearing bound pods are fine
                "flag_tsc": 1,
            }
            lens0 = self._table_lens()
            guard_ok, res = native.pod_rows_into(
                [p for p, _ in app], self._native_ctx(), slots, specs,
                limits,
            )
            if not guard_ok:
                return False  # a native row overflowed the sticky dims
            # per-pod fallback, as _encode_delta's fb_slots: a pod the
            # native writer does not cover (volumes / nodeAffinity /
            # exotic operators) gets its row from the Python builder (a
            # _pod_cache hit when it was pending the cycle before) and
            # only the fold's own guards decide about the full path
            fb = [j for j, r in enumerate(res) if r is None]
            fb_rows = [ds["pod_rowdata"](app[j][0]) for j in fb]
            for d in fb_rows:
                if (
                    len(d["lab_k"]) > limits["MPL"]
                    or d["n_aff"] > limits["MA"]
                    or len(d["ports"]) > limits["MPorts"]
                    or len(d["reqvec"]) > limits["R"]
                    or (not flag_aff and d["n_aff"] > 0)
                ):
                    return False  # same guards as the native rows
            if self._table_lens() != lens0:
                return False  # interning grew: finalize tables stale
            if fb:
                idx = slots[fb]
                # apply_rows writes no f64 column: creation goes beside
                # it, as on the pending side
                native.apply_rows(specs[:-1], idx, fb_rows)
                ca[idx] = [d["creation"] for d in fb_rows]
                n_fallback = len(fb)
            nidx = ds["node_index"]
            en_new = np.array(
                [nidx.get(nm, -1) for _, nm in app], np.int32
            )
            exist_node[slots] = en_new
            st["exist_valid"][slots] = True
            m = en_new >= 0
            # on top of the kept rows' sums: the ascending-slot order
            # of a from-scratch assembly
            np.add.at(nr, en_new[m], exist_req[slots][m])
            g = exist_group[slots]
            np.add.at(st["group_existing_count"], g[g >= 0], 1)
            port_nodes.update(
                int(n) for n in en_new[(exist_ports[slots, 0] >= 0) & m]
            )
            pdbs = st["__refs"][5]
            if pdbs:
                MB = st["exist_pdb"].shape[1]
                for j, (p, _nm) in enumerate(app):
                    b = 0
                    row = st["exist_pdb"][L + j]
                    for gi, pdb in enumerate(pdbs):
                        if b >= MB:
                            break
                        if _pdb_matches(pdb, p):
                            row[b] = gi
                            b += 1

        # ---- used-port lists of affected nodes (rebuilt exactly as the
        # full path builds them: member slots ascending, ports in row
        # order) ----
        if port_nodes:
            if len(port_nodes) > 256:
                return False  # pathological: cheaper as a full encode
            nup = st["node_used_ports"]
            used = _used_ports_by_node(
                exist_node, exist_ports, n_new,
                only=np.fromiter(port_nodes, np.int64),
            )
            for n in port_nodes:
                u = used.get(n, ())
                if len(u) > nup.shape[1]:
                    return False
                nup[n] = -1
                nup[n, : len(u)] = u

        # ---- victim table rows of the stale nodes (the full path's own
        # sort, restricted to those nodes' rows) ----
        en_all = exist_node[:n_new]
        moved = en_all[first:]
        stale[moved[moved >= 0]] = True
        if stale.any():
            npods = st["node_pods"]
            npods[stale] = -1
            e_ids = np.flatnonzero(stale[en_all] & (en_all >= 0))
            if e_ids.size:
                sn, col, se = _victim_table(en_all, st["exist_prio"], e_ids)
                if int(col.max()) >= npods.shape[1]:
                    return False  # a node outgrew the victim-table width
                npods[sn, col] = se

        # ---- start times: re-base exactly when the oldest pod changed
        # (full assembly computes base = min over the live set): an
        # older pod arrived, or the oldest one left ----
        newbase = float(ca[:n_new].min()) if n_new else 0.0
        if newbase != st["start_base"]:
            st["exist_start"][:n_new] = (
                ca[:n_new] - newbase
            ).astype(np.float32)
            st["start_base"] = newbase
        elif n_new > L:
            st["exist_start"][L:n_new] = (
                ca[L:n_new] - newbase
            ).astype(np.float32)
        st["e_real"] = n_new

        # ---- mirror into the packed arena: the per-slot rows from the
        # first hole on, the aggregates whole ----
        A = self._arena
        rng = slice(first, max(n_old, n_new))
        for st_name, _pad, arena_name in _EXIST_SLOT_ARRAYS:
            if arena_name is not None:
                A[arena_name][rng] = st[st_name][rng]
        A["exist_start"][:] = st["exist_start"]
        A["node_requested"][:] = nr
        A["node_pods"][:] = st["node_pods"]
        A["node_used_ports"][:] = st["node_used_ports"]
        A["group_existing_count"][:] = st["group_existing_count"]
        A["num_existing"][...] = n_new

        # ---- commit identity bookkeeping ----
        refs = st["__refs"]
        st["__refs"] = (
            refs[0], [p for p, _ in existing], refs[2], refs[3], refs[4],
            refs[5],
        )
        k = self._stable_key
        self._stable_key = (k[0], new) + k[2:]
        ds["exist_ids"] = (id(existing), len(existing))
        ds["exist_list"] = existing
        ds["exist_elems"] = new
        # NodePorts static rows read node_used_ports: when the fold
        # actually touched a used-port list, recompute the carry rows of
        # every port-bearing pending slot this cycle
        if port_nodes:
            ds["fold_port_dirty"] = True
        self.fold_hits += 1
        self.fold_fallback_pods += n_fallback
        self.fold_removed_pods += int(gone.size)
        return True

    def _encode_delta(self, ds, pending, pod_groups, mutated_ids):
        """The fast path: rewrite only changed pod slots in the arena.
        Returns None to request a full encode (any partial bookkeeping it
        did is simply superseded — the full path rebuilds everything).

        `self.delta_profile` records per-segment milliseconds of the last
        delta encode (detect/rows/ports/apply/order) — the encode-budget
        attribution tool (scripts/profile_encode4.py)."""
        import time as _time

        from .. import native

        _t0 = _time.perf_counter()
        _prof = self.delta_profile = {}
        fold_ms = getattr(self, "_fold_ms", None)
        if fold_ms is not None:
            _prof["fold"] = fold_ms
            self._fold_ms = None

        def _mark(name):
            nonlocal _t0
            t = _time.perf_counter()
            _prof[name] = _prof.get(name, 0.0) + (t - _t0) * 1e3
            _t0 = t

        dims = ds["dims"]
        P = ds["pads"][2]
        p_real = len(pending)
        if p_real > P:
            return None
        ids = ds["pend_ids"]
        rows = ds["pend_rows"]
        refs = ds["pend_refs"]
        n_prev = len(ids)
        if n_prev < p_real:
            ids += [0] * (p_real - n_prev)
            rows += [None] * (p_real - n_prev)
            refs += [None] * (p_real - n_prev)
        dirty = [
            i for i in range(p_real)
            if ids[i] != id(pending[i]) or ids[i] in mutated_ids
        ]
        # consumers that track POD-CONTENT changes (the extender-verdict
        # carry) read this instead of the returned dirty set, which may
        # be inflated by the port-repair slots below
        self.last_changed_slots = np.asarray(dirty, np.int32)
        if ds.pop("fold_port_dirty", False):
            # an existing-fold changed node_used_ports; NodePorts static
            # rows of port-bearing pending pods must reach the carry
            # update, so their slots join the dirty set (their arena
            # rewrite is a byte-identical no-op)
            extra = [i for i in ds["port_set"] if i < p_real]
            if extra:
                dirty = sorted(set(dirty) | set(extra))
        _mark("detect")
        rowdata = ds["pod_rowdata"]
        lens0 = self._table_lens()
        flag_aff, flag_tsc, flag_vol, flag_mvol = ds["flags"]
        new_rows = []  # dict-interchange rows (fallback pods only)
        fb_slots = []  # their arena slots
        port_set = ds["port_set"]
        creation = ds["creation"]
        fused = native.pod_rows_into
        fused_res = None
        if fused is not None and dirty:
            # fused fast path (PERF.md round-5): ONE native call parses
            # every dirty pod and writes its arena row + creation column
            # directly — no 26-key rowdata dict, no apply_rows re-read.
            # Pods the native parser does not cover (volumes /
            # nodeAffinity / exotic operators) come back as None and take
            # the dict path below; a guard_ok=False return means a pod
            # overflowed the arena dims, so the whole delta bails to the
            # full encode (partially written rows are rebuilt there).
            specs2 = ds.get("into_specs")
            if specs2 is None:
                specs2 = self._apply_specs(ds) + [
                    (creation, "creation", 0.0, 1)
                ]
                ds["into_specs"] = specs2
            limits = ds.get("into_limits")
            if limits is None:
                limits = {
                    "MPL": dims["MPL"], "MA": dims["MA"],
                    "MPorts": dims["MPorts"], "MC": dims["MC"],
                    "R": dims["R"], "flag_aff": int(flag_aff),
                    "flag_tsc": int(flag_tsc),
                }
                ds["into_limits"] = limits
            guard_ok, fused_res = fused(
                [pending[i] for i in dirty], self._native_ctx(),
                np.asarray(dirty, np.int64), specs2, limits,
            )
            if not guard_ok:
                return None  # arena dims too small: full re-encode
        for j, i in enumerate(dirty):
            p = pending[i]
            ids[i] = id(p)
            refs[i] = p
            r = fused_res[j] if fused_res is not None else None
            if r is None:  # no native builder, or pod needs dict path
                d = rowdata(p)
                new_rows.append(d)
                fb_slots.append(i)
                rows[i] = d
                r = d["ports"]
            else:
                # only "ports" is ever read back from delta rows
                rows[i] = {"ports": r}
            if len(r):
                port_set.add(i)
            else:
                port_set.discard(i)
        _mark("rows")
        if self._table_lens() != lens0:
            return None  # interning grew: stable tables need new entries
        for d in new_rows:
            if (
                len(d["lab_k"]) > dims["MPL"]
                or d["n_aff"] > dims["MA"]
                or len(d["ports"]) > dims["MPorts"]
                or len(d["tsc_skew"]) > dims["MC"]
                or len(d["vol_mode"]) > dims["MVol"]
                or len(d["reqvec"]) > dims["R"]
            ):
                return None
            if not flag_aff and d["n_aff"] > 0:
                return None
            if not flag_tsc and len(d["tsc_skew"]) > 0:
                return None
            if not flag_vol and len(d["vol_mode"]) > 0:
                return None
            if not flag_mvol and len(d["vol_mode"]) >= 2:
                # a first multi-PVC pod flips the joint-admission
                # capability: full path recompiles with the flag on
                return None
        # distinct-port axis: re-intern over every slot that has ports
        # (matches the full path's slot-order interning exactly); the
        # slot set is maintained incrementally, sorted here so interning
        # order equals the full path's slot order
        port_slots = sorted(i for i in port_set if i < p_real)
        port_tab: dict[int, int] = {}
        port_id_rows = []
        for i in port_slots:
            pr = []
            for ep in rows[i]["ports"]:
                ep = int(ep)
                j = port_tab.get(ep)
                if j is None:
                    j = len(port_tab)
                    port_tab[ep] = j
                pr.append(j)
            port_id_rows.append(np.array(pr, np.int32))
        if _pad_dim(len(port_tab), 4) > dims["Q"]:
            return None
        _mark("ports")

        # ---- all checks passed: write the arena ----
        # fused-path rows are already in place; only fallback dict rows
        # need the batched apply + creation write here
        A = self._arena
        if fb_slots:
            idx = np.asarray(fb_slots, np.int64)
            native.apply_rows(self._apply_specs(ds), idx, new_rows)
            creation[idx] = [d["creation"] for d in new_rows]
        if dirty:
            idx = np.asarray(dirty, np.int64)
            nidx = ds["node_index"]
            A["pod_node_name"][idx] = [
                nidx.get(pending[i].spec.node_name, -2)
                if pending[i].spec.node_name else -1
                for i in dirty
            ]
            A["pod_nominated"][idx] = [
                nidx.get(pending[i].nominated_node_name, -1)
                if pending[i].nominated_node_name else -1
                for i in dirty
            ]

        _mark("apply")
        if p_real != ds["p_real"]:
            pv = A["pod_valid"]
            pv[:] = False
            pv[:p_real] = True
            if p_real < ds["p_real"]:
                self._clear_slots(slice(p_real, ds["p_real"]))
                creation[p_real:ds["p_real"]] = 0.0
                for i in range(p_real, ds["p_real"]):
                    port_set.discard(i)
            del ids[p_real:]
            del rows[p_real:]
            del refs[p_real:]
            ds["p_real"] = p_real
            A["num_pending"][...] = p_real

        ppi = A["pod_port_ids"]
        ppi[:] = -1
        if port_slots:
            native.scatter_rows_at(
                ppi, np.asarray(port_slots, np.int64), port_id_rows
            )

        prio = A["pod_priority"]
        po = A["pod_order"]
        po[:] = np.iinfo(np.int32).max
        if p_real:
            po[:p_real] = self.queue_sort.rank(
                pending, prio[:p_real], creation[:p_real]
            )

        gm = A["group_min_member"]
        gm[:] = 0
        if pod_groups or self._group_ids:
            declared = {g.name: g.min_member for g in pod_groups}
            if declared:
                for name, gi in self._group_ids.items():
                    mm = declared.get(name)
                    if mm:
                        gm[gi] = mm

        _mark("order")
        self._cycle_index += 1
        A["cycle_index"][...] = self._cycle_index
        return EncodedFrame(
            self._arena_w, self._arena_b, self._arena_spec,
            self._arena_snap, np.asarray(dirty, np.int32),
        )

    def _install_arena(self, snap: ClusterSnapshot):
        """(Re)build the persistent packed arena from a fully-encoded
        snapshot and return (wbuf, bbuf, spec, view_snapshot)."""
        from . import packing

        spec = packing.make_spec(snap)
        reuse = (
            self._arena_spec is not None
            and spec.key() == self._arena_spec.key()
        )
        if not reuse:
            wbuf = np.empty(spec.n_words, np.uint32)
            bbuf = np.zeros(spec.n_bytes, np.uint8)
            views: dict[str, np.ndarray] = {}
            for name, dt, shape, off in spec.words:
                n = int(np.prod(shape, dtype=np.int64)) if shape else 1
                views[name] = (
                    wbuf[off:off + n]
                    .view(np.int32 if dt == "int32" else np.float32)
                    .reshape(shape)
                )
            for name, shape, off in spec.bools:
                n = int(np.prod(shape, dtype=np.int64)) if shape else 1
                views[name] = bbuf[off:off + n].view(np.bool_).reshape(shape)
            self._arena_spec = spec
            self._arena_w = wbuf
            self._arena_b = bbuf
            self._arena = views
            self._arena_snap = dataclasses.replace(snap, **views)
        for name, v in self._arena.items():
            v[...] = getattr(snap, name)
        self._arena_synced = True
        return EncodedFrame(
            self._arena_w, self._arena_b, self._arena_spec,
            self._arena_snap, None,
        )


# The per-slot arrays of the stable side's existing-pod block: st name,
# the pad value a from-scratch assembly leaves in an unused slot, and
# the arena field that mirrors the array row for row (None: not packed;
# exist_start is mirrored whole, a re-base rewrites every row). The
# existing-set fold compacts and re-pads exactly these.
_EXIST_SLOT_ARRAYS = (
    ("exist_req", 0.0, "exist_requested"),
    ("el_keys", -1, "exist_label_keys"),
    ("el_vals", -1, "exist_label_vals"),
    ("exist_ports", -1, "exist_ports"),
    ("exist_anti", -1, "exist_anti_terms"),
    ("exist_pref", -1, "exist_pref_aff"),
    ("exist_pref_w", 0.0, "exist_pref_aff_w"),
    ("exist_prio", 0, "exist_priority"),
    ("exist_pdb", -1, "exist_pdb"),
    ("exist_start", 0.0, None),
    ("exist_node", -1, "exist_node"),
    ("exist_group", -1, None),
    ("exist_creation_abs", 0.0, None),
    ("exist_valid", False, "exist_valid"),
)


def _victim_table(en, prio, e_ids):
    """node_pods' cells for the existing slots `e_ids` (ascending, all
    placed): per node the slots in ascending priority, ties the higher
    slot first. Returns (node, column, slot) of every cell. Shared by
    the full stable assembly (every placed slot) and the existing-set
    fold (the slots of the nodes whose row went stale), so the two
    cannot drift."""
    order_v = np.lexsort((-e_ids, prio[e_ids], en[e_ids]))
    se = e_ids[order_v].astype(np.int32)
    sn = en[se]
    starts = np.r_[True, sn[1:] != sn[:-1]]
    group_start = np.maximum.accumulate(
        np.where(starts, np.arange(sn.size), 0)
    )
    col = np.arange(sn.size) - group_start
    return sn, col, se


def _used_ports_by_node(exist_node, exist_ports, e_real, only=None):
    """The host ports in use per node, {node: [encoded port, ...]}:
    member slots ascending, ports in row order (a sparse residue loop:
    few pods hold a host port). `only` restricts it to those nodes.
    Shared by the full stable assembly and the existing-set fold."""
    rows = np.flatnonzero(
        (exist_ports[:e_real, 0] >= 0) & (exist_node[:e_real] >= 0)
    )
    if only is not None:
        rows = rows[np.isin(exist_node[rows], only)]
    used: dict[int, list[int]] = {}
    for s in rows:
        used.setdefault(int(exist_node[s]), []).extend(
            int(x) for x in exist_ports[s] if x >= 0
        )
    return used


def _pdb_matches(pdb: api.PodDisruptionBudget, p: Pod) -> bool:
    """Does `pdb`'s selector cover pod `p`? Shared by the full stable
    assembly and the incremental existing-fold."""
    if p.namespace != pdb.namespace:
        return False
    sel = pdb.selector
    for k, v in sel.match_labels.items():
        if p.metadata.labels.get(k) != v:
            return False
    for e in sel.match_expressions:
        val = p.metadata.labels.get(e.key)
        if e.operator == api.OP_IN and val not in e.values:
            return False
        if e.operator == api.OP_NOT_IN and val in e.values:
            return False
        if e.operator == api.OP_EXISTS and val is None:
            return False
        if e.operator == api.OP_DOES_NOT_EXIST and val is not None:
            return False
    return True


def _aff(p: Pod) -> Affinity:
    return p.spec.affinity or Affinity()


def _pref_count(p: Pod) -> int:
    a = _aff(p)
    n = 0
    if a.pod_affinity:
        n += len(a.pod_affinity.preferred)
    if a.pod_anti_affinity:
        n += len(a.pod_anti_affinity.preferred)
    return n
