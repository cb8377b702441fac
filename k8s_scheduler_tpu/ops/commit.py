"""Greedy sequential-commit pass: the batched equivalent of ScheduleOne.

The reference schedules ONE pod per `ScheduleOne` iteration: pop the
highest-priority pod, filter+score nodes, pick the max, assume it in the
cache so the next pod sees reduced capacity (SURVEY.md §3.2 — expected
`schedule_one.go`/`generic_scheduler.go`, [UNVERIFIED], mount empty). The
TPU design batches a whole pending set per cycle but must preserve those
sequential-commit semantics: pods earlier in priority order constrain later
ones (SURVEY.md §7 "hard parts" (a)).

This is a `lax.scan` over the priority-ordered pending set. Everything that
does NOT depend on in-cycle commitments (label/taint/affinity-vs-existing
masks, static scores) is precomputed batched [P, N] outside the scan; the
scan body only evaluates the dynamic residue — resource fit against the
running allocatable matrix plus caller-provided hooks (running
topology-domain counts for inter-pod affinity / topology spread arrive via
`dyn_fn`/`update_fn`). Each step is O(N) vector work, so the whole commit is
O(P*N) — the same work one Filter pass does in the reference, but fused into
one XLA while-loop on device.

Tie-breaking: upstream `selectHost` breaks score ties with reservoir
sampling; we take the lowest node index (deterministic — the differential
oracle does the same).
"""

from __future__ import annotations

import dataclasses
from typing import Any, Callable

import jax
import jax.numpy as jnp

from . import argsel
from . import sampling

NEG_INF = -1e9

# dyn_fn(pod_idx, node_requested [N,R], extra, static_row [N] bool)
#   -> (full feasibility mask [N] bool, score [N] f32)
#   or (mask, score, aux) — aux is any pytree emitted per step (e.g.
#   per-filter reject counts for failure attribution); stacked over the
#   pod axis into CommitResult.dyn_aux
# The static row is passed IN so score hooks that normalize across nodes
# (inter-pod affinity, topology spread) can normalize over feasible nodes
# only, like upstream NormalizeScore running after Filter.
DynFn = Callable[
    [jnp.ndarray, jnp.ndarray, Any, jnp.ndarray],
    tuple[jnp.ndarray, jnp.ndarray],
]
# update_fn(extra, pod_idx, node_idx, committed) -> extra
UpdateFn = Callable[[Any, jnp.ndarray, jnp.ndarray, jnp.ndarray], Any]


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class CommitResult:
    assignment: jnp.ndarray  # i32 [P] node index or -1
    node_requested: jnp.ndarray  # f32 [N, R] post-commit
    extra: Any  # final hook state (e.g. running domain counts)
    dyn_aux: Any = None  # per-pod stacked dyn_fn aux (None w/ 2-tuple dyn_fn)
    sample_narrowed: jnp.ndarray | None = None  # i32 [] pods whose
    # feasible nodes outnumbered the sample's k (None: no sampling)


def greedy_commit(
    *,
    order: jnp.ndarray,  # i32 [P]: pod index scheduled at each rank
    static_mask: jnp.ndarray,  # bool [P, N]
    static_score: jnp.ndarray,  # f32 [P, N]
    pod_requested: jnp.ndarray,  # f32 [P, R]
    pod_valid: jnp.ndarray,  # bool [P]
    pod_nominated: jnp.ndarray,  # i32 [P] node index (-1 none)
    node_allocatable: jnp.ndarray,  # f32 [N, R]
    node_requested: jnp.ndarray,  # f32 [N, R] at cycle start
    dyn_fn: DynFn,
    extra: Any = None,
    update_fn: UpdateFn | None = None,
    sample=None,  # (off i32 [P], k i32 []) | None — percentageOfNodesTo-
    # Score: a pod is scored on the first k nodes feasible in the state
    # it is scheduled in, walking from off[p] (ops/sampling.py)
) -> CommitResult:
    P, N = static_mask.shape

    def step(carry, rank):
        node_req, ext = carry
        p = order[rank]
        out = dyn_fn(p, node_req, ext, static_mask[p])
        feasible, dyn_score = out[0], out[1]
        aux = out[2] if len(out) > 2 else jnp.int32(0)
        # dyn_fn is expected to fold the static row in (it needs it for
        # normalize-over-feasible scoring); AND it again here so a dyn_fn
        # that ignores its 4th arg can never bypass static filters
        feasible = feasible & static_mask[p]
        scored = feasible
        if sample is not None:
            off, k = sample
            scored, narrowed = sampling.sample_feasible(feasible, off[p], k)
            aux = (aux, narrowed & pod_valid[p])
        score = jnp.where(scored, static_score[p] + dyn_score, NEG_INF)
        # A nominated node (set by a previous preemption) is honored when
        # feasible, regardless of score — upstream evaluates the nominated
        # node first and keeps it if it passes filters.
        nom = jnp.clip(pod_nominated[p], 0, N - 1)
        nom_ok = (pod_nominated[p] >= 0) & feasible[nom]
        # lowest-index tie-break that survives a sharded nodes axis
        # (ops/argsel.py) — identical to argmax on a single device
        best = jnp.where(
            nom_ok, nom, argsel.argmax_first(score, axis=0)
        ).astype(jnp.int32)
        ok = feasible[best] & pod_valid[p]
        node = jnp.where(ok, best, jnp.int32(-1))
        node_req = node_req.at[best].add(
            jnp.where(ok, pod_requested[p], 0.0)
        )
        if update_fn is not None:
            ext = update_fn(ext, p, best, ok)
        return (node_req, ext), (p, node, aux)

    (node_req_final, extra_final), (pods, assigned, auxs) = jax.lax.scan(
        step, (node_requested, extra), jnp.arange(P, dtype=jnp.int32)
    )
    narrowed = None
    if sample is not None:
        auxs, narrow = auxs
        narrowed = jnp.sum(narrow, dtype=jnp.int32)
    assignment = jnp.zeros(P, jnp.int32).at[pods].set(assigned)
    # ys arrive in rank order; re-scatter to pod order like `assignment`
    dyn_aux = jax.tree_util.tree_map(
        lambda a: jnp.zeros_like(a).at[pods].set(a), auxs
    )
    return CommitResult(
        assignment, node_req_final, extra_final, dyn_aux, narrowed
    )


def unwind_assignments(
    result: CommitResult,
    drop: jnp.ndarray,  # bool [P] — assignments to roll back (e.g. gang fail)
    pod_requested: jnp.ndarray,  # f32 [P, R]
) -> CommitResult:
    """Roll back a subset of commitments (all-or-nothing gang semantics:
    a group that did not fully place releases its members' capacity and the
    pods go back to the queue — upstream Permit-timeout behaviour)."""
    P, _ = pod_requested.shape
    assigned = result.assignment >= 0
    undo = drop & assigned
    node_req = result.node_requested
    # scatter-subtract each dropped pod's request from its node
    idx = jnp.clip(result.assignment, 0, node_req.shape[0] - 1)
    node_req = node_req.at[idx].add(
        jnp.where(undo[:, None], -pod_requested, 0.0)
    )
    assignment = jnp.where(undo, -1, result.assignment)
    return CommitResult(assignment, node_req, result.extra, result.dyn_aux)
