"""Scheduler-framework extension points, TPU-native shape.

The reference's framework (`framework/runtime/framework.go` — [UNVERIFIED],
mount empty; SURVEY.md §2 C6) runs plugin callbacks per pod per extension
point: PreEnqueue, QueueSort, PreFilter, Filter, PostFilter, PreScore,
Score+NormalizeScore, Reserve, Permit, PreBind, Bind, PostBind.

The TPU-native mapping, per extension point:

- QueueSort        -> the priority-ordered `pod_order` rank (encoder) used
                      by the commit scan; PrioritySort semantics built in.
- PreFilter        -> `CycleContext` precomputes shared across plugins
                      (expression-table node masks etc.), computed ONCE per
                      cycle, batched — the analogue of PreFilter state.
- Filter           -> `static_mask` (batched [P, N], independent of
                      in-cycle commitments) and/or `dyn_mask` ([N] inside
                      the commit scan, sees running state).
- PostFilter       -> `post_filter` (batched preemption, ops/preemption.py).
- PreScore/Score   -> `static_score` / `dyn_score`, each 0..100 per the
                      upstream NormalizeScore contract; the runtime applies
                      the configured integer plugin weight.
- Reserve..PostBind-> host-side (core/scheduler.py, service/): assume,
                      gang Permit, binding. Not device code.

A plugin implements any subset; `None` means "not implemented at this
point". All array-returning hooks are traced inside ONE jit, so plugins
compose into a single fused XLA program — the registry is a program
assembler, not a callback dispatcher.
"""

from __future__ import annotations

from typing import Any, Protocol, runtime_checkable

import jax.numpy as jnp

from ..models.encoding import ClusterSnapshot
from ..ops import interpod, labels


class CycleContext:
    """Shared per-cycle precomputes (the PreFilter-state analogue).

    Lazily computed, cached: plugins ask for what they need; anything no
    enabled plugin asks for is never computed (and XLA dead-code-eliminates
    anything unused)."""

    def __init__(self, snap: ClusterSnapshot):
        self.snap = snap
        self._cache: dict[str, Any] = {}

    def get(self, key: str, compute) -> Any:
        if key not in self._cache:
            # a CycleContext lives exactly as long as one trace: the
            # memo is MEANT to be written at trace time (it dedupes
            # recomputation across plugins within the trace) and is
            # garbage the moment tracing ends
            self._cache[key] = compute(self.snap)  # schedlint: disable=JP004 -- per-trace memo; the object dies with the trace
        return self._cache[key]

    @property
    def expr_node_mask(self) -> jnp.ndarray:  # bool [Ex, N]
        return self.get("expr_node_mask", labels.expr_node_mask)

    @property
    def matched_pending(self) -> jnp.ndarray:  # bool [S, P]
        return self.get("matched_pending", interpod.matched_pending)

    @property
    def matched_existing(self) -> jnp.ndarray:  # bool [S, E]
        return self.get("matched_existing", interpod.matched_existing)

    def initial_affinity_state(self):
        return self.get(
            "initial_affinity_state",
            lambda s: interpod.initial_state(s, self.matched_existing),
        )


@runtime_checkable
class Plugin(Protocol):
    """Base protocol. Concrete plugins subclass `PluginBase`."""

    name: str


class PluginBase:
    name: str = ""

    def __init__(self, args: dict | None = None):
        self.args = args or {}

    # --- Filter ---
    def static_mask(self, ctx: CycleContext) -> jnp.ndarray | None:
        return None

    def dyn_mask(self, ctx: CycleContext, p, node_requested, extra) -> jnp.ndarray | None:
        return None

    # --- Score (0..100; runtime applies weight) ---
    def static_score(self, ctx: CycleContext) -> jnp.ndarray | None:
        return None

    def dyn_score(self, ctx: CycleContext, p, node_requested, extra,
                  feasible) -> jnp.ndarray | None:
        """`feasible` is the pod's full feasibility row [N] (static &
        dynamic masks combined) for upstream-style normalize-over-feasible
        scoring."""
        return None

    # --- scan-carried state (running domain counts etc.) ---
    def extra_init(self, ctx: CycleContext) -> Any | None:
        return None

    def extra_update(self, ctx: CycleContext, extra, p, node, committed):
        return extra

    # --- batched dynamic path (round-based commit, ops/rounds.py):
    # whole-pending-set [P, N] evaluation against the current running
    # state, plus a whole-round state fold. A plugin that implements a
    # per-pod dyn hook MUST implement the batched counterpart too —
    # Framework.check_batched_parity() (run when a rounds-mode cycle is
    # built) raises otherwise, because the rounds engine only calls the
    # batched path. ---
    def dyn_mask_batched(self, ctx: CycleContext, node_requested, extra,
                         shared: dict) -> jnp.ndarray | None:
        """`shared` is a per-round trace-time scratch dict: plugins stash
        precomputes derived from the round state there (e.g. the
        counts-by-node table) so co-enabled plugins don't recompute them."""
        return None

    def dyn_mask_reopens(self, ctx: CycleContext) -> jnp.ndarray | None:
        """bool [P]: the pods for which a placement made LATER IN THE
        SAME CYCLE can open a node this plugin's dynamic mask closed, or
        None where it can for none. Within a cycle placements only
        consume room and add pods, so a dynamic mask that counts
        capacity, ports, claimed volumes or anti-affinity only ever
        closes (the default). The rounds engine parks a pod whose every
        node is closed by masks that cannot reopen for it
        (Framework.closed_for_cycle)."""
        return None

    def dyn_mask_reach_batched(self, ctx: CycleContext, node_requested,
                               extra, shared: dict,
                               active) -> tuple | None:
        """(mask bool [P, N], share f32 [P, N], domain i32 [P, N]) as
        the rounds engine lets the `active` (bool [P]) pods CLAIM within
        one round, or None where claims go by `dyn_mask_batched` as it
        stands (the default). `mask` is that mask plus the nodes that
        acceptances of the same round can open; `domain` groups the
        nodes (-1: ungrouped) and `share` says how much of the group's
        claims each domain should draw. A plugin that answers must have
        a guard in the round's sweep that holds every acceptance to its
        rule (ops/rounds.py: the spread guard's level-fill); what it
        returns here only says where claims go."""
        return None

    def dyn_score_batched(self, ctx: CycleContext, node_requested, extra,
                          feasible, shared: dict) -> jnp.ndarray | None:
        """`feasible` is the full [P, N] feasibility (static & dynamic)
        for normalize-over-feasible scoring."""
        return None

    def extra_update_batched(self, ctx: CycleContext, extra, accepted,
                             node_of):
        """Fold a round's placements (accepted bool [P], node_of i32 [P])
        into this plugin's extra state."""
        return extra

    def score_node_anchor(self, ctx: CycleContext,
                          node_requested) -> jnp.ndarray | None:
        """Node-local component of this plugin's dynamic score at the
        given node_requested (f32 [N]), or None if the score has no such
        component. The rounds engine adds (anchor(now) - anchor(round
        start)) to stale claim scores between acceptance passes so a node
        that fills up loses attractiveness immediately — the batched
        analogue of sequential scheduling's per-pod score freshness. Used
        ONLY for claim ordering; masks and reported scores are
        unaffected."""
        return None

    # --- PostFilter (preemption): runs after the commit scan over the
    # pods that found no node; returns a PreemptionResult or None.
    # `excluded` [P] marks pods that must not preempt (gang-dropped) ---
    def post_filter(self, ctx: CycleContext, assignment, node_requested,
                    gate_rows, excluded=None):
        return None
