"""TenantFrontHost: the arena behind the existing front door.

service/admission.py's AdmissionController (and FrontDoor around it)
talks to a `scheduler` through a narrow duck-typed surface: config,
metrics, a queue with a depth, a cache that answers has_pod, an
informer-path `on_pod_add`, a clock. This adapter presents that
surface over a TenantRegistry + MultiTenantArena, so the PR 13 Submit
path — whole-request atomicity, WAL-before-ack, shed semantics,
/debug/explain history — fronts thousands of virtual clusters without
a fork of the admission layer: a Submit carries its tenant in the pod
namespace, admission consults that tenant's quota and weighted-fair
share, and accepted pods route into their tenant's arena slot.
"""

from __future__ import annotations

import time
from types import SimpleNamespace

from ..config.types import SchedulerConfiguration
from ..metrics.metrics import SchedulerMetrics
from .arena import MultiTenantArena
from .registry import TenantRegistry


class _ArenaQueueView:
    """Queue-shaped read view over every tenant's pending set (the
    admission depth bound counts fleet-wide pending, same as the
    single-cluster queue)."""

    def __init__(self, registry: TenantRegistry) -> None:
        self._registry = registry

    def __len__(self) -> int:
        return sum(t.depth() for t in self._registry.tenants())

    def pending_counts(self) -> dict:
        return {"active": len(self)}


class _ArenaCacheView:
    """Cache-shaped dup check: a uid any tenant knows (pending OR
    bound) is a duplicate — same lost-ack retry semantics as the
    single-cluster cache.has_pod."""

    def __init__(self, registry: TenantRegistry) -> None:
        self._registry = registry

    def has_pod(self, uid: str) -> bool:
        return self._registry.has_pod(uid)


class _NoLadder:
    """The arena serve loop has no degradation ladder yet; rung 0 =
    the admission predicate's healthy reading."""

    rung = 0


class TenantFrontHost:
    """Duck-typed scheduler surface for AdmissionController/FrontDoor,
    backed by the tenant registry and the arena packer."""

    def __init__(
        self,
        registry: TenantRegistry,
        *,
        config: SchedulerConfiguration | None = None,
        metrics: SchedulerMetrics | None = None,
        observer=None,
        arena: MultiTenantArena | None = None,
        state=None,
    ) -> None:
        self.registry = registry
        self.config = config or SchedulerConfiguration()
        self.metrics = metrics or SchedulerMetrics()
        self.observer = observer
        self.arena = arena or MultiTenantArena(
            registry, observer=observer, metrics=self.metrics
        )
        self.queue = _ArenaQueueView(registry)
        self.cache = _ArenaCacheView(registry)
        self.ladder = _NoLadder()
        self.state = state  # DurableState-shaped ack-barrier provider
        self.admission = None  # AdmissionController installs itself

    # ---- informer-path surface ------------------------------------------

    def on_pod_add(self, pod) -> None:
        self.registry.route(pod)

    def on_node_add(self, node) -> None:
        # nodes are namespaced here the same way pods are: the tenant
        # rides ObjectMeta.namespace (virtual clusters own their nodes)
        self.registry.add_node(node.metadata.namespace, node)

    def on_node_update(self, node) -> None:
        raise NotImplementedError(
            "arena node update not supported yet (delete + add)"
        )

    def on_node_delete(self, name: str) -> None:
        raise NotImplementedError(
            "arena node delete not supported yet"
        )

    def _now(self) -> float:
        return time.monotonic()

    # ---- serve loop ------------------------------------------------------

    def schedule_cycle(self):
        """One fleet cycle for FrontDoor: returns a stats object with
        the `attempted` field the idle/drain logic reads."""
        adm = self.admission
        if adm is not None and self.arena.on_bind is None:
            # close the submit->bind latency window on arena folds
            self.arena.on_bind = adm.note_bind
        stats = self.arena.run_cycle()
        return SimpleNamespace(
            attempted=stats["bound"] + stats["unschedulable"],
            **stats,
        )
