"""Framework runtime: assembles enabled plugins into the fused cycle
program (the analogue of `framework/runtime/framework.go`'s
RunFilterPlugins/RunScorePlugins — [UNVERIFIED], mount empty; SURVEY.md §2
C6). Where the reference dispatches plugin callbacks per pod on 16
goroutines, this runtime asks each enabled plugin for its batched mask/
score fragments once per cycle and AND/weighted-sums them inside one jit —
plugin composition happens at trace time, parallelism comes from XLA."""

from __future__ import annotations

from typing import Any

import jax.numpy as jnp

from ..config import SchedulerConfiguration, default_plugins
from .interfaces import CycleContext, PluginBase
from .registry import Registry, default_registry

# Default-enabled plugins whose TPU kernels are scheduled but not landed:
# silently skipped when missing from the registry (unlike unknown names,
# which raise). Empty — every default plugin has a kernel.
PLANNED_PLUGINS: frozenset[str] = frozenset()


class Framework:
    def __init__(
        self,
        filters: list[PluginBase],
        scores: list[tuple[PluginBase, float]],
        post_filters: list[PluginBase] = (),
    ):
        self.filters = list(filters)
        self.scores = list(scores)
        self.post_filters = list(post_filters)

    @staticmethod
    def from_config(
        config: SchedulerConfiguration | None = None,
        scheduler_name: str = "default-scheduler",
        registry: Registry | None = None,
    ) -> "Framework":
        config = config or SchedulerConfiguration()
        registry = registry or default_registry()
        profile = config.profile(scheduler_name)
        defaults = default_plugins()
        args = profile.plugin_config

        def make(entries):
            out = []
            for e in entries:
                if e.name in registry.names():
                    out.append((registry.make(e.name, args.get(e.name)), e.weight))
                elif e.name in PLANNED_PLUGINS:
                    continue  # default-enabled, kernel not landed yet
                else:
                    # unknown names fail loudly (a typo must not silently
                    # change scheduling semantics) — same error Registry.make
                    # raises, reachable from the config path
                    registry.make(e.name)
            return out

        filters = [p for p, _ in make(profile.plugins.filter.resolve(defaults["filter"]))]
        scores = [
            (p, float(w)) for p, w in make(profile.plugins.score.resolve(defaults["score"]))
        ]
        post_filters = [
            p for p, _ in make(profile.plugins.post_filter.resolve(defaults["post_filter"]))
        ]
        return Framework(filters, scores, post_filters)

    # ---- trace-time assembly (called inside jit) ----

    @property
    def filter_names(self) -> list[str]:
        """Column names of the per-pod reject-count tables (filter order =
        upstream Filter execution order = first-rejector attribution)."""
        return [f.name for f in self.filters]

    def static(
        self, ctx: CycleContext
    ) -> tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
        """Batched static masks/scores plus per-pod reject attribution.

        Returns (mask [P,N], score [P,N], rejects i32 [P,F]) where
        rejects[p,i] counts the nodes FIRST rejected for pod p by filter i —
        the batched analogue of upstream's per-node "first failing plugin"
        Status that feeds FailedScheduling events and queueing hints."""
        snap = ctx.snap
        base = jnp.broadcast_to(snap.node_valid[None, :], (snap.P, snap.N))
        per_filter = [f.static_mask(ctx) for f in self.filters]
        rejects = self.attribute_rejects(base, per_filter)
        mask = base
        for m in per_filter:
            if m is not None:
                mask = mask & m
        score = jnp.zeros((snap.P, snap.N), jnp.float32)
        for s, w in self.scores:
            v = s.static_score(ctx)
            if v is not None:
                score = score + w * v
        return mask, score, rejects

    def static_lean(
        self, ctx: CycleContext
    ) -> tuple[jnp.ndarray, jnp.ndarray]:
        """static() without per-filter reject attribution: one fused AND
        chain (mask) + weighted sum (score). The latency-path cycle uses
        this (attribution lives in the separate diagnosis program), and
        the carry-update program runs it on dirty-row views."""
        snap = ctx.snap
        mask = jnp.broadcast_to(snap.node_valid[None, :], (snap.P, snap.N))
        for f in self.filters:
            m = f.static_mask(ctx)
            if m is not None:
                mask = mask & m
        score = jnp.zeros((snap.P, snap.N), jnp.float32)
        for s, w in self.scores:
            v = s.static_score(ctx)
            if v is not None:
                score = score + w * v
        return mask, score

    def _stateful_plugins(self) -> list[PluginBase]:
        # a plugin enabled at several points (e.g. InterPodAffinity filter +
        # score) owns ONE extra-state slot, keyed by name
        seen: dict[str, PluginBase] = {}
        for p in self.filters + [s for s, _ in self.scores]:
            seen.setdefault(p.name, p)
        return list(seen.values())

    def extra_init(self, ctx: CycleContext) -> dict[str, Any]:
        extra = {}
        for p in self._stateful_plugins():
            e = p.extra_init(ctx)
            if e is not None:
                extra[p.name] = e
        return extra

    def dyn(self, ctx: CycleContext, p, node_requested, extra, static_row):
        """Returns (mask [N], score [N], rejects i32 [F]) — `rejects[i]`
        counts nodes first rejected by filter i's DYNAMIC mask at this scan
        step (nodes already statically rejected are attributed by
        `static`; the two tables add up per filter name)."""
        snap = ctx.snap
        mask = static_row
        rejects = []
        for f in self.filters:
            m = f.dyn_mask(ctx, p, node_requested, extra)
            if m is None:
                rejects.append(jnp.int32(0))
            else:
                newly = mask & ~m
                rejects.append(jnp.sum(newly, dtype=jnp.int32))
                mask = mask & m
        score = jnp.zeros((snap.N,), jnp.float32)
        for s, w in self.scores:
            # dyn_score sees the FULL feasibility row (static & dynamic) so
            # cross-node normalization covers feasible nodes only, like
            # upstream NormalizeScore running after Filter
            v = s.dyn_score(ctx, p, node_requested, extra, mask)
            if v is not None:
                score = score + w * v
        return mask, score, jnp.stack(rejects)

    def extra_update(self, ctx: CycleContext, extra, p, node, committed):
        out = dict(extra)
        for pl in self._stateful_plugins():
            if pl.name in out:
                out[pl.name] = pl.extra_update(ctx, out[pl.name], p, node, committed)
        return out

    # ---- batched dynamic path (round-based commit) ----

    def check_batched_parity(self) -> None:
        """Fail fast when a plugin implements a per-pod dynamic hook but
        not its batched counterpart: in rounds mode the batched path is
        the only one that runs, and a silently-skipped constraint would
        produce invalid placements with no error."""
        from .interfaces import PluginBase

        pairs = [
            ("dyn_mask", "dyn_mask_batched"),
            ("dyn_score", "dyn_score_batched"),
            ("extra_update", "extra_update_batched"),
        ]
        for p in self.filters + [s for s, _ in self.scores]:
            for single, batched in pairs:
                overrides_single = getattr(type(p), single) is not getattr(
                    PluginBase, single
                )
                overrides_batched = getattr(type(p), batched) is not getattr(
                    PluginBase, batched
                )
                if overrides_single and not overrides_batched:
                    raise TypeError(
                        f"plugin {p.name!r} implements {single} but not "
                        f"{batched}: its constraint would be silently "
                        f"dropped by the rounds commit engine. Implement "
                        f"{batched} or run with commit_mode='scan'."
                    )

    def dyn_batched(self, ctx: CycleContext, node_requested, extra,
                    static_mask):
        """Whole-pending-set analogue of `dyn`: returns (mask [P,N],
        score [P,N], per_filter list of [P,N] masks or None in filter
        order — the latter feeds reject attribution)."""
        snap = ctx.snap
        shared: dict = {}
        mask = static_mask
        per_filter = []
        for f in self.filters:
            m = f.dyn_mask_batched(ctx, node_requested, extra, shared)
            per_filter.append(m)
            if m is not None:
                mask = mask & m
        score = jnp.zeros((snap.P, snap.N), jnp.float32)
        for s, w in self.scores:
            v = s.dyn_score_batched(ctx, node_requested, extra, mask, shared)
            if v is not None:
                score = score + w * v
        return mask, score, per_filter

    def reach_mask_batched(self, ctx: CycleContext, node_requested, extra,
                           static_mask, per_filter, active):
        """(mask, share, domain) the `active` pods CLAIM by within one
        round: `dyn_batched`'s mask with the filter that can say where
        the round's own acceptances open a node read that way, and that
        filter's share and domain (PluginBase.dyn_mask_reach_batched;
        the first filter that answers, today the only one). None when
        no filter does, so a cycle without such a constraint traces
        nothing here."""
        shared: dict = {}
        mask, steer = static_mask, None
        for f, m in zip(self.filters, per_filter):
            w = (
                f.dyn_mask_reach_batched(
                    ctx, node_requested, extra, shared, active
                ) if steer is None else None
            )
            if w is not None:
                m, steer = w[0], w[1:]
            if m is not None:
                mask = mask & m
        return None if steer is None else (mask, *steer)

    def closed_for_cycle(self, ctx: CycleContext, static_mask,
                         per_filter):
        """bool [P]: the pods that no placement made later in this cycle
        can give a node, from one round's masks (`per_filter` as
        `dyn_batched` returns it). A filter's mask counts as open for
        the pods it can reopen for (PluginBase.dyn_mask_reopens:
        required affinity, DoNotSchedule spread) and as it stands for
        every other pod; static masks never move within a cycle. A pod
        with no node left under that reading is refused whatever else
        the cycle places, so the rounds engine parks it."""
        open_ = static_mask
        for f, m in zip(self.filters, per_filter):
            if m is None:
                continue
            reopens = f.dyn_mask_reopens(ctx)
            open_ = open_ & (
                m if reopens is None else m | reopens[:, None]
            )
        return ~jnp.any(open_, axis=1)

    def attribute_rejects(self, base_mask, per_filter, rows=None):
        """First-rejector attribution over a filter-mask chain: returns
        i32 [P, F] where column i counts the nodes newly rejected by
        filter i (None entries contribute zeros). `rows` (bool [P])
        restricts attribution to those pods. The single owner of the
        chain/column convention used by static(), dyn() and the rounds
        engine's final pass."""
        mask = base_mask
        cols = []
        for m in per_filter:
            if m is None:
                cols.append(jnp.zeros((base_mask.shape[0],), jnp.int32))
            else:
                newly = mask & ~m
                c = jnp.sum(newly, axis=1, dtype=jnp.int32)
                cols.append(c if rows is None else jnp.where(rows, c, 0))
                mask = mask & m
        return jnp.stack(cols, axis=1)

    def score_anchor(self, ctx: CycleContext, node_requested):
        """Weighted sum of the enabled score plugins' node-local capacity
        components (f32 [N]), or None when no plugin has one. See
        PluginBase.score_node_anchor."""
        total = None
        for s, w in self.scores:
            a = s.score_node_anchor(ctx, node_requested)
            if a is not None:
                total = w * a if total is None else total + w * a
        return total

    def extra_update_batched(self, ctx: CycleContext, extra, accepted,
                             node_of):
        out = dict(extra)
        for pl in self._stateful_plugins():
            if pl.name in out:
                out[pl.name] = pl.extra_update_batched(
                    ctx, out[pl.name], accepted, node_of
                )
        return out

    def post_filter(self, ctx: CycleContext, assignment, node_requested,
                    gate_rows, excluded=None):
        """Run PostFilter plugins in order; first non-None result wins
        (upstream RunPostFilterPlugins stops at the first nomination)."""
        for p in self.post_filters:
            r = p.post_filter(ctx, assignment, node_requested, gate_rows,
                              excluded)
            if r is not None:
                return r
        return None
