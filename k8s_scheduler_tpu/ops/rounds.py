"""Round-based batched commit: the TPU-first ScheduleOne batching.

The sequential commit scan (ops/commit.py) preserves exact one-pod-at-a-
time semantics but costs one `lax.scan` step per pod — and a TPU scan step
is latency-bound (~100us+ through the sequencer), so 10k pods cost seconds
regardless of how little work each step does. This module replaces the
per-pod loop with a small number of ROUNDS; each round is a handful of
large batched ops (matmuls, row-gathers, sorts, segmented scans) that use
the MXU/VPU at full width:

  1. CLAIM   — every still-pending pod evaluates all plugin masks/scores
               against the current state (exactly: the same kernels the
               scan uses, batched over [B, N]) and claims its best node
               (nominated node first, then argmax with a deterministic
               hash tie-break — the analogue of upstream selectHost's
               random tie-break, which also prevents herding).
  2. ACCEPT  — a number of cheap acceptance PASSES (waterfall): in each
               pass, every still-unaccepted pod claims its best node among
               choices not yet known-dead, with the capacity-sensitive
               node-local score component RE-ANCHORED to the in-round
               node_req (a filling node loses attractiveness immediately —
               the spread mechanism sequential scheduling gets from score
               freshness); capacity losers fall to their next-best node in
               the next pass, reusing the round's masks (no dyn
               recompute). At round end, ONE guard sweep checks all
               capacity-accepted claims for mutual consistency and revokes
               violators (they retry next round against refreshed masks).
               Within a pass, claims resolve in `pod_order` rank without
               any sequential host loop:
               a. per-node capacity: sort claims by (node, rank), then a
                  segmented exclusive prefix-sum of requests admits each
                  claimant iff it still fits (earlier-rank claimants of
                  the same node are charged first);
               b. interaction guards: claims that could invalidate one
                  another within the round (required anti-affinity,
                  DoNotSchedule spread skew, affinity bootstrap, hostPort
                  exclusivity) are resolved by a participant table — one
                  row per (claimant, constraint-role) — sorted by
                  (group, rank) and swept with segmented exclusive scans.
                  Rank order within a group decides, exactly like the
                  sequential scan would have. The spread guard comes
                  last and holds a domain to the level the round's own
                  arrivals lift the group's minimum to (rounds_commit).
  3. UPDATE  — accepted placements fold into the running state in one
               batched pass (segment-adds into domain counts, scatter
               rows into the symmetric tables, port-bitmap scatter).

Rounds repeat (lax.while_loop) until no claim is accepted or `max_rounds`
is hit; leftover pods are unschedulable this cycle. Round 1 runs over the
full pending set; subsequent rounds run over a COMPACTED view — the
lowest-rank `P/compact` still-active pods, re-gathered each round — since
round 1 typically places the large majority, and [B, N] work shrinks
proportionally. The compacted view is a real ClusterSnapshot whose
pod-axis arrays are gathered at the active ids, so every plugin kernel
runs unchanged. A pod that no acceptance of this cycle can give a node
is PARKED the round it is judged and never holds a row of that view
again (see `rounds_commit`).

Semantics contract (documented deviation from the strict scan):
  - Every accepted placement satisfies every filter against the state at
    the start of its round, and the guards make same-round acceptances
    mutually consistent, so the FINAL assignment is valid under the final
    state — same validity invariant the sequential scan provides
    (oracle.validate_rounds_assignment checks it).
  - Guards count REJECTED claimants too (conservative): a claim that lost
    capacity can still hold an anti-affinity slot for its round; the loser
    simply retries next round against the true state. This only delays
    placements, never invalidates them.
  - Outcomes can differ from the strict scan where in-cycle contention
    exists (scores against a slightly older state, hash tie-break); the
    strict scan remains available as commit_mode="scan".

A pod that matches more than MS_MATCH guard-active selectors overflows the
matcher table; overflow claimants are deferred while any normal claimant
exists and then accepted one per round (exact, since they run alone).
"""

from __future__ import annotations

import dataclasses
from typing import Any, Callable

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec

from ..models import encoding as enc
from ..parallel.mesh import MESH_AXES, mesh_pin
from . import argsel
from . import interpod as interpod_ops
from . import sampling

NEG_INF = -1e9
_REL_EPS = 1e-5  # mirrors ops/resources.py fit slack
MS_MATCH = 4  # guard-active selectors tracked per pod (overflow = defer)
# Claim scores are rounded to INTEGERS before the hash tie-break — the
# upstream scheduler's own granularity (plugin Score returns int64 in
# [0, 100]; selectHost random-tie-breaks across the whole max class).
# Keeping f32 score sums un-rounded created artificial total orders that
# herded every pod's claim onto the same argmax node; integer classes let
# the per-pod hash spread contending claims across all equally-good nodes.
TIE_EPS = 0.9375  # hash spread, strictly below the integer quantum
# numpy scalars, not jnp: a jnp scalar is a device array, and making one
# at import initialises a backend in EVERY process that imports this
# package — a plain gRPC client (service/client.py) would take the chip
_PR1 = np.uint32(2654435761)
_PR2 = np.uint32(40503)
_PR3 = np.uint32(0x85EBCA6B)  # with _PR4, murmur3's finishing multipliers:
_PR4 = np.uint32(0xC2B2AE35)  # the domain draw of one_round
_BIG = np.int32(2**31 - 1)

# In the name of every program that embeds this engine (core/cycle.py
# appends it to the name's discriminator): the executable store keys on
# names and call conventions, not on code, and an entry built by an
# engine that did not park, or that held a spread group to the counts
# at the round's start (see rounds_commit), must never load.
ENGINE_MARK = ":levels"

# participant role bits (packed into one sort operand)
_RB_MATCH = 1
_RB_ANTI = 2
_RB_BOOT = 4
_RB_GMATCH = 8
_RB_SPREAD = 16
_RB_PORT = 32
_RB_PV = 64  # static-PV exclusivity: one claimant per PV per cycle


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class RoundsResult:
    assignment: jnp.ndarray  # i32 [P] node index or -1
    node_requested: jnp.ndarray  # f32 [N, R] post-commit
    extra: Any  # final plugin state
    rounds_used: jnp.ndarray  # i32 []
    accepted_per_round: jnp.ndarray  # i32 [max_rounds] acceptance counts
    diag_per_round: jnp.ndarray  # i32 [max_rounds, 3] summed over passes:
    # (live claims, capacity rejections, guard rejections) — convergence
    # diagnostics, negligible cost
    parked: jnp.ndarray  # i32 [] pods parked for the rest of the cycle
    # (see rounds_commit)
    round_cap_hit: jnp.ndarray  # i32 [] 1 where the loop ended at
    # `max_rounds` with claimants still to be judged, else 0
    spread_revoked: jnp.ndarray  # i32 [] claims the spread guard revoked,
    # summed over the rounds
    sample_narrowed: jnp.ndarray | None = None  # i32 [] pods whose
    # feasible nodes outnumbered the sample's k in round 1 (None: the
    # cycle does not sample)


def compact_window(P: int, compact: int = 8) -> int:
    """Row count of the compacted per-round view (also used by the
    cycle's final attribution/preemption-gate view): the `P/compact`
    lowest-rank actives, padded to a lane multiple."""
    return min(P, max(256, -(-P // compact) // 128 * 128))


def _tie_break(gid: jnp.ndarray, N: int) -> jnp.ndarray:
    """f32 [B, N] in [0, TIE_EPS), keyed on GLOBAL pod id so compaction
    does not change a pod's tie-break row."""
    p = gid.astype(jnp.uint32)[:, None]
    n = jax.lax.broadcasted_iota(jnp.uint32, (1, N), 1)
    h = (p * _PR1 + n * _PR2) & jnp.uint32(0xFFFF)
    return h.astype(jnp.float32) * (TIE_EPS / 65536.0)


def _matched_active(m_pending, active_sel, ms: int):
    """Per-pod list of up to `ms` guard-active selectors it matches.

    Returns (sels i32 [P, ms] (-1 pad), overflow bool [P]). Selector ids
    ascending (deterministic). Implemented as `ms` masked argmin passes —
    a lax.top_k here would sort the whole [P, S] table, which costs
    hundreds of ms at 10k pods for a table that is almost entirely
    False."""
    S, P = m_pending.shape
    m = (m_pending & active_sel[:, None]).T  # [P, S]
    sel_ids = jnp.arange(S, dtype=jnp.int32)[None, :]
    cols = []
    remaining = m
    for _ in range(ms):
        # lowest matching selector id still unclaimed
        cand = jnp.where(remaining, sel_ids, S)
        nxt = jnp.min(cand, axis=1).astype(jnp.int32)  # [P]
        cols.append(jnp.where(nxt < S, nxt, -1))
        remaining = remaining & (sel_ids != nxt[:, None])
    overflow = jnp.any(remaining, axis=1)
    return jnp.stack(cols, axis=1), overflow


def _pod_view(snap, gid: jnp.ndarray):
    """A ClusterSnapshot whose pod-axis arrays are gathered at `gid` —
    plugin kernels run on it unchanged with P = len(gid)."""
    updates = {
        f.name: getattr(snap, f.name)[gid]
        for f in dataclasses.fields(snap)
        # extender verdicts (None unless configured) are pre-folded into
        # the static mask/score, so views never need them
        if f.name.startswith("pod_") and getattr(snap, f.name) is not None
    }
    return dataclasses.replace(snap, **updates)


def _seg_scan_tables(keys, pods, counts):
    """Entries sorted by (key, rank): for each 0/1 indicator column,
    return the in-segment count strictly before each entry's POD (one
    pod's own entries never block each other).

    All indicator columns ride ONE stacked [L, C] cumsum and TWO stacked
    row-gathers — per-column 1-D gathers are pathologically slow on this
    backend (~2ms each at L=283k; 12 of them dominated the sweep)."""
    L = keys.shape[0]
    i = jnp.arange(L, dtype=jnp.int32)
    seg_start = jnp.concatenate(  # schedlint: disable=SH002 -- the [L] sorted entries axis is replicated (lax.sort all-gathers its operands; the audit suite bounds exactly that payload), so no operand here is sharded
        [jnp.ones((1,), bool), keys[1:] != keys[:-1]]
    )
    run_start = seg_start | jnp.concatenate(  # schedlint: disable=SH002 -- same replicated [L] axis as the line above
        [jnp.ones((1,), bool), pods[1:] != pods[:-1]]
    )
    seg_first = jax.lax.cummax(jnp.where(seg_start, i, -1))
    run_first = jax.lax.cummax(jnp.where(run_start, i, -1))
    names = list(counts.keys())
    x = jnp.stack([counts[n] for n in names], axis=1)  # [L, C]
    before = jnp.cumsum(x, axis=0) - x  # strictly before index j
    delta = before[run_first] - before[seg_first]  # [L, C]
    return {n: delta[:, c] for c, n in enumerate(names)}


def _owner_state(ext_state):
    for v in ext_state.values():
        if isinstance(v, interpod_ops.AffinityState):
            return v
    return None


def rounds_commit(
    *,
    snap,
    static_mask: jnp.ndarray = None,  # bool [P, N]
    static_score: jnp.ndarray = None,  # f32 [P, N]
    sbase: jnp.ndarray = None,  # f32 [P, N] pre-combined static score
    # (NEG_INF where infeasible) — the carry path passes this directly
    # instead of (static_mask, static_score)
    m_pending: jnp.ndarray,  # bool [S, P]
    dyn_batched_view_fn: Callable,  # (vsnap, vmp, node_req, ext, vsmask)
    #   -> (mask [B,N], score [B,N], per_filter)
    update_batched_view_fn: Callable,  # (vsnap, vmp, ext, accepted, node_of)
    closed_for_cycle_fn: Callable,  # (vsnap, vmp, vsmask, per_filter)
    #   -> bool [B]: pods no acceptance of this cycle can give a node
    #   (Framework.closed_for_cycle), from the per-filter masks the
    #   round computed anyway
    reach_mask_fn: Callable | None = None,  # (vsnap, vmp, node_req, ext,
    #   vsmask, per_filter, act_v) -> (mask bool, share f32, domain i32,
    #   each [B, N]) | None: what the round's claims go by where that is
    #   wider than the filters' own mask (Framework.reach_mask_batched:
    #   the domains a spread group's claimants can open within the
    #   round, and how many of them each domain should draw)
    extra: Any,
    max_rounds: int = 64,
    compact: int = 8,
    passes: int = 6,  # device-time flat across 4..10 at config-#4 scale;
    passes_round0: int = 10,  # smaller counts compile ~30% faster
    shortlist: int = 0,  # >0: acceptance passes run on a per-pod top-k
    # candidate shortlist [B, shortlist] instead of [B, N], with a
    # rescue pass preserving the "unplaced => infeasible vs final
    # state" invariant (see one_round). MEASURED (sweep_shortlist4, real
    # TPU, config #4 10k x 5k): the shortlist LOSES at this geometry —
    # 212 ms vs 158 ms wide — because the per-pass saving (~0.5 ms; the
    # [B,N] pass chain is bandwidth-cheap at N=5k) is smaller than the
    # added per-round top_k (~6.5 ms at [10k,5k]) and per-pass [B,k]
    # anchor-delta gathers (~1.7 ms). Default therefore 0 (wide). The
    # path is kept, tested, for geometries where N dwarfs the pass
    # count's bandwidth economics (N >> 5k). ROUNDING CAVEAT (advisor
    # r4): the shortlist scores round(base)+tie+round(delta) while the
    # wide path scores round(base+delta)+tie — the two roundings can
    # differ by 1, so node CHOICES may diverge from the wide engine
    # beyond the top-k approximation itself (heuristic-only; the
    # unplaced=>infeasible invariant is unaffected).
    anchor_stride: int = 1,  # re-anchor every pass (the spread signal
    # is load-bearing: stride 2 cost ~19% of round-0 acceptance in the
    # same sweep)
    compact_gather: str = "rows",  # how compacted rounds fetch the
    # active rows of the [P, N] static base: "rows" = row-gather (fast
    # single-chip; under GSPMD it makes XLA all-gather the FULL [P, N]
    # sbase per round — 200 MB at config #4); "onehot" = one-hot [B, P]
    # matmul (exact: one 1.0 per row, f32) whose contraction runs over
    # the sharded pods axis, so the mesh path pays one small [B, N]
    # all-reduce instead. The sharded build selects "onehot".
    score_anchor_fn: Callable | None = None,  # node_requested -> f32 [N]
    # capacity-sensitive node-local score component (Framework.score_anchor)
    pv_choice_fn: Callable | None = None,  # (vsnap, node_of, live, ext)
    # -> i32 [B, MVol] chosen static PV per claimant/slot (-1 none): the
    # guard arbitrates same-round claimants of one PV by rank
    mesh=None,  # jax.sharding.Mesh | None — the collective-payload
    # diet's sharding hint: with a mesh, the compacted per-round [B, N]
    # views carry an explicit with_sharding_constraint over the mesh
    # axes (parallel/mesh.MESH_AXES), so the one-hot compaction's psum
    # lowers to a reduce-scatter of the PARTITIONED view instead of
    # all-reducing a replicated [B, N] (the largest single collective of
    # the sharded cycle before the diet; its cost on the chip is not
    # measured). None (the default, and every single-device build)
    # changes nothing.
    sample=None,  # (off i32 [P], k i32 []) | None — percentageOfNodesTo-
    # Score (core/cycle.node_sample): each round, a pod's candidates are
    # the first k nodes FEASIBLE FOR IT IN THAT ROUND'S STATE, in its
    # rotation order from off (ops/sampling.py); all of them when there
    # are fewer. A pod whose sample fills up in-round meets a fresh
    # sample of what is still feasible next round, so a zero-accept
    # round still means every active pod's mask was empty: the
    # "unplaced => infeasible against the final state" invariant holds
    # as without sampling.
) -> RoundsResult:
    """Commit the pending set in rounds (the module docstring has the
    round itself). What the loop over rounds guarantees:

    **At the end of a cycle every unplaced valid pod has had a full-mask
    check against a state no later acceptance changed, or is parked by a
    reason no acceptance can lift; `max_rounds` is reached only where
    acceptances themselves keep coming.**

    Within one cycle acceptances only consume room and add pods. A node
    closed to a pod by a static filter, by NodeResourcesFit, NodePorts,
    a claimed PV or required anti-affinity therefore stays closed; only
    required affinity (a peer arrives) and DoNotSchedule spread (the
    minimum rises) can OPEN one. `closed_for_cycle_fn` says, from the
    masks of the round that judged it, whether a pod has no node left
    once the filters that can reopen FOR THAT POD are taken as open. Such
    a pod is PARKED: it leaves `active` for the rest of the cycle, so it
    never fills a row of the compacted window, never counts towards the
    sweep below and is returned unplaced. Every other active pod is
    judged as before. Without parking, about B pods that fit nowhere,
    ranked before the feasible leftovers of round 1, held the window:
    a round accepted a handful or the cap ended the cycle, and pods that
    fit hundreds of nodes were refused (PERF.md section 6, PR 36).

    On inputs where no pod parks, the window, the scores, the tie-break,
    the guards and every placement are bit for bit what they were.

    **One spread group is filled level by level within a round.** For a
    (topology key, selector) group let `cnt_d` be domain d's count at
    the round's start and `a_d` the capacity-accepted claimants that
    arrive in d. The level the round reaches is `m' = min over eligible
    d of (cnt_d + a_d)` (a domain nobody claims holds it at `cnt_d`),
    and d accepts, in rank order, the claimants with fewer than
    `maxSkew + m' - cnt_d` matching arrivals ranked before them. Every
    domain then ends at `m'` or above, so each accepted claim met
    `count + 1 - minimum <= maxSkew` in the serial order that places
    into the lowest domain first, and where every carrier has the same
    `maxSkew` no domain a pod went to ends more than `maxSkew` above the
    minimum. (The engine before held d to `maxSkew + minimum - cnt_d`
    at the round's START: one acceptance a zone a round at `maxSkew` 1,
    so a group of 2,000 ended at `max_rounds` with ~1,600 refused beside
    open nodes, ISSUE 46.)
    A claim another guard revokes in the same sweep arrives nowhere and
    would lower the true level, so the OTHER GUARDS ARE SETTLED FIRST:
    `a_d` and the places in rank order count only claims they left
    standing, and of those only the sure ones (`spread_level_fill`: no
    DoNotSchedule constraint, or exactly one, on this very group, that
    passes at the level; the level is tried and, where the sure
    arrivals do not carry it, the group falls back to the round's
    start). For `m'` to rise, claims have to ARRIVE in every domain up
    to it: `reach_mask_fn` lets a group's claimants claim every domain
    below the line that pouring all of them into the group gives
    (`interpod.spread_reach`), each within one domain drawn with odds
    as the pouring's shares. Claims by that wider mask are only ever
    accepted through the guard; a round of them that accepts nothing
    proves nothing, and the window is judged again by the filters' own
    mask before the sweep steps on (`body`).

    On inputs where no spread rule binds (the wider mask is wider for
    no row, and no claim stands at or past its domain's room),
    placements stay bit for bit what they were; where one binds they
    may differ, and `ENGINE_MARK` differs either way. `round_cap_hit`
    and `spread_revoked` are the two counts by which a cycle that ends
    at `max_rounds`, and the guard's work, are seen."""
    P, N = (sbase if sbase is not None else static_mask).shape
    S = m_pending.shape[0]
    D = snap.domain_key.shape[0]
    K = snap.node_domains.shape[1]
    MA = snap.pod_anti_terms.shape[1]
    MC = snap.pod_tsc.shape[1]
    Q = snap.num_distinct_ports
    MPorts = snap.pod_port_ids.shape[1]

    rank_g = snap.pod_order.astype(jnp.int32)  # [P] lower = earlier

    # guard-active selectors (static per cycle)
    anti_active, spread_active = interpod_ops.selector_activity(snap)
    aff_used = (
        jnp.zeros((S,), bool)
        .at[jnp.clip(snap.pod_aff_terms[..., 0].reshape(-1), 0, S - 1)]
        .max(snap.pod_aff_terms[..., 0].reshape(-1) >= 0)
    )
    active_sel = anti_active | spread_active | aff_used
    matched_sels_g, overflow_g = _matched_active(
        m_pending, active_sel, MS_MATCH
    )

    has_guards = bool(snap.has_inter_pod_affinity or snap.has_topology_spread)
    has_port_guards = bool(Q > 0)

    # group-key space: domain groups, per-selector global groups,
    # (node, port) groups, static-PV groups, invalid
    GK_GLOBAL = S * (D + 1)
    GK_PORT = GK_GLOBAL + S
    GK_PV = GK_PORT + N * Q
    V = snap.pv_avail.shape[0]
    GK_INVALID = GK_PV + V + 1
    has_pv_guards = bool(snap.has_volumes and pv_choice_fn is not None)

    def shard_view(arr):
        """Constrain a compacted [B, ...] view onto the mesh axes
        (row dim on 'pods', a second dim on 'nodes' when present and
        divisible — parallel/mesh.mesh_pin owns the rule). Identity
        without a mesh."""
        if mesh is None:
            return arr
        return mesh_pin(arr, mesh, MESH_AXES)

    def local_update_fn(fn):
        """Force the per-round plugin-state update to run device-LOCAL
        on a mesh (identity without one). The update contracts [B, S]/
        [B, D] one-hots over the claims axis; left to GSPMD those dots
        get contraction-sharded — each device computes a partial and
        all-reduces the FULL [S, N]/[S, D] count tables, 58 MB/cycle at
        the audit shape even with every input pinned replicated (the
        partitioner trades our per-cycle payload for FLOP spread).
        shard_map admits no such choice: inputs arrive replicated
        (kilobyte-scale [B, ...] vectors — shard_map inserts the tiny
        gathers itself), every device computes the identical full
        update, zero collectives inside."""
        if mesh is None:
            return fn
        return jax.shard_map(
            fn, mesh=mesh,
            in_specs=(PartitionSpec(),) * 5,  # schedlint: disable=SH003 -- shard_map plumbing: the EMPTY spec (replicated) carries no layout rule, it marks these inputs as not-mesh_pin's-business
            out_specs=PartitionSpec(),  # schedlint: disable=SH003 -- same replicated shard_map plumbing as the line above
            check_vma=False,
        )

    slack = _REL_EPS * snap.node_allocatable + _REL_EPS  # [N, R]
    # static mask+score pre-combined; scores clamp to +-1e6 (far above any
    # plugin-weight scale, far below |NEG_INF|/2) so an extreme extender
    # score can never push a feasible node across the infeasible threshold
    # the compacted rounds reconstruct the mask with (vsbase > NEG_INF/2)
    if sbase is None:
        sbase = jnp.where(
            static_mask, jnp.clip(static_score, -1e6, 1e6), NEG_INF
        )  # [P, N]

    def guards_ok(vsnap, vmp, vrank, vsels, choice, live, ext_state):
        """Participant-table sweep over the round's accepted claims;
        (ok bool [B], claims the spread guard revoked i32 []). Within a
        (selector/port, domain/node) group, entries resolve in rank
        order — the same outcome a sequential pass over the claims
        would produce. The spread guard is settled AFTER every other
        guard, over the claims those left standing (see
        `spread_level_fill`)."""
        B = vrank.shape[0]
        state = _owner_state(ext_state) if has_guards else None
        if state is None and not has_port_guards and not has_pv_guards:
            return jnp.ones((B,), bool), jnp.int32(0)
        nsafe = jnp.clip(choice, 0, N - 1)

        keys, role_ids = [], []
        spread_terms = []  # per constraint slot: (emit slot, hard bool
        # [B], group row i32 [B], skew - count at the round's start f32 [B])

        def emit(key, valid, role):
            keys.append(jnp.where(valid & live, key, GK_INVALID))
            role_ids.append(role)

        if state is not None:
            # each capability pays only for its own machinery: affinity-
            # only clusters never trace the spread sections and vice versa
            # (the encoder's capability-flag convention)
            node_dom = snap.node_domains[nsafe]  # [B, K]
            boot_active = state.total == 0  # [S]
            if snap.has_inter_pod_affinity:
                for a in range(MA):
                    sel = vsnap.pod_anti_terms[:, a, 0]
                    k = jnp.clip(vsnap.pod_anti_terms[:, a, 1], 0, K - 1)
                    d = jnp.take_along_axis(node_dom, k[:, None], 1)[:, 0]
                    key = jnp.clip(sel, 0, S - 1) * (D + 1) + (d + 1)
                    emit(key, (sel >= 0) & (d >= 0), _RB_ANTI)
                for a in range(MA):
                    sel = vsnap.pod_aff_terms[:, a, 0]
                    scl = jnp.clip(sel, 0, S - 1)
                    emit(GK_GLOBAL + scl, (sel >= 0) & boot_active[scl],
                         _RB_BOOT)
            if snap.has_topology_spread:
                for c in range(MC):
                    k = vsnap.pod_tsc[:, c, 0]
                    sel = vsnap.pod_tsc[:, c, 1]
                    when = vsnap.pod_tsc[:, c, 2]
                    kcl = jnp.clip(k, 0, K - 1)
                    d = jnp.take_along_axis(node_dom, kcl[:, None], 1)[:, 0]
                    scl = jnp.clip(sel, 0, S - 1)
                    hard = (k >= 0) & (when == enc.WHEN_DO_NOT_SCHEDULE) & (
                        d >= 0
                    )
                    cnt = state.counts[scl, jnp.clip(d, 0, D - 1)]  # [B]
                    spread_terms.append((
                        len(keys), hard & live, kcl * S + scl,
                        vsnap.pod_tsc_skew[:, c].astype(jnp.float32) - cnt,
                    ))
                    emit(scl * (D + 1) + (d + 1), hard, _RB_SPREAD)
            # matchers feed the anti guard AND the spread arrival counts —
            # needed whenever either capability is on
            for m in range(MS_MATCH):
                sel = vsels[:, m]
                scl = jnp.clip(sel, 0, S - 1)
                for k in range(K):
                    d = node_dom[:, k]
                    emit(scl * (D + 1) + (d + 1), (sel >= 0) & (d >= 0),
                         _RB_MATCH)
                if snap.has_inter_pod_affinity:
                    emit(GK_GLOBAL + scl, (sel >= 0) & boot_active[scl],
                         _RB_GMATCH)
        if has_port_guards:
            for j in range(MPorts):
                ids = vsnap.pod_port_ids[:, j]
                key = GK_PORT + nsafe * Q + jnp.clip(ids, 0, Q - 1)
                emit(key, ids >= 0, _RB_PORT)
        if has_pv_guards:
            # one entry per (claimant, volume slot) naming the static PV
            # the claim would bind; first rank per PV survives
            pvc = pv_choice_fn(vsnap, nsafe, live, ext_state)  # [B, MVol]
            for j in range(pvc.shape[1]):
                ids = pvc[:, j]
                emit(GK_PV + jnp.clip(ids, 0, V - 1), ids >= 0, _RB_PV)

        # stack+reshape, NOT jnp.concatenate: on a multi-axis mesh this
        # jaxlib's SPMD partitioner miscompiles an axis-0 concatenate of
        # 1-D pods-sharded integer vectors — the partially-replicated
        # operands are summed over the free ('nodes') axis, so every
        # value comes back multiplied by that axis size (minimal repro:
        # tests/test_shard_invariance.py::test_sharded_concat_workaround
        # — THIS, not reduce tie order, was the real source of the 2-D
        # mesh guard divergence behind the old dryrun_multichip_8 xfail;
        # stack+reshape takes the safe partitioner path and is the same
        # piece-major layout)
        keys_c = jnp.stack(keys, axis=0).reshape(-1)
        n_emit = len(keys)
        ranks_c = jnp.tile(vrank, n_emit)
        # Collective-payload diet: the claimant id and role of table
        # entry j are FUNCTIONS of position (claimant j % B of emit slot
        # j // B; roles are per-slot trace constants), so the sweep
        # gathers NEITHER through the sort — the permutation alone
        # reconstructs pods/roles, and a spread entry's place in its
        # domain goes back to its (slot, claimant) the same way. (The
        # old stacked [L, 3] payload gather was the audit's
        # s32[283136,3] all-reduce — 3.4 MB at the P=10112 shape — for
        # data the sort result already encodes.)
        role_tab = jnp.asarray(role_ids, jnp.int32)  # [n_emit] constant

        # The participant-table sort dominates the sweep. When (key, rank)
        # fits one u32 word, sort a SINGLE packed operand plus an iota
        # permutation — a multi-key sort costs ~2x the packed one at
        # L≈290k, and per-column 1-D gathers are ~2ms each on this backend.
        rank_space = 1 << int(P - 1).bit_length()  # active ranks are < P
        # minimal index width for the sort's permutation operand (the
        # compacted table fits i16; round 0's P-scale table takes i32)
        iota = jnp.arange(
            keys_c.shape[0], dtype=argsel.index_dtype(keys_c.shape[0])
        )
        if (GK_INVALID + 1) * rank_space <= 2**32:
            # padded/inactive rows carry rank INT32_MAX (pod_order pad);
            # clamp so they cannot wrap the key bits (their key is
            # GK_INVALID, so relative order among them is irrelevant)
            packed = (
                keys_c.astype(jnp.uint32) * jnp.uint32(rank_space)
                + jnp.minimum(ranks_c, rank_space - 1).astype(jnp.uint32)
            )
            packed_s, perm = jax.lax.sort((packed, iota), num_keys=1)
            keys_s = (packed_s // jnp.uint32(rank_space)).astype(jnp.int32)
        else:
            keys_s, _ranks_s, perm = jax.lax.sort(
                (keys_c, ranks_c, iota), num_keys=2
            )
        slot = perm // B
        pods_s = perm - slot * B
        role_s = role_tab[slot]
        before = _seg_scan_tables(
            keys_s, pods_s,
            {
                "match": (role_s == _RB_MATCH).astype(jnp.int32),
                "anti": (role_s == _RB_ANTI).astype(jnp.int32),
                "boot": (role_s == _RB_BOOT).astype(jnp.int32),
                "gmatch": (role_s == _RB_GMATCH).astype(jnp.int32),
                "port": (role_s == _RB_PORT).astype(jnp.int32),
                "pv": (role_s == _RB_PV).astype(jnp.int32),
            },
        )
        ok_e = jnp.ones(keys_s.shape, bool)
        ok_e &= jnp.where(role_s == _RB_ANTI, before["match"] == 0, True)
        ok_e &= jnp.where(role_s == _RB_MATCH, before["anti"] == 0, True)
        ok_e &= jnp.where(
            role_s == _RB_BOOT,
            (before["boot"] == 0) & (before["gmatch"] == 0),
            True,
        )
        ok_e &= jnp.where(role_s == _RB_PORT, before["port"] == 0, True)
        ok_e &= jnp.where(role_s == _RB_PV, before["pv"] == 0, True)
        ok_e |= keys_s == GK_INVALID
        ok_pod = (
            jnp.ones((B,), jnp.int32).at[pods_s].min(ok_e.astype(jnp.int32))
        ) > 0
        if not spread_terms:
            return ok_pod, jnp.int32(0)
        # a spread claimant's place in its (selector, domain): the
        # matching arrivals ranked before it that the other guards left
        # standing (a claim they revoked arrives nowhere), sent back
        # from the sorted table to its (slot, claimant)
        ahead_s = _seg_scan_tables(
            keys_s, pods_s,
            {"m": ((role_s == _RB_MATCH) & ok_pod[pods_s])
             .astype(jnp.int32)},
        )["m"]
        ahead = (
            jnp.zeros(keys_s.shape, jnp.int32)
            .at[perm].set(ahead_s, unique_indices=True)
            .reshape(n_emit, B)
        )
        ok_spread = spread_level_fill(
            vmp, node_dom, live & ok_pod, state,
            [(hard, row, room, ahead[sl].astype(jnp.float32))
             for sl, hard, row, room in spread_terms],
        )
        n_revoked = jnp.sum(live & ok_pod & ~ok_spread, dtype=jnp.int32)
        return ok_pod & ok_spread, n_revoked

    def spread_level_fill(vmp, node_dom, standing, state, terms):
        """The spread guard: ok bool [B] over the claims `standing`
        after every other guard. `terms` holds, per constraint slot,
        (hard bool [B], the (key, selector) row i32 [B], `room` =
        maxSkew less the domain's count at the round's start f32 [B],
        `ahead` = standing matching arrivals of the same (selector,
        domain) ranked before the claimant f32 [B]).

        A claimant is held to `ahead < room + level`, the level being
        what the group's minimum is SURE to reach in this round (the
        docstring of `rounds_commit` has the rule and why it is sound).
        Sure arrivals of a (selector, domain) are the standing
        claimants that match the selector and carry no DoNotSchedule
        constraint at all, and those whose ONE such constraint is on
        this very group and passes at the level tried. The level tried
        is the minimum, over the group's eligible domains, of count +
        every such claimant, passing or not; it stands if the minimum
        over count + the sure arrivals at that level comes out no
        lower, and else the group falls back to the minimum at the
        round's start for this round. A claimant with two or more such
        constraints, or one on another group than it is counted in, is
        never a sure arrival (it may be revoked elsewhere) and is
        itself held to the round's start: conservative, and rare."""
        n_hard = sum(t[0].astype(jnp.int32) for t in terms)
        free = standing & (n_hard == 0)
        single = standing & (n_hard == 1)
        # a single claimant's one group (garbage where not single)
        own = sum(jnp.where(t[0], t[1], 0) for t in terms)
        s_ids = jnp.arange(S, dtype=jnp.int32)[:, None]
        d_ids = jnp.arange(D, dtype=jnp.int32)[None, :]
        oh_d = [
            (node_dom[:, k][:, None] == d_ids).astype(jnp.float32)
            for k in range(K)
        ]  # K x [B, D]; a node without the key (-1) lands nowhere

        def level(sure_single):
            """min over eligible domains of count + sure arrivals,
            f32 [K*S]."""
            arrive = jnp.zeros((S, D), jnp.float32)
            for k in range(K):
                mine = sure_single[None, :] & (own[None, :] == k * S + s_ids)
                arrive = arrive + jax.lax.dot(
                    (vmp & (free[None, :] | mine)).astype(jnp.float32),
                    oh_d[k],
                )
            return interpod_ops.spread_minc(
                snap, dataclasses.replace(state, counts=state.counts + arrive)
            )

        def passes(lvl_of):
            ok = jnp.ones(standing.shape, bool)
            for hard, row, room, ahead in terms:
                ok &= ~hard | (ahead < room + lvl_of(row))
            return ok

        start = interpod_ops.spread_minc(snap, state)
        tried = level(single)
        holds = level(single & passes(lambda row: tried[row])) >= tried
        lvl = jnp.where(holds, tried, start)
        return passes(
            lambda row: jnp.where(single, lvl[row], start[row])
        )

    def one_round(gid, act_v, node_req, ext, passes: int, reach, rnd,
                  identity_gid: bool = False):
        """One round over the pods in `gid` (global ids; `act_v` marks
        which rows are genuinely active; `reach` (bool []) lets claims
        go by `reach_mask_fn`'s wider mask; `rnd` is the round's number).

        The round computes plugin masks/scores ONCE, then runs `passes`
        CAPACITY-ONLY acceptance passes: in each pass every
        still-unaccepted pod claims its best node (score re-anchored to
        the in-round node_req) among choices not yet known-dead, claims
        resolve by a (node, rank) capacity prefix, and losers that no
        longer fit the node alone mark the choice dead and fall to their
        next-best node next pass — without waiting a full dyn recompute.
        ONE guard sweep at round end checks every capacity-accepted claim
        for mutual consistency (original ranks decide within a group) and
        REVOKES violators, who retry next round against refreshed
        masks.

        With `shortlist` > 0 the passes run over a per-pod top-k
        candidate SHORTLIST of the round-start scores ([B, k] — top_k is
        one bandwidth-bound read of the scored array, while each wide
        pass re-materialized several [B, N] arrays plus a [B, N]
        dead-scatter). A pod whose entire shortlist dies in-round waits
        for the RESCUE pass: one wide pass, entered via lax.cond only
        when some active pod is mask-feasible but shortlist-exhausted,
        which restores the engine's invariant that a round accepts at
        least one claim whenever any active pod is feasible — so loop
        termination still implies every unplaced pod is infeasible
        against the final state (oracle.validate_rounds_assignment)."""
        B = gid.shape[0]
        if identity_gid:
            # round 0: gid is the identity permutation — indexing with
            # it is not always elided by XLA, and under GSPMD the
            # residual gather all-gathers the full sharded [P, N] base
            vsnap, vmp, vsbase = snap, m_pending, sbase
            vrank, vsels, vovf = rank_g, matched_sels_g, overflow_g
        else:
            vsnap = _pod_view(snap, gid)
            vmp = m_pending[:, gid]
            # static mask+score travel as ONE pre-combined f32 array
            # (score where feasible, NEG_INF where not): compacted
            # rounds pay a single [B, N] row-gather instead of two
            # (~2ms each at 10k x 5k)
            if compact_gather == "onehot":
                oh = jax.nn.one_hot(gid, P, dtype=jnp.float32)  # [B, P]
                vsbase = jnp.matmul(
                    oh, sbase, precision=jax.lax.Precision.HIGHEST
                )
                # with a mesh, pin the compacted view SHARDED: the
                # contraction over the pods axis then lowers to a
                # reduce-scatter of the partitioned [B, N] view instead
                # of all-reducing a replicated one — at the audit shape
                # that single collective was over half the cycle's
                # payload (scripts/audit_sharded.py counts it from the
                # compiled HLO; not measured on the chip)
                vsbase = shard_view(vsbase)
            else:
                vsbase = sbase[gid]
            vrank = rank_g[gid]
            vsels = matched_sels_g[gid]
            vovf = overflow_g[gid]
        vsmask = vsbase > NEG_INF * 0.5

        mask, score, per_filter = dyn_batched_view_fn(
            vsnap, vmp, node_req, ext, vsmask
        )
        # judged on the round-start state, before the sample narrows the
        # mask: what is closed for the cycle is closed on every node
        parked = act_v & vsnap.pod_valid & closed_for_cycle_fn(
            vsnap, vmp, vsmask, per_filter
        )
        mask = mask & vsmask & act_v[:, None]
        # where claims may go: the filters' own mask, or with `reach`
        # the wider one (a spread group's domains up to the level its
        # claimants can lift the minimum to within this round; the
        # guard sweep holds every acceptance to the skew). `reached`
        # says the wider mask was in force AND was wider for some row:
        # only then can a round without an acceptance have passed over
        # a node the filters' own mask held open
        reached = jnp.zeros((), bool)
        wide = (
            reach_mask_fn(vsnap, vmp, node_req, ext, vsmask, per_filter,
                          act_v)
            if reach_mask_fn is not None else None
        )
        if wide is not None:
            wide, share, domain = wide
            wide = wide & act_v[:, None]
            # the rows the wider mask is wider FOR: a pod no rule holds
            # back claims as it always did, bit for bit
            bound = reach & jnp.any(wide & ~mask, axis=1)  # [B]
            reached = jnp.any(bound)
            # Such a pod claims within ONE domain, drawn with odds as
            # the domains' shares (the largest of log(u) / share, u a
            # hash of (pod, domain, round): weighted sampling without a
            # table), and the best node by score within it. Left to the
            # scores, a group's claimants crowd the domains whose nodes
            # look best, the others draw nobody, their count holds the
            # level down and the guard revokes nearly every claim. A
            # fresh draw every round: a claim revoked in a domain the
            # round could not open after all goes elsewhere next time.
            h = (
                gid.astype(jnp.uint32)[:, None] * _PR1
                + (domain.astype(jnp.uint32) + 1) * _PR3
                + (rnd.astype(jnp.uint32) + 1) * _PR4
            )
            h = (h ^ (h >> 16)) * _PR3
            h = (h ^ (h >> 13)) * _PR4
            h = h ^ (h >> 16)
            u = ((h >> 8).astype(jnp.float32) + 0.5) * (1.0 / (1 << 24))
            draw = jnp.where(wide, jnp.log(u) / (share + 0.5), -jnp.inf)
            drawn = wide & (draw >= jnp.max(draw, axis=1, keepdims=True))
            mask = jnp.where(bound[:, None], drawn, mask)
        narrowed = None
        if sample is not None:
            off, k = sample
            sampled, narrowed = sampling.sample_feasible(
                mask, off if identity_gid else off[gid], k
            )
            # upstream evaluates the nominated node before the walk: a
            # feasible one stays claimable wherever the sample ended
            # (pod_nominated is -1 where there is none: no column)
            col = jax.lax.broadcasted_iota(jnp.int32, (1, N), 1)
            mask = sampled | (
                mask & (col == vsnap.pod_nominated[:, None])
            )
        base = vsbase + score  # un-rounded; claim ranking re-rounds with
        # the per-pass anchor delta applied (see score_node_anchor)
        tie = _tie_break(gid, N)
        anchor0 = (
            score_anchor_fn(node_req) if score_anchor_fn is not None else None
        )
        pid = jnp.arange(B, dtype=jnp.int32)
        i = jnp.arange(B, dtype=jnp.int32)
        nom = jnp.clip(vsnap.pod_nominated, 0, N - 1)
        has_nom = vsnap.pod_nominated >= 0

        def resolve_capacity(live, best, node_req):
            """Rank-ordered capacity resolution of one pass's claims
            (sorted segmented prefix vs in-round state): returns
            (accepted bool [B], node_req'). Passes accept on capacity
            ONLY; the guard sweep runs once at round end over all
            capacity-accepted claims and revokes violators — guards are
            ~5% of rejections but the table sort is the dominant
            per-pass cost, so it must not run per pass.

            The (node, rank) sort key is PACKED into one u32 when it
            fits (N+1 node values x a pow2 rank space) — the sorted key
            then carries s_node/s_live for free, so the sort's
            partitioned all-gather moves (key, iota) instead of the old
            (key, iota) + two post-sort [B] row-gathers. Beyond u32
            range (the 100k-pod x 50k-node bench grid: the old
            `best * P + vrank` i32 key silently WRAPPED there) a 2-key
            sort keeps exact lexicographic order at any scale."""
            rank_space = 1 << int(P - 1).bit_length()  # ranks are < P
            nkey = jnp.where(live, best, N).astype(jnp.uint32)
            rkey = jnp.minimum(vrank, rank_space - 1).astype(jnp.uint32)
            # minimal index width: the permutation operand rides the
            # sort's partitioned all-gather (i16 halves it when B fits)
            bidx = jnp.arange(B, dtype=argsel.index_dtype(B))
            if (N + 1) * rank_space <= 2**32:
                packed = nkey * jnp.uint32(rank_space) + rkey
                packed_s, order = jax.lax.sort(
                    (packed, bidx), num_keys=1
                )
                s_node = (packed_s // jnp.uint32(rank_space)).astype(
                    jnp.int32
                )
            else:
                s_nkey, _s_rkey, order = jax.lax.sort(
                    (nkey, rkey, bidx), num_keys=2
                )
                s_node = s_nkey.astype(jnp.int32)
            s_live = s_node < N  # live claims carry a real node id
            s_req = jnp.where(
                s_live[:, None], vsnap.pod_requested[order], 0.0
            )
            cum = jnp.cumsum(s_req, axis=0)
            before = cum - s_req
            seg_start = jnp.concatenate(  # schedlint: disable=SH002 -- s_node is lax.sort output, which GSPMD materializes replicated here (the sort's all-gather is the audited claim_sort payload); the shard-invariance suite pins this bit-exact at devices 1-8
                [jnp.ones((1,), bool), s_node[1:] != s_node[:-1]]
            )
            seg_first = jax.lax.cummax(jnp.where(seg_start, i, -1))
            seg_before = before - before[seg_first]
            nsafe = jnp.clip(s_node, 0, N - 1)
            free = (
                snap.node_allocatable[nsafe] - node_req[nsafe]
                + slack[nsafe]
            )
            fits = jnp.all(seg_before + s_req <= free, axis=1) & s_live
            accepted_t = jnp.zeros((B,), bool).at[order].set(fits)
            node_of_t = jnp.where(accepted_t, best, 0)
            req_add = jnp.where(
                accepted_t[:, None], vsnap.pod_requested, 0.0
            )
            # one-hot matmul instead of scatter-add: 0.27 vs 1.14 ms at
            # B=10k (probe_shortlist_prims) and this runs once per pass;
            # 0/1 x f32 products are exact, accumulation order differs
            # from a sequential scatter only in fp summation order
            oh = jax.nn.one_hot(node_of_t, N, dtype=jnp.float32)
            node_req = node_req + jnp.matmul(
                oh.T, req_add, precision=jax.lax.Precision.HIGHEST
            )
            return accepted_t, node_req

        def fits_alone_at(best, node_req):
            # A capacity loser keeps the node alive if it still fits
            # ALONE in the node's post-pass free space: the segmented
            # prefix charges REJECTED earlier-rank claims too (a huge
            # non-fitting claim shadows smaller ones behind it), so such
            # losers retry next pass once the contenders settle.
            bsafe = jnp.clip(best, 0, N - 1)
            return jnp.all(
                vsnap.pod_requested
                <= snap.node_allocatable[bsafe] - node_req[bsafe]
                + slack[bsafe],
                axis=1,
            )

        def pick_overflow(has, acc, normal):
            # Overflow claimants (matching more guard-active selectors
            # than the MS_MATCH table tracks) are invisible to other
            # claims' guard checks, so one may only be accepted in a
            # round that accepts NOTHING else: lowest rank, alone, iff
            # the round is still empty-handed.
            allow_ovf = ~jnp.any(acc) & ~jnp.any(normal)
            ovf_rank = jnp.min(jnp.where(has & vovf, vrank, _BIG))
            return has & vovf & (vrank == ovf_rank) & allow_ovf

        acc = jnp.zeros((B,), bool)
        acc_node = jnp.full((B,), -1, jnp.int32)
        diag = jnp.zeros((3,), jnp.int32)
        use_sl = 0 < shortlist < N

        if use_sl:
            k = shortlist
            scored0 = jnp.where(mask, jnp.round(base) + tie, NEG_INF)
            # shard-invariant top_k (ops/argsel.py): equal-score entries
            # keep the lowest-index-first order at ANY device count —
            # lax.top_k's partitioned form merges ties shard-locally
            vals, sl = argsel.top_k_first(scored0, k)  # [B, k]
            # the nominated node (post-preemption) must be claimable even
            # when outside the top-k: force it into the last column (and
            # NEG_INF any earlier duplicate so a dead node is not offered
            # twice)
            nom_val = jnp.take_along_axis(scored0, nom[:, None], 1)[:, 0]
            vals = jnp.where(
                has_nom[:, None] & (sl == nom[:, None]), NEG_INF, vals
            )
            sl = sl.at[:, k - 1].set(jnp.where(has_nom, nom, sl[:, k - 1]))
            vals = vals.at[:, k - 1].set(
                jnp.where(has_nom, nom_val, vals[:, k - 1])
            )
            sl_ok = vals > NEG_INF * 0.5
            dead = jnp.zeros((B, k), bool)
            # the [B*k] anchor-delta gather is ~1.7 ms at B=10k;
            # anchor_stride > 1 trades acceptance for that gather (one
            # pass of staleness ages the spread signal — measured -19%
            # round-0 acceptance at stride 2)
            delta_stride = max(1, anchor_stride)
            dsl = jnp.zeros((B, k), jnp.float32)
            for t in range(passes):
                avail = sl_ok & ~dead & ~acc[:, None]
                if anchor0 is not None and t > 0:
                    # nodes that filled this round lose attractiveness
                    # NOW — the spread mechanism sequential scheduling
                    # gets from per-pod score freshness; the delta rides
                    # a [B*k] gather from the [N] anchor vector
                    if (t - 1) % delta_stride == 0:
                        delta = jnp.round(
                            score_anchor_fn(node_req) - anchor0
                        )
                        dsl = delta[sl.reshape(-1)].reshape(B, k)
                    eff = jnp.where(avail, vals + dsl, NEG_INF)
                else:
                    eff = jnp.where(avail, vals, NEG_INF)
                bj = argsel.argmax_first(eff, axis=1)
                nom_ok = has_nom & avail[:, k - 1]
                bj = jnp.where(nom_ok, k - 1, bj)
                best = jnp.take_along_axis(sl, bj[:, None], 1)[:, 0]
                has = (
                    jnp.take_along_axis(avail, bj[:, None], 1)[:, 0]
                    & act_v & vsnap.pod_valid & ~acc
                )
                normal = has & ~vovf
                ovf_pick = (
                    pick_overflow(has, acc, normal)
                    if t == passes - 1
                    else jnp.zeros_like(normal)
                )
                live = normal | ovf_pick
                accepted_t, node_req = resolve_capacity(live, best,
                                                        node_req)
                acc = acc | accepted_t
                acc_node = jnp.where(accepted_t, best, acc_node)
                dead = dead.at[pid, bj].max(
                    live & ~accepted_t & ~fits_alone_at(best, node_req)
                )
                diag = diag + jnp.stack([
                    jnp.sum(live, dtype=jnp.int32),
                    jnp.sum(live & ~accepted_t, dtype=jnp.int32),
                    jnp.zeros((), jnp.int32),
                ])

            # ---- rescue pass (shortlist-exhaustion escape hatch) ----
            # Runs only when some active pod is feasible by this round's
            # mask yet has no live shortlist entry left; one wide pass
            # over the full mask for exactly those pods. Guarantees a
            # zero-accept round implies every active pod's mask was
            # empty — the invariant the validity checker relies on.
            feas0 = jnp.any(mask, axis=1)
            exhausted = (
                act_v & vsnap.pod_valid & ~acc & feas0
                & ~jnp.any(sl_ok & ~dead, axis=1)
            )

            def rescue(op):
                acc, acc_node, node_req, diag = op
                if anchor0 is not None:
                    delta = score_anchor_fn(node_req) - anchor0
                    scored = jnp.round(base + delta[None, :]) + tie
                else:
                    scored = jnp.round(base) + tie
                avail = mask & ~acc[:, None]
                eff = jnp.where(avail, scored, NEG_INF)
                best = argsel.argmax_first(eff, axis=1)
                r_nom_ok = has_nom & avail[pid, nom]
                best = jnp.where(r_nom_ok, nom, best)
                has = avail[pid, best] & exhausted
                normal = has & ~vovf
                ovf_pick = pick_overflow(has, acc, normal)
                live = normal | ovf_pick
                accepted_t, node_req = resolve_capacity(live, best,
                                                        node_req)
                acc = acc | accepted_t
                acc_node = jnp.where(accepted_t, best, acc_node)
                diag = diag + jnp.stack([
                    jnp.sum(live, dtype=jnp.int32),
                    jnp.sum(live & ~accepted_t, dtype=jnp.int32),
                    jnp.zeros((), jnp.int32),
                ])
                return acc, acc_node, node_req, diag

            acc, acc_node, node_req, diag = jax.lax.cond(
                jnp.any(exhausted), rescue, lambda op: op,
                (acc, acc_node, node_req, diag),
            )
        else:
            dead = jnp.zeros((B, N), bool)
            for t in range(passes):
                avail = mask & ~dead & ~acc[:, None]
                if anchor0 is not None and t > 0:
                    # nodes that filled this round lose attractiveness
                    # NOW — the spread mechanism sequential scheduling
                    # gets from per-pod score freshness
                    delta = score_anchor_fn(node_req) - anchor0  # [N]
                    scored = jnp.round(base + delta[None, :]) + tie
                else:
                    scored = jnp.round(base) + tie
                eff_t = jnp.where(avail, scored, NEG_INF)
                nom_ok = has_nom & avail[pid, nom]
                # argmax_first (ops/argsel.py): lowest-index tie-break
                # survives a sharded nodes axis — the shard-exactness
                # contract (sharded == replicated placements bit-
                # identically, test_dryrun_multichip_8)
                best = jnp.where(
                    nom_ok, nom, argsel.argmax_first(eff_t, axis=1)
                ).astype(jnp.int32)
                has = avail[pid, best] & act_v & vsnap.pod_valid & ~acc
                normal = has & ~vovf
                ovf_pick = (
                    pick_overflow(has, acc, normal)
                    if t == passes - 1
                    else jnp.zeros_like(normal)
                )
                live = normal | ovf_pick
                accepted_t, node_req = resolve_capacity(live, best,
                                                        node_req)
                acc = acc | accepted_t
                acc_node = jnp.where(accepted_t, best, acc_node)
                dead = dead.at[pid, best].max(
                    live & ~accepted_t & ~fits_alone_at(best, node_req)
                )
                diag = diag + jnp.stack([
                    jnp.sum(live, dtype=jnp.int32),
                    jnp.sum(live & ~accepted_t, dtype=jnp.int32),
                    jnp.zeros((), jnp.int32),
                ])

        # ---- round-end guard sweep over ALL capacity-accepted claims ----
        # Revoking a violator leaves node_req slightly over-charged for
        # claims accepted after it this round — those stay valid (the node
        # is merely LESS full than they assumed). Revoked pods retry next
        # round; persistent violations (anti slot held by the winner) are
        # then excluded by the refreshed dyn masks.
        g_ok, n_spread = guards_ok(
            vsnap, vmp, vrank, vsels, acc_node, acc, ext
        )
        revoked = acc & ~g_ok
        node_req = node_req.at[jnp.where(revoked, acc_node, 0)].add(
            jnp.where(revoked[:, None], -vsnap.pod_requested, 0.0)
        )
        acc = acc & g_ok
        acc_node = jnp.where(acc, acc_node, -1)
        diag = diag + jnp.stack([
            jnp.zeros((), jnp.int32),
            jnp.zeros((), jnp.int32),
            jnp.sum(revoked, dtype=jnp.int32),
        ])

        ext = local_update_fn(update_batched_view_fn)(
            vsnap, vmp, ext, acc, jnp.where(acc, acc_node, 0)
        )
        return (acc, acc_node, node_req, ext, diag, narrowed, parked,
                reached, n_spread)

    # ---- round 1: full pending set ----
    gid0 = jnp.arange(P, dtype=jnp.int32)
    (acc0, node0, node_req, extra, diag0, narrowed0, parked0, reached0,
     n_spread0) = one_round(
        gid0, snap.pod_valid, snap.node_requested, extra, passes_round0,
        jnp.ones((), bool), jnp.int32(0), identity_gid=True,
    )
    placed = jnp.where(acc0, node0, -1)
    active = snap.pod_valid & ~acc0 & ~parked0
    acc_hist = jnp.zeros((max_rounds,), jnp.int32).at[0].set(
        jnp.sum(acc0, dtype=jnp.int32)
    )
    diag_hist = jnp.zeros((max_rounds, 3), jnp.int32).at[0].set(diag0)

    # ---- rounds 2+: compacted to the lowest-rank actives ----
    # The window holds the B lowest-rank actives. A zero-accept round
    # must NOT terminate the loop while actives remain beyond the
    # window (they may be feasible — the windowed pods can all be
    # stuck on constraints while a higher-rank pod would place; caught
    # by the 500x100 mid-size differential, invisible to <=B-pod toy
    # cases): instead the window ADVANCES by B over the rank order
    # (`skip`). State provably does not change during a zero-accept
    # round (no accepts => no node_req/extra updates, and revocations
    # only touch same-round accepts), so a full zero-accept sweep gives
    # every active pod a genuine full-mask check against what is then
    # the final state — the validity invariant "unplaced => infeasible"
    # holds exactly. Any acceptance resets the sweep to the lowest
    # ranks. A pod parked in a round leaves `active` with its verdict
    # final (see the docstring), so the sweep steps on by the rows that
    # STAY active: the pods ranked after the window move up by as many
    # places as it parked, and none of them is passed over.
    # A round whose claims went by the wider mask (`reached`) and that
    # accepted nothing is NO such check: its pods may have claimed
    # domains the round could not open after all, and passed over nodes
    # the filters' own mask held open. The same window is then judged
    # again by the filters' own mask, and so is every round after it
    # until one accepts (`reach` off): a sweep steps on only past rounds
    # that claimed by the filters' own mask.
    B = compact_window(P, compact)

    def body(carry):
        (node_req, ext, placed, active, rnd, skip, hist, dhist,
         n_parked, reach, n_spread) = carry
        key = jnp.where(active, rank_g, _BIG)
        order = jnp.argsort(key).astype(jnp.int32)
        start = jnp.minimum(skip, jnp.maximum(P - B, 0))
        gid = jax.lax.dynamic_slice(order, (start,), (B,))
        act_v = active[gid]
        (accepted, node_of, node_req, ext, diag, _, parked, reached,
         n_sp) = one_round(gid, act_v, node_req, ext, passes, reach, rnd)
        placed = placed.at[gid].set(jnp.where(accepted, node_of, placed[gid]))
        active = active.at[gid].set(act_v & ~accepted & ~parked)
        n_acc = jnp.sum(accepted, dtype=jnp.int32)
        n_out = jnp.sum(parked, dtype=jnp.int32)
        hist = hist.at[jnp.minimum(rnd, max_rounds - 1)].set(n_acc)
        dhist = dhist.at[jnp.minimum(rnd, max_rounds - 1)].set(diag)
        skip = jnp.where(
            n_acc > 0, jnp.int32(0),
            jnp.where(reached, skip, skip + jnp.int32(B) - n_out),
        )
        reach = (n_acc > 0) | (reach & ~reached)
        return (node_req, ext, placed, active, rnd + 1, skip, hist,
                dhist, n_parked + n_out, reach, n_spread + n_sp)

    def unjudged(active, skip):
        # actives the zero-accept sweep has not passed yet
        return skip < jnp.sum(active, dtype=jnp.int32)

    def cond(carry):
        _, _, _, active, rnd, skip, *_ = carry
        return unjudged(active, skip) & (rnd < max_rounds)

    # round 0 was full-width: if it accepted nothing by the filters' own
    # mask, every pod already had its full-mask check and the sweep is
    # complete (skip = P)
    any0 = jnp.any(acc0)
    skip0 = jnp.where(any0 | reached0, jnp.int32(0), jnp.int32(P))
    (node_req, extra, placed, active, rounds_used, skip, acc_hist,
     diag_hist, n_parked, _, n_spread) = jax.lax.while_loop(
        cond, body,
        (node_req, extra, placed, active, jnp.int32(1), skip0,
         acc_hist, diag_hist, jnp.sum(parked0, dtype=jnp.int32),
         any0 | ~reached0, n_spread0),
    )

    return RoundsResult(
        assignment=placed,
        node_requested=node_req,
        extra=extra,
        rounds_used=rounds_used,
        accepted_per_round=acc_hist,
        diag_per_round=diag_hist,
        parked=n_parked,
        # the loop's own condition, read once more: it ended with
        # actives unjudged, so `max_rounds` ended it
        round_cap_hit=unjudged(active, skip).astype(jnp.int32),
        spread_revoked=n_spread,
        # round 1 judged every valid pod; an invalid row's mask is empty
        sample_narrowed=(
            None if narrowed0 is None
            else jnp.sum(narrowed0, dtype=jnp.int32)
        ),
    )
