"""Preemption as a batched what-if program (SURVEY.md §2 C9, §3.4).

The reference's `DefaultPreemption` PostFilter (expected
`framework/plugins/defaultpreemption/` or `generic_scheduler.go (preempt)`
— [UNVERIFIED], mount empty) runs, per unschedulable pod:

    findCandidates: for each node (parallel goroutines):
        SelectVictimsOnNode: dry-run remove lower-priority pods, re-run
        Filter until the pod fits; re-add highest-priority victims back
        while it still fits (minimize victims)
    pickOneNodeForPreemption: min highest-victim-priority, then min
        priority sum, then fewest victims, then node order
    evict victims, set pod.Status.NominatedNodeName

The TPU-native design exploits the encoder's `node_pods` table: per node,
existing-pod indices sorted ascending by priority, so every candidate
victim set is a PREFIX of that list and the whole
remove/re-add-highest-first minimization collapses to "find the smallest
prefix k whose freed resources make the pod fit" — one cumulative sum plus
a first-true search, vectorized over all nodes at once. Preemptor claims
resolve in two phases: a BATCHED PREFILTER evaluates every budgeted
candidate against the pristine post-cycle state in one [C, N, MPN] pass
and drops those with no feasible preemption node anywhere — exact,
because contention state (`k_claimed` victims already spoken for per
node, `nominated_req` resources nominated pods will consume, spent PDB
budgets) only ever SHRINKS feasibility; then a short `lax.scan` over the
surviving contenders (typically ~the preemptor count, capped at
`scan_budget`)
serializes claims in priority-rank order exactly the way the reference's
one-pod-per-ScheduleOne loop does, so two preemptors never count the
same freed capacity. (A full-budget 256-step scan cost ~50ms on TPU —
one latency-bound step per candidate, mostly no-ops.)

PodDisruptionBudgets: a victim protected by a PDB whose remaining budget
(disruptionsAllowed minus victims already claimed THIS cycle) is exhausted
is evicted only as a LAST RESORT: the per-prefix violation count is the
FIRST node-choice key (upstream pickOneNodeForPreemption criterion #1),
so a zero-violation node always wins, and claimed victims decrement
their PDBs' budgets in the scan carry. Residual vs upstream (PARITY #4):
within one node the victim set stays a priority-ascending PREFIX, while
upstream's two-pass re-add prefers KEEPING a protected pod over an
unprotected higher-priority one — the pod places either way; the victim
identity can differ in mixed protected/unprotected prefixes.

Tie-breaks mirror upstream pickOneNodeForPreemption: min highest-victim
priority, min victim priority sum, min victim count, then LATEST start
time of the highest victim (prefer evicting younger pods), then lowest
node index.

Victim removal relaxes NON-RESOURCE constraints too (upstream re-runs all
filters with victims removed; SURVEY.md §3.4): per candidate (pod, node,
prefix k) the scan phase checks, against the FINAL post-cycle state with
the prefix's victims subtracted —
  - the pod's required anti-affinity (count in the node's key-domain
    minus evicted matching victims must reach zero),
  - the pod's required affinity (must still have a matching pod left, or
    bootstrap on itself),
  - symmetric anti-affinity (every evictable OWNER of an anti term
    matching the pod must be inside the prefix),
  - DoNotSchedule topology spread (post-eviction skew, with the min-over-
    domains recomputed via a min1/argmin/min2 table),
  - hostPorts (every existing holder of a wanted port must be inside the
    prefix; ports held by this cycle's winners or claimed by earlier
    nominations in this pass never clear).
`gate_rows` is accordingly the PURE STATIC candidate gate, computed on
the budgeted candidate view and excluding NodePorts (see
core.cycle._preemption_gate_rows). Remaining deviations: victims are
priority-order PREFIXES per node (upstream's remove/re-add minimization
is prefix-shaped too, except that it can skip PDB-protected pods — see
the PARITY #4 residual above); and within one batch pass, earlier
candidates' victims are
reflected in capacity (k_claimed / nominated_req) but not in the
affinity/spread count tables later candidates read — stale counts are
conservative for anti (never evict where upstream would not) and at
worst waste a nomination elsewhere, which the next cycle's feasibility
check heals (upstream nominates one pod per ScheduleOne iteration and
re-lists, so the same information lag exists across its cycles).
"""

from __future__ import annotations

import dataclasses
import os

import jax
import jax.numpy as jnp
import numpy as np

from ..models import encoding as enc
from . import argsel
from . import interpod as interpod_ops

# Production per-cycle latency budgets (the DefaultPreemption plugin's
# defaults; the differential soak imports these so oracle-side truncation
# semantics can never drift from what the kernel actually runs).
DEFAULT_BUDGET = 256
DEFAULT_SCAN_BUDGET = 64

_REL_EPS = 1e-5
_BIG_I32 = np.int32(2**31 - 1)  # numpy: no backend init at import


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class PreemptionResult:
    nominated: jnp.ndarray  # i32 [P] node nominated by preemption (-1 none)
    victims: jnp.ndarray  # bool [E] existing pods to evict
    num_preemptors: jnp.ndarray  # i32 [] pods that got a nomination


def run_preemption(
    ctx,
    *,
    assignment: jnp.ndarray,  # i32 [P] from the commit scan (-1 = unsched)
    node_requested: jnp.ndarray,  # f32 [N, R] post-cycle running requests
    gate_rows,  # callable ids i32 [C] -> bool [C, N]: pure-static
    # candidate gate (what eviction can never change), minus NodePorts
    excluded: jnp.ndarray | None = None,  # bool [P] never preempt (e.g.
    # gang-dropped members: they fit without eviction, their group is what
    # failed — upstream never runs PostFilter for Permit rejections)
    budget: int = DEFAULT_BUDGET,  # max preemptor candidates PREFILTERED per cycle:
    # phase 1 evaluates the `budget` lowest-rank unschedulable pods in one
    # batched pass (bounds the [C, N, MPN] table); candidates beyond it
    # stay queued and get their attempt next cycle
    scan_budget: int = DEFAULT_SCAN_BUDGET,  # max NOMINATIONS per cycle: phase 2 scans the
    # `scan_budget` lowest-rank prefilter survivors sequentially (one
    # latency-bound lax.scan step each, ~0.2ms); survivors beyond it defer
    # to the next cycle — upstream nominates ONE pod per ScheduleOne
    # iteration, so 64 per cycle is still generous
) -> PreemptionResult:
    snap = ctx.snap
    P, N = snap.P, snap.N
    E = snap.E
    MPN = snap.node_pods.shape[1]
    K = snap.node_domains.shape[1]

    # ---- final-state affinity/spread tables (what-if baselines) ----
    use_state = snap.has_inter_pod_affinity or snap.has_topology_spread
    if use_state:
        mp = ctx.matched_pending  # [S, P]
        me = ctx.matched_existing  # [S, E]
        state0 = ctx.initial_affinity_state()
        placed = snap.pod_valid & (assignment >= 0)
        node_of_placed = jnp.where(placed, assignment, 0)
        state_f = interpod_ops.affinity_update_batched(
            snap, state0, mp, placed, node_of_placed
        )
        counts_f = state_f.counts  # [S, D]
        total_f = state_f.total  # [S]
        S_, D_ = counts_f.shape
    else:
        placed = snap.pod_valid & (assignment >= 0)
        node_of_placed = jnp.where(placed, assignment, 0)
    if snap.has_inter_pod_affinity:
        anti_cnt_sd = interpod_ops.anti_owner_counts(snap, assignment)
    if snap.has_topology_spread:
        sp_min1, sp_amin, sp_min2 = interpod_ops.spread_min2(
            snap, counts_f
        )
    MA = snap.pod_anti_terms.shape[1]
    MC = snap.pod_tsc.shape[1]
    Q = snap.num_distinct_ports

    # ---- per-node victim tables (shared across all preemptors) ----
    vict_valid = snap.node_pods >= 0  # [N, MPN]
    safe_idx = jnp.clip(snap.node_pods, 0, E - 1)
    vict_prio = jnp.where(
        vict_valid, snap.exist_priority[safe_idx], _BIG_I32
    )  # [N, MPN]
    vict_req = jnp.where(
        vict_valid[:, :, None], snap.exist_requested[safe_idx], 0.0
    )  # [N, MPN, R]
    vict_start = jnp.where(
        vict_valid, snap.exist_start[safe_idx], 0.0
    )  # [N, MPN]
    vict_pdb = jnp.where(
        vict_valid[:, :, None], snap.exist_pdb[safe_idx], -1
    )  # [N, MPN, MB]
    GP = snap.pdb_allowed.shape[0]
    MB = vict_pdb.shape[2]
    # prefix_freed[:, k] = resources freed by evicting the first k victims
    prefix_freed = jnp.concatenate(
        [jnp.zeros_like(vict_req[:, :1]), jnp.cumsum(vict_req, axis=1)], axis=1
    )  # [N, MPN+1, R]
    prio_for_sum = jnp.where(vict_valid, vict_prio, 0)
    prefix_prio = jnp.concatenate(
        [jnp.zeros_like(prio_for_sum[:, :1]), jnp.cumsum(prio_for_sum, axis=1)],
        axis=1,
    )  # [N, MPN+1]
    ks = jnp.arange(MPN + 1, dtype=jnp.int32)[None, :]  # [1, MPN+1]
    slack = _REL_EPS * snap.node_allocatable + _REL_EPS  # [N, R]

    unschedulable = snap.pod_valid & (assignment < 0) & snap.pod_can_preempt
    if excluded is not None:
        unschedulable = unschedulable & ~excluded
    # compact to the budgeted lowest-rank candidates (rank order preserved)
    C = min(P, budget)
    cand_key = jnp.where(unschedulable, snap.pod_order, _BIG_I32)
    cand_ids = jnp.argsort(cand_key)[:C].astype(jnp.int32)
    cand_ok = unschedulable[cand_ids]  # [C]

    # ---- phase 1: batched prefilter (one pass, no contention state) ----
    # A candidate with no feasible preemption node against the PRISTINE
    # post-cycle state never gains one: contention (k_claimed,
    # nominated_req, pdb_used) only shrinks feasibility. Dropping those
    # candidates up front cuts the sequential phase from `budget` steps to
    # the handful of genuine contenders (typically ~the preemptor count).
    prio_c = snap.pod_priority[cand_ids]  # [C]
    req_c = snap.pod_requested[cand_ids]  # [C, R]
    elig_cn = jnp.sum(
        vict_valid[None, :, :] & (vict_prio[None, :, :] < prio_c[:, None, None]),
        axis=2,
    ).astype(jnp.int32)  # [C, N]
    # last-resort eviction (SURVEY §3.4 / PARITY #4): PDB-protected
    # victims no longer truncate the eligible prefix — upstream MAY evict
    # them when nothing else places the pod, preferring nodes with the
    # fewest violations (pickOneNodeForPreemption criterion #1, the
    # scan phase's first lexmin key). The prefilter therefore caps
    # prefixes by priority only.
    elig0 = elig_cn  # [C, N]
    free0 = snap.node_allocatable - node_requested + slack  # [N, R]
    fits0 = jnp.all(
        req_c[:, None, None, :]
        <= free0[None, :, None, :] + prefix_freed[None, :, :, :],
        axis=-1,
    )  # [C, N, MPN+1]
    gate_c = gate_rows(cand_ids)  # [C, N] pure-static candidate gate
    allowed0 = fits0 & (ks[None] >= 1) & (ks[None] <= elig0[:, :, None])
    feasible_any = jnp.any(
        allowed0 & gate_c[:, :, None]
        & snap.node_valid[None, :, None],
        axis=(1, 2),
    ) & cand_ok  # [C]

    C2 = min(C, scan_budget)
    key2 = jnp.where(feasible_any, snap.pod_order[cand_ids], _BIG_I32)
    sel2 = jnp.argsort(key2)[:C2].astype(jnp.int32)
    cand_ids2 = cand_ids[sel2]  # [C2] global pod ids, rank order
    live2 = feasible_any[sel2]
    gate2 = gate_c[sel2]  # [C2, N]

    # ---- batched non-resource what-if over the C2 scan candidates ----
    # Everything here is independent of the scan carry (only claimed
    # ports are not), so it runs ONCE as wide batched ops over
    # [C2, N, MPN+1] instead of per scan step — per-step arbitrary
    # gathers at [N, MPN, MA] scale are pathological on this backend.
    def nonresource_ok_batched(cids):
        """bool [C2, N, MPN+1]: for each scan candidate, node and victim
        prefix — do ALL the candidate's evictable non-resource
        constraints hold once the prefix is gone? (module docstring)"""
        C2 = cids.shape[0]
        ok = jnp.ones((C2, N, MPN + 1), bool)
        s_ids = None

        def cum3(x):  # [C2, N, MPN] f32 -> [C2, N, MPN+1] prefix sums
            c = jnp.cumsum(x, axis=2)
            return jnp.concatenate(
                [jnp.zeros_like(c[:, :, :1]), c], axis=2
            )

        if use_state:
            s_ids = jnp.arange(S_, dtype=jnp.int32)[None, :]
            cbn_f = interpod_ops.counts_by_node(snap, state_f)  # [K*S, N]
            me_vic = (
                me[:, safe_idx.reshape(-1)].reshape(S_, N, MPN)
                & vict_valid[None]
            )
            mvic_f = me_vic.astype(jnp.float32).reshape(S_, N * MPN)

            def term_m_vic(sel_c):  # [C2] -> f32 [C2, N, MPN]
                oh = (
                    jnp.clip(sel_c, 0, S_ - 1)[:, None] == s_ids
                ).astype(jnp.float32)
                return jax.lax.dot(oh, mvic_f).reshape(C2, N, MPN)

            def cnt_at(sel_c, key_c):  # [C2, N]; -1 marks "no domain"
                return interpod_ops._term_pick(
                    snap, cbn_f, sel_c, key_c, exact=True
                )

            if snap.has_inter_pod_affinity:
                for a in range(MA):
                    sel_c = snap.pod_anti_terms[cids, a, 0]  # [C2]
                    key_c = snap.pod_anti_terms[cids, a, 1]
                    cnt = cnt_at(sel_c, key_c)
                    after = cnt[:, :, None] - cum3(term_m_vic(sel_c))
                    ok &= (
                        (sel_c < 0)[:, None, None]
                        | (cnt < -0.5)[:, :, None]
                        | (after <= 0.5)
                    )
                for a in range(MA):
                    sel_c = snap.pod_aff_terms[cids, a, 0]
                    key_c = snap.pod_aff_terms[cids, a, 1]
                    scl = jnp.clip(sel_c, 0, S_ - 1)
                    cnt = cnt_at(sel_c, key_c)
                    cum = cum3(term_m_vic(sel_c))
                    after = cnt[:, :, None] - cum
                    tot_after = total_f[scl][:, None, None] - cum
                    boot = (tot_after <= 0.5) & mp[scl, cids][
                        :, None, None
                    ]
                    ok &= (
                        (sel_c < 0)[:, None, None]
                        | boot
                        | ((cnt > -0.5)[:, :, None] & (after > 0.5))
                    )
                # symmetric: every evictable OWNER of an anti term
                # matching the candidate must fall inside the prefix
                mp_c = mp[:, cids].astype(jnp.float32)  # [S, C2]
                row_d = jax.lax.dot(mp_c.T, anti_cnt_sd)  # [C2, D]
                sym_tot = jnp.zeros((C2, N), jnp.float32)
                for k in range(K):
                    dn = snap.node_domains[:, k]  # [N]
                    g = jnp.take(
                        row_d, jnp.clip(dn, 0, D_ - 1), axis=1
                    )  # [C2, N]
                    sym_tot = sym_tot + jnp.where(dn >= 0, g, 0.0)
                # per-victim owner weight table [S, N*MPN], candidate-
                # independent: victim j on node n owning term (s, key)
                # with a live domain contributes 1 at (s, n*MPN+j)
                sel_v = snap.exist_anti_terms[safe_idx][..., 0]
                key_v = snap.exist_anti_terms[safe_idx][..., 1]
                domk = snap.node_domains[
                    jnp.arange(N)[:, None, None],
                    jnp.clip(key_v, 0, K - 1),
                ]  # [N, MPN, MA]
                valid_v = (
                    (sel_v >= 0) & (domk >= 0) & vict_valid[:, :, None]
                )
                pos = jnp.broadcast_to(
                    (jnp.arange(N)[:, None] * MPN
                     + jnp.arange(MPN)[None, :])[:, :, None],
                    valid_v.shape,
                ).reshape(-1)
                own_f = (
                    jnp.zeros((S_, N * MPN), jnp.float32)
                    .at[
                        jnp.clip(sel_v, 0, S_ - 1).reshape(-1), pos
                    ]
                    .add(valid_v.reshape(-1).astype(jnp.float32))
                )
                w = jax.lax.dot(mp_c.T, own_f).reshape(C2, N, MPN)
                ok &= (sym_tot[:, :, None] - cum3(w)) <= 0.5
            if snap.has_topology_spread:
                for c in range(MC):
                    key_c = snap.pod_tsc[cids, c, 0]
                    sel_c = snap.pod_tsc[cids, c, 1]
                    when_c = snap.pod_tsc[cids, c, 2]
                    skew_c = snap.pod_tsc_skew[cids, c].astype(
                        jnp.float32
                    )
                    hard = (key_c >= 0) & (
                        when_c == enc.WHEN_DO_NOT_SCHEDULE
                    )
                    scl = jnp.clip(sel_c, 0, S_ - 1)
                    cnt = cnt_at(sel_c, key_c)
                    after = cnt[:, :, None] - cum3(term_m_vic(sel_c))
                    row = jnp.clip(key_c, 0, K - 1) * S_ + scl  # [C2]
                    dnc = snap.node_domains.T[
                        jnp.clip(key_c, 0, K - 1)
                    ]  # [C2, N]
                    mexcl = jnp.where(
                        dnc == sp_amin[row][:, None],
                        sp_min2[row][:, None],
                        sp_min1[row][:, None],
                    )
                    min_after = jnp.minimum(mexcl[:, :, None], after)
                    viol = (
                        after + 1.0 - min_after > skew_c[:, None, None]
                    ) | (cnt < -0.5)[:, :, None]
                    ok &= jnp.where(hard[:, None, None], ~viol, True)
        # hostPorts: every existing holder of a wanted port must be in
        # the prefix; ports held by this cycle's winners never clear
        pp_c = snap.pod_ports[cids]  # [C2, MPorts]
        has_p = jnp.any(pp_c >= 0, axis=1)  # [C2]
        vic_ports = snap.exist_ports[safe_idx]  # [N, MPN, MEP]
        conf = (
            (vic_ports[None, :, :, :, None] == pp_c[:, None, None, None])
            & (pp_c >= 0)[:, None, None, None]
        ).any((-2, -1)) & vict_valid[None]  # [C2, N, MPN]
        cum_c = cum3(conf.astype(jnp.float32))
        tot_c = cum_c[:, :, -1:]
        conflict_pw = (
            (snap.pod_ports[None, :, :, None] == pp_c[:, None, None])
            & (pp_c >= 0)[:, None, None]
        ).any((-2, -1)) & placed[None, :]  # [C2, P]
        n_oh = (
            node_of_placed[:, None]
            == jnp.arange(N, dtype=jnp.int32)[None, :]
        ) & placed[:, None]  # [P, N]
        winner_conf = (
            jax.lax.dot(
                conflict_pw.astype(jnp.float32), n_oh.astype(jnp.float32)
            ) > 0.5
        )  # [C2, N]
        ports_ok = (tot_c - cum_c <= 0.5) & ~winner_conf[:, :, None]
        ok &= jnp.where(has_p[:, None, None], ports_ok, True)
        return ok

    ok_nr2 = nonresource_ok_batched(cand_ids2)  # [C2, N, MPN+1]

    def step(carry, rank):
        k_claimed, nominated_req, victim_mask, pdb_used, claimed_q = carry
        p = cand_ids2[rank]
        prio = snap.pod_priority[p]

        # eligible victims: strictly lower priority than the preemptor
        elig = jnp.sum(vict_valid & (vict_prio < prio), axis=1).astype(jnp.int32)
        # PDB protection no longer truncates the prefix: protected
        # victims are evictable as a LAST RESORT, and the per-prefix
        # violation count becomes the first node-choice key below.
        # A victim VIOLATES when its within-group ordinal among the NEW
        # victims (slots >= k_claimed; earlier claims already consumed
        # pdb_used) exceeds the group's remaining budget — upstream's
        # filterPodsWithPDBViolation decrements per victim, so a
        # budget-1 group with two members in one prefix yields exactly
        # one violation, not zero.
        budget_rem = snap.pdb_allowed - pdb_used  # [GP]
        gids = jnp.arange(GP, dtype=vict_pdb.dtype)
        memb = jnp.any(
            vict_pdb[:, :, :, None] == gids[None, None, None, :], axis=2
        ) & vict_valid[:, :, None]  # [N, MPN, GP]
        ordinal = jnp.cumsum(memb.astype(jnp.int32), axis=1)  # inclusive
        pos3 = jnp.arange(MPN, dtype=jnp.int32)[None, :, None]
        claimed_cnt = jnp.sum(
            jnp.where(pos3 < k_claimed[:, None, None], memb, False)
            .astype(jnp.int32),
            axis=1,
        )  # [N, GP] members already claimed by earlier nominations
        prot = jnp.any(
            memb
            & (
                ordinal - claimed_cnt[:, None, :]
                > budget_rem[None, None, :]
            ),
            axis=2,
        ) & vict_valid  # [N, MPN]
        cum_prot = jnp.concatenate(
            [
                jnp.zeros((N, 1), jnp.int32),
                jnp.cumsum(prot.astype(jnp.int32), axis=1),
            ],
            axis=1,
        )  # [N, MPN+1]
        free_base = (
            snap.node_allocatable - node_requested - nominated_req + slack
        )  # [N, R]
        fits = jnp.all(
            snap.pod_requested[p][None, None, :]
            <= free_base[:, None, :] + prefix_freed,
            axis=-1,
        )  # [N, MPN+1]
        # the only carry-dependent non-resource check: ports claimed by
        # earlier nominations in this pass never clear
        qp = snap.pod_port_ids[p]  # [MPorts] -> Q ids
        claimed_conf = jnp.any(
            claimed_q[:, jnp.clip(qp, 0, Q - 1)] & (qp >= 0)[None, :],
            axis=1,
        )  # [N]
        allowed = (
            fits
            & ok_nr2[rank]
            & ~claimed_conf[:, None]
            & (ks >= k_claimed[:, None])
            & (ks <= elig[:, None])
        )
        exists = jnp.any(allowed, axis=1)
        k_min = jnp.argmax(allowed, axis=1).astype(jnp.int32)  # first True  # schedlint: disable=SH001 -- reduce over the MPN+1 victim-prefix axis, an inner pad dimension no mesh axis ever shards; first-True over bool is deterministic per row
        # preemption must actually help: new victims >= 1 (a node feasible
        # with zero evictions would have been chosen by the main cycle)
        candidate = (
            gate2[rank] & snap.node_valid & exists & (k_min > k_claimed)
        )

        # ---- pickOneNodeForPreemption: lexicographic minimization ----
        # row picks via one-hot masked sums, NOT take_along_axis: an
        # arbitrary [N]-gather costs ~50us on this backend and the loop
        # pays it per step x4; the masked reduce over the tiny MPN axis
        # fuses into the surrounding elementwise work
        def pick1(tab, idx):  # tab [N, W], idx [N] -> tab[n, idx[n]]
            pos = jnp.arange(tab.shape[1], dtype=jnp.int32)[None, :]
            return jnp.sum(
                jnp.where(pos == idx[:, None], tab, 0), axis=1
            )

        last = jnp.clip(k_min - 1, 0, MPN - 1)
        max_vict_prio = pick1(vict_prio, last)
        sum_vict_prio = pick1(prefix_prio, k_min) - pick1(
            prefix_prio, k_claimed
        )
        n_vict = k_min - k_claimed
        # NEW victims' PDB violations (upstream pickOneNodeForPreemption
        # criterion #1): nodes needing no violation always win over
        # last-resort nodes
        viol = pick1(cum_prot, k_min) - pick1(cum_prot, k_claimed)

        def lexmin(cand, key, big=_BIG_I32):
            key = jnp.where(cand, key, big)
            return cand & (key == jnp.min(key))

        best = lexmin(candidate, viol)
        best = lexmin(best, max_vict_prio)
        best = lexmin(best, sum_vict_prio)
        best = lexmin(best, n_vict)
        # upstream: prefer the node whose highest victim started LATEST
        # (evict younger pods); minimize the negated start time
        hi_start = pick1(vict_start, last)
        best = lexmin(best, -hi_start, big=jnp.float32(jnp.inf))
        # lowest node index among ties — shard-invariant over a sharded
        # nodes axis (ops/argsel.py; plain argmax merges shard-locally)
        b = argsel.argmax_first(best, axis=0)

        do = live2[rank] & jnp.any(candidate)
        nominated_p = jnp.where(do, b, jnp.int32(-1))

        # claim victims node_pods[b, k_claimed[b]:k_min[b]]
        pos1 = jnp.arange(MPN, dtype=jnp.int32)
        newly = do & (pos1 >= k_claimed[b]) & (pos1 < k_min[b]) & vict_valid[b]
        victim_mask = victim_mask.at[safe_idx[b]].max(newly)
        # newly-claimed victims consume their PDBs' budgets
        for bb in range(MB):
            g = vict_pdb[b, :, bb]  # [MPN]
            pdb_used = pdb_used.at[jnp.clip(g, 0, GP - 1)].add(
                jnp.where(newly & (g >= 0), 1, 0)
            )
        k_claimed = k_claimed.at[b].set(
            jnp.where(do, k_min[b], k_claimed[b])
        )
        nominated_req = nominated_req.at[b].add(
            jnp.where(do, snap.pod_requested[p], 0.0)
        )
        # ports this nomination will occupy: later candidates in this
        # pass must not count on evicting their way onto them
        qp2 = snap.pod_port_ids[p]
        claimed_q = claimed_q.at[b, jnp.clip(qp2, 0, Q - 1)].max(
            do & (qp2 >= 0)
        )
        return (
            (k_claimed, nominated_req, victim_mask, pdb_used, claimed_q),
            (p, nominated_p),
        )

    init = (
        jnp.zeros(N, jnp.int32),
        jnp.zeros_like(node_requested),
        jnp.zeros(E, bool),
        jnp.zeros(GP, jnp.int32),
        jnp.zeros((N, Q), bool),
    )
    # the serialization loop runs only over LIVE candidates: sel2 sorts
    # feasible candidates first (infeasible keys are _BIG_I32), so ranks
    # >= n_live are guaranteed no-ops (live2 False -> no claim, no
    # nomination) and a while_loop bounded by n_live skips them. At
    # config #4 that is ~19 latency-bound steps instead of scan_budget
    # (64) — each dead step cost ~0.2 ms on TPU.
    n_live = jnp.sum(live2).astype(jnp.int32)
    if os.environ.get("K8S_TPU_PREEMPT_FIXED_LOOP") == "1":
        # debug/workaround knob: run every budgeted rank (dead ranks are
        # no-ops) instead of the data-dependent live bound — isolates
        # rig issues with dynamic-trip while loops at ~0.2 ms per dead
        # step
        n_live = jnp.int32(C2)
    pods0 = cand_ids2  # rank -> pod id is static; dead ranks emit -1
    noms0 = jnp.full(C2, -1, jnp.int32)

    def w_cond(st):
        return st[0] < n_live

    def w_body(st):
        rank, carry, noms_acc = st
        carry, (_p, nom_p) = step(carry, rank)
        return rank + 1, carry, noms_acc.at[rank].set(nom_p)

    _, (_, _, victims, _, _), noms = jax.lax.while_loop(
        w_cond, w_body, (jnp.int32(0), init, noms0)
    )
    nominated = jnp.full(P, -1, jnp.int32).at[pods0].max(noms)
    return PreemptionResult(
        nominated=nominated,
        victims=victims & snap.exist_valid,
        num_preemptors=jnp.sum(nominated >= 0).astype(jnp.int32),
    )
