"""Inter-pod affinity / topology-spread kernels — the quadratic hot path.

The reference's InterPodAffinity plugin is its worst-case cost center:
O(nodes x existing-pods-with-affinity) per pod (SURVEY.md §3.5, benchmark
config #3; expected `framework/plugins/interpodaffinity/` — [UNVERIFIED],
mount empty). The TPU-native design never materializes pods x nodes x pods:

1. Label selectors are deduplicated ([S] distinct selectors, each an AND of
   expression-table rows incl. an implicit namespace expression).
2. ONE batched pass computes matched_pending [S, P] and matched_existing
   [S, E] via the shared expression kernel.
3. Affinity state collapses to per-(selector, topology-domain) COUNTS
   [S, D] (plus per-selector node tables [S, N] for the symmetric checks) —
   segment-sums over existing pods, not pairwise comparisons.
4. The commit scan carries these counts and updates them as pods place, so
   in-cycle affinity among pending pods resolves exactly like the
   reference's sequential NodeInfo mutation. Per-step cost is O(S*N + MA*N).

Semantics parity notes:
- Required affinity: >=1 matching pod in the node's domain, with the
  upstream bootstrap rule (a pod matching its own selector may place when
  NO pod in the cluster matches it — the first pod of a self-affine group).
- Required anti-affinity: zero matching pods in the domain; symmetric
  anti-affinity of existing AND in-cycle pods is enforced via the [S, N]
  presence table.
- Preferred terms score both directions (incoming pod's preferences against
  placed pods, placed pods' preferences against the incoming pod),
  normalized by max |raw| over feasible nodes like the oracle.
- A node missing the topology key cannot satisfy required affinity, cannot
  violate anti-affinity, and fails DoNotSchedule spread constraints.
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

from ..models import encoding as enc
from . import labels as labels_ops


def selector_match(snap, label_keys, label_vals) -> jnp.ndarray:  # [S, X]
    """Every deduplicated selector against every labeled subject."""
    em = labels_ops.expr_pod_mask(snap, label_keys, label_vals)  # [Ex, X]
    g = labels_ops._gather_expr(em, snap.sel_exprs, fill=True)  # [S, MSE, X]
    return g.all(axis=1)


def matched_pending(snap) -> jnp.ndarray:  # bool [S, P]
    return selector_match(snap, snap.pod_label_keys, snap.pod_label_vals) & (
        snap.pod_valid[None, :]
    )


def matched_existing(snap) -> jnp.ndarray:  # bool [S, E]
    return selector_match(snap, snap.exist_label_keys, snap.exist_label_vals) & (
        snap.exist_valid[None, :]
    )


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class AffinityState:
    """Scan-carried affinity state (see module docstring)."""

    counts: jnp.ndarray  # f32 [S, D] matching pods per (selector, domain)
    total: jnp.ndarray  # f32 [S] matching pods anywhere (bootstrap rule)
    anti_presence: jnp.ndarray  # bool [S, N] node blocked-by-anti(sel) table
    pref_sym: jnp.ndarray  # f32 [S, N] symmetric preferred-term weights


def _exist_domains(snap) -> jnp.ndarray:  # i32 [E, K]
    safe_node = jnp.clip(snap.exist_node, 0, snap.N - 1)
    dom = snap.node_domains[safe_node]  # [E, K]
    return jnp.where((snap.exist_node >= 0)[:, None], dom, -1)


def initial_state(snap, m_exist: jnp.ndarray) -> AffinityState:
    """Aggregate existing pods into the four state tables."""
    S, E = m_exist.shape
    D = snap.domain_key.shape[0]
    K = snap.node_domains.shape[1]
    dom = _exist_domains(snap)  # [E, K]

    # counts[s, d] = number of existing pods matching s whose node is in d
    # — [S,E] @ [E,D] one-hot matmul per key on the MXU (3x faster than
    # the per-selector scatter-add at 16k x 5k; 0/1 operands are exact at
    # any matmul precision, accumulation is f32). A -1 domain (node
    # missing the key) produces an all-zero one-hot row.
    counts = jnp.zeros((S, D), jnp.float32)
    mb = m_exist.astype(jnp.float32)
    d_ids = jnp.arange(D, dtype=jnp.int32)[None, :]
    for k in range(K):  # K is tiny (distinct topology keys)
        oh = (dom[:, k][:, None] == d_ids).astype(jnp.float32)  # [E, D]
        counts = counts + jax.lax.dot(mb, oh)
    total = jnp.sum(m_exist.astype(jnp.float32), axis=1)  # [S]

    # anti_presence[s, n] = some placed pod with required anti-term (s, k)
    # shares node n's k-domain. Built as ONE scatter into a flat [S, D]
    # table (flat domain ids are globally unique, so no key collisions),
    # then expanded to nodes with K gathers. Gated on the static capability
    # flag: a spread-only cluster never traces the affinity tables.
    if snap.has_inter_pod_affinity:
        anti = _flat_to_node(
            snap, _flat_table(snap.exist_anti_terms, None, dom, S, D), True
        )
        pref = _flat_to_node(
            snap,
            _flat_table(snap.exist_pref_aff, snap.exist_pref_aff_w, dom, S, D),
            False,
        )
    else:
        anti = jnp.zeros((S, snap.N), bool)
        pref = jnp.zeros((S, snap.N), jnp.float32)
    return AffinityState(counts, total, anti, pref)


def _flat_table(terms, weights, owner_dom, S, D):
    """Scatter every term (sel, k) of every owner into [S, D] at the
    owner's k-domain. terms [X, MA, 2], owner_dom [X, K]; weights None ->
    bool OR table, else f32 sum table."""
    X, MA, _ = terms.shape
    K = owner_dom.shape[1]
    sel = terms[..., 0].reshape(-1)  # [X*MA]
    k = jnp.clip(terms[..., 1].reshape(-1), 0, K - 1)
    xi = jnp.repeat(jnp.arange(X), MA)
    d = owner_dom[xi, k]
    valid = (sel >= 0) & (d >= 0)
    si = jnp.clip(sel, 0, S - 1)
    di = jnp.clip(d, 0, D - 1)
    if weights is None:
        return jnp.zeros((S, D), bool).at[si, di].max(valid)
    w = jnp.where(valid, weights.reshape(-1), 0.0)
    return jnp.zeros((S, D), jnp.float32).at[si, di].add(w)


def _flat_to_node(snap, flat, bool_mode: bool):
    """[S, D] per-domain table -> [S, N] per-node table (a node is in one
    domain per topology key; flat ids are unique across keys)."""
    out = jnp.zeros((flat.shape[0], snap.N), bool if bool_mode else jnp.float32)
    for k in range(snap.node_domains.shape[1]):
        nd = snap.node_domains[:, k]  # [N]
        g = flat[:, jnp.clip(nd, 0, flat.shape[1] - 1)]  # [S, N]
        m = (nd >= 0)[None, :]
        out = (out | (g & m)) if bool_mode else (out + jnp.where(m, g, 0.0))
    return out


def _node_domain_match(snap, k, d):  # bool [N]: nodes whose k-domain == d
    nd = jnp.take(snap.node_domains, jnp.clip(k, 0, snap.node_domains.shape[1] - 1),
                  axis=1)  # [N]
    return (nd == d) & (d >= 0)


# --------------------------------------------------------------------------
# per-step (inside the commit scan)
# --------------------------------------------------------------------------


def _counts_at_nodes(snap, state: AffinityState, sel, k) -> jnp.ndarray:
    """counts[sel, domain(n, k)] for all nodes n; -1 domains -> -1."""
    D = state.counts.shape[1]
    nd = jnp.take(
        snap.node_domains, jnp.clip(k, 0, snap.node_domains.shape[1] - 1), axis=1
    )  # [N]
    row = state.counts[jnp.clip(sel, 0, state.counts.shape[0] - 1)]  # [D]
    c = row[jnp.clip(nd, 0, D - 1)]
    return jnp.where(nd >= 0, c, -1.0)  # -1 marks "no such domain"


def affinity_dyn_mask(snap, state: AffinityState, m_pending, p) -> jnp.ndarray:
    """Required affinity + anti-affinity + symmetric anti for pod p: [N]."""
    N = snap.N
    ok = jnp.ones((N,), bool)
    MA = snap.pod_aff_terms.shape[1]
    aff = snap.pod_aff_terms[p]  # [MA, 2]
    anti = snap.pod_anti_terms[p]
    for a in range(MA):
        sel, k = aff[a, 0], aff[a, 1]
        c = _counts_at_nodes(snap, state, sel, k)
        # bootstrap: nothing matches the selector anywhere AND the pod
        # matches its own selector -> term ignored
        boot = (state.total[jnp.clip(sel, 0, state.total.shape[0] - 1)] == 0) & (
            m_pending[jnp.clip(sel, 0, m_pending.shape[0] - 1), p]
        )
        term_ok = jnp.where(sel >= 0, boot | (c > 0), True)
        ok &= term_ok
    for a in range(MA):
        sel, k = anti[a, 0], anti[a, 1]
        c = _counts_at_nodes(snap, state, sel, k)
        # c == -1 (key absent) cannot be violated; c == 0 is fine
        term_ok = jnp.where(sel >= 0, c <= 0, True)
        ok &= term_ok
    # symmetric: placed pods' anti terms whose selector matches p
    mp = m_pending[:, p]  # [S]
    viol = jnp.any(mp[:, None] & state.anti_presence, axis=0)  # [N]
    return ok & ~viol


def affinity_dyn_score(snap, state: AffinityState, m_pending, p,
                       feasible) -> jnp.ndarray:
    """Preferred-term score for pod p, normalized to [-100, 100] by the max
    |raw| over feasible nodes (both sides of the symmetry)."""
    N = snap.N
    raw = jnp.zeros((N,), jnp.float32)
    MA = snap.pod_pref_aff.shape[1]
    pref = snap.pod_pref_aff[p]
    w = snap.pod_pref_aff_w[p]
    for a in range(MA):
        sel, k = pref[a, 0], pref[a, 1]
        c = _counts_at_nodes(snap, state, sel, k)
        raw += jnp.where((sel >= 0) & (c > 0), w[a] * jnp.maximum(c, 0.0), 0.0)
    mp = m_pending[:, p].astype(jnp.float32)  # [S]
    raw += mp @ state.pref_sym  # symmetric direction, [S]x[S,N]
    hi = jnp.max(jnp.where(feasible, jnp.abs(raw), 0.0))
    return jnp.where(hi > 0, raw / hi * 100.0, 0.0)


def affinity_update(snap, state: AffinityState, m_pending, p, node,
                    committed) -> AffinityState:
    """Pod p committed to `node`: fold it into counts/total/anti/pref."""
    K = snap.node_domains.shape[1]
    S, D = state.counts.shape
    mp = jnp.where(committed, m_pending[:, p].astype(jnp.float32), 0.0)  # [S]
    counts = state.counts
    node_dom = snap.node_domains[node]  # [K]
    for k in range(K):
        d = node_dom[k]
        add = jnp.where(d >= 0, mp, 0.0)
        counts = counts.at[:, jnp.clip(d, 0, D - 1)].add(add)
    total = state.total + mp

    # fold p's own anti/preferred terms into the node tables (unrolled over
    # the tiny MA axis; each slot is one [N]-row mask + scatter); statically
    # skipped when the cluster has no affinity terms at all
    anti = state.anti_presence
    pref = state.pref_sym
    if not snap.has_inter_pod_affinity:
        return AffinityState(counts, total, anti, pref)
    MA = snap.pod_anti_terms.shape[1]
    anti_terms = snap.pod_anti_terms[p]
    pref_terms = snap.pod_pref_aff[p]
    pref_w = snap.pod_pref_aff_w[p]
    for a in range(MA):
        sel, k = anti_terms[a, 0], anti_terms[a, 1]
        d = node_dom[jnp.clip(k, 0, K - 1)]
        row = _node_domain_match(snap, k, d) & (sel >= 0) & committed
        anti = anti.at[jnp.clip(sel, 0, S - 1)].max(row)

        sel2, k2 = pref_terms[a, 0], pref_terms[a, 1]
        d2 = node_dom[jnp.clip(k2, 0, K - 1)]
        row2 = _node_domain_match(snap, k2, d2) & (sel2 >= 0) & committed
        pref = pref.at[jnp.clip(sel2, 0, S - 1)].add(
            jnp.where(row2, pref_w[a], 0.0)
        )
    return AffinityState(counts, total, anti, pref)


# --------------------------------------------------------------------------
# topology spread
# --------------------------------------------------------------------------


def spread_dyn_mask(snap, state: AffinityState, p) -> jnp.ndarray:
    """DoNotSchedule constraints: count(dom) + 1 - min(dom counts of the
    key) <= maxSkew; nodes missing the key fail."""
    N = snap.N
    ok = jnp.ones((N,), bool)
    MC = snap.pod_tsc.shape[1]
    tsc = snap.pod_tsc[p]  # [MC, 3]
    skews = snap.pod_tsc_skew[p]
    D = state.counts.shape[1]
    for c in range(MC):
        k, sel, when = tsc[c, 0], tsc[c, 1], tsc[c, 2]
        cnt = _counts_at_nodes(snap, state, sel, k)  # [N], -1 = no key
        row = state.counts[jnp.clip(sel, 0, state.counts.shape[0] - 1)]  # [D]
        eligible = (snap.domain_key == k) & (snap.domain_node_count > 0)
        minc = jnp.min(jnp.where(eligible, row, jnp.inf))
        minc = jnp.where(jnp.isfinite(minc), minc, 0.0)
        viol = (cnt + 1.0 - minc > skews[c].astype(jnp.float32)) | (cnt < 0)
        hard = (k >= 0) & (when == enc.WHEN_DO_NOT_SCHEDULE)
        ok &= jnp.where(hard, ~viol, True)
    return ok


def spread_dyn_score(snap, state: AffinityState, p, feasible) -> jnp.ndarray:
    """ScheduleAnyway constraints: fewer matching pods in the node's domain
    is better; raw = sum of counts, normalized reverse over feasible nodes
    (both sides use this simplified form of upstream's two-pass score)."""
    N = snap.N
    raw = jnp.zeros((N,), jnp.float32)
    MC = snap.pod_tsc.shape[1]
    tsc = snap.pod_tsc[p]
    for c in range(MC):
        k, sel, when = tsc[c, 0], tsc[c, 1], tsc[c, 2]
        cnt = _counts_at_nodes(snap, state, sel, k)
        soft = (k >= 0) & (when == enc.WHEN_SCHEDULE_ANYWAY)
        raw += jnp.where(soft, jnp.maximum(cnt, 0.0), 0.0)
    hi = jnp.max(jnp.where(feasible, raw, 0.0))
    return jnp.where(hi > 0, (1.0 - raw / hi) * 100.0, 100.0)


# ==========================================================================
# Batched (whole-pending-set) variants — the round-based commit's kernels.
#
# The per-pod functions above run inside the sequential commit scan: one
# [N]-row at a time, P scan steps. On TPU that is latency-bound (~100us+
# per scan step through the sequencer), so the round-based commit
# (ops/rounds.py) evaluates ALL pods against the current state at once:
# count lookups become row-gathers from a [K*S, N] table and the symmetric
# terms become [P,S]x[S,N] matmuls on the MXU.
# ==========================================================================


def counts_by_node(snap, state: AffinityState) -> jnp.ndarray:
    """[K*S, N] table: counts[s, domain(n, k)] for every (k, s, n); -1
    where node n has no domain for key k."""
    K = snap.node_domains.shape[1]
    S, D = state.counts.shape
    rows = []
    for k in range(K):
        nd = snap.node_domains[:, k]  # [N]
        g = state.counts[:, jnp.clip(nd, 0, D - 1)]  # [S, N]
        rows.append(jnp.where((nd >= 0)[None, :], g, -1.0))
    return jnp.concatenate(rows, axis=0)  # [K*S, N]  # schedlint: disable=SH002 -- 2-D selector-table rows stacked on the K*S axis, which is never mesh-sharded (the PR 9 miscompile needs sharded 1-D operands)


def _row_onehot(snap, sel, k) -> jnp.ndarray:  # f32 [P, K*S]
    """One-hot row selector for per-pod (selector, key) terms."""
    S = snap.sel_exprs.shape[0]
    K = snap.node_domains.shape[1]
    row = jnp.clip(k, 0, K - 1) * S + jnp.clip(sel, 0, S - 1)
    ks = jnp.arange(K * S, dtype=row.dtype)[None, :]
    return (row[:, None] == ks).astype(jnp.float32)


def _term_pick(snap, table, sel, k, exact: bool) -> jnp.ndarray:
    """table[row(sel, k)] for every pod as a one-hot [P, K*S] @ [K*S, N]
    matmul on the MXU — ~5x faster than the arbitrary-row gather at
    10k x 5k. With `exact`, bf16_3x precision keeps integer-valued f32
    table entries exact through the matmul (each f32 splits into three
    bf16 terms exactly; the single nonzero per one-hot row sums them back
    in f32); without it, entries must already be bf16-exact (0/1 presence
    bits, small sentinels)."""
    oh = _row_onehot(snap, sel, k)
    prec = jax.lax.Precision.HIGH if exact else jax.lax.Precision.DEFAULT
    return jax.lax.dot(oh, table, precision=prec)


def _term_counts(snap, cbn, sel, k):  # sel,k: i32 [P] -> f32 [P, N]
    """Exact counts-at-node pick for per-pod terms (spread skew and
    preference scores compare/weight true counts)."""
    return _term_pick(snap, cbn, sel, k, exact=True)


def _multi_hot(snap, sel, k, w) -> jnp.ndarray:  # [P, A] each -> f32 [P, K*S]
    """Weighted MULTI-hot term matrix: row (k, sel) accumulates w[:, a]
    over the term axis. Collapses A per-slot `one-hot @ table` dots into
    ONE dot — the term-compaction lever from PERF.md item 4. Callers
    zero w for invalid slots; duplicate (sel, k) slots sum, which every
    consumer's algebra wants (satisfied-term counts, additive weights)."""
    S = snap.sel_exprs.shape[0]
    K = snap.node_domains.shape[1]
    W = jnp.zeros((sel.shape[0], K * S), jnp.float32)
    ks = jnp.arange(K * S, dtype=jnp.int32)[None, :]
    for a in range(sel.shape[1]):  # A is tiny/static; fuses to one pass
        row = jnp.clip(k[:, a], 0, K - 1) * S + jnp.clip(sel[:, a], 0, S - 1)
        W = W + jnp.where(row[:, None] == ks, w[:, a][:, None], 0.0)
    return W


def affinity_mask_batched(snap, state: AffinityState, m_pending,
                          cbn) -> jnp.ndarray:  # bool [P, N]
    """Required affinity + anti-affinity + symmetric anti for ALL pods.

    Only the SIGN of the domain counts matters here (c > 0 / c <= 0), so
    the picks run over a shared 0/1 presence table — bf16-exact at any
    matmul precision; the -1 no-domain sentinel lands in the 'not
    positive' bucket both checks want.

    Term-compacted (PERF item 4): instead of one [P,K*S]@[K*S,N] dot per
    term slot (2*MA dots), a multi-hot count matrix per direction gives
    TWO dots total. Required terms: a valid non-boot term is satisfied
    iff its row is positive, so satisfied-count == required-count iff
    every term holds (counts are small ints — bf16-exact, f32 accum).
    Anti terms: violated iff the multi-hot dot against positivity is
    nonzero."""
    P, N = m_pending.shape[1], snap.N
    S = state.total.shape[0]
    pid = jnp.arange(P, dtype=jnp.int32)
    pos = (cbn > 0).astype(jnp.float32)  # [K*S, N]

    sel = snap.pod_aff_terms[..., 0]  # [P, MA]
    k = snap.pod_aff_terms[..., 1]
    scl = jnp.clip(sel, 0, S - 1)
    boot = (state.total[scl] == 0) & m_pending[scl, pid[:, None]]  # [P, MA]
    need = (sel >= 0) & ~boot
    W = _multi_hot(snap, sel, k, need.astype(jnp.float32))
    n_req = jnp.sum(need, axis=1).astype(jnp.float32)  # [P]
    ok = jax.lax.dot(W, pos) >= n_req[:, None] - 0.5

    a_sel = snap.pod_anti_terms[..., 0]
    a_k = snap.pod_anti_terms[..., 1]
    Wa = _multi_hot(snap, a_sel, a_k, (a_sel >= 0).astype(jnp.float32))
    ok &= jax.lax.dot(Wa, pos) < 0.5
    # symmetric: any placed pod's anti term whose selector matches p —
    # [P,S]x[S,N] matmul on the MXU instead of a per-pod [S,N] reduction
    viol = (
        m_pending.T.astype(jnp.float32) @ state.anti_presence.astype(jnp.float32)
    ) > 0.0
    return ok & ~viol


def affinity_score_batched(snap, state: AffinityState, m_pending, cbn,
                           feasible) -> jnp.ndarray:  # f32 [P, N]
    """Preferred-term score for ALL pods, normalized per pod to
    [-100, 100] by max |raw| over that pod's feasible nodes.

    Term-compacted: per-slot contribution w * max(c, 0) * (c > 0) equals
    w * relu(c) (the -1 no-domain sentinel relus to 0), which is LINEAR
    in the table — so all MA exact picks collapse to one weighted
    multi-hot dot against relu(cbn) at HIGH precision (counts exceed
    bf16's integer range; bf16_3x keeps the products exact)."""
    sel = snap.pod_pref_aff[..., 0]  # [P, MA]
    k = snap.pod_pref_aff[..., 1]
    w = jnp.where(sel >= 0, snap.pod_pref_aff_w, 0.0)
    Ww = _multi_hot(snap, sel, k, w)
    raw = jax.lax.dot(Ww, jnp.maximum(cbn, 0.0),
                      precision=jax.lax.Precision.HIGH)
    raw += m_pending.T.astype(jnp.float32) @ state.pref_sym  # [P, N]
    hi = jnp.max(jnp.where(feasible, jnp.abs(raw), 0.0), axis=1, keepdims=True)
    return jnp.where(hi > 0, raw / hi * 100.0, 0.0)


def _eligible(snap, k: int) -> jnp.ndarray:  # bool [D]
    """The domains of topology key k that hold a node: the ones a spread
    group's minimum is taken over."""
    return (snap.domain_key == k) & (snap.domain_node_count > 0)


def spread_minc(snap, state: AffinityState) -> jnp.ndarray:  # f32 [K*S]
    """min matching-pod count over eligible domains, per (key, selector) —
    the `minc` of the spread rule, shared by all pods."""
    K = snap.node_domains.shape[1]
    S, D = state.counts.shape
    outs = []
    for k in range(K):
        eligible = _eligible(snap, k)
        m = jnp.min(
            jnp.where(eligible[None, :], state.counts, jnp.inf), axis=1
        )  # [S]
        outs.append(jnp.where(jnp.isfinite(m), m, 0.0))
    return jnp.concatenate(outs, axis=0)  # schedlint: disable=SH002 -- per-key [S] minima on the replicated selector axis; never pods-sharded


def spread_reach(snap, state: AffinityState, minc,
                 active) -> jnp.ndarray:  # f32 [K*S]
    """The level one commit round can lift a (key, selector) group's
    minimum to: pour the group's `n` claimants (the `active` pods that
    carry a DoNotSchedule constraint on it) into its eligible domains,
    lowest first, and read the water line, i.e. the largest T with
    sum_d max(0, T - count_d) <= n. It is `minc` where nobody claims.
    `spread_mask_batched` given this level in place of `minc` admits
    every domain the round's own acceptances can open, which is what
    the rounds engine lets pods CLAIM (ops/rounds.py: the guard sweep,
    not this mask, then holds every acceptance to the skew)."""
    K = snap.node_domains.shape[1]
    S, D = state.counts.shape
    k = snap.pod_tsc[..., 0]
    hard = (k >= 0) & (
        snap.pod_tsc[..., 2] == enc.WHEN_DO_NOT_SCHEDULE
    ) & active[:, None]
    row = jnp.clip(k, 0, K - 1) * S + jnp.clip(snap.pod_tsc[..., 1], 0, S - 1)
    n = jnp.zeros((K * S,), jnp.float32).at[row.reshape(-1)].add(
        hard.reshape(-1).astype(jnp.float32)
    )
    eligible = [_eligible(snap, kk) for kk in range(K)]

    def halve(_, lh):
        lo, hi = lh
        mid = jnp.floor((lo + hi + 1.0) * 0.5)
        need = jnp.concatenate([  # schedlint: disable=SH002 -- per-key [S] sums on the replicated selector axis; never pods-sharded
            jnp.sum(jnp.where(
                eligible[kk][None, :],
                jnp.maximum(mid[kk * S:(kk + 1) * S, None] - state.counts,
                            0.0),
                0.0,
            ), axis=1)
            for kk in range(K)
        ])
        fits = need <= n
        return jnp.where(fits, mid, lo), jnp.where(fits, hi, mid - 1.0)

    # the line lies in [minc, minc + n] and n <= P: bisect to a unit
    steps = int(snap.pod_tsc.shape[0]).bit_length() + 1
    lo, _ = jax.lax.fori_loop(0, steps, halve, (minc, minc + n))
    return lo


def spread_claim_share(snap, state: AffinityState, cbn, reach):
    """(share f32 [P, N], domain i32 [P, N]) for the rounds engine's
    claims under `spread_reach`'s level: under each pod's FIRST
    DoNotSchedule constraint, how many pods node n's domain takes
    before it stands at the level (what pouring gives the domain; 0 at
    or above the line), and that domain's id (-1: the pod has no such
    constraint, or the node lacks the key). A group whose claimants
    pick their domain with odds as these shares arrive as the pouring
    would place them, whatever the nodes' scores say."""
    P, N = snap.P, snap.N
    K = snap.node_domains.shape[1]
    S = state.counts.shape[0]
    share = jnp.zeros((P, N), jnp.float32)
    domain = jnp.full((P, N), -1, jnp.int32)
    taken = jnp.zeros((P,), bool)
    for c in range(snap.pod_tsc.shape[1]):
        k = snap.pod_tsc[:, c, 0]
        sel = snap.pod_tsc[:, c, 1]
        first = (k >= 0) & (
            snap.pod_tsc[:, c, 2] == enc.WHEN_DO_NOT_SCHEDULE
        ) & ~taken
        taken |= first
        kcl = jnp.clip(k, 0, K - 1)
        cnt = _term_counts(snap, cbn, sel, k)  # [P, N]
        line = reach[kcl * S + jnp.clip(sel, 0, S - 1)]  # [P]
        share = jnp.where(
            first[:, None], jnp.maximum(line[:, None] - cnt, 0.0), share
        )
        domain = jnp.where(
            first[:, None], snap.node_domains.T[kcl], domain
        )
    return share, domain


def spread_mask_batched(snap, state: AffinityState, cbn,
                        minc) -> jnp.ndarray:  # bool [P, N]
    P, N = snap.P, snap.N
    ok = jnp.ones((P, N), bool)
    MC = snap.pod_tsc.shape[1]
    S = state.counts.shape[0]
    K = snap.node_domains.shape[1]
    for c in range(MC):
        k = snap.pod_tsc[:, c, 0]
        sel = snap.pod_tsc[:, c, 1]
        when = snap.pod_tsc[:, c, 2]
        cnt = _term_counts(snap, cbn, sel, k)  # [P, N]
        row = jnp.clip(k, 0, K - 1) * S + jnp.clip(sel, 0, S - 1)
        mc = minc[row]  # [P]
        skew = snap.pod_tsc_skew[:, c].astype(jnp.float32)
        viol = (cnt + 1.0 - mc[:, None] > skew[:, None]) | (cnt < 0)
        hard = (k >= 0) & (when == enc.WHEN_DO_NOT_SCHEDULE)
        ok &= jnp.where(hard[:, None], ~viol, True)
    return ok


def spread_score_batched(snap, state: AffinityState, cbn,
                         feasible) -> jnp.ndarray:  # f32 [P, N]
    # Term-compacted like affinity_score_batched: soft-slot contribution
    # max(cnt, 0) is relu-linear in the table, so MC exact picks become
    # one multi-hot dot against relu(cbn).
    k = snap.pod_tsc[..., 0]  # [P, MC]
    sel = snap.pod_tsc[..., 1]
    when = snap.pod_tsc[..., 2]
    soft = (k >= 0) & (when == enc.WHEN_SCHEDULE_ANYWAY)
    Ws = _multi_hot(snap, sel, k, soft.astype(jnp.float32))
    raw = jax.lax.dot(Ws, jnp.maximum(cbn, 0.0),
                      precision=jax.lax.Precision.HIGH)
    hi = jnp.max(jnp.where(feasible, raw, 0.0), axis=1, keepdims=True)
    return jnp.where(hi > 0, (1.0 - raw / hi) * 100.0, 100.0)


def affinity_update_batched(snap, state: AffinityState, m_pending,
                            accepted, node_of) -> AffinityState:
    """Fold a whole round's accepted placements (accepted bool [P],
    node_of i32 [P]) into the state tables in one batched pass.

    Every table update is an MXU matmul instead of a scatter (profiled:
    one [S, N] scatter-max cost ~7ms per round at 10k x 5k; the
    equivalent [S, P] @ [P, N] matmul is ~0.2ms). Exactness: counts/anti
    matmuls have 0/1 operands (exact at any matmul precision, f32
    accumulation); pref weights go through f32 dots at HIGH precision,
    which represents the inputs exactly."""
    K = snap.node_domains.shape[1]
    S, D = state.counts.shape
    N = snap.N
    P = accepted.shape[0]
    acc_f = accepted.astype(jnp.float32)
    mp_acc = m_pending.astype(jnp.float32) * acc_f[None, :]  # [S, P]
    nsafe = jnp.clip(node_of, 0, N - 1)
    node_dom = snap.node_domains[nsafe]  # [P, K]

    counts = state.counts
    d_ids = jnp.arange(D, dtype=jnp.int32)[None, :]
    for k in range(K):
        d = jnp.where(accepted, node_dom[:, k], -1)  # [P]
        oh_d = (d[:, None] == d_ids).astype(jnp.float32)  # [P, D]
        counts = counts + jax.lax.dot(mp_acc, oh_d)
    total = state.total + jnp.sum(mp_acc, axis=1)

    anti = state.anti_presence
    pref = state.pref_sym
    if not snap.has_inter_pod_affinity:
        return AffinityState(counts, total, anti, pref)
    MA = snap.pod_anti_terms.shape[1]
    s_ids = jnp.arange(S, dtype=jnp.int32)[None, :]
    for a in range(MA):
        sel = snap.pod_anti_terms[:, a, 0]  # [P]
        k = jnp.clip(snap.pod_anti_terms[:, a, 1], 0, K - 1)
        d = jnp.take_along_axis(node_dom, k[:, None], axis=1)[:, 0]  # [P]
        nd_k = snap.node_domains.T[k]  # [P, N] domain of every node under k
        row = (nd_k == d[:, None]) & (d >= 0)[:, None] & (
            sel >= 0
        )[:, None] & accepted[:, None]  # [P, N]
        oh_s = (sel[:, None] == s_ids).astype(jnp.float32)  # [P, S]
        hits = jax.lax.dot(oh_s.T, row.astype(jnp.float32))  # [S, N]
        anti = anti | (hits > 0.0)

        sel2 = snap.pod_pref_aff[:, a, 0]
        k2 = jnp.clip(snap.pod_pref_aff[:, a, 1], 0, K - 1)
        d2 = jnp.take_along_axis(node_dom, k2[:, None], axis=1)[:, 0]
        nd_k2 = snap.node_domains.T[k2]  # [P, N]
        row2 = (nd_k2 == d2[:, None]) & (d2 >= 0)[:, None] & (
            sel2 >= 0
        )[:, None] & accepted[:, None]
        w2 = snap.pod_pref_aff_w[:, a]  # [P]
        oh_w = jnp.where(sel2[:, None] == s_ids, w2[:, None], 0.0)  # [P, S]
        pref = pref + jax.lax.dot(
            oh_w.T, row2.astype(jnp.float32),
            precision=jax.lax.Precision.HIGH,
        )  # [S, N]
    return AffinityState(counts, total, anti, pref)


def spread_min2(snap, counts):
    """Per (key, selector): (min1, argmin-domain, min2) of the matching-
    pod counts over eligible domains — each f32/i32 [K*S].

    Preemption's what-if needs "min over domains EXCLUDING d" for the
    candidate node's domain d (evicting on one node only lowers that
    domain's count): min_excl(d) = min2 if argmin == d else min1. A
    (key, selector) with a single eligible domain gets min2 = 1e9 so
    min_after collapses to the domain's own post-eviction count."""
    K = snap.node_domains.shape[1]
    S, D = counts.shape
    d_ids = jnp.arange(D, dtype=jnp.int32)[None, :]
    m1s, aas, m2s = [], [], []
    for k in range(K):
        eligible = _eligible(snap, k)
        vals = jnp.where(eligible[None, :], counts, jnp.inf)  # [S, D]
        a1 = jnp.argmin(vals, axis=1).astype(jnp.int32)  # [S]  # schedlint: disable=SH001 -- reduce over the domain axis D, which is never mesh-sharded (MESH_AXES is pods/nodes); counts ties are broken identically on every replica
        m1 = jnp.min(vals, axis=1)
        vals2 = jnp.where(d_ids == a1[:, None], jnp.inf, vals)
        m2 = jnp.min(vals2, axis=1)
        m1s.append(jnp.where(jnp.isfinite(m1), m1, 0.0))
        aas.append(a1)
        m2s.append(jnp.where(jnp.isfinite(m2), m2, 1e9))
    return (
        jnp.concatenate(m1s), jnp.concatenate(aas), jnp.concatenate(m2s)  # schedlint: disable=SH002 -- [S] per-key vectors on the replicated selector axis; never pods-sharded
    )


def anti_owner_counts(snap, assignment) -> jnp.ndarray:
    """f32 [S, D]: how many pods (existing + placed-this-cycle) OWN a
    required anti-affinity term (sel, key) whose key-domain is d — the
    COUNT version of AffinityState.anti_presence, which preemption needs
    to know whether evicting a node's victim prefix removes the last
    owner blocking a symmetric-anti candidate."""
    S = snap.sel_exprs.shape[0]
    D = snap.domain_key.shape[0]
    dom_e = _exist_domains(snap)  # [E, K]
    onesE = jnp.ones(snap.exist_anti_terms.shape[:2], jnp.float32)
    cnt = _flat_table(snap.exist_anti_terms, onesE, dom_e, S, D)
    placed = snap.pod_valid & (assignment >= 0)
    node_dom = snap.node_domains[jnp.clip(assignment, 0, snap.N - 1)]
    terms_p = jnp.where(
        placed[:, None, None], snap.pod_anti_terms, -1
    )
    onesP = jnp.ones(terms_p.shape[:2], jnp.float32)
    return cnt + _flat_table(terms_p, onesP, node_dom, S, D)


def selector_activity(snap) -> tuple[jnp.ndarray, jnp.ndarray]:
    """(anti_active [S], spread_active [S]): selectors referenced by any
    required anti-affinity term (pending or existing pods) / any topology
    spread constraint — the selectors whose MATCHERS matter for the
    round-commit interaction guards."""
    S = snap.sel_exprs.shape[0]

    def mark(terms_sel):  # i32 [..] selector ids (-1 pad) -> bool [S]
        flat = terms_sel.reshape(-1)
        return (
            jnp.zeros((S,), bool)
            .at[jnp.clip(flat, 0, S - 1)]
            .max(flat >= 0)
        )

    anti_active = mark(snap.pod_anti_terms[..., 0]) | mark(
        snap.exist_anti_terms[..., 0]
    )
    spread_active = mark(snap.pod_tsc[..., 1])
    return anti_active, spread_active
