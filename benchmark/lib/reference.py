"""The plain reference: what a correct scheduler may and may not do.

numpy and Python integers only. It reads pods and nodes through the API
types (`models/api.py` dataclasses) and imports no scheduling code of
the program. It decides `correct` from what the client was SENT:

Pods finish: a cycle's `completed` uids (what the agent deleted before
it) leave the replayed cluster before anything of that cycle is read, so
(b), (c)'s start-of-cycle counts and (d) see the room and the peers a
correct server sees. A completed uid that was not resident is a fault of
the harness (`bad_completions`, limit 0); a resident set that stands over
the configuration's `resident_target` at a cycle's start means the
completions stopped (`resident_over_target`, limit 0). A server that lost
a delete keeps a node fuller than it is, and refuses a pod the reference
finds room for: (d).

(a) every binding names a known node and a pod that was pending, once;
(b) per node, CPU (milli), memory (bytes) and pod count within
    allocatable, in exact integers, after every cycle;
(c) every pod a cycle bound passes taints, node selector, required node
    affinity, required anti-affinity both ways, required affinity (with
    the bootstrap allowance) and `DoNotSchedule` skew. A cycle binds
    many pods at once, so the two order-dependent rules are held to what
    any serial order of that cycle must satisfy: affinity needs a peer
    in the domain at the cycle's end if one existed anywhere at its
    start; skew is the domain's count at the cycle's START + 1 minus the
    smallest count at its END;
(d) a pod the cycle refused with a diagnosis that rejects every node is
    infeasible on every node in the state at the end of that cycle, its
    bindings in and its evictions (the answer to the refusals) not yet. A
    refusal whose own diagnosis leaves nodes open (the engine's round
    cap, a guard that deferred the pod) is the documented allowance: it
    is counted apart and held to `REFUSED_OPEN_LIMIT` a run, so the
    program's diagnosis cannot choose which refusals are looked at;
(e) a probe pod sits on a node whose float64 resource score is within
    one point of its pool's best: the engine rounds score sums to
    integers and breaks ties by hash, so one point is what it may not
    tell apart — whatever constant the other plugins add. And the
    probes did bind: of every round of one probe per pool that fell
    due, at most `PROBES_MISSING_LIMIT` may be missing, so a run that
    leaves its probes pending or refuses them has not passed (e).

`probe_choice` is the reference put in the program's place for (e); in
`bfloat16` it is the control that has to fail.
"""

from __future__ import annotations

import dataclasses

import numpy as np

HOST_KEY = "kubernetes.io/hostname"
RESOURCES = ("cpu", "memory", "pods")
# constant part of a probe's score sum over its pool, by the default
# plugin weights: TaintToleration 3 x 100 (no PreferNoSchedule taints),
# NodeAffinity 1 x 100 (the preferred term matches every pool node)
PROBE_CONSTANT = (3.0 * 100.0, 1.0 * 100.0)
# one integer score class, plus float32's error on a sum near 600
PROBE_GAP_LIMIT = 1.0 + 1e-3
# share of (pools x rounds due) probes that never bound. Sound runs bind
# every probe in the cycle that offers it (0.0); a run that leaves them
# pending offers one per pool and no more (>= 0.8 from five rounds on),
# and under a sampled node window none bound at all (1.0): PERF.md
PROBES_MISSING_LIMIT = 0.2
# refusals a run may make with nodes left open by the program's own
# diagnosis: 0 in every sound run, 8 in each run under the sampled node
# window that wrongly refused 157 and 183 pods (PERF.md, section 4)
REFUSED_OPEN_LIMIT = 3


def requests(pod) -> tuple[int, int, int]:
    r = pod.resource_requests()
    return tuple(int(round(r.get(k, 0.0))) for k in RESOURCES)


def _selector_key(namespace: str, sel) -> tuple:
    return (
        namespace,
        tuple(sorted(sel.match_labels.items())),
        tuple((e.key, e.operator, tuple(e.values))
              for e in sel.match_expressions),
    )


def _expr_ok(labels: dict, key: str, op: str, values) -> bool:
    if op == "In":
        return labels.get(key) in values
    if op == "NotIn":
        return labels.get(key) not in values
    if op == "Exists":
        return key in labels
    if op == "DoesNotExist":
        return key not in labels
    have = labels.get(key)
    try:
        return (int(have) > int(values[0])) if op == "Gt" else (
            int(have) < int(values[0]))
    except (TypeError, ValueError, IndexError):
        return False


def _selector_matches(key: tuple, pod) -> bool:
    namespace, match_labels, exprs = key
    labels = pod.metadata.labels
    return (
        pod.namespace == namespace
        and all(labels.get(k) == v for k, v in match_labels)
        and all(_expr_ok(labels, k, op, vals) for k, op, vals in exprs)
    )


def _tolerates(pod, taint) -> bool:
    for t in pod.spec.tolerations:
        if t.effect and t.effect != taint.effect:
            continue
        if t.operator == "Exists":
            if t.key in ("", taint.key):
                return True
        elif t.key == taint.key and t.value == taint.value:
            return True
    return False


@dataclasses.dataclass
class _Terms:
    """A pod's required inter-pod terms, as (topology key, selector)."""

    affinity: list
    anti: list
    spread: list  # (topology key, selector, max skew)


def _terms(pod) -> _Terms:
    aff = pod.spec.affinity
    ns = pod.namespace

    def keyed(terms):
        return [
            (t.topology_key, _selector_key((t.namespaces or (ns,))[0],
                                           t.label_selector))
            for t in terms
        ]

    return _Terms(
        keyed(aff.pod_affinity.required) if aff and aff.pod_affinity else [],
        keyed(aff.pod_anti_affinity.required)
        if aff and aff.pod_anti_affinity else [],
        [
            (c.topology_key, _selector_key(ns, c.label_selector), c.max_skew)
            for c in pod.spec.topology_spread_constraints
            if c.when_unsatisfiable == "DoNotSchedule"
        ],
    )


class Cluster:
    """Per-node state in exact integers, built from what was sent."""

    def __init__(self, nodes) -> None:
        self.nodes = nodes
        self.index = {n.name: i for i, n in enumerate(nodes)}
        n = len(nodes)
        self.alloc = np.array(
            [[int(round(nd.status.allocatable.get(r, 0.0)))
              for r in RESOURCES] for nd in nodes], np.int64)
        self.used = np.zeros((n, 3), np.int64)
        self.labels = [
            {HOST_KEY: nd.name, **nd.metadata.labels} for nd in nodes
        ]
        self.unschedulable = np.array(
            [nd.spec.unschedulable for nd in nodes], bool)
        # nodes grouped by taint set: a pod is tested once per group
        groups: dict[tuple, list[int]] = {}
        for i, nd in enumerate(nodes):
            hard = tuple(t for t in nd.spec.taints
                         if t.effect in ("NoSchedule", "NoExecute"))
            groups.setdefault(
                tuple((t.key, t.value, t.effect) for t in hard), []
            ).append(i)
        self.taint_groups = [
            ([_Taint(*k) for k in key], np.array(idx))
            for key, idx in groups.items()
        ]
        self._domains: dict[str, tuple[np.ndarray, int]] = {}
        self._label_cols: dict[str, np.ndarray] = {}
        # selector -> matching pods per node; selector -> topology key ->
        # pods per domain that HOLD a required anti-affinity term on it
        self.match: dict[tuple, np.ndarray] = {}
        self.anti_held: dict[tuple, dict[str, np.ndarray]] = {}
        self.where: dict[str, int] = {}  # uid -> node index
        self._pods: dict[str, object] = {}
        self._matches_of: dict[str, list] = {}
        self._by_label: dict[tuple, list] = {}
        self._general: list = []
        self._placed_with: dict[tuple, set] = {}  # label item -> uids

    # ---- topology and labels ----------------------------------------

    def domain(self, key: str) -> tuple[np.ndarray, int]:
        """(domain id per node, or -1 where the key is absent; count)."""
        if key not in self._domains:
            ids: dict[str, int] = {}
            col = np.array([
                ids.setdefault(lb[key], len(ids)) if key in lb else -1
                for lb in self.labels
            ])
            self._domains[key] = (col, len(ids))
        return self._domains[key]

    def _label_col(self, key: str) -> np.ndarray:
        if key not in self._label_cols:
            self._label_cols[key] = np.array(
                [lb.get(key) for lb in self.labels], object)
        return self._label_cols[key]

    def per_domain(self, key: str, per_node: np.ndarray) -> np.ndarray:
        col, n = self.domain(key)
        ok = col >= 0
        return np.bincount(col[ok], weights=per_node[ok], minlength=n)

    # ---- selectors --------------------------------------------------

    def watch(self, sel: tuple) -> np.ndarray:
        """Start counting pods that match `sel` (placed pods included)."""
        if sel not in self.match:
            counts = np.zeros(len(self.nodes), np.int64)
            _ns, match_labels, exprs = sel
            for uid in (self._placed_with.get(match_labels[0], ())
                        if match_labels else list(self.where)):
                if _selector_matches(sel, self._pods[uid]):
                    counts[self.where[uid]] += 1
                    self._matches_of[uid].append(sel)
            self.match[sel] = counts
            if len(match_labels) >= 1 and not exprs:
                self._by_label.setdefault(match_labels[0], []).append(sel)
            else:
                self._general.append(sel)
        return self.match[sel]

    def _watched_matching(self, pod) -> list:
        cands = list(self._general)
        for item in pod.metadata.labels.items():
            cands.extend(self._by_label.get(item, ()))
        return [s for s in cands if _selector_matches(s, pod)]

    # ---- state ------------------------------------------------------

    def add(self, pod, node: int) -> None:
        terms = _terms(pod)
        for _key, sel in terms.affinity + terms.anti:
            self.watch(sel)
        for _key, sel, _skew in terms.spread:
            self.watch(sel)
        self.used[node] += requests(pod)
        self.where[pod.uid] = node
        self._pods[pod.uid] = pod
        for item in pod.metadata.labels.items():
            self._placed_with.setdefault(item, set()).add(pod.uid)
        matched = self._matches_of[pod.uid] = self._watched_matching(pod)
        for sel in matched:
            self.match[sel][node] += 1
        for key, sel in terms.anti:
            held = self.anti_held.setdefault(sel, {}).setdefault(
                key, np.zeros(self.domain(key)[1], np.int64))
            d = self.domain(key)[0][node]
            if d >= 0:
                held[d] += 1

    def remove(self, uid: str) -> None:
        pod, node = self._pods.pop(uid), self.where.pop(uid)
        for item in pod.metadata.labels.items():
            self._placed_with[item].discard(uid)
        self.used[node] -= requests(pod)
        for sel in self._matches_of.pop(uid):
            self.match[sel][node] -= 1
        for key, sel in _terms(pod).anti:
            d = self.domain(key)[0][node]
            if d >= 0:
                self.anti_held[sel][key][d] -= 1

    def matches_of(self, uid: str) -> list:
        """The watched selectors a placed pod matches."""
        return self._matches_of[uid]

    def over_capacity(self) -> np.ndarray:
        return np.flatnonzero((self.used > self.alloc).any(axis=1))

    # ---- static filters ---------------------------------------------

    def static_mask(self, pod) -> np.ndarray:
        ok = ~self.unschedulable
        for taints, idx in self.taint_groups:
            if not all(_tolerates(pod, t) for t in taints):
                ok[idx] = False
        if pod.spec.node_name:
            ok &= self._label_col(HOST_KEY) == pod.spec.node_name
        for k, v in pod.spec.node_selector.items():
            ok &= self._label_col(k) == v
        aff = pod.spec.affinity
        if aff and aff.node_affinity and aff.node_affinity.required:
            any_term = np.zeros(len(self.nodes), bool)
            for term in aff.node_affinity.required:
                any_term |= np.array([
                    all(_expr_ok(lb, e.key, e.operator, e.values)
                        for e in term.match_expressions)
                    and all(_expr_ok({"metadata.name": lb[HOST_KEY]},
                                     e.key, e.operator, e.values)
                            for e in term.match_fields)
                    for lb in self.labels
                ])
            ok &= any_term
        return ok

    def static_ok(self, pod, node: int) -> bool:
        """`static_mask(pod)[node]` without the other nodes."""
        nd, lb = self.nodes[node], self.labels[node]
        aff = pod.spec.affinity
        required = (aff.node_affinity.required
                    if aff and aff.node_affinity else ())
        return (
            not nd.spec.unschedulable
            and all(_tolerates(pod, t) for t in nd.spec.taints
                    if t.effect in ("NoSchedule", "NoExecute"))
            and pod.spec.node_name in ("", nd.name)
            and all(lb.get(k) == v for k, v in pod.spec.node_selector.items())
            and (not required or any(
                all(_expr_ok(lb, e.key, e.operator, e.values)
                    for e in term.match_expressions)
                and all(_expr_ok({"metadata.name": nd.name},
                                 e.key, e.operator, e.values)
                        for e in term.match_fields)
                for term in required))
        )

    # ---- (d): where could a pending pod go --------------------------

    def feasible(self, pod) -> np.ndarray:
        """bool per node: every hard rule admits `pod` there now."""
        ok = self.static_mask(pod)
        ok &= (self.used + np.array(requests(pod)) <= self.alloc).all(axis=1)
        terms = _terms(pod)
        for key, sel in terms.anti:
            col, _n = self.domain(key)
            here = self.per_domain(key, self.watch(sel))
            ok &= (col < 0) | (here[np.maximum(col, 0)] == 0)
        for sel in self._watched_matching(pod):
            for key, held in self.anti_held.get(sel, {}).items():
                col, _n = self.domain(key)
                ok &= (col < 0) | (held[np.maximum(col, 0)] == 0)
        for key, sel in terms.affinity:
            counts = self.watch(sel)
            if not counts.any() and _selector_matches(sel, pod):
                continue  # bootstrap: the first of a self-affine group
            col, _n = self.domain(key)
            here = self.per_domain(key, counts)
            ok &= (col >= 0) & (here[np.maximum(col, 0)] > 0)
        for key, sel, skew in terms.spread:
            col, n = self.domain(key)
            if n == 0:
                continue
            here = self.per_domain(key, self.watch(sel))
            ok &= (col >= 0) & (
                here[np.maximum(col, 0)] + 1 - here.min() <= skew)
        return ok


@dataclasses.dataclass(frozen=True)
class _Taint:
    key: str
    value: str
    effect: str


@dataclasses.dataclass
class Cycle:
    """One Cycle as the client saw it."""

    offered: set  # uids pending at the server when the cycle ran
    bindings: list  # (uid, node name)
    evictions: list  # (uid, node name)
    # (uid, nodes the diagnosis rejected, nodes it counted, its message)
    refused: list
    # uids the agent deleted as finished before this cycle ran
    completed: list = dataclasses.field(default_factory=list)


@dataclasses.dataclass
class Verdict:
    problems: list  # human-readable, first few of each kind
    counts: dict  # every number compared, beside its limit
    wrongly_refused: int = 0
    # refusals whose own diagnosis left nodes open: where and what they
    # said, so that a run over REFUSED_OPEN_LIMIT names its cause
    open_refusals: dict = dataclasses.field(default_factory=dict)
    # bound pods in the replayed cluster, per cycle: at its start (its
    # completions gone) and after its confirmations (bindings in,
    # evictions out)
    resident_at_start: list = dataclasses.field(default_factory=list)
    resident_after: list = dataclasses.field(default_factory=list)

    @property
    def ok(self) -> bool:
        return not self.problems


def check_run(nodes, init, pods: dict, cycles: list[Cycle],
              pools, probe_rounds: int,
              resident_target: int | None = None) -> Verdict:
    """Replay the run cycle by cycle. `pods` maps uid -> Pod for every
    pod ever offered; `probe_rounds` is how many times a probe per pool
    fell due; `resident_target` is what the configuration holds the
    resident set to at a cycle's start (None: nothing completes)."""
    cl = Cluster(nodes)
    for pod, node in init:
        cl.add(pod, cl.index[node])
    bad: dict[str, list] = {}

    def note(kind: str, what: str) -> None:
        bad.setdefault(kind, []).append(what)

    n_bind = n_refused = n_allowed = n_checked = wrongly = 0
    bound_ever: set = set()
    probe_nodes: list[int] = []  # where each probe was bound
    open_by_cycle: dict[int, int] = {}
    open_said: dict[str, int] = {}
    open_uids: set = set()
    open_samples: list[str] = []
    n_completed = 0
    at_start: list[int] = []
    after: list[int] = []
    for ci, cyc in enumerate(cycles):
        for uid in cyc.completed:  # gone before the cycle read anything
            n_completed += 1
            if uid in cl.where:
                cl.remove(uid)
            else:
                note("completion", f"cycle {ci}: {uid} was not resident")
        at_start.append(len(cl.where))
        seen = set()
        fresh = []
        for uid, node in cyc.bindings:  # (a)
            n_bind += 1
            if (uid in seen or uid in bound_ever or uid not in cyc.offered
                    or node not in cl.index):
                note("binding", f"cycle {ci}: {uid} -> {node}")
                continue
            seen.add(uid)
            fresh.append((pods[uid], cl.index[node]))
            if is_probe(uid):
                probe_nodes.append(cl.index[node])
        bound_ever |= seen
        # (c) part 1: what has to be read at the cycle's START
        start = []
        for pod, node in fresh:
            t = _terms(pod)
            start.append((
                [cl.watch(sel).any() for _k, sel in t.affinity],
                [cl.per_domain(k, cl.watch(sel))[cl.domain(k)[0][node]]
                 if cl.domain(k)[0][node] >= 0 else None
                 for k, sel, _s in t.spread],
            ))
        for pod, node in fresh:
            cl.add(pod, node)
        for i in cl.over_capacity():  # (b)
            note("capacity", f"cycle {ci}: {nodes[i].name} "
                 f"{cl.used[i].tolist()} > {cl.alloc[i].tolist()}")
        for (pod, node), (peer_at_start, count_at_start) in zip(fresh, start):
            _check_bound(cl, pod, node, peer_at_start, count_at_start,
                         lambda what: note("constraint", f"cycle {ci}: {what}"))
        for uid, rejected, counted, said in cyc.refused:  # (d)
            n_refused += 1
            if rejected < counted:
                n_allowed += 1  # the program itself leaves nodes open
                open_by_cycle[ci] = open_by_cycle.get(ci, 0) + 1
                open_said[said] = open_said.get(said, 0) + 1
                open_uids.add(uid)
                if len(open_samples) < 8:
                    open_samples.append(
                        f"cycle {ci}: {uid} ({said!r}); the reference "
                        f"finds {int(cl.feasible(pods[uid]).sum())} nodes "
                        f"open at the cycle's end; pod: "
                        f"{describe(pods[uid])}")
                continue
            n_checked += 1
            open_nodes = np.flatnonzero(cl.feasible(pods[uid]))
            if open_nodes.size:
                wrongly += 1
                note("refused", f"cycle {ci}: {uid} refused ({said!r}), "
                     f"feasible on {[nodes[i].name for i in open_nodes[:3]]}"
                     f"; pod: {describe(pods[uid])}")
        # evictions are this cycle's answer to its own refusals: the
        # victims were still there when the refused pods were judged
        for uid, node in cyc.evictions:
            if cl.where.get(uid) != cl.index.get(node):
                note("eviction", f"cycle {ci}: {uid} not on {node}")
            else:
                cl.remove(uid)
        after.append(len(cl.where))
    over_target = 0 if resident_target is None else max(
        0, max(at_start, default=0) - resident_target)
    if over_target:
        note("resident", f"{over_target} pods over the resident target "
             f"{resident_target} at a cycle's start: completions stopped")
    gap, n_probe = probe_gaps(cl, pools, probe_nodes)
    if gap > PROBE_GAP_LIMIT:
        note("probe", f"a probe sits {gap:.4f} score points under its "
             f"pool's best (limit {PROBE_GAP_LIMIT})")
    due = len(pools) * probe_rounds
    missing = 1.0 - n_probe / due if due else float(bool(pools))
    if missing > PROBES_MISSING_LIMIT:
        note("probe", f"{n_probe} of {due} probes due were bound: "
             "the precision check (e) has not run")
    if n_allowed > REFUSED_OPEN_LIMIT:
        note("refused", f"{n_allowed} refusals left nodes open by the "
             f"program's own diagnosis (limit {REFUSED_OPEN_LIMIT})")
    counts = {
        "bindings": n_bind,
        "bad_bindings": [len(bad.get("binding", [])), 0],
        "nodes_over_allocatable": [len(bad.get("capacity", [])), 0],
        "constraint_breaches": [len(bad.get("constraint", [])), 0],
        "bad_evictions": [len(bad.get("eviction", [])), 0],
        "completed": n_completed,
        "bad_completions": [len(bad.get("completion", [])), 0],
        "resident_over_target": [over_target, 0],
        "refused": n_refused,
        "refused_with_nodes_left_open_by_the_program": [
            n_allowed, REFUSED_OPEN_LIMIT],
        "refused_checked": n_checked,
        "wrongly_refused": [wrongly, 0],
        "probes_bound": n_probe,
        "probes_missing_share": [missing, PROBES_MISSING_LIMIT],
        "probe_score_gap_max": [gap, PROBE_GAP_LIMIT],
    }
    problems = [f"{k}: {v[0]} (+{len(v) - 1} more)" for k, v in bad.items()]
    opened = {
        "by_cycle": open_by_cycle, "of_cycles": len(cycles),
        "distinct_pods": len(open_uids),
        "said": sorted(open_said.items(), key=lambda kv: -kv[1])[:6],
        "samples": open_samples,
    } if n_allowed else {}
    return Verdict(problems, counts, wrongly, opened, at_start, after)


def describe(pod) -> str:
    t = _terms(pod)
    return (f"labels={pod.metadata.labels} selector={pod.spec.node_selector} "
            f"tolerations={len(pod.spec.tolerations)} affinity={t.affinity} "
            f"anti={t.anti} spread={t.spread}")


def _check_bound(cl: Cluster, pod, node: int, peer_at_start,
                 count_at_start, note) -> None:
    """(c) for one pod the cycle bound, in the state at the cycle's end."""
    if not cl.static_ok(pod, node):
        note(f"{pod.name} fails a node filter on {cl.nodes[node].name}")
    t = _terms(pod)
    for key, sel in t.anti:
        d = cl.domain(key)[0][node]
        if d < 0:
            continue
        others = cl.per_domain(key, cl.match[sel])[d] - _selector_matches(
            sel, pod)
        if others > 0:
            note(f"{pod.name}: anti-affinity on {key} broken by "
                 f"{int(others)} pods")
    for sel in cl.matches_of(pod.uid):
        for key, held in cl.anti_held.get(sel, {}).items():
            d = cl.domain(key)[0][node]
            own = sum(1 for k, s in t.anti if (k, s) == (key, sel))
            if d >= 0 and held[d] - own > 0:
                note(f"{pod.name} breaks an anti-affinity held on {key}")
    for (key, sel), had_peer in zip(t.affinity, peer_at_start):
        d = cl.domain(key)[0][node]
        if d < 0:
            note(f"{pod.name}: affinity key {key} absent")
        elif had_peer or not _selector_matches(sel, pod):
            peers = cl.per_domain(key, cl.match[sel])[d] - _selector_matches(
                sel, pod)
            if peers <= 0:
                note(f"{pod.name}: affinity on {key} has no peer")
    for (key, sel, skew), at_start in zip(t.spread, count_at_start):
        if at_start is None:
            note(f"{pod.name}: spread key {key} absent")
            continue
        low = cl.per_domain(key, cl.match[sel]).min()
        if at_start + 1 - low > skew:
            note(f"{pod.name}: skew {int(at_start + 1 - low)} > {skew}")


# ---- (e): probes -----------------------------------------------------


def resource_scores(alloc, used, dtype=np.float64) -> np.ndarray:
    """LeastAllocated + BalancedAllocation over cpu and memory for a pod
    that requests nothing, per node, every operation in `dtype`."""
    a = np.asarray(alloc)[:, :2].astype(dtype)
    u = np.asarray(used)[:, :2].astype(dtype)
    one, hundred, two = dtype(1), dtype(100), dtype(2)
    frac = np.clip((u / a).astype(dtype), dtype(0), one)
    least = (((one - frac) * hundred).astype(dtype).sum(axis=1) / two)
    mean = (frac.sum(axis=1) / two).astype(dtype)
    var = (((frac - mean[:, None]) ** 2).astype(dtype).sum(axis=1) / two)
    balanced = ((one - np.sqrt(var.astype(dtype)).astype(dtype)) * hundred)
    return (least.astype(dtype) + balanced.astype(dtype)).astype(dtype)


def probe_choice(alloc, used, dtype=np.float64) -> int:
    """The reference in the program's place: the node a probe goes to,
    by the whole score sum rounded to an integer, lowest index on a tie.
    In float64 this is the answer; in the precision below float32 it is
    the control."""
    total = resource_scores(alloc, used, dtype)
    for c in PROBE_CONSTANT:
        total = (total + dtype(c)).astype(dtype)
    return int(np.argmax(np.round(total.astype(np.float64))))


def is_probe(uid: str) -> bool:
    name = uid.split("/")[-1]
    return name.startswith("probe-") and not name.startswith("probe-load-")


def probe_gaps(cl: Cluster, pools, probe_nodes) -> tuple[float, int]:
    """Widest gap, in float64 score points, by which a probe's node lies
    under the best node of its pool; and how many probes were bound.
    Probes request nothing and only they enter a pool, so a pool's
    scores do not move in a run."""
    worst = 0.0
    chosen = set(probe_nodes)
    for pool in pools:
        idx = np.array(pool.nodes)
        scores = resource_scores(cl.alloc[idx], cl.used[idx])
        for j, node in enumerate(pool.nodes):
            if node in chosen:
                worst = max(worst, float(scores.max() - scores[j]))
    return worst, len(probe_nodes)
