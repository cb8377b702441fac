"""The deployment from `--seed`: nodes, bound pods, the stream of
pending pods, and the probe pools — every size from the configuration's
file, every draw from the seed.

The shapes of `utils/synth.make_cluster`/`make_pods` are kept (zone
and region labels by node index; one `app` label per pod and
constraints that select the pod's own app), copied here so the yardstick
does not move with the program's test fixtures. Three things differ, all
for steadiness: node and pod sizes come from the configuration (synth's
make a full cluster); a constraint is carried by an exact count of
pods in every block of `BLOCK`, shuffled by the seed, not by a coin per
pod — every seed offers the same multiset in another order; and every
node type stands in every zone (synth's `i % 3` beside `i % 6` puts a
type in two zones of six, and a pod with a selector AND a zone spread
whose two zones are not the app's emptiest fits no node, ever: 1.4% of
all pods offered, a backlog that grows all through a run — PERF.md
section 6, PR 29). What a cell refuses is said in the configuration
(`unschedulable`), a fixed count, not left to the labels.
"""

from __future__ import annotations

import dataclasses

import numpy as np

from k8s_scheduler_tpu.models.api import Node, Pod
from k8s_scheduler_tpu.models.builders import MakeNode, MakePod

ZONES = [f"zone-{c}" for c in "abcdef"]
REGIONS = ["region-1", "region-2"]
NODE_TYPES = ["general", "compute", "memory"]
ZONE_KEY = "topology.kubernetes.io/zone"
HOST_KEY = "kubernetes.io/hostname"
POOL_KEY = "bench.probe/pool"
BLOCK = 1000
MIX_KINDS = ("selector", "toleration", "affinity", "anti_affinity", "spread")


@dataclasses.dataclass
class ProbePool:
    index: int
    nodes: list[int]  # indices into Deployment.nodes
    best: int  # node index the float64 reference ranks first


@dataclasses.dataclass
class Deployment:
    nodes: list[Node]
    init: list[tuple[Pod, str]]  # bound before the first cycle
    pools: list[ProbePool]
    rng: np.random.Generator
    cfg: dict
    _made: dict = dataclasses.field(default_factory=dict)
    _probes: int = 0

    def pending(self, count: int, prefix: str) -> list[Pod]:
        """The next `count` pods of the `prefix` stream (drawn in whole
        blocks, so any stretch of the stream carries the mix)."""
        buf = self._made.setdefault(prefix, [0, []])
        while len(buf[1]) < count:
            buf[1].extend(_block(self.cfg["pods"], self.rng, buf[0], prefix))
            buf[0] += BLOCK
        out, buf[1] = buf[1][:count], buf[1][count:]
        return out

    def unschedulable(self) -> list[Pod]:
        """The configuration's `unschedulable` pods: scheduler_perf's
        Unschedulable workload, pods that ask for more CPU than a node
        has and stay pending beside the measured ones. A fixed count,
        the oldest of the lowest priority; they ride the warm-up batch
        (named `warm-`, so not among the pods a run attempts), so that
        the programs that serve a refusal are warm before the window.
        The program's queue parks them after a refusal; a completion
        (`PodDelete`, a queueing hint of NodeResourcesFit) un-parks
        them, so they are judged again as their backoff runs out."""
        uc = self.cfg.get("unschedulable") or {}
        return [
            MakePod(f"warm-unschedulable-{i}")
            .req({"cpu": uc["cpu"], "memory": uc["memory"]})
            .labels({"app": "unschedulable"})
            .priority(0)
            .created(-2.0 + i * 1e-6)
            .obj()
            for i in range(int(uc.get("count", 0)))
        ]

    def probe(self, pool: ProbePool) -> Pod:
        """A best-effort pod that only its pool admits: a selector and a
        toleration for the pool, and a preferred term every node of the
        pool satisfies (a constant the precision control needs: it lifts
        the score sum over 512, where bfloat16 steps by 4)."""
        self._probes += 1
        k = f"pool-{pool.index}"
        return (
            MakePod(f"probe-{pool.index}-{self._probes}")
            .req({})
            .labels({"app": f"probe-{k}"})
            .node_selector({POOL_KEY: k})
            .toleration(POOL_KEY, k, "NoSchedule")
            .node_affinity_preferred(1, POOL_KEY, [k])
            .created(1e6 + self._probes)
            .obj()
        )


def _exact(rng, n: int, fraction: float) -> np.ndarray:
    mask = np.zeros(n, bool)
    mask[: int(round(fraction * n))] = True
    return rng.permutation(mask)


def _block(pc: dict, rng, start: int, prefix: str) -> list[Pod]:
    mix = pc.get("mix", {})
    has = {k: _exact(rng, BLOCK, mix.get(k, 0.0)) for k in MIX_KINDS}
    apps = rng.permutation(np.arange(BLOCK) % pc["num_apps"])
    prios = rng.permutation(
        np.resize(np.asarray(pc["priorities"]), BLOCK)
    )
    pods = []
    for j in range(BLOCK):
        i = start + j
        app = f"app-{int(apps[j])}"
        b = (
            MakePod(f"{prefix}-{i}")
            .req({"cpu": pc["cpu"], "memory": pc["memory"]})
            .labels({"app": app})
            .priority(int(prios[j]))
            .created(float(i))
        )
        if has["selector"][j]:
            b.node_selector({"node-type": NODE_TYPES[i % 3]})
        if has["toleration"][j]:
            b.toleration("dedicated", "special", "NoSchedule")
        if has["affinity"][j]:
            b.pod_affinity(ZONE_KEY, {"app": app})
        if has["anti_affinity"][j]:
            b.pod_affinity(HOST_KEY, {"app": app}, anti=True)
        if has["spread"][j]:
            b.spread(pc["spread_max_skew"], ZONE_KEY, {"app": app})
        pods.append(b.obj())
    return pods


def _quantity(text: str, millis: bool = False) -> int:
    from k8s_scheduler_tpu.utils.quantity import parse_quantity

    return int(round(parse_quantity(text, as_millis=millis)))


def _pool_loads(rng, n: int, pc: dict) -> list[float]:
    """Resource-score targets (LeastAllocated + BalancedAllocation, out
    of 200) for one pool's nodes: the best leads the second by `gap`
    points, inside bfloat16's step at the score sum and far outside
    float32's; the rest trail further."""
    best = rng.uniform(*pc["best_score"])
    second = best - rng.uniform(*pc["gap"])
    rest = second - rng.uniform(*pc["trail"], size=n - 2)
    return [best, second, *rest.tolist()]


def deployment(cfg: dict, seed: int, cut: dict | None = None) -> Deployment:
    """`cut` (the rehearsal's sizes) overrides counts only."""
    cfg = {**cfg, **(cut or {})}
    rng = np.random.default_rng(seed)
    nc, pc = cfg["nodes"], cfg["probe"]
    n = nc["count"]
    n_pool = pc["pools"] * pc["nodes_per_pool"]
    tainted = _exact(rng, n - n_pool, nc["taint_fraction"])
    cpu_m = _quantity(nc["cpu"], millis=True)
    mem_b = _quantity(nc["memory"])
    nodes, init, pools = [], [], []
    for i in range(n):
        b = MakeNode(f"node-{i}").capacity(
            {"cpu": nc["cpu"], "memory": nc["memory"], "pods": nc["pods"]}
        ).labels({
            ZONE_KEY: ZONES[i % len(ZONES)],
            "topology.kubernetes.io/region": REGIONS[i % len(REGIONS)],
            # every type in every zone (see the module's note)
            "node-type": NODE_TYPES[(i // len(ZONES)) % len(NODE_TYPES)],
        })
        if i < n - n_pool:
            if tainted[i]:
                b.taint("dedicated", "special")
        else:
            k = (i - (n - n_pool)) // pc["nodes_per_pool"]
            b.labels({POOL_KEY: f"pool-{k}"}).taint(POOL_KEY, f"pool-{k}")
        nodes.append(b.obj())
    for k in range(pc["pools"]):
        first = n - n_pool + k * pc["nodes_per_pool"]
        idx = [int(first + j) for j in rng.permutation(pc["nodes_per_pool"])]
        for score, node in zip(_pool_loads(rng, len(idx), pc), idx):
            # score = 100(1 - (fc+fm)/2) + 100(1 - |fc-fm|/2), fc = fm + e
            e = rng.uniform(0.0, pc["imbalance"])
            fm = (200.0 - score) / 100.0 - e
            load = (
                MakePod(f"probe-load-{node}")
                .req({
                    "cpu": f"{int(round((fm + e) * cpu_m))}m",
                    "memory": str(int(round(fm * mem_b))),
                })
                .labels({"app": f"probe-{k}"})
                .toleration(POOL_KEY, f"pool-{k}", "NoSchedule")
                .created(-1.0)
                .obj()
            )
            init.append((load, nodes[node].name))
        pools.append(ProbePool(k, sorted(idx), idx[0]))
    dep = Deployment(nodes, init, pools, rng, cfg)
    plain = n - n_pool
    for j, pod in enumerate(dep.pending(cfg["init_pods"], "init")):
        init.append((pod, nodes[j % plain].name))
    return dep
