"""percentageOfNodesToScore: upstream's feasible-node sample, batched.

Upstream's filter pass (`findNodesThatPassFilters`, schedule_one.go)
walks the nodes from a rotating start index and STOPS once
`numFeasibleNodesToFind` of them have passed every filter in the state
the pod is scheduled in; only those are scored. A pod with fewer feasible
nodes than that sees all of them, so the knob can narrow a choice but
never refuse a pod while a node admits it.

A data-dependent early exit per pod is what a TPU does worst, so the
same set is computed shape-static for a whole [B, N] block: one prefix
count of the feasibility mask along the nodes axis gives every node its
rank among the row's feasible nodes in rotated order, and the sample is
`feasible & (rank <= k)`.

Departure from upstream, the only one: the start offset. Upstream keeps
ONE `nextStartNodeIndex` and advances it by the nodes each pod visited;
a batch has no serial order to advance it in, so each pod gets a
deterministic rotation from its queue rank and the cycle's index
(`start_offsets`). Plugin scores that normalise across nodes are still
normalised over all feasible nodes, not over the sample.

`oracle.sampled_candidates` is the sequential reference of the walk;
tests/test_sampling.py holds the two equal."""

from __future__ import annotations

import jax
import jax.numpy as jnp

# upstream's minFeasibleNodesToFind / minFeasibleNodesPercentageToFind
MIN_FEASIBLE_NODES = 100
MIN_FEASIBLE_PERCENTAGE = 5


def num_feasible_nodes_to_find(n, pct: int):
    """How many feasible nodes end a pod's filter pass (i32 []), from the
    REAL node count `n` (traced: a changed count must not recompile) and
    the configured percentage (0 = adaptive, `50 - n/125` floored at 5).
    `n` itself where everything is considered: under 100 nodes."""
    n = jnp.asarray(n).astype(jnp.int32)
    if pct <= 0:
        pct = jnp.maximum(50 - n // 125, MIN_FEASIBLE_PERCENTAGE)
    k = jnp.maximum(n * pct // 100, MIN_FEASIBLE_NODES)
    return jnp.minimum(k, n)


def start_offsets(snap):
    """Per-pod start index of the walk (i32 [P], in [0, n)): rotates with
    the pod's queue rank, so a batch spreads over the cluster the way
    upstream's advancing index spreads consecutive pods, and with the
    encoder's cycle index, so a pod meets a different sample each cycle.
    Each factor is reduced mod n first: no int32 product overflows."""
    n = jnp.maximum(snap.num_nodes.astype(jnp.int32), 1)
    return (
        snap.pod_order.astype(jnp.int32) % n * (75347 % n)
        + snap.cycle_index.astype(jnp.int32) % n * (31337 % n)
    ) % n


def sample_feasible(feasible, off, k):
    """The first `k` feasible nodes of each row in rotated order from
    `off`: (sample bool [..., N], narrowed bool [...]). `feasible` is
    bool [..., N] (padding nodes False; one row for the scan, a [B, N]
    block for the rounds), `off` i32 [...], `k` i32 []. A row with at
    most `k` feasible nodes keeps them all; `narrowed` marks the rows
    that lost a candidate."""
    count = jnp.cumsum(feasible.astype(jnp.int32), axis=-1)  # inclusive
    total = count[..., -1:]
    col = jax.lax.broadcasted_iota(
        jnp.int32, feasible.shape, feasible.ndim - 1
    )
    wrapped = col < off[..., None]  # visited after the walk wraps round
    before = jnp.sum(feasible & wrapped, axis=-1, keepdims=True,
                     dtype=jnp.int32)
    rank = count - before + jnp.where(wrapped, total, 0)
    return feasible & (rank <= k), total[..., 0] > k
