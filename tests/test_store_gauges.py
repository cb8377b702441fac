"""`scheduler_cache_size{type}` and `scheduler_pending_pods{queue}`
follow every applied `Update` (PR 44), not the last cycle's end alone:
between two cycles /metrics shows what the agent added, confirmed and
deleted. Preemption's victims are the case that a benchmark run reads:
they leave the cache when the agent's delete arrives (the served
path's evictor is the no-op default), not in the cycle that evicted
them.

A real gRPC server on an ephemeral port (`test_agent_flush.Served`)."""

from __future__ import annotations

import re

from test_agent_flush import Served, served  # noqa: F401  (the fixture)

from k8s_scheduler_tpu.models import MakeNode, MakePod


def gauges(s: Served, family: str) -> dict[str, int]:
    """{label value: sample} of one gauge family, from the exposition."""
    text = s.scheduler.metrics.expose().decode()
    return {
        label: int(float(value)) for label, value in re.findall(
            rf'^{family}{{\w+="(\w+)"}} (\S+)$', text, re.M)}


def held(s: Served) -> dict[str, int]:
    """What the cache holds, as `stamp_store_gauges` says it."""
    c = s.scheduler.cache.counts()
    return {"nodes": c["nodes"], "pods": c["bound"] + c["assumed"],
            "assumed_pods": c["assumed"]}


def test_the_cache_gauge_follows_the_update_after_an_evicting_cycle(served):
    """One node full of four low-priority pods and one with room for a
    small pod: the cycle binds the small pod, nominates the large one
    and evicts three. The gauge at the cycle's end still counts the
    victims and the assumed bind; after the ONE `Update` that confirms
    the bind and deletes the victims, with no cycle in between, it
    counts neither."""
    s = served()
    low = [MakePod(f"low{i}").req({"cpu": "900m"}).priority(0).obj()
           for i in range(4)]
    small = MakePod("small").req({"cpu": "500m"}).priority(10).obj()
    large = MakePod("large").req({"cpu": "3"}).priority(10).obj()
    with s.agent.batched():
        s.agent.upsert_node(MakeNode("full").capacity({"cpu": "4"}).obj())
        s.agent.upsert_node(MakeNode("room").capacity({"cpu": "1"}).obj())
        for p in low:
            s.agent.upsert_pod(p, bound_node="full")
        s.agent.upsert_pod(small)
        s.agent.upsert_pod(large)
    assert gauges(s, "scheduler_cache_size") == held(s) == {
        "nodes": 2, "pods": 4, "assumed_pods": 0}
    resp = s.client.cycle()
    assert [(b.pod_uid, b.node_name) for b in resp.bindings] == [
        (small.uid, "room")]
    assert [n.pod_uid for n in resp.nominations] == [large.uid]
    assert len(resp.evictions) == 3
    assert gauges(s, "scheduler_cache_size") == {
        "nodes": 2, "pods": 5, "assumed_pods": 1}
    before = len(s.requests)
    with s.agent.batched():
        s.agent.upsert_pod(small, bound_node="room")
        for ev in resp.evictions:
            s.agent.delete_pod(ev.pod_uid)
    assert len(s.requests) == before + 1
    assert held(s) == {"nodes": 2, "pods": 2, "assumed_pods": 0}
    assert gauges(s, "scheduler_cache_size") == held(s)


def test_the_pending_gauge_follows_an_update_that_only_adds_pods(served):
    s = served()
    with s.agent.batched():
        s.agent.upsert_node(MakeNode("n0").capacity({"cpu": "4"}).obj())
    assert gauges(s, "scheduler_pending_pods") == {
        "active": 0, "backoff": 0, "unschedulable": 0}
    with s.agent.batched():
        for i in range(7):
            s.agent.upsert_pod(MakePod(f"p{i}").req({"cpu": "100m"}).obj())
    assert gauges(s, "scheduler_pending_pods") == \
        s.scheduler.queue.pending_counts() == {
            "active": 7, "backoff": 0, "unschedulable": 0}
    # ... and one that takes some of them away again
    with s.agent.batched():
        for i in range(3):
            s.agent.delete_pod(MakePod(f"p{i}").obj().uid)
    assert gauges(s, "scheduler_pending_pods")["active"] == 4
