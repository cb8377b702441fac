"""Per-plugin profiling pass (SURVEY.md §5.1 tracing/profiling).

The production cycle fuses every plugin kernel into one XLA program, so
per-plugin latency is not separable there (upstream can time each plugin
because it dispatches callbacks eagerly). This pass re-runs each enabled
plugin's static kernel as its own jitted program, blocked to completion,
and records the upstream per-plugin histograms:

    scheduler_plugin_execution_duration_seconds{plugin,extension_point,...}
    scheduler_framework_extension_point_duration_seconds{extension_point,...}

plus a per-plugin decision-log report (feasible fraction per Filter, score
stats per Score) — the per-plugin mask statistics from SURVEY.md §5.5.

Run it sampled (Scheduler.profile_cycle, or the CLI's --profile-every
knob), never in the hot loop. For kernel-level detail beyond this, trace
the serving process from outside with `jax.profiler` (as
benchmark/traced_server.py does): core/pipeline.py's `sched.dispatch`
events put the trace on the flight recorder's clock.
"""

from __future__ import annotations

import time as _time
from typing import Any, Iterable

import jax
import jax.numpy as jnp
import numpy as np

from ..framework.interfaces import CycleContext
from ..framework.runtime import Framework
from ..metrics import SchedulerMetrics
from ..models.encoding import ClusterSnapshot
from ..ops import argsel


def _time_call(fn, snap, repeats: int = 3) -> tuple[float, Any]:
    """Compile (untimed), then best-of-`repeats` wall time, result blocked."""
    out = fn(snap)
    jax.block_until_ready(out)
    best = float("inf")
    for _ in range(repeats):
        t0 = _time.perf_counter()
        out = fn(snap)
        jax.block_until_ready(out)
        best = min(best, _time.perf_counter() - t0)
    return best, out


def profile_plugins(
    framework: Framework,
    snap: ClusterSnapshot,
    metrics: SchedulerMetrics | None = None,
    repeats: int = 3,
) -> dict[str, dict[str, Any]]:
    """Time each plugin's static kernel in isolation; returns a report
    {plugin_name: {extension_point, seconds, ...stats}} and records the
    per-plugin/per-point histograms when `metrics` is given."""
    report: dict[str, dict[str, Any]] = {}
    point_totals = {"Filter": 0.0, "Score": 0.0}
    # jitted probes are cached on the framework so repeated profiling
    # passes (--profile-every) reuse compiled programs instead of paying
    # full XLA recompilation on every pass; jax.jit itself handles shape
    # changes within one cached callable
    cache: dict[Any, Any] = framework.__dict__.setdefault("_probe_cache", {})
    valid = (
        np.asarray(snap.pod_valid)[:, None] & np.asarray(snap.node_valid)[None, :]
    )
    n_valid = max(valid.sum(), 1)

    for plugin in framework.filters:
        if ("static", plugin.name, "Filter") not in cache:
            cache[("static", plugin.name, "Filter")] = jax.jit(  # schedlint: disable=JP006 -- _probe_cache guard above: built once per plugin per process, then reused
                lambda s, p=plugin: p.static_mask(CycleContext(s))
            )
        fn = cache[("static", plugin.name, "Filter")]
        if fn(snap) is None:  # dynamic-only plugin (no static kernel)
            continue
        secs, mask = _time_call(fn, snap, repeats)
        feasible = float((np.asarray(mask) & valid).sum() / n_valid)
        report[f"{plugin.name}/Filter"] = {
            "extension_point": "Filter",
            "seconds": secs,
            "feasible_fraction": feasible,
        }
        point_totals["Filter"] += secs
        if metrics is not None:
            metrics.plugin_duration.labels(
                plugin=plugin.name, extension_point="Filter", status="Success"
            ).observe(secs)

    for plugin, weight in framework.scores:
        if ("static", plugin.name, "Score") not in cache:
            cache[("static", plugin.name, "Score")] = jax.jit(  # schedlint: disable=JP006 -- _probe_cache guard above: built once per plugin per process, then reused
                lambda s, p=plugin: p.static_score(CycleContext(s))
            )
        fn = cache[("static", plugin.name, "Score")]
        if fn(snap) is None:
            continue
        secs, score = _time_call(fn, snap, repeats)
        sc = np.asarray(score)[valid]
        report[f"{plugin.name}/Score"] = {
            "extension_point": "Score",
            "seconds": secs,
            "weight": weight,
            "score_mean": float(sc.mean()) if sc.size else 0.0,
            "score_max": float(sc.max()) if sc.size else 0.0,
        }
        point_totals["Score"] += secs
        if metrics is not None:
            metrics.plugin_duration.labels(
                plugin=plugin.name, extension_point="Score", status="Success"
            ).observe(secs)

    # ---- dynamic path: the actual hot loop -------------------------------
    # Filter/Score work that runs INSIDE the commit scan (resource fit
    # against running capacity, affinity/spread domain counts) is invisible
    # to the static timings above. Time each plugin's dyn path as its own
    # isolated scan over the full pending set — the per-cycle cost the
    # plugin adds to the fused program.
    for plugin in framework.filters:
        if ("dyn", plugin.name, "Filter") not in cache:
            cache[("dyn", plugin.name, "Filter")] = _dyn_probe(
                plugin, snap, as_score=False
            )
        fn = cache[("dyn", plugin.name, "Filter")]
        if fn is None:
            continue
        secs, _ = _time_call(fn, snap, repeats)
        report[f"{plugin.name}/Filter[dyn]"] = {
            "extension_point": "Filter",
            "seconds": secs,
        }
        point_totals["Filter"] += secs
        if metrics is not None:
            metrics.plugin_duration.labels(
                plugin=plugin.name, extension_point="Filter", status="Success"
            ).observe(secs)

    for plugin, weight in framework.scores:
        if ("dyn", plugin.name, "Score") not in cache:
            cache[("dyn", plugin.name, "Score")] = _dyn_probe(
                plugin, snap, as_score=True
            )
        fn = cache[("dyn", plugin.name, "Score")]
        if fn is None:
            continue
        secs, _ = _time_call(fn, snap, repeats)
        report[f"{plugin.name}/Score[dyn]"] = {
            "extension_point": "Score",
            "seconds": secs,
            "weight": weight,
        }
        point_totals["Score"] += secs
        if metrics is not None:
            metrics.plugin_duration.labels(
                plugin=plugin.name, extension_point="Score", status="Success"
            ).observe(secs)

    if metrics is not None:
        for point, total in point_totals.items():
            if total > 0.0:
                metrics.extension_point_duration.labels(
                    extension_point=point, status="Success"
                ).observe(total)
    return report


def _dyn_probe(plugin, snap: ClusterSnapshot, as_score: bool):
    """A jitted isolated commit-scan exercising ONE plugin's dynamic path
    (mask or score) plus its state update; None when the plugin has no such
    path. The scan mirrors greedy_commit's shape so timings are
    representative of the plugin's marginal cost in the fused cycle."""
    # a plugin with no dyn path returns None at trace time (a Python-level
    # decision, same with tracers or concrete arrays) — check eagerly
    ctx0 = CycleContext(snap)
    e0 = plugin.extra_init(ctx0)
    ext0 = {} if e0 is None else {plugin.name: e0}
    probe = (
        plugin.dyn_score(ctx0, 0, snap.node_requested, ext0,
                         jnp.broadcast_to(snap.node_valid, (snap.N,)))
        if as_score
        else plugin.dyn_mask(ctx0, 0, snap.node_requested, ext0)
    )
    if probe is None:
        return None

    def fn(snap):
        ctx = CycleContext(snap)
        e = plugin.extra_init(ctx)
        extra = {} if e is None else {plugin.name: e}
        order = jnp.argsort(snap.pod_order)

        def step(carry, rank):
            node_req, ext = carry
            p = order[rank]
            mask = jnp.broadcast_to(snap.node_valid, (snap.N,))
            score = jnp.zeros((snap.N,), jnp.float32)
            if as_score:
                score = plugin.dyn_score(ctx, p, node_req, ext, mask)
            else:
                mask = mask & plugin.dyn_mask(ctx, p, node_req, ext)
            best = argsel.argmax_first(
                jnp.where(mask, score, -1e9), axis=0
            )
            ok = mask[best] & snap.pod_valid[p]
            node_req = node_req.at[best].add(
                jnp.where(ok, snap.pod_requested[p], 0.0)
            )
            if plugin.name in ext:
                ext = {
                    plugin.name: plugin.extra_update(
                        ctx, ext[plugin.name], p, best, ok
                    )
                }
            return (node_req, ext), ()

        (node_req, _), _ = jax.lax.scan(
            step, (snap.node_requested, extra),
            jnp.arange(snap.P, dtype=jnp.int32),
        )
        return node_req

    return jax.jit(fn)


def overlap_stats(
    encode_s: float, device_s: float, pipelined_s: float
) -> dict[str, float]:
    """Split-phase overlap accounting for the serving pipeline
    (core/pipeline.py): given three independently measured medians —
    host encode alone, device cycle (dispatch + slimmed decision fetch)
    alone, and the pipelined per-cycle wall time (dispatch cycle k, then
    encode cycle k+1 on the host, then fetch k's decisions) — report how
    much of the smaller stage was hidden behind the larger one.

        hidden      = encode + device - pipelined   (>= 0)
        overlap_pct = hidden / min(encode, device) * 100

    100% means the cheaper stage ran entirely in the other's shadow (the
    pipelined cycle costs max(encode, device), not the sum); 0% means no
    overlap (fully serial — e.g. forced_sync)."""
    hidden = max(0.0, encode_s + device_s - pipelined_s)
    denom = min(encode_s, device_s)
    pct = 100.0 * hidden / denom if denom > 0 else 0.0
    return {
        "encode_ms": round(encode_s * 1e3, 3),
        "device_ms": round(device_s * 1e3, 3),
        "pipelined_ms": round(pipelined_s * 1e3, 3),
        "encode_hidden_ms": round(min(hidden, encode_s) * 1e3, 3),
        "overlap_pct": round(min(pct, 100.0), 1),
    }


def overlap_from_records(
    phase_dicts: "Iterable[dict[str, float]]",
) -> dict[str, float]:
    """Continuous overlap accounting from flight-recorder records —
    the production counterpart of `overlap_stats`, which needs three
    separated probe runs. Each input dict is a CycleRecord's `phases`
    (the ServingPipeline stage report: encode_ms, decision_wait_ms,
    encode_hidden_ms, diag_lag_ms, ...).

    `overlap_ratio` = hidden encode / total encode over the window,
    using the pipeline's conservative per-cycle estimate
    (hidden = max(0, encode - decision_wait)); 0.0 = fully serial
    (forced_sync), 1.0 = every encode ran in the device's shadow.
    Pure python — safe to call from endpoints at serving rate."""
    n = 0
    enc = hidden = wait = diag = diag_n = 0.0
    for ph in phase_dicts:
        n += 1
        e = ph.get("encode_ms", 0.0)
        w = ph.get("decision_wait_ms", 0.0)
        enc += e
        wait += w
        hidden += ph.get("encode_hidden_ms", max(0.0, e - w))
        if "diag_lag_ms" in ph:
            diag += ph["diag_lag_ms"]
            diag_n += 1
    return {
        "window": float(n),
        "encode_ms_mean": round(enc / n, 4) if n else 0.0,
        "decision_wait_ms_mean": round(wait / n, 4) if n else 0.0,
        "encode_hidden_ms_mean": round(hidden / n, 4) if n else 0.0,
        "diag_lag_ms_mean": round(diag / diag_n, 4) if diag_n else 0.0,
        "overlap_ratio": round(min(hidden / enc, 1.0), 4) if enc > 0
        else 0.0,
    }

