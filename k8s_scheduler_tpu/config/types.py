"""Config API: the KubeSchedulerConfiguration analogue.

Mirrors the reference's versioned config (`apis/config/` — [UNVERIFIED],
mount empty; SURVEY.md §2 C12): profiles keyed by schedulerName, per-
extension-point plugin enable/disable lists, per-plugin args, and the
`percentageOfNodesToScore` knob, loadable from the same YAML field names.
No multi-version conversion machinery (SURVEY.md §5.6)."""

from __future__ import annotations

import dataclasses
from dataclasses import dataclass, field
from typing import Any


@dataclass
class PluginEntry:
    name: str
    weight: int = 1


@dataclass
class PluginSet:
    enabled: list[PluginEntry] = field(default_factory=list)
    disabled: list[str] = field(default_factory=list)  # ["*"] = all defaults

    def resolve(self, defaults: list[PluginEntry]) -> list[PluginEntry]:
        """Upstream merge semantics: defaults minus disabled, plus enabled
        (enabled entries replace same-named defaults to carry new weights)."""
        if "*" in self.disabled:
            base: list[PluginEntry] = []
        else:
            base = [d for d in defaults if d.name not in self.disabled]
        out = {e.name: e for e in base}
        for e in self.enabled:
            out[e.name] = e
        return list(out.values())


@dataclass
class Plugins:
    queue_sort: PluginSet = field(default_factory=PluginSet)
    pre_filter: PluginSet = field(default_factory=PluginSet)
    filter: PluginSet = field(default_factory=PluginSet)
    post_filter: PluginSet = field(default_factory=PluginSet)
    pre_score: PluginSet = field(default_factory=PluginSet)
    score: PluginSet = field(default_factory=PluginSet)
    reserve: PluginSet = field(default_factory=PluginSet)
    permit: PluginSet = field(default_factory=PluginSet)
    bind: PluginSet = field(default_factory=PluginSet)


@dataclass
class Profile:
    scheduler_name: str = "default-scheduler"
    plugins: Plugins = field(default_factory=Plugins)
    plugin_config: dict[str, dict[str, Any]] = field(default_factory=dict)


@dataclass
class Extender:
    """HTTP scheduler-extender config (upstream `Extender` in
    KubeSchedulerConfiguration): filter/prioritize/bind delegation to an
    external webhook."""

    url_prefix: str
    filter_verb: str = ""
    prioritize_verb: str = ""
    bind_verb: str = ""
    weight: int = 1
    http_timeout_seconds: float = 5.0
    # errors from an ignorable extender don't fail the pod's attempt
    ignorable: bool = False
    # operator assertion that this extender's Filter/Prioritize verdicts
    # depend only on (pod, node set) — i.e. are DETERMINISTIC per pod.
    # When every configured extender sets this, the scheduler keeps the
    # device-carry latency path: verdict rows live on device and only
    # CHANGED pods re-consult the webhook each cycle (PERF.md "Extenders
    # and the carry path"). Off by default: upstream extenders may be
    # stateful, and those must be re-consulted for every pod each cycle
    # (the full-path behavior).
    carry_verdicts: bool = False


@dataclass
class SchedulerConfiguration:
    profiles: list[Profile] = field(default_factory=lambda: [Profile()])
    # upstream's default, 0, is adaptive (50 - nodes/125 percent, floored
    # at 5; every node under 100 nodes). Under 100, a pod is scored on the
    # first k FEASIBLE nodes of a walk from its start offset, never
    # refused while a node admits it; the start is a fixed rotation per
    # pod and cycle, not upstream's one advancing index (ops/sampling.py)
    percentage_of_nodes_to_score: int = 0
    pod_initial_backoff_seconds: float = 1.0
    pod_max_backoff_seconds: float = 10.0
    # gang scheduling (Coscheduling PodGroup CRD analogue, SURVEY.md C14)
    gang_scheduling: bool = True
    # in-cycle commitment engine (TPU-native extension, see ops/rounds.py):
    # "rounds" = batched round commit (production default at scale),
    # "scan" = strict sequential per-pod scan (exact ScheduleOne order)
    commit_mode: str = "rounds"
    extenders: list[Extender] = field(default_factory=list)
    # sticky-regime pre-sizing (TPU-native extension): a fold-heavy
    # deployment (bindings folded into the existing set every cycle)
    # should pre-size the existing-pod pad to its steady-state count and
    # the per-node victim-table depth to its hot-node depth, so the
    # packed regime never flips mid-serving — a flip costs a full
    # recompile and has tripped a rig-side executable wedge (PERF.md
    # "fold-mode rig wedge"). 0 = size from the first snapshot.
    pad_existing: int = 0
    pad_pods_per_node: int = 0
    # pre-size the sticky per-pod term pads the same way (ADVICE r5): MA
    # = (anti-)affinity/preferred terms per pod, MC = topology-spread
    # constraints per pod. Both bucket by 2, so a mid-serving arrival of
    # a 3-4-term pod otherwise flips the regime. 0 = size from the first
    # snapshot.
    pad_ma: int = 0
    pad_mc: int = 0
    # serving-pipeline escape hatch: block every cycle dispatch to
    # completion before continuing (strict sequential execution —
    # identical results, no overlap). For tests and latency measurement;
    # production serving leaves this False and overlaps preemption/
    # diagnosis/transfer with host bind work (core/pipeline.py).
    forced_sync: bool = False
    # cycle flight recorder (core/flight_recorder.py): ring capacity for
    # per-cycle phase records — feeds /debug/flightrecorder, the
    # /debug/trace Perfetto export, the per-pod timelines, and the
    # derived pipeline gauges. 0 disables recording entirely.
    flight_recorder_size: int = 512
    # /healthz staleness deadline: report 503 when no scheduling cycle
    # completed within this many seconds (0 = never go stale). Uses the
    # flight recorder's last-cycle age, so a wedged scheduler stops
    # reporting healthy (cmd/main.py).
    health_max_cycle_age_seconds: float = 0.0
    # latency SLO (core/observe.py): objective "p99 of cycle wall time
    # <= sloP99Ms over sloWindowCycles cycles" (i.e. at most 1% of the
    # window's cycles may exceed the bound). Drives the
    # scheduler_slo_burn_rate{window} / scheduler_slo_budget_remaining
    # gauges and the /healthz degraded flag on a fast-window burn.
    # 0 disables the objective (attribution + anomalies still run).
    slo_p99_ms: float = 0.0
    slo_window_cycles: int = 1024
    # compile-regime management (core/compile_cache.py):
    # padHysteresisPct — down-step margin for the P/N pad buckets: a
    # shrinking pending/node count only steps the pad regime DOWN when
    # it leaves at least this many percent of headroom inside the
    # smaller bucket, so a workload oscillating around a bucket
    # boundary holds the larger (already-compiled) regime instead of
    # flip-flopping. 0 disables (immediate down-step).
    pad_hysteresis_pct: float = 0.0
    # compileCacheDir — directory for the persistent compiled-program
    # cache (AOT executables keyed by pad regime + profile + program
    # kind + jaxlib/backend fingerprint). "" derives
    # <stateDir>/compile_cache when stateDir is set, else disables;
    # "off"/"none" disables even with a state dir (slow shared
    # storage, poisoned-cache triage). A warm restart then compiles
    # zero programs for previously-seen regimes (entry load ~<1 s vs
    # the 8.8-16.8 s cold compile).
    compile_cache_dir: str = ""
    # shardDevices — shard the serving path's device-resident carry
    # (the [P, N] static base and [S, P] matched-pending tables) over a
    # 1-D ('pods',) jax.sharding.Mesh of this many local devices; the
    # claim path's shard-invariant tie-breaking (ops/argsel.py) keeps
    # placements bit-identical to the single-device run at any count.
    # 0/1 disables (everything stays on one device). Must divide the
    # pod pad bucket (64) and not exceed jax.devices().
    shard_devices: int = 0
    # speculativeCompile — background pre-compilation of the ADJACENT
    # pad regime on a warm thread (never the bind path) when the
    # anomaly sentinel's demand EWMA drifts toward a bucket boundary;
    # a flip speculation won costs ~0 compile on the serve path.
    speculative_compile: bool = True
    # dispatch watchdog (core/pipeline.py): bound, in milliseconds, on
    # the ONE blocking device->host decision fetch. On expiry the fetch
    # is abandoned (DispatchDeadlineExceeded), the cycle's pods requeue
    # with backoff, and the degradation ladder (core/degrade.py) steps
    # down one rung — a hung tunnel can no longer wedge the serve loop
    # forever. 0 disables the bound (the pre-watchdog behavior).
    dispatch_deadline_ms: float = 0.0
    # degradation ladder promotion: after this many consecutive clean
    # scheduling cycles (dispatches that completed without a failure)
    # the ladder steps one rung back up toward `normal`.
    degrade_promote_cycles: int = 8
    # fault injection (core/faults.py): a FaultPlan spec like
    # "fetch_hang@cycle=40:ms=5000" — scripted, seeded faults fired at
    # named points on the real code paths (soaks/benches/tests only;
    # env SCHED_FAULTS overrides when this is empty). "" disarms.
    fault_spec: str = ""
    # submission front door (service/admission.py): bound on the
    # admission queue — pending pods (all queue tiers). A Submit that
    # would push the depth past this bound is SHED whole
    # (RESOURCE_EXHAUSTED + retry-after): overload degrades to shedding,
    # not to unbounded memory. Shedding also engages while the SLO
    # fast-burn gauge fires or the degradation ladder sits below rung
    # 0. 0 disables the front door's depth bound (tests only).
    admission_queue_depth: int = 65536
    # retry-after hint (milliseconds) attached to shed submissions —
    # gRPC trailing metadata "retry-after-ms" and the HTTP
    # Retry-After header on the debug server's POST /submit path.
    admission_retry_after_ms: float = 250.0
    # pod-lifecycle tracing (core/spans): head-sampling probability
    # for submissions that arrive WITHOUT an explicit traceparent —
    # deterministic per pod uid, so a shed retry keeps its sampling
    # fate. An explicit traceparent always samples. 0 disables
    # arming entirely (stamp sites pay one flag load); 1 traces every
    # pod (bench overhead stages and acceptance runs).
    trace_sample_rate: float = 1.0 / 64.0
    # durable scheduler state (state/ package): directory for the
    # write-ahead journal + snapshots. "" disables durability — a
    # takeover then rebuilds only what informer events re-deliver,
    # losing backoff deadlines, attempt counts, and assumed pods.
    state_dir: str = ""
    # snapshot cadence: how often the journal is compacted into a full
    # snapshot (seconds; 0 = journal only, never compact)
    snapshot_interval_seconds: float = 60.0
    # watchtower (metrics/tsdb.py + metrics/rules.py): per-series raw
    # ring capacity of the in-process metrics history store. The CLI
    # arms the TSDB + the built-in alert rule pack when > 0; 0 disables
    # the whole watchtower (history, rules, dashboard) — the unarmed
    # cost at the cycle hook is one module-flag check.
    metrics_history_samples: int = 512
    # wall-ticker cadence (seconds) for scrape-time gauges: the TSDB
    # samples the full Prometheus registry — set_function gauges
    # evaluate exactly as on a /metrics GET — every this-many seconds.
    # 0 disables the ticker (cycle-driven samples only).
    metrics_ticker_seconds: float = 2.0
    # extra alert rules (YAML/JSON list of rule objects, the
    # metrics/rules.py shape) appended to the built-in pack. "" = the
    # built-in pack only.
    alert_rules_file: str = ""
    # crash black box (core/blackbox.py): how many post-mortem bundles
    # to keep under <stateDir>/blackbox/ (oldest deleted first; also
    # capped at 64 MB total). 0 disables black-box capture. Needs
    # stateDir — the bundle directory lives next to the journal.
    blackbox_retention: int = 8
    # /debug/dashboard HTML sparkline page (needs the watchtower
    # armed); False turns just the page off, the history/alerts JSON
    # endpoints stay.
    debug_dashboard: bool = True

    def profile(self, scheduler_name: str = "default-scheduler") -> Profile:
        for p in self.profiles:
            if p.scheduler_name == scheduler_name:
                return p
        return self.profiles[0]


# Upstream default plugin sets (getDefaultPlugins — [UNVERIFIED] weights
# follow the widely-documented defaults: PodTopologySpread 2,
# TaintToleration 3, others 1).
_DEFAULT_FILTERS = [
    "NodeUnschedulable",
    "NodeName",
    "TaintToleration",
    "NodeAffinity",
    "NodePorts",
    "NodeResourcesFit",
    "VolumeBinding",
    "InterPodAffinity",
    "PodTopologySpread",
]
_DEFAULT_SCORES = [
    ("NodeResourcesBalancedAllocation", 1),
    ("ImageLocality", 1),
    ("InterPodAffinity", 1),
    ("NodeResourcesFit", 1),
    ("NodeAffinity", 1),
    ("PodTopologySpread", 2),
    ("TaintToleration", 3),
]
_DEFAULT_POST_FILTERS = ["DefaultPreemption"]


def default_plugins() -> dict[str, list[PluginEntry]]:
    return {
        "filter": [PluginEntry(n) for n in _DEFAULT_FILTERS],
        "score": [PluginEntry(n, w) for n, w in _DEFAULT_SCORES],
        "post_filter": [PluginEntry(n) for n in _DEFAULT_POST_FILTERS],
    }


def _duration_seconds(v) -> float:
    """Upstream serializes durations as strings ('5s', '500ms', '1m30s');
    accept those and plain numbers."""
    if isinstance(v, (int, float)):
        return float(v)
    import re

    total = 0.0
    for num, unit in re.findall(r"([0-9.]+)(h|m(?!s)|s|ms|us|ns)", str(v)):
        total += float(num) * {
            "h": 3600.0, "m": 60.0, "s": 1.0,
            "ms": 1e-3, "us": 1e-6, "ns": 1e-9,
        }[unit]
    return total or 5.0


def _plugin_set_from_dict(d: dict) -> PluginSet:
    return PluginSet(
        enabled=[
            PluginEntry(e["name"], e.get("weight", 1)) for e in d.get("enabled", [])
        ],
        disabled=[e["name"] if isinstance(e, dict) else e
                  for e in d.get("disabled", [])],
    )


def load_config(source: "str | dict") -> SchedulerConfiguration:
    """Load from a YAML string/path or a dict with upstream field names."""
    if isinstance(source, str):
        import yaml

        if "\n" not in source and source.endswith((".yaml", ".yml", ".json")):
            with open(source) as f:
                data = yaml.safe_load(f)
        else:
            data = yaml.safe_load(source)
    else:
        data = source
    data = data or {}

    profiles = []
    for pd in data.get("profiles", [{}]):
        plugins = Plugins()
        for point, attr in [
            ("queueSort", "queue_sort"),
            ("preFilter", "pre_filter"),
            ("filter", "filter"),
            ("postFilter", "post_filter"),
            ("preScore", "pre_score"),
            ("score", "score"),
            ("reserve", "reserve"),
            ("permit", "permit"),
            ("bind", "bind"),
        ]:
            if point in pd.get("plugins", {}):
                setattr(plugins, attr, _plugin_set_from_dict(pd["plugins"][point]))
        plugin_config = {
            e["name"]: e.get("args", {}) for e in pd.get("pluginConfig", [])
        }
        profiles.append(
            Profile(
                scheduler_name=pd.get("schedulerName", "default-scheduler"),
                plugins=plugins,
                plugin_config=plugin_config,
            )
        )
    return SchedulerConfiguration(
        profiles=profiles or [Profile()],
        percentage_of_nodes_to_score=data.get("percentageOfNodesToScore", 0),
        pod_initial_backoff_seconds=data.get("podInitialBackoffSeconds", 1.0),
        pod_max_backoff_seconds=data.get("podMaxBackoffSeconds", 10.0),
        gang_scheduling=data.get("gangScheduling", True),
        commit_mode=data.get("commitMode", "rounds"),
        pad_existing=int(data.get("padExisting", 0)),
        pad_pods_per_node=int(data.get("padPodsPerNode", 0)),
        pad_ma=int(data.get("padMa", 0)),
        pad_mc=int(data.get("padMc", 0)),
        forced_sync=bool(data.get("forcedSync", False)),
        flight_recorder_size=int(data.get("flightRecorderSize", 512)),
        health_max_cycle_age_seconds=_duration_seconds(
            data.get("healthMaxCycleAge", 0.0)
        ),
        slo_p99_ms=float(data.get("sloP99Ms", 0.0)),
        slo_window_cycles=int(data.get("sloWindowCycles", 1024)),
        pad_hysteresis_pct=float(data.get("padHysteresisPct", 0.0)),
        compile_cache_dir=str(data.get("compileCacheDir", "")),
        shard_devices=int(data.get("shardDevices", 0)),
        speculative_compile=bool(data.get("speculativeCompile", True)),
        dispatch_deadline_ms=float(data.get("dispatchDeadlineMs", 0.0)),
        degrade_promote_cycles=int(data.get("degradePromoteCycles", 8)),
        fault_spec=str(data.get("faultSpec", "")),
        admission_queue_depth=int(data.get("admissionQueueDepth", 65536)),
        admission_retry_after_ms=float(
            data.get("admissionRetryAfterMs", 250.0)
        ),
        trace_sample_rate=float(
            data.get("traceSampleRate", 1.0 / 64.0)
        ),
        state_dir=str(data.get("stateDir", "")),
        snapshot_interval_seconds=_duration_seconds(
            data.get("snapshotInterval", 60.0)
        ),
        metrics_history_samples=int(
            data.get("metricsHistorySamples", 512)
        ),
        metrics_ticker_seconds=float(
            data.get("metricsTickerSeconds", 2.0)
        ),
        alert_rules_file=str(data.get("alertRulesFile", "")),
        blackbox_retention=int(data.get("blackboxRetention", 8)),
        debug_dashboard=bool(data.get("debugDashboard", True)),
        extenders=[
            Extender(
                url_prefix=e["urlPrefix"],
                filter_verb=e.get("filterVerb", ""),
                prioritize_verb=e.get("prioritizeVerb", ""),
                bind_verb=e.get("bindVerb", ""),
                weight=e.get("weight", 1),
                http_timeout_seconds=_duration_seconds(
                    e.get("httpTimeout", 5.0)
                ),
                ignorable=e.get("ignorable", False),
                carry_verdicts=e.get("carryVerdicts", False),
            )
            for e in data.get("extenders", [])
        ],
    )


def to_dict(cfg: SchedulerConfiguration) -> dict:
    return dataclasses.asdict(cfg)
