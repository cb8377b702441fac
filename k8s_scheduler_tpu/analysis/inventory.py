"""INVENTORY-DRIFT (ID0xx): code <-> documentation surface cross-checks.

The generalization of scripts/lint_metrics.py (now a shim over this
pass): dashboards, runbooks, and the README are built from inventories
that silently rot when code moves. Three inventories are checked, each
in BOTH directions:

- ID001  metric families registered on SchedulerMetrics vs the
         metrics/metrics.py docstring and the README "## Observability"
         table, plus the REQUIRED_FAMILIES floor (the durable-state /
         leader families operations depends on)
- ID002  SchedulerConfiguration fields vs the camelCase YAML keys
         load_config() reads (a field without a key is dead config; a
         key without a field is a silent no-op in every user's YAML)
- ID003  cmd/main.py: `config.X` attribute writes must name real
         SchedulerConfiguration fields, `args.Y` reads must name real
         argparse flags (a typo'd override silently keeps the default)
- ID004  every YAML config key and every CLI flag is mentioned
         somewhere in README.md (the operator-facing surface)
- ID005  the cycle-phase inventory: every phase name in
         core/observe.PHASES must appear in the flight recorder's
         chrome-trace lane mapping (TRACE_LANE_FOR_PHASE, and vice
         versa), in the metrics/metrics.py docstring entry for
         scheduler_cycle_phase_seconds, and in the README
         "## Observability" section — the recorder, the metrics, and
         the trace export cannot disagree about what a phase is
- ID006  the compile-cache key inventory: the dimension names of
         models/packing.SIGNATURE_DIMS must equal
         core/compile_cache.SIG_KEY_FIELDS (a new pad dimension added
         without a cache-key field silently ALIASES distinct programs
         into one persistent-cache entry; a stale key field caches
         against a dimension that no longer exists), and every field of
         SIG_KEY_FIELDS + EXTRA_KEY_FIELDS must appear in the README
         "## Compile-regime management" key table
- ID007  the degradation-rung inventory: every rung name in
         core/degrade.RUNGS must appear in the README "## Failure
         model & degradation ladder" rung table (operators act on the
         rung names /healthz and the transition events carry; a rung
         added or renamed without its README row leaves the runbook
         pointing at modes that no longer exist)
- ID008  the sharded-collective budget inventory: every budget class
         in parallel/audit.COLLECTIVE_BUDGETS (the committed allowlist
         scripts/audit_sharded.py gates on) and every mesh-axis name
         in parallel/mesh.MESH_AXES must appear in the README
         "## Multi-chip and multi-host" budget table — a class or axis
         renamed without its doc row silently un-classifies the very
         collectives the payload diet bounds
- ID009  the finding-code inventory: every code registered by every
         pass (registry.all_codes) must appear in the README
         "## Static analysis" pass/code table, and every code-shaped
         token in that table must name a registered code — the table
         is where operators look up what a CI failure means, so a pass
         added without its row (or a row for a deleted code) rots the
         one documentation surface the lint itself points at. Range
         notation (`TS001`-`TS004`) covers the codes between its
         endpoints. Checked against the DEFAULT registry (out-of-tree
         registries document themselves); gated like HY003 — fixture
         trees without the section are only judged when they carry the
         real registry module
- ID010  the span-name inventory: every span name in
         core/spans.SPAN_NAMES (the pod-lifecycle tracing inventory)
         must appear in the metrics/metrics.py docstring entry for
         scheduler_trace_spans_total and in the README
         "## Distributed tracing" span table — the explain endpoint,
         the Perfetto export, and the runbook all key on these names,
         so a span added or renamed without its doc row leaves
         operators reading traces the docs cannot decode

The metric-registry half (ID001) imports the live package; pass
`{"metrics_runtime": False}` to skip it when linting fixture trees.
ID005 and ID010 are pure AST + file reads, so they run on fixture
trees too.
"""

from __future__ import annotations

import ast
import os
import re

from .core import Finding, LintContext
from .registry import PassBase

_NAME_RE = re.compile(r"\bscheduler_[a-z0-9_]+\b")

# Families that MUST exist: the durable-state (journal/snapshot) and
# leader-election surfaces are operational contracts — dashboards and
# the failover runbook depend on them, so their silent removal from the
# registry is a lint failure even though the two-way doc check would
# only notice if the docs were cleaned up in the same commit.
REQUIRED_FAMILIES = {
    "scheduler_journal_appends_total",
    "scheduler_journal_bytes_total",
    "scheduler_journal_fsync_seconds",
    "scheduler_journal_buffer_depth",
    "scheduler_journal_segments",
    "scheduler_snapshot_writes_total",
    "scheduler_snapshot_duration_seconds",
    "scheduler_snapshot_rows_total",
    "scheduler_snapshot_last_bytes",
    "scheduler_snapshot_last_restore_records",
    "scheduler_snapshot_last_restore_seconds",
    "scheduler_leader_state",
    "scheduler_leader_lease_age_seconds",
    # watchtower + build-identity floor: the alert counter is what the
    # rule engine fires into, build_info/uptime are what dashboards
    # correlate restarts against — all three are operational contracts
    "scheduler_build_info",
    "scheduler_uptime_seconds",
    "scheduler_alerts_total",
}

# dataclass fields that are structured sub-configs, not flat YAML keys
_STRUCTURED_FIELDS = {"profiles", "extenders"}
# top-level YAML keys that feed the structured fields above
_STRUCTURED_KEYS = {"profiles", "extenders"}


def camel(field: str) -> str:
    parts = field.split("_")
    return parts[0] + "".join(p.capitalize() for p in parts[1:])


def _key_matches(field: str, keys: set[str]) -> bool:
    if camel(field) in keys:
        return True
    if field.endswith("_seconds") and camel(field[: -len("_seconds")]) in keys:
        return True
    return False


def _field_matches(key: str, fields: set[str]) -> bool:
    snake = re.sub(r"([A-Z])", lambda m: "_" + m.group(1).lower(), key)
    return snake in fields or f"{snake}_seconds" in fields


class InventoryDriftPass(PassBase):
    name = "INVENTORY-DRIFT"
    codes = {
        "ID001": "metric registry drifted from docstring/README/"
                 "required-families inventory",
        "ID002": "SchedulerConfiguration fields drifted from "
                 "load_config YAML keys",
        "ID003": "cmd/main.py references an unknown config field or "
                 "CLI flag",
        "ID004": "config key / CLI flag undocumented in README",
        "ID005": "cycle-phase inventory drifted between observe.PHASES, "
                 "the trace lane mapping, the metrics docstring, and "
                 "the README",
        "ID006": "compile-cache key inventory drifted between "
                 "packing.SIGNATURE_DIMS, compile_cache.SIG_KEY_FIELDS, "
                 "and the README key table",
        "ID007": "degradation-rung inventory drifted between "
                 "degrade.RUNGS and the README rung table",
        "ID008": "sharded-collective budget inventory drifted between "
                 "audit.COLLECTIVE_BUDGETS, mesh.MESH_AXES, and the "
                 "README budget table",
        "ID009": "finding-code inventory drifted between the pass "
                 "registry and the README Static-analysis table",
        "ID010": "span-name inventory drifted between spans.SPAN_NAMES, "
                 "the metrics docstring, and the README tracing table",
        "ID011": "alert rule-pack inventory drifted between "
                 "rules.BUILTIN_RULES, the README alert table, and the "
                 "anomaly-class docs",
    }

    def run(self, ctx: LintContext) -> list[Finding]:
        findings: list[Finding] = []
        types_sf = self._find(ctx, "config/types.py")
        main_sf = self._find(ctx, "cmd/main.py")
        fields = self._config_fields(types_sf) if types_sf else {}
        keys = self._yaml_keys(types_sf) if types_sf else {}
        if types_sf:
            findings += self._check_config(types_sf, fields, keys)
        if main_sf:
            flags = self._cli_flags(main_sf)
            findings += self._check_main(main_sf, fields, flags)
            findings += self._check_readme(
                ctx, types_sf, main_sf, keys, flags
            )
        if self.args.get("metrics_runtime", True) and self._find(
            ctx, "metrics/metrics.py"
        ):
            findings += self._check_metrics(ctx)
        findings += self._check_phases(ctx)
        findings += self._check_spans(ctx)
        findings += self._check_compile_key(ctx)
        findings += self._check_rungs(ctx)
        findings += self._check_collective_budgets(ctx)
        findings += self._check_code_table(ctx)
        findings += self._check_alert_rules(ctx)
        return findings

    @staticmethod
    def _find(ctx: LintContext, suffix: str):
        for sf in ctx.files:
            if sf.rel.endswith(suffix):
                return sf
        return None

    # ---- ID002: config fields <-> YAML keys ------------------------------

    @staticmethod
    def _config_fields(sf) -> dict[str, int]:
        """SchedulerConfiguration field -> lineno."""
        for node in sf.tree.body:
            if isinstance(node, ast.ClassDef) and node.name == (
                "SchedulerConfiguration"
            ):
                return {
                    st.target.id: st.lineno
                    for st in node.body
                    if isinstance(st, ast.AnnAssign)
                    and isinstance(st.target, ast.Name)
                }
        return {}

    @staticmethod
    def _yaml_keys(sf) -> dict[str, int]:
        """Top-level `data.get("...")` keys in load_config -> lineno."""
        out: dict[str, int] = {}
        for node in sf.walk():
            if not (
                isinstance(node, ast.FunctionDef)
                and node.name == "load_config"
            ):
                continue
            for call in ast.walk(node):
                if not isinstance(call, ast.Call):
                    continue
                fn = call.func
                if (
                    isinstance(fn, ast.Attribute) and fn.attr == "get"
                    and isinstance(fn.value, ast.Name)
                    and fn.value.id == "data"
                    and call.args
                    and isinstance(call.args[0], ast.Constant)
                    and isinstance(call.args[0].value, str)
                ):
                    out.setdefault(call.args[0].value, call.lineno)
        return out

    def _check_config(self, sf, fields, keys) -> list[Finding]:
        findings = []
        for field, line in sorted(fields.items()):
            if field in _STRUCTURED_FIELDS:
                continue
            if not _key_matches(field, set(keys)):
                findings.append(Finding(
                    sf.rel, line, "ID002",
                    f"SchedulerConfiguration.{field} has no matching "
                    f"YAML key in load_config (expected "
                    f"{camel(field)!r}): the field is dead in every "
                    "config file",
                ))
        for key, line in sorted(keys.items()):
            if key in _STRUCTURED_KEYS:
                continue
            if not _field_matches(key, set(fields)):
                findings.append(Finding(
                    sf.rel, line, "ID002",
                    f"load_config reads YAML key {key!r} with no "
                    "matching SchedulerConfiguration field: the key "
                    "parses into nothing",
                ))
        return findings

    # ---- ID003: cmd/main.py coherence ------------------------------------

    @staticmethod
    def _cli_flags(sf) -> dict[str, int]:
        """'--flag-name' -> lineno for every add_argument call."""
        out: dict[str, int] = {}
        for node in sf.walk():
            if (
                isinstance(node, ast.Call)
                and isinstance(node.func, ast.Attribute)
                and node.func.attr == "add_argument"
                and node.args
                and isinstance(node.args[0], ast.Constant)
                and isinstance(node.args[0].value, str)
                and node.args[0].value.startswith("--")
            ):
                out[node.args[0].value] = node.lineno
        return out

    def _check_main(self, sf, fields, flags) -> list[Finding]:
        findings = []
        dests = {
            flag[2:].replace("-", "_") for flag in flags
        }
        for node in sf.walk():
            if not isinstance(node, ast.Attribute):
                continue
            if (
                isinstance(node.value, ast.Name)
                and node.value.id == "config"
                and fields and node.attr not in fields
            ):
                findings.append(Finding(
                    sf.rel, node.lineno, "ID003",
                    f"cmd/main.py references config.{node.attr}, which "
                    "is not a SchedulerConfiguration field: the "
                    "override writes into nothing",
                ))
            elif (
                isinstance(node.value, ast.Name)
                and node.value.id == "args"
                and dests and node.attr not in dests
            ):
                findings.append(Finding(
                    sf.rel, node.lineno, "ID003",
                    f"cmd/main.py reads args.{node.attr}, which no "
                    "add_argument flag defines",
                ))
        return findings

    # ---- ID004: README coverage ------------------------------------------

    def _check_readme(
        self, ctx, types_sf, main_sf, keys, flags
    ) -> list[Finding]:
        path = os.path.join(ctx.root, "README.md")
        if not os.path.exists(path):
            return []
        with open(path, encoding="utf-8") as f:
            text = f.read()
        findings = []
        for key, line in sorted(keys.items()):
            if key in _STRUCTURED_KEYS:
                continue
            if key not in text:
                findings.append(Finding(
                    types_sf.rel, line, "ID004",
                    f"YAML config key {key!r} is not documented "
                    "anywhere in README.md",
                ))
        for flag, line in sorted(flags.items()):
            if flag not in text:
                findings.append(Finding(
                    main_sf.rel, line, "ID004",
                    f"CLI flag {flag!r} is not documented anywhere in "
                    "README.md",
                ))
        return findings

    # ---- ID005: cycle-phase inventory ------------------------------------

    @staticmethod
    def _module_const(sf, name: str):
        """AST value of a module-level `NAME = <literal>` assignment:
        tuples of strings -> set of strings, dict literals -> set of
        string keys; None when absent or non-literal."""
        for node in sf.tree.body:
            if not (
                isinstance(node, ast.Assign)
                and len(node.targets) == 1
                and isinstance(node.targets[0], ast.Name)
                and node.targets[0].id == name
            ):
                continue
            v = node.value
            if isinstance(v, (ast.Tuple, ast.List)):
                return {
                    e.value for e in v.elts
                    if isinstance(e, ast.Constant)
                    and isinstance(e.value, str)
                }, node.lineno
            if isinstance(v, ast.Dict):
                return {
                    k.value for k in v.keys
                    if isinstance(k, ast.Constant)
                    and isinstance(k.value, str)
                }, node.lineno
        return None, 0

    def _check_phases(self, ctx: LintContext) -> list[Finding]:
        obs_sf = self._find(ctx, "core/observe.py")
        if obs_sf is None:
            return []
        phases, obs_line = self._module_const(obs_sf, "PHASES")
        if not phases:
            return [Finding(
                obs_sf.rel, 1, "ID005",
                "core/observe.py defines no literal PHASES tuple — the "
                "phase inventory every surface is checked against",
            )]
        findings: list[Finding] = []

        fr_sf = self._find(ctx, "core/flight_recorder.py")
        if fr_sf is not None:
            lanes, fr_line = self._module_const(
                fr_sf, "TRACE_LANE_FOR_PHASE"
            )
            if lanes is None:
                findings.append(Finding(
                    fr_sf.rel, 1, "ID005",
                    "core/flight_recorder.py has no literal "
                    "TRACE_LANE_FOR_PHASE mapping: the trace export "
                    "cannot be checked against observe.PHASES",
                ))
            else:
                for p in sorted(phases - lanes):
                    findings.append(Finding(
                        fr_sf.rel, fr_line, "ID005",
                        f"phase {p!r} (observe.PHASES) is missing from "
                        "TRACE_LANE_FOR_PHASE: the trace export does "
                        "not know where to render it",
                    ))
                for p in sorted(lanes - phases):
                    findings.append(Finding(
                        fr_sf.rel, fr_line, "ID005",
                        f"TRACE_LANE_FOR_PHASE maps {p!r}, which is not "
                        "an observe.PHASES phase: stale lane mapping",
                    ))

        met_sf = self._find(ctx, "metrics/metrics.py")
        if met_sf is not None:
            doc = ast.get_docstring(met_sf.tree) or ""
            # scope to the scheduler_cycle_phase_seconds bullet so an
            # incidental word elsewhere cannot satisfy the check
            i = doc.find("scheduler_cycle_phase_seconds")
            region = doc[i:] if i >= 0 else ""
            j = region.find("\n- scheduler_")
            if j > 0:
                region = region[:j]
            for p in sorted(phases):
                if not re.search(rf"\b{re.escape(p)}\b", region):
                    findings.append(Finding(
                        met_sf.rel, 1, "ID005",
                        f"phase {p!r} (observe.PHASES) is not named in "
                        "the metrics docstring entry for "
                        "scheduler_cycle_phase_seconds",
                    ))

        path = os.path.join(ctx.root, "README.md")
        if os.path.exists(path):
            with open(path, encoding="utf-8") as f:
                text = f.read()
            m = re.search(
                r"^## Observability\b(.*?)(?=^## |\Z)", text, re.M | re.S
            )
            section = m.group(1) if m else ""
            for p in sorted(phases):
                if not re.search(rf"\b{re.escape(p)}\b", section):
                    findings.append(Finding(
                        obs_sf.rel, obs_line, "ID005",
                        f"phase {p!r} (observe.PHASES) is not documented "
                        'in the README "## Observability" section',
                    ))
        return findings

    # ---- ID010: span-name inventory --------------------------------------

    def _check_spans(self, ctx: LintContext) -> list[Finding]:
        sp_sf = self._find(ctx, "core/spans.py")
        if sp_sf is None:
            return []
        names, sp_line = self._module_const(sp_sf, "SPAN_NAMES")
        if not names:
            return [Finding(
                sp_sf.rel, 1, "ID010",
                "core/spans.py defines no literal SPAN_NAMES tuple — "
                "the span inventory every surface is checked against",
            )]
        findings: list[Finding] = []

        met_sf = self._find(ctx, "metrics/metrics.py")
        if met_sf is not None:
            doc = ast.get_docstring(met_sf.tree) or ""
            # scope to the scheduler_trace_spans_total bullet so an
            # incidental word elsewhere cannot satisfy the check
            i = doc.find("scheduler_trace_spans")
            region = doc[i:] if i >= 0 else ""
            j = region.find("\n- scheduler_")
            if j > 0:
                region = region[:j]
            for n in sorted(names):
                if not re.search(rf"\b{re.escape(n)}\b", region):
                    findings.append(Finding(
                        met_sf.rel, 1, "ID010",
                        f"span {n!r} (spans.SPAN_NAMES) is not named in "
                        "the metrics docstring entry for "
                        "scheduler_trace_spans_total",
                    ))

        path = os.path.join(ctx.root, "README.md")
        if os.path.exists(path):
            with open(path, encoding="utf-8") as f:
                text = f.read()
            m = re.search(
                r"^## Distributed tracing\b(.*?)(?=^## |\Z)",
                text, re.M | re.S,
            )
            section = m.group(1) if m else ""
            for n in sorted(names):
                if not re.search(rf"\b{re.escape(n)}\b", section):
                    findings.append(Finding(
                        sp_sf.rel, sp_line, "ID010",
                        f"span {n!r} (spans.SPAN_NAMES) is not documented "
                        'in the README "## Distributed tracing" section',
                    ))
        return findings

    # ---- ID006: compile-cache key inventory ------------------------------

    @staticmethod
    def _tuple_of_tuples_heads(sf, name: str):
        """First string element of each inner tuple of a module-level
        `NAME = ((..., ...), ...)` literal — the dimension names of
        packing.SIGNATURE_DIMS."""
        for node in sf.tree.body:
            if not (
                isinstance(node, ast.Assign)
                and len(node.targets) == 1
                and isinstance(node.targets[0], ast.Name)
                and node.targets[0].id == name
            ):
                continue
            if not isinstance(node.value, (ast.Tuple, ast.List)):
                return None, node.lineno
            out = set()
            for e in node.value.elts:
                if (
                    isinstance(e, (ast.Tuple, ast.List)) and e.elts
                    and isinstance(e.elts[0], ast.Constant)
                    and isinstance(e.elts[0].value, str)
                ):
                    out.add(e.elts[0].value)
            return out, node.lineno
        return None, 0

    def _check_compile_key(self, ctx: LintContext) -> list[Finding]:
        cc_sf = self._find(ctx, "core/compile_cache.py")
        pk_sf = self._find(ctx, "models/packing.py")
        if cc_sf is None or pk_sf is None:
            return []
        findings: list[Finding] = []
        dims, pk_line = self._tuple_of_tuples_heads(
            pk_sf, "SIGNATURE_DIMS"
        )
        sig_fields, cc_line = self._module_const(cc_sf, "SIG_KEY_FIELDS")
        extra_fields, _ = self._module_const(cc_sf, "EXTRA_KEY_FIELDS")
        if sig_fields is None:
            return [Finding(
                cc_sf.rel, 1, "ID006",
                "core/compile_cache.py defines no literal "
                "SIG_KEY_FIELDS tuple — the cache-key inventory the "
                "pad dimensions are checked against",
            )]
        if dims is None:
            return [Finding(
                pk_sf.rel, 1, "ID006",
                "models/packing.py defines no literal SIGNATURE_DIMS — "
                "the pad-dimension inventory the cache key must cover",
            )]
        for d in sorted(dims - sig_fields):
            findings.append(Finding(
                cc_sf.rel, cc_line, "ID006",
                f"pad dimension {d!r} (packing.SIGNATURE_DIMS) has no "
                "cache-key field in SIG_KEY_FIELDS: two regimes "
                f"differing only in {d} would alias one persistent "
                "executable entry",
            ))
        for d in sorted(sig_fields - dims):
            findings.append(Finding(
                pk_sf.rel, pk_line, "ID006",
                f"cache-key field {d!r} (SIG_KEY_FIELDS) names no "
                "SIGNATURE_DIMS dimension: stale key field",
            ))
        path = os.path.join(ctx.root, "README.md")
        if os.path.exists(path):
            with open(path, encoding="utf-8") as f:
                text = f.read()
            m = re.search(
                r"^## Compile-regime management\b(.*?)(?=^## |\Z)",
                text, re.M | re.S,
            )
            if m is None:
                findings.append(Finding(
                    cc_sf.rel, cc_line, "ID006",
                    'README.md has no "## Compile-regime management" '
                    "section documenting the cache-key table",
                ))
            else:
                section = m.group(1)
                for fld in sorted(sig_fields | (extra_fields or set())):
                    if not re.search(
                        rf"\b{re.escape(fld)}\b", section
                    ):
                        findings.append(Finding(
                            cc_sf.rel, cc_line, "ID006",
                            f"cache-key field {fld!r} is not documented "
                            'in the README "## Compile-regime '
                            'management" key table',
                        ))
        return findings

    # ---- ID007: degradation-rung inventory -------------------------------

    def _check_rungs(self, ctx: LintContext) -> list[Finding]:
        dg_sf = self._find(ctx, "core/degrade.py")
        if dg_sf is None:
            return []
        rungs, dg_line = self._module_const(dg_sf, "RUNGS")
        if not rungs:
            return [Finding(
                dg_sf.rel, 1, "ID007",
                "core/degrade.py defines no literal RUNGS tuple — the "
                "ladder inventory the README rung table is pinned to",
            )]
        path = os.path.join(ctx.root, "README.md")
        if not os.path.exists(path):
            return []
        with open(path, encoding="utf-8") as f:
            text = f.read()
        m = re.search(
            r"^## Failure model & degradation ladder\b(.*?)(?=^## |\Z)",
            text, re.M | re.S,
        )
        if m is None:
            return [Finding(
                dg_sf.rel, dg_line, "ID007",
                'README.md has no "## Failure model & degradation '
                'ladder" section documenting the rung table',
            )]
        section = m.group(1)
        findings: list[Finding] = []
        for rung in sorted(rungs):
            if not re.search(rf"\b{re.escape(rung)}\b", section):
                findings.append(Finding(
                    dg_sf.rel, dg_line, "ID007",
                    f"rung {rung!r} (degrade.RUNGS) is not documented "
                    'in the README "## Failure model & degradation '
                    'ladder" rung table',
                ))
        return findings

    # ---- ID008: sharded-collective budget inventory ----------------------

    def _check_collective_budgets(self, ctx: LintContext) -> list[Finding]:
        au_sf = self._find(ctx, "parallel/audit.py")
        if au_sf is None:
            return []
        budgets, au_line = self._module_const(
            au_sf, "COLLECTIVE_BUDGETS"
        )
        if not budgets:
            return [Finding(
                au_sf.rel, 1, "ID008",
                "parallel/audit.py defines no literal "
                "COLLECTIVE_BUDGETS dict — the committed allowlist "
                "scripts/audit_sharded.py gates the payload diet on",
            )]
        findings: list[Finding] = []
        mesh_sf = self._find(ctx, "parallel/mesh.py")
        axes: "set[str] | None" = None
        if mesh_sf is not None:
            axes, mesh_line = self._module_const(mesh_sf, "MESH_AXES")
            if axes is None:
                findings.append(Finding(
                    mesh_sf.rel, 1, "ID008",
                    "parallel/mesh.py defines no literal MESH_AXES "
                    "tuple — the axis-name inventory the budget table "
                    "and the sharding constraints are pinned to",
                ))
        path = os.path.join(ctx.root, "README.md")
        if not os.path.exists(path):
            return findings
        with open(path, encoding="utf-8") as f:
            text = f.read()
        m = re.search(
            r"^## Multi-chip and multi-host\b(.*?)(?=^## |\Z)",
            text, re.M | re.S,
        )
        if m is None:
            findings.append(Finding(
                au_sf.rel, au_line, "ID008",
                'README.md has no "## Multi-chip and multi-host" '
                "section documenting the collective budget table",
            ))
            return findings
        section = m.group(1)
        for cls in sorted(budgets):
            if not re.search(rf"\b{re.escape(cls)}\b", section):
                findings.append(Finding(
                    au_sf.rel, au_line, "ID008",
                    f"budget class {cls!r} (audit.COLLECTIVE_BUDGETS) "
                    'is not documented in the README "## Multi-chip '
                    'and multi-host" budget table',
                ))
        for axis in sorted(axes or ()):
            if not re.search(rf"\b{re.escape(axis)}\b", section):
                findings.append(Finding(
                    mesh_sf.rel, mesh_line, "ID008",
                    f"mesh axis {axis!r} (mesh.MESH_AXES) is not "
                    'documented in the README "## Multi-chip and '
                    'multi-host" section',
                ))
        return findings

    # ---- ID009: finding-code inventory -----------------------------------

    _REGISTRY_ANCHOR = "k8s_scheduler_tpu/analysis/registry.py"
    # the historical family prefixes: the phantom-row check only treats
    # tokens with one of these prefixes as finding codes, so prose like
    # "SHA256" in the section can never read as a stale row — while a
    # wholesale-deleted family's leftover rows are still caught
    _CODE_FAMILIES = ("TS", "LD", "JE", "ID", "HY", "RB", "TR", "SH")
    _CODE_RANGE_RE = re.compile(
        r"\b([A-Z]{2,3})(\d{3})`?\s*[-–]\s*`?\1(\d{3})\b"
    )

    def _check_code_table(self, ctx: LintContext) -> list[Finding]:
        from .registry import all_codes

        path = os.path.join(ctx.root, "README.md")
        if not os.path.exists(path):
            return []
        with open(path, encoding="utf-8") as f:
            text = f.read()
        m = re.search(
            r"^## Static analysis\b(.*?)(?=^## |\Z)", text, re.M | re.S
        )
        if m is None:
            # gated like HY003: only the real tree (which carries the
            # registry module) owes the README a Static-analysis table
            if ctx.file(self._REGISTRY_ANCHOR) is not None:
                return [Finding(
                    self._REGISTRY_ANCHOR, 1, "ID009",
                    'README.md has no "## Static analysis" section '
                    "documenting the pass/code table",
                )]
            return []
        section = m.group(1)
        registered = set(all_codes())
        prefixes = sorted(
            set(self._CODE_FAMILIES)
            | {re.match(r"[A-Z]+", c).group() for c in registered}
        )
        token_re = re.compile(
            rf"\b(?:{'|'.join(prefixes)})\d{{3}}\b"
        )
        documented = set(token_re.findall(section))
        # expand `TS001`-`TS004`-style ranges to the codes between
        for prefix, lo, hi in self._CODE_RANGE_RE.findall(section):
            for n in range(int(lo), int(hi) + 1):
                documented.add(f"{prefix}{n:03d}")
        findings: list[Finding] = []
        for code in sorted(registered - documented):
            findings.append(Finding(
                self._REGISTRY_ANCHOR, 1, "ID009",
                f"finding code {code!r} is registered but missing from "
                'the README "## Static analysis" pass/code table',
            ))
        for code in sorted(documented - registered):
            findings.append(Finding(
                self._REGISTRY_ANCHOR, 1, "ID009",
                f'the README "## Static analysis" table documents '
                f"{code!r}, which no registered pass defines: stale row",
            ))
        return findings

    # ---- ID011: alert rule-pack inventory --------------------------------

    @staticmethod
    def _rule_pack_names(sf):
        """Rule names out of the module-level `BUILTIN_RULES = (...)`
        literal: a tuple/list of dict literals whose "name" values are
        string constants. None when the literal is absent or not
        statically extractable — the rule pack MUST stay a pure
        literal, that is what makes it a machine-checked inventory."""
        for node in sf.tree.body:
            if not (
                isinstance(node, ast.Assign)
                and len(node.targets) == 1
                and isinstance(node.targets[0], ast.Name)
                and node.targets[0].id == "BUILTIN_RULES"
            ):
                continue
            v = node.value
            if not isinstance(v, (ast.Tuple, ast.List)):
                return None, node.lineno
            names: set[str] = set()
            for elt in v.elts:
                if not isinstance(elt, ast.Dict):
                    return None, node.lineno
                for k, val in zip(elt.keys, elt.values):
                    if (
                        isinstance(k, ast.Constant) and k.value == "name"
                        and isinstance(val, ast.Constant)
                        and isinstance(val.value, str)
                    ):
                        names.add(val.value)
            return names, node.lineno
        return None, 0

    # rule names the phantom-row scan recognizes: bare snake_case
    # tokens in the alert table's first column (family names carry the
    # scheduler_ prefix and belong to ID001's tables, not this one)
    _RULE_ROW_RE = re.compile(r"^\| *`([a-z][a-z0-9_]*)` *\|", re.M)

    def _check_alert_rules(self, ctx: LintContext) -> list[Finding]:
        rules_sf = self._find(ctx, "metrics/rules.py")
        if rules_sf is None:
            return []
        names, r_line = self._rule_pack_names(rules_sf)
        if not names:
            return [Finding(
                rules_sf.rel, max(r_line, 1), "ID011",
                "metrics/rules.py defines no statically-extractable "
                "BUILTIN_RULES literal (tuple of dict literals with "
                'string "name" values) — the committed rule pack the '
                "README alert table is pinned to",
            )]
        findings: list[Finding] = []
        # the anomaly-class leg: rule firings raise the `alert` class,
        # so its removal from observe.ANOMALY_CLASSES would make every
        # firing crash raise_anomaly's class validation
        obs_sf = self._find(ctx, "core/observe.py")
        if obs_sf is not None:
            classes, obs_line = self._module_const(
                obs_sf, "ANOMALY_CLASSES"
            )
            if classes is not None and "alert" not in classes:
                findings.append(Finding(
                    obs_sf.rel, max(obs_line, 1), "ID011",
                    'anomaly class "alert" is missing from '
                    "observe.ANOMALY_CLASSES — rule firings raise it, "
                    "so every alert would crash class validation",
                ))
        path = os.path.join(ctx.root, "README.md")
        if not os.path.exists(path):
            return findings
        with open(path, encoding="utf-8") as f:
            text = f.read()
        m = re.search(
            r"^### Metrics history, alert rules & the black box\b"
            r"(.*?)(?=^#{2,3} |\Z)",
            text, re.M | re.S,
        )
        if m is None:
            findings.append(Finding(
                rules_sf.rel, r_line, "ID011",
                'README.md has no "### Metrics history, alert rules & '
                'the black box" subsection documenting the built-in '
                "rule table",
            ))
            return findings
        section = m.group(1)
        for name in sorted(names):
            if not re.search(rf"\b{re.escape(name)}\b", section):
                findings.append(Finding(
                    rules_sf.rel, r_line, "ID011",
                    f"rule {name!r} (rules.BUILTIN_RULES) is not "
                    "documented in the README alert-rule table",
                ))
        for doc in sorted(set(self._RULE_ROW_RE.findall(section))):
            if doc.startswith("scheduler_"):
                continue  # family column rows belong to ID001
            if doc not in names:
                findings.append(Finding(
                    rules_sf.rel, r_line, "ID011",
                    f"the README alert-rule table documents {doc!r}, "
                    "which rules.BUILTIN_RULES does not define: "
                    "stale row",
                ))
        return findings

    # ---- ID001: metric inventory (runtime) -------------------------------

    def _check_metrics(self, ctx: LintContext) -> list[Finding]:
        problems = metric_inventory_problems(ctx.root)
        metrics_rel = self._find(ctx, "metrics/metrics.py").rel
        return [
            Finding(metrics_rel, 1, "ID001", p) for p in problems
        ]


# ---- the lint_metrics.py logic, kept importable for the shim -------------


def registered_names() -> set[str]:
    """Metric families registered on a fresh SchedulerMetrics, in
    Prometheus exposition naming (counters get their _total suffix)."""
    from k8s_scheduler_tpu.metrics import SchedulerMetrics

    names: set[str] = set()
    for fam in SchedulerMetrics().registry.collect():
        name = fam.name
        if fam.type == "counter":
            name += "_total"
        names.add(name)
    return names


def _strip_series_suffixes(names: set[str], families: set[str]) -> set[str]:
    """Collapse `foo_bucket`/`foo_count`/`foo_sum`/`foo_created` doc
    mentions onto their family name so prose quoting a specific series
    does not count as a phantom metric."""
    out = set()
    for n in names:
        base = re.sub(r"_(bucket|count|sum|created)$", "", n)
        out.add(base if base in families and n not in families else n)
    return out


def docstring_names() -> set[str]:
    import k8s_scheduler_tpu.metrics.metrics as mod

    return set(_NAME_RE.findall(mod.__doc__ or ""))


def readme_names(root: str | None = None) -> set[str]:
    if root is None:
        root = os.path.dirname(
            os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        )
    path = os.path.join(root, "README.md")
    with open(path, encoding="utf-8") as f:
        text = f.read()
    m = re.search(r"^## Observability\b(.*?)(?=^## |\Z)", text,
                  re.M | re.S)
    if m is None:
        return set()
    return set(_NAME_RE.findall(m.group(1)))


def metric_inventory_problems(root: str | None = None) -> list[str]:
    """Human-readable metric-inventory drift complaints (empty = ok)."""
    reg = registered_names()
    problems: list[str] = []
    gone = sorted(REQUIRED_FAMILIES - reg)
    if gone:
        problems.append(
            "required durable-state/leader metric families no longer "
            f"registered: {gone}"
        )
    for surface, found in (
        ("metrics/metrics.py docstring", docstring_names()),
        ('README "## Observability" section', readme_names(root)),
    ):
        found = _strip_series_suffixes(found, reg)
        missing = sorted(reg - found)
        phantom = sorted(found - reg)
        if not found:
            problems.append(f"{surface}: no metric names found at all")
        if missing:
            problems.append(
                f"{surface}: registered but undocumented: {missing}"
            )
        if phantom:
            problems.append(
                f"{surface}: documented but not registered: {phantom}"
            )
    return problems
