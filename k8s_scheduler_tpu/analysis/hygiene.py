"""HYGIENE (HY0xx): dead module-level names and script-layer sprawl.

The probe/profiling script layer accretes imports and private constants
that outlive the experiment that needed them; in the package they also
cost import time. Conservative by construction:

- HY001  a module-level import whose bound name is never referenced in
         the module (skipped in __init__.py — re-exports are the
         point — and for names listed in __all__)
- HY002  a module-level `_private` assignment never referenced again
         (underscore names only: public constants may be external API)
- HY003  scripts/ inventory drift: a `scripts/*.py` not named in
         SCRIPT_ALLOWLIST (one-off probe/bisect/trace scripts
         historically accumulated 25 deep before ISSUE 6 pruned them —
         adding a script now requires the deliberate act of listing it
         here), or an allowlist entry whose file no longer exists
"""

from __future__ import annotations

import ast
import re

from .core import Finding, LintContext
from .registry import PassBase

# The maintained scripts/ inventory. Everything here is referenced by
# the README, the test suite, or CI; a new script joins by being added
# HERE in the same commit (HY003 fails otherwise), which is the review
# hook that keeps dead one-off probes from accumulating silently again.
SCRIPT_ALLOWLIST = frozenset({
    "scripts/alerts_check.py",    # clean-soak alert-rule CI gate
    "scripts/audit_sharded.py",   # compile-only collective-budget gate
    "scripts/blackbox_read.py",   # crash black-box bundle reader
    "scripts/fuzz_scheduler.py",  # scenario-fuzzer differential soak
    "scripts/lint_metrics.py",    # metric-inventory shim (tests)
    "scripts/loadgen.py",         # open-loop front-door load generator
    "scripts/probe_pipeline.py",  # CPU-runnable pipeline smoke probe
    "scripts/schedlint.py",       # this framework's CLI
    "scripts/soak_chaos.py",      # slow-marked fault-injection chaos soak
    "scripts/soak_differential.py",  # slow-marked differential soak
    "scripts/soak_failover.py",   # slow-marked kill -9 failover soak
    "scripts/warm_cache.py",      # compile-cache pre-warmer (ops tool)
})


class HygienePass(PassBase):
    name = "HYGIENE"
    codes = {
        "HY001": "unused module-level import",
        "HY002": "dead private module-level constant",
        "HY003": "scripts/ inventory drift (not in SCRIPT_ALLOWLIST)",
    }

    def run(self, ctx: LintContext) -> list[Finding]:
        findings: list[Finding] = []
        seen_scripts: set[str] = set()
        for sf in ctx.files:
            rel = sf.rel.replace("\\", "/")
            if rel.startswith("scripts/"):
                seen_scripts.add(rel)
                if rel not in SCRIPT_ALLOWLIST:
                    findings.append(Finding(
                        sf.rel, 1, "HY003",
                        f"{rel} is not in analysis/hygiene.py's "
                        "SCRIPT_ALLOWLIST — list it deliberately or "
                        "remove the script (one-off probes accumulate)",
                    ))
            if sf.rel.endswith("__init__.py"):
                continue
            if sf.rel.endswith("_pb2.py"):
                continue  # generated protobuf output, not hand-written
            findings.extend(self._check(sf))
        # dangling allowlist entries: judged against the DISK, not the
        # scanned set — a path-scoped scan of one script must not
        # report every other (existing) entry as stale. Gated on the
        # scan having covered either scripts/ or this pass's own module
        # (any real-repo scan has one of the two): fixture trees that
        # contain neither must not be judged against the repo's
        # inventory, but "scripts/ was deleted wholesale while the
        # allowlist still names it" — seen_scripts empty — must be
        if seen_scripts or ctx.file(
            "k8s_scheduler_tpu/analysis/hygiene.py"
        ) is not None:
            import os

            for rel in sorted(SCRIPT_ALLOWLIST - seen_scripts):
                if not os.path.exists(os.path.join(ctx.root, rel)):
                    findings.append(Finding(
                        "k8s_scheduler_tpu/analysis/hygiene.py", 1,
                        "HY003",
                        f"SCRIPT_ALLOWLIST names {rel} but no such "
                        "file exists — remove the stale entry",
                    ))
        return findings

    def _check(self, sf) -> list[Finding]:
        tree = sf.tree
        used: set[str] = set()
        exported: set[str] = set()
        imported: dict[str, tuple[int, str]] = {}  # name -> (line, shown)
        assigned: dict[str, int] = {}
        multi_assigned: set[str] = set()

        for node in tree.body:
            if isinstance(node, ast.Import):
                for a in node.names:
                    bound = a.asname or a.name.split(".")[0]
                    imported[bound] = (node.lineno, a.name)
            elif isinstance(node, ast.ImportFrom):
                if node.module == "__future__":
                    continue
                for a in node.names:
                    if a.name == "*":
                        continue
                    bound = a.asname or a.name
                    imported[bound] = (
                        node.lineno,
                        f"{'.' * node.level}{node.module or ''}.{a.name}",
                    )
            elif isinstance(node, ast.Assign):
                for t in node.targets:
                    if isinstance(t, ast.Name):
                        if t.id in assigned:
                            multi_assigned.add(t.id)
                        assigned[t.id] = node.lineno
                        if t.id == "__all__":
                            for e in ast.walk(node.value):
                                if isinstance(e, ast.Constant) and \
                                        isinstance(e.value, str):
                                    exported.add(e.value)
            elif isinstance(node, ast.AnnAssign) and isinstance(
                node.target, ast.Name
            ):
                assigned[node.target.id] = node.lineno

        def _string_annotation(n: ast.AST | None) -> None:
            # quoted annotations ("Iterable[dict[str, float]]") hide
            # their names in a Constant; count every identifier inside
            if isinstance(n, ast.Constant) and isinstance(n.value, str):
                used.update(
                    re.findall(r"[A-Za-z_][A-Za-z0-9_]*", n.value)
                )

        class _Uses(ast.NodeVisitor):
            def visit_Name(self, n: ast.Name) -> None:
                if isinstance(n.ctx, ast.Load):
                    used.add(n.id)
                elif isinstance(n.ctx, ast.Store):
                    # a later module-level rebind doesn't "use" it, but
                    # a function-level `global x; x = ...` pattern pairs
                    # with a read somewhere to matter; keep Store out
                    pass
                self.generic_visit(n)

            def visit_Global(self, n: ast.Global) -> None:
                used.update(n.names)

            def visit_AnnAssign(self, n: ast.AnnAssign) -> None:
                _string_annotation(n.annotation)
                self.generic_visit(n)

            def visit_arg(self, n: ast.arg) -> None:
                _string_annotation(n.annotation)
                self.generic_visit(n)

            def _visit_fn(self, n) -> None:
                _string_annotation(n.returns)
                self.generic_visit(n)

            visit_FunctionDef = _visit_fn
            visit_AsyncFunctionDef = _visit_fn

        _Uses().visit(tree)

        findings = []
        for name, (line, shown) in sorted(imported.items()):
            if name in used or name in exported or name == "_":
                continue
            findings.append(Finding(
                sf.rel, line, "HY001",
                f"import {shown!r} binds {name!r}, never referenced in "
                "this module",
            ))
        for name, line in sorted(assigned.items()):
            if (
                not name.startswith("_") or name.startswith("__")
                or name in used or name in exported
                or name in multi_assigned or name in imported
            ):
                continue
            findings.append(Finding(
                sf.rel, line, "HY002",
                f"private module-level name {name!r} is assigned but "
                "never referenced",
            ))
        return findings
