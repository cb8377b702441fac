"""SHARD-SAFETY (SH0xx): the PR 9 shard-exactness rules, machine-pinned.

PR 9 made multi-chip serving bit-identical to single-device at every
device count by (a) routing every claim-path reduce through the
shard-invariant selection primitives in ops/argsel.py (`jnp.argmax` /
`lax.top_k` merge equal-valued entries in shard-local order under
GSPMD), (b) eliminating the axis-0 `jnp.concatenate` of pods-sharded
1-D vectors that this jaxlib miscompiles under SPMD (guarded until
now only by one repro test, tests/test_shard_invariance.py), and
(c) centralizing every "which PartitionSpec does this array get" rule
in `parallel/mesh.mesh_pin`. ROADMAP item 3 (multi-host mesh) rewrites
exactly these surfaces — this pass is the static guardrail that must
hold while it does.

Scope: SH001/SH002 walk the call graph from the mesh-built program
roots — functions named `build_carry_fns`, `rounds_commit`, or
`_constrain_carry` (the carry-cycle builder, the rounds engine entry,
and the carry sharding constraint; everything that can ever trace under
a mesh is reachable from these). SH003 scans the WHOLE tree: a
PartitionSpec built anywhere outside parallel/mesh.py is a second copy
of the sharding rule waiting to drift.

- SH001  raw `jnp.argmax`/`jnp.argmin`/`*.top_k` in mesh-reachable
         code: use ops/argsel.argmax_first / top_k_first (shard-
         invariant tie order). Reduces over axes that can never be
         mesh-sharded (inner pad axes like MPN+1) are inventoried with
         `# schedlint: disable=SH001 -- why`.
- SH002  axis-0 (or default-axis) `jnp.concatenate` in mesh-reachable
         code: the PR 9 jaxlib SPMD miscompile class — concatenating
         pods-sharded 1-D operands produced wrong values under GSPMD.
         Use stack+reshape (ops/rounds.py's fix) or inventory
         replicated-operand sites.
- SH003  `PartitionSpec` / `NamedSharding` constructed outside
         parallel/mesh.py: the sharding rule lives in `mesh_pin` (and
         `shard_snapshot`) ONLY — a spec built elsewhere can disagree
         with the carry tables' layout and silently resharded-copy
         every dispatch.

Like the rest of the framework the walk is over-approximate: a
function referenced from a mesh root (lax.scan/cond bodies, plugin
hooks passed through the rounds engine) counts as called.
"""

from __future__ import annotations

import ast

from .callgraph import attribute_chain, own_body_nodes
from .core import Finding, LintContext, SourceFile
from .registry import PassBase
from .trace_safety import _ALIAS_TARGETS, _module_aliases

# the mesh-built program roots (see module docstring)
MESH_ROOT_FUNCTIONS = frozenset({
    "build_carry_fns", "rounds_commit", "_constrain_carry",
})

# the sharding-layout module that OWNS PartitionSpec construction
_MESH_MODULE_SUFFIX = "parallel/mesh.py"

_RAW_REDUCES = frozenset({"argmax", "argmin"})


def _is_axis0(call: ast.Call) -> bool:
    """True when a concatenate call can run on axis 0: explicitly, by
    default, via a NEGATIVE axis (for the 1-D operands that define the
    miscompile class, axis=-1 IS axis 0 — rank is not statically
    knowable, so negatives count as dangerous), or via a dynamic axis
    expression (same conservatism)."""
    axis = None
    for kw in call.keywords:
        if kw.arg == "axis":
            axis = kw.value
    if axis is None and len(call.args) >= 2:
        axis = call.args[1]
    if axis is None:
        return True  # default axis=0
    if isinstance(axis, ast.Constant) and isinstance(axis.value, int):
        return axis.value <= 0
    if isinstance(axis, ast.UnaryOp) and isinstance(axis.op, ast.USub):
        return True  # -1 parses as USub(Constant(1))
    return True  # dynamic axis: assume the dangerous one


class ShardSafetyPass(PassBase):
    name = "SHARD-SAFETY"
    codes = {
        "SH001": "raw argmax/top_k reduce in mesh-reachable code "
                 "(shard-local tie order; use ops/argsel)",
        "SH002": "axis-0 jnp.concatenate in mesh-reachable code "
                 "(the PR 9 jaxlib SPMD miscompile class)",
        "SH003": "PartitionSpec/NamedSharding built outside "
                 "parallel/mesh.py (mesh_pin owns the sharding rule)",
    }

    def run(self, ctx: LintContext) -> list[Finding]:
        index = ctx.index
        roots = {
            fid for fid, f in index.funcs.items()
            if f.name in MESH_ROOT_FUNCTIONS
        }
        reachable = index.reachable(roots)
        # aliases once per FILE, not per reachable function — a file
        # like ops/rounds.py holds dozens of mesh-reachable nested fns
        self._aliases: dict[str, dict] = {}
        findings: list[Finding] = []
        for fid in sorted(reachable):
            f = index.funcs[fid]
            findings.extend(self._check_reachable(f))
        for sf in ctx.files:
            findings.extend(self._check_spec_construction(sf))
        return findings

    # ---- SH001 / SH002 (mesh-reachable only) -----------------------------

    def _check_reachable(self, f) -> list[Finding]:
        sf = f.file
        aliases = self._aliases.get(sf.rel)
        if aliases is None:
            aliases = self._aliases[sf.rel] = _module_aliases(
                sf, _ALIAS_TARGETS
            )
        out: list[Finding] = []
        for node in own_body_nodes(f.node):
            if not isinstance(node, ast.Call):
                continue
            chain = attribute_chain(node.func)
            if chain is None:
                continue
            tag = aliases.get(chain[0]) if len(chain) > 1 else None
            if (
                tag == "jnp" and len(chain) == 2
                and chain[1] in _RAW_REDUCES
            ):
                out.append(Finding(
                    sf.rel, node.lineno, "SH001",
                    f"jnp.{chain[1]} in mesh-reachable {f.qualname}: "
                    "ties merge in shard-local order under GSPMD, so "
                    "placements diverge across device counts — use "
                    "ops/argsel.argmax_first (or inventory a reduce "
                    "over a never-sharded axis)",
                ))
            elif chain[-1] == "top_k":
                out.append(Finding(
                    sf.rel, node.lineno, "SH001",
                    f"top_k in mesh-reachable {f.qualname}: the "
                    "partitioned (value, index) combiner's tie order "
                    "is implementation-defined — use "
                    "ops/argsel.top_k_first (total-order 2-key sort)",
                ))
            elif (
                tag == "jnp" and len(chain) == 2
                and chain[1] == "concatenate"
                and _is_axis0(node)
            ):
                out.append(Finding(
                    sf.rel, node.lineno, "SH002",
                    f"axis-0 jnp.concatenate in mesh-reachable "
                    f"{f.qualname}: this jaxlib miscompiles axis-0 "
                    "concatenation of sharded 1-D operands under SPMD "
                    "(the PR 9 root cause) — use stack+reshape, or "
                    "inventory a provably-replicated site",
                ))
        return out

    # ---- SH003 (whole tree) ----------------------------------------------

    def _check_spec_construction(self, sf: SourceFile) -> list[Finding]:
        if sf.rel.endswith(_MESH_MODULE_SUFFIX):
            return []
        out: list[Finding] = []
        for node in sf.walk():
            if not isinstance(node, ast.Call):
                continue
            chain = attribute_chain(node.func)
            if chain and chain[-1] in ("PartitionSpec", "NamedSharding"):
                out.append(Finding(
                    sf.rel, node.lineno, "SH003",
                    f"{chain[-1]} constructed outside parallel/mesh.py: "
                    "the which-spec-does-this-array-get rule lives in "
                    "mesh.mesh_pin/shard_snapshot only — route through "
                    "them (or inventory plumbing like shard_map "
                    "in_specs with a justification)",
                ))
        return out
