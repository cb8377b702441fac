"""Compiled-program collective audit: the sharded serving path's
payload accounting (ISSUE 10 / ROADMAP item 3).

At the "millions of users" cluster sizes the north star names, the
carry cycle's cross-device traffic — not FLOPs — is expected to be the
cycle floor (not measured on the chip): before the payload diet the
compiled cycle at the P=10112/N=5120 audit shape moved over ten times
the bytes it moves now, more than half of them in one all-reduce of the
replicated compacted [B, N] static base. This module turns that
accounting into a COMMITTED, compile-only gate:

- `parse_collectives` reads a compiled HLO module's text and returns
  one record per collective op (all-reduce / all-gather / reduce-
  scatter / all-to-all / collective-permute, sync and `-start` async
  forms, tuple-shaped results included) with element counts and bytes
  under two payload models: real dtype widths (`bytes`) and a flat
  4-bytes-per-element model (`flat4`, the first audit's, kept so
  audits stay comparable across rounds).
- `classify` buckets each record into the budget classes of
  `COLLECTIVE_BUDGETS` — the committed allowlist `scripts/
  audit_sharded.py` asserts against, pinned by schedlint ID008 to the
  README "## Multi-chip and multi-host" budget table and to the mesh
  axis names in `parallel/mesh.MESH_AXES` (renaming an axis or a class
  without its doc row fails the tree).
- `check_budgets` returns the violations (loud, named, per class).

The scheduler's per-regime program probe (`collective_payload_bytes`)
reuses the same parser to stamp flight records and the
`scheduler_collective_payload_bytes` gauge, so serving telemetry and
the CI gate can never disagree about what a byte of collective is.
"""

from __future__ import annotations

import dataclasses
import re

# Budget classes x per-cycle budgets (MB, REAL dtype widths) for the
# carry-cycle program at the audit shape (P=10112, N=5120, 8-device
# 1-D pods mesh). schedlint ID008
# pins every class name here to a row of the README "## Multi-chip and
# multi-host" budget table; scripts/audit_sharded.py asserts the
# measured per-class totals against these numbers and the grand total
# against TOTAL_BUDGET_MB. Calibration: the post-diet values the
# audit counts from the compiled HLO plus ~25% headroom.
COLLECTIVE_BUDGETS = {
    # f32 planes of the [B, N]/[S, N] class: the compacted static-base
    # transport and the affinity-state count tables. Post-diet this is
    # ZERO — the compacted view stays sharded end-to-end (shard_view)
    # and the state update runs device-local (local_update_fn), where
    # the cycle before the diet paid a replicated-view all-reduce. The
    # budget is small headroom, not an allowance: any [.,N]-wide f32
    # collective reappearing is a diet regression and should trip this
    # row.
    "static_base": 2.0,
    # claim/participant-table sort operands (packed u32 keys + index
    # permutations + per-claim key vectors) gathered across the pods
    # axis by the global sorts — measured 2.20 MB (index operands ride
    # at the minimal width the table extent allows: argsel.index_dtype)
    "claim_sort": 4.0,
    # capacity resolution: requested-vector [B, R] gathers and the
    # node_req [N, R] partial-sum reductions — measured 0.78 MB
    "capacity": 1.5,
    # boolean liveness/acceptance planes (pred all-reduces/gathers) —
    # measured 0.63 MB
    "predicates": 1.5,
    # sort-internal permute traffic (collective-permute lanes)
    "permute": 1.0,
    # anything unclassified — kept tight so a new heavy collective
    # cannot hide here
    "other": 1.0,
}
# grand total (real dtype widths). The audit counts 3.62 MB post-diet
# at the audit shape (compiled HLO, not a chip run); this budget holds
# the diet at ~2x that.
TOTAL_BUDGET_MB = 8.0

_COLL_RE = re.compile(
    r"= (?P<type>.*?) (?P<op>(?:all-reduce|all-gather|reduce-scatter|"
    r"all-to-all|collective-permute)(?:-start)?)\("
)
_TENSOR_RE = re.compile(r"(pred|bf16|[fsu]\d+)\[([\d,]*)\]")

_WIDTH = {
    "pred": 1, "u8": 1, "s8": 1,
    "u16": 2, "s16": 2, "f16": 2, "bf16": 2,
    "u32": 4, "s32": 4, "f32": 4,
    "u64": 8, "s64": 8, "f64": 8,
}


@dataclasses.dataclass(frozen=True)
class Collective:
    op: str  # e.g. "all-reduce", "all-gather-start"
    type_str: str  # the HLO result type, tuple forms included
    elems: int  # total elements across the (possibly tuple) result
    bytes: int  # real dtype-width bytes
    flat4: int  # r05-comparable flat 4-bytes-per-element payload

    @property
    def base_op(self) -> str:
        return self.op[:-6] if self.op.endswith("-start") else self.op


def _tensors(type_str: str) -> list[tuple[str, list[int]]]:
    out = []
    for dt, dims in _TENSOR_RE.findall(type_str):
        shape = [int(d) for d in dims.split(",") if d] if dims else []
        out.append((dt, shape))
    return out


def parse_collectives(hlo_text: str) -> list[Collective]:
    """One record per collective op line of a compiled HLO module.
    Parsed per LINE so tuple-shaped (variadic/combined) collectives are
    covered; `-start` async halves are counted once (their `-done`
    partner carries no new payload and does not match the regex)."""
    out: list[Collective] = []
    for line in hlo_text.splitlines():
        m = _COLL_RE.search(line)
        if not m:
            continue
        elems = 0
        nbytes = 0
        for dt, shape in _tensors(m.group("type")):
            n = 1
            for d in shape:
                n *= d
            elems += n
            nbytes += n * _WIDTH.get(dt, 4)
        out.append(Collective(
            op=m.group("op"),
            type_str=m.group("type"),
            elems=elems,
            bytes=nbytes,
            flat4=elems * 4,
        ))
    return out


def collective_payload_bytes(hlo_text: str) -> int:
    """Total real-width collective payload of a compiled program — the
    per-regime cost probe the scheduler stamps on flight records and
    exports as `scheduler_collective_payload_bytes`."""
    return sum(c.bytes for c in parse_collectives(hlo_text))


def classify(coll: Collective, P: int, N: int) -> str:
    """Budget class of one collective at audit geometry (P, N).

    Heuristics keyed on what each class structurally looks like, not on
    exact shapes (pass counts and window sizes move between configs):
    2-D f32 planes whose column extent is N (or a shard of it) are the
    static-base transport; wide integer vectors/pairs scaling with P
    are sort-key/permutation gathers; narrow f32 [., R<=8] tables are
    capacity traffic; pred planes are liveness predicates; collective-
    permutes of u8/u16/u32 lanes are sort internals."""
    tensors = _tensors(coll.type_str)
    if coll.base_op == "collective-permute":
        return "permute"
    # [., R<=8] capacity tables fail the width guard on their own; any
    # f32 plane at node-scale width is static-base-class transport
    f32_2d_n = any(
        dt == "f32" and len(sh) == 2 and sh[1] >= max(N // 64, 64)
        for dt, sh in tensors
    )
    if f32_2d_n:
        return "static_base"
    if any(dt == "pred" for dt, _sh in tensors) and all(
        dt == "pred" for dt, _sh in tensors
    ):
        return "predicates"
    if any(
        dt == "f32" and len(sh) == 2 and sh[1] <= 8
        for dt, sh in tensors
    ):
        return "capacity"
    if all(dt in ("s32", "u32", "s16", "u16") for dt, _sh in tensors):
        return "claim_sort"
    return "other"


def classify_totals(
    colls: "list[Collective]", P: int, N: int
) -> dict[str, int]:
    """Per-class real-width byte totals (every COLLECTIVE_BUDGETS class
    present, zero-filled, so a budget row can never silently vanish
    from a report)."""
    out = {k: 0 for k in COLLECTIVE_BUDGETS}
    for c in colls:
        out[classify(c, P, N)] += c.bytes
    return out


def check_budgets(
    class_bytes: "dict[str, int]",
    total_budget_mb: float = TOTAL_BUDGET_MB,
) -> list[str]:
    """Violations of the committed allowlist (empty = within budget).
    An unknown class in `class_bytes` is itself a violation — the
    allowlist must grow deliberately, in the same commit."""
    problems: list[str] = []
    mb = 1024.0 * 1024.0
    for cls, nbytes in sorted(class_bytes.items()):
        budget = COLLECTIVE_BUDGETS.get(cls)
        if budget is None:
            problems.append(
                f"collective class {cls!r} is not in "
                f"COLLECTIVE_BUDGETS ({nbytes / mb:.2f} MB unbudgeted)"
            )
        elif nbytes / mb > budget:
            problems.append(
                f"collective class {cls!r} moves {nbytes / mb:.2f} MB "
                f"per cycle, over its {budget:.2f} MB budget"
            )
    total = sum(class_bytes.values()) / mb
    if total > total_budget_mb:
        problems.append(
            f"total collective payload {total:.2f} MB per cycle, over "
            f"the {total_budget_mb:.2f} MB budget"
        )
    return problems
