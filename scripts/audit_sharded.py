#!/usr/bin/env python
"""audit_sharded: compile-only collective-payload gate for the sharded
carry cycle (ISSUE 10 acceptance; parallel/audit.py holds the committed
budget allowlist).

    python scripts/audit_sharded.py                # audit + assert budgets
    python scripts/audit_sharded.py --no-assert    # report only
    python scripts/audit_sharded.py --devices 8 --pods 10112 --nodes 5120

Builds the production carry-cycle program at the AUDIT SHAPE
(P=10112 x N=5120, a 10,000 x 5,000 cluster's padded geometry) over
an N-device 1-D ('pods',) virtual CPU mesh, compiles it with the carry
partitioned — NO execution, so the [P, N] arrays are never
materialized — and parses every collective out of the compiled HLO. The per-class totals are asserted against
`parallel/audit.COLLECTIVE_BUDGETS` and the grand total against
`TOTAL_BUDGET_MB`; schedlint ID008 pins those class names to the README
budget table and the mesh-axis names, so the allowlist can only move
together with its documentation.

Output: shape counts, payload totals under BOTH the real-dtype-width
model and the flat 4-bytes-per-element model, budget verdict, rc — one
format from round to round, so audits stay diffable. Exit: 0 within
budget, 1 over budget, 2 build error.
"""

from __future__ import annotations

import argparse
import os
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)


def _force_devices(n: int) -> None:
    flag = f"--xla_force_host_platform_device_count={n}"
    xla_flags = os.environ.get("XLA_FLAGS", "")
    if flag not in xla_flags:
        os.environ["XLA_FLAGS"] = (xla_flags + " " + flag).strip()
    import jax

    jax.config.update("jax_platforms", "cpu")


def main(argv: "list[str] | None" = None) -> int:
    ap = argparse.ArgumentParser(prog="audit_sharded")
    ap.add_argument("--devices", type=int, default=8)
    ap.add_argument("--pods", type=int, default=10112)
    ap.add_argument("--nodes", type=int, default=5120)
    ap.add_argument(
        "--no-assert", action="store_true",
        help="report payloads without gating on the budget allowlist",
    )
    args = ap.parse_args(argv)
    _force_devices(args.devices)

    import jax

    from k8s_scheduler_tpu.core import (
        build_packed_cycle_carry_fn,
        build_stable_state_fn,
    )
    from k8s_scheduler_tpu.core.cycle import CarryKeeper
    from k8s_scheduler_tpu.models import SnapshotEncoder
    from k8s_scheduler_tpu.parallel import audit
    from k8s_scheduler_tpu.parallel.mesh import make_mesh
    from k8s_scheduler_tpu.utils.compilation_cache import (
        enable_compilation_cache,
    )
    from k8s_scheduler_tpu.utils.synth import make_cluster, make_pods

    enable_compilation_cache()
    P, N = args.pods, args.nodes
    mesh = make_mesh(jax.devices()[: args.devices])

    # the BENCH config-4 pending distribution at the audit scale —
    # affinity/spread/selector terms keep every guard path compiled in
    nodes = make_cluster(
        min(N, 5000), taint_fraction=0.1, cpu_choices=(4, 8, 16)
    )
    pods = make_pods(
        min(P, 10000), seed=0, affinity_fraction=0.3,
        anti_affinity_fraction=0.2, spread_fraction=0.2,
        selector_fraction=0.3, toleration_fraction=0.1,
        priorities=(0, 0, 10, 100), num_apps=500,
    )
    enc = SnapshotEncoder(pad_pods=P, pad_nodes=N)
    wbuf, bbuf, spec, _vs, _dirty = enc.encode_packed(nodes, pods)

    import numpy as np

    w = jax.ShapeDtypeStruct((spec.n_words,), np.uint32)
    b = jax.ShapeDtypeStruct((spec.n_bytes,), np.uint8)

    try:
        stable_fn = build_stable_state_fn(spec)
        stable_sds = jax.tree_util.tree_map(
            lambda o: jax.ShapeDtypeStruct(o.shape, o.dtype),
            stable_fn.lower(w, b).out_info,
        )
        keeper = CarryKeeper(spec, mesh=mesh)
        carry_low = keeper.ci.lower(w, b, stable_sds)
        carry_sds = jax.tree_util.tree_map(
            lambda o: jax.ShapeDtypeStruct(
                o.shape, o.dtype, sharding=getattr(o, "sharding", None)
            ),
            carry_low.out_info,
        )
        cyc = build_packed_cycle_carry_fn(
            spec, mesh=mesh, rounds_kw={"compact_gather": "onehot"}
        )
        compiled = cyc.lower(w, b, stable_sds, carry_sds).compile()
    except Exception as e:
        print(f"audit_sharded: build failed: {e}", file=sys.stderr)
        return 2

    hlo = compiled.as_text()
    colls = audit.parse_collectives(hlo)
    mb = 1024.0 * 1024.0

    # ---- the r05-style shape histogram ----
    from collections import Counter

    hist = Counter((c.base_op, c.type_str, c.bytes) for c in colls)
    print(f"P={P} N={N} devices={args.devices} collectives={len(colls)}")
    for (op, tstr, nbytes), cnt in sorted(
        hist.items(), key=lambda kv: -kv[1]
    ):
        print(
            f"{cnt:>5} x {op:<20} {tstr}  (~{nbytes / 1024.0:.1f} KB "
            "each)"
        )

    total = sum(c.bytes for c in colls)
    flat4 = sum(c.flat4 for c in colls)
    by_class = audit.classify_totals(colls, P, N)
    print(
        f"approx collective payload total: {total / mb:.2f} MB "
        f"(flat-4B model, r05-comparable: {flat4 / mb:.2f} MB)"
    )
    biggest = max(colls, key=lambda c: c.elems, default=None)
    if biggest is not None:
        print(
            f"max single-collective payload: {biggest.elems} elems "
            f"({biggest.bytes / mb:.2f} MB) {biggest.type_str}"
        )
    for cls in sorted(audit.COLLECTIVE_BUDGETS):
        print(
            f"class {cls:<12} {by_class.get(cls, 0) / mb:>8.2f} MB "
            f"(budget {audit.COLLECTIVE_BUDGETS[cls]:.2f} MB)"
        )

    if args.no_assert:
        print("budget assertion SKIPPED (--no-assert)")
        return 0
    problems = audit.check_budgets(by_class)
    if problems:
        for p in problems:
            print(f"BUDGET VIOLATION: {p}")
        print("compile-only audit FAILED (payload over budget)")
        return 1
    print("compile-only audit PASSED (payload bounds asserted)")
    return 0


if __name__ == "__main__":
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    rc = main()
    print(f"rc={rc}")
    sys.exit(rc)
