#!/usr/bin/env python
"""warm_cache: pre-populate the persistent compiled-program cache.

A deployment that knows its serving shape ahead of time (pod/node pad
buckets, sticky E/MPN pre-sizes, profile config) can pay every compile
BEFORE taking traffic: run this against the scheduler's state dir (or an
explicit --cache-dir), and the first serving process loads every program
from the cache instead of compiling cold (8.8-16.8 s per program on the
rig; ~100 s historical worst case on a regime flip).

    python scripts/warm_cache.py --cache-dir /var/lib/sched/compile_cache \
        --pods 10000 --nodes 5000 [--config scheduler.yaml] \
        [--adjacent 1]

`--adjacent N` also pre-builds N pad-bucket regimes above the given pod
count — the regimes churn would otherwise flip into mid-serve.
"""

from __future__ import annotations

import argparse
import os
import sys
import time

sys.path.insert(
    0, os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
)


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(prog="warm_cache")
    ap.add_argument("--cache-dir", default="",
                    help="compile-cache directory (or use --state-dir)")
    ap.add_argument("--state-dir", default="",
                    help="state dir; cache goes to <state-dir>/compile_cache")
    ap.add_argument("--config", default="",
                    help="KubeSchedulerConfiguration YAML (profiles, pads)")
    ap.add_argument("--pods", type=int, default=256)
    ap.add_argument("--nodes", type=int, default=64)
    ap.add_argument("--pad-bucket", type=int, default=64)
    ap.add_argument("--adjacent", type=int, default=1,
                    help="extra P pad buckets above --pods to pre-build")
    args = ap.parse_args(argv)

    cache_dir = args.cache_dir or (
        os.path.join(args.state_dir, "compile_cache")
        if args.state_dir else ""
    )
    if not cache_dir:
        ap.error("one of --cache-dir / --state-dir is required")

    from k8s_scheduler_tpu.config import (
        SchedulerConfiguration,
        load_config,
    )
    from k8s_scheduler_tpu.core import Scheduler
    # the scheduler's own bucket rounding: the pre-built regimes must
    # be byte-for-byte the pads serving will ask for
    from k8s_scheduler_tpu.core.scheduler import _pad
    from k8s_scheduler_tpu.models import packing
    from k8s_scheduler_tpu.utils.synth import make_cluster, make_pods

    config = (
        load_config(args.config) if args.config
        else SchedulerConfiguration()
    )
    config.compile_cache_dir = cache_dir
    config.speculative_compile = False  # builds run HERE, synchronously
    sched = Scheduler(config=config, pad_bucket=args.pad_bucket)
    nodes = make_cluster(args.nodes)
    pending = make_pods(args.pods, seed=1)
    bucket = args.pad_bucket

    total = 0
    for profile in sched._profile_order:
        enc = sched._encoders[profile]
        enc.pad_nodes = _pad(args.nodes, bucket)
        for step in range(args.adjacent + 1):
            enc.pad_pods = _pad(args.pods, bucket) + step * bucket
            snap = enc.encode(nodes, pending)
            spec = packing.make_spec(snap)
            t0 = time.perf_counter()
            sched._packed_fns(spec, profile)
            total += 1
            print(
                f"profile={profile} P={enc.pad_pods} "
                f"source={sched._last_compile_source} "
                f"{time.perf_counter() - t0:.2f}s",
                flush=True,
            )
    cc = sched._compile_cache
    print(
        f"warmed {total} regime(s): {cc.status() if cc else 'no cache'}",
        flush=True,
    )
    return 0


if __name__ == "__main__":
    sys.exit(main())
