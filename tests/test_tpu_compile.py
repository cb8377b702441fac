"""The served path's XLA programs, compiled for a v5e that is described
and not attached (ISSUE 22 steps 2 and 6).

No Pallas kernel exists in this repo, so "the kernels compile" means the
cycle's XLA programs — `stable`, `carry_init`, `cycle`, `carry_update`,
`preempt`, `diag` — at the pad regime PR 22 first served on the chip:
cell 4 (10,000 pending x 5,000 nodes, 12,000 bound) under
`padExisting: 32768`, `padPodsPerNode: 32`. The TPU compiler installed
here compiles them for `v5e:2x2` without a chip; what it refuses here
(a program that does not fit 16 GB, an op it cannot lower) would be
refused there, at no chip time.

Rules this file keeps (on-chip-measurement guide, section 2):

- the topology is described inside a module-scoped fixture that skips
  when it cannot be — never at import, in a `skipif` or in
  `parametrize`: only one process may load libtpu, and every xdist
  worker imports every test file;
- the compile runs in the test's own process (a child could not load
  the library the worker holds);
- JAX's persistent cache is off around the compiles: an entry compiled
  for a described chip cannot be read back without one;
- all of it lives in this ONE file, so one worker gets all of it.

Tier-1 keeps the programs that compile in well under 20 s each; the
rest are marked `slow` and are run by hand with `-s` to read compile
seconds and `memory_analysis()` (the numbers in CHANGES.md PR 22):

    JAX_PLATFORMS=cpu python -m pytest tests/test_tpu_compile.py -s
"""

import os
import time

import jax
import numpy as np
import pytest

# the sticky pads that keep cell 4 in one regime
SMOKE_PADS = dict(pad_existing=32768, pad_pods_per_node=32)
HBM_BYTES = 16 * 1024**3  # one v5e chip


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def no_persistent_cache():
    from jax.experimental.compilation_cache import compilation_cache as jcc

    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    jcc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", prev)
    jcc.reset_cache()


def make_cell(cfg: int) -> tuple:
    """(nodes, pending, existing) of cell `cfg`: 2 = 1,000 pods x 100
    nodes with node affinity and taints, 3 = 5,000 x 1,000 with
    inter-pod (anti-)affinity, 4 = 10,000 x 5,000 with the full default
    plugin set over 12,000 low-priority bound pods on small nodes, so
    that preemption has real work."""
    from k8s_scheduler_tpu.utils.synth import make_cluster, make_pods

    mix = dict(affinity_fraction=0.3, anti_affinity_fraction=0.2,
               spread_fraction=0.2, num_apps=500)
    if cfg == 2:
        return (
            make_cluster(100, taint_fraction=0.3),
            make_pods(1000, seed=0, selector_fraction=0.5,
                      toleration_fraction=0.4),
            [],
        )
    if cfg == 3:
        return make_cluster(1000), make_pods(5000, seed=0, **mix), []
    assert cfg == 4, cfg
    bound = make_pods(12000, seed=991, name_prefix="run",
                      affinity_fraction=0.1, spread_fraction=0.1,
                      num_apps=500)
    return (
        make_cluster(5000, taint_fraction=0.1, cpu_choices=(4, 8, 16)),
        make_pods(10000, seed=0, selector_fraction=0.3,
                  toleration_fraction=0.1, priorities=(0, 0, 10, 100),
                  **mix),
        [(p, f"node-{i % 5000}") for i, p in enumerate(bound)],
    )


def lower_regime(cfg: int, default, mesh_devices=()) -> tuple:
    """Encode cell `cfg` through a real Scheduler's encoder under
    SMOKE_PADS, and lower its program chain
    (core/scheduler.aot_chain — the walk `_aot_install` serves from)
    with the host-made inputs placed by `default`, as served; chained
    avals that carry no sharding of their own get it too (a described
    device must be named somewhere, or lowering reaches for the CPU).

    One chip: returns (spec, {kind: Lowered}); nothing is compiled, the
    chained avals are plain. With `mesh_devices` (the described
    topology's), the Scheduler's mesh is built over them and each
    program is COMPILED as it is reached, because the next one's avals
    carry its output shardings (compile_cache._out_avals, as served):
    returns (spec, {kind: (compiled, seconds)})."""
    from k8s_scheduler_tpu.config import SchedulerConfiguration
    from k8s_scheduler_tpu.core import compile_cache as cc
    from k8s_scheduler_tpu.core import scheduler as sched_mod

    nodes, pending, existing = make_cell(cfg)
    config = SchedulerConfiguration(**SMOKE_PADS)
    config.compile_cache_dir = "off"
    config.speculative_compile = False
    sched = sched_mod.Scheduler(config=config)
    if mesh_devices:
        from k8s_scheduler_tpu.parallel.mesh import make_mesh

        sched._mesh = make_mesh(list(mesh_devices))
    profile = sched._profile_order[0]
    enc = sched._encoders[profile]
    enc.pad_pods = sched_mod._pad(len(pending), sched._pad_bucket)
    enc.pad_nodes = sched_mod._pad(len(nodes), sched._pad_bucket)
    _w, _b, spec, _snap, _dirty = enc.encode_packed(
        nodes, pending, existing
    )
    entry = sched._build_packed_entry(spec, profile, aot=False)
    cyc, preempt, stable_fn, keeper, diag, _ek, _pipe = entry["fns"]
    out: dict = {}

    def place(tree):
        return jax.tree_util.tree_map(
            lambda s: s if getattr(s, "sharding", None) is not None
            else jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=default),
            tree,
        )

    def one(kind, fn, args, kwargs):
        low = fn.lower(*place(args), **place(kwargs or {}))
        if not mesh_devices:
            out[kind] = low
            return cc._out_avals(low)
        t0 = time.perf_counter()
        compiled = low.compile()
        out[kind] = (compiled, time.perf_counter() - t0)
        return cc._out_avals(low, compiled)

    assert sched_mod.aot_chain(
        spec, one, cyc=cyc, preempt=preempt, stable_fn=stable_fn,
        keeper=keeper, diag=diag, placement=default,
    )
    return spec, out


@pytest.fixture(scope="module")
def one_chip_regimes(topo):
    """cfg -> (spec, {kind: Lowered}), lowered once per cell."""
    from jax.sharding import SingleDeviceSharding

    one_chip = SingleDeviceSharding(topo.devices[0])
    cache: dict = {}

    def get(cfg: int):
        if cfg not in cache:
            cache[cfg] = lower_regime(cfg, one_chip)
        return cache[cfg]

    return get


def device_bytes(mem) -> int:
    return int(
        mem.argument_size_in_bytes + mem.output_size_in_bytes
        + mem.temp_size_in_bytes + mem.generated_code_size_in_bytes
        - mem.alias_size_in_bytes
    )


def report(label: str, spec, seconds: float, mem) -> int:
    from k8s_scheduler_tpu.models.packing import shape_signature

    total = device_bytes(mem)
    print(
        f"\ntpu-compile {label} regime={dict(shape_signature(spec))} "
        f"compile_s={seconds:.1f} args={mem.argument_size_in_bytes} "
        f"out={mem.output_size_in_bytes} temp={mem.temp_size_in_bytes} "
        f"code={mem.generated_code_size_in_bytes} "
        f"alias={mem.alias_size_in_bytes} total={total}"
    )
    return total


# (cell, program kind). Step 2 timed every program of cells 2-4 on this
# sandbox's 8 cores (seconds; cell 4 / 3 / 2):
#   stable 15.5/12.7/0.2  carry_init 4.0/2.7/1.0  cycle 114.9/57.0/13.6
#   carry_update 8.2/5.9/0.8  preempt 92.8/17.7/1.5  diag 45.9/33.2/1.3
# Tier-1 keeps what is under ~20 s each and ~60 s together: the three
# cell-4 programs that are, and cell 2's for the three kinds that are
# not; the rest are `slow`.
FAST = [
    (4, "stable"), (4, "carry_init"), (4, "carry_update"),
    (2, "cycle"), (2, "preempt"), (2, "diag"),
]
SLOW = [(4, "cycle"), (4, "preempt"), (4, "diag"), (3, "cycle")]


@pytest.mark.parametrize(
    "cfg,kind",
    FAST + [pytest.param(c, k, marks=pytest.mark.slow) for c, k in SLOW],
)
def test_program_compiles_for_v5e(
    one_chip_regimes, no_persistent_cache, cfg, kind
):
    spec, lowered = one_chip_regimes(cfg)
    t0 = time.perf_counter()
    compiled = lowered[kind].compile()
    total = report(
        f"cell={cfg} kind={kind}", spec, time.perf_counter() - t0,
        compiled.memory_analysis(),
    )
    assert total < HBM_BYTES, f"{kind} needs {total} B of a 16 GiB chip"


@pytest.mark.slow
def test_sharded_regime_compiles_for_four_v5e_chips(
    topo, no_persistent_cache
):
    """Step 4's rehearsal: cell 4's programs as `--shard-devices 4`
    builds them, partitioned over a Mesh of the described topology's
    four devices. The packed buffers arrive replicated
    (parallel/mesh.replicated — left unplaced, XLA propagates a
    sharding onto them and the preemption program it then partitions
    ABORTS this compiler: reproduced here before the second four-chip
    run); everything downstream arrives as its producer's executable
    hands it on. Per-device bytes must fit one chip, and the compiler
    must have put collectives into the cycle — "everything on the first
    chip" is not a partitioned program."""
    from k8s_scheduler_tpu.parallel.audit import collective_payload_bytes
    from k8s_scheduler_tpu.parallel.mesh import make_mesh, replicated

    assert len(topo.devices) == 4
    spec, compiled = lower_regime(
        4, replicated(make_mesh(topo.devices)), mesh_devices=topo.devices
    )
    for kind, (exe, seconds) in compiled.items():
        total = report(
            f"cell=4 x4 kind={kind}", spec, seconds, exe.memory_analysis()
        )
        assert total < HBM_BYTES, f"{kind}: {total} B per device"
    hlo = compiled["cycle"][0].as_text()
    ops = {
        op: hlo.count(f" {op}(") + hlo.count(f" {op}-start(")
        for op in ("all-reduce", "all-gather", "all-to-all",
                   "collective-permute", "reduce-scatter")
    }
    payload = int(collective_payload_bytes(hlo))
    print(f"tpu-compile cell=4 x4 collectives={ops} payload_bytes={payload}")
    assert sum(ops.values()) > 0 and payload > 0
