"""Compile-regime management (ISSUE 8): the persistent executable
cache's framing robustness (truncation / bit flips / version and
fingerprint mismatches are refused loudly and recompiled, never crashed
on), atomic concurrent writes, the AOT load-or-compile path, the
adjacent-regime spec rewrite (packing.respec) against real encodes, pad
hysteresis (an oscillating workload holds the larger regime), the
program memo's LRU eviction regression, and the slow-tier end-to-end proofs:
warm restart with zero cold compiles, and a speculation-won flip with
compile_ms ~= 0."""

from __future__ import annotations

import struct
import threading
import time

import jax
import numpy as np
import pytest

from k8s_scheduler_tpu.config import SchedulerConfiguration
from k8s_scheduler_tpu.core import Scheduler
from k8s_scheduler_tpu.core import compile_cache as cc
from k8s_scheduler_tpu.core.cycle import _jit
from k8s_scheduler_tpu.models import MakeNode, MakePod, packing
from k8s_scheduler_tpu.models.encoding import SnapshotEncoder
from k8s_scheduler_tpu.utils.synth import make_cluster, make_pods


def _tiny_spec():
    """A real (cheap — no jit) PackSpec for key construction."""
    enc = SnapshotEncoder(pad_pods=8, pad_nodes=8)
    nodes = [MakeNode("n0").capacity({"cpu": "8"}).obj()]
    pods = [MakePod("p0").req({"cpu": "1"}).obj()]
    return packing.make_spec(enc.encode(nodes, pods))


def _fresh_fn(disc: str = "t"):
    """A distinctively-named jitted toy program (same deterministic
    name per disc — the cross-'process' cache-key property)."""
    return _jit(
        lambda w, b: {"s": w.sum() + b.sum(), "n": (b != 0).sum()},
        "cc_test", disc=disc,
    )


_ARGS = (
    jax.ShapeDtypeStruct((16,), np.uint32),
    jax.ShapeDtypeStruct((8,), np.uint8),
)


# ---- entry framing robustness -------------------------------------------


def test_load_or_compile_roundtrip(tmp_path):
    spec = _tiny_spec()
    cache = cc.CompileCache(str(tmp_path))
    comp, source, dt, out_sds = cc.load_or_compile(
        _fresh_fn(), cache, spec, "default", "cycle", args=_ARGS
    )
    assert comp is not None and source == "cold"
    assert cache.misses == 1 and cache.hits == 0
    assert out_sds["s"].shape == ()
    w = np.arange(16, dtype=np.uint32)
    b = np.ones(8, np.uint8)
    first = np.asarray(comp(w, b)["s"])

    # a "restarted process": fresh cache object, fresh (but
    # identically-named) jit wrapper, same directory — and the loaded-
    # executable memo cleared, so the load REALLY deserializes
    cc.clear_loaded_memo()
    cache2 = cc.CompileCache(str(tmp_path))
    comp2, source2, dt2, _ = cc.load_or_compile(
        _fresh_fn(), cache2, spec, "default", "cycle", args=_ARGS
    )
    assert comp2 is not None and source2 == "cache"
    assert cache2.hits == 1 and cache2.misses == 0
    assert cache2.load_seconds and cache2.load_seconds[0] == dt2
    assert np.asarray(comp2(w, b)["s"]) == first


def test_stored_one_device_executable_loads_on_a_multi_device_host(
    tmp_path,
):
    """The JAX 0.9.0 fault, pinned (ISSUE 22): `deserialize_and_load`
    defaults `execution_devices` to EVERY device of the backend, so on
    a host with more than one — the 8-device test platform, a four-chip
    TPU host — a stored one-device executable loaded as an N-way one
    and failed at its first CALL ("Expected args to
    execute_sharded_on_local_devices to have 8 shards, got: [1, 1]"),
    which `_Resilient` and the ladder would then have served around on
    every warm restart."""
    assert len(jax.devices()) > 1, "conftest forces 8 virtual devices"
    spec = _tiny_spec()
    fn = _fresh_fn("onedev")
    cc.load_or_compile(
        fn, cc.CompileCache(str(tmp_path)), spec, "default", "cycle",
        args=_ARGS,
    )
    cc.clear_loaded_memo()  # the next load REALLY deserializes
    loaded, source, _dt, _out = cc.load_or_compile(
        _fresh_fn("onedev"), cc.CompileCache(str(tmp_path)), spec,
        "default", "cycle", args=_ARGS,
    )
    assert source == "cache"
    w = np.arange(16, dtype=np.uint32)
    b = np.ones(8, np.uint8)
    out = loaded(w, b)
    assert int(out["s"]) == int(w.sum()) + 8 and int(out["n"]) == 8
    assert len(out["s"].sharding.device_set) == 1


def test_sharded_program_outputs_chain_sharded_avals(tmp_path):
    """A program partitioned over a mesh hands its outputs on sharded;
    the avals `load_or_compile` returns for chaining must say so, or
    the downstream executable is compiled for replicated inputs and
    refuses the real (sharded) carry at its first call — found by a
    four-device rehearsal of the served path, where the ladder
    absorbed it. Cold and loaded executables must agree, and the
    loaded one must run on the mesh's devices, not the whole
    backend's."""
    from jax.sharding import Mesh, NamedSharding, PartitionSpec

    mesh = Mesh(np.array(jax.devices()[:4]), ("pods",))
    rows = NamedSharding(mesh, PartitionSpec("pods"))

    def make(name):
        return _jit(
            lambda x: jax.lax.with_sharding_constraint(x * 2.0, rows),
            name, disc="sh",
        )

    spec = _tiny_spec()
    x_sds = jax.ShapeDtypeStruct((8, 4), np.float32, sharding=rows)
    _c, source, _dt, cold_out = cc.load_or_compile(
        make("cc_shard_a"), cc.CompileCache(str(tmp_path)), spec,
        "default", "carry_init", args=(x_sds,),
    )
    assert source == "cold" and cold_out.sharding.is_equivalent_to(rows, 2)
    cc.clear_loaded_memo()
    loaded, source, _dt, out = cc.load_or_compile(
        make("cc_shard_a"), cc.CompileCache(str(tmp_path)), spec,
        "default", "carry_init", args=(x_sds,),
    )
    assert source == "cache" and out.sharding.is_equivalent_to(rows, 2)
    # the downstream program, compiled against the chained aval, takes
    # the upstream's real output
    down, _s, _dt, _o = cc.load_or_compile(
        make("cc_shard_b"), cc.CompileCache(str(tmp_path)), spec,
        "default", "cycle", args=(out,),
    )
    x = jax.device_put(np.ones((8, 4), np.float32), rows)
    y = down(loaded(x))
    assert float(y.sum()) == 8 * 4 * 4.0
    assert y.sharding.device_set == set(mesh.devices.flat)


@pytest.mark.parametrize("placed", ["environment", "unset", "disabled"])
def test_persistent_cache_is_placed_from_outside(tmp_path, placed):
    """`JAX_COMPILATION_CACHE_DIR` set: the cache is exactly that
    directory (no per-backend subdirectory, nothing under ~). Unset: the
    one fixed directory inside the checkout. Checked in a child, whose
    environment is what places it — and which must not have initialised
    a backend by asking."""
    import os
    import subprocess
    import sys

    from k8s_scheduler_tpu.utils import compilation_cache as ucc

    env = {
        k: v for k, v in os.environ.items()
        if k not in ("JAX_COMPILATION_CACHE_DIR",
                     "K8S_TPU_DISABLE_COMPILE_CACHE")
    }
    want = {"environment": str(tmp_path / "placed"),
            "unset": ucc.DEFAULT_CACHE_DIR, "disabled": ""}[placed]
    if placed == "environment":
        env["JAX_COMPILATION_CACHE_DIR"] = want
    if placed == "disabled":
        env["K8S_TPU_DISABLE_COMPILE_CACHE"] = "1"
    code = (
        "import jax\n"
        "from jax._src import xla_bridge\n"
        "from k8s_scheduler_tpu.utils.compilation_cache import "
        "enable_compilation_cache\n"
        "d = enable_compilation_cache()\n"
        "assert not xla_bridge.backends_are_initialized()\n"
        "print(repr((d, jax.config.jax_compilation_cache_dir or '')))\n"
    )
    out = subprocess.run(
        [sys.executable, "-c", code], env=env, text=True, check=True,
        capture_output=True,
        cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    )
    assert out.stdout.strip() == repr((want, want))
    assert os.path.dirname(ucc.DEFAULT_CACHE_DIR) == os.path.dirname(
        os.path.dirname(os.path.abspath(__file__))
    )


def _entry_path(tmp_path):
    files = [p for p in tmp_path.iterdir() if p.name.endswith(".kscc")]
    assert len(files) == 1
    return files[0]


@pytest.mark.parametrize("damage", ["truncate", "bitflip", "version"])
def test_damaged_entry_refused_loudly_then_recompiled(
    tmp_path, caplog, damage
):
    """Satellite: truncated / bit-flipped / future-version entries are
    REFUSED with a loud log line and the program recompiles cleanly —
    the cache can cost a compile, never a crash."""
    spec = _tiny_spec()
    cache = cc.CompileCache(str(tmp_path))
    cc.load_or_compile(
        _fresh_fn(), cache, spec, "default", "cycle", args=_ARGS
    )
    path = _entry_path(tmp_path)
    blob = path.read_bytes()
    if damage == "truncate":
        path.write_bytes(blob[: len(blob) // 2])
    elif damage == "bitflip":
        mid = len(blob) // 2
        path.write_bytes(
            blob[:mid] + bytes([blob[mid] ^ 0x40]) + blob[mid + 1:]
        )
    else:  # a future format version must be refused, not half-parsed
        path.write_bytes(
            blob[:4] + struct.pack("<I", 99) + blob[8:]
        )
    cache2 = cc.CompileCache(str(tmp_path))
    with caplog.at_level("ERROR", logger=cc.log.name):
        comp, source, _dt, _ = cc.load_or_compile(
            _fresh_fn(), cache2, spec, "default", "cycle", args=_ARGS
        )
    assert comp is not None and source == "cold"  # clean recompile
    assert any("REFUSING" in r.message for r in caplog.records)
    # the recompile overwrote the bad entry: next load is a clean hit
    cache3 = cc.CompileCache(str(tmp_path))
    _comp, source3, _dt, _ = cc.load_or_compile(
        _fresh_fn(), cache3, spec, "default", "cycle", args=_ARGS
    )
    assert source3 == "cache"


def test_fingerprint_mismatch_is_miss_not_crash(tmp_path):
    """Satellite: a jaxlib/backend fingerprint mismatch is a MISS. The
    fingerprint rides the key (so a different backend gets a different
    filename) AND the entry meta (defense in depth, exercised here)."""
    spec = _tiny_spec()
    cache = cc.CompileCache(str(tmp_path))
    key = cc.cache_key(spec, "default", "cycle", "prog")
    assert cache.store(key, b"payload", 1.0)
    assert cache.load(key) == b"payload"
    cache._fingerprint = "jax9.9.9-othertpu"
    assert cache.load(key) is None  # miss, no exception
    # and the key itself embeds the fingerprint: a rebuilt key under
    # the new fingerprint names a different file entirely
    key2 = cc.cache_key(
        spec, "default", "cycle", "prog",
        fingerprint="jax9.9.9-othertpu",
    )
    assert key2.name != key.name


def test_concurrent_same_key_writers_leave_one_intact_entry(tmp_path):
    """Satellite: a warm-thread + serve-loop build of the same key must
    produce ONE entry with no torn bytes (tmp+fsync+rename, unique tmp
    per writer) — every interleaving loads a whole payload."""
    spec = _tiny_spec()
    cache = cc.CompileCache(str(tmp_path))
    key = cc.cache_key(spec, "default", "cycle", "prog")
    payloads = [bytes([i]) * 4096 for i in range(4)]
    stop = threading.Event()
    errors: list = []

    def writer(payload):
        while not stop.is_set():
            if not cache.store(key, payload, 0.1):
                errors.append("store failed")

    threads = [
        threading.Thread(target=writer, args=(p,), daemon=True)
        for p in payloads
    ]
    for t in threads:
        t.start()
    deadline = time.monotonic() + 1.0
    reads = 0
    while time.monotonic() < deadline:
        got = cache.load(key)
        if got is not None:
            assert got in payloads  # whole payload, never torn
            reads += 1
    stop.set()
    for t in threads:
        t.join(5.0)
    assert not errors and reads > 0
    files = [p for p in tmp_path.iterdir() if p.name.endswith(".kscc")]
    assert len(files) == 1  # one entry; tmp files all cleaned/renamed


# ---- the adjacent-regime spec rewrite -----------------------------------


def _rich_workload():
    nodes = make_cluster(20, taint_fraction=0.3)
    pods = make_pods(
        40, seed=3, affinity_fraction=0.3, anti_affinity_fraction=0.2,
        spread_fraction=0.2, selector_fraction=0.3,
        toleration_fraction=0.2, priorities=(0, 10), num_apps=5,
    )
    existing = [
        (p, f"node-{i % 20}")
        for i, p in enumerate(make_pods(30, seed=9, name_prefix="run"))
    ]
    return nodes, pods, existing


def test_respec_matches_real_encode_exactly():
    """packing.respec's naming contract (pod_*/node_* carry P/N on axis
    0 and nowhere else) verified against the encoder's ground truth: the
    rewritten spec must equal the spec a REAL encode at the adjacent pad
    produces — byte-identical key, so the pre-built programs are the
    ones the flip will ask for."""
    nodes, pods, existing = _rich_workload()
    enc = SnapshotEncoder(pad_pods=64, pad_nodes=32)
    spec64 = packing.make_spec(enc.encode(nodes, pods, existing))
    enc.pad_pods = 128
    spec128 = packing.make_spec(enc.encode(nodes, pods, existing))
    enc.pad_pods = 64
    enc.pad_nodes = 64
    spec_n64 = packing.make_spec(enc.encode(nodes, pods, existing))

    up = packing.respec(spec64, {"P": 128})
    assert up is not None and up.key() == spec128.key()
    down = packing.respec(spec128, {"P": 64})
    assert down is not None and down.key() == spec64.key()
    n_up = packing.respec(spec64, {"N": 64})
    assert n_up is not None and n_up.key() == spec_n64.key()


def test_respec_refuses_extender_planes_and_unknown_dims():
    import dataclasses

    nodes, pods, existing = _rich_workload()
    enc = SnapshotEncoder(pad_pods=64, pad_nodes=32)
    snap = enc.encode(nodes, pods, existing)
    spec = packing.make_spec(snap)
    assert packing.respec(spec, {"E": 512}) is None  # sticky dims: no
    assert packing.respec(spec, {}) is None
    P, N = snap.pod_valid.shape[0], snap.node_valid.shape[0]
    ext = dataclasses.replace(
        snap,
        has_extender=True,
        pod_extender_mask=np.ones((P, N), bool),
        pod_extender_score=np.zeros((P, N), np.float32),
    )
    # the [P, N] verdict planes break the axis-0-only rule: refuse
    assert packing.respec(packing.make_spec(ext), {"P": 128}) is None


# ---- pad hysteresis ------------------------------------------------------


def test_hysteresis_pad_unit():
    enc = SnapshotEncoder(pad_hysteresis_pct=25.0)
    assert enc.hysteresis_pad("P", 64, 60) == 64   # first sighting
    assert enc.hysteresis_pad("P", 128, 80) == 128  # up-step: immediate
    # candidate shrank to 64 but real=60 leaves only 6% headroom: hold
    assert enc.hysteresis_pad("P", 64, 60) == 128
    # real=40 leaves 37% headroom inside 64: step down
    assert enc.hysteresis_pad("P", 64, 40) == 64
    # knob off = identity
    enc0 = SnapshotEncoder()
    assert enc0.hysteresis_pad("P", 128, 80) == 128
    assert enc0.hysteresis_pad("P", 64, 60) == 64


def test_hysteresis_holds_regime_under_oscillating_trace():
    """Satellite: an oscillating pending count crossing a pad-bucket
    boundary produces ZERO regime flips after the first up-step with
    hysteresis on, where the no-hysteresis baseline flips every
    crossing. Asserted on spec KEYS (what actually triggers a
    recompile) — no jit needed, so this runs in the fast tier."""
    nodes = make_cluster(8)

    def keys_for(pct: float) -> list:
        enc = SnapshotEncoder(pad_hysteresis_pct=pct)  # pow2 buckets
        out = []
        for i in range(8):
            pods = make_pods(70 if i % 2 else 60, seed=i)
            out.append(packing.make_spec(enc.encode(nodes, pods)).key())
        return out

    base = keys_for(0.0)
    base_flips = sum(1 for a, b in zip(base, base[1:]) if a != b)
    assert base_flips >= 7  # flips every crossing without hysteresis

    held = keys_for(15.0)
    held_flips = sum(1 for a, b in zip(held, held[1:]) if a != b)
    assert held_flips == 1  # the initial up-step only
    assert held[1:] == [held[1]] * 7  # larger regime held throughout


# ---- program-memo LRU eviction regression -------------------------------


class _FakeSpec:
    def __init__(self, k):
        self._k = k

    def key(self):
        return self._k


def test_packed_memo_eviction_is_true_lru(monkeypatch):
    """Same property for the single-cycle program memo."""
    s = Scheduler(
        config=SchedulerConfiguration(flight_recorder_size=0)
    )
    profile = s._profile_order[0]
    monkeypatch.setattr(
        s, "_build_packed_entry",
        lambda spec, prof, aot: {
            "fns": ("f", spec.key()), "build_s": 0.0, "source": "cold",
        },
    )
    cap = 4 * len(s.frameworks)
    for i in range(cap):
        s._packed_fns(_FakeSpec(f"regime{i}"), profile)
    s._packed_fns(_FakeSpec("regime0"), profile)
    s._packed_fns(_FakeSpec(f"regime{cap}"), profile)
    keys = {k[0] for k in s._packed}
    assert "regime0" in keys and "regime1" not in keys


# ---- observer demand EWMA ------------------------------------------------


def test_observer_demand_ewma_tracks_pod_counts():
    from k8s_scheduler_tpu.core.observe import CycleObserver

    obs = CycleObserver(metrics=None)
    assert obs.demand_ewma("default-scheduler") == 0.0
    for _ in range(30):
        obs.observe_phases({"total": 0.01}, counts={"pods": 50})
    assert abs(obs.demand_ewma("default-scheduler") - 50.0) < 1.0
    # drifts toward a new level within a handful of cycles
    for _ in range(10):
        obs.observe_phases({"total": 0.01}, counts={"pods": 100})
    assert obs.demand_ewma("default-scheduler") > 80.0
    # per-profile isolation
    obs.observe_phases(
        {"total": 0.01}, counts={"pods": 7}, profile="other"
    )
    assert obs.demand_ewma("other") == 7.0


# ---- AOT fallback behaviour ---------------------------------------------


def test_resilient_falls_back_to_jit_on_convention_mismatch(tmp_path):
    """An installed AOT executable serves matching-aval calls; any
    other call shape falls through to the jit path instead of raising
    (the preemption program is legitimately called under two
    conventions)."""
    spec = _tiny_spec()
    cache = cc.CompileCache(str(tmp_path))
    fn = _fresh_fn("fallback")
    comp, source, _dt, _ = cc.load_or_compile(
        fn, cache, spec, "default", "cycle", args=_ARGS
    )
    fn.install_aot(comp)
    w = np.arange(16, dtype=np.uint32)
    b = np.ones(8, np.uint8)
    assert int(np.asarray(fn(w, b)["n"])) == 8  # AOT-served
    big_w = np.arange(32, dtype=np.uint32)
    big_b = np.ones(16, np.uint8)
    assert int(np.asarray(fn(big_w, big_b)["n"])) == 16  # jit fallback
    assert fn._aot is not None  # still installed for matching calls
    assert int(np.asarray(fn(w, b)["n"])) == 8


# ---- end-to-end proofs (slow tier) --------------------------------------


def _mini_cluster(s, n_nodes=4, cpu="640"):
    for i in range(n_nodes):
        s.on_node_add(MakeNode(f"n{i}").capacity({"cpu": cpu}).obj())


def test_warm_restart_compiles_zero_programs(tmp_path):
    """Acceptance: a second scheduler against a populated
    compile_cache/ records ZERO cold compiles for previously-seen
    regimes, with entry load time far below the cold compile it
    replaced."""
    cfg = SchedulerConfiguration(compile_cache_dir=str(tmp_path))
    s1 = Scheduler(config=cfg, pad_bucket=8)
    _mini_cluster(s1)
    for i in range(6):
        s1.on_pod_add(MakePod(f"p{i}").req({"cpu": "1"}).obj())
    t0 = time.perf_counter()
    assert s1.schedule_cycle().scheduled == 6
    cold_s = time.perf_counter() - t0
    assert s1._compile_cache.misses >= 5  # full program set stored
    assert s1._compile_cache.hits == 0

    # "restart": fresh Scheduler = fresh jit wrappers, empty in-memory
    # caches, loaded-executable memo cleared — only the disk entries
    # carry over, so every program REALLY deserializes
    cc.clear_loaded_memo()
    s2 = Scheduler(
        config=SchedulerConfiguration(compile_cache_dir=str(tmp_path)),
        pad_bucket=8,
    )
    _mini_cluster(s2)
    for i in range(6):
        s2.on_pod_add(MakePod(f"w{i}").req({"cpu": "1"}).obj())
    t0 = time.perf_counter()
    assert s2.schedule_cycle().scheduled == 6
    warm_s = time.perf_counter() - t0
    st = s2._compile_cache.status()
    assert st["misses"] == 0, "warm restart paid a cold compile"
    assert st["hits"] >= 5
    entry = next(iter(s2._packed.values()))
    assert entry["source"] == "cache"
    # flight record of the warm first cycle attributes the flip to the
    # cache, and the loads were cheap next to the cold build
    rec = s2.flight.snapshot()[0]
    assert rec.counts.get("regime_flip") == 1
    assert rec.compile_source == "cache"
    assert st["load_p50_s"] < 1.0
    assert warm_s < cold_s


def test_an_executable_of_the_engine_before_is_not_loaded(
        tmp_path, monkeypatch):
    """The executable store keys on program names, not on code: the
    commit rounds' mark rides in the name of every program that embeds
    them (`core/cycle._engine_marks`), so an entry stored by the engine
    that held a spread group to the counts at the round's start (mark
    ":parks", PR 36-45) is a miss for this one, which compiles its own."""
    from k8s_scheduler_tpu.ops import rounds as rounds_ops

    assert rounds_ops.ENGINE_MARK != ":parks"
    cfg = SchedulerConfiguration(compile_cache_dir=str(tmp_path))
    with monkeypatch.context() as m:
        m.setattr(rounds_ops, "ENGINE_MARK", ":parks")
        old = Scheduler(config=cfg, pad_bucket=8)
        _mini_cluster(old)
        old.on_pod_add(MakePod("p0").req({"cpu": "1"}).obj())
        assert old.schedule_cycle().scheduled == 1
        stored = old._compile_cache.misses
        assert stored >= 5 and old._compile_cache.hits == 0
    cc.clear_loaded_memo()
    new = Scheduler(
        config=SchedulerConfiguration(compile_cache_dir=str(tmp_path)),
        pad_bucket=8,
    )
    _mini_cluster(new)
    new.on_pod_add(MakePod("w0").req({"cpu": "1"}).obj())
    assert new.schedule_cycle().scheduled == 1
    entry = next(iter(new._packed.values()))
    assert entry["source"] == "cold"
    assert new._compile_cache.misses >= 1
    # ... and under its own mark the next process loads every program
    cc.clear_loaded_memo()
    again = Scheduler(
        config=SchedulerConfiguration(compile_cache_dir=str(tmp_path)),
        pad_bucket=8,
    )
    _mini_cluster(again)
    again.on_pod_add(MakePod("x0").req({"cpu": "1"}).obj())
    assert again.schedule_cycle().scheduled == 1
    assert again._compile_cache.misses == 0
    assert next(iter(again._packed.values()))["source"] == "cache"


def test_speculative_precompile_wins_the_flip(tmp_path):
    """Acceptance: with demand drifting toward the P bucket boundary,
    the warm thread pre-builds the adjacent regime; the flip then costs
    ~zero serve-path compile and is stamped
    compile_source="speculative" on the record AND the /debug/anomalies
    recompile event."""
    cfg = SchedulerConfiguration(
        compile_cache_dir=str(tmp_path),
        # pre-sized sticky pads (the documented fold-mode pattern):
        # the oscillation then moves exactly one dimension — P
        pad_existing=512,
        pad_pods_per_node=256,
    )
    s = Scheduler(config=cfg, binder=lambda p, n: None, pad_bucket=8)
    _mini_cluster(s)
    k = 0
    for _cyc in range(10):  # demand EWMA -> 7 >= 0.85 * P(=8)
        for _ in range(7):
            s.on_pod_add(MakePod(f"p{k}").req({"cpu": "1"}).obj())
            k += 1
        s.schedule_cycle()
    assert s._warmer is not None
    assert s._warmer.join(300), "speculative build never finished"
    assert s._warmer.built >= 1 and s._warmer.failed == 0
    assert any(
        e.get("fresh") for e in s._packed.values()
    ), "no speculative entry landed in the program memo"

    for _ in range(12):  # cross the boundary: P 8 -> 16
        s.on_pod_add(MakePod(f"p{k}").req({"cpu": "1"}).obj())
        k += 1
    t0 = time.perf_counter()
    s.schedule_cycle()
    flip_s = time.perf_counter() - t0
    flips = [
        r for r in s.flight.snapshot() if r.counts.get("regime_flip")
    ]
    won = [r for r in flips if r.compile_source == "speculative"]
    assert won, f"no speculation-won flip in {len(flips)} flips"
    assert won[-1].phases.get("compile_ms", 1e9) < 50.0  # ~zero
    evs = [
        e for e in s.observer.anomalies() if e["class"] == "recompile"
    ]
    assert evs and evs[-1]["detail"].get("compile_source") == (
        "speculative"
    )
    assert "P" in evs[-1]["detail"]["dims"]
    assert flip_s < 2.0  # the flip cycle never paid a compile
    assert (
        "scheduler_compile_cache_speculative_builds_total"
        in s.metrics.expose().decode()
    )


def _churn_drive(cache_dir: str, hysteresis_pct: float, cycles: int = 8):
    """One real Scheduler over `cache_dir`, its pending count flipping
    60 <-> 80 across the P=64/128 pad boundary every cycle (E and MPN
    pre-sized, so P is the one dimension that moves)."""
    # manual clock: a cold compile takes real seconds, and an assumed
    # pod's TTL expiring mid-drive would requeue it and move P off the
    # scripted oscillation
    clk = [0.0]
    sched = Scheduler(
        config=SchedulerConfiguration(
            compile_cache_dir=cache_dir,
            pad_existing=4096,
            pad_pods_per_node=1024,
            pad_hysteresis_pct=hysteresis_pct,
            speculative_compile=False,  # would race the oscillation
        ),
        binder=lambda p, n: None,
        now=lambda: clk[0],
    )
    for nd in make_cluster(16):
        sched.on_node_add(nd)
    for i in range(cycles):
        for p in make_pods(
            80 if i % 2 else 60, seed=9000 + i, name_prefix=f"rc{i}-"
        ):
            sched.on_pod_add(p)
        sched.schedule_cycle()
        clk[0] += 0.05
    recs = sched.flight.snapshot()
    # a stall = a cycle that paid > 50 ms of program build for a
    # regime the scheduler had already been through
    seen: set = set()
    stalls = 0
    for r in recs:
        if r.phases.get("compile_ms", 0.0) > 50.0 and r.sig in seen:
            stalls += 1
        seen.add(r.sig)
    return {
        "flips": sum(1 for a, b in zip(recs, recs[1:]) if a.sig != b.sig),
        "stalls": stalls,
        "compile_s": sum(r.phases.get("compile_ms", 0.0) for r in recs) / 1e3,
        "sources": {
            r.compile_source for r in recs
            if r.counts.get("regime_flip") and r.compile_source
        },
        "cache": sched._compile_cache.status(),
    }


def test_regime_churn_soak_zero_compile_stalls(tmp_path):
    """Acceptance: the pad-bucket-crossing churn soak records zero
    compile-attributed stall cycles after the first traversal of each
    regime, a warm start with zero cold compiles, and hysteresis
    holding the oscillation to a single flip."""
    cold = _churn_drive(str(tmp_path), 0.0)
    hyst = _churn_drive(str(tmp_path), 20.0)
    # a fresh process would start without the loaded-executable memo:
    # the warm drive must really deserialize
    cc.clear_loaded_memo()
    warm = _churn_drive(str(tmp_path), 0.0)
    assert cold["flips"] >= 7  # the workload really oscillated
    assert hyst["flips"] == 1  # held after the first up-step
    assert cold["stalls"] + hyst["stalls"] + warm["stalls"] == 0
    assert warm["cache"]["misses"] == 0  # compiled nothing cold
    assert warm["cache"]["hits"] > 0
    assert warm["sources"] <= {"cache"}
    assert cold["compile_s"] > warm["compile_s"]


def test_warmer_stop_wakes_idle_worker_and_reports_a_running_build():
    """`stop(timeout)` is what lets cmd/main.py exit cleanly on SIGTERM:
    an idle worker is woken and gone at once (True); a worker inside a
    build — an XLA compile cannot be interrupted — reports False, and
    the CLI then leaves without finalizing the interpreter under it
    (the process used to die -11 after sealing its state)."""
    w = cc.CompileWarmer()
    assert w.stop(timeout=1.0)  # never started: nothing to wait for
    w = cc.CompileWarmer()
    assert w.enqueue_build("quick", lambda: None)
    assert w.join(10)
    t0 = time.monotonic()
    assert w.stop(timeout=10.0)  # idle in q.get(): the sentinel wakes it
    assert time.monotonic() - t0 < 4.0
    assert not w.enqueue_build("late", lambda: None)  # stopped: refused

    release = threading.Event()
    w = cc.CompileWarmer()
    assert w.enqueue_build("slow", lambda: release.wait(30))
    time.sleep(0.1)
    assert not w.stop(timeout=0.2)  # still inside the build
    release.set()
    assert w.stop(timeout=10.0)
