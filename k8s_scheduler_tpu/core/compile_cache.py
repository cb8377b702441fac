"""Persistent compiled-program cache + speculative pre-compilation.

A cold start pays seconds to minutes of XLA compile per program (~200 s
for the six programs of the 10,000 x 5,000 regime on a v5e's host), and
a pad-regime flip re-pays it mid-serve (historically up to ~100 s, or a
backend wedge — ISSUE 5). Three layers attack that cost:

- **`CompileCache`** — an on-disk executable store under
  `<state-dir>/compile_cache/` (PR 3's durable-state directory; a
  standalone `compileCacheDir` works without durability). Programs are
  AOT-compiled (`fn.lower(...).compile()`) and serialized via
  `jax.experimental.serialize_executable`; entries are CRC-framed
  (magic + version + meta + payload + CRC32, written tmp+fsync+rename
  like PR 3 snapshots, so a concurrent warm-thread + serve-loop build
  of the same key leaves exactly one intact entry). A corrupt,
  truncated, or version-mismatched entry is REFUSED LOUDLY and the
  program recompiles — the cache can cost a compile, never a crash.
  Where the PJRT backend cannot serialize executables, the cache
  degrades to JAX's own persistent compilation-cache directory
  (utils/compilation_cache.py decides where that is).

- **Cache keys** — `models/packing.shape_signature(spec)` (the named
  pad regime: every SIGNATURE_DIMS dimension) + a hash of the full
  `spec.key()` + profile + program kind (cycle / stable / preempt /
  diag / carry_init / carry_update) + the program's
  deterministic build name + the jax/jaxlib/backend fingerprint. The
  literal `SIG_KEY_FIELDS`/`EXTRA_KEY_FIELDS` inventories below are
  machine-checked by schedlint ID006 against packing.SIGNATURE_DIMS and
  the README key table: a new pad dimension added without a cache-key
  field would silently alias distinct programs.

- **`CompileWarmer`** — a lazy daemon thread the scheduler feeds
  speculative build jobs (never the bind path): when the sentinel's
  per-profile demand EWMA (core/observe.py) drifts toward a pad-bucket
  boundary, the ADJACENT regime's spec is derived by `packing.respec`
  and its programs are pre-built into the scheduler's `_packed` memo
  and this disk cache. A flip that speculation won then
  stamps `regime_flip` with `compile_ms~=0` and
  `compile_source="speculative"`.
"""

from __future__ import annotations

import collections
import hashlib
import json
import logging
import os
import queue as _queue
import struct
import threading
import time as _time
import zlib
from typing import Any, Callable

from . import faults as _faults

log = logging.getLogger("k8s_scheduler_tpu.compile_cache")

_MAGIC = b"KSCC"
_VERSION = 1

# The cache-key inventory, pinned by schedlint ID006: SIG_KEY_FIELDS
# must equal the dimension names of models/packing.SIGNATURE_DIMS (a
# pad dimension without a key field would alias distinct programs into
# one entry), and every field of both tuples must appear in the README
# "## Compile-regime management" key table.
SIG_KEY_FIELDS = ("P", "N", "E", "MPN", "MA", "MC")
EXTRA_KEY_FIELDS = (
    "spec", "profile", "kind", "program", "mesh", "fingerprint",
)


def backend_fingerprint() -> str:
    """jax/jaxlib/backend identity an executable is only valid under.
    A mismatch is a MISS (the key embeds this), never a crash — a
    jaxlib upgrade or a CPU<->TPU move recompiles from scratch."""
    import jax
    import jaxlib

    try:
        kind = jax.devices()[0].device_kind
    except Exception:  # schedlint: disable=RB001 -- benign default: an
        # uninitializable backend still gets a usable fingerprint, and
        # the compile that follows will raise its own (louder) error
        kind = "unknown"
    return (
        f"jax{jax.__version__}-jaxlib{jaxlib.__version__}-"
        f"{jax.default_backend()}-{kind}"
    )


def program_name(fn) -> str:
    """The deterministic build name of a `_jit`-built program (the
    `_unique` base+discriminator-hash name — stable across restarts)."""
    inner = getattr(fn, "_fn", fn)
    return getattr(inner, "__name__", "anon")


class CacheKey:
    """One program's cache identity: the human-readable key string
    (stored inside the entry and verified on load) plus the filename
    stem (kind + a hash of the full key)."""

    __slots__ = ("text", "name")

    def __init__(self, text: str, kind: str) -> None:
        self.text = text
        digest = hashlib.sha256(text.encode()).hexdigest()[:24]
        self.name = f"{kind}-{digest}.kscc"

    def __repr__(self) -> str:  # pragma: no cover — debugging aid
        return f"CacheKey({self.name}: {self.text})"


def cache_key(
    spec, profile: str, kind: str, program: str,
    fingerprint: str | None = None,
    mesh: str = "none",
) -> CacheKey:
    """Build the key for one (regime, profile, program kind) triple.
    Iterates the literal key-field inventories above so the key string
    and the documented key table cannot structurally diverge.

    `mesh` is the sharding descriptor of the call's argument layout
    (see `_args_mesh_desc`): an executable compiled against sharded
    buffers partitions its kernels and is NOT interchangeable with the
    single-device build of the same regime — without this field the
    two would alias one entry and a sharded load could serve the
    unsharded program (or vice versa). Mesh-closure programs (the
    carry cycle built with `mesh=`) additionally carry the mesh in
    their deterministic program NAME, so both routes stay distinct."""
    from ..models.packing import shape_signature

    sig = dict(shape_signature(spec))
    parts = [f"{d}{sig.get(d, 0)}" for d in SIG_KEY_FIELDS]
    extra = {
        "spec": hashlib.sha256(
            repr(spec.key()).encode()
        ).hexdigest()[:16],
        "profile": profile,
        "kind": kind,
        "program": program,
        "mesh": mesh,
        "fingerprint": fingerprint or backend_fingerprint(),
    }
    parts += [f"{f}={extra[f]}" for f in EXTRA_KEY_FIELDS]
    return CacheKey("|".join(parts), kind)


class CompileCache:
    """The on-disk executable store. Thread-safe: `load`/`store` may be
    called concurrently from the serve loop and the warm thread (writes
    are tmp+fsync+rename; the last same-key writer wins whole)."""

    def __init__(self, directory: str, metrics=None) -> None:
        self.dir = directory
        os.makedirs(directory, exist_ok=True)
        self._metrics = metrics
        self._fingerprint = backend_fingerprint()
        # in-memory tallies (the bench and /debug read these; the
        # prometheus families mirror them when metrics is wired).
        # load_seconds is a BOUNDED window — a long-lived scheduler
        # whose regime churn outruns the program memos reloads entries
        # indefinitely, and an unbounded list would grow (and be
        # re-sorted per /debug/state scrape) forever
        self.hits = 0
        self.misses = 0
        self.load_seconds: "collections.deque[float]" = (
            collections.deque(maxlen=256)
        )
        self.serialize_unsupported = False
        # fallback for backends without executable serialization: JAX's
        # own persistent compilation cache, at the one place
        # utils/compilation_cache.py puts it (the environment's
        # directory, else the fixed in-checkout one) — never under this
        # tree, whose path changes with every state dir. Only when the
        # process has none yet: the CLI and the test conftest configure
        # it at startup.
        import jax

        if not jax.config.jax_compilation_cache_dir:
            from ..utils.compilation_cache import enable_compilation_cache

            enable_compilation_cache()

    # ---- entry framing ---------------------------------------------------

    def _path(self, key: CacheKey) -> str:
        return os.path.join(self.dir, key.name)

    def load(self, key: CacheKey) -> bytes | None:
        """The validated payload for `key`, or None (miss). Any framing
        violation — truncation, bit flips, a future format version, a
        key/fingerprint mismatch — logs loudly and reports a miss; the
        caller recompiles and overwrites the bad entry."""
        path = self._path(key)
        try:
            with open(path, "rb") as f:
                blob = f.read()
        except FileNotFoundError:
            return None
        except OSError as e:
            log.error("compile cache: cannot read %s: %s", path, e)
            return None
        head = len(_MAGIC) + 8
        if len(blob) < head + 4 or blob[: len(_MAGIC)] != _MAGIC:
            log.error(
                "compile cache: REFUSING %s: bad magic/truncated header "
                "(%d bytes) — recompiling", key.name, len(blob),
            )
            return None
        version, meta_len = struct.unpack_from("<II", blob, len(_MAGIC))
        if version != _VERSION:
            log.error(
                "compile cache: REFUSING %s: format version %d (this "
                "build writes %d) — recompiling", key.name, version,
                _VERSION,
            )
            return None
        body = blob[head:-4]
        (crc,) = struct.unpack_from("<I", blob, len(blob) - 4)
        if zlib.crc32(body) & 0xFFFFFFFF != crc:
            log.error(
                "compile cache: REFUSING %s: CRC mismatch (truncated or "
                "bit-flipped entry) — recompiling", key.name,
            )
            return None
        if meta_len > len(body):
            log.error(
                "compile cache: REFUSING %s: meta length %d exceeds "
                "body — recompiling", key.name, meta_len,
            )
            return None
        try:
            meta = json.loads(body[:meta_len].decode())
        except ValueError:
            log.error(
                "compile cache: REFUSING %s: unparseable meta — "
                "recompiling", key.name,
            )
            return None
        if meta.get("key") != key.text:
            log.error(
                "compile cache: REFUSING %s: key mismatch (hash "
                "collision or stale rename) — recompiling", key.name,
            )
            return None
        if meta.get("fingerprint") != self._fingerprint:
            # defense in depth: the fingerprint is part of the key (and
            # so of the filename), so this is a miss, not corruption
            log.warning(
                "compile cache: %s was built under %r, this process is "
                "%r — miss", key.name, meta.get("fingerprint"),
                self._fingerprint,
            )
            return None
        return body[meta_len:]

    def store(
        self, key: CacheKey, payload: bytes, build_seconds: float = 0.0
    ) -> bool:
        """Atomically write one entry: tmp file (unique per writer) +
        fsync + rename, exactly the PR 3 snapshot discipline — a torn
        write can never be observed, and concurrent same-key writers
        each land a whole entry (last rename wins)."""
        meta = json.dumps({
            "key": key.text,
            "fingerprint": self._fingerprint,
            "build_seconds": round(build_seconds, 3),
            "built_wall": _time.time(),
            "payload_bytes": len(payload),
        }).encode()
        body = meta + payload
        blob = (
            _MAGIC
            + struct.pack("<II", _VERSION, len(meta))
            + body
            + struct.pack("<I", zlib.crc32(body) & 0xFFFFFFFF)
        )
        tmp = os.path.join(
            self.dir,
            f".{key.name}.tmp.{os.getpid()}.{threading.get_ident()}",
        )
        try:
            if _faults.ARMED:
                # `cache_enospc` raises here (caught by the OSError
                # handler below — a refused store, never a crash);
                # `cache_torn` lands a TRUNCATED entry at the FINAL
                # path, as if a rename survived a crash its data did
                # not — load() must refuse it and recompile
                _faults.raise_enospc("cache_enospc")
                if _faults.torn_store():
                    with open(self._path(key), "wb") as f:
                        f.write(blob[: max(len(blob) // 2, 1)])
                    log.error(
                        "compile cache: fault-injected torn write of "
                        "%s", key.name,
                    )
                    return False
            with open(tmp, "wb") as f:
                f.write(blob)
                f.flush()
                os.fsync(f.fileno())
            os.replace(tmp, self._path(key))
            return True
        except OSError as e:
            log.error("compile cache: cannot store %s: %s", key.name, e)
            try:
                os.unlink(tmp)
            except OSError:
                pass
            return False

    # ---- bookkeeping -----------------------------------------------------

    def note_hit(self, seconds: float) -> None:
        self.hits += 1
        self.load_seconds.append(seconds)
        m = self._metrics
        if m is not None:
            m.compile_cache_hits.inc()
            m.compile_cache_loads.observe(seconds)

    def note_miss(self) -> None:
        self.misses += 1
        m = self._metrics
        if m is not None:
            m.compile_cache_misses.inc()

    def note_unsupported(self, err: BaseException) -> None:
        if not self.serialize_unsupported:
            self.serialize_unsupported = True
            log.warning(
                "compile cache: this backend cannot serialize "
                "executables (%s); falling back to the JAX persistent "
                "compilation cache", err,
            )

    def status(self) -> dict:
        """The /debug/state enrichment + bench artifact fields."""
        loads = sorted(self.load_seconds)
        return {
            "dir": self.dir,
            "fingerprint": self._fingerprint,
            "hits": self.hits,
            "misses": self.misses,
            "entries": sum(
                1 for n in os.listdir(self.dir) if n.endswith(".kscc")
            ) if os.path.isdir(self.dir) else 0,
            "serialize_unsupported": self.serialize_unsupported,
            "load_p50_s": round(loads[len(loads) // 2], 4) if loads else 0.0,
            "load_max_s": round(loads[-1], 4) if loads else 0.0,
        }


# Process-level memo of loaded executables: (entry name, payload sha)
# -> Compiled. One deserialize per entry per process — repeated
# same-process deserialization of one entry is both wasted work and,
# on this jaxlib's CPU backend, occasionally fails with "Symbols not
# found" (observed on the third load of a large carry-cycle executable;
# the first load is reliable). A REAL warm restart is a new process, so
# this memo never weakens the restart story; it makes in-process
# re-opens (standby handover in one test process, bench drives) cheap
# and deterministic. Bounded FIFO — executables are small host objects
# and the live ones are pinned by the scheduler's program memos anyway.
_LOADED_LOCK = threading.Lock()
_LOADED: dict = {}
_LOADED_CAP = 64

# Serializes the jax_enable_compilation_cache toggle around native
# AOT compiles (see load_or_compile): the flag is PROCESS-GLOBAL, and
# an unsynchronized read/toggle/restore between the serve loop and the
# warm thread could let one builder compile WITH the XLA cache enabled
# (storing the symbol-less corrupt payload the bypass exists to avoid)
# and then restore a stale False, disabling the cache for the rest of
# the process.
_NATIVE_COMPILE_LOCK = threading.Lock()


def clear_loaded_memo() -> None:
    """Tests only: force the next load to really deserialize."""
    with _LOADED_LOCK:
        _LOADED.clear()


def _avals_digest(args: tuple, kwargs: dict) -> str:
    """Deterministic digest of a call convention (aval shapes/dtypes +
    pytree structure). Part of the key's `program` field: one program
    object can be called under more than one convention (the diagnosis
    program with/without `pv_claimed`; the preemption program fed a
    CycleResult vs a CycleDecision), and each convention is a distinct
    executable — sharing a key would load the wrong one."""
    import jax

    leaves, treedef = jax.tree_util.tree_flatten((args, kwargs))
    sig = repr([
        (tuple(getattr(v, "shape", ()) or ()),
         str(getattr(v, "dtype", type(v).__name__)))
        for v in leaves
    ]) + repr(treedef)
    return hashlib.sha256(sig.encode()).hexdigest()[:12]


def _args_mesh_desc(args: tuple, kwargs: dict) -> str:
    """Sharding descriptor of a call's argument layout: "none" when
    every leaf is unsharded/single-device, else a short digest over the
    (mesh shape, partition spec) of EACH leaf in order ("-" for an
    unplaced one). Feeds the cache key's `mesh` field so sharded and
    unsharded builds of one program never alias a persistent entry —
    and neither do two sharded builds that place different arguments:
    an executable is compiled for its inputs' shardings and refuses
    any other at its first call."""
    import jax

    leaves, _treedef = jax.tree_util.tree_flatten((args, kwargs))
    parts: list[str] = []
    for v in leaves:
        sh = getattr(v, "sharding", None)
        if sh is None or len(sh.device_set) == 1:
            parts.append("-")  # unplaced, or on one device
            continue
        mesh = getattr(sh, "mesh", None)
        parts.append(
            f"{tuple(mesh.shape.items())}|{sh.spec!r}"
            if mesh is not None else repr(sh)  # a GSPMDSharding
        )
    if all(p == "-" for p in parts):
        return "none"
    return hashlib.sha256("||".join(parts).encode()).hexdigest()[:10]


def _execution_devices(low) -> list:
    """The devices `low` was lowered for: one for an unsharded program,
    the mesh's (in assignment order) for a sharded one. JAX 0.9's
    `deserialize_and_load` defaults `execution_devices` to EVERY device
    of the backend, so on a host with more than one a one-device
    executable would load as an N-way one and fail at its first call
    ("Expected args to execute_sharded_on_local_devices to have N
    shards")."""
    return list(low._lowering._device_list)


def _out_avals(low, compiled=None):
    """The output aval pytree of a lowered program, for chaining the
    next program's argument avals. A program partitioned over a mesh
    hands its outputs on SHARDED (the carry's `sbase` lives on
    'pods'), and a downstream executable compiled for unplaced —
    hence replicated — inputs refuses them at its first call
    ("compiled for input shardings that disagree"); so once the
    executable exists its output shardings ride the avals. One-device
    programs keep plain avals."""
    import jax

    if compiled is None or len(_execution_devices(low)) == 1:
        return jax.tree_util.tree_map(
            lambda o: jax.ShapeDtypeStruct(o.shape, o.dtype), low.out_info
        )
    return jax.tree_util.tree_map(
        lambda o, s: jax.ShapeDtypeStruct(o.shape, o.dtype, sharding=s),
        low.out_info, compiled.output_shardings,
    )


def _compile_natively(low):
    """Compile a Lowered with JAX's persistent compilation cache truly
    OUT of the loop. Toggling `jax_enable_compilation_cache` alone is
    not enough: `compilation_cache.is_cache_used()` memoizes its
    decision process-globally on the FIRST compile, so in any process
    that already compiled with the cache enabled the flag is dead — and
    a compile that LOADS from that cache returns an executable whose
    serialize() emits a symbol-less payload (the corruption this whole
    path exists to avoid; only programs over the cache's
    min_compile_time ever land there, which is why exactly the largest
    program's entry went bad). `reset_cache()` drops the memo so the
    disabled flag is actually consulted; a second reset afterwards lets
    the next ordinary jit compile re-evaluate with the restored flag.
    Caller holds _NATIVE_COMPILE_LOCK."""
    import jax

    try:
        from jax._src import compilation_cache as _jcc
    except Exception:  # pragma: no cover — jax internals moved  # schedlint: disable=RB001 -- degraded-but-correct: without the internal module the flag toggle still applies
        _jcc = None
    prev = jax.config.jax_enable_compilation_cache
    try:
        jax.config.update("jax_enable_compilation_cache", False)
        if _jcc is not None:
            try:
                _jcc.reset_cache()
            except Exception:  # pragma: no cover  # schedlint: disable=RB001 -- best-effort memo drop; the verification deserialize downstream catches a poison build
                pass
        return low.compile()
    finally:
        jax.config.update("jax_enable_compilation_cache", prev)
        if _jcc is not None:
            try:
                _jcc.reset_cache()
            except Exception:  # pragma: no cover  # schedlint: disable=RB001 -- best-effort memo drop on the restore side
                pass


def load_or_compile(
    fn,
    cache: CompileCache | None,
    spec,
    profile: str,
    kind: str,
    args: tuple = (),
    kwargs: dict | None = None,
) -> tuple[Any, str, float, Any]:
    """AOT-compile `fn` (a `_jit`-built program) for the exact
    `args`/`kwargs` avals, loading the serialized executable from
    `cache` when a valid entry exists.

    Returns `(compiled_or_None, source, seconds, out_sds)` with source
    one of "cache" (deserialized from disk), "cold" (compiled here), or
    "unsupported" (this program cannot be AOT-handled — caller keeps the
    plain jit path); `out_sds` is the output aval pytree (for chaining
    downstream programs' argument avals), or None when lowering failed.
    The in_tree/out_tree a deserialize needs are not serializable, so a
    load still TRACES the program (`fn.lower`) — sub-second — and skips
    only the XLA compile (the 8.8-16.8 s part)."""
    import jax
    from jax.experimental import serialize_executable as _se

    kwargs = kwargs or {}
    key = cache_key(
        spec, profile, kind,
        f"{program_name(fn)}+{_avals_digest(args, kwargs)}",
        mesh=_args_mesh_desc(args, kwargs),
    )
    t0 = _time.perf_counter()
    try:
        low = fn.lower(*args, **kwargs)
    except Exception as e:
        log.warning(
            "compile cache: cannot lower %s (%s); keeping the jit path",
            key.name, e,
        )
        return None, "unsupported", 0.0, None
    out_sds = _out_avals(low)
    payload = cache.load(key) if cache is not None else None
    if payload is not None:
        memo_key = (key.name, hashlib.sha256(payload).hexdigest())
        with _LOADED_LOCK:
            compiled = _LOADED.get(memo_key)
        if compiled is not None:
            dt = _time.perf_counter() - t0
            cache.note_hit(dt)
            return compiled, "cache", dt, _out_avals(low, compiled)
        try:
            _flat, in_tree = jax.tree_util.tree_flatten(low.args_info)
            compiled = _se.deserialize_and_load(
                payload, in_tree, low.out_tree,
                execution_devices=_execution_devices(low),
            )
            dt = _time.perf_counter() - t0
            cache.note_hit(dt)
            with _LOADED_LOCK:
                _LOADED[memo_key] = compiled
                while len(_LOADED) > _LOADED_CAP:
                    _LOADED.pop(next(iter(_LOADED)))
            return compiled, "cache", dt, _out_avals(low, compiled)
        except Exception as e:
            log.error(
                "compile cache: entry %s failed to deserialize (%s); "
                "recompiling", key.name, e,
            )
    will_store = cache is not None and not cache.serialize_unsupported
    try:
        if will_store:
            # compile NATIVELY, bypassing JAX's persistent XLA cache
            # for this one build: serialize() of an executable that
            # compile() loaded from that cache emits a payload missing
            # its symbol definitions ("Symbols not found" on a later
            # deserialize — reproduced: the cache-loaded build's
            # payload is ~half the size of the native one). Our own
            # entry IS the persistent layer here, so the XLA-cache
            # bypass costs one native compile exactly where we are
            # about to make it durable ourselves.
            with _NATIVE_COMPILE_LOCK:
                compiled = _compile_natively(low)
        else:
            compiled = low.compile()
    except Exception as e:
        log.warning(
            "compile cache: AOT compile of %s failed (%s); keeping the "
            "jit path", key.name, e,
        )
        return None, "unsupported", 0.0, out_sds
    dt = _time.perf_counter() - t0
    out_sds = _out_avals(low, compiled)
    if cache is not None:
        cache.note_miss()
    if will_store:
        try:
            data, _in_tree, _out_tree = _se.serialize(compiled)
        except Exception as e:
            cache.note_unsupported(e)
            return compiled, "cold", dt, out_sds
        # verify BEFORE persisting: a payload that cannot deserialize
        # (defense in depth against serialize-of-a-cache-loaded
        # executable sneaking past _compile_natively) must never become
        # a poison entry that every later restart trips over loudly
        try:
            _flat, in_tree = jax.tree_util.tree_flatten(low.args_info)
            _se.deserialize_and_load(
                data, in_tree, low.out_tree,
                execution_devices=_execution_devices(low),
            )
        except Exception as e:
            log.error(
                "compile cache: NOT storing %s — freshly serialized "
                "payload failed its verification deserialize (%s); "
                "the in-process executable still serves", key.name,
                str(e)[:200],
            )
            return compiled, "cold", dt, out_sds
        if cache.store(key, data, build_seconds=dt):
            # later same-process loads of this entry reuse the
            # executable we just compiled instead of deserializing
            memo_key = (
                key.name, hashlib.sha256(data).hexdigest()
            )
            with _LOADED_LOCK:
                _LOADED[memo_key] = compiled
                while len(_LOADED) > _LOADED_CAP:
                    _LOADED.pop(next(iter(_LOADED)))
    return compiled, "cold", dt, out_sds


class CompileWarmer:
    """The speculative-precompilation thread: a queue of build thunks,
    drained by one lazy daemon thread so a build NEVER runs on the
    scheduling loop. Jobs are deduplicated by key while queued or
    running (a drifting workload re-triggers the same adjacent regime
    every cycle until it lands). Failures are logged and swallowed —
    speculation is an optimization, a bad prediction must cost nothing
    but the wasted build."""

    def __init__(self, metrics=None) -> None:
        self._metrics = metrics
        self._q: _queue.Queue = _queue.Queue()
        self._inflight: set = set()
        self._lock = threading.Lock()
        self._thread: threading.Thread | None = None
        self._stop = threading.Event()
        self.built = 0
        self.failed = 0

    def enqueue_build(self, key, thunk: Callable[[], None]) -> bool:
        """Enqueue one speculative build; False when the same key is
        already queued or building."""
        with self._lock:
            if self._stop.is_set() or key in self._inflight:
                return False
            self._inflight.add(key)
            # the put rides INSIDE the lock: the worker's drain-exit
            # checks queue emptiness under the same lock, so an item is
            # either visible to the exiting worker (queue non-empty ->
            # it keeps running) or enqueued after the worker cleared
            # self._thread (-> a fresh worker starts here)
            self._q.put((key, thunk))
            if self._thread is None:
                self._thread = threading.Thread(
                    target=self._run,
                    name="compile-warmer",
                    daemon=True,
                )
                self._thread.start()
        return True

    def _run(self) -> None:
        while not self._stop.is_set():
            try:
                job = self._q.get(timeout=5.0)
                if job is None:  # stop()'s wake-up
                    return
                key, thunk = job
            except _queue.Empty:
                # drained: exit instead of polling forever — a process
                # that constructs many Schedulers must not accumulate
                # idle warmer threads. The next submit starts a fresh
                # worker (thread cleared under the submit lock, so no
                # enqueued job can be stranded).
                with self._lock:
                    if self._q.empty():
                        self._thread = None
                        return
                continue
            try:
                thunk()
                # counted under the submit lock: the worker respawns, so
                # a successor thread (or a reader polling built/failed
                # between respawns) must see each increment whole
                with self._lock:
                    self.built += 1
                m = self._metrics
                if m is not None:
                    m.compile_cache_speculative.inc()
            except Exception:
                with self._lock:
                    self.failed += 1
                log.exception(
                    "compile warmer: speculative build %r failed "
                    "(prediction discarded)", key,
                )
            finally:
                with self._lock:
                    self._inflight.discard(key)
                self._q.task_done()

    def idle(self) -> bool:
        with self._lock:
            return not self._inflight

    def join(self, timeout: float = 30.0) -> bool:
        """Wait for the queue to drain (tests / warm_cache.py)."""
        deadline = _time.monotonic() + timeout
        while _time.monotonic() < deadline:
            if self.idle():
                return True
            _time.sleep(0.02)
        return False

    def stop(self, timeout: float = 0.0) -> bool:
        """Refuse new jobs and wake the worker; with `timeout`, also
        wait that long for the build it is in (an XLA compile cannot be
        interrupted). False while the worker is still building — the
        caller must then not finalize the interpreter under it
        (cmd/main.py)."""
        self._stop.set()
        with self._lock:
            thread = self._thread
            self._q.put(None)  # wake a worker blocked on the empty queue
        if thread is None:
            return True
        if timeout > 0:
            thread.join(timeout)
        return not thread.is_alive()
