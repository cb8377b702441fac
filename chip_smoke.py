#!/usr/bin/env python3
"""chip_smoke: the served scheduler, once, on the chip.

Starts `python -m k8s_scheduler_tpu` as the ONE process that holds the
chip and drives it over gRPC through `service.client.SchedulerAgent`,
the way a cluster-side agent would:

- small phase — bench cell 2 (1,000 pending pods with selectors and
  tolerations on 100 nodes, 30% tainted), one cycle, every binding
  checked by `oracle.validate_rounds_assignment`;
- full phase — bench cell 4 (5,000 nodes, 12,000 bound pods, 10,000
  pending with affinity / anti-affinity / spread / selectors /
  tolerations / priorities), three cycles with evictions applied and
  2,000 fresh pods upserted between them, checked in aggregate with
  numpy from the objects this script sent.

Each phase gets its own server child, one after the other. This parent
is a plain client: it never initialises a JAX backend (asserted), and
takes the device from the child's `build:` line. After the last cycle
it fails unless the degradation ladder is on `normal`, no program retry
strike, fetch failure or wedge/degraded anomaly was counted, and the
child exits 0 on SIGTERM with its durable state sealed. Any failed
check raises: the script exits non-zero and the contract line — the
last line of stdout, `{"ok": true, "device": {...}}` — is not printed.

    python chip_smoke.py              # one chip (what the driver runs)
    python chip_smoke.py --chips 4    # shard-invariance at size, 4 chips
    JAX_PLATFORMS=cpu python chip_smoke.py --rehearse   # no chip, cut
        size, "ok": false — a rehearsal is never reported as a chip run

Earlier stdout lines are one JSON object each: set-up facts (counts,
cache entries, wall seconds), not metrics.
"""

from __future__ import annotations

import argparse
import contextlib
import functools
import json
import os
import shlex
import shutil
import signal
import socket
import subprocess
import sys
import time
import urllib.error
import urllib.request

import numpy as np

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, ROOT)

import bench_suite  # noqa: E402
from k8s_scheduler_tpu import oracle  # noqa: E402
from k8s_scheduler_tpu.service.client import (  # noqa: E402
    SchedulerAgent,
    SchedulerClient,
)
from k8s_scheduler_tpu.utils.compilation_cache import (  # noqa: E402
    compilation_cache_dir,
)

# the sticky pads that keep cell 4 in ONE compile regime while binds fold
# into the existing set — and what step 2 showed is needed on top:
# tests/test_tpu_compile.py timed a regime's six programs at ~280 s of
# compile, so (a) the hysteresis holds the 10k-pod regime when the next
# cycles bring 2,000 (a flip mid-run is a second cold start; 50% holds
# it at the rehearsal's few hundred pods as well), and (b)
# the speculative build of the ADJACENT regime, which the default
# config starts after a cycle that fills its pad bucket, is off: it
# could not finish inside this run, and would leave the caches with a
# number of entries that depends on when SIGTERM caught it
SMOKE_YAML = (
    "padExisting: 32768\npadPodsPerNode: 32\n"
    "padHysteresisPct: 50\nspeculativeCompile: false\n"
)

# (nodes, bound, pending, fresh per later cycle)
FULL_SIZE = (5000, 12000, 10000, 2000)
REHEARSE_SIZE = (300, 720, 600, 120)

START_TIMEOUT_S = 300.0
# step 2 (tests/test_tpu_compile.py) compiled cell 4's regime in ~280 s
# on 8 sandbox cores; the first Cycle also AOT-verifies and serializes
# each program. Three times that, not client.py's 120 s.
FIRST_CYCLE_TIMEOUT_S = 900.0
LATER_CYCLE_TIMEOUT_S = 300.0
DEADLINE_S = 1150.0  # the contract gives 1200 s, compilation included

_T0 = time.monotonic()


def say(**fields) -> None:
    print(json.dumps(fields), flush=True)


def check(cond: bool, what: str) -> None:
    if not cond:
        raise SystemExit(f"chip_smoke: FAILED: {what}")


class StrictAgent(SchedulerAgent):
    """An agent whose recovery path is a failure: `relist()` is what
    the stock agent does after an RPC error or a server restart, and a
    smoke that quietly replayed its state would pass over both."""

    def relist(self) -> None:
        raise SystemExit(
            "chip_smoke: FAILED: the agent fell into relist() — an RPC "
            "failed or timed out, or the server lost its state"
        )


# ---- the server child -----------------------------------------------------


def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def _http(port: int, path: str) -> tuple[int, bytes]:
    try:
        with urllib.request.urlopen(
            f"http://127.0.0.1:{port}{path}", timeout=10
        ) as r:
            return r.status, r.read()
    except urllib.error.HTTPError as e:
        return e.code, e.read()


def _entries(directory: str, suffix: str) -> int:
    if not os.path.isdir(directory):
        return 0
    return sum(1 for n in os.listdir(directory) if n.endswith(suffix))


class Server:
    """One `python -m k8s_scheduler_tpu` child and what it printed."""

    def __init__(self, name: str, out: str, aot_dir: str, extra=()) -> None:
        self.name = name
        self.grpc_port, self.http_port = _free_port(), _free_port()
        state = os.path.join(out, f"state-{name}")
        shutil.rmtree(state, ignore_errors=True)  # never restore a journal
        yaml_path = os.path.join(out, "smoke.yaml")
        with open(yaml_path, "w") as f:
            f.write(SMOKE_YAML)
        self.log_path = os.path.join(out, f"server-{name}.log")
        self._log = open(self.log_path, "w")
        # the environment as given: no platform variable added or removed
        self.proc = subprocess.Popen(
            [
                sys.executable, "-m", "k8s_scheduler_tpu",
                "--address", f"127.0.0.1:{self.grpc_port}",
                "--http-port", str(self.http_port),
                "--state-dir", state,
                "--config", yaml_path,
                "--compile-cache-dir", aot_dir,
                *extra,
            ],
            cwd=ROOT, stdout=self._log, stderr=subprocess.STDOUT,
        )

    def log(self) -> str:
        with open(self.log_path, errors="replace") as f:
            return f.read()

    def line(self, prefix: str) -> dict:
        """The k=v fields of the child's first `prefix` line."""
        for ln in self.log().splitlines():
            if ln.startswith(prefix):
                return dict(
                    kv.split("=", 1) for kv in shlex.split(ln[len(prefix):])
                )
        raise SystemExit(
            f"chip_smoke: FAILED: server {self.name} printed no "
            f"{prefix!r} line:\n{self.log()[-4000:]}"
        )

    def wait_healthy(self) -> None:
        deadline = time.monotonic() + START_TIMEOUT_S
        while time.monotonic() < deadline:
            if self.proc.poll() is not None:
                raise SystemExit(
                    f"chip_smoke: FAILED: server {self.name} exited "
                    f"{self.proc.returncode} at start:\n{self.log()[-4000:]}"
                )
            try:
                if _http(self.http_port, "/healthz")[0] == 200:
                    return
            except OSError:
                pass  # not listening yet
            time.sleep(0.5)
        raise SystemExit(
            f"chip_smoke: FAILED: server {self.name} not healthy in "
            f"{START_TIMEOUT_S:g}s:\n{self.log()[-4000:]}"
        )

    def started(self, require_tpu: bool, chips: int) -> dict:
        """Wait for /healthz, then read what the child said of itself —
        and refuse a child on the wrong device BEFORE any cycle: on a
        machine without a chip the smoke fails in seconds, not after
        compiling cell 4 for the CPU."""
        self.wait_healthy()
        build, encoder = self.line("build: "), self.line("encoder: ")
        say(server=self.name, build=build, encoder=encoder)
        if require_tpu:
            check(
                build["platform"] == "tpu",
                f"the server runs on {build['platform']!r}, not a TPU",
            )
        check(
            int(build["device_count"]) >= chips,
            f"--chips {chips} but the server sees {build['device_count']}",
        )
        # the numpy loops native/__init__.py falls back to in silence are
        # the host-side twin of running on the CPU
        check(
            encoder == {"native": "1", "pod_rows_into": "1"},
            f"the numpy fallback encoder is active: {encoder}",
        )
        return build

    def metrics(self) -> dict[str, float]:
        """/metrics as {sample name with labels: value}."""
        status, body = _http(self.http_port, "/metrics")
        check(status == 200, f"/metrics answered {status}")
        out = {}
        for ln in body.decode().splitlines():
            if ln and not ln.startswith("#"):
                key, _, val = ln.rpartition(" ")
                out[key] = float(val)
        return out

    def no_hidden_failure(self) -> dict:
        """The product serves around device failures by design
        (_Resilient retries, the ladder steps down); a smoke must not."""
        status, body = _http(self.http_port, "/healthz")
        check(status == 200, f"/healthz answered {status}")
        health = json.loads(body)
        rung = health["degradation"]
        check(
            rung["name"] == "normal" and rung["degradations"] == 0,
            f"degradation ladder left normal: {rung}",
        )
        check(not health.get("degraded"), f"/healthz degraded: {health}")
        m = self.metrics()

        def total(prefix):
            return sum(v for k, v in m.items() if k.startswith(prefix))

        counts = {
            "retry_strikes": total("scheduler_program_retry_strikes_total"),
            "fetch_failures": total("scheduler_fetch_failures_total"),
            "wedge_precursor": total(
                'scheduler_anomalies_total{class="wedge_precursor"}'
            ),
            "degraded": total('scheduler_anomalies_total{class="degraded"}'),
        }
        check(not any(counts.values()), f"hidden failure counted: {counts}")
        return {"ladder": rung["name"], **counts}

    def stop(self) -> None:
        """SIGTERM; the child must exit 0 with its state sealed."""
        self.proc.send_signal(signal.SIGTERM)
        try:
            rc = self.proc.wait(timeout=120)
        finally:
            self.kill()
        check(rc == 0, f"server {self.name} exited {rc} on SIGTERM")
        check(
            "durable state sealed" in self.log(),
            f"server {self.name} did not seal its state",
        )

    def kill(self) -> None:
        if self.proc.poll() is None:
            self.proc.kill()
            self.proc.wait()
        self._log.close()


@contextlib.contextmanager
def serving(name, chips=1, extra=(), *, out, aot_dir, require_tpu):
    """One server child for the block: started and checked, stopped
    with SIGTERM when the block ends well, killed whatever happens.
    main() binds the keyword arguments and hands the phases `serve`."""
    server = Server(name, out, aot_dir, extra)
    try:
        build = server.started(require_tpu, chips)
        yield server, build
        server.stop()
    finally:
        server.kill()


# ---- driving one cluster --------------------------------------------------


class Cluster:
    """The agent-side truth: what was sent, what came back."""

    def __init__(self, server: Server, nodes, existing) -> None:
        self.server = server
        self.nodes = nodes
        self.node_index = {n.name: i for i, n in enumerate(nodes)}
        self.pods = {}  # uid -> Pod, every pod ever sent
        self.bound = {}  # uid -> node index
        self.pending = set()  # uids sent as pending and not bound yet
        self.new_bindings: list[tuple[str, str]] = []
        self.new_evictions: list[tuple[str, str]] = []
        # default channel limits: the CycleResponse for 10,000 pods (a
        # binding and an event each) is 0.9 MB, under gRPC's 4 MiB
        self.client = SchedulerClient(f"127.0.0.1:{server.grpc_port}")
        self.agent = StrictAgent(
            self.client,
            bind_applier=lambda uid, _n, _ns, node: self.new_bindings.append(
                (uid, node)
            ),
            evict_applier=lambda uid, node: self.new_evictions.append(
                (uid, node)
            ),
        )
        with self.agent.batched():
            for n in nodes:
                self.agent.upsert_node(n)
            for pod, node in existing:
                self.pods[pod.uid] = pod
                self.bound[pod.uid] = self.node_index[node]
                self.agent.upsert_pod(pod, bound_node=node)
        self.requests = {}  # uid -> (cpu millis, memory, pods)
        self.alloc = np.array([
            [n.status.allocatable.get(r, 0.0) for r in ("cpu", "memory", "pods")]
            for n in nodes
        ])

    def submit(self, pods) -> None:
        with self.agent.batched():
            for p in pods:
                check(p.uid not in self.pods, f"duplicate pod uid {p.uid}")
                self.pods[p.uid] = p
                self.pending.add(p.uid)
                self.agent.upsert_pod(p)

    def apply_evictions(self) -> int:
        """What the cluster does with an eviction: the pod goes away."""
        evicted, self.new_evictions = self.new_evictions, []
        with self.agent.batched():
            for uid, _node in evicted:
                self.agent.delete_pod(uid)
        return len(evicted)

    def _usage(self, uids) -> np.ndarray:
        use = np.zeros_like(self.alloc)
        for uid in uids:
            if uid not in self.requests:
                r = self.pods[uid].resource_requests()
                self.requests[uid] = (
                    r.get("cpu", 0.0), r.get("memory", 0.0), r["pods"]
                )
            use[self.bound[uid]] += self.requests[uid]
        return use

    def cycle(self, timeout: float) -> dict:
        """One Cycle through the agent (which confirms the bindings
        itself), then the aggregate checks."""
        self.new_bindings = []
        self.agent.cycle_timeout = timeout
        pending_before = set(self.pending)
        misses0 = self.server.metrics().get(
            "scheduler_compile_cache_misses_total", 0.0
        )
        t0 = time.monotonic()
        resp = self.agent.run_cycle()
        seconds = time.monotonic() - t0
        st = resp.stats
        uids = [uid for uid, _ in self.new_bindings]
        check(len(uids) == len(resp.bindings), "agent dropped a binding")
        check(len(set(uids)) == len(uids), "a pod was bound twice in a cycle")
        for uid, node in self.new_bindings:
            check(uid in pending_before, f"binding for non-pending pod {uid}")
            check(node in self.node_index, f"binding to unknown node {node}")
        check(st.scheduled == len(uids), f"scheduled {st.scheduled} != bindings")
        check(
            st.scheduled + st.unschedulable == st.attempted
            and st.bind_errors == 0 and st.gang_dropped == 0,
            f"bound + unschedulable != attempted: {st}",
        )
        check(st.attempted <= len(pending_before), "attempted more than pending")
        # evictions: bound pods, each once; the server already counts
        # them gone, so capacity is (bound before - evicted + bound now)
        victims = [uid for uid, _ in self.new_evictions]
        check(len(set(victims)) == len(victims), "a pod was evicted twice")
        for uid, node in self.new_evictions:
            check(
                self.bound.get(uid) == self.node_index.get(node),
                f"eviction of {uid} from {node}: not bound there",
            )
            del self.bound[uid]
        touched = np.zeros(len(self.nodes), bool)
        for uid, node in self.new_bindings:
            self.bound[uid] = self.node_index[node]
            self.pending.discard(uid)
            touched[self.bound[uid]] = True
        use = self._usage(self.bound)
        over = (use > self.alloc * (1 + 1e-5) + 1e-5).any(axis=1)
        check(
            not (over & touched).any(),
            f"{int((over & touched).sum())} nodes over allocatable after "
            f"binding: {np.flatnonzero(over & touched)[:5].tolist()}",
        )
        return {
            "attempted": st.attempted, "bound": st.scheduled,
            "unschedulable": st.unschedulable,
            "evictions": len(victims), "preemptors": st.preemptors,
            "nominations": len(resp.nominations),
            "response_bytes": resp.ByteSize(),
            "programs_compiled": self.server.metrics().get(
                "scheduler_compile_cache_misses_total", 0.0
            ) - misses0,
            # nodes the generator itself overcommitted (cell 4 places its
            # 12,000 bound pods round-robin, capacity unseen) and that
            # received nothing: not the scheduler's doing
            "nodes_over_untouched": int((over & ~touched).sum()),
            "wall_seconds": round(seconds, 3),
        }

    def close(self) -> None:
        self.client.close()


# ---- phases ---------------------------------------------------------------


def small_phase(serve, seed: int) -> dict:
    nodes, pending, _existing, _groups = bench_suite.make_config_workload(
        2, seed
    )
    with serve("small") as (server, build):
        cluster = Cluster(server, nodes, [])
        cluster.submit(pending)
        facts = cluster.cycle(FIRST_CYCLE_TIMEOUT_S)
        check(facts["attempted"] == len(pending), "not every pod attempted")
        assignment = np.array(
            [cluster.bound.get(p.uid, -1) for p in pending], np.int64
        )
        violations = oracle.validate_rounds_assignment(
            nodes, pending, assignment
        )
        check(not violations, f"validator: {violations[:5]}")
        say(
            phase="small", nodes=len(nodes), bound_pods=0,
            pending=len(pending), validator_violations=0,
            first_cycle=facts, **server.no_hidden_failure(),
        )
        cluster.close()
    return build


def cell4(size, seed: int):
    """Bench cell 4's cluster, whole or (rehearsal) its first objects."""
    n_nodes, n_bound, n_pending, _fresh = size
    nodes, pending, existing, _g = bench_suite.make_config_workload(4, seed)
    nodes = nodes[:n_nodes]
    existing = [
        (p, nodes[i % n_nodes].name)
        for i, (p, _node) in enumerate(existing[:n_bound])
    ]
    return nodes, pending[:n_pending], existing


def full_phase(serve, seed: int, size) -> None:
    nodes, pending, existing = cell4(size, seed)
    with serve("full") as (server, _build):
        t0 = time.monotonic()
        cluster = Cluster(server, nodes, existing)
        cluster.submit(pending)
        load_s = time.monotonic() - t0
        cycles = [cluster.cycle(FIRST_CYCLE_TIMEOUT_S)]
        check(
            cycles[0]["attempted"] == len(pending), "not every pod attempted"
        )
        for k in (1, 2):
            cluster.apply_evictions()
            fresh, _g = bench_suite.make_config_pending(
                4, seed + k, count=size[3], name_prefix=f"fresh{k}"
            )
            cluster.submit(fresh)
            cycles.append(cluster.cycle(LATER_CYCLE_TIMEOUT_S))
            check(
                cycles[-1]["attempted"] >= len(fresh),
                "fresh pods not attempted",
            )
        check(sum(c["bound"] for c in cycles) > 0, "no pod was bound")
        # the cell is built so high-priority pods must preempt
        # (bench_suite.make_config_workload: small nodes, a low-priority
        # workload on most capacity)
        check(
            sum(c["evictions"] for c in cycles) > 0, "no eviction was issued"
        )
        say(
            phase="full", nodes=len(nodes), bound_pods=len(existing),
            pending=[len(pending), size[3], size[3]],
            load_seconds=round(load_s, 3), first_cycle=cycles[0],
            later_cycles=cycles[1:], **server.no_hidden_failure(),
        )
        cluster.close()


def sharded_phase(serve, seed: int, size, chips: int) -> dict:
    """The shard-invariance contract of tests/test_shard_invariance.py at
    size, on real chips: cell 4's first cycle under --shard-devices
    `chips` and under 0 must bind identically."""
    nodes, pending, existing = cell4(size, seed)
    runs, build = {}, {}
    for devices in (chips, 0):
        with serve(
            f"shard{devices}", chips, ("--shard-devices", str(devices))
        ) as (server, build):
            cluster = Cluster(server, nodes, existing)
            cluster.submit(pending)
            facts = cluster.cycle(FIRST_CYCLE_TIMEOUT_S)
            m = server.metrics()
            facts["shard_devices"] = m["scheduler_shard_devices"]
            facts["collective_payload_bytes"] = sum(
                v for k, v in m.items()
                if k.startswith("scheduler_collective_payload_bytes")
            )
            if devices:
                # "everything on the first chip" must not pass
                check(
                    facts["shard_devices"] == chips,
                    f"scheduler_shard_devices = {facts['shard_devices']}",
                )
                check(
                    facts["collective_payload_bytes"] > 0,
                    "sharded cycle moved no collective payload",
                )
            runs[devices] = sorted(cluster.new_bindings)
            say(
                phase=f"shard_devices={devices}", nodes=len(nodes),
                bound_pods=len(existing), pending=len(pending),
                first_cycle=facts, **server.no_hidden_failure(),
            )
            cluster.close()
    check(len(runs[chips]) > 0, "sharded run bound nothing")
    check(
        runs[chips] == runs[0],
        f"sharded and unsharded bindings differ: {len(runs[chips])} vs "
        f"{len(runs[0])} bindings",
    )
    say(phase="shard_invariance", bindings_equal=True, count=len(runs[0]))
    return build


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument(
        "--chips", type=int, default=1, choices=(1, 4),
        help="4: run ONLY the sharded-vs-unsharded comparison of the full "
        "phase's first cycle (the driver never passes this)",
    )
    ap.add_argument(
        "--rehearse", action="store_true",
        help="cut the full phase to a few hundred objects, accept a "
        'non-TPU server, and print "ok": false on the last line',
    )
    args = ap.parse_args()

    signal.signal(signal.SIGALRM, lambda *_: check(
        False, f"not done in {DEADLINE_S:g}s"
    ))
    if not args.rehearse:
        signal.alarm(int(DEADLINE_S))

    out = os.path.join(ROOT, "chiprun_out", "chip_smoke")
    os.makedirs(out, exist_ok=True)
    jax_cache = compilation_cache_dir()
    aot_dir = os.path.join(jax_cache, "aot") if jax_cache else "off"

    def cache_entries():
        return {
            "jax": _entries(jax_cache, "-cache"),
            "aot": _entries(aot_dir, ".kscc"),
        }

    before = cache_entries()
    say(compile_cache={"jax": jax_cache, "aot": aot_dir},
        entries_before=before)
    size = REHEARSE_SIZE if args.rehearse else FULL_SIZE
    serve = functools.partial(
        serving, out=out, aot_dir=aot_dir, require_tpu=not args.rehearse
    )
    if args.chips == 4:
        build = sharded_phase(serve, args.seed, size, 4)
    else:
        build = small_phase(serve, args.seed)
        full_phase(serve, args.seed, size)
    say(entries_before=before, entries_after=cache_entries(),
        total_seconds=round(time.monotonic() - _T0, 1))
    # this parent is a client: the chip belonged to the child alone
    if "jax" in sys.modules:
        from jax._src import xla_bridge

        check(
            not xla_bridge.backends_are_initialized(),
            "the smoke's parent initialised a JAX backend",
        )
    print(json.dumps({
        "ok": not args.rehearse,
        "device": {
            "platform": build["platform"],
            "kind": build["device_kind"],
            "count": int(build["device_count"]),
        },
    }), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
