"""The server child: start, wait for health, read what it says, stop.

Copied from `chip_smoke.py` (`Server`, `serving`, `no_hidden_failure`)
so that a later PR cannot change the yardstick by editing the smoke.
The child is `benchmark/traced_server.py`, which calls the `main()` of
`python -m k8s_scheduler_tpu` unchanged; it is the one process that
holds the chip.
"""

from __future__ import annotations

import json
import os
import shlex
import signal
import socket
import subprocess
import sys
import time
import urllib.error
import urllib.request

START_TIMEOUT_S = 300.0
STOP_TIMEOUT_S = 120.0


class BenchError(Exception):
    """A run that must print no contract line."""


def free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


class Server:
    """One scheduler child and what it printed."""

    def __init__(self, root: str, workdir: str, yaml_path: str,
                 aot_dir: str, jax_cache_dir: str, traced: bool) -> None:
        self.grpc_port, self.http_port = free_port(), free_port()
        self.state_dir = os.path.join(workdir, "state")
        self.trace_dir = os.path.join(workdir, "trace")
        self.log_path = os.path.join(workdir, "server.log")
        self._log = open(self.log_path, "w")
        env = dict(os.environ)
        # the program takes its JAX cache from this variable when set:
        # one fixed directory inside the checkout, whatever the machine
        # or the caller had set
        env["JAX_COMPILATION_CACHE_DIR"] = jax_cache_dir
        env["PYTHONPATH"] = root + os.pathsep + env.get("PYTHONPATH", "")
        # traced_server.py calls the program's main() unchanged; it is
        # the child in every run because only the process that holds the
        # chip can read its peak memory. Tracing is armed under --trace 1
        entry = [os.path.join(root, "benchmark", "traced_server.py")]
        if traced:
            entry += ["--bench-trace-dir", self.trace_dir]
        self.proc = subprocess.Popen(
            [
                sys.executable, *entry,
                "--address", f"127.0.0.1:{self.grpc_port}",
                "--http-port", str(self.http_port),
                "--state-dir", self.state_dir,
                "--config", yaml_path,
                "--compile-cache-dir", aot_dir,
            ],
            cwd=root, env=env, stdout=self._log, stderr=subprocess.STDOUT,
        )

    def log(self) -> str:
        with open(self.log_path, errors="replace") as f:
            return f.read()

    def fail(self, what: str) -> BenchError:
        return BenchError(f"{what}\n--- server log tail ---\n{self.log()[-4000:]}")

    def line(self, prefix: str) -> dict:
        """The k=v fields of the child's first `prefix` line."""
        for ln in self.log().splitlines():
            if ln.startswith(prefix):
                return dict(
                    kv.split("=", 1) for kv in shlex.split(ln[len(prefix):])
                )
        raise self.fail(f"the server printed no {prefix!r} line")

    def http(self, path: str, timeout: float = 30.0) -> tuple[int, bytes]:
        try:
            with urllib.request.urlopen(
                f"http://127.0.0.1:{self.http_port}{path}", timeout=timeout
            ) as r:
                return r.status, r.read()
        except urllib.error.HTTPError as e:
            return e.code, e.read()

    def wait_healthy(self) -> None:
        deadline = time.monotonic() + START_TIMEOUT_S
        while time.monotonic() < deadline:
            if self.proc.poll() is not None:
                raise self.fail(
                    f"the server exited {self.proc.returncode} at start"
                )
            try:
                if self.http("/healthz", 5.0)[0] == 200:
                    return
            except OSError:
                pass  # not listening yet
            time.sleep(0.2)
        raise self.fail(f"the server was not healthy in {START_TIMEOUT_S:g}s")

    def started(self, require_tpu: bool, chips: int) -> dict:
        """Health, then the device the child holds: refused BEFORE any
        cycle, so a machine without a chip fails in seconds."""
        self.wait_healthy()
        build, encoder = self.line("build: "), self.line("encoder: ")
        if require_tpu and build["platform"] != "tpu":
            raise self.fail(
                f"the server runs on {build['platform']!r}, not a TPU"
            )
        if int(build["device_count"]) < chips:
            raise self.fail(
                f"the cell asks for {chips} chips, the server sees "
                f"{build['device_count']}"
            )
        if encoder != {"native": "1", "pod_rows_into": "1"}:
            raise self.fail(f"the numpy fallback encoder is active: {encoder}")
        return build

    def metrics(self) -> dict[str, float]:
        """/metrics as {sample name with labels: value}."""
        status, body = self.http("/metrics")
        if status != 200:
            raise self.fail(f"/metrics answered {status}")
        out = {}
        for ln in body.decode().splitlines():
            if ln and not ln.startswith("#"):
                key, _, val = ln.rpartition(" ")
                out[key] = float(val)
        return out

    def flight_records(self, last: int) -> list[dict]:
        status, body = self.http(f"/debug/flightrecorder?last={last}")
        if status != 200:
            raise self.fail(f"/debug/flightrecorder answered {status}")
        return json.loads(body)["cycles"]

    def trace_events(self, last: int) -> list[dict] | None:
        """The Chrome-trace events of `/debug/traces`: the last `last`
        cycles' lanes and every span the ring still holds. None from a
        program that does not serve them: its span metrics are left
        out, the run is not failed."""
        status, body = self.http(f"/debug/traces?last={last}")
        if status != 200:
            return None
        return json.loads(body).get("traceEvents")

    def health(self) -> dict:
        status, body = self.http("/healthz")
        if status != 200:
            raise self.fail(f"/healthz answered {status}")
        return json.loads(body)

    def signal(self, sig: int) -> None:
        self.proc.send_signal(sig)

    def stop(self) -> None:
        """SIGTERM; the child must exit 0 with its state sealed."""
        self.proc.send_signal(signal.SIGTERM)
        try:
            rc = self.proc.wait(timeout=STOP_TIMEOUT_S)
        except subprocess.TimeoutExpired:
            raise self.fail("the server did not exit on SIGTERM") from None
        finally:
            self.kill()
        if rc != 0:
            raise self.fail(f"the server exited {rc} on SIGTERM")
        if "durable state sealed" not in self.log():
            raise self.fail("the server did not seal its durable state")

    def kill(self) -> None:
        if self.proc.poll() is None:
            self.proc.kill()
        self.proc.wait()
        if not self._log.closed:
            self._log.close()


def counter_total(metrics: dict[str, float], prefix: str) -> float:
    return sum(v for k, v in metrics.items() if k.startswith(prefix))
