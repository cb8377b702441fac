"""Multi-host DCN bring-up (SURVEY.md §5.8, VERDICT r3 item 6): two
real `jax.distributed` CPU processes on localhost prove
`initialize_distributed` wiring, a cross-process collective, and a tiny
scheduling cycle sharded across both processes (equal to the replicated
run). Slow-marked: two interpreter starts + distributed init."""

import os
import socket
import subprocess
import sys

import pytest

pytestmark = pytest.mark.slow

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def test_two_process_dcn_psum_and_sharded_cycle():
    port = _free_port()
    env = dict(os.environ)
    # 2 local CPU devices per process -> a 4-device global mesh (the
    # flag is consumed at the worker's first backend use)
    env["XLA_FLAGS"] = (
        env.get("XLA_FLAGS", "")
        + " --xla_force_host_platform_device_count=2"
    )
    env.pop("JAX_PLATFORMS", None)  # the workers force cpu themselves
    procs = [
        subprocess.Popen(
            [sys.executable, os.path.join(_ROOT, "tests", "_dcn_worker.py"),
             str(port), str(pid), "2"],
            cwd=_ROOT,
            env=env,
            stdout=subprocess.PIPE,
            stderr=subprocess.STDOUT,
            text=True,
        )
        for pid in range(2)
    ]
    outs = []
    try:
        for p in procs:
            out, _ = p.communicate(timeout=420)
            outs.append(out)
    finally:
        for p in procs:
            if p.poll() is None:
                p.kill()
    for pid, (p, out) in enumerate(zip(procs, outs)):
        assert p.returncode == 0, f"worker {pid} failed:\n{out}"
        assert "INIT ok: processes=2 devices=4" in out, out
        assert "PSUM ok: " in out, out
        assert "CYCLE ok: placed=16 sharded==replicated" in out, out
