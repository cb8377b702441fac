#!/usr/bin/env python3
"""The server child: `k8s_scheduler_tpu.cmd.main.main()` unchanged, plus
the two things only the process that holds the chip can give.

- SIGUSR1 starts a `jax.profiler` trace into `--bench-trace-dir`, SIGUSR2
  stops it (the harness sends them around a few steady seconds of a
  `--trace 1` run; a `--trace 0` run sends neither, and nothing here
  then runs until exit). `trace.done` appears when the file is written.
- After `main()` returns (SIGTERM, state sealed) it prints one
  `bench_device:` line with the device's `peak_bytes_in_use`, which
  nothing outside this process can read.

Every other argument goes to `main()` as `python -m k8s_scheduler_tpu`
would get it.
"""

from __future__ import annotations

import json
import os
import signal
import sys
import threading


def main() -> int:
    argv = sys.argv[1:]
    trace_dir = ""
    if "--bench-trace-dir" in argv:
        i = argv.index("--bench-trace-dir")
        trace_dir = argv[i + 1]
        del argv[i:i + 2]

    start, stop = threading.Event(), threading.Event()
    # handlers only set events: the tracer thread does the work, so the
    # main thread (parked in main()'s stop.wait()) is never inside jax
    signal.signal(signal.SIGUSR1, lambda *_: start.set())
    signal.signal(signal.SIGUSR2, lambda *_: stop.set())

    def tracer() -> None:
        import time

        import jax

        start.wait()
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0  # device and XLA host events only
        options.host_tracer_level = 2
        jax.profiler.start_trace(trace_dir, profiler_options=options)
        wall, t0 = time.time(), time.monotonic()
        stop.wait(timeout=30.0)
        t1 = time.monotonic()
        jax.profiler.stop_trace()
        with open(os.path.join(trace_dir, "trace.done"), "w") as f:
            json.dump({"start": t0, "stop": t1, "wall_start": wall}, f)

    if trace_dir:
        os.makedirs(trace_dir, exist_ok=True)
        threading.Thread(target=tracer, daemon=True).start()

    from k8s_scheduler_tpu.cmd.main import main as serve

    rc = serve(argv)

    import jax

    peaks = [
        (d.memory_stats() or {}).get("peak_bytes_in_use", 0)
        for d in jax.local_devices()
    ]
    print("bench_device: " + json.dumps({"peak_bytes_in_use": max(peaks)}),
          flush=True)
    return rc


if __name__ == "__main__":
    sys.exit(main())
