"""Test environment: force an 8-device virtual CPU platform.

The tests run on the CPU: the driver launches them with
`JAX_PLATFORMS=cpu`, and this file forces it as well (environment and
`jax.config`, both before the first backend use) so a bare `pytest`
cannot reach for an accelerator. Multi-chip sharding is exercised on
the 8 virtual CPU devices. The chip is reached only through
`python benchmark/run.py` on a machine that holds one (here its
`--rehearse` mode runs on the CPU: tests/test_benchmark_rehearsal.py);
the one test file that loads the TPU compiler
(tests/test_tpu_compile.py) compiles for a chip that is described, not
attached.
"""

import os

os.environ["XLA_FLAGS"] = (
    os.environ.get("XLA_FLAGS", "") + " --xla_force_host_platform_device_count=8"
)
os.environ["JAX_PLATFORMS"] = "cpu"

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")
jax.config.update("jax_enable_x64", False)

from k8s_scheduler_tpu.utils.compilation_cache import (  # noqa: E402
    enable_compilation_cache,
)

enable_compilation_cache()

import pytest  # noqa: E402

# What tier 1 leaves out: tests of minutes, not seconds. Tier 1 is the
# driver's command (`commands` in /root/TESTS_LAST_RUN.json):
#   python -m pytest tests/ -q -m "not slow" -n 6 --dist load
# six workers, ~8 minutes of a 1,470 s limit. A test that takes under a
# minute belongs in it; re-survey with `--durations=40` when one drifts.
# Marked `slow` in their own files besides: test_tpu_compile.py's five
# compiles for a described chip, test_fuzz_arrivals_via_api_bit_equal,
# the soak and kill -9 cases (test_front_door.py, test_faults.py),
# test_thousand_tenants_one_bucket and all of test_distributed.py.
_SLOW_TESTS = {
    # durable-state failover tests that spawn jax-importing subprocesses
    "test_kill9_failover_digest_matches_pre_kill",
    "test_soak_failover_smoke",
    # compile-regime management end-to-end proofs: each drives real
    # Schedulers through cold XLA compiles of whole program sets
    # (warm-restart zero-cold-compile, speculation-won flip, and the
    # three-phase regime-churn soak)
    "test_warm_restart_compiles_zero_programs",
    "test_speculative_precompile_wins_the_flip",
    "test_regime_churn_soak_zero_compile_stalls",
    # the open-ended fuzz soak's smoke run (minutes; the differential
    # cases it samples are tier 1, one seed each)
    "test_fuzz_soak_smoke",
    # compile-bound integration drives whose properties have faster
    # tier-1 siblings: dominance-group claims, the sharded scheduler's
    # digest against the unsharded one, the two multi-device dry runs
    "test_eight_slot_claims_via_dominance_groups",
    "test_scheduler_shard_devices_bind_stream_and_digest_invariant",
    "test_dryrun_multichip_2",
    "test_dryrun_multichip_8",
}
_SLOW_MODULES = {"tests.test_concurrency"}


def pytest_collection_modifyitems(config, items):
    for it in items:
        base = it.name.split("[")[0]
        if base in _SLOW_TESTS or it.module.__name__ in _SLOW_MODULES:
            it.add_marker(pytest.mark.slow)
