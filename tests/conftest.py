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

# Tests measured >8s (compile-bound integration tests; `--durations`
# re-survey when this list drifts). The fast tier skips them:
#   python -m pytest tests/ -q -m "not slow"
_SLOW_TESTS = {
    "test_packed_cycle_matches_unpacked",
    "test_carry_cycle_matches_plain_over_churn",
    "test_stable_state_injection_matches",
    "test_profile_cycle_fills_per_plugin_histograms",
    "test_stable_state_reused_across_pending_changes",
    "test_rounds_deterministic",
    "test_extender_error_nonignorable_backoff",
    "test_rounds_throughput_close_to_scan",
    "test_bind_error_and_unschedulable_results",
    "test_gang_drop_reason_is_coscheduling",
    "test_rounds_validity_on_mixed_workload",
    "test_dryrun_multichip_2",
    "test_rounds_validity_with_existing_pods",
    "test_profiles_place_identical_pods_differently",
    "test_scheduled_event_and_reason_metric",
    "test_extender_filter_and_bind_delegation",
    "test_rounds_affinity_bootstrap_and_colocation",
    "test_host_plugin_lifecycle_order",
    "test_scheduler_sequential_cycles_respect_capacity",
    "test_scheduler_end_to_end_bind",
    "test_scheduler_preemption_flow",
    "test_volume_binding_over_the_wire",
    "test_scheduler_node_delete_requeues",
    "test_scheduler_gang_requeue",
    # durable-state failover tests that spawn jax-importing subprocesses
    "test_kill9_failover_digest_matches_pre_kill",
    "test_soak_failover_smoke",
    # multi-cycle heavyweights: the 3-seed scheduler-level equivalence
    # drive (~40 s/seed: two full Schedulers + WAL per seed) and the
    # 15-cycle burst/lull trace (the device-level equivalence cases
    # stay fast)
    "test_scheduler_multicycle_matches_sequential",
    "test_mixed_burst_lull_traffic_no_false_fold_miss",
    # compile-regime management end-to-end proofs (ISSUE 8): each
    # drives real Schedulers through cold XLA compiles of whole
    # program sets (warm-restart zero-cold-compile, speculation-won
    # flip, and the three-phase regime-churn soak)
    "test_warm_restart_compiles_zero_programs",
    "test_speculative_precompile_wins_the_flip",
    "test_regime_churn_soak_zero_compile_stalls",
    # scenario-fuzzer live differential smoke (ISSUE 11): each case is
    # a full trace replay through a fresh Scheduler (engine compile) —
    # and for the differential cases a second, oracle-side replay. The
    # corpus replays and shrinker units stay fast-tier: minimal-repro
    # traces compile tiny programs the persistent cache keeps warm.
    "test_fuzz_differential_plain_seed",
    "test_fuzz_differential_multicycle_seed",
    "test_fuzz_differential_sharded_seed",
    "test_fuzz_chaos_seed",
    "test_fuzz_catches_seeded_tiebreak_bug",
    "test_corpus_repro_still_catches_its_bug",
    "test_fuzz_soak_smoke",
    # depth-2 speculative dispatch (ISSUE 13) heavyweights: the
    # 3-scheduler equivalence ladder and the 2-scheduler mismatch
    # drive (~40 s of Scheduler+WAL each), the speculative fuzz
    # differential (TWO engine replays per trace) and the chaos
    # mid-speculation replay (a real 15 s injected hang bounded by
    # the watchdog) — the device-level chain/pipeline/record/sentinel
    # cases stay fast
    "test_scheduler_speculative_matches_sequential",
    "test_mismatch_abandons_redispatches_bit_identical",
    "test_fuzz_differential_speculative_seed",
    "test_fuzz_chaos_fetch_hang_mid_speculation",
    # admission-time incremental encode (ISSUE 16) heavyweights: the
    # incremental fuzz differential (TWO engine replays per trace,
    # same class as its sibling seeds above) and the two table-growth
    # drives (each compiles a fresh K=4 packed program set) — the
    # journal batch-record cases stay fast
    "test_fuzz_differential_incremental_seed",
    "test_multicycle_table_growth_within_padding_rebinds",
    "test_multicycle_growth_reencode_reuses_interned_entries",
    # tier-1 headroom re-survey (ISSUE 17 --durations audit): the four
    # slowest fast-tier tests, each a compile-bound integration drive
    # (92 s dominance-group claims, 69 s shard-invariance digest, 26 s
    # 8-device dryrun, 23 s randomized preemption differential) — the
    # properties they prove have faster fast-tier siblings
    "test_eight_slot_claims_via_dominance_groups",
    "test_scheduler_shard_devices_bind_stream_and_digest_invariant",
    "test_dryrun_multichip_8",
    "test_randomized_differential_preemption",
}
_SLOW_MODULES = {"tests.test_concurrency"}


def pytest_collection_modifyitems(config, items):
    for it in items:
        base = it.name.split("[")[0]
        if base in _SLOW_TESTS or it.module.__name__ in _SLOW_MODULES:
            it.add_marker(pytest.mark.slow)
