"""Fault tests for the `_Resilient` program wrapper (core/cycle.py).

A transport-class error from the device runtime gets one retry with a
recorded strike, a cache-corruption error clears the compile cache and
retries, a wedge fails fast, and anything else re-raises untouched;
the strikes reach the served registry per scheduler.
"""

import pytest

from k8s_scheduler_tpu.core.cycle import (
    RESILIENT_STRIKES,
    _Resilient,
    is_transport_error,
)


class _FakeTransportError(RuntimeError):
    pass


def test_is_transport_error_classification():
    assert is_transport_error(
        RuntimeError("remote_compile: response body closed")
    )
    assert is_transport_error(OSError("Connection reset by peer"))
    assert not is_transport_error(ValueError("rank mismatch"))
    assert not is_transport_error(
        ValueError("Executable expected parameter 3")
    )


def test_resilient_absorbs_transport_flake_and_counts_strike():
    # the one transport retry sleeps 0.5s — acceptable in a unit test
    state = {"calls": 0, "cleared": 0}

    def fn(x):
        state["calls"] += 1
        if state["calls"] == 1:
            raise _FakeTransportError(
                "http://127.0.0.1:8103/remote_execute: broken pipe"
            )
        return x + 1

    fn.__name__ = "fake_program"
    fn.clear_cache = lambda: state.__setitem__(
        "cleared", state["cleared"] + 1
    )

    RESILIENT_STRIKES.clear()
    r = _Resilient(fn)
    assert r(41) == 42
    assert state["calls"] == 2
    assert state["cleared"] == 0  # transport retries must NOT clear_cache
    assert RESILIENT_STRIKES == {("fake_program", "transport"): 1}

    from k8s_scheduler_tpu.metrics.metrics import global_metrics

    v = global_metrics().registry.get_sample_value(
        "scheduler_program_retry_strikes_total",
        {"program": "fake_program", "kind": "transport"},
    )
    assert v is not None and v >= 1


def test_resilient_corruption_strike_clears_cache_and_counts():
    state = {"calls": 0, "cleared": 0}

    def fn(x):
        state["calls"] += 1
        if state["calls"] == 1:
            raise ValueError(
                "Execution supplied 3 buffers but compiled program "
                "expected 4 buffers"
            )
        return x * 2

    fn.__name__ = "fake_corrupt"
    fn.clear_cache = lambda: state.__setitem__(
        "cleared", state["cleared"] + 1
    )

    RESILIENT_STRIKES.clear()
    r = _Resilient(fn)
    assert r(21) == 42
    assert state["cleared"] == 1
    assert RESILIENT_STRIKES == {("fake_corrupt", "executable_cache"): 1}


def test_resilient_wedge_fails_fast_with_strike():
    """The rig-wedge signature is NOT healable in-process (clear_cache +
    retrace fail once the backend session is wedged — PERF.md r5), so
    _Resilient must record the strike and raise on the FIRST attempt
    instead of burning ~100s retraces."""
    state = {"calls": 0, "cleared": 0}

    def fn(x):
        state["calls"] += 1
        raise RuntimeError(
            "INVALID_ARGUMENT: TPU backend error (InvalidArgument)."
        )

    fn.__name__ = "fake_wedge"
    fn.clear_cache = lambda: state.__setitem__(
        "cleared", state["cleared"] + 1
    )

    RESILIENT_STRIKES.clear()
    with pytest.raises(RuntimeError, match="TPU backend error"):
        _Resilient(fn)(1)
    assert state["calls"] == 1  # no doomed retries
    assert state["cleared"] == 0  # no needless retrace
    assert RESILIENT_STRIKES == {("fake_wedge", "backend_wedge"): 1}


def test_resilient_reraises_non_retryable():
    def fn(x):
        raise ValueError("rank mismatch in dot_general")

    fn.__name__ = "fake_bad"
    fn.clear_cache = lambda: None
    with pytest.raises(ValueError, match="rank mismatch"):
        _Resilient(fn)(1)


def test_strike_metric_reaches_served_registry():
    """VERDICT r3 item 7 end-to-end: a _Resilient strike must appear in
    the registry the CLI serves on /metrics. Strikes land in
    global_metrics(); the CLI constructs its Scheduler with
    metrics=global_metrics() (cmd/main.py), mirrored here."""
    from k8s_scheduler_tpu.core.scheduler import Scheduler
    from k8s_scheduler_tpu.metrics.metrics import global_metrics

    state = {"calls": 0}

    def fn(x):
        state["calls"] += 1
        if state["calls"] == 1:
            raise ValueError(
                "Executable expected parameter 0 of size 8 but got "
                "buffer with incompatible size 4"
            )
        return x

    fn.__name__ = "fake_served"
    fn.clear_cache = lambda: None
    assert _Resilient(fn)(5) == 5

    sched = Scheduler(metrics=global_metrics())
    assert sched.metrics is global_metrics()
    payload = sched.metrics.expose().decode()
    assert "scheduler_program_retry_strikes_total" in payload
    assert 'program="fake_served"' in payload


def test_two_schedulers_do_not_cross_count():
    """r4 regression (VERDICT r4 weak #2): default-constructed Schedulers
    must each get a FRESH registry — metric increments on one must not
    appear in the other's served payload, and neither must write the
    process-wide registry."""
    from k8s_scheduler_tpu.core.scheduler import Scheduler
    from k8s_scheduler_tpu.metrics.metrics import global_metrics

    a, b = Scheduler(), Scheduler()
    assert a.metrics is not b.metrics
    assert a.metrics is not global_metrics()

    a.metrics.schedule_attempts.labels(
        result="isolation-probe", profile="isolation-probe"
    ).inc()
    val = lambda m: m.registry.get_sample_value(
        "scheduler_schedule_attempts_total",
        {"result": "isolation-probe", "profile": "isolation-probe"},
    )
    assert val(a.metrics) == 1.0
    assert val(b.metrics) is None
    assert val(global_metrics()) is None
