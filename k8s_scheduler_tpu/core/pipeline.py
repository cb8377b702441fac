"""Double-buffered async serving pipeline: only decision bytes block.

The serving hot path's latency budget is dominated by work that does NOT
have to sit between "snapshot encoded" and "bindings out": FailedScheduling
attribution, per-round convergence diagnostics, the preemption what-if, and
most of the device->host transfer itself (a full CycleResult fetch moves
[P, F] reject counts and per-round tables nobody reads before binding).
`ServingPipeline` restructures one cycle as:

    encode (host)                       # caller, before dispatch()
    -> dispatch: upload into slot k%2, carry update, latency cycle program
       (all ASYNC — JAX dispatches and returns futures)
    -> caller continues host work (extender webhooks, event drain, ...)
    -> decisions(): block on ONE slimmed device->host copy — an i16 (when
       N < 2^15) assignment plus a u8 flag byte per pod, instead of the
       i32 + 2 x bool + diagnostics payload
    -> winners bind; the preemption and diagnosis programs are dispatched
       non-blocking and forced only when a loser actually needs them

Two slots double-buffer the packed input arenas: slot k's buffers stay
alive for cycle k's deferred consumers (diagnosis / preemption) while
cycle k+1 uploads into the other slot; when a slot is reused its previous
buffers are released first, so the allocator recycles the same-sized
blocks instead of growing (no per-cycle realloc). Optional donation
(`donate_diagnosis`) hands the slot's buffers to the diagnosis program
outright — the last consumer — trading the _Resilient retry of that one
program for immediate arena reuse.

Ordering contract: cycle k's binds MUST fold into the cache before cycle
k+1's *adopted* encode reads it. The pipeline enforces the observable
half — by default `dispatch()` refuses to start cycle k+1 until cycle
k's decisions were fetched (without them no bind can have been issued,
so an encode that already ran read a stale cache). Drivers that fold
nothing (pure throughput loops, probes) opt out with
`require_decision_fetch=False`.

`forced_sync=True` is the escape hatch for tests and latency measurement:
every dispatch blocks to completion before returning, restoring strict
sequential execution with identical results (the split is a scheduling
change, not a semantic one).
"""

from __future__ import annotations

import contextlib as _contextlib
import threading as _threading
import time as _time

import jax
import jax.numpy as jnp
import numpy as np

from . import faults as _faults
from .cycle import _jit


def _dispatch_anchor(anchor, now):
    """The one thing this program writes into a profiler trace: a
    `sched.dispatch` host event around the dispatch call, carrying the
    flight record's `seq` and `t_us`, the pipeline clock (the recorder's
    and the span ring's: perf_counter) read immediately before it, in
    microseconds since the recorder's epoch. The event's own start is
    on the profiler's clock, so one of them gives the offset between
    that clock and the clock of every span and flight mark, in the same
    `.xplane.pb` as the device operations. With no profiler session it
    is an inactive TraceMe; with no `anchor` (tracing unarmed) no
    annotation object is made."""
    if anchor is None:
        return _contextlib.nullcontext()
    seq, epoch = anchor
    return jax.profiler.TraceAnnotation(
        "sched.dispatch", seq=seq, t_us=int((now() - epoch) * 1e6)
    )


class DispatchDeadlineExceeded(RuntimeError):
    """The blocking decision fetch exceeded `dispatchDeadlineMs`: the
    watchdog abandoned the wedged transfer (its worker thread keeps
    blocking harmlessly until the backend lets go) so the serve loop
    can step down the degradation ladder and requeue the cycle's pods
    instead of hanging forever. The cycle is CONSUMED — same contract
    as any other failed fetch (the ordering guard releases)."""


class _FetchWorker:
    """Deadline-bounding for a blocking call the host cannot interrupt
    (`jax.device_get` holds no Python-level cancellation point): the
    fetch runs on a reusable daemon thread while the serve loop waits
    with a timeout. On expiry the worker is considered wedged and
    abandoned — told to exit when (if ever) the fetch returns — and the
    next bounded fetch lazily starts a fresh worker. Cost when a fetch
    completes in time: one queue hand-off + one Event wait (~tens of
    microseconds), paid only when a deadline is configured."""

    def __init__(self) -> None:
        self._lock = _threading.Lock()
        self._q = None
        self._thread: "_threading.Thread | None" = None

    def _run(self, jobs) -> None:
        while True:
            fn, box, done = jobs.get()
            if fn is None:
                return  # abandoned after a deadline expiry
            try:
                box["v"] = fn()
            except BaseException as e:  # schedlint: disable=RB001 -- not swallowed: delivered whole to the waiting serve thread, which classifies + attributes it
                box["e"] = e
            finally:
                done.set()

    def run(self, fn, deadline_s: float):
        import queue as _queue

        with self._lock:
            if self._thread is None or not self._thread.is_alive():
                self._q = _queue.Queue()
                self._thread = _threading.Thread(
                    target=self._run, args=(self._q,),
                    name="decision-fetch", daemon=True,
                )
                self._thread.start()
            q = self._q
            box: dict = {}
            done = _threading.Event()
            q.put((fn, box, done))
        if not done.wait(deadline_s):
            with self._lock:
                if self._q is q:
                    # tell the wedged worker to exit once the hung
                    # fetch finally returns; a fresh worker spawns on
                    # the next bounded fetch
                    q.put((None, None, None))
                    self._thread = None
                    self._q = None
            raise DispatchDeadlineExceeded(
                f"decision fetch exceeded the dispatch deadline "
                f"({deadline_s * 1e3:.0f} ms); transfer abandoned"
            )
        if "e" in box:
            raise box["e"]
        return box["v"]


def build_decision_slim_fn(num_nodes: int):
    """Jitted output-transfer slimming for the decision fetch:
    (assignment i32 [P], unschedulable bool [P], gang_dropped bool [P])
    -> (assignment i16|i32 [P], flags u8 [P]) where flags bit0 =
    unschedulable, bit1 = gang_dropped. The i16 narrowing is exact
    whenever every node index (and -1) fits, i.e. N < 2**15."""
    narrow = num_nodes < (1 << 15)

    def slim(assignment, unschedulable, gang_dropped):
        a = assignment.astype(jnp.int16) if narrow else assignment
        flags = unschedulable.astype(jnp.uint8) | (
            gang_dropped.astype(jnp.uint8) << 1
        )
        return a, flags

    return _jit(slim, "decision_slim", disc=f"narrow{int(narrow)}")


def _cpu_safe_buffers(wbuf, bbuf):
    """Force a device copy of numpy packed buffers on the CPU backend.

    jax's CPU backend copies a jit's numpy arguments ASYNCHRONOUSLY on
    the dispatch thread (reproduced in PR 4's pure-jax repro), so a
    deferred program (diagnosis/preemption) still holding the host arena
    can race the NEXT encode's in-place rewrite and read a torn copy.
    The rig/TPU paths device_put explicitly and are unaffected; this is
    the explicit copy for drivers that skip device_put on CPU
    (K8S_TPU_NO_DEVICE_PUT=1, probes). A HOST-side np.copy is taken
    first: jax.device_put on the CPU backend may zero-copy alias an
    aligned numpy array, which would re-create exactly the aliasing
    this guard exists to break."""
    if isinstance(wbuf, np.ndarray) and jax.default_backend() == "cpu":
        return jax.device_put(wbuf.copy()), jax.device_put(bbuf.copy())
    return wbuf, bbuf


class CycleHandle:
    """One in-flight cycle: device-side futures plus the host-side fetch
    state. Created by ServingPipeline.dispatch(); the caller blocks only
    in decisions() (the slimmed fetch) — everything else resolves lazily."""

    def __init__(self, pipe, result, slim, wbuf, bbuf, stable, emask):
        self._pipe = pipe
        self.result = result  # CycleResult/CycleDecision device futures
        self._slim = slim  # (i16|i32 [P], u8 [P]) device futures
        # a program that samples nodes (cycle.SampledCycleResult) hands
        # back two more scalars, fetched in the same transfer
        self._sample = (
            (result.sample_k, result.sample_narrowed_pods)
            if hasattr(result, "sample_k") else None
        )
        # ... and a program that reports its commit rounds (a
        # CycleResult; the latency subset carries none) four more
        self._rounds = (
            (result.rounds_used, result.rounds_parked,
             result.round_cap_hit, result.spread_revoked)
            if hasattr(result, "rounds_parked") else None
        )
        self._wbuf = wbuf
        self._bbuf = bbuf
        self._stable = stable
        self._emask = emask
        self._decisions = None
        self._t_decisions = None
        self._diag = None
        self._pre = None
        self.fetched = False

    # ---- the one blocking fetch -----------------------------------------

    def decisions(self):
        """(assignment i32 [P], unschedulable bool [P], gang_dropped
        bool [P]) as numpy — blocks on the slimmed transfer only."""
        if self._decisions is None:
            now = self._pipe._now
            t0 = now()
            self._pipe.stats["t_decision_start"] = t0
            try:
                (a, flags), sample, rounds = self._pipe.fetch_decisions(
                    lambda: jax.device_get(
                        (self._slim, self._sample, self._rounds)
                    )
                )
            except Exception as e:
                # a failed fetch consumes the cycle: no bind can come of
                # it, so the ordering guard must NOT hold the pipeline
                # hostage — the next dispatch proceeds against a cache
                # without this cycle's (never-issued) binds, which is
                # exactly what it would have read. Without this, one
                # transient device error would poison the memoized
                # pipeline's guard forever (permanent serving outage).
                # Attribution BEFORE the re-raise: a consumed cycle must
                # leave an on-box trace of WHY (events-ring entry +
                # scheduler_fetch_failures_total{class}).
                self._pipe.note_fetch_failure(e)
                self.fetched = True
                self.release()
                self._pipe._note_inflight()
                raise
            self._t_decisions = now()
            st = self._pipe.stats
            st["decision_wait_ms"] = (self._t_decisions - t0) * 1e3
            st["t_decision_end"] = self._t_decisions
            st["fetch_bytes"] = int(a.nbytes + flags.nbytes)
            if sample is not None:
                st["fetch_bytes"] += sum(int(v.nbytes) for v in sample)
                st["sample_k"] = int(sample[0])
                st["sample_narrowed_pods"] = int(sample[1])
            if rounds is not None:
                st["fetch_bytes"] += sum(int(v.nbytes) for v in rounds)
                st["commit_rounds"] = int(rounds[0])
                st["rounds_parked"] = int(rounds[1])
                st["round_cap_hits"] = int(rounds[2])
                st["spread_revoked"] = int(rounds[3])
            # what the un-slimmed fetch of the same fields would move
            st["fetch_bytes_full"] = int(a.shape[0] * (4 + 1 + 1))
            self._pipe._fetch_bytes_total += st["fetch_bytes"]
            m = self._pipe._metrics
            if m is not None:
                m.cycle_duration.labels(phase="decision_fetch").observe(
                    self._t_decisions - t0
                )
                m.decision_fetch_bytes.inc(st["fetch_bytes"])
            self._decisions = (
                np.asarray(a, dtype=np.int32),
                (flags & 1) != 0,
                (flags & 2) != 0,
            )
            self.fetched = True
            self._pipe._note_inflight()
        return self._decisions

    # ---- deferred (off the bind path) -----------------------------------

    def dispatch_preemption(self):
        """Dispatch the preemption PostFilter program (non-blocking);
        returns its device-side result or None. Forcing it is the
        caller's choice — typically after winners were bound, so device
        preemption time overlaps the host bind loop."""
        if self._pre is None and self._pipe._preempt_fn is not None:
            self._pre = self._pipe._preempt_fn(
                self._wbuf, self._bbuf, self.result, self._stable
            )
        return self._pre

    def dispatch_diagnosis(self):
        """Dispatch the FailedScheduling diagnosis program (non-blocking);
        returns the device-side [P, F] handle or None when the pipeline
        has no diagnosis program."""
        if self._diag is None and self._pipe._diag_fn is not None:
            r = self.result
            # pv_claimed and emask are INDEPENDENT optionals — forwarded
            # by keyword so a latency cycle without pv_claimed still
            # carries the extender verdicts into attribution
            kw = {}
            pv = getattr(r, "pv_claimed", None)
            if pv is not None:
                kw["pv_claimed"] = pv
            if self._emask is not None:
                kw["emask"] = self._emask
            self._diag = self._pipe._diag_fn(
                self._wbuf, self._bbuf, self._stable,
                r.assignment, r.node_requested, **kw,
            )
            if self._pipe._donate_diagnosis:
                # the diagnosis program consumed (donated) the slot's
                # packed buffers — nothing may reference them again
                self._wbuf = self._bbuf = None
            if self._pipe.forced_sync:
                # strict sequential execution covers the deferred
                # programs too: block here (before the caller's bind
                # loop) and stamp availability now, so the flight
                # recorder's diag lane serializes instead of riding the
                # bind overlap
                jax.block_until_ready(self._diag)
                if self._t_decisions is not None:
                    t_done = self._pipe._now()
                    self._pipe.stats["diag_lag_ms"] = (
                        t_done - self._t_decisions
                    ) * 1e3
                    self._pipe.stats["t_diag_done"] = t_done
        return self._diag

    def reject_counts(self):
        """Force the diagnosis output (i32 [P, F]); returns None when no
        diagnosis program exists. Records the deferred-diagnosis lag —
        how long after the decision fetch the attribution became
        available (the window FailedScheduling events trail binds by)."""
        d = self.dispatch_diagnosis()
        if d is None:
            return None
        arr = np.asarray(d)
        if (
            self._t_decisions is not None
            and "t_diag_done" not in self._pipe.stats
        ):
            # first force stamps availability; a forced_sync
            # dispatch_diagnosis already did (earlier — see above)
            t_done = self._pipe._now()
            lag = (t_done - self._t_decisions) * 1e3
            self._pipe.stats["diag_lag_ms"] = lag
            self._pipe.stats["t_diag_done"] = t_done
        if self._t_decisions is not None:
            m = self._pipe._metrics
            if m is not None:
                m.cycle_duration.labels(phase="diag_lag").observe(
                    self._pipe.stats.get("diag_lag_ms", 0.0) / 1e3
                )
        return arr

    def reject_counts_matrix(self, n: int):
        """The per-plugin attribution as ONE forced [n, F] matrix: the
        vectorized apply fold reads whole columns (one counter inc per
        plugin across the cycle's losers) instead of re-entering the
        force per pod. Falls back to the fused program's in-result
        counts when no deferred diagnosis program exists."""
        rc = self.reject_counts()
        if rc is None:
            rc = np.asarray(self.result.reject_counts)
        return np.asarray(rc)[:n]

    def block(self):
        """Force everything in flight (the forced_sync escape hatch).
        Routed through the same bounded-fetch path as decisions(): at
        the ladder's forced_sync rung THIS is the serve loop's blocking
        wait, and without the watchdog a persistently hung tunnel would
        re-wedge the loop at exactly the rung meant to contain it (the
        next expiry then escalates to stateless/seal-for-failover)."""
        try:
            self._pipe.fetch_decisions(
                lambda: jax.block_until_ready((self.result, self._slim))
            )
        except Exception as e:
            # same contract as a failed decisions() fetch: the cycle is
            # consumed, the guard releases (see decisions) — and the
            # failure class is stamped before the re-raise
            self._pipe.note_fetch_failure(e)
            self.fetched = True
            self.release()
            self._pipe._note_inflight()
            raise
        return self

    def release(self):
        """Drop every device reference so the slot's arena blocks free
        (the allocator then recycles them for the next upload)."""
        self.result = self._slim = self._diag = self._pre = None
        self._sample = None
        self._wbuf = self._bbuf = self._stable = self._emask = None


class ServingPipeline:
    """Owns the two upload slots, the in-flight handle, and the carry
    hand-off (CarryKeeper-compatible). One instance per compiled packed
    regime — the Scheduler memoizes it next to the programs.

    `cycle_fn` is any packed cycle program: carry-path
    (build_packed_cycle_carry_fn, with `keeper`), or plain packed
    (build_packed_cycle_fn, `keeper=None`). `diag_fn`/`preempt_fn` are
    the deferred companions (None disables them)."""

    def __init__(
        self,
        cycle_fn,
        *,
        keeper=None,
        diag_fn=None,
        preempt_fn=None,
        forced_sync: bool = False,
        require_decision_fetch: bool = True,
        donate_diagnosis: bool = False,
        metrics=None,
        events=None,  # core/events.EventRecorder | None: fetch-failure
        # attribution stamps a system event on the ring before re-raise
        dispatch_deadline_s: float = 0.0,  # bound on the blocking
        # decision fetch (0 = unbounded); expiry raises
        # DispatchDeadlineExceeded via the _FetchWorker watchdog
        now=_time.perf_counter,
    ) -> None:
        if donate_diagnosis and preempt_fn is not None:
            # a donated diagnosis consumes the slot's packed buffers; a
            # preemption program dispatched after it would read freed
            # memory — refuse the combination instead of ordering traps
            raise ValueError(
                "donate_diagnosis requires preempt_fn=None "
                "(preemption reads the packed buffers after diagnosis)"
            )
        self._cycle_fn = cycle_fn
        self._keeper = keeper
        self._diag_fn = diag_fn
        self._preempt_fn = preempt_fn
        self.forced_sync = forced_sync
        self.require_decision_fetch = require_decision_fetch
        self._donate_diagnosis = donate_diagnosis
        self._metrics = metrics
        self._events = events
        self.dispatch_deadline_s = dispatch_deadline_s
        self._fetch_worker = _FetchWorker()  # no thread until first use
        self._now = now
        self._slots = [None, None]
        self._slim_fn = None
        self._last = None
        self._n = 0
        self._fetch_bytes_total = 0
        self._pending_encode_ms: float | None = None
        # per-cycle stage report (the split-phase measurement): refreshed
        # by dispatch()/decisions()/reject_counts(); encode_ms is fed by
        # the caller via note_encode()
        self.stats: dict[str, float] = {}

    @property
    def cycles(self) -> int:
        return self._n

    @property
    def fetch_bytes_total(self) -> int:
        return self._fetch_bytes_total

    def fetch_decisions(self, fn):
        """Run the one blocking device->host decision fetch with the
        fault hooks and (when `dispatch_deadline_s` > 0) the watchdog
        applied. `fetch_delay` sleeps OUTSIDE the bounded call (a slow
        tunnel: visible latency); `fetch_hang` sleeps INSIDE it (a
        wedged tunnel: exactly what the deadline bounds)."""
        if _faults.ARMED:
            _faults.sleep_point("fetch_delay")
            inner = fn

            def fn():
                _faults.sleep_point("fetch_hang")
                return inner()

        d = self.dispatch_deadline_s
        if d and d > 0:
            return self._fetch_worker.run(fn, d)
        return fn()

    def note_fetch_failure(self, e: BaseException) -> str:
        """Attribute a consumed cycle's fetch failure before it
        re-raises: `scheduler_fetch_failures_total{class}` + an
        events-ring entry. Returns the class (transport | corrupt |
        wedge | deadline | other). MUST NOT raise: it runs inside the
        failure handlers BEFORE the ordering-guard release — an
        attribution error that escaped would leave the guard held
        forever (the permanent-outage mode the release exists to
        prevent), so a broken metrics registry or events ring costs
        the trace, never the pipeline."""
        from .cycle import classify_failure

        cls = (
            "deadline" if isinstance(e, DispatchDeadlineExceeded)
            else classify_failure(e)
        )
        try:
            m = self._metrics
            if m is not None:
                m.fetch_failures.labels(cls).inc()
            ev = self._events
            if ev is not None:
                from .events import FETCH_FAILED

                ev.system(
                    FETCH_FAILED,
                    f"cycle decision fetch failed ({cls}): {e}"[:400],
                )
        except Exception:  # schedlint: disable=RB001 -- deliberately silent: the original error re-raises right after this call and carries the story; attribution must never hold the ordering guard hostage
            pass
        return cls

    def note_encode(self, seconds: float) -> None:
        """Record the host encode time of the snapshot about to be
        dispatched — feeds the overlap accounting in stage_report."""
        self._pending_encode_ms = seconds * 1e3

    def _claim_slot(self) -> int:
        """Claim the next upload slot, releasing its previous occupant's
        device references first, so the allocator hands back the
        same-sized blocks (buffered arena reuse instead of per-cycle
        growth)."""
        slot = self._n % len(self._slots)
        prev = self._slots[slot]
        if prev is not None:
            prev.release()
        return slot

    def dispatch(
        self,
        wbuf,
        bbuf,
        stable,
        *,
        dirty=None,
        carry_key=None,
        pin=None,
        emask=None,
        escore=None,
        device_put: bool = True,
        anchor=None,
    ) -> CycleHandle:
        """Upload + dispatch one cycle; returns immediately with a
        CycleHandle (unless forced_sync). Raises if the previous cycle's
        decisions were never fetched while require_decision_fetch — the
        strict-ordering guard (see module docstring)."""
        if (
            self.require_decision_fetch
            and self._last is not None
            and not self._last.fetched
        ):
            raise RuntimeError(
                "ServingPipeline: cycle k+1 dispatched before cycle k's "
                "decisions were fetched — binds cannot have folded before "
                "this snapshot was encoded (pass "
                "require_decision_fetch=False for fold-free loops)"
            )
        t0 = self._now()
        slot = self._claim_slot()
        if device_put:
            wbuf = jax.device_put(wbuf)
            bbuf = jax.device_put(bbuf)
        else:
            # CPU backend: numpy arena buffers must not feed async
            # dispatch directly — the deferred diagnosis/preemption
            # programs would race the next encode's arena rewrite
            # (see _cpu_safe_buffers)
            wbuf, bbuf = _cpu_safe_buffers(wbuf, bbuf)
        with _dispatch_anchor(anchor, self._now):
            if self._keeper is not None:
                carry = self._keeper.state(
                    wbuf, bbuf, stable, dirty, carry_key, pin=pin
                )
                if emask is not None:
                    result = self._cycle_fn(
                        wbuf, bbuf, stable, carry, emask, escore
                    )
                else:
                    result = self._cycle_fn(wbuf, bbuf, stable, carry)
            else:
                result = self._cycle_fn(wbuf, bbuf, stable)
        if self._slim_fn is None:
            self._slim_fn = build_decision_slim_fn(
                result.node_requested.shape[0]
            )
        slim = self._slim_fn(
            result.assignment, result.unschedulable, result.gang_dropped
        )
        handle = CycleHandle(
            self, result, slim, wbuf, bbuf, stable, emask
        )
        self._slots[slot] = handle
        self._last = handle
        self._n += 1
        t1 = self._now()
        dispatch_s = t1 - t0
        # absolute marks (pipeline clock = perf_counter) feed the flight
        # recorder's per-cycle trace lanes (core/flight_recorder.py)
        self.stats = {
            "dispatch_ms": dispatch_s * 1e3,
            "slot": slot,
            "t_dispatch_start": t0,
            "t_dispatch_end": t1,
        }
        if self._pending_encode_ms is not None:
            self.stats["encode_ms"] = self._pending_encode_ms
            self._pending_encode_ms = None
        if self._metrics is not None:
            self._metrics.cycle_duration.labels(phase="dispatch").observe(
                dispatch_s
            )
        self._note_inflight()
        if self.forced_sync:
            handle.block()
            # sequential execution hides nothing: the device time sits
            # inside dispatch_ms here, so the conservative
            # encode-vs-decision-wait estimate would misread the tiny
            # post-block fetch as "encode fully hidden" — pin it to 0
            self.stats["encode_hidden_ms"] = 0.0
        return handle

    def inflight(self) -> int:
        """Dispatched cycles whose decisions were not fetched yet (0 or
        1 under the strict-ordering guard)."""
        return sum(
            1 for h in self._slots if h is not None and not h.fetched
        )

    def _note_inflight(self) -> None:
        g = getattr(self._metrics, "cycle_inflight", None)
        if g is not None:
            g.set(self.inflight())

    def stage_report(self) -> dict[str, float]:
        """Last-cycle per-stage breakdown: dispatch_ms, decision_wait_ms,
        fetch_bytes (+ the full-payload bytes it replaced), diag_lag_ms,
        encode_ms, and encode_hidden_ms — the portion of the reported
        encode that overlapped in-flight device work (encode minus the
        observed decision wait shortfall is not derivable per-cycle, so
        hidden = max(0, encode - decision_wait) is the conservative
        per-cycle estimate; the probe/bench compute the exact overlap
        from separated encode/device baselines)."""
        st = dict(self.stats)
        if "encode_hidden_ms" not in st:  # forced_sync pre-pins it to 0
            enc = st.get("encode_ms", 0.0)
            wait = st.get("decision_wait_ms", 0.0)
            st["encode_hidden_ms"] = max(0.0, enc - wait)
        return st
