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

Depth-2 speculative dispatch (`dispatch_multi(..., speculative=True)`)
is the one sanctioned relaxation: batch k+1 may be dispatched while
batch k is still in flight, encoded against the PREDICTED post-k state
(device-side carry chaining — cycle.build_packed_multicycle_fn
`carry_in`). The guard is then "binds fold before the next ADOPTED
encode": the speculative handle only becomes the current batch through
`adopt_speculative()` — called after batch k's host fold landed and
matched the speculation's predicate digest — and is otherwise abandoned
(`abandon_speculative()`) and re-dispatched against the true carry.
Correctness is never speculative, only latency is. Depth 2 needs a
THIRD arena slot (`slots=3`): the two double-buffered slots assume one
batch in flight, and with two in flight the slot-reuse release would
otherwise overwrite a batch whose decisions were never fetched —
`dispatch`/`dispatch_multi` refuse that loudly instead of corrupting
an in-flight upload.

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
from . import spans as _spans
from .cycle import CycleDecision, _jit


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


def build_multicycle_slim_rows_fn(num_nodes: int, k: int):
    """STREAMED variant of the multi-cycle decision slimming: the same
    i16|u8 diet, but split K ways so each inner cycle's row is its own
    fetchable device buffer — `(((a_0, flags_0), …, (a_{K-1},
    flags_{K-1})), cycles_run)` instead of one stacked [K, P] pair.
    MultiCycleHandle.decisions_row(i) then blocks on row i's transfer
    alone, so the apply loop can bind inner cycle i's winners while
    rows i+1…K-1 are still in flight (and, under depth-2 speculative
    dispatch, while the NEXT batch is still running on device). Flag
    bits: 0 = unschedulable, 1 = gang_dropped, 2 = attempted (the pod
    was valid in that inner cycle — the host needs it to tell "not
    this cycle's pod" from "placed at node 0")."""
    narrow = num_nodes < (1 << 15)

    def slim(assignment, unschedulable, gang_dropped, attempted,
             cycles_run):
        a = assignment.astype(jnp.int16) if narrow else assignment
        flags = (
            unschedulable.astype(jnp.uint8)
            | (gang_dropped.astype(jnp.uint8) << 1)
            | (attempted.astype(jnp.uint8) << 2)
        )
        rows = tuple((a[i], flags[i]) for i in range(k))
        return rows, cycles_run

    return _jit(
        slim, "multicycle_slim_rows", disc=f"narrow{int(narrow)}|k{k}"
    )


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


class MultiCycleHandle:
    """One in-flight multi-cycle batch (K inner cycles dispatched as a
    single device program — core/cycle.build_packed_multicycle_fn).
    Mirrors CycleHandle's contract, streamed: the slimmed decision
    payload is split into per-inner-cycle fetchable rows
    (build_multicycle_slim_rows_fn), so `decisions_row(i)` blocks on
    row i's transfer alone and the apply loop binds cycle i's winners
    while later rows (and, under depth-2 speculation, the next batch)
    are still in flight. The handle counts as fetched — releasing the
    binds-fold ordering guard — once every LIVE row (`n_live`, the
    dispatched `n_cycles`) was fetched. The per-inner-cycle deferred
    programs (diagnosis, preemption) dispatch lazily against the
    stacked buffers' row i and the loop's post-cycle-i
    `node_requested`."""

    def __init__(
        self, pipe, result, slim, wbufs, bbufs, stable,
        n_live: int, speculative: bool = False,
    ):
        self._pipe = pipe
        self.result = result  # MultiCycleResult device futures
        # (((i16|i32 [P], u8 [P]) x K), i32) futures — per-row slimmed
        self._slim = slim
        self._wbufs = wbufs
        self._bbufs = bbufs
        self._stable = stable
        self.n_live = n_live
        self.speculative = speculative
        self._rows: dict[int, tuple] = {}
        self._cycles_run: "int | None" = None
        self._decisions = None
        self._t_decisions = None
        self._diag: dict[int, object] = {}
        self._pre: dict[int, object] = {}
        # inner cycle i -> (lag_s, t_done): deferred-diagnosis
        # availability, stamped at first force so the scheduler can put
        # diag_lag on inner-cycle flight records (stage_report is
        # snapshotted BEFORE the apply loop that forces these)
        self.diag_lag: dict[int, tuple[float, float]] = {}
        self.fetched = False

    def _consumed(self, e: BaseException) -> None:
        """A failed fetch consumes the batch: same contract as
        CycleHandle.decisions — the ordering guard releases, the
        failure class is stamped before the re-raise."""
        self._pipe.note_fetch_failure(e)
        self.fetched = True
        self.release()
        self._pipe._note_inflight()

    def decisions_row(self, i: int):
        """Inner cycle i's decisions as numpy — `(assignment i32 [P],
        unschedulable bool [P], gang_dropped bool [P], attempted bool
        [P])` — blocking on row i's slimmed transfer only. The first
        row fetched stamps `t_first_decision` (the scheduler's
        `first_bind` phase anchor); fetching every live row marks the
        handle consumed (ordering-guard release)."""
        hit = self._rows.get(i)
        if hit is not None:
            return hit
        now = self._pipe._now
        t0 = now()
        st = self._pipe.stats
        st.setdefault("t_decision_start", t0)
        try:
            a, flags = self._pipe.fetch_decisions(
                lambda: jax.device_get(self._slim[0][i])
            )
        except Exception as e:  # schedlint: disable=RB001 -- not swallowed: _consumed stamps the failure class (metric + events ring) before the re-raise — the consumed-cycle contract
            self._consumed(e)
            raise
        t1 = now()
        self._t_decisions = t1
        st["decision_wait_ms"] = (
            st.get("decision_wait_ms", 0.0) + (t1 - t0) * 1e3
        )
        st["t_decision_end"] = t1
        st.setdefault("t_first_decision", t1)
        if _spans.ARMED:
            # per-row decision window for the decision.row trace span
            # (scheduler._apply_mc_row reads it back by row index; a
            # plain-list key, so the stage report's t_*/"*_ms" copy
            # loops never see it and flight records stay unchanged)
            st.setdefault("decision_rows", []).append((i, t0, t1))
        nbytes = int(a.nbytes + flags.nbytes)
        st["fetch_bytes"] = st.get("fetch_bytes", 0) + nbytes
        self._pipe._fetch_bytes_total += nbytes
        m = self._pipe._metrics
        if m is not None:
            m.cycle_duration.labels(phase="decision_fetch").observe(
                t1 - t0
            )
            m.decision_fetch_bytes.inc(nbytes)
        row = (
            np.asarray(a, dtype=np.int32),
            (flags & 1) != 0,
            (flags & 2) != 0,
            (flags & 4) != 0,
        )
        self._rows[i] = row
        if len(self._rows) >= self.n_live and not self.fetched:
            self.fetched = True
            self._pipe._note_inflight()
        return row

    def cycles_run(self) -> int:
        """Inner cycles the device loop actually executed (blocks on
        the scalar transfer; ~free once the rows landed)."""
        if self._cycles_run is None:
            try:
                cr = self._pipe.fetch_decisions(
                    lambda: jax.device_get(self._slim[1])
                )
            except Exception as e:  # schedlint: disable=RB001 -- not swallowed: _consumed stamps the failure class (metric + events ring) before the re-raise
                self._consumed(e)
                raise
            self._cycles_run = int(cr)
        return self._cycles_run

    def decisions(self):
        """(assignment i32 [K, P], unschedulable bool [K, P],
        gang_dropped bool [K, P], attempted bool [K, P], cycles_run int)
        as numpy — the whole-batch fetch (every row + the scalar in one
        transfer). Kept for drivers that want the stacked shape; the
        streaming apply path uses decisions_row."""
        if self._decisions is None:
            now = self._pipe._now
            t0 = now()
            st = self._pipe.stats
            st.setdefault("t_decision_start", t0)
            try:
                rows, cycles_run = self._pipe.fetch_decisions(
                    lambda: jax.device_get(self._slim)
                )
            except Exception as e:  # schedlint: disable=RB001 -- not swallowed: _consumed stamps the failure class (metric + events ring) before the re-raise
                self._consumed(e)
                raise
            self._t_decisions = now()
            st["decision_wait_ms"] = (
                st.get("decision_wait_ms", 0.0)
                + (self._t_decisions - t0) * 1e3
            )
            st["t_decision_end"] = self._t_decisions
            st.setdefault("t_first_decision", self._t_decisions)
            nbytes = sum(
                int(r[0].nbytes + r[1].nbytes) for r in rows
            ) + 4
            a = np.stack([np.asarray(r[0], dtype=np.int32)
                          for r in rows])
            flags = np.stack([np.asarray(r[1]) for r in rows])
            st["fetch_bytes"] = st.get("fetch_bytes", 0) + nbytes
            self._pipe._fetch_bytes_total += nbytes
            m = self._pipe._metrics
            if m is not None:
                m.cycle_duration.labels(phase="decision_fetch").observe(
                    self._t_decisions - t0
                )
                m.decision_fetch_bytes.inc(nbytes)
            self._cycles_run = int(cycles_run)
            self._decisions = (
                a,
                (flags & 1) != 0,
                (flags & 2) != 0,
                (flags & 4) != 0,
                self._cycles_run,
            )
            self.fetched = True
            self._pipe._note_inflight()
        return self._decisions

    def _inner_decision(self, i: int) -> CycleDecision:
        """Inner cycle i's decision carry as the deferred programs'
        input: stacked row i plus the loop's POST-cycle-i state."""
        r = self.result
        return CycleDecision(
            assignment=r.assignment[i],
            node_requested=r.node_requested[i],
            unschedulable=r.unschedulable[i],
            gang_dropped=r.gang_dropped[i],
        )

    def dispatch_preemption(self, i: int):
        """Dispatch inner cycle i's preemption PostFilter (non-blocking);
        returns its device-side result or None. NOTE the documented
        multi-cycle deviation: candidates/victims are computed against
        the BATCH-start existing set — a pod bound by an earlier inner
        cycle is not yet evictable (it becomes so next batch)."""
        if i not in self._pre and self._pipe._preempt_fn is not None:
            self._pre[i] = self._pipe._preempt_fn(
                self._wbufs[i], self._bbufs[i],
                self._inner_decision(i), self._stable,
            )
        return self._pre.get(i)

    def dispatch_diagnosis(self, i: int):
        """Dispatch inner cycle i's FailedScheduling diagnosis program
        (non-blocking); returns the device-side [P, F] handle or None.
        Uses `pipe.multi_diag_fn` when set — the multi-cycle decisions
        are lean (no fused reject counts), so the scheduler installs a
        diagnosis program even for regimes whose single-cycle path runs
        the fused full program and needs none."""
        fn = self._pipe.multi_diag_fn or self._pipe._diag_fn
        if i not in self._diag and fn is not None:
            r = self.result
            self._diag[i] = fn(
                self._wbufs[i], self._bbufs[i], self._stable,
                r.assignment[i], r.node_requested[i],
            )
            if self._pipe.forced_sync:
                jax.block_until_ready(self._diag[i])
                self._stamp_diag_lag(i)
        return self._diag.get(i)

    def _stamp_diag_lag(self, i: int) -> None:
        if self._t_decisions is None or i in self.diag_lag:
            return
        t_done = self._pipe._now()
        lag_s = max(0.0, t_done - self._t_decisions)
        self.diag_lag[i] = (lag_s, t_done)
        m = self._pipe._metrics
        if m is not None:
            m.cycle_duration.labels(phase="diag_lag").observe(lag_s)

    def reject_counts(self, i: int):
        """Force inner cycle i's diagnosis output (i32 [P, F]); None
        when the pipeline has no diagnosis program. First force stamps
        the deferred-diagnosis lag for inner cycle i — how long after
        the batch's decision fetch the attribution became available."""
        d = self.dispatch_diagnosis(i)
        if d is None:
            return None
        arr = np.asarray(d)
        self._stamp_diag_lag(i)
        return arr

    def reject_counts_matrix(self, i: int, n: int):
        """Inner cycle i's per-plugin attribution as ONE forced [n, F]
        matrix (see CycleHandle.reject_counts_matrix — same one-force
        contract for the vectorized apply fold)."""
        return np.asarray(self.reject_counts(i))[:n]

    def block(self):
        """Force everything in flight (the forced_sync escape hatch);
        watchdog-bounded like CycleHandle.block."""
        try:
            self._pipe.fetch_decisions(
                lambda: jax.block_until_ready((self.result, self._slim))
            )
        except Exception as e:
            # consumed batch: guard releases, class stamped (see
            # CycleHandle.block)
            self._pipe.note_fetch_failure(e)
            self.fetched = True
            self.release()
            self._pipe._note_inflight()
            raise
        return self

    def release(self):
        self.result = self._slim = None
        self._wbufs = self._bbufs = self._stable = None
        self._diag = {}
        self._pre = {}
        self.diag_lag = {}


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
        multi_fn=None,  # optional multi-cycle program
        # (build_packed_multicycle_fn) driving dispatch_multi; the
        # scheduler assigns it lazily (`pipe.multi_fn = ...`) when
        # multiCycleK > 1 and the workload is in the envelope
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
        slots: int = 2,
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
        self._slots = [None] * max(2, slots)
        self._slim_fn = None
        self.multi_fn = multi_fn
        # multi-cycle diagnosis program (build_diagnosis_fn): the
        # scheduler installs it next to multi_fn; falls back to
        # _diag_fn (carry mode shares one) when None
        self.multi_diag_fn = None
        # continuation variant (build_packed_multicycle_fn carry_in):
        # consumes a predecessor batch's device-resident carry — the
        # program depth-2 speculative dispatches run on
        self.multi_cont_fn = None
        self._multi_slim_fn = None
        self._last = None
        # the one in-flight SPECULATIVE batch (depth 2: at most one),
        # pending adopt_speculative/abandon_speculative resolution
        self._spec: "MultiCycleHandle | None" = None
        # speculation ledger: outcomes of every speculative dispatch
        # (mirrored into scheduler_speculation_total{outcome})
        self.speculation = {
            "adopted": 0, "abandoned": 0, "redispatched": 0,
        }
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
        device references for arena reuse. Refuses to overwrite a slot
        whose batch was never fetched: under depth-2 speculation two
        batches are legitimately in flight, and silently releasing an
        unfetched handle would corrupt an in-flight upload — the
        slot-accounting invariant is that `slots >= in-flight + 1`
        (three slots for depth 2), enforced here loudly."""
        slot = self._n % len(self._slots)
        prev = self._slots[slot]
        if prev is not None:
            if not prev.fetched and self.require_decision_fetch:
                # fold-free drivers (require_decision_fetch=False) opted
                # out of the ordering guard and may legitimately leave
                # handles unfetched — they keep the silent release
                raise RuntimeError(
                    f"ServingPipeline: upload slot {slot} still holds "
                    "an unfetched in-flight batch — dispatch depth "
                    f"exceeds the {len(self._slots)}-slot arena "
                    "(speculative depth-2 needs slots=3)"
                )
            # release the old occupant's device references BEFORE
            # uploading so the allocator hands back the same-sized
            # blocks (buffered arena reuse instead of per-cycle growth)
            prev.release()
        return slot

    def _speculation_outcome(self, outcome: str) -> None:
        self.speculation[outcome] += 1
        m = self._metrics
        counter = getattr(m, "speculation", None) if m else None
        if counter is not None:
            counter.labels(outcome=outcome).inc()

    def adopt_speculative(self) -> "MultiCycleHandle":
        """The host fold of the predecessor batch matched the
        speculation's predicate: the in-flight speculative batch
        becomes the current one (zero added latency — it has been on
        device the whole time) and the ordering guard resumes guarding
        it like any adopted dispatch."""
        h = self._spec
        if h is None:
            raise RuntimeError("adopt_speculative: no speculation in flight")
        self._spec = None
        self._last = h
        # the adopted batch's dispatch marks become the current stage
        # report (its rows' fetch stats land on top as they stream in)
        self.stats = dict(getattr(h, "_stats_seed", {}))
        self._speculation_outcome("adopted")
        return h

    def abandon_speculative(self) -> None:
        """The host fold diverged from the speculation's predicate (or
        the predecessor batch failed outright): drop the in-flight
        speculative batch — its results are never observed — and free
        its arena slot. The caller re-dispatches against the true
        carry (note_redispatch) or requeues. Idempotent/no-op when no
        speculation is in flight, so failure paths can call it
        unconditionally without leaking a slot."""
        h = self._spec
        if h is None:
            return
        self._spec = None
        h.fetched = True  # consumed-without-observation: guard releases
        h.release()
        for i, s in enumerate(self._slots):
            if s is h:
                self._slots[i] = None
        self._speculation_outcome("abandoned")
        self._note_inflight()

    def note_redispatch(self) -> None:
        """Ledger mark: an abandoned speculation's groups were
        re-dispatched against the true carry."""
        self._speculation_outcome("redispatched")

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
        if self._spec is not None:
            raise RuntimeError(
                "ServingPipeline: dispatch with an unresolved "
                "speculative batch in flight — adopt_speculative() or "
                "abandon_speculative() first"
            )
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

    def dispatch_multi(
        self,
        wbufs,
        bbufs,
        stable,
        n_cycles: int,
        *,
        device_put: bool = True,
        carry0=None,
        speculative: bool = False,
        anchor=None,
    ) -> MultiCycleHandle:
        """Upload + dispatch one MULTI-CYCLE batch (stacked [K, ...]
        packed snapshots, one device dispatch for up to `n_cycles` inner
        cycles — see build_packed_multicycle_fn). Shares the single-
        dispatch ordering guard: a batch counts as the in-flight cycle,
        so the next dispatch (single or multi) is refused until the
        batch's decisions were fetched — binds-fold ordering holds
        across the batch boundary exactly as it does between single
        cycles.

        `speculative=True` is the depth-2 relaxation: the batch may be
        dispatched while its predecessor is still unfetched (the guard
        becomes "binds fold before the next ADOPTED encode" — module
        docstring). The handle is held aside until the caller resolves
        it via adopt_speculative()/abandon_speculative(); at most one
        speculation is in flight. `carry0 = (carry_node_requested,
        carry_gplaced)` chains the predecessor's device-resident final
        carry into this batch through `multi_cont_fn` (the carry_in
        continuation program) — no host round trip."""
        fn = self.multi_fn
        if carry0 is not None:
            fn = self.multi_cont_fn
            if fn is None:
                raise RuntimeError(
                    "ServingPipeline.dispatch_multi: carry0 given but "
                    "no continuation program (assign pipe.multi_cont_fn"
                    " = build_packed_multicycle_fn(..., carry_in=True))"
                )
        if fn is None:
            raise RuntimeError(
                "ServingPipeline.dispatch_multi: no multi-cycle program "
                "(assign pipe.multi_fn = build_packed_multicycle_fn(...))"
            )
        if self._spec is not None:
            raise RuntimeError(
                "ServingPipeline: dispatch_multi with an unresolved "
                "speculative batch in flight — adopt_speculative() or "
                "abandon_speculative() first"
            )
        if (
            not speculative
            and self.require_decision_fetch
            and self._last is not None
            and not self._last.fetched
        ):
            raise RuntimeError(
                "ServingPipeline: multi-cycle batch dispatched before "
                "the previous cycle's decisions were fetched — binds "
                "cannot have folded before this batch was encoded "
                "(speculative=True is the sanctioned depth-2 path)"
            )
        t0 = self._now()
        slot = self._claim_slot()
        if device_put:
            wbufs = jax.device_put(wbufs)
            bbufs = jax.device_put(bbufs)
        else:
            wbufs, bbufs = _cpu_safe_buffers(wbufs, bbufs)
        with _dispatch_anchor(anchor, self._now):
            if carry0 is not None:
                result = fn(
                    wbufs, bbufs, stable, np.int32(n_cycles), *carry0
                )
            else:
                result = fn(wbufs, bbufs, stable, np.int32(n_cycles))
        if self._multi_slim_fn is None:
            self._multi_slim_fn = build_multicycle_slim_rows_fn(
                result.node_requested.shape[1],
                result.assignment.shape[0],
            )
        slim = self._multi_slim_fn(
            result.assignment, result.unschedulable,
            result.gang_dropped, result.attempted, result.cycles_run,
        )
        handle = MultiCycleHandle(
            self, result, slim, wbufs, bbufs, stable,
            n_live=n_cycles, speculative=speculative,
        )
        self._slots[slot] = handle
        if speculative:
            self._spec = handle
        else:
            self._last = handle
        self._n += 1
        t1 = self._now()
        stats = {
            "dispatch_ms": (t1 - t0) * 1e3,
            "slot": slot,
            "multi_cycles": n_cycles,
            "t_dispatch_start": t0,
            "t_dispatch_end": t1,
        }
        if self._pending_encode_ms is not None:
            stats["encode_ms"] = self._pending_encode_ms
            self._pending_encode_ms = None
        if speculative:
            # a speculative dispatch must not clobber the in-flight
            # batch's stage report: its marks are held on the handle
            # and installed by adopt_speculative — the predecessor's
            # stats only note that a speculation was dispatched in its
            # shadow
            handle._stats_seed = stats
            self.stats["spec_dispatch_ms"] = stats["dispatch_ms"]
        else:
            self.stats = stats
        if self._metrics is not None:
            self._metrics.cycle_duration.labels(phase="dispatch").observe(
                t1 - t0
            )
        self._note_inflight()
        if self.forced_sync and not speculative:
            handle.block()
            self.stats["encode_hidden_ms"] = 0.0
        return handle

    def inflight(self) -> int:
        """Dispatched cycles whose decisions were not fetched yet (0 or
        1 under the strict-ordering guard; up to 2 while a depth-2
        speculative batch is in flight)."""
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
