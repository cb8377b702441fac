"""The server process's policy for CPython's cyclic collector.

A scheduler's heap is one large set that stays (the cache's pods and
nodes, the queue, the encoder's mirrors: ~26 container objects a pod,
2.6 million at 100,000 pods) and a stream that does not (a cycle's
protos, API objects, journal records, timeline events). The
interpreter's full pass, due whenever the old generation has grown by a
quarter, walks the whole resident set (0.4-1.2 s at 100,000 pods) in
the middle of whichever RPC allocates most. Reference counting frees
almost everything here; the collector only ever finds the odd cycle. So
the policy is:

- freeze what a cycle leaves standing: after every scheduling cycle a
  young pass (`gc.collect(1)`, so that fresh cyclic garbage is not
  frozen with the rest) and `gc.freeze()`. Frozen objects are walked by
  no pass; reference counting still frees them the moment nothing
  points at them;
- no automatic full pass between two freezes (`THRESHOLDS`);
- a sweep (`gc.unfreeze()`, `gc.collect()`, `gc.freeze()`) that bounds
  what freezing can leak. Frozen cyclic garbage can only come from
  objects that left the resident set, so the sweep follows departures
  and not the clock: CPython's own 25% rule, over the pods and nodes
  removed since the last sweep instead of over allocations. How much
  of a departure leaks is measured, not presumed: the objects a sweep
  found (`collected`) over the departures it covered, at most 1, is
  the rate `q` the next one is placed by. An object found counts as a
  whole departure: a pod holds ~26, so the rate overstates a leak many
  times over and never understates one, and any pass that finds as
  many objects as pods have left puts the schedule back where the
  presumption had it. `q` starts at 1 (no evidence yet: every
  departure leaks), a sweep that finds a leak puts it at the rate
  found at once, and one that finds nothing lets it fall to a quarter,
  so the next waits four times as long and stale evidence is tested
  again on a geometric schedule. On the served path sweeps find
  nothing (0 objects in every one of 66 sweeps a window at 500 nodes
  and of 2 at 5,000, PERF.md section 6, PR 41): pods leave by
  reference count;
- all of it placed by the caller outside `Cycle`: the servicer runs
  `cycle_done` after the response has left (service/server.py).
  Somebody waits all the same: a pass holds the interpreter lock, and
  the agent's next `Update` (the confirmations) arrives a few
  milliseconds in and stands behind it (94-99% of every sweep, PERF.md
  section 6, PR 40). A freeze is a few milliseconds; a sweep walks the
  whole resident set, which is why it has to be rare.

Installed by `cmd/main.main()` and by nothing else: importing this
module or constructing a `Scheduler` or a `SchedulerService` leaves the
interpreter's collector as it was, so tests and embedders keep the
defaults. No configuration: the one thing that varies between
deployments, when to sweep, follows what the process observes.

Seen through `core/spans`: one `gc.pass` span for every placed
operation (`kind` `freeze` or `sweep`) and, from a `gc.callbacks` hook,
for every generation-2 pass the interpreter starts itself (`auto_full`),
with `generation`, `collected` and `frozen`. Automatic young passes are
too many for the ring: they are counted, and `cycle_done` carries the
counts to `scheduler_gc_young_passes_total` and
`scheduler_gc_young_pass_seconds_total`. Placed sweeps are counted too
(`sweeps`, `scheduler_gc_sweeps_total`, and `gc_sweeps` in every flight
record): a span's `kind` is read by no metric, a count is. So are the
cycles after which the departures alone asked for a sweep and the
measured rate did not (`deferred`, `scheduler_gc_sweeps_deferred_total`,
`gc_sweeps_deferred`); a sweep's span also carries `left`, the
departures it covered, and `q`, the rate it was placed by.
"""

from __future__ import annotations

import gc
import threading
from typing import Callable

from . import spans as _spans

# (generation 0, 1, 2). The first two are the interpreter's own, and
# stay: a young pass over 700 new objects, or over the 7,700 that ten of
# them promote, works inside the processor's cache (66 and 104 ns an
# object on the chip's host; at 20,000 and 220,000 objects the same
# passes cost 115 and 150 ns an object, PERF.md section 6, PR 30), and
# every object a cycle leaves standing is walked by one of each either
# way. The third is what the policy changes. It counts middle passes
# since the last full one, and a freeze zeroes it: a saturated cycle of
# 9,800 pods leaves ~0.7 million objects standing, ~100 middle passes,
# so at the interpreter's 10 a full pass would fall every tenth of a
# cycle, over whatever has gathered since the last freeze. At 1,000 it
# falls only once 7.7 million objects have gathered with no freeze
# between, eight such cycles' worth, and then walks those and nothing
# frozen.
THRESHOLDS = (700, 10, 1_000)

# Sweep once what the pods and nodes that left since the last sweep have
# leaked (their number times the measured rate `q`) passes this share of
# those resident: the share of its old generation by which CPython lets
# the heap grow before a full pass
# (`long_lived_pending < long_lived_total / 4`). It is also the most `q`
# falls by in one sweep: a fruitless sweep makes the next wait four
# times the departures, no more.
SWEEP_SHARE = 0.25
# ... and at least this many: a sweep walks every module the process has
# imported as well, and under a small cluster the rule above would ask
# for that every few deletes, to free kilobytes.
SWEEP_MIN_DEPARTURES = 1_000


class CollectorPolicy:
    """`census()` gives (pods and nodes resident, pods and nodes that
    have left since the process began): `Scheduler.census`. `metrics`
    (a `SchedulerMetrics`) receives the two young-pass counters and the
    counts of sweeps placed and deferred; `sweeps` and `deferred` are
    the same running totals, which the scheduler's flight records carry
    as `gc_sweeps` and `gc_sweeps_deferred`."""

    def __init__(
        self, census: Callable[[], "tuple[int, int]"], metrics
    ) -> None:
        self._census = census
        self._metrics = metrics
        # one placed operation at a time: the agent's `Cycle` and the
        # front door's local loop both end cycles
        self._lock = threading.Lock()
        # the interpreter's thresholds while installed, else None
        self._restore: "tuple[int, int, int] | None" = None
        self._swept_at = 0  # departures at the last sweep
        self._placing = 0  # ident of the thread inside a placed operation
        self._t_start = 0.0  # the hook's: when the pass under way began
        # the share of a departure that leaks, as the last sweep
        # measured it (objects found over departures covered, at most
        # 1); 1 until a sweep has run
        self.q = 1.0
        # `gc.get_freeze_count()` walks every frozen object (~60 ns each
        # on the chip's host: 105-175 ms at 1.1-2.9 million, several times
        # the freeze it would describe). So it is taken after a sweep and
        # an automatic full pass, which cost more anyway, and after a
        # freeze once the resident set has doubled since the last count.
        self._frozen = 0
        self._counted_at = 0  # resident when `_frozen` was counted
        self.young_passes = 0
        self.young_seconds = 0.0
        self._young_flushed = (0, 0.0)
        self.sweeps = 0
        # cycles after which the departures alone asked for a sweep and
        # `q` did not
        self.deferred = 0

    # ---- install / uninstall --------------------------------------------

    def install(self) -> None:
        """Thresholds, the hook, and the first freeze: everything
        imported and whatever state the scheduler restored."""
        with self._lock:
            if self._restore is not None:
                return
            self._restore = gc.get_threshold()
            gc.set_threshold(*THRESHOLDS)
            gc.callbacks.append(self._on_pass)
            resident, self._swept_at = self._census()
            self._place("freeze", resident, count=True)

    def uninstall(self) -> None:
        """Thresholds and callbacks as `install` found them, nothing
        left frozen. For tests and embedders: the server keeps the
        policy to its exit, which is the faster for walking nothing."""
        with self._lock:
            if self._restore is None:
                return
            gc.callbacks.remove(self._on_pass)
            gc.set_threshold(*self._restore)
            gc.unfreeze()
            self._restore = None

    # ---- the placed operations ------------------------------------------

    def cycle_done(self) -> None:
        """A scheduling cycle has ended and its response has left:
        sweep if what the departures have leaked, at the measured rate,
        asks for one, else freeze what the cycle left standing. A cycle
        that left less than one young pass's worth (no automatic pass
        has run since the last freeze) is left to the next."""
        with self._lock:
            if self._restore is None:
                return
            self._flush_young()
            resident, departed = self._census()
            left = departed - self._swept_at
            due = SWEEP_SHARE * resident
            # what every departure presumed leaked whole would ask
            asked = left >= SWEEP_MIN_DEPARTURES and left > due
            if asked and self.q * left > due:
                collected = self._place(
                    "sweep", resident, count=True, left=left, q=self.q)
                self._swept_at = departed
                self.sweeps += 1
                self._metrics.gc_sweeps.inc()
                # the rate found, and never under a quarter of the rate
                # before: one fruitless sweep is no proof
                self.q = min(
                    1.0, max(collected / left, self.q * SWEEP_SHARE))
                return
            if asked:
                self.deferred += 1
                self._metrics.gc_sweeps_deferred.inc()
            if any(gc.get_count()[1:]):
                self._place(
                    "freeze", resident,
                    count=resident > 2 * self._counted_at,
                )

    def _place(self, kind: str, resident: int, count: bool, **attrs) -> int:
        sweep = kind == "sweep"
        t0 = _spans.now()
        self._placing = threading.get_ident()
        try:
            if sweep:
                gc.unfreeze()
            collected = gc.collect(2 if sweep else 1)
            gc.freeze()
        finally:
            self._placing = 0
        if _spans.ARMED:
            if count:
                self._frozen = gc.get_freeze_count()
                self._counted_at = resident
            self._stamp(kind, t0, 2 if sweep else 1, collected, **attrs)
        return collected

    # ---- what the interpreter starts itself -----------------------------

    def _on_pass(self, phase: str, info: dict) -> None:
        """`gc.callbacks` hook, on whichever thread's allocation set the
        pass off (passes never nest: the interpreter runs one at a
        time). Arithmetic on attributes and, for a full pass, one span;
        it takes no lock, because that thread may hold any."""
        if self._placing == threading.get_ident():
            return  # the placed operation stamps itself, whole
        if phase == "start":
            self._t_start = _spans.now()
        elif info["generation"] < 2:
            self.young_passes += 1
            self.young_seconds += _spans.now() - self._t_start
        elif _spans.ARMED:
            self._frozen = gc.get_freeze_count()
            self._stamp("auto_full", self._t_start, 2, info["collected"])

    # ---- stamping --------------------------------------------------------

    def _stamp(self, kind: str, t0: float, generation: int,
               collected: int, **attrs) -> None:
        # a trace of its own, with no parent: a pass belongs to no RPC,
        # it delays whichever one is open
        trace = _spans.TraceContext(
            _spans.new_trace_id(), _spans.new_span_id()
        )
        _spans.record_span(
            "gc.pass", trace, t0, _spans.now(), root_of="", kind=kind,
            generation=generation, collected=collected,
            frozen=self._frozen, **attrs,
        )

    def _flush_young(self) -> None:
        n, s = self.young_passes, self.young_seconds
        n0, s0 = self._young_flushed
        self._young_flushed = (n, s)
        self._metrics.gc_young_passes.inc(n - n0)
        self._metrics.gc_young_pass_seconds.inc(s - s0)
