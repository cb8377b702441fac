"""Durable scheduler state: write-ahead journal + snapshots + restore.

SURVEY.md §5 item 3 assumes a standby "rebuilds all state from the
agent's re-list"; in this reproduction there is no agent to re-list
from, so a takeover used to silently drop the `SchedulingQueue`'s
backoff deadlines and attempt counts and the `SchedulerCache`'s
assumed-but-unconfirmed pods. This package is the crash-consistent
state layer that closes that gap:

- `journal.py` — checksummed, segment-rotated write-ahead journal of
  logical queue/cache mutations, drained by a writer thread with group
  fsync (appends never touch the bind path's latency budget);
- `codec.py` — fast hand-rolled Pod/Node <-> plain-dict converters
  (the journal/snapshot wire format), the serialiser of a row's
  snapshot fragment, plus the canonical state digest;
- `snapshot.py` — atomic whole-state snapshots that compact the
  journal (write-temp + fsync + rename);
- `manager.py` — `DurableState`: wires emitters into a live
  queue/cache pair, restores snapshot+tail on attach, snapshots on an
  interval, and seals the journal on clean shutdown.

A snapshot file is the whole state every time, but a compaction
serialises no pod twice: the cache and the queue keep each resident
row's fragment, the compact JSON bytes of the pod as the row's last
journal record carried it (the cache makes them where the pod is
journaled; a queue entry keeps the record's state dict and the first
compaction that meets it turns that into bytes), and
`DurableState.snapshot()` splices kept fragments, so its cost follows
what entered the queue since the last one and not what is resident.

Replay is exact: each journal record carries the emitting clock value
and restore re-executes the logical operation under a replay clock, so
backoff expiries, attempt counts, and assumed-pod TTL deadlines come
back bit-identical (differential tests in tests/test_state_failover.py).
Timestamps are CLOCK_MONOTONIC of the host — valid for same-host
failover (the FileLease deployment shape); snapshots carry a wall-clock
anchor for observability.
"""

from .journal import (
    FORMAT_VERSION,
    Journal,
    StateCorruption,
    StateError,
    StateVersionError,
    replay_dir,
)
from .codec import state_digest
from .manager import DurableState

__all__ = [
    "FORMAT_VERSION",
    "Journal",
    "DurableState",
    "StateCorruption",
    "StateError",
    "StateVersionError",
    "replay_dir",
    "state_digest",
]
