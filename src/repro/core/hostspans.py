"""Wall-clock spans and counters of the real read path, on the profiler's clock.

The program's one real-clock tracer.  :mod:`repro.core.telemetry`'s
``Tracer`` records *simulated* seconds on a ``SimClock``; this records the
host's own time while ``TokenLoader`` reads batches through
``StripeStore.read_item``.  The stripe spans here are the real-clock side of
the simulator's ``stripe-read`` flows, whose waiting the simulator charges to
the stall class ``disk-queue``.

* ``loader.batch`` opens a batch and gives it the next id; every span and
  counter inside it is charged to that batch.  A span opened outside a batch
  (a HoardFS or simulator read) is charged to nothing.
* Every span is also a ``jax.profiler.TraceAnnotation`` of the same name, so
  a profiler trace shows it on its host plane, nested in whatever span the
  caller has open; the trace is where single spans and their nesting live.
  TSL's ``TraceMe`` stamps host events with ``GetCurrentTimeNanos()``, which
  is ``EnvTime::NowNanos()``: ``clock_gettime(CLOCK_REALTIME)``.  The spans
  here read the same clock (``time.time_ns``) inside the annotation, so a
  batch's totals agree with its trace events.

Closed batches go into a ring of :data:`RING_BATCHES` records (nanoseconds
per span name, and counters).  The ring stays in memory; the recorder is
always on.
"""

from __future__ import annotations

import itertools
import threading
import time
from collections import deque
from dataclasses import dataclass, field
from typing import Optional

RING_BATCHES = 4096         # over 7 minutes of short128 batches, 8.8 a second on a v5e
BATCH_SPAN = "loader.batch"


@dataclass
class BatchRecord:
    """One batch: nanoseconds per span name, summed, and its counters."""

    batch: int
    total_ns: dict = field(default_factory=dict)
    counters: dict = field(default_factory=dict)


class _Open:
    __slots__ = ("rec", "name", "ann", "t0")

    def __init__(self, rec: "HostSpans", name: str):
        self.rec, self.name = rec, name

    def __enter__(self):
        self.ann = self.rec._annotation(self.name)
        self.ann.__enter__()
        self.t0 = time.time_ns()
        return self

    def __exit__(self, *exc):
        d = time.time_ns() - self.t0
        self.ann.__exit__(*exc)
        batch = self.rec._local.batch
        if batch is not None:
            batch.total_ns[self.name] = batch.total_ns.get(self.name, 0) + d


class _Batch(_Open):
    __slots__ = ()

    def __enter__(self):
        local = self.rec._local
        if local.batch is not None:
            raise RuntimeError("a batch is already open on this thread")
        local.batch = BatchRecord(next(self.rec._ids))
        return super().__enter__()

    def __exit__(self, *exc):
        super().__exit__(*exc)
        local = self.rec._local
        if exc[0] is None:          # a batch that raised was never delivered
            self.rec.batches.append(local.batch)
        local.batch = None


class _Local(threading.local):
    def __init__(self):
        self.batch: Optional[BatchRecord] = None


class HostSpans:
    """The recorder: a bounded ring of closed batches."""

    def __init__(self, batches: int = RING_BATCHES):
        self.batches: deque[BatchRecord] = deque(maxlen=batches)
        self._local = _Local()
        self._ids = itertools.count()           # next() on it is atomic
        self._trace_annotation = None

    def _annotation(self, name: str):
        if self._trace_annotation is None:
            from jax.profiler import TraceAnnotation
            self._trace_annotation = TraceAnnotation
        return self._trace_annotation(name)

    def span(self, name: str) -> _Open:
        return _Open(self, name)

    def batch(self) -> _Batch:
        """Open a batch and its ``loader.batch`` span (one per thread at a time)."""
        return _Batch(self, BATCH_SPAN)

    def count(self, name: str, n: int = 1) -> None:
        batch = self._local.batch
        if batch is not None:
            batch.counters[name] = batch.counters.get(name, 0) + n

    def last(self, n: int) -> Optional[list[BatchRecord]]:
        """The last ``n`` closed batches, oldest first; ``None`` if fewer are held."""
        if n <= 0 or len(self.batches) < n:
            return None
        return list(itertools.islice(reversed(self.batches), n))[::-1]


RECORDER = HostSpans()
span = RECORDER.span
batch = RECORDER.batch
count = RECORDER.count
last = RECORDER.last


def per_batch_ms(records: list[BatchRecord], name: str) -> float:
    """Mean milliseconds of span ``name`` per batch over ``records``."""
    return sum(r.total_ns.get(name, 0) for r in records) / len(records) / 1e6


def read_amplification(records: list[BatchRecord]) -> Optional[float]:
    """Bytes read from disk over item bytes delivered; ``None`` if none delivered."""
    read = sum(r.counters.get("stripe.bytes_read", 0) for r in records)
    delivered = sum(r.counters.get("stripe.bytes_delivered", 0) for r in records)
    return read / delivered if delivered else None
