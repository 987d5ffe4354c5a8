"""Host-side tracer: the framework's own executions emit Pipit-native traces.

This closes the paper's loop — the training/serving runtime is *itself* a
trace source.  Events use the uniform data model (§III-A): Enter/Leave pairs
with nanosecond timestamps per logical process.  ``to_trace()`` returns a
:class:`repro.core.Trace`; ``save_jsonl`` writes the native format the
``repro.readers.jsonl`` reader loads back.

**Live mode** (``sink="rank_0.pack"``): the tracer spills its buffer to an
append-mode pack shard (:meth:`repro.readers.pack.PackWriter.open_append`)
every ``flush_every`` events *and* at least every ``heartbeat_interval``
seconds, each flush ending in a durable commit plus an atomically-replaced
heartbeat record (``<sink>.hb``).  The buffer is therefore bounded — a
day-long training run cannot OOM the traced job — and a monitor process
(:class:`repro.core.liveset.LiveTraceSet`) can watch the shard directory,
query the committed prefix while the job runs, and classify this rank as
live/lagging/dead from the heartbeat.  A SIGKILLed tracer loses at most
the uncommitted tail since its last flush.

Without a sink the tracer buffers in memory exactly as before (bounded by
a one-time warning at ``max_buffer_events`` — it never drops events).

**Spans and counters** (:func:`span`, :func:`begin`, :func:`counter`,
:func:`count`, :func:`snapshot`, :func:`record_spans`): the program's own
timing of its layers.  A span records its name, start and end on
``time.perf_counter_ns``, its parent span and its request id, and adds its
duration and its *self time* (duration minus the child spans it covers on
its own thread) to process-wide per-name totals; a counter adds a work
count.  The totals are always on and cost two clock reads and one locked
add per span; :class:`~repro.serving.tracequery.TraceService` exports them
on ``/stats`` as ``"telemetry"``.  The current span lives in a
``contextvars.ContextVar``, so coroutines interleaved on one event-loop
thread keep their own parents, and work handed to another thread inside
``contextvars.copy_context().run`` keeps its request's id and parent.
When ``jax`` is already imported, each span also opens a
``jax.profiler.TraceAnnotation``, so with a profiler attached the spans
land in its host plane on the device trace's clock.  ``record_spans(t)``
forwards every span into a :class:`Tracer`, one Pipit process per thread,
so a process's own spans open in Pipit.
"""

from __future__ import annotations

import collections
import contextlib
import contextvars
import itertools
import json
import os
import sys
import threading
import time
import warnings
from typing import Dict, List, Optional

import numpy as np

from ..core.constants import (ENTER, ET, LEAVE, MPI_RECV, MPI_SEND, MSG_SIZE,
                              NAME, PARTNER, PROC, TAG, TS)

__all__ = ["Tracer", "write_heartbeat", "read_heartbeat", "span", "begin",
           "counter", "count", "snapshot", "record_spans"]

#: wall-clock heartbeat cadence is checked every this many events, so the
#: hot _push path stays a couple of list appends
_HB_CHECK_EVERY = 256


def write_heartbeat(sink: str, rank: int, events: int, ts_max,
                    seq: int, wall: Optional[float] = None,
                    final: bool = False) -> str:
    """Atomically (tmp + rename) write the heartbeat record next to a
    shard: ``<sink>.hb`` with {rank, wall, events, ts_max, seq, pid,
    final}.  Readers classify the rank's liveness from ``wall`` age."""
    hb = {"rank": int(rank), "wall": time.time() if wall is None else wall,
          "events": int(events),
          "ts_max": None if ts_max is None else int(ts_max),
          "seq": int(seq), "pid": os.getpid(), "final": bool(final)}
    path = sink + ".hb"
    tmp = path + ".tmp"
    with open(tmp, "w") as f:
        json.dump(hb, f)
    os.replace(tmp, path)
    return path


def read_heartbeat(sink: str) -> Optional[dict]:
    """The shard's heartbeat record, or None when absent/unparseable."""
    try:
        with open(sink + ".hb") as f:
            return json.load(f)
    except (OSError, ValueError):
        return None


class Tracer:
    """Event recorder for one logical process (rank).

    ``sink=None`` (default): pure in-memory buffering, list-backed —
    ``to_trace()`` / ``save_jsonl`` consume the buffer.

    ``sink="<path>.pack"``: bounded-buffer live mode.  The buffer spills
    to an append-mode pack shard with a durable commit every
    ``flush_every`` events and at least every ``heartbeat_interval``
    seconds of wall time (checked every few hundred events), each flush
    also refreshing the ``<sink>.hb`` heartbeat.  ``close()`` flushes the
    tail and (by default) finalizes the shard into an ordinary pack.
    With a sink, ``to_trace()`` only sees the *unflushed tail* — open the
    shard itself (``Trace.open(sink, live=True)``) for the full stream.
    """

    def __init__(self, process: int = 0, clock=time.perf_counter_ns,
                 sink: Optional[str] = None, flush_every: int = 50_000,
                 heartbeat_interval: float = 1.0, fsync: bool = True,
                 max_buffer_events: int = 2_000_000,
                 chunk_rows: Optional[int] = None,
                 wall_clock=time.time):
        self.process = process
        self.clock = clock
        self._t0 = clock()
        self.sink = os.fspath(sink) if sink is not None else None
        self.flush_every = int(flush_every)
        if self.flush_every <= 0:
            raise ValueError("flush_every must be positive")
        self.heartbeat_interval = float(heartbeat_interval)
        self.max_buffer_events = int(max_buffer_events)
        self._chunk_rows = chunk_rows or self.flush_every
        self._fsync = bool(fsync)
        self._wall = wall_clock
        self._writer = None          # lazily-opened append PackWriter
        self._flushed_events = 0     # events committed to the sink
        self._flush_seq = 0
        self._last_hb = self._wall()
        self._last_ts: Optional[int] = None
        self._warned_unbounded = False
        self._closed = False
        self.ts: List[int] = []
        self.et: List[str] = []
        self.name: List[str] = []
        self.proc: List[int] = []
        self.partner: List[int] = []
        self.size: List[float] = []

    def _now(self) -> int:
        return self.clock() - self._t0

    def enter(self, name: str, proc: Optional[int] = None,
              ts: Optional[int] = None) -> None:
        """``ts``: the event's own reading of :attr:`clock` (default: now)."""
        self._push(self._now() if ts is None else ts - self._t0, ENTER,
                   name, proc)

    def leave(self, name: str, proc: Optional[int] = None,
              ts: Optional[int] = None) -> None:
        self._push(self._now() if ts is None else ts - self._t0, LEAVE,
                   name, proc)

    def instant(self, name: str, proc: Optional[int] = None,
                partner: int = -1, size: float = float("nan"),
                et: str = "Instant") -> None:
        self._push(self._now(), et, name, proc, partner, size)

    def message(self, kind: str, partner: int, size: float,
                proc: Optional[int] = None) -> None:
        """kind: 'send' | 'recv' — models collective traffic as messages."""
        name = MPI_SEND if kind == "send" else MPI_RECV
        self._push(self._now(), "Mpi" + kind.capitalize(), name, proc,
                   partner, size)

    def _push(self, ts, et, name, proc, partner=-1, size=float("nan")):
        self.ts.append(ts)
        self.et.append(et)
        self.name.append(name)
        self.proc.append(self.process if proc is None else proc)
        self.partner.append(partner)
        self.size.append(size)
        n = len(self.ts)
        if self.sink is not None:
            if n >= self.flush_every:
                self.flush()
            elif n % _HB_CHECK_EVERY == 0 and \
                    self._wall() - self._last_hb >= self.heartbeat_interval:
                self.flush()
        elif n > self.max_buffer_events and not self._warned_unbounded:
            self._warned_unbounded = True
            warnings.warn(
                f"Tracer buffer passed {self.max_buffer_events} events "
                f"with no sink — a long run will exhaust memory.  Pass "
                f"sink='<shard>.pack' to spill with bounded memory "
                f"(flush_every={self.flush_every}).",
                RuntimeWarning, stacklevel=3)

    @contextlib.contextmanager
    def span(self, name: str, proc: Optional[int] = None):
        self.enter(name, proc)
        try:
            yield
        finally:
            self.leave(name, proc)

    # -- live sink ---------------------------------------------------------
    def _tail_frame(self) -> "EventFrame":
        from ..core.frame import EventFrame
        return EventFrame({
            TS: np.asarray(self.ts, np.int64),
            ET: np.asarray(self.et),
            NAME: np.asarray(self.name),
            PROC: np.asarray(self.proc, np.int64),
            PARTNER: np.asarray(self.partner, np.int64),
            MSG_SIZE: np.asarray(self.size, np.float64),
            TAG: np.zeros(len(self.ts), np.int64),
        })

    def _clear(self) -> None:
        for lst in (self.ts, self.et, self.name, self.proc, self.partner,
                    self.size):
            lst.clear()

    def flush(self) -> dict:
        """Spill the buffer to the sink as one durable commit, refresh the
        heartbeat, clear the buffer.  Returns the shard watermark.  No-op
        buffer still commits (syncs) and heartbeats — an idle rank keeps
        proving it is alive."""
        if self.sink is None:
            raise RuntimeError("Tracer has no sink to flush to")
        if self._closed:
            raise RuntimeError("Tracer is closed")
        if self._writer is None:
            from ..readers.pack import PackWriter
            self._writer = PackWriter.open_append(
                self.sink, chunk_rows=self._chunk_rows, fsync=self._fsync)
        n = len(self.ts)
        if n:
            self._last_ts = int(self.ts[-1])
            self._writer.append(self._tail_frame())
            self._clear()
        wm = self._writer.commit()
        self._flushed_events += n
        self._flush_seq += 1
        self._last_hb = self._wall()
        write_heartbeat(self.sink, self.process, self._flushed_events,
                        self._last_ts, self._flush_seq, wall=self._last_hb)
        return wm

    def close(self, finalize: bool = True, sidecar: bool = False) -> None:
        """Flush the tail and stop writing.  ``finalize=True`` seals the
        shard's footer (it becomes an ordinary pack; ``sidecar=True`` also
        derives/stores the structure sidecar — one whole-shard pass).  The
        final heartbeat is marked ``final`` so monitors report a clean
        shutdown instead of a dead rank."""
        if self.sink is None or self._closed:
            self._closed = True
            return
        self.flush()
        if self._writer is not None and finalize:
            self._writer.finalize(sidecar=sidecar)
        elif self._writer is not None:
            self._writer._out.close()
        write_heartbeat(self.sink, self.process, self._flushed_events,
                        self._last_ts, self._flush_seq, final=True)
        self._writer = None
        self._closed = True

    # -- output ----------------------------------------------------------------
    def to_trace(self, label: Optional[str] = None) -> "Trace":
        """The buffered events as an in-memory Trace.  With a sink this is
        only the unflushed tail — open the shard (``Trace.open(sink,
        live=True)``) for everything committed."""
        from ..core.frame import EventFrame
        from ..core.trace import Trace
        ev = EventFrame({
            TS: np.asarray(self.ts, np.float64),
            ET: np.asarray(self.et),
            NAME: np.asarray(self.name),
            PROC: np.asarray(self.proc, np.int64),
            PARTNER: np.asarray(self.partner, np.int64),
            MSG_SIZE: np.asarray(self.size, np.float64),
            TAG: np.zeros(len(self.ts), np.int64),
        })
        return Trace.from_events(ev.sort_by([PROC, TS]), label=label)

    def save_jsonl(self, path: str) -> None:
        with open(path, "w") as f:
            for i in range(len(self.ts)):
                d: Dict = {"ts": int(self.ts[i]), "et": self.et[i],
                           "name": self.name[i], "proc": int(self.proc[i])}
                if self.partner[i] >= 0:
                    d["partner"] = int(self.partner[i])
                    d["size"] = float(self.size[i])
                f.write(json.dumps(d) + "\n")


# ---------------------------------------------------------------------------
# spans and counters: the program's own timing of its layers
# ---------------------------------------------------------------------------

_clock = time.perf_counter_ns
_get_ident = threading.get_ident
_modules = sys.modules
_current: contextvars.ContextVar = contextvars.ContextVar("repro_span",
                                                          default=None)
_lock = threading.Lock()
# name -> [count, total_ns, self_ns]
_totals: Dict[str, List[int]] = collections.defaultdict(lambda: [0, 0, 0])
_counters: Dict[str, int] = {}
_recorders: List["_Recorder"] = []
_request_ids = itertools.count(1)
_annotation = None                      # jax.profiler.TraceAnnotation


def _annotation_class():
    """``jax.profiler.TraceAnnotation`` once jax is imported, else None: a
    numpy-only process never loads jax."""
    global _annotation
    prof = getattr(_modules.get("jax"), "profiler", None)
    if prof is not None:
        _annotation = prof.TraceAnnotation
    return _annotation


class span:
    """A context manager timing one layer's work: ``with span("read.chunk"):``.

    The span's parent is the span current in this context, on any thread;
    a span with no parent starts a new request id, which its descendants
    share.  On exit it adds its duration and its self time (duration minus
    the child spans on its own thread) to the per-name totals of
    :func:`snapshot`; ``duration_ns`` then holds its duration.  ``attrs``
    go to the profiler annotation.  A generator must close its spans
    before it yields, so the consumer's time is never charged to it."""

    __slots__ = ("name", "attrs", "parent", "request", "thread", "t0", "t1",
                 "child_ns", "_token", "_ann")

    def __init__(self, name: str, **attrs):
        self.name = name
        self.attrs = attrs
        self.t1 = None

    def __enter__(self) -> "span":
        parent = self.parent = _current.get()
        self.request = (parent.request if parent is not None
                        else next(_request_ids))
        self.thread = _get_ident()
        self.child_ns = 0
        self._token = _current.set(self)
        # a profiler annotation only while a profiler is attached
        ann = _annotation or ("jax" in _modules and _annotation_class())
        if ann and ann.is_enabled():
            ann = ann(self.name, request=self.request, **self.attrs)
            ann.__enter__()
        else:
            ann = None
        self._ann = ann
        self.t0 = _clock()
        if _recorders:
            for r in _recorders:
                r.enter(self)
        return self

    def __exit__(self, *exc) -> None:
        t1 = self.t1 = _clock()
        if _recorders:
            for r in _recorders:
                r.leave(self)
        if self._ann is not None:
            self._ann.__exit__(None, None, None)
        if self._token is not None:
            _current.reset(self._token)
        dur = t1 - self.t0
        parent = self.parent
        if parent is not None and parent.thread == self.thread:
            parent.child_ns += dur
        # acquire/release: cheaper than a ``with`` block on this hot path
        _lock.acquire()
        try:
            tot = _totals[self.name]
            tot[0] += 1
            tot[1] += dur
            tot[2] += dur - self.child_ns
        finally:
            _lock.release()

    def end(self) -> None:
        """Close a span from :func:`begin`, on any thread."""
        self.__exit__()

    @property
    def duration_ns(self) -> Optional[int]:
        return None if self.t1 is None else self.t1 - self.t0


def begin(name: str, **attrs) -> span:
    """Open a span that is not made current and that :meth:`span.end`
    closes later, on any thread: a wait that starts on one thread and ends
    on another (a job queued for a worker thread)."""
    sp = span(name, **attrs)
    sp.__enter__()
    _current.reset(sp._token)
    sp._token = None
    return sp


def counter(name: str, n: int = 1) -> None:
    """Add ``n`` to the process-wide work count ``name``."""
    _lock.acquire()
    try:
        _counters[name] = _counters.get(name, 0) + n
    finally:
        _lock.release()


def count(name: str) -> int:
    """The process-wide work count ``name`` so far."""
    return _counters.get(name, 0)


def snapshot() -> dict:
    """The process-wide totals since start: ``{"spans": {name: {"count",
    "total_ns", "self_ns"}}, "counters": {name: n}}``.  Totals only grow:
    read a layer's time over an interval as the difference of two
    snapshots."""
    with _lock:
        return {"spans": {n: {"count": c, "total_ns": t, "self_ns": s}
                          for n, (c, t, s) in _totals.items()},
                "counters": dict(_counters)}


class _Recorder:
    """Forwards span boundaries into a :class:`Tracer`, one process per
    thread (numbered in order of first appearance).  Its lock serialises
    the threads' events, so the tracer itself takes none."""

    def __init__(self, tracer: Tracer):
        self.tracer = tracer
        self.procs: Dict[int, int] = {}
        self._lock = threading.Lock()

    def _proc(self, thread: int) -> int:
        return self.procs.setdefault(thread, len(self.procs))

    def enter(self, sp: span) -> None:
        with self._lock:
            self.tracer.enter(sp.name, self._proc(sp.thread), ts=sp.t0)

    def leave(self, sp: span) -> None:
        with self._lock:
            self.tracer.leave(sp.name, self._proc(sp.thread), ts=sp.t1)


@contextlib.contextmanager
def record_spans(tracer: Tracer):
    """Record every span opened inside the block into ``tracer`` (whose
    clock must be ``time.perf_counter_ns``, the default), each thread as
    one Pipit process.  Feed the tracer nothing else meanwhile: only the
    recorder serialises its threads' events.  A span from :func:`begin` is
    recorded on the thread that opened it.  ``tracer.to_trace().
    flat_profile(metrics=["time.exc"])`` then gives each span name's self
    time.  Spans of requests that interleave on one thread (coroutines of
    concurrent requests) do not nest; record one request at a time
    there."""
    rec = _Recorder(tracer)
    _recorders.append(rec)
    try:
        yield tracer
    finally:
        _recorders.remove(rec)
