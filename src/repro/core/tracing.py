"""Region tracing — the Score-P analogue (§II-D) — and program spans.

``RegionTracer`` records host-timestamped, nested application regions in a
unified timebase (``time.perf_counter_ns``).  Its cost was measured on a
TPU v5e host with the ``chipbench`` cells (PERF.md): traced counter runs
with and without the program spans below read the same rate (57.82 and
57.78 M reads/s), and the profiler and spans together cost a counter job
2.0% and a replay job 0.6%; untraced, a span site is one check.
``LiveSampler`` is the APAPI analogue: a dedicated thread polling sensors
asynchronously so instrumentation never blocks application threads.

Both buffers are bounded for 24/7 streaming runs: pass ``max_events`` /
``max_samples`` to keep only the newest entries (a ring — the OLDEST
entry is dropped and counted in ``.dropped``), and drain periodically
with ``flush()``.  ``health.HealthRegistry.track_tracer`` /
``track_sampler`` export the buffer depth and drop counters.

Program spans: ``span(name)`` marks a layer boundary of the program's hot
paths (``fleet.*``, ``stage.*``, ``serve.*``).  It records only while a
``jax.profiler`` session records (``jax.profiler.trace``,
``start_trace`` or a client of ``start_server``); otherwise it returns one
shared null span and costs one ``TraceAnnotation.is_enabled()`` check.
While recording, each span lands twice: as a ``RegionEvent`` on
``PROGRAM`` (absolute ``perf_counter`` seconds) and as a
``repro.<name>`` event in the profiler trace with the stats ``span_id``,
``parent``, ``n``, ``rid`` and ``slot``.  The two pair one to one through
``span_id``, so every program span sits on the device trace's clock.
Spans recorded after the fact (``add_span``) live on ``PROGRAM`` only.
"""
from __future__ import annotations

import collections
import contextlib
import dataclasses
import itertools
import threading
import time
from typing import Callable, Optional

import numpy as np
from jax.profiler import TraceAnnotation


@dataclasses.dataclass
class RegionEvent:
    name: str
    t_start: float       # seconds, unified timebase
    t_end: float
    depth: int
    device: int = -1     # -1 = host region
    step: int = -1
    slot: int = -1       # -1 = engine-global (serve: batch slot id)
    span_id: int = -1    # process-wide, monotonic in opening order
    parent: int = -1     # span_id of the enclosing span; -1 at a root
    n: int = -1          # work done in the span (columns, reads, steps)


_span_ids = itertools.count()


class RegionTracer:
    """Nested region recording with a unified monotonic timebase.

    max_events: ring capacity; None (default) keeps every event.  When
    the ring is full each append evicts the oldest event and increments
    ``dropped`` — long streaming runs should size the ring to the flush
    cadence and drain with ``flush()``.
    """

    def __init__(self, timebase: Optional[Callable[[], float]] = None,
                 max_events: Optional[int] = None):
        self._now = timebase or (lambda: time.perf_counter_ns() * 1e-9)
        self.max_events = max_events
        self.events: collections.deque = collections.deque()
        self.dropped = 0
        self._local = threading.local()
        self._lock = threading.Lock()
        self.t0 = self._now()

    @property
    def _stack(self) -> list:
        """Span ids open on THIS thread, innermost last: a span opened on
        the ingest or sampler thread never takes a parent from another."""
        stack = getattr(self._local, "stack", None)
        if stack is None:
            stack = self._local.stack = []
        return stack

    def _append(self, ev: RegionEvent) -> None:
        with self._lock:        # spans may close on several threads
            if (self.max_events is not None
                    and len(self.events) >= self.max_events):
                self.events.popleft()
                self.dropped += 1
            self.events.append(ev)

    def now(self) -> float:
        return self._now() - self.t0

    @contextlib.contextmanager
    def region(self, name: str, *, device: int = -1, step: int = -1,
               slot: int = -1):
        stack = self._stack
        parent = stack[-1] if stack else -1
        sid = next(_span_ids)
        t_s = self.now()
        stack.append(sid)
        try:
            yield
        finally:
            stack.pop()
            self._append(RegionEvent(name, t_s, self.now(), len(stack),
                                     device, step, slot, sid, parent))

    def add_region(self, name, t_start, t_end, *, depth=0, device=-1,
                   step=-1, slot=-1, span_id=-1, parent=-1, n=-1):
        """Record an externally-timed region (e.g. replayed traces)."""
        self._append(RegionEvent(name, t_start, t_end, depth, device, step,
                                 slot, span_id, parent, n))

    def flush(self) -> list:
        """Drain and return the buffered events (oldest first); the
        cumulative ``dropped`` counter is left untouched."""
        out = list(self.events)
        self.events.clear()
        return out

    def phases(self, *, depth: Optional[int] = None, name=None,
               slot: Optional[int] = None):
        """(name, t_start, t_end) tuples, sorted by start time.

        ``slot=`` filters to one serve-engine batch slot (slot-scoped
        regions carry the slot id; engine-global regions are slot=-1).
        """
        evs = list(self.events)
        if depth is not None:
            evs = [e for e in evs if e.depth == depth]
        if name is not None:
            evs = [e for e in evs if e.name == name]
        if slot is not None:
            evs = [e for e in evs if e.slot == slot]
        return sorted(((e.name, e.t_start, e.t_end) for e in evs),
                      key=lambda x: x[1])

    def to_arrays(self):
        names = sorted({e.name for e in self.events})
        name_id = {n: i for i, n in enumerate(names)}
        ev = sorted(self.events, key=lambda e: e.t_start)
        return {
            "names": names,
            "name_id": np.asarray([name_id[e.name] for e in ev], np.int32),
            "t_start": np.asarray([e.t_start for e in ev], np.float64),
            "t_end": np.asarray([e.t_end for e in ev], np.float64),
            "depth": np.asarray([e.depth for e in ev], np.int32),
            "device": np.asarray([e.device for e in ev], np.int32),
            "step": np.asarray([e.step for e in ev], np.int32),
            "slot": np.asarray([e.slot for e in ev], np.int32),
        }


#: Ring capacity of the program's span store.  A traced benchmark window
#: records ~7,200 spans (73 counter jobs of 99); the ring holds several.
PROGRAM_RING = 1 << 16

#: The program's span store.  Its times are absolute ``perf_counter``
#: seconds (``t0`` = 0): the clock of ``StreamPipeline.stage_wall_s`` and
#: of a benchmark's own host spans.
PROGRAM = RegionTracer(max_events=PROGRAM_RING)
PROGRAM.t0 = 0.0

recording = TraceAnnotation.is_enabled


class _Span:
    """One open program span (see the module docstring)."""

    __slots__ = ("name", "n", "rid", "slot", "stats", "span_id", "parent",
                 "t_start", "t_end", "_ann")

    def __init__(self, name, n, rid, slot, stats):
        self.name, self.n, self.rid, self.slot = name, n, rid, slot
        self.stats = stats
        self.t_end = None

    # Each clock read sits next to the profiler's own (the annotation's
    # enter and exit), so that the two pair at one offset.
    def __enter__(self):
        stack = PROGRAM._stack
        self.parent = stack[-1] if stack else -1
        self.span_id = next(_span_ids)
        stack.append(self.span_id)
        self._ann = TraceAnnotation(
            "repro." + self.name, span_id=self.span_id, parent=self.parent,
            n=self.n, rid=self.rid, slot=self.slot, **self.stats)
        self.t_start = PROGRAM.now()
        self._ann.__enter__()
        return self

    def clock(self, t_start: float, t_end: float) -> None:
        """Take the span's times from the caller's own reads of ``now()``
        (so that a duration it also keeps equals the span's)."""
        self.t_start, self.t_end = t_start, t_end

    def __exit__(self, *exc):
        if self.t_end is None:
            self.t_end = PROGRAM.now()
        self._ann.__exit__(*exc)
        stack = PROGRAM._stack
        stack.pop()
        PROGRAM._append(RegionEvent(
            self.name, self.t_start, self.t_end, len(stack), step=self.rid,
            slot=self.slot, span_id=self.span_id, parent=self.parent,
            n=self.n))
        return False


class _NullSpan:
    """What ``span`` returns while no profiler session records."""

    t_start = t_end = float("nan")

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False

    def clock(self, t_start: float, t_end: float) -> None:
        pass


_NULL = _NullSpan()


def now() -> float:
    """The program spans' clock: ``perf_counter`` seconds."""
    return PROGRAM.now()


def span(name: str, *, n: int = -1, rid: int = -1, slot: int = -1,
         **stats):
    """Context manager marking ``name`` as a program span while a profiler
    session records.  ``n``: the work done in it; ``rid``/``slot``: the
    request and batch slot it serves (kept in ``RegionEvent.step`` and
    ``.slot``, as the serve engine's slot regions keep them).  Further
    integer ``stats`` go on the profiler event only."""
    if not recording():
        return _NULL
    return _Span(name, n, rid, slot, stats)


def add_span(name: str, t_start: float, t_end: float, *, n: int = -1,
             rid: int = -1, slot: int = -1) -> None:
    """Record a program span whose start is known only after the fact (a
    wait), under the span open on this thread, while a profiler session
    records.  It lives on ``PROGRAM`` alone: the trace takes no past
    start."""
    if not (recording() and t_start <= t_end):
        return
    stack = PROGRAM._stack
    PROGRAM.add_region(name, t_start, t_end, depth=len(stack), step=rid,
                       slot=slot, span_id=next(_span_ids),
                       parent=stack[-1] if stack else -1, n=n)


class LiveSampler:
    """Dedicated sampling thread (APAPI analogue): polls ``read_fn`` at a
    requested cadence, recording (t_read, value) without touching the
    application thread.  Used by bench_overhead.py to validate the <1%
    instrumentation-overhead claim.

    max_samples: ring capacity; None keeps everything.  A full ring
    evicts the oldest sample per poll (counted in ``dropped``) so the
    buffer always holds the newest window; drain with ``flush()``.
    """

    def __init__(self, read_fn: Callable[[float], float],
                 interval_s: float = 1e-3,
                 timebase: Optional[Callable[[], float]] = None,
                 max_samples: Optional[int] = None):
        self._read = read_fn
        self._interval = interval_s
        self._now = timebase or (lambda: time.perf_counter_ns() * 1e-9)
        self._stop = threading.Event()
        self._thread = None
        self.max_samples = max_samples
        self.t_read: collections.deque = collections.deque()
        self.values: collections.deque = collections.deque()
        self.dropped = 0

    def start(self):
        self._stop.clear()
        self._thread = threading.Thread(target=self._run, daemon=True)
        self._thread.start()
        return self

    def _run(self):
        nxt = self._now()
        while not self._stop.is_set():
            t = self._now()
            if (self.max_samples is not None
                    and len(self.t_read) >= self.max_samples):
                self.t_read.popleft()
                self.values.popleft()
                self.dropped += 1
            self.t_read.append(t)
            self.values.append(self._read(t))
            nxt += self._interval
            delay = nxt - self._now()
            if delay > 0:
                self._stop.wait(delay)
            else:
                nxt = self._now()     # fell behind: resync (observed gap)

    def flush(self):
        """Drain and return (t_read, values) arrays for the buffered
        samples; the cumulative ``dropped`` counter keeps counting.
        Safe against the concurrent sampler thread: only the front of
        the deques is consumed while the thread appends at the back."""
        n = min(len(self.t_read), len(self.values))
        t = [self.t_read.popleft() for _ in range(n)]
        v = [self.values.popleft() for _ in range(n)]
        return (np.asarray(t, np.float64), np.asarray(v, np.float64))

    def stop(self):
        self._stop.set()
        if self._thread is not None:
            self._thread.join(timeout=2.0)
        return (np.asarray(self.t_read, np.float64),
                np.asarray(self.values, np.float64))
