"""Step-phase spans: nestable wall-time scopes correlated with xplane,
and the one in-memory ring every closed span lands in.

``Span("dispatch")`` (or ``session.span("dispatch")``) is a context
manager that (1) appends ``(path, t0, t1, attrs)`` to the process-wide
:data:`ring` when it closes, **whether or not a session is attached**,
(2) with a session also records the scope's wall seconds into the
session's per-step phase accumulator and the
``phase_seconds{phase=...}`` histogram, and (3) opens a
``jax.profiler.TraceAnnotation`` so the same scope shows up as a named
range in an xprof/xplane trace — host phases and device timelines line
up in one view. The annotation costs well under a microsecond while no
profiler runs.

:data:`clock` is *the* clock of spans and of every stamp the serving
path puts on a request (``time.perf_counter``; on Linux
``time.monotonic`` reads the same counter). :func:`epoch_offset` maps a
``clock`` reading onto Unix-epoch seconds, which is what the profiler's
host timeline counts from (less the profile's own start time, which the
trace file carries as ``profile_start_time``).

Spans nest: ``Span.path`` carries the full ``a/b`` nesting path
(per-thread). Exit is exception-safe — a phase that raises still
records its duration and closes its annotation before re-raising.
``attrs`` is a small dict the ring keeps *by reference*, so counters
that are only known at the end of the scope can be filled in before it
closes; ``rid`` joins the spans of one request, ``step`` those of one
scheduler step.

The ring is bounded (:data:`RING_SIZE` records; what falls out is
counted in ``ring.dropped``). :func:`recent` snapshots it,
:func:`record` appends a record that no context manager timed (the
scheduler's per-request ``serve/request`` record). The flight recorder
(`telemetry/flight.py`) builds its phase log from this ring and from
:func:`live_spans`; the benchmark's readers
(`benchmarks/suite/program_ring.py`) read it after a run.

The disabled fast path of the *training* engine is :func:`null_span`:
a module-level singleton whose ``__enter__``/``__exit__`` do nothing,
so an engine with telemetry off pays one attribute check + one no-op
context manager per phase (pinned by the overhead micro-benchmark
test).

Every thread's span stack is also registered in a process-global map so
the forensics layer (`telemetry/flight.py`, `telemetry/watchdog.py`)
can read *other* threads' in-flight phase paths — thread-locals are
invisible cross-thread, and "which phase is the main thread stuck in"
is exactly what a hang dump must answer. :func:`live_phase_paths`
snapshots that map.
"""

import collections
import threading
import time

try:                                     # annotations are optional:
    from jax.profiler import TraceAnnotation   # telemetry must work in
except Exception:                        # jax-less tools (the CLI).
    TraceAnnotation = None

clock = time.perf_counter
RING_SIZE = 65536


class SpanRing:
    """Bounded ring of closed spans ``(path, t0, t1, attrs)`` on
    :data:`clock`. Appends come from any thread (``deque.append`` is
    atomic); ``dropped`` is a forensic count, exact with one writer."""

    def __init__(self, maxlen=RING_SIZE):
        self.records = collections.deque(maxlen=maxlen)
        self.dropped = 0

    def append(self, rec):
        records = self.records
        if len(records) == records.maxlen:
            self.dropped += 1
        records.append(rec)

    def recent(self, since=None):
        """A snapshot, oldest first; with ``since`` only the records
        that closed at or after that clock reading."""
        while True:
            try:
                snap = list(self.records)
                break
            except RuntimeError:        # appended to while copying
                continue
        if since is None:
            return snap
        return [r for r in snap if r[2] >= since]


ring = SpanRing()


def record(path, t0, t1, attrs=None):
    """Append a record that was not timed by a context manager."""
    ring.append((path, t0, t1, attrs))


def recent(since=None):
    return ring.recent(since)


def epoch_offset():
    """Seconds to add to a :data:`clock` reading to get Unix-epoch
    seconds: the tightest of five back-to-back readings of both."""
    best = None
    for _ in range(5):
        a = time.perf_counter_ns()
        wall = time.time_ns()
        b = time.perf_counter_ns()
        if best is None or b - a < best[0]:
            best = (b - a, wall - (a + b) // 2)
    return best[1] * 1e-9


_local = threading.local()
# thread ident -> that thread's live span stack (the same list object
# _local.stack aliases); entries for exited threads are pruned on read
_live_stacks = {}


def _stack():
    stack = getattr(_local, "stack", None)
    if stack is None:
        stack = _local.stack = []
        _live_stacks[threading.get_ident()] = stack
    return stack


def live_spans():
    """``{thread_ident: [(path, t0), ...]}``, outermost first, for
    every thread currently inside at least one span. Reads are
    lock-free snapshots: a concurrently-mutating stack at worst yields
    a one-frame-stale answer, which is fine for forensics."""
    live = {t.ident for t in threading.enumerate()}
    out = {}
    for ident, stack in list(_live_stacks.items()):
        if ident not in live:
            _live_stacks.pop(ident, None)
            continue
        spans = [(s.path, s._t0) for s in list(stack)]
        if spans:
            out[ident] = spans
    return out


def live_phase_paths():
    """``{thread_ident: "a/b" in-flight span path}`` (the innermost
    open span of each thread)."""
    return {ident: spans[-1][0] for ident, spans in live_spans().items()}


def enclosing_attr(key, default=None):
    """``attrs[key]`` of the innermost open span of this thread that
    has it: how a callee's span takes over its caller's ``rid``."""
    for span in reversed(_stack()):
        if span.attrs is not None and key in span.attrs:
            return span.attrs[key]
    return default


class Span:
    """One timed, annotated scope; ``TelemetrySession.span`` makes one
    that also feeds the session."""

    __slots__ = ("name", "path", "attrs", "duration_s", "_session", "_t0",
                 "_annotation")

    def __init__(self, name, session=None, attrs=None):
        self.name = name
        self.path = name
        self.attrs = attrs
        self.duration_s = None
        self._session = session
        self._t0 = None
        self._annotation = None

    def __enter__(self):
        stack = _stack()
        if stack:
            self.path = f"{stack[-1].path}/{self.name}"
        stack.append(self)
        if TraceAnnotation is not None:
            self._annotation = TraceAnnotation(f"ds_tpu/{self.path}")
            self._annotation.__enter__()
        if self._session is not None:
            self._session._enter_phase(self.name, self.path)
        self._t0 = clock()
        return self

    def __exit__(self, exc_type, exc, tb):
        t1 = clock()
        self.duration_s = t1 - self._t0
        ring.append((self.path, self._t0, t1, self.attrs))
        try:
            if self._annotation is not None:
                self._annotation.__exit__(exc_type, exc, tb)
        finally:
            stack = _stack()
            if stack and stack[-1] is self:
                stack.pop()
            if self._session is not None:
                self._session._record_phase(self.name, self.path,
                                            self.duration_s)
        return False   # never swallow the phase's exception


class _NullSpan:
    """Singleton no-op context manager — the telemetry-off fast path."""

    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, exc_type, exc, tb):
        return False


_NULL_SPAN = _NullSpan()


def null_span(name=None):
    """Drop-in for ``session.span`` when telemetry is disabled."""
    return _NULL_SPAN
