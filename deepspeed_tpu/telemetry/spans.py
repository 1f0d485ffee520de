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

The ring keeps two classes of record apart. The per-step spans, a
dozen a step for as long as the process runs, wrap. What happens a few
times in a process's life and explains the rest is *kept*
(``ring.keep``): the spans under ``setup/`` (an engine's
construction), the compile ledger's ``.../jax/trace``, ``/jax/lower``,
``/jax/backend_compile`` (`telemetry/compile_cache.py`) and the
collector's ``.../gc`` (:class:`Collector`), and the spans of that
thread such a record fell in (the step that compiled, with its
counters). 200,000 step spans later ``ring.recent()`` still returns
them, merged among the rest by close time. The kept side is bounded
too, at the newest :data:`RING_SIZE`: a server that has run long
enough to keep that many collections and compiles no longer holds its
set-up.

A ``Span`` with no session *is* the telemetry-off path, in training as
in serving: it lands in the ring and feeds nothing else (its cost is
pinned by the overhead micro-benchmark test). A step's span
(``serve/step``, ``train/step``) also carries ``gc_s``, the
collector's seconds over it, and every 50 ms or more the thread's CPU
seconds beside the wall's (:class:`CpuMark`).

Every thread's span stack is also registered in a process-global map so
the forensics layer (`telemetry/flight.py`, `telemetry/watchdog.py`)
can read *other* threads' in-flight phase paths — thread-locals are
invisible cross-thread, and "which phase is the main thread stuck in"
is exactly what a hang dump must answer. :func:`live_phase_paths`
snapshots that map.
"""

import collections
import gc
import heapq
import operator
import threading
import time

try:                                     # annotations are optional:
    from jax.profiler import TraceAnnotation   # telemetry must work in
except Exception:                        # jax-less tools (the CLI).
    TraceAnnotation = None

clock = time.perf_counter
RING_SIZE = 65536
KEPT_PREFIX = "setup/"      # a span under this path is kept
_close = operator.itemgetter(2)


def _snapshot(records):
    while True:
        try:
            return list(records)
        except RuntimeError:            # appended to while copying
            continue


class SpanRing:
    """Bounded ring of closed spans ``(path, t0, t1, attrs)`` on
    :data:`clock`. Appends come from any thread (``deque.append`` is
    atomic); ``dropped`` is a forensic count, exact with one writer.

    ``keep`` takes the records that must outlive any number of steps
    (set-up, compiles, collector pauses) into a deque of their own, as
    long again, so the per-step records cannot push them out;
    ``dropped`` counts the per-step side alone."""

    def __init__(self, maxlen=RING_SIZE):
        self.records = collections.deque(maxlen=maxlen)
        self.kept = collections.deque(maxlen=maxlen)
        self.dropped = 0

    def append(self, rec):
        records = self.records
        if len(records) == records.maxlen:
            self.dropped += 1
        records.append(rec)

    def keep(self, rec):
        self.kept.append(rec)

    def recent(self, since=None):
        """A snapshot of both classes, merged by close time (each class
        in the order it was appended: the per-step records as before
        there was a second class); with ``since`` only the records that
        closed at or after that clock reading."""
        snap = list(heapq.merge(_snapshot(self.kept),
                                _snapshot(self.records), key=_close))
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


def keep_under_open_span(leaf, t0, t1, attrs):
    """One kept record ``<path of the span open in this thread>/<leaf>``
    (bare ``leaf`` where none is), with the ``step`` of the innermost
    open span that carries one added to ``attrs``: how the compile
    ledger and the collector put what happened where it happened. The
    spans of this thread it fell in are kept with it when they close."""
    stack = _stack()
    for span in stack:
        span._keep = True
        if span.attrs is not None and "step" in span.attrs:
            attrs["step"] = span.attrs["step"]
    path = stack[-1].path if stack else None
    ring.keep((f"{path}/{leaf}" if path else leaf, t0, t1, attrs))


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
                 "_annotation", "_keep")

    def __init__(self, name, session=None, attrs=None):
        self.name = name
        self.path = name
        self.attrs = attrs
        self.duration_s = None
        self._session = session
        self._t0 = None
        self._annotation = None
        self._keep = False

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
        self._keep = self.path.startswith(KEPT_PREFIX)
        self._t0 = clock()
        return self

    def __exit__(self, exc_type, exc, tb):
        t1 = clock()
        self.duration_s = t1 - self._t0
        rec = (self.path, self._t0, t1, self.attrs)
        # a set-up span, or one that held a kept record (a compile's
        # step stays with it), is kept; the rest wrap
        if self._keep:
            ring.keep(rec)
        else:
            ring.append(rec)
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


GC_RECORD_S = 1e-3      # a shorter collection of generation 0 or 1 is
#                         only tallied


class Collector:
    """Python's collector on the ring: the one ``gc.callbacks`` entry
    (:func:`install_collector`). Every collection of generation 1 or 2
    adds to ``seconds`` (a step's span carries its delta as ``gc_s``)
    and to ``by_generation`` (``[count, seconds]`` each); one of
    generation 2, or of :data:`GC_RECORD_S` or more, also becomes a kept
    ring record ``<open span's path>/gc`` with ``generation`` and
    ``collected``. Generation 0 is counted and not timed: a pass over a
    few hundred young objects takes ~20 us, a serving step sets off one
    to four of them, and the two clock readings cost as much again
    where the clock is slow. The interpreter runs a collection, and
    this, in the thread whose allocation set it off, with the lock
    held."""

    def __init__(self):
        self.seconds = 0.0
        self.by_generation = [[0, 0.0] for _ in range(3)]
        self._t0 = None

    def __call__(self, phase, info):
        gen = info["generation"]
        if gen == 0:
            if phase == "stop":
                self.by_generation[0][0] += 1
            return
        if phase == "start":
            self._t0 = clock()
            return
        t0, self._t0 = self._t0, None
        if t0 is None:          # installed in the middle of one
            return
        t1 = clock()
        self.seconds += t1 - t0
        tally = self.by_generation[gen]
        tally[0] += 1
        tally[1] += t1 - t0
        if gen == 2 or t1 - t0 >= GC_RECORD_S:
            keep_under_open_span("gc", t0, t1, {
                "generation": gen, "collected": info["collected"]})


collector = Collector()


def install_collector():
    """Put :data:`collector` among ``gc.callbacks`` (idempotent)."""
    if collector not in gc.callbacks:
        gc.callbacks.append(collector)


CPU_MARK_S = 0.05


class CpuMark:
    """The calling thread's CPU clock beside the wall clock, read where
    a step closes but at most once every :data:`CPU_MARK_S` of wall:
    ``stamp(attrs)`` then sets ``cpu_s`` and ``cpu_wall_s``, the
    thread's CPU seconds and the wall seconds since the mark before (a
    step of 50 ms or more always gets them; of shorter steps the first
    to close 50 ms after the last mark). Wall without CPU is a blocked
    or descheduled thread, wall with CPU is Python or a native call
    burning it: what a stall of a tenth of a second or more needs to be
    read afterwards. Not once a step, because the clock is a system
    call: 0.5 us on a bare kernel, 34 us where it was measured under a
    sandboxed one (with a resolution of 10 ms), 1 % of a 6.5 ms decode
    step for two of them. One mark belongs to one thread; another
    thread's first stamp only moves the mark."""

    __slots__ = ("t", "cpu", "thread")

    def __init__(self):
        self.t, self.cpu = clock(), time.thread_time()
        self.thread = threading.get_ident()

    def stamp(self, attrs):
        now = clock()
        if now - self.t < CPU_MARK_S:
            return
        cpu, thread = time.thread_time(), threading.get_ident()
        if thread == self.thread:
            attrs["cpu_s"] = cpu - self.cpu
            attrs["cpu_wall_s"] = now - self.t
        self.t, self.cpu, self.thread = now, cpu, thread
