"""Flight recorder: an always-on black box for "why did the run stop".

The telemetry layer answers "how fast was the run"; this module answers
the question the repo's own history keeps asking — two real
collective-rendezvous deadlocks (caught only statically), runs that
stalled in silence, guards that see bad *values* but not absent
*progress*. The :class:`FlightRecorder` keeps a bounded in-memory ring
of

- recent telemetry **events** (it plugs into the session's exporter
  fan-out, so it sees exactly what the JSONL log sees),
- **phase-span transitions** (enter/exit of every span scope: read at
  dump time from the process-wide span ring and the live span stacks
  of `telemetry/spans.py`; the recorder keeps no phase log of its
  own), and
- the compiled step's **collective confessions**
  (`parallel/collectives.py:SiteRecord` — which sites emitted which
  rings, captured at trace time),

and on demand dumps all of it — plus ``faulthandler``-style stacks of
every live Python thread and the per-thread in-flight span path —
atomically (tmp + rename, the resilience-checkpoint contract) to a
crash-dump directory. ``ds_tpu_metrics postmortem <dump>`` renders a
dump; `telemetry/watchdog.py` fires one on hangs.

Dumps are triggered by (see :func:`install_crash_hooks`):

- an **unhandled exception** (chained ``sys.excepthook``),
- **SIGTERM** (dump first, then the chained preemption handler runs) and
  **SIGQUIT** (dump + thread stacks on stderr; the process keeps
  running — the operator's "where is it stuck" signal),
- a **health-guard abort** (the engine dumps before raising), and
- the **hang watchdog** expiring.

Everything here is exception-contained: forensics must never be the
thing that kills the run.
"""

import json
import os
import signal
import sys
import threading
import time
import traceback
import collections

from deepspeed_tpu.telemetry import spans
from deepspeed_tpu.utils.logging import logger

FLIGHT_SCHEMA = "ds-tpu-flight/1"


def thread_stacks():
    """``faulthandler``-style stacks of all live Python threads, as
    structured data: ``[{name, ident, daemon, stack: [lines...]}]``."""
    names = {t.ident: t for t in threading.enumerate()}
    out = []
    for ident, frame in sys._current_frames().items():
        t = names.get(ident)
        out.append({
            "name": t.name if t is not None else f"thread-{ident}",
            "ident": ident,
            "daemon": bool(t.daemon) if t is not None else None,
            "stack": [ln.rstrip("\n")
                      for ln in traceback.format_stack(frame)],
        })
    return out


class FlightRecorder:
    """Bounded black-box ring + atomic crash dumps.

    Implements the exporter protocol (``export``/``close``) so a
    :class:`~deepspeed_tpu.telemetry.events.EventLog` fans events into
    the ring exactly like any other exporter; collective confessions
    arrive through the session hook; phase transitions are the span
    ring's (closed since this recorder was made, at most ``history``).
    """

    def __init__(self, dump_dir, history=512, meta=None):
        self.dump_dir = str(dump_dir)
        self.meta = dict(meta or {})
        self.history = int(history)
        self._events = collections.deque(maxlen=self.history)
        self._since = spans.clock()
        self._collectives = []
        self._lock = threading.Lock()
        self._dumps = 0

    # -- exporter protocol (events fan in) ----------------------------
    def export(self, event):
        with self._lock:
            self._events.append(dict(event))

    def close(self):
        pass

    # -- session hooks -------------------------------------------------
    def record_phase(self, kind, path, duration_s=None):
        """A span transition reported by hand (a ``Span`` needs no such
        call: it lands in the ring by itself). Only an ``"exit"`` with
        its duration is a closed span; an ``"enter"`` alone is not
        kept."""
        if kind == "exit" and duration_s is not None:
            t1 = spans.clock()
            spans.record(path, t1 - duration_s, t1)

    def _phase_log(self):
        """Enter/exit records, oldest first, from the ring's closed
        spans and the live stacks' open ones, on Unix-epoch seconds."""
        off = spans.epoch_offset()
        log = []
        for path, t0, t1, _ in spans.recent(self._since)[-self.history:]:
            log.append({"t": t0 + off, "kind": "enter", "path": path})
            log.append({"t": t1 + off, "kind": "exit", "path": path,
                        "duration_s": round(t1 - t0, 6)})
        for stack in spans.live_spans().values():
            log.extend({"t": t0 + off, "kind": "enter", "path": path}
                       for path, t0 in stack if t0 is not None)
        log.sort(key=lambda rec: rec["t"])
        return log[-self.history:]

    def record_collectives(self, records):
        """Stamp the step's trace-time :class:`SiteRecord` confessions
        (the last recorded set wins — one compiled step, one set)."""
        rows = []
        for r in records:
            if isinstance(r, dict):
                rows.append(dict(r))
            else:
                rows.append({"site": r.site, "axis": r.axis,
                             "primitive": r.primitive, "chunks": r.chunks,
                             "hops": r.hops, "chained": r.chained})
        with self._lock:
            if rows or not self._collectives:
                self._collectives = rows

    # -- dumping -------------------------------------------------------
    def snapshot(self, reason, extra=None):
        """The dump payload as a dict (no I/O)."""
        with self._lock:
            events = list(self._events)
            collectives = list(self._collectives)
        phases = self._phase_log()
        names = {t.ident: t.name for t in threading.enumerate()}
        in_flight = {names.get(ident, f"thread-{ident}"): path
                     for ident, path in spans.live_phase_paths().items()}
        snap = {
            "schema": FLIGHT_SCHEMA,
            "reason": reason,
            "t": time.time(),
            "pid": os.getpid(),
            "meta": dict(self.meta),
            "in_flight_phases": in_flight,
            "threads": thread_stacks(),
            "events": events,
            "phase_log": phases,
            "collectives": collectives,
        }
        if extra:
            snap.update(extra)
        return snap

    def dump(self, reason, extra=None):
        """Atomically write one dump file; returns its path (or None —
        a failing dump logs one warning and never raises)."""
        try:
            snap = self.snapshot(reason, extra=extra)
            os.makedirs(self.dump_dir, exist_ok=True)
            with self._lock:
                self._dumps += 1
                seq = self._dumps
            tag = str(reason).replace(":", "-").replace("/", "-")
            pidx = self.meta.get("process_index", 0)
            name = (f"flight-p{int(pidx):05d}-{tag}-"
                    f"{int(snap['t'] * 1000)}-{seq}.json")
            path = os.path.join(self.dump_dir, name)
            tmp = path + ".tmp"
            with open(tmp, "w") as f:
                json.dump(snap, f, indent=1, default=str)
                f.flush()
                os.fsync(f.fileno())
            os.replace(tmp, path)
            logger.warning("flight recorder: dumped %s record to %s",
                           reason, path)
            return path
        except Exception as e:   # pragma: no cover - disk-full etc.
            logger.warning("flight recorder: dump failed (%s)", e)
            return None

    # -- crash hooks ---------------------------------------------------
    def install(self, signals=(signal.SIGTERM, getattr(signal, "SIGQUIT",
                                                       None))):
        install_crash_hooks(self, signals=signals)
        return self

    def uninstall(self):
        uninstall_crash_hooks(self)


def read_dump(path):
    """Parse + validate one flight-recorder dump (the ``postmortem``
    CLI's loader). Raises ``ValueError`` on a non-dump JSON file."""
    with open(path) as f:
        dump = json.load(f)
    if not isinstance(dump, dict) or \
            dump.get("schema") != FLIGHT_SCHEMA:
        raise ValueError(
            f"{path} is not a flight-recorder dump "
            f"(expected schema {FLIGHT_SCHEMA!r}, "
            f"got {dump.get('schema') if isinstance(dump, dict) else dump!r})")
    return dump


# ---------------------------------------------------------------------------
# process-level crash hooks (one set per process; re-install swaps the
# target recorder, so tests / multiple engines never stack handlers)
# ---------------------------------------------------------------------------

_hooks = {"recorder": None, "excepthook": None, "signals": {}}


def _on_unhandled(exc_type, exc, tb):
    rec = _hooks["recorder"]
    if rec is not None:
        try:
            rec.dump("exception", extra={"exception": {
                "type": exc_type.__name__,
                "message": str(exc),
                "traceback": traceback.format_exception(exc_type, exc, tb),
            }})
        except Exception:   # pragma: no cover
            pass
    prev = _hooks["excepthook"]
    (prev or sys.__excepthook__)(exc_type, exc, tb)


def _on_signal(signum, frame):
    rec = _hooks["recorder"]
    name = signal.Signals(signum).name
    if rec is not None:
        try:
            rec.dump(f"signal:{name}")
        except Exception:   # pragma: no cover
            pass
    prev = _hooks["signals"].get(signum, (None,))[0]
    if signum == getattr(signal, "SIGQUIT", None):
        # Operator "where is it stuck" signal: stacks on stderr too
        # (the satellite faulthandler registration prints the same when
        # no recorder is installed), then keep running.
        try:
            import faulthandler
            faulthandler.dump_traceback(file=sys.stderr)
        except Exception:   # pragma: no cover
            pass
        if callable(prev):
            prev(signum, frame)
        return
    if callable(prev):
        prev(signum, frame)     # e.g. the preemption latch
    elif prev == signal.SIG_DFL:
        # restore + re-deliver so default semantics (terminate) hold
        signal.signal(signum, signal.SIG_DFL)
        os.kill(os.getpid(), signum)


def install_crash_hooks(recorder, signals=(signal.SIGTERM,
                                           getattr(signal, "SIGQUIT",
                                                   None))):
    """Point the process crash hooks at ``recorder``. First call chains
    ``sys.excepthook`` and the given signals; later calls only swap the
    recorder (handlers never stack). Off the main thread, signal
    chaining degrades to excepthook-only (CPython restriction)."""
    _hooks["recorder"] = recorder
    if _hooks["excepthook"] is None and sys.excepthook is not _on_unhandled:
        _hooks["excepthook"] = sys.excepthook
        sys.excepthook = _on_unhandled
    if threading.current_thread() is not threading.main_thread():
        return
    for sig in signals:
        if sig is None or sig in _hooks["signals"]:
            continue
        try:
            prev = signal.signal(sig, _on_signal)
        except (ValueError, OSError):   # pragma: no cover - exotic envs
            continue
        _hooks["signals"][sig] = (prev,)


def uninstall_crash_hooks(recorder=None):
    """Restore the chained hooks (tests). A no-op when ``recorder`` is
    given and is not the installed one."""
    if recorder is not None and _hooks["recorder"] is not recorder:
        return
    _hooks["recorder"] = None
    if _hooks["excepthook"] is not None:
        sys.excepthook = _hooks["excepthook"]
        _hooks["excepthook"] = None
    if threading.current_thread() is threading.main_thread():
        for sig, (prev,) in list(_hooks["signals"].items()):
            try:
                signal.signal(sig, prev)
            except (ValueError, OSError):   # pragma: no cover
                pass
            _hooks["signals"].pop(sig, None)
