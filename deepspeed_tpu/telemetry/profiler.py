"""Device-time profiling: jax.profiler trace capture + per-step timings.

The reference's tracing story is host timers around engine phases plus
CUDA-event kernel timers (`utils/timer.py:26-104`, `csrc/includes/
StopWatch.h`); SURVEY §5.1 names the TPU equivalents: ``jax.profiler``
traces for xprof/tensorboard, synchronized host timers, and per-step
device-time deltas. This module supplies the trace capture and the
per-step record; `telemetry/timers.py` supplies the synchronized timers.

The trace window is the device half of the telemetry story — the
step-phase spans (`telemetry/spans.py`) emit ``TraceAnnotation``s, so a
trace captured by this window shows the host phases as named ranges on
the xplane timeline.

Config surface (engine ``wall_clock_breakdown`` drives the timers; this is
the trace window)::

    "profiling": {
        "trace_dir": "/tmp/tpu_trace",   # where xprof events go
        "trace_start_step": 10,           # first traced optimizer step
        "trace_num_steps": 3              # how many steps to capture
    }

The trace is viewable with tensorboard's profile plugin or xprof.
"""

import collections

from deepspeed_tpu.utils.logging import log_dist


_KNOWN_KEYS = ("trace_dir", "trace_start_step", "trace_num_steps",
               "history")


class TraceProfiler:
    """Captures a ``jax.profiler`` trace for a configured step window and
    keeps a rolling record of synchronized per-step durations."""

    def __init__(self, trace_dir=None, trace_start_step=0,
                 trace_num_steps=0, history=100, **unknown):
        if unknown:
            raise ValueError(
                f"unknown 'profiling' config keys {sorted(unknown)}; "
                f"supported: {list(_KNOWN_KEYS)}")
        self.trace_dir = trace_dir
        self.start_step = int(trace_start_step)
        self.num_steps = int(trace_num_steps)
        self._active = False
        self.armed_reason = None
        self.step_times = collections.deque(maxlen=history)

    @property
    def enabled(self):
        return self.trace_dir is not None and self.num_steps > 0

    def arm(self, start_step, num_steps, trace_dir=None, reason=None):
        """(Re-)point the capture window at a future step — the
        anomaly-triggered capture path (step-wall regression, recompile,
        guard trip arm the *next* ``num_steps`` steps). Re-arming after
        a window closed is supported; an in-flight window is never
        disturbed. Returns True when armed."""
        if self._active:
            return False
        if trace_dir is not None:
            self.trace_dir = str(trace_dir)
        if self.trace_dir is None or int(num_steps) <= 0:
            return False
        self.start_step = int(start_step)
        self.num_steps = int(num_steps)
        self.armed_reason = reason
        log_dist(f"profiler: armed {self.num_steps}-step trace window at "
                 f"step {self.start_step}"
                 f"{f' ({reason})' if reason else ''} -> {self.trace_dir}",
                 ranks=[0])
        return True

    def in_window(self, global_step):
        """True only for steps inside the trace window — the engine syncs
        per-step timing for these (plus wall_clock_breakdown runs), NOT
        for the whole run."""
        return self.enabled and (
            self.start_step <= global_step <
            self.start_step + self.num_steps)

    def before_step(self, global_step):
        if not self.enabled or self._active:
            return
        if self.in_window(global_step):
            import atexit
            import jax

            jax.profiler.start_trace(self.trace_dir)
            self._active = True
            # a window past the end of the run still flushes xprof files
            atexit.register(self.close)
            log_dist(f"profiler: trace started at step {global_step} "
                     f"-> {self.trace_dir}", ranks=[0])

    def after_step(self, global_step, duration=None):
        if duration is not None:
            self.step_times.append(duration)
        if self._active and \
                global_step >= self.start_step + self.num_steps - 1:
            self.close(global_step)

    def close(self, global_step=None):
        """Stop an in-flight trace (idempotent) — also called at interpreter
        exit so a run ending inside the window still flushes xprof files."""
        if not self._active:
            return
        import jax

        jax.profiler.stop_trace()
        self._active = False
        log_dist(f"profiler: trace stopped"
                 f"{f' after step {global_step}' if global_step is not None else ''}",
                 ranks=[0])

    def summary(self):
        """(mean, min, max) of recorded synchronized step seconds."""
        if not self.step_times:
            return None
        ts = list(self.step_times)
        return sum(ts) / len(ts), min(ts), max(ts)


def device_report(out=None):
    """Print the device/mesh/ICI picture (`ds_tpu_report`): platform,
    chip kind, per-device coords — the topology a mesh maps onto."""
    import sys

    out = out or sys.stdout
    try:
        import jax

        devices = jax.devices()
    except Exception as e:  # backend unavailable — report, don't crash
        print(f"devices: unavailable ({e})", file=out)
        return
    print("-" * 64, file=out)
    print("device / interconnect topology", file=out)
    print("-" * 64, file=out)
    print(f"{'platform':.<30} {devices[0].platform}", file=out)
    print(f"{'device kind':.<30} {devices[0].device_kind}", file=out)
    print(f"{'local devices':.<30} {len(jax.local_devices())}", file=out)
    print(f"{'global devices':.<30} {len(devices)}", file=out)
    print(f"{'processes':.<30} {jax.process_count()}", file=out)
    for d in devices[:16]:
        coords = getattr(d, "coords", None)
        core = getattr(d, "core_on_chip", None)
        extra = f" coords={coords}" if coords is not None else ""
        extra += f" core={core}" if core is not None else ""
        print(f"  device {d.id}: process={d.process_index}{extra}",
              file=out)
    if len(devices) > 16:
        print(f"  ... {len(devices) - 16} more", file=out)
