#!/usr/bin/env python3
"""Run one cell (the same arguments as run.py, which is run unchanged)
under a watchdog, to find what a stall of seconds inside a measured
window is: near capacity a serving cell's p90 belongs to one such stall,
and a train cell loses a twelfth of its window to it (PERF.md section 7).

    python3 benchmarks/suite/tools/stall_watch.py <log> -- --workload ...

A step is a return of ``DeepSpeedEngine.train_batch`` or of
``ContinuousBatchingScheduler.step``. The log gets, once a second, the
steps so far, the process's CPU time and the machine's dirty pages,
load and pressure; ``STALL`` with every thread's Python stack when no
step has returned for ``stall_s`` seconds; and ``FROZEN`` when the
watchdog's own thread, which only sleeps, did not run for a second:
then either the whole process stood still (its CPU time did not
advance: look outside the process) or a native call held the
interpreter's lock (the native threads that burned CPU meanwhile are
listed: look there). Garbage collections of the oldest generation and
any over 50 ms are logged too.
"""

import faulthandler
import gc
import os
import runpy
import sys
import threading
import time

SUITE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(os.path.dirname(SUITE))


def thread_cpu():
    """``{tid: (name, cpu seconds)}`` of this process's native threads;
    empty where ``/proc`` does not say."""
    res = {}
    try:
        tck = os.sysconf("SC_CLK_TCK")
        tids = os.listdir("/proc/self/task")
    except (OSError, ValueError):
        return res
    for tid in tids:
        try:
            with open(f"/proc/self/task/{tid}/stat") as f:
                stat = f.read()
        except OSError:         # the thread ended meanwhile
            continue
        fields = stat[stat.rindex(")") + 2:].split()
        res[tid] = (stat[stat.index("(") + 1:stat.rindex(")")],
                    (int(fields[11]) + int(fields[12])) / tck)
    return res


def machine():
    """Dirty and written-back pages, load and pressure, on one line."""
    def read(path):
        try:
            with open(path) as f:
                return f.read().strip().replace("\n", " | ")
        except OSError as e:
            return type(e).__name__

    keep = ("Dirty", "Writeback", "MemAvailable", "Cached")
    mem = {}
    try:
        with open("/proc/meminfo") as f:
            for line in f:
                key, _, value = line.partition(":")
                if key in keep:
                    mem[key] = value.strip()
    except OSError:
        pass
    return (f"meminfo {mem} loadavg {read('/proc/loadavg')} "
            f"psi_io {read('/proc/pressure/io')} "
            f"psi_cpu {read('/proc/pressure/cpu')}")


class Watchdog:
    """Counts steps (``beat()``) and writes to ``out`` what the module's
    docstring says, from a daemon thread, until ``stop()``."""

    def __init__(self, out, stall_s=0.6, after_steps=8, poll_s=0.05):
        self.out, self.stall_s = out, stall_s
        self.after_steps, self.poll_s = after_steps, poll_s
        self.t0 = time.perf_counter()
        self.steps, self.last_step = 0, self.t0
        self._stop = threading.Event()
        self._gc_start = 0.0
        self._thread = threading.Thread(target=self._watch, daemon=True,
                                        name="stall-watchdog")

    def start(self):
        gc.callbacks.append(self._on_gc)
        self._thread.start()

    def stop(self):
        self._stop.set()
        self._thread.join(timeout=5)
        if self._on_gc in gc.callbacks:
            gc.callbacks.remove(self._on_gc)

    def beat(self):
        self.steps += 1         # one writer: the thread that steps
        self.last_step = time.perf_counter()

    def _say(self, msg):
        self.out.write(f"[{time.perf_counter() - self.t0:8.3f}s] {msg}\n")

    def _on_gc(self, phase, info):
        if phase == "start":
            self._gc_start = time.perf_counter()
            return
        took = time.perf_counter() - self._gc_start
        if info.get("generation", 0) >= 2 or took > 0.05:
            self._say(f"gc gen{info.get('generation')} took {took:.3f}s, "
                      f"collected {info.get('collected')}")

    def _watch(self):
        in_stall, sampled = False, 0.0
        woke = time.perf_counter()
        cpu_then, process_then = thread_cpu(), time.process_time()
        while not self._stop.wait(self.poll_s):
            now = time.perf_counter()
            if now - woke > 1.0:
                cpu_now = thread_cpu()
                busy = sorted(((cpu - cpu_then.get(tid, ("", 0.0))[1], name,
                                tid) for tid, (name, cpu) in
                               cpu_now.items()), reverse=True)[:8]
                self._say(
                    f"FROZEN for {now - woke:.2f}s (steps {self.steps}): "
                    f"process cpu +{time.process_time() - process_then:.2f}s"
                    f" since the sample {now - sampled:.2f}s ago; threads "
                    f"by cpu since then: "
                    f"{[(round(c, 2), n, t) for c, n, t in busy]}; "
                    f"{len(cpu_now)} threads")
            woke = now
            gap = now - self.last_step
            if now - sampled > 1.0:
                sampled = now
                cpu_then, process_then = thread_cpu(), time.process_time()
                self._say(f"steps {self.steps} cpu {process_then:.2f} "
                          f"{machine()}")
            if self.steps < self.after_steps:
                continue
            if gap > self.stall_s and not in_stall:
                in_stall = True
                self._say(f"STALL: no step returned for {gap:.2f}s after "
                          f"step {self.steps}; {machine()}")
                faulthandler.dump_traceback(file=self.out, all_threads=True)
            elif in_stall and gap < self.stall_s / 2:
                in_stall = False
                self._say(f"stall over at step {self.steps}")


def beat_on_return(cls, method, dog):
    """``cls.method`` calls ``dog.beat()`` after it returns."""
    real = getattr(cls, method)

    def stepped(self, *args, **kwargs):
        out = real(self, *args, **kwargs)
        dog.beat()
        return out

    setattr(cls, method, stepped)


def main(argv):
    if "--" not in argv or argv.index("--") != 1:
        print(__doc__, file=sys.stderr)
        return 2
    sys.path.insert(0, ROOT)
    from deepspeed_tpu.inference.scheduler import (
        ContinuousBatchingScheduler)
    from deepspeed_tpu.runtime.engine import DeepSpeedEngine

    with open(argv[0], "w", buffering=1) as out:
        dog = Watchdog(out)
        beat_on_return(DeepSpeedEngine, "train_batch", dog)
        beat_on_return(ContinuousBatchingScheduler, "step", dog)
        dog.start()
        try:
            sys.argv = [os.path.join(SUITE, "run.py")] + argv[2:]
            runpy.run_path(sys.argv[0], run_name="__main__")
        except SystemExit as e:
            return e.code or 0
        finally:
            dog.stop()
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
