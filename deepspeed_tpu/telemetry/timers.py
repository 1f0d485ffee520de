"""Wall-clock and throughput timers.

TPU-native analog of the reference's ``deepspeed/utils/timer.py``
(`utils/timer.py:26,106`): named synchronized timers and a throughput timer.
Where the reference calls ``torch.cuda.synchronize()`` before reading the
clock, we block on outstanding async XLA dispatch with
``jax.effects_barrier()`` (device work in JAX is async-dispatched; the barrier
is the TPU-correct way to make host wall-clock measurements meaningful).

These are the *synchronized* timers behind ``wall_clock_breakdown``; the
un-synchronized per-phase spans live in `telemetry/spans.py`.
"""

import time

from deepspeed_tpu.utils.logging import logger, log_dist


def _synchronize():
    try:
        import jax
        # Drains the async dispatch queue on all local devices.
        jax.effects_barrier()
    except Exception:
        pass


class SynchronizedWallClockTimer:
    """Group of named timers, synchronized against async device execution."""

    class Timer:
        def __init__(self, name):
            self.name_ = name
            self.elapsed_ = 0.0
            self.started_ = False
            self.start_time = time.time()

        def start(self):
            assert not self.started_, f"timer {self.name_} has already been started"
            _synchronize()
            self.start_time = time.time()
            self.started_ = True

        def stop(self, reset=False):
            assert self.started_, f"timer {self.name_} is not started"
            _synchronize()
            if reset:
                self.elapsed_ = time.time() - self.start_time
            else:
                self.elapsed_ += time.time() - self.start_time
            self.started_ = False

        def reset(self):
            self.elapsed_ = 0.0
            self.started_ = False

        def elapsed(self, reset=True):
            started_ = self.started_
            if self.started_:
                self.stop()
            elapsed_ = self.elapsed_
            if reset:
                self.reset()
            if started_:
                self.start()
            return elapsed_

    def __init__(self):
        self.timers = {}

    def __call__(self, name):
        if name not in self.timers:
            self.timers[name] = self.Timer(name)
        return self.timers[name]

    @staticmethod
    def memory_usage():
        """Per-device memory report (HBM analog of the CUDA alloc stats)."""
        try:
            import jax
            parts = []
            for d in jax.local_devices():
                stats = d.memory_stats() or {}
                in_use = stats.get("bytes_in_use", 0)
                limit = stats.get("bytes_limit", 0)
                parts.append(f"{d}: in_use {in_use / 2**30:.2f}GB "
                             f"limit {limit / 2**30:.2f}GB")
            return " | ".join(parts)
        except Exception:
            return "memory stats unavailable"

    def log(self, names, normalizer=1.0, reset=True, memory_breakdown=False, ranks=None):
        assert normalizer > 0.0
        string = "time (ms)"
        for name in names:
            if name in self.timers:
                elapsed_time = self.timers[name].elapsed(reset=reset) * 1000.0 / normalizer
                string += f" | {name}: {elapsed_time:.2f}"
        if memory_breakdown:
            string += " | " + self.memory_usage()
        log_dist(string, ranks=ranks or [0])


class ThroughputTimer:
    """Samples/sec tracker printed every ``steps_per_output`` steps."""

    def __init__(self,
                 batch_size,
                 num_workers,
                 start_step=2,
                 steps_per_output=50,
                 monitor_memory=False,
                 logging_fn=None):
        self.start_time = 0
        self.end_time = 0
        self.started = False
        self.batch_size = batch_size if batch_size else 1
        self.num_workers = num_workers
        self.start_step = start_step
        self.epoch_count = 0
        self.micro_step_count = 0
        self.global_step_count = 0
        self.total_elapsed_time = 0
        self.steps_per_output = steps_per_output
        self.monitor_memory = monitor_memory
        self.logging = logging_fn or logger.info
        self.initialized = False

    def update_epoch_count(self):
        self.epoch_count += 1
        self.micro_step_count = 0

    def _init_timer(self):
        self.initialized = True

    def start(self):
        self._init_timer()
        self.started = True
        if self.global_step_count >= self.start_step:
            _synchronize()
            self.start_time = time.time()

    def stop(self, report_speed=True):
        if not self.started:
            return
        self.started = False
        self.micro_step_count += 1
        self.global_step_count += 1
        if self.start_time > 0:
            _synchronize()
            self.end_time = time.time()
            duration = self.end_time - self.start_time
            self.total_elapsed_time += duration
            if report_speed and self.global_step_count % self.steps_per_output == 0:
                self.logging(
                    f"{self.global_step_count}/{self.micro_step_count}, "
                    f"SamplesPerSec={self.avg_samples_per_sec():.4f}")
                if self.monitor_memory:
                    self.logging(SynchronizedWallClockTimer.memory_usage())

    def avg_samples_per_sec(self):
        if self.global_step_count > 0 and self.total_elapsed_time > 0:
            samples_per_step = self.batch_size * self.num_workers
            total_step_offset = self.global_step_count - self.start_step
            avg_time_per_step = self.total_elapsed_time / max(total_step_offset, 1)
            return samples_per_step / avg_time_per_step
        return float("-inf")
