"""What every driver needs from the harness: host spans and series, the
compile counter, seeded weights, device facts."""

import contextlib
import dataclasses
import shutil
import tempfile
import time

from benchmarks.suite import xplane

COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"

clock = time.perf_counter


class Recorder:
    """Host spans around calls into a layer, kept in memory.

    ``collect``: a closed span adds its duration (seconds) to
    ``series[name]``. ``annotate``: the span is also written into the
    profiler's trace (``jax.profiler.TraceAnnotation``), so a device gap
    can be laid to what the host was doing. Both are off in an
    end-to-end run's window except where a driver says otherwise."""

    def __init__(self):
        self.series = {}
        self.collect = False
        self.annotate = False

    def add(self, name, value):
        if self.collect:
            self.series.setdefault(name, []).append(float(value))

    @contextlib.contextmanager
    def span(self, name):
        if not (self.collect or self.annotate):
            yield
            return
        with contextlib.ExitStack() as stack:
            if self.annotate:
                import jax
                stack.enter_context(jax.profiler.TraceAnnotation(
                    xplane.SPAN_PREFIX + name))
            t0 = clock()
            try:
                yield
            finally:
                self.add(name, clock() - t0)


class CompileCounter:
    """Counts backend compiles (persistent-cache reads included): any
    inside a measured window means a shape was not warmed up."""

    def __init__(self):
        from jax import monitoring
        self.n = 0
        monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, name, secs, **kw):
        if name == COMPILE_EVENT:
            self.n += 1


@dataclasses.dataclass
class Context:
    """One run of one cell, as a driver and the readers see it."""
    cell: dict              # the BENCHMARK.json workloads entry
    workload: dict          # workloads/<cell>.json
    config: dict            # the configuration's file
    seed: int
    seconds: float
    trace: bool
    t_process: float        # clock() at process start
    devices: list
    peaks: dict
    log: object             # log(str): a progress line to stderr
    recorder: Recorder = dataclasses.field(default_factory=Recorder)
    compiles: CompileCounter = None
    keep_trace: str = None  # tools only: copy the raw trace here


class Profiler:
    """The JAX profiler round one segment of a traced run. While it runs
    the recorder annotates (host spans go into the trace) and does not
    collect (a traced host is slower, so its durations are not kept)."""

    def __init__(self, ctx):
        self.ctx = ctx
        self.dir = None

    @property
    def running(self):
        return self.dir is not None

    def start(self):
        import jax
        self.dir = tempfile.TemporaryDirectory(prefix="bench_trace_")
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        rec = self.ctx.recorder
        rec.collect, rec.annotate = False, True
        jax.profiler.start_trace(self.dir.name, profiler_options=opts)

    def stop(self):
        """Stops the profiler and returns the ``xplane.Trace`` (None if
        no device plane holds an op)."""
        import jax
        jax.profiler.stop_trace()
        self.ctx.recorder.annotate = False
        try:
            if self.ctx.keep_trace:
                shutil.copytree(self.dir.name, self.ctx.keep_trace,
                                dirs_exist_ok=True)
            return xplane.load(self.dir.name)
        finally:
            self.dir.cleanup()
            self.dir = None


@dataclasses.dataclass
class Result:
    """What a driver hands back."""
    correct: bool
    attempted: int
    failed: int
    setup_s: float
    end_to_end: dict        # metric name -> value
    facts: dict             # what per-layer readers need besides series
    detail: dict            # medians, counts, checks: an earlier line
    trace: object = None    # xplane.Trace of the profiled segment


def gpt2_model(config, group, **extra):
    """The program's model for a configuration file's ``train`` or
    ``serve`` group."""
    import jax.numpy as jnp
    from deepspeed_tpu.models.gpt2 import GPT2Config, GPT2LMHead

    g = config[group]
    cfg = GPT2Config(
        vocab_size=config["vocab_size"], n_positions=config["n_positions"],
        n_embd=config["n_embd"], n_layer=config["n_layer"],
        n_head=config["n_head"], dropout=config["resid_pdrop"],
        dtype=getattr(jnp, g["compute_dtype"]),
        param_dtype=getattr(jnp, g["param_dtype"]),
        use_flash_attention=bool(g.get("use_flash_attention", False)),
        **extra)
    return GPT2LMHead(cfg)


def seeded_params(model, seed, mesh=None):
    """The model's weights from the seed, on the device, in one jitted
    call, in the type they are run in; with a ``mesh``, replicated on
    every chip of it by that same call (no second copy on the first
    chip). Initialised through the dense attention path (the same
    parameters; the flash kernel does not tile the 8-token dummy)."""
    import dataclasses as dc

    import jax
    import jax.numpy as jnp

    plain = type(model)(dc.replace(model.config, use_flash_attention=False))
    dummy = jnp.zeros((1, 8), jnp.int32)
    out = None
    if mesh is not None:
        from jax.sharding import NamedSharding, PartitionSpec
        out = NamedSharding(mesh, PartitionSpec())
    init = jax.jit(lambda key: plain.init({"params": key}, dummy)["params"],
                   out_shardings=out)
    return init(jax.random.PRNGKey(seed))


def memory_peak_bytes(devices):
    """Peak bytes in use on the fullest chip."""
    return max(int(d.memory_stats()["peak_bytes_in_use"]) for d in devices)
