"""What a runner is handed: the clock, the devices, counters and spans."""

from __future__ import annotations

import contextlib
import time
from dataclasses import dataclass

from .reduce.xplane import SPAN_PREFIX


class CompileCounter:
    """Compile requests as `jax.monitoring` reports them (the idea of
    `chip_smoke.CacheCounters`): every program JAX had to compile or fetch
    from the persistent cache. Inside the measured window there are none."""

    REQUEST = "/jax/compilation_cache/compile_requests_use_cache"
    HIT = "/jax/compilation_cache/cache_hits"

    def __init__(self):
        import jax.monitoring

        self.requests = 0
        self.hits = 0
        jax.monitoring.register_event_listener(self._on_event)

    def _on_event(self, event, **_):
        if event == self.REQUEST:
            self.requests += 1
        elif event == self.HIT:
            self.hits += 1

    def close(self):
        import jax.monitoring

        jax.monitoring.unregister_event_listener(self._on_event)


@dataclass
class Context:
    seed: int
    seconds: float
    devices: list
    t_start: float  # perf_counter at process start
    trace_dir: str = ""  # empty: no traced slice
    samples_path: str = ""  # where the runner's raw samples are kept, if anywhere
    clock: callable = time.perf_counter
    compiles: CompileCounter = None

    def say(self, text: str):
        """A line for the reader, printed before the result line."""
        print(text, flush=True)

    def span(self, name: str):
        """A host span in the profiler's own trace (free when not tracing)."""
        import jax.profiler

        return jax.profiler.TraceAnnotation(SPAN_PREFIX + name)

    @contextlib.contextmanager
    def tracing(self):
        """Trace what runs inside, device and host spans, python tracer off."""
        import jax.profiler

        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 2
        jax.profiler.start_trace(self.trace_dir, profiler_options=opts)
        try:
            yield
        finally:
            jax.profiler.stop_trace()


@dataclass
class Result:
    correct: bool
    attempted: int
    failed: int
    metrics: dict  # end-to-end name -> value
    samples: dict  # what the per-layer readers read
