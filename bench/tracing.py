"""The profiler window of a ``--trace 1`` run and the benchmark's spans.

The trace covers the last ``TRACE_SECONDS`` of the measured window (a
whole window of a fast cell would write hundreds of MB), without the
Python tracer.  It starts inside the window and stops after it, so the
seconds the profiler takes to write its file hold up no request.

The spans are the benchmark's own, around its calls into the program:
``bench.ingest``, ``bench.pool_step``, ``bench.collect`` and
``bench.wait``; ``bench.window`` marks the traced window.  With tracing
off every span is a no-op.
"""

from __future__ import annotations

import contextlib
import os
import shutil
import time

TRACE_SECONDS = 2.0


class Tracer:
    def __init__(self, enabled: bool, log_dir: str):
        self.enabled = enabled
        self.log_dir = log_dir
        self.state = "idle"
        self._window = None
        self.start = None

    def span(self, name: str):
        if self.state != "on":
            return contextlib.nullcontext()
        import jax
        return jax.profiler.TraceAnnotation(name)

    def plan(self, t0: float, seconds: float):
        self.start = t0 + seconds - min(TRACE_SECONDS, seconds)

    def tick(self, now: float):
        """Start the profiler when the clock crosses the plan."""
        if not self.enabled or self.start is None:
            return
        if self.state == "idle" and now >= self.start:
            import jax
            shutil.rmtree(self.log_dir, ignore_errors=True)
            os.makedirs(self.log_dir, exist_ok=True)
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            jax.profiler.start_trace(self.log_dir, profiler_options=opts)
            self.state = "on"
            self._window = jax.profiler.TraceAnnotation("bench.window")
            self._window.__enter__()

    def finish(self):
        if self.state == "on":
            import jax
            self._window.__exit__(None, None, None)
            jax.profiler.stop_trace()
            self.state = "done"


def now() -> float:
    return time.perf_counter()
