"""The traced part of a window: starts and stops JAX's profiler on the
window's own clock, and gives the runners host spans that land in the same
trace (``jax.profiler.TraceAnnotation``)."""
from __future__ import annotations

import contextlib
import time


class Tracer:
    """``poll(now)`` is called from the window's loop: it starts the
    profiler ``start_after_s`` into the window and stops it ``length_s``
    later. With ``enabled`` false everything is a no-op."""

    def __init__(self, enabled: bool, out_dir, start_after_s=1.0,
                 length_s=4.0):
        self.enabled = bool(enabled)
        self.out_dir = str(out_dir)
        self.start_after_s, self.length_s = start_after_s, length_s
        self.t_open = None
        self.t_start = self.t_stop = None
        self._on = False

    def window_opened(self, t_open):
        self.t_open = t_open

    def poll(self, now=None):
        if not self.enabled or self.t_open is None:
            return
        now = time.perf_counter() if now is None else now
        if self.t_start is None and now >= self.t_open + self.start_after_s:
            import jax
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0     # host spans: annotations and
            #                                  runtime calls, not every frame
            jax.profiler.start_trace(self.out_dir, profiler_options=opts)
            self._on = True
            self.t_start = time.perf_counter()
        elif self._on and now >= self.t_start + self.length_s:
            self._stop()

    def _stop(self):
        import jax
        self.t_stop = time.perf_counter()
        self._on = False
        jax.profiler.stop_trace()

    def window_closed(self, t_close):
        if self._on:
            self._stop()

    def span(self, name):
        if not self._on:
            return contextlib.nullcontext()
        import jax
        return jax.profiler.TraceAnnotation(name)

    @property
    def traced_s(self):
        if self.t_start is None or self.t_stop is None:
            return None
        return self.t_stop - self.t_start
