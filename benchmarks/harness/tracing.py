"""Starts and stops the JAX profiler around the last seconds of a window
and hands the trace to ``benchmarks/trace/reduce.py``."""

from __future__ import annotations

import shutil

from benchmarks.harness import common
from benchmarks.trace import reduce as trace_reduce

# kept inside the checkout, at a fixed place, emptied by every traced run
TRACE_ROOT = common.ROOT / ".bench_trace"


class Tracer:
    """``poll(t)`` is called with the seconds since the offered load began
    (serving: from ``source()``); the trace covers ``[start, end)``."""

    def __init__(self, name: str, start_s: float, end_s: float) -> None:
        self.dir = TRACE_ROOT / name
        shutil.rmtree(self.dir, ignore_errors=True)
        self.dir.mkdir(parents=True)
        self.start_s, self.end_s = start_s, end_s
        self.state = "idle"

    def poll(self, t: float) -> None:
        if self.state == "idle" and t >= self.start_s:
            self.start()
        elif self.state == "on" and t >= self.end_s:
            self.stop()

    def start(self) -> None:
        import jax

        jax.profiler.start_trace(str(self.dir))
        self.state = "on"

    def stop(self) -> None:
        import jax

        if self.state == "on":
            jax.profiler.stop_trace()
        self.state = "done"

    def reduce(self, chips: int) -> dict | None:
        path = trace_reduce.find_xplane(str(self.dir))
        return trace_reduce.reduce(path, chips) if path else None
