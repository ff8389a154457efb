"""XLA compile / memory / cost telemetry.

ROADMAP's north star ("fast as the hardware allows") is unverifiable
without three signals this module feeds into the obs registry:

* **Compile telemetry** — ``xla/compiles`` counter and
  ``xla/compile_seconds`` histogram.  Two feeds: explicit timing at the
  framework's own lower/compile sites
  (:meth:`tpudist.runtime.ici.IciCollectives._executable`), and — so a
  recompile STORM anywhere (a shape leak re-tracing every step) is
  visible without instrumenting every jit — a process-wide
  ``jax.monitoring`` duration listener on the backend-compile event
  (:func:`install_compile_telemetry`, installed by
  :func:`tpudist.runtime.cache.enable_compilation_cache`).  Every
  compile also lands in the flight-recorder ring.
* **Memory telemetry** — per-device ``memory_stats()`` HBM gauges
  (``xla/mem/bytes_in_use/d{i}``, ``.../peak_bytes_in_use/d{i}``),
  degrading to nothing on backends that report no stats (CPU).
* **Cost/MFU telemetry** — ``cost_analysis()``-derived FLOPs per
  compiled step feeding live ``xla/step_tflops`` and ``xla/mfu`` gauges
  against the chip's known bf16 peak (:func:`peak_tflops` /
  :func:`mfu`).

Everything degrades to a no-op without jax or without a backend — the
obs layer must stay importable everywhere.
"""

from __future__ import annotations

import threading
import time
from typing import Any

__all__ = [
    "PEAK_TFLOPS",
    "compile_watch",
    "cost_flops",
    "install_compile_telemetry",
    "mfu",
    "note_compile",
    "note_step",
    "peak_tflops",
    "update_memory_gauges",
]

# bf16 peak TFLOP/s per chip, by jax device_kind
PEAK_TFLOPS = {
    "TPU v4": 275.0,
    "TPU v5 lite": 197.0,   # v5e
    "TPU v5e": 197.0,
    "TPU v5": 459.0,        # v5p
    "TPU v5p": 459.0,
    "TPU v6 lite": 918.0,   # Trillium
    "TPU v6e": 918.0,
}

# memory_stats() keys worth exporting (allocator-dependent; TPU reports
# these, CPU reports nothing)
_MEM_KEYS = ("bytes_in_use", "peak_bytes_in_use", "bytes_limit")

_install_lock = threading.Lock()
_installed = False


def _registry(registry: Any = None):
    if registry is not None:
        return registry
    from tpudist import obs

    return obs.registry


def peak_tflops(device: Any = None) -> float | None:
    """The chip's bf16 peak TFLOP/s, or None off-TPU / for unknown
    kinds."""
    try:
        if device is None:
            import jax

            device = jax.devices()[0]
        return PEAK_TFLOPS.get(device.device_kind)
    except Exception:  # noqa: BLE001 - no backend
        return None


def mfu(tflops: float | None, device: Any = None) -> float | None:
    """Achieved / peak, or None when either side is unknown."""
    peak = peak_tflops(device)
    if peak is None or tflops is None:
        return None
    return round(tflops / peak, 4)


# -- compile telemetry -------------------------------------------------------

def note_compile(seconds: float, registry: Any = None,
                 source: str = "jit") -> None:
    """Record one compilation: count, duration histogram, and a
    flight-recorder event (the recompile-storm breadcrumb).  The
    innermost ``obs.span`` open on the compiling thread names the cause:
    it rides the event as ``span`` and ticks
    ``xla/compiles~span=<name>`` beside ``xla/compiles``."""
    from tpudist import obs

    reg = _registry(registry)
    reg.counter("xla/compiles", unit="compiles").inc()
    reg.histogram("xla/compile_seconds", unit="s").record(float(seconds))
    stack = obs.tracer._depth()
    span = stack[-1] if stack else None
    if span is not None:
        try:
            reg.counter(f"xla/compiles~span={span}", unit="compiles").inc()
        except ValueError:  # a span name that cannot be a label value
            pass
    obs.recorder.record("xla_compile", seconds=round(float(seconds), 4),
                        source=source, span=span)


def install_compile_telemetry(registry: Any = None) -> bool:
    """Register a process-wide ``jax.monitoring`` listener that feeds
    every backend compile into :func:`note_compile`.  Idempotent; returns
    True when the listener is (already) installed, False when this jax
    has no monitoring hooks."""
    global _installed
    with _install_lock:
        if _installed:
            return True
        try:
            from jax._src import monitoring
        except Exception:  # noqa: BLE001 - jax absent or reshaped
            return False
        reg = _registry(registry)

        def _listener(event: str, duration: float, **_kw) -> None:
            # '/jax/core/compile/backend_compile_duration' on this jax;
            # match loosely so minor renames keep reporting
            if "backend_compile" in event:
                note_compile(duration, registry=reg, source="monitoring")

        try:
            monitoring.register_event_duration_secs_listener(_listener)
        except Exception:  # noqa: BLE001
            return False
        _installed = True
        return True


class compile_watch:
    """``with compile_watch("ici"):`` — explicit timing for the
    framework's own lower/compile sites.  Records under per-site names
    (``xla/compiles_{name}``, ``xla/compile_seconds_{name}``) so the
    process-wide monitoring listener's ``xla/compiles`` totals never
    double-count a compile that was also timed at its call site."""

    def __init__(self, name: str, registry: Any = None) -> None:
        self.name = name
        self._registry = _registry(registry)
        self.seconds = 0.0

    def __enter__(self) -> "compile_watch":
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, *exc) -> None:
        self.seconds = time.perf_counter() - self._t0
        if exc[0] is None:
            reg = self._registry
            reg.counter(f"xla/compiles_{self.name}", unit="compiles").inc()
            reg.histogram(f"xla/compile_seconds_{self.name}",
                          unit="s").record(self.seconds)


# -- memory telemetry --------------------------------------------------------

def update_memory_gauges(registry: Any = None) -> dict[str, float]:
    """Refresh per-device HBM gauges from ``device.memory_stats()``;
    returns the values set (empty off-TPU, where the allocator reports
    nothing).  Cheap host-side calls — safe once per epoch/interval, not
    meant for the per-step path."""
    try:
        import jax

        devices = jax.local_devices()
    except Exception:  # noqa: BLE001 - no backend
        return {}
    reg = _registry(registry)
    out: dict[str, float] = {}
    for i, d in enumerate(devices):
        try:
            stats = d.memory_stats()
        except Exception:  # noqa: BLE001 - backend without stats
            stats = None
        if not stats:
            continue
        for key in _MEM_KEYS:
            if key in stats:
                name = f"xla/mem/{key}/d{i}"
                reg.gauge(name, unit="bytes").set(float(stats[key]))
                out[name] = float(stats[key])
    return out


# -- cost / MFU telemetry ----------------------------------------------------

def _cost_analysis(stage: Any) -> dict | None:
    try:
        cost = stage.cost_analysis()
    except Exception:  # noqa: BLE001 - analysis unsupported here
        return None
    if isinstance(cost, (list, tuple)):
        cost = cost[0] if cost else None
    return cost if isinstance(cost, dict) else None


def cost_flops(stage: Any) -> float | None:
    """Total FLOPs from a ``Lowered``/``Compiled`` stage's
    ``cost_analysis()`` (handles both the flat-dict and the
    list-of-dicts shapes jax has shipped), or None when unavailable.

    A ``Lowered`` stage answers only where jax can analyse unoptimized
    HLO in process (the CPU backend).  On a PJRT-plugin backend — the
    TPU — ``Lowered.cost_analysis()`` is None and only the compiled
    program has an analysis, so the stage is compiled for it: a
    persistent-cache hit when the step itself compiles the same program
    (:func:`tpudist.runtime.cache.enable_compilation_cache`).  FLOPs
    inside Pallas custom calls are not in XLA's count."""
    cost = _cost_analysis(stage)
    if cost is None and hasattr(stage, "compile"):
        try:
            cost = _cost_analysis(stage.compile())
        except Exception:  # noqa: BLE001 - telemetry must not stop a run
            cost = None
    flops = (cost or {}).get("flops")
    if flops is None or flops <= 0:
        return None
    return float(flops)


def note_step(seconds: float, flops: float | None,
              registry: Any = None) -> float | None:
    """Feed one measured step (wall seconds + program FLOPs) into the
    live gauges: ``xla/step_tflops`` always, ``xla/mfu`` when the chip's
    peak is known.  Returns the achieved TFLOP/s."""
    if not flops or seconds <= 0:
        return None
    reg = _registry(registry)
    tflops = flops / seconds / 1e12
    reg.gauge("xla/step_tflops", unit="TFLOP/s").set(tflops)
    frac = mfu(tflops)
    if frac is not None:
        reg.gauge("xla/mfu").set(frac)
    return tflops
