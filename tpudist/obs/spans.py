"""Span tracer — Chrome-trace-format timelines for host-side phases.

``with tracer.span("train_step"): ...`` records a complete event per exit
into an in-memory buffer; :meth:`SpanTracer.dump` / :meth:`write` render
the catapult JSON that chrome://tracing and Perfetto load directly:

    {"traceEvents": [{"name", "ph": "X", "ts", "dur", "pid", "tid",
                      "args"}, ...], "displayTimeUnit": "ms"}

with ``ts``/``dur`` in microseconds.  Nesting falls out of the format —
the viewer stacks events on the same tid by containment, and we record a
``depth`` arg from a per-thread stack for programmatic consumers.

Two accelerator-facing hooks:

* ``fence=True`` (or ``TPUDIST_OBS_FENCE=1``) calls
  ``jax.effects_barrier()`` on span exit, so asynchronously dispatched
  device work is attributed to the span that launched it instead of
  whichever span happens to be open when the queue drains.  Off by
  default: fencing serializes dispatch and is a measurement tool, not a
  production default.
* every span is also wrapped in ``jax.profiler.TraceAnnotation``, so
  while a profiler trace is active spans appear as named regions on the
  trace's host plane, each with the scalar ``args`` it was given (``seq``,
  ``steps``, ``slot``...) and ``pc_us``, the ``time.perf_counter()`` stamp
  of its entry in microseconds: the one number that ties the ring's clock
  to the profiler's (offset = median over the traced annotations of their
  start on the trace less ``pc_us``), so that a :meth:`SpanTracer.complete`
  span, which is never annotated, can be placed on the trace's time line
  too.  Without an active session the annotation is handed the name alone:
  an untraced run pays one flag read more than before.

Spans stay importable and functional without a jax backend: both hooks
degrade to no-ops when jax (or the annotation API) is unavailable.
"""

from __future__ import annotations

import collections
import contextlib
import json
import os
import re
import tempfile
import threading
import time

from tpudist.utils.config import env_flag

__all__ = ["ROUTINE_SCOPES", "SpanTracer", "atomic_write_json", "routine",
           "scope_of"]

# The routines of a compiled serve program, as ``jax.named_scope`` names
# (metadata on the instructions' ``op_name``: no operation added or moved).
# ONE vocabulary: the models open these and nothing else
# (:func:`routine`), ``docs/OBSERVABILITY.md`` tabulates them, the
# benchmark's readers sum a traced step's device time by them
# (:func:`scope_of`).  What carries none reads as ``other``: embedding,
# residual adds, the ``while_loop``'s bookkeeping, the expert counts.  A
# block's norm counts to the routine that reads it first (``attn/proj``;
# ``mlp/dense`` or, in an expert layer, ``mlp/route``); a parallel block
# has ONE norm, counted to ``attn/proj``.
ROUTINE_SCOPES = (
    "attn/proj",    # q/k/v/latent/output projections, q-k norms, rotary
    "attn/cache",   # K/V/latent/index-key writes, the side -> pool merge
    "attn/index",   # an indexer's scores, exact top-k, chosen columns
    "attn/rows",    # the gathers of the chosen rows, staged rows patched in
    "attn/core",    # the attention itself (a kernel, or the dense fallback)
    "mlp/dense",    # MLPBlock
    "mlp/route",    # router matmul, top-k, the counting sort
    "mlp/experts",  # the grouped products and the combine
    "mlp/shared",   # the shared experts (and, averaged, their 1/n)
    "head",         # final norm, lm_head or the tied table, logit_scale,
                    # sampling, the emit buffer's write
    # a linear-attention layer: its projections, conv, gates, norms and
    # state copies, and nested in it the recurrence itself, by its form
    "linear_attn",
    "delta_step",   # one token a lane (the Pallas kernel of that name)
    "delta_chunk",  # a chunk of tokens from a carried state
)
_SCOPE_IN_OP_NAME = re.compile(
    "(?:^|/)(" + "|".join(map(re.escape, ROUTINE_SCOPES)) + ")(?=/|$)")


def routine(name: str):
    """``jax.named_scope(name)`` for one of :data:`ROUTINE_SCOPES`."""
    if name not in ROUTINE_SCOPES:
        raise ValueError(f"{name!r} is not one of {ROUTINE_SCOPES}")
    import jax

    return jax.named_scope(name)


def scope_of(op_name: str) -> str | None:
    """The innermost of :data:`ROUTINE_SCOPES` on an instruction's
    ``op_name`` (its name stack), or None."""
    found = _SCOPE_IN_OP_NAME.findall(op_name)
    return found[-1] if found else None


def atomic_write_json(path: str | os.PathLike, doc,
                      indent: int | None = None) -> str:
    """Write ``doc`` as JSON via temp file + atomic rename, so a crash
    mid-dump (the exact moment traces and post-mortems get written)
    can never leave a truncated/unparseable file at ``path``."""
    path = os.fspath(path)
    fd, tmp = tempfile.mkstemp(
        prefix=".tmp-" + os.path.basename(path) + "-",
        dir=os.path.dirname(path) or ".")
    try:
        with os.fdopen(fd, "w") as f:
            json.dump(doc, f, indent=indent)
            f.flush()
            os.fsync(f.fileno())
        os.replace(tmp, path)
    except BaseException:
        try:
            os.unlink(tmp)
        except OSError:
            pass
        raise
    return path


def _trace_annotation(name: str, start: float, args: dict):
    """The profiler's annotation of a span that began at ``start``.  Only
    while a session is active (``is_enabled``: a flag read, 0.02 us) does
    it get the span's scalar ``args`` and ``pc_us``; without one it is
    the bare name, as ever."""
    try:
        import jax.profiler

        annotation = jax.profiler.TraceAnnotation
        if not annotation.is_enabled():
            return annotation(name)
        return annotation(name, pc_us=start * 1e6, **{
            k: v for k, v in args.items()
            if isinstance(v, (int, float, str))})
    except Exception:
        return contextlib.nullcontext()


def _effects_barrier() -> None:
    try:
        import jax

        jax.effects_barrier()
    except Exception:
        pass


class SpanTracer:
    """Per-process span recorder.

    ``max_events`` bounds the buffer as a RING (long serving jobs would
    otherwise grow without limit): overflow evicts the OLDEST span and
    increments :attr:`dropped`, keeping the newest spans — the tail a
    post-mortem (:mod:`tpudist.obs.recorder`) actually wants.
    Thread-safe: each thread keeps its own nesting stack, the event
    buffer is lock-guarded.
    """

    def __init__(self, max_events: int = 100_000,
                 fence: bool | None = None) -> None:
        self.max_events = max_events
        # None -> env-controlled so a test or a run can fence without code
        self.fence = env_flag("TPUDIST_OBS_FENCE") if fence is None else fence
        self.dropped = 0
        self._events: collections.deque[dict] = collections.deque(
            maxlen=max_events)
        self._lock = threading.Lock()
        self._local = threading.local()
        self._pid = os.getpid()

    def _depth(self) -> list:
        stack = getattr(self._local, "stack", None)
        if stack is None:
            stack = self._local.stack = []
        return stack

    @contextlib.contextmanager
    def span(self, name: str, **args):
        """Record a complete ("ph": "X") event for the enclosed block.
        ``args`` must be JSON-serializable; they land in the event's
        ``args`` field next to the nesting ``depth``.  While a profiler
        trace runs the scalar ones go to its annotation as they are, with
        ``pc_us`` (the entry stamp)."""
        stack = self._depth()
        stack.append(name)
        start = time.perf_counter()
        try:
            with _trace_annotation(name, start, args):
                yield self
        finally:
            if self.fence:
                _effects_barrier()
            dur_us = (time.perf_counter() - start) * 1e6
            depth = len(stack) - 1
            stack.pop()
            self._append({
                "name": name,
                "ph": "X",
                # perf_counter origin is arbitrary but shared across the
                # process, which is all the viewer needs
                "ts": start * 1e6,
                "dur": dur_us,
                "pid": self._pid,
                "tid": threading.get_ident(),
                "args": {"depth": depth, **args},
            })

    def complete(self, name: str, start: float, end: float, **args) -> None:
        """Record a span from two ``time.perf_counter()`` stamps already
        taken: for a phase whose ends, or whose ``args``, are only known
        after it is over (a request's life, a drained segment's sums).
        No nesting depth and no ``TraceAnnotation``: it lies in the ring
        alone, and a reader places it on a profiler's time line by the
        clock offset that the annotated spans' ``pc_us`` give."""
        self._append({
            "name": name, "ph": "X", "ts": start * 1e6,
            "dur": (end - start) * 1e6, "pid": self._pid,
            "tid": threading.get_ident(), "args": args,
        })

    def _append(self, event: dict) -> None:
        with self._lock:
            if len(self._events) == self.max_events:
                self.dropped += 1  # deque maxlen evicts the oldest
            self._events.append(event)

    def events(self) -> list[dict]:
        with self._lock:
            return list(self._events)

    def dump(self) -> dict:
        """The Chrome-trace JSON document (catapult "JSON object format")."""
        return {"traceEvents": self.events(), "displayTimeUnit": "ms"}

    def write(self, path: str) -> str:
        return atomic_write_json(path, self.dump())

    def clear(self) -> None:
        with self._lock:
            self._events.clear()
            self.dropped = 0
