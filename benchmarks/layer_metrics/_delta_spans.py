"""What the readers of the linear-attention layers share: the decode
kernel's name, and the prefill chunk's recurrence found by its routine
scope.

``delta_step`` is a Pallas kernel: the ``name=`` of its ``pallas_call`` is
the name of its HLO instruction, and an ``XLA Ops`` event is named by its
whole instruction.  ``delta_chunk`` is plain ``jax.numpy`` under the routine
scope of that name, inside the prefill-chunk program: its device time is the
SELF-time (``trace/reduce.self_times``) of the operations whose instruction
carries that scope in the program's own map (``ServeLoop.scope_map()``,
through ``_scopes``), inside the WHOLE runs of that program in the trace (a
run the trace's edge may have cut, the first and the last module event, is
left out).  One call is one linear layer of one chunk.

On a program without the scope or the kernel (the parent of PR 41), or
without a trace, every reader built on this returns ``None``."""

from __future__ import annotations

import bisect
import re

from benchmarks.layer_metrics import _joined, _named_kernels as nk, _scopes
from benchmarks.layer_metrics import _serve_trace as st

STEP = nk.kernel_pattern("delta_step")
CHUNK_SCOPE = "delta_chunk"


def linear_dims(run: dict):
    """``(linear layers, heads, dk, dv)`` of the run's model, or ``None``
    for a model without such layers."""
    dims = run["dims"]
    linear = getattr(dims, "linear", None)
    if not linear or not any(linear):
        return None
    return sum(linear), dims.lin_heads, dims.key_dim, dims.value_dim


def _prefill_scopes(run: dict):
    if "scope_map" in run:
        return run["scope_map"].get(st.PREFILL)
    if _scopes.scope_map(run) is None:
        return None
    return _scopes._MAPS[run["cell"]["name"]].get(st.PREFILL)


def chunk_calls(run: dict):
    """``(calls, seconds)`` of the chunk form in the trace's whole runs of
    the prefill-chunk program; ``None`` where nothing can be read."""
    if "_delta_chunk" in run:
        return run["_delta_chunk"]
    run["_delta_chunk"] = out = _chunk_calls(run)
    return out


def _chunk_calls(run: dict):
    from benchmarks.trace import reduce as tr

    shape = linear_dims(run)
    ev = _joined.trace_events(run)
    if shape is None or not ev or not ev["modules"]:
        return None
    scopes = _prefill_scopes(run)
    if not scopes or CHUNK_SCOPE not in scopes.values():
        return None
    edge = (min(m[1] for m in ev["modules"]),
            max(m[1] for m in ev["modules"]))
    runs = sorted((start, end) for name, start, end in ev["modules"]
                  if re.search(st.PREFILL, name) and start not in edge)
    if not runs:
        return None
    starts = [s for s, _ in runs]
    inside: list[list] = [[] for _ in runs]
    for name, start, end in ev["ops"]:
        i = bisect.bisect_right(starts, start) - 1
        if i >= 0 and start < runs[i][1]:
            inside[i].append(tr.Event(name, start, end))
    seconds = sum(
        spent for events in inside
        for name, spent in tr.self_times(events).items()
        if scopes.get(_scopes.instruction(name)) == CHUNK_SCOPE)
    return len(runs) * shape[0], seconds
