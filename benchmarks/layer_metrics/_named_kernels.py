"""Kernels found in a trace BY NAME: the ``name=`` of a ``pallas_call`` is
the name of its HLO instruction, and an ``XLA Ops`` event is named by its
whole instruction (``%paged_mla_decode.7 = ...``).  Optionally only the
events that began inside a run of a compiled program whose name matches
(a kernel that the segment and the prefill chunk both call)."""

from __future__ import annotations

import bisect
import re

from benchmarks.harness import tracing
from benchmarks.trace import reduce as tr


def kernel_pattern(*names: str) -> re.Pattern:
    return re.compile(r"^%?(" + "|".join(names) + r")[.\d]* = ")


def calls(run: dict, pattern: re.Pattern,
          module: str | None = None) -> tuple[int, float]:
    """(events, their device seconds) of the traced run's kernels whose
    instruction matches ``pattern``; (0, 0.0) where there is no trace."""
    if not run["trace"]:
        return 0, 0.0
    path = tr.find_xplane(str(tracing.TRACE_ROOT / run["cell"]["name"]))
    if not path:
        return 0, 0.0
    n, seconds = 0, 0.0
    for dev in tr.load(path)["devices"].values():
        spans = None
        if module is not None:
            spans = sorted((m.start, m.end) for m in dev["modules"]
                           if re.search(module, m.name))
            starts = [s for s, _ in spans]
        for e in dev["ops"]:
            if not pattern.match(e.name):
                continue
            if spans is not None:
                i = bisect.bisect_right(starts, e.start) - 1
                if i < 0 or e.start >= spans[i][1]:
                    continue
            n += 1
            seconds += e.end - e.start
    return n, seconds
