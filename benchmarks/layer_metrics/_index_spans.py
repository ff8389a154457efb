"""An indexer's sums over the window's drained segments
(``serve/segment_drain`` carries ``rows_scored`` and ``rows_selected`` where
the model has an indexer): per decode step and layer call, ``(index keys
read, rows attended, live lanes)``; ``None`` on a program or a model without
them.  And its kernels in the trace, found by their names."""

import bisect
import re

from benchmarks.layer_metrics import _loop_spans as ls
from benchmarks.layer_metrics import _named_kernels as nk

SCORES = nk.kernel_pattern("paged_index_scores")
ATTEND = nk.kernel_pattern("sparse_gqa_attend")
CHUNK_ATTEND = nk.kernel_pattern("sparse_gqa_prefill")


def per_step(run: dict):
    w = ls.window(run)
    if w is None:
        return None
    segs = [a for a in ls.drained(w) if "rows_selected" in a]
    steps = sum(a["steps_run"] for a in segs)
    if not steps:
        return None
    return (sum(a["rows_scored"] * a["steps_run"] for a in segs) / steps,
            sum(a["rows_selected"] * a["steps_run"] for a in segs) / steps,
            sum(a["lanes"] * a["steps_run"] for a in segs) / steps)


def _devices(run: dict):
    from benchmarks.harness import tracing
    from benchmarks.trace import reduce as tr

    if not run["trace"]:
        return []
    path = tr.find_xplane(str(tracing.TRACE_ROOT / run["cell"]["name"]))
    return list(tr.load(path)["devices"].values()) if path else []


def kernel_call(run: dict, pattern):
    """Seconds of one call of the kernels matching ``pattern`` inside runs
    of the segment program (a prefill chunk calls them too, on other
    shapes); ``None`` where the trace has none."""
    from benchmarks.layer_metrics import _serve_trace as st

    calls, seconds = nk.calls(run, pattern, module=st.SEGMENT)
    return seconds / calls if calls else None


def sparse_chunks(run: dict):
    """``(runs, device seconds)`` of the prefill-chunk program's runs that
    attended chosen rows: those inside which the kernel
    ``sparse_gqa_prefill`` ran (the chunk's other branch, a prompt's first
    chunk, is the dense flash pass)."""
    from benchmarks.layer_metrics import _serve_trace as st

    n, seconds = 0, 0.0
    for dev in _devices(run):
        marks = sorted(e.start for e in dev["ops"]
                       if CHUNK_ATTEND.match(e.name))
        for m in dev["modules"]:
            if not re.search(st.PREFILL, m.name):
                continue
            i = bisect.bisect_left(marks, m.start)
            if i < len(marks) and marks[i] < m.end:
                n += 1
                seconds += m.end - m.start
    return n, seconds
