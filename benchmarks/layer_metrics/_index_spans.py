"""An indexer's sums over the window's drained segments
(``serve/segment_drain`` carries ``rows_scored`` and ``rows_selected`` where
the model has an indexer): per decode step and layer call, ``(index keys
read, rows attended, live lanes)``; ``None`` on a program or a model without
them.  And the device time of the indexer's routines, cut where the trace
can tell them apart: where a routine's data first appears, not where its
kernel starts."""

import bisect
import math
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


def routines(run: dict):
    """Mean device seconds ``(selection, attention)`` of one layer of one
    decode step, over the pairs of a ``paged_index_scores`` and the
    ``sparse_gqa_attend`` that follows it inside runs of the segment
    program; ``None`` where the trace holds no such pair.

    The ATTENTION routine is everything that reads or writes the chosen
    rows: it starts with the first instruction after the scores whose result
    is a buffer of gathered rows (``lanes x index_topk`` rows at a pool's
    stored width, whatever its rank: this version's XLA gathers of K and of
    V, then the staged rows patched in), and ends with the kernel; a version
    whose kernel reads the rows itself has no such instruction and starts at
    the kernel.  The SELECTION is what lies before it, from the end of the
    scores: the staged rows' scores, the exact top ``index_topk`` and the
    flat row ids (XLA's operations, under names of the compiler's)."""
    from benchmarks.layer_metrics import _serve_trace as st
    from benchmarks.trace import reduce as tr

    dims = run["dims"]
    width = dims.kv_heads * dims.head_dim
    gathered = run["options"]["num_slots"] * dims.index_topk * width

    def is_gathered(name: str) -> bool:
        for shape in tr.parse_op(name)["outputs"]:
            sizes = [int(n) for n in
                     re.search(r"\[([\d,]*)\]", shape).group(1).split(",")
                     if n]
            if sizes and sizes[-1] == width and math.prod(sizes) == gathered:
                return True
        return False

    pairs, select, attend = 0, 0.0, 0.0
    for dev in _devices(run):
        runs = sorted((m.start, m.end) for m in dev["modules"]
                      if re.search(st.SEGMENT, m.name))
        starts = [s for s, _ in runs]
        scored = rows_at = None
        for e in sorted(dev["ops"], key=lambda e: e.start):
            i = bisect.bisect_right(starts, e.start) - 1
            if i < 0 or e.start >= runs[i][1]:
                continue
            if SCORES.match(e.name):
                scored, rows_at = e.end, None
            elif scored is None:
                continue
            elif ATTEND.match(e.name):
                begin = e.start if rows_at is None else rows_at
                pairs += 1
                select += begin - scored
                attend += e.end - begin
                scored = None
            elif rows_at is None and is_gathered(e.name):
                rows_at = e.start
    return (select / pairs, attend / pairs) if pairs else None


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
