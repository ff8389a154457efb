"""A traced window's runs of the segment program, each joined to the segment
it WAS: the ``seq`` of the ``serve/segment`` annotation that dispatched it
and the sums the ring's ``serve/segment_drain`` of that ``seq`` reports.

Since PR 37 every ``obs.span`` reaches the profiler's host plane with its
scalar args (``seq``) and ``pc_us`` (its entry on ``time.perf_counter()``,
in microseconds).  So:

* segments run in dispatch order on one device, and a run begins at or
  after its ``serve/segment`` annotation's start and ends at or before the
  end of the ``serve/segment_fetch`` of the same ``seq``.  Consecutive
  ``seq`` are consecutive runs; the one shift under which every placed run
  keeps both inequalities is taken (the latest, should two do).  A run the
  trace's edge cut is LEFT OUT: at the end it has no fetch inside the
  trace, at the start it is the first event of the device's line;
* offset = median over the annotated events of (start on the trace -
  ``pc_us``) puts the ring's spans on the trace's time line, which picks,
  of the ring's drains with that ``seq`` (``seq`` restarts with every
  ``run()``: the warm-up has the same numbers), the one that began when
  the fetch ended.

On a program without the stamps (the parent of PR 37) nothing is joined and
every reader built on this returns ``None``.
"""

from __future__ import annotations

import dataclasses
import functools
import re
import statistics

from benchmarks.layer_metrics import _loop_spans as ls
from benchmarks.layer_metrics import _serve_trace as st

TOLERANCE_S = 200e-6   # host plane against device plane, and D2H's tail
DRAIN_WITHIN_S = 20e-3  # a drain begins when its fetch ends


@dataclasses.dataclass
class Run:
    seq: int
    start: float    # the device run, seconds on the trace's clock
    end: float
    drain: dict     # args of the ring's serve/segment_drain of this seq

    @property
    def seconds(self) -> float:
        return self.end - self.start


def trace_events(run: dict):
    """``{"modules": [(name, start, end)], "ops": [(name, start, end)],
    "host": [(name, start, end, stats)]}`` of the first device and the
    ``serve/`` annotations, seconds; a run bag may carry its own under
    ``"trace_events"`` (the readers' tests); ``None`` without a trace."""
    if "trace_events" in run:
        return run["trace_events"]
    if not run.get("trace"):
        return None
    from benchmarks.harness import tracing
    from benchmarks.trace import reduce as tr

    path = tr.find_xplane(str(tracing.TRACE_ROOT / run["cell"]["name"]))
    return _load(path) if path else None


@functools.lru_cache(maxsize=1)     # every reader of a run asks for it
def _load(path: str) -> dict:
    from jax.profiler import ProfileData

    from benchmarks.trace import reduce as tr

    def rows(line, stats=False):
        return [(e.name, e.start_ns * 1e-9,
                 (e.start_ns + e.duration_ns) * 1e-9)
                + ((dict(e.stats),) if stats else ())
                for e in line.events]

    out = {"modules": [], "ops": [], "host": []}
    first = None
    for plane in ProfileData.from_file(path).planes:
        m = tr.DEVICE_PLANE.match(plane.name)
        if m and (first is None or int(m.group(1)) <= first):
            first = int(m.group(1))
            for line in plane.lines:
                if line.name == tr.OPS_LINE:
                    out["ops"] = rows(line)
                elif line.name == tr.MODULES_LINE:
                    out["modules"] = rows(line)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                out["host"] += [e for e in rows(line, stats=True)
                                if e[0].startswith("serve/")]
    return out


def clock(host) -> tuple[float, float] | None:
    """``(offset, residual)`` seconds: trace time = ``perf_counter`` +
    offset, fitted as the median over the annotations that carry
    ``pc_us``; the residual is the median distance from it."""
    offsets = [start - stats["pc_us"] * 1e-6
               for _, start, _, stats in host if "pc_us" in stats]
    if not offsets:
        return None
    mid = statistics.median(offsets)
    return mid, statistics.median(abs(o - mid) for o in offsets)


def _align(ann: dict, fetched: dict, runs: list) -> dict:
    """``{seq: run index}``: consecutive ``seq`` are consecutive runs, so
    one shift places them all; the shift under which every placed run
    keeps both inequalities and most fetched segments are placed (the
    latest of equals)."""
    seqs = sorted(set(ann) | set(fetched))
    if not seqs or not runs:
        return {}
    lo, hi = seqs[0], seqs[-1]
    best, most = {}, 0
    for d in range(lo - hi, len(runs)):
        placed = {}
        for seq in range(lo, hi + 1):
            j = seq - lo + d
            if not 0 <= j < len(runs):
                continue
            if ((seq in ann and runs[j][0] < ann[seq] - TOLERANCE_S)
                    or (seq in fetched
                        and runs[j][1] > fetched[seq] + TOLERANCE_S)):
                placed = None
                break
            placed[seq] = j
        n = sum(1 for seq in placed or () if seq in fetched)
        if n and n >= most:
            best, most = placed, n
    return best


def segments(run: dict) -> list[Run] | None:
    """The WHOLE runs of the segment program inside the trace, joined;
    ``None`` where fewer than two could be.  Whole: the segment's fetch
    ended inside the trace, and its dispatch began there too or, where it
    was dispatched before the trace began, something ran on the device
    before it (the profiler keeps a run that was under way when it started
    as the FIRST event of the line, with part of its time)."""
    if "_joined" not in run:    # every reader of the bag asks
        run["_joined"] = _segments(run)
    return run["_joined"]


def _segments(run: dict):
    ev = trace_events(run)
    fit = ev and clock(ev["host"])
    if not fit:
        return None
    offset, _ = fit
    ann, fetched = {}, {}
    for name, start, end, stats in sorted(ev["host"], key=lambda e: e[1]):
        seq = stats.get("seq")
        if seq is None or name not in ("serve/segment",
                                       "serve/segment_fetch"):
            continue
        into = ann if name == "serve/segment" else fetched
        if into and seq <= max(into):
            return None     # a second run() began inside the trace
        into[seq] = start if name == "serve/segment" else end
    runs = sorted((start, end) for name, start, end in ev["modules"]
                  if re.search(st.SEGMENT, name))
    first = min((start for _, start, _ in ev["modules"]), default=None)
    drains = [(begin + offset, args) for name, begin, _, args
              in ls.spans_of(run) if name == "serve/segment_drain"]
    out = []
    for seq, j in sorted(_align(ann, fetched, runs).items()):
        if seq not in fetched or (seq not in ann and runs[j][0] <= first):
            continue
        near = [(abs(t - fetched[seq]), args) for t, args in drains
                if args.get("seq") == seq]
        if not near:
            continue
        gap, args = min(near, key=lambda g: g[0])
        if gap <= DRAIN_WITHIN_S and args.get("steps_run"):
            out.append(Run(seq, runs[j][0], runs[j][1], args))
    return out if len(out) >= 2 else None


def ms_per_step(run: dict):
    """Device milliseconds of the joined segment runs over the sum of
    THEIR ``steps_run``."""
    joined = segments(run)
    if joined is None:
        return None
    return (1e3 * sum(r.seconds for r in joined)
            / sum(r.drain["steps_run"] for r in joined))
