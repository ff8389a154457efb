"""What the readers of ``ServeLoop``'s own clock share: the spans the loop
left in ``tpudist.obs.tracer`` (``serve/request``, one per finished request;
``serve/segment_fetch`` and ``serve/segment_drain``, one each per drained
segment; the loop's phases), cut to the measured window.

A reader runs in the benchmark's process after the window, so it reads the
ring directly.  The run bag holds no window edges, so the window is bounded
by the program's own stamps: the requests that count are those whose ``rid``
is among the ``admit`` events of ``run["events"]`` (which leaves the warm-up
out: it carries no trace context), and the window runs from the first such
request's enqueue plus the mix's ``ramp_s``, for ``run["stats"]["window_s"]``
seconds.  The load's first request is sent at its first poll or up to one
arrival gap later, so these edges lie that much after the harness's; a
``benchmark`` PR can hand readers the exact edges later.  On a program
without these spans every reader finds nothing and returns ``None``.
"""

from __future__ import annotations

import dataclasses

# the loop's phases: together they cover run()'s wall but the idle sleep
PHASES = ("serve/admit_poll", "serve/admit", "serve/prefill_chunk",
          "serve/admit_finish", "serve/segment_plan", "serve/segment",
          "serve/segment_fetch", "serve/segment_drain")


@dataclasses.dataclass
class Window:
    lo: float            # perf_counter seconds
    hi: float
    requests: list       # args of the serve/request spans enqueued inside
    spans: list          # (name, start, end, args) of every span inside


def spans_of(run: dict) -> list:
    """Every span in the ring as ``(name, start_s, end_s, args)``; a run
    bag may carry its own list under ``"spans"`` (the readers' tests)."""
    events = run.get("spans")
    if events is None:
        from tpudist import obs

        events = obs.tracer.events()
    return [(e["name"], e["ts"] * 1e-6, (e["ts"] + e["dur"]) * 1e-6,
             e["args"]) for e in events]


def window(run: dict) -> Window | None:
    rids = {e["trace"] for e in run["events"] if e["kind"] == "admit"}
    spans = spans_of(run)
    mine = [(start, args) for name, start, _, args in spans
            if name == "serve/request" and str(args["rid"]) in rids]
    if not mine:
        return None
    lo = min(start for start, _ in mine) + float(
        run["cell"]["traffic"]["ramp_s"])
    hi = lo + float(run["stats"]["window_s"])
    return Window(lo, hi, [a for start, a in mine if lo <= start < hi],
                  [s for s in spans if s[1] < hi and s[2] > lo])


def clipped(w: Window, name: str) -> list[tuple[float, float]]:
    """The parts of the spans called ``name`` that lie inside the window."""
    return [(max(s, w.lo), min(e, w.hi)) for n, s, e, _ in w.spans
            if n == name]


def drained(w: Window) -> list[dict]:
    """Args of the segments whose drain began inside the window."""
    return [a for n, s, _, a in w.spans
            if n == "serve/segment_drain" and w.lo <= s < w.hi]
