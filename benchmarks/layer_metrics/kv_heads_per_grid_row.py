"""The K/V heads ONE grid row of a paged decode walk serves:
``heads_per_grid_row`` of the window's ``serve/segment_drain`` spans (the
fold ``ops.flash_decode.paged_grid_rows`` takes from the loop's shapes: a
lane whose heads' tile slots do not all fit the kernel's budget takes
several rows, each a group of them).  A program counter, no device read; the
same in every segment of a run, so the first is read.  ``None`` on a program
whose drain does not say it."""

from benchmarks.layer_metrics import _loop_spans as ls


def read(run: dict):
    w = ls.window(run)
    if w is None:
        return None
    said = [a["heads_per_grid_row"] for a in ls.drained(w)
            if "heads_per_grid_row" in a]
    return float(said[0]) if said else None
