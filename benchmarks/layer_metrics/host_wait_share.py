"""Percent of the window the loop spent blocked on a segment's fetch
(``serve/host_wait`` summed over the window)."""


def read(run: dict):
    total, count = run["stats"]["host_wait"]
    return 100.0 * total / run["stats"]["window_s"] if count else None
