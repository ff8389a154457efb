"""Mean wait from enqueue to admission over the window: the differences of
``serve/queue_wait_s``'s sum and count (its buckets are logarithmic, so no
quantile is read from it)."""


def read(run: dict):
    total, count = run["stats"]["queue_wait"]
    return 1e3 * total / count if count else None
