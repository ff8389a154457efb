"""Every seed offers the same work, in another order."""

import numpy as np

from benchmarks.traffic import generator


def test_open_loop_same_seed_same_requests():
    mix = generator.load("code_steady")
    a = generator.serve_items(mix, 7, 44.0, 49152)
    b = generator.serve_items(mix, 7, 44.0, 49152)
    assert [i.at for i in a] == [i.at for i in b]
    assert all(np.array_equal(x.prompt, y.prompt) for x, y in zip(a, b))


def test_open_loop_seeds_share_the_schedule():
    mix = generator.load("code_steady")
    a = generator.serve_items(mix, 1, 44.0, 49152)
    b = generator.serve_items(mix, 2 ** 31 + 12345, 44.0, 49152)
    # the same arrivals and sizes; other tokens
    assert [i.at for i in a] == [i.at for i in b]
    assert [(len(i.prompt), i.max_new) for i in a] == [
        (len(i.prompt), i.max_new) for i in b]
    assert not all(np.array_equal(x.prompt, y.prompt)
                   for x, y in zip(a, b))
    lo, hi = mix["lengths"]["prompt"]["min"], 6144
    assert all(lo <= len(i.prompt) <= hi for i in a)
    assert all(len(i.prompt) + i.max_new <= mix["max_total"] for i in a)
    assert abs(len(a) - mix["rate_per_s"] * 44.0) <= 2
    other = generator.serve_items(dict(mix, order_seed=1), 1, 44.0, 49152)
    assert [i.at for i in other] != [i.at for i in a]


def test_closed_loop_has_no_due_times():
    mix = generator.load("longgen_batch")
    items = generator.serve_items(mix, 5, 52.0, 49152)
    assert len(items) >= mix["clients"]
    assert all(i.at is None for i in items)
    assert all(128 <= len(i.prompt) <= 1024 and 512 <= i.max_new <= 1024
               for i in items)


def test_train_ring_rows_differ():
    mix = generator.load("train_8k")
    ring = generator.train_batches(mix, 9, 2, 512, 64)
    assert len(ring) == mix["ring"]
    x, y = ring[0]
    assert x.shape == (2, 64) and not np.array_equal(x[0], x[1])
    assert np.array_equal(x[:, 1:], y[:, :-1])
