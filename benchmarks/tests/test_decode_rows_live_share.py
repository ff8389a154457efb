"""``decode_rows_live_share`` on a span list made by hand: a known answer
weighted by the steps a segment ran, segments drained outside the window
left out, and ``None`` on a program whose spans carry no rows (the parent
of PR 28) or that computed none."""

import pytest

from benchmarks.layer_metrics import decode_rows_live_share
from benchmarks.tests.test_layer_readers import SPANS, bag

ROWS = {1: (2048, 1500), 2: (1024, 1000), 3: (4096, 4096)}


def with_rows(rows):
    """``SPANS`` whose drains carry ``rows`` / ``rows_live`` by ``seq``."""
    out = []
    for s in SPANS:
        seq = s["args"].get("seq")
        if s["name"] == "serve/segment_drain" and seq in rows:
            computed, live = rows[seq]
            s = {**s, "args": {**s["args"], "rows": computed,
                               "rows_live": live}}
        out.append(s)
    return out


def test_live_share_is_weighted_by_the_steps_run():
    # segments 1-3 drain inside the window, having run 8, 4 and 8 steps;
    # 0 and 4 (outside) carry rows that must not count
    spans = with_rows(ROWS | {0: (10 ** 6, 1), 4: (10 ** 6, 1)})
    assert decode_rows_live_share.read(bag(spans)) == pytest.approx(
        100.0 * (1500 * 8 + 1000 * 4 + 4096 * 8)
        / (2048 * 8 + 1024 * 4 + 4096 * 8))


def test_a_program_without_the_rows_gives_none():
    assert decode_rows_live_share.read(bag(SPANS)) is None      # the parent
    assert decode_rows_live_share.read(bag([])) is None
    assert decode_rows_live_share.read(bag(SPANS, rids=())) is None
    # the dense layout: the args are there and read 0
    dense = with_rows({1: (0, 0), 2: (0, 0), 3: (0, 0)})
    assert decode_rows_live_share.read(bag(dense)) is None
