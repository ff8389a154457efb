"""``BlockPool`` block groups: the window layers' group beside the full
layers' (reservation, release below the window, ``check()`` over both), and
a pool with ONE group held, to the block, to a sequence of calls recorded
on the pool as it was before groups (PR 30's commit)."""

import json
import pathlib

import numpy as np
import pytest

from tpudist.models.kv_pages import (BlockPool, WindowGroup, blocks_for,
                                     span_blocks)

BS, WINDOW, STEPS = 8, 24, 4


def _pool(**kw) -> BlockPool:
    return BlockPool(64, BS, 3, 256, window=WINDOW, window_steps=STEPS,
                     **kw)


@pytest.mark.parametrize("rows,blocks", [
    (0, 0), (1, 1), (2, 2), (8, 2), (9, 2), (10, 3), (27, 5), (1039, 131)])
def test_span_blocks_is_the_worst_alignment(rows, blocks):
    assert span_blocks(rows, BS) == blocks
    # by enumeration: rows consecutive positions from every offset
    if rows:
        assert blocks == max(
            (off + rows - 1) // BS - off // BS + 1 for off in range(BS))


def test_a_lane_holds_a_window_and_a_segment_whatever_its_length():
    pool = _pool()
    group = pool.window_group
    # 23 rows of window + 4 of a segment touch 5 blocks at worst
    assert group.lane_blocks == span_blocks(WINDOW - 1 + STEPS, BS) == 5
    assert group.num_blocks == 3 * 5
    pool.admit(0, 100, 150)
    pool.check()
    # the full group covers the prompt; the window group its last 23 rows
    assert pool.used_blocks == blocks_for(100, BS) == 13
    assert group.used_blocks == 13 - (100 + 1 - WINDOW) // BS == 4
    assert (group._lo[0], len(group._blocks[0])) == (9, 4)
    assert group.table[0, 9:13].tolist() == group._blocks[0]
    held = []
    for _ in range(38):                       # to the cap of 250 tokens
        pool.grow(0, STEPS)
        pool.check()
        held.append(group.used_blocks)
    assert max(held) <= group.lane_blocks
    assert pool.used_blocks == blocks_for(250, BS) == 32
    # every block the lane passed through below its window was released
    assert group._lo[0] >= (246 + 1 - WINDOW) // BS
    assert group._lo[0] + len(group._blocks[0]) == 32
    assert group.released == group._lo[0] - 9
    assert not group.table[0, : group._lo[0]].any()
    pool.free_slot(0)
    pool.check()
    assert pool.used_blocks == group.used_blocks == 0
    assert group.free_blocks == group.num_blocks


def test_reservation_is_the_smaller_of_the_request_and_a_lane():
    group = _pool().window_group
    assert group.request_blocks(5, 6) == blocks_for(11, BS) == 2
    assert group.request_blocks(100, 150) == group.lane_blocks
    assert group.request_blocks(200, 500) == group.lane_blocks  # capped at S


def test_the_window_group_refuses_what_it_cannot_promise():
    pool = _pool(window_blocks=7)            # one lane and a bit
    group = pool.window_group
    assert pool.can_admit(100, 100)
    pool.admit(0, 100, 100)
    # 5 promised to lane 0 (4 held + 1 reserved): 2 left
    assert group.free_blocks == 2
    assert not pool.can_admit(100, 100)       # the window group says no
    assert pool.can_admit(5, 6)               # 2 blocks: fits
    with pytest.raises(RuntimeError, match="window blocks exceeds free"):
        group.admit(1, 100, 100)
    pool.admit(1, 5, 6)
    pool.check()
    pool.free_slot(0)
    assert pool.can_admit(100, 100)
    with pytest.raises(ValueError, match="at least one lane"):
        BlockPool(64, BS, 3, 256, window=WINDOW, window_steps=STEPS,
                  window_blocks=4)


def test_released_blocks_stay_promised_to_the_lane():
    """Growth can never fail: what a lane releases goes back to the free
    list but stays counted against the lane's reservation."""
    pool = _pool(window_blocks=10)
    group = pool.window_group
    pool.admit(0, 100, 150)
    pool.admit(1, 60, 190)
    assert group.free_blocks == 0
    for _ in range(40):
        pool.grow(0, STEPS)
        pool.grow(1, STEPS)
        pool.check()
        assert group.free_blocks == 0
        assert group.used_blocks <= 10
    assert group.released > 30


def test_check_sees_a_drifted_window_table_and_a_double_hold():
    pool = _pool()
    group = pool.window_group
    pool.admit(0, 30, 30)
    group.table[0, 0] = 5
    with pytest.raises(AssertionError, match="table row drifted"):
        pool.check()
    group.table[0, 0] = 0
    pool.check()
    group._blocks[1].append(group._blocks[0][0])
    with pytest.raises(AssertionError, match="held twice"):
        pool.check()


def test_short_lanes_release_nothing_and_long_prompts_start_trimmed():
    pool = _pool()
    group = pool.window_group
    pool.admit(0, 10, 10)                     # never reaches the window
    for _ in range(3):
        pool.grow(0, STEPS)
    assert group.released == 0 and group.used_blocks == blocks_for(20, BS)
    pool.admit(1, 200, 8)                     # admitted already trimmed
    assert group._lo[1] == (200 + 1 - WINDOW) // BS == 22
    assert len(group._blocks[1]) == 25 - 22
    pool.check()


def test_window_group_alone():
    group = WindowGroup(10, BS, 2, 128, WINDOW, STEPS)
    group.admit(0, 50, 50)
    group.grow(0, 50, 54)
    group.check()
    assert group.first_block(50) == (50 + 1 - WINDOW) // BS
    with pytest.raises(ValueError, match="window and steps"):
        WindowGroup(10, BS, 2, 128, 0, STEPS)


def test_one_group_pool_is_the_recorded_pool_to_the_block():
    """60 calls (admit / grow / free_slot, one refusal) recorded on the
    pool of PR 30 with its table, ``used_blocks``, ``free_blocks`` and
    ``covered_pages`` after each: a pool without a window group gives the
    same, block for block."""
    doc = json.loads((pathlib.Path(__file__).parent / "data"
                      / "blockpool_recorded_pr30.json").read_text())
    pool = BlockPool(24, 16, 3, 256)
    assert pool.window_group is None
    for call, want in zip(doc["calls"], doc["states"]):
        kind, slot, *rest = call
        if kind == "refused":
            assert not pool.can_admit(*rest)
        elif kind == "admit":
            assert pool.can_admit(*rest)
            pool.admit(slot, *rest)
        elif kind == "grow":
            pool.grow(slot, *rest)
        else:
            pool.free_slot(slot)
        pool.check()
        assert pool.used_blocks == want["used"]
        assert pool.free_blocks == want["free"]
        assert pool.table.tolist() == want["table"]
        assert [pool.covered_pages(s) for s in range(3)] == want["pages"]
