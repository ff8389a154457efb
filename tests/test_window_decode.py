"""``paged_flash_decode(window=...)``: a sliding-window layer's decode step
over the paged cache.  The walk starts at the page that holds the first
row the query sees, masks the rows of that page before the window and
leaves every page below it alone: against ``paged_gather_kv`` + a dense
masked softmax, over lengths below, at and above the window, on tile and
page edges, with an empty lane and with the side buffer.

Page-table entries the walk must not read (pages wholly below the window:
the host may have RELEASED them; pages past the length) name pool block 0,
which is NaN-filled: a dead page that reached the result would show.  The
kernel runs under ``interpret``."""

import functools
import importlib
from unittest import mock

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tpudist.ops.flash_decode import (paged_flash_decode, paged_gather_kv,
                                      paged_tile_pages, walk_rows)

BLOCK, M_BLOCKS, CAP, HEADS = 128, 20, 8, 8
P = paged_tile_pages(BLOCK, M_BLOCKS)
LANES = 7
WINDOWS = {"w1024": 1024, "w200": 200}
SIDE_LENS = {"side1": 1, "side5": 5, "side8": 8}
F32, BF16 = jnp.float32, jnp.bfloat16
# (kv heads, head_dim, query heads, dtype of q and the pools): one K/V head;
# grouped heads that share a lane's grid row (the layout a GQA model runs:
# two of 128; four of 128 in bf16, the Mellum cell's row; two pair chunks of
# d = 64); and lanes over the VMEM budget, several rows each a group of heads
# (four of 128 in float32: 2 rows of 2; eight of 128 in bf16: 2 rows of 4)
LAYOUTS = {"kv1": (1, 16, 8, F32), "kv2_d128": (2, 128, 8, F32),
           "kv4_d128_bf16": (4, 128, 8, BF16),
           "kv4_d64_paired": (4, 64, 8, F32),
           "kv4_d128": (4, 128, 8, F32), "kv8_d128_bf16": (8, 128, 16, BF16)}
# the layouts of this PR run the lengths that tell a folded row from a row a
# head: ragged lanes with an empty one and one shorter than the window, every
# lane empty, one row past the window, a last tile of one live row
FOLDED = ("kv4_d128_bf16", "kv4_d64_paired", "kv4_d128", "kv8_d128_bf16")
FOLDED_LENGTHS = ("mixed", "len0", "below_window", "window+1", "tile_edge+1")


def _edges(w: int) -> dict:
    return {
        "len0": 0, "len1": 1, "below_window": w - 7, "window-1": w - 1,
        "window": w, "window+1": w + 1, "window+block": w + BLOCK,
        "page_edge": w + 3 * BLOCK, "page_edge+1": w + 3 * BLOCK + 1,
        "page_edge-1": w + 3 * BLOCK - 1,
        "tile_edge": 2 * P * BLOCK, "tile_edge+1": 2 * P * BLOCK + 1,
        "all_pages": M_BLOCKS * BLOCK,
        "all_pages-side": M_BLOCKS * BLOCK - CAP,
    }


def _alone(n: int) -> list[int]:
    lens = [0] * LANES
    lens[LANES // 2] = n
    return lens


def _lengths(w: int) -> dict:
    mixed = [0, w + 5, 3, 0, 2 * w + BLOCK + 9, w - 1, M_BLOCKS * BLOCK - 3]
    return {"mixed": mixed, **{k: _alone(v) for k, v in _edges(w).items()}}


CASES = [(wn, ln, layout) for layout in LAYOUTS
         for wn, w in WINDOWS.items() for ln in _lengths(w)
         if layout not in FOLDED or ln in FOLDED_LENGTHS]


def _reference(q, k_pool, v_pool, table, lens, h_kv, side_k, side_v,
               side_len, window):
    b, _, h, d = q.shape
    k = paged_gather_kv(k_pool, table)
    v = paged_gather_kv(v_pool, table)
    pos = jnp.arange(k.shape[1])[None, :]
    # the query is row len + side_len - 1; it sees `window` rows, itself
    # included: the side buffer's live rows and the pool's from
    # len + side_len - window on
    keep = (pos < lens[:, None]) & (pos >= (lens + side_len - window)[:, None])
    k = jnp.concatenate([k, side_k], axis=1)
    v = jnp.concatenate([v, side_v], axis=1)
    keep = jnp.concatenate(
        [keep, jnp.broadcast_to(
            jnp.arange(side_k.shape[1])[None, :] < side_len,
            (b, side_k.shape[1]))], axis=1)
    k = jnp.where(keep[:, :, None], k, 0).reshape(b, -1, h_kv, d)
    v = jnp.where(keep[:, :, None], v, 0).reshape(b, -1, h_kv, d)
    k = jnp.repeat(k, h // h_kv, axis=2)
    v = jnp.repeat(v, h // h_kv, axis=2)
    s = jnp.einsum("bhd,bshd->bhs", q[:, 0], k,
                   precision="highest") * d ** -0.5
    s = jnp.where(keep[:, None, :], s, -jnp.inf)
    top = jnp.max(s, axis=-1, keepdims=True)
    p = jnp.exp(s - jnp.where(jnp.isfinite(top), top, 0.0))
    out = jnp.einsum("bhs,bshd->bhd", p, v, precision="highest")
    return (out / jnp.maximum(jnp.sum(p, -1, keepdims=True), 1e-30))[:, None]


def _owned():
    return 1 + np.random.default_rng(11).permutation(
        LANES * M_BLOCKS).reshape(LANES, M_BLOCKS)


@functools.cache
def _setup(layout: str, window: int):
    h_kv, d, heads, dtype = LAYOUTS[layout]
    flat = h_kv * d
    ks = jax.random.split(jax.random.key(31), 5)
    n_pool = LANES * M_BLOCKS + 1
    q = jax.random.normal(ks[0], (LANES, 1, heads, d), dtype)
    pools = [np.array(jax.random.normal(k, (n_pool, BLOCK, flat), dtype))
             for k in ks[1:3]]
    for pool in pools:
        pool[0] = np.nan
    side_k = jax.random.normal(ks[3], (LANES, CAP, flat), dtype)
    side_v = jax.random.normal(ks[4], (LANES, CAP, flat), dtype)

    @jax.jit
    def both(k_pool, v_pool, table, lens, side_len):
        got = paged_flash_decode(
            q, k_pool, v_pool, table, lens, packed_kv_heads=h_kv,
            side_k=side_k, side_v=side_v, side_len=side_len,
            interpret=True, window=window)
        # the reference in float32 from the same (bf16) values
        want = _reference(
            q.astype(F32), k_pool.astype(F32), v_pool.astype(F32), table,
            lens, h_kv, side_k.astype(F32), side_v.astype(F32), side_len,
            window)
        return got.astype(F32), want

    return pools, both


def _held_table(lens, side_len: int, window: int):
    """Only the pages the walk may read name a real block: those from the
    one that holds row ``len + side_len - window`` to the last under the
    length.  Every other entry names the poisoned block 0."""
    pages = -(-lens // BLOCK)
    first = np.maximum(lens + side_len - window, 0) // BLOCK
    j = np.arange(M_BLOCKS)[None, :]
    return np.where((j >= first[:, None]) & (j < pages[:, None]),
                    _owned(), 0)


@pytest.mark.parametrize("side", SIDE_LENS)
@pytest.mark.parametrize("window,lengths,layout", CASES)
def test_window_walk_matches_dense_masked_softmax(window, lengths, layout,
                                                  side):
    w, side_len = WINDOWS[window], SIDE_LENS[side]
    lens = np.asarray(_lengths(w)[lengths], np.int32)
    pools, both = _setup(layout, w)
    got, want = both(*pools,
                     jnp.asarray(_held_table(lens, side_len, w), jnp.int32),
                     jnp.asarray(lens), jnp.int32(side_len))
    got, want = np.asarray(got), np.asarray(want)
    assert np.isfinite(got).all(), \
        "a page outside the window (released, poisoned) reached the result"
    # bf16: the probabilities go into the MXU in the pools' dtype (2^-9 of a
    # weight each); a head that read another head's columns is off by ~1
    tol = 2e-5 if LAYOUTS[layout][3] == F32 else 3e-2
    np.testing.assert_allclose(got, want, rtol=tol, atol=tol)


def test_a_lane_shorter_than_the_window_walks_as_without_one():
    """Bit for bit: no page is skipped, no row is masked."""
    w = 1024
    lens = np.asarray([5, w - CAP, 0, 300, BLOCK, 1, 700], np.int32)
    pools, both = _setup("kv2_d128", w)
    h_kv, d = LAYOUTS["kv2_d128"][:2]
    ks = jax.random.split(jax.random.key(31), 5)
    q = jax.random.normal(ks[0], (LANES, 1, HEADS, d), jnp.float32)
    flat = h_kv * d
    side_k = jax.random.normal(ks[3], (LANES, CAP, flat), jnp.float32)
    side_v = jax.random.normal(ks[4], (LANES, CAP, flat), jnp.float32)
    table = jnp.asarray(_held_table(lens, CAP, w), jnp.int32)
    kw = dict(packed_kv_heads=h_kv, side_k=side_k, side_v=side_v,
              side_len=CAP, interpret=True)
    with_w = paged_flash_decode(q, *pools, table, jnp.asarray(lens),
                                window=w, **kw)
    without = paged_flash_decode(q, *pools, table, jnp.asarray(lens), **kw)
    np.testing.assert_array_equal(np.asarray(with_w), np.asarray(without))


def test_window_needs_the_side_buffers_and_one_query():
    q = jnp.ones((2, 1, HEADS, 16))
    pool = jnp.ones((4, BLOCK, 16))
    table = jnp.zeros((2, 3), jnp.int32)
    with pytest.raises(ValueError, match="side buffers"):
        paged_flash_decode(q, pool, pool, table, jnp.zeros((2,), jnp.int32),
                           packed_kv_heads=1, window=64, interpret=True)


# -- walk_rows with a window is the host's count of what the kernel computes

@pytest.mark.parametrize("side_len", [1, 5])
@pytest.mark.parametrize("length", [
    1024 - 9, 1024, 1024 + 1, 1024 + 3 * BLOCK + 1, 2 * P * BLOCK + 7,
    M_BLOCKS * BLOCK - 8],
    ids=["below", "window", "window+1", "page_edge+1", "two_tiles+7",
         "all_pages-8"])
def test_walk_rows_with_a_window_is_what_the_kernel_computes(length,
                                                             side_len):
    """As ``tests/test_paged_decode_walk.py`` holds it without a window:
    the widths of the score tiles ``_softmax_update`` was really given sum
    to ``walk_rows(..., window=, side_len=)`` (the side buffer's rows ride
    in the last tile's update as ``also`` and are not counted)."""
    w = 1024
    fd = importlib.import_module("tpudist.ops.flash_decode")
    update, widths = fd._softmax_update, []

    def counted(m, l, acc, s, *rest, **kw):
        jax.debug.callback(lambda n=s.shape[1]: widths.append(n))
        update(m, l, acc, s, *rest, **kw)

    lens = jnp.asarray([0, length, 0], jnp.int32)
    table = jnp.asarray(1 + np.arange(3 * M_BLOCKS).reshape(3, M_BLOCKS),
                        jnp.int32)
    fd._paged_decode_one.clear_cache()
    try:
        with mock.patch.object(fd, "_softmax_update", counted):
            pool = jnp.ones((3 * M_BLOCKS + 1, BLOCK, 16))
            side = jnp.ones((3, CAP, 16))
            out = paged_flash_decode(
                jnp.ones((3, 1, HEADS, 16)), pool, pool, table, lens,
                packed_kv_heads=1, side_k=side, side_v=side,
                side_len=side_len, interpret=True, window=w)
            jax.block_until_ready(out)
            jax.effects_barrier()
    finally:
        fd._paged_decode_one.clear_cache()
    # the two empty lanes pay the side buffer's lone update each
    assert sum(widths) - 2 * CAP == walk_rows(length, BLOCK, P, w, side_len)
    # never more than the window, the page the window starts in and the
    # last tile's width granule
    assert walk_rows(length, BLOCK, P, w, side_len) <= w + 2 * BLOCK
    assert walk_rows(length, BLOCK, P, w, side_len) >= min(length, w - side_len)


@pytest.mark.parametrize("length", [0, 1, 127, 128, 1023, 5000])
def test_walk_rows_without_a_window_is_unchanged(length):
    assert walk_rows(length, BLOCK, P) == walk_rows(length, BLOCK, P, None)
    if length + 1 <= 1024:
        assert walk_rows(length, BLOCK, P, 1024) == walk_rows(
            length, BLOCK, P)
