"""``paged_flash_decode`` walks a lane's LIVE pages only, several pages a
tile, and computes its live rows only (the tiles before a lane's last unmasked,
the last in ONE masked update at the width its live pages need, the side
buffer's rows in that same update): every length that sits on an edge of the walk, against
``paged_gather_kv`` + masked attention.

Dead page-table entries point at pool block 0, which is NaN-filled here:
a dead page that reached the result would show.  The kernel runs under
``interpret`` (its own copies, semaphores and ``fori_loop`` included)."""

import functools
import importlib
from unittest import mock

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tpudist.ops.flash_decode import (paged_flash_decode, paged_gather_kv,
                                      paged_grid_rows, paged_mla_decode,
                                      walk_rows)

BLOCK, M_BLOCKS, CAP, HEADS = 128, 20, 8, 4
P = 1024 // BLOCK                      # pages a tile, as the kernel derives
LANES = 11

# one batch with empty lanes between live ones, then each edge alone
# between two empty lanes
MIXED = [0, 1, BLOCK - 1, 0, BLOCK, BLOCK + 1, 0, 11 * BLOCK - 5,
         P * BLOCK, M_BLOCKS * BLOCK, 0]
EDGES = {
    "len0": 0, "len1": 1, "block-1": BLOCK - 1, "block": BLOCK,
    "block+1": BLOCK + 1, "pages_not_multiple_of_P": (P + 3) * BLOCK - 7,
    "exactly_P_pages": P * BLOCK, "P_pages+1": P * BLOCK + 1,
    "all_pages": M_BLOCKS * BLOCK,
    "tile-1": P * BLOCK - 1,
    # a full tile, then a last tile of k pages: its widths (k pages at
    # "-1" and "+0", k + 1 at "+1"), and the mask's place one row either
    # side of each page edge
    **{f"tile+{k}pages{row:+d}": (P + k) * BLOCK + row
       for k in (1, 2, 3, 4, 5, 7) for row in (-1, 0, 1)},
}


def _alone(n: int) -> list[int]:
    lens = [0] * LANES
    lens[LANES // 2] = n
    return lens


LENGTHS = {"mixed": MIXED, **{k: _alone(v) for k, v in EDGES.items()}}
F32, BF16 = jnp.float32, jnp.bfloat16
# (kv heads, head_dim, query heads, dtype of q and the pools) -> grid rows a
# lane.  One K/V head; a head-paired layout (d * 2 <= 128); then the layouts
# whose K/V heads SHARE a lane's grid row (its page copies, its rank
# updates): two heads of 128; four of 128 in bf16 (the Mellum cell's row);
# two pair chunks of d = 64 (four heads in one block-diagonal query); and
# the lanes whose tile slots pass the VMEM budget and take several rows,
# each a group of heads: four heads of 128 in float32 (2 rows of 2), eight
# of 128 in bf16 (2 rows of 4)
LAYOUTS = {"kv1": (1, 16, 4, F32), "kv2_paired": (2, 16, 4, F32),
           "kv2_d128": (2, 128, 4, F32),
           "kv4_d128_bf16": (4, 128, 8, BF16),
           "kv4_d64_paired": (4, 64, 8, F32),
           "kv4_d128": (4, 128, 8, F32), "kv8_d128_bf16": (8, 128, 16, BF16)}
ROWS_A_LANE = {"kv1": 1, "kv2_paired": 1, "kv2_d128": 1, "kv4_d128_bf16": 1,
               "kv4_d64_paired": 1, "kv4_d128": 2, "kv8_d128_bf16": 2}
# the layouts of this PR run the lengths that tell a folded row from a row a
# head: ragged lanes with empty ones between, every lane empty, a last tile
# of one live row, a last tile of three pages and a row, a lane's whole table
FOLDED = ("kv4_d128_bf16", "kv4_d64_paired", "kv4_d128", "kv8_d128_bf16")
FOLDED_LENGTHS = ("mixed", "len0", "P_pages+1", "tile+3pages+1", "all_pages")
CASES = [(n, layout) for layout in LAYOUTS
         for n in (FOLDED_LENGTHS if layout in FOLDED else LENGTHS)]
SIDES = {"noside": None, "side0": 0, "side5": 5}


def _reference(q, k_pool, v_pool, table, lens, h_kv, side_k, side_v,
               side_len):
    b, _, h, d = q.shape
    k = paged_gather_kv(k_pool, table)
    v = paged_gather_kv(v_pool, table)
    keep = jnp.arange(k.shape[1])[None, :] < lens[:, None]
    if side_k is not None:
        k = jnp.concatenate([k, side_k], axis=1)
        v = jnp.concatenate([v, side_v], axis=1)
        keep = jnp.concatenate(
            [keep, jnp.broadcast_to(
                jnp.arange(side_k.shape[1])[None, :] < side_len,
                (b, side_k.shape[1]))], axis=1)
    # dead positions hold NaN: take them out before any product
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
    # a lane with nothing to attend gives 0, as the kernel's clamp does
    return (out / jnp.maximum(jnp.sum(p, -1, keepdims=True), 1e-30))[:, None]


def _owned():
    """Every lane's M_BLOCKS scattered pool blocks (block 0 is nobody's)."""
    return 1 + np.random.default_rng(7).permutation(
        LANES * M_BLOCKS).reshape(LANES, M_BLOCKS)


@functools.cache
def _setup(layout: str, side: str):
    """The two pools (numpy, block 0 NaN: the block dead entries name) and
    the jitted call of one (layout, side): pools, table and lengths are
    arguments, so every case of it shares one compile."""
    h_kv, d, heads, dtype = LAYOUTS[layout]
    flat = h_kv * d
    ks = jax.random.split(jax.random.key(26), 5)
    n_pool = LANES * M_BLOCKS + 1
    q = jax.random.normal(ks[0], (LANES, 1, heads, d), dtype)
    pools = [np.array(jax.random.normal(k, (n_pool, BLOCK, flat), dtype))
             for k in ks[1:3]]
    for pool in pools:
        pool[0] = np.nan
    side_len = SIDES[side]
    if side_len is None:
        side_k = side_v = None
    else:
        side_k = jax.random.normal(ks[3], (LANES, CAP, flat), dtype)
        side_v = jax.random.normal(ks[4], (LANES, CAP, flat), dtype)

    @jax.jit
    def both(k_pool, v_pool, table, lens):
        got = paged_flash_decode(
            q, k_pool, v_pool, table, lens, packed_kv_heads=h_kv,
            side_k=side_k, side_v=side_v, side_len=side_len or 0,
            interpret=True)
        # the reference in float32 from the same (bf16) values
        up = lambda x: None if x is None else x.astype(F32)  # noqa: E731
        want = _reference(up(q), up(k_pool), up(v_pool), table, lens, h_kv,
                          up(side_k), up(side_v), side_len or 0)
        return got.astype(F32), want

    return pools, both


def _live_table(lens):
    """Entries past a lane's live pages are DEAD and name the poisoned
    block 0."""
    pages = -(-lens // BLOCK)
    return np.where(np.arange(M_BLOCKS)[None, :] < pages[:, None],
                    _owned(), 0)


def _check(got, want, what: str, layout: str = "kv1"):
    got, want = np.asarray(got), np.asarray(want)
    assert np.isfinite(got).all(), what
    # bf16: the probabilities go into the MXU in the pools' dtype (2^-9 of a
    # weight each); a head that read another head's columns is off by ~1
    tol = 2e-5 if LAYOUTS[layout][3] == F32 else 3e-2
    np.testing.assert_allclose(got, want, rtol=tol, atol=tol)
    return got


@pytest.mark.parametrize("side", SIDES)
@pytest.mark.parametrize("lengths,layout", CASES)
def test_walk_matches_gather_reference(lengths, layout, side):
    lens = np.asarray(LENGTHS[lengths], np.int32)
    pools, both = _setup(layout, side)
    got = _check(*both(*pools, jnp.asarray(_live_table(lens), jnp.int32),
                       jnp.asarray(lens)),
                 "a dead (poisoned) page reached the result", layout)
    if SIDES[side] in (None, 0):
        # nothing to attend: an empty lane's output is 0
        assert not got[lens == 0].any()


# -- the same walk over ONE pool whose rows are keys and, in their first
# columns, values: paged_mla_decode (absorbed latent attention) -------------

MLA_HEADS, MLA_W, MLA_DV, MLA_SCALE = 12, 128, 64, 0.21


def _mla_reference(q, pool, table, lens, side, side_len):
    b = q.shape[0]
    rows = paged_gather_kv(pool, table)
    keep = jnp.arange(rows.shape[1])[None, :] < lens[:, None]
    if side is not None:
        rows = jnp.concatenate([rows, side], axis=1)
        keep = jnp.concatenate(
            [keep, jnp.broadcast_to(
                jnp.arange(side.shape[1])[None, :] < side_len,
                (b, side.shape[1]))], axis=1)
    rows = jnp.where(keep[:, :, None], rows, 0)   # dead rows hold NaN
    s = jnp.einsum("bhw,bsw->bhs", q, rows, precision="highest") * MLA_SCALE
    s = jnp.where(keep[:, None, :], s, -jnp.inf)
    top = jnp.max(s, axis=-1, keepdims=True)
    p = jnp.exp(s - jnp.where(jnp.isfinite(top), top, 0.0))
    out = jnp.einsum("bhs,bsc->bhc", p, rows[..., :MLA_DV],
                     precision="highest")
    return out / jnp.maximum(jnp.sum(p, -1, keepdims=True), 1e-30)


@functools.cache
def _mla_setup(side: str):
    ks = jax.random.split(jax.random.key(27), 3)
    n_pool = LANES * M_BLOCKS + 1
    q = jax.random.normal(ks[0], (LANES, MLA_HEADS, MLA_W), jnp.float32)
    pool = np.array(jax.random.normal(ks[1], (n_pool, BLOCK, MLA_W)))
    pool[0] = np.nan                           # the block dead entries name
    side_len = SIDES[side]
    buf = (None if side_len is None else
           jax.random.normal(ks[2], (LANES, CAP, MLA_W), jnp.float32))

    @jax.jit
    def both(pool, table, lens):
        got = paged_mla_decode(
            q, pool, table, lens, d_v=MLA_DV, scale=MLA_SCALE, side=buf,
            side_len=side_len or 0, interpret=True)
        return got, _mla_reference(q, pool, table, lens, buf, side_len or 0)

    return pool, both


@pytest.mark.parametrize("side", SIDES)
@pytest.mark.parametrize("lengths", LENGTHS)
def test_latent_walk_matches_gather_reference(lengths, side):
    lens = np.asarray(LENGTHS[lengths], np.int32)
    pool, both = _mla_setup(side)
    got = _check(*both(pool, jnp.asarray(_live_table(lens), jnp.int32),
                       jnp.asarray(lens)),
                 "a dead (poisoned) page reached the result")
    assert got.shape == (LANES, MLA_HEADS, MLA_DV)
    if SIDES[side] in (None, 0):
        assert not got[lens == 0].any()


# -- rows beyond a length are not computed: poison them all -----------------

# a batch whose lanes end at every width of the last tile, one row either
# side of a page edge, beside the first batch's edges
WIDTHS = [P * BLOCK - 1, (P + 1) * BLOCK - 1, (P + 2) * BLOCK + 1,
          (P + 3) * BLOCK, (P + 4) * BLOCK - 1, (P + 5) * BLOCK + 1,
          (P + 7) * BLOCK - 1, 2 * P * BLOCK + 1, 1, 0, 777]
POISONED = {"mixed": MIXED, "widths": WIDTHS}


def _poison(pool, lens):
    """``pool`` with NaN in every row that no lane's length reaches: the
    rows at and beyond each lane's length in ALL the pages it owns (its
    last live page's tail and every page past it), beside block 0."""
    pool = pool.copy()
    for blocks, n in zip(_owned(), lens):
        rows = pool[blocks].reshape(M_BLOCKS * BLOCK, -1)
        rows[n:] = np.nan
        pool[blocks] = rows.reshape(M_BLOCKS, BLOCK, -1)
    return pool


@pytest.mark.parametrize("side", SIDES)
@pytest.mark.parametrize("layout", LAYOUTS)
@pytest.mark.parametrize("lengths", POISONED)
def test_no_row_beyond_a_length_is_computed(lengths, layout, side):
    """The table names every page a lane owns, live or not, and the pools
    hold NaN in every row at or beyond its length: a dead row that is
    still computed (a weight of 0 on it is NaN in the MXU) shows."""
    lens = np.asarray(POISONED[lengths], np.int32)
    pools, both = _setup(layout, side)
    _check(*both(*(_poison(p, lens) for p in pools),
                 jnp.asarray(_owned(), jnp.int32), jnp.asarray(lens)),
           "a dead (poisoned) row was computed", layout)


@pytest.mark.parametrize("side", SIDES)
@pytest.mark.parametrize("lengths", POISONED)
def test_no_latent_row_beyond_a_length_is_computed(lengths, side):
    lens = np.asarray(POISONED[lengths], np.int32)
    pool, both = _mla_setup(side)
    _check(*both(_poison(pool, lens), jnp.asarray(_owned(), jnp.int32),
                 jnp.asarray(lens)),
           "a dead (poisoned) row was computed")


# -- walk_rows is the host's count of what the kernel computes ---------------

@pytest.mark.parametrize("length", [
    (P + 2) * BLOCK + 1, 2 * P * BLOCK, 5 * BLOCK - 7],
    ids=["tile+2pages+1", "two_tiles", "5pages-7"])
@pytest.mark.parametrize("kernel", ["flash", "mla"])
def test_walk_rows_is_what_the_kernel_computes(kernel, length):
    """Every rank update of the walk goes through ``_softmax_update``: the
    widths of the score tiles it was really given (a callback inside the
    interpreted body, so an update under a ``pl.when`` that did not fire
    is not counted) sum to ``walk_rows`` of the lane's length.  Poisoning
    cannot count them: the rows of the last tile beyond the length are
    computed and, by design, never show."""
    fd = importlib.import_module("tpudist.ops.flash_decode")
    update, widths = fd._softmax_update, []

    def counted(m, l, acc, s, *rest, **kw):
        jax.debug.callback(lambda n=s.shape[1]: widths.append(n))
        update(m, l, acc, s, *rest, **kw)

    lens = jnp.asarray([0, length, 0], jnp.int32)
    table = jnp.asarray(1 + np.arange(3 * M_BLOCKS).reshape(3, M_BLOCKS),
                        jnp.int32)
    # the calls are jitted: neither an earlier trace may answer here nor
    # this one, with its callback, later
    jitted = fd._paged_mla_one if kernel == "mla" else fd._paged_decode_one
    jitted.clear_cache()
    try:
        with mock.patch.object(fd, "_softmax_update", counted):
            if kernel == "mla":
                out = paged_mla_decode(
                    jnp.ones((3, MLA_HEADS, MLA_W)),
                    jnp.ones((3 * M_BLOCKS + 1, BLOCK, MLA_W)), table, lens,
                    d_v=MLA_DV, scale=MLA_SCALE, interpret=True)
            else:
                pool = jnp.ones((3 * M_BLOCKS + 1, BLOCK, 16))
                out = paged_flash_decode(
                    jnp.ones((3, 1, HEADS, 16)), pool, pool, table, lens,
                    packed_kv_heads=1, interpret=True)
            jax.block_until_ready(out)
            jax.effects_barrier()
    finally:
        jitted.clear_cache()
    assert sum(widths) == walk_rows(length, BLOCK, P)
    assert walk_rows(length, BLOCK, P) - length < BLOCK
    assert walk_rows(0, BLOCK, P) == 0


# -- a grid row is a lane: paged_grid_rows is the call's grid and the host's
# count (serve/decode_grid_rows) -------------------------------------------

def _pallas_grids(jaxpr) -> list[tuple]:
    """The ``grid=`` of every ``pallas_call`` under ``jaxpr``."""
    grids = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            grids.append(tuple(eqn.params["grid_mapping"].grid))
        for sub in jax.core.jaxprs_in_params(eqn.params):
            grids += _pallas_grids(sub)
    return grids


# the serving cells' calls (lanes, K/V heads, head width, pools, bytes an
# element; block 128, 64 table entries): Mellum 2 and the 3B a row a lane,
# DeepSeek's latent pool likewise; over the VMEM budget, rows of head groups
@pytest.mark.parametrize("shape,rows", [
    ((40, 4, 128, 2, 2), 40), ((24, 1, 128, 2, 2), 24),
    ((128, 1, 640, 1, 2), 128), ((40, 8, 128, 2, 2), 2 * 40),
    ((40, 4, 128, 2, 4), 2 * 40), ((40, 16, 128, 2, 2), 4 * 40),
    # one pair of 64 is one chunk already; two pairs share the row; a chunk
    # narrower than a 128-lane tile keeps a row of its own
    ((4, 2, 64, 2, 2), 4), ((4, 4, 64, 2, 2), 4), ((4, 3, 16, 2, 2), 3 * 4)],
    ids=["mellum2", "sc3b", "dsv3_latent", "8_heads", "4_heads_f32",
         "16_heads", "one_pair", "two_pairs", "narrow"])
def test_grid_rows_at_the_cells_shapes(shape, rows):
    lanes, h_kv, d, pools, itemsize = shape
    assert paged_grid_rows(lanes, h_kv, d, 128, 64, pools=pools,
                           itemsize=itemsize) == rows


@pytest.mark.parametrize("layout", LAYOUTS)
def test_the_call_takes_its_grid_from_the_helper(layout):
    h_kv, d, heads, dtype = LAYOUTS[layout]
    pool = jax.ShapeDtypeStruct((9, BLOCK, h_kv * d), dtype)
    jaxpr = jax.make_jaxpr(functools.partial(
        paged_flash_decode, packed_kv_heads=h_kv, interpret=True))(
        jax.ShapeDtypeStruct((LANES, 1, heads, d), dtype), pool, pool,
        jax.ShapeDtypeStruct((LANES, M_BLOCKS), jnp.int32),
        jax.ShapeDtypeStruct((LANES,), jnp.int32))
    rows = paged_grid_rows(LANES, h_kv, d, BLOCK, M_BLOCKS,
                           itemsize=jnp.dtype(dtype).itemsize)
    assert _pallas_grids(jaxpr.jaxpr) == [(rows,)]
    assert rows == ROWS_A_LANE[layout] * LANES


def test_the_latent_call_takes_its_grid_from_the_helper():
    jaxpr = jax.make_jaxpr(functools.partial(
        paged_mla_decode, d_v=MLA_DV, scale=MLA_SCALE, interpret=True))(
        jax.ShapeDtypeStruct((LANES, MLA_HEADS, MLA_W), F32),
        jax.ShapeDtypeStruct((9, BLOCK, MLA_W), F32),
        jax.ShapeDtypeStruct((LANES, M_BLOCKS), jnp.int32),
        jax.ShapeDtypeStruct((LANES,), jnp.int32))
    assert _pallas_grids(jaxpr.jaxpr) == [(paged_grid_rows(
        LANES, 1, MLA_W, BLOCK, M_BLOCKS, pools=1, itemsize=4),)] == [
        (LANES,)]
