"""``paged_flash_decode`` walks a lane's LIVE pages only, several pages a
tile: every length that sits on an edge of the walk, against
``paged_gather_kv`` + masked attention.

Dead page-table entries point at pool block 0, which is NaN-filled here:
a dead page that reached the result would show.  The kernel runs under
``interpret`` (its own copies, semaphores and ``fori_loop`` included)."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tpudist.ops.flash_decode import (paged_flash_decode, paged_gather_kv,
                                      paged_mla_decode)

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
}


def _alone(n: int) -> list[int]:
    lens = [0] * LANES
    lens[LANES // 2] = n
    return lens


LENGTHS = {"mixed": MIXED, **{k: _alone(v) for k, v in EDGES.items()}}
# (kv heads, head_dim): one K/V head; a head-paired layout (d * 2 <= 128);
# two K/V heads of 128, each grid row its own chunk of the packed minor dim
LAYOUTS = {"kv1": (1, 16), "kv2_paired": (2, 16), "kv2_d128": (2, 128)}
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


@functools.cache
def _setup(layout: str, side: str):
    """Pools, buffers and the jitted call of one (layout, side): the
    lengths and the table are arguments, so every case of it shares one
    compile."""
    h_kv, d = LAYOUTS[layout]
    flat = h_kv * d
    ks = jax.random.split(jax.random.key(26), 5)
    n_pool = LANES * M_BLOCKS + 1
    q = jax.random.normal(ks[0], (LANES, 1, HEADS, d), jnp.float32)
    k_pool = jax.random.normal(ks[1], (n_pool, BLOCK, flat), jnp.float32)
    v_pool = jax.random.normal(ks[2], (n_pool, BLOCK, flat), jnp.float32)
    k_pool = k_pool.at[0].set(jnp.nan)         # the block dead entries name
    v_pool = v_pool.at[0].set(jnp.nan)
    side_len = SIDES[side]
    if side_len is None:
        side_k = side_v = None
    else:
        side_k = jax.random.normal(ks[3], (LANES, CAP, flat), jnp.float32)
        side_v = jax.random.normal(ks[4], (LANES, CAP, flat), jnp.float32)

    @jax.jit
    def both(table, lens):
        got = paged_flash_decode(
            q, k_pool, v_pool, table, lens, packed_kv_heads=h_kv,
            side_k=side_k, side_v=side_v, side_len=side_len or 0,
            interpret=True)
        want = _reference(q, k_pool, v_pool, table, lens, h_kv, side_k,
                          side_v, side_len or 0)
        return got, want

    return both


@pytest.mark.parametrize("side", SIDES)
@pytest.mark.parametrize("layout", LAYOUTS)
@pytest.mark.parametrize("lengths", LENGTHS)
def test_walk_matches_gather_reference(lengths, layout, side):
    lens = np.asarray(LENGTHS[lengths], np.int32)
    pages = -(-lens // BLOCK)
    # every lane owns M_BLOCKS scattered pool blocks; entries past its
    # live pages are DEAD and name the poisoned block 0
    owned = 1 + np.random.default_rng(7).permutation(
        LANES * M_BLOCKS).reshape(LANES, M_BLOCKS)
    table = np.where(np.arange(M_BLOCKS)[None, :] < pages[:, None], owned, 0)
    got, want = _setup(layout, side)(jnp.asarray(table, jnp.int32),
                                     jnp.asarray(lens))
    got, want = np.asarray(got), np.asarray(want)
    assert np.isfinite(got).all(), "a dead (poisoned) page reached the result"
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-5)
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
    pool = jax.random.normal(ks[1], (n_pool, BLOCK, MLA_W), jnp.float32)
    pool = pool.at[0].set(jnp.nan)             # the block dead entries name
    side_len = SIDES[side]
    buf = (None if side_len is None else
           jax.random.normal(ks[2], (LANES, CAP, MLA_W), jnp.float32))

    @jax.jit
    def both(table, lens):
        got = paged_mla_decode(
            q, pool, table, lens, d_v=MLA_DV, scale=MLA_SCALE, side=buf,
            side_len=side_len or 0, interpret=True)
        return got, _mla_reference(q, pool, table, lens, buf, side_len or 0)

    return both


@pytest.mark.parametrize("side", SIDES)
@pytest.mark.parametrize("lengths", LENGTHS)
def test_latent_walk_matches_gather_reference(lengths, side):
    lens = np.asarray(LENGTHS[lengths], np.int32)
    pages = -(-lens // BLOCK)
    owned = 1 + np.random.default_rng(7).permutation(
        LANES * M_BLOCKS).reshape(LANES, M_BLOCKS)
    table = np.where(np.arange(M_BLOCKS)[None, :] < pages[:, None], owned, 0)
    got, want = _mla_setup(side)(jnp.asarray(table, jnp.int32),
                                 jnp.asarray(lens))
    got, want = np.asarray(got), np.asarray(want)
    assert got.shape == (LANES, MLA_HEADS, MLA_DV)
    assert np.isfinite(got).all(), "a dead (poisoned) page reached the result"
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-5)
    if SIDES[side] in (None, 0):
        assert not got[lens == 0].any()
