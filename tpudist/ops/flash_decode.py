"""Pallas flash-decode kernel: single-token attention against a KV cache.

The decode hot op.  Training flash attention (`tpudist/ops/flash_attention.py`)
tiles queries in ``block_q`` rows; at decode time there is exactly ONE query
per head, so that layout wastes the (8, 128) tile on padding.  The decode
trick is to put the GQA *query-head group* on the sublane axis instead: with
``g = H / H_kv`` query heads per KV head, the per-(batch, kv-head) work is a
``[g, D] × [S, D]ᵀ`` matmul — queries of the same group share the K/V
stream, so the cache is read ONCE per kv head (the memory-bound quantity at
long context) while the MXU sees a real tile.

Grid: ``(B·H_kv, nb)``, K sequential innermost with the online-softmax
recurrence in VMEM scratch — the same structure as the training kernel's
K loop.  Without ``window``, ``nb = S/block_k`` and blocks past
``cache_len`` skip their FLOPs under ``pl.when`` (the fetch still
streams, bounded by the allocated cache).  With ``window`` the grid is
TRIMMED: a scalar-prefetch ``start_block`` points the block index maps
at the ~``window/block_k`` blocks intersecting the window span, so a
windowed decode streams ~``window`` positions per step instead of the
whole cache — at the bandwidth-bound decode op that is a ~S/window
speedup.  Positions beyond the cache index, or older than the window,
mask to -inf as before.

The PAGED kernel (:func:`paged_flash_decode`) shares the softmax update
and has its own body: grid ``(B,)``, a row a lane with all its K/V heads
(:func:`paged_grid_rows`), the pools left in HBM, and a loop inside the
body over the pages a lane really holds, fetched whole, a tile of several
pages at a time, by its own double-buffered copies.

Guideline (pre-PR 1 capture, not re-measured): ``head_dim < 128`` underfills
the 128-lane tile width of the K/V blocks (measured: half DMA
bandwidth).  With EVEN ``h_kv`` both the bf16 AND int8 paths recover
full width by HEAD PAIRING (see ``_flash_decode_impl``; since round 4
the int8 per-(token, head) scales ride the paired tile as one row per
pair member, applied half-wise in the kernel): bf16 kernel-level parity
with d=128 (636 vs 639 GB/s measured), model-level within ~1.37×
(residual per-step packing overhead).  Odd-``h_kv`` narrow-head models
stay unpaired at ~half DMA width — prefer even ``h_kv`` or
head_dim-128 configurations where the model design allows.

Reference scope note: the reference suite is training-only (SURVEY.md §2 —
no inference path anywhere); this kernel + the TP rollout in
:mod:`tpudist.models.generate` are the framework's serving story.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from tpudist.obs.spans import routine
from tpudist.utils.config import env_flag

_NEG_BIG = -1e30

# benchmarking/debug escape to measure the unpaired narrow-head path
# (normally strictly slower).  Accepted values: 1/true/yes/on disable
# pairing; unset/empty/0/false/no/off keep it (env_flag — the raw
# bool(getenv) this replaced treated "=0" as disable).  Read ONCE at
# import: jit caches are not keyed on env vars, so a mid-process flip
# would silently re-time the cached paired executable.
_DISABLE_PAIRING = env_flag("TPUDIST_DISABLE_HEAD_PAIRING")
# where in a paired row's [1, 2, gp, d] query and output blocks each
# member's tile sits
_PAIR_SLOTS = ((0, 0), (0, 1))


def _softmax_update(m_scr, l_scr, acc_scr, s, pv_scale, vb, also=None):
    """One online-softmax rank update of the f32 ``m`` / ``l`` / ``acc``
    scratch from masked scores ``s`` and the value tile ``vb``
    (``pv_scale`` folds per-token V scales into the probability rows;
    None for the bf16 path).  ``also``: a second ``(scores, values)`` pair
    that joins the SAME update (one max, one rescale of ``acc``).  Shared
    by every decode kernel body."""
    m = m_scr[:]
    top = jnp.max(s, axis=-1, keepdims=True)
    if also is not None:
        top = jnp.maximum(top, jnp.max(also[0], axis=-1, keepdims=True))
    new_m = jnp.maximum(m, jnp.maximum(top, _NEG_BIG))
    p = jnp.exp(s - new_m)
    corr = jnp.exp(m - new_m)
    m_scr[:] = new_m
    total = jnp.sum(p, axis=-1, keepdims=True)
    if also is not None:
        p2 = jnp.exp(also[0] - new_m)
        total = total + jnp.sum(p2, axis=-1, keepdims=True)
    l_scr[:] = l_scr[:] * corr + total
    if pv_scale is not None:
        vs = pv_scale                            # [rows, bk]
        if vs.shape[0] == 2:
            # half m's output lands in member m's lane half (sliced
            # out at unpack), so folding member m's V scale into
            # half-m probability rows is exact
            half = p.shape[0] // 2
            pv32 = (p.reshape(2, half, p.shape[1])
                    * vs[:, None, :]).reshape(p.shape)
        else:
            pv32 = p * vs
        pv = pv32.astype(jnp.bfloat16)
    else:
        pv = p.astype(vb.dtype)
    out = jax.lax.dot_general(
        pv, vb, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)
    if also is not None:
        out = out + jax.lax.dot_general(
            p2.astype(also[1].dtype), also[1], (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
    acc_scr[:] = acc_scr[:] * corr + out


def _softmax_init(m_scr, l_scr, acc_scr, q_ref, q_scr, slots):
    """Reset the online-softmax scratch for a new grid row and, where
    several heads share the row's update (``slots`` not None: the
    head-paired layout, a paged lane's folded K/V heads), build their
    block-diagonal query tile in ``q_scr`` from the heads' ``[gp, d]``
    queries, at ``slots`` of the row's query block."""
    m_scr[:] = jnp.full_like(m_scr, _NEG_BIG)
    l_scr[:] = jnp.zeros_like(l_scr)
    acc_scr[:] = jnp.zeros_like(acc_scr)
    if slots is not None:
        # block-diagonal [n·gp, n·d] from the n [gp, d] heads: rows
        # [j·gp, (j+1)·gp) carry head j's queries in lanes [j·d, (j+1)·d)
        # — the zeros annihilate the other heads in the single n·d
        # contraction.  Built ONCE per grid row into scratch: the
        # lane-offset concatenates are not free under Mosaic, and
        # rebuilding them every K step measured ~2x on the whole
        # kernel at B=8
        heads = [q_ref[at] for at in slots]
        z = jnp.zeros_like(heads[0])
        q_scr[:] = jnp.concatenate(
            [jnp.concatenate([q if i == j else z
                              for i in range(len(heads))], axis=1)
             for j, q in enumerate(heads)], axis=0)


def _softmax_finalize(l_scr, acc_scr, o_ref, slots=None):
    """Write ``acc / l`` to the grid row's output block; returns the
    clamped ``l`` (the log-sum-exp needs it).  ``slots`` (a block-diagonal
    row): where in ``o_ref`` each head's output goes."""
    l = jnp.maximum(l_scr[:], 1e-30)
    o = (acc_scr[:] / l).astype(o_ref.dtype)
    if slots is None:
        o_ref[0] = o
        return l
    # UNPACK in kernel: head j's output lives in rows [j·gp, (j+1)·gp) ×
    # lanes [j·d, (j+1)·d) of the block-diagonal result — write each
    # head's tile to its own [gp, d] output slot, so XLA sees the natural
    # layout and pays no per-token lane slicing/stacking
    gp, d = o.shape[0] // len(slots), o.shape[1] // len(slots)
    for j, at in enumerate(slots):
        o_ref[at] = o[j * gp:(j + 1) * gp, j * d:(j + 1) * d]
    return l


def _side_update(m_scr, l_scr, acc_scr, q, side_k, load_side_v, side_len,
                 scale: float):
    """The side buffer's rank update: its first ``side_len`` positions
    (key tile ``side_k``; ``load_side_v(side_k)`` gives the value tile once
    the scores are made) join the same online softmax as the main cache."""
    s = jax.lax.dot_general(
        q, side_k, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32) * scale
    pos = jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    s = jnp.where(pos < side_len, s, -jnp.inf)
    _softmax_update(m_scr, l_scr, acc_scr, s, None, load_side_v(side_k))


def _decode_kernel(meta_ref, q_ref, k_ref, *rest, scale: float,
                   block_k: int, num_kb: int, window: int | None,
                   with_lse: bool, quant: bool,
                   rows_per_batch: int | None = None,
                   paired_q: bool = False,
                   side: bool = False):
    """Online-softmax decode over one (batch·kv-head) row of the cache.

    ``meta_ref`` is the scalar-prefetch vector ``[cache_len, offset,
    start_block]`` — or, with ``rows_per_batch`` set (per-row lengths),
    ``[side_len, offset, start_block, len_0, ..., len_{B-1}]``:
    ``offset`` is this shard's global cache start (sequence-parallel
    decode; 0 for the whole-cache case), and ``start_block`` trims the K
    grid to the sliding window — with ``window`` the grid runs only the
    ~``window/block_k`` blocks that intersect it, so a windowed decode
    STREAMS ~``window`` positions instead of the whole cache (bandwidth
    is the decode bound).

    ``paired_q``: the head-paired layout's block-diagonal query tile is
    built IN VMEM from the two natural [gp, d] halves (a couple of
    concatenates against a zero tile) instead of being scattered into an
    HBM array by XLA every decode step — the per-step packing cost the
    round-4 verdict measured as the d=64 model-level residual.

    ``side``: one extra trailing grid step attends over a small side
    buffer (the continuous-batching segment-local K/V staging) with
    ``meta[0]`` live positions — folding the serve loop's side attention
    and its log-sum-exp merge into this kernel's own online softmax.

    ``quant``: K/V tiles are int8 with per-token scales riding the LANE
    axis ([1, bk] blocks — a [bk, 1] layout would pad every scale to a
    128-lane row and stride the DMA; measured 2× slower).  Scales fold in
    AFTER the matmuls (Σ_d q_d·(k_jd·s_j) = s_j·(q·k_j)), so dequant
    costs [gp, bk] multiplies, not a [bk, D] tile rescale."""
    if quant:
        ks_ref, v_ref, vs_ref = rest[:3]
        rest = rest[3:]
    else:
        v_ref = rest[0]
        rest = rest[1:]
    if side:
        sk_ref, sv_ref = rest[:2]
        rest = rest[2:]
    if paired_q:
        q_scr = rest[-1]
        rest = rest[:-1]
    if with_lse:
        o_ref, lse_ref, m_scr, l_scr, acc_scr = rest
    else:
        o_ref, m_scr, l_scr, acc_scr = rest
    slots = _PAIR_SLOTS if paired_q else None
    kj = pl.program_id(1)
    if rows_per_batch is None:
        cache_len = meta_ref[0]
    else:
        # per-row lengths (the continuous-batching serve loop: every
        # cache row decodes at its own position): meta carries
        # [side_len, off, start, len_0..len_{B-1}] and grid row g
        # belongs to batch row g // rows_per_batch
        cache_len = meta_ref[3 + pl.program_id(0) // rows_per_batch]
    offset = meta_ref[1]
    kb_idx = meta_ref[2] + kj  # grid step kj streams cache block kb_idx

    @pl.when(kj == 0)
    def _init():
        _softmax_init(m_scr, l_scr, acc_scr, q_ref,
                      q_scr if paired_q else None, slots)

    def q_tile():
        return q_scr[:] if paired_q else q_ref[0]    # [gp, D]

    def _accum(s, pv_scale, vb):
        _softmax_update(m_scr, l_scr, acc_scr, s, pv_scale, vb)

    @pl.when(offset + kb_idx * block_k < cache_len)
    def _compute():
        q = q_tile()
        if quant:
            kb = k_ref[0].astype(jnp.bfloat16)       # int8 fits exactly
            s = jax.lax.dot_general(
                q.astype(jnp.bfloat16), kb, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32)
            ks = ks_ref[0] * scale                   # [rows, bk]
            if ks.shape[0] == 2:
                # paired tile: score rows of half m carry pair member
                # m's K only (block-diagonal q), so member m's per-token
                # scale applies to exactly those rows
                half = s.shape[0] // 2
                s = (s.reshape(2, half, s.shape[1])
                     * ks[:, None, :]).reshape(s.shape)
            else:
                s = s * ks                           # [gp, bk]·[1, bk]
        else:
            s = jax.lax.dot_general(
                q, k_ref[0], (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32) * scale
        k_pos = offset + kb_idx * block_k + jax.lax.broadcasted_iota(
            jnp.int32, s.shape, 1)                   # GLOBAL positions
        keep = k_pos < cache_len
        if window is not None:
            keep = jnp.logical_and(keep, k_pos >= cache_len - window)
        s = jnp.where(keep, s, -jnp.inf)
        _accum(s, vs_ref[0] if quant else None, (
            v_ref[0].astype(jnp.bfloat16) if quant else v_ref[0]))

    if side:
        # the side buffer rides the LAST main grid step (an extra
        # sequential step measured +17 µs — pipeline bubbles at the
        # boundary of every grid row; folded here it is one more rank
        # update on tiles that are already resident)
        @pl.when(kj == num_kb - 1)
        def _side():
            _side_update(m_scr, l_scr, acc_scr, q_tile(), sk_ref[0],
                         lambda _: sv_ref[0], meta_ref[0], scale)

    @pl.when(kj == num_kb - 1)
    def _finalize():
        l = _softmax_finalize(l_scr, acc_scr, o_ref, slots)
        if with_lse:
            # log-sum-exp of this shard's scores: the merge key for
            # sequence-parallel decode (out = Σ out_i·exp(lse_i − LSE))
            lse_ref[0, 0] = (m_scr[:] + jnp.log(l))[:, 0]


def _paged_decode_kernel(meta_ref, q_ref, *rest, scale: float,
                         block: int, pages_per_tile: int, m_blocks: int,
                         lanes: int, groups: int, slots: tuple | None,
                         side: bool, d_v: int | None = None,
                         window: int | None = None, joined: bool = False):
    """Online-softmax decode over ONE grid row of a paged cache: a LANE
    and all its K/V heads, walking the lane's live pages only.

    The K/V heads of a lane walk the same pages, so they share a row: it
    fetches each live page ONCE, whole (``[block, h_kv * d]``, contiguous
    in the pool), and one rank update a tile serves every head, through
    the block-diagonal query the head-paired layout already builds
    (``slots``: where in the row's ``[heads, .., gp, d]`` query and output
    blocks each head sits; its queries meet zeros in the other heads'
    columns, so within a head the sums are what a row of its own gives).
    ``slots`` None: one head, the query block as it is.  A lane whose
    heads' tile slots do not fit VMEM takes ``groups`` rows, each a group
    of heads and this same program (:func:`paged_grid_rows`).  VMEM at the
    widest cell (4 K/V heads of 128 in bf16, tiles of 8 pages of 128
    rows): 2 slots x 1 MiB a pool = 4 MiB for both pools, beside the
    masked copy of each pool's last tile (1 MiB each), f32 scores of
    ``[32, 1024]`` and the ``[32, 512]`` query, ``m`` / ``l`` / ``acc``.

    ``meta_ref`` is the scalar-prefetch vector ``[side_len, len_0 ..
    len_{B-1}, table[0, 0] .. table[B-1, M-1]]``.  The pools stay in HBM;
    the body fetches them itself, a TILE of ``pages_per_tile`` pages at a
    time, into two VMEM slots: the next tile's copies start before the
    current tile is computed, and the first tile of the next grid row that
    holds a page starts during the last tile of this one (``state_ref``
    carries its slot across grid rows), so a row's DMA latency hides behind
    its neighbour's work.  A lane needs ``ceil(len / block)`` pages: no
    copy is started, waited for or stepped over beyond that.

    The arithmetic follows the copies.  What a lane pays for, on a v5e, is
    each RANK UPDATE (about 0.6 us of dependent latency: scores, max, exp,
    sum, P.V, the ``acc`` rescale, whatever the width) and each PAGE it
    computes (its two products at the MXU's rate); masks and selects ride
    free beside them.  So a tile before the lane's last is full and takes
    one unmasked update; the LAST tile takes one update at the width its
    live pages need (in steps of :func:`_width_step` pages), masked at the
    length, and the side buffer's rows join that same update instead of
    one of their own; a lane of length 0 costs the side buffer's update
    and the output write alone.  Per live row it is ``_decode_kernel``'s
    arithmetic (f32 scores scaled in f32, f32 ``m`` / ``l`` / ``acc``,
    operands in their own dtype into the MXU).

    No row that was not copied is computed, and in the masked update every
    value row at or beyond the length is set to 0 before the MXU sees it
    (a weight of 0 on a row that is not finite would be NaN), so the tile
    slots need no zero fill: what a slot's uncopied pages hold never
    reaches a product.

    The walk serves three layouts.  ``d_v`` None: TWO pools (keys, values)
    of one width, each with its side buffer (``paged_flash_decode``).
    ``d_v`` set: ONE pool whose rows are the keys and whose first ``d_v``
    columns are also the values, read once (``paged_mla_decode``: the
    latent cache of multi-head latent attention).  ``joined``: ONE pool
    whose rows are :func:`kv_row`'s (an indexer's layer:
    ``sparse_gqa_attend`` and its every-row branch), one copy a page and
    tile slots of the bytes the two pools' held.  Where the pool is
    ``uint32`` a WORD holds a key in its low half and the value of the
    same place in its high half: the copies and the side buffer are the
    one-pool walk's, and the key tile and the value tile are made from the
    word tile on the vector unit (:func:`_word_half`: bits, nothing
    rounded).  Else a row holds its keys in its first half and its values
    in its second: the key and value tiles are the two static halves of a
    slot (a multiple of 128 lanes apart on the chip), and the side buffer
    comes in as its two halves.

    ``window`` (static; None is the program above, instruction for
    instruction): a SLIDING-WINDOW layer.  The query sits at position
    ``len + side_len - 1`` and sees the cache rows from ``lo = len +
    side_len - window`` on, so the walk STARTS at the page that holds
    ``lo`` (``first_page``): tiles are counted from there, pages below it
    are neither copied nor computed (the host may have released them: a
    page id below the window is never read), the row's first tile masks
    its rows under ``lo`` (copied, finite: their scores are -inf and
    their values need no cleaning), and the first-tile prefetch of this
    row and of the next live row aim there.  A lane shorter than the
    window starts at page 0 and walks as without one; the side buffer's
    rows (never more than the window) join the last tile's update as
    before.  :func:`walk_rows` counts the same."""
    if window is not None and (d_v is not None or joined):
        raise ValueError("the one-pool walks have no window")
    n_pools = 2 if d_v is None and not joined else 1
    pools, rest = rest[:n_pools], rest[n_pools:]
    # the joined pool's rows: words of a key and a value each, or the keys
    # beside the values
    words = joined and pools[0].dtype == jnp.uint32
    halves = joined and not words
    n_sides = 2 if halves else n_pools
    if side:
        sides, rest = rest[:n_sides], rest[n_sides:]
    o_ref, rest = rest[0], rest[1:]
    bufs, rest = rest[:n_pools], rest[n_pools:]
    sems, state_ref, m_scr, l_scr, acc_scr = rest[:5]
    q_scr = rest[5] if slots is not None else None
    g = pl.program_id(0)
    lane, r = g // groups, g % groups
    d = bufs[0].shape[-1] // (2 if halves else 1)
    # (tile buffer, its columns, its words' half) of the key tile and of
    # the value tile
    if words:
        key_at, value_at = ((bufs[0], slice(None), h) for h in range(2))
    elif halves:
        key_at, value_at = ((bufs[0], pl.ds(h * d, d), None)
                            for h in range(2))
    else:
        key_at, value_at = ((buf, slice(None), None)
                            for buf in (bufs[0], bufs[-1]))

    def numbers(x, half):
        """``x`` as the MXU's operand: itself, or one half of its words."""
        return x if half is None else _word_half(x, half, q_ref.dtype)

    def values(keys, load_values):
        """The value tile beside the key tile ``keys``: the second pool's
        (loaded only now, after the scores), or the keys' own first
        ``d_v`` columns."""
        return load_values() if d_v is None else keys[:, :d_v]

    def lane_len(i):
        return meta_ref[1 + i]

    def first_row(i):
        """The first cache row lane ``i``'s query sees (window only)."""
        return jnp.maximum(lane_len(i) + meta_ref[0] - window, 0)

    def first_page(i):
        return 0 if window is None else first_row(i) // block

    def lane_pages(i):
        """Pages lane ``i``'s walk covers: those under its length, from
        the window's first on."""
        return (lane_len(i) + block - 1) // block - first_page(i)

    def tile_copies(i, r_, t, slot, fn):
        """Apply ``fn`` (start or wait) to every pool's copy of every LIVE
        page of lane ``i``'s tile ``t`` — a loop and not an unrolled
        ladder of predicates: the body is traced and lowered once a call
        site, and a segment program holds one call a layer."""
        live = jnp.minimum(lane_pages(i) - t * pages_per_tile,
                           pages_per_tile)
        base = (1 + lanes + i * m_blocks + first_page(i)
                + t * pages_per_tile)
        # the grid row's heads in the packed minor dim (all of it where
        # a lane is one row: the page whole, one contiguous copy)
        chunk = (slice(None) if groups == 1
                 else pl.ds(pl.multiple_of(r_ * d, d), d))

        def one_page(p, _):
            page = meta_ref[base + p]
            if halves and groups > 1:
                # the row's heads of the keys' half and of the values'
                half = pools[0].shape[-1] // 2
                for kv in range(2):
                    fn(pltpu.make_async_copy(
                        pools[0].at[page, :, pl.ds(pl.multiple_of(
                            kv * half + r_ * d, d), d)],
                        bufs[0].at[slot, p, :, pl.ds(kv * d, d)],
                        sems.at[0, slot]))
                return
            for kv, (hbm, buf) in enumerate(zip(pools, bufs)):
                fn(pltpu.make_async_copy(
                    hbm.at[page, :, chunk], buf.at[slot, p],
                    sems.at[kv, slot]))

        jax.lax.fori_loop(0, live, one_page, None)

    def start(i, r_, t, slot):
        tile_copies(i, r_, t, slot, lambda c: c.start())

    def wait(i, r_, t, slot):
        tile_copies(i, r_, t, slot, lambda c: c.wait())

    def next_live_row():
        """The next grid row that holds a page: this lane's next group of
        heads (a lane of several rows), else the first row of the next
        lane whose length is not 0 (``lanes`` if there is none).  The scan
        stops at the first such lane: it runs on the scalar unit with
        nothing beside it, and a scan of every lane left cost a row of 128
        lanes half a microsecond."""
        nxt = jax.lax.while_loop(
            lambda i: jnp.logical_and(
                i < lanes, lane_len(jnp.minimum(i, lanes - 1)) == 0),
            lambda i: i + 1, lane + 1)
        if groups == 1:
            return nxt, 0
        more = r + 1 < groups
        return jnp.where(more, lane, nxt), jnp.where(more, r + 1, 0)

    @pl.when(g == 0)
    def _first_row():
        # state = [slot of the pending first tile, 1 if it is in flight]
        state_ref[0] = 0
        state_ref[1] = 0

    _softmax_init(m_scr, l_scr, acc_scr, q_ref, q_scr, slots)

    def q_tile():
        return q_ref[0] if slots is None else q_scr[:]

    n_pages = lane_pages(lane)
    n_tiles = (n_pages + pages_per_tile - 1) // pages_per_tile
    # rows of the walk, counted from its first page: the lane's length
    # there, and (window) the first row the query sees
    walk_row0 = first_page(lane) * block
    cache_len = lane_len(lane) - walk_row0
    first_live = None if window is None else first_row(lane) - walk_row0

    def scores(keys, live, below=None):
        """f32 scores of ``keys``' rows, -inf from row ``live`` on and
        (a window's first tile) under row ``below``."""
        s = jax.lax.dot_general(
            q_tile(), keys, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale
        if live is None and below is None:
            return s
        col = jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        keep = None if live is None else col < live
        if below is not None:
            keep = col >= below if keep is None else keep & (col >= below)
        return jnp.where(keep, s, -jnp.inf)

    def side_tiles():
        """The side buffer's ``(scores, values)``: its first ``side_len``
        positions join the same online softmax as the main cache."""
        keys = numbers(sides[0][0], key_at[2])
        return scores(keys, meta_ref[0]), values(
            keys, lambda: numbers(sides[-1][0], value_at[2]))

    def attend(slot, n, live=None, below=None):
        """One rank update over the first ``n`` pages of ``slot``.  ``live``
        None: a tile before the last, every row under the length.  Else
        the lane's last tile, ``live`` of its rows under the length: the
        scores of the others are -inf, their values 0, and the side
        buffer's rows join the update.  ``below`` (window): the tile's
        rows under it lie before the window."""
        rows = n * block

        def load(at, cleaned):
            buf, cols, half = at
            x = buf[slot, :n, :, cols].reshape(rows, d)
            if live is not None and cleaned:
                row = jax.lax.broadcasted_iota(jnp.int32, x.shape, 0)
                x = jnp.where(row < live, x, jnp.zeros_like(x))
            return numbers(x, half)

        # the latent pool: its rows are the values too, so the keys are
        # cleaned
        keys = load(key_at, d_v is not None)
        _softmax_update(
            m_scr, l_scr, acc_scr, scores(keys, live, below), None,
            values(keys, lambda: load(value_at, True)),
            also=side_tiles() if side and live is not None else None)

    @pl.when(n_tiles > 0)
    def _walk():
        slot0 = state_ref[0]

        @pl.when(state_ref[1] == 0)
        def _exposed():
            start(lane, r, 0, slot0)

        state_ref[1] = 0

        def one_tile(t, _):
            slot = (slot0 + t) % 2

            @pl.when(t + 1 < n_tiles)
            def _next_tile():
                start(lane, r, t + 1, 1 - slot)

            @pl.when(t + 1 == n_tiles)
            def _next_row():
                lane_n, r_n = next_live_row()

                @pl.when(lane_n < lanes)
                def _():
                    start(lane_n, r_n, 0, 1 - slot)
                    state_ref[0] = 1 - slot
                    state_ref[1] = 1

            wait(lane, r, t, slot)
            # a window's lower edge lies in the walk's first tile: at or
            # under 0 in every later one
            below = (None if window is None
                     else first_live - t * pages_per_tile * block)

            @pl.when(t + 1 < n_tiles)
            def _full_tile():
                attend(slot, pages_per_tile, below=below)

            @pl.when(t + 1 == n_tiles)
            def _last_tile():
                # one body a width: the widths are static shapes
                held = n_pages - t * pages_per_tile
                step = _width_step(pages_per_tile)
                for lo in range(0, pages_per_tile, step):
                    n = min(lo + step, pages_per_tile)

                    @pl.when(jnp.logical_and(held > lo, held <= n))
                    def _at_width(n=n):
                        attend(slot, n,
                               cache_len - t * pages_per_tile * block,
                               below)

        jax.lax.fori_loop(0, n_tiles, one_tile, None)

    if side:
        @pl.when(n_tiles == 0)
        def _side_alone():
            s, vals = side_tiles()
            _softmax_update(m_scr, l_scr, acc_scr, s, None, vals)

    _softmax_finalize(l_scr, acc_scr, o_ref, slots)


def _word_half(words, half: int, dtype):
    """The low (``half`` 0) or high (1) halves of ``uint32`` words as numbers
    of the 16-bit ``dtype`` (in the walk: of a tile, on the vector unit):
    the half moved down, the word cut to 16 bits, the bits taken as they
    are."""
    low = words if half == 0 else words >> 16
    return jax.lax.bitcast_convert_type(low.astype(jnp.uint16), dtype)


def _width_step(pages_per_tile: int) -> int:
    """Pages between two widths the last tile of a paged walk can be
    computed at: an eighth of a tile, rounded up (one page at the serving
    cells' 8 pages a tile).  Each width is a body of its own, lowered once
    a program."""
    return -(-pages_per_tile // 8)


def paged_tile_pages(block: int, m_blocks: int) -> int:
    """Pages a tile of the paged walk holds: about 1024 tokens, big enough
    that a rank update's fixed cost and a copy's latency are small beside
    it, small enough that two slots of every pool stay a small part of
    VMEM; never more than a lane's table row."""
    return max(1, min(m_blocks, 1024 // block))


# VMEM the tile slots of one paged call may take (every pool, both slots):
# a quarter of the 16 MiB a v5e kernel gets by default; the walk's masked
# copy of a last tile and its f32 scores come on top.  4 K/V heads of 128
# in bf16 sit on it (8 pages a tile: 2 pools x 2 slots x 1 MiB); the widest
# cell has 8 and takes TWO rows of four a lane.  One row of eight (8 MiB)
# compiles too and read the same on the chip: 1780 / 1781 us the full walk,
# 1123 / 1123 the window's, 48 lanes (PR 48), so the budget stands
_TILE_SLOT_BYTES = 4 << 20


def _pairs(h_kv: int, d: int) -> bool:
    """Whether adjacent K/V heads share a chunk of the packed minor dim
    (the head-paired layout of narrow heads)."""
    return h_kv % 2 == 0 and d * 2 <= 128 and not _DISABLE_PAIRING


def paged_grid_rows(lanes: int, h_kv: int, d: int, block: int,
                    m_blocks: int, *, pools: int = 2,
                    itemsize: int = 2) -> int:
    """Grid rows of ONE paged decode call over ``lanes`` lanes of ``h_kv``
    K/V heads of ``d`` (the latent pool: one head of the row's width,
    ``pools=1``), pages of ``block`` rows, ``m_blocks`` table entries a
    lane, pools of ``itemsize`` bytes an element.

    A grid row is a lane: the lane's K/V-head chunks (a head; a pair of
    narrow heads) share its page copies and its rank updates.  Chunks a
    row = the largest divisor of the lane's chunks whose tile slots (every
    pool, two slots, :func:`paged_tile_pages` pages) fit
    ``_TILE_SLOT_BYTES``; a lane whose chunks do not all fit takes several
    rows, each a group of them, and a chunk that is not a whole number of
    128-lane tiles keeps a row of its own (its slice of a fetched page
    would not be lane-aligned).  Follows from shapes alone.  Both the
    call's ``grid=`` and the host's count (``serve/decode_grid_rows``)."""
    chunks, width = (h_kv // 2, 2 * d) if _pairs(h_kv, d) else (h_kv, d)
    slot = (pools * 2 * paged_tile_pages(block, m_blocks) * block * width
            * itemsize)
    fold = 1
    if width % 128 == 0:
        fold = max((n for n in range(1, chunks + 1)
                    if chunks % n == 0 and n * slot <= _TILE_SLOT_BYTES),
                   default=1)
    return lanes * (chunks // fold)


def walk_rows(length: int, block: int, pages_per_tile: int,
              window: int | None = None, side_len: int = 1) -> int:
    """The cache rows :func:`_paged_decode_kernel`'s arithmetic covers for
    ONE lane of ``length`` under pages of ``block`` rows, ``pages_per_tile``
    (:func:`paged_tile_pages`) a tile: the tiles before the last whole, the
    last at the width its live pages need.  With a ``window`` the walk
    starts at the page that holds row ``length + side_len - window``
    (``side_len`` counts the step's own token: 1 at a segment's first
    step).  The host's count of what a call computes
    (``serve/decode_rows_computed``, ``serve/decode_rows_window_computed``),
    held to the kernel by ``tests/test_paged_decode_walk.py`` and
    ``tests/test_window_decode.py``."""
    pages = -(-length // block)
    if window is not None:
        pages -= max(length + side_len - window, 0) // block
    if not pages:
        return 0
    before = (pages - 1) // pages_per_tile * pages_per_tile
    step = _width_step(pages_per_tile)
    width = min(-(-(pages - before) // step) * step, pages_per_tile)
    return (before + width) * block


def _pick_block_k(s: int, block_k: int) -> int:
    """Largest usable K block: the cap if it divides S, else the largest
    multiple-of-8 divisor (VMEM-safe for arbitrary S), with a one-block
    fast path for small caches whose best divisor is tiny."""
    block_k = min(block_k, s)
    if block_k < s:
        # a PARTIAL block must sit on the 8-row sublane tile (a whole-
        # array block is exempt): a caller-chosen block_k like 12 would
        # otherwise reach Mosaic as an unlowerable block spec
        block_k = max(8, block_k - block_k % 8)
    if s % block_k == 0:
        return block_k
    bk = block_k - block_k % 8
    while bk >= 8 and s % bk:
        bk -= 8
    if bk >= 128 or (bk >= 8 and s > 4096):
        return bk
    if s <= 4096:
        # small cache whose best divisor is tiny (e.g. S = 8·prime):
        # one whole-cache block beats hundreds of sequential 8-row
        # grid steps, and [S, D] tiles at S <= 4096 fit VMEM
        return s
    raise ValueError(
        f"cache length {s} has no block divisor that is a multiple "
        f"of 8 up to {min(block_k, s)}; allocate the cache at a "
        f"multiple of 8 (e.g. {-(-s // 8) * 8})")


def _one_query(s_q: int) -> None:
    """The decode kernels take ONE query token a row a call."""
    if s_q != 1:
        raise ValueError(
            f"a decode kernel takes one query token a call (q [B, 1, H, "
            f"D]), got {s_q}: a chunk of queries goes through the prefill "
            "kernel")


def flash_decode(
    q: jnp.ndarray,
    k_cache: jnp.ndarray,
    v_cache: jnp.ndarray,
    cache_len: jnp.ndarray | int,
    *,
    window: int | None = None,
    block_k: int = 1024,
    interpret: bool | None = None,
    pos_offset: jnp.ndarray | int = 0,
    return_lse: bool = False,
    side_k: jnp.ndarray | None = None,
    side_v: jnp.ndarray | None = None,
    side_len: jnp.ndarray | int = 0,
    packed_kv_heads: int | None = None,
) -> jnp.ndarray | tuple[jnp.ndarray, jnp.ndarray]:
    """One decode step of attention.

    Args:
      q: ``[B, 1, H, D]`` — the current token's queries.
      k_cache / v_cache: ``[B, S, H_kv, D]`` fixed-size cache buffers
        (GQA: ``H_kv`` may divide ``H``); slots ``>= cache_len`` are
        ignored.  Alternatively PACKED 3-D buffers ``[B, S, H_kv·D]``
        with ``packed_kv_heads=H_kv`` — the layout the serving cache
        stores (minor dim a lane multiple, so XLA never pays a layout
        conversion at this call; head/pair chunks are selected by the
        kernel's block index maps, not host reshapes).  Measured: the
        4-D ``[B, S, 2, 64]`` cache carry sat in an S-minor layout and
        XLA inserted TWO full-cache copies per decode step feeding this
        kernel (~2× step time at 8k).
      cache_len: number of valid cache positions INCLUDING the current
        token (the flax ``cache_index + 1``); may be traced.  With
        ``pos_offset`` it stays GLOBAL: this buffer's slot ``j`` holds
        global position ``pos_offset + j`` (the sequence-parallel shard
        layout); validity and windowing are evaluated globally.  A
        VECTOR ``[B]`` selects per-row lengths (the continuous-batching
        serve path; row ``r`` attends over its own first ``len_r``
        slots).
      window: sliding-window width (attend to the last ``window``
        positions only), matching :func:`tpudist.models.sdpa` semantics.
      return_lse: also return the per-head log-sum-exp ``[B, H]`` — the
        merge key for combining partial attention across cache shards
        (:func:`sp_flash_decode`).
      side_k / side_v: optional ``[B, cap, H_kv, D]`` side buffers (the
        serve loop's segment-local K/V staging); the first ``side_len``
        positions are attended AFTER the main cache in the same online
        softmax — no separate attend, no log-sum-exp merge.  Requires
        per-row ``cache_len`` and ``window=None``.

    Returns ``[B, 1, H, D]`` (plus ``[B, H]`` lse when requested).
    """
    return _flash_decode_impl(
        q, k_cache, None, v_cache, None, cache_len, window=window,
        block_k=block_k, interpret=interpret, pos_offset=pos_offset,
        return_lse=return_lse, side_k=side_k, side_v=side_v,
        side_len=side_len, packed_kv_heads=packed_kv_heads)


def _flash_decode_impl(q, k_cache, k_scale, v_cache, v_scale, cache_len,
                       *, window, block_k, interpret, pos_offset,
                       return_lse, side_k=None, side_v=None, side_len=0,
                       packed_kv_heads=None):
    """Shared wrapper for the bf16 and int8 cache paths (``k_scale`` /
    ``v_scale`` None selects bf16)."""
    quant = k_scale is not None
    side = side_k is not None
    packed = k_cache.ndim == 3
    b, s_q, h, d = q.shape
    _one_query(s_q)
    if packed:
        if packed_kv_heads is None:
            raise ValueError(
                "a 3-D packed cache needs packed_kv_heads=H_kv")
        if quant:
            raise ValueError(
                "packed caches compose with the bf16 path only")
        s, h_kv = k_cache.shape[1], packed_kv_heads
        if k_cache.shape[2] != h_kv * d:
            raise ValueError(
                f"packed cache minor dim {k_cache.shape[2]} != "
                f"H_kv*D = {h_kv * d}")
    else:
        s, h_kv = k_cache.shape[1], k_cache.shape[2]
    if h % h_kv:
        raise ValueError(f"num_heads {h} not a multiple of kv heads {h_kv}")
    g = h // h_kv
    gp = -(-g // 8) * 8  # pad the group to the 8-row sublane tile
    block_k = _pick_block_k(s, block_k)
    num_kb_full = s // block_k
    if interpret is None:
        interpret = jax.default_backend() == "cpu"

    cache_len = jnp.asarray(cache_len, jnp.int32)
    per_row = cache_len.ndim == 1
    if per_row and window is not None:
        raise ValueError(
            "per-row cache lengths compose with window=None only (the "
            "sliding-window grid trim needs one start block per grid)")
    if per_row and cache_len.shape[0] != b:
        raise ValueError(
            f"per-row cache_len has {cache_len.shape[0]} entries for "
            f"batch {b}")
    if side:
        if quant:
            raise ValueError("side buffers compose with the bf16 cache "
                             "path only")
        if not per_row or window is not None:
            raise ValueError(
                "side buffers require per-row cache_len and window=None "
                "(the continuous-batching serve configuration)")
        # pad the side capacity to the 8-row sublane tile; side_len masks
        # the padding rows.  Packed main caches take packed side buffers
        # ([B, cap, Hkv·D]) — same layout contract.
        if side_k.ndim != k_cache.ndim:
            raise ValueError(
                "side buffers must match the cache layout (both packed "
                "3-D or both [B, S, H_kv, D])")
        cap = side_k.shape[1]
        capp = max(8, -(-cap // 8) * 8)
        if capp != cap:
            pad = (((0, 0), (0, capp - cap), (0, 0))
                   if side_k.ndim == 3
                   else ((0, 0), (0, capp - cap), (0, 0), (0, 0)))
            side_k = jnp.pad(side_k, pad)
            side_v = jnp.pad(side_v, pad)
        side_k = side_k.astype(k_cache.dtype)
        side_v = side_v.astype(v_cache.dtype)
    offset = jnp.asarray(pos_offset, jnp.int32)
    if window is None:
        nb = num_kb_full
        start_block = jnp.int32(0)
    else:
        # grid trimming: only blocks intersecting the window's GLOBAL
        # span [cache_len - window, cache_len) are streamed — a windowed
        # decode reads ~window positions, not the whole cache
        nb = min(num_kb_full, -(-window // block_k) + 1)
        start_block = jnp.clip(
            (cache_len - window - offset) // block_k, 0, num_kb_full - nb)
    if per_row:
        meta = jnp.concatenate(
            [jnp.stack([jnp.asarray(side_len, jnp.int32), offset,
                        start_block]), cache_len])
    else:
        meta = jnp.stack([cache_len, offset, start_block])

    # HEAD PAIRING for narrow head_dim: a [block_k, d] K/V tile with
    # d < 128 underfills the 128-lane width and streams at ~half
    # bandwidth (measured: 305 vs 636 GB/s).  When d*2 <= 128 and h_kv
    # is even, ADJACENT KV-head pairs merge into one [*, 2d] tile (a
    # pure reshape of the [B, S, H_kv, D] cache), and the queries go in
    # BLOCK-DIAGONAL: pair rows [q_h0 | 0] and [0 | q_h1] make the
    # single 2d-lane contraction compute each real head's scores
    # exactly (the zero half annihilates the other head), while PV
    # produces each head's output in its own lane half, sliced apart
    # below.  Costs 2x matmul FLOPs on zeros; buys full-width DMA rows
    # at the bandwidth-bound op — measured kernel parity with a d=128
    # layout.  The int8 path pairs too (round-3 verdict #6 — the
    # narrow-head fix and the cache-compression fix now COMPOSE): its
    # per-(token, head) scales ride as [2, block_k] blocks, one row per
    # pair member, and the kernel applies them half-wise — score rows of
    # half m only ever contract member m's K (the zero q half
    # annihilates the other member), and member m's V lands in its own
    # lane half, so folding member m's scale into half-m score/prob rows
    # is exact.
    scale = d ** -0.5
    paired = _pairs(h_kv, d)
    q4 = q.reshape(b, h_kv, g, d)                    # [B, Hkv, g, d]
    q4 = jnp.pad(q4, ((0, 0), (0, 0), (0, gp - g), (0, 0)))
    if paired:
        # the block-diagonal query tile is built INSIDE the kernel
        # (paired_q) from this natural [·, 2, gp, d] layout — building it
        # here cost an HBM zeros + two scatters EVERY decode step, the
        # measured model-level residual of the paired path (round-4
        # verdict #8); in VMEM it is two concatenates against a zero tile
        n_rows, kv_rows, d_eff = 2 * gp, h_kv // 2, 2 * d
        q3 = q4.reshape(b * kv_rows, 2, gp, d)
        if not packed:
            k3 = k_cache.reshape(
                b, s, kv_rows, d_eff).swapaxes(1, 2).reshape(
                b * kv_rows, s, d_eff)
            v3 = v_cache.reshape(
                b, s, kv_rows, d_eff).swapaxes(1, 2).reshape(
                b * kv_rows, s, d_eff)
            if side:
                side_k = side_k.reshape(
                    b, capp, kv_rows, d_eff).swapaxes(1, 2).reshape(
                    b * kv_rows, capp, d_eff)
                side_v = side_v.reshape(
                    b, capp, kv_rows, d_eff).swapaxes(1, 2).reshape(
                    b * kv_rows, capp, d_eff)
        gp, h_kv, d = n_rows, kv_rows, d_eff
    else:
        q3 = q4.reshape(b * h_kv, gp, d)
        if not packed:
            k3 = k_cache.swapaxes(1, 2).reshape(b * h_kv, s, d)
            v3 = v_cache.swapaxes(1, 2).reshape(b * h_kv, s, d)
            if side:
                side_k = side_k.swapaxes(1, 2).reshape(b * h_kv, capp, d)
                side_v = side_v.swapaxes(1, 2).reshape(b * h_kv, capp, d)

    # index maps see the prefetched meta first: grid step j streams cache
    # block meta[2] + j.  In PACKED mode the cache stays [B, S, Hkv·D]
    # and the grid row's head/pair chunk is picked by the index map's
    # third coordinate — no host reshape ever touches the buffer (a
    # host-side head-major relayout of an S-minor carry measured as two
    # full-cache copies per decode step).
    R = h_kv  # post-pairing rows per batch (pairs when paired)
    if packed:
        k3, v3 = k_cache, v_cache
        kv_spec = pl.BlockSpec(
            (1, block_k, d),
            lambda g_, j, m: (g_ // R, m[2] + j, g_ % R))
    else:
        kv_spec = pl.BlockSpec(
            (1, block_k, d), lambda g_, j, m: (g_, m[2] + j, 0))
    # scales as [B·Hkv, rows, S] (rows = 2 pair members when paired, else
    # 1): the sequence dim rides the LANE axis so a block is a dense
    # [rows, block_k] row set, not a strided column (measured 2× on the
    # whole kernel)
    sc_rows = 2 if paired else 1
    sc_spec = pl.BlockSpec((1, sc_rows, block_k),
                           lambda g_, j, m: (g_, 0, m[2] + j))

    def pack_scale(sc):
        # [B, S, Hkv_orig, 1] -> [B·(Hkv_orig/rows), rows, S]
        flat = sc[..., 0].swapaxes(1, 2)          # [B, Hkv_orig, S]
        return flat.reshape(b * h_kv, sc_rows, s)

    if paired:
        q_spec = pl.BlockSpec((1, 2, gp // 2, d // 2),
                              lambda g_, j, m: (g_, 0, 0, 0))
    else:
        q_spec = pl.BlockSpec((1, gp, d), lambda g_, j, m: (g_, 0, 0))
    args = [meta, q3, k3]
    in_specs = [q_spec, kv_spec]
    if quant:
        args.append(pack_scale(k_scale))
        in_specs.append(sc_spec)
    args.append(v3)
    in_specs.append(kv_spec)
    if quant:
        args.append(pack_scale(v_scale))
        in_specs.append(sc_spec)
    if side:
        if packed:
            side_spec = pl.BlockSpec(
                (1, capp, d), lambda g_, j, m: (g_ // R, 0, g_ % R))
        else:
            side_spec = pl.BlockSpec(
                (1, capp, d), lambda g_, j, m: (g_, 0, 0))
        args += [side_k, side_v]
        in_specs += [side_spec, side_spec]

    if paired:
        out_specs = [pl.BlockSpec((1, 2, gp // 2, d // 2),
                                  lambda g_, j, m: (g_, 0, 0, 0))]
        out_shape = [jax.ShapeDtypeStruct(
            (b * h_kv, 2, gp // 2, d // 2), q.dtype)]
    else:
        out_specs = [pl.BlockSpec((1, gp, d), lambda g_, j, m: (g_, 0, 0))]
        out_shape = [jax.ShapeDtypeStruct((b * h_kv, gp, d), q.dtype)]
    if return_lse:
        out_specs.append(
            pl.BlockSpec((1, 1, gp), lambda g_, j, m: (g_, 0, 0)))
        out_shape.append(
            jax.ShapeDtypeStruct((b * h_kv, 1, gp), jnp.float32))
    outs = pl.pallas_call(
        functools.partial(
            _decode_kernel, scale=scale, block_k=block_k,
            num_kb=nb, window=window, with_lse=return_lse,
            quant=quant,
            # h_kv here is POST-pairing: grid row g -> batch g // h_kv
            rows_per_batch=h_kv if per_row else None,
            paired_q=paired, side=side),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(b * h_kv, nb),
            in_specs=in_specs,
            out_specs=out_specs if return_lse else out_specs[0],
            scratch_shapes=[
                pltpu.VMEM((gp, 1), jnp.float32),
                pltpu.VMEM((gp, 1), jnp.float32),
                pltpu.VMEM((gp, d), jnp.float32),
            ] + ([pltpu.VMEM((gp, d), q.dtype)] if paired else []),
        ),
        out_shape=out_shape if return_lse else out_shape[0],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
        name="flash_decode",
    )(*args)
    def unpack_out(out):
        if paired:
            # the kernel already wrote each pair member's [gp, d'] tile
            # to its own output slot — unpacking is a pure reshape +
            # row slice, no lane-half gathers
            d0 = d // 2
            o = out.reshape(b, h_kv * 2, gp // 2, d0)
            return o[:, :, :g].reshape(b, 1, h, d0)
        return out.reshape(b, h_kv, gp, d)[:, :, :g].reshape(b, 1, h, d)

    def unpack_lse(lse):
        if paired:
            return lse.reshape(b, h_kv, 2, gp // 2)[
                :, :, :, :g].reshape(b, h)
        return lse.reshape(b, h_kv, gp)[:, :, :g].reshape(b, h)

    if not return_lse:
        return unpack_out(outs)
    out, lse = outs
    return unpack_out(out), unpack_lse(lse)


def paged_gather_kv(pool: jnp.ndarray, page_table: jnp.ndarray
                    ) -> jnp.ndarray:
    """Gather a paged pool into a contiguous per-slot view: ``[N, bs, F]``
    pool + ``[B, M]`` page table -> ``[B, M*bs, F]`` (slot ``b``'s logical
    position ``p`` lands at row ``p``).  The DENSE-fallback path for
    CPU/test runs and the reference the paged kernel is checked against —
    on TPU it materializes the whole logical cache every step, which is
    exactly the copy :func:`paged_flash_decode` exists to avoid."""
    b, m = page_table.shape
    _, bs, flat = pool.shape
    return pool[page_table.reshape(-1)].reshape(b, m * bs, flat)


def paged_flash_decode(
    q: jnp.ndarray,
    k_pool: jnp.ndarray,
    v_pool: jnp.ndarray | None,
    page_table: jnp.ndarray,
    cache_len: jnp.ndarray,
    *,
    packed_kv_heads: int,
    side_k: jnp.ndarray | None = None,
    side_v: jnp.ndarray | None = None,
    side_len: jnp.ndarray | int = 0,
    interpret: bool | None = None,
    window: int | None = None,
) -> jnp.ndarray:
    """One decode step of attention against a PAGED KV cache.

    The continuous-batching capacity lever (PagedAttention): instead of a
    dense ``[B, S, Hkv*D]`` buffer per slot, K/V live in ONE shared block
    pool ``[num_blocks, block_size, Hkv*D]`` and each slot maps its
    logical positions through a page table — slot ``b``'s position ``p``
    is ``pool[page_table[b, p // block_size], p % block_size]``.  HBM
    then scales with tokens actually allocated, not
    ``num_slots x max_seq_len``.

    The kernel keeps :func:`flash_decode`'s arithmetic (the shared
    online-softmax update, block-diagonal head pairing included) and has
    its own body, :func:`_paged_decode_kernel`: the pools stay in HBM and
    the body copies in, double-buffered, tiles of about 1024 tokens of the
    pages a lane really HOLDS — ``ceil(cache_len[b] / block_size)`` of
    them, by the ids in the scalar-prefetched page table.  Its cost
    follows the live pages and not the table's width: a lane of length 0
    costs one grid row's fixed overhead, and dead page-table entries are
    never read (they may hold anything).  The kernel this replaced put
    one page-table entry on a grid step, all of them for every lane:
    the gather cost no BYTES on top of the dense kernel's DMA, but every
    dead entry cost a grid step's time (about 0.16 us on a v5e, 1536 of
    them a call at 24 lanes x 64 entries).

    Args:
      q: ``[B, 1, H, D]`` current-token queries.
      k_pool / v_pool: ``[num_blocks, block_size, Hkv*D]`` packed block
        pools (``block_size`` a multiple of 8 — the sublane tile).
        ``v_pool`` None: ``k_pool`` is ONE pool whose rows hold a token's
        K and V as :func:`pack_kv` lays them (:func:`kv_row` of ``Hkv*D``
        numbers in ``q``'s dtype; what an indexer's layer keeps);
        ``side_k`` is then the one side buffer of the same rows and
        ``side_v`` None.
      page_table: ``[B, max_blocks_per_slot]`` int32 pool indices; only
        a row's first ``ceil(cache_len / block_size)`` entries are read.
      cache_len: ``[B]`` per-row valid lengths INCLUDING the current
        token (the serve loop's vector ``cache_index`` + side occupancy
        semantics are the caller's business, as with ``flash_decode``).
      packed_kv_heads: ``H_kv`` of the packed minor dim.
      side_k / side_v / side_len: the serve loop's segment-local staging
        buffers (``[B, cap, Hkv*D]`` packed), attended after the paged
        cache in the same online softmax — as on :func:`flash_decode`.
      window: a sliding-window layer's width (static).  The query is the
        row ``cache_len + side_len - 1`` (the step's token is the side
        buffer's last live row) and sees ``window`` rows, itself
        included; the walk starts at the page that holds the first of
        them, and page-table entries below it are never read.  Needs the
        side buffers and one query a call.  In a trace the kernel is
        ``paged_window_decode``.

    Returns ``[B, 1, H, D]``.
    """
    b, s_q, h, d = q.shape
    _one_query(s_q)
    if window is not None and (side_k is None or window < 1):
        raise ValueError(
            "a windowed paged decode takes the side buffers (the query's "
            "position is cache_len + side_len - 1) and a window >= 1")
    if v_pool is None and (window is not None or side_v is not None):
        raise ValueError(
            "the one pool of K and V takes its one side buffer and no "
            "window")
    if k_pool.ndim != 3:
        raise ValueError(
            f"paged pools are packed 3-D [N, block, Hkv*D]; got "
            f"{k_pool.shape}")
    _, block, flat = k_pool.shape
    h_kv = packed_kv_heads
    row = (h_kv * d, k_pool.dtype) if v_pool is not None else kv_row(
        h_kv * d, q.dtype)
    if (flat, k_pool.dtype) != row:
        raise ValueError(
            f"pool minor dim {flat} of {k_pool.dtype} != H_kv*D = "
            f"{h_kv * d} (for the one pool of K and V: kv_row's {row[0]} "
            f"of {row[1]})")
    if h % h_kv:
        raise ValueError(f"num_heads {h} not a multiple of kv heads {h_kv}")
    if block < 8 or block % 8:
        raise ValueError(
            f"block_size must be a multiple of 8, got {block}")
    cache_len = jnp.asarray(cache_len, jnp.int32)
    if cache_len.ndim != 1 or cache_len.shape[0] != b:
        raise ValueError(
            f"paged decode takes per-row cache_len [B={b}]; got "
            f"{cache_len.shape}")
    table = jnp.asarray(page_table, jnp.int32)
    if table.ndim != 2 or table.shape[0] != b:
        raise ValueError(
            f"page_table must be [B={b}, max_blocks]; got {table.shape}")
    if side_k is not None:
        if side_k.ndim != 3:
            raise ValueError(
                "side buffers must be packed 3-D [B, cap, Hkv*D]")
        side_k = _pad_side(side_k, k_pool.dtype)
        if v_pool is not None:
            side_v = _pad_side(side_v, v_pool.dtype)
    if interpret is None:
        interpret = jax.default_backend() == "cpu"

    return _paged_decode_one(
        q, k_pool, v_pool, table, cache_len,
        jnp.asarray(side_len, jnp.int32), side_k, side_v, h_kv=h_kv,
        interpret=bool(interpret), window=window)


@functools.partial(jax.jit, static_argnames=("h_kv", "interpret", "window",
                                             "name"))
def _paged_decode_one(q, k_pool, v_pool, table, cache_len, side_len,
                      side_k, side_v, *, h_kv: int, interpret: bool,
                      window: int | None = None, name: str | None = None):
    """The validated single-query call of :func:`paged_flash_decode`
    (``v_pool`` None: the one pool of K and V, ``side_v`` None with it).
    Under its own ``jit``: a segment program calls it once a layer with
    the same shapes, and the kernel body is then traced and lowered once
    a program and not once a layer (measured: 36 calls lowered in 7 s
    without it, and a persistent-cache hit still pays the lowering)."""
    b, _, h, d = q.shape
    block, m_blocks = k_pool.shape[1], table.shape[1]
    g = h // h_kv
    gp = -(-g // 8) * 8
    side = side_k is not None
    # meta = [side_len, len_0..len_{B-1}, table[0,0]..table[B-1,M-1]]: the
    # one scalar-prefetch operand.  A length is held to the table's reach,
    # so the walk never reads a page id past a lane's row
    meta = jnp.concatenate([
        side_len.reshape(1),
        jnp.minimum(cache_len, m_blocks * block), table.reshape(-1)])

    paired = _pairs(h_kv, d)
    q4 = q.reshape(b, h_kv, g, d)
    q4 = jnp.pad(q4, ((0, 0), (0, 0), (0, gp - g), (0, 0)))
    if paired:
        # pool pairing is free: adjacent KV heads are contiguous in the
        # packed minor dim, so a pair chunk is just a wider slice of it —
        # no reshape of the pool ever happens
        r_kv = h_kv // 2
        q3 = q4.reshape(b * r_kv, 2, gp, d)
    else:
        r_kv = h_kv
        q3 = q4.reshape(b * h_kv, gp, d)
    joined = v_pool is None
    out = _paged_call(
        meta, q3, (k_pool,) if joined else (k_pool, v_pool),
        None if not side else (side_k,) if joined else (side_k, side_v),
        scale=d ** -0.5, lanes=b, r_kv=r_kv, paired=paired, d_v=None,
        interpret=interpret, window=window, joined=joined,
        name=name or ("paged_flash_decode" if window is None
                      else "paged_window_decode"))
    if paired:
        o = out.reshape(b, r_kv * 2, gp, d)
        return o[:, :, :g].reshape(b, 1, h, d)
    return out.reshape(b, r_kv, gp, d)[:, :, :g].reshape(b, 1, h, d)


def _paged_call(meta, q3, pools, sides, *, scale: float, lanes: int,
                r_kv: int, paired: bool, d_v: int | None, interpret: bool,
                name: str, window: int | None = None, joined: bool = False):
    """The ``pallas_call`` the paged decode kernels share: one grid row a
    lane and all its ``r_kv`` K/V-head chunks where their tile slots fit
    (:func:`paged_grid_rows`; several rows a lane, each a group of
    chunks, where not), the pools left in HBM for the body's own copies,
    ``q3 [lanes * r_kv, gp, d]`` (``[.., 2, gp, d]`` paired) in and an
    output of its shape (``d_v`` wide where the one pool's rows double as
    values) out.  ``joined``: the one pool (and the one side buffer) holds
    keys and values in rows of :func:`kv_row`; its tile slots are the two
    pools' together and it is counted as the two, of the queries' dtype."""
    block, m_blocks = pools[0].shape[1], (meta.shape[0] - 1 - lanes) // lanes
    members = 2 if paired else 1
    gp, d_head = q3.shape[-2:]
    # keys beside values a row (else a word holds one of each, and the
    # copies and the side buffer are those of any one pool)
    halves = joined and pools[0].dtype != jnp.uint32
    rows = paged_grid_rows(
        lanes, r_kv * members, d_head, block, m_blocks,
        pools=2 if joined else len(pools),
        itemsize=(q3 if joined else pools[0]).dtype.itemsize)
    groups = rows // lanes
    chunks = r_kv // groups                # of the lane's r_kv, a grid row
    # where each head of a row sits in its query and output blocks (None:
    # the one head is the block), and the row's columns of a page
    slots = (tuple((j, m) for j in range(chunks) for m in range(2))
             if paired else
             tuple((j,) for j in range(chunks)) if chunks > 1 else None)
    n = chunks * members
    d = n * d_head
    # a grid row's queries in, its output out: the same block of both
    row_spec = pl.BlockSpec((chunks, *q3.shape[1:]),
                            lambda g_, m: (g_,) + (0,) * (q3.ndim - 1))
    if d_v is None:
        out_spec, out_shape, acc_d = row_spec, q3.shape, d
    else:
        out_spec = pl.BlockSpec((1, gp, d_v), lambda g_, m: (g_, 0, 0))
        out_shape, acc_d = (q3.shape[0], gp, d_v), d_v
    G = groups  # noqa: N806 — closed over by the index maps
    pages_per_tile = paged_tile_pages(block, m_blocks)
    # the pools are left where they are (HBM): the kernel's own copies
    # fetch the pages a lane really holds, by the ids in meta
    pool_spec = pl.BlockSpec(memory_space=pl.ANY)
    args = [meta, q3, *pools]
    in_specs = [row_spec] + [pool_spec] * len(pools)
    if sides is not None and halves:
        # the one side buffer as its two halves: the row's key columns and,
        # a half further (``G`` blocks of ``d``), its value columns
        args += [sides[0]] * 2
        in_specs += [
            pl.BlockSpec((1, sides[0].shape[1], d),
                         lambda g_, m, at=at: (g_ // G, 0, at + g_ % G))
            for at in (0, G)]
    elif sides is not None:
        side_spec = pl.BlockSpec(
            (1, sides[0].shape[1], d), lambda g_, m: (g_ // G, 0, g_ % G))
        args += list(sides)
        in_specs += [side_spec] * len(sides)

    tile_buf = pltpu.VMEM(
        (2, pages_per_tile, block, 2 * d if halves else d), pools[0].dtype)
    return pl.pallas_call(
        functools.partial(
            _paged_decode_kernel, scale=scale, block=block,
            pages_per_tile=pages_per_tile, m_blocks=m_blocks, lanes=lanes,
            groups=groups, slots=slots, side=sides is not None, d_v=d_v,
            window=window, joined=joined),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(rows,),
            in_specs=in_specs,
            out_specs=out_spec,
            scratch_shapes=[tile_buf] * len(pools) + [  # two slots a pool
                pltpu.SemaphoreType.DMA((len(pools), 2)),  # [pool, slot]
                pltpu.SMEM((2,), jnp.int32),         # cross-row prefetch
                pltpu.VMEM((n * gp, 1), jnp.float32),
                pltpu.VMEM((n * gp, 1), jnp.float32),
                pltpu.VMEM((n * gp, acc_d), jnp.float32),
            ] + ([pltpu.VMEM((n * gp, d), q3.dtype)] if n > 1 else []),
        ),
        out_shape=jax.ShapeDtypeStruct(out_shape, q3.dtype),
        # sequential: a row starts the next live row's first tile
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
        name=name,
    )(*args)


def _pad_side(side, dtype):
    """A side buffer's capacity rounded up to the sublane tile."""
    cap = side.shape[1]
    capp = max(8, -(-cap // 8) * 8)
    if capp != cap:
        side = jnp.pad(side, ((0, 0), (0, capp - cap), (0, 0)))
    return side.astype(dtype)


def paged_mla_decode(
    q: jnp.ndarray,
    pool: jnp.ndarray,
    page_table: jnp.ndarray,
    cache_len: jnp.ndarray,
    *,
    d_v: int,
    scale: float,
    side: jnp.ndarray | None = None,
    side_len: jnp.ndarray | int = 0,
    interpret: bool | None = None,
) -> jnp.ndarray:
    """One ABSORBED decode step of multi-head latent attention against a
    paged latent cache: multi-query attention of ``H`` query heads against
    ONE key row a token, whose first ``d_v`` columns are also the value.

    The walk is :func:`paged_flash_decode`'s (live pages only, tiles of
    about 1024 tokens, double-buffered copies, the next lane's first tile
    prefetched, f32 ``m`` / ``l`` / ``acc``) over one pool read once; a
    grid row is a lane, its ``[H, W]`` query block against a tile's
    ``[1024, W]`` rows.

    Args:
      q: ``[B, H, W]`` absorbed queries (``q_nope W_kvb^K`` beside the
        rotated ``q_rope``, zero in any padding columns).
      pool: ``[num_blocks, block_size, W]`` latent rows ``[c_kv | k_rope |
        padding]``; ``W`` a multiple of 128, padding columns ZERO (``d_v``
        a multiple of 128 too wherever the chip's compiler is to slice
        the values off lane-aligned).
      page_table / cache_len: as :func:`paged_flash_decode`.
      d_v: the latent width (the first ``d_v`` columns are the values).
      scale: the softmax scale (the model's, not ``W ** -0.5``).
      side / side_len: the segment-local staging buffer ``[B, cap, W]``.

    Returns ``[B, H, d_v]`` (the caller applies ``W_kvb^V``).  In a trace
    the kernel is ``paged_mla_decode`` with FOUR operands (meta, q, pool,
    side) where ``paged_flash_decode`` has six."""
    b, h, w = q.shape
    if pool.ndim != 3 or pool.shape[2] != w:
        raise ValueError(
            f"the latent pool is [N, block, W={w}]; got {pool.shape}")
    if w % 128 or not 0 < d_v <= w:
        raise ValueError(
            f"row width {w} must be a lane multiple (pad the row with zero "
            f"columns) and hold the {d_v} value columns")
    block = pool.shape[1]
    if block < 8 or block % 8:
        raise ValueError(
            f"block_size must be a multiple of 8, got {block}")
    cache_len = jnp.asarray(cache_len, jnp.int32)
    table = jnp.asarray(page_table, jnp.int32)
    if cache_len.shape != (b,) or table.ndim != 2 or table.shape[0] != b:
        raise ValueError(
            f"per-row cache_len [B={b}] and page_table [B, max_blocks] "
            f"needed; got {cache_len.shape}, {table.shape}")
    if side is not None:
        side = _pad_side(side, pool.dtype)
    if interpret is None:
        interpret = jax.default_backend() == "cpu"
    return _paged_mla_one(
        q, pool, table, cache_len, jnp.asarray(side_len, jnp.int32), side,
        d_v=int(d_v), scale=float(scale), interpret=bool(interpret))


@functools.partial(jax.jit, static_argnames=("d_v", "scale", "interpret"))
def _paged_mla_one(q, pool, table, cache_len, side_len, side, *, d_v: int,
                   scale: float, interpret: bool):
    """The validated call of :func:`paged_mla_decode`, under its own
    ``jit`` for the reason :func:`_paged_decode_one` gives."""
    b, h, w = q.shape
    block, m_blocks = pool.shape[1], table.shape[1]
    hp = -(-h // 8) * 8
    meta = jnp.concatenate([
        side_len.reshape(1),
        jnp.minimum(cache_len, m_blocks * block), table.reshape(-1)])
    q3 = jnp.pad(q, ((0, 0), (0, hp - h), (0, 0)))
    out = _paged_call(
        meta, q3, (pool,), None if side is None else (side,), scale=scale,
        lanes=b, r_kv=1, paired=False, d_v=d_v, interpret=interpret,
        name="paged_mla_decode")
    return out[:, :h]


# -- learned sparse attention (an indexer) -----------------------------------
#
# Three routines, the same for a decode step and a prefill chunk: the index
# scores of every cached row (a kernel on the paged walk), the exact top-k
# of them (as a mask; as ascending positions where rows are gathered), and
# grouped-query attention over the chosen rows alone.

def _index_scores_kernel(meta_ref, q_ref, w_ref, pool, o_ref, buf, sems, *,
                         block: int, pages_per_tile: int, m_blocks: int,
                         lanes: int, tq: int, heads: int,
                         shared_table: bool):
    """Index scores of ONE grid row: ``tq`` consecutive queries (a decode
    lane: one) against the rows of its pages, ``I[j, s] = sum_h w[j, h] x
    relu(q[j, h] . k[s])`` in float32, ``-inf`` from each query's own limit
    on.

    The walk is :func:`_paged_decode_kernel`'s: ``meta_ref`` is ``[len_0 ..
    len_{L-1}, table ...]`` (one table row a lane, or ONE row every
    lane shares: a prefill chunk's queries read one batch-1 cache), the pool
    stays in HBM and the body copies a lane's LIVE pages, a tile of
    ``pages_per_tile`` at a time into two slots, the next tile's copies
    started before this one is computed.  ``len`` is the rows the row's LAST
    query sees; query ``j`` sees ``len - (tq - 1 - j)`` (causal inside the
    row).  Columns are independent (no softmax runs across them), so a tile
    is computed whole and masked: what an uncopied page of a slot holds
    lands only in columns at or beyond the length.  The output block is the
    row's queries by ALL its columns, ``[tq, tiles x tile rows]`` (the form
    the selection reads: no transposition follows the call), and tile ``t``
    is stored at columns ``[t x tile rows, (t + 1) x tile rows)`` of it.  It
    is filled with ``-inf`` first: tiles the walk never reaches stay so."""
    g = pl.program_id(0)
    n_len = meta_ref[g]
    n_pages = (n_len + block - 1) // block
    n_tiles = (n_pages + pages_per_tile - 1) // pages_per_tile
    tile_rows = pages_per_tile * block
    base = lanes + (0 if shared_table else g * m_blocks)
    o_ref[...] = jnp.full(o_ref.shape, -jnp.inf, o_ref.dtype)

    def tile_copies(t, slot, fn):
        live = jnp.minimum(n_pages - t * pages_per_tile, pages_per_tile)

        def one_page(p, _):
            page = meta_ref[base + t * pages_per_tile + p]
            fn(pltpu.make_async_copy(pool.at[page], buf.at[slot, p],
                                     sems.at[slot]))

        jax.lax.fori_loop(0, live, one_page, None)

    @pl.when(n_tiles > 0)
    def _walk():
        tile_copies(0, 0, lambda c: c.start())

        def one_tile(t, _):
            slot = t % 2

            @pl.when(t + 1 < n_tiles)
            def _next_tile():
                tile_copies(t + 1, 1 - slot, lambda c: c.start())

            tile_copies(t, slot, lambda c: c.wait())
            keys = buf[slot].reshape(tile_rows, buf.shape[-1])
            s = jax.lax.dot_general(
                q_ref[0], keys, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32)   # [tq * heads, rows]
            s = jnp.maximum(s, 0.0) * w_ref[0]
            if tq == 1:
                s = jnp.sum(s, axis=0, keepdims=True)
            else:
                s = jnp.sum(s.reshape(tq, heads, tile_rows), axis=1)
            col = t * tile_rows + jax.lax.broadcasted_iota(
                jnp.int32, s.shape, 1)
            limit = n_len - (tq - 1) + jax.lax.broadcasted_iota(
                jnp.int32, s.shape, 0)
            at = pl.ds(pl.multiple_of(t * tile_rows, tile_rows), tile_rows)
            o_ref[0, :, at] = jnp.where(col < limit, s, -jnp.inf)

        jax.lax.fori_loop(0, n_tiles, one_tile, None)


def index_queries_per_row(queries: int, heads: int, rows: int) -> int:
    """Queries a grid row of :func:`paged_index_scores` takes where they
    share their rows (a prefill chunk): the most of 16, 8, 4, 2 that divide
    them while the row's float32 score tile ``[tq x heads, 1024]`` stays
    2 MiB and its output block ``[tq, rows]`` 2 MiB (two of them in flight)
    of the 16 MiB a kernel gets."""
    for tq in (16, 8, 4, 2):
        if (queries % tq == 0 and tq * heads <= 512
                and tq * rows * 4 <= 2 << 20):
            return tq
    return 1


def paged_index_scores(
    q: jnp.ndarray,
    w: jnp.ndarray,
    pool: jnp.ndarray,
    page_table: jnp.ndarray,
    rows_seen: jnp.ndarray,
    *,
    interpret: bool | None = None,
) -> jnp.ndarray:
    """A learned indexer's scores of cached rows: ``I[t, s] = sum_h
    w[t, h] relu(q[t, h] . k[s])`` for every row ``s`` query ``t`` sees,
    ``-inf`` for every other, float32.

    Args:
      q: ``[T, H, D]`` index queries; w: ``[T, H]`` float32 head weights.
      pool: ``[num_blocks, block, D]`` index keys by page.
      page_table: ``[L, M]`` pages of each of ``L`` grid rows, ``T / L``
        consecutive queries each (a decode step: ``L = T`` lanes, each its
        own pages), or ``[1, M]``: one row of pages every grid row reads (a
        prefill chunk over one batch-1 cache; the rows then take
        :func:`index_queries_per_row` queries each).
      rows_seen: ``[L]`` rows the LAST query of each grid row sees; the
        query ``j`` places before it sees ``j`` rows fewer.

    Returns ``[T, M x block]``.  In a trace the kernel is
    ``paged_index_scores``."""
    t, h, d = q.shape
    table = jnp.asarray(page_table, jnp.int32)
    rows_seen = jnp.asarray(rows_seen, jnp.int32)
    lanes = rows_seen.shape[0]
    if (pool.ndim != 3 or pool.shape[2] != d or w.shape != (t, h)
            or table.ndim != 2 or table.shape[0] not in (1, lanes)
            or t % lanes):
        raise ValueError(
            f"index scores take q [T, H, D], w [T, H], pool [N, block, D], "
            f"a table [L or 1, M] and rows_seen [L] with L dividing T; got "
            f"{q.shape}, {w.shape}, {pool.shape}, {table.shape}, "
            f"{rows_seen.shape}")
    block = pool.shape[1]
    if block < 8 or block % 8 or h % 8:
        raise ValueError(
            f"block_size and the index heads must be multiples of 8, got "
            f"{block}, {h}")
    if interpret is None:
        interpret = jax.default_backend() == "cpu"
    return _index_scores_one(q, w.astype(jnp.float32), pool, table,
                             rows_seen, interpret=bool(interpret))


@functools.partial(jax.jit, static_argnames=("interpret",))
def _index_scores_one(q, w, pool, table, rows_seen, *, interpret: bool):
    """The validated call of :func:`paged_index_scores`.  The kernel
    writes ``[lanes, tq, tiles x tile rows]``, a grid row's block the last
    two dimensions whole; ``[T, columns]`` is a reshape of it (on the chip
    a bitcast where ``tq`` is a multiple of the 8 rows of a tile, as a
    chunk's is) and the table's own columns a slice."""
    t, h, d = q.shape
    lanes, m_blocks = rows_seen.shape[0], table.shape[1]
    tq = t // lanes
    block = pool.shape[1]
    ppt = paged_tile_pages(block, m_blocks)
    n_tiles, tile_rows = -(-m_blocks // ppt), ppt * block
    meta = jnp.concatenate([
        jnp.minimum(rows_seen, m_blocks * block), table.reshape(-1)])
    row = lambda g, m: (g, 0, 0)  # noqa: E731
    out = pl.pallas_call(
        functools.partial(
            _index_scores_kernel, block=block, pages_per_tile=ppt,
            m_blocks=m_blocks, lanes=lanes, tq=tq, heads=h,
            shared_table=table.shape[0] == 1 and lanes > 1),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(lanes,),
            in_specs=[pl.BlockSpec((1, tq * h, d), row),
                      pl.BlockSpec((1, tq * h, 1), row),
                      pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=pl.BlockSpec((1, tq, n_tiles * tile_rows), row),
            scratch_shapes=[pltpu.VMEM((2, ppt, block, d), pool.dtype),
                            pltpu.SemaphoreType.DMA((2,))],
        ),
        out_shape=jax.ShapeDtypeStruct((lanes, tq, n_tiles * tile_rows),
                                       jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
        name="paged_index_scores",
    )(meta, q.reshape(lanes, tq * h, d).astype(pool.dtype),
      w.reshape(lanes, tq * h, 1), pool)
    return out.reshape(t, n_tiles * tile_rows)[:, : m_blocks * block]


def block_of(n: int, least: int = 1, most: int = 128) -> int:
    """The largest power of two up to ``most`` (and not under ``least``)
    that divides ``n``: rows a page, queries a block, columns a pass."""
    block = most
    while block >= max(least, 1):
        if n % block == 0:
            return block
        block //= 2
    raise ValueError(f"{n} is not a multiple of {least}")


_SELECT_CHUNK = 128   # columns a chunk of the positions' prefix sum
_SELECT_PASS = 2048   # columns a step of a counting pass that follows rows


def index_select_mask(scores: jnp.ndarray, k: int,
                      rows: jnp.ndarray | None = None) -> jnp.ndarray:
    """Which ``k`` columns of each row of ``scores [T, R]`` are the
    largest, EXACT, equal scores to the lower column (``jax.lax.top_k``'s
    rule): a bool mask ``[T, R]``.  Only columns with a score above
    ``-inf`` count: a row with fewer than ``k`` of them has those chosen.

    No sort (on the chip ``jax.lax.top_k`` is a stable sort of the whole
    row with its columns): the ``k``-th largest value and the cut among
    the columns equal to it from the kernel ``index_select_threshold``
    (:func:`_select_threshold`), the mask from them in one elementwise
    pass.  ``rows`` (a scalar; every column from it on is ``-inf``): the
    kernel's passes stop at the step of ``_SELECT_PASS`` columns that
    holds it, so a prefill chunk's selection follows the rows cached and
    not the cache's capacity.  Under the scope ``index_select``."""
    with jax.named_scope("index_select"):
        return _chosen_columns(scores, k, rows)


def _select_queries(t: int, width: int) -> int:
    """Query rows a grid row of the threshold kernel takes: the most of 32,
    16, 8 whose float32 scores ``[tq, width]``, two buffers of them and
    the int32 keys beside, stay 12 MiB of the 16 MiB a kernel gets, and no
    more than the rows there are (rounded up to the 8 of a tile)."""
    for tq in (32, 16):
        if 3 * 4 * tq * width <= 12 << 20 and tq < t + 8:
            return tq
    return 8


def _select_threshold_kernel(rows_ref, scores_ref, tau_ref, last_ref,
                             key_ref, *, k: int, step: int):
    """ONE grid row of the exact top-``k``'s search: a block of ``tq``
    query rows whose scores ``[tq, width]`` came into VMEM once.

    Their float32 bit patterns become signed integers in the floats'
    order (``key_ref``); the ``k``-th largest key of each row is found
    bit by bit from the sign down, each bit one counting pass over the
    block (compare and add on 128-column pieces, the partial counts kept
    a lane and summed across the lanes once a pass); then how many
    columns above it a row holds and how many equal to it, and, only in a
    block where some row holds more equal columns than it needs, the
    column of the last one it takes, bit by bit the same way.

    A pass covers the steps of ``step`` columns up to the one that holds
    ``rows_ref[0]`` (every column from there on is ``-inf``), and a last
    shorter step where the width is no multiple of ``step``."""
    tq, width = scores_ref.shape
    whole, tail = divmod(width, step)
    steps = jnp.minimum((rows_ref[0] + step - 1) // step, whole)
    lane = jax.lax.broadcasted_iota(jnp.int32, (tq, 128), 1)

    def columns(fn, acc):
        """``fn(acc, first column, columns)`` over the live steps."""
        def one(j, acc):
            return fn(acc, pl.multiple_of(j * step, step), step)

        acc = jax.lax.fori_loop(0, steps, one, acc)
        return fn(acc, whole * step, tail) if tail else acc

    def to_keys(_, base, n):
        bits = jax.lax.bitcast_convert_type(
            scores_ref[:, pl.ds(base, n)], jnp.int32)
        key_ref[:, pl.ds(base, n)] = jnp.where(
            bits < 0, bits ^ jnp.int32(0x7FFFFFFF), bits)

    columns(to_keys, None)

    def count(pred):
        """How many live columns of each row ``pred(keys, first column)``
        holds for: ``[tq, 1]``."""
        def pieces(acc, base, n):
            hits = [pred(key_ref[:, pl.ds(base + c, 128)], base + c)
                    .astype(jnp.int32) for c in range(0, n, 128)]
            while len(hits) > 1:     # pairwise: no chain of dependent adds
                hits = [a + b for a, b in zip(hits[::2], hits[1::2])] + (
                    hits[-1:] if len(hits) % 2 else [])
            return acc + hits[0]

        lanes = columns(pieces, jnp.zeros((tq, 128), jnp.int32))
        return jnp.sum(lanes, axis=1, keepdims=True)

    def wide(x):
        return jnp.broadcast_to(x, (tq, 128))

    def value_bit(i, tau):
        # offset binary from the sign bit down: 1 << 31 wraps to the
        # least integer, and the least integer plus itself to 0
        cand = tau + jnp.left_shift(jnp.int32(1), 31 - i)
        at = wide(cand)
        return jnp.where(count(lambda kb, c: kb >= at) >= k, cand, tau)

    tau = jax.lax.fori_loop(0, 32, value_bit,
                            jnp.full((tq, 1), -2 ** 31, jnp.int32))
    at = wide(tau)
    # of the columns that equal the k-th value, the lowest `need`
    need = k - count(lambda kb, c: kb > at)
    tied = count(lambda kb, c: kb == at) > need
    tau_ref[...] = tau
    last_ref[...] = jnp.full((tq, 1), width, jnp.int32)
    nbits = max(1, (width - 1).bit_length())

    # a row short of k finite scores needs all its equal columns, and a
    # row whose k-th value stands alone its one: neither is searched
    @pl.when(jnp.max(tied.astype(jnp.int32)) > 0)
    def _cut():
        def column_bit(i, p):
            cand = p + jnp.left_shift(jnp.int32(1), nbits - 1 - i)
            end = wide(cand)
            n = count(lambda kb, c: (kb == at) & (lane + c < end))
            return jnp.where(n < need, cand, p)

        p = jax.lax.fori_loop(0, nbits, column_bit,
                              jnp.zeros((tq, 1), jnp.int32))
        last_ref[...] = jnp.where(tied, p, width)


@functools.partial(jax.jit, static_argnames=("k", "interpret"))
def _select_threshold(scores, rows, *, k: int, interpret: bool):
    """``(tau, last)`` of each row of ``scores [T, W]`` (``W`` a multiple
    of 128), ``[T, 1]`` int32 each: the key of its ``k``-th largest score
    and the column of the last key equal to it that the top ``k`` take
    (``W`` where the row takes all of them).  In a trace the kernel is
    ``index_select_threshold``."""
    t, width = scores.shape
    tq = _select_queries(t, width)
    out = jax.ShapeDtypeStruct((t, 1), jnp.int32)
    block = lambda i, r: (i, 0)  # noqa: E731
    return pl.pallas_call(
        functools.partial(_select_threshold_kernel, k=k,
                          step=min(_SELECT_PASS, width)),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(-(-t // tq),),
            in_specs=[pl.BlockSpec((tq, width), block)],
            out_specs=[pl.BlockSpec((tq, 1), block),
                       pl.BlockSpec((tq, 1), block)],
            scratch_shapes=[pltpu.VMEM((tq, width), jnp.int32)],
        ),
        out_shape=[out, out],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",)),
        interpret=interpret,
        name="index_select_threshold",
    )(jnp.reshape(rows, (1,)).astype(jnp.int32), scores)


def _chosen_columns(scores, k: int, rows=None):
    t, r = scores.shape
    width = r + -r % 128
    padded = jnp.pad(scores, ((0, 0), (0, width - r)),
                     constant_values=-jnp.inf)
    tau, last = _select_threshold(
        padded, width if rows is None else rows, k=k,
        interpret=jax.default_backend() == "cpu")
    bits = jax.lax.bitcast_convert_type(scores, jnp.int32)
    # signed integers in the order of the floats they spell
    key = jnp.where(bits < 0, bits ^ jnp.int32(0x7FFFFFFF), bits)
    col = jax.lax.broadcasted_iota(jnp.int32, key.shape, 1)
    return ((key > tau) | ((key == tau) & (col <= last))
            ) & (scores > -jnp.inf)


def index_select(scores: jnp.ndarray, k: int) -> jnp.ndarray:
    """:func:`index_select_mask`'s columns as POSITIONS, ascending:
    ``[T, k]`` int32.  A row with ``n < k`` columns above ``-inf`` lists
    those in its first ``n`` places and anything after (the caller knows
    how many it offered).

    No scatter and no sort: the chosen columns from a prefix sum in two
    levels (chunks of 128 columns, then inside the chunk of each output
    place), products and comparisons only."""
    with jax.named_scope("index_select"):
        t, r = scores.shape
        scores = jnp.pad(scores, ((0, 0), (0, -r % _SELECT_CHUNK)),
                         constant_values=-jnp.inf)
        chosen = _chosen_columns(scores, k)
        # place j holds the (j + 1)-th chosen column: the place's chunk
        # from the chunks' running counts; that chunk's own running count
        # picked out by a one-hot product; the column inside the chunk is
        # how many of its columns lie before the place's rank.  Counts up
        # to 128 are exact in bfloat16.
        n_chunks = scores.shape[1] // _SELECT_CHUNK
        chunks = chosen.reshape(t, n_chunks, _SELECT_CHUNK)
        triangle = (jnp.arange(_SELECT_CHUNK)[:, None]
                    <= jnp.arange(_SELECT_CHUNK)[None, :]
                    ).astype(jnp.bfloat16)
        inside = jnp.einsum("tcd,de->tce", chunks.astype(jnp.bfloat16),
                            triangle, preferred_element_type=jnp.float32)
        per_chunk = inside[..., -1].astype(jnp.int32)         # [t, chunks]
        running = jnp.cumsum(per_chunk, axis=1)
        place = jnp.arange(k, dtype=jnp.int32)
        done = running[:, None, :] <= place[None, :, None]  # [t, k, chunks]
        chunk = jnp.sum(done.astype(jnp.int32), axis=-1)
        before = jnp.sum(jnp.where(done, per_chunk[:, None, :], 0), axis=-1)
        current = jnp.concatenate(
            [jnp.ones((t, k, 1), bool), done[..., :-1]], axis=-1) & ~done
        mine = jnp.einsum("tkc,tcd->tkd", current.astype(jnp.bfloat16),
                          inside.astype(jnp.bfloat16),
                          preferred_element_type=jnp.float32)  # [t, k, 128]
        within = jnp.sum(
            (mine <= (place[None, :] - before)[..., None].astype(jnp.float32)
             ).astype(jnp.int32), axis=-1)
        return jnp.minimum(chunk * _SELECT_CHUNK + within, r - 1)


# gathers of ``[T, k]`` rows one call of :func:`sparse_gqa_attend` makes
# (the host's count of what a decode step fetches, ``serve/rows_gathered``)
SPARSE_ATTEND_GATHERS = 1


def kv_row(flat: int, dtype) -> tuple[int, jnp.dtype]:
    """``(columns, dtype)`` of the ONE row that holds ``flat`` numbers of K
    and ``flat`` of V of a token in ``dtype`` (an indexer's layer's
    ``paged_kv`` / ``side_kv``).  A row is 32-bit WORDS, because the chip
    gathers a row of words a quarter cheaper than the same bytes as halves
    (a 16-bit row shares its sublanes with its neighbour): two 16-bit
    numbers share a word, ``uint32[flat]``, K's element ``j`` the low half
    of word ``j`` and V's the high; numbers of any other width lie side by
    side in their own dtype, ``[2 * flat]``, K then V."""
    dtype = jnp.dtype(dtype)
    return (flat, jnp.dtype(jnp.uint32)) if dtype.itemsize == 2 else (
        2 * flat, dtype)


def pack_kv(k: jnp.ndarray, v: jnp.ndarray) -> jnp.ndarray:
    """K and V of tokens, ``[..., flat]`` each in one dtype, as rows of
    :func:`kv_row`.  Bits are moved, never rounded."""
    if k.dtype != v.dtype or k.shape != v.shape:
        raise ValueError(
            f"K and V of one shape and dtype needed; got {k.shape} "
            f"{k.dtype}, {v.shape} {v.dtype}")
    if k.dtype.itemsize != 2:
        return jnp.concatenate([k, v], axis=-1)

    def bits(x):
        return jax.lax.bitcast_convert_type(x, jnp.uint16).astype(jnp.uint32)

    return bits(k) | (bits(v) << 16)


def unpack_kv(row: jnp.ndarray, dtype) -> tuple[jnp.ndarray, jnp.ndarray]:
    """``(K, V)`` in ``dtype`` out of rows of :func:`kv_row`."""
    dtype = jnp.dtype(dtype)
    _, held = kv_row(0, dtype)
    if row.dtype != held:
        raise ValueError(
            f"rows of {held} hold K and V in {dtype}; got {row.dtype}")
    if dtype.itemsize != 2:
        k, v = jnp.split(row, 2, axis=-1)
        return k, v
    return _word_half(row, 0, dtype), _word_half(row, 1, dtype)


def sparse_gqa_attend(
    q: jnp.ndarray,
    kv_source: jnp.ndarray,
    ids: jnp.ndarray,
    count: jnp.ndarray,
    *,
    packed_kv_heads: int,
    side_kv: jnp.ndarray | None = None,
    interpret: bool | None = None,
) -> jnp.ndarray:
    """Grouped-query attention of each query over ITS OWN chosen rows.

    Args:
      q: ``[T, H, D]`` queries (a decode step: one a lane).
      kv_source: ``[N, W]`` rows by flat row id (a pool seen flat:
        ``page x block + offset``), each a token's packed K and V as
        :func:`pack_kv` lays them (:func:`kv_row` of ``Hkv*D`` numbers in
        ``q``'s dtype: ``uint32[Hkv*D]`` words, K the low halves and V the
        high, for a 16-bit ``q``; ``[2*Hkv*D]``, K then V, else).
      ids: ``[T, k]`` row ids; the first ``count[t]`` of query ``t``'s are
        attended, the others ignored (any value).
      side_kv: ``[T, cap, W]``: an id of ``N + j`` names row ``j`` of
        the query's own side buffer (the segment's staging rows).  Those
        are the highest ids, and ``ids`` must list a query's first
        ``count`` in ASCENDING order (:func:`index_select`'s), so that the
        staged rows are the last ``cap`` or fewer of them.

    The rows are gathered with XLA, ONCE: K and V of a token are one row
    of 32-bit words, because the chip's gather costs by the row, hardly by
    the byte, and a quarter less for words than for halves (``[T, k, W]``:
    written once and read once, where a kernel with row-granular copies
    would read them once).  The paged walk then attends that one buffer,
    the chosen rows its pages, a tile's keys and values the two halves of
    its words (of its columns where a number is a word): the arithmetic is
    :func:`paged_flash_decode`'s.  Returns ``[T, H, D]``.  In a trace the
    kernel is ``sparse_gqa_attend``."""
    t, h, d = q.shape
    n, k = kv_source.shape[0], ids.shape[1]
    width, words = kv_row(packed_kv_heads * d, q.dtype)
    if (kv_source.shape != (n, width) or kv_source.dtype != words
            or ids.shape[0] != t or count.shape != (t,)
            or h % packed_kv_heads):
        raise ValueError(
            f"q [T, H, D], a source [N, {width}] of {words} (kv_row), ids "
            f"[T, k], count [T] needed; got {q.shape}, {kv_source.shape} "
            f"of {kv_source.dtype}, {ids.shape}, {count.shape}")
    count = jnp.minimum(jnp.asarray(count, jnp.int32), k)
    block = block_of(k, 8)   # rows a page of the gathered buffer
    pages = k // block
    if interpret is None:
        interpret = jax.default_backend() == "cpu"
    with routine("attn/rows"):
        rows = jnp.take(kv_source, jnp.minimum(ids, n - 1), axis=0,
                        mode="clip")
        if side_kv is not None:
            # the staged rows lie in the last ``cap`` places before
            # ``count``: only that window of the gathered buffer is looked
            # at again
            cap = min(side_kv.shape[1], k)
            at = (jnp.clip(count - cap, 0, k - cap)[:, None]
                  + jnp.arange(cap))
            window = jnp.take_along_axis(ids, at, axis=1)       # [T, cap]
            staged = jnp.take_along_axis(
                side_kv,
                jnp.clip(window - n, 0, side_kv.shape[1] - 1)[..., None],
                axis=1).astype(rows.dtype)
            fixed = jnp.where(
                (window >= n)[..., None], staged,
                jnp.take_along_axis(rows, at[..., None], axis=1))
            rows = rows.at[jnp.arange(t)[:, None], at].set(fixed)
        rows = rows.reshape(t * pages, block, width)
    with routine("attn/core"):
        out = _paged_decode_one(
            q[:, None], rows, None,
            jnp.arange(t * pages, dtype=jnp.int32).reshape(t, pages), count,
            jnp.zeros((), jnp.int32), None, None, h_kv=packed_kv_heads,
            interpret=bool(interpret), name="sparse_gqa_attend")
        return out[:, 0]


def quantize_kv(k: jnp.ndarray, v: jnp.ndarray):
    """Per-(token, head) symmetric int8 quantization of K/V cache blocks:
    ``[..., D] -> (int8 [..., D], f32 scale [..., 1])``.  Halves the
    bytes the decode step must stream — at long context decode is
    bandwidth-bound (measured 668 GB/s = 82% of the v5e's spec), so the
    ceiling on decode throughput is ~2× the bf16 cache's."""
    def q(x):
        x32 = x.astype(jnp.float32)
        scale = jnp.max(jnp.abs(x32), axis=-1, keepdims=True) / 127.0
        scale = jnp.maximum(scale, 1e-8)
        return (jnp.round(x32 / scale).astype(jnp.int8), scale)

    kq, ks = q(k)
    vq, vs = q(v)
    return kq, ks, vq, vs


def flash_decode_q8(
    q: jnp.ndarray,
    k_cache_q8: jnp.ndarray,
    k_scale: jnp.ndarray,
    v_cache_q8: jnp.ndarray,
    v_scale: jnp.ndarray,
    cache_len: jnp.ndarray | int,
    *,
    window: int | None = None,
    block_k: int = 1024,
    interpret: bool | None = None,
    pos_offset: jnp.ndarray | int = 0,
    return_lse: bool = False,
) -> jnp.ndarray | tuple[jnp.ndarray, jnp.ndarray]:
    """:func:`flash_decode` over an int8-quantized KV cache.

    Args:
      q: ``[B, 1, H, D]`` queries (bf16/f32).
      k_cache_q8 / v_cache_q8: ``[B, S, H_kv, D]`` int8 buffers from
        :func:`quantize_kv`.
      k_scale / v_scale: ``[B, S, H_kv, 1]`` f32 per-(token, head) scales.
      pos_offset / return_lse: as on :func:`flash_decode` (the sharded-
      cache partial-softmax contract composes with quantization).

    Returns ``[B, 1, H, D]`` in ``q.dtype`` (plus ``[B, H]`` lse when
    requested).  Decode streams ~half the cache bytes of the bf16 path
    (scales add D/4096 overhead); measured 1.12× at 8k context."""
    return _flash_decode_impl(
        q, k_cache_q8, k_scale, v_cache_q8, v_scale, cache_len,
        window=window, block_k=block_k, interpret=interpret,
        pos_offset=pos_offset, return_lse=return_lse)


def sp_flash_decode(
    q: jnp.ndarray,
    k_shard: jnp.ndarray,
    v_shard: jnp.ndarray,
    cache_len: jnp.ndarray | int,
    axis_name: str,
    *,
    window: int | None = None,
    block_k: int = 1024,
    interpret: bool | None = None,
    packed_kv_heads: int | None = None,
) -> jnp.ndarray:
    """Sequence-parallel flash decode: the KV cache's SEQUENCE dim is
    sharded over ``axis_name`` (shard i owns global slots
    ``[i·S_loc, (i+1)·S_loc)``); each shard runs :func:`flash_decode` on
    its slice with GLOBAL masking, then partial softmaxes merge with the
    log-sum-exp identity — one tiny ``[B, H]`` all-gather plus one psum
    of the output, no cache movement (the "flash decoding" parallelism,
    decode-side twin of ring attention's training split).

    Call inside a ``shard_map`` over ``axis_name`` with q replicated and
    k/v sequence-sharded (4-D per-head, or packed 3-D with
    ``packed_kv_heads``).  Returns the replicated ``[B, 1, H, D]``.
    """
    from jax import lax

    i = lax.axis_index(axis_name)
    s_loc = k_shard.shape[1]
    out, lse = flash_decode(
        q, k_shard, v_shard, cache_len, window=window, block_k=block_k,
        interpret=interpret, pos_offset=i * s_loc, return_lse=True,
        packed_kv_heads=packed_kv_heads)
    all_lse = lax.all_gather(lse, axis_name)             # [n, B, H]
    new_lse = jax.nn.logsumexp(all_lse, axis=0)          # [B, H]
    w = jnp.exp(lse - new_lse)
    return lax.psum(
        out.astype(jnp.float32) * w[:, None, :, None], axis_name
    ).astype(q.dtype)
