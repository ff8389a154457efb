"""Pallas TPU flash-attention kernel.

The hot op of the transformer workload, written as a fused Pallas kernel so
the [S, S] score matrix never exists in HBM: inputs are fused to a
[B·H, S, D] layout and per (batch·head, q-block) program, K/V stream
through VMEM in ``block_k`` tiles with the online-softmax recurrence, and
only the [S, D] output (plus the [S] log-sum-exp row statistics for the
backward pass) is written back.  This is the
single-chip counterpart of the cross-chip recurrence in
:func:`tpudist.parallel.ring_attention_fn` — same math, the ring rotates
blocks over ICI while this kernel rotates them through VMEM.

Matmuls hit the MXU with float32 accumulation (``preferred_element_type``);
statistics (row max / row sum) stay in 2-D [block_q, 1] layout to respect
the (8, 128) sublane×lane tiling.  Causal grid steps strictly above the
diagonal are skipped under ``pl.when`` — their K/V tiles are fetched by the
grid pipeline but no FLOPs run.

Training: :func:`flash_attention` carries a ``custom_vjp`` — the forward is
the fused kernel; the backward is two more Pallas kernels (a dQ pass and a
dK/dV pass) that recompute P block-by-block from the saved (q, k, v, lse)
with the standard dS = P ∘ (dO·Vᵀ − rowsum(dO ∘ O)) identities, so the
[S, S] score matrix never exists in HBM in either direction and training
memory stays linear in sequence length.  The per-row
Δ = rowsum(dO ∘ O) is an O(S·D) elementwise reduction left to XLA.  The
forward rule names the kernel's output and log-sum-exp
(:data:`FLASH_RESIDUALS`), so a ``jax.checkpoint`` policy can keep them:
under ``TransformerLM(remat=True)`` the backward pass recomputes a block
without a second run of the forward kernel.

On CPU (tests, CI) the kernel runs in interpreter mode automatically;
numerics match :func:`tpudist.models.sdpa` to float tolerance either way.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_NEG_BIG = -1e30
# Sentinel distinguishing "caller didn't pass window" (factory default
# applies) from an explicit window=None (full causal attention) — so a
# model config's attention_window always overrides the factory's.
_UNSET = object()
# The two residuals only the forward kernel can give the backward pass,
# named so that a ``jax.checkpoint`` policy can keep them, as
# ``TransformerLM(remat=True)`` does.
FLASH_OUT_NAME = "flash_out"
FLASH_LSE_NAME = "flash_lse"
FLASH_RESIDUALS = (FLASH_OUT_NAME, FLASH_LSE_NAME)


def _can_prune(window, causal, q_offset, k_offset) -> bool:
    """Whether the sliding-window band grids may be pruned: static zero
    offsets only (the ring path has traced offsets).  ONE definition so
    forward and backward can never prune differently."""
    return (window is not None and causal
            and isinstance(q_offset, int) and q_offset == 0
            and isinstance(k_offset, int) and k_offset == 0)


def _block_live(qi, kj, block_q: int, block_k: int, causal: bool, q0, k0,
                window: int | None = None):
    """Whether (q-block ``qi``, k-block ``kj``) can contribute: intersects
    the causal lower triangle AND (for sliding-window attention) the band
    ``q_pos - k_pos < window``.  ``True`` when not causal.  ``q0``/``k0``
    are global position offsets (ring attention rotates K/V blocks, so a
    block's global span is offset + local index).  Shared by the forward
    and both backward kernels so a masking change cannot desynchronize
    them."""
    if not causal:
        return True
    live = q0 + (qi + 1) * block_q > k0 + kj * block_k
    if window is not None:
        # k block's last position must reach past the window's left edge
        # of the q block's first position
        live = jnp.logical_and(
            live,
            k0 + (kj + 1) * block_k > q0 + qi * block_q - (window - 1))
    return live


def _causal_mask(s, qi, kj, block_q: int, block_k: int, q0, k0,
                 window: int | None = None):
    """Mask scores above the (global) diagonal — and, with ``window``,
    older than the sliding window — to -inf within a tile."""
    q_pos = q0 + qi * block_q + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 0)
    k_pos = k0 + kj * block_k + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 1)
    keep = q_pos >= k_pos
    if window is not None:
        keep = jnp.logical_and(keep, q_pos - k_pos < window)
    return jnp.where(keep, s, -jnp.inf)


def _band_k(window: int, block_q: int, block_k: int, num_kb: int):
    """K-block span and per-q-block start for a pruned sliding-window
    grid: q block ``qi`` only visits k blocks overlapping its band
    ``[qi·bq − (window−1), (qi+1)·bq − 1]`` (width bq + window − 1)."""
    span = min(num_kb, (block_q + window - 2) // block_k + 2)

    def start(qi):
        return jnp.clip(
            (qi * block_q - (window - 1)) // block_k, 0, num_kb - span)

    return span, start


def _band_q(window: int, block_q: int, block_k: int, num_qb: int):
    """Q-block span and per-k-block start for the pruned dK/dV grid."""
    span = min(num_qb, (block_k + window - 2) // block_q + 2)

    def start(kj):
        return jnp.clip((kj * block_k) // block_q, 0, num_qb - span)

    return span, start


def _fuse(x):
    """[B, S, H, D] → [B·H, S, D]: every block's minor dims become
    (seq_block, D), the (8, 128)-tileable shape Mosaic requires."""
    b, s, h, d = x.shape
    return x.swapaxes(1, 2).reshape(b * h, s, d)


def _unfuse(x, b: int, h: int):
    """[B·H, S, D] → [B, S, H, D] (inverse of :func:`_fuse`)."""
    bh, s, d = x.shape
    return x.reshape(b, h, s, d).swapaxes(1, 2)


def _flash_kernel(off_ref, q_ref, k_ref, v_ref, o_ref, lse_ref,
                  m_scr, l_scr, acc_scr,
                  *, scale: float, causal: bool, block_q: int, block_k: int,
                  num_kb: int, window: int | None = None,
                  prune: bool = False, total_kb: int | None = None):
    """One (batch·head, q-block, k-block) grid step on the fused
    [B·H, S, D] layout.

    The K grid dimension is innermost and sequential on TPU, so the VMEM
    scratch accumulators (running max / sum / weighted values) carry the
    online-softmax state across K steps while only one [block_k, D] K/V
    tile is resident at a time.
    """
    qi, j = pl.program_id(1), pl.program_id(2)
    if prune:  # pruned windowed grid: j indexes the band, not all of K
        kj = _band_k(window, block_q, block_k, total_kb)[1](qi) + j
    else:
        kj = j
    q0, k0 = off_ref[0, 0], off_ref[0, 1]

    @pl.when(j == 0)
    def _init():
        m_scr[:] = jnp.full_like(m_scr, _NEG_BIG)
        l_scr[:] = jnp.zeros_like(l_scr)
        acc_scr[:] = jnp.zeros_like(acc_scr)

    # Causal: q-blocks strictly above the diagonal contribute nothing;
    # with a sliding window, blocks left of the band are dead too.
    @pl.when(_block_live(qi, kj, block_q, block_k, causal, q0, k0, window))
    def _compute():
        # Matmuls run in the input dtype (bf16 hits the MXU at full rate)
        # with float32 accumulation; only the softmax math is f32.
        q, kb, vb = q_ref[0], k_ref[0], v_ref[0]               # [bq|bk, D]
        s = jax.lax.dot_general(
            q, kb, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale
        if causal:
            s = _causal_mask(s, qi, kj, block_q, block_k, q0, k0, window)
        m = m_scr[:]                                           # [bq, 1]
        blk_max = jnp.max(s, axis=-1, keepdims=True)
        new_m = jnp.maximum(m, jnp.maximum(blk_max, _NEG_BIG))
        p = jnp.exp(s - new_m)                                 # masked → 0
        corr = jnp.exp(m - new_m)
        m_scr[:] = new_m
        l_scr[:] = l_scr[:] * corr + jnp.sum(p, axis=-1, keepdims=True)
        acc_scr[:] = acc_scr[:] * corr + jnp.dot(
            p.astype(vb.dtype), vb, preferred_element_type=jnp.float32)

    @pl.when(j == num_kb - 1)
    def _finalize():
        l = jnp.maximum(l_scr[:], 1e-30)
        o_ref[0] = (acc_scr[:] / l).astype(o_ref.dtype)
        lse_ref[0] = (m_scr[:] + jnp.log(l)).T  # [1, bq]


def _offsets_arg(q_offset, k_offset):
    """(1, 2) int32 SMEM operand carrying the global position offsets;
    zeros in the plain (non-ring) path."""
    return jnp.stack(
        [jnp.asarray(q_offset, jnp.int32), jnp.asarray(k_offset, jnp.int32)]
    ).reshape(1, 2)


def _smem_spec():
    return pl.BlockSpec(memory_space=pltpu.SMEM)


def _flash_forward(q, k, v, causal, block_q, block_k, interpret,
                   q_offset=0, k_offset=0, window=None, scale=None):
    """[B, S, H, D] in; internally runs on a fused [B·H, S, D] layout so
    every block's minor two dims are (seq_block, D) — the (8, 128)-tileable
    shape Mosaic requires (an [.., S, H, ..] block with a size-1 H slice is
    not lowerable on real TPUs).  ``q_offset``/``k_offset`` shift the causal
    mask to global positions (ring attention).  ``v`` may be narrower or
    wider than ``q`` / ``k`` (latent attention: q/k 192, v 128): the output
    has ``v``'s width.  ``scale`` defaults to ``D ** -0.5``."""
    b, s, h, d = q.shape
    dv = v.shape[-1]
    sk, h_kv = k.shape[1], k.shape[2]
    group = h // h_kv
    num_kb = sk // block_k
    q3, k3, v3 = (_fuse(x) for x in (q, k, v))

    def kv_head(g):
        # Grouped-query attention: query head h attends KV head h // group
        # — resolved in the index map, so grouped K/V are never expanded.
        return (g // h) * h_kv + (g % h) // group

    # Sliding window on the plain (non-ring) path: prune the K grid to the
    # band so iterations AND K/V tile traffic scale with S·window, not S².
    prune = _can_prune(window, causal, q_offset, k_offset)
    if prune:
        span_k, k_start = _band_k(window, block_q, block_k, num_kb)
        kv_idx = lambda g, i, j: (kv_head(g), k_start(i) + j, 0)
    else:
        span_k = num_kb
        kv_idx = lambda g, i, j: (kv_head(g), j, 0)

    kernel = functools.partial(
        _flash_kernel, scale=d ** -0.5 if scale is None else scale,
        causal=causal,
        block_q=block_q, block_k=block_k, num_kb=span_k, window=window,
        prune=prune, total_kb=num_kb)
    out, lse = pl.pallas_call(
        kernel,
        grid=(b * h, s // block_q, span_k),
        in_specs=[
            _smem_spec(),
            pl.BlockSpec((1, block_q, d), lambda g, i, j: (g, i, 0)),
            pl.BlockSpec((1, block_k, d), kv_idx),
            pl.BlockSpec((1, block_k, dv), kv_idx),
        ],
        out_specs=[
            pl.BlockSpec((1, block_q, dv), lambda g, i, j: (g, i, 0)),
            pl.BlockSpec((1, 1, block_q), lambda g, i, j: (g, 0, i)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b * h, s, dv), q.dtype),
            jax.ShapeDtypeStruct((b * h, 1, s), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, dv), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
        name="flash_fwd",
    )(_offsets_arg(q_offset, k_offset), q3, k3, v3)
    return _unfuse(out, b, h), lse.reshape(b, h, s)


def _flash_chosen_kernel(off_ref, q_ref, k_ref, v_ref, mask_ref, o_ref,
                         m_scr, l_scr, acc_scr, *, scale: float,
                         block_q: int, block_k: int, num_kb: int):
    """:func:`_flash_kernel`'s causal step with a per-(query, row) mask on
    top: a row enters a query's softmax where ``mask_ref`` is not 0.
    ``off_ref`` (scalar prefetch) holds the queries' global offset; K
    blocks wholly above the diagonal are skipped (the index maps hold
    their copies at the last live block)."""
    qi, kj = pl.program_id(1), pl.program_id(2)
    q0 = off_ref[0]

    @pl.when(kj == 0)
    def _init():
        m_scr[:] = jnp.full_like(m_scr, _NEG_BIG)
        l_scr[:] = jnp.zeros_like(l_scr)
        acc_scr[:] = jnp.zeros_like(acc_scr)

    @pl.when(_block_live(qi, kj, block_q, block_k, True, q0, 0))
    def _compute():
        q, kb, vb = q_ref[0], k_ref[0], v_ref[0]
        s = jax.lax.dot_general(
            q, kb, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale
        s = _causal_mask(s, qi, kj, block_q, block_k, q0, 0)
        s = jnp.where(mask_ref[...].astype(jnp.int32) != 0, s, -jnp.inf)
        m = m_scr[:]
        blk_max = jnp.max(s, axis=-1, keepdims=True)
        new_m = jnp.maximum(m, jnp.maximum(blk_max, _NEG_BIG))
        p = jnp.exp(s - new_m)
        corr = jnp.exp(m - new_m)
        m_scr[:] = new_m
        l_scr[:] = l_scr[:] * corr + jnp.sum(p, axis=-1, keepdims=True)
        acc_scr[:] = acc_scr[:] * corr + jnp.dot(
            p.astype(vb.dtype), vb, preferred_element_type=jnp.float32)

    @pl.when(kj == num_kb - 1)
    def _finalize():
        l = jnp.maximum(l_scr[:], 1e-30)
        o_ref[0] = (acc_scr[:] / l).astype(o_ref.dtype)


def flash_chosen_rows(q, k, v, mask, q_offset, *, scale=None,
                      block_q: int | None = None,
                      block_k: int | None = None,
                      interpret: bool | None = None):
    """Causal grouped-query attention of a block of queries over the rows
    a mask names: ``q [1, S, H, D]`` at global positions ``q_offset + [0,
    S)`` against ``k`` / ``v [1, R, Hkv, D]`` (a batch-1 cache, row = its
    position), a row attended where ``mask [S, R]`` (bool or int8; read as int8) is not 0 and
    the row is not after the query.  A prefill chunk's attention over the
    rows an indexer chose: a dense flash pass whose work follows the rows
    at or below the chunk (blocks above the diagonal are neither computed
    nor copied), not a gather of ``S x k`` rows.  Returns ``[1, S, H,
    D]``.  In a trace the kernel is ``sparse_gqa_prefill``."""
    b, s, h, d = q.shape
    sk, h_kv = k.shape[1], k.shape[2]
    if b != 1 or mask.shape != (s, sk) or h % h_kv:
        raise ValueError(
            f"q [1, S, H, D], k / v [1, R, Hkv, D] and mask [S, R] needed; "
            f"got {q.shape}, {k.shape}, {mask.shape}")
    group = h // h_kv
    block_q = block_q or _auto_block(s)
    block_k = block_k or _auto_block(sk, 512)
    if interpret is None:
        interpret = jax.default_backend() == "cpu"
    num_kb = sk // block_k
    q3, k3, v3 = (_fuse(x) for x in (q, k, v))

    def last_live(i, off):
        return jnp.minimum((off[0] + (i + 1) * block_q - 1) // block_k,
                           num_kb - 1)

    kv_idx = lambda g, i, j, off: (  # noqa: E731
        g // group, jnp.minimum(j, last_live(i, off)), 0)
    out = pl.pallas_call(
        functools.partial(
            _flash_chosen_kernel,
            scale=d ** -0.5 if scale is None else scale, block_q=block_q,
            block_k=block_k, num_kb=num_kb),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(h, s // block_q, num_kb),
            in_specs=[
                pl.BlockSpec((1, block_q, d),
                             lambda g, i, j, off: (g, i, 0)),
                pl.BlockSpec((1, block_k, d), kv_idx),
                pl.BlockSpec((1, block_k, d), kv_idx),
                pl.BlockSpec((block_q, block_k),
                             lambda g, i, j, off: (
                                 i, jnp.minimum(j, last_live(i, off)))),
            ],
            out_specs=pl.BlockSpec((1, block_q, d),
                                   lambda g, i, j, off: (g, i, 0)),
            scratch_shapes=[
                pltpu.VMEM((block_q, 1), jnp.float32),
                pltpu.VMEM((block_q, 1), jnp.float32),
                pltpu.VMEM((block_q, d), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((h, s, d), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=bool(interpret),
        name="sparse_gqa_prefill",
    )(jnp.asarray(q_offset, jnp.int32).reshape(1), q3, k3, v3,
      mask.astype(jnp.int8))
    return _unfuse(out, 1, h)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7))
def _flash(q, k, v, causal, block_q, block_k, interpret, window):
    out, _ = _flash_forward(q, k, v, causal, block_q, block_k, interpret,
                            window=window)
    return out


def _flash_fwd(q, k, v, causal, block_q, block_k, interpret, window):
    out, lse = _flash_forward(q, k, v, causal, block_q, block_k, interpret,
                              window=window)
    # The TAGGED values are both the primal output and the residuals, so
    # nothing downstream reads the kernel's untagged outputs: under a
    # policy that saves these names the recomputation's forward kernel has
    # no consumer and is dropped.  q, k, v stay untagged (cheap to redo).
    out = checkpoint_name(out, FLASH_OUT_NAME)
    lse = checkpoint_name(lse, FLASH_LSE_NAME)
    return out, (q, k, v, out, lse)


def _bwd_block(q, kb, vb, do, lse_col, delta_col, qi, kj, q0, k0, *,
               scale, causal, block_q, block_k, window=None):
    """Shared per-(q-block, k-block) backward math: recompute P from the
    saved log-sum-exp, then ds = P ∘ (dO·Vᵀ − Δ).  Returns (p, ds) in
    float32; callers contract them onto the MXU in the input dtype."""
    s = jax.lax.dot_general(
        q, kb, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32) * scale
    if causal:
        s = _causal_mask(s, qi, kj, block_q, block_k, q0, k0, window)
    p = jnp.exp(s - lse_col)                               # masked → 0
    dp = jax.lax.dot_general(
        do, vb, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)
    ds = p * (dp - delta_col)
    return p, ds


def _flash_bwd_dq_kernel(off_ref, q_ref, k_ref, v_ref, do_ref, lse_ref,
                         delta_ref, dq_ref, dq_scr, *, scale: float,
                         causal: bool, block_q: int, block_k: int,
                         num_kb: int, window: int | None = None,
                         prune: bool = False, total_kb: int | None = None):
    """Grid (B·H, q-block, k-block); K innermost/sequential accumulates
    dQ = scale · Σ_k dS·K in a VMEM scratch."""
    qi, j = pl.program_id(1), pl.program_id(2)
    if prune:
        kj = _band_k(window, block_q, block_k, total_kb)[1](qi) + j
    else:
        kj = j
    q0, k0 = off_ref[0, 0], off_ref[0, 1]

    @pl.when(j == 0)
    def _init():
        dq_scr[:] = jnp.zeros_like(dq_scr)

    @pl.when(_block_live(qi, kj, block_q, block_k, causal, q0, k0, window))
    def _compute():
        q, kb, vb, do = q_ref[0], k_ref[0], v_ref[0], do_ref[0]
        _, ds = _bwd_block(
            q, kb, vb, do, lse_ref[0].T, delta_ref[0].T, qi, kj, q0, k0,
            scale=scale, causal=causal, block_q=block_q, block_k=block_k,
            window=window)
        dq_scr[:] += jax.lax.dot_general(
            ds.astype(q.dtype), kb, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32) * scale

    @pl.when(j == num_kb - 1)
    def _finalize():
        dq_ref[0] = dq_scr[:].astype(dq_ref.dtype)


def _flash_bwd_dq_fused_kernel(off_ref, q_ref, k_ref, v_ref, do_ref,
                               lse_ref, o_ref, dq_ref, delta_out_ref,
                               dq_scr, delta_scr, *, scale: float,
                               causal: bool, block_q: int, block_k: int,
                               num_kb: int, window: int | None = None,
                               prune: bool = False,
                               total_kb: int | None = None):
    """The dQ pass with the Δ = rowsum(dO ∘ O) prepass FUSED in: at the
    first K step of each q-block, Δ is computed in VMEM from the already-
    resident dO and O tiles (one extra [bq, D] read, amortized over the
    whole K loop) and emitted as a side output for the dK/dV pass — the
    separate XLA elementwise pass over O/dO and its HBM round-trip
    disappear (round-1 verdict #3)."""
    qi, j = pl.program_id(1), pl.program_id(2)
    if prune:
        kj = _band_k(window, block_q, block_k, total_kb)[1](qi) + j
    else:
        kj = j
    q0, k0 = off_ref[0, 0], off_ref[0, 1]

    @pl.when(j == 0)
    def _init():
        dq_scr[:] = jnp.zeros_like(dq_scr)
        delta_scr[:] = jnp.sum(
            do_ref[0].astype(jnp.float32) * o_ref[0].astype(jnp.float32),
            axis=-1, keepdims=True)

    @pl.when(_block_live(qi, kj, block_q, block_k, causal, q0, k0, window))
    def _compute():
        q, kb, vb, do = q_ref[0], k_ref[0], v_ref[0], do_ref[0]
        _, ds = _bwd_block(
            q, kb, vb, do, lse_ref[0].T, delta_scr[:], qi, kj, q0, k0,
            scale=scale, causal=causal, block_q=block_q, block_k=block_k,
            window=window)
        dq_scr[:] += jax.lax.dot_general(
            ds.astype(q.dtype), kb, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32) * scale

    @pl.when(j == num_kb - 1)
    def _finalize():
        dq_ref[0] = dq_scr[:].astype(dq_ref.dtype)
        delta_out_ref[0] = delta_scr[:].T  # [1, bq] row layout, like lse


def _flash_bwd_dkv_kernel(off_ref, q_ref, k_ref, v_ref, do_ref, lse_ref,
                          delta_ref, dk_ref, dv_ref, dk_scr, dv_scr, *,
                          scale: float, causal: bool, block_q: int,
                          block_k: int, num_q_iters: int, group: int,
                          window: int | None = None, prune: bool = False,
                          total_qb: int | None = None):
    """Grid (B·Hkv, k-block, q-block × group-member); the innermost
    sequential dimension walks every (q-block, query-head-of-the-group)
    pair, accumulating dK = scale · Σ dSᵀ·Q and dV = Σ Pᵀ·dO in VMEM —
    under GQA each KV head's grads sum over its whole query-head group
    here, with no cross-program races and no K/V expansion."""
    kj, t = pl.program_id(1), pl.program_id(2)
    qi = t // group
    if prune:
        qi = _band_q(window, block_q, block_k, total_qb)[1](kj) + qi
    q0, k0 = off_ref[0, 0], off_ref[0, 1]

    @pl.when(t == 0)
    def _init():
        dk_scr[:] = jnp.zeros_like(dk_scr)
        dv_scr[:] = jnp.zeros_like(dv_scr)

    @pl.when(_block_live(qi, kj, block_q, block_k, causal, q0, k0, window))
    def _compute():
        q, kb, vb, do = q_ref[0], k_ref[0], v_ref[0], do_ref[0]
        p, ds = _bwd_block(
            q, kb, vb, do, lse_ref[0].T, delta_ref[0].T, qi, kj, q0, k0,
            scale=scale, causal=causal, block_q=block_q, block_k=block_k,
            window=window)
        dv_scr[:] += jax.lax.dot_general(
            p.astype(do.dtype), do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        dk_scr[:] += jax.lax.dot_general(
            ds.astype(q.dtype), q, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32) * scale

    @pl.when(t == num_q_iters - 1)
    def _finalize():
        dk_ref[0] = dk_scr[:].astype(dk_ref.dtype)
        dv_ref[0] = dv_scr[:].astype(dv_ref.dtype)


def flash_block_grads(q, k, v, dout, lse, delta, *, causal, block_q,
                      block_k, interpret, q_offset=0, k_offset=0,
                      window=None, out=None):
    """(dQ, dK, dV) of one attention block given the FINAL softmax
    statistics ``lse``/``delta`` (shapes [B, H, S]).

    With ``delta=None`` (the plain, non-ring path) Δ is not precomputed:
    the dQ kernel derives it from ``out``/``dout`` tiles in VMEM and emits
    it for the dK/dV pass — no separate elementwise pass, no Δ HBM write
    from XLA.  The ring backward passes an explicit Δ because its identity
    must come from the FINAL output across all blocks
    (`parallel/ring_attention.py`).

    The flash backward identities hold per K/V block when P is computed
    against the final log-sum-exp, which is what makes the ring backward a
    sum of per-block kernel calls; the plain backward below is the
    single-block case with zero offsets.
    """
    b, s, h, d = q.shape
    sk, h_kv = k.shape[1], k.shape[2]
    group = h // h_kv
    scale = d ** -0.5
    num_qb, num_kb = s // block_q, sk // block_k
    q3, k3, v3, do3 = (_fuse(x) for x in (q, k, v, dout))
    lse3 = lse.reshape(b * h, 1, s)
    fuse_delta = delta is None
    if fuse_delta:
        if out is None:
            raise ValueError("flash_block_grads needs `out` when delta=None")
        o3 = _fuse(out)
    else:
        delta3 = delta.reshape(b * h, 1, s)

    def kv_head(g):
        return (g // h) * h_kv + (g % h) // group

    def q_head(g, t):
        # dK/dV grid runs per KV head; member t % group selects which of
        # its query heads this inner step contracts.
        return (g // h_kv) * h + (g % h_kv) * group + t % group

    prune = _can_prune(window, causal, q_offset, k_offset)
    if prune:
        span_k, k_start = _band_k(window, block_q, block_k, num_kb)
        span_q, q_start = _band_q(window, block_q, block_k, num_qb)
    else:
        span_k, k_start = num_kb, (lambda i: 0)
        span_q, q_start = num_qb, (lambda j: 0)

    def qi_of(j, t):
        return q_start(j) + t // group

    q_spec = pl.BlockSpec((1, block_q, d), lambda g, i, j: (g, i, 0))
    row_spec = pl.BlockSpec((1, 1, block_q), lambda g, i, j: (g, 0, i))
    kv_spec = pl.BlockSpec((1, block_k, d),
                           lambda g, i, j: (kv_head(g), k_start(i) + j, 0))
    # dK/dV pass walks the transposed grid: KV-head programs, k-block
    # major, (q-block × group-member) minor.
    q_spec_t = pl.BlockSpec((1, block_q, d),
                            lambda g, j, t: (q_head(g, t), qi_of(j, t), 0))
    row_spec_t = pl.BlockSpec((1, 1, block_q),
                              lambda g, j, t: (q_head(g, t), 0, qi_of(j, t)))
    kv_spec_t = pl.BlockSpec((1, block_k, d), lambda g, j, t: (g, j, 0))
    semantics = pltpu.CompilerParams(
        dimension_semantics=("parallel", "parallel", "arbitrary"))

    offs = _offsets_arg(q_offset, k_offset)
    if fuse_delta:
        dq, delta3 = pl.pallas_call(
            functools.partial(
                _flash_bwd_dq_fused_kernel, scale=scale, causal=causal,
                block_q=block_q, block_k=block_k, num_kb=span_k,
                window=window, prune=prune, total_kb=num_kb),
            grid=(b * h, num_qb, span_k),
            in_specs=[_smem_spec(), q_spec, kv_spec, kv_spec, q_spec,
                      row_spec, q_spec],
            out_specs=[q_spec, row_spec],
            out_shape=[
                jax.ShapeDtypeStruct((b * h, s, d), q.dtype),
                jax.ShapeDtypeStruct((b * h, 1, s), jnp.float32),
            ],
            scratch_shapes=[
                pltpu.VMEM((block_q, d), jnp.float32),
                pltpu.VMEM((block_q, 1), jnp.float32),
            ],
            compiler_params=semantics,
            interpret=interpret,
            name="flash_bwd_dq",
        )(offs, q3, k3, v3, do3, lse3, o3)
    else:
        dq = pl.pallas_call(
            functools.partial(
                _flash_bwd_dq_kernel, scale=scale, causal=causal,
                block_q=block_q, block_k=block_k, num_kb=span_k,
                window=window, prune=prune, total_kb=num_kb),
            grid=(b * h, num_qb, span_k),
            in_specs=[_smem_spec(), q_spec, kv_spec, kv_spec, q_spec,
                      row_spec, row_spec],
            out_specs=[q_spec],
            out_shape=[jax.ShapeDtypeStruct((b * h, s, d), q.dtype)],
            scratch_shapes=[pltpu.VMEM((block_q, d), jnp.float32)],
            compiler_params=semantics,
            interpret=interpret,
            name="flash_bwd_dq",
        )(offs, q3, k3, v3, do3, lse3, delta3)[0]

    dk, dv = pl.pallas_call(
        functools.partial(
            _flash_bwd_dkv_kernel, scale=scale, causal=causal,
            block_q=block_q, block_k=block_k,
            num_q_iters=span_q * group, group=group, window=window,
            prune=prune, total_qb=num_qb),
        grid=(b * h_kv, num_kb, span_q * group),
        in_specs=[_smem_spec(), q_spec_t, kv_spec_t, kv_spec_t, q_spec_t,
                  row_spec_t, row_spec_t],
        out_specs=[kv_spec_t, kv_spec_t],
        out_shape=[
            jax.ShapeDtypeStruct((b * h_kv, sk, d), k.dtype),
            jax.ShapeDtypeStruct((b * h_kv, sk, d), v.dtype),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_k, d), jnp.float32),
            pltpu.VMEM((block_k, d), jnp.float32),
        ],
        compiler_params=semantics,
        interpret=interpret,
        name="flash_bwd_dkv",
    )(offs, q3, k3, v3, do3, lse3, delta3)

    return _unfuse(dq, b, h), _unfuse(dk, b, h_kv), _unfuse(dv, b, h_kv)


def flash_delta(out, dout):
    """Δ = rowsum(dO ∘ O) per query position, as [B, H, S] float32."""
    return jnp.sum(
        out.astype(jnp.float32) * dout.astype(jnp.float32), axis=-1
    ).transpose(0, 2, 1)


def _flash_bwd(causal, block_q, block_k, interpret, window, res, dout):
    q, k, v, out, lse = res
    return flash_block_grads(
        q, k, v, dout, lse, None, out=out,
        causal=causal, block_q=block_q, block_k=block_k, interpret=interpret,
        window=window)


_flash.defvjp(_flash_fwd, _flash_bwd)


def _auto_block(s: int, cap: int = 1024) -> int:
    """Largest power-of-two ≤ ``cap`` dividing ``s`` (≥ 8 when possible).

    Measured on real TPU at S=2048/8192: 1024-sized blocks run ~1.6× the
    throughput of 128-sized ones (fewer grid steps, larger MXU matmuls),
    so the default block is as big as divisibility allows.
    """
    b = 1
    while b < cap and s % (b * 2) == 0:
        b *= 2
    return b


def flash_attention(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    *,
    causal: bool = True,
    block_q: int | None = None,
    block_k: int | None = None,
    interpret: bool | None = None,
    window: int | None = None,
) -> jnp.ndarray:
    """Fused attention on [B, S, H, D] arrays; drop-in for
    :func:`tpudist.models.sdpa` (same ``AttentionFn`` contract),
    differentiable via ``custom_vjp``.  Block sizes default to the largest
    power-of-two divisor of S up to 1024 (the measured sweet spot).

    ``window`` enables sliding-window attention (Mistral-style): each
    query attends only the last ``window`` positions (itself included).
    Requires ``causal=True``; blocks wholly left of the band are skipped,
    so FLOPs scale with S·window instead of S².  K/V may carry fewer
    (grouped) heads — GQA."""
    s = q.shape[1]
    if q.shape[2] % k.shape[2]:
        raise ValueError(
            f"num_heads {q.shape[2]} must be a multiple of kv heads "
            f"{k.shape[2]} (GQA)")
    block_q = _auto_block(s) if block_q is None else min(block_q, s)
    block_k = _auto_block(s) if block_k is None else min(block_k, s)
    if s % block_q or s % block_k:
        raise ValueError(f"block sizes ({block_q}, {block_k}) must divide "
                         f"seq_len {s}")
    if window is not None and (not causal or window < 1):
        raise ValueError(
            f"window={window} requires causal=True and window >= 1")
    if interpret is None:
        interpret = jax.default_backend() == "cpu"
    return _flash(q, k, v, causal, block_q, block_k, interpret, window)


def flash_attention_fn(
    block_q: int | None = None, block_k: int | None = None,
    interpret: bool | None = None, window: int | None = None,
):
    """``AttentionFn`` factory for :class:`tpudist.models.TransformerLM`:
    ``TransformerLM(cfg, attention_fn=flash_attention_fn())``."""
    factory_window = window

    def attend(q, k, v, *, causal: bool = True, window=_UNSET):
        eff = factory_window if window is _UNSET else window
        return flash_attention(q, k, v, causal=causal, block_q=block_q,
                               block_k=block_k, interpret=interpret,
                               window=eff)

    # Discoverable by TransformerLM: a model whose cfg.attention_window
    # disagrees with this must fail loudly instead of silently training
    # full-attention against a windowed decode cache (or vice versa).
    attend.factory_window = factory_window
    return attend
