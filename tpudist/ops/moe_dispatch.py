"""Fused grouped-matmul kernel for MoE expert MLPs (megablocks-style).

Round-4 measured decomposition of the ragged MoE layer (4096 tokens,
E=8, top-2, d=512, f=2048 on a v5e): the XLA glue is NOT the main cost
once the counting sort is lane-major — the two ``jax.lax.ragged_dot``
calls themselves run at ~1.7× the equal-FLOP dense twin with real
(imbalanced) groups, and they round-trip the [T·k, f] intermediate
through HBM between them (~33 MB each way).  This kernel runs BOTH
expert matmuls in one ``pallas_call`` over block-aligned groups:

* the dispatch layout pads each expert's group start to the row-block
  size, so every [bn, d] input block belongs to EXACTLY one expert —
  the per-block expert id rides scalar-prefetch meta and selects the
  w_up/w_down blocks via their index maps (consecutive blocks of one
  expert keep the weights resident);
* ``h = gelu(xs @ w_up[e])`` stays in VMEM and feeds ``h @ w_down[e]``
  directly — the intermediate never touches HBM;
* the gate weight is folded into the epilogue (``y_slot *= gate_slot``),
  so the combine outside is a pure gather + k-sum.

A previous round-4 design absorbed the row GATHER into this kernel via
per-row async DMA; Mosaic rejects it (VMEM slices must be 8-sublane
aligned — single-row ``memref_slice`` of a [T, d] ref does not lower),
which is why TPU grouped-matmul kernels in the wild take pre-sorted
contiguous inputs.  The gather stays in XLA, where it measures a benign
~37 µs for 8192×512 bf16 rows.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from tpudist.obs.spans import routine


def _gmm_kernel(meta_ref, xs_ref, gate_ref, w_up_ref, w_down_ref, y_ref):
    """One grid step = one [bn, d] slot block of one expert: both expert
    matmuls back to back, gate folded into the epilogue."""
    xs = xs_ref[...]                                     # [bn, d]
    h = jax.lax.dot_general(
        xs, w_up_ref[0], (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)
    h = jax.nn.gelu(h).astype(xs.dtype)                  # [bn, f] in VMEM
    y = jax.lax.dot_general(
        h, w_down_ref[0], (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)
    y_ref[...] = (y * gate_ref[...]).astype(y_ref.dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6))
def _fused_moe_diff(x, w_up, w_down, top_idx, top_vals, block_rows,
                    interpret):
    return _fused_moe_fwd_only(x, w_up, w_down, top_idx, top_vals,
                               block_rows, interpret)


def _fused_fwd(x, w_up, w_down, top_idx, top_vals, block_rows, interpret):
    out = _fused_moe_fwd_only(x, w_up, w_down, top_idx, top_vals,
                              block_rows, interpret)
    return out, (x, w_up, w_down, top_idx, top_vals)


def _fused_bwd(block_rows, interpret, res, g):
    """Backward by REMATERIALIZATION through the differentiable ragged
    path (``pallas_call`` has no autodiff rule): one extra forward's
    FLOPs in exchange for a trainable fused dispatch — same trade the
    remat'd transformer blocks make."""
    import numpy as np

    from tpudist.models.moe import _ragged_moe

    x, w_up, w_down, top_idx, top_vals = res
    _, vjp = jax.vjp(
        lambda xx, wu, wd, tv: _ragged_moe(xx, wu, wd, top_idx, tv),
        x, w_up, w_down, top_vals)
    dx, dwu, dwd, dtv = vjp(g)
    d_idx = np.zeros(top_idx.shape, dtype=jax.dtypes.float0)
    return dx, dwu, dwd, d_idx, dtv


_fused_moe_diff.defvjp(_fused_fwd, _fused_bwd)


def fused_moe_mlp(x: jnp.ndarray, w_up: jnp.ndarray, w_down: jnp.ndarray,
                  top_idx: jnp.ndarray, top_vals: jnp.ndarray,
                  *, block_rows: int = 128,
                  interpret: bool | None = None) -> jnp.ndarray:
    """MoE MLP layer through the fused grouped-matmul kernel.

    Same contract as ``tpudist.models.moe._ragged_moe``: ``x [T, d]``,
    stacked expert weights ``w_up [E, d, f]`` / ``w_down [E, f, d]``,
    router choices ``top_idx/top_vals [T, k]``; returns ``[T, d]``.
    Exact (no capacity, no dropping): group starts are padded to
    ``block_rows``; pad slots carry gate 0 and their rows are never read
    by the combine.  Differentiable: the backward rematerializes through
    the ragged XLA path (see ``_fused_bwd``).
    """
    if interpret is None:
        interpret = jax.default_backend() == "cpu"
    return _fused_moe_diff(x, w_up, w_down, top_idx, top_vals,
                           block_rows, interpret)


def _block_experts(starts, bn: int, nb: int):
    """The expert whose rows each of ``nb`` row blocks holds, from the
    block-aligned group ``starts [E]``: the last group that starts at or
    before the block (an empty group shares its successor's start)."""
    block_ids = jnp.zeros((nb,), jnp.int32).at[
        jnp.minimum(starts // bn, nb - 1)].add(1)
    return jnp.clip(jnp.cumsum(block_ids) - 1, 0, starts.shape[0] - 1)


def _fused_moe_fwd_only(x, w_up, w_down, top_idx, top_vals, block_rows,
                        interpret):
    t, d = x.shape
    e, _, f = w_up.shape
    k = top_idx.shape[1]
    n = t * k
    bn = block_rows

    # shared lane-major counting sort, block-aligned group starts
    from tpudist.models.moe import _counting_sort

    pos, order, _, starts, np_pad = _counting_sort(
        top_idx.reshape(-1), e, block_rows=bn)
    nb = np_pad // bn
    xs = x[order // k]                                    # [NP, d] sorted rows
    gate = jnp.zeros((np_pad, 1), jnp.float32).at[pos, 0].set(
        top_vals.reshape(-1).astype(jnp.float32))         # pad slots: gate 0
    block_expert = _block_experts(starts, bn, nb)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,                            # block_expert
        grid=(nb,),
        in_specs=[
            pl.BlockSpec((bn, d), lambda b, m: (b, 0)),
            pl.BlockSpec((bn, 1), lambda b, m: (b, 0)),
            pl.BlockSpec((1, d, f), lambda b, m: (m[b], 0, 0)),
            pl.BlockSpec((1, f, d), lambda b, m: (m[b], 0, 0)),
        ],
        out_specs=pl.BlockSpec((bn, d), lambda b, m: (b, 0)),
    )
    ys = pl.pallas_call(
        _gmm_kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((np_pad, d), x.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
    )(block_expert, xs, gate, w_up, w_down)

    # combine: gate already folded in-kernel — gather + sum over choices
    return jnp.sum(ys[pos].reshape(t, k, d), axis=1).astype(x.dtype)


# -- gated grouped product: the expert layer of a served model --------------

def _gg_kernel(meta_ref, x_ref, *refs, gated: bool, n_k: int):
    """One grid step = one ``[bn, tk]`` x ``[tk, tn]`` product of one
    expert's row block, accumulated over the ``k`` axis in f32 scratch;
    the epilogue of the gated form is ``silu(gate) * up``."""
    del meta_ref  # read by the index maps only
    n_w = 2 if gated else 1
    w_refs, o_ref, accs = refs[:n_w], refs[n_w], refs[n_w + 1:]
    kk = pl.program_id(2)

    @pl.when(kk == 0)
    def _zero():
        for acc in accs:
            acc[...] = jnp.zeros_like(acc)

    x = x_ref[...]
    for w_ref, acc in zip(w_refs, accs):
        acc[...] += jax.lax.dot_general(
            x, w_ref[0], (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when(kk == n_k - 1)
    def _store():
        y = accs[0][...]
        if gated:
            y = jax.nn.silu(y) * accs[1][...]
        o_ref[...] = y.astype(o_ref.dtype)


def _tiles(k: int, n: int, itemsize: int, budget: int) -> tuple[int, int]:
    """``(tk, tn)`` of a weight tile of at most about ``budget`` bytes:
    whole rows first (a tile is then one contiguous slab of the expert's
    matrix), halved while they stay multiples of 128."""
    tn = n
    while tn * 128 * itemsize > budget and tn % 256 == 0:
        tn //= 2
    tk = k
    while tk * tn * itemsize > budget and tk % 256 == 0:
        tk //= 2
    return tk, tn


def _grouped_matmul(xs, weights, block_expert, n_live, bn: int, *,
                    gated: bool, interpret: bool, name: str):
    """``xs [NP, K]`` (rows sorted by expert, every ``bn``-row block one
    expert's) times ``weights[i] [E, K, N]`` -> ``[NP, N]``; with two
    weights the gated form ``silu(xs @ w0) * (xs @ w1)``.  Only the first
    ``n_live`` row blocks are visited (a DYNAMIC grid bound): the rest of
    the output is never written and must never be read."""
    np_pad, k = xs.shape
    n = weights[0].shape[2]
    tk, tn = _tiles(k, n, xs.dtype.itemsize, (2 if gated else 4) << 20)
    n_k = k // tk
    w_spec = pl.BlockSpec((1, tk, tn), lambda b, j, kk, m: (m[b], kk, j))
    return pl.pallas_call(
        functools.partial(_gg_kernel, gated=gated, n_k=n_k),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,                        # block_expert
            grid=(n_live, n // tn, n_k),
            in_specs=[pl.BlockSpec((bn, tk), lambda b, j, kk, m: (b, kk))]
            + [w_spec] * len(weights),
            out_specs=pl.BlockSpec((bn, tn), lambda b, j, kk, m: (b, j)),
            scratch_shapes=[pltpu.VMEM((bn, tn), jnp.float32)
                            for _ in weights],
        ),
        out_shape=jax.ShapeDtypeStruct((np_pad, n), xs.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary", "arbitrary"),
            # two slots of every weight tile, the row block, the
            # accumulators: under 20 MB at the budgets above
            vmem_limit_bytes=48 << 20),
        interpret=interpret,
        name=name,
    )(block_expert, xs, *weights)


def row_block(assignments: int, num_experts: int) -> int:
    """Rows a block of the grouped product: twice the mean rows an expert
    gets when ``assignments`` spread evenly over ``num_experts``, as a
    power of two between 16 (a bf16 sublane tile) and 256.  A decode step
    of 128 lanes x 8 choices over 256 experts gives 16, where padding every
    group to 128 rows would multiply the rows by ten."""
    mean = max(1, (2 * assignments) // max(num_experts, 1))
    return min(256, max(16, 1 << (mean - 1).bit_length()))


def grouped_gated_mlp(x: jnp.ndarray, w_gate: jnp.ndarray,
                      w_up: jnp.ndarray, w_down: jnp.ndarray,
                      local_idx: jnp.ndarray, weights: jnp.ndarray, *,
                      num_experts: int | None = None,
                      interpret: bool | None = None):
    """The routed part of an expert layer over the experts HELD here:
    ``sum_i w_i * down_e(silu(gate_e x) * up_e x)`` over a token's choices
    ``e = local_idx[t, i]`` that fall in ``[0, E)``; a choice outside (an
    expert another chip holds) adds nothing.  Sorted dispatch (the shared
    counting sort) and two grouped Pallas products, ``moe_experts_gate_up``
    and ``moe_experts_down``; no capacity, no token dropped.  The row
    block follows from the number of assignments and ``num_experts`` (all
    the router routes over; default ``E``), see :func:`row_block`.

    ``x [T, d]``, ``w_gate / w_up [E, d, f]``, ``w_down [E, f, d]``,
    ``local_idx / weights [T, k]``.  Returns ``(y [T, d], counts [E])``,
    ``counts`` the tokens each held expert was given."""
    from tpudist.models.moe import _counting_sort

    if interpret is None:
        interpret = jax.default_backend() == "cpu"
    t, d = x.shape
    e = w_gate.shape[0]
    k = local_idx.shape[1]
    n = t * k
    bn = row_block(n, num_experts or e)
    with routine("mlp/route"):
        held = (local_idx >= 0) & (local_idx < e)
        # the choices held elsewhere sort into a last group no block visits
        flat_e = jnp.where(held, local_idx, e).reshape(-1)
        pos, order, sizes, starts, np_pad = _counting_sort(
            flat_e, e + 1, block_rows=bn)
        nb = np_pad // bn
        n_live = starts[e] // bn
        block_expert = _block_experts(starts[:e], bn, nb)
    with routine("mlp/experts"):
        xs = x[order // k]                              # [NP, d] sorted rows
        h = _grouped_matmul(xs, (w_gate, w_up), block_expert, n_live, bn,
                            gated=True, interpret=interpret,
                            name="moe_experts_gate_up")
        ys = _grouped_matmul(h, (w_down,), block_expert, n_live, bn,
                             gated=False, interpret=interpret,
                             name="moe_experts_down")
        # a choice held elsewhere points past the visited blocks: masked,
        # never multiplied (those rows are not written)
        y = jnp.where(held[..., None], ys[pos].reshape(t, k, d), 0)
        y = jnp.sum(y.astype(jnp.float32)
                    * weights[..., None].astype(jnp.float32), axis=1)
        return y.astype(x.dtype), sizes[:e]
