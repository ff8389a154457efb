"""The gated delta rule: a linear-attention layer's recurrence over a
matrix state a head, in the two forms a server needs.

A head keeps ``S [dk, dv]`` (float32).  A token with key ``k``, value ``v``,
query ``q``, decay ``alpha = exp(g)`` in (0, 1] and write strength ``beta``
does

    S' = alpha * S_prev
    S  = S' + beta * k (v - S'^T k)^T
    o  = S^T q

:func:`gated_delta_step` is that, once, for one token a lane: the decode
step.  A Pallas kernel named ``delta_step`` that reads the state once and
writes it once, in place.  :func:`gated_delta_chunk` carries a state across
``T`` tokens and gives their ``T`` outputs: the prefill chunk, and the
one-shot forward.  Chunkwise over sub-blocks of 64 tokens (the WY form: a
unit lower-triangular solve inside a sub-block, matrix products between
them), plain ``jax.numpy`` in float32 at the highest matmul precision,
under the routine scope ``delta_chunk``.

Both take ``valid``: a token that is not valid (a lane that is frozen,
empty or past its budget; the padded rows of a prompt's last chunk) runs
with ``alpha = 1`` and ``beta = 0`` and leaves the state as it was, bit for
bit.  Its output is whatever comes and is nobody's to read.

THE STATE'S LAYOUT.  The step takes and returns ``[B, dk, H * dv]``: the
heads' ``[dk, dv]`` matrices side by side along the minor axis.  A value
head of 192 is one and a half 128-lane tiles, so ``[B, H, dk, dv]`` would
pad every head's matrix by a third, in memory and on every read; side by
side, 30 heads of 192 are 45 whole tiles.  The chunk form takes and returns
``[B, H, dk, dv]`` (its products are a head's); :func:`state_to_heads` and
:func:`state_from_heads` go between the two.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from tpudist.obs.spans import routine

_HIGHEST = jax.lax.Precision.HIGHEST
# tokens of a sub-block of the chunk form, and of a diagonal block of its
# triangular solve (inverted row by row, then block forward substitution)
CHUNK_BLOCK = 64
_SOLVE_BLOCK = 16
# the state block of one grid step of the decode kernel: in and out, two
# slots each, stay a small part of the 16 MiB a v5e kernel gets
_STEP_BLOCK_BYTES = 1 << 20


def state_to_heads(state: jnp.ndarray, heads: int) -> jnp.ndarray:
    """``[B, dk, H * dv] -> [B, H, dk, dv]``."""
    b, dk, flat = state.shape
    return state.reshape(b, dk, heads, flat // heads).transpose(0, 2, 1, 3)


def state_from_heads(state: jnp.ndarray) -> jnp.ndarray:
    """``[B, H, dk, dv] -> [B, dk, H * dv]``."""
    b, h, dk, dv = state.shape
    return state.transpose(0, 2, 1, 3).reshape(b, dk, h * dv)


def _masked(g, beta, valid):
    """``g`` and ``beta`` of the tokens that count; 0 and 0 of the rest."""
    if valid is None:
        return g, beta
    valid = valid[..., None]
    return jnp.where(valid, g, 0.0), jnp.where(valid, beta, 0.0)


# -- the decode step ----------------------------------------------------------

def step_heads(heads: int, dk: int, dv: int) -> tuple[int, int]:
    """``(heads a grid step, heads a slab)`` of the decode kernel.  A slab
    is the fewest adjacent heads whose ``dv`` columns are whole 128-lane
    tiles (two heads of 192; one of 128): the kernel's arithmetic runs a
    slab at a time on aligned slices.  A grid step takes as many slabs as
    keep its state block under ``_STEP_BLOCK_BYTES``."""
    slab = math.lcm(dv, 128) // dv
    if heads % slab:
        slab = heads        # one slab, as wide as the array: no slice at all
    per = slab
    for n in range(slab, heads + 1, slab):
        if heads % n == 0 and n * dk * dv * 4 <= _STEP_BLOCK_BYTES:
            per = n
    return per, slab


def _step_kernel(q_ref, k_ref, v_ref, a_ref, b_ref, s_ref, o_ref, s_out, *,
                 per: int, slab: int, dv: int):
    """One lane's ``per`` heads: ``q_ref`` / ``k_ref`` ``[1, 1, dk, per]``
    (a head a COLUMN, so that it broadcasts along the state's lanes);
    ``v_ref`` / ``a_ref`` / ``b_ref`` / ``o_ref`` ``[1, 1, per * dv]`` (a
    head's ``dv`` numbers side by side, alpha and beta repeated over
    them); the state ``[1, dk, per * dv]``."""
    dk = s_ref.shape[1]
    width = slab * dv
    lane = jax.lax.broadcasted_iota(jnp.int32, (dk, width), 1)

    def columns(ref, first):
        """``[dk, width]``: head ``first + i``'s column over its lanes."""
        out = jnp.broadcast_to(ref[0, 0, :, first + slab - 1:first + slab],
                               (dk, width))
        for i in range(slab - 2, -1, -1):
            out = jnp.where(lane < (i + 1) * dv,
                            ref[0, 0, :, first + i:first + i + 1], out)
        return out

    for j in range(per // slab):
        at = slice(j * width, (j + 1) * width)
        kp, qp = columns(k_ref, j * slab), columns(q_ref, j * slab)
        decayed = s_ref[0, :, at] * a_ref[0, :, at]
        seen = jnp.sum(decayed * kp, axis=0, keepdims=True)
        delta = (v_ref[0, :, at] - seen) * b_ref[0, :, at]
        new = decayed + kp * delta
        s_out[0, :, at] = new
        o_ref[0, :, at] = jnp.sum(new * qp, axis=0, keepdims=True)


@functools.partial(jax.jit, static_argnames=("interpret",))
def _step(q, k, v, g, beta, state, *, interpret: bool):
    b, h, dk = q.shape
    dv = v.shape[-1]
    per, slab = step_heads(h, dk, dv)
    groups = h // per

    def cols(x):        # [B, H, dk] -> [B, groups, dk, per]
        return x.astype(jnp.float32).reshape(
            b, groups, per, dk).transpose(0, 1, 3, 2)

    def lanes(x):       # [B, H] -> [B, 1, H * dv]
        return jnp.repeat(x.astype(jnp.float32), dv, axis=-1)[:, None]

    col_spec = pl.BlockSpec((1, 1, dk, per), lambda i, j: (i, j, 0, 0))
    row_spec = pl.BlockSpec((1, 1, per * dv), lambda i, j: (i, 0, j))
    state_spec = pl.BlockSpec((1, dk, per * dv), lambda i, j: (i, 0, j))
    out, new = pl.pallas_call(
        functools.partial(_step_kernel, per=per, slab=slab, dv=dv),
        grid=(b, groups),
        in_specs=[col_spec, col_spec, row_spec, row_spec, row_spec,
                  state_spec],
        out_specs=[row_spec, state_spec],
        out_shape=[jax.ShapeDtypeStruct((b, 1, h * dv), jnp.float32),
                   jax.ShapeDtypeStruct(state.shape, jnp.float32)],
        input_output_aliases={5: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel")),
        interpret=interpret,
        name="delta_step",
    )(cols(q), cols(k), v.astype(jnp.float32).reshape(b, 1, h * dv),
      lanes(jnp.exp(g)), lanes(beta), state)
    return out.reshape(b, h, dv), new


def gated_delta_step(q, k, v, g, beta, state, valid=None, *,
                     interpret: bool | None = None):
    """One token a lane.  ``q``, ``k`` ``[B, H, dk]`` (normalised and
    scaled by the caller), ``v`` ``[B, H, dv]``, ``g`` (log alpha) and
    ``beta`` ``[B, H]``, ``state`` ``[B, dk, H * dv]`` float32, ``valid``
    ``[B]`` bool or None.  Returns ``(o [B, H, dv] float32, the new
    state)``; the state is updated in place where the caller donates it."""
    if interpret is None:
        interpret = jax.default_backend() == "cpu"
    g, beta = _masked(g, beta, valid)
    with routine("delta_step"):
        return _step(q, k, v, g, beta, state, interpret=bool(interpret))


# -- the chunk form -----------------------------------------------------------

def _solve_unit_lower(a, rhs):
    """``X`` of ``(I + a) X = rhs``, ``a [..., C, C]`` strictly lower
    triangular, by forward substitution: the diagonal blocks of
    ``_SOLVE_BLOCK`` rows are inverted a row at a time (every block of
    every head at once), then the block rows are substituted in order.
    Forward substitution and not the product form ``(I - a)(I + a^2)...``:
    with ``beta`` up to 2 the powers of ``a`` reach 1e17 and cancel."""
    c = a.shape[-1]
    n = min(_SOLVE_BLOCK, c)
    nb = c // n
    lead = a.shape[:-2]
    blocks = a.reshape(*lead, nb, n, nb, n)
    diag = jnp.stack([blocks[..., i, :, i, :] for i in range(nb)], -3)
    inv = jnp.broadcast_to(jnp.eye(n, dtype=a.dtype), diag.shape)
    for i in range(1, n):
        row = -jnp.einsum("...j,...jk->...k", diag[..., i, :], inv,
                          precision=_HIGHEST)
        inv = inv.at[..., i, :].set(row.at[..., i].add(1.0))
    r = rhs.reshape(*lead, nb, n, rhs.shape[-1])
    out = []
    for i in range(nb):
        acc = r[..., i, :, :]
        for j in range(i):
            acc = acc - jnp.einsum("...ij,...jk->...ik",
                                   blocks[..., i, :, j, :], out[j],
                                   precision=_HIGHEST)
        out.append(jnp.einsum("...ij,...jk->...ik", inv[..., i, :, :], acc,
                              precision=_HIGHEST))
    return jnp.concatenate(out, axis=-2)


def gated_delta_chunk(q, k, v, g, beta, state, valid=None, *,
                      block: int = CHUNK_BLOCK):
    """``T`` tokens from a carried state.  ``q``, ``k`` ``[B, T, H, dk]``
    (normalised and scaled by the caller), ``v`` ``[B, T, H, dv]``, ``g``
    (log alpha) and ``beta`` ``[B, T, H]``, ``state`` ``[B, H, dk, dv]``
    float32, ``valid`` ``[B, T]`` bool or None.  Returns ``(o [B, T, H,
    dv] float32, the state after the last token)``.

    Inside a sub-block of ``block`` tokens, with ``G`` the running sum of
    ``g``: the writes ``U`` solve ``(I + A) U = beta V - (beta e^G K) S0``,
    ``A[t, i] = beta_t e^(G_t - G_i) k_t.k_i`` below the diagonal, so ``U =
    U0 - W S0`` with ``[U0 | W]`` solved for every sub-block at once, ahead
    of the one sequential pass that carries ``S`` from sub-block to
    sub-block: ``O = (e^G Q) S0 + tril(e^(G_t - G_i) q_t.k_i) U``, ``S =
    e^(G_last) S0 + (e^(G_last - G) K)^T U``."""
    f32 = jnp.float32
    b, t, h, dk = q.shape
    dv = v.shape[-1]
    g, beta = _masked(g.astype(f32), beta.astype(f32), valid)
    pad = -t % block
    n = (t + pad) // block

    def blocked(x):     # [B, T, H, ...] -> [N, B, H, C, ...]
        x = jnp.pad(x.astype(f32), ((0, 0), (0, pad)) + ((0, 0),)
                    * (x.ndim - 2))
        x = x.reshape(b, n, block, *x.shape[2:])
        return jnp.moveaxis(jnp.moveaxis(x, 3, 1), 2, 0)

    with routine("delta_chunk"):
        q, k, v = blocked(q), blocked(k), blocked(v)
        g, beta = blocked(g), blocked(beta)               # [N, B, H, C]
        run = jnp.cumsum(g, axis=-1)
        lower = jnp.tril(jnp.ones((block, block), bool))
        decay = jnp.exp(jnp.where(
            lower, run[..., :, None] - run[..., None, :], -jnp.inf))
        kk = jnp.einsum("...td,...id->...ti", k, k, precision=_HIGHEST)
        a = jnp.where(jnp.tril(lower, -1),
                      beta[..., :, None] * decay * kk, 0.0)
        bk = beta[..., None] * jnp.exp(run)[..., None] * k
        solved = _solve_unit_lower(
            a, jnp.concatenate([beta[..., None] * v, bk], -1))
        u0, w = solved[..., :dv], solved[..., dv:]
        qk = decay * jnp.einsum("...td,...id->...ti", q, k,
                                precision=_HIGHEST)
        qg = q * jnp.exp(run)[..., None]
        last = run[..., -1:]
        kd = k * jnp.exp(last - run)[..., None]

        def sub_block(s, xs):
            u0_n, w_n, qk_n, qg_n, kd_n, last_n = xs
            u = u0_n - jnp.einsum("...ck,...kv->...cv", w_n, s,
                                  precision=_HIGHEST)
            o = (jnp.einsum("...ck,...kv->...cv", qg_n, s,
                            precision=_HIGHEST)
                 + jnp.einsum("...ci,...iv->...cv", qk_n, u,
                              precision=_HIGHEST))
            s = (jnp.exp(last_n)[..., None] * s
                 + jnp.einsum("...ck,...cv->...kv", kd_n, u,
                              precision=_HIGHEST))
            return s, o

        state, o = jax.lax.scan(sub_block, state.astype(f32),
                                (u0, w, qk, qg, kd, last))
        # [N, B, H, C, dv] -> [B, T, H, dv]
        o = jnp.moveaxis(jnp.moveaxis(o, 0, 2), 1, 3).reshape(
            b, n * block, h, dv)[:, :t]
        return o, state
