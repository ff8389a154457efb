"""The plain reference for the Keye-VL 2.0 language block (``model_type:
KeyeVL2``): jax.numpy, float32, matmuls at HIGHEST precision, the whole
sequence at once, no cache, no kernel, no batching, the selection by
``jax.lax.top_k`` and the softmax over the chosen positions as a MASK over
all of them (no gather), routing by a literal sort.

It imports nothing of the program; what is equal to the Mellum 2 block (the
linear layer and its float8 control, RMSNorm, the gated expert, the softmax
router over all experts renormalised over the chosen, the held experts'
partial sum, the head) it imports from ``benchmarks/reference/mellum2.py``.
It follows the published ``config.json`` the catalog row names and, where
that is silent, what the configuration file lists under ``assumed``:

* block: ``h = x + Attn(RMSNorm(x))``, ``y = h + MoE(RMSNorm(h))``; every
  layer sparse; a final RMSNorm and an untied head; no bias in a linear
  layer;
* attention: ``heads`` query heads on ``kv_heads`` K/V heads of
  ``head_dim``; a per-head RMSNorm (one scale of ``head_dim``) on q and on
  k before the rotation; rotary over the whole head at ``theta``, half-split
  pairs; scores ``q k^T / sqrt(head_dim)``;
* indexer: ``q^I = x W^IQ`` as ``index_heads`` heads of ``index_dim``, ONE
  key a token ``k^I = LayerNorm(x W^IK)`` (scale and bias, eps 1e-6), both
  rotated over all ``index_dim`` features at the token's position; head
  weights ``w = (x W^IW) * index_heads^-0.5 * index_dim^-0.5`` in float32;
  ``I[t, s] = sum_j w[t, j] relu(q^I[t, j] . k^I[s])`` for ``s <= t``;
* selection: the ``min(index_topk, t + 1)`` positions ``s <= t`` of the
  largest ``I[t, s]``, equal scores to the lower position
  (``jax.lax.top_k``); the softmax runs over those alone.

Controls (each must read as not correct): ``quant="fp8"`` (every linear
layer's input rounded to float8 e4m3 per row, its weight per column; the
router and the indexer's head weights stay float32), ``select="all"`` (the
selection SKIPPED: every position at or before the query) and
``select="recent"`` (the WRONG rows: the most recent ``index_topk``).
"""

from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp

from benchmarks.reference.mellum2 import (_mm, head_logits, linear, moe,
                                          rms_norm)

Q_BLOCK = 256  # query rows per attention block


@dataclasses.dataclass(frozen=True)
class Dims:
    """The numbers of one KeyeVL2-shaped language model (``config.json``
    keys in the comments)."""

    vocab: int                 # vocab_size
    layers: int                # num_hidden_layers
    embed: int                 # hidden_size
    heads: int                 # num_attention_heads
    kv_heads: int              # num_key_value_heads
    head_dim: int              # head_dim
    expert_ff: int             # moe_intermediate_size
    experts: int               # num_experts (all of them: the router's width)
    top_k: int                 # num_experts_per_tok
    held: tuple[int, int]      # (first, count) of the routed experts here
    index_heads: int           # sa_config.indexer_num_heads
    index_dim: int             # sa_config.indexer_head_dim
    index_topk: int            # sa_config.topk
    norm_eps: float = 1e-6     # rms_norm_eps
    rope_theta: float = 1e7    # rope_theta
    # what the benchmark's readers ask of every expert model
    first_k_dense: int = 0


def layer_norm(x, scale, bias, eps=1e-6):
    mean = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean(jnp.square(x - mean), -1, keepdims=True)
    return ((x - mean) * jax.lax.rsqrt(var + eps)
            * scale.astype(jnp.float32) + bias.astype(jnp.float32))


def rope(x, theta: float):
    """Rotate the last axis of ``x [S, ..., dim]`` at positions ``0 ..
    S-1``: plain frequencies ``theta^(-2i/dim)``, half-split pairs."""
    dim = x.shape[-1]
    half = dim // 2
    inv = 1.0 / theta ** (jnp.arange(half, dtype=jnp.float32) * 2.0 / dim)
    ang = jnp.arange(x.shape[0], dtype=jnp.float32)[:, None] * inv[None]
    shape = (x.shape[0],) + (1,) * (x.ndim - 2) + (half,)
    cos, sin = jnp.cos(ang).reshape(shape), jnp.sin(ang).reshape(shape)
    a, b = x[..., :half], x[..., half:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], -1)


def _attend_block(qb, qib, wb, k, v, ki, first_row, scale, topk, select):
    """``qb [H, Bq, D]`` (index queries ``qib [Hi, Bq, Di]``, head weights
    ``wb [Bq, Hi]``) at rows ``first_row..`` against ``k, v [H, S, D]`` and
    the index keys ``ki [S, Di]``: row ``p`` attends the chosen of the
    positions ``j <= p``."""
    rows = first_row + jnp.arange(qb.shape[1])[:, None]
    cols = jnp.arange(k.shape[1])[None, :]
    seen = rows >= cols
    if select == "index":
        index = jnp.einsum("hqs,qh->qs",
                           jnp.maximum(_mm(qib, ki.T[None]), 0.0), wb,
                           precision=jax.lax.Precision.HIGHEST)
        index = jnp.where(seen, index, -jnp.inf)
        ids = jax.lax.top_k(index, min(topk, index.shape[1]))[1]
        chosen = jnp.zeros(seen.shape, bool).at[
            jnp.arange(seen.shape[0])[:, None], ids].set(True)
        seen = seen & chosen
    elif select == "recent":
        seen = seen & (rows - cols < topk)
    elif select != "all":
        raise ValueError(f"unknown selection {select!r}")
    scores = _mm(qb, k.transpose(0, 2, 1)) * scale
    scores = jnp.where(seen, scores, -jnp.inf)
    scores = scores - jnp.max(scores, -1, keepdims=True)
    probs = jnp.exp(scores)
    probs = probs / jnp.sum(probs, -1, keepdims=True)
    return _mm(probs, v)


def attention(x, p, dims: Dims, select: str = "index", quant=None):
    """Grouped-query attention over the chosen rows on ``x [S, E]`` ->
    ``[S, E]``, in blocks of ``Q_BLOCK`` query rows."""
    s = x.shape[0]
    h, hk, d = dims.heads, dims.kv_heads, dims.head_dim
    hi, di = dims.index_heads, dims.index_dim
    q = linear(x, p["q"]["kernel"], quant).reshape(s, h, d)
    kv = linear(x, p["kv"]["kernel"], quant).reshape(s, 2, hk, d)
    q = rope(rms_norm(q, p["q_norm"]["scale"], dims.norm_eps),
             dims.rope_theta)
    k = rope(rms_norm(kv[:, 0], p["k_norm"]["scale"], dims.norm_eps),
             dims.rope_theta)
    qi = rope(linear(x, p["idx_q"]["kernel"], quant).reshape(s, hi, di),
              dims.rope_theta)
    ki = rope(layer_norm(linear(x, p["idx_k"]["kernel"], quant),
                         p["idx_k_norm"]["scale"], p["idx_k_norm"]["bias"]),
              dims.rope_theta)
    w = _mm(x, p["idx_w"]["kernel"].astype(jnp.float32)) * (
        hi ** -0.5 * di ** -0.5)
    # each K/V head serves heads / kv_heads query heads in turn
    k = jnp.repeat(k, h // hk, axis=1).transpose(1, 0, 2)
    v = jnp.repeat(kv[:, 1], h // hk, axis=1).transpose(1, 0, 2)
    bq = min(Q_BLOCK, s)
    if s % bq:
        raise ValueError(f"sequence {s} is not a multiple of {bq}")
    nb = s // bq
    blocks = q.transpose(1, 0, 2).reshape(h, nb, bq, d).transpose(1, 0, 2, 3)
    iblocks = qi.transpose(1, 0, 2).reshape(hi, nb, bq, di).transpose(
        1, 0, 2, 3)
    out = jax.lax.map(
        lambda a: _attend_block(a[0], a[1], a[2], k, v, ki, a[3], d ** -0.5,
                                dims.index_topk, select),
        (blocks, iblocks, w.reshape(nb, bq, hi), jnp.arange(nb) * bq))
    out = out.transpose(0, 2, 1, 3).reshape(s, h * d)
    return linear(out, p["proj"]["kernel"], quant)


def block(x, p, *, dims: Dims, select: str = "index", quant=None):
    """One decoder block on ``x [S, E]``; also the experts each token chose
    (``[S, k]``)."""
    x = x + attention(rms_norm(x, p["ln1"]["scale"], dims.norm_eps),
                      p["attn"], dims, select, quant)
    y, chosen = moe(rms_norm(x, p["ln2"]["scale"], dims.norm_eps), p["moe"],
                    dims, quant)
    return x + y, chosen


class Forward:
    """Teacher-forced logits of one sequence, layer by layer: one layer's
    weights are float32 at a time (and inside it one expert's), whatever
    type the tree holds.  ``select``: ``"index"`` (the model), ``"all"`` /
    ``"recent"`` (the structure controls)."""

    def __init__(self, dims: Dims, quant=None, select: str = "index"):
        self.dims = dims
        self._block = jax.jit(functools.partial(
            block, dims=dims, select=select, quant=quant))
        self._head = jax.jit(functools.partial(
            head_logits, dims=dims, quant=quant))

    def hidden(self, params, tokens):
        """The residual stream after the last block and the experts chosen
        in each layer (``[layers, S, k]``)."""
        x = params["tok_embed"]["embedding"][tokens].astype(jnp.float32)
        chosen = []
        for i in range(self.dims.layers):
            x, c = self._block(x, params[f"block{i}"])
            chosen.append(c)
        return x, chosen

    def head(self, params, hidden):
        return self._head(hidden, {k: params[k]
                                   for k in ("ln_f", "lm_head")})

    def logits(self, params, tokens, first: int = 0):
        """Logits at positions ``first..`` of ``tokens [S]``."""
        return self.head(params, self.hidden(params, tokens)[0][first:])
