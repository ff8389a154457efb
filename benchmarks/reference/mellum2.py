"""The plain reference for the Mellum 2 block (``model_type: mellum``):
jax.numpy, float32, matmuls at HIGHEST precision, the whole sequence at
once, no cache, no kernel, no batching, a literal band mask, routing by a
literal sort.

It imports nothing of the program.  It follows the published description
(the ``config.json`` this repo's catalog row names):

* block: ``h = x + Attn_l(RMSNorm(x))``, ``y = h + MoE(RMSNorm(h))``; every
  layer is sparse (``mlp_layer_types``); a final RMSNorm and an untied head;
  no position table, no bias anywhere;
* attention: ``heads`` query heads and ``kv_heads`` key/value heads of
  ``head_dim`` (a stated width: ``heads * head_dim`` is not the model's
  width), ``heads / kv_heads`` query heads a K/V head; rotary over the whole
  head at ``theta``; scores ``q k^T / sqrt(head_dim)``;
* a layer has a KIND (``layer_types``): on a ``full_attention`` layer
  position ``p`` sees ``j`` iff ``0 <= p - j``, and its rotary frequencies
  are YaRN's blend with cos and sin times ``attention_factor``; on a
  ``sliding_attention`` layer iff ``0 <= p - j < sliding_window``, with the
  plain frequencies;
* expert FFN: ``logits = x Wr`` in float32, a softmax over ALL experts, the
  ``top_k`` largest chosen, their probabilities renormalised to sum 1
  (``norm_topk_prob``), no scale, no bias, no groups, no shared expert;
  expert ``e`` is ``down_e(silu(gate_e x) * up_e x)``.

Departures, each also under ``assumed`` in the benchmark's configuration:

* the multi-token-prediction head the model card mentions is not loaded (no
  config key describes it; the main model's logits do not depend on it);
* no q/k norm (no config key names one);
* rotary PAIRING is the half-split: feature ``i`` pairs with ``i + dim/2``
  (what public implementations permute published interleaved weights to);
* ``held = (first, count)``: this chip's share of the routed experts.  The
  router routes over all ``experts``; a token's choices outside the held
  range are left out and the partial sum goes on, as in the program;
* keys and values come from ONE matrix ``kv`` whose first half is ``Wk`` and
  second half ``Wv`` (the names the program gives its parameters; with
  seeded weights the same as two matrices);
* weights arrive in any float type and are upcast one matrix at a time.

``quant="fp8"`` turns the same code into the precision control: every linear
layer's input is rounded to float8 e4m3 per row and its weight per output
column (absmax scaling), the nearest precision below the bfloat16 the
configuration states; the router stays float32 either way.  ``window=False``
is the STRUCTURE control: the sliding layers attend the whole context (the
band left out), which must read as not correct.
"""

from __future__ import annotations

import dataclasses
import functools
import math

import jax
import jax.numpy as jnp

Q_BLOCK = 512  # query rows per attention block

_mm = functools.partial(jnp.matmul, precision=jax.lax.Precision.HIGHEST)


@dataclasses.dataclass(frozen=True)
class Dims:
    """The numbers of one Mellum-2-shaped model (``config.json`` keys in the
    comments)."""

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
    sliding: tuple[bool, ...]  # layer_types[i] == "sliding_attention"
    window: int                # sliding_window
    norm_eps: float = 1e-6     # rms_norm_eps
    rope_theta: float = 500000.0
    # rope_parameters.full_attention (sliding_attention: plain frequencies)
    yarn_factor: float = 16.0
    yarn_original: int = 8192
    yarn_beta_fast: float = 32.0
    yarn_beta_slow: float = 1.0
    yarn_attention_factor: float = 1.2772588722239782
    # what the benchmark's readers ask of every expert model
    first_k_dense: int = 0


# -- pieces ------------------------------------------------------------------

def _fake_fp8(x, axis):
    """Round to float8 e4m3 (3 bits of mantissa) under an absmax scale."""
    scale = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / 448.0
    scale = jnp.where(scale == 0, 1.0, scale)
    return (x / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale


def linear(x, w, quant=None):
    """``x [S, in] @ w [in, out]`` in float32."""
    w = w.astype(jnp.float32)
    if quant == "fp8":
        x, w = _fake_fp8(x, -1), _fake_fp8(w, 0)
    elif quant is not None:
        raise ValueError(f"unknown control precision {quant!r}")
    return _mm(x, w)


def rms_norm(x, scale, eps):
    var = jnp.mean(jnp.square(x), -1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * scale.astype(jnp.float32)


def inv_freq(dims: Dims, yarn: bool):
    """``theta^(-2i/head_dim)``; under YaRN blended with the same divided by
    ``factor``, a linear ramp between the correction dims that ``beta_fast``
    / ``beta_slow`` give for the original positions."""
    d = dims.head_dim
    half = d // 2
    extra = 1.0 / dims.rope_theta ** (
        jnp.arange(half, dtype=jnp.float32) * 2.0 / d)
    if not yarn:
        return extra

    def correction_dim(rotations):
        return (d * math.log(dims.yarn_original / (rotations * 2 * math.pi))
                / (2 * math.log(dims.rope_theta)))

    low = max(math.floor(correction_dim(dims.yarn_beta_fast)), 0)
    high = min(math.ceil(correction_dim(dims.yarn_beta_slow)), d - 1)
    if low == high:
        high += 0.001
    ramp = jnp.clip((jnp.arange(half, dtype=jnp.float32) - low)
                    / (high - low), 0.0, 1.0)
    return extra / dims.yarn_factor * ramp + extra * (1.0 - ramp)


def rope(x, dims: Dims, yarn: bool):
    """Rotate ``x [S, H, head_dim]`` at positions ``0 .. S-1``; half-split
    pairs; under YaRN cos and sin times ``attention_factor``."""
    half = dims.head_dim // 2
    ang = (jnp.arange(x.shape[0], dtype=jnp.float32)[:, None]
           * inv_freq(dims, yarn)[None])
    mult = dims.yarn_attention_factor if yarn else 1.0
    cos = (jnp.cos(ang) * mult)[:, None, :]
    sin = (jnp.sin(ang) * mult)[:, None, :]
    a, b = x[..., :half], x[..., half:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], -1)


def _attend_block(qb, k, v, first_row, scale, window):
    """``qb [H, Bq, D]`` at rows ``first_row..`` against ``k, v [H, S, D]``:
    row ``p`` sees ``j`` iff ``0 <= p - j`` and, with a ``window``,
    ``p - j < window``."""
    scores = _mm(qb, k.transpose(0, 2, 1)) * scale
    rows = first_row + jnp.arange(qb.shape[1])[:, None]
    cols = jnp.arange(k.shape[1])[None, :]
    seen = rows >= cols
    if window is not None:
        seen = seen & (rows - cols < window)
    scores = jnp.where(seen, scores, -jnp.inf)
    scores = scores - jnp.max(scores, -1, keepdims=True)
    probs = jnp.exp(scores)
    probs = probs / jnp.sum(probs, -1, keepdims=True)
    return _mm(probs, v)


def attention(x, p, dims: Dims, sliding: bool, window: bool, quant=None):
    """Grouped-query attention on ``x [S, E]`` -> ``[S, E]``, in blocks of
    ``Q_BLOCK`` query rows."""
    s = x.shape[0]
    h, hk, d = dims.heads, dims.kv_heads, dims.head_dim
    q = linear(x, p["q"]["kernel"], quant).reshape(s, h, d)
    kv = linear(x, p["kv"]["kernel"], quant).reshape(s, 2, hk, d)
    yarn = not sliding
    q = rope(q, dims, yarn)
    k = rope(kv[:, 0], dims, yarn)
    # each K/V head serves heads / kv_heads query heads in turn
    k = jnp.repeat(k, h // hk, axis=1).transpose(1, 0, 2)
    v = jnp.repeat(kv[:, 1], h // hk, axis=1).transpose(1, 0, 2)
    q = q.transpose(1, 0, 2)
    bq = min(Q_BLOCK, s)
    if s % bq:
        raise ValueError(f"sequence {s} is not a multiple of {bq}")
    blocks = q.reshape(h, s // bq, bq, d).transpose(1, 0, 2, 3)
    starts = jnp.arange(s // bq) * bq
    band = dims.window if sliding and window else None
    out = jax.lax.map(
        lambda a: _attend_block(a[0], k, v, a[1], d ** -0.5, band),
        (blocks, starts))
    out = out.transpose(0, 2, 1, 3).reshape(s, h * d)
    return linear(out, p["proj"]["kernel"], quant)


def gated_mlp(x, w_gate, w_up, w_down, quant=None):
    return linear(jax.nn.silu(linear(x, w_gate, quant))
                  * linear(x, w_up, quant), w_down, quant)


def route(x, router, dims: Dims):
    """The published gate, by a literal sort: ``(weights [S, k], experts
    [S, k])`` over all ``dims.experts``."""
    probs = jax.nn.softmax(_mm(x, router.astype(jnp.float32)), axis=-1)
    experts = jnp.argsort(-probs, axis=-1)[:, : dims.top_k]
    w = jnp.take_along_axis(probs, experts, axis=1)
    return w / w.sum(-1, keepdims=True), experts


def moe(x, p, dims: Dims, quant=None):
    """The routed sum over the held experts (a choice held elsewhere adds
    nothing here)."""
    w, experts = route(x, p["router"]["kernel"], dims)
    first, count = dims.held

    def one(e):
        # this token's weight for held expert e (0 if it did not choose it)
        mine = jnp.sum(jnp.where(experts == first + e, w, 0.0), -1)
        y = gated_mlp(x, p["w_gate"][e], p["w_up"][e], p["w_down"][e],
                      quant)
        return y * mine[:, None]

    return jnp.sum(jax.lax.map(one, jnp.arange(count)), axis=0), experts


def block(x, p, *, dims: Dims, sliding: bool, window: bool = True,
          quant=None):
    """One decoder block on ``x [S, E]``; also the experts each token chose
    (``[S, k]``)."""
    x = x + attention(rms_norm(x, p["ln1"]["scale"], dims.norm_eps),
                      p["attn"], dims, sliding, window, quant)
    y, chosen = moe(rms_norm(x, p["ln2"]["scale"], dims.norm_eps), p["moe"],
                    dims, quant)
    return x + y, chosen


def head_logits(hidden, params, dims: Dims, quant=None):
    return linear(rms_norm(hidden, params["ln_f"]["scale"], dims.norm_eps),
                  params["lm_head"]["kernel"], quant)


class Forward:
    """Teacher-forced logits of one sequence, layer by layer: one layer's
    weights are float32 at a time (and inside it one expert's), whatever
    type the tree holds.  ``window=False``: the sliding layers see the whole
    context (the control that must fail)."""

    def __init__(self, dims: Dims, quant=None, window: bool = True) -> None:
        self.dims = dims
        self._block = {
            kind: jax.jit(functools.partial(
                block, dims=dims, sliding=kind, window=window, quant=quant))
            for kind in (True, False)}
        self._head = jax.jit(functools.partial(
            head_logits, dims=dims, quant=quant))

    def hidden(self, params, tokens):
        """The residual stream after the last block and the experts chosen
        in each layer (``[layers, S, k]``)."""
        x = params["tok_embed"]["embedding"][tokens].astype(jnp.float32)
        chosen = []
        for i in range(self.dims.layers):
            x, c = self._block[self.dims.sliding[i]](x, params[f"block{i}"])
            chosen.append(c)
        return x, chosen

    def head(self, params, hidden):
        return self._head(hidden, {k: params[k]
                                   for k in ("ln_f", "lm_head")})

    def logits(self, params, tokens, first: int = 0):
        """Logits at positions ``first..`` of ``tokens [S]``."""
        return self.head(params, self.hidden(params, tokens)[0][first:])
