"""The plain reference for the Command A+ block (``model_type:
cohere2_moe``): jax.numpy, float32, matmuls at HIGHEST precision, the whole
sequence at once, no cache, no kernel, no batching, a literal band mask,
routing by a literal sort, the four shared experts one by one.

It imports nothing of the program.  It follows the published description
(the ``config.json`` this repo's catalog row ``command-a-plus-05-2026``
names, and the row's ``described_as``):

* block (``use_parallel_block``): ``h = LN(x)``, ``y = x + Attn_l(h) +
  MoE(h)``: ONE norm a layer, both sublayers read it, one add.  ``LN`` is a
  LayerNorm over the features, mean-centred, a scale and NO bias, eps
  ``layer_norm_eps``; a final ``LN`` and a TIED head: ``logits =
  logit_scale * LN_f(x) E^T`` with ``E`` the embedding;
* attention: ``heads`` query heads and ``kv_heads`` key/value heads of
  ``head_dim``, ``heads / kv_heads`` query heads a K/V head, no bias, no
  q/k norm; scores ``q k^T / sqrt(head_dim)``;
* a layer has a KIND (``layer_types``): on a ``sliding_attention`` layer
  queries and keys are rotated over the whole head at ``rope_theta`` (plain
  frequencies) and position ``p`` sees ``j`` iff ``0 <= p - j <
  sliding_window``; on a ``full_attention`` layer NOTHING is rotated (no
  positions at all) and ``p`` sees ``j`` iff ``0 <= p - j``;
* expert layer (every layer: ``first_k_dense_replace`` 0): ``s = sigmoid(h
  Wr)`` over ALL experts in float32, the ``top_k`` largest chosen, ``w =
  s_chosen / sum(s_chosen)`` (``norm_topk_prob``), no groups, no bias, no
  scale; expert ``e`` is ``down_e(silu(gate_e h) * up_e h)``; the
  ``n_shared`` shared experts have the same form and width and their MEAN
  is added (``shared_expert_combination_strategy: average``).

Departures, each also under ``assumed`` in the benchmark's configuration:

* ``intermediate_size`` is read as ONE expert's width, routed and shared;
* the mean over the shared experts is this repo's reading of "average";
* the vision tower is not loaded; ``prefix_dense_*`` is read by nothing;
* rotary PAIRING is the half-split: feature ``i`` pairs with ``i + dim/2``
  (the published ``rope_gptj`` interleaves; with seeded weights the two
  differ by a column permutation of q and k);
* ``held = (first, count)``: this chip's share of the routed experts.  The
  router routes over all ``experts``; a token's choices outside the held
  range are left out and the partial sum goes on, as in the program;
* the vocabulary is the held rows of the embedding (ids, logits and arg-max
  over them alone);
* keys and values come from ONE matrix ``kv`` whose first half is ``Wk`` and
  second half ``Wv``, and the shared experts' matrices lie side by side in
  ``shared/{gate,up,down}`` (expert ``j``: columns / rows ``j f .. (j+1)
  f``): the names the program gives its parameters;
* weights arrive in any float type and are upcast one matrix at a time.

The CONTROLS the cell's limits are fitted against, each of which must read
as not correct: ``quant="fp8"`` (every linear layer's input rounded to
float8 e4m3 per row and its weight per output column, absmax scaling; the
router stays float32), ``rope_full=True`` (rotary put on the full layers
too), ``sequential=True`` (the block made sequential: ``u = x + Attn(LN(x))``,
``y = u + MoE(LN(u))`` with the same scale) and ``shared="sum"`` (the shared
experts summed where they are averaged).
"""

from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp

Q_BLOCK = 512  # query rows per attention block

_mm = functools.partial(jnp.matmul, precision=jax.lax.Precision.HIGHEST)


@dataclasses.dataclass(frozen=True)
class Dims:
    """The numbers of one Command-A+-shaped model (``config.json`` keys in
    the comments)."""

    vocab: int                 # vocab_size (the rows held)
    layers: int                # num_hidden_layers
    embed: int                 # hidden_size
    heads: int                 # num_attention_heads
    kv_heads: int              # num_key_value_heads
    head_dim: int              # head_dim
    expert_ff: int             # intermediate_size (ONE expert's width)
    experts: int               # num_experts (all of them: the router's width)
    top_k: int                 # num_experts_per_tok
    held: tuple[int, int]      # (first, count) of the routed experts here
    n_shared: int              # num_shared_experts
    sliding: tuple[bool, ...]  # layer_types[i] == "sliding_attention"
    window: int                # sliding_window
    norm_eps: float = 1e-5     # layer_norm_eps
    rope_theta: float = 50000.0
    logit_scale: float = 1.0
    # what the benchmark's readers ask of every expert model
    first_k_dense: int = 0


@dataclasses.dataclass(frozen=True)
class Control:
    """How a reference departs from the equations, for the limits' sake
    (the default: not at all)."""

    quant: str | None = None   # "fp8": the precision control
    rope_full: bool = False    # rotary on the full layers too
    sequential: bool = False   # x + a, a second norm, then + m
    shared: str = "mean"       # "sum": the shared experts summed


EXACT = Control()
CONTROLS = {
    "fp8": Control(quant="fp8"),
    "rope_full": Control(rope_full=True),
    "sequential": Control(sequential=True),
    "shared_sum": Control(shared="sum"),
}


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


def layer_norm(x, scale, eps):
    """Mean-centred, a scale and no bias."""
    mean = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean(jnp.square(x - mean), -1, keepdims=True)
    return (x - mean) * jax.lax.rsqrt(var + eps) * scale.astype(jnp.float32)


def rope(x, dims: Dims):
    """Rotate ``x [S, H, head_dim]`` at positions ``0 .. S-1`` by the plain
    frequencies ``theta^(-2i/head_dim)``; half-split pairs."""
    d = dims.head_dim
    half = d // 2
    inv = 1.0 / dims.rope_theta ** (
        jnp.arange(half, dtype=jnp.float32) * 2.0 / d)
    ang = jnp.arange(x.shape[0], dtype=jnp.float32)[:, None] * inv[None]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
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


def attention(x, p, dims: Dims, sliding: bool, control: Control = EXACT):
    """Grouped-query attention on ``x [S, E]`` -> ``[S, E]``, in blocks of
    ``Q_BLOCK`` query rows."""
    s = x.shape[0]
    h, hk, d = dims.heads, dims.kv_heads, dims.head_dim
    q = linear(x, p["q"]["kernel"], control.quant).reshape(s, h, d)
    kv = linear(x, p["kv"]["kernel"], control.quant).reshape(s, 2, hk, d)
    k = kv[:, 0]
    if sliding or control.rope_full:
        q, k = rope(q, dims), rope(k, dims)
    # each K/V head serves heads / kv_heads query heads in turn
    k = jnp.repeat(k, h // hk, axis=1).transpose(1, 0, 2)
    v = jnp.repeat(kv[:, 1], h // hk, axis=1).transpose(1, 0, 2)
    q = q.transpose(1, 0, 2)
    bq = min(Q_BLOCK, s)
    if s % bq:
        raise ValueError(f"sequence {s} is not a multiple of {bq}")
    blocks = q.reshape(h, s // bq, bq, d).transpose(1, 0, 2, 3)
    starts = jnp.arange(s // bq) * bq
    band = dims.window if sliding else None
    out = jax.lax.map(
        lambda a: _attend_block(a[0], k, v, a[1], d ** -0.5, band),
        (blocks, starts))
    out = out.transpose(0, 2, 1, 3).reshape(s, h * d)
    return linear(out, p["proj"]["kernel"], control.quant)


def gated_mlp(x, w_gate, w_up, w_down, quant=None):
    return linear(jax.nn.silu(linear(x, w_gate, quant))
                  * linear(x, w_up, quant), w_down, quant)


def route(x, router, dims: Dims):
    """The published gate, by a literal sort: ``(weights [S, k], experts
    [S, k])`` over all ``dims.experts``."""
    scores = jax.nn.sigmoid(_mm(x, router.astype(jnp.float32)))
    experts = jnp.argsort(-scores, axis=-1)[:, : dims.top_k]
    w = jnp.take_along_axis(scores, experts, axis=1)
    return w / w.sum(-1, keepdims=True), experts


def routed(x, p, dims: Dims, quant=None):
    """The routed sum over the held experts (a choice held elsewhere adds
    nothing here) and every token's choices."""
    w, experts = route(x, p["router"]["kernel"], dims)
    first, count = dims.held

    def one(e):
        # this token's weight for held expert e (0 if it did not choose it)
        mine = jnp.sum(jnp.where(experts == first + e, w, 0.0), -1)
        y = gated_mlp(x, p["w_gate"][e], p["w_up"][e], p["w_down"][e],
                      quant)
        return y * mine[:, None]

    return jnp.sum(jax.lax.map(one, jnp.arange(count)), axis=0), experts


def shared(x, p, dims: Dims, control: Control = EXACT):
    """The ``n_shared`` shared experts, one by one, and their mean."""
    f = dims.expert_ff
    total = 0.0
    for j in range(dims.n_shared):
        cols = slice(j * f, (j + 1) * f)
        total = total + gated_mlp(
            x, p["gate"]["kernel"][:, cols], p["up"]["kernel"][:, cols],
            p["down"]["kernel"][cols, :], control.quant)
    if control.shared == "sum":
        return total
    return total / dims.n_shared


def moe(x, p, dims: Dims, control: Control = EXACT):
    y, chosen = routed(x, p, dims, control.quant)
    return y + shared(x, p["shared"], dims, control), chosen


def block(x, p, *, dims: Dims, sliding: bool, control: Control = EXACT):
    """One decoder block on ``x [S, E]``; also the experts each token chose
    (``[S, k]``)."""
    h = layer_norm(x, p["ln1"]["scale"], dims.norm_eps)
    a = attention(h, p["attn"], dims, sliding, control)
    if control.sequential:
        x = x + a
        h = layer_norm(x, p["ln1"]["scale"], dims.norm_eps)
        a = 0.0
    m, chosen = moe(h, p["moe"], dims, control)
    return x + a + m, chosen


def head_logits(hidden, params, dims: Dims, quant=None):
    """``logit_scale * LN_f(x) E^T`` over the held rows of ``E``."""
    table = params["tok_embed"]["embedding"].astype(jnp.float32)
    return dims.logit_scale * linear(
        layer_norm(hidden, params["ln_f"]["scale"], dims.norm_eps),
        table.T, quant)


class Forward:
    """Teacher-forced logits of one sequence, layer by layer: one layer's
    weights are float32 at a time (and inside it one expert's), whatever
    type the tree holds."""

    def __init__(self, dims: Dims, control: Control = EXACT) -> None:
        self.dims = dims
        self._block = {
            kind: jax.jit(functools.partial(
                block, dims=dims, sliding=kind, control=control))
            for kind in (True, False)}
        self._head = jax.jit(functools.partial(
            head_logits, dims=dims, quant=control.quant))

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
                                   for k in ("ln_f", "tok_embed")})

    def logits(self, params, tokens, first: int = 0):
        """Logits at positions ``first..`` of ``tokens [S]``."""
        return self.head(params, self.hidden(params, tokens)[0][first:])
