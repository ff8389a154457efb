"""The plain reference for the DeepSeek-V3 block: jax.numpy, float32,
matmuls at HIGHEST precision, the full sequence at once, no cache, no
kernel, no batching, expanded attention only, routing by a literal sort.

It imports nothing of the program.  It follows the published description
(``modeling_deepseek.py`` beside the config this repo's catalog row names):

* block: ``x + Attn(RMSNorm(x))``, then ``x + FFN(RMSNorm(x))``; a final
  RMSNorm and an untied head; no position table, no bias anywhere;
* MLA: queries through a ``q_lora_rank`` latent with its own RMSNorm;
  keys and values through ONE ``kv_lora_rank`` latent (RMSNorm'd) plus one
  rotary key of ``qk_rope_head_dim`` shared by all heads; per head
  ``q = [q_nope | q_rope]``, ``k = [k_nope | k_rope]``, softmax scale
  ``(nope + rope)^-0.5 * m^2`` with ``m = 0.1 * mscale_all_dim * ln(factor)
  + 1``; rotary frequencies are YaRN's blend;
* dense FFN: ``down(silu(gate x) * up x)``;
* expert FFN: sigmoid scores, a correction bias that moves the CHOICE and
  not the weight, groups scored by the sum of their two best, ``topk_group``
  groups kept, ``top_k`` experts chosen among them, weights renormalised
  (``+ 1e-20``) and scaled; a shared expert every token passes.

Departures, each also under ``assumed`` in the benchmark's configuration:

* the multi-token-prediction module is not loaded (the main model's logits
  do not depend on it);
* rotary PAIRING is the half-split: feature ``i`` pairs with ``i + rope/2``.
  The published weights pair ``(2i, 2i + 1)`` and the public implementation
  permutes to the half-split before rotating; with seeded weights the two
  differ by a column permutation of ``q_b`` / ``kv_a``;
* ``held = (first, count)``: this chip's share of the routed experts.  The
  router routes over all ``num_experts``; a token's choices outside the
  held range are left out and the partial sum goes on, as in the program.
  ``w_gate`` / ``w_up`` / ``w_down`` hold the ``count`` held experts;
* the vocabulary may be a slice: the embedding and the head simply have
  fewer rows;
* weights arrive in any float type and are upcast one matrix at a time.

``quant="fp8"`` turns the same code into the control: every linear layer's
input is rounded to float8 e4m3 per row and its weight per output column
(absmax scaling), the nearest precision below the bfloat16 the configuration
states.  The router stays in float32 either way, as published.
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
    """The numbers of one DeepSeek-V3-shaped model (``config.json`` keys in
    the comments)."""

    vocab: int                 # vocab_size (or the slice held here)
    layers: int                # num_hidden_layers
    first_k_dense: int         # first_k_dense_replace
    embed: int                 # hidden_size
    heads: int                 # num_attention_heads
    q_lora: int                # q_lora_rank
    kv_lora: int               # kv_lora_rank
    nope: int                  # qk_nope_head_dim
    rope: int                  # qk_rope_head_dim
    v_head: int                # v_head_dim
    dense_ff: int              # intermediate_size
    expert_ff: int             # moe_intermediate_size
    experts: int               # n_routed_experts (all of them: the router's width)
    top_k: int                 # num_experts_per_tok
    n_group: int
    topk_group: int
    routed_scale: float        # routed_scaling_factor
    n_shared: int              # n_shared_experts
    held: tuple[int, int]      # (first, count) of the routed experts here
    norm_eps: float = 1e-6     # rms_norm_eps
    rope_theta: float = 10000.0
    yarn_factor: float = 40.0
    yarn_original: int = 4096  # original_max_position_embeddings
    yarn_beta_fast: float = 32.0
    yarn_beta_slow: float = 1.0
    yarn_mscale: float = 1.0
    yarn_mscale_all_dim: float = 1.0

    @property
    def softmax_scale(self) -> float:
        m = 0.1 * self.yarn_mscale_all_dim * math.log(self.yarn_factor) + 1.0
        return (self.nope + self.rope) ** -0.5 * m * m


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


def yarn_inv_freq(dims: Dims):
    """YaRN's blend of ``theta^(-2i/rope)`` and the same divided by
    ``factor``: a linear ramp between the correction dims that
    ``beta_fast`` / ``beta_slow`` give for the original positions."""
    half = dims.rope // 2
    expo = jnp.arange(half, dtype=jnp.float32) * 2.0 / dims.rope
    extra = 1.0 / dims.rope_theta ** expo
    inter = extra / dims.yarn_factor

    def correction_dim(rotations):
        return (dims.rope * math.log(dims.yarn_original
                                     / (rotations * 2 * math.pi))
                / (2 * math.log(dims.rope_theta)))

    low = max(math.floor(correction_dim(dims.yarn_beta_fast)), 0)
    high = min(math.ceil(correction_dim(dims.yarn_beta_slow)), dims.rope - 1)
    if low == high:
        high += 0.001
    ramp = jnp.clip((jnp.arange(half, dtype=jnp.float32) - low)
                    / (high - low), 0.0, 1.0)
    return inter * ramp + extra * (1.0 - ramp)


def rope(x, positions, dims: Dims):
    """Rotate ``x [S, ..., rope]`` at ``positions [S]``; half-split pairs;
    the cos/sin multiplier ``mscale / mscale_all_dim`` of the published
    code is applied (1 for this model)."""
    def get_mscale(scale, m):
        return 1.0 if scale <= 1 else 0.1 * m * math.log(scale) + 1.0

    mult = (get_mscale(dims.yarn_factor, dims.yarn_mscale)
            / get_mscale(dims.yarn_factor, dims.yarn_mscale_all_dim))
    ang = positions.astype(jnp.float32)[:, None] * yarn_inv_freq(dims)[None]
    shape = (x.shape[0],) + (1,) * (x.ndim - 2) + (dims.rope // 2,)
    cos = (jnp.cos(ang) * mult).reshape(shape)
    sin = (jnp.sin(ang) * mult).reshape(shape)
    a, b = x[..., : dims.rope // 2], x[..., dims.rope // 2:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], -1)


def _attend_block(qb, k, v, first_row, scale):
    """``qb [H, Bq, Dk]`` at rows ``first_row..`` against ``k [H, S, Dk]``,
    ``v [H, S, Dv]``, causal."""
    scores = _mm(qb, k.transpose(0, 2, 1)) * scale
    rows = first_row + jnp.arange(qb.shape[1])[:, None]
    scores = jnp.where(rows >= jnp.arange(k.shape[1])[None, :], scores,
                       -jnp.inf)
    scores = scores - jnp.max(scores, -1, keepdims=True)
    probs = jnp.exp(scores)
    probs = probs / jnp.sum(probs, -1, keepdims=True)
    return _mm(probs, v)


def attention(q, k, v, scale):
    """``q, k [S, H, Dk]``, ``v [S, H, Dv]`` -> ``[S, H * Dv]``, causal, in
    blocks of ``Q_BLOCK`` query rows."""
    s, h, dk = q.shape
    k, v, q = (t.transpose(1, 0, 2) for t in (k, v, q))
    bq = min(Q_BLOCK, s)
    if s % bq:
        raise ValueError(f"sequence {s} is not a multiple of {bq}")
    blocks = q.reshape(h, s // bq, bq, dk).transpose(1, 0, 2, 3)
    starts = jnp.arange(s // bq) * bq
    out = jax.lax.map(lambda a: _attend_block(a[0], k, v, a[1], scale),
                      (blocks, starts))
    return out.transpose(0, 2, 1, 3).reshape(s, -1)


def mla(x, p, dims: Dims, quant=None):
    """Multi-head latent attention on ``x [S, E]``, expanded form."""
    s = x.shape[0]
    h, nope, rp, dv = dims.heads, dims.nope, dims.rope, dims.v_head
    pos = jnp.arange(s)
    c_q = rms_norm(linear(x, p["q_a"]["kernel"], quant),
                   p["q_norm"]["scale"], dims.norm_eps)
    q = linear(c_q, p["q_b"]["kernel"], quant).reshape(s, h, nope + rp)
    q = jnp.concatenate([q[..., :nope], rope(q[..., nope:], pos, dims)], -1)
    kv_a = linear(x, p["kv_a"]["kernel"], quant)
    c_kv = rms_norm(kv_a[:, : dims.kv_lora], p["kv_norm"]["scale"],
                    dims.norm_eps)
    k_rope = rope(kv_a[:, dims.kv_lora:], pos, dims)        # [S, rope]
    kv = linear(c_kv, p["kv_b"]["kernel"], quant).reshape(s, h, nope + dv)
    k = jnp.concatenate(
        [kv[..., :nope],
         jnp.broadcast_to(k_rope[:, None, :], (s, h, rp))], -1)
    out = attention(q, k, kv[..., nope:], dims.softmax_scale)
    return linear(out, p["proj"]["kernel"], quant)


def gated_mlp(x, w_gate, w_up, w_down, quant=None):
    return linear(jax.nn.silu(linear(x, w_gate, quant))
                  * linear(x, w_up, quant), w_down, quant)


def route(x, router, bias, dims: Dims):
    """The published gate, by literal sorts: returns ``(weights [S, k],
    experts [S, k])`` over all ``dims.experts``."""
    s = x.shape[0]
    scores = jax.nn.sigmoid(_mm(x, router.astype(jnp.float32)))
    choice = scores + bias.astype(jnp.float32)[None, :]
    per_group = choice.reshape(s, dims.n_group, -1)
    best_two = -jnp.sort(-per_group, axis=-1)[..., :2]
    group_score = best_two.sum(-1)                              # [S, G]
    keep = jnp.argsort(-group_score, axis=-1)[:, : dims.topk_group]
    group_ok = jnp.zeros((s, dims.n_group), bool).at[
        jnp.arange(s)[:, None], keep].set(True)
    ok = jnp.repeat(group_ok, dims.experts // dims.n_group, axis=1)
    masked = jnp.where(ok, choice, -jnp.inf)
    experts = jnp.argsort(-masked, axis=-1)[:, : dims.top_k]
    w = jnp.take_along_axis(scores, experts, axis=1)            # NOT choice
    w = w / (w.sum(-1, keepdims=True) + 1e-20) * dims.routed_scale
    return w, experts


def moe(x, p, dims: Dims, quant=None):
    """The routed part over the held experts plus the shared expert."""
    w, experts = route(x, p["router"]["kernel"], p["router_bias"], dims)
    first, count = dims.held

    def one(e):
        # this token's weight for held expert e (0 if it did not choose it)
        mine = jnp.sum(jnp.where(experts == first + e, w, 0.0), -1)
        y = gated_mlp(x, p["w_gate"][e], p["w_up"][e], p["w_down"][e],
                      quant)
        return y * mine[:, None]

    out = jnp.sum(jax.lax.map(one, jnp.arange(count)), axis=0)
    if dims.n_shared:
        sh = p["shared"]
        out = out + gated_mlp(x, sh["gate"]["kernel"], sh["up"]["kernel"],
                              sh["down"]["kernel"], quant)
    return out, experts


def block(x, p, *, dims: Dims, dense: bool, quant=None):
    """One decoder block on ``x [S, E]``; also the experts each token
    chose (``[S, k]``; all -1 for a dense layer)."""
    x = x + mla(rms_norm(x, p["ln1"]["scale"], dims.norm_eps), p["attn"],
                dims, quant)
    h = rms_norm(x, p["ln2"]["scale"], dims.norm_eps)
    if dense:
        m = p["mlp"]
        y = gated_mlp(h, m["gate"]["kernel"], m["up"]["kernel"],
                      m["down"]["kernel"], quant)
        chosen = jnp.full((x.shape[0], dims.top_k), -1, jnp.int32)
    else:
        y, chosen = moe(h, p["moe"], dims, quant)
    return x + y, chosen


def head_logits(hidden, params, dims: Dims, quant=None):
    return linear(rms_norm(hidden, params["ln_f"]["scale"], dims.norm_eps),
                  params["lm_head"]["kernel"], quant)


class Forward:
    """Teacher-forced logits of one sequence, layer by layer: one layer's
    weights are float32 at a time (and inside an expert layer one expert's),
    whatever type the tree holds."""

    def __init__(self, dims: Dims, quant=None) -> None:
        self.dims = dims
        self._dense = jax.jit(functools.partial(
            block, dims=dims, dense=True, quant=quant))
        self._expert = jax.jit(functools.partial(
            block, dims=dims, dense=False, quant=quant))
        self._head = jax.jit(functools.partial(
            head_logits, dims=dims, quant=quant))

    def hidden(self, params, tokens):
        """The residual stream after the last block and the experts chosen
        in each expert layer (``[expert layers, S, k]``)."""
        x = params["tok_embed"]["embedding"].astype(jnp.float32)[tokens]
        chosen = []
        for i in range(self.dims.layers):
            if i < self.dims.first_k_dense:
                x, _ = self._dense(x, params[f"block{i}"])
            else:
                x, c = self._expert(x, params[f"block{i}"])
                chosen.append(c)
        return x, chosen

    def head(self, params, hidden):
        return self._head(hidden, {k: params[k]
                                   for k in ("ln_f", "lm_head")})

    def logits(self, params, tokens, first: int = 0):
        """Logits at positions ``first..`` of ``tokens [S]``."""
        return self.head(params, self.hidden(params, tokens)[0][first:])
