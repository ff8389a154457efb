"""The plain reference for the Olmo-Hybrid block (``model_type:
olmo_hybrid``): jax.numpy, float32, matmuls at HIGHEST precision, the whole
sequence at once, the linear layers' recurrence as a plain ``lax.scan`` over
the tokens, no chunking, no cache, no kernel, no batching.

It imports nothing of the program.  It follows the published ``config.json``
(the catalog row this repo's configuration file names) and, for what the
keys name and do not spell out, the layer of the public
``flash-linear-attention`` ``GatedDeltaNet`` whose keys they are:

* ``layer_types``: a layer is ``linear_attention`` or ``full_attention``;
* a linear layer, input ``u``: ``q~, k~, v~ = SiLU(conv(u Wqkv))``, the
  convolution causal, depthwise, ``conv`` taps a channel, no bias; a head's
  ``q = q~ / |q~| * dk^-1/2``, ``k = k~ / |k~|``, ``v = v~``; ``beta = 2 *
  sigmoid(u Wb)`` (the 2 is ``linear_allow_neg_eigval``), ``alpha =
  exp(-exp(A_log) * softplus(u Wa + dt_bias))``; a head's state ``S [dk,
  dv]`` from zeros: ``S' = alpha S``, ``S = S' + beta k (v - S'^T k)^T``,
  ``o = S^T q``; ``y = Wo[RMSNorm_dv(o) * w * SiLU(u Wg)]``;
* a full layer: ``q = RMSNorm(u Wq)``, ``k = RMSNorm(u Wk)`` over the WHOLE
  projection before the split into heads, causal softmax at
  ``head_dim^-1/2``, no positions;
* the block: ``h = x + RMSNorm(Mixer(x))``, ``out = h + RMSNorm(MLP(h))``,
  ``MLP(h) = Wd(SiLU(Wg h) * Wu h)``; a final RMSNorm and an untied head; no
  bias anywhere.

Departures and readings, each also under ``assumed`` in the benchmark's
configuration: the block's order (the family's: the norm after the
sublayer); ``rope_parameters.rope_theta`` null read as no positions on the
full layers; the unit-length scaling adds 1e-6 under the root; ``Wq``,
``Wk``, ``Wv`` are the column ranges of ONE matrix ``qkv`` and ``Wa``,
``Wb`` of one matrix ``ab`` (the names the program gives its parameters;
with seeded weights the same as separate matrices); weights arrive in any
float type and are upcast one matrix at a time.

``quant="fp8"`` turns the same code into the precision control: every linear
layer's input is rounded to float8 e4m3 per row and its weight per output
column (absmax scaling), the nearest precision below the bfloat16 the
configuration states; the decay's and the write strength's tiny projection
``ab`` stays float32 either way.  Two STRUCTURE controls, each of which must
read as not correct: ``decay=False`` (``alpha = 1``: the state never
forgets) and ``double_beta=False`` (``beta`` in (0, 1)).
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
    """The numbers of one Olmo-Hybrid-shaped model (``config.json`` keys in
    the comments)."""

    vocab: int                 # vocab_size
    layers: int                # num_hidden_layers
    embed: int                 # hidden_size
    heads: int                 # num_attention_heads (= num_key_value_heads)
    head_dim: int              # hidden_size / num_attention_heads
    ff: int                    # intermediate_size
    linear: tuple[bool, ...]   # layer_types[i] == "linear_attention"
    lin_heads: int             # linear_num_key_heads = linear_num_value_heads
    key_dim: int               # linear_key_head_dim
    value_dim: int             # linear_value_head_dim
    conv: int                  # linear_conv_kernel_dim
    neg_eigval: bool = True    # linear_allow_neg_eigval
    norm_eps: float = 1e-6     # rms_norm_eps


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


def causal_conv(x, w):
    """``x [S, C]``, ``w [taps, C]``: ``y_t = sum_j w_j x_(t - taps + 1 +
    j)``, zeros before the sequence."""
    taps, s = w.shape[0], x.shape[0]
    padded = jnp.pad(x, ((taps - 1, 0), (0, 0)))
    return sum(w[j].astype(jnp.float32) * padded[j:j + s]
               for j in range(taps))


def delta_scan(q, k, v, alpha, beta):
    """The recurrence, token by token: ``q, k [S, H, dk]``, ``v [S, H,
    dv]``, ``alpha, beta [S, H]`` -> ``o [S, H, dv]``."""
    def token(state, x):
        q_t, k_t, v_t, a_t, b_t = x
        decayed = state * a_t[:, None, None]
        seen = jnp.einsum("hkv,hk->hv", decayed, k_t,
                          precision=jax.lax.Precision.HIGHEST)
        delta = (v_t - seen) * b_t[:, None]
        state = decayed + k_t[:, :, None] * delta[:, None, :]
        return state, jnp.einsum("hkv,hk->hv", state, q_t,
                                 precision=jax.lax.Precision.HIGHEST)

    h, dk, dv = q.shape[1], q.shape[2], v.shape[2]
    return jax.lax.scan(token, jnp.zeros((h, dk, dv), jnp.float32),
                        (q, k, v, alpha, beta))[1]


def linear_attention(x, p, dims: Dims, quant=None, decay: bool = True,
                     double_beta: bool = True):
    """The gated delta-rule layer on ``x [S, E]`` -> ``[S, E]``."""
    s = x.shape[0]
    h, dk, dv = dims.lin_heads, dims.key_dim, dims.value_dim
    mixed = jax.nn.silu(causal_conv(linear(x, p["qkv"]["kernel"], quant),
                                    p["conv"]))

    def unit(t):
        return t * jax.lax.rsqrt(jnp.sum(t * t, -1, keepdims=True) + 1e-6)

    q = unit(mixed[:, :h * dk].reshape(s, h, dk)) * dk ** -0.5
    k = unit(mixed[:, h * dk:2 * h * dk].reshape(s, h, dk))
    v = mixed[:, 2 * h * dk:].reshape(s, h, dv)
    ab = _mm(x, p["ab"]["kernel"].astype(jnp.float32))
    alpha = jnp.exp(-jnp.exp(p["A_log"].astype(jnp.float32))
                    * jax.nn.softplus(ab[:, :h]
                                      + p["dt_bias"].astype(jnp.float32)))
    if not decay:
        alpha = jnp.ones_like(alpha)
    beta = jax.nn.sigmoid(ab[:, h:]) * (
        2.0 if dims.neg_eigval and double_beta else 1.0)
    o = delta_scan(q, k, v, alpha, beta)
    o = rms_norm(o, p["o_norm"]["scale"], dims.norm_eps)
    o = o * jax.nn.silu(linear(x, p["gate"]["kernel"], quant)).reshape(
        s, h, dv)
    return linear(o.reshape(s, h * dv), p["out"]["kernel"], quant)


def _attend_block(qb, k, v, first_row, scale):
    """``qb [H, Bq, D]`` at rows ``first_row..`` against ``k, v [H, S, D]``:
    row ``p`` sees ``j`` iff ``j <= p``."""
    scores = _mm(qb, k.transpose(0, 2, 1)) * scale
    rows = first_row + jnp.arange(qb.shape[1])[:, None]
    cols = jnp.arange(k.shape[1])[None, :]
    scores = jnp.where(rows >= cols, scores, -jnp.inf)
    scores = scores - jnp.max(scores, -1, keepdims=True)
    probs = jnp.exp(scores)
    probs = probs / jnp.sum(probs, -1, keepdims=True)
    return _mm(probs, v)


def full_attention(x, p, dims: Dims, quant=None):
    """Multi-head attention on ``x [S, E]`` -> ``[S, E]``, in blocks of
    ``Q_BLOCK`` query rows; q and k normed over the whole projection; no
    positions."""
    s = x.shape[0]
    h, d = dims.heads, dims.head_dim
    qkv = linear(x, p["qkv"]["kernel"], quant).reshape(s, 3, h * d)
    q = rms_norm(qkv[:, 0], p["q_norm"]["scale"], dims.norm_eps)
    k = rms_norm(qkv[:, 1], p["k_norm"]["scale"], dims.norm_eps)
    q = q.reshape(s, h, d).transpose(1, 0, 2)
    k = k.reshape(s, h, d).transpose(1, 0, 2)
    v = qkv[:, 2].reshape(s, h, d).transpose(1, 0, 2)
    bq = min(Q_BLOCK, s)
    if s % bq:
        raise ValueError(f"sequence {s} is not a multiple of {bq}")
    blocks = q.reshape(h, s // bq, bq, d).transpose(1, 0, 2, 3)
    starts = jnp.arange(s // bq) * bq
    out = jax.lax.map(
        lambda a: _attend_block(a[0], k, v, a[1], d ** -0.5),
        (blocks, starts))
    out = out.transpose(0, 2, 1, 3).reshape(s, h * d)
    return linear(out, p["proj"]["kernel"], quant)


def gated_mlp(x, p, quant=None):
    return linear(jax.nn.silu(linear(x, p["gate"]["kernel"], quant))
                  * linear(x, p["up"]["kernel"], quant),
                  p["down"]["kernel"], quant)


def block(x, p, *, dims: Dims, is_linear: bool, quant=None,
          decay: bool = True, double_beta: bool = True):
    """One decoder block on ``x [S, E]``: the norm AFTER each sublayer."""
    if is_linear:
        y = linear_attention(x, p["linear_attn"], dims, quant, decay,
                             double_beta)
    else:
        y = full_attention(x, p["attn"], dims, quant)
    x = x + rms_norm(y, p["ln1"]["scale"], dims.norm_eps)
    y = gated_mlp(x, p["mlp"], quant)
    return x + rms_norm(y, p["ln2"]["scale"], dims.norm_eps)


def head_logits(hidden, params, dims: Dims, quant=None):
    return linear(rms_norm(hidden, params["ln_f"]["scale"], dims.norm_eps),
                  params["lm_head"]["kernel"], quant)


class Forward:
    """Teacher-forced logits of one sequence, layer by layer: one layer's
    weights are float32 a matrix at a time, whatever type the tree holds.
    ``quant``, ``decay``, ``double_beta``: the controls (module docstring)."""

    def __init__(self, dims: Dims, quant=None, decay: bool = True,
                 double_beta: bool = True) -> None:
        self.dims = dims
        self._block = {
            kind: jax.jit(functools.partial(
                block, dims=dims, is_linear=kind, quant=quant, decay=decay,
                double_beta=double_beta))
            for kind in (True, False)}
        self._head = jax.jit(functools.partial(
            head_logits, dims=dims, quant=quant))

    def hidden(self, params, tokens):
        """The residual stream after the last block."""
        x = params["tok_embed"]["embedding"][tokens].astype(jnp.float32)
        for i in range(self.dims.layers):
            x = self._block[self.dims.linear[i]](x, params[f"block{i}"])
        return x

    def head(self, params, hidden):
        return self._head(hidden, {k: params[k]
                                   for k in ("ln_f", "lm_head")})

    def logits(self, params, tokens, first: int = 0):
        """Logits at positions ``first..`` of ``tokens [S]``."""
        return self.head(params, self.hidden(params, tokens)[first:])
