"""The plain reference: jax.numpy, float32, matmuls at HIGHEST precision, no
kernel, no cache, no paging, no batching.  Copied from ``chip_smoke.py``
(``Reference`` / ``_ref_block``) and made to run layer by layer from weights
of any type, with attention in blocks of query rows so that a sequence of
8192 fits beside a served model.

It imports nothing of the program.  It implements the program's departures
from the published GPTBigCode block, because those are what the benchmark
runs: no biases in the linear layers, an untied ``lm_head``, LayerNorm
epsilon 1e-6.  Everything else follows the published description: learned
absolute positions, pre-LayerNorm, multi-query attention, tanh-GELU MLP.

``quant="fp8"`` turns the same code into the serve cells' control: every
linear layer's input is rounded to float8 e4m3 per row and its weight per
output column (absmax scaling), the nearest precision below the bfloat16
the configuration states.
"""

from __future__ import annotations

import functools
import math
import time

import jax
import jax.numpy as jnp
import numpy as np

LN_EPS = 1e-6
Q_BLOCK = 1024  # query rows per attention block

_mm = functools.partial(jnp.matmul, precision=jax.lax.Precision.HIGHEST)


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


def layer_norm(x, p):
    mu = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), -1, keepdims=True)
    return ((x - mu) / jnp.sqrt(var + LN_EPS) * p["scale"].astype(jnp.float32)
            + p["bias"].astype(jnp.float32))


def _attend_block(qb, k, v, first_row):
    """``qb [H, Bq, D]`` at rows ``first_row..`` against ``k, v [H, S, D]``,
    causal."""
    d = qb.shape[-1]
    scores = _mm(qb, k.transpose(0, 2, 1)) / math.sqrt(d)
    rows = first_row + jnp.arange(qb.shape[1])[:, None]
    scores = jnp.where(rows >= jnp.arange(k.shape[1])[None, :], scores,
                       -jnp.inf)
    scores = scores - jnp.max(scores, -1, keepdims=True)
    probs = jnp.exp(scores)
    probs = probs / jnp.sum(probs, -1, keepdims=True)
    return _mm(probs, v)


def attention(q, k, v):
    """``q [S, H, D]``, ``k, v [S, Hkv, D]`` -> ``[S, H*D]``, causal, in
    blocks of ``Q_BLOCK`` query rows (each block recomputed in a backward
    pass, so one block's scores are alive at a time)."""
    s, h, d = q.shape
    group = h // k.shape[1]
    k = jnp.repeat(k, group, axis=1).transpose(1, 0, 2)
    v = jnp.repeat(v, group, axis=1).transpose(1, 0, 2)
    q = q.transpose(1, 0, 2)
    bq = min(Q_BLOCK, s)
    if s % bq:
        raise ValueError(f"sequence {s} is not a multiple of {bq}")
    blocks = q.reshape(h, s // bq, bq, d).transpose(1, 0, 2, 3)
    starts = jnp.arange(s // bq) * bq
    out = jax.lax.map(
        lambda a: jax.checkpoint(_attend_block)(a[0], k, v, a[1]),
        (blocks, starts))
    return out.transpose(0, 2, 1, 3).reshape(s, h * d)


def gelu_tanh(h):
    return 0.5 * h * (1.0 + jnp.tanh(
        math.sqrt(2.0 / math.pi) * (h + 0.044715 * h ** 3)))


def block(x, p, *, heads: int, kv_heads: int, quant=None):
    """One decoder block on ``x [S, E]``."""
    s, e = x.shape
    d = e // heads
    h = layer_norm(x, p["ln1"])
    q = linear(h, p["attn"]["q"]["kernel"], quant).reshape(s, heads, d)
    kv = linear(h, p["attn"]["kv"]["kernel"], quant).reshape(
        s, 2, kv_heads, d)
    x = x + linear(attention(q, kv[:, 0], kv[:, 1]),
                   p["attn"]["proj"]["kernel"], quant)
    h = linear(layer_norm(x, p["ln2"]), p["mlp"]["up"]["kernel"], quant)
    return x + linear(gelu_tanh(h), p["mlp"]["down"]["kernel"], quant)


def embed(params, tokens):
    return (params["tok_embed"]["embedding"].astype(jnp.float32)[tokens]
            + params["pos_embed"]["embedding"].astype(jnp.float32)[
                : tokens.shape[0]])


def head_logits(hidden, params, quant=None):
    return linear(layer_norm(hidden, params["ln_f"]),
                  params["lm_head"]["kernel"], quant)


class Forward:
    """Teacher-forced logits of one sequence, layer by layer: one layer's
    weights are float32 at a time, whatever type the tree holds."""

    def __init__(self, layers: int, heads: int, kv_heads: int,
                 quant=None) -> None:
        self.layers = layers
        self._block = jax.jit(functools.partial(
            block, heads=heads, kv_heads=kv_heads, quant=quant))
        self._embed = jax.jit(embed)
        self._head = jax.jit(functools.partial(head_logits, quant=quant))

    def logits(self, params, tokens, first: int):
        """Logits at positions ``first..`` of ``tokens [S]``."""
        x = self._embed({k: params[k] for k in ("tok_embed", "pos_embed")},
                        tokens)
        for i in range(self.layers):
            x = self._block(x, params[f"block{i}"])
        return self._head(x[first:], {k: params[k]
                                      for k in ("ln_f", "lm_head")})


# -- training: loss, gradients and adamw, all float32 -----------------------

LOSS_CHUNK = 1024  # positions per logits chunk


def _chunk_loss(hidden, targets, params):
    lg = head_logits(hidden, params)
    m = jnp.max(lg, -1)
    lse = m + jnp.log(jnp.sum(jnp.exp(lg - m[:, None]), -1))
    picked = jnp.take_along_axis(lg, targets[:, None], -1)[:, 0]
    return jnp.sum(lse - picked)


def row_loss(params, x, y, *, layers: int, heads: int, kv_heads: int):
    """Mean next-token cross-entropy of one row ``x, y [S]``."""
    h = embed(params, x)
    blk = jax.checkpoint(functools.partial(
        block, heads=heads, kv_heads=kv_heads))
    for i in range(layers):
        h = blk(h, params[f"block{i}"])
    s = h.shape[0]
    c = min(LOSS_CHUNK, s)
    head = {k: params[k] for k in ("ln_f", "lm_head")}
    sums = jax.lax.map(
        lambda a: jax.checkpoint(_chunk_loss)(a[0], a[1], head),
        (h.reshape(s // c, c, -1), y.reshape(s // c, c)))
    return jnp.sum(sums) / s


def batch_loss(params, x, y, **dims):
    """Mean over the rows of ``x, y [B, S]``, one row at a time."""
    return jnp.mean(jax.lax.map(
        lambda xy: row_loss(params, xy[0], xy[1], **dims), (x, y)))


def adamw_step(params, grads, mu, nu, step: int, *, lr: float,
               b1: float = 0.9, b2: float = 0.999, eps: float = 1e-8,
               weight_decay: float = 1e-4):
    """optax.adamw's arithmetic, written out; ``step`` counts from 1."""
    def one(p, g, m, n):
        m = b1 * m + (1.0 - b1) * g
        n = b2 * n + (1.0 - b2) * jnp.square(g)
        m_hat = m / (1.0 - b1 ** step)
        n_hat = n / (1.0 - b2 ** step)
        return (p - lr * (m_hat / (jnp.sqrt(n_hat) + eps)
                          + weight_decay * p), m, n)

    out = jax.tree.map(one, params, grads, mu, nu)
    pick = lambda i: jax.tree.map(  # noqa: E731
        lambda t: t[i], out, is_leaf=lambda t: isinstance(t, tuple))
    return pick(0), pick(1), pick(2)


def leaf_norms(tree) -> dict:
    """``{path: l2 norm}`` over the leaves, as one device computation."""
    flat = jax.tree_util.tree_flatten_with_path(tree)[0]
    norms = jax.jit(lambda leaves: [
        jnp.sqrt(jnp.sum(jnp.square(a.astype(jnp.float32))))
        for a in leaves])([a for _, a in flat])
    return {jax.tree_util.keystr(path): float(n)
            for (path, _), n in zip(flat, norms)}


def diff_norms(a, b) -> dict:
    """``{path: ||a - b||}`` leaf by leaf."""
    flat_a = jax.tree_util.tree_flatten_with_path(a)[0]
    flat_b = jax.tree.leaves(b)
    one = jax.jit(lambda x, y: jnp.sqrt(jnp.sum(jnp.square(
        x.astype(jnp.float32) - y.astype(jnp.float32)))))
    return {jax.tree_util.keystr(path): float(one(x, y))
            for (path, x), y in zip(flat_a, flat_b)}


def follow_steps(params, batches, *, lr: float, layers: int, heads: int,
                 kv_heads: int, remake_start):
    """Follow the optimizer through ``batches`` (a list of ``(x, y)``) from
    float32 ``params``: each step's loss, the first gradient's norm by leaf
    and the norm of the parameters' change by leaf after the last step.

    Kept lean on the device, so that the process's peak of live buffers
    stays the program's and not the reference's: adam's two moments live on
    the host between steps and visit the device one leaf at a time, and
    ``remake_start()`` makes the starting point again at the end instead of
    a copy living all along."""
    grad = jax.jit(jax.value_and_grad(functools.partial(
        batch_loss, layers=layers, heads=heads, kv_heads=kv_heads)))
    update = jax.jit(functools.partial(adamw_step, lr=lr),
                     static_argnames=("step",), donate_argnums=(0, 2, 3))
    host_mu: list = []
    host_nu: list = []
    losses, grad_norms = [], None
    seconds = {"grad": [], "update": []}
    last = len(batches)
    for t, (x, y) in enumerate(batches, start=1):
        t0 = time.perf_counter()
        loss, g = grad(params, jnp.asarray(x), jnp.asarray(y))
        losses.append(float(loss))
        seconds["grad"].append(time.perf_counter() - t0)
        t0 = time.perf_counter()
        if t == 1:
            grad_norms = leaf_norms(g)
        leaves, treedef = jax.tree.flatten(params)
        g_leaves = jax.tree.leaves(g)
        del params, g
        for i in range(len(leaves)):
            if t == 1:
                m, n = jnp.zeros_like(leaves[i]), jnp.zeros_like(leaves[i])
            else:
                m, n = jnp.asarray(host_mu[i]), jnp.asarray(host_nu[i])
            leaves[i], m, n = update(leaves[i], g_leaves[i], m, n, step=t)
            g_leaves[i] = None
            if t < last:
                if t == 1:
                    host_mu.append(np.asarray(m))
                    host_nu.append(np.asarray(n))
                else:
                    host_mu[i], host_nu[i] = np.asarray(m), np.asarray(n)
        params = jax.tree.unflatten(treedef, leaves)
        seconds["update"].append(time.perf_counter() - t0)
    return {"losses": losses, "grad_norms": grad_norms, "seconds": seconds,
            "change_norms": diff_norms(params, remake_start())}
