"""Seeded weights, made on the device in one jitted call.

The tree has the names ``tpudist.models.TransformerLM`` gives its parameters
(``tok_embed/embedding``, ``block{i}/attn/q/kernel`` ...), but nothing here
imports the program: shapes come from the configuration's numbers, so the
plain reference can use the same draws.  Each leaf has a key of its own,
``fold_in(key(seed), index)``, and is drawn in float32, scaled and then
rounded once to the served type, so the bits do not depend on which program
the draw is compiled into.
"""

from __future__ import annotations

import dataclasses
import functools
import math

import jax
import jax.numpy as jnp


@dataclasses.dataclass(frozen=True)
class ModelDims:
    """The sizes of one GPTBigCode-shaped model, from its config file."""

    vocab: int
    layers: int
    heads: int
    kv_heads: int
    embed: int
    inner: int
    positions: int

    @property
    def head_dim(self) -> int:
        return self.embed // self.heads

    @classmethod
    def from_config(cls, cfg: dict, tiny: bool = False) -> "ModelDims":
        src = {**cfg, **cfg["tiny"]} if tiny else cfg
        if src["n_inner"] != 4 * src["n_embd"]:
            raise ValueError("TransformerLM's MLP is 4x the hidden size; "
                             f"got n_inner={src['n_inner']}")
        return cls(vocab=src["vocab_size"], layers=src["n_layer"],
                   heads=src["n_head"],
                   kv_heads=1 if src.get("multi_query") else src["n_head"],
                   embed=src["n_embd"], inner=src["n_inner"],
                   positions=src["n_positions"])


def leaf_table(dims: ModelDims) -> list[tuple[tuple[str, ...], tuple, float]]:
    """``(path, shape, std)`` per leaf in a fixed order; ``std`` 0 marks a
    LayerNorm bias (zeros) and -1 a LayerNorm scale (ones)."""
    e, d, kv = dims.embed, dims.head_dim, dims.kv_heads
    dense = lambda fan_in: 1.0 / math.sqrt(fan_in)  # noqa: E731
    out = [(("tok_embed", "embedding"), (dims.vocab, e), dense(e)),
           (("pos_embed", "embedding"), (dims.positions, e), dense(e))]
    for i in range(dims.layers):
        b = f"block{i}"
        out += [
            ((b, "ln1", "scale"), (e,), -1.0),
            ((b, "ln1", "bias"), (e,), 0.0),
            ((b, "attn", "q", "kernel"), (e, e), dense(e)),
            ((b, "attn", "kv", "kernel"), (e, 2 * kv * d), dense(e)),
            ((b, "attn", "proj", "kernel"), (e, e), dense(e)),
            ((b, "ln2", "scale"), (e,), -1.0),
            ((b, "ln2", "bias"), (e,), 0.0),
            ((b, "mlp", "up", "kernel"), (e, dims.inner), dense(e)),
            ((b, "mlp", "down", "kernel"), (dims.inner, e),
             dense(dims.inner)),
        ]
    out += [(("ln_f", "scale"), (e,), -1.0), (("ln_f", "bias"), (e,), 0.0),
            (("lm_head", "kernel"), (e, dims.vocab), dense(e))]
    if dims.kv_heads == dims.heads:
        raise ValueError("only the grouped/multi-query layout (separate q "
                         "and kv projections) is described here")
    return out


def seed_key(seed: int):
    """A key from any whole number: JAX keys take 32 bits."""
    seed = int(seed)
    return jax.random.fold_in(jax.random.key(seed & 0x7FFFFFFF), seed >> 31)


def _draw(key, index: int, shape, std: float, dtype):
    if std == 0.0:
        return jnp.zeros(shape, dtype)
    if std < 0.0:
        return jnp.ones(shape, dtype)
    k = jax.random.fold_in(key, index)
    return (jax.random.normal(k, shape, jnp.float32) * std).astype(dtype)


def _nest(flat: dict) -> dict:
    tree: dict = {}
    for path, leaf in flat.items():
        node = tree
        for name in path[:-1]:
            node = node.setdefault(name, {})
        node[path[-1]] = leaf
    return tree


@functools.partial(jax.jit, static_argnums=(1, 2))
def _make_all(key, dims: ModelDims, dtype):
    return _nest({path: _draw(key, i, shape, std, dtype)
                  for i, (path, shape, std) in enumerate(leaf_table(dims))})


def make_params(seed: int, dims: ModelDims, dtype) -> dict:
    """The whole tree on the default device, one compiled program."""
    return _make_all(seed_key(seed), dims, jnp.dtype(dtype))


def count_params(dims: ModelDims) -> int:
    return sum(math.prod(shape) for _, shape, _ in leaf_table(dims))


def matmul_params(dims: ModelDims) -> int:
    """Parameters that a token multiplies by (everything but the two
    embedding tables and the LayerNorm vectors)."""
    return sum(math.prod(shape) for path, shape, _ in leaf_table(dims)
               if path[-1] == "kernel")
