"""The parameter tree and the cache trees of every configuration the
benchmark runs, at its tiny preset: names, shapes and dtypes, as a digest.

The five configurations older than PR 41 were read on the PARENT's tree
(ad16120, before layer kinds, the norm's order, the whole-projection q/k
norm and "no positions" entered ``TransformerConfig``) and are written here
as constants: a new word of block vocabulary whose default moved a leaf of
an old configuration, added one or renamed one fails here.  The sixth is
the configuration that PR brought, read on its own tree.  The cache trees
of ``keye-vl-2.0-30b-a3b`` were read anew on the tree of PR 42, which
gave an indexer's layers ONE pool and ONE staging buffer of K beside V
(``paged_kv`` / ``side_kv`` for the two pairs: 26 leaves -> 22), and again
on the tree of PR 46, which made that row 32-bit words (the same 22
leaves; the two of a layer ``uint32`` of half the columns in bfloat16);
its parameters, and every other configuration's three trees, stayed.  The
seventh (PR 48: the parallel block, a scale-only LayerNorm, a tied head) was
read on the tree that brought it; the six before it stayed to the digit."""

import hashlib
import importlib

import jax
import jax.numpy as jnp
import pytest

from benchmarks.harness import common, serve, weights
from tpudist.models import ServeLoop, TransformerLM
from tpudist.models.generate import _blank_cache

# name -> ((parameter leaves, digest), (cache leaves, digest))
TREES = {
    "starcoderbase-3b": ((23, "0f789c0aba9ad442"), (20, "e053bf54e4e58098")),
    "starcoderbase-1b": ((23, "9024a99fe137a574"), (6, "e9525ab79e042568")),
    "deepseek-v3": ((49, "c5341169d9d88c48"), (21, "f844416a5214ad85")),
    "mellum2-12b-a2.5b": ((39, "6449b1b6c08d19e4"),
                          (40, "74e4b734b903b89e")),
    "keye-vl-2.0-30b-a3b": ((35, "6181a46c1edeab4e"),
                            (22, "b5662f8cc9ab7060")),
    "olmo-hybrid-7b": ((51, "90c4b5ddcc5c5e64"), (22, "ef3c1ab0e982065f")),
    # PR 48, read on its own tree: no ln2, no bias, no lm_head; its tiny
    # cache trees are Mellum's (the same K/V heads, window and two groups)
    "command-a-plus-05-2026": ((46, "ed98ab414a7b6875"),
                               (40, "74e4b734b903b89e")),
}


def digest(tree) -> tuple[int, str]:
    flat = jax.tree_util.tree_flatten_with_path(tree)[0]
    lines = sorted(
        "/".join(str(getattr(k, "key", k)) for k in path)
        + f" {tuple(leaf.shape)} {jnp.dtype(leaf.dtype).name}"
        for path, leaf in flat)
    return len(lines), hashlib.sha256(
        "\n".join(lines).encode()).hexdigest()[:16]


def trees(name: str):
    """The tiny preset's parameter tree (abstract) and cache trees: a serve
    configuration's slot cache and batch-1 prefill cache as its loop builds
    them, the trained configuration's decode cache."""
    bench = common.load_json(common.ROOT / "BENCHMARK.json")
    entry = {c["name"]: c for c in bench["configs"]}[name]
    config = common.load_json(common.ROOT / entry["file"])
    runner = importlib.import_module(
        f"benchmarks.harness.{config['runner']}")
    if config["runner"] in ("serve", "train"):
        dims = weights.ModelDims.from_config(config, True)
        cfg = serve.transformer_config(dims, jnp.bfloat16)
    else:
        dims = runner.model_dims(config, True)
        cfg = runner.transformer_config(
            dims, runner.max_seq_len(config, True), jnp.bfloat16)
    params = jax.eval_shape(
        TransformerLM(cfg).init, jax.random.key(0),
        jnp.zeros((1, 8), jnp.int32))["params"]
    if config["runner"] == "train":
        cache = {"decode": _blank_cache(TransformerLM(cfg, decode=True), 2)}
    else:
        loop = ServeLoop(cfg, params, **serve.loop_options(config, True))
        cache = {"slots": loop.cache, "prefill": loop._blank1}
    return digest(params), digest(cache)


def test_every_configuration_of_the_benchmark_is_listed():
    bench = common.load_json(common.ROOT / "BENCHMARK.json")
    assert {c["name"] for c in bench["configs"]} == set(TREES)


@pytest.mark.parametrize("name", TREES)
def test_trees_are_what_they_were(name):
    assert trees(name) == TREES[name]
