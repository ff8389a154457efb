"""``TransformerLM`` with layers of mixed KINDS (gated delta-rule layers
beside full attention, the norm after the sublayer, a q/k norm over the
whole projection, no positions) against the plain reference
``benchmarks/reference/olmo_hybrid.py``."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.reference import olmo_hybrid as ref
from tpudist.models.generate import _blank_cache
from tpudist.models.transformer import (LinearAttentionConfig,
                                        TransformerConfig, TransformerLM)

KINDS = ("linear", "linear", "linear", "full")


def tiny_cfg(**kw):
    base = dict(
        vocab_size=96, num_layers=4, num_heads=4, embed_dim=64,
        max_seq_len=128, norm="rmsnorm", norm_order="post",
        positions="none", mlp="gated_silu", mlp_dim=96, qk_norm="whole",
        layer_kinds=KINDS,
        linear=LinearAttentionConfig(num_heads=4, key_dim=8, value_dim=16))
    base.update(kw)
    return TransformerConfig(**base)


def dims_of(cfg) -> ref.Dims:
    lin = cfg.linear
    return ref.Dims(
        vocab=cfg.vocab_size, layers=cfg.num_layers, embed=cfg.embed_dim,
        heads=cfg.num_heads, head_dim=cfg.head_dim, ff=cfg.ffn_dim,
        linear=tuple(k == "linear" for k in cfg.kinds),
        lin_heads=lin.num_heads, key_dim=lin.key_dim,
        value_dim=lin.value_dim, conv=lin.conv_width,
        neg_eigval=lin.neg_eigval, norm_eps=cfg.norm_eps)


def init_params(cfg, seed=0):
    params = TransformerLM(cfg).init(
        jax.random.key(seed), jnp.zeros((1, 8), jnp.int32))["params"]
    # norm scales away from 1, so that a norm left out or misplaced shows
    key = jax.random.key(seed + 1)

    def shake(path, leaf):
        if path[-1].key == "scale":
            k = jax.random.fold_in(key, hash(str(path)) % (1 << 30))
            return 1.0 + 0.3 * jax.random.normal(k, leaf.shape, leaf.dtype)
        return leaf

    return jax.tree_util.tree_map_with_path(shake, params)


@pytest.fixture(scope="module")
def model():
    cfg = tiny_cfg()
    return cfg, init_params(cfg)


def test_param_and_cache_trees(model):
    cfg, params = model
    lin = params["block0"]["linear_attn"]
    assert {k: (v.shape if hasattr(v, "shape") else sorted(v))
            for k, v in lin.items()} == {
        "qkv": ["kernel"], "gate": ["kernel"], "ab": ["kernel"],
        "conv": (4, 128), "A_log": (4,), "dt_bias": (4,),
        "o_norm": ["scale"], "out": ["kernel"]}
    assert sorted(params["block3"]["attn"]) == ["k_norm", "proj", "q_norm",
                                                "qkv"]
    assert params["block3"]["attn"]["q_norm"]["scale"].shape == (64,)
    assert "pos_embed" not in params
    cache = _blank_cache(TransformerLM(cfg, decode=True), 2)
    leaves = cache["block0"]["linear_attn"]
    assert {k: (v.shape, v.dtype) for k, v in leaves.items()} == {
        "conv": ((2, 3 * 128), jnp.float32),
        "state": ((2, 8, 64), jnp.float32)}
    assert sorted(cache["block3"]["attn"]) == ["cache_index", "cached_key",
                                               "cached_value"]


def test_full_forward_matches_reference(model):
    cfg, params = model
    tokens = jax.random.randint(jax.random.key(3), (96,), 0, cfg.vocab_size)
    want = ref.Forward(dims_of(cfg)).logits(params, tokens)
    got = TransformerLM(cfg).apply({"params": params}, tokens[None])[0]
    np.testing.assert_allclose(got, want, atol=2e-4, rtol=2e-4)


@pytest.mark.parametrize("chunk", [16, 24, 64])
def test_chunked_prefill_then_decode_matches_reference(model, chunk):
    """Prefill in chunks, the last one padded and masked by ``valid``, then
    one token a step through the caches: every logit the reference's."""
    cfg, params = model
    prompt_len, steps = 41, 9
    tokens = jax.random.randint(jax.random.key(chunk), (prompt_len + steps,),
                                0, cfg.vocab_size)
    want = ref.Forward(dims_of(cfg)).logits(
        params, jnp.pad(tokens, (0, 128 - tokens.size)))[:tokens.size]
    net = TransformerLM(cfg, decode=True)
    cache = _blank_cache(net, 1)
    apply = jax.jit(lambda cache, toks, pos, valid: net.apply(
        {"params": params, "cache": cache}, toks, positions=pos,
        valid=valid, mutable=["cache"]))
    from tpudist.models.speculative import _set_cache_index

    for off in range(0, prompt_len, chunk):
        piece = np.zeros((1, chunk), np.int32)
        n = min(chunk, prompt_len - off)
        piece[0, :n] = tokens[off:off + n]
        cache = _set_cache_index(cache, off)
        logits, mut = apply(cache, piece, off + jnp.arange(chunk)[None],
                            (jnp.arange(chunk) < n)[None])
        cache = mut["cache"]
        np.testing.assert_allclose(logits[0, :n], want[off:off + n],
                                   atol=3e-4, rtol=3e-4)
    cache = _set_cache_index(cache, prompt_len)
    for t in range(prompt_len, prompt_len + steps):
        logits, mut = apply(cache, tokens[None, t:t + 1],
                            jnp.full((1, 1), t), jnp.ones((1, 1), bool))
        cache = mut["cache"]
        np.testing.assert_allclose(logits[0, 0], want[t], atol=3e-4,
                                   rtol=3e-4)


def test_tokens_not_valid_move_no_state(model):
    cfg, params = model
    net = TransformerLM(cfg, decode=True)
    cache = _blank_cache(net, 2)
    toks = jax.random.randint(jax.random.key(5), (2, 16), 0, cfg.vocab_size)
    _, mut = net.apply({"params": params, "cache": cache}, toks,
                       mutable=["cache"])
    before = mut["cache"]
    _, mut = net.apply(
        {"params": params, "cache": before}, toks[:, :1],
        valid=jnp.asarray([[True], [False]]), mutable=["cache"])
    for i, kind in enumerate(KINDS):
        if kind != "linear":
            continue
        old = before[f"block{i}"]["linear_attn"]
        new = mut["cache"][f"block{i}"]["linear_attn"]
        for leaf in ("state", "conv"):
            np.testing.assert_array_equal(new[leaf][1], old[leaf][1])
            assert not np.array_equal(new[leaf][0], old[leaf][0])


def test_reference_controls_differ(model):
    cfg, params = model
    tokens = jax.random.randint(jax.random.key(7), (64,), 0, cfg.vocab_size)
    dims = dims_of(cfg)
    exact = ref.Forward(dims).logits(params, tokens)
    for kw in ({"decay": False}, {"double_beta": False}, {"quant": "fp8"}):
        other = ref.Forward(dims, **kw).logits(params, tokens)
        assert float(jnp.max(jnp.abs(other - exact))) > 1e-2, kw


@pytest.mark.parametrize("kw,match", [
    (dict(layer_kinds=("linear",) * 3), "entries for 4 layers"),
    (dict(layer_kinds=("linear", "dense", "full", "full")), "kind is"),
    (dict(linear=None), "takes its sizes"),
    (dict(layer_kinds=("window", "full", "full", "full")), "states its width"),
    (dict(norm_order="sandwich"), "norm_order"),
    (dict(qk_norm="head"), "qk_norm"),
])
def test_config_refusals(kw, match):
    with pytest.raises(ValueError, match=match):
        tiny_cfg(**kw)


def test_layer_kind_falls_back_to_the_window():
    cfg = TransformerConfig(num_layers=2, layer_windows=(None, 8))
    assert cfg.kinds == ("full", "window")
    assert tiny_cfg().kinds == KINDS
    assert tiny_cfg().layer_kind(None) == "full"
