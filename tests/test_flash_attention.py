"""Pallas flash attention (interpret mode on CPU) vs. sdpa ground truth."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tpudist.models import TransformerConfig, TransformerLM, sdpa
from tpudist.ops.flash_attention import flash_attention, flash_attention_fn


def _qkv(b=2, s=64, h=2, d=16, seed=0):
    rng = np.random.default_rng(seed)
    mk = lambda: jnp.asarray(rng.normal(size=(b, s, h, d)), jnp.float32)
    return mk(), mk(), mk()


@pytest.mark.parametrize("causal", [True, False])
@pytest.mark.parametrize("block", [16, 32, 64])
def test_flash_matches_sdpa(causal, block):
    q, k, v = _qkv()
    want = sdpa(q, k, v, causal=causal)
    got = flash_attention(q, k, v, causal=causal,
                          block_q=block, block_k=block)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=1e-5, rtol=1e-5)


def test_flash_uneven_blocks():
    q, k, v = _qkv(s=64)
    want = sdpa(q, k, v, causal=True)
    got = flash_attention(q, k, v, causal=True, block_q=16, block_k=32)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=1e-5, rtol=1e-5)


@pytest.mark.parametrize("causal", [True, False])
def test_flash_gradients_match(causal):
    q, k, v = _qkv(b=1, s=32, h=2, d=8)

    def loss_ref(q, k, v):
        return jnp.sum(jnp.square(sdpa(q, k, v, causal=causal)))

    def loss_flash(q, k, v):
        return jnp.sum(jnp.square(
            flash_attention(q, k, v, causal=causal, block_q=8, block_k=8)))

    ref_grads = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    got_grads = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    for g_ref, g_got in zip(ref_grads, got_grads):
        np.testing.assert_allclose(np.asarray(g_got), np.asarray(g_ref),
                                   atol=1e-4, rtol=1e-4)


@pytest.mark.parametrize("causal", [True, False])
def test_flash_gradients_uneven_blocks(causal):
    """The Pallas backward's dQ and dK/dV passes walk transposed grids;
    block_q != block_k exercises their causal-liveness predicates."""
    q, k, v = _qkv(b=1, s=64, h=2, d=16, seed=3)

    def loss_ref(q, k, v):
        return jnp.sum(jnp.square(sdpa(q, k, v, causal=causal)))

    def loss_flash(q, k, v):
        return jnp.sum(jnp.square(
            flash_attention(q, k, v, causal=causal, block_q=16, block_k=32)))

    ref_grads = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    got_grads = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    for g_ref, g_got in zip(ref_grads, got_grads):
        np.testing.assert_allclose(np.asarray(g_got), np.asarray(g_ref),
                                   atol=1e-4, rtol=1e-4)


def test_flash_gradients_bfloat16():
    """bf16 training path: backward kernels contract P/dS on the MXU in
    bf16 with f32 accumulation, like the forward."""
    q, k, v = (x.astype(jnp.bfloat16) for x in _qkv(b=1, s=32, h=2, d=8))

    def loss_ref(q, k, v):
        return jnp.sum(jnp.square(sdpa(q, k, v, causal=True)))

    def loss_flash(q, k, v):
        return jnp.sum(jnp.square(
            flash_attention(q, k, v, causal=True, block_q=16, block_k=16)))

    ref_grads = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    got_grads = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    for g_ref, g_got in zip(ref_grads, got_grads):
        np.testing.assert_allclose(
            np.asarray(g_got, np.float32), np.asarray(g_ref, np.float32),
            atol=5e-2, rtol=5e-2)


def test_flash_backward_memory_is_linear():
    """The jaxpr of the backward must not contain an [S, S]-shaped
    intermediate — the whole point of the kernelized backward."""
    s = 256
    q, k, v = _qkv(b=1, s=s, h=1, d=8, seed=5)

    def loss(q, k, v):
        return jnp.sum(flash_attention(q, k, v, causal=True,
                                       block_q=64, block_k=64))

    jaxpr = jax.make_jaxpr(jax.grad(loss, argnums=(0, 1, 2)))(q, k, v)
    for eqn in jaxpr.jaxpr.eqns:
        for var in eqn.outvars:
            shape = getattr(var.aval, "shape", ())
            assert not (len(shape) >= 2 and shape[-1] == s
                        and shape[-2] == s), (
                f"quadratic [{s}, {s}] intermediate: {eqn.primitive}")


def test_flash_matches_sdpa_bfloat16():
    """The three attention impls share f32 softmax statistics even when
    inputs are bf16 (sdpa uses preferred_element_type=f32)."""
    q, k, v = (x.astype(jnp.bfloat16) for x in _qkv(s=32))
    want = sdpa(q, k, v, causal=True)
    got = flash_attention(q, k, v, causal=True, block_q=16, block_k=16)
    np.testing.assert_allclose(
        np.asarray(got, np.float32), np.asarray(want, np.float32),
        atol=2e-2, rtol=2e-2)


def test_transformer_with_flash_attention():
    cfg = TransformerConfig(vocab_size=32, num_layers=1, num_heads=2,
                            embed_dim=16, max_seq_len=32)
    tokens = jnp.asarray(
        np.random.default_rng(0).integers(0, 32, (2, 32)), jnp.int32)
    ref = TransformerLM(cfg)
    params = ref.init(jax.random.key(0), tokens)["params"]
    want = ref.apply({"params": params}, tokens)
    flash_model = TransformerLM(
        cfg, attention_fn=flash_attention_fn(block_q=8, block_k=8))
    got = flash_model.apply({"params": params}, tokens)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=1e-4, rtol=1e-4)


def test_auto_block_defaults():
    """No block args: _auto_block picks the largest power-of-two divisor
    ≤ 1024, and the kernel matches sdpa with those defaults."""
    from tpudist.ops.flash_attention import _auto_block

    assert _auto_block(2048) == 1024
    assert _auto_block(8192) == 1024
    assert _auto_block(384) == 128   # 384 = 3·128
    assert _auto_block(96) == 32
    assert _auto_block(7) == 1
    for s in (64, 384, 2048):
        assert s % _auto_block(s) == 0

    q, k, v = (
        jax.random.normal(jax.random.key(i), (2, 384, 2, 64), jnp.float32)
        for i in range(3)
    )
    got = flash_attention(q, k, v, causal=True)  # defaults, interpret on CPU
    want = sdpa(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-3)


@pytest.mark.parametrize("causal", [True, False])
@pytest.mark.parametrize("h,h_kv", [(4, 2), (4, 1), (6, 3)])
def test_flash_gqa_matches_sdpa(causal, h, h_kv):
    """Grouped-query attention: K/V carry fewer heads; the kernel resolves
    the head group in its index maps (no expansion)."""
    rng = np.random.default_rng(0)
    q = jnp.asarray(rng.normal(size=(2, 32, h, 8)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(2, 32, h_kv, 8)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(2, 32, h_kv, 8)), jnp.float32)
    want = sdpa(q, k, v, causal=causal)
    got = flash_attention(q, k, v, causal=causal, block_q=16, block_k=16)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=1e-5, rtol=1e-5)


@pytest.mark.parametrize("causal", [True, False])
def test_flash_gqa_gradients_match(causal):
    """GQA backward: dK/dV must sum over each KV head's query group (the
    expanded inner grid of the dkv kernel)."""
    rng = np.random.default_rng(1)
    q = jnp.asarray(rng.normal(size=(1, 32, 4, 8)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(1, 32, 2, 8)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(1, 32, 2, 8)), jnp.float32)

    def loss_ref(q, k, v):
        return jnp.sum(jnp.square(sdpa(q, k, v, causal=causal)))

    def loss_flash(q, k, v):
        return jnp.sum(jnp.square(
            flash_attention(q, k, v, causal=causal, block_q=8, block_k=16)))

    ref_grads = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    got_grads = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    for g_ref, g_got in zip(ref_grads, got_grads):
        assert g_ref.shape == g_got.shape
        np.testing.assert_allclose(np.asarray(g_got), np.asarray(g_ref),
                                   atol=1e-4, rtol=1e-4)


def test_flash_gqa_rejects_non_multiple_heads():
    q, k, v = (jnp.zeros((1, 16, h, 8)) for h in (4, 3, 3))
    with pytest.raises(ValueError, match="multiple of kv heads"):
        flash_attention(q, k, v)


def test_transformer_gqa_with_flash_matches_sdpa_model():
    cfg = TransformerConfig(vocab_size=32, num_layers=1, num_heads=4,
                            num_kv_heads=2, embed_dim=32, max_seq_len=32)
    tokens = jnp.asarray(
        np.random.default_rng(0).integers(0, 32, (2, 32)), jnp.int32)
    ref = TransformerLM(cfg)
    params = ref.init(jax.random.key(0), tokens)["params"]
    want = ref.apply({"params": params}, tokens)
    flash_model = TransformerLM(
        cfg, attention_fn=flash_attention_fn(block_q=8, block_k=8))
    got = flash_model.apply({"params": params}, tokens)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=1e-4, rtol=1e-4)


def _sdpa_windowed(q, k, v, window):
    """Reference sliding-window attention via explicit band mask."""
    from tpudist.models.transformer import _masked_attend, repeat_kv

    k, v = repeat_kv(q, k, v)
    s = q.shape[1]
    pos = np.arange(s)
    mask = (pos[:, None] >= pos[None, :]) & (
        pos[:, None] - pos[None, :] < window)
    return _masked_attend(q, k, v, jnp.asarray(mask))


@pytest.mark.parametrize("window", [1, 8, 24, 64])
def test_flash_sliding_window_matches_reference(window):
    q, k, v = _qkv(s=64)
    want = _sdpa_windowed(q, k, v, window)
    got = flash_attention(q, k, v, causal=True, window=window,
                          block_q=16, block_k=16)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=1e-5, rtol=1e-5)


@pytest.mark.parametrize("window", [8, 24])
def test_flash_sliding_window_gradients(window):
    q, k, v = _qkv(b=1, s=32, h=2, d=8, seed=6)

    def loss_ref(q, k, v):
        return jnp.sum(jnp.square(_sdpa_windowed(q, k, v, window)))

    def loss_flash(q, k, v):
        return jnp.sum(jnp.square(flash_attention(
            q, k, v, causal=True, window=window, block_q=8, block_k=8)))

    ref_grads = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    got_grads = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    for g_ref, g_got in zip(ref_grads, got_grads):
        np.testing.assert_allclose(np.asarray(g_got), np.asarray(g_ref),
                                   atol=1e-4, rtol=1e-4)


def test_flash_window_gqa_composes():
    rng = np.random.default_rng(7)
    q = jnp.asarray(rng.normal(size=(1, 32, 4, 8)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(1, 32, 2, 8)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(1, 32, 2, 8)), jnp.float32)
    want = _sdpa_windowed(q, k, v, 8)
    got = flash_attention(q, k, v, causal=True, window=8,
                          block_q=8, block_k=8)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=1e-5, rtol=1e-5)


def test_flash_window_requires_causal():
    q, k, v = _qkv(s=32)
    with pytest.raises(ValueError, match="requires causal"):
        flash_attention(q, k, v, causal=False, window=8)


# -- what TransformerLM(remat=True) keeps of the kernel ----------------------

def _eqns(jaxpr):
    """Every equation of ``jaxpr`` and of the jaxprs inside its equations'
    parameters (remat, scan, custom_vjp and jit bodies)."""
    for eqn in jaxpr.eqns:
        yield eqn
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _eqns(sub)


def _wide_products(eqns, width):
    """How many ``dot_general`` equations give an output ``width`` wide."""
    return sum(e.primitive.name == "dot_general"
               and e.outvars[0].aval.shape[-1] == width for e in eqns)


def _grad_of(model, toks):
    from tpudist.ops.losses import cross_entropy

    def loss(p):
        logits = model.apply({"params": p}, toks)
        return cross_entropy(logits[:, :-1].reshape(-1, logits.shape[-1]),
                             toks[:, 1:].reshape(-1))

    return jax.value_and_grad(loss)


def _grad_eqns(model, toks, params):
    """Every equation of the jaxpr of ``model``'s loss and gradient."""
    return list(_eqns(jax.make_jaxpr(_grad_of(model, toks))(params).jaxpr))


def _bare_remat(monkeypatch):
    """``_remat_block`` as a bare ``nn.remat``: a block's input alone."""
    import flax.linen as nn

    from tpudist.models import transformer

    monkeypatch.setattr(
        transformer, "_remat_block",
        lambda: nn.remat(transformer.DecoderBlock, static_argnums=(2,)))


def _assert_trees_equal(a, b):
    jax.tree.map(lambda x, y: np.testing.assert_array_equal(
        np.asarray(x), np.asarray(y)), a, b)


def _assert_matches_no_remat(got, want, scan_layers):
    """Equal to the bit; under ``scan_layers`` to rounding: a scan body
    compiled with its remat is another CPU program than the plain body (so
    it was under the bare remat)."""
    if scan_layers:
        jax.tree.map(lambda x, y: np.testing.assert_allclose(
            np.asarray(x), np.asarray(y), rtol=1e-5, atol=1e-7), got, want)
    else:
        _assert_trees_equal(got, want)


@pytest.mark.parametrize("attention,scan_layers,fwd_calls", [
    ("flash", False, 2),    # one forward kernel a layer (a bare remat: 4)
    ("flash", True, 1),     # the forward scan's body alone (a bare remat: 2)
    ("sdpa", False, 0),     # no kernel and none of its names
], ids=["flash_unrolled", "flash_scan_layers", "sdpa"])
def test_remat_keeps_the_kernels_residuals(monkeypatch, attention,
                                           scan_layers, fwd_calls):
    """Under ``remat`` a block keeps its flash kernel's output and
    log-sum-exp, so the backward pass's recomputation holds no second
    forward kernel; what it hands the backward kernels is bit for bit what
    a second call would have produced."""
    from tpudist.models.transformer import MLP_PRE_NAME
    from tpudist.ops.flash_attention import FLASH_RESIDUALS

    cfg = TransformerConfig(vocab_size=32, num_layers=2, num_heads=2,
                            embed_dim=32, max_seq_len=32,
                            scan_layers=scan_layers)
    fn = (flash_attention_fn(block_q=16, block_k=16) if attention == "flash"
          else sdpa)
    toks = jnp.asarray(
        np.random.default_rng(0).integers(0, 32, (2, 32)), jnp.int32)
    plain = TransformerLM(cfg, attention_fn=fn)
    remat = TransformerLM(cfg, attention_fn=fn, remat=True)
    params = plain.init(jax.random.key(0), toks)["params"]

    def census(model):
        eqns = _grad_eqns(model, toks, params)
        kernels = [e.params["name"] for e in eqns
                   if e.primitive.name == "pallas_call"]
        names = sorted(e.params["name"] for e in eqns
                       if e.primitive.name == "name")
        return _wide_products(eqns, cfg.ffn_dim), kernels, names

    wide, kernels, names = census(remat)
    assert kernels.count("flash_fwd") == fwd_calls
    if attention == "flash":
        # the backward kernels stand: one of each a layer (one scan body)
        assert kernels.count("flash_bwd_dq") == fwd_calls
        assert kernels.count("flash_bwd_dkv") == fwd_calls
        assert set(names) == {*FLASH_RESIDUALS, MLP_PRE_NAME}
    else:
        # another attention function carries none of the kernel's names:
        # the block keeps its MLP's pre-activation alone
        assert set(names) == {MLP_PRE_NAME}

    got = _grad_of(remat, toks)(params)
    # the same model under a bare remat, which keeps a block's input alone:
    # twice the forward kernels, and the same numbers to the bit (the saved
    # tensor IS the recomputed one)
    with monkeypatch.context() as m:
        _bare_remat(m)
        bare_wide, bare_kernels, _ = census(remat)
        if attention == "flash":
            assert bare_kernels.count("flash_fwd") == 2 * fwd_calls
        else:
            assert bare_kernels == kernels == []
        # and one more up-projection a layer (a scan body)
        assert bare_wide - wide == (1 if scan_layers else cfg.num_layers)
        _assert_trees_equal(got, _grad_of(remat, toks)(params))

    _assert_matches_no_remat(got, _grad_of(plain, toks)(params), scan_layers)


@pytest.mark.parametrize("scan_layers", [False, True],
                         ids=["unrolled", "scan_layers"])
@pytest.mark.parametrize("mlp,products", [("gelu", 1), ("gated_silu", 2)])
def test_remat_keeps_the_mlps_pre_activation(monkeypatch, mlp, products,
                                             scan_layers):
    """Under ``remat`` a block keeps what its MLP's ``ffn_dim``-wide
    products gave (``up``; ``up`` and ``gate`` in the gated form), so the
    backward pass's recomputation holds none of them a second time; the
    activation is redone on the kept tensor, which is bit for bit what a
    second product would have given."""
    from tpudist.models.transformer import MLP_PRE_NAME

    cfg = TransformerConfig(vocab_size=48, num_layers=2, num_heads=2,
                            embed_dim=32, max_seq_len=16, mlp=mlp,
                            scan_layers=scan_layers)
    assert cfg.ffn_dim == 128     # no other tensor of the model is as wide
    toks = jnp.asarray(
        np.random.default_rng(1).integers(0, 48, (2, 16)), jnp.int32)
    plain = TransformerLM(cfg)
    remat = TransformerLM(cfg, remat=True)
    params = plain.init(jax.random.key(1), toks)["params"]
    bodies = 1 if scan_layers else cfg.num_layers

    def wide_products(model):
        return _wide_products(_grad_eqns(model, toks, params), cfg.ffn_dim)

    assert {e.params["name"] for e in _grad_eqns(remat, toks, params)
            if e.primitive.name == "name"} == {MLP_PRE_NAME}
    # as many products that wide as a step without remat: none runs twice
    wide = wide_products(remat)
    assert wide == wide_products(plain)
    got = _grad_of(remat, toks)(params)
    with monkeypatch.context() as m:
        _bare_remat(m)
        assert wide_products(remat) - wide == bodies * products
        _assert_trees_equal(got, _grad_of(remat, toks)(params))
    _assert_matches_no_remat(got, _grad_of(plain, toks)(params), scan_layers)


@pytest.mark.parametrize("first_k_dense,kept_products", [(0, 0), (1, 1)],
                         ids=["experts_alone", "one_dense_layer"])
def test_remat_leaves_an_expert_layer_to_its_own_vjp(monkeypatch,
                                                     first_k_dense,
                                                     kept_products):
    """An expert layer carries no name: under ``remat`` it is recomputed as
    under a bare ``remat``, and only a dense layer beside it keeps its
    product."""
    from tpudist.models import MoEConfig
    from tpudist.models.transformer import MLP_PRE_NAME

    cfg = TransformerConfig(vocab_size=48, num_layers=2, num_heads=2,
                            embed_dim=32, max_seq_len=16,
                            moe=MoEConfig(num_experts=4, top_k=2),
                            first_k_dense=first_k_dense)
    toks = jnp.asarray(
        np.random.default_rng(2).integers(0, 48, (2, 16)), jnp.int32)
    remat = TransformerLM(cfg, remat=True)
    params = TransformerLM(cfg).init(jax.random.key(2), toks)["params"]

    def census():
        eqns = _grad_eqns(remat, toks, params)
        return (sum(e.primitive.name == "dot_general" for e in eqns),
                any(e.primitive.name == "name"
                    and e.params["name"] == MLP_PRE_NAME for e in eqns))

    products, named = census()
    # the name rides in a dense layer's trace, never in an expert layer's
    assert named == bool(kept_products)
    got = _grad_of(remat, toks)(params)
    with monkeypatch.context() as m:
        _bare_remat(m)
        assert census() == (products + kept_products, named)
        _assert_trees_equal(got, _grad_of(remat, toks)(params))


@pytest.mark.parametrize("remat", [True, False], ids=["remat", "no_remat"])
def test_remat_publishes_how_many_names_it_keeps(remat):
    """Tracing a program through ``_remat_block`` sets the gauge to the
    policy's names (the kernel's two and the MLP's one); a model without
    ``remat`` never touches it."""
    from tpudist import obs

    cfg = TransformerConfig(vocab_size=32, num_layers=1, num_heads=2,
                            embed_dim=32, max_seq_len=16)
    toks = jnp.zeros((1, 16), jnp.int32)
    params = TransformerLM(cfg).init(jax.random.key(0), toks)["params"]
    gauge = obs.gauge("train/remat_kept_names")
    gauge.clear()
    jax.make_jaxpr(_grad_of(TransformerLM(cfg, remat=remat), toks))(params)
    assert gauge.value() == (3.0 if remat else None)
