"""One mesh-axis spec: the composed dp × fsdp × tp × pp × ep step must be
BITWISE the single-strategy program it replaces (same init, same data, same
global batch — only the axis names and the entry point differ), re-compile
cleanly when the MeshSpec changes between runs, keep the real-model 1F1B
path faithful to a sequential TransformerLM, and stay donation-safe."""

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest
from jax.sharding import PartitionSpec as P

from tpudist import obs
from tpudist.parallel.mesh import (
    MeshSpec,
    make_composed_state,
    make_composed_train_step,
    shard_composed_batch,
)
from tpudist.parallel.pipeline import (
    interleave_params,
    make_1f1b_pipeline_train_step,
    make_stacked_pipeline_train_step,
    stacked_state_specs,
    state_specs_like,
)
from tpudist.train.state import TrainState


# ---------------------------------------------------------------------------
# set-up shared by the matrix and the grow/shrink test
# ---------------------------------------------------------------------------

def _assert_bitwise(got, want):
    """Two pytrees of arrays, leaf for leaf, byte for byte."""
    la, lb = jax.tree.leaves(got), jax.tree.leaves(want)
    assert len(la) == len(lb)
    for a, b in zip(la, lb):
        a, b = np.asarray(a), np.asarray(b)
        assert a.tobytes() == b.tobytes(), (
            f"max_abs_diff {np.max(np.abs(a.astype(np.float64) - b))}")


def _run(step, state, batch, steps=2):
    metrics = None
    for _ in range(steps):
        state, metrics = step(state, *batch)
    jax.block_until_ready((state, metrics))
    return state, metrics


def _reports_flops(step, state, batch):
    """The composed step's ``.lower`` delegate yields cost_analysis FLOPs
    (the Trainer's ``xla/step_tflops`` / ``xla/mfu`` feed)."""
    from tpudist.obs import xla as obs_xla

    return obs_xla.cost_flops(step.lower(state, *batch)) is not None


def _lm_setup(num_layers=1):
    from tpudist.models import TransformerConfig, TransformerLM
    from tpudist.ops.losses import cross_entropy

    cfg = TransformerConfig(vocab_size=32, num_layers=num_layers,
                            num_heads=2, embed_dim=16, max_seq_len=8)
    model = TransformerLM(cfg)
    rng = np.random.default_rng(0)
    tokens = jnp.asarray(rng.integers(0, cfg.vocab_size, (8, 8)), jnp.int32)
    targets = jnp.roll(tokens, -1, axis=1)
    params = model.init(jax.random.key(0), tokens)["params"]

    def loss_fn(p, batch, rng):
        toks, tgts = batch
        logits = model.apply({"params": p}, toks)
        return cross_entropy(logits.reshape(-1, logits.shape[-1]),
                             tgts.reshape(-1)), {}

    return cfg, model, params, loss_fn, (tokens, targets)


def _check_gspmd_combo(spec, ref_axes, ref_specs_fn, ref_data_axes,
                       model, params, loss_fn, batch):
    """One GSPMD point of the matrix: two steps of the composed step
    against the single-strategy step assembled from the same blocks."""
    from jax.sharding import NamedSharding

    from tpudist.parallel.tensor_parallel import (
        make_spmd_train_step, shard_tree,
    )
    from tpudist.runtime.mesh import make_mesh

    devs = jax.devices()[: spec.n_devices]
    tx = optax.sgd(0.1)

    ref_mesh = make_mesh(ref_axes, devs)
    ref_specs = ref_specs_fn(ref_mesh)
    ref_state = TrainState.create(
        model.apply, shard_tree(params, ref_mesh, ref_specs), tx)
    ref_step = make_spmd_train_step(loss_fn, ref_mesh, ref_specs,
                                    donate=False)
    ref_batch = jax.tree.map(
        lambda x: jax.device_put(
            x, NamedSharding(ref_mesh, P(ref_data_axes))), batch)
    ref_state, ref_metrics = _run(ref_step, ref_state, ref_batch)

    mesh = spec.build(devs)
    step = make_composed_train_step(spec, mesh, loss_fn, params=params,
                                    donate=False)
    state, _ = make_composed_state(model.apply, params, tx, spec, mesh)
    cbatch = shard_composed_batch(batch, mesh, spec)
    state, metrics = _run(step, state, cbatch)

    _assert_bitwise((metrics["loss"], state.params),
                    (ref_metrics["loss"], ref_state.params))
    assert _reports_flops(step, state, cbatch)


# ---------------------------------------------------------------------------
# composition matrix: each combo vs its single-strategy reference
# ---------------------------------------------------------------------------

class TestCompositionMatrix:
    """Six points of the dp x fsdp x tp x pp x ep space, each BITWISE its
    single-strategy entry point at equal global batch, each reporting
    FLOPs through ``.lower``.  The GSPMD and expert points are
    slow-marked (each compiles two LM programs); the two pipeline points
    run in the fast tier."""

    @pytest.mark.slow
    def test_gspmd_combos_bitwise(self, devices8):
        from tpudist.parallel.fsdp import fsdp_specs
        from tpudist.parallel.tensor_parallel import (
            spec_tree_from_rules, transformer_tp_rules,
        )

        cfg, model, params, loss_fn, batch = _lm_setup()
        tp_rules = tuple(transformer_tp_rules("tp"))

        def fsdp_tp(m):
            return fsdp_specs(params, m, axis="fsdp",
                              tp_rules=transformer_tp_rules("model"))

        _check_gspmd_combo(                                   # dp2_tp2
            MeshSpec(dp=2, tp=2, rules=tp_rules),
            {"data": 2, "model": 2},
            lambda m: spec_tree_from_rules(
                params, transformer_tp_rules("model")),
            "data", model, params, loss_fn, batch)
        _check_gspmd_combo(                                   # fsdp2_tp2
            MeshSpec(fsdp=2, tp=2, rules=tp_rules),
            {"fsdp": 2, "model": 2}, fsdp_tp,
            "fsdp", model, params, loss_fn, batch)
        _check_gspmd_combo(                                   # dp2_fsdp2_tp2
            MeshSpec(dp=2, fsdp=2, tp=2, rules=tp_rules),
            {"data": 2, "fsdp": 2, "model": 2}, fsdp_tp,
            ("data", "fsdp"), model, params, loss_fn, batch)

    def test_pipeline_combos_bitwise(self, devices8):
        """dp x pp (1F1B) and dp x pp x tp (stacked schedule, Megatron
        block) against the direct pipeline entry points."""
        from tpudist.parallel.common import id_fwd_psum_bwd, psum_fwd_id_bwd
        from tpudist.runtime.mesh import make_mesh

        rng = np.random.default_rng(0)
        M, d, ff, Pp = 4, 8, 16, 2
        tx = optax.sgd(0.1)

        def mse(out, y):
            return jnp.mean((out - y) ** 2)

        batch = (jnp.asarray(rng.standard_normal((16, d)), jnp.float32),
                 jnp.asarray(rng.standard_normal((16, d)), jnp.float32))

        # -- dp2 x pp2: homogeneous tanh blocks through 1F1B
        params = {
            "w": jnp.asarray(rng.standard_normal((Pp, d, d)) * 0.3,
                             jnp.float32),
            "b": jnp.zeros((Pp, d), jnp.float32),
        }

        def block(p, a):
            return jnp.tanh(a @ p["w"] + p["b"])

        devs = jax.devices()[:4]
        ref_state = TrainState.create(None, params, tx)
        ref_step = make_1f1b_pipeline_train_step(
            block, mse, make_mesh({"data": 2, "stage": Pp}, devs), M,
            ref_state, donate=False)
        ref_state, ref_metrics = _run(ref_step, ref_state, batch)

        spec = MeshSpec(dp=2, pp=Pp, num_microbatches=M)
        state = TrainState.create(None, params, tx)
        step = make_composed_train_step(
            spec, spec.build(devs), block_fn=block, stage_loss_fn=mse,
            state_example=state, donate=False)
        state, metrics = _run(step, state, batch)
        _assert_bitwise((metrics["loss"], state.params),
                        (ref_metrics["loss"], ref_state.params))
        assert _reports_flops(step, state, batch)
        assert 0 < step.bubble_fraction < 1

        # -- dp2 x pp2 x tp2: stacked schedule, Megatron MLP block
        params3 = {
            "up": jnp.asarray(rng.standard_normal((Pp, d, ff)) * 0.3,
                              jnp.float32),
            "down": jnp.asarray(rng.standard_normal((Pp, ff, d)) * 0.3,
                                jnp.float32),
        }

        def tp_block(axis):
            def fn(p, a):
                a = id_fwd_psum_bwd(a, axis)
                h = jnp.tanh(a @ p["up"])
                return psum_fwd_id_bwd(h @ p["down"], axis)
            return fn

        devs8 = jax.devices()[:8]
        ref_mesh = make_mesh({"data": 2, "stage": Pp, "model": 2}, devs8)
        ref_state = TrainState.create(None, params3, tx)
        ref_specs = state_specs_like(
            ref_state, {"up": P("stage", None, "model"),
                        "down": P("stage", "model", None)})
        ref_step = make_stacked_pipeline_train_step(
            tp_block("model"), mse, ref_mesh, M, ref_state,
            state_specs=ref_specs, grad_sync_axes=("model",), donate=False)
        ref_state, ref_metrics = _run(ref_step, ref_state, batch)

        spec = MeshSpec(dp=2, pp=Pp, tp=2, num_microbatches=M)
        state = TrainState.create(None, params3, tx)
        specs = state_specs_like(
            state, {"up": P("pp", None, "tp"), "down": P("pp", "tp", None)})
        step = make_composed_train_step(
            spec, spec.build(devs8), block_fn=tp_block("tp"),
            stage_loss_fn=mse, state_example=state, state_specs=specs,
            grad_sync_axes=("tp",), donate=False)
        state, metrics = _run(step, state, batch)
        _assert_bitwise((metrics["loss"], state.params),
                        (ref_metrics["loss"], ref_state.params))
        assert _reports_flops(step, state, batch)
        assert 0 < step.bubble_fraction < 1

    @pytest.mark.slow
    def test_ep_combo_bitwise(self, devices8):
        from tpudist.models import (
            MoEConfig, MoETransformerLM, TransformerConfig,
        )
        from tpudist.ops.losses import cross_entropy
        from tpudist.parallel.expert_parallel import moe_ep_rules
        from tpudist.parallel.tensor_parallel import spec_tree_from_rules

        cfg = TransformerConfig(vocab_size=32, num_layers=1, num_heads=2,
                                embed_dim=16, max_seq_len=8)
        model = MoETransformerLM(cfg, MoEConfig(num_experts=2, top_k=1,
                                                capacity_factor=4.0))
        tokens = jnp.asarray(
            np.random.default_rng(0).integers(0, 32, (8, 8)), jnp.int32)
        params = model.init(jax.random.key(0), tokens)["params"]

        def loss_fn(p, batch, rng):
            (toks,) = batch
            logits, aux = model.apply({"params": p}, toks)
            ce = cross_entropy(logits[:, :-1].reshape(-1, cfg.vocab_size),
                               toks[:, 1:].reshape(-1))
            return ce + aux, {}

        # the reference keeps the expert rules on their native axis name;
        # the composed step uses the same rules over "ep"
        _check_gspmd_combo(
            MeshSpec(dp=2, ep=2, rules=tuple(moe_ep_rules("ep"))),
            {"data": 2, "expert": 2},
            lambda m: spec_tree_from_rules(params, moe_ep_rules("expert")),
            "data", model, params, loss_fn, (tokens,))


# ---------------------------------------------------------------------------
# real multi-stage TransformerLM through the interleaved 1F1B schedule
# ---------------------------------------------------------------------------

@pytest.mark.slow
def test_real_lm_interleaved_1f1b_matches_sequential(devices8):
    """4-layer TransformerLM split into P=2 × V=2 chunks with dp=2: the
    composed 1F1B step (embedding and head riding the extra-params path,
    stage-boundary activations over the ppermute ring) must train like the
    plain full-model step."""
    from tpudist.models import TransformerConfig, TransformerLM
    from tpudist.models.transformer import DecoderBlock
    from tpudist.ops.losses import cross_entropy
    import flax.linen as nn

    Pp, V, M, dp = 2, 2, 4, 2
    L = Pp * V
    cfg = TransformerConfig(vocab_size=32, num_layers=L, num_heads=2,
                            embed_dim=16, max_seq_len=8)
    rng = np.random.default_rng(0)
    tokens = jnp.asarray(rng.integers(0, 32, (16, 8)), jnp.int32)
    targets = jnp.roll(tokens, -1, axis=1)
    model = TransformerLM(cfg)
    flat = model.init(jax.random.key(0), tokens[:2])["params"]

    # sequential reference: one full-model CE step on one device
    def ref_loss(p):
        logits = model.apply({"params": p}, tokens)
        return cross_entropy(logits.reshape(-1, cfg.vocab_size),
                             targets.reshape(-1))

    loss0, grads = jax.value_and_grad(ref_loss)(flat)
    ref_params = TrainState.create(None, flat, optax.sgd(0.1)).apply_gradients(
        grads).params

    stages = jax.tree.map(lambda *xs: jnp.stack(xs),
                          *[flat[f"block{i}"] for i in range(L)])
    stages = interleave_params(stages, Pp, V)
    extra = {k: v for k, v in flat.items() if not k.startswith("block")}
    state = TrainState.create(None, {"stages": stages, "extra": extra},
                              optax.sgd(0.1))

    block_mod = DecoderBlock(cfg)
    ln_f = nn.LayerNorm(name="ln_f")

    def block_fn(p, a):
        return block_mod.apply({"params": p}, a)

    def embed_fn(ex, x_mb):
        a = jnp.take(ex["tok_embed"]["embedding"], x_mb, axis=0)
        pos = jnp.arange(x_mb.shape[1])
        return a + jnp.take(ex["pos_embed"]["embedding"], pos, axis=0)[None]

    def head_loss_fn(ex, out, y_mb):
        h = ln_f.apply({"params": ex["ln_f"]}, out)
        logits = h @ ex["lm_head"]["kernel"]
        return cross_entropy(logits.reshape(-1, cfg.vocab_size),
                             y_mb.reshape(-1))

    spec = MeshSpec(dp=dp, pp=Pp, num_microbatches=M, virtual_stages=V)
    step = make_composed_train_step(
        spec, spec.build(jax.devices()[:4]), block_fn=block_fn,
        embed_fn=embed_fn, head_loss_fn=head_loss_fn, state_example=state,
        donate=False)
    new_state, metrics = step(state, tokens, targets)

    np.testing.assert_allclose(float(metrics["loss"]), float(loss0),
                               rtol=1e-5)
    # fold the reference into the same interleaved stacked layout
    ref_stages = interleave_params(
        jax.tree.map(lambda *xs: jnp.stack(xs),
                     *[ref_params[f"block{i}"] for i in range(L)]), Pp, V)
    ref_extra = {k: v for k, v in ref_params.items()
                 if not k.startswith("block")}
    jax.tree.map(
        lambda a, b: np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), atol=2e-5, rtol=1e-4),
        new_state.params, {"stages": ref_stages, "extra": ref_extra})
    assert step.bubble_fraction < 0.5


# ---------------------------------------------------------------------------
# grow / shrink: changing the MeshSpec between runs re-compiles cleanly
# ---------------------------------------------------------------------------

def test_meshspec_grow_shrink_recompile(devices8):
    """Step 1 under MeshSpec(dp=4), step 2 under MeshSpec(dp=2, tp=2) from
    the step-1 weights: both layouts must continue the exact single-device
    trajectory — proof that a spec change between runs is a clean re-shard
    + re-compile, not a silent layout corruption."""
    from tpudist.parallel.tensor_parallel import transformer_tp_rules

    cfg, model, params, loss_fn, batch = _lm_setup()
    tx = optax.sgd(0.1)

    ref_state = TrainState.create(model.apply, params, tx)
    ref_losses = []
    for _ in range(2):
        (l, _), g = jax.value_and_grad(loss_fn, has_aux=True)(
            ref_state.params, batch, ref_state.rng)
        ref_losses.append(float(l))
        ref_state = ref_state.apply_gradients(g)

    spec_a = MeshSpec(dp=4)
    mesh_a = spec_a.build(jax.devices()[:4])
    step_a = make_composed_train_step(spec_a, mesh_a, loss_fn, params=params,
                                      donate=False)
    state_a, _ = make_composed_state(model.apply, params, tx, spec_a, mesh_a)
    state_a, metrics_a = step_a(state_a,
                                *shard_composed_batch(batch, mesh_a, spec_a))

    # "shrink dp, grow tp": rebuild the world from the updated weights
    host_params = jax.device_get(state_a.params)
    spec_b = MeshSpec(dp=2, tp=2, rules=tuple(transformer_tp_rules("tp")))
    mesh_b = spec_b.build(jax.devices()[:4])
    step_b = make_composed_train_step(spec_b, mesh_b, loss_fn,
                                      params=host_params, donate=False)
    state_b, _ = make_composed_state(model.apply, host_params, tx, spec_b,
                                     mesh_b)
    state_b, metrics_b = step_b(state_b,
                                *shard_composed_batch(batch, mesh_b, spec_b))

    np.testing.assert_allclose(float(metrics_a["loss"]), ref_losses[0],
                               rtol=1e-6)
    np.testing.assert_allclose(float(metrics_b["loss"]), ref_losses[1],
                               rtol=1e-6)
    jax.tree.map(
        lambda a, b: np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), atol=1e-5, rtol=1e-5),
        jax.device_get(state_b.params), jax.device_get(ref_state.params))


# ---------------------------------------------------------------------------
# donation safety: pp stage buffers
# ---------------------------------------------------------------------------

def test_pp_stage_buffer_donation_safety(devices8):
    """donate=True must be a pure perf knob for the pipeline step: two
    donating steps produce bitwise the same trajectory as two non-donating
    ones, and the donated state buffers are actually consumed."""
    rng = np.random.default_rng(0)
    d, M, Pp = 8, 4, 2
    params = {
        "w": jnp.asarray(rng.standard_normal((Pp, d, d)) * 0.3, jnp.float32),
        "b": jnp.zeros((Pp, d), jnp.float32),
    }

    def block(p, a):
        return jnp.tanh(a @ p["w"] + p["b"])

    def mse(out, y):
        return jnp.mean((out - y) ** 2)

    x = jnp.asarray(rng.standard_normal((16, d)), jnp.float32)
    y = jnp.asarray(rng.standard_normal((16, d)), jnp.float32)
    spec = MeshSpec(dp=2, pp=Pp, num_microbatches=M)
    mesh = spec.build(jax.devices()[:4])

    def run(donate):
        state = TrainState.create(None, params, optax.sgd(0.1))
        step = make_composed_train_step(
            spec, mesh, block_fn=block, stage_loss_fn=mse,
            state_example=state, donate=donate)
        mid, _ = step(state, x, y)
        state, metrics = step(mid, x, y)
        jax.block_until_ready(state)
        return mid, state, metrics

    mid_d, state_d, metrics_d = run(donate=True)
    _, state_nd, metrics_nd = run(donate=False)
    assert np.asarray(metrics_d["loss"]).tobytes() == np.asarray(
        metrics_nd["loss"]).tobytes()
    for a, b in zip(jax.tree.leaves(state_d.params),
                    jax.tree.leaves(state_nd.params)):
        assert np.asarray(a).tobytes() == np.asarray(b).tobytes()
    # the donating step really consumed its (correctly laid-out) input
    # stage buffers — the step-1 output fed to step 2
    assert all(leaf.is_deleted() for leaf in jax.tree.leaves(mid_d.params))


# ---------------------------------------------------------------------------
# state-spec mirroring: explicit overrides + the naming error (satellite 2)
# ---------------------------------------------------------------------------

class TestStateSpecOverrides:
    def _state(self):
        params = {"w": jnp.zeros((4, 8)), "b": jnp.zeros((4,))}
        return TrainState.create(None, params, optax.adam(1e-3))

    def test_mirroring_still_guessed_for_exact_match(self):
        state = self._state()
        specs = {"w": P("fsdp", None), "b": P("fsdp")}
        out = state_specs_like(state, specs)
        # Adam's mu/nu mirror the params; count replicates
        mus = [s for s in jax.tree.leaves(
            out.opt_state, is_leaf=lambda x: isinstance(x, P))]
        assert P("fsdp", None) in mus and P("fsdp") in mus and P() in mus

    def test_structure_match_with_shape_mismatch_names_subtree(self):
        state = self._state()
        # same tree STRUCTURE as params, different leaf shapes — the case
        # the old heuristic silently replicated
        weird = {"w": jnp.zeros((4, 2)), "b": jnp.zeros((2,))}
        state = state.replace(opt_state=(state.opt_state[0], weird))
        with pytest.raises(ValueError, match=r"mirrors=") as ei:
            state_specs_like(state, {"w": P("fsdp", None), "b": P("fsdp")})
        # the error names the offending subtree path
        assert "[1]" in str(ei.value)

    def test_mirrors_override_resolves_both_ways(self):
        state = self._state()
        weird = {"w": jnp.zeros((4, 2)), "b": jnp.zeros((2,))}
        state = state.replace(opt_state=(state.opt_state[0], weird))
        specs = {"w": P("fsdp", None), "b": P("fsdp")}
        out = state_specs_like(state, specs, mirrors={"[1]": False})
        assert jax.tree.leaves(
            out.opt_state[1], is_leaf=lambda x: isinstance(x, P)
        ) == [P(), P()]
        out = state_specs_like(state, specs, mirrors={"[1]": True})
        assert out.opt_state[1] == specs

    def test_stacked_specs_override_pins_false_positive(self):
        # a [P, P] leaf looks stage-stacked to the shape heuristic
        params = {"stacked": jnp.zeros((2, 8)), "table": jnp.zeros((2, 2))}
        state = TrainState.create(None, params, optax.sgd(0.1))
        guessed = stacked_state_specs(state, 2)
        assert guessed.params["table"] == P("stage")  # the trap
        pinned = stacked_state_specs(state, 2, overrides={"table": P()})
        assert pinned.params["table"] == P()
        assert pinned.params["stacked"] == P("stage")


# ---------------------------------------------------------------------------
# MeshSpec surface: validation, parsing, gauges, trainer integration
# ---------------------------------------------------------------------------

class TestMeshSpecSurface:
    def test_parse_and_sizes(self):
        spec = MeshSpec.parse("dp=2, fsdp=2,tp=2")
        assert (spec.dp, spec.fsdp, spec.tp, spec.pp, spec.ep) == (
            2, 2, 2, 1, 1)
        assert spec.n_devices == 8
        assert spec.batch_spec() == P(("dp", "fsdp"))
        with pytest.raises(ValueError, match="unknown mesh axis"):
            MeshSpec.parse("dp=2,bogus=2")

    def test_pp_with_fsdp_or_ep_rejected(self, devices8):
        spec = MeshSpec(fsdp=2, pp=2, num_microbatches=2)
        with pytest.raises(ValueError, match="not supported"):
            make_composed_train_step(
                spec, spec.build(jax.devices()[:4]), block_fn=lambda p, a: a,
                stage_loss_fn=lambda o, y: jnp.mean(o),
                state_example=TrainState.create(
                    None, {"w": jnp.zeros((2, 4))}, optax.sgd(0.1)))

    def test_mesh_spec_mismatch_rejected(self, devices8):
        spec = MeshSpec(dp=2, tp=2)
        other = MeshSpec(dp=4).build(jax.devices()[:4])
        with pytest.raises(ValueError, match="build the mesh with"):
            make_composed_train_step(spec, other, lambda p, b, r: (0.0, {}))

    def test_gauges_published(self, devices8):
        spec = MeshSpec(dp=2, pp=2, num_microbatches=4)
        state = TrainState.create(
            None, {"w": jnp.zeros((2, 4, 4))}, optax.sgd(0.1))
        step = make_composed_train_step(
            spec, spec.build(jax.devices()[:4]),
            block_fn=lambda p, a: jnp.tanh(a @ p["w"]),
            stage_loss_fn=lambda o, y: jnp.mean((o - y) ** 2),
            state_example=state, donate=False)
        assert obs.gauge("mesh/axis_size~axis=dp").value() == 2.0
        assert obs.gauge("mesh/axis_size~axis=pp").value() == 2.0
        assert obs.gauge("mesh/axis_size~axis=fsdp").value() == 1.0
        assert obs.gauge("train/bubble_fraction").value() == pytest.approx(
            step.bubble_fraction)

    def test_trainer_takes_meshspec(self, tmp_path, devices8):
        """TrainerConfig selects axis sizes, not strategy functions: the
        same Trainer call trains dp×fsdp×tp from a MeshSpec, with the
        batch sharded over both data axes and eval running as a GSPMD
        global program."""
        from tpudist.data.loader import ShardedLoader
        from tpudist.data.mnist import synthetic_mnist
        from tpudist.models import MLP
        from tpudist.train.trainer import Trainer, TrainerConfig

        spec = MeshSpec.parse("dp=2,fsdp=2,tp=2")
        mesh = spec.build()
        train_ds = synthetic_mnist("train", n=256)
        test_ds = synthetic_mnist("test", n=128)
        loaders = [
            ShardedLoader([ds.images, ds.labels], global_batch=64,
                          mesh=mesh, data_axis=("dp", "fsdp"))
            for ds in (train_ds, test_ds)
        ]
        model = MLP(hidden_layers=1, features=64)
        params = model.init(jax.random.key(0), train_ds.images[:1])["params"]
        config = TrainerConfig(
            total_epochs=1, batch_size=64, log_every=1000,
            snapshot_path=str(tmp_path / "snap.npz"),
            mesh_axes="dp=2,fsdp=2,tp=2")
        trainer = Trainer(config, model.apply, params, optax.adam(1e-3),
                          spec, loaders[0], loaders[1])
        assert trainer.mesh_spec == spec
        summary = trainer.train()
        assert np.isfinite(summary["loss"])
        assert 0.0 <= summary["test_accuracy"] <= 1.0
        # cost probe worked through the composed step's .lower delegate
        assert trainer._step_flops is not None

    def test_trainer_rejects_pp_spec(self, devices8):
        from tpudist.data.loader import ShardedLoader
        from tpudist.data.mnist import synthetic_mnist
        from tpudist.models import MLP
        from tpudist.train.trainer import Trainer, TrainerConfig

        spec = MeshSpec(dp=2, pp=2, num_microbatches=4)
        ds = synthetic_mnist("train", n=64)
        loader = ShardedLoader([ds.images, ds.labels], global_batch=16,
                               mesh=spec.build(), data_axis="dp")
        model = MLP(hidden_layers=1, features=8)
        params = model.init(jax.random.key(0), ds.images[:1])["params"]
        with pytest.raises(ValueError, match="make_composed_train_step"):
            Trainer(TrainerConfig(total_epochs=1, batch_size=16), model.apply,
                    params, optax.sgd(0.1), spec, loader)
