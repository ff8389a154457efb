"""Runner for the DeepSeek-V3 serving cells: ``ServeLoop`` over a model with
latent attention and expert layers, one chip's share of a stated deployment.

The load, the warm-up, the window's sums and the sample for the reference
are ``harness/serve.py``'s (imported; that file builds GPTBigCode
dimensions, weights and reference and is not this runner's to edit).  This
module brings what differs: the dimensions from a ``deepseek_v3`` config
file, seeded weights drawn leaf by leaf on the device, the loop's
``TransformerConfig``, and the comparison with the plain reference
(``benchmarks/reference/deepseek_v3.py``).  ``run`` follows ``serve.run``
step for step.
"""

from __future__ import annotations

import functools
import math
import time

import jax
import numpy as np

from benchmarks.harness import common, weights
from benchmarks.harness.serve import (Load, _bucket, loop_options,
                                      pick_sample, set_program_env,
                                      summarize, warm_up)
from benchmarks.reference import deepseek_v3 as ref
from benchmarks.traffic import generator

BIAS_STD = 0.01  # of the seeded correction bias (trained in the real model)


def model_dims(config: dict, tiny: bool = False) -> ref.Dims:
    """The reference's ``Dims`` from the configuration file: the published
    keys, ``held`` (which routed experts live here, of the router's width)
    and, for the rehearsal, the overrides under ``tiny``."""
    src = dict(config)
    yarn = dict(config["rope_scaling"])
    router_width = config["held"]["router_width"]
    if tiny:
        t = config["tiny"]
        src.update(t)
        router_width = t["router_width"]
        yarn["original_max_position_embeddings"] = t[
            "original_max_position_embeddings"]
    if yarn["type"] != "yarn" or src["scoring_func"] != "sigmoid":
        raise ValueError("this runner builds the deepseek_v3 block only")
    first = config["held"]["routed_experts"][0]
    return ref.Dims(
        vocab=src["vocab_size"], layers=src["num_hidden_layers"],
        first_k_dense=src["first_k_dense_replace"], embed=src["hidden_size"],
        heads=src["num_attention_heads"], q_lora=src["q_lora_rank"],
        kv_lora=src["kv_lora_rank"], nope=src["qk_nope_head_dim"],
        rope=src["qk_rope_head_dim"], v_head=src["v_head_dim"],
        dense_ff=src["intermediate_size"],
        expert_ff=src["moe_intermediate_size"], experts=router_width,
        top_k=src["num_experts_per_tok"], n_group=src["n_group"],
        topk_group=src["topk_group"],
        routed_scale=float(src["routed_scaling_factor"]),
        n_shared=src["n_shared_experts"],
        held=(first, src["n_routed_experts"]),
        norm_eps=float(src["rms_norm_eps"]),
        rope_theta=float(src["rope_theta"]),
        yarn_factor=float(yarn["factor"]),
        yarn_original=yarn["original_max_position_embeddings"],
        yarn_beta_fast=float(yarn["beta_fast"]),
        yarn_beta_slow=float(yarn["beta_slow"]),
        yarn_mscale=float(yarn["mscale"]),
        yarn_mscale_all_dim=float(yarn["mscale_all_dim"]))


def max_seq_len(config: dict, tiny: bool) -> int:
    return (config["tiny"] if tiny else config["program"])["max_seq_len"]


def transformer_config(dims: ref.Dims, positions: int, dtype):
    from tpudist.models import (MLAConfig, MoEConfig, TransformerConfig,
                                YarnScaling)

    moe = MoEConfig(
        num_experts=dims.experts, top_k=dims.top_k, experts="gated_silu",
        d_ff=dims.expert_ff, scoring="sigmoid", n_group=dims.n_group,
        topk_group=dims.topk_group, routed_scale=dims.routed_scale,
        correction_bias=True, n_shared=dims.n_shared, held=dims.held)
    return TransformerConfig(
        vocab_size=dims.vocab, num_layers=dims.layers,
        num_heads=dims.heads, embed_dim=dims.embed, max_seq_len=positions,
        compute_dtype=dtype, norm="rmsnorm", norm_eps=dims.norm_eps,
        positions="rotary", rope_theta=dims.rope_theta,
        rope_scaling=YarnScaling(
            factor=dims.yarn_factor,
            original_max_position=dims.yarn_original,
            beta_fast=dims.yarn_beta_fast, beta_slow=dims.yarn_beta_slow,
            mscale=dims.yarn_mscale,
            mscale_all_dim=dims.yarn_mscale_all_dim),
        mlp="gated_silu", mlp_dim=dims.dense_ff,
        mla=MLAConfig(dims.q_lora, dims.kv_lora, dims.nope, dims.rope,
                      dims.v_head),
        moe=moe, first_k_dense=dims.first_k_dense)


def leaf_table(dims: ref.Dims) -> list[tuple[tuple[str, ...], tuple, float]]:
    """``(path, shape, std)`` per leaf in a fixed order, under the names
    ``TransformerLM`` gives its parameters; ``std`` -1 marks an RMSNorm
    scale (ones)."""
    e, h = dims.embed, dims.heads
    dense = lambda fan_in: 1.0 / math.sqrt(fan_in)  # noqa: E731
    held = dims.held[1]
    out = [(("tok_embed", "embedding"), (dims.vocab, e), dense(e))]
    for i in range(dims.layers):
        b = f"block{i}"
        out += [
            ((b, "ln1", "scale"), (e,), -1.0),
            ((b, "attn", "q_a", "kernel"), (e, dims.q_lora), dense(e)),
            ((b, "attn", "q_norm", "scale"), (dims.q_lora,), -1.0),
            ((b, "attn", "q_b", "kernel"),
             (dims.q_lora, h * (dims.nope + dims.rope)), dense(dims.q_lora)),
            ((b, "attn", "kv_a", "kernel"), (e, dims.kv_lora + dims.rope),
             dense(e)),
            ((b, "attn", "kv_norm", "scale"), (dims.kv_lora,), -1.0),
            ((b, "attn", "kv_b", "kernel"),
             (dims.kv_lora, h * (dims.nope + dims.v_head)),
             dense(dims.kv_lora)),
            ((b, "attn", "proj", "kernel"), (h * dims.v_head, e),
             dense(h * dims.v_head)),
            ((b, "ln2", "scale"), (e,), -1.0),
        ]
        if i < dims.first_k_dense:
            f = dims.dense_ff
            out += [((b, "mlp", "gate", "kernel"), (e, f), dense(e)),
                    ((b, "mlp", "up", "kernel"), (e, f), dense(e)),
                    ((b, "mlp", "down", "kernel"), (f, e), dense(f))]
            continue
        f, fs = dims.expert_ff, dims.n_shared * dims.expert_ff
        out += [
            ((b, "moe", "router", "kernel"), (e, dims.experts), dense(e)),
            ((b, "moe", "router_bias"), (dims.experts,), BIAS_STD),
            ((b, "moe", "w_gate"), (held, e, f), dense(e)),
            ((b, "moe", "w_up"), (held, e, f), dense(e)),
            ((b, "moe", "w_down"), (held, f, e), dense(f)),
            ((b, "moe", "shared", "gate", "kernel"), (e, fs), dense(e)),
            ((b, "moe", "shared", "up", "kernel"), (e, fs), dense(e)),
            ((b, "moe", "shared", "down", "kernel"), (fs, e), dense(fs)),
        ]
    out += [(("ln_f", "scale"), (e,), -1.0),
            (("lm_head", "kernel"), (e, dims.vocab), dense(e))]
    return out


@functools.partial(jax.jit, static_argnums=(2, 3, 4))
def _draw_leaf(key, index, shape, std, dtype):
    return weights._draw(key, index, shape, std, dtype)


def make_params(seed: int, dims: ref.Dims, dtype) -> dict:
    """The tree on the default device, a leaf a call: a held-experts leaf
    is 0.47 GB in bfloat16 and twice that while it is drawn in float32,
    so the draws do not share one program."""
    import jax.numpy as jnp

    key = weights.seed_key(seed)
    return weights._nest({
        path: _draw_leaf(key, i, shape, std, jnp.dtype(dtype))
        for i, (path, shape, std) in enumerate(leaf_table(dims))})


def count_params(dims: ref.Dims) -> int:
    return sum(math.prod(shape) for _, shape, _ in leaf_table(dims))


def build_loop(config: dict, dims: ref.Dims, params, tiny: bool):
    import jax.numpy as jnp

    from tpudist.models import ServeLoop

    cfg = transformer_config(dims, max_seq_len(config, tiny), jnp.bfloat16)
    return ServeLoop(cfg, params, **loop_options(config, tiny))


def reference_gaps(params, dims: ref.Dims, positions: int, sample,
                   quant=None) -> dict:
    """As ``serve.reference_gaps``: teacher-forced reference logits at
    every served position of the sample, and how far the served token's
    logit lies below the reference's best.  With ``quant`` the reference
    runs in that lower precision too and the gap is read for the token IT
    puts first (the control).  Also the share of the routed work the
    reference's OWN router sends to the held experts (``held_share``)."""
    import jax.numpy as jnp

    exact = ref.Forward(dims)
    low = ref.Forward(dims, quant=quant) if quant else None
    worst, total, matches, checked = 0.0, 0.0, 0, 0
    routed = held = 0
    first_held, n_held = dims.held
    for comp in sample:
        served = np.asarray(comp.tokens)
        seq = np.concatenate([np.asarray(comp.prompt), served[:-1]])
        first = len(comp.prompt) - 1
        padded = np.zeros(_bucket(len(seq), positions), np.int32)
        padded[: len(seq)] = seq
        tokens = jnp.asarray(padded)
        hidden, chosen = exact.hidden(params, tokens)
        logits = np.asarray(exact.head(params, hidden[first:]))[
            : len(served)]
        if not np.isfinite(logits).all():
            raise RuntimeError("reference logits are not finite")
        for c in chosen:
            c = np.asarray(c)[: len(seq)]
            routed += c.size
            held += int(((c >= first_held)
                         & (c < first_held + n_held)).sum())
        picked = served
        if low is not None:
            picked = np.asarray(low.logits(params, tokens, first))[
                : len(served)].argmax(-1)
        gaps = logits.max(-1) - logits[np.arange(len(served)), picked]
        worst = max(worst, float(gaps.max()))
        total += float(gaps.sum())
        matches += int((logits.argmax(-1) == picked).sum())
        checked += len(served)
    return {"worst_gap": worst, "mean_gap": total / max(checked, 1),
            "tokens": checked,
            "exact_argmax_share": matches / max(checked, 1),
            "held_share": held / max(routed, 1),
            "requests": len(sample)}


def run(cell: dict, args, t_start: float) -> dict:
    set_program_env(cell)
    import jax.numpy as jnp

    from benchmarks.harness import tracing

    device = common.device_info(cell["chips"], args.tiny)
    common.start_caches()
    config, mix = cell["config"], cell["traffic"]
    dims = model_dims(config, args.tiny)
    positions = max_seq_len(config, args.tiny)
    opts = loop_options(config, args.tiny)
    scale = (positions / config["program"]["max_seq_len"]
             if args.tiny else 1.0)

    # first, so that a program without this block's vocabulary fails at
    # once and not after nine gigabytes of weights are drawn
    transformer_config(dims, positions, jnp.bfloat16)
    params = make_params(args.seed, dims, jnp.bfloat16)
    loop = build_loop(config, dims, params, args.tiny)
    warm_up(loop, dims, opts, np.random.default_rng([args.seed, 0xA]))
    items = generator.serve_items(
        mix, args.seed, float(mix["ramp_s"]) + args.seconds, dims.vocab,
        scale)
    ramp_end = float(mix["ramp_s"]) + args.seconds
    tracer = (tracing.Tracer(cell["name"], ramp_end - min(
        float(mix["trace_s"]), args.seconds), ramp_end)
        if args.trace else None)
    load = Load(loop, items, mix, args.seconds, traced=bool(args.trace),
                trace_hook=tracer.poll if tracer else None)
    jax.block_until_ready(loop.cache)
    compiles_setup = common.compile_stats()

    loop.run(source=load.source, sink=load.sink)
    setup_s = load.edges["start"]["t"] - t_start
    if tracer:
        tracer.stop()
    stats = summarize(load, loop, args.seconds)
    peak = common.memory_peak(cell["chips"])
    common.say(phase="window", setup_s=setup_s, **{
        k: v for k, v in stats.items()
        if not isinstance(v, tuple)})

    sample = pick_sample(load, args.seed)
    pool_blocks = loop.kv_num_blocks
    del loop, load.loop  # frees the pool before the reference runs
    t_ref = time.perf_counter()
    check = reference_gaps(params, dims, positions, sample)
    compared = [
        {"number": "worst_logit_gap", "value": check["worst_gap"],
         "limit": cell["limits"].get("serve_gap_limit")},
        {"number": "mean_logit_gap", "value": check["mean_gap"],
         "limit": cell["limits"].get("serve_mean_gap_limit")},
        {"number": "failed_requests", "value": stats["failed"], "limit": 0}]
    correct = bool(sample) and all(
        r["limit"] is not None and r["value"] <= r["limit"]
        for r in compared)
    common.say(phase="correct", compared=compared,
               reference_s=time.perf_counter() - t_ref, **check)

    device["memory_peak_bytes"] = peak
    result = {"correct": correct, "attempted": stats["attempted"],
              "failed": stats["failed"], "device": device}
    if args.trace:
        run_bag = {"cell": cell, "dims": dims, "stats": stats,
                   "options": opts, "events": load.events,
                   "pool_blocks": pool_blocks,
                   "compile_s_setup": compiles_setup[1],
                   "trace": tracer.reduce(cell["chips"]),
                   "peaks": None if args.tiny
                   else common.peaks_for(device["kind"])}
        result["metrics"] = common.read_layer_metrics(cell, run_bag)
        if run_bag["trace"]:
            device["busy_s"] = run_bag["trace"]["busy_s"]
            device["window_s"] = run_bag["trace"]["window_s"]
            result["breakdown"] = run_bag["trace"]["breakdown"]
    else:
        values = {"setup_s": setup_s, **stats}
        result["metrics"] = {
            m["name"]: {"value": float(values[m["name"]]),
                        "unit": m["unit"]}
            for m in cell["end_to_end"]}
    return result
