"""Runner for the Mellum 2 serving cells: ``ServeLoop`` over a model whose
layers are of two kinds (sliding-window and full attention, both grouped-
query) with an expert FFN in every layer, one chip's share of a stated
four-chip deployment.

The load, the warm-up, the window's sums and the sample for the reference
are ``harness/serve.py``'s, the seeded draw of one leaf
``harness/serve_deepseek.py``'s (imported; neither file is this runner's to
edit).  This module brings what differs: the dimensions from a ``mellum``
config file, the leaf table, the loop's ``TransformerConfig``, the window
block group's peak beside the full group's, and the comparison with the
plain reference (``benchmarks/reference/mellum2.py``).  ``run`` follows
``serve_deepseek.run`` step for step (PERF.md section 7: what a
``benchmark`` PR would fold).
"""

from __future__ import annotations

import math
import time

import jax
import numpy as np

from benchmarks.harness import common, weights
from benchmarks.harness.serve import (Load, _bucket, loop_options,
                                      pick_sample, set_program_env,
                                      summarize, warm_up)
from benchmarks.harness.serve_deepseek import _draw_leaf
from benchmarks.reference import mellum2 as ref
from benchmarks.traffic import generator


def model_dims(config: dict, tiny: bool = False) -> ref.Dims:
    """The reference's ``Dims`` from the configuration file: the published
    keys, ``held`` (which routed experts live here, of the router's width)
    and, for the rehearsal, the overrides under ``tiny``."""
    src = dict(config)
    router_width = config["held"]["router_width"]
    full = dict(config["rope_parameters"]["full_attention"])
    sliding = config["rope_parameters"]["sliding_attention"]
    if tiny:
        t = config["tiny"]
        src.update(t)
        router_width = t["router_width"]
        full["original_max_position_embeddings"] = t[
            "original_max_position_embeddings"]
    if (config["model_type"] != "mellum" or full["rope_type"] != "yarn"
            or sliding["rope_type"] != "default"
            or sliding["rope_theta"] != full["rope_theta"]
            or not src["norm_topk_prob"] or src["attention_bias"]
            or src["tie_word_embeddings"]
            or set(src["mlp_layer_types"]) != {"sparse"}):
        raise ValueError("this runner builds the mellum block only")
    kinds = src["layer_types"][: src["num_hidden_layers"]]
    if set(kinds) - {"sliding_attention", "full_attention"}:
        raise ValueError(f"unknown layer kinds in {sorted(set(kinds))}")
    first = config["held"]["routed_experts"][0]
    return ref.Dims(
        vocab=src["vocab_size"], layers=src["num_hidden_layers"],
        embed=src["hidden_size"], heads=src["num_attention_heads"],
        kv_heads=src["num_key_value_heads"], head_dim=src["head_dim"],
        expert_ff=src["moe_intermediate_size"], experts=router_width,
        top_k=src["num_experts_per_tok"], held=(first, src["num_experts"]),
        sliding=tuple(k == "sliding_attention" for k in kinds),
        window=src["sliding_window"], norm_eps=float(src["rms_norm_eps"]),
        rope_theta=float(full["rope_theta"]),
        yarn_factor=float(full["factor"]),
        yarn_original=full["original_max_position_embeddings"],
        yarn_beta_fast=float(full["beta_fast"]),
        yarn_beta_slow=float(full["beta_slow"]),
        yarn_attention_factor=float(full["attention_factor"]))


def max_seq_len(config: dict, tiny: bool) -> int:
    return (config["tiny"] if tiny else config["program"])["max_seq_len"]


def transformer_config(dims: ref.Dims, positions: int, dtype):
    """The program's configuration: a window a layer, YaRN on the full
    layers alone.  ``attention_factor`` is stated in the file as ``0.1 ln
    factor + 1``, which is what ``YarnScaling(mscale=1, mscale_all_dim=0)``
    multiplies cos and sin by; the two are held together here."""
    from tpudist.models import MoEConfig, TransformerConfig, YarnScaling

    if abs(0.1 * math.log(dims.yarn_factor) + 1.0
           - dims.yarn_attention_factor) > 1e-9:
        raise ValueError("attention_factor is not 0.1 ln(factor) + 1")
    moe = MoEConfig(
        num_experts=dims.experts, top_k=dims.top_k, experts="gated_silu",
        d_ff=dims.expert_ff, scoring="softmax", held=dims.held)
    return TransformerConfig(
        vocab_size=dims.vocab, num_layers=dims.layers,
        num_heads=dims.heads, num_kv_heads=dims.kv_heads,
        head_size=dims.head_dim, embed_dim=dims.embed,
        max_seq_len=positions, compute_dtype=dtype, norm="rmsnorm",
        norm_eps=dims.norm_eps, positions="rotary",
        rope_theta=dims.rope_theta,
        rope_scaling=YarnScaling(
            factor=dims.yarn_factor,
            original_max_position=dims.yarn_original,
            beta_fast=dims.yarn_beta_fast, beta_slow=dims.yarn_beta_slow,
            mscale=1.0, mscale_all_dim=0.0),
        window_rope_scaling=None,
        layer_windows=tuple(dims.window if s else None
                            for s in dims.sliding),
        mlp="gated_silu", mlp_dim=dims.expert_ff, moe=moe, first_k_dense=0)


def leaf_table(dims: ref.Dims) -> list[tuple[tuple[str, ...], tuple, float]]:
    """``(path, shape, std)`` per leaf in a fixed order, under the names
    ``TransformerLM`` gives its parameters; ``std`` -1 marks an RMSNorm
    scale (ones)."""
    e, f = dims.embed, dims.expert_ff
    wide = dims.heads * dims.head_dim
    dense = lambda fan_in: 1.0 / math.sqrt(fan_in)  # noqa: E731
    held = dims.held[1]
    out = [(("tok_embed", "embedding"), (dims.vocab, e), dense(e))]
    for i in range(dims.layers):
        b = f"block{i}"
        out += [
            ((b, "ln1", "scale"), (e,), -1.0),
            ((b, "attn", "q", "kernel"), (e, wide), dense(e)),
            ((b, "attn", "kv", "kernel"),
             (e, 2 * dims.kv_heads * dims.head_dim), dense(e)),
            ((b, "attn", "proj", "kernel"), (wide, e), dense(wide)),
            ((b, "ln2", "scale"), (e,), -1.0),
            ((b, "moe", "router", "kernel"), (e, dims.experts), dense(e)),
            ((b, "moe", "w_gate"), (held, e, f), dense(e)),
            ((b, "moe", "w_up"), (held, e, f), dense(e)),
            ((b, "moe", "w_down"), (held, f, e), dense(f)),
        ]
    out += [(("ln_f", "scale"), (e,), -1.0),
            (("lm_head", "kernel"), (e, dims.vocab), dense(e))]
    return out


def make_params(seed: int, dims: ref.Dims, dtype) -> dict:
    """The tree on the default device, a leaf a call (``serve_deepseek``'s
    draw)."""
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


class WindowLoad(Load):
    """``serve.Load`` that also samples the WINDOW block group at every
    poll inside the window, as ``Load`` samples the full group."""

    def __init__(self, *args, **kw) -> None:
        super().__init__(*args, **kw)
        self.window_block_samples: list[int] = []

    def source(self):
        out = super().source()
        group = getattr(self.loop.pool, "window_group", None)
        if (group is not None and "start" in self.edges
                and "end" not in self.edges):
            self.window_block_samples.append(group.used_blocks)
        return out


def reference_gaps(params, dims: ref.Dims, positions: int, sample,
                   quant=None, window: bool = True) -> dict:
    """As ``serve.reference_gaps``: teacher-forced reference logits at
    every served position of the sample, and how far the served token's
    logit lies below the reference's best.  With ``quant`` (the precision
    control) or ``window=False`` (the structure control: the band left out
    of the sliding layers) a second reference runs so and the gap is read
    for the token IT puts first.  Also the share of the routed work the
    reference's OWN router sends to the held experts (``held_share``)."""
    import jax.numpy as jnp

    exact = ref.Forward(dims)
    low = (ref.Forward(dims, quant=quant, window=window)
           if quant or not window else None)
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

    # first, so that a program without this block's vocabulary (a stated
    # head width, a window a layer) fails at once and not after seven
    # gigabytes of weights are drawn
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
    load = WindowLoad(loop, items, mix, args.seconds,
                      traced=bool(args.trace),
                      trace_hook=tracer.poll if tracer else None)
    jax.block_until_ready(loop.cache)
    compiles_setup = common.compile_stats()

    loop.run(source=load.source, sink=load.sink)
    setup_s = load.edges["start"]["t"] - t_start
    if tracer:
        tracer.stop()
    stats = summarize(load, loop, args.seconds)
    stats["kv_window_blocks_peak"] = max(load.window_block_samples,
                                         default=0)
    stats["kv_window_blocks_total"] = loop.kv_window_blocks
    peak = common.memory_peak(cell["chips"])
    common.say(phase="window", setup_s=setup_s, **{
        k: v for k, v in stats.items()
        if not isinstance(v, tuple)})

    sample = pick_sample(load, args.seed)
    pool_blocks = loop.kv_num_blocks
    del loop, load.loop  # frees the pools before the reference runs
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
