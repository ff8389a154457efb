"""Runner for the Command A+ serving cells: ``ServeLoop`` over a model whose
block is PARALLEL (one scale-only LayerNorm a layer, attention and the
expert layer both read it, one add), whose sliding-window layers rotate and
whose full layers carry no positions, with sigmoid-routed experts beside
four shared experts that are averaged and a head tied to the embedding: one
chip's share of a stated eight-chip deployment.

The load, the warm-up, the window's sums and the sample are
``harness/serve.py``'s, the seeded draw of one leaf
``harness/serve_deepseek.py``'s and the window group's peak
``harness/serve_mellum.py``'s (imported; none of them is this runner's to
edit).  This module brings what differs: the dimensions from a
``cohere2_moe`` config file, the leaf table with the recipe's ONE factor on
the residual branches' output projections, the loop's ``TransformerConfig``
and the comparison with the plain reference
(``benchmarks/reference/command_a.py``), whose sequences are padded to few
lengths and whose weights are handed over a layer at a time from the host.
``run`` follows ``serve_mellum.run`` step for step (PERF.md section 7: what
a ``benchmark`` PR would fold).
"""

from __future__ import annotations

import gc
import math
import time

import jax
import numpy as np

from benchmarks.harness import common, weights
from benchmarks.harness.serve import (loop_options, pick_sample,
                                      set_program_env, summarize, warm_up)
from benchmarks.harness.serve_deepseek import _draw_leaf
from benchmarks.harness.serve_mellum import WindowLoad
from benchmarks.reference import command_a as ref
from benchmarks.traffic import generator

# reference sequences are padded to one of these lengths (to the sequence
# itself when tiny): a sample of four compiles four shapes at most
REFERENCE_LENGTHS = (2048, 4096, 6144, 8192, 12288, 18432)
SAMPLE_EXTRA = 1  # requests drawn from the seed beside the three by length


def model_dims(config: dict, tiny: bool = False) -> ref.Dims:
    """The reference's ``Dims`` from the configuration file: the published
    keys, ``held`` (which routed experts and vocabulary rows live here) and,
    for the rehearsal, the overrides under ``tiny``."""
    src = dict(config)
    router_width = config["held"]["router_width"]
    if tiny:
        t = config["tiny"]
        src.update(t)
        router_width = t["router_width"]
    rope = config["rope_parameters"]
    said = {
        "model_type": "cohere2_moe", "use_parallel_block": True,
        "tie_word_embeddings": True, "expert_selection_fn": "sigmoid",
        "norm_topk_prob": True, "attention_bias": False,
        "use_qk_norm": False, "first_k_dense_replace": 0,
        "shared_expert_combination_strategy": "average",
        "hidden_act": "silu", "use_gated_activation": True, "rotary_pct": 1}
    wrong = {k: src[k] for k, v in said.items() if src[k] != v}
    if wrong or rope["rope_type"] != "default":
        raise ValueError(f"this runner builds the cohere2_moe block only: "
                         f"{wrong or rope}")
    kinds = src["layer_types"][: src["num_hidden_layers"]]
    if set(kinds) - {"sliding_attention", "full_attention"}:
        raise ValueError(f"unknown layer kinds in {sorted(set(kinds))}")
    first = config["held"]["routed_experts"][0]
    return ref.Dims(
        vocab=src["vocab_size"], layers=src["num_hidden_layers"],
        embed=src["hidden_size"], heads=src["num_attention_heads"],
        kv_heads=src["num_key_value_heads"], head_dim=src["head_dim"],
        expert_ff=src["intermediate_size"], experts=router_width,
        top_k=src["num_experts_per_tok"], held=(first, src["num_experts"]),
        n_shared=src["num_shared_experts"],
        sliding=tuple(k == "sliding_attention" for k in kinds),
        window=src["sliding_window"], norm_eps=float(src["layer_norm_eps"]),
        rope_theta=float(rope["rope_theta"]),
        logit_scale=float(src["logit_scale"]))


def max_seq_len(config: dict, tiny: bool) -> int:
    return (config["tiny"] if tiny else config["program"])["max_seq_len"]


def transformer_config(dims: ref.Dims, positions: int, dtype):
    """The program's configuration: the parallel block under a scale-only
    LayerNorm, a window and rotary positions on the sliding layers and
    neither on the full ones, the shared experts' mean, the tied head."""
    from tpudist.models import MoEConfig, TransformerConfig

    moe = MoEConfig(
        num_experts=dims.experts, top_k=dims.top_k, experts="gated_silu",
        d_ff=dims.expert_ff, scoring="sigmoid", n_shared=dims.n_shared,
        shared_combine="mean", held=dims.held)
    return TransformerConfig(
        vocab_size=dims.vocab, num_layers=dims.layers,
        num_heads=dims.heads, num_kv_heads=dims.kv_heads,
        head_size=dims.head_dim, embed_dim=dims.embed,
        max_seq_len=positions, compute_dtype=dtype, norm="layernorm_scale",
        norm_eps=dims.norm_eps, norm_order="parallel", positions="rotary",
        full_layer_positions="none", rope_theta=dims.rope_theta,
        layer_windows=tuple(dims.window if s else None
                            for s in dims.sliding),
        mlp="gated_silu", mlp_dim=dims.expert_ff, moe=moe, first_k_dense=0,
        tie_embeddings=True, logit_scale=dims.logit_scale)


def leaf_table(dims: ref.Dims, branch_factor: float
               ) -> list[tuple[tuple[str, ...], tuple, float]]:
    """``(path, shape, std)`` per leaf in a fixed order, under the names
    ``TransformerLM`` gives its parameters; ``std`` -1 marks a LayerNorm
    scale (ones).  Every matrix is drawn at ``1 / sqrt(fan_in)`` as
    ``harness/weights.py`` draws; the residual branches' OUTPUT projections
    (``attn/proj``, every expert's ``w_down``, the shared experts'
    ``down``) at ``branch_factor`` times that (the configuration's
    ``seeded_weights`` says why)."""
    e, f = dims.embed, dims.expert_ff
    wide = dims.heads * dims.head_dim
    dense = lambda fan_in: 1.0 / math.sqrt(fan_in)  # noqa: E731
    out_std = lambda fan_in: branch_factor * dense(fan_in)  # noqa: E731
    held, shared = dims.held[1], dims.n_shared * f
    # no lm_head: the head reads this table
    out = [(("tok_embed", "embedding"), (dims.vocab, e), dense(e))]
    for i in range(dims.layers):
        b = f"block{i}"
        out += [
            ((b, "ln1", "scale"), (e,), -1.0),
            ((b, "attn", "q", "kernel"), (e, wide), dense(e)),
            ((b, "attn", "kv", "kernel"),
             (e, 2 * dims.kv_heads * dims.head_dim), dense(e)),
            ((b, "attn", "proj", "kernel"), (wide, e), out_std(wide)),
            ((b, "moe", "router", "kernel"), (e, dims.experts), dense(e)),
            ((b, "moe", "w_gate"), (held, e, f), dense(e)),
            ((b, "moe", "w_up"), (held, e, f), dense(e)),
            ((b, "moe", "w_down"), (held, f, e), out_std(f)),
            # the four shared experts side by side: expert j is columns
            # (rows, for down) j f .. (j + 1) f, each of fan-in f
            ((b, "moe", "shared", "gate", "kernel"), (e, shared), dense(e)),
            ((b, "moe", "shared", "up", "kernel"), (e, shared), dense(e)),
            ((b, "moe", "shared", "down", "kernel"), (shared, e),
             out_std(f)),
        ]
    out += [(("ln_f", "scale"), (e,), -1.0)]
    return out


def branch_factor(config: dict) -> float:
    return float(config["seeded_weights"]["branch_output_factor"])


def make_params(seed: int, dims: ref.Dims, dtype,
                factor: float = 1.0) -> dict:
    """The tree on the default device, a leaf a call (``serve_deepseek``'s
    draw).  ``factor``: the recipe's (``branch_factor(config)``); shapes do
    not depend on it, so the readers' abstract loop takes the default."""
    import jax.numpy as jnp

    key = weights.seed_key(seed)
    return weights._nest({
        path: _draw_leaf(key, i, shape, std, jnp.dtype(dtype))
        for i, (path, shape, std) in enumerate(leaf_table(dims, factor))})


def count_params(dims: ref.Dims) -> int:
    return sum(math.prod(shape) for _, shape, _ in leaf_table(dims, 1.0))


def build_loop(config: dict, dims: ref.Dims, params, tiny: bool):
    import jax.numpy as jnp

    from tpudist.models import ServeLoop

    cfg = transformer_config(dims, max_seq_len(config, tiny), jnp.bfloat16)
    return ServeLoop(cfg, params, **loop_options(config, tiny))


def _padded(n: int, positions: int) -> int:
    """The reference's length for a sequence of ``n``: the next of
    ``REFERENCE_LENGTHS`` (the sequence itself at the rehearsal's sizes)."""
    if positions < REFERENCE_LENGTHS[0]:
        return -(-n // 8) * 8
    return next(b for b in REFERENCE_LENGTHS if b >= n)


def to_host(params) -> dict:
    """The served weights as host arrays: the reference then holds ONE
    layer on the device at a time (9.5 GB of weights beside its float32
    blocks of an 18 k sequence do not fit)."""
    return jax.device_get(params)


def reference_gaps(params, dims: ref.Dims, positions: int, sample,
                   control: ref.Control | None = None,
                   lanes: int = 0) -> dict:
    """As ``serve.reference_gaps``: teacher-forced reference logits at
    every served position of the sample, and how far the served token's
    logit lies below the reference's best.  With a ``control`` a second
    reference runs so and the gap is read for the token IT puts first.
    Also what the reference's OWN router did at the served positions: the
    share of its choices that fall on the held experts (``held_share``),
    the busiest (layer, held expert)'s share of those (``held_max_share``)
    and, with ``lanes``, how many of a layer's held experts that many
    tokens drawn from the positions touch (``held_touched``, a layer: a
    step's, were its lanes' tokens as these; the calibration tool's reading
    for the recipe's factor)."""
    import jax.numpy as jnp

    exact = ref.Forward(dims)
    low = ref.Forward(dims, control) if control is not None else None
    worst, total, matches, checked = 0.0, 0.0, 0, 0
    first_held, n_held = dims.held
    picks = []  # [layers, served positions, k] of every request
    for comp in sample:
        served = np.asarray(comp.tokens)
        seq = np.concatenate([np.asarray(comp.prompt), served[:-1]])
        first = len(comp.prompt) - 1
        padded = np.zeros(_padded(len(seq), positions), np.int32)
        padded[: len(seq)] = seq
        tokens = jnp.asarray(padded)
        hidden, chosen = exact.hidden(params, tokens)
        logits = np.asarray(exact.head(params, hidden[first:]))[
            : len(served)]
        if not np.isfinite(logits).all():
            raise RuntimeError("reference logits are not finite")
        picks.append(np.stack([np.asarray(c)[first: len(seq)]
                               for c in chosen]))
        picked = served
        if low is not None:
            picked = np.asarray(low.logits(params, tokens, first))[
                : len(served)].argmax(-1)
        gaps = logits.max(-1) - logits[np.arange(len(served)), picked]
        worst = max(worst, float(gaps.max()))
        total += float(gaps.sum())
        matches += int((logits.argmax(-1) == picked).sum())
        checked += len(served)
    out = {"worst_gap": worst, "mean_gap": total / max(checked, 1),
           "tokens": checked,
           "exact_argmax_share": matches / max(checked, 1),
           "requests": len(sample)}
    if picks:
        allp = np.concatenate(picks, axis=1) - first_held  # [L, P, k]
        held = (allp >= 0) & (allp < n_held)
        counts = np.stack([np.bincount(layer[h], minlength=n_held)
                           for layer, h in zip(allp, held)])
        out["held_share"] = float(held.mean())
        out["held_max_share"] = float(counts.max() / max(counts.sum(), 1))
        out["held_share_by_layer"] = [float(h.mean()) for h in held]
        if lanes:
            out["held_touched"] = [_touched(layer, n_held, lanes)
                                   for layer in allp]
    return out


def _touched(choices, n_held: int, lanes: int, draws: int = 200) -> float:
    """The mean number of distinct held experts among the choices of
    ``lanes`` positions drawn from ``choices [positions, k]`` (held experts
    are ``0 .. n_held - 1`` there)."""
    rng = np.random.default_rng(0)
    counts = []
    for rows in rng.integers(0, choices.shape[0], (draws, lanes)):
        picked = choices[rows].reshape(-1)
        counts.append(np.unique(
            picked[(picked >= 0) & (picked < n_held)]).size)
    return float(np.mean(counts))


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

    # first, so that a program without this block's vocabulary (the
    # parallel order, positions a layer kind, the shared mean, the tied
    # head) fails at once, in its own words, and not after nine gigabytes
    # of weights are drawn
    transformer_config(dims, positions, jnp.bfloat16)
    params = make_params(args.seed, dims, jnp.bfloat16,
                         branch_factor(config))
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

    sample = pick_sample(load, args.seed, SAMPLE_EXTRA)
    pool_blocks = loop.kv_num_blocks
    host = to_host(params)
    # the loop and its jitted methods refer to each other: only a
    # collection frees the pools and the weights before the reference runs
    del loop, load.loop, params
    gc.collect()
    t_ref = time.perf_counter()
    check = reference_gaps(host, dims, positions, sample)
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
