"""Runner for the Keye-VL 2.0 serving cells: ``ServeLoop`` over the language
model of a ``KeyeVL2`` config file: grouped-query attention with a per-head
q/k norm and a learned indexer (``sa_config``) in every layer, an expert FFN
in every layer, one chip's share of a stated eight-chip deployment.

The load, the warm-up, the window's sums and the sample for the reference
are ``harness/serve.py``'s, the seeded draw of one leaf
``harness/serve_deepseek.py``'s, the leaves outside the q/k norm and the
indexer ``harness/serve_mellum.py``'s (all imported, none edited).  This
module brings what differs: the dimensions, the loop's ``TransformerConfig``
and the comparison with the plain reference
(``benchmarks/reference/keye_vl2.py``), which also runs as three controls:
float8, the selection skipped, the wrong rows.  ``run`` follows
``serve_mellum.run`` step for step.
"""

from __future__ import annotations

import gc
import math
import time

import jax
import numpy as np

from benchmarks.harness import common, serve_mellum, weights
from benchmarks.harness.serve import (Load, loop_options, pick_sample,
                                      set_program_env, summarize, warm_up)
from benchmarks.harness.serve_deepseek import _draw_leaf
from benchmarks.harness.serve_mellum import max_seq_len
from benchmarks.reference import keye_vl2 as ref
from benchmarks.traffic import generator

# the controls of ``correct``: how each reference is built
CONTROLS = {"fp8": {"quant": "fp8"}, "no_selection": {"select": "all"},
            "recent_rows": {"select": "recent"}}


def model_dims(config: dict, tiny: bool = False) -> ref.Dims:
    """The reference's ``Dims`` from the configuration file: the published
    keys, ``held`` (which routed experts live here, of the router's width)
    and, for the rehearsal, the overrides under ``tiny``."""
    src = dict(config)
    sa = dict(config["sa_config"])
    router_width = config["held"]["router_width"]
    if tiny:
        t = dict(config["tiny"])
        sa.update(t.pop("sa_config", {}))
        src.update(t)
        router_width = t["router_width"]
    if (config["model_type"] != "KeyeVL2" or sa["indexer_num_kv_heads"] != 1
            or not src["norm_topk_prob"] or src["attention_bias"]
            or src["tie_word_embeddings"] or src["use_sliding_window"]
            or src["decoder_sparse_step"] != 1 or src["mlp_only_layers"]
            or src["rope_scaling"]["rope_type"] != "default"):
        raise ValueError("this runner builds the KeyeVL2 language block only")
    first = config["held"]["routed_experts"][0]
    return ref.Dims(
        vocab=src["vocab_size"], layers=src["num_hidden_layers"],
        embed=src["hidden_size"], heads=src["num_attention_heads"],
        kv_heads=src["num_key_value_heads"], head_dim=src["head_dim"],
        expert_ff=src["moe_intermediate_size"], experts=router_width,
        top_k=src["num_experts_per_tok"], held=(first, src["num_experts"]),
        index_heads=sa["indexer_num_heads"], index_dim=sa["indexer_head_dim"],
        index_topk=sa["topk"], norm_eps=float(src["rms_norm_eps"]),
        rope_theta=float(src["rope_theta"]))


def transformer_config(dims: ref.Dims, positions: int, dtype):
    """The program's configuration.  A program without the q/k norm or the
    indexer's sizes refuses the keywords: the parent commit fails here."""
    from tpudist.models import MoEConfig, TransformerConfig

    moe = MoEConfig(
        num_experts=dims.experts, top_k=dims.top_k, experts="gated_silu",
        d_ff=dims.expert_ff, scoring="softmax", held=dims.held)
    return TransformerConfig(
        vocab_size=dims.vocab, num_layers=dims.layers,
        num_heads=dims.heads, num_kv_heads=dims.kv_heads,
        head_size=dims.head_dim, embed_dim=dims.embed,
        max_seq_len=positions, compute_dtype=dtype, norm="rmsnorm",
        norm_eps=dims.norm_eps, positions="rotary",
        rope_theta=dims.rope_theta, mlp="gated_silu",
        mlp_dim=dims.expert_ff, moe=moe, first_k_dense=0, qk_norm=True,
        index_heads=dims.index_heads, index_head_dim=dims.index_dim,
        index_topk=dims.index_topk)


def _mellum_view(dims: ref.Dims):
    """``dims`` as ``serve_mellum.leaf_table`` reads them (the fields the
    two blocks share)."""
    return serve_mellum.ref.Dims(
        vocab=dims.vocab, layers=dims.layers, embed=dims.embed,
        heads=dims.heads, kv_heads=dims.kv_heads, head_dim=dims.head_dim,
        expert_ff=dims.expert_ff, experts=dims.experts, top_k=dims.top_k,
        held=dims.held, sliding=(False,) * dims.layers, window=0)


def leaf_table(dims: ref.Dims) -> list[tuple[tuple[str, ...], tuple, float]]:
    """``serve_mellum.leaf_table`` (the leaves the two blocks share) and,
    after it, every layer's q/k norms and indexer; ``std`` -1 marks a norm
    scale (ones), 0 the index key norm's bias (zeros)."""
    dense = lambda fan_in: 1.0 / math.sqrt(fan_in)  # noqa: E731
    out = serve_mellum.leaf_table(_mellum_view(dims))
    e, hi, di = dims.embed, dims.index_heads, dims.index_dim
    for i in range(dims.layers):
        a = (f"block{i}", "attn")
        out += [
            (a + ("q_norm", "scale"), (dims.head_dim,), -1.0),
            (a + ("k_norm", "scale"), (dims.head_dim,), -1.0),
            (a + ("idx_q", "kernel"), (e, hi * di), dense(e)),
            (a + ("idx_k", "kernel"), (e, di), dense(e)),
            (a + ("idx_k_norm", "scale"), (di,), -1.0),
            (a + ("idx_k_norm", "bias"), (di,), 0.0),
            (a + ("idx_w", "kernel"), (e, hi), dense(e)),
        ]
    return out


def make_params(seed: int, dims: ref.Dims, dtype) -> dict:
    """The tree on the default device, a leaf a call (``serve_deepseek``'s
    draw); the indexer's head weights ``idx_w`` stay float32."""
    import jax.numpy as jnp

    key = weights.seed_key(seed)
    return weights._nest({
        path: _draw_leaf(
            key, i, shape, std,
            jnp.dtype(jnp.float32 if path[-2] == "idx_w" else dtype))
        for i, (path, shape, std) in enumerate(leaf_table(dims))})


def count_params(dims: ref.Dims) -> int:
    return sum(math.prod(shape) for _, shape, _ in leaf_table(dims))


def build_loop(config: dict, dims: ref.Dims, params, tiny: bool):
    import jax.numpy as jnp

    from tpudist.models import ServeLoop

    cfg = transformer_config(dims, max_seq_len(config, tiny), jnp.bfloat16)
    return ServeLoop(cfg, params, **loop_options(config, tiny))


def _bucket(n: int, positions: int) -> int:
    """Reference sequences are padded to an eighth of the positions (4096
    at the cell's size; never under the reference's block of query rows):
    the reference's attention is quadratic in the
    padded length, and ``serve._bucket``'s powers of two would pad every
    sequence over 16384 tokens to 32768."""
    step = max(positions // 8, min(positions, ref.Q_BLOCK))
    return min(positions, -(-n // step) * step)


def reference_gaps(params, dims: ref.Dims, positions: int, sample,
                   controls=()) -> dict:
    """Teacher-forced reference logits at every served position of the
    sample, and how far the served token's logit lies below the reference's
    best: ``{"program": {...}}``.  Each name in ``controls`` (of
    ``CONTROLS``) adds the same numbers for the tokens THAT reference puts
    first, against the same exact logits (computed once a request)."""
    import jax.numpy as jnp

    exact = ref.Forward(dims)
    others = {name: ref.Forward(dims, **CONTROLS[name]) for name in controls}
    sums = {name: {"worst_gap": 0.0, "total": 0.0, "matches": 0}
            for name in ("program", *others)}
    checked = 0
    for comp in sample:
        served = np.asarray(comp.tokens)
        seq = np.concatenate([np.asarray(comp.prompt), served[:-1]])
        first = len(comp.prompt) - 1
        padded = np.zeros(_bucket(len(seq), positions), np.int32)
        padded[: len(seq)] = seq
        tokens = jnp.asarray(padded)
        logits = np.asarray(exact.logits(params, tokens, first))[
            : len(served)]
        if not np.isfinite(logits).all():
            raise RuntimeError("reference logits are not finite")
        checked += len(served)
        for name, acc in sums.items():
            picked = served if name == "program" else np.asarray(
                others[name].logits(params, tokens, first))[
                : len(served)].argmax(-1)
            gaps = logits.max(-1) - logits[np.arange(len(served)), picked]
            acc["worst_gap"] = max(acc["worst_gap"], float(gaps.max()))
            acc["total"] += float(gaps.sum())
            acc["matches"] += int((logits.argmax(-1) == picked).sum())
    return {name: {"worst_gap": acc["worst_gap"],
                   "mean_gap": acc["total"] / max(checked, 1),
                   "exact_argmax_share": acc["matches"] / max(checked, 1),
                   "tokens": checked, "requests": len(sample)}
            for name, acc in sums.items()}


def compare(check: dict, failed: int, limits: dict) -> tuple[list, bool]:
    """What decides ``correct``: one reference's gaps and the failed
    requests, each beside its limit (the run's own comparison; the
    calibration tool holds the controls to it too)."""
    compared = [
        {"number": "worst_logit_gap", "value": check["worst_gap"],
         "limit": limits.get("serve_gap_limit")},
        {"number": "mean_logit_gap", "value": check["mean_gap"],
         "limit": limits.get("serve_mean_gap_limit")},
        {"number": "failed_requests", "value": failed, "limit": 0}]
    return compared, bool(check["requests"]) and all(
        r["limit"] is not None and r["value"] <= r["limit"]
        for r in compared)


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

    # first, so that a program without this block's vocabulary (a q/k
    # norm, an indexer) fails at once and not after three gigabytes of
    # weights are drawn
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
    # the loop and its jitted methods refer to each other: only a
    # collection frees the pools before the reference needs the room
    del loop, load.loop
    gc.collect()
    t_ref = time.perf_counter()
    check = reference_gaps(params, dims, positions, sample)["program"]
    compared, correct = compare(check, stats["failed"], cell["limits"])
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
