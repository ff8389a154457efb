"""Runner for the Olmo-Hybrid serving cells: ``ServeLoop`` over a
``olmo_hybrid`` config file: gated delta-rule layers (a conv tail and a
matrix state a lane, kept per slot) beside full multi-head attention layers
(K/V pages), the norm after each sublayer, no positions; the stated stage
of a two-chip pipeline, every layer it runs whole.

The load, the warm-up, the window's sums and the sample for the reference
are ``harness/serve.py``'s, the comparison's rule ``harness/serve_keye.py``'s
(all imported, none edited).  This module brings what differs: the
dimensions, the seeded weights (the decay's parameters have a law of their
own), the loop's ``TransformerConfig`` and the comparison with the plain
reference (``benchmarks/reference/olmo_hybrid.py``), which also runs as
three controls: float8, no decay, ``beta`` not doubled.  ``run`` follows
``serve_keye.run`` step for step.
"""

from __future__ import annotations

import gc
import math
import time

import jax
import numpy as np

from benchmarks.harness import common, weights
from benchmarks.harness.serve import (Load, loop_options, pick_sample,
                                      set_program_env, summarize, warm_up)
from benchmarks.harness.serve_keye import compare
from benchmarks.harness.serve_mellum import max_seq_len
from benchmarks.reference import olmo_hybrid as ref
from benchmarks.traffic import generator

# the controls of ``correct``: how each reference is built
CONTROLS = {"fp8": {"quant": "fp8"}, "no_decay": {"decay": False},
            "single_beta": {"double_beta": False}}

# the seeded decay (the configuration file's `assumed` says why):
# exp(A_log) and softplus(dt_bias) log-uniform a head, their product, which
# is -log(alpha) at a token whose projection is 0, in 0.001-0.1
_A_RANGE = (0.5, 2.0)
_DT_RANGE = (0.002, 0.05)
_AB_STD = 0.25


def model_dims(config: dict, tiny: bool = False) -> ref.Dims:
    """The reference's ``Dims`` from the configuration file: the published
    keys and, for the rehearsal, the overrides under ``tiny``."""
    src = dict(config)
    if tiny:
        src.update(config["tiny"])
    if (config["model_type"] != "olmo_hybrid" or src["attention_bias"]
            or src["tie_word_embeddings"] or src["hidden_act"] != "silu"
            or src["num_key_value_heads"] != src["num_attention_heads"]
            or src["linear_num_key_heads"] != src["linear_num_value_heads"]
            or src["rope_parameters"]["rope_theta"] is not None
            or len(src["layer_types"]) != src["num_hidden_layers"]
            or set(src["layer_types"]) - {"linear_attention",
                                          "full_attention"}):
        raise ValueError("this runner builds the olmo_hybrid block only")
    return ref.Dims(
        vocab=src["vocab_size"], layers=src["num_hidden_layers"],
        embed=src["hidden_size"], heads=src["num_attention_heads"],
        head_dim=src["hidden_size"] // src["num_attention_heads"],
        ff=src["intermediate_size"],
        linear=tuple(t == "linear_attention" for t in src["layer_types"]),
        lin_heads=src["linear_num_key_heads"],
        key_dim=src["linear_key_head_dim"],
        value_dim=src["linear_value_head_dim"],
        conv=src["linear_conv_kernel_dim"],
        neg_eigval=bool(src["linear_allow_neg_eigval"]),
        norm_eps=float(src["rms_norm_eps"]))


def transformer_config(dims: ref.Dims, positions: int, dtype):
    """The program's configuration.  A program without layer kinds (or the
    norm's order, the whole-projection q/k norm, "no positions") refuses
    the keywords: the parent commit fails here."""
    from tpudist.models import LinearAttentionConfig, TransformerConfig

    return TransformerConfig(
        vocab_size=dims.vocab, num_layers=dims.layers, num_heads=dims.heads,
        head_size=dims.head_dim, embed_dim=dims.embed, max_seq_len=positions,
        compute_dtype=dtype, norm="rmsnorm", norm_eps=dims.norm_eps,
        norm_order="post", positions="none", mlp="gated_silu",
        mlp_dim=dims.ff, qk_norm="whole",
        layer_kinds=tuple("linear" if lin else "full"
                          for lin in dims.linear),
        linear=LinearAttentionConfig(
            num_heads=dims.lin_heads, key_dim=dims.key_dim,
            value_dim=dims.value_dim, conv_width=dims.conv,
            neg_eigval=dims.neg_eigval))


def leaf_table(dims: ref.Dims) -> list[tuple[tuple[str, ...], tuple, object]]:
    """``(path, shape, law)`` per leaf in a fixed order, under the names
    ``TransformerLM`` gives its parameters.  ``law``: a float is a normal's
    std (-1 marks a norm scale: ones); ``"A_log"`` / ``"dt_bias"`` the
    decay's two vectors (:func:`_draw_decay`)."""
    dense = lambda fan_in: 1.0 / math.sqrt(fan_in)  # noqa: E731
    e, f = dims.embed, dims.ff
    wide = dims.heads * dims.head_dim
    h, dk, dv = dims.lin_heads, dims.key_dim, dims.value_dim
    chans = h * (2 * dk + dv)
    out = [(("tok_embed", "embedding"), (dims.vocab, e), dense(e))]
    for i in range(dims.layers):
        b = f"block{i}"
        if dims.linear[i]:
            a = (b, "linear_attn")
            out += [
                (a + ("qkv", "kernel"), (e, chans), dense(e)),
                (a + ("gate", "kernel"), (e, h * dv), dense(e)),
                (a + ("ab", "kernel"), (e, 2 * h), _AB_STD * dense(e)),
                (a + ("conv",), (dims.conv, chans), 0.5),
                (a + ("A_log",), (h,), "A_log"),
                (a + ("dt_bias",), (h,), "dt_bias"),
                (a + ("o_norm", "scale"), (dv,), -1.0),
                (a + ("out", "kernel"), (h * dv, e), dense(h * dv)),
            ]
        else:
            a = (b, "attn")
            out += [
                (a + ("qkv", "kernel"), (e, 3 * wide), dense(e)),
                (a + ("q_norm", "scale"), (wide,), -1.0),
                (a + ("k_norm", "scale"), (wide,), -1.0),
                (a + ("proj", "kernel"), (wide, e), dense(wide)),
            ]
        out += [
            ((b, "ln1", "scale"), (e,), -1.0),
            ((b, "mlp", "up", "kernel"), (e, f), dense(e)),
            ((b, "mlp", "gate", "kernel"), (e, f), dense(e)),
            ((b, "mlp", "down", "kernel"), (f, e), dense(f)),
            ((b, "ln2", "scale"), (e,), -1.0),
        ]
    out += [(("ln_f", "scale"), (e,), -1.0),
            (("lm_head", "kernel"), (e, dims.vocab), dense(e))]
    return out


def _draw_decay(key, index: int, shape, law: str):
    """``A_log`` or ``dt_bias``, float32: the logarithm of a log-uniform
    draw, and the inverse softplus of one."""
    import jax.numpy as jnp

    lo, hi = _A_RANGE if law == "A_log" else _DT_RANGE
    x = jnp.exp(jax.random.uniform(
        jax.random.fold_in(key, index), shape, jnp.float32,
        math.log(lo), math.log(hi)))
    return jnp.log(x) if law == "A_log" else x + jnp.log(-jnp.expm1(-x))


def make_params(seed: int, dims: ref.Dims, dtype) -> dict:
    """The tree on the default device, a leaf a call (an embedding is 0.77
    GB in bfloat16 and twice that while it is drawn in float32, so the
    draws do not share one program)."""
    import jax.numpy as jnp

    key = weights.seed_key(seed)
    return weights._nest({
        path: (_draw_decay(key, i, shape, law) if isinstance(law, str)
               else weights._draw(key, i, shape, law, jnp.dtype(dtype)))
        for i, (path, shape, law) in enumerate(leaf_table(dims))})


def count_params(dims: ref.Dims) -> int:
    return sum(math.prod(shape) for _, shape, _ in leaf_table(dims))


def state_bytes_per_lane(dims: ref.Dims) -> int:
    """What the linear layers keep for one lane: a float32 matrix a head
    and the conv's last inputs in bfloat16, a layer."""
    h, dk, dv = dims.lin_heads, dims.key_dim, dims.value_dim
    return sum(dims.linear) * (
        h * dk * dv * 4 + (dims.conv - 1) * h * (2 * dk + dv) * 2)


def build_loop(config: dict, dims: ref.Dims, params, tiny: bool):
    import jax.numpy as jnp

    from tpudist.models import ServeLoop

    cfg = transformer_config(dims, max_seq_len(config, tiny), jnp.bfloat16)
    return ServeLoop(cfg, params, **loop_options(config, tiny))


def _bucket(n: int, positions: int) -> int:
    """Reference sequences are padded to about an eighth of the positions,
    in whole blocks of the reference's query rows (1536 at the cell's
    size): the reference's attention is quadratic in the padded length."""
    step = -(-(positions // 8) // ref.Q_BLOCK) * ref.Q_BLOCK
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

    # first, so that a program without this block's vocabulary (layer
    # kinds, the norm's order) fails at once and not after eight gigabytes
    # of weights are drawn
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
    # collection frees the pools and the state before the reference needs
    # the room
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
