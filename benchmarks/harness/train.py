"""Runner for training cells: the composed ``MeshSpec`` step, fed a ring of
distinct host batches through ``shard_composed_batch`` every step.

Order of one run: the plain float32 reference follows the first steps from
the seed's weights and batches and is freed; then set-up builds ONE state and
ONE compiled step, drives them through those same steps (which is also the
warm-up: the composed step compiles a second program on its second call) and
hands the same objects to the measured window.
"""

from __future__ import annotations

import math
import statistics
import time

from benchmarks.harness import common, weights
from benchmarks.traffic import generator


def lm_loss_fn(model):
    """``(params, batch, rng) -> (loss, aux)`` for the composed step."""
    from tpudist.ops.losses import cross_entropy

    def loss_fn(params, batch, rng):
        x, y = batch
        return cross_entropy(model.apply({"params": params}, x), y), {}

    return loss_fn


def build(config: dict, dims: weights.ModelDims, mix: dict, tiny: bool):
    """(spec, mesh, model, optimizer, step) for the cell."""
    import jax.numpy as jnp
    import optax

    from benchmarks.harness.serve import transformer_config
    from tpudist.models import TransformerLM
    from tpudist.ops.flash_attention import flash_attention_fn
    from tpudist.parallel import MeshSpec, make_composed_train_step

    opts = config["program"]["options"]
    if opts["attention"] != "flash":
        raise ValueError("the training cells time the flash kernels")
    model = TransformerLM(transformer_config(dims, jnp.bfloat16),
                          attention_fn=flash_attention_fn(),
                          remat=bool(opts["remat"]))
    spec = MeshSpec.parse(mix["mesh"])
    mesh = spec.build()
    tx = optax.adamw(float(opts["learning_rate"]))
    step = make_composed_train_step(spec, mesh, lm_loss_fn(model))
    return spec, mesh, model, tx, step


def adam_mu(opt_state):
    """The first-moment tree inside an optax state."""
    import jax

    found = [s.mu for s in jax.tree.leaves(
        opt_state, is_leaf=lambda s: hasattr(s, "mu")) if hasattr(s, "mu")]
    if len(found) != 1:
        raise RuntimeError(f"{len(found)} adam states in the optimizer")
    return found[0]


def worst_leaf_gap(got: dict, want: dict) -> float:
    """The largest ``|got - want|`` over the leaves, against the reference's
    norm of that leaf or of its median leaf, whichever is larger."""
    floor = statistics.median(want.values())
    return max(abs(got[k] - want[k]) / max(want[k], floor) for k in want)


def follow_reference(seed, dims, batches, lr: float) -> dict:
    import jax.numpy as jnp

    from benchmarks.reference import transformer as ref

    params = weights.make_params(seed, dims, jnp.float32)
    out = ref.follow_steps(
        params, batches, lr=lr, layers=dims.layers, heads=dims.heads,
        kv_heads=dims.kv_heads,
        remake_start=lambda: weights.make_params(seed, dims, jnp.float32))
    del params
    return out


def compare(prog: dict, ref: dict, limits: dict) -> list[dict]:
    """Each number compared, beside its limit."""
    rows = [{"number": f"loss_step{i + 1}",
             "value": abs(p - r) / abs(r), "limit": limits.get("loss_rel"),
             "program": p, "reference": r}
            for i, (p, r) in enumerate(zip(prog["losses"], ref["losses"]))]
    rows.append({"number": "first_grad_norm_worst_leaf",
                 "value": worst_leaf_gap(prog["grad_norms"],
                                         ref["grad_norms"]),
                 "limit": limits.get("grad_norm_rel")})
    rows.append({"number": "param_change_norm_worst_leaf",
                 "value": worst_leaf_gap(prog["change_norms"],
                                         ref["change_norms"]),
                 "limit": limits.get("change_norm_rel")})
    return rows


def first_steps(step, state, feed, n: int, seed, dims, param_dtype):
    """Drive the cell's own step and feed through its first ``n`` steps and
    read what the reference is compared on; returns the same state."""
    import jax

    from benchmarks.reference import transformer as ref

    losses, grad_norms = [], None
    for i in range(n):
        state, metrics = step(state, *feed(i))
        losses.append(float(jax.block_until_ready(metrics["loss"])))
        if i == 0:
            # adam's first moment after one step is (1 - b1) g
            grad_norms = {k: v / 0.1 for k, v in ref.leaf_norms(
                adam_mu(state.opt_state)).items()}
    start = weights.make_params(seed, dims, param_dtype)
    change = ref.diff_norms(state.params, start)
    del start
    return state, {"losses": losses, "grad_norms": grad_norms,
                   "change_norms": change}


def run(cell: dict, args, t_start: float) -> dict:
    import jax
    import jax.numpy as jnp

    from benchmarks.harness import tracing
    from tpudist.parallel import make_composed_state, shard_composed_batch

    device = common.device_info(cell["chips"], args.tiny)
    common.start_caches()
    config, mix = cell["config"], cell["traffic"]
    dims = weights.ModelDims.from_config(config, args.tiny)
    seq = dims.positions if args.tiny else int(mix["seq_len"])
    lr = float(config["program"]["options"]["learning_rate"])
    spec, mesh, model, tx, step = build(config, dims, mix, args.tiny)
    rows = int(mix["rows_per_chip"]) * spec.n_devices
    ring = generator.train_batches(mix, args.seed, rows, dims.vocab, seq)
    n_check = int(mix["checked_steps"])

    t_ref = time.perf_counter()
    reference = follow_reference(args.seed, dims, ring[:n_check], lr)
    reference_s = time.perf_counter() - t_ref

    params = weights.make_params(args.seed, dims, jnp.float32)
    state, _ = make_composed_state(model.apply, params, tx, spec, mesh,
                                   rng=int(args.seed) & 0x7FFFFFFF)
    del params
    feed = lambda i: shard_composed_batch(  # noqa: E731
        ring[i % len(ring)], mesh, spec)
    state, program = first_steps(step, state, feed, n_check, args.seed,
                                 dims, jnp.float32)
    rows_cmp = compare(program, reference, cell["limits"])
    compiles_setup = common.compile_stats()

    tracer = None
    if args.trace:
        tracer = tracing.Tracer(cell["name"], 0.0, math.inf)
    step_s, losses, i = [], [], n_check
    setup_s = time.perf_counter() - t_start - reference_s
    t0 = t_prev = time.perf_counter()
    while t_prev - t0 < args.seconds:
        if tracer and len(step_s) == int(mix["trace_steps"]):
            tracer.stop()
        if tracer and not step_s:
            tracer.start()
        state, metrics = step(state, *feed(i))
        losses.append(float(jax.block_until_ready(metrics["loss"])))
        now = time.perf_counter()
        step_s.append(now - t_prev)
        t_prev, i = now, i + 1
    window = t_prev - t0
    if tracer:
        tracer.stop()
    compiles_end = common.compile_stats()
    peak = common.memory_peak(cell["chips"])
    tokens_per_step = rows * seq
    failed = sum(1 for v in losses if not math.isfinite(v))
    falling = statistics.fmean(losses[-3:]) < program["losses"][0]
    rows_cmp.append({"number": "loss_falls_over_window",
                     "value": float(not falling), "limit": 0})
    correct = failed == 0 and all(
        r["limit"] is not None and r["value"] <= r["limit"]
        for r in rows_cmp)
    stats = {
        "setup_s": setup_s, "window_s": window, "steps": len(step_s),
        "train_tok_s": tokens_per_step * len(step_s) / window,
        "step_median_s": statistics.median(step_s),
        "tokens_per_step": tokens_per_step,
        "compiles_in_window": compiles_end[0] - compiles_setup[0],
        "first_loss": program["losses"][0], "last_loss": losses[-1],
    }
    common.say(phase="window", reference_s=reference_s,
               reference_parts=reference["seconds"], **stats)
    common.say(phase="correct", compared=rows_cmp)

    device["memory_peak_bytes"] = peak
    result = {"correct": correct, "attempted": len(step_s), "failed": failed,
              "device": device}
    if args.trace:
        run_bag = {"cell": cell, "dims": dims, "stats": stats,
                   "chips": spec.n_devices, "seq": seq,
                   "rows_per_chip": int(mix["rows_per_chip"]),
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
        result["metrics"] = {
            m["name"]: {"value": float(stats[m["name"]]), "unit": m["unit"]}
            for m in cell["end_to_end"]}
    return result
