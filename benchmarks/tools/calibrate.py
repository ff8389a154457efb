#!/usr/bin/env python3
"""Read, in ONE process and so one set-up, what a limit or a fixed rate is
set from (a tool for the builder of a cell; the benchmark's runs never call
it).

Serving cells:
    --seeds a,b,c   short windows at the cell's own load, new weights and
                    traffic per seed: the program's worst logit gap and,
                    with --control, the gap of the float8 reference's tokens
    --rates r,...   one window per offered rate: latency, tokens/s and the
                    backlog left at the window's end (the sweep for the knee)
Training cells:
    --seeds a,b,c   the first steps against the reference, per seed; with
                    --control also the program with a bfloat16 state, and
                    the program fed only the batch's first row (the fault
                    the loss limit is held against)
"""

from __future__ import annotations

import argparse
import pathlib
import sys
import time

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[2]))

import numpy as np  # noqa: E402

from benchmarks.harness import common, serve, train, weights  # noqa: E402
from benchmarks.traffic import generator  # noqa: E402


def serve_cell(cell: dict, args) -> None:
    serve.set_program_env(cell)
    import jax.numpy as jnp

    config = cell["config"]
    dims = weights.ModelDims.from_config(config, args.tiny)
    opts = serve.loop_options(config, args.tiny)
    scale = (dims.positions / config["n_positions"]) if args.tiny else 1.0
    seeds = [int(s) for s in args.seeds.split(",")]
    rates = [float(r) for r in args.rates.split(",")] if args.rates else [
        None]
    params = weights.make_params(seeds[0], dims, jnp.bfloat16)
    loop = serve.build_loop(config, dims, params, args.tiny)
    serve.warm_up(loop, dims, opts, np.random.default_rng(0))
    for seed in seeds:
        if seed != seeds[0]:
            params = weights.make_params(seed, dims, jnp.bfloat16)
            loop.params = params
            loop.flush_prefix_cache()
        for rate in rates:
            mix = dict(cell["traffic"])
            if rate is not None:
                mix["rate_per_s"] = rate
            items = generator.serve_items(
                mix, seed, float(mix["ramp_s"]) + args.seconds, dims.vocab,
                scale)
            load = serve.Load(loop, items, mix, args.seconds, traced=False)
            loop.run(source=load.source, sink=load.sink)
            if args.timeline:
                step, nxt = 5.0, 5.0
                for t, tok, blocks, fin in load.polls:
                    if t >= nxt:
                        common.say(t=round(t, 2), served_tokens=tok,
                                   used_blocks=blocks, finished=fin,
                                   sent=sum(1 for _, s_ in load.sent.values()
                                            if s_ - load.t0 <= t))
                        nxt += step
            stats = serve.summarize(load, loop, args.seconds)
            backlog = sum(1 for rid in load.sent if load.in_window(rid)
                          and rid in load.done
                          and load.done[rid][1] > load.edges["end"]["t"])
            common.say(seed=seed, rate=mix.get("rate_per_s"),
                       backlog_at_end=backlog,
                       peak=common.memory_peak(1), **{
                k: v for k, v in stats.items() if not isinstance(v, tuple)})
            if args.rates:
                continue
            sample = serve.pick_sample(load, seed)
            t = time.perf_counter()
            line = {"seed": seed, "program": serve.reference_gaps(
                params, dims, sample)}
            line["reference_s"] = time.perf_counter() - t
            if args.control and seeds.index(seed) < args.control_seeds:
                line["control_fp8"] = serve.reference_gaps(
                    params, dims, sample, quant="fp8")
            common.say(**line)


def train_cell(cell: dict, args) -> None:
    import jax.numpy as jnp

    from tpudist.parallel import make_composed_state, shard_composed_batch

    config, mix = cell["config"], cell["traffic"]
    dims = weights.ModelDims.from_config(config, args.tiny)
    seq = dims.positions if args.tiny else int(mix["seq_len"])
    lr = float(config["program"]["options"]["learning_rate"])
    spec, mesh, model, tx, step = train.build(config, dims, mix, args.tiny)
    rows = int(mix["rows_per_chip"]) * spec.n_devices
    n_check = int(mix["checked_steps"])
    for done_seeds, seed in enumerate(
            int(s) for s in args.seeds.split(",")):
        ring = generator.train_batches(mix, seed, rows, dims.vocab, seq)
        t = time.perf_counter()
        reference = train.follow_reference(seed, dims, ring[:n_check], lr)
        ref_s = time.perf_counter() - t
        line = {"seed": seed, "reference_s": ref_s,
                "reference_parts": reference["seconds"],
                "ref_peak": common.memory_peak(spec.n_devices)}
        first_row = [tuple(np.repeat(a[:1], rows, 0) for a in xy)
                     for xy in ring]
        for name, dtype, fed in (("program", jnp.float32, ring),
                                 ("control", jnp.bfloat16, ring),
                                 ("row_left_out", jnp.float32, first_row)):
            if name != "program" and not (
                    args.control and done_seeds < args.control_seeds):
                continue
            feed = lambda i, fed=fed: shard_composed_batch(  # noqa: E731
                fed[i % len(fed)], mesh, spec)
            params = weights.make_params(seed, dims, dtype)
            state, _ = make_composed_state(
                model.apply, params, tx, spec, mesh, rng=seed & 0x7FFFFFFF)
            del params
            try:
                state, got = train.first_steps(step, state, feed, n_check,
                                               seed, dims, dtype)
                line[name] = [{k: r[k] for k in ("number", "value")}
                              for r in train.compare(got, reference, {})]
                line[name + "_losses"] = got["losses"]
            except Exception as e:  # noqa: BLE001 - a control may crash
                line[name] = f"failed: {type(e).__name__}: {e}"[:300]
            del state
        line["reference_losses"] = reference["losses"]
        common.say(**line)


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--rates", default="")
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--control", action="store_true")
    ap.add_argument("--control-seeds", type=int, default=99,
                    help="run the control on the first N seeds only")
    ap.add_argument("--timeline", action="store_true")
    ap.add_argument("--tiny", action="store_true")
    args = ap.parse_args()
    cell = common.load_cell(args.workload)
    common.say(device=common.device_info(cell["chips"], args.tiny))
    common.start_caches()
    if cell["config"]["runner"] == "serve":
        serve_cell(cell, args)
    else:
        train_cell(cell, args)
    return 0


if __name__ == "__main__":
    sys.exit(main())
