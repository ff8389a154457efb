#!/usr/bin/env python3
"""Read what the Command A+ cell's lanes, blocks, recipe and limits are set
from (``tools/calibrate_olmo_hybrid.py`` for the ``serve_command_a`` runner;
a tool for the builder of a cell, never called by the benchmark's runs).

    --aot           compile the cell's three serve programs at full size
                    for a DESCRIBED v5e (no chip, nothing runs) and print
                    the compiler's memory analysis, the pools' and a
                    prefilling lane's bytes, and the paged walks' grid
    --replay        the mix's one schedule replayed on the host against
                    ``ServeLoop``'s admission (``calibrate_olmo_hybrid``'s):
                    the most blocks the full group ever has reserved
    --seeds a,b,c   one window at the cell's own load per seed, with new
                    weights and traffic: the window's numbers, the chunks
                    the window's iterations carried, the sorted gap samples
                    round the 90th percentile, the program's worst and mean
                    logit gap, what the reference's own router did at the
                    served positions (``held_share``, ``held_max_share``,
                    ``held_touched`` a layer at the cell's lanes) and, on
                    the first ``--control-seeds`` of them, the gaps of the
                    four controls' tokens
    --scan f,g      the recipe's factor: for each value, the FIRST seed's
                    weights drawn with it, ``--scan-requests`` requests of
                    the mix's own prompts with outputs cut to
                    ``--scan-tokens`` served (no window, no ramp), and the
                    same readings from the reference, with all four controls

``--lanes``, ``--blocks`` and ``--factor`` stand in for the files' values.
"""

from __future__ import annotations

import argparse
import gc
import os
import pathlib
import sys
import time

os.environ.setdefault("TPU_LOG_DIR", "disabled")
sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[2]))

import numpy as np  # noqa: E402

from benchmarks.harness import common, serve  # noqa: E402
from benchmarks.harness import serve_command_a as runner  # noqa: E402
from benchmarks.reference import command_a as ref  # noqa: E402
from benchmarks.tools.calibrate_olmo_hybrid import replay  # noqa: E402
from benchmarks.traffic import generator  # noqa: E402

GIB = 2 ** 30


def aot(cell: dict, opts: dict) -> None:
    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding
    from unittest import mock

    from tpudist.ops.flash_decode import paged_grid_rows

    jax.config.update("jax_enable_compilation_cache", False)
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    one = SingleDeviceSharding(topo.devices[0])
    config = dict(cell["config"])
    config["program"] = {**config["program"], "options": opts}
    dims = runner.model_dims(config)
    params = jax.eval_shape(
        lambda: runner.make_params(0, dims, jnp.bfloat16, 1.0))
    loop = runner.build_loop(config, dims, params, tiny=False)
    on = lambda tree: jax.tree.map(   # noqa: E731
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one),
        tree)
    nbytes = lambda tree: sum(   # noqa: E731
        int(np.prod(x.shape)) * x.dtype.itemsize
        for x in jax.tree.leaves(tree))
    common.say(
        parameters=runner.count_params(dims),
        weight_gib=nbytes(params) / GIB,
        slot_cache_gib=nbytes(loop.cache) / GIB,
        full_blocks=loop.kv_num_blocks, window_blocks=loop.kv_window_blocks,
        prefilling_lane_bytes=nbytes(loop._blank1),
        grid_rows=paged_grid_rows(
            opts["num_slots"], dims.kv_heads, dims.head_dim,
            opts["kv_block_size"], loop.pool.max_blocks_per_slot),
        heads_per_grid_row=loop._row_heads)
    for name, (jitted, args, static) in loop.serve_programs().items():
        with mock.patch.object(jax, "default_backend", lambda: "tpu"):
            compiled = jitted.lower(*on(args), **static).compile()
        m = compiled.memory_analysis()
        text = compiled.as_text()
        common.say(program=name,
                   arguments_gib=m.argument_size_in_bytes / GIB,
                   outputs_gib=m.output_size_in_bytes / GIB,
                   aliased_gib=m.alias_size_in_bytes / GIB,
                   temporaries_gib=m.temp_size_in_bytes / GIB,
                   kernels=text.count("tpu_custom_call"))


def gap_ranks(load, loop, around: int = 6) -> dict:
    """The window's gap samples sorted, round the rank at which the
    token-weighted 90th percentile falls (``gap_p90_ms`` is that sample)."""
    a, b = load.edges["start"], load.edges["end"]
    gaps = sorted(loop.intertoken_samples[a["gap_samples"]:
                                          b["gap_samples"]])
    total = sum(n for _, n in gaps)
    acc, rank = 0.0, len(gaps) - 1
    for i, (_, n) in enumerate(gaps):
        acc += n
        if acc >= 0.9 * total:
            rank = i
            break
    lo, hi = max(0, rank - around), min(len(gaps), rank + around + 1)
    return {"samples": len(gaps), "rank": rank,
            "ms_round_the_rank": [round(1e3 * g, 3) for g, _ in gaps[lo:hi]],
            "ms_deciles": [round(1e3 * gaps[int(q * (len(gaps) - 1))][0], 3)
                           for q in (0.1, 0.3, 0.5, 0.7, 0.9, 1.0)]}


def score(host, dims, positions, sample, lanes: int, controls) -> dict:
    """The program's gaps and each control's, with ``correct`` as the run
    decides it left to the reader: a set of gaps beside the limits."""
    out = {"program": runner.reference_gaps(host, dims, positions, sample,
                                            lanes=lanes)}
    for name in controls:
        out[name] = {k: v for k, v in runner.reference_gaps(
            host, dims, positions, sample, ref.CONTROLS[name]).items()
            if k in ("worst_gap", "mean_gap", "exact_argmax_share")}
    return out


def _setup(cell: dict, args, opts: dict):
    common.say(device=common.device_info(cell["chips"], args.tiny))
    common.start_caches()
    serve.set_program_env(cell)
    config, mix = dict(cell["config"]), dict(cell["traffic"])
    key = "tiny" if args.tiny else "program"
    config[key] = {**config[key], "options": opts}
    dims = runner.model_dims(config, args.tiny)
    positions = runner.max_seq_len(config, args.tiny)
    scale = (positions / config["program"]["max_seq_len"]
             if args.tiny else 1.0)
    return config, mix, dims, positions, scale


def windows(cell: dict, args, opts: dict) -> None:
    import jax.numpy as jnp

    from tpudist import obs

    config, mix, dims, positions, scale = _setup(cell, args, opts)
    factor = args.factor or runner.branch_factor(config)
    full = serve.loop_options(config, args.tiny)
    for n, seed in enumerate(int(s) for s in args.seeds.split(",")):
        params = runner.make_params(seed, dims, jnp.bfloat16, factor)
        loop = runner.build_loop(config, dims, params, args.tiny)
        serve.warm_up(loop, dims, full, np.random.default_rng(0))
        items = generator.serve_items(
            mix, seed, float(mix["ramp_s"]) + args.seconds, dims.vocab,
            scale)
        load = runner.WindowLoad(loop, items, mix, args.seconds,
                                 traced=False)
        obs.tracer.clear()
        loop.run(source=load.source, sink=load.sink)
        stats = serve.summarize(load, loop, args.seconds)
        lo, hi = load.edges["start"]["t"], load.edges["end"]["t"]
        spans = [e for e in obs.tracer.events()
                 if lo <= e["ts"] * 1e-6 < hi]
        drains = [e["args"] for e in spans
                  if e["name"] == "serve/segment_drain"]
        # the chunks each of the window's iterations carried: the chunk
        # spans between two drains
        carried, count = {}, 0
        for e in sorted(spans, key=lambda e: e["ts"]):
            if e["name"] == "serve/prefill_chunk":
                count += 1
            elif e["name"] == "serve/segment_drain":
                carried[count] = carried.get(count, 0) + 1
                count = 0
        steps = max(sum(a["steps_run"] for a in drains), 1)
        common.say(
            seed=seed, factor=factor, order_seed=mix["order_seed"],
            peak=common.memory_peak(1),
            kv_window_blocks_peak=max(load.window_block_samples, default=0),
            kv_window_blocks_total=loop.kv_window_blocks,
            lanes_mean=sum(a["lanes"] * a["steps_run"] for a in drains)
            / steps,
            rows_live_mean=sum(a["rows_live"] * a["steps_run"]
                               for a in drains) / steps,
            expert_tokens_per_step=sum(a.get("expert_tokens", 0)
                                       for a in drains) / steps,
            expert_load_max_share=100.0 * sum(
                a.get("expert_tokens_max", 0) for a in drains) / max(sum(
                    a.get("expert_tokens", 0) for a in drains), 1),
            iterations_by_chunks=sorted(carried.items()),
            **{k: v for k, v in stats.items() if not isinstance(v, tuple)})
        common.say(seed=seed, **gap_ranks(load, loop))
        if args.sample_extra < 0:
            del loop, load, params
            gc.collect()
            continue
        sample = serve.pick_sample(load, seed, args.sample_extra)
        host = runner.to_host(params)
        # the loop and its jitted methods refer to each other: only a
        # collection frees the pools before the reference needs the room
        del loop, load, params
        gc.collect()
        t = time.perf_counter()
        gaps = score(host, dims, positions, sample, opts["num_slots"],
                     ref.CONTROLS if n < args.control_seeds else ())
        common.say(seed=seed, reference_s=time.perf_counter() - t,
                   lengths=[len(c.prompt) + len(c.tokens) for c in sample],
                   distinct_tokens=[len(set(np.asarray(c.tokens).tolist()))
                                    for c in sample], **gaps)
        del host
        gc.collect()


def scan(cell: dict, args, opts: dict) -> None:
    import jax.numpy as jnp

    from tpudist.models import Request

    config, mix, dims, positions, scale = _setup(cell, args, opts)
    seed = int(args.seeds.split(",")[0])
    full = serve.loop_options(config, args.tiny)
    for factor in (float(f) for f in args.scan.split(",")):
        params = runner.make_params(seed, dims, jnp.bfloat16, factor)
        loop = runner.build_loop(config, dims, params, args.tiny)
        serve.warm_up(loop, dims, full, np.random.default_rng(0))
        items = generator.serve_items(mix, seed, 1.0, dims.vocab, scale)[
            : args.scan_requests]
        reqs = [Request(it.prompt, min(it.max_new, args.scan_tokens),
                        rid=it.rid) for it in items]
        done = []

        def source():
            if not reqs:
                return None
            out = reqs[: serve.ADMIT_PER_POLL]
            del reqs[: serve.ADMIT_PER_POLL]
            return out

        t = time.perf_counter()
        loop.run(source=source, sink=done.append)
        served_s = time.perf_counter() - t
        done.sort(key=lambda c: (len(c.prompt), c.rid))
        sample = [done[0], done[len(done) // 2], done[-1]]
        peak = common.memory_peak(1)
        host = runner.to_host(params)
        del loop, params
        gc.collect()
        t = time.perf_counter()
        gaps = score(host, dims, positions, sample, opts["num_slots"],
                     ref.CONTROLS)
        common.say(factor=factor, seed=seed, served_s=served_s, peak=peak,
                   reference_s=time.perf_counter() - t,
                   lengths=[len(c.prompt) + len(c.tokens) for c in sample],
                   distinct_tokens=[len(set(np.asarray(c.tokens).tolist()))
                                    for c in sample], **gaps)
        del host
        gc.collect()


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", default="cmdaplus_rag_mixed")
    ap.add_argument("--aot", action="store_true")
    ap.add_argument("--replay", action="store_true")
    ap.add_argument("--seeds", default="")
    ap.add_argument("--scan", default="")
    ap.add_argument("--scan-requests", type=int, default=48)
    ap.add_argument("--scan-tokens", type=int, default=256)
    ap.add_argument("--seconds", type=float, default=40.0)
    ap.add_argument("--control-seeds", type=int, default=0,
                    help="run the four controls on the first N seeds")
    ap.add_argument("--sample-extra", type=int, default=runner.SAMPLE_EXTRA,
                    help="requests of the sample beyond the longest, the "
                         "median and the shortest (-1: no reference)")
    ap.add_argument("--requests", type=int, default=0)
    ap.add_argument("--lanes", type=int, default=0)
    ap.add_argument("--blocks", type=int, default=0)
    ap.add_argument("--factor", type=float, default=0.0)
    ap.add_argument("--tiny", action="store_true")
    args = ap.parse_args()
    cell = common.load_cell(args.workload, args.tiny)
    opts = serve.loop_options(cell["config"], args.tiny)
    if args.lanes:
        opts["num_slots"] = args.lanes
    if args.blocks:
        opts["kv_num_blocks"] = args.blocks
    if args.replay:
        common.say(replay=replay(
            cell["traffic"], opts,
            runner.max_seq_len(cell["config"], args.tiny), args.seconds,
            args.requests), lanes=opts["num_slots"])
    if args.aot:
        aot(cell, opts)
    if args.scan:
        scan(cell, args, opts)
    elif args.seeds:
        windows(cell, args, opts)
    return 0


if __name__ == "__main__":
    sys.exit(main())
