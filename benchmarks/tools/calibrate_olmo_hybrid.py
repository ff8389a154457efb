#!/usr/bin/env python3
"""Read what the Olmo-Hybrid cell's numbers are set from (a tool for the
builder of a cell, never called by the benchmark's runs).

    --aot           compile the cell's three serve programs at full size
                    for a DESCRIBED v5e (no chip attached, nothing runs)
                    and print the compiler's memory analysis and what the
                    lanes' state and the pages take; --lanes / --blocks
                    override the file's
    --replay        the mix's one schedule replayed on the host against
                    the loop's admission rule (no device, no model): the
                    most blocks the lanes reserve at once, so that
                    kv_num_blocks can be set where no admission waits for
                    blocks; --lanes overrides the file's, --requests N
                    stops after the first N of the list have finished
                    (the list is sized by an upper estimate, several
                    times what a run reaches)
    --seeds a,b,c   on the chip (or --tiny anywhere): one window at the
                    cell's own load per seed, with new weights and
                    traffic: the program's worst and mean logit gap and,
                    on the first --control-seeds of them, the gaps of the
                    three controls' tokens: the float8 reference, the
                    reference with alpha = 1, the reference with beta not
                    doubled

Weights, pools and state of this configuration leave the reference no room
beside the loop, so the loop is built anew for every seed (the compiled
programs come from the persistent cache) and dropped before the reference
runs.
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
from benchmarks.harness import serve_olmo_hybrid as runner  # noqa: E402
from benchmarks.traffic import generator  # noqa: E402

GIB = 2 ** 30


def replay(mix: dict, opts: dict, positions: int, seconds: float,
           requests: int = 0) -> dict:
    """The schedule every seed offers, against ``ServeLoop``'s admission:
    FIFO into free lanes, at most ``max_prefill_lanes`` in admission, one
    chunk a prefilling lane and one segment of ``steps_per_sync`` tokens a
    decoding lane an iteration, a lane released an iteration after its last
    token (the pipelined drain).  A request holds its worst case
    (``blocks_for(prompt + budget)``) from admission to release, as
    ``BlockPool.admit`` reserves it."""
    items = generator.serve_items(
        mix, 0, float(mix["ramp_s"]) + seconds, 2, 1.0)
    if requests:    # the first of the list: what a run gets to
        items = items[:requests + int(mix["clients"])]
    block, chunk = opts["kv_block_size"], opts["prefill_chunk"]
    steps, lanes = opts["steps_per_sync"], opts["num_slots"]
    held = [None] * lanes     # [chunks left, tokens left, blocks, lag]
    nxt = peak = finished = iterations = 0
    peak_lanes = []
    while finished < len(items) - int(mix["clients"]):
        prefilling = sum(1 for h in held if h and h[0])
        for slot in range(lanes):
            if held[slot] is None and nxt < len(items):
                if prefilling >= opts["max_prefill_lanes"]:
                    break
                it = items[nxt]
                nxt += 1
                total = min(len(it.prompt) + it.max_new, positions)
                held[slot] = [-(-len(it.prompt) // chunk), it.max_new - 1,
                              -(-total // block), 1]
                prefilling += 1
        used = sum(h[2] for h in held if h)
        if used > peak:
            peak, peak_lanes = used, [h[2] for h in held if h]
        for slot, h in enumerate(held):
            if h is None:
                continue
            if h[0]:
                h[0] -= 1
                if h[0]:
                    continue
            if h[1] > 0:
                h[1] -= steps
            elif h[3]:
                h[3] -= 1
            else:
                held[slot] = None
                finished += 1
        iterations += 1
    return {"requests": finished, "iterations": iterations,
            "blocks_reserved_peak": peak, "lanes_at_peak": peak_lanes}


def aot(cell: dict, opts: dict) -> None:
    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding
    from unittest import mock

    jax.config.update("jax_enable_compilation_cache", False)
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    one = SingleDeviceSharding(topo.devices[0])
    config = dict(cell["config"])
    config["program"] = {**config["program"], "options": opts}
    dims = runner.model_dims(config)
    params = jax.eval_shape(
        lambda: runner.make_params(0, dims, jnp.bfloat16))
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
        state_gib=loop._state_lane_bytes * loop.B / GIB,
        state_lane_bytes=loop._state_lane_bytes,
        prefilling_lane_gib=nbytes(loop._blank1) / GIB)
    for name, (jitted, args, static) in loop.serve_programs().items():
        with mock.patch.object(jax, "default_backend", lambda: "tpu"):
            compiled = jitted.lower(*on(args), **static).compile()
        m = compiled.memory_analysis()
        common.say(program=name,
                   arguments_gib=m.argument_size_in_bytes / GIB,
                   outputs_gib=m.output_size_in_bytes / GIB,
                   aliased_gib=m.alias_size_in_bytes / GIB,
                   temporaries_gib=m.temp_size_in_bytes / GIB,
                   kernels=compiled.as_text().count("tpu_custom_call"))


def windows(cell: dict, args, opts: dict) -> None:
    import jax.numpy as jnp

    common.say(device=common.device_info(cell["chips"], args.tiny))
    common.start_caches()
    serve.set_program_env(cell)
    config, mix = dict(cell["config"]), cell["traffic"]
    key = "tiny" if args.tiny else "program"
    config[key] = {**config[key], "options": opts}
    dims = runner.model_dims(config, args.tiny)
    positions = runner.max_seq_len(config, args.tiny)
    scale = (positions / config["program"]["max_seq_len"]
             if args.tiny else 1.0)
    controls = tuple(runner.CONTROLS)
    for n, seed in enumerate(int(s) for s in args.seeds.split(",")):
        params = runner.make_params(seed, dims, jnp.bfloat16)
        loop = runner.build_loop(config, dims, params, args.tiny)
        full = serve.loop_options(config, args.tiny)
        serve.warm_up(loop, dims, full, np.random.default_rng(0))
        items = generator.serve_items(
            mix, seed, float(mix["ramp_s"]) + args.seconds, dims.vocab,
            scale)
        load = serve.Load(loop, items, mix, args.seconds, traced=False)
        loop.run(source=load.source, sink=load.sink)
        stats = serve.summarize(load, loop, args.seconds)
        common.say(seed=seed, peak=common.memory_peak(1), **{
            k: v for k, v in stats.items() if not isinstance(v, tuple)})
        sample = serve.pick_sample(load, seed, args.sample_extra)
        # the loop and its jitted methods refer to each other: only a
        # collection frees the pools before the reference needs the room
        del loop, load
        gc.collect()
        t = time.perf_counter()
        gaps = runner.reference_gaps(
            params, dims, positions, sample,
            controls if n < args.control_seeds else ())
        common.say(seed=seed, reference_s=time.perf_counter() - t, **gaps)
        del params
        gc.collect()


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", default="olmoh7b_doc_mixed")
    ap.add_argument("--aot", action="store_true")
    ap.add_argument("--replay", action="store_true")
    ap.add_argument("--seeds", default="")
    ap.add_argument("--seconds", type=float, default=40.0)
    ap.add_argument("--control-seeds", type=int, default=0,
                    help="run the three controls on the first N seeds")
    ap.add_argument("--sample-extra", type=int, default=2,
                    help="requests of the sample beyond the longest, the "
                         "median and the shortest (each control costs a "
                         "forward pass a request)")
    ap.add_argument("--requests", type=int, default=0)
    ap.add_argument("--lanes", type=int, default=0)
    ap.add_argument("--blocks", type=int, default=0)
    ap.add_argument("--tiny", action="store_true")
    args = ap.parse_args()
    cell = common.load_cell(args.workload)
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
    if args.seeds:
        windows(cell, args, opts)
    return 0


if __name__ == "__main__":
    sys.exit(main())
