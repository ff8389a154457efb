#!/usr/bin/env python3
"""Read what the Mellum 2 cells' limits are set from
(``tools/calibrate_deepseek.py`` for the ``serve_mellum`` runner; a tool for
the builder of a cell, never called by the benchmark's runs).

    --seeds a,b,c   one window at the cell's own load per seed, with new
                    weights and traffic: the program's worst and mean logit
                    gap and, on the first ``--control-seeds`` of them, the
                    gaps of the two controls' tokens: the float8 reference,
                    and the reference with the band left out of the sliding
                    layers

Weights and pools of this configuration leave the reference no room beside
the loop, so the loop is built anew for every seed (the compiled programs
come from the persistent cache) and dropped before the reference runs.
"""

from __future__ import annotations

import argparse
import gc
import pathlib
import sys
import time

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[2]))

import numpy as np  # noqa: E402

from benchmarks.harness import common, serve, serve_mellum  # noqa: E402
from benchmarks.traffic import generator  # noqa: E402


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=40.0)
    ap.add_argument("--control-seeds", type=int, default=0,
                    help="run the two controls on the first N seeds")
    ap.add_argument("--tiny", action="store_true")
    args = ap.parse_args()
    cell = common.load_cell(args.workload)
    common.say(device=common.device_info(cell["chips"], args.tiny))
    common.start_caches()
    serve.set_program_env(cell)
    import jax.numpy as jnp

    config, mix = cell["config"], cell["traffic"]
    dims = serve_mellum.model_dims(config, args.tiny)
    positions = serve_mellum.max_seq_len(config, args.tiny)
    opts = serve.loop_options(config, args.tiny)
    scale = (positions / config["program"]["max_seq_len"]
             if args.tiny else 1.0)
    for n, seed in enumerate(int(s) for s in args.seeds.split(",")):
        params = serve_mellum.make_params(seed, dims, jnp.bfloat16)
        loop = serve_mellum.build_loop(config, dims, params, args.tiny)
        serve.warm_up(loop, dims, opts, np.random.default_rng(0))
        items = generator.serve_items(
            mix, seed, float(mix["ramp_s"]) + args.seconds, dims.vocab,
            scale)
        load = serve_mellum.WindowLoad(loop, items, mix, args.seconds,
                                       traced=False)
        loop.run(source=load.source, sink=load.sink)
        stats = serve.summarize(load, loop, args.seconds)
        common.say(seed=seed, peak=common.memory_peak(1),
                   kv_window_blocks_peak=max(load.window_block_samples,
                                             default=0),
                   **{k: v for k, v in stats.items()
                      if not isinstance(v, tuple)})
        sample = serve.pick_sample(load, seed)
        # the loop and its jitted methods refer to each other: only a
        # collection frees the pools before the reference needs the room
        del loop, load
        gc.collect()
        t = time.perf_counter()
        line = {"seed": seed, "program": serve_mellum.reference_gaps(
            params, dims, positions, sample)}
        line["reference_s"] = time.perf_counter() - t
        if n < args.control_seeds:
            line["control_fp8"] = serve_mellum.reference_gaps(
                params, dims, positions, sample, quant="fp8")
            line["control_no_window"] = serve_mellum.reference_gaps(
                params, dims, positions, sample, window=False)
        common.say(**line)
        del params
        gc.collect()
    return 0


if __name__ == "__main__":
    sys.exit(main())
