#!/usr/bin/env python3
"""Read, in ONE process and so one set-up, what the Keye-VL 2.0 cell's
limits, lanes, blocks, ramp and order are set from
(``tools/calibrate_mellum.py`` for the ``serve_keye`` runner; a tool for the
builder of a cell, never called by the benchmark's runs).

    --seeds a,b,c   one window at the cell's own load per seed, with new
                    weights and traffic: the window's numbers, the sorted
                    gap samples round the 90th percentile's rank, the
                    balance of lanes, and the program's worst and mean
                    logit gap; on the first ``--control-seeds`` of them the
                    gaps of the three controls' tokens (float8, the
                    selection skipped, the wrong rows)
    --order-seeds   the mix's list sent in these orders instead of the
                    file's ``order_seed``, one window each (paired with
                    ``--seeds`` in turn): how a builder looks for an order
                    whose 90th percentile lies clear of a step
    --iterations D  write ``D/<seed>_<order>.json``: every loop iteration
                    of the run from its first poll (its drain's gap sample,
                    lanes, rows and routed tokens; its chunk's offset and
                    host time), to tell what a seed changes in an iteration
                    that another seed's run holds too

Every set of gaps goes through the run's own comparison
(``serve_keye.compare`` against the cell's limits) and is printed with its
``correct``: true for the program, false for each control.
"""

from __future__ import annotations

import argparse
import gc
import pathlib
import sys
import time

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[2]))

import numpy as np  # noqa: E402

from benchmarks.harness import common, serve  # noqa: E402
from benchmarks.harness import serve_keye as runner  # noqa: E402
from benchmarks.traffic import generator  # noqa: E402


def verdicts(gaps: dict, failed: int, limits: dict) -> dict:
    """``correct`` of each set of gaps by the run's own comparison."""
    return {name: runner.compare(check, failed, limits)[1]
            for name, check in gaps.items()}


def gap_ranks(load, loop, around: int = 8) -> dict:
    """The window's gap samples sorted, round the rank at which the
    token-weighted 90th percentile falls (``gap_p90_ms`` is that sample):
    a step over 1% near the rank is what a run in ten would cross."""
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
            "ms_all": [round(1e3 * g, 2) for g, _ in gaps]}


def write_iterations(out_dir: str, seed: int, order: int, load, loop,
                     events) -> None:
    """Every drained segment of the run, by the loop's ``seq``, with the
    chunk that rode in its iteration; times in seconds from the first
    poll."""
    import json

    t0 = load.t0
    chunks = {e["args"]["seq"]: e for e in events
              if e["name"] == "serve/prefill_chunk"}
    rows = []
    for e in events:
        if e["name"] != "serve/segment_drain":
            continue
        a = e["args"]
        c = chunks.get(a["seq"])
        rows.append({
            "seq": a["seq"], "t": e["ts"] * 1e-6 - t0,
            **{k: a.get(k) for k in (
                "steps_run", "lanes", "tokens", "rows_scored",
                "rows_selected", "expert_tokens", "expert_tokens_max")},
            "chunk_off": None if c is None else c["args"]["off"],
            "chunk_host_ms": None if c is None else c["dur"] * 1e-3})
    a, b = load.edges["start"], load.edges["end"]
    path = pathlib.Path(out_dir)
    path.mkdir(parents=True, exist_ok=True)
    with open(path / f"{seed}_{order}.json", "w") as f:
        json.dump({"seed": seed, "order_seed": order,
                   "window": [a["t"] - t0, b["t"] - t0],
                   "window_samples": [a["gap_samples"], b["gap_samples"]],
                   "gap_samples": [[1e3 * g, n] for g, n
                                   in loop.intertoken_samples],
                   "iterations": rows}, f)


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=40.0)
    ap.add_argument("--control-seeds", type=int, default=0,
                    help="run the three controls on the first N seeds")
    ap.add_argument("--requests", type=int, default=0,
                    help="score only the N shortest requests of the sample "
                         "(0: all; each control is a reference of its own; "
                         "-1: no reference at all)")
    ap.add_argument("--order-seeds", default="")
    ap.add_argument("--iterations", default="")
    ap.add_argument("--tiny", action="store_true")
    args = ap.parse_args()
    cell = common.load_cell(args.workload, args.tiny)
    common.say(device=common.device_info(cell["chips"], args.tiny))
    common.start_caches()
    serve.set_program_env(cell)
    import jax.numpy as jnp

    from tpudist import obs

    config, mix = cell["config"], cell["traffic"]
    dims = runner.model_dims(config, args.tiny)
    positions = runner.max_seq_len(config, args.tiny)
    opts = serve.loop_options(config, args.tiny)
    scale = (positions / config["program"]["max_seq_len"]
             if args.tiny else 1.0)
    orders = [int(s) for s in args.order_seeds.split(",") if s]
    for n, seed in enumerate(int(s) for s in args.seeds.split(",")):
        if orders:
            mix = dict(mix, order_seed=orders[n % len(orders)])
        params = runner.make_params(seed, dims, jnp.bfloat16)
        loop = runner.build_loop(config, dims, params, args.tiny)
        serve.warm_up(loop, dims, opts, np.random.default_rng(0))
        items = generator.serve_items(
            mix, seed, float(mix["ramp_s"]) + args.seconds, dims.vocab,
            scale)
        load = serve.Load(loop, items, mix, args.seconds, traced=False)
        obs.tracer.clear()
        loop.run(source=load.source, sink=load.sink)
        stats = serve.summarize(load, loop, args.seconds)
        # requests that had finished when the window opened (the lanes
        # that had turned over by then) and the lanes that decoded in the
        # window's segments
        before = max((done for t, _, _, done in load.polls
                      if t <= float(mix["ramp_s"])), default=0)
        lo, hi = load.edges["start"]["t"], load.edges["end"]["t"]
        drains = [e["args"] for e in obs.tracer.events()
                  if e["name"] == "serve/segment_drain"
                  and lo <= e["ts"] * 1e-6 < hi]
        chunks = sum(1 for e in obs.tracer.events()
                     if e["name"] == "serve/prefill_chunk"
                     and lo <= e["ts"] * 1e-6 < hi)
        common.say(seed=seed, order_seed=mix["order_seed"],
                   peak=common.memory_peak(1),
                   finished_before_window=before,
                   lanes_decoding=sorted(a["lanes"] for a in drains),
                   chunks_in_window=chunks, **{
                       k: v for k, v in stats.items()
                       if not isinstance(v, tuple)})
        common.say(seed=seed, **gap_ranks(load, loop))
        if args.iterations:
            write_iterations(args.iterations, seed, mix["order_seed"], load,
                             loop, obs.tracer.events())
        sample = serve.pick_sample(load, seed)
        sample = sample[: max(args.requests, 0) or len(sample)]
        # the loop and its jitted methods refer to each other: only a
        # collection frees the pools before the reference needs the room
        del loop, load
        gc.collect()
        if args.requests < 0:
            del params
            continue
        t = time.perf_counter()
        line = runner.reference_gaps(
            params, dims, positions, sample,
            controls=runner.CONTROLS if n < args.control_seeds else ())
        common.say(seed=seed, reference_s=time.perf_counter() - t,
                   lengths=[len(c.prompt) + len(c.tokens) for c in sample],
                   correct=verdicts(line, stats["failed"], cell["limits"]),
                   **line)
        del params
        gc.collect()
    return 0


if __name__ == "__main__":
    sys.exit(main())
