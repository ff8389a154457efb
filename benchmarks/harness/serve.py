"""Runner for serving cells: one ``ServeLoop`` fed through ``source`` and
``sink`` by an open or a closed loop of seeded requests.

One run: weights from the seed on the device, the loop, a warm-up that
compiles every program the traffic reaches, then ONE ``loop.run`` that
takes the ramp, the measured window and the drain.  The window's edges are
the polls of ``source()`` that straddle them, so tokens and time are read
at the same instants.  After ``run`` returns, the pool is freed and the
plain reference scores a seeded sample of the finished requests.
"""

from __future__ import annotations

import os
import statistics
import time

import numpy as np

from benchmarks.harness import common, weights
from benchmarks.traffic import generator

WARM_REQUESTS = 1.25  # times the lane count: lanes are reused in the warm-up
TRACE_STOP_ALLOWANCE_S = 120.0
ADMIT_PER_POLL = 4  # new requests a poll in the warm-up and a closed loop


def transformer_config(dims: weights.ModelDims, dtype):
    from tpudist.models import TransformerConfig

    return TransformerConfig(
        vocab_size=dims.vocab, num_layers=dims.layers, num_heads=dims.heads,
        num_kv_heads=dims.kv_heads, embed_dim=dims.embed,
        max_seq_len=dims.positions, compute_dtype=dtype)


def loop_options(config: dict, tiny: bool) -> dict:
    opts = dict(config["program"]["options"])
    if tiny:
        opts.update(config["tiny"]["options"])
    return opts


def set_program_env(cell: dict) -> None:
    """The program's own environment knobs, as the configuration (and, over
    it, the traffic mix) states them; set before the loop is built."""
    for src in (cell["config"]["program"], cell["traffic"]):
        os.environ.update(src.get("env", {}))


def build_loop(config: dict, dims: weights.ModelDims, params, tiny: bool):
    import jax.numpy as jnp

    from tpudist.models import ServeLoop

    return ServeLoop(transformer_config(dims, jnp.bfloat16), params,
                     **loop_options(config, tiny))


class Load:
    """The generator's side of ``loop.run(source=, sink=)``."""

    def __init__(self, loop, items, mix: dict, seconds: float,
                 traced: bool, trace_hook=None) -> None:
        self.loop, self.items, self.mix = loop, items, mix
        self.ramp = float(mix["ramp_s"])
        self.end = self.ramp + seconds
        self.closed_loop = mix["kind"] == "closed_loop"
        self.clients = int(mix.get("clients", 0))
        self.traced = traced
        self.trace_hook = trace_hook
        self.t0 = None            # perf_counter at the first poll
        self.next = 0             # next item to send
        self.outstanding = 0
        self.sent: dict = {}      # rid -> (due, sent) perf_counter times
        self.done: dict = {}      # rid -> (completion, perf_counter)
        self.edges: dict = {}     # "start"/"end" -> snapshot at that poll
        self.block_samples: list[int] = []
        self.polls: list[tuple] = []  # (t, tokens served, blocks, finished)
        self.events: list[dict] = []
        self._event_i = -1

    def _snapshot(self, now: float) -> dict:
        from tpudist import obs

        return {
            "t": now,
            "served_tokens": self.loop._served_tokens,
            "gap_samples": len(self.loop.intertoken_samples),
            "queue_wait": common.hist_totals("serve/queue_wait_s"),
            "host_wait": common.hist_totals("serve/host_wait"),
            "segments": obs.counter("serve/segments").value(),
            "prompt_tokens": obs.counter("serve/prompt_tokens").value(),
            "compiles": common.compile_stats(),
        }

    def _request(self, item, due: float, now: float):
        from tpudist.models import Request
        from tpudist.obs import TraceContext

        self.sent[item.rid] = (due, now)
        self.outstanding += 1
        return Request(
            item.prompt, item.max_new, rid=item.rid,
            deadline_s=self.deadline_wall,
            trace=(TraceContext(trace_id=str(item.rid))
                   if self.traced else None))

    def source(self):
        now = time.perf_counter()
        if self.t0 is None:
            self.t0 = now
            # a traced run may stand still for a minute while the profiler
            # writes its trace at the window's end
            self.deadline_wall = (time.time() + self.end
                                  + float(self.mix["drain_limit_s"])
                                  + (TRACE_STOP_ALLOWANCE_S if self.traced
                                     else 0.0))
        t = now - self.t0
        if "start" not in self.edges:
            if t >= self.ramp:
                self.edges["start"] = self._snapshot(now)
        elif "end" not in self.edges:  # at a later poll than the start
            if t >= self.end:
                self.edges["end"] = self._snapshot(now)
            else:
                self.block_samples.append(self.loop.pool.used_blocks)
        self.polls.append((t, self.loop._served_tokens,
                           self.loop.pool.used_blocks, len(self.done)))
        if self.trace_hook is not None:
            self.trace_hook(t)
        if self.traced:
            self._collect_events()
        if t >= self.end:
            return None if self.outstanding == 0 else []
        out = []
        if self.closed_loop:
            while (self.outstanding < self.clients
                   and self.next < len(self.items)
                   and len(out) < ADMIT_PER_POLL):
                out.append(self._request(self.items[self.next], now, now))
                self.next += 1
        else:
            while (self.next < len(self.items)
                   and self.items[self.next].at <= t):
                item = self.items[self.next]
                out.append(self._request(item, self.t0 + item.at, now))
                self.next += 1
        return out

    def sink(self, comp) -> None:
        self.done[comp.rid] = (comp, time.perf_counter())
        self.outstanding -= 1

    def _collect_events(self) -> None:
        from tpudist import obs

        for ev in obs.events.events():
            if ev["i"] > self._event_i:
                self._event_i = ev["i"]
                if ev["kind"] in ("segment", "admit", "prefill_chunk"):
                    self.events.append(ev)

    def in_window(self, rid) -> bool:
        """Does this request count as attempted?  In an open loop: it was
        due inside the measured window.  In a closed loop, where the next
        request is due when one completes: its completion (or its failure)
        fell inside the window; what is still running at the window's end is
        cut by the deadline and belongs to no window."""
        if self.closed_loop:
            if rid not in self.done or "end" not in self.edges:
                return False
            return (self.edges["start"]["t"] <= self.done[rid][1]
                    < self.edges["end"]["t"])
        due = self.sent[rid][0] - self.t0
        return self.ramp <= due < self.end


def warm_up(loop, dims, opts: dict, rng: np.random.Generator) -> None:
    """Every program the traffic reaches: one prefill chunk width (at a zero
    and a non-zero offset), the admission's finish, the segment, and the
    op-by-op pieces around them, lane reuse included.  Requests arrive
    ``ADMIT_PER_POLL`` at a time: a lane that prefills holds a batch-1 cache
    and a chunk of float32 logits (237 MB at the 3b's sizes), and all lanes
    prefilling at once do not fit beside the weights and the pool."""
    from tpudist.models import Request

    chunk = opts["prefill_chunk"]
    n = int(WARM_REQUESTS * opts["num_slots"])
    reqs = [Request(rng.integers(0, dims.vocab,
                                 chunk + 1 + i % 7).astype(np.int32),
                    opts["steps_per_sync"] + 2 + i % 5, rid=f"warm{i}")
            for i in range(n)]
    done = []

    def source():
        if not reqs:
            return None
        out = reqs[:ADMIT_PER_POLL]
        del reqs[:ADMIT_PER_POLL]
        return out

    loop.run(source=source, sink=done.append)
    if len(done) != n or any(c.reason != "length" for c in done):
        raise RuntimeError("warm-up requests did not all finish")


def pick_sample(load: Load, seed: int, extra: int = 2) -> list:
    """Finished requests of the window for the reference: the longest, the
    shortest, the median, and ``extra`` more drawn from the seed."""
    ok = [c for rid, (c, _) in load.done.items()
          if load.in_window(rid) and c.reason in ("length", "stop")
          and len(c.tokens)]
    if not ok:
        return []
    ok.sort(key=lambda c: (len(c.prompt) + len(c.tokens), c.rid))
    picks = {0, len(ok) // 2, len(ok) - 1}
    rng = np.random.default_rng([int(seed), 0xC0FFEE])
    picks.update(int(i) for i in rng.integers(0, len(ok), extra))
    return [ok[i] for i in sorted(picks)]


def _bucket(n: int, positions: int) -> int:
    """Reference sequences are padded to a power of two from 1024 up (to
    the sequence itself when tiny), so the reference compiles four shapes
    and not one per length."""
    b = min(1024, positions)
    while b < n:
        b *= 2
    return b


def reference_gaps(params, dims, sample, quant=None) -> dict:
    """Teacher-forced reference logits at every served position of the
    sample.  ``worst_gap``: how far the served token's logit lies below the
    reference's best, at its widest.  With ``quant`` the reference runs in
    that lower precision too, and the gap is read for the token IT puts
    first (the control)."""
    import jax.numpy as jnp

    from benchmarks.reference import transformer as ref

    exact = ref.Forward(dims.layers, dims.heads, dims.kv_heads)
    low = (ref.Forward(dims.layers, dims.heads, dims.kv_heads, quant=quant)
           if quant else None)
    worst, total, matches, checked = 0.0, 0.0, 0, 0
    for comp in sample:
        served = np.asarray(comp.tokens)
        seq = np.concatenate([np.asarray(comp.prompt), served[:-1]])
        first = len(comp.prompt) - 1
        padded = np.zeros(_bucket(len(seq), dims.positions), np.int32)
        padded[: len(seq)] = seq
        tokens = jnp.asarray(padded)
        logits = np.asarray(exact.logits(params, tokens, first))[
            : len(served)]
        if not np.isfinite(logits).all():
            raise RuntimeError("reference logits are not finite")
        chosen = served
        if low is not None:
            chosen = np.asarray(low.logits(params, tokens, first))[
                : len(served)].argmax(-1)
        gaps = logits.max(-1) - logits[np.arange(len(served)), chosen]
        worst = max(worst, float(gaps.max()))
        total += float(gaps.sum())
        matches += int((logits.argmax(-1) == chosen).sum())
        checked += len(served)
    return {"worst_gap": worst, "mean_gap": total / max(checked, 1),
            "tokens": checked,
            "exact_argmax_share": matches / max(checked, 1),
            "requests": len(sample)}


def _longest_gap(load: Load, start: float, end: float) -> float:
    """The longest gap between two polls that both lie in ``[start, end]``
    (``perf_counter`` times)."""
    ts = [load.t0 + p[0] for p in load.polls]
    return max((y - x for x, y in zip(ts, ts[1:]) if start <= x and y <= end),
               default=0.0)


def summarize(load: Load, loop, seconds: float) -> dict:
    """End-to-end numbers and the counters' differences over the window."""
    a, b = load.edges["start"], load.edges["end"]
    span = b["t"] - a["t"]
    attempted = [rid for rid in load.sent if load.in_window(rid)]
    lat, failed, late = [], 0, []
    for rid in attempted:
        due, sent = load.sent[rid]
        late.append(sent - due)
        comp, t_done = load.done.get(rid, (None, None))
        if comp is None or comp.reason not in ("length", "stop"):
            failed += 1
            lat.append(float("inf"))
        else:
            lat.append(t_done - due)
    gaps = loop.intertoken_samples[a["gap_samples"]: b["gap_samples"]]
    out = {
        "window_s": span, "attempted": len(attempted), "failed": failed,
        "late_mean_ms": 1e3 * statistics.fmean(late) if late else 0.0,
        "late_max_ms": 1e3 * max(late) if late else 0.0,
        "serve_tok_s": (b["served_tokens"] - a["served_tokens"]) / span,
        "gap_p90_ms": 1e3 * common.weighted_quantile(
            [g for g, _ in gaps], [n for _, n in gaps], 0.9),
        "gap_median_ms": 1e3 * common.weighted_quantile(
            [g for g, _ in gaps], [n for _, n in gaps], 0.5),
        "segments": b["segments"] - a["segments"],
        "prompt_tokens": b["prompt_tokens"] - a["prompt_tokens"],
        "queue_wait": tuple(y - x for x, y in zip(a["queue_wait"],
                                                  b["queue_wait"])),
        "host_wait": tuple(y - x for x, y in zip(a["host_wait"],
                                                 b["host_wait"])),
        "compiles_in_window": b["compiles"][0] - a["compiles"][0],
        "kv_blocks_peak": max(load.block_samples, default=0),
        "kv_blocks_total": loop.kv_num_blocks,
        # the longest time between two polls: where the host stood still
        # (PERF.md section 6, PR 38) it is seconds, and an iteration's else
        "poll_gap_max_s": _longest_gap(load, a["t"], b["t"]),
        "ramp_poll_gap_max_s": _longest_gap(load, load.t0, a["t"]),
    }
    if not load.closed_loop:
        lat.sort()
        # the 90th percentile of ALL requests due in the window; one that
        # failed counts as slower than any that finished
        p90 = lat[min(len(lat) - 1, int(np.ceil(0.9 * len(lat))) - 1)]
        limit = seconds + float(load.mix["drain_limit_s"]) + (
            TRACE_STOP_ALLOWANCE_S if load.traced else 0.0)
        out["latency_p90_s"] = min(p90, limit)
        out["latency_median_s"] = min(lat[len(lat) // 2], limit)
    return out


def run(cell: dict, args, t_start: float) -> dict:
    set_program_env(cell)
    import jax
    import jax.numpy as jnp

    from benchmarks.harness import tracing

    device = common.device_info(cell["chips"], args.tiny)
    common.start_caches()
    config, mix = cell["config"], cell["traffic"]
    dims = weights.ModelDims.from_config(config, args.tiny)
    opts = loop_options(config, args.tiny)
    scale = (dims.positions / config["n_positions"]) if args.tiny else 1.0

    params = weights.make_params(args.seed, dims, jnp.bfloat16)
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
    del loop, load.loop  # frees the pool before the reference runs
    t_ref = time.perf_counter()
    check = reference_gaps(params, dims, sample)
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
