#!/usr/bin/env python3
"""First-run smoke on the chip: ``ServeLoop`` and the composed train step at
the full width of the repo's 8-layer / 512-wide / 8k-context LM.

    python chip_smoke.py             one TPU chip: device, serve, train
    python chip_smoke.py --chips 4   one four-chip host: the dp=2 x tp=2
                                     composed step vs dp=1, and nothing else
    python chip_smoke.py --tiny      toy sizes on whatever platform is there,
                                     to rehearse paths and control flow; it
                                     never prints "ok": true

One process (a chip belongs to one process; nothing here starts a child).
Every phase prints one JSON line; the LAST line of stdout is
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}`` on
success, or ``{"ok": false, ...}`` with a non-zero exit code on any failure.
There is no CPU fallback: off a TPU the device phase fails at once.

Timings printed here are smoke timings (one cold run, compile included), not
benchmark numbers.
"""

from __future__ import annotations

import argparse
import dataclasses
import functools
import importlib.metadata
import json
import math
import os
import sys
import time
import traceback

import numpy as np

# serve reference gate: every served token's f32-reference logit must lie
# within this of the reference maximum.  bf16 kernels may flip a near-tie
# (worst gap seen on the v5e: 0.005); logits here are ~N(0,1) over the
# vocabulary, so a wrong page, mask or position picks a token ~4 below the
# maximum.
SERVE_LOGIT_TOL = 0.1
# |bf16 step loss - plain f32 reference loss| at the same params and batch
LOSS_TOL = 0.05
LEARNING_RATE = 1e-3


@dataclasses.dataclass(frozen=True)
class Sizes:
    vocab: int = 32000
    layers: int = 8
    embed: int = 512
    seq: int = 8192
    slots: int = 4
    steps_per_sync: int = 32
    prefill_chunk: int = 512
    kv_block: int = 128
    prompt_lens: tuple = (7680, 5120, 2560, 7680, 2560, 5120, 7680, 2560)
    new_tokens: int = 64
    train_batch: int = 4
    train_steps: int = 4
    mesh_batch: int = 2
    mesh_steps: int = 3


TINY = Sizes(vocab=256, layers=2, embed=128, seq=128, steps_per_sync=4,
             prefill_chunk=32, kv_block=16,
             prompt_lens=(96, 64, 32, 96, 32, 64, 96, 32),
             new_tokens=8, train_batch=2, train_steps=2)


def _emit(obj: dict) -> None:
    print(json.dumps(obj), flush=True)


# -- the plain reference: jax.numpy, f32, no kernel, no cache, no paging -----

def _ref_layer_norm(x, p):
    import jax.numpy as jnp

    mu = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), -1, keepdims=True)
    return (x - mu) / jnp.sqrt(var + 1e-6) * p["scale"] + p["bias"]


def _ref_block(x, p, *, heads: int, kv_heads: int):
    """One decoder block on ``x [S, E]`` (pre-LN, GQA, tanh-GELU MLP)."""
    import jax
    import jax.numpy as jnp

    mm = functools.partial(jnp.matmul, precision=jax.lax.Precision.HIGHEST)
    s, e = x.shape
    d = e // heads
    h = _ref_layer_norm(x, p["ln1"])
    if "qkv" in p["attn"]:
        qkv = mm(h, p["attn"]["qkv"]["kernel"]).reshape(s, 3, heads, d)
        q, k, v = qkv[:, 0], qkv[:, 1], qkv[:, 2]
    else:
        q = mm(h, p["attn"]["q"]["kernel"]).reshape(s, heads, d)
        kv = mm(h, p["attn"]["kv"]["kernel"]).reshape(s, 2, kv_heads, d)
        k = jnp.repeat(kv[:, 0], heads // kv_heads, axis=1)
        v = jnp.repeat(kv[:, 1], heads // kv_heads, axis=1)
    scores = mm(q.transpose(1, 0, 2), k.transpose(1, 2, 0)) / math.sqrt(d)
    causal = jnp.arange(s)[:, None] >= jnp.arange(s)[None, :]
    scores = jnp.where(causal, scores, -jnp.inf)
    scores = scores - jnp.max(scores, -1, keepdims=True)
    probs = jnp.exp(scores)
    probs = probs / jnp.sum(probs, -1, keepdims=True)
    out = mm(probs, v.transpose(1, 0, 2)).transpose(1, 0, 2).reshape(s, e)
    x = x + mm(out, p["attn"]["proj"]["kernel"])
    h = mm(_ref_layer_norm(x, p["ln2"]), p["mlp"]["up"]["kernel"])
    h = 0.5 * h * (1.0 + jnp.tanh(
        math.sqrt(2.0 / math.pi) * (h + 0.044715 * h ** 3)))
    return x + mm(h, p["mlp"]["down"]["kernel"])


class Reference:
    """f32 forward of the LM from its parameter tree alone."""

    def __init__(self, cfg, params) -> None:
        import jax
        import jax.numpy as jnp

        self.cfg = cfg
        self.p = jax.tree.map(lambda a: jnp.asarray(a, jnp.float32), params)
        self._block = jax.jit(functools.partial(
            _ref_block, heads=cfg.num_heads, kv_heads=cfg.kv_heads))
        mm = functools.partial(jnp.matmul,
                               precision=jax.lax.Precision.HIGHEST)

        def logits(hidden, ln_f, head):
            return mm(_ref_layer_norm(hidden, ln_f), head)

        def row_loss(hidden, ln_f, head, targets):
            lg = logits(hidden, ln_f, head)
            m = jnp.max(lg, -1)
            lse = m + jnp.log(jnp.sum(jnp.exp(lg - m[:, None]), -1))
            picked = jnp.take_along_axis(lg, targets[:, None], -1)[:, 0]
            return jnp.mean(lse - picked)

        self._logits = jax.jit(logits)
        self._row_loss = jax.jit(row_loss)

    def hidden(self, tokens):
        """``tokens [S]`` -> pre-``ln_f`` hidden states ``[S, E]``."""
        p = self.p
        x = (p["tok_embed"]["embedding"][tokens]
             + p["pos_embed"]["embedding"][: tokens.shape[0]])
        for i in range(self.cfg.num_layers):
            x = self._block(x, p[f"block{i}"])
        return x

    def logits(self, tokens, first: int):
        """Reference logits at positions ``first..`` of ``tokens``."""
        return self._logits(self.hidden(tokens)[first:], self.p["ln_f"],
                            self.p["lm_head"]["kernel"])

    def loss(self, x, y) -> float:
        """Mean next-token cross-entropy over the batch ``x, y [B, S]``."""
        rows = [self._row_loss(self.hidden(xr), self.p["ln_f"],
                               self.p["lm_head"]["kernel"], yr)
                for xr, yr in zip(x, y)]
        return float(np.mean([float(r) for r in rows]))


# -- bookkeeping -------------------------------------------------------------

def _compile_stats() -> tuple[float, float]:
    """(compiles, compile seconds) so far, from the process-wide listener
    ``enable_compilation_cache`` installs (cache hits do not count)."""
    from tpudist import obs

    summary = obs.histogram("xla/compile_seconds", unit="s").summary()
    return (float(obs.counter("xla/compiles", unit="compiles").value()),
            float(summary.get("sum") or 0.0))


def _peak_bytes():
    import jax

    stats = jax.devices()[0].memory_stats()  # None on the CPU backend
    return None if not stats else stats.get("peak_bytes_in_use")


def _kernel_calls(lowered) -> int:
    return lowered.as_text().count("tpu_custom_call")


def _model_cfg(sz: Sizes, heads: int, kv_heads: int):
    import jax.numpy as jnp

    from tpudist.models import TransformerConfig

    return TransformerConfig(
        vocab_size=sz.vocab, num_layers=sz.layers, num_heads=heads,
        num_kv_heads=kv_heads, embed_dim=sz.embed, max_seq_len=sz.seq,
        compute_dtype=jnp.bfloat16)


def _init_params(cfg, seed: int):
    """Random weights from the seed, as host arrays (train steps donate
    their state, so every consumer places its own copy)."""
    import jax
    import jax.numpy as jnp

    from tpudist.models import TransformerLM

    params = TransformerLM(cfg).init(
        jax.random.key(seed), jnp.ones((1, 8), jnp.int32))["params"]
    return jax.tree.map(np.asarray, params)


def _lm_batch(sz: Sizes, batch: int, seed: int):
    rng = np.random.default_rng(seed)
    x = rng.integers(0, sz.vocab, (batch, sz.seq)).astype(np.int32)
    return x, np.roll(x, -1, axis=1)


def _lm_loss_fn(model):
    from tpudist.ops.losses import cross_entropy

    def loss_fn(params, batch, rng):
        x, y = batch
        return cross_entropy(model.apply({"params": params}, x), y), {}

    return loss_fn


def _run_steps(step, state, batch, n: int):
    """``n`` optimizer steps on one repeated batch -> (state, losses,
    seconds per step); each step is timed to its loss on the host."""
    import jax

    losses, seconds = [], []
    for _ in range(n):
        t0 = time.perf_counter()
        state, metrics = step(state, *batch)
        losses.append(float(jax.block_until_ready(metrics["loss"])))
        seconds.append(time.perf_counter() - t0)
    if not all(math.isfinite(v) for v in losses):
        raise RuntimeError(f"non-finite loss: {losses}")
    return state, losses, seconds


# -- phases ------------------------------------------------------------------

def phase_device(ctx: dict) -> dict:
    import jax
    import jaxlib

    from tpudist.obs.xla import PEAK_TFLOPS
    from tpudist.runtime.cache import enable_compilation_cache

    devices = jax.devices()
    d0 = devices[0]
    ctx["device"] = {"platform": d0.platform, "kind": d0.device_kind,
                     "count": len(devices)}
    cache_dir = enable_compilation_cache()
    try:
        libtpu = importlib.metadata.version("libtpu")
    except importlib.metadata.PackageNotFoundError:
        libtpu = None
    info = {
        **ctx["device"],
        "jax": jax.__version__, "jaxlib": jaxlib.__version__,
        "libtpu": libtpu,
        "cache_dir": cache_dir,
        "cache_dir_from_env": bool(
            os.environ.get("JAX_COMPILATION_CACHE_DIR")),
        "cache_entries_at_start": len(os.listdir(cache_dir))
        if os.path.isdir(cache_dir) else 0,
    }
    if d0.platform != "tpu":
        raise RuntimeError(f"platform is {d0.platform!r}, not 'tpu': {info}")
    if d0.device_kind not in PEAK_TFLOPS:
        raise RuntimeError(f"device kind {d0.device_kind!r} is not in the "
                           f"peak table {sorted(PEAK_TFLOPS)}")
    if len(devices) < ctx["chips"]:
        raise RuntimeError(
            f"{ctx['chips']} chips needed, {len(devices)} found")
    return info


def phase_serve(ctx: dict) -> dict:
    import jax
    import jax.numpy as jnp

    from tpudist.models import Request, ServeLoop

    sz: Sizes = ctx["sizes"]
    on_tpu = ctx["device"]["platform"] == "tpu"
    cfg = _model_cfg(sz, heads=4, kv_heads=1)
    params = _init_params(cfg, ctx["seed"])
    rng = np.random.default_rng(ctx["seed"])
    requests = [
        Request(rng.integers(0, sz.vocab, (n,)).astype(np.int32),
                sz.new_tokens, rid=i)
        for i, n in enumerate(sz.prompt_lens)]

    loop = ServeLoop(cfg, jax.device_put(params), num_slots=sz.slots,
                     steps_per_sync=sz.steps_per_sync,
                     decode_attention="flash",
                     prefill_chunk=sz.prefill_chunk, cache_layout="paged",
                     kv_block_size=sz.kv_block)
    t0 = time.perf_counter()
    done = {c.rid: c for c in loop.run(requests)}
    wall = time.perf_counter() - t0

    for req in requests:
        comp = done.get(req.rid)
        if comp is None or comp.reason != "length" \
                or len(comp.tokens) != sz.new_tokens:
            raise RuntimeError(
                f"request {req.rid} did not finish with its budget: "
                f"{None if comp is None else (comp.reason, len(comp.tokens))}")
    if loop.pool.used_blocks != 0:
        raise RuntimeError(
            f"{loop.pool.used_blocks} KV blocks still held after the run")

    # the kernels must be IN the programs that ran: a quiet dense or
    # interpret path lowers to no tpu_custom_call
    seg = _kernel_calls(loop._segment.lower(
        loop.params, loop.cache, loop._tok, loop._active, loop._remaining,
        loop._first, loop._key, jnp.int32(sz.steps_per_sync),
        jnp.bool_(False)))
    pre = _kernel_calls(loop._prefill_chunk.lower(
        loop.params, loop._blank1,
        jnp.zeros((1, sz.prefill_chunk), jnp.int32), jnp.int32(0),
        chunk=sz.prefill_chunk))
    if on_tpu and min(seg, pre) < cfg.num_layers:
        raise RuntimeError(
            f"Pallas kernels missing: {seg} tpu_custom_call in the "
            f"segment, {pre} in the prefill chunk, {cfg.num_layers} layers")

    # teacher-forced plain reference over prompt + served tokens, for the
    # shortest and the longest request
    ref = Reference(cfg, params)
    by_len = sorted(requests, key=lambda r: len(r.prompt))
    worst_gap, matches, checked = 0.0, 0, 0
    for req in (by_len[0], by_len[-1]):
        served = np.asarray(done[req.rid].tokens)
        tokens = jnp.asarray(np.concatenate([req.prompt, served[:-1]]))
        logits = np.asarray(ref.logits(tokens, len(req.prompt) - 1))
        if logits.shape != (sz.new_tokens, sz.vocab) \
                or not np.isfinite(logits).all():
            raise RuntimeError(f"reference logits {logits.shape} not finite")
        gaps = logits.max(-1) - logits[np.arange(len(served)), served]
        worst_gap = max(worst_gap, float(gaps.max()))
        matches += int((logits.argmax(-1) == served).sum())
        checked += len(served)
    if worst_gap > SERVE_LOGIT_TOL:
        raise RuntimeError(
            f"a served token's reference logit is {worst_gap:.3f} below "
            f"the reference maximum (tolerance {SERVE_LOGIT_TOL})")
    return {
        "requests": len(requests), "tokens": len(requests) * sz.new_tokens,
        "prompt_tokens": int(sum(sz.prompt_lens)),
        "wall_s": round(wall, 3),
        "kernel_calls_segment": seg, "kernel_calls_prefill_chunk": pre,
        "reference_tokens_checked": checked,
        "exact_argmax_share": round(matches / checked, 4),
        "worst_logit_gap": round(worst_gap, 4),
        "logit_tolerance": SERVE_LOGIT_TOL,
    }


def phase_train(ctx: dict) -> dict:
    import optax

    from tpudist import obs
    from tpudist.models import TransformerLM
    from tpudist.obs.xla import cost_flops, note_step
    from tpudist.ops.flash_attention import flash_attention_fn
    from tpudist.parallel import (
        MeshSpec, make_composed_state, make_composed_train_step,
        shard_composed_batch)

    sz: Sizes = ctx["sizes"]
    on_tpu = ctx["device"]["platform"] == "tpu"
    cfg = _model_cfg(sz, heads=4, kv_heads=1)
    params = _init_params(cfg, ctx["seed"])
    x, y = _lm_batch(sz, sz.train_batch, ctx["seed"])
    ref_loss = Reference(cfg, params).loss(x, y)

    model = TransformerLM(cfg, attention_fn=flash_attention_fn())
    spec = MeshSpec.parse("dp=1")
    mesh = spec.build()
    state, _ = make_composed_state(
        model.apply, params, optax.adamw(LEARNING_RATE), spec, mesh,
        rng=ctx["seed"])
    step = make_composed_train_step(spec, mesh, _lm_loss_fn(model),
                                    params=params)
    batch = shard_composed_batch((x, y), mesh, spec)

    lowered = step.lower(state, *batch)
    kernels = _kernel_calls(lowered)
    flops = cost_flops(lowered)
    if on_tpu and kernels < 3 * cfg.num_layers:
        raise RuntimeError(
            f"{kernels} tpu_custom_call in the train step; forward, dQ and "
            f"dK/dV kernels of {cfg.num_layers} layers expected")
    if on_tpu and flops is None:
        raise RuntimeError("step.lower(...) reported no FLOPs")

    state, losses, seconds = _run_steps(step, state, batch, sz.train_steps)
    if abs(losses[0] - ref_loss) > LOSS_TOL:
        raise RuntimeError(
            f"first-step loss {losses[0]:.4f} vs plain f32 reference "
            f"{ref_loss:.4f} (tolerance {LOSS_TOL})")
    if not losses[-1] < losses[0]:
        raise RuntimeError(f"loss did not fall on a repeated batch: {losses}")
    steady = float(np.median(seconds[1:]))
    note_step(steady, flops)
    return {
        "mesh": "dp=1", "batch": sz.train_batch, "seq": sz.seq,
        "steps": len(losses), "losses": [round(v, 4) for v in losses],
        "reference_loss": round(ref_loss, 4), "loss_tolerance": LOSS_TOL,
        "step_s": [round(v, 3) for v in seconds],
        "median_later_step_s": round(steady, 4),
        "kernel_calls": kernels, "xla_cost_flops": flops,
        # XLA's count: the Pallas kernels' FLOPs are not in it
        "xla/mfu": obs.gauge("xla/mfu").value(),
    }


def phase_mesh(ctx: dict) -> dict:
    """dp=2 x tp=2 on four chips vs dp=1 on one, same global batch and
    seed.  ``transformer_tp_rules`` needs ``num_kv_heads % tp == 0``, hence
    the 8q/2kv layout.  Attention is ``sdpa`` under remat: the GSPMD
    program cannot partition a Pallas call ("Mosaic kernels cannot be
    automatically partitioned"), and giving the kernel a sharding rule is
    ROADMAP R4's work."""
    import jax
    import optax

    from tpudist.models import TransformerLM, sdpa
    from tpudist.parallel import (
        MeshSpec, make_composed_state, make_composed_train_step,
        shard_composed_batch)
    from tpudist.parallel.tensor_parallel import transformer_tp_rules

    sz: Sizes = ctx["sizes"]
    cfg = _model_cfg(sz, heads=8, kv_heads=2)
    params = _init_params(cfg, ctx["seed"])
    x, y = _lm_batch(sz, sz.mesh_batch, ctx["seed"])
    model = TransformerLM(cfg, attention_fn=sdpa, remat=True)
    loss_fn = _lm_loss_fn(model)
    total_bytes = sum(a.nbytes for a in jax.tree.leaves(params))

    out: dict = {"batch": sz.mesh_batch, "seq": sz.seq, "attention": "sdpa",
                 "param_bytes": total_bytes}
    for name, spec in (
            ("dp=1", MeshSpec.parse("dp=1")),
            ("dp=2,tp=2", MeshSpec(dp=2, tp=2,
                                   rules=tuple(transformer_tp_rules("tp"))))):
        mesh = spec.build()
        state, _ = make_composed_state(
            model.apply, params, optax.adamw(LEARNING_RATE), spec, mesh,
            rng=ctx["seed"])
        per_device = {d.id: 0 for d in mesh.devices.flat}
        for leaf in jax.tree.leaves(state.params):
            for shard in leaf.addressable_shards:
                per_device[shard.device.id] += shard.data.nbytes
        step = make_composed_train_step(spec, mesh, loss_fn, params=params)
        batch = shard_composed_batch((x, y), mesh, spec)
        c0 = _compile_stats()
        state, losses, seconds = _run_steps(step, state, batch,
                                            sz.mesh_steps)
        c1 = _compile_stats()
        out[name] = {
            "devices": [d.id for d in mesh.devices.flat],
            "param_bytes_per_device": per_device,
            "losses": [round(v, 4) for v in losses],
            "step_s": [round(v, 3) for v in seconds],
            "median_later_step_s": round(float(np.median(seconds[1:])), 4),
            "compiles": c1[0] - c0[0],
            "compile_s": round(c1[1] - c0[1], 2),
        }
        if spec.n_devices > 1:
            hlo = step.lower(state, *batch).compile().as_text()
            out[name]["collectives"] = {
                op: hlo.count(f" {op}(") + hlo.count(f" {op}-start(")
                for op in ("all-reduce", "all-gather", "reduce-scatter",
                           "all-to-all", "collective-permute")}
            if len(per_device) != 4 or not all(
                    0 < b < total_bytes for b in per_device.values()):
                raise RuntimeError(
                    f"params are not spread over four devices: "
                    f"{per_device} of {total_bytes} bytes")
            if not out[name]["collectives"]["all-reduce"]:
                raise RuntimeError("no all-reduce in the dp=2,tp=2 program")
        del state, step, batch

    diffs = [abs(a - b) for a, b in zip(out["dp=1"]["losses"],
                                        out["dp=2,tp=2"]["losses"])]
    out["max_loss_diff"] = round(max(diffs), 5)
    out["loss_tolerance"] = LOSS_TOL
    if max(diffs) > LOSS_TOL:
        raise RuntimeError(
            f"dp=2,tp=2 losses differ from dp=1 by {max(diffs):.4f} "
            f"(tolerance {LOSS_TOL}): {out}")
    return out


def phase_pipeline(ctx: dict) -> dict:
    """One optimizer step of the same 8q/2kv LM under ``dp=2, pp=2``
    through the interleaved 1F1B schedule (one layer per chunk), first
    loss against the plain reference.  Under shard_map the Pallas kernel
    needs no partitioning rule, so attention is the flash kernel here.
    (``pp=2, tp=2`` has no 1F1B path for this model: ``tp > 1`` under
    ``pp`` selects the stacked fill-drain schedule over hand-written
    tensor-parallel blocks.)"""
    import flax.linen as nn
    import jax
    import jax.numpy as jnp
    import optax

    from tpudist.models.transformer import DecoderBlock
    from tpudist.ops.flash_attention import flash_attention_fn
    from tpudist.ops.losses import cross_entropy
    from tpudist.parallel import (
        MeshSpec, interleave_params, make_composed_train_step)
    from tpudist.train.state import TrainState

    sz: Sizes = ctx["sizes"]
    cfg = _model_cfg(sz, heads=8, kv_heads=2)
    flat = _init_params(cfg, ctx["seed"])
    stages, micro = 2, 2
    virtual = cfg.num_layers // stages
    x, y = _lm_batch(sz, 2 * micro, ctx["seed"])
    ref_loss = Reference(cfg, flat).loss(x, y)

    stacked = jax.tree.map(
        lambda *xs: jnp.stack(xs),
        *[flat[f"block{i}"] for i in range(cfg.num_layers)])
    params = {
        "stages": interleave_params(stacked, stages, virtual),
        "extra": {k: v for k, v in flat.items()
                  if not k.startswith("block")}}
    block = DecoderBlock(cfg, attention_fn=flash_attention_fn())
    ln_f = nn.LayerNorm(dtype=cfg.compute_dtype, name="ln_f")

    def block_fn(p, a):
        return block.apply({"params": p}, a)

    def embed_fn(ex, x_mb):
        pos = jnp.arange(x_mb.shape[1])
        a = (jnp.take(ex["tok_embed"]["embedding"], x_mb, axis=0)
             + jnp.take(ex["pos_embed"]["embedding"], pos, axis=0)[None])
        return a.astype(cfg.compute_dtype)

    def head_loss_fn(ex, out, y_mb):
        h = ln_f.apply({"params": ex["ln_f"]}, out)
        logits = h @ ex["lm_head"]["kernel"].astype(cfg.compute_dtype)
        return cross_entropy(logits.astype(jnp.float32), y_mb)

    spec = MeshSpec(dp=2, pp=stages, num_microbatches=micro,
                    virtual_stages=virtual)
    mesh = spec.build()
    state = TrainState.create(None, params, optax.adamw(LEARNING_RATE))
    step = make_composed_train_step(
        spec, mesh, block_fn=block_fn, embed_fn=embed_fn,
        head_loss_fn=head_loss_fn, state_example=state)
    state, losses, seconds = _run_steps(step, state, (x, y), 1)
    if abs(losses[0] - ref_loss) > LOSS_TOL:
        raise RuntimeError(
            f"1F1B first loss {losses[0]:.4f} vs plain f32 reference "
            f"{ref_loss:.4f} (tolerance {LOSS_TOL})")
    return {
        "mesh": "dp=2,pp=2", "schedule": "1f1b", "virtual_stages": virtual,
        "microbatches": micro, "batch": int(x.shape[0]), "seq": sz.seq,
        "attention": "flash", "loss": round(losses[0], 4),
        "reference_loss": round(ref_loss, 4), "loss_tolerance": LOSS_TOL,
        "bubble_fraction": round(step.bubble_fraction, 4),
        "step_s": [round(v, 3) for v in seconds],
    }


def _run_phase(name: str, fn, ctx: dict) -> bool:
    """Run one phase and print its JSON line; False on failure (the
    caller stops and the process exits non-zero)."""
    t0 = time.perf_counter()
    line: dict = {"phase": name}
    try:
        c0 = _compile_stats()
        line.update(fn(ctx))
        c1 = _compile_stats()
        line.update(compiles=c1[0] - c0[0],
                    compile_s=round(c1[1] - c0[1], 2),
                    peak_bytes_in_use=_peak_bytes())
        line["ok"] = True
    except Exception as e:  # noqa: BLE001 - reported, and the run fails
        traceback.print_exc()
        line.update(ok=False, error=f"{type(e).__name__}: {e}"[:2000])
    line["phase_wall_s"] = round(time.perf_counter() - t0, 3)
    _emit(line)
    return line["ok"]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--tiny", action="store_true",
                    help="toy sizes, any platform, never ok: rehearsal only")
    args = ap.parse_args(argv)
    ctx = {"chips": args.chips, "seed": args.seed,
           "sizes": TINY if args.tiny else Sizes(), "device": None}
    phases = ([("mesh", phase_mesh), ("pipeline", phase_pipeline)]
              if args.chips == 4
              else [("serve", phase_serve), ("train", phase_train)])

    failed = []
    if not _run_phase("device", phase_device, ctx):
        failed.append("device")
    # --tiny rehearses the other phases wherever it is; the real run stops
    # at the first failure
    if ctx["device"] is not None and (not failed or args.tiny):
        for name, fn in phases:
            if not _run_phase(name, fn, ctx):
                failed.append(name)
                break
    if failed or args.tiny:
        _emit({"ok": False, "device": ctx["device"], "failed": failed,
               "tiny": args.tiny})
        return 1
    _emit({"ok": True, "device": ctx["device"]})
    return 0


if __name__ == "__main__":
    sys.exit(main())
