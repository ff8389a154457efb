"""Autoregressive generation with a KV cache — the inference path.

The reference suite is training-only (SURVEY.md §2: no inference or serving
code anywhere); a complete framework needs a decode loop, so tpudist ships
one, TPU-idiomatic end to end: the whole autoregressive rollout is ONE
compiled program (``lax.scan`` over positions, fixed-shape cache buffers
updated with ``dynamic_update_slice``) — no per-token host round-trips, no
dynamic shapes.
"""

from __future__ import annotations

from typing import Any, Callable, Optional, Sequence

import jax
import jax.numpy as jnp
from jax import lax

from tpudist.models.transformer import (
    TransformerConfig,
    TransformerLM,
    unstack_layer_params,
)

# (logits [B, V], key) -> next token [B] int32
SelectFn = Callable[[jnp.ndarray, jax.Array], jnp.ndarray]


def serving_layout(cfg: TransformerConfig, params: Any,
                   ) -> tuple[TransformerConfig, Any]:
    """Normalize ``(cfg, params)`` to the UNROLLED layout for serving.

    ``scan_layers=True`` is the right layout for TRAINING (depth-
    independent compile size) but the wrong one for token-at-a-time
    decode: every step pays a per-layer dynamic-slice of the stacked
    cache (~4× slower at 8k context; pre-PR 1 capture, not
    re-measured), and the sharded entry
    points' Megatron rules match per-layer kernel names.  Every serving
    entry point calls this, so a checkpoint trained scanned serves at
    unrolled speed with no manual conversion step: stacked ``blocks``
    params are unstacked (a few slices, free next to any rollout) and the
    config is flipped.  Already-unrolled inputs pass through untouched.
    """
    import dataclasses

    try:
        stacked = "blocks" in params
    except TypeError:  # non-mapping param containers pass through
        stacked = False
    if stacked:
        params = unstack_layer_params(params, cfg.num_layers)
    if cfg.scan_layers:
        cfg = dataclasses.replace(cfg, scan_layers=False)
    return cfg, params


def _stop_array(stop_tokens: Sequence[int] | None) -> jnp.ndarray | None:
    if stop_tokens is None:
        return None
    toks = tuple(int(t) for t in stop_tokens)
    if not toks:
        raise ValueError("stop_tokens must be non-empty when given")
    return jnp.asarray(toks, jnp.int32)


def _is_stop(tokens: jnp.ndarray, stop_arr: jnp.ndarray) -> jnp.ndarray:
    """Membership mask against the stop set, over the last axis appended:
    ``[...]`` int tokens -> ``[...]`` bool."""
    return jnp.any(tokens[..., None] == stop_arr, axis=-1)


def sequence_lengths(generated: jnp.ndarray, stop_arr: jnp.ndarray,
                     prompt_len: int) -> jnp.ndarray:
    """Per-sequence total lengths: prompt + generated up to and INCLUDING
    the first stop token (or all of ``generated`` if none fired).  The
    position axis is the LAST one (works for [B, N] rollouts and
    [B, W, N] beam hypotheses alike)."""
    hit = _is_stop(generated, stop_arr)
    strictly_after = jnp.cumsum(hit, axis=-1) - hit  # stops before position
    return prompt_len + jnp.sum(strictly_after == 0, axis=-1)


def apply_cache_constraint(cache, constraint):
    """Pin a blank cache's layout for sharded decoding: ``constraint``
    maps leaf -> sharding (or None to leave the leaf alone).  The ONE
    copy of the idiom every sharded rollout (plain, speculative) uses."""
    if constraint is None:
        return cache
    return jax.tree.map(
        lambda x: (x if constraint(x) is None
                   else lax.with_sharding_constraint(x, constraint(x))),
        cache)


def _blank_cache(model, batch: int):
    """Fresh zeroed KV cache for ``model`` (cache_index 0, empty slots);
    shapes via ``eval_shape`` — no FLOPs, no throwaway params."""
    struct = jax.eval_shape(
        model.init, jax.random.key(0), jnp.zeros((batch, 1), jnp.int32),
        positions=jnp.zeros((batch, 1), jnp.int32))["cache"]
    return jax.tree.map(lambda s: jnp.zeros(s.shape, s.dtype), struct)


def _set_cache_index(cache: Any, idx: jnp.ndarray) -> Any:
    """Roll the cache to ``idx`` tokens: every ``cache_index`` leaf is
    reset (K/V buffers are left as-is — slots past the index are masked
    by every cached-attention path and overwritten on the next write at
    that position).  Index leaves are 0-D scalars in the unrolled layout
    and [num_layers] vectors under ``cfg.scan_layers``; K/V buffers are
    always >= 3-D (packed [B, S, Hkv·D]), so dimensionality separates
    them."""
    return jax.tree.map(
        lambda leaf: (jnp.full_like(leaf, idx) if leaf.ndim <= 1 else leaf),
        cache)


def _prefill(model, params, cache, prompt: jnp.ndarray,
             prefill_chunk: int | None):
    """Ingest the prompt into the cache in chunks of ``prefill_chunk``
    tokens (None = one shot), each attending causally over everything
    cached so far.  Returns ``(cache, last-chunk logits)`` — the serving
    split's prompt half, shared by the plain and speculative rollouts."""
    prompt_len = prompt.shape[1]
    chunk = prompt_len if prefill_chunk is None else min(
        prefill_chunk, prompt_len)
    for lo in range(0, prompt_len, chunk):
        piece = prompt[:, lo:lo + chunk]
        logits, mutated = model.apply(
            {"params": params, "cache": cache}, piece,
            positions=jnp.arange(lo, lo + piece.shape[1])[None, :],
            mutable=["cache"],
        )
        cache = mutated["cache"]
    return cache, logits


def _rollout(
    cfg: TransformerConfig,
    params: Any,
    prompt: jnp.ndarray,
    max_new_tokens: int,
    select: SelectFn,
    key: jax.Array,
    decode_attention: str = "dense",
    cache_constraint=None,
    prefill_chunk: int | None = None,
    stop_tokens: Sequence[int] | None = None,
    pad_token: int = 0,
    decode_shard=None,
) -> jnp.ndarray | tuple[jnp.ndarray, jnp.ndarray]:
    """Shared KV-cached decode loop; ``select`` picks the next token from
    each step's last-position logits (argmax for greedy, a sampler
    otherwise).  ``cache_constraint`` (leaf -> sharding or None) pins the
    cache layout for sharded decoding (:func:`tp_generate`).

    ``stop_tokens`` enables EOS semantics under static shapes: once a
    sequence emits a stop token, every later emitted position is frozen to
    ``pad_token`` (the model still runs — SIMD lanes can't retire early in
    a ``lax.scan`` — but its selections are masked, so the output is
    deterministic past EOS).  The return becomes ``(tokens, lengths)``
    with ``lengths[b]`` = prompt + generated up to and including the stop.

    ``prefill_chunk`` bounds prefill memory: the prompt is ingested in
    chunks of that many tokens (each attending causally over everything
    cached so far) — with the dense cache attention the peak logits
    buffer is [B, H, chunk, S] instead of [B, H, prompt, S], which is what
    keeps long-context prefill feasible off the flash path (e.g. under
    GSPMD sharding, where the Pallas kernel cannot partition)."""
    b, prompt_len = prompt.shape
    stop_arr = _stop_array(stop_tokens)  # validate before any device work
    if prompt_len < 1:
        raise ValueError("prompt must hold at least one token")
    if max_new_tokens < 1:
        raise ValueError(
            f"max_new_tokens must be >= 1, got {max_new_tokens}")
    total = prompt_len + max_new_tokens
    if total > cfg.max_seq_len:
        raise ValueError(
            f"prompt_len + max_new_tokens = {total} exceeds "
            f"max_seq_len {cfg.max_seq_len}")
    model = TransformerLM(cfg, decode=True, decode_attention=decode_attention,
                          decode_shard=decode_shard)
    cache = apply_cache_constraint(_blank_cache(model, b), cache_constraint)
    keys = jax.random.split(key, max_new_tokens)

    # PREFILL: the prompt through batched forwards (the serving split — at
    # long context this is the difference between streaming the cache once
    # per prompt TOKEN and once per prompt) ...
    cache, logits = _prefill(model, params, cache, prompt, prefill_chunk)
    first = select(logits[:, -1], keys[0]).astype(jnp.int32)
    done0 = (_is_stop(first, stop_arr) if stop_arr is not None
             else jnp.zeros((b,), bool))

    # ... then DECODE one token a step.
    def step(carry, inputs):
        t, step_key = inputs
        cache, prev, done = carry
        logits, mutated = model.apply(
            {"params": params, "cache": cache},
            prev[:, None],
            positions=jnp.full((b, 1), prompt_len + t - 1, jnp.int32),
            mutable=["cache"],
        )
        nxt = select(logits[:, -1], step_key).astype(jnp.int32)
        if stop_arr is not None:
            nxt = jnp.where(done, jnp.int32(pad_token), nxt)
            done = done | _is_stop(nxt, stop_arr)
        return (mutated["cache"], nxt, done), prev

    if max_new_tokens > 1:
        # emits the token it consumes, so `toks` is [g0 .. g_{n-2}] and the
        # final carry holds g_{n-1}
        (_, last, _), toks = lax.scan(
            step, (cache, first, done0),
            (jnp.arange(1, max_new_tokens), keys[1:]))
        generated = jnp.concatenate([toks.T, last[:, None]], axis=1)
    else:
        generated = first[:, None]
    out = jnp.concatenate([prompt, generated], axis=1)
    if stop_arr is None:
        return out
    return out, sequence_lengths(generated, stop_arr, prompt_len)


def greedy_generate(
    cfg: TransformerConfig,
    params: Any,
    prompt: jnp.ndarray,
    max_new_tokens: int,
    decode_attention: str = "dense",
    prefill_chunk: int | None = None,
    stop_tokens: Sequence[int] | None = None,
    pad_token: int = 0,
    auto_unstack: bool = True,
) -> jnp.ndarray | tuple[jnp.ndarray, jnp.ndarray]:
    """Greedy-decode ``max_new_tokens`` past ``prompt``.

    Args:
      cfg: the model configuration the ``params`` were trained with.
      params: TransformerLM parameter pytree (trained with any attention
        implementation — the cache path recomputes attention itself).
      prompt: ``[batch, prompt_len]`` int32 tokens, ``prompt_len >= 1``.
      max_new_tokens: tokens to append.
      stop_tokens: optional EOS set; positions past a sequence's first
        stop token freeze to ``pad_token`` and per-sequence lengths are
        returned alongside the tokens.
      auto_unstack: serve scanned-trained checkpoints through the
        unrolled layout (:func:`serving_layout` — ~4× faster decode).
        Pass False to decode through the stacked layout itself (the
        depth-independent-compile-size trade).

    Returns:
      ``[batch, prompt_len + max_new_tokens]`` int32: prompt + greedy
      continuation (plus ``[batch]`` lengths when ``stop_tokens`` is
      given).  ``prompt_len + max_new_tokens`` must fit in
      ``cfg.max_seq_len``.
    """
    if auto_unstack:
        cfg, params = serving_layout(cfg, params)
    return _rollout(
        cfg, params, prompt, max_new_tokens,
        lambda logits, _key: jnp.argmax(logits, axis=-1),
        jax.random.key(0), decode_attention=decode_attention,
        prefill_chunk=prefill_chunk, stop_tokens=stop_tokens,
        pad_token=pad_token)


def _sharded_generate(cfg, params, prompt, max_new_tokens, mesh, *,
                      cache_spec, decode_shard, decode_attention,
                      prefill_chunk, key, temperature, top_k, top_p,
                      stop_tokens, pad_token):
    """Common tail of the sharded decode entry points (tp / sp / tp_sp):
    a jitted :func:`_rollout` under the mesh, with the 4-D cache buffers
    pinned to ``cache_spec`` and scalars replicated, optionally routing
    the attention through per-shard kernel islands (``decode_shard``).
    Kept in ONE place so the key default, stop-token plumbing, and
    sampling selector can never drift between the three layouts."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    # (cfg, params) arrive NORMALIZED: every public sharded entry point
    # runs serving_layout before computing its shardings — sharded
    # serving requires the unrolled layout (the TP rules regex-match
    # per-layer kernel names and the 5-D stacked cache would escape the
    # cache-sharding constraint)

    def cache_constraint(leaf):
        if leaf.ndim == 3:  # PACKED [B, S, Hkv*D] K/V buffers
            return NamedSharding(mesh, cache_spec)
        return NamedSharding(mesh, P())  # cache_index scalars

    select = _make_select(temperature, top_k, top_p)

    def run(params, prompt):
        return _rollout(
            cfg, params, prompt, max_new_tokens, select,
            key if key is not None else jax.random.key(0),
            decode_attention=decode_attention,
            cache_constraint=cache_constraint,
            prefill_chunk=prefill_chunk, stop_tokens=stop_tokens,
            pad_token=pad_token, decode_shard=decode_shard)

    with mesh:
        return jax.jit(run)(params, prompt)


def tp_generate(
    cfg: TransformerConfig,
    params: Any,
    prompt: jnp.ndarray,
    max_new_tokens: int,
    mesh,
    axis: str = "model",
    rules=None,
    decode_attention: str = "dense",
    prefill_chunk: int | None = 512,
    key: jax.Array | None = None,
    temperature: float = 0.0,
    top_k: Optional[int] = None,
    top_p: Optional[float] = None,
    stop_tokens: Sequence[int] | None = None,
    pad_token: int = 0,
) -> jnp.ndarray | tuple[jnp.ndarray, jnp.ndarray]:
    """Tensor-parallel decode (greedy by default; ``temperature``/``top_k``
    / ``top_p`` + ``key`` select sampling): Megatron-layout params sharded over
    ``axis`` and the KV cache sharded over its HEADS dimension, so both
    weight and cache memory scale 1/tp per chip.  The whole rollout is one
    GSPMD program: qkv/up matmuls run column-sharded, the cache update and
    per-head attention stay head-local, and proj/down insert the pair
    all-reduces — no code change to the model, the shardings ARE the
    parallelism (same principle as
    :func:`tpudist.parallel.tensor_parallel.make_spmd_train_step`).

    Requires ``cfg.kv_heads % tp == 0`` (each shard owns whole KV heads).
    Returns the same tokens as :func:`greedy_generate`.
    """
    from jax.sharding import PartitionSpec as P

    from tpudist.parallel.tensor_parallel import (
        shard_tree,
        spec_tree_from_rules,
        transformer_tp_rules,
    )

    # normalize BEFORE the spec computation: the TP rules regex-match
    # per-layer kernel names, which a stacked checkpoint doesn't have
    cfg, params = serving_layout(cfg, params)
    tp = mesh.shape[axis]
    if cfg.kv_heads % tp:
        raise ValueError(
            f"kv_heads {cfg.kv_heads} not divisible by {axis!r} size {tp}")
    # decode_attention="flash" composes via shard_map: GSPMD cannot
    # partition a Pallas call, so the attention kernels run per-shard on
    # each shard's own (whole) KV-head groups inside a shard_map island —
    # the decode twin of the training-side ring_attention pattern
    # (VERDICT r2 #3; the old ValueError is gone).
    specs = spec_tree_from_rules(params, rules or transformer_tp_rules(axis))
    return _sharded_generate(
        cfg, shard_tree(params, mesh, specs), prompt, max_new_tokens, mesh,
        cache_spec=P(None, None, axis),
        decode_shard=((mesh, axis) if decode_attention == "flash"
                      else None),
        decode_attention=decode_attention, prefill_chunk=prefill_chunk,
        key=key, temperature=temperature, top_k=top_k, top_p=top_p,
        stop_tokens=stop_tokens, pad_token=pad_token)


def sp_generate(
    cfg: TransformerConfig,
    params: Any,
    prompt: jnp.ndarray,
    max_new_tokens: int,
    mesh,
    axis: str = "seq",
    prefill_chunk: int | None = 512,
    key: jax.Array | None = None,
    temperature: float = 0.0,
    top_k: Optional[int] = None,
    top_p: Optional[float] = None,
    stop_tokens: Sequence[int] | None = None,
    pad_token: int = 0,
    decode_attention: str = "dense",
) -> jnp.ndarray | tuple[jnp.ndarray, jnp.ndarray]:
    """Sequence-sharded-cache decode (greedy by default; the sampling
    controls mirror :func:`sample_generate`): the KV cache's SEQUENCE
    dimension is sharded over ``axis``, so per-chip cache memory is 1/n —
    the layout that serves contexts larger than one chip's HBM (the
    decode-side counterpart of ring attention).  Params stay replicated.

    ``decode_attention="dense"``: GSPMD partitions the cached attention
    into per-shard partial attention + softmax reductions over the
    sharded axis; measured HLO keeps the cache sharded end-to-end
    (all-reduces only — no cache all-gather, and the per-token
    ``dynamic_update_slice`` stays local to the owning shard).
    ``decode_attention="flash"``: per-token steps run
    :func:`tpudist.ops.flash_decode.sp_flash_decode` — each shard's
    flash kernel over its own cache slice, partial softmaxes merged by
    log-sum-exp (prefill stays on the dense partitioned path).  Returns
    the same tokens as :func:`greedy_generate`."""
    from jax.sharding import PartitionSpec as P

    cfg, params = serving_layout(cfg, params)
    if cfg.max_seq_len % mesh.shape[axis]:
        raise ValueError(
            f"max_seq_len {cfg.max_seq_len} not divisible by {axis!r} "
            f"size {mesh.shape[axis]}")
    return _sharded_generate(
        cfg, params, prompt, max_new_tokens, mesh,
        cache_spec=P(None, axis, None),
        decode_shard=((mesh, axis, "seq") if decode_attention == "flash"
                      else None),
        decode_attention=decode_attention, prefill_chunk=prefill_chunk,
        key=key, temperature=temperature, top_k=top_k, top_p=top_p,
        stop_tokens=stop_tokens, pad_token=pad_token)


def tp_sp_generate(
    cfg: TransformerConfig,
    params: Any,
    prompt: jnp.ndarray,
    max_new_tokens: int,
    mesh,
    axis: str = "model",
    seq_axis: str = "seq",
    rules=None,
    decode_attention: str = "flash",
    prefill_chunk: int | None = 512,
    key: jax.Array | None = None,
    temperature: float = 0.0,
    top_k: Optional[int] = None,
    top_p: Optional[float] = None,
    stop_tokens: Sequence[int] | None = None,
    pad_token: int = 0,
) -> jnp.ndarray | tuple[jnp.ndarray, jnp.ndarray]:
    """2-D sharded decode — the full distributed-serving layout: params
    Megatron-sharded over ``axis`` (weight memory 1/tp), the KV cache
    sharded over BOTH its head dim (``axis``) and its sequence dim
    (``seq_axis``), so per-chip cache memory is 1/(tp·sp) — contexts
    larger than any single chip's HBM with tensor-parallel weights.

    ``decode_attention="flash"`` (default): each shard runs the flash
    kernel on its own (head-group × cache-slice) block and the partial
    softmaxes merge by log-sum-exp over ``seq_axis`` only — heads need no
    collective.  ``"dense"`` leaves the partitioning to GSPMD.  Prefill
    runs on the dense partitioned path either way (queries must attend
    across every sequence shard).  Same tokens as
    :func:`greedy_generate`; sampling/stop controls as elsewhere."""
    from jax.sharding import PartitionSpec as P

    from tpudist.parallel.tensor_parallel import (
        shard_tree,
        spec_tree_from_rules,
        transformer_tp_rules,
    )

    cfg, params = serving_layout(cfg, params)  # TP rules need per-layer names
    tp, sp = mesh.shape[axis], mesh.shape[seq_axis]
    if cfg.kv_heads % tp:
        raise ValueError(
            f"kv_heads {cfg.kv_heads} not divisible by {axis!r} size {tp}")
    if cfg.max_seq_len % sp:
        raise ValueError(
            f"max_seq_len {cfg.max_seq_len} not divisible by "
            f"{seq_axis!r} size {sp}")
    specs = spec_tree_from_rules(params, rules or transformer_tp_rules(axis))
    return _sharded_generate(
        cfg, shard_tree(params, mesh, specs), prompt, max_new_tokens, mesh,
        cache_spec=P(None, seq_axis, axis),
        decode_shard=((mesh, (axis, seq_axis), "heads_seq")
                      if decode_attention == "flash" else None),
        decode_attention=decode_attention, prefill_chunk=prefill_chunk,
        key=key, temperature=temperature, top_k=top_k, top_p=top_p,
        stop_tokens=stop_tokens, pad_token=pad_token)


def top_k_filter(logits: jnp.ndarray, k: int) -> jnp.ndarray:
    """Mask all but the k highest logits to -inf (last axis)."""
    if k >= logits.shape[-1]:
        return logits
    kth = lax.top_k(logits, k)[0][..., -1:]
    return jnp.where(logits < kth, -jnp.inf, logits)


def top_p_filter(logits: jnp.ndarray, p: float) -> jnp.ndarray:
    """Mask to the nucleus: the smallest prefix of probability-sorted
    tokens whose cumulative probability reaches ``p`` (the argmax is
    always kept)."""
    if p >= 1.0:
        return logits
    sorted_logits = jnp.sort(logits, axis=-1)[..., ::-1]
    probs = jax.nn.softmax(sorted_logits, axis=-1)
    cum = jnp.cumsum(probs, axis=-1)
    # Keep every token whose PREDECESSORS sum below p; the cutoff is the
    # SMALLEST kept logit (min, not max — max would degenerate to greedy).
    keep_sorted = jnp.concatenate(
        [jnp.zeros_like(cum[..., :1]), cum[..., :-1]], -1) < p
    cutoff = jnp.min(
        jnp.where(keep_sorted, sorted_logits, jnp.inf), axis=-1,
        keepdims=True)
    return jnp.where(logits < cutoff, -jnp.inf, logits)


def sample_generate(
    cfg: TransformerConfig,
    params: Any,
    prompt: jnp.ndarray,
    max_new_tokens: int,
    key: jax.Array,
    *,
    temperature: float = 1.0,
    top_k: Optional[int] = None,
    top_p: Optional[float] = None,
    decode_attention: str = "dense",
    prefill_chunk: int | None = None,
    stop_tokens: Sequence[int] | None = None,
    pad_token: int = 0,
    auto_unstack: bool = True,
) -> jnp.ndarray | tuple[jnp.ndarray, jnp.ndarray]:
    """Sample ``max_new_tokens`` past ``prompt`` with the standard
    controls, all static-shape (one compiled rollout, like greedy):

    * ``temperature`` scales logits (0 → greedy argmax);
    * ``top_k`` keeps only the k highest-probability tokens;
    * ``top_p`` keeps the smallest nucleus whose cumulative probability
      reaches p (applied after top_k when both are set);
    * ``stop_tokens`` freezes a sequence at its first stop token (see
      :func:`greedy_generate`); returns ``(tokens, lengths)`` when set.

    ``auto_unstack``: as in :func:`greedy_generate` — scanned-trained
    checkpoints serve through the unrolled layout by default.
    """
    if auto_unstack:
        cfg, params = serving_layout(cfg, params)
    select = _make_select(temperature, top_k, top_p)
    return _rollout(cfg, params, prompt, max_new_tokens, select, key,
                    decode_attention=decode_attention,
                    prefill_chunk=prefill_chunk, stop_tokens=stop_tokens,
                    pad_token=pad_token)


def _filtered_logits(logits: jnp.ndarray, temperature: float,
                     top_k: Optional[int],
                     top_p: Optional[float]) -> jnp.ndarray:
    """The scale-then-top_k-then-top_p pipeline, in ONE place: both the
    rollout samplers (`_make_select`) and the speculative accept rule
    (`speculative._filtered_probs`) consume it — speculative sampling is
    distribution-exact only while the two see the SAME filtered
    categorical.  Requires ``temperature > 0``."""
    logits = logits.astype(jnp.float32) / temperature
    if top_k is not None:
        logits = top_k_filter(logits, top_k)
    if top_p is not None:
        logits = top_p_filter(logits, top_p)
    return logits


def _make_select(temperature: float, top_k: Optional[int],
                 top_p: Optional[float]) -> SelectFn:
    """Validated token-selection fn shared by the local and sharded
    rollouts (``temperature == 0`` reduces to greedy argmax)."""
    if temperature < 0:
        raise ValueError(f"temperature must be >= 0, got {temperature}")
    if top_k is not None and top_k < 1:
        raise ValueError(f"top_k must be >= 1, got {top_k}")
    if top_p is not None and not 0 < top_p <= 1:
        raise ValueError(f"top_p must be in (0, 1], got {top_p}")

    def select(logits: jnp.ndarray, step_key: jax.Array) -> jnp.ndarray:
        if temperature == 0.0:
            return jnp.argmax(logits.astype(jnp.float32), axis=-1)
        return jax.random.categorical(
            step_key, _filtered_logits(logits, temperature, top_k, top_p),
            axis=-1)

    return select
