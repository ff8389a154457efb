"""Speculative decoding: a draft model proposes, the target verifies.

Decode at long context is HBM-bandwidth-bound — each generated token
streams the whole KV cache once (measured 668-739 GB/s, 82-90% of the
v5e's spec, in `tpudist/ops/flash_decode.py`).  Speculative decoding
attacks exactly that bound: a cheap DRAFT model autoregressively
proposes ``num_draft`` tokens, then the TARGET model scores all of them
in ONE chunked forward (its cache is streamed once per *round*, not once
per token).  Accepted prefixes keep the target's exact output
distribution — greedy speculative decoding emits the target's own greedy
tokens (bit-identical whenever the verify-chunk and per-token decode
paths produce identical logits, as in f32; in bf16 a near-tie argmax can
flip across the two attention kernels), and sampled speculative decoding
emits tokens whose distribution is exactly the target's, by the standard
accept/resample argument (accept draft token x with probability
min(1, p(x)/q(x)); on rejection resample from norm(max(p-q, 0))).

TPU-shaped design decisions:

* The whole loop is ONE compiled ``lax.while_loop`` — fixed-shape draft
  scans, fixed-shape verify chunks, a fixed-capacity output buffer
  written with ``dynamic_update_slice``.  No per-token host round trips.
* Cache rollback is O(1): the flax cache masks by its scalar
  ``cache_index`` and every write lands at an explicit index, so
  rejecting draft tokens = resetting the index (stale slots are masked
  now and overwritten later).  No cache copies.
* Batched rollouts stay in LOCKSTEP: every row advances by the same
  ``m + 1`` tokens per round, where ``m`` is the BATCH-MIN accepted
  prefix length.  Rows that accepted more simply re-draft from the
  shorter prefix next round — per-row output distributions are
  unchanged (a prefix of an accepted prefix is accepted), and uniform
  advancement keeps the scalar cache index / static output offsets.
  Acceptance-rate throughput therefore degrades with batch; batch 1-8
  with a well-matched draft is the intended regime.

Reference scope note: the reference suite is training-only
(SURVEY.md §2 — no inference path anywhere); this module extends the
serving story that `tpudist/models/generate.py` opens.
"""

from __future__ import annotations

from typing import Any, Optional, Sequence

import jax
import jax.numpy as jnp
from jax import lax

from tpudist.models.generate import (
    _blank_cache,
    _filtered_logits,
    _is_stop,
    _make_select,
    _prefill,
    _set_cache_index,
    _stop_array,
    apply_cache_constraint,
    sequence_lengths,
)
from tpudist.models.transformer import TransformerConfig, TransformerLM


def _filtered_probs(logits: jnp.ndarray, temperature: float,
                    top_k: Optional[int], top_p: Optional[float]):
    """The (possibly filtered) categorical the rollout samples from, as
    PROBABILITIES — the quantity the accept/resample rule needs on both
    the draft and target sides.  Exactness requires this to be the SAME
    distribution ``_make_select`` samples, so the filtering pipeline is
    the shared :func:`tpudist.models.generate._filtered_logits`.
    ``temperature == 0`` returns the argmax one-hot (greedy is the
    zero-temperature limit of the same rule)."""
    if temperature == 0.0:
        logits = logits.astype(jnp.float32)
        return jax.nn.one_hot(
            jnp.argmax(logits, axis=-1), logits.shape[-1], dtype=jnp.float32)
    return jax.nn.softmax(
        _filtered_logits(logits, temperature, top_k, top_p), axis=-1)


def _accept_and_next(p: jnp.ndarray, q: jnp.ndarray, draft: jnp.ndarray,
                     key: jax.Array, active: Optional[jnp.ndarray] = None):
    """The speculative accept/advance rule for one round, batched.

    Args:
      p: ``[B, K+1, V]`` target probabilities — ``p[:, j]`` is the
        target's next-token distribution AFTER draft token j (``p[:, 0]``
        conditions on the round's input token only; ``p[:, K]`` is the
        bonus position after all K drafts).
      q: ``[B, K, V]`` draft probabilities — ``q[:, j]`` is the
        distribution draft token ``draft[:, j]`` was sampled from.
      draft: ``[B, K]`` proposed tokens.
      key: randomness for accept tests and residual resampling.
      active: optional ``[B]`` bool — FROZEN rows (finished lanes inside
        a fused serve segment) count as all-accept so they never drag the
        batch-min ``m`` down for live rows; their emit is discarded by
        the caller's lane masks.

    Returns ``(m, emit, accepted)``: the batch-min accepted prefix
    length ``m`` (scalar int32, 0..K), the ``[B]`` token to emit at
    position ``m + 1`` (accepted draft for rows whose acceptance reached
    past ``m``, a residual/bonus resample otherwise), and the ``[B]``
    per-row accepted counts (for telemetry).

    Output-distribution exactness is the standard argument, applied at
    position ``m + 1``: rows with ``accepted > m`` passed the accept
    test for draft ``m+1`` (keep it); rows with ``accepted == m``
    rejected there (resample from ``norm(max(p - q, 0))``); when
    ``m == K`` every row accepted everything and the emit is a pure
    sample of ``p[:, K]`` — which is the ``q = 0`` degenerate case of
    the same residual formula, so one code path serves both.
    """
    b, k = draft.shape
    u_key, r_key = jax.random.split(key)
    p_at_draft = jnp.take_along_axis(
        p[:, :k], draft[..., None], axis=-1)[..., 0]         # [B, K]
    q_at_draft = jnp.take_along_axis(
        q, draft[..., None], axis=-1)[..., 0]                # [B, K]
    u = jax.random.uniform(u_key, (b, k))
    # Greedy (one-hot p/q) reduces to: accept iff the draft token IS the
    # target argmax — p_at_draft is 1 or 0 and u < 1 almost surely.
    ok = u * jnp.maximum(q_at_draft, 1e-20) < p_at_draft     # [B, K]
    if active is not None:
        ok = ok | ~active[:, None]
    cum_ok = jnp.cumprod(ok.astype(jnp.int32), axis=1)
    accepted = jnp.sum(cum_ok, axis=1)                       # [B] in 0..K
    m = jnp.min(accepted)

    # q padded with a zero row at index K: the all-accepted bonus position
    # resamples from norm(max(p - 0, 0)) = p itself.
    q_pad = jnp.concatenate([q, jnp.zeros_like(q[:, :1])], axis=1)
    p_m = lax.dynamic_index_in_dim(p, m, axis=1, keepdims=False)
    q_m = lax.dynamic_index_in_dim(q_pad, m, axis=1, keepdims=False)
    residual = jnp.maximum(p_m - q_m, 0.0)
    # all-zero residual can only arise when p == q (any draft sample is
    # accepted with probability 1, so rejection there has probability 0);
    # guard anyway so the categorical never sees -inf everywhere
    residual = jnp.where(
        jnp.sum(residual, axis=-1, keepdims=True) > 0, residual, p_m)
    resampled = jax.random.categorical(
        r_key, jnp.log(jnp.maximum(residual, 1e-38)), axis=-1)

    # rows whose acceptance reached PAST m keep draft token m+1 (only
    # possible when m < K; at m == K the gather index clamps but the
    # take-branch is all-False)
    took_next = accepted > m
    next_draft = lax.dynamic_index_in_dim(
        draft, jnp.minimum(m, k - 1), axis=1, keepdims=False)
    emit = jnp.where(took_next, next_draft, resampled).astype(jnp.int32)
    return m, emit, accepted


def speculative_generate(
    target_cfg: TransformerConfig,
    target_params: Any,
    draft_cfg: TransformerConfig,
    draft_params: Any,
    prompt: jnp.ndarray,
    max_new_tokens: int,
    *,
    num_draft: int = 4,
    key: jax.Array | None = None,
    temperature: float = 0.0,
    top_k: Optional[int] = None,
    top_p: Optional[float] = None,
    decode_attention: str = "dense",
    draft_decode_attention: str = "dense",
    prefill_chunk: int | None = None,
    stop_tokens: Sequence[int] | None = None,
    pad_token: int = 0,
    return_stats: bool = False,
    decode_shard: Any = None,
    cache_constraint: Any = None,
    draft_cache_constraint: Any = None,
    auto_unstack: bool = True,
):
    """Generate ``max_new_tokens`` past ``prompt`` with draft/verify
    speculative decoding.

    Args:
      target_cfg / target_params: the model whose output distribution the
        result follows exactly.
      draft_cfg / draft_params: the proposal model.  Only
        ``vocab_size`` must match the target; it may be arbitrarily
        smaller/shallower and may use sliding-window attention
        (``attention_window``) so its own cache streaming stays cheap at
        long context.
      num_draft: draft tokens proposed per verify round (the classic K).
      temperature / top_k / top_p: sampling controls, applied to BOTH
        models' distributions (temperature 0 = greedy: output matches
        :func:`tpudist.models.generate.greedy_generate` of the target —
        bit-identical when both paths' logits agree bitwise; bf16
        near-tie argmaxes can flip between the chunked-verify and
        single-token attention kernels).
      decode_attention / draft_decode_attention: cached-attention
        implementation per model ("dense" or "flash"); the target only
        ever runs chunk forwards (prefill path), the draft runs
        single-token steps.
      prefill_chunk: bound prompt-prefill memory, as in ``_rollout``.
      stop_tokens / pad_token: EOS semantics as elsewhere — positions
        past a sequence's first stop token freeze to ``pad_token`` and
        per-sequence lengths are returned.
      return_stats: also return ``{"rounds", "draft_accepted"}``
        (scalars; ``draft_accepted`` counts ACCEPTED draft tokens summed
        over rounds AND batch rows — acceptance rate =
        draft_accepted / (rounds · K · batch) — guard the division:
        ``rounds`` is 0 when ``max_new_tokens == 1`` (the prefill's
        own next token satisfies the request before any draft/verify
        round runs), so compute it as
        ``draft_accepted / max(rounds, 1) / (K · batch)``.  Note the
        lockstep rollout only ADVANCES by the batch-min accepted prefix
        each round, so emitted tokens can trail acceptance for
        batch > 1; emitted tokens additionally include one verify token
        per round).
      auto_unstack: normalize a scanned-trained DRAFT to the unrolled
        layout (its single-token steps pay ~4× through the stacked
        cache); the target's layout is preserved either way — scanned
        targets keep their depth-independent compile size and verify
        chunks amortize the slicing.
      decode_shard / cache_constraint / draft_cache_constraint: the
        sharded-serving hooks (same contracts as in
        :mod:`tpudist.models.generate`): ``decode_shard`` routes the
        TARGET's attention kernels through per-shard ``shard_map``
        islands, and the constraints (leaf -> sharding or None) pin the
        two cache layouts under GSPMD.  :func:`tp_speculative_generate`
        wires them for the Megatron layout.

    Returns ``[B, prompt_len + max_new_tokens]`` tokens, with
    ``(tokens, lengths)`` when ``stop_tokens`` is given, and the stats
    dict appended when ``return_stats`` is set.
    """
    if auto_unstack:
        # Serve a scanned-trained DRAFT through the unrolled layout by
        # default (generate.serving_layout): the draft runs single-token
        # steps, where the stacked layout costs ~4×.  The TARGET's layout
        # is PRESERVED: it only ever runs chunk verifies, which amortize
        # the stacked-cache slicing, so a scanned target keeps its
        # depth-independent compile size at ~no step-time cost.  The
        # sharded entry points normalize BOTH unconditionally (their
        # sharding rules need per-layer names).
        from tpudist.models.generate import serving_layout

        draft_cfg, draft_params = serving_layout(draft_cfg, draft_params)
    if target_cfg.vocab_size != draft_cfg.vocab_size:
        raise ValueError(
            f"draft vocab {draft_cfg.vocab_size} != target vocab "
            f"{target_cfg.vocab_size}")
    if num_draft < 1:
        raise ValueError(f"num_draft must be >= 1, got {num_draft}")
    if max_new_tokens < 1:
        raise ValueError(
            f"max_new_tokens must be >= 1, got {max_new_tokens}")
    b, prompt_len = prompt.shape
    if prompt_len < 1:
        raise ValueError("prompt must hold at least one token")
    k = num_draft
    # the verify chunk writes up to K cache slots past the last emitted
    # token, so both caches need headroom beyond prompt+max_new
    need = prompt_len + max_new_tokens + k - 1
    for name, cfg in (("target", target_cfg), ("draft", draft_cfg)):
        if need > cfg.max_seq_len:
            raise ValueError(
                f"{name} max_seq_len {cfg.max_seq_len} < prompt_len + "
                f"max_new_tokens + num_draft - 1 = {need}")
    stop_arr = _stop_array(stop_tokens)
    select = _make_select(temperature, top_k, top_p)
    if key is None:
        key = jax.random.key(0)

    target = TransformerLM(target_cfg, decode=True,
                           decode_attention=decode_attention,
                           decode_shard=decode_shard)
    draft = TransformerLM(draft_cfg, decode=True,
                          decode_attention=draft_decode_attention)

    # PREFILL both models on the prompt (the shared serving split)
    t_cache, t_logits = _prefill(
        target, target_params,
        apply_cache_constraint(_blank_cache(target, b), cache_constraint),
        prompt, prefill_chunk)
    d_cache, _ = _prefill(
        draft, draft_params,
        apply_cache_constraint(_blank_cache(draft, b),
                               draft_cache_constraint),
        prompt, prefill_chunk)
    key, k0 = jax.random.split(key)
    first = select(t_logits[:, -1], k0).astype(jnp.int32)

    cap = max_new_tokens + k + 1
    out0 = jnp.zeros((b, cap), jnp.int32)
    out0 = lax.dynamic_update_slice(out0, first[:, None], (0, 0))

    def round_body(carry):
        t_cache, d_cache, x, emitted, out, key, rounds, acc_total = carry
        n_cache = prompt_len + emitted - 1  # tokens resident in caches
        key, dk, vk = jax.random.split(key, 3)

        # DRAFT: K single-token proposals with their distributions.  The
        # scan runs K+1 steps so the LAST iteration writes d_K into the
        # draft cache (needed for the all-accepted case); its sampled
        # output is discarded — one copy of the draft-step body.
        def chain(carry, inp):
            j, step_key = inp
            cache, tok = carry
            logits, mut = draft.apply(
                {"params": draft_params, "cache": cache}, tok[:, None],
                positions=jnp.full((b, 1), n_cache + j, jnp.int32),
                mutable=["cache"])
            q_probs = _filtered_probs(
                logits[:, -1], temperature, top_k, top_p)
            nxt = select(logits[:, -1], step_key).astype(jnp.int32)
            return (mut["cache"], nxt), (nxt, q_probs)

        d_keys = jax.random.split(dk, k + 1)
        (d_cache2, _), (drafts_t, q_t) = lax.scan(
            chain, (d_cache, x), (jnp.arange(k + 1), d_keys))
        drafts = drafts_t[:k].T                               # [B, K]
        q = jnp.moveaxis(q_t[:k], 0, 1)                       # [B, K, V]

        # VERIFY: one target forward over [x, d_1..d_K]
        verify = jnp.concatenate([x[:, None], drafts], axis=1)  # [B, K+1]
        positions = (n_cache + jnp.arange(k + 1))[None, :]
        t_logits, mut = target.apply(
            {"params": target_params, "cache": t_cache}, verify,
            positions=positions, mutable=["cache"])
        t_cache2 = mut["cache"]
        p = _filtered_probs(t_logits, temperature, top_k, top_p)

        m, emit, accepted = _accept_and_next(p, q, drafts, vk)

        # emit e_1..e_{m+1}: the accepted drafts then the verify token —
        # written as a full K+1 window (positions past m+1 are garbage,
        # overwritten next round or trimmed at the end)
        e_buf = jnp.concatenate([drafts, emit[:, None]], axis=1)
        e_buf = lax.dynamic_update_slice(e_buf, emit[:, None], (0, m))
        out = lax.dynamic_update_slice(out, e_buf, (0, emitted))

        new_len = n_cache + m + 1
        return (_set_cache_index(t_cache2, new_len),
                _set_cache_index(d_cache2, new_len),
                emit, emitted + m + 1, out, key,
                rounds + 1, acc_total + jnp.sum(accepted))

    def cond(carry):
        return carry[3] < max_new_tokens

    carry = (t_cache, d_cache, first, jnp.int32(1), out0, key,
             jnp.int32(0), jnp.int32(0))
    if max_new_tokens > 1:
        carry = lax.while_loop(cond, round_body, carry)
    _, _, _, _, out, _, rounds, acc_total = carry
    generated = out[:, :max_new_tokens]

    if stop_arr is not None:
        # EOS semantics as in _rollout: keep each row's first stop token,
        # freeze everything after it to pad_token
        hit = _is_stop(generated, stop_arr)
        after_stop = (jnp.cumsum(hit, axis=1) - hit) > 0
        generated = jnp.where(after_stop, jnp.int32(pad_token), generated)
    tokens = jnp.concatenate([prompt, generated], axis=1)

    result = (tokens,) if stop_arr is None else (
        tokens, sequence_lengths(generated, stop_arr, prompt_len))
    if return_stats:
        result = result + ({"rounds": rounds, "draft_accepted": acc_total},)
    return result[0] if len(result) == 1 else result


def _sharded_speculative(
    target_cfg, target_params, draft_cfg, draft_params, prompt,
    max_new_tokens, mesh, *, cache_spec, decode_shard, decode_attention,
    num_draft, key, temperature, top_k, top_p, prefill_chunk,
    stop_tokens, pad_token, return_stats):
    """Common tail of the sharded speculative entry points (tp / sp) —
    one copy of the serving-layout normalization, cache-constraint
    closures, key default, and kwarg plumbing, mirroring
    ``generate._sharded_generate`` so the layouts can never drift."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    # (cfgs, params) arrive NORMALIZED: every public sharded entry point
    # runs serving_layout on target AND draft before its shardings

    def cache_constraint(leaf):
        if leaf.ndim == 3:  # PACKED [B, S, Hkv*D] K/V buffers
            return NamedSharding(mesh, cache_spec)
        return NamedSharding(mesh, P())

    def draft_cache_constraint(leaf):
        return NamedSharding(mesh, P())

    def run(tp_params, dp_params, t):
        return speculative_generate(
            target_cfg, tp_params, draft_cfg, dp_params, t,
            max_new_tokens, num_draft=num_draft,
            key=key if key is not None else jax.random.key(0),
            temperature=temperature, top_k=top_k, top_p=top_p,
            decode_attention=decode_attention,
            draft_decode_attention="dense",
            prefill_chunk=prefill_chunk, stop_tokens=stop_tokens,
            pad_token=pad_token, return_stats=return_stats,
            decode_shard=decode_shard,
            cache_constraint=cache_constraint,
            draft_cache_constraint=draft_cache_constraint,
            auto_unstack=False)

    with mesh:
        return jax.jit(run)(target_params, draft_params, prompt)


def tp_speculative_generate(
    target_cfg: TransformerConfig,
    target_params: Any,
    draft_cfg: TransformerConfig,
    draft_params: Any,
    prompt: jnp.ndarray,
    max_new_tokens: int,
    mesh,
    axis: str = "model",
    rules=None,
    *,
    num_draft: int = 4,
    key: jax.Array | None = None,
    temperature: float = 0.0,
    top_k: Optional[int] = None,
    top_p: Optional[float] = None,
    decode_attention: str = "dense",
    prefill_chunk: int | None = 512,
    stop_tokens: Sequence[int] | None = None,
    pad_token: int = 0,
    return_stats: bool = False,
):
    """Tensor-parallel speculative decoding: the TARGET runs in the
    Megatron layout (weights + KV cache sharded over ``axis``, memory
    1/tp per chip — the :func:`tpudist.models.generate.tp_generate`
    layout) while the tiny DRAFT stays replicated, so every chip drafts
    locally and the verify rounds are the only sharded compute.  One
    GSPMD program; ``decode_attention="flash"`` routes the target's
    prefill/verify kernels through per-shard ``shard_map`` islands.

    Requires ``target_cfg.kv_heads % tp == 0``.  Same output contract
    as :func:`speculative_generate`.
    """
    from jax.sharding import PartitionSpec as P

    from tpudist.parallel.tensor_parallel import (
        shard_tree,
        spec_tree_from_rules,
        transformer_tp_rules,
    )

    tp = mesh.shape[axis]
    if target_cfg.kv_heads % tp:
        raise ValueError(
            f"target kv_heads {target_cfg.kv_heads} not divisible by "
            f"{axis!r} size {tp}")

    from tpudist.models.generate import serving_layout

    # normalize BEFORE the spec computation: the TP rules regex-match
    # per-layer kernel names, which a stacked checkpoint doesn't have
    target_cfg, target_params = serving_layout(target_cfg, target_params)
    draft_cfg, draft_params = serving_layout(draft_cfg, draft_params)
    specs = spec_tree_from_rules(
        target_params, rules or transformer_tp_rules(axis))
    return _sharded_speculative(
        target_cfg, shard_tree(target_params, mesh, specs), draft_cfg,
        draft_params, prompt, max_new_tokens, mesh,
        cache_spec=P(None, None, axis),
        decode_shard=((mesh, axis) if decode_attention == "flash"
                      else None),
        decode_attention=decode_attention, num_draft=num_draft, key=key,
        temperature=temperature, top_k=top_k, top_p=top_p,
        prefill_chunk=prefill_chunk, stop_tokens=stop_tokens,
        pad_token=pad_token, return_stats=return_stats)


def tp_sp_speculative_generate(
    target_cfg: TransformerConfig,
    target_params: Any,
    draft_cfg: TransformerConfig,
    draft_params: Any,
    prompt: jnp.ndarray,
    max_new_tokens: int,
    mesh,
    axis: str = "model",
    seq_axis: str = "seq",
    rules=None,
    *,
    num_draft: int = 4,
    key: jax.Array | None = None,
    temperature: float = 0.0,
    top_k: Optional[int] = None,
    top_p: Optional[float] = None,
    prefill_chunk: int | None = 512,
    stop_tokens: Sequence[int] | None = None,
    pad_token: int = 0,
    return_stats: bool = False,
):
    """2-D sharded speculative decoding — the full distributed-serving
    layout with a draft: the TARGET's weights are Megatron-sharded over
    ``axis`` and its KV cache sharded over heads (``axis``) AND sequence
    (``seq_axis``), so per-chip target cache memory is 1/(tp·sp) (the
    :func:`tpudist.models.generate.tp_sp_generate` layout); the tiny
    DRAFT stays replicated.  Verify chunks run on the GSPMD-partitioned
    dense path.  Same output contract as :func:`speculative_generate`.
    """
    from jax.sharding import PartitionSpec as P

    from tpudist.parallel.tensor_parallel import (
        shard_tree,
        spec_tree_from_rules,
        transformer_tp_rules,
    )

    tp, sp = mesh.shape[axis], mesh.shape[seq_axis]
    if target_cfg.kv_heads % tp:
        raise ValueError(
            f"target kv_heads {target_cfg.kv_heads} not divisible by "
            f"{axis!r} size {tp}")
    if target_cfg.max_seq_len % sp:
        raise ValueError(
            f"target max_seq_len {target_cfg.max_seq_len} not divisible "
            f"by {seq_axis!r} size {sp}")

    from tpudist.models.generate import serving_layout

    # normalize BEFORE the spec computation: the TP rules regex-match
    # per-layer kernel names, which a stacked checkpoint doesn't have
    target_cfg, target_params = serving_layout(target_cfg, target_params)
    draft_cfg, draft_params = serving_layout(draft_cfg, draft_params)
    specs = spec_tree_from_rules(
        target_params, rules or transformer_tp_rules(axis))
    return _sharded_speculative(
        target_cfg, shard_tree(target_params, mesh, specs), draft_cfg,
        draft_params, prompt, max_new_tokens, mesh,
        cache_spec=P(None, seq_axis, axis),
        decode_shard=None, decode_attention="dense",
        num_draft=num_draft, key=key, temperature=temperature,
        top_k=top_k, top_p=top_p, prefill_chunk=prefill_chunk,
        stop_tokens=stop_tokens, pad_token=pad_token,
        return_stats=return_stats)


def sp_speculative_generate(
    target_cfg: TransformerConfig,
    target_params: Any,
    draft_cfg: TransformerConfig,
    draft_params: Any,
    prompt: jnp.ndarray,
    max_new_tokens: int,
    mesh,
    axis: str = "seq",
    *,
    num_draft: int = 4,
    key: jax.Array | None = None,
    temperature: float = 0.0,
    top_k: Optional[int] = None,
    top_p: Optional[float] = None,
    prefill_chunk: int | None = 512,
    stop_tokens: Sequence[int] | None = None,
    pad_token: int = 0,
    return_stats: bool = False,
):
    """Sequence-sharded speculative decoding: the TARGET's KV cache is
    sharded over ``axis`` on its SEQUENCE dimension (per-chip target
    cache memory 1/n — the :func:`tpudist.models.generate.sp_generate`
    layout for contexts beyond one chip's HBM) with params replicated;
    the tiny DRAFT stays fully replicated.  The target's verify chunks
    run on the dense partitioned attention path (GSPMD turns them into
    per-shard partial attention + reductions; the sequence-sharded
    prefill never gathers the cache), so no ``decode_shard`` islands are
    needed.  Same output contract as :func:`speculative_generate`.
    """
    from jax.sharding import PartitionSpec as P

    from tpudist.models.generate import serving_layout

    target_cfg, target_params = serving_layout(target_cfg, target_params)
    draft_cfg, draft_params = serving_layout(draft_cfg, draft_params)
    sp = mesh.shape[axis]
    if target_cfg.max_seq_len % sp:
        raise ValueError(
            f"target max_seq_len {target_cfg.max_seq_len} not divisible "
            f"by {axis!r} size {sp}")

    return _sharded_speculative(
        target_cfg, target_params, draft_cfg, draft_params, prompt,
        max_new_tokens, mesh,
        cache_spec=P(None, axis, None),
        decode_shard=None, decode_attention="dense",
        num_draft=num_draft, key=key, temperature=temperature,
        top_k=top_k, top_p=top_p, prefill_chunk=prefill_chunk,
        stop_tokens=stop_tokens, pad_token=pad_token,
        return_stats=return_stats)


class AdaptiveDraftPolicy:
    """Acceptance-driven choice of ``num_draft`` (the round-3 verdict's
    adaptive-K ask): low acceptance makes long draft chunks WORSE than
    plain decode — the target still streams its cache/weights once per
    round, but the round only advances by the batch-min accepted prefix
    plus one — so K must shrink with measured acceptance, not be tuned
    for the perfect-draft ceiling.

    Model (the standard speculative-throughput algebra, batch-aware):
    with per-token acceptance probability ``a`` (i.i.d. across rows and
    positions), a batch-B round advances by::

        E[tokens/round] = 1 + sum_{j=1..K} a^(B*j)

    (each term is P(every row's accepted prefix reaches j) — the
    batch-min lockstep documented in this module's header), while the
    round costs ``K * c_draft + c_verify``.  :meth:`best_k` maximizes
    tokens/cost over the candidate ladder; ``a`` itself is recovered from
    observed per-row acceptance (``draft_accepted / (rounds * B)`` =
    ``sum_{j=1..K} a^j``) by bisection, because the reported accept rate
    is a K-truncated mean, not ``a``.

    The policy is HOST-side state adapting ACROSS compiled rollouts —
    inside one rollout K is a static shape (a lax.while_loop cannot
    reshape its draft scan), so adaptation happens at segment boundaries
    (:func:`adaptive_speculative_generate`), each ladder K reusing its
    own jit-cached executable.

    COSTS ARE MEASURED, NOT MODELED (round-4 verdict #2): the analytic
    ``K·r + 1`` round-cost shape mispredicts on real hardware — the
    verify chunk is cache-stream-bound (nearly K-independent) with a
    large fixed cost, so long chunks stay cheap even at modest
    acceptance.  Feed the policy realized per-round seconds via
    :meth:`observe_round_cost` (the adaptive driver does this
    automatically, skipping each K's first — compile-polluted — segment)
    and, optionally, the plain-decode per-token cost via
    :meth:`set_plain_cost`; :meth:`best_k` then maximizes MEASURED
    tokens/second over the ladder, interpolating a linear fit for
    not-yet-probed Ks, and — the break-even gate — returns ``0``
    ("use plain decode") whenever even the best ladder K's predicted
    rate loses to the plain rollout.  Until any cost is observed the
    analytic shape with ``draft_cost_ratio`` is the prior (and the gate
    stays off: unit-less analytic costs cannot be compared to plain
    seconds).

    Args:
      ladder: candidate K values (each gets its own compiled rollout).
      draft_cost_ratio: PRIOR c_draft / c_verify used only before any
        measured cost arrives.
      ema: smoothing for the acceptance estimate AND the cost estimates
        across updates.
    """

    def __init__(self, ladder: Sequence[int] = (4, 8, 16),
                 draft_cost_ratio: float = 0.1, ema: float = 0.5,
                 initial_acceptance: float = 0.8) -> None:
        if not ladder or any(k < 1 for k in ladder):
            raise ValueError(f"ladder must hold K >= 1, got {ladder}")
        if not 0 < draft_cost_ratio:
            raise ValueError("draft_cost_ratio must be > 0")
        self.ladder = tuple(sorted(ladder))
        self.r = float(draft_cost_ratio)
        self.ema = float(ema)
        self.acceptance = float(initial_acceptance)
        self.rounds_seen = 0
        self._round_cost: dict[int, float] = {}   # K -> seconds/round
        self._plain_tok_s: float | None = None    # seconds/token, plain

    # -- the algebra -------------------------------------------------------

    @staticmethod
    def _per_row_mean(a: float, k: int) -> float:
        """E[accepted prefix] / 1 for one row at per-token prob a."""
        return sum(a ** j for j in range(1, k + 1))

    @classmethod
    def infer_acceptance(cls, accept_rate: float, k: int) -> float:
        """Per-token acceptance probability ``a`` from the K-truncated
        mean accept fraction (``draft_accepted / (rounds*K*B)``)."""
        accept_rate = min(max(accept_rate, 0.0), 1.0)
        target = accept_rate * k
        lo, hi = 0.0, 1.0
        for _ in range(50):
            mid = (lo + hi) / 2
            if cls._per_row_mean(mid, k) < target:
                lo = mid
            else:
                hi = mid
        return (lo + hi) / 2

    def expected_tokens_per_round(self, a: float, k: int,
                                  batch: int) -> float:
        return 1.0 + sum(a ** (batch * j) for j in range(1, k + 1))

    # -- measured costs ----------------------------------------------------

    def observe_round_cost(self, k: int, seconds_per_round: float) -> None:
        """Fold one measured draft+verify round cost at chunk ``k`` into
        the cost model (EMA-smoothed per K)."""
        if seconds_per_round <= 0:
            return
        prev = self._round_cost.get(k)
        self._round_cost[k] = (
            seconds_per_round if prev is None
            else self.ema * seconds_per_round + (1 - self.ema) * prev)

    def set_plain_cost(self, seconds_per_token: float) -> None:
        """Arm the break-even gate with the measured plain-decode cost
        (EMA-smoothed once armed, like the per-K round costs — one noisy
        timing must not flip the gate wholesale)."""
        if seconds_per_token <= 0:
            return
        prev = self._plain_tok_s
        self._plain_tok_s = (
            float(seconds_per_token) if prev is None
            else self.ema * float(seconds_per_token) + (1 - self.ema) * prev)

    @property
    def calibrated(self) -> bool:
        return bool(self._round_cost)

    def round_cost(self, k: int) -> float:
        """Seconds (measured mode) or c_verify units (analytic prior)
        for one draft+verify round at chunk ``k``: exact where observed;
        a least-squares linear-in-K fit where ≥ 2 Ks were observed; the
        one observed point scaled by the analytic shape at 1; the pure
        analytic shape at 0."""
        if k in self._round_cost:
            return self._round_cost[k]
        pts = sorted(self._round_cost.items())
        if len(pts) >= 2:
            xs = [p[0] for p in pts]
            ys = [p[1] for p in pts]
            n = len(pts)
            mx = sum(xs) / n
            my = sum(ys) / n
            den = sum((x - mx) ** 2 for x in xs)
            slope = (sum((x - mx) * (y - my) for x, y in pts) / den
                     if den else 0.0)
            return max(my + slope * (k - mx), 1e-9)
        if len(pts) == 1:
            k0, c0 = pts[0]
            return c0 * (k * self.r + 1.0) / (k0 * self.r + 1.0)
        return k * self.r + 1.0

    def best_k(self, a: float | None = None, batch: int = 1,
               allow_plain: bool = True) -> int:
        """The ladder K maximizing expected tokens per unit cost at
        acceptance ``a`` (default: the policy's running estimate) —
        or ``0``, meaning "fall back to plain decode", when the break-
        even gate is armed (measured costs + plain cost known) and even
        the best K's predicted tokens/sec loses to the plain rollout.
        ``allow_plain=False`` bypasses the gate (the adaptive driver's
        periodic re-probe: a plain-locked policy would otherwise never
        see acceptance recover)."""
        a = self.acceptance if a is None else a

        def rate(k):
            return (self.expected_tokens_per_round(a, k, batch)
                    / self.round_cost(k))

        k_star = max(self.ladder, key=rate)
        if allow_plain and self.calibrated and self._plain_tok_s is not None:
            if rate(k_star) <= 1.0 / self._plain_tok_s:
                return 0
        return k_star

    # -- the feedback loop -------------------------------------------------

    @property
    def num_draft(self) -> int:
        return self.best_k()

    def update(self, stats: dict, batch: int, num_draft: int) -> None:
        """Fold one rollout's ``return_stats`` dict into the acceptance
        estimate (guarding the documented ``rounds == 0`` case)."""
        rounds = int(stats["rounds"])
        if rounds == 0:
            return
        rate = float(stats["draft_accepted"]) / (rounds * num_draft * batch)
        a = self.infer_acceptance(rate, num_draft)
        w = self.ema if self.rounds_seen else 1.0
        self.acceptance = w * a + (1.0 - w) * self.acceptance
        self.rounds_seen += rounds


def adaptive_speculative_generate(
    target_cfg: TransformerConfig,
    target_params: Any,
    draft_cfg: TransformerConfig,
    draft_params: Any,
    prompt: jnp.ndarray,
    max_new_tokens: int,
    policy: AdaptiveDraftPolicy,
    *,
    segment_tokens: int = 128,
    key: jax.Array | None = None,
    temperature: float = 0.0,
    top_k: Optional[int] = None,
    top_p: Optional[float] = None,
    decode_attention: str = "dense",
    draft_decode_attention: str = "dense",
    prefill_chunk: int | None = None,
    return_stats: bool = False,
    auto_unstack: bool = True,
    probe_plain: bool = True,
    reprobe_every: int = 4,
):
    """Speculative decoding with ``num_draft`` ADAPTED to measured
    acceptance, in segments.

    Each segment is one compiled :func:`speculative_generate` rollout at
    the policy's current K; its stats update the policy before the next
    segment.  Output distribution stays EXACT: a greedy (or sampled, with
    fresh per-segment keys) continuation of an exact prefix is an exact
    sample of the whole — K only changes the schedule, never the accept
    rule.  The cost is one compile per (segment boundary, ladder K) pair;
    a serving deployment amortizes the grid across requests (segment
    lengths and the ladder are static), and the common case converges to
    ONE K after the first segment.

    ``stop_tokens`` is deliberately unsupported here: per-row early stop
    interacts with segment boundaries (a stopped row would keep paying
    rollout segments); serve bounded-length requests through the
    continuous-batching loop instead.

    Segment wall times feed the policy's MEASURED cost model (each K's
    first segment is skipped — it contains the compile), so the K choice
    adapts to realized hardware costs, not the analytic prior.  With
    ``probe_plain`` (default), segment 2 runs the PLAIN rollout as a
    probe — its first call carries the compile, and a same-input re-run
    of the compiled executable supplies the clean timing that arms the
    policy's break-even gate — after which any segment where even the
    best K's predicted rate loses to plain decode runs the plain rollout
    instead (the "never worse than plain" guarantee costs ~two plain
    segments' device time once; pass ``probe_plain=False`` to skip the
    probe and arm the gate manually via ``policy.set_plain_cost``).
    Exactness is untouched either way: both continuations are exact
    samples.

    Returns tokens ``[B, prompt_len + max_new_tokens]`` (and, with
    ``return_stats``, a dict with per-segment ``ks`` (0 = plain
    fallback), acceptance estimates, and summed rounds/accepted)."""
    import time as _time

    if max_new_tokens < 1:
        raise ValueError(
            f"max_new_tokens must be >= 1, got {max_new_tokens}")
    if segment_tokens < 1:
        raise ValueError(
            f"segment_tokens must be >= 1, got {segment_tokens}")
    if key is None:
        key = jax.random.key(0)
    batch = prompt.shape[0]
    toks = prompt
    remaining = max_new_tokens
    seg_stats: dict = {"ks": [], "acceptance": [], "rounds": 0,
                       "draft_accepted": 0}
    # compile-pollution guard keyed by (K, n): jit executables are per
    # SEGMENT LENGTH too, so a shorter final segment would otherwise feed
    # its compile time into the measured cost model
    uses: dict[tuple[int, int], int] = {}
    seg_i = 0
    plain_streak = 0
    while remaining > 0:
        n = min(segment_tokens, remaining)
        k_seg = policy.best_k(batch=batch)
        if probe_plain and policy._plain_tok_s is None and seg_i == 1:
            k_seg = 0   # the plain probe segment (arms the gate below)
        elif (k_seg == 0 and reprobe_every > 0
                and plain_streak >= reprobe_every):
            # plain segments observe no acceptance, so a gate-locked
            # policy would never notice the draft recovering — re-probe
            # speculation periodically (one spec segment per
            # ``reprobe_every`` plain ones, bounded cost)
            k_seg = policy.best_k(batch=batch, allow_plain=False)
        plain_streak = plain_streak + 1 if k_seg == 0 else 0
        key, seg_key = jax.random.split(key)
        t0 = _time.perf_counter()
        if k_seg == 0:
            # break-even fallback: plain rollout for this segment
            from tpudist.models.generate import (
                greedy_generate, sample_generate,
            )

            def plain_call(t):
                if temperature > 0:
                    return sample_generate(
                        target_cfg, target_params, t, n, key=seg_key,
                        temperature=temperature, top_k=top_k, top_p=top_p,
                        decode_attention=decode_attention,
                        prefill_chunk=prefill_chunk,
                        auto_unstack=auto_unstack)
                return greedy_generate(
                    target_cfg, target_params, t, n,
                    decode_attention=decode_attention,
                    prefill_chunk=prefill_chunk,
                    auto_unstack=auto_unstack)

            toks_in = toks
            toks = plain_call(toks_in)
            jax.block_until_ready(toks)
            dt = _time.perf_counter() - t0
            if uses.get((0, n), 0) >= 1:   # first call holds the compile
                policy.set_plain_cost(dt / n)
            elif policy._plain_tok_s is None:
                # first plain call at this length carried the compile —
                # re-run the now-compiled executable on the SAME input
                # (output discarded) so the gate arms in ONE probe
                # segment regardless of segment-length truncation
                t1 = _time.perf_counter()
                jax.block_until_ready(plain_call(toks_in))
                policy.set_plain_cost(
                    (_time.perf_counter() - t1) / n)
            stats = {"rounds": 0, "draft_accepted": 0}
        else:
            toks, stats = speculative_generate(
                target_cfg, target_params, draft_cfg, draft_params, toks,
                n, num_draft=k_seg, key=seg_key, temperature=temperature,
                top_k=top_k, top_p=top_p,
                decode_attention=decode_attention,
                draft_decode_attention=draft_decode_attention,
                prefill_chunk=prefill_chunk, return_stats=True,
                auto_unstack=auto_unstack)
            jax.block_until_ready(toks)
            dt = _time.perf_counter() - t0
            rounds = int(stats["rounds"])
            if rounds > 0 and uses.get((k_seg, n), 0) >= 1:
                policy.observe_round_cost(k_seg, dt / rounds)
            policy.update(stats, batch, k_seg)
        uses[(k_seg, n)] = uses.get((k_seg, n), 0) + 1
        seg_stats["ks"].append(k_seg)
        seg_stats["acceptance"].append(policy.acceptance)
        seg_stats["rounds"] += int(stats["rounds"])
        seg_stats["draft_accepted"] += int(stats["draft_accepted"])
        remaining -= n
        seg_i += 1
    return (toks, seg_stats) if return_stats else toks
