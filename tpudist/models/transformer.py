"""Decoder-only transformer LM — the long-context workload of the zoo.

The reference suite has no attention model anywhere (SURVEY.md §2.3: the
sequence-parallel family is absent; largest model is ResNet50,
`model_parallel_ResNet50.py:43-139`).  tpudist adds one deliberately: it is
the workload that exercises tensor parallelism
(:mod:`tpudist.parallel.tensor_parallel`), sequence/context parallelism and
ring attention (:mod:`tpudist.parallel.ring_attention`), and the pallas
flash-attention kernel (:mod:`tpudist.ops.flash_attention`) — the
capabilities a modern user of the reference's *mechanisms* (RPC model
parallelism, DDP) actually scales with on TPU.

Design notes (TPU-first):

* every projection width is a multiple of 128 (MXU lane width); compute in
  bfloat16 with float32 params via ``compute_dtype``;
* attention is **pluggable**: any ``AttentionFn`` with the
  ``(q, k, v, *, causal) -> out`` contract on ``[batch, seq, heads, hd]``
  arrays can be swapped in — the default is plain softmax attention, ring
  attention and the pallas kernel provide drop-in replacements;
* static shapes everywhere; the layer stack is a Python loop (unrolled at
  trace time), causality is a static flag.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any, Callable, Optional

import flax.linen as nn
import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name

from tpudist import obs
from tpudist.obs.spans import routine

# (q, k, v, *, causal, window=None) on [batch, seq, heads, head_dim]
# arrays -> out shaped like q.  ``window`` is the sliding-window width
# (None = full causal attention); implementations may reject it.
AttentionFn = Callable[..., jnp.ndarray]


def _masked_attend(
    q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
    mask: Optional[jnp.ndarray], scale: float | None = None,
) -> jnp.ndarray:
    """The one copy of the attention numerics every path shares: scaled
    f32-accumulated QKᵀ, finfo-min mask fill, f32 softmax, cast back.
    ``mask`` is boolean, broadcastable to [B, H, Sq, Sk] (True = attend)."""
    dtype = q.dtype
    if scale is None:
        scale = q.shape[-1] ** -0.5
    logits = jnp.einsum(
        "bqhd,bkhd->bhqk", q, k, preferred_element_type=jnp.float32) * scale
    if mask is not None:
        logits = jnp.where(mask, logits, jnp.finfo(jnp.float32).min)
    probs = jax.nn.softmax(logits, axis=-1).astype(dtype)
    return jnp.einsum("bhqk,bkhd->bqhd", probs, v)


def repeat_kv(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray):
    """Expand grouped K/V heads to match Q's head count (GQA → MHA view).

    KV head ``j`` serves query heads ``[j·g, (j+1)·g)`` — the convention
    the pallas kernels implement natively via index maps (no expansion)."""
    group = q.shape[2] // k.shape[2]
    if group > 1:
        k = jnp.repeat(k, group, axis=2)
        v = jnp.repeat(v, group, axis=2)
    return k, v


def sdpa(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    *,
    causal: bool = True,
    window: int | None = None,
) -> jnp.ndarray:
    """Plain scaled-dot-product attention on [B, S, H, D] arrays; K/V may
    carry fewer (grouped) heads (GQA), and ``window`` restricts each query
    to the last ``window`` positions (sliding-window attention).

    The reference semantics all pluggable attention implementations (ring,
    pallas flash) must match.  Softmax statistics in float32 regardless of
    the compute dtype — bfloat16 logits lose too much for long sequences.
    """
    k, v = repeat_kv(q, k, v)
    mask = None
    if causal:
        s_q, s_k = q.shape[1], k.shape[1]
        mask = jnp.tril(jnp.ones((s_q, s_k), jnp.bool_), k=s_k - s_q)
        if window is not None:
            pos_q = jnp.arange(s_q)[:, None] + (s_k - s_q)
            mask = mask & (pos_q - jnp.arange(s_k)[None, :] < window)
    elif window is not None:
        raise ValueError("window requires causal=True")
    return _masked_attend(q, k, v, mask)


def _shard_kind(decode_shard) -> str:
    return decode_shard[2] if len(decode_shard) > 2 else "heads"


def _head_sharded(decode_shard, fn, q, k, v, scalar):
    """Run ``fn(q, k, v, scalar)`` per shard over the HEAD dim of q/k/v
    (``scalar`` replicated) — the shard_map island that lets Pallas
    attention kernels compose with a GSPMD rollout (GSPMD cannot
    partition a pallas_call; heads are embarrassingly parallel)."""
    from jax.sharding import PartitionSpec as P

    mesh, ax = decode_shard[0], decode_shard[1]
    spec = P(None, None, ax, None)
    return jax.shard_map(
        fn, mesh=mesh, in_specs=(spec, spec, spec, P()),
        out_specs=spec, check_vma=False)(q, k, v, scalar)


def _seq_sharded_decode(decode_shard, q, k_all, v_all, n, window, h_kv):
    """Sequence-sharded kernelized decode over the PACKED cache: cache
    slices stay put, each shard runs flash_decode with global masking,
    partial softmaxes merge by log-sum-exp (one [B, H] all-gather + one
    psum — no cache movement).  With the 2-D ``"heads_seq"`` kind the
    axis pair ``(head_axis, seq_axis)`` shards heads AND sequence: each
    shard kernels its own (head slice × cache slice) — the packed minor
    dim shards by whole KV heads, contiguous per head — and the merge
    runs over the sequence axis only."""
    from jax.sharding import PartitionSpec as P

    from tpudist.ops.flash_decode import sp_flash_decode

    mesh, ax = decode_shard[0], decode_shard[1]
    if isinstance(ax, tuple):
        hax, sax = ax
    else:
        hax, sax = None, ax
    n_h = mesh.shape[hax] if hax else 1
    if h_kv % n_h:
        raise ValueError(
            f"kv heads {h_kv} not divisible by {hax!r} axis size {n_h}")
    local_kv = h_kv // n_h
    q_spec = P(None, None, hax, None)
    kv_spec = P(None, sax, hax)
    return jax.shard_map(
        lambda qs, ks, vs, nn_: sp_flash_decode(
            qs, ks, vs, nn_, sax, window=window,
            packed_kv_heads=local_kv),
        mesh=mesh, in_specs=(q_spec, kv_spec, kv_spec, P()),
        out_specs=q_spec, check_vma=False)(q, k_all, v_all, n)


def _head_sharded_packed(decode_shard, q, k_all, v_all, n, window, h_kv):
    """Head-sharded flash decode over the PACKED cache: each shard owns
    whole KV-head chunks of the packed minor dim and runs the kernel on
    its slice — the TP layout, no collectives at all."""
    from jax.sharding import PartitionSpec as P

    from tpudist.ops.flash_decode import flash_decode

    mesh, ax = decode_shard[0], decode_shard[1]
    if h_kv % mesh.shape[ax]:
        raise ValueError(
            f"kv heads {h_kv} not divisible by {ax!r} axis size "
            f"{mesh.shape[ax]}")
    local_kv = h_kv // mesh.shape[ax]
    q_spec = P(None, None, ax, None)
    kv_spec = P(None, None, ax)
    return jax.shard_map(
        lambda qs, ks, vs, nn_: flash_decode(
            qs, ks, vs, nn_, window=window, packed_kv_heads=local_kv),
        mesh=mesh, in_specs=(q_spec, kv_spec, kv_spec, P()),
        out_specs=q_spec, check_vma=False)(q, k_all, v_all, n)


@dataclasses.dataclass(frozen=True)
class YarnScaling:
    """YaRN's rotary frequency blend (``rope_scaling`` of a published
    config): frequencies above the ``beta_fast`` correction dim stay,
    those below ``beta_slow``'s are divided by ``factor``, a linear ramp
    between.  ``mscale_all_dim`` also enters the softmax scale."""

    factor: float = 40.0
    original_max_position: int = 4096
    beta_fast: float = 32.0
    beta_slow: float = 1.0
    mscale: float = 1.0
    mscale_all_dim: float = 0.0


@dataclasses.dataclass(frozen=True)
class MLAConfig:
    """Multi-head latent attention: queries through a ``q_lora_rank``
    latent, keys and values through ONE ``kv_lora_rank`` latent plus one
    rotary key of ``qk_rope_head_dim`` shared by all heads.  The cache
    holds ``[latent | rotary key]`` a token, padded to a lane multiple."""

    q_lora_rank: int
    kv_lora_rank: int
    qk_nope_head_dim: int
    qk_rope_head_dim: int
    v_head_dim: int

    @property
    def qk_head_dim(self) -> int:
        return self.qk_nope_head_dim + self.qk_rope_head_dim

    @property
    def cache_width(self) -> int:
        """A cached row: latent, rotary key, zeros up to a multiple of 128
        lanes.  On the TPU a 576-wide bf16 row occupies 640 lanes of tiled
        memory anyway; stated so, the row's copies and the contraction
        over it are lane-aligned, and the zeros add nothing to a score."""
        return -(-(self.kv_lora_rank + self.qk_rope_head_dim) // 128) * 128


@dataclasses.dataclass(frozen=True)
class LinearAttentionConfig:
    """The sizes of a gated delta-rule layer (:class:`LinearAttention`):
    ``num_heads`` heads, each a ``key_dim x value_dim`` float32 matrix of
    state; a causal depthwise convolution of ``conv_width`` taps over the
    q, k and v channels; ``neg_eigval`` doubles the write strength
    (``beta`` in (0, 2): a state's eigenvalues may go negative)."""

    num_heads: int
    key_dim: int
    value_dim: int
    conv_width: int = 4
    neg_eigval: bool = True

    @property
    def conv_channels(self) -> int:
        """q, k and v side by side: what the convolution runs over."""
        return self.num_heads * (2 * self.key_dim + self.value_dim)


@dataclasses.dataclass(frozen=True)
class TransformerConfig:
    vocab_size: int = 256
    num_layers: int = 2
    num_heads: int = 4
    embed_dim: int = 128
    mlp_ratio: int = 4
    max_seq_len: int = 512
    compute_dtype: jnp.dtype = jnp.float32
    # Grouped-query attention: K/V heads (None = num_heads, plain MHA).
    # Shrinks the decode KV cache by num_heads/num_kv_heads.
    num_kv_heads: int | None = None
    # Sliding-window attention width (None = full causal attention).
    # The single source of truth: the training path passes it to the
    # attention_fn and the decode cache mask applies the same band.
    # This is the spelling for "every layer"; ``layer_windows`` states a
    # window a layer.
    attention_window: int | None = None
    # One entry a layer: that layer's sliding-window width, None for full
    # causal attention (None here: every layer takes attention_window).
    # Read through ``layer_window(i)``.
    layer_windows: tuple | None = None
    # One entry a layer: its KIND, "full" | "window" | "linear" (None: by
    # its window alone, as ever).  "linear" is a gated delta-rule layer of
    # the sizes under ``linear``: its past is a fixed-size state and not
    # rows of a cache.  Read through ``layer_kind(i)``.
    layer_kinds: tuple | None = None
    linear: LinearAttentionConfig | None = None
    # A head's width where it is not embed_dim // num_heads (None: that
    # quotient).  Read through ``head_dim``.
    head_size: int | None = None
    # Compile the layer stack as ONE lax.scan over stacked parameters
    # instead of a Python loop (the maxtext-style "scan over layers").
    # The traced program holds one block body regardless of depth, so
    # HLO size and compile time stop scaling with num_layers.
    # Param layout changes from block{i}/... to blocks/block/... with a
    # leading layer axis; convert with stack_layer_params /
    # unstack_layer_params.  Lives on the config so every cache-decode
    # rollout (generate / speculative) builds the matching model.
    # NOTE: `transformer_tp_rules` targets the UNROLLED layout — its
    # 2-D PartitionSpecs would land on the wrong axes of the stacked
    # [L, in, out] kernels, so tp_generate/TP training take the
    # unrolled layout (serving rollouts convert with
    # unstack_layer_params if needed).  Single-token DECODE is ~4×
    # slower scanned (measured): every scan step dynamic-slices its
    # layer's cache from the stacked buffer and writes it back, ~3×
    # extra HBM traffic per token — prefer the unrolled layout for
    # plain decode latency; chunked verify forwards (speculative)
    # amortize the cost and keep the compile-size win.
    scan_layers: bool = False
    # -- the block's vocabulary.  The defaults are the GPT-2 / GPTBigCode
    # block this class began as (LayerNorm, a learned position table, a
    # tanh-GELU MLP of mlp_ratio x embed_dim); every other value is read
    # where the block is built, and nothing is keyed on a model's name.
    # | "rmsnorm" | "layernorm_scale" (mean-centred, a scale and NO bias)
    norm: str = "layernorm"
    norm_eps: float = 1e-6
    # "pre": x + f(norm(x)); "post": x + norm(f(x)) (the norm AFTER the
    # sublayer, on its output alone); "parallel": ONE norm a layer that
    # both sublayers read, x + attn(norm(x)) + mlp(norm(x)), one add
    norm_order: str = "pre"
    # | "rotary" (no position table) | "none" (no positions at all: layers
    # that carry the order themselves, a convolution and a decay)
    positions: str = "learned"
    # the FULL layers' positions where they are not the model's ("as_model":
    # ``positions`` for every layer; "none": under rotary ``positions`` the
    # layers without a window rotate nothing and build no angles, and the
    # windowed layers alone carry the order).  Read through
    # ``layer_positions(i)``.
    full_layer_positions: str = "as_model"
    rope_theta: float = 10000.0
    rope_scaling: YarnScaling | None = None
    # the windowed layers' rotary scaling where it is not the full
    # layers' ("as_full": rope_scaling for every layer; None: plain
    # frequencies on the windowed layers).  Read through
    # ``layer_rope_scaling(i)``.
    window_rope_scaling: Any = "as_full"
    # rotary width of a plain head (None = all of head_dim); MLA rotates
    # its own qk_rope_head_dim slice
    rope_dim: int | None = None
    mlp: str = "gelu"                  # | "gated_silu": down(silu(gate) * up)
    mlp_dim: int | None = None         # None = mlp_ratio * embed_dim
    # latent attention instead of MHA/GQA (num_kv_heads is then unused)
    mla: MLAConfig | None = None
    # expert layers: a tpudist.models.moe.MoEConfig; the first
    # ``first_k_dense`` layers keep the dense MLP
    moe: Any = None
    first_k_dense: int = 0
    # True: a per-head RMSNorm (one scale of head_dim, shared by the
    # heads) on the queries and the keys before the rotation; "whole": one
    # RMSNorm over the WHOLE projection (a scale of every head's every
    # feature), before the split into heads
    qk_norm: bool | str = False
    # a learned indexer (sparse attention over grouped-query K/V):
    # ``index_heads`` index queries of ``index_head_dim`` a token, ONE index
    # key a token (cached beside K and V), a float32 weight a head; a query
    # attends only the ``index_topk`` rows of the highest ``sum_h w_h
    # relu(q_h . k)``.  All three None: no indexer, the program as it was.
    index_heads: int | None = None
    index_head_dim: int | None = None
    index_topk: int | None = None
    # True: the head reads the embedding's table (no ``lm_head`` leaf);
    # either head's logits are multiplied by ``logit_scale``
    tie_embeddings: bool = False
    logit_scale: float = 1.0

    def __post_init__(self):
        if self.norm_order not in ("pre", "post", "parallel"):
            raise ValueError(f"norm_order must be 'pre', 'post' or "
                             f"'parallel', got {self.norm_order!r}")
        if self.full_layer_positions not in ("as_model", "none"):
            raise ValueError(
                f"full_layer_positions must be 'as_model' or 'none', got "
                f"{self.full_layer_positions!r}")
        if self.full_layer_positions == "none" and (
                self.positions != "rotary" or self.mla is not None
                or self.index_topk is not None):
            raise ValueError(
                "position-free full layers stand beside ROTARY window "
                "layers of multi-head / grouped-query attention (a learned "
                "table is added once for every layer; latent attention and "
                "an indexer rotate slices of their own)")
        if self.qk_norm not in (False, True, "whole"):
            raise ValueError(f"qk_norm must be False, True or 'whole', got "
                             f"{self.qk_norm!r}")
        if self.layer_kinds is not None:
            if len(self.layer_kinds) != self.num_layers:
                raise ValueError(
                    f"layer_kinds has {len(self.layer_kinds)} entries for "
                    f"{self.num_layers} layers")
            for i, kind in enumerate(self.layer_kinds):
                if kind not in ("full", "window", "linear"):
                    raise ValueError(
                        f"a layer's kind is 'full', 'window' or 'linear', "
                        f"got {kind!r}")
                if (kind == "window") != (kind != "linear" and
                                          self.layer_window(i) is not None):
                    raise ValueError(
                        f"layer {i} is {kind!r} with window "
                        f"{self.layer_window(i)}: a 'window' layer states "
                        "its width, a 'full' layer none")
            if "linear" in self.layer_kinds and (
                    self.linear is None or self.mla is not None
                    or self.index_topk is not None or self.scan_layers):
                raise ValueError(
                    "a 'linear' layer takes its sizes from `linear` and "
                    "stands beside multi-head / grouped-query layers in "
                    "the unrolled layout: no latent attention, no "
                    "indexer, no scan_layers")
        sizes = (self.index_heads, self.index_head_dim, self.index_topk)
        if any(v is None for v in sizes) and any(v is not None
                                                 for v in sizes):
            raise ValueError(
                "an indexer states index_heads, index_head_dim and "
                f"index_topk together; got {sizes}")
        if self.index_topk is not None:
            if (self.index_topk % 8 or self.index_heads % 8
                    or self.index_head_dim % 2):
                raise ValueError(
                    "index_topk and index_heads are multiples of 8 (the "
                    "chosen rows are attended as pages, the heads fill "
                    "sublanes) and an index head rotates in pairs; got "
                    f"{sizes}")
            if (self.mla is not None or self.positions != "rotary"
                    or any(w is not None for w in self.windows)):
                raise ValueError(
                    "an indexer chooses rows for grouped-query attention "
                    "under rotary positions, every layer over its whole "
                    "context: no latent attention, no sliding window")

    @property
    def index_cache_width(self) -> int:
        """A cached index key: ``index_head_dim`` numbers and zeros up to
        a multiple of 128 lanes.  On the TPU a 64-wide bf16 row occupies
        128 lanes of tiled memory anyway (and the chip's compiler refuses
        a page copy narrower than a tile); stated so, the row's copies and
        the contraction over it are lane-aligned, and the zeros add nothing
        to a score."""
        return -(-self.index_head_dim // 128) * 128

    @property
    def ffn_dim(self) -> int:
        return self.mlp_dim or self.mlp_ratio * self.embed_dim

    def is_expert_layer(self, i: int) -> bool:
        return self.moe is not None and i >= self.first_k_dense

    @property
    def head_dim(self) -> int:
        if self.head_size is not None:
            return self.head_size
        assert self.embed_dim % self.num_heads == 0
        return self.embed_dim // self.num_heads

    @property
    def attn_dim(self) -> int:
        """Width of the heads side by side: what ``proj`` takes."""
        return self.num_heads * self.head_dim

    def layer_window(self, i: int | None) -> int | None:
        """Layer ``i``'s sliding-window width (None: full attention);
        ``i`` None (a module built on its own, a scanned stack): every
        layer's, ``attention_window``."""
        if self.layer_windows is None or i is None:
            return self.attention_window
        if len(self.layer_windows) != self.num_layers:
            raise ValueError(
                f"layer_windows has {len(self.layer_windows)} entries for "
                f"{self.num_layers} layers")
        return self.layer_windows[i]

    @property
    def windows(self) -> tuple:
        """``layer_window`` of every layer."""
        return tuple(self.layer_window(i) for i in range(self.num_layers))

    def layer_kind(self, i: int | None) -> str:
        """Layer ``i``'s kind: ``layer_kinds[i]`` where the model states
        kinds, else by its window."""
        if self.layer_kinds is not None and i is not None:
            return self.layer_kinds[i]
        return "full" if self.layer_window(i) is None else "window"

    @property
    def kinds(self) -> tuple:
        """``layer_kind`` of every layer."""
        return tuple(self.layer_kind(i) for i in range(self.num_layers))

    def layer_rope_scaling(self, i: int | None) -> YarnScaling | None:
        if (self.layer_window(i) is not None
                and self.window_rope_scaling != "as_full"):
            return self.window_rope_scaling
        return self.rope_scaling

    def layer_positions(self, i: int | None) -> str:
        """Layer ``i``'s positions: ``positions``, except on a layer
        without a window where ``full_layer_positions`` says otherwise."""
        if (self.full_layer_positions != "as_model"
                and self.layer_window(i) is None):
            return self.full_layer_positions
        return self.positions

    @property
    def kv_heads(self) -> int:
        kv = self.num_kv_heads or self.num_heads
        assert self.num_heads % kv == 0, (self.num_heads, kv)
        return kv


def make_norm(cfg: TransformerConfig, name: str):
    """The block's normalisation layer, by ``cfg.norm``."""
    if cfg.norm == "layernorm":
        return nn.LayerNorm(epsilon=cfg.norm_eps, dtype=cfg.compute_dtype,
                            name=name)
    if cfg.norm == "rmsnorm":
        return nn.RMSNorm(epsilon=cfg.norm_eps, dtype=cfg.compute_dtype,
                          name=name)
    if cfg.norm == "layernorm_scale":
        return nn.LayerNorm(epsilon=cfg.norm_eps, dtype=cfg.compute_dtype,
                            use_bias=False, name=name)
    raise ValueError(f"norm must be 'layernorm', 'rmsnorm' or "
                     f"'layernorm_scale', got {cfg.norm!r}")


def rope_inv_freq(dim: int, theta: float,
                  scaling: YarnScaling | None) -> jnp.ndarray:
    """``[dim // 2]`` rotary frequencies: ``theta^(-2i/dim)``, under YaRN
    blended with the same divided by ``factor`` (a linear ramp between the
    correction dims ``beta_fast`` / ``beta_slow`` give for the original
    positions)."""
    import math

    half = dim // 2
    extra = 1.0 / theta ** (jnp.arange(half, dtype=jnp.float32) * 2.0 / dim)
    if scaling is None:
        return extra

    def correction_dim(rotations):
        return (dim * math.log(scaling.original_max_position
                               / (rotations * 2 * math.pi))
                / (2 * math.log(theta)))

    low = max(math.floor(correction_dim(scaling.beta_fast)), 0)
    high = min(math.ceil(correction_dim(scaling.beta_slow)), dim - 1)
    if low == high:
        high += 0.001
    ramp = jnp.clip((jnp.arange(half, dtype=jnp.float32) - low)
                    / (high - low), 0.0, 1.0)
    return extra / scaling.factor * ramp + extra * (1.0 - ramp)


def _yarn_mscale(factor: float, m: float) -> float:
    import math

    return 1.0 if factor <= 1 or not m else 0.1 * m * math.log(factor) + 1.0


def apply_rope(x: jnp.ndarray, positions: jnp.ndarray,
               cfg: TransformerConfig, scaling: Any = "cfg") -> jnp.ndarray:
    """Rotate the last axis of ``x [B, S, ..., dim]`` at ``positions
    [B or 1, S]``.  HALF-SPLIT pairing: feature ``i`` pairs with
    ``i + dim/2`` (what public implementations permute published
    interleaved weights to).  Angles and the rotation in float32.
    ``scaling``: the layer's own (``cfg.layer_rope_scaling``) where layers
    differ; by default ``cfg.rope_scaling``."""
    dim = x.shape[-1]
    sc = cfg.rope_scaling if isinstance(scaling, str) else scaling
    ang = (positions.astype(jnp.float32)[..., None]
           * rope_inv_freq(dim, cfg.rope_theta, sc))      # [B|1, S, dim/2]
    mult = 1.0 if sc is None else (_yarn_mscale(sc.factor, sc.mscale)
                                   / _yarn_mscale(sc.factor,
                                                  sc.mscale_all_dim))
    shape = ang.shape[:2] + (1,) * (x.ndim - 3) + (dim // 2,)
    cos = (jnp.cos(ang) * mult).reshape(shape)
    sin = (jnp.sin(ang) * mult).reshape(shape)
    a = x[..., : dim // 2].astype(jnp.float32)
    b = x[..., dim // 2:].astype(jnp.float32)
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin],
                           -1).astype(x.dtype)


def _flash_prefill(q, k_all, v_all, idx, *, window=None, scale=None,
                   decode_shard=None, k_offset=0):
    """Chunk prefill through the flash forward kernel: queries at global
    positions ``[idx, idx + s)`` against ``k_all`` / ``v_all [B, S, Hkv,
    D]`` at ``q_offset=idx`` (its causal mask also silences the garbage in
    not-yet-written rows; dead tiles are pruned).  ``k_offset``: the global
    position of ``k_all``'s row 0 (a rolling buffer's base).

    Pads the query-row count so _auto_block lands on a Mosaic-lowerable
    block: the LSE output's [1, 1, block_q] block needs block_q % 128 == 0
    or block_q == s_pad, and q/out need the 8-row sublane tile.  Short
    chunks round up to a power of two (block = whole chunk); long ones to
    a multiple of 1024 so block_q is the measured-optimal 1024 (a prompt
    like 7928 = 8·991 would otherwise get block_q = 8, which real-TPU
    lowering rejects).  Padded rows are causally garbage but independent
    of the real rows; sliced off below."""
    from tpudist.ops.flash_attention import _auto_block, _flash_forward

    s, rows = q.shape[1], k_all.shape[1]
    if s <= 1024:
        s_pad = max(8, 1 << (s - 1).bit_length())
    else:
        s_pad = -(-s // 1024) * 1024
    q_in = q if s_pad == s else jnp.pad(
        q, ((0, 0), (0, s_pad - s), (0, 0), (0, 0)))
    block_k = _auto_block(rows)
    if block_k < 8:  # the K side has the same sublane floor
        raise ValueError(
            f"decode_attention='flash' needs a power-of-two factor "
            f">= 8 in max_seq_len (got {rows}); round "
            f"max_seq_len up to a multiple of 8")
    interp = jax.default_backend() == "cpu"
    bq = _auto_block(s_pad)
    if decode_shard is not None:
        def local(qs, ks, vs, off):
            out, _ = _flash_forward(
                qs, ks, vs, True, bq, block_k, interp,
                q_offset=off, window=window, scale=scale)
            return out

        out = _head_sharded(decode_shard, local, q_in, k_all, v_all, idx)
        return out[:, :s]
    out, _ = _flash_forward(
        q_in, k_all, v_all, True, bq, block_k, interp,
        q_offset=idx, k_offset=k_offset, window=window, scale=scale)
    return out[:, :s]


def _index_scores(q_i, w_i, ikeys):
    """A learned indexer's scores by plain jax.numpy: ``q_i [B, S, Hi, Di]``
    and ``w_i [B, S, Hi]`` float32 against ``ikeys [B, R, Di]`` -> ``sum_h
    w_h relu(q_h . k)``, ``[B, S, R]`` float32."""
    sc = jnp.einsum("bshd,brd->bshr", q_i, ikeys.astype(q_i.dtype),
                    preferred_element_type=jnp.float32)
    return jnp.einsum("bshr,bsh->bsr", jnp.maximum(sc, 0.0),
                      w_i.astype(jnp.float32))


def _index_choice(q_i, w_i, ikeys, valid, k: int):
    """The rows a learned indexer chooses, as a mask, by plain jax.numpy:
    ``q_i [B, S, Hi, Di]`` and ``w_i [B, S, Hi]`` float32 against ``ikeys
    [B, R, Di]``; ``valid [B, S, R]`` says which rows a query sees.  True
    for its ``min(k, rows seen)`` rows of the highest ``sum_h w_h relu(q_h
    . k)`` (float32; equal scores to the lower row).  Holds ``[B, S, Hi,
    R]`` float32: the one-shot forward, the dense fallbacks and the tests'
    comparison, not the serving path."""
    b, s, r = valid.shape
    sc = jnp.where(valid, _index_scores(q_i, w_i, ikeys), -jnp.inf)
    ids = jax.lax.top_k(sc, min(k, r))[1]
    chosen = jnp.zeros((b, s, r), bool).at[
        jnp.arange(b)[:, None, None], jnp.arange(s)[None, :, None],
        ids].set(True)
    return chosen & valid


def _per_row_takes_one_token(s: int) -> None:
    """The per-row cache paths (a vector ``cache_index``: the serve loop's
    slot cache) decode ONE token a lane a call."""
    if s != 1:
        raise ValueError(
            "a cache with a vector cache_index (the serve loop's slot "
            f"cache) takes one token a lane a call, got {s}: a prompt is "
            "prefilled through the batch-1 scalar-index cache and inserted")


class CausalSelfAttention(nn.Module):
    """Multi-head / grouped-query attention with the serve loop's caches.

    With an INDEXER (``cfg.index_topk``) a query attends only the
    ``index_topk`` rows its index scores put first.  A token then keeps a
    third row, its index key (``index_head_dim`` numbers after its
    LayerNorm and its rotation, zeros up to ``cfg.index_cache_width``),
    under ``cached_ikey`` / ``paged_ikey`` /
    ``side_ikey`` beside the K and V leaves; and in the PAGED cache its K
    and V are ONE row of 32-bit words (``paged_kv`` / ``side_kv``,
    ``ops.flash_decode.kv_row``: for a 16-bit ``compute_dtype``
    ``uint32[kv_heads x head_dim]``, word ``j`` K's element ``j`` in its
    low half and V's in its high, the raw bits; for a 32-bit one a number
    is a word, ``[2 x kv_heads x head_dim]``, K's heads then V's),
    because a decode step gathers each lane's chosen rows and the chip's
    gather costs by the row, and least for a row of words: one gather a
    layer brings both.  What differs follows from the dtype's width
    alone.  The batch-1 prefill cache keeps ``cached_key`` /
    ``cached_value`` apart (its kernels read each whole); the finish's
    scatter packs them (``pack_kv``).  Both cached paths then run
    scores -> selection -> attention over the chosen rows
    (``ops.flash_decode.paged_index_scores``, ``index_select_mask`` /
    ``index_select``, ``sparse_gqa_attend``; a prefill chunk through
    ``ops.flash_attention.flash_chosen_rows``): the decode step when some
    lane holds more than ``index_topk`` rows, a prefill chunk when it ends
    beyond row ``index_topk``; below that every row is chosen and the
    program is the one without an indexer."""

    cfg: TransformerConfig
    attention_fn: AttentionFn = sdpa
    decode: bool = False
    # "dense": masked softmax over the whole cache buffer; "flash": the
    # Pallas flash-decode kernel (tpudist.ops.flash_decode) — same numerics,
    # one cache read per KV head, the long-context serving path.
    decode_attention: str = "dense"
    # (mesh, axis) or (mesh, axis, kind): run the flash kernels PER SHARD
    # via shard_map — GSPMD cannot partition a Pallas call.  kind="heads"
    # (default, the TP layout): heads are embarrassingly parallel, each
    # shard owns whole KV-head groups; both prefill and decode kernelize.
    # kind="seq" (the SP layout, cache sequence-sharded): each shard runs
    # flash_decode on its cache slice with GLOBAL masking and partial
    # softmaxes merge by log-sum-exp (tpudist.ops.flash_decode.
    # sp_flash_decode); prefill stays on the dense GSPMD path (queries
    # must attend across every shard's slice).
    # kind="heads_seq" (the 2-D TP×SP layout): axis is the PAIR
    # (head_axis, seq_axis) — each shard kernels its own (head-group ×
    # cache-slice) block and the merge runs over seq_axis only.
    decode_shard: Any = None
    # continuous-batching side-buffer capacity (tokens per segment); > 0
    # selects the sided serve step — see _serve_attend_sided
    serve_side_slots: int = 0
    # "dense": per-slot [B, S, Hkv*D] cache buffers; "paged": ONE shared
    # block pool [kv_num_blocks, kv_block_size, Hkv*D] per layer plus a
    # [B, max_blocks] page table (PagedAttention) — HBM scales with
    # allocated tokens, not B x S.  Paged serving requires the side-
    # buffer step (the pool is frozen within a segment; the ServeLoop's
    # per-segment merge scatters side tokens through the page table).
    cache_layout: str = "dense"
    kv_num_blocks: int = 0
    kv_block_size: int = 0
    # this layer's index in the stack: its window and rotary scaling are
    # cfg.layer_window(layer) / cfg.layer_rope_scaling(layer)
    layer: int | None = None
    # > 0 (the serve loop's batch-1 prefill model): a WINDOWED layer's
    # dense scalar-index cache is a rolling buffer of window +
    # prefill_window_rows rows, fed chunks of prefill_window_rows tokens
    # in order (see _rolling_prefill); 0: max_seq_len rows as ever
    prefill_window_rows: int = 0

    @property
    def window(self) -> int | None:
        return self.cfg.layer_window(self.layer)

    @nn.compact
    def __call__(self, x: jnp.ndarray, *, causal: bool = True,
                 positions: Optional[jnp.ndarray] = None) -> jnp.ndarray:
        cfg = self.cfg
        b, s, _ = x.shape
        with routine("attn/proj"):
            if cfg.kv_heads == cfg.num_heads:
                qkv = nn.Dense(3 * cfg.attn_dim, use_bias=False,
                               dtype=cfg.compute_dtype, name="qkv")(x)
                qkv = qkv.reshape(b, s, 3, cfg.num_heads, cfg.head_dim)
                q, k, v = qkv[:, :, 0], qkv[:, :, 1], qkv[:, :, 2]
            else:  # GQA: separate projections, K/V at the grouped head count
                q = nn.Dense(cfg.attn_dim, use_bias=False,
                             dtype=cfg.compute_dtype, name="q")(x)
                q = q.reshape(b, s, cfg.num_heads, cfg.head_dim)
                kv = nn.Dense(2 * cfg.kv_heads * cfg.head_dim, use_bias=False,
                              dtype=cfg.compute_dtype, name="kv")(x)
                kv = kv.reshape(b, s, 2, cfg.kv_heads, cfg.head_dim)
                k, v = kv[:, :, 0], kv[:, :, 1]
            if cfg.qk_norm == "whole":
                def whole(x, name):
                    flat = x.reshape(b, s, -1)
                    return nn.RMSNorm(
                        epsilon=cfg.norm_eps, dtype=cfg.compute_dtype,
                        name=name)(flat).reshape(x.shape)

                q, k = whole(q, "q_norm"), whole(k, "k_norm")
            elif cfg.qk_norm:
                q = nn.RMSNorm(epsilon=cfg.norm_eps, dtype=cfg.compute_dtype,
                               name="q_norm")(q)
                k = nn.RMSNorm(epsilon=cfg.norm_eps, dtype=cfg.compute_dtype,
                               name="k_norm")(k)
            if cfg.layer_positions(self.layer) == "rotary":
                # keys are cached AFTER the rotation, at their own positions
                if positions is None:
                    positions = jnp.arange(s)[None, :]
                rd = cfg.rope_dim or cfg.head_dim
                sc = cfg.layer_rope_scaling(self.layer)
                q = jnp.concatenate(
                    [apply_rope(q[..., :rd], positions, cfg, sc),
                     q[..., rd:]], -1)
                k = jnp.concatenate(
                    [apply_rope(k[..., :rd], positions, cfg, sc),
                     k[..., rd:]], -1)
        # cfg is the single source of truth for the sliding window: a
        # factory built with its OWN window (flash_attention_fn(window=W))
        # that disagrees is rejected — in BOTH branches, since the decode
        # cache masks from cfg alone and would otherwise silently discard
        # the factory's window.
        fw = getattr(self.attention_fn, "factory_window", None)
        if fw is not None and fw != self.window:
            raise ValueError(
                f"attention_fn was built with window={fw} but "
                f"cfg.attention_window={self.window}; set the "
                "window on TransformerConfig (the single source of "
                "truth) or make the two agree")
        index = None
        if cfg.index_topk is not None:
            if not causal:
                raise ValueError("an indexer chooses among the rows before "
                                 "a query: causal attention only")
            hi, di = cfg.index_heads, cfg.index_head_dim
            with routine("attn/proj"):
                # index queries and the one index key a token, every feature
                # rotated at the token's position by plain frequencies
                q_i = apply_rope(
                    nn.Dense(hi * di, use_bias=False, dtype=cfg.compute_dtype,
                             name="idx_q")(x).reshape(b, s, hi, di),
                    positions, cfg, None)
                k_i = apply_rope(
                    nn.LayerNorm(epsilon=1e-6, dtype=cfg.compute_dtype,
                                 name="idx_k_norm")(
                        nn.Dense(di, use_bias=False, dtype=cfg.compute_dtype,
                                 name="idx_k")(x)),
                    positions, cfg, None)
                w_i = nn.Dense(hi, use_bias=False, dtype=jnp.float32,
                               param_dtype=jnp.float32, name="idx_w")(
                    x) * (hi ** -0.5 * di ** -0.5)
            index = (q_i, w_i, k_i)
        if self.decode:
            if index is not None:
                # the cached key's width (index_cache_width): zero columns
                pad = [(0, 0)] * 3 + [(0, cfg.index_cache_width - di)]
                with routine("attn/proj"):
                    index = (jnp.pad(q_i, pad), w_i, jnp.pad(k_i, pad[1:]))
            out = self._cached_attend(q, k, v, index)
        elif index is not None and s > cfg.index_topk:
            # the one-shot forward: the chosen rows as a mask
            with routine("attn/index"):
                mask = _index_choice(
                    q_i, w_i, k_i, jnp.broadcast_to(
                        jnp.tril(jnp.ones((s, s), bool)), (b, s, s)),
                    cfg.index_topk)
            with routine("attn/core"):
                out = _masked_attend(q, *repeat_kv(q, k, v), mask[:, None])
        else:
            # window passed unconditionally (None = full causal) so the
            # training path can never diverge from the decode cache mask,
            # and a fn that doesn't accept the kwarg fails loudly instead
            # of training full-attention against a windowed decode cache.
            with routine("attn/core"):
                out = self.attention_fn(q, k, v, causal=causal,
                                        window=self.window)
        with routine("attn/proj"):
            out = out.reshape(b, s, cfg.attn_dim)
            return nn.Dense(cfg.embed_dim, use_bias=False,
                            dtype=cfg.compute_dtype, name="proj")(out)

    def _cached_attend(self, q, k, v, index=None):
        """Decoding against a KV cache of ``max_seq_len`` slots (the
        standard flax ``cache`` collection pattern): fixed-shape buffers +
        ``dynamic_update_slice`` keep the whole autoregressive loop
        jittable as a ``lax.scan``.

        ``s == 1`` is the per-token decode step; ``s > 1`` is PREFILL —
        the whole prompt chunk lands in the cache in one call and attends
        causally within itself + everything cached before it (the serving
        split: one batched forward for the prompt, then one-token steps).
        """
        cfg = self.cfg
        b, s, _, d = q.shape
        h_kv = k.shape[2]  # the GQA cache-memory win: Hkv slots, not H
        if self.cache_layout == "paged":
            # the paged layout never materializes the dense buffers —
            # that absence IS the capacity win, so branch before the
            # cached_key/cached_value variables exist
            return self._paged_attend(q, k, v, index)
        if self.cache_layout != "dense":
            raise ValueError(
                f"cache_layout must be 'dense' or 'paged', got "
                f"{self.cache_layout!r}")
        # The cache is stored PACKED [B, S, Hkv*D]: with the per-head
        # 4-D shape and narrow heads (e.g. [B, S, 2, 64]), XLA lays the
        # carry out S-minor and inserts TWO full-cache layout-conversion
        # copies per decode step feeding the pallas kernel (measured ~2x
        # step time at 8k context; see flash_decode's packed mode).  A
        # lane-multiple minor dim keeps every consumer relayout-free;
        # per-head views are reshaped where semantics need them.
        flat = h_kv * d
        if self.prefill_window_rows and self.window is not None:
            return self._rolling_prefill(q, k, v)
        cached_k = self.variable(
            "cache", "cached_key", jnp.zeros,
            (b, cfg.max_seq_len, flat), cfg.compute_dtype)
        cached_v = self.variable(
            "cache", "cached_value", jnp.zeros,
            (b, cfg.max_seq_len, flat), cfg.compute_dtype)
        cached_ik = None if index is None else self.variable(
            "cache", "cached_ikey", jnp.zeros,
            (b, cfg.max_seq_len, cfg.index_cache_width), cfg.compute_dtype)
        idx_var = self.variable(
            "cache", "cache_index", lambda: jnp.zeros((), jnp.int32))
        idx = idx_var.value
        if idx.ndim == 1:
            if index is not None:
                raise ValueError(
                    "a model with an indexer decodes per-row through the "
                    "paged cache (ServeLoop with cache_layout='paged'): "
                    "the dense layout's per-row step has no index scores "
                    "or selection")
            # PER-ROW cache positions (vector cache_index [B]) — the
            # continuous-batching serve mode: every slot decodes at its
            # own length (tpudist.models.serving swaps the scalar index
            # leaves for vectors when building the slot cache), one
            # token a call; prefill runs per-slot through a scalar-index
            # side cache and is INSERTED (serving._insert).
            _per_row_takes_one_token(s)
            return self._serve_attend(
                q, k, v, cached_k, cached_v, idx_var)
        with routine("attn/cache"):
            k_all = jax.lax.dynamic_update_slice(
                cached_k.value,
                k.reshape(b, s, flat).astype(cached_k.value.dtype),
                (0, idx, 0))
            v_all = jax.lax.dynamic_update_slice(
                cached_v.value,
                v.reshape(b, s, flat).astype(cached_v.value.dtype),
                (0, idx, 0))
            cached_k.value, cached_v.value = k_all, v_all
            idx_var.value = idx + s
            if index is not None:
                q_i, w_i, k_i = index
                ikeys = jax.lax.dynamic_update_slice(
                    cached_ik.value, k_i.astype(cached_ik.value.dtype),
                    (0, idx, 0))
                cached_ik.value = ikeys
                index = (q_i, w_i, ikeys)

        def view4(x):
            return x.reshape(b, cfg.max_seq_len, h_kv, d)

        if s > 1:
            return self._prefill_attend(q, view4(k_all), view4(v_all), idx,
                                        index)
        if index is not None:
            # a scalar-index rollout's step: the chosen rows as a mask
            with routine("attn/index"):
                mask = _index_choice(
                    *index, jnp.broadcast_to(
                        jnp.arange(cfg.max_seq_len) <= idx,
                        (b, 1, cfg.max_seq_len)), cfg.index_topk)
            with routine("attn/core"):
                k4, v4 = repeat_kv(q, view4(k_all), view4(v_all))
                return _masked_attend(q, k4, v4, mask[:, None])
        with routine("attn/core"):
            if self.decode_attention == "flash":
                from tpudist.ops.flash_decode import flash_decode

                if self.decode_shard is not None:
                    if _shard_kind(self.decode_shard) in ("seq",
                                                          "heads_seq"):
                        return _seq_sharded_decode(
                            self.decode_shard, q, k_all, v_all, idx + 1,
                            self.window, h_kv)
                    return _head_sharded_packed(
                        self.decode_shard, q, k_all, v_all, idx + 1,
                        self.window, h_kv)
                return flash_decode(q, k_all, v_all, idx + 1,
                                    window=self.window,
                                    packed_kv_heads=h_kv)
            mask = jnp.arange(cfg.max_seq_len) <= idx        # causal: ≤ self
            if self.window is not None:  # sliding window: last W only
                mask = mask & (
                    idx - jnp.arange(cfg.max_seq_len) < self.window)
            k4, v4 = repeat_kv(q, view4(k_all), view4(v_all))
            return _masked_attend(q, k4, v4, mask[None, None, None, :])

    def _rolling_prefill(self, q, k, v):
        """Chunk prefill of a WINDOWED layer through a rolling buffer of
        ``window + prefill_window_rows`` rows instead of ``max_seq_len``:
        a prefilling lane of the serve loop then holds, in this layer,
        the window and the chunk in hand and not the whole context.

        The layout is a function of the write cursor alone (the serve
        loop forces the cursor to a chunk's offset, so nothing else can
        carry it): chunks arrive IN ORDER, each ``prefill_window_rows``
        wide but the last, and at entry with the cursor at ``idx`` row
        ``i`` holds position ``base(idx - chunk) + i``, ``base(x) =
        max(0, x - window)``: what the chunk before left.  The buffer is
        moved down to ``base(idx)``, the chunk lands at row ``idx -
        base(idx)`` (at most ``window``), and the queries attend at
        ``k_offset=base(idx)``.  Nothing moves AFTER the chunk, so the
        last chunk's padded tail never pushes a live row out: the rows a
        decode step needs after a prompt of ``L`` tokens, ``[L - window +
        1, L)``, are all there (``serving._insert_window_node`` reads them
        at ``base(offset of the last chunk)``)."""
        cfg = self.cfg
        b, s, h_kv, d = k.shape
        w, c = self.window, self.prefill_window_rows
        rows, flat = w + c, h_kv * d
        if s > c:
            raise ValueError(
                f"a windowed layer's prefill buffer takes chunks of at "
                f"most prefill_window_rows={c} tokens, got {s}")
        cached_k = self.variable(
            "cache", "cached_key", jnp.zeros, (b, rows, flat),
            cfg.compute_dtype)
        cached_v = self.variable(
            "cache", "cached_value", jnp.zeros, (b, rows, flat),
            cfg.compute_dtype)
        idx_var = self.variable(
            "cache", "cache_index", lambda: jnp.zeros((), jnp.int32))
        idx = idx_var.value
        if idx.ndim != 0:
            raise ValueError(
                "the rolling prefill buffer is the batch-1 scalar-index "
                "cache of the serve loop's admission")
        base = jnp.maximum(idx - w, 0)
        shift = base - jnp.maximum(idx - c - w, 0)

        def put(var, new):
            buf = jnp.pad(var.value, ((0, 0), (0, c), (0, 0)))
            buf = jax.lax.dynamic_slice(buf, (0, shift, 0), (b, rows, flat))
            return jax.lax.dynamic_update_slice(
                buf, new.reshape(b, s, flat).astype(buf.dtype),
                (0, idx - base, 0))

        with routine("attn/cache"):
            k_all, v_all = put(cached_k, k), put(cached_v, v)
            cached_k.value, cached_v.value = k_all, v_all
            idx_var.value = idx + s
        with routine("attn/core"):
            k4 = k_all.reshape(b, rows, h_kv, d)
            v4 = v_all.reshape(b, rows, h_kv, d)
            if self.decode_attention == "flash":
                return _flash_prefill(q, k4, v4, idx, window=w,
                                      k_offset=base)
            q_pos = idx + jnp.arange(s)[:, None]                  # [s, 1]
            k_pos = base + jnp.arange(rows)[None, :]              # [1, rows]
            mask = (k_pos <= q_pos) & (q_pos - k_pos < w)
            k4, v4 = repeat_kv(q, k4, v4)
            return _masked_attend(q, k4, v4, mask[None, None])

    def _serve_attend(self, q, k, v, cached_k, cached_v, idx_var):
        """One decode step with PER-ROW cache positions: row ``r``'s K/V
        logically lives at its own ``idx[r]`` and it attends over its own
        first ``idx[r] + 1`` slots.

        With ``serve_side_slots > 0`` (the ServeLoop configuration) the
        write goes to a SEGMENT-LOCAL side buffer at a SCALAR in-segment
        index — XLA keeps scalar dynamic_update_slice chains in place,
        while per-row-indexed main-cache writes do not (neither batched
        scatters nor per-row-index DUS chains stay in place inside the
        full segment graph).  Attention then runs as ONE fused flash-decode call over
        the frozen main cache (per-row lengths) plus the side buffer's
        live positions (:meth:`_serve_attend_sided`); the ServeLoop
        scatters side → main once per segment (amortized to ~nothing).
        ``serve_side_slots == 0`` keeps the direct per-row-write path
        (simple, correct, slower)."""
        cfg = self.cfg
        b, s = q.shape[0], q.shape[1]
        idx = idx_var.value
        if self.decode_shard is not None:
            raise NotImplementedError(
                "sharded decode with per-row cache positions is not "
                "wired yet; serve through the replicated path")

        if self.serve_side_slots > 0:
            return self._serve_attend_sided(
                q, k, v, cached_k, cached_v, idx_var)

        h_kv, d = k.shape[2], k.shape[3]
        flat = h_kv * d
        at = jnp.minimum(idx, cfg.max_seq_len - s)
        k_all, v_all = cached_k.value, cached_v.value
        kf = k.reshape(b, s, flat)
        vf = v.reshape(b, s, flat)
        with routine("attn/cache"):
            for r in range(b):
                k_all = jax.lax.dynamic_update_slice(
                    k_all, kf[r:r + 1].astype(k_all.dtype), (r, at[r], 0))
                v_all = jax.lax.dynamic_update_slice(
                    v_all, vf[r:r + 1].astype(v_all.dtype), (r, at[r], 0))
            cached_k.value, cached_v.value = k_all, v_all
            idx_var.value = idx + s
        with routine("attn/core"):
            n = idx + 1  # [B] valid lengths including the current token
            if self.decode_attention == "flash" and self.window is None:
                from tpudist.ops.flash_decode import flash_decode

                return flash_decode(q, k_all, v_all, n,
                                    packed_kv_heads=h_kv)
            # NOTE: flash + attention_window falls back to the dense masked
            # path here (the per-row kernel has no per-row window trim yet) —
            # ServeLoop warns about the bandwidth cost at construction.
            positions = jnp.arange(cfg.max_seq_len)[None, None, :]  # [1,1,S]
            q_pos = idx[:, None] + jnp.arange(s)[None, :]           # [B, s]
            mask = positions < (q_pos + 1)[:, :, None]              # [B,s,S]
            if self.window is not None:
                mask = mask & (q_pos[:, :, None] - positions
                               < self.window)
            k4 = k_all.reshape(b, cfg.max_seq_len, h_kv, d)
            v4 = v_all.reshape(b, cfg.max_seq_len, h_kv, d)
            k_rep, v_rep = repeat_kv(q, k4, v4)
            return _masked_attend(q, k_rep, v_rep, mask[:, None])

    def _serve_attend_sided(self, q, k, v, cached_k, cached_v, idx_var):
        """The side-buffer serve step (see :meth:`_serve_attend`).

        ``cache_index`` stays the MAIN-cache per-row length for the whole
        segment; the side buffer's own scalar counter tracks in-segment
        tokens (every row writes the same side slot each step — admission
        only happens at segment boundaries, so side occupancy is uniform
        across rows; frozen rows write garbage that their discarded
        outputs never expose and the merge-time mask drops).

        Attention runs as ONE fused kernel call: the flash-decode kernel
        streams the frozen main cache at each row's own length and then
        attends the side buffer's live positions as a trailing grid step
        of the SAME online softmax (``flash_decode(side_k=...)``), with
        no separate dense side attend and no explicit log-sum-exp
        merge."""
        cfg = self.cfg
        b, s = q.shape[0], q.shape[1]
        cap = self.serve_side_slots
        h_kv, d = k.shape[2], k.shape[3]
        flat = h_kv * d
        side_k = self.variable(
            "cache", "side_key", jnp.zeros, (b, cap, flat),
            cfg.compute_dtype)
        side_v = self.variable(
            "cache", "side_value", jnp.zeros, (b, cap, flat),
            cfg.compute_dtype)
        side_idx = self.variable(
            "cache", "side_index", lambda: jnp.zeros((), jnp.int32))
        with routine("attn/cache"):
            s_at = jnp.minimum(side_idx.value, cap - s)
            side_k.value = jax.lax.dynamic_update_slice(
                side_k.value,
                k.reshape(b, s, flat).astype(side_k.value.dtype),
                (0, s_at, 0))
            side_v.value = jax.lax.dynamic_update_slice(
                side_v.value,
                v.reshape(b, s, flat).astype(side_v.value.dtype),
                (0, s_at, 0))
            side_idx.value = side_idx.value + s

        from tpudist.ops.flash_decode import flash_decode

        with routine("attn/core"):
            return flash_decode(
                q, cached_k.value, cached_v.value, idx_var.value,
                side_k=side_k.value, side_v=side_v.value,
                side_len=side_idx.value, packed_kv_heads=h_kv)

    def _paged_attend(self, q, k, v, index=None):
        """One decode step against the PAGED cache: K/V live in a shared
        block pool (``paged_key``/``paged_value``,
        ``[kv_num_blocks, kv_block_size, Hkv*D]``) and each slot reaches
        its logical positions through ``page_table``
        (``[B, max_blocks_per_slot]`` int32 pool indices) — slot ``b``'s
        position ``p`` is ``pool[table[b, p // bs], p % bs]``.

        Within a compiled segment the pool is FROZEN (like the dense
        sided path's main cache): the current token's K/V goes to the
        segment-local side buffer at a scalar index, and the ServeLoop's
        per-segment merge scatters side -> pool through the page table.
        That makes the side-buffer step mandatory here — there is no
        per-step paged write path (a per-row scatter through the table
        every step would re-materialize exactly the indexed-write cost
        the sided design measured and removed).

        Attention: ``decode_attention="flash"`` runs
        :func:`tpudist.ops.flash_decode.paged_flash_decode` (the dense
        kernel's online softmax over the pages a lane really holds,
        fetched by the page table's ids);
        ``"dense"`` gathers the slot's pages into a contiguous view and
        masks — the CPU/test fallback."""
        cfg = self.cfg
        b, s = q.shape[0], q.shape[1]
        h_kv, d = k.shape[2], k.shape[3]
        flat = h_kv * d
        bs_, nb = self.kv_block_size, self.kv_num_blocks
        if bs_ < 1 or nb < 1:
            raise ValueError(
                "cache_layout='paged' needs kv_block_size and "
                f"kv_num_blocks > 0 (got {bs_}, {nb})")
        m_blocks = -(-cfg.max_seq_len // bs_)
        # the leaves of a token's K and V, pool and side buffer: a pair of
        # ``flat`` columns each or, in a layer with an indexer, ONE row of
        # 32-bit words that holds both (``kv_row``: its decode step
        # gathers the chosen rows, and a gather costs by the row, least a
        # row of words), and its index keys in a further pool under the
        # same table
        from tpudist.ops.flash_decode import kv_row, pack_kv, unpack_kv

        kv_names, (kv_width, kv_dtype) = (
            (("key", "value"), (flat, cfg.compute_dtype)) if index is None
            else (("kv",), kv_row(flat, cfg.compute_dtype)))
        pools = [self.variable(
            "cache", f"paged_{name}", jnp.zeros, (nb, bs_, kv_width),
            kv_dtype) for name in kv_names]
        paged_ik = None if index is None else self.variable(
            "cache", "paged_ikey", jnp.zeros,
            (nb, bs_, cfg.index_cache_width), cfg.compute_dtype)
        table = self.variable(
            "cache", "page_table", jnp.zeros, (b, m_blocks), jnp.int32)
        idx_var = self.variable(
            "cache", "cache_index", lambda: jnp.zeros((), jnp.int32))
        idx = idx_var.value
        if idx.ndim == 0:
            if self.is_initializing():
                # init only creates the cache variables' shapes; the
                # serve loop swaps cache_index to the per-row vector
                # before any real apply
                return jnp.zeros_like(q)
            raise ValueError(
                "the paged cache decodes through per-row vector "
                "cache_index only (ServeLoop with cache_layout='paged'); "
                "scalar-index rollouts use the dense layout")
        # (prefill goes through a dense batch-1 side cache and
        # serving._insert scatters it into pages)
        _per_row_takes_one_token(s)
        if self.decode_shard is not None:
            raise NotImplementedError(
                "sharded decode over the paged cache is not wired yet; "
                "serve paged through the replicated path")
        window = self.window
        if window is not None and self.serve_side_slots > window:
            raise ValueError(
                "a windowed layer decodes over the paged cache with a side "
                "buffer no longer than its window (got "
                f"serve_side_slots={self.serve_side_slots}, "
                f"window={window})")
        if self.serve_side_slots <= 0:
            raise ValueError(
                "cache_layout='paged' requires serve_side_slots > 0 "
                "(the pool is frozen within a segment; tokens stage in "
                "the side buffer)")
        cap = self.serve_side_slots
        sides = [self.variable(
            "cache", f"side_{name}", jnp.zeros, (b, cap, kv_width),
            kv_dtype) for name in kv_names]
        side_idx = self.variable(
            "cache", "side_index", lambda: jnp.zeros((), jnp.int32))
        s_base = side_idx.value
        with routine("attn/cache"):
            s_at = jnp.minimum(s_base, cap - s)
            # the token's K and V, each to its buffer, or packed to the
            # one (its bits, in the cache's dtype: packed before any cast
            # could take a word for a number)
            news = (k, v) if index is None else (pack_kv(*(
                x.reshape(b, s, flat).astype(cfg.compute_dtype)
                for x in (k, v))),)
            for side, new in zip(sides, news):
                side.value = jax.lax.dynamic_update_slice(
                    side.value,
                    new.reshape(b, s, kv_width).astype(side.value.dtype),
                    (0, s_at, 0))
            side_idx.value = s_base + s
            if index is not None:
                q_i, w_i, k_i = index
                side_ik = self.variable(
                    "cache", "side_ikey", jnp.zeros,
                    (b, cap, cfg.index_cache_width), cfg.compute_dtype)
                side_ik.value = jax.lax.dynamic_update_slice(
                    side_ik.value, k_i.astype(side_ik.value.dtype),
                    (0, s_at, 0))

        if self.decode_attention == "flash":
            from tpudist.ops.flash_decode import paged_flash_decode

            def every_row():
                # the one pool of K and V goes in alone
                pool_v, side_v = ((None, None) if index is not None else
                                  (pools[1].value, sides[1].value))
                with routine("attn/core"):
                    return paged_flash_decode(
                        q, pools[0].value, pool_v, table.value, idx,
                        packed_kv_heads=h_kv, side_k=sides[0].value,
                        side_v=side_v, side_len=side_idx.value,
                        window=window)

            if index is None:
                return every_row()
            # some lane holds more rows than a query attends: the step's
            # lanes all take scores -> selection -> the chosen rows (a
            # lane with fewer has them all chosen)
            return jax.lax.cond(
                jnp.max(idx) + side_idx.value > cfg.index_topk,
                lambda: self._chosen_pages(
                    q, q_i[:, 0], w_i[:, 0], pools[0].value,
                    paged_ik.value, table.value, idx, sides[0].value,
                    side_ik.value, side_idx.value),
                every_row)
        # dense fallback: gather the slot's pages into a contiguous view
        # (one full-logical-cache copy per step — fine on CPU, the reason
        # the kernel exists on TPU) and mask main + side positions
        from tpudist.ops.flash_decode import paged_gather_kv

        with routine("attn/core"):
            if index is None:
                k_main, v_main = (paged_gather_kv(pool.value, table.value)
                                  for pool in pools)
                side_k, side_v = (side.value for side in sides)
            else:
                # the one pool of K and V, and its side buffer, unpacked
                k_main, v_main = unpack_kv(
                    paged_gather_kv(pools[0].value, table.value),
                    cfg.compute_dtype)
                side_k, side_v = unpack_kv(sides[0].value,
                                           cfg.compute_dtype)
            s_all = k_main.shape[1]
            live_main = jnp.arange(s_all)[None, :] < idx[:, None]
            if window is not None:
                # the query sits at idx + s_base: the side rows are all
                # inside its window (cap <= window), the pool's from idx +
                # s_base - window + 1 on
                live_main = live_main & (
                    jnp.arange(s_all)[None, :]
                    > (idx + s_base - window)[:, None])
            mask_main = jnp.broadcast_to(live_main[:, None],
                                         (b, s, s_all))        # [B, s, S']
            mask_side = jnp.broadcast_to(
                jnp.arange(cap)[None, None, :]
                < s_base + jnp.arange(s)[None, :, None] + 1,
                (b, s, cap))                                   # [B, s, cap]
            mask = jnp.concatenate([mask_main, mask_side], axis=2)
            if index is not None:
                with routine("attn/index"):
                    ikeys = jnp.concatenate(
                        [paged_gather_kv(paged_ik.value, table.value),
                         side_ik.value], axis=1)
                    mask = _index_choice(q_i, w_i, ikeys, mask,
                                         cfg.index_topk)
            k_all = jnp.concatenate([k_main, side_k], axis=1)
            v_all = jnp.concatenate([v_main, side_v], axis=1)
            k4 = k_all.reshape(b, s_all + cap, h_kv, d)
            v4 = v_all.reshape(b, s_all + cap, h_kv, d)
            k_rep, v_rep = repeat_kv(q, k4, v4)
            return _masked_attend(q, k_rep, v_rep, mask[:, None])

    def _chosen_pages(self, q, q_i, w_i, kv_pool, ik_pool, table, idx,
                      side_kv, side_ik, side_len):
        """The decode step's attention over each lane's chosen rows: index
        scores of the lane's pages on the paged walk, and of its staged
        rows (the tokens this segment decoded are candidates like any
        other) beside them; the exact top ``index_topk``; a candidate's
        column is its position for a pool row (flat row ``page x block +
        offset`` through the table) and ``rows of the table + j`` for
        staged row ``j``."""
        from tpudist.ops.flash_decode import (index_select,
                                              paged_index_scores,
                                              sparse_gqa_attend)

        cfg = self.cfg
        nb, bs_, width = kv_pool.shape
        b, cap = side_kv.shape[:2]
        reach = table.shape[1] * bs_
        with routine("attn/index"):
            main = paged_index_scores(q_i, w_i, ik_pool, table, idx)
            staged = jnp.where(
                jnp.arange(cap)[None, :] < side_len,
                _index_scores(q_i[:, None], w_i[:, None], side_ik)[:, 0],
                -jnp.inf)
            ids = index_select(jnp.concatenate([main, staged], axis=1),
                               cfg.index_topk)
            pos = jnp.minimum(ids, reach - 1)
            # a column's page out of the lane's table row by a masked sum
            # over the row (a gather of one number a chosen row costs as
            # much as the gather of the rows themselves)
            page = jnp.sum(
                jnp.where(
                    (pos // bs_)[..., None] == jnp.arange(table.shape[1]),
                    table[:, None, :], 0), axis=-1)
            rows = page * bs_ + pos % bs_
            rows = jnp.where(ids >= reach, nb * bs_ + ids - reach, rows)
        # the gather opens attn/rows and the kernel attn/core, inside
        out = sparse_gqa_attend(
            q[:, 0], kv_pool.reshape(nb * bs_, width), rows,
            jnp.minimum(jnp.minimum(idx, reach) + side_len,
                        cfg.index_topk),
            packed_kv_heads=cfg.kv_heads, side_kv=side_kv)
        return out[:, None]

    def _chosen_rows(self, q, k_all, v_all, idx, index):
        """A prefill chunk's queries at positions ``idx + [0, s)`` over
        the chosen of the batch-1 cache's rows: index scores of the rows
        at or below the chunk (the cache read in pages, several queries a
        grid row), the exact top ``index_topk`` a query as a mask, one
        masked flash pass."""
        from tpudist.ops.flash_attention import flash_chosen_rows
        from tpudist.ops.flash_decode import (block_of,
                                              index_queries_per_row,
                                              index_select_mask,
                                              paged_index_scores)

        cfg = self.cfg
        s, n = q.shape[1], k_all.shape[1]
        q_i, w_i, ikeys = index
        page = block_of(n, 8)
        tq = index_queries_per_row(s, cfg.index_heads, n)
        with routine("attn/index"):
            seen = idx + (jnp.arange(s // tq) + 1) * tq
            scores = paged_index_scores(
                q_i[0], w_i[0], ikeys[0].reshape(n // page, page, -1),
                jnp.arange(n // page)[None], seen)               # [s, n]
            mask = index_select_mask(scores, cfg.index_topk, rows=idx + s)
        with routine("attn/core"):
            return flash_chosen_rows(q, k_all, v_all, mask, idx)

    def _prefill_attend(self, q, k_all, v_all, idx, index=None):
        """Chunk prefill: queries at global positions [idx, idx+s) attend
        over the cache's first idx+s slots, causally.  The flash path
        reuses the forward kernel at ``q_offset=idx`` (its causal mask
        also silences the garbage in not-yet-written slots; dead tiles are
        pruned); the dense path builds the banded mask explicitly.  With
        an indexer a chunk that ends beyond row ``index_topk`` attends its
        chosen rows (:meth:`_chosen_rows`; the dense path by a mask)."""
        cfg = self.cfg
        s = q.shape[1]
        if index is not None and self.decode_attention == "flash":
            if self.decode_shard is not None or q.shape[0] != 1:
                raise ValueError(
                    "a model with an indexer prefills through the "
                    "replicated batch-1 cache")
            def every_row():
                with routine("attn/core"):
                    return _flash_prefill(q, k_all, v_all, idx)

            return jax.lax.cond(
                idx + s > cfg.index_topk,
                lambda: self._chosen_rows(q, k_all, v_all, idx, index),
                every_row)
        if index is not None:
            with routine("attn/index"):
                q_pos = idx + jnp.arange(s)[:, None]
                valid = jnp.broadcast_to(
                    jnp.arange(cfg.max_seq_len)[None, :] <= q_pos,
                    (q.shape[0], s, cfg.max_seq_len))
                mask = _index_choice(*index, valid, cfg.index_topk)
            with routine("attn/core"):
                k_all, v_all = repeat_kv(q, k_all, v_all)
                return _masked_attend(q, k_all, v_all, mask[:, None])
        seq_sharded = (self.decode_shard is not None
                       and _shard_kind(self.decode_shard)
                       in ("seq", "heads_seq"))
        # seq-sharded prefill stays on the dense GSPMD path below: the
        # queries attend across every shard's cache slice, which GSPMD
        # partitions into per-shard partial attention + reductions
        # (measured HLO: no cache all-gather), while a Pallas call cannot
        # be partitioned at all
        with routine("attn/core"):
            if self.decode_attention == "flash" and not seq_sharded:
                return _flash_prefill(q, k_all, v_all, idx,
                                      window=self.window,
                                      decode_shard=self.decode_shard)
            q_pos = idx + jnp.arange(s)[:, None]                  # [s, 1]
            k_pos = jnp.arange(cfg.max_seq_len)[None, :]          # [1, S]
            mask = k_pos <= q_pos
            if self.window is not None:
                mask = mask & (q_pos - k_pos < self.window)
            k_all, v_all = repeat_kv(q, k_all, v_all)
            return _masked_attend(q, k_all, v_all, mask[None, None])


# rows of the latent cache the expanded prefill rebuilds keys and values
# for: the first power-of-two multiple of this that covers the chunk's end
_MLA_PREFILL_ROWS = 1024


class _Kernel(nn.Module):
    """A bare ``kernel`` parameter under a ``nn.Dense``-like name, for a
    matrix that is applied in more than one form."""

    shape: tuple
    dtype: Any

    @nn.compact
    def __call__(self) -> jnp.ndarray:
        return self.param("kernel", nn.initializers.lecun_normal(),
                          self.shape).astype(self.dtype)


class LatentSelfAttention(nn.Module):
    """Multi-head latent attention (``cfg.mla``), two paths from one set of
    weights.

    * EXPANDED (training, and prefill through the batch-1 cache): keys and
      values are rebuilt per head from the latent rows, ``k = [k_nope |
      k_rope]`` with the one rotary key shared by all heads, and attention
      is plain MHA with q/k ``qk_head_dim`` wide and v ``v_head_dim`` wide.
    * ABSORBED (the serve loop's decode step over the paged cache): the
      same mathematics reordered, ``q_abs = q_nope W_kvb^K`` per head, so
      the cached row itself is the key and its latent part the value —
      multi-query attention of all heads against one row a token,
      ``o = (sum p c_kv) W_kvb^V``.  ``W_kvb`` is never applied per cached
      position.

    The cache holds ``[c_kv (after its norm) | k_rope (after the rotation)
    | zeros]``, ``cfg.mla.cache_width`` numbers a token, under the leaves
    ``cached_latent`` (dense, scalar index: prefill), ``paged_latent`` +
    ``page_table`` (the serve loop's pool) and ``side_latent`` (its
    segment-local staging buffer)."""

    cfg: TransformerConfig
    decode: bool = False
    decode_attention: str = "dense"
    serve_side_slots: int = 0
    cache_layout: str = "dense"
    kv_num_blocks: int = 0
    kv_block_size: int = 0

    @property
    def softmax_scale(self) -> float:
        cfg = self.cfg
        scale = cfg.mla.qk_head_dim ** -0.5
        if cfg.rope_scaling is not None:
            m = _yarn_mscale(cfg.rope_scaling.factor,
                             cfg.rope_scaling.mscale_all_dim)
            scale *= m * m
        return scale

    @nn.compact
    def __call__(self, x: jnp.ndarray, *, causal: bool = True,
                 positions: Optional[jnp.ndarray] = None) -> jnp.ndarray:
        cfg, m = self.cfg, self.cfg.mla
        b, s, _ = x.shape
        h, nope, dv = cfg.num_heads, m.qk_nope_head_dim, m.v_head_dim
        dt = cfg.compute_dtype
        if cfg.attention_window is not None:
            raise ValueError("latent attention has no sliding window")
        if positions is None:
            positions = jnp.arange(s)[None, :]
        dense = functools.partial(nn.Dense, use_bias=False, dtype=dt)
        norm = functools.partial(nn.RMSNorm, epsilon=cfg.norm_eps, dtype=dt)
        with routine("attn/proj"):
            c_q = norm(name="q_norm")(dense(m.q_lora_rank, name="q_a")(x))
            q = dense(h * m.qk_head_dim, name="q_b")(c_q).reshape(
                b, s, h, m.qk_head_dim)
            q_nope, q_rope = q[..., :nope], apply_rope(q[..., nope:],
                                                       positions, cfg)
            kv_a = dense(m.kv_lora_rank + m.qk_rope_head_dim,
                         name="kv_a")(x)
            c_kv = norm(name="kv_norm")(kv_a[..., : m.kv_lora_rank])
            k_rope = apply_rope(kv_a[..., m.kv_lora_rank:], positions, cfg)
            pad = m.cache_width - m.kv_lora_rank - m.qk_rope_head_dim
            row = jnp.concatenate(
                [c_kv, k_rope, jnp.zeros((b, s, pad), c_kv.dtype)], -1)
            # [kv_lora, H, nope | v]: applied to latent rows (expanded) or
            # to the queries and the attended latent (absorbed)
            w_kvb = _Kernel(
                (m.kv_lora_rank, h * (nope + dv)), dt,
                name="kv_b")().reshape(m.kv_lora_rank, h, nope + dv)

        if not self.decode:
            out = self._expanded(q_nope, q_rope, row, w_kvb, 0, causal)
        elif self.cache_layout == "paged":
            out = self._paged_absorbed(q_nope, q_rope, row, w_kvb)
        elif self.cache_layout == "dense":
            out = self._cached_expanded(q_nope, q_rope, row, w_kvb)
        else:
            raise ValueError(
                f"cache_layout must be 'dense' or 'paged', got "
                f"{self.cache_layout!r}")
        with routine("attn/proj"):
            return dense(cfg.embed_dim, name="proj")(
                out.reshape(b, s, h * dv))

    def _expanded(self, q_nope, q_rope, rows, w_kvb, idx, causal=True):
        """MHA of queries at positions ``idx + [0, s)`` over the latent
        ``rows [B, R, W]``, keys and values rebuilt from them."""
        cfg, m = self.cfg, self.cfg.mla
        b, r, _ = rows.shape
        h, nope = cfg.num_heads, m.qk_nope_head_dim
        with routine("attn/proj"):
            kv = jnp.einsum("brc,chd->brhd", rows[..., : m.kv_lora_rank],
                            w_kvb)
            k_rope = rows[..., m.kv_lora_rank:
                          m.kv_lora_rank + m.qk_rope_head_dim]
            k = jnp.concatenate(
                [kv[..., :nope],
                 jnp.broadcast_to(k_rope[:, :, None, :],
                                  (b, r, h, m.qk_rope_head_dim))], -1)
            v = kv[..., nope:]
            q = jnp.concatenate([q_nope, q_rope], -1)
        with routine("attn/core"):
            if self.decode and self.decode_attention == "flash":
                return _flash_prefill(q, k, v, idx, scale=self.softmax_scale)
            mask = None
            if causal:
                q_pos = idx + jnp.arange(q.shape[1])[:, None]
                mask = (jnp.arange(r)[None, :] <= q_pos)[None, None]
            return _masked_attend(q, k, v, mask, scale=self.softmax_scale)

    def _cached_expanded(self, q_nope, q_rope, row, w_kvb):
        """Prefill (and scalar-index rollouts) through a dense latent
        cache: the chunk's rows land at the write cursor and the queries
        attend, expanded, over everything cached so far — rebuilt from the
        first rows of the cache that cover the chunk's end, in steps of
        ``_MLA_PREFILL_ROWS`` doubled, and not from all ``max_seq_len``."""
        cfg = self.cfg
        b, s = row.shape[:2]
        S = cfg.max_seq_len
        cached = self.variable(
            "cache", "cached_latent", jnp.zeros,
            (b, S, cfg.mla.cache_width), cfg.compute_dtype)
        idx_var = self.variable(
            "cache", "cache_index", lambda: jnp.zeros((), jnp.int32))
        idx = idx_var.value
        if idx.ndim != 0:
            raise ValueError(
                "latent attention serves per-row positions through "
                "cache_layout='paged' only")
        with routine("attn/cache"):
            rows = jax.lax.dynamic_update_slice(
                cached.value, row.astype(cached.value.dtype), (0, idx, 0))
            cached.value = rows
            idx_var.value = idx + s
        buckets = []
        r = min(S, _MLA_PREFILL_ROWS)
        while r < S:
            if r >= s:
                buckets.append(r)
            r *= 2
        buckets.append(S)
        branches = [
            lambda qn, qr, rw, w, i, n=n: self._expanded(
                qn, qr, rw[:, :n], w, i)
            for n in buckets]
        if len(branches) == 1:
            return branches[0](q_nope, q_rope, rows, w_kvb, idx)
        which = sum((idx + s > n).astype(jnp.int32) for n in buckets[:-1])
        return jax.lax.switch(which, branches, q_nope, q_rope, rows, w_kvb,
                              idx)

    def _paged_absorbed(self, q_nope, q_rope, row, w_kvb):
        """One decode step against the PAGED latent cache (see
        ``CausalSelfAttention._paged_attend`` for the pool, the page table
        and the side buffer: the same arrangement with one leaf)."""
        cfg, m = self.cfg, self.cfg.mla
        b, s = row.shape[:2]
        w = m.cache_width
        nope, lat = m.qk_nope_head_dim, m.kv_lora_rank
        bs_, nb = self.kv_block_size, self.kv_num_blocks
        if bs_ < 1 or nb < 1:
            raise ValueError(
                "cache_layout='paged' needs kv_block_size and "
                f"kv_num_blocks > 0 (got {bs_}, {nb})")
        m_blocks = -(-cfg.max_seq_len // bs_)
        pool = self.variable(
            "cache", "paged_latent", jnp.zeros, (nb, bs_, w),
            cfg.compute_dtype)
        table = self.variable(
            "cache", "page_table", jnp.zeros, (b, m_blocks), jnp.int32)
        idx_var = self.variable(
            "cache", "cache_index", lambda: jnp.zeros((), jnp.int32))
        idx = idx_var.value
        if idx.ndim == 0:
            if self.is_initializing():
                return jnp.zeros(q_nope.shape[:3] + (m.v_head_dim,),
                                 q_nope.dtype)
            raise ValueError(
                "the paged cache decodes through per-row vector "
                "cache_index only (ServeLoop with cache_layout='paged')")
        if s != 1:
            raise NotImplementedError(
                "the paged latent cache decodes one token a step")
        if self.serve_side_slots <= 0:
            raise ValueError(
                "cache_layout='paged' requires serve_side_slots > 0")
        cap = self.serve_side_slots
        side = self.variable(
            "cache", "side_latent", jnp.zeros, (b, cap, w),
            cfg.compute_dtype)
        side_idx = self.variable(
            "cache", "side_index", lambda: jnp.zeros((), jnp.int32))
        s_base = side_idx.value
        with routine("attn/cache"):
            side.value = jax.lax.dynamic_update_slice(
                side.value, row.astype(side.value.dtype),
                (0, jnp.minimum(s_base, cap - 1), 0))
            side_idx.value = s_base + 1

        with routine("attn/proj"):
            # absorb W_kvb^K into the queries: [B, H, nope] x [lat, H, nope]
            q_abs = jnp.einsum("bhd,chd->bhc", q_nope[:, 0],
                               w_kvb[..., :nope])
            q_row = jnp.concatenate(
                [q_abs, q_rope[:, 0],
                 jnp.zeros((b, cfg.num_heads, w - lat - m.qk_rope_head_dim),
                           q_abs.dtype)], -1)               # [B, H, W]
        if self.decode_attention == "flash":
            from tpudist.ops.flash_decode import paged_mla_decode

            with routine("attn/core"):
                o_lat = paged_mla_decode(
                    q_row, pool.value, table.value, idx, d_v=lat,
                    scale=self.softmax_scale, side=side.value,
                    side_len=side_idx.value)
        else:
            # dense fallback (CPU / tests): gather the lane's pages and
            # mask pool + side positions
            from tpudist.ops.flash_decode import paged_gather_kv

            with routine("attn/core"):
                main = paged_gather_kv(pool.value, table.value)
                keys = jnp.concatenate([main, side.value], axis=1)
                mask = jnp.concatenate(
                    [jnp.arange(main.shape[1])[None, :] < idx[:, None],
                     jnp.broadcast_to(jnp.arange(cap)[None, :] < s_base + 1,
                                      (b, cap))], axis=1)
                logits = jnp.einsum(
                    "bhw,bkw->bhk", q_row, keys,
                    preferred_element_type=jnp.float32) * self.softmax_scale
                logits = jnp.where(mask[:, None, :], logits,
                                   jnp.finfo(jnp.float32).min)
                probs = jax.nn.softmax(logits, axis=-1).astype(keys.dtype)
                o_lat = jnp.einsum("bhk,bkc->bhc", probs, keys[..., :lat])
        with routine("attn/proj"):
            out = jnp.einsum("bhc,chd->bhd", o_lat, w_kvb[..., nope:])
            return out[:, None]


class LinearAttention(nn.Module):
    """A gated delta-rule layer: the layer of the public
    ``flash-linear-attention`` ``GatedDeltaNet``.  From the block's input
    ``u``: ``q, k, v = SiLU(conv(u W))`` (one projection, a causal
    depthwise convolution a channel, no bias); a head's ``q`` and ``k``
    scaled to unit length, ``q`` by ``key_dim ** -0.5`` besides; ``beta =
    sigmoid(u Wb)`` (doubled under ``neg_eigval``), ``log alpha = -exp(A_log)
    * softplus(u Wa + dt_bias)``, both a number a head, float32; the state
    through :mod:`tpudist.ops.delta_rule`; ``y = Wo[RMSNorm(o) * SiLU(u
    Wg)]``, the norm a head.

    With ``decode`` its past lives in two cache leaves that are NOT rows:
    ``state [B, key_dim, heads * value_dim]`` float32 (the heads' matrices
    side by side: ``ops.delta_rule`` says why) and ``conv [B, (conv_width -
    1) * channels]`` (the last inputs of the convolution, oldest first, side
    by side).  One token a call takes the recurrent step, several the chunk
    form.  ``valid [B, S]`` (None: every token): the tokens that count, a
    PREFIX of each row; the rest move neither leaf."""

    cfg: TransformerConfig
    decode: bool = False

    @nn.compact
    def __call__(self, x: jnp.ndarray, *,
                 valid: Optional[jnp.ndarray] = None) -> jnp.ndarray:
        from tpudist.ops import delta_rule as dr

        cfg, lin = self.cfg, self.cfg.linear
        f32 = jnp.float32
        b, s, _ = x.shape
        h, dk, dv = lin.num_heads, lin.key_dim, lin.value_dim
        taps, chans = lin.conv_width, lin.conv_channels
        dense = functools.partial(nn.Dense, use_bias=False,
                                  dtype=cfg.compute_dtype)
        with routine("linear_attn"):
            mixed = dense(chans, name="qkv")(x)
            gate = dense(h * dv, name="gate")(x)
            # the decay's and the write strength's inputs: float32 sums of
            # bf16-valued products
            ab = nn.Dense(2 * h, use_bias=False, dtype=f32, name="ab")(x)
            w = self.param("conv", nn.initializers.lecun_normal(),
                           (taps, chans))
            a_log = self.param(
                "A_log", lambda key, shape: jnp.log(jax.random.uniform(
                    key, shape, f32, 1.0, 16.0)), (h,))

            def dt_init(key, shape):
                dt = jnp.exp(jax.random.uniform(
                    key, shape, f32, jnp.log(1e-3), jnp.log(1e-1)))
                return dt + jnp.log(-jnp.expm1(-dt))    # softplus^-1

            dt_bias = self.param("dt_bias", dt_init, (h,))
            tail_var = state_var = None
            if self.decode:
                tail_var = self.variable(
                    "cache", "conv", jnp.zeros, (b, (taps - 1) * chans),
                    cfg.compute_dtype)
                state_var = self.variable(
                    "cache", "state", jnp.zeros, (b, dk, h * dv), f32)
                tail = tail_var.value.reshape(b, taps - 1, chans)
            else:
                tail = jnp.zeros((b, taps - 1, chans), mixed.dtype)
            seen = jnp.concatenate([tail, mixed.astype(tail.dtype)], axis=1)
            conv = sum(w[j].astype(f32) * seen[:, j:j + s].astype(f32)
                       for j in range(taps))
            conv = nn.silu(conv)
            if tail_var is not None:
                # the last inputs that count: rows [n, n + taps - 1) of
                # tail + chunk, n the valid tokens (none: the tail stays)
                if valid is None:
                    kept = seen[:, s:]
                else:
                    n = jnp.sum(valid.astype(jnp.int32), axis=1)
                    kept = jax.vmap(lambda row, at: jax.lax.dynamic_slice_in_dim(
                        row, at, taps - 1, axis=0))(seen, n)
                tail_var.value = kept.reshape(b, -1)

            def unit(t):
                return t * jax.lax.rsqrt(
                    jnp.sum(t * t, axis=-1, keepdims=True) + 1e-6)

            q = unit(conv[..., :h * dk].reshape(b, s, h, dk)) * dk ** -0.5
            k = unit(conv[..., h * dk:2 * h * dk].reshape(b, s, h, dk))
            v = conv[..., 2 * h * dk:].reshape(b, s, h, dv)
            g = -jnp.exp(a_log.astype(f32)) * jax.nn.softplus(
                ab[..., :h] + dt_bias.astype(f32))
            beta = jax.nn.sigmoid(ab[..., h:]) * (
                2.0 if lin.neg_eigval else 1.0)
            if state_var is not None and s == 1:
                o, state_var.value = dr.gated_delta_step(
                    q[:, 0], k[:, 0], v[:, 0], g[:, 0], beta[:, 0],
                    state_var.value,
                    None if valid is None else valid[:, 0])
                o = o[:, None]
            else:
                state = (jnp.zeros((b, h, dk, dv), f32) if state_var is None
                         else dr.state_to_heads(state_var.value, h))
                o, state = dr.gated_delta_chunk(q, k, v, g, beta, state,
                                                valid)
                if state_var is not None:
                    state_var.value = dr.state_from_heads(state)
            o = nn.RMSNorm(epsilon=cfg.norm_eps, dtype=f32,
                           name="o_norm")(o)
            o = o * nn.silu(gate.reshape(b, s, h, dv).astype(f32))
            return dense(cfg.embed_dim, name="out")(
                o.reshape(b, s, h * dv).astype(cfg.compute_dtype))


# The dense MLP's pre-activation: what its ``ffn_dim``-wide products (``up``,
# and ``gate`` in the gated form) give, named so that a ``jax.checkpoint``
# policy can keep it (see _remat_block).  The activation is elementwise on it
# and is redone.  Outside ``jax.checkpoint`` the name lowers to its operand.
MLP_PRE_NAME = "mlp_pre"


class MLPBlock(nn.Module):
    cfg: TransformerConfig

    @nn.compact
    def __call__(self, x: jnp.ndarray) -> jnp.ndarray:
        cfg = self.cfg
        dense = functools.partial(nn.Dense, use_bias=False,
                                  dtype=cfg.compute_dtype)
        with routine("mlp/dense"):
            h = checkpoint_name(dense(cfg.ffn_dim, name="up")(x),
                                MLP_PRE_NAME)
            if cfg.mlp == "gelu":
                h = nn.gelu(h)
            elif cfg.mlp == "gated_silu":
                gate = checkpoint_name(dense(cfg.ffn_dim, name="gate")(x),
                                       MLP_PRE_NAME)
                h = nn.silu(gate) * h
            else:
                raise ValueError(f"mlp must be 'gelu' or 'gated_silu', got "
                                 f"{cfg.mlp!r}")
            return dense(cfg.embed_dim, name="down")(h)


class DecoderBlock(nn.Module):
    cfg: TransformerConfig
    attention_fn: AttentionFn = sdpa
    decode: bool = False
    decode_attention: str = "dense"
    decode_shard: Any = None
    serve_side_slots: int = 0
    cache_layout: str = "dense"
    kv_num_blocks: int = 0
    kv_block_size: int = 0

    # an expert layer (cfg.moe) in place of the dense MLP
    expert_layer: bool = False
    # see CausalSelfAttention
    layer: int | None = None
    prefill_window_rows: int = 0

    @nn.compact
    def __call__(self, x: jnp.ndarray, causal: bool = True,
                 positions: Optional[jnp.ndarray] = None,
                 valid: Optional[jnp.ndarray] = None) -> jnp.ndarray:
        # NOTE: ``causal`` is positional (arg 2) so nn.remat can mark it
        # static (static_argnums) — keyword args would be traced.
        cfg = self.cfg
        linear = cfg.layer_kind(self.layer) == "linear"
        post = cfg.norm_order == "post"
        parallel = cfg.norm_order == "parallel"
        # a block's norm counts to the routine that reads it first (under
        # "post": that wrote what it reads)
        mixer_scope = "linear_attn" if linear else "attn/proj"
        mlp_scope = "mlp/route" if self.expert_layer else "mlp/dense"
        h = x
        if not post:
            with routine(mixer_scope):
                h = make_norm(cfg, "ln1")(x)
        cache_kw = dict(decode=self.decode,
                        decode_attention=self.decode_attention,
                        serve_side_slots=self.serve_side_slots,
                        cache_layout=self.cache_layout,
                        kv_num_blocks=self.kv_num_blocks,
                        kv_block_size=self.kv_block_size, name="attn")
        if linear:
            if not causal:
                raise ValueError("a linear-attention layer is a recurrence "
                                 "over the tokens in order: causal only")
            if self.decode_shard is not None:
                raise NotImplementedError(
                    "a linear-attention layer has no sharded decode yet")
            y = LinearAttention(cfg, decode=self.decode,
                                name="linear_attn")(h, valid=valid)
        elif cfg.mla is not None:
            if self.decode_shard is not None:
                raise NotImplementedError(
                    "latent attention has no sharded decode yet")
            if any(w is not None for w in cfg.windows):
                raise ValueError("latent attention has no sliding window")
            y = LatentSelfAttention(cfg, **cache_kw)(
                h, causal=causal, positions=positions)
        else:
            y = CausalSelfAttention(
                cfg, self.attention_fn, decode_shard=self.decode_shard,
                layer=self.layer,
                prefill_window_rows=self.prefill_window_rows, **cache_kw)(
                h, causal=causal, positions=positions)
        if post:
            with routine(mixer_scope):
                y = make_norm(cfg, "ln1")(y)
        if parallel:
            # the expert layer / MLP reads the SAME h; no second norm, and
            # the one add below takes both
            attn_out = y
        else:
            x = x + y
            h = x
            if not post:
                with routine(mlp_scope):
                    h = make_norm(cfg, "ln2")(x)
        if not self.expert_layer:
            y = MLPBlock(cfg, name="mlp")(h)
        else:
            from tpudist.models.moe import MoEMLP

            b, s, d = h.shape
            y, _ = MoEMLP(d_model=d, d_ff=cfg.moe.d_ff or cfg.ffn_dim,
                          moe=cfg.moe, dtype=cfg.compute_dtype,
                          name="moe")(h.reshape(b * s, d))
            y = y.reshape(b, s, d)
        if post:
            with routine(mlp_scope):
                y = make_norm(cfg, "ln2")(y)
        if parallel:
            return x + attn_out + y
        return x + y


def _remat_block():
    """:class:`DecoderBlock` under ``nn.remat``, for both layer layouts:
    the backward pass recomputes a block from its input, except what a
    whole product or kernel call would have to give back, which is kept by
    name: the two residuals of its flash-attention kernel (the output and
    the log-sum-exp: about one more block input, ``B x S x E`` in the
    compute dtype plus 1/64 of it) and the dense MLP's pre-activation
    (:data:`MLP_PRE_NAME`: ``ffn_dim / embed_dim`` block inputs, twice that
    in the gated form).  Everything elementwise on them (the activation,
    the norms) and the narrow projections are redone.  A layer observes
    its own type: another attention function carries no flash names and an
    expert layer no MLP name (its grouped product rematerializes by its
    own ``custom_vjp``), so those parts are recomputed whole."""
    from tpudist.ops.flash_attention import FLASH_RESIDUALS

    kept = (*FLASH_RESIDUALS, MLP_PRE_NAME)
    # called where a program that uses it is traced, once a program: the
    # step builders see only a loss function
    obs.gauge("train/remat_kept_names").set(float(len(kept)))
    return nn.remat(
        DecoderBlock, static_argnums=(2,),
        policy=jax.checkpoint_policies.save_only_these_names(*kept))


class _ScanBody(nn.Module):
    """One scanned step of the layer stack: wraps :class:`DecoderBlock`
    with the ``(carry, x) -> (carry, y)`` signature ``nn.scan`` expects.
    ``causal`` rides as a static attribute (it must not be traced)."""

    cfg: TransformerConfig
    attention_fn: AttentionFn
    decode: bool
    decode_attention: str
    decode_shard: Any
    causal: bool
    remat: bool

    @nn.compact
    def __call__(self, x, _):
        blk = _remat_block() if self.remat else DecoderBlock
        x = blk(self.cfg, self.attention_fn, decode=self.decode,
                decode_attention=self.decode_attention,
                decode_shard=self.decode_shard,
                name="block")(x, self.causal)
        return x, None


def stack_layer_params(params, num_layers: int):
    """Convert unrolled-layout params (``block{i}/...``) to the
    ``scan_layers`` layout (``blocks/block/...`` with a leading layer
    axis) — e.g. to serve a model trained unrolled through a scanned
    rollout.  Non-block leaves pass through unchanged."""
    out = {k: v for k, v in params.items() if not k.startswith("block")}
    blocks = [params[f"block{i}"] for i in range(num_layers)]
    out["blocks"] = {
        "block": jax.tree.map(lambda *xs: jnp.stack(xs), *blocks)}
    return out


def unstack_layer_params(params, num_layers: int):
    """Inverse of :func:`stack_layer_params`."""
    out = {k: v for k, v in params.items() if k != "blocks"}
    stacked = params["blocks"]["block"]
    for i in range(num_layers):
        out[f"block{i}"] = jax.tree.map(lambda x: x[i], stacked)
    return out


class TransformerLM(nn.Module):
    """Decoder-only LM: tokens [B, S] int32 -> logits [B, S, vocab] f32.

    With ``decode=True`` the attention layers keep a KV cache in the flax
    ``cache`` collection and expect one token per call — see
    :func:`tpudist.models.generate.greedy_generate`.
    """

    cfg: TransformerConfig
    attention_fn: AttentionFn = sdpa
    decode: bool = False
    remat: bool = False
    decode_attention: str = "dense"
    decode_shard: Any = None
    serve_side_slots: int = 0
    cache_layout: str = "dense"
    kv_num_blocks: int = 0
    kv_block_size: int = 0
    # the paged pool of a WINDOWED layer (cfg.layer_window(i) not None)
    # where it is not kv_num_blocks: the serve loop's window block group
    kv_window_blocks: int = 0
    # see CausalSelfAttention
    prefill_window_rows: int = 0

    @nn.compact
    def __call__(
        self,
        tokens: jnp.ndarray,
        *,
        causal: bool = True,
        positions: Optional[jnp.ndarray] = None,
        valid: Optional[jnp.ndarray] = None,
    ) -> jnp.ndarray:
        """``valid [B, S]`` bool (None: every token): the tokens that
        count, a prefix of each row.  Read by the layers whose past is a
        STATE (``layer_kinds`` "linear"), which a token that does not count
        must not move; a cache of rows takes such a token as a row nobody
        reads."""
        cfg = self.cfg
        if positions is None:
            positions = jnp.arange(tokens.shape[1])[None, :]
        embed = nn.Embed(cfg.vocab_size, cfg.embed_dim,
                         dtype=cfg.compute_dtype, name="tok_embed")
        x = embed(tokens)
        if cfg.positions == "learned":
            x = x + nn.Embed(cfg.max_seq_len, cfg.embed_dim,
                             dtype=cfg.compute_dtype,
                             name="pos_embed")(positions)
        elif cfg.positions not in ("rotary", "none"):
            raise ValueError(f"positions must be 'learned', 'rotary' or "
                             f"'none', got {cfg.positions!r}")
        # remat: recompute each block's activations in backward instead of
        # storing them — the jax.checkpoint memory/FLOPs trade that makes
        # long-context training fit in HBM.  What is kept a layer is the
        # block's input, where the attention is the flash kernel its
        # output and log-sum-exp (about a second block input), and the
        # dense MLP's pre-activation (ffn_dim / embed_dim block inputs,
        # twice that in the gated form): 6 block inputs a layer at ffn_dim
        # = 4 x embed_dim in the gelu form, 2 x ffn_dim / embed_dim + 2 in
        # the gated one, where no remat holds about 30.  Each would cost a
        # whole kernel call or product to give back (see _remat_block).
        # ONE policy, no option: a step that does not fit is refused by
        # the compiler at its first compile, in the compiler's words.
        # Default prevent_cse=True: under plain jit XLA could otherwise
        # CSE the recomputation back into the stored forward and silently
        # undo the memory savings.
        if cfg.scan_layers:
            if (cfg.moe is not None or cfg.positions == "rotary"
                    or len(set(cfg.windows)) > 1):
                raise ValueError(
                    "scan_layers stacks identical blocks that take no "
                    "positions: expert layers, rotary positions and "
                    "windows that differ by layer need the unrolled "
                    "layout")
            if self.serve_side_slots:
                raise ValueError(
                    "serve_side_slots requires the unrolled layout "
                    "(scan_layers=False); serving normalizes via "
                    "serving_layout / auto_unstack")
            if self.cache_layout != "dense":
                raise ValueError(
                    "cache_layout='paged' requires the unrolled layout "
                    "(scan_layers=False), same as serve_side_slots")
            scanned = nn.scan(
                _ScanBody,
                variable_axes={"params": 0, "cache": 0},
                split_rngs={"params": True},
                length=cfg.num_layers,
            )
            x, _ = scanned(cfg, self.attention_fn, self.decode,
                           self.decode_attention, self.decode_shard,
                           causal, self.remat, name="blocks")(x, None)
        else:
            block_cls = _remat_block() if self.remat else DecoderBlock
            for i in range(cfg.num_layers):
                windowed = cfg.layer_window(i) is not None
                x = block_cls(cfg, self.attention_fn, decode=self.decode,
                              decode_attention=self.decode_attention,
                              decode_shard=self.decode_shard,
                              serve_side_slots=self.serve_side_slots,
                              cache_layout=self.cache_layout,
                              kv_num_blocks=(
                                  self.kv_window_blocks
                                  if windowed and self.kv_window_blocks
                                  else self.kv_num_blocks),
                              kv_block_size=self.kv_block_size,
                              expert_layer=cfg.is_expert_layer(i),
                              # only a model whose layers differ tells
                              # them apart: every other stack is built of
                              # the blocks it always was
                              layer=(i if cfg.layer_windows is not None
                                     or cfg.layer_kinds is not None
                                     else None),
                              prefill_window_rows=self.prefill_window_rows,
                              name=f"block{i}")(
                    x, causal, positions,
                    *(() if valid is None else (valid,)))
        with routine("head"):
            x = make_norm(cfg, "ln_f")(x)
            if cfg.tie_embeddings:
                logits = embed.attend(x)
            else:
                logits = nn.Dense(cfg.vocab_size, use_bias=False,
                                  dtype=cfg.compute_dtype, name="lm_head")(x)
            if cfg.logit_scale != 1.0:
                logits = logits * jnp.asarray(cfg.logit_scale, logits.dtype)
            return logits.astype(jnp.float32)
