"""Mixture-of-Experts layers — the expert-parallel workload of the zoo.

Absent from the reference (SURVEY.md §2.3: "EP — NO"), added so the
framework covers the full parallelism alphabet.  The design follows the
GShard/Switch dense-dispatch formulation, which is the TPU-idiomatic one:
routing is expressed as einsums against a static-shaped one-hot dispatch
tensor (no gather/scatter, no dynamic shapes), so the whole layer lowers to
MXU matmuls, and sharding the expert dimension over an ``expert`` mesh axis
turns the two dispatch einsums into the all-to-alls of expert parallelism
(see :mod:`tpudist.parallel.expert_parallel`).

Capacity semantics: each expert processes at most ``capacity`` tokens per
batch (``capacity_factor × tokens/num_experts``); overflow tokens are
dropped from that expert's contribution (their combine weight is zero), the
residual connection carries them through — standard Switch behavior.
"""

from __future__ import annotations

import dataclasses

import flax.linen as nn
import jax
import jax.numpy as jnp

from tpudist.obs.spans import routine
from tpudist.models.transformer import (
    AttentionFn,
    CausalSelfAttention,
    TransformerConfig,
    sdpa,
)


@dataclasses.dataclass(frozen=True)
class MoEConfig:
    num_experts: int = 8
    top_k: int = 2
    capacity_factor: float = 2.0
    aux_loss_weight: float = 1e-2
    # "einsum": the GShard [T, E, C] one-hot dispatch/combine (capacity-
    # bounded, drops overflow tokens; the formulation EP's all-to-all
    # transports).  "ragged": sorted dispatch + jax.lax.ragged_dot grouped
    # matmuls — no [T, E, C] einsums (which at small E cost MORE FLOPs
    # than the experts themselves), no capacity, no token dropping.  "fused": the ragged layout through
    # the Pallas grouped-matmul kernel (tpudist.ops.moe_dispatch) — both
    # expert matmuls in one kernel, the [T·k, f] intermediate resident in
    # VMEM.  Both non-einsum paths are single-shard only (ep_axis needs
    # the block layout).
    dispatch: str = "einsum"
    # "fused" only: slot rows per kernel block.  Group starts pad to
    # this, wasting up to E*fused_block_rows rows of expert FLOPs — at
    # small token counts (decode-time MoE) shrink it or use "ragged".
    fused_block_rows: int = 128
    # -- the expert layer's vocabulary.  "gelu" experts are the up/down
    # MLPs above, dispatched as ``dispatch`` says.  "gated_silu" experts
    # (down(silu(gate x) * up x)) take the served path: sorted dispatch
    # and the gated grouped product of tpudist.ops.moe_dispatch, which
    # picks its own row block — ``dispatch`` / ``fused_block_rows`` /
    # ``capacity_factor`` are not read there, and no token is dropped.
    experts: str = "gelu"
    d_ff: int | None = None            # expert width (None: the block's)
    scoring: str = "softmax"           # | "sigmoid"
    # group-limited routing: experts in n_group groups, a group scored by
    # the sum of its two best, topk_group groups eligible
    n_group: int = 1
    topk_group: int = 1
    routed_scale: float = 1.0          # after renormalisation
    # a per-expert bias added to the scores for the CHOICE only (the
    # weights stay the raw scores): aux-loss-free balancing
    correction_bias: bool = False
    n_shared: int = 0                  # shared experts every token passes
    # what the n_shared experts' outputs add to the routed sum: their "sum"
    # or their "mean" (the sum times 1 / n_shared)
    shared_combine: str = "sum"
    # (first, count): the routed experts THIS holder has, of num_experts.
    # Routing is over all num_experts; compute over the held ones, and a
    # choice held elsewhere adds nothing here (its holder adds it)
    held: tuple[int, int] | None = None


def _gate_choices(gates: jnp.ndarray, top_k: int):
    """Shared routing head: top-k expert choices with renormalised gate
    mass + the Switch load-balancing aux loss."""
    e = gates.shape[-1]
    top_vals, top_idx = jax.lax.top_k(gates, top_k)
    top_vals = top_vals / jnp.maximum(
        jnp.sum(top_vals, axis=-1, keepdims=True), 1e-9)
    frac_tokens = jnp.mean(
        jax.nn.one_hot(top_idx[:, 0], e, dtype=gates.dtype), axis=0)
    mean_gates = jnp.mean(gates, axis=0)
    aux = jnp.sum(frac_tokens * mean_gates) * e
    return top_vals, top_idx, aux


def route(logits: jnp.ndarray, bias: jnp.ndarray | None, moe: MoEConfig):
    """The router's choices from float32 ``logits [T, E]``: ``(weights
    [T, k], experts [T, k])``.  Scores are a softmax or a sigmoid; the
    correction ``bias`` moves the choice and not the weight; with groups,
    only the ``topk_group`` groups with the best two-expert sums are
    eligible; the weights are the SCORES at the chosen experts,
    renormalised (``+ 1e-20``) and scaled."""
    t, e = logits.shape
    logits = logits.astype(jnp.float32)
    if moe.scoring == "sigmoid":
        scores = jax.nn.sigmoid(logits)
    elif moe.scoring == "softmax":
        scores = jax.nn.softmax(logits, axis=-1)
    else:
        raise ValueError(f"scoring must be 'softmax' or 'sigmoid', got "
                         f"{moe.scoring!r}")
    choice = scores if bias is None else scores + bias.astype(jnp.float32)
    if moe.n_group > 1:
        per_group = choice.reshape(t, moe.n_group, e // moe.n_group)
        group_score = jnp.sum(jax.lax.top_k(per_group, 2)[0], axis=-1)
        _, keep = jax.lax.top_k(group_score, moe.topk_group)
        group_ok = jnp.sum(jax.nn.one_hot(keep, moe.n_group,
                                          dtype=jnp.int32), axis=1) > 0
        choice = jnp.where(jnp.repeat(group_ok, e // moe.n_group, axis=1),
                           choice, -jnp.inf)
    _, experts = jax.lax.top_k(choice, moe.top_k)
    w = jnp.take_along_axis(scores, experts, axis=1)
    w = w / (jnp.sum(w, axis=-1, keepdims=True) + 1e-20) * moe.routed_scale
    return w, experts


class GatedMLP(nn.Module):
    """``down(silu(gate x) * up x)``, no biases."""

    d_model: int
    d_ff: int
    dtype: jnp.dtype | None = None

    @nn.compact
    def __call__(self, x: jnp.ndarray) -> jnp.ndarray:
        dense = lambda n, name: nn.Dense(  # noqa: E731
            n, use_bias=False, dtype=self.dtype, name=name)
        h = nn.silu(dense(self.d_ff, "gate")(x)) * dense(self.d_ff, "up")(x)
        return dense(self.d_model, "down")(h)


def _top_k_routing(gates: jnp.ndarray, top_k: int, capacity: int):
    """GShard routing: from router probabilities ``gates [T, E]`` build

    * ``dispatch [T, E, C]`` — one-hot: token t goes to expert e at slot c,
    * ``combine  [T, E, C]`` — dispatch weighted by the (renormalised) gate,
    * ``aux`` — the load-balancing loss (mean fraction·mean gate × E²).
    """
    t, e = gates.shape
    # [T, k] indices of the chosen experts, gate mass renormalised over them.
    top_vals, top_idx, aux = _gate_choices(gates, top_k)

    dispatch = jnp.zeros((t, e, capacity), gates.dtype)
    combine = jnp.zeros((t, e, capacity), gates.dtype)
    # Slots are assigned in token order per expert, k-th choices after the
    # (k-1)-th (Switch/GShard priority), tracked by a running per-expert count.
    counts = jnp.zeros((e,), jnp.int32)
    for k in range(top_k):
        onehot = jax.nn.one_hot(top_idx[:, k], e, dtype=jnp.int32)  # [T, E]
        pos = counts[None, :] + jnp.cumsum(onehot, axis=0) - onehot  # slot idx
        keep = (pos < capacity) & (onehot > 0)
        slot = jax.nn.one_hot(pos, capacity, dtype=gates.dtype)      # [T, E, C]
        sel = slot * keep[..., None].astype(gates.dtype)
        dispatch = dispatch + sel
        combine = combine + sel * top_vals[:, k, None, None]
        counts = counts + jnp.sum(onehot, axis=0)

    return dispatch, combine, aux


def _counting_sort(flat_e: jnp.ndarray, e: int,
                   block_rows: int | None = None):
    """Expert-grouped slot assignment as a COUNTING SORT — the shared
    dispatch bookkeeping of the ragged and fused MoE paths.

    E is small, so the rank of each assignment within its expert comes
    from one LANE-MAJOR ``[E, N]`` one-hot cumsum (the ``[N, E]`` layout
    puts an 8-wide row on the 128-lane axis and measured ~2× the whole
    glue budget in padded cumsum passes), and ``rank + group_start`` is
    its destination slot — which IS the inverse permutation the combine
    needs; one scatter of iota builds the forward order.  No comparison
    sorts, no index gathers (the per-assignment start/rank picks are
    one-hot reductions).  This replaced the round-3 double ``argsort``,
    the bulk of the measured 3.3–3.8× ragged-dispatch overhead.

    ``block_rows`` pads each group's start to a block multiple (the
    fused kernel's block-aligned layout).  Returns
    ``(pos [N], order [NP], group_sizes [E], starts [E], np_pad)`` where
    ``NP = np_pad`` is ``N`` when unpadded.
    """
    n = flat_e.shape[0]
    onehot = (jnp.arange(e)[:, None] == flat_e[None, :]).astype(jnp.int32)
    within = jnp.cumsum(onehot, axis=1) - onehot        # [E, N] lane cumsum
    group_sizes = jnp.sum(onehot, axis=1)               # [E]
    padded = (group_sizes if block_rows is None
              else -(-group_sizes // block_rows) * block_rows)
    starts = jnp.concatenate(
        [jnp.zeros((1,), jnp.int32), jnp.cumsum(padded)[:-1]])
    pos = jnp.sum((within + starts[:, None]) * onehot, axis=0)   # [N]
    if block_rows is None:
        np_pad = n
    else:
        np_pad = (n // block_rows + e) * block_rows     # static bound
    order = jnp.zeros((np_pad,), jnp.int32).at[pos].set(
        jnp.arange(n, dtype=jnp.int32))
    return pos, order, group_sizes, starts, np_pad


def _ragged_moe(x: jnp.ndarray, w_up: jnp.ndarray, w_down: jnp.ndarray,
                top_idx: jnp.ndarray, top_vals: jnp.ndarray) -> jnp.ndarray:
    """Sorted dispatch + grouped matmuls: every (token, choice) assignment
    is grouped by expert id (:func:`_counting_sort`), expert MLPs run as
    TWO ``jax.lax.ragged_dot`` calls over the contiguous groups, and the
    inverse permutation + gate-weighted sum combines.  Zero [T, E, C]
    one-hots, zero capacity padding, zero dropped tokens."""
    t, d = x.shape
    k = top_idx.shape[1]
    e = w_up.shape[0]
    pos, order, group_sizes, _, _ = _counting_sort(top_idx.reshape(-1), e)
    xs = x[order // k]                                  # slot -> token row
    h = jax.nn.gelu(jax.lax.ragged_dot(xs, w_up, group_sizes))
    ys = jax.lax.ragged_dot(h, w_down, group_sizes)     # [N, d]
    y = ys[pos].reshape(t, k, d)                        # pos IS the inverse
    return jnp.sum(y * top_vals[:, :, None].astype(y.dtype), axis=1)


class MoEMLP(nn.Module):
    """Expert-parallel MLP: router + ``num_experts`` gelu MLPs.

    Input/output ``[tokens, d_model]``; expert weights are single stacked
    arrays ``[E, d, f]`` / ``[E, f, d]`` so the expert dim is shardable.
    Returns ``(out, aux_loss)``.

    ``ep_axis`` selects the EXPLICIT expert-parallel path: the module must
    then run inside a ``shard_map`` over that mesh axis with tokens sharded
    across it and the expert stacks sharded on their leading dim — each
    device routes its local tokens to ALL experts, a ``lax.all_to_all``
    delivers every expert's batch to the device that owns it, the local
    expert MLPs run, and a second all-to-all returns the outputs — the
    canonical EP dispatch, *guaranteed* in the lowering rather than left to
    GSPMD (which prefers replicate-tokens + all-reduce for the dense
    formulation; see ``tests/test_moe.py``).  Initialize the global model
    with ``ep_axis=None``, then shard.
    """

    d_model: int
    d_ff: int
    moe: MoEConfig
    ep_axis: str | None = None
    dtype: jnp.dtype | None = None     # compute dtype (None: the input's)

    def _gated(self, x: jnp.ndarray) -> tuple[jnp.ndarray, jnp.ndarray]:
        """The served expert layer (``experts="gated_silu"``): the router
        in float32 over all ``num_experts``, the gated grouped product
        over the ``held`` experts, the shared expert.  Sows the tokens
        each held expert was given into ``stats/expert_tokens`` (a no-op
        unless the caller makes ``stats`` mutable)."""
        from tpudist.ops.moe_dispatch import grouped_gated_mlp

        moe = self.moe
        e = moe.num_experts
        dt = self.dtype or x.dtype
        if self.ep_axis is not None:
            raise ValueError(
                "gated experts run single-shard: tell the layer the "
                "experts it holds with MoEConfig.held")
        first, count = moe.held or (0, e)
        if not (0 <= first and first + count <= e and count > 0):
            raise ValueError(f"held={moe.held} outside {e} experts")
        with routine("mlp/route"):
            logits = nn.Dense(e, use_bias=False, dtype=jnp.float32,
                              name="router")(x)
            bias = (self.param("router_bias", nn.initializers.zeros, (e,))
                    if moe.correction_bias else None)
            weights, experts = route(logits, bias, moe)
        shape_up = (count, self.d_model, self.d_ff)
        init = nn.initializers.lecun_normal(batch_axis=0)
        with routine("mlp/experts"):
            w_gate = self.param("w_gate", init, shape_up).astype(dt)
            w_up = self.param("w_up", init, shape_up).astype(dt)
            w_down = self.param(
                "w_down", init, (count, self.d_ff, self.d_model)).astype(dt)
            # its counting sort opens mlp/route, inside
            out, counts = grouped_gated_mlp(
                x.astype(dt), w_gate, w_up, w_down, experts - first,
                weights, num_experts=e)
        self.sow("stats", "expert_tokens", counts,
                 reduce_fn=lambda a, b: a + b,
                 init_fn=lambda: jnp.zeros((count,), jnp.int32))
        if moe.shared_combine not in ("sum", "mean"):
            raise ValueError(f"shared_combine must be 'sum' or 'mean', got "
                             f"{moe.shared_combine!r}")
        if moe.n_shared:
            with routine("mlp/shared"):
                shared = GatedMLP(self.d_model, moe.n_shared * self.d_ff,
                                  dt, name="shared")(x)
                if moe.shared_combine == "mean":
                    shared = shared * jnp.asarray(1.0 / moe.n_shared,
                                                  shared.dtype)
                out = out + shared
        return out, jnp.zeros((), jnp.float32)

    @nn.compact
    def __call__(self, x: jnp.ndarray) -> tuple[jnp.ndarray, jnp.ndarray]:
        if self.moe.experts == "gated_silu":
            return self._gated(x)
        if self.moe.experts != "gelu":
            raise ValueError(f"experts must be 'gelu' or 'gated_silu', got "
                             f"{self.moe.experts!r}")
        t = x.shape[0]
        e = self.moe.num_experts
        capacity = max(
            1, int(self.moe.capacity_factor * t * self.moe.top_k / e))
        gates = jax.nn.softmax(
            nn.Dense(e, use_bias=False, name="router")(x).astype(jnp.float32))
        if self.moe.dispatch in ("ragged", "fused"):
            if self.ep_axis is not None:
                raise ValueError(
                    f"dispatch={self.moe.dispatch!r} is single-shard (the "
                    "EP all-to-all transports the [E, C, d] block layout); "
                    "use dispatch='einsum' with ep_axis")
            top_vals, top_idx, aux = _gate_choices(gates, self.moe.top_k)
            w_up = self.param(
                "w_up", nn.initializers.lecun_normal(),
                (e, self.d_model, self.d_ff)).astype(x.dtype)
            w_down = self.param(
                "w_down", nn.initializers.lecun_normal(),
                (e, self.d_ff, self.d_model)).astype(x.dtype)
            if self.moe.dispatch == "fused":
                from tpudist.ops.moe_dispatch import fused_moe_mlp

                out = fused_moe_mlp(x, w_up, w_down, top_idx, top_vals,
                                    block_rows=self.moe.fused_block_rows)
            else:
                out = _ragged_moe(x, w_up, w_down, top_idx, top_vals)
            return out, aux.astype(jnp.float32)
        if self.moe.dispatch != "einsum":
            raise ValueError(
                f"unknown dispatch {self.moe.dispatch!r} "
                f"(expected einsum|ragged|fused)")
        dispatch, combine, aux = _top_k_routing(
            gates, self.moe.top_k, capacity)

        # Params in float32, compute in the input dtype (the same f32-params/
        # bf16-compute contract nn.Dense(dtype=...) gives the dense layers).
        # Under ep_axis the declared (local) expert count is E / axis size —
        # matching the shard this device holds of the stacked weights.
        n_shards = 1 if self.ep_axis is None else jax.lax.axis_size(
            self.ep_axis)
        if e % n_shards:
            raise ValueError(
                f"num_experts {e} not divisible by {self.ep_axis!r} axis "
                f"size {n_shards}")
        w_up = self.param(
            "w_up", nn.initializers.lecun_normal(),
            (e // n_shards, self.d_model, self.d_ff)).astype(x.dtype)
        w_down = self.param(
            "w_down", nn.initializers.lecun_normal(),
            (e // n_shards, self.d_ff, self.d_model)).astype(x.dtype)

        # dispatch: [T,E,C] × [T,d] -> per-expert batches [E,C,d] ...
        expert_in = jnp.einsum("tec,td->ecd", dispatch.astype(x.dtype), x)
        if self.ep_axis is not None:
            # THE all-to-all of expert parallelism: expert-major blocks
            # scatter to their owners, every shard's token batches gather
            # along capacity -> [E/n, n·C, d]
            expert_in = jax.lax.all_to_all(
                expert_in, self.ep_axis, split_axis=0, concat_axis=1,
                tiled=True)
        h = jax.nn.gelu(jnp.einsum("ecd,edf->ecf", expert_in, w_up))
        expert_out = jnp.einsum("ecf,efd->ecd", h, w_down)
        if self.ep_axis is not None:
            # return trip: [E/n, n·C, d] -> [E, C, d] back at the sources
            expert_out = jax.lax.all_to_all(
                expert_out, self.ep_axis, split_axis=1, concat_axis=0,
                tiled=True)
        # ... and the combine, weighted by the (renormalised) gates.
        out = jnp.einsum("tec,ecd->td", combine.astype(x.dtype), expert_out)
        return out, aux.astype(jnp.float32)


class MoEDecoderBlock(nn.Module):
    cfg: TransformerConfig
    moe: MoEConfig
    attention_fn: AttentionFn = sdpa
    ep_axis: str | None = None

    @nn.compact
    def __call__(self, x, *, causal: bool = True):
        h = nn.LayerNorm(dtype=self.cfg.compute_dtype, name="ln1")(x)
        x = x + CausalSelfAttention(self.cfg, self.attention_fn,
                                    name="attn")(h, causal=causal)
        h = nn.LayerNorm(dtype=self.cfg.compute_dtype, name="ln2")(x)
        b, s, d = h.shape
        out, aux = MoEMLP(d_model=self.cfg.embed_dim,
                          d_ff=self.cfg.mlp_ratio * self.cfg.embed_dim,
                          moe=self.moe, ep_axis=self.ep_axis,
                          name="moe")(h.reshape(b * s, d))
        return x + out.reshape(b, s, d), aux


class MoETransformerLM(nn.Module):
    """Decoder-only LM with an MoE MLP in every block.

    ``tokens [B, S] -> (logits [B, S, vocab] f32, aux_loss scalar)``; add
    ``aux_loss`` (already weighted) to the training loss.
    """

    cfg: TransformerConfig
    moe: MoEConfig
    attention_fn: AttentionFn = sdpa
    ep_axis: str | None = None

    @nn.compact
    def __call__(self, tokens, *, causal: bool = True, positions=None):
        cfg = self.cfg
        if positions is None:
            positions = jnp.arange(tokens.shape[1])[None, :]
        x = nn.Embed(cfg.vocab_size, cfg.embed_dim,
                     dtype=cfg.compute_dtype, name="tok_embed")(tokens)
        x = x + nn.Embed(cfg.max_seq_len, cfg.embed_dim,
                         dtype=cfg.compute_dtype, name="pos_embed")(positions)
        aux_total = jnp.zeros((), jnp.float32)
        for i in range(cfg.num_layers):
            x, aux = MoEDecoderBlock(cfg, self.moe, self.attention_fn,
                                     ep_axis=self.ep_axis,
                                     name=f"block{i}")(x, causal=causal)
            aux_total = aux_total + aux
        x = nn.LayerNorm(dtype=cfg.compute_dtype, name="ln_f")(x)
        logits = nn.Dense(cfg.vocab_size, use_bias=False,
                          dtype=cfg.compute_dtype, name="lm_head")(x)
        return logits.astype(jnp.float32), self.moe.aux_loss_weight * aux_total
