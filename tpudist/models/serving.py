"""Continuous-batching serving loop — request-level scheduling over the
compiled decode step.

The round-3 verdict: the kernels and sharded rollouts existed, the
REQUEST layer didn't — fixed-batch rollouts make every sequence in the
batch start and stop together, so a mixed workload pays the longest
request's schedule.  This module adds the vLLM-style iteration-level
scheduler, shaped for TPU/XLA rather than for a GPU runtime:

* ``num_slots`` fixed decode lanes, each owning one row of the KV cache;
  the cache's ``cache_index`` leaves are VECTORS ``[B]`` — every slot
  decodes at its own length through the per-row cache path
  (``CausalSelfAttention._serve_attend``; the flash kernel takes per-row
  lengths) — one compiled step, no padding to a common position;
* ONE compiled SEGMENT (``lax.scan`` of ``steps_per_sync`` single-token
  steps) between host syncs: per-token host round trips would be
  RTT-bound, so admission/completion happen at segment granularity (a
  slot finishing mid-segment idles ≤ ``steps_per_sync`` ticks — the
  standard iteration-level-scheduling trade);
* admission PREFILLS the prompt through the scalar-index path into a
  side cache of batch 1 (chunked — the same ``_prefill`` the rollouts
  use, prompts right-padded to a chunk multiple so compile count is
  bounded by ``max_seq_len / prefill_chunk`` distinct shapes), then one
  compiled INSERT scatters the row into the freed slot and stamps its
  true length;
* per-request ``max_new_tokens`` and stop tokens: budgets ride the
  compiled segment as an ``[B]`` countdown (a stopped/funded-out slot
  freezes inside the segment), the host finalizes completions and reuses
  the slot.

What this loop costs on the chip is measured by the benchmark's serving
cells (``python3 benchmarks/run.py --workload sc3b_code_steady``;
PERF.md section 5 has where the time goes).
"""

from __future__ import annotations

import dataclasses
import hashlib
import os
import re
import time
from collections import deque
from typing import Any, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from tpudist import obs
from tpudist.runtime import faults
from tpudist.models.generate import (
    _blank_cache,
    _make_select,
    _prefill,
    _set_cache_index,
    _stop_array,
    serving_layout,
)
from tpudist.models.kv_pages import (
    BlockPool,
    PrefixCache,
    chain_hashes,
    span_blocks,
)
from tpudist.models.kv_tier import HostTier, tier_budget_from_env
from tpudist.models.transformer import TransformerConfig, TransformerLM
from tpudist.ops.flash_decode import (SPARSE_ATTEND_GATHERS, pack_kv,
                                      paged_grid_rows, paged_tile_pages,
                                      walk_rows)

# placeholder page row for the dense layout's admit signature (the insert
# walk never reaches a paged node there)
_NO_PAGES = np.zeros((0,), np.int32)


def _span_rid(rid: Any):
    """A request id as a span argument: JSON-ready as it is, or its
    ``str``."""
    return rid if isinstance(rid, (str, int, float, type(None))) else str(rid)


def _park_hash(rid: str, i: int) -> int:
    """Synthetic host-tier key for a parked (preempted) slot's i-th KV
    block: a 63-bit blake2b digest of ``(rid, i)`` — int-typed as the
    tier requires, and disjoint from prefix chain hashes with
    overwhelming probability."""
    d = hashlib.blake2b(f"park:{rid}:{i}".encode(), digest_size=8)
    return int.from_bytes(d.digest(), "big") >> 1


@dataclasses.dataclass
class Request:
    """One serving request: a prompt and its generation budget.

    ``deadline_s`` is an ABSOLUTE wall-clock deadline (``time.time()``
    epoch seconds, ``None`` = no deadline).  A request whose deadline
    passes while queued completes with ``reason="timeout"`` and no
    tokens; one that expires mid-decode is killed at the next segment
    boundary, completes with the tokens generated so far, and refunds
    its KV block reservation — a stuck client can never pin pool
    capacity forever.

    ``priority`` ranks requests for overload degradation: 0 (default) is
    best-effort, higher values are more important.  Under pressure the
    loop degrades best-effort traffic FIRST — clamps its
    ``max_new_tokens`` past the soft watermark, sheds it first at the
    hard bound — so paid/interactive traffic keeps full service until
    best-effort is exhausted.

    ``trace`` is the distributed-tracing context
    (:class:`tpudist.obs.events.TraceContext`, ``None`` for untraced
    local runs): minted by the router at submit, it rides the fleet
    wire format and keys every lifecycle event this loop records —
    admit, segments, degrade clamps, timeouts, finalize — to the one
    fleet-wide id that survives a SIGKILL + redispatch.

    ``prefix_hash`` is an opaque client-stamped hash of the prompt's
    shared prefix (:func:`tpudist.models.kv_pages.request_prefix_hash`
    over e.g. a tenant's system prompt; ``None`` = no known prefix).
    The serve loop records recently admitted hashes while prefix
    sharing is on (:meth:`ServeLoop.prefix_summary`), replicas publish
    them, and the router steers same-hash requests to a replica whose
    prefix cache is already warm — fleet-level hit rate survives
    scale-out without any process agreeing on block sizes."""

    prompt: np.ndarray            # [L] int32 tokens, L >= 1
    max_new_tokens: int
    rid: Any = None               # caller's correlation id
    deadline_s: float | None = None
    priority: int = 0             # 0 = best-effort; higher = keep longer
    trace: Any = None             # TraceContext | None (fleet tracing)
    prefix_hash: int | None = None  # router prefix-affinity key
    # disaggregated serving: a KV-migration payload from a prefill
    # replica (see tpudist.runtime.disagg).  A decode-role loop ADOPTS
    # the migrated pages instead of prefilling; None (or a payload that
    # fails verification) means ordinary admission — the re-prefill
    # fallback that keeps a lost handoff exact.
    kv_handoff: Any = None
    # fleet-global prefix cache (pull mode): an opaque KVTransport ref
    # to a peer-exported prefix payload.  The replica worker resolves
    # it and installs the pages as cached-idle blocks BEFORE admission,
    # so the admission below hits locally; a missing/corrupt/stale ref
    # installs nothing and the ordinary prefill is the exact fallback.
    prefix_ref: str | None = None


@dataclasses.dataclass
class Completion:
    rid: Any
    prompt: np.ndarray
    tokens: np.ndarray            # the generated tokens (stop included)
    # "stop" | "length" — the normal endings; "rejected" (load-shed at a
    # full admission queue), "timeout" (deadline_s passed), "invalid"
    # (service-mode request failed validation), "shed" (router-side SLO
    # admission refused it before any replica paid a prefill — see
    # tpudist.runtime.router), "handoff" (a prefill-role loop finished
    # the prompt and exported its KV; `handoff` carries the migration
    # payload and the DECODE stage produces the tokens)
    reason: str
    handoff: Any = None           # KV-migration payload (prefill role)
    # the serving loop's own clock for this request (RequestTiming);
    # None on completions built outside a ServeLoop (the router's: a
    # perf_counter stamp means nothing in another process, so the fleet
    # wire format leaves it out)
    timing: Any = None


@dataclasses.dataclass(frozen=True)
class RequestTiming:
    """Where a request's time went inside one :meth:`ServeLoop.run`, as
    ``time.perf_counter()`` stamps of this process, each taken where the
    thing happens.  A request that never reached a lane has ``enqueue``
    and ``done`` only; one resumed from a park or adopted from a peer is
    stamped from its latest enqueue here."""

    enqueue: float                     # intake put it in the queue
    done: float                        # its completion left the loop
    admit: float | None = None         # _admit returned (lane taken)
    # the admission's finish was DISPATCHED (== admit when one-shot)
    prefill_done: float | None = None
    # the fetch that carried its first token RETURNED to the host
    first_token: float | None = None
    chunks: int = 0                    # prefill chunks computed for it
    tokens: int = 0                    # tokens in the completion


def _index_leaves(cache: Any) -> tuple[jnp.ndarray, jnp.ndarray | None]:
    """(cache_index [B], side_index scalar | None), matched BY NAME:
    every layer carries the same values, so the first of each suffices."""
    main = side = None

    def walk(node):
        nonlocal main, side
        if not isinstance(node, dict):
            return
        for k, v in node.items():
            if k == "cache_index" and main is None:
                main = v
            elif k == "side_index" and side is None:
                side = v
            else:
                walk(v)

    walk(cache)
    if main is None:
        raise ValueError("cache holds no index leaves")
    return main, side


def _kv_leaves(node: dict, prefix: str) -> list[str]:
    """What a layer's attention keeps a token: the ``<suffix>`` of its
    cache leaves ``<prefix>_<suffix>`` (``prefix`` one of ``cached``, the
    dense buffers, ``paged``, the block pools, ``side``, the segment's
    staging buffers).  ``["key", "value"]`` for MHA/GQA/MQA, ``["latent"]``
    for latent attention; for grouped-query attention with an indexer
    ``["ikey", "kv"]`` in the pools and the staging buffers (its index key
    a token, and K and V in ONE row of 32-bit words,
    ``ops.flash_decode.kv_row``: its decode step gathers the chosen rows,
    at a cost by the row) and ``["ikey", "key", "value"]`` in the batch-1
    prefill cache, whose kernels read K and V each whole
    (:func:`_dense_parts` says which dense leaves make up a pool's row).
    Every place that moves cache rows iterates over these and is
    indifferent to their number, width and dtype.  No leaf's suffix may be
    ``index``: the
    staging buffer's cursor is the leaf ``side_index``, which shares the
    prefix and is left out here BY NAME (hence ``side_ikey``)."""
    return sorted(k[len(prefix) + 1:] for k in node
                  if k.startswith(prefix + "_") and k != "side_index")


def _dense_parts(leaf: str) -> tuple[str, ...]:
    """The batch-1 prefill cache's leaves whose rows make up a row of pool
    leaf ``leaf``: ``kv`` is ``key`` and ``value`` packed by
    ``ops.flash_decode.pack_kv`` (a word of a 16-bit K and V each; 32-bit
    numbers side by side); any other leaf is its dense namesake."""
    return ("key", "value") if leaf == "kv" else (leaf,)


_HLO_INSTRUCTION = re.compile(r"^\s*(?:ROOT )?%([^\s=]+) = ")
_HLO_OP_NAME = re.compile(r'op_name="([^"]*)"')
_HLO_OPERAND = re.compile(r"%([^\s,(){}]+)")


def hlo_scopes(hlo_text: str) -> dict[str, str]:
    """``{instruction name: routine scope}`` of a compiled program's HLO
    text, under the name a profiler's ``XLA Ops`` event begins with
    (``%fusion.12 = ...``).  An instruction whose ``metadata={op_name=
    ...}`` names one of ``obs.ROUTINE_SCOPES`` has that scope (a fusion
    carries the ``op_name`` of the instruction it is named after).  One the
    COMPILER made, with no ``op_name`` at all (the asynchronous copies and
    slices that bring weights and tables next to their consumer, their
    bitcasts and concatenations), takes the scope of what consumes it,
    where every consumer has one and they agree: its time is that
    routine's wait for its operands.  The program's own instructions
    outside every scope stay out."""
    scopes: dict[str, str] = {}
    made, users = [], {}
    for line in hlo_text.splitlines():
        m = _HLO_INSTRUCTION.match(line)
        if not m:
            continue
        name = m.group(1)
        op = _HLO_OP_NAME.search(line)
        if op is None:
            made.append(name)
        else:
            scope = obs.scope_of(op.group(1))
            if scope:
                scopes[name] = scope
        for operand in set(_HLO_OPERAND.findall(line[m.end():])):
            users.setdefault(operand, []).append(name)
    left = made
    while left:     # copy-start <- copy-done <- bitcast <- its consumer
        still = []
        for name in left:
            found = {scopes.get(user) for user in users.get(name, ())}
            if len(found) == 1 and None not in found:
                scopes[name] = found.pop()
            else:
                still.append(name)
        if len(still) == len(left):
            break
        left = still
    return scopes


def _state_nodes(cache: Any) -> list[dict]:
    """The cache nodes of the layers whose past is a STATE and not rows (a
    linear-attention layer's ``state`` and ``conv`` leaves): a slot's slice
    of each leaf is the whole of what the layer keeps for that lane."""
    found = []

    def walk(node):
        if isinstance(node, dict):
            if "state" in node:
                found.append(node)
            else:
                for v in node.values():
                    walk(v)

    walk(cache)
    return found


def _bound_paged_walk(cache: Any, active) -> Any:
    """Entry of a segment, paged layout: a lane that is not active holds
    no request (released, finalised, or still prefilling through the
    batch-1 cache) and stays inactive for the whole segment, so its main
    length is set to 0 and the paged decode kernel, which walks
    ``ceil(cache_index / block)`` pages a lane, walks none for it.
    Admission and adoption stamp the length anew with the lane; the merge
    advances it by the lane's ``lived`` (0 here); the host keeps its own
    lengths in the pool and never reads this leaf."""
    def walk(node):
        if not isinstance(node, dict):
            return node
        out = {k: walk(v) for k, v in node.items()}
        if "page_table" in out:
            out["cache_index"] = jnp.where(active, out["cache_index"], 0)
        return out
    return walk(cache)


class ServeLoop:
    """Continuous-batching server over one model.

    Args:
      cfg / params: the model (scanned checkpoints are normalized via
        :func:`serving_layout`).
      num_slots: decode lanes (the B of the slot cache).  Pick the
        fixed-batch size that saturates the chip; the request layer keeps
        those lanes full across requests of different lengths.
      steps_per_sync: decode ticks per compiled segment (the admission
        latency / dispatch-amortization trade: one host dispatch and
        one emit fetch per segment, not per token).
      decode_attention: "flash" (per-row kernel) or "dense".
      prefill_chunk: admission prefill chunk; prompts are right-padded to
        a multiple of it, so it also bounds the number of distinct
        prefill executables.
      stop_tokens / pad_token: EOS semantics as in ``greedy_generate``.
      temperature / top_k / top_p: sampling controls (0 = greedy).
      key: the sampling key the loop splits a key a prefill and a key a
        step from (default ``jax.random.key(0)``; greedy decoding never
        reads it).
      auto_unstack: convert a scanned checkpoint (``cfg.scan_layers``) to
        the unrolled layout the loop needs, via :func:`serving_layout`
        (default).  ``False`` takes ``cfg`` / ``params`` as they are and
        refuses a scanned ``cfg``.
      cache_layout: "dense" (per-slot ``[B, S]`` KV buffers) or "paged"
        (a shared block pool per layer + per-slot page tables —
        PagedAttention).  Paged serving's KV HBM scales with the tokens
        requests actually reserve, not ``num_slots × max_seq_len``; see
        :mod:`tpudist.models.kv_pages`.  Admission gains a capacity
        check against free blocks (requests QUEUE when the pool is
        full, FIFO), and dispatch grows every live slot's page coverage
        by ``steps_per_sync`` before each segment.
      kv_block_size: tokens per KV block (paged only); a positive
        multiple of 8.  Small blocks waste less memory on the last
        partial block per request (~block_size/2 tokens × slots), large
        blocks mean fewer grid steps and page-table entries.
      kv_num_blocks: pool capacity (paged only).  Default ``None``
        sizes the pool to full dense capacity
        (``num_slots × ceil(max_seq_len / block_size)``); the HBM win
        comes from passing the capacity the workload actually needs.
      A model with sliding-window layers (``cfg.layer_windows`` or
        ``attention_window``) served paged keeps them in a block group of
        their own beside ``kv_num_blocks`` (the full-attention layers'):
        a lane holds there what covers its last ``window`` tokens and a
        segment, and releases what fell below.  That group is sized to
        every lane's worst case (``kv_window_blocks``, read-only), so it
        never refuses an admission.  ``docs/DESIGN.md`` ("Window and full
        layers in one paged cache") lists what such a model cannot be
        combined with; each is refused here with the reason.
      pipeline_depth: compiled segments in flight before the host blocks
        on a fetch.  2 (the default) dispatches segment ``k+1`` as soon
        as ``k`` returns — the carry chains on device — and then fetches
        ``k``'s emits (whose device→host copy was started async at
        dispatch time) overlapped with ``k+1``'s compute, so the device
        never waits on the host round trip in steady state.  The cost is
        BOUNDED STALENESS: the host learns stop/budget events one
        segment later, so admissions and finalizations shift one segment
        — the same trade the segment design already accepts at
        ``steps_per_sync`` granularity — while the drain path stays
        token-identical (frozen rows emit pads in-graph; stale columns
        are dropped by the same rules as the synchronous loop).  1
        restores the fully synchronous loop.
      max_queue: bound on WAITING requests (excluding the ones already
        in slots).  ``None`` (default) keeps the queue unbounded; with a
        bound, overflow requests are load-shed — lowest ``priority``
        class first, newest-first within a class — completing
        immediately with ``reason="rejected"`` and ticking the
        ``serve/rejected`` counter, which a router reads to back off a
        saturated replica instead of piling more work on it.
      degrade_queue: soft overload watermark (defaults to
        ``max_queue // 2`` when ``max_queue`` is set).  While the queue
        sits above it the loop is DEGRADED (``serve/degraded`` gauge = 1)
        and newly admitted best-effort requests (``priority == 0``) get
        ``max_new_tokens`` clamped to ``degrade_max_new`` — shorter
        answers for everyone beats no answer for the tail, and the clamp
        engages BEFORE any request is rejected outright.
      degrade_max_new: the degraded-mode ``max_new_tokens`` clamp for
        best-effort traffic (default 32).
      chunked_prefill: interleave admission prefill with decode.
        Instead of one fused prefill+insert dispatch, admission
        dispatches ONE ``prefill_chunk``-wide slice per host-loop
        iteration between fused decode segments, so a 10k-token prompt
        can no longer stall every in-flight request's inter-token
        latency for its whole prefill.  The chunk partition is the SAME
        grid the one-shot path uses, so output stays token-identical.
      max_prefill_lanes: the most lanes in chunked admission at once
        (``None``: no bound).  A lane in admission holds a batch-1
        prefill cache (``max_seq_len`` rows in every full-attention
        layer) from its first chunk to its finish, so a burst of long
        prompts (a cold start filling every lane) holds ``lanes x
        chunks`` of them at once; with the bound, a request whose turn
        comes while that many lanes prefill WAITS in the queue (FIFO,
        ``serve/queue_wait_s``) for a finish.  Bounds memory, never
        results.  Every lane in admission runs one chunk between two
        decode segments, so it is also the prefill an iteration may
        carry (``max_prefill_lanes x prefill_chunk`` tokens): the
        bound on the inter-token gap's tail.
      prefix_sharing: copy-on-write prefix page sharing (paged layout +
        chunked prefill only; silently off otherwise).  A host-side
        :class:`~tpudist.models.kv_pages.PrefixCache` maps rolling
        token-hash chains to pool blocks; an admission whose prompt
        prefix is cached ALIASES those blocks (refcounted, read-only)
        and prefills only the suffix — a full-prompt hit recomputes
        one position through a COW split of the last shared block.
        The cache is flushed at every weight hot-swap (cached KV is
        stale the moment params change).
      role: ``"both"`` (default — the unified loop), ``"prefill"``, or
        ``"decode"`` — the disaggregated fleet split
        (:mod:`tpudist.runtime.disagg`).  A PREFILL loop runs chunked
        prefill to completion, exports the slot's KV pages plus the
        first sampled token as a migration payload
        (``Completion(reason="handoff", handoff=payload)``), frees the
        slot, and never dispatches a decode segment — its lanes turn
        over at prompt cadence.  A DECODE loop admits requests whose
        ``Request.kv_handoff`` carries such a payload by ADOPTING the
        pages into its own pool (no prefill) and decoding from the
        migrated state; a missing or unverifiable payload falls back
        to an ordinary prefill of the same prompt, which greedy
        decoding over identical weights makes byte-identical.
        ``"prefill"`` requires the paged layout + chunked prefill;
        ``"decode"`` requires the paged layout.
      preempt: what a ``degrade_queue`` breach does to best-effort
        traffic.  ``"degrade"`` (default) clamps its budgets
        (``degrade_max_new``).  ``"migrate"`` (paged layout only) PAUSES
        it instead: the victim slot's KV pages go to the host tier (or a
        dict park) and are adopted again when pressure clears, so
        best-effort output is byte-identical to the undisturbed run;
        admission is then priority-first, and the worker can evacuate
        in-flight work at drain and swap time.
    """

    def __init__(
        self,
        cfg: TransformerConfig,
        params: Any,
        num_slots: int,
        *,
        steps_per_sync: int = 32,
        decode_attention: str = "flash",
        prefill_chunk: int = 512,
        stop_tokens: Sequence[int] | None = None,
        pad_token: int = 0,
        temperature: float = 0.0,
        top_k: Optional[int] = None,
        top_p: Optional[float] = None,
        key: jax.Array | None = None,
        auto_unstack: bool = True,
        pipeline_depth: int = 2,
        cache_layout: str = "dense",
        kv_block_size: int = 128,
        kv_num_blocks: int | None = None,
        max_queue: int | None = None,
        degrade_queue: int | None = None,
        degrade_max_new: int = 32,
        chunked_prefill: bool = True,
        max_prefill_lanes: int | None = None,
        prefix_sharing: bool = True,
        role: str = "both",
        preempt: str = "degrade",
    ) -> None:
        if num_slots < 1:
            raise ValueError(f"num_slots must be >= 1, got {num_slots}")
        if max_queue is not None and max_queue < 0:
            raise ValueError(f"max_queue must be >= 0, got {max_queue}")
        if steps_per_sync < 1:
            raise ValueError(
                f"steps_per_sync must be >= 1, got {steps_per_sync}")
        if pipeline_depth < 1:
            raise ValueError(
                f"pipeline_depth must be >= 1, got {pipeline_depth}")
        if auto_unstack:
            cfg, params = serving_layout(cfg, params)
        if cfg.scan_layers:
            raise ValueError(
                "ServeLoop needs the unrolled layout; pass the scanned "
                "checkpoint with auto_unstack=True (the default)")
        if cache_layout not in ("dense", "paged"):
            raise ValueError(
                f"cache_layout must be 'dense' or 'paged', got "
                f"{cache_layout!r}")
        # a layer's window, by its name in the cache tree; the one width
        # of the windowed layers (None: the model has none)
        self._window_of = {f"block{i}": w
                           for i, w in enumerate(cfg.windows)}
        widths = {w for w in cfg.windows if w is not None}
        self._window = min(widths) if widths else None
        windowed_paged = cache_layout == "paged" and bool(widths)
        if windowed_paged:
            # what a cache of two block groups cannot do yet, refused
            # here with the reason (docs/DESIGN.md has the list)
            if len(widths) > 1:
                raise ValueError(
                    f"the paged cache keeps ONE window block group: the "
                    f"windowed layers must share a width, got "
                    f"{sorted(widths)}")
            if role != "both" or preempt == "migrate":
                raise ValueError(
                    "KV handoff and migration payloads carry one block "
                    "list a slot; a cache with a window block group "
                    "serves with role='both' and preempt='degrade'")
            if self._window < steps_per_sync:
                raise ValueError(
                    f"a segment's {steps_per_sync} staged tokens must "
                    f"fit in the window ({self._window}): lower "
                    "steps_per_sync")
        if role not in ("both", "prefill", "decode"):
            raise ValueError(
                f"role must be 'both', 'prefill', or 'decode', got "
                f"{role!r}")
        if role == "prefill" and not (cache_layout == "paged"
                                      and chunked_prefill):
            raise ValueError(
                "role='prefill' needs cache_layout='paged' with chunked "
                "prefill: the handoff exports pool pages at the "
                "chunked-admission finish")
        if role == "decode" and cache_layout != "paged":
            raise ValueError(
                "role='decode' needs cache_layout='paged': handoff "
                "adoption scatters migrated pages into the block pool")
        if preempt not in ("degrade", "migrate"):
            raise ValueError(
                f"preempt must be 'degrade' or 'migrate', got "
                f"{preempt!r}")
        if preempt == "migrate" and cache_layout != "paged":
            raise ValueError(
                "preempt='migrate' needs cache_layout='paged': "
                "preemption exports the victim slot's pool pages")
        # the pressure policy (the class docstring says what each does)
        self.preempt = preempt
        self.role = role
        self.cfg = cfg
        self.params = params
        self.B = num_slots
        self.steps = steps_per_sync
        # mutable on purpose: a test flips the SAME instance between
        # synchronous (1) and pipelined runs, so both share executables
        self.pipeline_depth = pipeline_depth
        self.prefill_chunk = prefill_chunk
        self.pad_token = int(pad_token)
        self._stop = _stop_array(stop_tokens)
        self._stop_set = (set(np.asarray(self._stop).tolist())
                          if self._stop is not None else set())
        if (decode_attention == "flash" and self._window is not None
                and cache_layout != "paged"):
            import warnings

            warnings.warn(
                "ServeLoop with a sliding-window model falls back to "
                "DENSE per-row attention (the per-row flash kernel has "
                "no window trim yet): every decode step streams the "
                "whole cache instead of ~window positions",
                stacklevel=2)
        self._select = _make_select(temperature, top_k, top_p)
        self._key = key if key is not None else jax.random.key(0)
        # rows a query attends at most where an indexer chooses them
        # (None: every row under its length)
        self._index_topk = cfg.index_topk
        if self._index_topk is not None and cache_layout != "paged":
            raise ValueError(
                "a model with an indexer serves through "
                "cache_layout='paged': the dense layout's per-row "
                "decode has no index scores or selection")
        # layers whose past is a fixed-size state a lane (cfg.layer_kinds
        # "linear"): what such a cache cannot do yet is refused here with
        # the reason (docs/DESIGN.md has the list)
        self._state_layers = [i for i, kind in enumerate(cfg.kinds)
                              if kind == "linear"]
        if self._state_layers:
            if role != "both" or preempt == "migrate":
                raise ValueError(
                    "KV handoff and migration payloads carry a lane's "
                    "blocks and no state; a model with linear-attention "
                    "layers serves with role='both' and preempt='degrade'")
            if not chunked_prefill:
                raise ValueError(
                    "a model with linear-attention layers is admitted "
                    "chunk by chunk (chunked_prefill=True): the one-shot "
                    "admission pads the prompt and has no mask for the "
                    "padded rows, which would move the state")
        # SIDE-BUFFER mode (flash, no window): steps write a segment-
        # local buffer at a SCALAR index (XLA keeps those in place;
        # per-row-indexed main-cache writes measured +0.35 ms/step on the
        # 8-layer 8k model) and one per-segment merge scatters side ->
        # main.  Other configurations use the direct per-row writes.
        # the paged layout is sided UNCONDITIONALLY: the pool is frozen
        # within a segment (growth happens at dispatch boundaries), so
        # every in-segment token must stage in the side buffer.
        self.side = (steps_per_sync
                     if (decode_attention == "flash"
                         and self._window is None)
                     or cache_layout == "paged" else 0)
        self.cache_layout = cache_layout
        if cache_layout == "paged":
            bs_ = int(kv_block_size)
            nb = (num_slots * -(-cfg.max_seq_len // bs_)
                  if kv_num_blocks is None else int(kv_num_blocks))
            self.kv_block_size, self.kv_num_blocks = bs_, nb
            # the host half: free list, per-slot block lists, and the
            # page table the compiled carry consumes (stamped at
            # dispatch); with windowed layers, their block group beside
            self.pool = BlockPool(
                nb, bs_, num_slots, cfg.max_seq_len, window=self._window,
                window_steps=steps_per_sync)
        else:
            self.kv_block_size = self.kv_num_blocks = 0
            self.pool = None
        wg = self.pool.window_group if self.pool is not None else None
        self.kv_window_blocks = wg.num_blocks if wg is not None else 0
        # chunked-interleaved prefill; prefix sharing additionally
        # needs the paged layout — shared blocks live in the pool
        self.chunked = bool(chunked_prefill)
        if max_prefill_lanes is not None and max_prefill_lanes < 1:
            raise ValueError(
                f"max_prefill_lanes must be >= 1, got {max_prefill_lanes}")
        self.max_prefill_lanes = max_prefill_lanes
        # (a cache with a window group shares nothing: a hit's boundary
        # would have to bring the window group's blocks before it, which
        # the finished request released long ago; off, as with the dense
        # layout.  A model with an indexer shares nothing either: a hit
        # starts the suffix's first chunk at a block boundary inside a
        # chunk, and no test holds a chunk's selection there, nor three
        # leaves through the gathered prefix.  A model with state layers
        # shares nothing: a hit would need the STATE as it stood at the
        # prefix's last block boundary, and only the state after the whole
        # prompt is kept; and so no host tier either)
        self._prefix_cache = (
            PrefixCache(self.pool)
            if prefix_sharing and self.chunked and self.pool is not None
            and wg is None and self._index_topk is None
            and not self._state_layers else None)
        # the weights version the loop's CURRENT params correspond to;
        # stamps tier entries and pull-mode exports so KV computed
        # under one version can never be adopted under another (the
        # swap-point flush is the front door, the stamp the backstop)
        self.weights_version = 0
        # host-RAM spill tier (tier 2 of the KV hierarchy): prefix-
        # cache evictions land here instead of vanishing, keyed by the
        # same chain hashes — see tpudist.models.kv_tier.  Budgeted by
        # TPUDIST_KV_HOST_TIER_BYTES (0 disables).
        self._tier: HostTier | None = None
        if self._prefix_cache is not None:
            budget = tier_budget_from_env()
            if budget > 0:
                self._tier = HostTier(budget)
                self._prefix_cache.spill_hook = self._spill_block
        # recently admitted request prefix hashes (wire-opaque ints from
        # Request.prefix_hash), LRU-bounded — the replica's published
        # affinity summary (see prefix_summary)
        self._affinity_recent: dict[int, None] = {}
        # cumulative host-side tallies callers read as deltas (obs
        # counters also tick; this avoids registry round trips)
        self.prefix_stats = {"requests": 0, "hits": 0, "hit_tokens": 0,
                             "prompt_tokens": 0, "prefill_tokens": 0}
        # per-run (gap_seconds_per_token, tokens) samples, one per
        # drained decode segment — the benchmark's gap_p90_ms is
        # computed from these (reset at every run())
        self.intertoken_samples: list[tuple[float, int]] = []
        self._last_drain_t: float | None = None
        self.model = TransformerLM(cfg, decode=True,
                                   decode_attention=decode_attention,
                                   serve_side_slots=self.side,
                                   cache_layout=cache_layout,
                                   kv_num_blocks=self.kv_num_blocks,
                                   kv_block_size=self.kv_block_size,
                                   kv_window_blocks=self.kv_window_blocks)
        # admission prefill ALWAYS runs dense: it fills a fresh batch-1
        # scalar-index cache (contiguous chunked writes) and the insert
        # scatters that row into pages — prefilling straight into the
        # pool would need per-chunk page-table plumbing for zero gain
        # (the batch-1 cache is transient)
        # a windowed layer's batch-1 cache follows its kind too: the
        # window and the chunk in hand (CausalSelfAttention.
        # _rolling_prefill), not max_seq_len rows
        self._prefill_model = (
            TransformerLM(cfg, decode=True,
                          decode_attention=decode_attention,
                          serve_side_slots=self.side,
                          prefill_window_rows=(
                              min(prefill_chunk, cfg.max_seq_len)
                              if wg is not None else 0))
            if cache_layout == "paged" else self.model)
        # the slot cache: blank, with VECTOR index leaves (one position
        # per slot) — this is what routes attention through the per-row
        # cache path — and, in sided mode, the side buffers materialized
        # EAGERLY (a lax.scan carry's structure cannot grow mid-scan)
        blank = _blank_cache(self.model, num_slots)
        self.cache = jax.tree.map(
            lambda leaf: (jnp.zeros((num_slots,), jnp.int32)
                          if leaf.ndim == 0 else leaf), blank)
        if self.side:
            self.cache = self._with_side_buffers(self.cache)
        self._blank1 = _blank_cache(self._prefill_model, 1)  # prefill cache
        # what the state layers keep for ONE lane, all of them together
        # (fixed at construction: num_slots bounds it, where the pool
        # bounds the rows)
        self._state_lane_bytes = sum(
            leaf.nbytes for node in _state_nodes(self.cache)
            for leaf in node.values()) // num_slots
        if self.pool is not None and _kv_leaves(
                self._paged_nodes(self.cache)[0], "paged") != ["key",
                                                               "value"]:
            # the loop itself moves whatever leaves a layer declares; the
            # payloads that LEAVE it (handoff, migration, the host tier's
            # spill) are still written as a K/V pair a layer
            if role != "both" or preempt == "migrate":
                raise ValueError(
                    "KV handoff and migration payloads carry a key/value "
                    "pair a layer; a cache of other leaves (a latent row; "
                    "an indexer's key beside K and V) serves with "
                    "role='both' and preempt='degrade'")
            if self._tier is not None:
                self._tier = None
                self._prefix_cache.spill_hook = None
        # what ONE decode step's paged kernel calls put on the grid: the
        # rows of a call (paged_grid_rows, which the call itself takes its
        # grid= from: a lane's K/V heads share a row where their tiles fit
        # VMEM) times the attention layers, each one call a step; 0 where
        # no kernel runs (the dense layout, the gather fallback)
        self._grid_rows = self._attn_layers = self._row_heads = 0
        # the device arrays _stamp_table makes a dispatch: a table a layer
        self._table_copies = (len(self._paged_nodes(self.cache))
                              if self.pool is not None else 0)
        if self.pool is not None and decode_attention == "flash":
            nodes = self._paged_nodes(self.cache)
            leaves = _kv_leaves(nodes[0], "paged")
            # what the attention kernel walks: K and V of the K/V heads,
            # in a pool each or (an indexer's layer, whose index keys have
            # a walk of their own) side by side in one that counts as the
            # two; or the latent rows, one head of the row's width
            pool0 = nodes[0]["paged_" + leaves[-1]]    # never the ikey
            if {"key", "value"} <= set(leaves) or "kv" in leaves:
                # numbers of the compute dtype, whatever row holds them
                h_kv, d_head, n_pools = cfg.kv_heads, cfg.head_dim, 2
                itemsize = jnp.dtype(cfg.compute_dtype).itemsize
            else:
                h_kv, d_head, n_pools = 1, pool0.shape[2], len(leaves)
                itemsize = pool0.dtype.itemsize
            self._grid_rows = paged_grid_rows(
                num_slots, h_kv, d_head, self.kv_block_size,
                self.pool.max_blocks_per_slot, pools=n_pools,
                itemsize=itemsize)
            self._attn_layers = len(nodes)
            # the K/V heads ONE grid row serves, by that fold: a lane whose
            # heads do not all fit the kernel's tile budget takes several
            self._row_heads = h_kv * num_slots // self._grid_rows
        # expert layers (cfg.moe): the segment sums, step by step, the
        # tokens each held expert was given and returns the sums as extra
        # rows of the emit buffer it already returns
        self._expert_blocks = [i for i in range(cfg.num_layers)
                               if cfg.is_expert_layer(i)]
        self._held = ((cfg.moe.held or (0, cfg.moe.num_experts))[1]
                      if self._expert_blocks else 0)
        self._tok = jnp.full((num_slots,), self.pad_token, jnp.int32)
        self._active = jnp.zeros((num_slots,), bool)
        self._remaining = jnp.zeros((num_slots,), jnp.int32)
        # deferred first-from-prefill tokens, one lane per slot: admission
        # stamps it on device; the next segment's emits carry it to the
        # host as column 0 — so resolving a first token costs ZERO extra
        # transfers (a per-slot int() fetch is one blocking
        # device->host sync per admission)
        self._first = jnp.full((num_slots,), self.pad_token, jnp.int32)
        # obs handles cached once; recording on the serve loop is host
        # ints/floats only, never a device fetch
        self.max_queue = None if max_queue is None else int(max_queue)
        if degrade_queue is None and max_queue is not None:
            degrade_queue = max(1, max_queue // 2)
        if degrade_queue is not None and degrade_queue < 0:
            raise ValueError(
                f"degrade_queue must be >= 0, got {degrade_queue}")
        if degrade_max_new < 1:
            raise ValueError(
                f"degrade_max_new must be >= 1, got {degrade_max_new}")
        self.degrade_queue = (None if degrade_queue is None
                              else int(degrade_queue))
        self.degrade_max_new = int(degrade_max_new)
        self._degraded = False
        # deadline clock, swappable by tests (deterministic expiry
        # without real sleeps); production uses wall time because
        # Request.deadline_s crosses process boundaries via the router
        self._clock = time.time
        # drain-gated weight hot-swap (see request_swap): set by
        # request_swap, consumed by run() once the loop is fully drained
        self._pending_swap: dict | None = None
        self._obs_requests = obs.counter("serve/requests", unit="reqs")
        self._obs_tokens = obs.counter("serve/tokens", unit="tokens")
        # prefix-sharing accounting: prompt_tokens is every admitted
        # prompt position, prefill_tokens only the positions actually
        # recomputed (the suffix past the cached prefix) — their ratio
        # is the prefill work the cache saved
        self._obs_prompt_tokens = obs.counter("serve/prompt_tokens",
                                              unit="tokens")
        self._obs_prefill_tokens = obs.counter("serve/prefill_tokens",
                                               unit="tokens")
        self._obs_rejected = obs.counter("serve/rejected", unit="reqs")
        self._obs_timeouts = obs.counter("serve/timeouts", unit="reqs")
        # data-plane integrity: lanes the in-graph NaN/inf logit guard
        # froze, plus host-side token-range failures — either way the
        # request finishes reason="corrupt_segment" (the router's cue
        # to redispatch and strike this replica) instead of emitting
        # garbage as if it were output
        self._obs_corrupt = obs.counter("serve/corrupt_segments",
                                        unit="segments")
        # lifetime tokens drained to the host: the trip point for the
        # TPUDIST_FAULT_NAN_AFTER_TOKENS injection
        self._served_tokens = 0
        # ticked once per DRAINED segment with that segment's sums:
        # tokens appended to requests (what _served_tokens counts), the
        # decode steps the segment's while_loop ran before its last lane
        # froze, and num_slots times that (the lane-steps it paid for)
        self._obs_tokens_drained = obs.counter("serve/tokens_drained",
                                               unit="tokens")
        self._obs_decode_steps = obs.counter("serve/decode_steps",
                                             unit="steps")
        self._obs_lane_steps = obs.counter("serve/lane_steps",
                                           unit="steps")
        # paged layout: pages the decode kernel walked, a layer — the
        # segment's live pages at dispatch times the steps it ran.  Over
        # lane_steps x max_blocks_per_slot it is the share of the page
        # table a walk touches
        self._obs_pages_walked = obs.counter("serve/decode_pages_walked",
                                             unit="pages")
        # the rows the kernel's arithmetic covered for those pages
        # (ops.flash_decode.walk_rows of each live lane's length) and the
        # live ones among them (the lengths), ticked the same way: live
        # over computed is the share of the kernel's scores, exponentials
        # and MXU passes that land on a row the softmax keeps
        self._obs_rows_computed = obs.counter("serve/decode_rows_computed",
                                              unit="rows")
        self._obs_rows_live = obs.counter("serve/decode_rows_live",
                                          unit="rows")
        # a model with an indexer: the index keys a decode step's scores
        # read (the live lanes' lengths) and the rows its attention reads
        # (min(length, index_topk) a lane), a layer, ticked the same way
        self._obs_rows_scored = obs.counter("serve/index_rows_scored",
                                            unit="rows")
        self._obs_rows_selected = obs.counter("serve/index_rows_selected",
                                              unit="rows")
        # and the rows the gathers of its chosen rows fetched: every lane's
        # index_topk a gather, a layer, in each step that took the
        # chosen-rows branch (some lane held more than a query attends)
        self._obs_rows_gathered = obs.counter("serve/rows_gathered",
                                              unit="rows")
        # and the 32-bit words a gathered row is (a token's K and V in
        # ``paged_kv``, ``ops.flash_decode.kv_row``: kv_heads x head_dim
        # where two 16-bit numbers share a word, twice that where a number
        # is a word; 0 for a model without an indexer): the layout is not
        # an option, so this is how a run says which it had
        self._kv_row_words = 0
        if self._index_topk is not None:      # paged: refused otherwise
            row = self._paged_nodes(self.cache)[0]["paged_kv"]
            self._kv_row_words = row.shape[2] * row.dtype.itemsize // 4
        obs.gauge("serve/kv_row_words", unit="words").set(
            self._kv_row_words)
        # K and V of ONE token in ONE attention layer, in bytes (0 for a
        # model whose layers keep no K/V heads: latent rows), and the K/V
        # heads one grid row of a paged walk serves (0 where no kernel runs)
        obs.gauge("serve/kv_row_bytes", unit="bytes").set(
            0 if cfg.mla is not None else 2 * cfg.kv_heads * cfg.head_dim
            * jnp.dtype(cfg.compute_dtype).itemsize)
        obs.gauge("serve/kv_heads_per_grid_row", unit="heads").set(
            self._row_heads)
        # the same two of the WINDOW layers' calls (a layer): walk_rows
        # with the window, and min(length, window)
        self._obs_rows_window = obs.counter(
            "serve/decode_rows_window_computed", unit="rows")
        self._obs_rows_window_live = obs.counter(
            "serve/decode_rows_window_live", unit="rows")
        # the grid rows the paged kernel's calls took: rows a call x
        # attention layers x the steps a drained segment ran.  Over
        # lane_steps x layers it is the rows a lane costs a call: 1 where
        # a lane's K/V heads share a row, their number where each has its
        # own
        self._obs_grid_rows = obs.counter("serve/decode_grid_rows",
                                          unit="rows")
        # expert layers: tokens the held experts were given over a drained
        # segment's steps (all lanes, as lane_steps counts them), the
        # busiest (layer, expert)'s part of that, and the (step, layer,
        # expert) slots they were spread over.  expert_tokens over
        # lane_steps x top_k x expert layers is the share of the routed
        # work that lands here: held / num_experts under an even router
        self._obs_expert_tokens = obs.counter("serve/expert_tokens",
                                              unit="tokens")
        self._obs_expert_tokens_max = obs.counter(
            "serve/expert_tokens_max", unit="tokens")
        self._obs_expert_slots = obs.counter("serve/expert_slots",
                                             unit="slots")
        self._obs_segments = obs.counter("serve/segments", unit="segments")
        # state layers: bytes of state the decoding lanes held when the
        # last segment was dispatched (lanes x _state_lane_bytes; 0 for a
        # model without such layers)
        self._obs_state_bytes = obs.gauge("serve/state_bytes", unit="bytes")
        self._obs_queue = obs.gauge("serve/queue_depth", unit="reqs")
        self._obs_degraded = obs.gauge("serve/degraded", unit="bool")
        self._obs_degrade_clamped = obs.counter("serve/degrade_clamped",
                                                unit="reqs")
        # both from ENQUEUE, on the loop's perf_counter: to the drain
        # that brought the first token to the host, and to the completion
        self._obs_ttft = obs.histogram("serve/ttft_s", unit="s")
        self._obs_latency = obs.histogram("serve/request_latency", unit="s")
        # segments dispatched between a request's admission and the first
        # segment that carries it: what its lane stood filled and not
        # decoding (ints the loop holds; 0 for a one-chunk prompt)
        self._obs_admit_segments = obs.histogram("serve/admit_segments",
                                                 unit="segments")
        # enqueue -> admit: how long requests sit behind busy lanes (and,
        # paged, behind a full block pool).  Sliding-window so the SLO
        # gate and the autoscaler react to the LAST minute, not the
        # process lifetime; <= 0 disables the window.
        wait_window = float(
            os.environ.get("TPUDIST_SERVE_WAIT_WINDOW_S", "60"))
        self._obs_queue_wait = obs.histogram(
            "serve/queue_wait_s", unit="s",
            window_s=wait_window if wait_window > 0 else None)
        # host_wait = time run() actually BLOCKS on a segment fetch (the
        # np.asarray tail not hidden by later segments' compute); depth
        # is the live in-flight segment count
        self._obs_host_wait = obs.histogram("serve/host_wait", unit="s")
        self._obs_depth = obs.gauge("serve/pipeline_depth", unit="segments")
        self._obs_swaps = obs.counter("serve/swaps", unit="swaps")
        self._obs_weights_version = obs.gauge("serve/weights_version",
                                              unit="version")
        # RTT-amortization observability: dispatches counts host round
        # trips, steps_per_dispatch is the tokens the last drained
        # dispatch generated — their ratio is the amortization factor
        # the router merges per replica
        self._obs_dispatches = obs.counter("serve/dispatches",
                                           unit="dispatches")
        self._obs_steps_per_dispatch = obs.gauge("serve/steps_per_dispatch",
                                                 unit="tokens")
        # EMA of seconds per generated token as the deadline clamp in
        # _plan_steps sees them: dispatch -> drain wall time / tokens of
        # the segment.  Under pipelining that wall spans the segment in
        # front too, so it OVERestimates (which only clamps harder): the
        # clamp's control input, not a service rate.  Published as a
        # gauge and stamped into segment events (the fleet simulator
        # replays it).
        self._step_ema: float | None = None
        self._obs_spt = obs.gauge(
            "serve/seconds_per_token", unit="s",
            help="deadline clamp's control input: EMA of dispatch->drain "
                 "wall / tokens of a segment (an overestimate under "
                 "pipelining; not a service rate)")
        # donate every rebound carry: cache, tok, active, remaining, key
        # (argnums 2-4 and 6) mirror _admit_dev — their inputs are dead
        # the moment the segment returns replacements.  `first` (argnum 5)
        # is NOT donated: self._first persists across segments.
        self._segment = jax.jit(self._segment_impl,
                                donate_argnums=(1, 2, 3, 4, 6))
        # params is a jit ARGUMENT (a closure capture would lower the
        # whole parameter tree into the traced program as duplicated
        # constants — and would pin
        # first-trace weights if self.params is ever rebound)
        self._admit_dev = jax.jit(self._admit_dev_impl,
                                  donate_argnums=(1, 2, 3, 4, 5),
                                  static_argnames=("true_chunk",))
        # standalone prefill: admission's device work without touching
        # live state
        self._prefill_one = jax.jit(self._prefill_impl,
                                    static_argnames=("true_chunk",))
        if cache_layout == "paged":
            # disaggregated handoff adoption: one dispatch scatters the
            # migrated KV blocks into this pool's pages and stamps the
            # lane (the decode-side mirror of _admit_finish, minus any
            # prefill).  Compiled per distinct used-block count, which
            # max_blocks_per_slot bounds.
            self._adopt_dev = jax.jit(self._adopt_dev_impl,
                                      donate_argnums=(0, 1, 2, 3, 4))
            # tiered-KV install: scatter re-admitted (host-tier or
            # pull-mode) blocks into pool pages — the page-write half
            # of adoption with NO lane stamps, because the blocks land
            # as cached-idle prefix entries rather than a live slot.
            # Compiled per distinct block count, like _adopt_dev.
            self._install_dev = jax.jit(self._install_dev_impl,
                                        donate_argnums=(0,))
        # disaggregation accounting: adoptions took the migrated-KV
        # path; fallbacks re-prefilled because the payload was missing
        # or failed verification (both exact by construction — the
        # counters tell which path a request took)
        self._obs_adoptions = obs.counter("serve/adoptions", unit="reqs")
        self._obs_handoff_fallbacks = obs.counter(
            "serve/handoff_fallbacks", unit="reqs")
        # live-migration accounting (PR 19): preempted/resumed count the
        # LOCAL park/unpark cycle (priority preemption), migrated_out
        # counts reason="migrate" exports handed to the router
        # (rebalance + fast drain) — the fleet-level mirror lives on
        # router/migrations
        self._obs_preempted = obs.counter("serve/preempted", unit="reqs")
        self._obs_resumed = obs.counter("serve/resumed", unit="reqs")
        self._obs_migrated_out = obs.counter("serve/migrated_out",
                                             unit="reqs")
        # rid -> parked entry: the request, its original enqueue time,
        # and the exported payload — either whole ("payload") or with
        # its page bytes spilled per-block into the host tier ("keys",
        # "meta"); loss anywhere falls back to re-prefill, byte-exact
        self._parked: dict[str, dict] = {}
        # router-initiated migration intents, consumed by the run loop:
        # request keys to migrate out (rebalance) / evacuate-everything
        # (fast drain, fast swap)
        self._migrate_rids: set[str] = set()
        self._evacuate = False
        if self.chunked:
            # chunked admission's three dispatches: (a) gather a shared
            # prefix's pool blocks into the dense batch-1 prefill cache
            # (reads self.cache without donating — the segment chain
            # donates it later, which is fine sequentially), (b) ONE
            # prompt chunk per host-loop iteration (cache1 is NOT
            # donated: the first chunk may receive the shared _blank1
            # template), (c) the finish: insert + lane stamps, donating
            # the live carry exactly like _admit_dev
            self._gather_prefix = jax.jit(self._gather_prefix_impl)
            self._prefill_chunk = jax.jit(self._prefill_chunk_impl,
                                          static_argnames=("chunk",))
            self._admit_finish = jax.jit(self._admit_finish_impl,
                                         donate_argnums=(0, 1, 2, 3, 4))

    def _with_side_buffers(self, cache):
        def walk(node):
            if not isinstance(node, dict):
                return node
            out = {k: walk(v) for k, v in node.items()}
            # the cache (and therefore the side buffers) is PACKED
            # [B, S, F] — see CausalSelfAttention._cached_attend; a paged
            # pool is [num_blocks, block, F] and its side buffers are
            # per-SLOT, so their batch is self.B, not the pool's
            for prefix in ("cached", "paged"):
                for name in _kv_leaves(out, prefix):
                    main = out[f"{prefix}_{name}"]
                    out[f"side_{name}"] = jnp.zeros(
                        (self.B, self.side, main.shape[2]), main.dtype)
                    out["side_index"] = jnp.zeros((), jnp.int32)
            return out
        return walk(cache)

    def serve_programs(self) -> dict:
        """``{program: (jitted, args, static kwargs)}`` of the compiled
        serve programs at THIS loop's shapes, for ``.lower(*args,
        **static)``: the segment and, with chunked admission, one prompt
        chunk and the finish, under the names their runs carry in a trace
        (``jit_<name>``).  Nothing is dispatched; the arguments are the
        loop's own arrays or shapes."""
        out = {"_segment_impl": (self._segment, (
            self.params, self.cache, self._tok, self._active,
            self._remaining, self._first, self._key, np.int32(self.steps),
            np.bool_(False)), {})}
        if self.chunked:
            c = min(self.prefill_chunk, self.cfg.max_seq_len)
            chunk = (self.params, self._blank1, np.zeros((1, c), np.int32),
                     np.int32(0), np.int32(0))
            out["_prefill_chunk_impl"] = (self._prefill_chunk, chunk,
                                          {"chunk": c})
            # the chunk hands back its cache, shaped as it came, and the
            # one row of logits it was asked for
            logits = jax.ShapeDtypeStruct((1, 1, self.cfg.vocab_size),
                                          jnp.float32)
            pages = (self._slot_pages(0) if self.pool is not None
                     else _NO_PAGES)
            out["_admit_finish_impl"] = (self._admit_finish, (
                self.cache, self._tok, self._active, self._remaining,
                self._first, self._blank1, logits, np.int32(0), np.int32(1),
                np.int32(0), np.int32(1), pages, np.int32(0), self._key),
                {})
        return out

    def scope_map(self) -> dict[str, dict[str, str]]:
        """``{program: {instruction name: routine scope}}`` from the
        compiled programs' HLO text (:func:`hlo_scopes`): how a reader of a
        device trace, whose ``XLA Ops`` events carry an instruction's text
        and no ``op_name``, gets from an event inside a run of ``program``
        to its routine.  Built on demand by lowering and compiling
        :meth:`serve_programs` (a persistent compilation cache answers for
        programs this process already compiled); never on the serving
        path."""
        return {name: hlo_scopes(
            jitted.lower(*args, **static).compile().as_text())
            for name, (jitted, args, static) in self.serve_programs().items()}

    def _stamp_table(self) -> None:
        """Push the host allocator's page table into the device carry.
        Each layer gets a FRESH device array: the segment donates the
        whole cache, and one buffer shared across every layer's
        ``page_table`` leaf would be donated more than once."""
        wg = self.pool.window_group
        # a SNAPSHOT of the window group's table: the host zeroes a live
        # lane's released entries in place while an earlier segment may
        # not have run yet, and on the CPU backend a device array can
        # alias the numpy buffer it was made from.  (The full group's
        # entries of a live lane never change, so its table goes as ever.)
        window_tbl = None if wg is None else wg.table.copy()

        def walk(node, window=None):
            if not isinstance(node, dict):
                return node
            out = {k: walk(v, self._window_of.get(k, window))
                   for k, v in node.items()}
            if "page_table" in out:
                # a layer's own group's table
                out["page_table"] = jnp.asarray(
                    self.pool.table if window is None or wg is None
                    else window_tbl)
            return out

        self.cache = walk(self.cache)

    # -- compiled pieces ---------------------------------------------------

    def _segment_impl(self, params, cache, tok, active, remaining, first,
                      key, n_steps, poison):
        """One fused multi-token segment: a ``lax.while_loop`` of up to
        ``n_steps`` decode ticks (``n_steps`` is a DYNAMIC arg — the
        deadline clamp in :meth:`_plan_steps` shortens segments without
        recompiling) that EXITS EARLY once every lane is frozen, so an
        almost-idle batch never pays full-length segments.  The emit
        buffer is fixed at ``steps_per_sync`` columns (pad-filled past
        ``n_steps``); the host slices to the dispatched length.

        ``poison`` (dynamic bool, normally False) NaN-floods the step's
        logits — the TPUDIST_FAULT_NAN_AFTER_TOKENS injection point,
        kept as a dynamic arg so fault runs reuse the clean executable.
        The integrity guard below it is always on: a lane whose logits
        go NaN/inf is frozen IN-GRAPH before its garbage token reaches
        the emit buffer, and reported in the per-lane ``corrupt``
        output so the host can finalize it ``corrupt_segment``."""
        stop_arr = self._stop
        pad = jnp.int32(self.pad_token)
        S = self.cfg.max_seq_len

        def cond(carry):
            return (carry[0] < n_steps) & jnp.any(carry[3])

        experts = self._expert_blocks

        def step(carry):
            (i, cache, tok, active, remaining, lived, corrupt, key, E,
             X) = carry
            main_idx, side_idx = _index_leaves(cache)
            pos = main_idx if side_idx is None else main_idx + side_idx
            pos = jnp.minimum(pos, S - 1)
            # a row active at step ENTRY writes a real token's K/V this
            # step — the merge later scatters exactly these side slots
            lived = lived + active.astype(jnp.int32)
            # a lane that is frozen, empty or past its budget computes a
            # step like any other; rows of a cache shrug that off, a STATE
            # must be told (``lived`` above counts by the same rule)
            owned = ({"valid": active[:, None]} if self._state_layers
                     else {})
            logits, mut = self.model.apply(
                {"params": params, "cache": cache}, tok[:, None],
                positions=pos[:, None], **owned,
                mutable=["cache", "stats"] if experts else ["cache"])
            if experts:
                X = X + jnp.stack([
                    mut["stats"][f"block{b}"]["moe"]["expert_tokens"]
                    for b in experts])
            with obs.routine("head"):
                last = logits[:, -1]
                last = jnp.where(poison, jnp.full_like(last, jnp.nan), last)
                # integrity guard: freeze (not emit) lanes whose logits
                # are no longer finite — overflowed accumulator, scrambled
                # KV page, injected fault — so corruption surfaces as a
                # verdict instead of as plausible-looking tokens
                bad = active & ~jnp.all(jnp.isfinite(last), axis=-1)
                corrupt = corrupt | bad
                active = active & ~bad
                key, sk = jax.random.split(key)
                nxt = self._select(last, sk).astype(jnp.int32)
                emit = jnp.where(active, nxt, pad)
                E = lax.dynamic_update_slice(E, emit[:, None], (0, i))
            remaining = remaining - active.astype(jnp.int32)
            hit_stop = (jnp.isin(nxt, stop_arr)
                        if stop_arr is not None
                        else jnp.zeros_like(active))
            active = active & ~hit_stop & (remaining > 0)
            tok = jnp.where(active, nxt, pad)
            return (i + 1, mut["cache"], tok, active, remaining, lived,
                    corrupt, key, E, X)

        lived0 = jnp.zeros((self.B,), jnp.int32)
        corrupt0 = jnp.zeros((self.B,), bool)
        E0 = jnp.full((self.B, self.steps), pad, jnp.int32)
        X0 = jnp.zeros((len(experts), self._held), jnp.int32)
        with obs.routine("attn/cache"):
            cache = _bound_paged_walk(cache, active)
        (_, cache, tok, active, remaining, lived, corrupt, key,
         E, X) = lax.while_loop(
            cond, step,
            (jnp.int32(0), cache, tok, active, remaining, lived0,
             corrupt0, key, E0, X0))
        if self.side:
            # side -> main merge INSIDE the segment executable: one
            # dispatch per wave instead of two, and XLA can overlap
            # the merge with the tail of the loop
            with obs.routine("attn/cache"):
                cache = self._merge_impl(cache, lived)
        # column 0 carries the admission-deferred first tokens so ONE
        # host fetch resolves them together with the segment's emits
        emits = jnp.concatenate([first[:, None], E], axis=1)
        if experts:
            # [expert layers, held] counts ride home as whole extra rows
            # below the lanes': the one fetch brings them
            width = emits.shape[1]
            flat = X.reshape(-1)
            flat = jnp.pad(flat, (0, -flat.shape[0] % width))
            emits = jnp.concatenate([emits, flat.reshape(-1, width)], axis=0)
        return cache, tok, active, remaining, key, emits, corrupt

    def _prefill_impl(self, params, prompt_padded, true_len, key,
                      *, true_chunk):
        """Chunked prefill of ONE prompt into a fresh batch-1 cache;
        returns the cache (index stamped to the TRUE length — padded
        positions hold garbage that masking hides and decode overwrites)
        and the first generated token."""
        cache, logits = _prefill(self._prefill_model, params, self._blank1,
                                 prompt_padded, true_chunk)
        cache = _set_cache_index(cache, true_len)
        with obs.routine("head"):
            last = logits[0, true_len - 1 - (prompt_padded.shape[1]
                                             - logits.shape[1])]
            first = self._select(last[None, :], key)[0].astype(jnp.int32)
        return cache, first

    def _insert_impl(self, cache, cache1, slot, true_len, pages,
                     write_block=0, off_last=0):
        """Scatter the prefilled batch-1 cache into slot ``slot`` —
        matched BY NAME because the slot cache carries side buffers the
        prefill cache does not (they are left untouched: side_index is 0
        between segments and stale side rows are masked).  Paged nodes
        are intercepted whole: the prefill cache is always DENSE and its
        row is re-blocked into the slot's pages.  ``write_block`` skips
        the scatter below that block index — a shared-prefix admission
        must not rewrite blocks other slots alias (its page row still
        maps them; only the suffix's private blocks take writes).  With a
        window block group ``pages`` is the pair (full group's row, window
        group's row) and ``off_last`` the offset of the prompt's last
        prefill chunk, which places the windowed layers' rolling rows."""
        def walk(big, small, window=None):
            if not isinstance(big, dict):
                if big.ndim == 1:      # cache_index vector <- true length
                    return big.at[slot].set(true_len)
                return big.at[slot].set(small[0])
            if "page_table" in big:
                if isinstance(pages, tuple) and window is not None:
                    return self._insert_window_node(
                        big, small, slot, true_len, pages[1], off_last)
                return self._insert_paged_node(
                    big, small, slot, true_len,
                    pages[0] if isinstance(pages, tuple) else pages,
                    write_block)
            return {k: (walk(v, small[k], self._window_of.get(k, window))
                        if k in small else v)
                    for k, v in big.items()}
        return walk(cache, cache1)

    def _insert_paged_node(self, big, small, slot, true_len, pages,
                           write_block=0):
        """Scatter one layer's dense batch-1 prefill row into the block
        pool through the slot's page row: the ``[S, F]`` row reshapes to
        ``[M, block, F]`` blocks and lands at pool indices ``pages``;
        blocks past the prompt's coverage — and below ``write_block``
        (shared-prefix blocks owned by the cache) — target the
        (out-of-range) index ``num_blocks`` and are DROPPED — only this
        admission's own allocated pages are written, so no live or
        cached block of another owner can be hit.  A pool leaf whose row
        is several dense leaves side by side (:func:`_dense_parts`: an
        indexer's layer's ``kv``) has them joined a scatter's blocks at a
        time.

        Two scatters a leaf, the second under a ``cond``: the chip runs
        a scatter's updates one after another, landed or dropped, so
        the blocks two prefill chunks cover go first and the rest of
        the page row (``max_seq_len / block`` entries) is walked only
        for a prompt that reaches it.  The finish rides in the
        iteration of the admission's last chunk, between two decode
        segments, and most prompts are a chunk or two."""
        out = dict(big)
        bs = self.kv_block_size
        m = pages.shape[0]
        covered = ((jnp.arange(m) * bs < true_len)
                   & (jnp.arange(m) >= write_block))
        head = min(m, max(1, 2 * self.prefill_chunk // bs))
        for leaf in _kv_leaves(big, "paged"):
            name = f"paged_{leaf}"
            tgt = jnp.where(covered, pages, big[name].shape[0])
            parts = []
            for dense in _dense_parts(leaf):
                row = small[f"cached_{dense}"][0]     # dense [S, F]
                pad = m * bs - row.shape[0]
                parts.append(jnp.pad(row, ((0, pad), (0, 0)))
                             .reshape(m, bs, -1))

            def blocks(lo, hi):
                """Pool rows of the blocks ``[lo, hi)``: the dense leaf's,
                or the dense leaves' packed HERE (their bits: a cast to
                the pool's dtype would take words for numbers), for the
                blocks one scatter takes (inside its branch, where it has
                one): no packed copy of a whole prompt's rows outlives its
                layer's scatter."""
                if len(parts) == 1:
                    return parts[0][lo:hi].astype(big[name].dtype)
                rows = pack_kv(*(p[lo:hi] for p in parts))
                if (rows.dtype, rows.shape[2:]) != (big[name].dtype,
                                                    big[name].shape[2:]):
                    raise ValueError(
                        f"{name} holds rows of {big[name].shape[2:]} "
                        f"{big[name].dtype}; the prefill cache's K and V "
                        f"pack to {rows.shape[2:]} {rows.dtype}")
                return rows

            pool = big[name].at[tgt[:head]].set(blocks(0, head),
                                                mode="drop")
            if head < m:
                # traced here and now, so the closure sees this leaf
                pool = lax.cond(
                    true_len > head * bs,
                    lambda p: p.at[tgt[head:]].set(
                        blocks(head, m), mode="drop"),
                    lambda p: p, pool)
            out[name] = pool
        out["page_table"] = big["page_table"].at[slot].set(pages)
        out["cache_index"] = big["cache_index"].at[slot].set(true_len)
        return out

    def _insert_window_node(self, big, small, slot, true_len, pages,
                            off_last):
        """A WINDOWED layer's insert: its batch-1 cache is the rolling
        buffer ``_rolling_prefill`` left (row ``i`` holds position
        ``max(0, off_last - window) + i``) and its group holds the blocks
        from the one with row ``true_len + 1 - window`` (the first row the
        first decode step sees) to the prompt's last: those, at most what
        ``window - 1`` rows touch, are gathered a block at a time and
        scattered to the group's pages; an index past the prompt drops.
        Rows of the first block that lie before the buffer's base are
        whatever the clip finds: they are below the window for good."""
        out = dict(big)
        bs, w = self.kv_block_size, self._window
        base = jnp.maximum(off_last - w, 0)
        n_w = max(1, span_blocks(w - 1, bs))
        j = jnp.maximum(true_len + 1 - w, 0) // bs + jnp.arange(n_w)
        m = pages.shape[0]
        pos = j[:, None] * bs + jnp.arange(bs)[None, :]       # [n_w, bs]
        for leaf in _kv_leaves(big, "paged"):
            name = f"paged_{leaf}"
            row = small[f"cached_{leaf}"][0]                  # [rows, F]
            tgt = jnp.where(j * bs < true_len,
                            pages[jnp.minimum(j, m - 1)],
                            big[name].shape[0])
            blocks = row[jnp.clip(pos - base, 0, row.shape[0] - 1)]
            out[name] = big[name].at[tgt].set(
                blocks.astype(big[name].dtype), mode="drop")
        out["page_table"] = big["page_table"].at[slot].set(pages)
        out["cache_index"] = big["cache_index"].at[slot].set(true_len)
        return out

    def _admit_dev_impl(self, params, cache, tok, active, remaining,
                        first_buf, prompt_padded, true_len, slot, max_new,
                        pages, key, *, true_chunk):
        """The WHOLE of admission's device work — chunked prefill of the
        prompt into a fresh batch-1 cache, insertion into the freed slot,
        and the slot's token/active/budget lane stamps (plus the
        deferred-first lane the next segment's emits carry home) — in
        ONE dispatch with no host sync.  The first token's stop check
        runs on device too: the host learns the token's value at the
        NEXT segment sync, by which time the prefill has long finished
        (chunked-prefill overlap: admission stalls the decode cadence by
        dispatch time only, not the prefill's round trip)."""
        cache1, first = self._prefill_impl(
            params, prompt_padded, true_len, key, true_chunk=true_chunk)
        width = prompt_padded.shape[1]
        chunk = min(true_chunk, width)
        with obs.routine("attn/cache"):
            cache = self._insert_impl(cache, cache1, slot, true_len, pages,
                                      off_last=(width - 1) // chunk * chunk)
        tok = tok.at[slot].set(first)
        act = max_new > 1
        if self._stop is not None:
            act = act & ~jnp.isin(first, self._stop)
        active = active.at[slot].set(act)
        remaining = remaining.at[slot].set(max_new - 1)
        first_buf = first_buf.at[slot].set(first)
        return cache, tok, active, remaining, first_buf

    # -- chunked-interleaved admission (see chunked_prefill) ---------------

    def _gather_prefix_impl(self, cache, blank1, pages):
        """Build a fresh batch-1 dense prefill cache whose leading rows
        hold a shared prefix's KV gathered from pool blocks ``pages``
        (the slot's full padded page row).  Rows past the prefix carry
        whatever lives in the referenced blocks — suffix chunks
        overwrite the covered span and attention never reads past the
        write cursor, so the garbage is unreachable.  KV bytes come
        straight from the original admission's prefill, which is what
        makes a cache-hit admission bitwise-identical to recomputing."""
        def walk(big, small):
            if not isinstance(small, dict):
                return small
            if "page_table" in big:
                out = dict(small)
                for leaf in _kv_leaves(big, "paged"):
                    pname, dname = f"paged_{leaf}", f"cached_{leaf}"
                    rows = big[pname][pages]          # [M, bs, F]
                    flat = rows.reshape(-1, rows.shape[-1])
                    S = small[dname].shape[1]
                    flat = flat[:S]
                    if flat.shape[0] < S:
                        flat = jnp.pad(
                            flat, ((0, S - flat.shape[0]), (0, 0)))
                    out[dname] = flat[None].astype(small[dname].dtype)
                return out
            return {k: (walk(big[k], v) if k in big else v)
                    for k, v in small.items()}
        return walk(cache, blank1)

    def _prefill_chunk_impl(self, params, cache1, toks, off, row=None, *,
                            chunk):
        """ONE prompt chunk through the scalar-index prefill path:
        write cursor forced to ``off`` (dynamic — every chunk of a given
        width shares one executable), positions ``off + [0, chunk)``.
        The chunk grid matches :func:`_prefill`'s exactly (same widths
        at the same offsets), so the per-chunk dispatches produce
        bitwise the same cache and logits as the fused one-shot path —
        chunking changes WHEN prefill work runs, never its result.

        ``row`` (dynamic): hand back that ONE row of the chunk's logits,
        ``[1, 1, V]``, the only one the finish reads, so that a lane in
        admission does not hold ``[1, chunk, V]`` float32 from its last
        chunk to its finish; None: all of them."""
        cache1 = _set_cache_index(cache1, off)
        # the prompt's tokens end at ``row`` (the loop asks for the row of
        # the prompt's last token, or a chunk's last): the padded rows of a
        # last chunk past it must not move a state
        owned = ({"valid": jnp.arange(chunk)[None, :] <= row}
                 if self._state_layers and row is not None else {})
        logits, mut = self._prefill_model.apply(
            {"params": params, "cache": cache1}, toks,
            positions=off + jnp.arange(chunk)[None, :], **owned,
            mutable=["cache"])
        if row is not None:
            with obs.routine("head"):
                logits = lax.dynamic_slice_in_dim(logits, row, 1, axis=1)
        return mut["cache"], logits

    def _admit_finish_impl(self, cache, tok, active, remaining, first_buf,
                           cache1, logits, off, true_len, slot, max_new,
                           pages, write_block, key):
        """The tail of a chunked admission, one dispatch: insert the
        prefilled batch-1 cache into the slot (skipping shared blocks
        below ``write_block``), sample the deferred first token from the
        LAST chunk's logits (position ``true_len - 1`` lives at row
        ``true_len - 1 - off`` of that chunk; a chunk program that was
        told the row hands back that row alone), stamp the lane."""
        with obs.routine("attn/cache"):
            cache1 = _set_cache_index(cache1, true_len)
            cache = self._insert_impl(cache, cache1, slot, true_len, pages,
                                      write_block=write_block, off_last=off)
        with obs.routine("head"):
            last = (logits[0, 0] if logits.shape[1] == 1
                    else lax.dynamic_index_in_dim(
                        logits[0], true_len - 1 - off, keepdims=False))
            first = self._select(last[None, :], key)[0].astype(jnp.int32)
        tok = tok.at[slot].set(first)
        act = max_new > 1
        if self._stop is not None:
            act = act & ~jnp.isin(first, self._stop)
        active = active.at[slot].set(act)
        remaining = remaining.at[slot].set(max_new - 1)
        first_buf = first_buf.at[slot].set(first)
        return cache, tok, active, remaining, first_buf

    def _adopt_dev_impl(self, cache, tok, active, remaining, first_buf,
                        kv, pages_used, full_row, true_len, slot,
                        max_new, first):
        """Adopt a MIGRATED prefill into ``slot``, one dispatch: the
        handoff's per-layer KV blocks scatter into this pool's freshly
        allocated pages and the lane stamps mirror
        :meth:`_admit_finish_impl`'s tail exactly — except ``first`` is
        the token the EXPORTER sampled (carried in the payload), not a
        local selection, so no prefill runs here at all.  ``kv`` walks
        the cache's paged nodes in natural dict order, the SAME order
        :meth:`_paged_nodes` exported them in: every replica builds an
        identical cache structure from the same model code, so index
        ``i`` here names the layer index ``i`` named there."""
        i = 0

        def walk(node):
            nonlocal i
            if not isinstance(node, dict):
                return node
            if "paged_key" in node:
                k, v = kv[i]
                i += 1
                out = dict(node)
                out["paged_key"] = node["paged_key"].at[pages_used].set(
                    k.astype(node["paged_key"].dtype))
                out["paged_value"] = (
                    node["paged_value"].at[pages_used].set(
                        v.astype(node["paged_value"].dtype)))
                out["page_table"] = (
                    node["page_table"].at[slot].set(full_row))
                out["cache_index"] = (
                    node["cache_index"].at[slot].set(true_len))
                return out
            return {key: walk(val) for key, val in node.items()}

        cache = walk(cache)
        tok = tok.at[slot].set(first)
        act = max_new > 1
        if self._stop is not None:
            act = act & ~jnp.isin(first, self._stop)
        active = active.at[slot].set(act)
        remaining = remaining.at[slot].set(max_new - 1)
        first_buf = first_buf.at[slot].set(first)
        return cache, tok, active, remaining, first_buf

    def _install_dev_impl(self, cache, kv, pages):
        """Scatter re-admitted KV blocks into pool pages ``pages`` —
        the page-write half of :meth:`_adopt_dev_impl` only: no page
        table, no cache index, no lane stamps.  The blocks become
        cached-idle prefix-cache entries (pinned, refcount 0); the
        admission that matches them aliases them in via the ordinary
        ``share`` path, which is what makes a tier re-admit or a peer
        pull byte-identical to having kept the pages in HBM all
        along."""
        i = 0

        def walk(node):
            nonlocal i
            if not isinstance(node, dict):
                return node
            if "paged_key" in node:
                k, v = kv[i]
                i += 1
                out = dict(node)
                out["paged_key"] = node["paged_key"].at[pages].set(
                    k.astype(node["paged_key"].dtype))
                out["paged_value"] = (
                    node["paged_value"].at[pages].set(
                        v.astype(node["paged_value"].dtype)))
                return out
            return {key: walk(val) for key, val in node.items()}

        return walk(cache)

    def _merge_impl(self, cache, lived):
        """End-of-segment: scatter each layer's side buffer into the main
        cache at every row's own offset (per-row-index writes, but ONCE
        per segment instead of once per step), advance the per-row
        lengths, reset the side counter.

        ``lived`` is the per-row count of REAL side tokens (steps the row
        entered active); the merge is masked to exactly those slots and
        the length advance uses it too, so a frozen row's garbage side
        writes never land in the main cache and its length never drifts —
        local correctness, not a host-loop invariant.  Near the cache end
        the cap-aligned write window shifts below ``idx[r]``; the side
        row is re-aligned by ``sh`` so live token ``t`` still lands at
        global position ``idx[r] + t`` and everything below ``idx[r]``
        rewrites the main cache's own (sliced-out) values."""
        B = self.B

        def walk(node):
            if not isinstance(node, dict):
                return node
            out = {k: walk(v) for k, v in node.items()}
            if "page_table" in out:
                return self._merge_paged_node(out, lived)
            if "side_index" in out:
                idx = out["cache_index"]
                leaves = _kv_leaves(out, "side")
                S = out[f"cached_{leaves[0]}"].shape[1]
                cap = out[f"side_{leaves[0]}"].shape[1]
                p = jnp.arange(cap)
                for leaf in leaves:
                    name = f"cached_{leaf}"
                    main = out[name]                 # packed [B, S, F]
                    side = out[f"side_{leaf}"]       # packed [B, cap, F]
                    for r in range(B):
                        start = jnp.minimum(idx[r], S - cap)
                        sh = idx[r] - start          # 0 unless near S
                        src = p - sh
                        cur = jax.lax.dynamic_slice(
                            main, (r, start, 0), (1, cap, main.shape[2]))
                        live = ((src >= 0) & (src < lived[r]))[
                            None, :, None]
                        shifted = side[r][jnp.clip(src, 0, cap - 1)][None]
                        merged = jnp.where(
                            live, shifted.astype(main.dtype), cur)
                        main = jax.lax.dynamic_update_slice(
                            main, merged, (r, start, 0))
                    out[name] = main
                out["cache_index"] = jnp.minimum(idx + lived, S)
                out["side_index"] = jnp.zeros((), jnp.int32)
            return out
        return walk(cache)

    def _merge_paged_node(self, out, lived):
        """End-of-segment side -> POOL merge for one paged layer: row
        ``r``'s live side token ``t`` lands at logical position
        ``idx[r] + t``, i.e. pool block ``table[r, pos // block]`` offset
        ``pos % block`` — a single two-axis scatter per buffer (unlike
        the dense merge there is no contiguous window to dynamic-slice;
        pages are scattered by construction).  Dead entries (frozen rows
        past ``lived``, positions past ``max_seq_len``) are redirected to
        the out-of-range pool index and DROPPED, so a frozen row's
        garbage side writes never reach a block — including blocks that
        the host has freed and re-admitted to another slot while this
        segment was in flight (the pipelined-staleness hazard)."""
        idx = out["cache_index"]                   # [B] main lengths
        tbl = out["page_table"]                    # [B, M]
        bs = self.kv_block_size
        S = self.cfg.max_seq_len
        leaves = _kv_leaves(out, "paged")
        n_pool = out[f"paged_{leaves[0]}"].shape[0]
        cap = out[f"side_{leaves[0]}"].shape[1]
        m = tbl.shape[1]
        t = jnp.arange(cap)[None, :]               # [1, cap]
        pos = idx[:, None] + t                     # [B, cap] logical
        live = (t < lived[:, None]) & (pos < S)
        blk = jnp.minimum(pos // bs, m - 1)
        page = jnp.take_along_axis(tbl, blk, axis=1)
        page = jnp.where(live, page, n_pool).reshape(-1)
        off = (pos % bs).reshape(-1)
        for leaf in leaves:
            name = f"paged_{leaf}"
            vals = out[f"side_{leaf}"].astype(out[name].dtype)
            out[name] = out[name].at[page, off].set(
                vals.reshape(-1, vals.shape[2]), mode="drop")
        out["cache_index"] = jnp.minimum(idx + lived, S)
        out["side_index"] = jnp.zeros((), jnp.int32)
        return out

    # -- the host loop -----------------------------------------------------

    def _validate(self, req: Request) -> None:
        prompt = np.asarray(req.prompt)
        if prompt.ndim != 1 or prompt.size < 1:
            raise ValueError("request prompt must be a non-empty 1-D "
                             "token array")
        if not np.issubdtype(prompt.dtype, np.integer):
            raise ValueError(
                f"request prompt must be integer token ids, got dtype "
                f"{prompt.dtype} (_admit's int32 cast would silently "
                "truncate float values)")
        if req.max_new_tokens < 1:
            raise ValueError("request max_new_tokens must be >= 1")
        if prompt.size + req.max_new_tokens > self.cfg.max_seq_len:
            raise ValueError(
                f"request needs {prompt.size + req.max_new_tokens} cache "
                f"slots > max_seq_len {self.cfg.max_seq_len}")
        if self.pool is not None:
            need = self.pool.request_blocks(prompt.size, req.max_new_tokens)
            if need > self.pool.num_blocks:
                raise ValueError(
                    f"request reserves {need} KV blocks > pool capacity "
                    f"{self.pool.num_blocks}; it could never be admitted "
                    "(raise kv_num_blocks or shrink the request)")

    def _prefix_plan(self, prompt: np.ndarray,
                     L: int) -> tuple[list[int], int, bool]:
        """Match ``prompt`` against the prefix cache: returns
        ``(shared_blocks, suffix_start, cow)``.  ``suffix_start`` is the
        first position prefill must actually compute; a FULL-prompt hit
        still recomputes position ``L - 1`` (the first output logit has
        to come from somewhere) and that write lands in the last shared
        block — the ``cow`` split.

        With a host tier, the chain walk CONTINUES past the HBM-resident
        run: spilled blocks extending the match are re-admitted (host ->
        HBM scatter into freshly pinned cached-idle pages) and aliased
        exactly like blocks that never left."""
        blocks = self._prefix_cache.match(prompt)
        if self._tier is not None and len(self._tier):
            chain = chain_hashes(prompt, self.kv_block_size)
            blocks = blocks + self._readmit_tiered(chain, len(blocks))
        if not blocks:
            return [], 0, False
        matched = len(blocks) * self.kv_block_size
        if matched >= L:
            return blocks, L - 1, True
        return blocks, matched, False

    def prefix_summary(self, limit: int = 64) -> list[int]:
        """Most recently admitted ``Request.prefix_hash`` values while
        prefix sharing is on — the replica's published affinity summary
        (the router steers matching requests here).  Empty when sharing
        is off: never advertise affinity this loop cannot honor."""
        return list(self._affinity_recent)[-limit:]

    def flush_prefix_cache(self) -> None:
        """Drop every cached prefix (idle blocks return to the free
        list) AND every host-tier entry.  Called automatically at
        weight hot-swaps — spilled KV is exactly as stale as resident
        KV — and by callers before asserting fully drained pool and
        tier."""
        if self._prefix_cache is not None:
            self._prefix_cache.flush()
        if self._tier is not None:
            self._tier.flush()
        self._affinity_recent.clear()

    # -- tiered KV memory (see tpudist.models.kv_tier) ---------------------

    def _spill_block(self, h: int, blk: int, parent: int | None) -> None:
        """PrefixCache spill hook: copy an evicted idle block's page
        bytes to the host tier before its pin (and page) drop.  The
        block is refcount-0 and still pinned here, so the bytes are
        stable; the ``np.asarray`` gather syncs the device — an
        eviction is already a capacity-pressure event, so the stall
        buys keeping a prefix instead of losing it."""
        layers = [{"k": np.asarray(node["paged_key"][blk]),
                   "v": np.asarray(node["paged_value"][blk])}
                  for node in self._paged_nodes(self.cache)]
        self._tier.put(h, layers, parent=parent,
                       version=self.weights_version)

    def _readmit_tiered(self, chain: list[int], start: int) -> list[int]:
        """Re-admit the longest run of tiered blocks extending a local
        chain match at index ``start``: take each entry (version-
        checked), land it in a freshly pinned cached-idle page, and
        index it back into the prefix cache.  Allocation never evicts —
        paging one cached block in must not page another out — so when
        only reclaimable-cached capacity is left the walk stops and the
        suffix re-prefills.  Returns the installed pool blocks, in
        chain order."""
        taken: list[tuple[int, int | None, int, list]] = []
        j = start
        while j < len(chain):
            if not self._tier.has(chain[j], version=self.weights_version):
                break
            blk = self.pool.alloc_cached_block()
            if blk is None:
                break
            layers = self._tier.take(chain[j],
                                     version=self.weights_version)
            if layers is None:   # unreachable after has(); stay safe
                self.pool.cache_unpin(blk)
                break
            taken.append((chain[j], chain[j - 1] if j else None,
                          blk, layers))
            j += 1
        self._scatter_install(taken)
        return [t[2] for t in taken]

    def _scatter_install(self,
                         taken: list[tuple[int, int | None, int, list]]
                         ) -> int:
        """One ``_install_dev`` dispatch landing ``taken``'s block
        bytes (``(hash, parent, pool_block, layers)`` each) into their
        pages, then the cache-index installs — host-ordered AFTER the
        scatter, so any later match's gather reads the written pages
        (the same ordering argument as register-after-insert)."""
        if not taken:
            return 0
        nodes = self._paged_nodes(self.cache)
        kv = tuple(
            (jnp.asarray(np.stack([np.asarray(t[3][li]["k"])
                                   for t in taken])),
             jnp.asarray(np.stack([np.asarray(t[3][li]["v"])
                                   for t in taken])))
            for li in range(len(nodes)))
        pages = jnp.asarray(
            np.asarray([t[2] for t in taken], np.int32))
        self.cache = self._install_dev(self.cache, kv, pages)
        for h, parent, blk, _ in taken:
            self._prefix_cache.install(h, blk, parent)
        return len(taken)

    def prefix_residency(self, limit: int = 256) -> dict:
        """Resident prefix chain hashes for the fleet directory:
        ``{"chains": [...], "tiered": [...]}`` — HBM prefix-cache
        entries plus host-tier entries (``tiered`` is the subset that
        lives in the tier), most-recently-used last, bounded."""
        if self._prefix_cache is None:
            return {"chains": [], "tiered": []}
        hbm = list(self._prefix_cache._entries)
        tiered = self._tier.hashes() if self._tier is not None else []
        chains = (hbm + tiered)[-int(limit):]
        tset = set(tiered)
        return {"chains": chains,
                "tiered": [h for h in chains if h in tset]}

    def export_prefix(self, chain: Sequence[int]) -> dict | None:
        """Pull-mode owner half: serialize the longest leading run of
        ``chain`` resident here — HBM prefix-cache pages gathered from
        the device, host-tier entries read in place (no removal: the
        export is a COPY, local hits keep working) — as a migration-
        style payload a peer installs via :meth:`install_prefix`.
        ``None`` when the leading link is not resident (the directory
        was stale; the requester just re-prefills)."""
        if self._prefix_cache is None or self.pool is None:
            return None
        chain = [int(h) for h in chain]
        hbm_blocks: list[int] = []
        for h in chain:
            blk = self._prefix_cache._entries.get(h)
            if blk is None:
                break
            hbm_blocks.append(blk)
        tier_layers: list[list] = []
        if self._tier is not None:
            while len(hbm_blocks) + len(tier_layers) < len(chain):
                layers = self._tier.peek_layers(
                    chain[len(hbm_blocks) + len(tier_layers)],
                    version=self.weights_version)
                if layers is None:
                    break
                tier_layers.append(layers)
        n = len(hbm_blocks) + len(tier_layers)
        if not n:
            return None
        nodes = self._paged_nodes(self.cache)
        pages = np.asarray(hbm_blocks, np.int32)
        layers_out = []
        for li, node in enumerate(nodes):
            ks, vs = [], []
            if hbm_blocks:
                ks.append(np.asarray(node["paged_key"][pages]))
                vs.append(np.asarray(node["paged_value"][pages]))
            for tl in tier_layers:
                ks.append(np.asarray(tl[li]["k"])[None])
                vs.append(np.asarray(tl[li]["v"])[None])
            layers_out.append({"k": np.concatenate(ks, axis=0),
                               "v": np.concatenate(vs, axis=0)})
        return {
            "key": None,      # stamped by the worker at publish
            "rid": None,
            "prompt": [],     # pull payloads carry no fallback prompt:
                              # the REQUESTER holds the real request
            "chain": chain[:n],
            "block_size": int(self.kv_block_size),
            "version": int(self.weights_version),
            "published_at": time.time(),
            "layers": layers_out,
        }

    def install_prefix(self, prompt, payload: dict) -> int:
        """Pull-mode requester half: verify a peer-exported prefix
        payload against ``prompt``'s OWN chain (recomputed locally —
        the peer is never trusted), the loop's block size, and the
        CURRENT weights version, then land its blocks as cached-idle
        prefix entries so the admission that follows hits locally and
        prefills only the suffix.  Any gate failing installs nothing
        and returns 0 — the ordinary prefill is the byte-identical
        fallback.  Returns the number of blocks installed."""
        if self._prefix_cache is None or self.pool is None:
            return 0
        try:
            bs = int(payload["block_size"])
            version = int(payload.get("version", -1))
            chain = [int(h) for h in payload["chain"]]
            layers = payload["layers"]
        except (KeyError, TypeError, ValueError):
            return 0
        nodes = self._paged_nodes(self.cache)
        prompt = np.asarray(prompt, np.int32)
        want = chain_hashes(prompt, self.kv_block_size)
        n = len(chain)
        if (bs != self.kv_block_size
                or version != self.weights_version
                or not n or n > len(want) or chain != want[:n]
                or not isinstance(layers, (list, tuple))
                or len(layers) != len(nodes)):
            return 0
        arrs = []
        for l in layers:
            try:
                k = np.asarray(l["k"])
                v = np.asarray(l["v"])
            except (KeyError, TypeError, ValueError):
                return 0
            if (k.ndim != 3 or k.shape[0] != n or k.shape[1] != bs
                    or v.shape != k.shape):
                return 0
            arrs.append((k, v))
        taken: list[tuple[int, int | None, int, list]] = []
        try:
            for j in range(n):
                if chain[j] in self._prefix_cache._entries:
                    continue   # local copy wins (first-wins install)
                blk = self.pool.alloc_cached_block()
                if blk is None:
                    break
                taken.append((chain[j],
                              want[j - 1] if j else None, blk,
                              [{"k": arrs[li][0][j], "v": arrs[li][1][j]}
                               for li in range(len(nodes))]))
            installed = self._scatter_install(taken)
            if self._tier is not None:
                # a pulled link that was ALSO spilled locally is now
                # HBM-resident: drop the tier copy (disjointness rule)
                for h, _, _, _ in taken:
                    self._tier.discard(h)
            return installed
        except Exception:
            # a half-taken install must not leak pinned pages: undo the
            # allocations that never reached the cache index
            for _, _, blk, _ in taken:
                if blk not in self._prefix_cache._entries.values():
                    self.pool.cache_unpin(blk)
            raise

    def tier_drained(self) -> bool | None:
        """Tier invariants + emptiness — the exit report's drain
        gate (``None`` when no tier exists).  Runs the cross-structure
        check: no hash simultaneously tiered and HBM-resident."""
        if self._tier is None:
            return None
        resident = (self._prefix_cache._entries.keys()
                    if self._prefix_cache is not None else ())
        self._tier.check(resident)
        return len(self._tier) == 0

    def _admit(self, slot: int, req: Request) -> dict:
        """Admit ``req`` into ``slot`` WITHOUT a host sync: the prefill
        and the state stamp are dispatched; the first token stays a
        device scalar until the next segment sync resolves it (by which
        point the decode segment has already hidden the prefill).

        With ``chunked_prefill`` the prefill is NOT dispatched here:
        admission allocates (and prefix-aliases) pool blocks, stages a
        batch-1 prefill cache, and returns a slot state carrying a
        ``prefill`` phase — the run loop dispatches one prompt chunk
        per iteration between decode segments, and the finish (insert +
        lane stamps) in the iteration of the last chunk (see
        ``advance_admissions``)."""
        self._validate(req)
        prompt = np.asarray(req.prompt, np.int32)
        L = int(prompt.size)
        self.prefix_stats["requests"] += 1
        self.prefix_stats["prompt_tokens"] += L
        self._obs_prompt_tokens.inc(L)
        if req.prefix_hash is not None and self._prefix_cache is not None:
            self._affinity_recent.pop(int(req.prefix_hash), None)
            self._affinity_recent[int(req.prefix_hash)] = None
            while len(self._affinity_recent) > 128:
                self._affinity_recent.pop(
                    next(iter(self._affinity_recent)))
        if (req.kv_handoff is not None and self.pool is not None
                and self.role != "prefill"):
            # disaggregated decode stage: adopt the migrated pages —
            # zero prefill compute — unless the payload fails
            # verification, in which case fall THROUGH to an ordinary
            # admission of the same prompt (greedy + fleet-identical
            # weights make the re-prefill output byte-identical, so the
            # fallback trades only latency)
            st = self._admit_adopt(slot, req, prompt, L)
            if st is not None:
                return st
            self._obs_handoff_fallbacks.inc()
        if self.chunked:
            return self._admit_start(slot, req, prompt, L)
        self.prefix_stats["prefill_tokens"] += L
        self._obs_prefill_tokens.inc(L)
        if self.pool is not None:
            # allocate-on-admit: pages covering the prompt now, the rest
            # of the worst-case footprint RESERVED (growth at dispatch
            # boundaries draws on the reservation and can never fail —
            # required: the pipelined host learns stops a segment late
            # and keeps growing blindly until the finalize lands)
            self.pool.admit(slot, L, int(req.max_new_tokens))
            pages = self._slot_pages(slot)
        else:
            pages = _NO_PAGES
        chunk = min(self.prefill_chunk, self.cfg.max_seq_len)
        # pad to a chunk multiple, CAPPED at the cache size: an uncapped
        # pad past max_seq_len would make the final chunk's
        # dynamic_update_slice clamp backwards and overwrite real prompt
        # positions (observed: silently corrupted completions)
        Lp = min(-(-L // chunk) * chunk, self.cfg.max_seq_len)
        padded = np.full((1, Lp), self.pad_token, np.int32)
        padded[0, :L] = prompt
        self._key, pk = jax.random.split(self._key)
        (self.cache, self._tok, self._active, self._remaining,
         self._first) = self._admit_dev(
            self.params, self.cache, self._tok, self._active,
            self._remaining, self._first, padded, np.int32(L),
            np.int32(slot), np.int32(req.max_new_tokens), pages, pk,
            true_chunk=chunk)
        return {"req": req, "tokens": [], "pending_first": True,
                "chunks": -(-Lp // chunk)}

    def _slot_pages(self, slot: int):
        """``slot``'s page row as the insert takes it: the full group's,
        or the pair (full, window) where the pool has a window group."""
        full = jnp.asarray(self.pool.table[slot])
        wg = self.pool.window_group
        return full if wg is None else (
            full, jnp.asarray(wg.table[slot].copy()))

    def _admit_start(self, slot: int, req: Request, prompt: np.ndarray,
                     L: int) -> dict:
        """Phase A of a chunked admission — all host bookkeeping, at
        most one device dispatch (the shared-prefix gather):

        * pool admit, with cached prefix blocks ALIASED in via
          ``shared=`` and the full-prompt-hit COW split applied (the
          split block's content is rewritten whole by the finish insert,
          which IS the copy);
        * the newly prefilled prefix registered into the cache
          (first-wins; an already-cached hash keeps its block);
        * the chunk worklist: the SAME ``prefill_chunk`` grid the
          one-shot path uses (full-width chunks plus one remainder —
          identical executables, bitwise-identical output), starting at
          the chunk containing ``suffix_start`` so a cache hit skips
          the covered prefix entirely (positions below ``suffix_start``
          inside the first chunk are recomputed to identical bytes).

        The run loop pops one ``(off, width)`` per iteration; the one
        that empties the worklist takes the finish dispatch with it."""
        max_new = int(req.max_new_tokens)
        suffix_start = 0
        write_block = 0
        shared_n = 0
        cache1 = self._blank1
        if self._prefix_cache is not None:
            blocks, suffix_start, cow = self._prefix_plan(prompt, L)
            shared_n = len(blocks)
            self.pool.admit(slot, L, max_new, shared=blocks)
            if suffix_start:
                # gathered THROUGH the shared blocks, so before the COW
                # split: the split's new block is empty until the finish
                # insert, and a chunk narrower than a block recomputes
                # only part of it
                cache1 = self._gather_prefix(
                    self.cache, self._blank1,
                    jnp.asarray(self.pool.table[slot]))
            if cow:
                self.pool.cow_write(slot, len(blocks) - 1)
            # registration is DEFERRED to the finish dispatch: the
            # prompt's KV only lands in these blocks at the finish
            # insert, and registering now would let a concurrent
            # admission match and gather blocks not yet written
            write_block = suffix_start // self.kv_block_size
            if shared_n:
                self.prefix_stats["hits"] += 1
                self.prefix_stats["hit_tokens"] += (
                    shared_n * self.kv_block_size)
        elif self.pool is not None:
            self.pool.admit(slot, L, max_new)
        self.prefix_stats["prefill_tokens"] += L - suffix_start
        self._obs_prefill_tokens.inc(L - suffix_start)
        pages = (self._slot_pages(slot)
                 if self.pool is not None else _NO_PAGES)
        C = min(self.prefill_chunk, self.cfg.max_seq_len)
        Lp = min(-(-L // C) * C, self.cfg.max_seq_len)
        padded = np.full((1, Lp), self.pad_token, np.int32)
        padded[0, :L] = prompt
        chunks = []
        off = (suffix_start // C) * C
        while off < Lp:
            w = min(C, Lp - off)
            chunks.append((off, w))
            off += w
        return {"req": req, "tokens": [], "pending_first": True,
                "chunks": 0,  # counted as the run loop dispatches them
                "prefill": {"cache1": cache1, "padded": padded,
                            "chunks": chunks, "logits": None,
                            "off_last": 0, "L": L, "max_new": max_new,
                            "pages": pages, "write_block": write_block}}

    # -- disaggregated handoff (see tpudist.runtime.disagg) ----------------

    def _paged_nodes(self, cache) -> list:
        """The cache's paged layer nodes in natural dict order — the
        canonical layer order for KV migration payloads.  Export and
        adoption both walk this order (see ``_adopt_dev_impl``), which
        is stable fleet-wide because every replica instantiates the
        same model structure."""
        out = []

        def walk(node):
            if not isinstance(node, dict):
                return
            if "page_table" in node:
                out.append(node)
                return
            for v in node.values():
                walk(v)

        walk(cache)
        return out

    def _build_handoff(self, slot: int, req: Request, pf: dict) -> dict:
        """Serialize ``slot``'s finished prefill as a migration payload
        (see :mod:`tpudist.runtime.disagg` for the schema) and free the
        slot.  The page gather syncs the device — acceptable on a
        prefill-only replica, where no decode cadence exists to stall —
        and the export freeze guarantees the pages it reads are this
        slot's (``check()`` would catch a mutation mid-copy)."""
        manifest = self.pool.export_slot(slot)
        pages = np.asarray(manifest["blocks"], np.int32)
        layers = [{"k": np.asarray(node["paged_key"][pages]),
                   "v": np.asarray(node["paged_value"][pages])}
                  for node in self._paged_nodes(self.cache)]
        prompt = np.asarray(req.prompt, np.int32)
        payload = {
            "key": None,   # stamped by the worker at publish
            "rid": req.rid,
            "prompt": [int(t) for t in prompt],
            "max_new_tokens": int(req.max_new_tokens),
            # the exporter's sampled first token rides along: the decode
            # side emits it verbatim instead of re-running the prompt's
            # last logit
            "first": int(self._first[slot]),
            "true_len": int(pf["L"]),
            "block_size": int(self.kv_block_size),
            "chain": chain_hashes(prompt, self.kv_block_size),
            "published_at": time.time(),
            "layers": layers,
        }
        self.pool.complete_export(slot)
        return payload

    def _build_migration(self, slot: int, st: dict) -> dict:
        """Serialize an IN-FLIGHT decode slot as a migration payload
        and free the slot — the mid-decode sibling of
        :meth:`_build_handoff`, used by priority preemption (local
        park), hot/cold rebalancing, and fast drain.

        The caller must have resolved every in-flight segment first
        (the host token list is final, no stale merge can touch the
        exported pages) and frozen the lane on device.  The payload's
        ``generated`` rider carries every emitted token but the last;
        the last emitted token travels as ``first`` (the adopter's
        deferred-first lane stamp re-emits it), so the resumed output
        concatenates to exactly the uninterrupted sequence.  The
        ``version`` stamp keeps a roll in flight from mixing KV across
        weight versions — a mismatched adopter re-prefills instead."""
        req = st["req"]
        tokens = st["tokens"]
        prompt = np.asarray(req.prompt, np.int32)
        if st.get("pending_first") or not tokens:
            # the deferred first token is still device-side (fresh
            # admission — or a re-exported ADOPTION, whose seeded
            # tokens are already page-covered and ride ``generated``)
            first = int(self._first[slot])
            generated = [int(t) for t in tokens]
        else:
            first = int(tokens[-1])
            generated = [int(t) for t in tokens[:-1]]
        prompt_eff = (np.concatenate(
            [prompt, np.asarray(generated, np.int32)])
            if generated else prompt)
        true_len = int(prompt.size) + len(generated)
        manifest = self.pool.export_slot(slot)
        # the pool grows lanes a segment ahead of the watermark; the
        # adopter allocates exactly ceil(true_len / bs), so trim the
        # gather to the pages real KV occupies
        n_used = -(-true_len // self.kv_block_size)
        pages = np.asarray(manifest["blocks"], np.int32)[:n_used]
        layers = [{"k": np.asarray(node["paged_key"][pages]),
                   "v": np.asarray(node["paged_value"][pages])}
                  for node in self._paged_nodes(self.cache)]
        payload = {
            "key": None,   # stamped by the worker at publish
            "rid": req.rid,
            "prompt": [int(t) for t in prompt],
            "max_new_tokens": int(req.max_new_tokens),
            "first": first,
            "generated": generated,
            "true_len": true_len,
            "block_size": int(self.kv_block_size),
            "chain": chain_hashes(prompt_eff, self.kv_block_size),
            "published_at": time.time(),
            "version": int(self.weights_version),
            "layers": layers,
        }
        self.pool.complete_export(slot)
        return payload

    def _admit_adopt(self, slot: int, req: Request, prompt: np.ndarray,
                     L: int) -> dict | None:
        """Admit ``req`` by ADOPTING its migrated KV payload — zero
        prefill compute.  Returns ``None`` when the payload fails any
        verification gate (structure, lengths, block size, prefix-hash
        chain, layer count/shape, weights version): the caller falls
        back to an ordinary re-prefill of the carried prompt, which
        greedy decoding over fleet-identical weights makes
        byte-identical.

        A MID-DECODE payload (preemption / rebalance / drain) carries
        ``generated`` — tokens the exporter already emitted, excluding
        the ``first`` rider.  The pages then cover prompt+generated,
        the chain is recomputed over that effective prompt, the slot's
        output list is SEEDED with the generated tokens, and the
        remaining budget shrinks by their count — so the terminal
        completion carries the full byte-identical token sequence and
        the original request (deadline, trace, priority) rides along
        untouched."""
        payload = req.kv_handoff
        try:
            first = int(payload["first"])
            true_len = int(payload["true_len"])
            bs = int(payload["block_size"])
            chain = [int(h) for h in payload["chain"]]
            generated = [int(t) for t in payload.get("generated", ())]
            layers = payload["layers"]
        except (KeyError, TypeError, ValueError):
            return None
        if "version" in payload:
            # KV computed under one weights version must never continue
            # under another (a roll in flight): refuse and re-prefill
            # under THIS replica's weights instead
            try:
                if int(payload["version"]) != self.weights_version:
                    return None
            except (TypeError, ValueError):
                return None
        if generated:
            prompt_eff = np.concatenate(
                [prompt, np.asarray(generated, np.int32)])
        else:
            prompt_eff = prompt
        L_eff = L + len(generated)
        max_new_eff = int(req.max_new_tokens) - len(generated)
        nodes = self._paged_nodes(self.cache)
        if (true_len != L_eff or bs != self.kv_block_size
                or max_new_eff < 1
                or chain != chain_hashes(prompt_eff, self.kv_block_size)
                or len(layers) != len(nodes)):
            return None
        max_new = max_new_eff
        blocks = self.pool.adopt_blocks(slot, L_eff, max_new)
        m_used = len(blocks)
        kv = []
        for l in layers:
            try:
                k = jnp.asarray(l["k"])
                v = jnp.asarray(l["v"])
            except (KeyError, TypeError, ValueError):
                k = v = None
            if (k is None or k.ndim != 3 or k.shape[0] != m_used
                    or k.shape[1] != bs or v.shape != k.shape):
                # shape lies past the chain check: un-admit and let the
                # fallback prefill take the slot instead
                self.pool.free_slot(slot)
                return None
            kv.append((k, v))
        pages_used = jnp.asarray(np.asarray(blocks, np.int32))
        full_row = jnp.asarray(self.pool.table[slot])
        (self.cache, self._tok, self._active, self._remaining,
         self._first) = self._adopt_dev(
            self.cache, self._tok, self._active, self._remaining,
            self._first, tuple(kv), pages_used, full_row,
            np.int32(true_len), np.int32(slot), np.int32(max_new),
            np.int32(first))
        self._obs_adoptions.inc()
        obs.recorder.record("serve_adopt", slot=slot, prompt_len=L,
                            blocks=m_used, generated=len(generated))
        # seed the output with the exporter's already-emitted tokens:
        # the terminal completion replaces the exporter's partial state
        # wholesale, so the router never assembles tokens across hops
        return {"req": req, "tokens": list(generated),
                "pending_first": True, "chunks": 0}

    def _plan_steps(self, slot_state) -> int:
        """Per-dispatch segment length: ``steps_per_sync``, CLAMPED
        against the tightest live in-flight deadline so a timeout is
        detected within ~one token of expiry instead of up to a full
        fixed-length segment late.  Uses the measured per-token EMA
        (``None`` until the first dispatch drains — the first segment
        runs full-length, which matches the old behavior)."""
        if self._step_ema is None or self._step_ema <= 0:
            return self.steps
        tightest = None
        for st in slot_state:
            if st is None or st.get("zombie"):
                continue
            dl = st["req"].deadline_s
            if dl is not None and (tightest is None or dl < tightest):
                tightest = dl
        if tightest is None:
            return self.steps
        slack = tightest - self._clock()
        if slack <= self._step_ema:
            return 1
        return max(1, min(self.steps, int(slack / self._step_ema)))

    def request_swap(self, params_fn, *, version: int | None = None,
                     on_swapped=None) -> None:
        """Schedule a DRAIN-GATED weight hot-swap: admission pauses,
        every lane already decoding runs to completion on the OLD
        weights, every in-flight segment drains, and only then is
        ``params_fn()`` called and its tree rebound as ``self.params``
        before admission resumes — no request ever straddles two weight
        versions, so greedy output stays exact-match against whichever
        single-version reference admitted it.  Because ``params`` is a
        jit ARGUMENT (not a closure capture), a same-shape/dtype tree
        swaps in with ZERO recompilation.

        ``params_fn`` returning ``None`` (e.g. a missing snapshot)
        aborts the rebind — old weights stay, the version gauge does
        not move — but the swap still COMPLETES: ``on_swapped()`` fires
        either way, so a rolling-upgrade chain (``runtime/router.py``'s
        ticket protocol) can never stall on one replica's failed
        restore.  ``version`` (when given and applied) lands on the
        ``serve/weights_version`` gauge the router and ``wait_swapped``
        poll.  Callable between :meth:`run` calls or during one from
        the ``source()``/``sink`` callbacks — the loop is single-
        threaded, so no locking; the latest request wins if one is
        already pending."""
        self._pending_swap = {"fn": params_fn, "version": version,
                              "on_swapped": on_swapped}

    def request_migrate(self, rids) -> None:
        """Ask the loop to migrate the named requests OUT (hot/cold
        rebalancing): at the next safe point each named request —
        queued, parked, or in-flight — leaves as a
        ``reason="migrate"`` completion carrying its exported KV
        payload (in-flight) or nothing (queued: a ref-less requeue the
        router redispatches as a fresh, byte-identical prefill).
        Unknown rids are ignored — the request finished first, and its
        normal terminal wins.  Callable from the ``source()`` callback;
        the loop is single-threaded, so no locking."""
        self._migrate_rids.update(str(r) for r in rids)

    def request_evacuate(self) -> None:
        """Ask the loop to migrate EVERYTHING out — queued, parked, and
        in-flight (fast drain): the worker calls this when its replica
        is marked draining, collapsing drain time from "longest
        remaining decode" to roughly one handoff RTT.  Idempotent; the
        flag clears after one evacuation pass, so a draining worker
        re-arms it every poll to bounce late arrivals too."""
        self._evacuate = True

    def run(self, requests: Sequence[Request] = (), *,
            source=None, sink=None,
            idle_wait_s: float = 0.005) -> list[Completion]:
        """Serve every request to completion; returns completions in
        FINISH order (slot events), each with its generated tokens.

        The loop keeps up to ``pipeline_depth`` compiled segments in
        flight: each dispatch chains the device carry immediately and
        starts an async device→host copy of its emits; the host fetch
        (and the admission/finalization decisions it feeds) happens
        while the NEXT segment computes.  A per-slot ``seq`` stamp — the
        index of the first segment whose emits can carry the slot's
        tokens — gates draining, so a lane re-admitted while an older
        segment's emits are still in flight never has stale rows
        misread as the new request's output.  The drain itself applies
        the same stop/budget rules as the synchronous loop, so output
        is token-identical at any depth (greedy selection ignores the
        RNG key; sampled runs see a shifted key chain across depths).

        SERVICE MODE — ``source`` / ``sink`` turn the batch runner into
        a long-lived replica worker (the router tier's unit):

        * ``source()`` is polled once per outer-loop iteration and
          returns an iterable of new :class:`Request`\\ s (``[]`` =
          open but idle; the loop sleeps ``idle_wait_s`` when there is
          nothing to do), or ``None`` to CLOSE intake — the loop then
          drains everything in flight and returns.  Service-mode
          requests that fail validation complete with
          ``reason="invalid"`` instead of raising (one malformed
          request must not take the replica down).
        * ``sink(completion)`` fires at every finalize — including
          rejections and timeouts — so completions stream out while the
          loop runs; the full list is still returned.

        Deadline kills and the paged layout interact with pipelining:
        segments already in flight at kill time carry the PRE-KILL
        active mask and page table, so the killed lane's blocks cannot
        be refunded (and re-allocated) until every one of those
        segments has drained — a freed-then-recycled block would be
        written by a stale merge.  The lane is parked as a ZOMBIE
        (finalized for the caller, un-admittable, blocks held) and the
        refund happens when the drain index passes the kill point; the
        in-graph freeze (``active=False``) guarantees segments
        dispatched AFTER the kill never write it."""
        for req in requests:  # fail BEFORE any slot is touched, not mid-run
            self._validate(req)
        self.intertoken_samples = []
        self._last_drain_t = None
        pending: deque[tuple[Request, float]] = deque()
        slot_state: list[dict | None] = [None] * self.B
        done: list[Completion] = []
        # (seq, emits, corrupt, n_steps, t_dispatch, the plan's sums)
        inflight: deque[tuple] = deque()
        seq = 0   # segments dispatched so far == index of the next one
        closed = source is None
        swap_pause_logged = False   # one swap_pause event per barrier

        def complete(req: Request, tokens, reason: str, stamps: dict, *,
                     slot: int | None = None, chunks: int = 0,
                     handoff=None) -> None:
            """The one way a request leaves the loop: its stamps become
            ``Completion.timing`` and ONE ``serve/request`` span
            (enqueue -> now, the inner stamps as offsets from enqueue),
            then the completion goes to the list and the ``sink``."""
            now = time.perf_counter()
            t_q = stamps["enqueue"]
            inner = {k: stamps.get(k)
                     for k in ("admit", "prefill_done", "first_token")}
            timing = RequestTiming(enqueue=t_q, done=now, chunks=chunks,
                                   tokens=len(tokens), **inner)
            if timing.admit is not None:
                self._obs_latency.record(now - t_q)
            obs.tracer.complete(
                "serve/request", t_q, now, rid=_span_rid(req.rid),
                slot=slot, prompt_len=int(np.asarray(req.prompt).size),
                chunks=chunks, tokens=timing.tokens, reason=reason,
                admit_seq=stamps.get("admit_seq"),
                decode_seq=stamps.get("decode_seq"),
                **{k: None if t is None else t - t_q
                   for k, t in inner.items()})
            comp = Completion(
                rid=req.rid, prompt=np.asarray(req.prompt),
                tokens=np.asarray(tokens, np.int32), reason=reason,
                handoff=handoff, timing=timing)
            done.append(comp)
            if sink is not None:
                sink(comp)

        def tev(kind: str, req: Request, **fields) -> None:
            """One request-lifecycle event into the tracing ring —
            only for TRACED requests (fleet traffic); untraced local
            runs stay out of the ring entirely."""
            tc = getattr(req, "trace", None)
            if tc is not None:
                obs.events.record(kind, trace=tc.trace_id, **fields)

        def complete_unadmitted(req: Request, reason: str,
                                t_q: float) -> None:
            """Finalize a request that never reached a slot (shed,
            expired in queue, or invalid): no tokens, no lane state."""
            if reason == "rejected":
                self._obs_rejected.inc()
            elif reason == "timeout":
                self._obs_timeouts.inc()
            tev(reason, req, stage="queue")
            complete(req, (), reason, {"enqueue": t_q})

        def intake(batch, strict: bool) -> None:
            """Enqueue new requests; service mode (strict=False) turns
            validation failures into ``reason="invalid"`` completions."""
            for req in batch:
                t_q = time.perf_counter()
                if not strict:
                    try:
                        self._validate(req)
                    except ValueError:
                        complete_unadmitted(req, "invalid", t_q)
                        continue
                pending.append((req, t_q))

        def shed() -> None:
            """Overload ladder.  Past the soft ``degrade_queue``
            watermark the loop goes DEGRADED (admissions clamp
            best-effort budgets — see admit_free).  Past the hard
            ``max_queue`` bound it sheds: lowest ``priority`` class
            first, newest-first within a class, so earlier arrivals keep
            their FIFO place and important traffic is the LAST to be
            rejected."""
            self._degraded = (self.degrade_queue is not None
                              and len(pending) > self.degrade_queue)
            self._obs_degraded.set(1.0 if self._degraded else 0.0)
            while (self.max_queue is not None
                   and len(pending) > self.max_queue):
                lowest = min(r.priority for r, _ in pending)
                victim = max(i for i, (r, _) in enumerate(pending)
                             if r.priority == lowest)
                req, t_q = pending[victim]
                del pending[victim]
                complete_unadmitted(req, "rejected", t_q)
            self._obs_queue.set(len(pending))

        def finalize(slot: int, reason: str, *,
                     free_pool: bool = True) -> None:
            st = slot_state[slot]
            tev("finalize", st["req"], slot=slot, reason=reason,
                tokens=len(st["tokens"]))
            complete(st["req"], st["tokens"], reason, st["stamps"],
                     slot=slot, chunks=st["chunks"])
            self._obs_tokens.inc(len(st["tokens"]))
            slot_state[slot] = None
            if self.pool is not None and free_pool:
                # free-on-finalize: blocks AND the unused reservation
                # return to the pool now.  Safe against in-flight
                # segments that still map this slot to these blocks: the
                # lane froze in-graph at the stop token, and the merge is
                # masked by `lived`, so a frozen row never writes a page
                # (its reads of recycled pages feed discarded pad emits).
                self.pool.free_slot(slot)

        def expire_inflight() -> None:
            """Kill lanes whose deadline passed: freeze the row on
            device, finalize with the tokens drained so far.  Dense
            lanes free immediately (the seq stamp already gates stale
            emits); paged lanes with segments in flight become zombies
            until the pre-kill segments drain (see the docstring)."""
            now = None
            for slot in range(self.B):
                st = slot_state[slot]
                if (st is None or st.get("zombie")
                        or st["req"].deadline_s is None):
                    continue
                if now is None:
                    now = self._clock()
                if now <= st["req"].deadline_s:
                    continue
                self._active = self._active.at[slot].set(False)
                self._obs_timeouts.inc()
                obs.recorder.record("serve_timeout", slot=slot, seq=seq,
                                    tokens=len(st["tokens"]))
                tev("timeout", st["req"], stage="decode", slot=slot,
                    tokens=len(st["tokens"]))
                if "prefill" in st:
                    # mid-prefill kill: the lane was never stamped
                    # active, so in-flight segments have lived=0 for it
                    # (merges masked) and its chunk dispatches touched
                    # only the transient batch-1 cache — the pool refund
                    # is safe immediately, no zombie needed
                    finalize(slot, "timeout")
                elif self.pool is not None and inflight:
                    finalize(slot, "timeout", free_pool=False)
                    slot_state[slot] = {"zombie": True, "free_at": seq}
                else:
                    finalize(slot, "timeout")

        def join_decode(st: dict) -> None:
            """The lane's tokens first surface in the NEXT dispatched
            segment (index ``seq``): the stamp gates its drain, rides
            on the ``serve/request`` span as ``decode_seq`` next to
            ``admit_seq``, and their difference (the segments the lane
            stood filled and not decoding) ticks
            ``serve/admit_segments``."""
            st["seq"] = st["stamps"]["decode_seq"] = seq
            self._obs_admit_segments.record(
                seq - st["stamps"]["admit_seq"])

        def admit_free() -> None:
            """Expire queued deadlines, then fill free lanes from the
            queue; a new admission's tokens first surface in the NEXT
            dispatched segment (index ``seq``), so its drain is gated
            on that stamp."""
            nonlocal pending, swap_pause_logged
            if pending:
                now = None
                kept: deque[tuple[Request, float]] = deque()
                for req, t_q in pending:
                    if req.deadline_s is not None:
                        if now is None:
                            now = self._clock()
                        if now > req.deadline_s:
                            complete_unadmitted(req, "timeout", t_q)
                            continue
                    kept.append((req, t_q))
                pending = kept
            if self._pending_swap is not None:
                # swap barrier: no new admissions until the rebind lands
                # (queued-deadline expiry above still runs — a request
                # cannot outlive its deadline waiting on a swap)
                if not swap_pause_logged:
                    swap_pause_logged = True
                    for req, _ in pending:
                        tev("swap_pause", req, queued=len(pending),
                            version=self._pending_swap.get("version"))
                self._obs_queue.set(len(pending))
                return
            prefilling = sum(1 for st in slot_state
                             if st is not None and "prefill" in st)
            for slot in range(self.B):
                if slot_state[slot] is None and pending:
                    if (self.max_prefill_lanes is not None
                            and prefilling >= self.max_prefill_lanes):
                        # what lanes in admission hold is bounded: the
                        # head waits for a finish (FIFO, as for blocks)
                        break
                    if self.preempt == "migrate":
                        # priority-first admission: the best waiting
                        # class jumps the queue (FIFO within a class);
                        # a blocked high-priority head is what arms
                        # maybe_preempt rather than starving behind
                        # best-effort arrivals
                        sel = max(range(len(pending)),
                                  key=lambda i: (pending[i][0].priority,
                                                 -i))
                    else:
                        sel = 0
                    req, t_q = pending[sel]
                    if self.pool is not None:
                        L_q = int(np.asarray(req.prompt).size)
                        if self._prefix_cache is not None:
                            # count the aliased prefix against nothing:
                            # shared blocks cost no allocation, but a
                            # full-prompt hit draws one COW block
                            n_sh = self._prefix_cache.peek(req.prompt)
                            cow = int(
                                n_sh * self.kv_block_size >= L_q)
                            ok = self.pool.can_admit(
                                L_q, int(req.max_new_tokens),
                                shared=n_sh, cow=cow)
                        else:
                            ok = self.pool.can_admit(
                                L_q, int(req.max_new_tokens))
                        if not ok:
                            # capacity gate: QUEUE instead of OOMing the
                            # pool.  FIFO — the head waits for blocks
                            # rather than being jumped by a smaller
                            # request behind it, which would starve
                            # long prompts
                            break
                    del pending[sel]
                    if (self.preempt != "migrate" and self._degraded
                            and req.priority <= 0
                            and req.max_new_tokens > self.degrade_max_new):
                        # degraded mode: best-effort traffic gets a short
                        # answer instead of (later) no answer.  A copy —
                        # the caller's Request is never mutated.
                        req = dataclasses.replace(
                            req, max_new_tokens=self.degrade_max_new)
                        self._obs_degrade_clamped.inc()
                        tev("degrade_clamp", req, stage="replica",
                            max_new=self.degrade_max_new)
                    self._obs_queue_wait.record(time.perf_counter() - t_q)
                    with obs.span("serve/admit", slot=slot,
                                  rid=_span_rid(req.rid)):
                        st = slot_state[slot] = self._admit(slot, req)
                    # stamped here, not in _admit: a caller may wrap
                    # loop._admit, and the stamp must cover the wrapper.
                    # A chunked admission gets its seq stamp (and its
                    # prefill_done) at the FINISH dispatch
                    # (advance_admissions) — its tokens cannot surface
                    # before that segment.
                    st["stamps"] = {"enqueue": t_q,
                                    "admit": time.perf_counter(),
                                    "admit_seq": seq}
                    if "prefill" not in st:
                        st["stamps"]["prefill_done"] = st["stamps"]["admit"]
                        join_decode(st)
                    else:
                        prefilling += 1
                    self._obs_requests.inc()
                    obs.recorder.record(
                        "serve_admit", slot=slot, seq=seq,
                        prompt_len=int(np.asarray(req.prompt).size),
                        max_new=req.max_new_tokens)
                    tev("admit", req, slot=slot, seq=seq,
                        prompt_len=int(np.asarray(req.prompt).size),
                        max_new=req.max_new_tokens)
            self._obs_queue.set(len(pending))

        def drain(slot: int, emit_row, t_fetched: float) -> int:
            """Feed a slot's newly visible tokens (column 0 = the
            admission-deferred first token, then the segment's emits)
            through the stop/budget rules; the first hit finalizes
            BEFORE any frozen-row pad could be consumed, mirroring the
            compiled freeze rule token for token.  Returns how many
            tokens the request took.  ``t_fetched`` is when this
            segment's emits reached the host: the first-token time of a
            request whose deferred first token rode in column 0."""
            st = slot_state[slot]
            row = [int(t) for t in emit_row]
            if st["pending_first"]:
                st["pending_first"] = False
                st["stamps"]["first_token"] = t_fetched
                self._obs_ttft.record(t_fetched - st["stamps"]["enqueue"])
            else:
                row = row[1:]               # column 0 is a stale first
            vocab = self.cfg.vocab_size
            had = len(st["tokens"])
            for t in row:
                if not 0 <= t < vocab:
                    # host-side range net: an id outside the vocab can
                    # only come from scrambled device memory or a bad
                    # transfer (the sampler indexes [0, vocab)).
                    self._obs_corrupt.inc()
                    obs.recorder.record(
                        "serve_corrupt_segment", slot=slot,
                        token=t, tokens=len(st["tokens"]))
                    tev("corrupt_segment", st["req"], slot=slot,
                        token=t, tokens=len(st["tokens"]))
                    self._active = self._active.at[slot].set(False)
                    if self.pool is not None and inflight:
                        # host-side kill, like timeout: pre-kill
                        # segments may still write this lane's pages,
                        # so the refund waits for them to drain
                        finalize(slot, "corrupt_segment",
                                 free_pool=False)
                        slot_state[slot] = {"zombie": True,
                                            "free_at": seq}
                    else:
                        finalize(slot, "corrupt_segment")
                    break
                st["tokens"].append(t)
                self._served_tokens += 1
                if t in self._stop_set:
                    finalize(slot, "stop")
                    break
                if len(st["tokens"]) >= st["req"].max_new_tokens:
                    finalize(slot, "length")
                    break
            return len(st["tokens"]) - had

        def advance_admissions() -> None:
            """Chunked prefill: advance every prefilling lane by ONE
            prompt chunk per outer-loop iteration, interleaved with the
            decode segments ``dispatch()`` chains — a 10k-token prompt
            spreads its prefill across many iterations instead of
            stalling every in-flight request behind one long dense
            pass.  Each chunk is an async dispatch into the lane's
            transient batch-1 cache (same chunk grid as the one-shot
            ``_prefill``, so the KV and logits are bitwise identical).
            The FINISH dispatch rides in the SAME iteration as the
            lane's last chunk: it needs nothing but that chunk's
            outputs, which are device futures already in hand, and the
            device runs dispatches in order.  It scatters the batch-1
            cache into the paged table (suffix blocks only — shared
            prefix blocks are read in place), selects the first token
            from the final chunk's logits, stamps the lane active, and
            the slot joins decode with its drain gated on the segment
            ``dispatch()`` chains next, in this iteration: a lane never
            stands filled and idle for an iteration of its own."""
            freed_by_handoff: list[int] = []
            for slot in range(self.B):
                st = slot_state[slot]
                if st is None or "prefill" not in st:
                    continue
                pf = st["prefill"]
                if pf["chunks"]:
                    off, w = pf["chunks"].pop(0)
                    toks = pf["padded"][:, off:off + w]
                    # the one row of logits the finish reads (any row
                    # of a chunk that is not the prompt's last)
                    row = min(max(pf["L"] - 1 - off, 0), w - 1)
                    # with an indexer: whether the chunk attends chosen
                    # rows (CausalSelfAttention._prefill_attend's rule)
                    chosen = ({} if self._index_topk is None else
                              {"sparse": off + w > self._index_topk})
                    with obs.span("serve/prefill_chunk", slot=slot,
                                  rid=_span_rid(st["req"].rid), off=off,
                                  width=w, seq=seq, **chosen):
                        pf["cache1"], pf["logits"] = self._prefill_chunk(
                            self.params, pf["cache1"], toks,
                            np.int32(off), np.int32(row), chunk=w)
                    st["chunks"] += 1
                    pf["off_last"] = off
                    tev("prefill_chunk", st["req"], slot=slot,
                        off=off, width=w, left=len(pf["chunks"]))
                    if pf["chunks"]:
                        continue   # ONE chunk a lane per iteration
                self._key, pk = jax.random.split(self._key)
                with obs.span("serve/admit_finish", slot=slot,
                              rid=_span_rid(st["req"].rid), seq=seq):
                    (self.cache, self._tok, self._active,
                     self._remaining, self._first) = self._admit_finish(
                        self.cache, self._tok, self._active,
                        self._remaining, self._first, pf["cache1"],
                        pf["logits"], np.int32(pf["off_last"]),
                        np.int32(pf["L"]), np.int32(slot),
                        np.int32(pf["max_new"]), pf["pages"],
                        np.int32(pf["write_block"]), pk)
                st["stamps"]["prefill_done"] = time.perf_counter()
                if self._prefix_cache is not None:
                    # register AFTER the insert dispatch: any later
                    # match's gather is host-ordered behind the write
                    # (first-wins — a hash cached meanwhile keeps its
                    # original block)
                    self._prefix_cache.register(
                        pf["padded"][0, :pf["L"]],
                        self.pool._slot_blocks[slot])
                    if self._tier is not None and len(self._tier):
                        # every full block of this prompt is now
                        # HBM-resident (first-wins or fresh): drop any
                        # surviving tier copy — e.g. a re-admit that
                        # stopped at pool exhaustion left deep links
                        # spilled, and the full prefill just recomputed
                        # them.  Tiered/resident must stay disjoint.
                        for h in chain_hashes(pf["padded"][0, :pf["L"]],
                                              self.kv_block_size):
                            self._tier.discard(h)
                tev("prefill_done", st["req"], slot=slot, seq=seq,
                    prompt_len=pf["L"])
                if self.role == "prefill":
                    # disaggregated handoff: this loop's job ENDS at
                    # prefill_done.  Undo the finish dispatch's active
                    # stamp (no decode segment may advance this lane),
                    # export the slot's pages + first token as the
                    # migration payload, and emit a reason="handoff"
                    # completion the router turns into a decode-stage
                    # dispatch.  complete_export (inside _build_handoff)
                    # frees the slot, so the lane recycles immediately —
                    # the structural TTFT win of a prefill-only replica.
                    self._active = self._active.at[slot].set(False)
                    payload = self._build_handoff(slot, st["req"], pf)
                    tev("handoff_export", st["req"], slot=slot, seq=seq,
                        prompt_len=pf["L"],
                        blocks=-(-pf["L"] // self.kv_block_size))
                    complete(st["req"], (), "handoff", st["stamps"],
                             slot=slot, chunks=st["chunks"],
                             handoff=payload)
                    del st["prefill"]
                    slot_state[slot] = None
                    freed_by_handoff.append(slot)
                    continue
                del st["prefill"]
                join_decode(st)
            if freed_by_handoff:
                # a prefill-role loop has no decode dispatches, so
                # nothing else would refill a lane freed by export —
                # pull from the queue NOW or an idle source starves the
                # loop with work still pending
                admit_free()
                shed()

        def busy_decode() -> bool:
            """Lanes a decode segment could advance — zombie and
            PREFILL-phase slots excluded: a prefilling lane is inactive
            on device until its finish dispatch lands, so segments
            dispatched for it alone would run empty."""
            return any(st is not None and not st.get("zombie")
                       and "prefill" not in st for st in slot_state)

        def can_work() -> bool:
            """Is there decode work a dispatch could advance?  A pending
            swap gates QUEUED requests out (the admission barrier means
            they cannot reach a slot, so dispatching for them would spin
            empty segments forever); lanes already decoding still count
            — they must run to completion before the swap lands.
            ``pending`` alone also counts: queued requests can be
            blocked on pool blocks held by ZOMBIE lanes, whose refund
            only lands when segments drain past the kill point.  A
            prefill-role loop NEVER decodes: its lanes hand off at
            prefill_done, so decode segments would only spin empty."""
            if self.role == "prefill":
                return False
            return busy_decode() or (bool(pending)
                                     and self._pending_swap is None)

        def maybe_swap() -> None:
            """Apply a pending weight swap once the loop is fully
            drained: no in-flight segments (their emits were computed
            under the old weights and must finalize against them) and
            no occupied lanes (zombies included — their pool blocks are
            refunded by the drain that just ran)."""
            nonlocal swap_pause_logged
            if (self._pending_swap is None or inflight
                    or any(st is not None for st in slot_state)):
                return
            swap, self._pending_swap = self._pending_swap, None
            swap_pause_logged = False   # barrier is down; next swap re-logs
            with obs.span("serve/swap", version=swap["version"]):
                tree = swap["fn"]()
                if tree is not None:
                    self.params = jax.tree.map(jnp.asarray, tree)
                    self._obs_swaps.inc()
                    if swap["version"] is not None:
                        self._obs_weights_version.set(int(swap["version"]))
                        # the version stamp every subsequent tier spill
                        # and pull-mode export carries: KV computed
                        # before this line can never pass the
                        # version gate after it
                        self.weights_version = int(swap["version"])
                    # cached prefix KV was computed under the OLD
                    # weights — serving it to a post-swap admission
                    # would break exactness.  The loop is drained here,
                    # so every refcount is zero and the flush returns
                    # every cached block to the free list (and empties
                    # the host tier, whose spilled KV is exactly as
                    # stale).
                    self.flush_prefix_cache()
            obs.recorder.record("serve_swap", seq=seq,
                                version=swap["version"],
                                applied=tree is not None)
            if swap["on_swapped"] is not None:
                swap["on_swapped"]()
            admit_free()   # the barrier is down; refill lanes now
            shed()

        def dispatch() -> None:
            """Chain one more segment on device and start its emits'
            async device→host copy — no host block."""
            nonlocal seq
            with obs.span("serve/segment_plan", seq=seq):
                n = self._plan_steps(slot_state)
                pages = rows = rows_live = rows_selected = longest = 0
                windowed = None
                # the lanes whose state the slot cache holds (a lane in
                # admission carries its state in its batch-1 cache)
                state_lanes = 0
                if self._state_layers:
                    state_lanes = sum(
                        1 for st in slot_state
                        if st is not None and not st.get("zombie")
                        and "prefill" not in st)
                    self._obs_state_bytes.set(
                        state_lanes * self._state_lane_bytes)
                if self.pool is not None:
                    wg = self.pool.window_group
                    rows_w = rows_w_live = 0
                    released = wg.released if wg is not None else 0
                    block = self.pool.block_size
                    per_tile = paged_tile_pages(
                        block, self.pool.max_blocks_per_slot)
                    # grow-on-decode-boundary: advance every live lane's
                    # page coverage by the segment's worst case (drawn
                    # from its admit-time reservation, so this cannot
                    # fail), then stamp the fresh table into the carry
                    # this segment consumes.  Lanes already frozen on
                    # device (host hasn't drained the stop yet) grow
                    # harmlessly within their reservation and refund it
                    # at finalize.
                    # Zombie lanes are dead (their reservation was
                    # dropped at finalize); their held blocks just wait
                    # for the refund.
                    for slot in range(self.B):
                        st = slot_state[slot]
                        if (st is not None and not st.get("zombie")
                                and "prefill" not in st):
                            # prefill-phase lanes don't grow: nothing
                            # decodes there yet, and their prompt
                            # coverage was allocated at admit
                            pages += self.pool.covered_pages(slot)
                            held = self.pool.covered_rows(slot)
                            rows += walk_rows(held, block, per_tile)
                            rows_live += held
                            if self._index_topk is not None:
                                rows_selected += min(held, self._index_topk)
                                longest = max(longest, held)
                            if wg is not None:
                                rows_w += walk_rows(held, block, per_tile,
                                                    wg.window)
                                rows_w_live += min(held, wg.window)
                            self.pool.grow(slot, n)
                    if wg is not None:
                        # the window layers' walk (a layer) and the
                        # blocks this segment's growth let go of
                        windowed = (rows_w, rows_w_live,
                                    wg.released - released)
                    # a span of its own: the copies are the suspect of the
                    # stalls inside serve/segment_plan (ROADMAP S6)
                    with obs.span("serve/segment_stamp", seq=seq,
                                  copies=self._table_copies):
                        self._stamp_table()
            # the segment splits per-step keys and returns the advanced
            # key — no per-wave host-side split dispatch needed
            t_disp = time.perf_counter()
            with obs.span("serve/segment", steps=n, seq=seq):
                poison = faults.poison_logits(self._served_tokens)
                (self.cache, self._tok, self._active, self._remaining,
                 self._key, emits, corrupt) = self._segment(
                    self.params, self.cache, self._tok, self._active,
                    self._remaining, self._first, self._key,
                    jnp.int32(n), jnp.bool_(poison))
            self._obs_segments.inc()
            self._obs_dispatches.inc()
            for slot in range(self.B):
                st = slot_state[slot]
                if (st is not None and not st.get("zombie")
                        and "prefill" not in st):
                    tev("segment", st["req"], slot=slot, seq=seq,
                        steps=n, tokens=len(st["tokens"]),
                        spt=(round(self._step_ema, 6)
                             if self._step_ema is not None else None))
            try:
                emits.copy_to_host_async()
            except AttributeError:  # non-jax array (test doubles)
                pass
            inflight.append((seq, emits, corrupt, n, t_disp,
                             (pages, rows, rows_live, rows_selected,
                              longest, windowed, state_lanes)))
            seq += 1
            self._obs_depth.set(len(inflight))
            # fault harness: a configured kill-after-K-segments SIGKILLs
            # here — mid-decode, with segments in flight
            faults.on_segment()

        def drain_oldest() -> None:
            """Resolve the oldest in-flight segment: block on its fetch
            (usually already landed — the copy overlapped later compute),
            then feed every lane whose stamp says this segment carries
            its tokens.  A segment carries exactly ``n`` emit columns
            past the deferred-first column, and the drain slices to that
            width so pad columns past a short segment are never consumed.

            Leaves one ``serve/segment_fetch`` span (the block on the
            device) and one ``serve/segment_drain`` span (the host's work
            on what came), the latter with the segment's sums: ``steps``
            dispatched, ``steps_run`` (the most decode tokens any lane
            took: the device's ``while_loop`` runs until its last live
            lane freezes and the host's rules mirror its freeze token for
            token, so this is its exit step; a lane the HOST killed is
            not fed and not counted), ``lanes`` fed, ``tokens`` appended,
            how many of them were ``first_tokens``, and ``pages`` (paged
            layout: the pages under the live lanes' lengths when the
            segment was dispatched, by the pool's own count — what one
            call of the decode kernel walks; a lane frozen on the device
            that the host has not drained yet is still counted), with
            ``rows`` (what the kernel's arithmetic covers for those lanes,
            ``walk_rows`` of each length), ``rows_live`` (the lengths) and
            ``grid_rows`` (the grid rows of one call of the paged kernel,
            ``ops.flash_decode.paged_grid_rows`` of the loop's shapes) with
            ``heads_per_grid_row`` (the K/V heads one of them serves, by
            the same fold: a two-row lane reads half its heads).
            A model with sliding-window layers adds ``rows_window`` /
            ``rows_window_live`` (the same two of a WINDOW layer's call:
            ``walk_rows`` with the window, ``min(length, window)``; ``rows``
            and ``rows_live`` stay the full layers') and ``blocks_released``
            (what the window group let go of when the segment was planned).
            A model with an indexer adds ``rows_scored`` (the index keys its
            scores read, a layer: the live lanes' lengths) and
            ``rows_selected`` (the rows its attention reads:
            ``min(length, index_topk)`` a lane), ``gathers`` (the gathers
            of chosen rows a layer makes in a step:
            ``ops.flash_decode.SPARSE_ATTEND_GATHERS``) and
            ``rows_gathered`` (what they fetched over the segment, all
            layers: every lane's ``index_topk`` rows a gather, in each
            step that found some lane beyond ``index_topk`` rows; 0 where
            no kernel runs) and ``row_words`` (the 32-bit words of a
            gathered row, ``serve/kv_row_words``).  A model with
            linear-attention layers adds ``state_lanes`` (the lanes whose
            state the slot cache held at dispatch: the decoding ones) and
            ``state_bytes`` (those lanes times what the state layers keep
            for one)."""
            (s_idx, emits_dev, corrupt_dev, n_disp, t_disp,
             (pages, rows, rows_live, rows_selected, longest, windowed,
              state_lanes)) = inflight.popleft()
            self._obs_depth.set(len(inflight))
            if any(st is not None and not st.get("zombie")
                   and "seq" in st and st["seq"] <= s_idx
                   for st in slot_state):
                t0 = time.perf_counter()
                with obs.span("serve/segment_fetch", seq=s_idx):
                    emits = np.asarray(emits_dev)
                # the one stamp of "this segment's tokens are on the
                # host": host_wait's end, the first-token time of the
                # requests whose first token it carried, the inter-token
                # sample and the clamp's EMA all read it
                t_fetched = time.perf_counter()
                self._obs_host_wait.record(t_fetched - t0)
                # inter-token latency sample: wall gap between
                # consecutive decode-segment drains, per token of this
                # segment.  A one-shot long-prompt admission lands
                # between two segments and shows up here as one huge
                # gap — exactly the stall chunked prefill removes.
                if self._last_drain_t is not None:
                    self.intertoken_samples.append(
                        ((t_fetched - self._last_drain_t) / n_disp,
                         n_disp))
                self._last_drain_t = t_fetched
                # dispatch->drain wall time per token; under pipelining
                # this spans overlapped segments, so it OVERestimates —
                # which only makes the deadline clamp more conservative
                per = (t_fetched - t_disp) / n_disp
                self._step_ema = (
                    per if self._step_ema is None
                    else 0.7 * self._step_ema + 0.3 * per)
                self._obs_spt.set(self._step_ema)
                self._obs_steps_per_dispatch.set(n_disp)
                corrupt = np.asarray(corrupt_dev)
                lanes = tokens = first_tokens = steps_run = 0
                for slot in range(self.B):
                    st = slot_state[slot]
                    if (st is not None and not st.get("zombie")
                            and "seq" in st and st["seq"] <= s_idx):
                        lanes += 1
                        if bool(corrupt[slot]):
                            # the in-graph guard froze this lane before
                            # emitting anything from the bad step, but
                            # this segment's earlier columns are from
                            # the same poisoned state — discard them
                            # all and surface the verdict.  free_pool
                            # is safe for the same reason stop-finalize
                            # is: the lane is frozen in-graph, so later
                            # in-flight segments never write its pages.
                            self._obs_corrupt.inc()
                            obs.recorder.record(
                                "serve_corrupt_segment", slot=slot,
                                seq=s_idx, tokens=len(st["tokens"]))
                            tev("corrupt_segment", st["req"], slot=slot,
                                seq=s_idx, tokens=len(st["tokens"]))
                            finalize(slot, "corrupt_segment")
                        else:
                            first = int(st["pending_first"])
                            got = drain(slot, emits[slot, :1 + n_disp],
                                        t_fetched)
                            first = min(first, got)  # none from a corrupt column 0
                            tokens += got
                            first_tokens += first
                            steps_run = max(steps_run, got - first)
                self._obs_tokens_drained.inc(tokens)
                self._obs_decode_steps.inc(steps_run)
                self._obs_lane_steps.inc(self.B * steps_run)
                self._obs_pages_walked.inc(pages * steps_run)
                self._obs_rows_computed.inc(rows * steps_run)
                self._obs_rows_live.inc(rows_live * steps_run)
                self._obs_grid_rows.inc(
                    self._grid_rows * self._attn_layers * steps_run)
                routed = {}
                if windowed is not None:
                    # the window layers' calls, ticked like the full
                    # layers' rows; blocks_released is the segment's own
                    rows_w, rows_w_live, released = windowed
                    self._obs_rows_window.inc(rows_w * steps_run)
                    self._obs_rows_window_live.inc(rows_w_live * steps_run)
                    routed = {"rows_window": rows_w,
                              "rows_window_live": rows_w_live,
                              "blocks_released": released}
                if self._index_topk is not None:
                    self._obs_rows_scored.inc(rows_live * steps_run)
                    self._obs_rows_selected.inc(rows_selected * steps_run)
                    # step j of the segment gathers when the longest lane's
                    # rows and the j staged ones pass index_topk
                    sparse_steps = steps_run - min(
                        max(self._index_topk - longest, 0), steps_run)
                    gathered = (self.B * self._index_topk
                                * self._attn_layers * SPARSE_ATTEND_GATHERS
                                * sparse_steps)
                    self._obs_rows_gathered.inc(gathered)
                    routed.update(rows_scored=rows_live,
                                  rows_selected=rows_selected,
                                  gathers=SPARSE_ATTEND_GATHERS,
                                  rows_gathered=gathered,
                                  row_words=self._kv_row_words)
                if self._state_layers:
                    routed.update(
                        state_lanes=state_lanes,
                        state_bytes=state_lanes * self._state_lane_bytes)
                if self._expert_blocks:
                    n_cells = len(self._expert_blocks) * self._held
                    counts = emits[self.B:].reshape(-1)[:n_cells]
                    routed.update(expert_tokens=int(counts.sum()),
                                  expert_tokens_max=int(counts.max()))
                    self._obs_expert_tokens.inc(routed["expert_tokens"])
                    self._obs_expert_tokens_max.inc(
                        routed["expert_tokens_max"])
                    self._obs_expert_slots.inc(steps_run * n_cells)
                obs.tracer.complete(
                    "serve/segment_drain", t_fetched, time.perf_counter(),
                    seq=s_idx, steps=n_disp, steps_run=steps_run,
                    lanes=lanes, tokens=tokens, first_tokens=first_tokens,
                    pages=pages, rows=rows, rows_live=rows_live,
                    grid_rows=self._grid_rows,
                    heads_per_grid_row=self._row_heads, **routed)
            # zombie refund: every segment dispatched before the kill
            # (index < free_at) has drained once s_idx reaches
            # free_at - 1 — no stale merge can touch the blocks now
            for slot in range(self.B):
                st = slot_state[slot]
                if (st is not None and st.get("zombie")
                        and s_idx >= st["free_at"] - 1):
                    self.pool.free_slot(slot)
                    slot_state[slot] = None

        # -- live KV migration (preempt / rebalance / fast drain) ----------

        def quiesce() -> None:
            """Resolve EVERY in-flight segment: after this, each lane's
            host token list is final and no stale merge can touch pages
            an export is about to read — the precondition of
            ``_build_migration``."""
            while inflight:
                drain_oldest()

        def export_slot_payload(slot: int) -> dict:
            """Freeze ``slot`` on device, serialize it as a migration
            payload, and release the lane (no Completion — the caller
            decides whether the request parks locally or leaves as a
            ``reason="migrate"`` commit)."""
            st = slot_state[slot]
            self._active = self._active.at[slot].set(False)
            payload = self._build_migration(slot, st)
            slot_state[slot] = None
            return payload

        def park(slot: int) -> None:
            """Export ``slot`` and park it LOCALLY: payload metadata in
            the host dict, page bytes spilled per-block into the host
            tier when one exists (budget-accounted; eviction of any
            parked block downgrades the resume to a byte-identical
            re-prefill)."""
            st = slot_state[slot]
            req = st["req"]
            n_gen = len(st["tokens"])
            payload = export_slot_payload(slot)
            entry: dict = {"req": req, "t_q": time.perf_counter()}
            if self._tier is not None and payload["layers"]:
                n_blk = int(np.asarray(
                    payload["layers"][0]["k"]).shape[0])
                keys: list[int] = []
                parent = None
                ok = True
                for i in range(n_blk):
                    h = _park_hash(req.rid, i)
                    blk = [{"k": np.asarray(l["k"][i]),
                            "v": np.asarray(l["v"][i])}
                           for l in payload["layers"]]
                    if not self._tier.put(h, blk, parent=parent,
                                          version=self.weights_version):
                        ok = False
                        break
                    keys.append(h)
                    parent = h
                if ok:
                    entry["meta"] = {k: v for k, v in payload.items()
                                     if k != "layers"}
                    entry["keys"] = keys
                else:
                    # tier refused (budget): keep the payload whole in
                    # host RAM rather than losing the pages outright
                    for h in keys:
                        self._tier.discard(h)
                    entry["payload"] = payload
            else:
                entry["payload"] = payload
            self._parked[req.rid] = entry
            self._obs_preempted.inc()
            obs.recorder.record("serve_preempt", slot=slot,
                                tokens=n_gen, parked=len(self._parked))
            tev("preempt", req, stage="replica", slot=slot,
                tokens=n_gen, parked=len(self._parked))

        def unpark(entry: dict) -> dict | None:
            """Rebuild a parked payload; ``None`` when any tier block
            was evicted or version-flushed — the resume falls back to a
            re-prefill of the original request (byte-identical)."""
            if "payload" in entry:
                return entry["payload"]
            blocks = []
            for h in entry["keys"]:
                blk = self._tier.take(h, version=self.weights_version)
                if blk is None:
                    drop_parked(entry)
                    return None
                blocks.append(blk)
            n_lay = len(blocks[0]) if blocks else 0
            layers = [{"k": np.stack([b[li]["k"] for b in blocks]),
                       "v": np.stack([b[li]["v"] for b in blocks])}
                      for li in range(n_lay)]
            return {**entry["meta"], "layers": layers}

        def drop_parked(entry: dict) -> None:
            for h in entry.get("keys", ()):
                self._tier.discard(h)
            entry.pop("keys", None)
            entry.pop("payload", None)

        def migrate_out(req: Request, payload: dict | None,
                        stage: str, stamps: dict) -> None:
            """Hand one request back to the router as a
            ``reason="migrate"`` completion — with its exported KV
            (in-flight) or ref-less (queued/prefill-phase: the
            redispatch re-prefills, byte-identical)."""
            self._obs_migrated_out.inc()
            tev("migrate_export", req, stage=stage,
                tokens=(len(payload.get("generated", ()))
                        + 1 if payload else 0),
                refless=payload is None)
            complete(req, (), "migrate", stamps, handoff=payload)

        def do_migrates() -> bool:
            """Router-initiated migration: evacuate everything (fast
            drain / fast swap) or the named requests (hot/cold
            rebalance).  Unknown rids mean the request finished first —
            its normal terminal wins and the intent is dropped."""
            nonlocal pending
            if not (self._evacuate or self._migrate_rids):
                return False
            if sink is None and source is None:
                # batch mode has no router to resume a migrated
                # request — the intents are meaningless here
                self._migrate_rids.clear()
                self._evacuate = False
                return False
            evac = self._evacuate
            wanted = set(self._migrate_rids)
            moved = False
            if pending:
                kept: deque[tuple[Request, float]] = deque()
                for req, t_q in pending:
                    if evac or req.rid in wanted:
                        migrate_out(req, None, "queue", {"enqueue": t_q})
                        moved = True
                    else:
                        kept.append((req, t_q))
                pending = kept
            for rid in list(self._parked):
                if evac or rid in wanted:
                    entry = self._parked.pop(rid)
                    payload = unpark(entry)
                    migrate_out(entry["req"], payload, "parked",
                                {"enqueue": entry["t_q"]})
                    moved = True
            if self.pool is not None and any(
                    st is not None and not st.get("zombie")
                    and (evac or st["req"].rid in wanted)
                    for st in slot_state):
                quiesce()
                for slot in range(self.B):
                    st = slot_state[slot]
                    if (st is None or st.get("zombie")
                            or not (evac or st["req"].rid in wanted)):
                        continue
                    req = st["req"]
                    if "prefill" in st:
                        # mid-chunked-prefill: the pages are not a
                        # finished prefix yet — requeue ref-less, the
                        # target re-prefills to identical bytes
                        self.pool.free_slot(slot)
                        slot_state[slot] = None
                        migrate_out(req, None, "prefill", st["stamps"])
                    else:
                        migrate_out(req, export_slot_payload(slot),
                                    "decode", st["stamps"])
                    moved = True
            self._migrate_rids.clear()
            self._evacuate = False
            return moved

        def maybe_preempt() -> bool:
            """Priority preemption (``preempt='migrate'``): under
            pressure — the degrade watermark breached, or the
            best-priority waiting request blocked on a lane/pool a
            strictly-lower-priority decode holds — quiesce and PARK the
            lowest-priority in-flight slot instead of degrade-clamping
            it.  Paused, never killed or truncated."""
            if (self.preempt != "migrate" or self.pool is None
                    or self.role == "prefill" or not pending):
                return False

            def victims() -> list[tuple[int, int, int]]:
                top = max(r.priority for r, _ in pending)
                return sorted(
                    (st["req"].priority, -len(st["tokens"]), slot)
                    for slot, st in enumerate(slot_state)
                    if st is not None and not st.get("zombie")
                    and "prefill" not in st
                    and st["req"].priority < top)
            if not victims():
                return False
            if not self._degraded:
                top_req = max(
                    (r for r, _ in pending), key=lambda r: r.priority)
                blocked = not any(s is None for s in slot_state)
                if not blocked:
                    blocked = not self.pool.can_admit(
                        int(np.asarray(top_req.prompt).size),
                        int(top_req.max_new_tokens))
                if not blocked:
                    return False
            quiesce()   # drains may finalize lanes: re-pick after
            vs = victims()
            if not vs:
                return False
            park(vs[0][2])
            return True

        def maybe_resume() -> bool:
            """Resume the oldest parked request once pressure clears
            (or unconditionally once intake is closed): its payload
            re-enters through the adopt path at the FRONT of the queue,
            original deadline/trace/priority intact."""
            if not self._parked or self._pending_swap is not None:
                return False
            if not closed and self._degraded:
                return False
            rid = next(iter(self._parked))
            entry = self._parked[rid]
            req = entry["req"]
            if (req.deadline_s is not None
                    and self._clock() > req.deadline_s):
                drop_parked(entry)
                del self._parked[rid]
                complete_unadmitted(req, "timeout", entry["t_q"])
                return True
            if not any(s is None for s in slot_state):
                return False
            if not self.pool.can_admit(
                    int(np.asarray(req.prompt).size),
                    int(req.max_new_tokens)):
                return False
            payload = unpark(entry)
            del self._parked[rid]
            resumed = (dataclasses.replace(req, kv_handoff=payload)
                       if payload is not None else req)
            pending.appendleft((resumed, entry["t_q"]))
            self._obs_resumed.inc()
            obs.recorder.record("serve_resume",
                                fallback=payload is None,
                                parked=len(self._parked))
            tev("resume", req, stage="replica",
                fallback=payload is None, parked=len(self._parked))
            return True

        # an unhandled exception mid-serve dumps the flight-recorder
        # bundle (admission ring, final snapshot) before propagating
        with obs.recorder.guard("serve_loop", num_slots=self.B,
                                requests=len(requests),
                                pipeline_depth=self.pipeline_depth):
            intake(requests, strict=True)
            admit_free()
            shed()
            while True:
                # the loop's own phases are spans (admit_poll, admit,
                # prefill_chunk, admit_finish, segment_plan, segment,
                # segment_fetch, segment_drain): together they account
                # for the wall of run() but the idle sleep
                with obs.span("serve/admit_poll"):
                    if not closed:
                        batch = source()
                        if batch is None:
                            closed = True
                        elif batch:
                            intake(batch, strict=False)
                            admit_free()
                            shed()
                    expire_inflight()
                    if (self.preempt == "migrate"
                            and self._pending_swap is not None
                            and source is not None):
                        # fast swap: evacuate in-flight work to peers so
                        # the swap barrier drains in ~one handoff RTT
                        # instead of the longest remaining decode
                        self._evacuate = True
                    if do_migrates() | maybe_preempt() | maybe_resume():
                        admit_free()
                        shed()
                advance_admissions()
                if can_work():
                    dispatch()
                # fetch when the pipeline is full — or when there is
                # nothing left to dispatch and only fetches remain
                while inflight and (
                        len(inflight) >= self.pipeline_depth
                        or not can_work()):
                    drain_oldest()
                    with obs.span("serve/admit_poll"):
                        admit_free()
                maybe_swap()
                if not (pending or inflight or self._parked or any(
                        st is not None for st in slot_state)):
                    if closed:
                        break
                    time.sleep(idle_wait_s)
            # the queue drained on the way out: an idle loop must not
            # keep advertising DEGRADED to the router
            self._degraded = False
            self._obs_degraded.set(0.0)
        return done
