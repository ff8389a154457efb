"""Paged KV cache: host-side block-pool allocator + page-table layout.

The dense serve cache allocates ``num_slots x max_seq_len`` KV positions
per layer no matter what the requests actually use — at 8k context that
full-context HBM bill per lane is what caps ``num_slots`` (and therefore
decode throughput).  The paged layout (PagedAttention, vLLM SOSP '23)
replaces the per-slot buffers with ONE device-resident block pool per
layer (``[num_blocks, block_size, Hkv*D]``) plus a per-slot PAGE TABLE
(``[num_slots, max_blocks_per_slot]`` int32 pool indices); a slot's
logical position ``p`` lives at ``pool[table[slot, p // block_size],
p % block_size]``.  Serve capacity then scales with the tokens requests
actually RESERVE (prompt + budget), not with ``num_slots x max_seq_len``.

This module is the HOST half: :class:`BlockPool` owns the free list and
the per-slot block lists, and renders the page table the compiled side
consumes.  Allocation policy (all host-side, O(blocks) bookkeeping — no
device syncs anywhere):

* **allocate-on-admit**: admission allocates blocks covering the prompt
  (the insert scatter writes exactly those) and RESERVES the rest of the
  request's worst-case footprint ``min(prompt + max_new_tokens,
  max_seq_len)`` — growth can then never fail mid-flight, which matters
  because the pipelined serve loop learns stop events a segment late and
  must keep growing blindly until the finalize lands;
* **grow-on-decode-boundary**: before each dispatched segment every live
  slot's coverage is advanced by ``steps_per_sync`` tokens (drawn from
  its reservation), so the per-segment side->pool merge always has pages
  under every position it can write;
* **free-on-finalize**: a finished request returns its blocks AND its
  unused reservation immediately — early stops refund capacity the
  moment the host learns of them.

Admission control: :meth:`can_admit` checks the request's FULL
reservation against unreserved free blocks and the serve loop queues the
request instead of OOMing the pool.  Reserving the worst case forgoes
optimistic over-commit (no preemption/swap machinery needed), yet keeps
the capacity win: a short-prompt / small-budget request holds a few
blocks, not a ``max_seq_len`` lane.

Prefix sharing (PR 14) adds copy-on-write block aliasing on top:

* every block carries a REFCOUNT (number of slot references); a freed
  slot decrements instead of freeing, and a block returns to the free
  list only when its refcount hits zero and the prefix cache does not
  pin it;
* :meth:`share` aliases an existing block run into a fresh slot's
  leading positions (the shared prefix is strictly read-only for that
  slot — decode and suffix-prefill writes land past it);
* :meth:`cow_write` splits the one legal write into a shared region —
  the LAST shared block, written when a full-prompt cache hit must
  recompute its final position to produce the first output logit — by
  moving the slot onto a private copy (``serve/cow_splits``);
* :class:`PrefixCache` maps rolling token-hash chains (one blake2b
  chain link per full block, so a hash names the block's content AND
  everything before it) to pool blocks, pinning them so idle prefixes
  survive ``free_slot``; eviction is LRU over refcount-0 entries only
  and runs on demand when the free list is empty.

Cached-but-idle blocks (pinned, refcount 0) are RECLAIMABLE capacity:
:attr:`free_blocks` and the ``serve/kv_blocks_free`` gauge count them,
``serve/kv_frag`` measures fragmentation over live (slot-referenced)
blocks only, and :attr:`used_blocks` excludes them — so admission
control, the drain check, and the autoscaler all see truthful pressure.

Block GROUPS (PR 31).  A model whose layers are not all of one kind
keeps them in pools of different needs: a full-attention layer holds every
token of a request, a sliding-window layer the last ``window`` of them
whatever the length.  The pool above is the FULL group, and behaves as it
always did, to the block; :class:`WindowGroup` is the window layers' group
beside it, with its own capacity, free list, table, reservation rule
(what covers a window and a segment, never a request's whole length) and
``used_blocks``, and it RELEASES a lane's blocks that fell wholly below
the window before each segment.  ``BlockPool(window=...)`` builds it; the
pool's ``can_admit`` / ``admit`` / ``grow`` / ``free_slot`` / ``check``
cover both groups, so the serve loop asks one object.

The device half lives in :mod:`tpudist.models.transformer`
(``CausalSelfAttention._paged_attend``) and
:func:`tpudist.ops.flash_decode.paged_flash_decode`.
"""

from __future__ import annotations

import hashlib
from collections import OrderedDict
from collections.abc import Callable, Sequence

import numpy as np

from tpudist import obs


def blocks_for(tokens: int, block_size: int) -> int:
    """Blocks needed to cover ``tokens`` positions (ceil division)."""
    return -(-int(tokens) // block_size)


def _hash_bytes(data: bytes) -> int:
    return int.from_bytes(
        hashlib.blake2b(data, digest_size=8).digest(), "big")


def chain_hashes(tokens: Sequence[int], block_size: int) -> list[int]:
    """Rolling hash chain over ``tokens``, one link per FULL block.

    Link ``j`` hashes block ``j``'s tokens together with link ``j-1``,
    so it names the block's content AND the entire prefix before it —
    two prompts share link ``j`` iff their first ``(j+1)*block_size``
    tokens are identical.  blake2b over the int32 byte encoding keeps
    the chain deterministic across processes (router, replicas, and the
    offline simulator must agree)."""
    toks = np.asarray(tokens, np.int32)
    out: list[int] = []
    prev = b""
    for j in range(len(toks) // block_size):
        prev = hashlib.blake2b(
            prev + toks[j * block_size:(j + 1) * block_size].tobytes(),
            digest_size=8).digest()
        out.append(int.from_bytes(prev, "big"))
    return out


def request_prefix_hash(tokens: Sequence[int]) -> int:
    """Order-64-bit hash of a token span, for wire-level prefix affinity.

    Clients stamp ``Request.prefix_hash`` with this over the shared
    prefix they know about (e.g. a tenant's system prompt); replicas
    publish the hashes they recently admitted; the router steers
    matching requests to a replica that already holds the prefix.  The
    hash is opaque end to end — nothing needs to agree on block sizes."""
    return _hash_bytes(np.asarray(tokens, np.int32).tobytes())


def span_blocks(rows: int, block_size: int) -> int:
    """The most blocks ``rows`` consecutive positions touch, at the worst
    alignment."""
    return max(rows, 0) if rows < 2 else (rows - 2) // block_size + 2


class WindowGroup:
    """The block group of a model's SLIDING-WINDOW layers: a lane holds the
    blocks that cover ``[len - window + 1, len + steps)`` and nothing
    below, whatever its length.

    * **reservation**: ``min(blocks of the request's whole length,
      lane_blocks)``, where ``lane_blocks`` is what a window and a segment
      of ``steps`` tokens touch at the worst alignment.  Held for the
      request's life: released blocks stay promised to the lane, so its
      growth can never fail (the same promise the full group makes).
    * **admit**: the blocks that cover the prompt's last ``window - 1``
      rows (what the first decode step sees; the finish insert writes
      exactly those).
    * **grow** (before each dispatched segment, with the lane's length
      ``held`` at its start and its coverage ``target`` after): blocks
      wholly below row ``held + 1 - window`` are RELEASED to the free list
      (``released`` counts them), then blocks are drawn to cover
      ``target``.  A released block may be handed to another lane at once:
      the device runs dispatches in order, so a segment already in flight
      reads it before its next owner's insert or merge writes it, and a
      windowed decode never reads a page id below its window.
    * a lane's ``table`` row holds its blocks at their logical indices and
      0 (a valid pool index) everywhere else.
    """

    def __init__(self, num_blocks: int | None, block_size: int,
                 num_slots: int, max_seq_len: int, window: int,
                 steps: int) -> None:
        """``num_blocks`` None: every lane's ``lane_blocks``, a group
        that never refuses an admission."""
        if window < 1 or steps < 1:
            raise ValueError(
                f"window and steps must be >= 1, got {window}, {steps}")
        self.window, self.steps = int(window), int(steps)
        self.block_size = int(block_size)
        self.max_seq_len = int(max_seq_len)
        self.lane_blocks = min(
            span_blocks(self.window - 1 + self.steps, block_size),
            blocks_for(max_seq_len, block_size))
        if num_blocks is None:
            num_blocks = num_slots * self.lane_blocks
        if num_blocks < self.lane_blocks:
            raise ValueError(
                f"the window group needs at least one lane's "
                f"{self.lane_blocks} blocks, got {num_blocks}")
        self.num_blocks = int(num_blocks)
        self._free: list[int] = list(range(self.num_blocks - 1, -1, -1))
        # a lane's blocks at logical indices _lo .. _lo + len - 1
        self._blocks: list[list[int]] = [[] for _ in range(num_slots)]
        self._lo = [0] * num_slots
        self._need = [0] * num_slots          # the lane's reservation
        self._reserved_total = 0              # promised, not yet drawn
        self.released = 0                     # lifetime, blocks
        self.table = np.zeros(
            (num_slots, blocks_for(max_seq_len, block_size)), np.int32)
        self._obs_used = obs.gauge("serve/kv_window_blocks_used",
                                   unit="blocks")
        self._obs_free = obs.gauge("serve/kv_window_blocks_free",
                                   unit="blocks")
        self._obs_released = obs.counter("serve/kv_window_blocks_released",
                                         unit="blocks")
        self._publish()

    @property
    def free_blocks(self) -> int:
        return len(self._free) - self._reserved_total

    @property
    def used_blocks(self) -> int:
        return self.num_blocks - len(self._free)

    def _publish(self) -> None:
        self._obs_used.set(self.used_blocks)
        self._obs_free.set(len(self._free))

    def request_blocks(self, prompt_len: int, max_new_tokens: int) -> int:
        total = min(prompt_len + max_new_tokens, self.max_seq_len)
        return min(blocks_for(total, self.block_size), self.lane_blocks)

    def can_admit(self, prompt_len: int, max_new_tokens: int) -> bool:
        return (self.request_blocks(prompt_len, max_new_tokens)
                <= self.free_blocks)

    def first_block(self, held: int) -> int:
        """The block that holds the first row a query at position ``held``
        sees: everything below it is outside the window for good."""
        return max(held + 1 - self.window, 0) // self.block_size

    def _cover(self, slot: int, lo: int, count: int) -> None:
        """Hold blocks ``lo .. count - 1``: release below, draw above."""
        blks = self._blocks[slot]
        drop = min(max(lo - self._lo[slot], 0), len(blks))
        if drop:
            self.table[slot, self._lo[slot]:self._lo[slot] + drop] = 0
            self._free.extend(reversed(blks[:drop]))
            del blks[:drop]
            self._lo[slot] += drop
            self._reserved_total += drop
            self.released += drop
            self._obs_released.inc(drop)
        if not blks:
            self._lo[slot] = lo
        while self._lo[slot] + len(blks) < count:
            if not self._free:
                raise RuntimeError("window block group exhausted")
            blk = self._free.pop()
            self.table[slot, self._lo[slot] + len(blks)] = blk
            blks.append(blk)
            self._reserved_total -= 1
        if len(blks) > self._need[slot]:
            raise AssertionError(
                f"slot {slot} holds {len(blks)} window blocks over its "
                f"reservation {self._need[slot]}")

    def admit(self, slot: int, prompt_len: int, max_new_tokens: int) -> None:
        if self._blocks[slot] or self._need[slot]:
            raise RuntimeError(f"slot {slot} still holds window blocks")
        need = self.request_blocks(prompt_len, max_new_tokens)
        if need > self.free_blocks:
            raise RuntimeError(
                f"admit of {need} window blocks exceeds free "
                f"{self.free_blocks} (call can_admit first)")
        self._need[slot] = need
        self._reserved_total += need
        self._cover(slot, self.first_block(prompt_len),
                    blocks_for(prompt_len, self.block_size))
        self._publish()

    def grow(self, slot: int, held: int, target: int) -> None:
        self._cover(slot, self.first_block(held),
                    blocks_for(target, self.block_size))
        self._publish()

    def free_slot(self, slot: int) -> None:
        blks = self._blocks[slot]
        self._reserved_total -= self._need[slot] - len(blks)
        self._free.extend(reversed(blks))
        blks.clear()
        self.table[slot, :] = 0
        self._lo[slot] = 0
        self._need[slot] = 0
        self._publish()

    def check(self) -> None:
        held = [b for blks in self._blocks for b in blks]
        if len(held) != len(set(held)):
            raise AssertionError("a window block is held twice")
        free = set(self._free)
        if len(free) != len(self._free):
            raise AssertionError("duplicate blocks on the window free list")
        if free & set(held):
            raise AssertionError(
                f"window blocks both free and held: {free & set(held)}")
        if len(free) + len(held) != self.num_blocks:
            raise AssertionError("leaked window blocks: held + free != pool")
        promised = sum(n - len(b) for n, b in zip(self._need, self._blocks))
        if promised != self._reserved_total or not (
                0 <= self._reserved_total <= len(self._free)):
            raise AssertionError(
                f"window reservation {self._reserved_total} (counted "
                f"{promised}) outside the free list's {len(self._free)}")
        for slot, blks in enumerate(self._blocks):
            if len(blks) > self.lane_blocks:
                raise AssertionError(
                    f"slot {slot} holds {len(blks)} window blocks, more "
                    f"than a lane's {self.lane_blocks}")
            want = np.zeros_like(self.table[slot])
            want[self._lo[slot]:self._lo[slot] + len(blks)] = blks
            if not np.array_equal(want, self.table[slot]):
                raise AssertionError(
                    f"slot {slot}'s window table row drifted from its "
                    f"blocks")


class BlockPool:
    """Host-side allocator for the paged KV cache.

    Args:
      num_blocks: pool capacity (the device buffers' leading dim).
      block_size: tokens per block; must be a positive multiple of 8
        (the paged kernel streams one block per grid step and Mosaic
        needs the 8-row sublane tile).
      num_slots: decode lanes (page-table rows).
      max_seq_len: model context; bounds ``max_blocks_per_slot``.
      window / window_blocks / window_steps: a model with sliding-window
        layers: their width, the capacity of their block group (default:
        every lane's ``lane_blocks``, so that group never refuses an
        admission) and the most tokens a segment adds.  Builds
        :attr:`window_group`; everything above is then the FULL layers'
        group.  Prefix aliasing and KV export are the full group's alone
        and are refused with a window group.

    The page table (:attr:`table`) is a ``[num_slots,
    max_blocks_per_slot]`` int32 array; rows are filled left-to-right
    with the slot's allocated blocks and UNALLOCATED entries hold 0 — a
    valid pool index, so the dense fallback's gather and the merge's
    clamped lookups always read real memory; the decode kernel reads a
    row's first ``ceil(length / block_size)`` entries only.
    """

    def __init__(self, num_blocks: int, block_size: int, num_slots: int,
                 max_seq_len: int, *, window: int | None = None,
                 window_blocks: int | None = None,
                 window_steps: int = 1) -> None:
        if block_size < 8 or block_size % 8:
            raise ValueError(
                f"block_size must be a positive multiple of 8, got "
                f"{block_size}")
        if num_blocks < 1:
            raise ValueError(f"num_blocks must be >= 1, got {num_blocks}")
        self.num_blocks = int(num_blocks)
        self.block_size = int(block_size)
        self.num_slots = int(num_slots)
        self.max_blocks_per_slot = blocks_for(max_seq_len, block_size)
        self.max_seq_len = int(max_seq_len)
        # LIFO free list: recently freed (hot) blocks are reused first
        self._free: list[int] = list(range(self.num_blocks - 1, -1, -1))
        self._slot_blocks: list[list[int]] = [[] for _ in range(num_slots)]
        # per-slot tokens covered so far (the grow watermark) and the
        # reservation cap (min(prompt + max_new, max_seq_len))
        self._watermark = [0] * num_slots
        self._cap = [0] * num_slots
        self._reserved_total = 0  # blocks promised but not yet allocated
        # COW bookkeeping: per-block slot-reference counts, the set of
        # blocks pinned by the prefix cache, and per-slot count of
        # leading blocks that are SHARED (read-only for that slot)
        self._refcount = [0] * self.num_blocks
        self._pinned: set[int] = set()
        self._shared_upto = [0] * num_slots
        self._prompt_len = [0] * num_slots
        # set by PrefixCache: frees >=1 refcount-0 cached block on
        # demand; lets reservations count evictable blocks as capacity
        self._evict_hook: Callable[[], bool] | None = None
        # slots whose KV is mid-migration to another pool: slot -> the
        # frozen block list snapshotted at export_slot().  Until the ack
        # (complete_export) or abort lands, the slot may not be freed,
        # grown, or COW-split — the exporter is still reading the pages.
        self._migrating: dict[int, list[int]] = {}
        self.table = np.zeros(
            (num_slots, self.max_blocks_per_slot), np.int32)
        self._obs_used = obs.gauge("serve/kv_blocks_used", unit="blocks")
        self._obs_free = obs.gauge("serve/kv_blocks_free", unit="blocks")
        self._obs_frag = obs.gauge("serve/kv_frag", unit="fraction")
        self._obs_cow = obs.counter("serve/cow_splits", unit="blocks")
        self.window_group: WindowGroup | None = None
        if window is not None:
            self.window_group = WindowGroup(
                window_blocks, block_size, num_slots, max_seq_len, window,
                window_steps)
        self._publish()

    # -- accounting --------------------------------------------------------

    def _evictable(self) -> int:
        """Cached-but-idle blocks: pinned by the prefix cache, referenced
        by no slot — reclaimable on demand via the eviction hook."""
        return sum(1 for b in self._pinned if self._refcount[b] == 0)

    @property
    def free_blocks(self) -> int:
        """Blocks neither live nor promised to a reservation.  Counts
        cached-but-idle blocks: they are evicted on demand, so they ARE
        capacity — hiding them would starve admission behind a cache."""
        return len(self._free) + self._evictable() - self._reserved_total

    @property
    def used_blocks(self) -> int:
        """Blocks holding live, non-reclaimable data.  Cached-but-idle
        blocks are excluded: a drained pool with a warm prefix cache is
        still drained."""
        return self.num_blocks - len(self._free) - self._evictable()

    def _publish(self) -> None:
        evictable = self._evictable()
        used = self.num_blocks - len(self._free) - evictable
        self._obs_used.set(used)
        self._obs_free.set(len(self._free) + evictable)
        live = {b for blks in self._slot_blocks for b in blks}
        covered = sum(self._watermark)
        alloc_tokens = len(live) * self.block_size
        # internal fragmentation of the LIVE set: the fraction of live
        # token slots not under any slot's coverage watermark.  Shared
        # blocks are counted once but covered by several watermarks, so
        # the ratio is clamped — sharing is the opposite of waste.
        frag = 0.0 if not alloc_tokens else 1.0 - covered / alloc_tokens
        self._obs_frag.set(min(1.0, max(0.0, frag)))

    def check(self) -> None:
        """Allocator invariants — cheap enough to run in tests every
        segment: refcounts match slot references, nothing both free and
        referenced/pinned, shared blocks only ever aliased read-only,
        reservation arithmetic consistent."""
        counts = [0] * self.num_blocks
        for slot, blks in enumerate(self._slot_blocks):
            if len(blks) != len(set(blks)):
                raise AssertionError(
                    f"slot {slot} references a block twice: {blks}")
            for blk in blks:
                counts[blk] += 1
        if counts != self._refcount:
            bad = [b for b in range(self.num_blocks)
                   if counts[b] != self._refcount[b]]
            raise AssertionError(
                f"refcount drift on blocks {bad}: "
                f"counted {[counts[b] for b in bad]}, "
                f"recorded {[self._refcount[b] for b in bad]}")
        free = set(self._free)
        if len(free) != len(self._free):
            raise AssertionError("duplicate blocks on the free list")
        bad = [b for b in free if counts[b] or b in self._pinned]
        if bad:
            raise AssertionError(
                f"blocks both free and referenced/pinned: {bad}")
        live = {b for b in range(self.num_blocks) if counts[b]}
        idle_cached = {b for b in self._pinned if not counts[b]}
        if len(live) + len(idle_cached) + len(free) != self.num_blocks:
            raise AssertionError(
                "leaked blocks: live + cached-idle + free != pool")
        for slot, blks in enumerate(self._slot_blocks):
            for j, blk in enumerate(blks):
                # a slot writes block j only past its shared boundary
                # AND past its prompt (suffix prefill at admission,
                # decode appends after) — any aliased or pinned block
                # in that writable region is a latent corruption
                writable = (j >= self._shared_upto[slot]
                            and (j + 1) * self.block_size
                            > self._prompt_len[slot])
                if writable and counts[blk] > 1:
                    raise AssertionError(
                        f"block {blk} aliased by {counts[blk]} slots but "
                        f"writable from slot {slot} (index {j}, shared "
                        f"boundary {self._shared_upto[slot]}, prompt "
                        f"{self._prompt_len[slot]})")
                if writable and blk in self._pinned:
                    raise AssertionError(
                        f"pinned block {blk} in slot {slot}'s writable "
                        "region — decode writes would corrupt the cache")
        if self._reserved_total < 0 or self._reserved_total > (
                len(self._free) + len(idle_cached)):
            raise AssertionError(
                f"reservation {self._reserved_total} outside reclaimable "
                f"capacity {len(self._free)} + {len(idle_cached)}")
        for slot, snapshot in self._migrating.items():
            if self._slot_blocks[slot] != snapshot:
                raise AssertionError(
                    f"slot {slot} mutated mid-migration: exported "
                    f"{snapshot}, now holds {self._slot_blocks[slot]}")
            bad = [b for b in snapshot if b in free]
            if bad:
                raise AssertionError(
                    f"in-migration blocks of slot {slot} on the free "
                    f"list: {bad}")
            bad = [b for b in snapshot if self._refcount[b] < 1]
            if bad:
                raise AssertionError(
                    f"in-migration blocks of slot {slot} unreferenced: "
                    f"{bad}")
        if self.window_group is not None:
            self.window_group.check()

    # -- allocation --------------------------------------------------------

    def request_blocks(self, prompt_len: int, max_new_tokens: int) -> int:
        """The full worst-case footprint of a request, in blocks."""
        total = min(prompt_len + max_new_tokens, self.max_seq_len)
        return blocks_for(total, self.block_size)

    def can_admit(self, prompt_len: int, max_new_tokens: int,
                  shared: int = 0, cow: int = 0) -> bool:
        """``shared`` blocks arrive by aliasing (no allocation); ``cow``
        is the extra private block a copy-on-write split will draw
        immediately after admit (full-prompt cache hits)."""
        return (self.request_blocks(prompt_len, max_new_tokens)
                - shared + cow <= self.free_blocks
                and (self.window_group is None
                     or self.window_group.can_admit(prompt_len,
                                                    max_new_tokens)))

    def _take_block(self) -> int:
        if not self._free and not (
                self._evict_hook is not None and self._evict_hook()):
            raise RuntimeError("block pool exhausted")
        return self._free.pop()

    def admit(self, slot: int, prompt_len: int, max_new_tokens: int,
              shared: Sequence[int] = ()) -> None:
        """Allocate blocks covering the prompt and reserve the rest of
        the request's footprint.  ``shared`` aliases existing blocks
        (refcount++) under the slot's leading positions instead of
        allocating them.  Caller must have checked :meth:`can_admit`
        with the same ``shared`` count (raises ``RuntimeError``
        otherwise)."""
        if self._slot_blocks[slot]:
            raise RuntimeError(f"slot {slot} still holds blocks; "
                               "free_slot it before re-admitting")
        total = self.request_blocks(prompt_len, max_new_tokens)
        now = blocks_for(prompt_len, self.block_size)
        if self.window_group is not None:
            if shared:
                raise RuntimeError(
                    "prefix aliasing is the full group's alone: a pool "
                    "with a window group shares nothing")
            self.window_group.admit(slot, prompt_len, max_new_tokens)
        if len(shared) > now:
            raise ValueError(
                f"{len(shared)} shared blocks exceed the prompt's "
                f"{now}-block footprint")
        if total - len(shared) > self.free_blocks:
            raise RuntimeError(
                f"admit of {total - len(shared)} blocks exceeds free "
                f"{self.free_blocks} (call can_admit first)")
        self._cap[slot] = min(prompt_len + max_new_tokens,
                              self.max_seq_len)
        self._reserved_total += total - now
        if shared:
            self.share(slot, shared)
        self._grow_to(slot, now)
        self._watermark[slot] = prompt_len
        self._prompt_len[slot] = prompt_len
        self._publish()

    def share(self, slot: int, blocks: Sequence[int]) -> None:
        """Alias ``blocks`` under ``slot``'s leading positions
        (refcount++ each).  The slot must be empty — a shared prefix is
        by construction the FIRST thing in a sequence — and treats the
        aliased run as read-only: the only legal write into it is the
        :meth:`cow_write` split of its final block."""
        blks = self._slot_blocks[slot]
        if blks:
            raise RuntimeError(
                f"share() into non-empty slot {slot}: a shared prefix "
                "must precede any private blocks")
        for blk in blocks:
            self._refcount[blk] += 1
            self.table[slot, len(blks)] = blk
            blks.append(blk)
        self._shared_upto[slot] = len(blks)

    def cow_write(self, slot: int, block_idx: int) -> int:
        """Make ``slot``'s block at ``block_idx`` privately writable,
        splitting (new private block, old refcount--) if it is aliased
        or pinned.  Only the LAST shared block is a legal target: that
        is the one block the serving protocol ever writes inside a
        shared region (a full-prompt hit recomputing its final position
        for the first output logit).  Returns the block now under the
        slot — the caller re-inserts that block's content from its
        recomputed dense cache, which IS the copy."""
        if slot in self._migrating:
            raise RuntimeError(
                f"cow_write on slot {slot} while its KV is in migration")
        blks = self._slot_blocks[slot]
        old = blks[block_idx]
        if self._refcount[old] == 1 and old not in self._pinned:
            return old  # already private — write in place
        if block_idx != self._shared_upto[slot] - 1:
            raise RuntimeError(
                f"cow_write at index {block_idx} of slot {slot}, but only "
                f"the last shared block "
                f"({self._shared_upto[slot] - 1}) is writable")
        new = self._take_block()
        self._refcount[old] -= 1
        self._refcount[new] = 1
        blks[block_idx] = new
        self.table[slot, block_idx] = new
        self._shared_upto[slot] = block_idx
        self._obs_cow.inc()
        self._publish()
        return new

    def covered_pages(self, slot: int) -> int:
        """Pages under ``slot``'s coverage watermark: its KV length by the
        host's own count, in blocks — what a decode step walks for it."""
        return blocks_for(self._watermark[slot], self.block_size)

    def covered_rows(self, slot: int) -> int:
        """``slot``'s coverage watermark itself: the rows of its KV length
        by the host's own count, the number ``covered_pages`` rounds up."""
        return self._watermark[slot]

    def grow(self, slot: int, steps: int) -> None:
        """Advance ``slot``'s coverage by ``steps`` decode tokens (capped
        at its reservation), allocating from the reserved budget — this
        can never fail for an admitted slot."""
        if slot in self._migrating:
            raise RuntimeError(
                f"grow on slot {slot} while its KV is in migration")
        target = min(self._watermark[slot] + steps, self._cap[slot])
        if self.window_group is not None:
            self.window_group.grow(slot, self._watermark[slot], target)
        need = blocks_for(target, self.block_size)
        have = len(self._slot_blocks[slot])
        if need > have:
            self._reserved_total -= need - have
            self._grow_to(slot, need)
        self._watermark[slot] = target
        self._publish()

    def _grow_to(self, slot: int, count: int) -> None:
        blks = self._slot_blocks[slot]
        while len(blks) < count:
            blk = self._take_block()
            self._refcount[blk] = 1
            self.table[slot, len(blks)] = blk
            blks.append(blk)

    def free_slot(self, slot: int) -> None:
        """Decrement ``slot``'s block refcounts and return its unused
        reservation; blocks reaching refcount 0 go back to the free list
        unless the prefix cache pins them (those stay resident as
        cached-idle capacity, reclaimed lazily by LRU eviction)."""
        if slot in self._migrating:
            raise RuntimeError(
                f"free_slot on slot {slot} while its KV is in migration; "
                "complete_export or abort_export it first")
        blks = self._slot_blocks[slot]
        held = blocks_for(self._cap[slot], self.block_size) if blks else 0
        self._reserved_total -= max(held - len(blks), 0)
        drop = []
        for blk in blks:
            self._refcount[blk] -= 1
            if not self._refcount[blk] and blk not in self._pinned:
                drop.append(blk)
        self._free.extend(reversed(drop))
        blks.clear()
        self.table[slot, :] = 0
        self._watermark[slot] = 0
        self._cap[slot] = 0
        self._shared_upto[slot] = 0
        self._prompt_len[slot] = 0
        if self.window_group is not None:
            self.window_group.free_slot(slot)
        self._publish()

    # -- KV migration (disaggregated prefill/decode) ----------------------

    def export_slot(self, slot: int) -> dict:
        """Begin migrating ``slot``'s KV to another pool.

        Returns the migration manifest — the slot's ORDERED block list
        (pool indices, leftmost = logical position 0), its prompt
        length, coverage watermark, and shared-prefix boundary — and
        freezes the slot: until :meth:`complete_export` (the ack) or
        :meth:`abort_export` lands, the slot may not be freed, grown,
        or COW-split, and :meth:`check` asserts its pages stay off the
        free list.  The caller reads the device pages named by
        ``blocks`` while the freeze holds."""
        blks = self._slot_blocks[slot]
        if self.window_group is not None:
            raise RuntimeError(
                "KV export carries one block list a slot: a pool with a "
                "window group exports nothing")
        if not blks:
            raise RuntimeError(f"export_slot on empty slot {slot}")
        if slot in self._migrating:
            raise RuntimeError(f"slot {slot} already in migration")
        self._migrating[slot] = list(blks)
        return {
            "blocks": list(blks),
            "prompt_len": self._prompt_len[slot],
            "watermark": self._watermark[slot],
            "shared_upto": self._shared_upto[slot],
            "block_size": self.block_size,
        }

    def complete_export(self, slot: int) -> None:
        """Ack ``slot``'s migration: the payload has been copied out of
        the pool's pages, so the freeze lifts and the slot frees."""
        if slot not in self._migrating:
            raise RuntimeError(f"slot {slot} is not in migration")
        del self._migrating[slot]
        self.free_slot(slot)

    def abort_export(self, slot: int) -> None:
        """Cancel ``slot``'s migration without freeing it — the slot is
        whole again (the export never mutated it) and the caller decides
        what happens next (resume serving it locally, or free it)."""
        self._migrating.pop(slot, None)

    def adopt_blocks(self, slot: int, prompt_len: int,
                     max_new_tokens: int) -> list[int]:
        """Allocate pages for a migrated-in sequence: ``slot`` receives
        fresh blocks covering ``prompt_len`` positions plus the same
        worst-case reservation :meth:`admit` would take, and the caller
        scatters the received KV bytes into the returned block indices.
        No prefix aliasing — migrated pages are private to the slot
        (the local prefix cache never saw their token chain prefill
        here, so registration happens separately if at all).  Caller
        must have checked :meth:`can_admit` first."""
        self.admit(slot, prompt_len, max_new_tokens)
        return list(self._slot_blocks[slot])

    # -- prefix-cache pinning ---------------------------------------------

    def cache_pin(self, blk: int) -> None:
        self._pinned.add(blk)

    def cache_unpin(self, blk: int) -> None:
        """Drop the cache's pin; if no slot references the block either,
        it returns to the free list immediately."""
        self._pinned.discard(blk)
        if not self._refcount[blk]:
            self._free.append(blk)
        self._publish()

    def alloc_cached_block(self) -> int | None:
        """A plain-free block, taken and PINNED as cached-idle (refcount
        stays 0) — the landing page for a host-tier re-admit or a
        pull-mode install, whose bytes arrive by scatter rather than by
        prefill.  Deliberately never triggers the eviction hook: paging
        one cached block in must not page another cached block out
        (tier thrash), so when only reclaimable-cached capacity is left
        the caller skips the install and re-prefills instead.  Returns
        ``None`` in that case."""
        if not self._free:
            return None
        blk = self._free.pop()
        self._pinned.add(blk)
        self._publish()
        return blk


class PrefixCache:
    """Host-side map from rolling prefix-hash chains to pool blocks.

    One entry per FULL block of a registered prompt: ``chain_hashes(
    prompt)[j] -> block``, where the chain construction guarantees the
    hash names the block's content and its entire prefix.  Matching a
    new prompt walks its own chain left to right and collects blocks
    while hashes keep hitting — the longest cached prefix, always
    block-aligned.

    Entries PIN their blocks in the pool, so an idle prefix survives
    ``free_slot`` and the next same-prefix admission aliases it back in
    via :meth:`BlockPool.share`.  Eviction is LRU and only over entries
    whose block no live slot references (refcount 0) — evicting a block
    under a live slot would tear KV out from under in-flight decode.
    The pool calls :meth:`_evict_for_pool` on demand when its free list
    runs dry, which is what lets cached-idle blocks count as capacity.

    Registration is first-wins: a hash already present keeps its
    original block (the new admission's identical copy stays private to
    its slot and is freed normally).  Content safety: a pinned block is
    written only by the admission that registered it, below its
    prompt's coverage — decode writes land past the prompt, COW splits
    move writers OFF the cached block — so a hit always aliases bytes
    bit-identical to a fresh prefill (greedy determinism holds).
    """

    def __init__(self, pool: BlockPool,
                 capacity_blocks: int | None = None) -> None:
        self.pool = pool
        self.capacity_blocks = capacity_blocks
        self._entries: OrderedDict[int, int] = OrderedDict()
        # chain-parent links (hash -> previous chain hash, None for a
        # prompt's first block): the spill hook forwards them so the
        # host tier can evict by chain suffix
        self._parent: dict[int, int | None] = {}
        # set by the serve loop when a host tier exists: called as
        # ``spill_hook(hash, block, parent)`` just before an evicted
        # block's pin drops (the block is refcount-0, so its page bytes
        # are stable — the hook's one chance to copy them to host RAM)
        self.spill_hook: Callable[[int, int, int | None], None] | None \
            = None
        pool._evict_hook = self._evict_for_pool
        self._obs_hits = obs.counter("serve/prefix_hits", unit="requests")
        self._obs_hit_tokens = obs.counter(
            "serve/prefix_hit_tokens", unit="tokens")
        self._obs_evictions = obs.counter(
            "serve/prefix_evictions", unit="blocks")
        self._obs_cached = obs.gauge(
            "serve/prefix_cached_blocks", unit="blocks")

    def __len__(self) -> int:
        return len(self._entries)

    def match(self, tokens: Sequence[int]) -> list[int]:
        """Blocks covering the longest cached prefix of ``tokens``
        (possibly all of it).  Touches matched entries' LRU recency but
        takes no references — the caller aliases the blocks via
        ``admit(..., shared=...)``, which is what protects them from
        eviction while the request lives."""
        out: list[int] = []
        for h in chain_hashes(tokens, self.pool.block_size):
            blk = self._entries.get(h)
            if blk is None:
                break
            self._entries.move_to_end(h)
            out.append(blk)
        if out:
            self._obs_hits.inc()
            self._obs_hit_tokens.inc(len(out) * self.pool.block_size)
        return out

    def peek(self, tokens: Sequence[int]) -> int:
        """Matched block count WITHOUT touching recency or the hit
        metrics — admission control's capacity precheck (the real
        :meth:`match` runs once, at the admit that follows)."""
        n = 0
        for h in chain_hashes(tokens, self.pool.block_size):
            if h not in self._entries:
                break
            n += 1
        return n

    def register(self, tokens: Sequence[int],
                 slot_blocks: Sequence[int]) -> int:
        """Pin and index ``tokens``'s fully-covered blocks (first-wins
        per hash).  ``slot_blocks`` is the owning slot's block list from
        the admission that just prefilled them.  Returns the number of
        newly registered blocks."""
        added = 0
        hashes = chain_hashes(tokens, self.pool.block_size)
        for j, h in enumerate(hashes):
            if h in self._entries:
                self._entries.move_to_end(h)
                continue
            while (self.capacity_blocks is not None
                   and len(self._entries) >= self.capacity_blocks):
                if not self.evict_one():
                    break
            if (self.capacity_blocks is not None
                    and len(self._entries) >= self.capacity_blocks):
                break
            self._entries[h] = slot_blocks[j]
            self._parent[h] = hashes[j - 1] if j else None
            self.pool.cache_pin(slot_blocks[j])
            added += 1
        self._obs_cached.set(len(self._entries))
        self.pool._publish()
        return added

    def install(self, h: int, blk: int, parent: int | None) -> None:
        """Index an externally-filled cached-idle block under ``h`` —
        the landing half of a host-tier re-admit or a pull-mode
        install.  ``blk`` must come from
        :meth:`BlockPool.alloc_cached_block` (already pinned, refcount
        0) with the page bytes scattered in by the caller; from here on
        the entry is indistinguishable from one :meth:`register` made.
        First-wins like registration: a hash already resident keeps its
        block and the caller must not have allocated for it."""
        if h in self._entries:
            raise RuntimeError(
                f"install of already-resident prefix hash {h}")
        if blk not in self.pool._pinned or self.pool._refcount[blk]:
            raise RuntimeError(
                f"install target block {blk} is not cached-idle "
                "(use alloc_cached_block)")
        self._entries[h] = blk
        self._parent[h] = parent
        self._obs_cached.set(len(self._entries))
        self.pool._publish()

    def evict_one(self) -> bool:
        """Drop the least-recently-used entry whose block no live slot
        references.  Returns False when every entry is in use."""
        for h, blk in self._entries.items():  # OrderedDict: LRU first
            if not self.pool._refcount[blk]:
                del self._entries[h]
                parent = self._parent.pop(h, None)
                if self.spill_hook is not None:
                    # the block is refcount-0 and still pinned: its
                    # page bytes are stable, so the hook can copy them
                    # to the host tier before the pin (and page) drop
                    self.spill_hook(h, blk, parent)
                self.pool.cache_unpin(blk)
                self._obs_evictions.inc()
                self._obs_cached.set(len(self._entries))
                return True
        return False

    def _evict_for_pool(self) -> bool:
        """Pool callback: free at least one block into the free list."""
        return self.evict_one()

    def flush(self) -> None:
        """Drop every entry — cached KV is invalid the moment weights
        hot-swap.  Blocks still referenced by live slots (there are none
        at the drain-gated swap point, but be safe) just lose their pin
        and are freed by their slot's finalize.  Deliberately does NOT
        spill: flush means the bytes are invalid, not cold."""
        for h, blk in list(self._entries.items()):
            del self._entries[h]
            self.pool.cache_unpin(blk)
        self._parent.clear()
        self._obs_cached.set(0)
