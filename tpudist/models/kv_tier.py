"""Host-RAM KV spill tier: the second level of the KV memory hierarchy.

HBM is the fleet's scarcest resource: at any instant most prefix-cached
pages are COLD, yet PR 14's :class:`~tpudist.models.kv_pages.PrefixCache`
frees refcount-0 blocks the moment HBM pressure arrives, and the bytes
are gone — the next same-prefix admission pays a full re-prefill.  The
:class:`HostTier` catches those evictions instead: when the prefix cache
evicts an idle block, its page bytes move to pinned host RAM, keyed by
the SAME rolling chain hash that indexed it in HBM (one entry per full
block; the hash names the block's content and its entire prefix, so a
tier hit is exactly as trustworthy as an HBM cache hit).  A later
admission whose chain walks past the HBM-resident prefix continues into
the tier and re-admits the spilled blocks (host -> HBM scatter, staged
off the dispatch path), turning what would have been re-prefill compute
into a memcpy.

Tier state machine for one chain hash ``h`` (the block-content name, not
a pool index — the pool's page is recycled the moment it spills)::

      (uncached) --register--> HBM-resident --evict+spill--> TIERED
          ^                        ^                            |
          |                        +---------re-admit-----------+
          +-----flush / budget-evict / version-mismatch---------+

A hash is never simultaneously HBM-resident and tiered: the spill
removes it from the cache before :meth:`put`, the re-admit installs it
in the cache before :meth:`take` removes it here.
:meth:`check` asserts that disjointness (alongside the pool's own
live/free/frozen invariants) plus the tier's internal accounting.

Eviction is LRU **by chain suffix**: a chain walk needs CONSECUTIVE
hits, so evicting a mid-chain entry while its extension survives would
leave unreachable bytes — a hole at link ``j`` makes every resident
link past ``j`` dead weight.  The tier therefore only evicts entries
with no tier-resident child (chain leaves), trimming chains from the
deep (cold, long-prefix) end inward; the budget
(``TPUDIST_KV_HOST_TIER_BYTES``) is enforced at :meth:`put` time.

Weight hot-swaps invalidate cached KV; tier entries are stamped with
the serving weights version at :meth:`put` and a lookup under any OTHER
version drops the entry instead of returning it — a post-swap hit can
never adopt pre-swap KV (the serve loop also flushes the tier outright
at the swap point; the stamp is the belt to that suspender).
"""

from __future__ import annotations

import os
from collections import OrderedDict

import numpy as np

from tpudist import obs

__all__ = ["HostTier", "tier_budget_from_env", "DEFAULT_TIER_BYTES"]

# 64 MiB default: plenty for the test models, obviously tunable
# for real fleets via TPUDIST_KV_HOST_TIER_BYTES (0 disables the tier)
DEFAULT_TIER_BYTES = 64 * 1024 * 1024


def tier_budget_from_env(default: int = DEFAULT_TIER_BYTES) -> int:
    """Host-tier byte budget from ``TPUDIST_KV_HOST_TIER_BYTES``;
    ``0`` (or any unparsable value) disables the tier."""
    raw = os.environ.get("TPUDIST_KV_HOST_TIER_BYTES")
    if raw is None:
        return int(default)
    try:
        return max(0, int(raw))
    except ValueError:
        return 0


class HostTier:
    """Bounded host-RAM store of spilled KV blocks, keyed by chain hash.

    One entry per spilled block::

        hash -> {"layers":  [{"k": [bs, F], "v": [bs, F]}, ...],
                 "parent":  previous chain link (None for block 0),
                 "version": weights version the bytes were computed
                            under,
                 "nbytes":  page bytes held}

    ``layers`` follows the migration-payload convention (one dict per
    paged layer in cache-walk order), so tier bytes drop straight into
    the pull-mode export payload or the re-admit scatter without
    reshaping.
    """

    def __init__(self, budget_bytes: int) -> None:
        self.budget_bytes = max(0, int(budget_bytes))
        self._entries: OrderedDict[int, dict] = OrderedDict()
        # resident-children index: _children[h] = tier-resident hashes
        # whose parent link is h.  An entry with resident children is
        # mid-chain and NOT evictable (see module docstring).
        self._children: dict[int, set[int]] = {}
        self._nbytes = 0
        self._obs_blocks = obs.gauge("serve/tier_blocks", unit="blocks")
        self._obs_bytes = obs.gauge("serve/tier_bytes", unit="bytes")
        self._obs_budget = obs.gauge("serve/tier_budget_bytes",
                                     unit="bytes")
        self._obs_hits = obs.counter("serve/tier_hits", unit="blocks")
        self._obs_spills = obs.counter("serve/tier_spills", unit="blocks")
        self._obs_evictions = obs.counter("serve/tier_evictions",
                                          unit="blocks")
        self._obs_readmits = obs.counter("serve/tier_readmits",
                                         unit="blocks")
        self._obs_budget.set(self.budget_bytes)
        self._publish()

    def __len__(self) -> int:
        return len(self._entries)

    def __contains__(self, h: int) -> bool:
        return int(h) in self._entries

    @property
    def nbytes(self) -> int:
        return self._nbytes

    def hashes(self) -> list[int]:
        """Resident chain hashes, LRU-first (for residency summaries)."""
        return list(self._entries)

    def _publish(self) -> None:
        self._obs_blocks.set(len(self._entries))
        self._obs_bytes.set(self._nbytes)

    @staticmethod
    def _layers_nbytes(layers: list[dict]) -> int:
        return int(sum(np.asarray(l["k"]).nbytes
                       + np.asarray(l["v"]).nbytes for l in layers))

    # -- admission ---------------------------------------------------------

    def put(self, h: int, layers: list[dict], *, parent: int | None,
            version: int = 0) -> bool:
        """Admit one spilled block.  First-wins per hash (a resident
        entry keeps its bytes and just refreshes recency).  Returns
        False when the tier is disabled, the entry alone exceeds the
        budget, or eviction cannot make room (every colder entry is
        mid-chain)."""
        h = int(h)
        if h in self._entries:
            self._entries.move_to_end(h)
            return True
        layers = [{"k": np.ascontiguousarray(np.asarray(l["k"])),
                   "v": np.ascontiguousarray(np.asarray(l["v"]))}
                  for l in layers]
        n = self._layers_nbytes(layers)
        if not self.budget_bytes or n > self.budget_bytes:
            return False
        while self._nbytes + n > self.budget_bytes:
            if not self.evict_one():
                return False
        self._entries[h] = {"layers": layers, "parent": parent,
                            "version": int(version), "nbytes": n}
        if parent is not None:
            self._children.setdefault(int(parent), set()).add(h)
        self._nbytes += n
        self._obs_spills.inc()
        self._publish()
        return True

    # -- lookup ------------------------------------------------------------

    def has(self, h: int, *, version: int | None = None) -> bool:
        """Residency probe — no recency touch, no metrics.  A version
        mismatch reads as absent (the entry is dropped lazily by
        :meth:`take`)."""
        e = self._entries.get(int(h))
        if e is None:
            return False
        return version is None or e["version"] == int(version)

    def match_chain(self, hashes, *, version: int | None = None) -> int:
        """Length of the longest leading run of ``hashes`` resident
        under ``version`` — the tier half of a prefix-plan probe."""
        n = 0
        for h in hashes:
            if not self.has(h, version=version):
                break
            n += 1
        return n

    def take(self, h: int, *, version: int | None = None) -> list | None:
        """Remove and return ``h``'s layers for re-admission to HBM
        (ticks ``serve/tier_hits`` + ``serve/tier_readmits``).  A
        version mismatch DROPS the stale entry and returns ``None`` —
        pre-swap bytes must never flow back into the cache."""
        h = int(h)
        e = self._entries.get(h)
        if e is None:
            return None
        if version is not None and e["version"] != int(version):
            self._remove(h)
            self._publish()
            return None
        layers = e["layers"]
        self._remove(h)
        self._obs_hits.inc()
        self._obs_readmits.inc()
        self._publish()
        return layers

    def peek_layers(self, h: int, *,
                    version: int | None = None) -> list | None:
        """``h``'s layers WITHOUT removal (pull-mode export reads tier
        bytes in place — the entry stays resident for local hits).
        Ticks ``serve/tier_hits`` only."""
        e = self._entries.get(int(h))
        if e is None:
            return None
        if version is not None and e["version"] != int(version):
            return None
        self._entries.move_to_end(int(h))
        self._obs_hits.inc()
        return e["layers"]

    # -- eviction ----------------------------------------------------------

    def _remove(self, h: int) -> None:
        e = self._entries.pop(h)
        self._nbytes -= e["nbytes"]
        parent = e["parent"]
        if parent is not None:
            sibs = self._children.get(int(parent))
            if sibs is not None:
                sibs.discard(h)
                if not sibs:
                    del self._children[int(parent)]

    def discard(self, h: int) -> None:
        """Drop ``h`` if resident, silently (no hit/eviction metrics):
        the caller just made the hash HBM-resident again (admission
        ``register`` of a prompt whose re-admit stopped early, or a
        pull install of a link that was also spilled locally), and a
        hash must never be simultaneously tiered and cached.  A
        surviving tier CHILD of ``h`` stays reachable: the chain walk
        continues from the now-HBM-resident parent into the tier."""
        if int(h) in self._entries:
            self._remove(int(h))
            self._publish()

    def evict_one(self) -> bool:
        """Evict the least-recently-used chain LEAF (no tier-resident
        child).  Returns False when every entry is mid-chain — which
        cannot happen while any entry exists (a finite parent forest
        always has leaves), so False means the tier is empty."""
        for h in self._entries:  # OrderedDict: LRU first
            if not self._children.get(h):
                self._remove(h)
                self._obs_evictions.inc()
                self._publish()
                return True
        return False

    def flush(self) -> None:
        """Drop everything — cached KV is invalid the moment weights
        hot-swap (the version stamps are the backstop for entries that
        would somehow survive; this is the front door)."""
        self._entries.clear()
        self._children.clear()
        self._nbytes = 0
        self._publish()

    # -- invariants --------------------------------------------------------

    def check(self, resident_hashes=()) -> None:
        """Tier invariants, cheap enough for every-op test cadence:
        byte accounting exact, budget respected, children index derived
        exactly from resident parent links, and — the cross-structure
        rule — no hash simultaneously tiered and HBM-resident
        (``resident_hashes`` is the prefix cache's key set)."""
        n = sum(e["nbytes"] for e in self._entries.values())
        if n != self._nbytes:
            raise AssertionError(
                f"tier byte drift: entries hold {n}, recorded "
                f"{self._nbytes}")
        for h, e in self._entries.items():
            if e["nbytes"] != self._layers_nbytes(e["layers"]):
                raise AssertionError(f"tier entry {h} nbytes drift")
        if self.budget_bytes and self._nbytes > self.budget_bytes:
            raise AssertionError(
                f"tier over budget: {self._nbytes} > {self.budget_bytes}")
        want: dict[int, set[int]] = {}
        for h, e in self._entries.items():
            if e["parent"] is not None:
                want.setdefault(int(e["parent"]), set()).add(h)
        if want != self._children:
            raise AssertionError(
                f"tier children index drift: derived {want}, "
                f"recorded {self._children}")
        both = set(self._entries) & {int(h) for h in resident_hashes}
        if both:
            raise AssertionError(
                f"hashes simultaneously tiered and HBM-resident: "
                f"{sorted(both)}")
