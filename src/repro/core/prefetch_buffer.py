"""Paged device buffer for prefetched IVF clusters, backed by the shared
``DevicePagePool``.

TPU analogue of the paper's pinned-CPU→GPU contiguous prefetch buffer
(Appendix D): cluster pages live in the replica-wide HBM slab owned by
``repro.memory.DevicePagePool``; this class keeps the *cluster* view —
which clusters are resident, in which page slots (their block tables),
which waves have them pinned — and routes all device mutation through
the pool's ONE batched, donated scatter per prefetch round (the JAX
equivalent of an async DMA burst; dispatch is async, so subsequent
decode steps overlap with it).

Consistency invariants (tests/test_core.py, tests/test_memory.py):
  * a device slot always holds a whole, un-corrupted page of exactly one
    cluster (page granularity transfers);
  * eviction is host bookkeeping + queued device invalidation — a slot is
    never searchable once its cluster was evicted (no duplicate results
    after refetch into different slots);
  * a cluster pinned by an in-flight wave is never evicted from under it
    (release happens on the wave's completion event);
  * transfers are counted in bytes for the budget/telemetry layer, and an
    invalidation-only scatter is NOT a transfer round (zero new pages
    moved means zero H2D rounds).
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Dict, List, Optional, Sequence, Set, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.datastore import PagedClusters
from repro.memory.pool import DevicePagePool, PageLease, Reservation


@dataclass
class TransferStats:
    bytes_h2d: int = 0
    pages_h2d: int = 0
    rounds: int = 0

    def add(self, pages: int, page_bytes: int):
        self.pages_h2d += pages
        self.bytes_h2d += pages * page_bytes
        self.rounds += 1


class PrefetchBuffer:
    def __init__(self, paged: PagedClusters, num_pages: Optional[int] = None,
                 *, pool: Optional[DevicePagePool] = None,
                 quota_pages: Optional[int] = None):
        if pool is None:
            if num_pages is None:
                raise ValueError("need num_pages or a pool")
            pool = DevicePagePool(paged, num_pages)
        self.paged = paged
        self.pool = pool
        # the prefetch share of the pool (cache quotas key off this, not
        # the slab extent, so pool size never changes cache behaviour)
        self.quota_pages = (quota_pages if quota_pages is not None
                            else pool.num_pages)
        # host mirrors / page table
        self.slot_cluster = np.full(pool.num_pages, -1, np.int64)
        self.resident: Dict[int, List[int]] = {}
        self._leases: Dict[int, PageLease] = {}          # cluster -> lease
        self._pins: Dict[object, List[PageLease]] = {}   # wave key -> leases
        self._pending_invalid: Set[int] = set()
        self.stats = TransferStats()

    # -- capacity ----------------------------------------------------------
    @property
    def num_pages(self) -> int:
        return self.pool.num_pages

    @property
    def pages(self) -> jax.Array:
        return self.pool.pages

    @property
    def page_ids(self) -> jax.Array:
        return self.pool.page_ids

    @property
    def page_cluster(self) -> jax.Array:
        return self.pool.page_cluster

    @property
    def page_nbytes(self) -> int:
        return self.paged.page_nbytes()

    @property
    def capacity_bytes(self) -> int:
        return self.pool.capacity_bytes

    @property
    def used_pages(self) -> int:
        return self.pool.used_pages

    def free_pages(self) -> int:
        return self.pool.free_pages()

    def resident_clusters(self) -> Set[int]:
        return set(self.resident)

    def is_resident(self, cluster: int) -> bool:
        return cluster in self.resident

    # -- pinning (waves hold their working set until completion) -----------
    def pin_clusters(self, key: object,
                     clusters: Sequence[int]) -> List[PageLease]:
        """Take a reference on each resident cluster for wave ``key`` so
        spill/eviction cannot reclaim it while the wave is in flight.
        Returns the leases pinned (for a targeted ``release_pins``)."""
        pinned = self._pins.setdefault(key, [])
        taken: List[PageLease] = []
        for c in clusters:
            lease = self._leases.get(int(c))
            if lease is not None:
                self.pool.retain(lease)
                pinned.append(lease)
                taken.append(lease)
        return taken

    def release_pins(self, key: object, leases: Sequence[PageLease]) -> None:
        """Drop exactly these previously-taken pins for wave ``key`` (a
        parked wave must not hold its tentative hit pins — other parked
        waves would mutually wait on them)."""
        held = self._pins.get(key, [])
        for lease in leases:
            held.remove(lease)
            if lease.lease_id in self.pool.leases:
                self.pool.release(lease)

    def unpin(self, key: object) -> int:
        """Drop wave ``key``'s references; returns pages made evictable."""
        pages = 0
        for lease in self._pins.pop(key, []):
            if lease.lease_id in self.pool.leases:   # force-evict already
                pages += lease.num_pages if lease.refcount == 2 else 0
                self.pool.release(lease)             # dropped stale pins
        return pages

    def pinned_clusters(self) -> Set[int]:
        return {c for c, l in self._leases.items() if l.refcount > 1}

    def _own_lease_ids(self, key: object) -> Set[int]:
        """Lease ids pinned under ``key`` — a single pin key, or a
        tuple/list/set of keys (a continuous-batching wave's view is
        the union of its member requests' pins)."""
        if key is None:
            return set()
        if isinstance(key, (tuple, list, set, frozenset)):
            own: Set[int] = set()
            for k in key:
                own.update(l.lease_id for l in self._pins.get(k, ()))
            return own
        return {l.lease_id for l in self._pins.get(key, ())}

    def reclaimable_split(self, key: object,
                          hit_clusters: Sequence[int] = (),
                          ) -> Tuple[int, int]:
        """(waitable, spillable) page counts from wave ``key``'s view:
        *waitable* pages are pinned by other in-flight waves (their
        completion events release them — legitimate stall targets),
        *spillable* pages are unpinned residency evictable right now.
        The wave's own pins and the given ``hit_clusters`` (residency
        the wave is about to pin as its device hits) count as neither.
        ``key`` may be one pin key or a collection of per-request pin
        keys (the wave's members under continuous batching)."""
        own = self._own_lease_ids(key)
        hits = {int(c) for c in hit_clusters}
        waitable = spillable = 0
        for c, lease in self._leases.items():
            if lease.lease_id in own or c in hits:
                continue
            if lease.refcount > 1:
                waitable += lease.num_pages
            else:
                spillable += lease.num_pages
        return waitable, spillable

    def pages_pinned_by_others(self, key: object) -> int:
        """Pages pinned by in-flight waves other than ``key``."""
        return self.reclaimable_split(key)[0]

    # -- load --------------------------------------------------------------
    def load_clusters(self, clusters: Sequence[int], *,
                      reservation: Optional[Reservation] = None,
                      ) -> Tuple[List[int], List[int]]:
        """Fetch whole clusters into pool slots. Returns (loaded, rejected).

        Rejected = the pool cannot lease the *whole* cluster (admission
        should have reserved headroom; kept as a hard guarantee).
        """
        loaded: List[int] = []
        rejected: List[int] = []
        slot_list: List[int] = []
        np_pages: List[np.ndarray] = []
        np_ids: List[np.ndarray] = []
        np_cl: List[int] = []
        for c in clusters:
            c = int(c)
            if c in self.resident:
                loaded.append(c)
                continue
            npg = int(self.paged.cluster_num_pages[c])
            lease = self.pool.lease_slots(npg, "prefetch", tag=c,
                                          reservation=reservation)
            if lease is None:
                rejected.append(c)
                continue
            slots = list(lease.slots)
            self.resident[c] = slots
            self._leases[c] = lease
            self.slot_cluster[slots] = c
            self._pending_invalid.difference_update(slots)
            pg = self.paged.cluster_pages(c)
            pidc = self.paged.cluster_page_ids(c)
            for i, s in enumerate(slots):
                slot_list.append(s)
                np_pages.append(pg[i])
                np_ids.append(pidc[i])
                np_cl.append(c)
            loaded.append(c)

        # fold queued invalidations into the same scatter
        for s in sorted(self._pending_invalid):
            slot_list.append(s)
            np_pages.append(np.zeros((self.paged.page_size, self.paged.dim),
                                     np.float32))
            np_ids.append(np.full(self.paged.page_size, -1, np.int32))
            np_cl.append(-1)
        self._pending_invalid.clear()

        if slot_list:
            self.pool.scatter(slot_list, np_pages, np_ids, np_cl)
            new_pages = sum(1 for c in np_cl if c >= 0)
            if new_pages:          # invalidation-only flushes move no bytes
                self.stats.add(new_pages, self.page_nbytes)
        return loaded, rejected

    # -- evict -------------------------------------------------------------
    def evict_clusters(self, clusters: Sequence[int], *,
                       force: bool = False) -> int:
        """Host-side free + queued device invalidation. Returns pages freed.

        A cluster pinned by an in-flight wave is skipped unless ``force``
        (its pages belong to that wave until its completion event).
        """
        freed = 0
        for c in clusters:
            c = int(c)
            lease = self._leases.get(c)
            if lease is None:
                continue
            if lease.refcount > 1 and not force:
                continue
            slots = self.resident.pop(c)
            del self._leases[c]
            self.slot_cluster[slots] = -1
            self._pending_invalid.update(slots)
            while lease.lease_id in self.pool.leases:
                self.pool.release(lease)   # force: strip remaining pins too
            freed += len(slots)
        return freed

    def flush_invalidations(self) -> None:
        """Force queued invalidations to the device (normally folded into
        the next load; needed before a search with no intervening load).
        Moves zero new pages, so it never counts as a transfer round."""
        if self._pending_invalid:
            self.load_clusters([])

    # -- views for the search kernel ----------------------------------------
    def device_view(self):
        return self.pool.device_view()

    def allowed_lut(self, clusters: Sequence[int]) -> jax.Array:
        """Boolean LUT [Nc] marking clusters searchable on-device."""
        lut = np.zeros(self.paged.num_clusters + 1, bool)   # +1: cluster -1 pad
        res = [c for c in clusters if c in self.resident]
        lut[res] = True
        return jnp.asarray(lut)
