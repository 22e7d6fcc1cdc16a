"""Host-side prefix cache: tokenized prompt prefix -> resident KV pages.

The shared-system-prompt-times-a-million-users pattern: identical
prompt prefixes should occupy the page pool ONCE.  This module is pure
host bookkeeping over the device-resident pool of serving/kv_cache.py —
it never touches a jax array and is owned by the engine's single decode
thread, so it needs no lock.

Sharing is full-page-only: a prompt of length L can share at most
``floor((L - 1) / page_size)`` pages (the -1 guarantees at least one
suffix token so admission always has a position to compute logits at,
and full-page alignment means the copy-on-write boundary page is always
the slot's own freshly allocated page — shared pages are strictly
read-only).  On a miss the admitting request registers one entry per
prefix page count (keys are the raw token bytes of each full-page
prefix), so a later request sharing ANY page-aligned prefix hits
regardless of how the two prompts' lengths differ.

Lifetime is refcount-per-page: a page is referenced by every cache
entry containing it plus every active slot pinned to it.  LRU eviction
(bounded entry count) and slot release decrement; pages reaching zero
are handed back to the engine, which returns them to the device free
stack through the ``reclaim`` executable.

For a model whose layers hold a recurrent state beside their pages
(serving/kv_cache.py ``state_layers``) a hit is worth only as deep as a
SNAPSHOT of that state lies: page ids map K/V back, nothing maps a state
back.  The cache then also keeps which of its entries has a snapshot in
the device's pool of ``snapshots`` places (``take_snapshot``), finds the
deepest one under a match (``lookup_state``), reuses the place of the one
used longest ago when the pool is full, and lets a snapshot go with its
entry's pages: ``snapshots_live`` x one lane's state is what they hold.
"""
from __future__ import annotations

import collections

__all__ = ["PrefixCache"]


class PrefixCache:
    """Refcounted read-only shared KV pages keyed by prompt prefix."""

    def __init__(self, page_size: int, capacity: int = 1024,
                 split: int | None = None, snapshots: int = 0):
        if page_size < 1:
            raise ValueError(f"page_size must be >= 1, got {page_size}")
        if capacity < 1:
            raise ValueError(f"capacity must be >= 1, got {capacity}")
        self.page_size = int(page_size)
        self.capacity = int(capacity)
        self._entries = collections.OrderedDict()  # key -> tuple(page ids)
        self._rc: dict[int, int] = {}              # page id -> refcount
        # an engine with a second pool (window layers, kv_cache.py): its
        # page w goes by the id split + w, an entry of j pages holds j ids
        # of each pool, and rows are [2, pages_per_slot]
        self.split = None if split is None else int(split)
        self.resident_high = 0                     # resident ids >= split
        # state snapshots: entry key -> its place in the device's pool,
        # least recently restored first
        self._snaps = collections.OrderedDict()
        self._snap_free = list(range(int(snapshots) - 1, -1, -1))
        self.snapshots_taken = 0
        self.snapshots_evicted = 0

    # -- introspection -----------------------------------------------------
    def __len__(self) -> int:
        return len(self._entries)

    @property
    def resident_pages(self) -> int:
        """Pages currently held resident by entries and/or slot pins —
        the scheduler subtracts these from the allocatable pool."""
        return len(self._rc)

    @property
    def snapshots_live(self) -> int:
        return len(self._snaps)

    def shareable_pages(self, prompt_len: int) -> int:
        """Max pages of an L-token prompt that may ever be shared."""
        return max(0, (int(prompt_len) - 1) // self.page_size)

    # -- lookup / registration (engine decode thread only) -----------------
    def _key(self, prompt, n_pages: int) -> bytes:
        return prompt[:n_pages * self.page_size].tobytes()

    def ids(self, row, n: int, start: int = 0) -> list:
        """The ids of table columns ``[start, n)`` of a fetched row (of
        both pools' rows, where there are two)."""
        if self.split is None:
            return [int(p) for p in row[start:n]]
        return [int(p) for p in row[0][start:n]] \
            + [self.split + int(p) for p in row[1][start:n]]

    def lookup(self, prompt):
        """Longest cached page-aligned prefix of ``prompt`` (np.int32
        1-D).  Returns (n_shared_pages, page_ids tuple) — (0, ()) on a
        miss (with a second pool the tuple holds the n ids of the first
        pool, then the n of the second).  LRU-touches the hit entry; the caller pins the returned
        pages before any device work.  Idempotent and side-effect-free
        on a miss: the engine probes the backlog head every loop
        iteration while waiting for pages, so hit/miss METRICS are
        counted at actual admission (metrics.count_prefix), not here."""
        for j in range(self.shareable_pages(len(prompt)), 0, -1):
            pages = self._entries.get(self._key(prompt, j))
            if pages is not None:
                self._entries.move_to_end(self._key(prompt, j))
                return j, pages
        return 0, ()

    def lookup_state(self, prompt, j_max: int):
        """The deepest page count ``j <= j_max`` at which the cache holds a
        snapshot of ``prompt``'s state, and its place in the pool: (j,
        place), or (0, -1).  Touches the snapshot (the pool reuses the
        place of the one restored longest ago)."""
        for j in range(int(j_max), 0, -1):
            key = self._key(prompt, j)
            place = self._snaps.get(key)
            if place is not None:
                self._snaps.move_to_end(key)
                return j, place
        return 0, -1

    def take_snapshot(self, prompt, j: int) -> int:
        """A place in the pool for the state after ``prompt``'s first ``j``
        pages (whose entry the cache holds): a free one, else the place of
        the snapshot restored longest ago, which is dropped."""
        key = self._key(prompt, j)
        place = self._snaps.pop(key, None)
        if place is None:
            if self._snap_free:
                place = self._snap_free.pop()
            else:
                _, place = self._snaps.popitem(last=False)
                self.snapshots_evicted += 1
        self._snaps[key] = place
        self.snapshots_taken += 1
        return place

    def drop_snapshot_of(self, prompt, j: int):
        """The snapshot of ``prompt``'s first ``j`` pages goes: the pass
        that was to write it failed."""
        self.drop_snapshot(self._key(prompt, j))

    def drop_snapshot(self, key):
        """The snapshot of entry ``key`` goes with the entry's pages."""
        place = self._snaps.pop(key, None)
        if place is not None:
            self._snap_free.append(place)
            self.snapshots_evicted += 1

    def register(self, prompt, row, j_hit: int, j_reg: int):
        """Register entries for every unshared full-page prefix of an
        admitted prompt: prefix page counts ``j_hit+1 .. j_reg`` map to
        ``row[:j]`` (the slot's just-fetched page-table row).  Returns
        pages freed by LRU eviction whose refcount reached zero — the
        caller reclaims them on device."""
        reclaim = []
        for j in range(j_hit + 1, j_reg + 1):
            key = self._key(prompt, j)
            if key in self._entries:
                continue
            pages = tuple(self.ids(row, j))
            self._entries[key] = pages
            self.pin(pages)
            while len(self._entries) > self.capacity:
                gone, old = self._entries.popitem(last=False)
                self.drop_snapshot(gone)
                reclaim.extend(self._unref(old))
        return reclaim

    def evict_idle(self, n_pages: int):
        """Pool-pressure eviction: pop LRU entries until at least
        ``n_pages`` pages have dropped to refcount zero, or the cache
        is empty.  Returns the freed page ids for device reclaim.

        Entries whose pages are still pinned by active slots free
        nothing when popped (the slot's unpin returns them later) —
        under pressure, future sharing is sacrificed before a queued
        request is starved.  The engine calls this from admission when
        ``can_admit`` fails on pages while cache residents hold the
        pool; without it a stream of DISTINCT prompts fills the pool
        with one-reader prefixes and the backlog head waits forever
        (entry-count capacity never trips on a small pool)."""
        reclaim = []
        while self._entries and len(reclaim) < n_pages:
            gone, old = self._entries.popitem(last=False)
            self.drop_snapshot(gone)
            reclaim.extend(self._unref(old))
        return reclaim

    # -- per-slot pinning --------------------------------------------------
    def pin(self, pages):
        """A slot started reading ``pages`` (its shared prefix + any
        pages it just registered): hold them resident until unpin."""
        rc, split = self._rc, self.split
        for p in pages:
            p = int(p)
            n = rc.get(p, 0)
            rc[p] = n + 1
            if n == 0 and split is not None and p >= split:
                self.resident_high += 1

    def unpin(self, pages):
        """The slot retired: drop its holds.  Returns pages whose
        refcount hit zero (their entries were evicted mid-flight) for
        device reclaim."""
        return self._unref(int(p) for p in pages)

    def _unref(self, pages):
        freed = []
        for p in pages:
            p = int(p)
            n = self._rc.get(p, 0) - 1
            if n <= 0:
                if self._rc.pop(p, None) is not None \
                        and self.split is not None and p >= self.split:
                    self.resident_high -= 1
                freed.append(p)
            else:
                self._rc[p] = n
        return freed
