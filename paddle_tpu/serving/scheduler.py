"""Iteration-level slot + page scheduler for continuous-batching
generation.

Host-side bookkeeping only (the Orca-style scheduling half of the
generation engine): which decode lane holds which request, which lanes
are free, which occupied lanes must be swept (client cancellation,
deadline expiry) — and, since the paged KV cache, whether the PAGE POOL
can absorb a request's worst case.  Admission reserves
``ceil((prompt + max_new) / page_size)`` pages minus any shared prefix
pages; a free slot with an exhausted pool queues the request instead of
admitting it into an in-graph free-list underflow.  The invariant the
reservation buys: the device's ``free_count`` register never drops
below ``pages_available`` here, so decode's in-graph tail-page
allocation cannot underflow.

All device state lives in serving/kv_cache.py; the scheduler never
touches a jax array, so it needs no lock beyond the engine's single
decode thread owning it.
"""
from __future__ import annotations

import time

__all__ = ["SlotScheduler"]


class SlotScheduler:
    """Fixed-capacity slot + page table: ``admit`` at iteration
    boundaries, ``retire`` on EOS/length, ``sweep`` for mid-decode
    preemption."""

    def __init__(self, max_slots: int, num_pages: int | None = None,
                 window_pages: int | None = None):
        if max_slots < 1:
            raise ValueError(f"max_slots must be >= 1, got {max_slots}")
        self.max_slots = int(max_slots)
        # LIFO free list: hot slots are reused first, which keeps the
        # occupied lanes dense at low load (cache locality on TPU)
        self._free = list(range(self.max_slots - 1, -1, -1))
        self._occupants: dict[int, object] = {}   # slot -> request
        self.num_pages = None if num_pages is None else int(num_pages)
        self._reserved: dict[int, int] = {}       # slot -> pages reserved
        self._shared_resident = 0                 # prefix-cache pages
        # a second pool (the window layers', kv_cache.py) under the same
        # discipline: page demands are then pairs (full pool, window pool)
        self.window_pages = None if window_pages is None \
            else int(window_pages)
        self._window_reserved: dict[int, int] = {}
        self._window_resident = 0

    @property
    def free_slots(self) -> int:
        return len(self._free)

    @property
    def occupied(self) -> dict:
        return self._occupants

    def has_free(self) -> bool:
        return bool(self._free)

    # -- page accounting ---------------------------------------------------
    @property
    def pages_reserved(self) -> int:
        return sum(self._reserved.values())

    @property
    def pages_available(self) -> int:
        """Pages the pool can still promise to a new admission: total
        minus active worst-case reservations minus prefix-cache
        residents (conservative — a slot's own registered pages may be
        counted in both, never under)."""
        if self.num_pages is None:
            return 1 << 30
        return self.num_pages - self.pages_reserved - self._shared_resident

    @property
    def window_available(self) -> int:
        """``pages_available`` of the window pool."""
        return self.window_pages - sum(self._window_reserved.values()) \
            - self._window_resident

    def set_shared_resident(self, n_pages: int, n_window: int = 0):
        """Pages currently held by the prefix cache (refcount > 0) —
        the engine refreshes this after register/unpin/evict."""
        self._shared_resident = int(n_pages)
        self._window_resident = int(n_window)

    def short_of(self, n_pages) -> int:
        """Pages a demand exceeds what the pool(s) can promise by (0 when
        it fits): what eviction has to free."""
        if self.window_pages is None:
            return max(0, n_pages - self.pages_available)
        return max(0, n_pages[0] - self.pages_available) \
            + max(0, n_pages[1] - self.window_available)

    def can_admit(self, n_pages) -> bool:
        """True when a free slot exists AND the pool can reserve the
        request's worst-case ``n_pages`` — an exhausted pool queues the
        request even with lanes free (admit-and-crash is the failure
        mode this check exists to prevent).  With a window pool
        ``n_pages`` is the pair of demands, and both have to fit."""
        return bool(self._free) and self.short_of(n_pages) == 0

    def admit(self, request, n_pages=0) -> int:
        """Claim a free slot for ``request`` and reserve its worst-case
        page demand; raises when full (the engine checks ``can_admit``
        first — a raise is a logic bug)."""
        slot = self._free.pop()
        self._occupants[slot] = request
        if self.window_pages is not None:
            n_pages, self._window_reserved[slot] = n_pages
        self._reserved[slot] = int(n_pages)
        return slot

    def retire(self, slot: int):
        """Release ``slot`` (and its page reservation) back to the free
        lists; returns its request."""
        req = self._occupants.pop(slot)
        self._free.append(slot)
        self._reserved.pop(slot, None)
        self._window_reserved.pop(slot, None)
        return req

    def prefilling(self) -> int:
        """Occupied lanes still mid-chunked-prefill (request carries a
        truthy ``prefilling``) — they hold a slot + full worst-case page
        reservation but are not yet armed for decode."""
        return sum(1 for req in self._occupants.values()
                   if getattr(req, "prefilling", False))

    def sweep(self, now=None):
        """Occupied lanes whose request is cancelled or past deadline:
        [(slot, request, reason)].  The engine releases them on-device
        and retires them here.

        Mid-chunk prefills are swept EXACTLY like armed decode lanes:
        a chunked prompt's already-written pages are private table
        entries above the lane's ``pinned`` register (the shared-prefix
        head), so the engine's release executable returns every one of
        them to the free stack the moment the sweep fires — a cancelled
        32k-token prefill must not strand half its pages until some
        later decode notices.  tests/test_spec_decode.py pins this with
        a pool-occupancy tripwire (cancel mid-chunk, assert free_count
        returns to baseline)."""
        now = time.monotonic() if now is None else now
        out = []
        for slot, req in self._occupants.items():
            if getattr(req, "cancelled", False):
                out.append((slot, req, "cancelled"))
            elif getattr(req, "deadline", None) is not None \
                    and now > req.deadline:
                out.append((slot, req, "deadline_expired"))
        return out
