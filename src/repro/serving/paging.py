"""Page-granular KV storage: `PagePool` + `BlockTables`.

The contiguous serving cache sizes every slot for ``max_len`` tokens up
front, so ``n_slots x max_len`` is a compile-time memory wall. Paging
splits the global-attention KV buffers into fixed-size physical pages
(``(n_pages, n_kv, page_size, head_dim)``) shared by all slots; each slot
holds a *block table* row mapping its logical page index to a physical
page. The compiled decode step receives the table as data — occupancy
changes never retrace.

Conventions (relied on by `models.attention` and the paged kernel):

- **Physical page 0 is the null page.** It is never allocated; free (or
  freshly reset) block-table rows are all-zeros, so inactive slots'
  writes land on page 0 where no active slot ever reads them. The pool
  therefore hands out pages ``1..n_pages-1`` only.
- Tables are host-side numpy; the engine ships them to the device once
  per tick (fixed shape ``(n_slots, pages_per_seq)`` int32).
- Allocation is all-or-nothing per request step: a slot either gets the
  page it needs or the caller preempts someone (policy lives in
  `launch.serving`, not here).
"""
from __future__ import annotations

from typing import List, Optional, Sequence

import numpy as np

NULL_PAGE = 0


class PagePool:
    """Free-list over ``n_pages`` physical KV pages (page 0 reserved)."""

    def __init__(self, n_pages: int):
        if n_pages < 2:
            raise ValueError(f"PagePool needs >= 2 pages (one is the "
                             f"reserved null page), got {n_pages}")
        self.n_pages = int(n_pages)
        # LIFO free list; seeded so the first allocations are 1, 2, 3, ...
        # A set mirrors membership: `free()`'s double-free check used to
        # scan the list (O(n) per page), and the pool holds thousands of
        # pages in a serving process.
        self._free: List[int] = list(range(self.n_pages - 1, 0, -1))
        self._free_set = set(self._free)

    @property
    def capacity(self) -> int:
        """Usable pages (excludes the null page)."""
        return self.n_pages - 1

    @property
    def n_free(self) -> int:
        return len(self._free)

    @property
    def n_used(self) -> int:
        return self.capacity - self.n_free

    def alloc(self) -> Optional[int]:
        """One page, or None when exhausted (never raises: the caller
        decides between queueing and preemption)."""
        if not self._free:
            return None
        p = self._free.pop()
        self._free_set.discard(p)
        return p

    def alloc_many(self, k: int) -> Optional[List[int]]:
        """k pages all-or-nothing; None leaves the pool untouched."""
        if k < 0:
            raise ValueError(f"alloc_many({k})")
        if len(self._free) < k:
            return None
        pages = self._free[-k:][::-1]
        del self._free[len(self._free) - k:]
        self._free_set.difference_update(pages)
        return pages

    def free(self, pages: Sequence[int]) -> None:
        """Return a batch of pages. Atomic: the WHOLE batch is validated
        (range, double-free against the pool, duplicates within the
        batch) before any page is returned, so a raising call leaves the
        pool exactly as it was — a mid-sequence raise used to strand the
        already-appended prefix as freed while the rest stayed leaked."""
        batch = [int(p) for p in pages]
        seen = set()
        for p in batch:
            if not (0 < p < self.n_pages):
                raise ValueError(f"freeing invalid page {p}")
            if p in self._free_set or p in seen:
                raise ValueError(f"double free of page {p}")
            seen.add(p)
        self._free.extend(batch)
        self._free_set.update(batch)


class BlockTables:
    """Per-slot logical->physical page maps, ``(n_slots, pages_per_seq)``.

    Owns the host-side table array and each slot's allocation list; the
    pool stays a dumb free-list. `grow` is idempotent per page index and
    all-or-nothing, `release` returns every page and zeroes the row back
    to the null page.
    """

    def __init__(self, n_slots: int, pages_per_seq: int):
        self.n_slots = int(n_slots)
        self.pages_per_seq = int(pages_per_seq)
        self.table = np.zeros((self.n_slots, self.pages_per_seq), np.int32)
        self._owned: List[List[int]] = [[] for _ in range(self.n_slots)]

    def n_pages(self, slot: int) -> int:
        return len(self._owned[slot])

    def owned(self, slot: int) -> List[int]:
        return list(self._owned[slot])

    def grow(self, slot: int, upto_page: int, pool: PagePool) -> bool:
        """Ensure logical pages ``0..upto_page`` are mapped for ``slot``.
        Returns False (pool unchanged) when the pool cannot cover the
        missing pages."""
        if upto_page >= self.pages_per_seq:
            raise ValueError(
                f"slot {slot} needs logical page {upto_page} but tables "
                f"cover {self.pages_per_seq} pages per sequence")
        need = upto_page + 1 - len(self._owned[slot])
        if need <= 0:
            return True
        pages = pool.alloc_many(need)
        if pages is None:
            return False
        for p in pages:
            self.table[slot, len(self._owned[slot])] = p
            self._owned[slot].append(p)
        return True

    def release(self, slot: int, pool: PagePool) -> None:
        pool.free(self._owned[slot])
        self._owned[slot] = []
        self.table[slot, :] = NULL_PAGE
