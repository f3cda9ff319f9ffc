"""Host-side paged KV-cache pool: fixed-size pages, per-request page tables.

The pool owns page *ids* only — the actual K/V page arrays live on device
(heads-major ``(L, Hkv, n_pages, page_size, Dh)``, see `ops.init_page_arrays` and the
model's ``init_paged_cache``).  Page 0 is reserved as the **null page**:
free table slots point at it, and padded batch rows (``kv_len == 0``)
write their dead token there, so a table is always fully populated with
valid indices and the kernel never needs a bounds branch.

Allocation is all-or-nothing (a request either gets every page it asked
for or ``None`` — no partial grants to unwind), frees return pages to a
LIFO free stack (hot reuse), and :meth:`defrag` compacts the in-use pages
to the low end of the pool, returning the gather permutation to apply to
the device arrays (`ops.apply_page_permutation`).
"""
from __future__ import annotations

from dataclasses import dataclass

import numpy as np

from repro.obs import trace as obs_trace

NULL_PAGE = 0


def pages_for(n_tokens: int, page_size: int) -> int:
    """Pages needed to hold ``n_tokens`` (0 tokens still owns 0 pages)."""
    return -(-int(n_tokens) // int(page_size)) if n_tokens > 0 else 0


@dataclass(frozen=True)
class PoolStats:
    n_pages: int  # total pages incl. the reserved null page
    page_size: int
    in_use: int
    free: int
    high_water: int  # max pages simultaneously in use over the pool's life
    allocs: int  # page grants
    frees: int  # pages returned
    alloc_failures: int  # all-or-nothing requests refused for capacity
    reused_pages: int  # grants of a page that had a previous owner
    defrags: int
    tokens: int  # tokens currently stored across all requests
    utilization: float  # tokens / (in_use * page_size); 1.0 when empty
    fragmentation: float  # 1 - in_use/(highest in-use id); 0 when compact


class PagedKVPool:
    """Page-table allocator for a paged KV cache.

    ``n_pages`` includes the reserved null page, so a pool built for ``k``
    usable pages needs ``n_pages = k + 1``.
    """

    def __init__(self, n_pages: int, page_size: int):
        if n_pages < 2:
            raise ValueError("need at least one usable page beside the null page")
        if page_size < 1:
            raise ValueError("page_size must be >= 1")
        self.n_pages = int(n_pages)
        self.page_size = int(page_size)
        # LIFO: low ids are handed out first, so a freshly built pool stays
        # compact until churn actually fragments it
        self._free: list[int] = list(range(self.n_pages - 1, 0, -1))
        self._tables: dict[int, list[int]] = {}
        self._lens: dict[int, int] = {}
        self._ever_used: set[int] = set()
        self.allocs = 0
        self.frees = 0
        self.alloc_failures = 0
        self.reused_pages = 0
        self.defrags = 0
        self.high_water = 0

    # ----------------------------------------------------------- queries
    @property
    def rids(self) -> set[int]:
        return set(self._tables)

    @property
    def in_use(self) -> int:
        return (self.n_pages - 1) - len(self._free)

    def kv_len(self, rid: int) -> int:
        return self._lens[rid]

    def pages_of(self, rid: int) -> list[int]:
        return list(self._tables[rid])

    def capacity_tokens(self, rid: int) -> int:
        return len(self._tables[rid]) * self.page_size

    # ----------------------------------------------------------- alloc/free
    def _grant(self, n: int) -> list[int] | None:
        if n > len(self._free):
            self.alloc_failures += 1
            return None
        pages = [self._free.pop() for _ in range(n)]
        self.allocs += n
        self.reused_pages += sum(1 for p in pages if p in self._ever_used)
        self._ever_used.update(pages)
        self.high_water = max(self.high_water, self.in_use)
        return pages

    def alloc(self, rid: int, n_tokens: int, extra_pages: int = 0) -> list[int] | None:
        """Admit ``rid`` with capacity for ``n_tokens`` (+ ``extra_pages``).

        All-or-nothing; returns the granted page list or ``None`` (counted
        in ``alloc_failures``) without side effects.  The request starts at
        ``kv_len == 0`` — use :meth:`note_tokens` / :meth:`append` as its
        cache actually fills.
        """
        if rid in self._tables:
            raise KeyError(f"rid {rid} already allocated")
        n = pages_for(n_tokens, self.page_size) + int(extra_pages)
        with obs_trace.span("pool:alloc", rid=rid, pages=n):
            pages = self._grant(n)
        if pages is None:
            return None
        self._tables[rid] = pages
        self._lens[rid] = 0
        return pages

    def extend(self, rid: int, n_tokens: int) -> list[int] | None:
        """Grow ``rid``'s reservation to cover ``n_tokens`` total."""
        need = pages_for(n_tokens, self.page_size) - len(self._tables[rid])
        if need <= 0:
            return []
        pages = self._grant(need)
        if pages is None:
            return None
        self._tables[rid].extend(pages)
        return pages

    def note_tokens(self, rid: int, n_tokens: int) -> None:
        """Record that ``rid`` now holds ``n_tokens`` (within its reservation)."""
        if n_tokens > self.capacity_tokens(rid):
            raise ValueError(
                f"rid {rid}: {n_tokens} tokens exceeds the "
                f"{self.capacity_tokens(rid)}-token reservation"
            )
        self._lens[rid] = int(n_tokens)

    def append(self, rid: int, n_tokens: int = 1) -> bool:
        """Append decoded tokens, allocating pages on demand; False on OOM."""
        want = self._lens[rid] + int(n_tokens)
        if want > self.capacity_tokens(rid) and self.extend(rid, want) is None:
            return False
        self._lens[rid] = want
        return True

    def free(self, rid: int) -> int:
        """Release every page ``rid`` owns; returns how many came back."""
        with obs_trace.span("pool:free", rid=rid, pages=len(self._tables[rid])):
            pages = self._tables.pop(rid)
            del self._lens[rid]
            self._free.extend(reversed(pages))  # LIFO: freed pages are reused first
            self.frees += len(pages)
        return len(pages)

    # ----------------------------------------------------------- tables
    def table_row(self, rid: int | None, width: int) -> np.ndarray:
        """(width,) int32 page-table row, null-padded; all-null for ``None``."""
        row = np.full(width, NULL_PAGE, np.int32)
        if rid is not None:
            pages = self._tables[rid]
            if len(pages) > width:
                raise ValueError(f"rid {rid} owns {len(pages)} pages > width {width}")
            row[: len(pages)] = pages
        return row

    def table(self, slot_rids: list[int | None], width: int) -> np.ndarray:
        """(B, width) page table for a batch of slots (``None`` = free slot)."""
        live = [r for r in slot_rids if r is not None]
        with obs_trace.span(
            "pool:table", rows=len(live), used=sum(self._lens[r] for r in live),
            reserved=self.page_size * sum(len(self._tables[r]) for r in live),
        ):
            return np.stack([self.table_row(r, width) for r in slot_rids])

    def kv_lens(self, slot_rids: list[int | None]) -> np.ndarray:
        return np.array(
            [0 if r is None else self._lens[r] for r in slot_rids], np.int32
        )

    # ----------------------------------------------------------- defrag
    def defrag(self) -> np.ndarray:
        """Compact in-use pages to ids ``1..in_use``; returns the gather perm.

        ``perm`` is a (n_pages,) array with ``perm[new_id] = old_id`` — apply
        it to the device page arrays as ``pages = pages[perm]`` (see
        `ops.apply_page_permutation`) *before* using any table built after
        the call.  The null page stays put.
        """
        perm = np.full(self.n_pages, -1, np.int64)
        perm[NULL_PAGE] = NULL_PAGE
        nxt = 1
        for rid in sorted(self._tables):
            pages = self._tables[rid]
            for i, old in enumerate(pages):
                perm[nxt] = old
                pages[i] = nxt
                nxt += 1
        leftover = [p for p in range(1, self.n_pages) if p not in set(perm[:nxt])]
        perm[nxt:] = leftover
        self._free = list(range(self.n_pages - 1, nxt - 1, -1))
        self.defrags += 1
        return perm

    # ----------------------------------------------------------- stats
    def stats(self) -> PoolStats:
        tokens = sum(self._lens.values())
        in_use = self.in_use
        highest = max((p for t in self._tables.values() for p in t), default=0)
        return PoolStats(
            n_pages=self.n_pages,
            page_size=self.page_size,
            in_use=in_use,
            free=len(self._free),
            high_water=self.high_water,
            allocs=self.allocs,
            frees=self.frees,
            alloc_failures=self.alloc_failures,
            reused_pages=self.reused_pages,
            defrags=self.defrags,
            tokens=tokens,
            utilization=tokens / (in_use * self.page_size) if in_use else 1.0,
            fragmentation=1.0 - in_use / highest if highest else 0.0,
        )
