"""Page-pool allocator for paged-KV continuous batching.

Host-side bookkeeping for the device-side paged cache
(``models/llama.py`` paged surface): a fixed pool of KV pages shared by
all slots, per-slot block tables mapping position//page_size → page id.
Memory then scales with tokens actually held instead of the dense
engine's slots × max_len reservation, so `--kv-pages` can deliberately
oversubscribe (admission waits for pages; a live row that cannot
extend fails loudly rather than corrupting a neighbour).

Cross-request KV reuse is a **radix tree over token prefixes**
(``RadixPrefixIndex``): tree nodes own runs of full pages keyed by the
token chain they hold, admission longest-prefix-matches the prompt
against the tree and adopts the matched pages by refcount, a
divergence *inside* a page forks copy-on-write (the partially-shared
page is duplicated once on device, at fork time, and the new branch
writes only its divergent tokens), and unreferenced tree pages stay
resident until allocation pressure LRU-evicts them from the tails of
the coldest branches. The engine skips prefill compute for every
matched token — a thousand requests sharing a system prompt pay its
KV once (vLLM/PagedAttention + SGLang-style radix reuse, PAPERS.md).

Page 0 is scratch — never allocated; idle rows and masked holes write
there (see ``paged_coords``). The allocator is plain numpy/ints on the
host: allocation happens between decode steps at Python speed, never
inside the compiled program.
"""

from __future__ import annotations

import threading
from dataclasses import dataclass
from typing import Optional

import numpy as np


def _common(node: "_RadixNode", tokens, start: int, limit: int) -> int:
    """Length of the common prefix of ``node.key`` and
    ``tokens[start:]``, capped at ``limit - start`` total tokens.

    A chain is thousands of tokens where documents are shared, and an
    admission's pick asks this of every request it scans, so whole runs
    are compared at once: a prompt handed over as an ``int32`` array
    (the engine's, made once at submit) against the node's key as one
    (kept on the node), any other sequence slice against slice, a page
    at first and twice as many tokens after every run that agrees, then
    token by token inside the run that differs. The same answers as the
    walk token by token."""
    key = node.key
    n = min(len(key), max(limit - start, 0))
    if n == 0 or key[0] != tokens[start]:
        return 0
    if isinstance(tokens, np.ndarray):
        held = node.array
        if held is None or len(held) < n:
            held = node.array = np.asarray(key, np.int32)
        differ = np.flatnonzero(held[:n] != tokens[start:start + n])
        return int(differ[0]) if differ.size else n
    j, run = 0, 16
    while run >= 16 and j < n:
        if key[j:j + run] == tuple(tokens[start + j:start + j + run]):
            j += run
            run *= 2
        else:
            run //= 2
    j = min(j, n)
    while j < n and key[j] == tokens[start + j]:
        j += 1
    return j


class _RadixNode:
    """One edge of the prefix tree: a run of FULL pages and the token
    chain they hold (``len(key) == len(pages) * page_size`` always).
    Children are a list, not a first-token dict: a copy-on-write fork
    splits *inside* a page, so siblings may share up to page_size-1
    leading tokens — match picks the child with the longest agreement
    (a fully-matched first page always beats any partial sibling)."""

    __slots__ = ("key", "pages", "children", "parent", "last_used", "array")

    def __init__(self, key: tuple, pages: list, parent: "_RadixNode"):
        self.key = key
        # `key` as an int32 array, made when a prompt that is one is
        # first compared with it (`_common`). A key only ever shrinks
        # to a prefix of itself (a split, an eviction), so the array's
        # own prefix stays true.
        self.array: Optional[np.ndarray] = None
        self.pages = pages
        self.children: list[_RadixNode] = []
        self.parent = parent
        self.last_used = 0


@dataclass
class AdmitResult:
    """What an admission reused. Truthy (admit() returns None on
    failure), so ``if pool.admit(...)`` keeps working for callers that
    only care about success."""

    matched_tokens: int = 0   # prefill positions already resident
    matched_pages: int = 0    # full pages adopted from the tree
    live_hits: int = 0        # ...of which were live in another slot
    cow: Optional[tuple] = None  # (src_page, dst_page) device copy, or None


class RadixPrefixIndex:
    """Token-prefix radix tree whose nodes own page runs. Pure host
    bookkeeping — refcounts live in the PagePool; the tree only says
    which pages hold which token chains and how recently each branch
    mattered (the LRU clock is a monotonic touch counter)."""

    def __init__(self, page_size: int):
        self.page_size = page_size
        self.root = _RadixNode((), [], None)
        self._page_owner: dict[int, _RadixNode] = {}
        self._clock = 0

    def __len__(self) -> int:
        return len(self._page_owner)

    def owns(self, page: int) -> bool:
        return page in self._page_owner

    def _touch(self) -> int:
        self._clock += 1
        return self._clock

    def _nodes(self):
        stack = [self.root]
        while stack:
            node = stack.pop()
            yield node
            stack.extend(node.children)

    def n_nodes(self) -> int:
        return sum(1 for n in self._nodes() if n is not self.root)

    def match(self, tokens, limit: int, touch: bool = True):
        """Longest-prefix match of ``tokens[:limit]`` against the tree:
        (full_pages, cow) where ``full_pages`` are entirely-matched tree
        pages in chain order and ``cow`` is ``(src_page, m)`` when the
        divergence lands ``m`` tokens INTO the next page (fork point for
        copy-on-write) — None when it falls on a page boundary."""
        ps = self.page_size
        node = self.root
        i = 0
        pages: list[int] = []
        cow = None
        while True:
            best, bj = None, 0
            # Most siblings differ at their first token: one read of
            # the prompt's, compared as plain ints.
            head = int(tokens[i]) if i < limit else None
            for child in node.children:
                if child.key[0] != head:
                    continue
                j = _common(child, tokens, i, limit)
                if j > bj:
                    best, bj = child, j
            if best is None or bj == 0:
                break
            full = bj // ps
            pages.extend(best.pages[:full])
            if touch:
                best.last_used = self._touch()
            rem = bj - full * ps
            if rem == 0 and bj == len(best.key) and i + bj < limit:
                node = best
                i += bj
                continue
            if rem > 0:
                cow = (best.pages[full], rem)
            break
        return pages, cow

    def insert(self, tokens, pages: list) -> Optional[_RadixNode]:
        """Register a completed chain (``len(tokens) == len(pages) *
        page_size``). Existing nodes win on overlap (first-wins — the
        caller adopted matched pages at admission, so the overlap IS
        those pages); a mid-node join splits the node at the page
        boundary. Returns the ONE new leaf holding the chain's novel
        pages (None when the chain is already fully present) — the
        caller keeps it as the slot's fresh-leaf marker so a failed
        prefill can detach exactly the pages it never wrote."""
        ps = self.page_size
        node = self.root
        i, ti = 0, 0
        limit = len(tokens)
        while i < limit:
            best, bj = None, 0
            head = int(tokens[i])
            for child in node.children:
                if child.key[0] != head:
                    continue
                j = _common(child, tokens, i, limit)
                if j > bj:
                    best, bj = child, j
            if best is None or bj == 0:
                break
            full = bj // ps
            if bj == len(best.key) and bj % ps == 0:
                node = best
                i += bj
                ti += len(best.pages)
                continue
            if full == 0:
                break  # diverges inside the child's first page: sibling
            node = self._split(best, full)
            i += full * ps
            ti += full
            break
        if ti >= len(pages):
            return None
        leaf = _RadixNode(tuple(tokens[i:]), list(pages[ti:]), node)
        leaf.last_used = self._touch()
        node.children.append(leaf)
        for page in leaf.pages:
            self._page_owner[page] = leaf
        return leaf

    def _split(self, node: _RadixNode, at_pages: int) -> _RadixNode:
        """Split ``node`` after its first ``at_pages`` pages; returns
        the (upper) prefix node. Page-aligned by construction."""
        ps = self.page_size
        suffix = _RadixNode(node.key[at_pages * ps:],
                            node.pages[at_pages:], node)
        suffix.children = node.children
        for child in suffix.children:
            child.parent = suffix
        suffix.last_used = node.last_used
        for page in suffix.pages:
            self._page_owner[page] = suffix
        node.key = node.key[:at_pages * ps]
        node.pages = node.pages[:at_pages]
        node.children = [suffix]
        return node

    def detach(self, leaf: _RadixNode) -> list[int]:
        """Unregister a fresh leaf (failed admission: its pages were
        never written by a completed prefill). Returns the pages the
        tree no longer owns."""
        if leaf.parent is not None and leaf in leaf.parent.children:
            leaf.parent.children.remove(leaf)
        for page in leaf.pages:
            self._page_owner.pop(page, None)
        pages, leaf.pages, leaf.key = leaf.pages, [], ()
        return pages

    def evict_one(self, ref: np.ndarray) -> Optional[int]:
        """Pop ONE unreferenced page from the tail of the
        least-recently-used evictable leaf (evicting a middle page
        would break the chain; a page a live slot still references is
        never a candidate). None = nothing evictable right now."""
        best = None
        for node in self._nodes():
            if node is self.root or node.children or not node.pages:
                continue
            if ref[node.pages[-1]] != 0:
                continue
            if best is None or node.last_used < best.last_used:
                best = node
        if best is None:
            return None
        page = best.pages.pop()
        best.key = best.key[:len(best.pages) * self.page_size]
        del self._page_owner[page]
        if not best.pages and best.parent is not None:
            best.parent.children.remove(best)
        return page

    def reclaimable(self, ref: np.ndarray) -> int:
        """How many tree pages repeated ``evict_one`` calls could free
        right now: pages in maximal all-unreferenced suffixes of the
        tree (a ref==0 page buried under a live descendant is resident
        but NOT reclaimable — admission planning must not count it)."""

        # As a list: the walk reads a count a resident page, tens of
        # thousands where documents are shared, and a numpy scalar read
        # costs several times a list's.
        ref = ref.tolist()

        def visit(node: _RadixNode):
            count, kids_clean = 0, True
            for child in node.children:
                sub, clean = visit(child)
                count += sub
                kids_clean = kids_clean and clean
            if not kids_clean:
                return count, False
            i = len(node.pages)
            while i > 0 and ref[node.pages[i - 1]] == 0:
                i -= 1
                count += 1
            return count, i == 0

        return visit(self.root)[0]


def page_bytes(cache: dict, n_pages: int, page_size: int) -> tuple:
    """(bytes a page holds of per-token leaves, of per-page state
    leaves, bytes a row holds of per-row leaves), over all layers, read
    off the device cache's structure. A paged leaf has the pages on
    axis 1: ``[L, P, KV, page_size, Hd]`` holds tokens (K, V), any other
    shape one fixed-size state a page. What a sequence carries whatever
    its length (a recurrent state too large to keep a page) lies under
    ``rows``, leaves ``[L, rows, ...]`` indexed by the engine's row. A
    leaf without a page axis (a counter) is no page's content, and the
    window layers' pages (``window``) are a space of their own
    (`window_page_bytes`)."""
    tokens = state = 0
    for name, leaf in cache.items():
        if (name in ("rows", "window") or leaf.ndim < 3
                or leaf.shape[1] != n_pages):
            continue
        per_page = leaf.size * leaf.dtype.itemsize // n_pages
        if leaf.ndim == 5 and leaf.shape[3] == page_size:
            tokens += per_page
        else:
            state += per_page
    row = sum(leaf.size * leaf.dtype.itemsize // leaf.shape[1]
              for leaf in cache.get("rows", {}).values())
    return tokens, state, row


def window_page_bytes(cache: dict) -> int:
    """Bytes one page id of the window space holds, over the window
    layers: the leaves under ``cache["window"]``, ``[L_w, P_w, KV,
    page_size, Hd]`` each; 0 for a cache without such layers."""
    return sum(leaf.size * leaf.dtype.itemsize // leaf.shape[1]
               for leaf in cache.get("window", {}).values())


class PagePool:
    def __init__(self, slots: int, max_len: int, page_size: int,
                 n_pages: int, prefix_cache: bool = True):
        if page_size < 1:
            raise ValueError(f"page_size must be >= 1, got {page_size}")
        self.page_size = page_size
        self.max_pages_per_row = -(-max_len // page_size)
        # Page 0 is scratch: usable pages are 1..n_pages-1.
        if n_pages < 2:
            raise ValueError(f"kv pool needs >= 2 pages, got {n_pages}")
        self.n_pages = n_pages
        self._free = list(range(n_pages - 1, 0, -1))
        self.tables = np.full((slots, self.max_pages_per_row), -1, np.int32)
        self.prefix_cache = prefix_cache
        # Set by the engine for a cache whose pages also hold a
        # fixed-size state (`page_bytes`): the state of a page is the
        # one after its last written position, so a match that ends
        # inside a page has none. Matches are then rounded down to
        # whole pages and no copy-on-write fork is planned.
        self.whole_page_matches = False
        self._ref = np.zeros(n_pages, np.int32)
        self._index = RadixPrefixIndex(page_size) if prefix_cache else None
        # The ONE leaf each slot's admission added to the tree — the
        # only pages a failed prefill must forget (matched pages hold
        # content from COMPLETED prefills and stay shareable).
        self._fresh_leaf: dict[int, _RadixNode] = {}
        # Guards every structure above: the engine loop allocates
        # between decode steps while HTTP threads read stats/invariants.
        self._lock = threading.Lock()
        self._reclaim_cache: Optional[int] = None
        self.prefix_hits = 0        # full pages adopted from the tree
        self.prefix_misses = 0      # shareable pages with no chain match
        self.prefix_hits_live = 0   # adopted pages live in another slot
        self.cow_forks = 0          # mid-page divergences forked
        self.cached_tokens_total = 0  # prefill tokens served from cache
        self.prefix_evictions = 0   # resident pages reclaimed under pressure

    def match_nothing(self) -> None:
        """For a cache with per-row leaves (`page_bytes`): what a row
        carries is the state after its own last position, and a matched
        prefix has none to resume from, so no prompt matches and no
        retired page is kept (called by the engine before any
        admission, from the cache's structure; there is no option)."""
        with self._lock:
            assert not self._ref.any(), "pages admitted before match_nothing"
            self.prefix_cache = False
            self._index = None

    @classmethod
    def dense_equivalent(cls, slots: int, max_len: int, page_size: int,
                         prefix_cache: bool = True) -> "PagePool":
        """Pool sized to the dense engine's reservation (+ scratch)."""
        maxp = -(-max_len // page_size)
        return cls(slots, max_len, page_size, slots * maxp + 1,
                   prefix_cache=prefix_cache)

    # ------------------------------------------------------------ sizing
    @property
    def free_pages(self) -> int:
        """Allocatable pages: truly free + tree pages reclaimable by
        LRU eviction right now (resident pages pinned under a live
        branch do NOT count — admission must not plan against them)."""
        with self._lock:
            return len(self._free) + self._reclaimable_locked()

    def _live_locked(self, pages: list) -> int:
        """How many of ``pages`` a live slot references (one read of
        the counts, not one a page: a matched document is a thousand)."""
        return int(np.count_nonzero(self._ref[pages])) if pages else 0

    def _fits_locked(self, want: int) -> bool:
        """Whether ``want`` pages can be allocated now. The free list
        answers alone where it suffices: what eviction could reclaim
        is a walk of the whole tree."""
        return (want <= len(self._free)
                or want <= len(self._free) + self._reclaimable_locked())

    def _reclaimable_locked(self) -> int:
        if self._index is None:
            return 0
        if self._reclaim_cache is None:
            self._reclaim_cache = self._index.reclaimable(self._ref)
        return self._reclaim_cache

    def pages_for(self, length: int) -> int:
        return -(-max(length, 1) // self.page_size)

    def utilization(self) -> dict:
        """Pool occupancy in the user's units (usable pages — the
        scratch page is internal): the engine-tick gauges and /v1/stats
        both read this one snapshot. `free` counts allocatable pages,
        so reclaimable resident prefix pages land there."""
        total = self.n_pages - 1
        free = self.free_pages
        used = max(total - free, 0)
        return {"total": total, "used": used, "free": free,
                "fraction": round(used / total, 4) if total else 0.0}

    def radix_stats(self) -> dict:
        """Tree shape for the serving gauges: node count plus pages by
        state (referenced = a live slot holds them too, resident =
        retired-but-shareable)."""
        with self._lock:
            if self._index is None:
                return {"nodes": 0, "pages": 0, "referenced": 0,
                        "resident": 0}
            owned = len(self._index._page_owner)
            pages = np.fromiter(self._index._page_owner, np.intp, owned)
            referenced = int(np.count_nonzero(self._ref[pages]))
            return {"nodes": self._index.n_nodes(), "pages": owned,
                    "referenced": referenced,
                    "resident": owned - referenced}

    # ---------------------------------------------------------- planning
    def _match_locked(self, length: int, tokens, touch: bool):
        """(full_pages, cow) the tree offers for this prompt. Only the
        PREFILL positions 0..length-2 are matchable: the decode write
        at length-1 needs a private page regardless."""
        if self._index is None or tokens is None:
            return [], None
        pages, cow = self._index.match(tokens, length - 1, touch=touch)
        return pages, None if self.whole_page_matches else cow

    def _plan_locked(self, length: int, tokens) -> int:
        """Allocatable units this admission consumes: adopted pages
        LIVE in another slot cost nothing; adopted resident pages cost
        at most their own reclaim slot (charged 1 — conservative) and
        every miss/CoW/private page costs one fresh allocation."""
        matched, _ = self._match_locked(length, tokens, touch=False)
        live = self._live_locked(matched)
        return self.pages_for(length) - live

    def can_admit(self, length: int, tokens=None) -> bool:
        with self._lock:
            return self._fits_locked(self._plan_locked(length, tokens))

    def admissible_match(self, length: int, tokens=None) -> Optional[int]:
        """`can_admit` and `peek_matched_tokens` from one walk of the
        tree, for the engine's pick, which asks both of every request
        it scans: the prefill tokens the tree would serve, or None
        where the request does not fit now. Read-only."""
        with self._lock:
            matched, cow = self._match_locked(length, tokens, touch=False)
            live = self._live_locked(matched)
            if not self._fits_locked(self.pages_for(length) - live):
                return None
            return len(matched) * self.page_size + (cow[1] if cow else 0)

    def peek_matched_tokens(self, length: int, tokens=None) -> int:
        """How many prefill tokens the radix tree would serve for this
        prompt — the cache-aware admission score. Read-only: no LRU
        touch, no allocation."""
        with self._lock:
            matched, cow = self._match_locked(length, tokens, touch=False)
            return len(matched) * self.page_size + (cow[1] if cow else 0)

    def slot_pages(self, slot: int) -> int:
        """Pages mapped into this slot's row — its live KV footprint.
        The preemption policy ranks eviction victims by it ("most
        over-budget first"), and the eviction test uses it to assert
        the exact page delta a release returns."""
        with self._lock:
            return int(np.count_nonzero(self.tables[slot] >= 0))

    # -------------------------------------------------------- allocation
    def _alloc_one_locked(self):
        """One page: free list first, then evict the LRU reclaimable
        prefix page. None = pool genuinely dry."""
        if self._free:
            return self._free.pop()
        if self._index is not None:
            page = self._index.evict_one(self._ref)
            if page is not None:
                self.prefix_evictions += 1
                self._reclaim_cache = None
                return page
        return None

    def admit(self, slot: int, length: int,
              tokens: Optional[list] = None) -> Optional[AdmitResult]:
        """Allocate pages covering positions 0..length-1 for ``slot``.
        With ``tokens`` (the full prompt) and the prefix cache on, the
        prompt longest-prefix-matches the radix tree: fully-matched
        pages are adopted by refcount (their KV is already written — the
        engine skips their prefill compute), a mid-page divergence
        reports a copy-on-write pair for the engine to duplicate on
        device, and the remaining novel full-page chain is registered
        as ONE fresh tree leaf (invalidated if the prefill never runs).
        None = nothing allocated (the request should wait).

        Page i is shareable iff fully inside the prefill range: the
        decode write at length-1 (and everything after) must land on
        private pages."""
        with self._lock:
            need = self.pages_for(length)
            row = self.tables[slot]
            assert (row < 0).all(), \
                f"slot {slot} admitted while still holding pages"
            matched, cow_src = self._match_locked(length, tokens, touch=True)
            live = self._live_locked(matched)
            if not self._fits_locked(need - live):
                return None
            self._reclaim_cache = None
            for i, page in enumerate(matched):
                row[i] = page
                self._ref[page] += 1
            fresh_start = len(matched)
            for i in range(fresh_start, need):
                page = self._alloc_one_locked()
                if page is None:
                    # _plan said this fits, so this branch is belt-and-
                    # braces against accounting drift: roll back cleanly
                    # rather than corrupt the row.
                    self._release_locked(slot, invalidate_prefix=True)
                    return None
                row[i] = page
                self._ref[page] += 1
            result = AdmitResult(matched_pages=len(matched), live_hits=live)
            m_extra = 0
            if cow_src is not None:
                # Fork point inside page `fresh_start`: the engine
                # copies src → dst once, then the suffix prefill writes
                # only the divergent tokens into the private copy.
                src, m_extra = cow_src
                result.cow = (src, int(row[fresh_start]))
                self.cow_forks += 1
            result.matched_tokens = (len(matched) * self.page_size
                                     + m_extra)
            self.prefix_hits += len(matched)
            self.prefix_hits_live += live
            self.cached_tokens_total += result.matched_tokens
            shareable = 0
            if self._index is not None and tokens is not None:
                shareable = min((length - 1) // self.page_size, need)
            self.prefix_misses += max(shareable - len(matched), 0)
            if shareable > len(matched):
                leaf = self._index.insert(
                    tuple(tokens[:shareable * self.page_size]),
                    [int(p) for p in row[:shareable]])
                if leaf is not None:
                    self._fresh_leaf[slot] = leaf
            return result

    def ensure(self, slot: int, pos: int) -> bool:
        """Make position ``pos`` writable for ``slot`` (allocating its
        page if new). False = pool exhausted; the row keeps its pages."""
        with self._lock:
            idx = pos // self.page_size
            if idx >= self.max_pages_per_row:
                return False
            if self.tables[slot, idx] >= 0:
                return True
            page = self._alloc_one_locked()
            if page is None:
                return False
            self.tables[slot, idx] = page
            self._ref[page] += 1
            return True

    # ----------------------------------------------------------- handoff
    def handoff(self, src_slot: int, dst_slot: int) -> int:
        """Transfer ownership of ``src_slot``'s pages to ``dst_slot``
        (prefill lane → decode lane). Pure bookkeeping: the block-table
        row moves, the fresh-leaf marker follows, and refcounts are
        untouched — the pages appear in exactly one row before and
        after, so ``check_invariants`` holds across the boundary and
        nothing is recomputed or copied on device. Returns the number
        of pages transferred."""
        with self._lock:
            dst = self.tables[dst_slot]
            assert (dst < 0).all(), \
                f"handoff into slot {dst_slot} which still holds pages"
            src = self.tables[src_slot]
            dst[:] = src
            src[:] = -1
            leaf = self._fresh_leaf.pop(src_slot, None)
            if leaf is not None:
                self._fresh_leaf[dst_slot] = leaf
            return int((dst >= 0).sum())

    # ----------------------------------------------------------- release
    def commit_prefix(self, slot: int) -> None:
        """The slot's prefill completed: its fresh tree leaf now holds
        real KV content and survives the slot (drop the invalidation
        marker)."""
        with self._lock:
            self._fresh_leaf.pop(slot, None)

    def release(self, slot: int, invalidate_prefix: bool = False) -> None:
        """Drop the slot's references. A page at refcount 0 returns to
        the free list — unless the radix tree owns it, in which case it
        stays resident (LRU-evicted only under allocation pressure) so
        the next matching prompt reuses its KV.

        ``invalidate_prefix``: the slot's admission failed before its
        prefill wrote the pages — detach the ONE fresh leaf this slot
        registered (pages it merely adopted carry content from
        completed prefills and stay shareable)."""
        with self._lock:
            self._release_locked(slot, invalidate_prefix)

    def _release_locked(self, slot: int, invalidate_prefix: bool) -> None:
        leaf = self._fresh_leaf.pop(slot, None)
        if invalidate_prefix and leaf is not None and self._index is not None:
            self._index.detach(leaf)
        row = self.tables[slot]
        for idx in np.flatnonzero(row >= 0):
            page = int(row[idx])
            self._ref[page] -= 1
            if self._ref[page] <= 0:
                self._ref[page] = 0
                if self._index is None or not self._index.owns(page):
                    self._free.append(page)
        row[:] = -1
        self._reclaim_cache = None

    def invalidate_prefix_cache(self) -> None:
        """Forget the whole tree (device cache rebuilt → its content is
        gone). Unreferenced resident pages return to the free list;
        pages still referenced by live rows keep their allocation but
        lose their shareability (they free normally at release)."""
        with self._lock:
            if self._index is None:
                return
            for page in list(self._index._page_owner):
                if self._ref[page] == 0:
                    self._free.append(page)
            self._index = RadixPrefixIndex(self.page_size)
            self._fresh_leaf.clear()
            self._reclaim_cache = None

    # -------------------------------------------------------- invariants
    def check_invariants(self) -> list[str]:
        """Refcount/CoW bookkeeping cross-check (chaos tests and the CI
        radix smoke assert this stays empty): every usable page is free
        XOR referenced XOR resident-in-tree, refcounts equal block-table
        occurrences, the tree's shape is consistent, and scratch page 0
        is never allocated anywhere."""
        out = []
        with self._lock:
            counts = np.bincount(
                self.tables[self.tables >= 0].ravel(),
                minlength=self.n_pages)
            if counts[0]:
                out.append("scratch page 0 appears in a block table")
            if 0 in self._free:
                out.append("scratch page 0 on the free list")
            if len(set(self._free)) != len(self._free):
                out.append("duplicate pages on the free list")
            free = set(self._free)
            owned = set(self._index._page_owner) if self._index else set()
            if self._index is not None and 0 in owned:
                out.append("scratch page 0 owned by the radix tree")
            for page in range(1, self.n_pages):
                ref = int(self._ref[page])
                if ref != int(counts[page]):
                    out.append(f"page {page}: ref {ref} != "
                               f"{int(counts[page])} table occurrences")
                in_free = page in free
                if in_free and ref > 0:
                    out.append(f"page {page}: on free list with ref {ref}")
                if in_free and page in owned:
                    out.append(f"page {page}: on free list AND tree-owned")
                if ref == 0 and not in_free and page not in owned:
                    out.append(f"page {page}: leaked (ref 0, not free, "
                               "not tree-resident)")
            if self._index is not None:
                seen: set[int] = set()
                for node in self._index._nodes():
                    if node is self._index.root:
                        continue
                    if len(node.key) != len(node.pages) * self.page_size:
                        out.append("radix node key/page length mismatch")
                    if not node.pages:
                        out.append("empty radix node left attached")
                    for child in node.children:
                        if child.parent is not node:
                            out.append("radix child/parent link broken")
                    for page in node.pages:
                        if page in seen:
                            out.append(f"page {page}: owned by two nodes")
                        seen.add(page)
                        if self._index._page_owner.get(page) is not node:
                            out.append(f"page {page}: owner map disagrees "
                                       "with node membership")
                if seen != owned:
                    out.append("owner map and tree pages diverge")
                for slot, leaf in self._fresh_leaf.items():
                    node = leaf
                    while node.parent is not None:
                        node = node.parent
                    if node is not self._index.root:
                        out.append(f"slot {slot}: fresh leaf detached "
                                   "from the tree")
        return out

    def padded_row(self, slot: int) -> np.ndarray:
        """The slot's block-table row (fixed [max_pages_per_row]), as a
        copy: the caller hands it to a program that may run after the
        row has moved on (a handoff, the next page), and `jnp.asarray`
        of a view can alias the table itself on the CPU backend."""
        return self.tables[slot].copy()


def window_suffix_start(matched: int, window: int, window_layers: int,
                        page_size: int) -> int:
    """Where the suffix program behind ``matched`` tokens starts in a
    model with ``window_layers`` layers that attend the last ``window``
    positions and start empty there: the page boundary at or below
    ``matched - window x window_layers``, 0 for a shorter match (which
    saves the full layers' writes and no computation: a pool none of
    whose rows can hold a longer one matches nothing). Never above
    ``matched - window - (window - 1) x (window_layers - 1)``, the
    highest start from which every window layer's K and V over
    ``[matched - window, matched)`` come out exact (``models/
    smallthinker.py``)."""
    below = matched - window * window_layers
    return max(0, below // page_size * page_size)


class WindowedPagePool(PagePool):
    """A pool for a model whose layers are of two kinds: *full* layers
    keep every position of a row, *window* layers attend the last
    ``window`` positions and nothing older. Each kind has a page space
    of its own (its own device leaves, free list and block table), so a
    row holds two chains of pages with different lives.

    The full space is the base class's, as it is. The window space is a
    table as wide as the full one, indexed by the same logical page
    ``position // page_size``, **whose head is released**: a row holds
    at most ``window // page_size + 1`` window pages, the last ones of
    its chain (at a position not on a page boundary the window reaches
    one token into that many pages), every entry before them is -1
    again, and its page is on the free list from the moment `roll`
    moved past it. A logical page is then found by its index in either
    table, which is what lets the decode kernel and the masks take a
    window as a lower bound and nothing else. The space is sized for
    every row's longest chain (``slots`` x that many pages, plus
    scratch page 0), so only the full space ever makes a request wait.

    **A shared prefix.** The radix tree indexes the full space's pages
    and shares them between rows as `PagePool` does (refcounts, LRU
    eviction), by whole pages: the suffix program takes the match's
    length from the pages it is handed, and the window space has no page
    to fork. The window space is shared with nobody. A row that adopts
    ``m`` matched tokens still needs, in every window layer, K and V of
    the ``window`` positions below ``m``, which no retired row keeps: the
    engine's suffix program starts at `suffix_start` (``m``), far enough
    below the match that every window layer's come out exact (``models/
    smallthinker.py`` says why), and writes them into the row's own
    window pages. What the pool adds for it is that one number;
    nothing is pinned, so eviction and release are the full space's.
    Where that stretch, ``window x window_layers``, is as long as a row
    may grow, no match can skip a position, and the pool matches nothing
    (`set_window_layers`): a tree that saves nothing still costs its
    evictions, a walk of its nodes a page once the free list is dry.

    Which kind a pool is is decided once, where the engine builds it; a
    model without window layers gets a plain `PagePool` and runs none of
    this."""

    def __init__(self, slots: int, max_len: int, page_size: int,
                 n_pages: int, window: int, window_layers: int = 1,
                 prefix_cache: bool = True):
        super().__init__(slots, max_len, page_size, n_pages,
                         prefix_cache=prefix_cache)
        if window < page_size or window % page_size:
            raise ValueError(f"window {window} is not whole pages of "
                             f"{page_size}")
        self.whole_page_matches = True
        self.window = window
        self.max_len = max_len
        self.set_window_layers(window_layers)
        self.window_pages_per_row = min(window // page_size + 1,
                                        self.max_pages_per_row)
        self.window_n_pages = slots * self.window_pages_per_row + 1
        self._window_free = list(range(self.window_n_pages - 1, 0, -1))
        self.window_tables = np.full(
            (slots, self.max_pages_per_row), -1, np.int32)
        self.window_pages_released = 0   # handed back by `roll`
        self.window_row_pages_max = 0    # most any row has held

    # ---------------------------------------------------------- planning
    def _window_span(self, length: int) -> tuple:
        """[first, stop) of the logical pages a row of ``length``
        positions holds in the window space."""
        stop = self.pages_for(length)
        return max(0, stop - self.window_pages_per_row), stop

    def _window_room(self, length: int) -> bool:
        first, stop = self._window_span(length)
        with self._lock:
            return stop - first <= len(self._window_free)

    def can_admit(self, length: int, tokens=None) -> bool:
        return self._window_room(length) and super().can_admit(length, tokens)

    def admissible_match(self, length: int, tokens=None) -> Optional[int]:
        if not self._window_room(length):
            return None
        return super().admissible_match(length, tokens)

    def set_window_layers(self, n: int) -> None:
        """How many window layers a suffix program walks (`suffix_start`):
        the engine reads it off the cache it builds, before any
        admission. Where ``window x n`` reaches the rows' length every
        suffix program would start at 0: the pool then matches nothing
        (`match_nothing`), as for a cache a match cannot resume from."""
        self.window_layers = n
        if self.prefix_cache and self.window * n >= self.max_len:
            self.match_nothing()

    def suffix_start(self, matched: int) -> int:
        """`window_suffix_start` for this pool's window and layers."""
        return window_suffix_start(matched, self.window, self.window_layers,
                                   self.page_size)

    # -------------------------------------------------------- allocation
    def admit(self, slot: int, length: int,
              tokens: Optional[list] = None) -> Optional[AdmitResult]:
        """Every page of positions 0..length-1 in the full space
        (matched ones adopted from the tree, as `PagePool.admit`), the
        last `window_pages_per_row` of them at most in the window
        space, the row's own; None = nothing allocated in either."""
        assert (self.window_tables[slot] < 0).all(), \
            f"slot {slot} admitted while still holding window pages"
        if not self._window_room(length):
            return None
        result = super().admit(slot, length, tokens)
        if result is None:
            return None
        first, stop = self._window_span(length)
        with self._lock:
            taken = self._window_free[first - stop:]
            del self._window_free[first - stop:]
            self.window_tables[slot, first:stop] = taken
            self.window_row_pages_max = max(self.window_row_pages_max,
                                            stop - first)
        return result

    def roll(self, slot: int, pos: int) -> None:
        """Make ``pos`` writable in the window space (`ensure` is the
        full space's): at a page boundary the row takes a page for it
        and hands back the one that no position within ``window`` of
        ``pos`` lies in, which is on the free list at once."""
        idx = pos // self.page_size
        row = self.window_tables[slot]
        if idx >= self.max_pages_per_row or row[idx] >= 0:
            return
        with self._lock:
            old = idx - self.window_pages_per_row
            if old >= 0 and row[old] >= 0:
                self._window_free.append(int(row[old]))
                row[old] = -1
                self.window_pages_released += 1
            row[idx] = self._window_free.pop()
            held = min(idx + 1, self.window_pages_per_row)
            if held > self.window_row_pages_max:
                self.window_row_pages_max = held

    def handoff(self, src_slot: int, dst_slot: int) -> int:
        moved = super().handoff(src_slot, dst_slot)
        with self._lock:
            self.window_tables[dst_slot] = self.window_tables[src_slot]
            self.window_tables[src_slot] = -1
        return moved

    def _release_locked(self, slot: int, invalidate_prefix: bool) -> None:
        super()._release_locked(slot, invalidate_prefix)
        row = self.window_tables[slot]
        self._window_free.extend(int(p) for p in row[row >= 0])
        row[:] = -1

    # ------------------------------------------------------------- views
    def padded_row(self, slot: int) -> np.ndarray:
        """Both of the slot's block-table rows, ``[2, max_pages_per_row]``
        (the full space's, the window space's), as a copy."""
        return np.stack([self.tables[slot], self.window_tables[slot]])

    def window_stats(self) -> dict:
        total = self.window_n_pages - 1
        with self._lock:
            free = len(self._window_free)
            return {"total": total, "free": free, "live": total - free,
                    "released": self.window_pages_released,
                    "row_max": self.window_row_pages_max}

    def check_invariants(self) -> list[str]:
        """The base class's checks of the full space, and of the window
        space: every usable page free or in exactly one row, never
        both; no row over its limit, and none holding a page that lies
        behind its window."""
        out = super().check_invariants()
        with self._lock:
            held = self.window_tables[self.window_tables >= 0]
            counts = np.bincount(held.ravel(), minlength=self.window_n_pages)
            free = np.zeros(self.window_n_pages, bool)
            free[self._window_free] = True
            if counts[0] or free[0]:
                out.append("window space: scratch page 0 allocated")
            if int(free.sum()) != len(self._window_free):
                out.append("window space: duplicate pages on the free list")
            usable = np.arange(self.window_n_pages) > 0
            for what, bad in (
                    ("in several table entries", counts > 1),
                    ("held and on the free list", (counts > 0) & free),
                    ("leaked", (counts == 0) & ~free & usable)):
                out.extend(f"window page {page}: {what}"
                           for page in np.flatnonzero(bad))
            for slot, row in enumerate(self.window_tables):
                at = np.flatnonzero(row >= 0)
                if len(at) > self.window_pages_per_row:
                    out.append(f"slot {slot}: {len(at)} window pages")
                if len(at) and at[-1] - at[0] >= self.window_pages_per_row:
                    out.append(f"slot {slot}: a window page behind the "
                               "window")
        return out
