"""Host-side page accounting for the paged KV cache.

The device side is dumb on purpose: a fixed pool of KV pages
(:func:`chainermn_tpu.models.init_paged_kv_cache`) read through
per-sequence page tables
(:func:`chainermn_tpu.ops.flash_attention_decode_paged`).  Everything
that makes paging pay -- allocation, refcounting, prefix sharing,
copy-on-write -- is plain Python here, off the hot path: the scheduler
consults these structures BETWEEN device dispatches and the result is
just int32 page tables.

Three pieces:

- :class:`PagePool` -- free-list allocator over page ids with
  refcounts.  Page 0 is reserved as the SCRATCH page (pad rows and
  idle table entries point there; it is never handed out), so a
  garbage write can never land in live data.
- :class:`RadixPrefixIndex` -- a radix trie over page-sized token
  chunks of completed prompts.  A lookup walks the longest banked
  prefix and returns its pages; N requests sharing a system prompt
  then READ one banked copy, multiplying effective capacity
  (``docs/serving.md``).  The index holds its own reference on every
  banked page; leaves are LRU-evicted when the pool runs dry.
- :func:`prefix_key` -- a stable hash of the shareable (page-aligned)
  prompt prefix, stamped on requests at admission so the scheduler
  can co-admit shared-prefix requests.  It is a pure function of the
  token ids: arrival order can never change it
  (``tests/test_serving.py``).

Write-safety invariant (why decode never needs a copy): a sequence
only ever writes at positions ``>= its admission-time shared prefix``.
The page spanning that boundary is copy-on-write-duplicated ONCE at
admission (:meth:`RadixPrefixIndex.lookup` callers; counted by the
``serve_kv_cow_total`` telemetry counter); every later page is
privately allocated.  A page the index banks from a FINISHED prefill
may keep receiving that sequence's decode tokens, but only at offsets
beyond the indexed ``tail_len`` -- the banked tokens themselves are
immutable.
"""

import binascii
import heapq

import numpy as np

__all__ = ['PagePool', 'RadixPrefixIndex', 'prefix_key']

SCRATCH_PAGE = 0


def prefix_key(prompt, page_size):
    """Stable key of the shareable prefix of ``prompt``: a CRC32 over
    the page-aligned prefix token ids (the whole prompt when shorter
    than one page -- short prompts still group exact duplicates).

    A pure function of the token values: two requests with the same
    prompt prefix get the same key no matter when or in what order
    they arrive, which is the property the co-admission test pins.
    """
    toks = np.asarray(prompt, np.int32).reshape(-1)
    cut = (toks.size // int(page_size)) * int(page_size)
    if cut == 0:
        cut = toks.size
    return int(binascii.crc32(toks[:cut].tobytes()) & 0xffffffff)


class PagePool:
    """Refcounted free-list allocator over ``n_pages`` page ids.

    Page ids are plain ints; the device-side pool array is indexed by
    them.  ``alloc`` hands out a free page at refcount 1; ``retain``/
    ``release`` move the count; a page returns to the free list when
    its count hits zero.  Page 0 (:data:`SCRATCH_PAGE`) is never
    allocated.
    """

    def __init__(self, n_pages, page_size):
        if n_pages < 2:
            raise ValueError('need at least 2 pages (1 scratch + 1 '
                             'live), got %d' % n_pages)
        if page_size < 1:
            raise ValueError('page_size must be >= 1, got %d'
                             % page_size)
        self.n_pages = int(n_pages)
        self.page_size = int(page_size)
        self._free = list(range(n_pages - 1, 0, -1))   # pop() -> low ids
        self._ref = {}
        self.peak_in_use = 0

    def available(self):
        return len(self._free)

    def in_use(self):
        return len(self._ref)

    def refcount(self, page):
        return self._ref.get(page, 0)

    def alloc(self):
        """One free page at refcount 1, or ``None`` when dry (the
        caller decides between eviction and shedding -- the pool
        itself never blocks)."""
        if not self._free:
            return None
        page = self._free.pop()
        self._ref[page] = 1
        self.peak_in_use = max(self.peak_in_use, len(self._ref))
        return page

    def retain(self, page):
        if page not in self._ref:
            raise ValueError('retain of free page %d' % page)
        self._ref[page] += 1

    def release(self, page):
        count = self._ref.get(page)
        if count is None:
            raise ValueError('release of free page %d' % page)
        if count == 1:
            del self._ref[page]
            self._free.append(page)
        else:
            self._ref[page] = count - 1


class _Node:
    """One banked page and its place in the trie.  A node reached by a
    FULL page-sized chunk may have ``children`` and ``tails``; a tail
    (a banked partial page) is a node that never has either.  The root
    banks nothing."""
    __slots__ = ('children', 'tails', 'page', 'touch', 'parent', 'home',
                 'key', 'queued')

    def __init__(self, page=None, parent=None, home=None, key=None):
        self.children = {}     # page-sized chunk of token ids -> _Node
        self.tails = {}        # partial chunk of token ids -> _Node
        self.page = page       # pool page banking this chunk
        self.touch = 0
        self.parent = parent
        self.home = home       # the dict of ``parent`` that holds it
        self.key = key         # ... and its key there
        self.queued = False    # has a record in the eviction heap

    def evictable(self):
        return not self.children and not self.tails


def _token_bytes(prompt):
    """The prompt's token ids as the bytes of an int32 vector: a chunk
    of ``n`` tokens is a slice of ``4 * n`` bytes, hashed and compared
    at memory speed, and equal exactly where the ids are."""
    return np.ascontiguousarray(prompt, np.int32).tobytes()


class RadixPrefixIndex:
    """Radix trie over page-sized token chunks of banked prompts.

    Each trie edge is one FULL page worth of token ids; the node it
    leads to records the pool page holding that chunk's K/V.  Nodes
    additionally carry ``tails``: banked partial pages (a prompt whose
    length is not page-aligned) keyed by their token suffix.  The
    index owns one reference per banked page (taken at
    :meth:`insert`, dropped at eviction), so a banked page survives
    its sequence and is shared by every later lookup that matches it.

    ``lookup`` returns page ids only -- callers retain what they keep.
    Matching is exact on token ids (the radix property: one walk,
    longest banked prefix wins).

    Eviction order is kept as the index goes, in a heap: every
    EVICTABLE entry (a tail, or a node with no children and no tails)
    has exactly one record ``(touch, serial, entry)`` there, made when
    it became evictable.  A later touch does not move the record (so
    ``lookup`` stays a walk of the matched path): the recorded touch is
    never newer than the entry's own, hence the heap's top is a lower
    bound on every evictable entry's touch, and :meth:`evict` re-keys a
    record that surfaces stale and drops one whose node has gained a
    child since.  An eviction therefore costs O(log n) amortised and
    visits no trie node it does not evict or re-key.
    """

    def __init__(self, pool):
        self.pool = pool
        self._root = _Node()
        self._clock = 0
        self._heap = []
        self._serial = 0       # breaks ties: entries do not compare
        self._banked = 0
        self.lookups = 0
        self.hits = 0
        self.tokens_reused = 0
        self.evictions = 0     # index references dropped, ever
        self.examined = 0      # heap records :meth:`evict` has popped

    # -- stats ---------------------------------------------------------
    def hit_rate(self):
        return self.hits / self.lookups if self.lookups else 0.0

    def banked_pages(self):
        return self._banked

    # -- queries -------------------------------------------------------
    def lookup(self, prompt):
        """Longest banked prefix of ``prompt``.

        Returns ``(pages, tail_page, tail_len)``: ``pages`` are the
        FULL banked pages in position order (``len(pages) *
        page_size`` matched tokens) and ``tail_page`` (or ``None``)
        banks ``tail_len`` further tokens.  No references are taken
        -- the caller retains exactly the pages it keeps.
        """
        ps = self.pool.page_size
        step = 4 * ps
        toks = _token_bytes(prompt)
        self.lookups += 1
        self._clock += 1
        node, pages = self._root, []
        i = 0
        while i + step <= len(toks):
            child = node.children.get(toks[i:i + step])
            if child is None:
                break
            child.touch = self._clock
            pages.append(child.page)
            node = child
            i += step
        # longest banked partial page continuing the match
        best = None
        if node.tails:
            rest = toks[i:i + step]
            for key, tail in node.tails.items():
                if rest.startswith(key) and (
                        best is None or len(key) > len(best.key)):
                    best = tail
        tail_page, tail_len = None, 0
        if best is not None:
            best.touch = self._clock
            tail_page, tail_len = best.page, len(best.key) // 4
        matched = len(pages) * ps + tail_len
        if matched:
            self.hits += 1
            self.tokens_reused += matched
        return pages, tail_page, tail_len

    # -- updates -------------------------------------------------------
    def _bank(self, parent, home, key, page):
        self.pool.retain(page)
        node = home[key] = _Node(page, parent, home, key)
        self._banked += 1
        return node

    def _queue(self, entry):
        """``entry`` is evictable: one record of it in the heap, at its
        present touch -- unless an older record is still there, which
        :meth:`evict` re-keys when it surfaces."""
        if not entry.queued:
            entry.queued = True
            self._serial += 1
            heapq.heappush(self._heap,
                           (entry.touch, self._serial, entry))

    def insert(self, prompt, pages):
        """Bank a finished prompt's pages: ``pages`` cover
        ``ceil(len(prompt) / page_size)`` pages in position order.
        Already-banked chunks keep their existing page (first banking
        wins -- later duplicates are simply not indexed); each NEWLY
        indexed page gains one index-owned reference.
        """
        step = 4 * self.pool.page_size
        toks = _token_bytes(prompt)
        self._clock += 1
        node = self._root
        i = 0
        while i + step <= len(toks):
            chunk = toks[i:i + step]
            child = node.children.get(chunk)
            if child is None:
                child = self._bank(node, node.children, chunk,
                                   pages[i // step])
            child.touch = self._clock
            node = child
            i += step
        rest = toks[i:]
        if rest:
            tail = node.tails.get(rest)
            if tail is None:
                tail = self._bank(node, node.tails, rest,
                                  pages[i // step])
            tail.touch = self._clock
            node = tail
        # of the path only its end can be evictable (the root, an
        # empty prompt's end, banks nothing)
        if node is not self._root and node.evictable():
            self._queue(node)

    def evict(self, n_needed=1):
        """LRU-drop banked leaves until ``n_needed`` pages could be
        freed or nothing evictable remains: each victim is an
        evictable entry of the least ``touch`` among all of them.
        Only drops the INDEX's reference -- a page still used by live
        sequences stays allocated (and stays counted in ``in_use``)
        until they finish.  Returns the number of references
        dropped."""
        dropped = 0
        heap = self._heap
        while dropped < n_needed and heap:
            touch, _, entry = heapq.heappop(heap)
            self.examined += 1
            entry.queued = False
            if not entry.evictable():
                continue       # queued again when its last child goes
            if entry.touch != touch:
                self._queue(entry)
                continue
            del entry.home[entry.key]
            self.pool.release(entry.page)
            self._banked -= 1
            dropped += 1
            parent = entry.parent
            if parent is not self._root and parent.evictable():
                # a leaf from now on, at ITS OWN touch (a touch stamps
                # the whole path: never older than the victim's)
                self._queue(parent)
        self.evictions += dropped
        return dropped

    def flush(self):
        """Drop every banked reference (used by tests and by engines
        tearing down)."""
        self.evict(self._banked)
