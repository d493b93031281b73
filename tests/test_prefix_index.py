"""``serving.paged.RadixPrefixIndex`` by itself (ISSUE 40): the index
keeps its eviction order as it goes, so ``evict`` never walks the trie.

Two pins.  The VICTIMS are the ones the whole-tree walk chose: seeded
random traces of admissions, releases and evictions run in lockstep
against :class:`_WalkIndex`, the index as it stood before (kept here,
and only here, as the oracle).  The COST is counted, not clocked: trie
nodes visited and heap records popped for 1,000 evictions out of 500,
4,000 and 32,000 banked pages.
"""

import math

import numpy as np
import pytest

from chainermn_tpu.serving import paged
from chainermn_tpu.serving.paged import PagePool, RadixPrefixIndex


# -- the oracle: the index of before, whole-tree walk and all ----------

class _WalkNode:
    __slots__ = ('children', 'page', 'tails', 'touch')

    def __init__(self, page=None):
        self.children = {}     # page-sized token tuple -> _WalkNode
        self.page = page
        self.tails = {}        # partial-chunk token tuple -> [page, touch]
        self.touch = 0


class _WalkIndex:
    """``RadixPrefixIndex`` as it was: tuple keys, no kept order, and
    ``_lru_leaf``'s walk of the WHOLE trie to find one victim."""

    def __init__(self, pool):
        self.pool = pool
        self._root = _WalkNode()
        self._clock = 0
        self.lookups = self.hits = self.tokens_reused = 0

    def banked_pages(self):
        n, stack = 0, [self._root]
        while stack:
            node = stack.pop()
            n += len(node.tails) + len(node.children)
            stack.extend(node.children.values())
        return n

    def lookup(self, prompt):
        ps = self.pool.page_size
        toks = tuple(int(t) for t in np.asarray(prompt).reshape(-1))
        self.lookups += 1
        self._clock += 1
        node, pages = self._root, []
        i = 0
        while i + ps <= len(toks):
            child = node.children.get(toks[i:i + ps])
            if child is None:
                break
            child.touch = self._clock
            pages.append(child.page)
            node = child
            i += ps
        tail_page, tail_len = None, 0
        rest = toks[i:]
        for tail, entry in node.tails.items():
            n = len(tail)
            if n > tail_len and rest[:n] == tail:
                tail_page, tail_len = entry[0], n
        if tail_page is not None:
            for entry in node.tails.values():
                if entry[0] == tail_page:
                    entry[1] = self._clock
        matched = len(pages) * ps + tail_len
        if matched:
            self.hits += 1
            self.tokens_reused += matched
        return pages, tail_page, tail_len

    def insert(self, prompt, pages):
        ps = self.pool.page_size
        toks = tuple(int(t) for t in np.asarray(prompt).reshape(-1))
        self._clock += 1
        node = self._root
        i = 0
        while i + ps <= len(toks):
            chunk = toks[i:i + ps]
            child = node.children.get(chunk)
            if child is None:
                page = pages[i // ps]
                child = _WalkNode(page)
                self.pool.retain(page)
                node.children[chunk] = child
            child.touch = self._clock
            node = child
            i += ps
        rest = toks[i:]
        if rest and rest not in node.tails:
            page = pages[i // ps]
            self.pool.retain(page)
            node.tails[rest] = [page, self._clock]
        elif rest:
            node.tails[rest][1] = self._clock

    def evict(self, n_needed=1):
        dropped = 0
        while dropped < n_needed:
            victim = self.lru_leaf()
            if victim is None:
                break
            _, parent, kind, key, page = victim
            if kind == 'tail':
                del parent.tails[key]
            else:
                del parent.children[key]
            self.pool.release(page)
            dropped += 1
        return dropped

    def lru_leaf(self):
        best = None
        stack = [self._root]
        while stack:
            node = stack.pop()
            for tkey, (page, touch) in node.tails.items():
                if best is None or touch < best[0]:
                    best = (touch, node, 'tail', tkey, page)
            for ckey, child in node.children.items():
                if not child.children and not child.tails:
                    if best is None or child.touch < best[0]:
                        best = (child.touch, node, 'child', ckey,
                                child.page)
                stack.append(child)
        return best

    def flush(self):
        while self.evict(1):
            pass


def _evictable_or_not(index):
    """Every entry of the index under test, by a walk (the test's own:
    the index has none)."""
    out, stack = [], [index._root]
    while stack:
        node = stack.pop()
        entries = list(node.tails.values()) + list(node.children.values())
        out += entries
        stack += entries
    return out


def _evictable(index):
    """``{page: touch}`` over its evictable entries."""
    return {entry.page: entry.touch
            for entry in _evictable_or_not(index)
            if not entry.children and not entry.tails}


# -- one engine's worth of page accounting, over both indexes ----------

class _Lockstep:
    """What ``GenerationEngine`` does with its pool and index, without
    the engine, done to the index under test and to the oracle at once
    (a pool each): admissions that look up, retain what matched,
    allocate the rest (evicting while the pool is dry), bank the prompt
    and HOLD the pages until the sequence is released.  Every call must
    answer the same on both sides, and every single eviction is judged
    by the oracle's walk: the victim is an evictable entry, and its
    touch is the least there is."""

    def __init__(self, n_pages, page_size):
        self.pools = (PagePool(n_pages, page_size),
                      PagePool(n_pages, page_size))
        self.index = RadixPrefixIndex(self.pools[0])
        self.oracle = _WalkIndex(self.pools[1])
        self.live = []
        self.checked = 0       # evictions judged
        self.outlived = 0      # ... whose page a live sequence kept

    def both(self, call):
        mine, oracles = (call(pool, index) for pool, index in zip(
            self.pools, (self.index, self.oracle)))
        assert mine == oracles
        return mine

    def evict_one(self):
        pool = self.pools[0]
        evictable = _evictable(self.index)
        best = self.oracle.lru_leaf()
        before = dict(pool._ref)
        dropped = self.both(lambda _, index: index.evict(1))
        assert dropped == (best is not None)
        if dropped:
            page, = [p for p, n in before.items()
                     if pool.refcount(p) == n - 1]
            assert page in evictable, 'the victim was not evictable'
            assert evictable[page] == min(evictable.values()) == best[0]
            self.checked += 1
            self.outlived += pool.refcount(page) > 0
        return dropped

    def alloc(self):
        page = self.both(lambda pool, _: pool.alloc())
        while page is None and self.evict_one():
            page = self.both(lambda pool, _: pool.alloc())
        return page

    def admit(self, prompt, extra):
        """True where the sequence was shed for a pool dry of
        evictable pages too."""
        shared, _, _ = self.both(
            lambda _, index: index.lookup(prompt))
        pages = list(shared)
        for page in pages:
            self.both(lambda pool, _: pool.retain(page))
        n_cover = -(-len(prompt) // self.pools[0].page_size)
        while len(pages) < n_cover + extra:
            page = self.alloc()
            if page is None:
                self.release_pages(pages)
                return True
            pages.append(page)
        self.both(lambda _, index: index.insert(prompt,
                                                pages[:n_cover]))
        self.live.append(pages)
        return False

    def release_pages(self, pages):
        for page in pages:
            self.both(lambda pool, _: pool.release(page))

    def agree(self):
        self.both(lambda pool, index: (
            index.banked_pages(), pool.in_use(), pool.available(),
            dict(pool._ref), index.lookups, index.hits,
            index.tokens_reused))


PS = 4
TRAFFIC = {
    # name: (pool pages, live sequences held, shared-prefix share,
    #        share of page-aligned lengths, share of exact repeats)
    'disjoint': (48, 3, 0.0, 0.3, 0.0),
    'shared_prefixes': (48, 3, 0.7, 0.3, 0.1),
    'page_aligned': (40, 2, 0.4, 1.0, 0.2),
    'tailed_repeats': (40, 2, 0.3, 0.0, 0.4),
    'pages_held_by_live_sequences': (36, 6, 0.6, 0.3, 0.2),
    'roomy_pool': (400, 4, 0.5, 0.3, 0.2),
}


def _trace(kind, seed, n_ops=400):
    """A seeded list of operations: ``('admit', prompt, extra)``,
    ``('release', which)``, ``('evict', n)``."""
    _, held, shared, aligned, repeats = TRAFFIC[kind]
    rng = np.random.RandomState(seed)
    stems = [rng.randint(0, 9, size=PS * rng.randint(1, 4)).tolist()
             for _ in range(3)]
    seen, ops, live = [], [], 0
    for _ in range(n_ops):
        roll = rng.rand()
        if roll < 0.08:
            ops.append(('evict', int(rng.randint(1, 4))))
            continue
        if live and (live > held or roll < 0.3):
            ops.append(('release', int(rng.randint(0, 1 << 16))))
            live -= 1
            continue
        if seen and rng.rand() < repeats:
            prompt = seen[rng.randint(len(seen))]
        else:
            n = int(rng.randint(1, 5 * PS))
            if rng.rand() < aligned:
                n = max(PS, n // PS * PS)
            stem = (stems[rng.randint(len(stems))]
                    if rng.rand() < shared else [])
            # a small alphabet: prompts also collide by chance, chunk
            # by chunk and inside a tail
            prompt = (stem + rng.randint(0, 9, size=n).tolist())[
                :max(n, PS if stem else 1)]
            seen.append(prompt)
        ops.append(('admit', prompt, int(rng.randint(0, 3))))
        live += 1
    return ops


@pytest.mark.parametrize('seed', [0, 1, 2, 2147483659])
@pytest.mark.parametrize('kind', sorted(TRAFFIC))
def test_victims_are_the_whole_tree_walks(kind, seed):
    n_pages = TRAFFIC[kind][0]
    sim = _Lockstep(n_pages, PS)
    for op in _trace(kind, seed):
        if op[0] == 'admit':
            sim.admit(op[1], op[2])
        elif op[0] == 'release':
            if sim.live:
                sim.release_pages(sim.live.pop(op[1] % len(sim.live)))
        else:
            for _ in range(op[1]):
                sim.evict_one()
        sim.agree()
        assert sim.index.banked_pages() == len(_evictable_or_not(
            sim.index))
    assert sim.index.evictions == sim.checked
    if kind != 'roomy_pool':
        assert sim.checked > 50, 'the pool never ran dry: no test'
    if kind == 'pages_held_by_live_sequences':
        assert sim.outlived > 10
    # flush() empties the index; releasing the sequences, the pool
    sim.both(lambda _, index: index.flush())
    assert sim.index.banked_pages() == 0 and not sim.index._heap
    assert not sim.index._root.children and not sim.index._root.tails
    sim.agree()
    while sim.live:
        sim.release_pages(sim.live.pop())
    sim.agree()
    assert sim.pools[0].in_use() == 0
    assert sim.pools[0].available() == n_pages - 1


def test_evict_many_and_a_dry_index():
    pool = PagePool(9, PS)
    index = RadixPrefixIndex(pool)
    assert index.evict(1) == 0 and index.evict(5) == 0
    pages = [pool.alloc() for _ in range(6)]
    index.insert(list(range(10)), pages[:3])        # 2 chunks + a tail
    index.insert(list(range(4)) + [9] * 6, pages[3:])  # shares chunk 0
    for page in pages:
        pool.release(page)
    # chunk 0 is banked once: the second prompt's first page went back
    assert index.banked_pages() == 5 and pool.in_use() == 5
    assert index.evict(2) == 2      # the older prompt's tail, chunk 1
    assert index.lookup(list(range(10)))[0] == [pages[0]]
    assert index.evict(9) == 3 and index.evict(1) == 0
    assert index.banked_pages() == 0 and pool.in_use() == 0
    assert index.evictions == 5


def test_a_touch_moves_an_entry_behind_the_untouched():
    """A lookup re-keys nothing when it happens (it only stamps the
    path): the stale record is re-keyed when it surfaces."""
    pool = PagePool(8, PS)
    index = RadixPrefixIndex(pool)
    prompts = [[i] * PS for i in range(3)]
    pages = [pool.alloc() for _ in prompts]
    for prompt, page in zip(prompts, pages):
        index.insert(prompt, [page])
        pool.release(page)
    heap_before = list(index._heap)
    assert index.lookup(prompts[0] + [7])[0] == [pages[0]]
    assert index._heap == heap_before
    assert index.evict(1) == 1 and pool.refcount(pages[1]) == 0
    assert index.evict(1) == 1 and pool.refcount(pages[2]) == 0
    assert pool.refcount(pages[0]) == 1
    assert index.evict(1) == 1 and index.banked_pages() == 0


def test_any_integer_dtype_keys_the_same_chunk():
    pool = PagePool(8, PS)
    index = RadixPrefixIndex(pool)
    page = pool.alloc()
    index.insert(np.arange(PS, dtype=np.int64), [page])
    for prompt in (list(range(PS)) + [1], np.arange(PS + 1, dtype=np.int32),
                   np.arange(PS + 1, dtype=np.int64).reshape(1, -1)):
        assert index.lookup(prompt) == ([page], None, 0)
    assert index.lookup([0, 1, 2, 4]) == ([], None, 0)
    assert index.hit_rate() == 0.75


# -- the cost, counted ---------------------------------------------------

_SLOT = paged._Node.children      # the slot's own descriptor


class _CountingNode(paged._Node):
    """A trie node that counts every read of its ``children``: no walk
    visits a node without one."""
    __slots__ = ()
    reads = 0

    @property
    def children(self):
        _CountingNode.reads += 1
        return _SLOT.__get__(self)

    @children.setter
    def children(self, value):
        _SLOT.__set__(self, value)


CHAIN = 8       # pages a banked prompt, as the benchmark cell's median


def _dry_index(n_banked):
    """An index banking ``n_banked`` pages of disjoint ``CHAIN``-page
    prompts, in a pool with nothing free."""
    pool = PagePool(1 + n_banked, PS)
    index = RadixPrefixIndex(pool)
    rng = np.random.RandomState(n_banked)
    prompts = rng.randint(0, 1 << 30,
                          size=(n_banked // CHAIN, CHAIN * PS))
    for prompt in prompts:
        pages = [pool.alloc() for _ in range(CHAIN)]
        index.insert(prompt, pages)
        for page in pages:
            pool.release(page)
    assert pool.available() == n_banked % CHAIN
    return pool, index, prompts, rng


@pytest.mark.parametrize('n_banked', [500, 4000, 32000])
def test_eviction_visits_no_node_it_does_not_evict_or_rekey(
        monkeypatch, n_banked):
    monkeypatch.setattr(paged, '_Node', _CountingNode)
    pool, index, prompts, rng = _dry_index(n_banked)
    assert index.banked_pages() == n_banked // CHAIN * CHAIN
    # a fifth of the prompts looked up since: stale records to re-key
    stale = prompts[rng.rand(len(prompts)) < 0.2]
    for prompt in stale:
        assert len(index.lookup(prompt)[0]) == CHAIN
    _CountingNode.reads = 0
    index.examined = 0
    evictions, held = 0, []
    while evictions < 1000:
        # a dry pool's steady state: every page allocated is one
        # evicted, and what was prefilled is banked in its place
        page = pool.alloc()
        while page is None:
            assert index.evict(1) == 1
            evictions += 1
            page = pool.alloc()
        held.append(page)
        if len(held) == CHAIN:
            index.insert(rng.randint(0, 1 << 30, size=CHAIN * PS), held)
            for page in held:
                pool.release(page)
            held = []
    inserted = 1000 // CHAIN + 1
    # an eviction pops its victim's record and asks the victim and its
    # parent whether they are leaves; a record touched since is popped
    # once more; an insert reads its path twice (the get, the banking)
    assert 1000 <= index.examined <= 1000 + len(stale)
    assert _CountingNode.reads <= (2 * 1000 + len(stale)
                                   + inserted * (2 * CHAIN + 1))
    # and, what the issue asks, no faster than log N (the walk: N)
    assert _CountingNode.reads + index.examined \
        <= 1000 * math.log2(n_banked)


def test_records_gone_stale_are_rekeyed_once_each(monkeypatch):
    """Every banked prompt looked up after banking: each record in the
    heap is stale, so the first eviction re-keys until one holds; after
    that an eviction pops one record again (O(log n) AMORTISED)."""
    monkeypatch.setattr(paged, '_Node', _CountingNode)
    pool = PagePool(1 + 800, PS)
    index = RadixPrefixIndex(pool)
    prompts = [[i] * (2 * PS) for i in range(400)]
    banked = []
    for prompt in prompts:
        pages = [pool.alloc(), pool.alloc()]
        index.insert(prompt, pages)
        for page in pages:
            pool.release(page)
        banked.append(pages)
    for prompt in reversed(prompts):
        assert len(index.lookup(prompt + [0])[0]) == 2
    index.examined = 0
    assert index.evict(1) == 1
    assert index.examined == 401        # 400 re-keyed, then the victim
    # the first looked up was the last banked: LRU now runs backwards
    assert [pool.refcount(page) for page in banked[-1]] == [1, 0]
    assert index.evict(799) == 799
    # 799 victims, 399 of them parents at a fresh record each: nothing
    # is popped twice
    assert index.examined == 401 + 799
    assert index.banked_pages() == 0 and pool.in_use() == 0
