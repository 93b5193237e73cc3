"""PrefixCache's eviction index against the scan it replaced.

``ScanCache`` below carries ``evict_one`` and ``evictable_pages`` as
they stood before the index (a walk over every node, copied here as
the oracle). A seeded random sequence of register / match / slot
holds and releases / evict_one / evictable_pages / reclaim / restore
runs on two pools side by side, one under each cache: after every
step the two tries, both pools' reference counts and free lists, the
router's hit counts and the residency map must be equal, which is
"the same victim, dropped or spilled, in the same order". One more
case builds a large trie and counts what an eviction looks at.
"""

from typing import Dict

import numpy as np
import pytest

from distkeras_tpu.models import Model, zoo
from distkeras_tpu.serving.kv_pool import PagedKVPool, PrefixCache


class ScanCache(PrefixCache):
    """The oracle: victims chosen and evictables counted by walking
    the whole trie, as before the index."""

    def evict_one(self) -> bool:
        pool = self._pool
        while True:
            spill = drop = host_leaf = None
            for node in self._nodes.values():
                leaf = not self._children.get(node.nid)
                if node.page is None:
                    if leaf and (host_leaf is None or
                                 node.last_used < host_leaf.last_used):
                        host_leaf = node
                    continue
                if pool.ref[node.page] != 1:
                    continue                      # a slot still reads it
                if spill is None or node.last_used < spill.last_used:
                    spill = node
                if leaf and (drop is None
                             or node.last_used < drop.last_used):
                    drop = node
            if spill is not None and pool.host_free_pages > 0:
                hids = pool.offload_pages([spill.page])
                if hids is not None:
                    self._by_page.pop(spill.page, None)
                    pool.decref(spill.page)
                    spill.page = None
                    spill.host = hids[0]
                    return True
            if drop is not None:
                self._drop(drop)
                return True
            if spill is None or host_leaf is None:
                return False
            self._drop(host_leaf)

    def evictable_pages(self) -> int:
        memo: Dict[int, bool] = {}

        def ok(nid: int) -> bool:
            got = memo.get(nid)
            if got is not None:
                return got
            node = self._nodes[nid]
            memo[nid] = res = (
                (node.page is None
                 or self._pool.ref[node.page] == 1)
                and all(ok(c.nid)
                        for c in self._children.get(nid, {}).values()))
            return res

        droppable = spill_only = 0
        for node in self._nodes.values():
            if node.page is None or self._pool.ref[node.page] != 1:
                continue
            if ok(node.nid):
                droppable += 1
            else:
                spill_only += 1
        return droppable + min(spill_only, self._pool.host_free_pages)


@pytest.fixture(scope="module")
def tiny_lm():
    """An untrained one-layer LM: the pool needs its cache layout
    only, never its outputs."""
    from distkeras_tpu.models.decoding import _resolve_head_dims
    m = Model.build(
        zoo.transformer_lm(11, d_model=8, num_heads=1, num_layers=1,
                           mlp_ratio=1, use_rope=True), (8,), seed=0)
    _resolve_head_dims(m.module, m.params)
    return m


def _state(cache: PrefixCache):
    """Everything the two sides must agree on."""
    pool = cache._pool
    return {
        "trie": {nid: (n.page, n.host, n.last_used, n.parent, n.key)
                 for nid, n in cache._nodes.items()},
        "order": list(cache._nodes),
        "hits": dict(cache._hits),
        "by_page": {pid: n.nid for pid, n in cache._by_page.items()},
        "resident": [cache.resident(p) for p in range(pool.num_pages)],
        "ref": pool.ref.tolist(),
        "free": list(pool._free),
        "host_free": list(pool._host_free),
        "tables": pool.tables.tolist(),
    }


def _check_index(cache: PrefixCache):
    """The index's own invariants (``PrefixCache``'s class doc),
    recomputed from the facts."""
    pool = cache._pool
    spill, drop, host_leaf = set(), set(), set()
    pinned_below = {}

    def below(nid):
        if nid not in pinned_below:
            node = cache._nodes[nid]
            pinned_below[nid] = (
                (node.page is not None and pool.ref[node.page] != 1)
                or any(below(c.nid)
                       for c in cache._children[nid].values()))
        return pinned_below[nid]

    n_only = n_drop = 0
    for nid, node in cache._nodes.items():
        leaf = not cache._children[nid]
        if node.page is None:
            if leaf:
                host_leaf.add(nid)
            assert not node.pinned and node.counted == 0
        elif pool.ref[node.page] == 1:
            spill.add(nid)
            if leaf:
                drop.add(nid)
            n_only += 1
            n_drop += not below(nid)
            assert not node.pinned
            assert node.counted == (1 if below(nid) else 2)
        else:
            assert node.pinned and node.counted == 0
        assert node.blocked == sum(
            below(c.nid) for c in cache._children[nid].values())
    for heap, want in ((cache._spill_lru, spill), (cache._drop_lru, drop),
                       (cache._host_leaf_lru, host_leaf)):
        assert set(heap._at) == want
        assert len(heap._a) == len(want)
        for i, node in enumerate(heap._a):
            assert heap._at[node.nid] == i
            if i:
                up = heap._a[(i - 1) >> 1]
                assert (up.last_used, up.nid) < (node.last_used, node.nid)
    assert (cache._n_cache_only, cache._n_droppable) == (n_only, n_drop)


class _Sides:
    """The same operations on two pools, one under each cache."""

    def __init__(self, lm, page_len, host_pages, num_pages, num_slots,
                 max_len):
        self.pools = [
            PagedKVPool(lm.module, num_slots=num_slots, max_len=max_len,
                        page_len=page_len, num_pages=num_pages,
                        host_pages=host_pages)
            for _ in range(2)]
        self.caches = [PrefixCache(self.pools[0]), ScanCache(self.pools[1])]

    def both(self, op):
        got = [op(pool, cache)
               for pool, cache in zip(self.pools, self.caches)]
        assert got[0] == got[1], got
        a, b = (_state(c) for c in self.caches)
        for key in a:
            assert a[key] == b[key], key
        return got[0]


def _prompt(rng, page_len, templates):
    """A prompt that shares a template's head as often as not, so the
    trie branches at every depth."""
    n_pages = int(rng.integers(1, 6))
    toks = rng.integers(
        0, 4, n_pages * page_len + int(rng.integers(0, page_len)))
    if templates and rng.random() < 0.6:
        head = templates[int(rng.integers(len(templates)))]
        k = min(int(rng.integers(1, len(head) + 1)), len(toks))
        toks[:k] = head[:k]
    return toks.astype(np.int32)


def _admit(pool, cache, slot, toks):
    """What ``ServingEngine._page_plan`` and ``_apply_page_plan`` do
    for one request, without the prefill: match, hold the shared
    pages, reclaim if that can close the gap, allocate the rest."""
    full, _shared, donor = cache.match(toks)
    for pid in full:
        pool.incref(pid)
    if donor is not None:
        pool.incref(donor)
    need = pool.pages_for(len(toks) + 1) - len(full)
    if pool.free_pages < need:
        deficit = need - pool.free_pages
        if cache.evictable_pages() >= deficit:
            cache.reclaim(deficit)
    funded = pool.free_pages >= need
    if funded:
        for j, pid in enumerate(full):
            pool.assign(slot, j, pid)
        for j in range(len(full), len(full) + need):
            pool.assign(slot, j, pool.alloc_page())
    else:
        for pid in full:
            pool.decref(pid)
    if donor is not None:
        pool.decref(donor)
    return funded, list(full), donor


@pytest.mark.parametrize("seed", [0, 1, 2])
@pytest.mark.parametrize("page_len", [2, 4])
@pytest.mark.parametrize("host_pages", [0, 5])
def test_index_chooses_what_the_scan_chose(tiny_lm, host_pages, page_len,
                                           seed):
    rng = np.random.default_rng(1000 * seed + 10 * page_len + host_pages)
    num_slots = 4
    sides = _Sides(tiny_lm, page_len, host_pages, num_pages=24,
                   num_slots=num_slots, max_len=8 * page_len)
    templates = [rng.integers(0, 4, 3 * page_len).astype(np.int32)
                 for _ in range(3)]
    live = {}                         # slot -> tokens
    counts = dict.fromkeys(
        ("admit", "finish", "evict", "evicted", "query", "reclaim",
         "match", "restore", "hold"), 0)
    held = []                         # pages under a swap snapshot's hold
    for step in range(400):
        roll = rng.random()
        free_slots = [s for s in range(num_slots) if s not in live]
        if roll < 0.3 and free_slots:
            slot, toks = free_slots[0], _prompt(rng, page_len, templates)
            funded, _, _ = sides.both(
                lambda pool, cache: _admit(pool, cache, slot, toks))
            counts["admit"] += 1
            if funded:
                live[slot] = toks
        elif roll < 0.55 and live:
            # a prefill ends (register) and, as often as not, the
            # request finishes at once (release_slot)
            slot = list(live)[int(rng.integers(len(live)))]
            toks = live[slot]
            sides.both(lambda pool, cache: cache.register(
                toks, pool.tables[slot]))
            if rng.random() < 0.7:
                sides.both(lambda pool, cache: pool.release_slot(slot))
                del live[slot]
                counts["finish"] += 1
        elif roll < 0.7:
            counts["evict"] += 1
            counts["evicted"] += sides.both(
                lambda pool, cache: cache.evict_one())
        elif roll < 0.8:
            counts["query"] += 1
            sides.both(lambda pool, cache: cache.evictable_pages())
        elif roll < 0.85:
            n = int(rng.integers(1, 6))
            counts["reclaim"] += 1
            sides.both(lambda pool, cache: cache.reclaim(n))
        elif roll < 0.9:
            toks = _prompt(rng, page_len, templates)
            counts["match"] += 1
            sides.both(lambda pool, cache: cache.match(toks))
        elif roll < 0.95:
            # a spilled node comes back by itself (what match() does
            # when it meets one)
            spilled = [nid for nid, n in sides.caches[0]._nodes.items()
                       if n.page is None]
            if spilled:
                nid = spilled[int(rng.integers(len(spilled)))]
                counts["restore"] += 1
                sides.both(lambda pool, cache: cache._restore_node(
                    cache._nodes[nid]))
        else:
            # a swap snapshot's hold on a resident page, or its release
            if held and rng.random() < 0.5:
                pid = held.pop()
                sides.both(lambda pool, cache: pool.decref(pid))
            else:
                resident = sorted(sides.caches[0]._by_page)
                if resident:
                    pid = resident[int(rng.integers(len(resident)))]
                    held.append(pid)
                    counts["hold"] += 1
                    sides.both(lambda pool, cache: pool.incref(pid))
        _check_index(sides.caches[0])
    # the sequence did reach what it is for
    assert counts["evicted"] >= 10 and counts["finish"] >= 20, counts
    assert len(sides.caches[0]) > 0
    if host_pages:
        assert sides.pools[0].pages_offloaded > 0
    cache = sides.caches[0]
    assert cache.evictable_queries >= counts["query"]
    assert cache.evictions >= counts["evicted"]


def test_an_eviction_looks_at_a_handful_of_nodes_in_a_large_trie(tiny_lm):
    """5,000 nodes, 2,000 pages given back: the scan looked at every
    node for each (10 million visits); the index reads heap tops and
    the ancestors whose verdict flips. A count, not a clock."""
    page_len, per_prompt, n_prompts = 2, 10, 500
    pool = PagedKVPool(tiny_lm.module, num_slots=1,
                       max_len=per_prompt * page_len + page_len,
                       page_len=page_len, num_pages=5200)
    cache = PrefixCache(pool)
    rng = np.random.default_rng(7)
    for i in range(n_prompts):
        # distinct first pages: 500 chains of 10 nodes
        toks = np.concatenate([
            np.array([i // 4, i % 4], np.int32) + 4,
            rng.integers(0, 4, (per_prompt - 1) * page_len)]).astype(np.int32)
        for j in range(per_prompt):
            pool.assign(0, j, pool.alloc_page())
        assert cache.register(toks, pool.tables[0]) == per_prompt
        pool.release_slot(0)
    assert len(cache) == 5000 and cache.evictable_pages() == 5000
    before = cache.evict_examined
    assert cache.reclaim(2000) == 2000
    assert cache.evictions == 2000 and len(cache) == 3000
    assert cache.evictable_pages() == 3000
    assert (cache.evict_examined - before) / cache.evictions <= 16
    _check_index(cache)
