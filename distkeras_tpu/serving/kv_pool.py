"""The serving engine's KV cache: a block-pooled PAGED cache.

``PagedKVPool`` is the vLLM/PagedAttention layout:
one fixed pool of ``[num_pages, Hkv, page_len, Dh]`` pages per layer,
a per-slot page table mapping logical position ``t`` to physical page
``table[slot, t // page_len]``, pages allocated on demand as requests
grow and returned the moment they finish. Occupancy tracks ACTUAL
tokens (within ``page_len`` rounding), not worst-case length, which is
what turns memory into throughput: the pool admits however many
requests fit their real lengths, not ``HBM / max_len``.

On top of the pool, ``PrefixCache`` hash-conses shared prompt
prefixes: finished requests register their full (immutable) prompt
pages under a chained token hash, and a new request whose prompt
matches reuses those pages read-only (refcounted) — prefill then skips
the shared positions entirely. A PARTIAL page match is served
copy-on-write: the donor page is loaded into the prefill staging
cache, the chunks from the first divergent token overwrite its tail
there, and the insert writes the result to the request's own private
page — the shared original is never written.

Refcounting contract: a physical page is held by every slot whose
table points at it plus (for registered prefix pages) the cache node;
``decref`` to zero returns it to the free list. Pages the prefix cache
alone holds (``ref == 1``) are reclaimable LRU-leaf-first when
allocation pressure needs them.

The pool composes with the int8 quantized cache (``dtype="int8"``):
payload and per-token-per-head scale planes share the page tables and
move together through every insert/load/gather program.

LATENT PAGES: a model of latent-attention layers
(``models.attention.LatentAttention``) keeps ONE vector a token and
layer, the latent and behind it the roped shared key: its planes are
``[num_pages, latent_dim, page_len]``, no head axis and no separate V,
the positions last (``pool.latent``; ``models.decoding`` says why). Tables, refcounts, the prefix cache, inserts and
loads are the pool's as ever; there is no host tier, no quantized page
and no byte budget for them yet.

PAGE GROUPS BY ATTENTION KIND: a model whose layers are not all of one
kind (some attend to the whole context, some to a sliding window) gets
one group of pages per kind. The layers without a window are the pool
itself, as above. Each window kind is a ``WindowPages`` group
(``pool.aux``): physical pages of its own (planes for its layers
only), a per-slot table that is a RING as wide as a window and not as
the context, and a slot gives a page back once every position in it
lies a window behind the next one it writes (``release_behind``). A
model of one kind builds no such group and is served exactly as
before. docs/serving.md §Page groups has the contract, and which pages
of a registered prefix a window group keeps.

HOST KV OFFLOAD TIER (decode-kernel/offload PR, ROADMAP item 3b):
``PagedKVPool(host_pages=N)`` adds a host-memory page pool mirroring
the device pool's per-layer planes. ``offload_pages`` copies physical
device pages D2H (all layers' transfers enqueued async first, then
fenced and copied into the pinned host rows — the checkpoint-snapshot
discipline from docs/overlap.md) and ``restore_pages`` scatters them
back into freshly allocated device pages byte-identically. Two
consumers:

  * the serving engine's PREEMPTION path — a victim's pages swap out
    instead of being discarded, so resume is an H2D page copy + table
    restore instead of a full context re-prefill (order-of-magnitude
    cheaper eviction, which is what makes aggressive oversubscription
    safe);
  * ``PrefixCache`` eviction — a cold chain SPILLS its LRU leaves to
    host before dropping them outright, so the effective prefix-cache
    capacity multiplies: a later match restores the spilled page H2D
    and the chain serves hits again.
"""

from __future__ import annotations

import itertools
from functools import partial
from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from distkeras_tpu.models.attention import LatentAttention
from distkeras_tpu.models.decoding import (_decode_block_of, init_cache,
                                           pack_int4, unpack_int4)


#: refcount slot for "no page": table entries >= num_pages are the
#: unallocated sentinel (scatter drops, gather clamps into masked range)


@partial(jax.jit, donate_argnums=0, static_argnames=("groups",))
def _write_pages(pool, staging, table, groups=None):
    """Scatter staging pages into the pool: logical page ``p`` of the
    batch-1 staging cache lands on physical page ``table[p]``; sentinel
    entries (>= N) drop. One compiled program serves every insert —
    which pages to SKIP (shared prefix pages, pages past the prompt)
    is encoded by the sentinel, not by program shape. int4 pools
    (``"q4"`` marker) nibble-pack the payload pages here: the staging
    cache stays unpacked (one int8 byte per entry, the shared dequant
    contract), the POOL planes are where the 2x byte saving lives.
    ``pool`` is donated (the pages are written in place, the marker
    leaf passes through aliased); ``staging`` lives on. ``groups`` (a
    pool with page groups: per layer its group's index) makes ``table``
    the tuple of one such vector per group."""
    def write(pl, st, packed, table, latent=False):
        if latent:
            # a latent plane [N, C, page_len] has no head axis: staging
            # [1, C, s_max] is its pages side by side
            c, page_len = pl.shape[1:]
            pages = st.reshape(c, -1, page_len).transpose(1, 0, 2)
            return pl.at[table].set(pages.astype(pl.dtype), mode="drop")
        page_len = 2 * pl.shape[2] if packed else pl.shape[2]
        if st.ndim == 4:
            _, h, s_max, d = st.shape
            pages = st.reshape(h, s_max // page_len, page_len, d) \
                      .transpose(1, 0, 2, 3)
            if packed:
                pages = pack_int4(pages)
        else:
            _, h, s_max = st.shape
            pages = st.reshape(h, s_max // page_len, page_len) \
                      .transpose(1, 0, 2)
        return pl.at[table].set(pages.astype(pl.dtype), mode="drop")
    out = []
    for i, (pl_kv, st_kv) in enumerate(zip(pool, staging)):
        if pl_kv is None:
            out.append(None)
            continue
        q4 = "q4" in pl_kv
        tbl = table if groups is None else table[groups[i]]
        out.append({
            key: pl if key == "q4"
            else write(pl, st_kv[key], q4 and key in ("k", "v"), tbl,
                       key == "c")
            for key, pl in pl_kv.items()})
    return out


@jax.jit
def _gather_rows(pool, ids):
    """Gather physical pages ``ids`` out of every pool plane — the D2H
    offload read. One compiled program per (structure, n) pair, the
    same bounded cardinality as the per-``n_pos`` insert programs.
    Donates nothing: the pool lives on, and the gathered rows are
    buffers of their own that later donating steps cannot touch."""
    return jax.tree_util.tree_map(lambda p: p[ids], pool)


@partial(jax.jit, donate_argnums=0)
def _scatter_rows(pool, ids, vals):
    """Scatter host page payloads ``vals`` into pool rows ``ids`` —
    the H2D restore write (byte-identical: storage dtypes in, storage
    dtypes out, no recompute anywhere). ``pool`` is donated."""
    return jax.tree_util.tree_map(
        lambda p, v: p.at[ids].set(v.astype(p.dtype)), pool, vals)


@partial(jax.jit, donate_argnums=0, static_argnames=("groups",))
def _load_pages(staging, pool, table, valid, groups=None):
    """Gather pool pages into the batch-1 staging cache: logical page
    ``p`` becomes ``pool[table[p]]`` where ``valid[p]``, else keeps the
    staging content. The prefix-cache hit path: shared pages (and a
    copy-on-write donor) materialize as the staging prefix the
    remaining prefill chunks attend to. ``staging`` is donated, the
    pool never. ``groups`` as in ``_write_pages``: ``table`` and
    ``valid`` are then tuples, one vector a group."""
    def load(st, pl, packed, table, valid, latent=False):
        if latent:
            c, page_len = pl.shape[1:]
            cur = st.reshape(c, -1, page_len).transpose(1, 0, 2)
            sel = jnp.where(valid[:, None, None],
                            pl[table].astype(cur.dtype), cur)
            return sel.transpose(1, 0, 2).reshape(st.shape)
        g = pl[table]                        # [P, H, page_len(/2), D?]
        if packed:
            g = unpack_int4(g)               # [P, H, page_len, D]
        page_len = g.shape[2]
        if st.ndim == 4:
            _, h, s_max, d = st.shape
            cur = st.reshape(h, s_max // page_len, page_len, d) \
                    .transpose(1, 0, 2, 3)
            sel = jnp.where(valid[:, None, None, None],
                            g.astype(cur.dtype), cur)
            return sel.transpose(1, 0, 2, 3).reshape(1, h, s_max, d)
        _, h, s_max = st.shape
        cur = st.reshape(h, s_max // page_len, page_len) \
                .transpose(1, 0, 2)
        sel = jnp.where(valid[:, None, None], g.astype(cur.dtype), cur)
        return sel.transpose(1, 0, 2).reshape(1, h, s_max)
    out = []
    for i, (st_kv, pl_kv) in enumerate(zip(staging, pool)):
        if st_kv is None:
            out.append(None)
            continue
        q4 = "q4" in pl_kv
        tbl, ok = (table, valid) if groups is None \
            else (table[groups[i]], valid[groups[i]])
        out.append({
            key: st if key == "q4"
            else load(st, pl_kv[key], q4 and key in ("k", "v"), tbl, ok,
                      key == "c")
            for key, st in st_kv.items()})
    return out


class _PageBook:
    """Host-side accounts of one group's physical pages: a free list
    and a holder count per page (``ref``), with a listener told when a
    page's holders pass between 1 and 2."""

    def _init_book(self, num_pages: int) -> None:
        self.num_pages = int(num_pages)
        self.ref = np.zeros(self.num_pages, np.int64)
        # pop() hands out page 0 first (deterministic placement for
        # tests/traces, same convention as the slot allocator)
        self._free = list(range(self.num_pages))[::-1]
        #: called with a page id whenever that page's holder count
        #: passes between 1 and 2 (``incref``, ``decref``,
        #: ``release_slot``): ``PrefixCache`` sets it, because "only
        #: the cache holds this page" is a fact its eviction index
        #: keeps and only the pool sees change
        self._ref_listener = None

    @property
    def free_pages(self) -> int:
        return len(self._free)

    @property
    def shared_pages(self) -> int:
        """Physical pages with more than one holder (slots and/or the
        prefix cache) — the prefix-sharing win, measured."""
        return int((self.ref > 1).sum())

    def alloc_page(self) -> Optional[int]:
        """One free page with ``ref = 1`` (the caller's), or None."""
        if not self._free:
            return None
        pid = self._free.pop()
        self.ref[pid] = 1
        return pid

    def incref(self, pid: int) -> None:
        self.ref[pid] = n = self.ref[pid] + 1
        if n == 2 and self._ref_listener is not None:
            self._ref_listener(pid)

    def decref(self, pid: int) -> None:
        self.ref[pid] = n = self.ref[pid] - 1
        if n < 0:
            raise RuntimeError(
                f"page {pid} refcount went negative (double free)")
        if n == 0:
            self._free.append(pid)
        elif n == 1 and self._ref_listener is not None:
            self._ref_listener(pid)


class WindowPages(_PageBook):
    """The pages of the layers that attend to a sliding window of
    ``window`` positions: one group of a ``PagedKVPool`` whose layers
    are not all of one kind (module doc).

    ``tables`` is ``[S, ring]``: a RING in which logical page ``p`` of a
    slot sits in column ``p % ring``, ``ring = ceil((window - 1) /
    page_len) + 1`` columns being what the positions ``(t - window,
    t]`` can span. ``logical[slot, col]`` says which logical page a
    column holds (-1: none). A slot holds only the pages its next
    write's window reaches: ``release_behind(slot, t)`` gives back
    every page that lies wholly at or under ``t - window``, ``t``
    being the next position the slot writes."""

    def __init__(self, pool: "PagedKVPool", name: str, layers, window: int,
                 num_pages: Optional[int] = None):
        self._pool = pool
        self.name = name
        self.layers = tuple(layers)
        self.window = int(window)
        self.page_len = pool.page_len
        self.ring = -(-(self.window - 1) // self.page_len) + 1
        if num_pages is None:
            # every slot's ring at once, and as much again less a page
            # for what the prefix cache keeps of registered prefixes
            # (one window's pages before a point where prompts part)
            num_pages = pool.num_slots * (2 * self.ring - 1)
        self._init_book(num_pages)
        if self.num_pages < self.ring:
            raise ValueError(
                f"page group {name!r} needs at least {self.ring} pages "
                f"(one slot's window), got {self.num_pages}")
        self.tables = np.full((pool.num_slots, self.ring), self.num_pages,
                              np.int32)
        self.logical = np.full((pool.num_slots, self.ring), -1, np.int64)
        self._tables_dev = None
        #: pages slots gave back behind their window, since construction
        self.pages_released = 0

    def device_table(self):
        """The ring tables on the device (cached; a SNAPSHOT, as
        ``PagedKVPool.device_tables``)."""
        if self._tables_dev is None:
            self._tables_dev = jnp.asarray(self.tables.copy())
        return self._tables_dev

    def first_needed(self, t: int) -> int:
        """The lowest logical page that the window of a write at
        position ``t`` reaches."""
        return max(0, int(t) - self.window + 1) // self.page_len

    def span(self, t: int) -> range:
        """The logical pages a slot holds whose next write is ``t``."""
        return range(self.first_needed(t), int(t) // self.page_len + 1)

    def page_of(self, slot: int, lp: int) -> Optional[int]:
        col = lp % self.ring
        if self.logical[slot, col] != lp:
            return None
        return int(self.tables[slot, col])

    def slot_pages(self, slot: int) -> Dict[int, int]:
        """``{logical page: physical page}`` of what ``slot`` holds."""
        held = self.logical[slot] >= 0
        return dict(zip(self.logical[slot][held].tolist(),
                        self.tables[slot][held].tolist()))

    def assign(self, slot: int, lp: int, pid: int) -> None:
        """Put ``pid`` (refcount already the caller's) at logical page
        ``lp`` of ``slot``; the column has to be empty."""
        col = lp % self.ring
        if self.logical[slot, col] >= 0:
            raise RuntimeError(
                f"group {self.name!r}: slot {slot} still holds page "
                f"{self.logical[slot, col]} where page {lp} goes (the "
                "window behind it was not released)")
        self.tables[slot, col] = pid
        self.logical[slot, col] = lp
        self._tables_dev = None

    def _drop_columns(self, slot: int, cols) -> int:
        for col in cols:
            self.decref(int(self.tables[slot, col]))
        self.tables[slot, cols] = self.num_pages
        self.logical[slot, cols] = -1
        if len(cols):
            self._tables_dev = None
        return len(cols)

    def release_behind(self, slot: int, t: int) -> int:
        """Give back the pages of ``slot`` that lie wholly behind the
        window of a write at ``t``; returns how many."""
        held = self.logical[slot]
        cols = np.nonzero((held >= 0) & (held < self.first_needed(t)))[0]
        n = self._drop_columns(slot, cols)
        self.pages_released += n
        return n

    def release_slot(self, slot: int) -> int:
        return self._drop_columns(slot, np.nonzero(
            self.logical[slot] >= 0)[0])

    @property
    def live_pages(self) -> int:
        """Pages some slot's ring holds."""
        return int((self.logical >= 0).sum())


class PagedKVPool(_PageBook):
    """Fixed pool of ``num_pages`` KV pages per layer + per-slot page
    tables + host-side refcounted allocation.

    ``cache`` is the live device pytree ``decode_step_slots_paged``
    consumes; ``tables`` is the host ``[S, P]`` int32 page-table array
    (``device_tables()`` returns the cached device mirror, invalidated
    by any mutation). A table entry of ``num_pages`` is the
    unallocated sentinel.

    ``cache`` is DONATED to every program that advances it
    (``insert_pages``, ``restore_pages``, the engine's decode, verify
    and fused programs): there is one pool on the device, written in
    place, and the arrays of the old value are deleted, not merely
    stale — a handle kept across such a call raises on its next read.
    ``load_prefix`` and ``offload_pages`` only read the pool
    (``load_prefix`` donates the STAGING cache it is handed); the
    offload snapshot is a buffer of its own."""

    def __init__(self, module, num_slots: int, max_len: int,
                 page_len: int = 16, num_pages=None,
                 host_pages: int = 0, dtype=jnp.float32,
                 hbm_budget: Optional[int] = None,
                 reserve_bytes: int = 0):
        if num_slots < 1:
            raise ValueError(f"num_slots must be >= 1, got {num_slots}")
        if max_len < 1:
            raise ValueError(f"max_len must be >= 1, got {max_len}")
        if page_len < 1:
            raise ValueError(f"page_len must be >= 1, got {page_len}")
        self._module = module
        self.num_slots = int(num_slots)
        self.max_len = int(max_len)
        self.page_len = int(page_len)
        self._int4 = isinstance(dtype, str) and dtype == "int4"
        if self._int4 and self.page_len % 2:
            raise ValueError(
                f"int4 pages nibble-pack two positions per byte; "
                f"page_len must be even, got {page_len}")
        #: logical pages per slot: the page-table width (covers max_len)
        self.pages_per_slot = -(-self.max_len // self.page_len)
        #: bytes ONE physical page occupies across every layer's
        #: planes — quantized payload (int4: packed, page_len // 2
        #: bytes per head-dim row) AND the per-token scale planes.
        #: Satellite fix: budget math that counts payload bytes only
        #: overcommits quantized admission by the scale-plane share
        #: (Dh=64 -> ~6% at int8, ~12% at int4 f32 scales).
        # page groups by attention kind (module doc): the layers' windows
        # decide. One kind: this pool alone, as ever. Several: the
        # layers without a window are this pool, each window a group
        windows, kinds = {}, set()
        for i, layer in enumerate(module.layers):
            blk = _decode_block_of(layer)
            if blk is not None:
                windows.setdefault(blk.attn.attn_window, []).append(i)
                kinds.add(isinstance(blk.attn, LatentAttention))
        #: the pool's layers are LATENT attention: the planes are
        #: ``[N, latent_dim, page_len]``, one vector a token (module doc)
        if len(kinds) > 1:
            raise ValueError(
                "latent attention layers and layers that keep K and V a "
                "head side by side are not served from one pool")
        self.latent = kinds == {True}
        if self.latent and (host_pages or hbm_budget is not None
                            or isinstance(dtype, str)):
            raise ValueError(
                "latent pages are served without host_kv_pages, "
                "hbm_budget or int8 / int4 pages")
        self.aux: List[WindowPages] = []
        #: per layer ``(group index, ring)`` (None: no attention), or
        #: None for a pool of one group: ``ring`` is the logical pages a
        #: slot spans where the group's table is a ring, else None
        self.layer_groups = None
        aux_pages = ()
        if len(windows) > 1:
            if None not in windows:
                raise ValueError(
                    "layers with different windows and none without: "
                    "page groups need a kind that holds the whole "
                    f"context (windows {sorted(windows)})")
            if host_pages or self._int4 or hbm_budget is not None:
                raise ValueError(
                    "page groups by attention kind are served without a "
                    "host tier, int4 pages or hbm_budget")
            if isinstance(num_pages, (tuple, list)):
                num_pages, *aux_pages = num_pages
        elif isinstance(num_pages, (tuple, list)):
            raise ValueError("num_pages per group needs layers of more "
                             "than one attention kind")
        own = windows.get(None) if len(windows) > 1 else None
        self.page_bytes = self._page_bytes(module, self.page_len, dtype,
                                           self.max_len, own)
        if hbm_budget is not None:
            # size the pool to a BYTE budget: pages = what fits after
            # reserved bytes (weights etc.) — quantization translates
            # directly into more resident pages, hence more admitted
            # streams under the same budget
            if num_pages is not None:
                raise ValueError(
                    "pass num_pages or hbm_budget, not both")
            avail = int(hbm_budget) - int(reserve_bytes)
            num_pages = avail // self.page_bytes
            if num_pages < 1:
                raise ValueError(
                    f"hbm_budget {hbm_budget} - reserve {reserve_bytes}"
                    f" does not fit one {self.page_bytes}-byte page")
        if num_pages is None:
            # every slot's worst case at once by default; real
            # deployments size this to the HBM budget and rely on
            # cost-aware admission + preemption
            num_pages = self.num_slots * self.pages_per_slot
        self._init_book(num_pages)
        if self.num_pages < 1:
            raise ValueError(
                f"num_pages must be >= 1, got {self.num_pages}")
        # a pool SMALLER than worst-case-per-request is legitimate —
        # that is what cost-aware admission is for; the engine rejects
        # individual requests whose own worst case exceeds the pool
        self.dtype = dtype
        if own is None:
            # page pool: init_cache's batch axis is the PAGE axis; the
            # position table is validated against max_len (check_len),
            # not the page length
            self.cache = init_cache(module, self.num_pages, self.page_len,
                                    dtype, check_len=self.max_len)
        else:
            kinds = sorted(w for w in windows if w is not None)
            if len(aux_pages) > len(kinds):
                raise ValueError(
                    f"num_pages names {1 + len(aux_pages)} groups, the "
                    f"model has {1 + len(kinds)}")
            for j, w in enumerate(kinds):
                self.aux.append(WindowPages(
                    self, f"window{w}", windows[w], w,
                    aux_pages[j] if j < len(aux_pages) else None))
            pages_of, groups = {i: self.num_pages for i in own}, {}
            for i in own:
                groups[i] = (0, None)
            for g, grp in enumerate(self.aux, start=1):
                for i in grp.layers:
                    pages_of[i] = grp.num_pages
                    groups[i] = (g, self.pages_per_slot)
            self.layer_groups = tuple(groups.get(i)
                                      for i in range(len(module.layers)))
            #: per layer its group's index: what the staging programs
            #: (``_write_pages`` / ``_load_pages``) index their tables by
            self._group_of_layer = tuple(
                None if g is None else g[0] for g in self.layer_groups)
            # every layer's planes at its own group's page count (a
            # probe gives the shapes; nothing of the full width is ever
            # allocated for a window layer)
            probe = jax.eval_shape(
                lambda: init_cache(module, 1, self.page_len, dtype,
                                   check_len=self.max_len))
            self.cache = [
                None if kv is None else
                {key: jnp.zeros((pages_of[i],) + a.shape[1:], a.dtype)
                 for key, a in kv.items()}
                for i, kv in enumerate(probe)]
        if self._int4:
            # the POOL stores packed nibbles: the unpacked-payload
            # planes init_cache built become [N, H, page_len//2, D]
            # byte planes (zeros pack to zeros — no convert pass)
            self.cache = [
                kv if kv is None else {
                    key: (jnp.zeros(a.shape[:2] + (a.shape[2] // 2,)
                                    + a.shape[3:], jnp.int8)
                          if key in ("k", "v") else a)
                    for key, a in kv.items()}
                for kv in self.cache]
        self.tables = np.full((self.num_slots, self.pages_per_slot),
                              self.num_pages, np.int32)
        #: cached [pages_per_slot] logical-page index — reused by the
        #: serving loop's per-iteration vector scans (pages_per_slot is
        #: fixed at construction; rebuilding the arange every decode
        #: iteration is avoidable hot-loop churn)
        self.page_index = np.arange(self.pages_per_slot)
        self._tables_dev = None
        # --- host offload tier (module doc): a host-memory mirror of
        # the page planes, sized independently of the device pool —
        # host RAM is an order of magnitude cheaper than HBM, so this
        # is where preemption victims and cold prefix chains go
        self.host_pages = int(host_pages)
        if self.host_pages < 0:
            raise ValueError(
                f"host_pages must be >= 0, got {host_pages}")
        self.host_cache = None
        self._host_free: List[int] = []
        if self.host_pages:
            self.host_cache = [
                None if kv is None else
                {key: np.zeros((self.host_pages,) + tuple(a.shape[1:]),
                               a.dtype)
                 for key, a in kv.items()}
                for kv in self.cache]
            self._host_free = list(range(self.host_pages))[::-1]
        #: offload odometers (cumulative since construction — the
        #: engine publishes per-window deltas into ServingMetrics)
        self.pages_offloaded = 0
        self.pages_restored = 0
        self.offload_bytes = 0
        #: async swap-out (tree-speculation PR satellite): offload
        #: batches whose D2H copies are enqueued but not yet fenced
        #: into the host rows — each entry {"hids": [...], "dev":
        #: gathered device pages}. The gather is a jitted snapshot, so
        #: holding it is safe against later cache mutation; it pins
        #: device memory until the fence, bounded by outstanding swaps.
        self._pending_host: List[Dict] = []
        #: lazy-fence odometer (tests pin laziness through it)
        self.host_fences = 0

    @staticmethod
    def _page_bytes(module, page_len: int, dtype, max_len: int,
                    layers=None) -> int:
        """Per-physical-page byte cost across all layers (or the
        ``layers`` of one page group), from an
        abstract (eval_shape — nothing allocated) one-page probe:
        payload planes (int4: halved, two nibbles per byte) plus scale
        planes. The structural ``"q4"`` marker is per-LAYER, not
        per-page, and is excluded."""
        probe = jax.eval_shape(
            lambda: init_cache(module, 1, page_len, dtype,
                               check_len=max_len))
        int4 = isinstance(dtype, str) and dtype == "int4"
        total = 0
        for i, kv in enumerate(probe):
            if kv is None or (layers is not None and i not in layers):
                continue
            for key, a in kv.items():
                if key == "q4":
                    continue
                n = int(np.prod(a.shape)) * jnp.dtype(a.dtype).itemsize
                if int4 and key in ("k", "v"):
                    n //= 2
                total += n
        return total

    # -- device views -------------------------------------------------------

    def make_request_cache(self):
        """The batch-1 prefill staging cache: ``pages_per_slot *
        page_len`` positions (a page-multiple, so page loads/inserts
        reshape exactly), position-validated at ``max_len`` — prefill
        never writes past it."""
        return init_cache(self._module, 1,
                          self.pages_per_slot * self.page_len,
                          self.dtype, check_len=self.max_len)

    def device_tables(self):
        """The [S, P] page tables on device (cached; any host-side
        table mutation invalidates). Built from a SNAPSHOT of the host
        array: the CPU client zero-copy aliases suitably aligned numpy
        buffers into device memory, and the zero-bubble serving loop
        keeps launched programs in flight while the host mutates
        ``tables`` — without the copy an in-flight step could read a
        page assignment made after its dispatch."""
        if self._tables_dev is None:
            self._tables_dev = jnp.asarray(self.tables.copy())
        if self.aux:
            # one table a group, the window groups' rings behind this
            # pool's own (``layer_groups`` says which layer reads which)
            return (self._tables_dev,) + tuple(
                g.device_table() for g in self.aux)
        return self._tables_dev

    def _dirty(self):
        self._tables_dev = None

    # -- allocation ---------------------------------------------------------

    def pages_for(self, n_positions: int) -> int:
        """Pages required to hold ``n_positions`` cache positions."""
        return -(-int(n_positions) // self.page_len)

    def assign(self, slot: int, logical: int, pid: int) -> None:
        """Point ``tables[slot, logical]`` at ``pid`` (the caller has
        already arranged the refcount)."""
        self.tables[slot, logical] = pid
        self._dirty()

    def slot_pages(self, slot: int) -> List[int]:
        row = self.tables[slot]
        return row[row < self.num_pages].tolist()

    def release_slot(self, slot: int) -> int:
        """Drop the slot's hold on every page it references (pages the
        prefix cache still holds survive with the cache's ref) and
        reset its table row to the sentinel; returns the number of
        pages released. Vectorized (zero-bubble PR): one numpy
        decrement over the row instead of a per-page python loop —
        this runs on the serving loop's finish/preempt path."""
        row = self.tables[slot]
        pages = row[row < self.num_pages]
        crossed = ()
        if pages.size:
            self.ref[pages] -= 1    # a row never repeats a page
            left = self.ref[pages]
            if (left < 0).any():
                raise RuntimeError(
                    f"slot {slot} release drove a page refcount "
                    "negative (double free)")
            # freed pages return in row (logical) order — the same
            # deterministic order the per-page decref loop produced
            self._free.extend(pages[left == 0].tolist())
            if self._ref_listener is not None:
                crossed = pages[left == 1].tolist()
        self.tables[slot] = self.num_pages
        self._dirty()
        for pid in crossed:
            self._ref_listener(pid)
        for grp in self.aux:
            grp.release_slot(slot)
        return int(pages.size)

    # -- host offload tier --------------------------------------------------

    @property
    def host_free_pages(self) -> int:
        return len(self._host_free)

    def offload_pages(self, page_ids) -> Optional[List[int]]:
        """Enqueue physical device pages for D2H copy into free host
        pages; returns the host page ids (the caller owns them until
        ``free_host``), or None when the host tier is off or lacks
        capacity — callers fall back to the discard/re-prefill path.

        ASYNC (tree-speculation PR satellite): the call only gathers
        the pages into a device-side snapshot (a jitted copy — later
        cache mutation cannot touch it) and enqueues the D2H
        transfers (``copy_to_host_async``); nothing blocks. The fence
        into the pinned host rows runs LAZILY at the first
        ``restore_pages``/``free_host`` touch of these host pages —
        the preempt-heavy serving path no longer stalls its iteration
        on a D2H round trip that only the (much later, often never)
        resume actually needs. A batch freed before any restore is
        dropped without ever fencing."""
        n = len(page_ids)
        if self.host_cache is None or n == 0 \
                or len(self._host_free) < n:
            return None
        ids = jnp.asarray(np.asarray(page_ids, np.int32))
        dev = _gather_rows(self.cache, ids)
        for leaf in jax.tree_util.tree_leaves(dev):
            self.offload_bytes += leaf.nbytes
            try:
                leaf.copy_to_host_async()
            except Exception:  # lint: allow-swallow — a backend
                pass           # without async D2H fetches at the fence
        hids = [self._host_free.pop() for _ in range(n)]
        self._pending_host.append({"hids": list(hids), "dev": dev})
        self.pages_offloaded += n
        return hids

    @property
    def host_swap_pending(self) -> int:
        """Host pages whose D2H payload is enqueued but not yet
        fenced (the async swap-out's backlog; tests pin laziness)."""
        return sum(len(p["hids"]) for p in self._pending_host)

    def _fence_host(self, host_ids) -> None:
        """Materialize every pending D2H batch that covers any of
        ``host_ids`` into the host pool rows (whole batches — the
        gather was batch-granular). The fancy-index store always
        copies, so no view of runtime-owned device memory survives."""
        need = {int(h) for h in host_ids}
        if not need or not self._pending_host:
            return
        keep = []
        for pend in self._pending_host:
            if need.isdisjoint(pend["hids"]):
                keep.append(pend)
                continue
            self.host_fences += 1
            hsel = np.asarray(pend["hids"], np.int64)
            for kv_host, kv_dev in zip(self.host_cache, pend["dev"]):
                if kv_host is None:
                    continue
                for key, host_arr in kv_host.items():
                    host_arr[hsel] = np.asarray(kv_dev[key])
        self._pending_host = keep

    def restore_pages(self, host_ids, dev_ids) -> None:
        """H2D: host page payloads -> the given (already allocated)
        device pages, byte-identical — the swap-in that replaces a
        preemption victim's full context re-prefill. Fences any
        pending async swap-out of these pages first. The host pages
        are NOT freed here (``free_host`` is the owner's call)."""
        if self.host_cache is None:
            raise RuntimeError(
                "no host page pool (construct with host_pages > 0)")
        if len(host_ids) != len(dev_ids):
            raise ValueError(
                f"host/device page counts differ: {len(host_ids)} "
                f"vs {len(dev_ids)}")
        if not len(host_ids):
            return
        self._fence_host(host_ids)
        hsel = np.asarray(host_ids, np.int64)
        vals = [None if kv is None else
                {key: a[hsel] for key, a in kv.items()}
                for kv in self.host_cache]
        self.cache = _scatter_rows(
            self.cache, jnp.asarray(np.asarray(dev_ids, np.int32)),
            vals)
        self.pages_restored += len(host_ids)

    def free_host(self, host_ids) -> None:
        """Return host pages to the free list. A pending async batch
        fully covered by the free is DROPPED without fencing (its
        payload has no reader left); partially freed batches fence
        first so the surviving pages' data lands. Double-free is a
        loud error — two owners sharing one host page would corrupt
        both (the device-side ``decref`` contract, host edition)."""
        need = {int(h) for h in host_ids}
        if need and self._pending_host:
            keep = []
            for pend in self._pending_host:
                hs = set(pend["hids"])
                if hs and hs <= need:
                    continue             # fully freed: never fence
                keep.append(pend)
            self._pending_host = keep
            self._fence_host(need)
        for h in host_ids:
            h = int(h)
            if h in self._host_free:
                raise RuntimeError(f"host page {h} double-freed")
            self._host_free.append(h)

    # -- staging transfers --------------------------------------------------

    def _group_vectors(self, aux_pages):
        """Per window group, ``{logical page: physical page}`` as the
        ``[pages_per_slot]`` vector the staging programs index with,
        and which entries are set."""
        tvs, oks = [], []
        for grp, pages in zip(self.aux, aux_pages):
            tv = np.full(self.pages_per_slot, grp.num_pages, np.int32)
            for lp, pid in pages.items():
                tv[lp] = pid
            tvs.append(jnp.asarray(tv))
            oks.append(jnp.asarray(tv < grp.num_pages))
        return tvs, oks

    def insert_pages(self, staging, slot: int, skip_pages: int,
                     n_pos: int, aux_pages=None) -> None:
        """Scatter the staging cache's logical pages
        ``[skip_pages, pages_for(n_pos))`` into the slot's physical
        pages — ONLY the pages the prompt actually fills and that are
        not already shared (the prefix-cache pages at the front hold
        identical data and are skipped wholesale). ``aux_pages`` (a
        pool with window groups): per group ``{logical page: physical
        page}``, the staging pages its layers write and where."""
        n_needed = self.pages_for(n_pos)
        tv = np.full(self.pages_per_slot, self.num_pages, np.int32)
        tv[skip_pages:n_needed] = self.tables[slot, skip_pages:n_needed]
        if not self.aux:
            self.cache = _write_pages(self.cache, staging, jnp.asarray(tv))
            return
        tvs, _ = self._group_vectors(aux_pages)
        self.cache = _write_pages(self.cache, staging,
                                  (jnp.asarray(tv), *tvs),
                                  groups=self._group_of_layer)

    def load_prefix(self, staging, page_ids: List[int], n_tokens: int,
                    aux_pages=None):
        """Materialize a shared prefix into the staging cache: pages
        ``page_ids`` (full shared pages, plus the copy-on-write donor
        as the last entry for a partial match) become staging positions
        ``[0, n_tokens)`` (plus donor tail garbage the prefill chunks
        overwrite). Returns the new staging pytree. ``aux_pages``: per
        window group ``{logical page: physical page}``, the pages of
        that group to load beside them (one window's before
        ``n_tokens``)."""
        n_load = self.pages_for(n_tokens)
        if len(page_ids) < n_load:
            raise ValueError(
                f"{len(page_ids)} pages cannot cover {n_tokens} shared "
                f"tokens ({n_load} pages)")
        tv = np.full(self.pages_per_slot, self.num_pages, np.int32)
        tv[:n_load] = page_ids[:n_load]
        valid = self.page_index < n_load
        if not self.aux:
            return _load_pages(staging, self.cache, jnp.asarray(tv),
                               jnp.asarray(valid))
        tvs, oks = self._group_vectors(aux_pages)
        return _load_pages(staging, self.cache, (jnp.asarray(tv), *tvs),
                           (jnp.asarray(valid), *oks),
                           groups=self._group_of_layer)


# --- prefix cache -----------------------------------------------------------


class _Node:
    __slots__ = ("nid", "page", "parent", "key", "last_used", "host",
                 "pinned", "blocked", "counted", "aux")

    def __init__(self, nid, page, parent, key, last_used):
        self.nid = nid
        self.page = page                 # device page id, or None when
        self.host = None                 # spilled (``host`` holds the
        self.parent = parent             # host page id instead)
        self.key = key
        self.last_used = last_used
        # the eviction index's view (``PrefixCache._index``):
        self.pinned = False              # on the device, a slot reads it
        self.blocked = 0                 # children with a pin at or below
        self.counted = 0                 # 0 not cache-only, 1 spill-only,
        #                                  2 droppable
        self.aux = None                  # per window group: its page of
        #                                  these positions, or None


class _LRUHeap:
    """Min-heap of trie nodes on ``(last_used, nid)`` that knows where
    each node sits, so a node is added, re-keyed after a touch or
    taken out of the middle in O(log n), and ``top()`` is the least
    recently used member with nothing stale to skip. ``nid`` breaks
    ties the way a scan of ``PrefixCache._nodes`` with a strict ``<``
    does: the node created first."""

    __slots__ = ("_a", "_at")

    def __init__(self):
        self._a: List[_Node] = []
        self._at: Dict[int, int] = {}    # nid -> index in _a

    def __len__(self) -> int:
        return len(self._a)

    def top(self) -> Optional[_Node]:
        return self._a[0] if self._a else None

    def keep(self, node: _Node, member: bool) -> None:
        """Make ``node`` a member (at the place its key now has) or
        not one."""
        if not member:
            self.discard(node)
            return
        i = self._at.get(node.nid)
        if i is None:
            i = len(self._a)
            self._a.append(node)
        self._settle(node, i)

    def moved(self, node: _Node) -> None:
        """``node``'s key changed: re-place it if it is a member."""
        i = self._at.get(node.nid)
        if i is not None:
            self._settle(node, i)

    def discard(self, node: _Node) -> None:
        i = self._at.pop(node.nid, None)
        if i is None:
            return
        last = self._a.pop()
        if last is not node:
            self._settle(last, i)

    def _settle(self, node: _Node, i: int) -> None:
        """Place ``node`` starting from the hole at index ``i``: up
        while it is older than its parent, then down while a child is
        older than it."""
        a, at = self._a, self._at
        key = (node.last_used, node.nid)
        while i:
            up = (i - 1) >> 1
            above = a[up]
            if (above.last_used, above.nid) < key:
                break
            a[i] = above
            at[above.nid] = i
            i = up
        n = len(a)
        while True:
            c = 2 * i + 1
            if c >= n:
                break
            below = a[c]
            if c + 1 < n:
                right = a[c + 1]
                if (right.last_used, right.nid) \
                        < (below.last_used, below.nid):
                    c += 1
                    below = right
            if key < (below.last_used, below.nid):
                break
            a[i] = below
            at[below.nid] = i
            i = c
        a[i] = node
        at[node.nid] = i


class PrefixCache:
    """Hash-consed shared prompt prefixes over a ``PagedKVPool``.

    A trie keyed by page-sized token runs: node ``(parent, tokens)``
    owns the physical page holding those positions' KV. Finished
    prefills ``register()`` their full (immutable — decode never
    writes them) prompt pages; ``match()`` walks the longest chain a
    new prompt shares and additionally finds the best PARTIAL match
    among the last node's children (the copy-on-write donor). Matches
    are capped at ``len(tokens) - 1``: the final prompt position is
    always recomputed because its logits seed the first sampled token.

    KV sharing is exact up to chunked-prefill fp reassociation: a
    page's values were computed by SOME request's prefill over the
    same token prefix; a different total prompt length can place the
    ragged final chunk differently, which reassociates the softmax
    sums. Greedy token identity is unaffected at any realistic argmax
    margin (the oracle tests pin this); bitwise-KV-sensitive callers
    can disable sharing per engine.

    Eviction is LRU over LEAF nodes whose page only the cache holds
    (``ref == 1``) — evicting a leaf exposes its parent for the next
    round, so sustained pressure unwinds whole chains.

    With a pool host tier (``PagedKVPool(host_pages=N)``) eviction
    SPILLS before it drops: the LRU victim's page copies D2H and the
    node stays in the trie host-resident (``match()`` restores it to
    a fresh device page on the next hit — H2D copy, no recompute), so
    the effective cache capacity is device + host pages. Only when
    the host tier is full (or absent) does a victim drop outright;
    sustained pressure then unwinds the OLDEST host-resident leaves
    first, exposing their parents for spilling in turn.

    THE EVICTION INDEX. Which page to give back, and how many could
    be, are answered from an index kept where the facts change, not
    by walking the trie. A node is CACHE-ONLY when it is on the
    device and ``pool.ref[page] == 1``, PINNED when it is on the
    device and a slot (or a swap snapshot) holds the page too, and a
    LEAF when it has no children. Invariants, true between any two
    calls:

    * ``_spill_lru`` holds exactly the cache-only nodes, ``_drop_lru``
      the cache-only leaves, ``_host_leaf_lru`` the host-resident
      leaves, each a heap on ``(last_used, nid)``: its top is the
      node the scan ``evict_one`` used to make would have chosen
      (least ``last_used``, the node created first among equals);
    * ``node.pinned`` says whether the node is pinned, and
      ``node.blocked`` counts its children that are pinned or have a
      pinned node anywhere below them; a cache-only node is DROPPABLE
      when ``blocked == 0`` (leaf-first dropping can reach it) and
      SPILL-ONLY otherwise; ``_n_cache_only`` and ``_n_droppable``
      count them.

    They are restored by ``_index(node)`` after every change to a
    node's page, tier or children (``register``, ``_drop``,
    ``_restore_node``, the spill), by ``_touch`` where ``last_used``
    moves (``match``, ``register``), and by ``_ref_crossed`` when the
    pool sees a page's holders pass between 1 and 2 (``incref`` /
    ``decref`` / ``release_slot``: the cache registers itself as the
    pool's ``_ref_listener``: one cache a pool). So every change to
    ``pool.ref`` must go through those pool methods. Cost: a heap move is O(log n); a pin
    or unpin walks up only while an ancestor's verdict flips, at most
    the chain's depth (``pages_per_slot``) and one step where the
    parent is pinned too, which is how a slot holds a chain.
    ``evict_one`` reads three heap tops a round and
    ``evictable_pages`` two counters: neither grows with the trie.
    ``evictions``, ``evict_examined`` (heap tops read choosing
    victims plus nodes visited keeping the counts) and
    ``evictable_queries`` are the odometers that show it.

    WINDOW GROUPS (a pool with ``aux`` groups). The trie stays keyed
    and counted on the pool's own pages (the layers that hold the whole
    context). A node MAY also hold a page of each window group
    (``node.aux[g]``): the window layers' K/V of the same positions. A
    prompt can resume prefill at a page boundary only if the window
    layers' keys of the last ``window - 1`` positions before it are to
    hand, so ``match_groups`` gives the whole chain a prompt shares, or
    nothing where a group lacks a page of that reach. Which nodes get
    such pages: those within one window's reach behind a boundary at
    which some later prompt stopped matching (``register(...,
    aux_rows=)``; the request that found the boundary cut for lack of
    them wrote them): a hit ends where prompts part, and only there.
    A window page only the cache holds is given back least recently
    used first under that group's own pressure (``aux_evict_one``),
    the node and its own page staying; a node that drops releases
    them too."""

    def __init__(self, pool: PagedKVPool):
        self._pool = pool
        self._nodes: Dict[int, _Node] = {}
        #: parent nid -> {page-token bytes -> node}; 0 is the root
        self._children: Dict[int, Dict[bytes, _Node]] = {0: {}}
        #: parent nid -> {first token -> [nodes]}: the partial-match
        #: candidate index (a donor match needs >= 1 leading token, so
        #: only children sharing the probe's first token can qualify —
        #: without this, every lookup scanned ALL children of the
        #: chain end, O(distinct prompts) per admission)
        self._first: Dict[int, Dict[int, List[_Node]]] = {}
        #: routing signal (serving router): first-page key -> how many
        #: times ``match()`` served a chain rooted at that page. The
        #: dict is bounded by the root's live children (entries die
        #: with their node in ``_drop``)
        self._hits: Dict[bytes, int] = {}
        #: device page id -> owning node: the O(1) residency probe the
        #: engine's prefix-aware swap snapshot consults (tree-spec PR
        #: satellite) — a resident page need not be copied to host, it
        #: just needs a refcount hold until resume re-links it
        self._by_page: Dict[int, _Node] = {}
        self._nid = itertools.count(1)
        self._tick = itertools.count()
        # the eviction index (class doc)
        self._spill_lru = _LRUHeap()
        self._drop_lru = _LRUHeap()
        self._host_leaf_lru = _LRUHeap()
        self._lrus = (self._spill_lru, self._drop_lru,
                      self._host_leaf_lru)
        self._n_cache_only = 0
        self._n_droppable = 0
        #: odometers, cumulative since construction (the engine
        #: publishes per-window deltas into ServingMetrics): device
        #: pages given back, heap tops and nodes looked at to choose
        #: them and to keep the counts, ``evictable_pages()`` calls
        self.evictions = 0
        self.evict_examined = 0
        self.evictable_queries = 0
        pool._ref_listener = self._ref_crossed
        # window groups (class doc): per group, page id -> node, the
        # heap of nodes whose page of that group only the cache holds,
        # and how many such pages were given back
        self._aux_by_page: List[Dict[int, _Node]] = []
        self._aux_lru: List[_LRUHeap] = []
        self.aux_evictions: List[int] = []
        for g, grp in enumerate(getattr(pool, "aux", ())):
            self._aux_by_page.append({})
            self._aux_lru.append(_LRUHeap())
            self.aux_evictions.append(0)
            grp._ref_listener = partial(self._aux_ref_crossed, g)
        self._lrus += tuple(self._aux_lru)

    def __len__(self) -> int:
        return len(self._nodes)

    def resident(self, pid: int) -> bool:
        """Is device page ``pid`` held by a cache node right now?"""
        return int(pid) in self._by_page

    # -- window groups (class doc) ------------------------------------------

    def _aux_ref_crossed(self, g: int, pid: int) -> None:
        node = self._aux_by_page[g].get(int(pid))
        if node is not None:
            self._aux_lru[g].keep(
                node, self._pool.aux[g].ref[int(pid)] == 1)

    def _aux_release(self, node: _Node, g: int) -> None:
        pid = node.aux[g]
        node.aux[g] = None
        del self._aux_by_page[g][pid]
        self._aux_lru[g].discard(node)
        self._pool.aux[g].decref(pid)

    def aux_evictable(self, g: int) -> int:
        """Pages of window group ``g`` that only the cache holds."""
        return len(self._aux_lru[g])

    def aux_evict_one(self, g: int) -> bool:
        """Give back the least recently used page of window group ``g``
        that only the cache holds (its node stays, a boundary behind
        which it lay can no longer be resumed at); False if none."""
        node = self._aux_lru[g].top()
        if node is None:
            return False
        self._aux_release(node, g)
        self.aux_evictions[g] += 1
        return True

    def match_groups(self, tokens):
        """``match`` for a pool with window groups, at page boundaries
        only (no copy-on-write donor): ``(pages, shared_len, None,
        aux_pages, reach)``. ``reach`` is how many pages the prompt
        shares with the trie, the boundary at which it parts from
        every prompt seen; the hit is that whole chain if every window
        group still holds what its window reaches back to from there
        (``aux_pages[g]``: ``{logical page: page id}`` of those pages of
        group ``g``, which ``load_prefix`` loads beside ``pages``), and
        nothing otherwise: a hit resumes where prompts part, which is
        where such pages are left, or not at all (so the residual
        prefill shapes stay those of whole hits and misses)."""
        pool = self._pool
        pl = pool.page_len
        toks = np.ascontiguousarray(np.asarray(tokens, np.int32))
        chain = self._walk(toks, next(self._tick))
        reach = len(chain)
        need = [range(grp.first_needed(reach * pl), reach)
                for grp in pool.aux]
        if reach and all(chain[lp].aux is not None
                         and chain[lp].aux[g] is not None
                         for g, lps in enumerate(need) for lp in lps):
            return ([n.page for n in chain], reach * pl, None,
                    [{lp: chain[lp].aux[g] for lp in lps}
                     for g, lps in enumerate(need)], reach)
        return [], 0, None, [{} for _ in pool.aux], reach

    # -- the eviction index (class doc) -------------------------------------

    def _index(self, node: _Node) -> None:
        """Bring the index up to date with ``node``'s own facts: its
        pin (told to the ancestors if it changed), its count, and its
        place in or out of the three heaps."""
        page = node.page
        pinned = page is not None and self._pool.ref[page] != 1
        if pinned != node.pinned:
            node.pinned = pinned
            if not node.blocked:         # its own verdict just flipped
                self._block(node.parent, 1 if pinned else -1)
        self._count(node)
        cache_only = page is not None and not pinned
        leaf = not self._children[node.nid]
        self._spill_lru.keep(node, cache_only)
        self._drop_lru.keep(node, cache_only and leaf)
        self._host_leaf_lru.keep(node, page is None and leaf)

    def _count(self, node: _Node) -> None:
        counted = (0 if node.page is None or node.pinned
                   else 1 if node.blocked else 2)
        if counted != node.counted:
            self._n_cache_only += (counted > 0) - (node.counted > 0)
            self._n_droppable += (counted == 2) - (node.counted == 2)
            node.counted = counted

    def _block(self, nid: int, delta: int) -> None:
        """A child of node ``nid`` began (+1) or ceased (-1) to have a
        pin at or below it. Walks up only while the verdict flips."""
        crossed = 1 if delta > 0 else 0
        while nid:
            node = self._nodes[nid]
            self.evict_examined += 1
            node.blocked += delta
            if node.blocked != crossed:
                return                   # blocked before and after
            self._count(node)
            if node.pinned:
                return                   # its own pin decides above it
            nid = node.parent

    def _touch(self, node: _Node, tick: int) -> None:
        node.last_used = tick
        for lru in self._lrus:
            lru.moved(node)

    def _ref_crossed(self, pid: int) -> None:
        """The pool's ``_ref_listener``: page ``pid`` went from one
        holder to two or back."""
        node = self._by_page.get(int(pid))
        if node is not None:
            self._index(node)

    # -- router affinity signal ---------------------------------------------

    def affinity_key(self, tokens) -> bytes:
        """Cheap placement key for prefix-affinity routing: the byte
        string of the prompt's FIRST page-sized token run — the trie's
        root edge, so two prompts share cached pages only if their
        affinity keys agree. A prompt shorter than one full page can
        never share a full page; its (short) raw bytes come back and
        ``probe()`` simply misses."""
        toks = np.ascontiguousarray(np.asarray(tokens, np.int32))
        return toks[:self._pool.page_len].tobytes()

    def probe(self, key: bytes) -> Optional[int]:
        """Side-effect-free affinity probe (no LRU touch, no counter
        bump — a router may call this per replica per submit): ``None``
        when no registered chain starts with this page run, else the
        number of times ``match()`` has served a chain rooted at it
        (0 = resident but not yet re-used). The serving router ranks
        replicas by this signal (``serving.router.PrefixAffinity``)."""
        if key not in self._children.get(0, {}):
            return None
        return self._hits.get(key, 0)

    def match(self, tokens) -> Tuple[List[int], int, Optional[int]]:
        """Longest shared prefix of ``tokens``: returns ``(full_pages,
        shared_len, donor_page)`` where ``full_pages`` are the chained
        full-page hits (``len * page_len`` tokens), ``shared_len`` adds
        the best partial-page match and ``donor_page`` is the page to
        copy-on-write for it (None for a page-aligned match)."""
        pool = self._pool
        pl = pool.page_len
        toks = np.ascontiguousarray(np.asarray(tokens, np.int32))
        n = len(toks)
        tick = next(self._tick)
        chain = self._walk(toks, tick)
        pages: List[int] = [node.page for node in chain]
        parent = chain[-1].nid if chain else 0
        pos = len(chain) * pl
        # best partial continuation among the chain's children (the
        # copy-on-write donor); also catches the "whole prompt cached"
        # case — the last page re-enters here with pl - 1 tokens
        donor = None
        best = 0
        limit = min(pl, n - 1 - pos)
        if limit > 0:
            cands = self._first.get(parent, {}).get(int(toks[pos]), [])
            for node in cands:
                cand = np.frombuffer(node.key, np.int32)[:limit]
                m = int((np.cumprod(cand == toks[pos:pos + limit]))
                        .sum())
                if m > best:
                    best, donor = m, node
        if donor is not None and donor.page is None \
                and not self._restore_node(donor):
            donor = None                 # spilled donor, pool full
        if donor is not None:
            self._touch(donor, tick)
            return pages, pos + best, donor.page
        return pages, pos, None

    def _walk(self, toks, tick: int) -> List[_Node]:
        """The chain of full-page nodes ``toks`` shares with the trie,
        each on the device and touched; capped so that the shared
        length stays under ``len(toks)``."""
        pl = self._pool.page_len
        n = len(toks)
        chain: List[_Node] = []
        parent = 0
        pos = 0
        while pos + pl < n:
            key = toks[pos:pos + pl].tobytes()
            node = self._children.get(parent, {}).get(key)
            if node is None:
                break
            if node.page is None and not self._restore_node(node):
                break                    # host-resident, no device page
            self._touch(node, tick)
            if parent == 0:
                # affinity hit counter: this chain's root page served
                # a match (the router's "hot prefix" signal)
                self._hits[key] = self._hits.get(key, 0) + 1
            chain.append(node)
            parent = node.nid
            pos += pl
        return chain

    def _restore_node(self, node: _Node) -> bool:
        """Bring a host-resident (spilled) node back onto a fresh
        device page — H2D copy, byte-identical, no prefill recompute.
        False when no device page can be allocated (the chain walk
        stops there; the node stays spilled for a later try)."""
        pool = self._pool
        pid = pool.alloc_page()          # ref = 1: the cache's hold
        if pid is None:
            return False
        pool.restore_pages([node.host], [pid])
        pool.free_host([node.host])
        node.host = None
        node.page = pid
        self._by_page[pid] = node
        self._index(node)
        return True

    def register(self, tokens, table_row, aux_rows=None) -> int:
        """Install every FULL prompt page of ``tokens`` (physical ids
        from ``table_row``) into the trie; pages already registered
        along the chain are left as-is (a privately recomputed
        duplicate stays private and dies with its request). Each new
        node increfs its page — the cache is a holder. Returns the
        number of pages newly registered. ``aux_rows`` (window groups,
        class doc): per group ``{logical page: page id}``, pages of
        that group holding the same positions; a node on the chain
        that has none of that group takes it (and a hold on it)."""
        pool = self._pool
        pl = pool.page_len
        toks = np.ascontiguousarray(np.asarray(tokens, np.int32))
        tick = next(self._tick)
        parent = 0
        added = 0
        for j in range(len(toks) // pl):
            key = toks[j * pl:(j + 1) * pl].tobytes()
            ch = self._children.setdefault(parent, {})
            node = ch.get(key)
            if node is not None and node.page is None:
                # the chain spilled (or its restore failed) between
                # this request's match and its register — the request
                # recomputed the page privately, so ADOPT that live
                # device page and retire the host copy: sharing
                # revives at zero copy cost (same fp-reassociation
                # contract as any registered page)
                pid = int(table_row[j])
                if pid < pool.num_pages:
                    node.page = pid
                    pool.incref(pid)
                    self._by_page[pid] = node
                    pool.free_host([node.host])
                    node.host = None
                    self._index(node)
            if node is None:
                pid = int(table_row[j])
                if pid >= pool.num_pages:
                    break                # unallocated: nothing to share
                node = _Node(next(self._nid), pid, parent, key, tick)
                ch[key] = node
                self._children[node.nid] = {}
                self._nodes[node.nid] = node
                self._first.setdefault(parent, {}).setdefault(
                    int(toks[j * pl]), []).append(node)
                pool.incref(pid)
                self._by_page[pid] = node
                added += 1
                self._index(node)
                if parent and len(ch) == 1:      # no longer a leaf
                    self._index(self._nodes[parent])
            self._touch(node, tick)
            parent = node.nid
            for g, row in enumerate(aux_rows or ()):
                pid = row.get(j)
                if pid is None:
                    continue
                if node.aux is None:
                    node.aux = [None] * len(aux_rows)
                if node.aux[g] is None:
                    node.aux[g] = int(pid)
                    self._aux_by_page[g][int(pid)] = node
                    self._pool.aux[g].incref(int(pid))
                    self._aux_ref_crossed(g, int(pid))
        return added

    def _drop(self, node: _Node) -> None:
        """Remove a node from the trie, releasing whichever page
        (device or host) it holds."""
        del self._children[node.parent][node.key]
        del self._children[node.nid]
        del self._nodes[node.nid]
        if node.parent == 0:
            self._hits.pop(node.key, None)
        tok0 = int(np.frombuffer(node.key, np.int32)[0])
        bucket = self._first.get(node.parent, {}).get(tok0, [])
        if node in bucket:
            bucket.remove(node)
        if node.page is not None:
            self._by_page.pop(node.page, None)
            self._pool.decref(node.page)
        else:
            self._pool.free_host([node.host])
        for g, pid in enumerate(node.aux or ()):
            if pid is not None:
                self._aux_release(node, g)
        # the index: the node leaves it, its parent may be a leaf now
        for lru in self._lrus:
            lru.discard(node)
        self._n_cache_only -= node.counted > 0
        self._n_droppable -= node.counted == 2
        if node.pinned or node.blocked:
            self._block(node.parent, -1)
        if node.parent and not self._children[node.parent]:
            self._index(self._nodes[node.parent])

    def evict_one(self) -> bool:
        """Free ONE device page held only by the cache. With a pool
        host tier, the LRU cache-only node SPILLS (page copied D2H,
        node stays matchable — ``match()`` restores it in place, so
        spilling ANY node, leaf or interior, leaves the trie intact);
        without host space the LRU cache-only LEAF drops outright
        (dropping must stay leaf-first or the chain below would
        orphan), and when every droppable leaf is already
        host-resident, the OLDEST spilled leaves drop first to free
        host space and expose their parents. False when no device
        page can be freed (every cached page is also live in some
        slot)."""
        pool = self._pool
        while True:
            # the three choices, each the top of its heap (class doc)
            spill = self._spill_lru.top()
            drop = self._drop_lru.top()
            host_leaf = self._host_leaf_lru.top()
            self.evict_examined += 3
            if spill is not None and pool.host_free_pages > 0:
                hids = pool.offload_pages([spill.page])
                if hids is not None:
                    self._by_page.pop(spill.page, None)
                    pool.decref(spill.page)
                    spill.page = None
                    spill.host = hids[0]
                    self._index(spill)
                    self.evictions += 1
                    return True
            if drop is not None:
                self._drop(drop)
                self.evictions += 1
                return True
            if spill is None or host_leaf is None:
                # no device page to free at all (spill is None: the
                # host-resident remainder must NOT be drained for
                # nothing), or nothing left to unwind
                return False
            # unwind: a device page exists but the host tier is full
            # and it is not a droppable leaf — dropping the oldest
            # spilled leaf frees host space (the next round can spill
            # again) and may expose a device-resident parent
            self._drop(host_leaf)

    def evictable_pages(self) -> int:
        """DEVICE pages the cache could EVENTUALLY free under
        pressure. Without a host tier: nodes whose page only the
        cache holds and whose whole subtree is in the same position
        (dropping is leaf-first, so children must be freeable before
        their parent; a host-resident node blocks nothing and
        contributes nothing). A cache-only node NOT drop-reachable
        that way can still SPILL — but each such spill permanently
        consumes a host page (a spilled interior node is not
        unwindable while slot-pinned children keep it off the leaf
        frontier), so the spill-only contribution is capped at the
        host pool's free capacity. Callers check this BEFORE
        reclaiming toward a target — a reclaim that cannot reach its
        goal would drain the whole reusable cache for nothing."""
        self.evictable_queries += 1
        spill_only = self._n_cache_only - self._n_droppable
        return self._n_droppable + min(spill_only,
                                       self._pool.host_free_pages)

    def reclaim(self, n_pages: int) -> int:
        """Evict until ``n_pages`` pages were freed (or nothing more is
        evictable); returns the number freed."""
        freed = 0
        while freed < n_pages and self.evict_one():
            freed += 1
        return freed
