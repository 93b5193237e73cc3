"""Draft sources for speculative decoding in the serving engine.

Decode is memory-bandwidth-bound: every iteration moves the whole
parameter set plus the KV pages to emit ONE token per slot. Speculative
decoding (Leviathan et al.) amortizes one target-model pass over ``k``
candidate tokens: a cheap DRAFT proposes ``d_1..d_k`` per slot, the
target scores the whole ``[tok, d_1, .., d_k]`` window in one batched
verify step (``models.decoding.verify_step_slots_paged``), and the
longest prefix of drafts matching the target's own choices is accepted
— plus the target's next candidate for free. High-acceptance streams
emit up to ``k + 1`` tokens per target pass; the worst case emits the
1 token plain decode would have.

Two draft sources, one interface:

``NgramDraft`` — prompt-lookup / n-gram SELF-drafting: propose the
    continuation that followed the most recent earlier occurrence of
    the stream's current suffix (searched over prompt + generated
    tokens, host-side, zero extra weights and zero device work).
    Excellent on repetitive / templated / retrieval-grounded streams
    (summarization, code edits, RAG quoting its context); near-zero
    acceptance on text whose continuation never re-occurs — which the
    engine's per-request acceptance EMA detects, kicking the stream
    back to plain decode.

``DraftModel`` — a small target-compatible model (same vocab) decoded
    greedily ``k`` steps ahead through the EXISTING paged machinery:
    its own ``PagedKVPool`` (sized worst-case up front, so drafting can
    never starve the target pool's admission budget mid-flight), its
    own per-slot page tables, ``decode_step_slots_paged`` as the draft
    step. Context enters via a head-less chunk prefill at the moment a
    request joins decode (``begin_slot``); after every verify the
    engine's position vector is the single source of truth, so the
    draft cache's rejected-tail garbage self-heals exactly like the
    target's (each position is re-written the iteration it becomes
    current, before any mask admits it).

Drafts are DETERMINISTIC (argmax / lookup) by design: a point-mass
draft distribution makes the exact rejection-sampling acceptance rule
collapse to "sample from the target, accept while it equals the
draft" — which keeps sampled streams byte-identical to plain decode
(same per-request key stream, one split per emitted token) instead of
merely distribution-equivalent. See docs/serving.md §Speculative
decoding for the acceptance math.

TREE SPECULATION (tree-speculation PR): the engine can also drive
``propose_tree`` — a per-slot token TREE (SpecInfer/Medusa-style
multi-chain drafts) verified through ONE tree-masked window
(``models.decoding.verify_step_slots_paged(tree=)``). A tree raises
expected accepted-tokens-per-verify over a single chain exactly when
the chain's next token is AMBIGUOUS: several plausible continuations
exist and the linear draft can only bet on one. ``NgramDraft`` trees
branch on distinct historical continuations of the matched suffix
(top-m continuations hash-consed into a trie — one node per divergence
point); ``DraftModel`` trees are beam-style (the greedy chain plus the
per-step top-``width`` runner-up tokens as single-node side branches).
Every ``DraftSource`` gets trees for free via the default
``propose_tree`` (its linear chain laid out as a width-1 tree — the
engine's ``spec_tree`` A/B and the byte-identity oracle hook).

Host-sync discipline: ``propose``/``propose_tree`` and the tree
helpers below run INSIDE the serving iteration (a speculative
iteration is synchronous by design — the verify fetch is its
sanctioned sync), so they are a ``tools/lint_host_sync.py`` zone: no
``jax.device_get``/``block_until_ready``/``float(<traced>)``. The
draft-model step's per-step ``np.asarray`` fetch is the sources'
sanctioned medium (drafting is host-driven by design).
"""

from __future__ import annotations

from typing import Dict, Optional

import jax
import numpy as np

__all__ = ["DraftSource", "NgramDraft", "DraftModel", "tree_ancestors",
           "build_token_tree"]


def tree_ancestors(parents: np.ndarray):
    """Host-side tree derivation: parent-index vectors ``[S, W]``
    (node 0 = root; ``parents[s, 0] = -1``; unused nodes carry -1) ->
    ``(depth [S, W] int32, anc [S, W, W] bool, n_nodes [S] int64)``.
    ``anc[s, i, j]`` is True iff node j is i or an ancestor of i —
    the verify window's visibility mask; ``depth`` is each node's
    root-path position offset; ``n_nodes`` counts root + used nodes
    (the page-lookahead span: the forward writes window columns
    ``t .. t + n_nodes - 1``). Parents must be topologically ordered
    (``parents[s, j] < j``) — the tree builders guarantee it."""
    parents = np.asarray(parents, np.int64)
    s_n, w_len = parents.shape
    depth = np.zeros((s_n, w_len), np.int32)
    anc = np.zeros((s_n, w_len, w_len), bool)
    anc[:, 0, 0] = True
    rows = np.arange(s_n)
    for j in range(1, w_len):
        p = parents[:, j]
        used = p >= 0
        pc = np.where(used, p, 0)
        anc[:, j] = np.where(used[:, None], anc[rows, pc], False)
        anc[rows, j, j] = used
        depth[:, j] = np.where(used, depth[rows, pc] + 1, 0)
    n_nodes = (parents >= 0).sum(axis=1) + 1
    return depth, anc, n_nodes


def build_token_tree(chains, toks_row: np.ndarray,
                     parents_row: np.ndarray, max_nodes: int) -> int:
    """Merge candidate continuation ``chains`` (iterable of int token
    sequences, best first) into one slot's tree arrays: shared
    prefixes hash-cons onto one node — the trie of continuations, one
    branch per divergence point — under a ``max_nodes`` draft-node
    budget (later chains truncate first: insertion order is priority
    order). ``toks_row[0]`` (the pending input/root) is the caller's;
    returns the number of draft nodes used."""
    index = {}
    nxt = 1
    cap = min(int(max_nodes), len(toks_row) - 1)
    for chain in chains:
        par = 0
        for tokv in chain:
            key = (par, int(tokv))
            nid = index.get(key)
            if nid is None:
                if nxt > cap:
                    break
                nid = nxt
                nxt += 1
                index[key] = nid
                toks_row[nid] = int(tokv)
                parents_row[nid] = par
            par = nid
    return nxt - 1


class DraftSource:
    """Interface the serving engine drives. Implementations fill a
    fixed ``[S, k]`` draft buffer per iteration; all hooks are
    host-side calls on the engine thread (no locking needed).

    ``begin_slot`` returns False when the source cannot draft for this
    request (e.g. its own KV pool is dry) — the engine then disables
    speculation for THAT request and admission proceeds untouched:
    drafting is an accelerator, never a gate."""

    def bind(self, engine) -> None:
        """Called once from ``ServingEngine.__init__`` with the owning
        engine (slot count, max_len, spec_k are known here)."""

    def begin_slot(self, slot: int, context: np.ndarray) -> bool:
        """A request joined the decode batch in ``slot`` with
        ``context`` tokens already in the TARGET cache (prompt, plus
        generated[:-1] after a preemption resume). Returns whether this
        source can draft for the slot."""
        return True

    def end_slot(self, slot: int) -> None:
        """The slot's request left decode (finish/preempt/cancel).
        Must be tolerant of slots never begun."""

    def propose(self, requests: Dict[int, object], tok: np.ndarray,
                t: np.ndarray, out: np.ndarray,
                active: np.ndarray) -> None:
        """Fill ``out[slot, :k]`` with draft tokens continuing after
        ``tok[slot]`` (the slot's pending decode input at position
        ``t[slot]``) for every slot with ``active[slot]``.
        ``requests`` maps slot -> Request (token history access).
        Rows left untouched are harmless — inactive slots' drafts are
        force-rejected in the verify program."""
        raise NotImplementedError

    def propose_tree(self, requests: Dict[int, object], tok: np.ndarray,
                     t: np.ndarray, toks: np.ndarray,
                     parents: np.ndarray, active: np.ndarray,
                     depth: np.ndarray, width: np.ndarray,
                     max_nodes: np.ndarray) -> None:
        """Fill per-slot token TREES for a tree-masked verify window.
        ``toks``/``parents`` are ``[S, W]``; node 0 (the root) already
        holds the pending input with parent -1, and every unused node
        must keep parent -1. For each active slot the source may use
        up to ``max_nodes[slot]`` draft nodes shaped by the engine's
        adaptive per-stream ``depth[slot]`` (longest chain) and
        ``width[slot]`` (branches per divergence point) — parents must
        stay topologically ordered (``parents[s, j] < j``).

        The default lays the source's LINEAR proposal out as a width-1
        root path, so every ``DraftSource`` speculates through the
        tree window unchanged (the engine's byte-identity oracle
        hook); branching sources override."""
        k = toks.shape[1] - 1
        buf = np.zeros((toks.shape[0], k), np.int32)
        self.propose(requests, tok, t, buf, active)
        cols = np.arange(k)
        use = active[:, None] & (
            cols[None, :] < np.minimum(depth, max_nodes)[:, None])
        toks[:, 1:] = np.where(use, buf, 0)
        parents[:, 1:] = np.where(use, cols[None, :], -1)


class NgramDraft(DraftSource):
    """Prompt-lookup self-drafting: suffix-match over each stream's own
    prompt + generated tokens.

    For suffix lengths ``max_ngram`` down to ``min_ngram``, find the
    most recent EARLIER occurrence of the stream's current suffix and
    propose the ``k`` tokens that followed it (preferring an occurrence
    with a full ``k``-token continuation). No weights, no device work —
    the proposal is a numpy scan over at most ``max_context`` recent
    tokens. Streams whose continuation never re-occurs get filler
    drafts that the verify step rejects; the engine's acceptance EMA
    then disables speculation for them."""

    def __init__(self, max_ngram: int = 3, min_ngram: int = 1,
                 max_context: int = 4096):
        if not 1 <= min_ngram <= max_ngram:
            raise ValueError(
                f"need 1 <= min_ngram <= max_ngram, got "
                f"{min_ngram}/{max_ngram}")
        if max_context < max_ngram + 1:
            raise ValueError(
                f"max_context ({max_context}) must exceed max_ngram")
        self.max_ngram = int(max_ngram)
        self.min_ngram = int(min_ngram)
        self.max_context = int(max_context)

    def _context(self, req) -> np.ndarray:
        """The capped lookup context (prompt + generated, most recent
        ``max_context`` tokens). Slices BEFORE concatenating: the cap
        must bound the per-iteration host copy too, not just the scan
        — at long prompts the full-history concat was the hot-loop
        cost. Shared by the linear and tree proposals so the bound
        stays in one place."""
        cap = self.max_context
        gen = req.generated[-cap:]
        head = req.prompt[-max(0, cap - len(gen)):] \
            if len(gen) < cap else req.prompt[:0]
        return np.concatenate([head, np.asarray(gen, np.int32)])

    def propose(self, requests, tok, t, out, active):
        k = out.shape[1]
        for slot, req in requests.items():
            if not active[slot]:
                continue
            out[slot] = self.lookup(self._context(req), k)

    def lookup(self, ctx: np.ndarray, k: int) -> np.ndarray:
        """The k-token proposal continuing ``ctx`` (which ends with the
        pending decode input). Zeros when no suffix re-occurs — filler
        the verify step will reject."""
        buf = np.zeros(k, np.int32)
        n_hi = min(self.max_ngram, len(ctx) - 1)
        for n in range(n_hi, self.min_ngram - 1, -1):
            suffix = ctx[-n:]
            # candidate starts 0 .. len-1-n: every hit has at least one
            # continuation token; the suffix's own occurrence (start
            # len-n) is excluded by construction
            win = np.lib.stride_tricks.sliding_window_view(ctx[:-1], n)
            hits = np.flatnonzero((win == suffix).all(axis=1))
            if not hits.size:
                continue
            # most recent occurrence, preferring one whose continuation
            # covers the full k tokens (periodic streams: the last
            # overlapping hit may sit too close to the end)
            full = hits[hits + n + k <= len(ctx)]
            i = int(full[-1] if full.size else hits[-1])
            cont = ctx[i + n:i + n + k]
            buf[:len(cont)] = cont
            if 0 < len(cont) < k:
                buf[len(cont):] = cont[-1]       # pad; tail likely rejects
            return buf
        return buf

    def continuations(self, ctx: np.ndarray, m: int):
        """The ``m`` most recent DISTINCT next tokens following the
        current suffix of ``ctx`` — the single-step branching
        primitive of the tree proposal: where :meth:`lookup` bets on
        ONE occurrence's whole continuation, this surfaces every way
        the matched suffix has historically continued (most recent
        first). Suffix lengths ``max_ngram`` down to ``min_ngram``;
        empty when nothing re-occurs."""
        if m < 1:
            return []
        n_hi = min(self.max_ngram, len(ctx) - 1)
        for n in range(n_hi, self.min_ngram - 1, -1):
            suffix = ctx[-n:]
            win = np.lib.stride_tricks.sliding_window_view(ctx[:-1], n)
            hits = np.flatnonzero((win == suffix).all(axis=1))
            if not hits.size:
                continue
            out = []
            for h in hits[::-1]:                 # most recent first
                tv = int(ctx[h + n])
                if tv not in out:
                    out.append(tv)
                    if len(out) >= m:
                        break
            return out
        return []

    def propose_tree(self, requests, tok, t, toks, parents, active,
                     depth, width, max_nodes):
        """Branching prompt-lookup: grow each stream's tree node by
        node, branching into the top-``width`` distinct historical
        continuations AT EVERY DIVERGENCE POINT — a node whose
        (context + root path) suffix has only ever continued one way
        gets one child; a suffix with disagreeing historical
        continuations gets up to ``width``. Depth-first along the
        most-recent continuation (the linear draft's exact chain is
        the tree's primary path), so a tight node budget spends
        itself on the primary chain before the alternates."""
        for slot, req in requests.items():
            if not active[slot]:
                continue
            self._grow(self._context(req), toks[slot], parents[slot],
                       int(depth[slot]), int(width[slot]),
                       int(max_nodes[slot]))

    def _grow(self, ctx, toks_row, parents_row, depth: int, width: int,
              max_nodes: int) -> int:
        """Tree growth over historical continuations; returns the
        number of draft nodes placed. Budget order: (1) the PRIMARY
        chain — the most-recent continuation at every node, i.e. the
        linear draft's exact bet — to full depth; (2) alternates
        SHALLOW-FIRST (a divergence near the root truncates the whole
        window when missed, so its coverage is worth the most), each
        alternate immediately extended by its own primary chain (the
        branch's aftermath is usually unambiguous — a bare one-token
        branch would waste the depth behind it). Each expansion
        re-runs the suffix scan on ``ctx`` extended by the node's
        root path, so deeper nodes condition on the branch taken;
        scans are bounded by ``max_nodes`` (≤ depth * width)."""
        from collections import deque
        cap = min(int(max_nodes), len(toks_row) - 1)
        if cap < 1 or depth < 1:
            return 0
        used = 0
        alternates = deque()

        def chain(par: int, path, depth_left: int):
            nonlocal used
            while depth_left > 0 and used < cap:
                ctx_ext = (np.concatenate(
                    [ctx, np.asarray(path, np.int32)]) if path else ctx)
                conts = self.continuations(ctx_ext, width)
                if not conts:
                    return
                for tv in conts[1:]:
                    alternates.append((par, list(path), tv, depth_left))
                used += 1
                nid = used
                toks_row[nid] = conts[0]
                parents_row[nid] = par
                par = nid
                path = path + [conts[0]]
                depth_left -= 1

        chain(0, [], depth)
        while alternates and used < cap:
            par, path, tv, depth_left = alternates.popleft()
            used += 1
            nid = used
            toks_row[nid] = tv
            parents_row[nid] = par
            chain(nid, path + [tv], depth_left - 1)
        return used


class DraftModel(DraftSource):
    """A small target-compatible LM drafting ``k`` greedy steps ahead
    through its own paged KV machinery.

    The draft pool is provisioned at ``bind`` time — by default at
    worst-case parity (``num_slots * ceil(max_len / page_len)`` pages),
    so draft-KV memory is a FIXED budget decided up front and the
    target pool's admission arithmetic never competes with drafting. A
    smaller explicit ``num_pages`` is allowed: ``begin_slot`` then
    allocates a slot's worst case eagerly and reports False when the
    draft pool is dry, which disables speculation for that request
    only — admission is never blocked on draft pages.

    The draft model must share the target's tokenizer/vocab (the
    proposals are target token ids); architecture and size are free —
    the win condition is ``k`` draft steps + one (k+1)-wide target pass
    beating ``acc + 1`` plain target steps."""

    def __init__(self, model, *, page_len: int = 16,
                 num_pages: Optional[int] = None, cache_dtype=None,
                 weights_dtype="auto"):
        from distkeras_tpu.models.core import Sequential
        module = model.module
        if not isinstance(module, Sequential):
            raise TypeError("DraftModel expects a Sequential LM "
                            f"(got {type(module).__name__})")
        from distkeras_tpu.models.decoding import (_attn_compute_dtype,
                                                   _resolve_head_dims,
                                                   _serving_params)
        self.model = model
        self.module = module
        _resolve_head_dims(module, model.params)
        compute_dt = _attn_compute_dtype(module)
        import jax.numpy as jnp
        if cache_dtype is None:
            cache_dtype = (compute_dt if compute_dt is not None
                           else jnp.float32)
        if weights_dtype == "auto":
            weights_dtype = compute_dt if (
                compute_dt is not None
                and compute_dt != jnp.dtype(jnp.float32)) else None
        self._params = (model.params if weights_dtype is None
                        else _serving_params(model.params, weights_dtype))
        self._state = model.state
        self._page_len = int(page_len)
        self._num_pages = num_pages
        self._cache_dtype = cache_dtype
        self.pool = None                     # built at bind()
        self._staging = None
        self._prefill_fns = {}               # length-keyed LRU, engine cap
        self._step_fns = {}                  # width -> jit draft step
        self._active = set()                 # slots with live draft KV
        #: slot -> (t0, [tokens]) — what the last draft round WROTE
        #: into the draft KV at positions t0.. (the greedy chain). The
        #: heal pass rewrites positions where the stream actually
        #: committed a DIFFERENT token (an accepted tree side branch);
        #: without it the draft cache silently diverges after the
        #: first non-primary acceptance and every later draft attends
        #: wrong-token KV (code-review finding, this PR).
        self._written = {}

    #: same LRU bound the engine uses for its ragged prefill programs
    MAX_PREFILL_PROGRAMS = 64

    def bind(self, engine) -> None:
        from distkeras_tpu.serving.kv_pool import PagedKVPool
        self.pool = PagedKVPool(self.module, engine.num_slots,
                                engine.max_len, page_len=self._page_len,
                                num_pages=self._num_pages,
                                dtype=self._cache_dtype)
        self._staging = self.pool.make_request_cache()

    def begin_slot(self, slot: int, context: np.ndarray) -> bool:
        import jax.numpy as jnp
        self.end_slot(slot)                  # tolerate re-begin
        pool = self.pool
        # eager worst-case allocation: the draft step never needs a
        # mid-decode growth path (and with the default parity sizing
        # this can never fail)
        pids = []
        for _ in range(pool.pages_per_slot):
            pid = pool.alloc_page()
            if pid is None:
                for p in pids:
                    pool.decref(p)
                return False                 # draft pool dry: no drafting
            pids.append(pid)
        for j, pid in enumerate(pids):
            pool.assign(slot, j, pid)
        n = len(context)
        fn = self._prefill_fn(n)
        self._staging = fn(self._params, self._state, self._staging,
                           jnp.asarray(np.asarray(context,
                                                  np.int32)[None]))
        pool.insert_pages(self._staging, slot, 0, n)
        self._active.add(slot)
        return True

    def end_slot(self, slot: int) -> None:
        if self.pool is not None and slot in self._active:
            self.pool.release_slot(slot)
            self._active.discard(slot)
        self._written.pop(slot, None)

    def _prefill_fn(self, n: int):
        """Head-less whole-context chunk prefill at batch 1 (the draft
        only ever needs cache entries, never logits). One program per
        context length, LRU-capped like the engine's. The staging
        cache (argument 2) is donated, as in every serving program."""
        fn = self._prefill_fns.pop(n, None)
        if fn is None:
            from distkeras_tpu.models.decoding import prefill_chunk_step
            module = self.module

            def f(params, state, cache, chunk):
                _, cache = prefill_chunk_step(module, params, state,
                                              cache, chunk, 0,
                                              final=False)
                return cache

            fn = jax.jit(f, donate_argnums=2)
        self._prefill_fns[n] = fn
        while len(self._prefill_fns) > self.MAX_PREFILL_PROGRAMS:
            self._prefill_fns.pop(next(iter(self._prefill_fns)))
        return fn

    def _decode_fn(self, width: int = 1):
        """Jitted draft step: argmax ids (``width`` 1) or the
        ``lax.top_k`` id matrix ``[S, width]`` (beam-style trees —
        column 0 is the argmax the greedy chain follows). One program
        per distinct width (the engine's per-request widths share the
        engine-level cap, so the set is tiny). The draft pool
        (argument 2) is donated: callers rebind ``pool.cache`` from
        the result."""
        fn = self._step_fns.get(width)
        if fn is None:
            from distkeras_tpu.models.decoding import \
                decode_step_slots_paged
            import jax.numpy as jnp
            from jax import lax
            module = self.module
            page_len = self.pool.page_len

            def fn(params, state, cache, tok, t, tables):
                logits, cache = decode_step_slots_paged(
                    module, params, state, cache, tok, t, tables,
                    page_len)
                if width == 1:
                    return jnp.argmax(logits, axis=-1), cache
                return lax.top_k(logits, width)[1], cache

            fn = self._step_fns[width] = jax.jit(fn, donate_argnums=2)
        return fn

    def _heal(self, requests, tok, t) -> None:
        """Rewrite draft-KV positions where the stream committed a
        token OTHER than the one the last draft round wrote there —
        the accepted side branch of a tree verify. The linear path is
        immune by construction (the accepted prefix IS the draft's
        own chain), so this almost always no-ops; after a non-primary
        acceptance it replays the actual accepted tokens through the
        ordinary draft step (correct rope, correct KV), bounded by
        the previous round's chain length. Runs batched over slots
        like ``_draft_steps``, inert slots at the sentinel."""
        import jax.numpy as jnp
        s_n = len(t)
        start = np.full(s_n, -1, np.int64)
        stop = np.zeros(s_n, np.int64)
        actual = {}
        for slot, req in requests.items():
            rec = self._written.get(slot)
            if slot not in self._active or rec is None:
                continue
            t0, chain = rec
            ctx = np.concatenate(
                [req.prompt, np.asarray(req.generated, np.int32)])
            hi = min(int(t[slot]), t0 + len(chain), len(ctx))
            d = t0
            while d < hi and chain[d - t0] == int(ctx[d]):
                d += 1
            if d < hi:
                start[slot] = d
                stop[slot] = hi
                actual[slot] = ctx
        if (start < 0).all():
            return
        fn = self._decode_fn(1)
        tables = self.pool.device_tables()
        n_heal = int((stop - np.maximum(start, 0)).max())
        for j in range(n_heal):
            pos = start + j
            live = (start >= 0) & (pos < stop)
            tt = np.where(live, pos, self.pool.max_len).astype(np.int32)
            cur = np.zeros(s_n, np.int32)
            for slot in actual:
                if live[slot]:
                    cur[slot] = int(actual[slot][pos[slot]])
            _, self.pool.cache = fn(self._params, self._state,
                                    self.pool.cache, jnp.asarray(cur),
                                    jnp.asarray(tt), tables)

    def _draft_steps(self, requests, tok, t, k: int, width: int):
        """Run ``k`` greedy draft steps feeding the argmax forward;
        returns the per-step ``[S, width]`` top-id matrices. Slots
        without live draft KV run at the inert sentinel so their
        writes drop and their garbage proposals stay inactive. Heals
        side-branch divergence from the previous round first, and
        records what this round writes for the next heal."""
        import jax.numpy as jnp
        self._heal(requests, tok, t)
        fn = self._decode_fn(width)
        tables = self.pool.device_tables()
        tt = np.where([s in self._active for s in range(len(t))],
                      t, self.pool.max_len).astype(np.int32)
        cur = np.asarray(tok, np.int32).copy()
        tops = []
        for _ in range(k):
            nxt, self.pool.cache = fn(self._params, self._state,
                                      self.pool.cache, jnp.asarray(cur),
                                      jnp.asarray(tt), tables)
            ids = np.asarray(nxt, np.int32)
            if ids.ndim == 1:
                ids = ids[:, None]
            tops.append(ids)
            cur = ids[:, 0].copy()
            tt = tt + 1
        for slot in self._active:
            self._written[slot] = (
                int(t[slot]),
                [int(tok[slot])] + [int(ids[slot, 0])
                                    for ids in tops[:-1]])
        return tops

    def propose(self, requests, tok, t, out, active):
        if not self._active:
            return
        tops = self._draft_steps(requests, tok, t, out.shape[1], 1)
        for j, ids in enumerate(tops):
            out[:, j] = ids[:, 0]

    def propose_tree(self, requests, tok, t, toks, parents, active,
                     depth, width, max_nodes):
        """Beam-style draft tree: the greedy chain carries the depth,
        and at every chain position the draft's top-``width`` runner-up
        tokens hang off as single-node side branches — the target gets
        ``width`` chances per divergence point at one extra verify
        column each, without the draft paying extra sequential
        steps."""
        if not self._active:
            return
        k = int(depth.max()) if depth.size else 0
        w = int(width.max()) if width.size else 1
        if k < 1:
            return
        tops = self._draft_steps(requests, tok, t, k, max(1, w))
        for slot in range(toks.shape[0]):
            if not active[slot] or slot not in self._active:
                continue
            d = int(depth[slot])
            wd = int(width[slot])
            greedy_chain = np.asarray(
                [tops[j][slot, 0] for j in range(d)], np.int32)
            chains = [greedy_chain]
            for j in range(d):
                for r in range(1, min(wd, tops[j].shape[1])):
                    chains.append(np.concatenate(
                        [greedy_chain[:j],
                         tops[j][slot, r:r + 1]]).astype(np.int32))
            build_token_tree(chains, toks[slot], parents[slot],
                             int(max_nodes[slot]))
