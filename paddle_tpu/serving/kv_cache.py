"""Block-paged KV cache — the physical memory manager behind the serving
engine, with refcounted pages and a radix prefix cache.

vLLM's PagedAttention memory model on TPU (arXiv:2604.15464): K/V live in
fixed-size pages drawn from one shared pool, a per-sequence page table
maps logical token positions to physical pages, and sequences of wildly
different lengths share the pool with at most page_size-1 slots of waste
each.  The pool is a single stacked array [L, P, page_size, H, hd]
(layer-major: the model's lax.scan over layers carries it whole and each
layer writes and reads its own [layer, page] blocks in place), bf16 by
default.

Which arrays there are is the served model's to say (``state=``, a list
of ``(name, shape, dtype, kind)``): kind ``"pages"`` has the physical
page on axis 1 and follows the one allocator — every such array is
indexed by the same page ids, so keys, values and whatever a model keeps
beside them (compressed keys for block selection) are allocated, shared,
copied on write, compacted and freed together; kind ``"slots"`` has the
batch row on axis 1 and holds one fixed state per in-flight sequence (a
recurrent layer's state).  A sequence is bound to its row slot when it is
allocated and released with its pages.  What a page holds per position is
the model's: per-head keys and values, or a **latent page** of latent
attention (``models/mla_sparse.py``), one row a position shared by every
head, in either kind; the manager sees the page axis alone.  Without ``state=`` the manager
holds the two pools of a model with ``num_heads`` equal heads.

Window pools (kind ``"window_pages"``: the pages of sliding-window
attention layers) have a page axis of their own size, their own free list
and their own table per sequence.  They are allocated and extended with
the sequence's other pages, position for position, but a page whose every
position lies behind the window is given back (``release_window``), so a
sequence holds a number of them that does not grow with its context.  A
released page's entry in the table stays where it was and is never read.
They take no part in the prefix cache, copy-on-write or ``defrag``: a
model that has them is served cold.

Allocation is chunk-granular: the engine's chunked-prefill scheduler
``allocate``s only a prompt's first chunk at admission and ``extend``s
the table as later chunks (and decode tokens) land, so a long prompt
holds exactly the pages its written tokens need — never a whole-prompt
reservation sitting idle while other requests starve.

Prefix reuse (the millions-of-users economics): chat traffic shares a
system prompt, and re-prefilling it per request burns FLOPs on K/V the
pool already holds.  Every page therefore carries a **refcount**, and a
**radix tree keyed on page-aligned token-ID prefixes** (one edge = one
FULL page of prompt tokens) indexes pages whose contents are a pure
function of their token prefix.  ``allocate_prefixed`` walks the tree
for the longest cached prefix of a new prompt, maps those pages into
the new sequence's table read-only (a refcount bump instead of prefill
FLOPs), and allocates fresh pages only from the first uncached token.
When the *whole* prompt is cached the final page is **copied on write**
(the one page the new sequence must write its last prompt token into)
so shared pages are never mutated.  Only full prompt pages ever enter
the tree: a partial final page keeps receiving decode writes and
mid-decode pages are owned by exactly one sequence, never shared.

Freeing decrements; a page returns to the free list only at refcount
zero.  Cached pages nobody references (tree-only, refcount 1) are
*evictable*: ``num_free_pages``/``occupancy()`` count them as free, so
a warm cache never trips the engine's occupancy watermark (no
RETRY_AFTER storm from cache residue), and allocation under pressure
transparently evicts least-recently-used zero-ref leaves before
failing.

Host-side bookkeeping (free list, page tables, radix tree) is plain
Python — it sits on the scheduler path, not the device path; the device
only ever sees the dense page arrays plus int32 tables the engine
builds per step.  Shared pages are read through the existing page-table
indirection — the ragged kernel needs no change.  The tree, refcount
map and prefix stats are read by telemetry scrape threads while the
scheduler mutates them, so they are lock-guarded (and annotated for the
lock-discipline pass).
"""
from __future__ import annotations

import hashlib
import heapq
import math
import threading

import jax.numpy as jnp

__all__ = ["PagedKVCache", "prefix_hashes", "window_pages_per_row"]

#: chain hash of the empty prefix (the radix root)
_ROOT_HASH = "radix-root"


def _chunk_hash(parent_hash, key):
    """Chain hash of one page-aligned token chunk appended to a prefix.

    Stable across processes (hashlib, not ``hash()``) — it is the wire
    identity of a cached prefix in the fleet gossip protocol: a router
    hashing a prompt's page chunks client-side can test membership
    against a replica's published radix summary without shipping token
    ids."""
    h = hashlib.blake2b(digest_size=8)
    h.update(parent_hash.encode("ascii"))
    h.update(",".join(str(int(t)) for t in key).encode("ascii"))
    return h.hexdigest()


def prefix_hashes(token_ids, page_size, max_pages=64):
    """Chain hashes of the page-aligned prefixes of ``token_ids``.

    ``prefix_hashes(t, ps)[i]`` identifies the prefix ``t[:(i+1)*ps]``
    and equals the ``chain_hash`` of the radix node any
    :class:`PagedKVCache` holds for that exact prefix — the client side
    of cache-aware routing: the deepest hash present in a replica's
    prefix summary is that replica's expected hit length."""
    out, h = [], _ROOT_HASH
    for i in range(min(len(token_ids) // page_size, max_pages)):
        key = token_ids[i * page_size:(i + 1) * page_size]
        h = _chunk_hash(h, key)
        out.append(h)
    return out


def window_pages_per_row(window, page_size, chunk_len):
    """Pages of a window pool one row can hold: the window behind its
    chunk's first token and the chunk, and one more for where they start
    in a page.  It does not grow with the context."""
    return -(-(window + chunk_len) // page_size) + 1


class _PrefixNode:
    """One radix-tree edge: one FULL page of prompt tokens.

    ``key`` is the page's token tuple, ``page`` the physical page id
    whose K/V encodes exactly the root→here token prefix,
    ``chain_hash`` the gossip identity of that prefix, ``last_used`` a
    logical LRU tick (clock-free: deterministic under injected engine
    clocks)."""

    __slots__ = ("key", "page", "parent", "children", "chain_hash",
                 "last_used")

    def __init__(self, key, page, parent, chain_hash, last_used):
        self.key = key
        self.page = page
        self.parent = parent
        self.children = {}
        self.chain_hash = chain_hash
        self.last_used = last_used


class PagedKVCache:
    """Page pool + per-sequence page tables with alloc/free/defrag,
    per-page refcounts and a radix prefix cache.

    The arrays (``arrays``, in the model's order; ``k_pages``/``v_pages``
    name the first two) are functional: jitted model steps take them as
    inputs and return updated copies; the engine assigns the results
    back.  Bookkeeping methods never touch the arrays except
    ``defrag`` (a gather), the copy-on-write path of
    ``allocate_prefixed`` (one page copy) and ``reset`` (a fill).
    """

    def __init__(self, *, num_pages, page_size, max_seq_len,
                 num_layers=None, num_heads=None, head_dim=None,
                 dtype=jnp.bfloat16, state=None):
        if num_pages <= 0 or page_size <= 0:
            raise ValueError("num_pages and page_size must be positive")
        self.num_pages = int(num_pages)
        self.page_size = int(page_size)
        self.max_pages_per_seq = math.ceil(max_seq_len / page_size)
        if state is None:
            shape = (num_layers, num_pages, page_size, num_heads, head_dim)
            state = [("k_pages", shape, dtype, "pages"),
                     ("v_pages", shape, dtype, "pages")]
        # name -> array, in the order the model's step takes them
        self.arrays = {}
        self._kinds = {}
        for name, shape, dt, kind in state:
            if kind not in ("pages", "slots", "window_pages"):
                raise ValueError(f"state {name!r}: kind {kind!r}")
            if kind == "pages" and shape[1] != self.num_pages:
                raise ValueError(f"state {name!r}: axis 1 of {shape} is "
                                 f"not the {self.num_pages} pages")
            self.arrays[name] = jnp.zeros(shape, dt)
            self._kinds[name] = kind
        slots = {self.arrays[n].shape[1] for n in self._of_kind("slots")}
        if len(slots) > 1:
            raise ValueError(f"slot states disagree on the rows: {slots}")
        self.num_slots = slots.pop() if slots else None
        self._slot_of = {}         # seq_id -> batch row, where bound
        window = {self.arrays[n].shape[1]
                  for n in self._of_kind("window_pages")}
        if len(window) > 1:
            raise ValueError(f"window pools disagree on the pages: {window}")
        self.num_window_pages = window.pop() if window else 0
        self._window_free = list(range(self.num_window_pages - 1, -1, -1))
        self._window_tables = {}   # seq_id -> [physical window page ids]
        self._window_first = {}    # seq_id -> logical pages given back
        self.window_pages_released = 0      # monotonic, for the counter
        # LIFO free list: recently-freed (still-warm) pages are reused first
        self._free = list(range(num_pages - 1, -1, -1))
        self._tables = {}          # seq_id -> [physical page ids]
        # scheduler thread vs telemetry scrapes (prefix_summary via
        # /fleet) race on the shared prefix structures — one re-entrant
        # lock serializes them (public methods lock, _locked helpers
        # assert the caller holds it)
        self._lock = threading.RLock()
        self._ref = {}             # page -> refcount  # guarded-by: self._lock
        self._radix = _PrefixNode((), None, None, _ROOT_HASH, 0)  # guarded-by: self._lock
        self._tree_pages = {}      # page -> its radix node  # guarded-by: self._lock
        # evictable pages (tree-held, refcount 1) are counted
        # incrementally — num_free_pages/occupancy sit on every
        # admission check and must not walk the tree
        self._evictable = 0        # guarded-by: self._lock
        # lazy min-heap of (last_used, seq, node) eviction candidates;
        # stale entries (touched/bumped/detached nodes) are skipped at
        # pop time, so eviction is O(log heap) not O(tree)
        self._evict_heap = []      # guarded-by: self._lock
        self._heap_seq = 0         # guarded-by: self._lock
        # monotonic counters for the serving_prefix_* metrics (the
        # engine syncs deltas each step)
        self._prefix_stats = {"hits": 0, "hit_tokens": 0,
                              "evictions": 0,
                              "inserted_pages": 0}  # guarded-by: self._lock
        self._tick = 0             # logical LRU clock

    # ------------------------------------------------------------- arrays
    def _of_kind(self, kind):
        return [n for n, k in self._kinds.items() if k == kind]

    @property
    def k_pages(self):
        return self.arrays["k_pages"]

    @k_pages.setter
    def k_pages(self, value):
        self.arrays["k_pages"] = value

    @property
    def v_pages(self):
        return self.arrays["v_pages"]

    @v_pages.setter
    def v_pages(self, value):
        self.arrays["v_pages"] = value

    def state_arrays(self):
        """Every pool, in the order the model's step takes them."""
        return tuple(self.arrays.values())

    def set_state(self, arrays):
        """The step's results, in the same order."""
        for name, a in zip(self.arrays, arrays, strict=True):
            self.arrays[name] = a

    def recurrent_state_bytes(self):
        """Bytes of the per-row (``"slots"``) state."""
        return sum(self.arrays[n].nbytes for n in self._of_kind("slots"))

    def slot_of(self, seq_id):
        return self._slot_of.get(seq_id)

    def _check_slot(self, seq_id, slot):
        """Before any page is taken: the row slot is there and unbound."""
        if slot is None:
            return
        if self.num_slots is not None and not 0 <= slot < self.num_slots:
            raise ValueError(f"seq {seq_id!r}: row slot {slot} outside the "
                             f"{self.num_slots} rows of state")
        if slot in self._slot_of.values():
            raise ValueError(f"seq {seq_id!r}: row slot {slot} is bound")

    # ------------------------------------------------------------ queries
    @property
    def num_free_pages(self):
        """Allocatable pages: the free list PLUS cached prefix pages no
        sequence references (refcount 1, tree-only) — those are evicted
        on demand, so a warm cache never looks like memory pressure."""
        with self._lock:
            return len(self._free) + self._evictable_locked()

    @property
    def num_used_pages(self):
        return self.num_pages - self.num_free_pages

    def occupancy(self):
        """Fraction of the pool in *hard* use (pages some sequence
        references), 0..1.  Evictable cached pages do not count — the
        watermark shedding reading this must not RETRY_AFTER traffic a
        one-page eviction would admit."""
        return self.num_used_pages / self.num_pages

    def pages_for(self, num_tokens):
        return math.ceil(num_tokens / self.page_size)

    def can_allocate(self, num_tokens):
        return self.pages_for(num_tokens) <= self.num_free_pages

    @property
    def num_used_window_pages(self):
        return self.num_window_pages - len(self._window_free)

    def _take_window_locked(self, seq_id, num_tokens):
        """Window pages for ``seq_id``'s table to cover ``num_tokens``
        (all or nothing): the ids, or None when the window pools cannot
        cover them.  [] for a model without window pools."""
        if not self.num_window_pages:
            return []
        need = self.pages_for(num_tokens) - len(
            self._window_tables.get(seq_id, ()))
        if need > len(self._window_free):
            return None
        return [self._window_free.pop() for _ in range(max(need, 0))]

    def _take_both_locked(self, seq_id, num_tokens, need):
        """``need`` pages and the window pages for ``seq_id`` to cover
        ``num_tokens``, from both groups or from neither: ``(pages, window
        pages)`` or None."""
        window = self._take_window_locked(seq_id, num_tokens)
        pages = None if window is None else self._take_pages_locked(need)
        if pages is None:
            self._window_free.extend(window or ())
            return None
        return pages, window

    def release_window(self, seq_id, first_position):
        """Give back ``seq_id``'s window pages whose every position is
        below ``first_position`` (the first one its window layers still
        read).  Their table entries stay and are never read again.
        Returns the number of pages released."""
        table = self._window_tables.get(seq_id)
        if table is None:
            return 0
        first = self._window_first[seq_id]
        upto = min(max(first_position, 0) // self.page_size, len(table))
        if upto <= first:
            return 0
        with self._lock:
            self._window_free.extend(table[first:upto])
            self._window_first[seq_id] = upto
            self.window_pages_released += upto - first
        return upto - first

    def seq_ids(self):
        return list(self._tables)

    # ------------------------------------------------------- alloc / free
    def allocate(self, seq_id, num_tokens, slot=None):
        """Reserve pages for a new sequence of num_tokens and bind it to
        batch row ``slot`` (whose per-row state the model's step zeroes
        when the sequence runs its first token).  Returns True on
        success; False (allocating nothing) when the pool can't cover
        the request — the engine's admission gate."""
        if seq_id in self._tables:
            raise ValueError(f"seq {seq_id!r} already allocated")
        self._check_slot(seq_id, slot)
        need = self.pages_for(num_tokens)
        if need > self.max_pages_per_seq:
            raise ValueError(
                f"seq {seq_id!r}: {num_tokens} tokens need {need} pages > "
                f"max_pages_per_seq {self.max_pages_per_seq}")
        with self._lock:
            taken = self._take_both_locked(seq_id, num_tokens, need)
            if taken is None:
                return False
            pages, window = taken
            if slot is not None:
                self._slot_of[seq_id] = slot
            self._tables[seq_id] = pages
            if self.num_window_pages:
                self._window_tables[seq_id] = window
                self._window_first[seq_id] = 0
        return True

    def extend(self, seq_id, num_tokens):
        """Grow seq_id's table to cover num_tokens total.  True on
        success; False (table unchanged) when the pool is exhausted —
        the engine then preempts.  Under pressure, zero-ref cached
        prefix pages are LRU-evicted before giving up."""
        table = self._tables[seq_id]
        need = self.pages_for(num_tokens) - len(table)
        if need <= 0:
            return True
        if len(table) + need > self.max_pages_per_seq:
            raise ValueError(
                f"seq {seq_id!r}: extend to {num_tokens} tokens exceeds "
                f"max_pages_per_seq {self.max_pages_per_seq}")
        with self._lock:
            taken = self._take_both_locked(seq_id, num_tokens, need)
            if taken is None:
                return False
            pages, window = taken
            table.extend(pages)
            if self.num_window_pages:
                self._window_tables[seq_id].extend(window)
        return True

    def free(self, seq_id):
        """Drop seq_id's references: each page's refcount is
        DECREMENTED, and only pages nobody else holds (no other table,
        no radix node) return to the pool.  Stale contents of truly
        freed pages are fine: pages are fully overwritten before they
        are ever read again."""
        with self._lock:
            for p in self._tables.pop(seq_id):
                self._release_page_locked(p)
            self._slot_of.pop(seq_id, None)
            first = self._window_first.pop(seq_id, 0)
            self._window_free.extend(
                self._window_tables.pop(seq_id, ())[first:])

    def reset(self):
        """Free everything — tables, prefix cache, refcounts — and zero
        the pool.  Prefix hit/eviction counters stay monotonic (they
        feed Prometheus counters)."""
        with self._lock:
            self._tables.clear()
            self._free = list(range(self.num_pages - 1, -1, -1))
            self._ref = {}
            self._radix = _PrefixNode((), None, None, _ROOT_HASH, 0)
            self._tree_pages = {}
            self._evictable = 0
            self._evict_heap = []
            self._slot_of.clear()
            self._window_free = list(range(self.num_window_pages - 1, -1,
                                           -1))
            self._window_tables.clear()
            self._window_first.clear()
            for name, a in self.arrays.items():
                self.arrays[name] = jnp.zeros_like(a)

    # --------------------------------------------------- locked internals
    def _release_page_locked(self, page):
        self._ref[page] -= 1
        count = self._ref[page]
        if count == 0:
            del self._ref[page]
            self._free.append(page)
        elif count == 1:
            node = self._tree_pages.get(page)
            if node is not None:      # tree-only now: became evictable
                self._evictable += 1
                if not node.children:
                    self._note_evictable_locked(node)

    def _bump_ref_locked(self, page):
        count = self._ref.get(page, 0)
        self._ref[page] = count + 1
        if count == 1 and page in self._tree_pages:
            self._evictable -= 1      # referenced again: no longer evictable

    def _note_evictable_locked(self, node):
        """Push ``node`` as an eviction candidate at its current
        ``last_used``.  Lazy: a later touch/bump/detach makes the entry
        stale, detected (and skipped) at pop time.  Compacts the heap
        when stale entries dominate so it stays O(tree)-sized."""
        self._heap_seq += 1
        heapq.heappush(self._evict_heap,
                       (node.last_used, self._heap_seq, node))
        if len(self._evict_heap) > 4 * (len(self._tree_pages) + 16):
            live = {}
            for entry in self._evict_heap:
                last_used, _, cand = entry
                if (cand.last_used == last_used and not cand.children
                        and self._tree_pages.get(cand.page) is cand
                        and self._ref.get(cand.page) == 1):
                    live[id(cand)] = entry
            self._evict_heap = sorted(live.values())

    def _take_pages_locked(self, need):
        """Pop ``need`` pages (refcount 1 each), LRU-evicting zero-ref
        cached prefixes as required.  None (nothing taken) when the
        pool genuinely can't cover it."""
        while len(self._free) < need:
            if not self._evict_one_locked():
                return None
        pages = [self._free.pop() for _ in range(need)]
        for p in pages:
            self._ref[p] = 1
        return pages

    def _evictable_locked(self):
        """Cached pages reclaimable by eviction: tree-held with no
        sequence reference.  A sequence referencing a node references
        every ancestor too, so refcount-1 tree pages always form
        evictable (leaf-first) subtrees.  Maintained incrementally on
        refcount 1<->2 transitions and insert/evict — this sits behind
        num_free_pages/occupancy on every admission check."""
        return self._evictable

    def _iter_nodes_locked(self):
        stack = list(self._radix.children.values())
        while stack:
            node = stack.pop()
            stack.extend(node.children.values())
            yield node

    def _evict_one_locked(self):
        """Evict the least-recently-used zero-ref LEAF node (leaf-only:
        an inner node's page is the prefix its cached descendants
        attend through).  Pops the lazy candidate heap, skipping stale
        entries.  Returns True when a page was reclaimed."""
        while self._evict_heap:
            last_used, _, victim = heapq.heappop(self._evict_heap)
            if (victim.last_used != last_used or victim.children
                    or self._tree_pages.get(victim.page) is not victim
                    or self._ref.get(victim.page) != 1):
                continue              # stale entry
            parent = victim.parent
            parent.children.pop(victim.key)
            del self._tree_pages[victim.page]
            self._evictable -= 1
            self._release_page_locked(victim.page)
            self._prefix_stats["evictions"] += 1
            # the parent may have just become an evictable leaf itself
            if (parent is not self._radix and not parent.children
                    and self._ref.get(parent.page) == 1):
                self._note_evictable_locked(parent)
            return True
        return False

    def _match_locked(self, token_ids):
        """Longest cached page-aligned prefix of token_ids: the radix
        walk.  Returns the node-chain pages (LRU-touched)."""
        self._tick += 1
        node, pages = self._radix, []
        for i in range(len(token_ids) // self.page_size):
            key = tuple(int(t) for t in
                        token_ids[i * self.page_size:
                                  (i + 1) * self.page_size])
            child = node.children.get(key)
            if child is None:
                break
            child.last_used = self._tick
            if not child.children and self._ref.get(child.page) == 1:
                # touch stales the old heap entry; re-arm at the new tick
                self._note_evictable_locked(child)
            pages.append(child.page)
            node = child
        return pages

    # ------------------------------------------------------- prefix reuse
    def allocate_prefixed(self, seq_id, token_ids, chunk_tokens, slot=None):
        """Admission with prefix reuse.

        Walks the radix tree for the longest cached page-aligned prefix
        of ``token_ids``, maps those pages into ``seq_id``'s new table
        read-only (refcount bump), and allocates fresh pages covering
        the first ``chunk_tokens`` uncached tokens — prefill starts at
        the first uncached token.  When the whole prompt is cached the
        match is capped at ``len(token_ids) - 1`` (the model must still
        run ≥1 token for logits) and the final page is **copied on
        write**: the copy receives the last prompt token's K/V, the
        shared original is never written.

        The matched chain is pinned (refcount-bumped) before fresh
        pages are taken, so allocation-pressure eviction can never
        reclaim the very pages being attached.  When a deep match would
        starve its own admission — the matched pages ARE most of the
        evictable pool — the match is shrunk a page at a time (each
        dropped page becomes evictable again), trading hit length for
        admissibility down to a cold admission.

        Returns the number of prompt tokens served from cache (0 = cold
        admission), or None — nothing allocated, no refcount moved —
        when the pool can't cover the request even after evicting every
        zero-ref cached page."""
        if seq_id in self._tables:
            raise ValueError(f"seq {seq_id!r} already allocated")
        if self.num_window_pages:
            raise ValueError(
                "prefix reuse with window pools: a cached prefix's window "
                "pages were given back as its sequence moved on, so it "
                "cannot be resumed; such a model is admitted cold")
        self._check_slot(seq_id, slot)
        n = len(token_ids)
        with self._lock:
            full_match = self._match_locked(token_ids)
            keep = len(full_match)
            while True:
                shared = full_match[:keep]
                cow_src = None
                if shared and len(shared) * self.page_size >= n:
                    # fully cached: COW the final page, re-run its last
                    # token
                    cow_src = shared[-1]
                    shared = shared[:-1]
                    matched = n - 1
                else:
                    matched = len(shared) * self.page_size
                cover = min(matched + max(1, int(chunk_tokens)), n)
                need = self.pages_for(cover)
                if need > self.max_pages_per_seq:
                    raise ValueError(
                        f"seq {seq_id!r}: {cover} tokens need {need} "
                        f"pages > max_pages_per_seq "
                        f"{self.max_pages_per_seq}")
                # PIN the matched chain (and the COW source) BEFORE
                # taking fresh pages: _take_pages_locked may LRU-evict
                # zero-ref tree leaves, and an unpinned match is exactly
                # such a leaf chain — without the bump, eviction could
                # free a matched page and hand it straight back as
                # "fresh" for this same sequence (one physical page at
                # two logical positions: prefill writes would corrupt
                # the cached prefix).
                pinned = list(shared)
                if cow_src is not None:
                    pinned.append(cow_src)
                for p in pinned:
                    self._bump_ref_locked(p)
                fresh = self._take_pages_locked(need - len(shared))
                if fresh is not None:
                    break
                for p in pinned:      # unwind this attempt: no
                    self._release_page_locked(p)  # refcount moved
                if keep == 0:
                    return None       # nothing allocated
                # a pinned match is unevictable, so a deep match can
                # starve its own admission — shrink it one page at a
                # time (the dropped tail becomes evictable again),
                # trading cache reuse for allocatable pages, down to a
                # cold admission before giving up
                keep -= 1
            if cow_src is not None:
                # one-page copy-on-write; cow page is fresh[0] (owned)
                dst = fresh[0]
                for name in self._of_kind("pages"):
                    a = self.arrays[name]
                    self.arrays[name] = a.at[:, dst].set(a[:, cow_src])
                # copy landed; the source keeps only its tree/table refs
                self._release_page_locked(cow_src)
            if slot is not None:
                self._slot_of[seq_id] = slot
            self._tables[seq_id] = shared + fresh
            if matched:
                self._prefix_stats["hits"] += 1
                self._prefix_stats["hit_tokens"] += matched
            return matched

    def insert_prefix(self, seq_id, token_ids):
        """Register ``seq_id``'s FULL prompt pages in the radix tree
        (each newly cached page gets a tree refcount).  Called by the
        engine when a prompt's prefill completes — from then on an
        identical prefix is a refcount bump instead of prefill FLOPs.
        The partial final page (if any) never enters the tree: decode
        keeps writing into it, and mid-decode pages are never shared.
        Returns the number of pages newly inserted."""
        table = self._tables.get(seq_id)
        if table is None:
            return 0
        added = 0
        with self._lock:
            self._tick += 1
            node = self._radix
            for i in range(len(token_ids) // self.page_size):
                key = tuple(int(t) for t in
                            token_ids[i * self.page_size:
                                      (i + 1) * self.page_size])
                child = node.children.get(key)
                if child is None:
                    page = table[i]
                    child = _PrefixNode(
                        key, page, node,
                        _chunk_hash(node.chain_hash, key), self._tick)
                    node.children[key] = child
                    # bump precedes tree entry: the inserting sequence's
                    # table already holds the page, so post-bump ref >= 2
                    # and the new node is never immediately evictable
                    self._ref[page] = self._ref.get(page, 0) + 1
                    self._tree_pages[page] = child
                    self._prefix_stats["inserted_pages"] += 1
                    added += 1
                else:
                    child.last_used = self._tick
                    if (not child.children
                            and self._ref.get(child.page) == 1):
                        # another sequence's since-freed page: the touch
                        # stales its heap entry, re-arm at the new tick
                        self._note_evictable_locked(child)
                node = child
        return added

    def prefix_stats(self):
        """Monotonic prefix-cache counters plus the live cached-page
        gauge — the engine's serving_prefix_* metrics source."""
        with self._lock:
            out = dict(self._prefix_stats)
            out["cached_pages"] = len(self._tree_pages)
        return out

    def prefix_summary(self, max_entries=32):
        """Bounded radix summary for fleet gossip: the ``chain_hash`` →
        cached-prefix-token-depth map of the ``max_entries`` most
        recently used nodes, plus the stats counters.  A router hashes
        an incoming prompt with :func:`prefix_hashes` and the deepest
        hash present here is this pool's expected hit length — token
        ids never leave the process, and the payload is bounded no
        matter how large the tree grows."""
        with self._lock:
            nodes = []
            stack = [(self._radix, 0)]
            while stack:
                node, depth = stack.pop()
                for child in node.children.values():
                    nodes.append((child, depth + 1))
                    stack.append((child, depth + 1))
            nodes.sort(key=lambda t: t[0].last_used, reverse=True)
            entries = {child.chain_hash: depth * self.page_size
                       for child, depth in nodes[:int(max_entries)]}
            stats = dict(self._prefix_stats)
            stats["cached_pages"] = len(self._tree_pages)
            stats["nodes"] = len(nodes)
        return {"page_size": self.page_size, "entries": entries,
                "stats": stats}

    def check_integrity(self):
        """Debug invariant sweep (tests): every page is exactly one of
        free/referenced, refcounts equal table + tree occurrences, the
        free list holds no duplicates, every page-indexed pool still has
        the allocator's pages and every per-row pool its rows, row slots
        are bound one to a sequence and released with its pages (and,
        where the model keeps per-row state, every sequence has one), the
        incremental evictable
        counter matches a full rescan, and every evictable leaf has a
        live entry in the eviction heap.  Raises AssertionError."""
        with self._lock:
            counts = {}
            for table in self._tables.values():
                for p in table:
                    counts[p] = counts.get(p, 0) + 1
            for node in self._iter_nodes_locked():
                counts[node.page] = counts.get(node.page, 0) + 1
            assert counts == self._ref, \
                f"refcount drift: counted {counts} vs {self._ref}"
            assert len(self._free) == len(set(self._free)), \
                "free list holds duplicates (double free)"
            assert not (set(self._free) & set(counts)), \
                "page both free and referenced"
            assert len(self._free) + len(counts) == self.num_pages, \
                "pages leaked: free + referenced != pool"
            for name in self._of_kind("pages"):
                assert self.arrays[name].shape[1] == self.num_pages, \
                    f"{name}: {self.arrays[name].shape} lost the page axis"
            slots = list(self._slot_of.values())
            assert len(slots) == len(set(slots)), \
                f"two sequences bound to one row slot: {self._slot_of}"
            assert set(self._slot_of) <= set(self._tables), \
                "a row slot outlived its sequence's pages"
            if self.num_slots is not None:
                for name in self._of_kind("slots"):
                    assert self.arrays[name].shape[1] == self.num_slots, \
                        f"{name}: {self.arrays[name].shape} lost its rows"
                assert set(self._slot_of) == set(self._tables), \
                    (f"sequences without a row of state: "
                     f"{set(self._tables) - set(self._slot_of)}")
            held = [p for sid, t in self._window_tables.items()
                    for p in t[self._window_first[sid]:]]
            assert len(held) == len(set(held)), \
                "a window page is in two tables"
            assert len(self._window_free) == len(set(self._window_free)), \
                "window free list holds duplicates (double free)"
            assert not (set(self._window_free) & set(held)), \
                "window page both free and held"
            assert len(self._window_free) + len(held) \
                == self.num_window_pages, \
                "window pages leaked: free + held != pool"
            assert set(self._window_tables) == (
                set(self._tables) if self.num_window_pages else set()), \
                "a sequence's window table outlived it (or never was)"
            for name in self._of_kind("window_pages"):
                assert self.arrays[name].shape[1] \
                    == self.num_window_pages, \
                    f"{name}: {self.arrays[name].shape} lost its pages"
            for page, node in self._tree_pages.items():
                assert node.page == page, \
                    f"tree-page map drift: {page} -> node.page {node.page}"
            evictable = sum(1 for p in self._tree_pages
                            if self._ref.get(p) == 1)
            assert evictable == self._evictable, \
                (f"evictable counter drift: counted {evictable} vs "
                 f"{self._evictable}")
            for node in self._iter_nodes_locked():
                if node.children or self._ref.get(node.page) != 1:
                    continue
                assert any(nd is node and lu == node.last_used
                           for lu, _, nd in self._evict_heap), \
                    f"evictable leaf (page {node.page}) missing from heap"

    # ---------------------------------------------------------- page table
    def page_table(self, seq_id, width=None):
        """seq_id's table padded with 0 to ``width`` (default
        max_pages_per_seq).  Pad entries are never read: attention masks
        by seq_len and writes are index-routed out of bounds first."""
        width = width or self.max_pages_per_seq
        table = self._tables[seq_id]
        return table + [0] * (width - len(table))

    def window_page_table(self, seq_id, width=None):
        """seq_id's window table padded with 0 to ``width``; the entries
        of pages given back are stale, and like the padding never read."""
        width = width or self.max_pages_per_seq
        table = self._window_tables[seq_id]
        return table + [0] * (width - len(table))

    def window_pages_held(self, seq_id):
        return len(self._window_tables[seq_id]) \
            - self._window_first[seq_id]

    # -------------------------------------------------------------- defrag
    def defrag(self):
        """Compact live pages into the low-index prefix of the pool.

        Long-running engines interleave alloc/free until the free list is
        scattered; compaction restores locality (sequential page ids DMA
        as one contiguous stream on TPU) and makes the pool's live set
        checkpointable as a prefix slice.  One gather per pool array.

        Refcount-aware: a page shared by several page tables (a cached
        prefix) — or held only by the radix tree — relocates exactly
        ONCE, and every referencing table plus its tree node is updated
        to the new id, so sequences sharing a prefix keep decoding
        token-identically across a defrag.  Returns pages moved."""
        with self._lock:
            order = []               # new physical slot -> old page id
            remap = {}               # old page id -> new page id
            for seq_id in self._tables:
                for old in self._tables[seq_id]:
                    if old not in remap:
                        remap[old] = len(order)
                        order.append(old)
            # cached-but-unreferenced prefix pages are live too: their
            # contents are the cache
            for node in self._iter_nodes_locked():
                if node.page not in remap:
                    remap[node.page] = len(order)
                    order.append(node.page)
            n_used = len(order)
            moved = sum(1 for old, new in remap.items() if old != new)
            if moved == 0:
                return 0
            order += [p for p in range(self.num_pages) if p not in remap]
            idx = jnp.asarray(order, jnp.int32)
            for name in self._of_kind("pages"):
                self.arrays[name] = jnp.take(self.arrays[name], idx, axis=1)
            self._tables = {sid: [remap[p] for p in t]
                            for sid, t in self._tables.items()}
            for node in self._iter_nodes_locked():
                node.page = remap[node.page]
            self._ref = {remap[p]: c for p, c in self._ref.items()}
            self._tree_pages = {remap[p]: nd
                                for p, nd in self._tree_pages.items()}
            self._free = list(range(self.num_pages - 1, n_used - 1, -1))
            return moved
