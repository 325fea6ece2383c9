"""mx.pages — block-granular paged KV cache with prefix reuse.

mx.serve's cache manager. A dense (slots, H, bucket, D) cache per layer
owns memory per slot at the bucket's full length, and two requests
sharing a system prefix each recompute and store it. This module is the
vLLM/PagedAttention answer (PAPERS.md 2309.06180) adapted to this
runtime:

  * **PagePool** — the KV store is one pooled (pages, H, page_size, D)
    array per layer (or, for a latent cache, (pages, page_size, width)
    arenas of two widths side by side); a request owns a LIST of
    fixed-size pages instead of a dense span. Pages are refcounted: the prefix tree and every
    request sharing a block hold one reference each, and a page returns
    to the free list when the last reference drops. The pool is sized
    once at server construction and priced through mx.memsafe at
    every admission (`Server._admit_budget` / `_exec_peak`).
  * **PrefixTree** — a content-hashed radix tree over FULL prompt
    blocks (SGLang-style radix cache). A finished prefill inserts its
    full prompt pages; a later request walks its prompt block-by-block
    and starts mid-cache with the matched pages mapped read-only into
    its page table (refcount bumped — prefill work is skipped, not
    copied). Hash collisions are harmless: every node stores its block
    tokens and parent digest, and a lookup verifies both before
    trusting the digest.
  * **copy-on-write** — a request never writes a page it does not own
    exclusively. When its first write position lands INSIDE a shared
    page (a fully-matched prompt recomputes its last token to get
    logits), the page is copied into a fresh one at admission
    (`PagePool.copy_page`) and the shared reference dropped.
  * **eviction** — under page pressure the server evicts tree-held
    pages LRU-leaf-first (`PrefixTree.evict`); a page still referenced
    by a running request survives until that request drains. Freed
    pages go straight back to the pool — the "pages reclaimed" half of
    the serve degradation ladder, now at page granularity.

  * **page classes** — a model whose layers do not all keep the same
    rows (sliding-window layers beside full ones) names a CLASS for each
    cache stream by its window (`ServingSpec.windows`). Each class is an
    allocator of its own (`PageClass`: page count, free list, refcounts)
    over arenas of its own, and a request holds a page table per class.
    The class that keeps everything (window None) is the pool itself, as
    before; a window class (`PagePool.windows[w]`) is sized for what the
    slots can hold inside their windows, its pages are taken as a
    request's position reaches them and returned once every row of a
    page lies more than `w` behind it (`serve.Server`). Position ->
    table index is `pos // page_size` in every class, no ring: a freed
    entry points at scratch and is never walked.

Layout invariant: page id `p` addresses physical row `p` in EVERY
pooled array of its class — all layers, K and V, and (when a drafter
serves speculative decoding) the drafter's arrays too. One allocator,
one refcount, one page table per request and class covers the class's
part of the model stack. Pages `0..scratch-1` of every class are
per-slot scratch: masked-out lanes of a batched step write there so
real pages are never polluted.
"""
from __future__ import annotations

import collections
import hashlib
import heapq
import itertools

import numpy as np

__all__ = ["PagePool", "PageClass", "PrefixTree", "PagesExhausted"]


class PagesExhausted(RuntimeError):
    """The pool cannot satisfy an allocation — admission control's
    signal to walk the degradation ladder (tree eviction, shrink,
    evict-and-requeue), never a device OOM. `PagePool.alloc` raises it;
    `serve.Server._seat` returns one as its refusal."""

    def __init__(self, need, free):
        self.need = int(need)
        self.free = int(free)
        super().__init__(
            f"page pool exhausted: need {need} pages, {free} free")


def _block_digest(parent, block_bytes):
    """Content hash of one prompt block, chained through the parent
    digest — the radix-tree node key. Collisions are tolerated (nodes
    verify tokens + parent on lookup), so the digest only has to be
    cheap and stable."""
    return hashlib.blake2b(parent + block_bytes, digest_size=16).digest()


# ---------------------------------------------------------------------------
# PagePool
# ---------------------------------------------------------------------------

class PageClass:
    """One allocator: refcounts and a free list over the pages
    `scratch .. num_pages - 1` of the arenas of one page class. Host-side
    metadata only, guarded by the owning Server's lock."""

    def __init__(self, data_pages, scratch_pages):
        if data_pages < 1:
            raise ValueError(
                f"a page class needs data_pages >= 1, got {data_pages}")
        self.scratch = int(scratch_pages)
        self.num_pages = self.scratch + int(data_pages)
        self.refcount = np.zeros(self.num_pages, np.int32)
        self.free = collections.deque(range(self.scratch, self.num_pages))
        self.stats = {"allocs": 0, "frees": 0, "cow_copies": 0,
                      "peak_used": 0}

    # -- accounting ------------------------------------------------------
    @property
    def data_pages(self):
        return self.num_pages - self.scratch

    def free_pages(self):
        return len(self.free)

    def used_pages(self):
        return self.data_pages - len(self.free)

    # -- alloc / refcount ------------------------------------------------
    def alloc(self, n):
        """Take `n` pages off the free list (refcount 1 each). Raises
        PagesExhausted — with the accounting — when the list is short;
        nothing is allocated partially."""
        if n > len(self.free):
            raise PagesExhausted(n, len(self.free))
        pages = [self.free.popleft() for _ in range(int(n))]
        for p in pages:
            self.refcount[p] = 1
        self.stats["allocs"] += len(pages)
        self.stats["peak_used"] = max(self.stats["peak_used"],
                                      self.used_pages())
        return pages

    def incref(self, page):
        if self.refcount[page] <= 0:
            raise RuntimeError(f"incref on free page {page}")
        self.refcount[page] += 1

    def decref(self, page):
        """Drop one reference; the page returns to the free list when
        the count reaches zero (its stale contents are harmless — every
        position is rewritten before the causal mask can see it)."""
        c = int(self.refcount[page])
        if c <= 0:
            raise RuntimeError(f"decref on free page {page}")
        self.refcount[page] = c - 1
        if c == 1:
            self.free.append(int(page))
            self.stats["frees"] += 1


class PagePool(PageClass):
    """Refcounted fixed-size KV pages over pooled per-layer arrays.

    `streams` maps a tag ('target', and 'draft' when a speculative
    drafter is attached) to a list of `(*lead, width, dtype)` specs — one
    per pooled array, in the order of the model's flat state
    (`models/_decode.ServingSpec.streams`). Every array is allocated as
    (pages, *lead, page_size, width) zeros: `(heads, head_dim, dtype)` is
    a key or value arena with a head axis (GPT: 2 * n_layers of them, K
    then V), `(width, dtype)` one row a token with none (a latent cache
    beside its indexer keys). Arenas of different shapes lie side by side
    under ONE allocator: page id p is physical row p in all of them. The
    last dimension is padded to the lane width where the paged kernels
    run (`pallas_ops.kv_page_write.arena_head_dim`), so that the arenas'
    default layout is the one the kernels read.

    The pool is itself the allocator of the class that keeps every row
    (`PageClass`, window None). `windows` maps a tag to one entry per
    stream, None or the stream's window, and `window_pages` each window
    to the data pages of its class: those streams' arenas have that
    class's page count and `self.windows[w]` is its allocator. Without
    `windows` there is one class and everything is as it was.

    Page-table metadata (refcounts, free list) lives host-side and is
    guarded by the owning Server's lock; the device arrays in
    `self.state[tag]` are threaded (donated) through the paged step
    executables by the scheduler thread only."""

    def __init__(self, page_size, data_pages, scratch_pages, streams,
                 windows=None, window_pages=None):
        if page_size < 1 or data_pages < 1:
            raise ValueError(
                f"PagePool needs page_size >= 1 and data_pages >= 1, got "
                f"{page_size}/{data_pages}")
        super().__init__(data_pages, scratch_pages)
        self.page_size = int(page_size)
        self.state = {}
        self._specs = {tag: list(specs) for tag, specs in streams.items()}
        self._windows = {tag: list((windows or {}).get(tag)
                                   or [None] * len(specs))
                         for tag, specs in self._specs.items()}
        self.windows = {
            w: PageClass(window_pages[w], scratch_pages)
            for w in sorted({w for ws in self._windows.values()
                             for w in ws if w is not None})}
        import jax.numpy as jnp
        from .pallas_ops.kv_page_write import arena_head_dim
        for tag, specs in self._specs.items():
            self.state[tag] = [
                jnp.zeros((self.class_of(w).num_pages, *lead,
                           self.page_size, arena_head_dim(width)), dt)
                for (*lead, width, dt), w in zip(specs, self._windows[tag])]

    def class_of(self, window):
        """The allocator of the class named by `window` (None: the pool)."""
        return self if window is None else self.windows[window]

    def pool_bytes(self):
        return sum(int(a.nbytes) for arrs in self.state.values()
                   for a in arrs)

    def copy_page(self, src):
        """Copy-on-write: allocate a fresh page and device-copy `src`'s
        row in every pooled array of this class (all tags — the drafter's
        K/V for a block must travel with the target's). Returns the new
        page id; the caller drops its shared reference on `src`."""
        (dst,) = self.alloc(1)
        for tag, arrs in self.state.items():
            self.state[tag] = [
                a.at[dst].set(a[src]) if w is None else a
                for a, w in zip(arrs, self._windows[tag])]
        self.stats["cow_copies"] += 1
        return dst


# ---------------------------------------------------------------------------
# PrefixTree
# ---------------------------------------------------------------------------

class _Node:
    __slots__ = ("digest", "parent", "block", "page", "children",
                 "last_used")

    def __init__(self, digest, parent, block, page, stamp):
        self.digest = digest
        self.parent = parent          # parent digest (b"" at the root)
        self.block = block            # the block's token bytes
        self.page = int(page)
        self.children = set()         # child digests
        self.last_used = stamp


class PrefixTree:
    """Content-hashed radix tree over full prompt blocks: digest(node) =
    blake2b(digest(parent) + block_tokens). Only FULL pages are shared —
    a partial tail block stays exclusively owned by its request (the
    "partial-block tail" rule the tests pin).

    The tree holds ONE pool reference per node; `match` bumps the
    refcount of every returned page (the caller owns those references),
    `insert` adopts a request's page into a new node (one more ref),
    and `evict` walks leaf nodes LRU-first, dropping the tree's
    reference so idle cached pages return to the pool under pressure."""

    def __init__(self, pool):
        self.pool = pool
        self.nodes = {}                         # digest -> _Node
        self.roots = set()                      # digests with parent b""
        self._stamp = itertools.count(1)        # deterministic LRU clock
        self.stats = {"hits": 0, "misses": 0, "matched_tokens": 0,
                      "inserted_pages": 0, "evicted_pages": 0}

    def __len__(self):
        return len(self.nodes)

    def _blocks(self, prompt):
        ps = self.pool.page_size
        prompt = np.asarray(prompt, np.int32)
        n_full = prompt.size // ps
        return [prompt[i * ps:(i + 1) * ps].tobytes()
                for i in range(n_full)]

    def match(self, prompt):
        """Walk the prompt's full blocks down the tree. Returns
        (pages, matched_tokens): the shared pages (refcount bumped —
        the caller now owns one reference each) covering the longest
        cached prefix. A digest hit whose stored tokens or parent
        disagree (hash collision) stops the walk — correctness never
        rests on the hash."""
        pages, parent = [], b""
        for block in self._blocks(prompt):
            digest = _block_digest(parent, block)
            node = self.nodes.get(digest)
            if node is None or node.block != block \
                    or node.parent != parent:
                break
            node.last_used = next(self._stamp)
            self.pool.incref(node.page)
            pages.append(node.page)
            parent = digest
        matched = len(pages) * self.pool.page_size
        if pages:
            self.stats["hits"] += 1
            self.stats["matched_tokens"] += matched
        else:
            self.stats["misses"] += 1
        return pages, matched

    def insert(self, prompt, pages):
        """Register a prefilled prompt's FULL blocks: `pages[i]` holds
        block i's K/V. Existing nodes are refreshed (their page stays
        authoritative — concurrent identical prefills do not
        duplicate); new nodes adopt the request's page with one more
        reference. Safe to call again after a requeue replay."""
        parent = b""
        for i, block in enumerate(self._blocks(prompt)):
            if i >= len(pages):
                break
            digest = _block_digest(parent, block)
            node = self.nodes.get(digest)
            if node is not None and (node.block != block
                                     or node.parent != parent):
                break                    # collision: stop registering
            if node is None:
                node = _Node(digest, parent, block, pages[i],
                             next(self._stamp))
                self.nodes[digest] = node
                if parent == b"":
                    self.roots.add(digest)
                else:
                    self.nodes[parent].children.add(digest)
                self.pool.incref(node.page)
                self.stats["inserted_pages"] += 1
            else:
                node.last_used = next(self._stamp)
            parent = digest

    def evict(self, need_free):
        """Drop tree references, LRU leaf first, until the pool has
        `need_free` free pages or no leaf remains. Returns the number of
        nodes evicted (a node whose page is still shared by a running
        request is evicted from the TREE but only returns to the pool
        when that request drains)."""
        if self.pool.free_pages() >= need_free:
            return 0
        # one pass over the nodes, then a heap: a parent joins it when its
        # last child goes (a tree of thousands of pages gives back a
        # document's hundreds in one admission)
        heap = [(n.last_used, n.digest) for n in self.nodes.values()
                if not n.children]
        heapq.heapify(heap)
        evicted = 0
        while heap and self.pool.free_pages() < need_free:
            victim = self.nodes[heapq.heappop(heap)[1]]
            parent = self.nodes.get(victim.parent)
            self._drop(victim)
            evicted += 1
            if parent is not None and not parent.children:
                heapq.heappush(heap, (parent.last_used, parent.digest))
        return evicted

    def clear(self):
        """Drop every tree reference (server shutdown)."""
        n = len(self.nodes)
        while self.nodes:
            leaves = [d for d, node in self.nodes.items()
                      if not node.children]
            for d in leaves:
                self._drop(self.nodes[d])
        return n

    def _drop(self, node):
        del self.nodes[node.digest]
        if node.parent == b"":
            self.roots.discard(node.digest)
        else:
            p = self.nodes.get(node.parent)
            if p is not None:
                p.children.discard(node.digest)
        self.pool.decref(node.page)
        self.stats["evicted_pages"] += 1
