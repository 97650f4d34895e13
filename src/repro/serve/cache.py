"""The paged decode cache of the continuous-batching engine.

:class:`PagedCache`: attention K/V lives in a global pool of fixed-size
pages per layer (the serving-side dual of the paper's block-structured
weights), each request holds an ordered list of page ids (its *block
table*), a host-side free list hands pages out, and a ref-counted prefix
trie keyed on page-aligned prompt chunks lets requests that share a
prompt prefix reuse already-prefilled pages. Cached pages are immutable —
extending a shared prefix allocates fresh pages (copy-on-write without the
copy, since sharing is only ever whole-page). Recurrent layers
(mamba/rwkv) keep their O(1) state as a single pinned page per slot, so
the engine treats all block families uniformly.

Sharding: leaves are placed via ``repro.dist`` logical-axis rules
(``Model.paged_cache_axes()``) when a mesh is active — KV heads shard as
usual; the page axis stays unsharded (pages are fetched by id).
"""

from __future__ import annotations

import math
from typing import Any, Dict, List, Optional, Tuple

import jax
import numpy as np

from repro.dist import sharding as sh

NULL_PAGE = 0


class PagePool:
    """Host-side page allocator: a free list plus per-page refcounts.

    Page 0 is the reserved null page (never handed out): block-table
    entries past a request's used depth point at it, so device scatters
    and gathers always hit a valid pool index. A page is *free* when its
    refcount is 0; holders are requests (one ref per block-table entry
    naming it) and the prefix trie (one ref per cached node).
    """

    def __init__(self, n_pages: int):
        if n_pages < 2:
            raise ValueError(f"pool needs >= 2 pages (null + 1), got {n_pages}")
        self.n_pages = n_pages
        self.ref = np.zeros(n_pages, np.int32)
        self.ref[NULL_PAGE] = 1                    # permanently pinned
        self._free = list(range(n_pages - 1, 0, -1))   # pop() -> lowest id

    @property
    def free_count(self) -> int:
        return len(self._free)

    @property
    def allocated_count(self) -> int:
        """Pages currently held by at least one owner (excluding null)."""
        return (self.n_pages - 1) - len(self._free)

    def alloc(self) -> int:
        if not self._free:
            raise RuntimeError("page pool exhausted")
        pid = self._free.pop()
        assert self.ref[pid] == 0, pid
        self.ref[pid] = 1
        return pid

    def retain(self, pid: int) -> None:
        assert pid != NULL_PAGE and self.ref[pid] > 0, pid
        self.ref[pid] += 1

    def release(self, pid: int) -> None:
        assert pid != NULL_PAGE and self.ref[pid] > 0, pid
        self.ref[pid] -= 1
        if self.ref[pid] == 0:
            self._free.append(pid)


class PrefixTrie:
    """Ref-counted prefix cache keyed on page-aligned prompt chunks.

    A node is a *full* page of prompt tokens, keyed by the whole token
    prefix it completes (hashable tuple) — matching walks page by page and
    stops at the first miss, so an entry is only reachable while all its
    ancestors are cached; eviction is therefore leaf-first (LRU among
    nodes no longer extended by another cached node, tracked by a
    per-node child count so the evictable scan is linear, not quadratic).
    The trie holds one pool ref per node: a page whose only holder is the
    trie (ref == 1) is *evictable*; pages also held by a live request are
    not.

    Keys store the full prefix per node — O(depth²·page_size) ints for a
    deep chain — which is fine at serving-bench scale; a parent-linked
    layout (``(parent_id, page_tokens)`` keys) is the upgrade path if
    multi-thousand-page prompts ever matter.

    Shared mode (speculative decoding): construct with a *sequence* of
    pools and each node holds one page id per pool (the trie is keyed on
    tokens; per-model pools hold the pages). Node values are then tuples
    — draft and target share one prefix cache, hit or evicted as a unit —
    and a node is evictable only when every pool's ref is trie-only.
    Single-pool construction keeps the original int-valued API.
    """

    def __init__(self, pool, page_size: int):
        self.pools: Tuple[PagePool, ...] = tuple(pool) \
            if isinstance(pool, (list, tuple)) else (pool,)
        self.pool = self.pools[0]                 # back-compat alias
        self.page_size = page_size
        # token prefix -> page id (single pool) / per-pool page ids (shared)
        self.nodes: Dict[Tuple[int, ...], Any] = {}
        self._tick = 0
        self._last_use: Dict[Tuple[int, ...], int] = {}
        self._n_children: Dict[Tuple[int, ...], int] = {}

    def _as_tuple(self, value) -> Tuple[int, ...]:
        return value if isinstance(value, tuple) else (value,)

    def is_reclaimable(self, value) -> bool:
        """True when a node's only holder, in *every* pool, is the trie."""
        return all(pool.ref[pid] == 1
                   for pool, pid in zip(self.pools, self._as_tuple(value)))

    def __len__(self) -> int:
        return len(self.nodes)

    def match(self, prompt: np.ndarray, max_pages: int,
              touch: bool = True) -> List[int]:
        """Longest cached page-aligned prefix of ``prompt`` (read-only —
        refs are taken by the caller). Capped at ``max_pages`` so at least
        one prompt token is always left to compute (the engine needs the
        last-token logits to sample). ``touch=False`` is the capacity
        probe: it must not bump LRU recency (a blocked queue head re-probes
        every step and would otherwise pin its own prefix hot)."""
        ps = self.page_size
        toks = tuple(int(t) for t in prompt[: max_pages * ps])
        pages: List[int] = []
        if touch:
            self._tick += 1
        for j in range(max_pages):
            key = toks[: (j + 1) * ps]
            if len(key) < (j + 1) * ps or key not in self.nodes:
                break
            pages.append(self.nodes[key])
            if touch:
                self._last_use[key] = self._tick
        return pages

    def insert(self, prompt: np.ndarray, page_index: int, pid) -> bool:
        """Cache page ``page_index`` of ``prompt`` (must be full and
        prefilled). ``pid`` is an int (single pool) or a per-pool tuple
        (shared mode). Takes one ref per pool on insert; no-op if already
        cached."""
        key = tuple(int(t) for t in prompt[: (page_index + 1) * self.page_size])
        if key in self.nodes:
            return False
        pids = self._as_tuple(pid)
        assert len(pids) == len(self.pools), (pids, len(self.pools))
        self.nodes[key] = pid
        parent = key[:-self.page_size]
        if parent in self.nodes:
            self._n_children[parent] = self._n_children.get(parent, 0) + 1
        for pool, p in zip(self.pools, pids):
            pool.retain(p)
        self._tick += 1
        self._last_use[key] = self._tick
        return True

    def evictable(self) -> List[Tuple[int, Tuple[int, ...]]]:
        """(last_use, key) of evictable leaves: trie-only refs (ref == 1 in
        every pool), not extended by another cached node (per-node child
        counts keep this scan linear in cached nodes)."""
        return [(self._last_use[key], key)
                for key, pid in self.nodes.items()
                if self.is_reclaimable(pid)
                and not self._n_children.get(key)]

    def evict_one(self):
        """Drop the LRU evictable leaf, freeing its page(s). Returns the
        node value — page id (single pool) / per-pool tuple (shared),
        now back on the free list(s) — or None."""
        cands = self.evictable()
        if not cands:
            return None
        _, key = min(cands)
        pid = self.nodes.pop(key)
        self._last_use.pop(key, None)
        self._n_children.pop(key, None)
        parent = key[:-self.page_size]
        if parent in self._n_children:
            self._n_children[parent] -= 1
            if not self._n_children[parent]:
                del self._n_children[parent]
        for pool, p in zip(self.pools, self._as_tuple(pid)):
            pool.release(p)
        return pid

    def evictable_count(self) -> int:
        return len(self.evictable())

    def reclaimable_count(self) -> int:
        """Pages (per pool) the trie could hand back via *cascading* leaf
        eviction: every trie-only (ref == 1 in all pools) node. Strictly
        larger than :meth:`evictable_count` for deep chains — a 15-page
        chain has one evictable leaf but 15 reclaimable pages, and
        ``_alloc_page``'s evict-per-allocation loop does drain it leaf by
        leaf. (A ref==1 parent can never hide a ref>1 child: matching
        retains every ancestor, so request refs are upward-closed along a
        chain.)"""
        return int(sum(1 for pid in self.nodes.values()
                       if self.is_reclaimable(pid)))


class PagedCache:
    """Owns the device-side paged caches, the host-side block tables, the
    page allocator, and the prefix trie.

    The engine drives it host-side: :meth:`can_admit` /
    :meth:`admit_request` at admission, :meth:`publish_prefix` as prefill
    chunks land (pages become reusable only once their K/V is actually
    written), :meth:`ensure_decode_page` before decode steps, and
    :meth:`free_slot` at eviction. Deadlock-freedom: admission reserves the
    request's worst-case page count (prompt + ``max_new_tokens``), decode
    pages materialize lazily against that reservation, so an admitted
    request can always run to completion.
    """

    def __init__(self, model, n_slots: int, max_len: int, *,
                 page_size: int = 16, n_pages: Optional[int] = None,
                 dtype=None, slack_tokens: int = 0):
        self.model = model
        self.n_slots = n_slots
        self.max_len = max_len
        self.page_size = page_size
        # slack_tokens: speculative decoding scatters a k-token window past
        # the accepted depth, so a slot can transiently need pages beyond
        # prompt + max_new_tokens; the slack widens the block table and the
        # per-request reservation so the window never outruns capacity
        self.slack_tokens = slack_tokens
        self.max_pages = math.ceil((max_len + slack_tokens) / page_size)
        if n_pages is None:
            # dense-equivalent capacity + the null page
            n_pages = n_slots * self.max_pages + 1
        self.n_pages = n_pages
        self.dtype = dtype
        # position of this cache's page ids inside shared-trie node tuples
        # (see share_trie); 0 and int-valued nodes while the trie is private
        self._trie_slot = 0

        caches = model.init_paged_caches(n_slots, n_pages, page_size, dtype)
        mesh, rules = sh.current()
        if mesh is not None and rules is not None:
            placements = sh.tree_shardings(mesh, rules,
                                           model.paged_cache_axes(),
                                           like=caches)
            caches = jax.device_put(caches, placements)
        self.caches = caches
        self.pool = PagePool(n_pages)
        self.trie = PrefixTrie(self.pool, page_size)
        # host-authoritative block tables; device copies are sliced views
        # pushed on demand (see Engine._block_tables_dev)
        self.block_tables = np.zeros((n_slots, self.max_pages), np.int32)
        self.dirty = True
        self.reserved = 0                       # promised-but-unallocated
        self._slot_reserved = [0] * n_slots
        # prefix caching needs every admitted token's K/V to live in pages;
        # recurrent state cannot be reconstructed from a matched prefix
        self.prefix_cache_enabled = all(
            s["kind"] in ("attn", "attn_moe") for s in model.block_specs)
        # degradation ladder: at the flush_prefix stage the engine stops
        # publishing new prefixes (and has flushed the trie); correctness
        # is unchanged — misses just recompute
        self.publish_enabled = True
        # fault-injection seam (site "pool_exhaust"): when armed, the
        # injector *withholds* pages from available() — pure admission
        # pressure, never a failed allocation, so allocator bookkeeping
        # stays exact under any schedule
        self.injector = None

        # bytes accounting (attention K/V only — recurrent state is a
        # fixed size per slot whatever the depth)
        page_bytes = 0
        for spec, c in zip(model.block_specs, self.caches):
            if spec["kind"] in ("attn", "attn_moe"):
                for leaf in (c["kp"], c["vp"]):
                    page_bytes += leaf.nbytes // n_pages
        self.page_bytes = page_bytes
        self.token_bytes = page_bytes / page_size if page_size else 0.0
        self.dense_reserved_bytes = int(n_slots * max_len * self.token_bytes)

    # ------------------------------------------------------------ accounting
    def kv_bytes_allocated(self) -> int:
        return self.pool.allocated_count * self.page_bytes

    def pages_for(self, n_tokens: int) -> int:
        return math.ceil(n_tokens / self.page_size)

    def available(self) -> int:
        """Pages obtainable right now: free-list plus trie pages
        reclaimable by cascading leaf eviction, minus outstanding
        reservations. Counting only *currently evictable* leaves here
        would under-report deep cached chains and livelock admission
        (can_admit refusing forever what _alloc_page could satisfy)."""
        avail = (self.pool.free_count + self.trie.reclaimable_count()
                 - self.reserved)
        if self.injector is not None:
            avail -= self.injector.withheld_pages()
        return avail

    # ------------------------------------------------------------- admission
    def _match_nodes(self, prompt: np.ndarray, touch: bool = True) -> List[Any]:
        """Trie node values (page id, or per-pool tuple in shared mode)
        for the longest cached prefix."""
        if not self.prefix_cache_enabled or len(prompt) <= self.page_size:
            return []
        # never match the *entire* prompt: the engine must compute at least
        # one token to read last-token logits
        cap = (len(prompt) - 1) // self.page_size
        return self.trie.match(prompt, cap, touch=touch)

    def _own_pid(self, node_value) -> int:
        return node_value[self._trie_slot] \
            if isinstance(node_value, tuple) else node_value

    def _match(self, prompt: np.ndarray, touch: bool = True) -> List[int]:
        return [self._own_pid(v) for v in self._match_nodes(prompt, touch)]

    def can_admit(self, prompt_len: int, max_new_tokens: int,
                  prompt: Optional[np.ndarray] = None) -> bool:
        matched = self._match_nodes(prompt, touch=False) \
            if prompt is not None else []
        total = self.pages_for(prompt_len + max_new_tokens
                               + self.slack_tokens)
        # matched pages whose only holder is the trie are counted in
        # available() as evictable, but admission pins them (retain) —
        # they are consumed capacity, not free capacity
        pinned = sum(1 for v in matched if self.trie.is_reclaimable(v))
        return total - len(matched) + pinned <= self.available()

    def _alloc_page(self) -> int:
        if self.pool.free_count == 0:
            if self.trie.evict_one() is None:
                raise RuntimeError(
                    "page pool exhausted with nothing evictable — "
                    "admission reservation accounting is broken")
        return self.pool.alloc()

    def admit_request(self, slot: int, prompt: np.ndarray,
                      max_new_tokens: int) -> int:
        """Build the slot's block table: reuse trie-matched prefix pages
        (retained per-request), allocate fresh pages for the rest of the
        prompt, and reserve the worst-case decode pages. Returns the number
        of prefix tokens whose prefill is skipped."""
        matched = self._match(prompt)
        for pid in matched:
            self.pool.retain(pid)
        n_prompt_pages = self.pages_for(len(prompt))
        row = self.block_tables[slot]
        row[:] = NULL_PAGE
        for j, pid in enumerate(matched):
            row[j] = pid
        for j in range(len(matched), n_prompt_pages):
            row[j] = self._alloc_page()
        total = self.pages_for(len(prompt) + max_new_tokens
                               + self.slack_tokens)
        n_res = total - n_prompt_pages
        self.reserved += n_res
        self._slot_reserved[slot] = n_res
        self.dirty = True
        return len(matched) * self.page_size

    # -------------------------------------------------------------- runtime
    def publish_prefix(self, prompt: np.ndarray, slot: int,
                       upto_tokens: int, from_tokens: int = 0) -> None:
        """Insert the slot's *full, already-prefilled* prompt pages (tokens
        ``[from_tokens, upto_tokens)``) into the prefix trie so later
        requests can share them. Idempotent; partial pages are never
        published (decode may still write into the last prompt page).
        ``from_tokens`` (the pre-chunk prefill position) keeps per-chunk
        publishing O(chunk): pages before it are already cached (matched
        prefix or an earlier chunk's publish) — re-keying the whole prefix
        per chunk would be quadratic in prompt length on the host."""
        if not self.prefix_cache_enabled or not self.publish_enabled:
            return
        assert len(self.trie.pools) == 1, \
            "shared trie: publish via publish_prefix_shared"
        n_full = min(upto_tokens, len(prompt)) // self.page_size
        row = self.block_tables[slot]
        for j in range(from_tokens // self.page_size, n_full):
            self.trie.insert(prompt, j, int(row[j]))

    def ensure_decode_page(self, slot: int, write_pos: int) -> None:
        """Make sure the page covering ``write_pos`` exists in the slot's
        table, drawing on the slot's reservation when it must allocate."""
        j = write_pos // self.page_size
        if self.block_tables[slot, j] == NULL_PAGE:
            self.block_tables[slot, j] = self._alloc_page()
            self.reserved -= 1
            self._slot_reserved[slot] -= 1
            self.dirty = True

    def pages_used(self, slot: int, kv_len: int) -> int:
        """Block-table width needed to cover ``kv_len`` cached tokens."""
        return min(self.pages_for(max(kv_len, 1)), self.max_pages)

    def rollback(self, slot: int, keep_tokens: int) -> int:
        """Truncate the slot's block table to the pages covering
        ``keep_tokens`` accepted tokens, releasing materialized pages past
        them (the speculative-decode rejection path — host-side bookkeeping
        only; device K/V there is garbage that the next window re-scatters
        anyway). Only private decode pages can live past the accepted depth
        — publishing covers full *prompt* pages and the accepted depth
        never retreats below the prompt — so every release actually frees.
        Freed pages return to the slot's reservation
        (:meth:`ensure_decode_page` re-draws on it). Returns the number of
        pages released."""
        keep_pages = self.pages_for(max(keep_tokens, 0))
        row = self.block_tables[slot]
        n = 0
        for j in range(keep_pages, self.max_pages):
            pid = int(row[j])
            if pid != NULL_PAGE:
                self.pool.release(pid)
                row[j] = NULL_PAGE
                n += 1
        if n:
            self.reserved += n
            self._slot_reserved[slot] += n
            self.dirty = True
        return n

    def free_slot(self, slot: int) -> None:
        """Release the slot's page refs (trie-cached pages persist for
        reuse; private pages return to the free list) and drop its
        remaining reservation."""
        row = self.block_tables[slot]
        for pid in row[row != NULL_PAGE]:
            self.pool.release(int(pid))
        row[:] = NULL_PAGE
        self.reserved -= self._slot_reserved[slot]
        self._slot_reserved[slot] = 0
        self.dirty = True

    def flush_trie(self) -> int:
        """Degradation-ladder stage 2: cascade-evict every reclaimable
        trie node, returning trie-only pages to the free list(s). Pages
        also held by a live request keep that request's refs — only the
        trie's own holds drop, so block tables and conservation are
        untouched. With a shared trie one flush drains both pools (nodes
        hold a page per pool). Returns the number of nodes evicted."""
        n = 0
        while self.trie.evict_one() is not None:
            n += 1
        return n

    def preempt_slot(self, slot: int) -> int:
        """Preemptively evict a *live* slot: drop the request's refs on its
        pages and its outstanding reservation, exactly like a finish-time
        :meth:`free_slot` — the distinction is semantic (the request will
        come back) and observable: trie-shared pages survive (the trie
        holds its own ref; only this request's ref drops), so when the
        preempted request is re-admitted its published prefix is a trie
        hit and re-prefill is cheap. Returns the number of page refs
        dropped (the requeued request's admission sees exactly this much
        capacity returned, minus what stays pinned by the trie)."""
        n = int((self.block_tables[slot] != NULL_PAGE).sum())
        self.free_slot(slot)
        return n


# --------------------------------------------------------------- shared trie

def share_trie(caches: List[PagedCache]) -> PrefixTrie:
    """Replace each cache's private trie with ONE shared, token-keyed trie
    whose nodes hold a page id per cache's pool — speculative decoding's
    prefix cache: draft and target hit (and are evicted) as a unit, so a
    trie hit is counted once and never leaves the two pools disagreeing
    about which prefixes are cached. Call right after construction, before
    any admission."""
    ps = caches[0].page_size
    assert all(c.page_size == ps for c in caches), "page_size must match"
    trie = PrefixTrie([c.pool for c in caches], ps)
    for i, c in enumerate(caches):
        assert len(c.trie) == 0, "share_trie must run before any publish"
        c.trie = trie
        c._trie_slot = i
    return trie


def publish_prefix_shared(caches: List[PagedCache], prompt: np.ndarray,
                          slot: int, upto_tokens: int,
                          from_tokens: int = 0) -> None:
    """Shared-trie counterpart of :meth:`PagedCache.publish_prefix`: insert
    the slot's full, already-prefilled prompt pages as joint (per-pool)
    nodes. All caches must have prefilled the same token range into the
    same slot before this runs."""
    if not all(c.prefix_cache_enabled and c.publish_enabled for c in caches):
        return
    trie = caches[0].trie
    assert all(c.trie is trie for c in caches), "caches must share one trie"
    ps = trie.page_size
    n_full = min(upto_tokens, len(prompt)) // ps
    for j in range(from_tokens // ps, n_full):
        pids = tuple(int(c.block_tables[slot, j]) for c in caches)
        trie.insert(prompt, j, pids)
