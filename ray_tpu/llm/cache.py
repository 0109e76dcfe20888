"""Paged KV cache: device arrays + host-side page allocator.

Reference analog: the vLLM engine the reference wraps for LLM serving
(python/ray/llm/_internal/serve/deployments/llm/vllm/vllm_engine.py) keeps
its paged KV cache in CUDA; here the cache is jax arrays of STATIC shape
living in HBM, XLA-friendly (no dynamic allocation inside jit), with all
paging decisions made host-side by a free-list allocator. What the arrays
are is the configuration's cache kind's affair (``llm/kinds``: K and V
rows in pages, one pair a layer group; one latent row; a third pool of an
indexer's keys; a state a slot beside pages and sums); the allocator, the
tables and the prefix cache here serve every kind.

Page 0 is reserved and never handed out: block tables are padded with 0,
so the gathers read it (under a mask). Nothing writes to it: rows that are
not tokens are dropped by the scatter or written nowhere by the loops of
slices (``kinds/paged.py``, ``kinds/latent.py``).
"""

from __future__ import annotations

from collections import OrderedDict
from dataclasses import dataclass
from functools import partial
from typing import Any, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np


@dataclass
class KVCache:
    """Device-side paged cache, the five pools the runner's programs
    take and return; a kind fills the fields its format has."""

    k: Any  # [L, num_pages, page_size, kv_heads, head_dim] (a group)
    v: Any  # None for a latent configuration: k holds its rows
    i: Any = None  # [L, num_pages, page_size, indexer_row]: an indexer's
    # [L, num_pages, strides a page, kv_heads, head_dim] float32: the
    # block layers' sums of keys (compressed keys' halves)
    c: Any = None
    # [linear layers, slots, heads, head_dim, head_dim] float32
    s: Any = None

    @property
    def num_pages(self) -> int:
        return jax.tree.leaves(self.k)[0].shape[1]

    @property
    def page_size(self) -> int:
        return jax.tree.leaves(self.k)[0].shape[2]


def window_group_pages(slots: int, window: int, page_size: int,
                       burst: int) -> int:
    """Pages of a window group's pool, the reserved page 0 among them:
    for every slot the window, a page (the window's edges fall inside
    pages) and a burst's rows."""
    return slots * -(-(window + page_size + burst) // page_size) + 1


@partial(jax.jit, donate_argnums=(0,))
def zero_slot_state(pool, slot):
    """The state pool with ``slot``'s states of every layer zeroed, in
    place (the pool is donated)."""
    return jax.lax.dynamic_update_slice_in_dim(
        pool, jnp.zeros((pool.shape[0], 1, *pool.shape[2:]), pool.dtype),
        slot, 1)


def init_kv_cache(cfg, num_pages, page_size: int, dtype=None,
                  slots: int = 0) -> KVCache:
    """The pools of the configuration's kind. ``num_pages``: an int for
    a one-group configuration, else one number a group of
    ``cfg.kv_groups``. ``slots``: the rows of the state pool, for a
    configuration with linear layers."""
    from . import kinds      # the kinds name KVCache

    return kinds.of(cfg).init_pools(cfg, num_pages, page_size,
                                    dtype or cfg.dtype, slots)


class PageAllocator:
    """Host-side free list over the cache's page pool (page 0 reserved).

    Pages are REFERENCE-COUNTED: prefix caching shares prompt pages
    across sequences (vLLM's automatic-prefix-caching page sharing), so
    ``free`` decrements and only a zero count returns the page to the
    free list."""

    def __init__(self, num_pages: int, page_size: int):
        if num_pages < 2:
            raise ValueError("need at least 2 pages (page 0 is reserved)")
        self.page_size = page_size
        self.num_pages = num_pages
        self._free: List[int] = list(range(num_pages - 1, 0, -1))
        self._refs: dict = {}

    @property
    def free_pages(self) -> int:
        return len(self._free)

    def pages_needed(self, n_tokens: int) -> int:
        return (n_tokens + self.page_size - 1) // self.page_size

    def can_allocate(self, n_tokens: int) -> bool:
        return self.pages_needed(n_tokens) <= len(self._free)

    def allocate(self, n_pages: int) -> List[int]:
        if n_pages > len(self._free):
            raise MemoryError(
                f"KV cache out of pages: want {n_pages}, "
                f"free {len(self._free)}")
        out = [self._free.pop() for _ in range(n_pages)]
        for p in out:
            self._refs[p] = 1
        return out

    def incref(self, page: int) -> None:
        if page not in self._refs:
            raise ValueError(f"incref on unallocated page {page}")
        self._refs[page] += 1

    def refcount(self, page: int) -> int:
        return self._refs.get(page, 0)

    def free(self, pages: List[int]) -> None:
        for p in pages:
            if not 0 < p < self.num_pages:
                raise ValueError(f"freeing invalid page {p}")
            if p not in self._refs:
                raise ValueError(f"double free of page {p}")
        for p in pages:
            self._refs[p] -= 1
            if self._refs[p] == 0:
                del self._refs[p]
                self._free.append(p)


class PrefixCache:
    """Content-addressed full prompt pages (ref: vLLM automatic prefix
    caching — --enable-prefix-caching). A page's key is the hash chain
    (parent key, the page's token ids), so a lookup walks the prompt's
    full pages and reuses the longest cached chain; reused pages are
    shared via the allocator's refcounts and their KV is NOT recomputed
    (chunked prefill starts past them). The cache holds one reference
    per cached page; eviction (LRU) releases it."""

    def __init__(self, allocator: PageAllocator):
        self._alloc = allocator
        self._pages: Dict[Any, int] = {}      # key -> page index
        self._lru: "OrderedDict[Any, None]" = OrderedDict()
        self._parent: Dict[Any, Any] = {}     # key -> parent key (0=root)
        self._children: Dict[Any, int] = {}   # cached children per key

    @staticmethod
    def page_keys(prompt, page_size: int) -> List[Any]:
        """Keys for each FULL page of the prompt (chained).

        SHA-256 over (parent digest + the page's token bytes), NOT the
        builtin hash(): these keys route one request's cached KV pages
        to other prompts, so a 64-bit (and PYTHONHASHSEED-dependent)
        hash collision silently serves a DIFFERENT prompt's KV — the
        same class of cross-request leak as vLLM's prefix-cache hash
        fix. Tokens pack as fixed-width int64 so no two token sequences
        share an encoding.

        The chain itself lives in serve/kv_router.py (stdlib-only, so
        handles/proxies can derive it without importing jax); this
        delegates so engines and routers can never drift apart."""
        from ..serve.kv_router import chained_page_keys

        return chained_page_keys(prompt, page_size)

    def __len__(self) -> int:
        return len(self._pages)

    def lookup(self, keys: List[Any]) -> List[int]:
        """Longest cached prefix chain: pages for keys[0..k), each
        increffed for the caller."""
        out: List[int] = []
        for key in keys:
            page = self._pages.get(key)
            if page is None:
                break
            self._alloc.incref(page)
            self._lru.move_to_end(key)
            out.append(page)
        return out

    def insert(self, keys: List[Any], pages: List[int]) -> None:
        """Register freshly-filled prompt pages; the cache takes one
        reference per NEW entry (a key already present keeps the
        existing page — identical content)."""
        parent = 0
        for key, page in zip(keys, pages):
            if key in self._pages:
                parent = key
                continue
            self._alloc.incref(page)
            self._pages[key] = page
            self._lru[key] = None
            self._parent[key] = parent
            if parent:
                self._children[parent] = self._children.get(parent, 0) + 1
            parent = key

    def evictable(self) -> int:
        """Pages only the cache holds (the reclaimable set)."""
        return sum(1 for p in self._pages.values()
                   if self._alloc.refcount(p) == 1)

    def evict(self, n_pages: int) -> int:
        """Release up to n_pages cache-only pages, LEAF pages first (a
        chain's root evicted first would strand its whole tail
        unreachable — lookups break at the first miss; vLLM evicts leaf
        blocks first for the same reason), LRU-ordered within leaves.
        Returns pages released."""
        released = 0
        progress = True
        while released < n_pages and progress:
            progress = False
            for key in list(self._lru):
                if released >= n_pages:
                    break
                if self._children.get(key, 0):
                    continue   # interior node: evict its leaves first
                page = self._pages[key]
                if self._alloc.refcount(page) != 1:
                    continue   # a live sequence still shares it
                self._alloc.free([page])
                del self._pages[key]
                del self._lru[key]
                parent = self._parent.pop(key, 0)
                if parent and parent in self._children:
                    self._children[parent] -= 1
                    if not self._children[parent]:
                        del self._children[parent]
                self._children.pop(key, None)
                released += 1
                progress = True
        return released

    def evict_for(self, n_tokens: int) -> None:
        """Evict until the allocator can serve n_tokens (best effort)."""
        while not self._alloc.can_allocate(n_tokens):
            if not self.evict(1):
                return


class SequenceTable:
    """Per-sequence page bookkeeping: block table rows handed to the
    jitted kernels (numpy host-side; copied to device per step)."""

    def __init__(self, max_seqs: int, max_pages_per_seq: int):
        self.block_tables = np.zeros((max_seqs, max_pages_per_seq), np.int32)
        # a slot's pages are the entries [first, n_pages): a window
        # group's sequence has given the ones before ``first`` back
        self.n_pages = np.zeros(max_seqs, np.int32)
        self.first = np.zeros(max_seqs, np.int32)
        # bumped on every mutation so the engine can cache the device copy
        self.version = 0

    def assign(self, slot: int, pages: List[int], first: int = 0) -> None:
        """``pages`` hold the positions from ``first * page_size`` on."""
        self.block_tables[slot, :] = 0
        self.block_tables[slot, first:first + len(pages)] = pages
        self.first[slot] = first
        self.n_pages[slot] = first + len(pages)
        self.version += 1

    def release_front(self, slot: int, upto: int) -> List[int]:
        """Take the slot's pages before entry ``upto`` out of the table
        (their entries read 0 again) and return them, for the allocator."""
        first = int(self.first[slot])
        upto = min(upto, int(self.n_pages[slot]))
        if upto <= first:
            return []
        pages = [int(p) for p in self.block_tables[slot, first:upto]]
        self.block_tables[slot, first:upto] = 0
        self.first[slot] = upto
        self.version += 1
        return pages

    def append_page(self, slot: int, page: int) -> None:
        idx = int(self.n_pages[slot])
        if idx >= self.block_tables.shape[1]:
            raise MemoryError(f"slot {slot}: sequence exceeds "
                              f"max_pages_per_seq={self.block_tables.shape[1]}")
        self.block_tables[slot, idx] = page
        self.n_pages[slot] = idx + 1
        self.version += 1

    def pages_of(self, slot: int) -> List[int]:
        return [int(p) for p in self.block_tables[
            slot, int(self.first[slot]):int(self.n_pages[slot])]]

    def clear(self, slot: int) -> None:
        self.block_tables[slot, :] = 0
        self.n_pages[slot] = 0
        self.first[slot] = 0
        self.version += 1
