"""Prefix/prompt cache over the paged KV pool (reference analogue:
vLLM's automatic prefix caching, SOSP '23 §4.3).

Prompt KV is cached at *page* granularity under a content hash CHAINED
over token ids: page ``i`` of a prompt hashes ``H(hash_of_page_{i-1} ||
tokens[i*ps:(i+1)*ps])``, so two prompts map to the same page hash iff
they agree on EVERY token up to and including that page. A lookup walks
the chain page by page and stops at the first miss — the matched run is
handed to :meth:`PagedKVCache.allocate_shared` as a block-table pointer
copy (refcount bump, no KV moved, no prefill compute), and only the
unmatched tail is prefilled.

Lifecycle is retain-on-release: when the last sequence referencing a
registered page frees it, the page is NOT returned to the free list —
it parks here, hash intact and KV warm, in an LRU order. Allocation
pressure reclaims parked pages oldest-hit-first (the cache never makes
the pool smaller, it only keeps otherwise-idle pages useful). Pages are
registered only once their KV is fully written (whole pages covered by
a finished prefill chunk), so a shared page is immutable by
construction: writers always append past the shared prefix into private
pages — copy-on-write where the "copy" is the tail allocation itself.

Everything here is host-side Python over page ids; the jitted engine
never sees the cache.
"""

from __future__ import annotations

import hashlib
from collections import OrderedDict
from typing import Dict, List, Optional, Sequence

from raytpu.inference.kv_cache import PagedKVCache
from raytpu.util.metrics import Counter

_hit_tokens_total = Counter(
    "raytpu_infer_prefix_hit_tokens_total",
    "Prompt tokens whose prefill was skipped via prefix-cache hits")
_lookups_total = Counter(
    "raytpu_infer_prefix_lookups_total",
    "Prefix-cache lookups (one per admitted request)")
_hits_total = Counter(
    "raytpu_infer_prefix_hits_total",
    "Prefix-cache lookups that matched at least one page")
_evictions_total = Counter(
    "raytpu_infer_prefix_evictions_total",
    "Cached prefix pages evicted under allocation pressure")


def _page_hash(prev: bytes, tokens: Sequence[int]) -> bytes:
    h = hashlib.blake2b(prev, digest_size=16)
    h.update(b"".join(int(t).to_bytes(8, "little", signed=True)
                      for t in tokens))
    return h.digest()


def chain_hashes(tokens: Sequence[int], page_size: int) -> List[bytes]:
    """Chain hashes for every FULL page of ``tokens``.

    Module-level so routers and the disaggregation plane can compute a
    prompt's page chain without holding a cache (the hashes depend only
    on the token ids and the page size, never on pool state) — a client
    and every replica therefore agree on the chain byte-for-byte.
    """
    out: List[bytes] = []
    prev = b"raytpu-prefix"
    for i in range(len(tokens) // page_size):
        prev = _page_hash(prev, tokens[i * page_size:(i + 1) * page_size])
        out.append(prev)
    return out


class PrefixCache:
    """Content-addressed index of full prompt pages in a PagedKVCache.

    Installs itself as the cache's *retainer*: ref-0 registered pages
    are parked here (reclaimable, LRU-evicted under pressure) instead
    of returning to the free list. One PrefixCache per PagedKVCache.
    """

    def __init__(self, cache: PagedKVCache):
        if cache.window is not None:
            raise ValueError(
                "no prefix cache over window layers: a prompt's window "
                "pages are given back as the window slides on, so a "
                "later prompt finds its prefix in the full pools only")
        if cache.state:
            raise ValueError(
                "no prefix cache over layers that keep a state: the state "
                "at a prefix's end is in none of its pages, and a sequence "
                "that took them would start behind a state nobody kept")
        self.cache = cache
        self.page_size = cache.page_size
        # chain hash -> page id holding that page's KV
        self._by_hash: Dict[bytes, int] = {}
        # page id -> its chain hash (reverse index for eviction)
        self._hash_of: Dict[int, bytes] = {}
        # ref-0 registered pages, least-recently-matched first
        self._lru: "OrderedDict[int, None]" = OrderedDict()
        # Bumped whenever the hash index changes, so a holder of an
        # old summary() can tell without walking the index.
        self.version = 0
        cache._retainer = self

    # ---- lookup / registration --------------------------------------

    def page_hashes(self, tokens: Sequence[int]) -> List[bytes]:
        """Chain hashes for every FULL page of ``tokens``."""
        return chain_hashes(tokens, self.page_size)

    def match(self, tokens: Sequence[int],
              max_pages: Optional[int] = None) -> List[int]:
        """Longest run of cached pages matching ``tokens`` from the
        start, capped at ``max_pages``. Touches hits in the LRU."""
        _lookups_total.inc()
        pages: List[int] = []
        for h in self.page_hashes(tokens):
            if max_pages is not None and len(pages) >= max_pages:
                break
            page = self._by_hash.get(h)
            if page is None:
                break
            pages.append(page)
        for page in pages:
            if page in self._lru:  # referenced pages aren't in the LRU
                self._lru.move_to_end(page)
        if pages:
            _hits_total.inc()
            _hit_tokens_total.inc(len(pages) * self.page_size)
        return pages

    def register(self, seq_id: str, tokens: Sequence[int],
                 covered_len: int) -> int:
        """Index every full page of ``tokens`` whose KV is fully
        written (``covered_len`` tokens cached so far). First writer
        wins on hash collision-by-content — a page already indexed
        under the same hash keeps its mapping and the duplicate page
        stays private. Returns pages newly registered."""
        table = self.cache.block_table(seq_id)
        added = 0
        for i, h in enumerate(self.page_hashes(tokens)):
            if (i + 1) * self.page_size > covered_len:
                break
            if h in self._by_hash:
                continue
            page = table[i]
            if page in self._hash_of:
                continue  # already registered under an earlier prompt
            self._by_hash[h] = page
            self._hash_of[page] = h
            added += 1
        self.version += added
        return added

    def adopt(self, pages: Sequence[int], hashes: Sequence[bytes]) -> int:
        """Index externally-filled pages (a streamed KV handoff) under
        pre-computed chain hashes. The caller must hold references on
        ``pages`` (a pin sequence) and have fully written their KV —
        adoption makes them matchable exactly like locally-prefilled
        pages, so when the pin is freed they park retained instead of
        returning to the free list. First writer wins, same as
        :meth:`register`: a hash already indexed keeps its mapping and
        the duplicate incoming page simply stays un-indexed (its pin
        release returns it to the free list). Returns pages adopted."""
        added = 0
        for page, h in zip(pages, hashes):
            if h in self._by_hash or page in self._hash_of:
                continue
            self._by_hash[h] = page
            self._hash_of[page] = h
            added += 1
        self.version += added
        return added

    def summary(self, max_entries: int = 1024) -> List[str]:
        """Compact digest list for router-side prefix matching: the
        first 8 bytes of each registered chain hash, hex-encoded.
        Truncation keeps probe payloads small; 64 bits of a blake2b
        chain digest leaves collisions negligible for routing (a wrong
        route costs one redundant prefill, never correctness). Capped
        at ``max_entries`` digests, insertion order (oldest first)."""
        out: List[str] = []
        for h in self._by_hash:
            out.append(h[:8].hex())
            if len(out) >= max_entries:
                break
        return out

    # ---- retainer protocol (driven by PagedKVCache) -----------------

    def retain(self, page: int) -> bool:
        """A page's refcount hit 0. Park it if registered; else decline
        (the cache returns it to the free list)."""
        if page not in self._hash_of:
            return False
        self._lru[page] = None
        self._lru.move_to_end(page)
        return True

    def activate(self, page: int) -> None:
        """A parked page is referenced again — stop tracking it for
        eviction (its KV is live, not reclaimable)."""
        self._lru.pop(page, None)

    def reclaimable(self) -> int:
        return len(self._lru)

    def reclaim(self, need: int) -> int:
        """Evict up to ``need`` parked pages LRU back to the free
        list, dropping their hash index entries."""
        freed = 0
        while freed < need and self._lru:
            page, _ = self._lru.popitem(last=False)
            h = self._hash_of.pop(page)
            self._by_hash.pop(h, None)
            self.cache._free.append(page)
            freed += 1
        if freed:
            _evictions_total.inc(freed)
        self.version += freed
        return freed

    # ---- introspection ----------------------------------------------

    def stats(self) -> Dict[str, float]:
        return {
            "registered_pages": len(self._by_hash),
            "reclaimable_pages": len(self._lru),
            "lookups": _lookups_total.value,
            "hits": _hits_total.value,
            "hit_tokens": _hit_tokens_total.value,
            "evictions": _evictions_total.value,
        }
