"""Block-paged mixed-precision KV cache — the serving-time layout behind
continuous batching.

The contiguous cache (`serving/kvcache.py`) reserves ``max_seq`` tokens per
batch slot whether or not a request uses them; every decode step then streams
that full reservation through the attention reduction.  Here the cache is a
**pool of fixed-size pages** shared by all slots, indexed per request through
a block table, so

* HBM held per request is proportional to its *actual* length (rounded up to
  one page), and
* int4 nibble packing quadruples the tokens per HBM page vs bf16 — the
  "4.008-bit effective cache" (§B.2) becomes 4.008 bits of *allocated* HBM,
  not just of traffic.

Layout per attention stack (stacked over scan periods ``P``; quantization
reuses `kvcache.py`'s per-token quant + nibble packing bit-for-bit):

* **hi pool** — ``k_hi / v_hi``: ``(P, NH, bs, kv, hd)`` int8.  The first
  ``num_hi`` (=64) logical tokens of every sequence live here at 8 bits (the
  attention-sink region, §B.2); ``num_hi % bs == 0`` so a page is entirely
  hi or entirely lo.
* ``k_hi_scale / k_hi_zp / v_hi_*`` — ``(P, NH, bs, kv)`` float16
  per-token params, paged alongside their codes (a page is
  self-describing, so eviction / swap moves one contiguous unit).
* **lo pool** — ``k_lo / v_lo``: ``(P, NL, bs, kv·hd/2)`` int8 holding
  the bytes of two packed int4 nibbles along head_dim, one row of every
  kv head per token.
* ``lo_scale_zp`` — ``(P, NL, 2·⌈bs/4⌉, M)`` int16 holding the float16
  bits of the lo pages' per-token params (see :func:`lo_param_lanes`): for
  token ``4r + b`` row ``2r`` holds scales and row ``2r + 1`` zero points,
  at lane ``kv_index·4·kv + b·kv + head``; ``M`` is ``8·kv`` rounded up to
  128.

The lo pool is laid out for the paged-attention kernel
(`kernels/paged_attention.py`), which copies pages HBM → VMEM one at a
time: every lo array keeps a page contiguous with a minor dim that is a
multiple of 128 lanes (at the served widths), so the device stores it
page-major and unpadded, and a page copy is one aligned tile run.  (With a
narrow minor dim — ``hd/2`` or ``kv`` — the device stores such a pool
with pages on its minor axis, where no page can be copied alone.)  Paired
as 32-bit words, the params rows give the scale and zero point of four
tokens' worth of one head in the word row that holds those tokens' codes.
The kernel takes neither uint8 nor float16 arrays, hence the int8 and
int16 storage: the bytes are the uint8 nibbles and float16 values, and
only the values written or gathered are reinterpreted.

Page 0 of each pool is the **null page**: never handed out by the
allocator, and never *read unmasked*.  Block tables hold 0 for unmapped
logical blocks, and masked / pad / inactive-slot writes are routed there,
so neither reads nor scatters need a validity branch — but those routed
writes mean the null page accumulates stale quantized values; correctness
rests on every reader masking unmapped blocks by the slot length (which
all readers do), **not** on the page staying zero.

Block ids are shared across layers and periods (one allocation covers the
whole stack, vLLM-style), which keeps the allocator — a host-side numpy free
list — out of the jit'd step entirely: the engine turns (slot, position) into
(page, offset) arrays on the host and the device code only ever sees dense
int32 indices.

**Hybrid stacks (Mamba + attention)** add a second, *slot-dense* state
family next to the page pools: a Mamba layer's recurrent state is
fixed-size per request — one ``(heads, head_dim, ssm_state)`` f32 state
matrix plus a ``(conv_width - 1, conv_dim)`` bf16 conv tail — so it needs
no paging at all.  :func:`init_ssm_slots` allocates it per *slot*
(``num_slots + 1`` rows; the extra row is the **null slot**, the scatter
target for unused prefill chunk rows — the slot-indexed twin of the null
page).  Preemption swaps the per-slot state with the victim's pages
(`extract_pages` / `insert_pages` take the slot), so a hybrid resume is
bit-identical end to end: pages AND recurrence state restored exactly.
"""

from __future__ import annotations

import collections
import dataclasses
import hashlib
import heapq
import zlib
from typing import Callable, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.serving import kvcache as KV

Array = jax.Array


@dataclasses.dataclass(frozen=True)
class PagedCacheConfig:
    """Pool geometry.  ``quant`` carries the precision split (num_hi/bits)."""

    block_size: int = 16          # tokens per page
    num_lo_blocks: int = 64       # lo-pool pages (page 0 = null)
    num_hi_blocks: int = 16       # hi-pool pages (page 0 = null)
    max_blocks_per_seq: int = 16  # lo-table width (static decode grid)
    quant: KV.KVCacheConfig = KV.KVCacheConfig()

    def __post_init__(self):
        if self.quant.quantized and self.quant.num_hi % self.block_size:
            raise ValueError(
                f"num_hi={self.quant.num_hi} must be a multiple of "
                f"block_size={self.block_size} (pages are single-precision)")

    @property
    def hi_blocks_per_seq(self) -> int:
        if not self.quant.quantized:
            return 0
        return self.quant.num_hi // self.block_size

    @property
    def num_hi(self) -> int:
        return self.quant.num_hi if self.quant.quantized else 0


# ---------------------------------------------------------------------------
# pools
# ---------------------------------------------------------------------------


def init_pools(periods: int, kv_heads: int, head_dim: int,
               cfg: PagedCacheConfig) -> dict:
    """Zero page pools for one attention position in the period pattern."""
    bs = cfg.block_size
    if not cfg.quant.quantized:
        shape = (periods, cfg.num_lo_blocks, bs, kv_heads, head_dim)
        return {"k": jnp.zeros(shape, jnp.bfloat16),
                "v": jnp.zeros(shape, jnp.bfloat16)}
    nh, nl = cfg.num_hi_blocks, cfg.num_lo_blocks
    return {
        "k_hi": jnp.zeros((periods, nh, bs, kv_heads, head_dim), jnp.int8),
        "v_hi": jnp.zeros((periods, nh, bs, kv_heads, head_dim), jnp.int8),
        "k_lo": jnp.zeros((periods, nl, bs, kv_heads * head_dim // 2),
                          jnp.int8),
        "v_lo": jnp.zeros((periods, nl, bs, kv_heads * head_dim // 2),
                          jnp.int8),
        # f16 for the same exactness/traffic argument as the contiguous cache
        "k_hi_scale": jnp.zeros((periods, nh, bs, kv_heads), jnp.float16),
        "k_hi_zp": jnp.zeros((periods, nh, bs, kv_heads), jnp.float16),
        "v_hi_scale": jnp.zeros((periods, nh, bs, kv_heads), jnp.float16),
        "v_hi_zp": jnp.zeros((periods, nh, bs, kv_heads), jnp.float16),
        "lo_scale_zp": jnp.zeros((periods, nl) + lo_param_shape(bs, kv_heads),
                                 jnp.int16),
    }


def lo_param_shape(block_size: int, kv_heads: int) -> tuple:
    """Trailing shape of one lo page's ``lo_scale_zp``: two rows (scales,
    zero points) per four tokens, ``8·kv`` lanes rounded up to 128."""
    return 2 * (-(-block_size // 4)), -(-8 * kv_heads // 128) * 128


def lo_param_lanes(offsets: Array, kv: int, kv_heads: int) -> tuple:
    """Where the params of tokens at in-page ``offsets`` (n,) live in a lo
    page's ``lo_scale_zp``, for K (``kv`` 0) or V (1): the scale row (n,)
    (the zero point is the next row) and the lanes (n, kv_heads)."""
    lanes = (kv * 4 * kv_heads + (offsets % 4)[:, None] * kv_heads
             + jnp.arange(kv_heads)[None, :])
    return 2 * (offsets // 4), lanes


def _is_lo(name: str) -> bool:
    """Whether a pool array lives in the lo (or unquantized) page pool."""
    return name in ("k", "v") or "lo" in name.split("_")


def pool_bytes(entry: dict) -> int:
    return sum(int(a.size) * a.dtype.itemsize for a in entry.values())


# ---------------------------------------------------------------------------
# slot-dense SSM state pool (hybrid / pure-SSM stacks)
# ---------------------------------------------------------------------------


def init_ssm_slots(periods: int, num_slots: int, conv_width: int,
                   conv_dim: int, heads: int, head_dim: int,
                   state: int) -> dict:
    """Per-slot recurrent state for one Mamba position in the period
    pattern.  Unlike K/V, SSM state is **fixed-size per request** — one
    ``(heads, head_dim, state)`` matrix and a ``(conv_width - 1,
    conv_dim)`` conv tail — so it lives slot-dense, not paged.  Row
    ``num_slots`` (the last one) is the **null slot**: never assigned to a
    request, it absorbs the scatter from unused prefill chunk rows the way
    the null page absorbs masked K/V writes, so the unified step needs no
    validity branch on its state write either."""
    return {
        "state": jnp.zeros((periods, num_slots + 1, heads, head_dim, state),
                           jnp.float32),
        "conv": jnp.zeros((periods, num_slots + 1, conv_width - 1, conv_dim),
                          jnp.bfloat16),
    }


def is_ssm_entry(entry: dict) -> bool:
    return "state" in entry


def ssm_state_bytes_per_slot(pools: dict) -> int:
    """Fixed HBM bytes ONE slot pins across every Mamba layer (the
    admission-time cost of a hybrid request, independent of its length —
    the scheduler's slot gate is the capacity check for this family)."""
    total = 0
    for entry in pools.values():
        if not is_ssm_entry(entry):
            continue
        slots_axis = 1 if _ssm_has_periods(entry) else 0
        for arr in entry.values():
            total += (int(arr.size) // arr.shape[slots_axis]) * \
                arr.dtype.itemsize
    return total


def _ssm_has_periods(entry: dict) -> bool:
    """Scanned-period SSM entries are state ``(P, S+1, h, p, n)`` / conv
    ``(P, S+1, w-1, cd)``; prologue entries come period-stripped (one axis
    fewer) — mirror of :func:`_has_periods_axis` for the page pools."""
    return entry["state"].ndim == 5


# ---------------------------------------------------------------------------
# host-side page allocator
# ---------------------------------------------------------------------------


class OutOfBlocks(Exception):
    """Raised by the allocator; the scheduler turns it into preemption."""


class SwapCorruption(Exception):
    """A swapped-out page set failed its checksum at swap-in: the host copy
    was corrupted while the request sat preempted.  The restore is refused
    (pools untouched) — the engine fails that one request and keeps
    serving."""


#: root of the prefix-hash chain (the digest "before" page 0)
_PREFIX_ROOT = b""


def _prefix_digest(parent: bytes, tokens: np.ndarray) -> bytes:
    """Chain hash for one token-chunk-aligned page of prompt tokens:
    ``H(parent_digest || page_tokens)``.  The digest addresses the page's
    *entire prefix content*, not just its own tokens, so two pages holding
    equal tokens after different prefixes never collide — and an
    incremental walk over a prompt costs O(block_size) per page."""
    h = hashlib.sha256(parent)
    h.update(np.ascontiguousarray(tokens, np.int32).tobytes())
    return h.digest()


@dataclasses.dataclass
class PrefixMatch:
    """One prefix-cache hit: ``matched`` logical tokens [0, matched) are
    covered by the cached ``hi_pages`` / ``lo_pages`` (refs already
    acquired).  ``cow`` names the one *partially* covered page — ``(pool,
    index into that pool's list)`` — when ``matched`` is not a page
    multiple: the caller must copy that page before any write scatters
    into it (copy-on-write on the first divergent write)."""

    matched: int
    hi_pages: List[int]
    lo_pages: List[int]
    cow: Optional[tuple] = None      # ("hi"|"lo", list index) or None


@dataclasses.dataclass
class _CacheEntry:
    pool: str                        # "hi" | "lo"
    page: int
    tokens: np.ndarray               # the block_size prompt tokens it holds
    parent: bytes                    # parent digest in the chain


class BlockAllocator:
    """Ref-counted, hash-addressed page store over the hi and lo pools
    (host, deterministic).

    Page ids are handed out lowest-first (min-heap pop) so identical request
    streams produce identical placements (the engine-parity tests rely on
    this).  Page 0 of either pool is never allocated — it is the null page.
    Releasing page 0, an out-of-range id, or a page nobody holds raises
    ``ValueError`` (a real exception, not an ``assert`` stripped under
    ``python -O``); membership is tracked in set/dict mirrors so the check
    is O(1) per page.

    **Ref-counting + prefix cache** (vLLM-style prefix reuse): every
    allocated page carries a reference count (``alloc_* = 1``; ``acquire``
    adds a holder, ``release`` drops one).  Pages *registered* in the
    prefix cache (`register_prefix`) are addressed by the chain hash of
    the prompt tokens they hold; a later request with the same prompt
    prefix shares them (`lookup_prefix`) instead of re-allocating and
    re-prefilling.  A cached page whose ref count reaches zero is not
    freed — it parks in a per-pool LRU of **evictable** pages, still
    holding its quantized content for future hits, and is reclaimed
    lazily: ``alloc_*`` evicts the least-recently-used zero-ref cached
    page only once the true free list is empty.  ``can_allocate`` /
    ``all_free`` therefore count evictable pages as free-equivalent
    capacity (`flush_cache` evicts everything for tests that want exact
    free-list equality).

    ``fault`` is the deterministic fault-injection hook
    (`serving/faults.py`): a zero-arg callable that returns True while
    injected page exhaustion is active — ``can_allocate`` then reports no
    capacity and ``alloc_*`` raises :class:`OutOfBlocks`, driving the
    scheduler's real preemption/degradation paths without consuming any
    actual pages.
    """

    def __init__(self, cfg: PagedCacheConfig,
                 fault: Optional[Callable[[], bool]] = None):
        self.cfg = cfg
        self.fault = fault
        # ascending ranges are already valid min-heaps
        self._free_hi = list(range(1, cfg.num_hi_blocks)) \
            if cfg.quant.quantized else []
        self._free_lo = list(range(1, cfg.num_lo_blocks))
        self._free_hi_set = set(self._free_hi)
        self._free_lo_set = set(self._free_lo)
        self._num_blocks = {"hi": cfg.num_hi_blocks if cfg.quant.quantized
                            else 0, "lo": cfg.num_lo_blocks}
        # page id -> holders; an entry exists while the page is allocated
        # OR parked evictable (ref 0, cached)
        self._ref = {"hi": {}, "lo": {}}
        # prefix cache: chain digest -> entry, plus the reverse and
        # parent->children maps the lookup/eviction paths need
        self._cache: dict = {}                       # digest -> _CacheEntry
        self._by_page: dict = {}                     # (pool, page) -> digest
        self._children: dict = {}                    # digest -> set(digest)
        # zero-ref cached pages in LRU order (oldest first) per pool
        self._evict = {"hi": collections.OrderedDict(),
                       "lo": collections.OrderedDict()}
        self.cache_evictions = 0
        # peak pages simultaneously *referenced* (ref >= 1) — the bench's
        # pages-held-per-workload signal (evictable cache copies excluded:
        # they are reclaimable capacity, not demand)
        self.peak_referenced = 0

    def free_counts(self) -> tuple[int, int]:
        return len(self._free_hi), len(self._free_lo)

    def evictable_counts(self) -> tuple[int, int]:
        """(hi, lo) zero-ref cached pages — reclaimable on demand."""
        return len(self._evict["hi"]), len(self._evict["lo"])

    def available_counts(self) -> tuple[int, int]:
        """(hi, lo) pages an allocation could obtain: free + evictable."""
        return (len(self._free_hi) + len(self._evict["hi"]),
                len(self._free_lo) + len(self._evict["lo"]))

    def capacity(self) -> tuple[int, int]:
        """(hi, lo) *allocatable* pages — pool sizes minus the null page.
        The scheduler's submit-time feasibility check compares a request's
        worst-case page demand against this, so a prompt that could never
        be placed is rejected up front instead of livelocking the step
        loop."""
        return (max(self._num_blocks["hi"] - 1, 0),
                max(self._num_blocks["lo"] - 1, 0))

    def all_free(self) -> bool:
        """True when every allocatable page is reclaimable — on the free
        list or parked as a zero-ref cached page (the prefix cache
        legitimately outlives the requests that populated it).  The leak
        invariant the chaos/soak tests assert once all requests reach a
        terminal state; `flush_cache` collapses it to exact free-list
        equality."""
        return self.available_counts() == self.capacity()

    def _fault_active(self) -> bool:
        return self.fault is not None and self.fault()

    def can_allocate(self, n_hi: int, n_lo: int) -> bool:
        if (n_hi > 0 or n_lo > 0) and self._fault_active():
            return False
        avail_hi, avail_lo = self.available_counts()
        return n_hi <= avail_hi and n_lo <= avail_lo

    def _note_usage(self) -> None:
        cap_hi, cap_lo = self.capacity()
        avail_hi, avail_lo = self.available_counts()
        used = (cap_hi - avail_hi) + (cap_lo - avail_lo)
        if used > self.peak_referenced:
            self.peak_referenced = used

    def _heap(self, pool: str) -> tuple[list, set]:
        return ((self._free_hi, self._free_hi_set) if pool == "hi"
                else (self._free_lo, self._free_lo_set))

    def _evict_lru(self, pool: str) -> None:
        """Reclaim the least-recently-used zero-ref cached page: drop its
        cache registration and return it to the free list."""
        page, _ = self._evict[pool].popitem(last=False)
        self._drop_cache_entry(pool, page)
        del self._ref[pool][page]
        heap, members = self._heap(pool)
        heapq.heappush(heap, page)
        members.add(page)
        self.cache_evictions += 1

    def _drop_cache_entry(self, pool: str, page: int) -> None:
        digest = self._by_page.pop((pool, page))
        entry = self._cache.pop(digest)
        kids = self._children.get(entry.parent)
        if kids is not None:
            kids.discard(digest)
            if not kids:
                del self._children[entry.parent]

    def _alloc(self, pool: str) -> int:
        heap, members = self._heap(pool)
        if self._fault_active():
            raise OutOfBlocks(f"{pool} pool exhausted")
        if not heap and self._evict[pool]:
            self._evict_lru(pool)
        if not heap:
            raise OutOfBlocks(f"{pool} pool exhausted")
        i = heapq.heappop(heap)
        members.remove(i)
        self._ref[pool][i] = 1
        self._note_usage()
        return i

    def alloc_hi(self) -> int:
        return self._alloc("hi")

    def alloc_lo(self) -> int:
        return self._alloc("lo")

    def ref_count(self, pool: str, page: int) -> int:
        return self._ref[pool].get(int(page), 0)

    def acquire(self, hi_ids, lo_ids) -> None:
        """Add one holder to each page (a prefix-cache hit sharing them).
        A zero-ref evictable page leaves the LRU — it is referenced
        again."""
        for pool, ids in (("hi", hi_ids), ("lo", lo_ids)):
            for i in ids:
                i = int(i)
                refs = self._ref[pool]
                if refs.get(i) is None:
                    raise ValueError(
                        f"cannot acquire {pool} page {i}: not allocated")
                if refs[i] == 0:
                    self._evict[pool].pop(i, None)
                refs[i] += 1
        self._note_usage()

    def release(self, hi_ids, lo_ids) -> None:
        """Drop one holder from each page.  A page reaching zero holders
        returns to the free list — unless it is registered in the prefix
        cache, in which case it parks in the evictable LRU with its
        content intact (newest-released = most recently used)."""
        for pool, ids in (("hi", hi_ids), ("lo", lo_ids)):
            heap, members = self._heap(pool)
            for i in ids:
                i = int(i)
                if not 0 < i < self._num_blocks[pool]:
                    raise ValueError(
                        f"cannot free {pool} page {i}: outside the "
                        f"allocatable range [1, {self._num_blocks[pool]}) "
                        f"(page 0 is the null page)")
                refs = self._ref[pool]
                if i in members or refs.get(i, 0) <= 0:
                    raise ValueError(f"double free of {pool} page {i}")
                refs[i] -= 1
                if refs[i] > 0:
                    continue
                if (pool, i) in self._by_page:
                    # cached: keep content, park LRU-evictable
                    self._evict[pool][i] = None
                    self._evict[pool].move_to_end(i)
                else:
                    del refs[i]
                    heapq.heappush(heap, i)
                    members.add(i)

    # back-compat name: scheduler/tests predate ref-counting — with every
    # page at ref 1 (no sharing) this is exactly the old free()
    def free(self, hi_ids, lo_ids) -> None:
        self.release(hi_ids, lo_ids)

    # -- prefix cache ---------------------------------------------------
    def _hi_per_seq(self) -> int:
        return self.cfg.hi_blocks_per_seq

    def _page_for_index(self, g: int, hi_pages, lo_pages) -> tuple[str, int]:
        hps = self._hi_per_seq()
        if g < hps:
            return "hi", int(hi_pages[g])
        return "lo", int(lo_pages[g - hps])

    def register_prefix(self, prompt: np.ndarray, upto: int,
                        hi_pages, lo_pages) -> int:
        """Register every *fully materialized* prompt page in [0, upto) —
        upto is the request's materialized position, so only pages whose
        block_size tokens are all written (and all prompt tokens, never
        generated ones) become addressable.  A digest collision keeps the
        existing entry: the newcomer's page simply stays private.  Returns
        the number of new registrations."""
        bs = self.cfg.block_size
        n_full = min(int(upto), int(len(prompt))) // bs
        parent, new = _PREFIX_ROOT, 0
        for g in range(n_full):
            toks = np.asarray(prompt[g * bs:(g + 1) * bs], np.int32)
            digest = _prefix_digest(parent, toks)
            if digest not in self._cache:
                pool, page = self._page_for_index(g, hi_pages, lo_pages)
                if (pool, page) not in self._by_page:
                    self._cache[digest] = _CacheEntry(pool, page,
                                                      toks.copy(), parent)
                    self._by_page[(pool, page)] = digest
                    self._children.setdefault(parent, set()).add(digest)
                    new += 1
            parent = digest
        return new

    def _walk_prefix(self, prompt: np.ndarray,
                     limit: int) -> tuple[int, list]:
        """Longest cached coverage of ``prompt[:limit]``: full pages along
        the digest chain, then at most one partially-matching child page
        (the divergence point CoW exists for).  Returns ``(raw_tokens,
        [(pool, page), ...])`` covering them — no refs taken."""
        bs = self.cfg.block_size
        limit = min(int(limit), int(len(prompt)))
        parent, pages = _PREFIX_ROOT, []
        full = 0
        while (full + 1) * bs <= limit:
            toks = np.asarray(prompt[full * bs:(full + 1) * bs], np.int32)
            digest = _prefix_digest(parent, toks)
            entry = self._cache.get(digest)
            if entry is None:
                break
            pages.append((entry.pool, entry.page))
            parent = digest
            full += 1
        matched = full * bs
        # partial tail: a cached child page whose stored tokens share a
        # proper prefix with the remaining prompt (divergence mid-page)
        rest = np.asarray(prompt[matched:limit], np.int32)
        best_extra, best = 0, None
        for digest in sorted(self._children.get(parent, ()),
                             key=lambda d: (self._cache[d].pool,
                                            self._cache[d].page)):
            entry = self._cache[digest]
            n = min(len(rest), len(entry.tokens))
            eq = entry.tokens[:n] == rest[:n]
            extra = int(n if eq.all() else np.argmin(eq))
            if extra > best_extra:
                best_extra, best = extra, (entry.pool, entry.page)
        if best is not None:
            pages.append(best)
            matched += best_extra
        return matched, pages

    def peek_prefix(self, prompt: np.ndarray, limit: int,
                    quantum: int) -> int:
        """Side-effect-free probe: the aligned token count `lookup_prefix`
        would return right now (the submit-time capacity check's prefix
        credit)."""
        raw, _ = self._walk_prefix(prompt, limit)
        return min(raw, int(limit)) // quantum * quantum

    def lookup_prefix(self, prompt: np.ndarray, limit: int,
                      quantum: int) -> Optional[PrefixMatch]:
        """Longest cached prefix of ``prompt``, aligned DOWN to a multiple
        of ``quantum`` (the engine's aligned-chunk length, so a cache-hit
        prefill restarts exactly on a cache-off chunk boundary — the
        bit-identical-token guarantee) and capped at ``limit``.  Acquires
        one reference on every returned page.  When the aligned match ends
        mid-page, the final page is returned for *reading* only and
        flagged in ``cow``: the caller must replace it with a copy before
        writing (see `copy_page`) — if the CoW copy could not be allocated
        the match is shortened until it ends on a page boundary."""
        bs = self.cfg.block_size
        raw, pages = self._walk_prefix(prompt, limit)
        matched = min(raw, int(limit)) // quantum * quantum
        while matched > 0 and matched % bs and not (
                self.can_allocate(1, 0)
                if pages[(matched - 1) // bs][0] == "hi"
                else self.can_allocate(0, 1)):
            # no page for the copy-on-write copy: retreat to the previous
            # quantum until the match ends on a page boundary (or dies)
            matched = (matched - 1) // quantum * quantum
        if matched <= 0:
            return None
        n_pages = -(-matched // bs)
        hi_pages = [p for pool, p in pages[:n_pages] if pool == "hi"]
        lo_pages = [p for pool, p in pages[:n_pages] if pool == "lo"]
        cow = None
        if matched % bs:
            pool, _ = pages[n_pages - 1]
            cow = (pool, (len(hi_pages) if pool == "hi" else len(lo_pages))
                   - 1)
        self.acquire(hi_pages, lo_pages)
        return PrefixMatch(matched=matched, hi_pages=hi_pages,
                           lo_pages=lo_pages, cow=cow)

    def flush_cache(self) -> int:
        """Drop every prefix-cache registration: zero-ref (evictable) pages
        return to the free list; pages still referenced by live requests
        merely lose their registration (they free normally on release).
        Returns the number of registrations dropped — the fault-injection
        hook for cache-eviction storms, and the test hook for exact
        free-list equality."""
        dropped = len(self._cache)
        for pool in ("hi", "lo"):
            while self._evict[pool]:
                self._evict_lru(pool)
        # remaining registrations belong to ref>0 pages: unregister only
        for (pool, page) in list(self._by_page):
            self._drop_cache_entry(pool, page)
        return dropped

    def cache_stats(self) -> dict:
        """Live prefix-cache occupancy for the engine's gauges."""
        shared = sum(1 for refs in self._ref.values()
                     for r in refs.values() if r >= 2)
        pinned_sink = sum(1 for (pool, page) in self._by_page
                          if pool == "hi"
                          and self._ref["hi"].get(page, 0) >= 1)
        ev_hi, ev_lo = self.evictable_counts()
        return {"cached_pages": len(self._by_page),
                "evictable_pages": ev_hi + ev_lo,
                "kv_pages_shared": shared,
                "sink_pages_pinned": pinned_sink,
                "cache_evictions": self.cache_evictions,
                "peak_referenced_pages": self.peak_referenced}


# ---------------------------------------------------------------------------
# host-side index math (slot position -> page/offset)
# ---------------------------------------------------------------------------


def token_page_index(pos: int, cfg: PagedCacheConfig) -> tuple[bool, int, int]:
    """Logical position -> (is_hi, page_index_within_table, offset)."""
    bs = cfg.block_size
    if pos < cfg.num_hi:
        return True, pos // bs, pos % bs
    rel = pos - cfg.num_hi
    return False, rel // bs, rel % bs


def pages_needed(pos: int, cfg: PagedCacheConfig) -> tuple[int, int]:
    """(hi, lo) page counts required to hold logical positions [0, pos) —
    the shared demand arithmetic behind the scheduler's reservations and
    the engine's submit-time capacity-feasibility check."""
    bs = cfg.block_size
    hi_tokens = min(pos, cfg.num_hi)
    lo_tokens = pos - hi_tokens
    return -(-hi_tokens // bs), -(-lo_tokens // bs)


# ---------------------------------------------------------------------------
# device-side write / read
# ---------------------------------------------------------------------------


def _quant_token(t: Array, bits: int) -> tuple[Array, Array, Array]:
    """Per-token quant matching `kvcache.quant_tokens` + signed shift for
    8-bit codes (identical math, so paged and contiguous caches hold
    bit-identical codes for the same K/V)."""
    q, sc, zp = KV.quant_tokens(t, bits)
    if bits == 8:
        q, zp = KV.to_signed8(q, zp)
        return q.astype(jnp.int8), sc, zp
    return KV.pack_nibbles(q), sc, zp


def _scatter_tokens(entry: dict, kc: Array, vc: Array,
                    pages: Array, offsets: Array, is_hi: Array,
                    cfg: PagedCacheConfig) -> dict:
    """Scatter N token rows into the pools.  ``kc / vc``: (N, kv, hd);
    ``pages / offsets``: (N,) int32 physical page + in-page offset
    (host-computed); ``is_hi``: (N,) bool.  A write lands in exactly one
    pool — the other pool's scatter (and any masked/pad token) is routed to
    its null page, which is never read unmasked, so no validity branch is
    needed on device."""
    out = dict(entry)
    if not cfg.quant.quantized:
        pg_lo = jnp.where(is_hi, 0, pages)
        for name, t in (("k", kc), ("v", vc)):
            out[name] = entry[name].at[pg_lo, offsets].set(
                t.astype(entry[name].dtype))
        return out
    pg_hi = jnp.where(is_hi, pages, 0)
    pg_lo = jnp.where(is_hi, 0, pages)
    kv_heads = kc.shape[1]
    params = entry["lo_scale_zp"]
    for i, (name, t) in enumerate((("k", kc), ("v", vc))):
        q8, sc8, zp8 = _quant_token(t, 8)
        q4, sc4, zp4 = _quant_token(t, cfg.quant.lo_bits)
        out[f"{name}_hi"] = entry[f"{name}_hi"].at[pg_hi, offsets].set(q8)
        out[f"{name}_lo"] = entry[f"{name}_lo"].at[pg_lo, offsets].set(
            jax.lax.bitcast_convert_type(q4.reshape(q4.shape[0], -1),
                                         jnp.int8))
        for suffix, hi_val in (("scale", sc8), ("zp", zp8)):
            out[f"{name}_hi_{suffix}"] = \
                entry[f"{name}_hi_{suffix}"].at[pg_hi, offsets].set(
                    hi_val.astype(jnp.float16))
        row, lanes = lo_param_lanes(offsets, i, kv_heads)
        for r, val in ((row, sc4), (row + 1, zp4)):
            params = params.at[pg_lo[:, None], r[:, None], lanes].set(
                jax.lax.bitcast_convert_type(val.astype(jnp.float16),
                                             jnp.int16))
    out["lo_scale_zp"] = params
    return out


def write_tokens(entry: dict, k_new: Array, v_new: Array,
                 pages: Array, offsets: Array, is_hi: Array,
                 cfg: PagedCacheConfig) -> dict:
    """Decode path: scatter one new token per slot into the pools.
    ``k_new / v_new``: (S, 1, kv, hd); inactive slots arrive with
    ``pages == 0`` (the null page)."""
    return _scatter_tokens(entry, k_new[:, 0], v_new[:, 0], pages, offsets,
                           is_hi, cfg)


def write_chunk(entry: dict, k: Array, v: Array,
                pages: Array, offsets: Array, is_hi: Array,
                cfg: PagedCacheConfig) -> dict:
    """Prefill path: scatter a (1, C, kv, hd) K/V chunk of one slot into
    the pools; pad tokens beyond the chunk's valid length arrive with
    ``pages == 0``."""
    return _scatter_tokens(entry, k[0], v[0], pages, offsets, is_hi, cfg)


def write_ragged(entry: dict, k: Array, v: Array,
                 pages: Array, offsets: Array, is_hi: Array,
                 cfg: PagedCacheConfig) -> dict:
    """Unified-step path: scatter the whole flattened token stream — every
    prefill chunk's tokens followed by one token per decode slot — in ONE
    device scatter.  ``k / v``: (T, kv, hd); pad / inactive entries arrive
    with ``pages == 0`` (the null page).  Real writes always target
    disjoint (page, offset) pairs (requests own disjoint pages), so the
    combined scatter is order-independent except on the never-read null
    page."""
    return _scatter_tokens(entry, k, v, pages, offsets, is_hi, cfg)


def gather_region(entry: dict, region: str, table: Array, block_size: int,
                  dtype=jnp.bfloat16) -> tuple:
    """Dequantised ``(k, v)`` of one quantized region (``"hi"``: int8 codes,
    ``"lo"``: int4 nibbles) through its block table ``(S, n)``: each
    (S, n*bs, kv, hd)."""
    s, n = table.shape
    tokens = n * block_size

    def dense(arr):
        g = arr[table]                                # (S, n, bs, ...)
        return g.reshape(s, tokens, *g.shape[3:])

    out = []
    if region == "hi":
        for name in ("k", "v"):
            out.append(KV.dequant_tokens(
                dense(entry[f"{name}_hi"]).astype(jnp.float32),
                dense(entry[f"{name}_hi_scale"]),
                dense(entry[f"{name}_hi_zp"]), dtype))
        return tuple(out)
    kv_heads = entry["k_hi_scale"].shape[-1]
    # (S, n, ⌈bs/4⌉, scale|zp, k|v, 4, kv) → per token, in page order
    params = jax.lax.bitcast_convert_type(
        entry["lo_scale_zp"][table][..., :8 * kv_heads], jnp.float16
    ).reshape(s, n, -1, 2, 2, 4, kv_heads).transpose(0, 1, 4, 3, 2, 5, 6)
    params = params.reshape(s, n, 2, 2, -1, kv_heads)[..., :block_size, :]
    for i, name in enumerate(("k", "v")):
        codes = jax.lax.bitcast_convert_type(
            dense(entry[f"{name}_lo"]), jnp.uint8).reshape(s, tokens,
                                                           kv_heads, -1)
        sc, zp = (params[:, :, i, j].reshape(s, tokens, kv_heads)
                  for j in (0, 1))
        out.append(KV.dequant_tokens(KV.unpack_nibbles(codes), sc, zp,
                                     dtype))
    return tuple(out)


def gather_segments(entry: dict, hi_table: Array, lo_table: Array,
                    cfg: PagedCacheConfig, dtype=jnp.bfloat16):
    """Block tables -> dense dequantized segments for the XLA attention path.

    ``hi_table``: (S, nh) int32; ``lo_table``: (S, nl) int32 — unmapped
    logical blocks hold 0 (the null page, all-zero) and are masked by length
    downstream.  Returns ``[(k_hi, v_hi, 0), (k_lo, v_lo, num_hi)]`` shaped
    (S, nh*bs, kv, hd) / (S, nl*bs, kv, hd) — the same segment structure
    `decode_attention_segments` consumes for the contiguous cache, so the
    two layouts share one attention implementation (and its exact numerics).
    """
    bs = cfg.block_size
    if not cfg.quant.quantized:
        s = lo_table.shape[0]
        k, v = (entry[name][lo_table].reshape(
            s, lo_table.shape[1] * bs, *entry[name].shape[2:]).astype(dtype)
            for name in ("k", "v"))
        return [(k, v, 0)]
    segs = []
    if hi_table.shape[1]:                # a sink region is configured
        segs.append((*gather_region(entry, "hi", hi_table, bs, dtype), 0))
    segs.append((*gather_region(entry, "lo", lo_table, bs, dtype),
                 cfg.num_hi))
    return segs


# ---------------------------------------------------------------------------
# page swap (host <-> device) — preemption support
# ---------------------------------------------------------------------------


def _has_periods_axis(entry: dict) -> bool:
    """Scanned-period pools are (P, N, bs, kv, hd); prologue entries come
    period-stripped as (N, bs, kv, hd) (see `lm.init_paged_cache`) — the
    page axis moves accordingly."""
    probe = entry["k_hi"] if "k_hi" in entry else entry["k"]
    return probe.ndim == 5


# reserved top-level key in the swap dict: per-array CRC32 of the saved
# bytes, recorded at swap-out and verified before swap-in touches the pools
CRC_KEY = "__crc__"


def _crc(arr: np.ndarray) -> int:
    return zlib.crc32(np.ascontiguousarray(arr).tobytes())


def verify_swapped(swapped: dict) -> None:
    """Check every saved array against the checksums `extract_pages`
    recorded; raise :class:`SwapCorruption` on the first mismatch.  A swap
    dict without checksums (older callers, hand-built test fixtures)
    passes unverified."""
    crcs = swapped.get(CRC_KEY)
    if crcs is None:
        return
    for layer_key, layer in swapped.items():
        if layer_key == CRC_KEY:
            continue
        for name, arr in layer.items():
            if _crc(np.asarray(arr)) != crcs[layer_key][name]:
                raise SwapCorruption(
                    f"swap-in checksum mismatch at {layer_key}/{name}: the "
                    f"host copy was corrupted while the request was "
                    f"preempted — refusing to restore it")


def extract_pages(pools: dict, hi_ids: list[int], lo_ids: list[int],
                  slot: int | None = None) -> dict:
    """Copy a request's pages — and, for hybrid stacks, its per-slot SSM
    state — to host memory (vLLM-style swap-out).  The result maps each
    layer key to {array_name: np.ndarray of the selected pages / slot row}
    and restores bit-identically via :func:`insert_pages`, so a preempted
    request resumes from the exact cache state it was evicted with — no
    recompute, no numeric drift.  ``slot`` selects the SSM row for
    slot-dense entries; it is required when the pools contain any.  The
    result also carries a CRC32 per saved array under :data:`CRC_KEY`;
    :func:`insert_pages` verifies them before touching the pools, so
    corruption of the host copy fails loudly (`SwapCorruption`) instead of
    silently resuming garbage."""
    hi = np.asarray(hi_ids, np.int32)
    lo = np.asarray(lo_ids, np.int32)
    swapped = {}
    for layer_key, entry in pools.items():
        if is_ssm_entry(entry):
            if slot is None:
                raise ValueError(
                    "pools hold slot-dense SSM state; extract_pages needs "
                    "the request's slot to swap it out")
            periods = _ssm_has_periods(entry)
            swapped[layer_key] = {
                name: np.asarray(arr[:, slot] if periods else arr[slot])
                for name, arr in entry.items()}
            continue
        periods = _has_periods_axis(entry)
        layer = {}
        for name, arr in entry.items():
            ids = lo if _is_lo(name) else hi
            layer[name] = np.asarray(arr[:, ids] if periods else arr[ids])
        swapped[layer_key] = layer
    swapped[CRC_KEY] = {
        layer_key: {name: _crc(arr) for name, arr in layer.items()}
        for layer_key, layer in swapped.items() if layer_key != CRC_KEY}
    return swapped


def insert_pages(pools: dict, swapped: dict, hi_ids: list[int],
                 lo_ids: list[int], slot: int | None = None) -> dict:
    """Swap-in: place saved pages at (possibly different) page ids — and
    saved SSM state at the (possibly different) ``slot`` the scheduler
    re-admitted the request into.  Checksums recorded at swap-out are
    verified *first*: on mismatch the restore raises
    :class:`SwapCorruption` with the pools untouched, so the engine can
    fail just the corrupted request and keep the batch running."""
    verify_swapped(swapped)
    hi = jnp.asarray(np.asarray(hi_ids, np.int32))
    lo = jnp.asarray(np.asarray(lo_ids, np.int32))
    out = {}
    for layer_key, entry in pools.items():
        if is_ssm_entry(entry):
            if slot is None:
                raise ValueError(
                    "pools hold slot-dense SSM state; insert_pages needs "
                    "the resumed request's slot to swap it back in")
            periods = _ssm_has_periods(entry)
            layer = dict(entry)
            for name, arr in entry.items():
                saved = jnp.asarray(swapped[layer_key][name])
                layer[name] = arr.at[:, slot].set(saved) if periods \
                    else arr.at[slot].set(saved)
            out[layer_key] = layer
            continue
        periods = _has_periods_axis(entry)
        layer = dict(entry)
        for name, arr in entry.items():
            ids = lo if _is_lo(name) else hi
            if ids.size:
                saved = jnp.asarray(swapped[layer_key][name])
                layer[name] = arr.at[:, ids].set(saved) if periods \
                    else arr.at[ids].set(saved)
        out[layer_key] = layer
    return out


def copy_page(pools: dict, pool: str, src: int, dst: int) -> dict:
    """Copy-on-write device copy: duplicate one physical page (codes +
    scale/zp) from ``src`` to ``dst`` within the named pool, across every
    attention layer.  Used when a prefix-cache match ends mid-page: the
    child reads positions below the divergence point from the copy and
    its first `write_ragged` scatters the divergent tokens into the copy,
    leaving the shared original untouched.  Bytes beyond the divergence
    offset carry the parent's stale values — masked by slot length exactly
    like the null page's residue, never read.  SSM slot entries (hybrid
    stacks) are skipped: recurrent state is per-request, never shared."""
    out = {}
    for layer_key, entry in pools.items():
        if is_ssm_entry(entry):
            out[layer_key] = entry
            continue
        periods = _has_periods_axis(entry)
        layer = dict(entry)
        for name, arr in entry.items():
            if _is_lo(name) != (pool == "lo"):
                continue
            layer[name] = arr.at[:, dst].set(arr[:, src]) if periods \
                else arr.at[dst].set(arr[src])
        out[layer_key] = layer
    return out


def swapped_bytes(swapped: dict) -> int:
    """Host bytes one swap-out moved (pages + SSM state) — the
    ``swap_bytes`` stat the serving bench reports per preemption."""
    return sum(int(arr.nbytes)
               for layer_key, layer in swapped.items()
               if layer_key != CRC_KEY
               for arr in layer.values())
