"""Paged KV cache: fixed-size pages, block tables, ragged decode attention.

The serving-side answer to "every (batch, seq) bucket owns a dense
[B, S, Hk, D] cache": K/V live in a single pool of fixed-size pages
([num_pages, page_size, Hk, D] per layer) and each sequence owns an
ordered list of page indices (its *block table*). Logical slot `s` of a
sequence lives at page `block_table[s // page_size]`, offset
`s % page_size`. Sequences of wildly different lengths then share one
pool — the HBM cost of a batch is the sum of its real lengths (rounded
up to pages), not num_slots × max_len — and a finished sequence's pages
return to the free list for the next admission (continuous batching,
arXiv 2604.15464 / 2605.25645).

Three pieces live here:
  * `PageAllocator` — the host-side free list. Pure Python; the device
    never sees it. Page 0..num_pages-1 are real; `allocator.sentinel`
    (== num_pages) marks unallocated block-table entries. Writes routed
    to the sentinel fall off the end of the pool and are DROPPED by
    XLA's out-of-bounds scatter rule; gathers CLIP to the last page and
    the garbage is masked out of attention. Both behaviors are load-
    bearing: masked rows need no branch on device.
  * `write_pages` / `gather_pages` — the device-side page I/O, plain
    scatter/gather in slot order. Shapes are static; the block table is
    a traced [B, max_pages] int32 operand, so growing a sequence never
    recompiles.
  * `ragged_decode_attention` — the pure-JAX reference decode path:
    gather each row's pages into a contiguous [B, K, Hk, D] view and
    run the stock fp32-softmax attention. Bit-identical to the dense
    cache path when the padded KV width matches (masked columns are
    exactly 0 probability either way). The Pallas twin
    (`ops/pallas/paged_attention.py`) reads pages in place through the
    block table instead of gathering.
  * `write_pages_packed` / `ragged_paged_attention` — the PACKED
    (ragged) twins: one query buffer of R rows drawn from many
    sequences with MIXED query lengths (decode rows contribute one
    token, a chunked-prefill suffix contributes many), addressed per
    row by (segment, position) instead of per batch row by (start, T).
    This is what lets the serving engine run prefill suffixes and
    decode steps for every live slot in ONE dispatch
    (models/generate.paged_ragged_step; arXiv 2604.15464). The
    reference here is the CPU bit-parity anchor; the Pallas twin walks
    the block tables in place.
  * `spec_lane_metadata` — the SPECULATIVE extension of the same
    packing: each live slot contributes 1+k verify lanes (its fed
    token plus k drafted continuations at consecutive positions).
    Draft lanes need NO new kernel — a draft at position len+j is just
    one more (segment, position) row, causally masked at its own
    position, attending to the earlier lanes' K/V written in the same
    forward exactly as a chunked-prefill suffix already does
    (models/generate.paged_spec_step).
"""

from __future__ import annotations

import time
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from oryx_tpu.ops.attention import NEG_INF, attention
from oryx_tpu.utils import faults
from oryx_tpu.utils import quant as quant_lib


class OutOfPagesError(RuntimeError):
    """The free list cannot satisfy an allocation (caller should evict
    or defer admission — this is a scheduling signal, not a crash)."""


class PageAllocator:
    """Host-side free-list allocator over `num_pages` fixed-size pages,
    with per-page REFERENCE COUNTS so pages can be shared.

    LIFO recycling: freshly freed pages are handed out first, which
    keeps the hot working set of pages small and stable (good for any
    cache layer under the pool). Allocation is all-or-nothing so a
    failed admission never leaks a partial block table.

    Sharing (the prefix-cache contract, serve/prefix_cache.py): `alloc`
    hands out pages at refcount 1; `share` adds a holder; `free` /
    `release` drops one, and the page returns to the free list only at
    refcount 0. A shared page is IMMUTABLE by convention — a writer
    that owns only one of several references must copy-on-write first
    (`copy_pages` below); `refcount(p) > 1` is the "must COW" test.
    Freeing an unallocated page, or more references than a page holds,
    raises immediately with the page id (leak/double-free guard).

    Ownership observatory (docs/OBSERVABILITY.md "Memory & device
    time"): every reference carries an OWNER TAG stamped by the caller
    at the transition (`alloc`/`share`/`free` take `owner=`; the
    scheduler stamps `req:<request-id>`, the prefix cache `cache`), and
    every page records when its current tenancy began (`_born`, set at
    refcount 0→1) and when a reference last changed (`_touched`).
    `snapshot()` turns that into the live ownership map `/debug/pages`
    serves; an attached `observer` (utils/pagemap.PoolObservatory) is
    told the lifetime + idle time of every page returning to the free
    list, feeding the oryx_page_{lifetime,idle}_seconds histograms.
    Owner tags are accounting labels only — they never change what the
    allocator does, and an untagged transition stamps "?".
    """

    def __init__(self, num_pages: int, page_size: int, plane: str = ""):
        if num_pages < 1 or page_size < 1:
            raise ValueError(f"need >= 1 page/slot, got {num_pages=} {page_size=}")
        # Which plane of a pool of several this list is over ("global",
        # "window"): said in OutOfPagesError, nothing else.
        self.plane = plane
        self.num_pages = num_pages
        self.page_size = page_size
        self._free: list[int] = list(range(num_pages - 1, -1, -1))
        self._refs: list[int] = [0] * num_pages
        # 1 / holders of each page (1.0 while it is free), and 0.0 at
        # the sentinel's index: what a holder is charged for the page,
        # kept beside `_refs` so that a block table's charge is one
        # C-level sum (`charge`), sentinels and all.
        self._share: list[float] = [1.0] * num_pages + [0.0]
        # Ownership map state (one tag per live reference, in grant
        # order) + tenancy clocks, all monotonic-clock based.
        self._owners: list[list[str]] = [[] for _ in range(num_pages)]
        self._born: list[float] = [0.0] * num_pages
        self._touched: list[float] = [0.0] * num_pages
        # Low-water mark of the free list since construction — the
        # peak-occupancy watermark the loadgen memory block reads.
        self.min_free: int = num_pages
        # utils/pagemap.PoolObservatory (or any object with a
        # page_freed(lifetime_s, idle_s) method); None = no telemetry.
        self.observer = None

    @property
    def sentinel(self) -> int:
        """Block-table filler for unallocated entries: one past the pool
        (writes drop, gathers clip; see module docstring)."""
        return self.num_pages

    @property
    def num_free(self) -> int:
        return len(self._free)

    def pages_for(self, num_tokens: int) -> int:
        """Pages needed to hold `num_tokens` KV slots."""
        return max(0, -(-num_tokens // self.page_size))

    def refcount(self, page: int) -> int:
        """Current holder count of `page` (0 = free)."""
        if not 0 <= page < self.num_pages:
            raise ValueError(f"page {page} outside pool of {self.num_pages}")
        return self._refs[page]

    def charge(self, table: list[int]) -> float:
        """Sum over `table` (page ids; the sentinel counts nothing) of
        1 / the page's holders: a block table's refcount-weighted page
        count, without a Python-level walk of it."""
        return sum(map(self._share.__getitem__, table))

    def alloc(self, n: int, *, owner: str | None = None) -> list[int]:
        if n > 0:
            # Chaos site: simulated pool exhaustion. Every caller must
            # treat OutOfPagesError as a scheduling signal (defer /
            # evict / COW-fallback), never a crash — the chaos suite
            # proves refcounts stay exact through it.
            faults.fault_point(
                "page_alloc_oom",
                exc=lambda: OutOfPagesError(
                    f"injected pool exhaustion (asked {n} pages)"
                ),
            )
        if n > len(self._free):
            raise OutOfPagesError(
                f"need {n} pages, {len(self._free)} free of {self.num_pages}"
                + (f" in the {self.plane} plane" if self.plane else "")
            )
        if n <= 0:
            return []
        out = self._free[-n:][::-1]
        del self._free[-n:]
        now = time.monotonic()
        tag = owner or "?"
        for p in out:
            self._refs[p] = 1
            self._share[p] = 1.0
            self._owners[p] = [tag]
            self._born[p] = self._touched[p] = now
        self.min_free = min(self.min_free, len(self._free))
        return out

    def share(self, pages: list[int], *, owner: str | None = None) -> None:
        """Add one reference per page. All-or-nothing: sharing a FREE
        page is a bug (its contents are up for grabs) and raises with
        the page id before anything is mutated."""
        for p in pages:
            if not 0 <= p < self.num_pages:
                raise ValueError(f"page {p} outside pool of {self.num_pages}")
            if self._refs[p] <= 0:
                raise ValueError(f"share of unallocated page {p}")
        now = time.monotonic()
        tag = owner or "?"
        for p in pages:
            self._refs[p] += 1
            self._share[p] = 1.0 / self._refs[p]
            self._owners[p].append(tag)
            self._touched[p] = now

    def free(self, pages: list[int], *, owner: str | None = None) -> None:
        """Drop one reference per page; pages reaching refcount 0 return
        to the free list (in `pages` order, LIFO-recycled). Raises with
        the offending page id — before mutating anything — on a double
        free (refcount already 0) or when one call drops more references
        to a page than it holds. `owner` removes that holder's tag from
        the ownership map (falling back to the most recent tag when the
        caller's stamp is absent — accounting only, never a guard)."""
        from collections import Counter

        drops = Counter(pages)
        for p, n in drops.items():
            if not 0 <= p < self.num_pages:
                raise ValueError(f"page {p} outside pool of {self.num_pages}")
            if self._refs[p] <= 0:
                raise ValueError(f"double free of page {p}")
            if n > self._refs[p]:
                raise ValueError(
                    f"freeing {n} references to page {p}, which holds "
                    f"only {self._refs[p]}"
                )
        now = time.monotonic()
        released = []
        for p in pages:
            self._refs[p] -= 1
            self._share[p] = 1.0 / max(1, self._refs[p])
            tags = self._owners[p]
            if owner is not None and owner in tags:
                tags.remove(owner)
            elif tags:
                tags.pop()
            if self._refs[p] == 0:
                released.append(p)
                if self.observer is not None:
                    # Free-time telemetry: how long the page was
                    # resident, and how long since its last reference
                    # transition (the idle tail nobody was using it).
                    self.observer.page_freed(
                        now - self._born[p], now - self._touched[p]
                    )
            self._touched[p] = now
        self._free.extend(reversed(released))

    # `release` is `free` under its sharing-aware name: both drop one
    # reference; the page only leaves the pool's live set at refcount 0.
    release = free

    def check_invariant(self, holders=None) -> None:
        """Pool accounting invariant; raises RuntimeError on violation.

        Always checked: free list and refcounts partition the pool
        (num_free + pages-with-refcount > 0 == num_pages, no page in
        both sets, no negative refcount). With `holders` — an iterable
        of page lists, one per live holder (slots' block tables, the
        prefix cache's entries) — additionally checks that every page's
        refcount equals its holder count, i.e. nothing leaked and
        nothing is double-held. Callable from tests; the scheduler
        asserts it at `_reset_pool`."""
        from collections import Counter

        allocated = {p for p, r in enumerate(self._refs) if r > 0}
        if any(r < 0 for r in self._refs):
            raise RuntimeError(f"negative refcount: {self._refs}")
        if self._share != [1.0 / max(1, r) for r in self._refs] + [0.0]:
            raise RuntimeError(f"page shares out of step: {self._share}")
        free_set = set(self._free)
        if len(free_set) != len(self._free):
            raise RuntimeError(f"duplicate pages in free list: {self._free}")
        if free_set & allocated:
            raise RuntimeError(
                f"pages both free and allocated: {sorted(free_set & allocated)}"
            )
        if len(self._free) + len(allocated) != self.num_pages:
            raise RuntimeError(
                f"pool accounting broken: {len(self._free)} free + "
                f"{len(allocated)} allocated != {self.num_pages} pages"
            )
        if holders is None:
            return
        held = Counter()
        for pages in holders:
            held.update(int(p) for p in pages)
        for p in range(self.num_pages):
            if held.get(p, 0) != self._refs[p]:
                raise RuntimeError(
                    f"page {p}: refcount {self._refs[p]} but "
                    f"{held.get(p, 0)} holders"
                )

    @staticmethod
    def classify(refcount: int, owners: list[str]) -> str:
        """Observatory state of one page — the four states partition
        the pool (free + slot + cache + shared == num_pages): free
        (refcount 0), shared (>= 2 holders, whoever they are), cache
        (exactly the prefix cache's own reference) or slot (exactly one
        request-held reference)."""
        if refcount <= 0:
            return "free"
        if refcount >= 2:
            return "shared"
        return "cache" if owners == ["cache"] else "slot"

    def snapshot(self) -> dict:
        """The live ownership map: one record per page (state, refcount,
        owner tags, tenancy age, idle time) plus the raw pool geometry.
        Pure read — derived summaries (state counts, fragmentation,
        age quantiles) live in utils/pagemap.summarize so the router
        and the bench harness share one implementation.

        Thread contract: the map is engine-owned state; a read from a
        debug-endpoint thread is best-effort (each page record is
        internally consistent, the map is exact on a quiesced engine —
        the reconciliation gate scrapes quiesced by design)."""
        now = time.monotonic()
        pages = []
        for p in range(self.num_pages):
            r = self._refs[p]
            owners = list(self._owners[p])
            pages.append({
                "page": p,
                "state": self.classify(r, owners),
                "refcount": r,
                "owners": owners,
                "age_s": round(now - self._born[p], 6) if r > 0 else None,
                "idle_s": (
                    round(now - self._touched[p], 6) if r > 0 else None
                ),
            })
        return {
            "num_pages": self.num_pages,
            "page_size": self.page_size,
            "num_free": len(self._free),
            "min_free": self.min_free,
            "free_pages": sorted(self._free),
            "pages": pages,
        }


def window_table_pages(window: int, reach: int, page_size: int) -> int:
    """Width (pages) of a window layer's block table: a dispatch whose
    first query is at n reads from n - window + 1 (its page's start is
    at most page_size - 1 before) and writes up to n + reach - 1
    (`reach`: the longest dispatch, a prefill chunk with its padding or
    a decode chunk)."""
    return -(-(window + reach + page_size - 2) // page_size)


class WindowPlane:
    """Host-side cache manager of the WINDOW layers' plane of a pool of
    two planes (`qwen2.init_paged_kv_cache`, `WINDOW_PLANES`): its own
    `PageAllocator` over `num_pages` pages, one block table a slot
    [num_slots, table_pages] whose first `count[s]` entries are lane
    s's pages, and the position `base[s]` (a multiple of the page size)
    that slot 0 of lane s's table holds. A window layer's query at t
    sees t - window < u <= t, so before a dispatch whose first query is
    at n a page whose last token lies before n - window + 1 will never
    be read again: `advance` gives such pages back, shifts the table
    and moves the base. The device programs get the table and the base
    and work in positions relative to it (`qwen2.forward`), so no
    kernel knows a page was ever released. The global plane keeps the
    scheduler's own allocator and table, untouched by any of this."""

    def __init__(self, num_pages: int, page_size: int, num_slots: int,
                 table_pages: int, window: int):
        self.allocator = PageAllocator(num_pages, page_size, plane="window")
        self.page_size, self.window = page_size, window
        self.sentinel = self.allocator.sentinel
        self.tables = np.full((num_slots, table_pages), self.sentinel,
                              np.int32)
        self.count = np.zeros((num_slots,), np.int64)
        self.base = np.zeros((num_slots,), np.int32)

    def held(self, s: int) -> list[int]:
        return self.tables[s, :self.count[s]].tolist()

    def advance(self, s: int, first_query: int,
                owner: str | None = None) -> list[int]:
        """Lane s's next dispatch has its first query at position
        `first_query`: free every page wholly older than its window,
        shift the table, move the base. Returns the pages freed."""
        ps = self.page_size
        base = max(0, first_query - self.window + 1) // ps * ps
        drop = (base - int(self.base[s])) // ps
        if drop <= 0:
            return []
        row = self.tables[s]
        freed = row[:min(drop, self.count[s])].tolist()
        if freed:
            self.allocator.free(freed, owner=owner)
        row[:len(row) - drop] = row[drop:]
        row[len(row) - drop:] = self.sentinel
        self.count[s] -= len(freed)
        self.base[s] = base
        return freed

    def need(self, s: int, tokens: int) -> int:
        """Pages lane s lacks to hold positions base .. tokens - 1 (what
        the table's width allows of them)."""
        want = min(self.allocator.pages_for(tokens - int(self.base[s])),
                   self.tables.shape[1])
        return max(0, want - int(self.count[s]))

    def grow(self, s: int, tokens: int, owner: str | None = None) -> bool:
        """Cover positions up to `tokens` - 1; False, nothing taken,
        when the plane's free list cannot."""
        n = self.need(s, tokens)
        if n > self.allocator.num_free:
            return False
        if n:
            try:
                pages = self.allocator.alloc(n, owner=owner)
            except OutOfPagesError:
                return False
            self.tables[s, self.count[s]:self.count[s] + n] = pages
            self.count[s] += n
        return True

    def release(self, s: int, owner: str | None = None) -> None:
        """Lane s leaves: every page back, the base at 0."""
        pages = self.held(s)
        if pages:
            self.allocator.free(pages, owner=owner)
        self.tables[s] = self.sentinel
        self.count[s] = self.base[s] = 0

    def check_invariant(self) -> None:
        """The plane's allocator against the tables, and every table
        packed from its slot 0 (a hole would shift positions)."""
        slots = range(len(self.base))
        self.allocator.check_invariant([self.held(s) for s in slots])
        width = np.arange(self.tables.shape[1])
        for s in slots:
            if ((self.tables[s] != self.sentinel)
                    != (width < self.count[s])).any():
                raise RuntimeError(
                    f"window table of slot {s} has a hole: {self.tables[s]}")


@jax.tree_util.register_pytree_node_class
class QuantPages:
    """A quantized paged KV pool (one plane — K or V — of the pool
    pytree): storage-dtype codes plus a PER-PAGE SCALE BLOCK.

      q:     [..., P, page_size, Hk, D] int8 (or fp8-e4m3) codes
      scale: [..., P, page_size] fp32 — one scale per token row,
             stored page-major so every page carries its own scale
             block: COW (`copy_pages`), host spill (`fetch_page`) and
             reload (`upload_page`) move q-bytes and scales together,
             verbatim, with zero special-casing.

    Scale granularity (docs/DESIGN.md "KV quantization & cache
    tiering"): the scale is per token ROW within the page block, not
    one scalar per page. A single per-page scalar would have to grow
    as later tokens land in the page (pages fill incrementally across
    prefill chunks and decode steps), forcing an in-place requantize
    of earlier rows — making the stored bytes depend on write
    GROUPING, which would break the cold-vs-cached, eviction-replay
    and spill/reload byte-parity contracts the serving engine leans
    on. Per-row scales make the encoding a pure function of the
    token's own value; the storage overhead is 4 bytes per Hk*D-byte
    row (<1%), and the layout is what rides the block-table stream
    into the Pallas kernel (a block's scale rows are copied beside its
    code pages, addressed through the same scalar-prefetched table).

    Registered as a pytree node, so everything downstream — the layer
    scan in qwen2.forward, jit donation, `copy_pages`' tree_map, host
    fetch/upload — treats the pool transparently; `dequant_dtype` (the
    logical dtype consumers see, static aux data) is what the ops
    dequantize into."""

    def __init__(self, q, scale, dequant_dtype=jnp.float32):
        # Tracers and pytree sentinels have no ndim worth checking.
        if getattr(q, "ndim", 5) == 4 and getattr(scale, "ndim", 0) == 3:
            raise ValueError(
                "latent pool: a quantized pool is per-head K and V planes "
                "[L, P, page, Hk, D] with one scale a token; a latent "
                "(MLA) page [L, P, page, D] has no head axis and is not "
                "built for it (kv_dtype must be bf16)"
            )
        self.q = q
        self.scale = scale
        self.dequant_dtype = jnp.dtype(dequant_dtype)

    def tree_flatten(self):
        return (self.q, self.scale), str(self.dequant_dtype)

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(children[0], children[1], aux)

    # Shape/dtype impersonation: callers read pool geometry off the
    # leaf (`kv_pages["k"].shape[2]` is the page size everywhere).
    @property
    def shape(self):
        return self.q.shape

    @property
    def ndim(self):
        return self.q.ndim

    @property
    def dtype(self):  # the LOGICAL dtype consumers see after dequant
        return self.dequant_dtype

    @property
    def storage_dtype(self):
        return self.q.dtype

    def __repr__(self):
        return (
            f"QuantPages(q={self.q.shape}:{self.q.dtype}, "
            f"scale={self.scale.shape}, dequant={self.dequant_dtype})"
        )


def init_quant_pages(
    num_layers: int, num_pages: int, page_size: int, num_kv_heads: int,
    head_dim: int, *, fmt: str = "int8", dequant_dtype=jnp.float32,
) -> QuantPages:
    """A zeroed quantized pool plane (the int8 counterpart of one
    jnp.zeros leaf of qwen2.init_paged_kv_cache)."""
    storage, _ = quant_lib.kv_storage_dtype(fmt)
    return QuantPages(
        jnp.zeros(
            (num_layers, num_pages, page_size, num_kv_heads, head_dim),
            storage,
        ),
        jnp.zeros((num_layers, num_pages, page_size), jnp.float32),
        dequant_dtype=dequant_dtype,
    )


def pool_plane(kv_pages):
    """One plane of a pool pytree ([L, P, page, ...]; K of a per-head
    pool, the one plane of a latent pool): what callers read the pool's
    geometry off (`pool_plane(kv).shape[2]` is the page size)."""
    if isinstance(kv_pages, dict):
        return kv_pages["k"] if "k" in kv_pages else kv_pages[LATENT]
    return kv_pages


# The one plane of a latent (MLA) pool, [L2, P, page, Dp]: cache layer
# 2l + i is attention sublayer i of model layer l; a token's row is its
# kv latent, its roped shared key and zeros up to whole 128-lane tiles
# (`LLMConfig.latent_page_dim`). No head axis.
LATENT = "latent"


def is_latent_pool(kv_pages) -> bool:
    return isinstance(kv_pages, dict) and LATENT in kv_pages


# The indexer's plane of a latent pool whose model attends the keys an
# indexer selected (`LLMConfig.indexed`): [L, P, page, index_head_dim],
# a token's roped index key at the page and offset its latent has, behind
# the SAME block table and allocator. Whatever moves a page by its index
# (`copy_pages`, `fetch_page`, `upload_page`) moves both planes.
INDEX_K = "index_k"


# The per-SLOT planes of a pool whose model has state layers
# (`qwen2.init_paged_kv_cache`): [Ls, S, ...], addressed by slot, never
# through a block table. Everything that moves PAGES leaves them alone.
# A Mamba hybrid's pool has both, a gated-short-convolution hybrid's
# `conv` alone (its two rows are its whole state).
SLOT_PLANES = ("conv", "ssm")


# The page-edge snapshots of a pool whose state layers are gated short
# convolutions: [Lc, P, (K-1) * d], row p the `conv` rows of the lane
# that filled page p as they stood after the page's LAST token. A PAGED
# plane, behind the same block table and allocator as `k` / `v`: what
# moves, shares, evicts or reuses a page does the same to its snapshot,
# which is what a prefix-cache hit hands over (`handover_state`). Valid
# wherever the page is full, which is the only kind the cache indexes.
CONV_EDGE = "conv_edge"


# The window layers' paged planes of a pool whose model has window
# layers (`qwen2.init_paged_kv_cache`): [Lw, Pw, page, Hk, D], behind a
# page count, an allocator and a block table of their own; `k` / `v`
# are then the GLOBAL layers' planes alone. A page index means
# something in one of the two, so nothing that moves a page by ONE index
# through every plane (`copy_pages`, `fetch_page`, `upload_page`) is
# built for such a pool.
WINDOW_PLANES = ("wk", "wv")


def paged_planes(kv_pages):
    """The pool without its per-slot planes: what a page index means
    something in. `kv_pages` itself where it has none."""
    if isinstance(kv_pages, dict) and any(n in kv_pages for n in SLOT_PLANES):
        return {k: v for k, v in kv_pages.items() if k not in SLOT_PLANES}
    return kv_pages


def _with_paged(kv_pages, paged):
    """`paged` (an edited `paged_planes(kv_pages)`) back beside the
    per-slot planes."""
    if paged_planes(kv_pages) is kv_pages:
        return paged
    return {**kv_pages, **paged}


@partial(jax.jit, donate_argnums=0)
def handover_state(kv_pages, page: jnp.ndarray, slot: jnp.ndarray):
    """A prefix-cache hit's state: slot `slot`'s `conv` rows become the
    snapshot page `page` keeps (`CONV_EDGE`), every state layer at once.
    Donates the pool; page and slot are traced scalars (one compiled
    program a pool shape)."""
    with jax.named_scope("mixer"), jax.named_scope("conv_handover"):
        conv = kv_pages[SLOT_PLANES[0]].at[:, slot].set(
            kv_pages[CONV_EDGE][:, page])
    return {**kv_pages, SLOT_PLANES[0]: conv}


def kv_pool_dtype(kv_pages) -> str:
    """The pool's wire format: "int8" / "fp8_e4m3" for a quantized
    pool, else the dense leaf dtype's name (e.g. "float32")."""
    leaf = pool_plane(kv_pages)
    if isinstance(leaf, QuantPages):
        try:
            return _quant_fmt(leaf)
        except ValueError:
            return str(leaf.storage_dtype)
    return str(leaf.dtype)


@partial(jax.jit, donate_argnums=0)
def copy_pages(kv_pages, src: jnp.ndarray, dst: jnp.ndarray):
    """Copy page `src` onto page `dst` across every layer of a paged KV
    pytree ([L, P, page_size, Hk, D] leaves) — the device half of
    copy-on-write: a writer holding one of several references to a page
    allocates a fresh page, copies the shared contents here, and swaps
    the fresh page into its block table before writing. Donates the
    pool, so the copy is in place; src/dst are traced scalars (one
    compiled program per pool shape). On a QUANTIZED pool the tree_map
    descends into each plane's (codes, scales) children — both carry
    the page axis at position 1 — so COW moves the raw quantized bytes
    AND the page's scale block verbatim: share/splice/eviction-replay/
    spec-rollback semantics are untouched by the storage format."""
    return _with_paged(kv_pages, jax.tree_util.tree_map(
        lambda a: a.at[:, dst].set(a[:, src]), paged_planes(kv_pages)
    ))


def fetch_page(kv_pages, page: int):
    """Host-side byte-verbatim copy of ONE page across the whole pool
    pytree (every layer, K and V — and, on a quantized pool, the
    page's scale blocks): the spill half of the host-RAM prefix-cache
    tier. Returns a pytree of numpy arrays shaped [L, page_size, ...];
    `upload_page` is its exact inverse, so spill -> reload is lossless
    by construction (same dtype, same bytes, no re-encode)."""
    return jax.tree_util.tree_map(
        lambda a: np.asarray(a[:, page]), paged_planes(kv_pages)
    )


def host_blob_bytes(blob) -> int:
    """Total host bytes of a `fetch_page` blob (the --host-cache-bytes
    accounting unit)."""
    return int(sum(
        leaf.nbytes for leaf in jax.tree_util.tree_leaves(blob)
    ))


@partial(jax.jit, donate_argnums=0)
def upload_page(kv_pages, dst: jnp.ndarray, blob):
    """Write a `fetch_page` host blob back into page `dst` of the pool
    (donated, in place; dst is a traced scalar — one compiled program
    per pool shape). The astype is a no-op by contract (same dtype
    both ways): the reload is byte-verbatim."""
    return _with_paged(kv_pages, jax.tree_util.tree_map(
        lambda a, b: a.at[:, dst].set(b.astype(a.dtype)),
        paged_planes(kv_pages), blob,
    ))


def write_pages(
    cache_layer: jnp.ndarray,  # [P, page_size, Hk, D]
    new: jnp.ndarray,  # [B, T, Hk, D]
    block_tables: jnp.ndarray,  # [B, max_pages] int32 (sentinel = P)
    start: jnp.ndarray,  # [B] int32 first logical slot per row
    *,
    write_mask: jnp.ndarray | None = None,  # [B] bool rows that may write
) -> jnp.ndarray:
    """Write T contiguous tokens per row into the page pool.

    Row b's token t lands at logical slot start[b] + t, i.e. page
    block_tables[b, slot // page_size] offset slot % page_size. Rows
    with write_mask False — and any slot routed through the sentinel —
    scatter out of bounds and are dropped (the masked-decode idiom:
    finished/empty slots cost no branch).

    Quantized pool (`cache_layer` a QuantPages plane): the incoming fp
    rows are quantized ON WRITE — per-token-row symmetric scales
    (utils/quant.quantize_kv_rows) — and the codes + scales scatter
    through the SAME flat slot indices, so masked/sentinel rows drop
    both identically and the scale blocks always describe exactly the
    codes that landed.
    """
    if isinstance(cache_layer, QuantPages):
        return _write_pages_quant(
            cache_layer, new, block_tables, start, write_mask=write_mask
        )
    P, ps, Hk, D = cache_layer.shape
    B, T, _, _ = new.shape
    slots = start[:, None].astype(jnp.int32) + jnp.arange(T, dtype=jnp.int32)
    page = jnp.take_along_axis(block_tables, slots // ps, axis=1)  # [B, T]
    flat = page * ps + slots % ps  # sentinel page P -> index >= P*ps -> drop
    if write_mask is not None:
        flat = jnp.where(write_mask[:, None], flat, P * ps)
    pool = cache_layer.reshape(P * ps, Hk, D)
    pool = pool.at[flat.reshape(-1)].set(
        new.reshape(B * T, Hk, D).astype(pool.dtype), mode="drop"
    )
    return pool.reshape(P, ps, Hk, D)


def _quant_fmt(pages: QuantPages) -> str:
    """The quant format name of a QuantPages plane (for the shared
    quantize helpers)."""
    for name, (dt, _) in quant_lib.KV_STORAGE_DTYPES.items():
        if pages.storage_dtype == jnp.dtype(dt):
            return name
    raise ValueError(
        f"QuantPages carries unknown storage dtype {pages.storage_dtype}"
    )


def _scatter_quant(
    pages: QuantPages, flat: jnp.ndarray, rows: jnp.ndarray
) -> QuantPages:
    """Scatter packed fp rows [N, Hk, D] into a quantized pool plane at
    flat slot indices [N] (one shared index stream for codes AND
    scales; OOB -> dropped for both)."""
    P, ps, Hk, D = pages.q.shape
    codes, scale = quant_lib.quantize_kv_rows(rows, _quant_fmt(pages))
    qpool = pages.q.reshape(P * ps, Hk, D)
    qpool = qpool.at[flat].set(codes, mode="drop")
    spool = pages.scale.reshape(P * ps)
    spool = spool.at[flat].set(scale, mode="drop")
    return QuantPages(
        qpool.reshape(P, ps, Hk, D), spool.reshape(P, ps),
        dequant_dtype=pages.dequant_dtype,
    )


def _write_pages_quant(
    pages: QuantPages,
    new: jnp.ndarray,  # [B, T, Hk, D]
    block_tables: jnp.ndarray,
    start: jnp.ndarray,
    *,
    write_mask: jnp.ndarray | None = None,
) -> QuantPages:
    """Quantize-on-write twin of the dense `write_pages` body: same
    slot routing, same drop semantics, codes + per-row scales written
    by one shared index stream."""
    P, ps, Hk, D = pages.q.shape
    B, T, _, _ = new.shape
    slots = start[:, None].astype(jnp.int32) + jnp.arange(T, dtype=jnp.int32)
    page = jnp.take_along_axis(block_tables, slots // ps, axis=1)  # [B, T]
    flat = page * ps + slots % ps
    if write_mask is not None:
        flat = jnp.where(write_mask[:, None], flat, P * ps)
    return _scatter_quant(
        pages, flat.reshape(-1), new.reshape(B * T, Hk, D)
    )


def gather_pages(
    cache_layer: jnp.ndarray,  # [P, page_size, Hk, D]
    block_tables: jnp.ndarray,  # [B, max_pages]
) -> jnp.ndarray:
    """Materialize each row's logical KV stream: [B, max_pages*ps, Hk, D].

    Sentinel entries clip to the last real page; whatever they read is
    past every row's valid length and masked out of attention. This is
    the portable reference path — the Pallas kernel replaces it with
    in-place page reads on TPU.

    Quantized pool: the gathered codes are DEQUANTIZED here — each
    page's scale block rides the same block-table gather — so every
    consumer downstream (the stock attention reference, the ragged
    reference) sees a plain fp stream. The Pallas kernels instead
    dequantize inside the page walk (the tile's scale block is fetched
    alongside its code tile), multiplying out identically.
    """
    B, maxp = block_tables.shape
    if isinstance(cache_layer, QuantPages):
        P, ps, Hk, D = cache_layer.q.shape
        dt = cache_layer.dequant_dtype
        codes = cache_layer.q[block_tables]  # OOB gather clips
        scale = cache_layer.scale[block_tables]  # [B, maxp, ps]
        out = codes.astype(dt) * scale[..., None, None].astype(dt)
        return out.reshape(B, maxp * ps, Hk, D)
    P, ps, Hk, D = cache_layer.shape
    out = cache_layer[block_tables]  # OOB gather clips
    return out.reshape(B, maxp * ps, Hk, D)


def ragged_decode_attention(
    q: jnp.ndarray,  # [B, 1, Hq, D] (or [B, Hq, D])
    k_pages: jnp.ndarray,  # [P, page_size, Hk, D]
    v_pages: jnp.ndarray,
    block_tables: jnp.ndarray,  # [B, max_pages]
    kv_lengths: jnp.ndarray,  # [B] valid kv count INCLUDING the current token
    *,
    scale: float | None = None,
) -> jnp.ndarray:
    """Pure-JAX reference for single-token paged decode attention.

    Each query attends to its own ragged KV prefix, addressed through
    its block table. Returns [B, 1, Hq, D] (or [B, Hq, D], matching q).
    """
    squeezed = q.ndim == 3
    if squeezed:
        q = q[:, None]
    B = q.shape[0]
    K = block_tables.shape[1] * k_pages.shape[1]
    k = gather_pages(k_pages, block_tables)
    v = gather_pages(v_pages, block_tables)
    kv_mask = (
        jnp.arange(K, dtype=jnp.int32)[None, :] < kv_lengths[:, None]
    ).astype(jnp.int32)
    out = attention(
        q, k, v,
        causal=True,
        q_positions=(kv_lengths - 1)[:, None].astype(jnp.int32),
        kv_positions=None,  # arange over logical slots == absolute positions
        kv_mask=kv_mask,
        scale=scale,
    )
    return out[:, 0] if squeezed else out


# ---------------------------------------------------------------------------
# Packed ragged mode: mixed query lengths, one buffer, one dispatch
# ---------------------------------------------------------------------------


def write_pages_packed(
    cache_layer: jnp.ndarray,  # [P, page_size, Hk, D]
    new: jnp.ndarray,  # [R, Hk, D] packed new K or V rows
    block_tables: jnp.ndarray,  # [S, max_pages] int32 (sentinel = P)
    q_segments: jnp.ndarray,  # [R] int32 owning slot per packed row
    q_positions: jnp.ndarray,  # [R] int32 logical slot index per row
    *,
    write_mask: jnp.ndarray | None = None,  # [R] bool rows that may write
) -> jnp.ndarray:
    """Write R packed tokens into the page pool, each routed through its
    OWN sequence's block table: row r lands at logical slot
    q_positions[r] of sequence q_segments[r]. The packed twin of
    `write_pages` (whose rows are per-sequence and contiguous): here a
    decode token and a prefill-chunk token of two different sequences
    sit side by side in one buffer and one scatter places both. Rows
    with write_mask False — and any slot routed through the sentinel —
    drop, exactly as in `write_pages` (quantized pools quantize on
    write with per-row scales, same routing — see `write_pages`)."""
    P, ps, Hk, D = cache_layer.q.shape if isinstance(
        cache_layer, QuantPages
    ) else cache_layer.shape
    S, maxp = block_tables.shape
    seg = jnp.clip(q_segments.astype(jnp.int32), 0, S - 1)
    pos = q_positions.astype(jnp.int32)
    # Page index clamps into the row's own table (matching the
    # take_along_axis OOB clamp of the per-sequence writer); the
    # sentinel page then routes the write off the pool end -> dropped.
    page = block_tables[seg, jnp.clip(pos // ps, 0, maxp - 1)]  # [R]
    flat = page * ps + pos % ps
    if write_mask is not None:
        flat = jnp.where(write_mask, flat, P * ps)
    if isinstance(cache_layer, QuantPages):
        return _scatter_quant(cache_layer, flat, new)
    pool = cache_layer.reshape(P * ps, Hk, D)
    pool = pool.at[flat].set(new.astype(pool.dtype), mode="drop")
    return pool.reshape(P, ps, Hk, D)


def spec_lane_metadata(
    lengths: jnp.ndarray,  # [S] int32 confirmed kv tokens per slot
    k: int,  # drafts per slot (static)
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """(q_segments, q_positions) for S slots x (1+k) speculative verify
    lanes, slot-major: lane j of slot s sits at logical position
    lengths[s] + j — lane 0 is the slot's fed decode token, lanes 1..k
    its drafted continuations. The packed writer and the ragged
    attention kernel consume this unchanged (a draft lane IS a
    chunked-prefill-suffix lane whose token happens to be proposed, not
    given): per-row causal masking at own position makes lane j attend
    to lanes < j of its own slot — freshly written this forward — and
    to nothing of any other slot's lanes. Returns ([S*(1+k)],
    [S*(1+k)]) int32."""
    S = lengths.shape[0]
    seg = jnp.repeat(jnp.arange(S, dtype=jnp.int32), k + 1)
    pos = (
        lengths[:, None].astype(jnp.int32)
        + jnp.arange(k + 1, dtype=jnp.int32)[None, :]
    ).reshape(-1)
    return seg, pos


def stop_window_hit(
    recent: jnp.ndarray,  # [S, stop_L] rolling recent-token window
    stop_sequences: jnp.ndarray | None,  # [Sq, stop_L] (-1 = wildcard)
) -> jnp.ndarray:
    """In-scan stop mask over the per-slot recent-token windows: row s
    is True when its window's tail matches ANY template stop sequence
    (right-aligned; -1 template slots are wildcards, which is also how
    shorter sequences left-pad). The window initializes at -2,
    matching nothing, and carries across dispatches. Returns [S]
    bool."""
    if stop_sequences is None:
        return jnp.zeros((recent.shape[0],), bool)
    m = (stop_sequences[None] == -1) | (
        recent[:, None, :] == stop_sequences[None]
    )
    return jnp.any(jnp.all(m, axis=-1), axis=-1)


def ragged_paged_attention(
    q: jnp.ndarray,  # [R, Hq, D] packed query rows
    k_pages: jnp.ndarray,  # [P, page_size, Hk, D]
    v_pages: jnp.ndarray,
    block_tables: jnp.ndarray,  # [S, max_pages] int32
    q_segments: jnp.ndarray,  # [R] int32 owning slot per packed row
    q_positions: jnp.ndarray,  # [R] int32 absolute position per row
    *,
    scale: float | None = None,
) -> jnp.ndarray:
    """Pure-JAX reference for packed RAGGED paged attention — the one
    semantics both engine paths must agree on bit-for-bit.

    Each packed row r attends to the KV prefix of its own sequence
    q_segments[r], addressed through that sequence's block table,
    causally masked at its own position: logical slot j is visible iff
    j <= q_positions[r]. A decode step (one row at position len-1) and
    a chunked-prefill suffix (one row per suffix token, consecutive
    positions) are THE SAME case under this mask — which is exactly
    what makes one dispatch serve a mixed batch. Returns [R, Hq, D].

    Bit-parity contract (tests/test_ragged_attention.py): for a decode
    row this reproduces `ragged_decode_attention` exactly (the causal
    mask at position len-1 equals its kv_lengths mask), and for a
    prefill row it reproduces the row's logits from the per-sequence
    chunked prefill (same masked set, same fp32 reductions per row).
    """
    from oryx_tpu.parallel.sharding import constrain

    R = q.shape[0]
    S, maxp = block_tables.shape
    seg = jnp.clip(q_segments.astype(jnp.int32), 0, S - 1)
    k_all = gather_pages(k_pages, block_tables)  # [S, K, Hk, D]
    v_all = gather_pages(v_pages, block_tables)
    # On a tp mesh the pool is heads-sharded (sharding.paged_kv_spec);
    # pin the gathered per-row view to the same head split so GSPMD
    # never reshards the packed buffer's KV (no-op off-mesh).
    k_r = constrain(k_all[seg], None, None, "tp", None)  # [R, K, Hk, D]
    v_r = constrain(v_all[seg], None, None, "tp", None)
    out = attention(
        q[:, None], k_r, v_r,
        causal=True,
        q_positions=q_positions[:, None].astype(jnp.int32),
        kv_positions=None,  # arange over logical slots == absolute positions
        scale=scale,
    )
    return out[:, 0]


def latent_decode_attention(
    q: jnp.ndarray,  # [B, Hq, Dp] absorbed queries: (q_lat | q_rope | 0)
    pages: jnp.ndarray,  # [P, page_size, Dp] latent pool of one cache layer
    block_tables: jnp.ndarray,  # [B, max_pages]
    kv_lengths: jnp.ndarray,  # [B] valid kv count INCLUDING the current token
    *,
    scale: float,
    value_dim: int,
) -> jnp.ndarray:
    """Pure-JAX reference of absorbed latent (MLA) decode over a paged
    pool: every head of row b scores against the ONE shared key a
    cached token holds (the whole page row) and sums the row's first
    `value_dim` columns, the latent, as its value. Returns
    [B, Hq, value_dim]; a row of length 0 reads nothing and returns 0.
    The Pallas twin (ops/pallas/paged_attention.latent_decode_attention)
    walks the live pages in place; this one gathers them."""
    P, ps, Dp = pages.shape
    lat = gather_pages(pages[:, :, None, :], block_tables)[:, :, 0]
    s = jnp.einsum(
        "bhd,bkd->bhk", q, lat, preferred_element_type=jnp.float32
    ) * scale
    seen = (
        jnp.arange(lat.shape[1], dtype=jnp.int32)[None, :]
        < kv_lengths[:, None]
    )[:, None, :]
    s = jnp.where(seen, s, NEG_INF)
    p = jnp.where(seen, jnp.exp(s - jnp.max(s, axis=-1, keepdims=True)), 0.0)
    l = jnp.sum(p, axis=-1, keepdims=True)
    out = jnp.einsum(
        "bhk,bkc->bhc", p.astype(lat.dtype), lat[..., :value_dim],
        preferred_element_type=jnp.float32,
    )
    return (out / jnp.where(l == 0.0, 1.0, l)).astype(q.dtype)


# ---------------------------------------------------------------------------
# Learned sparse attention: index scores, the exact top k, the selected rows
# ---------------------------------------------------------------------------


def index_tile(q: jnp.ndarray, w: jnp.ndarray, keys: jnp.ndarray):
    """The indexer's scores, the one plain copy of their arithmetic:
    I[b, t, u] = sum_j w[b, t, j] relu(q[b, t, j] . keys[b, u]),
    float32. q [B, T, Hi, Di], w [B, T, Hi] float32, keys [B, K, Di]."""
    s = jnp.einsum(
        "bthd,bkd->bthk", q, keys, preferred_element_type=jnp.float32)
    return jnp.einsum("bthk,bth->btk", jax.nn.relu(s), w.astype(jnp.float32))


def index_scores(
    q: jnp.ndarray,  # [B, Hi, Di] a decode row's index queries
    w: jnp.ndarray,  # [B, Hi] float32 weights of the index heads
    pages: jnp.ndarray,  # [P, page_size, Di] index keys of one cache layer
    block_tables: jnp.ndarray,  # [B, max_pages]
    kv_lengths: jnp.ndarray,  # [B] valid kv count INCLUDING the current token
) -> jnp.ndarray:
    """Pure-JAX reference of one query a row against its paged index
    keys: `index_tile`, -inf at u >= kv_lengths[b]. Returns
    [B, max_pages * page_size]. The Pallas twin
    (ops/pallas/paged_attention.index_scores) walks the live pages in
    place; this one gathers them."""
    keys = gather_pages(pages[:, :, None, :], block_tables)[:, :, 0]
    s = index_tile(q[:, None], w[:, None], keys)[:, 0]
    seen = (jnp.arange(keys.shape[1], dtype=jnp.int32)[None, :]
            < kv_lengths[:, None])
    return jnp.where(seen, s, -jnp.inf)


def _order_key(scores: jnp.ndarray) -> jnp.ndarray:
    """float32 -> uint32 whose unsigned order is the floats' total
    order, `jax.lax.top_k`'s (no NaN; -0.0 under 0.0)."""
    b = jax.lax.bitcast_convert_type(scores.astype(jnp.float32), jnp.uint32)
    return jnp.where(b >> 31 == 1, ~b, b | jnp.uint32(1 << 31))


def _kth_mask(key: jnp.ndarray, k: int) -> jnp.ndarray:
    """uint32 [..., K], K > k -> bool [..., K] with exactly k True a
    row, at the k largest keys, ties to the lower index: the k-th
    largest key found bit by bit (32 counts over the row), then the
    last index that still belongs among the keys equal to it (one count
    a bit of K). What `topk_mask` and `topk_indices` share."""
    K = key.shape[-1]
    rows = key.shape[:-1]

    def value_bit(i, thr):
        cand = thr | (jnp.uint32(1 << 31) >> i.astype(jnp.uint32))
        enough = jnp.sum(key >= cand[..., None], axis=-1) >= k
        return jnp.where(enough, cand, thr)

    thr = jax.lax.fori_loop(0, 32, value_bit, jnp.zeros(rows, jnp.uint32))
    above = key > thr[..., None]
    equal = key == thr[..., None]
    need = k - jnp.sum(above, axis=-1)  # >= 1 of the equal ones
    u = jnp.arange(K, dtype=jnp.int32)
    bits = max(1, (K - 1).bit_length())

    def index_bit(i, cut):
        cand = cut | (jnp.int32(1 << (bits - 1)) >> i)
        few = jnp.sum(equal & (u < cand[..., None]), axis=-1) < need
        return jnp.where(few, cand, cut)

    cut = jax.lax.fori_loop(0, bits, index_bit, jnp.zeros(rows, jnp.int32))
    return above | (equal & (u <= cut[..., None]))


def topk_mask(scores: jnp.ndarray, k: int) -> jnp.ndarray:
    """[..., K] float32 -> bool [..., K]: True at the k largest scores
    of a row, ties to the lower index, the SET `jax.lax.top_k` returns
    (everything where K <= k) but for one rule: -0.0 counts as 0.0. No
    sort and no index array, so it serves a [chunk, table] block of
    scores (`_kth_mask`: 32 + log2 K counts over the row)."""
    if scores.shape[-1] <= k:
        return jnp.ones(scores.shape, bool)
    return _kth_mask(_order_key(jnp.where(scores == 0, 0.0, scores)), k)


# `_set_indices` views a row page by page: a page of 64 positions (the
# pool's page as served; a width it does not divide is padded with unset
# bits) is four words of 16 bits, and pages go eight to a group, so that
# a group's row of the float32 table holds whole numbers under 2**16,
# which a one-hot product returns exactly.
_WORD_BITS, _GROUP_WORDS = 16, 32


def _set_indices(mask: jnp.ndarray, k: int) -> jnp.ndarray:
    """bool [B, K] with exactly k True a row -> their indices ascending,
    int32 [B, k], with no sort, scan or search along K: one pass packs
    the bits into words, and output place j is then found by counting,
    three times over: the group whose running count of set bits first
    passes j (k places against K / 512 running sums), the word of that
    group likewise (32 sums), the bit of that word likewise (16). A
    group's words and sums reach its places as a one-hot product, exact
    in float32 at the highest precision: 0.02 ms on a v5e at [12,
    65536], k 2048, where a gather of as many scalars takes 0.25 (chip
    run, PR 51)."""
    B, K = mask.shape
    bits = jnp.pad(mask, ((0, 0), (0, -K % (_WORD_BITS * _GROUP_WORDS))))
    bits = bits.reshape(B, -1, _GROUP_WORDS, _WORD_BITS).astype(jnp.int32)
    shifts = jnp.arange(_WORD_BITS, dtype=jnp.int32)
    words = jnp.sum(bits << shifts, axis=-1)  # [B, G, 32]
    word_ends = jnp.cumsum(jax.lax.population_count(words), axis=-1)
    group_ends = jnp.cumsum(word_ends[..., -1], axis=-1)  # [B, G]
    place = jnp.arange(k, dtype=jnp.int32)

    def passed(ends, rank):
        """How many running sums `rank` has reached, and their last."""
        done = ends <= rank[..., None]
        return (jnp.sum(done, axis=-1, dtype=jnp.int32),
                jnp.max(jnp.where(done, ends, 0), axis=-1))

    group, before = passed(group_ends[:, None, :], place[None, :])
    hot = group[..., None] == jnp.arange(words.shape[1], dtype=jnp.int32)
    row = jnp.einsum(
        "bkg,bgw->bkw", hot.astype(jnp.float32),
        jnp.concatenate([word_ends, words], axis=-1).astype(jnp.float32),
        precision=jax.lax.Precision.HIGHEST).astype(jnp.int32)
    rank = place - before  # among the group's set bits
    word, before = passed(row[..., :_GROUP_WORDS], rank)
    held = jnp.sum(
        jnp.where(word[..., None] == jnp.arange(_GROUP_WORDS),
                  row[..., _GROUP_WORDS:], 0), axis=-1)  # the word's bits
    bit, _ = passed(
        jax.lax.population_count(held[..., None] & ((2 << shifts) - 1)),
        rank - before)
    return (group * _GROUP_WORDS + word) * _WORD_BITS + bit


def topk_indices(scores: jnp.ndarray, k: int) -> jnp.ndarray:
    """[B, K] float32 -> int32 [B, min(k, K)]: the indices of the k
    largest scores of a row in ASCENDING order: what `jax.lax.top_k`
    followed by a sort of its indices returns (exact, ties to the lower
    index, -0.0 under 0.0), found without either: the set by
    `_kth_mask`'s counts, its indices by `_set_indices`'. A row with
    n < k finite scores at its head (the rest -inf) returns 0..n-1
    first and the lowest-numbered -inf places after."""
    B, K = scores.shape
    if K <= k:
        return jnp.broadcast_to(jnp.arange(K, dtype=jnp.int32), (B, K))
    return _set_indices(_kth_mask(_order_key(scores), k), k)


def gather_rows(
    pages: jnp.ndarray,  # [P, page_size, D]
    block_tables: jnp.ndarray,  # [B, max_pages]
    idx: jnp.ndarray,  # [B, k] logical slots of a row's stream
) -> jnp.ndarray:
    """Rows `idx` of each row's logical stream, [B, k, D]: the selected
    rows alone leave the pool. A slot behind a sentinel entry clips to a
    real row, which the caller masks."""
    P, ps, D = pages.shape
    page = jnp.take_along_axis(block_tables, idx // ps, axis=1)
    flat = jnp.clip(page * ps + idx % ps, 0, P * ps - 1)
    return pages.reshape(P * ps, D)[flat]
