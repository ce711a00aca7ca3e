"""Shared-prefix KV reuse: one block-aligned token-ID radix index, two
storage planes.

Real Oryx traffic is dominated by a shared per-conversation prefix (the
system prompt, the media context, earlier turns), and the TPU kernel
side is indifferent to which request owns a KV page (ragged paged
attention, PAPERS.md arXiv 2604.15464) — so "have I already computed
this prefix?" should be answered ONCE, by one index, for every serving
engine. `TokenTrie` below is that index: a radix trie over fixed-size
blocks of token ids (block size == the KV page size, so a cached prefix
is always page-aligned), with LRU stamps for eviction. Two clients give
its nodes meaning:

  * `PagedPrefixCache` — the continuous scheduler's plane. Each node
    owns ONE page of the paged pool (the cache's own reference, via
    `PageAllocator.share`); admission splices matched pages into the
    new slot's block table (sharing full pages, copy-on-writing a
    partially-consumed one) and prefills only the suffix. Under pool
    pressure, refcount-1 entries (pages nobody but the cache holds) are
    LRU-evicted back to the free list — cached pages go before live
    requests ever do.
  * `SessionPrefixCache` — the dense-cache plane for the pipeline's
    own `ChatSession` path. Nodes hold whole `PrefixCacheState` snapshots,
    so a fresh `ChatSession` over the same media + system prompt seeds
    itself from a finished session's KV instead of cold-prefilling.
    Capacity-bounded (dense caches are HBM-expensive), LRU.

Matching is on token IDS (vLLM-style): a tokenizer boundary merge just
shortens the reuse, never changes a reply. Multimodal streams key their
visual slots positionally, so both planes root their tries at a media
fingerprint — a cache built over different media can never be matched.
"""

from __future__ import annotations

from typing import Any, Iterable

import numpy as np

from oryx_tpu.utils import faults


class TrieNode:
    __slots__ = ("children", "payload", "stamp", "parent", "key")

    def __init__(self, parent: "TrieNode | None", key: bytes):
        self.children: dict[bytes, TrieNode] = {}
        self.payload: Any = None
        self.stamp = 0
        self.parent = parent
        self.key = key


class TokenTrie:
    """Radix trie over fixed-size BLOCKS of token ids.

    Only whole blocks index (a partial tail block never creates a
    node), so every match length is a multiple of `block` — the
    page-alignment invariant both cache planes rely on. `root_key`
    partitions the trie (media fingerprints); `stamp` is a global LRU
    clock bumped on every walk/extend touch.
    """

    def __init__(self, block: int):
        if block < 1:
            raise ValueError(f"block size must be >= 1, got {block}")
        self.block = block
        self.roots: dict[tuple, TrieNode] = {}
        self._clock = 0

    @staticmethod
    def _block_key(tokens: np.ndarray) -> bytes:
        return np.ascontiguousarray(tokens, np.int64).tobytes()

    def _touch(self, node: TrieNode) -> None:
        self._clock += 1
        node.stamp = self._clock

    def walk(self, tokens, root_key: tuple = ()) -> list[TrieNode]:
        """Longest-prefix match: the node path for the leading full
        blocks of `tokens` present in the trie (LRU-touched), possibly
        empty. Matched length is `len(result) * block` tokens."""
        tokens = np.asarray(tokens)
        node = self.roots.get(root_key)
        path: list[TrieNode] = []
        if node is None:
            return path
        for i in range(len(tokens) // self.block):
            key = self._block_key(
                tokens[i * self.block: (i + 1) * self.block]
            )
            child = node.children.get(key)
            if child is None:
                break
            path.append(child)
            node = child
        for n in path:
            self._touch(n)
        return path

    def extend(self, tokens, root_key: tuple = ()) -> list[TrieNode]:
        """Walk + create: the node path for ALL leading full blocks of
        `tokens`, creating missing nodes (payload None) along the way."""
        tokens = np.asarray(tokens)
        node = self.roots.get(root_key)
        if node is None:
            node = self.roots[root_key] = TrieNode(None, b"")
        path: list[TrieNode] = []
        for i in range(len(tokens) // self.block):
            key = self._block_key(
                tokens[i * self.block: (i + 1) * self.block]
            )
            child = node.children.get(key)
            if child is None:
                child = node.children[key] = TrieNode(node, key)
            path.append(child)
            node = child
        for n in path:
            self._touch(n)
        return path

    def remove(self, node: TrieNode) -> None:
        """Detach a LEAF node (asserted) from its parent; empty roots
        are pruned."""
        if node.children:
            raise ValueError("only leaf nodes can be removed")
        parent = node.parent
        if parent is not None:
            del parent.children[node.key]
            if parent.parent is None and not parent.children:
                for rk, root in list(self.roots.items()):
                    if root is parent:
                        del self.roots[rk]
        node.parent = None

    def nodes(self) -> Iterable[TrieNode]:
        """Every block node (roots are structural, not yielded)."""
        stack = list(self.roots.values())
        while stack:
            n = stack.pop()
            stack.extend(n.children.values())
            if n.parent is not None:
                yield n

    def leaves(self) -> list[TrieNode]:
        return [n for n in self.nodes() if not n.children]

    def __len__(self) -> int:
        return sum(1 for _ in self.nodes())


class HostEntry:
    """One spilled cache page living in host RAM: the byte-verbatim
    device blob (every layer's K/V — and scale blocks on a quantized
    pool — for one page, from ops/paged_kv.fetch_page) plus its byte
    size for the --host-cache-bytes budget."""

    __slots__ = ("blob", "nbytes")

    def __init__(self, blob, nbytes: int):
        self.blob = blob
        self.nbytes = int(nbytes)


class PagedPrefixCache:
    """The continuous scheduler's shared-prefix page cache.

    Each trie node owns one page of the paged pool: `insert` takes the
    cache's OWN reference on newly indexed pages (`allocator.share`), so
    a donated page outlives the request that computed it; `lookup`
    returns the matched page list for the caller to splice (the CALLER
    shares the pages it keeps — lookup itself takes no references).
    `evict` walks leaves least-recently-used first and frees pages only
    the cache still holds (refcount 1); entries shared with a live slot
    are pinned until that slot releases them.

    Host-RAM spill tier (docs/DESIGN.md "KV quantization & cache
    tiering"): with `host_cache_bytes > 0` and the two device-copy
    callbacks wired, an LRU-evicted entry SPILLS to pinned host RAM —
    a byte-verbatim copy of the page (and, on a quantized pool, its
    scale block) — instead of dying. The device page still returns to
    the free list (eviction's whole point), but the prefix survives in
    a parallel host-side trie: a later lookup that walks past the
    device-resident prefix into spilled blocks re-uploads those pages
    ahead of the suffix prefill (`reload`), so cache capacity is
    bounded by HOST RAM, not HBM. Spill/reload is lossless by
    construction (same dtype both ways, no re-encode), so a reloaded
    splice is byte-identical to never having evicted. A failed
    re-upload (fault site `host_spill_upload`, or pool pressure at
    reload time) just shortens the match — the suffix recomputes cold,
    never crashes.

      spill_fetch(page) -> (blob, nbytes): device -> host page copy.
      spill_upload(blob, page) -> None: host -> device, into a page
        the cache just allocated.
    """

    def __init__(self, allocator, *, metrics=None,
                 host_cache_bytes: int = 0,
                 spill_fetch=None, spill_upload=None):
        self.allocator = allocator
        self.page_size = allocator.page_size
        if host_cache_bytes < 0:
            raise ValueError(
                f"host_cache_bytes must be >= 0, got {host_cache_bytes}"
            )
        self.host_cache_bytes = int(host_cache_bytes)
        self.spill_fetch = spill_fetch
        self.spill_upload = spill_upload
        self.spill_enabled = bool(
            host_cache_bytes > 0
            and spill_fetch is not None and spill_upload is not None
        )
        # The host tier's own trie (same block geometry; payloads are
        # HostEntry blobs, no pool pages) + its byte ledger. Engine-
        # thread-owned like the device trie.
        self._host = TokenTrie(allocator.page_size)  # thread-owned: engine
        self._host_bytes = 0  # thread-owned: engine
        self._spilled = 0  # thread-owned: engine
        # No locks BY DESIGN: the cache (trie + page accounting) is
        # engine-thread-owned — admission splice, insert-at-donate,
        # LRU eviction and clear all run on the engine loop. That
        # ownership is not folklore: the `# thread-owned:` annotations
        # are enforced by the armed race detector
        # (analysis/sanitizers.py), which flags any touch from a
        # second live thread. The supervisor/drain paths may rebuild
        # the cache only once the engine thread is dead (thread death
        # is the happens-before edge the detector honors).
        self.trie = TokenTrie(allocator.page_size)  # thread-owned: engine
        self.metrics = metrics
        self._pages = 0  # thread-owned: engine
        # Publish zeros now: a cache rebuilt after a pool reset must not
        # leave the gauges reporting the dead pool's values.
        self._gauges()

    # ---- accounting ------------------------------------------------------

    @property
    def pages(self) -> int:
        """Pages the cache holds a reference to (== trie nodes)."""
        return self._pages

    @property
    def entries(self) -> int:
        """Distinct cached prefixes (trie leaves)."""
        return len(self.trie.leaves())

    def held_pages(self) -> list[int]:
        """Every page the cache holds one reference to (for the pool
        invariant check)."""
        return [n.payload for n in self.trie.nodes()]

    def evictable_pages(self, exclude=()) -> int:
        """Upper bound on what `evict` could free right now: pages only
        the cache holds (refcount 1), minus `exclude` (pages the caller
        is about to pin). An inner refcount-1 node blocked by a shared
        descendant is counted but unreachable — callers use this as a
        feasibility screen, not a promise."""
        exclude = set(exclude)
        return sum(
            1 for n in self.trie.nodes()
            if n.payload not in exclude
            and self.allocator.refcount(n.payload) == 1
        )

    @property
    def spilled_pages(self) -> int:
        """Host-tier entries (pages living in host RAM only)."""
        return self._spilled

    @property
    def host_bytes(self) -> int:
        """Host RAM the spill tier currently holds."""
        return self._host_bytes

    def _gauges(self) -> None:
        if self.metrics is not None:
            self.metrics.set_gauge("prefix_cache_pages", self._pages)
            self.metrics.set_gauge("prefix_cache_entries", self.entries)
            reg = self.metrics.registry
            reg.gauge("oryx_cache_spilled_pages", raw_name=True).set(
                self._spilled
            )
            reg.gauge("oryx_cache_host_bytes", raw_name=True).set(
                self._host_bytes
            )

    # ---- the cache surface -----------------------------------------------

    def lookup(self, tokens, root_key: tuple = ()) -> tuple[int, list[int]]:
        """Longest page-aligned cached prefix of `tokens` →
        (matched_tokens, pages). pages[i] holds tokens
        [i*page_size, (i+1)*page_size). Takes no page references.
        Device tier only — `lookup_tiered` also surfaces the host-side
        continuation."""
        pages = self._device_pages(self.trie.walk(tokens, root_key))
        return len(pages) * self.page_size, pages

    @staticmethod
    def _device_pages(path: list[TrieNode]) -> list[int]:
        """The walked path's page ids, truncated at the first node
        without one. Payload-less device nodes cannot arise through
        the public surface (insert/reload always set payloads along
        the path), but a hole must shorten the match, never reach the
        splice as int(None)."""
        pages: list[int] = []
        for n in path:
            if n.payload is None:
                break
            pages.append(n.payload)
        return pages

    def lookup_tiered(
        self, tokens, root_key: tuple = ()
    ) -> tuple[int, list[int], list[TrieNode]]:
        """`lookup` plus the spilled continuation: (device_matched
    tokens, device_pages, host_nodes) where host_nodes are the
    host-tier trie nodes for the blocks immediately FOLLOWING the
    device-resident prefix, contiguous and each holding a HostEntry
    (a hole — a hard-evicted block — ends the run: everything past
    it must recompute anyway). Takes no references; pass the nodes
    to `reload` to bring them back on device."""
        pages = self._device_pages(self.trie.walk(tokens, root_key))
        host_nodes: list[TrieNode] = []
        if self.spill_enabled:
            hpath = self._host.walk(tokens, root_key)
            for node in hpath[len(pages):]:
                if node.payload is None:
                    break
                host_nodes.append(node)
        return len(pages) * self.page_size, pages, host_nodes

    def reload(self, tokens, host_nodes: list[TrieNode],
               root_key: tuple = ()) -> list[int]:
        """Re-upload spilled blocks onto fresh device pages, ahead of
        the caller's suffix prefill: for each host node in order,
        allocate one page (cache-owned), upload the blob byte-verbatim
        (fault site `host_spill_upload`), and re-index the block in the
        DEVICE trie — the entry is device-resident again, exactly as if
        it had never been evicted. Stops at the first failure
        (allocation or upload) and returns the device pages of the
        blocks actually reloaded: a partial reload is a shorter splice,
        and the suffix recomputes cold — degradation, never a crash."""
        depth0 = self._depth(host_nodes[0]) if host_nodes else 0
        reloaded: list[int] = []
        for node in host_nodes:
            entry = node.payload
            try:
                page = self.allocator.alloc(1, owner="cache")[0]
            except Exception:
                break
            try:
                # Chaos site: host->device re-upload failure. The
                # contract under it: free the page, shorten the match,
                # let admission recompute the suffix cold.
                faults.fault_point(
                    "host_spill_upload",
                    exc=lambda: RuntimeError(
                        "injected host-tier re-upload failure"
                    ),
                )
                self.spill_upload(entry.blob, page)
            # fault-boundary: a failed re-upload degrades to a cold
            # recompute of the suffix — the page returns, the spilled
            # entry stays for the next attempt, nothing leaks
            except Exception:
                self.allocator.free([page], owner="cache")
                break
            reloaded.append(page)
            self._host_forget_node(node)
        if reloaded:
            path = self.trie.extend(
                np.asarray(tokens)[
                    : (depth0 + len(reloaded)) * self.page_size
                ],
                root_key,
            )
            for i, page in enumerate(reloaded):
                node = path[depth0 + i]
                if node.payload is None:
                    node.payload = int(page)
                    self._pages += 1
                else:  # unreachable by the engine-thread ownership
                    self.allocator.free([page], owner="cache")
            if self.metrics is not None:
                reg = self.metrics.registry
                reg.counter(
                    "oryx_cache_reload_hit_total", raw_name=True
                ).inc()
                reg.counter(
                    "oryx_cache_reload_upload_total", raw_name=True
                ).inc(len(reloaded))
        self._gauges()
        return reloaded

    # ---- host tier internals --------------------------------------------

    @staticmethod
    def _depth(node: TrieNode) -> int:
        """Block index of a trie node (root children are index 0; the
        structural root is not a block and does not count)."""
        d = -1
        while node is not None and node.parent is not None:
            d += 1
            node = node.parent
        return d

    def _node_tokens(self, node: TrieNode) -> np.ndarray:
        """The full token stream a device-trie node indexes (its path's
        concatenated block keys) — what keys the host twin on spill."""
        keys: list[bytes] = []
        while node is not None and node.parent is not None:
            keys.append(node.key)
            node = node.parent
        return np.frombuffer(b"".join(reversed(keys)), np.int64)

    def _node_root_key(self, node: TrieNode) -> tuple:
        """The root partition a node lives under (media fingerprint)."""
        while node.parent is not None:
            node = node.parent
        for rk, root in self.roots_of(self.trie):
            if root is node:
                return rk
        return ()

    @staticmethod
    def roots_of(trie: TokenTrie):
        return list(trie.roots.items())

    def _spill(self, victim: TrieNode) -> bool:
        """Move a device-trie victim's page contents to the host tier
        (byte-verbatim). Returns False — caller falls back to a plain
        eviction — when the tier is off, the budget cannot fit the
        entry even after LRU drops, or the device copy fails."""
        if not self.spill_enabled:
            return False
        try:
            blob, nbytes = self.spill_fetch(victim.payload)
        # fault-boundary: a failed device->host copy demotes the spill
        # to a plain eviction; the entry dies, nothing leaks
        except Exception:
            return False
        if nbytes > self.host_cache_bytes:
            return False
        if self._host_bytes + nbytes > self.host_cache_bytes:
            # ONE LRU scan per spill, dropping oldest leaf entries
            # until the new blob fits (a per-drop rescan would make a
            # budget-pressure spill storm quadratic on the engine
            # thread — same discipline as the device evict's
            # one-gather-per-round loop).
            victims = sorted(
                (n for n in self._host.leaves()
                 if n.payload is not None),
                key=lambda n: n.stamp,
            )
            for v in victims:
                if self._host_bytes + nbytes <= self.host_cache_bytes:
                    break
                self._host_bytes -= v.payload.nbytes
                self._spilled -= 1
                v.payload = None
                self._host_prune_chain(v)
            if self._host_bytes + nbytes > self.host_cache_bytes:
                return False
        tokens = self._node_tokens(victim)
        root_key = self._node_root_key(victim)
        hpath = self._host.extend(tokens, root_key)
        node = hpath[-1]
        if node.payload is not None:
            self._host_bytes -= node.payload.nbytes
            self._spilled -= 1
        node.payload = HostEntry(blob, nbytes)
        self._host_bytes += nbytes
        self._spilled += 1
        return True

    def _host_forget_node(self, node: TrieNode) -> None:
        """Drop one host entry's bytes (reloaded or superseded) and
        prune whatever chain that leaves dead."""
        if node.payload is not None:
            self._host_bytes -= node.payload.nbytes
            self._spilled -= 1
            node.payload = None
        self._host_prune_chain(node)

    def _host_prune_chain(self, node: TrieNode | None) -> None:
        """Remove the dead suffix of ONE path: walking UP from `node`,
        drop childless payload-less nodes until a live ancestor (or
        the root). O(depth) per forget/drop — a full-trie rescan here
        made reload and LRU churn quadratic on the engine thread
        (dead nodes only ever appear along the path just touched, so
        the upward walk reaches every one a rescan would)."""
        while (
            node is not None and node.parent is not None
            and not node.children and node.payload is None
        ):
            parent = node.parent
            self._host.remove(node)
            node = parent

    def insert(self, tokens, pages: list[int], root_key: tuple = ()) -> int:
        """Index the full-page prefix of `tokens`, whose KV lives in
        `pages` (one per block, in order). Newly indexed pages get one
        cache-owned reference (`share`); blocks already present keep
        their existing page — the duplicate stays the caller's to
        release — and just have their LRU refreshed. Returns the number
        of pages newly indexed."""
        n_full = min(len(tokens) // self.page_size, len(pages))
        if n_full <= 0:
            return 0
        path = self.trie.extend(
            np.asarray(tokens)[: n_full * self.page_size], root_key
        )
        new = 0
        for node, page in zip(path, pages):
            if node.payload is None:
                # "cache" is the ownership-map stamp the page-pool
                # observatory classifies cache-owned pages by.
                self.allocator.share([int(page)], owner="cache")
                node.payload = int(page)
                new += 1
        self._pages += new
        if new and self.spill_enabled:
            # Blocks recomputed cold (e.g. after a failed re-upload)
            # are device-resident again: their host twins are stale
            # duplicates now — drop them so the budget holds live
            # spill value only.
            hpath = self._host.walk(
                np.asarray(tokens)[: n_full * self.page_size], root_key
            )
            for dnode, hnode in zip(path, hpath):
                if hnode.payload is not None and dnode.payload is not None:
                    self._host_forget_node(hnode)
        self._gauges()
        return new

    def evict(self, need_pages: int, *, exclude=()) -> int:
        """Free at least `need_pages` pages the cache alone holds
        (refcount 1), least-recently-used leaves first — cached pages
        are reclaimed before any live request is ever evicted. With the
        host tier armed, each victim's bytes SPILL to host RAM before
        its device page returns (the entry survives, reloadable);
        otherwise the entry dies. Returns the number of device pages
        actually freed (may be fewer: entries shared with live slots
        are pinned).

        exclude: page ids that must NOT be evicted this call. The
        reload path passes the device prefix it just matched — those
        pages are still refcount-1 (lookup takes no references; the
        requester's share lands only after reload), so without the
        exclusion an eviction round could free the very pages the
        splice is about to share."""
        exclude = {int(p) for p in exclude}
        freed = 0
        while freed < need_pages:
            # One gather per ROUND, oldest first (removing a leaf never
            # un-leafs another gathered leaf); parents exposed as new
            # leaves are picked up by the next round only if still
            # short — O(rounds x trie), not O(pages x trie).
            candidates = sorted(
                (
                    n for n in self.trie.leaves()
                    if n.payload not in exclude
                    and self.allocator.refcount(n.payload) == 1
                ),
                key=lambda n: n.stamp,
            )
            if not candidates:
                break
            for victim in candidates:
                if freed >= need_pages:
                    break
                self._spill(victim)
                self.allocator.release([victim.payload], owner="cache")
                self.trie.remove(victim)
                self._pages -= 1
                freed += 1
        if freed and self.metrics is not None:
            self.metrics.inc("prefix_cache_evicted_pages_total", freed)
        self._gauges()
        return freed

    def clear(self) -> None:
        """Drop every entry — device references AND the host tier
        (used when the scheduler rebuilds a consumed pool, and by
        degraded-mode cache shedding: a shed must actually free the
        host RAM too)."""
        for node in list(self.trie.nodes()):
            if node.payload is not None:
                self.allocator.release([node.payload], owner="cache")
        self.trie = TokenTrie(self.page_size)
        self._pages = 0
        self._host = TokenTrie(self.page_size)
        self._host_bytes = 0
        self._spilled = 0
        self._gauges()


class SessionPrefixCache:
    """Dense-cache plane: longest-prefix lookup over `PrefixCacheState`
    snapshots (serve/pipeline.py), so a fresh ChatSession over the same
    media + system prompt inherits a finished session's KV instead of
    cold-prefilling it.

    A state is reachable from EVERY node along its id stream's path —
    a new prompt diverges from a stored stream at its own question, so
    the useful hit is the deepest COMMON node, not the stored stream's
    end. `lookup` returns the state at that node; the pipeline's
    `_prefix_plan` then computes the exact longest common token prefix
    against it and re-prefills only the rest (so an over-long candidate
    only ever shortens the reuse, never corrupts it). Dense caches are
    HBM-expensive: capacity bounds the number of live states, LRU.
    """

    def __init__(self, block_size: int = 16, capacity: int = 4):
        if capacity < 1:
            raise ValueError(f"capacity must be >= 1, got {capacity}")
        self.trie = TokenTrie(block_size)
        self.capacity = capacity
        self._states: dict[int, Any] = {}  # id(state) -> state, LRU order

    @property
    def entries(self) -> int:
        return len(self._states)

    def lookup(self, flat_ids, media_key: tuple = ()):
        """The state stored at the deepest node along `flat_ids`' block
        path (LRU-refreshed), or None."""
        path = self.trie.walk(flat_ids, root_key=tuple(media_key))
        for node in reversed(path):
            if node.payload is not None:
                state = node.payload
                self._states.pop(id(state), None)
                self._states[id(state)] = state
                return state
        return None

    def insert(self, state) -> None:
        """Store `state` along its full block path (streams shorter than
        one block are not worth caching), evicting the least-recently-
        used stored state beyond capacity. States the overwrite leaves
        with no reachable node (the normal multi-turn case: each turn's
        stream extends the last, shadowing its whole path) are dropped
        immediately — an unreachable state would otherwise pin a dense
        HBM cache against capacity for zero hit value."""
        path = self.trie.extend(
            np.asarray(state.ids), root_key=tuple(state.media_key)
        )
        if not path:
            return
        displaced = {
            id(n.payload): n.payload for n in path
            if n.payload is not None and n.payload is not state
        }
        for node in path:
            node.payload = state
        self._states.pop(id(state), None)
        self._states[id(state)] = state
        if displaced:
            reachable = {
                id(n.payload) for n in self.trie.nodes()
                if n.payload is not None
            }
            for sid in displaced.keys() - reachable:
                self._states.pop(sid, None)
        while len(self._states) > self.capacity:
            _, victim = next(iter(self._states.items()))
            self._drop(victim)

    def _drop(self, state) -> None:
        self._states.pop(id(state), None)
        for node in list(self.trie.nodes()):
            if node.payload is state:
                node.payload = None
        # Prune now-useless branches (childless, payload-less).
        changed = True
        while changed:
            changed = False
            for leaf in self.trie.leaves():
                if leaf.payload is None:
                    self.trie.remove(leaf)
                    changed = True
