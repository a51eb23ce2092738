"""Paged KV-cache: fixed-size token pages, free-list allocator, block tables.

Replaces the engine's dense per-slot ``(P, B, max_len, H, hd)`` caches with a
shared pool of pages, vLLM-style: KV memory scales with the tokens actually
resident instead of ``max_batch * max_len``.  Two halves:

  * ``PageAllocator`` — pure-Python bookkeeping (free list, per-request block
    tables, committed token counts).  No JAX; unit-testable in isolation.
  * ``PagedKVCache`` — the device arrays, one (k, v) page pool per
    attention-bearing position of ``cfg.block_pattern`` (leading ``periods``
    dim, like the dense caches), plus ONE shared position pool (the token
    layout is identical across layers).  Gather/scatter helpers are pure
    functions over arrays so engine code can jit around them.

Layout per attention position:  k_pages (Pd, N+1, Hkv, page_size, hd),
head-major so that the paged kernels' ``(page_size, hd)`` block per KV head
spans the pool's trailing dims (kernels/flash_decode.py says why the TPU
needs that).  Page index N is a reserved scratch page: batched-decode
scatters from inactive slots are routed there, so the update stays a single
dynamic scatter with no masking inside the kernel.
"""
from __future__ import annotations

from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding
from jax.sharding import PartitionSpec as P

from repro.config import ModelConfig
from repro.layers.heads import head_layout

# block kinds that own a KV cache (mirrors models/decoder.init_caches)
KV_KINDS = ("attn_mlp", "attn_moe", "hybrid", "dec_block")


class OutOfPages(RuntimeError):
    """Raised by PageAllocator when the pool cannot satisfy a request; the
    scheduler turns this into preemption-by-eviction."""


def pages_for(n_tokens: int, page_size: int) -> int:
    return -(-n_tokens // page_size)


class PageAllocator:
    """Free-list page allocator with per-request block tables and refcounted
    prefix/page sharing.

    Invariants (asserted in tests):
      * free + unique-allocated == num_pages, always;
      * a page's refcount equals the number of block tables referencing it
        (no aliasing beyond declared sharing, no double-free);
      * a freshly handed-out page (``ensure`` growth or ``cow`` copy target)
        comes from the free list — never a page another request still holds;
      * a request's capacity ``len(table) * page_size`` always covers its
        committed token count.
    """

    def __init__(self, num_pages: int, page_size: int, trace=None):
        assert num_pages > 0 and page_size > 0
        self.num_pages = num_pages
        self.page_size = page_size
        self._free: List[int] = list(range(num_pages - 1, -1, -1))
        self._free_set = set(self._free)
        self.tables: Dict[int, List[int]] = {}
        self.lengths: Dict[int, int] = {}
        self.refcount: Dict[int, int] = {}        # page -> #tables holding it
        # optional obs.TraceRing: every pool mutation narrates itself
        # (alloc/free/cow/adopt) so a trace replay can prove conservation —
        # pages_allocated - pages_freed == used_pages.  None = silent.
        self.trace = trace

    def _emit(self, kind: str, rid: int, **payload) -> None:
        if self.trace is not None:
            self.trace.emit(kind, rid=rid, free=len(self._free),
                            used=self.used_pages, **payload)

    # ---- queries ----------------------------------------------------------
    @property
    def free_pages(self) -> int:
        return len(self._free)

    @property
    def used_pages(self) -> int:
        return self.num_pages - len(self._free)

    def capacity(self, rid: int) -> int:
        return len(self.tables.get(rid, ())) * self.page_size

    def tokens(self, rid: int) -> int:
        return self.lengths.get(rid, 0)

    def can_fit(self, rid: int, n_tokens: int) -> bool:
        need = pages_for(n_tokens, self.page_size) - len(self.tables.get(rid, ()))
        return need <= len(self._free)

    def shared_pages(self) -> int:
        """Pages referenced by more than one block table."""
        return sum(1 for rc in self.refcount.values() if rc > 1)

    def page_shared(self, pg: int) -> bool:
        return self.refcount.get(pg, 0) > 1

    def logical_tokens(self) -> int:
        """Tokens committed across requests (counts shared pages per sharer)."""
        return sum(self.lengths.values())

    def utilization(self) -> float:
        """Committed tokens per allocated page slot.  Can exceed 1.0 when
        prefix sharing packs several requests' tokens onto one page."""
        used = self.used_pages * self.page_size
        if not used:
            return 1.0
        return sum(self.lengths.values()) / used

    def fragmentation(self) -> int:
        """Allocated-but-empty token slots (tail waste of partial pages);
        floored at 0 under sharing (shared slots count once)."""
        return max(0, self.used_pages * self.page_size
                   - sum(self.lengths.values()))

    # ---- mutation ---------------------------------------------------------
    def ensure(self, rid: int, n_tokens: int) -> None:
        """Grow ``rid``'s block table so it can hold ``n_tokens`` total tokens.
        Raises OutOfPages (allocating nothing) if the pool can't cover it."""
        table = self.tables.setdefault(rid, [])
        need = pages_for(n_tokens, self.page_size) - len(table)
        if need <= 0:
            return
        if need > len(self._free):
            if not self.tables[rid]:
                del self.tables[rid]
            raise OutOfPages(f"need {need} pages, {len(self._free)} free")
        for _ in range(need):
            pg = self._free.pop()
            self._free_set.discard(pg)
            assert self.refcount.get(pg, 0) == 0, \
                f"free list handed out live page {pg}"
            self.refcount[pg] = 1
            table.append(pg)
        self._emit("alloc", rid, n=need)

    def commit(self, rid: int, n_tokens: int) -> None:
        """Record ``n_tokens`` more live tokens for ``rid`` (capacity must
        already exist via ``ensure``)."""
        new = self.lengths.get(rid, 0) + n_tokens
        assert new <= self.capacity(rid), (rid, new, self.capacity(rid))
        self.lengths[rid] = new

    def adopt(self, rid: int, pages: List[int], n_tokens: int) -> None:
        """Map another request's prefix ``pages`` into fresh request ``rid``'s
        table (prefix sharing): refcounts bump, ``n_tokens`` are committed as
        already resident.  The donor keeps its pages; nothing is copied."""
        assert rid not in self.tables, f"adopt into non-fresh request {rid}"
        assert n_tokens <= len(pages) * self.page_size
        for pg in pages:
            assert self.refcount.get(pg, 0) > 0, f"adopting dead page {pg}"
            assert pg not in self._free_set
            self.refcount[pg] += 1
        self.tables[rid] = list(pages)
        self.lengths[rid] = n_tokens
        self._emit("adopt", rid, n_pages=len(pages), tokens=n_tokens)

    def cow(self, rid: int, block_idx: int) -> Optional[Tuple[int, int]]:
        """Copy-on-write: give ``rid`` a private copy of a shared page before
        it writes into it.  Returns (old_page, new_page) for the device-side
        content copy, or None if the page was already exclusive.  Raises
        OutOfPages (mutating nothing) when no free page is available."""
        table = self.tables[rid]
        old = table[block_idx]
        if self.refcount.get(old, 0) <= 1:
            return None
        if not self._free:
            raise OutOfPages(f"cow of page {old}: no free pages")
        new = self._free.pop()
        self._free_set.discard(new)
        assert self.refcount.get(new, 0) == 0, \
            f"free list handed out live page {new}"
        self.refcount[new] = 1
        self.refcount[old] -= 1
        table[block_idx] = new
        # the copy target counts as an allocation for conservation (the old
        # page stays live with the other sharers)
        self._emit("alloc", rid, n=1)
        self._emit("cow", rid, old=old, new=new)
        return old, new

    def free(self, rid: int) -> List[int]:
        """Drop all of ``rid``'s page references.  Returns the pages whose
        refcount hit zero (actually released — the caller scrubs only those;
        pages still shared by another request stay live)."""
        table = self.tables.pop(rid, [])
        self.lengths.pop(rid, None)
        released = []
        rc_drops = 0
        for pg in table:
            assert pg not in self._free_set, f"double free of page {pg}"
            rc = self.refcount.get(pg, 0)
            assert rc > 0, f"freeing page {pg} with refcount 0"
            if rc == 1:
                del self.refcount[pg]
                self._free.append(pg)
                self._free_set.add(pg)
                released.append(pg)
            else:
                self.refcount[pg] = rc - 1
                rc_drops += 1
        if released:
            self._emit("free", rid, n=len(released))
        if rc_drops:
            # a sharer dropping its refcount releases nothing, so it is
            # invisible to the free/alloc conservation pair — narrate it as
            # its own (replay-neutral) event so cross-allocator accounting
            # can balance shared pages (tests/test_disagg.py)
            self._emit("rc_drop", rid, n=rc_drops)
        return released

    def import_tables(self, tables: Dict[int, List[int]],
                      lengths: Dict[int, int]) -> Dict[int, int]:
        """Adopt exported block tables into THIS pool (serving/kvstate.py
        page migration): ``tables`` reference export-local page ids; every
        distinct id gets one fresh page from the free list (so sharing
        structure among the imported requests is preserved, refcounts equal
        to the number of importing tables).  Returns the local-id -> new-page
        mapping for the device-side payload scatter.  Raises OutOfPages
        (mutating nothing) when the free list can't cover the distinct-page
        count — the disagg router's defer-and-retry path."""
        local_ids = sorted({pg for t in tables.values() for pg in t})
        for rid in tables:
            assert rid not in self.tables, f"import into live request {rid}"
            assert rid in lengths, rid
        if len(local_ids) > len(self._free):
            raise OutOfPages(f"import needs {len(local_ids)} pages, "
                             f"{len(self._free)} free")
        mapping: Dict[int, int] = {}
        for lid in local_ids:
            pg = self._free.pop()
            self._free_set.discard(pg)
            assert self.refcount.get(pg, 0) == 0, \
                f"free list handed out live page {pg}"
            mapping[lid] = pg
        for rid, t in tables.items():
            new_t = [mapping[lid] for lid in t]
            for pg in new_t:
                self.refcount[pg] = self.refcount.get(pg, 0) + 1
            self.tables[rid] = new_t
            self.lengths[rid] = lengths[rid]
            assert self.lengths[rid] <= len(new_t) * self.page_size
        self._emit("alloc", next(iter(tables), -1), n=len(local_ids))
        return mapping

    # ---- serialization ----------------------------------------------------
    def snapshot(self) -> Dict[str, Any]:
        """Full allocator state as a plain JSON-able dict (dict keys become
        strings; ``restore`` converts back).  Free-list ORDER is preserved so
        a restored allocator hands out pages in the identical sequence."""
        return {"num_pages": self.num_pages, "page_size": self.page_size,
                "free": list(self._free),
                "tables": {str(r): list(t) for r, t in self.tables.items()},
                "lengths": {str(r): n for r, n in self.lengths.items()},
                "refcount": {str(p): rc for p, rc in self.refcount.items()}}

    def restore(self, snap: Dict[str, Any]) -> None:
        """Overwrite this allocator's state from a ``snapshot()`` dict (the
        geometry must match) and re-check the structural invariants."""
        assert snap["num_pages"] == self.num_pages, \
            (snap["num_pages"], self.num_pages)
        assert snap["page_size"] == self.page_size, \
            (snap["page_size"], self.page_size)
        self._free = [int(p) for p in snap["free"]]
        self._free_set = set(self._free)
        self.tables = {int(r): [int(p) for p in t]
                       for r, t in snap["tables"].items()}
        self.lengths = {int(r): int(n) for r, n in snap["lengths"].items()}
        self.refcount = {int(p): int(rc)
                         for p, rc in snap["refcount"].items()}
        self.check()

    def check(self) -> None:
        """Structural invariants (asserted after ``restore`` and by the
        round-trip property tests): free + unique-allocated == num_pages, a
        page's refcount equals the number of tables referencing it, no page
        is both free and referenced, and every request's committed tokens
        fit its capacity."""
        allocated = {pg for t in self.tables.values() for pg in t}
        assert not (allocated & self._free_set), \
            f"pages both free and allocated: {allocated & self._free_set}"
        assert len(self._free) == len(self._free_set), "free-list duplicates"
        assert len(self._free) + len(allocated) == self.num_pages, \
            (len(self._free), len(allocated), self.num_pages)
        refs: Dict[int, int] = {}
        for t in self.tables.values():
            for pg in t:
                refs[pg] = refs.get(pg, 0) + 1
        assert refs == self.refcount, (refs, self.refcount)
        for rid, n in self.lengths.items():
            assert n <= len(self.tables.get(rid, ())) * self.page_size, \
                (rid, n)

    def block_table(self, rid: int, max_blocks: int) -> np.ndarray:
        """Padded (-1) block table row of static width ``max_blocks``."""
        table = self.tables.get(rid, [])
        assert len(table) <= max_blocks, (rid, len(table), max_blocks)
        row = np.full(max_blocks, -1, np.int32)
        row[:len(table)] = table
        return row

    def stats(self) -> Dict[str, Any]:
        return {"num_pages": self.num_pages, "page_size": self.page_size,
                "free_pages": self.free_pages, "used_pages": self.used_pages,
                "shared_pages": self.shared_pages(),
                "logical_tokens": self.logical_tokens(),
                "utilization": self.utilization(),
                "fragmentation_tokens": self.fragmentation()}


class PrefixCache:
    """Hash index over committed prompt prefixes for page sharing.

    Every admitted request registers its prompt: one hash per page-aligned
    prefix (``tokens[:k * page_size]`` for each full page ``k``).  A new
    request looks up the LONGEST page-aligned prefix of its own prompt that
    matches a live donor (hash first, then exact token verification — hash
    collisions can suggest, never corrupt), then extends token-by-token into
    the donor's next page so a partially-matching page can be shared too
    (the engine CoWs it before the sharer's first divergent write).

    The index holds request ids, not pages: validity is re-checked against
    the allocator at lookup time, so donor eviction/free needs no eager
    invalidation — a dead donor simply stops matching.
    """

    def __init__(self, page_size: int):
        self.ps = page_size
        self._prompts: Dict[int, np.ndarray] = {}       # rid -> prompt tokens
        self._by_hash: Dict[int, List[int]] = {}        # prefix hash -> rids

    @staticmethod
    def _h(tokens: np.ndarray) -> int:
        return hash(np.asarray(tokens, np.int32).tobytes())

    def register(self, rid: int, prompt: np.ndarray) -> None:
        if rid in self._prompts:
            return                            # re-admission after preemption
        prompt = np.asarray(prompt, np.int32)
        self._prompts[rid] = prompt
        for k in range(1, len(prompt) // self.ps + 1):
            self._by_hash.setdefault(self._h(prompt[:k * self.ps]),
                                     []).append(rid)

    def forget(self, rid: int) -> None:
        prompt = self._prompts.pop(rid, None)
        if prompt is None:
            return
        for k in range(1, len(prompt) // self.ps + 1):
            h = self._h(prompt[:k * self.ps])
            rids = self._by_hash.get(h, [])
            if rid in rids:
                rids.remove(rid)
            if not rids:
                self._by_hash.pop(h, None)

    def snapshot(self) -> Dict[str, Any]:
        """Registered prompts as a JSON-able dict.  The hash index is NOT
        serialized: ``hash(bytes)`` is salted per process, so ``restore``
        rebuilds it from the prompts (re-registration is the one canonical
        index constructor — a stale serialized index could never be
        verified)."""
        return {"page_size": self.ps,
                "prompts": {str(r): [int(t) for t in p]
                            for r, p in self._prompts.items()}}

    def restore(self, snap: Dict[str, Any]) -> None:
        assert snap["page_size"] == self.ps, (snap["page_size"], self.ps)
        self._prompts = {}
        self._by_hash = {}
        for r, toks in snap["prompts"].items():
            self.register(int(r), np.asarray(toks, np.int32))

    def lookup(self, prompt: np.ndarray, alloc: "PageAllocator",
               exclude: int = -1):
        """Best live donor for ``prompt``.  Returns (donor_rid, shared_tokens,
        shared_pages) or None.  ``shared_tokens`` is capped at
        ``len(prompt) - 1`` so the sharer always prefills at least one token
        (it needs last-position logits to sample)."""
        prompt = np.asarray(prompt, np.int32)
        ps = self.ps
        for k in range(len(prompt) // ps, 0, -1):
            for rid in self._by_hash.get(self._h(prompt[:k * ps]), ()):
                if rid == exclude or rid not in alloc.tables:
                    continue
                donor = self._prompts.get(rid)
                if donor is None or len(donor) < k * ps or \
                        not np.array_equal(donor[:k * ps], prompt[:k * ps]):
                    continue
                if alloc.tokens(rid) < k * ps or \
                        len(alloc.tables[rid]) < k:
                    continue                  # donor hasn't prefilled this far
                # extend token-wise into the donor's page k (partial share)
                limit = min(len(prompt) - 1, len(donor), alloc.tokens(rid),
                            len(alloc.tables[rid]) * ps)
                t = k * ps
                while t < limit and donor[t] == prompt[t]:
                    t += 1
                t = min(t, len(prompt) - 1)
                if t <= 0:
                    continue
                n_pages = pages_for(t, ps)
                return rid, t, list(alloc.tables[rid][:n_pages])
        return None


# ---------------------------------------------------------------------------
# device arrays + pure gather/scatter
# ---------------------------------------------------------------------------

def token_page_coords(positions, block_table, page_size: int, scratch: int):
    """Map absolute token positions -> (page_id, offset) through a block table.

    positions: (T,) int32; block_table: (MB,) int32 (-1 pad).  Entries whose
    block-table slot is unallocated map to the scratch page.
    """
    blk = positions // page_size
    page = jnp.where(blk < block_table.shape[0],
                     block_table[jnp.clip(blk, 0, block_table.shape[0] - 1)],
                     -1)
    page = jnp.where(page < 0, scratch, page)
    return page, positions % page_size


def window_page_coords(lengths, block_tables, k_tokens: int, page_size: int,
                       scratch: int, decode_mask=None):
    """Map a K-token decode window's positions -> (page, off, ok, positions)
    through per-request block tables (the batched sibling of
    ``token_page_coords``; shared by core/iso's KV scatter and the paged
    engine's pos-array update so their validity rules cannot drift).

    lengths: (B,) int32; block_tables: (B, MB) int32 (-1 pad); window token
    qi sits at position ``lengths[b] + qi``; ``decode_mask``: optional (B,)
    bool of slots really decoding.  All returns are (B, K): ``ok`` marks
    positions landing in a live page of an active slot — everything else has
    ``page`` already routed to ``scratch`` (and must record pos -1).
    """
    positions = (lengths[:, None].astype(jnp.int32)
                 + jnp.arange(k_tokens, dtype=jnp.int32)[None])
    blk = positions // page_size
    MB = block_tables.shape[1]
    page = jnp.take_along_axis(block_tables, jnp.clip(blk, 0, MB - 1), axis=1)
    ok = (page >= 0) & (blk < MB)
    if decode_mask is not None:
        ok &= decode_mask[:, None]
    page = jnp.where(ok, page, scratch)
    return page, positions % page_size, ok, positions


def scatter_pages(pages: jnp.ndarray, page, off, kv) -> jnp.ndarray:
    """Write token KV into a head-major pool at (page, offset) coordinates.

    pages: (Pd, N+1, Hkv, ps, hd), page N the scratch page; page/off: int
    (*R, T), each row a run of T tokens at consecutive positions (a decode
    window or a prefill grant) whose pad and inactive tokens are routed to
    the scratch page; kv: (Pd, *R, T, Hkv, hd) token-major values.  Returns
    the updated pool, equal to a token-by-token write on every live page.

    Layout rule: the update window must be contiguous in the head-major
    layout, or XLA relayouts the whole pool around the write (a pool-sized
    copy each way, every call).  A token's ``(Hkv, hd)`` values are not
    contiguous there, since ``ps`` lies between them; a page's
    ``(Hkv, ps, hd)`` is.  So the writer reads each page a row touches,
    merges the row's tokens into it, and writes it back whole."""
    Pd, n_pages, Hkv, ps, hd = pages.shape
    T = page.shape[-1]
    page, off = page.reshape(-1, T), off.reshape(-1, T)
    # head-major like the pool, so the merged pages come out in its order
    kv = kv.reshape(Pd, -1, T, Hkv, hd).astype(pages.dtype).swapaxes(2, 3)
    R = page.shape[0]
    n_tiles = (T + ps - 2) // ps + 1        # pages T consecutive tokens span
    # the token each slot of the row's pages receives (outside [0, T): none)
    tok = jnp.arange(n_tiles * ps, dtype=jnp.int32)[None] - off[:, :1]
    first = jnp.clip(tok[:, ::ps], 0, T - 1)
    tile = jnp.where(tok[:, ::ps] < T,
                     jnp.take_along_axis(page, first, axis=1), n_pages - 1)
    tok_c = jnp.clip(tok, 0, T - 1)
    mine = (tok >= 0) & (tok < T) & (
        jnp.take_along_axis(page, tok_c, axis=1) == jnp.repeat(tile, ps, 1))
    # slide each row's tokens to their page slots: pad, then one slice a row
    kv = jnp.pad(kv, ((0, 0), (0, 0), (0, 0), (ps, n_tiles * ps - T), (0, 0)))
    new = jax.vmap(lambda x, o: jax.lax.dynamic_slice_in_dim(
        x, ps - o, n_tiles * ps, axis=2), in_axes=(1, 0), out_axes=1)(
            kv, off[:, 0])
    new = new.reshape(Pd, R, Hkv, n_tiles, ps, hd).swapaxes(2, 3)
    mine = mine.reshape(1, R, n_tiles, 1, ps, 1)
    return pages.at[:, tile].set(jnp.where(mine, new, pages[:, tile]))


def gather_pages(pages: jnp.ndarray, block_tables: jnp.ndarray) -> jnp.ndarray:
    """Head-major pages (Pd, N, Hkv, ps, hd), block_tables (B, MB) -> dense
    token-major (Pd, B, MB*ps, Hkv, hd).

    Padded (-1) table entries gather page 0 but are masked by the caller via
    ``gather_positions`` (their positions come back -1)."""
    Pd, _, Hkv, ps, hd = pages.shape
    B, MB = block_tables.shape
    g = pages[:, jnp.maximum(block_tables, 0)]      # (Pd, B, MB, Hkv, ps, hd)
    return g.transpose(0, 1, 2, 4, 3, 5).reshape(Pd, B, MB * ps, Hkv, hd)


def gather_positions(pos_pages: jnp.ndarray, block_tables: jnp.ndarray
                     ) -> jnp.ndarray:
    """pos_pages (N, ps), block_tables (B, MB) -> (B, MB*ps) int32, -1 invalid."""
    B, MB = block_tables.shape
    ps = pos_pages.shape[1]
    g = pos_pages[jnp.maximum(block_tables, 0)]     # (B, MB, ps)
    g = jnp.where((block_tables >= 0)[:, :, None], g, -1)
    return g.reshape(B, MB * ps)


class PagedKVCache:
    """Owns the page pools.  All arrays live in a dict pytree so jitted engine
    closures can take/return them wholesale."""

    def __init__(self, cfg: ModelConfig, num_pages: int, page_size: int,
                 tp: int = 1, dtype=jnp.bfloat16, sharding=None):
        self.cfg = cfg
        self.num_pages = num_pages            # usable pages (scratch excluded)
        self.page_size = page_size
        n = len(cfg.block_pattern)
        periods = cfg.num_layers // n
        layout = head_layout(cfg.num_heads, max(cfg.num_kv_heads, 1), tp)
        hkv = layout.hkv_eff                  # single-device engine: global view
        hd = cfg.resolved_head_dim
        self.kv_positions = tuple(i for i, kind in enumerate(cfg.block_pattern)
                                  if kind in KV_KINDS)
        # ``sharding`` places each pool at creation (TP serving shards the
        # head axis over the mesh), so no device ever holds the whole pool
        shape = (periods, num_pages + 1, hkv, page_size, hd)
        k_pages = [jnp.zeros(shape, dtype, device=sharding)
                   for _ in self.kv_positions]
        v_pages = [jnp.zeros(shape, dtype, device=sharding)
                   for _ in self.kv_positions]
        pos_sharding = None if sharding is None else \
            NamedSharding(sharding.mesh, P())
        self.arrays: Dict[str, Any] = {
            "k": tuple(k_pages), "v": tuple(v_pages),
            "pos": jnp.full((num_pages + 1, page_size), -1, jnp.int32,
                            device=pos_sharding),
        }

    @property
    def scratch_page(self) -> int:
        return self.num_pages

    def page_bytes(self) -> int:
        """KV bytes per page across all layers (k and v)."""
        return sum(2 * k[:, 0].size * k.dtype.itemsize for k in self.arrays["k"])

    def kv_bytes(self, allocator: PageAllocator) -> int:
        """Live KV footprint: bytes of pages actually allocated to requests."""
        return allocator.used_pages * self.page_bytes()

    def total_bytes(self) -> int:
        leaves = jax.tree_util.tree_leaves(self.arrays)
        return sum(l.size * l.dtype.itemsize for l in leaves)
