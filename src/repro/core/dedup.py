"""Content-addressed snapshot page store (§3.6 extension).

Serverless snapshots share runtime pages (interpreter, shared libraries); in
our analogue, snapshots of fine-tuned variants share base-model pages.  The
offset array can point anywhere in a tier, so dedup integrates at publish
time: pages are content-hashed (vectorized FNV-1a 64-bit by default; the
``kernels/page_checksum`` Pallas op plugs in behind the same ``hash_fn``
signature) and identical pages are stored ONCE with a reference count.

Refcount protocol (the ownership protocol's extension, DESIGN.md §12):

* ``put_pages`` on publish/update/re-curation — one increment per catalog
  offset that will point at the page;
* ``release_offsets`` when an owner op retires an offset array (update's
  free-old phase, delete's gc, demotion's republish) — decrements only;
* the tier byte range is freed exactly when a page's refcount reaches zero.

A hash match NEVER shares a page on its own: the candidate page's bytes are
compared against the stored bytes first (hash collisions fall back to a
separate physical page in the same bucket).  ``hash_fn`` is an injectable
seam, so tests force collisions deliberately and the Pallas checksum kernel
can replace the numpy fold on the hashing hot path.

Restore-path consequence recorded by the cost model: a deduplicated snapshot
can no longer flush/read one contiguous CXL extent; readers walk the offset
array and coalesce only *adjacent* store offsets (§3.6,
``SnapshotReader.iter_hot_extents`` / ``iter_cold_extents``).

Invariant I6 (refcount conservation, checked every sim step): each store
refcount equals the number of live catalog offsets pointing at it — see
``repro.sim.invariants``.
"""
from __future__ import annotations

import threading
from typing import Callable, Dict, List, Optional

import numpy as np

from .pagestore import PAGE_SIZE
from .pool import MemoryTier

FNV_OFFSET = np.uint64(0xCBF29CE484222325)
FNV_PRIME = np.uint64(0x100000001B3)

# hash_fn(pages_matrix uint8[N, PAGE_SIZE]) -> integer ndarray[N]
HashFn = Callable[[np.ndarray], np.ndarray]


def fnv1a_page(page: np.ndarray) -> int:
    """FNV-1a over a 4 KiB page, processed as u64 lanes (vector-friendly —
    this exact formulation is what kernels/page_checksum implements)."""
    lanes = np.ascontiguousarray(page).view(np.uint64).reshape(-1)
    h = FNV_OFFSET
    with np.errstate(over="ignore"):
        for lane in lanes:
            h = (h ^ lane) * FNV_PRIME
    return int(h)


def fnv1a_pages(pages_matrix: np.ndarray) -> np.ndarray:
    """Vectorized FNV-1a per page row. pages_matrix: uint8[N, PAGE_SIZE]."""
    lanes = pages_matrix.view(np.uint64).reshape(pages_matrix.shape[0], -1)
    h = np.full(pages_matrix.shape[0], FNV_OFFSET, dtype=np.uint64)
    with np.errstate(over="ignore"):
        for j in range(lanes.shape[1]):
            h = (h ^ lanes[:, j]) * FNV_PRIME
    return h


def pallas_hash_fn(pages_matrix: np.ndarray) -> np.ndarray:
    """The TPU-shaped alternative: the ``page_checksum`` polynomial rolling
    hash (the compiled kernel on TPU, the jnp oracle elsewhere), adapted to
    the ``HashFn`` signature.  Weaker (32-bit) than FNV-1a-64, which is fine
    — the store byte-verifies every hash match before sharing."""
    from ..kernels.backend import on_tpu
    from ..kernels.page_checksum.ops import page_checksum

    return np.asarray(page_checksum(pages_matrix, use_pallas=on_tpu()))


# Marker consumed by the fused publish path (core/snapshot.py): the fused
# sweep's checksum column IS this hash, so a store hashing with it can be
# handed the precomputed values (put_pages(..., hashes=...)) and skip its
# own streaming pass over the batch.
pallas_hash_fn.is_poly32 = True


class DedupStore:
    """Content-addressed, refcounted page store inside one tier.

    The store owns its pages' tier allocations: callers never ``tier.free``
    a deduped page directly — they :meth:`release` their reference and the
    store frees the byte range when the last reference drops.
    """

    def __init__(self, tier: MemoryTier, hash_fn: Optional[HashFn] = None):
        self.tier = tier
        tier.dedup_store = self   # checksum repair resolves store from tier
        self.hash_fn = hash_fn or fnv1a_pages
        # hash -> [offset, ...]: collisions coexist in one bucket, each
        # offset holding distinct bytes (verified before every share)
        self._buckets: Dict[int, List[int]] = {}
        self._refs: Dict[int, int] = {}          # offset -> refcount
        self._hash_of: Dict[int, int] = {}       # offset -> hash (for release)
        self._quarantined: set = set()           # offsets barred from sharing
        self._lock = threading.RLock()
        self.stats = {"unique": 0, "dedup_hits": 0, "collisions": 0,
                      "released": 0, "freed": 0, "quarantined": 0,
                      "rematerialized": 0}

    # -- internal (lock held) -------------------------------------------------
    def _match(self, h: int, page_row: np.ndarray) -> Optional[int]:
        """Offset of a stored page with hash `h` AND equal bytes, else None."""
        for off in self._buckets.get(h, ()):
            if np.array_equal(self.tier.buf[off : off + PAGE_SIZE], page_row):
                return off
        return None

    def _store_new(self, h: int, page_row: np.ndarray) -> int:
        off = self.tier.alloc(PAGE_SIZE)
        self.tier.write(off, page_row)
        bucket = self._buckets.setdefault(h, [])
        if bucket:
            self.stats["collisions"] += 1
        bucket.append(off)
        self._refs[off] = 1
        self._hash_of[off] = h
        self.stats["unique"] += 1
        return off

    # -- write side -----------------------------------------------------------
    def put_pages(self, pages_matrix: np.ndarray,
                  hashes: Optional[np.ndarray] = None) -> np.ndarray:
        """Store (or reference) every row; returns int64 tier byte offsets.

        Hashing is vectorized over the whole batch; per-row work is dict
        lookups plus a byte-compare only on hash match.  On a mid-batch
        tier ``AllocError`` the rows already referenced by THIS call are
        released again, so a failed put leaves the store unchanged.

        ``hashes`` MUST be this store's own ``hash_fn`` outputs for exactly
        these rows (the fused publish sweep precomputes them in the same
        pass that compacts the pages); passing foreign hashes would split
        identical content across buckets and silently disable sharing.
        """
        mat = np.ascontiguousarray(pages_matrix).view(np.uint8)
        mat = mat.reshape(-1, PAGE_SIZE)
        if mat.shape[0] == 0:
            return np.zeros(0, dtype=np.int64)
        if hashes is None:
            hashes = np.asarray(self.hash_fn(mat))
        else:
            hashes = np.asarray(hashes)
            assert hashes.shape[0] == mat.shape[0], \
                f"precomputed hashes: {hashes.shape[0]} != {mat.shape[0]} rows"
        offs = np.empty(mat.shape[0], dtype=np.int64)
        with self._lock:
            done = 0
            try:
                for i in range(mat.shape[0]):
                    h = int(hashes[i])
                    off = self._match(h, mat[i])
                    if off is not None:
                        self._refs[off] += 1
                        self.stats["dedup_hits"] += 1
                    else:
                        off = self._store_new(h, mat[i])
                    offs[i] = off
                    done = i + 1
            except Exception:
                for off in offs[:done]:
                    self._release_locked(int(off))
                raise
        return offs

    def put(self, page: np.ndarray) -> int:
        """Store (or reference) a single page; returns its tier byte offset."""
        return int(self.put_pages(page.reshape(1, -1))[0])

    def probe_new_bytes(self, pages_matrix: np.ndarray) -> int:
        """Tier bytes :meth:`put_pages` would NEWLY allocate for this batch —
        distinct page contents not already stored — without storing anything.
        The capacity manager admits dedup publishes on this marginal size."""
        mat = np.ascontiguousarray(pages_matrix).view(np.uint8)
        mat = mat.reshape(-1, PAGE_SIZE)
        if mat.shape[0] == 0:
            return 0
        hashes = np.asarray(self.hash_fn(mat))
        new_pages = 0
        batch_seen: Dict[int, List[int]] = {}   # hash -> row indices counted new
        with self._lock:
            for i in range(mat.shape[0]):
                h = int(hashes[i])
                if self._match(h, mat[i]) is not None:
                    continue
                dup_in_batch = any(np.array_equal(mat[j], mat[i])
                                   for j in batch_seen.get(h, ()))
                if not dup_in_batch:
                    batch_seen.setdefault(h, []).append(i)
                    new_pages += 1
        return new_pages * PAGE_SIZE

    # -- release side ---------------------------------------------------------
    def _release_locked(self, offset: int) -> None:
        rc = self._refs.get(offset)
        if rc is None:
            raise ValueError(f"release of unknown dedup offset {offset}")
        self.stats["released"] += 1
        if rc > 1:
            self._refs[offset] = rc - 1
            return
        h = self._hash_of.pop(offset)
        del self._refs[offset]
        bucket = self._buckets.get(h, [])
        if offset in bucket:          # a quarantined offset left its bucket
            bucket.remove(offset)
        if not bucket:
            self._buckets.pop(h, None)
        self._quarantined.discard(offset)
        self.tier.free(offset, PAGE_SIZE)
        self.stats["freed"] += 1

    def release(self, offset: int) -> None:
        """Drop one reference; frees the tier page at refcount zero."""
        with self._lock:
            self._release_locked(int(offset))

    def release_offsets(self, offsets: np.ndarray) -> None:
        """Batch :meth:`release` (an offset array being retired: each slot
        is one reference, so duplicates decrement once per occurrence)."""
        with self._lock:
            for off in np.asarray(offsets, dtype=np.int64):
                self._release_locked(int(off))

    def drop(self, page: np.ndarray) -> None:
        """Release one reference by CONTENT (hash + byte-match); unknown
        pages are ignored.  Offset-based :meth:`release` is the protocol
        path — this form serves callers that never kept the offset."""
        mat = np.ascontiguousarray(page).view(np.uint8).reshape(1, PAGE_SIZE)
        h = int(np.asarray(self.hash_fn(mat))[0])
        with self._lock:
            off = self._match(h, mat[0])
            if off is not None:
                self._release_locked(off)

    # -- checksum repair (DESIGN.md §15) --------------------------------------
    def quarantine(self, offset: int) -> bool:
        """Bar a suspect offset from NEW sharing: its hash-bucket entry is
        removed so no future publish matches it, while existing references
        stay (I6 refcount conservation is untouched — live offset arrays
        still point here and release normally).  Returns False for offsets
        the store does not own or that are already quarantined."""
        offset = int(offset)
        with self._lock:
            h = self._hash_of.get(offset)
            if h is None or offset in self._quarantined:
                return False
            self._quarantined.add(offset)
            bucket = self._buckets.get(h, [])
            if offset in bucket:
                bucket.remove(offset)
            if not bucket:
                self._buckets.pop(h, None)
            self.stats["quarantined"] += 1
            return True

    def rematerialize(self, offset: int, page_row: np.ndarray) -> None:
        """Scrub a quarantined offset with verified-clean bytes (the owner's
        ``reconstruct_image``-style re-read) and restore its bucket entry so
        the content is shareable again.  The bytes MUST hash to the offset's
        recorded hash — re-materializing different content would corrupt
        every snapshot referencing it."""
        offset = int(offset)
        mat = np.ascontiguousarray(page_row).view(np.uint8).reshape(1, PAGE_SIZE)
        h = int(np.asarray(self.hash_fn(mat))[0])
        with self._lock:
            if offset not in self._quarantined:
                raise ValueError(f"offset {offset} is not quarantined")
            if h != self._hash_of[offset]:
                raise ValueError(
                    f"rematerialize hash mismatch at offset {offset}: "
                    f"{h:#x} != recorded {self._hash_of[offset]:#x}")
            self.tier.write(offset, mat[0])
            self._quarantined.discard(offset)
            self._buckets.setdefault(h, []).append(offset)
            self.stats["rematerialized"] += 1

    def quarantined_offsets(self) -> List[int]:
        with self._lock:
            return sorted(self._quarantined)

    # -- introspection --------------------------------------------------------
    def refcounts(self) -> Dict[int, int]:
        """offset -> refcount snapshot (the I6 checker's ground truth)."""
        with self._lock:
            return dict(self._refs)

    def unique_pages(self) -> int:
        with self._lock:
            return len(self._refs)

    def unique_bytes(self) -> int:
        """Physical tier bytes currently owned by the store."""
        return self.unique_pages() * PAGE_SIZE

    def logical_pages(self) -> int:
        """Sum of refcounts == pages the catalog believes it stores."""
        with self._lock:
            return sum(self._refs.values())

    def dedup_ratio(self) -> float:
        total = self.stats["unique"] + self.stats["dedup_hits"]
        return self.stats["dedup_hits"] / total if total else 0.0

    def report(self) -> Dict[str, float]:
        with self._lock:
            unique = len(self._refs)
            logical = sum(self._refs.values())
        return {
            "unique_pages": unique,
            "logical_pages": logical,
            "unique_bytes": unique * PAGE_SIZE,
            "logical_bytes": logical * PAGE_SIZE,
            "dedup_ratio": self.dedup_ratio(),
            **self.stats,
        }
