"""Dispatch wrappers for the fused snapshot data plane.

``fused_publish``   — one sweep: zero bitmap + poly checksum/dedup hash +
                      hot/cold compaction.  Plugs into ``build_snapshot``
                      via the ``publish_fn`` seam (``make_fused_publish_fn``).
``fused_restore``   — one kernel: gather-from-chunk → checksum-verify →
                      scatter-into-guest-frame.
``FusedScatter``    — ``fused_restore`` adapted to the serving layer's
                      ``ScatterFn`` signature ``(dest, compact, indices) ->
                      dest``; optionally bound to a snapshot's publish-time
                      checksum table, in which case every installed page's
                      checksum, computed by the kernel that installs it, is
                      compared with the table: per call, or once at the end
                      of a bulk install (``PendingChecks``).

The backend is explicit and never falls back.  ``use_pallas=False`` runs the
numpy oracle on host page matrices (the restore updates them in place);
``use_pallas=True`` runs the Pallas kernels on device arrays, compiled, or in
the interpreter where a test asks with ``interpret=True``.  A kernel-backed
``FusedScatter`` owns the restoring instance's memory: one donated device
page array that every install writes into.  :func:`default_publish_fn` and
:func:`default_scatter_fn` decide the data plane once, from the backend: the
compiled kernels on a TPU, the host path elsewhere.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from ...spans import SCATTER_LAUNCH, SCATTER_STAGE, SCATTER_VERIFY, span
from ..backend import on_tpu
from ..layout import LANES, page_tiles, page_words, weight_tile
from .kernel import fused_publish_pallas, fused_restore_pallas
from .ref import fused_publish_ref, fused_restore_ref

# The publish kernel sweeps the image in slabs of this many pages (256 MiB
# of 4 KiB pages): its compacted outputs are two slab-sized buffers, not two
# image-sized ones, so a multi-GiB image publishes beside the HBM it lives in.
SLAB_PAGES = 1 << 16

# The restore kernel installs batches of at most this many pages (the hot
# chunk, ``RestoreEngine.HOT_CHUNK_PAGES``); longer batches (whole cold runs)
# are split, so only the power-of-two batch sizes up to it ever compile.
MAX_BATCH_PAGES = 256


class ChecksumMismatchError(RuntimeError):
    """A restored page's checksum disagreed with the publish-time record.

    ``bad_pages`` is the structured payload — a 1-D int64 array of the
    failing GUEST page indices — which the serving layer's checksum-repair
    path consumes (``RestoreEngine._install_verified``).  The message stays
    human-readable and truncated no matter how many pages failed.  ``dest``
    is the page memory after the failed batch was installed: the kernel path
    donates its input, so the caller keeps this array instead.
    """

    MAX_SHOWN = 8

    def __init__(self, pages: np.ndarray, dest=None):
        self.bad_pages = np.atleast_1d(
            np.asarray(pages, dtype=np.int64)).reshape(-1)
        self.dest = dest
        shown = self.bad_pages[: self.MAX_SHOWN].tolist()
        extra = self.bad_pages.size - len(shown)
        super().__init__(
            f"checksum mismatch on {self.bad_pages.size} restored page(s): "
            f"{shown}{f' (+{extra} more)' if extra > 0 else ''}")

    @property
    def pages(self) -> np.ndarray:
        """Back-compat alias for :attr:`bad_pages`."""
        return self.bad_pages


@dataclasses.dataclass
class FusedPublishResult:
    """One publish sweep's outputs, guest-page order throughout."""

    zero_bitmap: np.ndarray   # bool[N]
    checksums: np.ndarray     # uint32[N] poly hash (== pallas_hash_fn output)
    hot: np.ndarray           # uint8[n_hot, page_bytes], ascending page order
    cold: np.ndarray          # uint8[n_cold, page_bytes], ascending page order


def _pad_rows(a: np.ndarray, n: int) -> np.ndarray:
    """``a`` with zero rows appended up to ``n`` rows."""
    if a.shape[0] == n:
        return a
    return np.concatenate([a, np.zeros((n - a.shape[0],) + a.shape[1:], a.dtype)])


def fused_publish(pages_bytes: np.ndarray, ws_mask: np.ndarray, *,
                  block_pages: int = 256, use_pallas: bool = False,
                  interpret: bool = False) -> FusedPublishResult:
    """pages_bytes: (N, page_bytes) uint8; ws_mask: bool[N] working set."""
    tiles = page_tiles(pages_bytes)
    n, rows, _ = tiles.shape
    page_bytes = rows * LANES * 4
    ws = np.asarray(ws_mask, dtype=bool)
    if n == 0 or not use_pallas:
        zero, csum, hot, cold = fused_publish_ref(page_words(tiles), ws)
        return FusedPublishResult(zero, csum,
                                  hot.view(np.uint8), cold.view(np.uint8))
    # every slab has one shape (one compile); the last is padded with zero
    # pages, which both compactions skip and whose flags are sliced off
    slab = min(SLAB_PAGES, -(-n // block_pages) * block_pages)
    assert slab % block_pages == 0, (slab, block_pages)
    zero = np.empty(n, bool)
    csum = np.empty(n, np.uint32)
    # compacted outputs are written as slabs come back; untouched tails of
    # these n-row buffers are never committed to host memory
    hot = np.empty((n, page_bytes), np.uint8)
    cold = np.empty((n, page_bytes), np.uint8)
    n_hot = n_cold = 0
    w = weight_tile(rows)
    for lo in range(0, n, slab):
        part = tiles[lo : lo + slab]
        k = part.shape[0]
        z, c, h, cd, counts = fused_publish_pallas(
            jnp.asarray(_pad_rows(part, slab)),
            jnp.asarray(_pad_rows(ws[lo : lo + slab].astype(np.int32), slab)),
            w, block_pages=block_pages, interpret=interpret)
        kh, kc = (int(v) for v in np.asarray(counts))
        zero[lo : lo + k] = np.asarray(z)[:k] != 0
        csum[lo : lo + k] = np.asarray(c)[:k]
        if kh:
            hot[n_hot : n_hot + kh] = np.asarray(h)[:kh].view(np.uint8).reshape(kh, -1)
        if kc:
            cold[n_cold : n_cold + kc] = np.asarray(cd)[:kc].view(np.uint8).reshape(kc, -1)
        n_hot, n_cold = n_hot + kh, n_cold + kc
    nz = ~zero
    assert n_hot == int(np.count_nonzero(nz & ws)), "hot count drifted"
    assert n_cold == int(np.count_nonzero(nz & ~ws)), "cold count drifted"
    return FusedPublishResult(zero, csum, hot[:n_hot], cold[:n_cold])


# build_snapshot's publish_fn seam: (pages_matrix uint8[N, PAGE_SIZE],
# ws bool[N]) -> FusedPublishResult
PublishFn = Callable[[np.ndarray, np.ndarray], FusedPublishResult]


def make_fused_publish_fn(*, block_pages: int = 256, use_pallas: bool = False,
                          interpret: bool = False) -> PublishFn:
    def publish_fn(pages_matrix: np.ndarray, ws: np.ndarray) -> FusedPublishResult:
        return fused_publish(pages_matrix, ws, block_pages=block_pages,
                             use_pallas=use_pallas, interpret=interpret)

    return publish_fn


def _bucket(m: int) -> int:
    """Next power of two: batch shapes the restore kernel compiles for."""
    return 1 << max(0, int(m) - 1).bit_length()


def _install(dest, compact: np.ndarray, indices: np.ndarray,
             src_indices: np.ndarray, use_pallas: bool, interpret: bool,
             checks: Optional["PendingChecks"] = None):
    """Install ``compact[src_indices[i]]`` at ``dest[indices[i]]`` without
    waiting for it.  Returns ``(dest', got)``: ``got`` is the host checksum
    vector (host path), or per kernel batch ``(csums, lo, k)``, its padded
    device checksum vector and the ``indices[lo : lo + k]`` it installed.
    Given ``checks``, each batch's checksums also go to its device stash."""
    if indices.shape[0] == 0:
        return dest, np.zeros(0, np.uint32)
    if not use_pallas:
        chunk = page_tiles(compact)
        out = dest if isinstance(dest, np.ndarray) else np.asarray(dest).copy()
        _, csums = fused_restore_ref(page_words(out), page_words(chunk),
                                     src_indices, indices)
        return out, csums
    with span(SCATTER_STAGE):
        chunk = page_tiles(compact)
        out = (jnp.asarray(page_tiles(dest)) if isinstance(dest, np.ndarray)
               else dest)
        w = weight_tile(chunk.shape[1])
    parts = []
    for lo in range(0, indices.shape[0], MAX_BATCH_PAGES):
        with span(SCATTER_STAGE):
            src = src_indices[lo : lo + MAX_BATCH_PAGES]
            dst = indices[lo : lo + MAX_BATCH_PAGES]
            pad = _bucket(dst.size) - dst.size
            rows = chunk[np.concatenate([src, np.repeat(src[-1:], pad)])]
            args = (jnp.asarray(rows), jnp.arange(rows.shape[0], dtype=jnp.int32),
                    jnp.asarray(np.concatenate([dst, np.repeat(dst[-1:], pad)])))
        with span(SCATTER_LAUNCH):
            out, c = fused_restore_pallas(out, *args, w, interpret=interpret)
            if checks is not None:
                checks.stash(c)
        parts.append((c, lo, dst.size))
    return out, parts


def _read_back(got) -> np.ndarray:
    """Host checksums of an :func:`_install`: waits for its kernel batches."""
    if isinstance(got, np.ndarray):
        return got
    return np.concatenate([np.asarray(c)[:k] for c, _lo, k in got])


def _check(csums: np.ndarray, indices: np.ndarray, expected, dest) -> None:
    bad = csums != np.asarray(expected, dtype=np.uint32)
    if bad.any():
        raise ChecksumMismatchError(indices[bad], dest=dest)


def fused_restore(dest, compact: np.ndarray, indices: np.ndarray,
                  *, src_indices: Optional[np.ndarray] = None,
                  expected_csums: Optional[np.ndarray] = None,
                  use_pallas: bool = False, interpret: bool = False):
    """Install ``compact[src_indices[i]]`` at ``dest[indices[i]]`` and return
    ``(dest', csums uint32[M])``; raises :class:`ChecksumMismatchError` when
    ``expected_csums`` (aligned with ``indices``) disagree.

    Host path: ``dest`` is a uint8 page matrix, updated in place and
    returned.  Kernel path: ``dest`` is the device page array ``(N, R, 128)``
    uint32 (a host matrix is uploaded first); it is donated and the new
    array returned.  Only the compact rows and their indices cross to the
    device, in batches of at most :data:`MAX_BATCH_PAGES`, each padded to a
    power of two by repeating its last (source, destination) pair, which
    rewrites the same page.  The checksums are read back once, after the
    last batch."""
    indices = np.asarray(indices, dtype=np.int32)
    if src_indices is None:
        src_indices = np.arange(indices.shape[0], dtype=np.int32)
    else:
        src_indices = np.asarray(src_indices, dtype=np.int32)
    out, got = _install(dest, compact, indices, src_indices, use_pallas, interpret)
    with span(SCATTER_VERIFY):
        csums = _read_back(got)
        if expected_csums is not None:
            _check(csums, indices, expected_csums, out)
    return out, csums


# A bulk install's device checksums are written into a stash vector of this
# many entries (4 MiB), a new one when it fills: the hot phase of a
# 1M-page restore holds one vector, not a buffer per kernel batch.
STASH_ENTRIES = 1 << 20
# Batches of one shape are written to the stash this many at a time: a
# dispatch costs about as much host time as the kernel's own, so one per
# batch would give back a quarter of what holding the compares saves.
STASH_GROUP = 32


@functools.partial(jax.jit, donate_argnums=(0, 1))
def _stash(seg: jax.Array, pos: jax.Array, *csums: jax.Array):
    """Write the batches' checksums, in order, at ``pos`` in ``seg``;
    advance ``pos``."""
    c = jnp.concatenate(csums)
    return lax.dynamic_update_slice(seg, c, (pos,)), pos + c.shape[0]


def _pad_to(a: np.ndarray, n: int) -> np.ndarray:
    """``a`` with its last element repeated up to ``n`` entries, as the
    kernel pads a batch."""
    if a.size == n:
        return a
    return np.concatenate([a, np.repeat(a[-1:], n - a.size)])


class PendingChecks:
    """The checksum compares of one bulk install, held until it ends.

    Each held batch keeps its guest indices and expected checksums on the
    host.  Its computed checksums stay where they were made: host arrays on
    the host path; on the kernel path the padded batch vectors are written,
    :data:`STASH_GROUP` of one shape at a time, into a device stash of
    :data:`STASH_ENTRIES` entries, so a phase of thousands of batches keeps
    a few device vectors alive, not one per batch.  :meth:`settle` reads
    them back once and compares everything in one vectorised step;
    ``stats["verify_syncs"]`` counts that wait."""

    def __init__(self, stats: dict):
        self.stats = stats
        self._dst: list = []
        self._exp: list = []
        self._host: list = []
        self._full: list = []        # filled stash vectors: (array, used)
        self._seg = self._pos = None
        self._used = 0
        self._group: list = []       # batch vectors of one shape, not yet written

    def stash(self, csums: jax.Array) -> None:
        """Queue one kernel batch's padded checksums for the device stash."""
        if self._group and csums.shape != self._group[0].shape:
            self._flush()
        self._group.append(csums)
        if len(self._group) == STASH_GROUP:
            self._write(self._group)
            self._group = []

    def _flush(self) -> None:
        """Write a part-filled group one batch at a time (one program per
        batch shape, not one per group size)."""
        for c in self._group:
            self._write([c])
        self._group = []

    def _write(self, csums: list) -> None:
        """One dispatch: ``csums`` into the stash, in a new stash vector when
        this one has no room left."""
        n = sum(c.shape[0] for c in csums)
        if self._seg is None or self._used + n > STASH_ENTRIES:
            if self._seg is not None:
                self._seg.copy_to_host_async()
                self._full.append((self._seg, self._used))
            self._seg = jnp.zeros(STASH_ENTRIES, jnp.uint32)
            self._pos = jnp.zeros((), jnp.int32)
            self._used = 0
        self._seg, self._pos = _stash(self._seg, self._pos, *csums)
        self._used += n

    def hold(self, got, indices: np.ndarray, expected: np.ndarray) -> None:
        """Keep one install's guest indices and expected checksums, aligned
        with its checksums ``got`` (from :func:`_install`)."""
        if isinstance(got, np.ndarray):
            self._host.append(got)
            self._dst.append(indices)
            self._exp.append(expected)
            return
        for c, lo, k in got:
            self._dst.append(_pad_to(indices[lo : lo + k], c.shape[0]))
            self._exp.append(_pad_to(expected[lo : lo + k], c.shape[0]))

    def settle(self) -> np.ndarray:
        """ONE readback and ONE compare for everything held: the sorted guest
        pages whose checksum disagreed."""
        if not self._dst:
            return np.zeros(0, np.int64)
        self.stats["verify_syncs"] += 1
        with span(SCATTER_VERIFY):
            self._flush()
            if self._seg is None:
                got = np.concatenate(self._host)
            else:
                stash = self._full + [(self._seg, self._used)]
                host = jax.device_get([a for a, _used in stash])
                got = np.concatenate([h[:used] for h, (_a, used) in zip(host, stash)])
            bad = got != np.concatenate(self._exp)
            return np.unique(np.concatenate(self._dst)[bad]).astype(np.int64)


class FusedScatter:
    """``ScatterFn``-shaped adapter over the fused restore.

    Drop-in for the serving layer's scatter seam (``Instance``,
    ``RestoreEngine``, ``NodePageServer.attach``, ``Orchestrator``): the
    call signature stays ``(dest, compact, indices) -> dest``.  When bound
    to a snapshot's guest-indexed publish-time checksum table
    (:meth:`bind_checksums` — ``RestoreEngine.__init__`` does this when the
    reader's regions carry one), every batch is verified against
    ``table[indices]``: at once, reading its checksums back, or — given the
    ``checks`` of a bulk install (:meth:`pending_checks`) — when that
    install ends (DESIGN.md §13).  Unbound, it reads each call's checksums
    back and compares nothing.  ``stats["verify_syncs"]`` counts the host's
    waits on checksum readbacks.  Bound copies share the template's
    ``stats`` dict so fan-out totals stay observable in one place.  With
    ``use_pallas`` the instance's memory is the device array
    :meth:`new_memory` allocates.
    """

    def __init__(self, *, use_pallas: bool = False, interpret: bool = False,
                 expected: Optional[np.ndarray] = None,
                 stats: Optional[dict] = None):
        self.use_pallas = use_pallas
        self.interpret = interpret
        self.expected = None if expected is None else np.asarray(expected, np.uint32)
        self.stats = stats if stats is not None else {
            "batches": 0, "pages": 0, "pages_verified": 0, "verify_syncs": 0}

    def bind_checksums(self, table: np.ndarray) -> "FusedScatter":
        return FusedScatter(use_pallas=self.use_pallas, interpret=self.interpret,
                            expected=table, stats=self.stats)

    def new_memory(self, total_pages: int, page_bytes: int) -> Optional[jax.Array]:
        """A zeroed device page array for a restoring instance, or None when
        installs run on the host (the instance keeps its numpy image)."""
        if not self.use_pallas:
            return None
        return jnp.zeros((total_pages, page_bytes // (4 * LANES), LANES),
                         jnp.uint32)

    def pending_checks(self) -> Optional[PendingChecks]:
        """Holder for a bulk install's deferred compares, or None when this
        scatter verifies nothing."""
        return None if self.expected is None else PendingChecks(self.stats)

    def __call__(self, dest, compact: np.ndarray, indices: np.ndarray,
                 checks: Optional[PendingChecks] = None):
        idx = np.asarray(indices, dtype=np.int32)
        exp = self.expected[idx] if self.expected is not None else None
        if checks is None:
            self.stats["verify_syncs"] += 1
            out, _csums = fused_restore(dest, compact, idx, expected_csums=exp,
                                        use_pallas=self.use_pallas,
                                        interpret=self.interpret)
        else:
            out, got = _install(dest, compact, idx,
                                np.arange(idx.size, dtype=np.int32),
                                self.use_pallas, self.interpret, checks)
            checks.hold(got, idx, exp)
        if exp is not None:
            self.stats["pages_verified"] += int(idx.size)
        self.stats["batches"] += 1
        self.stats["pages"] += int(idx.size)
        return out


def default_publish_fn() -> Optional[PublishFn]:
    """The publish data plane: the compiled kernel on a TPU, else None (the
    host pipeline in ``build_snapshot``)."""
    return make_fused_publish_fn(use_pallas=True) if on_tpu() else None


def default_scatter_fn() -> Optional[FusedScatter]:
    """The restore data plane: the compiled kernel over HBM-resident
    instance memory on a TPU, else None (numpy installs)."""
    return FusedScatter(use_pallas=True) if on_tpu() else None
