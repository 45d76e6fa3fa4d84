"""Dispatch wrappers for the fused snapshot data plane.

``fused_publish``   — one sweep: zero bitmap + poly checksum/dedup hash +
                      hot/cold compaction.  Plugs into ``build_snapshot``
                      via the ``publish_fn`` seam (``make_fused_publish_fn``).
``fused_restore``   — one kernel: gather-from-chunk → checksum-verify →
                      scatter-into-guest-frame.
``FusedScatter``    — ``fused_restore`` adapted to the serving layer's
                      ``ScatterFn`` signature ``(dest, compact, indices) ->
                      dest``; optionally bound to a snapshot's publish-time
                      checksum table, in which case every installed page is
                      verified in the same kernel invocation that installs it.

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
from typing import Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np

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
    rewrites the same page."""
    indices = np.asarray(indices, dtype=np.int32)
    m = indices.shape[0]
    if src_indices is None:
        src_indices = np.arange(m, dtype=np.int32)
    else:
        src_indices = np.asarray(src_indices, dtype=np.int32)
    if m == 0:
        return dest, np.zeros(0, np.uint32)
    if not use_pallas:
        chunk = page_tiles(compact)
        out = dest if isinstance(dest, np.ndarray) else np.asarray(dest).copy()
        _, csums = fused_restore_ref(page_words(out), page_words(chunk),
                                     src_indices, indices)
        parts = None
    else:
        with span(SCATTER_STAGE):
            chunk = page_tiles(compact)
            out = (jnp.asarray(page_tiles(dest)) if isinstance(dest, np.ndarray)
                   else dest)
            w = weight_tile(chunk.shape[1])
        parts = []
        for lo in range(0, m, MAX_BATCH_PAGES):
            with span(SCATTER_STAGE):
                src = src_indices[lo : lo + MAX_BATCH_PAGES]
                dst = indices[lo : lo + MAX_BATCH_PAGES]
                pad = _bucket(dst.size) - dst.size
                rows = chunk[np.concatenate([src, np.repeat(src[-1:], pad)])]
                args = (jnp.asarray(rows), jnp.arange(rows.shape[0], dtype=jnp.int32),
                        jnp.asarray(np.concatenate([dst, np.repeat(dst[-1:], pad)])))
            with span(SCATTER_LAUNCH):
                out, c = fused_restore_pallas(out, *args, w, interpret=interpret)
            parts.append((c, dst.size))
    with span(SCATTER_VERIFY):
        if parts is not None:
            # reading the checksums back waits for each batch's kernel
            csums = np.concatenate([np.asarray(c)[:k] for c, k in parts])
        if expected_csums is not None:
            bad = csums != np.asarray(expected_csums, dtype=np.uint32)
            if bad.any():
                raise ChecksumMismatchError(indices[bad], dest=out)
    return out, csums


class FusedScatter:
    """``ScatterFn``-shaped adapter over :func:`fused_restore`.

    Drop-in for the serving layer's scatter seam (``Instance``,
    ``RestoreEngine``, ``NodePageServer.attach``, ``Orchestrator``): the
    call signature stays ``(dest, compact, indices) -> dest``.  When bound
    to a snapshot's guest-indexed publish-time checksum table
    (:meth:`bind_checksums` — ``RestoreEngine.__init__`` does this when the
    reader's regions carry one), every batch is verified against
    ``table[indices]`` inside the same fused invocation that installs it.
    Bound copies share the template's ``stats`` dict so fan-out totals stay
    observable in one place.  With ``use_pallas`` the instance's memory is
    the device array :meth:`new_memory` allocates.
    """

    def __init__(self, *, use_pallas: bool = False, interpret: bool = False,
                 expected: Optional[np.ndarray] = None,
                 stats: Optional[dict] = None):
        self.use_pallas = use_pallas
        self.interpret = interpret
        self.expected = None if expected is None else np.asarray(expected, np.uint32)
        self.stats = stats if stats is not None else {
            "batches": 0, "pages": 0, "pages_verified": 0}

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

    def __call__(self, dest, compact: np.ndarray, indices: np.ndarray):
        idx = np.asarray(indices)
        exp = self.expected[idx] if self.expected is not None else None
        out, _csums = fused_restore(dest, compact, idx, expected_csums=exp,
                                    use_pallas=self.use_pallas,
                                    interpret=self.interpret)
        self.stats["batches"] += 1
        self.stats["pages"] += int(idx.size)
        if exp is not None:
            self.stats["pages_verified"] += int(idx.size)
        return out


def default_publish_fn() -> Optional[PublishFn]:
    """The publish data plane: the compiled kernel on a TPU, else None (the
    host pipeline in ``build_snapshot``)."""
    return make_fused_publish_fn(use_pallas=True) if on_tpu() else None


def default_scatter_fn() -> Optional[FusedScatter]:
    """The restore data plane: the compiled kernel over HBM-resident
    instance memory on a TPU, else None (numpy installs)."""
    return FusedScatter(use_pallas=True) if on_tpu() else None
