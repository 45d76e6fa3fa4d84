"""Paged flat address space over model/server state.

The paper treats a MicroVM's guest memory as a flat, page-granular address
space.  Our analogue: a *StateImage* lays out a collection of named arrays
(params, optimizer moments, KV-cache arena, activation workspace, ...) into a
single page-aligned byte address space.  Every Aquifer mechanism (zero-page
elimination, hot/cold partitioning, the offset array, page serving) operates
on page indices of this address space.
"""
from __future__ import annotations

import dataclasses
from typing import Callable, Dict, Iterable, List, Mapping, Optional, Sequence, Tuple

import numpy as np

PAGE_SIZE = 4096  # bytes — matches the paper's 4 KiB guest pages

# zero_scan(pages_matrix uint8[N, PAGE_SIZE]) -> bool[N] (True = all-zero).
# Pluggable backend for the publish-path zero scan: the numpy oracle by
# default; ``set_zero_scan_backend`` swaps in kernels/zero_detect (compiled
# on TPU) — parity-asserted in tests/test_fused_kernels.py.
ZeroScanFn = Callable[[np.ndarray], np.ndarray]

_zero_scan_backend: Optional[ZeroScanFn] = None


def numpy_zero_scan(pages_matrix: np.ndarray) -> np.ndarray:
    """CPU oracle: vectorized any() over each page row."""
    return ~pages_matrix.any(axis=1)


def set_zero_scan_backend(fn: Optional[ZeroScanFn]) -> Optional[ZeroScanFn]:
    """Install a process-wide zero-scan backend (None restores the numpy
    oracle); returns the previous backend so callers can restore it."""
    global _zero_scan_backend
    prev = _zero_scan_backend
    _zero_scan_backend = fn
    return prev


def pallas_zero_scan(pages_matrix: np.ndarray, *,
                     interpret: bool = False) -> np.ndarray:
    """kernels/zero_detect adapted to the ``ZeroScanFn`` signature (same
    output as the oracle, asserted equal in tests).  The kernel runs
    compiled; ``interpret=True`` runs it in the interpreter (tests)."""
    from ..kernels.zero_detect.ops import zero_detect

    return np.asarray(zero_detect(pages_matrix, use_pallas=True,
                                  interpret=interpret)) != 0


def num_pages(nbytes: int) -> int:
    return -(-nbytes // PAGE_SIZE)


@dataclasses.dataclass(frozen=True)
class ArrayExtent:
    """Placement of one named array inside the flat address space."""

    name: str
    byte_offset: int          # page-aligned start
    nbytes: int               # payload bytes (may end mid-page; tail is zero)
    shape: Tuple[int, ...]
    dtype: str

    @property
    def first_page(self) -> int:
        return self.byte_offset // PAGE_SIZE

    @property
    def page_count(self) -> int:
        return num_pages(self.nbytes)

    def pages(self) -> range:
        return range(self.first_page, self.first_page + self.page_count)

    def element_pages(self, start_elem: int, stop_elem: int) -> range:
        """Pages covering elements [start, stop) of the flattened array."""
        itemsize = np.dtype(self.dtype).itemsize
        lo = self.byte_offset + start_elem * itemsize
        hi = self.byte_offset + stop_elem * itemsize
        return range(lo // PAGE_SIZE, num_pages(hi) if hi % PAGE_SIZE else hi // PAGE_SIZE)

    def row_pages(self, row: int, row_elems: int) -> range:
        """Pages covering one leading-axis row (e.g. one embedding row)."""
        return self.element_pages(row * row_elems, (row + 1) * row_elems)


@dataclasses.dataclass
class Manifest:
    """Address-space layout: the restore-time 'machine state' index."""

    extents: List[ArrayExtent]
    total_pages: int

    def by_name(self) -> Dict[str, ArrayExtent]:
        return {e.name: e for e in self.extents}

    def to_dict(self) -> dict:
        return {
            "total_pages": self.total_pages,
            "extents": [dataclasses.asdict(e) for e in self.extents],
        }

    @staticmethod
    def from_dict(d: dict) -> "Manifest":
        return Manifest(
            extents=[ArrayExtent(**{**e, "shape": tuple(e["shape"])}) for e in d["extents"]],
            total_pages=d["total_pages"],
        )


class StateImage:
    """A flat, paged byte image of named arrays (the 'guest memory').

    Arrays are laid out back-to-back, each starting on a page boundary so a
    page never spans two arrays (mirrors guest-physical frames owning a
    single mapping).
    """

    def __init__(self, manifest: Manifest, buf: np.ndarray):
        assert buf.dtype == np.uint8 and buf.ndim == 1
        self.manifest = manifest
        self.buf = buf

    # -- construction -----------------------------------------------------
    @staticmethod
    def build(arrays: Mapping[str, np.ndarray]) -> "StateImage":
        extents: List[ArrayExtent] = []
        offset = 0
        for name, arr in arrays.items():
            arr = np.ascontiguousarray(arr)
            extents.append(
                ArrayExtent(name, offset, arr.nbytes, tuple(arr.shape), str(arr.dtype))
            )
            offset += num_pages(arr.nbytes) * PAGE_SIZE
        buf = np.zeros(offset, dtype=np.uint8)
        img = StateImage(Manifest(extents, offset // PAGE_SIZE), buf)
        for name, arr in arrays.items():
            img.write_array(name, arr)
        return img

    @staticmethod
    def empty_like(manifest: Manifest) -> "StateImage":
        return StateImage(manifest, np.zeros(manifest.total_pages * PAGE_SIZE, np.uint8))

    # -- array views ------------------------------------------------------
    def write_array(self, name: str, arr: np.ndarray) -> None:
        e = self.manifest.by_name()[name]
        raw = np.ascontiguousarray(arr).view(np.uint8).reshape(-1)
        assert raw.nbytes == e.nbytes, f"{name}: {raw.nbytes} != {e.nbytes}"
        self.buf[e.byte_offset : e.byte_offset + e.nbytes] = raw

    def read_array(self, name: str) -> np.ndarray:
        e = self.manifest.by_name()[name]
        raw = self.buf[e.byte_offset : e.byte_offset + e.nbytes]
        return raw.view(np.dtype(e.dtype)).reshape(e.shape)

    # -- page views -------------------------------------------------------
    @property
    def total_pages(self) -> int:
        return self.manifest.total_pages

    def page(self, idx: int) -> np.ndarray:
        return self.buf[idx * PAGE_SIZE : (idx + 1) * PAGE_SIZE]

    def pages_matrix(self) -> np.ndarray:
        return self.buf.reshape(self.total_pages, PAGE_SIZE)

    def write_page(self, idx: int, data: np.ndarray) -> None:
        assert data.nbytes == PAGE_SIZE
        self.buf[idx * PAGE_SIZE : (idx + 1) * PAGE_SIZE] = data.view(np.uint8).reshape(-1)

    def zero_page_bitmap(self, backend: Optional[ZeroScanFn] = None) -> np.ndarray:
        """bool[total_pages]; True where the page content is all zero.

        ``backend`` (or the process-wide one installed via
        ``set_zero_scan_backend``) swaps the numpy oracle for
        kernels/zero_detect — same output, asserted equal in tests.
        """
        fn = backend or _zero_scan_backend or numpy_zero_scan
        out = np.asarray(fn(self.pages_matrix()), dtype=bool)
        assert out.shape == (self.total_pages,), \
            f"zero-scan backend returned shape {out.shape}"
        return out


def runs_from_pages(pages: Sequence[int]) -> List[Tuple[int, int]]:
    """Collapse a sorted page-index set into (start, length) runs.

    Used for the Fig-4 fragmentation analysis and for batched installs.
    """
    out: List[Tuple[int, int]] = []
    it = iter(sorted(set(pages)))
    try:
        start = prev = next(it)
    except StopIteration:
        return out
    for p in it:
        if p == prev + 1:
            prev = p
            continue
        out.append((start, prev - start + 1))
        start = prev = p
    out.append((start, prev - start + 1))
    return out


def pages_from_runs(runs: Iterable[Tuple[int, int]]) -> List[int]:
    out: List[int] = []
    for s, n in runs:
        out.extend(range(s, s + n))
    return out
