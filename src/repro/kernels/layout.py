"""The device layout of a page matrix, shared by every page kernel.

A page of ``W`` 32-bit words is one ``(W // 128, 128)`` uint32 tile, so a
4 KiB page is exactly one ``(8, 128)`` vreg-shaped tile and a page matrix is
``(N, W // 128, 128)``.  Kernel blocks are ``(rows, W // 128, 128)``: their
last two dims equal the array's, which is what the TPU compiler asks of a
block.  Per-page scalars (zero flags, checksums, working-set bits) live in
SMEM as ``(N // block, 1, block)`` int32 so that their blocks obey the same
rule; checksums are computed in int32, which wraps mod 2**32 exactly as
uint32 does, and are bitcast back to uint32 by the wrappers.
"""
import functools

import jax
import jax.numpy as jnp
import numpy as np

from .page_checksum.ref import poly_weights

LANES = 128


def page_tiles(pages) -> np.ndarray:
    """Host rows of any dtype -> a uint32 ``(n, words // 128, 128)`` view.

    A row must be a multiple of 512 bytes (one 128-lane row of words)."""
    arr = np.ascontiguousarray(pages)
    n = arr.shape[0]
    row_bytes = arr.dtype.itemsize * int(np.prod(arr.shape[1:], dtype=np.int64))
    if row_bytes % (4 * LANES):
        raise ValueError(f"page rows of {row_bytes} bytes are not a multiple "
                         f"of {4 * LANES}")
    return arr.reshape(n, row_bytes // arr.dtype.itemsize).view(np.uint8).view(
        np.uint32).reshape(n, row_bytes // (4 * LANES), LANES)


def page_words(pages) -> np.ndarray:
    """Host rows of any dtype -> a uint32 ``(n, words)`` view."""
    tiles = page_tiles(pages)
    return tiles.reshape(tiles.shape[0], tiles.shape[1] * LANES)


@functools.lru_cache(maxsize=None)
def weight_tile(rows: int) -> jnp.ndarray:
    """The polynomial checksum weights of one page, as an int32 tile."""
    w = np.asarray(poly_weights(rows * LANES)).view(np.int32)
    return jnp.asarray(w.reshape(rows, LANES))


def page_checksum_i32(tile, w):
    """In-kernel: one page tile's polynomial checksum as an int32 scalar."""
    return jnp.sum(jax.lax.bitcast_convert_type(tile, jnp.int32) * w)


def page_nonzero(tile):
    """In-kernel: True where any word of the page tile is nonzero."""
    return jnp.max(jnp.where(tile != 0, 1, 0)) != 0
