"""Wrapper for page checksums: the Pallas kernel or the jnp oracle."""
import jax.numpy as jnp
import numpy as np

from ..layout import page_tiles, page_words, weight_tile
from .kernel import page_checksum_pallas
from .ref import page_checksum_ref, poly_weights


def page_checksum(pages_bytes, *, block_pages: int = 256,
                  use_pallas: bool = False,
                  interpret: bool = False) -> jnp.ndarray:
    """pages_bytes: (n_pages, page_bytes) -> uint32[n_pages]."""
    tiles = page_tiles(pages_bytes)
    n, rows, lanes = tiles.shape
    if not use_pallas:
        return page_checksum_ref(jnp.asarray(page_words(tiles)),
                                 poly_weights(rows * lanes))
    pad = (-n) % block_pages
    if pad:
        tiles = np.concatenate([tiles, np.zeros((pad, rows, lanes), np.uint32)])
    out = page_checksum_pallas(jnp.asarray(tiles), weight_tile(rows),
                               block_pages=block_pages, interpret=interpret)
    return out[:n]
