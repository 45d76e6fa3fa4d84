"""Wrapper: page tiles, pad-to-block, then the Pallas kernel or the oracle."""
import jax.numpy as jnp
import numpy as np

from ..layout import page_tiles, page_words
from .kernel import zero_detect_pallas
from .ref import zero_detect_ref


def zero_detect(pages, *, block_pages: int = 256, use_pallas: bool = False,
                interpret: bool = False) -> jnp.ndarray:
    """int32[n_pages] zero-page bitmap (1 = every bit of the page is zero).

    ``use_pallas`` runs the kernel (compiled, or in the interpreter with
    ``interpret``); ragged tails are padded with a nonzero sentinel so
    padding never reports zero."""
    tiles = page_tiles(pages)
    n = tiles.shape[0]
    if not use_pallas:
        return zero_detect_ref(jnp.asarray(page_words(tiles)))
    pad = (-n) % block_pages
    if pad:
        tiles = np.concatenate([tiles, np.ones((pad,) + tiles.shape[1:], np.uint32)])
    out = zero_detect_pallas(jnp.asarray(tiles), block_pages=block_pages,
                             interpret=interpret)
    return out[:n]


def zero_bitmap_numpy(buf: np.ndarray, page_bytes: int = 4096) -> np.ndarray:
    """Host-side fast path used by core/ when no accelerator is attached."""
    mat = buf.reshape(-1, page_bytes)
    return ~mat.any(axis=1)
