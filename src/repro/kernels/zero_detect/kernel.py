"""Pallas TPU kernel: block-wise zero-page detection.

The snapshot walk (§3.2 "first walk all page contents to identify zero
pages") over ~10-100 GB of sharded state is a pure HBM-bandwidth job; on TPU
we tile it so each grid step streams a (block_pages, rows, 128) block of
page tiles HBM→VMEM (``kernels/layout.py``: a 4 KiB page is one (8, 128)
uint32 tile) and reduces each page to one flag.  A page is zero when all of
its bits are.  Flags go to an SMEM output block of (1, 1, block_pages).
Default block: 256 pages = 1 MiB of 4 KiB pages in VMEM.
"""
import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..layout import page_nonzero


def _zero_detect_block(pages_ref, out_ref):
    def body(r, carry):
        out_ref[0, 0, r] = jnp.where(page_nonzero(pages_ref[r]), 0, 1)
        return carry

    jax.lax.fori_loop(0, pages_ref.shape[0], body, 0)


@functools.partial(jax.jit, static_argnames=("block_pages", "interpret"))
def zero_detect_pallas(pages: jnp.ndarray, *, block_pages: int = 256, interpret: bool = False):
    """pages: (n_pages, rows, 128) uint32 -> int32[n_pages] (1 = all-zero page).

    n_pages must be a multiple of block_pages (ops.py pads).
    """
    n_pages, rows, lanes = pages.shape
    assert n_pages % block_pages == 0, (n_pages, block_pages)
    nb = n_pages // block_pages
    out = pl.pallas_call(
        _zero_detect_block,
        grid=(nb,),
        in_specs=[
            pl.BlockSpec((block_pages, rows, lanes), lambda i: (i, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, block_pages), lambda i: (i, 0, 0),
                               memory_space=pltpu.SMEM),
        out_shape=jax.ShapeDtypeStruct((nb, 1, block_pages), jnp.int32),
        interpret=interpret,
    )(pages)
    return out.reshape(n_pages)
