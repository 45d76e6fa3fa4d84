"""Pallas TPU kernel: per-page polynomial checksum (dedup layer, §3.6).

Streams (block_pages, rows, 128) blocks of uint32 page tiles HBM→VMEM
(``kernels/layout.py``), multiplies each page by the precomputed power-of-P
weight tile and reduces it with wraparound int32 arithmetic (the bits of the
uint32 checksum: a TPU reduces signed integers, not unsigned ones).
Bandwidth-bound like zero_detect; the two walks are fused at the ops level
when dedup is enabled (one HBM pass computes both).
"""
import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..layout import page_checksum_i32


def _checksum_block(pages_ref, w_ref, out_ref):
    w = w_ref[...]

    def body(r, carry):
        out_ref[0, 0, r] = page_checksum_i32(pages_ref[r], w)
        return carry

    jax.lax.fori_loop(0, pages_ref.shape[0], body, 0)


@functools.partial(jax.jit, static_argnames=("block_pages", "interpret"))
def page_checksum_pallas(pages_u32: jnp.ndarray, weights: jnp.ndarray,
                         *, block_pages: int = 256, interpret: bool = False):
    """pages_u32: (n_pages, rows, 128); weights: (rows, 128) int32 ->
    uint32[n_pages]."""
    n_pages, rows, lanes = pages_u32.shape
    assert n_pages % block_pages == 0
    nb = n_pages // block_pages
    out = pl.pallas_call(
        _checksum_block,
        grid=(nb,),
        in_specs=[
            pl.BlockSpec((block_pages, rows, lanes), lambda i: (i, 0, 0)),
            pl.BlockSpec((rows, lanes), lambda i: (0, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, block_pages), lambda i: (i, 0, 0),
                               memory_space=pltpu.SMEM),
        out_shape=jax.ShapeDtypeStruct((nb, 1, block_pages), jnp.int32),
        interpret=interpret,
    )(pages_u32, weights)
    return jax.lax.bitcast_convert_type(out.reshape(n_pages), jnp.uint32)
