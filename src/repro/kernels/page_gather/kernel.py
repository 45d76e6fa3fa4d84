"""Pallas TPU kernel: gather pages by index (snapshot compaction, §3.2).

Building the compact hot/cold data regions is a gather of M pages out of an
N-page sharded state image.  The page index list is **scalar-prefetched**
(PrefetchScalarGridSpec) so the pipeline can issue the HBM→VMEM DMA for page
``idx[i+1]`` while page ``idx[i]`` is being written back — random-access
reads become overlapped streaming.

One grid step moves one page tile (``kernels/layout.py``: a 4 KiB page is
one (8, 128) uint32 tile); the input BlockSpec picks the source page per
step via the prefetched index ref.
"""
import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _gather_kernel(idx_ref, pages_ref, out_ref):
    del idx_ref
    out_ref[...] = pages_ref[...]


@functools.partial(jax.jit, static_argnames=("interpret",))
def page_gather_pallas(pages: jnp.ndarray, indices: jnp.ndarray, *, interpret: bool = False):
    """pages: (N, rows, 128); indices: int32[M] -> (M, rows, 128)."""
    n, rows, lanes = pages.shape
    (m,) = indices.shape
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(m,),
        in_specs=[
            pl.BlockSpec((1, rows, lanes), lambda i, idx_ref: (idx_ref[i], 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, rows, lanes), lambda i, idx_ref: (i, 0, 0)),
    )
    return pl.pallas_call(
        _gather_kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((m, rows, lanes), pages.dtype),
        interpret=interpret,
    )(indices, pages)
