"""Pallas TPU kernel: scatter compact pages into the instance image (§3.4).

The device-side bulk analogue of hot-set pre-installation: M compacted pages
stream VMEM→HBM into their guest page slots.  The destination image is
donated (input_output_aliases) and never read, so unwritten pages keep their
prior contents — the kernel only touches the scattered rows, mirroring
uffd.copy semantics (private copy, pool source untouched).  Pages are page
tiles (``kernels/layout.py``: a 4 KiB page is one (8, 128) uint32 tile).

Scalar-prefetched indices drive the *output* BlockSpec's index_map.
"""
import functools

import jax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _scatter_kernel(idx_ref, compact_ref, dest_ref, out_ref):
    del idx_ref, dest_ref  # dest is aliased to out; untouched rows persist
    out_ref[...] = compact_ref[...]


@functools.partial(jax.jit, static_argnames=("interpret",), donate_argnums=(0,))
def page_scatter_pallas(dest, compact, indices, *, interpret: bool = False):
    """dest: (N, rows, 128) donated; compact: (M, rows, 128); indices:
    int32[M] -> updated dest."""
    n, rows, lanes = dest.shape
    m = compact.shape[0]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(m,),
        in_specs=[
            pl.BlockSpec((1, rows, lanes), lambda i, idx_ref: (i, 0, 0)),  # compact row i
            pl.BlockSpec(memory_space=pl.ANY),                             # dest (aliased)
        ],
        out_specs=pl.BlockSpec((1, rows, lanes),
                               lambda i, idx_ref: (idx_ref[i], 0, 0)),
    )
    return pl.pallas_call(
        _scatter_kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((n, rows, lanes), dest.dtype),
        input_output_aliases={2: 0},  # alias dest (input incl. scalar prefetch) -> output
        interpret=interpret,
    )(indices, compact, dest)
