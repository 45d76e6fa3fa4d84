"""Pallas TPU kernels: the fused snapshot data plane (DESIGN.md §13).

Two ops replace the piecemeal kernel sequences on Aquifer's byte-moving hot
paths, turning three (publish) / three (restore) HBM sweeps into one each.
Pages are ``(rows, 128)`` uint32 tiles (``kernels/layout.py``): a 4 KiB page
is one ``(8, 128)`` tile.

``fused_publish_pallas`` — publish sweep.  One blocked pass over the page
matrix emits, per page: the zero flag (``zero_detect``), the polynomial
checksum / dedup hash (``page_checksum``), and a compacted gather of the
non-zero pages split hot/cold by the working-set mask (``page_gather`` twice)
— 4 passes' worth of outputs for ONE read of the matrix.  Compaction under
static shapes works because the TPU grid is sequential: running hot/cold
counters live in SMEM scratch and survive across grid steps.  Each page of a
block is classified with scalar results (zero flag and checksum go straight
to SMEM outputs) and, if kept, copied into VMEM staging rows; each staging
block is then DMA'd to the ANY-space output at the carried row offset
(``pltpu.make_async_copy``).  The output is oversized by one block and
garbage tail rows are overwritten by the next block's copy, so the host
slices ``[:count]`` using the SMEM counts output.

``fused_restore_pallas`` — restore pre-install.  Per compact row the kernel
gathers from the streamed CXL chunk (scalar-prefetched ``src_idx`` drives the
input index map), computes the verify checksum from the row already in VMEM
(a free byproduct — the verify pass costs zero extra HBM traffic), and
scatters into the guest frame (``dst_idx`` drives the output index map, dest
donated via ``input_output_aliases`` and never read, so untouched rows keep
their contents, mirroring uffd.copy).  Double buffering comes from Pallas's
revolving input buffers over the sequential grid: the HBM→VMEM stream of
chunk row *k+1* overlaps the checksum+scatter of row *k*, so CXL streaming
and guest-frame installs pipeline exactly as §3.4 wants.
"""

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..layout import page_checksum_i32, page_nonzero


def _publish_kernel(pages_ref, ws_ref, w_ref, zero_ref, csum_ref, hot_ref,
                    cold_ref, counts_ref, carry, stage_hot, stage_cold, sems):
    i = pl.program_id(0)

    @pl.when(i == 0)
    def _():
        carry[0] = 0
        carry[1] = 0

    block = pages_ref.shape[0]
    w = w_ref[...]

    def body(r, hc):
        h, c = hc
        row = pages_ref[r]
        nz = page_nonzero(row)
        zero_ref[0, 0, r] = jnp.where(nz, 0, 1)
        csum_ref[0, 0, r] = page_checksum_i32(row, w)
        ws = ws_ref[0, 0, r] != 0
        hot = jnp.logical_and(nz, ws)
        cold = jnp.logical_and(nz, jnp.logical_not(ws))

        @pl.when(hot)
        def _():
            stage_hot[h] = row

        @pl.when(cold)
        def _():
            stage_cold[c] = row

        return h + hot.astype(jnp.int32), c + cold.astype(jnp.int32)

    k_hot, k_cold = jax.lax.fori_loop(
        0, block, body, (jnp.int32(0), jnp.int32(0)))

    # Copy the FULL staging block to the carried offset: rows past the local
    # count are garbage, but the next block's copy lands on top of them, so
    # only the final tail (sliced away by the host) ever holds stale rows.
    hot_base, cold_base = carry[0], carry[1]
    cp_h = pltpu.make_async_copy(
        stage_hot, hot_ref.at[pl.ds(hot_base, block)], sems.at[0])
    cp_c = pltpu.make_async_copy(
        stage_cold, cold_ref.at[pl.ds(cold_base, block)], sems.at[1])
    cp_h.start()
    cp_c.start()
    cp_h.wait()
    cp_c.wait()
    carry[0] = hot_base + k_hot
    carry[1] = cold_base + k_cold
    counts_ref[0] = carry[0]
    counts_ref[1] = carry[1]


@functools.partial(jax.jit, static_argnames=("block_pages", "interpret"))
def fused_publish_pallas(pages: jnp.ndarray, ws_mask: jnp.ndarray,
                         weights: jnp.ndarray, *, block_pages: int = 256,
                         interpret: bool = False):
    """One sweep over ``pages (N, R, 128)`` uint32 (N % block_pages == 0).

    ``ws_mask`` is int32[N] (nonzero = working set); ``weights`` the
    ``(R, 128)`` int32 checksum tile.  Returns ``(zero int32[N],
    csum uint32[N], hot (N+block, R, 128), cold (N+block, R, 128),
    counts int32[2])``; the caller slices the compacted outputs to
    ``[:counts[0]]`` / ``[:counts[1]]``.
    """
    n, rows, lanes = pages.shape
    nb = n // block_pages
    per_page = pl.BlockSpec((1, 1, block_pages), lambda i: (i, 0, 0),
                            memory_space=pltpu.SMEM)
    per_page_shape = jax.ShapeDtypeStruct((nb, 1, block_pages), jnp.int32)
    zero, csum, hot, cold, counts = pl.pallas_call(
        _publish_kernel,
        grid=(nb,),
        in_specs=[
            pl.BlockSpec((block_pages, rows, lanes), lambda i: (i, 0, 0)),
            per_page,
            pl.BlockSpec((rows, lanes), lambda i: (0, 0)),
        ],
        out_specs=[
            per_page,
            per_page,
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec(memory_space=pltpu.SMEM),
        ],
        out_shape=[
            per_page_shape,
            per_page_shape,
            jax.ShapeDtypeStruct((n + block_pages, rows, lanes), jnp.uint32),
            jax.ShapeDtypeStruct((n + block_pages, rows, lanes), jnp.uint32),
            jax.ShapeDtypeStruct((2,), jnp.int32),
        ],
        scratch_shapes=[
            pltpu.SMEM((2,), jnp.int32),
            pltpu.VMEM((block_pages, rows, lanes), jnp.uint32),
            pltpu.VMEM((block_pages, rows, lanes), jnp.uint32),
            pltpu.SemaphoreType.DMA((2,)),
        ],
        # the hot/cold carry makes the grid order part of the result
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
    )(pages, ws_mask.reshape(nb, 1, block_pages), weights)
    return (zero.reshape(n),
            jax.lax.bitcast_convert_type(csum.reshape(n), jnp.uint32),
            hot, cold, counts)


def _restore_kernel(src_ref, dst_ref, chunk_ref, w_ref, dest_ref,
                    out_ref, csum_ref):
    del src_ref, dst_ref, dest_ref  # index maps consumed them; dest aliased
    row = chunk_ref[0]
    out_ref[0] = row
    csum_ref[pl.program_id(0)] = page_checksum_i32(row, w_ref[...])


@functools.partial(jax.jit, static_argnames=("interpret",), donate_argnums=(0,))
def fused_restore_pallas(dest: jnp.ndarray, chunk: jnp.ndarray,
                         src_idx: jnp.ndarray, dst_idx: jnp.ndarray,
                         weights: jnp.ndarray, *, interpret: bool = False):
    """gather(chunk[src_idx[i]]) → checksum → scatter(dest[dst_idx[i]]).

    dest: ``(N, R, 128)`` uint32, donated; chunk: ``(C, R, 128)``;
    src_idx/dst_idx: int32[M]; weights: ``(R, 128)`` int32.
    Returns ``(dest', csum uint32[M])`` with csum in compact (i) order.
    """
    n, rows, lanes = dest.shape
    m = src_idx.shape[0]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(m,),
        in_specs=[
            pl.BlockSpec((1, rows, lanes), lambda i, src, dst: (src[i], 0, 0)),
            pl.BlockSpec((rows, lanes), lambda i, src, dst: (0, 0)),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=[
            pl.BlockSpec((1, rows, lanes), lambda i, src, dst: (dst[i], 0, 0)),
            pl.BlockSpec(memory_space=pltpu.SMEM),
        ],
    )
    out, csum = pl.pallas_call(
        _restore_kernel,
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((n, rows, lanes), dest.dtype),
            jax.ShapeDtypeStruct((m,), jnp.int32),
        ],
        input_output_aliases={4: 0},  # dest (input incl. scalar prefetch) -> out
        interpret=interpret,
    )(src_idx, dst_idx, chunk, weights, dest)
    return out, jax.lax.bitcast_convert_type(csum, jnp.uint32)
