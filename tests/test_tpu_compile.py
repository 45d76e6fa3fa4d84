"""The page kernels and the attention kernel compile for a TPU v5e at the
sizes the served path runs them, without a chip attached.

The TPU compiler refuses what interpret mode accepts (block shapes off the
(8, 128) tiling, unsigned reductions, layouts Mosaic cannot match), so these
compiles guard the chip path at no chip time.  Sizes: the phi4-mini-3.8b
image ``chip_smoke.py`` publishes (published widths, 8 of 32 layers, bf16
weights), the publish slab, every restore batch size, and attention at phi4 widths.
The topology is described inside a fixture, never at import.
"""
import dataclasses
import os

import pytest
import jax
import jax.numpy as jnp
from jax.sharding import SingleDeviceSharding

from repro.configs.base import get_config
from repro.core.pagestore import PAGE_SIZE, num_pages
from repro.core.serving import RestoreEngine
from repro.kernels.flash_attention.kernel import flash_attention_pallas
from repro.kernels.page_checksum.kernel import page_checksum_pallas
from repro.kernels.page_gather.kernel import page_gather_pallas
from repro.kernels.page_scatter.kernel import page_scatter_pallas
from repro.kernels.snapshot_fuse.kernel import (
    fused_publish_pallas,
    fused_restore_pallas,
)
from repro.kernels.snapshot_fuse.ops import (
    MAX_BATCH_PAGES,
    SLAB_PAGES,
    STASH_ENTRIES,
    STASH_GROUP,
    _stash,
)
from repro.kernels.zero_detect.kernel import zero_detect_pallas
from repro.models.model_zoo import build

HBM_BYTES = 16 << 30          # one v5e chip
BLOCK = 256                   # publish block_pages
ROWS, LANES = PAGE_SIZE // 512, 128
CHUNK = RestoreEngine.HOT_CHUNK_PAGES
# every batch size the restore kernel is given: powers of two up to the cap
BATCHES = [1 << k for k in range(MAX_BATCH_PAGES.bit_length())]
MAX_LEN = 64                  # the serving skeleton's max_len (launch/serve)


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    os.environ.setdefault("TPU_LOG_DIR", "disabled")  # else libtpu logs to /tmp
    try:
        return topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def image_pages():
    """Pages of the phi4-mini-3.8b image at published widths, 8 layers,
    bf16, rounded up to whole publish blocks (shapes only, no weights)."""
    cfg = dataclasses.replace(get_config("phi4-mini-3.8b"), n_layers=8,
                              param_dtype="bfloat16")
    shapes = jax.eval_shape(build(cfg).init, jax.random.PRNGKey(0))
    n = sum(num_pages(x.size * x.dtype.itemsize) for x in jax.tree.leaves(shapes))
    return -(-n // BLOCK) * BLOCK


def _spec(sharding, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _compiled(fn, *args):
    compiled = fn.lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    return compiled


def _device_bytes(compiled) -> int:
    m = compiled.memory_analysis()
    return (m.argument_size_in_bytes + m.output_size_in_bytes
            - m.alias_size_in_bytes + m.temp_size_in_bytes)


@pytest.mark.parametrize("extent", ["slab", "image"])
def test_fused_publish_compiles_and_fits(one_chip, image_pages, extent):
    n = SLAB_PAGES if extent == "slab" else image_pages
    compiled = _compiled(
        fused_publish_pallas,
        _spec(one_chip, (n, ROWS, LANES), jnp.uint32),
        _spec(one_chip, (n,), jnp.int32),
        _spec(one_chip, (ROWS, LANES), jnp.int32))
    assert _device_bytes(compiled) < HBM_BYTES


def test_restore_batches_are_capped_at_the_hot_chunk():
    assert MAX_BATCH_PAGES == CHUNK and BATCHES[-1] == CHUNK


@pytest.mark.parametrize("batch", BATCHES)
def test_fused_restore_compiles_into_donated_image(one_chip, image_pages, batch):
    """Single-page faults (1), cold-run pieces and hot chunks (256) alike."""
    compiled = _compiled(
        fused_restore_pallas,
        _spec(one_chip, (image_pages, ROWS, LANES), jnp.uint32),
        _spec(one_chip, (batch, ROWS, LANES), jnp.uint32),
        _spec(one_chip, (batch,), jnp.int32),
        _spec(one_chip, (batch,), jnp.int32),
        _spec(one_chip, (ROWS, LANES), jnp.int32))
    m = compiled.memory_analysis()
    assert m.alias_size_in_bytes == image_pages * PAGE_SIZE  # written in place
    assert _device_bytes(compiled) < HBM_BYTES


@pytest.mark.parametrize("group", [1, STASH_GROUP])
@pytest.mark.parametrize("batch", BATCHES)
def test_checksum_stash_compiles_in_place(one_chip, batch, group):
    """A bulk install writes its batches' checksums, singly or a group of
    one shape at a time, into one donated stash vector and its donated
    write position."""
    compiled = _stash.lower(
        _spec(one_chip, (STASH_ENTRIES,), jnp.uint32),
        _spec(one_chip, (), jnp.int32),
        *[_spec(one_chip, (batch,), jnp.uint32)] * group).compile()
    # both are written in place (the scalar takes a padded tile)
    assert compiled.memory_analysis().alias_size_in_bytes >= 4 * STASH_ENTRIES + 4
    assert STASH_ENTRIES >= STASH_GROUP * MAX_BATCH_PAGES


@pytest.mark.parametrize("kernel", ["zero_detect", "page_checksum"])
def test_piecemeal_sweeps_compile(one_chip, image_pages, kernel):
    pages = _spec(one_chip, (image_pages, ROWS, LANES), jnp.uint32)
    if kernel == "zero_detect":
        compiled = _compiled(zero_detect_pallas, pages)
    else:
        compiled = _compiled(page_checksum_pallas, pages,
                             _spec(one_chip, (ROWS, LANES), jnp.int32))
    assert _device_bytes(compiled) < HBM_BYTES


@pytest.mark.parametrize("kernel", ["page_gather", "page_scatter"])
def test_piecemeal_gather_scatter_compile(one_chip, image_pages, kernel):
    pages = _spec(one_chip, (image_pages, ROWS, LANES), jnp.uint32)
    idx = _spec(one_chip, (CHUNK,), jnp.int32)
    if kernel == "page_gather":
        _compiled(page_gather_pallas, pages, idx)
    else:
        _compiled(page_scatter_pallas, pages,
                  _spec(one_chip, (CHUNK, ROWS, LANES), jnp.uint32), idx)


@pytest.mark.parametrize("sq,skv", [(512, 512), (1, MAX_LEN)],
                         ids=["prefill512", "decode1"])
def test_flash_attention_compiles_at_phi4_widths(one_chip, sq, skv):
    cfg = get_config("phi4-mini-3.8b")
    hd = cfg.head_dim
    q = _spec(one_chip, (1, cfg.n_heads, sq, hd), jnp.bfloat16)
    kv = _spec(one_chip, (1, cfg.n_kv_heads, skv, hd), jnp.bfloat16)
    _compiled(flash_attention_pallas, q, kv, kv)
