"""Aquifer-backed checkpointing: TrainState / serving state ⇄ paged snapshots.

This is where the paper becomes a first-class framework feature:

* **save** — flatten the state pytree into named arrays, build a
  ``StateImage``, zero-detect (optimizer moments are predominantly zero
  early in training; KV arenas and workspaces are zero at snapshot time),
  profile hotness, and publish to the two-tier pool through the pool master
  (ownership protocol, §3.3).
* **restore** — borrow + clflush + pre-install the hot set (params), then
  demand-page the cold set (optimizer moments / rare vocab rows) — compute
  can resume on the hot set before the RDMA tier finishes (§3.4).
* **elastic restore** — pages are location-independent (offset-array
  indirection), so the restored arrays can be device_put onto a *different*
  mesh than the one that saved them.

Hotness defaults for training state: params hot, Adam moments cold.
Serving-state hotness comes from the offline profiler (core/profiler.py).
"""
from __future__ import annotations

import functools
import time
from typing import Any, Callable, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..core import (
    Manifest,
    Orchestrator,
    PoolMaster,
    StateImage,
)
from ..core.pagestore import num_pages
from ..core.profiler import AccessRecorder
from ..spans import RESTORE, RESTORE_EXTRACT, span, spanned


# --------------------------------------------------------------------------
# pytree <-> named arrays
# --------------------------------------------------------------------------

def _path_name(path) -> str:
    return "/".join(
        str(getattr(p, "key", getattr(p, "idx", getattr(p, "name", p)))) for p in path
    )


def leaf_names(tree) -> List[str]:
    """The image names of ``tree``'s leaves, in leaf order."""
    return [_path_name(path) for path, _ in jax.tree_util.tree_leaves_with_path(tree)]


def flatten_state(tree) -> Dict[str, np.ndarray]:
    return {_path_name(path): np.asarray(leaf)
            for path, leaf in jax.tree_util.tree_leaves_with_path(tree)}


def unflatten_state(template, arrays: Dict[str, np.ndarray]):
    leaves, treedef = jax.tree.flatten(template)
    new_leaves = [jnp.asarray(arrays[name].reshape(np.shape(leaf)))
                  for name, leaf in zip(leaf_names(template), leaves)]
    return jax.tree.unflatten(treedef, new_leaves)


# --------------------------------------------------------------------------
# save / restore
# --------------------------------------------------------------------------

def default_train_hotness(manifest: Manifest) -> np.ndarray:
    """Params hot; Adam moments (opt/m, opt/v) cold; step counter hot."""
    rec = AccessRecorder(manifest)
    for e in manifest.extents:
        if not ("/m/" in f"/{e.name}/" or "/v/" in f"/{e.name}/"
                or e.name.startswith(("opt/m", "opt/v", "1/m", "1/v"))):
            rec.touch_array(e.name)
    return rec.working_set()


def save_checkpoint(
    master: PoolMaster,
    name: str,
    state,
    step: int,
    metadata: Optional[dict] = None,
    hotness: Callable[[Manifest], np.ndarray] = default_train_hotness,
) -> Tuple[StateImage, dict]:
    """Publish `state` as snapshot `name`, hot set ``hotness(manifest)``.
    Returns (image, stats)."""
    arrays = flatten_state(state)
    image = StateImage.build(arrays)
    working_set = hotness(image.manifest)
    meta = {"step": step, **(metadata or {})}
    t0 = time.perf_counter()
    regions = master.publish(name, image, working_set, metadata=meta)
    stats = {
        "publish_s": time.perf_counter() - t0,
        "total_pages": regions.total_pages,
        "zero": regions.n_zero,
        "hot": regions.n_hot,
        "cold": regions.n_cold,
        "cxl_bytes": regions.cxl_size,
        "rdma_bytes": regions.rdma_size,
    }
    return image, stats


@functools.partial(jax.jit, static_argnums=(2, 3, 4))
def _extent_array(pages, first_page, nbytes, shape, dtype):
    """One extent of a device page array as its named array: bitcast the
    page-aligned words and slice off the zero tail.  One program per extent
    shape (the first page is traced, so same-shape layers share it) keeps the
    relayout's scratch to one array's size."""
    dtype = jnp.dtype(dtype)
    assert dtype.itemsize <= 4, f"{dtype} is wider than a page word"
    words = jax.lax.dynamic_slice_in_dim(pages, first_page, num_pages(nbytes))
    words = words.reshape(-1)[: -(-nbytes // 4)]
    flat = jax.lax.bitcast_convert_type(words, dtype).reshape(-1)
    return flat[: int(np.prod(shape, dtype=np.int64))].reshape(shape)


@spanned(RESTORE)
def restore_checkpoint(
    orch: Orchestrator,
    name: str,
    template,
) -> Tuple[Any, dict]:
    """Borrow + restore `name`; returns (state, stats) for the arrays
    ``template`` names.

    The hot set (params) is pre-installed from the CXL tier; the rest is
    installed in bulk (zero runs as ranges, cold runs as batched RDMA reads).
    When the instance lives on the device (the TPU data plane), each array is
    bitcast and sliced out of its page array there, with no host round trip.

    ``stats["time_to_hot_s"]`` is the host's wall time from the call to the
    end of the borrow and the hot pre-install (``Orchestrator.restore``);
    ``stats["time_to_full_s"]`` runs on to the end of every install
    (``install_all_sync``).  Neither waits for the device beyond what the
    installs wait for themselves: on the kernel data plane the hot and the
    cold phase each read their checksums back once, at their end, which waits
    for their kernels, and the arrays extracted afterwards are dispatched,
    not awaited.  The whole restore is the span
    ``aquifer.restore`` (repro/spans.py).
    """
    t0 = time.perf_counter()
    ri = orch.restore(name)
    if ri is None:
        raise FileNotFoundError(f"no published snapshot named {name!r}")
    try:
        t_hot = time.perf_counter() - t0
        ri.engine.install_all_sync()
        t_full = time.perf_counter() - t0
        with span(RESTORE_EXTRACT):
            manifest, meta = ri.engine.reader.machine_state()
            by_name = manifest.by_name()
            pages = ri.instance.device_pages
            arrays = {}
            for n in leaf_names(template):
                e = by_name[n]
                arrays[n] = (ri.instance.image.read_array(n) if pages is None else
                             _extent_array(pages, np.int32(e.first_page), e.nbytes,
                                           tuple(e.shape), e.dtype))
            state = unflatten_state(template, arrays)
        stats = {
            "time_to_hot_s": t_hot,
            "time_to_full_s": t_full,
            "modeled": dict(ri.ledger.seconds),
            "instance": dict(ri.instance.stats),
            "device_resident": pages is not None,
            "meta": meta,
        }
    finally:
        ri.shutdown()
    return state, stats


def reshard(state, mesh, spec_tree):
    """Elastic restore: place a host-resident state onto a (new) mesh."""
    from jax.sharding import NamedSharding

    def put(x, spec):
        return jax.device_put(x, NamedSharding(mesh, spec))

    return jax.tree.map(put, state, spec_tree)
