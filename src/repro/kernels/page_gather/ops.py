"""Wrapper for page gather: the Pallas kernel or the jnp oracle."""
import jax.numpy as jnp
import numpy as np

from ..layout import page_tiles
from .kernel import page_gather_pallas
from .ref import page_gather_ref


def page_gather(pages, indices, *, use_pallas: bool = False,
                interpret: bool = False):
    """(N, E) pages, int32[M] indices -> (M, E) pages of the same dtype."""
    indices = np.asarray(indices, dtype=np.int32)
    if not use_pallas or indices.shape[0] == 0:
        return page_gather_ref(jnp.asarray(pages), jnp.asarray(indices))
    host = np.asarray(pages)
    out = page_gather_pallas(jnp.asarray(page_tiles(host)), jnp.asarray(indices),
                             interpret=interpret)
    return np.asarray(out).view(host.dtype).reshape(
        (indices.shape[0],) + host.shape[1:])
