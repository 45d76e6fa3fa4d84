"""Wrapper for page scatter: the Pallas kernel or the jnp oracle."""
import jax.numpy as jnp
import numpy as np

from ..layout import page_tiles
from .kernel import page_scatter_pallas
from .ref import page_scatter_ref


def page_scatter(dest, compact, indices, *, use_pallas: bool = False,
                 interpret: bool = False):
    """dest (N, E) with dest[indices[i]] = compact[i]; same dtype as dest."""
    indices = np.asarray(indices, dtype=np.int32)
    if indices.shape[0] == 0:
        return dest
    if not use_pallas:
        return page_scatter_ref(jnp.asarray(dest), jnp.asarray(compact),
                                jnp.asarray(indices))
    host = np.asarray(dest)
    out = page_scatter_pallas(jnp.asarray(page_tiles(host)),
                              jnp.asarray(page_tiles(compact)),
                              jnp.asarray(indices), interpret=interpret)
    return np.asarray(out).view(host.dtype).reshape(host.shape)
