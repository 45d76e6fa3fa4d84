"""Pure-jnp oracle for zero-page detection."""
import jax.numpy as jnp


def zero_detect_ref(pages: jnp.ndarray) -> jnp.ndarray:
    """pages: (n_pages, page_elems) -> int32[n_pages], 1 where the page is
    entirely zero.  Callers pass the page's bits as unsigned words
    (``kernels/layout.page_tiles``), so a page is zero exactly when its bytes
    are, as the snapshot walk (§3.2) and a bit-exact restore need: a -0.0
    float is not a zero page."""
    return (pages == 0).all(axis=1).astype(jnp.int32)
