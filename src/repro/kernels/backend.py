"""Where the page kernels run, decided once per process.

On a TPU the Pallas kernels run compiled and are the data plane; elsewhere
the numpy/jnp oracles stand in.  Nothing here picks interpret mode: a test
asks for it explicitly, and a kernel that fails on a TPU fails the run.
"""
import functools

import jax


@functools.cache
def on_tpu() -> bool:
    return jax.default_backend() == "tpu"
