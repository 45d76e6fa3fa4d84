"""jit'd attention entry point: Pallas flash kernel on TPU, oracle elsewhere.

The model layer calls `attention(...)`; on this CPU container it resolves to
the jnp oracle (identical numerics modulo fp reassociation), on TPU to the
Pallas kernel (``kernels/backend.on_tpu``, decided once per process).
`use_pallas=True, interpret=True` forces kernel-in-Python validation (tests).
"""
import jax.numpy as jnp

from ..backend import on_tpu
from .kernel import flash_attention_pallas
from .ref import attention_ref, chunked_attention_ref

# Above this KV length the non-Pallas path uses the chunked online-softmax
# formulation so compile-time memory/cost analysis matches the TPU kernel.
CHUNKED_THRESHOLD = 2048


def flash_attention(q, k, v, *, causal: bool = True, scale: float | None = None,
                    block_q: int = 512, block_k: int = 512,
                    use_pallas: bool | None = None,
                    interpret: bool = False) -> jnp.ndarray:
    if use_pallas is None:
        use_pallas = on_tpu()
    if not use_pallas:
        if k.shape[2] > CHUNKED_THRESHOLD:
            return chunked_attention_ref(q, k, v, causal=causal, scale=scale,
                                         block_k=block_k)
        return attention_ref(q, k, v, causal=causal, scale=scale)
    return flash_attention_pallas(
        q, k, v, causal=causal, scale=scale,
        block_q=block_q, block_k=block_k, interpret=interpret,
    )
