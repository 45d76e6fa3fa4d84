"""Pallas TPU kernel: blocked online-softmax (Flash) attention with GQA.

Training/prefill hot spot for the full-attention architectures.  Standard
TPU formulation (cf. jax.experimental.pallas.ops.tpu.flash_attention):

  grid = (batch, q_heads, Sq/bq, Skv/bk), kv axis innermost & "arbitrary"
  scratch: f32 acc (bq, Dv), running max m and sum l stored replicated as
  (bq, 128) tiles (TPU VREG lane width).

Causal handling is two-level: whole kv-blocks strictly above the diagonal
are skipped via pl.when (no FLOPs, no DMA wait), the diagonal block applies
an element mask.  GQA is free: the K/V BlockSpec index_map maps q-head h to
kv-head h // group, so K/V tiles for a group are fetched once per q-head
(the pipeline caches the revisit).

Block sizes default to (bq, bk) = (512, 512): VMEM ≈ bq*Dk(q) + bk*(Dk+Dv)
+ bq*Dv f32 acc ≈ 1.6 MiB at D=128 — comfortably inside 16 MiB VMEM with
double buffering, and MXU-aligned (multiples of 128).
"""
import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30
LANES = 128


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr,
                  *, causal: bool, scale: float, bq: int, bk: int,
                  nk: int, kv_offset: int):
    iq = pl.program_id(2)
    ik = pl.program_id(3)

    @pl.when(ik == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    # causal: kv block strictly above the diagonal contributes nothing.
    # q row global pos = iq*bq + r + kv_offset ; kv col global pos = ik*bk + c
    def _compute():
        q = q_ref[0, 0].astype(jnp.float32)              # (bq, Dk)
        k = k_ref[0, 0].astype(jnp.float32)              # (bk, Dk)
        v = v_ref[0, 0].astype(jnp.float32)              # (bk, Dv)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        ) * scale                                         # (bq, bk)
        if causal:
            qpos = iq * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0) + kv_offset
            kpos = ik * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
            s = jnp.where(kpos <= qpos, s, NEG_INF)

        m_prev = m_scr[:, 0]                              # (bq,)
        m_cur = s.max(axis=1)
        m_new = jnp.maximum(m_prev, m_cur)
        alpha = jnp.exp(m_prev - m_new)                   # rescale of old acc
        p = jnp.exp(s - m_new[:, None])                   # (bq, bk)
        l_new = alpha * l_scr[:, 0] + p.sum(axis=1)
        acc_scr[...] = acc_scr[...] * alpha[:, None] + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )
        m_scr[...] = jnp.broadcast_to(m_new[:, None], m_scr.shape)
        l_scr[...] = jnp.broadcast_to(l_new[:, None], l_scr.shape)

    if causal:
        block_relevant = ik * bk <= iq * bq + (bq - 1) + kv_offset
        pl.when(block_relevant)(_compute)
        last_ik = jnp.minimum(nk - 1, (iq * bq + (bq - 1) + kv_offset) // bk)
    else:
        _compute()
        last_ik = nk - 1

    @pl.when(ik == last_ik)
    def _finalize():
        l = l_scr[:, 0]
        l = jnp.where(l == 0.0, 1.0, l)
        o_ref[0, 0] = (acc_scr[...] / l[:, None]).astype(o_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("causal", "scale", "block_q", "block_k", "interpret"),
)
def flash_attention_pallas(
    q, k, v, *, causal: bool = True, scale: float | None = None,
    block_q: int = 512, block_k: int = 512, interpret: bool = False,
):
    """q: (B, Hq, Sq, Dk); k/v: (B, Hkv, Skv, Dk/Dv) -> (B, Hq, Sq, Dv)."""
    b, hq, sq, dk = q.shape
    hkv, skv, dv = k.shape[1], k.shape[2], v.shape[3]
    assert hq % hkv == 0
    group = hq // hkv
    if scale is None:
        scale = dk ** -0.5
    bq = min(block_q, sq)
    bk = min(block_k, skv)
    assert sq % bq == 0 and skv % bk == 0, (sq, bq, skv, bk)
    nq, nk = sq // bq, skv // bk
    kv_offset = skv - sq  # suffix-aligned causal (supports chunked prefill)

    kernel = functools.partial(
        _flash_kernel, causal=causal, scale=float(scale),
        bq=bq, bk=bk, nk=nk, kv_offset=kv_offset,
    )
    return pl.pallas_call(
        kernel,
        grid=(b, hq, nq, nk),
        in_specs=[
            pl.BlockSpec((1, 1, bq, dk), lambda b_, h, iq, ik: (b_, h, iq, 0)),
            pl.BlockSpec((1, 1, bk, dk), lambda b_, h, iq, ik: (b_, h // group, ik, 0)),
            pl.BlockSpec((1, 1, bk, dv), lambda b_, h, iq, ik: (b_, h // group, ik, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, bq, dv), lambda b_, h, iq, ik: (b_, h, iq, 0)),
        out_shape=jax.ShapeDtypeStruct((b, hq, sq, dv), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq, LANES), jnp.float32),
            pltpu.VMEM((bq, LANES), jnp.float32),
            pltpu.VMEM((bq, dv), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(q, k, v)
