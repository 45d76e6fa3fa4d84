"""Plain reference of the decoder-only transformers the configurations
describe: a float32 forward pass at ``Precision.HIGHEST`` over the whole
prompt, in ``jax.numpy``, with no kernel, cache or batching trick.

Per layer: ``x += attn(rmsnorm(x))``, ``x += ffn(rmsnorm(x))``; then a final
RMSNorm and the output head.  Attention is grouped-query with half-split
rotary embeddings over the whole head, scaled by ``head_dim ** -0.5`` and
causal.  The FFN is SwiGLU (``silu(x wg) * (x wi)) wo``).  Each layer runs
over blocks of ``BLOCK`` query rows against every key, so that a prompt of
thousands of tokens fits beside the weights.  Weights are read by name from
the tree the benchmark made.  It imports nothing of the program.

:func:`quantize` gives the control: every matrix rounded to an 8-bit float
(4 exponent, 3 mantissa bits) with one scale per output channel, the
precision below the configuration's bfloat16.
"""
from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST
BLOCK = 256
# largest normal of a 4-bit-exponent, 3-bit-mantissa float as
# ``reduce_precision`` rounds (IEEE-style: 1.875 x 2**7)
F8_MAX = 240.0


@dataclasses.dataclass(frozen=True)
class Arch:
    heads: int
    kv_heads: int
    head_dim: int
    vocab: int
    theta: float
    eps: float

    @classmethod
    def from_config(cls, c: dict) -> "Arch":
        if c["family"] != "dense":
            raise ValueError(f"no reference for family {c['family']!r}")
        if (c.get("partial_rotary_factor", 1.0) != 1.0 or c.get("qk_norm")
                or c.get("rope_scaling") or c.get("sliding_window")
                or c["tie_word_embeddings"]):
            raise ValueError("this reference rotates whole heads without scaling, "
                             "attends to every earlier token, has no QK-norm and "
                             "an untied head")
        return cls(c["num_attention_heads"], c["num_key_value_heads"], c["head_dim"],
                   c["vocab_size"], float(c["rope_theta"]), float(c["rms_norm_eps"]))


def _mm(eq, a, b):
    return jnp.einsum(eq, a.astype(jnp.float32), b.astype(jnp.float32),
                      precision=HIGHEST)


def _rmsnorm(x, scale, eps):
    return x * jax.lax.rsqrt((x * x).mean(-1, keepdims=True) + eps) * scale.astype(jnp.float32)


def _rope(x, positions, theta):
    """x: (B, S, H, D) at ``positions`` (S,), half-split rotation."""
    d = x.shape[-1]
    freqs = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    ang = positions.astype(jnp.float32)[:, None, None] * freqs
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def _swiglu(p, x):
    return _mm("...f,fd->...d", jax.nn.silu(_mm("...d,df->...f", x, p["wg"]))
               * _mm("...d,df->...f", x, p["wi"]), p["wo"])


def _layer(a: Arch, p, x):
    b, s, _ = x.shape
    pos = jnp.arange(s)
    h = _rmsnorm(x, p["ln1"]["scale"], a.eps)
    k = _mm("bsd,de->bse", h, p["attn"]["wk"]).reshape(b, s, a.kv_heads, a.head_dim)
    v = _mm("bsd,de->bse", h, p["attn"]["wv"]).reshape(b, s, a.kv_heads, a.head_dim)
    k = _rope(k, pos, a.theta)
    group = a.heads // a.kv_heads

    def block(start):
        rows = start + jnp.arange(BLOCK)
        xb = jax.lax.dynamic_slice_in_dim(x, start, BLOCK, axis=1)
        hb = jax.lax.dynamic_slice_in_dim(h, start, BLOCK, axis=1)
        q = _mm("bsd,de->bse", hb, p["attn"]["wq"]).reshape(b, BLOCK, a.heads, a.head_dim)
        q = _rope(q, rows, a.theta).reshape(b, BLOCK, a.kv_heads, group, a.head_dim)
        scores = _mm("bqkgd,bskd->bkgqs", q, k) * a.head_dim ** -0.5
        causal = rows[:, None] >= pos[None, :]
        probs = jax.nn.softmax(jnp.where(causal, scores, -jnp.inf), axis=-1)
        o = _mm("bkgqs,bskd->bqkgd", probs, v).reshape(b, BLOCK, -1)
        xb = xb + _mm("bse,ed->bsd", o, p["attn"]["wo"])
        return xb + _swiglu(p["mlp"], _rmsnorm(xb, p["ln2"]["scale"], a.eps))

    out = jax.lax.map(block, jnp.arange(0, s, BLOCK))      # (blocks, B, BLOCK, D)
    return out.transpose(1, 0, 2, 3).reshape(x.shape)


@functools.partial(jax.jit, static_argnums=0)
def last_logits(a: Arch, w, tokens, length):
    """Logits ``(B, vocab)`` after the first ``length`` tokens of each row of
    ``tokens`` (B, S), S a multiple of ``BLOCK``; the tokens past ``length``
    are padding the causal mask keeps out."""
    x = w["embed"]["table"][tokens].astype(jnp.float32)
    x, _ = jax.lax.scan(lambda x, p: (_layer(a, p, x), None), x, w["layers"])
    h = _rmsnorm(jax.lax.dynamic_index_in_dim(x, length - 1, axis=1, keepdims=False),
                 w["final_norm"]["scale"], a.eps)
    return _mm("bd,dv->bv", h, w["embed"]["head"])[:, : a.vocab]


def _f8(x):
    """Round to an 8-bit float (4 exponent, 3 mantissa bits) with one scale
    per channel of the last axis, taken over the second-last axis.
    ``reduce_precision`` rounds for certain: a float32 → float8 → float32
    round trip of converts may be folded away by the compiler."""
    x = x.astype(jnp.float32)
    scale = jnp.max(jnp.abs(x), axis=-2, keepdims=True) / F8_MAX
    scale = jnp.where(scale == 0, 1.0, scale)
    q = jax.lax.reduce_precision(x / scale, exponent_bits=4, mantissa_bits=3)
    return q * scale


@jax.jit
def quantize(w):
    """The control's weights: every matrix rounded to float8, kept in its own
    dtype; norm scales as they are."""
    def q(path, x):
        if getattr(path[-1], "key", None) == "scale":
            return x
        return _f8(x).astype(x.dtype)
    return jax.tree_util.tree_map_with_path(q, w)
