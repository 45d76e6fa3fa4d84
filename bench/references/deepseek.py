"""Plain reference of DeepSeek-V3 as the configurations cut it: a float32
forward pass at ``Precision.HIGHEST`` over the whole prompt, in
``jax.numpy``, with no kernel, cache or batching trick.  Written from the
published description (arXiv:2412.19437 §2.1, the model's ``config.json``
and DeepSeek's inference code); it imports nothing of the program.

Per layer: ``x += mla(rmsnorm(x))``, ``x += ffn(rmsnorm(x))``; the first
``first_k_dense_replace`` layers have a SwiGLU FFN, the rest the MoE below.
Then a final RMSNorm and the untied head over the vocabulary slice.

- MLA: ``q = wq_b(rmsnorm(wq_a x))``; the kv latent ``c = rmsnorm(wkv_a x)``
  (its first ``kv_lora_rank`` columns), the rope key the last
  ``qk_rope_head_dim`` columns, shared by the heads; ``[k_nope, v] = wkv_b c``.
  Rotary embedding on interleaved pairs ``(2i, 2i + 1)`` (complex
  multiplication, as the inference code applies it), with YaRN's
  frequencies: a linear ramp, between the dims whose rotations at the
  original length are ``beta_fast`` and ``beta_slow``, from the original
  frequency to it ÷ ``factor``.  Softmax scale ``(nope + rope)^-0.5 ×
  (0.1 · mscale_all_dim · ln factor + 1)²``, causal.
- MoE: scores ``sigmoid(x W)`` over all routed experts; the bias is added
  for the choice only.  A group's score is the sum of its two best biased
  scores; the top ``num_experts_per_tok`` are chosen among the experts of
  the ``topk_group`` best groups (the others masked with ``-inf``, as the
  inference code does; HF's code masks with 0, which chooses the same
  whenever a searched biased score is positive).  The weights are the
  chosen unbiased scores over their sum, × ``routed_scaling_factor``.
- The cut (departure from the whole model): the layer holds the
  ``n_routed_experts`` experts from ``first_held_expert`` on, of the
  router's ``published.n_routed_experts``; a token's result is its held
  experts' weighted outputs plus the shared expert.  What experts held
  elsewhere add is left out, as in the program.  Experts are evaluated
  densely over the held ones and weighted by zero where not chosen.
- Each layer's queries run over blocks of ``BLOCK`` rows against every
  key, so that thousands of tokens fit beside the weights.

:func:`quantize` gives the control: every matrix (router and bias too)
rounded to an 8-bit float (4 exponent, 3 mantissa bits) with one scale per
output channel, the precision below the configuration's bfloat16.
"""
from __future__ import annotations

import dataclasses
import functools
import math

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
    nope: int
    rope: int
    v: int
    vocab: int
    eps: float
    inv_freq: tuple            # YaRN inverse frequencies of the rope dims
    softmax_scale: float
    groups: int
    groups_kept: int
    top_k: int
    norm_topk: bool
    route_scale: float
    first_held: int

    @classmethod
    def from_config(cls, c: dict) -> "Arch":
        if c["family"] != "moe" or c["scoring_func"] != "sigmoid":
            raise ValueError("this reference is DeepSeek-V3's: sigmoid-routed MoE with MLA")
        if c["tie_word_embeddings"] or c["rope_scaling"]["type"] != "yarn":
            raise ValueError("this reference has an untied head and YaRN rope scaling")
        y = c["rope_scaling"]
        rope, theta, factor = c["qk_rope_head_dim"], float(c["rope_theta"]), float(y["factor"])
        return cls(c["num_attention_heads"], c["qk_nope_head_dim"], rope, c["v_head_dim"],
                   c["vocab_size"], float(c["rms_norm_eps"]),
                   _yarn(rope, theta, factor, y["beta_fast"], y["beta_slow"],
                         y["original_max_position_embeddings"]),
                   (c["qk_nope_head_dim"] + rope) ** -0.5
                   * _mscale(factor, y["mscale_all_dim"]) ** 2,
                   c["n_group"], c["topk_group"], c["num_experts_per_tok"],
                   bool(c["norm_topk_prob"]), float(c["routed_scaling_factor"]),
                   c["first_held_expert"])


def _mscale(factor: float, mscale: float) -> float:
    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


def _yarn(dim, theta, factor, beta_fast, beta_slow, original) -> tuple:
    def correction_dim(rotations):
        return dim * math.log(original / (rotations * 2 * math.pi)) / (2 * math.log(theta))

    low = max(math.floor(correction_dim(beta_fast)), 0)
    high = min(math.ceil(correction_dim(beta_slow)), dim - 1)
    high = high + 0.001 if low == high else high
    out = []
    for i in range(dim // 2):
        freq = 1.0 / theta ** (2 * i / dim)
        smooth = 1.0 - min(max((i - low) / (high - low), 0.0), 1.0)
        out.append(freq / factor * (1 - smooth) + freq * smooth)
    return tuple(out)


def _mm(eq, a, b):
    return jnp.einsum(eq, a.astype(jnp.float32), b.astype(jnp.float32),
                      precision=HIGHEST)


def _rmsnorm(x, scale, eps):
    return x * jax.lax.rsqrt((x * x).mean(-1, keepdims=True) + eps) * scale.astype(jnp.float32)


def _rope(x, positions, inv_freq):
    """x (B, S, ..., D) at ``positions`` (S,): pairs (2i, 2i + 1) turned by
    ``positions × inv_freq[i]``."""
    ang = positions.astype(jnp.float32)[:, None] * jnp.asarray(inv_freq, jnp.float32)
    ang = ang.reshape(ang.shape[0], *([1] * (x.ndim - 3)), ang.shape[-1])
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    re, im = x[..., 0::2], x[..., 1::2]
    return jnp.stack([re * cos - im * sin, re * sin + im * cos], axis=-1).reshape(x.shape)


def _swiglu(p, x):
    return _mm("...f,fd->...d", jax.nn.silu(_mm("...d,df->...f", x, p["wg"]))
               * _mm("...d,df->...f", x, p["wi"]), p["wo"])


def _moe(a: Arch, p, x):
    """The held experts' part of each token's result, plus the shared expert."""
    scores = jax.nn.sigmoid(_mm("...d,de->...e", x, p["router"]))
    biased = scores + p["bias"][:, 0].astype(jnp.float32)
    n = scores.shape[-1]
    grouped = biased.reshape(*biased.shape[:-1], a.groups, n // a.groups)
    group_score = jax.lax.top_k(grouped, 2)[0].sum(-1)
    _, kept = jax.lax.top_k(group_score, a.groups_kept)
    searched = jax.nn.one_hot(kept, a.groups).sum(-2) > 0
    biased = jnp.where(jnp.repeat(searched, n // a.groups, axis=-1), biased, -jnp.inf)
    _, chosen = jax.lax.top_k(biased, a.top_k)
    w = jnp.take_along_axis(scores, chosen, axis=-1)
    if a.norm_topk:
        w = w / w.sum(-1, keepdims=True)
    w = w * a.route_scale
    held = p["wi"].shape[0]
    weight = (w[..., None] * jax.nn.one_hot(chosen - a.first_held, held)).sum(-2)
    up = _mm("...d,edf->...ef", x, p["wi"])
    gate = _mm("...d,edf->...ef", x, p["wg"])
    out = _mm("...ef,efd->...ed", jax.nn.silu(gate) * up, p["wo"])
    return (weight[..., None] * out).sum(-2) + _swiglu(p["shared"], x)


def _layer(a: Arch, p, x, moe: bool):
    b, s, _ = x.shape
    pos = jnp.arange(s)
    h = _rmsnorm(x, p["ln1"]["scale"], a.eps)
    at = p["attn"]
    kv = _mm("bsd,dr->bsr", h, at["wkv_a"])
    latent = _rmsnorm(kv[..., :-a.rope], at["kv_norm"]["scale"], a.eps)
    k_rope = _rope(kv[..., -a.rope:], pos, a.inv_freq)                  # (B, S, rope)
    kv = _mm("bsr,re->bse", latent, at["wkv_b"]).reshape(b, s, a.heads, a.nope + a.v)
    k_nope, v = kv[..., :a.nope], kv[..., a.nope:]

    def block(start):
        rows = start + jnp.arange(BLOCK)
        xb = jax.lax.dynamic_slice_in_dim(x, start, BLOCK, axis=1)
        hb = jax.lax.dynamic_slice_in_dim(h, start, BLOCK, axis=1)
        q = _rmsnorm(_mm("bsd,dr->bsr", hb, at["wq_a"]), at["q_norm"]["scale"], a.eps)
        q = _mm("bsr,re->bse", q, at["wq_b"]).reshape(b, BLOCK, a.heads, a.nope + a.rope)
        q_rope = _rope(q[..., a.nope:], rows, a.inv_freq)
        scores = (_mm("bqhd,bshd->bhqs", q[..., :a.nope], k_nope)
                  + _mm("bqhd,bsd->bhqs", q_rope, k_rope)) * a.softmax_scale
        causal = rows[:, None] >= pos[None, :]
        probs = jax.nn.softmax(jnp.where(causal, scores, -jnp.inf), axis=-1)
        o = _mm("bhqs,bshd->bqhd", probs, v).reshape(b, BLOCK, -1)
        xb = xb + _mm("bse,ed->bsd", o, at["wo"])
        hb = _rmsnorm(xb, p["ln2"]["scale"], a.eps)
        return xb + (_moe(a, p["moe"], hb) if moe else _swiglu(p["mlp"], hb))

    out = jax.lax.map(block, jnp.arange(0, s, BLOCK))      # (blocks, B, BLOCK, D)
    return out.transpose(1, 0, 2, 3).reshape(x.shape)


@functools.partial(jax.jit, static_argnums=0)
def last_logits(a: Arch, w, tokens, length):
    """Logits ``(B, vocab)`` after the first ``length`` tokens of each row of
    ``tokens`` (B, S), S a multiple of ``BLOCK``; the tokens past ``length``
    are padding the causal mask keeps out."""
    x = w["embed"]["table"][tokens].astype(jnp.float32)
    for p in w.get("dense_layers", []):
        x = _layer(a, p, x, moe=False)
    x, _ = jax.lax.scan(lambda x, p: (_layer(a, p, x, moe=True), None), x, w["layers"])
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
