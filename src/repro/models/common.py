"""Shared model components: norms, rotary embeddings (incl. M-RoPE), SwiGLU,
initializers.  Pure functional style: params are nested dicts of jnp arrays;
every module provides ``init_*`` and an apply function.
"""
from __future__ import annotations

import math
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np


# --------------------------------------------------------------------------
# init
# --------------------------------------------------------------------------

def dense_init(key, shape, dtype, fan_in: Optional[int] = None):
    fan_in = fan_in if fan_in is not None else shape[0]
    std = 1.0 / math.sqrt(fan_in)
    return (jax.random.truncated_normal(key, -2.0, 2.0, shape, jnp.float32) * std).astype(dtype)


def embed_init(key, shape, dtype):
    return (jax.random.truncated_normal(key, -2.0, 2.0, shape, jnp.float32) * 0.02).astype(dtype)


# --------------------------------------------------------------------------
# RMSNorm
# --------------------------------------------------------------------------

def init_rmsnorm(d: int, dtype) -> Dict:
    return {"scale": jnp.ones((d,), dtype)}


def rmsnorm(params: Dict, x: jnp.ndarray, eps: float = 1e-5) -> jnp.ndarray:
    dt = x.dtype
    xf = x.astype(jnp.float32)
    var = (xf * xf).mean(axis=-1, keepdims=True)
    y = xf * jax.lax.rsqrt(var + eps)
    return (y * params["scale"].astype(jnp.float32)).astype(dt)


# --------------------------------------------------------------------------
# Rotary embeddings — standard RoPE and Qwen2-VL M-RoPE
# --------------------------------------------------------------------------

def rope_freqs(d_head: int, theta: float) -> jnp.ndarray:
    return 1.0 / (theta ** (jnp.arange(0, d_head, 2, dtype=jnp.float32) / d_head))


def apply_rope(x: jnp.ndarray, positions: jnp.ndarray, theta: float) -> jnp.ndarray:
    """x: (..., S, D); positions: broadcastable to (..., S). Half-split RoPE."""
    d = x.shape[-1]
    freqs = rope_freqs(d, theta)                       # (D/2,)
    return rotate(x, positions[..., None].astype(jnp.float32) * freqs)  # (..., S, D/2)


def rotate(x: jnp.ndarray, angles: jnp.ndarray) -> jnp.ndarray:
    """Half-split rotation of x (..., S, D) by ``angles`` (..., S, D/2)."""
    cos, sin = jnp.cos(angles), jnp.sin(angles)
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


def yarn_mscale(factor: float, mscale: float) -> float:
    """YaRN's attention factor: 0.1 · mscale · ln(factor) + 1 (1 without
    scaling)."""
    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


def yarn_inv_freq(d: int, theta: float, factor: float, beta_fast: float,
                  beta_slow: float, original_len: int) -> np.ndarray:
    """YaRN inverse frequencies of a D-wide rotary head, as DeepSeek-V3's
    published code computes them: the dims whose rotations at
    ``original_len`` fall between ``beta_fast`` and ``beta_slow`` ramp
    linearly from the original frequency to the interpolated one (÷
    ``factor``); faster dims keep the original, slower ones interpolate."""
    extra = 1.0 / theta ** (np.arange(0, d, 2, dtype=np.float32) / d)
    if factor <= 1:
        return extra

    def dim_of(rotations):
        return d * math.log(original_len / (rotations * 2 * math.pi)) / (2 * math.log(theta))

    low = max(math.floor(dim_of(beta_fast)), 0)
    high = min(math.ceil(dim_of(beta_slow)), d - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(d // 2, dtype=np.float32) - low) / (high - low), 0, 1)
    keep = 1.0 - ramp                  # 1: the original frequency
    return (extra / factor * (1 - keep) + extra * keep).astype(np.float32)


def mrope_positions(
    seq_len: int, vision_prefix: int, grid: Tuple[int, int], start: int = 0
) -> jnp.ndarray:
    """Qwen2-VL M-RoPE position ids: int32[3, S] = (temporal, height, width).

    The vision prefix occupies a (grid_h × grid_w) patch raster at temporal
    position 0..; text tokens resume with all three components equal
    (degenerating to 1-D RoPE), offset past the vision span — the Qwen2-VL
    scheme with dynamic resolution stubbed to a fixed grid.
    """
    gh, gw = grid
    vp = min(vision_prefix, seq_len)
    idx = jnp.arange(vp, dtype=jnp.int32)
    t_vis = jnp.zeros((vp,), jnp.int32)
    h_vis = idx // gw
    w_vis = idx % gw
    text_start = max(gh, gw)  # continue past the max spatial extent
    n_text = seq_len - vp
    t_txt = jnp.arange(n_text, dtype=jnp.int32) + text_start
    pos = jnp.stack([
        jnp.concatenate([t_vis, t_txt]),
        jnp.concatenate([h_vis, t_txt]),
        jnp.concatenate([w_vis, t_txt]),
    ])
    return pos + start


def apply_mrope(x: jnp.ndarray, pos3: jnp.ndarray, theta: float,
                sections=(16, 24, 24)) -> jnp.ndarray:
    """M-RoPE: frequency channels split into (t, h, w) sections (scaled to
    d_head/2 lanes).  x: (B, H, S, D); pos3: (3, S)."""
    d = x.shape[-1]
    half = d // 2
    # scale the published 1/4-1/4-1/2-ish section split to this head dim
    total = sum(sections)
    sec = [max(1, round(s * half / total)) for s in sections]
    sec[2] = half - sec[0] - sec[1]
    freqs = rope_freqs(d, theta)                       # (half,)
    # choose the position component per frequency channel
    comp = jnp.concatenate([
        jnp.full((sec[0],), 0, jnp.int32),
        jnp.full((sec[1],), 1, jnp.int32),
        jnp.full((sec[2],), 2, jnp.int32),
    ])
    pos_per_chan = pos3[comp, :]                       # (half, S)
    angles = pos_per_chan.T.astype(jnp.float32) * freqs  # (S, half)
    cos, sin = jnp.cos(angles), jnp.sin(angles)
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


# --------------------------------------------------------------------------
# SwiGLU MLP
# --------------------------------------------------------------------------

def init_mlp(key, d: int, f: int, dtype) -> Dict:
    k1, k2, k3 = jax.random.split(key, 3)
    return {
        "wi": dense_init(k1, (d, f), dtype),
        "wg": dense_init(k2, (d, f), dtype),
        "wo": dense_init(k3, (f, d), dtype, fan_in=f),
    }


def mlp(params: Dict, x: jnp.ndarray) -> jnp.ndarray:
    h = jnp.einsum("...d,df->...f", x, params["wi"])
    g = jnp.einsum("...d,df->...f", x, params["wg"])
    return jnp.einsum("...f,fd->...d", jax.nn.silu(g) * h, params["wo"])


# --------------------------------------------------------------------------
# Embedding / unembedding
# --------------------------------------------------------------------------

def init_embedding(key, vocab: int, d: int, dtype, tie: bool,
                   padded_vocab: Optional[int] = None) -> Dict:
    """Tables are allocated at `padded_vocab` (TP-divisible); pad logits are
    masked to -1e30 in `unembed`, so they never win argmax and contribute
    exp(-1e30)=0 to the CE logsumexp."""
    vp = padded_vocab or vocab
    k1, k2 = jax.random.split(key)
    p = {"table": embed_init(k1, (vp, d), dtype)}
    if not tie:
        p["head"] = dense_init(k2, (d, vp), dtype)
    return p


def embed(params: Dict, tokens: jnp.ndarray, compute_dtype) -> jnp.ndarray:
    return params["table"].astype(compute_dtype)[tokens]


def unembed(params: Dict, x: jnp.ndarray, logits_fp32: bool = True,
            vocab: Optional[int] = None) -> jnp.ndarray:
    if "head" in params:
        w = params["head"]
        out = jnp.einsum("...d,dv->...v", x, w.astype(x.dtype))
    else:
        w = params["table"]
        out = jnp.einsum("...d,vd->...v", x, w.astype(x.dtype))
    if vocab is not None and vocab != out.shape[-1]:
        mask = jnp.arange(out.shape[-1]) < vocab
        out = jnp.where(mask, out, jnp.asarray(-1e30, out.dtype))
    return out.astype(jnp.float32) if logits_fp32 else out


def cast_tree(tree, dtype, keep=()):
    """Floating leaves in ``dtype``, but those named in ``keep``."""
    if keep:
        return jax.tree_util.tree_map_with_path(
            lambda path, x: x if getattr(path[-1], "key", None) in keep
            else cast_tree(x, dtype), tree)
    return jax.tree.map(
        lambda x: x.astype(dtype) if jnp.issubdtype(x.dtype, jnp.floating) else x, tree
    )
