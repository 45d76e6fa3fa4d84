"""Attention variants: GQA (with optional QKV bias) and DeepSeek-V3 MLA.

Full-sequence paths (train/prefill) route through the flash-attention op
(Pallas on TPU, jnp oracle on CPU); decode paths use einsum attention over
the KV cache (one query — no flash needed).

KV caches:
  GQA : k,v (B, Hkv, S, Dh) — standard cache.
  MLA : latent cache (B, S, kv_lora + qk_rope_head_dim) — the MLA memory
        saving is structural: we cache the compressed latent + rope key only.
"""
from __future__ import annotations

from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from ..configs.base import ModelConfig
from ..kernels.flash_attention.ops import flash_attention
from ..sharding.partition import cache_constrain, constrain
from ..spans import MLA
from .common import (apply_mrope, apply_rope, dense_init, init_rmsnorm, rmsnorm, rotate,
                     yarn_inv_freq, yarn_mscale)


# ==========================================================================
# GQA
# ==========================================================================

def init_gqa(key, cfg: ModelConfig, dtype) -> Dict:
    d, h, hk, dh = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    ks = jax.random.split(key, 4)
    p = {
        "wq": dense_init(ks[0], (d, h * dh), dtype),
        "wk": dense_init(ks[1], (d, hk * dh), dtype),
        "wv": dense_init(ks[2], (d, hk * dh), dtype),
        "wo": dense_init(ks[3], (h * dh, d), dtype, fan_in=h * dh),
    }
    if cfg.qkv_bias:
        p["bq"] = jnp.zeros((h * dh,), dtype)
        p["bk"] = jnp.zeros((hk * dh,), dtype)
        p["bv"] = jnp.zeros((hk * dh,), dtype)
    return p


def _proj_qkv(params, x, cfg: ModelConfig):
    b, s, _ = x.shape
    h, hk, dh = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    q = jnp.einsum("bsd,de->bse", x, params["wq"])
    k = jnp.einsum("bsd,de->bse", x, params["wk"])
    v = jnp.einsum("bsd,de->bse", x, params["wv"])
    if cfg.qkv_bias:
        q = q + params["bq"].astype(q.dtype)
        k = k + params["bk"].astype(k.dtype)
        v = v + params["bv"].astype(v.dtype)
    q = q.reshape(b, s, h, dh).transpose(0, 2, 1, 3)
    k = k.reshape(b, s, hk, dh).transpose(0, 2, 1, 3)
    v = v.reshape(b, s, hk, dh).transpose(0, 2, 1, 3)
    return q, k, v


def gqa_attention(
    params: Dict,
    x: jnp.ndarray,
    cfg: ModelConfig,
    positions: Optional[jnp.ndarray] = None,
    mrope_pos: Optional[jnp.ndarray] = None,
    causal: bool = True,
) -> jnp.ndarray:
    """Full-sequence GQA. x: (B, S, D)."""
    b, s, _ = x.shape
    q, k, v = _proj_qkv(params, x, cfg)
    if positions is None:
        positions = jnp.arange(s)
    if cfg.mrope and mrope_pos is not None:
        q = apply_mrope(q, mrope_pos, cfg.rope_theta)
        k = apply_mrope(k, mrope_pos, cfg.rope_theta)
    else:
        q = apply_rope(q, positions, cfg.rope_theta)
        k = apply_rope(k, positions, cfg.rope_theta)
    if cfg.attn_batch_shard:
        # §Perf: when n_heads % TP != 0, head-sharded attention forces
        # per-KV-block partial-sum all-reduces; shard the attention region
        # over batch instead (heads replicated, one gather at the boundary)
        q = constrain(q, ("pod", "data"), None, None, None)
        k = constrain(k, ("pod", "data"), None, None, None)
        v = constrain(v, ("pod", "data"), None, None, None)
    o = flash_attention(q, k, v, causal=causal)
    o = o.transpose(0, 2, 1, 3).reshape(b, s, cfg.n_heads * cfg.head_dim)
    return jnp.einsum("bse,ed->bsd", o, params["wo"])


def init_gqa_cache(cfg: ModelConfig, batch: int, max_len: int, dtype):
    hk, dh = cfg.n_kv_heads, cfg.head_dim
    return {
        "k": jnp.zeros((batch, hk, max_len, dh), dtype),
        "v": jnp.zeros((batch, hk, max_len, dh), dtype),
    }


def gqa_decode(
    params: Dict,
    x: jnp.ndarray,                 # (B, 1, D)
    cache: Dict,
    pos: jnp.ndarray,               # scalar int32: index of the new token
    cfg: ModelConfig,
    mrope_pos3: Optional[jnp.ndarray] = None,   # (3, 1) M-RoPE components
) -> Tuple[jnp.ndarray, Dict]:
    b = x.shape[0]
    h, hk, dh = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    group = h // hk
    q, k, v = _proj_qkv(params, x, cfg)       # (B, h, 1, dh), (B, hk, 1, dh)
    if cfg.mrope and mrope_pos3 is not None:
        q = apply_mrope(q, mrope_pos3, cfg.rope_theta)
        k = apply_mrope(k, mrope_pos3, cfg.rope_theta)
    else:
        q = apply_rope(q, pos[None], cfg.rope_theta)
        k = apply_rope(k, pos[None], cfg.rope_theta)
    ck = cache_constrain(jax.lax.dynamic_update_slice(
        cache["k"], k.astype(cache["k"].dtype), (0, 0, pos, 0)))
    cv = cache_constrain(jax.lax.dynamic_update_slice(
        cache["v"], v.astype(cache["v"].dtype), (0, 0, pos, 0)))
    qg = q.reshape(b, hk, group, dh)
    # f32 accumulation via preferred_element_type: casting the cache with
    # astype would materialize an f32 copy of the whole stacked cache (XLA
    # hoists the convert out of the layer loop)
    s = jnp.einsum("bkgd,bksd->bkgs", qg, ck,
                   preferred_element_type=jnp.float32)
    s = s * (dh ** -0.5)
    valid = jnp.arange(ck.shape[2])[None, None, None, :] <= pos
    s = jnp.where(valid, s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bkgs,bksd->bkgd", p.astype(cv.dtype), cv,
                   preferred_element_type=jnp.float32)
    o = o.reshape(b, 1, h * dh).astype(x.dtype)
    return jnp.einsum("bse,ed->bsd", o, params["wo"]), {"k": ck, "v": cv}


# ==========================================================================
# MLA (DeepSeek-V3)
# ==========================================================================

def init_mla(key, cfg: ModelConfig, dtype) -> Dict:
    d = cfg.d_model
    h = cfg.n_heads
    qr, kr = cfg.q_lora_rank, cfg.kv_lora_rank
    dqn, dqr, dv = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim
    ks = jax.random.split(key, 6)
    p = {
        "wq_a": dense_init(ks[0], (d, qr), dtype),                      # down
        "wq_b": dense_init(ks[1], (qr, h * (dqn + dqr)), dtype, fan_in=qr),
        "wkv_a": dense_init(ks[2], (d, kr + dqr), dtype),               # latent + rope-k
        "wkv_b": dense_init(ks[3], (kr, h * (dqn + dv)), dtype, fan_in=kr),
        "wo": dense_init(ks[4], (h * dv, d), dtype, fan_in=h * dv),
    }
    p["q_norm"] = init_rmsnorm(qr, dtype)
    p["kv_norm"] = init_rmsnorm(kr, dtype)
    return p


def mla_rope(x: jnp.ndarray, positions: jnp.ndarray, cfg: ModelConfig) -> jnp.ndarray:
    """Rotary embedding of MLA's rope dims x (..., S, dqr), on interleaved
    pairs (2i, 2i + 1) as the published code turns them, with YaRN
    frequencies when the config scales them.  The pairs are first gathered
    into halves, as HF's modelling code does, so q and k come out in the
    same (half-split) order."""
    d = x.shape[-1]
    x = x.reshape(*x.shape[:-1], d // 2, 2).swapaxes(-1, -2).reshape(x.shape)
    inv = yarn_inv_freq(d, cfg.rope_theta, cfg.yarn_factor, cfg.yarn_beta_fast,
                        cfg.yarn_beta_slow, cfg.yarn_original_len)
    return rotate(x, positions[..., None].astype(jnp.float32) * jnp.asarray(inv))


def mla_softmax_scale(cfg: ModelConfig) -> float:
    """(nope + rope)^-0.5 × YaRN's mscale² (1 where unscaled)."""
    m = yarn_mscale(cfg.yarn_factor, cfg.yarn_mscale_all_dim)
    return (cfg.qk_nope_head_dim + cfg.qk_rope_head_dim) ** -0.5 * m * m


def _mla_q(params, x, positions, cfg: ModelConfig):
    """Queries (B, h, S, ·): the nope part and the rotated rope part."""
    b, s, _ = x.shape
    dqn, dqr = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim
    q = rmsnorm(params["q_norm"], jnp.einsum("bsd,dr->bsr", x, params["wq_a"]), cfg.norm_eps)
    q = jnp.einsum("bsr,re->bse", q, params["wq_b"]).reshape(b, s, cfg.n_heads, dqn + dqr)
    q = q.transpose(0, 2, 1, 3)
    return q[..., :dqn], mla_rope(q[..., dqn:], positions, cfg)


def _mla_entry(params, x, positions, cfg: ModelConfig):
    """What the latent cache holds per token (B, S, kv_lora + rope): the
    normed kv latent and the rotated rope key."""
    kr = cfg.kv_lora_rank
    kv = jnp.einsum("bsd,dr->bsr", x, params["wkv_a"])
    latent = rmsnorm(params["kv_norm"], kv[..., :kr], cfg.norm_eps)
    return jnp.concatenate([latent, mla_rope(kv[..., kr:], positions, cfg)], axis=-1)


def _mla_qkv(params, x, positions, cfg: ModelConfig):
    b, s, _ = x.shape
    h = cfg.n_heads
    dqn, dqr, dv = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim
    kr = cfg.kv_lora_rank

    q_nope, q_rope = _mla_q(params, x, positions, cfg)
    entry = _mla_entry(params, x, positions, cfg)
    latent, k_rope = entry[..., :kr], entry[:, None, :, kr:]          # (B,1,S,dqr)

    kvu = jnp.einsum("bsr,re->bse", latent, params["wkv_b"]).reshape(b, s, h, dqn + dv)
    kvu = kvu.transpose(0, 2, 1, 3)
    k_nope, v = kvu[..., :dqn], kvu[..., dqn:]
    k = jnp.concatenate([k_nope, jnp.broadcast_to(k_rope, (b, h, s, dqr))], axis=-1)
    qq = jnp.concatenate([q_nope, q_rope], axis=-1)
    return qq, k, v


def mla_attention(params: Dict, x: jnp.ndarray, cfg: ModelConfig,
                  positions: Optional[jnp.ndarray] = None,
                  causal: bool = True) -> jnp.ndarray:
    b, s, _ = x.shape
    if positions is None:
        positions = jnp.arange(s)
    with jax.named_scope(MLA):
        q, k, v = _mla_qkv(params, x, positions, cfg)
        o = flash_attention(q, k, v, causal=causal, scale=mla_softmax_scale(cfg))
        o = o.transpose(0, 2, 1, 3).reshape(b, s, cfg.n_heads * cfg.v_head_dim)
        return jnp.einsum("bse,ed->bsd", o, params["wo"])


def init_mla_cache(cfg: ModelConfig, batch: int, max_len: int, dtype):
    return {"latent": jnp.zeros((batch, max_len, cfg.kv_lora_rank + cfg.qk_rope_head_dim), dtype)}


def _mla_step(params: Dict, x: jnp.ndarray, cache: Dict, pos: jnp.ndarray,
              cfg: ModelConfig):
    """One token's queries, and the latent cache with its entry written at
    ``pos``."""
    q_nope, q_rope = _mla_q(params, x, pos[None], cfg)          # (B,h,1,·)
    entry = _mla_entry(params, x, pos[None], cfg)                # (B,1,kr+dqr)
    lat = cache_constrain(jax.lax.dynamic_update_slice(
        cache["latent"], entry.astype(cache["latent"].dtype), (0, pos, 0)
    ), seq_shard=cfg.flash_decoding)
    return q_nope, q_rope, lat


def mla_decode_absorbed(params: Dict, x: jnp.ndarray, cache: Dict, pos: jnp.ndarray,
                        cfg: ModelConfig) -> Tuple[jnp.ndarray, Dict]:
    """§Perf: weight-absorbed MLA decode (DeepSeek-V2 trick, beyond-paper
    here).  Never re-expands K/V: wkv_b's key half is absorbed into the
    query (q_eff = q_nope · w_kᵀ, rank-kr) and attention runs directly over
    the cached latent; the value half is applied after the softmax.  Per-step
    traffic drops from O(S·h·(dqn+dv)) re-expansion to O(S·kr)."""
    b = x.shape[0]
    h = cfg.n_heads
    dqn, dv = cfg.qk_nope_head_dim, cfg.v_head_dim
    kr = cfg.kv_lora_rank

    q_nope, q_rope, lat = _mla_step(params, x, cache, pos, cfg)
    latent_all, k_rope_all = lat[..., :kr], lat[..., kr:]

    wkv_b = params["wkv_b"].reshape(kr, h, dqn + dv)
    w_k, w_v = wkv_b[..., :dqn], wkv_b[..., dqn:]              # (kr,h,dqn/dv)
    q_eff = jnp.einsum("bhd,rhd->bhr", q_nope[:, :, 0], w_k,
                       preferred_element_type=jnp.float32)       # (B,h,kr)
    s_nope = jnp.einsum("bhr,bsr->bhs", q_eff.astype(latent_all.dtype),
                        latent_all, preferred_element_type=jnp.float32)
    s_rope = jnp.einsum("bhqd,bsd->bhs", q_rope, k_rope_all,
                        preferred_element_type=jnp.float32)
    s = (s_nope + s_rope) * mla_softmax_scale(cfg)
    valid = jnp.arange(lat.shape[1])[None, None, :] <= pos
    s = jnp.where(valid, s, -1e30)
    p = jax.nn.softmax(s, axis=-1)                              # (B,h,S)
    ctx = jnp.einsum("bhs,bsr->bhr", p.astype(latent_all.dtype), latent_all,
                     preferred_element_type=jnp.float32)
    o = jnp.einsum("bhr,rhd->bhd", ctx.astype(w_v.dtype), w_v,
                   preferred_element_type=jnp.float32)
    o = o.reshape(b, 1, h * dv).astype(x.dtype)
    return jnp.einsum("bse,ed->bsd", o, params["wo"]), {"latent": lat}


def mla_decode_expanded(params: Dict, x: jnp.ndarray, cache: Dict, pos: jnp.ndarray,
                        cfg: ModelConfig) -> Tuple[jnp.ndarray, Dict]:
    """Latent-cache decode: re-expands K/V from the cached latent (B,S,kr)."""
    b = x.shape[0]
    h = cfg.n_heads
    dqn, dv = cfg.qk_nope_head_dim, cfg.v_head_dim
    kr = cfg.kv_lora_rank

    q_nope, q_rope, lat = _mla_step(params, x, cache, pos, cfg)
    latent_all, k_rope_all = lat[..., :kr], lat[..., kr:]

    kvu = jnp.einsum("bsr,re->bse", latent_all, params["wkv_b"].astype(cache["latent"].dtype))
    kvu = kvu.reshape(b, -1, h, dqn + dv).transpose(0, 2, 1, 3)   # (B,h,S,dqn+dv)
    k_nope, v = kvu[..., :dqn], kvu[..., dqn:]

    s_nope = jnp.einsum("bhqd,bhsd->bhqs", q_nope, k_nope,
                        preferred_element_type=jnp.float32)
    s_rope = jnp.einsum("bhqd,bsd->bhqs", q_rope, k_rope_all,
                        preferred_element_type=jnp.float32)
    s = (s_nope + s_rope) * mla_softmax_scale(cfg)
    valid = jnp.arange(lat.shape[1])[None, None, None, :] <= pos
    s = jnp.where(valid, s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bhqs,bhsd->bhqd", p.astype(v.dtype), v,
                   preferred_element_type=jnp.float32)
    o = o.transpose(0, 2, 1, 3).reshape(b, 1, h * dv).astype(x.dtype)
    return jnp.einsum("bse,ed->bsd", o, params["wo"]), {"latent": lat}


def mla_decode(params: Dict, x: jnp.ndarray, cache: Dict, pos: jnp.ndarray,
               cfg: ModelConfig) -> Tuple[jnp.ndarray, Dict]:
    """One token through the latent cache: weight-absorbed (``mla_absorb``)
    or with K/V re-expanded."""
    with jax.named_scope(MLA):
        step = mla_decode_absorbed if cfg.mla_absorb else mla_decode_expanded
        return step(params, x, cache, pos, cfg)


# ==========================================================================
# dispatch
# ==========================================================================

def init_attention(key, cfg: ModelConfig, dtype) -> Dict:
    return init_mla(key, cfg, dtype) if cfg.attn_kind == "mla" else init_gqa(key, cfg, dtype)


def attention(params, x, cfg: ModelConfig, positions=None, mrope_pos=None, causal=True):
    if cfg.attn_kind == "mla":
        return mla_attention(params, x, cfg, positions, causal=causal)
    return gqa_attention(params, x, cfg, positions, mrope_pos, causal=causal)


def init_cache(cfg: ModelConfig, batch: int, max_len: int, dtype):
    if cfg.attn_kind == "mla":
        return init_mla_cache(cfg, batch, max_len, dtype)
    return init_gqa_cache(cfg, batch, max_len, dtype)


def decode(params, x, cache, pos, cfg: ModelConfig, mrope_pos3=None):
    if cfg.attn_kind == "mla":
        return mla_decode(params, x, cache, pos, cfg)
    return gqa_decode(params, x, cache, pos, cfg, mrope_pos3=mrope_pos3)
