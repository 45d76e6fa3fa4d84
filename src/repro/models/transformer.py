"""Decoder-only LM assembly: scan-over-layers + remat, covering the dense,
moe, ssm (xLSTM), hybrid (Zamba2) and vlm families.

Layer heterogeneity is handled by scanning over *homogeneous groups*:
  dense/vlm : scan over identical (attn + SwiGLU) blocks
  moe       : unrolled leading dense layers + scan over MoE blocks
  ssm       : scan over (mLSTM, sLSTM) block pairs
  hybrid    : scan over groups of [shared-attn block + k Mamba2 blocks]
              (the shared block's params are loop-invariant — Zamba2's
              parameter reuse for free)

Caches for decode are stacked along the scan axis and threaded through
lax.scan as xs/ys, so decode HLO is as compact as train HLO.
"""
from __future__ import annotations

from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from ..configs.base import ModelConfig
from ..sharding.partition import constrain
from . import attention as attn_mod
from . import ssm as ssm_mod
from .common import (
    cast_tree,
    embed,
    init_embedding,
    init_mlp,
    init_rmsnorm,
    mlp,
    mrope_positions,
    rmsnorm,
    unembed,
)
from .moe import init_moe, moe_ffn, moe_held


# --------------------------------------------------------------------------
# blocks
# --------------------------------------------------------------------------

def init_dense_block(key, cfg: ModelConfig, dtype) -> Dict:
    k1, k2 = jax.random.split(key)
    return {
        "ln1": init_rmsnorm(cfg.d_model, dtype),
        "attn": attn_mod.init_attention(k1, cfg, dtype),
        "ln2": init_rmsnorm(cfg.d_model, dtype),
        "mlp": init_mlp(k2, cfg.d_model, cfg.d_ff, dtype),
    }


def _res_constrain(x, cfg: ModelConfig):
    """Residual-stream sharding: baseline = batch over DP; seq_parallel adds
    Megatron-SP (sequence dim sharded over the TP axis between blocks, which
    divides saved scan carries and their converts by the TP width)."""
    if cfg.seq_parallel:
        return constrain(x, ("pod", "data"), "model", None)
    return constrain(x, ("pod", "data"), None, None)


def dense_block(params, x, cfg: ModelConfig, positions=None, mrope_pos=None):
    x = _res_constrain(x, cfg)
    x = x + attn_mod.attention(params["attn"], rmsnorm(params["ln1"], x, cfg.norm_eps), cfg,
                               positions, mrope_pos)
    x = x + mlp(params["mlp"], rmsnorm(params["ln2"], x, cfg.norm_eps))
    return _res_constrain(x, cfg)


def dense_block_decode(params, x, cache, pos, cfg: ModelConfig, mrope_pos3=None):
    h, cache = attn_mod.decode(params["attn"], rmsnorm(params["ln1"], x, cfg.norm_eps), cache,
                               pos, cfg, mrope_pos3=mrope_pos3)
    x = x + h
    x = x + mlp(params["mlp"], rmsnorm(params["ln2"], x, cfg.norm_eps))
    return x, cache


def init_moe_block(key, cfg: ModelConfig, dtype) -> Dict:
    k1, k2 = jax.random.split(key)
    return {
        "ln1": init_rmsnorm(cfg.d_model, dtype),
        "attn": attn_mod.init_attention(k1, cfg, dtype),
        "ln2": init_rmsnorm(cfg.d_model, dtype),
        "moe": init_moe(k2, cfg, dtype),
    }


def _held(cfg: ModelConfig) -> bool:
    """DeepSeek's sigmoid router runs the held-share layer (``moe_held``);
    softmax routers the capacity baseline (``moe_ffn``)."""
    return cfg.family == "moe" and cfg.moe_score == "sigmoid"


def _keep_f32(cfg: ModelConfig):
    """Leaves the compute-dtype cast leaves as they are: the held layer's
    router and bias run in f32."""
    return ("router", "bias") if _held(cfg) else ()


def moe_block(params, x, cfg: ModelConfig, positions=None):
    x = _res_constrain(x, cfg)
    x = x + attn_mod.attention(params["attn"], rmsnorm(params["ln1"], x, cfg.norm_eps),
                               cfg, positions)
    h = rmsnorm(params["ln2"], x, cfg.norm_eps)
    if _held(cfg):
        y, _ = moe_held(params["moe"], h, cfg)
        aux = jnp.zeros((), jnp.float32)
    else:
        y, aux = moe_ffn(params["moe"], h, cfg)
    return _res_constrain(x + y, cfg), aux


def moe_block_decode(params, x, cache, pos, cfg: ModelConfig):
    """-> (x, cache, tokens routed to a held expert; 0 from ``moe_ffn``)."""
    h, cache = attn_mod.decode(params["attn"], rmsnorm(params["ln1"], x, cfg.norm_eps),
                               cache, pos, cfg)
    x = x + h
    h = rmsnorm(params["ln2"], x, cfg.norm_eps)
    if _held(cfg):
        y, n_held = moe_held(params["moe"], h, cfg)
    else:
        y, _ = moe_ffn(params["moe"], h, cfg, group_size=x.shape[0])
        n_held = jnp.zeros((), jnp.int32)
    return x + y, cache, n_held


def init_xlstm_pair(key, cfg: ModelConfig, dtype) -> Dict:
    k1, k2 = jax.random.split(key)
    return {
        "ln_m": init_rmsnorm(cfg.d_model, dtype),
        "mlstm": ssm_mod.init_mlstm(k1, cfg, dtype),
        "ln_s": init_rmsnorm(cfg.d_model, dtype),
        "slstm": ssm_mod.init_slstm(k2, cfg, dtype),
    }


def xlstm_pair(params, x, cfg: ModelConfig):
    x = _res_constrain(x, cfg)
    x = x + ssm_mod.mlstm_parallel(params["mlstm"], rmsnorm(params["ln_m"], x), cfg)
    x = x + ssm_mod.slstm_scan(params["slstm"], rmsnorm(params["ln_s"], x), cfg)
    return _res_constrain(x, cfg)


def xlstm_pair_decode(params, x, state, cfg: ModelConfig):
    h, sm = ssm_mod.mlstm_decode(params["mlstm"], rmsnorm(params["ln_m"], x), state["m"], cfg)
    x = x + h
    h, ss = ssm_mod.slstm_decode(params["slstm"], rmsnorm(params["ln_s"], x), state["s"], cfg)
    x = x + h
    return x, {"m": sm, "s": ss}


def init_mamba_block(key, cfg: ModelConfig, dtype) -> Dict:
    return {
        "ln": init_rmsnorm(cfg.d_model, dtype),
        "mamba": ssm_mod.init_mamba2(key, cfg, dtype),
    }


def mamba_block(params, x, cfg: ModelConfig):
    x = _res_constrain(x, cfg)
    return _res_constrain(
        x + ssm_mod.mamba2_ssd(params["mamba"], rmsnorm(params["ln"], x), cfg), cfg)


def mamba_block_decode(params, x, state, cfg: ModelConfig):
    h, state = ssm_mod.mamba2_decode(params["mamba"], rmsnorm(params["ln"], x), state, cfg)
    return x + h, state


# --------------------------------------------------------------------------
# LM assembly
# --------------------------------------------------------------------------

def _stack_init(key, n: int, init_fn):
    keys = jax.random.split(key, n)
    layers = [init_fn(k) for k in keys]
    return jax.tree.map(lambda *xs: jnp.stack(xs), *layers)


def init_lm(key, cfg: ModelConfig) -> Dict:
    dtype = cfg.pdtype()
    keys = jax.random.split(key, 8)
    params: Dict[str, Any] = {
        "embed": init_embedding(keys[0], cfg.vocab, cfg.d_model, dtype, cfg.tie_embeddings,
                                   padded_vocab=cfg.padded_vocab),
        "final_norm": init_rmsnorm(cfg.d_model, dtype),
    }
    fam = cfg.family
    if fam in ("dense", "vlm"):
        params["layers"] = _stack_init(
            keys[1], cfg.n_layers, lambda k: init_dense_block(k, cfg, dtype)
        )
    elif fam == "moe":
        if cfg.n_dense_layers:
            params["dense_layers"] = [
                init_dense_block(k, cfg, dtype)
                for k in jax.random.split(keys[1], cfg.n_dense_layers)
            ]
        params["layers"] = _stack_init(
            keys[2], cfg.n_layers - cfg.n_dense_layers,
            lambda k: init_moe_block(k, cfg, dtype),
        )
        if cfg.mtp:
            k1, k2 = jax.random.split(keys[3])
            params["mtp"] = {
                "norm_h": init_rmsnorm(cfg.d_model, dtype),
                "norm_e": init_rmsnorm(cfg.d_model, dtype),
                "proj": jax.random.normal(k1, (2 * cfg.d_model, cfg.d_model), dtype) * 0.02,
                "block": init_dense_block(k2, cfg, dtype),
            }
    elif fam == "ssm":
        assert cfg.n_layers % 2 == 0
        params["layers"] = _stack_init(
            keys[1], cfg.n_layers // 2, lambda k: init_xlstm_pair(k, cfg, dtype)
        )
    elif fam == "hybrid":
        k = cfg.attn_every
        assert cfg.n_layers % k == 0
        params["layers"] = _stack_init(
            keys[1], cfg.n_layers // k,
            lambda kk: _stack_init(kk, k, lambda k2: init_mamba_block(k2, cfg, dtype)),
        )
        params["shared_attn"] = init_dense_block(keys[2], cfg, dtype)
    else:
        raise ValueError(f"init_lm does not handle family {fam}")
    return params


def _maybe_remat(fn, cfg: ModelConfig):
    return jax.checkpoint(fn) if cfg.remat else fn


def _lm_trunk(
    params: Dict,
    tokens: jnp.ndarray,
    cfg: ModelConfig,
    vision_embeds: Optional[jnp.ndarray] = None,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """tokens: (B, S) -> (pre-final-norm hidden states (B, S, D), aux)."""
    cdt = cfg.cdtype()
    b, s = tokens.shape
    x = embed(params["embed"], tokens, cdt)
    x = constrain(x, ("pod", "data"), None, None)
    mrope_pos = None
    if cfg.family == "vlm":
        if vision_embeds is not None:
            vp = vision_embeds.shape[1]
            x = jnp.concatenate([vision_embeds.astype(cdt), x[:, vp:]], axis=1)
        mrope_pos = mrope_positions(s, cfg.vision_prefix, cfg.vision_grid)
    positions = jnp.arange(s)
    aux = jnp.zeros((), jnp.float32)
    cparams = cast_tree(params, cdt, keep=_keep_f32(cfg))
    fam = cfg.family

    if fam in ("dense", "vlm"):
        blk = _maybe_remat(
            lambda p, h: dense_block(p, h, cfg, positions, mrope_pos), cfg
        )
        if cfg.scan_layers:
            x, _ = jax.lax.scan(lambda h, p: (blk(p, h), None), x, cparams["layers"])
        else:
            n = jax.tree.leaves(cparams["layers"])[0].shape[0]
            for i in range(n):
                x = blk(jax.tree.map(lambda t: t[i], cparams["layers"]), x)
    elif fam == "moe":
        for p in cparams.get("dense_layers", []):
            x = _maybe_remat(lambda pp, h: dense_block(pp, h, cfg, positions), cfg)(p, x)
        def moe_step(carry, p):
            h, a = carry
            fn = _maybe_remat(lambda pp, hh: moe_block(pp, hh, cfg, positions), cfg)
            h, da = fn(p, h)
            return (h, a + da), None
        if cfg.scan_layers:
            (x, aux), _ = jax.lax.scan(moe_step, (x, aux), cparams["layers"])
        else:
            n = jax.tree.leaves(cparams["layers"])[0].shape[0]
            for i in range(n):
                (x, aux), _ = moe_step((x, aux), jax.tree.map(lambda t: t[i], cparams["layers"]))
    elif fam == "ssm":
        blk = _maybe_remat(lambda p, h: xlstm_pair(p, h, cfg), cfg)
        if cfg.scan_layers:
            x, _ = jax.lax.scan(lambda h, p: (blk(p, h), None), x, cparams["layers"])
        else:
            n = jax.tree.leaves(cparams["layers"])[0].shape[0]
            for i in range(n):
                x = blk(jax.tree.map(lambda t: t[i], cparams["layers"]), x)
    elif fam == "hybrid":
        shared = cparams["shared_attn"]
        def group(p, h):
            h = dense_block(shared, h, cfg, positions)        # shared attn block
            def inner(hh, pp):
                return mamba_block(pp, hh, cfg), None
            h, _ = jax.lax.scan(inner, h, p)
            return h
        blk = _maybe_remat(group, cfg)
        if cfg.scan_layers:
            x, _ = jax.lax.scan(lambda h, p: (blk(p, h), None), x, cparams["layers"])
        else:
            n = jax.tree.leaves(cparams["layers"])[0].shape[0]
            for i in range(n):
                x = blk(jax.tree.map(lambda t: t[i], cparams["layers"]), x)
    else:
        raise ValueError(fam)

    return x, aux


def lm_forward(
    params: Dict,
    tokens: jnp.ndarray,
    cfg: ModelConfig,
    vision_embeds: Optional[jnp.ndarray] = None,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """tokens: (B, S) -> (logits (B, S, V) f32, aux_loss scalar)."""
    h, aux = _lm_trunk(params, tokens, cfg, vision_embeds)
    cparams = cast_tree(params, cfg.cdtype())
    h = rmsnorm(cparams["final_norm"], h, cfg.norm_eps)
    logits = unembed(cparams["embed"], h, cfg.logits_fp32, vocab=cfg.vocab)
    return logits, aux


def lm_forward_mtp(params: Dict, tokens: jnp.ndarray, cfg: ModelConfig):
    """DeepSeek-V3 multi-token prediction: an extra depth-1 module predicts
    token t+2 from [h_t ; emb(token_{t+1})].  Returns (logits, mtp_logits, aux)."""
    cdt = cfg.cdtype()
    h_trunk, aux = _lm_trunk(params, tokens, cfg)
    cparams = cast_tree(params, cdt)
    hn = rmsnorm(cparams["final_norm"], h_trunk, cfg.norm_eps)
    logits = unembed(cparams["embed"], hn, cfg.logits_fp32, vocab=cfg.vocab)
    if not cfg.mtp:
        return logits, None, aux
    x = embed(cparams["embed"], tokens, cdt)
    nxt = jnp.concatenate([x[:, 1:], x[:, -1:]], axis=1)   # emb(token_{t+1})
    m = cparams["mtp"]
    comb = jnp.concatenate(
        [rmsnorm(m["norm_h"], h_trunk, cfg.norm_eps), rmsnorm(m["norm_e"], nxt, cfg.norm_eps)],
        axis=-1)
    h = jnp.einsum("bse,ed->bsd", comb, m["proj"])
    h = dense_block(m["block"], h, cfg, jnp.arange(tokens.shape[1]))
    mtp_logits = unembed(cparams["embed"], rmsnorm(cparams["final_norm"], h, cfg.norm_eps),
                         cfg.logits_fp32, vocab=cfg.vocab)
    return logits, mtp_logits, aux


# --------------------------------------------------------------------------
# decode
# --------------------------------------------------------------------------

# the held-share layer's decode state counts, on the device, the (token, MoE
# layer) pairs routed to at least one held expert since the caches were made
HELD_EXPERT_TOKENS = "held_expert_tokens"


def init_lm_caches(cfg: ModelConfig, batch: int, max_len: int):
    cdt = cfg.cdtype()
    fam = cfg.family

    def stack(n, make):
        one = make()
        return jax.tree.map(
            lambda t: (jnp.broadcast_to(t[None], (n, *t.shape)).copy()
                       if hasattr(t, "shape") else t), one)

    if fam in ("dense", "vlm"):
        return stack(cfg.n_layers, lambda: attn_mod.init_cache(cfg, batch, max_len, cdt))
    if fam == "moe":
        caches = {"scan": stack(cfg.n_layers - cfg.n_dense_layers,
                                lambda: attn_mod.init_cache(cfg, batch, max_len, cdt))}
        if cfg.n_dense_layers:
            caches["dense"] = [attn_mod.init_cache(cfg, batch, max_len, cdt)
                               for _ in range(cfg.n_dense_layers)]
        if _held(cfg):
            caches[HELD_EXPERT_TOKENS] = jnp.zeros((), jnp.int32)
        return caches
    if fam == "ssm":
        return stack(cfg.n_layers // 2, lambda: {
            "m": ssm_mod.init_mlstm_state(cfg, batch),
            "s": ssm_mod.init_slstm_state(cfg, batch),
        })
    if fam == "hybrid":
        ng = cfg.n_layers // cfg.attn_every
        return {
            "mamba": stack(ng, lambda: stack(cfg.attn_every,
                                             lambda: ssm_mod.init_mamba2_state(cfg, batch))),
            "attn": stack(ng, lambda: attn_mod.init_cache(cfg, batch, max_len, cdt)),
        }
    raise ValueError(fam)


def lm_decode_step(params: Dict, tokens: jnp.ndarray, caches, pos: jnp.ndarray,
                   cfg: ModelConfig):
    """tokens: (B, 1) new token ids; pos: scalar index. -> (logits, caches)."""
    cdt = cfg.cdtype()
    cparams = cast_tree(params, cdt, keep=_keep_f32(cfg))
    x = embed(cparams["embed"], tokens, cdt)
    fam = cfg.family
    mrope_pos3 = None
    if fam == "vlm":
        # M-RoPE for one position: vision prefix raster (t=0, h, w); text
        # tokens have all three components equal, offset past the grid span.
        gh, gw = cfg.vision_grid
        vp, m = cfg.vision_prefix, max(cfg.vision_grid)
        is_vis = pos < vp
        tt = jnp.where(is_vis, 0, pos - vp + m)
        hh = jnp.where(is_vis, pos // gw, pos - vp + m)
        ww = jnp.where(is_vis, pos % gw, pos - vp + m)
        mrope_pos3 = jnp.stack([tt, hh, ww])[:, None]      # (3, 1)

    if fam in ("dense", "vlm"):
        def step(h, pc):
            p, c = pc
            h, c = dense_block_decode(p, h, c, pos, cfg, mrope_pos3)
            return h, c
        x, caches = jax.lax.scan(step, x, (cparams["layers"], caches))
    elif fam == "moe":
        new_dense = []
        for p, c in zip(cparams.get("dense_layers", []), caches.get("dense", [])):
            x, c = dense_block_decode(p, x, c, pos, cfg)
            new_dense.append(c)
        def step(carry, pc):
            p, c = pc
            h, c, n = moe_block_decode(p, carry[0], c, pos, cfg)
            return (h, carry[1] + n), c
        (x, n_held), scan_caches = jax.lax.scan(
            step, (x, jnp.zeros((), jnp.int32)), (cparams["layers"], caches["scan"]))
        held_total = caches.get(HELD_EXPERT_TOKENS)
        caches = {"scan": scan_caches}
        if new_dense:
            caches["dense"] = new_dense
        if held_total is not None:
            caches[HELD_EXPERT_TOKENS] = held_total + n_held
    elif fam == "ssm":
        def step(h, pc):
            p, c = pc
            h, c = xlstm_pair_decode(p, h, c, cfg)
            return h, c
        x, caches = jax.lax.scan(step, x, (cparams["layers"], caches))
    elif fam == "hybrid":
        shared = cparams["shared_attn"]
        def group(h, pc):
            p, cm, ca = pc
            h, ca = dense_block_decode(shared, h, ca, pos, cfg)
            def inner(hh, pcc):
                pp, cc = pcc
                hh, cc = mamba_block_decode(pp, hh, cc, cfg)
                return hh, cc
            h, cm = jax.lax.scan(inner, h, (p, cm))
            return h, (cm, ca)
        x, (cm, ca) = jax.lax.scan(group, x, (cparams["layers"], caches["mamba"], caches["attn"]))
        caches = {"mamba": cm, "attn": ca}
    else:
        raise ValueError(fam)

    x = rmsnorm(cparams["final_norm"], x, cfg.norm_eps)
    logits = unembed(cparams["embed"], x, cfg.logits_fp32, vocab=cfg.vocab)
    return logits, caches
